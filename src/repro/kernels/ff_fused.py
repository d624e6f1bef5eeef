"""Pallas executors for fused FF expression pipelines + hand-fused
composite kernels (softmax / logsumexp / layer-norm stats).

Generic executor (:func:`run_pallas`): takes a traced
``repro.ff.fusion.Program`` and runs the WHOLE chain as one ``pallas_call``
— each input's hi/lo planes stream HBM -> VMEM once, every intermediate
stays in VMEM/vector registers via the branch-free ``repro.kernels.eft``
primitives, outputs are written once.  An optional trailing row reduction
per output accumulates a lane-parallel Neumaier cascade in VMEM scratch
across column blocks (same scheme as ``ff_reduce.ff_rowsum``) and folds it
exactly on the last column step.

Hand-fused composites: softmax and logsumexp need a row *max* BEFORE the
elementwise chain, which the trailing-reduction expression model cannot
express — so they get a dedicated kernel that holds the whole row in VMEM
(rows up to :data:`MAX_FUSED_COLS`; dispatch falls back to the jnp impl
beyond that).  ``norm_stats`` fuses BOTH LayerNorm reductions (mean and
centered variance — two passes over the row) into one kernel: x is read
from HBM once instead of three times (mean pass, center pass, square-sum
pass).

Numerics: elementwise chain results are bitwise-identical to op-by-op
dispatch (same EFT sequences).  Reduction results may differ from the
jnp references by the final-rounding ulp: both sides compute the sum to
~2^-40 relative before rounding to the f32-pair, so the represented values
agree far below f32 ulp but the two summation ORDERS (lane cascade here,
``ff_sum_blocked``'s scan there) can round the last bit differently.
Tests pin this to <= 1 ulp; ``docs/DESIGN_fusion.md`` has the argument.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ffmath
from repro.core.ff import FF
from repro.kernels import eft
from repro.kernels.ff_elementwise import (
    LANE, SUBLANE, _pad_to, _round_up, _spec_for, _to_2d, broadcast_planes,
)

# FF transcendentals usable inside fused chains (tracer ops -> the generic
# repro.core.ffmath bodies, instantiated with THIS module's barrier-free
# EFTs — the same arithmetic the jnp executor replays with the barrier-
# carrying core primitives, so the two stay bitwise-aligned)
_DEEP_OPS = ("exp22", "log22", "tanh22", "sigmoid22")

Array = jnp.ndarray

VMEM_BUDGET_BYTES = 4 * 1024 * 1024   # working-set target per block
MAX_FUSED_COLS = 16384                # whole-row kernels beyond this -> jnp


def _pick_block(planes: int, R: int, C: int,
                block: Optional[Tuple[int, int]] = None) -> Tuple[int, int]:
    """Tile for a ``planes``-deep chain: shrink rows (then cols) until
    ``planes * br * bc * 4B`` fits the VMEM budget.  Deeper chains get
    smaller tiles; the grid grows, the HBM traffic does not."""
    if block is not None:
        br, bc = block
        return (min(_round_up(br, SUBLANE), _round_up(max(R, 1), SUBLANE)),
                min(_round_up(bc, LANE), _round_up(max(C, 1), LANE)))
    budget_elems = VMEM_BUDGET_BYTES // (4 * max(planes, 1))
    bc = min(512, _round_up(max(C, 1), LANE))
    br = min(256, _round_up(max(R, 1), SUBLANE))
    while br * bc > budget_elems and br > SUBLANE:
        br = max(SUBLANE, _round_up(br // 2, SUBLANE))   # stay 8-aligned
    while br * bc > budget_elems and bc > LANE:
        bc = max(LANE, LANE * ((bc // 2) // LANE))
    return br, bc


def _eval_instrs(prog, leaf_blocks):
    """Evaluate the non-reduction instructions on loaded blocks.  FF values
    are (hi, lo) tuples; f32 values are arrays.  Returns the env list
    (rowsum instrs left as None — handled by the caller)."""
    env: List = []
    for ins in prog.instrs:
        op, args = ins.op, ins.args
        if op in ("leaf_ff", "leaf_f32"):
            v = leaf_blocks[int(ins.imm)]
        elif op == "const":
            v = jnp.float32(ins.imm)
        elif op == "fadd":
            v = env[args[0]] + env[args[1]]
        elif op == "fsub":
            v = env[args[0]] - env[args[1]]
        elif op == "fmul":
            v = env[args[0]] * env[args[1]]
        elif op == "fdiv":
            v = env[args[0]] / env[args[1]]
        elif op == "fneg":
            v = -env[args[0]]
        elif op == "fsqrt":
            v = jnp.sqrt(env[args[0]])
        elif op == "fexp":
            v = jnp.exp(env[args[0]])
        elif op == "flog":
            v = jnp.log(env[args[0]])
        elif op == "add22":
            v = eft.add22(*env[args[0]], *env[args[1]])
        elif op == "add212":
            v = eft.add212(*env[args[0]], env[args[1]])
        elif op == "mul22":
            v = eft.mul22(*env[args[0]], *env[args[1]])
        elif op == "mul212":
            v = eft.mul212(*env[args[0]], env[args[1]])
        elif op == "div22":
            v = eft.div22(*env[args[0]], *env[args[1]])
        elif op == "sqrt22":
            v = eft.sqrt22(*env[args[0]])
        elif op == "fma22":
            v = eft.fma22(*env[args[0]], *env[args[1]], *env[args[2]])
        elif op == "neg22":
            h, l = env[args[0]]
            v = (-h, -l)
        elif op in _DEEP_OPS:
            h, l = env[args[0]]
            v = getattr(ffmath, op)(h, l, eft)
        elif op == "lift":
            x = env[args[0]]
            v = (x, jnp.zeros_like(x))
        elif op == "hi":
            v = env[args[0]][0]
        elif op == "lo":
            v = env[args[0]][1]
        elif op == "pack":
            v = (env[args[0]], env[args[1]])
        elif op == "rowsum":
            v = None
        else:                                          # pragma: no cover
            raise NotImplementedError(op)
        env.append(v)
    return env


def _lane_cascade(val: Array, s, c, cc, lane: int):
    """One block's contribution to a lane-parallel Neumaier cascade:
    fold (br, bc) into three (br, lane) accumulators.  Static lane-aligned
    slices (unrolled): Mosaic has no lowering for a value-level
    ``dynamic_slice``."""
    for t in range(val.shape[1] // lane):
        s, e = eft.two_sum(s, val[:, t * lane:(t + 1) * lane])
        c, e2 = eft.two_sum(c, e)
        cc = cc + e2
    return s, c, cc


def _fold_lanes(s_acc, c_acc, cc_acc) -> Tuple[Array, Array]:
    """Exact sequential fold of the ``lane`` per-lane accumulators (same
    scheme as ``ff_reduce``): (br, lane) x3 -> FF per row, (br, 1).
    Runs on the transposed (lane, br) planes so each step reads one
    sublane row instead of one lane column of every row tile."""
    sT, cT, ccT = s_acc.T, c_acc.T, cc_acc.T
    fh = fl = jnp.zeros((1, sT.shape[1]), jnp.float32)
    for i in range(sT.shape[0]):
        sh, sl = eft.two_sum(fh, sT[i:i + 1])
        v = sl + (fl + cT[i:i + 1] + ccT[i:i + 1])
        fh, fl = eft.fast_two_sum(sh, v)
    return fh.T, fl.T


def _unbroadcast(arr: Array, full_shape, nd) -> Array:
    """Recover a value of true ND shape ``nd`` from its full-broadcast
    compute plane: along every dim the value broadcasts over, all slices
    are identical copies — take index 0."""
    if tuple(nd) == tuple(full_shape):
        return arr
    pad = len(full_shape) - len(nd)
    idx = tuple(
        slice(0, 1) if (1 if d < pad else nd[d - pad]) == 1 and size != 1
        else slice(None)
        for d, size in enumerate(full_shape))
    return arr[idx].reshape(nd)


def run_pallas(prog, operands: Sequence, *,
               block: Optional[Tuple[int, int]] = None,
               interpret: bool = False):
    """Execute a fused Program as ONE pallas_call (see module docstring)."""
    from repro.ff import fusion

    # -- flatten leaves to broadcastable 2-D planes --------------------------
    raw: List[Array] = []            # one entry per plane
    leaf_plane_ix: List[Tuple[int, ...]] = []  # per leaf: plane indices
    for kind, x in zip(prog.leaf_kinds, operands):
        if kind == "ff":
            leaf_plane_ix.append((len(raw), len(raw) + 1))
            raw.extend([jnp.asarray(x.hi, jnp.float32),
                        jnp.asarray(x.lo, jnp.float32)])
        else:
            leaf_plane_ix.append((len(raw),))
            raw.append(jnp.asarray(x, jnp.float32))
    # per-value ND shapes: outputs must come back with the SAME shapes the
    # jnp executor produces (an output may depend on a subset of operands
    # and be narrower than the full broadcast of all of them)
    nd_shapes = fusion.infer_shapes(
        prog, [jnp.shape(x.hi if hasattr(x, "hi") else x)
               for x in operands])
    planes, out_shape = broadcast_planes(raw)
    if len(out_shape) == 0:
        R, C = 1, 1
    else:
        R = 1
        for d in out_shape[:-1]:
            R *= d
        C = out_shape[-1]

    # plane_count already counts leaf and output instructions once each
    n_planes = prog.plane_count()
    br, bc = _pick_block(n_planes, R, C, block)
    Rp, Cp = _round_up(R, br), _round_up(C, bc)
    nr, nc = Rp // br, Cp // bc
    padded = [_pad_to(p, br if p.shape[0] != 1 else 1,
                      bc if p.shape[1] != 1 else 1) for p in planes]
    in_specs = [_spec_for(p.shape, (Rp, Cp), br, bc) for p in padded]

    # -- outputs + reduction scratch -----------------------------------------
    ew_spec = pl.BlockSpec((br, bc), lambda i, j: (i, j))
    red_spec = pl.BlockSpec((br, 1), lambda i, j: (i, 0))
    full = jax.ShapeDtypeStruct((Rp, Cp), jnp.float32)
    rcol = jax.ShapeDtypeStruct((Rp, 1), jnp.float32)
    out_shapes: List = []
    out_specs: List = []
    out_kinds: List[str] = []        # "ff" | "f32" | "red" per out id
    red_width: dict = {}             # out id -> the reduced VALUE's width
    n_red = 0
    for oid in prog.out_ids:
        ins = prog.instrs[oid]
        if ins.op == "rowsum":
            out_kinds.append("red")
            out_shapes += [rcol, rcol]
            out_specs += [red_spec, red_spec]
            vshape = nd_shapes[ins.args[0]]
            red_width[oid] = vshape[-1] if vshape else 1
            n_red += 1
        elif ins.dtype == "ff":
            out_kinds.append("ff")
            out_shapes += [full, full]
            out_specs += [ew_spec, ew_spec]
        else:
            out_kinds.append("f32")
            out_shapes.append(full)
            out_specs.append(ew_spec)
    scratch = [pltpu.VMEM((br, LANE), jnp.float32)
               for _ in range(3 * n_red)]

    n_in = len(padded)
    n_out_refs = len(out_shapes)

    def kernel(*refs):
        in_refs = refs[:n_in]
        out_refs = refs[n_in:n_in + n_out_refs]
        sc = refs[n_in + n_out_refs:]
        j = pl.program_id(1)

        if n_red:
            @pl.when(j == 0)
            def _init():
                for s in sc:
                    s[...] = jnp.zeros_like(s)

        leaf_blocks = []
        for kind, ix in zip(prog.leaf_kinds, leaf_plane_ix):
            if kind == "ff":
                leaf_blocks.append((in_refs[ix[0]][...], in_refs[ix[1]][...]))
            else:
                leaf_blocks.append(in_refs[ix[0]][...])
        env = _eval_instrs(prog, leaf_blocks)

        # a value built only from broadcast leaves keeps a degenerate
        # (1, bc)/(br, 1)/(1, 1) block shape — expand at the write/reduce
        bcast = lambda v: jnp.broadcast_to(v, (br, bc))

        oref = 0
        red = 0
        for oid, okind in zip(prog.out_ids, out_kinds):
            if okind == "red":
                val = bcast(env[prog.instrs[oid].args[0]])
                # mask padded columns — and broadcast copies beyond the
                # VALUE's own width: the chain may be nonzero on a zero
                # pad (x + 1), and a column-broadcast value must reduce
                # over its one true column, not C copies of it
                col = j * bc + lax.broadcasted_iota(jnp.int32, val.shape, 1)
                val = jnp.where(col < red_width[oid], val, jnp.float32(0))
                s, c, cc = _lane_cascade(val, sc[3 * red][...],
                                         sc[3 * red + 1][...],
                                         sc[3 * red + 2][...], LANE)
                sc[3 * red][...] = s
                sc[3 * red + 1][...] = c
                sc[3 * red + 2][...] = cc
                oh_ref, ol_ref = out_refs[oref], out_refs[oref + 1]

                @pl.when(j == nc - 1)
                def _flush(red=red, oh_ref=oh_ref, ol_ref=ol_ref):
                    fh, fl = _fold_lanes(sc[3 * red][...],
                                         sc[3 * red + 1][...],
                                         sc[3 * red + 2][...])
                    oh_ref[...] = fh
                    ol_ref[...] = fl

                oref += 2
                red += 1
            elif okind == "ff":
                h, l = env[oid]
                out_refs[oref][...] = bcast(h)
                out_refs[oref + 1][...] = bcast(l)
                oref += 2
            else:
                out_refs[oref][...] = bcast(env[oid])
                oref += 1

    flat = pl.pallas_call(
        kernel,
        out_shape=tuple(out_shapes),
        grid=(nr, nc),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*padded)

    # -- un-pad / un-broadcast / reshape back --------------------------------
    outs: List = []
    k = 0
    lead = out_shape[:-1] if len(out_shape) else ()
    for oid, okind in zip(prog.out_ids, out_kinds):
        nd = nd_shapes[oid]
        if okind == "red":
            outs.append(FF(
                _unbroadcast(flat[k][:R, 0].reshape(lead), lead, nd),
                _unbroadcast(flat[k + 1][:R, 0].reshape(lead), lead, nd)))
            k += 2
        elif okind == "ff":
            outs.append(FF(
                _unbroadcast(flat[k][:R, :C].reshape(out_shape),
                             out_shape, nd),
                _unbroadcast(flat[k + 1][:R, :C].reshape(out_shape),
                             out_shape, nd)))
            k += 2
        else:
            outs.append(_unbroadcast(flat[k][:R, :C].reshape(out_shape),
                                     out_shape, nd))
            k += 1
    return outs


# ===========================================================================
# hand-fused composite kernels (whole row in VMEM)
# ===========================================================================

def _row_block(R: int, C: int, planes: int, br: int) -> Tuple[int, int]:
    """Row-block size for whole-row kernels under the VMEM budget."""
    Cp = _round_up(max(C, 1), LANE)
    cap = max(SUBLANE, (VMEM_BUDGET_BYTES // (4 * planes * Cp))
              // SUBLANE * SUBLANE)
    br = min(_round_up(br, SUBLANE), cap, _round_up(max(R, 1), SUBLANE))
    return br, Cp


def _softmax_kernel(x_ref, out_ref, *, C: int, mode: str, accurate: bool):
    x = x_ref[...]                                     # (br, Cp)
    mask = lax.broadcasted_iota(jnp.int32, x.shape, 1) < C
    xm = jnp.where(mask, x, jnp.float32(-jnp.inf))
    m = jnp.max(xm, axis=1, keepdims=True)             # (br, 1)
    z = jnp.zeros((x.shape[0], LANE), jnp.float32)
    if accurate:
        # FF exponentials: x - m held exact (TwoSum), exp via the ff.math
        # kernel, BOTH limb planes through the lane cascade -> FF sum
        dh, dl = eft.two_sum(x, -m)
        eh, el = ffmath.exp22(dh, dl, eft)
        eh = jnp.where(mask, eh, jnp.float32(0))
        el = jnp.where(mask, el, jnp.float32(0))
        s, c, cc = _lane_cascade(eh, z, z, z, LANE)
        s, c, cc = _lane_cascade(el, s, c, cc, LANE)
        fh, fl = _fold_lanes(s, c, cc)                 # FF row sum
        if mode == "softmax":
            qh, _ql = eft.div22(eh, el, fh, fl)
            out_ref[...] = qh
        else:
            lh, ll = ffmath.log22(fh, fl, eft)
            oh, _ol = eft.add212(lh, ll, m)
            out_ref[...] = oh
        return
    e = jnp.where(mask, jnp.exp(x - m), jnp.float32(0))
    s, c, cc = _lane_cascade(e, z, z, z, LANE)
    fh, _fl = _fold_lanes(s, c, cc)                    # (br, 1)
    if mode == "softmax":
        out_ref[...] = e / fh
    else:                                              # logsumexp
        out_ref[...] = m + jnp.log(fh)


@functools.partial(jax.jit,
                   static_argnames=("mode", "br", "accurate", "interpret"))
def ff_softmax(x: Array, *, mode: str = "softmax", br: int = 256,
               accurate: bool = False, interpret: bool = False):
    """One-kernel compensated softmax / logsumexp over the last axis.

    The whole row lives in VMEM (C <= MAX_FUSED_COLS — callers fall back
    to the jnp impl beyond); the exp-sum uses the same lane-parallel
    Neumaier cascade as the fused rowsum.  ``mode``: "softmax" returns the
    (R, C) probabilities, "logsumexp" the (R,) LSE values.

    ``accurate=True`` is the ``ff.math``-powered accurate class: the
    exponentials run the FF exp kernel on an exact TwoSum-reduced
    argument and both limb planes feed the compensated sum, so the f32
    result is correctly-rounded-class instead of carrying the ~2^-24
    builtin-exp error into every term (still ONE kernel launch).
    """
    x = jnp.asarray(x, jnp.float32)
    shape = x.shape
    x2 = _to_2d(x)
    R, C = x2.shape
    if C > MAX_FUSED_COLS:
        raise ValueError(f"row length {C} exceeds MAX_FUSED_COLS "
                         f"({MAX_FUSED_COLS}); use the jnp impl")
    br, Cp = _row_block(R, C, planes=9 if accurate else 3, br=br)
    x2 = _pad_to(x2, br, Cp)
    Rp = x2.shape[0]
    row_spec = pl.BlockSpec((br, Cp), lambda i: (i, 0))
    if mode == "softmax":
        out_shape = jax.ShapeDtypeStruct((Rp, Cp), jnp.float32)
        out_spec = row_spec
    else:
        out_shape = jax.ShapeDtypeStruct((Rp, 1), jnp.float32)
        out_spec = pl.BlockSpec((br, 1), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_softmax_kernel, C=C, mode=mode,
                          accurate=accurate),
        out_shape=out_shape,
        grid=(Rp // br,),
        in_specs=[row_spec],
        out_specs=out_spec,
        interpret=interpret,
    )(x2)
    if mode == "softmax":
        return out[:R, :C].reshape(shape)
    return out[:R, 0].reshape(shape[:-1])


def _norm_stats_kernel(x_ref, mu_ref, var_ref, *, C: int):
    x = x_ref[...]                                     # (br, Cp)
    mask = lax.broadcasted_iota(jnp.int32, x.shape, 1) < C
    xz = jnp.where(mask, x, jnp.float32(0))
    z = jnp.zeros((x.shape[0], LANE), jnp.float32)
    s, c, cc = _lane_cascade(xz, z, z, z, LANE)
    s1h, _ = _fold_lanes(s, c, cc)
    mu = s1h / jnp.float32(C)                          # (br, 1)
    d = jnp.where(mask, x - mu, jnp.float32(0))
    s, c, cc = _lane_cascade(d * d, z, z, z, LANE)
    s2h, _ = _fold_lanes(s, c, cc)
    mu_ref[...] = mu
    var_ref[...] = s2h / jnp.float32(C)


@functools.partial(jax.jit, static_argnames=("br", "interpret"))
def ff_norm_stats(x: Array, *, br: int = 256,
                  interpret: bool = False) -> Tuple[Array, Array]:
    """One-kernel LayerNorm statistics over the last axis: compensated
    mean AND centered variance with x read from HBM once (the op-by-op
    path reads it three times).  Returns (mean, var), f32, shape[:-1]."""
    x = jnp.asarray(x, jnp.float32)
    shape = x.shape
    x2 = _to_2d(x)
    R, C = x2.shape
    if C > MAX_FUSED_COLS:
        raise ValueError(f"row length {C} exceeds MAX_FUSED_COLS "
                         f"({MAX_FUSED_COLS}); use the jnp impl")
    br, Cp = _row_block(R, C, planes=2, br=br)
    x2 = _pad_to(x2, br, Cp)
    Rp = x2.shape[0]
    col = jax.ShapeDtypeStruct((Rp, 1), jnp.float32)
    mu, var = pl.pallas_call(
        functools.partial(_norm_stats_kernel, C=C),
        out_shape=(col, col),
        grid=(Rp // br,),
        in_specs=[pl.BlockSpec((br, Cp), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((br, 1), lambda i: (i, 0)),
                   pl.BlockSpec((br, 1), lambda i: (i, 0))),
        interpret=interpret,
    )(x2)
    lead = shape[:-1]
    return mu[:R, 0].reshape(lead), var[:R, 0].reshape(lead)
