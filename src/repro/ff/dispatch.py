"""Backend dispatch registry for ``repro.ff``.

The paper presents float-float operators as a *library the application calls
uniformly*, with the GPU backend hidden behind the operator.  This module is
that seam for the JAX port: each public op name maps to a set of named
implementations, and resolution picks one per call site at trace time:

    per-call ``impl=`` kwarg
      > ``ff.use(op=impl)`` scope
      > policy (``PrecisionPolicy.matmul_impl``, for ``matmul``)
      > mesh default (ops with a registered mesh impl, inside ``ff.on_mesh``)
      > per-backend default registered here
      > first registered implementation

    Resolution is therefore *backend x mesh-context*: the same call site
    picks the best single-device implementation for the active backend, and
    — only inside an ``ff.on_mesh`` scope — the ``shard_map``-partitioned
    implementation from ``repro.ff.sharded``.  Single-device call sites
    (no mesh scope) never see the mesh tier.

Implementations are plain callables over ``repro.core`` algorithms and
``repro.kernels`` Pallas kernels; several are themselves backend-aware
(compiled Pallas on TPU, interpret-Pallas or pure-jnp on CPU) so "best
implementation per backend" lives in exactly one place.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import compensated, ffmatmul, ffmath
from repro.core import ff as core_ff
from repro.core import transforms as T
from repro.core.ff import FF
from repro.ff import scope

Array = jnp.ndarray

_REGISTRY: Dict[str, Dict[str, Callable]] = {}
_DEFAULTS: Dict[str, Dict[str, str]] = {}     # op -> {backend|"*": impl}
_MESH_DEFAULTS: Dict[str, str] = {}           # op -> impl inside ff.on_mesh
# (op, impl) pairs that hold a whole row in VMEM: as a backend default, a
# row past MAX_FUSED_COLS yields to the generic "*" default at resolution
# (named in telemetry) instead of falling back inside the call
_WHOLE_ROW: set = set()


class FFFallbackWarning(UserWarning):
    """A kernel impl ran its jnp formulation instead of the kernel."""


# static fallback order for a "tuned_accurate" request on an untuned shape
# bucket (see resolve_name): per-op, first registered name wins
_ACCURATE_FALLBACK: Dict[str, Tuple[str, ...]] = {
    "matmul": ("f64", "ozaki", "dot2"),
    "add": ("accurate",),
    # composites whose f32-builtin exponentials cap them at the fast class:
    # the accurate tier is the ff.math-powered impl
    "softmax": ("ff",),
    "logsumexp": ("ff",),
    # attention: native-f64 materialized scores where the hardware has
    # them (size-guarded; degrades to the FF recurrence on TPU / at
    # training shapes), else the compensated jnp recurrence
    "attention": ("f64", "ff"),
    # ff.math family: native f64 where the hardware has it (degrades to the
    # compensated jnp formulation on TPU), else the FF kernel itself
    **{op: ("f64", "jnp") for op in tuple(ffmath.UNARY22) + ("pow",)},
}


def backend() -> str:
    """The JAX backend the dispatcher routes for ("cpu", "tpu", "gpu")."""
    return jax.default_backend()


def register(op: str, impl: str, fn: Callable, *,
             default_for: Tuple[str, ...] = (),
             mesh_default: bool = False,
             whole_row: bool = False) -> Callable:
    """Register ``fn`` as implementation ``impl`` of ``op``.

    ``default_for`` lists backends this impl is the default on ("*" = any
    backend without a more specific default).  ``mesh_default=True`` makes
    it the default *inside an* ``ff.on_mesh`` *scope* (mesh-context
    resolution; see module docstring) — outside any mesh scope it is only
    reachable by explicit ``impl=``/``ff.use`` selection.
    ``whole_row=True`` marks a kernel that holds a whole last-axis row:
    as a backend default it yields rows that do not fit to the generic
    ``"*"`` default.
    """
    _REGISTRY.setdefault(op, {})[impl] = fn
    for b in default_for:
        _DEFAULTS.setdefault(op, {})[b] = impl
    if mesh_default:
        _MESH_DEFAULTS[op] = impl
    if whole_row:
        _WHOLE_ROW.add((op, impl))
    return fn


def mesh_default(op: str) -> Optional[str]:
    """The implementation ``op`` resolves to inside ``ff.on_mesh`` scopes
    (``None`` when the op has no mesh-partitioned implementation)."""
    return _MESH_DEFAULTS.get(op)


def ops() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def impls(op: str) -> Tuple[str, ...]:
    """Registered implementation names for ``op``."""
    return tuple(sorted(_REGISTRY.get(op, ())))


def resolve_name(op: str, impl: Optional[str] = None,
                 shape: Optional[Tuple[int, ...]] = None) -> str:
    """Resolve which implementation a call to ``op`` uses (see module doc).

    With ``shape`` (e.g. ``(M, K, N)`` for matmul), the measurement-driven
    tuning table (``repro.ff.tune``) participates in resolution:

      * the special names ``"tuned"`` / ``"tuned_accurate"`` (usable
        per-call, in ``ff.use`` scopes and in ``policy(matmul=...)``)
        resolve to the cached winner of the fast / accurate class;
      * when resolution falls through to the backend default (no explicit
        choice anywhere), a cached fast-class winner overrides the static
        default — ``dispatch_default`` is then never slower than the best
        registered impl wherever measurements exist.
    """
    if op not in _REGISTRY:
        raise KeyError(f"unknown ff op {op!r}; registered: {ops()}")
    # `src` tracks which resolution rule actually picked the winner — it
    # feeds the ff_dispatch_resolutions_total telemetry counter below.
    # Resolution runs at trace time only, so the recording is free in
    # steady-state jit execution.
    name = impl or scope.current_impl(op)
    src = ("explicit" if impl else
           "scope" if name is not None else None)
    if name is None and op == "matmul":
        pol = scope.current_policy().matmul_impl
        if pol and pol != "auto":
            name, src = pol, "policy"
    # mesh-context resolution: inside an ff.on_mesh scope, ops with a
    # registered mesh impl route to the shard_map tier UNLESS something
    # more explicit (per-call impl, use() scope, policy) chose otherwise.
    # Outside any mesh scope this branch never fires — single-device call
    # sites resolve exactly as before.
    if name is None and op in _MESH_DEFAULTS \
            and scope.current_mesh() is not None:
        name, src = _MESH_DEFAULTS[op], "mesh"
    if name in ("tuned", "tuned_accurate"):
        from repro.ff import tuning as _tune
        accurate = name == "tuned_accurate"
        name = (_tune.lookup_impl(op, shape,
                                  "accurate" if accurate else "fast")
                if shape is not None else None)
        src = "tuned_accurate" if accurate else "tuned"
        if name is not None and name not in _REGISTRY[op]:
            name = None   # stale/foreign sidecar must never break dispatch
        # an explicit accurate-tier request must NEVER degrade to the fast
        # class just because the shape bucket is untuned — fall back to the
        # static accurate-tier default (per-op: e.g. matmul's "f64"
        # resolves to one native dgemm where the hardware has f64 and
        # degrades to the fused Ozaki kernel on TPU)
        if name is None and accurate:
            reg = _REGISTRY.get(op, {})
            name = next((c for c in _ACCURATE_FALLBACK.get(op, ())
                         if c in reg), None)
            src = "accurate_fallback"
    if name is None and shape is not None:
        from repro.ff import tuning as _tune
        name = _tune.lookup_impl(op, shape)
        src = "tuned_default"
        if name is not None and name not in _REGISTRY[op]:
            name = None   # see above: unknown tuned winner -> static default
    if name is None:
        d = _DEFAULTS.get(op, {})
        name = d.get(backend(), d.get("*"))
        src = "static_default"
        if (shape is not None and (op, name) in _WHOLE_ROW
                and not _row_fits(shape)):
            name, src = d.get("*"), "shape_default"
    if name is None:
        name, src = next(iter(_REGISTRY[op])), "first_registered"
    if name not in _REGISTRY[op]:
        raise KeyError(
            f"ff op {op!r} has no implementation {name!r}; "
            f"available: {impls(op)}")
    # guard-context resolution: inside an ff.guard(mode="degrade") scope
    # that has recorded a violation for this op, the accurate-class
    # resolution drops one class (ff -> fast f32) — identity everywhere
    # else (see repro.ff.guard.maybe_degrade).
    import sys
    _guard = sys.modules.get("repro.ff.guard")   # NOT `from repro.ff import
    if _guard is None:                           # guard` — the package attr
        from importlib import import_module      # is the scope *class*
        _guard = import_module("repro.ff.guard")
    final = _guard.maybe_degrade(op, name)
    if final != name:
        src = "guard_degraded"
    _record_resolution(op, final, src or "static_default", shape)
    return final


def _record_resolution(op: str, name: str, src: str,
                       shape: Optional[Tuple[int, ...]]) -> None:
    """Dispatch telemetry (trace-time only): count (op, impl, source,
    backend, shape-bucket) into the process-global obs registry.  Lazy
    import — repro.obs must never be a hard import of the dispatch core,
    and obs itself never imports repro.ff (no cycle)."""
    try:
        from repro import obs as _obs
        if shape:
            from repro.ff import tuning as _tune
            bucket = _tune.bucket_key(shape)
        else:
            bucket = ""
        _obs.record_resolution(op, name, src, backend(), bucket)
    except Exception:     # telemetry must never break dispatch
        pass


def resolve_opts(op: str, name: str,
                 shape: Optional[Tuple[int, ...]] = None) -> dict:
    """Measured-best block config for ``name`` at ``shape`` (empty when the
    tuning table has no entry).  Callers merge these UNDER explicit opts."""
    if shape is None:
        return {}
    from repro.ff import tuning as _tune
    return _tune.lookup_opts(op, name, shape)


def lookup(op: str, impl: str) -> Callable:
    return _REGISTRY[op][impl]


def call(op: str, impl: Optional[str], *args, **kw):
    return lookup(op, resolve_name(op, impl))(*args, **kw)


# ===========================================================================
# implementation registrations
# ===========================================================================

def _interpret(flag: Optional[bool]) -> bool:
    """Pallas interpret mode: explicit flag wins, else compiled on TPU only."""
    return (backend() != "tpu") if flag is None else flag


def _fallback_warn(impl: str, op: str, why: str) -> None:
    """A kernel impl substituting its jnp formulation must say so: tuned
    winners/defaults must never brick a call, but an EXPLICIT impl=
    request landing here would otherwise silently validate or benchmark
    the wrong kernel.  Fires once per trace (Python-level warn), as an
    :class:`FFFallbackWarning` so a caller can make it an error."""
    import warnings
    warnings.warn(f"ff.{op}(impl={impl!r}): {why}; falling back to the "
                  f"jnp formulation", FFFallbackWarning, stacklevel=3)


def _as_ff(x) -> FF:
    if isinstance(x, FF):
        return x
    return FF.from_f32(jnp.asarray(x, jnp.float32))


# -- elementwise add/mul/div/sqrt -------------------------------------------

def _add_jnp(a, b, **_kw) -> FF:
    if isinstance(a, FF) and not isinstance(b, FF):
        return core_ff.add212(a, jnp.asarray(b, jnp.float32))
    if isinstance(b, FF) and not isinstance(a, FF):
        return core_ff.add212(b, jnp.asarray(a, jnp.float32))
    return core_ff.add22(_as_ff(a), _as_ff(b))


def _add_accurate(a, b, **_kw) -> FF:
    return core_ff.add22_accurate(_as_ff(a), _as_ff(b))


def _mul_jnp(a, b, **_kw) -> FF:
    if isinstance(a, FF) and not isinstance(b, FF):
        return core_ff.mul212(a, jnp.asarray(b, jnp.float32))
    if isinstance(b, FF) and not isinstance(a, FF):
        return core_ff.mul212(b, jnp.asarray(a, jnp.float32))
    return core_ff.mul22(_as_ff(a), _as_ff(b))


def _ew_block(block) -> tuple:
    from repro.kernels import ff_elementwise
    return tuple(block) if block else ff_elementwise.DEFAULT_BLOCK


def _elementwise_pallas(op22):
    def fn(a, b, *, block=None, interpret: Optional[bool] = None,
           **_kw) -> FF:
        from repro.kernels import ff_elementwise
        af, bf = _as_ff(a), _as_ff(b)
        rh, rl = ff_elementwise.elementwise(
            op22, af.hi, af.lo, bf.hi, bf.lo, block=_ew_block(block),
            interpret=_interpret(interpret))
        return FF(rh, rl)
    return fn


def _div_jnp(a, b, **_kw) -> FF:
    return core_ff.div22(_as_ff(a), _as_ff(b))


def _sqrt_jnp(a, **_kw) -> FF:
    return core_ff.sqrt22(_as_ff(a))


def _sqrt_pallas(a, *, block=None, interpret: Optional[bool] = None,
                 **_kw) -> FF:
    from repro.kernels import ff_elementwise
    af = _as_ff(a)
    rh, rl = ff_elementwise.elementwise(
        "sqrt22", af.hi, af.lo, block=_ew_block(block),
        interpret=_interpret(interpret))
    return FF(rh, rl)


# Elementwise default is jnp on EVERY backend: a 4-20 flop FF op fuses into
# the surrounding XLA graph, while a standalone pallas_call pads operands to
# (8,128) tiles and breaks fusion — Pallas only wins where a kernel owns a
# loop (matmul/rowsum below) or a whole CHAIN of FF ops rides one launch
# (ff.fused / the composite kernels below).  The per-op pallas impls stay
# registered for validation and for explicit callers.
register("add", "jnp", _add_jnp, default_for=("*",))
register("add", "accurate", _add_accurate)
register("add", "pallas", _elementwise_pallas("add22"))
register("mul", "jnp", _mul_jnp, default_for=("*",))
register("mul", "pallas", _elementwise_pallas("mul22"))
register("div", "jnp", _div_jnp, default_for=("*",))
register("div", "pallas", _elementwise_pallas("div22"))
register("sqrt", "jnp", _sqrt_jnp, default_for=("*",))
register("sqrt", "pallas", _sqrt_pallas)


# -- EFTs (f32, f32) -> FF ---------------------------------------------------

def _two_sum_jnp(a, b) -> FF:
    s, r = T.two_sum(jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32))
    return FF(s, r)


def _two_prod_jnp(a, b) -> FF:
    x, y = T.two_prod(jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32))
    return FF(x, y)


def _eft_pallas(op):
    def fn(a, b, *, interpret: Optional[bool] = None) -> FF:
        from repro.kernels import ff_elementwise
        x, y = ff_elementwise.elementwise(
            op, jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32),
            interpret=_interpret(interpret))
        return FF(x, y)
    return fn


register("two_sum", "jnp", _two_sum_jnp, default_for=("*",))
register("two_sum", "pallas", _eft_pallas("two_sum"))
register("two_prod", "jnp", _two_prod_jnp, default_for=("*",))
register("two_prod", "pallas", _eft_pallas("two_prod"))


# -- matmul (f32/FF operands handled by the autodiff layer; these take f32) --

def _mm_hybrid(a: Array, b: Array, *, block_k: int = 512,
               bm: int = 256, bn: int = 256,
               interpret: Optional[bool] = None, **_kw) -> FF:
    """Blocked-K MXU + Add22 — the production path.  Compiled Pallas on TPU,
    pure-jnp (identical K-block order) elsewhere."""
    if backend() == "tpu" and interpret is not True:
        from repro.kernels import ff_matmul
        hi, lo = ff_matmul.ff_matmul(a, b, bm=bm, bn=bn, bk=block_k,
                                     interpret=False)
        return FF(hi, lo)
    return ffmatmul.matmul_compensated(a, b, block_k=block_k)


def _mm_pallas_hybrid(a: Array, b: Array, *, bm: int = 256, bn: int = 256,
                      bk: int = 512, interpret: Optional[bool] = None,
                      **_kw) -> FF:
    from repro.kernels import ff_matmul
    hi, lo = ff_matmul.ff_matmul(a, b, bm=bm, bn=bn, bk=bk,
                                 interpret=_interpret(interpret))
    return FF(hi, lo)


def _mm_dot2(a: Array, b: Array, *, bm: int = 128, bn: int = 128,
             bk: int = 128, vec: int = 8, chunk: int = 32,
             interpret: Optional[bool] = None, **_kw) -> FF:
    """Paper-faithful Mul12 + Dot3 cascade (~2^-44), block-vectorized over
    K.  Pallas kernel on TPU, pure-jnp chunked scan elsewhere."""
    if backend() == "tpu" and interpret is not True:
        from repro.kernels import ff_matmul
        hi, lo = ff_matmul.ff_matmul_dot2(a, b, bm=bm, bn=bn, bk=bk,
                                          vec=vec, interpret=False)
        return FF(hi, lo)
    return ffmatmul.matmul_dot2(a, b, chunk=chunk)


def _mm_pallas_dot2(a: Array, b: Array, *, bm: int = 128, bn: int = 128,
                    bk: int = 128, vec: int = 8,
                    interpret: Optional[bool] = None, **_kw) -> FF:
    from repro.kernels import ff_matmul
    hi, lo = ff_matmul.ff_matmul_dot2(a, b, bm=bm, bn=bn, bk=bk, vec=vec,
                                      interpret=_interpret(interpret))
    return FF(hi, lo)


def _mm_split(a: Array, b: Array, *, block_k: int = 512, **_kw) -> FF:
    return ffmatmul.matmul_split(a, b, block_k=block_k)


def _mm_compensated(a: Array, b: Array, *, block_k: int = 512, **_kw) -> FF:
    return ffmatmul.matmul_compensated(a, b, block_k=block_k)


def _mm_ozaki(a: Array, b: Array, *, slices: int = 0, beta: int = 0,
              block_k: int = 0, interpret: Optional[bool] = None,
              **_kw) -> FF:
    """Exact-slice Ozaki matmul (~2^-46): fused Pallas kernel on TPU,
    batched stacked-GEMM jnp path elsewhere."""
    from repro.obs import annotate
    with annotate("ff.matmul_ozaki"):
        if backend() == "tpu" and interpret is not True:
            from repro.kernels import ff_matmul
            hi, lo = ff_matmul.ff_matmul_ozaki(
                a, b, slices=slices, beta=beta,
                bk=block_k or 512, interpret=False)
            return FF(hi, lo)
        return ffmatmul.matmul_ozaki(a, b, slices=slices, beta=beta,
                                     block_k=block_k)


def _mm_f64(a: Array, b: Array, *, interpret: Optional[bool] = None,
            **_kw) -> FF:
    """Native-f64 dgemm rounded to FF (~2^-48) — the accurate tier at
    hardware speed on backends that HAVE f64 (CPU, most GPUs).  TPU has no
    f64 unit, so the same name degrades gracefully to the best pure-f32
    accurate impl there (the fused Ozaki kernel): "f64" means "f64-quality
    results the fastest way this hardware can", which on f32-only hardware
    is exactly the paper's emulation."""
    if backend() == "tpu":
        return _mm_ozaki(a, b, interpret=interpret)
    return ffmatmul.matmul_f64(a, b)


def _mm_pallas_ozaki(a: Array, b: Array, *, slices: int = 0, beta: int = 0,
                     bm: int = 128, bn: int = 128, bk: int = 512,
                     interpret: Optional[bool] = None, **_kw) -> FF:
    from repro.kernels import ff_matmul
    hi, lo = ff_matmul.ff_matmul_ozaki(a, b, slices=slices, beta=beta,
                                       bm=bm, bn=bn, bk=bk,
                                       interpret=_interpret(interpret))
    return FF(hi, lo)


register("matmul", "hybrid", _mm_hybrid, default_for=("*",))
register("matmul", "pallas_hybrid", _mm_pallas_hybrid)
register("matmul", "compensated", _mm_compensated)
register("matmul", "split", _mm_split)
register("matmul", "dot2", _mm_dot2)
register("matmul", "pallas_dot2", _mm_pallas_dot2)
register("matmul", "ozaki", _mm_ozaki)
register("matmul", "pallas_ozaki", _mm_pallas_ozaki)
register("matmul", "f64", _mm_f64)


# -- reductions --------------------------------------------------------------

def _sum_blocked(x: Array, axis=None, *, block: int = 128, **_kw) -> FF:
    return compensated.ff_sum_blocked(x, axis=axis, block=block)


def _sum_cascade(x: Array, axis=None, **_kw) -> FF:
    return compensated.ff_sum(x, axis=axis)


def _sum_pallas_rowsum(x: Array, axis=None, *, br: int = 256, bc: int = 512,
                       lane: int = 128,
                       interpret: Optional[bool] = None, **_kw) -> FF:
    """Pallas row-reduction kernel over the last axis.  ND inputs flatten
    to (prod(leading), last) — the real call sites are 3-D/4-D
    activations and must actually reach the kernel.  Non-last axes fall
    back to the blocked jnp impl: this name can be a TUNED default for a
    shape bucket, and a tuned winner must never brick a call."""
    from repro.kernels import ff_reduce
    if isinstance(axis, tuple) and len(axis) == 1:
        axis = axis[0]
    if x.ndim < 1 or axis not in (-1, x.ndim - 1):
        _fallback_warn("pallas_rowsum", "sum",
                       f"axis {axis} of a {x.ndim}-D input is not a "
                       f"last-axis row reduction")
        return _sum_blocked(x, axis=axis)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]) if x.ndim != 2 else x
    hi, lo = ff_reduce.ff_rowsum(x2, br=br, bc=bc, lane=lane,
                                 interpret=_interpret(interpret))
    return FF(hi.reshape(lead), lo.reshape(lead))


def _dot_jnp(a: Array, b: Array, axis=None, **_kw) -> FF:
    return compensated.ff_dot(a, b, axis=axis)


def _mean_jnp(x: Array, axis=None, *, block: int = 128, **_kw) -> FF:
    n = x.size if axis is None else 1
    if axis is not None:
        axes = (axis,) if isinstance(axis, int) else tuple(axis)
        for ax in axes:
            n *= x.shape[ax]
    s = compensated.ff_sum_blocked(x, axis=axis, block=block)
    # divide in FF: multiplying by an f32-rounded 1/n would cap the op at
    # ~2^-24 (FF.from_f64 keeps n exact to 2^48, covering any real axis)
    return core_ff.div22(s, FF.from_f64(float(n)))


def _logsumexp_jnp(x: Array, axis: int = -1, *, block: int = 256, **_kw):
    """Compensated LSE: returns the f32 log-sum-exp values."""
    x = jnp.asarray(x, jnp.float32)
    m = jnp.max(x, axis=axis, keepdims=True)
    e = jnp.exp(x - m)
    s = compensated.ff_sum_blocked(e, axis=axis, block=block)
    return jnp.squeeze(m, axis=axis) + jnp.log(s.to_f32())


def _row_fits(shape: Tuple[int, ...]) -> bool:
    """Whether a row of ``shape`` (last axis) fits the whole-row composite
    kernels' VMEM budget (see ff_fused.MAX_FUSED_COLS)."""
    from repro.kernels import ff_fused
    return shape[-1] <= ff_fused.MAX_FUSED_COLS


def _last_axis_fusable(x: Array, axis: int) -> bool:
    """Whether the whole-row composite kernels apply: last-axis reduction
    with the row fitting the VMEM budget."""
    return x.ndim >= 1 and axis in (-1, x.ndim - 1) and _row_fits(x.shape)


def _logsumexp_pallas(x: Array, axis: int = -1, *, br: int = 256,
                      interpret: Optional[bool] = None, **_kw):
    """One-kernel max + exp + compensated sum + log (whole row in VMEM).
    Registered as the TPU default, so it must never brick a call it cannot
    serve: non-last axes / over-long rows fall back to the jnp impl."""
    x = jnp.asarray(x, jnp.float32)
    if not _last_axis_fusable(x, axis):
        _fallback_warn("pallas", "logsumexp",
                       "not a last-axis reduction within MAX_FUSED_COLS")
        return _logsumexp_jnp(x, axis=axis)
    from repro.kernels import ff_fused
    return ff_fused.ff_softmax(x, mode="logsumexp", br=br,
                               interpret=_interpret(interpret))


import functools as _ft


@_ft.partial(jax.jit, static_argnames=("axis",))
def _sum_f64_axis(e: Array, axis: int) -> Array:
    """Exp-sum at native f64 (the matmul_f64 corollary for reductions):
    on hardware WITH f64 units one wide sum reaches ~2^-53-per-step
    accuracy — past FF quality — at naive-sum speed.  Scoped exactly like
    ``ffmatmul._matmul_f64_jit`` (trace-local enable_x64 behind a nested
    jit boundary; see its docstring for why the boundary is load-bearing
    — and module-level like it, so eager callers hit the jit cache
    instead of recompiling per call)."""
    from jax import lax

    with jax.enable_x64(True):
        s = jnp.sum(lax.convert_element_type(e, jnp.float64), axis=axis)
        return lax.convert_element_type(s, jnp.float32)


def _logsumexp_f64(x: Array, axis: int = -1, **_kw):
    """Compensated-quality LSE via a native-f64 exp-sum (CPU default).
    Like matmul's "f64", the name means "f64-quality the fastest way this
    hardware can": TPU has no f64 unit, so it degrades to the fused
    Pallas kernel there."""
    if backend() == "tpu":
        return _logsumexp_pallas(x, axis=axis)
    x = jnp.asarray(x, jnp.float32)
    m = jnp.max(x, axis=axis, keepdims=True)
    e = jnp.exp(x - m)
    return jnp.squeeze(m, axis=axis) + jnp.log(_sum_f64_axis(e, axis))


def _softmax_f64(x: Array, axis: int = -1, **_kw):
    """Compensated-quality softmax via a native-f64 denominator; degrades
    to the fused Pallas kernel on TPU (see _logsumexp_f64)."""
    if backend() == "tpu":
        return _softmax_pallas(x, axis=axis)
    x = jnp.asarray(x, jnp.float32)
    m = jnp.max(x, axis=axis, keepdims=True)
    e = jnp.exp(x - m)
    s = _sum_f64_axis(e, axis)
    return e / jnp.expand_dims(s, axis % x.ndim)


register("sum", "blocked", _sum_blocked, default_for=("*",))
register("sum", "cascade", _sum_cascade)
register("sum", "pallas_rowsum", _sum_pallas_rowsum)
register("dot", "jnp", _dot_jnp, default_for=("*",))
register("mean", "jnp", _mean_jnp, default_for=("*",))
# per-backend resolution like every other op: jnp is the generic default,
# the fused Pallas kernel takes over where it is compiled (TPU), and the
# native-f64 reduction where the hardware has f64 units (CPU) — the old
# blanket default_for=("*",) left every non-jnp path dead code
register("logsumexp", "jnp", _logsumexp_jnp, default_for=("*",))
register("logsumexp", "pallas", _logsumexp_pallas, default_for=("tpu",),
         whole_row=True)
register("logsumexp", "f64", _logsumexp_f64, default_for=("cpu",))


# -- fused composite chains (the hot real-world FF pipelines) ----------------
#
# Each composite is ONE dispatch op with a jnp fallback (bitwise-identical
# to the op-by-op formulation it replaced) and a fused implementation that
# rides a single kernel launch — compiled Pallas on TPU, the replayed-jnp
# executor elsewhere (same graph XLA already fuses).  Callers go through
# the differentiable wrappers in repro.ff.autodiff.

def _softmax_jnp(x: Array, axis: int = -1, *, block: int = 256, **_kw):
    """Compensated softmax: exp(x - max) / FF-accurate denominator."""
    x = jnp.asarray(x, jnp.float32)
    m = jnp.max(x, axis=axis, keepdims=True)
    e = jnp.exp(x - m)
    s = compensated.ff_sum_blocked(e, axis=axis, block=block)
    return e / jnp.expand_dims(s.to_f32(), axis % x.ndim)


def _softmax_pallas(x: Array, axis: int = -1, *, br: int = 256,
                    interpret: Optional[bool] = None, **_kw):
    x = jnp.asarray(x, jnp.float32)
    if not _last_axis_fusable(x, axis):
        _fallback_warn("pallas", "softmax",
                       "not a last-axis reduction within MAX_FUSED_COLS")
        return _softmax_jnp(x, axis=axis)
    from repro.kernels import ff_fused
    return ff_fused.ff_softmax(x, mode="softmax", br=br,
                               interpret=_interpret(interpret))


register("softmax", "jnp", _softmax_jnp, default_for=("*",))
register("softmax", "pallas", _softmax_pallas, default_for=("tpu",),
         whole_row=True)
register("softmax", "f64", _softmax_f64, default_for=("cpu",))


def _adamw_chain(sqrtf, packf, addf, g, m, v, w, wlo,
                 lr, b1, b2, bc1, bc2, eps, wd):
    """THE AdamW leaf update — shared verbatim between the jnp impl and
    the fused tracer so the two can never drift (op order is bitwise-
    load-bearing: `(1.0 - b2) * g * g` associates left)."""
    m2 = b1 * m + (1.0 - b1) * g
    v2 = b2 * v + (1.0 - b2) * g * g
    upd = (m2 / bc1) / (sqrtf(v2 / bc2) + eps)
    upd = upd + wd * w
    delta = -lr * upd
    new = addf(packf(w, wlo), delta)        # Add212: FF master += delta
    return new, m2, v2


def _adamw_jnp(g, m, v, w, wlo, lr, b1, b2, bc1, bc2, *,
               eps: float, wd: float, **_kw):
    return _adamw_chain(jnp.sqrt, FF, core_ff.add212,
                        g, m, v, w, wlo, lr, b1, b2, bc1, bc2, eps, wd)


def _adamw_fused(g, m, v, w, wlo, lr, b1, b2, bc1, bc2, *,
                 eps: float, wd: float,
                 interpret: Optional[bool] = None, **_kw):
    from repro.ff import fusion

    fn = fusion.fused(lambda *a: _adamw_chain(
        fusion.sqrt, fusion.pack, (lambda x, y: x + y), *a, eps, wd))
    return fn(g, m, v, w, wlo, lr, b1, b2, bc1, bc2,
              interpret=interpret)


register("adamw_update", "jnp", _adamw_jnp, default_for=("*",))
register("adamw_update", "fused", _adamw_fused, default_for=("tpu",))


def _mean_sq_jnp(x: Array, *, block: int = 128, **_kw) -> Array:
    """RMSNorm statistic: compensated mean of squares -> f32."""
    x = jnp.asarray(x, jnp.float32)
    return (compensated.ff_sum_blocked(x * x, axis=-1, block=block).to_f32()
            / x.shape[-1])


def _mean_sq_fused(x: Array, *, interpret: Optional[bool] = None,
                   **_kw) -> Array:
    from repro.ff import fusion

    x = jnp.asarray(x, jnp.float32)
    if not _last_axis_fusable(x, -1):
        _fallback_warn("fused", "mean_sq", "row exceeds MAX_FUSED_COLS")
        return _mean_sq_jnp(x)
    fn = fusion.fused(lambda xf: (xf * xf).sum())
    return fn(x, interpret=interpret).to_f32() / x.shape[-1]


register("mean_sq", "jnp", _mean_sq_jnp, default_for=("*",))
register("mean_sq", "fused", _mean_sq_fused, default_for=("tpu",),
         whole_row=True)


def _norm_stats_jnp(x: Array, *, block: int = 128, **_kw):
    """LayerNorm statistics: compensated mean and centered variance."""
    x = jnp.asarray(x, jnp.float32)
    n = x.shape[-1]
    mu = compensated.ff_sum_blocked(x, axis=-1, block=block).to_f32() / n
    var = compensated.ff_sum_blocked(
        (x - mu[..., None]) ** 2, axis=-1, block=block).to_f32() / n
    return mu, var


def _norm_stats_pallas(x: Array, *, br: int = 256,
                       interpret: Optional[bool] = None, **_kw):
    x = jnp.asarray(x, jnp.float32)
    if not _last_axis_fusable(x, -1):
        _fallback_warn("pallas", "norm_stats", "row exceeds MAX_FUSED_COLS")
        return _norm_stats_jnp(x)
    from repro.kernels import ff_fused
    return ff_fused.ff_norm_stats(x, br=br, interpret=_interpret(interpret))


register("norm_stats", "jnp", _norm_stats_jnp, default_for=("*",))
register("norm_stats", "pallas", _norm_stats_pallas,
         default_for=("tpu",), whole_row=True)


# -- FF elementary functions (the ff.math subsystem) -------------------------
#
# Four implementation classes per function, mirroring the matmul tiers:
#
#   * ``jnp``     — the compensated reference: repro.core.ffmath argument
#                   reduction + FF polynomial kernels over the barrier-
#                   carrying core EFTs (the default on every backend
#                   WITHOUT native f64 — i.e. everywhere but CPU below;
#                   fuses into the surrounding XLA graph like the
#                   arithmetic elementwise ops).
#   * ``pallas``  — the same algorithm as a Pallas kernel (barrier-free
#                   eft primitives; compiled on TPU, interpret-mode
#                   validation elsewhere).  Bitwise-identical to ``jnp``
#                   under the EFT-safe ISA contract.
#   * ``f64``     — native double transcendental rounded to FF, scoped
#                   exactly like ``matmul_f64`` (trace-local enable_x64
#                   behind a module-level nested jit).  The accurate-tier
#                   default on CPU; degrades to ``jnp`` on TPU (no f64
#                   unit) — "f64-quality the fastest way this hardware
#                   can".
#   * ``fast``    — the f32 builtin on the rounded hi limb, lifted back to
#                   FF with a zero lo.  ~2^-24: a *documented-contract*
#                   escape hatch for throughput experiments, never a
#                   default and never fast-winner eligible in ff.tune.

MATH_UNARY_OPS: Tuple[str, ...] = tuple(sorted(ffmath.UNARY22))
MATH_OPS: Tuple[str, ...] = MATH_UNARY_OPS + ("pow",)


def _math_jnp(op: str):
    fn = ffmath.UNARY22[op]

    def impl(a, **_kw) -> FF:
        af = _as_ff(a)
        return FF(*fn(af.hi, af.lo, ffmath.CORE))
    return impl


def _math_pallas(op: str):
    def impl(a, *, block=None, interpret: Optional[bool] = None,
             **_kw) -> FF:
        from repro.kernels import ff_math
        af = _as_ff(a)
        rh, rl = ff_math.math_elementwise(
            op, af.hi, af.lo,
            block=tuple(block) if block else ff_math.DEFAULT_BLOCK,
            interpret=_interpret(interpret))
        return FF(rh, rl)
    return impl


def _math_f64_fns():
    # resolved lazily inside the jitted body so the x64 scope is active.
    # gelu is spelled out with weakly-typed python-float constants:
    # jax.nn.gelu's own constants canonicalize to f32 under the ambient
    # (x64-off) jit config and poison the f64 trace
    from jax import lax as _lax

    # constants are DERIVED from the traced value (exp(x-x) == 1): a bare
    # literal — python float or jnp.float64 — gets constant-folded at
    # trace time and canonicalized back to f32 under the ambient x64-off
    # config, poisoning the f64 graph (same hazard _pow_f64_jit dodges)
    def sig(x):
        one = jnp.exp(x - x)
        return one / (one + jnp.exp(-x))

    def gelu(x):
        one = jnp.exp(x - x)
        two = one + one
        return (one / two) * x * (one + _lax.erf(x / jnp.sqrt(two)))

    return {
        "exp": jnp.exp, "expm1": jnp.expm1, "log": jnp.log,
        "log1p": jnp.log1p, "tanh": jnp.tanh, "sigmoid": sig,
        "erf": _lax.erf, "gelu": gelu,
        "silu": lambda x: x * sig(x),
    }


@_ft.partial(jax.jit, static_argnames=("op",))
def _math_f64_jit(op: str, ah: Array, al: Array) -> Tuple[Array, Array]:
    """Native-f64 elementary function -> FF (the matmul_f64 corollary for
    transcendentals).  Same trace-scoped enable_x64 behind a module-level
    nested-jit boundary (see ``ffmatmul._matmul_f64_jit`` for why the
    boundary is load-bearing under custom_vjp lowering)."""
    from jax import lax

    with jax.enable_x64(True):
        x = (lax.convert_element_type(ah, jnp.float64)
             + lax.convert_element_type(al, jnp.float64))
        r = _math_f64_fns()[op](x)
        hi = lax.convert_element_type(r, jnp.float32)
        lo = lax.convert_element_type(
            r - lax.convert_element_type(hi, jnp.float64), jnp.float32)
    return hi, lo


def _math_f64(op: str):
    jnp_impl = _math_jnp(op)

    def impl(a, **_kw) -> FF:
        if backend() == "tpu":
            return jnp_impl(a)
        af = _as_ff(a)
        return FF(*_math_f64_jit(op, af.hi, af.lo))
    return impl


_MATH_FAST_FNS = {
    "exp": jnp.exp, "expm1": jnp.expm1, "log": jnp.log, "log1p": jnp.log1p,
    "tanh": jnp.tanh, "sigmoid": jax.nn.sigmoid,
    "erf": jax.lax.erf, "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "silu": jax.nn.silu,
}


def _math_fast(op: str):
    fn = _MATH_FAST_FNS[op]

    def impl(a, **_kw) -> FF:
        af = _as_ff(a)
        return FF.from_f32(fn(af.hi + af.lo))
    return impl


for _op in MATH_UNARY_OPS:
    register(_op, "jnp", _math_jnp(_op), default_for=("*",))
    register(_op, "pallas", _math_pallas(_op))
    register(_op, "f64", _math_f64(_op), default_for=("cpu",))
    register(_op, "fast", _math_fast(_op))


def _pow_jnp(a, b, **_kw) -> FF:
    af, bf = _as_ff(a), _as_ff(b)
    return FF(*ffmath.pow22(af.hi, af.lo, bf.hi, bf.lo, ffmath.CORE))


def _pow_pallas(a, b, *, block=None, interpret: Optional[bool] = None,
                **_kw) -> FF:
    from repro.kernels import ff_math
    af, bf = _as_ff(a), _as_ff(b)
    rh, rl = ff_math.math_elementwise(
        "pow", af.hi, af.lo, bf.hi, bf.lo,
        block=tuple(block) if block else ff_math.DEFAULT_BLOCK,
        interpret=_interpret(interpret))
    return FF(rh, rl)


@jax.jit
def _pow_f64_jit(ah, al, bh, bl) -> Tuple[Array, Array]:
    from jax import lax

    # domain test on the f32 limb (a < 0 iff hi < 0 for normalized FF):
    # literal promotion inside the scoped-x64 region mixes f32/f64 operands.
    # b == 0 is excluded: pow22's rule is b == 0 -> 1 LAST (0**0 == 1,
    # (-2)**0 == 1), and the mask must not flip that between impl tiers
    neg = (ah < jnp.float32(0)) & (bh != jnp.float32(0))
    with jax.enable_x64(True):
        a = (lax.convert_element_type(ah, jnp.float64)
             + lax.convert_element_type(al, jnp.float64))
        b = (lax.convert_element_type(bh, jnp.float64)
             + lax.convert_element_type(bl, jnp.float64))
        # match the FF kernel's domain rules (a < 0 -> nan, no integer-b
        # special case) so impl choice never flips domain semantics.  The
        # nan is derived from `a` (0/0) — a literal constant would be
        # canonicalized back to f32 under the trace-scoped x64 config
        nan64 = (a - a) / (a - a)         # 0/0; stays f64 under the
        r = jnp.where(neg, nan64, jnp.power(a, b))    # scoped-x64 trace
        hi = lax.convert_element_type(r, jnp.float32)
        lo = lax.convert_element_type(
            r - lax.convert_element_type(hi, jnp.float64), jnp.float32)
    return hi, lo


def _pow_f64(a, b, **_kw) -> FF:
    if backend() == "tpu":
        return _pow_jnp(a, b)
    af, bf = _as_ff(a), _as_ff(b)
    return FF(*_pow_f64_jit(af.hi, af.lo, bf.hi, bf.lo))


def _pow_fast(a, b, **_kw) -> FF:
    af, bf = _as_ff(a), _as_ff(b)
    a32, b32 = af.hi + af.lo, bf.hi + bf.lo
    return FF.from_f32(jnp.where((a32 < 0) & (b32 != 0),
                                 jnp.float32(jnp.nan),
                                 jnp.power(a32, b32)))


register("pow", "jnp", _pow_jnp, default_for=("*",))
register("pow", "pallas", _pow_pallas)
register("pow", "f64", _pow_f64, default_for=("cpu",))
register("pow", "fast", _pow_fast)


# -- accurate-class softmax / logsumexp (ff.math-powered) --------------------
#
# The existing impls compute their exponentials with the f32 builtin, so
# every term carries ~2^-24 relative error no matter how well the SUM is
# compensated — the Daumas–Da Graça–Defour gap in miniature.  The "ff"
# impls run exp in FF on an exact TwoSum-reduced argument and carry both
# limb planes through the compensated sum, making the f32 output
# correctly-rounded-class.  On TPU the whole chain is still ONE fused
# Pallas kernel (ff_softmax(accurate=True)); elsewhere it is the jnp
# formulation below.  Selected via impl="ff", ff.use, or tuned_accurate.

def _ff_exp_terms(x: Array, axis: int):
    """exp(x - max) in FF with the reduction held exact (TwoSum)."""
    m = jnp.max(x, axis=axis, keepdims=True)
    dh, dl = T.two_sum(x, jnp.broadcast_to(-m, x.shape))
    eh, el = ffmath.exp22(dh, dl, ffmath.CORE)
    return m, FF(eh, el)


def _ff_expsum(e: FF, axis: int, block: int) -> FF:
    hi = compensated.ff_sum_blocked(e.hi, axis=axis, block=block)
    lo = compensated.ff_sum_blocked(e.lo, axis=axis, block=block)
    return core_ff.add22_accurate(hi, lo)


def _softmax_ff(x: Array, axis: int = -1, *, block: int = 256,
                br: int = 256, interpret: Optional[bool] = None, **_kw):
    """Accurate-class softmax: FF exponentials + FF division per element."""
    x = jnp.asarray(x, jnp.float32)
    if backend() == "tpu" and interpret is not True \
            and _last_axis_fusable(x, axis):
        from repro.kernels import ff_fused
        return ff_fused.ff_softmax(x, mode="softmax", br=br, accurate=True,
                                   interpret=False)
    _m, e = _ff_exp_terms(x, axis)
    s = _ff_expsum(e, axis, block)
    sb = FF(jnp.expand_dims(s.hi, axis % x.ndim),
            jnp.expand_dims(s.lo, axis % x.ndim))
    return core_ff.div22(e, FF(jnp.broadcast_to(sb.hi, x.shape),
                               jnp.broadcast_to(sb.lo, x.shape))).hi


def _logsumexp_ff(x: Array, axis: int = -1, *, block: int = 256,
                  br: int = 256, interpret: Optional[bool] = None, **_kw):
    """Accurate-class LSE: FF exponentials, FF log of the FF exp-sum."""
    x = jnp.asarray(x, jnp.float32)
    if backend() == "tpu" and interpret is not True \
            and _last_axis_fusable(x, axis):
        from repro.kernels import ff_fused
        return ff_fused.ff_softmax(x, mode="logsumexp", br=br, accurate=True,
                                   interpret=False)
    m, e = _ff_exp_terms(x, axis)
    s = _ff_expsum(e, axis, block)
    logs = FF(*ffmath.log22(s.hi, s.lo, ffmath.CORE))
    return core_ff.add212(logs, jnp.squeeze(m, axis=axis)).hi


register("softmax", "ff", _softmax_ff)
register("logsumexp", "ff", _logsumexp_ff)


# -- attention (fused FF flash attention; kernels/ff_attention.py) ----------
#
# Impl classes:
#   * ``fast``   — the f32 online softmax that previously lived inline in
#                  ``models.layers.flash_attention``; bitwise the
#                  pre-registry model hot path, and the default on EVERY
#                  backend (the accurate tiers change result bits, so
#                  unlike softmax/logsumexp there is no silent TPU kernel
#                  default — models opt in via ``ff.policy(attention=...)``).
#   * ``ff``     — compensated online softmax: FF scores (TwoProd dot),
#                  ``ff.math.exp`` FF weights, TwoSum-carried FF
#                  numerator/denominator, Div22 normalize (pure jnp).
#   * ``pallas`` — the same recurrence as one fused kernel per
#                  (batch*head, q-block) stripe with the FF accumulators
#                  in VMEM scratch; static masks only, so per-row
#                  ``kv_len`` (ragged serving batches) falls back to ff.
#   * ``f64``    — materialized-score native-f64 oracle (CPU accurate
#                  tier; size-guarded, degrades to ff on TPU).

def _attention_fast(q, k, v, *, interpret=None, **kw):
    from repro.kernels import ff_attention
    return ff_attention.flash_attention_fast(q, k, v, **kw)


def _attention_ff(q, k, v, *, interpret=None, **kw):
    from repro.kernels import ff_attention
    return ff_attention.flash_attention_ff(q, k, v, **kw)


def _attention_pallas(q, k, v, *, interpret=None, block=128, **kw):
    from repro.kernels import ff_attention
    if kw.get("kv_len") is not None:
        _fallback_warn("pallas", "attention",
                       "per-row kv_len (ragged batch) needs dynamic masks "
                       "the kernel's static grid cannot express")
        return ff_attention.flash_attention_ff(q, k, v, block=block, **kw)
    kw.pop("kv_len", None)
    return ff_attention.flash_attention_pallas(
        q, k, v, interpret=_interpret(interpret), **kw)


def _attention_f64(q, k, v, *, causal=True, q_offset=0, kv_len=None,
                   scale=None, return_ff=False, **kw):
    from repro.kernels import ff_attention
    if backend() != "tpu":
        B, Sq, H = q.shape[0], q.shape[1], q.shape[2]
        if B * H * Sq * k.shape[1] <= (1 << 24):
            return ff_attention.attention_f64(
                q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len,
                scale=scale, return_ff=return_ff)
        _fallback_warn("f64", "attention",
                       "materialized f64 score plane exceeds the size guard")
    return ff_attention.flash_attention_ff(
        q, k, v, causal=causal, q_offset=q_offset, kv_len=kv_len,
        scale=scale, return_ff=return_ff)


register("attention", "fast", _attention_fast, default_for=("*",))
register("attention", "ff", _attention_ff)
register("attention", "pallas", _attention_pallas)
register("attention", "f64", _attention_f64)
