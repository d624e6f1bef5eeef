"""Pallas TPU kernels for FF matrix multiplication.

Three kernels, mirroring ``repro.core.ffmatmul`` (DESIGN_ozaki.md):

* ``ff_matmul``  (production): hybrid MXU/VPU.  Grid (M/bm, N/bn, K/bk) with
  K innermost; each step issues one MXU block-matmul (f32, HIGHEST) and folds
  it into an FF accumulator held in VMEM scratch with Add22 (VPU).  This is
  the paper's compensated-accumulation idea applied at MXU-block granularity:
  >99% of flops stay on the MXU, accumulation error drops from O(K)u to
  O(bk)u + O(K/bk)*2^-44.

* ``ff_matmul_ozaki`` (accurate tier): fused Ozaki-slice matmul.  Operands
  are pre-split (jnp, ``core.ffmatmul.extract_slices``) into ``n``
  exponent-aligned slices whose pairwise block products are EXACT f32
  matmuls (2*beta + log2(bk) <= 26).  The kernel runs grid
  (M/bm, N/bn, K/bk, P) with the slice-pair index P innermost: each step is
  one MXU block-matmul of slice pair (si[p], sj[p]) folded into an FF
  accumulator in VMEM scratch.  The pair tables arrive via scalar prefetch,
  already sorted largest-order-first and FILTERED — pairs below FF precision
  (beta*(i+j) > 50) are never scheduled (negligible-pair skipping).  A
  K-doubled f32 residual GEMM (wrapper, jnp) corrects everything below the
  sliced significand.  Paper-quality ~2^-46 at MXU speed.

* ``ff_matmul_dot2`` (paper-faithful): every elementwise product is made
  exact with Mul12 (Dekker split on the VPU) and accumulated with a TwoSum
  cascade — the full float-float quality of the paper, at VPU cost.
  Block-vectorized: K advances ``vec`` lanes at a time with a batched
  two_prod and a pairwise-compensated tree reduction, so the sequential
  depth per (bm, bn) block is bk/vec instead of bk.

VMEM budget at hybrid defaults (bm=bn=256, bk=512):
  A tile 256*512*4 = 512 KiB, B tile 512*256*4 = 512 KiB,
  acc scratch 2 * 256*256*4 = 512 KiB, out 2 * 256 KiB  ->  ~1.8 MiB << 16 MiB.
Ozaki defaults (bm=bn=128, bk=512, n=3): A/B tiles 256 KiB each (one slice
pair at a time), acc + out 256 KiB -> ~0.8 MiB.  Dot2 (bm=bn=128, bk=128,
vec=8): the (bm, vec, bn) two_prod intermediates are 512 KiB each, ~2.5 MiB
total.  MXU alignment: all block dims are multiples of 128.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import eft

Array = jnp.ndarray


def _block_dot(a, b):
    # f32 MXU matmul; HIGHEST = 6-pass bf16 (f32-faithful) on TPU.
    return lax.dot(a, b, precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# Hybrid kernel
# ---------------------------------------------------------------------------

def _ff_matmul_kernel(a_ref, b_ref, oh_ref, ol_ref, acc_hi, acc_lo, *, nk: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_hi[...] = jnp.zeros_like(acc_hi)
        acc_lo[...] = jnp.zeros_like(acc_lo)

    p = _block_dot(a_ref[...], b_ref[...])
    # add22(acc, (p, 0)) — fold the block product into the FF accumulator
    sh, sl = eft.two_sum(acc_hi[...], p)
    v = sl + acc_lo[...]
    rh, rl = eft.fast_two_sum(sh, v)
    acc_hi[...] = rh
    acc_lo[...] = rl

    @pl.when(k == nk - 1)
    def _flush():
        oh_ref[...] = acc_hi[...]
        ol_ref[...] = acc_lo[...]


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def ff_matmul(a: Array, b: Array, *, bm: int = 256, bn: int = 256,
              bk: int = 512, interpret: bool = False) -> Tuple[Array, Array]:
    """FF(M,N) = a(M,K) @ b(K,N), hybrid MXU + compensated accumulation.

    Returns (hi, lo) limbs.
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    Mp, Kp = a.shape
    _, Np = b.shape
    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)
    out = jax.ShapeDtypeStruct((Mp, Np), jnp.float32)
    oh, ol = pl.pallas_call(
        functools.partial(_ff_matmul_kernel, nk=nk),
        out_shape=(out, out),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=(
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        interpret=interpret,
    )(a, b)
    return oh[:M, :N], ol[:M, :N]


# ---------------------------------------------------------------------------
# Fused Ozaki-slice kernel
# ---------------------------------------------------------------------------

def _ff_matmul_ozaki_kernel(si_ref, sj_ref, a_ref, b_ref, oh_ref, ol_ref,
                            acc_hi, acc_lo, *, nk: int, npairs: int):
    k = pl.program_id(2)
    p = pl.program_id(3)

    @pl.when((k == 0) & (p == 0))
    def _init():
        acc_hi[...] = jnp.zeros_like(acc_hi)
        acc_lo[...] = jnp.zeros_like(acc_lo)

    # one EXACT slice-pair block product on the MXU
    prod = _block_dot(a_ref[0], b_ref[0])
    sh, sl = eft.two_sum(acc_hi[...], prod)
    v = sl + acc_lo[...]
    rh, rl = eft.fast_two_sum(sh, v)
    acc_hi[...] = rh
    acc_lo[...] = rl

    @pl.when((k == nk - 1) & (p == npairs - 1))
    def _flush():
        oh_ref[...] = acc_hi[...]
        ol_ref[...] = acc_lo[...]


@functools.partial(jax.jit,
                   static_argnames=("slices", "beta", "bm", "bn", "bk",
                                    "interpret"))
def ff_matmul_ozaki(a: Array, b: Array, *, slices: int = 0, beta: int = 0,
                    bm: int = 128, bn: int = 128, bk: int = 512,
                    interpret: bool = False) -> Tuple[Array, Array]:
    """Fused Ozaki-slice FF matmul: exact slice-pair MXU block products,
    FF-accumulated in VMEM, slice-pair as the innermost grid dimension.

    Slicing (jnp prologue) is exponent-aligned per (row, full K); the
    exactness budget therefore has to hold per K-*block*:
    2*beta + log2(bk) <= 26 (see ``core.ffmatmul.ozaki_params``).  Pairs
    with beta*(i+j) > 50 are dropped before scheduling — the scalar-prefetch
    pair tables are the skip list.  Returns (hi, lo) limbs.
    """
    from repro.core import ffmatmul as core_mm

    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    n, beta, bk, max_order = core_mm.ozaki_params(K, slices=slices, beta=beta,
                                                  block_k=bk)
    pairs = sorted(
        ((i, j) for i in range(n) for j in range(n) if i + j <= max_order),
        key=lambda q: (q[0] + q[1], q[0]))
    npairs = len(pairs)
    si = jnp.asarray([q[0] for q in pairs], jnp.int32)
    sj = jnp.asarray([q[1] for q in pairs], jnp.int32)

    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    Mp, Kp = a.shape
    _, Np = b.shape

    # slices aligned over the full (padded) K — block sums stay exact by the
    # bk budget above; the kernel accumulates across K-blocks in FF.
    pa, ra = core_mm.extract_slices(a, 1, n, beta)
    pb, rb = core_mm.extract_slices(b, 0, n, beta)
    As = jnp.stack(pa)                       # (n, Mp, Kp)
    Bs = jnp.stack(pb)                       # (n, Kp, Np)

    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk, npairs)
    out = jax.ShapeDtypeStruct((Mp, Np), jnp.float32)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda i, j, k, p, si, sj: (si[p], i, k)),
            pl.BlockSpec((1, bk, bn), lambda i, j, k, p, si, sj: (sj[p], k, j)),
        ],
        out_specs=(
            pl.BlockSpec((bm, bn), lambda i, j, k, p, si, sj: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k, p, si, sj: (i, j)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
    )
    oh, ol = pl.pallas_call(
        functools.partial(_ff_matmul_ozaki_kernel, nk=nk, npairs=npairs),
        grid_spec=grid_spec,
        out_shape=(out, out),
        interpret=interpret,
    )(si, sj, As, Bs)

    # residual correction: a@b - sum(pairs) == ra@b + (a-ra)@rb, one
    # K-doubled f32 GEMM (everything below the sliced significand).
    res = _block_dot(jnp.concatenate([ra, a - ra], axis=1),
                     jnp.concatenate([b, rb], axis=0))
    sh, sl = eft.two_sum(oh, res)
    rh, rl = eft.fast_two_sum(sh, sl + ol)
    return rh[:M, :N], rl[:M, :N]


# ---------------------------------------------------------------------------
# Paper-faithful Dot3 kernel (block-vectorized)
# ---------------------------------------------------------------------------

def _ff_matmul_dot2_kernel(a_ref, b_ref, oh_ref, ol_ref, s_acc, c_acc, cc_acc,
                           *, nk: int, bk: int, vec: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        s_acc[...] = jnp.zeros_like(s_acc)
        c_acc[...] = jnp.zeros_like(c_acc)
        cc_acc[...] = jnp.zeros_like(cc_acc)

    a = a_ref[...]          # (bm, bk)
    b = b_ref[...]          # (bk, bn)
    s, c, cc = s_acc[...], c_acc[...], cc_acc[...]
    # static slabs, unrolled: Mosaic cannot lower a value dynamic_slice
    for j in range(bk // vec):
        aj = a[:, j * vec:(j + 1) * vec]                         # (bm, vec)
        bj = b[j * vec:(j + 1) * vec, :]                         # (vec, bn)
        # batched Mul12: all vec outer products of this slab, exactly
        p, pe = eft.two_prod(aj[:, :, None], bj[None, :, :])     # (bm,vec,bn)
        # pairwise-compensated tree reduction over the slab axis
        slab, err = eft.pairwise_sum_compensated(
            p, axis=1, err=jnp.sum(pe, axis=1))
        s, se = eft.two_sum(s, slab)
        c, ce = eft.two_sum(c, se + err)
        cc = cc + ce
    s_acc[...] = s
    c_acc[...] = c
    cc_acc[...] = cc

    @pl.when(k == nk - 1)
    def _flush():
        rh, rl = eft.fast_two_sum(s_acc[...], c_acc[...] + cc_acc[...])
        oh_ref[...] = rh
        ol_ref[...] = rl


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "vec",
                                             "interpret"))
def ff_matmul_dot2(a: Array, b: Array, *, bm: int = 128, bn: int = 128,
                   bk: int = 128, vec: int = 8,
                   interpret: bool = False) -> Tuple[Array, Array]:
    """Paper-faithful FF matmul: exact per-element products (Mul12) +
    TwoSum cascade (Dot3 quality).  VPU-only; block-vectorized so each
    (bm, bn) tile advances K in ``vec``-wide slabs (O(K/vec) sequential
    steps) instead of rank-1 updates."""
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    vec = max(1, min(vec, bk))
    while bk % vec:
        vec -= 1     # largest divisor <= vec keeps the slab win for ragged bk
    pm, pn, pk = (-M) % bm, (-N) % bn, (-K) % bk
    if pm or pk:
        a = jnp.pad(a, ((0, pm), (0, pk)))
    if pk or pn:
        b = jnp.pad(b, ((0, pk), (0, pn)))
    Mp, Kp = a.shape
    _, Np = b.shape
    nk = Kp // bk
    grid = (Mp // bm, Np // bn, nk)
    out = jax.ShapeDtypeStruct((Mp, Np), jnp.float32)
    oh, ol = pl.pallas_call(
        functools.partial(_ff_matmul_dot2_kernel, nk=nk, bk=bk, vec=vec),
        out_shape=(out, out),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=(
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        ),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, bn), jnp.float32),
        ],
        interpret=interpret,
    )(a, b)
    return oh[:M, :N], ol[:M, :N]
