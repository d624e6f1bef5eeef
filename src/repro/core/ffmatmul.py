"""FF matrix multiplication — the MXU adaptation of the paper's Mul12/Add22.

The 2006 paper ran float-float *element-wise* in fragment shaders.  On TPU the
compute workhorse is the MXU (128x128 systolic matmul), which does NOT do
exact f32 products (f32 matmuls are composed of bf16 passes unless
``precision=HIGHEST`` forces 6-pass, and even then K-accumulation rounds).
Porting the paper mechanically (scalar Mul12 chains) would leave the MXU idle.

Instead we restructure (DESIGN_ozaki.md):

* ``matmul_compensated``  — blocked K: each K-block is a hardware matmul
  (``precision=HIGHEST``), blocks are combined with Add22.  Accumulation error
  drops from O(K)·2^-24 to O(block)·2^-24 + O(K/block)·2^-44: the compensated
  cascade of the paper applied at *block* granularity instead of element
  granularity.  This is the fast production path (used for FF logits).

* ``matmul_split``        — Dekker-split operands (12-bit halves) make every
  elementwise product exact; the three significant cross terms are separate
  MXU matmuls whose results are combined in FF.  Product error is eliminated
  entirely; remaining error is K-accumulation only.  Composable with blocked K.

* ``matmul_dot2``         — per-element Dot2 (two_prod + cascaded two_sum),
  block-vectorized over K-chunks.  Full ~2^-44 quality; VPU-only.  This is
  the oracle-grade path, also realized as a Pallas kernel in
  ``repro.kernels.ff_matmul``.

* ``matmul_ozaki``        — exponent-aligned slicing: ALL slice-pair products
  AND their in-chunk K-accumulation are exact in hardware matmuls.  Paper
  accuracy (~2^-46) at matrix-unit speed; the fast member of the accurate
  tier on f64-less backends.  See ``ozaki_params`` for the slicing rules.

* ``matmul_f64``          — native double-precision GEMM rounded to FF.  The
  paper emulates f64 on f32-only hardware; on backends whose hardware HAS
  f64 (CPU, most GPUs) the fastest route to paper-quality accuracy is one
  dgemm.  The accurate-tier dispatch default on such backends.

All take f32 (M,K) x (K,N) and return FF (M,N).
"""

from __future__ import annotations

import math
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import transforms as T
from repro.core.ff import FF, add22

Array = jnp.ndarray


def _dot_f32(a: Array, b: Array) -> Array:
    """Hardware matmul with forced f32-faithful passes (paper §5 lesson:
    never let the toolchain silently lower your precision)."""
    return lax.dot(a, b, precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def matmul_compensated(a: Array, b: Array, block_k: int = 512) -> FF:
    """Blocked-K FF-accumulated matmul (fast path).

    hypothesis: with K-blocks of size Bk, per-block error ~ Bk * 2^-24 * |.|
    and the FF combine contributes ~ (K/Bk) * 2^-44; Bk=512 balances both for
    K up to ~1M while keeping the MXU busy >99% of flops.
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    nb = max(1, -(-K // block_k))
    if nb == 1:
        # single K-block: the scan degenerates to add22(zeros, FF(p, 0)),
        # which is bitwise FF(p, 0) (TwoSum/Fast2Sum with exact zeros) —
        # skip the fold machinery AND the zero-pad (padding only fed the
        # block reshape; the unpadded GEMM is the same one-f32-GEMM error
        # class, though K < block_k callers may see different last-ulp
        # rounding than the padded formulation produced).  Measured ~40%
        # of the whole call at (4096, 512, 4096); this is every
        # K <= block_k call site, and in particular the K-split mesh
        # shard, whose combine renormalizes anyway.
        return FF(_dot_f32(a, b), jnp.zeros((M, N), jnp.float32))
    pad = nb * block_k - K
    if pad:
        a = jnp.concatenate([a, jnp.zeros((M, pad), jnp.float32)], axis=1)
        b = jnp.concatenate([b, jnp.zeros((pad, N), jnp.float32)], axis=0)
    a3 = a.reshape(M, nb, block_k).transpose(1, 0, 2)   # (nb, M, Bk)
    b3 = b.reshape(nb, block_k, N)                      # (nb, Bk, N)

    def body(acc: FF, ab):
        ai, bi = ab
        p = _dot_f32(ai, bi)
        return add22(acc, FF.from_f32(p)), None

    acc0 = FF.zeros((M, N))
    acc, _ = lax.scan(body, acc0, (a3, b3))
    return acc


def matmul_split(a: Array, b: Array, block_k: Optional[int] = 512) -> FF:
    """Split-operand FF matmul (exact products; TPU-native Mul12).

    a = a_hi + a_lo, b = b_hi + b_lo with 12-bit halves (Dekker split), so
    a_hi*b_hi, a_hi*b_lo, a_lo*b_hi, a_lo*b_lo are all exact f32 products.
    Each cross-term matmul still rounds in its K-accumulation; the four
    partial matrices are combined with Add22.  Composed with blocked K the
    same way as ``matmul_compensated``.
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    M, K = a.shape
    _, N = b.shape
    a_hi, a_lo = T.split(a)
    b_hi, b_lo = T.split(b)

    def partials(ai_hi, ai_lo, bi_hi, bi_lo):
        # dominant term first; combine low-order terms in f32 (they are
        # each <= 2^-12 of the dominant term; their own rounding is <=2^-48).
        hh = _dot_f32(ai_hi, bi_hi)
        hl = _dot_f32(ai_hi, bi_lo)
        lh = _dot_f32(ai_lo, bi_hi)
        ll = _dot_f32(ai_lo, bi_lo)
        t = add22(FF.from_f32(hl), FF.from_f32(lh))
        t = add22(t, FF.from_f32(ll))
        return add22(FF.from_f32(hh), t)

    if block_k is None or block_k >= K:
        return partials(a_hi, a_lo, b_hi, b_lo)

    nb = -(-K // block_k)
    pad = nb * block_k - K

    def padk(x, axis):
        if not pad:
            return x
        w = [(0, 0)] * x.ndim
        w[axis] = (0, pad)
        return jnp.pad(x, w)

    ah = padk(a_hi, 1).reshape(M, nb, block_k).transpose(1, 0, 2)
    al = padk(a_lo, 1).reshape(M, nb, block_k).transpose(1, 0, 2)
    bh = padk(b_hi, 0).reshape(nb, block_k, N)
    bl = padk(b_lo, 0).reshape(nb, block_k, N)

    def body(acc: FF, abi):
        ahi, ali, bhi, bli = abi
        return add22(acc, partials(ahi, ali, bhi, bli)), None

    acc0 = FF.zeros((M, N))
    acc, _ = lax.scan(body, acc0, (ah, al, bh, bl))
    return acc


def matmul_dot2(a: Array, b: Array, chunk: int = 32) -> FF:
    """Per-element Dot2 matmul: full float-float quality (~2^-44 relative).

    Block-vectorized: K is processed in ``chunk``-wide slabs.  Each slab
    forms the (M, chunk, N) outer products exactly with a batched two_prod
    (Mul12) and reduces them with a pairwise-compensated two_sum tree; the
    slab results feed a Dot3-quality cascade across slabs.  Versus the old
    one-rank-1-update-per-k ``lax.scan``, the sequential depth drops from K
    to K/chunk with identical error structure: every product is exact, every
    rounding is captured in a compensation term.

    O(M·chunk·N) live state, VPU-only — use for small, numerically critical
    matmuls (router logits, final LM-head rows under study) and as the oracle
    for the Pallas kernels.
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    M, K = a.shape
    _, N = b.shape
    chunk = max(1, min(chunk, K))
    nb = -(-K // chunk)
    pad = nb * chunk - K
    if pad:
        a = jnp.concatenate([a, jnp.zeros((M, pad), jnp.float32)], axis=1)
        b = jnp.concatenate([b, jnp.zeros((pad, N), jnp.float32)], axis=0)
    a3 = a.reshape(M, nb, chunk).transpose(1, 0, 2)   # (nb, M, c)
    b3 = b.reshape(nb, chunk, N)                      # (nb, c, N)

    def slab(ai, bi):
        """Exact products + pairwise-compensated reduction of one K-slab.

        Returns (sum, err) with sum + err == the slab's exact dot to ~2^-48.
        """
        p, pe = T.two_prod(ai[:, :, None], bi[None, :, :])   # (M, c, N) exact
        # product error terms are <= 2^-24 of their products; a plain sum
        # only rounds at ~2^-48 of the slab total.  The tree collects
        # every two_sum rounding into the same compensation term.
        return T.pairwise_sum_compensated(p, 1, jnp.sum(pe, axis=1))

    def body(carry, ab):
        s, c, cc = carry
        ai, bi = ab
        ps, pe = slab(ai, bi)
        s2, se = T.two_sum(s, ps)
        c2, ce = T.two_sum(c, se + pe)    # Dot3-quality cascade across slabs
        return (s2, c2, cc + ce), None

    z = jnp.zeros((M, N), jnp.float32)
    (s, c, cc), _ = lax.scan(body, (z, z, z), (a3, b3))
    rh, rl = T.fast_two_sum(s, c + cc)
    return FF(rh, rl)


# ---------------------------------------------------------------------------
# Ozaki-scheme FF matmul
# ---------------------------------------------------------------------------

def ozaki_params(K: int, slices: int = 0, beta: int = 0,
                 block_k: int = 0) -> Tuple[int, int, int, int]:
    """Slicing parameters for ``matmul_ozaki`` — the explicit heuristic.

    Exactness budget: a slice holds at most ``2^(beta-1)`` quanta of its
    per-(row, K-chunk) granularity (1.5*sigma extraction keeps r+sigma in one
    binade, so round-to-nearest never spills an extra bit).  A slice-pair
    product is then <= ``2^(2*beta-2)`` quanta, and its sum over a K-chunk of
    ``bk`` terms stays below f32's exact-integer ceiling 2^24 iff

        2*beta + ceil(log2 bk) <= 26.

    Heuristic defaults (overridable per argument):
      * ``block_k = min(K, 1024)`` — the largest chunk that still admits
        beta = 8, i.e. the fewest GEMM passes (slices^2 grows ~(24/beta)^2
        while chunking overhead grows with K/block_k).
      * ``beta = (26 - ceil(log2 block_k)) // 2`` — widest exact slice.
      * ``slices = ceil(24 / beta)`` — cover the full f32 significand below
        the per-(row, chunk) max exponent; everything deeper is handled by
        the f32 residual-correction GEMM at ~2^-24 * 2^-24 relative.
        Short contractions (K <= 512) get one extra margin slice when
        coverage would be under 27 bits: the residual GEMM's rounding lacks
        the ~sqrt(K) cancellation discount there, and small-K slice GEMMs
        are cheap.  Operands whose within-row exponent RANGE is wide
        (>~2^20 spread) push significance below the sliced horizon — pass a
        larger ``slices`` (see ``suggest_slices``) to extend coverage by
        beta bits per slice.

    Pairs with ``beta*(i+j) > 50`` fall below FF precision (2^-50 relative
    to the leading pair even before the condition-number discount) and are
    skipped; ``max_order`` encodes that rule.

    Returns ``(slices, beta, block_k, max_order)``.
    """
    K = max(int(K), 1)
    bk = int(block_k) or min(K, 1024)
    bk = min(bk, K)
    t = math.ceil(math.log2(max(bk, 2)))
    beta = int(beta) or max(2, (26 - t) // 2)
    if 2 * beta + t > 26:
        raise ValueError(
            f"ozaki exactness budget violated: 2*beta + ceil(log2 block_k) "
            f"= {2 * beta + t} > 26 (beta={beta}, block_k={bk}); slice-pair "
            f"block sums would round inside the 'exact' GEMMs — lower beta "
            f"or block_k")
    n = int(slices)
    if not n:
        n = max(2, -(-24 // beta))
        if n * beta < 27 and K <= 512:
            n += 1                      # small-K margin slice (see above)
    max_order = max(1, 50 // beta)
    return n, beta, bk, max_order


def suggest_slices(a, b, block_k: int = 0) -> int:
    """Host-side slice-count pick from the operands' exponent range.

    Eager-only helper (inspects concrete values; do not call under jit).
    Measures the within-row / within-column exponent spread that the
    row-aligned slicing must bridge and widens coverage accordingly:
    every extra ``beta`` bits of spread costs one extra slice.
    """
    a = np.asarray(a)
    b = np.asarray(b)
    K = a.shape[-1]
    n, beta, bk, _ = ozaki_params(K, block_k=block_k)

    def spread(x, axis):
        ax = np.abs(x)
        hi = ax.max(axis=axis)
        tiny = np.finfo(np.float32).tiny
        lo = np.where(ax > 0, ax, np.inf).min(axis=axis)
        s = np.log2(np.maximum(hi, tiny)) - np.log2(np.maximum(lo, tiny))
        s = s[np.isfinite(s)]
        return float(np.median(s)) if s.size else 0.0

    extra = max(0.0, max(spread(a, -1), spread(b, -2)) - 4.0)
    return min(n + int(math.ceil(extra / beta)), max(n, 50 // beta))


def extract_slices(x: Array, axis: int, n: int, beta: int
                   ) -> Tuple[List[Array], Array]:
    """n exponent-aligned slices of <= beta bits each, plus the residual.

    sigma_i = 1.5 * 2^(e + 24 - beta*(i+1)) with e = ceil(log2 max|x|) along
    ``axis``:  r + sigma_i stays inside sigma_i's binade for either sign of
    r, so ``(r + sigma) - sigma`` rounds r to the slice granularity
    2^(e+1-beta*(i+1)) *uniformly* — each slice is at most 2^(beta-1) quanta
    in magnitude (Ozaki et al. 2012; the 1.5 factor is what makes the
    2*beta + log2(K) <= 26 exactness budget hold for signed data, not just
    in expectation).  Each ``r - w`` is exact (aligned granularities).
    """
    mu = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    e = jnp.ceil(jnp.log2(jnp.maximum(mu, jnp.float32(1e-38))))
    # Two edges guard the alignment exponent, both of which would silently
    # break the 2*beta + log2(bk) <= 26 exactness budget by doubling every
    # slice's quanta allowance:
    #  * f32 log2 is not correctly rounded — for mu just ABOVE a power of
    #    two it can land exactly on the integer, so ceil underestimates by
    #    1; one compare against an exact 2^e repairs it (2^e >= mu after).
    #  * jnp.exp2 itself is polynomial-approximated on XLA:CPU (inexact at
    #    most integer exponents under the EFT-safe ISA pin!), so both the
    #    repair compare and the sigma grid must build their powers of two
    #    with ldexp, which is exact by construction.
    ie = e.astype(jnp.int32)
    ie = jnp.where(jnp.ldexp(jnp.float32(1), ie) < mu, ie + 1, ie)
    parts = []
    r = x
    for i in range(n):
        sigma = jnp.float32(1.5) * jnp.ldexp(jnp.float32(1),
                                             ie + (24 - beta * (i + 1)))
        w = (r + sigma) - sigma
        parts.append(w)
        r = r - w
    return parts, r


def matmul_ozaki(a: Array, b: Array, slices: int = 0, *, beta: int = 0,
                 block_k: int = 0) -> FF:
    """Ozaki-scheme FF matmul: error-free slice products with error-free
    in-chunk accumulation — paper-quality accuracy at matrix-unit speed.

    BEYOND-PAPER (DESIGN_ozaki.md): the 2006 paper made single *products*
    exact (Mul12).  For matmuls the accumulation over K also has to be
    exact.  Slice each operand into ``n`` exponent-aligned pieces of
    ``beta`` significand bits (see ``ozaki_params``/``extract_slices``) so
    every slice-pair product summed over a K-chunk still fits f32's
    significand: each hardware matmul is EXACT.

    The n^2 pair products for ALL chunks are issued as ONE batched stacked
    GEMM — slices concatenated along M and N, chunks batched:

        (nc, n*M, bk) @ (nc, bk, n*N)   ==   einsum('cik,ckj->cij')

    which keeps the matrix unit saturated instead of n^2 * nc separate
    dispatches (the old Python-level slice loop).  Two batched per-chunk
    f32 residual GEMMs (operands already live in the chunked layout — no
    concat/transpose traffic) catch everything below the sliced 24 bits:
    a@b = sliced-pairs + ra@b + a@rb - ra@rb, where the ra@rb term
    (~2^-48 relative, below FF precision) is deliberately dropped.  Pair
    and residual blocks are then folded with ONE vectorized
    pairwise-compensated reduction over the stacked block axis: the same
    error structure as the former sequential Add22 cascade (every two_sum
    rounding lands in the compensation term) at log2(#blocks) vectorized
    passes over (M, N) instead of ~n^2*nc serial sweeps.

    Total error ~2^-46 relative to |A||B| for operands with moderate
    within-row exponent range; n^2+2 matmul-unit flops vs dot2's K VPU
    steps.  ``slices=0`` picks the documented heuristic.
    """
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    M, K = a.shape
    K2, N = b.shape
    assert K == K2, (a.shape, b.shape)
    n, beta, bk, max_order = ozaki_params(K, slices=slices, beta=beta,
                                          block_k=block_k)
    nc = -(-K // bk)
    pad = nc * bk - K
    a_p, b_p = a, b
    if pad:
        a_p = jnp.concatenate([a, jnp.zeros((M, pad), jnp.float32)], axis=1)
        b_p = jnp.concatenate([b, jnp.zeros((pad, N), jnp.float32)], axis=0)
    Kp = nc * bk

    a3 = a_p.reshape(M, nc, bk).transpose(1, 0, 2)        # (nc, M, bk)
    b3 = b_p.reshape(nc, bk, N)                           # (nc, bk, N)
    pa, ra3 = extract_slices(a3, 2, n, beta)
    pb, rb3 = extract_slices(b3, 1, n, beta)

    As = jnp.concatenate(pa, axis=1)                      # (nc, n*M, bk)
    Bs = jnp.concatenate(pb, axis=2)                      # (nc, bk, n*N)
    G = jnp.matmul(As, Bs, precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    G = G.reshape(nc, n, M, n, N)                         # exact pair blocks

    # residual correction, batched per chunk:
    #   a@b - sum(pairs) == ra@b + (a-ra)@rb == ra@b + a@rb - ra@rb.
    # We issue ra@b and a@rb (a3/b3 are already materialized, so no extra
    # elementwise pass to form a-ra) and drop the over-counted ra@rb: both
    # factors sit ~2^-24 below their operand rows, so the term is ~2^-48
    # relative — below FF precision.
    res1 = jnp.matmul(ra3, b3, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)
    res2 = jnp.matmul(a3, rb3, precision=lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)

    # fold: one vectorized pairwise-compensated reduction over every kept
    # pair block and residual block; negligible pairs (order > max_order,
    # below FF precision even before the condition-number discount) are
    # dropped before stacking
    keep = [i * n + j for i in range(n) for j in range(n)
            if i + j <= max_order]
    blocks = G.transpose(1, 3, 0, 2, 4).reshape(n * n, nc, M, N)
    if len(keep) < n * n:
        blocks = blocks[np.asarray(keep)]
    blocks = jnp.concatenate([blocks.reshape(-1, M, N), res1, res2], axis=0)
    s, e = T.pairwise_sum_compensated(blocks, 0)
    rh, rl = T.two_sum(s, e)
    return FF(rh, rl)


# ---------------------------------------------------------------------------
# native-f64 reference matmul (backends whose hardware has f64)
# ---------------------------------------------------------------------------

def matmul_f64(a: Array, b: Array) -> FF:
    """Native double-precision GEMM, rounded to FF.

    The paper's premise is emulating f64 on f32-only hardware; the dispatch
    corollary is that on backends whose hardware HAS f64 (CPU, most GPUs)
    the fastest paper-quality path is a single native dgemm: every f32
    product is EXACT in f64 (24+24 < 53 significand bits) and the
    K-accumulation rounds at 2^-53 per step, so the FF-rounded result lands
    at ~2^-48 relative — comfortably inside the accurate tier at a small
    multiple of the naive f32 GEMM (vs ~10x+ for the best pure-f32 scheme).

    ``jax.enable_x64`` scopes the wide-dtype escape to this
    trace only: it works eagerly, inside an outer f32 ``jit``, and under
    ``vmap``/``grad``, without flipping the global x64 flag.  The body
    lives behind its own ``jit`` boundary on purpose: ``custom_vjp``'s
    lowering canonicalizes a sub-jaxpr's result types under the ambient
    (x64-off) config while leaving its f64 internals alone, which rejects
    an inlined mixed-dtype body — an opaque pjit call sidesteps that.
    TPU has no f64 unit — the dispatch wrapper substitutes the fused
    Ozaki kernel there (``repro.ff.dispatch._mm_f64``).
    """
    return FF(*_matmul_f64_jit(jnp.asarray(a, jnp.float32),
                               jnp.asarray(b, jnp.float32)))


@jax.jit
def _matmul_f64_jit(a: Array, b: Array) -> Tuple[Array, Array]:
    with jax.enable_x64(True):
        r = lax.dot(lax.convert_element_type(a, jnp.float64),
                    lax.convert_element_type(b, jnp.float64),
                    precision=lax.Precision.HIGHEST)
        hi = lax.convert_element_type(r, jnp.float32)
        lo = lax.convert_element_type(
            r - lax.convert_element_type(hi, jnp.float64), jnp.float32)
    return hi, lo
