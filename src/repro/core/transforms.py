"""Error-free transformations (EFTs) — the mathematical core of the paper.

Implements, in pure JAX f32, the primitives of Da Graça & Defour 2006:

  * ``two_sum``       — Add12 / Knuth TwoSum (branch-free, 6 flops).
  * ``fast_two_sum``  — Dekker Fast2Sum (3 flops, requires |a| >= |b|).
  * ``split``         — Dekker splitting at s=12 for p=24 (f32).
  * ``two_prod``      — Mul12 / Dekker product via ``split`` (no FMA assumed,
                        exactly as the paper: GPUs of 2006 had no FMA, and the
                        TPU VPU has no f32 scalar FMA primitive exposed either).

Hardware-assumption note (paper §3/§4): the paper proves these correct under
*faithful rounding + a guard bit*.  XLA:CPU and XLA:TPU f32 adds/muls are IEEE
round-to-nearest — strictly stronger, so every proof carries over.

XLA-safety note (paper §5): the paper had to hand-patch DirectX shaders
because the compiler rewrote ``(a ⊕ b) ⊖ a → b``.  XLA does **not** perform
unsafe floating-point reassociation on f32, so these sequences are preserved
under ``jax.jit``.  The one genuine hazard on TPU is *matmul* precision
(bf16 passes by default) — handled in ``ffmatmul.py`` via
``precision=HIGHEST`` / split-operand passes, never here.

Everything here is shape-polymorphic and dtype-strict: inputs must be f32
(asserted), outputs are f32.

Domain note (matches paper §6.1): XLA (like 2006 GPUs) flushes subnormals to
zero, so EFT exactness requires every intermediate to stay normal.  For
``split``/``two_prod`` that means |x| in [2^-100, 2^115] (the split residue is
up to 2^-12 smaller than x; products of halves must not underflow).  The
paper excludes denormal inputs from its accuracy study for the same reason.
"""

from __future__ import annotations

from typing import Tuple

import jax.numpy as jnp
from jax import lax

Array = jnp.ndarray


def _opaque(x: Array) -> Array:
    """Optimization barrier: prevents the backend from contracting a rounded
    product into a later add (``s + a*b -> fma(a,b,s)``), which silently
    changes ``fl(a*b)`` at its other use sites and breaks EFT exactness.

    This is the paper §5 problem reborn: they hand-edited DirectX fragment
    programs; we pin the rounded value with ``lax.optimization_barrier``.
    XLA:TPU does not contract f32 mul+add on the VPU, but XLA:CPU (the
    validation backend) does — measured in tests/test_core_ff.py.
    """
    return lax.optimization_barrier(x)

# Dekker split point for binary32: p = 24, s = 12  →  2^s + 1.
_SPLIT_CONST = 4097.0  # == 2**12 + 1
# |a| above this can overflow inside split's (2^s+1)*a product (f32 max ≈
# 2^128; 2^128 / 2^13 ≈ 2^115).  ``split_safe`` rescales above it.
_SPLIT_OVERFLOW_THRESH = 2.0**115


def _f32(x: Array) -> Array:
    x = jnp.asarray(x)
    if x.dtype != jnp.float32:
        raise TypeError(f"float-float EFTs are defined for f32, got {x.dtype}")
    return x


def two_sum(a: Array, b: Array) -> Tuple[Array, Array]:
    """Add12 (Knuth).  Returns (s, r) with s = fl(a+b) and s + r == a + b exactly.

    Branch-free 6-operation variant — the paper's preferred form (§4): GPU
    stream processors (and TPU VPU lanes) execute both sides of a branch, so
    3 extra flops beat one test.
    """
    a, b = _f32(a), _f32(b)
    s = a + b
    bb = s - a
    err_b = b - bb          # error on b's side
    err_a = a - (s - bb)    # error on a's side
    return s, err_a + err_b


def fast_two_sum(a: Array, b: Array) -> Tuple[Array, Array]:
    """Dekker Fast2Sum: 3 flops; exact only when |a| >= |b| (or a == 0).

    Used to renormalize results whose magnitude ordering is known
    (e.g. after Add22/Mul22 where |hi| dominates by construction).
    """
    a, b = _f32(a), _f32(b)
    s = a + b
    r = b - (s - a)
    return s, r


def split(a: Array) -> Tuple[Array, Array]:
    """Dekker SPLIT (paper Theorem 3), s = 12 for binary32.

    Returns (a_hi, a_lo), non-overlapping, a_hi + a_lo == a exactly,
    each half fitting in <= 12 significand bits, so products of halves are
    exact in f32.  No overflow guard — see ``split_safe``.
    """
    a = _f32(a)
    # _opaque: without it the backend may contract ``c - a`` into
    # ``fma(4097, a, -a)`` — computing 4096*a exactly and skipping the
    # rounding of c that the algorithm *relies on* (Theorem 3 proof).
    c = _opaque(jnp.float32(_SPLIT_CONST) * a)
    a_big = c - a
    a_hi = c - a_big
    a_lo = a - a_hi
    return a_hi, a_lo


def split_safe(a: Array) -> Tuple[Array, Array]:
    """Overflow-guarded split: rescales |a| >= 2^115 by 2^-16 and back.

    Branch-free (select), matching the paper's no-branches design rule.
    """
    a = _f32(a)
    big = jnp.abs(a) >= jnp.float32(_SPLIT_OVERFLOW_THRESH)
    scale_dn = jnp.where(big, jnp.float32(2.0**-16), jnp.float32(1.0))
    scale_up = jnp.where(big, jnp.float32(2.0**16), jnp.float32(1.0))
    hi, lo = split(a * scale_dn)
    return hi * scale_up, lo * scale_up


def two_prod(a: Array, b: Array) -> Tuple[Array, Array]:
    """Mul12 (Dekker, paper Theorem 4).  x + y == a * b exactly.

    x = fl(a*b); y recovers the rounding error via split products, every one
    of which is exact in f32 (12-bit halves).
    """
    a, b = _f32(a), _f32(b)
    # _opaque: pins x = fl(a*b).  Otherwise a consumer like ``s + x`` can be
    # contracted into fma(a, b, s) while y was computed against rounded x —
    # the residual no longer matches and the FF pair is inconsistent.
    x = _opaque(a * b)
    a_hi, a_lo = split(a)
    b_hi, b_lo = split(b)
    # The err chain itself is FMA-safe: contracting ``x - ahi*bhi`` into
    # fma(-ahi, bhi, x) computes the same (provably representable) value.
    err1 = x - (a_hi * b_hi)
    err2 = err1 - (a_lo * b_hi)
    err3 = err2 - (a_hi * b_lo)
    y = (a_lo * b_lo) - err3
    return x, y


def two_prod_safe(a: Array, b: Array) -> Tuple[Array, Array]:
    """Mul12 with overflow-guarded splits (for |a| or |b| near f32 max)."""
    a, b = _f32(a), _f32(b)
    x = _opaque(a * b)
    a_hi, a_lo = split_safe(a)
    b_hi, b_lo = split_safe(b)
    err1 = x - (a_hi * b_hi)
    err2 = err1 - (a_lo * b_hi)
    err3 = err2 - (a_hi * b_lo)
    y = (a_lo * b_lo) - err3
    return x, y


def two_diff(a: Array, b: Array) -> Tuple[Array, Array]:
    """TwoDiff: (s, r) with s + r == a - b exactly (branch-free).

    Negation is exact in IEEE binary formats, so this is two_sum(a, -b).
    """
    a, b = _f32(a), _f32(b)
    return two_sum(a, -b)


def pairwise_sum_compensated(p: Array, axis: int, err: Array = None,
                             *, two_sum_fn=None) -> Tuple[Array, Array]:
    """Pairwise two_sum tree reduction over ``axis``: returns (sum, err)
    with sum + err tracking the exact total to ~2^-48 relative.

    Every tree-level rounding is captured by two_sum and folded into
    ``err`` (which only ever absorbs terms <= one ulp of the running
    partials, so its own f32 accumulation rounds at second order).  The
    tree halves the reduced axis per level — this is the vectorized slab
    reducer of the block-vectorized dot2 paths.

    ``two_sum_fn`` selects the EFT flavor: this module's barrier-carrying
    ``two_sum`` by default (safe under XLA:CPU FMA contraction), or the
    barrier-free ``repro.kernels.eft.two_sum`` inside Pallas kernel bodies.
    """
    ts = two_sum_fn if two_sum_fn is not None else two_sum
    if err is None:
        err = jnp.zeros_like(lax.index_in_dim(p, 0, axis, keepdims=False))
    while p.shape[axis] > 1:
        width = p.shape[axis]
        half = width // 2
        lo = lax.slice_in_dim(p, 0, half, axis=axis)
        hi = lax.slice_in_dim(p, half, 2 * half, axis=axis)
        s, e = ts(lo, hi)
        err = err + jnp.sum(e, axis=axis)
        if width % 2:
            s = jnp.concatenate(
                [s, lax.slice_in_dim(p, width - 1, width, axis=axis)],
                axis=axis)
        p = s
    return lax.index_in_dim(p, 0, axis, keepdims=False), err
