"""Property-based (hypothesis) invariants for the FF core: adversarial
scalars against the paper's EFT theorems.

Split out of test_core_ff.py so the main suite runs without hypothesis;
this module skips itself when the dependency is absent.
"""
import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import (
    FF, add12, add22, add22_accurate, div22, mul22, split, sqrt22,
    two_prod, two_sum,
)


def ff64(x):
    return np.asarray(x.hi).astype(np.float64) + np.asarray(x.lo).astype(np.float64)


# ---------------------------------------------------------------------------
# Property-based tests (hypothesis): invariants on adversarial scalars
# ---------------------------------------------------------------------------

finite_f32 = st.floats(
    allow_nan=False, allow_infinity=False, width=32,
).filter(lambda x: x == 0.0 or 1e-30 < abs(x) < 1e30)


@settings(max_examples=200, deadline=None)
@given(finite_f32, finite_f32)
def test_prop_two_sum_exact(a, b):
    s, r = two_sum(jnp.float32(a), jnp.float32(b))
    assert float(s) + float(r) == float(np.float64(np.float32(a)) + np.float64(np.float32(b)))


@settings(max_examples=200, deadline=None)
@given(finite_f32, finite_f32)
def test_prop_two_prod_exact(a, b):
    p = np.float64(np.float32(a)) * np.float64(np.float32(b))
    if p != 0 and (abs(p) > 3e38 or abs(p) < 1e-25):
        return  # overflow/underflow (incl. subnormal split residues, FTZ)
        # excluded, like the paper §6.1
    x, y = two_prod(jnp.float32(a), jnp.float32(b))
    assert float(x) + float(y) == p


@settings(max_examples=200, deadline=None)
@given(finite_f32)
def test_prop_split_nonoverlap(a):
    hi, lo = split(jnp.float32(a))
    hi, lo = float(hi), float(lo)
    assert hi + lo == float(np.float32(a))
    assert abs(lo) <= abs(hi) or hi == 0.0


@settings(max_examples=100, deadline=None)
@given(finite_f32, finite_f32, finite_f32, finite_f32)
def test_prop_add22_associativity_error(a, b, c, d):
    """FF addition is not associative, but both orders stay within 2^-40 of
    exact — the invariant applications rely on."""
    fa, fb = add12(jnp.float32(a), jnp.float32(b)), add12(jnp.float32(c), jnp.float32(d))
    exact = (np.float64(np.float32(a)) + np.float64(np.float32(b))
             + np.float64(np.float32(c)) + np.float64(np.float32(d)))
    mag = (abs(np.float64(np.float32(a))) + abs(np.float64(np.float32(b)))
           + abs(np.float64(np.float32(c))) + abs(np.float64(np.float32(d))))
    if mag == 0:
        return
    r1 = ff64(add22_accurate(fa, fb))
    assert abs(r1 - exact) / mag < 2.0**-40


# ---------------------------------------------------------------------------
# adversarial limb classes: pairs constructed to sit exactly on the FF
# normalization boundaries the random strategies above almost never hit
# ---------------------------------------------------------------------------

# hi limbs in the safe interior (the paper §6.1 domain: well away from
# overflow and the Dekker-split window edges)
_safe_hi = st.floats(
    allow_nan=False, allow_infinity=False, width=32,
).filter(lambda x: 1e-20 < abs(x) < 1e20)

# near-overflow hi limbs: the top decades of the f32 range
_big_hi = st.floats(
    min_value=float(np.float32(1e30)), max_value=float(np.float32(3.0e38)),
    width=32,   # bounds must be f32-representable at width=32
).flatmap(lambda m: st.sampled_from([m, -m]))


def _ulp32(x: float) -> float:
    return float(np.nextafter(np.float32(x), np.float32(np.inf))
                 - np.float32(x)) if x >= 0 else _ulp32(-x)


@st.composite
def adversarial_pair(draw, hi_strategy=_safe_hi):
    """An FF pair whose lo limb lands in one of the adversarial classes:
    exactly +-0.5 ulp(hi) (the normalization tie), a subnormal magnitude,
    a maximal in-contract lo, or zero."""
    hi = np.float32(draw(hi_strategy))
    cls = draw(st.sampled_from(["tie", "subnormal", "max_lo", "zero"]))
    sign = draw(st.sampled_from([1.0, -1.0]))
    if cls == "tie":
        lo = np.float32(sign * 0.5 * _ulp32(float(hi)))
    elif cls == "subnormal":
        lo = np.float32(sign * 2.0 ** -140)
    elif cls == "max_lo":
        lo = np.float32(sign * 0.49 * _ulp32(float(hi)))
    else:
        lo = np.float32(sign * 0.0)
    return FF(jnp.float32(hi), jnp.float32(lo))


def _ff_exact64(x: FF) -> float:
    return float(np.asarray(x.hi, np.float64) + np.asarray(x.lo, np.float64))


@settings(max_examples=150, deadline=None)
@given(adversarial_pair(), adversarial_pair())
def test_prop_add22_adversarial_limbs(a, b):
    """Thm 5 class on tie/subnormal/max-lo limbs, opposite signs
    included: |err| <= max(2^-24 |al + bl|, 2^-43 |a + b|) in the f64
    view (f64 can resolve both floors at these magnitudes)."""
    exact = _ff_exact64(a) + _ff_exact64(b)
    got = ff64(add22(a, b))
    lo_mag = abs(float(np.asarray(a.lo, np.float64))
                 + float(np.asarray(b.lo, np.float64)))
    # the 2^-125 floor absorbs flush-to-zero hardware dropping a
    # subnormal lo limb outright (paper §6.1 exclusion)
    tol = max(2.0 ** -24 * lo_mag, 2.0 ** -43 * abs(exact), 2.0 ** -125)
    assert abs(got - exact) <= tol or exact == got


@settings(max_examples=150, deadline=None)
@given(adversarial_pair(), adversarial_pair())
def test_prop_add22_accurate_adversarial_limbs(a, b):
    exact = _ff_exact64(a) + _ff_exact64(b)
    got = ff64(add22_accurate(a, b))
    # opposite-sign cancellation can leave |exact| far below either
    # operand; the accurate variant must still track it to 2^-43 rel
    # (subnormal-lo pairs bottom out at the f32 representability floor)
    floor = 2.0 ** -126
    assert abs(got - exact) <= max(2.0 ** -43 * abs(exact), floor)


@settings(max_examples=150, deadline=None)
@given(adversarial_pair(), adversarial_pair())
def test_prop_mul22_adversarial_limbs(a, b):
    exact = _ff_exact64(a) * _ff_exact64(b)
    if not (1e-30 < abs(exact) < 1e30):
        return                                   # paper §6.1 exclusions
    got = ff64(mul22(a, b))
    # the 2^-125 floor absorbs flush-to-zero dropping a subnormal lo limb
    assert abs(got - exact) <= max(2.0 ** -43 * abs(exact), 2.0 ** -125)


@settings(max_examples=150, deadline=None)
@given(adversarial_pair(), adversarial_pair())
def test_prop_div22_adversarial_limbs(a, b):
    den = _ff_exact64(b)
    if den == 0:
        return
    exact = _ff_exact64(a) / den
    if not (1e-30 < abs(exact) < 1e30):
        return
    got = ff64(div22(a, b))
    assert abs(got - exact) <= max(2.0 ** -42 * abs(exact), 2.0 ** -125)


@settings(max_examples=150, deadline=None)
@given(adversarial_pair())
def test_prop_sqrt22_adversarial_limbs(a):
    v = _ff_exact64(a)
    if v <= 0:
        return
    exact = float(np.sqrt(np.float64(v)))
    got = ff64(sqrt22(a))
    assert abs(got - exact) <= 2.0 ** -43 * abs(exact)


@settings(max_examples=100, deadline=None)
@given(_big_hi)
def test_prop_add22_near_overflow_hi(hi):
    """Near-overflow hi limbs: add22 of (hi, ~max lo) with its negation
    cancels exactly; with itself it overflows to inf, never to garbage."""
    a = FF(jnp.float32(hi), jnp.float32(0.49 * _ulp32(abs(float(hi)))))
    cancel = add22(a, FF(-a.hi, -a.lo))
    assert float(cancel.hi) == 0.0 and float(cancel.lo) == 0.0
    doubled = add22(a, a)
    d64 = 2.0 * _ff_exact64(a)
    thresh = 3.4028236692093846e38              # f32 round-to-inf threshold
    if abs(d64) >= thresh * (1 + 2.0 ** -40):
        assert not np.isfinite(float(doubled.hi))
    elif abs(d64) <= thresh * (1 - 2.0 ** -40):
        assert abs(ff64(doubled) - d64) <= 2.0 ** -43 * abs(d64)
    # inside the 2^-40 band around the threshold either rounding is fine


