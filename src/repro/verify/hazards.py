"""Executable pins of the known XLA compiler hazards.

Both hazards were discovered empirically (PR 5) and are mitigated by
load-bearing code shapes rather than by flags — which means a compiler
upgrade can silently re-break them.  This corpus makes each hazard a
first-class, per-backend regression check with two independent probes:

* ``mitigated``      — the SHIPPED code shape still produces exact /
  in-contract results.  This is the gate: ``ok`` is ``mitigated``.
* ``hazard_present`` — the RAW (un-mitigated) shape still reproduces the
  miscompilation.  Informational only: if a future XLA stops folding,
  the pin reports it (the mitigation comment can then be retired) but
  does not fail.

Hazard 1 — **constant-folded TwoSum residual**: under jit, XLA's
algebraic simplifier rewrites ``(c + x) - c -> x`` for a constant
operand ``c``, zeroing the TwoSum residual — the paper's §5 compiler
hazard resurfacing through constant folding.  The ``(x, c)`` argument
orientation survives; ``ffmath.log1p22``'s far branch depends on it.

Hazard 2 — **x64-scope literal canonicalization**: python-float (and
``jnp.float64``) literals inside a trace-scoped ``enable_x64`` are
constant-folded at trace time and canonicalized back to f32 under the
ambient x64-off config, silently poisoning the f64 graph.  The shipped
``repro.ff.dispatch`` f64 tier derives every constant from traced values
(``one = jnp.exp(x - x)``) instead.

Expected values come from :mod:`repro.verify.oracle` (exact rational
residuals), never from another float path.
"""

from __future__ import annotations

import dataclasses
import math as _math
from typing import List, Optional

import numpy as np

from repro.verify import oracle

HAZARDS = ("constant_fold_two_sum", "x64_literal_canonicalization")
MODES = ("jit", "eager")


@dataclasses.dataclass
class HazardReport:
    hazard: str
    backend: str
    mode: str
    mitigated: bool
    hazard_present: Optional[bool]    # None when the probe can't run
    detail: str = ""

    @property
    def ok(self) -> bool:
        return self.mitigated


def _probe_grid() -> np.ndarray:
    """x with guaranteed-nonzero TwoSum residual against 1.0: magnitudes
    2^-25..2^-45 with odd significands (below 0.5 ulp(1), well above the
    residual floor)."""
    rng = np.random.default_rng(20260809)
    e = rng.integers(-45, -25, 256)
    m = rng.integers(1, 1 << 23, 256) | 1
    x = (m.astype(np.float64) / (1 << 23) + 1.0) * np.exp2(e.astype(np.float64))
    s = np.where(rng.integers(0, 2, 256) == 0, -1.0, 1.0)
    return (x * s).astype(np.float32)


def check_constant_fold_two_sum(mode: str = "jit") -> HazardReport:
    """Residual of ``two_sum(x, <constant 1>)`` must equal the exact
    rational residual bitwise (the shipped orientation); the reversed
    ``two_sum(<constant 1>, x)`` probes whether XLA still folds."""
    import jax
    import jax.numpy as jnp

    import repro.core.transforms as T

    xs = _probe_grid()
    want = np.array([oracle.round_f32(oracle.two_sum_residual(1.0, x))
                     for x in xs], np.float32)
    assert (want != 0).all()          # the grid construction guarantees it

    def shipped(x):                   # the log1p22 far-branch shape
        s, r = T.two_sum(x, jnp.ones_like(x))
        return s, r

    def raw(x):                       # the hazard shape
        s, r = T.two_sum(jnp.ones_like(x), x)
        return s, r

    if mode == "jit":
        shipped = jax.jit(shipped)
        raw = jax.jit(raw)
    _s, got = shipped(jnp.asarray(xs))
    got = np.asarray(got)
    mitigated = bool((got.view(np.uint32) == want.view(np.uint32)).all())
    _s, rgot = raw(jnp.asarray(xs))
    hazard_present = bool((np.asarray(rgot) == 0).all())
    n_bad = int((got.view(np.uint32) != want.view(np.uint32)).sum())
    return HazardReport(
        "constant_fold_two_sum", _backend(), mode, mitigated, hazard_present,
        f"{n_bad}/{xs.size} shipped-orientation residuals wrong; "
        f"raw orientation folds: {hazard_present}")


def check_x64_literal_canonicalization(mode: str = "jit") -> HazardReport:
    """The shipped f64 dispatch tier must stay in its <= 2^-47 class
    (traced-value-derived constants) without leaking x64 into the ambient
    config; the raw probe re-builds the literal-in-scope shape and asks
    whether it still canonicalizes to f32."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    import repro.ff as ff
    from repro.core.ff import FF

    rng = np.random.default_rng(42)
    x64 = rng.uniform(-4.0, 4.0, 2048)
    hi = x64.astype(np.float32)
    lo = (x64 - hi.astype(np.float64)).astype(np.float32)
    a = FF(jnp.asarray(hi), jnp.asarray(lo))
    # the f64 tier jits internally; "eager" exercises the same entry
    # point without an outer jit wrapper
    out = ff.sigmoid(a, impl="f64")
    if mode == "jit":
        out = jax.jit(lambda p: ff.sigmoid(FF(p[0], p[1]), impl="f64"))(
            (a.hi, a.lo))
    got = (np.asarray(out.hi, np.float64) + np.asarray(out.lo, np.float64))
    want = 1.0 / (1.0 + np.exp(-x64))
    rel = np.abs(got - want) / np.abs(want)
    mitigated = bool(rel.max() <= 2.0 ** -47)
    leaked = bool(jax.config.jax_enable_x64) or (
        jnp.asarray(1.0).dtype != jnp.float32)
    mitigated = mitigated and not leaked

    # raw probe: bare python-float constants inside the x64 scope (the
    # spelled-out gelu shape the dispatch comment warns about).  Today
    # the canonicalized f32 constant makes the f64 graph fail StableHLO
    # verification (mixed f32*f64 multiply) — a hard error rather than
    # silent wrongness, but proof the canonicalization still happens.
    @jax.jit
    def raw(h, l):
        with jax.enable_x64(True):
            x = (lax.convert_element_type(h, jnp.float64)
                 + lax.convert_element_type(l, jnp.float64))
            r = 0.5 * x * (1.0 + lax.erf(x / jnp.sqrt(jnp.asarray(2.0))))
            rhi = lax.convert_element_type(r, jnp.float32)
            rlo = lax.convert_element_type(
                r - lax.convert_element_type(rhi, jnp.float64), jnp.float32)
        return rhi, rlo

    gelu_want = (x64 / 2.0
                 * (1.0 + np.vectorize(_math.erf)(x64 / np.sqrt(2.0))))
    try:
        rh, rl = raw(a.hi, a.lo)
        rgot = np.asarray(rh, np.float64) + np.asarray(rl, np.float64)
        rrel = (np.abs(rgot - gelu_want)
                / np.maximum(np.abs(gelu_want), 1e-300))
        hazard_present = bool(rrel.max() > 2.0 ** -40)
        raw_note = f"raw literal shape rel={rrel.max():.2e}"
    except ValueError as e:
        # canonicalized constant -> type-mismatched graph: hazard alive
        hazard_present = True
        raw_note = f"raw literal shape fails lowering ({str(e)[:60]}...)"
    except Exception as e:                    # probe is best-effort
        hazard_present = None
        raw_note = f"raw probe failed: {e!r}"
    return HazardReport(
        "x64_literal_canonicalization", _backend(), mode, mitigated,
        hazard_present,
        f"shipped f64 tier rel={rel.max():.2e} (<= 2^-47 required), "
        f"x64 leak={leaked}; {raw_note}")


def _backend() -> str:
    import jax
    return jax.default_backend()


def run_corpus(modes=MODES) -> List[HazardReport]:
    out = []
    for mode in modes:
        out.append(check_constant_fold_two_sum(mode))
        out.append(check_x64_literal_canonicalization(mode))
    return out
