"""``repro.ff`` — the unified float-float namespace.

One numpy-like API surface for the paper's float-float operators, with the
backend hidden behind a dispatch registry (compiled Pallas on TPU,
interpret-Pallas or pure-jnp on CPU, ``shard_map``-partitioned on a device
mesh), ``jax.custom_vjp`` differentiation rules for the core ops, and
scoped configuration::

    import repro.ff as ff

    z = ff.mul(ff.from_f64(np.pi), ff.from_f64(np.e))   # ~2^-44 accurate
    s = ff.sum(x, axis=-1)                              # compensated, FF
    C = ff.matmul(A, B)                                 # blocked-K MXU path
    C = ff.matmul(A, B, impl="dot2")                    # paper-faithful

    with ff.policy("ff_full", matmul="hybrid"):
        loss, grads = jax.value_and_grad(loss_fn)(params)   # scope-aware

    with ff.on_mesh(mesh, axis="data"):
        C = ff.matmul(A, B)    # K split over the mesh, compensated combine

Scopes (all trace-time, thread-local): :func:`policy` installs a
``PrecisionPolicy`` level, :func:`use` overrides single ops'
implementations, :func:`on_mesh` routes the mesh-partitioned tier
(``repro.ff.sharded``).  :func:`tune` fills the measured-winner table that
drives default resolution; :func:`render_api_table` renders the registry
as the ``docs/API.md`` dispatch matrix (CI-checked).

Layering: ``repro.core`` holds the paper's algorithms (the registry
targets), ``repro.kernels`` the Pallas kernels, ``repro.ff.sharded`` the
mesh tier, and this namespace is the only import model/optimizer/training
code needs.  Reference: ``docs/API.md`` (ops x impls x backends),
``docs/NUMERICS.md`` (per-op error contracts, doctested).
"""

from repro.core.ff import (  # noqa: F401
    FF, FF_EPS, FF_PRECISION_BITS, normalize, tree_from_f32, tree_to_f32,
)
from repro.core.policy import (  # noqa: F401
    PrecisionPolicy, BASELINE, FF_MASTER, FF_REDUCE, FF_FULL,
)
from repro.ff.scope import (  # noqa: F401
    policy, use, current_policy, set_default_policy, resolve_policy,
    on_mesh, current_mesh,
)
from repro.ff.dispatch import (  # noqa: F401
    backend, register, ops, impls, resolve_name, resolve_opts, mesh_default,
    FFFallbackWarning,
)
from repro.ff.tuning import tune  # noqa: F401
from repro.ff import tuning  # noqa: F401
from repro.ff.autodiff import (  # noqa: F401
    add, sub, mul, div, sqrt, matmul, sum, mean, dot, logsumexp,
    softmax, attention, mean_sq, norm_stats, adamw_update,
    two_sum, two_prod,
)
from repro.ff import math  # noqa: F401  (the FF elementary-function tier)
from repro.ff.math import (  # noqa: F401
    exp, expm1, log, log1p, tanh, sigmoid, erf, gelu, silu, pow,
)
from repro.ff import fusion  # noqa: F401
from repro.ff.fusion import fused  # noqa: F401
from repro.ff import sharded  # noqa: F401  (registers the mesh impls)
from repro.ff.guard import (  # noqa: F401  (registers guard_probe)
    guard, guard_probe, health_mask, assert_healthy, current_guard,
    GuardCounts, FFError, FFNonFiniteError, FFNormalizationError,
    FFResourceError, FFGuardWarning, FFTuneWarning,
)
from repro.ff.docgen import render_api_table  # noqa: F401

# -- constructors / views (constructor sugar over the FF class) --------------
from_f32 = FF.from_f32        # f32 array -> FF with zero lo limb (exact)
from_f64 = FF.from_f64        # wide host value -> FF to ~2^-48 (host only)
zeros = FF.zeros              # FF of zeros with the given shape


def to_f32(x):
    """Round an FF to f32 (its ``hi`` limb — already correctly rounded);
    plain arrays pass through unchanged.

    The boundary from FF results (and FF-structured cotangents) back to
    plain-f32 code: exact up to the representation's own rounding, never
    an additional operation."""
    return x.to_f32() if isinstance(x, FF) else x


def asff(x) -> FF:
    """Coerce an array/scalar/FF to FF (exact: non-FF inputs become the
    ``hi`` limb with a zero ``lo``)."""
    if isinstance(x, FF):
        return x
    return FF.from_f32(x)
