"""Shared fixtures.  NOTE: do NOT set XLA_FLAGS device-count here — smoke
tests and benches must see 1 device (dry-run sets its own flags).

We DO set --xla_cpu_max_isa=SSE4_2 (before any jax import): XLA:CPU's LLVM
backend on AVX2+ contracts mul+add into FMA inside fusions, which breaks the
paper's error-free transformations (see core/selfcheck.py).  The paper's 2006
GPUs had no FMA either, so this is also the faithful hardware model."""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "--xla_cpu_max_isa" not in _flags:
    os.environ["XLA_FLAGS"] = ("--xla_cpu_max_isa=SSE4_2 " + _flags).strip()

import numpy as np
import pytest


@pytest.fixture(scope="module")
def rng():
    # per module, not per session: a file's data must not depend on which
    # other files a parallel worker happened to run before it
    return np.random.default_rng(1234)


def f32_vec(rng, n, lo=-10, hi=10):
    """Well-scaled random f32 test vector (no denormals/inf/nan — the paper
    excludes them too, §6.1)."""
    return (rng.standard_normal(n) * 10.0 ** rng.uniform(lo, hi, n)).astype(np.float32)
