"""Mesh-partitioned FF tier tests (``repro.ff.sharded``).

Resolution/scoping/fallback behavior runs in the main process (1 device —
the mesh scope is pure Python state).  Everything that needs an actual
device mesh runs in a SUBPROCESS with 8 simulated host devices, following
the ``test_distributed.py`` pattern (conftest keeps the main process at 1
device by design): sharded matmul (fast + accurate class), ``ff.sum`` /
``ff.dot`` with the compensated tree combine, grad flow through
``custom_vjp``-over-``shard_map``, and a mesh-scoped train step.

The asserted bounds are the DOCUMENTED per-impl contracts from
``docs/NUMERICS.md``: sharded results must match the f64 oracle and the
single-device results within each class's bound, not merely "be close".
"""
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

from repro.launch.mesh import make_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (f"--xla_cpu_max_isa=SSE4_2 "
                        f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


# ---------------------------------------------------------------------------
# main-process: scoping, resolution, fallback (no mesh devices needed)
# ---------------------------------------------------------------------------

def test_on_mesh_resolution():
    import jax
    import repro.ff as ff

    mesh = make_mesh((1, 1), ("data", "model"))
    assert ff.resolve_name("matmul") != "sharded"
    assert ff.mesh_default("matmul") == "sharded"
    assert ff.mesh_default("sum") == "sharded"
    assert ff.mesh_default("mul") is None
    with ff.on_mesh(mesh, axis="data"):
        assert ff.current_mesh() is not None
        for op in ("matmul", "sum", "dot", "norm_stats"):
            assert ff.resolve_name(op) == "sharded"
        # explicit choices outrank the mesh default
        assert ff.resolve_name("matmul", "dot2") == "dot2"
        with ff.use(matmul="hybrid"):
            assert ff.resolve_name("matmul") == "hybrid"
        with ff.policy(matmul="ozaki"):
            assert ff.resolve_name("matmul") == "ozaki"
        # inner disabler: the sharded impls resolve their per-shard inner
        # op under on_mesh(None) without leaving the outer scope
        with ff.on_mesh(None):
            assert ff.current_mesh() is None
            assert ff.resolve_name("matmul") != "sharded"
        assert ff.resolve_name("matmul") == "sharded"
    assert ff.current_mesh() is None
    assert ff.resolve_name("matmul") != "sharded"


def test_on_mesh_bad_axis():
    import jax
    import repro.ff as ff

    mesh = make_mesh((1, 1), ("data", "model"))
    with pytest.raises(ValueError, match="not in mesh axes"):
        ff.on_mesh(mesh, axis="nonexistent")


def test_sharded_fallback_without_scope_matches_class():
    """Explicit impl="sharded*" outside any on_mesh scope warns and is
    bitwise the single-device impl its class resolves to."""
    import jax.numpy as jnp
    import repro.ff as ff

    rng = np.random.default_rng(3)
    A = jnp.asarray(rng.standard_normal((32, 256)).astype(np.float32))
    B = jnp.asarray(rng.standard_normal((256, 32)).astype(np.float32))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        R = ff.matmul(A, B, impl="sharded")
        assert any("falling back" in str(x.message) for x in w)
    fast = ff.resolve_name("matmul", None, shape=(32, 256, 32))
    R1 = ff.matmul(A, B, impl=fast)
    assert bool(jnp.all(R.hi == R1.hi) & jnp.all(R.lo == R1.lo))
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        Ra = ff.matmul(A, B, impl="sharded_accurate")
        assert any("falling back" in str(x.message) for x in w)
    acc = ff.resolve_name("matmul", "tuned_accurate", shape=(32, 256, 32))
    Ra1 = ff.matmul(A, B, impl=acc)
    assert bool(jnp.all(Ra.hi == Ra1.hi) & jnp.all(Ra.lo == Ra1.lo))


def test_tune_never_times_sharded(tmp_path):
    """ff.tune must skip the mesh impls (no mesh in the tuning harness —
    timing them would double-count their single-device fallback)."""
    import repro.ff as ff
    from repro.ff import tuning

    tuning.clear()
    try:
        out = ff.tune("matmul", shapes=[(32, 64, 32)], reps=1,
                      cache=str(tmp_path / "tune.json"), force=True)
        for rec in out["table"].values():
            assert not any(n.startswith("sharded") for n in rec["impls"])
    finally:
        tuning.clear()


# ---------------------------------------------------------------------------
# 8-simulated-device subprocess: accuracy + determinism contracts
# ---------------------------------------------------------------------------

_ACCURACY_CODE = r"""
import json, warnings
import numpy as np
import jax, jax.numpy as jnp
import repro.ff as ff

out = {}
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("x",))
rng = np.random.default_rng(0)
M, K, N = 128, 2048, 128
A = jnp.asarray(rng.standard_normal((M, K)).astype(np.float32))
B = jnp.asarray(rng.standard_normal((K, N)).astype(np.float32))
E = np.asarray(A, np.float64) @ np.asarray(B, np.float64)
S = np.abs(np.asarray(A, np.float64)) @ np.abs(np.asarray(B, np.float64))

def err(R):
    return float((np.abs(np.asarray(R.to_f64()) - E) / S).max())

R1_fast = jax.jit(lambda a, b: ff.matmul(a, b))(A, B)
R1_acc = jax.jit(lambda a, b: ff.matmul(a, b, impl="tuned_accurate"))(A, B)
with ff.on_mesh(mesh, axis="x"):
    assert ff.resolve_name("matmul") == "sharded"
    Rf = jax.jit(lambda a, b: ff.matmul(a, b))(A, B)
    Ra = jax.jit(lambda a, b: ff.matmul(a, b, impl="sharded_accurate"))(A, B)
    Ra2 = jax.jit(lambda a, b: ff.matmul(a, b, impl="sharded_accurate"))(A, B)
    # explicit psum combine on the accurate inner: documents the fast
    # combine's (weaker) bound independently of the inner impl
    Rp = jax.jit(lambda a, b: ff.matmul(
        a, b, impl="sharded_accurate", combine="psum"))(A, B)
    # non-divisible K falls back to the single-device class impl
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        Rnd = ff.matmul(A[:, :2047], B[:2047])
        out["fallback_warned"] = any("falling back" in str(x.message)
                                     for x in w)
out["fast_oracle"] = err(Rf)
out["acc_oracle"] = err(Ra)
out["psum_acc_oracle"] = err(Rp)
out["fast_vs_single"] = float(
    (np.abs(np.asarray(Rf.to_f64()) - np.asarray(R1_fast.to_f64())) / S).max())
out["acc_vs_single"] = float(
    (np.abs(np.asarray(Ra.to_f64()) - np.asarray(R1_acc.to_f64())) / S).max())
out["tree_deterministic"] = bool(
    jnp.all(Ra.hi == Ra2.hi) & jnp.all(Ra.lo == Ra2.lo))

# reductions: rough-conditioned vector (wide dynamic range)
n = 1 << 16
v = (rng.standard_normal(n) * 10.0 ** rng.uniform(-4, 4, n)).astype(np.float32)
x = jnp.asarray(v)
exact = float(np.sum(v.astype(np.float64)))
with ff.on_mesh(mesh, axis="x"):
    s_tree = jax.jit(lambda u: ff.sum(u))(x)
    s_psum = jax.jit(lambda u: ff.sum(u, combine="psum"))(x)
    d_tree = jax.jit(lambda u, w: ff.dot(u, w))(x, x)
s1 = jax.jit(lambda u: ff.sum(u))(x)
dexact = float(np.sum(v.astype(np.float64) ** 2))
out["sum_tree_rel"] = abs(float(s_tree.to_f64()) - exact) / abs(exact)
out["sum_psum_rel"] = abs(float(s_psum.to_f64()) - exact) / abs(exact)
out["sum_single_rel"] = abs(float(s1.to_f64()) - exact) / abs(exact)
out["dot_tree_rel"] = abs(float(d_tree.to_f64()) - dexact) / abs(dexact)

# norm_stats: row-parallel, bitwise vs single-device
xm = jnp.asarray(rng.standard_normal((64, 256)).astype(np.float32))
with ff.on_mesh(mesh, axis="x"):
    mu, var = jax.jit(lambda u: ff.norm_stats(u))(xm)
mu1, var1 = jax.jit(lambda u: ff.norm_stats(u))(xm)
out["norm_stats_bitwise"] = bool(jnp.all(mu == mu1) & jnp.all(var == var1))

# non-power-of-two mesh axis: the all_gather + ordered-fold combine
mesh6 = jax.sharding.Mesh(np.array(jax.devices()[:6]), ("x",))
A6, B6 = A[:, :1536], B[:1536]
E6 = np.asarray(A6, np.float64) @ np.asarray(B6, np.float64)
S6 = np.abs(np.asarray(A6, np.float64)) @ np.abs(np.asarray(B6, np.float64))
with ff.on_mesh(mesh6, axis="x"):
    R6 = jax.jit(lambda a, b: ff.matmul(a, b, impl="sharded_accurate"))(A6, B6)
out["acc6_oracle"] = float((np.abs(np.asarray(R6.to_f64()) - E6) / S6).max())

# 2-axis mesh: tuple-axis partitioning folds one axis at a time
mesh24 = make_mesh((2, 4), ("a", "b"))
with ff.on_mesh(mesh24, axis=("a", "b")):
    R24 = jax.jit(lambda a, b: ff.matmul(a, b, impl="sharded_accurate"))(A, B)
out["acc24_oracle"] = err(R24)
print(json.dumps(out))
"""


def test_sharded_accuracy_subprocess():
    res = json.loads(_sub(_ACCURACY_CODE).strip().splitlines()[-1])
    # fast class: inner bound (blocked compensated, ~2^-24-relative class)
    # + psum combine slack log2(8)*2^-24 — documented 2^-19 class ceiling
    assert res["fast_oracle"] < 2.0 ** -19, res
    # accurate class: per-op ~2^-44 contract survives the tree combine
    assert res["acc_oracle"] < 2.0 ** -44, res
    assert res["acc6_oracle"] < 2.0 ** -44, res     # non-pow2 gather fold
    assert res["acc24_oracle"] < 2.0 ** -44, res    # tuple-axis butterfly
    # psum combine on an accurate inner: only the combine's
    # log2(P)*2^-24-class error remains — must sit between the classes
    assert res["psum_acc_oracle"] < 2.0 ** -20, res
    assert res["psum_acc_oracle"] > 2.0 ** -44, res
    # cross-checks against the single-device results
    assert res["fast_vs_single"] < 2.0 ** -20, res
    assert res["acc_vs_single"] < 2.0 ** -44, res
    assert res["tree_deterministic"], res
    assert res["fallback_warned"], res
    # reductions: the tree combine preserves the compensated-sum contract
    assert res["sum_tree_rel"] < 2.0 ** -40, res
    assert res["dot_tree_rel"] < 2.0 ** -40, res
    # ... and stays in the single-device ballpark (within 16x)
    assert res["sum_tree_rel"] <= max(res["sum_single_rel"] * 16, 2.0 ** -48), res
    assert res["norm_stats_bitwise"], res


_GRAD_CODE = r"""
import json
import numpy as np
import jax, jax.numpy as jnp
import repro.ff as ff

out = {}
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ("x",))
rng = np.random.default_rng(1)
M, K, N = 64, 1024, 64
A = jnp.asarray(rng.standard_normal((M, K)).astype(np.float32))
B = jnp.asarray(rng.standard_normal((K, N)).astype(np.float32))
W = jnp.asarray(rng.standard_normal((M, N)).astype(np.float32))

def loss(a, b):
    return (ff.matmul(a, b).to_f32() * W).sum()

def loss_acc(a, b):
    return (ff.matmul(a, b, impl="sharded_accurate").to_f32() * W).sum()

ga1, gb1 = jax.jit(jax.grad(loss, argnums=(0, 1)))(A, B)
with ff.on_mesh(mesh, axis="x"):
    ga, gb = jax.jit(jax.grad(loss, argnums=(0, 1)))(A, B)
    gaa, gba = jax.jit(jax.grad(loss_acc, argnums=(0, 1)))(A, B)

def rel(g, g1):
    return float(jnp.max(jnp.abs(g - g1)) / jnp.max(jnp.abs(g1)))

out["ga_rel"] = rel(ga, ga1)
out["gb_rel"] = rel(gb, gb1)
out["gaa_rel"] = rel(gaa, ga1)
out["gba_rel"] = rel(gba, gb1)

# grad through the mesh-partitioned ff.sum: d(sum)/dx == 1
x = jnp.asarray(rng.standard_normal(4096).astype(np.float32))
with ff.on_mesh(mesh, axis="x"):
    gs = jax.jit(jax.grad(lambda u: ff.sum(u).to_f32()))(x)
out["sum_grad_ones"] = bool(jnp.all(gs == 1.0))

# mesh-scoped train step on the 8-device mesh: loss/grad reductions
# partitioned, metrics finite, grad-norm matches the single-device step
from repro.configs import get_config
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import init_params
from repro.optim.adamw import AdamW
from repro.train.train_step import make_train_step

cfg = get_config("granite_3_2b").reduced(num_layers=2, vocab_size=512)
params = init_params(cfg, jax.random.PRNGKey(0))
opt = AdamW(learning_rate=1e-3, ff=True)
data = SyntheticLM(DataConfig(vocab_size=cfg.vocab_size, seq_len=64,
                              global_batch=8))
batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
mesh2 = make_mesh((8, 1), ("data", "model"))
with ff.policy("ff_reduce"):
    step1 = jax.jit(make_train_step(cfg, None, opt))
    stepm = jax.jit(make_train_step(cfg, None, opt, mesh=mesh2))
s0 = opt.init(params)
p1, s1, m1 = step1(params, s0, batch)
pm, sm, mm = stepm(params, opt.init(params), batch)
out["loss_single"] = float(m1["loss"])
out["loss_mesh"] = float(mm["loss"])
out["gnorm_single"] = float(m1["grad_norm"])
out["gnorm_mesh"] = float(mm["grad_norm"])
p2, s2, m2 = stepm(pm, sm, batch)
out["mesh_second_step_finite"] = bool(np.isfinite(float(m2["loss"])))
print(json.dumps(out))
"""


def test_sharded_grad_and_train_subprocess():
    res = json.loads(_sub(_GRAD_CODE).strip().splitlines()[-1])
    # backward matmuls re-enter the sharded tier; cotangent extraction is
    # f32, so the cross-device combine shows up at the 2^-24-class level
    for k in ("ga_rel", "gb_rel", "gaa_rel", "gba_rel"):
        assert res[k] < 2.0 ** -18, (k, res)
    assert res["sum_grad_ones"], res
    # mesh-scoped step computes the same loss/grad-norm (compensated
    # reductions agree to f32-visible precision)
    assert abs(res["loss_mesh"] - res["loss_single"]) <= \
        2e-5 * abs(res["loss_single"]), res
    assert abs(res["gnorm_mesh"] - res["gnorm_single"]) <= \
        1e-3 * abs(res["gnorm_single"]), res
    assert res["mesh_second_step_finite"], res
