"""Sharding rules + dry-run machinery tests.

SPMD lowering tests run in a SUBPROCESS with a small simulated device count
(conftest keeps the main test process at 1 device by design).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.launch.mesh import make_mesh

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_param_spec_rules():
    import jax
    from repro.configs import get_config
    from repro.distributed import sharding as shd
    from repro.models import init_params

    # 1-device mesh with both axis names still produces valid specs
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_config("olmoe_1b_7b")
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    sh = shd.param_shardings(params, cfg, mesh)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    assert len(flat) > 10
    # every leaf got a NamedSharding
    for _, s in flat:
        assert s.mesh is not None


def test_validate_spec_divisibility():
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import validate_spec

    mesh = make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        shape = {"data": 16, "model": 16}
        axis_names = ("data", "model")

    spec = validate_spec(P("model", "data"), (49155, 2048), FakeMesh())
    assert spec == P(None, "data")
    spec = validate_spec(P(("data", "model"), None), (512, 64), FakeMesh())
    assert spec == P(("data", "model"), None)
    spec = validate_spec(P(("data", "model"), None), (100, 64), FakeMesh())
    assert spec == P(None, None)


def test_dryrun_cell_subprocess_small_mesh():
    """Full dry-run machinery on a 2x4 mesh with a reduced config: lower,
    compile, memory+cost analysis, collective parsing."""
    code = """
import json
import jax
from repro.configs import SHAPES
from repro.configs import get_config
from repro.core.policy import PrecisionPolicy
from repro.launch import dryrun as dr
import dataclasses

cfg = get_config('granite_3_2b').reduced(num_layers=2, vocab_size=512)
spec = dataclasses.replace(SHAPES['train_4k'], seq_len=256, global_batch=8)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
lowered, compiled = dr._lower_cell(cfg, spec, mesh, PrecisionPolicy.make('ff_master'))
from repro.launch import hlo_costs, hlo_analysis as hla
parsed = hlo_costs.analyze_text(compiled.as_text())
mem = hla.memory_summary(compiled)
print(json.dumps({'flops': parsed['flops'], 'coll': parsed['collective_bytes'],
                  'temp': mem['temp_size_in_bytes']}))
"""
    out = _sub(code, devices=8)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["flops"] > 1e8          # nontrivial compute counted
    assert res["coll"] > 0             # sharded -> collectives exist
    assert res["temp"] > 0


def test_dryrun_decode_cell_subprocess():
    code = """
import json, dataclasses
import jax
from repro.configs import SHAPES, get_config
from repro.core.policy import PrecisionPolicy
from repro.launch import dryrun as dr
from repro.launch import hlo_costs

cfg = get_config('mamba2_370m').reduced(num_layers=2, vocab_size=512)
spec = dataclasses.replace(SHAPES['decode_32k'], seq_len=1024, global_batch=8)
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
lowered, compiled = dr._lower_cell(cfg, spec, mesh, PrecisionPolicy.make('ff_master'))
parsed = hlo_costs.analyze_text(compiled.as_text())
print(json.dumps({'flops': parsed['flops']}))
"""
    out = _sub(code, devices=8)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["flops"] > 1e5


def test_hlo_costs_loop_multiplication():
    """The cost parser must multiply while bodies by trip count (the reason
    it exists — XLA's cost_analysis counts them once)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from repro.launch.hlo_costs import analyze_text

    def f(w, x):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = lax.scan(body, x, w)
        return y.sum()

    L, D = 16, 64
    txt = jax.jit(f).lower(
        jax.ShapeDtypeStruct((L, D, D), jnp.float32),
        jax.ShapeDtypeStruct((4, D), jnp.float32)).compile().as_text()
    t = analyze_text(txt)
    expect = L * 2 * 4 * D * D
    assert t["flops"] >= expect, (t["flops"], expect)
    assert t["flops"] < expect * 3


def test_hlo_costs_exact_on_plain_dot():
    import jax
    import jax.numpy as jnp
    from repro.launch.hlo_costs import analyze_text

    M = K = N = 128
    txt = jax.jit(lambda a, b: a @ b).lower(
        jax.ShapeDtypeStruct((M, K), jnp.float32),
        jax.ShapeDtypeStruct((K, N), jnp.float32)).compile().as_text()
    t = analyze_text(txt)
    assert t["flops"] == 2 * M * K * N


def test_elastic_reshard_subprocess():
    """Elasticity: checkpoint written under one mesh restores onto a
    different device count (4 -> 8 devices) with identical values."""
    code = """
import json, tempfile, os
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import checkpoint as ckpt

devs = jax.devices()
n = len(devs)
from repro.launch.mesh import make_mesh
mesh_a = make_mesh((n // 4, 4), ("data", "model"))
tree = {"w": jnp.arange(64 * 8, dtype=jnp.float32).reshape(64, 8)}
sharded = jax.device_put(tree, NamedSharding(mesh_a, P("data", "model")))
d = tempfile.mkdtemp()
ckpt.save(d, 1, sharded)

# restart onto a different mesh shape (elastic scale-up of model axis)
mesh_b = make_mesh((n // 8, 8), ("data", "model"))
restored, step, _ = ckpt.load(d, tree)
resharded = jax.device_put(restored, NamedSharding(mesh_b, P("data", "model")))
ok = bool(jnp.all(resharded["w"] == tree["w"]))
print(json.dumps({"ok": ok, "nshards_a": 4, "nshards_b": 8}))
"""
    out = _sub(code, devices=8)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["ok"]
