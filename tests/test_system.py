"""End-to-end behaviour tests for the paper's system: the float-float
precision policy driving a full train->checkpoint->serve cycle."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core.policy import PrecisionPolicy
from repro.core.selfcheck import check_eft_safe
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import init_params, init_cache, prefill, decode_step
from repro.models.config import ModelConfig
from repro.optim.adamw import AdamW
from repro.train.train_step import make_train_step


def test_system_train_then_serve(tmp_path):
    """Full cycle: EFT-safe toolchain -> FF-policy training descends ->
    checkpoint -> restore -> serve greedily from the trained weights."""
    assert check_eft_safe()

    cfg = ModelConfig(
        name="sys", family="dense", num_layers=2, d_model=128, num_heads=4,
        num_kv_heads=2, d_ff=256, vocab_size=256, head_dim=32,
        max_seq_len=128, attn_block_q=32, attn_block_kv=32, loss_chunk=32,
        compute_dtype="float32", remat=False)
    policy = PrecisionPolicy.make("ff_reduce", compute_dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = AdamW(learning_rate=3e-3, ff=True)
    opt_state = opt.init(params)
    step = jax.jit(make_train_step(cfg, policy, opt))
    data = SyntheticLM(DataConfig(vocab_size=256, seq_len=64, global_batch=8))

    losses = []
    for i in range(30):
        batch = {k: jnp.asarray(v) for k, v in data.batch(i).items()}
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses  # actually learns

    # checkpoint round-trip
    from repro.checkpoint import checkpoint as ckpt
    ckpt.save(str(tmp_path), 30, {"params": params})
    restored, _, _ = ckpt.load(str(tmp_path), {"params": params})
    params = jax.tree_util.tree_map(jnp.asarray, restored["params"])

    # serve from trained weights
    B, S = 2, 16
    prompt = jnp.asarray(data.batch(99)["tokens"][:B, :S])
    cache = init_cache(cfg, B, 64, dtype=jnp.float32)
    logits, cache = jax.jit(lambda p, b, c: prefill(p, b, cfg, c, policy))(
        params, {"tokens": prompt}, cache)
    assert bool(jnp.all(jnp.isfinite(logits)))
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    logits2, _ = jax.jit(
        lambda p, t, c: decode_step(p, t, jnp.int32(S), c, cfg, policy))(
        params, tok, cache)
    assert bool(jnp.all(jnp.isfinite(logits2)))


def test_compile_cache_location(tmp_path, monkeypatch):
    """The launchers' cache goes where JAX_COMPILATION_CACHE_DIR says, else
    into the checkout's .jax_cache, and stays off outside a checkout."""
    from repro.launch import compile_cache

    set_dirs = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: set_dirs.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    assert compile_cache.enable_compile_cache() == str(tmp_path / "env")
    assert set_dirs == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    monkeypatch.setattr(compile_cache, "CHECKOUT", str(tmp_path))
    assert compile_cache.enable_compile_cache() is None
    assert set_dirs == []
    (tmp_path / "pyproject.toml").write_text("")
    path = str(tmp_path / ".jax_cache")
    assert compile_cache.enable_compile_cache() == path
    assert set_dirs == [("jax_compilation_cache_dir", path)]
