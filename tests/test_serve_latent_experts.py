"""DeepSeek-V2's block through the serving engine, at a small size on the
CPU, against plain references on seeded random weights: latent attention
over paged latents (with YaRN), the published group-limited gate, the
dropless layer over the experts a device holds, and the engine's replay
contracts with latent planes.

The end-to-end reference is the benchmark's (``bench/reference/
deepseek_v2.py``: float32 at HIGHEST, the non-absorbed attention, dense
per-expert loops), which imports nothing of the program.  With f32
compute and f32 pages the engine agrees with it to float32 rounding: the
absorbed and the published attention differ only in the order of their
sums (logprobs within 1e-4, and each served token the reference's argmax).
"""

import dataclasses
import importlib.util
import math
import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import moe as moe_lib
from repro.models.layers import (mlp_apply, softmax_mscale, yarn_freqs,
                                 yarn_mscale)
from repro.serve import OK, Request, ServeEngine, UnsupportedModelError
from repro.serve.paged_kv import PagedKVCache

_REF = os.path.join(os.path.dirname(os.path.dirname(__file__)), "bench",
                    "reference", "deepseek_v2.py")
YARN = {"type": "yarn", "factor": 40, "mscale": 0.707,
        "mscale_all_dim": 0.707, "original_max_position_embeddings": 4096,
        "beta_fast": 32, "beta_slow": 1}
# a toy DeepSeek-V2: 1 dense + 2 MoE layers; 16 routed experts in 4
# groups, top-3 of the best 2 groups; this device holds group 1 (4 experts)
CONF = dict(num_hidden_layers=3, first_k_dense_replace=1, hidden_size=64,
            num_attention_heads=4, num_key_value_heads=4, q_lora_rank=48,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
            n_routed_experts=4, published={"n_routed_experts": 16},
            deployment={"held_offset": 4}, n_shared_experts=2,
            num_experts_per_tok=3, n_group=4, topk_group=2, vocab_size=128,
            rms_norm_eps=1e-6, rope_theta=10000.0,
            routed_scaling_factor=16.0, norm_topk_prob=False,
            rope_scaling=YARN)
CFG = dataclasses.replace(
    get_config("deepseek_v2_236b"), num_layers=3, d_model=64, num_heads=4,
    num_kv_heads=4, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, d_ff=96, moe_d_ff=32,
    moe_num_experts=16, moe_held_experts=4, moe_held_offset=4, moe_top_k=3,
    moe_n_group=4, moe_topk_group=2, vocab_size=128,
    compute_dtype="float32", param_dtype="bfloat16", attn_block_q=16,
    attn_block_kv=16, remat=False)
MAX_CTX = 40


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location("deepseek_v2_ref", _REF)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def weights(ref):
    return ref.make_weights(CONF, 7)


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, CFG.vocab_size, size=n).astype(np.int32)
            for n in lens]


def _serve(weights, prompts, max_new=8, **kw):
    kw = {"max_batch": 3, "page_size": 4, "max_ctx": MAX_CTX,
          "kv_mode": "f32", **kw}
    eng = ServeEngine(weights, CFG, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p, max_new=max_new))
    return eng, eng.run()


def test_engine_matches_reference_forward(ref, weights):
    """Prefill, then decode through the latent pages, for rows of three
    lengths sharing the batch: every served token's f32 and FF logprobs
    against the reference's full-forward log-softmax at its position."""
    prompts = _prompts(0, (5, 9, 13))
    eng, res = _serve(weights, prompts)
    V = CFG.vocab_size
    for i, p in enumerate(prompts):
        r = res[i]
        assert r.status == OK, r.detail
        S, n = len(p), len(r.tokens)
        seq = np.zeros((MAX_CTX,), np.int32)
        seq[:S] = p
        seq[S:S + n - 1] = r.tokens[:-1]
        every = np.broadcast_to(np.arange(V, dtype=np.int32)[:, None],
                                (V, n))
        out = ref.score(weights, CONF, seq, S - 1, every)
        logits = np.asarray(out["at"]).T                     # (n, V)
        lp_ref = logits[np.arange(n), r.tokens] - np.asarray(out["lse"])
        np.testing.assert_array_equal(r.tokens, np.asarray(out["top"]))
        np.testing.assert_allclose(r.logprobs, lp_ref, atol=1e-4)
        np.testing.assert_allclose(r.logprobs_ff.sum(-1), lp_ref,
                                   atol=1e-4)
    moe = [e["args"] for e in eng.obs.trace.events()
           if e.get("ph") == "C" and e["name"] == "moe"]
    assert moe and all(a["layers"] == 2 and a["held"] == 4 for a in moe)
    assert all(0 <= a["pairs"] <= 3 * 3 * 2 for a in moe)


def _published_gate(x, router, cfg):
    """DeepSeek-V2's ``MoEGate.forward`` (group_limited_greedy), line by
    line in NumPy float64 over float32 logits."""
    logits = (x.astype(np.float32) @ router.astype(np.float32)).astype(
        np.float64)
    scores = np.exp(logits - logits.max(-1, keepdims=True))
    scores /= scores.sum(-1, keepdims=True)
    T, E = scores.shape
    group_scores = scores.reshape(T, cfg.moe_n_group, -1).max(-1)
    group_idx = np.argsort(-group_scores, axis=-1)[:, :cfg.moe_topk_group]
    group_mask = np.zeros_like(group_scores)
    np.put_along_axis(group_mask, group_idx, 1, axis=-1)
    score_mask = np.repeat(group_mask, E // cfg.moe_n_group, axis=-1)
    tmp = np.where(score_mask > 0, scores, 0.0)
    idx = np.argsort(-tmp, axis=-1)[:, :cfg.moe_top_k]
    weight = np.take_along_axis(tmp, idx, axis=-1)
    return weight * cfg.moe_routed_scaling, idx


def test_gate_is_the_published_rule():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((64, CFG.d_model)).astype(np.float32)
    router = (rng.standard_normal((CFG.d_model, CFG.moe_num_experts))
              / 8).astype(np.float32)
    gates, idx = moe_lib.moe_gate(jnp.asarray(router), jnp.asarray(x), CFG)
    want_g, want_i = _published_gate(x, router, CFG)
    order = np.argsort(np.asarray(idx), axis=-1)
    np.testing.assert_array_equal(
        np.take_along_axis(np.asarray(idx), order, -1),
        np.sort(want_i, axis=-1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(gates), order, -1),
        np.take_along_axis(want_g, np.argsort(want_i, -1), -1), rtol=1e-5)
    # unnormalised: the gates of a token sum to 16 x their probability mass
    assert np.all(np.asarray(gates).sum(-1) < CFG.moe_routed_scaling)
    # only experts of the two best groups are ever chosen
    groups = np.asarray(idx) // (CFG.moe_num_experts // CFG.moe_n_group)
    assert all(len(set(g)) <= CFG.moe_topk_group for g in groups)


def _expert(p, e, x):
    g = x @ p["w_gate"][e]
    return (g / (1 + np.exp(-g)) * (x @ p["w_up"][e])) @ p["w_down"][e]


def _f32_params(cfg, seed):
    p = moe_lib.moe_params(jax.random.PRNGKey(seed), cfg)
    return jax.tree_util.tree_map(lambda a: np.array(a, np.float32), p)


def _skewed_layer_matches_a_token_loop():
    """Every token routes to expert 5 (a held one): the layer computes all
    T of its pairs, where a capacity of 1.25 x the even share would drop
    most, and equals a per-token dense loop over the chosen experts."""
    cfg = dataclasses.replace(CFG, compute_dtype="float32")
    p = _f32_params(cfg, 4)
    p["router"][:, 5] += 0.1
    rng = np.random.default_rng(5)
    x = np.abs(rng.standard_normal((24, cfg.d_model))).astype(np.float32)
    out, pairs = moe_lib.moe_serve(
        jax.tree_util.tree_map(jnp.asarray, p), jnp.asarray(x), cfg)
    gates, idx = _published_gate(x, p["router"], cfg)
    assert np.all((idx == 5).any(-1))
    off, held = cfg.moe_held
    assert int(np.asarray(pairs)[5 - off]) == x.shape[0]
    np.testing.assert_array_equal(
        np.asarray(pairs),
        [int(((idx == off + e)).sum()) for e in range(held)])
    shared = {n: p["shared"][n][None] for n in ("w_gate", "w_up", "w_down")}
    want = np.zeros_like(x)
    for t in range(x.shape[0]):
        for g, e in zip(gates[t], idx[t]):
            if off <= e < off + held:
                want[t] += g * _expert(p, e - off, x[t])
        want[t] += _expert(shared, 0, x[t])
    np.testing.assert_allclose(np.asarray(out), want, rtol=2e-4, atol=2e-4)


def test_dropless_under_skewed_routing():
    """The held experts (4 for top-3) run over every token."""
    assert CFG.moe_held[1] <= moe_lib.EVERY_EXPERT_RATIO * CFG.moe_top_k
    _skewed_layer_matches_a_token_loop()


def test_dropless_pairs_form_under_skewed_routing(monkeypatch):
    """The same with the pairs sorted by expert through ``ragged_dot``,
    the form of many held experts against the top-k."""
    monkeypatch.setattr(moe_lib, "EVERY_EXPERT_RATIO", 0)
    monkeypatch.setattr(moe_lib, "EVERY_EXPERT_ROWS", 0)
    _skewed_layer_matches_a_token_loop()


def test_both_expert_forms_agree_on_idle_rows():
    """Rows marked idle get the shared experts alone and count no pair,
    in either form."""
    cfg = dataclasses.replace(CFG, compute_dtype="float32")
    p = jax.tree_util.tree_map(jnp.asarray, _f32_params(cfg, 8))
    x = jax.random.normal(jax.random.PRNGKey(9), (6, cfg.d_model))
    valid = jnp.array([True, False, True, True, False, True])
    every, n_every = moe_lib.moe_serve(p, x, cfg, valid=valid)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(moe_lib, "EVERY_EXPERT_RATIO", 0)
        mp.setattr(moe_lib, "EVERY_EXPERT_ROWS", 0)
        pairs, n_pairs = moe_lib.moe_serve(p, x, cfg, valid=valid)
    np.testing.assert_allclose(np.asarray(every), np.asarray(pairs),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(n_every), np.asarray(n_pairs))
    shared = mlp_apply(p["shared"], x)
    idle = ~np.asarray(valid)
    np.testing.assert_allclose(np.asarray(every)[idle],
                               np.asarray(shared)[idle], rtol=1e-6)
    _, idx = moe_lib.moe_gate(p["router"], x, cfg)
    off, held = cfg.moe_held
    local = np.asarray(idx)[~idle] - off
    assert int(np.asarray(n_every).sum()) == int(
        ((local >= 0) & (local < held)).sum())


def test_expert_shares_sum_to_the_uncut_layer():
    """Under EP over the 4 groups, each device's part of the layer (its
    held experts' pairs), with the shared experts counted once, adds up
    to the layer with every expert held."""
    whole = dataclasses.replace(CFG, moe_held_experts=0, moe_held_offset=0)
    p = jax.tree_util.tree_map(jnp.asarray, _f32_params(whole, 6))
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 10, CFG.d_model))
    want, pairs = moe_lib.moe_serve(p, x, whole)
    per = CFG.moe_num_experts // CFG.moe_n_group
    shared = mlp_apply(p["shared"], x)
    total = shared
    for g in range(CFG.moe_n_group):
        share = dataclasses.replace(whole, moe_held_experts=per,
                                    moe_held_offset=g * per)
        part = {**p, **{n: p[n][g * per:(g + 1) * per]
                        for n in ("w_gate", "w_up", "w_down")}}
        out, n_pairs = moe_lib.moe_serve(part, x, share)
        np.testing.assert_array_equal(
            np.asarray(n_pairs), np.asarray(pairs)[g * per:(g + 1) * per])
        total = total + (out - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert int(np.asarray(pairs).sum()) == 20 * CFG.moe_top_k


def test_yarn_frequencies_and_mscale():
    """DeepSeek-V2's rope_scaling by the published formulas: the
    correction range of 32 .. 1 rotations over 4096 positions is rotary
    pairs 10 .. 23 of 32; plain frequencies below it, divided by 40 above
    it, linear between; the softmax factor (0.1 x 0.707 x ln 40 + 1)^2."""
    cfg = get_config("deepseek_v2_236b")
    dim = cfg.qk_rope_head_dim
    f = np.asarray(yarn_freqs(dim, cfg), np.float64)
    plain = 1.0 / 10000.0 ** (np.arange(0, dim, 2) / dim)
    low = math.floor(dim * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(10000)))
    high = math.ceil(dim * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(f[:low + 1], plain[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(f[high:], plain[high:] / 40, rtol=1e-6)
    i = np.arange(low, high + 1)
    ramp = (i - low) / (high - low)
    np.testing.assert_allclose(f[low:high + 1], plain[low:high + 1]
                               * (1 - ramp) + plain[low:high + 1] / 40
                               * ramp, rtol=1e-6)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40, 0.707) == pytest.approx(m)
    assert softmax_mscale(cfg) == pytest.approx(m * m)
    assert softmax_mscale(cfg) == pytest.approx(1.5896, abs=1e-4)
    assert softmax_mscale(get_config("granite_3_2b")) == 1.0


def test_snapshot_restore_replays_latent_planes(weights):
    """Interrupt after 3 decode steps, restore the MLA planes into a fresh
    engine, run on: tokens and FF limbs bit for bit the uninterrupted
    run's."""
    prompts = _prompts(1, (6, 11, 7))
    _, base = _serve(weights, prompts)
    src = ServeEngine(weights, CFG, max_batch=3, page_size=4,
                      max_ctx=MAX_CTX, kv_mode="f32")
    for i, p in enumerate(prompts):
        src.submit(Request(uid=i, prompt=p, max_new=8))
    for _ in range(3):
        src.step()
    arrays, meta = src.snapshot()
    assert {"kv.plane_c_kv", "kv.plane_k_rope", "kv.latent"} <= set(arrays)
    dst = ServeEngine(weights, CFG, max_batch=3, page_size=4,
                      max_ctx=MAX_CTX, kv_mode="f32")
    dst.restore(arrays, meta, downtime_s=0.0)
    resumed = dst.run()
    for uid in base:
        assert resumed[uid].status == OK
        np.testing.assert_array_equal(resumed[uid].tokens, base[uid].tokens)
        np.testing.assert_array_equal(resumed[uid].logprobs_ff,
                                      base[uid].logprobs_ff)


def test_preemption_replays_latent_planes(weights):
    """reserve="prompt" on a pool too small for all three trajectories:
    the youngest row is preempted and re-prefilled, and every request's
    tokens and FF limbs are bit for bit those of a roomy pool."""
    prompts = _prompts(2, (7, 8, 9))
    _, base = _serve(weights, prompts)
    eng, res = _serve(weights, prompts, num_pages=8, reserve="prompt")
    assert eng.guard_stats["preempted"] >= 1
    for uid in base:
        assert res[uid].status == OK
        np.testing.assert_array_equal(res[uid].tokens, base[uid].tokens)
        np.testing.assert_array_equal(res[uid].logprobs_ff,
                                      base[uid].logprobs_ff)


@pytest.mark.parametrize("kv_mode", ["bf16", "f32", "ff_bf16"])
def test_gqa_planes_unchanged(kv_mode):
    """The GQA cache keeps its planes, shapes and snapshot keys; MLA's
    planes are the latent pair, through the same block table."""
    gqa = PagedKVCache(2, 3, 8, num_pages=5, page_size=4, max_seqs=2,
                       max_ctx=16, kv_mode=kv_mode)
    names = {"bf16": ("k", "v"), "f32": ("k", "v"),
             "ff_bf16": ("k_hi", "k_lo", "v_hi", "v_lo")}[kv_mode]
    assert tuple(gqa.planes) == names
    assert all(p.shape == (2, 5, 4, 3, 8) for p in gqa.planes.values())
    assert set(gqa.to_state()) == {
        "block_table", "seq_lens", "free_pages", "geometry", "kv_mode"} | {
        f"plane_{n}" for n in names}
    mla = PagedKVCache(2, 3, 8, num_pages=5, page_size=4, max_seqs=2,
                       max_ctx=16, kv_mode=kv_mode, latent=(32, 8))
    limbs = ("_hi", "_lo") if kv_mode == "ff_bf16" else ("",)
    assert {n: p.shape for n, p in mla.planes.items()} == {
        **{f"c_kv{x}": (2, 5, 4, 32) for x in limbs},
        **{f"k_rope{x}": (2, 5, 4, 8) for x in limbs}}
    mla.alloc(1, 6)
    rng = np.random.default_rng(0)
    mla.write_prefill(1, {
        "c_kv": jnp.asarray(rng.standard_normal((2, 6, 32)), jnp.float32),
        "k_rope": jnp.asarray(rng.standard_normal((2, 6, 8)), jnp.float32)})
    back = PagedKVCache.from_state(mla.to_state())
    assert back.kind == "mla" and back.check_integrity() == ([], set())
    for base in ("c_kv", "k_rope"):
        np.testing.assert_array_equal(np.asarray(back.gather(1)[base]),
                                      np.asarray(mla.gather(1)[base]))


def test_engine_refuses_what_it_cannot_serve(weights):
    with pytest.raises(UnsupportedModelError) as ei:
        ServeEngine(weights, dataclasses.replace(CFG, use_mla=False))
    assert ei.value.field == "use_mla"
    with pytest.raises(UnsupportedModelError) as ei:
        ServeEngine(weights, dataclasses.replace(CFG, moe_num_experts=0))
    assert ei.value.field == "moe_num_experts"
    with pytest.raises(UnsupportedModelError) as ei:
        ServeEngine(weights, dataclasses.replace(CFG, moe_every=2))
    assert ei.value.field == "moe_every"
    with pytest.raises(UnsupportedModelError) as ei:
        ServeEngine(weights, dataclasses.replace(CFG, family="ssm"))
    assert "moe" in ei.value.supported and "ROADMAP" not in str(ei.value)
