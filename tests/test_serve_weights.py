"""The serving engine holds its weights in the compute dtype
(docs/DESIGN_serving.md §4):

  * an engine handed float32 weights serves tokens, f32 scores and FF
    (hi, lo) scores bitwise equal to ``greedy_generate`` on the same
    float32 tree, whose programs convert every weight in the program, for
    a bfloat16-compute and a float32-compute config under ``ff_master``
    and ``ff_reduce``;
  * the caller's tree is never mutated or deleted, and serves a second
    engine; a tree the caller dropped is freed by the conversion, and an
    engine the caller dropped frees its copy at once;
  * after the first step every floating leaf the engine holds is in the
    compute dtype;
  * the conversion is recorded once: a ``weights_resident`` instant and
    the ``serve_weight_bytes{dtype}`` gauges.
"""

import dataclasses
import gc
import weakref

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.ff as ff
from repro import obs
from repro.models import init_cache, init_params
from repro.models.config import ModelConfig
from repro.serve import Request, ServeEngine
from repro.train.serve_step import (greedy_generate, make_decode_step,
                                    make_prefill_step, token_logprob,
                                    token_logprob_ff)

CFG = ModelConfig(name="serve-weights-test", family="dense", num_layers=2,
                  d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                  vocab_size=512, max_seq_len=128, compute_dtype="bfloat16",
                  remat=False)
CFG_F32 = dataclasses.replace(CFG, compute_dtype="float32")
MAX_CTX = 48


def _params(cfg=CFG):
    return init_params(cfg, jax.random.PRNGKey(0))


def _requests(n=3, max_new=6, seed=3):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(
        1, CFG.vocab_size, size=int(rng.integers(6, 14))).astype(np.int32),
        max_new=max_new) for i in range(n)]


def _serve(params, cfg=CFG, reqs=None, **kw):
    eng = ServeEngine(params, cfg, page_size=8, max_ctx=MAX_CTX,
                      obs=obs.Observer(), **kw)
    for r in reqs or _requests():
        eng.submit(r)
    return eng, eng.run()


def _greedy_scored(params, cfg, prompt, max_new):
    """``greedy_generate``'s loop at batch 1, returning each token's f32
    and FF (hi, lo) scores as well."""
    pf = jax.jit(make_prefill_step(cfg))
    dc = jax.jit(make_decode_step(cfg))
    score = jax.jit(token_logprob)

    def score_ff(lg, tk):
        r = token_logprob_ff(lg, tk)
        return r.hi, r.lo
    score_ff = jax.jit(score_ff)
    logits, cache = pf(params, {"tokens": jnp.asarray(prompt[None])},
                       init_cache(cfg, 1, MAX_CTX))
    toks, lps, lff = [], [], []
    for t in range(max_new):
        if t:
            logits, cache = dc(params, toks[-1][:, None],
                               jnp.int32(len(prompt) + t - 1), cache)
        toks.append(jnp.argmax(logits, -1).astype(jnp.int32))
        lps.append(score(logits, toks[-1]))
        lff.append(jnp.stack(score_ff(logits, toks[-1]), -1))
    return (np.asarray(jnp.concatenate(toks)),
            np.asarray(jnp.concatenate(lps)),
            np.asarray(jnp.concatenate(lff)))


@pytest.mark.parametrize("level", ["ff_master", "ff_reduce"])
@pytest.mark.parametrize("cfg", [CFG, CFG_F32], ids=["bf16", "f32"])
def test_engine_bitwise_greedy_on_f32_tree(cfg, level):
    """Batch 1, so the engine's matmuls tile as the baseline's do."""
    params = _params(cfg)
    reqs = _requests()
    with ff.policy(level):
        eng, res = _serve(params, cfg, reqs, max_batch=1)
        for r in reqs:
            toks, lps = greedy_generate(
                params, cfg, jnp.asarray(r.prompt[None]), r.max_new,
                cache_len=MAX_CTX, return_logprobs=True)
            t, lp, lp_ff = _greedy_scored(params, cfg, r.prompt, r.max_new)
            assert np.array_equal(t, np.asarray(toks[0]))
            assert np.array_equal(lp, np.asarray(lps[0]))
            got = res[r.uid]
            assert np.array_equal(got.tokens, t), r.uid
            assert np.array_equal(got.logprobs, lp), r.uid
            assert np.array_equal(got.logprobs_ff, lp_ff), r.uid
    assert all(w.dtype == cfg.compute_dtype
               for w in jax.tree_util.tree_leaves(eng.params))


def test_caller_tree_untouched_and_serves_again():
    params = _params()
    before = [np.asarray(w) for w in jax.tree_util.tree_leaves(params)]
    eng1, res1 = _serve(params, max_batch=2)
    leaves = jax.tree_util.tree_leaves(params)
    assert all(not w.is_deleted() and w.dtype == jnp.float32
               for w in leaves)
    assert all(np.array_equal(np.asarray(w), b)
               for w, b in zip(leaves, before))
    eng2, res2 = _serve(params, max_batch=2)
    for uid, r in res1.items():
        assert np.array_equal(r.tokens, res2[uid].tokens)
        assert np.array_equal(r.logprobs_ff, res2[uid].logprobs_ff)


def test_resident_leaves_after_first_step():
    params = _params()
    eng = ServeEngine(params, CFG, max_batch=2, page_size=8,
                      max_ctx=MAX_CTX)
    assert eng.params is params         # nothing converted at construction
    eng.submit(_requests(1)[0])
    eng.step()
    leaves = jax.tree_util.tree_leaves(eng.params)
    assert leaves and all(w.dtype == jnp.bfloat16 for w in leaves)
    assert (jax.tree_util.tree_structure(eng.params)
            == jax.tree_util.tree_structure(params))
    eng32 = ServeEngine(params, CFG_F32, max_batch=2, page_size=8,
                        max_ctx=MAX_CTX)
    eng32.submit(_requests(1)[0])
    eng32.step()
    assert all(a is b for a, b in zip(
        jax.tree_util.tree_leaves(eng32.params),
        jax.tree_util.tree_leaves(params)))


def test_dropped_tree_is_freed_by_the_conversion():
    params = _params()
    refs = [weakref.ref(w) for w in jax.tree_util.tree_leaves(params)]
    eng = ServeEngine(params, CFG, max_batch=2, page_size=8,
                      max_ctx=MAX_CTX)
    del params
    assert all(r() is not None for r in refs)
    eng.submit(_requests(1)[0])
    eng.step()
    assert all(r() is None for r in refs)


def test_dropped_engine_frees_its_weights_at_once():
    """No reference cycle keeps an engine, and so its resident copy of the
    weights, alive until the cyclic collector runs."""
    eng, _ = _serve(_params(), max_batch=2)
    refs = [weakref.ref(eng)] + [
        weakref.ref(w) for w in jax.tree_util.tree_leaves(eng.params)]
    gc.disable()
    try:
        del eng
        assert all(r() is None for r in refs)
    finally:
        gc.enable()


@pytest.mark.parametrize("cfg", [CFG, CFG_F32], ids=["bf16", "f32"])
def test_weights_resident_recorded_once(cfg):
    params = _params(cfg)
    leaves = jax.tree_util.tree_leaves(params)
    stored = sum(w.nbytes for w in leaves)
    eng, _ = _serve(params, cfg, max_batch=2)
    eng.prefill(_requests(1)[0].prompt)
    marks = [e["args"] for e in eng.obs.trace.events()
             if e["name"] == "weights_resident"]
    assert len(marks) == 1
    held = sum(w.nbytes for w in jax.tree_util.tree_leaves(eng.params))
    converted = len(leaves) if cfg is CFG else 0
    assert marks[0]["leaves"] == converted
    assert marks[0]["dtype"] == cfg.compute_dtype
    assert marks[0]["bytes_before"] == stored
    assert marks[0]["bytes_after"] == held
    assert held == (stored // 2 if cfg is CFG else stored)
    assert marks[0]["seconds"] >= 0.0
    gauges = {k: v for k, v in eng.obs.registry.snapshot()["gauges"].items()
              if k.startswith("serve_weight_bytes")}
    assert gauges == {
        f'serve_weight_bytes{{dtype="{cfg.compute_dtype}"}}': held}
