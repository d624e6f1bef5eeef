"""Serving subsystem: flash-attention accuracy contract, paged FF KV
cache, and continuous-batching engine parity with the sequential baseline.

Contracts under test (docs/DESIGN_serving.md):
  * accurate-tier flash attention ("ff"/"pallas") within 2^-40 of the f64
    oracle on long-K rows (the compensated online softmax claim);
  * the paged KV cache round-trips bitwise, pages FF hi/lo limbs through
    ONE block table, and serializes to plain numpy;
  * the engine is token-for-token ``greedy_generate`` under mixed-length
    continuous batching with join/evict (logprobs agree to batched-matmul
    ulp noise, NOT bitwise — XLA tiles B=8 matmuls differently than B=1);
  * FF token-logprob scoring within 2^-40 of the f64 oracle.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.ff as ff
from repro.core.policy import PrecisionPolicy
from repro.kernels.ff_attention import attention_f64
from repro.models import init_params
from repro.models.config import ModelConfig
from repro.serve import PagedKVCache, Request, ServeEngine
from repro.serve.paged_kv import ff_merge, ff_split
from repro.train.serve_step import greedy_generate, token_logprob_ff

TOL = 2.0 ** -40


# --------------------------------------------------------------------------
# flash-attention accuracy contract
# --------------------------------------------------------------------------

def _attn_operands(rng, B=2, Sq=4, Skv=768, H=2, KV=1, hd=32):
    q = jnp.asarray(rng.standard_normal((B, Sq, H, hd)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, Skv, KV, hd)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, Skv, KV, hd)).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("impl", ["ff", "pallas"])
def test_flash_attention_ulp_contract(rng, impl):
    """Accurate tiers <= 2^-40 of the f64 oracle on long-K rows (per-row
    relative to max|ref| — the paper's error model for dot-product
    accumulation)."""
    q, k, v = _attn_operands(rng)
    got = ff.attention(q, k, v, causal=False, impl=impl, return_ff=True)
    ref = attention_f64(q, k, v, causal=False, return_ff=True)
    r64 = np.asarray(ref.hi, np.float64) + np.asarray(ref.lo, np.float64)
    g64 = np.asarray(got.hi, np.float64) + np.asarray(got.lo, np.float64)
    den = np.abs(r64).max(axis=(1, 3), keepdims=True)
    err = float((np.abs(g64 - r64) / den).max())
    assert err <= TOL, f"attention[{impl}] err {err:.3e} > 2^-40"


def test_flash_attention_fast_vs_accurate(rng):
    """The fast tier agrees with the accurate tier to f32 working
    precision (sanity: both compute the same softmax(QK^T)V)."""
    q, k, v = _attn_operands(rng, Skv=256)
    fast = np.asarray(ff.attention(q, k, v, causal=False, impl="fast"))
    acc = np.asarray(ff.attention(q, k, v, causal=False, impl="ff"))
    assert np.max(np.abs(fast - acc)) < 1e-5


def test_flash_attention_kv_len_rows(rng):
    """Per-row kv_len masking matches slicing each row by hand."""
    q, k, v = _attn_operands(rng, B=3, Skv=96)
    kv_len = jnp.asarray([17, 96, 41], jnp.int32)
    got = ff.attention(q, k, v, causal=False, kv_len=kv_len, impl="ff",
                       return_ff=True)
    for b, n in enumerate(np.asarray(kv_len)):
        ref = attention_f64(q[b:b + 1], k[b:b + 1, :n], v[b:b + 1, :n],
                            causal=False, return_ff=True)
        r64 = np.asarray(ref.hi, np.float64) + np.asarray(ref.lo, np.float64)
        g64 = (np.asarray(got.hi[b:b + 1], np.float64)
               + np.asarray(got.lo[b:b + 1], np.float64))
        den = np.abs(r64).max(axis=(1, 3), keepdims=True)
        assert float((np.abs(g64 - r64) / den).max()) <= TOL


# --------------------------------------------------------------------------
# paged KV cache
# --------------------------------------------------------------------------

def _kv_tensors(rng, L=2, S=21, KV=2, hd=8):
    return {"k": jnp.asarray(rng.standard_normal((L, S, KV, hd)),
                             jnp.float32),
            "v": jnp.asarray(rng.standard_normal((L, S, KV, hd)),
                             jnp.float32)}


def test_paged_roundtrip_bitwise(rng):
    """write_prefill -> gather is bitwise the storage cast of the input,
    for every kv_mode; FF limbs recombine exactly (the split residual sum
    is exact in f32)."""
    tensors = _kv_tensors(rng)
    for mode in ("bf16", "f32", "ff_bf16"):
        kv = PagedKVCache(2, 2, 8, num_pages=12, page_size=4, max_seqs=2,
                          max_ctx=32, kv_mode=mode)
        kv.alloc(0, 21)
        kv.write_prefill(0, tensors)
        back = kv.gather(0)
        for base in ("k", "v"):
            x = tensors[base]
            if mode == "bf16":
                want = np.asarray(x.astype(jnp.bfloat16))
            elif mode == "f32":
                want = np.asarray(x)
            else:   # double-bf16 limbs recombine to hi+lo exactly
                hi, lo = ff_split(x)
                want = np.asarray(ff_merge(hi, lo))
            assert np.array_equal(np.asarray(back[base], np.float32),
                                  np.asarray(want, np.float32)), \
                f"{mode}/{base} round-trip not bitwise"


def test_ff_bf16_pages_beat_single_bf16(rng):
    """The double-bf16 limb pair carries ~2x the mantissa of one bf16."""
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    hi, lo = ff_split(x)
    err_ff = np.max(np.abs(np.asarray(ff_merge(hi, lo)) - np.asarray(x)))
    err_bf = np.max(np.abs(np.asarray(hi.astype(jnp.float32))
                           - np.asarray(x)))
    assert err_ff <= 2.0 ** -14 * float(np.abs(np.asarray(x)).max())
    assert err_ff < err_bf / 16


def test_paged_evict_reuse(rng):
    """Evicting a slot recycles its pages; a new sequence writing into the
    recycled pages reads back its own data bitwise."""
    kv = PagedKVCache(2, 2, 8, num_pages=6, page_size=4, max_seqs=2,
                      max_ctx=24, kv_mode="f32")
    a = _kv_tensors(rng, S=20)
    kv.alloc(0, 20)
    kv.write_prefill(0, a)
    used = set(int(p) for p in kv.block_table[0] if p >= 0)
    assert len(kv.free_pages) == 1
    kv.free_slot(0)
    assert len(kv.free_pages) == 6
    b = _kv_tensors(rng, S=20)
    kv.alloc(1, 20)                      # must reuse evicted pages
    assert used & set(int(p) for p in kv.block_table[1] if p >= 0)
    kv.write_prefill(1, b)
    back = kv.gather(1)
    assert np.array_equal(np.asarray(back["k"]), np.asarray(b["k"]))


def test_paged_alloc_guards():
    kv = PagedKVCache(1, 1, 4, num_pages=4, page_size=4, max_seqs=2,
                      max_ctx=16)
    kv.alloc(0, 13)                      # 4 pages
    assert not kv.can_alloc(1)
    with pytest.raises(RuntimeError):
        kv.alloc(1, 1)                   # pool exhausted
    with pytest.raises(RuntimeError):
        kv.alloc(0, 4)                   # slot occupied


def test_paged_state_roundtrip(rng):
    """to_state/from_state: plain numpy dict, bitwise planes + bookkeeping
    (including the FF limb planes and their SHARED block table)."""
    for mode in ("bf16", "ff_bf16"):
        kv = PagedKVCache(2, 2, 8, num_pages=10, page_size=4, max_seqs=2,
                          max_ctx=32, kv_mode=mode)
        kv.alloc(0, 9)
        kv.write_prefill(0, _kv_tensors(rng, S=9))
        state = kv.to_state()
        assert all(isinstance(v, np.ndarray) for v in state.values())
        kv2 = PagedKVCache.from_state(state)
        assert kv2.kv_mode == mode
        assert np.array_equal(kv2.block_table, kv.block_table)
        assert np.array_equal(kv2.seq_lens, kv.seq_lens)
        assert kv2.free_pages == kv.free_pages
        for name in kv.planes:
            a = np.asarray(kv.planes[name], np.float32)
            b = np.asarray(kv2.planes[name], np.float32)
            assert np.array_equal(a, b), f"{mode}/{name} plane drifted"
        assert np.array_equal(np.asarray(kv2.gather(0)["v"], np.float32),
                              np.asarray(kv.gather(0)["v"], np.float32))


# --------------------------------------------------------------------------
# engine vs greedy baseline
# --------------------------------------------------------------------------

CFG = ModelConfig(name="serve-test", family="dense", num_layers=2,
                  d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                  vocab_size=512, max_seq_len=128, compute_dtype="float32",
                  remat=False)


@pytest.fixture(scope="module")
def served():
    return init_params(CFG, jax.random.PRNGKey(0))


def _mixed_requests(rng, n, max_new):
    lens = rng.integers(5, 23, size=n)
    return [Request(uid=i,
                    prompt=rng.integers(1, CFG.vocab_size,
                                        size=int(l)).astype(np.int32),
                    max_new=max_new)
            for i, l in enumerate(lens)]


def test_engine_matches_greedy_mixed_lengths(served, rng):
    """5 mixed-length requests through max_batch=2 (forces joins and
    evictions) == per-request greedy_generate token-for-token; logprobs to
    batched-matmul ulp noise."""
    reqs = _mixed_requests(rng, 5, max_new=8)
    eng = ServeEngine(served, CFG, max_batch=2, page_size=8, max_ctx=48)
    for r in reqs:
        eng.submit(r)
    res = eng.run()
    assert sorted(res) == [r.uid for r in reqs]
    for r in reqs:
        toks, lps = greedy_generate(served, CFG, jnp.asarray(r.prompt[None]),
                                    r.max_new, cache_len=48,
                                    return_logprobs=True)
        assert np.array_equal(res[r.uid].tokens, np.asarray(toks[0])), \
            f"uid={r.uid}: engine tokens diverge from greedy"
        np.testing.assert_allclose(res[r.uid].logprobs, np.asarray(lps[0]),
                                   atol=1e-4)
        # the FF limb-pair score agrees with its own f32 tier at f32 ulp
        ffsum = res[r.uid].logprobs_ff.sum(axis=1)
        np.testing.assert_allclose(ffsum, res[r.uid].logprobs, atol=1e-4)


def test_engine_eos_matches_greedy(served, rng):
    """Per-sequence EOS early-exit: pick an eos_id the model actually
    emits, and check engine == greedy_generate(eos_id=...) per request
    (rows pin to EOS, loop exits early)."""
    reqs = _mixed_requests(rng, 3, max_new=10)
    probe = greedy_generate(served, CFG, jnp.asarray(reqs[0].prompt[None]),
                            10, cache_len=48)
    eos = int(np.asarray(probe)[0, 3])   # something it emits mid-stream
    eng = ServeEngine(served, CFG, max_batch=2, page_size=8, max_ctx=48,
                      eos_id=eos)
    for r in reqs:
        eng.submit(r)
    res = eng.run()
    for r in reqs:
        want = np.asarray(greedy_generate(
            served, CFG, jnp.asarray(r.prompt[None]), r.max_new,
            cache_len=48, eos_id=eos)[0])
        got = res[r.uid].tokens
        n = len(got)
        assert np.array_equal(got, want[:n])
        # greedy pads finished rows with EOS; the engine stops the row
        assert all(int(t) == eos for t in want[n:])


def test_engine_staggered_submit(served, rng):
    """Requests submitted mid-decode join the running batch at the next
    step() and still match their sequential runs."""
    reqs = _mixed_requests(rng, 3, max_new=6)
    eng = ServeEngine(served, CFG, max_batch=2, page_size=8, max_ctx=48)
    eng.submit(reqs[0])
    eng.step()
    eng.step()
    for r in reqs[1:]:
        eng.submit(r)                    # arrives mid-flight
    res = eng.run()
    assert sorted(res) == [0, 1, 2]
    for r in reqs:
        want = greedy_generate(served, CFG, jnp.asarray(r.prompt[None]),
                               r.max_new, cache_len=48)
        assert np.array_equal(res[r.uid].tokens, np.asarray(want[0]))


def test_greedy_generate_eos_semantics(served, rng):
    """eos_id=None is the historical full-length path; with eos_id set,
    tokens before the first EOS are unchanged and everything after a
    row's first EOS is pinned to EOS."""
    prompt = jnp.asarray(
        rng.integers(1, CFG.vocab_size, size=(2, 9)).astype(np.int32))
    base = np.asarray(greedy_generate(served, CFG, prompt, 10,
                                      cache_len=48))
    eos = int(base[0, 4])
    out = np.asarray(greedy_generate(served, CFG, prompt, 10, cache_len=48,
                                     eos_id=eos))
    assert out.shape[1] <= base.shape[1]
    for b in range(2):
        hits = np.nonzero(base[b, :out.shape[1]] == eos)[0]
        cut = int(hits[0]) + 1 if hits.size else out.shape[1]
        assert np.array_equal(out[b, :cut], base[b, :cut])
        assert np.all(out[b, cut:] == eos)


def test_engine_ff_policy(served, rng):
    """ff.policy(attention="ff") routes the engine decode softmax through
    the compensated FF class; outputs stay within working precision of the
    fast tier."""
    from repro.ff.scope import resolve_policy
    reqs = _mixed_requests(rng, 2, max_new=4)
    with ff.policy(attention="ff", compute_dtype="float32"):
        pol = resolve_policy(None)
        eng = ServeEngine(served, CFG, max_batch=2, page_size=8, max_ctx=48)
    assert pol.attention == "ff" and eng.policy.attention == "ff"
    for r in reqs:
        eng.submit(r)
    res = eng.run()
    for r in reqs:
        # the baseline under the SAME policy: threading is consistent
        want, lps = greedy_generate(served, CFG, jnp.asarray(r.prompt[None]),
                                    r.max_new, cache_len=48, policy=pol,
                                    return_logprobs=True)
        assert np.array_equal(res[r.uid].tokens, np.asarray(want[0]))
        # batched-vs-single matmul tiling noise compounds through the
        # layer stack to ~1e-4 on logprobs (tokens are the hard contract)
        np.testing.assert_allclose(res[r.uid].logprobs, np.asarray(lps[0]),
                                   atol=5e-4)
        # and the FF class only moves outputs at working precision
        plain = greedy_generate(served, CFG, jnp.asarray(r.prompt[None]),
                                r.max_new, cache_len=48)
        assert np.array_equal(res[r.uid].tokens, np.asarray(plain[0]))


# --------------------------------------------------------------------------
# paged KV failure paths (robustness tier — see docs/DESIGN_robustness.md)
# --------------------------------------------------------------------------

def test_paged_grow_failure_paths():
    """grow(): pool exhaustion raises WITHOUT mutating the bookkeeping
    (the engine relies on retry-after-preempt); multi-page jumps are
    structural errors; over-max_ctx allocation is a ValueError."""
    kv = PagedKVCache(1, 1, 4, num_pages=3, page_size=4, max_seqs=2,
                      max_ctx=16)
    with pytest.raises(ValueError):
        kv.alloc(0, 17)                      # length > max_ctx
    kv.alloc(0, 10)                          # 3 pages: pool now empty
    assert kv.grow(0, 12) is None            # same page: no allocation
    with pytest.raises(RuntimeError):
        kv.grow(0, 13)                       # needs a 4th page, pool dry
    assert int(kv.seq_lens[0]) == 12         # failed grow left state alone
    problems, _ = kv.check_integrity()
    assert not problems
    kv2 = PagedKVCache(1, 1, 4, num_pages=6, page_size=4, max_seqs=1,
                       max_ctx=24)
    kv2.alloc(0, 2)
    with pytest.raises(ValueError):
        kv2.grow(0, 12)                      # +2 pages in one call


def test_paged_double_alloc_and_exhaustion():
    kv = PagedKVCache(1, 1, 4, num_pages=4, page_size=4, max_seqs=3,
                      max_ctx=16)
    kv.alloc(0, 13)                          # 4 pages
    with pytest.raises(RuntimeError):
        kv.alloc(1, 1)                       # pool exhausted on alloc
    with pytest.raises(RuntimeError):
        kv.alloc(0, 4)                       # double-alloc of a live slot
    assert not kv.free_pages and int(kv.seq_lens[1]) == 0  # no leak


def test_paged_dirty_page_reuse_masked():
    """free_slot leaves page contents dirty by design; a shorter sequence
    reusing those pages must never observe the stale tail (gather slices
    to the live length; decode masks by lens).

    Local rng: this test was added after the suite's session-scoped rng
    stream was calibrated — consuming shared draws here would shift the
    random inputs of every later accuracy test."""
    rng = np.random.default_rng(779)
    kv = PagedKVCache(2, 2, 8, num_pages=5, page_size=4, max_seqs=2,
                      max_ctx=20, kv_mode="f32")
    big = _kv_tensors(rng, S=20)
    kv.alloc(0, 20)
    kv.write_prefill(0, big)
    kv.free_slot(0)                          # pages dirty with `big`
    small = _kv_tensors(rng, S=9)
    kv.alloc(1, 9)                           # reuses dirty pages
    kv.write_prefill(1, small)
    back = kv.gather(1)
    assert back["k"].shape[1] == 9           # stale tail not observable
    assert np.array_equal(np.asarray(back["k"]), np.asarray(small["k"]))


def test_paged_integrity_audit_and_rebuild():
    """check_integrity catalogues every corruption class; drop_slot +
    rebuild_free_list restore a clean, fully-accounted pool."""
    kv = PagedKVCache(1, 1, 4, num_pages=8, page_size=4, max_seqs=3,
                      max_ctx=16)
    kv.alloc(0, 8)
    kv.alloc(1, 8)
    problems, bad = kv.check_integrity()
    assert not problems and not bad
    kv.block_table[0, 0] = 99                # out of range
    kv.block_table[1, 1] = kv.block_table[1, 0]   # duplicate reference
    problems, bad = kv.check_integrity()
    assert problems and bad == {0, 1}
    for slot in bad:
        kv.drop_slot(slot)                   # pages untrusted: not freed
    kv.rebuild_free_list()
    problems, bad = kv.check_integrity()
    assert not problems and not bad
    assert sorted(kv.free_pages) == list(range(8))  # every page recovered
    kv.alloc(2, 16)                          # pool fully usable again


# --------------------------------------------------------------------------
# batched host sync (eos-less decode)
# --------------------------------------------------------------------------

def test_engine_batched_sync_parity(served):
    """sync_every=4 (one device_get per 4 decode steps) is token-for-token
    AND logprob-for-logprob identical to sync_every=1 — the next input
    token stays on device, so batching the sync changes no math.

    Local rng (not the session fixture): see
    test_paged_dirty_page_reuse_masked."""
    reqs = _mixed_requests(np.random.default_rng(780), 3, max_new=7)
    results = {}
    for n in (1, 4):
        eng = ServeEngine(served, CFG, max_batch=2, page_size=8,
                          max_ctx=48, sync_every=n)
        assert eng.sync_every == n
        for r in reqs:
            eng.submit(Request(uid=r.uid, prompt=r.prompt,
                               max_new=r.max_new))
        results[n] = eng.run()
    for r in reqs:
        a, b = results[1][r.uid], results[4][r.uid]
        assert np.array_equal(a.tokens, b.tokens)
        np.testing.assert_array_equal(a.logprobs, b.logprobs)
        np.testing.assert_array_equal(a.logprobs_ff, b.logprobs_ff)
        want = greedy_generate(served, CFG, jnp.asarray(r.prompt[None]),
                               r.max_new, cache_len=48)
        assert np.array_equal(b.tokens, np.asarray(want[0]))


def test_engine_eos_forces_per_step_sync(served):
    """EOS termination needs the token on the host every step, so eos_id
    overrides sync_every."""
    eng = ServeEngine(served, CFG, max_batch=2, page_size=8, max_ctx=48,
                      eos_id=3, sync_every=8)
    assert eng.sync_every == 1


# --------------------------------------------------------------------------
# FF token-logprob accuracy tier
# --------------------------------------------------------------------------

def _log_softmax_f64(logits: np.ndarray, tok: np.ndarray) -> np.ndarray:
    """f64 oracle as (x_tok - m) - log1p(sum of the non-argmax terms):
    log(sum) would round 1 + r and lose the relative accuracy of a
    near-certain row's small logprob."""
    x = np.asarray(logits, np.float64)
    rows = np.arange(x.shape[0])
    m = x.max(-1)
    e = np.exp(x - m[:, None])
    e[rows, x.argmax(-1)] = 0.0
    return (x[rows, tok] - m) - np.log1p(e.sum(-1))


def _check_logprob_ff(logits: np.ndarray, tok: np.ndarray) -> None:
    s = jax.jit(token_logprob_ff)(jnp.asarray(logits), jnp.asarray(tok))
    ref = _log_softmax_f64(logits, tok)
    got = np.asarray(s.hi, np.float64) + np.asarray(s.lo, np.float64)
    err = np.abs(got - ref) / np.maximum(np.abs(ref), 1e-30)
    assert err.max() <= TOL, (
        f"token_logprob_ff err 2^{np.log2(err.max()):.1f} > 2^-40 on "
        f"{int((err > TOL).sum())} of {len(err)} rows")


@pytest.mark.parametrize("rows", [4, 300])
def test_token_logprob_ff_oracle(rng, rows):
    """Limb-pair score within 2^-40 (relative) of the f64 log-softmax over
    wide-dynamic-range (sigma=8) vocab rows, for the argmax token."""
    logits = (rng.standard_normal((rows, 4096)) * 8.0).astype(np.float32)
    _check_logprob_ff(logits, logits.argmax(-1).astype(np.int32))


@pytest.mark.parametrize("margin", [8.0, 20.0, 40.0])
def test_token_logprob_ff_near_certain(margin):
    """A row whose top logit leads by ``margin``: the argmax logprob is
    about -V e^-margin, far below 1 in magnitude, and must keep 2^-40
    relative; a non-argmax token and a tied top are scored too."""
    r = np.random.default_rng(int(margin))
    logits = r.standard_normal((6, 4096)).astype(np.float32)
    top = r.integers(0, 4096, 6)
    logits[np.arange(6), top] = logits.max(-1) + np.float32(margin)
    logits[5, (top[5] + 1) % 4096] = logits[5, top[5]]         # tie
    tok = logits.argmax(-1).astype(np.int32)
    tok[4] = (top[4] + 7) % 4096                                # not argmax
    _check_logprob_ff(logits, tok)
