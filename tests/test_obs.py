"""Observability subsystem: metrics registry, dispatch telemetry, guard
violation accumulation, and the serving request trace.

Contracts under test (docs/DESIGN_observability.md):
  * the registry's counter/gauge/histogram primitives, the snapshot/delta
    API, and both expositions (JSON, Prometheus text 0.0.4);
  * ``ff.dispatch.resolve_name`` records one resolution counter per
    (op, impl, source, backend, shape-bucket) naming the winning impl —
    and recording happens at trace time only, so jit steady-state is
    untouched;
  * ``GuardScope.record`` keeps accumulating the per-(op, kind)
    ``ff_guard_violations_total`` counter after the first (warn-once
    suppressed) warning;
  * the engine's request trace has IDENTICAL span structure under
    sync_every=1 and sync_every=4 (spans mark lifecycle transitions, not
    host syncs), exports as Perfetto-loadable Chrome JSON, and keeps
    timestamps monotone.
"""

import json
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro.ff as ff
from repro import obs
from repro.ff.guard import FFGuardWarning, GuardScope
from repro.models import init_params
from repro.models.config import ModelConfig
from repro.obs.registry import LOG2_BUCKETS, MetricsRegistry
from repro.obs.trace import TraceRecorder
from repro.serve import Request, ServeEngine


# --------------------------------------------------------------------------
# registry primitives
# --------------------------------------------------------------------------

def test_counter_gauge_labels():
    reg = MetricsRegistry()
    c = reg.counter("req_total", status="OK")
    c.inc()
    c.inc(4)
    assert c.value == 5
    # same (name, labels) -> same series; different labels -> different
    assert reg.counter("req_total", status="OK") is c
    assert reg.counter("req_total", status="TIMEOUT") is not c
    g = reg.gauge("depth")
    g.set(7)
    g.inc(-2)
    assert g.value == 5
    snap = reg.snapshot()
    assert snap["counters"]['req_total{status="OK"}'] == 5
    assert snap["gauges"]["depth"] == 5


def test_histogram_log2_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds")
    for v in (1e-6, 1e-3, 1e-3, 0.5, 100.0):   # 100s -> +Inf overflow
        h.observe(v)
    snap = reg.snapshot()["histograms"]["lat_seconds"]
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(100.502001, rel=1e-6)
    buckets = snap["buckets"]
    assert len(buckets) == len(LOG2_BUCKETS) + 1
    assert buckets[-1][0] == "+Inf" and buckets[-1][1] == 5
    # cumulative and monotone
    counts = [n for _, n in buckets]
    assert counts == sorted(counts)
    # 1e-6 lands in the first (<= 2^-20 s ~ 0.95us... next) buckets; the
    # precise invariant: every observation <= its bucket's upper bound
    assert counts[0] <= 1


def test_snapshot_delta():
    reg = MetricsRegistry()
    c = reg.counter("n")
    c.inc(3)
    before = reg.snapshot()
    c.inc(2)
    reg.gauge("g").set(9)
    reg.histogram("h").observe(0.01)
    d = reg.delta(before)
    assert d["counters"]["n"] == 2
    assert d["gauges"]["g"] == 9           # gauges pass through
    assert d["histograms"]["h"]["count"] == 1


def test_prometheus_exposition():
    reg = MetricsRegistry()
    reg.counter("req_total", status="OK").inc(2)
    reg.histogram("lat_seconds").observe(0.25)
    text = reg.to_prometheus()
    assert "# TYPE req_total counter" in text
    assert 'req_total{status="OK"} 2' in text
    assert "# TYPE lat_seconds histogram" in text
    assert 'lat_seconds_bucket{le="+Inf"} 1' in text
    assert "lat_seconds_count 1" in text
    # every line parses as `name{labels} value` or comment
    for line in text.splitlines():
        assert line.startswith("#") or len(line.rsplit(" ", 1)) == 2
    assert json.loads(reg.to_json())


# --------------------------------------------------------------------------
# dispatch telemetry
# --------------------------------------------------------------------------

def test_dispatch_resolution_counters():
    """resolve_name records the winning impl + source per op; an explicit
    impl= call shows source=explicit, a bare call shows the fall-through
    source, and the matmul series carries the MxKxN shape bucket.

    Local rng (not the session fixture): see
    test_paged_dirty_page_reuse_masked."""
    rng = np.random.default_rng(47)
    a = jnp.asarray(rng.standard_normal((32, 32)).astype(np.float32))
    before = obs.REGISTRY.snapshot()
    ff.matmul(a, a, impl="compensated").to_f32().block_until_ready()
    ff.add(a, a)
    d = obs.REGISTRY.delta(before)["counters"]
    hits = {s: n for s, n in d.items()
            if n and s.startswith("ff_dispatch_resolutions_total")}
    assert any('op="matmul"' in s and 'impl="compensated"' in s
               and 'source="explicit"' in s for s in hits)
    assert any('op="matmul"' in s and 'shape="32x32x32"' in s for s in hits)
    assert any('op="add"' in s for s in hits)


def test_dispatch_telemetry_is_trace_time_only():
    """A jitted FF op resolves at trace time; re-running the compiled
    program must not move the resolution counters."""
    rng = np.random.default_rng(48)
    a = jnp.asarray(rng.standard_normal((16, 16)).astype(np.float32))

    @jax.jit
    def f(x):
        return ff.matmul(x, x, impl="compensated").to_f32()

    f(a).block_until_ready()               # trace + compile: counters move
    before = obs.REGISTRY.snapshot()
    for _ in range(3):
        f(a).block_until_ready()           # steady state: no re-trace
    d = obs.REGISTRY.delta(before)["counters"]
    assert not any(n for s, n in d.items()
                   if s.startswith("ff_dispatch_resolutions_total"))


# --------------------------------------------------------------------------
# guard accumulation past warn-once (satellite fix)
# --------------------------------------------------------------------------

def test_guard_violations_accumulate_past_warn_once():
    """The FFGuardWarning is warn-once per (op, kind), but the
    ``ff_guard_violations_total`` obs counter must keep growing on every
    subsequent record() call."""
    scope = GuardScope("check")
    before = obs.REGISTRY.snapshot()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for _ in range(4):
            scope.record("matmul", "nonfinite", 2)
    guard_warns = [w for w in caught
                   if issubclass(w.category, FFGuardWarning)]
    assert len(guard_warns) == 1, "user-facing warning is warn-once"
    assert scope.counters[("matmul", "nonfinite")] == 8
    d = obs.REGISTRY.delta(before)["counters"]
    series = 'ff_guard_violations_total{kind="nonfinite",op="matmul"}'
    assert d.get(series) == 8, (
        f"obs counter stopped at {d.get(series)} — must accumulate all 4 "
        f"record() calls, not just the warned one")
    warn_series = 'ff_warnings_total{kind="guard"}'
    assert d.get(warn_series, 0) == 1


# --------------------------------------------------------------------------
# serving request trace
# --------------------------------------------------------------------------

CFG = ModelConfig(name="obs-test", family="dense", num_layers=2,
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


def test_trace_structure_invariant_under_sync_every(served):
    """sync_every=4 batches device_gets but must not change the request
    lifecycle: both engines produce the SAME span structure (one queued +
    prefill + decode + request span per uid, same terminal statuses) and
    the same tokens.  The engine track's ``serve.flush`` spans follow the
    host syncs, so their number differs by design, and the structure
    summarises request tracks only.  The trace exports as Chrome JSON
    that survives a json round-trip with monotone timestamps."""
    reqs = _mixed_requests(np.random.default_rng(41), 3, max_new=7)
    structures, results, flushes = {}, {}, {}
    for n in (1, 4):
        eng = ServeEngine(served, CFG, max_batch=2, page_size=8,
                          max_ctx=48, sync_every=n, obs=obs.Observer())
        with obs.enable():
            for r in reqs:
                eng.submit(Request(uid=r.uid, prompt=r.prompt,
                                   max_new=r.max_new))
            results[n] = eng.run()
        structures[n] = eng.obs.trace.span_structure()
        flushes[n] = sum(1 for e in eng.obs.trace.events()
                         if e["ph"] == "X" and e["name"] == "serve.flush")

        payload = json.loads(json.dumps(eng.obs.to_chrome_trace()))
        assert payload["traceEvents"], "trace must not be empty"
        ts = [e["ts"] for e in payload["traceEvents"] if e["ph"] != "M"]
        assert ts == sorted(ts) and all(t >= 0 for t in ts)
        spans = [e for e in payload["traceEvents"] if e["ph"] == "X"]
        assert all(e["dur"] >= 0 for e in spans)
        for r in reqs:                     # exactly one lifecycle each
            tid = eng.obs.trace.request_tid(r.uid)
            names = sorted(e["name"] for e in spans if e["tid"] == tid)
            assert names == ["decode", "prefill", "queued", "request"]

    assert flushes[1] > flushes[4] > 0
    assert all(tid != obs.ENGINE_TID for tid, _, _ in structures[1])
    assert structures[1] == structures[4], (
        "span structure must be a lifecycle invariant, not a function of "
        "host-sync batching")
    for r in reqs:
        assert np.array_equal(results[1][r.uid].tokens,
                              results[4][r.uid].tokens)
        assert results[1][r.uid].status == results[4][r.uid].status


def test_engine_metrics_populated(served):
    """A plain run populates the per-engine counters and latency
    histograms, and token accounting agrees with the results: one
    prefill observation per admission, one flush observation per host
    sync, one build per program (the decode step and each prompt
    length's prefill)."""
    reqs = _mixed_requests(np.random.default_rng(42), 3, max_new=6)
    eng = ServeEngine(served, CFG, max_batch=2, page_size=8, max_ctx=48)
    for r in reqs:
        eng.submit(r)
    res = eng.run()
    snap = eng.obs.snapshot()
    assert snap["counters"]['serve_requests_total{status="OK"}'] == 3
    emitted = sum(len(r.tokens) for r in res.values())
    assert snap["counters"]["serve_tokens_emitted_total"] == emitted
    assert snap["histograms"]["serve_prefill_seconds"]["count"] == 3
    assert snap["histograms"]["serve_flush_seconds"]["count"] > 0
    assert "serve_decode_step_seconds" not in snap["histograms"]
    assert "serve_tokens_per_s" not in snap["histograms"]
    built = {k: v for k, v in snap["counters"].items()
             if k.startswith("serve_programs_built_total")}
    lens = {len(r.prompt) for r in reqs}
    assert built == {
        'serve_programs_built_total{program="jit_step_decode"}': 1,
        'serve_programs_built_total{program="jit_step_prefill"}': len(lens)}


def test_trace_recorder_primitives():
    rec = TraceRecorder()
    rec.name_request_track(5)
    t0 = rec.now()
    rec.complete("request", t0, 10.0, tid=rec.request_tid(5),
                 args={"status": "OK"})
    rec.instant("quarantine", tid=0, args={"uid": 5})
    rec.counter("queue", {"depth": 2})
    rec.complete("serve.step", t0, 5.0)            # engine track
    rec.metadata("program", {"name": "jit_f", "parts": {"dot.1": "head"}})
    out = rec.to_chrome_trace()
    assert out["displayTimeUnit"] == "ms"
    phs = [e["ph"] for e in out["traceEvents"]]
    # metadata first, then timestamp-sorted events
    assert phs[0] == "M" and set(phs) == {"M", "X", "i", "C"}
    assert rec.span_structure() == [(rec.request_tid(5), "request", "OK")]
