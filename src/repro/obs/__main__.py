"""Observability smoke (the CI ``obs`` job's sanity layer).

``python -m repro.obs`` serves a tiny dense model through the
continuous-batching engine with every observability layer on —
per-engine metrics, the process-global dispatch/tune/guard telemetry,
the Chrome request trace, and the ``obs.enable()`` profiler annotations
— then checks the acceptance contract end to end:

  * the metrics snapshot's dispatch-resolution counters name the winning
    impl per resolved op (``ff_dispatch_resolutions_total{op=...,
    impl=..., source=...}``);
  * the trace is Perfetto-loadable Chrome JSON (``json.loads``
    round-trip) with ONE complete ``request`` span per submitted
    request, each carrying a documented terminal status, and monotone
    non-negative timestamps;
  * guard/serve counters and latency histograms populated;
  * the engine's ``serve.*`` step spans and the decode program's
    ``program`` record of named parts are in the trace.

Exits non-zero listing every violated check.  ``--metrics-json`` /
``--trace-out`` write the artifacts (CI uploads them).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_f = os.environ.get("XLA_FLAGS", "")
if "--xla_cpu_max_isa" not in _f:
    os.environ["XLA_FLAGS"] = ("--xla_cpu_max_isa=SSE4_2 " + _f).strip()

FAILURES = []


def check(cond: bool, what: str) -> None:
    mark = "ok" if cond else "FAIL"
    print(f"  [{mark}] {what}")
    if not cond:
        FAILURES.append(what)


def main() -> int:
    ap = argparse.ArgumentParser(prog="python -m repro.obs")
    ap.add_argument("--metrics-json", type=str, default=None)
    ap.add_argument("--trace-out", type=str, default=None)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=6)
    args = ap.parse_args()

    import numpy as np
    import jax

    import repro.ff as ff
    from repro import obs
    from repro.models import init_params
    from repro.models.config import ModelConfig
    from repro.serve import STATUSES, Request, ServeEngine

    cfg = ModelConfig(name="obs-smoke", family="dense", num_layers=2,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=256, max_seq_len=64,
                      compute_dtype="float32", remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)

    print("obs: instrumented serving smoke (guard=check, profiling on)")
    observer = obs.Observer()
    before = obs.REGISTRY.snapshot()
    with obs.enable(), ff.policy("ff_reduce"):
        # an Ozaki-class matmul so the accurate tier shows up in the
        # dispatch telemetry next to the engine's fast-path resolutions
        a = jax.numpy.ones((64, 64), jax.numpy.float32)
        ff.matmul(a, a, impl="ozaki").to_f32().block_until_ready()
        eng = ServeEngine(params, cfg, max_batch=2, page_size=4,
                          max_ctx=32, guard="check", obs=observer)
        for i in range(args.requests):
            eng.submit(Request(
                uid=i,
                prompt=rng.integers(
                    1, cfg.vocab_size,
                    size=int(rng.integers(6, 14))).astype(np.int32),
                max_new=args.max_new))
        results = eng.run()

    check(sorted(results) == list(range(args.requests)),
          "every submitted request terminated")
    check(all(r.status in STATUSES for r in results.values()),
          "every status documented")

    # -- metrics contract --------------------------------------------------
    delta = obs.REGISTRY.delta(before)
    resolved = {}
    for series, n in delta["counters"].items():
        if n and series.startswith("ff_dispatch_resolutions_total"):
            labels = dict(kv.split("=", 1) for kv in
                          series.split("{", 1)[1].rstrip("}").split(","))
            op = labels["op"].strip('"')
            resolved.setdefault(op, set()).add(
                (labels["impl"].strip('"'), labels["source"].strip('"')))
    check(bool(resolved),
          "dispatch-resolution counters recorded during the run")
    check(all(impl for impls in resolved.values() for impl, _ in impls),
          "each resolution names the winning impl")
    check(any(impl == "ozaki" for i, _ in resolved.get("matmul", set())
              for impl in [i]),
          "explicit ozaki matmul resolution visible in telemetry")
    for op, impls in sorted(resolved.items()):
        wins = ", ".join(f"{i} ({s})" for i, s in sorted(impls))
        print(f"    ff.{op}: {wins}")
    snap = observer.snapshot()
    check(snap["counters"].get('serve_requests_total{status="OK"}', 0)
          + snap["counters"].get('serve_requests_total{status="DEGRADED"}',
                                 0) >= 1,
          "engine request counters populated")
    check(snap["histograms"].get("serve_flush_seconds",
                                 {}).get("count", 0) > 0,
          "flush (host blocked on the device) histogram populated")
    check(snap["counters"].get(
        'serve_programs_built_total{program="jit_step_decode"}', 0) == 1,
          "decode program built once")
    prom = observer.registry.to_prometheus() + obs.REGISTRY.to_prometheus()
    check("serve_guard_events_total" in prom
          and "ff_dispatch_resolutions_total" in prom,
          "Prometheus text exposition includes both registries")

    # -- trace contract ----------------------------------------------------
    payload = json.loads(json.dumps(observer.to_chrome_trace()))
    evs = payload["traceEvents"]
    spans = [e for e in evs if e["ph"] == "X" and e["name"] == "request"]
    check(len(spans) == args.requests,
          f"one complete request span per request "
          f"({len(spans)}/{args.requests})")
    check(all(e["args"]["status"] in STATUSES for e in spans),
          "every request span carries a documented terminal status")
    ts = [e["ts"] for e in evs if e["ph"] != "M"]
    check(all(t >= 0 for t in ts) and ts == sorted(ts),
          "trace timestamps monotone non-negative after export sort")
    check(all(e.get("dur", 0) >= 0 for e in evs if e["ph"] == "X"),
          "span durations non-negative")
    engine = {e["name"] for e in evs
              if e["ph"] == "X" and e["tid"] == obs.ENGINE_TID}
    check({"serve.step", "serve.prefill", "serve.decode_step",
           "serve.flush"} <= engine,
          "engine step spans recorded under obs.enable()")
    progs = [e["args"] for e in evs
             if e["ph"] == "M" and e["name"] == "program"]
    check(len(progs) == 1 and progs[0]["name"] == "jit_step_decode"
          and set(progs[0]["parts"].values())
          >= {"attn", "kv", "mlp", "head", "sample"},
          "decode program publishes its named parts (f32 compute: no cast)")

    if args.metrics_json:
        observer.dump_metrics(args.metrics_json)
        print(f"  metrics -> {args.metrics_json}")
    if args.trace_out:
        observer.dump_trace(args.trace_out)
        print(f"  trace   -> {args.trace_out}")

    print()
    if FAILURES:
        print(f"obs smoke: {len(FAILURES)} check(s) FAILED")
        for f in FAILURES:
            print(f"  - {f}")
        return 1
    print("obs smoke: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
