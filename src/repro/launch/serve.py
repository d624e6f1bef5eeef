"""Serving launcher: batched prefill + greedy decode loop.

Demo:
  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m --reduced \
      --batch 4 --prompt-len 32 --max-new 16

``--engine`` routes dense archs through the continuous-batching
:class:`repro.serve.ServeEngine` (paged FF KV cache, per-request
mixed-length prompts, FF token-logprob scoring) instead of the one-shot
padded-batch greedy loop.
"""

import argparse
import os
import time

_f = os.environ.get("XLA_FLAGS", "")
if "--xla_cpu_max_isa" not in _f:
    os.environ["XLA_FLAGS"] = ("--xla_cpu_max_isa=SSE4_2 " + _f).strip()

import jax
import jax.numpy as jnp


def build_params(cfg, seed: int = 0):
    """Random weights from ``seed``, built under ``jit``: the RNG
    intermediates of an eager build would sit on the device beside the
    finished weights and double the peak at full width."""
    from repro.models import init_params
    return jax.jit(lambda k: init_params(cfg, k))(jax.random.PRNGKey(seed))


def make_requests(cfg, lens, max_new: int, rng):
    """One engine request per prompt length, token ids drawn from ``rng``
    (a ``numpy.random.Generator``)."""
    import numpy as np
    from repro.serve import Request
    return [Request(uid=i,
                    prompt=rng.integers(1, cfg.vocab_size,
                                        size=int(n)).astype(np.int32),
                    max_new=max_new)
            for i, n in enumerate(lens)]


def serve(eng, requests, **run_kw):
    """Submit ``requests`` to ``eng`` and drain it.  Returns (results of
    these requests by uid, wall seconds)."""
    for r in requests:
        eng.submit(r)
    t0 = time.perf_counter()
    results = eng.run(**run_kw)
    dt = time.perf_counter() - t0
    return {r.uid: results[r.uid] for r in requests}, dt


def _start_metrics_server(observer, port: int):
    """Serve ``observer``'s registry (+ the global telemetry registry) as
    Prometheus text exposition on /metrics, in a daemon thread."""
    import threading
    from http.server import BaseHTTPRequestHandler, HTTPServer

    class Handler(BaseHTTPRequestHandler):
        def do_GET(self):
            if self.path.rstrip("/") not in ("", "/metrics"):
                self.send_response(404)
                self.end_headers()
                return
            from repro import obs
            body = (observer.registry.to_prometheus()
                    + obs.REGISTRY.to_prometheus()).encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):            # quiet: stats, not access logs
            pass

    srv = HTTPServer(("127.0.0.1", port), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--engine", action="store_true",
                    help="serve through the continuous-batching ServeEngine "
                         "(paged KV cache; dense non-MLA archs)")
    ap.add_argument("--kv-mode", type=str, default="bf16",
                    choices=("bf16", "f32", "ff_bf16"),
                    help="--engine page storage: bf16 (baseline parity), "
                         "f32, or ff_bf16 (double-bf16 limb planes)")
    ap.add_argument("--guard", type=str, default="off",
                    choices=("off", "check", "degrade"),
                    help="--engine numeric guardrails: 'check' compiles the "
                         "per-step FF/KV health probe (quarantine + fast-tier "
                         "retry of poisoned rows), 'degrade' also drops "
                         "violating ops one accuracy class")
    ap.add_argument("--mesh", action="store_true",
                    help="shard params over the local device mesh and route "
                         "the scoring reductions through the mesh-aware FF "
                         "tier")
    ap.add_argument("--snapshot-dir", type=str, default=None,
                    help="--engine crash safety: directory for engine "
                         "snapshots (atomic CRC32'd checkpoints, "
                         "keep-last-3) and the write-ahead request journal")
    ap.add_argument("--snapshot-every", type=int, default=0,
                    help="--engine: snapshot every N decode steps through "
                         "the async checkpointer (0 = off; requires "
                         "--snapshot-dir)")
    ap.add_argument("--resume", action="store_true",
                    help="--engine: warm-restart from the newest VERIFIED "
                         "snapshot generation under --snapshot-dir (corrupt "
                         "generations fall back warned) and replay the "
                         "journal, instead of submitting fresh requests")
    ap.add_argument("--metrics-json", type=str, default=None,
                    help="--engine observability: write the metrics snapshot "
                         "(engine counters/gauges/histograms + the global "
                         "dispatch/tune/guard telemetry) to this JSON file "
                         "after the run")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="--engine observability: write the Chrome "
                         "trace-event JSON (per-request spans + per-step "
                         "events; open in Perfetto) to this file")
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="--engine observability: serve Prometheus text "
                         "exposition on http://127.0.0.1:PORT/metrics for "
                         "the duration of the run (0 = off)")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if (args.snapshot_every or args.resume) and not args.snapshot_dir:
        ap.error("--snapshot-every/--resume require --snapshot-dir")
    if (args.metrics_json or args.trace_out or args.metrics_port) \
            and not args.engine:
        ap.error("--metrics-json/--trace-out/--metrics-port require --engine")

    import contextlib

    import repro.ff as ff
    from repro.configs import get_config
    from repro.train.serve_step import greedy_generate

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = build_params(cfg)
    mesh_scope = contextlib.nullcontext()
    if args.mesh:
        from repro.distributed.sharding import param_shardings
        from repro.launch.mesh import make_local_data_mesh
        mesh = make_local_data_mesh()
        params = jax.device_put(params, param_shardings(cfg=cfg, mesh=mesh,
                                                        params=params))
        mesh_scope = ff.on_mesh(mesh, axis="data")
        print(f"[serve] mesh: {dict(mesh.shape)} — params sharded, FF "
              f"scoring reductions mesh-routed")
    if args.engine:
        import numpy as np
        from repro import obs
        from repro.serve import ServeEngine, resume_engine
        journal = (os.path.join(args.snapshot_dir, "wal.jsonl")
                   if args.snapshot_dir else None)
        observer = obs.Observer()
        metrics_server = None
        if args.metrics_port:
            metrics_server = _start_metrics_server(observer, args.metrics_port)
            print(f"[serve] metrics: http://127.0.0.1:{args.metrics_port}"
                  f"/metrics")
        rng = np.random.default_rng(1)
        lo = max(4, args.prompt_len // 2)
        lens = rng.integers(lo, args.prompt_len + 1, size=args.batch)
        run_kw = dict(snapshot_dir=args.snapshot_dir,
                      snapshot_every=args.snapshot_every or None)
        if args.resume:
            t0 = time.perf_counter()
            eng = resume_engine(params, cfg, args.snapshot_dir,
                                journal=journal, max_batch=args.batch,
                                max_ctx=args.prompt_len + args.max_new + 8,
                                kv_mode=args.kv_mode, guard=args.guard,
                                obs=observer)
            n_restored = sum(s is not None for s in eng._slots)
            print(f"[serve] resumed from {args.snapshot_dir}: "
                  f"{len(eng.results)} completed, {n_restored} running, "
                  f"{len(eng.queue)} queued/replayed "
                  f"({time.perf_counter() - t0:.2f}s to warm state)")
            t0 = time.perf_counter()
            results = eng.run(**run_kw)
            dt = time.perf_counter() - t0
        else:
            eng = ServeEngine(params, cfg, max_batch=args.batch,
                              max_ctx=args.prompt_len + args.max_new + 8,
                              kv_mode=args.kv_mode, guard=args.guard,
                              journal=journal, obs=observer)
            results, dt = serve(
                eng, make_requests(cfg, lens, args.max_new, rng), **run_kw)
        n_tok = sum(len(r.tokens) for r in results.values())
        all_lps = np.concatenate(
            [r.logprobs for r in results.values()]
            or [np.zeros((0,), np.float32)])
        by_status: dict = {}
        for r in results.values():
            by_status[r.status] = by_status.get(r.status, 0) + 1
        status_str = " ".join(f"{k}={v}" for k, v in sorted(by_status.items()))
        mean_lp = float(all_lps.mean()) if all_lps.size else float("nan")
        print(f"[serve] {cfg.name} engine({args.kv_mode}, guard={args.guard}):"
              f" {len(results)} requests (prompts {lens.min()}..{lens.max()}),"
              f" {n_tok} tokens in {dt:.1f}s ({n_tok / dt:.1f} tok/s), mean "
              f"token logprob {mean_lp:.4f}, status {status_str}")
        if results:
            print(results[sorted(results)[0]].tokens)
        if args.metrics_json:
            observer.dump_metrics(args.metrics_json)
            print(f"[serve] metrics snapshot -> {args.metrics_json}")
        if args.trace_out:
            observer.dump_trace(args.trace_out)
            print(f"[serve] Perfetto trace ({len(observer.trace.events())} "
                  f"events) -> {args.trace_out}")
        if metrics_server is not None:
            metrics_server.shutdown()
        return
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (args.batch, args.prompt_len),
                                0, cfg.vocab_size)
    extra = {}
    if cfg.family == "vlm":
        extra["patches"] = jnp.zeros(
            (args.batch, cfg.num_patches, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        extra["frames"] = jnp.zeros(
            (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
    t0 = time.perf_counter()
    with mesh_scope:
        toks, lps = greedy_generate(
            params, cfg, prompt, max_new=args.max_new,
            cache_len=args.prompt_len + args.max_new + 8
            + (cfg.num_patches if cfg.family == "vlm" else 0),
            extra_inputs=extra or None, return_logprobs=True)
        # sequence score: compensated FF sum of token logprobs — inside a
        # --mesh scope this is the mesh-partitioned ff.sum (compensated
        # cross-device combine); without it, the blocked cascade
        mean_lp = ff.sum(lps.reshape(-1)).to_f32() / lps.size
    dt = time.perf_counter() - t0
    print(f"[serve] {cfg.name}: generated {toks.shape} in {dt:.1f}s "
          f"({args.batch * args.max_new / dt:.1f} tok/s), "
          f"mean token logprob {float(mean_lp):.4f}")
    print(toks[0])


if __name__ == "__main__":
    main()
