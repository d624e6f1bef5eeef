"""Kill-and-resume restart chaos: SIGKILL a subprocess engine mid-decode,
then warm-restart and prove exact-replay parity.

``python -m repro.chaos.restart`` (the CI ``chaos-restart`` job) runs the
full scenario per kv_mode:

  1. a CHILD process (``--child``) serves a deterministic request set
     with a write-ahead journal and a synchronous snapshot every 2 decode
     steps, throttled so the parent's SIGKILL reliably lands mid-decode;
  2. the PARENT waits for snapshot progress, SIGKILLs the child — which
     may die mid-snapshot-write (torn ``.tmp``) or mid-journal-append
     (torn JSONL tail); both are designed-for states;
  3. a second child (``--verify``) resumes via
     :func:`repro.serve.resume_engine` (newest VERIFIED snapshot
     generation + WAL replay) and runs to completion;
  4. in that child every request's tokens must be **identical** — and
     the FF logprob limb pairs **bit-for-bit identical** — to an
     uninterrupted engine run of the same request set (greedy decode is
     deterministic, and both processes compile the same XLA programs
     under the pinned ``--xla_cpu_max_isa`` ISA).

The parent never imports JAX: on an accelerator a device belongs to one
process at a time, so each child is the only process holding it.

Exit 0 iff every scenario ends in exact-replay parity with every request
in a documented terminal status.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_f = os.environ.get("XLA_FLAGS", "")
if "--xla_cpu_max_isa" not in _f:
    os.environ["XLA_FLAGS"] = ("--xla_cpu_max_isa=SSE4_2 " + _f).strip()

import numpy as np  # noqa: E402

KV_MODES = ("bf16", "f32", "ff_bf16")
MAX_NEW = 10
SNAPSHOT_EVERY = 2


def _cfg():
    from repro.models.config import ModelConfig
    return ModelConfig(name="restart-chaos", family="dense", num_layers=2,
                       d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                       vocab_size=256, max_seq_len=64,
                       compute_dtype="float32", remat=False)


def _params(cfg):
    import jax
    from repro.models import init_params
    return init_params(cfg, jax.random.PRNGKey(0))


def _requests():
    from repro.serve import Request
    rng = np.random.default_rng(17)
    prompts = [rng.integers(1, 256, size=int(n)).astype(np.int32)
               for n in (6, 9, 12)]
    return [Request(uid=i, prompt=p, max_new=MAX_NEW)
            for i, p in enumerate(prompts)]


def _engine(params, cfg, kv_mode, journal=None):
    from repro.serve import ServeEngine
    return ServeEngine(params, cfg, max_batch=2, page_size=4, max_ctx=32,
                       kv_mode=kv_mode, journal=journal)


def child_main(workdir: str, kv_mode: str, step_delay: float) -> int:
    """Serve the deterministic request set with WAL + periodic snapshots,
    throttled so the parent's SIGKILL lands mid-decode.  Writes a
    progress file after each snapshot and a ``done`` marker only on
    clean completion (the parent asserts it never appears)."""
    cfg = _cfg()
    params = _params(cfg)
    snapdir = os.path.join(workdir, "snap")
    eng = _engine(params, cfg, kv_mode,
                  journal=os.path.join(workdir, "wal.jsonl"))
    for r in _requests():
        eng.submit(r)
    snaps = 0
    while eng.step():
        if eng.decode_steps % SNAPSHOT_EVERY == 0:
            eng.save_snapshot(snapdir)
            snaps += 1
            tmp = os.path.join(workdir, "progress.tmp")
            with open(tmp, "w") as f:
                f.write(json.dumps({"snaps": snaps,
                                    "steps": eng.decode_steps}))
            os.replace(tmp, os.path.join(workdir, "progress.json"))
        time.sleep(step_delay)
    eng.save_snapshot(snapdir)
    with open(os.path.join(workdir, "done"), "w") as f:
        f.write("clean")
    return 0


def verify_main(workdir: str, kv_mode: str) -> dict:
    """Resume the killed engine, run it to completion, and check
    exact-replay parity against an uninterrupted run.  Returns a report
    dict; raises AssertionError on any contract violation."""
    cfg = _cfg()
    params = _params(cfg)
    from repro.serve import OK, resume_engine
    eng = resume_engine(params, cfg, os.path.join(workdir, "snap"),
                        journal=os.path.join(workdir, "wal.jsonl"))
    resumed = eng.run()

    base = _engine(params, cfg, kv_mode)
    for r in _requests():
        base.submit(r)
    baseline = base.run()

    assert set(resumed) == set(baseline), (
        f"[{kv_mode}] uid sets differ: resumed {sorted(resumed)} vs "
        f"baseline {sorted(baseline)}")
    for uid in sorted(baseline):
        a, b = baseline[uid], resumed[uid]
        assert b.status == OK, (
            f"[{kv_mode}] uid {uid}: resumed status {b.status} "
            f"({b.detail})")
        assert np.array_equal(a.tokens, b.tokens), (
            f"[{kv_mode}] uid {uid}: token mismatch after resume")
        assert np.array_equal(a.logprobs_ff, b.logprobs_ff), (
            f"[{kv_mode}] uid {uid}: FF logprob limbs not bit-identical")
    return {"resumed_uids": sorted(resumed),
            "statuses": {u: resumed[u].status for u in sorted(resumed)}}


def _child(mode_flag: str, workdir: str, kv_mode: str, *extra: str,
           **popen_kw) -> subprocess.Popen:
    env = dict(os.environ)
    src_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    env["PYTHONPATH"] = src_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return subprocess.Popen(
        [sys.executable, "-m", "repro.chaos.restart", mode_flag,
         "--dir", workdir, "--kv-mode", kv_mode, *extra], env=env,
        **popen_kw)


def run_scenario(workdir: str, kv_mode: str = "bf16", *,
                 step_delay: float = 0.25, kill_after_snaps: int = 2,
                 timeout_s: float = 300.0) -> dict:
    """Parent side: spawn, SIGKILL mid-decode, resume and verify parity
    in a second child.  Returns a report dict; raises AssertionError on
    any contract violation."""
    os.makedirs(workdir, exist_ok=True)
    progress = os.path.join(workdir, "progress.json")
    proc = _child("--child", workdir, kv_mode,
                  "--step-delay", str(step_delay),
                  stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    deadline = time.monotonic() + timeout_s
    try:
        while True:
            if time.monotonic() > deadline:
                raise AssertionError(
                    f"[{kv_mode}] child produced no snapshot progress "
                    f"within {timeout_s}s")
            if proc.poll() is not None:
                raise AssertionError(
                    f"[{kv_mode}] child exited (rc={proc.returncode}) "
                    f"before the kill — increase step_delay")
            if os.path.exists(progress):
                with open(progress) as f:
                    prog = json.load(f)
                if prog["snaps"] >= kill_after_snaps:
                    break
            time.sleep(0.05)
        proc.kill()                      # SIGKILL: no atexit, no cleanup
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=60)
    assert not os.path.exists(os.path.join(workdir, "done")), \
        f"[{kv_mode}] child finished cleanly; the kill tested nothing"

    verifier = _child("--verify", workdir, kv_mode,
                      stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
                      text=True)
    try:
        _, err = verifier.communicate(timeout=timeout_s)
    finally:
        if verifier.poll() is None:
            verifier.kill()
            verifier.wait(timeout=60)
    path = os.path.join(workdir, "report.json")
    assert os.path.exists(path), (
        f"[{kv_mode}] verify child wrote no report "
        f"(rc={verifier.returncode}): {err[-3000:]}")
    with open(path) as f:
        report = json.load(f)
    if "error" in report:
        raise AssertionError(report["error"])
    assert verifier.returncode == 0, err[-3000:]
    return {"kv_mode": kv_mode, "killed_at_snaps": kill_after_snaps,
            **report}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--verify", action="store_true",
                    help="resume a killed --child run and check parity")
    ap.add_argument("--dir", type=str, default=None)
    ap.add_argument("--kv-mode", type=str, default="bf16",
                    choices=KV_MODES)
    ap.add_argument("--step-delay", type=float, default=0.25)
    ap.add_argument("--modes", type=str, default=",".join(KV_MODES),
                    help="comma-separated kv_modes for the parent sweep")
    args = ap.parse_args(argv)
    if args.child:
        if not args.dir:
            ap.error("--child requires --dir")
        return child_main(args.dir, args.kv_mode, args.step_delay)
    if args.verify:
        if not args.dir:
            ap.error("--verify requires --dir")
        try:
            report = verify_main(args.dir, args.kv_mode)
        except AssertionError as e:
            report = {"error": str(e)}
        with open(os.path.join(args.dir, "report.json"), "w") as f:
            json.dump(report, f)
        return 1 if "error" in report else 0
    import tempfile
    failures = []
    for mode in args.modes.split(","):
        workdir = tempfile.mkdtemp(prefix=f"restart-chaos-{mode}-")
        print(f"chaos-restart: SIGKILL mid-decode + resume [{mode}]")
        try:
            report = run_scenario(workdir, mode,
                                  step_delay=args.step_delay)
        except AssertionError as e:
            print(f"  [FAIL] {e}")
            failures.append(str(e))
            continue
        print(f"  [ok] exact-replay parity: uids "
              f"{report['resumed_uids']} all "
              f"{sorted(set(report['statuses'].values()))}")
    if failures:
        print(f"chaos-restart: {len(failures)} scenario(s) FAILED")
        return 1
    print("chaos-restart: all kill-and-resume scenarios passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
