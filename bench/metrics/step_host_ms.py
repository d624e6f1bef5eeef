"""step_host_ms: host time per scheduler step not spent blocked on the
device, in ms: the mean, over the engine's ``serve.step`` spans that end
in the window, of the span less the time under its ``serve.flush.wait``
and ``serve.prefill.wait`` spans.  In a traced run it also logs how the
recorder's steps line up with the profiler's (``obs_clock``)."""

import bisect
import sys

WAITS = ("serve.flush.wait", "serve.prefill.wait")


def read(run, name):
    lo, hi = run["obs_window"]
    spans = [e for e in run["engine_events"] if e.get("ph") == "X"]
    steps = [e for e in spans if e["name"] == "serve.step"
             and lo <= e["ts"] + e["dur"] < hi]
    if not steps:
        return None
    waits = sorted((e["ts"], e["dur"]) for e in spans
                   if e["name"] in WAITS)
    starts = [w[0] for w in waits]
    host = []
    for s in steps:
        end = s["ts"] + s["dur"]
        i = bisect.bisect_left(starts, s["ts"])
        blocked = 0.0
        while i < len(waits) and waits[i][0] < end:
            blocked += waits[i][1]
            i += 1
        host.append(s["dur"] - blocked)
    if run.get("trace"):
        import obs_clock
        clock = obs_clock.align(run["engine_events"], run["trace"])
        if clock:
            print(f"[bench] obs clock: {clock['pairs']} serve.step spans "
                  f"aligned, offset {clock['offset_s']:.6f} s, largest "
                  f"error {clock['max_err_s'] * 1e3:.4f} ms",
                  file=sys.stderr, flush=True)
    return sum(host) / len(host) / 1e3
