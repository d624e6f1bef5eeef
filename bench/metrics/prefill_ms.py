"""prefill_ms.pNN: NN-th percentile of the engine's request ``prefill``
spans (admission to the first token and both its scores on the host)
that end in the window, in ms."""

import stats


def read(run, name):
    q = float(name.split(".p", 1)[1]) if ".p" in name else 50.0
    lo, hi = run["obs_window"]
    spans = [e["dur"] / 1e3 for e in run["engine_events"]
             if e.get("ph") == "X" and e["name"] == "prefill"
             and lo <= e["ts"] + e["dur"] < hi]
    return stats.percentile(spans, q)
