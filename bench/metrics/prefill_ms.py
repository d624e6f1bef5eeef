"""prefill_ms.pNN[.<cell>]: NN-th percentile of the engine's request
``prefill`` spans (admission to the first token and both its scores on
the host) that end in the window, in ms."""

import stats


def read(run, name):
    q = stats.name_percentile(name)
    lo, hi = run["obs_window"]
    spans = [e["dur"] / 1e3 for e in run["engine_events"]
             if e.get("ph") == "X" and e["name"] == "prefill"
             and lo <= e["ts"] + e["dur"] < hi]
    return stats.percentile(spans, q)
