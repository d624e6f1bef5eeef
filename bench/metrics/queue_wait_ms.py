"""queue_wait_ms.pNN[.<cell>]: NN-th percentile of the engine's ``queued``
spans (submit to admission) that end in the window, in ms."""

import stats


def read(run, name):
    q = stats.name_percentile(name)
    lo, hi = run["obs_window"]
    waits = [e["dur"] / 1e3 for e in run["engine_events"]
             if e.get("ph") == "X" and e["name"] == "queued"
             and lo <= e["ts"] + e["dur"] < hi]
    return stats.percentile(waits, q)
