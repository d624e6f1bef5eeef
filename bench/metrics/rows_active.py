"""rows_active: mean of ``active`` over the engine's per-step ``queue``
counter events in the window (rows decoding per scheduler step)."""


def read(run, name):
    lo, hi = run["obs_window"]
    act = [e["args"]["active"] for e in run["engine_events"]
           if e.get("ph") == "C" and e["name"] == "queue"
           and lo <= e["ts"] < hi]
    return sum(act) / len(act) if act else None
