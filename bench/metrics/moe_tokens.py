"""moe_tokens.<cell>: (token, expert) pairs routed to each held expert of
each MoE layer per decode step, the mean over the window, from the
engine's per-step ``moe`` counter events (``pairs`` over ``layers`` x
``held``).  A run of a model without routed experts records none."""


def moe_steps(run):
    """The ``moe`` counter events' args in the window, in order."""
    lo, hi = run["obs_window"]
    return [e["args"] for e in run["engine_events"]
            if e.get("ph") == "C" and e["name"] == "moe"
            and lo <= e["ts"] < hi]


def read(run, name):
    steps = moe_steps(run)
    if not steps:
        return None
    return sum(a["pairs"] / (a["layers"] * a["held"])
               for a in steps) / len(steps)
