"""Reduce a ``jax.profiler`` trace (``.xplane.pb``) to device events and
host spans on one clock.

What it returns (:func:`reduce_trace`), all times in seconds on the
trace's own clock:

* ``modules``: every execution of a compiled program on the device
  (``XLA Modules`` line of each ``/device:TPU:*`` plane): name, start,
  end, device, and ``kind``, the engine annotation it ran under;
* ``ops``: every device operation (``XLA Ops`` line): name, start, end,
  device;
* ``host``: the host spans whose names start with one of
  ``HOST_PREFIXES`` (the harness's ``bench.*`` spans and the engine's
  ``serve.*`` annotations): name, start, end;
* ``window``: the ``bench.window`` span, the stretch the trace covers.

Prefill and decode both jit a function named ``step``, so a module's
name cannot tell them apart.  A module execution is attributed to the
last engine annotation (``serve.prefill`` or ``serve.decode_step``) that
began before it: the engine dispatches each program inside its
annotation and waits for the result before it dispatches the next one.
"""

from __future__ import annotations

from typing import Dict, List, Optional

HOST_PREFIXES = ("bench.", "serve.")
ENGINE_SPANS = ("serve.prefill", "serve.decode_step")
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)


def _events(line):
    for e in line.events:
        s = e.start_ns * 1e-9
        yield e.name, s, s + e.duration_ns * 1e-9


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce_profile(pd) -> Dict:
    modules: List[Dict] = []
    ops: List[Dict] = []
    host: List[Dict] = []
    for plane in pd.planes:
        pname = plane.name
        if pname.startswith("/device:") and "TPU" in pname:
            for line in plane.lines:
                if line.name in MODULE_LINES:
                    modules += [{"name": n, "start": s, "end": e,
                                 "device": pname}
                                for n, s, e in _events(line)]
                elif line.name in OP_LINES:
                    ops += [{"name": n, "start": s, "end": e,
                             "device": pname}
                            for n, s, e in _events(line)]
        elif pname.startswith("/host:"):
            for line in plane.lines:
                host += [{"name": n, "start": s, "end": e}
                         for n, s, e in _events(line)
                         if n.startswith(HOST_PREFIXES)]
    host.sort(key=lambda h: h["start"])
    modules.sort(key=lambda m: m["start"])
    ops.sort(key=lambda o: o["start"])
    _attribute(modules, host)
    win = [h for h in host if h["name"] == "bench.window"]
    window: Optional[Dict] = None
    if win:
        window = {"start": win[0]["start"], "end": win[-1]["end"]}
    return {"modules": modules, "ops": ops, "host": host, "window": window,
            "devices": sorted({m["device"] for m in modules}
                              | {o["device"] for o in ops})}


def _attribute(modules: List[Dict], host: List[Dict]) -> None:
    marks = [(h["start"], h["name"]) for h in host
             if h["name"] in ENGINE_SPANS]
    i, last = 0, None
    for m in modules:
        while i < len(marks) and marks[i][0] <= m["start"]:
            last = marks[i][1]
            i += 1
        m["kind"] = last


def reduce_trace(path: str) -> Dict:
    return reduce_profile(load(path))


def decode_modules(red: Dict, name_prefix: str = "jit_step") -> List[Dict]:
    """Executions of the decode program inside the traced window."""
    w = red["window"]
    return [m for m in red["modules"]
            if m["kind"] == "serve.decode_step"
            and m["name"].startswith(name_prefix)
            and (w is None or w["start"] <= m["start"] < w["end"])]
