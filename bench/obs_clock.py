"""Put the engine's trace recorder on the profiler's clock.

The recorder (``repro.obs.TraceRecorder``) stamps its events in
microseconds from its own construction, a ``jax.profiler`` trace from
its own start.  Under ``obs.enable()`` the engine writes each
``serve.step`` span to both (``repro.obs.span``), so the two sequences
of step intervals are one sequence on two clocks: the trace holds a
contiguous stretch of the recorder's steps (the traced window).
:func:`align` finds that stretch and the offset between the clocks;
:func:`to_trace` then places any recorder span, a request's ``prefill``
say, on the device timeline of :mod:`trace_reduce`.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

SPAN = "serve.step"


def _recorder_spans(events: List[Dict], name: str) -> np.ndarray:
    """(n, 2) start and end, in seconds, sorted by start."""
    return np.array(sorted((e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6)
                           for e in events
                           if e.get("ph") == "X" and e["name"] == name)
                    ).reshape(-1, 2)


def align(events: List[Dict], red: Dict, name: str = SPAN
          ) -> Optional[Dict]:
    """The offset (seconds) that takes a recorder time onto the trace's
    clock, from the ``name`` spans of the recorder's ``events`` and of
    the reduced trace ``red`` (:func:`trace_reduce.reduce_profile`).

    The best-matching stretch is the one whose start and end differences
    spread least: the true one spreads by the time between the two clock
    reads of a span, a few microseconds, while a stretch shifted by a step
    spreads by how much the steps' lengths vary.

    Returns ``{"offset_s", "pairs", "max_err_s"}``: the median of the
    start and end differences over that stretch, the number of spans
    matched, and the largest difference from that median; None when
    either side has no such span or the recorder holds fewer than the
    trace."""
    rec = _recorder_spans(events, name)
    tr = np.array(sorted((h["start"], h["end"]) for h in red["host"]
                         if h["name"] == name)).reshape(-1, 2)
    n = len(tr)
    if n == 0 or len(rec) < n:
        return None
    windows = np.lib.stride_tricks.sliding_window_view(rec, n, axis=0)
    diffs = (tr.T[None] - windows).reshape(len(windows), -1)
    k = int(np.argmin(np.ptp(diffs, axis=1)))
    offset = float(np.median(diffs[k]))
    return {"offset_s": offset, "pairs": n,
            "max_err_s": float(np.max(np.abs(diffs[k] - offset)))}


def to_trace(ts_us: float, clock: Dict) -> float:
    """A recorder timestamp (microseconds) on the trace's clock (seconds)."""
    return ts_us * 1e-6 + clock["offset_s"]
