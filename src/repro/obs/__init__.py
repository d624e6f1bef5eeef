"""``repro.obs`` — metrics, tracing, and profiling for the FF system.

Three layers, all host-side and stdlib-only at import time (``repro.obs``
must never import ``repro.ff`` — dispatch/guard/tuning import *us*
lazily, and a cycle here would break the registry bootstrap):

* **Metrics** (:mod:`repro.obs.registry`): thread-safe counters / gauges /
  log2-bucket histograms with snapshot/delta and JSON + Prometheus
  exposition.  A process-global registry (:data:`REGISTRY`) collects
  dispatch-resolution, tune-cache, and warning counters — recorded at
  *trace* time only, so steady-state jit execution pays zero cost.
  Engines carry their own per-instance registry (via :class:`Observer`)
  so concurrent engines and tests don't share counts.

* **Tracing** (:mod:`repro.obs.trace`): Chrome trace-event JSON
  (Perfetto-loadable) — per-request span timelines and per-step engine
  events.

* **Profiling** (:mod:`repro.obs.profiling`): ``obs.enable()`` scope
  gating ``jax.profiler.TraceAnnotation``/``named_scope`` wrappers around
  Ozaki matmul and the sharded combines, and ``obs.span``: the serving
  engine's step phases, in the trace recorder and the profiler at once.
  :mod:`repro.obs.parts` maps a compiled program's instructions to the
  named parts of its scopes.

``python -m repro.obs`` runs an instrumented serving smoke and emits both
artifacts — see :mod:`repro.obs.__main__`.
"""

from __future__ import annotations

from typing import Optional

from repro.obs.registry import (Counter, Gauge, Histogram, MetricsRegistry,
                                LOG2_BUCKETS)
from repro.obs.trace import TraceRecorder, ENGINE_TID
from repro.obs.profiling import annotate, enable, enabled, span
from repro.obs.parts import DECODE_PARTS, part_of, program_parts

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "LOG2_BUCKETS",
    "TraceRecorder", "ENGINE_TID",
    "annotate", "enable", "enabled", "span",
    "DECODE_PARTS", "part_of", "program_parts",
    "REGISTRY", "Observer",
    "record_resolution", "record_tune_lookup", "record_warning",
    "record_guard_violation", "record_journal_event",
]

# Process-global registry: dispatch/tuning/guard telemetry that isn't tied
# to one engine instance.  Tests bracket assertions with snapshot/delta.
REGISTRY = MetricsRegistry()


# -- hooks called (lazily) from repro.ff internals -------------------------

def record_resolution(op: str, impl: str, source: str, backend: str,
                      shape_bucket: str) -> None:
    """One dispatch resolution: ``op`` resolved to ``impl`` because of
    ``source`` (explicit/scope/policy/mesh/tuned/.../guard_degraded) on
    ``backend`` for the pow2 ``shape_bucket``.  Trace-time only."""
    REGISTRY.counter("ff_dispatch_resolutions_total", op=op, impl=impl,
                     source=source, backend=backend,
                     shape=shape_bucket).inc()


def record_tune_lookup(hit: bool) -> None:
    REGISTRY.counter("ff_tune_cache_total",
                     result=("hit" if hit else "miss")).inc()


def record_warning(kind: str) -> None:
    """``kind`` in {"tune", "guard"} — one FFTuneWarning/FFGuardWarning
    *event* (counted even when the warning itself is warn-once
    suppressed)."""
    REGISTRY.counter("ff_warnings_total", kind=kind).inc()


def record_guard_violation(op: str, kind: str, count: int = 1) -> None:
    """Per-(op, kind) guard violation count; accumulates unconditionally,
    unlike the warn-once user-facing warning."""
    if count > 0:
        REGISTRY.counter("ff_guard_violations_total",
                         op=op, kind=kind).inc(int(count))


def record_journal_event(event: str, n: int = 1) -> None:
    """Write-ahead-journal activity: append/retire/compact/truncate."""
    REGISTRY.counter("serve_journal_events_total", event=event).inc(int(n))


class Observer:
    """Per-engine observability bundle: a private metrics registry plus a
    trace recorder.  ``ServeEngine(obs=...)`` accepts one; when omitted the
    engine builds its own so counter assertions stay per-instance."""

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 trace: Optional[TraceRecorder] = None):
        self.registry = registry if registry is not None else MetricsRegistry()
        self.trace = trace if trace is not None else TraceRecorder()

    def snapshot(self) -> dict:
        return self.registry.snapshot()

    def delta(self, prev: Optional[dict]) -> dict:
        return self.registry.delta(prev)

    def to_chrome_trace(self) -> dict:
        return self.trace.to_chrome_trace()

    def dump_trace(self, path: str) -> None:
        self.trace.dump(path)

    def dump_metrics(self, path: str,
                     extra: Optional[MetricsRegistry] = None) -> None:
        """Write a combined metrics JSON: this observer's registry plus the
        process-global one (dispatch/tune/guard counters) — the artifact
        ``launch/serve.py --metrics-json`` uploads."""
        import json
        payload = {"engine": self.registry.snapshot(),
                   "global": (extra if extra is not None
                              else REGISTRY).snapshot()}
        with open(path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
