"""Scoped profiler annotations (``obs.enable()`` / ``obs.annotate`` /
``obs.span``).

The library's hot paths — the Ozaki matmul slices, the sharded
combines — are wrapped in :func:`annotate`.  Outside
an :class:`enable` scope that wrapper is a no-op ``nullcontext`` (one
thread-local list check, nothing allocated), so the default serving path
pays effectively nothing.  Inside the scope it enters both

* :class:`jax.profiler.TraceAnnotation` — names the host-side dispatch
  region in ``jax.profiler.trace`` / TensorBoard / Perfetto captures; and
* :func:`jax.named_scope` — names the traced XLA ops so the annotation
  survives into compiled-program profiles,

mirroring the ``ff.policy`` thread-local-stack idiom: enter the scope
before tracing/profiling, per-thread, re-entrant.

:func:`span` is the engine's host-side form: one ``with`` block records a
``ph="X"`` event on the recorder's engine track AND enters a
``TraceAnnotation`` of the same name, so the serving engine's step
phases stand in both sinks under one name (no ``named_scope``: a span
names host work between dispatches, not traced ops).  Outside an
:class:`enable` scope it is the same shared no-op context.
"""

from __future__ import annotations

import contextlib
import threading

__all__ = ["enable", "enabled", "annotate", "span"]


class _ObsState(threading.local):
    def __init__(self):
        self.stack = []


_STATE = _ObsState()


def enabled() -> bool:
    """True inside an ``obs.enable()`` scope (innermost wins)."""
    return bool(_STATE.stack) and _STATE.stack[-1]


class enable:
    """Context manager toggling profiler annotations for the scope.

    ``obs.enable()`` turns annotations on; ``obs.enable(False)`` forces
    them off for an inner region (same disabler idiom as
    ``ff.on_mesh(None)``)."""

    def __init__(self, on: bool = True):
        self._on = bool(on)

    def __enter__(self) -> bool:
        _STATE.stack.append(self._on)
        return self._on

    def __exit__(self, *exc):
        _STATE.stack.pop()
        return False


def annotate(name: str):
    """Combined ``TraceAnnotation`` + ``named_scope`` when enabled,
    ``nullcontext`` otherwise.  Import of jax is deferred so the metrics
    registry stays importable in jax-free tooling contexts."""
    if not enabled():
        return contextlib.nullcontext()
    try:
        import jax
        import jax.profiler
    except Exception:                      # pragma: no cover - jax-free env
        return contextlib.nullcontext()
    stack = contextlib.ExitStack()
    stack.enter_context(jax.profiler.TraceAnnotation(name))
    stack.enter_context(jax.named_scope(name))
    return stack


class _Span:
    """The :func:`span` context: the annotation is entered first and left
    last, so the recorder's interval lies inside the profiler's."""

    __slots__ = ("_trace", "_name", "_args", "_ann", "_t0")

    def __init__(self, trace, name: str, args: dict):
        self._trace, self._name, self._args = trace, name, args

    def __enter__(self):
        import jax.profiler
        self._ann = jax.profiler.TraceAnnotation(self._name)
        self._ann.__enter__()
        self._t0 = self._trace.now()
        return self

    def __exit__(self, *exc):
        t1 = self._trace.now()
        self._trace.complete(self._name, self._t0, t1 - self._t0,
                             args=self._args or None)
        self._ann.__exit__(*exc)
        return False


_NULL = contextlib.nullcontext()


def span(observer, name: str, **args):
    """An engine span of ``observer`` (an :class:`repro.obs.Observer`):
    a ``ph="X"`` event on its trace's engine track plus a
    ``jax.profiler.TraceAnnotation``, both named ``name``, with ``args``
    on the event.  Records nothing outside ``obs.enable()``."""
    if not enabled():
        return _NULL
    return _Span(observer.trace, name, args)
