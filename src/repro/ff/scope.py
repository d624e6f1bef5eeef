"""Scoped precision policy + dispatch overrides for the ``repro.ff`` namespace.

Replaces positional ``PrecisionPolicy`` threading: models, the optimizer and
the train/serve step builders call :func:`resolve_policy` (explicit argument
wins, otherwise the innermost active :class:`policy` scope, otherwise the
process default).  Example::

    with ff.policy("ff_full", matmul="hybrid", compute_dtype="float32"):
        step = make_train_step(cfg, None, opt)   # reads the scope

Scopes are plain Python state consulted at *trace* time.  Enter them before
tracing (i.e. around step-builder calls or the first call of a jitted
function); re-entering a scope around an already-compiled function does not
retrace it — the same caveat as any Python-level configuration in JAX.

Scopes are thread-local, so concurrent trainer/server threads can hold
different policies.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Dict, Optional, Union

from repro.core.policy import PrecisionPolicy, BASELINE


class _ScopeState(threading.local):
    def __init__(self):
        self.policies = []      # innermost-last stack of PrecisionPolicy
        self.impls = []         # innermost-last stack of {op: impl_name}
        self.meshes = []        # innermost-last stack of (mesh, axes) | None


_STATE = _ScopeState()
_DEFAULT = [BASELINE]           # process-wide fallback (list for mutability)


def current_policy() -> PrecisionPolicy:
    """The innermost active policy scope, or the process default."""
    if _STATE.policies:
        return _STATE.policies[-1]
    return _DEFAULT[0]


def set_default_policy(p: PrecisionPolicy) -> PrecisionPolicy:
    """Set the process-wide fallback policy; returns the previous one."""
    old = _DEFAULT[0]
    _DEFAULT[0] = p
    return old


def resolve_policy(explicit: Optional[PrecisionPolicy] = None) -> PrecisionPolicy:
    """Explicit policy if given, else the ambient scoped/default policy."""
    return explicit if explicit is not None else current_policy()


class policy:
    """Context manager installing a :class:`PrecisionPolicy` for the scope.

    Accepts a level name (``"baseline" | "ff_master" | "ff_reduce" |
    "ff_full"``), an existing :class:`PrecisionPolicy`, or nothing (derive
    from the current scope), plus field overrides.  ``matmul=`` selects the
    FF matmul implementation the dispatch registry uses inside the scope
    (e.g. ``"hybrid"``, ``"split"``, ``"dot2"``, ``"ozaki"``); the special
    names ``"tuned"`` / ``"tuned_accurate"`` select the measured winner of
    the fast / paper-accuracy class from the ``ff.tune`` table, and the
    default ``"auto"`` also consults that table before falling back to the
    registered backend default.
    """

    def __init__(self,
                 level_or_policy: Union[str, PrecisionPolicy, None] = None,
                 *, matmul: Optional[str] = None, **overrides):
        self._base = level_or_policy
        self._matmul = matmul
        self._overrides = overrides

    def _build(self) -> PrecisionPolicy:
        base = self._base
        if isinstance(base, PrecisionPolicy):
            p = (dataclasses.replace(base, **self._overrides)
                 if self._overrides else base)
        elif base is None:
            p = dataclasses.replace(current_policy(), **self._overrides)
        else:
            p = PrecisionPolicy.make(base, **self._overrides)
        if self._matmul is not None:
            p = dataclasses.replace(p, matmul_impl=self._matmul)
        return p

    def __enter__(self) -> PrecisionPolicy:
        p = self._build()
        _STATE.policies.append(p)
        return p

    def __exit__(self, *exc):
        _STATE.policies.pop()
        return False


class use:
    """Context manager overriding dispatch per-op: ``with ff.use(matmul="dot2")``.

    Finer-grained than :class:`policy` — overrides only the implementation
    choice of the named ops, leaving the precision policy untouched.
    """

    def __init__(self, **op_impls: str):
        self._m = dict(op_impls)

    def __enter__(self) -> Dict[str, str]:
        _STATE.impls.append(self._m)
        return self._m

    def __exit__(self, *exc):
        _STATE.impls.pop()
        return False


def current_impl(op: str) -> Optional[str]:
    """The innermost ``use()`` override for ``op``, if any."""
    for m in reversed(_STATE.impls):
        if op in m:
            return m[op]
    return None


class on_mesh:
    """Context manager establishing the ambient device mesh for FF dispatch.

    Inside the scope, ops with a registered mesh implementation
    (``matmul``/``sum``/``dot``/``norm_stats`` — see ``repro.ff.sharded``)
    resolve to their ``shard_map``-partitioned variants, whose cross-device
    combines preserve the per-op FF error contract instead of flattening to
    a naive f32 ``psum``.  Call sites outside any ``on_mesh`` scope are
    completely untouched — mesh routing is a scoped opt-in, exactly like
    :class:`policy` / :class:`use`::

        mesh = repro.launch.mesh.make_mesh((8,), ("data",))   # Auto axes
        with ff.on_mesh(mesh, axis="data"):
            C = ff.matmul(A, B)                    # K split over "data"
            C = ff.matmul(A, B, impl="sharded_accurate")   # ppermute tree

    ``axis`` names the mesh axis (or tuple of axes) the contraction /
    leading dimension is partitioned over.  ``on_mesh(None)`` *disables*
    mesh routing for an inner region (the sharded implementations use this
    to resolve their per-shard inner op without re-entering themselves).

    Like every ``repro.ff`` scope this is trace-time Python state: enter it
    around ``jit``/``grad`` *tracing* (step-builder calls, first call of a
    jitted function), not around already-compiled calls.  Thread-local.
    """

    def __init__(self, mesh, axis: Union[str, tuple] = "data"):
        if mesh is not None:
            axes = (axis,) if isinstance(axis, str) else tuple(axis)
            missing = [a for a in axes if a not in mesh.axis_names]
            if missing:
                raise ValueError(
                    f"on_mesh: axis {missing} not in mesh axes "
                    f"{tuple(mesh.axis_names)}")
            self._entry = (mesh, axis if isinstance(axis, str) else axes)
        else:
            self._entry = None

    def __enter__(self):
        _STATE.meshes.append(self._entry)
        return self._entry

    def __exit__(self, *exc):
        _STATE.meshes.pop()
        return False


def current_mesh():
    """The innermost active ``on_mesh`` entry: ``(mesh, axis)`` or ``None``
    (no scope active, or the innermost scope is the ``on_mesh(None)``
    disabler)."""
    if _STATE.meshes:
        return _STATE.meshes[-1]
    return None
