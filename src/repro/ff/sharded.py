"""Mesh-partitioned FF ops: the ``shard_map`` tier of the dispatch registry.

The paper's float-float operators survive a device mesh only if the
*cross-device* combining step preserves the same error contract as the
on-device arithmetic — ``psum``-ing FF partials as two independent f32
planes silently reintroduces the naive-f32 rounding the whole technique
exists to remove.  This module partitions the FF matmul/reduction ops over
a mesh with ``jax.shard_map`` and combines partial results
across devices with *compensated* collectives:

``combine="psum"`` (the fast class)
    ``TwoSum(psum(hi), psum(lo))``: one hardware all-reduce per limb plane,
    then an exact renormalization.  The collective itself rounds in f32, so
    the combine adds at most ``ceil(log2 P) * 2^-24 * sum_i |hi_i|``
    absolute error over ``P`` devices — the right trade for the fast
    matmul class, whose on-device bound is already ~2^-24-relative
    (blocked compensated accumulation), and documented as such in
    ``docs/NUMERICS.md``.

``combine="tree"`` (the accurate class)
    A ``ppermute`` butterfly (recursive doubling): ``log2 P`` exchange
    steps, each folding the received partial into the local FF accumulator
    with the 2-ulp ``Add22_accurate``.  Every device applies the same
    exact-EFT folds, so the combine preserves the ~2^-44 per-op contract
    (adds ``<= log2 P`` Add22 rounding steps) and is bitwise deterministic
    and identical across devices (TwoSum residuals are exact, hence
    order-symmetric).  Non-power-of-two axis sizes fall back to an
    ``all_gather`` + ordered Add22_accurate fold — same bound, one gather.

Partitioning choices:

* ``matmul``: the K (contraction) dimension is split over the mesh axis —
  each device computes a full (M, N) FF partial from its K-chunk with the
  *resolved single-device implementation* (so the tuned table still picks
  the inner kernel, at the LOCAL (M, K/P, N) shape), then partials combine
  as above.  ``"sharded"`` is the fast class (inner = the fast-tier
  winner, psum combine); ``"sharded_accurate"`` the accurate class (inner
  = the accurate-tier winner — f64/ozaki/dot2 —, tree combine).
* ``sum`` / ``dot``: the leading (reduced) dimension is split; each device
  runs the on-device compensated cascade over its shard, then partial FF
  sums tree-combine.  Default combine is ``"tree"``: these ops *are* the
  accurate tier.
* ``norm_stats``: a last-axis (row) reduction — rows never cross devices,
  so the mesh impl just pins row-parallel execution (leading dim split,
  bitwise-identical per row to the single-device impl, no collective).

Routing is scoped opt-in via ``ff.on_mesh(mesh, axis=...)`` (see
``repro.ff.scope``): outside the scope nothing here is reachable except by
explicit ``impl="sharded*"`` request.  Every implementation degrades
gracefully — no mesh scope, a non-2D matmul, or a non-divisible dimension
falls back (with a warning) to the single-device implementation its class
resolves to, so a mesh default can never brick a call.

Differentiation: these impls slot into the existing ``custom_vjp``
primitives in ``repro.ff.autodiff`` — the vjp rules run *above* the
``shard_map``, and their backward matmuls re-enter this tier (the ambient
``on_mesh`` scope is read at trace time, so keep the scope open around
``jax.grad`` tracing, exactly like a policy scope).
"""

from __future__ import annotations

from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import ff as core_ff
from repro.core.ff import FF
from repro.ff import dispatch, scope

Array = jnp.ndarray
AxisName = Union[str, Tuple[str, ...]]


# ---------------------------------------------------------------------------
# mesh helpers
# ---------------------------------------------------------------------------

def _axes_tuple(axis: AxisName) -> Tuple[str, ...]:
    return (axis,) if isinstance(axis, str) else tuple(axis)


def axis_size(mesh, axis: AxisName) -> int:
    """Total number of shards along ``axis`` (product over tuple axes)."""
    n = 1
    for a in _axes_tuple(axis):
        n *= mesh.shape[a]
    return n


def _resolve_inner(op: str, inner: Optional[str], accurate: bool,
                   shape: Optional[Tuple[int, ...]]) -> str:
    """Resolve the per-shard single-device implementation for ``op``.

    Runs under ``on_mesh(None)`` so resolution cannot re-enter the mesh
    tier; ``inner=None`` resolves the class default — the tuned fast
    winner / backend default for the fast class, ``"tuned_accurate"`` (with
    its static f64/ozaki/dot2 fallback chain) for the accurate class — at
    the LOCAL shard shape, so measured winners apply to the work a device
    actually does."""
    with scope.on_mesh(None):
        name = dispatch.resolve_name(
            op, inner if inner is not None
            else ("tuned_accurate" if accurate else None), shape=shape)
    if name.startswith("sharded"):     # explicit inner="sharded" would recurse
        raise ValueError(f"inner implementation of a sharded {op} cannot "
                         f"itself be {name!r}")
    return name


# ---------------------------------------------------------------------------
# compensated cross-device combines (call inside a shard_map body)
# ---------------------------------------------------------------------------

def psum_combine(r: FF, axis: AxisName) -> FF:
    """Fast-class combine: per-limb ``psum`` + exact TwoSum renormalize.

    Error: the two all-reduces round in f32, adding at most
    ``ceil(log2 P) * 2^-24 * sum_i |hi_i|`` absolute (the lo-plane term is
    a factor 2^-24 smaller); the final TwoSum is exact."""
    hi = jax.lax.psum(r.hi, axis)
    lo = jax.lax.psum(r.lo, axis)
    return core_ff.add12(hi, lo)


def _tree_one_axis(r: FF, ax: str, n: int) -> FF:
    if n == 1:
        return r
    if n & (n - 1):
        # non-power-of-two axis: one gather, then an ordered exact fold —
        # same Add22_accurate bound, identical on every device
        his = jax.lax.all_gather(r.hi, ax)
        los = jax.lax.all_gather(r.lo, ax)
        acc = FF(his[0], los[0])
        for i in range(1, n):
            acc = core_ff.add22_accurate(acc, FF(his[i], los[i]))
        return acc
    step = 1
    while step < n:
        perm = [(i, i ^ step) for i in range(n)]
        oh = jax.lax.ppermute(r.hi, ax, perm)
        ol = jax.lax.ppermute(r.lo, ax, perm)
        r = core_ff.add22_accurate(r, FF(oh, ol))
        step <<= 1
    return r


def tree_combine(r: FF, axis: AxisName, mesh) -> FF:
    """Accurate-class combine: ``ppermute`` TwoSum butterfly.

    ``log2 P`` recursive-doubling steps, each folding the partner's FF
    partial with ``Add22_accurate`` (2-ulp).  The result is bitwise
    identical on every device (TwoSum residuals are exact, so Add22 is
    argument-order-symmetric) and deterministic; total combine error is
    ``<= log2(P)`` Add22_accurate roundings, preserving the ~2^-44
    contract.  Tuple axes fold one axis at a time."""
    for ax in _axes_tuple(axis):
        r = _tree_one_axis(r, ax, mesh.shape[ax])
    return r


def _combine(r: FF, axis: AxisName, mesh, how: str) -> FF:
    from repro.obs import annotate
    with annotate(f"ff.sharded_combine_{how}"):
        if how == "psum":
            return psum_combine(r, axis)
        if how == "tree":
            return tree_combine(r, axis, mesh)
    raise ValueError(f"unknown combine {how!r}; expected 'psum' or 'tree'")


# ---------------------------------------------------------------------------
# sharded matmul (K-contraction split)
# ---------------------------------------------------------------------------

def _mm_sharded(accurate: bool):
    cls = "sharded_accurate" if accurate else "sharded"

    def fn(a: Array, b: Array, *, inner: Optional[str] = None,
           combine: Optional[str] = None, **opts) -> FF:
        ctx = scope.current_mesh()
        M, K = int(a.shape[-2]), int(a.shape[-1])
        N = int(b.shape[-1])
        nshard = axis_size(ctx[0], ctx[1]) if ctx is not None else 1
        if ctx is None or a.ndim != 2 or b.ndim != 2 or K % nshard:
            why = ("no ff.on_mesh scope is active" if ctx is None else
                   f"K={K} is not divisible by the {nshard}-way mesh axis"
                   if K % nshard else
                   f"{a.ndim}-D/{b.ndim}-D operands are not a 2-D matmul")
            name = _resolve_inner("matmul", inner, accurate, (M, K, N))
            dispatch._fallback_warn(cls, "matmul",
                                    f"{why}; using single-device "
                                    f"impl {name!r}")
            kw = dict(opts)
            for k, v in dispatch.resolve_opts("matmul", name,
                                              (M, K, N)).items():
                kw.setdefault(k, v)
            return dispatch.lookup("matmul", name)(a, b, **kw)
        mesh, axis = ctx
        how = combine or ("tree" if accurate else "psum")
        kl = K // nshard
        name = _resolve_inner("matmul", inner, accurate, (M, kl, N))
        base = dispatch.lookup("matmul", name)
        kw = dict(opts)
        for k, v in dispatch.resolve_opts("matmul", name, (M, kl, N)).items():
            kw.setdefault(k, v)

        def body(al, bl):
            r = base(al, bl, **kw)
            r = _combine(r, axis, mesh, how)
            return r.hi, r.lo

        hi, lo = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(None, axis), P(axis, None)),
            out_specs=(P(), P()), check_vma=False)(
                jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32))
        return FF(hi, lo)

    fn.__name__ = f"_mm_{cls}"
    fn.__doc__ = (f"{'Accurate' if accurate else 'Fast'}-class mesh matmul: "
                  f"K split over the ff.on_mesh axis, "
                  f"{'ppermute Add22 tree' if accurate else 'psum+TwoSum'} "
                  f"combine (see module docstring).")
    return fn


# ---------------------------------------------------------------------------
# sharded reductions (leading-dim split)
# ---------------------------------------------------------------------------

def _lead_axes(axis, ndim: int) -> Tuple[int, ...]:
    if axis is None:
        return tuple(range(ndim))
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    return tuple(a % ndim for a in axes)


def _bucket2d(shape: Tuple[int, ...]) -> Tuple[int, int]:
    """Tuning-bucket view of a local shard shape — (prod(leading), last),
    mirroring ``repro.ff.autodiff._bucket2d`` so the tuned table's
    reduction winners apply to the work a device actually does."""
    if len(shape) == 0:
        return (1, 1)
    if len(shape) == 1:
        return (1, int(shape[0]))
    r = 1
    for d in shape[:-1]:
        r *= int(d)
    return (r, int(shape[-1]))


def _resolve_red_inner(op: str, local_shape: Tuple[int, ...]):
    """Per-shard inner impl + tuned opts for a reduction, resolved through
    the registry under ``on_mesh(None)`` (like the matmul inner): the
    backend default / tuned winner at the LOCAL shard bucket — so on TPU
    the mesh tier keeps the rowsum kernel and measured block configs
    instead of hardcoding the jnp cascade."""
    bucket = _bucket2d(local_shape)
    with scope.on_mesh(None):
        name = dispatch.resolve_name(op, None, shape=bucket)
    if name.startswith("sharded"):     # a foreign tuned table must not recurse
        name = "blocked" if op == "sum" else "jnp"
    return dispatch.lookup(op, name), dispatch.resolve_opts(op, name, bucket)


def _red_fallback(op: str, why: str, call):
    """Resolve + run the single-device impl for a reduction the mesh tier
    cannot serve (mesh defaults must never brick a call)."""
    with scope.on_mesh(None):
        name = dispatch.resolve_name(op)
    dispatch._fallback_warn("sharded", op,
                            f"{why}; using single-device impl {name!r}")
    return call(dispatch.lookup(op, name))


def _sum_sharded(x: Array, axis=None, *, combine: str = "tree",
                 block: int = 128, **opts) -> FF:
    """Mesh-partitioned compensated sum: leading dim split over the
    ``on_mesh`` axis, on-device blocked Neumaier cascade per shard, FF
    partials combined with the compensated tree (default) or psum."""
    ctx = scope.current_mesh()
    x = jnp.asarray(x, jnp.float32)
    axes = _lead_axes(axis, x.ndim)
    nshard = axis_size(ctx[0], ctx[1]) if ctx is not None else 1
    servable = (ctx is not None and x.ndim >= 1 and 0 in axes
                and x.shape[0] % nshard == 0)
    if not servable:
        why = ("no ff.on_mesh scope is active" if ctx is None else
               "axis does not reduce the leading (mesh-split) dim"
               if x.ndim < 1 or 0 not in axes else
               f"dim 0 ({x.shape[0] if x.ndim else 0}) is not divisible "
               f"by the {nshard}-way mesh axis")
        return _red_fallback("sum", why,
                             lambda f: f(x, axis=axis, block=block, **opts))
    mesh, maxis = ctx
    lshape = (x.shape[0] // nshard,) + tuple(x.shape[1:])
    base, tuned = _resolve_red_inner("sum", lshape)
    kw = dict(opts)
    kw.setdefault("block", block)
    for k, v in tuned.items():
        kw.setdefault(k, v)

    def body(xl):
        r = base(xl, axis=axes, **kw)
        r = _combine(r, maxis, mesh, combine)
        return r.hi, r.lo

    in_spec = P(maxis, *([None] * (x.ndim - 1)))
    hi, lo = jax.shard_map(body, mesh=mesh, in_specs=(in_spec,),
                           out_specs=(P(), P()), check_vma=False)(x)
    return FF(hi, lo)


def _dot_sharded(a: Array, b: Array, axis=None, *, combine: str = "tree",
                 **opts) -> FF:
    """Mesh-partitioned compensated dot: per-shard Dot2/Dot3 cascade over
    the leading dim, FF partials tree-combined."""
    ctx = scope.current_mesh()
    a = jnp.asarray(a, jnp.float32)
    b = jnp.asarray(b, jnp.float32)
    axes = _lead_axes(axis, a.ndim)
    nshard = axis_size(ctx[0], ctx[1]) if ctx is not None else 1
    servable = (ctx is not None and a.ndim >= 1 and 0 in axes
                and a.shape == b.shape and a.shape[0] % nshard == 0)
    if not servable:
        why = ("no ff.on_mesh scope is active" if ctx is None else
               "operands/axis are not a leading-dim reduction divisible "
               f"by the {nshard}-way mesh axis")
        return _red_fallback("dot", why,
                             lambda f: f(a, b, axis=axis, **opts))
    mesh, maxis = ctx
    lshape = (a.shape[0] // nshard,) + tuple(a.shape[1:])
    base, tuned = _resolve_red_inner("dot", lshape)
    kw = dict(opts)
    for k, v in tuned.items():
        kw.setdefault(k, v)

    def body(al, bl):
        r = base(al, bl, axis=axes, **kw)
        r = _combine(r, maxis, mesh, combine)
        return r.hi, r.lo

    in_spec = P(maxis, *([None] * (a.ndim - 1)))
    hi, lo = jax.shard_map(body, mesh=mesh, in_specs=(in_spec, in_spec),
                           out_specs=(P(), P()), check_vma=False)(a, b)
    return FF(hi, lo)


def _norm_stats_sharded(x: Array, **opts):
    """Row-parallel LayerNorm statistics on the mesh: the reduction is
    within-row (last axis), so shards never exchange data — the mesh impl
    pins leading-dim partitioning and runs the single-device impl
    bitwise-identically per row."""
    ctx = scope.current_mesh()
    x = jnp.asarray(x, jnp.float32)
    nshard = axis_size(ctx[0], ctx[1]) if ctx is not None else 1
    servable = (ctx is not None and x.ndim >= 2
                and x.shape[0] % nshard == 0)
    if not servable:
        why = ("no ff.on_mesh scope is active" if ctx is None else
               f"leading dim of a {x.ndim}-D input is not divisible by "
               f"the {nshard}-way mesh axis")
        return _red_fallback("norm_stats", why, lambda f: f(x, **opts))
    mesh, maxis = ctx
    with scope.on_mesh(None):
        inner_name = dispatch.resolve_name("norm_stats")
    base = dispatch.lookup("norm_stats", inner_name)

    def body(xl):
        return base(xl, **opts)

    in_spec = P(maxis, *([None] * (x.ndim - 1)))
    return jax.shard_map(body, mesh=mesh, in_specs=(in_spec,),
                         out_specs=(P(maxis), P(maxis)),
                         check_vma=False)(x)


# ---------------------------------------------------------------------------
# registration: mesh defaults inside ff.on_mesh scopes
# ---------------------------------------------------------------------------

dispatch.register("matmul", "sharded", _mm_sharded(accurate=False),
                  mesh_default=True)
dispatch.register("matmul", "sharded_accurate", _mm_sharded(accurate=True))
dispatch.register("sum", "sharded", _sum_sharded, mesh_default=True)
dispatch.register("dot", "sharded", _dot_sharded, mesh_default=True)
dispatch.register("norm_stats", "sharded", _norm_stats_sharded,
                  mesh_default=True)
