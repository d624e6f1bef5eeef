"""Fused FF expression pipelines: equivalence and composite-kernel tests.

The contract (ISSUE 3): every fused chain and composite kernel must be
bitwise-identical to the op-by-op dispatch result — or within 1 ulp with a
documented reason — in both interpret (Pallas) and compiled (jnp-executor
under jit) modes.  The two documented 1-ulp classes are:

  * reduction outputs: the fused kernels use the lane-parallel Neumaier
    cascade of ``ff_reduce`` while the op-by-op reference uses
    ``ff_sum_blocked``'s scan — both are accurate to ~2^-40 relative, so
    the two f32-rounded results can differ by at most the final ulp;
  * composites whose denominator/stat feeds further f32 ops (softmax,
    norm_stats variance): the <=1-ulp reduction difference propagates
    through one more rounding, giving <=2 ulp on the output.

Comparisons are made in the SAME compilation mode on both sides: eager and
jitted XLA already differ by ~1 ulp through f32 div/sqrt chains for any
program (the backend rewrites e.g. x/sqrt(y) under jit), which has nothing
to do with fusion.
"""
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.ff as ff
from repro.core import compensated
from repro.core.ff import FF
from repro.ff import dispatch, fusion

from conftest import f32_vec


def _f64(x):
    return np.asarray(x).astype(np.float64)


def ff64(x: FF):
    return _f64(x.hi) + _f64(x.lo)


def _rand_ff(rng, shape, lo=-3, hi=3):
    n = int(np.prod(shape))
    h = f32_vec(rng, n, lo, hi).reshape(shape)
    l = (h * 1e-8 * rng.standard_normal(shape)).astype(np.float32)
    return FF(jnp.asarray(h), jnp.asarray(l))


def _assert_bitwise(a, b, what=""):
    if isinstance(a, FF):
        assert np.array_equal(np.asarray(a.hi), np.asarray(b.hi)), what
        assert np.array_equal(np.asarray(a.lo), np.asarray(b.lo)), what
    else:
        assert np.array_equal(np.asarray(a), np.asarray(b)), what


def _assert_ulp(a, b, tol, what=""):
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    ulp = np.abs(a - b) / np.spacing(np.maximum(np.abs(b),
                                                np.float32(1e-30)))
    assert ulp.max() <= tol, (what, float(ulp.max()))


# ---------------------------------------------------------------------------
# generic fused chains vs op-by-op dispatch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(8, 128), (17, 300), (3, 130), (64,)])
def test_fused_chain_bitwise_both_modes(rng, shape):
    """A mixed FF/f32 chain (mul212/add22/div22/sqrt22 + f32 ops): the
    jnp executor replays the exact op-by-op graph (bitwise under jit) and
    the Pallas interpret executor evaluates the same EFT sequences."""
    x = _rand_ff(rng, shape)
    y = _rand_ff(rng, shape)
    s = jnp.float32(1.618)

    @ff.fused
    def chain(x, y, s):
        t = s * x + y                 # mul212, add22
        u = t * t                     # mul22
        return u / (y * y + 1.0), t   # mul22, add212, div22

    def op_by_op(x, y, s):
        t = ff.add(ff.mul(x, s), y)
        u = ff.mul(t, t)
        return ff.div(u, ff.add(ff.mul(y, y), jnp.float32(1.0))), t

    want = jax.jit(op_by_op)(x, y, s)
    got_jnp = jax.jit(lambda *a: chain(*a))(x, y, s)
    got_pal = jax.jit(lambda *a: chain(*a, interpret=True))(x, y, s)
    for g1, g2, w in zip(got_jnp, got_pal, want):
        _assert_bitwise(g1, w, "jnp executor vs op-by-op")
        _assert_bitwise(g2, w, "pallas executor vs op-by-op")


def test_fused_broadcast_and_scalars(rng):
    row = jnp.asarray(rng.standard_normal((1, 200)).astype(np.float32))
    col = jnp.asarray(rng.standard_normal((64, 1)).astype(np.float32))

    @ff.fused
    def chain(r, c, s):
        return r * c + s

    for interpret in (False, True):
        out = chain(row, col, 2.5, interpret=interpret)
        assert out.shape == (64, 200)
        ref = jax.jit(lambda r, c: r * c + 2.5)(row, col)
        _assert_bitwise(out, np.asarray(ref), f"interpret={interpret}")


def test_fused_rowsum_reduction(rng):
    """Trailing rowsum: jnp executor is bitwise ff.sum(block=128); the
    Pallas cascade is within the documented final ulp, and both are
    ~2^-40 vs the exact sum of the f32 squares."""
    x = jnp.asarray(f32_vec(rng, 5 * 1000, -4, 4).reshape(5, 1000))

    @ff.fused
    def msq(x):
        return (x * x).sum()

    want = jax.jit(lambda x: compensated.ff_sum_blocked(
        x * x, axis=-1, block=128))(x)
    got = jax.jit(lambda x: msq(x))(x)
    _assert_bitwise(got, want, "jnp rowsum vs ff_sum_blocked")

    got_pal = msq(x, interpret=True)
    _assert_ulp(got_pal.hi, want.hi, 1, "pallas rowsum hi")
    # oracle: exact sum of the f32 SQUARES (the chain squares in f32, as
    # the op-by-op path does — the reduction is what must be compensated)
    q = np.asarray(jnp.asarray(x) * jnp.asarray(x), np.float64)
    exact = q.sum(axis=1)
    for g in (got, got_pal):
        rel = np.abs(ff64(g) - exact) / np.abs(exact)
        assert rel.max() < 2.0 ** -40


def test_fused_rowsum_masks_padding(rng):
    """A chain that is NONZERO on padded columns (x + 1) must still reduce
    exactly over the true columns — the kernel masks before accumulating."""
    x = jnp.asarray(f32_vec(rng, 3 * 130, -2, 2).reshape(3, 130))

    @ff.fused
    def s1(x):
        return (x + 1.0).sum()

    got = s1(x, interpret=True)
    # oracle: exact sum of the f32 values of x+1 (per-element f32
    # rounding belongs to the chain, not the reduction)
    xp1 = np.asarray(jnp.asarray(x) + jnp.float32(1.0), np.float64)
    exact = xp1.sum(axis=1)
    mag = np.abs(xp1).sum(axis=1)
    assert (np.abs(ff64(got) - exact) / np.maximum(mag, 1e-30)).max() \
        < 2.0 ** -40


def test_fused_vmem_budget_blocks():
    """Deeper chains get smaller tiles, never a budget blowout."""
    from repro.kernels.ff_fused import VMEM_BUDGET_BYTES, _pick_block
    shallow = _pick_block(4, 4096, 4096)
    deep = _pick_block(64, 4096, 4096)
    assert shallow[0] * shallow[1] >= deep[0] * deep[1]
    assert 64 * deep[0] * deep[1] * 4 <= VMEM_BUDGET_BYTES
    assert deep[0] % 8 == 0 and deep[1] % 128 == 0


def test_fused_output_shapes_match_jnp_executor(rng):
    """An output that depends on a SUBSET of operands must come back with
    the same (narrower) shape from both executors — the Pallas executor
    un-broadcasts each output to its inferred ND shape."""
    x = jnp.asarray(rng.standard_normal((4,)).astype(np.float32))
    y = jnp.asarray(rng.standard_normal((3, 4)).astype(np.float32))
    col = jnp.asarray(rng.standard_normal((3, 1)).astype(np.float32))

    @ff.fused
    def chain(x, y, c):
        return x + 1.0, x * y, c.sum()

    o_jnp = chain(x, y, col)
    o_pal = chain(x, y, col, interpret=True)
    assert o_jnp[0].shape == o_pal[0].shape == (4,)
    assert o_jnp[1].shape == o_pal[1].shape == (3, 4)
    # rowsum of a column-broadcast value reduces ITS one true column,
    # not C copies of it
    assert o_jnp[2].shape == o_pal[2].shape == (3,)
    _assert_bitwise(o_pal[0], o_jnp[0], "narrow f32 out")
    _assert_bitwise(o_pal[1], o_jnp[1], "full f32 out")
    _assert_ulp(o_pal[2].hi, o_jnp[2].hi, 1, "degenerate rowsum")
    assert np.allclose(ff64(o_pal[2]), np.asarray(col)[:, 0], atol=1e-7)


def test_fused_sub_emits_fsub(rng):
    """f32 subtraction lowers to a real fsub instruction (live in both
    executors) and matches jnp bitwise."""
    a = jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32))

    fn = ff.fused(lambda a, b: (a - b, 1.0 - b))
    prog = fn.program(a, b)
    assert any(i.op == "fsub" for i in prog.instrs)
    for interpret in (False, True):
        o1, o2 = fn(a, b, interpret=interpret)
        _assert_bitwise(o1, np.asarray(a - b), "a-b")
        _assert_bitwise(o2, np.asarray(1.0 - b), "1-b")


def test_tracer_guards():
    with pytest.raises(ValueError, match="trailing"):
        fusion.trace(lambda x: x.sum() + 1.0, ("f32",))
    with pytest.raises(TypeError, match="f32-valued"):
        fusion.trace(lambda x: x.sum(), ("ff",))
    with pytest.raises(TypeError):
        fusion.trace(lambda x: 3.0, ("f32",))


# ---------------------------------------------------------------------------
# composite kernels vs the op-by-op dispatch formulations
# ---------------------------------------------------------------------------

def _adamw_args(rng, shape):
    mk = lambda s=1.0: jnp.asarray(
        (rng.standard_normal(shape) * s).astype(np.float32))
    g, m, w = mk(), mk(0.1), mk()
    v = jnp.abs(mk(0.01))
    wlo = mk(1e-8)
    scal = tuple(jnp.float32(z) for z in (1e-3, 0.9, 0.95, 0.1, 0.05))
    return (g, m, v, w, wlo) + scal


@pytest.mark.parametrize("fused_impl,interpret", [("fused", False),
                                                  ("fused", True)])
def test_adamw_update_fused_bitwise(rng, fused_impl, interpret):
    """The fused AdamW chain is bitwise the jnp op-by-op chain in both
    executor modes (pure elementwise: no reduction, no ulp allowance)."""
    args = _adamw_args(rng, (33, 257))
    kw = dict(eps=1e-8, wd=0.1)
    ref = jax.jit(lambda *a: ff.adamw_update(*a, impl="jnp", **kw))(*args)
    got = jax.jit(lambda *a: ff.adamw_update(*a, impl=fused_impl,
                                             interpret=interpret,
                                             **kw))(*args)
    for r, g2 in zip(ref, got):
        _assert_bitwise(g2, r, f"adamw {fused_impl} interpret={interpret}")


def test_adamw_optimizer_matches_pre_fusion_formulation(rng):
    """optim.AdamW(ff=True) through the composite == the pre-fusion leaf
    written out op-by-op, bitwise (same jit)."""
    from repro.optim.adamw import AdamW

    shape = (13, 40)
    params = {"w": jnp.asarray(rng.standard_normal(shape).astype(np.float32))}
    grads = {"w": jnp.asarray(rng.standard_normal(shape).astype(np.float32))}
    opt = AdamW(learning_rate=1e-3, ff=True)
    state = opt.init(params)

    @jax.jit
    def step(g, s, p):
        return opt.update(g, s, p)

    new_p, new_s = step(grads, state, params)

    def reference(g, m, v, w, wlo, c):
        b1, b2 = jnp.float32(0.9), jnp.float32(0.95)
        lr = jnp.float32(1e-3)
        bc1 = 1.0 - b1 ** c.astype(jnp.float32)
        bc2 = 1.0 - b2 ** c.astype(jnp.float32)
        m2 = b1 * m + (1.0 - b1) * g
        v2 = b2 * v + (1.0 - b2) * g * g
        upd = (m2 / bc1) / (jnp.sqrt(v2 / bc2) + 1e-8)
        upd = upd + 0.1 * w
        delta = (-lr * upd).astype(jnp.float32)
        new = ff.add(FF(w, wlo), delta)
        return new.hi, new.lo, m2, v2

    ref = jax.jit(reference)(grads["w"], state.m["w"], state.v["w"],
                             params["w"], state.master_lo["w"],
                             state.count + 1)
    assert np.array_equal(np.asarray(new_p["w"]), np.asarray(ref[0]))
    assert np.array_equal(np.asarray(new_s.master_lo["w"]),
                          np.asarray(ref[1]))
    assert np.array_equal(np.asarray(new_s.m["w"]), np.asarray(ref[2]))
    assert np.array_equal(np.asarray(new_s.v["w"]), np.asarray(ref[3]))


def _softmax_ref(x):
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    s = compensated.ff_sum_blocked(e, axis=-1, block=256)
    return e / s.to_f32()[..., None]


@pytest.mark.parametrize("impl", ["jnp", "f64", "pallas"])
def test_softmax_impls_vs_op_by_op(rng, impl):
    x = jnp.asarray(rng.standard_normal((37, 300)).astype(np.float32))
    want = jax.jit(_softmax_ref)(x)
    got = jax.jit(lambda x: ff.softmax(x, impl=impl))(x)
    # denominator is a <=1-ulp-different compensated sum -> <=2 ulp out
    tol = 0 if impl == "jnp" else 2
    _assert_ulp(got, want, tol, f"softmax {impl}")
    # and correct vs the f64 oracle
    x64 = _f64(x)
    e = np.exp(x64 - x64.max(axis=-1, keepdims=True))
    oracle = e / e.sum(axis=-1, keepdims=True)
    assert np.abs(np.asarray(got, np.float64) - oracle).max() < 1e-6


@pytest.mark.parametrize("impl", ["jnp", "f64", "pallas"])
def test_logsumexp_impls_vs_op_by_op(rng, impl):
    x = jnp.asarray(rng.standard_normal((37, 300)).astype(np.float32))

    def ref(x):
        m = jnp.max(x, axis=-1, keepdims=True)
        e = jnp.exp(x - m)
        s = compensated.ff_sum_blocked(e, axis=-1, block=256)
        return jnp.squeeze(m, -1) + jnp.log(s.to_f32())

    want = jax.jit(ref)(x)
    got = jax.jit(lambda x: ff.logsumexp(x, impl=impl))(x)
    tol = 0 if impl == "jnp" else 1
    _assert_ulp(got, want, tol, f"logsumexp {impl}")
    x64 = _f64(x)
    oracle = np.log(np.exp(x64 - x64.max(-1, keepdims=True)
                           ).sum(-1)) + x64.max(-1)
    assert np.abs(np.asarray(got, np.float64) - oracle).max() < 1e-5


@pytest.mark.parametrize("impl", ["jnp", "fused"])
def test_mean_sq_impls_vs_op_by_op(rng, impl):
    x = jnp.asarray(f32_vec(rng, 16 * 700, -3, 3).reshape(16, 700))
    want = jax.jit(lambda x: compensated.ff_sum_blocked(
        x * x, axis=-1, block=128).to_f32() / 700)(x)
    got = jax.jit(lambda x: ff.mean_sq(x, impl=impl))(x)
    _assert_bitwise(got, np.asarray(want), f"mean_sq {impl}")
    # interpret-mode fused kernel: documented final-ulp allowance
    got_i = ff.mean_sq(x, impl="fused", interpret=True)
    _assert_ulp(got_i, want, 1, "mean_sq fused interpret")


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_norm_stats_impls_vs_op_by_op(rng, impl):
    x = jnp.asarray(rng.standard_normal((21, 500)).astype(np.float32))

    def ref(x):
        mu = compensated.ff_sum_blocked(x, axis=-1, block=128).to_f32() / 500
        var = compensated.ff_sum_blocked(
            (x - mu[..., None]) ** 2, axis=-1, block=128).to_f32() / 500
        return mu, var

    want_mu, want_var = jax.jit(ref)(x)
    got_mu, got_var = jax.jit(lambda x: ff.norm_stats(x, impl=impl))(x)
    tol_mu = 0 if impl == "jnp" else 1
    tol_var = 0 if impl == "jnp" else 2   # mu's ulp feeds the square pass
    _assert_ulp(got_mu, want_mu, tol_mu, f"norm_stats mu {impl}")
    _assert_ulp(got_var, want_var, tol_var, f"norm_stats var {impl}")
    x64 = _f64(x)
    assert np.abs(np.asarray(got_mu) - x64.mean(-1)).max() < 1e-6
    assert np.abs(np.asarray(got_var) - x64.var(-1)).max() < 1e-6


def test_composite_grads(rng):
    """Custom vjps of the composite wrappers vs analytic f64 gradients."""
    x = jnp.asarray(rng.standard_normal((5, 64)).astype(np.float32))
    x64 = _f64(x)

    g_ms = jax.grad(lambda t: ff.mean_sq(t).sum())(x)
    assert np.allclose(np.asarray(g_ms), 2 * x64 / 64, atol=1e-6)

    g_sm = jax.grad(lambda t: (ff.softmax(t) ** 2).sum())(x)
    e = np.exp(x64 - x64.max(-1, keepdims=True))
    y = e / e.sum(-1, keepdims=True)
    gy = 2 * y
    want = (gy - (gy * y).sum(-1, keepdims=True)) * y
    assert np.allclose(np.asarray(g_sm), want, atol=1e-5)

    g_ns = jax.grad(lambda t: ff.norm_stats(t)[1].sum())(x)
    mu = x64.mean(-1, keepdims=True)
    assert np.allclose(np.asarray(g_ns), 2 * (x64 - mu) / 64, atol=1e-6)


def test_rms_layer_norm_use_composites(rng):
    """models.layers ff_stats paths route through the composites and stay
    numerically indistinguishable from the pre-migration formulations."""
    from repro.models.layers import layer_norm, rms_norm

    x = jnp.asarray(rng.standard_normal((4, 9, 256)).astype(np.float32))
    w = jnp.ones((256,), jnp.float32)
    b = jnp.zeros((256,), jnp.float32)

    got = rms_norm(x, w, 1e-6, ff_stats=True)
    ms = compensated.ff_sum_blocked(x * x, axis=-1,
                                    block=128).to_f32() / 256
    want = x * jax.lax.rsqrt(ms + 1e-6)[..., None] * w
    assert np.allclose(np.asarray(got), np.asarray(want), atol=1e-7)

    got_ln = layer_norm(x, w, b, 1e-6, ff_stats=True)
    mu = compensated.ff_sum_blocked(x, axis=-1, block=128).to_f32() / 256
    var = compensated.ff_sum_blocked(
        (x - mu[..., None]) ** 2, axis=-1, block=128).to_f32() / 256
    want_ln = (x - mu[..., None]) * jax.lax.rsqrt(var[..., None] + 1e-6)
    assert np.allclose(np.asarray(got_ln), np.asarray(want_ln), atol=1e-6)


def test_logsumexp_registration_per_backend():
    """Satellite: logsumexp resolves per-backend like every other op —
    jnp is the generic fallback, the fused Pallas kernel is the TPU
    default, the native-f64 reduction the CPU default."""
    d = dispatch._DEFAULTS["logsumexp"]
    assert d["*"] == "jnp" and d["tpu"] == "pallas" and d["cpu"] == "f64"
    assert set(d) >= {"*", "tpu", "cpu"}
    for b, want in (("tpu", "pallas"), ("cpu", "f64")):
        orig = dispatch.backend
        try:
            dispatch.backend = lambda b=b: b
            assert dispatch.resolve_name("logsumexp") == want
        finally:
            dispatch.backend = orig
    # softmax and the composites follow the same pattern
    assert dispatch._DEFAULTS["softmax"]["tpu"] == "pallas"
    assert dispatch._DEFAULTS["adamw_update"]["tpu"] == "fused"
    assert dispatch._DEFAULTS["norm_stats"]["tpu"] == "pallas"
    assert dispatch._DEFAULTS["mean_sq"]["tpu"] == "fused"


def test_long_row_falls_back_to_jnp(rng, monkeypatch):
    """Rows beyond the VMEM whole-row budget must not brick the default."""
    from repro.kernels import ff_fused
    monkeypatch.setattr(ff_fused, "MAX_FUSED_COLS", 128)
    x = jnp.asarray(rng.standard_normal((4, 300)).astype(np.float32))
    # ... but never SILENTLY: an explicit impl= request must hear about it
    with pytest.warns(UserWarning, match="falling back"):
        got = ff.softmax(x, impl="pallas")
    want = jax.jit(_softmax_ref)(x)
    _assert_ulp(got, want, 2, "fallback softmax")


@pytest.mark.parametrize("op", ["logsumexp", "softmax", "mean_sq",
                                "norm_stats"])
def test_long_row_resolves_to_generic_default(op, monkeypatch):
    """A whole-row TPU default that cannot hold the row (a 49155-token
    vocabulary) is not resolved at all: the generic default is, named
    ``shape_default`` in telemetry, and nothing falls back in the call."""
    from repro import obs
    from repro.kernels import ff_fused
    monkeypatch.setattr(dispatch, "backend", lambda: "tpu")
    wide = (8, ff_fused.MAX_FUSED_COLS + 1)
    before = obs.REGISTRY.snapshot()
    assert dispatch.resolve_name(op, shape=wide) == \
        dispatch._DEFAULTS[op]["*"]
    assert dispatch.resolve_name(op, shape=(8, 2048)) == \
        dispatch._DEFAULTS[op]["tpu"]
    grew = [k for k, v in obs.REGISTRY.delta(before)["counters"].items()
            if v and f'op="{op}"' in k and 'source="shape_default"' in k]
    assert len(grew) == 1, grew


def test_fallback_warning_category(rng, monkeypatch):
    """A kernel impl that runs its jnp formulation warns with its own
    category, so a caller (the chip smoke) can make it an error."""
    from repro.kernels import ff_fused
    monkeypatch.setattr(ff_fused, "MAX_FUSED_COLS", 128)
    x = jnp.asarray(rng.standard_normal((4, 300)).astype(np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("error", ff.FFFallbackWarning)
        with pytest.raises(ff.FFFallbackWarning, match="falling back"):
            ff.logsumexp(x, impl="pallas")


# ---------------------------------------------------------------------------
# elementwise kernel shape handling (satellite: broadcasting + alignment)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sa,sb", [((17, 200), (1, 200)),
                                   ((17, 200), (17, 1)),
                                   ((17, 200), ()),
                                   ((3, 130), (3, 130)),
                                   ((4, 3, 65), (3, 65)),
                                   ((5,), (5,))])
def test_elementwise_kernel_broadcasting(rng, sa, sb):
    from repro.kernels import ff_elementwise as fe
    na, nb = int(np.prod(sa or (1,))), int(np.prod(sb or (1,)))
    a = f32_vec(rng, na, -2, 2).reshape(sa)
    b = f32_vec(rng, nb, -2, 2).reshape(sb)
    rh, rl = fe.elementwise("add22", a, np.zeros_like(a), b,
                            np.zeros_like(b), interpret=True)
    want = _f64(a) + _f64(b)
    assert rh.shape == want.shape
    got = _f64(rh) + _f64(rl)
    assert np.abs(got - want).max() <= 2.0 ** -40 * np.abs(want).max() + 1e-30


def test_elementwise_block_alignment():
    """Row blocks are rounded up to the 8-sublane multiple and column
    blocks to the 128-lane multiple (never a ragged (3, 130) block)."""
    from repro.kernels.ff_elementwise import pick_block
    assert pick_block(3, 130) == (8, 256)
    assert pick_block(1000, 1000, (256, 512)) == (256, 512)
    assert pick_block(4, 4) == (8, 128)
    br, bc = pick_block(300, 700, (100, 200))
    assert br % 8 == 0 and bc % 128 == 0
