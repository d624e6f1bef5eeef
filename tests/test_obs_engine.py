"""The serving engine instrumented from inside (docs/DESIGN_observability.md):

  * under ``obs.enable()`` every ``step()`` is a ``serve.step`` span whose
    phases nest as documented, in the trace recorder and in the profiler
    at once, and outside it the engine records no ``serve.*`` span;
  * a request's ``prefill`` ends when its first token and both scores are
    on the host;
  * the decode and prefill programs are named ``jit_step_decode`` and
    ``jit_step_prefill``, and the decode program publishes a map of its
    instructions to named parts, built from the program just run (no
    second compile);
  * ``repro.obs.parts`` applies its rule to HLO text;
  * ``bench/obs_clock.py`` puts the recorder's spans on the profiler's
    clock.
"""

import glob
import os
import sys

import numpy as np
import pytest
import jax
import jax.monitoring

from repro import obs
from repro.models import init_params
from repro.models.config import ModelConfig
from repro.obs.parts import part_of, program_parts
from repro.serve import Request, ServeEngine

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench")

CFG = ModelConfig(name="obs-engine-test", family="dense", num_layers=2,
                  d_model=128, num_heads=4, num_kv_heads=2, d_ff=256,
                  vocab_size=512, max_seq_len=128, compute_dtype="bfloat16",
                  remat=False)

CHILDREN = {      # span -> the spans it may sit in directly
    "serve.schedule": {"serve.step"},
    "serve.admit": {"serve.step"},
    "serve.prefill": {"serve.admit"},
    "serve.prefill.wait": {"serve.prefill"},
    "serve.decode_prep": {"serve.step"},
    "serve.decode_step": {"serve.step"},
    "serve.flush": {"serve.step", "serve.schedule"},
    "serve.flush.wait": {"serve.flush"},
}


@pytest.fixture(scope="module")
def params():
    return init_params(CFG, jax.random.PRNGKey(0))


def _requests(n=3, max_new=5, seed=5):
    rng = np.random.default_rng(seed)
    return [Request(uid=i, prompt=rng.integers(
        1, CFG.vocab_size, size=int(rng.integers(6, 14))).astype(np.int32),
        max_new=max_new) for i in range(n)]


def _serve(params, enabled=True, reqs=None, **kw):
    eng = ServeEngine(params, CFG, max_batch=2, page_size=8, max_ctx=48,
                      obs=obs.Observer(), **kw)
    with obs.enable(enabled):
        for r in reqs or _requests():
            eng.submit(r)
        eng.run()
    return eng


def _engine_spans(eng):
    return [e for e in eng.obs.trace.events()
            if e["ph"] == "X" and e["tid"] == obs.ENGINE_TID]


def _parent(span, spans):
    """The innermost span that encloses ``span`` (None at top level)."""
    lo, hi = span["ts"], span["ts"] + span["dur"]
    outer = [s for s in spans if s is not span and s["ts"] <= lo
             and hi <= s["ts"] + s["dur"]]
    return min(outer, key=lambda s: s["dur"]) if outer else None


def test_engine_spans_nest(params):
    eng = _serve(params)
    spans = _engine_spans(eng)
    names = {s["name"] for s in spans}
    assert set(CHILDREN) | {"serve.step"} <= names
    steps = sorted((s for s in spans if s["name"] == "serve.step"),
                   key=lambda s: s["ts"])
    for a, b in zip(steps, steps[1:]):
        assert a["ts"] + a["dur"] <= b["ts"]       # steps do not overlap
    for s in spans:
        up = _parent(s, spans)
        if s["name"] == "serve.step":
            assert up is None
        else:
            assert up is not None and up["name"] in CHILDREN[s["name"]], (
                s["name"], up and up["name"])
    flushes = [s for s in spans if s["name"] == "serve.flush"]
    assert all(s["args"]["steps"] >= 1 for s in flushes)
    assert not any(e["name"] == "host_sync" for e in eng.obs.trace.events())


def test_no_engine_spans_outside_enable(params):
    eng = _serve(params, enabled=False)
    assert _engine_spans(eng) == []
    names = {e["name"] for e in eng.obs.trace.events() if e["ph"] == "X"}
    assert names == {"queued", "prefill", "decode", "request"}


def test_prefill_ends_with_first_token_on_host(params, monkeypatch):
    reads = []
    get = jax.device_get

    def spy(x):
        out = get(x)
        reads.append((eng.obs.trace.now(), out))
        return out

    eng = ServeEngine(params, CFG, max_batch=2, page_size=8, max_ctx=48,
                      obs=obs.Observer())
    monkeypatch.setattr(jax, "device_get", spy)
    with obs.enable():
        for r in _requests():
            eng.submit(r)
        res = eng.run()
    events = eng.obs.trace.events()
    waits = [s for s in _engine_spans(eng)
             if s["name"] == "serve.prefill.wait"]
    for uid, r in res.items():
        tid = eng.obs.trace.request_tid(uid)
        pf = [e for e in events if e["ph"] == "X" and e["tid"] == tid
              and e["name"] == "prefill"]
        assert len(pf) == 1
        lo, hi = pf[0]["ts"], pf[0]["ts"] + pf[0]["dur"]
        # the read that brought the first token and its two scores home
        got = [t for t, out in reads if isinstance(out, tuple)
               and len(out) == 4 and int(out[0][0]) == r.tokens[0]
               and float(out[1][0]) == r.logprobs[0]
               and float(out[2][0]) == r.logprobs_ff[0, 0]
               and lo <= t <= hi]
        assert got, uid
        assert any(w["ts"] <= got[0] <= w["ts"] + w["dur"] + 1.0
                   for w in waits)


def test_program_names_and_parts(params):
    eng = _serve(params)
    events = eng.obs.trace.events()
    progs = [e["args"] for e in events
             if e["ph"] == "M" and e["name"] == "program"]
    assert len(progs) == 1 and progs[0]["name"] == "jit_step_decode"
    found = set(progs[0]["parts"].values())
    assert {"kv", "head", "sample"} <= found
    # the engine serves weights already in the compute dtype
    assert "cast" not in found
    built = [e["args"] for e in events if e["name"] == "program_built"]
    assert built[0]["name"] == "jit_step_prefill"
    assert {b["name"] for b in built} == {"jit_step_prefill",
                                          "jit_step_decode"}
    S = int(_requests()[0].prompt.shape[0])
    fn = eng._prefill_cache[S]
    cache = {"layers": {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                        for k, v in eng.prefill(_requests()[0].prompt)[1][
                            "layers"].items()}}
    lowered = fn.lower(params, {"tokens": jax.ShapeDtypeStruct(
        (1, S), np.int32)}, cache)
    assert "jit_step_prefill" in lowered.as_text()


def test_part_map_compiles_nothing_more(params):
    seen = {"on": False, "n": 0}

    def hear(name, secs, **kw):
        if seen["on"] and name == \
                "/jax/core/compile/backend_compile_duration":
            seen["n"] += 1
    jax.monitoring.register_event_duration_secs_listener(hear)
    counts = {}
    try:
        for enabled in (False, True):
            seen.update(on=True, n=0)
            _serve(params, enabled=enabled)
            counts[enabled] = seen["n"]
    finally:
        seen["on"] = False
    assert counts[True] == counts[False] > 0


# -- repro.obs.parts on hand-written HLO text ------------------------------

HLO = """HloModule jit_step_decode, is_scheduled=true

%fused_computation (p0: f32[4]) -> bf16[4] {
  %p0 = f32[4]{0} parameter(0)
  %convert.1 = bf16[4]{0} convert(%p0), metadata={op_name="jit(step_decode)/while/body/closed_call/mlp/cast/convert_element_type"}
  ROOT %dot.1 = bf16[4]{0} multiply(%convert.1, %convert.1), metadata={op_name="jit(step_decode)/while/body/closed_call/mlp/dot_general"}
}

%fused_computation.2 (p0.2: bf16[2,4], p1.2: bf16[4]) -> bf16[2,4] {
  %p0.2 = bf16[2,4]{1,0} parameter(0)
  %p1.2 = bf16[4]{0} parameter(1)
  %scatter.1 = bf16[4]{0} add(%p1.2, %p1.2), metadata={op_name="jit(step_decode)/while/body/closed_call/kv/scatter"}
  ROOT %dus.1 = bf16[2,4]{1,0} dynamic-update-slice(%p0.2, %scatter.1), metadata={op_name="jit(step_decode)/while/body/dynamic_update_slice"}
}

%body (arg: (bf16[2,4], f32[2,4])) -> (bf16[2,4], f32[2,4]) {
  %arg = (bf16[2,4]{1,0}, f32[2,4]{1,0}) parameter(0)
  %gte.0 = bf16[2,4]{1,0} get-tuple-element(%arg), index=0
  %gte.1 = f32[2,4]{1,0} get-tuple-element(%arg), index=1
  %slice.1 = f32[4]{0} dynamic-slice(%gte.1), metadata={op_name="jit(step_decode)/while/body/dynamic_slice"}
  %copy.1 = f32[4]{0} copy(%slice.1)
  %fusion.1 = bf16[4]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step_decode)/while/body/closed_call/mlp/dot_general"}
  %fusion.2 = bf16[2,4]{1,0} fusion(%gte.0, %fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step_decode)/while/body/dynamic_update_slice"}
  %copy.2 = bf16[2,4]{1,0} copy(%fusion.2)
  ROOT %tuple.1 = (bf16[2,4]{1,0}, f32[2,4]{1,0}) tuple(%copy.2, %gte.1)
}

ENTRY %main (w: f32[2,4], tok: f32[8,4]) -> bf16[8] {
  %w = f32[2,4]{1,0} parameter(0)
  %tok = f32[8,4]{1,0} parameter(1)
  %convert.9 = bf16[8,4]{1,0} convert(%tok), metadata={op_name="jit(step_decode)/embed/cast/convert_element_type"}
  %convert.10 = bf16[2,4]{1,0} convert(%w)
  %gather.1 = bf16[4]{0} slice(%convert.9), metadata={op_name="jit(step_decode)/embed/gather"}
  %while.1 = (bf16[2,4]{1,0}, f32[2,4]{1,0}) while(%convert.10), condition=%cond, body=%body, metadata={op_name="jit(step_decode)/while"}
  %dot.9 = bf16[8]{0} dot(%convert.9, %gather.1), metadata={op_name="jit(step_decode)/head/dot_general"}
  ROOT %argmax.1 = bf16[8]{0} reduce(%dot.9), metadata={op_name="jit(step_decode)/sample/argmax"}
}
"""


def test_part_of_rule():
    assert part_of("jit(f)/while/body/closed_call/mlp/cast/convert") == "cast"
    assert part_of("jit(f)/head/dot_general") == "head"
    assert part_of("jit(f)/attn/while/body/kv/x") == "attn"    # the first
    assert part_of("jit(f)/broadcast_in_dim") == "other"       # no part


def test_program_parts_on_hlo_text():
    module, parts = program_parts(HLO)
    assert module == "jit_step_decode"
    assert parts["convert.9"] == "cast"          # a cast scope anywhere
    assert parts["convert.10"] == "cast"         # moved by XLA, no metadata
    assert parts["fusion.1"] == "mlp"            # fused convert: consumer's
    assert parts["fusion.2"] == "kv"             # scan's root, kv inside
    assert parts["copy.2"] == "kv"               # XLA's copy: its operand's
    assert parts["slice.1"] == "mlp"             # scan's slice: its user's
    assert parts["copy.1"] == "mlp"
    assert parts["dot.9"] == "head" and parts["argmax.1"] == "sample"
    assert "gather.1" not in parts               # embed: no part -> other
    assert "while.1" not in parts and "convert.1" not in parts  # fused


HLO_REDUCER = """HloModule jit_step_decode, is_scheduled=true

%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="reduce"}
  %b = f32[] parameter(1), metadata={op_name="reduce"}
  %gt.0 = pred[] compare(%a, %b), direction=GT, metadata={op_name="gt"}
  %select.0 = f32[] select(%gt.0, %a, %b), metadata={op_name="select_n"}
  %convert.2 = bf16[] convert(%select.0)
  ROOT %convert.3 = f32[] convert(%convert.2)
}

ENTRY %main (lg: f32[8,16]) -> f32[8] {
  %lg = f32[8,16]{1,0} parameter(0)
  %c = f32[] constant(0)
  ROOT %reduce.1 = f32[8]{0} reduce(%lg, %c), dimensions={1}, to_apply=%region_0.1, metadata={op_name="jit(step_decode)/sample/argmax"}
}
"""


def test_program_parts_skip_applied_computations():
    """A reduction's ``to_apply`` runs inside the reduction: its
    instructions are no operations, so a convert there is not ``cast``."""
    _, parts = program_parts(HLO_REDUCER)
    assert parts["reduce.1"] == "sample"
    assert not {"a", "b", "gt.0", "select.0", "convert.2",
                "convert.3"} & set(parts)


# -- the shared clock --------------------------------------------------------

def test_obs_clock_aligns_a_cpu_trace(params, tmp_path):
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import obs_clock
    import trace_reduce

    eng = ServeEngine(params, CFG, max_batch=2, page_size=8, max_ctx=48,
                      obs=obs.Observer())
    with obs.enable():
        for r in _requests(n=2, max_new=8, seed=6):     # warm every shape
            eng.submit(Request(uid=100 + r.uid, prompt=r.prompt,
                               max_new=r.max_new))
        eng.run()
        with jax.profiler.trace(str(tmp_path)):
            for r in _requests(n=2, max_new=8, seed=6):
                eng.submit(r)
            eng.run()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    red = trace_reduce.reduce_trace(path[0])
    traced = sorted(h["start"] for h in red["host"]
                    if h["name"] == "serve.step")
    clock = obs_clock.align(eng.obs.trace.events(), red)
    assert clock is not None and clock["pairs"] == len(traced) >= 3
    assert clock["max_err_s"] <= 0.5e-3
    rec = sorted(e["ts"] for e in _engine_spans(eng)
                 if e["name"] == "serve.step")
    placed = [obs_clock.to_trace(t, clock) for t in rec]
    for t in traced:
        assert min(abs(t - p) for p in placed) <= 0.5e-3
