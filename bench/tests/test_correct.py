"""``correct`` on a Granite-shaped cell small enough for the CPU: a sound
run passes, the fp8 control fails the limits, and so does a run whose
timed path is broken underneath.

The limits here are set like the cell's (bench/limits/): above what
sound runs of this size read (logprob errors 0.035-0.044, logit gaps
0-0.022 on the CPU) and below the control's (0.14-0.36)."""

import pytest

import run
import tiny

LIMITS = {"logit_gap": 0.08, "logprob_err": 0.1, "logprob_ff_err": 0.1}
SEED = 2 ** 33 + 17


CELLS = {"open_loop": "granite_3_2b.chat", "backlog": "minitron_4b.offline",
         "prefill": "granite_3_2b.prefill"}


def cell(kind="open_loop", control=False):
    parts = tiny.parts(kind)
    parts["limits"] = dict(LIMITS)
    return run.run_cell(CELLS[kind], SEED, 2.0, False, parts=parts,
                        need_chip=False, control=control)


def _break_decode(monkeypatch, fault):
    from repro.serve import engine as eng_mod
    make = eng_mod.ServeEngine._make_decode_step

    def broken(self):
        step = make(self)
        V = self.cfg.vocab_size

        def wrapped(params, token, lens, bt, active, planes):
            nxt, lp, hi, lo, bad, new = step(params, token, lens, bt,
                                             active, planes)
            if fault == "state_unchanged":
                new = planes
            elif fault == "token_altered":
                nxt = (nxt + 1) % V
            return nxt, lp, hi, lo, bad, new
        return wrapped
    monkeypatch.setattr(eng_mod.ServeEngine, "_make_decode_step", broken)


def _break_prefill(monkeypatch, fault):
    from repro.serve import engine as eng_mod
    if fault == "prefill_token_altered":
        first = eng_mod.ServeEngine._prefill_first

        def broken(self, slot, prompt):
            tok, lp, lp_ff = first(self, slot, prompt)
            return (tok + 1) % self.cfg.vocab_size, lp, lp_ff
        monkeypatch.setattr(eng_mod.ServeEngine, "_prefill_first", broken)
    elif fault == "prefill_cache_unwritten":
        monkeypatch.setattr(eng_mod.PagedKVCache, "write_prefill",
                            lambda self, slot, tensors: None)


def _argmax_off_by_one(monkeypatch):
    # every greedy token, in the prefill and in the decode step, is the
    # one after the argmax, and both scoring tiers score the token served:
    # the logprobs stay right for the wrong token
    from repro.serve import engine as eng_mod
    real = eng_mod.jnp

    class Jnp:
        def __getattr__(self, k):
            return getattr(real, k)

        @staticmethod
        def argmax(x, axis=None, **kw):
            return (real.argmax(x, axis, **kw) + 1) % x.shape[-1]
    monkeypatch.setattr(eng_mod, "jnp", Jnp())


@pytest.mark.parametrize("kind", ["open_loop", "backlog", "prefill"])
def test_sound_run_is_correct(kind):
    res = cell(kind, control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    want = {m["name"] for m in run.cell_metrics(run.load_benchmark(),
                                                 CELLS[kind], False)}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    for k in LIMITS:
        assert res["checks"][k]["limit"] == LIMITS[k]
    # the control: the reference with float8 matrix inputs, judged by the
    # same comparison against the same limits, is not correct
    ctl = res["control"]
    assert ctl["correct"] is False, ctl["checks"]
    assert {k: c["limit"] for k, c in ctl["checks"].items()} == LIMITS


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    _break_decode(monkeypatch, fault)
    res = cell()
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["prefill_token_altered",
                                   "prefill_cache_unwritten"])
def test_broken_prefill_is_not_correct(monkeypatch, fault):
    # the prefill cell's tokens come from the prefill, or from decode
    # steps that read what it wrote
    _break_prefill(monkeypatch, fault)
    res = cell("prefill")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("kind", ["open_loop", "prefill"])
def test_a_token_rescored_as_served_is_not_correct(monkeypatch, kind):
    # the logprob numbers pass such a run; the logit gap catches it
    _argmax_off_by_one(monkeypatch)
    res = cell(kind)
    assert not res["correct"], res["checks"]
    c = res["checks"]
    assert c["logprob_err"]["value"] <= c["logprob_err"]["limit"]
    assert c["logit_gap"]["value"] > c["logit_gap"]["limit"]


def test_a_cell_compares_at_least_one_number():
    parts = tiny.parts("prefill")
    parts["limits"] = {"from": "no number"}
    with pytest.raises(ValueError, match="compare none"):
        run.run_cell(CELLS["prefill"], SEED, 2.0, False, parts=parts,
                     need_chip=False)


@pytest.mark.parametrize("cell_name", [w["name"] for w in
                                       run.load_benchmark()["workloads"]])
def test_every_cell_compares_the_logit_gap(cell_name):
    # the served tokens themselves are compared in every cell, not only
    # their logprobs
    limits = run.resolve(run.load_benchmark(), cell_name)["limits"]
    assert limits["logit_gap"] > 0


def test_a_cell_compares_the_numbers_its_limits_hold():
    parts = tiny.parts("prefill")
    parts["limits"] = {k: LIMITS[k] for k in ("logprob_err",
                                              "logprob_ff_err")}
    res = run.run_cell(CELLS["prefill"], SEED, 2.0, False, parts=parts,
                       need_chip=False, control=True)
    assert res["correct"], res["checks"]
    assert list(res["checks"]) == ["logprob_err", "logprob_ff_err"]
    assert list(res["control"]["checks"]) == list(res["checks"])
    assert res["control"]["correct"] is False
    # the control tool still reads every number
    assert set(res["control"]["readings"]) == set(run.CHECKED)
    assert set(res["control"]["program"]) == set(run.CHECKED)


def test_deliveries_match_with_one_token_answers(monkeypatch):
    # a request of one token retires at its admission: the client times
    # every token the engine returns, and no more
    seen = []

    class Spy(run.Client):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            seen.append(self)

    monkeypatch.setattr(run, "Client", Spy)
    res = cell("prefill")
    assert res["correct"], res["checks"]
    client = seen[0]
    assert client.mismatch == []
    done = [r for u, r in client.recs.items() if u >= 0 and r.status == "OK"]
    assert {r.item.max_new for r in done} == {1, 2, 3, 4}
    assert all(len(r.times) == r.item.max_new for r in done)


def test_deliveries_match_when_pages_limit_admission(monkeypatch):
    # a pool smaller than the rows' trajectories: admission waits for
    # pages, and the client still times every token the engine returns
    seen = []

    class Spy(run.Client):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            seen.append(self)
            self.page_bound = 0

        def step(self):
            if self.queued and len(self.running) < self.max_batch:
                self.page_bound += 1
            return super().step()

    monkeypatch.setattr(run, "Client", Spy)
    parts = tiny.parts("backlog")
    parts["limits"] = dict(LIMITS)
    parts["mix"]["engine"]["num_pages"] = 12
    res = run.run_cell("minitron_4b.offline", SEED, 2.0, False, parts=parts,
                       need_chip=False)
    assert res["correct"], res["checks"]
    client = seen[0]
    assert client.page_bound > 0
    assert client.mismatch == []
    done = [r for u, r in client.recs.items() if u >= 0 and r.status == "OK"]
    assert done and all(len(r.times) == r.item.max_new for r in done)
