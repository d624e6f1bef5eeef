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


def cell(kind="open_loop", control=False):
    parts = tiny.parts(kind)
    parts["limits"] = dict(LIMITS)
    name = "granite_3_2b.chat" if kind == "open_loop" \
        else "minitron_4b.offline"
    return run.run_cell(name, SEED, 2.0, False, parts=parts,
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


@pytest.mark.parametrize("kind", ["open_loop", "backlog"])
def test_sound_run_is_correct(kind):
    res = cell(kind, control=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    name = "granite_3_2b.chat" if kind == "open_loop" \
        else "minitron_4b.offline"
    want = {m["name"] for m in run.cell_metrics(run.load_benchmark(), name,
                                                 False)}
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
