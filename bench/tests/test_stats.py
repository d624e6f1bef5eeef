"""Percentiles, rates and interval arithmetic, censored requests
included."""

import numpy as np
import pytest

import stats


@pytest.mark.parametrize("q", [0, 10, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy(q):
    x = np.random.default_rng(0).lognormal(size=101)
    assert stats.percentile(x, q) == pytest.approx(np.percentile(x, q))


def test_percentile_of_nothing():
    assert stats.percentile([], 90) is None


def test_ttft_counts_censored_requests():
    due = [0.0, 1.0, 2.0, 9.0, 11.0]
    first = [0.5, None, 12.0, 9.25, None]
    # due at 11 is after the close and does not count; the one with no
    # token and the one served after the close count their wait so far
    assert stats.ttfts(due, first, 10.0) == [0.5, 9.0, 8.0, 0.25]


def test_tail_sees_a_stall_at_the_close():
    due = [float(i) for i in range(10)]
    served = [d + 0.1 for d in due[:8]] + [None, None]
    t = stats.ttfts(due, served, 10.0)
    assert stats.percentile(t, 90) > 1.0


def test_gaps_and_counts_in_window():
    times = [0.5, 1.0, 1.0, 2.0, 3.5, 4.0]
    assert stats.gaps_in_window(times, 1.0, 3.6) == [0.5, 0.0, 1.0, 1.5]
    assert stats.count_in_window(times, 1.0, 3.6) == 4


def test_output_rate_reader():
    import importlib.util
    import os
    import types
    spec = importlib.util.spec_from_file_location(
        "r", os.path.join(stats.__file__.rsplit("/", 1)[0], "metrics",
                          "output_tokens_per_s.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    recs = [types.SimpleNamespace(times=[0.5, 1.5, 2.5]),
            types.SimpleNamespace(times=[1.0, 1.0, 3.9, 4.1])]
    run = {"recs": recs, "window": {"open": 1.0, "close": 4.0}}
    assert mod.read(run, "output_tokens_per_s") == pytest.approx(5 / 3)


def test_union_and_idle_gaps():
    iv = [(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.5, 3.6), (6.0, 7.0)]
    assert stats.union_length(iv, 0.0, 10.0) == pytest.approx(4.0)
    assert stats.union_length(iv, 1.5, 6.5) == pytest.approx(2.0)
    assert stats.idle_gaps(iv, 0.0, 8.0) == [(2.0, 3.0), (4.0, 6.0),
                                             (7.0, 8.0)]
