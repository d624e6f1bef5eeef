"""output_tokens_per_s: every output token returned in the window, over
the window's length."""

import stats


def read(run, name):
    w = run["window"]
    n = sum(stats.count_in_window(r.times, w["open"], w["close"])
            for r in run["recs"])
    return n / (w["close"] - w["open"])
