"""ttft_ms.pNN, ttft_pNN_ms: NN-th percentile of the time from a request's due time to
the return of its first token, over every request due in the window; one
still waiting at the close counts with the time it has waited."""

import stats


def read(run, name):
    q = stats.name_percentile(name)
    v = stats.percentile(stats.window_ttfts(run["recs"], run["window"]), q)
    return None if v is None else v * 1e3
