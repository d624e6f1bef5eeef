"""idle_share: share of the traced window in which no operation ran on
the device (1 - union of device op intervals / window), in %, averaged
over the devices used."""

import stats


def read(run, name):
    red = run["trace"]
    if not red or not red["window"] or not red["devices"]:
        return None
    lo, hi = red["window"]["start"], red["window"]["end"]
    busy = [stats.union_length([(o["start"], o["end"]) for o in red["ops"]
                                if o["device"] == d], lo, hi)
            for d in red["devices"]]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
