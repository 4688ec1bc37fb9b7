"""A series the harness's own clock took in the window.
args: {"series": "probe_ms" | "lags_ms" | "fresh_ms", "stat": "median" |
"p95"}. Nothing where the statistic falls on an event no probe showed
(``fresh_ms`` books such an event as inf)."""

import math
import statistics


def read(run, args):
    values = sorted(run.get(args["series"]) or ())
    if not values:
        return None
    if args["stat"] == "median":
        value = statistics.median(values)
    else:
        value = values[min(int(0.95 * len(values)), len(values) - 1)]
    return value if math.isfinite(value) else None
