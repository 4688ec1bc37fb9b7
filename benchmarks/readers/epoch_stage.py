"""Host-clock stages of the runtime's own EpochTrace, per barrier of
the window. args: {"stages": [...], "per": "barrier" | "event"}.
"barrier": median over the barriers of the stages' sum, in ms;
"event": the stages' sum over the whole window divided by the events
pushed, in us."""

import statistics


def read(run, args):
    sums = [
        sum(e["stages_ms"].get(s, 0.0) for s in args["stages"])
        for e in run["epochs"]
        if e["stages_ms"]
    ]
    if not sums:
        return None
    if args["per"] == "barrier":
        return statistics.median(sums)
    events = sum(e["events"] for e in run["epochs"] if e["stages_ms"])
    return sum(sums) * 1e3 / events if events else None
