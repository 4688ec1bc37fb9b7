"""The part of a barrier, seen from outside, that no span of the program
accounts for. args: {"stages": [...]}. Median over the window's barriers
of the harness's own clock around the barrier (``t_return - t_inject``,
in ms) minus the sum of the named stages of the runtime's EpochTrace:
lock waits, the GIL, a stall. Nothing when a named stage is in no
epoch's ``stages_ms`` — a program that does not instrument it (a key
that is written reads 0.0 where its span did not run, never absent)."""

import statistics


def read(run, args):
    epochs = [e for e in run["epochs"] if e["stages_ms"]]
    for stage in args["stages"]:
        if not any(stage in e["stages_ms"] for e in epochs):
            return None
    return statistics.median(
        (e["t_return"] - e["t_inject"]) * 1e3
        - sum(e["stages_ms"].get(s, 0.0) for s in args["stages"])
        for e in epochs
    )
