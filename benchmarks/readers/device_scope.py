"""Seconds of the traced span that the run's largest device operations
spend under given named scopes. The operations are the rows of the
trace's summary (``run["device_trace"]["breakdown"]["device_ops"]``:
``[["module/instruction", seconds], ...]``, the ten largest, as
trace_reduce.reduce keeps them), and what each one is comes from the
program itself: ``risingwave_tpu.trace.name_ops`` reads the optimized
HLO of the executables this very process ran, so at the cell's own
shapes and with the compiler's own instruction names, and says each
row's scope path, and whether a row that holds it (a loop its body's
fusion runs in) is among the rows too. args: {"scopes": [<scope>, ...]}:
a row counts when its scope path holds one of them (``hash/probe`` is
held by ``topn/rows/hash/probe`` and by ``hash/probe/match``); a nested
row is left out, so no second is counted twice.

The number is a FLOOR by construction: ten operations are all a reader
is handed, and a loop that ranks eleventh is not in it. A ``benchmark``
PR whose trace_reduce keeps every operation and calls ``name_ops``
itself turns it into a device time per barrier by scope (PERF.md 7).

0.0 where the map names the rows and none lies under the scopes;
nothing where the program has no ``name_ops`` (a tree from before it),
where the map names none of the rows, or where the run has no device
trace."""


def read(run, args):
    t = run.get("device_trace")
    rows = t and t.get("breakdown", {}).get("device_ops")
    if not rows:
        return None
    try:
        from risingwave_tpu.trace import name_ops
    except Exception:  # noqa: BLE001 - a program without the map
        return None
    named = name_ops(rows)
    if all(row.get("scope") is None for row in named):
        return None
    wanted = ["/" + scope + "/" for scope in args["scopes"]]
    return sum(
        row["seconds"] for row in named
        if not row["nested"] and row.get("scope")
        and any(w in "/" + row["scope"] + "/" for w in wanted)
    )
