"""Numbers from the profiler trace's summary (trace_reduce.reduce).
args: {"quantity": "idle_share"} -> 100 * (1 - busy / traced span);
{"quantity": "module_ms_per_barrier", "module": <regex>} -> device ms of
the XLA modules (jitted programs) whose name matches, per complete
epoch of the trace. Nothing when no device operation (or no such
module, or no complete epoch) was traced."""

import re


def read(run, args):
    t = run.get("device_trace")
    if not t or t["busy_s"] <= 0:
        return None
    if args["quantity"] == "idle_share":
        return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
    if args["quantity"] == "module_ms_per_barrier":
        wanted = re.compile(args["module"])
        seconds = sum(
            s for name, s in t["modules_in_cycles_s"].items()
            if wanted.search(name)
        )
        if not t["cycles"] or seconds <= 0:
            return None
        return seconds * 1e3 / t["cycles"]
    raise KeyError(args["quantity"])
