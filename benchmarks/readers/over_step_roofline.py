"""The general over-window's share of the HBM roofline: the bytes a
barrier's steps have to move (benchmarks/kernels/<kernel>: a count of
the work, from the rows the executor says it took, recomputed and
handed on, never from its capacity) over its modules' device time, over
the chip's peak bandwidth, both sides over the SAME epochs: the
complete epochs of the device trace (``scan_roofline`` says which:
those of the ring's ``traced`` barrier spans but the first; nothing if
they are not as many as the trace's ``cycles``). args: {"module":
<regex of the XLA modules>, "kernel": "<file>.py", "barrier_span": the
span the executor writes once an epoch (args in_rows, dirty_rows,
retract_rows, insert_rows), "step_span": the span around a step (arg
row_bytes), "key_bytes": the widths of the partition and order lanes,
"out_bytes": of the calls' results}.

Nothing without a device trace, the modules, the chip's peaks, the
ring or the spans (a tree from before them)."""

import importlib.util
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))
COUNTS = ("in_rows", "dirty_rows", "retract_rows", "insert_rows")


def _load(path):
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3], path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(run, args):
    t, peaks = run.get("device_trace"), run.get("peaks")
    if not t or not t.get("cycles") or not peaks:
        return None
    wanted = re.compile(args["module"])
    seconds = sum(
        s for name, s in t["modules_in_cycles_s"].items() if wanted.search(name)
    )
    if seconds <= 0:
        return None
    spans = _load(os.path.join(HERE, "epoch_spans.py")).ring()
    if not spans:
        return None
    traced = sorted(
        sp.epoch for sp in spans
        if sp.name == "barrier" and getattr(sp, "traced", False)
    )
    if len(traced) - 1 != t["cycles"]:
        return None
    epochs = set(traced[1:])
    barriers = [
        sp.args for sp in spans
        if sp.name == args["barrier_span"] and sp.epoch in epochs
        and all(k in sp.args for k in COUNTS)
    ]
    widths = {
        sp.args["row_bytes"] for sp in spans
        if sp.name == args["step_span"] and sp.epoch in epochs
        and "row_bytes" in sp.args
    }
    if not barriers or len(widths) != 1:
        return None
    kernel = _load(os.path.join(os.path.dirname(HERE), "kernels", args["kernel"]))
    moved = kernel.bytes_moved(
        *(sum(b[k] for b in barriers) for k in COUNTS),
        widths.pop(), args["key_bytes"], args["out_bytes"],
    )
    if moved <= 0:
        return None
    return 100.0 * moved / seconds / peaks["hbm_bytes_per_s"]
