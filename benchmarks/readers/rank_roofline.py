"""The GroupTopN barrier program's share of the HBM roofline: the bytes
its calls have to move (benchmarks/kernels/<kernel>: a lower bound, as a
function of the store's capacity, the emission size and the operand
widths) over its device time, over the chip's peak bandwidth, both sides
over the SAME epochs: the complete epochs of the device trace. args:
{"module": <regex of the XLA module>, "kernel": "<file>.py", "span": the
program's span around a call, "capacity", "out_lanes": the span's args
that carry them, "passes_span", "passes": the span (one a call, in the
same epoch) and arg that say how many sorts the call made, "key_bytes":
the widths of the stream-key lanes and of the order lane, "row_bytes"}.

Which epochs: as readers/scan_roofline.py takes them (the epochs of the
ring's ``traced`` barrier spans but the first; nothing if they are not
as many as the trace's ``cycles``). The program writes one ``span`` a
call. Nothing without a device trace, the module, the chip's peaks, the
ring or the span (a tree from before them)."""

import importlib.util
import os
import re

HERE = os.path.dirname(os.path.abspath(__file__))


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
    def of(name, *keys):
        return sorted(
            (
                (sp.epoch, sp.t0, sp) for sp in spans
                if sp.name == name and sp.epoch in epochs
                and all(k in sp.args for k in keys)
            ),
            key=lambda x: x[:2],
        )

    calls = of(args["span"], args["capacity"], args["out_lanes"])
    sorts = of(args["passes_span"], args["passes"])
    if not calls or len(calls) != len(sorts):
        return None
    kernel = _load(os.path.join(os.path.dirname(HERE), "kernels", args["kernel"]))
    moved = sum(
        kernel.bytes_moved(
            call.args[args["capacity"]], call.args[args["out_lanes"]],
            made.args[args["passes"]], args["key_bytes"], args["row_bytes"],
        )
        for (_, _, call), (_, _, made) in zip(calls, sorts)
    )
    return 100.0 * moved / seconds / peaks["hbm_bytes_per_s"]
