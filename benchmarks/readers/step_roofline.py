"""A join step's share of the HBM roofline: the bytes the epoch's rows
and pairs have to move (the function the metric file names under
benchmarks/kernels/) over the module's device time, over the chip's
peak bandwidth, both over the SAME epochs: the complete epochs of the
device trace (``scan_roofline`` says which those are). args: {"module":
<regex of the XLA module>, "kernel": "<file>.py", "span": the span the
join writes once an epoch, "left_row_bytes", "right_row_bytes",
"key_bytes"}.

The span carries what each side holds (``left_rows``, ``right_rows``)
and the pairs the equi key matched (``pairs_kept`` + ``pairs_dropped``):
the rows stored in the traced epochs are what the sides gained between
the first traced barrier and the last. Nothing without a device trace,
the module, the chip's peaks, the ring or the span's args (a tree from
before them), or when the ring's traced epochs are not as many as the
trace's."""

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
    ring = _load(os.path.join(HERE, "epoch_spans.py"))
    spans = ring.ring()
    if not spans:
        return None
    traced = sorted(
        sp.epoch for sp in spans
        if sp.name == "barrier" and getattr(sp, "traced", False)
    )
    if len(traced) - 1 != t["cycles"]:
        return None
    held = {
        sp.epoch: sp.args for sp in spans
        if sp.name == args["span"] and "left_rows" in sp.args
    }
    first, last = held.get(traced[0]), held.get(traced[-1])
    if first is None or last is None:
        return None
    pairs = sum(
        held[e]["pairs_kept"] + held[e]["pairs_dropped"]
        for e in traced[1:] if e in held
    )
    kernel = _load(os.path.join(os.path.dirname(HERE), "kernels", args["kernel"]))
    moved = kernel.bytes_moved(
        last["left_rows"] - first["left_rows"],
        last["right_rows"] - first["right_rows"],
        pairs,
        args["left_row_bytes"], args["right_row_bytes"], args["key_bytes"],
    )
    if moved <= 0:
        return None
    return 100.0 * moved / seconds / peaks["hbm_bytes_per_s"]
