"""A scan kernel's share of the HBM roofline: the bytes its calls have
to move (the function the metric file names under benchmarks/kernels/)
over its device time, over the chip's peak bandwidth, both sides over
the SAME epochs: the complete epochs of the device trace. args:
{"module": <regex of the XLA module>, "kernel": "<file>.py", "span",
"call_lanes": <arg>, "key_bytes", "residual_bytes"}.

The trace's complete epochs run from the end of the first barrier the
profiler session saw whole to the end of the last (trace_reduce's
``cycles``). The program's ring says which epochs those are: a span
has ``traced`` set when the session held it from start to end, so they
are the epochs of the traced ``barrier`` spans but the first. The
program writes what its scans did into one span an epoch (``span``);
``call_lanes`` is the lanes of the scanned side summed over the epoch's
calls. Nothing without a device trace, the module, the chip's peaks,
the ring or the span (a tree from before them), or when the ring's
traced epochs are not as many as the trace's."""

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
    lanes = ring.total(
        spans, set(traced[1:]), {"span": args["span"], "arg": args["call_lanes"]}
    )
    if not lanes:
        return None
    kernel = _load(os.path.join(os.path.dirname(HERE), "kernels", args["kernel"]))
    moved = kernel.bytes_moved(lanes, args["key_bytes"], args["residual_bytes"])
    return 100.0 * moved / seconds / peaks["hbm_bytes_per_s"]
