"""One kind of time along a barrier's critical path, as the program's
own reduction of its span ring gives it (risingwave_tpu/trace.py::
barrier_path: the barrier's thread, and over its waits for the graph
the actor that released it last, up to the sources). args: {"kind":
"host" | "device_wait" | "io" | "permit" | "queue" | "unattributed"}:
``host`` is the time the path's thread ran with nothing awaited,
``device_wait`` the time it spent inside ``device.read`` spans, blocked
on a device->host copy or a fence. Median over the window's epochs (as
readers/epoch_spans.py finds them), in ms.

Nothing when the program has no ring or no ``barrier_path`` (a tree
from before it), when the ring no longer holds the window, or when an
epoch of the window has no path there (its barrier's spans were
dropped)."""

import importlib.util
import os
import statistics

HERE = os.path.dirname(os.path.abspath(__file__))


def _load(path):
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3], path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(run, args):
    try:
        from risingwave_tpu.trace import barrier_path
    except Exception:  # noqa: BLE001 - a program without the reduction
        return None
    ring = _load(os.path.join(HERE, "epoch_spans.py"))
    spans = ring.ring()
    if not spans:
        return None
    epochs = ring.window_epochs(run, spans)
    if not epochs:
        return None
    paths = [barrier_path(epoch, spans) for epoch in sorted(epochs)]
    if any(p is None for p in paths):
        return None
    return statistics.median(p["by_kind"][args["kind"]] for p in paths)
