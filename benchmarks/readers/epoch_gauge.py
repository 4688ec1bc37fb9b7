"""Levels the program writes into the args of a span once an epoch
(what a join side holds, how full its fullest key is), over the epochs
of the window (``epoch_spans.window_epochs``). A level is no count: it
is not summed. args: {"span": name, "args": [keys], "quantity": "peak"
-> the largest value any of the keys reads in the window |
"growth_per_event" -> what the keys' sum gained between the window's
first epoch and its last, over the events the harness pushed in the
epochs after the first}. Nothing when the program has no ring, no such
span or arg (a tree from before they were written), or fewer than two
of the window's epochs carry it."""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def _ring():
    spec = importlib.util.spec_from_file_location(
        "epoch_spans", os.path.join(HERE, "epoch_spans.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(run, args):
    ring = _ring()
    spans = ring.ring()
    if not spans:
        return None
    epochs = ring.window_epochs(run, spans)
    levels = {
        sp.epoch: [sp.args[k] for k in args["args"]]
        for sp in spans
        if sp.name == args["span"] and sp.epoch in epochs
        and all(k in sp.args for k in args["args"])
    }
    if len(levels) < 2:
        return None
    if args["quantity"] == "peak":
        return max(max(v) for v in levels.values())
    if args["quantity"] == "growth_per_event":
        first, last = min(levels), max(levels)
        events = sum(n for e, n in epochs.items() if first < e <= last)
        if events <= 0:
            return None
        return (sum(levels[last]) - sum(levels[first])) / events
    raise KeyError(args["quantity"])
