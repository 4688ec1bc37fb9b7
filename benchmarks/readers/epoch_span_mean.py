"""A count the program writes into the args of a span once an epoch,
per barrier: its sum over the window's epochs (``epoch_spans`` says
which those are and reads the ring) divided by the window's epochs
that carry it. args: {"span": name, "arg": key}. Nothing when the
program has no ring, no such span or no such arg (a tree from before
they were written), or when the ring no longer holds the window."""

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
    carrying = {
        sp.epoch for sp in spans
        if sp.name == args["span"] and sp.epoch in epochs
        and args["arg"] in sp.args
    }
    if not carrying:
        return None
    return ring.total(spans, carrying, args) / len(carrying)
