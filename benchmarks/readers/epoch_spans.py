"""Counts the program writes into the args of its spans once an epoch
(its span ring, risingwave_tpu/trace.py), over the epochs of the window
only: a counter runs from process start, DDL and preload included, and
the preload's epochs are of other sizes than the window's. args:
{"numerator": <sum>, "denominator": [<sum>, ...] | "events", "scale":
1 | 100}; a <sum> is {"span": name, "arg": key}: that arg over every
span of that name whose epoch lies in the window. "events" is the
harness's own count of the events it pushed in those epochs. The metric
is scale x numerator / the denominators' total.

Which epochs are the window's: those whose ``barrier`` span began
between ``t_inject`` and ``t_return`` of one of the run's epochs (the
harness's clock is time.monotonic, the ring's time.perf_counter; the
difference is read here). Nothing when the program has no ring or no
such span or arg (a tree from before they were written), when the ring
no longer holds the window, or when the denominator is 0."""

import time


def ring():
    """The program's closed spans, or None where it keeps none."""
    try:
        from risingwave_tpu.trace import TRACER
    except Exception:  # noqa: BLE001 - a program without the ring
        return None
    return TRACER.spans()


def window_epochs(run, spans):
    """{epoch number: events pushed in it} for the window's epochs."""
    offset = time.monotonic() - time.perf_counter()
    began = sorted(
        (sp.t0 + offset, sp.epoch) for sp in spans
        if sp.name == "barrier" and sp.epoch is not None
    )
    out = {}
    for e in run["epochs"]:
        mine = [n for t, n in began if e["t_inject"] <= t <= e["t_return"]]
        if len(mine) == 1:
            out[mine[0]] = e["events"]
    return out


def total(spans, epochs, spec):
    """The arg's sum over the named spans of ``epochs``; None when no
    such span carries it."""
    values = [
        sp.args[spec["arg"]] for sp in spans
        if sp.name == spec["span"] and sp.epoch in epochs
        and spec["arg"] in sp.args
    ]
    return sum(values) if values else None


def read(run, args):
    spans = ring()
    if not spans:
        return None
    epochs = window_epochs(run, spans)
    if not epochs:
        return None
    num = total(spans, epochs, args["numerator"])
    if args["denominator"] == "events":
        dens = [sum(epochs.values())]
    else:
        dens = [total(spans, epochs, d) for d in args["denominator"]]
    if num is None or any(d is None for d in dens) or sum(dens) <= 0:
        return None
    return args.get("scale", 1) * num / sum(dens)
