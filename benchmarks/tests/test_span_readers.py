"""The readers this configuration brought: per-epoch counts out of the
args of the program's spans, over the window's epochs only, and the
keyed join scan's share of the HBM roofline over the traced epochs."""

import importlib.util
import os
import sys
import time

from conftest import BENCH, ROOT

sys.path.insert(0, ROOT)
from risingwave_tpu.trace import TRACER, span  # noqa: E402


def _reader(name):
    path = os.path.join(BENCH, "readers", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _epoch(number, events, traced=False, **join):
    """One epoch as the program leaves it in the ring, and as the
    harness records it."""
    t_inject = time.monotonic()
    with span("barrier", epoch=number) as b:
        with span("actor.fence") as fence:
            fence.args.update(actor="q/a#0", insert_rows=10, retract_rows=4)
            if join:
                with span("join.epoch", join="j", **join):
                    pass
    b.traced = traced
    return {"events": events, "t_inject": t_inject,
            "t_return": time.monotonic(), "stages_ms": {}}


def test_counts_are_taken_over_the_windows_epochs_only():
    TRACER.clear()
    read = _reader("epoch_spans").read
    _epoch(1, 32768, pairs_kept=900, pairs_dropped=100)  # the preload's
    run = {"epochs": [
        _epoch(2, 100, pairs_kept=1, pairs_dropped=99),
        _epoch(3, 300, pairs_kept=3, pairs_dropped=97),
    ]}
    per_event = {"numerator": {"span": "actor.fence", "arg": "retract_rows"},
                 "denominator": "events"}
    assert read(run, per_event) == 8 / 400
    kept = {"numerator": {"span": "join.epoch", "arg": "pairs_kept"},
            "denominator": [{"span": "join.epoch", "arg": "pairs_kept"},
                            {"span": "join.epoch", "arg": "pairs_dropped"}],
            "scale": 100}
    assert read(run, kept) == 2.0
    # a program that writes no such span or arg, a window the ring no
    # longer holds, an empty denominator: no number
    assert read(run, dict(kept, numerator={"span": "no.such", "arg": "x"})) is None
    assert read(run, dict(per_event, numerator={
        "span": "actor.fence", "arg": "never_written"})) is None
    late = {"epochs": [{"events": 5, "t_inject": time.monotonic() + 60,
                        "t_return": time.monotonic() + 61}]}
    assert read(late, per_event) is None
    TRACER.clear()
    assert read(run, per_event) is None


def test_scan_roofline_share_is_over_the_traced_epochs():
    TRACER.clear()
    read = _reader("scan_roofline").read
    args = {"module": "^jit_keyed_join_scan$", "kernel": "keyed_join_scan.py",
            "span": "join.epoch", "call_lanes": "probe_lanes",
            "key_bytes": 8, "residual_bytes": 8}
    common = dict(pairs_kept=0, pairs_dropped=0)
    _epoch(1, 1, probe_lanes=64 * 2**21, **common)  # before the trace
    _epoch(2, 1, traced=True, probe_lanes=32 * 2**21, **common)  # its first
    _epoch(3, 1, traced=True, probe_lanes=2**21, **common)
    _epoch(4, 1, traced=True, probe_lanes=3 * 2**21, **common)
    _epoch(5, 1, probe_lanes=64 * 2**21, **common)  # cut by the trace's end
    run = {
        "peaks": {"hbm_bytes_per_s": 819e9},
        "device_trace": {
            "cycles": 2, "busy_s": 1.0, "window_s": 8.0,
            "modules_in_cycles_s": {"jit_keyed_join_scan": 0.002,
                                    "jit_flat_emit": 0.5},
        },
    }
    # epochs 3 and 4: 4 calls' worth of 2^21 lanes x 25 B in 2 ms
    want = 100 * (4 * 2**21 * 25) / 0.002 / 819e9
    assert abs(read(run, args) - want) < 1e-9 and 5 < want < 105
    assert read({"peaks": run["peaks"], "device_trace": None}, args) is None
    other = dict(run, device_trace=dict(
        run["device_trace"], modules_in_cycles_s={"jit_join_step_fn": 1.0}))
    assert read(other, args) is None  # a tree without the kernel
    # the ring's traced epochs have to be the trace's
    three = dict(run, device_trace=dict(run["device_trace"], cycles=3))
    assert read(three, args) is None
    assert read(run, dict(args, span="no.such")) is None
