"""The reader nexmark_q7 brought: a count out of a span's args, per
barrier of the window (what the chained join's updating side took back
and left dead an epoch)."""

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


def _epoch(number, events, **join):
    """One epoch as the program leaves it in the ring, and as the
    harness records it (a few ms around the barrier's span: the reader
    lays the ring's clock over the harness's by one reading of both)."""
    t_inject = time.monotonic()
    time.sleep(0.005)
    with span("barrier", epoch=number):
        if join:
            with span("join.epoch", join="j", **join):
                pass
    time.sleep(0.005)
    return {"events": events, "t_inject": t_inject,
            "t_return": time.monotonic(), "stages_ms": {}}


def test_a_count_per_barrier_is_over_the_windows_epochs_that_carry_it():
    TRACER.clear()
    read = _reader("epoch_span_mean").read
    _epoch(1, 32768, retract_pairs=40, dead_lanes=9)  # the preload's
    run = {"epochs": [
        _epoch(2, 100, retract_pairs=1, dead_lanes=1),
        _epoch(3, 100, retract_pairs=0, dead_lanes=0),
        _epoch(4, 100, retract_pairs=5, dead_lanes=2),
        _epoch(5, 100),  # a barrier whose join wrote no span
    ]}
    assert read(run, {"span": "join.epoch", "arg": "retract_pairs"}) == 2.0
    assert read(run, {"span": "join.epoch", "arg": "dead_lanes"}) == 1.0
    # a program that writes no such span or arg (a tree from before
    # them), a window the ring no longer holds: no number, no raise
    assert read(run, {"span": "no.such", "arg": "x"}) is None
    assert read(run, {"span": "join.epoch", "arg": "never_written"}) is None
    late = {"epochs": [{"events": 5, "t_inject": time.monotonic() + 60,
                        "t_return": time.monotonic() + 61}]}
    assert read(late, {"span": "join.epoch", "arg": "dead_lanes"}) is None
    TRACER.clear()
    assert read(run, {"span": "join.epoch", "arg": "dead_lanes"}) is None


def test_the_new_metric_files_name_what_the_program_writes():
    import json

    def args(metric):
        with open(os.path.join(BENCH, "layer_metrics", metric + ".json")) as f:
            return json.load(f)

    for metric, arg in (
        ("join.retract_pairs_per_barrier.catchup", "retract_pairs"),
        ("join.dead_lanes_per_barrier.catchup", "dead_lanes"),
    ):
        spec = args(metric)
        assert spec["reader"] == "epoch_span_mean"
        assert spec["args"] == {"span": "join.epoch", "arg": arg}
    spec = args("agg.group_rows_max_share.catchup")
    assert spec["reader"] == "epoch_spans"
    assert spec["args"]["numerator"] == {
        "span": "agg.flush", "arg": "group_rows_max"
    }
    assert spec["args"]["denominator"] == "events"
    assert spec["args"]["scale"] == 100
