"""The four counts the Top-N's rank over an epoch's candidates brought
(``topn.full_rank_share.*``, ``topn.ranked_lanes_per_event.*``): each
metric's own file read, by the reader it names, off a recorded ring of
``topn.pull`` spans; and off the ring of a tree that writes no such
args, which gives no number and does not raise."""

import importlib.util
import json
import os
import sys
import time

import pytest
from conftest import BENCH, ROOT

sys.path.insert(0, ROOT)
from risingwave_tpu.trace import TRACER, span  # noqa: E402

METRICS = {
    "topn.full_rank_share.catchup": 100.0 * 1 / 3,
    "topn.full_rank_share.steady": 100.0 * 1 / 3,
    "topn.ranked_lanes_per_event.catchup": (2 * 65536 + 2**22 + 65536) / 700,
    "topn.ranked_lanes_per_event.steady": (2 * 65536 + 2**22 + 65536) / 700,
}


def _read(name, run):
    with open(os.path.join(BENCH, "layer_metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["name"] == name
    path = os.path.join(BENCH, "readers", spec["reader"] + ".py")
    mod_spec = importlib.util.spec_from_file_location(spec["reader"], path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read(run, spec["args"])


def _epoch(number, events, **pull):
    """One epoch as the program leaves it in the ring, and as the
    harness records it."""
    t_inject = time.monotonic()
    with span("barrier", epoch=number):
        with span("topn.pull", table_id="t", passes=pull.pop("passes", 0)) as sp:
            sp.args.update(pull)
    return {"events": events, "t_inject": t_inject,
            "t_return": time.monotonic(), "stages_ms": {}}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_the_count_is_read_off_the_windows_pulls(name):
    TRACER.clear()
    answered = dict(ranked_lanes=65536, full_rank=0, rank_calls=1)
    # the preload's barrier ranked the store: not the window's
    _epoch(1, 32768, passes=3, ranked_lanes=2**22, full_rank=1, rank_calls=1)
    run = {"epochs": [
        _epoch(2, 300, **answered),
        _epoch(3, 300, passes=3, ranked_lanes=65536 + 2**22, full_rank=1,
               rank_calls=1),
        _epoch(4, 100, **answered),
    ]}
    assert _read(name, run) == pytest.approx(METRICS[name])
    # every barrier answered by its candidates: a share of 0.0, read
    TRACER.clear()
    run = {"epochs": [_epoch(2, 300, **answered), _epoch(3, 100, **answered)]}
    want = 0.0 if "share" in name else 2 * 65536 / 400
    assert _read(name, run) == want


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_tree_from_before_the_args_gives_no_number(name):
    TRACER.clear()
    run = {"epochs": [_epoch(2, 300, passes=3), _epoch(3, 100, passes=4)]}
    assert _read(name, run) is None
    TRACER.clear()
    assert _read(name, run) is None


def test_the_benchmark_lists_them_for_the_topn_cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    cells = {
        "catchup": ["nexmark_q18.catchup", "nexmark_q9.catchup",
                    "nexmark_q19.catchup"],
        "steady": ["nexmark_q18.steady"],
    }
    for name in METRICS:
        m = by_name[name]
        kind = name.rsplit(".", 1)[1]
        assert m["workloads"] == cells[kind] and m["better"] == "lower"
        assert m["moves"] == (
            "events_per_s" if kind == "catchup" else "fresh_p50_ms"
        )
        assert m["source"] == "program_counter"
