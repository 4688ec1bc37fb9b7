"""The reader of a barrier's critical path: the program's own reduction
of its span ring (risingwave_tpu/trace.py::barrier_path), median over
the window's epochs; nothing on a tree that has no such reduction."""

import sys
import time

from conftest import ROOT

sys.path.insert(0, ROOT)
from risingwave_tpu import trace  # noqa: E402
from risingwave_tpu.trace import TRACER, device_read, span  # noqa: E402

import run as harness  # noqa: E402

reader = harness.load_module("readers", "barrier_path.py")
HOST, WAIT = {"kind": "host"}, {"kind": "device_wait"}


def _epoch(number, work_s, wait_s):
    """One barrier as the program leaves it in the ring (its thread at
    work, then blocked on a read), and as the harness records it."""
    t_inject = time.monotonic()
    with span("barrier", epoch=number):
        with span("barrier.fragment", stage="dispatch"):
            time.sleep(work_s)
            with device_read("unit.read", lanes=2):
                time.sleep(wait_s)
    return {"events": 100, "t_inject": t_inject,
            "t_return": time.monotonic(), "stages_ms": {}}


def test_median_over_the_windows_epochs_of_one_kind():
    TRACER.clear()
    _epoch(1, 0.0, 0.2)  # the preload's: not in the window
    run = {"epochs": [
        _epoch(2, 0.010, 0.020),
        _epoch(3, 0.010, 0.040),
        _epoch(4, 0.030, 0.060),
    ]}
    wait, host = reader.read(run, WAIT), reader.read(run, HOST)
    assert 40.0 <= wait < 60.0  # epoch 3's, not the preload's 200
    assert 10.0 <= host < 30.0
    # the kinds of one epoch sum to its barrier's wall
    for kind in ("io", "permit", "queue", "unattributed"):
        assert reader.read(run, {"kind": kind}) == 0.0
    path = trace.barrier_path(3)
    assert abs(sum(path["by_kind"].values()) - path["wall_ms"]) < 1e-6
    assert [row[:2] for row in path["by_span"][:2]] == [
        ("device.read[unit.read]", "device_wait"), ("barrier.fragment", "host"),
    ]


def test_nothing_without_the_reduction_the_ring_or_the_window(monkeypatch):
    TRACER.clear()
    run = {"epochs": [_epoch(2, 0.001, 0.001), _epoch(3, 0.001, 0.001)]}
    assert reader.read(run, HOST) is not None
    # a window whose barriers the ring never saw
    late = {"epochs": [{"events": 5, "t_inject": time.monotonic() + 60,
                        "t_return": time.monotonic() + 61}]}
    assert reader.read(late, HOST) is None
    # a tree from before barrier_path
    with monkeypatch.context() as m:
        m.delattr(trace, "barrier_path")
        assert reader.read(run, HOST) is None
    assert reader.read(run, HOST) is not None
    # an epoch of the window whose barrier has no path in the ring
    with monkeypatch.context() as m:
        m.setattr(trace, "barrier_path",
                  lambda epoch, spans=None: None if epoch == 3 else
                  {"by_kind": {"host": 1.0}})
        assert reader.read(run, HOST) is None
    # a program that keeps no ring
    TRACER.clear()
    assert reader.read(run, HOST) is None
