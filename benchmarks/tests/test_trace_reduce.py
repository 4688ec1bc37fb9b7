"""The reduction from a profiler trace to numbers, on a small trace
recorded on the chip (data/trace_small.json.gz) and on a hand-made one."""

import gzip
import json
import os

import pytest

import trace_reduce
from conftest import HERE
from readers import device_trace


def recorded():
    with gzip.open(os.path.join(HERE, "data", "trace_small.json.gz"), "rt") as f:
        doc = json.load(f)
    return [(a, b, c, d, e, c) for a, b, c, d, e in doc["records"]]


def test_recorded_trace_reduces_to_the_numbers_it_was_kept_with():
    s = trace_reduce.reduce(recorded())
    assert s["devices"] == 1 and s["cycles"] == 1
    assert s["busy_s"] == pytest.approx(0.553512305, rel=1e-9)
    assert s["modules_in_cycles_s"]["jit__fused_barrier_fn"] == pytest.approx(
        0.522981456, rel=1e-9)
    assert s["modules"]["jit__fused_barrier_fn"] == pytest.approx(0.522981456)
    top = s["breakdown"]["device_ops"][0]
    assert top[0] == "jit__fused_barrier_fn/while.54"
    assert top[1] == pytest.approx(0.229492922)
    assert len(s["breakdown"]["device_ops"]) == 10
    # most of the device's idle time falls inside the host's barrier
    assert s["breakdown"]["idle_gaps"][0][0] == "bench/barrier"
    assert s["breakdown"]["idle_gaps"][0][1] == pytest.approx(1.285334472)
    # busy time can never pass the span the records cover
    span = max(r[3] + r[4] for r in recorded()) - min(r[3] for r in recorded())
    assert s["busy_s"] * 1e9 < span


D, H = "/device:TPU:0", "/host:CPU"


def handmade():
    ops = [  # (start, dur) ns: two overlap, then a gap of 40, then one
        (100, 50), (120, 50), (210, 30),
    ]
    rec = [(D, "XLA Ops", f"%fusion.{i} = f32[8] fusion(...)", s, d, "")
           for i, (s, d) in enumerate(ops)]
    rec += [
        (D, "XLA Modules", "jit__fused_barrier_fn(123)", 90, 90, ""),
        (D, "XLA Modules", "jit_scatter(9)", 205, 40, ""),
        (D, "Async XLA Ops", "%copy-start.1 = ...", 0, 1000, ""),
        (H, "python3", "bench/barrier", 50, 100, ""),   # ends 150
        (H, "python3", "bench/feed", 150, 50, ""),      # 150..200
        (H, "python3", "bench/barrier", 200, 60, ""),   # ends 260
        (H, "reader", "bench/probe", 0, 1000, ""),
        (H, "python3", "not ours", 0, 1000, ""),
    ]
    return rec


def test_busy_is_the_union_and_gaps_go_to_the_host_phase():
    s = trace_reduce.reduce(handmade())
    # ops only (the async line's long copy is not an operation running):
    # [100,170) U [210,240) = 100 ns
    assert s["busy_s"] == pytest.approx(100e-9)
    # the gap [170,210) has its middle (190) in bench/feed
    assert s["breakdown"]["idle_gaps"] == [["bench/feed", pytest.approx(40e-9)]]
    # one complete epoch, barrier end (150) to barrier end (260); the
    # fused module's run started at 90, before it, so none of it counts;
    # the scatter's (205) does
    assert s["cycles"] == 1
    assert s["modules_in_cycles_s"] == {"jit_scatter": pytest.approx(40e-9)}
    names = dict(s["breakdown"]["device_ops"])
    assert names["jit__fused_barrier_fn/fusion.0"] == pytest.approx(50e-9)
    assert names["jit_scatter/fusion.2"] == pytest.approx(30e-9)


def test_no_device_plane_reduces_to_nothing():
    host_only = [r for r in handmade() if r[0] == H]
    assert trace_reduce.reduce(host_only) is None


def test_readers_leave_out_what_the_trace_does_not_hold():
    s = trace_reduce.reduce(recorded())
    s["window_s"] = 2.3
    run = {"device_trace": s}
    idle = device_trace.read(run, {"quantity": "idle_share"})
    assert idle == pytest.approx(100 * (1 - 0.553512305 / 2.3))
    per = {"quantity": "module_ms_per_barrier"}
    ms = device_trace.read(run, dict(per, module="^jit__fused_barrier_fn$"))
    assert ms == pytest.approx(522.981456)
    # a module the trace does not hold: nothing, not 0
    assert device_trace.read(run, dict(per, module="^jit_join_step_fn$")) is None
    empty = {"device_trace": None}
    assert device_trace.read(empty, {"quantity": "idle_share"}) is None


def test_loop_clock_reads_a_series_and_nothing_where_it_is_not_finite():
    from readers import loop_clock

    run = {"fresh_ms": [float(i) for i in range(100)]}
    p95 = {"series": "fresh_ms", "stat": "p95"}
    assert loop_clock.read(run, p95) == 95.0
    assert loop_clock.read(run, dict(p95, stat="median")) == 49.5
    # six events in a hundred that no probe showed: no 95th percentile
    run["fresh_ms"][-6:] = [float("inf")] * 6
    assert loop_clock.read(run, p95) is None
    assert loop_clock.read({}, p95) is None
