"""The feed loop on a made clock: the schedule, what goes into which
epoch, and when the window ends."""

import numpy as np
import pytest

import feed


class Clock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.t += max(s, 1e-6)


class FakeSystem:
    """Every push takes ``push_s`` and every barrier ``barrier_s``."""

    def __init__(self, clock, push_s=0.01, barrier_s=0.2, fail_at=None):
        self.clock, self.push_s, self.barrier_s = clock, push_s, barrier_s
        self.pushes, self.calls, self.fail_at = [], [], fail_at
        self.barriers = 0

    def begin_epoch(self):
        self.calls.append("begin")

    def end_epoch(self):
        self.calls.append("end")

    def push(self, stream, cols, rows):
        self.calls.append("push")
        self.pushes.append((stream, rows, cols["x"].tolist()))
        self.clock.t += self.push_s

    def barrier(self):
        self.calls.append("barrier")
        self.barriers += 1
        self.clock.t += self.barrier_s
        if self.fail_at == self.barriers:
            raise TimeoutError("barrier not collected")
        return {"n": self.barriers}


def plan_of(n, rate, chunk_rows, n_pre=0, phases=None):
    eid = np.arange(n, dtype=np.int64)
    events = {
        "a": {"eid": eid[eid % 2 == 0], "x": eid[eid % 2 == 0] * 10},
        "b": {"eid": eid[eid % 2 == 1], "x": eid[eid % 2 == 1] * 10},
    }
    due = np.full(n, -np.inf)
    phases = phases or [{"seconds": 1.0, "rate_factor": 1.0}]
    due[n_pre:] = feed.due_times(eid[n_pre:] - n_pre, rate, phases)
    return feed.FeedPlan(events, chunk_rows, due)


def test_due_times_steady_and_burst():
    steady = feed.due_times(np.arange(5), 10, [{"seconds": 1, "rate_factor": 1}])
    assert np.allclose(steady, [0, 0.1, 0.2, 0.3, 0.4])
    # 5 s at twice the rate, 5 s of nothing: the mean is the rate
    burst = [{"seconds": 5, "rate_factor": 2.0}, {"seconds": 5, "rate_factor": 0.0}]
    d = feed.due_times(np.array([0, 99, 100, 199, 200]), 10, burst)
    assert np.allclose(d, [0, 4.95, 10.0, 14.95, 20.0])
    with pytest.raises(ValueError):
        feed.due_times(np.arange(3), 10, [{"seconds": 1, "rate_factor": 0}])


def test_plan_chunks_split_by_stream_in_order():
    plan = plan_of(20, 10, 4)
    got = plan.chunks(3, 8)  # events 3,4,5,6,7
    assert [g[2] for g in got] == [2, 3]
    by = {s: c["x"].tolist() for s, c, _ in got}
    assert by == {"a": [40, 60], "b": [30, 50, 70]}
    assert plan.cut(0) == 0 and plan.cut(20) == np.inf
    assert "eid" not in got[0][1]


def test_window_epochs_are_cut_on_the_schedules_grid():
    clock = Clock()
    sysm = FakeSystem(clock)
    plan = plan_of(2000, 100, 30)  # 100 events/s, 30-row chunks
    t0 = clock()
    out = feed.run_feed(
        sysm, plan, start=0, t0=t0, seconds=5.0, interval_s=1.0,
        max_epoch_chunks=8, clock=clock, sleep=clock.sleep,
    )
    # epoch k holds the events due in (k - 1, k] seconds: 100 each,
    # whatever the pushes and barriers took
    assert [e.position for e in out.epochs] == [101, 201, 301, 401, 501]
    assert [e.due_position for e in out.epochs] == [101, 201, 301, 401, 501]
    # the barrier follows the boundary by the remainder's push (2 x 10 ms)
    assert [round(e.t_inject - t0, 2) for e in out.epochs[:3]] == [1.02, 2.02, 3.02]
    # the window ends with the barrier in flight at 5 s
    assert out.epochs[-1].t_return - t0 >= 5.0
    assert out.epochs[-2].t_return - t0 < 5.0
    # every event pushed once and in order; three full chunks as they
    # fell due and the remainder at the boundary
    flat = sorted(x for _, _, xs in sysm.pushes for x in xs)
    assert flat == [i * 10 for i in range(out.pushed)]
    assert out.chunks == 5 * 4
    # a chunk is never pushed before its last event is due
    assert min(out.lags_s) >= 0
    # begin before the first push of an epoch, end after its barrier
    calls = [c for c in sysm.calls if c != "push"]
    assert calls[:3] == ["begin", "barrier", "end"]
    assert calls.count("begin") == calls.count("end") == len(out.epochs)


def test_a_late_loop_takes_the_boundaries_it_missed_up_to_the_limit():
    clock = Clock()
    sysm = FakeSystem(clock, push_s=0.0, barrier_s=2.5)
    plan = plan_of(4000, 100, 50)
    t0 = clock()
    out = feed.run_feed(
        sysm, plan, start=0, t0=t0, seconds=12.0, interval_s=1.0,
        max_epoch_chunks=4, clock=clock, sleep=clock.sleep,
    )
    # first epoch on time (1 s of events); its barrier returns at 3.5 s,
    # three boundaries late: two of them fit under 4 chunks of 50 (200
    # events), the third waits; and so on, always on the grid
    assert [e.position for e in out.epochs[:4]] == [101, 301, 501, 701]
    assert out.epochs[1].due_position == 301  # boundaries 2 and 3 were due
    assert out.epochs[2].due_position == 601  # the loop is falling behind
    assert all((e.position - 1) % 100 == 0 for e in out.epochs)


def test_backlog_goes_in_full_epochs_back_to_back():
    clock = Clock()
    sysm = FakeSystem(clock, push_s=0.01, barrier_s=0.1)
    plan = plan_of(100000, 100, 50)
    plan.due[:] = 0.0
    t0 = clock()
    out = feed.run_feed(
        sysm, plan, start=0, t0=t0, seconds=4.0, interval_s=1.0,
        max_epoch_chunks=4, clock=clock, sleep=clock.sleep,
    )
    # four chunks, a barrier, four chunks, ...: no waiting for the interval
    per_epoch = np.diff([0] + [e.position for e in out.epochs])
    assert set(per_epoch.tolist()) == {200}
    gaps = np.diff([e.t_inject for e in out.epochs])
    assert np.allclose(gaps, 0.1 + 0.002 + 8 * 0.01, atol=0.005)
    assert out.epochs[-1].t_return - t0 >= 4.0


def test_window_that_runs_out_of_events_is_an_error():
    clock = Clock()
    plan = plan_of(300, 100, 30)
    with pytest.raises(RuntimeError, match="ran out of events"):
        feed.run_feed(
            FakeSystem(clock), plan, start=0, t0=clock(), seconds=10.0,
            interval_s=1.0, max_epoch_chunks=8, clock=clock,
            sleep=clock.sleep,
        )


def test_preload_pushes_epochs_of_exactly_the_sizes_asked():
    clock = Clock()
    sysm = FakeSystem(clock)
    plan = plan_of(1000, 100, 64, n_pre=400)
    out = feed.run_preload(
        sysm, plan, epoch_sizes=[200, 100, 100], clock=clock,
        sleep=clock.sleep,
    )
    assert [e.position for e in out.epochs] == [200, 300, 400]
    assert out.pushed == 400
    # 200 events are three full chunks of 64 and one of 8, by stream
    first = [rows for _, rows, _ in sysm.pushes[:8]]
    assert first == [32, 32] * 3 + [4, 4]
    calls = [c for c in sysm.calls if c != "push"]
    assert calls == ["begin", "barrier", "end"] * 3


def test_failed_barrier_ends_the_feed_and_is_kept():
    clock = Clock()
    plan = plan_of(2000, 100, 30)
    out = feed.run_feed(
        FakeSystem(clock, fail_at=2), plan, start=0, t0=clock(), seconds=5.0,
        interval_s=1.0, max_epoch_chunks=8, clock=clock, sleep=clock.sleep,
    )
    assert [e.ok for e in out.epochs] == [True, False]
    assert "TimeoutError" in out.epochs[-1].error


@pytest.mark.parametrize(
    "cell,want",
    [
        # on a schedule: the power-of-two chunk counts down to the
        # nominal epoch's, one epoch of twice and one of half the nominal
        # size, then nominal epochs
        ("nexmark_q8.steady", [32768, 16384, 4400, 1100, 2200, 2200]),
        # a backlog has neither late nor thin epochs: full ones only
        ("nexmark_q8.catchup", [32768, 32768]),
    ],
)
def test_preload_epochs_are_the_sizes_the_window_can_have(cell, want):
    import run

    _, _, config, mix = run.load_cell(cell)
    events, due, sizes, offered, expected = run.generate(
        config, mix, 7, 10.0, False, config["chunk_rows"]
    )
    assert sizes == want
    assert np.isinf(due[: sum(sizes)]).all()
    assert np.isfinite(due[sum(sizes):]).all()
