"""The feed loop, its schedule and the probing reader.

One loop serves every traffic mix: events fall due on a schedule that
does not slow when the system does (or are all due at once, a
backlog), are pushed a chunk at a time as they fall due, and a blocking
barrier is injected every ``interval`` seconds, as ``serve``'s clock
does (pump, then barrier). The loop knows the system only as an object
with ``push(stream, columns, rows)`` and ``barrier()``; the clock and
the sleep are arguments so that the tests can drive it on a made clock.
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field

import numpy as np


# after every barrier the loop steps aside this long, so that a reader
# waiting for the system's lock gets it before the next epoch's writes
READER_YIELD_S = 0.002


def due_times(ordinals: np.ndarray, rate: float, phases) -> np.ndarray:
    """Seconds after the window's start at which each ordinal (counted
    from the window's first) falls due. ``phases`` is a cycle of
    {"seconds", "rate_factor"}: in each the whole event stream (all
    three NEXmark streams) arrives at ``rate * rate_factor`` per second;
    a factor of 0 is a pause."""
    secs = np.array([p["seconds"] for p in phases], dtype=np.float64)
    rates = np.array([rate * p["rate_factor"] for p in phases], np.float64)
    if (secs <= 0).any() or (rates < 0).any() or not (rates > 0).any():
        raise ValueError("phases need seconds > 0 and some rate_factor > 0")
    per_phase = secs * rates
    t_start = np.concatenate([[0.0], np.cumsum(secs)[:-1]])
    n_start = np.concatenate([[0.0], np.cumsum(per_phase)[:-1]])
    live = rates > 0
    n_end_live = (n_start + per_phase)[live]
    cycle_n, cycle_s = per_phase.sum(), secs.sum()
    x = np.asarray(ordinals, dtype=np.float64)
    cycle, within = np.divmod(x, cycle_n)
    ph = np.minimum(
        np.searchsorted(n_end_live, within, side="right"), live.sum() - 1
    )
    return (
        cycle * cycle_s
        + t_start[live][ph]
        + (within - n_start[live][ph]) / rates[live][ph]
    )


class FeedPlan:
    """Every event of a run, in arrival order, as host arrays.

    ``events`` is {stream: {"eid": ordinals, column: values}} for the
    subscribed streams. Positions count subscribed events in ordinal
    order; ``due`` gives, for each, the second after the window's start
    at which it falls due (-inf for the preload)."""

    def __init__(self, events: dict, chunk_rows: int, due: np.ndarray):
        self.events = events
        self.chunk_rows = int(chunk_rows)
        self.order = np.sort(
            np.concatenate([cols["eid"] for cols in events.values()])
        )
        self.n = len(self.order)
        self.due = np.asarray(due, dtype=np.float64)
        live = self.due[np.isfinite(self.due)]
        if (
            len(self.due) != self.n
            or (np.diff(live) < 0).any()
            or np.isfinite(self.due[: self.n - len(live)]).any()
        ):
            raise ValueError("one non-decreasing due time per event")

    def cut(self, position: int) -> float:
        """The ordinal below which the first ``position`` events lie."""
        return float(self.order[position]) if position < self.n else np.inf

    def chunks(self, a: int, b: int):
        """Events [a, b) split by stream: [(stream, columns, rows)]."""
        lo, hi = self.cut(a), self.cut(b)
        out = []
        for stream, cols in self.events.items():
            i, j = np.searchsorted(cols["eid"], [lo, hi], side="left")
            if j > i:
                out.append(
                    (
                        stream,
                        {k: v[i:j] for k, v in cols.items() if k != "eid"},
                        int(j - i),
                    )
                )
        return out


@dataclass
class Epoch:
    position: int  # events included in this and every earlier epoch
    t_decide: float  # when the loop counted what was due and cut the epoch
    t_inject: float
    t_return: float
    due_position: int = 0  # events due by the newest boundary at t_decide
    ok: bool = True
    error: str = ""
    trace: object = None  # whatever the system's barrier() handed back


@dataclass
class FeedResult:
    epochs: list = field(default_factory=list)
    lags_s: list = field(default_factory=list)  # per chunk pushed
    pushed: int = 0
    chunks: int = 0


def _epoch(system, out, position, t_decide, clock, sleep, annotate, opened):
    """Inject one blocking barrier and book it."""
    if not opened:
        system.begin_epoch()
    ep = Epoch(position, t_decide, clock(), 0.0)
    try:
        with annotate("bench/barrier"):
            ep.trace = system.barrier()
    except Exception as e:  # noqa: BLE001 — a failed barrier ends the run
        ep.ok, ep.error = False, f"{type(e).__name__}: {e}"
    ep.t_return = clock()
    system.end_epoch()
    out.epochs.append(ep)
    sleep(READER_YIELD_S)
    return ep


def run_preload(
    system,
    plan: FeedPlan,
    *,
    epoch_sizes,
    clock,
    sleep,
    annotate=contextlib.nullcontext,
) -> FeedResult:
    """Push the plan's first ``sum(epoch_sizes)`` events, untimed, as
    epochs of exactly those sizes — the sizes the window's epochs will
    have, so that the window meets no shape the preload has not."""
    out = FeedResult()
    for size in epoch_sizes:
        end = out.pushed + int(size)
        if end > plan.n:
            raise ValueError("the preload is longer than the plan")
        system.begin_epoch()
        while out.pushed < end:
            b = min(out.pushed + plan.chunk_rows, end)
            with annotate("bench/feed"):
                for stream, cols, n in plan.chunks(out.pushed, b):
                    system.push(stream, cols, n)
            out.pushed, out.chunks = b, out.chunks + 1
        ep = _epoch(system, out, out.pushed, clock(), clock, sleep, annotate,
                    True)
        if not ep.ok:
            break
    return out


def run_feed(
    system,
    plan: FeedPlan,
    *,
    start: int,
    t0: float,
    seconds: float,
    interval_s: float,
    max_epoch_chunks: int,
    clock,
    sleep,
    annotate=contextlib.nullcontext,
) -> FeedResult:
    """The measured window: feed ``plan`` from position ``start``.

    Epochs are cut on the schedule's own grid: epoch k holds the events
    due in (t0 + (k-1) * interval, t0 + k * interval], so what an epoch
    holds does not depend on how the run's timing fell, and every seed
    meets the same sizes. Inside an interval a chunk is pushed as soon
    as ``chunk_rows`` events are due; when the boundary is reached the
    remainder due by then is pushed and a blocking barrier is injected.
    A loop that comes late to a boundary takes every boundary that has
    passed, as far as the admission limit allows: an epoch admits at
    most ``max_epoch_chunks`` chunks (what is due beyond them waits for
    the next epoch), and an epoch that is full is closed at once,
    boundary or not — so a backlog, all of it due at ``t0``, goes in
    epochs of exactly that many chunks, back to back. The loop starts
    no epoch once ``seconds`` have passed since ``t0`` and ends when
    the barrier in flight has returned; running out of events before
    that is an error.

    ``system.begin_epoch()`` is called before an epoch's first push and
    ``system.end_epoch()`` after its barrier has returned (a system
    whose readers may not overlap its writers holds them off between
    the two); the loop then yields for a moment so that waiting readers
    get in. ``annotate(name)`` wraps each phase (``bench/feed``,
    ``bench/barrier``, ``bench/wait_due``) for the profiler."""
    out = FeedResult(pushed=start)
    rows, stop = plan.chunk_rows, plan.n
    limit_rows = max_epoch_chunks * rows
    k_done = 0  # boundaries the epochs so far have covered
    epoch_start, in_epoch = start, 0

    def due_by(t: float) -> int:
        return min(int(np.searchsorted(plan.due, t, side="right")), stop)

    def push(b: int, now: float) -> None:
        nonlocal in_epoch
        if in_epoch == 0:
            system.begin_epoch()
        with annotate("bench/feed"):
            for stream, cols, n in plan.chunks(out.pushed, b):
                system.push(stream, cols, n)
        out.lags_s.append(now - (t0 + plan.due[b - 1]))
        out.pushed = b
        out.chunks += 1
        in_epoch += 1

    while True:
        now = clock()
        k_now = int((now - t0) // interval_s)
        full = out.pushed - epoch_start >= limit_rows
        if not full and k_now <= k_done:
            # inside interval k_done + 1: push what has fallen due
            if due_by(now - t0) - out.pushed >= rows:
                push(out.pushed + rows, now)
                continue
            wake = t0 + (k_done + 1) * interval_s
            nxt = out.pushed + rows - 1
            if nxt < stop:
                wake = min(wake, t0 + plan.due[nxt])
            with annotate("bench/wait_due"):
                sleep(max(wake - now, 1e-4))
            continue
        # close the epoch on the newest boundary the limit allows
        limit = min(epoch_start + limit_rows, stop)
        end = limit
        for k in range(k_now, k_done, -1):
            if due_by(k * interval_s) <= limit:
                end = due_by(k * interval_s)
                break
        end = max(end, out.pushed)
        while out.pushed < end:
            push(min(out.pushed + rows, end), now)
        ep = _epoch(system, out, out.pushed, now, clock, sleep, annotate,
                    in_epoch > 0)
        ep.due_position = due_by(k_now * interval_s)
        epoch_start, in_epoch = out.pushed, 0
        while k_done < k_now and due_by((k_done + 1) * interval_s) <= end:
            k_done += 1
        if not ep.ok or ep.t_return - t0 >= seconds:
            return out
        if out.pushed >= stop:
            raise RuntimeError(
                f"ran out of events after {out.pushed} with "
                f"{seconds - (ep.t_return - t0):.1f} s of the window left"
            )


class Reader(threading.Thread):
    """One client asking the probe query over and over: the next probe
    goes out ``interval_s`` after the previous one did, or as soon as
    its reply is in. Every reply is kept with its issue and reply
    times."""

    def __init__(self, client, sql: str, interval_s: float, clock,
                 annotate=contextlib.nullcontext):
        super().__init__(name="bench-reader", daemon=True)
        self.client, self.sql = client, sql
        self.interval_s, self.clock = interval_s, clock
        self.annotate = annotate
        self.replies = []  # (t_issue, t_reply, value tuple | None, error)
        self._halt = threading.Event()

    def run(self) -> None:
        while not self._halt.is_set():
            t_issue = self.clock()
            try:
                with self.annotate("bench/probe"):
                    rows = self.client.query(self.sql)
                value = tuple(int(x) if x is not None else 0 for x in rows[0])
                self.replies.append((t_issue, self.clock(), value, ""))
            except Exception as e:  # noqa: BLE001 — counted as failed
                self.replies.append(
                    (t_issue, self.clock(), None, f"{type(e).__name__}: {e}")
                )
                if isinstance(e, (ConnectionError, OSError)):
                    return
            self._halt.wait(max(t_issue + self.interval_s - self.clock(), 0))

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=60)
        if self.is_alive():
            raise RuntimeError("the reader did not stop")
