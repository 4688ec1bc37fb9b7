"""Tracing — the one span call the program times host work with.

Reference: the reference threads `tracing` spans through every actor/
executor and exports via opentelemetry (src/utils/runtime/src/, await
tree dumps). Here ``span(name, *, stage=None, wait=None, **args)`` is that call:

- every span records name, start, duration, thread, its **parent** (the
  span open on the same thread when it began) and the **epoch** it
  belongs to, into a bounded ring (chrome://tracing / Perfetto export);
- a span whose epoch is not known when it closes (a push or an actor's
  chunk, which belong to the epoch whose barrier closes them) waits on
  its thread until that thread calls ``close_epoch(epoch)``;
- with ``stage=`` its duration is reported to the thread's bound stage
  sink (``bind(sink)``: the epoch's EpochTrace on the barrier's thread
  and the checkpoint worker, an accumulator on a pushing or an actor
  thread), which feeds ``EpochTrace.stages_ms`` and the
  ``barrier_stage_ms{stage,fragment}`` histogram — one stamp per stage;
- while a profiler session runs (``jax.profiler.start_trace``), every
  span is also a ``TraceAnnotation("rw/<name>", epoch=..., ...)``, so
  the program's spans lie in the same ``.xplane.pb`` as ``XLA Ops``, on
  its clock, each on its own thread. With no session an annotation is a
  flag test: "tracing off" means no session, the ring and the stage
  stamps are always on; ``Span.traced`` says whether the session held
  the span from start to end (whether the xplane has its event), so a
  reader of the ring can tell the epochs a device trace covers. The
  ``barrier`` root's flag is held to the whole of ``Runtime.barrier()``
  (``whole_call``): a caller that wraps the call in an annotation of
  its own counts the same barriers as the ring does, also where the
  session began or ended between the call's first line and the span;
- with ``wait=`` a span says that its thread did not work but waited,
  and for what (``WAITS``): ``device`` (a blocking device->host read or
  ``block_until_ready``), ``actor`` (other threads of the graph),
  ``permit``, ``queue`` (an empty input, the checkpoint lane), ``io`` (a
  put or an fsync on the barrier's thread). It is kept on the ``Span``
  and is an argument of the xplane's event. ``device_read(what, ...)``
  is the one span around a blocking read, ``device.read``: it reports
  to the stage key ``<root>.device_wait`` of whatever encloses it;
- ``barrier_path(epoch)`` reduces the ring's spans of one epoch to the
  barrier's critical path: host time, device wait, I/O, permits and
  queues along the slowest actor (no cost unless called);
- jax's ``/jax/core/compile/*`` events become ``compile`` spans (stage
  ``compile``), children of whatever span was open on the compiling
  thread, and a finished backend compile under an open span becomes one
  ``compile`` entry of the event log.

The live span stack is per thread (``active_spans`` reads every
thread's own list), so spans on different actors share no lock.

Perfetto niceties (dispatch-wall profiler):
- stable per-thread tids (a small registry id, never ``tid % 1e6``
  which can collide across threads) plus ``ph:"M"`` thread_name
  metadata, so the flame view shows actor names;
- per-fragment pid lanes: spans carrying a ``fragment`` arg render in
  that fragment's own process track (named via process_name metadata);
- epoch flow events: spans of one epoch are linked with ``ph:"s"/"t"``
  flow arrows, so one barrier is traceable across every actor thread it
  crossed.
"""

from __future__ import annotations

import itertools
import json
import os
import re
import threading
import time
import weakref
from collections import deque
from contextlib import contextmanager

import jax
from jax.profiler import TraceAnnotation

from risingwave_tpu.epoch_trace import record_stage
from risingwave_tpu.event_log import EVENT_LOG

_MAX_EVENTS = 65_536
_MAX_PENDING = 8_192  # spans of one thread waiting for their epoch

# what a span may say it waits for (``wait=``) -> the kind its time is
# booked to on a barrier's path. An ``actor`` wait is never booked: over
# its interval the path goes on along the actor that released it.
WAITS = {
    "device": "device_wait",  # a blocking device->host read, a fence
    "actor": None,  # other threads of the graph
    "permit": "permit",  # a full channel downstream
    "queue": "queue",  # an empty input, the checkpoint lane
    "io": "io",  # a put or an fsync
}
KINDS = ("host", "device_wait", "io", "permit", "queue", "unattributed")

_SIDS = itertools.count(1)  # span ids; next() is atomic under the GIL
profiling = _profiling = TraceAnnotation.is_enabled  # is a session running

# per-thread state: the live span stack (the await-tree analogue: the
# reference dumps every actor's pending await tree on stall; here every
# thread's currently-open spans are snapshotable via active_spans()),
# the thread's stable small tid, the spans waiting for their epoch, and
# what ``bind`` set: the stage sink and the epoch
_TLS = threading.local()

# stable small tids: python thread idents are reused after thread death
# and collide under ``% 1_000_000`` — each thread gets its own monotonic
# id the first time it opens a span. Names live in a SEPARATE
# {small_tid: name} map that is append-only: the dead thread's tid keeps
# its name (post-recovery traces still label the pre-fault actor's lane
# correctly). Taken once per thread, never per span.
_THREADS_LOCK = threading.Lock()
# small_tid -> (weakref to the thread, its name, its live stack): an
# actor thread holds its executors, and a ring must not keep them alive
_THREADS: dict = {}
_TID_NAMES: dict = {}  # small_tid -> thread_name (never overwritten)
_NEXT_TID = [1]


class _ThreadState:
    __slots__ = ("tid", "stack", "pending", "sink", "epoch", "closed")

    def __init__(self):
        me = threading.current_thread()
        self.stack: list = []
        self.pending: deque = deque(maxlen=_MAX_PENDING)
        self.sink = None
        self.epoch = None
        self.closed = None  # the newest epoch close_epoch was told of
        with _THREADS_LOCK:
            self.tid = _NEXT_TID[0]
            _NEXT_TID[0] += 1
            _TID_NAMES[self.tid] = me.name
            for tid in [
                t for t, (th, _n, st) in _THREADS.items()
                if not st and (th() is None or not th().is_alive())
            ]:
                del _THREADS[tid]
            _THREADS[self.tid] = (weakref.ref(me), me.name, self.stack)


def _state() -> _ThreadState:
    st = getattr(_TLS, "st", None)
    if st is None:
        st = _TLS.st = _ThreadState()
    return st


def _thread_names() -> dict:
    with _THREADS_LOCK:
        return dict(_TID_NAMES)


def active_spans() -> dict:
    """Snapshot every thread's currently-open span stack — what each
    actor/worker is doing RIGHT NOW (outermost first), with elapsed
    seconds. The stall-dump surface (reference: await-tree dumps)."""
    now = time.perf_counter()
    with _THREADS_LOCK:
        threads = [(t, n, list(st)) for t, (_th, n, st) in _THREADS.items()]
    out = {}
    for tid, tname, stack in threads:
        if stack:
            out[f"{tname}({tid})"] = [
                {
                    "span": sp.name,
                    "elapsed_s": round(now - sp.t0, 4),
                    **({"wait": sp.wait} if sp.wait else {}),
                    **({"args": dict(sp.args)} if sp.args else {}),
                }
                for sp in stack
            ]
    return out


def add_stage(stage: str, ms: float, fragment: str = "-") -> None:
    """A stage duration -> the thread's bound sink, else straight into
    the ``barrier_stage_ms`` histogram (work outside any epoch). Spans
    with ``stage=`` report through it; so does a thread that hands on
    sums measured on another (a graph's actors, at their barrier)."""
    sink = _state().sink
    if sink is not None:
        sink.add_stage(stage, ms, fragment)
    else:
        record_stage(stage, ms, fragment)



class Span:
    """One span: the ring's record and, while open, the live frame.
    ``args`` may be added to while it is open (``sp.args["rows"] = n``)."""

    __slots__ = (
        "tracer", "name", "stage", "wait", "args", "tid", "sid", "parent",
        "epoch", "t0", "dur", "_ann", "traced",
    )

    def __init__(self, tracer, name, stage, args, wait=None):
        self.tracer = tracer
        self.name = name
        self.stage = stage
        if wait is not None and wait not in WAITS:
            raise ValueError(f"span {name!r}: unknown wait {wait!r}")
        self.wait = wait  # what the thread waits for (WAITS), or None
        self.args = args
        self.dur = None
        self._ann = None
        # whether a profiler session held this span from start to end,
        # i.e. whether the xplane has its ``rw/`` event
        self.traced = False

    def _adopt(self, st: _ThreadState):
        """Take thread, id, parent (the span open on this thread now)
        and epoch (given, else the parent's, else the thread's bound
        one); the parent is handed back."""
        up = st.stack[-1] if st.stack else None
        self.tid = st.tid
        self.sid = next(_SIDS)
        self.parent = up.sid if up is not None else None
        epoch = self.args.get("epoch")
        if epoch is None:
            epoch = up.epoch if up is not None else None
            if epoch is None:
                epoch = st.epoch
        self.epoch = epoch
        return up

    def __enter__(self):
        st = _state()
        stack = st.stack
        self._adopt(st)
        epoch = self.epoch
        if _profiling():
            kw = {
                k: v for k, v in self.args.items()
                if isinstance(v, (str, int, float))
            }
            if epoch is not None:
                kw["epoch"] = epoch
            elif st.closed is not None:
                # open epoch, number not known yet: the one after this
                kw["after"] = st.closed
            if self.wait is not None:
                kw["wait"] = self.wait
            self._ann = TraceAnnotation("rw/" + self.name, **kw)
            self._ann.__enter__()
        stack.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.dur = time.perf_counter() - self.t0
        st = _state()
        stack = st.stack
        if stack and stack[-1] is self:
            stack.pop()
        elif self in stack:
            stack.remove(self)
        if self._ann is not None:
            self.traced = _profiling()
            self._ann.__exit__(*exc)
            self._ann = None
        if self.stage is not None:
            add_stage(
                self.stage, self.dur * 1e3, self.args.get("fragment", "-")
            )
        self.tracer._record(self, st)
        return False

    def as_event(self):
        """(name, tid, t0, dur, args) as ``render_chrome_trace`` takes
        it, parent and epoch among the args."""
        args = dict(self.args)
        args["sid"] = self.sid
        if self.wait is not None:
            args["wait"] = self.wait
        if self.parent is not None:
            args["parent"] = self.parent
        if self.epoch is not None:
            args["epoch"] = self.epoch
        return (self.name, self.tid, self.t0, self.dur, args)


class _Off:
    """What ``span`` hands out while the tracer is disabled."""

    args: dict = {}
    epoch = None
    dur = 0.0

    def __enter__(self):
        self.args = {}
        return self

    def __exit__(self, *exc):
        return False


class Tracer:
    def __init__(self, max_events: int = _MAX_EVENTS):
        self._events: deque = deque(maxlen=max_events)
        self.enabled = True

    def span(self, name: str, *, stage=None, wait=None, **args):
        if not self.enabled:
            return _Off()
        return Span(self, name, stage, args, wait)

    def device_read(self, what: str, **args):
        """The span ``device.read`` (``wait="device"``): put exactly
        around a call that blocks this thread on the device (a device->
        host copy, a ``block_until_ready``) and around nothing else.
        ``what`` names the read; ``lanes`` or ``bytes`` where the site
        knows them. Its time goes to the stage key ``<root>.device_wait``,
        ``<root>`` being the first component of the stage of the
        outermost open span that has one (``ingest``, ``dispatch``,
        ``checkpoint_stage``; an actor's ``actor_fence`` reads
        ``actor``): the stage whose time the wait lies in, so a table
        fragment's copy under a push is the ingest's and not the view's.
        With no stage open it is the outermost span's name that is read
        (``actor.chunk``: ``actor``); a read under no span stamps none."""
        if not self.enabled:
            return _Off()
        stack = _state().stack
        within = next(
            (sp.stage for sp in stack if sp.stage is not None),
            stack[0].name if stack else None,
        )
        stage = None
        if within is not None:
            root = within.split(".", 1)[0]
            if root.startswith("actor_"):  # actor_fence, actor_busy, ...
                root = "actor"
            stage = root + ".device_wait"
        return Span(
            self, "device.read", stage, dict(args, what=what), "device"
        )

    def _record(self, sp: Span, st: _ThreadState) -> None:
        self._events.append(sp)  # deque.append is atomic
        if sp.epoch is None:
            st.pending.append(sp)

    def record(
        self, name: str, t0: float, dur: float, *, stage=None, wait=None,
        **args
    ):
        """A span that is only known once it is over (jax reports a
        compile when it has finished): child of the span open on this
        thread now, stamped and recorded like any other."""
        if not self.enabled:
            return None
        st = _state()
        sp = Span(self, name, stage, args, wait)
        up = sp._adopt(st)
        # jax times a compile on the wall clock: keep the span inside
        # the parent it fell in (the stage is stamped with jax's figure)
        ms = dur * 1e3
        if up is not None and t0 < up.t0:
            t0, dur = up.t0, max(t0 + dur - up.t0, 0.0)
        sp.t0, sp.dur = t0, dur
        if _profiling():
            # the profiler cannot be told of the past: a marker at the
            # end says what it was and how long it took
            with TraceAnnotation(
                "rw/" + name, ms=round(ms, 3),
                **({} if wait is None else {"wait": wait}),
                **{k: v for k, v in args.items() if isinstance(v, (str, int))},
            ):
                pass
        if stage is not None:
            add_stage(stage, ms, args.get("fragment", "-"))
        self._record(sp, st)
        return sp

    def spans(self) -> list:
        """The ring's closed spans, oldest first."""
        return list(self._events)

    def chrome_trace(self) -> str:
        """chrome://tracing / Perfetto 'traceEvents' JSON: named threads
        (ph:"M" thread_name), per-fragment pid lanes, and epoch flow
        events (ph:"s"/"t") linking one barrier across actor threads."""
        events = [sp.as_event() for sp in list(self._events)]
        return render_chrome_trace(events, _thread_names())

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.chrome_trace())

    def clear(self) -> None:
        self._events.clear()


@contextmanager
def bind(sink=None, epoch=None):
    """For the enclosed block, this thread's spans report their
    ``stage=`` durations to ``sink`` (anything with ``add_stage(stage,
    ms, fragment)``) and belong to ``epoch`` (default: the sink's own
    ``epoch``, where it has one)."""
    st = _state()
    old = (st.sink, st.epoch)
    st.sink = sink
    st.epoch = epoch if epoch is not None else getattr(sink, "epoch", None)
    try:
        yield sink
    finally:
        st.sink, st.epoch = old


def whole_call(root, began: bool) -> None:
    """Hold ``root.traced`` to the call that opened the span: ``began``
    is ``profiling()`` at the call's first statement, and this runs at
    its last. Between the two and the span lie a lock, a watchdog
    thread and the stage stamps, 1 + 3 ms of a barrier on the chip, and
    a session that starts or stops there is in the ring and not in what
    the caller wrapped around the call (or the reverse)."""
    if getattr(root, "traced", False):
        root.traced = began and _profiling()


def bound() -> bool:
    """Whether this thread's spans report to a stage sink (an epoch's
    owner is driving) or stand alone."""
    return _state().sink is not None


def close_epoch(epoch: int) -> None:
    """The barrier that closes this thread's open epoch has come: the
    spans that waited for it (pushes, an actor's chunks and waits)
    belong to ``epoch``."""
    st = _state()
    st.closed = epoch
    pending = st.pending
    while pending:
        sp = pending.popleft()
        sp.epoch = epoch
        if sp.name == "compile" and sp.args.get("event") == _COMPILED:
            _log_compile(sp)


def _enclosing_stage(st: _ThreadState):
    for sp in reversed(st.stack):
        if sp.stage is not None:
            return sp.stage
    return st.stack[-1].name if st.stack else None


def _log_compile(sp: Span) -> None:
    """/events names the program that compiled, once per executable, and
    only where the program was at work (a span open): a start's hundreds
    of compiles outside any epoch would flush the log."""
    if "within" not in sp.args:
        return
    EVENT_LOG.record(
        "compile",
        epoch=sp.epoch,
        stage=sp.args["within"],
        fun_name=sp.args["fun_name"],
        ms=round(sp.dur * 1e3, 3),
    )


# -- a barrier's critical path --------------------------------------------

# an ``actor`` wait ends when the last actor of its graph reaches a point
# of its ``actor.barrier``: these at its start (it has taken the barrier
# off its inputs), every other at its end (it has collected)
_RELEASED_AT_START = ("dispatch.drain",)
# an actor's spans that feed the actors downstream of it
_ACTOR_WORK = ("actor.chunk", "actor.barrier")
_NO_SPAN = "(no span)"


class _Path:
    """The walk of ``barrier_path`` over one epoch's spans."""

    def __init__(self, mine):
        self.by_kind = dict.fromkeys(KINDS, 0.0)
        self.by_span: dict = {}
        self.actors: list = []
        sids = {sp.sid for sp in mine}
        self.kids: dict = {}  # parent's sid -> its children, by start
        self.tops: dict = {}  # tid -> the thread's outermost spans
        self.barriers: dict = {}  # (graph, actor) -> its actor.barrier
        for sp in sorted(mine, key=lambda sp: sp.t0):
            if sp.parent is not None:
                self.kids.setdefault(sp.parent, []).append(sp)
            if sp.parent not in sids:
                self.tops.setdefault(sp.tid, []).append(sp)
            if sp.name == "actor.barrier":
                key = (sp.args.get("graph"), sp.args.get("actor"))
                self.barriers[key] = sp
        self.actor_of = {sp.tid: key for key, sp in self.barriers.items()}

    def book(self, name, kind, seconds) -> None:
        if seconds > 0.0:
            self.by_kind[kind] += seconds
            key = (name, kind)
            self.by_span[key] = self.by_span.get(key, 0.0) + seconds

    @staticmethod
    def end(sp, hi):
        # a span still open (the root, asked from inside) ends at ``hi``
        return hi if sp.dur is None else sp.t0 + sp.dur

    def span(self, sp, lo, hi) -> None:
        """``sp`` clipped to [lo, hi]: its children, and between them
        its own time."""
        a, b = max(sp.t0, lo), min(self.end(sp, hi), hi)
        cursor = a
        for ch in self.kids.get(sp.sid, ()):
            ca, cb = max(ch.t0, cursor), min(self.end(ch, b), b)
            if cb <= ca:
                continue
            self.own(sp, cursor, ca)
            self.span(ch, ca, cb)
            cursor = cb
        self.own(sp, cursor, b)

    def own(self, sp, a, b) -> None:
        """[a, b] of ``sp`` that no child covers."""
        if b <= a:
            return
        if sp.wait == "actor":
            self.await_actor(sp, a, b)
        elif sp.wait == "queue":
            self.await_upstream(sp, a, b)
        else:
            # a blocking read goes by what it read: device.read[<what>]
            what = sp.args.get("what") if sp.wait == "device" else None
            name = sp.name if what is None else f"{sp.name}[{what}]"
            self.book(name, WAITS.get(sp.wait) or "host", b - a)

    def thread(self, tid, lo, hi) -> None:
        """What thread ``tid`` did over [lo, hi]; what no span of it
        covers is unattributed."""
        cursor = lo
        for sp in self.tops.get(tid, ()):
            a, b = max(sp.t0, cursor), min(self.end(sp, hi), hi)
            if b <= a:
                continue
            self.book(_NO_SPAN, "unattributed", a - cursor)
            self.span(sp, a, b)
            cursor = b
        self.book(_NO_SPAN, "unattributed", hi - cursor)

    def cross(self, key) -> None:
        graph, actor = key
        label = f"{graph}/{actor}" if graph else actor
        if label not in self.actors:
            self.actors.append(label)

    def await_actor(self, sp, a, b) -> None:
        """The barrier's thread waited for its graph's actors over
        [a, b]: the path goes along the actor that released it last;
        from the release on it is the waiter's own waking up."""
        graph = sp.args.get("fragment")
        mine = [
            (key, x) for key, x in self.barriers.items() if key[0] == graph
        ]
        if not mine:
            self.book(sp.name, "unattributed", b - a)
            return
        if sp.name in _RELEASED_AT_START:
            key, last = max(mine, key=lambda kx: kx[1].t0)
            released = last.t0
        else:
            key, last = max(mine, key=lambda kx: self.end(kx[1], b))
            released = self.end(last, b)
        released = min(max(released, a), b)
        self.cross(key)
        self.thread(last.tid, a, released)
        self.book(sp.name, "host", b - released)

    def await_upstream(self, sp, a, b) -> None:
        """An actor sat on an empty input over [a, b]: the path goes
        along the actor upstream of it whose chunk or barrier was at
        work nearest to the wait's end (it sends from inside that span),
        and so on up to the sources, whose wait is the queue's; from
        that span's end on it is the message's way and the waking up."""
        me = self.actor_of.get(sp.tid)
        upstream = self.barriers[me].args.get("upstream", ()) if me else ()
        best = None  # (how near to b its work came, its key, its thread)
        for name in upstream:
            up = self.barriers.get((me[0], name))
            for x in self.tops.get(up.tid, ()) if up is not None else ():
                if x.name in _ACTOR_WORK and x.t0 < b:
                    reach = min(self.end(x, b), b)
                    if best is None or reach > best[0]:
                        best = (reach, (me[0], name), up.tid)
        if best is None or best[0] <= a:
            self.book(sp.name, "queue", b - a)
            return
        released, key, tid = best
        self.cross(key)
        self.thread(tid, a, released)
        self.book(sp.name, "queue", b - released)


def barrier_path(epoch: int, spans=None):
    """One epoch's barrier reduced to its critical path, from the ring
    (or from ``spans``); pure, and no cost unless called.

    It starts at the epoch's ``barrier`` root span on the barrier's
    thread. A span's own time (its duration less what its children
    cover) is booked to its kind: ``host`` when it has no ``wait``, else
    ``device_wait``, ``io``, ``permit``, ``queue``. A span with
    ``wait="actor"`` is replaced, over its own interval, by the spans of
    the actor thread that released it last (``dispatch.flush``: the actor
    whose ``actor.barrier`` of the epoch ended last; ``dispatch.drain``:
    the one whose began last, which is when it had taken the barrier),
    clipped to the interval and reduced the same way; a ``queue`` wait of
    that actor inside the interval is replaced in turn by the actor
    upstream of it, up to the sources, so a chain of actors reads as the
    work that was done and not as each one's wait for the one before.
    What no span covers is ``unattributed``.

    -> ``{"wall_ms", "by_kind": {kind: ms}, "by_span": [(span name,
    kind, ms), ... largest first; a blocking read as ``device.read[<what
    it read>]``], "actors": [the actors the path crossed]}``; ``by_kind``
    sums to ``wall_ms``. None when the ring does
    not hold the epoch's barrier whole (it wrapped, or there was none: a
    pipelined barrier has no root span)."""
    if spans is None:
        spans = TRACER.spans()
    mine = [sp for sp in spans if sp.epoch == epoch]
    root = next((sp for sp in mine if sp.name == "barrier"), None)
    if root is not None:
        end = root.t0 + root.dur
    else:
        # asked from inside the barrier: the root is still open here
        stack = _state().stack
        if not (stack and stack[0].name == "barrier"
                and stack[0].epoch == epoch):
            return None
        root, end = stack[0], time.perf_counter()
    if len(spans) >= TRACER._events.maxlen:
        oldest = spans[0]
        if oldest.t0 + oldest.dur > root.t0:
            return None  # spans of this barrier's time have been dropped
    walk = _Path(mine)
    walk.span(root, root.t0, end)
    return {
        "wall_ms": (end - root.t0) * 1e3,
        "by_kind": {k: v * 1e3 for k, v in walk.by_kind.items()},
        "by_span": sorted(
            ((n, k, s * 1e3) for (n, k), s in walk.by_span.items()),
            key=lambda row: -row[2],
        ),
        "actors": walk.actors,
    }


# -- what the device's operations are --------------------------------------

# The named scopes of the unfused kernels (``jax.named_scope``): a scope
# is ``layer/step``, lower case, no shape and no table id in it. An
# instruction's scope is a PATH of them, outermost first: the loop of
# the row table's probe in the per-group Top-N is ``topn/rows/hash/
# probe``, an operation of its body ``topn/rows/hash/probe/match`` (a
# scope opened inside a loop's body is named relative to the loop's). A
# kernel that opens a scope this table does not hold fails
# tests/test_device_scopes.py; PERF.md 3 says what each one covers.
SCOPES = (
    "hash/probe", "hash/probe/match", "hash/probe/elect",
    "hash/probe/write", "hash/probe/twins", "hash/lookup", "hash/set_live",
    "agg/reduce_by_key/sort", "agg/reduce_by_key/combine", "agg/apply",
    "agg/minput", "agg/flush/select", "agg/flush/gather",
    "agg/flush/snapshot",
    "topn/rows", "topn/groups", "topn/marks",
    "topn/rank/candidates", "topn/rank/gather", "topn/rank/rewritten",
    "topn/rank/digits", "topn/rank/sort", "topn/rank/segments",
    "topn/diff/masks", "topn/diff/retract", "topn/diff/insert",
    "topn/diff/status", "topn/diff/relink",
    "join/stream/probe", "join/stream/chain", "join/stream/emit",
    "join/stream/fold", "join/stream/fold/retract",
    "join/keyed/probe", "join/keyed/emit", "join/keyed/upsert",
    "join/keyed/scan", "join/keyed/pick",
    "join/bucket/probe", "join/bucket/emit", "join/bucket/apply_side",
    "join/bucket/degree",
    "dedup/seen", "dedup/first",
    "over/lay", "over/arena", "over/sort", "over/frame", "over/diff",
    "over/emit", "over/commit",
    "x64/split", "x64/combine",
)

# what jax itself puts on an operation's name stack around the scopes:
# the program and the programs it calls, the transforms (``name(...)``),
# and the parts of a loop or a branch
_STACK_WORD = re.compile(
    r"^(\w+\(.*\)|while|body|cond|branch_\d+_fun|closed_call|core_call)$"
)
_SCOPE_WORDS = frozenset(tuple(sc.split("/")) for sc in SCOPES)
_SCOPE_PREFIXES = frozenset(
    sc[:i] for sc in _SCOPE_WORDS for i in range(1, len(sc) + 1)
)


def scope_of(op_name: str) -> str:
    """The named-scope path cut out of an instruction's ``op_name``
    (``jit(_upsert_step_ed)/topn/rows/jit(lookup_or_insert)/hash/probe/
    while/body/match/eq`` -> ``topn/rows/hash/probe/match``): the words
    that read as scopes of ``SCOPES`` one after the other, once the
    program names, the transforms and a loop's or a branch's own words
    are taken away; "" where no scope was open. What closes the name —
    the primitive, and whatever word jax's own lowerings put before it
    (``jit(cumsum)/<the calling function>/reduce_window_sum``), or
    nothing at all (a ``cummax``'s is its scope alone) — continues no
    scope and falls away, so no rule about a name's last word is
    needed; a scope the table lacks falls away with it, which is why
    tests/test_device_scopes.py holds the sources to the table."""
    done, cur = [], ()
    # (instructions the compiler merged list every name, ``;`` between)
    for w in op_name.partition(";")[0].split("/"):
        if not w or _STACK_WORD.match(w):
            continue
        if cur + (w,) in _SCOPE_PREFIXES:
            cur += (w,)
        elif (not cur or cur in _SCOPE_WORDS) and (w,) in _SCOPE_PREFIXES:
            done += cur
            cur = (w,)
    while cur and cur not in _SCOPE_WORDS:
        cur = cur[:-1]
    return "/".join(done + list(cur))


def split_scopes(path: str):
    """``path`` as the scopes of ``SCOPES`` it is made of, outermost
    first (``topn/rows/hash/probe/match`` -> [``topn/rows``,
    ``hash/probe/match``]); None where it is not made of them."""
    if not path:
        return []
    for sc in sorted(SCOPES, key=len, reverse=True):
        if path == sc or path.startswith(sc + "/"):
            rest = split_scopes(path[len(sc) + 1:])
            if rest is not None:
                return [sc] + rest
    return None


_HLO_TABLE = re.compile(r"^(FileNames|FunctionNames|FileLocations|StackFrames)$")
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_HLO_CALLED = re.compile(
    r"\b(?:calls|body|condition|to_apply|true_computation|"
    r"false_computation)=%?([\w.\-]+)|\bbranch_computations=\{([^}]*)\}"
)
_HLO_FIELD = re.compile(r'(\w+)=(?:"((?:[^"\\]|\\.)*)"|(\d+))')


def _hlo_fields(text: str) -> dict:
    """``key="text" key=7 ...`` of a table's entry or a metadata block."""
    return {
        f.group(1): f.group(2) if f.group(3) is None else int(f.group(3))
        for f in _HLO_FIELD.finditer(text)
    }


def _skip_shape(rest: str) -> str:
    """``rest`` of an instruction line past its shape: a tuple's closing
    parenthesis, else the first blank."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[i + 1:].lstrip()
    return rest.partition(" ")[2]


def parse_hlo(text: str) -> dict:
    """One optimized HLO module's text -> {instruction: {"scope",
    "source", "opcode", "within"[, "scope_from"]}} (``program_ops`` says
    what they are). Every computation's instructions, the fused ones too: the
    xplane names a fusion, and ``within`` leads from what it holds up to
    it."""
    tables: dict = {}
    ops, holder, computation_of, operands = {}, {}, {}, {}
    table = computation = None
    for line in text.splitlines():
        if not line.strip():
            table = None
            continue
        if _HLO_TABLE.match(line):
            table = tables.setdefault(line.strip(), {})
            continue
        if table is not None and line[0].isdigit():
            key, _, rest = line.partition(" ")
            quoted = rest.strip()
            table[int(key)] = (
                quoted[1:-1] if quoted.startswith('"')
                else _hlo_fields(quoted)
            )
            continue
        table = None
        if line.startswith("}"):
            computation = None
            continue
        m = _HLO_COMPUTATION.match(line)
        if m and not line.startswith(" "):
            computation = m.group(1)
            continue
        m = _HLO_INSTRUCTION.match(line)
        if not m or computation is None:
            continue
        name, rest = m.group(1), _skip_shape(m.group(2))
        opcode = re.match(r"[\w\-]*", rest).group(0)
        # the operands: what the opcode's own parentheses hold
        args = rest[len(opcode):]
        operands[name] = re.findall(
            r"%([\w.\-]+)", args[: len(args) - len(_skip_shape(args))]
        )
        at = rest.find("metadata={")
        meta = _hlo_fields(rest[at + 10: rest.find("}", at)]) if at >= 0 else {}
        ops[name] = {
            "scope": scope_of(meta.get("op_name", "")),
            "source": _hlo_source(meta, tables),
            "opcode": opcode,
        }
        computation_of[name] = computation
        for called in _HLO_CALLED.finditer(rest[: at if at >= 0 else None]):
            for comp in (called.group(1) or called.group(2)).split(","):
                holder[comp.strip().lstrip("%")] = name
    for name, op in ops.items():
        op["within"] = holder.get(computation_of[name])
    _lend_scopes(ops, operands, computation_of)
    return ops


def _lend_scopes(ops, operands, computation_of) -> None:
    """What the compiler made carries no name: the halves it splits a
    64-bit lane into (``X64SplitLow``, ``X64Combine``), the copies and
    slices it moves between memories. Such an instruction takes the
    scope of what it was made FOR — the nearest instruction of its own
    computation that reads it and has a scope, else the nearest it
    reads — and says so (``scope_from``: ``user`` / ``operand``)."""
    users: dict = {}
    for name, reads in operands.items():
        for r in reads:
            if computation_of.get(r) == computation_of[name]:
                users.setdefault(r, []).append(name)
    for _ in range(3):  # (a copy's start, its done, the fusion it feeds)
        for name, op in ops.items():
            if op["scope"] or op["opcode"] in ("parameter", "constant"):
                continue
            for how, near in (
                ("user", users.get(name, ())),
                ("operand", operands[name]),
            ):
                lent = next(
                    (ops[n]["scope"] for n in near
                     if n in ops and ops[n]["scope"]), None,
                )
                if lent:
                    op.update(scope=lent, scope_from=how)
                    break


def _hlo_source(meta: dict, tables: dict):
    """``file.py:line`` of an instruction's metadata: its own
    ``source_file`` / ``source_line``, or its stack frame's through the
    module's tables."""
    if "source_file" in meta:
        return f"{os.path.basename(meta['source_file'])}:{meta.get('source_line', 0)}"
    frame = tables.get("StackFrames", {}).get(meta.get("stack_frame_id"))
    where = frame and tables.get("FileLocations", {}).get(
        frame.get("file_location_id")
    )
    file = where and tables.get("FileNames", {}).get(where.get("file_name_id"))
    return f"{os.path.basename(file)}:{where.get('line', 0)}" if file else None


def program_ops(module: str | None = None) -> dict:
    """What the device's operations are, by the names the xplane prints:
    {XLA module (a jitted program: ``jit__upsert_step_ed``): [one entry
    an executable of that name this process holds — a program compiled
    at two push widths is two — {"shapes": the entry computation's
    layout, "ops": {instruction: {"scope": the named-scope path cut out
    of its ``op_name`` (``scope_of``), "source": ``file.py:line``,
    "opcode", "within": the instruction (a while, a conditional, a
    fusion, a call) whose computation holds it, None in the entry
    computation; "scope_from" only where the compiler made the
    instruction and its scope is lent by what reads it}}}]}, read from
    the OPTIMIZED HLO of the loaded
    executables themselves (``client.live_executables()``), so at the
    shapes the session ran them with and with the compiler's own
    numbering. Made when asked and only then: nothing is noted per
    compile, per dispatch or per barrier, and no program is compiled
    for it. ``module``: that one alone."""
    out: dict = {}
    seen = set()
    for client in {d.client for d in jax.devices()}:
        for exe in client.live_executables():
            for mod in exe.hlo_modules():
                if module is not None and mod.name != module:
                    continue
                text = mod.to_string()
                if text in seen:
                    continue
                seen.add(text)
                layout = re.search(
                    r"entry_computation_layout=\{(.*?)\}(?:, \w+=|$)",
                    text.partition("\n")[0],
                )
                out.setdefault(mod.name, []).append({
                    "shapes": layout.group(1) if layout else "",
                    "ops": parse_hlo(text),
                })
    return out


def _shared_scope(scopes):
    """The scope path every one of ``scopes`` begins with (cut back to
    whole scopes of the table); None where they share none."""
    words = []
    for column in zip(*((sc or "").split("/") for sc in scopes)):
        if len(set(column)) > 1:
            break
        words.append(column[0])
    while words and split_scopes("/".join(words)) is None:
        words.pop()
    return "/".join(words) or None


def name_ops(rows, programs: dict | None = None) -> list:
    """The rows of a run's ``breakdown.device_ops`` (``[["module/
    instruction", seconds], ...]``, as benchmarks/trace_reduce.py makes
    them) each with what ``program_ops`` knows of it: {"op", "seconds",
    "scope", "source", "opcode", "within", "nested"}. ``nested`` is true
    where an instruction that holds it (``within``, and so on up) is
    among the rows too, so that a sum over the rows that are not nested
    counts no second twice. One module name can stand for several
    executables, each numbered by the compiler on its own: an
    instruction is looked up in all of them, and where they do not agree
    the row says ``ambiguous`` true and lists the ``candidates`` (its
    ``scope`` is then the leading scopes they all share — two bodies'
    fusions of one loop are surely that loop's — or None); an instruction none
    of them holds has ``scope`` None. Call it in the process that ran
    the programs. THIS is the function a ``benchmark`` PR's
    ``trace_reduce`` calls to print scopes in ``breakdown`` and to cut a
    ``*.device_ms_per_barrier`` by scope (PERF.md 7)."""
    if programs is None:
        programs = program_ops()
    listed = {}
    for key, _ in rows:
        mod, _, instruction = key.partition("/")
        listed.setdefault(mod, set()).add(instruction)
    out = []
    for key, seconds in rows:
        mod, _, instruction = key.partition("/")
        found = []
        for variant in programs.get(mod, ()):
            op = variant["ops"].get(instruction)
            if op is None:
                continue
            up, nested = op["within"], False
            while up is not None and not nested:
                nested = up in listed[mod]
                up = variant["ops"].get(up, {}).get("within")
            answer = dict(op, nested=nested)
            if answer not in found:
                found.append(answer)
        row = {"op": key, "seconds": seconds}
        if len(found) == 1:
            row.update(found[0])
        else:
            row.update(
                scope=_shared_scope([c["scope"] for c in found]),
                source=None, opcode=None, within=None,
                nested=any(c["nested"] for c in found),
            )
            if found:
                row.update(ambiguous=True, candidates=found)
        out.append(row)
    return out


_COMPILE_PREFIX = "/jax/core/compile/"
_COMPILED = "backend_compile_duration"


def _on_compile(event: str, start: float, end: float, **kw) -> None:
    """jax.monitoring listener: one ``compile`` span per program traced,
    lowered or compiled, on the compiling thread. jax times them on the
    wall clock; the span keeps the duration and ends now."""
    if not event.startswith(_COMPILE_PREFIX) or not TRACER.enabled:
        return
    args = {
        "fun_name": str(kw.get("fun_name", "?")),
        "event": event[len(_COMPILE_PREFIX):],
    }
    within = _enclosing_stage(_state())
    if within is not None:
        args["within"] = within
    dur = max(end - start, 0.0)
    sp = TRACER.record(
        "compile", time.perf_counter() - dur, dur, stage="compile", **args
    )
    # a compile whose epoch is still open is logged by close_epoch
    if sp.epoch is not None and args["event"] == _COMPILED:
        _log_compile(sp)


jax.monitoring.register_event_time_span_listener(_on_compile)


def render_chrome_trace(events, thread_names=None) -> str:
    """Render ``(name, tid, t0, dur, args)`` event tuples as chrome://
    tracing / Perfetto JSON. Shared by the live Tracer ring and
    offline renderers (the black-box reader CLI reconstructs barrier
    timelines from a crash-surviving segment through this same path)."""
    names = dict(thread_names or {})
    # the ring appends at span COMPLETION; flow binding needs start
    # order so the "s" (first) event of an epoch precedes its "t"s
    events = sorted(events, key=lambda e: e[2])
    out = []
    # pid lanes: 1 = host/unattributed; each fragment its own pid
    frag_pids: dict = {}
    pids_seen = {1}
    tids_by_pid: dict = {}  # pid -> set(tid)
    epochs_seen: dict = {}  # epoch -> first-event flag
    for name, tid, t0, dur, args in events:
        pid = 1
        if args and "fragment" in args:
            frag = str(args["fragment"])
            pid = frag_pids.setdefault(frag, 2 + len(frag_pids))
            pids_seen.add(pid)
        tids_by_pid.setdefault(pid, set()).add(tid)
        ev = {
            "name": name,
            "ph": "X",
            "pid": pid,
            "tid": tid,
            "ts": t0 * 1e6,
            "dur": dur * 1e6,
        }
        if args:
            ev["args"] = args
        out.append(ev)
        epoch = (args or {}).get("epoch")
        if epoch is not None:
            # flow arrows: first span of the epoch starts the flow,
            # every later span binds to it (enclosing-slice binding)
            first = epoch not in epochs_seen
            epochs_seen[epoch] = True
            out.append(
                {
                    "name": f"epoch {epoch}",
                    "cat": "epoch",
                    # string id: epochs are ms<<16, so truncating
                    # to 32 bits would alias barriers ~65s apart
                    # into one bogus flow chain
                    "ph": "s" if first else "t",
                    "id": str(epoch),
                    "pid": pid,
                    "tid": tid,
                    "ts": t0 * 1e6,
                    "bp": "e",
                }
            )
    # metadata: process names (fragment lanes) + thread names
    meta = [
        {
            "name": "process_name",
            "ph": "M",
            "pid": 1,
            "args": {"name": "host"},
        }
    ]
    for frag, pid in sorted(frag_pids.items(), key=lambda kv: kv[1]):
        meta.append(
            {
                "name": "process_name",
                "ph": "M",
                "pid": pid,
                "args": {"name": f"fragment:{frag}"},
            }
        )
    for pid in sorted(pids_seen):
        for tid in sorted(tids_by_pid.get(pid, ())):
            meta.append(
                {
                    "name": "thread_name",
                    "ph": "M",
                    "pid": pid,
                    "tid": tid,
                    "args": {"name": names.get(tid, f"thread-{tid}")},
                }
            )
    return json.dumps({"traceEvents": meta + out})


TRACER = Tracer()
span = TRACER.span
device_read = TRACER.device_read
