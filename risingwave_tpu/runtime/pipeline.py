"""Single-fragment pipeline: an ordered executor chain + epoch driver.

Reference roles:
- the actor's executor chain (src/stream/src/executor/mod.rs:180 — each
  executor wraps its input stream; here the host feeds messages down an
  ordered list instead);
- barrier flow-through: a barrier entering the chain flushes each
  executor in turn, and an executor's flush output is DATA for every
  executor below it (src/stream/src/task/barrier_manager.rs:634 +
  executor flush_data patterns);
- watermark propagation (executor/watermark_filter.rs): watermarks pass
  through every executor, letting stateful ones clean closed state.

The epoch counter follows the reference epoch encoding
(physical ms << 16, src/common/src/util/epoch.rs:36).
"""

from __future__ import annotations

import time
from collections import deque
from typing import List, Optional, Sequence

from risingwave_tpu.analysis.jax_sanitizer import SIGNATURES, transfer_guard
from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.blackbox import RECORDER
from risingwave_tpu.executors.base import Barrier, Epoch, Executor, Watermark
from risingwave_tpu.parallel.meshprof import MESHPROF
from risingwave_tpu.profiler import PROFILER
from risingwave_tpu.array.lattice import push_lattice
from risingwave_tpu.trace import bound, span


def _walk_span():
    """A serial pipeline's barrier walk. Under a runtime the walk lies
    inside the ``barrier.fragment`` span that stamps ``dispatch``; a
    standalone pipeline (bench drivers, tests) stamps it itself."""
    return span("pipeline.walk", stage=None if bound() else "dispatch")


class FreshnessSurface:
    """Host-side freshness sampling shared by every fragment shape
    (Pipeline / TwoInputPipeline / GraphPipeline): the wall time of the
    epoch's FIRST ingest, the max event-time watermark frontier seen,
    and one sample per barrier (freshness.py consumes these at
    ``runtime._end_trace``; bench.py summarizes them per query). Pure
    host timestamps and dict appends — zero device dispatches.
    """

    FRESHNESS_WINDOW = 512

    def _init_freshness(self) -> None:
        self._ingest_wall: Optional[float] = None
        self.low_watermark: Optional[int] = None
        self.freshness_samples: deque = deque(maxlen=self.FRESHNESS_WINDOW)
        self.last_freshness: Optional[dict] = None

    def _note_ingest(self) -> None:
        if self._ingest_wall is None:
            self._ingest_wall = time.time()

    def _note_watermark(self, value) -> None:
        try:
            v = int(value)
        except (TypeError, ValueError):
            return
        if self.low_watermark is None or v > self.low_watermark:
            self.low_watermark = v

    def _sample_freshness(self, barrier_ms: float) -> dict:
        now = time.time()
        ingest, self._ingest_wall = self._ingest_wall, None
        s = {
            "epoch": self._epoch,
            "ingest_wall": ingest,
            "low_watermark": self.low_watermark,
            "commit_to_visible_ms": round(barrier_ms, 3),
            "source_to_visible_ms": (
                round((now - ingest) * 1e3, 3) if ingest else None
            ),
            "event_time_lag_ms": (
                round(now * 1000.0 - self.low_watermark, 3)
                if self.low_watermark is not None
                else None
            ),
        }
        self.last_freshness = s
        self.freshness_samples.append(s)
        return s


def walk_chain(chain: Sequence[Executor], chunks, barrier=None, tap=None):
    """Feed chunks (then optionally a barrier) down an executor chain;
    every executor's output — including its barrier flush — is data for
    the executors below it. The single chain-walking loop shared by
    Pipeline, TwoInputPipeline and the graph runtime's FragmentActor.
    ``tap`` sees every chunk an executor hands on (an operator edge)."""
    pending = list(chunks)
    # recompile-hazard fingerprinting (analysis/jax_sanitizer) and the
    # dispatch counters: one attribute check each when disarmed — the
    # hot path stays flat
    watch = SIGNATURES if SIGNATURES.enabled else None
    prof = PROFILER if PROFILER.enabled else None
    for ex in chain:
        nxt: List[StreamChunk] = []
        for c in pending:
            if watch is not None:
                watch.observe(ex, c)
            if prof is None:
                nxt.extend(ex.apply(c))
            else:
                nxt.extend(prof.run(ex, ex.apply, c))
        if barrier is not None:
            if prof is None:
                nxt.extend(ex.on_barrier(barrier))
            else:
                nxt.extend(prof.run(ex, ex.on_barrier, barrier))
        if tap is not None:
            for c in nxt:
                tap(c)
        pending = nxt
    return pending


def warm_chain(chain: Sequence[Executor], chunks, tap=None):
    """``walk_chain`` for the warm-up pass: chunks with no valid row
    through every executor's ``warm`` (``Executor.warm``: the programs
    of ``apply``, and no mark). None once an executor does not know
    how: the pass stops there."""
    pending = list(chunks)
    for ex in chain:
        warm = getattr(ex, "warm", None)  # duck-typed executors have none
        nxt: List[StreamChunk] = []
        for c in pending:
            out = warm(c) if warm is not None else None
            if out is None:
                return None
            nxt.extend(out)
        if tap is not None:
            for c in nxt:
                tap(c)
        pending = nxt
    return pending


def chain_push_widths(chain: Sequence[Executor], capacity: int):
    """The widths at which every executor of ``chain`` takes a
    host-built chunk built at ``capacity`` lanes: the push lattice
    (``lattice.push_lattice``) where each declares it
    (``Executor.push_widths``), else the full width alone."""
    widths = set(push_lattice(capacity))
    for ex in chain:
        takes = getattr(ex, "push_widths", None)  # duck-typed: no base
        widths &= set(takes(capacity)) if takes is not None else {capacity}
    return tuple(sorted(widths))


def _pcall(ex, phase, fn, *args):
    """Dispatch-attributed call for executor entry points OUTSIDE
    walk_chain (join apply_left/right, on_barrier in two-input shapes)
    — also the
    recompile-hazard fingerprint tap for those paths: serial AND
    graph-mode join executors feed SignatureWatch here, so two-input
    shapes get the same shape-stability coverage as chain executors."""
    if SIGNATURES.enabled and phase == "apply" and args:
        SIGNATURES.observe(ex, args[0])
    if PROFILER.enabled:
        return PROFILER.run(ex, fn, *args)
    return fn(*args)


class Pipeline(FreshnessSurface):
    """An ordered chain of executors driven by the host epoch loop."""

    def __init__(self, executors: Sequence[Executor]):
        self.executors = list(executors)
        self._epoch = 0
        self._init_freshness()

    # -- message plumbing -------------------------------------------------
    def push(self, chunk: StreamChunk, start: int = 0) -> List[StreamChunk]:
        """Feed one data chunk into the chain; returns what falls out."""
        self._note_ingest()
        return walk_chain(self.executors[start:], [chunk])

    # -- the push lattice (StreamingRuntime.push, PR 32) -------------------
    def push_widths(self, capacity: int):
        """The widths at which this fragment takes a pushed chunk."""
        return chain_push_widths(self.executors, capacity)

    def warm_push(self, chunk: StreamChunk, side: str = "single"):
        """A chunk with no valid row down the chain, the way ``push``
        sends one, through every executor's ``warm``: the programs of a
        chunk of this width exist afterwards and nothing is marked.
        Returns what falls out, for the subscribers' own pass."""
        return warm_chain(self.executors, [chunk]) or []

    def barrier(
        self, checkpoint: bool = True, epoch: Optional[int] = None
    ) -> List[StreamChunk]:
        """Inject a barrier; each executor's flush output becomes data
        for the rest of the chain. Returns chunks exiting the chain.
        ``epoch`` pins the barrier's curr epoch (the runtime passes its
        own clock so held sink batches key by the COMMIT epoch);
        standalone pipelines derive one from the wall clock."""
        prev = self._epoch
        self._epoch = (
            epoch
            if epoch is not None
            else max(int(time.time() * 1000) << 16, prev + 1)
        )
        b = Barrier(Epoch(prev, self._epoch), checkpoint)
        # stage attribution (EpochTrace lifecycle): the walk is host
        # dispatch; the scalar materialization is the barrier-only
        # device fence
        with _walk_span() as walk:
            pending = walk_chain(self.executors, [], barrier=b)
            # executor-GENERATED watermarks (watermark_filter.rs)
            # walk the rest of the chain after the barrier flushes
            for i, ex in enumerate(self.executors):
                wm = ex.emit_watermark()
                if wm is not None:
                    self._note_watermark(wm.value)
                    _, outs = _walk_watermark(
                        self.executors[i + 1 :], wm
                    )
                    pending.extend(outs)
        # materialize every executor's staged barrier scalars AFTER
        # the walk: the async transfers overlapped, so the chain
        # pays ~one round-trip; raises still precede the runtime's
        # epoch commit. transfer_guard: when armed
        # (RW_TRANSFER_GUARD, tests) any IMPLICIT host<->device
        # transfer here raises at the offender
        with span(
            "pipeline.fence", stage="dispatch.fence"
        ) as fence, transfer_guard():
            for ex in self.executors:
                ex.finish_barrier()
        walk_ms, fence_ms = walk.dur * 1e3, fence.dur * 1e3
        self._sample_freshness(walk_ms + fence_ms)
        # standalone pipelines (bench drivers, tests) feed the black
        # box directly — a runtime-driven barrier records via its
        # EpochTrace instead
        RECORDER.record_pipeline_barrier(self._epoch, walk_ms, fence_ms)
        # mesh observability: close this pipeline's per-shard window
        # (no-op unless MESHPROF is armed and watched this chain)
        if MESHPROF.enabled:
            MESHPROF.pipeline_barrier(self)
        return pending

    def watermark(self, column: str, value: int) -> List[StreamChunk]:
        """Propagate a watermark; executors may transform it (e.g. hop
        window: event time -> window_start) or consume it; their flush
        outputs flow downstream as data."""
        self._note_watermark(value)
        _, pending = _walk_watermark(self.executors, Watermark(column, value))
        return pending

    @property
    def epoch(self) -> int:
        return self._epoch


def _walk_watermark(
    chain: Sequence[Executor], wm: Optional[Watermark], chunks=()
):
    """Walk a watermark down an executor chain, behind ``chunks``,
    feeding each executor's flushed output chunks through the rest of
    the chain as data.
    Returns (surviving watermark | None, chunks exiting the chain)."""
    pending: List[StreamChunk] = list(chunks)
    for ex in chain:
        nxt: List[StreamChunk] = []
        for c in pending:
            nxt.extend(ex.apply(c))
        if wm is not None:
            wm, outs = ex.on_watermark(wm)
            nxt.extend(outs)
        pending = nxt
    return wm, pending


def _side_watermark(join, chain, feed, wm, chunks, outs):
    """``chunks`` then ``wm`` down one side's chain into ``join``
    (``feed`` = its apply of that side): what comes out joined is
    appended to ``outs``; returns the join's aligned downstream
    watermark, if this side produced one."""
    wm, pending = _walk_watermark(chain, wm, chunks)
    for c in pending:
        outs.extend(feed(c))
    if wm is None:
        return None
    down, flushed = join.on_watermark(wm)
    outs.extend(flushed)
    return down


class TwoInputPipeline(FreshnessSurface):
    """Two upstream chains joined by a two-input executor, then a tail.

    Reference shape: a join actor's two MergeExecutor inputs aligned on
    barriers (executor/barrier_align.rs) — the host driver is the
    aligner: it feeds each side's chunks in arrival order and calls
    ``barrier`` only when both sides reached it.

    ``head`` is a sub-plan both sides start with over one stream (the
    planner's shared sub-plan; empty for every other join): it runs
    once, and what it hands on, its barrier flush included, goes down
    ``left`` into the join and then down ``right``. Such a pipeline has
    one input, ``push_both``.
    """

    def __init__(
        self,
        left: Sequence[Executor],
        right: Sequence[Executor],
        join,
        tail: Sequence[Executor],
        head: Sequence[Executor] = (),
    ):
        self.head = list(head)
        self.left = list(left)
        self.right = list(right)
        self.join = join
        self.tail = list(tail)
        self._epoch = 0
        self._init_freshness()
        # whole-pipeline fusion overlay (runtime/fused_step
        # fuse_two_input): when set, pushes buffer into the wrapper and
        # the barrier runs ONE donated device program — the member
        # chains above stay intact as the checkpoint/lint/watermark
        # surface (the wrapper is an execution strategy, not an owner)
        self._fused = None

    def _through(self, chain, chunks, barrier=None):
        return walk_chain(chain, chunks, barrier)

    def _sides(self):
        """The join's inputs in the order they are fed."""
        return (
            (self.left, self.join.apply_left),
            (self.right, self.join.apply_right),
        )

    def _join_side(self, chain, feed, chunks, barrier=None):
        outs = []
        for c in self._through(chain, chunks, barrier):
            outs.extend(_pcall(self.join, "apply", feed, c))
        return outs

    def _push_side(self, chain, feed, chunk):
        if self.head:
            raise ValueError(
                "both sides of this join start with one shared sub-plan: "
                "its stream goes in through push_both"
            )
        return self._through(
            self.tail, self._join_side(chain, feed, [chunk])
        )

    def push_left(self, chunk: StreamChunk) -> List[StreamChunk]:
        self._note_ingest()
        if self._fused is not None:
            return self._fused.buffer_left(chunk)
        return self._push_side(self.left, self.join.apply_left, chunk)

    def push_right(self, chunk: StreamChunk) -> List[StreamChunk]:
        self._note_ingest()
        if self._fused is not None:
            return self._fused.buffer_right(chunk)
        return self._push_side(self.right, self.join.apply_right, chunk)

    def push_both(self, chunk: StreamChunk) -> List[StreamChunk]:
        """A chunk of ONE stream that feeds both inputs (a self-join):
        through the head once, then left before right."""
        if not self.head:
            return self.push_left(chunk) + self.push_right(chunk)
        self._note_ingest()
        shared = self._through(self.head, [chunk])
        outs = []
        for chain, feed in self._sides():
            outs.extend(self._join_side(chain, feed, shared))
        return self._through(self.tail, outs)

    def barrier(
        self, checkpoint: bool = True, epoch: Optional[int] = None
    ) -> List[StreamChunk]:
        prev = self._epoch
        self._epoch = (
            epoch
            if epoch is not None
            else max(int(time.time() * 1000) << 16, prev + 1)
        )
        b = Barrier(Epoch(prev, self._epoch), checkpoint)
        with _walk_span() as walk:
            if self._fused is not None:
                # ONE donated device program for the whole fragment
                # barrier; finish defers to the K-boundary under
                # RW_FUSED_PIPELINE_DEPTH (the wrapper decides)
                outs = _pcall(
                    self._fused, "flush", self._fused.on_barrier, b
                )
            else:
                shared = self._through(self.head, [], barrier=b)
                joined: List[StreamChunk] = []
                for chain, feed in self._sides():
                    joined.extend(
                        self._join_side(chain, feed, shared, barrier=b)
                    )
                joined.extend(
                    _pcall(self.join, "flush", self.join.on_barrier, b)
                )
                outs = self._through(self.tail, joined, barrier=b)
            outs.extend(self._generated_watermarks())
        with span(
            "pipeline.fence", stage="dispatch.fence"
        ) as fence, transfer_guard():
            if self._fused is not None:
                self._fused.finish_barrier()
            else:
                for ex in self.executors:
                    ex.finish_barrier()
        walk_ms, fence_ms = walk.dur * 1e3, fence.dur * 1e3
        self._sample_freshness(walk_ms + fence_ms)
        RECORDER.record_pipeline_barrier(self._epoch, walk_ms, fence_ms)
        if MESHPROF.enabled:
            MESHPROF.pipeline_barrier(self)
        return outs

    def _generated_watermarks(self) -> List[StreamChunk]:
        """Poll emit_watermark on every executor; a side-chain watermark
        walks the rest of its chain, through the join's alignment, then
        the tail (the same route a driver-injected one takes)."""
        outs: List[StreamChunk] = []
        aligned: Optional[Watermark] = None
        for i, ex in enumerate(self.head):
            wm = ex.emit_watermark()
            if wm is None:
                continue
            self._note_watermark(wm.value)
            wm, pending = _walk_watermark(self.head[i + 1 :], wm)
            for chain, feed in self._sides():
                aligned = (
                    _side_watermark(self.join, chain, feed, wm, pending, outs)
                    or aligned
                )
        for chain, feed in self._sides():
            for i, ex in enumerate(chain):
                wm = ex.emit_watermark()
                if wm is None:
                    continue
                self._note_watermark(wm.value)
                aligned = (
                    _side_watermark(
                        self.join, chain[i + 1 :], feed, wm, (), outs
                    )
                    or aligned
                )
        outs = self._through(self.tail, outs)
        _, tail_outs = _walk_watermark(self.tail, aligned)
        outs.extend(tail_outs)
        for i, ex in enumerate(self.tail):
            wm = ex.emit_watermark()
            if wm is not None:
                _, touts = _walk_watermark(self.tail[i + 1 :], wm)
                outs.extend(touts)
        return outs

    def watermark(self, column: str, value: int) -> List[StreamChunk]:
        """Send a watermark down both input chains; each side's
        (possibly transformed) watermark reaches the join, which cleans
        that side's window state and emits an ALIGNED downstream
        watermark (min over both inputs) once both sides advanced —
        which then walks the tail chain (reference: per-input watermark
        alignment on multi-input executors)."""
        self._note_watermark(value)
        if self._fused is not None:
            # buffered rows precede the watermark in stream order: the
            # fused wrapper applies them (data-only program), then the
            # walk below runs over member state interpreted — state
            # lives in the members between programs, so interop is
            # exact (the FusedChainExecutor.on_watermark discipline)
            self._fused.flush_data()
        outs: List[StreamChunk] = []
        aligned: Optional[Watermark] = None
        wm, pending = _walk_watermark(self.head, Watermark(column, value))
        for chain, feed in self._sides():
            aligned = (
                _side_watermark(self.join, chain, feed, wm, pending, outs)
                or aligned
            )
        # data chunks enter the tail BEFORE the aligned watermark closes
        # anything they belong to
        data_outs = self._through(self.tail, outs)
        _, tail_outs = _walk_watermark(self.tail, aligned)
        return data_outs + tail_outs

    @property
    def executors(self) -> List[Executor]:
        """Every executor in the fragment, for checkpoint enumeration."""
        return self.head + self.left + self.right + [self.join] + self.tail

    @property
    def epoch(self) -> int:
        return self._epoch
