"""StreamingRuntime — the meta-lite control plane for one process.

Reference roles replaced:
- ``GlobalBarrierManager`` event loop + ``ScheduledBarriers`` min-
  interval tick (src/meta/src/barrier/mod.rs:532, barrier/schedule.rs:348);
- ``CheckpointControl`` in-flight epoch tracking + ``complete_barrier``
  -> ``HummockManager::commit_epoch`` (barrier/mod.rs:845);
- the async uploader overlapping checkpoint IO with the next epoch's
  compute (src/storage/src/hummock/event_handler/uploader.rs:548);
- recovery from max_committed_epoch (barrier/recovery.rs:353).

TPU re-design: fragments are host-driven pipelines over device state,
so the runtime is a synchronous epoch clock plus an ASYNC checkpoint
lane: at a checkpoint barrier the runtime stages every executor's
delta (the only device-touching step, O(changed rows) and mark flips
happen HERE, on the main thread), then hands SST build + upload +
manifest commit to a background worker that preserves epoch order. A
worker failure is fatal for live state (marks are already flipped):
the next barrier raises and the driver must recover() from the last
durable manifest — the reference's failed-barrier recovery contract.

Partial recovery departs from that contract where it can: an ACTOR
death is attributed to its fragment by the graph supervisor
(runtime/graph.py), and ``_auto_recover`` restores + replays ONLY the
blast radius (failed fragments + transitive subscribers) from a
per-fragment replay buffer of uncommitted inputs — healthy fragments
keep their live state and keep answering queries. Stop-the-world
recovery remains the floor: unattributable failures, whole-runtime
blasts, lost replay windows, and three consecutive failed partials all
fall back to it (and three consecutive fulls raise).
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from risingwave_tpu import blackbox
from risingwave_tpu import utils_sync_point as sync_point
from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.epoch_trace import (
    EpochTrace,
    StageSums,
    chunk_nbytes,
    dump_stalls,
)
from risingwave_tpu.event_log import EVENT_LOG
from risingwave_tpu.freshness import FRESHNESS, attribute_backpressure
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.resilience import (
    STORE_UNAVAILABLE,
    CircuitBreaker,
    DeltaSpill,
    RetryingObjectStore,
    RetryPolicy,
)
from risingwave_tpu.trace import (
    TRACER,
    active_spans,
    barrier_path,
    bind,
    close_epoch,
    profiling,
    span,
    whole_call,
)
from risingwave_tpu.executors.materialize import DeviceMaterializeExecutor, MaterializeExecutor
from risingwave_tpu.parallel.meshprof import MESHPROF
from risingwave_tpu.runtime.shape_governor import ShapeGovernor
from risingwave_tpu.storage.object_store import ObjectStore
from risingwave_tpu.storage.state_table import CheckpointManager

_LOG = logging.getLogger(__name__)


class StreamingRuntime:
    """Owns fragments (pipelines), the barrier clock, and checkpoints.

    Args:
      store: object store for checkpoints (None = no persistence).
      barrier_interval_ms: the reference's ``barrier_interval_ms``
        system param (default 1000) — used by ``tick()`` pacing.
      checkpoint_frequency: every Nth barrier is a checkpoint
        (system_param/mod.rs:78).
      async_checkpoint: overlap SST build/upload with the next epochs'
        compute (uploader analogue). ``wait_checkpoints()`` joins.
    """

    @classmethod
    def from_config(cls, cfg, store: Optional[ObjectStore] = None):
        """Build from an RwConfig (config.rs load path): the system
        params drive the barrier clock; storage config drives the
        store root + compaction cadence."""
        from risingwave_tpu.storage.object_store import LocalFsObjectStore

        if store is None:
            store = LocalFsObjectStore(cfg.storage.object_store_root)
        res = getattr(cfg, "resilience", None)
        retry_policy = breaker = None
        if res is not None:
            retry_policy = RetryPolicy.from_env(
                max_attempts=res.retry_max_attempts,
                base_backoff_s=res.retry_base_backoff_ms / 1e3,
                max_backoff_s=res.retry_max_backoff_ms / 1e3,
                deadline_s=res.retry_deadline_s,
            )
            breaker = CircuitBreaker.from_env(
                "object_store",
                failure_threshold=res.breaker_threshold,
                cooldown_s=res.breaker_cooldown_s,
            )
        bb = getattr(cfg, "blackbox", None)
        if bb is not None:
            # [blackbox] section arms the flight recorder's segment
            # persistence and/or the device sentinel (env wins inside)
            blackbox.configure(bb)
        return cls(
            store,
            barrier_interval_ms=cfg.system.barrier_interval_ms,
            checkpoint_frequency=cfg.system.checkpoint_frequency,
            compact_at=cfg.storage.compact_at,
            retry_policy=retry_policy,
            breaker=breaker,
        )

    def __init__(
        self,
        store: Optional[ObjectStore] = None,
        barrier_interval_ms: int = 1000,
        checkpoint_frequency: int = 1,
        async_checkpoint: bool = True,
        compact_at: int = 8,
        memory_budget_bytes: Optional[int] = None,
        auto_recover: bool = False,
        retry_policy: Optional[RetryPolicy] = None,
        breaker: Optional[CircuitBreaker] = None,
        degraded_dir: Optional[str] = None,
    ):
        # failure detection + self-healing (barrier/mod.rs:676-710 +
        # recovery.rs:353): a poisoned epoch or dead actor surfacing at
        # the barrier triggers recovery WITHOUT caller intervention —
        # rebuild actor graphs, restore state from the last committed
        # epoch, roll source offsets back so the pump replays
        self.auto_recover = auto_recover
        self.auto_recoveries = 0
        # RW_BLACKBOX_* env arming must work on EVERY construction path
        # (serve without --config, compute_node, direct construction),
        # not only from_config; a no-op when the env vars are unset
        blackbox.from_env()
        # recompile-storm governor (runtime/shape_governor.py): per-barrier
        # SignatureWatch hazard deltas vs RW_FUSION_RECOMPILE_BUDGET;
        # over budget (or ANY hazard while the device sentinel reports
        # SLOW) pins the offending executors to their max bucket. Own
        # instance per runtime — pin state never leaks across runtimes.
        self.shape_governor = ShapeGovernor()
        # HBM memory governor + overload ladder (runtime/
        # memory_governor.py): global device-state ledger enforcing
        # RW_HBM_BUDGET_BYTES via BucketAllocator grow vetoes + cold-
        # tier spill, credit-based source admission, and the NORMAL ->
        # THROTTLED -> SHEDDING -> DEGRADED ladder. Dormant (one
        # attribute check per barrier) unless a budget or
        # RW_OVERLOAD_LADDER arms it. Own instance per runtime.
        from risingwave_tpu.runtime.memory_governor import MemoryGovernor

        self.memory_governor = MemoryGovernor()
        # the admission controller is the governor's: SourceManager
        # attaches to THIS to have its polls credit-clamped
        self.admission = self.memory_governor.admission
        # RW_SHAPE_WATCH_WARMUP=<N>: arm SignatureWatch from construction
        # and mark it stable after N barriers — the env-only way to run
        # the governor hot in production/soak without code changes
        self._shape_watch_warmup = 0
        try:
            self._shape_watch_warmup = int(
                os.environ.get("RW_SHAPE_WATCH_WARMUP", "0")
            )
        except ValueError:
            pass
        if self._shape_watch_warmup > 0:
            from risingwave_tpu.analysis.jax_sanitizer import SIGNATURES

            if SIGNATURES.enabled:
                # another runtime (or the bench harness) already owns
                # the process-global watch: starting it again would
                # wipe the legitimized shape set mid-run and mint
                # false hazards — this runtime stands down from watch
                # management (its governor still consumes deltas)
                self._shape_watch_warmup = 0
            else:
                SIGNATURES.start()
        # state >> HBM control (the reference's LRU memory controller,
        # src/compute/src/memory/controller.rs role): when accounted
        # device state exceeds the budget, fully-durable groups are
        # evicted to the object store and fold back on next touch
        self.memory_budget_bytes = memory_budget_bytes
        # heap profiling (/heap): this runtime's executors feed the
        # device-state half of the report (utils_heap, jeprof analogue)
        from risingwave_tpu import utils_heap

        utils_heap.attach_runtime(self)
        # shared arrangements (runtime/arrangements.py): the registry
        # of refcounted device indexes serving N structurally-identical
        # MVs off one writer fragment; the barrier publishes a version
        # per arrangement (one attribute check when nothing is shared)
        from risingwave_tpu.runtime.arrangements import ArrangementRegistry

        self.arrangements = ArrangementRegistry(self)
        # monotonic write counter: every chunk entering ANY fragment
        # bumps it, so a published arrangement version can prove the
        # live state still sits at its barrier boundary (lazy snapshot
        # materialization without a torn-read window)
        self._write_gen = 0
        self.fragments: Dict[str, object] = {}
        # upstream -> [(downstream, side)]; side targets one input of a
        # two-input fragment ("left"/"right") or "single"
        self._subs: Dict[str, List[Tuple[str, str]]] = {}
        # (fragment, side, capacity) -> the widths a chunk built at
        # that capacity may be cut to on its way in (_push_widths);
        # emptied whenever the fragments or their edges change
        self._push_plans: Dict[Tuple[str, str, int], Tuple[int, ...]] = {}
        self._aux_state: List[object] = []
        self.barrier_interval_ms = barrier_interval_ms
        self.checkpoint_frequency = checkpoint_frequency
        # the durability boundary is retry-wrapped and breaker-gated
        # (resilience.py): transient store faults are absorbed by
        # backoff; a hard-down store opens the breaker and the runtime
        # DEGRADES instead of dying — queries keep answering from
        # live/HBM state, checkpoint deltas spill locally, compaction
        # pauses, and the spill replays when the breaker half-opens.
        if store is not None:
            if isinstance(store, RetryingObjectStore):
                if store.breaker is None:
                    # a breaker-less pre-wrapped store (e.g. bare
                    # store.resilient()) would make degraded-mode
                    # restore probes unthrottled — every barrier would
                    # pay the full retry deadline against a down store.
                    # The runtime REQUIRES the cooldown gate: attach one.
                    store.breaker = breaker or CircuitBreaker.from_env(
                        "object_store"
                    )
                self.store_breaker = store.breaker
            else:
                self.store_breaker = breaker or CircuitBreaker.from_env(
                    "object_store"
                )
                store = RetryingObjectStore(
                    store,
                    retry_policy or RetryPolicy.from_env(),
                    self.store_breaker,
                )
        else:
            self.store_breaker = None
        self.mgr = (
            CheckpointManager(store, compact_at=compact_at)
            if store is not None
            else None
        )
        # degraded-mode checkpointing state (guarded by _degraded_lock:
        # the async worker and the barrier thread both touch it)
        self._degraded = False
        self._degraded_lock = threading.Lock()
        self._spill = DeltaSpill(degraded_dir)
        # a persistent RW_DEGRADED_DIR can hold a PREVIOUS incarnation's
        # spill: those epochs rolled back with that process (sources
        # replay their data after recovery) — replaying them here would
        # at best trip the manifest's epoch guard and at worst
        # double-apply. Stale on arrival; discard.
        stale = self._spill.discard_all()
        if stale:
            EVENT_LOG.record("degraded_discard", epochs=stale, at="boot")
        self.async_checkpoint = async_checkpoint
        # -- partial recovery (fragment-scoped failover) ----------------
        # per-fragment replay buffer of UNCOMMITTED inputs: every chunk
        # entering a fragment (driver push, MV-on-MV routed delta,
        # backfill) plus per-fragment barrier markers. A scoped recovery
        # restores only the blast radius's state tables from the last
        # committed checkpoint and replays this log into the rebuilt
        # subtree — healthy fragments never roll back. Pruned as epochs
        # become durable; a fragment whose log overflows re-anchors at
        # the next barrier (replay floor) and is full-recovery-only
        # until the anchor epoch is durable.
        self._replay: Dict[str, List[tuple]] = {}
        # fragment -> lowest epoch the log can replay from (0 = any
        # committed state; None = window lost, re-anchors at the next
        # barrier marker)
        self._replay_floor: Dict[str, Optional[int]] = {}
        # fragment -> last durable epoch whose STAGING included this
        # fragment. Usually the global committed epoch, but a fragment
        # fenced for a deferred recovery is excluded from staging, so
        # healthy-only commits advance the manifest WITHOUT covering it
        # — pruning or replay-skipping by the global epoch would then
        # silently drop its un-durable window
        self._replay_covered: Dict[str, int] = {}
        self._replay_lock = threading.Lock()
        import os as _os

        try:
            self._replay_cap = int(
                _os.environ.get("RW_REPLAY_BUFFER_EVENTS", "4096")
            )
        except ValueError:
            self._replay_cap = 4096
        # deferred partial recovery (store unavailable mid-recovery):
        # the blast radius stays fenced — skipped by barriers, its
        # inputs parked in the replay buffer — until the breaker lets a
        # restore probe through (composes with degraded mode)
        self._pending_partial: Optional[Dict[str, object]] = None
        self._consecutive_partials = 0
        self._consecutive_recoveries = 0
        # "partial" | "full" | None — chaos pumps read this to decide
        # whether the failed epoch's data was replayed (partial) or
        # rolled back with everything else (full: re-feed / re-poll)
        self.last_recovery_mode: Optional[str] = None
        self.partial_recoveries = 0
        self._epoch = self.mgr.max_committed_epoch if self.mgr else 0
        self._barrier_seq = 0
        self._barrier_root = None  # the last barrier's root span
        self._last_barrier_at = 0.0
        self.barrier_latencies_ms: List[float] = []
        self.checkpoint_sync_ms: List[float] = []  # stage->durable, per ckpt
        self._worker: Optional[threading.Thread] = None
        self._work_q: deque = deque()
        self._work_event = threading.Event()
        self._work_err: List[BaseException] = []
        self._inflight = 0
        self._inflight_lock = threading.Lock()
        self._compactor: Optional[threading.Thread] = None
        self._compact_event = threading.Event()
        self._compact_pause = threading.Event()  # set = paused (recovery)
        self._compact_idle = threading.Event()
        self._compact_idle.set()
        self.compaction_errors: List[BaseException] = []
        self._work_abort = threading.Event()
        # serializes barrier/DDL/DML against a background barrier clock
        # (the CLI's tick thread vs pgwire sessions — the reference
        # serializes via the meta barrier scheduler's command queue)
        self.lock = threading.RLock()
        # -- barrier-lifecycle observability (EpochTrace) ---------------
        # every barrier gets a stage-attributed trace; the ring keeps
        # the recent history for /events-style inspection and bench
        self.epoch_traces: deque = deque(maxlen=256)
        self.last_epoch_trace: Optional[EpochTrace] = None
        # stage sums of the pushes since the last barrier (the open
        # epoch has no EpochTrace yet): _begin_trace folds them in
        self._open_stages = StageSums()
        self._ingest_bytes = 0  # chunk bytes moved since last barrier
        self._prev_state_bytes = 0
        # stall watchdog: if a barrier exceeds this deadline, dump every
        # actor's span stack + channel depths BEFORE recovery destroys
        # the evidence (the q7 wedge forensic path). None disables.
        # Default rides just under the barrier deadman
        # (RW_BARRIER_TIMEOUT_S, which device benches raise to cover
        # first-epoch XLA compiles) so a legitimately-compiling barrier
        # never writes a false stall artifact.
        from risingwave_tpu.runtime.graph import _default_barrier_timeout

        try:
            self.stall_dump_after_s: Optional[float] = float(
                os.environ.get(
                    "RW_STALL_DUMP_S",
                    max(60.0, 0.9 * _default_barrier_timeout()),
                )
            )
        except ValueError:
            self.stall_dump_after_s = 0.9 * _default_barrier_timeout()

    # -- fragments -------------------------------------------------------
    def register(
        self,
        name: str,
        pipeline,
        upstream: Optional[str] = None,
        backfill: bool = True,
    ) -> None:
        """Register a fragment. With ``upstream`` (an already-registered
        fragment name), this is MV-on-MV: the upstream's emitted deltas
        are routed into this pipeline after every push/barrier, and —
        unless ``backfill=False`` (recovery re-registration: the state
        is already checkpointed) — the upstream MV's current rows are
        snapshot-backfilled first (no_shuffle_backfill.rs:66; see
        runtime/backfill.py)."""
        if name in self.fragments:
            raise ValueError(f"fragment {name!r} already registered")
        if upstream is not None and upstream not in self.fragments:
            raise KeyError(f"unknown upstream fragment {upstream!r}")
        self.fragments[name] = pipeline
        self._push_plans.clear()
        set_label = getattr(pipeline, "set_label", None)
        if set_label is not None:  # graph-backed: actors get unique keys
            set_label(name)
        if self.mgr is not None:
            for ex in pipeline.executors:
                # sinks: delivery is deferred until the epoch's manifest
                # is durable (ADVICE r2: sink commits may never run
                # ahead of durability)
                if hasattr(ex, "deliver_on_durable"):
                    ex.deliver_on_durable = True
                # checkpoint staging will drain pending buffers, so
                # executors skip their own per-barrier compaction
                if hasattr(ex, "checkpoint_enabled"):
                    ex.checkpoint_enabled = True
                # cold tier: evicted durable groups read back through
                # the manager's point-read path (storage get_rows)
                if hasattr(ex, "cold_reader") and hasattr(ex, "table_id"):
                    ex.cold_reader = (
                        lambda keys, _tid=ex.table_id: self.mgr.get_rows(
                            _tid, keys
                        )
                    )
                # multi-table executors (join sides) pick their own
                # table per read
                if hasattr(ex, "cold_get_rows"):
                    ex.cold_get_rows = self.mgr.get_rows
        # mesh observability: instrument sharded chains as they come up
        # (no-op unless MESHPROF is armed AND the chain carries sharded
        # executors — serial fragments stay byte-for-byte untouched)
        if MESHPROF.enabled:
            MESHPROF.watch(pipeline, name=name)
        if upstream is not None:
            self.subscribe(upstream, name, backfill=backfill)

    def subscribe(
        self,
        upstream: str,
        name: str,
        backfill: bool = True,
        side: str = "single",
    ) -> None:
        """Add a delta edge upstream -> name. Multiple subscriptions of
        one fragment realize UNION ALL (the reference's UnionExecutor,
        union.rs: n inputs merged into one stream — here the host
        routes every upstream's chunks into the same pipeline).
        ``side`` targets one input of a two-input fragment ("left" /
        "right"), so joins over two upstream MVs/tables work."""
        if upstream not in self.fragments:
            raise KeyError(f"unknown upstream fragment {upstream!r}")
        if name not in self.fragments:
            raise KeyError(f"unknown fragment {name!r}")
        # UNION schema check (union.rs asserts input schemas match):
        # a second upstream feeding the same (fragment, side) must
        # expose the same lane set, or the mismatch would surface deep
        # inside a kernel long after DDL time
        def _mv_sig(frag):
            try:
                mv = self._fragment_mview(frag)
            except ValueError:
                return None  # no materialize stage: nothing to compare
            dts = getattr(mv, "dtypes", None)  # device MVs
            if not isinstance(dts, dict):
                dts = getattr(mv, "_dtypes", None)  # host MVs (lazy)
            if not isinstance(dts, dict):
                dts = {}
            return {
                n: (str(dts[n]) if n in dts else None)
                for n in tuple(mv.pk) + tuple(mv.columns)
            }

        new_sig = _mv_sig(upstream)
        if new_sig is not None:
            for prev_up, edges in self._subs.items():
                if prev_up == upstream or (name, side) not in edges:
                    continue
                prev_sig = _mv_sig(prev_up)
                if prev_sig is None:
                    continue
                mismatch = set(prev_sig) != set(new_sig) or any(
                    # dtypes compare only where BOTH sides know them
                    # (host MVs learn dtypes from their first chunk)
                    a is not None and b is not None and a != b
                    for a, b in (
                        (new_sig[n], prev_sig[n]) for n in new_sig
                    )
                )
                if mismatch:
                    raise ValueError(
                        f"UNION inputs disagree on schema: {upstream!r} "
                        f"exposes {sorted(new_sig.items())} but "
                        f"{prev_up!r} exposes {sorted(prev_sig.items())}"
                    )
        self._subs.setdefault(upstream, []).append((name, side))
        self._push_plans.clear()
        if backfill:
            from risingwave_tpu.runtime.backfill import snapshot_chunks

            up_mv = self._fragment_mview(upstream)
            for chunk in snapshot_chunks(up_mv):
                self._route(name, self._push_into(name, chunk, side))

    def unregister(self, name: str) -> None:
        """Remove a fragment and every subscription edge touching it —
        the rollback path when CREATE fails mid-registration (the
        reference cleans dirty streaming jobs the same way,
        ddl_controller.rs + barrier/recovery.rs 'clean dirty jobs')."""
        self.fragments.pop(name, None)
        self._subs.pop(name, None)
        self._push_plans.clear()
        FRESHNESS.drop(name)
        with self._replay_lock:
            self._replay.pop(name, None)
            self._replay_floor.pop(name, None)
        for up, edges in list(self._subs.items()):
            kept = [e for e in edges if e[0] != name]
            if kept:
                self._subs[up] = kept
            else:
                del self._subs[up]

    def rename_fragment(self, old: str, new: str) -> None:
        """Re-key a fragment (and every edge/replay record touching
        it) without disturbing its pipeline, state, or the topological
        registration order — the shared-arrangement owner-drop handoff
        (the writer keeps streaming under an internal alias while the
        user-visible name frees up)."""
        if old not in self.fragments:
            raise KeyError(f"unknown fragment {old!r}")
        if new in self.fragments:
            raise ValueError(f"fragment {new!r} already registered")
        self._push_plans.clear()
        # rebuilt in place so the barrier walk's topological order holds
        self.fragments = {
            (new if k == old else k): v for k, v in self.fragments.items()
        }
        if old in self._subs:
            self._subs[new] = self._subs.pop(old)
        for up, edges in self._subs.items():
            self._subs[up] = [
                ((new if n == old else n), s) for n, s in edges
            ]
        with self._replay_lock:
            for m in (self._replay, self._replay_floor, self._replay_covered):
                if old in m:
                    m[new] = m.pop(old)

    def _fragment_mview(self, name: str):
        for ex in reversed(self.fragments[name].executors):
            if isinstance(
                ex, (MaterializeExecutor, DeviceMaterializeExecutor)
            ):
                return ex
        raise ValueError(f"fragment {name!r} has no materialize stage")

    # -- replay buffer (partial recovery's data source) -------------------
    def _record_push(self, name: str, chunk: StreamChunk, side: str) -> None:
        if self.mgr is None:
            return  # no durability boundary -> no recovery -> no log
        with self._replay_lock:
            if self._replay_floor.get(name, 0) is None:
                return  # window lost: re-anchors at the next barrier
            log = self._replay.setdefault(name, [])
            if len(log) >= self._replay_cap:
                # bounded: drop the window rather than grow without
                # limit — this fragment falls back to full recovery
                # until the log re-anchors at a durable barrier
                log.clear()
                self._replay_floor[name] = None
                REGISTRY.counter("replay_buffer_overflows_total").inc(
                    fragment=name
                )
                return
            log.append(("push", chunk, side))

    def _record_barrier(self, name: str, epoch: int, checkpoint: bool) -> None:
        if self.mgr is None:
            return
        with self._replay_lock:
            if self._replay_floor.get(name, 0) is None:
                # re-anchor: state as of THIS barrier is the new replay
                # baseline; the log replays any committed epoch >= it
                self._replay[name] = []
                self._replay_floor[name] = epoch
                return
            self._replay.setdefault(name, []).append(
                ("barrier", epoch, checkpoint)
            )

    def _prune_replay(self, epoch: int) -> None:
        """Epoch is durable: events at or before its barrier marker can
        never be replayed again (restores land at >= this epoch).
        Fragments fenced for a deferred recovery were EXCLUDED from
        this epoch's staging — their durable coverage did not advance,
        so their logs must keep the whole window for the resume."""
        pp = self._pending_partial
        skip = pp["scope"] if pp is not None else ()
        with self._replay_lock:
            for name, log in self._replay.items():
                if name in skip:
                    continue
                self._replay_covered[name] = max(
                    self._replay_covered.get(name, 0), epoch
                )
                cut = 0
                for i, ev in enumerate(log):
                    if ev[0] == "barrier" and ev[1] <= epoch:
                        cut = i + 1
                if cut:
                    del log[:cut]

    def _push_into(self, name: str, chunk: StreamChunk, side: str):
        # failpoint for crash tests: a push that dies mid-fan-out (one
        # subscriber absorbed the chunk, a later one did not) is the
        # half-applied-epoch window the compute node must roll back
        sync_point.hit(f"push_into:{name}:{side}")
        self._write_gen += 1
        self._record_push(name, chunk, side)
        pp = self._pending_partial
        if pp is not None and name in pp["scope"]:
            # fenced for a deferred partial recovery: the input is
            # parked in the replay buffer and applied when the store
            # heals — healthy fragments keep flowing around it
            return []
        p = self.fragments[name]
        if side == "left":
            return p.push_left(chunk)
        if side == "right":
            return p.push_right(chunk)
        if side == "both":
            # self-join: ONE base stream feeds both join inputs (the
            # Nexmark q7 shape — bid joined against its own per-window
            # max); the reference realizes this as two upstream edges
            # from the same fragment, or as one into the sub-plan the
            # two sides share (StreamShare)
            return p.push_both(chunk)
        return p.push(chunk)

    def push(self, name: str, chunk: StreamChunk, side: str = "single"):
        """Feed one chunk into a fragment and route its emitted deltas
        into every subscribed downstream fragment (the exchange edge an
        MV-on-MV chain rides). A host-built chunk goes in as wide as
        what it holds (``_cut_to_rows``)."""
        # ingest attribution: the next barrier's EpochTrace charges this
        # host time + chunk bytes to its "ingest" stage
        with bind(self._open_stages), span(
            "push", stage="ingest", fragment=name, side=side,
            rows=chunk.host_rows,
        ) as sp:
            cut = self._cut_to_rows(name, chunk, side)
            sp.args["capacity"] = cut.capacity
            outs = self._push_into(name, cut, side)
            REGISTRY.counter("chunks_pushed_total").inc(fragment=name)
            REGISTRY.counter("push_chunks_total").inc(
                fragment=name, lanes=str(cut.capacity)
            )
            self._route(name, outs)
        self._ingest_bytes += chunk_nbytes(chunk)
        return outs

    # -- the push lattice (lattice.push_lattice, PR 32) ------------------
    def _cut_to_rows(
        self, name: str, chunk: StreamChunk, side: str
    ) -> StreamChunk:
        """A host-built chunk cut to the smallest declared width that
        holds its rows, where the fragment and everything the chunk is
        routed on to take such widths. The decision rests on what the
        host already has — ``host_rows`` (``from_numpy`` packs the rows
        into the leading lanes and counts them) and the capacity —
        and reads nothing off the device; a chunk a device step
        derived (``host_rows`` None) goes in as it is."""
        rows = chunk.host_rows
        if rows is None:
            return chunk
        widths = self._push_plans.get((name, side, chunk.capacity))
        if widths is None:
            widths = self._plan_push(name, chunk, side)
        lanes = next(w for w in widths if w >= rows)
        return chunk if lanes == chunk.capacity else chunk.leading(lanes)

    def _push_widths(self, name: str, capacity: int) -> set:
        """What ``name`` and every fragment subscribed behind it take
        of a chunk built at ``capacity`` lanes: a fragment that does
        not say takes the full width alone."""
        takes = getattr(self.fragments[name], "push_widths", None)
        widths = set(takes(capacity)) if takes is not None else {capacity}
        for sub, _side in self._subs.get(name, ()):
            widths &= self._push_widths(sub, capacity)
        return widths

    def _plan_push(
        self, name: str, chunk: StreamChunk, side: str
    ) -> Tuple[int, ...]:
        """First chunk built at this capacity on this route: settle the
        widths it may be cut to and, where there is more than one, send
        a chunk with no valid row of EVERY one of them the same way
        (``warm_push``), so that each width's programs exist before a
        stream meets it and a size first met compiles nothing. Only
        here does the runtime learn the width its feeder builds: the
        capacity is the pusher's, no setting of the session."""
        if self._pending_partial is not None:
            return (chunk.capacity,)  # fenced fragments: settle later
        widths = tuple(sorted(self._push_widths(name, chunk.capacity)))
        self._push_plans[(name, side, chunk.capacity)] = widths
        if len(widths) > 1:
            for lanes in widths:
                whole = lanes == chunk.capacity
                self._warm_into(
                    name,
                    (chunk if whole else chunk.leading(lanes)).emptied(),
                    side,
                )
        return widths

    def _warm_into(self, name: str, chunk: StreamChunk, side: str) -> None:
        for out in self.fragments[name].warm_push(chunk, side):
            for sub, sub_side in self._subs.get(name, ()):
                self._warm_into(sub, out, sub_side)

    def _route(self, upstream: str, chunks) -> None:
        for sub, side in self._subs.get(upstream, ()):
            outs = []
            for c in chunks:
                outs.extend(self._push_into(sub, c, side))
            self._route(sub, outs)

    def register_state(self, obj) -> None:
        """Register a non-pipeline Checkpointable (e.g. a source's
        split offsets) into the checkpoint/recovery cycle."""
        self._aux_state.append(obj)

    def unregister_state(self, obj) -> None:
        """Drop a Checkpointable (DROP SOURCE): a dead executor must
        not keep persisting its state every checkpoint."""
        self._aux_state = [o for o in self._aux_state if o is not obj]

    def executors(self) -> List[object]:
        out = []
        for p in self.fragments.values():
            out.extend(p.executors)
        out.extend(self._aux_state)
        return out

    # -- barrier clock ---------------------------------------------------
    @property
    def epoch(self) -> int:
        return self._epoch

    def next_epoch(self) -> int:
        return max(int(time.time() * 1000) << 16, self._epoch + 1)

    def barrier(self) -> Dict[str, List[StreamChunk]]:
        """Inject one barrier into every fragment; commit a checkpoint
        every ``checkpoint_frequency``-th barrier. Returns each
        fragment's emitted chunks.

        With ``auto_recover``, a failure here (poisoned epoch, dead
        actor, commit-lane error) recovers in place and returns {} —
        the failed epoch is abandoned, offsets roll back, and the
        caller's next pump replays it (no manual recover())."""
        began = profiling()
        try:
            return self._barrier()
        finally:
            root, self._barrier_root = self._barrier_root, None
            whole_call(root, began)

    def _barrier(self) -> Dict[str, List[StreamChunk]]:
        with self.lock:
            watchdog = self._arm_stall_watchdog()
            try:
                outs = self._barrier_locked()
                self._consecutive_recoveries = 0
                self._consecutive_partials = 0
                # a clean barrier clears the pump contract flag: pumps
                # consult it ONLY when a barrier recovered instead of
                # committing, so it must never linger from a past one
                self.last_recovery_mode = None
                if getattr(self, "_grew_last_recovery", False):
                    # the grown replay committed: the growths were
                    # legitimate cures, not a runaway — refund the
                    # per-executor give-up budget
                    self._grew_last_recovery = False
                    for ex in self.executors():
                        if getattr(ex, "_growth_rounds", 0):
                            ex._growth_rounds = 0
                return outs
            except (KeyboardInterrupt, SystemExit):
                raise  # never convert an operator stop into a recovery
            except Exception as e:
                if not self.auto_recover or self.mgr is None:
                    raise
                self._auto_recover(e)
                return {}
            finally:
                if watchdog is not None:
                    watchdog.cancel()

    def _arm_stall_watchdog(self) -> Optional[threading.Timer]:
        """Fire a stall dump if the barrier outlives its deadline — the
        artifact lands while the barrier is STILL stuck, before any
        recovery/abandonment destroys the evidence (q7 wedge case)."""
        if self.stall_dump_after_s is None or self.stall_dump_after_s <= 0:
            return None
        epoch_at_arm = self._epoch

        def _fire() -> None:
            dump_stalls(
                f"barrier after epoch {epoch_at_arm} exceeded "
                f"{self.stall_dump_after_s}s deadline",
                runtime=self,
            )

        # one Timer thread per barrier: ~100µs against a >=100ms barrier
        # cadence (barrier_interval_ms); canceled timers exit promptly.
        # The name is load-bearing for the orphan-timer regression test:
        # every exit path of barrier() (success, recovery, escalation
        # raise) runs the finally-cancel, so no timer with this name may
        # outlive its barrier.
        t = threading.Timer(self.stall_dump_after_s, _fire)
        t.daemon = True
        t.name = "rw-stall-watchdog"
        t.start()
        return t

    def _auto_recover(self, cause: Exception) -> None:
        """Failure routing with the partial→full→raise escalation
        ladder:

        1. If the failure is attributable to one (or a few) graph-backed
           fragments and the blast radius is a strict subset of the
           runtime, run FRAGMENT-SCOPED PARTIAL RECOVERY: restore only
           the affected fragments' state tables, replay their buffered
           inputs, and leave healthy fragments' live state untouched.
        2. Three consecutive partial-recovery failures (the fault keeps
           re-firing) escalate to today's FULL recovery.
        3. Three consecutive full recoveries raise the deterministic-
           fault error (the existing contract)."""
        self.last_failure = cause
        REGISTRY.counter("auto_recoveries_total").inc()
        self.auto_recoveries += 1
        # deviceprof re-arms across the rebuild: stale per-barrier
        # telemetry drops, program analyses survive (the rebuilt
        # fragments re-fuse into the SAME compiled programs), and no
        # capture window can orphan — deviceprof never opens one
        from risingwave_tpu.deviceprof import DEVICEPROF

        DEVICEPROF.on_recovery()
        # a DeviceWedged is handled like an actor fault, not a crash:
        # abort the sentinel's capture window and disarm the wedge so
        # the recovered runtime's next barrier proceeds — a device that
        # is STILL wedged re-arms on the next missed heartbeat, and the
        # consecutive-recovery ladder surfaces it as deterministic
        blackbox.SENTINEL.abort_capture()
        if isinstance(cause, blackbox.DeviceWedged):
            blackbox.SENTINEL.clear_wedge()
        # a latched capacity overflow needs the full path's grow-and-
        # replay cure; everything else may be partial-eligible
        latched = any(
            fn()
            for fn in (
                getattr(ex, "capacity_overflow_latched", None)
                for ex in self.executors()
            )
            if fn is not None
        )
        scope = None if latched else self._partial_scope()
        while scope is not None and self._consecutive_partials < 3:
            self._consecutive_partials += 1
            EVENT_LOG.record(
                "recovery",
                mode="partial",
                fragments=sorted(scope),
                scope=len(scope),
                total=len(self.fragments),
                consecutive=self._consecutive_partials,
                cause=repr(cause),
            )
            try:
                # store-free cleanup FIRST — even before draining the
                # async lane, which can itself raise STORE_UNAVAILABLE:
                # a fenced sink's stale held batch must be gone before
                # ANY later epoch can become durable and release it
                self._discard_scope(scope)
                # drain — never abort — the async lane: healthy
                # fragments' staged epochs must still commit; only the
                # blast radius rolls back
                self.wait_checkpoints()
                self._partial_recover(scope, repr(cause))
                self.last_recovery_mode = "partial"
                return
            except (KeyboardInterrupt, SystemExit):
                raise
            except STORE_UNAVAILABLE:
                # degraded-mode composition: the store is down, so the
                # restore DEFERS — the blast radius stays fenced (its
                # inputs park in the replay buffer) and healthy
                # fragments keep serving; the barrier clock retries the
                # restore once the breaker lets a probe through
                self._pending_partial = {
                    "scope": set(scope), "cause": repr(cause)
                }
                REGISTRY.counter("partial_recovery_deferrals_total").inc()
                EVENT_LOG.record(
                    "recovery",
                    mode="partial_deferred",
                    fragments=sorted(scope),
                )
                self.last_recovery_mode = "partial"
                return
            except Exception as e:  # noqa: BLE001 — count + escalate
                cause = e
                scope = self._partial_scope() or scope
        # -- full recovery (the stop-the-world floor) --------------------
        # a DETERMINISTIC failure (e.g. a capacity overflow) would
        # recover-replay-fail forever: after a few consecutive failed
        # epochs, surface the cause instead
        self._consecutive_recoveries += 1
        EVENT_LOG.record(
            "recovery",
            mode="auto",
            cause=repr(cause),
            consecutive=self._consecutive_recoveries,
        )
        # a latched sharded-capacity overflow is DETERMINISTIC at the
        # old shape but curable: grow the overflowed op 2x before the
        # replay (the reference reschedules with more parallelism,
        # scale.rs:453 — here capacity is the per-shard analogue) and
        # refund the deterministic-fault budget so the grown replay
        # gets its attempt. Quiesce FIRST: an in-flight worker step
        # could otherwise write an old-shape table back over the grown
        # one.
        self._quiesce()
        grew = 0
        for ex in self.executors():
            latched_fn = getattr(ex, "capacity_overflow_latched", None)
            if latched_fn is None or not latched_fn():
                continue
            rounds = getattr(ex, "_growth_rounds", 0)
            if rounds >= 5:
                raise RuntimeError(
                    f"{type(ex).__name__} still overflows after "
                    f"{rounds} capacity doublings — giving up"
                ) from cause
            ex.grow_for_replay()
            ex._growth_rounds = rounds + 1
            REGISTRY.counter("overflow_growths_total").inc()
            grew += 1
        if grew:
            self._grew_last_recovery = True
            self._consecutive_recoveries = min(
                self._consecutive_recoveries, 1
            )
        if self._consecutive_recoveries >= 3:
            raise RuntimeError(
                "auto-recovery failed 3 consecutive epochs — the fault "
                "is deterministic, not transient"
            ) from cause
        self.last_recovery_mode = "full"
        # dead actor threads never come back: rebuild graph-backed
        # fragments (fresh actors/channels around the same executors)
        # BEFORE restoring executor state
        for p in self.fragments.values():
            fn = getattr(p, "rebuild", None)
            if fn is not None:
                fn()
        self.recover()

    # -- partial recovery (fragment-scoped failover) ---------------------
    def _partial_scope(self) -> Optional[set]:
        """The runtime-level blast radius of the current failure: the
        fragments whose actor graphs recorded an actor death, plus
        their transitive subscribers (MV-on-MV closure). None when the
        failure is not scopeable — no graph attributed it, the scope
        covers every fragment, or the replay window was lost."""
        if self.mgr is None:
            return None
        failed = set()
        for name, p in self.fragments.items():
            fn = getattr(p, "failure_scope", None)
            if fn is not None and fn():
                failed.add(name)
        if not failed:
            return None
        scope = set(failed)
        frontier = list(failed)
        while frontier:
            up = frontier.pop()
            for sub, _side in self._subs.get(up, ()):
                if sub not in scope:
                    scope.add(sub)
                    frontier.append(sub)
        if scope >= set(self.fragments):
            return None  # whole-runtime blast: full recovery is the floor
        committed = self.mgr.max_committed_epoch
        with self._replay_lock:
            for name in scope:
                floor = self._replay_floor.get(name, 0)
                cov = min(committed, self._replay_covered.get(name, committed))
                if floor is None or floor > cov:
                    return None  # replay window lost for this fragment
        return scope

    def _scoped_plans(self, scope: set) -> Dict[str, tuple]:
        """(graph_fragments_or_None, executors_to_restore) per scoped
        fragment, in registration (topological) order."""
        plans: Dict[str, tuple] = {}
        for name, p in self.fragments.items():
            if name not in scope:
                continue
            fn = getattr(p, "scoped_recovery_plan", None)
            plans[name] = fn() if fn is not None else (None, list(p.executors))
        return plans

    def _discard_scope(self, scope: set) -> None:
        """Store-free cleanup of a blast radius: drop held sink batches
        of every scoped fragment, so no later
        durable epoch can release output whose producing state is about
        to roll back and replay (double delivery). Runs BEFORE any
        store touch — a deferred restore must leave nothing stale."""
        for name, p in self.fragments.items():
            if name not in scope:
                continue
            for ex in p.executors:
                fn = getattr(ex, "discard_pending", None)
                if fn is not None:
                    fn()

    def _partial_recover(self, scope: set, cause: str) -> None:
        """Restore + replay ONLY ``scope``: rebuild each affected
        pipeline's actors (scoped inside the graph when sound), restore
        its state tables from the last committed checkpoint, replay its
        buffered inputs, and rejoin at the next barrier boundary.
        Healthy fragments are never touched — their MVs keep answering
        ``query()`` throughout. Raises STORE_UNAVAILABLE (caller defers)
        when the store cannot serve the restore reads."""
        t0 = time.perf_counter()
        committed = self.mgr.max_committed_epoch
        plans = self._scoped_plans(scope)
        self._discard_scope(scope)
        br = self.store_breaker
        if br is not None and not br.allow():
            from risingwave_tpu.resilience import CircuitOpenError

            raise CircuitOpenError(
                "object store breaker open: partial recovery deferred"
            )
        self.partial_recoveries += 1
        REGISTRY.counter("partial_recoveries_total").inc()
        REGISTRY.gauge("recovery_scope_fragments").set(float(len(scope)))
        # quiesce compaction: its GC deletes SSTs the restore reads
        self._compact_pause.set()
        try:
            self._compact_idle.wait()
            for name, (gfrags, exs) in plans.items():
                tf = time.perf_counter()
                p = self.fragments[name]
                rb = getattr(p, "rebuild", None)
                if rb is not None:
                    try:
                        rb(fragments=gfrags)
                    except TypeError:  # a rebuild() without scoping
                        rb()
                self.mgr.recover(exs)
                # this fragment's restore lands at ITS durable coverage
                # — which lags the global committed epoch if healthy-
                # only commits advanced the manifest while it was fenced
                with self._replay_lock:
                    cov = min(
                        committed, self._replay_covered.get(name, committed)
                    )
                p._epoch = cov
                for ex in exs:
                    fn = getattr(ex, "on_recover", None)
                    if fn is not None:
                        fn(cov)
                # test/operator hook: fires INSIDE the recovery window,
                # after the subtree restored and before it rejoins —
                # healthy MVs must answer query() right now
                sync_point.hit(f"partial_recovery:{name}")
                self._replay_fragment(name, p, cov)
                REGISTRY.histogram("recovery_downtime_ms").observe(
                    (time.perf_counter() - tf) * 1e3, fragment=name
                )
        finally:
            self._compact_pause.clear()
        self._work_abort.clear()
        self._work_err.clear()
        # shared arrangements must not keep serving snapshots that
        # postdate the restored state — republish off the recovery
        self.arrangements.on_recovery(committed)
        EVENT_LOG.record(
            "recovery",
            mode="partial_done",
            fragments=sorted(scope),
            epoch=committed,
            wall_ms=round((time.perf_counter() - t0) * 1e3, 2),
        )

    def _replay_fragment(self, name: str, p, covered: int) -> int:
        """Replay a fragment's buffered inputs on top of its restored
        state: skip everything the fragment's durable coverage already
        holds, re-push the rest in order, re-running barrier boundaries
        as NON-checkpoint barriers (the next real checkpoint stages the
        whole replayed delta). Outputs are discarded — every subscriber
        is inside the scope and replays its OWN recorded inputs, so
        routing them again would double-apply."""
        with self._replay_lock:
            log = list(self._replay.get(name, ()))
        start = 0
        for i, ev in enumerate(log):
            if ev[0] == "barrier" and ev[1] <= covered:
                start = i + 1
        replayed = 0
        # replay re-runs ALREADY-SEEN epochs: recording them would
        # break the black box's monotonic timeline — suppress
        with blackbox.RECORDER.suppress_pipeline_records():
            for ev in log[start:]:
                if ev[0] == "push":
                    _k, chunk, side = ev
                    if side == "left":
                        p.push_left(chunk)
                    elif side == "right":
                        p.push_right(chunk)
                    elif side == "both":
                        p.push_both(chunk)
                    else:
                        p.push(chunk)
                    replayed += 1
                else:
                    _k, epoch, _ck = ev
                    # mutation-style rejoin boundary: the rebuilt
                    # subtree re-aligns at the SAME epoch fence the
                    # healthy graph already passed
                    p.barrier(checkpoint=False, epoch=epoch)
        if replayed or start < len(log):
            REGISTRY.counter("replay_events_total").inc(
                len(log) - start, fragment=name
            )
        return replayed

    def _maybe_resume_partial(self) -> bool:
        """Deferred partial recovery rides the barrier clock (like the
        degraded-mode restore probe): retry the scoped restore once the
        breaker lets a store touch through. If the replay window was
        lost while deferred, escalate to full recovery instead of
        silently dropping data."""
        pp = self._pending_partial
        if pp is None:
            return False
        br = self.store_breaker
        if br is not None and not br.allow():
            return False
        scope = set(pp["scope"])
        committed = self.mgr.max_committed_epoch if self.mgr else 0
        with self._replay_lock:
            lost = any(
                self._replay_floor.get(n, 0) is None
                or self._replay_floor.get(n, 0)
                > min(committed, self._replay_covered.get(n, committed))
                for n in scope
            )
        if lost:
            self._pending_partial = None
            EVENT_LOG.record(
                "recovery",
                mode="auto",
                cause="deferred partial recovery lost its replay window",
            )
            for p in self.fragments.values():
                fn = getattr(p, "rebuild", None)
                if fn is not None:
                    fn()
            self.last_recovery_mode = "full"
            self.recover()
            return True
        try:
            self._partial_recover(scope, str(pp["cause"]))
        except STORE_UNAVAILABLE:
            return False  # still down: stay deferred, never wedge
        except Exception:
            self._pending_partial = None
            raise  # surfaces through barrier() -> _auto_recover routing
        self._pending_partial = None
        self.last_recovery_mode = "partial"
        return True

    def _staging_executors(self) -> List[object]:
        """Executors eligible for checkpoint staging: while a deferred
        partial recovery has fragments fenced, their (unrestored) state
        must not be staged into a manifest — healthy fragments and aux
        state keep committing around them."""
        pp = self._pending_partial
        if pp is None:
            return self.executors()
        skip = pp["scope"]
        out: List[object] = []
        for name, p in self.fragments.items():
            if name in skip:
                continue
            out.extend(p.executors)
        out.extend(self._aux_state)
        return out

    def _barrier_locked(self) -> Dict[str, List[StreamChunk]]:
        # device-wedge fail-fast: an armed sentinel wedge raises the
        # structured DeviceWedged HERE instead of letting the barrier
        # walk dispatch into a dead device and hang until an outer
        # alarm (the q7 wedge path); auto_recover routes it like any
        # other barrier fault
        blackbox.SENTINEL.check()
        # degraded-mode probe rides the barrier clock: the breaker's
        # cooldown gates actual store touches, so a down store costs
        # nothing per barrier and a healed one replays the spill here
        self._maybe_restore_degraded()
        # deferred partial recovery probes on the same clock
        self._maybe_resume_partial()
        t0 = time.perf_counter()
        prev, self._epoch = self._epoch, self.next_epoch()
        self._barrier_seq += 1
        is_ckpt = (
            self.mgr is not None
            and self._barrier_seq % self.checkpoint_frequency == 0
        )
        tr = self._begin_trace(is_ckpt)
        with bind(tr), span("barrier", seq=self._barrier_seq) as root:
            self._barrier_root = root
            outs = self._barrier_walk(tr, prev, is_ckpt)
        ms = (time.perf_counter() - t0) * 1e3
        self.barrier_latencies_ms.append(ms)
        REGISTRY.histogram("barrier_latency_ms").observe(ms)
        REGISTRY.counter("barriers_total").inc()
        return outs

    def _barrier_walk(
        self, tr: EpochTrace, prev: int, is_ckpt: bool
    ) -> Dict[str, List[StreamChunk]]:
        """One synchronous barrier under its root span: the fragment
        walk, the checkpoint's staging, the governors, the trace's end."""
        outs = {}
        pending = self._pending_partial
        # registration order is topological (downstreams register after
        # their upstream), so an upstream's barrier-flush deltas reach a
        # subscriber BEFORE the subscriber's own barrier runs.
        # Suppression spans the whole walk: this barrier records ONCE
        # via its EpochTrace in _end_trace, not per fragment pipeline
        with blackbox.RECORDER.suppress_pipeline_records():
            for name, p in self.fragments.items():
                if pending is not None and name in pending["scope"]:
                    continue  # fenced: deferred recovery owns this subtree
                p._epoch = prev  # fragments share the runtime's clock
                # non-checkpoint barriers must NOT commit sinks
                # (exactly-once: sink commits may never run ahead of
                # durability); the runtime's epoch is passed down so
                # held sink batches key by the exact epoch
                # _commit/_on_epoch_durable will use
                with span(
                    "barrier.fragment", stage="dispatch", fragment=name
                ):
                    outs[name] = p.barrier(
                        checkpoint=is_ckpt, epoch=self._epoch
                    )
                    with span("barrier.route", fragment=name):
                        self._route(name, outs[name])
                    # replay-buffer epoch fence: everything recorded
                    # before this marker belongs to epochs <=
                    # self._epoch for this fragment
                    self._record_barrier(name, self._epoch, is_ckpt)
        if is_ckpt:
            self._commit(self._epoch, tr)
        with span("barrier.bookkeeping", stage="bookkeeping"):
            if self.memory_budget_bytes is not None:
                self._enforce_memory_budget()
            # recompile-storm governor: consume this barrier's hazard
            # deltas; over budget (or SLOW device) → pin to max bucket.
            # One attribute check while SignatureWatch is disarmed.
            self._shape_watch_tick()
            self.shape_governor.observe_barrier(self)
        self._end_trace(tr)
        return outs

    def _shape_watch_tick(self) -> None:
        """RW_SHAPE_WATCH_WARMUP bookkeeping: after N barriers the
        armed SignatureWatch turns stable — every later novel shape is
        a hazard the governor may act on."""
        if self._shape_watch_warmup <= 0:
            return
        self._shape_watch_warmup -= 1
        if self._shape_watch_warmup == 0:
            from risingwave_tpu.analysis.jax_sanitizer import SIGNATURES

            SIGNATURES.mark_stable()

    # -- EpochTrace plumbing ---------------------------------------------
    def _begin_trace(self, is_ckpt: bool) -> EpochTrace:
        tr = EpochTrace(self._epoch, self._barrier_seq, is_ckpt)
        # commit->visible anchor (freshness.py): wall clock at barrier
        # open; _end_trace measures to the post-publish visible point
        tr.barrier_open_wall = time.time()
        # the pushes' spans waited on this thread for their epoch
        close_epoch(tr.epoch)
        # what this path instruments reads 0.0 where its span does not
        # run (no permit waited for, no string new), never absent
        tr.declare(
            "ingest", "ingest.permit_wait", "ingest.device_wait",
            "publish", "bookkeeping", "dispatch",
        )
        if is_ckpt:
            tr.declare(
                "checkpoint_stage", "checkpoint_stage.marks",
                "checkpoint_stage.pull", "checkpoint_stage.dictionary",
                "checkpoint_stage.device_wait",
            )
        # charge accumulated push() time/bytes to this epoch's ingest
        sums: Dict[str, float] = {}
        for (stage, _frag), ms in self._open_stages.take().items():
            sums[stage] = sums.get(stage, 0.0) + ms
        for stage, ms in sums.items():
            tr.add_stage(stage, ms)
        tr.chunk_bytes = self._ingest_bytes
        self._ingest_bytes = 0
        return tr

    def _end_trace(self, tr: EpochTrace) -> None:
        """Close the barrier's trace. ``wall_ms`` closes in ``finalize``
        (the flight recorder, itself bookkeeping, reads it), so the
        stages ``publish`` and ``bookkeeping`` lie after it."""
        with span("barrier.bookkeeping", stage="bookkeeping"):
            with span("bookkeeping.state_nbytes"):
                state_bytes = self.state_nbytes()
            with span("bookkeeping.finalize"):
                tr.finalize(state_bytes, self._prev_state_bytes)
            self._prev_state_bytes = state_bytes
            self.epoch_traces.append(tr)
            self.last_epoch_trace = tr
        # shared arrangements: swap in this barrier's published version
        # (pointer swap; materializes only under active read demand)
        with span("barrier.publish", stage="publish"):
            self.arrangements.publish(tr.epoch)
        with span("barrier.bookkeeping", stage="bookkeeping"):
            self._observe_barrier(tr)

    def _observe_barrier(self, tr: EpochTrace) -> None:
        """The per-barrier collectors, on the barrier's thread, each
        under a span of its own so that the ring names the dearest."""
        # freshness + backpressure attribution (ISSUE 16): NOW the
        # epoch's snapshots are what a reader sees — measure to here.
        # Host timestamps and dict folds only; never faults a barrier.
        try:
            with span("bookkeeping.freshness"):
                self._observe_freshness(tr)
        except Exception:  # noqa: BLE001 — accounting never faults
            pass
        # memory governor + overload ladder: consumes the fresh state
        # bytes and this barrier's backpressure verdict, applies veto/
        # spill/ladder/credit actions. Dormant = one attribute check.
        # Never faults a barrier (self-guarded).
        with span("bookkeeping.memory_governor"):
            self.memory_governor.observe_barrier(self, tr)
        # mesh observability: fold the per-pipeline shard windows closed
        # this barrier into one mesh doc on the trace (per-shard stage
        # lanes + exchange matrix + skew verdict). Dormant = one
        # attribute check; self-guarded, never faults a barrier.
        with span("bookkeeping.meshprof"):
            MESHPROF.observe_barrier(self, tr)
        # flight recorder: the finalized trace is exactly one black-box
        # record (ring always; segment file when a dir is configured);
        # a barrier that stands out of the ring's takes its own path along
        with span("bookkeeping.recorder"):
            if blackbox.RECORDER.is_slow(tr.wall_ms):
                self._note_slow_barrier(tr)
            blackbox.RECORDER.record_barrier(tr, runtime=self)
        if tr.checkpoint:
            EVENT_LOG.record(
                "barrier_commit",
                epoch=tr.epoch,
                wall_ms=round(tr.wall_ms, 2),
                achieved_bw_frac=tr.achieved_bw_frac,
            )

    def _note_slow_barrier(self, tr: EpochTrace) -> None:
        """Where a slow barrier sat (S15): its critical path out of the
        span ring, while the ring still holds it, as one ``slow_barrier``
        event, one warning on stderr (an untraced run prints nothing
        else of a barrier's inside) and the ``slow`` field of the
        barrier's flight record. Never faults the barrier."""
        try:
            path = barrier_path(tr.epoch)
            if path is None:
                return
            me = threading.current_thread().name + "("
            tr.slow_path = {
                "epoch": tr.epoch,
                "wall_ms": round(path["wall_ms"], 3),
                "by_kind": {
                    k: round(v, 3) for k, v in path["by_kind"].items()
                },
                "by_span": [
                    [name, kind, round(ms, 3)]
                    for name, kind, ms in path["by_span"][:8]
                ],
                "actors": path["actors"],
                "threads": {
                    t: stack for t, stack in active_spans().items()
                    if not t.startswith(me)
                },
            }
            EVENT_LOG.record("slow_barrier", **tr.slow_path)
            _LOG.warning(
                "slow_barrier %s", json.dumps(tr.slow_path, default=str)
            )
        except Exception:  # noqa: BLE001 — accounting never faults
            pass

    def _observe_freshness(self, tr: EpochTrace) -> None:
        """Per-MV freshness deltas at the VISIBLE point + the barrier's
        backpressure verdict (freshness.py). commit->visible runs from
        the barrier-open wall clock to after ``arrangements.publish`` —
        the first instant a lock-free reader can see the epoch; the
        fragments contribute their own ingest wall + watermark frontier
        via FreshnessSurface samples keyed by this epoch."""
        visible = time.time()
        c2v = (
            round((visible - tr.barrier_open_wall) * 1e3, 3)
            if tr.barrier_open_wall
            else None
        )
        fr: Dict[str, dict] = {}
        for name, p in list(self.fragments.items()):
            ent: Dict[str, float] = {}
            if c2v is not None:
                ent["commit_to_visible_ms"] = c2v
            s = getattr(p, "last_freshness", None)
            if s is not None and s.get("epoch") == tr.epoch:
                iw = s.get("ingest_wall")
                if iw:
                    ent["source_to_visible_ms"] = round(
                        (visible - iw) * 1e3, 3
                    )
                lw = s.get("low_watermark")
                if lw is not None:
                    ent["event_time_lag_ms"] = round(
                        visible * 1000.0 - lw, 3
                    )
            FRESHNESS.observe(name, tr.epoch, tr.checkpoint, **ent)
            fr[name] = ent
        # attached shared-arrangement names become visible at the SAME
        # publish: they inherit their backing fragment's deltas
        reg = self.arrangements
        for mv in list(reg._facades):
            if mv in fr:
                continue
            frag = reg.fragment_for(mv)
            base = fr.get(
                frag,
                {"commit_to_visible_ms": c2v} if c2v is not None else {},
            )
            FRESHNESS.observe(mv, tr.epoch, tr.checkpoint, **base)
            fr[mv] = base
        tr.freshness = fr
        verdict = attribute_backpressure(self, tr)
        tr.backpressure_fragment = verdict["fragment"]
        tr.backpressure_ms = verdict["ms"]
        tr.backpressure = verdict["detail"]

    def state_nbytes(self) -> int:
        """Accounted device state across all fragments (host estimate)."""
        return sum(
            ex.state_nbytes()
            for ex in self.executors()
            if hasattr(ex, "state_nbytes")
        )

    def _enforce_memory_budget(self) -> None:
        total = self.state_nbytes()
        REGISTRY.gauge("state_bytes").set(float(total))
        if total <= self.memory_budget_bytes:
            return
        # eviction frees only durable slots; an in-flight async commit
        # has flipped stored marks for state that is not durable YET —
        # join the lane first so evict never races durability
        self.wait_checkpoints()
        evicted = 0
        for ex in self.executors():
            fn = getattr(ex, "evict_cold", None)
            has_reader = (
                getattr(ex, "cold_reader", None) is not None
                or getattr(ex, "cold_get_rows", None) is not None
            )
            if fn is not None and has_reader:
                evicted += fn()
        REGISTRY.counter("cold_evictions_total").inc(evicted)
        REGISTRY.gauge("state_bytes").set(float(self.state_nbytes()))

    def tick(self) -> bool:
        """Barrier iff ``barrier_interval_ms`` elapsed since the last
        one (ScheduledBarriers min-interval tick). Returns whether a
        barrier fired."""
        with self.lock:
            now = time.time()
            if (
                now - self._last_barrier_at
            ) * 1000 < self.barrier_interval_ms:
                return False
            self._last_barrier_at = now
            self.barrier()
            return True

    def p99_barrier_ms(self) -> float:
        if not self.barrier_latencies_ms:
            return 0.0
        return float(np.percentile(self.barrier_latencies_ms, 99))

    # -- degraded mode (store breaker open) ------------------------------
    @property
    def degraded(self) -> bool:
        return self._degraded

    def try_restore_degraded(self) -> bool:
        """Operator/driver surface: force a restore probe NOW (the
        barrier clock does this automatically). True = fully restored."""
        with self.lock:
            return self._maybe_restore_degraded()

    def _enter_degraded(
        self, epoch: int, staged, cause: BaseException
    ) -> None:
        """The store became unavailable mid-epoch (breaker open or
        retry budget exhausted): spill the staged deltas locally, pause
        compaction, keep serving queries from live/HBM state. The
        spilled epochs replay — in order — once the breaker half-opens
        (``_maybe_restore_degraded``)."""
        with self._degraded_lock:
            first = not self._degraded
            self._degraded = True
            self._spill.spill(epoch, staged)
        if first:
            self._compact_pause.set()
            REGISTRY.counter("degraded_entries_total").inc()
            REGISTRY.gauge("degraded_mode").set(1.0)
            EVENT_LOG.record(
                "degraded", epoch=epoch, cause=repr(cause)
            )

    def _commit_or_degrade(self, epoch: int, staged, tr=None) -> bool:
        """The single durable-commit gate for the sync path and the
        async worker: returns True iff the epoch is durable; a store-
        unavailable failure degrades instead of raising (any OTHER
        failure propagates — the failed-barrier recovery contract)."""
        with self._degraded_lock:
            if self._degraded:
                self._spill.spill(epoch, staged)
                return False
        try:
            self.mgr.commit_staged(epoch, staged, trace=tr)
            return True
        except STORE_UNAVAILABLE as e:
            self._enter_degraded(epoch, staged, e)
            return False

    def _maybe_restore_degraded(self) -> bool:
        """Probe the healed store: replay spilled epochs in order
        through the normal commit path. Called at every barrier (the
        breaker's cooldown gates how often the store is actually
        touched). Returns True when the runtime left degraded mode."""
        if not self._degraded:
            return False
        br = self.store_breaker
        if br is not None and not br.allow():
            return False  # still cooling down: no store touch at all
        replayed = []
        restored = False
        with self._degraded_lock:
            if not self._degraded:
                return False
            try:
                for epoch in self._spill.epochs():
                    if epoch <= self.mgr.max_committed_epoch:
                        # already covered by the manifest (e.g. a
                        # replay attempt that committed but failed
                        # later): the spill entry is redundant
                        self._spill.remove(epoch)
                        continue
                    staged = self._spill.load(epoch)
                    # replay is idempotent: a previous half-committed
                    # attempt left orphan SSTs at the same paths which
                    # this put simply overwrites; the manifest is the
                    # only durability authority
                    self.mgr.commit_staged(epoch, staged)
                    self._spill.remove(epoch)
                    replayed.append(epoch)
            except STORE_UNAVAILABLE:
                # breaker re-opened mid-replay; already-replayed epochs
                # ARE durable — only the tail stays spilled
                pass
            else:
                self._degraded = False
                restored = True
        # durable hooks (sink release — arbitrary external work) run
        # OUTSIDE the lock so the async worker never stalls behind them
        if replayed:
            REGISTRY.counter("degraded_epochs_replayed_total").inc(
                len(replayed)
            )
        for epoch in replayed:
            self._on_epoch_durable(epoch)
        if not restored:
            return False
        REGISTRY.gauge("degraded_mode").set(0.0)
        EVENT_LOG.record(
            "restored",
            epochs_replayed=len(replayed),
            epoch=self.mgr.max_committed_epoch,
        )
        self._compact_pause.clear()
        self._kick_compactor()
        return True

    # -- checkpoint lane -------------------------------------------------
    def _commit(self, epoch: int, tr: Optional[EpochTrace] = None) -> None:
        self._raise_worker_error()
        # stage on the main thread (device pull + eager mark flips, with
        # the duplicate-table_id check) — ONE code path with the sync
        # commit (CheckpointManager.stage / commit_staged)
        t_staged = time.perf_counter()
        with span("checkpoint.stage", stage="checkpoint_stage"):
            staged = self.mgr.stage(self._staging_executors())
        REGISTRY.counter("checkpoints_total").inc()
        REGISTRY.gauge("checkpoint_staged_tables").set(len(staged))
        if not self.async_checkpoint:
            with span("checkpoint.commit"):
                durable = self._commit_or_degrade(epoch, staged, tr)
            if durable:
                self.checkpoint_sync_ms.append(
                    (time.perf_counter() - t_staged) * 1e3
                )
                self._on_epoch_durable(epoch)
                self._kick_compactor()
            return
        # hand the staged deltas to the async checkpoint worker
        with self._inflight_lock:
            self._inflight += 1
        self._work_q.append(
            (epoch, staged, t_staged, time.perf_counter(), tr)
        )
        self._ensure_worker()
        self._work_event.set()

    def _ensure_worker(self):
        if self._worker is None or not self._worker.is_alive():
            self._worker = threading.Thread(
                target=self._worker_loop, daemon=True
            )
            self._worker.start()

    def _worker_loop(self):
        while True:
            self._work_event.wait(timeout=0.5)
            self._work_event.clear()
            while self._work_q:
                epoch, staged, t_staged, t_queued, tr = (
                    self._work_q.popleft()
                )
                try:
                    if self._work_err or self._work_abort.is_set():
                        # a prior epoch failed to commit (or recovery is
                        # aborting the lane): committing later epochs
                        # would persist a manifest covering a hole
                        # (silent data loss on recovery) and release
                        # sink output for unpersisted state — drop
                        # everything until the caller recover()s
                        continue
                    # single-worker FIFO queue -> epoch order holds;
                    # store-unavailable failures degrade (spill) rather
                    # than poisoning the lane — the stream keeps going
                    with bind(tr, epoch):
                        TRACER.record(
                            "checkpoint.queue_wait",
                            t_queued,
                            time.perf_counter() - t_queued,
                            wait="queue",
                        )
                        with span("checkpoint.commit"):
                            durable = self._commit_or_degrade(
                                epoch, staged, tr
                            )
                    if durable:
                        self.checkpoint_sync_ms.append(
                            (time.perf_counter() - t_staged) * 1e3
                        )
                        self._on_epoch_durable(epoch)
                        self._kick_compactor()
                except BaseException as e:  # surfaced on main thread
                    self._work_err.append(e)
                finally:
                    with self._inflight_lock:
                        self._inflight -= 1

    def _on_epoch_durable(self, epoch: int) -> None:
        """The epoch's manifest is persisted: release deferred sink
        deliveries (exactly-once: sink output never precedes the
        durability of the state that produced it), and prune the
        partial-recovery replay buffer past the durable frontier.
        Fragments fenced for a deferred partial recovery are EXCLUDED:
        their held output belongs to state that is about to roll back
        and replay — releasing it would double-deliver."""
        for ex in self._staging_executors():
            fn = getattr(ex, "on_epoch_durable", None)
            if fn is not None:
                fn(epoch)
        self._prune_replay(epoch)

    # -- compaction lane (off the commit path) ---------------------------
    def _kick_compactor(self):
        if self.mgr is None:
            return
        if not self.mgr.tables_needing_compaction():
            return
        if self._compactor is None or not self._compactor.is_alive():
            self._compactor = threading.Thread(
                target=self._compactor_loop, daemon=True
            )
            self._compactor.start()
        self._compact_event.set()

    def _compactor_loop(self):
        """Dedicated compaction worker (compactor_runner.rs:62 role):
        full-merges long SST runs without ever blocking the commit lane
        or FLUSH."""
        while True:
            self._compact_event.wait(timeout=0.5)
            self._compact_event.clear()
            # clear idle BEFORE checking pause: recover() sets pause
            # then waits for idle, so the reverse order here closes the
            # window where compaction slips past a just-set pause
            self._compact_idle.clear()
            try:
                if self._compact_pause.is_set():
                    continue
                for table_id in self.mgr.tables_needing_compaction():
                    if self._compact_pause.is_set():
                        break
                    self.mgr.compact_once(table_id, self.mgr.max_committed_epoch)
            except Exception as e:
                # best-effort (next commit re-kicks) but never silent:
                # a persistently failing compaction must be visible
                self.compaction_errors.append(e)
                REGISTRY.counter("compaction_errors_total").inc()
            finally:
                self._compact_idle.set()

    def wait_compaction(self) -> None:
        """Block until no table needs compaction (or compaction is
        failing/paused — a doomed compaction must not hang callers)."""
        while (
            self.mgr is not None
            and self.mgr.tables_needing_compaction()
            and not self.compaction_errors
            and not self._compact_pause.is_set()
            and self._compactor is not None
            and self._compactor.is_alive()
        ):
            self._compact_event.set()
            time.sleep(0.002)
        self._compact_idle.wait()

    def wait_checkpoints(self) -> None:
        """Join the async lane (the FLUSH / sync-epoch analogue).
        Compaction intentionally does NOT block this (it runs on its
        own worker — ADVICE r2: inline compaction stalled FLUSH)."""
        while True:
            with self._inflight_lock:
                if self._inflight == 0:
                    break
            time.sleep(0.002)
        self._raise_worker_error()

    def _raise_worker_error(self):
        if self._work_err:
            raise RuntimeError(
                "async checkpoint failed"
            ) from self._work_err[0]

    def p99_checkpoint_sync_ms(self) -> float:
        """p99 of stage->durable latency (what the reference's <1s
        checkpoint target measures — includes SST build + upload +
        manifest commit, not just staging)."""
        if not self.checkpoint_sync_ms:
            return 0.0
        return float(np.percentile(self.checkpoint_sync_ms, 99))

    # -- recovery --------------------------------------------------------
    def _quiesce(self) -> None:
        """Drain the async commit lane and in-flight worker steps.
        Leaves the abort flag SET — recover() clears it after the
        restore. Idempotent (auto-recovery quiesces before growing
        capacities; recover() quiesces again trivially)."""
        # abort the async lane FIRST: staged epochs still queued refer
        # to pre-recovery state; committing one after the restore would
        # advance the manifest past the epoch we just recovered to
        self._work_abort.set()
        while True:
            with self._inflight_lock:
                if self._inflight == 0:
                    break
            time.sleep(0.002)

    def recover(self, fragments: Optional[Sequence[str]] = None) -> None:
        """Rebuild fragment state from the last committed epoch.

        With ``fragments``, the recovery is FRAGMENT-SCOPED: only the
        named fragments' pipelines rebuild, restore their state tables,
        and replay their buffered inputs — every other fragment's live
        state (and the epoch clock) is untouched. Without it, the full
        stop-the-world restore (today's contract)."""
        if not self.mgr:
            raise RuntimeError("no object store configured")
        # manual recovery mirrors the auto path's capture hygiene
        blackbox.SENTINEL.abort_capture()
        blackbox.SENTINEL.clear_wedge()
        from risingwave_tpu.deviceprof import DEVICEPROF

        DEVICEPROF.on_recovery()
        if fragments is not None:
            scope = set(fragments)
            unknown = scope - set(self.fragments)
            if unknown:
                raise KeyError(f"unknown fragments {sorted(unknown)}")
            # close the scope over subscribers: _replay_fragment discards
            # replay outputs on the assumption every subscriber replays
            # its OWN log — a half-closed manual scope would starve them
            frontier = list(scope)
            while frontier:
                for sub, _side in self._subs.get(frontier.pop(), ()):
                    if sub not in scope:
                        scope.add(sub)
                        frontier.append(sub)
            # same replay-window guard the auto path enforces: replaying
            # a cleared/late-anchored log would silently drop the
            # un-durable window — refuse and point at full recovery
            committed = self.mgr.max_committed_epoch
            with self._replay_lock:
                lost = sorted(
                    n
                    for n in scope
                    if self._replay_floor.get(n, 0) is None
                    or self._replay_floor.get(n, 0)
                    > min(committed, self._replay_covered.get(n, committed))
                )
            if lost:
                raise RuntimeError(
                    f"replay window lost for {lost} (buffer overflow or "
                    "not yet re-anchored at a durable barrier) — a scoped "
                    "recovery would silently drop their un-durable "
                    "window; use a full recover()"
                )
            # an explicit scoped recovery is a manual store probe too
            if self.store_breaker is not None:
                self.store_breaker.force_probe()
            self.wait_checkpoints()
            self._partial_recover(scope, "manual recover(fragments=...)")
            self._pending_partial = None
            self.last_recovery_mode = "partial"
            return
        # an explicit recovery is a manual store probe: let it through
        # an open breaker (its reads settle the breaker either way)
        if self.store_breaker is not None:
            self.store_breaker.force_probe()
        self._quiesce()
        # quiesce compaction: its GC deletes SSTs that recovery's
        # read_table may be about to read
        self._compact_pause.set()
        try:
            self._compact_idle.wait()
            self.mgr.recover(self.executors())
        finally:
            self._compact_pause.clear()
            self._work_abort.clear()
        # degraded spill of rolled-back epochs is stale: recovery lands
        # on the last DURABLE manifest; sources replay the spilled
        # epochs' data, so replaying the spill too would double-apply
        with self._degraded_lock:
            if self._degraded or self._spill.epochs():
                discarded = self._spill.discard_all()
                if self._degraded:
                    EVENT_LOG.record(
                        "degraded_discard", epochs=discarded
                    )
                self._degraded = False
        REGISTRY.gauge("degraded_mode").set(0.0)
        # rolled-back epochs must not leave stale sink batches behind:
        # replay would re-hold the same rows -> duplicate delivery
        for ex in self.executors():
            fn = getattr(ex, "discard_pending", None)
            if fn is not None:
                fn()
        self._work_err.clear()
        # a full restore supersedes any deferred partial recovery and
        # resets the replay window: everything rolls back to the
        # committed epoch and sources replay from their offsets, so the
        # buffered inputs are stale
        self._pending_partial = None
        with self._replay_lock:
            self._replay.clear()
            self._replay_floor.clear()
            self._replay_covered.clear()
        self._epoch = self.mgr.max_committed_epoch
        for p in self.fragments.values():
            p._epoch = self._epoch
        # executors with recovery hooks (e.g. sink log stores dropping
        # rolled-back epochs) learn the recovered frontier
        for ex in self.executors():
            fn = getattr(ex, "on_recover", None)
            if fn is not None:
                fn(self._epoch)
        # stale published snapshots may postdate the restored epoch
        self.arrangements.on_recovery(self._epoch)
        EVENT_LOG.record("recovery", mode="restore", epoch=self._epoch)
