"""HBM memory governor + overload control: attribution becomes action.

PR 16 gave every barrier a backpressure *verdict* (freshness.py
``attribute_backpressure``: which fragment, how many ms, channel ages)
and PR 13/15 gave state a *planner* (bucketing.BucketAllocator) — but
nothing connected them: sources ingest unboundedly, allocators grow
eagerly with no global ceiling, and a skewed key storm ends in device
OOM instead of controlled lag. This module closes the loop, after the
reference's memory controller (src/compute/src/memory/controller.rs:
an LRU watermark driven by jemalloc stats) and the back-pressured
exchange (permits.rs), rebuilt for the host-pumped TPU model:

- :class:`MemoryGovernor` — the global device-state ledger. Per-table
  footprint from executor ``state_nbytes()`` contracts + the bucketing
  allocator's capacity notes, cross-checked against deviceprof modeled
  bytes and (when the backend exposes it) sampled
  ``Device.memory_stats()``. Enforces ``RW_HBM_BUDGET_BYTES`` (or
  ``RW_HBM_BUDGET_FRAC`` of the sampled device limit) by vetoing
  ``BucketAllocator`` growth that would cross the budget (the
  ``grow_gate`` surface — growth is *deferred*, never denied: the
  allocator re-probes each barrier once spill/lazy-shrink has freed
  room) and by triggering the cold-tier spill the executors already
  expose (``evict_cold`` via ``cold_reader``/``cold_get_rows``)
  above the spill watermark. Lag, never loss — and never OOM.
- :class:`OverloadLadder` — NORMAL -> THROTTLED -> SHEDDING ->
  DEGRADED with hysteresis: escalation is immediate (overload must be
  met now), de-escalation descends ONE rung after a sticky cool-down
  of consecutive calm barriers, so a load flapping at a threshold
  cannot flap the ladder (the same grow-eagerly/shrink-lazily
  discipline the bucket walk uses). Every transition is a structured
  ``overload`` event + ``overload_transitions_total`` counter.
- :class:`AdmissionController` — per-fragment credit windows in
  [0, 1] derived from the ladder rung, governor pressure and the
  barrier's backpressure verdict (the named bottleneck's feeders are
  clamped hardest). ``SourceManager.poll`` multiplies its
  ``max_rows_per_split`` by the credit; credit 0 parks the source at
  its anchored split offsets (a zero-row poll: offsets do not
  advance, exactly-once untouched).

The governor rides ``StreamingRuntime._end_trace`` (both the serial
and the pipelined closer path), is dormant unless armed (a budget via
env/ctor, or ``RW_OVERLOAD_LADDER=1``), runs inside the
``bookkeeping.memory_governor`` span and never faults a barrier.
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

from risingwave_tpu.config import env_float

__all__ = [
    "NORMAL",
    "THROTTLED",
    "SHEDDING",
    "DEGRADED",
    "LADDER",
    "AdmissionController",
    "MemoryGovernor",
    "OverloadLadder",
]

# the degradation ladder, mildest first; gauge value = list index
NORMAL = "NORMAL"
THROTTLED = "THROTTLED"
SHEDDING = "SHEDDING"
DEGRADED = "DEGRADED"
LADDER = (NORMAL, THROTTLED, SHEDDING, DEGRADED)

# rung -> base admission credit (fraction of the configured poll size)
_BASE_CREDIT = {
    NORMAL: 1.0,
    THROTTLED: 0.5,
    SHEDDING: 0.25,
    DEGRADED: 0.0,  # parked at the anchored offsets
}


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _device_memory_stats() -> Optional[Dict]:
    """One guarded ``memory_stats()`` sample from device 0, or None
    (CPU backends and older plugins may not expose it)."""
    try:
        import jax

        dev = jax.local_devices()[0]
        st = dev.memory_stats()
        return st if isinstance(st, dict) else None
    except Exception:  # noqa: BLE001 — sampling is best-effort
        return None


class OverloadLadder:
    """The degradation state machine. ``step(score)`` is called once
    per barrier with the combined pressure score (budget fractions:
    1.0 = at the HBM budget / at the queue-age budget) and returns the
    current rung.

    Escalation: immediate, to the highest rung whose enter threshold
    the score meets (overload is met the barrier it appears).
    De-escalation: one rung at a time, only after ``cooldown``
    CONSECUTIVE barriers below that rung's exit threshold (enter *
    ``exit_margin``) — the sticky cool-down that keeps a boundary-
    riding load from flapping the ladder. ``flaps`` counts
    re-escalations that land within ``cooldown`` barriers of a
    de-escalation (bounded by ``tests/test_overload.py``'s chaos
    storm)."""

    def __init__(
        self,
        throttle_at: Optional[float] = None,
        shed_at: Optional[float] = None,
        degrade_at: Optional[float] = None,
        cooldown: Optional[int] = None,
        exit_margin: float = 0.85,
    ):
        self.throttle_at = (
            throttle_at
            if throttle_at is not None
            else env_float("RW_OVERLOAD_THROTTLE_AT", 0.75)
        )
        self.shed_at = (
            shed_at
            if shed_at is not None
            else env_float("RW_OVERLOAD_SHED_AT", 0.90)
        )
        self.degrade_at = (
            degrade_at
            if degrade_at is not None
            else env_float("RW_OVERLOAD_DEGRADE_AT", 0.98)
        )
        self.cooldown = (
            cooldown
            if cooldown is not None
            else _env_int("RW_OVERLOAD_COOLDOWN_BARRIERS", 8)
        )
        self.exit_margin = exit_margin
        self.state = NORMAL
        self.flaps = 0
        self._calm = 0  # consecutive barriers below the exit threshold
        self._since_descent = 10**9  # barriers since the last de-escalation
        self.last_score = 0.0
        self.transitions: List[Dict] = []

    def _enter_threshold(self, state: str) -> float:
        return {
            THROTTLED: self.throttle_at,
            SHEDDING: self.shed_at,
            DEGRADED: self.degrade_at,
        }.get(state, 0.0)

    def _target(self, score: float) -> str:
        if score >= self.degrade_at:
            return DEGRADED
        if score >= self.shed_at:
            return SHEDDING
        if score >= self.throttle_at:
            return THROTTLED
        return NORMAL

    def step(self, score: float, epoch: int = 0) -> str:
        self.last_score = score
        self._since_descent += 1
        target = self._target(score)
        cur_i, tgt_i = LADDER.index(self.state), LADDER.index(target)
        if tgt_i > cur_i:
            # escalate NOW, possibly several rungs at once
            if self._since_descent <= self.cooldown:
                self.flaps += 1
            self._record(target, score, epoch)
            self._calm = 0
        elif tgt_i < cur_i:
            # below this rung's exit threshold? count calm barriers,
            # then descend exactly one rung
            exit_at = self._enter_threshold(self.state) * self.exit_margin
            if score < exit_at:
                self._calm += 1
                if self._calm >= self.cooldown:
                    self._record(LADDER[cur_i - 1], score, epoch)
                    self._calm = 0
                    self._since_descent = 0
            else:
                self._calm = 0
        else:
            self._calm = 0
        return self.state

    def _record(self, new: str, score: float, epoch: int) -> None:
        from risingwave_tpu.event_log import EVENT_LOG
        from risingwave_tpu.metrics import REGISTRY

        old, self.state = self.state, new
        ev = {
            "ts": time.time(),
            "epoch": epoch,
            "from": old,
            "to": new,
            "score": round(score, 4),
        }
        self.transitions.append(ev)
        del self.transitions[:-256]
        REGISTRY.counter("overload_transitions_total").inc(
            **{"from": old, "to": new}
        )
        REGISTRY.gauge("overload_state").set(float(LADDER.index(new)))
        EVENT_LOG.record(
            "overload",
            epoch=epoch,
            mode=new,
            prev=old,
            score=round(score, 4),
        )

    def snapshot(self) -> Dict:
        return {
            "state": self.state,
            "score": round(self.last_score, 4),
            "flaps": self.flaps,
            "cooldown": self.cooldown,
            "transitions": list(self.transitions[-32:]),
        }


class AdmissionController:
    """Per-fragment credit windows for source admission.

    ``credit(fragment)`` in [0, 1] multiplies the source's configured
    poll size (``SourceManager.poll``); ``rederive`` is called by the
    governor each barrier with the ladder rung, the memory pressure
    and the backpressure verdict detail. Credits move toward their
    target multiplicatively (halve on the way down, recover by at
    most ``recover_step`` per barrier on the way up) — the per-
    fragment hysteresis that damps throttle flapping below the ladder
    transitions themselves. A fragment named as the barrier's
    bottleneck is clamped one extra halving."""

    def __init__(self, recover_step: float = 0.25, floor: float = 0.0):
        self.credits: Dict[str, float] = {}
        self.recover_step = recover_step
        self.floor = floor
        self.parked_polls = 0
        self.rederives = 0

    def credit(self, fragment: Optional[str]) -> float:
        if not self.credits:
            return 1.0
        if fragment is None or fragment not in self.credits:
            # an unmapped source is governed by the tightest window
            return min(self.credits.values())
        return self.credits[fragment]

    def admit_rows(self, fragment: Optional[str], requested: int) -> int:
        """Clamp one poll's ``max_rows_per_split``; 0 = parked (the
        caller performs a zero-row poll so offsets stay anchored)."""
        c = self.credit(fragment)
        rows = int(requested * c)
        if rows <= 0 and c <= 0.0:
            self.parked_polls += 1
            return 0
        return max(rows, 1)

    def rederive(
        self,
        state: str,
        pressure: float,
        detail: Optional[Dict[str, Dict]] = None,
        bottleneck: Optional[str] = None,
        fragments=(),
    ) -> None:
        self.rederives += 1
        base = _BASE_CREDIT.get(state, 1.0)
        names = set(fragments) | set(detail or ()) | set(self.credits)
        for name in names:
            target = base
            if bottleneck is not None and name == bottleneck and target > 0:
                target *= 0.5  # the named bottleneck's feed halves again
            cur = self.credits.get(name, 1.0)
            if target <= 0.0:
                # DEGRADED parks NOW: the emergency rung anchors the
                # source at its split offsets (credit exactly 0 — a
                # zero-row poll), it does not trickle toward zero
                nxt = 0.0
            elif target < cur:
                # clamp fast: at least halve toward the target now
                nxt = max(target, cur * 0.5)
            else:
                # recover slowly: bounded step per barrier
                nxt = min(target, cur + self.recover_step)
            self.credits[name] = max(self.floor, min(1.0, round(nxt, 4)))

    def reset(self) -> None:
        self.credits.clear()

    def snapshot(self) -> Dict:
        return {
            "credits": dict(self.credits),
            "parked_polls": self.parked_polls,
            "rederives": self.rederives,
        }


class MemoryGovernor:
    """Global device-state ledger + the control actions above it.

    Armed when a budget resolves (``budget_bytes`` ctor arg,
    ``RW_HBM_BUDGET_BYTES``, or ``RW_HBM_BUDGET_FRAC`` of the sampled
    device ``bytes_limit``) or when ``RW_OVERLOAD_LADDER=1`` asks for
    queue-pressure-only laddering; otherwise ``observe_barrier`` is a
    single attribute check and NOTHING is gated (tier-1 behavior
    unchanged). One instance per runtime, like ShapeGovernor."""

    def __init__(self, budget_bytes: Optional[int] = None):
        env_b = os.environ.get("RW_HBM_BUDGET_BYTES")
        if budget_bytes is None and env_b:
            try:
                budget_bytes = int(env_b)
            except ValueError:
                budget_bytes = None
        if budget_bytes is None and os.environ.get("RW_HBM_BUDGET_FRAC"):
            st = _device_memory_stats()
            limit = (st or {}).get("bytes_limit")
            if limit:
                budget_bytes = int(
                    env_float("RW_HBM_BUDGET_FRAC", 0.8) * limit
                )
        self.budget_bytes = budget_bytes
        self.enabled = budget_bytes is not None or os.environ.get(
            "RW_OVERLOAD_LADDER", ""
        ).strip().lower() in ("1", "on", "true")
        # spill watermark: relieve (cold-tier spill) above this budget
        # fraction, BEFORE the hard veto wall at 1.0
        self.spill_at = env_float("RW_HBM_SPILL_AT", 0.85)
        # queue-age budget for the pressure score's second component
        self.queue_ms_budget = env_float("RW_OVERLOAD_QUEUE_MS", 2000.0)
        self.sample_every = max(1, _env_int("RW_HBM_SAMPLE_EVERY", 16))
        self.ladder = OverloadLadder()
        self.admission = AdmissionController()
        # ledger state (rebuilt per barrier while armed)
        self.ledger_total = 0
        self.ledger_high = 0  # high-water across barriers (pre-relief)
        self._ledger_prev = 0  # previous barrier's pre-relief ledger
        self._flat_streak = 0  # consecutive barriers with a flat ledger
        # flat barriers required before a raised ladder treats a flat
        # ledger as "storm over" and spills down to the exit floor
        self.relief_patience = self.ladder.cooldown + 1
        self.modeled_total = 0
        self.sampled_bytes: Optional[int] = None
        self.sampled_limit: Optional[int] = None
        self._tables: List[Dict] = []
        self._barriers = 0
        self.vetoes = 0
        self.spills = 0
        self._relief_wanted = False
        self._gated: set = set()
        # DEGRADED bookkeeping: original fused depths + whether WE
        # paused compaction (never clear a pause the store-degraded
        # path owns)
        self._saved_depths: Dict[int, int] = {}
        self._depth_owners: List = []
        self._compact_paused = False

    # -- the per-barrier hook (rides _end_trace) -------------------------
    def observe_barrier(self, runtime, tr=None) -> None:
        if not self.enabled:
            return
        try:
            self._observe(runtime, tr)
        except Exception:  # noqa: BLE001 — governance never faults a barrier
            pass

    def _observe(self, runtime, tr) -> None:
        self._barriers += 1
        self._rebuild_ledger(runtime)
        self.ledger_high = max(self.ledger_high, self.ledger_total)
        if (
            self.budget_bytes is not None
            and self._barriers % self.sample_every == 0
        ):
            st = _device_memory_stats()
            if st is not None:
                self.sampled_bytes = st.get("bytes_in_use")
                self.sampled_limit = st.get("bytes_limit")
        # score the pressure that EXISTED this barrier, then relieve:
        # the ladder must see the spike relief is responding to (else
        # a successful spill hides every overload from the ladder);
        # the post-relief ledger is what next barrier's gates enforce
        score = self._pressure_score(tr)
        # relief watermark: the steady-state spill line — except in the
        # DESCENT REGION, where spill keeps firing until memory clears
        # the NORMAL-exit floor (residual durable state would otherwise
        # hover between the exit floor and the spill line forever and
        # pin the ladder raised).  The ladder is descending when either
        #   (a) pressure has fallen below the current rung's own entry
        #       threshold (post-peak: the spike that raised the rung has
        #       been relieved), or
        #   (b) the ledger has been flat for `relief_patience` barriers
        #       (the storm has ceased; residual state is all that's
        #       left).  A single quiet barrier mid-storm is NOT enough —
        #       capacity-based footprints go flat between growth
        #       boundaries, and opening the floor there would let relief
        #       pre-empt escalation.
        if self.ledger_total > self._ledger_prev:
            self._flat_streak = 0
        else:
            self._flat_streak += 1
        self._ledger_prev = self.ledger_total
        relief_at = self.spill_at
        if self.ladder.state != NORMAL and (
            score < self.ladder._enter_threshold(self.ladder.state)
            or self._flat_streak >= self.relief_patience
        ):
            relief_at = min(
                relief_at,
                self.ladder.throttle_at * self.ladder.exit_margin,
            )
        if (
            self.budget_bytes is not None
            and self.ledger_total > relief_at * self.budget_bytes
        ) or self._relief_wanted:
            self._relief_wanted = False
            self._relieve(runtime)
            self._rebuild_ledger(runtime)
        prev = self.ladder.state
        state = self.ladder.step(score, epoch=getattr(tr, "epoch", 0))
        if state != prev:
            self._apply_state(runtime, prev, state)
        elif state == DEGRADED:
            # a recovery mid-DEGRADED rebuilds executors at configured
            # depth: re-assert depth=1 on the barrier clock (idempotent)
            self._enter_degraded(runtime)
        detail = getattr(tr, "backpressure", None) if tr is not None else None
        if state != NORMAL or self.admission.credits:
            self.admission.rederive(
                state,
                score,
                detail=detail,
                bottleneck=(
                    getattr(tr, "backpressure_fragment", None)
                    if tr is not None
                    else None
                ),
                fragments=getattr(runtime, "fragments", {}).keys(),
            )
        if tr is not None:
            tr.overload_state = state
        from risingwave_tpu.metrics import REGISTRY

        REGISTRY.gauge("memory_ledger_bytes").set(float(self.ledger_total))
        if self.budget_bytes:
            REGISTRY.gauge("memory_headroom_bytes").set(
                float(self.budget_bytes - self.ledger_total)
            )

    # -- ledger ----------------------------------------------------------
    def _rebuild_ledger(self, runtime) -> None:
        """Walk the executors' accounting contracts into per-table
        rows. Host metadata only (``.nbytes`` + allocator snapshots —
        no device reads, no flushes). Also (re)attaches grow gates:
        recovery rebuilds executors with fresh allocators, so
        attachment must self-heal on the barrier clock."""
        tables: List[Dict] = []
        total = 0
        gate_on = self.budget_bytes is not None
        for ex in runtime.executors():
            nb = None
            fn = getattr(ex, "state_nbytes", None)
            if fn is not None:
                try:
                    nb = int(fn())
                except Exception:  # noqa: BLE001
                    nb = None
            allocs = self._allocators(ex)
            if gate_on:
                for alloc in allocs:
                    if id(alloc) not in self._gated or alloc.grow_gate is None:
                        self._attach_gate(ex, alloc)
            if nb is None and not allocs:
                continue
            # per-shard ledger breakdown (ISSUE 18): sharded executors
            # expose state_nbytes_per_shard() — the mesh rw_memory rows
            # and hot-shard forensics read it from here, not the device
            shards = None
            sfn = getattr(ex, "state_nbytes_per_shard", None)
            if sfn is not None:
                try:
                    shards = [int(v) for v in sfn()]
                except Exception:  # noqa: BLE001
                    shards = None
            tables.append(
                {
                    "table_id": str(getattr(ex, "table_id", "")) or "-",
                    "executor": type(ex).__name__,
                    "ledger_bytes": nb or 0,
                    "high_water": max(
                        (a.high_water for a in allocs), default=0
                    ),
                    "pinned": any(a.pinned for a in allocs),
                    "vetoes": sum(a.vetoes for a in allocs),
                    "saturated": any(a._saturated for a in allocs),
                    "shards": shards,
                }
            )
            total += nb or 0
        self._tables = tables
        self.ledger_total = total
        # deviceprof modeled bytes: what the COMPILED programs say they
        # touch per barrier (a traffic model, not a residency model —
        # the reconciliation column, never the enforcement input)
        try:
            from risingwave_tpu.deviceprof import DEVICEPROF

            self.modeled_total = sum(
                int(f.get("modeled_bytes") or 0)
                for f in DEVICEPROF.fragments.values()
            )
        except Exception:  # noqa: BLE001
            self.modeled_total = 0

    @staticmethod
    def _allocators(ex) -> List:
        b = getattr(ex, "_buckets", None)
        if b is None:
            return []
        if isinstance(b, dict):
            return [a for a in b.values() if a is not None]
        return [b]

    def _attach_gate(self, ex, alloc) -> None:
        gov = self

        def gate(cap: int, new_cap: int, _ex=ex) -> bool:
            nb = 0
            fn = getattr(_ex, "state_nbytes", None)
            if fn is not None:
                try:
                    nb = int(fn())
                except Exception:  # noqa: BLE001
                    nb = 0
            per_slot = (nb / cap) if (nb and cap) else 8.0
            return gov.authorize_grow(
                str(getattr(_ex, "table_id", type(_ex).__name__)),
                cap,
                new_cap,
                per_slot,
            )

        alloc.grow_gate = gate
        self._gated.add(id(alloc))

    def authorize_grow(
        self, table_id: str, cap: int, new_cap: int, per_slot: float
    ) -> bool:
        """The ``BucketAllocator.grow_gate`` contract: may this buffer
        grow cap -> new_cap right now? Deferral, not denial — the
        allocator's ``_veto_hold`` re-probes next barrier, after spill
        and lazy-shrink have had a chance to free room."""
        if self.budget_bytes is None:
            return True
        projected = self.ledger_total + int((new_cap - cap) * per_slot)
        if projected <= self.budget_bytes:
            # optimistically charge the grow so several same-barrier
            # grows cannot each claim the same headroom
            self.ledger_total = projected
            return True
        self.vetoes += 1
        self._relief_wanted = True
        from risingwave_tpu.event_log import EVENT_LOG
        from risingwave_tpu.metrics import REGISTRY

        REGISTRY.counter("memory_governor_vetoes_total").inc()
        EVENT_LOG.record(
            "memory_governor",
            action="veto_grow",
            table_id=table_id,
            cap=cap,
            new_cap=new_cap,
            projected=projected,
            budget=self.budget_bytes,
        )
        return False

    def _relieve(self, runtime) -> None:
        """Cold-tier spill (the `_enforce_memory_budget` discipline):
        join the async commit lane so eviction never races durability,
        then evict durable-cold groups on every executor wired to the
        cold tier. Frees OCCUPANCY now; capacity follows via the
        allocator's lazy shrink."""
        evicted = 0
        try:
            runtime.wait_checkpoints()
            for ex in runtime.executors():
                fn = getattr(ex, "evict_cold", None)
                has_reader = (
                    getattr(ex, "cold_reader", None) is not None
                    or getattr(ex, "cold_get_rows", None) is not None
                )
                if fn is not None and has_reader:
                    evicted += fn()
        except Exception:  # noqa: BLE001 — relief is best-effort
            pass
        self.spills += 1
        from risingwave_tpu.event_log import EVENT_LOG
        from risingwave_tpu.metrics import REGISTRY

        REGISTRY.counter("memory_governor_spills_total").inc()
        if evicted:
            REGISTRY.counter("cold_evictions_total").inc(evicted)
        EVENT_LOG.record(
            "memory_governor",
            action="spill",
            evicted=evicted,
            ledger=self.ledger_total,
            budget=self.budget_bytes,
        )

    # -- pressure + ladder actions ---------------------------------------
    def _pressure_score(self, tr) -> float:
        mem = (
            self.ledger_total / self.budget_bytes
            if self.budget_bytes
            else 0.0
        )
        queue = 0.0
        if tr is not None and self.queue_ms_budget > 0:
            ages = [
                d.get("oldest_age_ms") or 0.0
                for d in (getattr(tr, "backpressure", None) or {}).values()
            ]
            if ages:
                # normalized so queue age AT budget lands on the
                # DEGRADED threshold, same scale as the memory axis
                queue = (
                    max(ages) / self.queue_ms_budget
                ) * self.ladder.degrade_at
        return max(mem, queue)

    def _apply_state(self, runtime, old: str, new: str) -> None:
        old_i, new_i = LADDER.index(old), LADDER.index(new)
        shed_i, deg_i = LADDER.index(SHEDDING), LADDER.index(DEGRADED)
        reg = getattr(runtime, "arrangements", None)
        if reg is not None:
            # SHEDDING: attached-MV eager materialization pauses —
            # publish becomes pointer-swap-only; readers fall back to
            # the lock path and demand re-latches after recovery
            reg.shed_eager = new_i >= shed_i
        if new_i >= deg_i and old_i < deg_i:
            self._enter_degraded(runtime)
        elif new_i < deg_i and old_i >= deg_i:
            self._exit_degraded(runtime)

    def _enter_degraded(self, runtime) -> None:
        # pipeline depth -> 1: each fused executor drains its pending
        # K-window packs on the next finish_barrier, then runs barrier-
        # synchronous (remember originals for the recovery path).
        # Idempotent on purpose: a recovery mid-DEGRADED rebuilds
        # executors at their configured depth, so the per-barrier
        # re-assert must reduce the NEW ones without forgetting the
        # saved depths of the already-reduced survivors.
        for ex in runtime.executors():
            d = getattr(ex, "depth", None)
            if isinstance(d, int) and d > 1:
                self._saved_depths[id(ex)] = d
                self._depth_owners.append(ex)
                ex.depth = 1
        # defer compaction (reuse the store-degraded pause latch, but
        # remember that WE set it: never clear the store path's pause)
        pause = getattr(runtime, "_compact_pause", None)
        if pause is not None and not pause.is_set():
            pause.set()
            self._compact_paused = True

    def _exit_degraded(self, runtime) -> None:
        for ex in self._depth_owners:
            saved = self._saved_depths.get(id(ex))
            if saved is not None and getattr(ex, "depth", None) == 1:
                ex.depth = saved
        self._saved_depths.clear()
        self._depth_owners = []
        if self._compact_paused:
            self._compact_paused = False
            if not getattr(runtime, "_degraded", False):
                pause = getattr(runtime, "_compact_pause", None)
                if pause is not None:
                    pause.clear()

    # -- introspection ---------------------------------------------------
    def ledger_snapshot(self) -> List[Dict]:
        """Per-table rows for ``rw_memory`` (copies)."""
        return [dict(t) for t in self._tables]

    def snapshot(self) -> Dict:
        return {
            "enabled": self.enabled,
            "budget_bytes": self.budget_bytes,
            "ledger_bytes": self.ledger_total,
            "ledger_high_bytes": self.ledger_high,
            "modeled_bytes": self.modeled_total,
            "sampled_bytes": self.sampled_bytes,
            "sampled_limit": self.sampled_limit,
            "headroom_bytes": (
                self.budget_bytes - self.ledger_total
                if self.budget_bytes is not None
                else None
            ),
            "vetoes": self.vetoes,
            "spills": self.spills,
            "barriers": self._barriers,
            "ladder": self.ladder.snapshot(),
            "admission": self.admission.snapshot(),
        }
