"""Pow2 bucket allocation for padded state buffers.

Capacity transitions of a device-visible buffer follow a
grow-eagerly / shrink-lazily hysteresis over the declared pow2 lattice
(``array/lattice.py``), so steady-state churn can never oscillate
across a bucket boundary.

- :class:`BucketPolicy` / :class:`BucketAllocator` — the capacity
  planner every window-keyed executor routes its ``_maybe_grow`` /
  barrier bookkeeping through.  The allocator's ``lattice`` is exactly
  what the executor declares as ``window_buckets`` in its
  ``trace_contract()`` (analysis/shape_domain.py), so the fusion
  analyzer's static proof and the runtime's actual shape set are the
  same object: total traces <= lattice size, one per bucket, never one
  per shape.
- :func:`padding_fraction` / :func:`padding_stats` — the wasted-lane
  accounting over those buffers.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

from risingwave_tpu.array.lattice import (
    ABS_MAX_CAP,
    DEFAULT_MAX_STEPS,
    lattice_between,
    pow2_at_least,
)
from risingwave_tpu.ops.hash_table import plan_rehash

__all__ = [
    "BucketAllocator",
    "BucketPolicy",
    "needs_plan",
    "padding_fraction",
    "padding_stats",
    "plan_capacity",
]


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


@dataclass(frozen=True)
class BucketPolicy:
    """Hysteresis parameters of one buffer's bucket walk.

    ``grow_at`` is the load factor that triggers eager growth (shared
    with the hash tables' rehash contract); shrink is LAZY: occupancy
    must sit below ``shrink_at * capacity`` for ``patience``
    consecutive barriers before the buffer compacts down — a window
    churning right at a bucket boundary therefore grows once and stays,
    it can never flap."""

    min_cap: int
    max_cap: int
    grow_at: float = 0.5
    shrink_at: float = 0.125
    patience: int = 4

    def __post_init__(self):
        if self.min_cap & (self.min_cap - 1) or self.min_cap <= 0:
            raise ValueError(f"min_cap {self.min_cap} not a power of two")
        if self.max_cap < self.min_cap:
            raise ValueError("max_cap < min_cap")
        if not (0.0 < self.shrink_at < self.grow_at <= 1.0):
            raise ValueError(
                "need 0 < shrink_at < grow_at <= 1 for hysteresis"
            )

    @staticmethod
    def from_capacity(
        capacity: int,
        max_steps: Optional[int] = None,
        grow_at: float = 0.5,
    ) -> "BucketPolicy":
        """The default policy for an executor configured with
        ``capacity``: lattice spans capacity .. capacity << steps
        (``RW_BUCKET_MAX_STEPS`` overrides; shrink floor = the
        configured capacity, honoring the operator's sizing)."""
        steps = (
            max_steps
            if max_steps is not None
            else _env_int("RW_BUCKET_MAX_STEPS", DEFAULT_MAX_STEPS)
        )
        # a configured capacity beyond the allocator bound clamps the
        # LATTICE (never raises: the capacity was legal before this
        # layer existed) — plan() tolerates cap > max_cap, so the
        # buffer simply never grows, and the declared lattice stays
        # satisfiable (no self-inflicted RW-E806)
        lo = min(pow2_at_least(capacity), ABS_MAX_CAP)
        hi = min(lo << max(steps, 0), ABS_MAX_CAP)
        return BucketPolicy(
            min_cap=lo,
            max_cap=max(hi, lo),
            grow_at=grow_at,
            patience=_env_int("RW_BUCKET_SHRINK_PATIENCE", 4),
        )

    def lattice(self) -> Tuple[int, ...]:
        return lattice_between(self.min_cap, self.max_cap)


class BucketAllocator:
    """Capacity planner for one (or one family of) padded state
    buffer(s). The owning executor calls:

    - ``should_plan(cap, bound, incoming)`` — the cheap pre-check its
      ``_maybe_grow`` already does, extended with pending-shrink and
      governor-pin wakeups;
    - ``plan(cap, incoming, claimed, survivors)`` — the
      ``plan_rehash`` replacement: next capacity drawn from the
      lattice (grow eagerly, clamped at ``max_cap``; pinned buffers
      jump back to their high-water bucket), or None;
    - ``note_barrier(cap, claimed)`` — per-barrier occupancy
      bookkeeping driving the lazy-shrink streak;
    - ``pin()`` — the governor hook: shrink disabled, next plan()
      returns the high-water bucket.
    """

    def __init__(self, policy: BucketPolicy):
        self.policy = policy
        self.pinned = False
        self.high_water = policy.min_cap
        self._streak = 0
        self._pending_shrink: Optional[int] = None
        # saturated = demand exceeds the lattice max and a same-cap
        # rebuild cannot relieve it; gates the load-factor trigger so
        # the apply path stops paying a device read + rebuild per
        # chunk (re-checked once per barrier via note_barrier)
        self._saturated = False
        # memory-governor veto surface (runtime/memory_governor.py):
        # when set, grow_gate(cap, new_cap) must approve every grow
        # plan() would return. A refusal latches _veto_hold so the
        # apply path stops re-asking per chunk (same per-chunk-storm
        # reasoning as _saturated); note_barrier re-probes. The veto
        # MUST fire before plan() touches hysteresis state: a vetoed
        # grow that later succeeds applies its _pending_shrink/_streak
        # resets exactly once, at the grow that actually happens —
        # the PR 13 K-stale-pack double-tick class of bug otherwise.
        self.grow_gate = None
        self._veto_hold = False
        self.vetoes = 0

    @property
    def lattice(self) -> Tuple[int, ...]:
        return self.policy.lattice()

    # -- apply-path hooks -------------------------------------------------
    def should_plan(self, cap: int, bound: int, incoming: int) -> bool:
        if (
            not self._saturated
            and not self._veto_hold
            and bound + incoming > cap * self.policy.grow_at
        ):
            return True
        if self.pinned and cap < self.high_water:
            return True
        return (
            self._pending_shrink is not None
            and self._pending_shrink < cap
        )

    def plan(
        self,
        cap: int,
        incoming: int,
        claimed: int,
        survivors: int,
        margin: int = 0,
    ) -> Optional[int]:
        """Next capacity, or None (current bucket still fits). A
        returned value == cap is a pure tombstone compaction (the
        plan_rehash contract). Growth beyond ``max_cap`` clamps: the
        executor's existing overflow latch ("grow capacity") then
        reports genuine overflow at the barrier instead of the device
        re-tracing through unbounded fresh shapes.

        ``margin`` is extra headroom folded into the NEED sizing only
        (never the trigger): executors planning from note-based
        occupancy estimates pass their per-epoch incoming here so
        growth converges in one rebuild instead of re-tripping at the
        next bucket's boundary once the true note lands."""
        p = self.policy
        self.high_water = max(self.high_water, cap)
        if self.pinned and cap < self.high_water:
            # governor pin: jump straight back to the high-water bucket
            self._pending_shrink = None
            return self.high_water
        if claimed + incoming > cap * p.grow_at:
            need = cap
            while survivors + incoming + margin > need * p.grow_at:
                need <<= 1
            new_cap = min(max(need, p.min_cap), max(p.max_cap, cap))
            if new_cap > cap and self.grow_gate is not None:
                # governor veto gates GENUINE growth only (a same-cap
                # tombstone compaction frees memory — always allowed)
                try:
                    allowed = bool(self.grow_gate(cap, new_cap))
                except Exception:  # noqa: BLE001 — a broken gate never wedges
                    allowed = True
                if not allowed:
                    # deferred, not denied: hysteresis state untouched —
                    # the resets below belong to the grow that actually
                    # runs, so a veto/release cycle ticks them once
                    self._veto_hold = True
                    self.vetoes += 1
                    return None
            self._pending_shrink = None
            self._streak = 0
            if new_cap == cap and survivors + incoming > cap * p.grow_at:
                # saturated at the lattice max: a same-capacity rebuild
                # cannot relieve the load (unlike a genuine tombstone
                # compaction, where survivors fit) — stop planning per
                # chunk and let the overflow latch report if the table
                # genuinely fills. note_barrier re-checks each barrier.
                self._saturated = True
                return None
            self.high_water = max(self.high_water, new_cap)
            return new_cap
        t = self._pending_shrink
        if t is not None and not self.pinned:
            self._pending_shrink = None
            self._streak = 0
            # never shrink below what this chunk (or the survivors)
            # need — re-growing next chunk would be the exact
            # oscillation this layer exists to prevent
            while survivors + incoming + margin > t * p.grow_at:
                t <<= 1
            if t < cap:
                return t
        return None

    def bump(self, cap: int) -> Optional[int]:
        """ONE-bucket emergency growth for a mid-epoch overflow guard.

        The guard's host insert bound counts padded chunk CAPACITIES,
        not true inserts — letting ``plan()`` size from it over-grows
        by several buckets and re-compiles every program touching the
        buffer (measured +68%% wall on the join-heavy CPU suites).
        The guard only needs to stay ahead of MAX_PROBE until the next
        barrier's true-note planning, so it doubles once (clamped at
        the lattice max; a genuine faster-than-2x single-epoch blow-up
        still trips the executor's overflow latch, the pre-existing
        contract). Shrink state resets like any growth."""
        p = self.policy
        if cap >= p.max_cap:
            return None
        new_cap = min(cap << 1, p.max_cap)
        self.high_water = max(self.high_water, new_cap)
        self._pending_shrink = None
        self._streak = 0
        return new_cap

    # -- barrier hook -----------------------------------------------------
    def note_barrier(self, cap: int, claimed: int) -> None:
        p = self.policy
        self.high_water = max(self.high_water, cap)
        # saturation and the governor-veto hold are re-evaluated once
        # per barrier (expiry/spill may have freed load), never per chunk
        self._saturated = False
        self._veto_hold = False
        if (
            self.pinned
            or cap <= p.min_cap
            or claimed > cap * p.shrink_at
        ):
            self._streak = 0
            self._pending_shrink = None
            return
        self._streak += 1
        if self._streak >= p.patience:
            target = pow2_at_least(
                max(p.min_cap, int(claimed / p.grow_at) + 1)
            )
            if target < cap:
                self._pending_shrink = target

    # -- governor hook ----------------------------------------------------
    def pin(self) -> int:
        """Disable shrink and freeze the buffer at its high-water
        bucket (applied by the next plan()). Returns the pinned
        capacity."""
        self.pinned = True
        self._pending_shrink = None
        self._streak = 0
        return self.high_water

    def snapshot(self) -> Dict:
        return {
            "lattice": list(self.lattice),
            "pinned": self.pinned,
            "high_water": self.high_water,
            "pending_shrink": self._pending_shrink,
            "saturated": self._saturated,
            "veto_hold": self._veto_hold,
            "vetoes": self.vetoes,
        }


def needs_plan(
    alloc: Optional[BucketAllocator],
    cap: int,
    bound: int,
    incoming: int,
    grow_at: float = 0.5,
) -> bool:
    """The apply-path pre-check shared by every ``_maybe_grow``:
    allocator-driven when bucketed, the legacy load-factor check on
    the unbucketed twin (alloc=None)."""
    if alloc is None:
        return bound + incoming > cap * grow_at
    return alloc.should_plan(cap, bound, incoming)


def plan_capacity(
    alloc: Optional[BucketAllocator],
    cap: int,
    incoming: int,
    claimed: int,
    survivors: int,
    grow_at: float = 0.5,
) -> Optional[int]:
    """``plan_rehash`` with the bucket lattice in the loop; falls back
    to the raw unbounded rehash policy on the unbucketed twin."""
    if alloc is None:
        return plan_rehash(cap, incoming, claimed, survivors, grow_at)
    return alloc.plan(cap, incoming, claimed, survivors)


def padding_fraction(entries) -> float:
    """Weighted wasted-lane fraction over ``(capacity, live,
    weight_bytes)`` triples — the ZERO-device-read twin of
    :func:`padding_stats`, fed from occupancy scalars that already
    rode a packed barrier read (the fused telemetry lane). Weighting
    by state bytes makes the fraction a traffic model: a padded lane
    of a wide table wastes more HBM bandwidth than one of a narrow
    table. Empty/degenerate input -> 0.0 (nothing padded = nothing
    wasted, the padding_stats convention)."""
    num = den = 0.0
    for cap, live, weight in entries:
        cap, weight = int(cap), float(weight)
        if cap <= 0 or weight <= 0.0:
            continue
        num += weight * (1.0 - min(int(live), cap) / cap)
        den += weight
    return round(num / den, 6) if den else 0.0


def padding_stats(executors) -> Dict[str, object]:
    """Wasted-lane accounting over every padded state buffer the given
    executors expose via ``padding_stats()`` (bench/PROFILE surface —
    this READS device occupancy counters; never call it per barrier).
    Returns totals + the worst per-executor fraction."""
    total_lanes = 0
    live_lanes = 0
    per: Dict[str, Dict] = {}
    for ex in executors:
        fn = getattr(ex, "padding_stats", None)
        if fn is None:
            continue
        try:
            st = fn()
        except Exception:  # noqa: BLE001 — accounting must never fault
            continue
        cap, live = int(st.get("capacity", 0)), int(st.get("live", 0))
        if cap <= 0:
            continue
        total_lanes += cap
        live_lanes += live
        name = type(ex).__name__
        agg = per.setdefault(name, {"capacity": 0, "live": 0})
        agg["capacity"] += cap
        agg["live"] += live
    for st in per.values():
        st["wasted_frac"] = round(
            1.0 - st["live"] / max(st["capacity"], 1), 4
        )
    return {
        "capacity_lanes": total_lanes,
        "live_lanes": live_lanes,
        # no padded buffers = nothing wasted (not 100% wasted)
        "wasted_lane_frac": (
            round(1.0 - live_lanes / total_lanes, 4) if total_lanes else 0.0
        ),
        "per_executor": per,
    }
