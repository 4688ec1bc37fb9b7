"""Shape-stability layer: pow2 bucket allocation + the recompile-storm
governor.

XLA compiles one program per abstract input signature, and on the
TPU one cold compile costs minutes — so a state buffer whose
capacity wanders freely re-traces every fused program that touches it
until the device queue deadlocks (the q7 wedge, RW-E803; BENCH_TPU_2/3
"device wedged; stopping").  The fix is the fixed-capacity
region-padded state model (PAPERS.md, "Streaming Computations with
Region-Based State on SIMD Architectures"): every device-visible
shape is drawn from a small DECLARED pow2 lattice, buffers are padded
to their bucket with validity masks, and capacity transitions follow a
grow-eagerly / shrink-lazily hysteresis so steady-state churn can
never oscillate across a bucket boundary.

Three layers live here:

- :class:`BucketPolicy` / :class:`BucketAllocator` — the capacity
  planner every window-keyed executor routes its ``_maybe_grow`` /
  barrier bookkeeping through.  The allocator's ``lattice`` is exactly
  what the executor declares as ``window_buckets`` in its
  ``trace_contract()`` (analysis/shape_domain.py), so the fusion
  analyzer's static proof and the runtime's actual shape set are the
  same object: total traces <= lattice size, one per bucket, never one
  per shape.
- emission bucketing helpers (:func:`emission_bucket`) — host-diff
  executors (dynamic filter rv flips, plain/retractable TopN) used to
  emit ``max(2, n)``-sized chunks, minting a fresh downstream program
  per distinct delta count; padding the emission to a pow2 bucket with
  masked lanes closes that set too.
- :class:`ShapeGovernor` — the runtime back-stop for when stability is
  violated anyway: per-barrier ``SignatureWatch`` hazard deltas feed a
  budget (``RW_FUSION_RECOMPILE_BUDGET``); exceeding it pins the
  offending executor to its max (high-water) bucket — shrink disabled,
  capacity immediately restored to the largest bucket it ever used —
  with a ``shape_governor`` event + metric, instead of letting the
  re-trace storm pile onto the device.  A SLOW device heartbeat
  (blackbox.DeviceSentinel) drops the budget to zero: the first
  hazard on a struggling device throttles proactively, before WEDGED.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

__all__ = [
    "BucketAllocator",
    "BucketPolicy",
    "ShapeGovernor",
    "emission_bucket",
    "flush_lattice",
    "flush_lattice_pad",
    "flush_pad",
    "flush_pad_schedule",
    "touched_lattice",
    "lattice_between",
    "needs_plan",
    "padding_fraction",
    "padding_stats",
    "plan_capacity",
    "pow2_at_least",
    "push_lattice",
    "validate_lattice",
    "delta_blocks",
    "prefix_pad",
]

# lattice span above the configured capacity: initial_cap << STEPS is
# the largest bucket growth may reach before the existing overflow
# latches ("grow capacity") fire. 8 doublings = 256x headroom, and a
# <= 9-entry lattice bounds worst-case traces per kernel.
DEFAULT_MAX_STEPS = 8
# a declared lattice may never exceed this capacity (2^26 slots of one
# int64 lane = 512 MiB: past any sane single-buffer HBM budget)
ABS_MAX_CAP = 1 << 26


def pow2_at_least(n: int) -> int:
    """Smallest power of two >= max(n, 1)."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


def lattice_between(lo: int, hi: int) -> Tuple[int, ...]:
    """All pow2 capacities in [lo, hi] (lo/hi rounded up to pow2)."""
    lo = pow2_at_least(lo)
    hi = max(pow2_at_least(hi), lo)
    out = []
    c = lo
    while c <= hi:
        out.append(c)
        c <<= 1
    return tuple(out)


def emission_bucket(n: int, floor: int = 2) -> int:
    """Pow2 emission capacity for an n-row host-built delta chunk.
    Downstream programs then see at most log2(max_delta) distinct
    shapes instead of one per distinct count."""
    return pow2_at_least(max(int(n), floor))


# The delta lattice: what a barrier moves between device and host (the
# rows a checkpoint stages, the live prefix of a chunk a host-map MV
# pulls) follows the rows the epoch changed, which no two epochs share.
# Padding those to the next power of two compiled one eager program per
# size the first time an epoch's count crossed one (PERF.md 6, R-m3).
# Staged rows go in pieces of two sizes instead, SMALL lanes or whole
# blocks of BLOCK lanes, and a chunk of up to PREFIX_WHOLE lanes is
# copied whole (half a megabyte a column at most). A program then exists
# per size, not per count: once an epoch has run, no later epoch of the
# same chunk shapes compiles.
DELTA_SMALL = 256
DELTA_BLOCK = 4096
PREFIX_WHOLE = 1 << 16


def delta_blocks(n: int) -> Tuple[int, int]:
    """(lanes a transfer, transfers) for ``n`` rows off the device."""
    if n <= DELTA_SMALL:
        return DELTA_SMALL, 1
    return DELTA_BLOCK, -(-int(n) // DELTA_BLOCK)


# the ranks one selection program of a checkpoint's changed slots ranges
# over (storage/state_table.py::_select): a block, or SELECT_SPAN. As
# the barrier's thread sees it on the chip, dispatch to result, one
# costs 1.1 ms at a block, 1.6 at 16,384 ranks and 3.0 at 65,536, four
# back to back 3.3 / 4.1 / 7.2 (most of it the round trip: the device's
# own clock reads a sixth of a millisecond for the classification of
# 2^23 lanes); compiling one costs 0.3 / 0.5 / 0.9 s, a capacity of a
# session's tables, when its view is created (PERF.md 6, PR 39)
SELECT_SPAN = 1 << 14


def select_spans(n: int) -> Tuple[int, int]:
    """(ranks a selection program, programs) for ``n`` changed slots: one
    block where that holds them, else as many spans as do."""
    if n <= DELTA_BLOCK:
        return DELTA_BLOCK, 1
    return SELECT_SPAN, -(-int(n) // SELECT_SPAN)


def prefix_pad(k: int, capacity: int) -> int:
    """Lanes to copy of a chunk whose live rows lie in its first ``k``
    lanes: all of them, or for a chunk past PREFIX_WHOLE lanes a power
    of two of blocks."""
    if capacity <= PREFIX_WHOLE:
        return capacity
    return min(capacity, pow2_at_least(max(k, DELTA_BLOCK)))


FLUSH_SMALL = 256


def flush_lattice(out_cap: int) -> Tuple[int, ...]:
    """The interpreted flush's declared chunk sizes (PR 30): 256 lanes
    (the empty and the near-empty barrier), a quarter of the full size,
    and the full ``2 * out_cap`` — 256 / 16,384 / 65,536 at ``out_cap``
    2^15. It is what ``HashAggExecutor`` declares as ``emission_caps``
    / ``window_buckets``, and every size of it is compiled when a
    graph-mode view is created (the actor's ``warm_flush_lattice``), so
    a size first met inside a stream opens no compile.

    One x4 step down from the full size, not the whole ladder to 256
    (the issue's 1,024 and 4,096): the chip priced a declared size at
    about 2.3 s of every start of a q5-like view (some eleven programs,
    traced, lowered and loaded, the sub-second ones compiled again:
    PERF.md 6, PR 30) against a bound of a quarter of a 34 s start,
    and of one extra size the quarter wastes least: at most 4x padding
    from 2,049 groups up, and under that the steps behind a
    16,384-lane chunk cost a fraction of what the full one's did."""
    full = 2 * int(out_cap)
    small = min(FLUSH_SMALL, full)
    return tuple(sorted({small, max(small, full // 4), full}))


def flush_lattice_pad(out_cap: int, n_take: int) -> int:
    """Lanes the interpreted slicer (hash_agg._delta_to_chunk) cuts a
    flush round's delta to: the smallest size of ``flush_lattice`` that
    holds the round's ``2 * n_take`` head lanes (``agg_ops.flush``
    interleaves (old, new) rows at the front). ``n_take`` is the exact
    count the round's status read brings to the host anyway; a round
    that overflowed took ``out_cap`` groups and so the full size."""
    need = 2 * int(n_take)
    return next(s for s in flush_lattice(out_cap) if s >= need)


# the steps' list an aggregate's flush ranges over (PR 34): the buffer's
# lanes, and the shortest declared walk
TOUCHED_MAX = 1 << 18
TOUCHED_SMALL = 1 << 14


def touched_lattice(capacity: int) -> Tuple[int, ...]:
    """The declared lengths of the list of touched slots an aggregate's
    flush ranges over (``ops/agg.flush``'s ``walk``): x4 steps from
    16,384 to 262,144 lanes, none longer than the table, since a list
    as long as the table has nothing over walking the table. A flush
    takes the shortest that holds the lanes the epoch's steps ranged
    over (a lane a row of their batches, 32,768 to 163,840 an epoch in
    the benchmark's cells), and one program a length is compiled when
    the view is created (``HashAggExecutor.warm_emissions``); an epoch
    of more lanes than the longest walks the table.

    x4 and not x2, for ``flush_lattice``'s reason; what a length costs a
    flush is a sort of its lanes twice and one gather of them (PERF.md
    6, PR 34)."""
    return tuple(
        sorted({min(s, int(capacity)) for s in (
            TOUCHED_SMALL, TOUCHED_SMALL * 4, TOUCHED_MAX
        )})
    )


# the narrowest chunk ``push_lattice`` cuts to: under it a per-chunk
# step costs what it costs at any width (PERF.md 6, PR 32), and the
# few-row chunks of an INSERT keep the one shape they have
PUSH_SMALL = 256


def push_lattice(capacity: int) -> Tuple[int, ...]:
    """The declared widths of a host-built chunk of ``capacity`` lanes
    on its way into a fragment of per-chunk steps (PR 32): its own
    capacity and one x4 step below it — 2,048 / 8,192 for a chunk built
    at 8,192. ``StreamingRuntime.push`` cuts the chunk to the smallest
    of them that holds its rows, the fragments that take such chunks
    declare it (``Executor.push_widths``), and every size is compiled
    before a stream meets it, so a size first met compiles nothing.

    One step down and no ladder, for ``flush_lattice``'s reason: a
    declared size is one more set of every program of the chain, paid
    at every start. A capacity that is no power of two, or whose
    quarter falls under ``PUSH_SMALL``, is its own whole lattice."""
    capacity = int(capacity)
    small = capacity // 4
    if capacity & (capacity - 1) or small < PUSH_SMALL:
        return (capacity,)
    return (small, capacity)


def flush_pad(out_cap: int, emitted_bound: int) -> int:
    """The FUSED barrier programs' flush pad: one delta chunk's
    capacity, quantized to exactly TWO buckets (small | full) from a
    BOUND on its emitted rows. The fused single-input program and the
    fused two-input join programs draw their pads from this pair
    (``flush_pad_schedule``; fused_step's single-input schedule spells
    the same rule out), because they know only the host dirty bound,
    which is too loose to pick a small size, and bake every round's
    pad into one executable: each extra size would multiply those.

    The interpreted slicer knows the exact count and follows
    ``flush_lattice`` instead (PR 30). The two used to share this pair
    so that "the downstream compile set cannot drift apart between
    paths"; a fragment is either fused or interpreted, so the two
    compile sets were never shared, and the separation is deliberate
    (tests/test_shape_stability.py pins both)."""
    full = 2 * int(out_cap)
    small = min(FLUSH_SMALL, full)
    return small if 2 * int(emitted_bound) <= small else full


def flush_pad_schedule(
    dirty_bound: int, capacity: int, out_cap: int
) -> Tuple[int, ...]:
    """Per-round flush pads for one barrier, from the HOST dirty bound
    (zero device reads): round r drains up to ``out_cap`` dirty
    groups, so its emitted-rows bound is what remains of the clamped
    dirty bound. Always at least one round (a trailing over-estimate
    emits an all-invalid chunk — masked lanes, a no-op downstream)."""
    out_cap = int(out_cap)
    bound = min(int(dirty_bound), int(capacity))
    rounds = max(1, -(-bound // out_cap))
    return tuple(
        flush_pad(out_cap, min(max(bound - r * out_cap, 0), out_cap))
        for r in range(rounds)
    )


def validate_lattice(buckets) -> Optional[str]:
    """Why the bucketing layer cannot satisfy a declared
    ``window_buckets`` lattice, or None when it can (RW-E806's
    predicate). Satisfiable = non-empty, all power-of-two ints,
    strictly increasing, and within the absolute allocator bound."""
    try:
        caps = tuple(int(b) for b in buckets)
    except (TypeError, ValueError):
        return f"lattice is not a capacity sequence: {buckets!r}"
    if not caps:
        return "lattice is empty"
    for b in caps:
        if b <= 0 or b & (b - 1):
            return f"capacity {b} is not a power of two"
        if b > ABS_MAX_CAP:
            return (
                f"capacity {b} exceeds the allocator bound {ABS_MAX_CAP}"
            )
    if any(b >= c for b, c in zip(caps, caps[1:])):
        return f"lattice is not strictly increasing: {caps}"
    return None


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
        from risingwave_tpu.ops.hash_table import plan_rehash

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


# ---------------------------------------------------------------------------
# recompile-storm governor
# ---------------------------------------------------------------------------


class ShapeGovernor:
    """Degrade gracefully instead of wedging when shape stability is
    violated at runtime anyway (a workload the static lattice proof
    did not anticipate, an unbucketed third-party executor, ...).

    Fed per barrier from :data:`analysis.jax_sanitizer.SIGNATURES`
    hazard deltas (one hazard = one post-warmup novel abstract input
    signature = one future re-trace). Cumulative hazards per executor
    CLASS above ``RW_FUSION_RECOMPILE_BUDGET`` pin every instance of
    that class to its max bucket via ``pin_max_bucket()``; while the
    device sentinel reports SLOW the budget is zero (first hazard
    throttles — proactive, before the heartbeat goes WEDGED). Each
    action lands in the meta event log (``shape_governor``) and in
    ``shape_governor_actions_total{executor,action,reason}``."""

    def __init__(
        self,
        budget: Optional[int] = None,
        enabled: Optional[bool] = None,
    ):
        if enabled is None:
            enabled = os.environ.get(
                "RW_SHAPE_GOVERNOR", "1"
            ).strip().lower() not in ("0", "off", "false")
        self.enabled = enabled
        self._budget = budget
        self.hazards: Dict[str, int] = {}
        self.pinned: Dict[str, Dict] = {}

    @property
    def budget(self) -> int:
        if self._budget is not None:
            return self._budget
        from risingwave_tpu.analysis.shape_domain import recompile_budget

        return recompile_budget()

    # -- the per-barrier hook --------------------------------------------
    def observe_barrier(self, target) -> List[str]:
        """Consume this barrier's hazard deltas and act. ``target`` is
        a runtime (``.executors()``) or a plain executor list. Costs
        one attribute check per barrier while SignatureWatch is
        disarmed. Returns the executor class names pinned this call."""
        if not self.enabled:
            return []
        from risingwave_tpu.analysis.jax_sanitizer import SIGNATURES

        if not SIGNATURES.enabled:
            return []
        deltas = SIGNATURES.take_hazard_deltas()
        if not deltas:
            return []
        slow = self._device_slow()
        budget = 0 if slow else self.budget
        acted = []
        for name, n in deltas.items():
            total = self.hazards.get(name, 0) + n
            self.hazards[name] = total
            if name in self.pinned:
                continue
            if total > budget:
                self._pin(
                    target,
                    name,
                    total,
                    "slow_device" if slow else "budget_exceeded",
                )
                acted.append(name)
        return acted

    @staticmethod
    def _device_slow() -> bool:
        try:
            from risingwave_tpu import blackbox

            return blackbox.SENTINEL.state == blackbox.SLOW
        except Exception:  # noqa: BLE001 — the governor never faults
            return False

    def _pin(self, target, name: str, hazards: int, reason: str) -> None:
        from risingwave_tpu.event_log import EVENT_LOG
        from risingwave_tpu.metrics import REGISTRY

        executors = (
            target.executors() if hasattr(target, "executors") else target
        )
        pins: List[Dict] = []
        for ex in executors or ():
            if type(ex).__name__ != name:
                continue
            fn = getattr(ex, "pin_max_bucket", None)
            if fn is None:
                continue
            try:
                pins.append(fn())
            except Exception:  # noqa: BLE001 — throttling is best-effort
                continue
        action = "pin_max_bucket" if pins else "no_pin_surface"
        self.pinned[name] = {
            "hazards": hazards,
            "reason": reason,
            "action": action,
            "pins": pins,
        }
        REGISTRY.counter("shape_governor_actions_total").inc(
            executor=name, action=action, reason=reason
        )
        REGISTRY.gauge("shape_governor_pinned").set(float(len(self.pinned)))
        EVENT_LOG.record(
            "shape_governor",
            executor=name,
            action=action,
            reason=reason,
            hazards=hazards,
            budget=self.budget,
        )

    def snapshot(self) -> Dict:
        return {
            "enabled": self.enabled,
            "budget": self.budget,
            "hazards": dict(self.hazards),
            "pinned": {
                k: {kk: vv for kk, vv in v.items() if kk != "pins"}
                for k, v in self.pinned.items()
            },
        }

    def reset(self) -> None:
        self.hazards.clear()
        self.pinned.clear()
