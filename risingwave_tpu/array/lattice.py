"""The declared sizes every compiled program is keyed on.

XLA compiles one program per abstract input signature, and on the
TPU one cold compile costs minutes — so a state buffer whose
capacity wanders freely re-traces every fused program that touches it
until the device queue deadlocks (the q7 wedge, RW-E803; BENCH_TPU_2/3
"device wedged; stopping").  The fix is the fixed-capacity
region-padded state model (PAPERS.md, "Streaming Computations with
Region-Based State on SIMD Architectures"): every device-visible
shape is drawn from a small DECLARED pow2 lattice, and buffers are
padded to their bucket with validity masks.

This module is the size rules that several layers share (chunks, state
tables, executors, the runtime's push): pure functions of ints, at the
bottom of the package so that any layer may import them. The capacity
planner built on them is ``ops/bucketing.py``; the runtime's back-stop
for when stability is violated anyway is ``runtime/shape_governor.py``.

Host-diff executors (dynamic filter rv flips, plain/retractable TopN)
used to emit ``max(2, n)``-sized chunks, minting a fresh downstream
program per distinct delta count; padding the emission to a pow2 bucket
with masked lanes (:func:`emission_bucket`) closes that set too.
"""

from __future__ import annotations

from typing import Optional, Tuple

__all__ = [
    "delta_blocks",
    "emission_bucket",
    "flush_lattice",
    "flush_lattice_pad",
    "flush_pad",
    "flush_pad_schedule",
    "lattice_between",
    "pow2_at_least",
    "prefix_pad",
    "push_lattice",
    "select_spans",
    "touched_lattice",
    "validate_lattice",
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
