"""Shape-stability hardening (array/lattice.py, ops/bucketing.py, PR 9):
the pow2 bucket allocator's grow-eager/shrink-lazy hysteresis, emission
bucketing mask correctness at exactly-full/one-over boundaries, the
bucket-boundary-oscillation recompile bound (one trace per bucket,
never per shape), RW-E806 lattice validation + strict-fusion DDL
refusal, the recompile-storm ShapeGovernor (budget + SLOW-device
proactive throttle, runtime-wired), and the q7 bucketed-vs-unbucketed
bit-identical twin. The adversarial q7 soak rides the slow tier."""

import numpy as np
import pytest

import jax.numpy as jnp

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Executor, Watermark
from risingwave_tpu.array.lattice import (
    emission_bucket,
    flush_lattice,
    flush_lattice_pad,
    flush_pad,
    flush_pad_schedule,
    lattice_between,
    pow2_at_least,
    push_lattice,
    validate_lattice,
)
from risingwave_tpu.ops.bucketing import (
    BucketAllocator,
    BucketPolicy,
    padding_stats,
)
from risingwave_tpu.runtime.shape_governor import ShapeGovernor

pytestmark = pytest.mark.smoke

I64 = jnp.int64


def _chunk(ws, ps, cap=None):
    ws = np.asarray(ws, np.int64)
    ps = np.asarray(ps, np.int64)
    return StreamChunk.from_numpy(
        {"w": ws, "p": ps}, cap or pow2_at_least(max(len(ws), 2))
    )


# ---------------------------------------------------------------------------
# lattice + allocator units
# ---------------------------------------------------------------------------


def test_pow2_lattice_helpers():
    assert pow2_at_least(1) == 1
    assert pow2_at_least(5) == 8
    assert pow2_at_least(64) == 64
    assert lattice_between(16, 128) == (16, 32, 64, 128)
    assert lattice_between(10, 10) == (16,)
    assert emission_bucket(0) == 2
    assert emission_bucket(4) == 4  # exactly-full: no extra padding
    assert emission_bucket(5) == 8  # one-over: next bucket
    assert validate_lattice((4, 8, 16)) is None
    assert "power of two" in validate_lattice((3, 8))
    assert "empty" in validate_lattice(())
    assert "increasing" in validate_lattice((8, 8))
    assert "increasing" in validate_lattice((16, 8))
    assert validate_lattice("nope") is not None
    assert "bound" in validate_lattice((1 << 30,))


def test_policy_from_capacity_and_env(monkeypatch):
    p = BucketPolicy.from_capacity(1 << 10)
    assert p.min_cap == 1 << 10
    assert p.lattice()[0] == 1 << 10
    assert p.lattice()[-1] == p.max_cap
    assert validate_lattice(p.lattice()) is None
    monkeypatch.setenv("RW_BUCKET_MAX_STEPS", "2")
    p2 = BucketPolicy.from_capacity(1 << 10)
    assert p2.lattice() == (1 << 10, 1 << 11, 1 << 12)
    with pytest.raises(ValueError):
        BucketPolicy(min_cap=24, max_cap=48)  # not pow2
    with pytest.raises(ValueError):
        BucketPolicy(min_cap=16, max_cap=64, shrink_at=0.6)  # >= grow_at


def test_allocator_grows_eagerly_and_clamps_at_max():
    a = BucketAllocator(BucketPolicy(min_cap=16, max_cap=128))
    # under the load factor: no plan needed
    assert not a.should_plan(16, 4, 2)
    # over it: plan fires and returns the smallest fitting bucket NOW
    assert a.should_plan(16, 6, 4)
    assert a.plan(16, incoming=4, claimed=6, survivors=6) == 32
    # demand beyond max_cap clamps (the overflow latch then reports)
    assert a.plan(32, incoming=200, claimed=20, survivors=20) == 128
    assert a.high_water == 128


def test_allocator_shrinks_lazily_with_hysteresis():
    pol = BucketPolicy(min_cap=16, max_cap=256, patience=3)
    a = BucketAllocator(pol)
    # occupancy far below shrink_at*cap, but only patience barriers in
    # a row earn a pending shrink
    a.note_barrier(128, 4)
    a.note_barrier(128, 4)
    assert not a.should_plan(128, 4, 2)
    a.note_barrier(128, 4)  # patience reached
    assert a.should_plan(128, 4, 2)
    got = a.plan(128, incoming=2, claimed=4, survivors=4)
    assert got is not None and got < 128 and got >= 16
    # oscillation at a bucket boundary NEVER flaps: one loaded barrier
    # resets the streak
    b = BucketAllocator(pol)
    for _ in range(10):
        b.note_barrier(128, 4)  # idle...
        b.note_barrier(128, 100)  # ...then loaded again
        assert not b.should_plan(128, 4, 2)
    # a pending shrink still respects what the next chunk needs
    c = BucketAllocator(pol)
    for _ in range(3):
        c.note_barrier(256, 8)
    assert c.plan(256, incoming=100, claimed=8, survivors=8) == 256 or (
        c.plan(256, incoming=100, claimed=8, survivors=8) is None
    )


def test_allocator_saturation_stops_per_chunk_replanning():
    """Demand beyond the lattice max must NOT degenerate into a
    blocking read + same-capacity rebuild per chunk: plan() returns
    None once saturated, should_plan() goes quiet until the next
    barrier re-check (the overflow latch owns genuine overflow)."""
    a = BucketAllocator(BucketPolicy(min_cap=16, max_cap=64))
    assert a.plan(16, 40, 10, 10) == 64  # legitimate growth to max
    # survivors alone exceed max_cap * grow_at: nothing to rebuild
    assert a.plan(64, 40, 60, 60) is None
    assert not a.should_plan(64, 60, 40)  # quiet until note_barrier
    a.note_barrier(64, 60)  # barrier re-check re-arms the trigger
    assert a.should_plan(64, 60, 40)
    # a genuine tombstone compaction (survivors fit) still returns cap
    b = BucketAllocator(BucketPolicy(min_cap=16, max_cap=64))
    assert b.plan(64, 8, 60, 10) == 64


def test_unbucketed_twin_keeps_legacy_emission_shapes():
    """The bucketed=False twin must reproduce the LEGACY max(2, n)
    emission capacities — it is the RW-E803 baseline the soak and the
    detection tests compare against."""
    from risingwave_tpu.executors.top_n_plain import TopNExecutor

    tn = TopNExecutor(
        "p", 5, ("k",), {"k": I64, "p": I64}, desc=True, capacity=64,
        bucketed=False,
    )
    tn.apply(
        StreamChunk.from_numpy(
            {
                "k": np.arange(9, dtype=np.int64),
                "p": np.arange(9, dtype=np.int64),
            },
            16,
        )
    )
    outs = tn.on_barrier(None)
    assert len(outs) == 1 and outs[0].capacity == 5  # max(2, 5), not 8
    assert tn.trace_contract()["emission"] == "data_dependent"


def test_allocator_pin_freezes_high_water():
    a = BucketAllocator(BucketPolicy(min_cap=16, max_cap=256, patience=1))
    assert a.plan(16, 20, 10, 10) == 64
    assert a.pin() == 64
    # pinned: below-high-water capacity jumps straight back up
    assert a.should_plan(16, 0, 0)
    assert a.plan(16, 0, 0, 0) == 64
    # pinned: no shrink, ever
    for _ in range(5):
        a.note_barrier(64, 1)
    assert not a.should_plan(64, 1, 1)
    snap = a.snapshot()
    assert snap["pinned"] and snap["high_water"] == 64


# ---------------------------------------------------------------------------
# executor integration: lattice-confined capacities + recompile bound
# ---------------------------------------------------------------------------


def test_bucket_boundary_oscillation_one_trace_per_bucket():
    """Satellite 3: drive the q7 pre-filter's window state across
    EVERY pow2 boundary of its declared lattice (growth + churn) —
    total traces of the hot step stay <= lattice size (one per bucket,
    never one per shape), capacities never leave the lattice, and the
    result matches the unbucketed twin exactly."""
    from risingwave_tpu.executors import dynamic_filter as df

    pol = BucketPolicy(min_cap=16, max_cap=128, patience=2)
    mk = lambda **kw: df.DynamicMaxFilterExecutor(
        "w", "p", {"w": I64, "p": I64}, capacity=16,
        window_key=("w", 0), **kw
    )
    ex = mk(bucket_policy=pol)
    lattice = ex._buckets.lattice
    assert lattice == (16, 32, 64, 128)

    # pre-generate the seeded script: window-key domain sweeps upward
    # across every bucket boundary, then churns after an expiry
    rng = np.random.default_rng(7)
    script = []
    for target in (8, 24, 56, 120):
        for _ in range(6):
            script.append(
                (
                    "chunk",
                    _chunk(
                        rng.integers(0, target, size=8),
                        rng.integers(0, 100, size=8),
                        cap=8,
                    ),
                )
            )
    script.append(("wm", 100))
    for _ in range(6):
        script.append(
            (
                "chunk",
                _chunk(
                    rng.integers(0, 140, size=8),
                    rng.integers(0, 100, size=8),
                    cap=8,
                ),
            )
        )

    def drive(executor):
        out, caps = [], set()
        for kind, payload in script:
            if kind == "wm":
                executor.on_watermark(Watermark("w", payload))
                continue
            out.extend(x.to_numpy() for x in executor.apply(payload))
            executor.on_barrier(None)
            caps.add(executor.table.capacity)
        return out, caps

    # trace accounting brackets ONLY the bucketed run (the jit cache
    # is shared process-wide; the unbounded twin would pollute it)
    base = df._filter_step._cache_size()
    out_b, caps_seen = drive(ex)
    traces = df._filter_step._cache_size() - base
    assert caps_seen <= set(lattice), caps_seen
    assert traces <= len(lattice), (
        f"{traces} traces of _filter_step > lattice size {len(lattice)}"
    )
    # bit-identical to the unbucketed twin, row for row
    out_t, _ = drive(mk(bucketed=False))
    assert len(out_b) == len(out_t)
    for got, want in zip(out_b, out_t):
        assert set(got) == set(want)
        for k in got:
            np.testing.assert_array_equal(got[k], want[k])


def test_emission_mask_exactly_full_and_one_over():
    """Bucketed host-diff emissions: a delta of exactly 2^k rows rides
    a 2^k-capacity chunk (all lanes valid), 2^k+1 rides the next
    bucket with the padding masked out — visible rows exact both
    ways."""
    from risingwave_tpu.executors.dynamic_filter import (
        DynamicFilterExecutor,
    )
    from risingwave_tpu.types import Op

    def flip_rows(n):
        """Store n rows passing, then move the rv so ALL n flip."""
        ex = DynamicFilterExecutor(
            "p", "<", ("k",), {"k": I64, "p": I64}, capacity=64
        )
        ks = np.arange(n, dtype=np.int64)
        ps = np.full(n, 10, np.int64)
        ex.apply_left(
            StreamChunk.from_numpy(
                {"k": ks, "p": ps}, pow2_at_least(max(n, 2))
            )
        )
        # rv=100: all pass (10 < 100)
        ex.apply_right(
            StreamChunk.from_numpy(
                {"k": np.zeros(1, np.int64), "p": np.asarray([100], np.int64)},
                2,
                ops=np.asarray([int(Op.INSERT)], np.int32),
            )
        )
        ex.on_barrier(None)
        # rv=5: all n retract in ONE barrier diff
        ex.apply_right(
            StreamChunk.from_numpy(
                {"k": np.zeros(1, np.int64), "p": np.asarray([5], np.int64)},
                2,
                ops=np.asarray([int(Op.INSERT)], np.int32),
            )
        )
        outs = ex.on_barrier(None)
        assert len(outs) == 1
        return outs[0]

    # exactly-full boundary: 4 flipped rows -> capacity 4, no padding
    out4 = flip_rows(4)
    assert out4.capacity == 4
    assert int(np.asarray(out4.valid).sum()) == 4
    assert sorted(out4.to_numpy()["k"].tolist()) == [0, 1, 2, 3]
    # one-over boundary: 5 flipped rows -> capacity 8, 3 masked lanes
    out5 = flip_rows(5)
    assert out5.capacity == 8
    assert int(np.asarray(out5.valid).sum()) == 5
    assert sorted(out5.to_numpy()["k"].tolist()) == [0, 1, 2, 3, 4]


# ---------------------------------------------------------------------------
# the flush lattice (PR 30): the interpreted slicer follows the count on
# a declared lattice (256, a quarter of the full size, the full size); the
# fused programs keep their {small, full} pair
# ---------------------------------------------------------------------------

_LATTICES = {
    128: (256,),
    1 << 15: (256, 16384, 65536),
}


def _edge_counts():
    """(out_cap, n_take) on and either side of every edge of the
    lattice, the empty round and the overflowed one."""
    cases = []
    for out_cap, lattice in _LATTICES.items():
        ns = {0, 1, out_cap - 1, out_cap}
        for size in lattice[:-1]:
            ns |= {size // 2 - 1, size // 2, size // 2 + 1}
        cases += [(out_cap, n) for n in sorted(ns) if 0 <= n <= out_cap]
    return cases


@pytest.mark.parametrize("out_cap,n_take", _edge_counts())
def test_flush_pad_is_the_smallest_declared_size(out_cap, n_take):
    lattice = flush_lattice(out_cap)
    assert lattice == _LATTICES[out_cap]
    assert validate_lattice(lattice) is None
    pad = flush_lattice_pad(out_cap, n_take)
    assert pad == min(s for s in lattice if s >= 2 * n_take)
    if n_take == out_cap:  # an overflowed round is full, as before
        assert pad == 2 * out_cap


@pytest.mark.parametrize("out_cap,n_take", _edge_counts())
def test_fused_pads_stay_small_or_full(out_cap, n_take):
    """The fused barrier programs draw their pads from a BOUND and bake
    them into one executable: they keep the {256, full} pair, on
    purpose, whatever the interpreted slicer does with the count."""
    full = 2 * out_cap
    small = min(256, full)
    assert flush_pad(out_cap, n_take) == (
        small if 2 * n_take <= small else full
    )
    capacity = 4 * out_cap
    for bound in (n_take, out_cap + n_take, 10 * capacity):
        left = min(bound, capacity)
        want = []
        while True:
            want.append(flush_pad(out_cap, min(left, out_cap)))
            left -= out_cap
            if left <= 0:
                break
        pads = flush_pad_schedule(bound, capacity, out_cap)
        assert pads == tuple(want) and set(pads) <= {small, full}


@pytest.mark.parametrize("capacity", [1 << 10, 1 << 12, 1 << 13, 1 << 16])
def test_push_lattice_is_a_quarter_and_the_chunks_own_capacity(capacity):
    """PR 32: the widths a pushed chunk may be cut to are one x4 step
    below its capacity and the capacity: never wider than the chunk,
    and a lattice the bucketing layer can satisfy."""
    lattice = push_lattice(capacity)
    assert lattice == (capacity // 4, capacity)
    assert max(lattice) == capacity and validate_lattice(lattice) is None


@pytest.mark.parametrize("capacity", [2, 4, 100, 512, 1000, 3 << 10])
def test_push_lattice_leaves_small_and_odd_capacities_whole(capacity):
    """An INSERT's few-row chunk, and a capacity that is no power of
    two, have one width: their own."""
    assert push_lattice(capacity) == (capacity,)


def test_shape_domain_lists_the_push_lattice_for_q8s_join_ports():
    """The analysis reads the set the runtime cuts to: the q8 join's
    two input chains and the join take a chunk built at 8,192 lanes at
    2,048 and at 8,192; a chain with an epoch-batched aggregate takes
    8,192 alone."""
    from risingwave_tpu.analysis.shape_domain import (
        ChunkSpec,
        push_lattice_specs,
    )
    from risingwave_tpu.executors.epoch_batch import EpochBatchedAggExecutor
    from risingwave_tpu.queries.nexmark_q import build_q5_lite, build_q8

    q8 = build_q8(capacity=1 << 10, fanout=4, out_cap=1 << 10).pipeline
    spec = ChunkSpec.from_schema(
        {"id": I64, "name": jnp.int32, "date_time": I64}, capacity=1 << 13
    )
    for chain in (q8.left, q8.right):
        specs = push_lattice_specs(spec, list(chain) + [q8.join])
        assert [s.capacity for s in specs] == [1 << 11, 1 << 13]
        assert all(s.columns == spec.columns for s in specs)
    agg = next(
        ex for ex in build_q5_lite(capacity=1 << 10).pipeline.executors
        if hasattr(ex, "apply_stacked")
    )
    batched = [EpochBatchedAggExecutor([], agg)]
    assert [s.capacity for s in push_lattice_specs(spec, batched)] == [1 << 13]


@pytest.mark.parametrize("out_cap", sorted(_LATTICES))
def test_agg_declares_and_cuts_to_the_flush_lattice(out_cap):
    from risingwave_tpu.executors.hash_agg import HashAggExecutor
    from risingwave_tpu.ops.agg import AggCall

    agg = HashAggExecutor(
        ("k",), (AggCall("count_star", None, "n"),), {"k": I64},
        capacity=max(2 * out_cap, 1 << 10), out_cap=out_cap,
    )
    contract = agg.trace_contract()
    assert contract["emission_caps"] == _LATTICES[out_cap]
    assert contract["window_buckets"] == _LATTICES[out_cap]
    assert [c.capacity for c in agg.warm_emissions()] == list(
        _LATTICES[out_cap]
    )
    for groups in {1, 100, 129, min(8200, out_cap)}:
        groups = min(groups, out_cap)
        ks = np.arange(groups, dtype=np.int64)
        agg.apply(StreamChunk.from_numpy({"k": ks}, pow2_at_least(groups)))
        (out,) = agg.on_barrier(None)
        assert out.capacity == flush_lattice_pad(out_cap, groups)
        assert int(np.asarray(out.valid).sum()) >= groups
    # a table smaller than out_cap drains at most itself a round
    small = HashAggExecutor(
        ("k",), (AggCall("count_star", None, "n"),), {"k": I64},
        capacity=1 << 10, out_cap=1 << 15,
    )
    assert small.trace_contract()["emission_caps"] == (256, 512, 2048)


def test_topn_candidate_sizes_compile_when_the_view_is_created():
    """The GroupTopN's barrier ranks an epoch's candidates in a declared
    number of lanes (``candidate_lanes``: twice the epoch's, on the
    emission lattice) and hands on chunks of a declared size; every
    pair an epoch of up to 2^15 lanes can take, and the pair that ranks
    the store, is compiled by ``warm_emissions``: epochs of one, two
    and four chunks of 8,192 then open no compile, whichever way their
    barrier goes."""
    from risingwave_tpu.executors.top_n_plain import (
        RetractableGroupTopNExecutor,
        _diff_gather,
        _rank,
        candidate_lanes,
    )
    from risingwave_tpu.trace import TRACER

    cap = 1 << 18
    ex = RetractableGroupTopNExecutor(
        ("g",), "v", 1, ("id",), {"g": I64, "id": I64, "v": I64},
        capacity=cap, table_id="sizes.gtopn",
    )
    assert [candidate_lanes(n, cap, 1) for n in ex._WARM_EPOCHS] == [
        1 << 14, 1 << 16, 1 << 16,
    ]
    assert [c.capacity for c in ex.warm_emissions()] == [1 << 14, 1 << 16]
    assert ex.emission_sizes() == (1 << 14, 1 << 16)
    assert int(ex.table.occupancy()) == 0 and not ex._cold
    compiled = _rank._cache_size(), _diff_gather._cache_size()
    next_id = 0
    for chunks, cold in ((1, False), (2, False), (4, False), (1, True)):
        for _ in range(chunks):
            ids = np.arange(next_id, next_id + 100, dtype=np.int64)
            next_id += 100
            ex.apply(StreamChunk.from_numpy(
                {"g": ids % 7, "id": ids, "v": ids % 13}, 1 << 13
            ))
        ex._cold = cold
        TRACER.clear()
        ex.on_barrier(None)
        (pull,) = [sp for sp in TRACER.spans() if sp.name == "topn.pull"]
        assert pull.args["full_rank"] == int(cold)
        assert pull.args["ranked_lanes"] == (
            cap if cold else candidate_lanes(chunks << 13, cap, 1)
        )
    assert (_rank._cache_size(), _diff_gather._cache_size()) == compiled


def test_padding_stats_accounting():
    from risingwave_tpu.executors.dedup import AppendOnlyDedupExecutor

    ex = AppendOnlyDedupExecutor(("w",), {"w": I64}, capacity=32)
    ex.apply(
        StreamChunk.from_numpy(
            {"w": np.arange(5, dtype=np.int64)}, 8
        )
    )
    ex.on_barrier(None)
    st = padding_stats([ex, object()])  # non-participants skipped
    assert st["capacity_lanes"] == 32
    assert st["live_lanes"] == 5
    assert 0.0 <= st["wasted_lane_frac"] <= 1.0
    per = st["per_executor"]["AppendOnlyDedupExecutor"]
    assert per["live"] == 5 and per["capacity"] == 32


# ---------------------------------------------------------------------------
# RW-E806 + strict-fusion DDL refusal
# ---------------------------------------------------------------------------


class _BadLatticeExecutor(Executor):
    """Window-keyed, declares a lattice the bucketing layer cannot
    satisfy (not pow2)."""

    window_key = ("w", 1000)

    def lint_info(self):
        return {"window_key": "w"}

    def trace_contract(self):
        return {
            "kind": "device",
            "trace_step": lambda c: c,
            "state": None,
            "donate": True,
            "emission": "passthrough",
            "window_buckets": (3, 5),
        }


def test_e806_unsatisfiable_lattice_flags_and_refuses(monkeypatch):
    from risingwave_tpu.analysis.fusion_analyzer import classify_executor
    from risingwave_tpu.analysis.diagnostics import PlanLintError
    from risingwave_tpu.analysis.lint import fusion_findings_for_ddl
    from risingwave_tpu.analysis.shape_domain import ChunkSpec
    from risingwave_tpu.frontend.session import SqlSession
    from risingwave_tpu.runtime import Pipeline, StreamingRuntime
    from risingwave_tpu.sql import Catalog

    spec = ChunkSpec.from_schema({"w": "int64", "p": "int64"})
    ec = classify_executor(_BadLatticeExecutor(), spec, "f", 0)
    codes = [d.code for d in ec.blockers]
    assert "RW-E806" in codes
    assert "RW-E803" not in codes  # declared, just unsatisfiable
    assert not ec.fusible

    class Shim:
        name = "bad"
        pipeline = Pipeline([_BadLatticeExecutor()])

    diags = fusion_findings_for_ddl(Shim())
    assert diags and all(d.code == "RW-E806" for d in diags)
    session = SqlSession(Catalog({}), StreamingRuntime(store=None))
    monkeypatch.delenv("RW_STRICT_FUSION", raising=False)
    # strict-fusion default is ON: the vacuous lattice is refused
    with pytest.raises(PlanLintError):
        session._fusion_lint(Shim(), strict=True)
    monkeypatch.setenv("RW_STRICT_FUSION", "0")
    session._fusion_lint(Shim(), strict=True)  # report-only escape


def test_valid_lattices_do_not_flag_e806():
    from risingwave_tpu.analysis.fusion_analyzer import classify_executor
    from risingwave_tpu.analysis.shape_domain import ChunkSpec
    from risingwave_tpu.executors.dedup import AppendOnlyDedupExecutor

    ex = AppendOnlyDedupExecutor(
        ("w",), {"w": I64}, capacity=32, window_key=("w", 0)
    )
    spec = ChunkSpec.from_schema({"w": "int64"})
    ec = classify_executor(ex, spec, "f", 0)
    codes = {d.code for d in ec.blockers}
    assert "RW-E803" not in codes and "RW-E806" not in codes


# ---------------------------------------------------------------------------
# recompile-storm governor
# ---------------------------------------------------------------------------


def _observe_capacities(watch, ex, caps):
    for cap in caps:
        watch.observe(
            ex,
            StreamChunk.from_numpy(
                {"w": np.arange(2, dtype=np.int64)}, cap
            ),
        )


def test_governor_pins_over_budget_and_records():
    from risingwave_tpu.analysis.jax_sanitizer import SIGNATURES
    from risingwave_tpu.event_log import EVENT_LOG
    from risingwave_tpu.executors.dedup import AppendOnlyDedupExecutor
    from risingwave_tpu.metrics import REGISTRY

    ex = AppendOnlyDedupExecutor(("w",), {"w": I64}, capacity=32)
    gov = ShapeGovernor(budget=2)
    SIGNATURES.start()
    try:
        _observe_capacities(SIGNATURES, ex, [8])  # warmup shape
        SIGNATURES.mark_stable()
        _observe_capacities(SIGNATURES, ex, [16, 64])  # 2 hazards
        assert gov.observe_barrier([ex]) == []  # == budget: no pin yet
        assert not ex._buckets.pinned
        _observe_capacities(SIGNATURES, ex, [128])  # 3rd: over budget
        acted = gov.observe_barrier([ex])
        assert acted == ["AppendOnlyDedupExecutor"]
        assert ex._buckets.pinned
        info = gov.pinned["AppendOnlyDedupExecutor"]
        assert info["reason"] == "budget_exceeded"
        assert info["action"] == "pin_max_bucket"
        # idempotent: further hazards never re-pin
        _observe_capacities(SIGNATURES, ex, [256])
        assert gov.observe_barrier([ex]) == []
        # surfaces: event + metric + snapshot
        evs = EVENT_LOG.events(kind="shape_governor")
        assert evs and evs[-1]["executor"] == "AppendOnlyDedupExecutor"
        assert (
            REGISTRY.counter("shape_governor_actions_total").get(
                executor="AppendOnlyDedupExecutor",
                action="pin_max_bucket",
                reason="budget_exceeded",
            )
            >= 1
        )
        assert gov.snapshot()["hazards"]["AppendOnlyDedupExecutor"] >= 3
    finally:
        SIGNATURES.stop()


def test_governor_slow_device_throttles_proactively(monkeypatch):
    """A SLOW sentinel heartbeat drops the budget to zero: the FIRST
    hazard pins, before the device degrades to WEDGED."""
    from risingwave_tpu import blackbox
    from risingwave_tpu.analysis.jax_sanitizer import SIGNATURES
    from risingwave_tpu.executors.dedup import AppendOnlyDedupExecutor

    ex = AppendOnlyDedupExecutor(("w",), {"w": I64}, capacity=32)
    gov = ShapeGovernor(budget=1000)  # budget alone would never trip
    monkeypatch.setattr(blackbox.SENTINEL, "state", blackbox.SLOW)
    SIGNATURES.start()
    try:
        _observe_capacities(SIGNATURES, ex, [8])
        SIGNATURES.mark_stable()
        _observe_capacities(SIGNATURES, ex, [16])  # ONE hazard
        assert gov.observe_barrier([ex]) == ["AppendOnlyDedupExecutor"]
        assert gov.pinned["AppendOnlyDedupExecutor"]["reason"] == (
            "slow_device"
        )
        assert ex._buckets.pinned
    finally:
        SIGNATURES.stop()


def test_governor_disabled_and_disarmed_paths(monkeypatch):
    from risingwave_tpu.analysis.jax_sanitizer import SIGNATURES

    assert ShapeGovernor(enabled=False).observe_barrier([]) == []
    monkeypatch.setenv("RW_SHAPE_GOVERNOR", "0")
    assert not ShapeGovernor().enabled
    monkeypatch.delenv("RW_SHAPE_GOVERNOR")
    # SignatureWatch disarmed: the hook is a no-op attribute check
    assert not SIGNATURES.enabled
    assert ShapeGovernor().observe_barrier([]) == []


def test_runtime_barrier_drives_governor(monkeypatch):
    """End to end through StreamingRuntime: shape-unstable pushes pin
    the offender via the runtime's own per-barrier hook."""
    from risingwave_tpu.analysis.jax_sanitizer import SIGNATURES
    from risingwave_tpu.executors.dedup import AppendOnlyDedupExecutor
    from risingwave_tpu.runtime import Pipeline, StreamingRuntime

    monkeypatch.setenv("RW_FUSION_RECOMPILE_BUDGET", "1")
    rt = StreamingRuntime(store=None)
    ex = AppendOnlyDedupExecutor(("w",), {"w": I64}, capacity=32)
    rt.register("f", Pipeline([ex]))
    SIGNATURES.start()
    try:
        rt.push("f", _chunk([1, 2], [0, 0], cap=8))
        rt.barrier()
        SIGNATURES.mark_stable()
        rt.push("f", _chunk([3], [0], cap=16))  # hazard 1
        rt.barrier()
        assert not ex._buckets.pinned  # == budget
        rt.push("f", _chunk([4], [0], cap=64))  # hazard 2 > budget
        rt.barrier()
        assert ex._buckets.pinned
        assert "AppendOnlyDedupExecutor" in rt.shape_governor.pinned
    finally:
        SIGNATURES.stop()


def test_runtime_shape_watch_warmup_env(monkeypatch):
    """RW_SHAPE_WATCH_WARMUP=N arms SignatureWatch at construction and
    flips it stable after N barriers."""
    from risingwave_tpu.analysis.jax_sanitizer import SIGNATURES
    from risingwave_tpu.runtime import Pipeline, StreamingRuntime

    monkeypatch.setenv("RW_SHAPE_WATCH_WARMUP", "2")
    rt = StreamingRuntime(store=None)
    try:
        assert SIGNATURES.enabled and not SIGNATURES._stable
        rt.register("f", Pipeline([]))
        rt.barrier()
        assert not SIGNATURES._stable
        rt.barrier()
        assert SIGNATURES._stable
    finally:
        SIGNATURES.stop()


# ---------------------------------------------------------------------------
# q7: bucketed vs unbucketed twin, bit-identical (tier-1 size)
# ---------------------------------------------------------------------------


def _drive_q7(q7, epochs, rng_seed=11, windows=(4, 20, 4, 24)):
    """Seeded bid stream whose open-window count sweeps across pow2
    bucket boundaries, with watermark-driven expiry between epochs."""
    rng = np.random.default_rng(rng_seed)
    window_ms = 10_000
    ts0 = 0
    for ep in range(epochs):
        n_w = windows[ep % len(windows)]
        n = 32
        ts = ts0 + rng.integers(0, n_w * window_ms, size=n)
        cols = {
            "auction": rng.integers(0, 50, size=n).astype(np.int64),
            "bidder": rng.integers(0, 50, size=n).astype(np.int64),
            "price": rng.integers(1, 200, size=n).astype(np.int64),
            "date_time": ts.astype(np.int64),
        }
        c = StreamChunk.from_numpy(cols, 32)
        q7.pipeline.push_left(c)
        q7.pipeline.push_right(c)
        q7.pipeline.barrier()
        q7.pipeline.watermark("date_time", int(ts.max()))
        if ep % 4 == 3:
            ts0 += 2 * window_ms  # windows close; fresh ones mint


def test_q7_bucketed_bit_identical_to_unbucketed_twin():
    from risingwave_tpu.queries.nexmark_q import build_q7

    mk = lambda **kw: build_q7(
        capacity=1 << 6,
        fanout=8,
        out_cap=1 << 10,
        agg_capacity=1 << 4,
        filter_capacity=1 << 4,
        **kw,
    )
    dev, twin = mk(), mk(bucketed=False)
    _drive_q7(dev, 8)
    _drive_q7(twin, 8)
    got, want = dev.mview.snapshot(), twin.mview.snapshot()
    assert got == want and len(got) > 0
    # and the shipped plan's shapes stayed on the declared lattice
    lat = set(dev.join.trace_contract()["window_buckets"])
    assert dev.join.left.capacity in lat
    assert dev.join.right.capacity in lat


# ---------------------------------------------------------------------------
# adversarial q7 soak (slow tier): zero hazards, zero wedges,
# bit-identical under sustained bucket-boundary churn
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_q7_soak_bucket_boundary_churn_unwedgeable():
    """The PR-9 acceptance soak: a seeded generator oscillates q7's
    open-window population across every pow2 bucket boundary for many
    epochs. After warmup (one full oscillation cycle, visiting the
    buckets) the steady phase must show ZERO recompile hazards and
    ZERO kernel-cache growth (no re-tracing — the wedge mechanism is
    gone), the armed device sentinel must never classify WEDGED, and
    the MV must stay bit-identical to the legacy unbucketed twin."""
    from risingwave_tpu import blackbox
    from risingwave_tpu.analysis.jax_sanitizer import (
        SIGNATURES,
        RecompileWatch,
    )
    from risingwave_tpu.queries.nexmark_q import build_q7

    mk = lambda **kw: build_q7(
        capacity=1 << 8,
        fanout=8,
        out_cap=1 << 12,
        agg_capacity=1 << 5,
        filter_capacity=1 << 5,
        **kw,
    )
    dev, twin = mk(), mk(bucketed=False)
    sentinel = blackbox.DeviceSentinel()
    sentinel.start(interval_s=0.1, slow_ms=5_000, deadline_s=30)
    SIGNATURES.start()
    try:
        execs = (
            list(dev.pipeline.left)
            + list(dev.pipeline.right)
            + [dev.join]
            + list(dev.pipeline.tail)
        )
        gov = ShapeGovernor()
        windows = (4, 40, 8, 64, 4, 48)
        # -- warmup: one full oscillation cycle visits every bucket --
        _drive_q7(dev, len(windows), rng_seed=23, windows=windows)
        _drive_q7(twin, len(windows), rng_seed=23, windows=windows)
        SIGNATURES.mark_stable()
        watch = RecompileWatch()
        watch.snapshot()
        # -- steady phase: 4 more full cycles of the SAME churn ------
        for cycle in range(4):
            _drive_q7(
                dev, len(windows), rng_seed=100 + cycle, windows=windows
            )
            _drive_q7(
                twin, len(windows), rng_seed=100 + cycle, windows=windows
            )
            gov.observe_barrier(execs)
        # zero recompile hazards after warmup (acceptance bar) ...
        assert SIGNATURES.hazard_total() == 0, SIGNATURES.report()
        # ... zero fresh kernel traces (nothing re-traced mid-soak) ...
        deltas = watch.deltas(record=False)
        assert deltas == {}, deltas
        # ... the governor never had to act ...
        assert gov.pinned == {}
        # ... the device never wedged ...
        assert sentinel.wedges == 0
        assert sentinel.wedged_error() is None
        assert sentinel.state != blackbox.WEDGED
        # ... and the result is bit-identical to the unpadded twin
        got, want = dev.mview.snapshot(), twin.mview.snapshot()
        assert got == want and len(got) > 0
    finally:
        SIGNATURES.stop()
        sentinel.stop()
