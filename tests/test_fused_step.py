"""Fused device-resident barrier step (runtime/fused_step).

Twin discipline: the fused program must be BIT-IDENTICAL to the
interpreted per-executor walk — same seeds, same epochs, identical MV
snapshots at every barrier — across q5 (hop->agg->MV), q7 (two-input
join with a fusible hop->maxagg side and a fused MV tail) and q8
(dedup join with a fused MV tail). Plus the operational contracts:
one device dispatch per barrier attributed as ``fused:<fragment>``,
donation leaves no orphaned state buffers, rebuilt fragments re-fuse,
latch checks still raise at finish_barrier, and RW_FUSED_STEP=0 falls
back to the epoch-batched interpreted path.
"""

import itertools

import jax
import jax.numpy as jnp
import pytest

from risingwave_tpu.analysis.jax_sanitizer import RecompileWatch
from risingwave_tpu.connectors.nexmark import (
    BID_SCHEMA,
    NexmarkConfig,
    NexmarkGenerator,
)
from risingwave_tpu.profiler import PROFILER
from risingwave_tpu.queries.nexmark_q import (
    build_q5_lite,
    build_q7,
    build_q8,
)
from risingwave_tpu.runtime.fused_step import (
    FusedChainExecutor,
    FusedTwoInputExecutor,
    expand_fused,
    fuse_chain,
    fuse_pipeline,
    fused_cache_stats,
    fused_fragments,
    fusion_refusals,
)

Q5_SQL = (
    "CREATE MATERIALIZED VIEW q5 AS "
    "SELECT auction, window_start, count(*) AS num "
    "FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND) "
    "GROUP BY auction, window_start"
)


# ---------------------------------------------------------------------------
# fused-vs-interpreted twins (bit-identity per barrier)
# ---------------------------------------------------------------------------


def _drive_q5(q5, *, fuse, watermarks, epochs=4, chunks_per_epoch=3):
    if fuse:
        wrappers = fuse_pipeline(q5.pipeline, label="q5")
        assert len(wrappers) == 1 and wrappers[0].covers_whole_chain
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=5_000))
    snaps, mx = [], 0
    for _ in range(epochs):
        for _ in range(chunks_per_epoch):
            c = gen.next_chunks(800, 1024)["bid"]
            if c is None:
                continue
            q5.pipeline.push(c)
            mx = max(mx, int(c.to_numpy()["date_time"].max()))
        q5.pipeline.barrier()
        if watermarks:
            q5.pipeline.watermark("date_time", mx)
        snaps.append(q5.mview.snapshot())
    return snaps


@pytest.mark.parametrize("watermarks", [False, True])
def test_q5_fused_bit_identical_to_interpreted_twin(watermarks):
    mk = lambda: build_q5_lite(
        capacity=1 << 12, state_cleaning=watermarks
    )
    interp = _drive_q5(mk(), fuse=False, watermarks=watermarks)
    fused = _drive_q5(mk(), fuse=True, watermarks=watermarks)
    for e, (a, b) in enumerate(zip(interp, fused)):
        assert a == b, f"epoch {e}: fused MV diverged from interpreted"
    assert len(interp[-1]) > 0


def _drive_q7(q7, *, fuse, epochs=4, depth=None):
    if fuse:
        wrappers = fuse_pipeline(
            q7.pipeline, label="q7", pipeline_depth=depth
        )
        # the WHOLE two-input pipeline fuses: hop -> maxagg ->
        # [bucket-masked flush] -> DynamicMaxFilter x HashJoin -> MV
        # is ONE donated program per barrier (PR 13); the old
        # epoch-batch + interpreted-join fallback is now the
        # RW_FUSED_TWO_INPUT=0 twin
        assert len(wrappers) == 1
        assert isinstance(wrappers[0], FusedTwoInputExecutor)
        assert q7.pipeline._fused is wrappers[0]
        assert wrappers[0].covers_whole_chain
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=10_000))
    snaps, mx = [], 0
    for _ in range(epochs):
        for _ in range(2):
            bid = gen.next_chunks(1200, 2048)["bid"]
            if bid is None:
                continue
            bid = bid.select(["auction", "bidder", "price", "date_time"])
            q7.pipeline.push_left(bid)
            q7.pipeline.push_right(bid)
            mx = max(mx, int(bid.to_numpy()["date_time"].max()))
        q7.pipeline.barrier()
        q7.pipeline.watermark("date_time", mx)
        snaps.append(q7.mview.snapshot())
    return snaps


def test_q7_fused_bit_identical_to_interpreted_twin():
    mk = lambda: build_q7(
        capacity=1 << 13,
        agg_capacity=1 << 11,
        filter_capacity=1 << 11,
        out_cap=1 << 11,
    )
    interp = _drive_q7(mk(), fuse=False)
    fused = _drive_q7(mk(), fuse=True)
    for e, (a, b) in enumerate(zip(interp, fused)):
        assert a == b, f"epoch {e}: fused q7 MV diverged"


def _drive_q8(q8, *, fuse, epochs=4, depth=None):
    if fuse:
        wrappers = fuse_pipeline(
            q8.pipeline, label="q8", pipeline_depth=depth
        )
        # dedup x join -> MV: one donated two-input program per barrier
        assert len(wrappers) == 1
        assert isinstance(wrappers[0], FusedTwoInputExecutor)
        assert wrappers[0].covers_whole_chain
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=10_000))
    snaps = []
    for _ in range(epochs):
        for _ in range(2):
            ev = gen.next_chunks(3000, 8192)
            p, a = ev["person"], ev["auction"]
            if p is not None:
                q8.pipeline.push_left(p.select(["id", "name", "date_time"]))
            if a is not None:
                q8.pipeline.push_right(a.select(["seller", "date_time"]))
        q8.pipeline.barrier()
        snaps.append(q8.mview.snapshot())
    return snaps


def test_q8_fused_bit_identical_to_interpreted_twin():
    mk = lambda: build_q8(capacity=1 << 12, out_cap=1 << 11)
    interp = _drive_q8(mk(), fuse=False)
    fused = _drive_q8(mk(), fuse=True)
    for e, (a, b) in enumerate(zip(interp, fused)):
        assert a == b, f"epoch {e}: fused q8 MV diverged"
    assert len(interp[-1]) > 0


# ---------------------------------------------------------------------------
# dispatch-wall evidence: ONE program per barrier, attributed
# ---------------------------------------------------------------------------


def _nothing():
    return None


def _q5_leg(*, fused, digests=False, runtime=False):
    """q5 steady state: one fixed chunk an epoch (fresh keys would grow
    the table: a legitimate recompile, not what these counts hold).
    Returns (prepare, counted, after, check)."""
    q5 = build_q5_lite(capacity=1 << 12, state_cleaning=False)
    wrappers = []
    if fused:
        wrappers = fuse_pipeline(q5.pipeline, label="q5")
        assert len(wrappers) == 1 and wrappers[0].covers_whole_chain
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=50_000))
    bid = gen.next_chunks(2000, 1 << 11)["bid"].select(
        ["auction", "date_time"]
    )

    def epoch():
        q5.pipeline.push(bid)
        q5.pipeline.barrier()

    if digests:
        # the digest lanes ride the staged scalar read; the commit
        # (host crc + digests) sits outside the counted barrier
        from risingwave_tpu.storage.object_store import MemObjectStore
        from risingwave_tpu.storage.state_table import CheckpointManager

        mgr = CheckpointManager(MemObjectStore())
        commits = itertools.count(1)

        def commit():
            mgr.commit_staged(
                next(commits) << 16, mgr.stage(wrappers[0].members)
            )

        def check():
            assert {"agg", "mv"} <= set(wrappers[0].last_digests)

        return _nothing, epoch, commit, check
    if runtime:
        # the whole _begin_trace -> dispatch -> publish ->
        # _observe_freshness lifecycle, with the frontier threaded; the
        # watermark walk is the hop executor's own dispatch, identical
        # with tracking off, so only rt.barrier() is counted
        import time

        from risingwave_tpu.freshness import FRESHNESS
        from risingwave_tpu.runtime import StreamingRuntime

        rt = StreamingRuntime(store=None)
        rt.register("q5_mv", q5.pipeline)
        FRESHNESS.reset()

        def prepare():
            rt.push("q5_mv", bid)
            q5.pipeline.watermark("date_time", int(time.time() * 1000))

        def check():
            rows = [r for r in FRESHNESS.history() if r["mv"] == "q5_mv"]
            assert len(rows) >= 3  # a sample every counted barrier
            assert all(r["event_time_lag_ms"] is not None for r in rows[-3:])
            assert rt.last_epoch_trace.backpressure_fragment is not None

        return prepare, rt.barrier, _nothing, check
    return _nothing, epoch, _nothing, _nothing


def _two_input_leg(query):
    """q7 / q8 fused whole: side chains x join x MV in one program; the
    pushes buffer, q7's watermark walk follows the counted barrier."""
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=10_000))
    after = _nothing
    if query == "q7":
        q = build_q7(
            capacity=1 << 13,
            agg_capacity=1 << 11,
            filter_capacity=1 << 11,
            out_cap=1 << 11,
        )
        seen = {}

        def prepare():
            bid = None
            while bid is None:
                bid = gen.next_chunks(1000, 1024)["bid"]
            bid = bid.select(["auction", "bidder", "price", "date_time"])
            q.pipeline.push_left(bid)
            q.pipeline.push_right(bid)
            seen["mx"] = int(bid.to_numpy()["date_time"].max())

        def after():
            q.pipeline.watermark("date_time", seen["mx"])
    else:
        q = build_q8(capacity=1 << 12, out_cap=1 << 11)

        def prepare():
            ev = gen.next_chunks(2000, 4096)
            p, a = ev["person"], ev["auction"]
            if p is not None:
                q.pipeline.push_left(p.select(["id", "name", "date_time"]))
            if a is not None:
                q.pipeline.push_right(a.select(["seller", "date_time"]))

    wrappers = fuse_pipeline(q.pipeline, label=query)
    assert len(wrappers) == 1 and wrappers[0].covers_whole_chain
    assert q.pipeline._fused is not None
    return prepare, q.pipeline.barrier, after, _nothing


# id -> (leg builder, warm epochs, dispatches per steady barrier, label)
_DISPATCH_CASES = {
    "q5-unfused": (lambda: _q5_leg(fused=False), 2, 4, None),
    "q5-fused": (lambda: _q5_leg(fused=True), 2, 1, "fused:q5"),
    "q7-fused": (lambda: _two_input_leg("q7"), 4, 1, "fused:q7"),
    "q8-fused": (lambda: _two_input_leg("q8"), 4, 1, "fused:q8"),
    "q5-fused-digests": (
        lambda: _q5_leg(fused=True, digests=True), 2, 1, "fused:q5",
    ),
    "q5-fused-freshness": (
        lambda: _q5_leg(fused=True, runtime=True), 2, 1, "fused:q5",
    ),
}


@pytest.mark.parametrize("case", list(_DISPATCH_CASES))
def test_dispatches_per_barrier(case, monkeypatch):
    """Steady state, exact: the interpreted q5 walk costs 4 device
    dispatches a barrier; every fused shape — q5's hop->agg->flush->MV,
    q7's and q8's side chains x join x MV — costs ONE, attributed
    ``fused:<fragment>``, and neither the digest lanes
    (``RW_STATE_DIGEST``) nor freshness tracking adds one. A fragment
    that silently de-fuses (q7 interpreted costs ~31) fails here, and
    so does a steady epoch that compiles anything."""
    build, warm, want, label = _DISPATCH_CASES[case]
    if case == "q5-fused-digests":
        monkeypatch.setenv("RW_STATE_DIGEST", "1")
    prepare, counted, after, check = build()

    def epoch(per=None):
        prepare()
        base = PROFILER.total_dispatches()
        counted()
        if per is not None:
            per.append(PROFILER.total_dispatches() - base)
        after()

    for _ in range(warm):
        epoch()  # compiles + growth transitions
    recompiles = RecompileWatch()
    recompiles.snapshot()
    programs = fused_cache_stats()["compiled_programs"]
    PROFILER.reset()
    PROFILER.enable()
    try:
        per = []
        for _ in range(3):
            epoch(per)
        counts = PROFILER.dispatch_counts()
    finally:
        PROFILER.disable()
        PROFILER.reset()
    assert per == [float(want)] * 3, per
    if label is not None:
        assert counts.get(label, 0) >= 3, counts
    # and the steady epochs compiled nothing: no step kernel and no
    # fused program re-traced (zero recompile hazards per query)
    assert recompiles.deltas(record=False) == {}
    assert fused_cache_stats()["compiled_programs"] == programs
    check()


def test_fused_fragments_report_shapes():
    q5 = build_q5_lite(capacity=1 << 10, state_cleaning=False)
    fuse_pipeline(q5.pipeline, label="q5")
    rep = fused_fragments(q5.pipeline)
    assert rep["count"] == 1 and rep["whole_chain"] is True
    assert rep["fragments"] == ["q5[3]"]


def test_no_orphaned_state_buffers_across_fused_barriers():
    """Donation contract: steady-state fused barriers must not leak
    device buffers (the donated state is consumed, the returned state
    replaces it — live-array count stays flat)."""
    q5 = build_q5_lite(capacity=1 << 10, state_cleaning=False)
    fuse_pipeline(q5.pipeline)
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=20_000))
    bid = gen.next_chunks(500, 512)["bid"].select(["auction", "date_time"])

    def epoch():
        q5.pipeline.push(bid)
        q5.pipeline.barrier()

    for _ in range(3):  # warm: compiles + capacity transitions
        epoch()
    counts = []
    for _ in range(4):
        epoch()
        counts.append(len(jax.live_arrays()))
    assert max(counts) - min(counts) <= 2, (
        f"live device arrays grew across fused barriers: {counts}"
    )


# ---------------------------------------------------------------------------
# wrapper mechanics
# ---------------------------------------------------------------------------


def _bid_chunk(gen, n=400, cap=512):
    c = None
    while c is None:
        c = gen.next_chunks(n, cap)["bid"]
    return c.select(["auction", "date_time"])


def test_fused_flush_rounds_cover_small_out_cap():
    """Regression (code-review finding): the fused flush-round count
    must be derived AFTER the buffered epoch lands in the dirty bound.
    With out_cap far below the epoch's distinct groups, an early round
    count silently dropped every group past the first round — the
    fused MV diverged from the interpreted twin permanently."""
    mk = lambda: build_q5_lite(capacity=1 << 10, state_cleaning=False)

    def drive(q5, fuse):
        q5.agg.out_cap = 128  # << distinct (auction, window) groups
        if fuse:  # fuse AFTER sizing: the plan captures out_cap
            fuse_pipeline(q5.pipeline)
        gen = NexmarkGenerator(NexmarkConfig(first_event_rate=20_000))
        for _ in range(2):
            q5.pipeline.push(_bid_chunk(gen, 800, 1024))
            q5.pipeline.barrier()
        return q5.mview.snapshot()

    interp = drive(mk(), fuse=False)
    fused = drive(mk(), fuse=True)
    assert len(interp) > 128  # the workload actually exceeds out_cap
    assert fused == interp


def test_signature_change_mid_epoch_flushes_buffer():
    mk = lambda: build_q5_lite(capacity=1 << 10, state_cleaning=False)
    a, b = mk(), mk()
    fuse_pipeline(b.pipeline)
    gen1 = NexmarkGenerator(NexmarkConfig(first_event_rate=20_000))
    gen2 = NexmarkGenerator(NexmarkConfig(first_event_rate=20_000))
    for q5, gen in ((a, gen1), (b, gen2)):
        c1 = _bid_chunk(gen, 400, 512)
        c2 = _bid_chunk(gen, 900, 1024)  # different capacity: new sig
        q5.pipeline.push(c1)
        q5.pipeline.push(c2)
        q5.pipeline.push(_bid_chunk(gen, 400, 512))
        q5.pipeline.barrier()
    assert a.mview.snapshot() == b.mview.snapshot()


def test_overflow_latch_still_raises_at_finish_barrier():
    """The agg's MAX_PROBE overflow latch rides the fused program's
    packed scalars and raises at the wrapper's finish_barrier — same
    raise point as the interpreted path."""
    q5 = build_q5_lite(capacity=1 << 10, state_cleaning=False)
    (wrapper,) = fuse_pipeline(q5.pipeline)
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=20_000))
    q5.pipeline.push(_bid_chunk(gen))
    q5.pipeline.barrier()
    # force the latch: a poisoned dropped flag must surface as the
    # hash-table overflow error when the staged scalars materialize
    q5.agg.dropped = jnp.ones((), jnp.bool_)
    with pytest.raises(RuntimeError, match="overflowed MAX_PROBE"):
        q5.pipeline.push(_bid_chunk(gen))
        q5.pipeline.barrier()
    assert wrapper.agg is q5.agg  # members stayed the system of record


def test_fuse_chain_falls_back_around_unfusible_ops():
    """Host-bound / opaque members break the run: interpretation is
    the automatic per-run fallback, not a process-wide switch — and an
    agg whose flush exits to an interpreted consumer epoch-batches
    instead of fusing (the exact-sliced flush stays)."""
    from risingwave_tpu.executors.base import Executor
    from risingwave_tpu.executors.epoch_batch import (
        EpochBatchedAggExecutor,
    )
    from risingwave_tpu.executors.hash_agg import HashAggExecutor
    from risingwave_tpu.ops.agg import AggCall

    class HostOp(Executor):  # no pure_step -> not fusible
        pass

    agg = HashAggExecutor(
        group_keys=("k",),
        calls=(AggCall("count_star", None, "n"),),
        schema_dtypes={"k": jnp.int64},
        capacity=64,
        out_cap=32,
    )
    host = HostOp()
    out = fuse_chain([host, agg], label="t")
    assert out[0] is host
    assert isinstance(out[1], EpochBatchedAggExecutor)
    assert out[1].agg is agg
    # pure-only runs stay interpreted unless defer_pure opts in
    from risingwave_tpu.executors.hop_window import HopWindowExecutor

    hop = HopWindowExecutor("t", 10, 10)
    assert fuse_chain([hop, host], label="t") == [hop, host]


def test_expand_fused_exposes_members_for_padding_and_governor():
    q5 = build_q5_lite(capacity=1 << 10, state_cleaning=False)
    fuse_pipeline(q5.pipeline)
    exs = expand_fused(q5.pipeline.executors)
    names = [type(e).__name__ for e in exs]
    assert "HashAggExecutor" in names
    assert "DeviceMaterializeExecutor" in names
    assert all(not isinstance(e, FusedChainExecutor) for e in exs)


def test_governor_bucket_pin_holds_fused_shapes_steady():
    """After a governor pin, steady fused barriers mint ZERO new
    compiled programs (exactly the recompile-storm throttle the fused
    step needs: pinned buckets = closed shape set)."""
    from risingwave_tpu.analysis.jax_sanitizer import RecompileWatch

    q5 = build_q5_lite(capacity=1 << 10, state_cleaning=False)
    fuse_pipeline(q5.pipeline)
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=20_000))
    bid = _bid_chunk(gen)

    def epoch():
        q5.pipeline.push(bid)
        q5.pipeline.barrier()

    epoch()
    epoch()
    pin_agg = q5.agg.pin_max_bucket()
    pin_mv = q5.mview.pin_max_bucket()
    assert pin_agg["pinned_cap"] == q5.agg.table.capacity
    assert pin_mv["pinned_cap"] == q5.mview.table.capacity
    watch = RecompileWatch()
    watch.snapshot()
    for _ in range(3):
        epoch()
    assert watch.deltas() == {}, watch.deltas()


# ---------------------------------------------------------------------------
# graph runtime: auto-fusion, rebuild re-fuses, recovery with fusion armed
# ---------------------------------------------------------------------------


def _catalog_factory(capacity=1 << 11):
    from risingwave_tpu.sql import Catalog, StreamPlanner

    catalog = Catalog({"bid": BID_SCHEMA})
    return lambda: StreamPlanner(catalog, capacity=capacity)


def _graph_mv(parallelism=1):
    from risingwave_tpu.runtime.fragmenter import graph_planned_mv

    return graph_planned_mv(
        _catalog_factory(), Q5_SQL, parallelism=parallelism
    )


def _fused_in_actors(gp):
    return [
        e
        for a in gp.graph.actors
        for e in a.executors
        if isinstance(e, FusedChainExecutor)
    ]


def test_graph_actors_fuse_by_default_and_rebuild_refuses():
    mv = _graph_mv()
    try:
        assert _fused_in_actors(mv.pipeline), "graph chain did not fuse"
        gen = NexmarkGenerator(NexmarkConfig(first_event_rate=20_000))
        bid = _bid_chunk(gen, 600, 1024)
        mv.pipeline.push(bid)
        mv.pipeline.barrier()
        before = mv.mview.snapshot()
        assert before
        # rebuild (the recovery path's actor replacement): fresh actors
        # around the SAME executor objects must RE-FUSE automatically
        mv.pipeline.rebuild()
        assert _fused_in_actors(mv.pipeline), "rebuilt actors lost fusion"
        assert mv.mview.snapshot() == before  # state survived the rebuild
        mv.pipeline.push(bid)
        mv.pipeline.barrier()
        after = mv.mview.snapshot()
        assert set(after) == set(before)
        assert all(after[k][0] == 2 * before[k][0] for k in before)
    finally:
        mv.pipeline.close()


class _PoisonOnce:
    """Raises at the first armed barrier, then behaves forever after
    (the transient-fault model of the recovery suites)."""

    def __init__(self):
        self.armed = False
        self.fired = 0

    def apply(self, chunk):
        return [chunk]

    def on_barrier(self, b):
        if self.armed:
            self.armed = False
            self.fired += 1
            raise RuntimeError("poisoned epoch (injected)")
        return []

    def on_watermark(self, wm):
        return wm, []

    def emit_watermark(self):
        return None

    def finish_barrier(self):
        return None

    def pure_step(self):
        return None


def test_actor_kill_recovery_with_fusion_armed():
    """Actor-kill chaos with the fused step armed: the poisoned
    barrier kills the actor thread, the watchdog rebuilds the graph,
    the rebuilt fragment RE-FUSES around the restored state, and the
    stream continues exact (the serial interpreted twin is the
    oracle)."""
    from risingwave_tpu.runtime.fragmenter import GraphPipeline
    from risingwave_tpu.runtime.graph import FragmentSpec
    from risingwave_tpu.runtime.runtime import StreamingRuntime
    from risingwave_tpu.storage.object_store import MemObjectStore

    poison = _PoisonOnce()
    q5 = build_q5_lite(capacity=1 << 11, state_cleaning=False)
    chain = [poison] + list(q5.pipeline.executors)
    gp = GraphPipeline(
        [FragmentSpec("gq5", lambda i, ch=tuple(chain): list(ch))],
        {"single": "gq5"},
        "gq5",
        [q5.agg, q5.mview],
    )
    rt = StreamingRuntime(
        MemObjectStore(), async_checkpoint=False, auto_recover=True
    )
    rt.register("gq5", gp)
    twin = build_q5_lite(capacity=1 << 11, state_cleaning=False)
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=20_000))
    first_graph = gp.graph
    try:
        assert _fused_in_actors(gp), "poisoned chain's fusible run lost"
        for epoch in range(5):
            chunk = _bid_chunk(gen, 500, 1024)
            if epoch == 2:
                poison.armed = True
            for _attempt in range(4):
                rt.push("gq5", chunk)
                before = rt.mgr.max_committed_epoch
                rt.barrier()
                if rt.mgr.max_committed_epoch > before:
                    break
            else:
                raise AssertionError("epoch never committed")
            twin.pipeline.push(chunk)
            twin.pipeline.barrier()
        assert rt.auto_recoveries == 1 and poison.fired == 1
        assert gp.graph is not first_graph  # actors were rebuilt
        assert _fused_in_actors(gp), "recovered graph lost fusion"
        assert q5.mview.snapshot() == twin.mview.snapshot()
    finally:
        gp.close()


# ---------------------------------------------------------------------------
# two-input fusion (PR 13): q7/q8 whole-pipeline programs, masked-lane
# padding proofs, K-barrier pipelining, recovery, refusal provenance
# ---------------------------------------------------------------------------

import numpy as np

from risingwave_tpu.array.chunk import StreamChunk


def test_two_input_fallback_twin_bit_identical(monkeypatch):
    """RW_FUSED_TWO_INPUT=0: the pre-PR-13 per-chain fallback
    (epoch-batched agg side, interpreted join) armed on q7 must stay
    bit-identical — and the join-fed MV tail now FUSES under the
    lattice-compatibility rule (the old hard carve-out is gone: the
    join's fixed out_cap emission is a closed shape family)."""
    from risingwave_tpu.executors.epoch_batch import (
        EpochBatchedAggExecutor,
    )

    mk = lambda: build_q7(
        capacity=1 << 13,
        agg_capacity=1 << 11,
        filter_capacity=1 << 11,
        out_cap=1 << 11,
    )
    interp = _drive_q7(mk(), fuse=False)
    monkeypatch.setenv("RW_FUSED_TWO_INPUT", "0")
    q7 = mk()
    wrappers = fuse_pipeline(q7.pipeline, label="q7")
    assert q7.pipeline._fused is None
    assert any(
        isinstance(e, EpochBatchedAggExecutor) for e in q7.pipeline.right
    )
    # the satellite bugfix: the MV tail fed by the (fixed-emission)
    # join fuses instead of staying interpreted
    assert len(wrappers) == 1 and isinstance(
        wrappers[0], FusedChainExecutor
    )
    assert wrappers[0].members == [q7.mview]
    monkeypatch.delenv("RW_FUSED_TWO_INPUT")
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=10_000))
    snaps, mx = [], 0
    for _ in range(4):
        for _ in range(2):
            bid = gen.next_chunks(1200, 2048)["bid"]
            if bid is None:
                continue
            bid = bid.select(["auction", "bidder", "price", "date_time"])
            q7.pipeline.push_left(bid)
            q7.pipeline.push_right(bid)
            mx = max(mx, int(bid.to_numpy()["date_time"].max()))
        q7.pipeline.barrier()
        q7.pipeline.watermark("date_time", mx)
        snaps.append(q7.mview.snapshot())
    for e, (a, b) in enumerate(zip(interp, snaps)):
        assert a == b, f"epoch {e}: fallback q7 MV diverged"


def test_two_input_refusal_records_provenance():
    """An unbucketed join (the RW-E803 wedge twin) must be REFUSED
    with RW-E807 provenance — never a silent interpret fallback."""
    fusion_refusals(clear=True)
    q7 = build_q7(capacity=1 << 10, bucketed=False)
    fuse_pipeline(q7.pipeline, label="q7twin")
    assert q7.pipeline._fused is None
    recs = fusion_refusals()
    assert any(
        r["code"] == "RW-E807"
        and r["fragment"] == "q7twin"
        and "lattice" in r["message"]
        for r in recs
    ), recs


def test_masked_lane_padding_inert():
    """The join's probe/build kernels must treat padded (invalid)
    lanes as provably inert: the same logical rows arriving at an
    exact-full 2^k capacity and padded into the one-over 2^(k+1)
    bucket produce identical emissions and identical downstream MVs —
    the proof that lattice-padded flush lanes cost one masked device
    op, not wrong answers (the pre-bucketing '80x slower exact-slice'
    contract is retired)."""
    from risingwave_tpu.executors.hash_join import HashJoinExecutor
    import jax.numpy as jnp

    def mk_join():
        return HashJoinExecutor(
            left_keys=("w", "p"),
            right_keys=("mw", "mp"),
            left_dtypes={"w": jnp.int64, "p": jnp.int64, "b": jnp.int64},
            right_dtypes={"mw": jnp.int64, "mp": jnp.int64},
            capacity=1 << 8,
            fanout=4,
            out_cap=1 << 6,
        )

    k = 3  # 2^3 = 8 rows
    n = 1 << k
    left = {
        "w": np.arange(n, dtype=np.int64),
        "p": np.full(n, 7, np.int64),
        "b": np.arange(n, dtype=np.int64) + 100,
    }
    right = {
        "mw": np.arange(n, dtype=np.int64),
        "mp": np.full(n, 7, np.int64),
    }

    def rows_of(chunks):
        out = []
        for c in chunks:
            d = c.to_numpy(with_ops=True)
            sel = np.flatnonzero(np.asarray(c.valid))
            out.extend(
                tuple(int(d[nm][i]) for nm in sorted(d))
                for i in sel
            )
        return sorted(out)

    emitted = {}
    for cap in (n, 2 * n):  # exact-full 2^k vs one-over bucket 2^k+1
        j = mk_join()
        j.apply_left(StreamChunk.from_numpy(left, n))
        outs = j.apply_right(StreamChunk.from_numpy(right, cap))
        j.on_barrier(None)
        emitted[cap] = rows_of(outs)
    assert emitted[n] == emitted[2 * n]
    assert len(emitted[n]) == n  # every pair matched exactly once


def test_two_input_flush_rounds_exact_and_one_over():
    """Fused q7 flush lanes: an epoch with dirty groups exactly filling
    one flush round (2^k) and one with a single group over (2^k + 1,
    padded into a second, mostly-masked round) must both be
    bit-identical to the interpreted twin — masked trailing rounds are
    no-ops, never data."""

    def bid_chunk(rows, cap=64):
        cols = {
            "auction": np.array([r[0] for r in rows], np.int64),
            "bidder": np.array([r[1] for r in rows], np.int64),
            "price": np.array([r[2] for r in rows], np.int64),
            "date_time": np.array([r[3] for r in rows], np.int64),
        }
        return StreamChunk.from_numpy(cols, cap)

    def drive(fuse, n_windows):
        q7 = build_q7(capacity=1 << 10, fanout=8, out_cap=1 << 10)
        q7.agg.out_cap = 8  # flush drains 8 dirty groups per round
        if fuse:
            (w,) = fuse_pipeline(q7.pipeline, label="q7small")
            assert w.plan.right.agg.out_cap == 8
        snaps = []
        for epoch in range(2):
            rows = [
                (w_, 10 + w_, 100 + w_ + epoch, w_ * 10_000 + 5)
                for w_ in range(n_windows)
            ]
            c = bid_chunk(rows)
            q7.pipeline.push_left(c)
            q7.pipeline.push_right(c)
            q7.pipeline.barrier()
            snaps.append(q7.mview.snapshot())
        return snaps

    for n_windows in (8, 9):  # 2^k exact-full, 2^k + 1 one-over
        a = drive(False, n_windows)
        b = drive(True, n_windows)
        assert a == b, f"{n_windows} windows: fused flush diverged"
        assert len(a[-1]) == n_windows


def test_two_input_donation_census_flat():
    """Donation contract: steady fused two-input barriers must not
    leak device buffers (donated state consumed, returned state
    replaces it)."""
    q8 = build_q8(capacity=1 << 10, out_cap=1 << 9)
    fuse_pipeline(q8.pipeline, label="q8")
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=10_000))

    def epoch():
        ev = gen.next_chunks(800, 1024)
        p, a = ev["person"], ev["auction"]
        if p is not None:
            q8.pipeline.push_left(p.select(["id", "name", "date_time"]))
        if a is not None:
            q8.pipeline.push_right(a.select(["seller", "date_time"]))
        q8.pipeline.barrier()

    for _ in range(4):
        epoch()
    counts = []
    for _ in range(4):
        epoch()
        counts.append(len(jax.live_arrays()))
    assert max(counts) - min(counts) <= 4, (
        f"live device arrays grew across fused two-input barriers: "
        f"{counts}"
    )


def _feed_q8(q8, gen, n):
    for _ in range(n):
        chunks = gen.next_chunks(2000, 2048)
        if chunks["person"] is not None:
            q8.pipeline.push_left(
                chunks["person"].select(["id", "name", "date_time"])
            )
        if chunks["auction"] is not None:
            q8.pipeline.push_right(
                chunks["auction"].select(["seller", "date_time"])
            )
        q8.pipeline.barrier()


def test_two_input_recovery_refuses():
    """Kill-and-recover with the two-input program armed: members stay
    the system of record, so checkpoint/restore work unchanged and a
    FRESH build re-fuses into the same compiled program (value-hashable
    plan statics)."""
    from risingwave_tpu.connectors.nexmark import NexmarkGenerator
    from risingwave_tpu.storage import CheckpointManager, MemObjectStore

    store = MemObjectStore()
    mgr = CheckpointManager(store)
    dicts = NexmarkGenerator.make_dictionaries()
    gen = NexmarkGenerator(NexmarkConfig(), dictionaries=dicts)

    mk = lambda: build_q8(capacity=1 << 12, fanout=8, out_cap=1 << 14)
    q8 = mk()
    fuse_pipeline(q8.pipeline, label="q8")
    for _ in range(3):
        _feed_q8(q8, gen, 1)
        mgr.commit_epoch(q8.pipeline.epoch, q8.pipeline.executors)
    snap = q8.mview.snapshot()
    assert len(snap) > 20

    q8b = mk()
    CheckpointManager(store).recover(q8b.pipeline.executors)
    wrappers = fuse_pipeline(q8b.pipeline, label="q8")
    assert len(wrappers) == 1  # restored members re-fuse
    assert q8b.mview.snapshot() == snap

    gen_b = NexmarkGenerator(NexmarkConfig(), dictionaries=dicts)
    for _ in range(3):
        gen_b.next_chunks(2000, 2048)
    _feed_q8(q8, gen, 2)
    _feed_q8(q8b, gen_b, 2)
    assert q8b.mview.snapshot() == q8.mview.snapshot()


@pytest.mark.parametrize("depth", [2, 4])
def test_pipeline_depth_twins_and_checkpoint_boundary(depth):
    """K-barrier pipelining: K in {1, K} produce bit-identical MVs at
    EVERY barrier; mid-window barriers defer the blocking scalar read
    (the host leaves the steady state), the K-boundary drains; and a
    checkpoint taken at the K-boundary recovers exactly."""
    from risingwave_tpu.connectors.nexmark import NexmarkGenerator
    from risingwave_tpu.storage import CheckpointManager, MemObjectStore

    dicts = NexmarkGenerator.make_dictionaries()

    def drive(d, nb=8):
        gen = NexmarkGenerator(
            NexmarkConfig(first_event_rate=10_000), dictionaries=dicts
        )
        q8 = build_q8(capacity=1 << 12, out_cap=1 << 11)
        (w,) = fuse_pipeline(
            q8.pipeline, label="q8", pipeline_depth=d
        )
        assert w.depth == d
        snaps = []
        for i in range(nb):
            _feed_q8(q8, gen, 1)
            # mid-window barriers hold their staged pack (no blocking
            # read); the K-boundary drains them all
            expect = 0 if (i + 1) % d == 0 else (i + 1) % d
            assert len(w._pending) == expect, (i, d, len(w._pending))
            snaps.append(q8.mview.snapshot())
        return q8, w, snaps

    _q1, _w1, s1 = drive(1)
    q8k, wk, sk = drive(depth)
    for e in range(8):
        assert s1[e] == sk[e], f"K={depth} diverged at barrier {e}"

    # checkpoint at the K-boundary (pending drained), then recover
    store = MemObjectStore()
    mgr = CheckpointManager(store)
    assert wk._pending == []  # 8 % depth == 0: boundary just drained
    mgr.commit_epoch(q8k.pipeline.epoch, q8k.pipeline.executors)
    q8r = build_q8(capacity=1 << 12, out_cap=1 << 11)
    CheckpointManager(store).recover(q8r.pipeline.executors)
    assert q8r.mview.snapshot() == sk[-1]


def test_two_input_governor_pin_holds_shapes_steady():
    """After a governor pin on every two-input member, steady fused
    barriers mint ZERO new compiled programs."""
    from risingwave_tpu.analysis.jax_sanitizer import RecompileWatch

    q8 = build_q8(capacity=1 << 10, out_cap=1 << 9)
    fuse_pipeline(q8.pipeline, label="q8")
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=10_000))

    def epoch():
        ev = gen.next_chunks(800, 1024)
        p, a = ev["person"], ev["auction"]
        if p is not None:
            q8.pipeline.push_left(p.select(["id", "name", "date_time"]))
        if a is not None:
            q8.pipeline.push_right(a.select(["seller", "date_time"]))
        q8.pipeline.barrier()

    epoch()
    epoch()
    for ex in expand_fused([q8.pipeline._fused]):
        pin = getattr(ex, "pin_max_bucket", None)
        if pin is not None:
            pin()
    watch = RecompileWatch()
    watch.snapshot()
    for _ in range(3):
        epoch()
    assert watch.deltas() == {}, watch.deltas()


def test_two_input_overflow_latch_raises_at_finish():
    """A poisoned member latch must surface at finish_barrier through
    the packed scalar lane — same raise point as interpreted."""
    import jax.numpy as jnp

    q8 = build_q8(capacity=1 << 10, out_cap=1 << 9)
    (w,) = fuse_pipeline(q8.pipeline, label="q8")
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=10_000))
    ev = gen.next_chunks(800, 1024)
    q8.pipeline.push_left(ev["person"].select(["id", "name", "date_time"]))
    q8.pipeline.barrier()
    dedup = q8.pipeline.left[1]
    dedup._dropped = jnp.ones((), jnp.bool_)
    with pytest.raises(RuntimeError, match="dedup table overflowed"):
        q8.pipeline.push_left(
            ev["person"].select(["id", "name", "date_time"])
        )
        q8.pipeline.barrier()
    assert w.l_stateful is dedup  # members stayed the system of record
