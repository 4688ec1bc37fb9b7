"""State >> HBM: evict durable groups, fold them back on next touch
(VERDICT r2 missing #6; reference: LRU state-table caches over Hummock,
hash_agg.rs:49 + compute memory controller)."""

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.hash_agg import HashAggExecutor
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.runtime.fused_step import (
    FusedChainExecutor,
    FusedTwoInputExecutor,
    fuse_pipeline,
)
from risingwave_tpu.runtime.pipeline import Pipeline
from risingwave_tpu.executors.materialize import (
    DeviceMaterializeExecutor,
    MaterializeExecutor,
)
from risingwave_tpu.storage.object_store import MemObjectStore
from risingwave_tpu.storage.state_table import CheckpointManager
from risingwave_tpu.trace import TRACER
from risingwave_tpu.types import Op

DT = {"k": jnp.int64, "v": jnp.int64}
CAP = 64


def _chunk(rows):
    return StreamChunk.from_numpy(
        {
            "k": np.asarray([r[0] for r in rows], np.int64),
            "v": np.asarray([r[1] for r in rows], np.int64),
        },
        CAP,
        ops=np.asarray([r[2] for r in rows], np.int32),
    )


def _mk(cap=1 << 12):
    return HashAggExecutor(
        group_keys=("k",),
        calls=(
            AggCall("count_star", None, "cnt"),
            AggCall("sum", "v", "s"),
        ),
        schema_dtypes=DT,
        capacity=cap,
        out_cap=1 << 10,
        table_id="cold1",
    )


def _replay(snap, chunks):
    for c in chunks:
        d = c.to_numpy(with_ops=True)
        for i in range(len(d["__op__"])):
            key = (int(d["k"][i]),)
            if d["__op__"][i] in (Op.DELETE, Op.UPDATE_DELETE):
                snap.pop(key, None)
            else:
                row = []
                for n in ("cnt", "s"):
                    nl = d.get(n + "__null")
                    row.append(None if nl is not None and nl[i] else int(d[n][i]))
                snap[key] = tuple(row)
    return snap


def test_evict_then_merge_on_return():
    store = MemObjectStore()
    mgr = CheckpointManager(store)
    ex = _mk()
    ex.cold_reader = lambda keys: mgr.get_rows("cold1", keys)
    snap = {}

    # 500 groups, checkpoint -> all durable
    rows = [(k, k * 3, Op.INSERT) for k in range(500)]
    for at in range(0, len(rows), CAP):
        _replay(snap, ex.apply(_chunk(rows[at : at + CAP])))
    _replay(snap, ex.on_barrier(None))
    mgr.commit_epoch(1 << 16, [ex])

    before = ex.state_nbytes()
    evicted = ex.evict_cold()
    assert evicted == 500
    assert ex.state_nbytes() < before  # capacity shrank: HBM freed
    assert int(ex.table.occupancy()) == 0

    # touch 40 evicted groups (+ some deletes) and 10 brand-new ones:
    # merged results must continue exactly from the durable state
    upd = [(k, 1, Op.INSERT) for k in range(40)]
    upd += [(k, k * 3, Op.DELETE) for k in range(5)]  # retract cold rows
    upd += [(k, 7, Op.INSERT) for k in range(1000, 1010)]
    _replay(snap, ex.apply(_chunk(upd[:CAP])))
    _replay(snap, ex.apply(_chunk(upd[CAP:])))
    _replay(snap, ex.on_barrier(None))

    want = {}
    for k in range(500):
        cnt, s = 1, k * 3
        if k < 40:
            cnt, s = cnt + 1, s + 1
        if k < 5:
            cnt, s = cnt - 1, s - k * 3
        want[(k,)] = (cnt, s)
    for k in range(1000, 1010):
        want[(k,)] = (1, 7)
    assert snap == want

    # checkpoint again, kill, recover: merged state must round-trip
    mgr.commit_epoch(2 << 16, [ex])
    ex2 = _mk()
    CheckpointManager(store).recover([ex2])
    snap2 = {}
    _replay(snap2, ex2.on_barrier(None))  # nothing dirty -> no emissions
    assert snap2 == {}
    _replay(snap2, ex2.apply(_chunk([(3, 100, Op.INSERT)])))
    _replay(snap2, ex2.on_barrier(None))
    assert snap2[(3,)][0] == want[(3,)][0] + 1


def test_runtime_memory_budget_triggers_eviction():
    store = MemObjectStore()
    rt = StreamingRuntime(store, async_checkpoint=False,
                          memory_budget_bytes=1)  # absurdly small
    agg = _mk()
    mv = MaterializeExecutor(pk=("k",), columns=("cnt", "s"),
                             table_id="cold1.mv")
    rt.register("f", Pipeline([agg, mv]))
    rt.push("f", _chunk([(k, k, Op.INSERT) for k in range(50)]))
    rt.barrier()  # checkpoint -> durable -> budget forces eviction
    assert int(agg.table.occupancy()) == 0  # everything evicted
    rt.push("f", _chunk([(7, 5, Op.INSERT)]))
    rt.barrier()
    assert mv.snapshot()[(7,)] == (2, 12)  # merged back exactly


def test_cold_min_max_merge_append_only():
    """Extremes merge in the order-key domain on return from cold."""
    store = MemObjectStore()
    mgr = CheckpointManager(store)
    ex = HashAggExecutor(
        group_keys=("k",),
        calls=(AggCall("min", "v", "mn"), AggCall("max", "v", "mx")),
        schema_dtypes=DT, capacity=1 << 10, out_cap=1 << 9,
        table_id="cold1",
    )
    ex.cold_reader = lambda keys: mgr.get_rows("cold1", keys)
    snap = {}

    def rep(chunks):
        for c in chunks:
            d = c.to_numpy(with_ops=True)
            for i in range(len(d["__op__"])):
                key = (int(d["k"][i]),)
                if d["__op__"][i] in (Op.DELETE, Op.UPDATE_DELETE):
                    snap.pop(key, None)
                else:
                    snap[key] = (int(d["mn"][i]), int(d["mx"][i]))

    rep(ex.apply(_chunk([(1, 50, Op.INSERT), (1, 10, Op.INSERT)])))
    rep(ex.on_barrier(None))
    mgr.commit_epoch(1 << 16, [ex])
    assert ex.evict_cold() == 1

    rep(ex.apply(_chunk([(1, 30, Op.INSERT), (1, 99, Op.INSERT)])))
    rep(ex.on_barrier(None))
    assert snap[(1,)] == (10, 99)  # cold min=10 survives, new max=99


def test_join_cold_tier_eviction_and_fault_in():
    """Join state >> HBM (VERDICT r3 #8): durable buckets evict under a
    memory budget and fault back in when their key is touched again —
    emissions stay exact vs an unbudgeted twin, including recovery."""
    from risingwave_tpu.executors.hash_join import HashJoinExecutor

    L = {"lk": jnp.int64, "lv": jnp.int64}
    R = {"rk": jnp.int64, "rv": jnp.int64}

    def mk(tid):
        return HashJoinExecutor(
            ("lk",), ("rk",), L, R,
            capacity=1 << 10, fanout=8, out_cap=1 << 12, table_id=tid,
        )

    store = MemObjectStore()
    rt = StreamingRuntime(
        store, async_checkpoint=False, memory_budget_bytes=1
    )
    j = mk("cj")
    mv = MaterializeExecutor(
        pk=("lk", "lv", "rk", "rv"), columns=(), table_id="cj.mv"
    )
    from risingwave_tpu.runtime.pipeline import TwoInputPipeline

    rt.register("j", TwoInputPipeline([], [], j, [mv]))

    twin = mk("cj_twin")
    twin_mv = MaterializeExecutor(
        pk=("lk", "lv", "rk", "rv"), columns=(), table_id="twin.mv"
    )

    rng = np.random.default_rng(41)

    def lchunk(ks, vs):
        return StreamChunk.from_numpy(
            {"lk": np.asarray(ks, np.int64), "lv": np.asarray(vs, np.int64)},
            32,
        )

    def rchunk(ks, vs):
        return StreamChunk.from_numpy(
            {"rk": np.asarray(ks, np.int64), "rv": np.asarray(vs, np.int64)},
            32,
        )

    seen_keys = []
    for epoch in range(8):
        # revisit OLD keys often: the whole point is faulting evicted
        # buckets back in before probing/appending
        ks = [
            int(rng.choice(seen_keys))
            if seen_keys and rng.random() < 0.5
            else int(rng.integers(0, 64)) + 100 * epoch
            for _ in range(6)
        ]
        seen_keys.extend(ks)
        lvs = rng.integers(0, 9, 6).tolist()
        rvs = rng.integers(0, 9, 6).tolist()
        lc, rc = lchunk(ks, lvs), rchunk(ks, rvs)
        rt.push("j", lc, side="left")
        rt.push("j", rc, side="right")
        rt.barrier()  # budget=1 byte: evicts EVERYTHING durable
        for out in twin.apply_left(lc):
            twin_mv.apply(out)
        for out in twin.apply_right(rc):
            twin_mv.apply(out)
        twin.on_barrier(None)
        twin_mv.on_barrier(None)
        assert j._evicted["left"] or j._evicted["right"] or epoch == 0

    assert mv.snapshot() == twin_mv.snapshot()
    assert len(mv.snapshot()) > 20

    # kill + recover: evicted state lives in the store; a fresh join
    # restores EVERYTHING and continues exactly. Quiesce the old
    # node's compactor first (a killed node's compactor is dead too).
    rt.wait_compaction()
    rt2 = StreamingRuntime(store, async_checkpoint=False)
    j2 = mk("cj")
    mv2 = MaterializeExecutor(
        pk=("lk", "lv", "rk", "rv"), columns=(), table_id="cj.mv"
    )
    rt2.register("j", TwoInputPipeline([], [], j2, [mv2]), backfill=False)
    rt2.recover()
    assert mv2.snapshot() == twin_mv.snapshot()
    ks = seen_keys[:5]
    lc = lchunk(ks, [7] * 5)
    rt2.push("j", lc, side="left")
    rt2.barrier()
    for out in twin.apply_left(lc):
        twin_mv.apply(out)
    twin.on_barrier(None)
    assert mv2.snapshot() == twin_mv.snapshot()


def test_join_evicted_keys_expire_under_watermark():
    """A watermark closing a window must close EVICTED buckets too:
    they never fault back in, and recovery does not resurrect them
    (review r4: expire_keys reaches only resident slots)."""
    from risingwave_tpu.executors.hash_join import HashJoinExecutor

    L = {"lw": jnp.int64, "lv": jnp.int64}
    R = {"rw": jnp.int64, "rv": jnp.int64}

    def mk():
        return HashJoinExecutor(
            ("lw",), ("rw",), L, R,
            capacity=1 << 8, fanout=4, out_cap=1 << 9,
            window_cols=("lw", "rw"), table_id="wj",
        )

    from risingwave_tpu.executors.base import Watermark

    mgr = CheckpointManager(MemObjectStore())
    j = mk()
    j.cold_get_rows = mgr.get_rows
    j.apply_left(
        StreamChunk.from_numpy(
            {"lw": np.asarray([10, 20], np.int64),
             "lv": np.asarray([1, 2], np.int64)}, 8,
        )
    )
    j.on_barrier(None)
    mgr.commit_staged(1, mgr.stage([j]))
    assert j.evict_cold() == 2
    # watermark closes window 10 on BOTH sides
    j.on_watermark(Watermark("lw", 15))
    j.on_watermark(Watermark("rw", 15))
    assert j._evicted["left"] == {(20,)}
    # a late probe of the closed window matches NOTHING
    outs = j.apply_right(
        StreamChunk.from_numpy(
            {"rw": np.asarray([10], np.int64),
             "rv": np.asarray([9], np.int64)}, 8,
        )
    )
    d = outs[0].to_numpy(with_ops=True)
    assert len(d["__op__"]) == 0
    j.on_barrier(None)
    mgr.commit_staged(2, mgr.stage([j]))  # cold tombstones land here

    # recovery: the closed window's bucket must NOT come back
    j2 = mk()
    mgr.recover([j2])
    outs = j2.apply_right(
        StreamChunk.from_numpy(
            {"rw": np.asarray([10, 20], np.int64),
             "rv": np.asarray([9, 9], np.int64)}, 8,
        )
    )
    d = outs[0].to_numpy(with_ops=True)
    rows = {(int(d["lw"][i]), int(d["lv"][i])) for i in range(len(d["lw"]))}
    assert rows == {(20, 2)}  # window 10 gone, window 20 restored


def test_cold_tombstone_dropped_when_key_recreated_late():
    """A late arrival re-creates a key AFTER its window closed while
    evicted: the staged cold tombstone must yield to the resident
    upsert — point reads and merge reads must agree post-recovery."""
    from risingwave_tpu.executors.base import Watermark
    from risingwave_tpu.executors.hash_join import HashJoinExecutor

    L = {"lw": jnp.int64, "lv": jnp.int64}
    R = {"rw": jnp.int64, "rv": jnp.int64}

    def mk():
        return HashJoinExecutor(
            ("lw",), ("rw",), L, R,
            capacity=1 << 8, fanout=4, out_cap=1 << 9,
            window_cols=("lw", "rw"), table_id="lj",
        )

    mgr = CheckpointManager(MemObjectStore())
    j = mk()
    j.cold_get_rows = mgr.get_rows
    j.apply_left(
        StreamChunk.from_numpy(
            {"lw": np.asarray([10], np.int64),
             "lv": np.asarray([1], np.int64)}, 8,
        )
    )
    j.on_barrier(None)
    mgr.commit_staged(1, mgr.stage([j]))
    assert j.evict_cold() == 1
    j.on_watermark(Watermark("lw", 15))  # closes window 10 (evicted)
    j.on_watermark(Watermark("rw", 15))
    # LATE left row for window 10 arrives BEFORE the next checkpoint:
    # the key is resident again
    j.apply_left(
        StreamChunk.from_numpy(
            {"lw": np.asarray([10], np.int64),
             "lv": np.asarray([5], np.int64)}, 8,
        )
    )
    j.on_barrier(None)
    mgr.commit_staged(2, mgr.stage([j]))

    # point read and full recovery must BOTH see exactly the late row
    found, vals = mgr.get_rows(
        "lj.left", {"k0": np.asarray([10], np.int64)}
    )
    assert found[0]
    j2 = mk()
    mgr.recover([j2])
    outs = j2.apply_right(
        StreamChunk.from_numpy(
            {"rw": np.asarray([10], np.int64),
             "rv": np.asarray([9], np.int64)}, 8,
        )
    )
    d = outs[0].to_numpy(with_ops=True)
    rows = [(int(d["lw"][i]), int(d["lv"][i])) for i in range(len(d["lw"]))]
    assert rows == [(10, 5)]  # the late row, not the pre-expiry one


def _replay_cols(snap, chunks, cols):
    for c in chunks:
        d = c.to_numpy(with_ops=True)
        for i in range(len(d["__op__"])):
            key = (int(d["k"][i]),)
            if d["__op__"][i] in (Op.DELETE, Op.UPDATE_DELETE):
                snap.pop(key, None)
            else:
                row = []
                for n in cols:
                    nl = d.get(n + "__null")
                    row.append(
                        None if nl is not None and nl[i] else int(d[n][i])
                    )
                snap[key] = tuple(row)
    return snap


def _mk_mi(table_id):
    return HashAggExecutor(
        group_keys=("k",),
        calls=(
            AggCall("min", "v", "mn", materialized=True),
            AggCall("max", "v", "mx", materialized=True),
            AggCall("count_star", None, "cnt"),
        ),
        schema_dtypes=DT,
        capacity=1 << 10,
        out_cap=1 << 10,
        table_id=table_id,
    )


def test_minput_min_max_evicts_and_faults_in_on_touch():
    """VERDICT r4 #9: MIN/MAX-bearing (materialized-input) state now
    participates in the cold tier. Evicted multisets fault back in ON
    TOUCH — so a delete of a pre-eviction value, arriving right after
    eviction, retracts exactly (merge-at-barrier could not do this)."""
    MI = ("mn", "mx", "cnt")
    store = MemObjectStore()
    mgr = CheckpointManager(store)
    ex = _mk_mi("coldmi")
    ex.cold_reader = lambda keys: mgr.get_rows("coldmi", keys)
    snap = {}

    # 100 groups x 3 values each; checkpoint -> durable
    rows = [
        (k, v, Op.INSERT) for k in range(100) for v in (k, k + 50, k + 90)
    ]
    for at in range(0, len(rows), CAP):
        _replay_cols(snap, ex.apply(_chunk(rows[at : at + CAP])), MI)
    _replay_cols(snap, ex.on_barrier(None), MI)
    mgr.commit_epoch(1 << 16, [ex])

    assert ex.evict_cold() == 100
    assert int(ex.table.occupancy()) == 0
    assert len(ex._evicted) == 100

    # delete each group's MINIMUM (a pre-eviction value) -> the min
    # must fall back to the next multiset value, exactly
    dels = [(k, k, Op.DELETE) for k in range(30)]
    _replay_cols(snap, ex.apply(_chunk(dels)), MI)
    _replay_cols(snap, ex.on_barrier(None), MI)
    for k in range(30):
        assert snap[(k,)] == (k + 50, k + 90, 2), (k, snap[(k,)])
    for k in range(30, 100):
        assert snap[(k,)] == (k, k + 90, 3)
    assert len(ex._evicted) == 70  # untouched groups stay cold

    # checkpoint + recover: round-trips (evicted set resets, durable
    # rows restore resident)
    mgr.commit_epoch(2 << 16, [ex])
    ex2 = _mk_mi("coldmi")
    CheckpointManager(store).recover([ex2])
    assert ex2._evicted == set()
    snap2 = dict(snap)
    _replay_cols(snap2, ex2.apply(_chunk([(5, 55, Op.DELETE)])), MI)
    _replay_cols(snap2, ex2.on_barrier(None), MI)
    assert snap2[(5,)] == (95, 95, 1)


def test_runtime_budget_evicts_minput_state():
    """The runtime no longer skips MIN/MAX-bearing executors when
    enforcing the memory budget."""
    agg = HashAggExecutor(
        group_keys=("k",),
        calls=(AggCall("min", "v", "mn", materialized=True),),
        schema_dtypes=DT,
        capacity=1 << 10,
        table_id="coldmib",
    )
    rt = StreamingRuntime(
        MemObjectStore(), async_checkpoint=False, memory_budget_bytes=1
    )
    rt.register("mi", Pipeline([agg]))
    rows = [(k, k, Op.INSERT) for k in range(50)]
    rt.push("mi", _chunk(rows))
    rt.barrier()  # checkpoint -> durable -> budget forces eviction
    assert int(agg.table.occupancy()) == 0
    assert len(agg._evicted) == 50
    # touch one back; its min continues exactly
    snap = {}
    _replay_cols(snap, agg.apply(_chunk([(7, 3, Op.INSERT)])), ("mn",))
    _replay_cols(snap, agg.on_barrier(None), ("mn",))
    assert snap[(7,)] == (3,)


def test_float_keyed_join_cold_tier():
    """VERDICT r4 #9: non-integer join keys ride the cold tier as exact
    bit patterns (host_key_view) instead of silently disabling
    eviction."""
    from risingwave_tpu.executors.hash_join import HashJoinExecutor

    store = MemObjectStore()
    mgr = CheckpointManager(store)
    ldt = {"fk": jnp.float64, "a": jnp.int64}
    rdt = {"fk2": jnp.float64, "b": jnp.int64}
    j = HashJoinExecutor(
        ("fk",), ("fk2",), ldt, rdt,
        capacity=1 << 8, fanout=4, out_cap=1 << 8, table_id="coldf.j",
    )
    j.cold_get_rows = mgr.get_rows

    def lchunk(pairs):
        return StreamChunk.from_numpy(
            {"fk": np.asarray([p[0] for p in pairs], np.float64),
             "a": np.asarray([p[1] for p in pairs], np.int64)}, 32)

    def rchunk(pairs):
        return StreamChunk.from_numpy(
            {"fk2": np.asarray([p[0] for p in pairs], np.float64),
             "b": np.asarray([p[1] for p in pairs], np.int64)}, 32)

    j.apply_left(lchunk([(0.5, 1), (1.25, 2), (2.75, 3)]))
    j.on_barrier(None)
    mgr.commit_epoch(1 << 16, [j])

    assert j.evict_cold() == 3
    assert len(j._evicted["left"]) == 3

    # probe from the right: the evicted left rows must fault in and
    # match by exact float key
    outs = j.apply_right(rchunk([(1.25, 9)]))
    d = outs[0].to_numpy()
    assert len(d["b"]) == 1 and int(d["a"][0]) == 2
    assert float(d["fk"][0]) == 1.25

    # watermark expiry of evicted float keys compares in the NUMERIC
    # domain (bit patterns are identity only): cutoff 1.0 closes 0.5
    assert len(j._evicted["left"]) == 2  # 1.25 faulted back in
    j._expire_evicted("left", 0, 1.0)
    assert len(j._evicted["left"]) == 1  # only 0.5 closed


def test_evicted_minput_groups_expire_under_watermark():
    """A cold-evicted group past the watermark cutoff still closes:
    it faults back in and the normal expiry path retracts it (the
    join's _expire_evicted analogue for aggs)."""
    from risingwave_tpu.executors.base import Watermark

    store = MemObjectStore()
    mgr = CheckpointManager(store)
    ex = HashAggExecutor(
        group_keys=("k",),
        calls=(AggCall("min", "v", "mn", materialized=True),),
        schema_dtypes=DT,
        capacity=1 << 8,
        table_id="coldexp",
        window_key=("k", 0, True),  # k doubles as the window column
    )
    ex.cold_reader = lambda keys: mgr.get_rows("coldexp", keys)
    snap = {}
    _replay_cols(
        snap,
        ex.apply(_chunk([(1000, 5, Op.INSERT), (2000, 7, Op.INSERT)])),
        ("mn",),
    )
    _replay_cols(snap, ex.on_barrier(None), ("mn",))
    mgr.commit_epoch(1 << 16, [ex])
    assert ex.evict_cold() == 2 and len(ex._evicted) == 2

    wm, outs = ex.on_watermark(Watermark("k", 1500))
    _replay_cols(snap, outs, ("mn",))
    _replay_cols(snap, ex.on_barrier(None), ("mn",))
    assert (1000,) not in snap, "closed window row was not retracted"
    assert snap[(2000,)] == (7,)
    assert all(t[0] >= 1500 for t in ex._evicted)


# -- the barrier's merge engages only once an eviction made a hit possible --
GATE_ROWS = [(k, v, Op.INSERT) for k in range(40) for v in (k, k + 50)]
# after the eviction: rows on ten cold groups, three of them retractions
# of a row the store holds, and two groups the store never saw
GATE_RETURN = (
    [(k, 100 + k, Op.INSERT) for k in range(10)]
    + [(k, k + 50, Op.DELETE) for k in range(3)]
    + [(k, 7, Op.INSERT) for k in (900, 901)]
)


def _gated(kind, barrier, table_id):
    """[aggregate -> device MV] over a store of its own, walked
    interpreted or as one fused program a barrier: (pipeline, agg, mv,
    manager, store)."""
    calls = {
        "plain": (
            AggCall("count_star", None, "cnt"),
            AggCall("sum", "v", "s"),
        ),
        "minput": (
            AggCall("max", "v", "mx", materialized=True),
            AggCall("count_star", None, "cnt"),
        ),
    }[kind]
    agg = HashAggExecutor(
        group_keys=("k",),
        calls=calls,
        schema_dtypes=DT,
        capacity=1 << 10,
        out_cap=1 << 8,
        table_id=table_id,
    )
    cols = tuple(c.output for c in calls)
    mv = DeviceMaterializeExecutor(
        ("k",),
        cols,
        {"k": jnp.int64, **{c: jnp.int64 for c in cols}},
        table_id=table_id + ".mv",
        capacity=1 << 10,
    )
    pipe = Pipeline([agg, mv])
    if barrier == "fused":
        (wrapper,) = fuse_pipeline(pipe, label=table_id)
        assert isinstance(wrapper, FusedChainExecutor)
    store = MemObjectStore()
    mgr = CheckpointManager(store)
    agg.cold_reader = lambda keys: mgr.get_rows(table_id, keys)
    return pipe, agg, mv, mgr, store


def _push_rows(pipe, rows):
    for at in range(0, len(rows), CAP):
        pipe.push(_chunk(rows[at : at + CAP]))


def _barrier_spans(pipe):
    """One barrier of ``pipe``; its spans, in the order they began."""
    TRACER.clear()
    pipe.barrier()
    return sorted(TRACER.spans(), key=lambda sp: sp.t0)


def _merge_span(spans, table_id):
    (sp,) = [
        sp for sp in spans
        if sp.name == "agg.merge_cold" and sp.args["table_id"] == table_id
    ]
    return sp


def _merge_count(table_id, outcome):
    return REGISTRY.counter("agg_cold_merge_total").get(
        table_id=table_id, outcome=outcome
    )


GATE_CASES = [
    (kind, barrier)
    for kind in ("plain", "minput")
    for barrier in ("interpreted", "fused")
]


@pytest.mark.parametrize("kind,barrier", GATE_CASES)
def test_a_barrier_before_any_eviction_reads_nothing_for_the_merge(
    kind, barrier
):
    """Nothing was evicted, so the store can hold no group the table
    lacks: the barrier asks it nothing (the reader here raises), copies
    no lane, and its flush is enqueued before the first blocking read."""
    tid = f"gate0.{kind}.{barrier}"
    pipe, agg, mv, _mgr, _store = _gated(kind, barrier, tid)

    def never(keys):
        raise AssertionError("the cold store was asked before an eviction")

    agg.cold_reader = never
    skipped = _merge_count(tid, "skipped")
    for epoch in range(2):
        _push_rows(pipe, GATE_ROWS if epoch == 0 else GATE_RETURN)
        spans = _barrier_spans(pipe)
        merge = _merge_span(spans, tid)
        assert merge.args == {
            "table_id": tid, "barrier": 1, "ran": 0,
            "candidates": 0, "found": 0,
        }
        assert not [sp for sp in spans if sp.parent == merge.sid]
        reads = [sp for sp in spans if sp.name == "device.read"]
        assert "agg.merge_cold" not in {sp.args["what"] for sp in reads}
        if barrier == "interpreted":
            assert reads[0].args["what"] == "agg.flush.status"
        else:
            # the one program steps and flushes: nothing is read before
            # it is on the device's queue
            (program,) = [sp for sp in spans if sp.name == f"fused:{tid}"]
            assert all(sp.t0 > program.t0 for sp in reads)
    assert _merge_count(tid, "skipped") == skipped + 2
    assert _merge_count(tid, "ran") == 0
    assert len(mv.snapshot()) == 42


@pytest.mark.parametrize("kind,barrier", GATE_CASES)
def test_after_an_eviction_the_merge_runs_and_a_restore_disarms_it(
    kind, barrier
):
    """From the first eviction on every barrier merges as it always
    did: a group touched again emits what its twin that never evicted
    emits. A restore brings every durable group back resident, and the
    barriers after it read nothing for the merge again."""
    tid = f"gate1.{kind}.{barrier}"
    pipe, agg, mv, mgr, store = _gated(kind, barrier, tid)
    twin_pipe, twin, twin_mv, twin_mgr, _ = _gated(
        kind, barrier, tid + ".twin"
    )
    for p, a, m in ((pipe, agg, mgr), (twin_pipe, twin, twin_mgr)):
        _push_rows(p, GATE_ROWS)
        p.barrier()
        # nothing is durable yet: an eviction that evicts nothing arms
        # nothing
        assert a.evict_cold() == 0 and not a._has_evicted
        m.commit_epoch(1 << 16, [a])
    assert agg.evict_cold() == 40
    assert agg._has_evicted and not twin._has_evicted

    for p in (pipe, twin_pipe):
        _push_rows(p, GATE_RETURN)
    ran = _merge_count(tid, "ran")
    merge = _merge_span(_barrier_spans(pipe), tid)
    assert (merge.args["barrier"], merge.args["ran"]) == (1, 1)
    if kind == "plain":
        # the ten cold groups fold back in; the two new ones miss
        assert (merge.args["candidates"], merge.args["found"]) == (12, 10)
    else:
        # multisets fault in on touch, before the step: stored already
        assert (merge.args["candidates"], merge.args["found"]) == (2, 0)
    assert _merge_count(tid, "ran") == ran + 1
    assert _merge_span(_barrier_spans(twin_pipe), tid + ".twin").args[
        "ran"
    ] == 0
    want = twin_mv.snapshot()
    assert len(want) == 42 and mv.snapshot() == want

    mgr.commit_epoch(2 << 16, [agg])
    twin_mgr.commit_epoch(2 << 16, [twin])
    CheckpointManager(store).recover([agg])
    assert not agg._has_evicted and agg._evicted == set()
    again = [(k, 1, Op.INSERT) for k in range(5, 15)]
    for p in (pipe, twin_pipe):
        _push_rows(p, again)
    skipped = _merge_count(tid, "skipped")
    merge = _merge_span(_barrier_spans(pipe), tid)
    assert (merge.args["ran"], merge.args["candidates"]) == (0, 0)
    assert _merge_count(tid, "skipped") == skipped + 1
    twin_pipe.barrier()
    assert mv.snapshot() == twin_mv.snapshot()


def test_two_input_fused_barrier_lands_the_epoch_before_it_merges():
    """q7's MAX side inside the two-input fused program: a window
    evicted and bid on again keeps the maximum the store holds, as the
    interpreted walk of the same bids does."""
    from risingwave_tpu.connectors.nexmark import (
        NexmarkConfig,
        NexmarkGenerator,
    )
    from risingwave_tpu.queries.nexmark_q import build_q7

    def drive(fuse):
        q7 = build_q7(capacity=1 << 12, state_cleaning=False)
        (agg,) = [
            e for e in q7.pipeline.right if isinstance(e, HashAggExecutor)
        ]
        mgr = CheckpointManager(MemObjectStore())
        agg.cold_reader = lambda keys: mgr.get_rows(agg.table_id, keys)
        if fuse:
            (wrapper,) = fuse_pipeline(q7.pipeline, label="q7")
            assert isinstance(wrapper, FusedTwoInputExecutor)
        gen = NexmarkGenerator(NexmarkConfig(first_event_rate=2_000))
        maxima = []
        for epoch in range(3):
            for _ in range(2):
                bid = gen.next_chunks(600, 1024)["bid"].select(
                    ["auction", "bidder", "price", "date_time"]
                )
                q7.pipeline.push_left(bid)
                q7.pipeline.push_right(bid)
            q7.pipeline.barrier()
            live = np.asarray(agg.table.live)
            maxima.append(
                sorted(np.asarray(agg.state.emitted["maxprice"])[live])
            )
            mgr.commit_epoch((epoch + 1) << 16, [agg])
            if epoch == 0:
                assert agg.evict_cold() == 1
        return maxima, q7.mview.snapshot()

    want, want_mv = drive(fuse=False)
    got, got_mv = drive(fuse=True)
    assert len(want[0]) == 1 and want[1] == want[0]  # the cold maximum stood
    assert got == want and got_mv == want_mv
