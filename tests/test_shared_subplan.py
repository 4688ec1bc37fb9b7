"""A join whose two sides start with the same sub-plan over the same
stream plans it once and feeds both sides from it (NEXmark q5 writes its
hop count twice; upstream merges such sub-plans under StreamShare).

What is recognised as equal and what is not, that pinned plans keep
their shape, that the view stays exact (retractions, a group created and
deleted inside one epoch, checkpoint and restore, one chunk counted
once), and that the paths which have no form for a shared head decline
it and still serve the right view."""

import json
import os

import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.connectors.nexmark import BID_SCHEMA
from risingwave_tpu.executors import (
    HashAggExecutor,
    HashJoinExecutor,
    HopWindowExecutor,
    ProjectExecutor,
)
from risingwave_tpu.executors.keyed_join import KeyedJoinExecutor
from risingwave_tpu.frontend import SqlSession
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.runtime.fragmenter import (
    fragment_chains,
    graph_planned_mv,
    sharded_planned_mv,
)
from risingwave_tpu.runtime.fused_step import fuse_pipeline, fusion_refusals
from risingwave_tpu.sql import Catalog, StreamPlanner
from risingwave_tpu.sql.optimizer import explain_sql
from risingwave_tpu.storage.object_store import LocalFsObjectStore
from risingwave_tpu.storage.state_table import Checkpointable
from risingwave_tpu.trace import TRACER
from risingwave_tpu.types import DataType, Field, Op, Schema

from test_nexmark_q5_sql import BID_DDL, Q5, Served, hot_items, nexmark_gen
from test_tpch import Q17_SQL

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

T = Schema((
    Field("k", DataType.INT64), Field("g", DataType.INT64),
    Field("v", DataType.INT64),
))

# q5's shape over a plain table: per-(k, g) counts, their maximum per g,
# and the counts that reach it. The two count sub-selects differ in their
# aliases (n / m, g / cg), in the order of their GROUP BY and in which
# key columns they list.
HOT = (
    "CREATE MATERIALIZED VIEW hot AS SELECT A.k, A.n, A.g FROM "
    "(SELECT k, g, count(*) AS n FROM t GROUP BY g, k) AS A JOIN "
    "(SELECT max(C.m) AS top, C.cg FROM "
    "(SELECT {count} AS m, g AS cg FROM t {where} GROUP BY {by}) AS C "
    "GROUP BY C.cg) AS B ON A.g = B.cg AND A.n >= B.top"
)
HOT_SHARED = HOT.format(count="count(*)", where="", by="k, g")


def _shared_total(mv):
    return REGISTRY.counter("plan_shared_subplans_total").get(mv=mv)


def _types(chain):
    return [type(ex) for ex in chain]


def _aggs(planned):
    return [
        ex for ex in planned.pipeline.executors
        if isinstance(ex, HashAggExecutor)
    ]


# -- (a) plans -----------------------------------------------------------------


def test_q5_plans_one_hop_and_one_count_and_says_so():
    before = _shared_total("q5")
    q5 = StreamPlanner(Catalog({"bid": BID_SCHEMA})).plan(Q5)
    pipe = q5.pipeline
    assert _types(pipe.head) == [HopWindowExecutor, HashAggExecutor]
    assert _types(pipe.left) == [ProjectExecutor]
    assert _types(pipe.right) == [ProjectExecutor, HashAggExecutor]
    assert _shared_total("q5") - before == 2
    # hop + count once, the max over it: no second count table
    assert sum(isinstance(e, HopWindowExecutor) for e in pipe.executors) == 1
    count, top = _aggs(q5)
    assert [c.kind for c in count.calls] == ["count_star"]
    assert [(c.kind, c.materialized) for c in top.calls] == [("max", True)]
    assert len({id(ex) for ex in pipe.executors}) == len(pipe.executors)
    assert isinstance(pipe.join, KeyedJoinExecutor)
    assert pipe.join.unique_side == "right"
    # each consumer renames the head's columns for itself
    assert dict(pipe.left[0].outputs)["starttime"].name == "window_start"
    assert dict(pipe.right[0].outputs)["starttime_c"].name == "window_start"
    assert q5.inputs == {"bid": "both"}
    # EXPLAIN shows the head once, with its two readers
    plan = explain_sql(Q5, Catalog({"bid": BID_SCHEMA}))
    shared = plan.split("-- shared sub-plan")
    assert len(shared) == 2
    assert "read by auctionbids and countbids" in shared[1]
    assert shared[1].count("LogicalHopWindow") == 1
    assert shared[1].count("LogicalAgg") == 1


UNEQUAL = {
    "window": lambda q: "'12' SECOND) GROUP BY auction".join(
        q.rsplit("'10' SECOND) GROUP BY auction", 1)
    ),
    "where": lambda q: q.replace(
        "GROUP BY auction, window_start",
        "WHERE price > 0 GROUP BY auction, window_start",
    ),
    "aggregate": lambda q: q.replace(
        "(SELECT count(*) AS num, window_start AS starttime_c",
        "(SELECT count(bidder) AS num, window_start AS starttime_c",
    ),
}


@pytest.mark.parametrize("what", sorted(UNEQUAL))
def test_a_side_whose_window_where_or_aggregate_differs_shares_nothing(what):
    sql = UNEQUAL[what](Q5)
    assert sql != Q5
    before = _shared_total("q5")
    q5 = StreamPlanner(Catalog({"bid": BID_SCHEMA})).plan(sql)
    pipe = q5.pipeline
    assert pipe.head == []
    assert _shared_total("q5") == before
    assert sum(isinstance(e, HopWindowExecutor) for e in pipe.executors) == 2
    assert len(_aggs(q5)) == 3
    assert "shared sub-plan" not in explain_sql(sql, Catalog({"bid": BID_SCHEMA}))


@pytest.mark.parametrize(
    "count,by",
    [("count(*)", "k, g"), ("count(*)", "g, k"), ("count(*)", "t.g, t.k")],
)
def test_aliases_and_group_by_order_do_not_matter(count, by):
    planned = StreamPlanner(Catalog({"t": T})).plan(
        HOT.format(count=count, where="", by=by)
    )
    pipe = planned.pipeline
    assert _types(pipe.head) == [HashAggExecutor]
    # the left side reads the head's columns as they are: no projection
    assert pipe.left == []
    assert _types(pipe.right) == [ProjectExecutor, HashAggExecutor]
    assert dict(pipe.right[0].outputs)["m"].name == "n"
    assert dict(pipe.right[0].outputs)["cg"].name == "g"
    assert pipe.join.unique_side == "right"
    assert [c.materialized for c in pipe.right[1].calls] == [True]


@pytest.mark.parametrize(
    "count,where",
    [("count(v)", ""), ("count(*)", "WHERE v > 3"), ("sum(v)", "")],
)
def test_over_a_table_too_a_changed_side_shares_nothing(count, where):
    planned = StreamPlanner(Catalog({"t": T})).plan(
        HOT.format(count=count, where=where, by="k, g")
    )
    assert planned.pipeline.head == []
    assert len(_aggs(planned)) == 3


def test_a_bare_scan_or_window_on_both_sides_is_not_a_sub_plan():
    # q7: a projection over TUMBLE(bid) against an aggregate over it
    planned = StreamPlanner(Catalog({"bid": BID_SCHEMA})).plan(
        "CREATE MATERIALIZED VIEW s AS SELECT a.auction, b.bidder FROM "
        "(SELECT auction, price FROM bid) AS a JOIN "
        "(SELECT bidder, price AS p2 FROM bid) AS b ON a.price = b.p2"
    )
    assert planned.pipeline.head == []
    assert planned.inputs == {"bid": "both"}


def _shape(planned):
    pipe = planned.pipeline
    out = {
        sec: [[type(ex).__name__, getattr(ex, "table_id", None)]
              for ex in getattr(pipe, sec)]
        for sec in ("head", "left", "right", "tail")
    }
    out["join"] = [type(pipe.join).__name__, pipe.join.table_id]
    out["inputs"] = planned.inputs
    return out


def _plan_q7():
    return StreamPlanner(Catalog({"bid": BID_SCHEMA})).plan(
        "CREATE MATERIALIZED VIEW q7 AS SELECT b.auction, b.price, b.bidder "
        "FROM (SELECT auction, price, bidder, window_start AS ws FROM "
        "TUMBLE(bid, date_time, INTERVAL '10' SECOND)) AS b JOIN "
        "(SELECT max(price) AS maxprice, window_start AS mws FROM "
        "TUMBLE(bid, date_time, INTERVAL '10' SECOND) GROUP BY window_start) "
        "AS m ON b.price = m.maxprice AND b.ws = m.mws"
    )


def _plan_q8():
    with open(os.path.join(ROOT, "benchmarks/configs/nexmark_q8.json")) as f:
        cfg = json.load(f)
    session = SqlSession(Catalog({}), capacity=1 << 10)
    try:
        for ddl in cfg["ddl"]:
            session.execute(ddl)
        (sql,) = cfg["mv_sql"]
        out, _ = session.execute("EXPLAIN " + sql)
        assert not any("shared" in line for line in out["QUERY PLAN"])
        return session.planner.plan(sql)
    finally:
        session.close()


def _plan_q17():
    session = SqlSession(Catalog({}), capacity=1 << 10)
    try:
        session.execute(
            "CREATE TABLE lineitem (l_partkey BIGINT, l_quantity BIGINT, "
            "l_extendedprice BIGINT)"
        )
        session.execute(
            "CREATE TABLE part (p_partkey BIGINT PRIMARY KEY, "
            "p_brand BIGINT, p_container BIGINT)"
        )
        return session.planner.plan(Q17_SQL)
    finally:
        session.close()


# the shapes these plans had before sub-plans were shared (table ids
# included: a checkpoint names its tables by them)
PINNED = {
    "q7": (_plan_q7, {
        "head": [],
        "left": [["HopWindowExecutor", None],
                 ["RowIdGenExecutor", "q7.rowid1"],
                 ["ProjectExecutor", None]],
        "right": [["HopWindowExecutor", None],
                  ["HashAggExecutor", "q7.agg3"],
                  ["ProjectExecutor", None]],
        "tail": [["ProjectExecutor", None],
                 ["MaterializeExecutor", "q7.mview"]],
        # (PR 33: the bids of a window are a stream of rows, tied to no
        # key of their own: the chained layout, under the same table id)
        "join": ["StreamJoinExecutor", "q7.join4"],
        "inputs": {"bid": "both"},
    }),
    "q8": (_plan_q8, {
        "head": [],
        "left": [["HopWindowExecutor", None],
                 ["AppendOnlyDedupExecutor", "q8.dedup2"],
                 ["ProjectExecutor", None]],
        "right": [["HopWindowExecutor", None],
                  ["AppendOnlyDedupExecutor", "q8.dedup4"],
                  ["ProjectExecutor", None]],
        "tail": [["ProjectExecutor", None],
                 ["MaterializeExecutor", "q8.mview"]],
        "join": ["HashJoinExecutor", "q8.join5"],
        "inputs": {"person": "left", "auction": "right"},
    }),
    "q17": (_plan_q17, {
        "head": [],
        "left": [["ProjectExecutor", None]],
        "right": [["HashAggExecutor", "q17.agg5"],
                  ["ProjectExecutor", None]],
        # (PR 45: the decorrelated ``l_quantity < 0.2 * avg`` reads both
        # sides of an INNER join, so it is the join's residual and no
        # filter stands behind it; the table ids are what they were)
        "tail": [["SimpleAggExecutor", "q17.sagg7"],
                 ["ProjectExecutor", None],
                 ["MaterializeExecutor", "q17.mview"]],
        # (PR 33: ``part`` declares a PRIMARY KEY, so its stream and
        # the join under it update: the many side is stored flat)
        "join": ["KeyedJoinExecutor", "q17.join6"],
        "inputs": {"q17__j0": "left", "lineitem": "right"},
    }),
}


@pytest.mark.parametrize("query", sorted(PINNED))
def test_the_pinned_plans_keep_their_shape_and_share_nothing(query):
    plan, want = PINNED[query]
    before = _shared_total(query)
    planned = plan()
    assert _shape(planned) == want
    assert _shared_total(query) == before
    for aux in planned.aux:
        assert getattr(aux.pipeline, "head", []) == []


# -- (b) semantics --------------------------------------------------------------


def _changes(rng, keys, deletes=0.35):
    """Chunks of consistent inserts and deletes of rows (k, g = k % 8)
    and the multiset they leave."""
    held = np.zeros(keys + 1, np.int64)

    def chunk(cap, extra=()):
        ks, ops = [], []
        for _ in range(cap - 8):
            k = int(rng.integers(keys))
            if held[k] and rng.random() < deletes:
                held[k] -= 1
                ops.append(Op.DELETE)
            else:
                held[k] += 1
                ops.append(Op.INSERT)
            ks.append(k)
        for k, op in extra:
            held[k] += 1 if op == Op.INSERT else -1
            ks.append(k)
            ops.append(op)
        ks = np.array(ks, np.int64)
        return StreamChunk.from_numpy(
            {"k": ks, "g": ks % 8, "v": np.zeros(len(ks), np.int64)}, cap,
            ops=np.array(ops, np.int32),
        )

    return held, chunk


def _hot(held):
    """{(k, g): n}: the keys whose count reaches their group's largest."""
    top = {}
    for k in np.flatnonzero(held):
        top[k % 8] = max(top.get(k % 8, 0), int(held[k]))
    return {
        (int(k), int(k % 8)): int(held[k])
        for k in np.flatnonzero(held) if held[k] >= top[k % 8]
    }


def _view(mview):
    names = list(mview.pk) + list(mview.columns)
    at = [names.index(c) for c in ("k", "g", "n")]
    out = {}
    for key, val in mview.snapshot().items():
        k, g, n = ((tuple(key) + tuple(val))[i] for i in at)
        assert (k, g) not in out
        out[(int(k), int(g))] = int(n)
    return out


class _Serial:
    deletes = 0.35

    def __init__(self, tmp_path):
        self.planned = StreamPlanner(Catalog({"t": T}), capacity=1 << 10).plan(
            HOT_SHARED
        )
        self.push = self.planned.pipeline.push_both
        self.barrier = self.planned.pipeline.barrier
        self.close = lambda: None


class _Graph:
    # the planner takes a table's stream as inserts only, and the actors'
    # epoch-batched aggregates hold it to that across epochs (the parent
    # tree fails the same way on deletes that outlive their epoch); what
    # retracts here is the head's output, and key 40 inside each epoch
    deletes = 0.0

    def __init__(self, tmp_path, parallelism=1):
        self.rt = StreamingRuntime(
            LocalFsObjectStore(str(tmp_path)), checkpoint_frequency=1
        )
        self.planned = graph_planned_mv(
            lambda: StreamPlanner(Catalog({"t": T}), capacity=1 << 10),
            HOT_SHARED, parallelism=parallelism,
        )
        self.rt.register("hot", self.planned.pipeline)
        self.push = lambda c: self.rt.push("hot", c, "both")
        self.barrier = self.rt.barrier
        self.close = self.planned.pipeline.close


class _Parallel(_Graph):
    """Two instances asked for: a shared head has no per-side dispatch,
    so the fragmenter declines the split and runs one join actor."""

    def __init__(self, tmp_path):
        super().__init__(tmp_path, parallelism=2)
        assert len(self.planned.pipeline.graph.actors) == 2  # src + join


@pytest.mark.parametrize("mode", [_Serial, _Graph, _Parallel])
@pytest.mark.parametrize("seed,cap", [(1, 32), (2, 64), (2147483999, 32)])
def test_the_view_equals_the_recompute_under_retractions(
    tmp_path, mode, seed, cap
):
    rng = np.random.default_rng(seed)
    held, chunk = _changes(rng, 40, mode.deletes)
    run = mode(tmp_path)
    try:
        for epoch in range(5):
            # key 40 (g = 0) is created in this epoch's first chunk and
            # deleted again in its second: the group never shows
            run.push(chunk(cap, extra=[(40, Op.INSERT), (40, Op.INSERT)]))
            run.push(chunk(cap, extra=[(40, Op.DELETE), (40, Op.DELETE)]))
            run.barrier()
            assert held[40] == 0
            assert _view(run.planned.mview) == _hot(held), f"epoch {epoch}"
        assert len(_hot(held)) >= 8
    finally:
        run.close()


@pytest.mark.parametrize("seed,chunk", [(5, 128), (2147484001, 256)])
def test_q5_on_the_plain_pipeline_equals_the_recompute(seed, chunk):
    gen = nexmark_gen.Generator(seed, {"first_event_rate": 2000})
    bids = gen.events(0, 10 * chunk * 50 // 46, ["bid"])["bid"]
    q5 = StreamPlanner(Catalog({"bid": BID_SCHEMA}), capacity=1 << 12).plan(Q5)
    names = list(q5.mview.pk) + list(q5.mview.columns)
    at = [names.index(c) for c in ("auction", "num", "starttime")]
    pos = 0
    for epoch in range(4):
        for _ in range(2):
            q5.pipeline.push_both(StreamChunk.from_numpy(
                {"auction": bids["auction"][pos:pos + chunk],
                 "date_time": bids["date_time"][pos:pos + chunk]}, chunk,
            ))
            pos += chunk
        q5.pipeline.barrier()
        got = {
            tuple(int((tuple(k) + tuple(v))[i]) for i in at)
            for k, v in q5.mview.snapshot().items()
        }
        want = hot_items(bids["auction"][:pos], bids["date_time"][:pos])
        assert got == want, f"epoch {epoch}"
    assert len(want) >= 5
    # a pipeline with a shared head has one input
    with pytest.raises(ValueError, match="push_both"):
        q5.pipeline.push_left(None)


def test_one_source_chunk_reaches_the_counts_state_once():
    q5 = StreamPlanner(Catalog({"bid": BID_SCHEMA}), capacity=1 << 10).plan(Q5)
    hop, count = q5.pipeline.head
    seen = []
    apply = count.apply
    count.apply = lambda c: seen.append(c) or apply(c)
    n = 48
    q5.pipeline.push_both(StreamChunk.from_numpy(
        {"auction": np.full(n, 7, np.int64),
         "date_time": np.full(n, 20_500, np.int64)}, 64,
    ))
    q5.pipeline.barrier()
    assert len(seen) == 1
    # 48 bids on one auction in five windows: 48 each, not 96
    names = list(q5.mview.pk) + list(q5.mview.columns)
    rows = {
        tuple(int((tuple(k) + tuple(v))[names.index(c)])
              for c in ("auction", "num", "starttime"))
        for k, v in q5.mview.snapshot().items()
    }
    assert rows == {(7, n, w) for w in range(12_000, 22_000, 2_000)}


def test_checkpoint_and_restore_give_the_same_view_and_digests(tmp_path):
    gen = nexmark_gen.Generator(9, {"first_event_rate": 2000})
    bids = gen.events(0, 10 * 256 * 50 // 46, ["bid"])["bid"]
    TRACER.clear()
    served = Served(tmp_path, 256)
    try:
        stateful = [
            ex for ex in served.rt.fragments["q5"].executors
            if isinstance(ex, Checkpointable)
        ]
        tables = [t for ex in stateful for t in ex.checkpoint_table_ids()]
        # one count table, the max over it, the join's two sides, the view
        assert len(tables) == len(set(tables)) == 5
        pos = 0
        for _ in range(3):
            for _ in range(2):
                served.push(bids, pos, pos + 256)
                pos += 256
            served.rt.barrier()
        served.rt.wait_checkpoints()
        view = served.read()
        digests = [ex.state_digest() for ex in stateful]
        served.rt.recover()
        assert [ex.state_digest() for ex in stateful] == digests
        assert served.read() == view
        assert view == hot_items(bids["auction"][:pos], bids["date_time"][:pos])
        # and it serves on from the restored state
        served.push(bids, pos, pos + 256)
        pos += 256
        served.rt.barrier()
        assert served.read() == hot_items(
            bids["auction"][:pos], bids["date_time"][:pos]
        )
    finally:
        served.close()
    # the join actor says how many executors its sides share; its one
    # input edge carries each chunk once
    barriers = [
        sp for sp in TRACER.spans()
        if sp.name == "actor.barrier" and "shared" in sp.args
    ]
    assert barriers and {sp.args["shared"] for sp in barriers} == {2}
    chunks = [
        sp for sp in TRACER.spans()
        if sp.name == "actor.chunk" and sp.args["actor"] == "join#0"
    ]
    assert {sp.args["port"] for sp in chunks} == {0}
    assert len(chunks) == 7


def test_the_served_graph_has_one_edge_into_the_join(tmp_path):
    served = Served(tmp_path, 256)
    try:
        pipe = served.rt.fragments["q5"]
        assert pipe._sources == {"both": "src"}
        assert [(s.name, s.inputs) for s in pipe._specs] == [
            ("src", []), ("join", [("src", 0)]),
        ]
        sections = fragment_chains(pipe)["join"]
        assert list(sections) == ["both", "head_left", "head_right", "join_tail"]
        assert _types(sections["both"]) == [HopWindowExecutor, HashAggExecutor]
    finally:
        served.close()


# -- (c) the paths that decline a head ---------------------------------------------

# both sides ARE the sub-plan and neither is unique on the join key: the
# bucket join, which whole-pipeline fusion takes when there is no head
PAIRS = (
    "CREATE MATERIALIZED VIEW pairs AS SELECT a.k, a.n, b.bk, b.m FROM "
    "(SELECT k, g, count(*) AS n FROM t GROUP BY k, g) AS a JOIN "
    "(SELECT k AS bk, g AS bg, count(*) AS m FROM t GROUP BY k, g) AS b "
    "ON a.g = b.bg AND a.n >= b.m"
)


def test_whole_pipeline_fusion_declines_a_head_and_the_chains_fall_back():
    planned = StreamPlanner(Catalog({"t": T}), capacity=1 << 10).plan(PAIRS)
    pipe = planned.pipeline
    assert type(pipe.join) is HashJoinExecutor
    assert _types(pipe.head) == [HashAggExecutor]
    assert pipe.left == [] and _types(pipe.right) == [ProjectExecutor]
    fusion_refusals(clear=True)
    fuse_pipeline(pipe, label="pairs")
    assert pipe._fused is None
    assert any(
        r["fragment"] == "pairs" and "shared sub-plan" in r["message"]
        for r in fusion_refusals()
    )
    # the head is rewritten by the per-chain policy like any chain
    assert len(pipe.head) == 1 and pipe.head[0].agg is planned.pipeline.executors[0].agg
    rng = np.random.default_rng(4)
    held, chunk = _changes(rng, 24)
    for _ in range(3):
        pipe.push_both(chunk(32))
        pipe.barrier()
        names = list(planned.mview.pk) + list(planned.mview.columns)
        at = [names.index(c) for c in ("k", "bk", "n", "m")]
        got = {}
        for key, val in planned.mview.snapshot().items():
            k, bk, n, m = ((tuple(key) + tuple(val))[i] for i in at)
            got[(int(k), int(bk))] = (int(n), int(m))
        live = np.flatnonzero(held)
        assert got == {
            (int(k), int(bk)): (int(held[k]), int(held[bk]))
            for k in live for bk in live
            if k % 8 == bk % 8 and held[k] >= held[bk]
        }
    assert len(got) > 10


def test_the_mesh_fragmenter_declines_a_head_and_runs_one_actor():
    import jax

    assert len(jax.devices()) >= 8
    mv = sharded_planned_mv(
        lambda: StreamPlanner(Catalog({"t": T}), capacity=1 << 10),
        HOT_SHARED, 8,
    )
    try:
        assert mv.pipeline._sources == {"both": "src"}
        rng = np.random.default_rng(6)
        held, chunk = _changes(rng, 40)
        for _ in range(3):
            mv.pipeline.push_both(chunk(32))
            mv.pipeline.barrier()
            assert _view(mv.mview) == _hot(held)
    finally:
        mv.pipeline.close()


@pytest.mark.parametrize("mode", ["serial", "graph"])
def test_a_watermark_generated_in_the_head_reaches_both_sides(tmp_path, mode):
    """``WATERMARK FOR`` puts the self-driving filter at the scan, which
    is in the head: its watermark walks the head (the count frees closed
    windows), then both sides' rests into the join's alignment. The view
    keeps the closed windows' final rows."""
    rt = StreamingRuntime(
        LocalFsObjectStore(str(tmp_path)), checkpoint_frequency=1
    )
    session = SqlSession(Catalog({}), rt, capacity=1 << 12, exec_mode=mode)
    try:
        session.execute(BID_DDL[:-1] + ", WATERMARK FOR date_time AS "
                        "date_time - INTERVAL '2' SECONDS)")
        session.execute(Q5)
        execs = rt.fragments["q5"].executors
        assert [type(ex).__name__ for ex in execs[:3]] == [
            "WatermarkFilterExecutor", "HopWindowExecutor", "HashAggExecutor",
        ]
        assert sum(isinstance(ex, HashAggExecutor) for ex in execs) == 2
        count = execs[2]
        assert count.window_key == ("window_start", 0, False)
        gen = nexmark_gen.Generator(3, {"first_event_rate": 150})
        bids = gen.events(0, 12 * 256 * 50 // 46, ["bid"])["bid"]
        pos, live = 0, []
        for epoch in range(10):
            cols = {
                name: bids[name][pos:pos + 256]
                for name in ("auction", "bidder", "price", "date_time")
            }
            cols["channel"] = cols["extra"] = np.zeros(256, np.int32)
            chunk = StreamChunk.from_numpy(
                cols, 256, schema=session.catalog.tables["bid"]
            )
            with rt.lock:
                for frag, side in session.dml._targets["bid"]:
                    rt.push(frag, chunk, side)
            pos += 256
            rt.barrier()
            out, _ = session.execute("SELECT auction, num, starttime FROM q5")
            got = set(zip(*(np.asarray(out[c]).tolist()
                            for c in ("auction", "num", "starttime"))))
            assert got == hot_items(
                bids["auction"][:pos], bids["date_time"][:pos]
            ), f"epoch {epoch}"
            live.append(int(np.asarray(count.table.live).sum()))
        # 18 s of event time: the first windows closed and left the state
        assert bids["date_time"][pos - 1] - bids["date_time"][0] > 15_000
        newest = bids["date_time"][:pos] // 2000 * 2000
        groups = {
            (int(w) - 2000 * k, int(a))
            for w, a in zip(newest, bids["auction"][:pos]) for k in range(5)
        }
        assert live[-1] < 0.8 * len(groups)
    finally:
        session.close()
        for pipe in rt.fragments.values():
            close = getattr(pipe, "close", None)
            if close is not None:
                close()
