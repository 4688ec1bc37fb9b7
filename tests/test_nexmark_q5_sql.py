"""NEXmark q5 "hot items" as its source writes it, through the served
path: sliding-window bid counts, their per-window maximum, and the join
back on ``num >= maxn``. The served MV against a plain numpy recompute
after every barrier, through SqlSession(exec_mode="graph") and across a
checkpoint -> recover(); then the three things the query forces, each on
plans that are not q5: a residual predicate on an inner join's ON, MAX
over an updating stream, and a join key with thousands of rows."""

import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors import HashAggExecutor, HashJoinExecutor
from risingwave_tpu.executors.filter import ResidualFilterExecutor
from risingwave_tpu.executors.keyed_join import KeyedJoinExecutor
from risingwave_tpu.executors.stream_join import StreamJoinExecutor
from risingwave_tpu.expr import expr as E
from risingwave_tpu.frontend import SqlSession
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.sql import Catalog, StreamPlanner
from risingwave_tpu.storage.object_store import LocalFsObjectStore
from risingwave_tpu.types import Op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
import nexmark_gen  # noqa: E402  (the benchmark's generator: Beam's bids)

BID_DDL = (
    "CREATE TABLE bid (auction BIGINT, bidder BIGINT, price BIGINT, "
    "channel VARCHAR, date_time TIMESTAMP, extra VARCHAR)"
)
Q5 = (
    "CREATE MATERIALIZED VIEW q5 AS "
    "SELECT AuctionBids.auction, AuctionBids.num, AuctionBids.starttime "
    "FROM (SELECT auction, count(*) AS num, window_start AS starttime "
    "FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND) "
    "GROUP BY window_start, auction) AS AuctionBids "
    "JOIN (SELECT max(CountBids.num) AS maxn, CountBids.starttime_c "
    "FROM (SELECT count(*) AS num, window_start AS starttime_c "
    "FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND) "
    "GROUP BY auction, window_start) AS CountBids "
    "GROUP BY CountBids.starttime_c) AS MaxBids "
    "ON AuctionBids.starttime = MaxBids.starttime_c "
    "AND AuctionBids.num >= MaxBids.maxn"
)


def hot_items(auction, date_time):
    """{(auction, num, starttime)}: the groups whose count is not below
    their window's largest."""
    counts = {}
    for a, t in zip(auction.tolist(), date_time.tolist()):
        newest = t // 2000 * 2000
        for k in range(5):
            key = (newest - 2000 * k, a)
            counts[key] = counts.get(key, 0) + 1
    most = {}
    for (w, _), n in counts.items():
        most[w] = max(most.get(w, 0), n)
    return {(a, n, w) for (w, a), n in counts.items() if n >= most[w]}


class Served:
    def __init__(self, state_dir, chunk, capacity=1 << 12):
        self.chunk = chunk
        self.rt = StreamingRuntime(
            LocalFsObjectStore(str(state_dir)), checkpoint_frequency=1
        )
        self.session = SqlSession(
            Catalog({}), self.rt, capacity=capacity, exec_mode="graph"
        )
        self.session.execute(BID_DDL)
        self.session.execute(Q5)

    def push(self, bids, lo, hi):
        cols = {
            "auction": bids["auction"][lo:hi],
            "bidder": bids["bidder"][lo:hi],
            "price": bids["price"][lo:hi],
            "channel": np.zeros(hi - lo, np.int32),
            "date_time": bids["date_time"][lo:hi],
            "extra": np.zeros(hi - lo, np.int32),
        }
        chunk = StreamChunk.from_numpy(
            cols, self.chunk, schema=self.session.catalog.tables["bid"]
        )
        with self.rt.lock:
            for frag, side in self.session.dml._targets.get("bid", ()):
                self.rt.push(frag, chunk, side)

    def read(self):
        out, _ = self.session.execute("SELECT auction, num, starttime FROM q5")
        return set(zip(*(np.asarray(out[c]).tolist()
                         for c in ("auction", "num", "starttime"))))

    def close(self):
        self.session.close()
        for p in self.rt.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()


@pytest.mark.parametrize("seed,chunk", [(7, 256), (2147483999, 512)])
def test_q5_served_equals_the_recompute_across_barriers_and_recovery(
    tmp_path, seed, chunk
):
    # 2,000 events/s: a 10 s window holds 1,200 auctions, an epoch of
    # two chunks spans several slides, so windows open and turn over
    gen = nexmark_gen.Generator(seed, {"first_event_rate": 2000})
    bids = gen.events(0, 14 * chunk * 50 // 46, ["bid"])["bid"]
    served = Served(tmp_path, chunk)
    try:
        planned = served.rt.fragments
        assert list(planned) == ["bid", "q5"]
        pos = 0
        for epoch in range(6):
            for _ in range(2):
                served.push(bids, pos, pos + chunk)
                pos += chunk
            served.rt.barrier()
            if epoch == 3:
                # drop the device state and rebuild it from the store
                served.rt.wait_checkpoints()
                served.rt.recover()
            want = hot_items(bids["auction"][:pos], bids["date_time"][:pos])
            assert served.read() == want, f"epoch {epoch}"
        assert len(want) >= 5
    finally:
        served.close()


# -- what q5 forces, on plans that are not q5 --------------------------------


def _catalog():
    from risingwave_tpu.types import DataType, Field, Schema

    t = Schema((
        Field("k", DataType.INT64), Field("g", DataType.INT64),
        Field("v", DataType.INT64),
    ))
    return Catalog({"t": t, "u": t})


def _calls(planned):
    return {
        c.output: c
        for ex in planned.pipeline.executors
        if isinstance(ex, HashAggExecutor)
        for c in ex.calls
    }


def test_max_over_an_aggregate_keeps_its_inputs_over_a_table_the_latch():
    planner = StreamPlanner(_catalog())
    over_agg = planner.plan(
        "CREATE MATERIALIZED VIEW m1 AS SELECT max(n) AS top, min(n) AS low, g "
        "FROM (SELECT count(*) AS n, g, k FROM t GROUP BY g, k) AS c "
        "GROUP BY g"
    )
    calls = _calls(over_agg)
    assert calls["top"].materialized and calls["low"].materialized
    assert not over_agg.append_only
    over_table = planner.plan(
        "CREATE MATERIALIZED VIEW m2 AS SELECT max(v) AS top, g FROM t GROUP BY g"
    )
    assert not _calls(over_table)["top"].materialized
    # a chain that only filters, projects, windows or dedups stays
    # append-only: the cheap latch again
    over_chain = planner.plan(
        "CREATE MATERIALIZED VIEW m3 AS SELECT max(v) AS top, g FROM "
        "(SELECT DISTINCT g, v FROM t WHERE v > 3) AS d GROUP BY g"
    )
    assert not _calls(over_chain)["top"].materialized


def test_the_planned_agg_calls_of_q5_q7_and_the_sql_tests_plans():
    from risingwave_tpu.connectors.nexmark import BID_SCHEMA

    planner = StreamPlanner(Catalog({"bid": BID_SCHEMA}))
    q5 = planner.plan(Q5)
    calls = _calls(q5)
    assert calls["maxn"].kind == "max" and calls["maxn"].materialized
    assert isinstance(q5.pipeline.join, KeyedJoinExecutor)
    assert q5.pipeline.join.unique_side == "right"
    assert q5.pipeline.join.condition is not None
    # one stream feeds both inputs, through the hop count the two sides
    # share: planned once, the head, which ``executors`` (and so
    # ``_calls``) walks once
    assert q5.inputs == {"bid": "both"}
    assert [type(ex).__name__ for ex in q5.pipeline.head] == [
        "HopWindowExecutor", "HashAggExecutor",
    ]
    assert [
        [c.output for c in ex.calls] for ex in q5.pipeline.executors
        if isinstance(ex, HashAggExecutor)
    ] == [["num"], ["maxn"]]
    # q7: MAX(price) over the bid table's tumbling window keeps the
    # latch; its bid side is a stream of rows tied to no key of its
    # own, so the join is the chained layout (PR 33), its residual
    # none. tests/test_sql.py's q5-lite count keeps the latch too
    q7 = planner.plan(
        "CREATE MATERIALIZED VIEW q7 AS SELECT b.auction, b.price, b.bidder "
        "FROM (SELECT auction, price, bidder, window_start AS ws FROM "
        "TUMBLE(bid, date_time, INTERVAL '10' SECOND)) AS b JOIN "
        "(SELECT max(price) AS maxprice, window_start AS mws FROM "
        "TUMBLE(bid, date_time, INTERVAL '10' SECOND) GROUP BY window_start) "
        "AS m ON b.price = m.maxprice AND b.ws = m.mws"
    )
    assert not _calls(q7)["maxprice"].materialized
    assert type(q7.pipeline.join) is StreamJoinExecutor
    assert q7.inputs == {"bid": "both"} and q7.pipeline.head == []
    lite = planner.plan(
        "CREATE MATERIALIZED VIEW l AS SELECT auction, window_start, "
        "count(*) AS num FROM HOP(bid, date_time, INTERVAL '2' SECOND, "
        "INTERVAL '10' SECOND) GROUP BY auction, window_start"
    )
    assert not any(c.materialized for c in _calls(lite).values())


JOIN = (
    "SELECT a.k, a.v FROM (SELECT k, g, v FROM t) AS a {jt} JOIN "
    "(SELECT k AS bk, g AS bg, v AS w FROM u) AS b ON a.g = b.bg {more}"
)


def test_a_residual_is_refused_on_outer_semi_anti_and_without_an_equi_key():
    planner = StreamPlanner(_catalog())
    for jt in ("LEFT", "RIGHT", "FULL", "LEFT SEMI", "LEFT ANTI"):
        with pytest.raises(ValueError, match="INNER joins only"):
            planner.plan(JOIN.format(jt=jt, more="AND a.v >= b.w"))
    with pytest.raises(ValueError, match="no equi-join keys"):
        planner.plan(
            "SELECT a.k FROM (SELECT k, v FROM t) AS a JOIN "
            "(SELECT k AS bk, v AS w FROM u) AS b ON a.v >= b.w"
        )


def _counted_changes(rng, keys):
    """Chunks of consistent inserts and deletes of rows (k, g = k % 8):
    a row is deleted only while the multiset holds it. Returns (the
    multiset's counts per k, chunk maker)."""
    held = np.zeros(keys, np.int64)

    def chunk(n=24, cap=32):
        ks, ops = [], []
        for _ in range(n):
            k = int(rng.integers(keys))
            if held[k] and rng.random() < 0.4:
                held[k] -= 1
                ops.append(Op.DELETE)
            else:
                held[k] += 1
                ops.append(Op.INSERT)
            ks.append(k)
        ks = np.array(ks, np.int64)
        return StreamChunk.from_numpy(
            {"k": ks, "g": ks % 8, "v": np.zeros(n, np.int64)}, cap,
            ops=np.array(ops, np.int32),
        )

    return held, chunk


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_a_residual_inner_join_is_a_filter_over_the_equi_join(seed):
    """sigma(A JOIN B) on random retracting inputs: two updating counts
    joined on a coarser key with ``n >= m`` beside it. Neither side is
    unique per key, so this is the bucket join with the residual as a
    filter over its change stream; U-/U+ pairs of which one half fails
    the predicate must come out as bare inserts and deletes."""
    rng = np.random.default_rng(seed)
    planned = StreamPlanner(_catalog()).plan(
        "CREATE MATERIALIZED VIEW j AS SELECT a.k, a.n, b.bk, b.m FROM "
        "(SELECT k, g, count(*) AS n FROM t GROUP BY k, g) AS a JOIN "
        "(SELECT k AS bk, g AS bg, count(*) AS m FROM u GROUP BY k, g) AS b "
        "ON a.g = b.bg AND a.n >= b.m"
    )
    pipe = planned.pipeline
    assert type(pipe.join) is HashJoinExecutor
    assert isinstance(pipe.tail[0], ResidualFilterExecutor)
    left, lchunk = _counted_changes(rng, 48)
    right, rchunk = _counted_changes(rng, 48)
    for _ in range(5):
        pipe.push_left(lchunk())
        pipe.push_right(rchunk())
        pipe.barrier()
        want = {
            (k, bk): (int(left[k]), int(right[bk]))
            for k in range(48) for bk in range(48)
            if left[k] and right[bk] and k % 8 == bk % 8
            and left[k] >= right[bk]
        }
        names = list(planned.mview.pk) + list(planned.mview.columns)
        at = [names.index(c) for c in ("k", "bk", "n", "m")]
        got = {}
        for key, val in planned.mview.snapshot().items():
            row = tuple((tuple(key) + tuple(val))[i] for i in at)
            got[row[:2]] = row[2:]
        assert got == want
    assert len(want) > 20


def _keyed(condition=None, capacity=1 << 13):
    return KeyedJoinExecutor(
        left_keys=("g",), right_keys=("ug",),
        left_dtypes={"k": jnp.int64, "g": jnp.int64, "v": jnp.int64},
        right_dtypes={"ug": jnp.int64, "top": jnp.int64},
        left_pk=("k",), right_pk=("ug",), unique_side="right",
        condition=condition, capacity=capacity, table_id="kj",
    )


def _apply(view, chunks):
    """Fold emitted change chunks into {(k, ug): row}; a delete must
    find its row, an insert must not (what the MV would refuse)."""
    for c in chunks:
        d = c.to_numpy()
        for i in range(len(d["__op__"])):
            key = (int(d["k"][i]), int(d["ug"][i]))
            row = (int(d["g"][i]), int(d["v"][i]), int(d["top"][i]))
            if d["__op__"][i] in (Op.DELETE, Op.UPDATE_DELETE):
                assert view.pop(key) == row
            else:
                assert key not in view
                view[key] = row
    return view


def _many(ks, gs, vs, ops, cap):
    return StreamChunk.from_numpy(
        {"k": np.asarray(ks, np.int64), "g": np.asarray(gs, np.int64),
         "v": np.asarray(vs, np.int64)}, cap, ops=np.asarray(ops, np.int32),
    )


def _unique(gs, tops, ops, cap=8):
    return StreamChunk.from_numpy(
        {"ug": np.asarray(gs, np.int64), "top": np.asarray(tops, np.int64)},
        cap, ops=np.asarray(ops, np.int32),
    )


@pytest.mark.parametrize("residual", [False, True])
def test_one_key_with_4096_rows_on_the_many_side(residual):
    """Inserted, updated and deleted, against numpy: every row of the
    many side under ONE join key (g = 7), plus a few under another."""
    cond = (E.col("v") >= E.col("top")) if residual else None
    kj = _keyed(cond)
    n = 4096
    rng = np.random.default_rng(5)
    v = rng.integers(0, 50, n)
    view = {}
    ks = np.arange(n)
    # the many side first: nothing to pair with yet
    _apply(view, kj.apply_left(_many(ks, np.full(n, 7), v, np.zeros(n), n)))
    _apply(view, kj.apply_left(_many([9000, 9001], [3, 3], [1, 2], [0, 0], n)))
    assert not view
    # the unique side arrives: one scan pairs all 4,096 lanes
    _apply(view, kj.apply_right(_unique([7, 3], [40, 2], [0, 0])))
    kj.on_barrier(None)

    def want(v, top7, alive):
        out = {
            (int(k), 7): (7, int(v[k]), top7)
            for k in ks[alive] if not residual or v[k] >= top7
        }
        if not residual or 2 >= 2:
            out[(9001, 3)] = (3, 2, 2)
        if not residual:
            out[(9000, 3)] = (3, 1, 2)
        return out

    alive = np.ones(n, bool)
    assert view == want(v, 40, alive)
    # update every other row of the many side in place (U-/U+ pairs)
    upd = ks[::2]
    nv = v.copy()
    nv[upd] = rng.integers(0, 50, len(upd))
    pairs = np.repeat(upd, 2)
    vals = np.stack([v[upd], nv[upd]], 1).ravel()
    ops = np.tile([Op.UPDATE_DELETE, Op.UPDATE_INSERT], len(upd))
    _apply(view, kj.apply_left(_many(pairs, np.full(2 * len(upd), 7), vals, ops, n)))
    v = nv
    assert view == want(v, 40, alive)
    # the unique row of the key is rewritten: all its pairs turn over
    _apply(view, kj.apply_right(_unique(
        [7, 7], [40, 25], [Op.UPDATE_DELETE, Op.UPDATE_INSERT])))
    assert view == want(v, 25, alive)
    # delete a quarter of the many side, then the unique row itself
    gone = ks[::4]
    alive[gone] = False
    _apply(view, kj.apply_left(
        _many(gone, np.full(len(gone), 7), v[gone], np.full(len(gone), Op.DELETE), n)))
    kj.on_barrier(None)
    assert view == want(v, 25, alive)
    _apply(view, kj.apply_right(_unique([7], [25], [Op.DELETE])))
    kj.on_barrier(None)
    assert set(view) == {k for k in want(v, 25, alive) if k[1] == 3}


def test_keyed_join_checkpoint_restore_and_digest():
    kj = _keyed(E.col("v") >= E.col("top"), capacity=1 << 8)
    kj.apply_left(_many(range(100), [i % 5 for i in range(100)],
                        [i % 11 for i in range(100)], np.zeros(100), 128))
    kj.apply_right(_unique(range(5), [3, 4, 5, 6, 7], np.zeros(5)))
    kj.on_barrier(None)
    first = kj.checkpoint_delta()
    assert {d.table_id for d in first} == {"kj.left", "kj.right"}
    assert [len(d.tombstone) for d in first] == [100, 5]
    assert kj.checkpoint_delta() == []  # marks flipped
    # delete ten rows: tombstones for stored rows only
    kj.apply_left(_many(range(10), [i % 5 for i in range(10)],
                        [i % 11 for i in range(10)],
                        np.full(10, Op.DELETE), 128))
    (second,) = kj.checkpoint_delta()
    assert second.tombstone.sum() == 10
    digest = kj.state_digest()
    other = _keyed(E.col("v") >= E.col("top"), capacity=1 << 8)
    keep = ~np.isin(first[0].key_cols["k0"], np.arange(10))
    other.restore_state(
        "kj.left", {k: a[keep] for k, a in first[0].key_cols.items()},
        {k: a[keep] for k, a in first[0].value_cols.items()})
    other.restore_state("kj.right", first[1].key_cols, first[1].value_cols)
    assert other.state_digest() == digest
    # and it joins on: a rewritten unique row turns its pairs over
    (out,) = other.apply_right(_unique(
        [2, 2], [5, 0], [Op.UPDATE_DELETE, Op.UPDATE_INSERT]))
    d = out.to_numpy()
    under_2 = [i for i in range(10, 100) if i % 5 == 2]
    assert sorted(d["k"][d["__op__"] == Op.INSERT].tolist()) == under_2
    assert sorted(d["k"][d["__op__"] == Op.DELETE].tolist()) == [
        i for i in under_2 if i % 11 >= 5
    ]


def test_keyed_join_grows_past_half_load_and_latches_emission_overflow():
    kj = _keyed(capacity=1 << 6)
    for lo in range(0, 512, 64):
        kj.apply_left(_many(range(lo, lo + 64), np.full(64, 1),
                            np.zeros(64), np.zeros(64), 64))
        kj.on_barrier(None)
    assert kj.left.capacity >= 1024
    assert int(kj.left.table.num_live()) == 512
    (out,) = kj.apply_right(_unique([1], [0], [0]))
    kj.on_barrier(None)  # 512 pairs: the bound grew with the table
    assert int(out.valid.sum()) == 512
    # more pairs of one kind than a chunk can emit (2^14 at 2^16 lanes)
    big = _keyed(capacity=1 << 16)
    for lo in range(0, 5 * 4096, 4096):
        big.apply_left(_many(range(lo, lo + 4096), np.full(4096, 1),
                             np.zeros(4096), np.zeros(4096), 4096))
    big.apply_right(_unique([1], [0], [0]))
    with pytest.raises(RuntimeError, match="follows the capacity"):
        big.on_barrier(None)


# -- NULLs through the keyed join ----------------------------------------

NULL_ROWS = [
    [(1, 10, None), (2, 10, 5), (3, 20, None), (1, 10, None), (4, 30, 2)],
    [(1, 10, 7), (3, 20, None), (5, 20, 1)],
    [(2, 10, 9), (4, 30, None)],
]


def _want_null_join(rows, residual):
    sums, tops = {}, {}
    for k, g, x in rows:
        sums.setdefault((k, g), [])
        tops.setdefault(g, [])
        if x is not None:
            sums[(k, g)].append(x)
            tops[g].append(x)
    out = set()
    for (k, g), xs in sums.items():
        s = sum(xs) if xs else None
        top = max(tops[g]) if tops[g] else None
        if residual and (s is None or top is None or not s >= top):
            continue  # a NULL predicate keeps nothing
        out.add((k, g, s, top))
    return out


@pytest.mark.parametrize("residual", [False, True])
def test_sum_over_nulls_on_the_many_side_comes_out_null(tmp_path, residual):
    rt = StreamingRuntime(
        LocalFsObjectStore(str(tmp_path)), checkpoint_frequency=1
    )
    session = SqlSession(Catalog({}), rt, capacity=1 << 10, exec_mode="graph")
    try:
        session.execute("CREATE TABLE t (k BIGINT, g BIGINT, x BIGINT)")
        session.execute(
            "CREATE MATERIALIZED VIEW m AS SELECT A.k, A.g, A.s, B.top FROM "
            "(SELECT k, g, sum(x) AS s FROM t GROUP BY k, g) AS A JOIN "
            "(SELECT g AS ug, max(x) AS top FROM t GROUP BY g) AS B "
            "ON A.g = B.ug" + (" AND A.s >= B.top" if residual else "")
        )
        assert any(
            isinstance(ex, KeyedJoinExecutor)
            for ex in rt.fragments["m"].executors
        )
        seen = []
        for batch in NULL_ROWS:
            seen += batch
            values = ", ".join(
                f"({k}, {g}, {'NULL' if x is None else x})"
                for k, g, x in batch
            )
            session.execute(f"INSERT INTO t VALUES {values}")
            rt.barrier()
            out, _ = session.execute("SELECT k, g, s, top FROM m")
            got = set(zip(*(np.asarray(out[c]).tolist()
                            for c in ("k", "g", "s", "top"))))
            assert got == _want_null_join(seen, residual)
    finally:
        session.close()
        for p in rt.fragments.values():  # the graph's actor threads
            close = getattr(p, "close", None)
            if close is not None:
                close()


def test_keyed_join_keeps_null_lanes_across_checkpoint_and_restore():
    def many(ks, gs, vs, null, ops, cap):
        c = _many(ks, gs, vs, ops, cap)
        lane = np.zeros(cap, bool)
        lane[: len(null)] = null
        return StreamChunk(c.columns, c.valid, {"v": jnp.asarray(lane)}, c.ops)

    kj = _keyed(capacity=1 << 8)
    null = np.arange(20) % 3 == 0
    kj.apply_left(many(range(20), np.full(20, 1), np.arange(20), null,
                       np.zeros(20), 32))
    (out,) = kj.apply_right(_unique([1], [0], [0]))
    kj.on_barrier(None)
    d = out.to_numpy()
    assert sorted(d["k"].tolist()) == list(range(20))
    assert set(d["k"][d["v__null"]].tolist()) == set(
        np.arange(20)[null].tolist()
    )
    deltas = {x.table_id: x for x in kj.checkpoint_delta()}
    assert deltas["kj.left"].value_cols["n_v"].sum() == null.sum()
    other = _keyed(capacity=1 << 8)
    for tid, x in deltas.items():
        other.restore_state(tid, x.key_cols, x.value_cols)
    assert other.state_digest() == kj.state_digest()
    assert int(other.left.row_nulls["v"].sum()) == null.sum()


def test_keyed_join_refuses_a_null_stream_key_that_is_no_join_key():
    kj = _keyed(capacity=1 << 8)
    c = _many([1, 2], [7, 7], [0, 0], np.zeros(2), 8)
    lane = jnp.asarray(np.arange(8) == 1)
    kj.apply_left(StreamChunk(c.columns, c.valid, {"k": lane}, c.ops))
    with pytest.raises(RuntimeError, match="NULL in a stream-key column"):
        kj.on_barrier(None)


def test_an_epochs_join_and_edge_totals_ride_its_spans(tmp_path):
    """What the benchmark's window readers read: once an epoch the
    join's totals on a ``join.epoch`` span and the actor's edge rows in
    the args of its ``actor.fence``, both stamped with the epoch; the
    same rows on the counters, which have no epoch."""
    from risingwave_tpu.metrics import REGISTRY
    from risingwave_tpu.trace import TRACER

    gen = nexmark_gen.Generator(11, {"first_event_rate": 2000})
    bids = gen.events(0, 7 * 256 * 50 // 46, ["bid"])["bid"]
    retracted = REGISTRY.counter("actor_chunk_retract_rows_total")
    before = sum(retracted._values.values())
    TRACER.clear()
    served = Served(tmp_path, 256)
    try:
        for epoch in range(3):
            for i in (0, 1):
                lo = (2 * epoch + i) * 256
                served.push(bids, lo, lo + 256)
            served.rt.barrier()
    finally:
        served.close()
    spans = TRACER.spans()
    barriers = [sp.epoch for sp in spans if sp.name == "barrier"][-3:]
    joins = [sp for sp in spans if sp.name == "join.epoch"]
    assert [sp.epoch for sp in joins][-3:] == barriers
    for sp in joins[-3:]:
        probes = [p for p in spans if p.name == "join.many_side_probe"
                  and p.epoch == sp.epoch]
        assert sp.args["probe_lanes"] == sum(p.args["lanes"] for p in probes)
        assert sp.args["rows_scanned"] == probes[0].args["epoch_rows_scanned"]
        assert sp.args["rows_scanned"] == sp.args["passes"] * probes[0].args["lanes"]
        assert sp.args["pairs_kept"] >= 1 and sp.args["pairs_dropped"] >= 1
    fences = [sp for sp in spans if sp.name == "actor.fence"
              and "retract_rows" in sp.args and sp.epoch in barriers]
    assert {sp.epoch for sp in fences} == set(barriers)
    assert sum(sp.args["retract_rows"] for sp in fences) > 0
    assert sum(
        sp.args["retract_rows"] for sp in spans
        if sp.name == "actor.fence" and "retract_rows" in sp.args
    ) == sum(retracted._values.values()) - before
    assert not any(sp.traced for sp in spans)  # no profiler session ran
