"""Lookup/delta join over shared CREATE INDEX arrangements (VERDICT r4
missing #5; reference: lookup.rs + frontend delta-join rule gated on a
session variable)."""

import numpy as np
import pytest

from risingwave_tpu.frontend.session import SqlSession
from risingwave_tpu.sql import Catalog

pytestmark = pytest.mark.smoke


def test_create_index_and_delta_join_from_sql():
    s = SqlSession(Catalog({}), capacity=1 << 10)
    s.execute("CREATE TABLE a (k BIGINT, x BIGINT)")
    s.execute("CREATE TABLE b (k BIGINT, y BIGINT)")
    # pre-index + pre-join data: index backfills, join seeds
    s.execute("INSERT INTO a VALUES (1, 10), (2, 20)")
    s.execute("INSERT INTO b VALUES (1, 100), (3, 300)")
    s.execute("CREATE INDEX ia ON a (k)")
    s.execute("CREATE INDEX ib ON b (k)")
    s.execute("SET enable_delta_join = true")
    s.execute(
        "CREATE MATERIALIZED VIEW dj AS "
        "SELECT a.k AS k, x, y FROM a JOIN b ON a.k = b.k"
    )
    # the join SHARES the index arrangements (no duplicated state)
    planned = s.catalog.mvs["dj"]
    from risingwave_tpu.executors.lookup import DeltaJoinExecutor

    join = planned.pipeline.join
    assert isinstance(join, DeltaJoinExecutor)
    assert join.left_arr is s.catalog.indexes["ia"]["arrangement"]
    assert join.right_arr is s.catalog.indexes["ib"]["arrangement"]

    out, _ = s.execute("SELECT k, x, y FROM dj")
    assert sorted(zip(out["k"], out["x"], out["y"])) == [(1, 10, 100)]

    # deltas on both sides join against the other's arrangement
    s.execute("INSERT INTO a VALUES (3, 30)")
    s.execute("INSERT INTO b VALUES (2, 200), (1, 101)")
    out, _ = s.execute("SELECT k, x, y FROM dj ORDER BY k")
    assert sorted(zip(out["k"], out["x"], out["y"])) == [
        (1, 10, 100),
        (1, 10, 101),
        (2, 20, 200),
        (3, 30, 300),
    ]


def test_without_session_var_or_index_no_delta_join():
    """The delta rule declines without the session variable or the
    indexes; the bare-table join then falls to the hash path, which
    reads a bare table as the derived table of the columns the select
    names (PR 33; it used to refuse one)."""
    from risingwave_tpu.executors.lookup import DeltaJoinExecutor

    s = SqlSession(Catalog({}), capacity=1 << 10)
    s.execute("CREATE TABLE a (k BIGINT, x BIGINT)")
    s.execute("CREATE TABLE b (k BIGINT, y BIGINT)")
    s.execute("CREATE INDEX ia ON a (k)")
    s.execute("CREATE INDEX ib ON b (k)")
    sql = (
        "CREATE MATERIALIZED VIEW hj AS "
        "SELECT a.k AS k, x, y FROM a JOIN b ON a.k = b.k"
    )
    s.execute(sql)  # var off -> the hash path
    assert not isinstance(s.catalog.mvs["hj"].pipeline.join, DeltaJoinExecutor)
    s.execute("SET enable_delta_join = true")
    # no index covers (x)/(y): the delta rule declines
    s.execute(
        "CREATE MATERIALIZED VIEW hj2 AS "
        "SELECT a.k AS k, x, y FROM a JOIN b ON a.x = b.y"
    )
    assert not isinstance(s.catalog.mvs["hj2"].pipeline.join, DeltaJoinExecutor)
    # subquery-form joins never take the delta path
    s.execute(
        "CREATE MATERIALIZED VIEW hj3 AS SELECT l.k AS k, x, y FROM "
        "(SELECT k, x FROM a) AS l JOIN (SELECT k AS k2, y FROM b) AS r "
        "ON l.k = r.k2"
    )
    from risingwave_tpu.executors.lookup import DeltaJoinExecutor

    join = getattr(s.catalog.mvs["hj3"].pipeline, "join", None)
    assert not isinstance(join, DeltaJoinExecutor)


def test_delta_join_retractions_match_hash_join_oracle():
    """Random insert/delete streams on both sides: the delta join's
    maintained MV equals a HashJoin-maintained oracle."""
    import jax.numpy as jnp

    from risingwave_tpu.array.chunk import StreamChunk
    from risingwave_tpu.executors.hash_join import HashJoinExecutor
    from risingwave_tpu.executors.lookup import (
        DeltaJoinExecutor,
        IndexArrangement,
    )

    la = IndexArrangement(("k",), ("lid",), ("x",), "dja.l")
    ra = IndexArrangement(("k",), ("rid",), ("y",), "dja.r")
    dj = DeltaJoinExecutor(
        la, ra, ("k",), ("k",),
        [("k", "k"), ("x", "x"), ("lid", "lid")],
        [("y", "y"), ("rid", "rid")],
    )
    hj = HashJoinExecutor(
        ("k",), ("k2",),
        {"k": jnp.int64, "x": jnp.int64, "lid": jnp.int64},
        {"k2": jnp.int64, "y": jnp.int64, "rid": jnp.int64},
        capacity=1 << 10, fanout=16, out_cap=1 << 12,
        table_id="djo",
    )

    def mv_apply(mv, chunks, names):
        for c in chunks:
            d = c.to_numpy(with_ops=True)
            for i in range(len(d["__op__"])):
                row = tuple(int(d[n][i]) for n in names)
                if int(d["__op__"][i]) in (1, 3):
                    mv.discard(row)
                else:
                    mv.add(row)

    rng = np.random.default_rng(17)
    dmv, hmv = set(), set()
    live_l, live_r = {}, {}
    names = ("k", "x", "lid", "y", "rid")
    lid = rid = 0
    for epoch in range(40):
        for _ in range(int(rng.integers(1, 4))):
            side = rng.random() < 0.5
            delete = rng.random() < 0.35
            if side:
                if delete and live_l:
                    key = rng.choice(list(live_l))
                    k, x = live_l.pop(int(key))
                    rows = {"k": [k], "x": [x], "lid": [int(key)]}
                    ops = np.asarray([1], np.int32)
                else:
                    k = int(rng.integers(0, 6))
                    x = int(rng.integers(0, 100))
                    live_l[lid] = (k, x)
                    rows = {"k": [k], "x": [x], "lid": [lid]}
                    ops = np.asarray([0], np.int32)
                    lid += 1
                c = StreamChunk.from_numpy(
                    {n: np.asarray(v, np.int64) for n, v in rows.items()},
                    4, ops=ops,
                )
                # arrangement FIRST (runtime routing order), then join
                la.apply(c)
                mv_apply(dmv, dj.apply_left(c), names)
                mv_apply(hmv, hj.apply_left(c), names)
            else:
                if delete and live_r:
                    key = rng.choice(list(live_r))
                    k, y = live_r.pop(int(key))
                    rows = {"k": [k], "y": [y], "rid": [int(key)]}
                    ops = np.asarray([1], np.int32)
                else:
                    k = int(rng.integers(0, 6))
                    y = int(rng.integers(0, 100))
                    live_r[rid] = (k, y)
                    rows = {"k": [k], "y": [y], "rid": [rid]}
                    ops = np.asarray([0], np.int32)
                    rid += 1
                c = StreamChunk.from_numpy(
                    {n: np.asarray(v, np.int64) for n, v in rows.items()},
                    4, ops=ops,
                )
                c2 = StreamChunk.from_numpy(
                    {
                        ("k2" if n == "k" else n): np.asarray(v, np.int64)
                        for n, v in rows.items()
                    },
                    4, ops=ops,
                )
                ra.apply(c)
                mv_apply(dmv, dj.apply_right(c), names)
                mv_apply(hmv, hj.apply_right(c2), names)
        hj.on_barrier(None)
        assert dmv == hmv, f"diverged at epoch {epoch}"
    assert len(dmv) > 3
