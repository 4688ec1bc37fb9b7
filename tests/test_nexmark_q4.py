"""Nexmark q4 from SQL: average closing price per category.

Reference: e2e_test/nexmark/ q4 — AVG over each auction's max bid,
grouped by category. The shape composes pieces this round completed:
a grouped MAX over a join (auction x bid) lowered to an MV, and an
avg() MV over it (MV-on-MV + extended aggregates).
"""

import numpy as np
import pytest

from risingwave_tpu.frontend.session import SqlSession
from risingwave_tpu.sql import Catalog

pytestmark = pytest.mark.smoke


def test_q4_avg_of_per_auction_max():
    s = SqlSession(Catalog({}), capacity=1 << 10)
    s.execute("CREATE TABLE auction (aid BIGINT, category BIGINT)")
    s.execute("CREATE TABLE bid (auction BIGINT, price BIGINT)")
    # per-auction winning bid, carrying the category through the join
    s.execute(
        "CREATE MATERIALIZED VIEW winning AS "
        "SELECT aid, category, max(price) AS final_p "
        "FROM (SELECT aid, category FROM auction) AS a "
        "JOIN (SELECT auction, price FROM bid) AS b "
        "ON a.aid = b.auction "
        "GROUP BY aid, category"
    )
    # q4: category-level average of the winning bids (MV-on-MV)
    s.execute(
        "CREATE MATERIALIZED VIEW q4 AS "
        "SELECT category, avg(final_p) AS avg_final "
        "FROM winning GROUP BY category"
    )
    s.execute(
        "INSERT INTO auction VALUES (1, 10), (2, 10), (3, 20)"
    )
    s.execute(
        "INSERT INTO bid VALUES (1, 100), (1, 300), (2, 50), "
        "(3, 700), (3, 900)"
    )
    out, _ = s.execute("SELECT category, avg_final FROM q4 ORDER BY category")
    # cat 10: max(1)=300, max(2)=50 -> avg 175; cat 20: max(3)=900
    assert list(out["category"]) == [10, 20]
    assert list(out["avg_final"]) == pytest.approx([175.0, 900.0])
    # a higher bid arrives for auction 2: the winning bid RISES and
    # the category average follows incrementally
    s.execute("INSERT INTO bid VALUES (2, 250)")
    out, _ = s.execute(
        "SELECT category, avg_final FROM q4 ORDER BY category"
    )
    assert list(out["avg_final"]) == pytest.approx([275.0, 900.0])


def test_q4_differential_vs_batch():
    """The same q4 aggregate computed by the batch engine over the
    winning MV agrees with the streaming q4 MV."""
    s = SqlSession(Catalog({}), capacity=1 << 10)
    s.execute("CREATE TABLE auction (aid BIGINT, category BIGINT)")
    s.execute("CREATE TABLE bid (auction BIGINT, price BIGINT)")
    s.execute(
        "CREATE MATERIALIZED VIEW winning AS "
        "SELECT aid, category, max(price) AS final_p "
        "FROM (SELECT aid, category FROM auction) AS a "
        "JOIN (SELECT auction, price FROM bid) AS b "
        "ON a.aid = b.auction "
        "GROUP BY aid, category"
    )
    s.execute(
        "CREATE MATERIALIZED VIEW q4 AS "
        "SELECT category, avg(final_p) AS avg_final "
        "FROM winning GROUP BY category"
    )
    rng = np.random.default_rng(5)
    aucs = ", ".join(
        f"({i}, {int(rng.integers(0, 4))})" for i in range(1, 21)
    )
    s.execute(f"INSERT INTO auction VALUES {aucs}")
    bids = ", ".join(
        f"({int(rng.integers(1, 21))}, {int(rng.integers(1, 1000))})"
        for _ in range(120)
    )
    s.execute(f"INSERT INTO bid VALUES {bids}")
    stream, _ = s.execute("SELECT category, avg_final FROM q4")
    batch, _ = s.execute(
        "SELECT category, avg(final_p) AS avg_final FROM winning "
        "GROUP BY category"
    )
    sm = dict(zip(stream["category"], stream["avg_final"]))
    bm = dict(zip(batch["category"], batch["avg_final"]))
    assert set(sm) == set(bm)
    for c in sm:
        assert sm[c] == pytest.approx(bm[c])


# -- PR 33: the source's own text, on the served path -------------------------
# NEXmark q4 as the Flink nexmark suite and upstream RisingWave write it
# (a comma join of two bare tables, BETWEEN in the WHERE, an aggregate
# over an aggregate), with the sum and the count AVG is made of beside
# it (benchmarks/configs/nexmark_q4.json, departures): one two-input
# actor, the chained join layout, checked against the benchmark's plain
# reference after every barrier.

import importlib.util  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import jax.numpy as jnp  # noqa: E402

from risingwave_tpu.array.chunk import StreamChunk  # noqa: E402
from risingwave_tpu.executors import HashAggExecutor, HashJoinExecutor  # noqa: E402
from risingwave_tpu.executors.stream_join import StreamJoinExecutor  # noqa: E402
from risingwave_tpu.metrics import REGISTRY  # noqa: E402
from risingwave_tpu.runtime import StreamingRuntime  # noqa: E402
from risingwave_tpu.sql import StreamPlanner  # noqa: E402
from risingwave_tpu.storage.object_store import LocalFsObjectStore  # noqa: E402
from risingwave_tpu.trace import TRACER  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
import nexmark_gen  # noqa: E402  (the benchmark's generator, Beam's defaults)


def _load(*parts):
    path = os.path.join(ROOT, "benchmarks", *parts)
    spec = importlib.util.spec_from_file_location(parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("configs", "nexmark_q4_ref.py")

AUCTION_DDL = (
    "CREATE TABLE auction (id BIGINT, item_name VARCHAR, description "
    "VARCHAR, initial_bid BIGINT, reserve BIGINT, date_time TIMESTAMP, "
    "expires TIMESTAMP, seller BIGINT, category BIGINT, extra VARCHAR)"
)
BID_DDL = (
    "CREATE TABLE bid (auction BIGINT, bidder BIGINT, price BIGINT, "
    "channel VARCHAR, date_time TIMESTAMP, extra VARCHAR)"
)
Q4 = (
    "CREATE MATERIALIZED VIEW q4 AS SELECT Q.category, AVG(Q.final) AS avg, "
    "SUM(Q.final) AS total, COUNT(Q.final) AS n FROM (SELECT MAX(B.price) "
    "AS final, A.category FROM auction A, bid B WHERE A.id = B.auction AND "
    "B.date_time BETWEEN A.date_time AND A.expires GROUP BY A.id, "
    "A.category) Q GROUP BY Q.category"
)
Q8 = (
    "CREATE MATERIALIZED VIEW q8 AS SELECT p.id, p.name, p.starttime FROM "
    "(SELECT id, name, window_start AS starttime FROM TUMBLE(person, "
    "date_time, INTERVAL '10' SECOND) GROUP BY id, name, window_start) AS p "
    "JOIN (SELECT seller, window_start AS astarttime FROM TUMBLE(auction, "
    "date_time, INTERVAL '10' SECOND) GROUP BY seller, window_start) AS a "
    "ON p.id = a.seller AND p.starttime = a.astarttime"
)
T0 = 1_436_918_400_000


class Served4:
    """A session serving q4 over the two tables; chunks pushed as the
    benchmark's harness pushes them (the DML route's targets)."""

    def __init__(self, state_dir, chunk, mode, capacity=1 << 12,
                 bid_ddl=BID_DDL):
        self.chunk = chunk
        self.rt = StreamingRuntime(
            LocalFsObjectStore(str(state_dir)), checkpoint_frequency=1
        )
        self.session = SqlSession(
            Catalog({}), self.rt, capacity=capacity, exec_mode=mode
        )
        for sql in (AUCTION_DDL, bid_ddl, Q4):
            self.session.execute(sql)

    def _codes(self, stream, cols):
        out = {}
        for c, v in cols.items():
            if c == "eid":
                continue
            if (stream, c) in nexmark_gen.VOCAB:
                words = nexmark_gen.VOCAB[(stream, c)]
                v = np.asarray(self.session.strings.encode(words), np.int32)[v]
            elif (stream, c) in nexmark_gen.TEXT:
                v = self.session.strings.encode(v)
            out[c] = v
        return out

    def push(self, stream, cols, lo, hi):
        """Rows lo..hi of ``cols`` in chunks of the session's size."""
        for a in range(lo, hi, self.chunk):
            b = min(a + self.chunk, hi)
            part = self._codes(stream, {c: v[a:b] for c, v in cols.items()})
            chunk = StreamChunk.from_numpy(
                part, self.chunk, schema=self.session.catalog.tables[stream]
            )
            with self.rt.lock:
                for frag, side in self.session.dml._targets.get(stream, ()):
                    self.rt.push(frag, chunk, side)

    def push_until(self, events, done, cut):
        """Every event whose ordinal lies in [done, cut): the auctions,
        then the bids."""
        for stream in ("auction", "bid"):
            eid = events[stream]["eid"]
            lo, hi = np.searchsorted(eid, [done, cut])
            if hi > lo:
                self.push(stream, events[stream], int(lo), int(hi))

    def read(self):
        out, _ = self.session.execute("SELECT category, avg, total, n FROM q4")
        rows = list(zip(*(np.asarray(out[c]).tolist()
                          for c in ("category", "avg", "total", "n"))))
        assert len({r[0] for r in rows}) == len(rows)
        return rows

    def join(self):
        (ex,) = [
            e for e in self.rt.fragments["q4"].executors
            if isinstance(e, StreamJoinExecutor)
        ]
        return ex

    def close(self):
        self.session.close()
        for p in self.rt.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()


def _held_to_the_reference(rows, events, cut):
    """The view is the reference's, and avg is total / n to the bit."""
    assert {(c, t, n) for c, _, t, n in rows} == REF.mv(events, cut)
    for _, avg, total, n in rows:
        assert avg == total / n


def _rows(stream, rows):
    """Hand-made events: auctions (eid, id, date_time ms after T0,
    expires, category) or bids (eid, auction, price, ms after T0)."""
    cols = [np.asarray(c, np.int64) for c in zip(*rows)]
    n = len(rows)
    text = np.asarray(["x"] * n, object)
    zero = np.zeros(n, np.int64)
    if stream == "auction":
        eid, ident, ts, exp, cat = cols
        return {
            "eid": eid, "id": ident, "item_name": zero, "description": text,
            "initial_bid": zero, "reserve": zero, "date_time": T0 + ts,
            "expires": T0 + exp, "seller": zero, "category": cat,
            "extra": text,
        }
    eid, auction, price, ts = cols
    return {
        "eid": eid, "auction": auction, "bidder": zero, "price": price,
        "channel": zero, "date_time": T0 + ts, "extra": text,
    }


def _catalog(*ddl):
    from risingwave_tpu.storage.object_store import MemObjectStore

    session = SqlSession(Catalog({}), StreamingRuntime(MemObjectStore()))
    for sql in ddl:
        session.execute(sql)
    cat = Catalog(dict(session.catalog.tables))
    cat.table_pks = dict(session.catalog.table_pks)
    return cat


# -- the plan -----------------------------------------------------------------


def test_the_sources_text_plans_as_written():
    planned = StreamPlanner(_catalog(AUCTION_DDL, BID_DDL)).plan(Q4)
    pipe = planned.pipeline
    join = pipe.join
    assert type(join) is StreamJoinExecutor and join.layout == "chain"
    assert (join.left_keys, join.right_keys) == (("id",), ("auction",))
    assert join.condition is not None  # the BETWEEN, inside the join
    # a side stores what the select reads of it, a shared name renamed
    assert join.left_names == ("a__date_time", "category", "expires", "id")
    assert join.right_names == ("auction", "b__date_time", "price")
    assert join._retract == {"left": False, "right": False}
    aggs = [ex for ex in pipe.tail if isinstance(ex, HashAggExecutor)]
    assert [a.group_keys for a in aggs] == [("id", "category"), ("category",)]
    (top,) = aggs[0].calls
    # the join of two insert-only tables only inserts: one value a group
    assert top.kind == "max" and not top.materialized
    # AVG is made of the SUM and the COUNT the select lists: two calls
    assert [(c.kind, c.output) for c in aggs[1].calls] == [
        ("sum", "total"), ("count", "n"),
    ]
    assert planned.aux == () and planned.inputs == {
        "auction": "left", "bid": "right",
    }
    assert list(planned.schema) == ["category", "avg", "total", "n"]


def test_a_join_with_an_updating_side_keeps_the_materialized_max():
    keyed = BID_DDL.replace("auction BIGINT", "auction BIGINT, id BIGINT "
                            "PRIMARY KEY", 1)
    planned = StreamPlanner(_catalog(AUCTION_DDL, keyed)).plan(Q4)
    join = planned.pipeline.join
    assert type(join) is StreamJoinExecutor
    assert join._retract == {"left": False, "right": True}
    inner = next(
        ex for ex in planned.pipeline.tail if isinstance(ex, HashAggExecutor)
    )
    assert inner.calls[0].kind == "max" and inner.calls[0].materialized


def test_q8_still_plans_onto_the_bucket_join_and_its_programs():
    """nexmark_q8's two deduplicated sides keep the bucket layout and
    the XLA module its cells' metrics read (``jit_join_step_fn``)."""
    from risingwave_tpu.executors import hash_join

    person = (
        "CREATE TABLE person (id BIGINT, name VARCHAR, email_address VARCHAR, "
        "credit_card VARCHAR, city VARCHAR, state VARCHAR, date_time "
        "TIMESTAMP, extra VARCHAR)"
    )
    planned = StreamPlanner(_catalog(person, AUCTION_DDL)).plan(Q8)
    assert type(planned.pipeline.join) is HashJoinExecutor
    assert planned.pipeline.join.layout == "bucket"
    assert hash_join.join_step_fn.__name__ == "join_step_fn"
    from risingwave_tpu.ops import stream_join

    assert stream_join.stream_join_step.__name__ == "stream_join_step"


def test_explain_shows_the_join_and_both_aggregates(tmp_path):
    served = Served4(tmp_path, 64, "graph")
    try:
        out, tag = served.session.execute("EXPLAIN " + Q4)
        text = "\n".join(out["QUERY PLAN"].tolist())
        assert tag == "EXPLAIN"
        assert "StreamJoin layout=chain type=inner keys=[id = auction]" in text
        assert "residual=[Between(" in text
        assert "HashAgg group=[id, category] calls=[max(price) AS final]" in text
        assert ("HashAgg group=[category] calls=[sum(final) AS total, "
                "count(final) AS n]") in text
        out, _ = served.session.execute("EXPLAIN SELECT auction FROM bid")
        assert "StreamJoin" not in "\n".join(out["QUERY PLAN"].tolist())
    finally:
        served.close()


# -- the served view against the plain reference -------------------------------


def _events(seed, ordinals):
    gen = nexmark_gen.Generator(seed, {"first_event_rate": 20000})
    return gen.events(0, ordinals, ["auction", "bid"])


@pytest.mark.parametrize(
    "mode,seed", [("graph", 1), ("graph", 2147483999), ("serial", 1)]
)
def test_q4_served_equals_the_reference_across_barriers_and_recovery(
    tmp_path, mode, seed
):
    events = _events(seed, 24_000)
    per_key = np.unique(events["bid"]["auction"], return_counts=True)[1]
    assert (per_key > 16).sum() > 100 and per_key.max() > 48
    served = Served4(tmp_path, 512, mode, capacity=1 << 12)
    try:
        assert list(served.rt.fragments) == ["auction", "bid", "q4"]
        done = 0
        for epoch, cut in enumerate(range(4_000, 24_001, 4_000)):
            served.push_until(events, done, cut)
            done = cut
            served.rt.barrier()
            if epoch == 2:
                # kill: drop the device state, rebuild it from the store
                served.rt.wait_checkpoints()
                served.rt.recover()
            _held_to_the_reference(served.read(), events, cut)
        join = served.join()
        # both sides grew past the session's capacity, and hold the rows
        assert join.right.row_cap > 1 << 12
        assert int(jnp.sum(join.right.count)) == len(events["bid"]["eid"])
        assert int(jnp.max(join.right.count)) == per_key.max()
    finally:
        served.close()


def test_keys_past_16_32_and_64_rows_on_either_side_pair_exactly(tmp_path):
    """One auction with 70 bids (the bucket layout raised at 17), read
    at 16, 17, 33 and 65 of them; and one auction id that arrives twice
    (two rows under one key on the auction side): nothing raises, every
    pair is there."""
    served = Served4(tmp_path, 32, "graph", capacity=1 << 8)
    try:
        auctions = _rows("auction", [(0, 1000, 0, 10_000, 10),
                                     (1, 1001, 0, 10_000, 11)])
        bids = _rows("bid", [(2 + i, 1000, 100 + i, 10 + i) for i in range(70)])
        events = {"auction": auctions, "bid": bids}
        served.push("auction", auctions, 0, 2)
        done = 0
        for upto in (16, 17, 33, 65, 70):
            served.push("bid", bids, done, upto)
            done = upto
            served.rt.barrier()
            assert served.read() == [(10, 99.0 + upto, 99 + upto, 1)]
            _held_to_the_reference(served.read(), events, 2 + upto)
        assert int(jnp.max(served.join().right.count)) == 70
        # the same id again, in another category: 70 more pairs, a
        # second group (id, category) with the same highest bid
        twin = _rows("auction", [(72, 1000, 0, 10_000, 12)])
        served.push("auction", twin, 0, 1)
        served.rt.barrier()
        events["auction"] = {
            c: np.concatenate([auctions[c], twin[c]]) for c in auctions
        }
        assert int(jnp.max(served.join().left.count)) == 2
        assert sorted(served.read()) == [
            (10, 169.0, 169, 1), (12, 169.0, 169, 1)
        ]
        _held_to_the_reference(served.read(), events, 73)
    finally:
        served.close()


def test_an_auction_after_its_bids_and_a_bid_outside_its_auction(tmp_path):
    served = Served4(tmp_path, 32, "graph", capacity=1 << 8)
    try:
        # 20 bids wait for their auction; one is too early, one too late
        bids = _rows(
            "bid",
            [(i, 1000, 500 + i, 2_000 + i) for i in range(20)]
            + [(20, 1000, 9_000, 999), (21, 1000, 9_001, 12_001)],
        )
        served.push("bid", bids, 0, 22)
        served.rt.barrier()
        assert served.read() == []
        auction = _rows("auction", [(22, 1000, 1_000, 12_000, 14)])
        served.push("auction", auction, 0, 1)
        served.rt.barrier()
        assert served.read() == [(14, 519.0, 519, 1)]
        _held_to_the_reference(
            served.read(), {"auction": auction, "bid": bids}, 23
        )
        # on the bounds: date_time and expires are inside
        edge = _rows("bid", [(23, 1000, 7_000, 1_000), (24, 1000, 8_000, 12_000)])
        served.push("bid", edge, 0, 2)
        served.rt.barrier()
        assert served.read() == [(14, 8000.0, 8000, 1)]
    finally:
        served.close()


def test_dml_on_a_keyed_bid_table_retracts_its_pair(tmp_path):
    """DELETE and UPDATE name a bid by its key: the pair goes, the
    auction's highest bid falls to the next, the category's sum and
    count follow exactly."""
    keyed = (
        "CREATE TABLE bid (id BIGINT PRIMARY KEY, auction BIGINT, bidder "
        "BIGINT, price BIGINT, channel VARCHAR, date_time TIMESTAMP, "
        "extra VARCHAR)"
    )
    served = Served4(tmp_path, 32, "graph", capacity=1 << 8, bid_ddl=keyed)
    s = served.session
    try:
        s.execute(
            "INSERT INTO auction VALUES (1, 'i', 'd', 1, 2, 1000, 9000, 7, 10, "
            "'x'), (2, 'i', 'd', 1, 2, 1000, 9000, 7, 10, 'x')"
        )
        s.execute(
            "INSERT INTO bid VALUES (1, 1, 5, 100, 'c', 2000, 'x'), "
            "(2, 1, 5, 300, 'c', 2000, 'x'), (3, 1, 5, 200, 'c', 2000, 'x'), "
            "(4, 2, 5, 50, 'c', 2000, 'x')"
        )
        assert served.read() == [(10, 175.0, 350, 2)]
        s.execute("DELETE FROM bid WHERE id = 2")  # auction 1: 300 -> 200
        assert served.read() == [(10, 125.0, 250, 2)]
        s.execute("UPDATE bid SET price = 20 WHERE id = 3")  # -> 100
        assert served.read() == [(10, 75.0, 150, 2)]
        s.execute("DELETE FROM bid WHERE id = 4")  # auction 2 has no bid
        assert served.read() == [(10, 100.0, 100, 1)]
        assert int(jnp.sum(served.join().right.count)) == 2
    finally:
        served.close()


# -- spans and counters ---------------------------------------------------------


def test_one_barrier_leaves_the_joins_spans_and_gauges(tmp_path):
    """``join.epoch`` lies under ``actor.fence`` and carries what numpy
    counts in the pushed chunks; a growth is one ``join.regrow``."""
    served = Served4(tmp_path, 256, "graph", capacity=1 << 9)
    try:
        events = _events(5, 6_000)
        served.push_until(events, 0, 3_000)
        served.rt.barrier()
        TRACER.clear()
        grown = {
            dict(k)["side"]: v for k, v in
            REGISTRY.counter("join_regrows_total")._values.items()
            if dict(k)["join"] == served.join().table_id
        }
        served.push_until(events, 3_000, 6_000)
        served.rt.barrier()
        spans = TRACER.spans()
        (epoch,) = [sp for sp in spans if sp.name == "join.epoch"]
        fences = [sp for sp in spans if sp.name == "actor.fence"]
        by_sid = {sp.sid: sp for sp in spans}
        up = by_sid.get(epoch.parent)
        while up is not None and up.name != "actor.fence":
            up = by_sid.get(up.parent)
        assert up in fences
        a, b = events["auction"], events["bid"]
        per_key = np.unique(b["auction"], return_counts=True)[1]
        assert epoch.args["layout"] == "chain"
        assert epoch.args["left_rows"] == len(a["eid"])
        assert epoch.args["right_rows"] == len(b["eid"])
        assert epoch.args["key_rows_max"] == per_key.max()
        assert epoch.args["probe_lanes"] > 0
        second = np.searchsorted(b["eid"], 3_000)
        known = np.isin(b["auction"], a["id"])  # every bid's auction came first
        assert known.all()
        assert (epoch.args["pairs_kept"] + epoch.args["pairs_dropped"]
                == len(b["eid"]) - second)
        steps = [sp for sp in spans if sp.name == "actor.join_step"]
        assert steps and {sp.args["layout"] for sp in steps} == {"chain"}
        regrows = [sp for sp in spans if sp.name == "join.regrow"]
        now = {
            dict(k)["side"]: v for k, v in
            REGISTRY.counter("join_regrows_total")._values.items()
            if dict(k)["join"] == served.join().table_id
        }
        assert len(regrows) == sum(now.values()) - sum(grown.values()) >= 1
        assert all(sp.args["from"] != sp.args["to"] for sp in regrows)
        gauge = {
            (dict(k)["side"]): v for k, v in
            REGISTRY.gauge("join_side_rows")._values.items()
            if dict(k)["join"] == served.join().table_id
        }
        assert gauge == {"left": len(a["eid"]), "right": len(b["eid"])}
    finally:
        served.close()
