"""NEXmark q9 "winning bids", the source's own text on the served path.

Apache Beam's WinningBids as the Flink nexmark suite (queries/q9.sql)
and upstream RisingWave (e2e_test/nexmark, q9.slt.part) write it: every
bid joined to its auction while the auction is open, ``A.*`` beside the
bid's columns, ROW_NUMBER() OVER (PARTITION BY A.id ORDER BY B.price
DESC, B.date_time ASC) <= 1. Planned as upstream plans it —
StreamGroupTopN over StreamHashJoin — in ONE two-input actor: the
chained join handing on every column of both sides, the retractable
GroupTopN with an order of two keys behind it, a host-map view of one
wide row an auction. Held to the benchmark's plain reference
(benchmarks/configs/nexmark_q9_ref.py) after every barrier.
"""

import hashlib
import importlib.util
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.over_window import GeneralOverWindowExecutor
from risingwave_tpu.executors.project import ProjectExecutor
from risingwave_tpu.executors.stream_join import StreamJoinExecutor
from risingwave_tpu.executors.top_n import GroupTopNExecutor
from risingwave_tpu.executors.top_n_plain import (
    RetractableGroupTopNExecutor,
    _rank,
)
from risingwave_tpu.frontend.session import SqlSession
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.sql import Catalog, StreamPlanner
from risingwave_tpu.sql import parser as P
from risingwave_tpu.storage.object_store import LocalFsObjectStore, MemObjectStore
from risingwave_tpu.trace import TRACER

pytestmark = pytest.mark.smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
import nexmark_gen  # noqa: E402  (the benchmark's generator, Beam's defaults)


def _load(*parts):
    path = os.path.join(ROOT, "benchmarks", *parts)
    spec = importlib.util.spec_from_file_location(parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load("configs", "nexmark_q9_ref.py")

AUCTION_DDL = (
    "CREATE TABLE auction (id BIGINT, item_name VARCHAR, description "
    "VARCHAR, initial_bid BIGINT, reserve BIGINT, date_time TIMESTAMP, "
    "expires TIMESTAMP, seller BIGINT, category BIGINT, extra VARCHAR)"
)
BID_DDL = (
    "CREATE TABLE bid (auction BIGINT, bidder BIGINT, price BIGINT, "
    "channel VARCHAR, date_time TIMESTAMP, extra VARCHAR)"
)
Q9 = """CREATE MATERIALIZED VIEW q9 AS
SELECT id, item_name, description, initial_bid, reserve, date_time, expires,
       seller, category, auction, bidder, price, bid_date_time
FROM (
  SELECT A.*, B.auction, B.bidder, B.price, B.date_time AS bid_date_time,
         ROW_NUMBER() OVER (PARTITION BY A.id
                            ORDER BY B.price DESC, B.date_time ASC) AS rownum
  FROM auction A, bid B
  WHERE A.id = B.auction AND B.date_time BETWEEN A.date_time AND A.expires
) tmp
WHERE rownum <= 1"""
COLUMNS = (
    "id", "item_name", "description", "initial_bid", "reserve", "date_time",
    "expires", "seller", "category", "auction", "bidder", "price",
    "bid_date_time",
)
T0 = 1_436_918_400_000
ITEMS = nexmark_gen.VOCAB[("auction", "item_name")]


class Served9:
    """A session serving q9 over the two tables; chunks pushed as the
    benchmark's harness pushes them (the DML route's targets)."""

    def __init__(self, state_dir, chunk, mode="graph", capacity=1 << 12):
        self.chunk = chunk
        self.rt = StreamingRuntime(
            LocalFsObjectStore(str(state_dir)), checkpoint_frequency=1
        )
        self.session = SqlSession(
            Catalog({}), self.rt, capacity=capacity, exec_mode=mode
        )
        for sql in (AUCTION_DDL, BID_DDL, Q9):
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
        out, _ = self.session.execute(f"SELECT {', '.join(COLUMNS)} FROM q9")
        cols = [np.asarray(out[c]).tolist() for c in COLUMNS]
        rows = set(zip(*cols))
        assert len(rows) == len(cols[0])  # no row twice
        assert len({r[0] for r in rows}) == len(rows)  # one row an auction
        return rows

    def executors(self):
        return self.rt.fragments["q9"].executors

    def topn(self):
        (ex,) = [
            e for e in self.executors()
            if isinstance(e, RetractableGroupTopNExecutor)
        ]
        return ex

    def close(self):
        self.session.close()
        for p in self.rt.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()


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
            "eid": eid, "id": ident, "item_name": zero,
            "description": np.asarray([f"item {i}" for i in ident], object),
            "initial_bid": ident * 3, "reserve": ident * 5,
            "date_time": T0 + ts, "expires": T0 + exp, "seller": ident + 7,
            "category": cat, "extra": text,
        }
    eid, auction, price, ts = cols
    return {
        "eid": eid, "auction": auction, "bidder": eid + 100, "price": price,
        "channel": zero, "date_time": T0 + ts, "extra": text,
    }


def _winner(rows, auction):
    """(bidder, price, bid_date_time - T0) of the auction's row."""
    (r,) = [r for r in rows if r[0] == auction]
    return r[10], r[11], r[12] - T0


def _catalog(*ddl):
    session = SqlSession(Catalog({}), StreamingRuntime(MemObjectStore()))
    for sql in ddl:
        session.execute(sql)
    cat = Catalog(dict(session.catalog.tables))
    cat.table_pks = dict(session.catalog.table_pks)
    return cat


# -- the parser and the plan ---------------------------------------------------


def test_a_qualified_star_parses_in_any_position_and_expands():
    sel = P.parse("SELECT b.price, a.*, b.* FROM auction a, bid b "
                  "WHERE a.id = b.auction")
    assert [type(it.expr) for it in sel.items] == [P.Ident, P.Star, P.Star]
    assert [it.expr.qualifier for it in sel.items[1:]] == ["a", "b"]
    assert P.parse("SELECT * FROM bid").items[0].expr.qualifier is None
    from risingwave_tpu.sql.typing import expand_star

    cat = _catalog(AUCTION_DDL, BID_DDL)
    out = expand_star(sel, cat)
    assert [(it.expr.qualifier, it.expr.name) for it in out.items] == (
        [("b", "price")]
        + [("a", c) for c in cat.tables["auction"].names]
        + [("b", c) for c in cat.tables["bid"].names]
    )
    with pytest.raises(ValueError, match=r"SELECT c\.\*"):
        expand_star(
            P.parse("SELECT c.* FROM auction a, bid b WHERE a.id = b.auction"),
            cat,
        )


def test_the_sources_text_plans_as_join_then_group_topn():
    planned = StreamPlanner(_catalog(AUCTION_DDL, BID_DDL)).plan(Q9)
    pipe = planned.pipeline
    join = pipe.join
    assert type(join) is StreamJoinExecutor and join.layout == "chain"
    assert (join.left_keys, join.right_keys) == (("id",), ("auction",))
    assert join.condition is not None  # the BETWEEN, inside the join
    # every column the source selects of either side is stored, a shared
    # name renamed, each side's row id (its stream key) beside them
    assert join.left_names == (
        "_l_row_id", "a__date_time", "category", "description", "expires",
        "extra", "id", "initial_bid", "item_name", "reserve", "seller",
    )
    assert join.right_names == (
        "_r_row_id", "auction", "b__date_time", "bidder", "price",
    )
    assert join._retract == {"left": False, "right": False}
    kinds = [type(ex) for ex in pipe.tail]
    assert kinds == [
        ProjectExecutor, RetractableGroupTopNExecutor, ProjectExecutor,
        type(planned.mview),
    ]
    everything = pipe.left + pipe.right + [join] + pipe.tail
    assert not any(
        isinstance(ex, (GeneralOverWindowExecutor, GroupTopNExecutor))
        for ex in everything
    )
    gt = pipe.tail[1]
    assert gt.group_by == ("id",) and gt.limit == 1
    assert gt.order == (("price", True), ("bid_date_time", False))
    assert gt.pk == ("_l_row_id", "_r_row_id")  # the join's stream key
    assert gt.upstream == "StreamJoinExecutor"
    assert planned.aux == () and planned.inputs == {
        "auction": "left", "bid": "right",
    }
    assert tuple(planned.schema)[:13] == COLUMNS
    assert tuple(planned.mview.pk) == ("_l_row_id", "_r_row_id")


def test_a_window_column_the_select_does_not_list_rides_along():
    """ORDER BY a column the inner select leaves out: the join's
    projection carries it under a hidden name, the view does not."""
    sql = (
        "CREATE MATERIALIZED VIEW w AS SELECT id, price FROM (SELECT A.id, "
        "B.price, ROW_NUMBER() OVER (PARTITION BY A.id ORDER BY B.bidder "
        "DESC) AS rn FROM auction A, bid B WHERE A.id = B.auction) t "
        "WHERE rn <= 2"
    )
    planned = StreamPlanner(_catalog(AUCTION_DDL, BID_DDL)).plan(sql)
    gt = planned.pipeline.tail[1]
    assert gt.order == (("_w_bidder", True),) and gt.limit == 2
    assert [c for c in planned.schema if not c.startswith("_")] == [
        "id", "price",
    ]


@pytest.mark.parametrize(
    "call",
    [
        "RANK()",  # ties numbered alike: no Top-N of rows
        "DENSE_RANK()",
    ],
)
def test_shapes_the_rule_refuses_keep_the_general_over_window(call):
    """What is no ROW_NUMBER() stays on the window path; the rank
    selected (R-m13) no longer does (tests/test_nexmark_q19.py)."""
    sql = (
        "CREATE MATERIALIZED VIEW w AS SELECT auction, price, rn FROM "
        f"(SELECT auction, price, {call} OVER (PARTITION BY auction "
        "ORDER BY price DESC) AS rn FROM bid) t WHERE rn <= 1"
    )
    planned = StreamPlanner(_catalog(AUCTION_DDL, BID_DDL)).plan(sql)
    assert any(
        isinstance(ex, GeneralOverWindowExecutor)
        for ex in planned.pipeline.executors
    )
    numbered = StreamPlanner(_catalog(AUCTION_DDL, BID_DDL)).plan(
        sql.replace(call, "ROW_NUMBER()")
    )
    kinds = [type(ex) for ex in numbered.pipeline.executors]
    assert GeneralOverWindowExecutor not in kinds
    assert RetractableGroupTopNExecutor in kinds


def test_explain_shows_the_join_and_the_topn_behind_it(tmp_path):
    served = Served9(tmp_path, 64)
    try:
        out, tag = served.session.execute("EXPLAIN " + Q9)
        text = "\n".join(out["QUERY PLAN"].tolist())
        assert tag == "EXPLAIN"
        assert "rownum <= 1: per-group top-n, not a window" in text
        assert "StreamJoin layout=chain type=inner keys=[id = auction]" in text
        assert "residual=[Between(" in text
        assert (
            "RetractableGroupTopN group=[id] order=[price DESC, "
            "bid_date_time, stream key] limit=1"
        ) in text
        assert "OverWindow" not in text.split("-- stream plan")[1]
    finally:
        served.close()


# -- the served view against the plain reference -------------------------------


def _events(seed, ordinals):
    gen = nexmark_gen.Generator(seed, {"first_event_rate": 20000})
    return gen.events(0, ordinals, ["auction", "bid"])


@pytest.mark.parametrize(
    "mode,seed", [("graph", 1), ("graph", 2147483999), ("serial", 1)]
)
def test_q9_served_equals_the_reference_across_barriers_and_recovery(
    tmp_path, mode, seed
):
    events = _events(seed, 24_000)
    served = Served9(tmp_path, 512, mode, capacity=1 << 12)
    try:
        assert list(served.rt.fragments) == ["auction", "bid", "q9"]
        done, seen = 0, set()
        for epoch, cut in enumerate(range(4_000, 24_001, 4_000)):
            served.push_until(events, done, cut)
            done = cut
            served.rt.barrier()
            if epoch == 2:
                # kill: drop the device state, rebuild it from the store
                served.rt.wait_checkpoints()
                served.rt.recover()
            rows = served.read()
            assert rows == REF.mv(events, cut, nexmark_gen.VOCAB)
            seen |= rows
        # auctions changed hands between barriers: rows were retracted
        assert len(seen) > len(rows) > 1_000
        # the store holds every pair (reduced: retained_history)
        assert int(served.topn().table.num_live()) == len(
            events["bid"]["eid"]
        )
    finally:
        served.close()


def test_ties_fall_to_date_time_then_to_arrival(tmp_path):
    served = Served9(tmp_path, 32, capacity=1 << 8)
    try:
        auctions = _rows("auction", [(0, 1000, 0, 10_000, 10),
                                     (1, 1001, 0, 10_000, 11)])
        served.push("auction", auctions, 0, 2)
        bids = _rows("bid", [
            (2, 1000, 500, 3_000),
            (3, 1000, 500, 2_000),  # same price, earlier date_time: wins
            (4, 1000, 400, 1_000),  # earlier still, but a lower price
            (5, 1001, 700, 4_000),
            (6, 1001, 700, 4_000),  # ties on both: the first to arrive stays
        ])
        served.push("bid", bids, 0, 5)
        served.rt.barrier()
        rows = served.read()
        assert _winner(rows, 1000) == (103, 500, 2_000)
        assert _winner(rows, 1001) == (105, 700, 4_000)
        events = {"auction": auctions, "bid": bids}
        assert rows == REF.mv(events, 7, nexmark_gen.VOCAB)
        # every column of the auction is there, whole
        (r,) = [r for r in rows if r[0] == 1000]
        assert r[:10] == (
            1000, ITEMS[0], "item 1000", 3000, 5000, T0, T0 + 10_000, 1007,
            10, 1000,
        )
        # a later epoch: a bid that ties the winner on price and has a
        # later date_time changes nothing; one that ties on both neither
        more = _rows("bid", [(7, 1000, 500, 2_500), (8, 1000, 500, 2_000)])
        served.push("bid", more, 0, 2)
        served.rt.barrier()
        assert _winner(served.read(), 1000) == (103, 500, 2_000)
    finally:
        served.close()


def test_an_auction_after_its_bids_and_a_bid_outside_its_auction(tmp_path):
    served = Served9(tmp_path, 32, capacity=1 << 8)
    try:
        # 20 bids wait for their auction; the two highest are too early
        # and too late
        bids = _rows(
            "bid",
            [(i, 1000, 500 + i, 2_000 + i) for i in range(20)]
            + [(20, 1000, 9_000, 999), (21, 1000, 9_001, 12_001)],
        )
        served.push("bid", bids, 0, 22)
        served.rt.barrier()
        assert served.read() == set()
        auction = _rows("auction", [(22, 1000, 1_000, 12_000, 14)])
        served.push("auction", auction, 0, 1)
        served.rt.barrier()
        rows = served.read()
        assert _winner(rows, 1000) == (119, 519, 2_019)
        assert rows == REF.mv(
            {"auction": auction, "bid": bids}, 23, nexmark_gen.VOCAB
        )
        # on the bounds: date_time and expires are inside
        edge = _rows("bid", [(23, 1000, 7_000, 1_000), (24, 1000, 8_000, 12_000)])
        served.push("bid", edge, 0, 2)
        served.rt.barrier()
        assert _winner(served.read(), 1000) == (124, 8_000, 12_000)
    finally:
        served.close()


def test_a_higher_bid_in_a_later_epoch_retracts_the_auctions_row(tmp_path):
    """U- of the old wide row, then U+ of the new, both out of the
    Top-N's barrier; the counters and the spans say what ran."""
    served = Served9(tmp_path, 32, capacity=1 << 8)
    try:
        auctions = _rows("auction", [(0, 1000, 0, 10_000, 10),
                                     (1, 1001, 0, 10_000, 11)])
        served.push("auction", auctions, 0, 2)
        served.push("bid", _rows("bid", [(2, 1000, 100, 1_000),
                                         (3, 1001, 50, 1_000)]), 0, 2)
        served.rt.barrier()
        assert _winner(served.read(), 1000) == (102, 100, 1_000)
        gt = served.topn()
        emitted = REGISTRY.counter("group_topn_emitted_rows_total")
        fed = REGISTRY.counter("group_topn_input_rows_total")

        def count(counter, **labels):
            return sum(
                v for k, v in counter._values.items()
                if all(dict(k).get(a) == b for a, b in labels.items())
            )

        before = {
            op: count(emitted, table_id=gt.table_id, op=op)
            for op in ("retract", "insert")
        }
        fed_before = count(
            fed, table_id=gt.table_id, upstream="StreamJoinExecutor"
        )
        TRACER.clear()
        served.push("bid", _rows("bid", [(4, 1000, 300, 2_000),
                                         (5, 1000, 200, 2_100)]), 0, 2)
        served.rt.barrier()
        rows = served.read()
        assert _winner(rows, 1000) == (104, 300, 2_000)
        assert _winner(rows, 1001) == (103, 50, 1_000)
        after = {
            op: count(emitted, table_id=gt.table_id, op=op)
            for op in ("retract", "insert")
        }
        assert (after["retract"] - before["retract"],
                after["insert"] - before["insert"]) == (1, 1)
        assert count(
            fed, table_id=gt.table_id, upstream="StreamJoinExecutor"
        ) - fed_before == 2
        spans = TRACER.spans()
        (rank,) = [sp for sp in spans if sp.name == "topn.rank"]
        # two order keys; a pair's sixteen lanes: 13 of 8 B, 3 codes of 4
        assert rank.args["order_keys"] == 2
        assert rank.args["row_bytes"] == 13 * 8 + 3 * 4
        # sort operands a pass: a word a digit (the three key lanes and
        # the two order lanes, two digits each, and liveness), the slot
        assert rank.args["words"] == 2 * 5 + 1 + 1
        (epoch,) = [sp for sp in spans if sp.name == "join.epoch"]
        assert epoch.args["pairs_kept"] == 2
        assert epoch.args["emit_row_bytes"] == 13 * 8 + 3 * 4
    finally:
        served.close()


# -- the single-key program is the one q18 runs --------------------------------

# sha256 of ``_rank.lower(...).as_text()`` at q18's shapes (2^22 lanes,
# (bidder, auction) groups, date_time DESC, the row id), taken on the
# commit before the order became a list (0fc41e7) and standing until
# the ranking became a named tuple (PR 46: the results' names and the
# place of the slots' iota, no operation; tests/test_nexmark_q19.py's
# pin of the same text): the digits, the words and the one sort of a
# single-key Top-N are what they were
RANK_Q18_SHA256 = (
    "3c78c8f708df032a5f90537bb1d6509cdfe2962752957a39c85c8b55b745d067"
)


def _lower_rank(order_col, desc, dtypes, group_by, pk, lanes=1 << 22):
    ex = RetractableGroupTopNExecutor(
        group_by, order_col, 1, pk, dtypes, desc=desc, capacity=256,
        table_id="pin.gtopn",
    )

    def big(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                tuple(lanes if d == 256 else d for d in a.shape), a.dtype
            ),
            tree,
        )

    return ex, _rank.lower(
        big(ex.table), big(ex.rows), big(ex.shadow), big(ex.emitted),
        big(ex.epoch_dirty),
        k=1, desc=ex.desc, n_group=len(group_by), order_col=ex.order_col,
    )


Q18_DTYPES = {
    "auction": jnp.int64, "bidder": jnp.int64, "price": jnp.int64,
    "channel": jnp.int32, "date_time": jnp.int64, "extra": jnp.int32,
    "_row_id": jnp.int64,
}


def test_a_single_key_topn_lowers_to_the_program_q18_ran_before():
    ex, low = _lower_rank(
        "date_time", True, Q18_DTYPES, ("bidder", "auction"), ("_row_id",)
    )
    # one key goes to the program as it always did: a column, a flag
    assert (ex.order_col, ex.desc) == ("date_time", True)
    assert ex.order == (("date_time", True),)
    text = low.as_text()
    assert text.startswith("module @jit__rank ")
    assert hashlib.sha256(text.encode()).hexdigest() == RANK_Q18_SHA256
    # and the same key written as a list of one is the same program
    _, as_list = _lower_rank(
        (("date_time", True),), False, Q18_DTYPES, ("bidder", "auction"),
        ("_row_id",),
    )
    assert as_list.as_text() == text


def test_two_order_keys_pack_below_each_other_and_rank_exactly():
    """The executor alone: (price DESC, date_time ASC) against a
    lexsort, ties to the stream key."""
    rng = np.random.default_rng(9)
    n = 400
    cols = {
        "g": rng.integers(0, 12, n), "price": rng.integers(0, 6, n),
        "date_time": rng.integers(0, 5, n), "rid": np.arange(n),
    }
    ex = RetractableGroupTopNExecutor(
        ("g",), (("price", True), ("date_time", False)), 2, ("rid",),
        {c: jnp.int64 for c in cols}, capacity=1 << 10, table_id="two.gtopn",
    )
    for a in range(0, n, 100):
        ex.apply(StreamChunk.from_numpy(
            {c: v[a:a + 100].astype(np.int64) for c, v in cols.items()}, 128
        ))
    out = ex.on_barrier(None)
    got = set()
    for c in out:
        d = c.to_numpy()
        got |= set(zip(*(d[k].tolist() for k in ("g", "rid"))))
    order = np.lexsort((cols["rid"], cols["date_time"], -cols["price"],
                        cols["g"]))
    want = set()
    for g in range(12):
        rows = [i for i in order if cols["g"][i] == g][:2]
        want |= {(g, int(cols["rid"][i])) for i in rows}
    assert got == want
