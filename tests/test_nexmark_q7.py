"""NEXmark q7 "highest bid" as the source writes it: the bid stream
joined to its own ten-second MAX on the price, ``window_end`` out of
TUMBLE, ``timestamp - INTERVAL`` in a scalar expression, a bare table
and a derived table told apart by their qualifiers, the band in WHERE
— through the served path. The text plans by the planner's own rules
onto the chained join keyed by the price with ``bid`` on both inputs
and a latched MAX; the served view equals the benchmark's plain
reference row for row after every barrier, over ties on a maximum, a
bid stamped exactly on a window's end, a price held by more than 16
rows, and across a checkpoint -> recover() after a retraction; and the
hop step of a query that names no ``window_end`` (q5's) lowers to the
program it lowered to before."""

import hashlib
import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.hash_agg import HashAggExecutor
from risingwave_tpu.executors.hop_window import HopWindowExecutor, _hop_step
from risingwave_tpu.executors.stream_join import StreamJoinExecutor
from risingwave_tpu.frontend import SqlSession
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.sql import Catalog, StreamPlanner
from risingwave_tpu.sql import parser as P
from risingwave_tpu.storage.object_store import LocalFsObjectStore, MemObjectStore
from risingwave_tpu.trace import TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
import nexmark_gen  # noqa: E402  (the benchmark's generator: Beam's bids)


def _load_ref():
    path = os.path.join(ROOT, "benchmarks", "configs", "nexmark_q7_ref.py")
    spec = importlib.util.spec_from_file_location("nexmark_q7_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_ref()  # the plain reference the benchmark's cell is held to

BID_DDL = (
    "CREATE TABLE bid (auction BIGINT, bidder BIGINT, price BIGINT, "
    "channel VARCHAR, date_time TIMESTAMP, extra VARCHAR)"
)
COLUMNS = ("auction", "bidder", "price", "channel", "date_time", "extra")
VIEW = ("auction", "price", "bidder", "date_time", "window_end")
# the source's text (upstream RisingWave's nexmark q7), and beside its
# four columns the window a row stands under: a bid on a window's edge
# may stand under two (benchmarks/configs/nexmark_q7.json, departures)
Q7 = (
    "CREATE MATERIALIZED VIEW q7 AS "
    "SELECT B.auction, B.price, B.bidder, B.date_time, "
    "B1.date_time AS window_end "
    "FROM bid B JOIN ("
    "SELECT MAX(price) AS maxprice, window_end AS date_time "
    "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
    "GROUP BY window_end"
    ") B1 ON B.price = B1.maxprice "
    "WHERE B.date_time BETWEEN B1.date_time - INTERVAL '10' SECOND "
    "AND B1.date_time"
)
T = 1_436_918_400_000  # the generator's base time, a multiple of 10 s


class Served:
    def __init__(self, state_dir, chunk, mode="graph", capacity=1 << 12):
        self.chunk = chunk
        self.rt = StreamingRuntime(
            LocalFsObjectStore(str(state_dir)), checkpoint_frequency=1
        )
        self.session = SqlSession(
            Catalog({}), self.rt, capacity=capacity, exec_mode=mode
        )
        self.session.execute(BID_DDL)
        self.session.execute(Q7)
        self.channels = np.asarray(
            self.session.strings.encode(nexmark_gen.VOCAB[("bid", "channel")]),
            np.int32,
        )

    def push(self, bids, lo, hi):
        cols = {c: bids[c][lo:hi] for c in COLUMNS}
        cols["channel"] = self.channels[cols["channel"]]
        cols["extra"] = self.session.strings.encode(cols["extra"])
        chunk = StreamChunk.from_numpy(
            cols, self.chunk, schema=self.session.catalog.tables["bid"]
        )
        with self.rt.lock:
            for frag, side in self.session.dml._targets.get("bid", ()):
                self.rt.push(frag, chunk, side)

    def read(self):
        out, _ = self.session.execute(
            "SELECT " + ", ".join(VIEW) + " FROM q7"
        )
        rows = list(zip(*(np.asarray(out[c]).tolist() for c in VIEW)))
        assert len(rows) == len(set(rows))
        return set(rows)

    def join(self):
        (ex,) = [
            e for e in self.rt.fragments["q7"].executors
            if isinstance(e, StreamJoinExecutor)
        ]
        return ex

    def close(self):
        self.session.close()
        for p in self.rt.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()


def _generated(seed, n, rate):
    """``n`` of Beam's bids at ``rate`` events a second of event time."""
    gen = nexmark_gen.Generator(seed, {"first_event_rate": rate})
    bids = gen.events(0, n * 50 // 46 + 50, ["bid"])["bid"]
    return {c: v[:n] for c, v in bids.items()}


def _written(rows):
    """rows: (auction, price, ms after T) — bids written out by hand."""
    a, p, ms = (np.asarray(c, np.int64) for c in zip(*rows))
    n = len(rows)
    return {
        "auction": a, "bidder": a + 100, "price": p, "date_time": T + ms,
        "channel": np.zeros(n, np.int64),
        "extra": np.asarray([f"x{i}" for i in range(n)], object),
    }


def _events(bids):
    return {"bid": dict(bids, eid=np.arange(len(bids["price"])))}


def _spans(name):
    return [sp for sp in TRACER.spans() if sp.name == name]


# -- the plan ---------------------------------------------------------------


def _bid_catalog():
    session = SqlSession(Catalog({}), StreamingRuntime(MemObjectStore()))
    session.execute(BID_DDL)
    return Catalog({"bid": session.catalog.tables["bid"]})


def test_the_sources_text_plans_by_the_planners_own_rules():
    planned = StreamPlanner(_bid_catalog(), capacity=1 << 12).plan(Q7)
    pipe = planned.pipeline
    # one table's chunk feeds both inputs of one join
    assert planned.inputs == {"bid": "both"} and pipe.head == []
    join = pipe.join
    assert type(join) is StreamJoinExecutor
    # the equi key is the PRICE alone; the stored side is the raw
    # stream (inserts only), the other an aggregate that takes its
    # word back
    assert (join.left_keys, join.right_keys) == (("price",), ("maxprice",))
    assert join._retract == {"left": False, "right": True}
    # the band of the WHERE is the residual the walk evaluates, on the
    # bid's own date_time (told from B1's by its qualifier)
    assert "b__date_time" in join.left_names
    assert join.right_names == ("date_time", "maxprice")
    assert type(join.condition).__name__ == "Between"
    assert "10000" in repr(join.condition)
    # the bid side stores what the query reads of a bid and its row id
    assert set(join.left_names) == {
        "_row_id", "auction", "bidder", "price", "b__date_time",
    }
    # the other side: TUMBLE handing on window_end, a latched MAX
    hop, agg = pipe.right[0], pipe.right[1]
    assert isinstance(hop, HopWindowExecutor)
    assert (hop.size_ms, hop.slide_ms, hop.out_end) == (
        10_000, 10_000, "window_end",
    )
    assert isinstance(agg, HashAggExecutor)
    assert agg.group_keys == ("window_end",)
    assert [(c.kind, c.output, c.materialized) for c in agg.calls] == [
        ("max", "maxprice", False)
    ]
    # no filter is left behind the join: the band went into it
    assert [type(e).__name__ for e in pipe.tail] == [
        "ProjectExecutor", "MaterializeExecutor",
    ]
    assert tuple(planned.schema) == VIEW + ("_row_id",)
    assert set(planned.mview.pk) == {"_row_id", "window_end"}


@pytest.mark.parametrize(
    "text,value",
    [
        ("INTERVAL '10' SECOND", 10_000),
        ("INTERVAL '2' MINUTE", 120_000),
        ("INTERVAL '250' MILLISECOND", 250),
        ("INTERVAL '3 seconds'", 3_000),
    ],
)
def test_an_interval_in_a_scalar_expression_is_its_length_in_ms(text, value):
    sel = P.parse(f"SELECT date_time - {text} AS t FROM bid")
    expr = sel.items[0].expr
    assert expr == P.BinaryOp("-", P.Ident("date_time"), P.Literal(value))
    # and through the planner: timestamp lanes count milliseconds
    planned = StreamPlanner(_bid_catalog()).plan(
        f"CREATE MATERIALIZED VIEW v AS SELECT auction, date_time + {text} "
        "AS later FROM bid"
    )
    chunk = StreamChunk.from_numpy(
        {"auction": np.array([1]), "bidder": np.array([1]),
         "price": np.array([1]), "channel": np.array([0], np.int32),
         "date_time": np.array([T]), "extra": np.array([0], np.int32)}, 4,
    )
    planned.pipeline.push(chunk)
    planned.pipeline.barrier()
    assert planned.mview.to_numpy()["later"].tolist() == [T + value]


@pytest.mark.parametrize(
    "sql,out_end",
    [
        # q5's hop count: names window_start alone
        ("SELECT auction, window_start, count(*) AS num FROM HOP(bid, "
         "date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND) "
         "GROUP BY auction, window_start", None),
        ("SELECT auction, window_end, count(*) AS num FROM HOP(bid, "
         "date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND) "
         "GROUP BY auction, window_end", "window_end"),
        ("SELECT auction, window_start FROM TUMBLE(bid, date_time, "
         "INTERVAL '10' SECOND) WHERE window_end > date_time", "window_end"),
    ],
)
def test_window_end_is_computed_only_where_the_query_names_it(sql, out_end):
    planned = StreamPlanner(_bid_catalog()).plan(
        "CREATE MATERIALIZED VIEW v AS " + sql
    )
    (hop,) = [e for e in planned.pipeline.executors
              if isinstance(e, HopWindowExecutor)]
    assert hop.out_end == out_end
    adds = hop.lint_info()["adds"]
    assert set(adds) == {"window_start"} | ({out_end} if out_end else set())


# sha256 of ``_hop_step.lower(...).as_text()`` over a chunk of 8,192
# bids for q5's HOP (2 s / 10 s) and for a ten-second TUMBLE, taken on
# the commit before the hop could hand on ``window_end`` (058d3f5)
HOP_SHA256 = {
    (10_000, 2_000):
        "220f3d964fc3a366df01b58df0af0cfede658ae9ac8dbc8ac7563c780b7f9b55",
    (10_000, 10_000):
        "ab84d51377364a2fdac7b2a175e5d00629c9aa77657bc801487723efdb1470ce",
}
BID_LANES = {
    "auction": jnp.int64, "bidder": jnp.int64, "price": jnp.int64,
    "channel": jnp.int32, "date_time": jnp.int64, "extra": jnp.int32,
}


def _lower_hop(size, slide, *more):
    lanes = 8192
    chunk = StreamChunk(
        {n: jax.ShapeDtypeStruct((lanes,), d) for n, d in BID_LANES.items()},
        jax.ShapeDtypeStruct((lanes,), jnp.bool_), {},
        jax.ShapeDtypeStruct((lanes,), jnp.int32),
    )
    return _hop_step.lower(
        chunk, "date_time", size, slide, "window_start", *more
    ).as_text()


@pytest.mark.parametrize("size,slide", sorted(HOP_SHA256))
def test_a_hop_that_names_no_window_end_lowers_to_the_program_it_was(
    size, slide
):
    text = _lower_hop(size, slide)
    assert text.startswith("module @jit_hop_step_fn ")
    assert hashlib.sha256(text.encode()).hexdigest() == HOP_SHA256[size, slide]
    # as the executor calls it (out_end None), the same program
    assert _lower_hop(size, slide, None) == text
    assert _lower_hop(size, slide, "window_end") != text


# What the planner made of the benchmark's own q5, q4 and q9 texts on the
# commit before this one (058d3f5): the executors of each part, the
# join with its keys and residual, what a chained side stores and
# whether it retracts, the aggregates' keys and calls
BETWEEN_Q4 = (
    "Between(inner=Col(name='b__date_time'), lo=Col(name='a__date_time'), "
    "hi=Col(name='expires'))"
)
PLANS = {
    "nexmark_q5": {
        "head": ["HopWindowExecutor", "HashAggExecutor"],
        "left": ["ProjectExecutor"],
        "right": ["ProjectExecutor", "HashAggExecutor"],
        "tail": ["ProjectExecutor", "MaterializeExecutor"],
        "join": ["KeyedJoinExecutor", ["starttime"], ["starttime_c"],
                 "BinOp(op='>=', left=Col(name='num'), "
                 "right=Col(name='maxn'))"],
        "inputs": {"bid": "both"},
        "aggs": [
            [["window_start", "auction"],
             [("count_star", None, "num", False)]],
            [["starttime_c"], [("max", "num", "maxn", True)]],
        ],
    },
    "nexmark_q4": {
        "head": [],
        "left": ["RowIdGenExecutor"] + ["ProjectExecutor"] * 3,
        "right": ["RowIdGenExecutor"] + ["ProjectExecutor"] * 3,
        "tail": ["HashAggExecutor", "HashAggExecutor", "ProjectExecutor",
                 "MaterializeExecutor"],
        "join": ["StreamJoinExecutor", ["id"], ["auction"], BETWEEN_Q4],
        "retract": {"left": False, "right": False},
        "stored": [["a__date_time", "category", "expires", "id"],
                   ["auction", "b__date_time", "price"]],
        "inputs": {"auction": "left", "bid": "right"},
        "aggs": [
            [["id", "category"], [("max", "price", "final", False)]],
            [["category"], [("sum", "final", "total", False),
                            ("count", "final", "n", False)]],
        ],
    },
    "nexmark_q9": {
        "head": [],
        "left": ["RowIdGenExecutor"] + ["ProjectExecutor"] * 2,
        "right": ["RowIdGenExecutor"] + ["ProjectExecutor"] * 2,
        "tail": ["ProjectExecutor", "RetractableGroupTopNExecutor",
                 "ProjectExecutor", "MaterializeExecutor"],
        "join": ["StreamJoinExecutor", ["id"], ["auction"], BETWEEN_Q4],
        "retract": {"left": False, "right": False},
        "stored": [
            ["_l_row_id", "a__date_time", "category", "description",
             "expires", "extra", "id", "initial_bid", "item_name",
             "reserve", "seller"],
            ["_r_row_id", "auction", "b__date_time", "bidder", "price"],
        ],
        "inputs": {"auction": "left", "bid": "right"},
        "aggs": [],
    },
}


def _describe(config):
    with open(os.path.join(
        ROOT, "benchmarks", "configs", config + ".json"
    )) as f:
        cfg = json.load(f)
    session = SqlSession(Catalog({}), StreamingRuntime(MemObjectStore()))
    for ddl in cfg["ddl"]:
        session.execute(ddl)
    planned = StreamPlanner(
        Catalog(dict(session.catalog.tables)), capacity=1 << 12
    ).plan(cfg["mv_sql"][-1])
    pipe, join = planned.pipeline, planned.pipeline.join
    out = {
        part: [type(e).__name__ for e in getattr(pipe, part)]
        for part in ("head", "left", "right", "tail")
    }
    out["join"] = [
        type(join).__name__, list(join.left_keys), list(join.right_keys),
        repr(join.condition),
    ]
    if isinstance(join, StreamJoinExecutor):
        out["retract"] = join._retract
        out["stored"] = [list(join.left_names), list(join.right_names)]
    out["inputs"] = planned.inputs
    out["aggs"] = [
        [list(e.group_keys),
         [(c.kind, c.input, c.output, c.materialized) for c in e.calls]]
        for e in pipe.executors if isinstance(e, HashAggExecutor)
    ]
    return out


@pytest.mark.parametrize("config", sorted(PLANS))
def test_the_other_join_configurations_plan_as_they_did(config):
    """The rules q7 plans by are the ones q5, q4 and q9 plan by: their
    texts give the plans they gave before."""
    assert _describe(config) == PLANS[config]


def test_a_where_over_both_sides_of_an_inner_join_joins_the_on():
    """sigma over an inner join: a conjunct of the WHERE that reads both
    sides is a conjunct of the ON (an equality a key, the rest the
    residual); one side's conjunct is pushed to its side as before;
    an outer join's WHERE stays a filter above it."""
    from risingwave_tpu.sql.optimizer import optimize_select

    def opt(sql):
        return optimize_select(P.parse(sql), catalog=None)

    sel = opt(
        "SELECT a.k FROM (SELECT k, v FROM t) AS a JOIN (SELECT k AS bk, "
        "v AS w FROM u) AS b ON a.k = b.bk WHERE a.v >= b.w AND a.v > 3"
    )
    assert sel.where is None
    assert sel.from_.on == P.BinaryOp(
        "and",
        P.BinaryOp("=", P.Ident("k", "a"), P.Ident("bk", "b")),
        P.BinaryOp(">=", P.Ident("v", "a"), P.Ident("w", "b")),
    )
    assert sel.from_.left.select.where is not None  # a.v > 3 went down
    left = opt(
        "SELECT a.k FROM (SELECT k, v FROM t) AS a LEFT JOIN (SELECT k AS "
        "bk, v AS w FROM u) AS b ON a.k = b.bk WHERE a.v >= b.w + 1"
    )
    assert left.where is not None


# -- the served view against the plain reference -----------------------------


@pytest.mark.parametrize(
    "mode,seed,chunk",
    [
        ("graph", 7, 256),
        ("graph", 2147483999, 128),
        ("serial", 7, 256),
    ],
)
def test_q7_served_equals_the_reference_across_barriers_and_recovery(
    tmp_path, mode, seed, chunk
):
    # 60 events a second of event time: 12 chunks span five windows
    # and more, two chunks an epoch
    bids = _generated(seed, 12 * chunk, 60 * chunk // 256)
    events = _events(bids)
    served = Served(tmp_path, chunk, mode)
    try:
        pos, retracted = 0, 0
        for epoch in range(6):
            TRACER.clear()
            for _ in range(2):
                served.push(bids, pos, pos + chunk)
                pos += chunk
            served.rt.barrier()
            retracted += sum(
                sp.args["retract_pairs"] for sp in _spans("join.epoch")
            )
            if mode == "graph":
                # the aggregate's flush says how many of the epoch's
                # rows fell on its largest group: here, window
                # (the epoch after the restore walks the table: its
                # step kept no list, and there is no such count)
                ends = bids["date_time"][pos - 2 * chunk:pos] // 10_000
                said = [sp.args["group_rows_max"] for sp in _spans("agg.flush")
                        if "group_rows_max" in sp.args]
                assert said == (
                    [] if epoch == 4
                    else [np.bincount(ends - ends.min()).max()]
                )
            want = REF.mv(events, pos)
            if epoch == 3:
                # kill: drop the device state, rebuild it from the store
                assert retracted > 0 or mode == "serial"
                served.rt.wait_checkpoints()
                served.rt.recover()
                ex = served.join()
                assert int(ex.left.n_rows) == pos
                # the updating side's dead lanes came back dead
                assert int(jnp.sum(ex.right.row_valid)) < int(ex.right.n_rows)
            assert served.read() == want, f"epoch {epoch}"
        windows = {r[-1] for r in want}
        assert len(windows) >= 4
        out, _ = served.session.execute(
            "SELECT count(*), sum(price), max(date_time) FROM q7"
        )
        got = tuple(int(np.asarray(v)[0]) for v in out.values())
        assert [got] == REF.probe(events, [pos])
    finally:
        served.close()


def test_ties_the_windows_edge_and_a_price_held_by_many_rows(tmp_path):
    """Written out by hand, a barrier after every step: a maximum that
    rises takes the old winners out and puts the new in; a bid that
    equals a maximum it did not set joins too; a bid stamped exactly on
    a window's end stands under that window and under the next; twenty
    rows of one price are twenty rows of the view, and leave together."""
    served = Served(tmp_path, 32)
    pushed = []

    def step(rows):
        bids = _written(rows)
        served.push(bids, 0, len(rows))
        served.rt.barrier()
        pushed.extend(rows)
        got = served.read()
        assert got == REF.mv(_events(_written(pushed)), len(pushed))
        return got

    try:
        # window [0, 10 s): 500 wins
        got = step([(1, 300, 1_000), (2, 500, 2_000), (3, 400, 3_000)])
        assert {(r[0], r[-1] - T) for r in got} == {(2, 10_000)}
        # a tie on the maximum it did not set: both stand
        got = step([(4, 500, 4_000)])
        assert {r[0] for r in got} == {2, 4}
        # the maximum rises: both leave, the new winner enters, in the
        # barrier that saw the rise
        TRACER.clear()
        got = step([(5, 700, 5_000)])
        assert {r[0] for r in got} == {5}
        (sp,) = _spans("join.epoch")
        assert sp.args["retract_pairs"] == 2 and sp.args["dead_lanes"] == 1
        # a bid stamped exactly on the window's end, at its maximum:
        # it belongs to [10 s, 20 s) and sets that window's maximum,
        # and the band of [0, 10 s) holds it too
        got = step([(6, 700, 10_000)])
        assert {(r[0], r[-1] - T) for r in got} == {
            (5, 10_000), (6, 10_000), (6, 20_000),
        }
        # twenty bids of one price in [10 s, 20 s), above 700: twenty
        # rows (a chain of more than 16), auction 6 leaves that window
        # and stays under the first
        got = step([(100 + i, 900, 11_000 + i) for i in range(20)])
        assert len(got) == 22
        assert {(r[0], r[-1] - T) for r in got} >= {(5, 10_000), (6, 10_000)}
        # a higher bid: all twenty leave at once
        TRACER.clear()
        got = step([(7, 950, 12_000)])
        assert {(r[0], r[-1] - T) for r in got} == {
            (5, 10_000), (6, 10_000), (7, 20_000),
        }
        (sp,) = _spans("join.epoch")
        assert sp.args["retract_pairs"] == 20
        # a late bid into the first window, above its maximum: the edge
        # bid leaves that window too
        got = step([(8, 800, 9_999)])
        assert {(r[0], r[-1] - T) for r in got} == {(8, 10_000), (7, 20_000)}
        # a third and a fourth window open on their own maxima
        got = step([(9, 10, 25_000), (10, 20, 39_999), (11, 20, 30_000)])
        # (auction 11, stamped on 30 s, is in the third window's band
        # and not at its maximum)
        assert {(r[0], r[-1] - T) for r in got} == {
            (8, 10_000), (7, 20_000), (9, 30_000), (10, 40_000),
            (11, 40_000),
        }
    finally:
        served.close()


def test_kill_and_restore_after_a_retraction(tmp_path):
    """The chained side that has retracted lanes and the MAX it is fed
    by come back as they were: the view after recover() is the view
    before the kill, and the stream goes on from there exactly."""
    served = Served(tmp_path, 32)
    pushed = []

    def step(rows):
        served.push(_written(rows), 0, len(rows))
        served.rt.barrier()
        pushed.extend(rows)
        return served.read()

    try:
        step([(1, 300, 1_000), (2, 500, 2_000)])
        step([(3, 600, 3_000), (4, 100, 12_000)])  # 500 -> 600: a retraction
        before = step([(5, 650, 4_000), (6, 650, 4_500)])  # and another, a tie
        assert before == REF.mv(_events(_written(pushed)), len(pushed))
        ex = served.join()
        dead = int(ex.right.n_rows) - int(jnp.sum(ex.right.row_valid))
        assert dead == 2
        served.rt.wait_checkpoints()
        served.rt.recover()
        assert served.read() == before
        ex = served.join()
        assert int(ex.right.n_rows) - int(jnp.sum(ex.right.row_valid)) == dead
        assert int(jnp.sum(ex.right.row_valid)) == 2  # two windows' maxima
        # the restored MAX still knows 650: a lower bid changes nothing,
        # a higher one retracts both winners
        assert step([(7, 640, 5_000)]) == before
        got = step([(8, 660, 6_000), (9, 100, 13_000)])
        assert got == REF.mv(_events(_written(pushed)), len(pushed))
        assert {r[0] for r in got} == {8, 4, 9}
    finally:
        served.close()


def test_the_joins_span_and_counters_say_what_it_retracted(tmp_path):
    served = Served(tmp_path, 32)
    try:
        def counter(name):
            return REGISTRY.counter(name).get(join=served.join().table_id)

        rows0 = counter("join_retract_rows_total")
        pairs0 = counter("join_retract_pairs_total")
        served.push(_written([(1, 300, 1_000), (2, 300, 1_500)]), 0, 2)
        served.rt.barrier()
        TRACER.clear()
        served.push(_written([(3, 400, 2_000)]), 0, 1)
        served.rt.barrier()
        (sp,) = _spans("join.epoch")
        # the MAX's change: U- (300) and U+ (400) on the updating side;
        # the two pairs of 300 retracted; one lane left dead
        assert sp.args["retract_rows"] == 2
        assert sp.args["retract_pairs"] == 2
        assert sp.args["dead_lanes"] == 1
        assert counter("join_retract_rows_total") - rows0 == 3  # + the insert
        assert counter("join_retract_pairs_total") - pairs0 == 2
        # the aggregate's flush: the epoch's one row fell on one group
        flushes = [sp for sp in _spans("agg.flush")
                   if "group_rows_max" in sp.args]
        assert [sp.args["group_rows_max"] for sp in flushes] == [1]
    finally:
        served.close()
