"""NEXmark q6 "average selling price by seller", the source's own text on
the served path.

Upstream RisingWave's q6_group_top1 (Beam's Query6: for each seller the
average closing price of their last auctions): a ROWS-framed AVG over
each seller's kept rows, the kept row of an auction being what
``ROW_NUMBER() OVER (PARTITION BY A.id, A.seller ORDER BY B.price) <= 1``
leaves of the auction's in-lifetime bids. Planned as upstream plans it —
StreamOverWindow over StreamGroupTopN over StreamHashJoin — in ONE
two-input actor: the chained join, the retractable GroupTopN at k = 1,
and the GENERAL over-window executor reading the Top-N's barrier delta
(an auction's lower bid in a later epoch is a U-/U+ into the window).
Held to the benchmark's plain reference
(benchmarks/configs/nexmark_q6_ref.py) after every barrier.
"""

import collections
import importlib.util
import json
import os
import sys

import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.over_window import (
    GROW_AT,
    GeneralOverWindowExecutor,
    WindowCall,
    emission_sizes,
    step_widths,
)
from risingwave_tpu.executors.project import ProjectExecutor
from risingwave_tpu.executors.stream_join import StreamJoinExecutor
from risingwave_tpu.executors.top_n_plain import RetractableGroupTopNExecutor
from risingwave_tpu.frontend.session import SqlSession
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.sql import Catalog, StreamPlanner
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


REF = _load("configs", "nexmark_q6_ref.py")
with open(os.path.join(ROOT, "benchmarks", "configs", "nexmark_q6.json")) as f:
    CONFIG = json.load(f)
AUCTION_DDL, BID_DDL = CONFIG["ddl"]
(Q6,) = CONFIG["mv_sql"]
T0 = 1_436_918_400_000


class Served6:
    """A session serving q6 over the two tables; chunks pushed as the
    benchmark's harness pushes them (the DML route's targets)."""

    def __init__(self, state_dir, chunk, mode="graph", capacity=1 << 12):
        self.chunk = chunk
        self.rt = StreamingRuntime(
            LocalFsObjectStore(str(state_dir)), checkpoint_frequency=1
        )
        self.session = SqlSession(
            Catalog({}), self.rt, capacity=capacity, exec_mode=mode
        )
        for sql in (AUCTION_DDL, BID_DDL, Q6):
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

    def push(self, stream, cols, lo=0, hi=None):
        """Rows lo..hi of ``cols`` in chunks of the session's size."""
        hi = len(cols["eid"]) if hi is None else hi
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
        """The view as the reference counts it, {(seller, total, n,
        rows)}; every row's avg is held to total / n on the way."""
        out, _ = self.session.execute("SELECT seller, avg, total, n FROM q6")
        cols = [np.asarray(out[c]).tolist() for c in ("seller", "total", "n")]
        for avg, total, n in zip(np.asarray(out["avg"]).tolist(), *cols[1:]):
            assert 1 <= n <= 11 and avg == total / n
        rows = collections.Counter(zip(*cols))
        return {key + (count,) for key, count in rows.items()}

    def read_grouped(self):
        """The benchmark's own read (``mv_read.sql``)."""
        out, _ = self.session.execute(CONFIG["mv_read"]["sql"])
        return set(zip(*(np.asarray(v).tolist() for v in out.values())))

    def executor(self, kind):
        (ex,) = [
            e for e in self.rt.fragments["q6"].executors
            if isinstance(e, kind)
        ]
        return ex

    def close(self):
        self.session.close()
        for p in self.rt.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()


def _rows(stream, rows):
    """Hand-made events: auctions (eid, id, seller, date_time ms after
    T0, expires) or bids (eid, auction, price, ms after T0)."""
    width = 5 if stream == "auction" else 4
    cols = [np.asarray(c, np.int64) for c in zip(*rows)] or [
        np.zeros(0, np.int64)
    ] * width
    n = len(rows)
    text = np.asarray(["x"] * n, object)
    zero = np.zeros(n, np.int64)
    if stream == "auction":
        eid, ident, seller, ts, exp = cols
        return {
            "eid": eid, "id": ident, "item_name": zero, "description": text,
            "initial_bid": zero, "reserve": zero, "date_time": T0 + ts,
            "expires": T0 + exp, "seller": seller, "category": zero,
            "extra": text,
        }
    eid, auction, price, ts = cols
    return {
        "eid": eid, "auction": auction, "bidder": eid + 100, "price": price,
        "channel": zero, "date_time": T0 + ts, "extra": text,
    }


def _catalog(*ddl):
    session = SqlSession(Catalog({}), StreamingRuntime(MemObjectStore()))
    for sql in ddl:
        session.execute(sql)
    cat = Catalog(dict(session.catalog.tables))
    cat.table_pks = dict(session.catalog.table_pks)
    return cat


# -- the plan -----------------------------------------------------------------


def test_the_sources_text_plans_as_join_then_topn_then_general_over_window():
    planned = StreamPlanner(_catalog(AUCTION_DDL, BID_DDL)).plan(Q6)
    pipe = planned.pipeline
    join = pipe.join
    assert type(join) is StreamJoinExecutor and join.layout == "chain"
    assert (join.left_keys, join.right_keys) == (("id",), ("auction",))
    assert join.condition is not None  # the BETWEEN, inside the join
    # three narrow columns and the residual's, each side's row id beside
    assert join.left_names == (
        "_l_row_id", "a__date_time", "expires", "id", "seller",
    )
    assert join.right_names == (
        "_r_row_id", "auction", "b__date_time", "price",
    )
    assert join._retract == {"left": False, "right": False}
    kinds = [type(ex) for ex in pipe.tail]
    assert kinds == [
        ProjectExecutor, RetractableGroupTopNExecutor, ProjectExecutor,
        ProjectExecutor, GeneralOverWindowExecutor, ProjectExecutor,
        type(planned.mview),
    ]
    gt, over = pipe.tail[1], pipe.tail[4]
    assert gt.group_by == ("_w_id", "seller") and gt.limit == 1
    assert gt.order == (("final", False),)  # B.price ASCENDING, as written
    assert gt.pk == over.pk == ("_l_row_id", "_r_row_id")
    assert gt.rank_col is None and gt.upstream == "StreamJoinExecutor"
    assert over.part_keys == ("seller",) and over.order_col == "date_time"
    # AVG is made of the SUM and the COUNT the select lists: two calls
    assert over.calls == (
        WindowCall("sum", "final", "total", frame=(-10, 0)),
        WindowCall("count", "final", "n", frame=(-10, 0)),
    )
    assert planned.aux == () and planned.inputs == {
        "auction": "left", "bid": "right",
    }
    assert [c for c in planned.schema if not c.startswith("_")] == [
        "seller", "avg", "total", "n",
    ]
    assert str(planned.schema["avg"]) == "float64"
    assert tuple(planned.mview.pk) == ("_l_row_id", "_r_row_id")
    assert planned.append_only is False


def test_explain_shows_the_join_the_topn_and_the_window_behind_it(tmp_path):
    served = Served6(tmp_path, 64)
    try:
        out, tag = served.session.execute("EXPLAIN " + Q6)
        text = "\n".join(out["QUERY PLAN"].tolist())
        assert tag == "EXPLAIN"
        assert "rank <= 1: per-group top-n under the select" in text
        assert "StreamJoin layout=chain type=inner keys=[id = auction]" in text
        assert (
            "RetractableGroupTopN group=[_w_id, seller] order=[final, "
            "stream key] limit=1 -> "
        ) in text
        assert (
            "GeneralOverWindow partition=[seller] order=[date_time, stream "
            "key] calls=[sum(final) ROWS -10..0 AS total, count(final) ROWS "
            "-10..0 AS n] pk=[_l_row_id, _r_row_id] -> Project -> "
            "Materialize"
        ) in text
    finally:
        served.close()


@pytest.mark.parametrize("config", ["nexmark_q9", "nexmark_q18", "nexmark_q19"])
def test_the_topn_rule_keeps_the_plans_it_made(config):
    """A bounded ROW_NUMBER() whose outer select lists columns is the
    Top-N's own view, as before: no over-window executor, no second
    projection behind it."""
    with open(os.path.join(ROOT, "benchmarks", "configs", config + ".json")) as f:
        cfg = json.load(f)
    planner = StreamPlanner(_catalog(*cfg["ddl"]))
    execs = planner.plan(cfg["mv_sql"][0]).pipeline.executors
    kinds = [type(ex) for ex in execs]
    assert GeneralOverWindowExecutor not in kinds
    at = kinds.index(RetractableGroupTopNExecutor)
    assert kinds[at + 1:] == [ProjectExecutor, kinds[-1]]


def test_avg_sum_and_count_over_a_frame_on_a_plain_table():
    s = SqlSession(Catalog({}), capacity=1 << 10)
    s.execute("CREATE TABLE t (k BIGINT, o BIGINT, v BIGINT)")
    w = "(PARTITION BY k ORDER BY o ROWS BETWEEN 2 PRECEDING AND CURRENT ROW)"
    s.execute(
        f"CREATE MATERIALIZED VIEW w AS SELECT k, o, AVG(v) OVER {w} AS a, "
        f"COUNT(v) OVER {w} AS c, SUM(v) OVER {w} AS s, COUNT(*) OVER {w} "
        "AS rows FROM t"
    )
    s.execute(
        "INSERT INTO t VALUES (1, 1, 10), (1, 2, NULL), (1, 3, 30), "
        "(1, 4, 50), (2, 1, NULL), (2, 2, 7)"
    )
    out, _ = s.execute("SELECT k, o, a, c, s, rows FROM w")
    got = sorted(
        zip(*(np.asarray(out[c]).tolist() for c in ("k", "o", "c", "rows")))
    )
    assert got == [
        (1, 1, 1, 1), (1, 2, 1, 2), (1, 3, 2, 3), (1, 4, 2, 3),
        (2, 1, 0, 1), (2, 2, 1, 2),
    ]
    rows = {
        (k, o): (a, total)
        for k, o, a, total in zip(
            *(np.asarray(out[c]).tolist() for c in ("k", "o", "a", "s"))
        )
    }
    null = (2, 1)  # a frame with no non-NULL input: SUM and AVG are NULL
    assert {k: v for k, v in rows.items() if k != null} == {
        (1, 1): (10.0, 10), (1, 2): (10.0, 10), (1, 3): (20.0, 40),
        (1, 4): (40.0, 80), (2, 2): (7.0, 7),
    }
    assert rows[null] == (None, None)
    # AVG alone makes its own two hidden calls, and nothing else shows
    s.execute(f"CREATE MATERIALIZED VIEW w1 AS SELECT k, AVG(v) OVER {w} AS a FROM t")
    out, _ = s.execute("SELECT * FROM w1")
    assert [c for c in out if not c.startswith("_") and "__" not in c] == ["k", "a"]
    (over,) = [
        ex for ex in StreamPlanner(Catalog(dict(s.catalog.tables))).plan(
            f"CREATE MATERIALIZED VIEW w2 AS SELECT k, AVG(v) OVER {w} AS a, "
            f"SUM(v) OVER {w} AS s FROM t"
        ).pipeline.executors
        if isinstance(ex, GeneralOverWindowExecutor)
    ]
    # the listed SUM is the AVG's; its COUNT is hidden
    assert [(c.kind, c.output) for c in over.calls] == [
        ("count", "__w1"), ("sum", "s"),
    ]


# -- the served view against the plain reference -------------------------------


def _events(seed, ordinals):
    gen = nexmark_gen.Generator(seed, {"first_event_rate": 20000})
    return gen.events(0, ordinals, ["auction", "bid"])


@pytest.mark.parametrize(
    "mode,seed,floor",
    [("graph", 1, None), ("graph", 2147483999, None), ("serial", 1, None),
     # emission sizes from 1,024 lanes up: the Top-N's gathers run at
     # 16,384 (the join hands it 8,192-lane chunks) and each delta, some
     # 30 retractions and 270 insertions, goes on in 1,024
     ("graph", 1, 1024)],
)
def test_q6_served_equals_the_reference_across_barriers_and_recovery(
    tmp_path, monkeypatch, mode, seed, floor
):
    if floor is not None:
        from risingwave_tpu.executors import over_window, top_n_plain

        monkeypatch.setattr(top_n_plain, "_EMIT_FLOOR", floor)
        monkeypatch.setattr(over_window, "_EMIT_FLOOR", floor)
    events = _events(seed, 24_000)
    served = Served6(tmp_path, 512, mode, capacity=1 << 12)
    try:
        assert list(served.rt.fragments) == ["auction", "bid", "q6"]
        done, seen = 0, set()
        for epoch, cut in enumerate(range(4_000, 24_001, 4_000)):
            TRACER.clear()
            served.push_until(events, done, cut)
            done = cut
            served.rt.barrier()
            if floor is not None:
                # the chunks follow the delta, and the step's width them
                (rank,), (diff,), (step,) = (
                    [sp.args for sp in TRACER.spans() if sp.name == name]
                    for name in ("topn.rank", "topn.diff", "over.step")
                )
                deltas = [diff["retract_rows"], diff["insert_rows"]]
                assert 0 < max(deltas) <= floor < rank["lanes"]
                assert diff["emit_lanes"] == floor * sum(n > 0 for n in deltas)
                assert step["chunk_lanes"] == 2 * floor
                assert step["in_rows"] == sum(deltas)
            if epoch == 2:
                # kill: drop the device state, rebuild it from the store
                over = served.executor(GeneralOverWindowExecutor)
                before = over.state_digest()
                served.rt.wait_checkpoints()
                served.rt.recover()
                over = served.executor(GeneralOverWindowExecutor)
                assert over.state_digest() == before  # bit-exact
            rows = served.read()
            assert rows == REF.mv(events, cut, nexmark_gen.VOCAB)
            assert served.read_grouped() == rows
            probe, _ = served.session.execute(CONFIG["probe"]["sql"])
            assert tuple(int(v[0]) for v in probe.values()) == REF.probe(
                events, [cut]
            )[0]
            seen |= rows
        # kept bids were undercut between barriers, frames slid
        assert len(seen) > len(rows) > 300
        assert max(r[2] for r in rows) == 11
        # the arena holds one row an auction that has a bid
        over = served.executor(GeneralOverWindowExecutor)
        assert int(over.present.sum()) == sum(r[3] for r in rows)
    finally:
        served.close()


CASES = {
    # a lower bid in a later epoch than the auction's first: U-/U+ into
    # the window, and the re-kept row (its date_time is the new bid's)
    # moves past its seller's neighbours
    "a_later_lower_bid_moves_the_row": (
        [(0, 1000, 7, 0, 10_000), (1, 1001, 7, 0, 10_000),
         (2, 1002, 7, 0, 10_000)],
        [[(3, 1000, 500, 100), (4, 1001, 300, 200), (5, 1002, 900, 300)],
         [(6, 1000, 100, 400)],
         [(7, 1001, 299, 50)]],
    ),
    # a seller with more than eleven auctions: the frame slides
    "the_frame_slides": (
        [(i, 1000 + i, 9, 0, 100_000) for i in range(14)],
        [[(14 + i, 1000 + i, 10 * (i + 1), 1_000 + i) for i in range(7)],
         [(21 + i, 1007 + i, 10 * (i + 8), 1_007 + i) for i in range(7)],
         # the oldest row's bid undercut: every frame over it changes
         [(28, 1000, 1, 2_000)]],
    ),
    # bids before their auction opens and after it expires (the
    # residual), and on both bounds
    "early_and_late_bids": (
        [(0, 1000, 7, 1_000, 11_000), (1, 1001, 7, 1_000, 11_000)],
        [[(2, 1000, 5, 999), (3, 1000, 6, 11_001), (4, 1001, 80, 5_000)],
         [(5, 1000, 50, 1_000), (6, 1000, 40, 11_000)],
         [(7, 1001, 1, 11_001)]],
    ),
    # equal date_times in one partition order by the stream key; ties
    # on the price keep the earlier arrival
    "equal_date_times_and_prices": (
        [(0, 1000, 7, 0, 10_000), (1, 1001, 7, 0, 10_000),
         (2, 1002, 7, 0, 10_000)],
        [[(3, 1001, 70, 500), (4, 1000, 30, 500), (5, 1002, 20, 500)],
         [(6, 1000, 30, 100)],  # ties on the price: nothing moves
         [(7, 1002, 19, 500), (8, 1001, 19, 500)]],
    ),
    # bids that wait for their auction, which comes in a later epoch
    "bids_before_their_auction": (
        [(9, 1000, 7, 0, 10_000)],
        [[(0, 1000, 500, 100), (1, 1000, 400, 200)], [], [(10, 1000, 450, 300)]],
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_hand_made_epochs_equal_the_reference_at_every_barrier(tmp_path, case):
    auctions, epochs = CASES[case]
    served = Served6(tmp_path, 32, capacity=1 << 8)
    try:
        late = [a for a in auctions if a[0] > min(b[0] for e in epochs for b in e)]
        early = [a for a in auctions if a not in late]
        pushed = {"auction": [], "bid": []}
        seen = []
        for i, bids in enumerate(epochs):
            for stream, rows in (
                ("auction", early if i == 0 else late if i == 1 else []),
                ("bid", bids),
            ):
                if rows:
                    served.push(stream, _rows(stream, rows))
                    pushed[stream] += rows
            served.rt.barrier()
            events = {s: _rows(s, sorted(r)) for s, r in pushed.items()}
            cut = 1 + max(r[0] for rows in pushed.values() for r in rows)
            want = REF.mv(events, cut)
            assert served.read() == want
            seen.append(want)
        assert seen[-1] != seen[0] or case == "bids_before_their_auction"
        assert seen[-1]
    finally:
        served.close()


def test_a_later_lower_bid_is_a_retraction_into_the_window(tmp_path):
    """The spans and counters say what the window took and handed on."""
    auctions, epochs = CASES["a_later_lower_bid_moves_the_row"]
    served = Served6(tmp_path, 32, capacity=1 << 8)
    try:
        served.push("auction", _rows("auction", auctions))
        served.push("bid", _rows("bid", epochs[0]))
        served.rt.barrier()
        assert served.read() == {(7, 500, 1, 1), (7, 800, 2, 1), (7, 1700, 3, 1)}
        over = served.executor(GeneralOverWindowExecutor)
        counters = {
            name: REGISTRY.counter(name)
            for name in (
                "over_window_input_rows_total",
                "over_window_emitted_rows_total", "over_window_steps_total",
                "over_window_buffered_chunks_total",
            )
        }

        def count(name):
            return sum(
                v for k, v in counters[name]._values.items()
                if dict(k).get("table_id") == over.table_id
            )

        before = {name: count(name) for name in counters}
        TRACER.clear()
        served.push("bid", _rows("bid", epochs[1]))
        served.rt.barrier()
        # 1000's row left the front of the seller's order for its end
        assert served.read() == {(7, 300, 1, 1), (7, 1200, 2, 1), (7, 1300, 3, 1)}
        spans = TRACER.spans()
        (barrier,) = [sp for sp in spans if sp.name == "over.barrier"]
        steps = [sp for sp in spans if sp.name == "over.step"]
        # the Top-N's delta, a retract chunk and an insert chunk, in ONE
        # step at the barrier
        assert barrier.args["steps"] == len(steps) == 1
        assert barrier.args["chunks"] == 2
        assert barrier.args["table_id"] == over.table_id
        assert barrier.args["in_rows"] == 2  # the U- and the U+
        assert barrier.args["dirty_partitions"] == 1
        # the row leaves the front of the seller's order for its end: its
        # own frame and both others' change, and each is handed on once
        assert barrier.args["retract_rows"] == barrier.args["insert_rows"] == 3
        assert barrier.args["emitted_rows"] == 6
        assert barrier.args["emit_lanes"] == 2 * emission_sizes(over.capacity)[0]
        for sp in steps:
            assert sp.args["capacity"] == over.capacity
            # the two chunks laid end to end, in a declared width
            assert sp.args["chunk_lanes"] == step_widths(over.capacity)[0]
            assert sp.args["in_rows"] == 2 and sp.args["retract_rows"] == 3
            assert sp.args["calls"] == 2 and sp.args["frame_rows"] == 11
            assert sp.args["row_bytes"] == over.row_bytes == 5 * 8 + 1
            assert sp.epoch == barrier.epoch
        after = {name: count(name) for name in counters}
        assert after["over_window_steps_total"] - before[
            "over_window_steps_total"] == 1
        assert after["over_window_buffered_chunks_total"] - before[
            "over_window_buffered_chunks_total"] == 2
        assert after["over_window_input_rows_total"] - before[
            "over_window_input_rows_total"] == 2
        assert after["over_window_emitted_rows_total"] - before[
            "over_window_emitted_rows_total"] == 6
        # one blocking read a step, and it is the step's own
        reads = [sp.args["what"] for sp in spans if sp.name == "device.read"]
        assert reads.count("over.status") == 1
    finally:
        served.close()


def test_the_arena_grows_outside_a_barrier_and_the_view_stands(tmp_path):
    """More sellers' rows than half the arena: the executor rehashes
    between two chunks, nothing is lost and no frame changes."""
    n = 200
    auctions = [(i, 1000 + i, 7 + i % 5, 0, 100_000) for i in range(n)]
    bids = [(n + i, 1000 + i, 10 + i, 1_000 + i) for i in range(n)]
    served = Served6(tmp_path, 32, capacity=1 << 8)
    try:
        over = served.executor(GeneralOverWindowExecutor)
        assert n > over.capacity * GROW_AT
        served.push("auction", _rows("auction", auctions))
        for lo in range(0, n, 50):
            served.push("bid", _rows("bid", bids[lo:lo + 50]))
            served.rt.barrier()
            events = {
                "auction": _rows("auction", auctions),
                "bid": _rows("bid", bids[:lo + 50]),
            }
            assert served.read() == REF.mv(events, n + lo + 50)
        assert over.capacity > 1 << 8  # grew
        assert int(over.present.sum()) == n
    finally:
        served.close()


def test_the_warm_up_leaves_no_mark_and_compiles_every_emission_size():
    import jax.numpy as jnp

    over = GeneralOverWindowExecutor(
        partition_by=("p",), order_col="o", pk=("id",),
        calls=(WindowCall("sum", "x", "s", frame=(-1, 0)),),
        schema_dtypes={"id": jnp.int64, "p": jnp.int64, "o": jnp.int64,
                       "x": jnp.int64},
        capacity=1 << 9, nullable=("x",),
    )
    cols = {"id": [1, 2], "p": [5, 5], "o": [1, 2], "x": [10, 20]}
    chunk = StreamChunk.from_numpy(
        {k: np.asarray(v, np.int64) for k, v in cols.items()}, 64
    )
    assert over.apply(chunk) == []  # kept for the barrier's step
    outs = over.on_barrier(None)
    assert [int(c.valid.sum()) for c in outs] == [0, 2]
    digest, bound = over.state_digest(), over._bound
    empty = StreamChunk.from_numpy(
        {k: np.zeros(0, np.int64) for k in cols}, 64
    )
    warmed = over.warm(empty)
    sizes = emission_sizes(over.capacity)
    assert [c.capacity for c in warmed] == list(sizes) * 2
    assert not any(int(c.valid.sum()) for c in warmed)
    assert over.state_digest() == digest and over._bound == bound
    # and keeps nothing: the next barrier has no step to run
    assert over._held == [] and over.on_barrier(None) == []
    # a delta past the largest size goes in rounds of it
    assert emission_sizes(1 << 22) == (1 << 14, 1 << 16)
    assert over.trace_contract()["emission_caps"] == sizes == (1 << 9,)


def test_a_delta_past_the_largest_size_goes_in_rounds(monkeypatch):
    import jax.numpy as jnp

    from risingwave_tpu.executors import over_window

    monkeypatch.setattr(over_window, "_EMIT_FLOOR", 64)
    monkeypatch.setattr(over_window, "_EMIT_MAX", 64)
    over = GeneralOverWindowExecutor(
        partition_by=("p",), order_col="o", pk=("id",),
        calls=(WindowCall("count", "x", "c", frame=(-1, 0)),),
        schema_dtypes={"id": jnp.int64, "p": jnp.int64, "o": jnp.int64,
                       "x": jnp.int64},
        capacity=1 << 10, nullable=("x",),
    )
    assert emission_sizes(over.capacity) == (64,)
    assert step_widths(over.capacity) == (128,)
    ids = np.arange(120, dtype=np.int64)
    cols = {"id": ids, "p": ids % 3, "o": ids, "x": ids}
    assert over.apply(StreamChunk.from_numpy(cols, 128)) == []
    outs = over.on_barrier(None)
    # two rounds of 64 lanes: every retraction before any insertion
    assert [c.capacity for c in outs] == [64] * 4
    assert [int(c.valid.sum()) for c in outs] == [0] * 2 + [64, 56]
    got = np.concatenate([c.to_numpy()["id"] for c in outs[2:]])
    assert sorted(got.tolist()) == ids.tolist()
    # one row's order moves to the front of its partition: it and the
    # row it now stands before change (the row behind its old place
    # still counts two), in one round
    moved = {"id": [114], "p": [0], "o": [-1], "x": [114]}
    assert over.apply(StreamChunk.from_numpy(
        {k: np.asarray(v, np.int64) for k, v in moved.items()}, 128
    )) == []
    outs = over.on_barrier(None)
    assert [c.capacity for c in outs] == [64, 64]
    ret, ins = (c.to_numpy() for c in outs)
    assert sorted(ret["id"].tolist()) == sorted(ins["id"].tolist()) == [0, 114]
    assert dict(zip(ins["id"].tolist(), ins["c"].tolist())) == {114: 1, 0: 2}


# -- the Top-N's chunks follow its delta, and the step's width with them ------

_CUT_FLOOR = 64  # the smallest emission size, for the two tests below


def _cut_chain(monkeypatch, cut=True):
    """A k = 1 Top-N by auction into the general over-window by seller,
    as q6 plans them, at sizes a CPU test can hold: emission sizes from
    64 lanes up (x4), the Top-N declaring 64 / 256 / 1,024 (epochs of
    one, two and eight chunks of 128). ``cut`` False: the Top-N as it
    was, every chunk at the size its gathers ran at."""
    import jax.numpy as jnp

    from risingwave_tpu.executors import over_window, top_n_plain

    monkeypatch.setattr(top_n_plain, "_EMIT_FLOOR", _CUT_FLOOR)
    monkeypatch.setattr(over_window, "_EMIT_FLOOR", _CUT_FLOOR)
    monkeypatch.setattr(
        RetractableGroupTopNExecutor, "_WARM_EPOCHS", (1, 256, 1024)
    )
    i64 = jnp.int64
    dtypes = {"a": i64, "id": i64, "price": i64, "seller": i64, "ts": i64}
    topn = RetractableGroupTopNExecutor(
        ("a",), "price", 1, ("id",), dtypes, capacity=1 << 12,
        table_id="cut.topn",
    )
    over = GeneralOverWindowExecutor(
        partition_by=("seller",), order_col="ts", pk=("id",),
        calls=(WindowCall("sum", "price", "total", frame=(-10, 0)),
               WindowCall("count", "price", "n", frame=(-10, 0))),
        schema_dtypes=dtypes, capacity=1 << 12, table_id="cut.over",
    )
    if not cut:
        topn._cut = lambda chunk, rows: chunk
    return topn, over


def _cut_epoch(topn, over, auctions, price):
    """A bid of ``price`` on each of ``auctions`` auctions and a chunk
    that holds no row (256 lanes an epoch: the gathers run at 256), the
    barrier of both executors; what the over-window handed on."""
    ids = np.arange(auctions, dtype=np.int64)
    cols = {"a": ids, "id": 1_000_000 - 1_000 * price + ids,
            "price": np.full(auctions, price, np.int64), "seller": ids % 7,
            "ts": 10 * ids + price}
    for part in (cols, {k: v[:0] for k, v in cols.items()}):
        assert topn.apply(StreamChunk.from_numpy(part, 128)) == []
    handed = topn.on_barrier(None)
    for c in handed:
        assert over.apply(c) == []
    return handed, over.on_barrier(None)


def _sorted_rows(chunks):
    out = []
    for c in chunks:
        d = c.to_numpy(with_ops=True)
        names = sorted(d)
        out.append(sorted(zip(*(d[n].tolist() for n in names))))
    return out


def _staged(ex):
    out = []
    for d in ex.checkpoint_delta():
        cols = {**d.key_cols, **d.value_cols, "tomb": d.tombstone}
        names = sorted(cols)
        out.append((d.table_id, names, sorted(
            zip(*(np.asarray(cols[n]).tolist() for n in names))
        )))
    return out


@pytest.mark.parametrize("auctions", (_CUT_FLOOR, _CUT_FLOOR + 1))
def test_a_delta_that_fits_the_floor_steps_the_window_at_twice_the_floor(
    monkeypatch, auctions
):
    """Each auction's kept bid is undercut in the second epoch: as many
    retractions and as many insertions as auctions. 64 of each go on in
    two chunks of 64 lanes and the window steps over 128; 65 keep the
    256 lanes the gathers ran at and the window steps over 512, as
    every delta did. Either way the window hands on what it handed on
    behind the uncut Top-N, the checkpoint stages the same rows of both
    executors, and — the sizes compiled when the chain was warmed — no
    barrier of either kind compiles a program."""
    from risingwave_tpu.array.chunk import _leading_lanes
    from risingwave_tpu.executors.over_window import (
        _general_over_commit, _general_over_emit, _general_over_lay,
        _general_over_step,
    )
    from risingwave_tpu.executors.top_n_plain import _diff_gather, _rank

    topn, over = _cut_chain(monkeypatch)
    ref_topn, ref_over = _cut_chain(monkeypatch, cut=False)
    assert topn.emission_sizes() == (64, 256, 1024)
    assert step_widths(over.capacity) == (128, 512, 2048, 8192)
    for ex, window in ((topn, over), (ref_topn, ref_over)):
        for c in ex.warm_emissions():
            window.warm(c)
    programs = (_leading_lanes, _rank, _diff_gather, _general_over_lay,
                _general_over_step, _general_over_emit, _general_over_commit)
    compiled = [f._cache_size() for f in programs]
    small = auctions <= _CUT_FLOOR
    for price in (900, 500):
        TRACER.clear()
        handed, got = _cut_epoch(topn, over, auctions, price)
        (step,) = [sp for sp in TRACER.spans() if sp.name == "over.step"]
        (diff,) = [sp for sp in TRACER.spans() if sp.name == "topn.diff"]
        ref_handed, want = _cut_epoch(ref_topn, ref_over, auctions, price)
        assert {c.capacity for c in ref_handed} == {256}
        assert {c.capacity for c in handed} == {64 if small else 256}
        assert diff.args["emit_lanes"] == sum(c.capacity for c in handed)
        assert step.args["in_rows"] == (auctions if price == 900 else 2 * auctions)
        assert _sorted_rows(handed) == _sorted_rows(ref_handed)
        assert _sorted_rows(got) == _sorted_rows(want)
        assert [c.capacity for c in got] == [c.capacity for c in want]
        assert _staged(over) == _staged(ref_over)
        assert _staged(topn) == _staged(ref_topn)
    # the undercut epoch: a retract chunk and an insert chunk
    assert len(handed) == 2 and diff.args["retract_rows"] == auctions
    assert step.args["chunk_lanes"] == (
        2 * _CUT_FLOOR if small else 2 * 4 * _CUT_FLOOR
    )
    assert [f._cache_size() for f in programs] == compiled
