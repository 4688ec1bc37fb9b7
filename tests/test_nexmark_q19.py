"""NEXmark q19 "auction TOP-10 price" as the Flink nexmark suite writes
it: each auction's ten highest bids WITH the rank in the select list,
through the served path. The text (a derived table without an alias,
``SELECT *`` twice) plans onto the retractable GroupTopN with the rank
handed on as a column; the served view equals the benchmark's plain
reference row for row, rank included, after every barrier and across a
checkpoint -> recover(); a rank that moves is an update in place under
the row's stream key and exactly the rows whose rank or columns differ
are handed on again; a delta larger than the epoch's lanes goes out in
rounds; and a Top-N that hands on no rank (q18's, q9's) still compiles
the two programs it compiled before."""

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
from risingwave_tpu.executors import top_n_plain
from risingwave_tpu.executors.over_window import GeneralOverWindowExecutor
from risingwave_tpu.executors.row_id_gen import RowIdGenExecutor
from risingwave_tpu.executors.top_n_plain import (
    RetractableGroupTopNExecutor,
    _diff_gather,
    _rank,
    emission_lanes,
)
from risingwave_tpu.frontend import SqlSession
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.sql import Catalog, StreamPlanner
from risingwave_tpu.storage.object_store import LocalFsObjectStore, MemObjectStore
from risingwave_tpu.trace import TRACER
from risingwave_tpu.types import Op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
import nexmark_gen  # noqa: E402  (the benchmark's generator: Beam's bids)


def _load_ref():
    path = os.path.join(ROOT, "benchmarks", "configs", "nexmark_q19_ref.py")
    spec = importlib.util.spec_from_file_location("nexmark_q19_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_ref()  # the plain reference the benchmark's cell is held to

BID_DDL = (
    "CREATE TABLE bid (auction BIGINT, bidder BIGINT, price BIGINT, "
    "channel VARCHAR, date_time TIMESTAMP, extra VARCHAR)"
)
COLUMNS = ("auction", "bidder", "price", "channel", "date_time", "extra")
VIEW = COLUMNS + ("rank_number",)
WINDOW = (
    "ROW_NUMBER() OVER (PARTITION BY auction ORDER BY price DESC) "
    "AS rank_number"
)
# the source's text: nexmark-flink/src/main/resources/queries/q19.sql
Q19 = (
    "CREATE MATERIALIZED VIEW q19 AS SELECT * FROM "
    f"(SELECT *, {WINDOW} FROM bid) WHERE rank_number <= 10"
)


class Served:
    def __init__(self, state_dir, chunk, mode="graph", capacity=1 << 12,
                 sql=Q19):
        self.chunk = chunk
        self.rt = StreamingRuntime(
            LocalFsObjectStore(str(state_dir)), checkpoint_frequency=1
        )
        self.session = SqlSession(
            Catalog({}), self.rt, capacity=capacity, exec_mode=mode
        )
        self.session.execute(BID_DDL)
        self.session.execute(sql)
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

    def push_rows(self, rows):
        """rows: (auction, price) — one bidder, one millisecond, so
        that only the price and the arrival order them."""
        n = len(rows)
        a, p = (np.asarray(c, np.int64) for c in zip(*rows))
        self.push({
            "auction": a, "bidder": np.full(n, 7, np.int64), "price": p,
            "date_time": np.full(n, 5000, np.int64),
            "channel": np.zeros(n, np.int64),
            "extra": np.asarray([f"x{v}" for v in p], object),
        }, 0, n)

    def read(self, cols=VIEW):
        out, _ = self.session.execute(
            "SELECT " + ", ".join(cols) + " FROM q19"
        )
        rows = list(zip(*(np.asarray(out[c]).tolist() for c in cols)))
        assert len(rows) == len(set(rows))
        return set(rows)

    def ranks(self):
        """{(auction, price): rank} of the served view."""
        return {
            (a, p): r for a, p, r in self.read(("auction", "price", "rank_number"))
        }

    def topn(self):
        (ex,) = [
            e for e in self.rt.fragments["q19"].executors
            if isinstance(e, RetractableGroupTopNExecutor)
        ]
        return ex

    def close(self):
        self.session.close()
        for p in self.rt.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()


def _bids(seed, n):
    gen = nexmark_gen.Generator(seed, {"first_event_rate": 20000})
    bids = gen.events(0, n * 50 // 46 + 50, ["bid"])["bid"]
    return {c: v[:n] for c, v in bids.items()}


def _spans(name):
    return [sp for sp in TRACER.spans() if sp.name == name]


# -- the plan ---------------------------------------------------------------


def _bid_catalog():
    session = SqlSession(Catalog({}), StreamingRuntime(MemObjectStore()))
    session.execute(BID_DDL)
    return Catalog({"bid": session.catalog.tables["bid"]})


@pytest.mark.parametrize(
    "sql,columns",
    [
        (Q19, VIEW),  # as the source writes it: no alias, two stars
        (Q19.replace(") WHERE", ") AS B WHERE"), VIEW),
        (
            "CREATE MATERIALIZED VIEW q19 AS SELECT auction, B.price, "
            f"rank_number AS r FROM (SELECT *, {WINDOW} FROM bid) B "
            "WHERE rank_number < 11",
            ("auction", "price", "r"),
        ),
    ],
)
def test_the_sources_text_plans_onto_group_topn_with_the_rank(sql, columns):
    planned = StreamPlanner(_bid_catalog()).plan(sql)
    kinds = [type(ex) for ex in planned.pipeline.executors]
    assert GeneralOverWindowExecutor not in kinds
    assert kinds[0] is RowIdGenExecutor
    (gt,) = [ex for ex in planned.pipeline.executors
             if isinstance(ex, RetractableGroupTopNExecutor)]
    assert gt.group_by == ("auction",) and gt.limit == 10
    assert gt.order == (("price", True),)
    assert gt.rank_col == "rank_number" and gt.erank.dtype == jnp.int32
    # the rank is a column the executor makes, not one it stores
    assert "rank_number" not in gt.names
    assert gt.lint_info()["emits"]["rank_number"] == jnp.dtype(jnp.int64)
    assert gt.trace_contract()["rank_lane"] == "rank_number"
    # the view: the selected columns, the rank under the query's name,
    # keyed by the rows' stream key (a shifted rank is an update in
    # place), which ``SELECT *`` does not show
    assert tuple(planned.schema) == columns + ("_row_id",)
    assert planned.schema[columns[-1]] == jnp.dtype(jnp.int64)
    assert planned.mview.pk == ("_row_id",)


def test_a_rank_named_like_a_column_keeps_the_window_path():
    planned = StreamPlanner(_bid_catalog()).plan(
        "CREATE MATERIALIZED VIEW w AS SELECT auction, price FROM (SELECT "
        "auction, ROW_NUMBER() OVER (PARTITION BY auction ORDER BY price "
        "DESC) AS price FROM bid) B WHERE price <= 10"
    )
    assert any(isinstance(ex, GeneralOverWindowExecutor)
               for ex in planned.pipeline.executors)


def test_explain_shows_group_topn_with_the_rank(tmp_path):
    served = Served(tmp_path, 64)
    try:
        assert list(served.rt.fragments) == ["bid", "q19"]
        out, tag = served.session.execute("EXPLAIN " + Q19)
        text = "\n".join(out["QUERY PLAN"].tolist())
        assert tag == "EXPLAIN"
        assert "RetractableGroupTopN group=[auction]" in text
        assert (
            "order=[price DESC, stream key] limit=10 rank=rank_number" in text
        )
        # q18's text selects no rank, and its plan says none
        out, _ = served.session.execute(
            "EXPLAIN SELECT auction FROM (SELECT *, ROW_NUMBER() OVER "
            "(PARTITION BY bidder, auction ORDER BY date_time DESC) AS "
            "rank_number FROM bid) B WHERE rank_number <= 1"
        )
        text = "\n".join(out["QUERY PLAN"].tolist())
        assert "limit=1 ->" in text and "rank=" not in text
        # the hidden row id does not show through the view's star
        out, _ = served.session.execute("SELECT * FROM q19")
        assert tuple(out) == VIEW
    finally:
        served.close()


# -- the served view against the plain reference -----------------------------


@pytest.mark.parametrize(
    "mode,seed,chunk",
    [
        ("graph", 7, 256),
        ("graph", 2147483999, 512),
        ("serial", 7, 256),
    ],
)
def test_q19_served_equals_the_reference_across_barriers_and_recovery(
    tmp_path, mode, seed, chunk
):
    bids = _bids(seed, 12 * chunk)
    events = {"bid": dict(bids, eid=np.arange(len(bids["price"])))}
    served = Served(tmp_path, chunk, mode)
    try:
        pos, moved = 0, 0
        for epoch in range(6):
            TRACER.clear()
            for _ in range(2):
                served.push(bids, pos, pos + chunk)
                pos += chunk
            served.rt.barrier()
            moved += sum(
                sp.args["rank_moved_rows"] for sp in _spans("topn.diff")
            )
            want = REF.mv(events, pos, nexmark_gen.VOCAB)
            if epoch == 3:
                # kill: drop the device state, rebuild it from the store
                served.rt.wait_checkpoints()
                served.rt.recover()
                ex = served.topn()
                assert ex._epoch_lanes == 0
                # the rank as handed on is derived state: the restore
                # ranked the restored rows
                assert int(jnp.sum(ex.erank > 0)) == len(want)
                assert int(jnp.sum(ex.erank)) == sum(r[-1] for r in want)
            assert served.read() == want, f"epoch {epoch}"
        # the data holds what q19 is about: full auctions, and rows that
        # moved for their rank alone
        ranks = [r[-1] for r in want]
        assert max(ranks) == 10 and ranks.count(1) > 20
        assert moved > 100 or mode == "serial"  # (serial keeps no ring)
        ex = served.topn()
        assert int(jnp.sum(ex.emitted)) == len(want)
        assert bool(jnp.all((ex.erank > 0) == ex.emitted))
        # the probe the benchmark asks: its last term moves with a rank
        out, _ = served.session.execute(
            "SELECT count(*), max(date_time), sum(price), sum(rank_number) "
            "FROM q19"
        )
        got = tuple(int(np.asarray(v)[0]) for v in out.values())
        assert [got] == REF.probe(events, [pos], nexmark_gen.VOCAB)
    finally:
        served.close()


def test_an_executor_that_hands_on_no_moved_rank_fails_the_reference(
    tmp_path, monkeypatch
):
    """What the comparison is for: with the diff blind to the rank (every
    row taken to stand at the rank it has now) the rows of the view are
    right and their ranks are stale, and the reference says so."""
    real = top_n_plain._diff_gather

    def blind(table, rows, shadow, emitted, ranked, *rest, **numbered):
        now = jnp.arange(
            ranked.packed.shape[0], dtype=jnp.int32
        ) - ranked.seg_start + 1
        ranked = ranked._replace(erank=jnp.where(ranked.in_topk, now, 0))
        return real(table, rows, shadow, emitted, ranked, *rest, **numbered)

    monkeypatch.setattr(top_n_plain, "_diff_gather", blind)
    bids = _bids(7, 1024)
    events = {"bid": dict(bids, eid=np.arange(1024))}
    served = Served(tmp_path, 256)
    try:
        for pos in range(0, 1024, 256):
            served.push(bids, pos, pos + 256)
            served.rt.barrier()
        want = REF.mv(events, 1024, nexmark_gen.VOCAB)
        got = served.read()
        assert {r[:-1] for r in got} == {r[:-1] for r in want}
        assert got != want
    finally:
        served.close()


# -- which rows a barrier hands on --------------------------------------------


def _full_auction(served, auction=1, low=110):
    """Ten bids at low, low + 10, ...: ranks 10 down to 1."""
    served.push_rows([(auction, low + 10 * i) for i in range(10)])
    served.rt.barrier()
    assert served.ranks() == {
        (auction, low + 10 * i): 10 - i for i in range(10)
    }


def test_a_bid_entering_at_rank_one_moves_nine_and_pushes_out_the_tenth(
    tmp_path,
):
    served = Served(tmp_path, 16)
    try:
        _full_auction(served)
        moved = REGISTRY.counter("group_topn_rank_moved_rows_total")
        tid = served.topn().table_id
        before = moved.get(table_id=tid)
        TRACER.clear()
        served.push_rows([(1, 500)])
        served.rt.barrier()
        (rank,), (pull,), (diff,) = (
            _spans("topn.rank"), _spans("topn.pull"), _spans("topn.diff")
        )
        assert rank.args["limit"] == 10 and rank.args["rank_emitted"] is True
        # nine rows move down one (a U- and a U+ each, under their own
        # stream keys), the tenth leaves, the new one enters: nothing else
        assert diff.args["retract_rows"] == 9 + 1
        assert diff.args["insert_rows"] == 9 + 1
        assert diff.args["rank_moved_rows"] == 9
        assert pull.args["rank_moved_rows"] == 9 and pull.args["rounds"] == 1
        assert diff.args["rounds"] == 1 and diff.args["groups"] == 1
        assert moved.get(table_id=tid) - before == 9
        # the view applied them chunk by chunk, and says how many rows
        applied = [sp.args["rows"] for sp in _spans("mv.apply")
                   if sp.args["table_id"] == "q19.mview"]
        assert applied == [10, 10]
        want = {(1, 500): 1}
        want.update({(1, 120 + 10 * i): 10 - i for i in range(9)})
        assert served.ranks() == want
        # a bid under the tenth moves nobody
        TRACER.clear()
        served.push_rows([(1, 100)])
        served.rt.barrier()
        (diff,) = _spans("topn.diff")
        assert diff.args["retract_rows"] == diff.args["insert_rows"] == 0
        assert served.ranks() == want
    finally:
        served.close()


def test_a_bid_that_enters_and_leaves_inside_one_epoch_reaches_nobody(
    tmp_path,
):
    served = Served(tmp_path, 16)
    try:
        _full_auction(served)
        TRACER.clear()
        # 115 would take rank 10 from 110; 116, in the same epoch, takes
        # it from 115: the view hears of 116 and 110 alone
        served.push_rows([(1, 115), (1, 116)])
        served.rt.barrier()
        (diff,) = _spans("topn.diff")
        assert diff.args["retract_rows"] == 1
        assert diff.args["insert_rows"] == 1
        assert diff.args["rank_moved_rows"] == 0
        ranks = served.ranks()
        assert ranks[(1, 116)] == 10
        assert (1, 115) not in ranks and (1, 110) not in ranks
    finally:
        served.close()


def test_ties_rank_by_arrival(tmp_path):
    """Bids of one price: the earlier arrival first, whichever chunk or
    epoch the later comes in (the stream key of a table without a key is
    the row id RowIdGen gives in arrival order)."""
    served = Served(tmp_path, 8)
    try:
        def push(rows):
            n = len(rows)
            a, b, p = (np.asarray(c, np.int64) for c in zip(*rows))
            served.push({
                "auction": a, "bidder": b, "price": p,
                "date_time": np.full(n, 5000, np.int64),
                "channel": np.zeros(n, np.int64),
                "extra": np.asarray([f"x{v}" for v in b], object),
            }, 0, n)

        def ranks():
            return {b: r for _, b, r in served.read(
                ("auction", "bidder", "rank_number"))}

        push([(1, 71, 100), (1, 72, 100), (1, 73, 90)])
        served.rt.barrier()
        assert ranks() == {71: 1, 72: 2, 73: 3}
        push([(1, 74, 100)])  # the same price, an epoch later: after both
        served.rt.barrier()
        assert ranks() == {71: 1, 72: 2, 74: 3, 73: 4}
        push([(1, 75, 101), (1, 76, 100)])
        served.rt.barrier()
        assert ranks() == {75: 1, 71: 2, 72: 3, 74: 4, 76: 5, 73: 6}
    finally:
        served.close()


def test_a_delta_larger_than_the_epochs_lanes_goes_out_in_rounds(
    tmp_path, monkeypatch
):
    """One chunk of 64 bids, each the new maximum of a full auction:
    640 rows each way where the epoch's chunks held 64 lanes. Nothing
    raises, no row is lost, and no chunk is wider than the declared
    size (the floor is lowered so that a store of 4,096 lanes can show
    it; the lattice is the same x4 ladder)."""
    monkeypatch.setattr(top_n_plain, "_EMIT_FLOOR", 16)
    served = Served(tmp_path, 64)
    try:
        ex = served.topn()
        assert ex.emission_sizes() == (16, 4096)
        for lo in range(0, 640, 64):  # ten bids an auction, 64 auctions
            served.push_rows([(i % 64, 100 + i) for i in range(lo, lo + 64)])
        served.rt.barrier()
        assert len(served.read()) == 640
        TRACER.clear()
        served.push_rows([(a, 5000 + a) for a in range(64)])
        served.rt.barrier()
        (rank,), (diff,) = _spans("topn.rank"), _spans("topn.diff")
        assert rank.args["lanes"] == 64
        assert diff.args["retract_rows"] == diff.args["insert_rows"] == 640
        assert diff.args["rank_moved_rows"] == 64 * 9
        assert diff.args["rounds"] == 10
        # twenty full rounds' chunks, none of which a smaller size holds
        assert diff.args["emit_lanes"] == 20 * 64
        applied = [sp.args["rows"] for sp in _spans("mv.apply")
                   if sp.args["table_id"] == "q19.mview"]
        assert applied == [64] * 20  # every round's U- before any U+
        ranks = served.ranks()
        assert len(ranks) == 640
        for a in range(64):
            assert ranks[(a, 5000 + a)] == 1
            assert (a, 100 + a) not in ranks  # the tenth, pushed out
            assert ranks[(a, 100 + a + 64)] == 10
        assert int(jnp.sum(ex.erank)) == 64 * 55
    finally:
        served.close()


def test_the_pull_says_the_lanes_the_gathers_covered(tmp_path, monkeypatch):
    """``topn.pull``'s ``gather_lanes`` is turns x the loops' block over
    both chunks and every round (the first round's from the device's
    status, a further round's reckoned from the counts), and the
    counter rises by it. A block of 16 lanes under chunks of 64: 30 rows
    each way take two turns a chunk, 640 take ten rounds of four."""
    monkeypatch.setattr(top_n_plain, "_EMIT_FLOOR", 16)
    monkeypatch.setattr(top_n_plain, "_GATHER_LANES", 16)
    _diff_gather.clear_cache()
    served = Served(tmp_path, 64)
    try:
        for lo in range(0, 640, 64):  # ten bids an auction, 64 auctions
            served.push_rows([(i % 64, 100 + i) for i in range(lo, lo + 64)])
        served.rt.barrier()
        covered = REGISTRY.counter("group_topn_gathered_lanes_total")
        tid = served.topn().table_id
        for auctions, rounds, lanes in ((3, 1, 2 * 2 * 16), (64, 10, 10 * 2 * 64)):
            before = covered.get(table_id=tid)
            TRACER.clear()
            served.push_rows([(a, 5000 + auctions + a) for a in range(auctions)])
            served.rt.barrier()
            (pull,) = _spans("topn.pull")
            assert pull.args["rows"] == 2 * 10 * auctions
            assert pull.args["rounds"] == rounds
            # (the first epoch's candidates answer; the second's 64
            # auctions bring ten rows each, more than the lanes hold)
            assert pull.args["full_rank"] == (auctions == 64)
            assert pull.args["touched_passes"] > 0
            assert (pull.args["passes"] > 0) == (auctions == 64)
            assert pull.args["gather_lanes"] == lanes
            assert covered.get(table_id=tid) - before == lanes
        assert len(served.ranks()) == 640
    finally:
        served.close()
        _diff_gather.clear_cache()


@pytest.mark.parametrize("chunk_lanes", [2048, 32768])
def test_rounds_at_the_declared_floor_lose_no_row(chunk_lanes):
    """The executor alone at the sizes it declares: 1,700 groups take a
    new maximum each, a chunk an epoch, until every group is full and
    then once more. A chunk of 2,048 lanes: 17,000 rows each way
    against gathers of 16,384, so the last two barriers take two
    rounds. A chunk of 32,768 lanes (four of the cells' pushes): the
    gathers run at 65,536 lanes, one round, and a delta goes on in
    16,384 lanes until it holds more rows than that."""
    n_groups = 1700
    ex = RetractableGroupTopNExecutor(
        ("g",), (("v", True),), 10, ("id",),
        {"g": jnp.int64, "id": jnp.int64, "v": jnp.int64},
        capacity=1 << 16, table_id="rounds.gtopn", rank_col="rn",
    )
    lanes = emission_lanes(chunk_lanes, 1 << 16)
    assert lanes == (16384 if chunk_lanes == 2048 else 65536)
    view = {}  # id -> rank, as a view keyed by the stream key holds it
    for batch in range(11):
        ids = np.arange(batch * n_groups, (batch + 1) * n_groups)
        ex.apply(StreamChunk.from_numpy(
            {"g": np.arange(n_groups, dtype=np.int64), "id": ids,
             "v": np.full(n_groups, 100 + batch, np.int64)}, chunk_lanes,
        ))
        chunks = ex.on_barrier(None)
        ops = []
        for c in chunks:
            # the smallest declared size that holds the chunk's rows,
            # never a capacity-wide chunk for a delta that needs none
            assert c.capacity == (
                16384 if int(c.valid.sum()) <= 16384 else lanes
            )
            d = c.to_numpy(with_ops=True)
            ops += d["__op__"].tolist()
            for op, i, r in zip(d["__op__"].tolist(), d["id"].tolist(),
                                d["rn"].tolist()):
                if op == int(Op.DELETE):
                    # at the rank it was handed on with
                    assert view.pop(i) == r
                else:
                    assert i not in view
                    view[i] = r
        # every round's retractions before any round's insertions
        assert ops == sorted(ops, key=lambda op: op != int(Op.DELETE))
        n_ret = min(batch, 10) * n_groups
        assert ops.count(int(Op.DELETE)) == n_ret
        assert len(chunks) == -(-n_ret // lanes) + -(
            -len(ids) * min(batch + 1, 10) // lanes
        )
        # batch b's bid is its group's highest so far
        want = {
            int(i): batch - b + 1
            for b in range(max(0, batch - 9), batch + 1)
            for i in range(b * n_groups, (b + 1) * n_groups)
        }
        assert view == want
    # two rounds, 16,384 + 616 rows each way, or one of 17,000
    assert [c.capacity for c in chunks] == (
        [16384] * 4 if lanes == 16384 else [65536] * 2
    )
    assert int(jnp.sum(ex.erank)) == 55 * n_groups
    assert int(jnp.sum(ex.emitted)) == 10 * n_groups


def test_delete_promotes_the_eleventh_and_renumbers(tmp_path):
    """A table with a PRIMARY KEY: the view is keyed by it, and DELETE /
    UPDATE through DML retract, promote and renumber exactly."""
    rt = StreamingRuntime(
        LocalFsObjectStore(str(tmp_path)), checkpoint_frequency=1
    )
    s = SqlSession(Catalog({}), rt, capacity=1 << 8, exec_mode="graph")
    try:
        s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, g BIGINT, v BIGINT)")
        s.execute(
            "CREATE MATERIALIZED VIEW top3 AS SELECT * FROM (SELECT *, "
            "ROW_NUMBER() OVER (PARTITION BY g ORDER BY v DESC) AS rn FROM t) "
            "WHERE rn <= 3"
        )
        assert s.catalog.mvs["top3"].mview.pk == ("id",)
        assert tuple(s.catalog.mvs["top3"].schema) == ("id", "g", "v", "rn")

        def view():
            out, _ = s.execute("SELECT id, v, rn FROM top3")
            return set(zip(*(np.asarray(out[c]).tolist()
                             for c in ("id", "v", "rn"))))

        s.execute("INSERT INTO t VALUES (1, 0, 10), (2, 0, 20), (3, 0, 30), "
                  "(4, 0, 40), (5, 1, 5)")
        rt.barrier()
        assert view() == {(4, 40, 1), (3, 30, 2), (2, 20, 3), (5, 5, 1)}
        TRACER.clear()
        s.execute("DELETE FROM t WHERE id = 3")  # promotes id 1, renumbers 2
        rt.barrier()
        assert view() == {(4, 40, 1), (2, 20, 2), (1, 10, 3), (5, 5, 1)}
        (diff,) = _spans("topn.diff")
        assert (diff.args["retract_rows"], diff.args["insert_rows"],
                diff.args["rank_moved_rows"]) == (2, 2, 1)
        s.execute("UPDATE t SET v = 50 WHERE id = 1")  # to the top, in place
        rt.barrier()
        assert view() == {(1, 50, 1), (4, 40, 2), (2, 20, 3), (5, 5, 1)}
        s.execute("UPDATE t SET v = 45 WHERE id = 1")  # other values, same rank
        rt.barrier()
        assert view() == {(1, 45, 1), (4, 40, 2), (2, 20, 3), (5, 5, 1)}
        s.execute("DELETE FROM t WHERE id = 5")  # the group's last row
        rt.barrier()
        assert view() == {(1, 45, 1), (4, 40, 2), (2, 20, 3)}
    finally:
        s.close()
        for p in rt.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()


def test_the_rank_over_a_join_is_handed_on_too(tmp_path):
    """q9's shape with the rank selected: the rule over a join."""
    rt = StreamingRuntime(MemObjectStore())
    s = SqlSession(Catalog({}), rt, capacity=1 << 8, exec_mode="graph")
    try:
        s.execute("CREATE TABLE auction (id BIGINT, seller BIGINT, "
                  "expires BIGINT)")
        s.execute("CREATE TABLE bid (auction BIGINT, bidder BIGINT, "
                  "price BIGINT, date_time BIGINT)")
        s.execute(
            "CREATE MATERIALIZED VIEW j AS SELECT id, price, bidder, rn FROM "
            "(SELECT A.id, B.price, B.bidder, ROW_NUMBER() OVER (PARTITION BY "
            "A.id ORDER BY B.price DESC, B.date_time ASC) AS rn FROM auction "
            "A, bid B WHERE A.id = B.auction AND B.date_time <= A.expires) "
            "WHERE rn <= 2"
        )
        kinds = [type(e) for e in s.catalog.mvs["j"].pipeline.executors]
        assert RetractableGroupTopNExecutor in kinds
        assert GeneralOverWindowExecutor not in kinds

        def view():
            out, _ = s.execute("SELECT id, price, rn FROM j")
            return set(zip(*(np.asarray(out[c]).tolist()
                             for c in ("id", "price", "rn"))))

        s.execute("INSERT INTO auction VALUES (1, 10, 100), (2, 20, 100)")
        s.execute("INSERT INTO bid VALUES (1, 7, 50, 1), (1, 8, 70, 2), "
                  "(1, 9, 60, 3), (2, 7, 5, 1), (1, 5, 500, 101)")
        assert view() == {(1, 70, 1), (1, 60, 2), (2, 5, 1)}
        s.execute("INSERT INTO bid VALUES (1, 11, 80, 4)")
        assert view() == {(1, 80, 1), (1, 70, 2), (2, 5, 1)}
    finally:
        s.close()
        for p in rt.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()


# -- a Top-N that hands on no rank runs the programs it ran -------------------

# sha256 of ``_rank.lower(...).as_text()`` and of
# ``_diff_gather.lower(...).as_text()`` for the Top-N the planner makes
# of each configuration's text, at 2^22 lanes and chunks of 2^16: the
# rank's stood from the commit before the rank could be a column
# (7f7d2b4) until the ranking became a named tuple that a rank over an
# epoch's candidates shares (PR 46: the results' names and the place of
# the slots' iota are what differs in the text, no operation; taken anew
# there), the diff's from where its gathers went into blocks (PR 42)
# until it said ``full_rank`` in a tenth count (PR 46, taken anew)
PINNED = {
    "nexmark_q18": (
        "3c78c8f708df032a5f90537bb1d6509cdfe2962752957a39c85c8b55b745d067",
        "0e699b9f3b7b6acd12abd19217c3fd9c98b2b7a7a8302bd0124ac0d6479afd76",
    ),
    "nexmark_q9": (
        "ef9384bc6e70c5b3556c05d3073a748314d0205e76236182370d80c1f11729cf",
        "4f112dd061b64d5c9799eb74415bcea9311338ecdc2c083fa6f6765a53cf1821",
    ),
}


def _planned_topn(config_name):
    path = os.path.join(ROOT, "benchmarks", "configs", config_name + ".json")
    with open(path) as f:
        config = json.load(f)
    session = SqlSession(Catalog({}), StreamingRuntime(MemObjectStore()))
    for sql in config["ddl"]:
        session.execute(sql)
    planner = StreamPlanner(Catalog(dict(session.catalog.tables)), capacity=256)
    (gt,) = [
        ex for ex in planner.plan(config["mv_sql"][0]).pipeline.executors
        if isinstance(ex, RetractableGroupTopNExecutor)
    ]
    return gt


@pytest.mark.parametrize("config_name", sorted(PINNED))
def test_a_topn_without_the_rank_lowers_to_the_programs_it_ran_before(
    config_name,
):
    ex = _planned_topn(config_name)
    assert ex.rank_col is None and ex.erank is None

    def big(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                tuple(1 << 22 if d == 256 else d for d in a.shape), a.dtype
            ),
            tree,
        )

    args = (big(ex.table), big(ex.rows), big(ex.shadow), big(ex.emitted),
            big(ex.epoch_dirty))
    static = dict(k=ex.limit, desc=ex.desc, n_group=len(ex.group_by),
                  order_col=ex.order_col)
    rank = _rank.lower(*args, **static).as_text()
    ranked = jax.eval_shape(lambda *a: _rank(*a, **static), *args)
    diff = _diff_gather.lower(
        *args[:4], ranked, jax.ShapeDtypeStruct((), jnp.bool_),
        out_lanes=1 << 16,
    ).as_text()
    assert rank.startswith("module @jit__rank ")
    assert diff.startswith("module @jit__diff_gather ")
    assert (
        hashlib.sha256(rank.encode()).hexdigest(),
        hashlib.sha256(diff.encode()).hexdigest(),
    ) == PINNED[config_name]


def test_the_numbered_topn_runs_the_same_two_programs_by_name():
    """q19's Top-N runs ``_rank`` with the rank as handed on riding
    along the sort (one operand more) and ``_diff_gather`` with the
    numbered body: two modules of the names the device trace's readers
    know, and no third."""
    ex = _planned_topn("nexmark_q19")
    assert ex.rank_col == "rank_number"
    static = dict(k=10, desc=ex.desc, n_group=1, order_col=ex.order_col)
    args = (ex.table, ex.rows, ex.shadow, ex.emitted, ex.epoch_dirty)
    text = _rank.lower(*args, erank=ex.erank, **static).as_text()
    assert text.startswith("module @jit__rank ")
    ranked = jax.eval_shape(
        lambda *a: _rank(*a, erank=ex.erank, **static), *args
    )
    # ... and the rank as handed on, sorted
    assert ranked.erank.shape == ranked.packed.shape
    assert ranked.group is None and ranked.full_rank is None
    diff = _diff_gather.lower(
        *args[:4], ranked, jax.ShapeDtypeStruct((), jnp.bool_),
        out_lanes=64, erank=ex.erank,
        start=jax.ShapeDtypeStruct((), jnp.int32), rank_col="rank_number",
    ).as_text()
    assert diff.startswith("module @jit__diff_gather ")
    # (auction, the row id, price: two digits each, liveness) + the
    # slot + the rank as handed on
    assert ex._sort_operands == 7 + 1 + 1
    assert ex._row_bytes == 5 * 8 + 2 * 4 + 4
