"""NEXmark q18 "find last bid" as the Flink nexmark suite writes it (less
``url``, which Beam's bid has not): the keep-last-row Top-N over
(bidder, auction), through the served path. The text plans onto the
retractable GroupTopN, the served view equals the benchmark's plain
reference after every barrier — in serial and in graph mode, with bids
of one pair in one millisecond in the data, at k = 1 and k = 10, across
a checkpoint -> recover() — a retracting input still retracts and
promotes exactly, and one barrier leaves the Top-N's spans and counters
agreeing with the chunks it handed on."""

import importlib.util
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.over_window import GeneralOverWindowExecutor
from risingwave_tpu.executors import top_n_plain
from risingwave_tpu.executors.row_id_gen import RowIdGenExecutor
from risingwave_tpu.executors.top_n_plain import (
    RetractableGroupTopNExecutor,
    candidate_lanes,
    emission_lanes,
)
from risingwave_tpu.frontend import SqlSession
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.sql import Catalog, StreamPlanner
from risingwave_tpu.storage.object_store import LocalFsObjectStore
from risingwave_tpu.trace import TRACER
from risingwave_tpu.types import Op

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
import nexmark_gen  # noqa: E402  (the benchmark's generator: Beam's bids)


def _load_ref():
    path = os.path.join(ROOT, "benchmarks", "configs", "nexmark_q18_ref.py")
    spec = importlib.util.spec_from_file_location("nexmark_q18_ref", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _load_ref()  # the plain reference the benchmark's cells are held to

BID_DDL = (
    "CREATE TABLE bid (auction BIGINT, bidder BIGINT, price BIGINT, "
    "channel VARCHAR, date_time TIMESTAMP, extra VARCHAR)"
)
COLUMNS = ("auction", "bidder", "price", "channel", "date_time", "extra")


def q18(k=1, bound="<="):
    return (
        "CREATE MATERIALIZED VIEW q18 AS "
        "SELECT auction, bidder, price, channel, B.date_time, B.extra "
        "FROM (SELECT *, ROW_NUMBER() OVER (PARTITION BY bidder, auction "
        "ORDER BY date_time DESC) AS rank_number FROM bid) B "
        f"WHERE rank_number {bound} {k}"
    )


def last_k(bids, n, k):
    """The reference's rule at any k: per (bidder, auction) the k rows
    of greatest date_time, earlier arrivals first among equals."""
    order = np.lexsort((
        np.arange(n), -bids["date_time"][:n], bids["auction"][:n],
        bids["bidder"][:n],
    ))
    b, a = bids["bidder"][order], bids["auction"][order]
    first = np.ones(n, bool)
    first[1:] = (b[1:] != b[:-1]) | (a[1:] != a[:-1])
    start = np.maximum.accumulate(np.where(first, np.arange(n), 0))
    at = order[np.arange(n) - start < k]
    channels = nexmark_gen.VOCAB[("bid", "channel")]
    return {
        (int(bids["auction"][i]), int(bids["bidder"][i]),
         int(bids["price"][i]), channels[int(bids["channel"][i])],
         int(bids["date_time"][i]), str(bids["extra"][i]))
        for i in at
    }


class Served:
    def __init__(self, state_dir, chunk, mode, k=1, capacity=1 << 12):
        self.chunk = chunk
        self.rt = StreamingRuntime(
            LocalFsObjectStore(str(state_dir)), checkpoint_frequency=1
        )
        self.session = SqlSession(
            Catalog({}), self.rt, capacity=capacity, exec_mode=mode
        )
        self.session.execute(BID_DDL)
        self.session.execute(q18(k))
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
            "SELECT " + ", ".join(COLUMNS) + " FROM q18"
        )
        rows = list(zip(*(np.asarray(out[c]).tolist() for c in COLUMNS)))
        assert len(rows) == len(set(rows))
        return set(rows)

    def topn(self):
        (ex,) = [
            e for e in self.rt.fragments["q18"].executors
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
    # 20,000 events/s: a millisecond holds 18 bids, three in eight of
    # them from the hot bidder to the hot auction, so one pair has
    # several bids of one date_time in nearly every chunk
    gen = nexmark_gen.Generator(seed, {"first_event_rate": 20000})
    bids = gen.events(0, n * 50 // 46 + 50, ["bid"])["bid"]
    return {c: v[:n] for c, v in bids.items()}


def _ties_within_a_pair(bids):
    key = np.stack([bids["bidder"], bids["auction"], bids["date_time"]])
    return bids["date_time"].size - np.unique(key, axis=1).shape[1]


# -- the plan ---------------------------------------------------------------


def _bid_catalog():
    """The catalog a session makes of the bid table's DDL."""
    from risingwave_tpu.storage.object_store import MemObjectStore

    session = SqlSession(Catalog({}), StreamingRuntime(MemObjectStore()))
    session.execute(BID_DDL)
    return Catalog({"bid": session.catalog.tables["bid"]})


def test_the_sources_text_plans_onto_group_topn_not_the_window_path():
    # the planner alone, no session's type pass before it: the rule
    # itself expands the star beside the window call
    for sql in (q18(), q18(10), q18(2, "<")):
        planned = StreamPlanner(_bid_catalog()).plan(sql)
        kinds = [type(ex) for ex in planned.pipeline.executors]
        assert RetractableGroupTopNExecutor in kinds
        assert GeneralOverWindowExecutor not in kinds
        assert kinds[0] is RowIdGenExecutor
    (gt,) = [ex for ex in planned.pipeline.executors
             if isinstance(ex, RetractableGroupTopNExecutor)]
    assert gt.group_by == ("bidder", "auction") and gt.limit == 1
    assert gt.order == (("date_time", True),)
    assert gt.order_col == "date_time" and gt.desc
    assert gt.pk == ("_row_id",) and gt.upstream == "RowIdGenExecutor"
    # the star stands for the table's columns and for nothing hidden
    assert list(planned.schema)[:6] == [
        "auction", "bidder", "price", "channel", "date_time", "extra"
    ]


def test_a_shape_the_rule_cannot_take_keeps_the_window_path():
    planner = StreamPlanner(_bid_catalog())
    ranked = (
        "CREATE MATERIALIZED VIEW {name} AS SELECT auction, bidder, "
        "rank_number FROM (SELECT *, {call} OVER (PARTITION BY auction ORDER "
        "BY price DESC) AS rank_number FROM bid) B WHERE rank_number <= 10"
    )
    # rank() numbers ties alike: no Top-N of rows
    tied = planner.plan(ranked.format(name="r19", call="RANK()"))
    kinds = [type(ex) for ex in tied.pipeline.executors]
    assert GeneralOverWindowExecutor in kinds
    assert RetractableGroupTopNExecutor not in kinds
    # the rank in the select list (Flink's q19 at k = 10) no longer
    # takes that detour: the GroupTopN hands the rank on as a column
    # (tests/test_nexmark_q19.py holds that plan to the reference)
    numbered = planner.plan(ranked.format(name="q19", call="ROW_NUMBER()"))
    kinds = [type(ex) for ex in numbered.pipeline.executors]
    assert GeneralOverWindowExecutor not in kinds
    (gt,) = [ex for ex in numbered.pipeline.executors
             if isinstance(ex, RetractableGroupTopNExecutor)]
    assert gt.rank_col == "rank_number" and gt.limit == 10
    assert list(numbered.schema) == [
        "auction", "bidder", "rank_number", "_row_id"
    ]


def test_explain_shows_the_topn_plan(tmp_path):
    served = Served(tmp_path, 64, "graph")
    try:
        out, tag = served.session.execute("EXPLAIN " + q18())
        text = "\n".join(out["QUERY PLAN"].tolist())
        assert tag == "EXPLAIN"
        assert "RetractableGroupTopN group=[bidder, auction]" in text
        assert "order=[date_time DESC, stream key] limit=1" in text
        # and a query it is not planned for says nothing of it
        out, _ = served.session.execute("EXPLAIN SELECT auction FROM bid")
        assert "GroupTopN" not in "\n".join(out["QUERY PLAN"].tolist())
    finally:
        served.close()


# -- the served view against the plain reference -----------------------------


@pytest.mark.parametrize(
    "mode,seed,chunk,k",
    [
        ("graph", 7, 256, 1),
        ("graph", 2147483999, 512, 1),
        ("serial", 7, 256, 1),
        ("graph", 11, 256, 10),
        ("serial", 11, 256, 10),
    ],
)
def test_q18_served_equals_the_reference_across_barriers_and_recovery(
    tmp_path, mode, seed, chunk, k
):
    bids = _bids(seed, 12 * chunk)
    assert _ties_within_a_pair(bids) > 50  # the tie rule is in the data
    events = {"bid": dict(bids, eid=np.arange(len(bids["price"])))}
    served = Served(tmp_path, chunk, mode, k=k)
    try:
        assert list(served.rt.fragments) == ["bid", "q18"]
        pos = 0
        for epoch in range(6):
            for _ in range(2):
                served.push(bids, pos, pos + chunk)
                pos += chunk
            served.rt.barrier()
            if epoch == 3:
                # kill: drop the device state, rebuild it from the store
                served.rt.wait_checkpoints()
                served.rt.recover()
                assert served.topn()._epoch_lanes == 0
            want = last_k(bids, pos, k)
            if k == 1:
                assert want == REF.mv(events, pos, nexmark_gen.VOCAB)
            assert served.read() == want, f"epoch {epoch}"
        assert len(want) > chunk
        # what the rank left standing is what it handed on: every row
        # of the view once, none since retracted
        ex = served.topn()
        assert int(jnp.sum(ex.emitted)) == len(want)
    finally:
        served.close()


def test_the_tie_rule_is_the_earlier_arrival(tmp_path):
    """Two bids of one pair in one millisecond: the first to arrive is
    the pair's row, whichever chunk or epoch the second comes in; a
    later millisecond takes the row over. (A rule that kept the later
    arrival, or the higher slot, fails here.)"""
    served = Served(tmp_path, 8, "graph")
    try:
        def push(rows):
            n = len(rows)
            a, b, p, t = (np.asarray(c, np.int64) for c in zip(*rows))
            served.push({
                "auction": a, "bidder": b, "price": p, "date_time": t,
                "channel": np.zeros(n, np.int64),
                "extra": np.asarray([f"x{v}" for v in p], object),
            }, 0, n)

        push([(1, 7, 100, 5000), (1, 7, 200, 5000), (2, 7, 300, 5000)])
        served.rt.barrier()
        assert {r[:3] for r in served.read()} == {(1, 7, 100), (2, 7, 300)}
        push([(1, 7, 400, 5000)])  # the same millisecond, an epoch later
        served.rt.barrier()
        assert {r[:3] for r in served.read()} == {(1, 7, 100), (2, 7, 300)}
        push([(1, 7, 500, 5001), (1, 7, 600, 5001)])
        served.rt.barrier()
        assert {r[:3] for r in served.read()} == {(1, 7, 500), (2, 7, 300)}
    finally:
        served.close()


@pytest.mark.parametrize("mode", ["graph", "serial"])
def test_row_ids_rise_with_arrival(tmp_path, mode):
    """What the tie rule rests on: the stream key of a table without a
    primary key is a row id that rises with arrival, chunk after chunk
    and epoch after epoch."""
    served = Served(tmp_path, 64, mode)
    try:
        bids = _bids(3, 6 * 64)
        for epoch in range(3):
            for j in range(2):
                lo = (2 * epoch + j) * 64
                served.push(bids, lo, lo + 64)
            served.rt.barrier()
        ex = served.topn()
        live = np.asarray(ex.table.live)
        ids = np.asarray(ex.rows["_row_id"])[live]
        price = np.asarray(ex.rows["price"])[live]
        extra = np.asarray(ex.rows["extra"])[live]
        assert len(ids) == 6 * 64 == len(set(ids.tolist()))
        # the stored rows in id order are the bids in arrival order
        by_id = np.argsort(ids)
        assert price[by_id].tolist() == bids["price"].tolist()
        want = served.session.strings.encode(bids["extra"])
        assert extra[by_id].tolist() == np.asarray(want).tolist()
    finally:
        served.close()


# -- a retracting input -------------------------------------------------------


def test_a_retracting_input_still_retracts_and_promotes(tmp_path):
    """DELETE and UPDATE through DML reach the retractable executor (the
    planner proved nothing append-only): a deleted top row promotes the
    next, an updated one is rewritten in place, an emptied pair leaves."""
    rt = StreamingRuntime(
        LocalFsObjectStore(str(tmp_path)), checkpoint_frequency=1
    )
    s = SqlSession(Catalog({}), rt, capacity=1 << 8, exec_mode="graph")
    try:
        s.execute("CREATE TABLE t (id BIGINT PRIMARY KEY, g BIGINT, v BIGINT)")
        s.execute(
            "CREATE MATERIALIZED VIEW top2 AS SELECT id, g, v FROM (SELECT *, "
            "ROW_NUMBER() OVER (PARTITION BY g ORDER BY v DESC) AS rn FROM t) x "
            "WHERE rn <= 2"
        )
        (ex,) = [e for e in rt.fragments["top2"].executors
                 if isinstance(e, RetractableGroupTopNExecutor)]

        def view():
            out, _ = s.execute("SELECT id, g, v FROM top2")
            return set(zip(*(np.asarray(out[c]).tolist()
                             for c in ("id", "g", "v"))))

        s.execute("INSERT INTO t VALUES (1, 0, 10), (2, 0, 20), (3, 0, 30), "
                  "(4, 1, 5)")
        rt.barrier()
        assert view() == {(2, 0, 20), (3, 0, 30), (4, 1, 5)}
        s.execute("DELETE FROM t WHERE id = 3")  # promotes id 1
        rt.barrier()
        assert view() == {(1, 0, 10), (2, 0, 20), (4, 1, 5)}
        s.execute("UPDATE t SET v = 15 WHERE id = 2")  # rewritten in place
        rt.barrier()
        assert view() == {(1, 0, 10), (2, 0, 15), (4, 1, 5)}
        s.execute("UPDATE t SET v = 1 WHERE id = 2")  # falls behind id 1
        rt.barrier()
        assert view() == {(1, 0, 10), (2, 0, 1), (4, 1, 5)}
        s.execute("DELETE FROM t WHERE id = 4")  # the pair's last row
        rt.barrier()
        assert view() == {(1, 0, 10), (2, 0, 1)}
        assert int(jnp.sum(ex.emitted)) == 2
    finally:
        s.close()
        for p in rt.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()


# -- sizes, spans, counters ---------------------------------------------------


def test_emission_sizes_are_declared_and_warmed_when_the_view_is_created(
    tmp_path,
):
    assert [emission_lanes(n, 1 << 22) for n in
            (1, 8192, 16384, 16385, 32768, 65536, 65537)] == [
        16384, 16384, 16384, 65536, 65536, 65536, 262144]
    assert emission_lanes(32768, 4096) == 4096  # never more than the store
    TRACER.clear()
    served = Served(tmp_path, 64, "graph", capacity=1 << 15)
    try:
        ex = served.topn()
        sizes = ex.emission_sizes()
        assert sizes == (16384, 32768)
        assert ex.trace_contract()["emission_caps"] == sizes
        warm = [sp for sp in TRACER.spans() if sp.name == "actor.warm"
                and sp.args["executor"] == "RetractableGroupTopNExecutor"]
        assert [sp.args["lanes"] for sp in warm] == [list(sizes)]
        # the pass left no mark: nothing stored, dirtied or handed on
        assert int(ex.table.occupancy()) == 0 and ex._epoch_lanes == 0
        assert not bool(jnp.any(ex.emitted | ex.epoch_dirty | ex.sdirty))
    finally:
        served.close()


# programs that take the width of a chunk the Top-N hands on
_WIDTH_PROGRAMS = (
    "_leading_lanes", "_diff_gather", "_rank", "_project_step",
    "_add_edge_rows", "dynamic_slice",
)


@pytest.mark.parametrize(
    "first_met,floor", [("a_small_delta", 16), ("a_large_delta", 32)]
)
def test_no_barrier_of_either_size_compiles_once_the_view_is_created(
    tmp_path, monkeypatch, first_met, floor
):
    """Emission sizes from 16 lanes up (x4), the Top-N declaring 16 /
    1,024 / 4,096: an epoch of two 256-lane chunks gathers into 1,024
    lanes. A delta of up to 16 rows goes on in 16 lanes, a larger one
    in the 1,024; whichever kind the stream meets SECOND finds the cut,
    the projection behind it and the view's edge compiled by the
    warm-up pass, and the view stays the reference's. (From 32 lanes up
    in the second case, 32 / 512 / 2,048: the process keeps compiled
    programs, and a size the first case built would prove nothing.)"""
    monkeypatch.setattr(top_n_plain, "_EMIT_FLOOR", floor)
    monkeypatch.setattr(
        RetractableGroupTopNExecutor, "_WARM_EPOCHS", (1, 512, 2048)
    )
    bids = _bids(21, 1200)
    served = Served(tmp_path, 256, "graph")
    try:
        ex = served.topn()
        gathered = emission_lanes(2 * 256, ex.table.capacity)
        assert ex.emission_sizes() == (floor, gathered, 4 * gathered)
        assert gathered == {16: 1024, 32: 512}[floor]
        small, large = (5, 5), (256, 200)
        plan = [large, large, small] if first_met == "a_small_delta" else [
            small, small, large
        ]
        pos = 0
        for epoch, rows in enumerate(plan):
            TRACER.clear()
            for n in rows:
                served.push(bids, pos, pos + n)
                pos += n
            served.rt.barrier()
            spans = TRACER.spans()
            (rank,) = [sp for sp in spans if sp.name == "topn.rank"]
            (diff,) = [sp for sp in spans if sp.name == "topn.diff"]
            deltas = [diff.args["retract_rows"], diff.args["insert_rows"]]
            assert rank.args["lanes"] == gathered
            assert (max(deltas) <= floor) == (rows == small)
            assert diff.args["emit_lanes"] == sum(
                floor if n <= floor else gathered for n in deltas if n
            )
            if epoch == 2:
                built = [
                    sp.args.get("fun_name", "") for sp in spans
                    if sp.name == "compile"
                    and sp.args.get("event") == "backend_compile_duration"
                ]
                assert not [
                    f for f in built if any(p in f for p in _WIDTH_PROGRAMS)
                ], built
            assert served.read() == last_k(bids, pos, 1)
    finally:
        served.close()


def test_one_barrier_leaves_the_topn_spans_and_counters(tmp_path):
    served = Served(tmp_path, 256, "graph")
    try:
        bids = _bids(5, 512)
        served.push(bids, 0, 256)
        served.rt.barrier()
        ex = served.topn()
        tid = ex.table_id
        touched = REGISTRY.counter("group_topn_touched_groups_total")
        rows = REGISTRY.counter("group_topn_emitted_rows_total")
        before = (
            touched.get(table_id=tid),
            rows.get(table_id=tid, op="insert"),
            rows.get(table_id=tid, op="retract"),
        )
        view_before = served.read()
        TRACER.clear()
        served.push(bids, 256, 512)
        served.rt.barrier()
        spans = TRACER.spans()
        by_id = {sp.sid: sp for sp in spans}

        def one(name):
            (sp,) = [sp for sp in spans if sp.name == name]
            return sp

        rank, pull, diff = one("topn.rank"), one("topn.pull"), one("topn.diff")
        for sp in (rank, pull, diff):
            assert by_id[sp.parent].name == "actor.barrier"
            assert sp.args["table_id"] == tid
        assert rank.args["capacity"] == ex.table.capacity
        assert rank.args["lanes"] == emission_lanes(256, ex.table.capacity)
        assert diff.stage == "topn_diff"
        # (a store under the floor has one size: nothing to cut to)
        assert diff.args["emit_lanes"] == 2 * rank.args["lanes"]
        (step,) = [sp for sp in spans if sp.name == "actor.topn_step"]
        assert step.args["table_id"] == tid
        # the counts agree with the view's change and with the actor's
        # own count of the rows on its edges
        view = served.read()
        inserted, retracted = len(view - view_before), len(view_before - view)
        assert inserted > 0 and retracted > 0
        assert diff.args["insert_rows"] == inserted
        assert diff.args["retract_rows"] == retracted
        assert pull.args["rows"] == inserted + retracted
        pairs = set(zip(bids["bidder"][256:512].tolist(),
                        bids["auction"][256:512].tolist()))
        assert pull.args["groups"] == diff.args["groups"] == len(pairs)
        # which way the barrier went, and over how many lanes: at this
        # size the store has no more lanes than the candidates' sorts
        # would cover, so it is ranked, in one to nine sorts
        cand = candidate_lanes(256, ex.table.capacity, ex.limit)
        assert cand is None and rank.args["candidates"] == 0
        assert pull.args["full_rank"] == pull.args["rank_calls"] == 1
        assert pull.args["ranked_lanes"] == ex.table.capacity
        assert pull.args["touched_passes"] == pull.args["sifted_lanes"] == 0
        assert 1 <= pull.args["passes"] <= 9
        assert touched.get(table_id=tid) - before[0] == len(pairs)
        assert rows.get(table_id=tid, op="insert") - before[1] == inserted
        assert rows.get(table_id=tid, op="retract") - before[2] == retracted
        assert REGISTRY.gauge("group_topn_rows").get(table_id=tid) == 512
        (fence,) = [sp for sp in spans if sp.name == "actor.fence"
                    and sp.args.get("actor", "").startswith("q18")]
        # the Top-N's two chunks cross three edges (out of the Top-N, of
        # the project, of the MV); the 256 input rows one (row ids)
        assert fence.args["retract_rows"] == 3 * retracted
        assert fence.args["insert_rows"] == 3 * inserted + 256
    finally:
        served.close()


def test_a_barrier_with_no_chunk_reads_nothing_off_the_device(tmp_path):
    served = Served(tmp_path, 64, "graph")
    try:
        served.push(_bids(9, 64), 0, 64)
        served.rt.barrier()
        TRACER.clear()
        served.rt.barrier()
        names = {sp.name for sp in TRACER.spans()}
        assert "actor.barrier" in names
        assert not names & {"topn.rank", "topn.pull", "topn.diff"}
    finally:
        served.close()


def test_an_update_in_one_epoch_carries_the_old_values_out():
    """The executor alone: a row rewritten in place (same stream key,
    other values) is retracted with the values it was handed on with."""
    ex = RetractableGroupTopNExecutor(
        ("g",), "v", 1, ("id",),
        {"g": jnp.int64, "id": jnp.int64, "v": jnp.int64, "w": jnp.int32},
        desc=True, capacity=1 << 6, table_id="gt_update",
    )

    def step(rows, ops=None):
        g, i, v, w = zip(*rows)
        ex.apply(StreamChunk.from_numpy(
            {"g": np.asarray(g, np.int64), "id": np.asarray(i, np.int64),
             "v": np.asarray(v, np.int64), "w": np.asarray(w, np.int32)},
            8, ops=None if ops is None else np.asarray(ops, np.int32),
        ))
        out = []
        for c in ex.on_barrier(None):
            d = c.to_numpy(with_ops=True)
            out += [
                (int(o), int(a), int(b), int(x), int(y)) for o, a, b, x, y in
                zip(d["__op__"], d["g"], d["id"], d["v"], d["w"])
            ]
        return out

    ins, dele = int(Op.INSERT), int(Op.DELETE)
    assert step([(0, 1, 10, 7)]) == [(ins, 0, 1, 10, 7)]
    # only a column that is no key changes: still a retraction + insert
    assert step([(0, 1, 10, 8)]) == [(dele, 0, 1, 10, 7), (ins, 0, 1, 10, 8)]
    # written again with what it holds: nothing to say
    assert step([(0, 1, 10, 8)]) == []
    # delete and insert of the same row in one chunk, other values
    assert step([(0, 1, 10, 8), (0, 1, 12, 9)], [dele, ins]) == [
        (dele, 0, 1, 10, 8), (ins, 0, 1, 12, 9)]
