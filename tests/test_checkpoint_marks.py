"""A checkpoint's marks classified on the device (``classify_marks``)
against the host rule it replaced, kept here as the plain reference;
and, for each executor family a benchmark cell runs, stage -> commit ->
``recover()`` onto a fresh executor giving the live ``state_digest``."""

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.dedup import AppendOnlyDedupExecutor
from risingwave_tpu.executors.hash_agg import HashAggExecutor
from risingwave_tpu.executors.hash_join import HashJoinExecutor
from risingwave_tpu.executors.keyed_join import KeyedJoinExecutor
from risingwave_tpu.executors.materialize import DeviceMaterializeExecutor
from risingwave_tpu.executors.stream_join import StreamJoinExecutor
from risingwave_tpu.executors.top_n_plain import RetractableGroupTopNExecutor
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.array.lattice import (
    DELTA_BLOCK,
    DELTA_SMALL,
    SELECT_SPAN,
    delta_blocks,
    select_spans,
)
from risingwave_tpu.runtime.pipeline import Pipeline, TwoInputPipeline
from risingwave_tpu.storage.object_store import MemObjectStore
from risingwave_tpu.storage.state_table import (
    CheckpointManager,
    classify_marks,
    pull_rows,
)
from risingwave_tpu.trace import TRACER
from risingwave_tpu.types import Op

I64 = jnp.int64


def host_rule(sdirty, alive, stored):
    """``stage_marks`` as every executor ran it on the host until PR
    39, and the flip each made after it."""
    upsert = sdirty & alive
    tomb = sdirty & stored & ~alive
    sel = np.flatnonzero(upsert | tomb)
    return sel, tomb[sel], (stored | upsert) & ~tomb


def _check(sdirty, alive, stored):
    """``alive`` a list of lanes, any of which keeps a slot."""
    kept = np.logical_or.reduce([np.asarray(a) != 0 for a in alive])
    sel, dead, flipped = host_rule(
        sdirty.reshape(-1), kept.reshape(-1), stored.reshape(-1)
    )
    flipped = flipped.reshape(sdirty.shape)
    marks = classify_marks(
        jnp.asarray(sdirty), tuple(jnp.asarray(a) for a in alive),
        jnp.asarray(stored),
    )
    assert len(marks) == len(sel)
    assert marks.slots().tolist() == sel.tolist()  # ascending
    assert marks.tombstone.tolist() == dead.tolist()
    assert np.asarray(marks.stored).tolist() == flipped.tolist()
    assert not np.asarray(marks.sdirty).any()
    assert marks.sdirty.shape == marks.stored.shape == sdirty.shape
    if len(sel):
        lane = jnp.arange(sdirty.size, dtype=I64).reshape(sdirty.shape) * 3
        flat = lane.reshape(-1)
        assert pull_rows({"x": flat}, marks)["x"].tolist() == (
            sel * 3
        ).tolist()
    return marks


@pytest.mark.parametrize("density", [0.0, 0.002, 0.05, 0.6, 1.0])
@pytest.mark.parametrize(
    "shape", [(2,), (100,), (128,), (16384,), (3 * 16384 + 5,), (8, 2048)]
)
def test_random_marks_equal_the_host_rule(shape, density):
    """Capacities under one row of marks, of no whole row, of no whole
    group of rows, and a mesh executor's (shards, capacity) lanes."""
    rng = np.random.default_rng(hash((shape, density)) % 2**32)
    _check(
        rng.random(shape) < density,
        [rng.random(shape) < 0.5],
        rng.random(shape) < 0.5,
    )


def test_alive_is_any_of_its_lanes_and_a_lane_that_is_no_bool():
    rng = np.random.default_rng(7)
    n = 4096
    sdirty = rng.random(n) < 0.3
    stored = rng.random(n) < 0.5
    # an aggregate's three lanes
    _check(sdirty, [rng.random(n) < 0.2 for _ in range(3)], stored)
    # an over-window table's fingerprints: a slot is claimed where not 0
    fp1 = np.where(rng.random(n) < 0.5, 0, rng.integers(1, 2**32, n))
    _check(sdirty, [fp1.astype(np.uint32)], stored)


def _marks_reads():
    return [
        sp for sp in TRACER.spans()
        if sp.name == "device.read" and sp.args["what"] == "checkpoint.marks"
    ]


def test_a_clean_table_costs_one_scalar_read():
    n = 16384
    rng = np.random.default_rng(3)
    lanes = [jnp.asarray(rng.random(n) < 0.5) for _ in range(2)]
    clean = jnp.zeros(n, jnp.bool_)
    # sdirty on dead slots that were never stored changes nothing either
    # (inserted and deleted inside one epoch), but the marks clear
    never = jnp.asarray(~np.asarray(lanes[0]) & ~np.asarray(lanes[1]))
    for sdirty in (clean, never):
        TRACER.clear()
        marks = classify_marks(sdirty, lanes[0], lanes[1])
        assert len(marks) == 0 and marks.blocks == []
        assert marks.tombstone.tolist() == []
        assert [sp.args["bytes"] for sp in _marks_reads()] == [4]
        assert not np.asarray(marks.sdirty).any()
        assert np.asarray(marks.stored).tolist() == (
            np.asarray(lanes[1]).tolist()
        )


@pytest.mark.parametrize(
    "count",
    [1, DELTA_SMALL, DELTA_SMALL + 1, DELTA_BLOCK, DELTA_BLOCK + 1,
     SELECT_SPAN, SELECT_SPAN + 1, 2 * SELECT_SPAN + DELTA_BLOCK + 1],
)
def test_a_count_at_and_one_over_each_declared_size(count):
    """The selection is made in ``select_spans``' two sizes (4,096
    ranks, or as many spans of 16,384 as hold the count) and comes in
    ``delta_blocks``' pieces: one of 256, or as many of 4,096 as hold
    the count."""
    n = 1 << 18
    rng = np.random.default_rng(count)
    sdirty = np.zeros(n, bool)
    sdirty[rng.choice(n, count, replace=False)] = True
    alive = rng.random(n) < 0.5
    TRACER.clear()
    marks = _check(sdirty, [alive], np.ones(n, bool))
    assert select_spans(count) == (
        (DELTA_BLOCK, 1) if count <= DELTA_BLOCK
        else (SELECT_SPAN, -(-count // SELECT_SPAN))
    )
    block, pieces = delta_blocks(count)
    assert [b.shape for b in marks.blocks] == [(block,)] * pieces
    span, programs = select_spans(count)
    reads = [sp.args["bytes"] for sp in _marks_reads()]
    # the count, a byte a rank selected, and (slots()) four a padded slot
    ranks = DELTA_SMALL if count <= DELTA_SMALL else span * programs
    assert reads[:3] == [4, ranks, 4 * block * pieces]


# -- the executor families the benchmark's cells run ----------------------
DT = {"k": I64, "v": I64}
CAP = 64


def _rows(ks, vs, ops=None, cap=CAP, names=("k", "v")):
    cols = {
        names[0]: np.asarray(ks, np.int64), names[1]: np.asarray(vs, np.int64)
    }
    if ops is None:
        return StreamChunk.from_numpy(cols, cap)
    return StreamChunk.from_numpy(cols, cap, ops=np.asarray(ops, np.int32))


class _Single:
    """A serial pipeline of one chain, driven three epochs: inserts,
    then deletes of stored rows beside new ones, then rows again."""

    def __init__(self, executors):
        self.pipe = Pipeline(executors)

    @property
    def executors(self):
        return self.pipe.executors

    def epoch(self, i):
        ks = np.arange(40 * i, 40 * i + 40)
        self.pipe.push(_rows(ks % self.groups, ks))
        if i and self.retracts:
            gone = np.arange(40 * (i - 1), 40 * (i - 1) + 12)
            self.pipe.push(
                _rows(gone % self.groups, gone, np.full(12, int(Op.DELETE)))
            )
        self.pipe.barrier()
        return self.pipe.epoch

    retracts = True
    groups = 50


class _Agg(_Single):
    groups = 1 << 20  # a group a row: a deleted row's group dies

    def __init__(self, capacity=1 << 9):
        calls = (AggCall("count_star", None, "cnt"), AggCall("sum", "v", "s"))
        self.agg = HashAggExecutor(
            group_keys=("k",), calls=calls, schema_dtypes=DT,
            capacity=capacity, out_cap=1 << 8, table_id="fam.agg",
        )
        # the aggregate feeds a device view: the family ``materialize``
        self.mv = DeviceMaterializeExecutor(
            ("k",), ("cnt", "s"), {"k": I64, "cnt": I64, "s": I64},
            table_id="fam.agg.mv", capacity=capacity,
        )
        super().__init__([self.agg, self.mv])


class _TopN(_Single):
    def __init__(self):
        super().__init__([
            RetractableGroupTopNExecutor(
                ("k",), "v", 2, ("v",), DT, desc=True,
                capacity=1 << 9, table_id="fam.topn",
            )
        ])


class _Dedup(_Single):
    retracts = False  # append-only by contract

    def __init__(self):
        super().__init__([
            AppendOnlyDedupExecutor(
                ("v",), DT, capacity=1 << 9, table_id="fam.dedup"
            )
        ])


class _Join:
    """A two-input pipeline over one join: rows on both sides, then
    deletes on the left and more on the right."""

    def __init__(self, join):
        self.pipe = TwoInputPipeline([], [], join, [])

    @property
    def executors(self):
        return self.pipe.executors

    def epoch(self, i):
        ks = np.arange(20 * i, 20 * i + 20)
        self.pipe.push_left(_rows(ks, ks % 7, names=("lk", "lv")))
        self.pipe.push_right(_rows(ks % 30, ks, names=("rk", "rv")))
        if i:
            gone = np.arange(20 * (i - 1), 20 * (i - 1) + 6)
            self.pipe.push_left(_rows(
                gone, gone % 7, np.full(6, int(Op.DELETE)), names=("lk", "lv")
            ))
        self.pipe.barrier()
        return self.pipe.epoch


L, R = {"lk": I64, "lv": I64}, {"rk": I64, "rv": I64}


def _bucket_join():
    return _Join(HashJoinExecutor(
        ("lk",), ("rk",), L, R, capacity=1 << 8, fanout=8,
        out_cap=1 << 10, table_id="fam.hj",
    ))


def _chained_join():
    # the left side retracts (its rows carry rdirty / stored marks),
    # the right is append-only (its rows are staged by position)
    return _Join(StreamJoinExecutor(
        ("lk",), ("rk",), L, R, right_append_only=True,
        capacity=1 << 8, out_cap=1 << 10, table_id="fam.sj",
    ))


class _KeyedJoin:
    """Many rows a key on the left, one on the right (q5's join)."""

    def __init__(self):
        self.join = KeyedJoinExecutor(
            left_keys=("g",), right_keys=("ug",),
            left_dtypes={"k": I64, "g": I64, "v": I64},
            right_dtypes={"ug": I64, "top": I64},
            left_pk=("k",), right_pk=("ug",), unique_side="right",
            capacity=1 << 8, table_id="fam.kj",
        )
        self.executors = [self.join]
        self.n = 0

    def epoch(self, i):
        ks = np.arange(30 * i, 30 * i + 30)
        self.join.apply_left(StreamChunk.from_numpy(
            {"k": ks, "g": ks % 5, "v": ks % 11}, CAP
        ))
        if i == 0:
            self.join.apply_right(StreamChunk.from_numpy(
                {"ug": np.arange(5), "top": np.arange(5) + 3}, 8
            ))
        else:
            gone = np.arange(30 * (i - 1), 30 * (i - 1) + 8)
            self.join.apply_left(StreamChunk.from_numpy(
                {"k": gone, "g": gone % 5, "v": gone % 11}, CAP,
                ops=np.full(8, int(Op.DELETE), np.int32),
            ))
        self.join.on_barrier(None)
        self.n += 1
        return self.n


FAMILIES = {
    "aggregate_and_materialize": _Agg,
    "top_n": _TopN,
    "dedup": _Dedup,
    "bucket_join": _bucket_join,
    "chained_join_retracting_side": _chained_join,
    "keyed_join": _KeyedJoin,
}


@pytest.mark.parametrize("family", list(FAMILIES))
def test_stage_commit_recover_gives_the_live_digest(family):
    live = FAMILIES[family]()
    mgr = CheckpointManager(MemObjectStore())
    staged = tombstones = 0
    for i in range(3):
        epoch = live.epoch(i)
        deltas = mgr.stage(live.executors)
        staged += sum(len(d.tombstone) for d in deltas)
        tombstones += sum(int(d.tombstone.sum()) for d in deltas)
        mgr.commit_staged(epoch, deltas)
        # the flip was eager: a second staging finds every table clean
        assert mgr.stage(live.executors) == []
    assert staged > 0
    assert tombstones > 0 or family == "dedup", "the drive staged no tombstone"
    fresh = FAMILIES[family]()
    mgr.recover(fresh.executors)
    for a, b in zip(live.executors, fresh.executors):
        assert a.state_digest() == b.state_digest(), type(a).__name__


def test_a_table_regrown_between_two_checkpoints():
    """Slots shift on a rehash: the marks staged after it are the new
    table's, the rows recover whole, and the digest is the live one."""
    live = _Agg(capacity=1 << 6)
    mgr = CheckpointManager(MemObjectStore())
    mgr.commit_epoch(live.epoch(0), live.executors)
    small = live.agg.table.capacity
    for i in range(1, 6):
        epoch = live.epoch(i)
    assert live.agg.table.capacity > small
    mgr.commit_epoch(epoch, live.executors)
    fresh = _Agg(capacity=1 << 6)
    mgr.recover(fresh.executors)
    assert fresh.agg.state_digest() == live.agg.state_digest()
    assert fresh.mv.state_digest() == live.mv.state_digest()
    keys, _ = mgr.read_table("fam.agg")
    gone = {40 * i + j for i in range(5) for j in range(12)}
    assert sorted(keys["k0"].tolist()) == sorted(set(range(240)) - gone)
