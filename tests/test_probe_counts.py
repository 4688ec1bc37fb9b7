"""What a probe did: ``lookup_or_insert_counted`` is ``lookup_or_insert``
over the same loop and says the loop's rounds, the lanes they ranged
over, the keys and the new keys; the per-group Top-N and the
epoch-batched aggregate sum that on the device, read it with the status
read their barrier makes anyway, and write one ``hash.probes`` span a
table a barrier. CPU; counts, never a time."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.frontend import SqlSession
from risingwave_tpu.ops import hash_table as ht
from risingwave_tpu.ops.hash_table import (
    MAX_PROBE,
    PROBE_STATS,
    HashTable,
    lookup_or_insert,
    lookup_or_insert_counted,
    set_live,
)
from risingwave_tpu.ops.hashing import hash128
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.sql import Catalog
from risingwave_tpu.storage.object_store import MemObjectStore
from risingwave_tpu.trace import TRACER


def _rounds_by_hand(table, keys, valid, insert_missing=True):
    """A plain linear-probe reference, no array program: every
    unresolved key looks at its ``t``-th slot in round ``t``; it stops at
    a slot that holds it, takes an empty one no other key took this
    round, and walks on otherwise. The rounds the batch needs are its
    longest chain + 1, and ``MAX_PROBE`` where a key found no slot."""
    cap = table.capacity
    h1 = np.asarray(hash128(keys)[0]).astype(np.int64)
    cols = [np.asarray(k) for k in keys]
    held = {
        s: tuple(np.asarray(lane)[s].item() for lane in table.keys)
        for s in np.flatnonzero(np.asarray(table.fp1) != 0)
    }
    walking = {}
    for i in np.flatnonzero(np.asarray(valid)):
        walking.setdefault(
            tuple(c[i].item() for c in cols), int(h1[i])
        )
    rounds = 0
    while walking and rounds < MAX_PROBE:
        for key, h in list(walking.items()):
            slot = (h + rounds) & (cap - 1)
            if slot not in held and insert_missing:
                held[slot] = key
            if held.get(slot) == key:
                del walking[key]
        rounds += 1
    return rounds


def _batch(rng, lanes, span, invalid=0.2):
    keys = (
        jnp.asarray(rng.integers(0, span, lanes), jnp.int64),
        jnp.asarray(rng.integers(0, 3, lanes), jnp.int32),
    )
    return keys, jnp.asarray(rng.random(lanes) > invalid)


def _same(a, b):
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_the_counted_probe_is_the_probe_and_counts_its_rounds(seed):
    """Random batches with duplicates into a table that fills up and
    holds tombstones: the twin hands back what ``lookup_or_insert``
    does, lane for lane, and its rounds are the reference's."""
    rng = np.random.default_rng(seed)
    plain = HashTable.create(256, (jnp.int64, jnp.int32))
    counted = HashTable.create(256, (jnp.int64, jnp.int32))
    for step in range(5):
        keys, valid = _batch(rng, 64, span=40 + 30 * step)
        want = _rounds_by_hand(counted, keys, valid)
        plain, *outs = lookup_or_insert(plain, keys, valid)
        counted, *outs_c, stats = lookup_or_insert_counted(
            counted, keys, valid
        )
        _same((plain, outs), (counted, outs_c))
        slots, _found, inserted = outs_c
        rounds, lane_rounds, n_keys, new_keys = np.asarray(stats).tolist()
        assert stats.dtype == jnp.int32 and len(PROBE_STATS) == 4
        assert rounds == want and 1 <= rounds < MAX_PROBE
        assert lane_rounds == rounds * 64
        assert n_keys == int(np.sum(np.asarray(valid)))
        # a new key's twins each count: ``inserted`` marks them all
        assert new_keys == int(np.sum(np.asarray(inserted)))
        assert new_keys >= len(set(np.asarray(slots)[np.asarray(inserted)]))
        # tombstones: every other row of this batch is deleted again
        dead = jnp.where(jnp.arange(64) % 2 == 0, slots, -1)
        plain = set_live(plain, dead, False)
        counted = set_live(counted, dead, False)
    # a read-only probe of the same keys walks and claims nothing
    keys, valid = _batch(rng, 64, span=400)
    want = _rounds_by_hand(counted, keys, valid, insert_missing=False)
    _, slots, found, _ = lookup_or_insert(plain, keys, valid, False)
    _, slots_c, found_c, inserted, stats = lookup_or_insert_counted(
        counted, keys, valid, False
    )
    _same((slots, found), (slots_c, found_c))
    assert np.asarray(stats).tolist()[3] == 0 and not np.any(inserted)
    # (a key no slot holds walks to the bound: nothing ends its chain)
    assert int(stats[0]) == want == MAX_PROBE


def test_an_overflowing_table_counts_every_round_to_the_bound():
    rng = np.random.default_rng(7)
    keys = (jnp.asarray(rng.permutation(200)[:96], jnp.int64),)
    valid = jnp.ones(96, jnp.bool_)
    plain, *outs = lookup_or_insert(
        HashTable.create(64, (jnp.int64,)), keys, valid
    )
    counted, *outs_c, stats = lookup_or_insert_counted(
        HashTable.create(64, (jnp.int64,)), keys, valid
    )
    _same((plain, outs), (counted, outs_c))
    slots = np.asarray(outs_c[0])
    assert np.sum(slots < 0) == 96 - 64  # the overflow signal, as before
    assert np.asarray(stats).tolist() == [MAX_PROBE, MAX_PROBE * 96, 96, 64]
    assert _rounds_by_hand(
        HashTable.create(64, (jnp.int64,)), keys, valid
    ) == MAX_PROBE


def test_both_probes_are_one_loop():
    """No second path: the two jitted entries lower the same probe (the
    twin's text is the plain one's plus the four sums)."""
    assert lookup_or_insert.__wrapped__.__code__.co_names.count(
        "_probe_or_insert"
    ) == 1
    assert lookup_or_insert_counted.__wrapped__.__code__.co_names.count(
        "_probe_or_insert"
    ) == 1
    table = HashTable.create(64, (jnp.int64,))
    args = (table, (jnp.arange(8, dtype=jnp.int64),), jnp.ones(8, jnp.bool_))
    loops = [
        fn.lower(*args).as_text().count("stablehlo.while")
        for fn in (lookup_or_insert, lookup_or_insert_counted)
    ]
    assert loops == [1, 1]
    assert ht.note_probes("t", "id", 0, [0, 0, 0, 0], 64) is None


# -- the two executors ---------------------------------------------------


class _Session:
    """A graph-mode session over one ``bid`` table and one view."""

    def __init__(self, view_sql, capacity=1024):
        self.runtime = StreamingRuntime(MemObjectStore())
        self.session = SqlSession(
            Catalog({}), self.runtime, capacity=capacity, exec_mode="graph"
        )
        self.session.execute(
            "CREATE TABLE bid (auction BIGINT, bidder BIGINT, price BIGINT, "
            "date_time TIMESTAMP)"
        )
        self.session.execute(view_sql)
        self.schema = self.session.catalog.tables["bid"]

    def push(self, cols):
        chunk = StreamChunk.from_numpy(cols, 256, schema=self.schema)
        with self.runtime.lock:
            for frag, side in self.session.dml._targets["bid"]:
                self.runtime.push(frag, chunk, side)

    def barrier(self):
        """The epoch's spans, once its barrier has returned."""
        TRACER.clear()
        self.runtime.barrier()
        self.runtime.wait_checkpoints()
        epoch = self.runtime.last_epoch_trace.epoch
        return [sp for sp in TRACER.spans() if sp.epoch == epoch]

    def close(self):
        self.session.close()
        for p in self.runtime.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()


def _bids(rng, pairs, at):
    """One chunk of bids, a distinct (bidder, auction) pair a row."""
    n = len(pairs)
    return {
        "auction": np.asarray([a for _b, a in pairs], np.int64),
        "bidder": np.asarray([b for b, _a in pairs], np.int64),
        "price": rng.integers(1, 1000, n).astype(np.int64),
        "date_time": (at + np.arange(n)).astype(np.int64),
    }


def _reads(spans):
    return collections.Counter(
        sp.args["what"] for sp in spans if sp.name == "device.read"
    )


TOPN_SQL = (
    "CREATE MATERIALIZED VIEW v AS SELECT auction, bidder, price FROM "
    "(SELECT *, ROW_NUMBER() OVER (PARTITION BY bidder, auction ORDER BY "
    "date_time DESC) AS rank_number FROM bid) B WHERE rank_number <= 1"
)
# the blocking reads of each epoch of the two sessions below AT THE
# PARENT COMMIT (37ea2c4; the same pushes through the same session):
# this PR adds lanes to one of them and no read
_BASE = {"edge_rows": 1, "checkpoint.marks": 2, "pull_rows": 1}
_ONE = {"chunk.valid": 2, "chunk.lanes": 1, "chunk.ops": 1}
_TWO = {"chunk.valid": 4, "chunk.lanes": 2, "chunk.ops": 2}
TOPN_READS = [
    {"topn.status": 1, **_ONE, **_BASE},  # inserts alone: one chunk on
    {"topn.status": 1, **_TWO, **_BASE, "scalars": 1},
    {"topn.status": 1, **_TWO, **_BASE},
]
AGG_READS = {"agg.flush.status": 1, "scalars": 1, **_ONE, **_BASE}


def test_a_topn_barrier_writes_one_record_a_table_and_no_new_read():
    rng = np.random.default_rng(3)
    s = _Session(TOPN_SQL)
    try:
        all_pairs = [(b, a) for b in range(30) for a in range(30)]
        order = rng.permutation(len(all_pairs))
        seen = set()
        for epoch in range(3):
            pushed = new_pairs = 0
            for c in range(2):
                # half the chunk's pairs are new, half were bid on before
                fresh = [all_pairs[i] for i in order[:60]]
                order = order[60:]
                old = list(seen)[:40]
                pairs = fresh + old
                s.push(_bids(rng, pairs, 1000 * (2 * epoch + c)))
                pushed += len(pairs)
                new_pairs += len(set(fresh) - seen)
                seen |= set(fresh)
            spans = s.barrier()
            probes = [sp for sp in spans if sp.name == "hash.probes"]
            assert sorted(sp.args["table"] for sp in probes) == [
                "topn.groups", "topn.rows",
            ]
            by = {sp.args["table"]: sp.args for sp in probes}
            for args in by.values():
                # (the store grows in the second epoch: ``scalars``)
                assert args["calls"] == 2
                assert args["capacity"] == (1024 if epoch == 0 else 2048)
                assert args["keys"] == pushed
                assert args["lane_rounds"] == args["rounds"] * 256
                assert 2 <= args["rounds"] <= 2 * MAX_PROBE
                assert args["table_id"].startswith("v.")
            # a bid is a row of its own (the row id is in the store's
            # key); its pair is new once
            assert by["topn.rows"]["new_keys"] == pushed
            assert by["topn.groups"]["new_keys"] == new_pairs
            assert by["topn.rows"]["claimed"] >= pushed
            assert "claimed" not in by["topn.groups"]
            # the reads are the parent's: one status read, 11 + 8 lanes
            assert dict(_reads(spans)) == TOPN_READS[epoch]
            (status,) = [
                sp for sp in spans if sp.name == "device.read"
                and sp.args["what"] == "topn.status"
            ]
            assert status.args["lanes"] == 19
        # a barrier no chunk preceded reads nothing and writes none
        assert not [
            sp for sp in s.barrier()
            if sp.name in ("hash.probes", "topn.pull")
        ]
    finally:
        s.close()


def test_an_aggregate_barrier_writes_one_record_and_no_new_read():
    rng = np.random.default_rng(4)
    s = _Session(
        "CREATE MATERIALIZED VIEW v AS SELECT auction, count(*) AS n, "
        "max(price) AS p FROM bid GROUP BY auction"
    )
    try:
        seen = set()
        for epoch in range(3):
            auctions = set()
            for c in range(2):
                cols = _bids(
                    rng,
                    [(b, int(rng.integers(0, 60 + 40 * epoch)))
                     for b in range(100)],
                    1000 * (2 * epoch + c),
                )
                s.push(cols)
                auctions |= set(cols["auction"].tolist())
            spans = s.barrier()
            (probe,) = [sp for sp in spans if sp.name == "hash.probes"]
            args = probe.args
            assert args["table"] == "agg" and args["calls"] == 1
            # the epoch's rows are reduced by key before the one probe:
            # a key is a distinct group of the epoch's batch
            assert args["keys"] == len(auctions)
            assert args["new_keys"] == len(auctions - seen)
            assert args["lane_rounds"] == args["rounds"] * 512
            assert args["capacity"] == 1024
            seen |= auctions
            assert args["claimed"] == len(seen)
            assert dict(_reads(spans)) == AGG_READS
            (status,) = [
                sp for sp in spans if sp.name == "device.read"
                and sp.args["what"] == "agg.flush.status"
            ]
            assert status.args["lanes"] == 2 + 4
        assert not [
            sp for sp in s.barrier() if sp.name == "hash.probes"
        ]
    finally:
        s.close()
