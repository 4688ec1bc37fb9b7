"""The retractable GroupTopN's barrier over what the epoch touched
(``executors/top_n_plain.py``): the rank over the epoch's candidates —
the rows its chunks wrote and the chains of their groups' top k —
against the rank over every lane of the store, chunk for chunk and lane
for lane; and each way the candidates cannot answer (a chain's row gone
with rows behind it, chains left cold by a restore or a re-slotted
store, more candidates than lanes, a list given up), where the barrier
ranks the store and stays exact."""

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors import top_n_plain
from risingwave_tpu.executors.base import Watermark
from risingwave_tpu.executors.top_n_plain import (
    RetractableGroupTopNExecutor,
    candidate_lanes,
    emission_lanes,
)
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.trace import TRACER
from risingwave_tpu.types import Op

DTYPES = {"g": jnp.int64, "id": jnp.int64, "v": jnp.int64, "w": jnp.int32}
CHUNK = 128
FLOOR = 64  # the smallest emission (and candidate) size, for these tests

# name: (order, limit, rank_col, capacity)
SHAPES = {
    "k1": ("v", 1, None, 1 << 13),
    "k10_rank_col": ("v", 10, "rn", 1 << 15),
    "two_keys": ([("v", True), ("w", False)], 3, None, 1 << 13),
}


@pytest.fixture(autouse=True)
def small_sizes(monkeypatch):
    """Sizes from 64 lanes up (x4), so that a store of 2^13 lanes has
    epochs whose candidates fit and epochs whose candidates do not."""
    monkeypatch.setattr(top_n_plain, "_EMIT_FLOOR", FLOOR)


def _executor(shape, **kw):
    order, limit, rank_col, capacity = SHAPES[shape]
    kw.setdefault("capacity", capacity)
    return RetractableGroupTopNExecutor(
        ("g",), order, limit, ("id",), DTYPES, desc=True,
        table_id=f"touched_{shape}", rank_col=rank_col, **kw,
    )


def _chunk(rows, ops):
    cols = {
        n: np.asarray([r[i] for r in rows], np.dtype(DTYPES[n]))
        for i, n in enumerate(("g", "id", "v", "w"))
    }
    return StreamChunk.from_numpy(cols, CHUNK, ops=np.asarray(ops, np.int32))


def _rows_of(chunks):
    """What a barrier handed on, chunk for chunk: each chunk's valid
    rows in their lanes' order as (g, id, v, w[, rank], op)."""
    out = []
    for c in chunks:
        cols = c.to_numpy()
        names = ["g", "id", "v", "w"] + sorted(
            set(cols) - {"g", "id", "v", "w", "__op__"}
        ) + ["__op__"]
        out.append(
            [tuple(cols[k][i].item() for k in names)
             for i in range(len(cols["g"]))]
        )
    return out


def _pull_of(table_id):
    (sp,) = [
        sp for sp in TRACER.spans()
        if sp.name == "topn.pull" and sp.args["table_id"] == table_id
    ]
    return sp.args


def _barrier(ex):
    """One barrier: (the rows handed on, ``topn.pull``'s args)."""
    TRACER.clear()
    return _rows_of(ex.on_barrier(None)), _pull_of(ex.table_id)


def _store_wide(ex):
    """The same barrier made by the rank over every lane of the store
    (what a cold chain asks for: the program that always ranked)."""
    ex._cold = True
    rows, pull = _barrier(ex)
    assert pull["full_rank"] == 1 and pull["passes"] >= 1
    assert pull["touched_passes"] == 0
    assert pull["ranked_lanes"] == ex.table.capacity
    return rows


def _same_state(ex, ref):
    np.testing.assert_array_equal(np.asarray(ex.emitted), np.asarray(ref.emitted))
    if ex.erank is not None:
        np.testing.assert_array_equal(np.asarray(ex.erank), np.asarray(ref.erank))
    for n in ex.shadow:
        np.testing.assert_array_equal(
            np.asarray(ex.shadow[n])[np.asarray(ex.emitted)],
            np.asarray(ref.shadow[n])[np.asarray(ref.emitted)],
        )


def _chains_say_the_top_k(ex):
    """Every group's chain is its rows the lane ``emitted`` marks, in
    rank order (``erank`` where it is kept)."""
    head, after = np.asarray(ex.tops.head), np.asarray(ex.tops.after)
    of_row = np.asarray(ex.groups.of_row)
    emitted = np.asarray(ex.emitted)
    chained = np.zeros_like(emitted)
    for g in np.unique(of_row[of_row >= 0]):
        at, rank = head[g], 0
        while at >= 0:
            rank += 1
            assert of_row[at] == g and emitted[at] and not chained[at]
            if ex.erank is not None:
                assert int(ex.erank[at]) == rank
            chained[at] = True
            at = after[at]
        assert rank <= ex.limit
    np.testing.assert_array_equal(chained, emitted)


class _Stream:
    """A seeded stream of inserts, deletes and in-place updates over a
    few groups, no row twice in one chunk."""

    def __init__(self, seed, groups, deletes=0.2, updates=0.2):
        self.rng = np.random.default_rng(seed)
        self.groups, self.deletes, self.updates = groups, deletes, updates
        self.live = {}  # (g, id) -> (v, w)
        self.next_id = 0

    def chunk(self, rows):
        batch, ops, used = [], [], set()
        for _ in range(rows):
            u = self.rng.random()
            free = [key for key in self.live if key not in used]
            if free and u < self.deletes + self.updates:
                key = free[self.rng.integers(len(free))]
                used.add(key)
                if u < self.deletes:
                    batch.append(key + self.live.pop(key))
                    ops.append(int(Op.DELETE))
                    continue
                vw = (int(self.rng.integers(8)), int(self.rng.integers(3)))
            else:
                key = (int(self.rng.integers(self.groups)), self.next_id)
                self.next_id += 1
                used.add(key)
                vw = (int(self.rng.integers(8)), int(self.rng.integers(3)))
            self.live[key] = vw
            batch.append(key + vw)
            ops.append(int(Op.INSERT))
        return _chunk(batch, ops)

    def view(self, ex):
        """The view a recompute gives: each group's top k, ranked."""
        directions = [(c, d) for c, d in ex.order]
        by_group = {}
        for (g, id_), (v, w) in self.live.items():
            by_group.setdefault(g, []).append({"g": g, "id": id_, "v": v, "w": w})
        out = set()
        for g, rows in by_group.items():
            rows.sort(key=lambda r: tuple(
                -r[c] if d else r[c] for c, d in directions) + (r["id"],))
            for rank, r in enumerate(rows[: ex.limit], 1):
                row = (r["g"], r["id"], r["v"], r["w"])
                out.add(row + ((rank,) if ex.rank_col else ()))
        return out


def _apply_to_view(view, chunks):
    """The chunks a barrier handed on applied to a view's rows."""
    for rows in chunks:
        for *vals, op in rows:
            if op in (int(Op.DELETE), int(Op.UPDATE_DELETE)):
                view.remove(tuple(vals))
            else:
                assert tuple(vals) not in view
                view.add(tuple(vals))


@pytest.mark.parametrize("seed", (0, 1, 2))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_touched_rank_hands_on_what_the_store_wide_rank_does(shape, seed):
    """Over a seeded stream of inserts, deletes and updates: every
    barrier's chunks, and the lanes ``emitted`` / ``erank`` after it,
    equal those of an executor that ranks its whole store every
    barrier; the view they build is the recompute's; and both ways are
    taken (the candidates answer; a chain's row went with rows behind
    it)."""
    ex, ref = _executor(shape), _executor(shape)
    stream = _Stream(seed, groups=40)
    view, taken = set(), set()
    for epoch in range(10):
        for _ in range(1 + epoch % 2):
            c = stream.chunk(int(stream.rng.integers(20, CHUNK)))
            ex.apply(c)
            ref.apply(c)
        got, pull = _barrier(ex)
        assert got == _store_wide(ref)
        _same_state(ex, ref)
        _chains_say_the_top_k(ex)
        _apply_to_view(view, got)
        assert view == stream.view(ex)
        assert pull["rank_calls"] == 1
        cand = candidate_lanes((1 + epoch % 2) * CHUNK, ex.table.capacity,
                               ex.limit)
        assert pull["ranked_lanes"] == cand + (
            ex.table.capacity if pull["full_rank"] else 0
        )
        assert pull["touched_passes"] >= 1
        assert (pull["passes"] > 0) == bool(pull["full_rank"])
        taken.add(pull["full_rank"])
    assert taken == {0, 1}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_an_epoch_of_new_rows_never_ranks_the_store(shape):
    """Insert-only epochs (NEXmark's bids): a group's new top k is the
    top k of its old one and its new rows, a row pushed out is a row of
    the chain, and with the rank a column every row whose rank moves is
    in the chain or new."""
    ex, ref = _executor(shape), _executor(shape)
    stream = _Stream(7, groups=12, deletes=0.0, updates=0.0)
    for _ in range(6):
        c = stream.chunk(100)
        ex.apply(c)
        ref.apply(c)
        got, pull = _barrier(ex)
        assert got == _store_wide(ref)
        assert pull["full_rank"] == 0 and pull["passes"] == 0
        assert pull["ranked_lanes"] == candidate_lanes(
            CHUNK, ex.table.capacity, ex.limit
        )
        assert pull["sifted_lanes"] == pull["ranked_lanes"] * (1 + ex.limit)
    _same_state(ex, ref)
    _chains_say_the_top_k(ex)


def _standing(ex, rows):
    ex.apply(_chunk(rows, [int(Op.INSERT)] * len(rows)))
    return _barrier(ex)


@pytest.mark.parametrize("how", ("deleted", "rewritten_worse"))
def test_a_chains_row_gone_with_rows_behind_it_ranks_the_store(how):
    """k = 1 and three rows in the group: the first is deleted (or
    overwritten with a worse order key), the next is in the store and
    in no chain — the program says so in the barrier's one read, the
    store is ranked, and the second row is handed on."""
    ex = _executor("k1")
    _, pull = _standing(ex, [(1, 10, 9, 0), (1, 11, 5, 0), (1, 12, 3, 0)])
    assert pull["full_rank"] == 0
    if how == "deleted":
        ex.apply(_chunk([(1, 10, 9, 0)], [int(Op.DELETE)]))
    else:
        ex.apply(_chunk([(1, 10, 1, 0)], [int(Op.INSERT)]))
    got, pull = _barrier(ex)
    assert pull["full_rank"] == 1 and pull["passes"] >= 1
    assert pull["ranked_lanes"] == 4 * FLOOR + ex.table.capacity
    (ret,), (ins,) = got
    assert ret[:4] == (1, 10, 9, 0) and ins[:4] == (1, 11, 5, 0)
    _chains_say_the_top_k(ex)
    # and the chains it rewrote answer the next barrier
    ex.apply(_chunk([(1, 13, 7, 0)], [int(Op.INSERT)]))
    got, pull = _barrier(ex)
    assert pull["full_rank"] == 0
    assert [r[:4] for rows in got for r in rows] == [(1, 11, 5, 0), (1, 13, 7, 0)]


@pytest.mark.parametrize("shape", ("k10_rank_col", "two_keys"))
def test_a_group_of_fewer_rows_than_k_needs_no_store(shape):
    """A chain shorter than k holds the whole group: its rows may go
    and nothing stands behind them."""
    ex, ref = _executor(shape), _executor(shape)
    rows = [(1, 10, 9, 0), (1, 11, 5, 0), (2, 20, 4, 1)]
    for e in (ex, ref):
        _standing(e, rows)
        e.apply(_chunk([(1, 10, 9, 0), (2, 20, 4, 1)], [int(Op.DELETE)] * 2))
    got, pull = _barrier(ex)
    assert pull["full_rank"] == 0 and pull["passes"] == 0
    assert got == _store_wide(ref)
    _same_state(ex, ref)
    _chains_say_the_top_k(ex)


def test_more_candidates_than_lanes_ranks_the_store():
    """k = 10 and every row of a full chunk in a group of its own whose
    chain is whole: eleven slots a row, where the lanes hold two."""
    ex, ref = _executor("k10_rank_col"), _executor("k10_rank_col")
    for e in (ex, ref):
        for g in range(0, CHUNK, 8):
            _standing(e, [(g + j, 100 * (g + j) + i, i, 0)
                          for j in range(8) for i in range(10)])
        c = _chunk([(g, 100 * g + 50, 99, 0) for g in range(CHUNK)],
                   [int(Op.INSERT)] * CHUNK)
        e.apply(c)
    got, pull = _barrier(ex)
    assert pull["full_rank"] == 1
    assert got == _store_wide(ref)
    _same_state(ex, ref)
    _chains_say_the_top_k(ex)


def _checkpointed(ex):
    keys, vals = {}, {}
    for d in ex.checkpoint_delta():
        live = ~np.asarray(d.tombstone)
        keys = {k: np.asarray(v)[live] for k, v in d.key_cols.items()}
        vals = {k: np.asarray(v)[live] for k, v in d.value_cols.items()}
    return keys, vals


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_restore_leaves_cold_chains_and_the_next_barrier_is_exact(shape):
    ex, ref = _executor(shape), _executor(shape)
    stream = _Stream(3, groups=12, deletes=0.0, updates=0.0)
    c = stream.chunk(90)
    for e in (ex, ref):
        e.apply(c)
        e.on_barrier(None)
    keys, vals = _checkpointed(ex)
    fresh = _executor(shape)
    fresh.restore_state(fresh.table_id, keys, vals)
    assert fresh._cold
    assert int(jnp.max(fresh.tops.head)) == -1  # no chain says anything
    assert int(jnp.sum(fresh.groups.of_row >= 0)) == 90
    stream.deletes = 0.3
    c = stream.chunk(60)
    fresh.apply(c)
    ref.apply(c)
    got, pull = _barrier(fresh)
    assert pull["full_rank"] == 1 and pull["ranked_lanes"] == fresh.table.capacity
    assert sorted(map(sorted, got)) == sorted(map(sorted, _store_wide(ref)))
    assert not fresh._cold
    _chains_say_the_top_k(fresh)
    # warm again: the candidates answer an epoch of new rows
    stream.deletes = 0.0
    c = stream.chunk(40)
    fresh.apply(c)
    ref.apply(c)
    got, pull = _barrier(fresh)
    assert pull["full_rank"] == 0
    assert sorted(map(sorted, got)) == sorted(map(sorted, _store_wide(ref)))
    _chains_say_the_top_k(fresh)


def test_a_grown_store_leaves_cold_chains_and_the_next_barrier_is_exact():
    """``_maybe_grow`` re-slots the store in the middle of an epoch:
    the list and the chains named the old slots."""
    ex, ref = _executor("k1", capacity=512), _executor("k1", capacity=1 << 13)
    stream = _Stream(5, groups=30, deletes=0.1, updates=0.1)
    view = set()
    for epoch in range(4):
        for _ in range(2):
            c = stream.chunk(CHUNK - 8)
            ex.apply(c)
            ref.apply(c)
        cold = ex._cold
        got, pull = _barrier(ex)
        assert pull["full_rank"] == 1 or not cold
        assert sorted(map(sorted, got)) == sorted(map(sorted, _store_wide(ref)))
        _chains_say_the_top_k(ex)
    assert ex.table.capacity > 512


def test_a_watermark_prunes_groups_and_the_next_barrier_is_exact():
    """Expired groups leave ``emitted`` without a retraction and stay
    in their chains as dead rows: a late row into one is ranked against
    them, and handed on alone."""
    def make():
        return _executor("k1", window_key=("g", 0))
    ex, ref = make(), make()
    rows = [(g, 10 * g + i, i, 0) for g in range(1, 9) for i in range(3)]
    for e in (ex, ref):
        _standing(e, rows)
        e.on_watermark(Watermark("g", 5))  # groups 1-4 close
    assert int(jnp.sum(ex.emitted)) == 4
    late = [(2, 29, 1, 0), (7, 79, 9, 0), (9, 90, 1, 0)]
    for e in (ex, ref):
        e.apply(_chunk(late, [int(Op.INSERT)] * 3))
    got, pull = _barrier(ex)
    assert got == _store_wide(ref)
    _same_state(ex, ref)
    (ret,), ins = got
    assert ret[:2] == (7, 72) and sorted(r[:2] for r in ins) == [
        (2, 29), (7, 79), (9, 90)
    ]


def test_a_list_with_no_room_ranks_the_store(monkeypatch):
    """An epoch of more lanes than the list of its slots holds: the
    list is given up until the barrier, which ranks the store."""
    monkeypatch.setattr(top_n_plain, "TOUCHED_MAX", 8 * CHUNK)
    ex, ref = _executor("k1"), _executor("k1")
    stream = _Stream(11, groups=20, deletes=0.0, updates=0.0)
    for chunks, full in ((9, 1), (2, 0), (1, 0)):
        for _ in range(chunks):
            c = stream.chunk(CHUNK // 2)
            ex.apply(c)
            ref.apply(c)
        got, pull = _barrier(ex)
        assert pull["full_rank"] == full
        assert got == _store_wide(ref)
    _chains_say_the_top_k(ex)


def test_the_sizes_of_the_candidates():
    """Twice the epoch's lanes on the emission lattice; None where the
    candidates' sorts would cover the store, or the list is shorter."""
    cap = 1 << 13
    assert candidate_lanes(1, cap, 1) == FLOOR
    assert candidate_lanes(32, cap, 1) == FLOOR
    assert candidate_lanes(33, cap, 1) == 4 * FLOOR
    assert candidate_lanes(CHUNK, cap, 1) == 4 * FLOOR
    assert candidate_lanes(CHUNK + 1, cap, 1) == 16 * FLOOR
    assert candidate_lanes(CHUNK + 1, cap, 7) is None  # 1,024 x 8 lanes
    assert candidate_lanes(4 * CHUNK, cap, 1) == 16 * FLOOR
    assert candidate_lanes(4 * CHUNK + 1, cap, 1) is None
    assert emission_lanes(2 * (4 * CHUNK + 1), cap) == 64 * FLOOR


# -- what a barrier hands on is cut to its delta ------------------------------


@pytest.fixture
def ladder(monkeypatch):
    """Declared emission sizes of 64 / 256 / 1,024 lanes: what epochs
    of one, two and eight of these tests' chunks gather into."""
    monkeypatch.setattr(
        RetractableGroupTopNExecutor, "_WARM_EPOCHS", (1, 2 * CHUNK, 8 * CHUNK)
    )


def _uncut(shape):
    """The executor as it was before a chunk followed its delta: every
    chunk goes on at the size the gathers ran at."""
    ref = _executor(shape)
    ref._cut = lambda chunk, rows: chunk
    return ref


def _new_firsts(groups, v):
    """A row that outranks all that stand, in each of ``groups`` groups
    (``v`` differs by epoch), and a second chunk that holds no row: an
    epoch of two chunks, whose gathers run at 256 lanes."""
    rows = [(g, 1_000 * v + g, v, 0) for g in range(groups)]
    return [_chunk(rows, [int(Op.INSERT)] * groups), _chunk([], [])]


def _diff_of(table_id):
    (sp,) = [
        sp for sp in TRACER.spans()
        if sp.name == "topn.diff" and sp.args["table_id"] == table_id
    ]
    return sp.args


def _declared_size(ex, rows, gathered):
    return next((s for s in ex.emission_sizes() if s >= rows), gathered)


@pytest.mark.parametrize("groups", (FLOOR // 2, FLOOR // 2 + 1, FLOOR, FLOOR + 1))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_a_barriers_chunks_are_as_wide_as_their_deltas(shape, groups, ladder):
    """Each of the two chunks goes on at the smallest declared size
    that holds ITS rows — ``FLOOR`` rows take ``FLOOR`` lanes, one more
    the next size, the size the gathers ran at — with the rows, ops and
    order of the chunk as gathered, and the lanes ``emitted`` /
    ``erank`` / ``shadow`` after it as they were."""
    ex, ref = _executor(shape), _uncut(shape)
    assert ex.emission_sizes() == (FLOOR, 4 * FLOOR, 16 * FLOOR)
    for v in (1, 2):  # the second epoch's firsts displace the first's
        for c in _new_firsts(groups, v):
            ex.apply(c)
            ref.apply(c)
        TRACER.clear()
        got = ex.on_barrier(None)
        diff = _diff_of(ex.table_id)
        want = ref.on_barrier(None)
        assert {c.capacity for c in want} == {4 * FLOOR}
        assert _rows_of(got) == _rows_of(want)
        rows = [r for r in (diff["retract_rows"], diff["insert_rows"]) if r]
        assert [int(c.valid.sum()) for c in got] == rows
        assert [c.capacity for c in got] == [
            _declared_size(ex, r, 4 * FLOOR) for r in rows
        ]
        _same_state(ex, ref)
    # at k = 1 each delta of the second epoch is the groups': FLOOR
    # fits the floor, FLOOR + 1 does not (k = 3 has room for both rows
    # and retracts nothing; k = 10 hands the old first on again, second)
    assert len(got) == (1 if shape == "two_keys" else 2)
    if shape == "k1":
        assert rows == [groups, groups]
        assert {c.capacity for c in got} == {
            FLOOR if groups <= FLOOR else 4 * FLOOR
        }


def test_a_numbered_delta_of_two_rounds_is_cut_in_its_last_round_only(ladder):
    """k = 10, thirty full groups, a new first in each: 300 rows each
    way where the gathers run at 256 lanes, so two rounds a side. The
    rounds are computed as they were (each from the ``256 x r``-th row
    of the same ranking); the full round goes on whole, the last at the
    size its 44 rows take, every retraction before any insertion, and
    row for row what the uncut chunks hold."""
    ex, ref = _executor("k10_rank_col"), _uncut("k10_rank_col")
    for e in (ex, ref):
        for i in range(10):
            _standing(e, [(g, 100 * g + i, i, 0) for g in range(30)])
        for c in _new_firsts(30, 50):
            e.apply(c)
    TRACER.clear()
    got = ex.on_barrier(None)
    diff, pull = _diff_of(ex.table_id), _pull_of(ex.table_id)
    want = ref.on_barrier(None)
    assert diff["retract_rows"] == diff["insert_rows"] == 300
    assert diff["rounds"] == pull["rounds"] == 2
    assert [c.capacity for c in want] == [4 * FLOOR] * 4
    assert [c.capacity for c in got] == [4 * FLOOR, FLOOR] * 2
    assert [int(c.valid.sum()) for c in got] == [256, 44] * 2
    assert _rows_of(got) == _rows_of(want)
    ops = [row[-1] for rows in _rows_of(got) for row in rows]
    assert ops == [int(Op.DELETE)] * 300 + [int(Op.INSERT)] * 300
    assert diff["emit_lanes"] == 2 * (4 * FLOOR + FLOOR)
    _same_state(ex, ref)
    _chains_say_the_top_k(ex)


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_the_lanes_and_rows_of_a_barrier_are_those_of_its_chunks(shape, ladder):
    """``topn.diff``'s ``emit_lanes`` and the counter
    ``group_topn_emitted_lanes_total{op}`` are the lanes of the chunks
    handed on, as ``retract_rows`` / ``insert_rows`` and
    ``group_topn_emitted_rows_total{op}`` are their rows: lanes / rows
    of a barrier can be read from either."""
    ex = _executor(shape)
    stream = _Stream(13, groups=40)
    lanes = REGISTRY.counter("group_topn_emitted_lanes_total")
    rows = REGISTRY.counter("group_topn_emitted_rows_total")
    for epoch in range(4):
        before = {
            op: (lanes.get(table_id=ex.table_id, op=op),
                 rows.get(table_id=ex.table_id, op=op))
            for op in ("retract", "insert")
        }
        for _ in range(2):
            ex.apply(stream.chunk(int(stream.rng.integers(20, CHUNK))))
        TRACER.clear()
        got = ex.on_barrier(None)
        diff = _diff_of(ex.table_id)
        by_op = {"retract": [], "insert": []}
        for c in got:
            (op,) = set(np.asarray(c.ops).tolist())
            by_op["retract" if op == int(Op.DELETE) else "insert"].append(c)
        for op, chunks in by_op.items():
            assert lanes.get(table_id=ex.table_id, op=op) - before[op][0] == sum(
                c.capacity for c in chunks
            )
            assert rows.get(table_id=ex.table_id, op=op) - before[op][1] == sum(
                int(c.valid.sum()) for c in chunks
            ) == diff[f"{op}_rows"]
        assert diff["emit_lanes"] == sum(c.capacity for c in got)
        assert diff["emit_lanes"] <= 2 * diff["rounds"] * 4 * FLOOR


def test_the_cut_is_compiled_with_the_sizes_it_cuts(ladder):
    """``warm_emissions`` runs the cut from every declared size to every
    smaller one, so that a barrier whose delta is small, and one whose
    delta is not, compile nothing — the first of either kind a stream
    meets included."""
    from risingwave_tpu.array.chunk import _leading_lanes
    from risingwave_tpu.executors.top_n_plain import _diff_gather, _rank

    ex = _executor("k1")
    assert [c.capacity for c in ex.warm_emissions()] == [
        FLOOR, 4 * FLOOR, 16 * FLOOR
    ]
    compiled = [f._cache_size() for f in (_leading_lanes, _rank, _diff_gather)]
    for groups in (3, FLOOR, FLOOR + 1):  # each epoch outranks the last
        for v in (1, 2):
            for c in _new_firsts(groups, 10 * groups + v):
                ex.apply(c)
            got = ex.on_barrier(None)
        assert {c.capacity for c in got} == {
            FLOOR if groups <= FLOOR else 4 * FLOOR
        }
    assert [
        f._cache_size() for f in (_leading_lanes, _rank, _diff_gather)
    ] == compiled
