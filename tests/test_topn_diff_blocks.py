"""The Top-N's second program in blocks (``executors/top_n_plain.py``):
``_compact`` from a two-level count against the running count and the
search it replaced, ``_touched_groups`` against its scan, and both
bodies of ``_diff_gather`` against a numpy recompute of the delta, for
deltas on every side of a turn of the gathers' loops."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors import top_n_plain
from risingwave_tpu.executors.top_n_plain import (
    RetractableGroupTopNExecutor,
    _compact,
    _count_set,
    _diff_gather,
    _rank,
    _touched_groups,
)
from risingwave_tpu.types import Op

# ---------------------------------------------------------------------------
# the plain references: what the program ran before it counted in blocks
# ---------------------------------------------------------------------------


def compact_reference(mask, out_lanes, start):
    csum = jnp.cumsum(mask.astype(jnp.int32))
    n = csum[-1]
    want = jnp.arange(1, out_lanes + 1, dtype=jnp.int32) + start
    pos = jnp.searchsorted(csum, want, side="left").astype(jnp.int32)
    valid = want <= n
    return jnp.where(valid, pos, 0), n, valid


def touched_groups_reference(dirty_s, seg_start):
    pos = jnp.arange(dirty_s.shape[0], dtype=jnp.int32)
    last_dirty = jax.lax.cummax(jnp.where(dirty_s, pos, -1))
    before = jnp.concatenate([jnp.full(1, -1, jnp.int32), last_dirty[:-1]])
    return jnp.sum((dirty_s & (before < seg_start)).astype(jnp.int32))


@jax.jit
def _both_compacts(mask, start):
    lanes = _count_set(mask)
    out_lanes = min(64, mask.shape[0])
    return (
        _compact(lanes, out_lanes, start) + (lanes.total,),
        compact_reference(mask, out_lanes, start),
    )


@jax.jit
def _both_touched(dirty_s, seg_start):
    return (
        _touched_groups(dirty_s, seg_start),
        touched_groups_reference(dirty_s, seg_start),
    )


# below a word of the mask, a word, below a row of counts, a row, two
# rows, and 2^16
CAPACITIES = (16, 32, 256, 4096, 8192, 1 << 16)
MASKS = ("empty", "one", "every", "sparse", "dense")


def _mask(kind, capacity):
    rng = np.random.default_rng(capacity + len(kind))
    mask = np.zeros(capacity, bool)
    if kind == "one":
        mask[rng.integers(capacity)] = True
    elif kind == "every":
        mask[:] = True
    elif kind == "sparse":
        mask[rng.permutation(capacity)[: max(2, capacity // 50)]] = True
    elif kind == "dense":  # more set lanes than the 64 asked for
        mask = rng.random(capacity) < 0.7
    return mask


@pytest.mark.parametrize("start", ("0", "5", "mid", "past"))
@pytest.mark.parametrize("kind", MASKS)
@pytest.mark.parametrize("capacity", CAPACITIES)
def test_compact_gives_what_the_running_count_and_its_search_gave(
    capacity, kind, start
):
    mask = _mask(kind, capacity)
    n = int(mask.sum())
    start = {"0": 0, "5": 5, "mid": n // 2, "past": n + 3}[start]
    (pos, valid, total), (ref_pos, ref_n, ref_valid) = _both_compacts(
        jnp.asarray(mask), jnp.asarray(start, jnp.int32)
    )
    assert int(total) == int(ref_n) == n
    np.testing.assert_array_equal(np.asarray(valid), np.asarray(ref_valid))
    np.testing.assert_array_equal(np.asarray(pos), np.asarray(ref_pos))
    # ... which are the set lanes from the ``start``-th on, in order
    want = np.flatnonzero(mask)[start : start + len(pos)]
    np.testing.assert_array_equal(np.asarray(pos)[: len(want)], want)
    assert int(np.asarray(valid).sum()) == len(want)


@pytest.mark.parametrize("kind", MASKS)
@pytest.mark.parametrize("capacity", CAPACITIES)
def test_touched_groups_counts_what_the_scan_counted(capacity, kind):
    rng = np.random.default_rng(capacity)
    dirty = _mask(kind, capacity)
    # groups of one to nine lanes, some across a word's and a row's edge
    starts = np.flatnonzero(rng.random(capacity) < 0.3)
    boundary = np.zeros(capacity, bool)
    boundary[0] = True
    boundary[starts] = True
    seg_start = np.maximum.accumulate(
        np.where(boundary, np.arange(capacity), 0)
    ).astype(np.int32)
    got, ref = _both_touched(jnp.asarray(dirty), jnp.asarray(seg_start))
    assert int(got) == int(ref) == len(set(seg_start[dirty].tolist()))


# ---------------------------------------------------------------------------
# both bodies of ``_diff_gather`` against a numpy recompute
# ---------------------------------------------------------------------------

TURN = 8  # lanes a turn of the gathers' loops, for these tests
OUT_LANES = 32
CAPACITY = 256
DTYPES = {"g": jnp.int64, "id": jnp.int64, "v": jnp.int64, "w": jnp.int32}


@pytest.fixture
def short_turns(monkeypatch):
    """The loops' block cut to ``TURN`` lanes, so that a store of 256
    lanes has deltas of exactly a turn, one more, and several."""
    monkeypatch.setattr(top_n_plain, "_GATHER_LANES", TURN)
    _diff_gather.clear_cache()
    yield
    _diff_gather.clear_cache()


def _executor(rank_col, limit):
    return RetractableGroupTopNExecutor(
        ("g",), [("v", True), ("w", False)], limit, ("id",), DTYPES,
        capacity=CAPACITY, table_id="blocks", rank_col=rank_col,
    )


def _chunk(rows, ops):
    cols = {
        n: np.asarray([r[i] for r in rows], np.dtype(DTYPES[n]))
        for i, n in enumerate(("g", "id", "v", "w"))
    }
    return StreamChunk.from_numpy(cols, 128, ops=np.asarray(ops, np.int32))


def _host(tree):
    return jax.tree.map(np.asarray, tree)


def _u64(lane, desc):
    """``_order_key_u64`` on the host, for the integer lanes used here."""
    key = lane.astype(np.int64).view(np.uint64) ^ np.uint64(1 << 63)
    return ~key if desc else key


def _delta_of(ex):
    """The barrier's delta from the executor's state as ``_rank`` finds
    it: the sorted order, both masks over it, each lane's rank now and
    as handed on, and the two counts a barrier reports of it."""
    keys = [np.asarray(k) for k in ex.table.keys]
    live = np.asarray(ex.table.live)
    rows, shadow = _host(ex.rows), _host(ex.shadow)
    dirty = np.asarray(ex.epoch_dirty)
    numbered = ex.rank_col is not None
    cap, n_group = len(live), len(ex.group_by)
    # the sorted order: group, the dead last, the order keys, the rest
    # of the stream key (np.lexsort: last key first, stable)
    by = [_u64(k, False) for k in reversed(keys[n_group:])]
    by += [_u64(rows[c], d) for c, d in reversed(ex.order)]
    by += [~live]
    by += [_u64(k, False) for k in reversed(keys[:n_group])]
    order = np.lexsort(by)
    group = np.stack([k[order] for k in keys[:n_group]])
    boundary = np.ones(cap, bool)
    boundary[1:] = (group[:, 1:] != group[:, :-1]).any(axis=0)
    pos = np.arange(cap)
    seg_start = np.maximum.accumulate(np.where(boundary, pos, 0))
    in_topk = live[order] & (pos - seg_start < ex.limit)
    differs = np.zeros(cap, bool)
    for n in rows:
        differs |= rows[n] != shadow[n]
    redo = (dirty & differs)[order]
    emitted_s = np.asarray(ex.emitted)[order]
    rank = np.where(in_topk, pos - seg_start + 1, 0)
    erank_s = np.asarray(ex.erank)[order] if numbered else None
    moved = np.zeros(cap, bool)
    if numbered:
        moved = emitted_s & in_topk & ~redo & (erank_s != rank)
    again = redo | moved
    return {
        "order": order,
        "ret_s": emitted_s & (~in_topk | again),
        "ins_s": in_topk & (~emitted_s | again),
        "rank": rank,
        "erank_s": erank_s,
        "moved": int(moved.sum()),
        "groups": len(set(seg_start[dirty[order]].tolist())),
    }


def _recompute(ex, delta, passes, start, out_lanes):
    """One round of ``delta`` over the executor's state as the round
    finds it: (emitted, erank, shadow, retract chunk, insert chunk,
    status) as numpy, the rank lane only where one is handed on."""
    order, ret_s, ins_s = delta["order"], delta["ret_s"], delta["ins_s"]
    rows, shadow = _host(ex.rows), _host(ex.shadow)
    emitted = np.asarray(ex.emitted).copy()
    numbered = ex.rank_col is not None
    erank = np.asarray(ex.erank).copy() if numbered else None
    ret_at = np.flatnonzero(ret_s)[start : start + out_lanes]
    ins_at = np.flatnonzero(ins_s)[start : start + out_lanes]

    def chunk(at, source, rank_lane):
        cols = {}
        for n, a in source.items():
            cols[n] = np.zeros(out_lanes, a.dtype)
            cols[n][: len(at)] = a[order[at]]
        if numbered:
            cols[ex.rank_col] = np.zeros(out_lanes, np.int64)
            cols[ex.rank_col][: len(at)] = rank_lane[at]
        valid = np.zeros(out_lanes, bool)
        valid[: len(at)] = True
        return cols, valid

    ret = chunk(ret_at, shadow, delta["erank_s"])
    ins = chunk(ins_at, rows, delta["rank"])
    shadow = {n: a.copy() for n, a in shadow.items()}
    if numbered:
        gone = order[ret_at[~ins_s[ret_at]]]
        emitted[gone] = False
        erank[gone] = 0
        emitted[order[ins_at]] = True
        erank[order[ins_at]] = delta["rank"][ins_at]
        renew = np.concatenate(
            [order[ret_at[ins_s[ret_at]]], order[ins_at[~ret_s[ins_at]]]]
        )
    else:
        emitted[order[ret_at]] = False
        emitted[order[ins_at]] = True
        renew = order[ins_at]
    for n in shadow:
        shadow[n][renew] = rows[n][renew]
    n_ret, n_ins = int(ret_s.sum()), int(ins_s.sum())
    block = math.gcd(TURN, out_lanes)
    covered = sum(
        -(-min(max(n - start, 0), out_lanes) // block) * block
        for n in (n_ret, n_ins)
    )
    status = [
        n_ret,
        n_ins,
        delta["groups"],
        delta["moved"] if numbered
        else int(n_ret > out_lanes or n_ins > out_lanes),
        0,
        int(ex.table.occupancy()),
        int(np.asarray(ex.table.live).sum()),
        passes,
        covered,
        0,  # the ranking is the store's: it answers
    ]
    return emitted, erank, shadow, ret, ins, status


def _run_round(ex, ranked, start, out_lanes):
    """``_diff_gather`` as the executor calls it, its results put back
    into the executor: (retract chunk, insert chunk, status)."""
    if ex.rank_col is None:
        # (no chains handed in: none comes back)
        ex.emitted, ex.shadow, ret, ins, status, _ = _diff_gather(
            ex.table, ex.rows, ex.shadow, ex.emitted, ranked, ex._dropped,
            out_lanes,
        )
    else:
        ret, ins, status = ex._round(ranked, start, out_lanes)
    return ret, ins, status


def _check_round(ex, ranked, delta, start, out_lanes, want_counts=None):
    expect = _recompute(ex, delta, int(ranked[3]), start, out_lanes)
    ret, ins, status = _run_round(ex, ranked, start, out_lanes)
    emitted, erank, shadow, want_ret, want_ins, want_status = expect
    assert np.asarray(status).tolist() == want_status
    if want_counts is not None:
        assert tuple(want_status[:2]) == want_counts
    for got, (cols, valid), op in (
        (ret, want_ret, Op.DELETE), (ins, want_ins, Op.INSERT)
    ):
        np.testing.assert_array_equal(np.asarray(got.valid), valid)
        assert sorted(got.columns) == sorted(cols)
        for n, a in cols.items():
            assert got.columns[n].dtype == a.dtype
            np.testing.assert_array_equal(np.asarray(got.columns[n]), a, n)
        assert np.asarray(got.ops).tolist() == [int(op)] * out_lanes
    np.testing.assert_array_equal(np.asarray(ex.emitted), emitted)
    for n, a in shadow.items():
        np.testing.assert_array_equal(np.asarray(ex.shadow[n]), a, n)
    if erank is not None:
        np.testing.assert_array_equal(np.asarray(ex.erank), erank)
    return want_status


def _rank_of(ex):
    return _rank(
        ex.table, ex.rows, ex.shadow, ex.emitted, ex.epoch_dirty, ex.limit,
        ex.desc, len(ex.group_by), ex.order_col, ex.erank,
        groups=ex.groups,
    )


def _standing(ex, groups):
    """``groups`` groups of one row each, handed on by a barrier."""
    rows = [(g, 1000 + g, 50 + g % 7, g % 5) for g in range(groups)]
    ex.apply(_chunk(rows, [int(Op.INSERT)] * len(rows)))
    ex.on_barrier(None)
    return rows


# (new groups, rows deleted, rows rewritten in place): with one row a
# group and a limit of one, that many insertions, retractions, both
DELTAS = {
    "nothing": (0, 0, 0),
    "a_few_rows": (3, 2, 1),
    "a_turn": (TURN, TURN, 0),
    "a_turn_and_one": (TURN + 1, 0, 0),
    "a_turn_and_one_retracted": (0, TURN + 1, 0),
    "rewritten_across_turns": (5, 0, TURN + 3),
    "the_chunks_full": (OUT_LANES - 4, OUT_LANES - 4, 4),
}


@pytest.mark.parametrize("rank_col", (None, "rn"))
@pytest.mark.parametrize("delta", sorted(DELTAS))
def test_a_delta_in_turns_is_the_recomputed_delta(
    short_turns, delta, rank_col
):
    new, deleted, rewritten = DELTAS[delta]
    ex = _executor(rank_col, limit=1)
    rows = _standing(ex, 48)
    rng = np.random.default_rng(len(delta))
    picked = rng.permutation(len(rows))
    batch, ops = [], []
    for i in picked[:deleted]:
        batch.append(rows[i])
        ops.append(int(Op.DELETE))
    for i in picked[deleted : deleted + rewritten]:
        g, id_, v, w = rows[i]
        batch.append((g, id_, v + 100, w + 1))  # its group's one row still
        ops.append(int(Op.INSERT))
    for j in range(new):
        batch.append((500 + j, 2000 + j, j % 11, j % 3))
        ops.append(int(Op.INSERT))
    # a row that changes nothing: dirty, and no part of the delta
    batch.append(rows[picked[-1]])
    ops.append(int(Op.INSERT))
    ex.apply(_chunk(batch, ops))
    _check_round(
        ex, _rank_of(ex), _delta_of(ex), 0, OUT_LANES,
        want_counts=(deleted + rewritten, new + rewritten),
    )


def test_a_numbered_delta_of_three_rounds_is_the_recomputed_delta(short_turns):
    """Every group's ranks shift under a new first row: three rows go
    and three come a group, 66 each way in chunks of 32, every round
    from the same ranking and the state the round before left."""
    ex = _executor("rn", limit=3)
    rows = [(g, 10 * g + j, 50 - j, j) for g in range(22) for j in range(3)]
    ex.apply(_chunk(rows, [int(Op.INSERT)] * len(rows)))
    ex.on_barrier(None)
    tops = [(g, 10 * g + 9, 99, 0) for g in range(22)]
    ex.apply(_chunk(tops, [int(Op.INSERT)] * len(tops)))
    ranked, delta = _rank_of(ex), _delta_of(ex)
    for r in range(3):
        status = _check_round(ex, ranked, delta, r * OUT_LANES, OUT_LANES)
        assert status[:4] == [66, 66, 22, 44]
    # the last round's two rows each way took one turn a chunk
    assert status[8] == 2 * TURN


def test_the_turns_are_counted_at_the_size_the_barrier_runs_them():
    """At the program's own block a delta of a few rows takes one turn
    a chunk, and an empty one none."""
    ex = _executor(None, limit=1)
    _standing(ex, 8)
    ex.apply(_chunk([(900, 9000, 1, 1)], [int(Op.INSERT)]))
    block = math.gcd(top_n_plain._GATHER_LANES, CAPACITY)
    _, _, status = _run_round(ex, _rank_of(ex), 0, CAPACITY)
    assert np.asarray(status).tolist()[:2] == [0, 1]
    assert int(status[8]) == block
    ex.epoch_dirty = jnp.zeros_like(ex.epoch_dirty)
    _, _, status = _run_round(ex, _rank_of(ex), 0, CAPACITY)
    assert np.asarray(status).tolist()[:2] == [0, 0]
    assert int(status[8]) == 0
