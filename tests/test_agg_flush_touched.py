"""An aggregate's flush ranges over what the epoch touched (PR 34).

``ops/agg.flush`` reads which slots are dirty from the list the step
programs keep of the slots they wrote (``note_touched``), and walks the
table only where no such list can be trusted. Held here, on the CPU:
the two ways give the same delta and leave the same state; every
fallback walks the table once and the next epoch the list again; a
flush to the end leaves nothing dirty; the compiled flush over a list
has no instruction as wide as the table but the in-place scatters; and
the span args and the counter say which way a round went."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk, stack_chunks
from risingwave_tpu.executors import hash_agg
from risingwave_tpu.executors.base import Watermark
from risingwave_tpu.executors.hash_agg import HashAggExecutor
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.ops import agg as agg_ops
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.array.lattice import TOUCHED_MAX, touched_lattice
from risingwave_tpu.storage.object_store import MemObjectStore
from risingwave_tpu.storage.state_table import CheckpointManager
from risingwave_tpu.trace import TRACER
from risingwave_tpu.types import Op

DT = {"k": jnp.int64, "v": jnp.int64, "f": jnp.float32}
LANES = 128
SUMS = (AggCall("count_star", None, "cnt"), AggCall("sum", "v", "s"))


def _chunk(rows, lanes=LANES):
    """rows of (key, v, f, op)."""
    return StreamChunk.from_numpy(
        {
            "k": np.asarray([r[0] for r in rows], np.int64),
            "v": np.asarray([r[1] for r in rows], np.int64),
            "f": np.asarray([r[2] for r in rows], np.float32),
        },
        lanes,
        ops=np.asarray([r[3] for r in rows], np.int32),
    )


def _mk(calls=SUMS, capacity=1 << 11, out_cap=1 << 9, **kw):
    return HashAggExecutor(
        ("k",), calls, DT, capacity=capacity, out_cap=out_cap, **kw
    )


def _paths(table_id):
    return [
        sp.args["path"] for sp in TRACER.spans()
        if sp.name == "agg.flush" and sp.args["table_id"] == table_id
    ]


def _replay(snap, chunks, cols):
    """Fold emitted chunks into {key: row}, as a materializer would."""
    for c in chunks:
        d = c.to_numpy(with_ops=True)
        for i, op in enumerate(d["__op__"]):
            if op in (Op.DELETE, Op.UPDATE_DELETE):
                snap.pop(int(d["k"][i]), None)
            else:
                snap[int(d["k"][i])] = tuple(int(d[n][i]) for n in cols)
    return snap


def _sums(rows):
    want = {}
    for k, v, _f, op in rows:
        sign = -1 if op == Op.DELETE else 1
        cnt, s = want.get(k, (0, 0))
        want[k] = (cnt + sign, s + sign * v)
    return {k: r for k, r in want.items() if r[0] > 0}


# -- (a) the two ways agree, array for array -----------------------------
# per epoch: keys inserted, how often each (in chunks of their own, so a
# slot is listed by several steps), and the share of the rows alive
# before the epoch that it deletes (1.0 retracts groups to zero: D rows)
_EPOCHS = {
    # every group touched once, every emission a first one (I rows)
    "once_first_emissions": (SUMS, 1 << 9, [(200, 1, 0.0), (150, 1, 0.0)]),
    # the same keys again and again: listed twice an epoch, U-/U+ after
    "twice_and_updates": (SUMS, 1 << 9, [(120, 2, 0.0), (120, 2, 0.3)]),
    # whole groups deleted (a D row each), some reborn in the same epoch
    "retracted_to_zero": (SUMS, 1 << 9, [(150, 1, 0.0), (40, 1, 1.0)]),
    # float MIN / MAX stored as order keys, NaN among the values
    "float_extremes": (
        (AggCall("min", "f", "mn"), AggCall("max", "f", "mx")),
        1 << 9, [(100, 2, 0.0), (100, 1, 0.0)],
    ),
    # more dirty groups than a round drains: two rounds, three rounds
    "two_rounds": (SUMS, 64, [(100, 1, 0.0), (110, 2, 0.2)]),
    "three_rounds": (SUMS, 64, [(150, 1, 0.0), (160, 1, 0.5)]),
    # exact MIN / MAX on materialized input under row-level retraction
    "materialized_input": (
        (AggCall("max", "v", "mx", materialized=True),
         AggCall("count_star", None, "cnt")),
        1 << 9, [(100, 2, 0.0), (60, 1, 0.4)],
    ),
}


def _by_hand(ex, listed):
    """The flush rounds of ``ex``'s state as it stands, on a copy, over
    the steps' list or over the table: the deltas and the state left."""
    state = jax.tree.map(jnp.copy, ex.state)
    kw = {}
    if listed:
        walk = ex._flush_walk()
        assert walk is not None
        kw = dict(touched=ex._touched, n_touched=ex._touched_lanes, walk=walk)
    deltas = []
    while True:
        state, delta = agg_ops.flush(
            state, ex.table.keys, ex.out_cap, ex._float_extremes, **kw
        )
        deltas.append(jax.tree.map(np.asarray, delta))
        if not deltas[-1]["overflow"]:
            return deltas, state


def _same_delta(a, b):
    """Array for array, the masked lanes too (both ways fill them from
    slot 0)."""
    assert a.keys() == b.keys()
    for lane in a:
        np.testing.assert_array_equal(a[lane], b[lane], err_msg=lane)
        assert a[lane].dtype == b[lane].dtype


def _same_state(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("stacked", [False, True], ids=["chunks", "epoch"])
@pytest.mark.parametrize("case", list(_EPOCHS))
def test_the_list_and_the_table_give_the_same_delta_and_state(case, stacked):
    calls, out_cap, epochs = _EPOCHS[case]
    rng = np.random.default_rng(len(case))
    ex = _mk(calls, out_cap=out_cap, table_id=f"t.{case}.{stacked}")
    alive, next_key, most_rounds = [], 0, 0
    for n_keys, times, deleted in epochs:
        rows = []
        if deleted == 1.0:
            gone = {r[0] for r in alive[: len(alive) // 2]}
            rows += [r[:3] + (Op.DELETE,) for r in alive if r[0] in gone]
            alive = [r for r in alive if r[0] not in gone]
        elif deleted:
            cut = rng.random(len(alive)) < deleted
            rows += [r[:3] + (Op.DELETE,) for r, c in zip(alive, cut) if c]
            alive = [r for r, c in zip(alive, cut) if not c]
        # half the keys old (an update), half new (a first emission)
        keys = list(range(next_key - n_keys // 2, next_key + n_keys // 2))
        keys = [k for k in keys if k >= 0]
        next_key += n_keys // 2
        for _ in range(times):
            for k in keys:
                f = np.nan if rng.random() < 0.05 else rng.normal()
                row = (k, int(rng.integers(-50, 50)), np.float32(f), Op.INSERT)
                rows.append(row)
                alive.append(row)
        chunks = [
            _chunk(rows[at : at + LANES]) for at in range(0, len(rows), LANES)
        ]
        if stacked:
            n = 1 << (len(chunks) - 1).bit_length()
            chunks += [chunks[0].emptied()] * (n - len(chunks))
            ex.apply_stacked(stack_chunks(chunks))
        else:
            for c in chunks:
                ex.apply(c)
        by_list, after_list = _by_hand(ex, listed=True)
        by_table, after_table = _by_hand(ex, listed=False)
        assert len(by_list) == len(by_table) == max(
            1, -(-int(np.asarray(ex.state.dirty).sum()) // out_cap)
        )
        most_rounds = max(most_rounds, len(by_list))
        for a, b in zip(by_list, by_table):
            _same_delta(a, b)
        _same_state(after_list, after_table)
        # (c) flushed to the end, nothing is dirty
        assert not np.asarray(after_list.dirty).any()
        # and the executor's own barrier goes by the list, to that state
        TRACER.clear()
        outs = ex.on_barrier(None)
        assert _paths(ex.table_id) == ["touched"] * len(by_list)
        assert sum(int(np.asarray(c.valid).sum()) for c in outs) == sum(
            int(d["valid"].sum()) for d in by_list
        )
        _same_state(ex.state, after_list)
        assert ex._touched_lanes == ex._dirty_bound == 0
    assert most_rounds == {"two_rounds": 2, "three_rounds": 3}.get(case, 1)


# -- (b) every fallback walks the table once, then the list again --------
def _durable(table_id, **kw):
    store = MemObjectStore()
    mgr = CheckpointManager(store)
    ex = _mk(table_id=table_id, **kw)
    return ex, mgr, store


def _retracting_expiry(tid):
    """A retracting watermark dirties every expired group, by no step."""
    ex = _mk(table_id=tid, window_key=("k", 0, True))
    rows = [(k, k, 0.0, Op.INSERT) for k in range(100)]
    snap = _replay({}, ex.apply(_chunk(rows)) + ex.on_barrier(None), ("cnt", "s"))
    _wm, outs = ex.on_watermark(Watermark("k", 40))
    rows += [(200, 7, 0.0, Op.INSERT)]
    ex.apply(_chunk(rows[-1:]))  # a step after it, in the same epoch
    return ex, snap, outs, [r for r in rows if r[0] >= 40]


def _restore_cold_groups(tid):
    """Evicted groups fault back in on touch (materialized input)."""
    calls = (AggCall("max", "v", "mx", materialized=True),
             AggCall("count_star", None, "cnt"))
    ex, mgr, _ = _durable(tid, calls=calls)
    ex.cold_reader = lambda keys: mgr.get_rows(tid, keys)
    rows = [(k, k, 0.0, Op.INSERT) for k in range(60)]
    snap = _replay({}, ex.apply(_chunk(rows)) + ex.on_barrier(None), ("cnt", "mx"))
    mgr.commit_epoch(1 << 16, [ex])
    assert ex.evict_cold() == 60 and len(ex._evicted) == 60
    more = [(k, 100 + k, 0.0, Op.INSERT) for k in range(20)]
    ex.apply(_chunk(more))
    assert len(ex._evicted) == 40
    return ex, snap, [], None


def _growth(tid):
    """The table is rebuilt between a step and its flush: slots move."""
    ex = _mk(table_id=tid, capacity=1 << 8)
    rows = [(k, 1, 0.0, Op.INSERT) for k in range(100)]
    ex.apply(_chunk(rows))
    more = [(k, 1, 0.0, Op.INSERT) for k in range(100, 220)]
    ex.apply(_chunk(more))
    assert ex.table.capacity > 1 << 8
    return ex, {}, [], rows + more


def _checkpoint_restore(tid):
    ex, mgr, store = _durable(tid)
    rows = [(k, k, 0.0, Op.INSERT) for k in range(80)]
    snap = _replay({}, ex.apply(_chunk(rows)) + ex.on_barrier(None), ("cnt", "s"))
    mgr.commit_epoch(1 << 16, [ex])
    ex2 = _mk(table_id=tid)
    CheckpointManager(store).recover([ex2])
    more = [(k, 5, 0.0, Op.INSERT) for k in range(70, 90)]
    ex2.apply(_chunk(more))
    return ex2, snap, [], rows + more


def _no_list(tid):
    """A program that steps the executor's state and keeps no list, as
    the fused barrier programs do, the host's bound moved by hand."""
    ex = _mk(table_id=tid)
    rows = [(k, 2, 0.0, Op.INSERT) for k in range(90)]
    ex.table, ex.state, ex.dropped = hash_agg._agg_epoch_reduced(
        ex.table, ex.state, ex.dropped, stack_chunks([_chunk(rows)]),
        ex.calls, ex.group_keys, ex.nullable, None,
    )
    ex._insert_bound += LANES
    ex._dirty_bound += LANES
    return ex, {}, [], rows


def _scan(tid):
    """The per-chunk scan of an epoch lists nothing."""
    ex = _mk(table_id=tid)
    rows = [(k, 3, 0.0, Op.INSERT) for k in range(90)]
    ex.apply_stacked(stack_chunks([_chunk(rows)]), mode="scan")
    return ex, {}, [], rows


def _cold_merge(tid):
    """Durable state merged into recreated groups at the barrier."""
    ex, mgr, _ = _durable(tid)
    ex.cold_reader = lambda keys: mgr.get_rows(tid, keys)
    rows = [(k, k, 0.0, Op.INSERT) for k in range(50)]
    snap = _replay({}, ex.apply(_chunk(rows)) + ex.on_barrier(None), ("cnt", "s"))
    mgr.commit_epoch(1 << 16, [ex])
    assert ex.evict_cold() == 50
    more = [(k, 1, 0.0, Op.INSERT) for k in range(10)]
    ex.apply(_chunk(more))
    return ex, snap, [], rows + more


def _longer_than_the_list(tid):
    """An epoch of more lanes than the longest declared list (in a
    table large enough not to grow under them)."""
    ex = _mk(table_id=tid, capacity=1 << 20)
    rows = [(k % 20, 1, 0.0, Op.INSERT) for k in range(3 * LANES)]
    for at in range(0, len(rows), LANES):
        ex.apply(_chunk(rows[at : at + LANES], lanes=TOUCHED_MAX // 2))
        assert (ex._touched_lanes is None) == (at == 2 * LANES)
    assert ex.table.capacity == 1 << 20
    return ex, {}, [], rows


_FALLBACKS = {
    f.__name__.lstrip("_"): f
    for f in (
        _retracting_expiry, _restore_cold_groups, _growth,
        _checkpoint_restore, _no_list, _scan, _cold_merge,
        _longer_than_the_list,
    )
}


@pytest.mark.parametrize("fallback", list(_FALLBACKS))
def test_a_fallback_walks_the_table_and_the_next_epoch_the_list(fallback):
    tid = f"fb.{fallback}"
    TRACER.clear()
    ex, snap, outs, rows = _FALLBACKS[fallback](tid)
    cols = tuple(c.output for c in ex.calls)
    cols = ("cnt",) + tuple(c for c in cols if c != "cnt")
    before = _paths(tid)
    snap = _replay(snap, list(outs) + ex.on_barrier(None), cols)
    assert _paths(tid)[len(before):] == ["table"], fallback
    assert not np.asarray(ex.state.dirty).any()
    if rows is not None:
        assert snap == _sums(rows)
    else:  # the materialized MAX: 20 groups came back and gained a row
        assert {k: snap[k] for k in range(20)} == {
            k: (2, 100 + k) for k in range(20)
        }
    # the next ordinary epoch goes by the list again, and is right
    TRACER.clear()
    more = [(k, 11, 0.0, Op.INSERT) for k in range(300, 330)]
    snap = _replay(snap, ex.apply(_chunk(more)) + ex.on_barrier(None), cols)
    assert _paths(tid) == ["touched"]
    assert not np.asarray(ex.state.dirty).any()
    assert all(snap[k][0] == 1 for k in range(300, 330))
    if rows is not None:
        assert snap == _sums(rows + more)


def test_the_warm_up_steps_leave_the_list_as_it_was():
    """``warm`` runs a step's program over a chunk with no valid row:
    it writes past the cursor and does not move it, so the barrier
    after it flushes what the real steps listed."""
    ex = _mk(table_id="warm.list")
    rows = [(k, 1, 0.0, Op.INSERT) for k in range(100)]
    ex.apply(_chunk(rows))
    ex.warm(_chunk(rows).emptied())
    ex.warm_stacked(stack_chunks([_chunk(rows).emptied()]), None, "reduce")
    assert ex._touched_lanes == ex._dirty_bound == LANES
    TRACER.clear()
    snap = _replay({}, ex.on_barrier(None), ("cnt", "s"))
    assert _paths("warm.list") == ["touched"] and snap == _sums(rows)


# -- (d) no instruction of the flush over a list is as wide as the table --
_CAP, _OUT, _WALK = 1 << 17, 1 << 10, 1 << 14
_INSTR = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.+?) ([a-z][a-z\-]*)\((.*?)\)(?:, |$)"
)
_SHAPE = re.compile(r"[a-z]+\d*\[([\d,]*)\]")


def _elements(shape_text):
    """Element counts of every array in a (possibly tuple) shape."""
    return [
        int(np.prod([int(d) for d in dims.split(",") if d] or [1]))
        for dims in _SHAPE.findall(shape_text)
    ]


def _wide(hlo, opcodes):
    """(opcode, name) of every instruction of ``opcodes`` whose result
    — or, for a gather, whose index operand — has ``_CAP`` elements."""
    shapes, found = {}, []
    lines = [m for m in map(_INSTR.match, hlo.splitlines()) if m]
    for m in lines:
        shapes[m.group(1)] = m.group(2)
    for m in lines:
        name, shape, op, operands = m.groups()
        if op not in opcodes:
            continue
        wide = _CAP in _elements(shape)
        named = re.findall(r"%([\w.\-]+)", operands)
        if op == "gather":  # (operand 0 is the column gathered FROM)
            named = named[-1:]
        for o in named:
            wide = wide or _CAP in _elements(shapes.get(o, ""))
        if wide:
            found.append((op, name))
    return found


@pytest.fixture(scope="module")
def flush_hlo():
    calls = (AggCall("max", "v", "mx"), AggCall("sum", "v", "s"))
    state = jax.eval_shape(lambda: agg_ops.create_state(_CAP, calls, DT))
    keys = (jax.ShapeDtypeStruct((_CAP,), jnp.int64),)
    listed = dict(
        touched=jax.ShapeDtypeStruct((TOUCHED_MAX,), jnp.int32),
        n_touched=0, walk=_WALK,
    )
    return {
        "touched": agg_ops.flush.lower(state, keys, _OUT, (), **listed)
        .compile().as_text(),
        "table": agg_ops.flush.lower(state, keys, _OUT, ()).compile().as_text(),
    }


def test_the_hlo_reader_sees_what_is_as_wide_as_the_table(flush_hlo):
    """The pin below would pass on a reader that finds nothing: the
    table path has its sort and its sum over the table's lanes."""
    wide = {op for op, _ in _wide(
        flush_hlo["table"],
        ("sort", "reduce", "reduce-window", "gather", "scatter"),
    )}
    # (the CPU backend sums a long lane as a tree of reduce-windows)
    assert {"sort", "scatter"} <= wide and wide & {"reduce", "reduce-window"}


@pytest.mark.parametrize("path,opcodes", [
    ("touched", ("sort", "gather", "reduce", "reduce-window")),
    ("table", ("gather",)),
])
def test_no_instruction_ranges_over_the_table(flush_hlo, path, opcodes):
    """Over a list: no sort, gather, cumsum or reduce as wide as the
    table (what is left at that width: gathers of ``out_cap`` slots
    FROM a column, the donated columns' in-place scatters). Over the
    table: the sort stays, and no gather keeps ``capacity`` lanes to
    hand on ``out_cap`` of them."""
    assert _wide(flush_hlo[path], opcodes) == []
    # the walk is there, at its own width
    sorts = [
        m.group(2) for m in map(_INSTR.match, flush_hlo[path].splitlines())
        if m and m.group(3) == "sort"
    ]
    width = _WALK if path == "touched" else _CAP
    assert any(width in _elements(s) for s in sorts)


# -- (e) the span args and the counter ------------------------------------
def test_the_span_and_the_counter_say_which_way_a_round_went():
    rounds = REGISTRY.counter("agg_flush_rounds_total")
    ex = _mk(table_id="ctr", out_cap=64)
    t0 = rounds.get(table_id="ctr", path="touched")
    b0 = rounds.get(table_id="ctr", path="table")
    TRACER.clear()
    ex.apply(_chunk([(k, 1, 0.0, Op.INSERT) for k in range(100)]))
    ex.on_barrier(None)  # two rounds over the list
    ex._touched_lanes = None
    ex.apply(_chunk([(k, 1, 0.0, Op.INSERT) for k in range(10)]))
    ex.on_barrier(None)  # one over the table
    ex.on_barrier(None)  # an empty barrier: the list, empty
    spans = [sp.args for sp in TRACER.spans() if sp.name == "agg.flush"]
    assert [
        (a["path"], a["walked"], a["round"], a["table_round"], a["rows"])
        for a in spans
    ] == [
        ("touched", 1 << 11, 1, 0, 128), ("touched", 1 << 11, 1, 0, 72),
        ("table", 1 << 11, 1, 1, 20), ("touched", 1 << 11, 1, 0, 0),
    ]
    assert rounds.get(table_id="ctr", path="touched") - t0 == 3
    assert rounds.get(table_id="ctr", path="table") - b0 == 1
    # the declared lengths: x4 steps, none longer than the table
    assert touched_lattice(1 << 11) == (1 << 11,)
    assert touched_lattice(1 << 15) == (1 << 14, 1 << 15)
    assert touched_lattice(1 << 23) == (1 << 14, 1 << 16, 1 << 18)
    assert ex.trace_contract()["flush_walks"] == ex.touched_sizes()
    big = _mk(capacity=1 << 16, table_id="ctr.big")
    TRACER.clear()
    big.apply(_chunk([(1, 1, 0.0, Op.INSERT)]))
    big.on_barrier(None)
    walked = [sp.args["walked"] for sp in TRACER.spans() if sp.name == "agg.flush"]
    assert walked == [1 << 14]
