"""General (retractable) OverWindow: randomized insert/delete/update
streams vs a full-recompute oracle, maintained through the executor's
retract/re-emit diffs; checkpoint/restore parity mid-stream.

Reference: src/stream/src/executor/over_window/general.rs:49 (any
change retracts and re-emits the affected frames)."""

import pytest as _pytest

pytestmark = _pytest.mark.smoke

CAP = 64  # chunk capacity


def _mk_exec(jnp, calls, capacity=1 << 9):
    from risingwave_tpu.executors.over_window import (
        GeneralOverWindowExecutor,
    )

    return GeneralOverWindowExecutor(
        partition_by=("p",),
        order_col="o",
        pk=("id",),
        calls=calls,
        schema_dtypes={
            "id": jnp.int64,
            "p": jnp.int64,
            "o": jnp.int64,
            "x": jnp.int64,
        },
        capacity=capacity,
        nullable=("x",),
    )


def _oracle(rows, calls):
    """Full recompute: rows = {id: (p, o, x_or_None, seq)} -> set of
    emitted tuples (id, p, o, x, out1, out2, ...) with None for NULL."""
    by_part = {}
    for rid, (p, o, x, seq) in rows.items():
        by_part.setdefault(p, []).append((o, seq, rid, x))
    out = set()
    for p, items in by_part.items():
        items.sort(key=lambda it: (it[0], it[2]))  # (order, stream key)
        n = len(items)
        for i, (o, seq, rid, x) in enumerate(items):
            vals = []
            for c in calls:
                if c.kind == "row_number":
                    vals.append(i + 1)
                elif c.kind == "rank":
                    vals.append(
                        1 + sum(1 for it in items if it[0] < o)
                    )
                elif c.kind == "dense_rank":
                    vals.append(
                        1 + len({it[0] for it in items if it[0] < o})
                    )
                elif c.kind == "sum" and c.frame is None:
                    window = [
                        it[3]
                        for it in items[: i + 1]
                        if it[3] is not None
                    ]
                    vals.append(sum(window))
                elif c.kind == "min" and c.frame is None:
                    window = [
                        it[3]
                        for it in items[: i + 1]
                        if it[3] is not None
                    ]
                    vals.append(min(window) if window else None)
                elif c.kind == "sum" and c.frame is not None:
                    lo, hi = c.frame
                    window = [
                        items[j][3]
                        for j in range(max(0, i + lo), min(n, i + hi + 1))
                        if items[j][3] is not None
                    ]
                    # frame sum is NULL when no non-NULL row is in frame
                    vals.append(sum(window) if window else None)
                elif c.kind == "count" and c.frame is not None:
                    lo, hi = c.frame
                    vals.append(sum(
                        items[j][3] is not None
                        for j in range(max(0, i + lo), min(n, i + hi + 1))
                    ))
                elif c.kind == "lead":
                    j = i + c.offset
                    vals.append(items[j][3] if j < n else None)
                elif c.kind == "lag":
                    j = i - c.offset
                    vals.append(items[j][3] if j >= 0 else None)
                else:
                    raise AssertionError(c.kind)
            out.add((rid, p, o, x) + tuple(vals))
    return out


def _chunk(ops_rows, np, cap=CAP):
    from risingwave_tpu.array.chunk import StreamChunk

    cols = {
        "id": np.array([r[1] for r in ops_rows], np.int64),
        "p": np.array([r[2] for r in ops_rows], np.int64),
        "o": np.array([r[3] for r in ops_rows], np.int64),
        "x": np.array(
            [0 if r[4] is None else r[4] for r in ops_rows], np.int64
        ),
    }
    nulls = {"x": np.array([r[4] is None for r in ops_rows], bool)}
    opcodes = np.array(
        [0 if r[0] == "+" else 1 for r in ops_rows], np.int32
    )
    return StreamChunk.from_numpy(cols, cap, ops=opcodes, nulls=nulls)


def _fold(mv, outs, calls, np):
    """The handed-on deltas into the downstream MV (a set of rows)."""
    out_names = [c.output for c in calls]
    for out in outs:
        d = out.to_numpy()
        no_nulls = np.zeros(len(d["id"]), bool)
        for i in range(len(d["id"])):
            x = None if d.get("x__null", no_nulls)[i] else int(d["x"][i])
            vals = tuple(
                None if d.get(f"{nm}__null", no_nulls)[i] else int(d[nm][i])
                for nm in out_names
            )
            row = (int(d["id"][i]), int(d["p"][i]), int(d["o"][i]), x) + vals
            if int(d["__op__"][i]) == 1:  # DELETE
                assert row in mv, f"retracting absent row {row}"
                mv.remove(row)
            else:
                assert row not in mv, f"double insert {row}"
                mv.add(row)


def _drive(ex, chunks_ops, calls, mv=None, np=None, per_epoch=1):
    """Push op lists through the executor, a barrier after every
    ``per_epoch`` of them, maintaining the downstream MV from its
    retract/insert emissions. Returns the MV set."""
    mv = set() if mv is None else mv
    for at in range(0, len(chunks_ops), per_epoch):
        for ops_rows in chunks_ops[at:at + per_epoch]:
            # (nothing is handed on before the barrier: the widest step
            # holds every epoch of these tests)
            assert ex.apply(_chunk(ops_rows, np)) == []
        _fold(mv, ex.on_barrier(None), calls, np)
    return mv


def _replay(rows, chunks_ops):
    """The live {id: (p, o, x, seq)} after the chunks' ops, in order."""
    rows = dict(rows)
    for ops_rows in chunks_ops:
        for op, rid, p, o, x in ops_rows:
            if op == "+":
                rows[rid] = (p, o, x, 0)
            else:
                assert rows.pop(rid)[:3] == (p, o, x)
    return rows


def _random_stream(rng, n_chunks, rows, next_id):
    """Generate chunks of mixed +/- ops; returns (chunks, rows, next_id)
    where rows tracks the live {id: (p, o, x, seq)} set."""
    chunks = []
    seq = [0]
    for _ in range(n_chunks):
        ops_rows = []
        n = int(rng.integers(3, 20))
        for _ in range(n):
            r = rng.random()
            if r < 0.55 or not rows:
                rid = next_id
                next_id += 1
                p = int(rng.integers(0, 3))
                o = int(rng.integers(0, 40))
                x = (
                    None
                    if rng.random() < 0.15
                    else int(rng.integers(-50, 50))
                )
                ops_rows.append(("+", rid, p, o, x))
                rows[rid] = (p, o, x, seq[0])
                seq[0] += 1
            elif r < 0.85:
                rid = int(rng.choice(list(rows)))
                p, o, x, _ = rows.pop(rid)
                ops_rows.append(("-", rid, p, o, x))
            else:  # update: -old +new, same pk
                rid = int(rng.choice(list(rows)))
                p, o, x, _ = rows.pop(rid)
                ops_rows.append(("-", rid, p, o, x))
                o2 = int(rng.integers(0, 40))
                x2 = (
                    None
                    if rng.random() < 0.15
                    else int(rng.integers(-50, 50))
                )
                ops_rows.append(("+", rid, p, o2, x2))
                rows[rid] = (p, o2, x2, seq[0])
                seq[0] += 1
        chunks.append(ops_rows)
    return chunks, rows, next_id


def test_retractable_rank_and_frames_oracle():
    """Inserts/deletes/updates anywhere in the order shift ranks, sums
    and frames; the maintained MV must equal a full recompute."""
    import jax.numpy as jnp
    import numpy as np

    from risingwave_tpu.executors.over_window import WindowCall

    calls = (
        WindowCall("row_number", None, "rn"),
        WindowCall("rank", "o", "rk"),
        WindowCall("dense_rank", "o", "dr"),
        WindowCall("sum", "x", "sx"),
        WindowCall("min", "x", "mn"),
        WindowCall("sum", "x", "fs", frame=(-1, 0)),
        WindowCall("lead", "x", "ld"),
        WindowCall("lag", "x", "lg"),
    )
    ex = _mk_exec(jnp, calls)
    rng = np.random.default_rng(11)
    rows = {}
    chunks, rows, _ = _random_stream(rng, 8, rows, 0)
    mv = _drive(ex, chunks, calls, np=np)
    assert mv == _oracle(rows, calls)


def test_rank_ties_and_ooo_arrivals():
    """Ties in the order column and out-of-order arrivals (forbidden in
    the append-only executor) are exactly handled here."""
    import jax.numpy as jnp
    import numpy as np

    from risingwave_tpu.executors.over_window import WindowCall

    calls = (
        WindowCall("rank", "o", "rk"),
        WindowCall("dense_rank", "o", "dr"),
        WindowCall("row_number", None, "rn"),
    )
    ex = _mk_exec(jnp, calls)
    # descending arrival order + ties
    chunks = [
        [("+", 0, 1, 30, 5), ("+", 1, 1, 20, 6), ("+", 2, 1, 30, 7)],
        [("+", 3, 1, 10, 8), ("+", 4, 1, 20, 9)],
        [("-", 1, 1, 20, 6)],
    ]
    rows = {
        0: (1, 30, 5, 0),
        2: (1, 30, 7, 2),
        3: (1, 10, 8, 3),
        4: (1, 20, 9, 4),
    }
    mv = _drive(ex, chunks, calls, np=np)
    assert mv == _oracle(rows, calls)


def test_same_chunk_partition_move_dirties_old_partition():
    """-old/+new in ONE chunk moving a row between partitions must
    re-emit the remaining rows of the OLD partition (their row_numbers
    shift)."""
    import jax.numpy as jnp
    import numpy as np

    from risingwave_tpu.executors.over_window import WindowCall

    calls = (
        WindowCall("row_number", None, "rn"),
        WindowCall("sum", "x", "sx"),
    )
    ex = _mk_exec(jnp, calls)
    chunks = [
        [
            ("+", 0, 1, 10, 5),
            ("+", 1, 1, 20, 6),
            ("+", 2, 1, 30, 7),
        ],
        # move id=1 from partition 1 to partition 2 in one fused chunk
        [("-", 1, 1, 20, 6), ("+", 1, 2, 20, 6)],
    ]
    rows = {
        0: (1, 10, 5, 0),
        1: (2, 20, 6, 3),
        2: (1, 30, 7, 2),
    }
    mv = _drive(ex, chunks, calls, np=np)
    assert mv == _oracle(rows, calls)


def test_churn_keeps_capacity_bounded():
    """Insert+delete with ever-fresh pks must compact at rehash, not
    double capacity forever (dead slots are reclaimed)."""
    import jax.numpy as jnp
    import numpy as np

    from risingwave_tpu.executors.over_window import WindowCall

    calls = (WindowCall("row_number", None, "rn"),)
    ex = _mk_exec(jnp, calls, capacity=1 << 7)
    rid = 0
    mv = set()
    for _ in range(40):
        # insert 8 fresh rows, then delete them next chunk
        ins = [("+", rid + i, 0, i, i) for i in range(8)]
        dels = [("-", rid + i, 0, i, i) for i in range(8)]
        rid += 8
        mv = _drive(ex, [ins, dels], calls, mv=mv, np=np)
        ex.checkpoint_delta()  # flush sdirty so slots become reclaimable
    assert mv == set()
    assert ex.capacity <= 1 << 9, (
        f"arena grew to {ex.capacity} despite zero live rows"
    )


def test_checkpoint_restore_mid_stream():
    """Kill after k chunks, restore from accumulated deltas, continue:
    the MV matches an uninterrupted run AND the oracle."""
    import jax.numpy as jnp
    import numpy as np

    from risingwave_tpu.executors.over_window import WindowCall

    calls = (
        WindowCall("row_number", None, "rn"),
        WindowCall("rank", "o", "rk"),
        WindowCall("sum", "x", "sx"),
        WindowCall("lead", "x", "ld"),
    )
    rng = np.random.default_rng(23)
    rows = {}
    chunks, rows, _ = _random_stream(rng, 10, rows, 0)

    ex = _mk_exec(jnp, calls)
    store = {}  # durable KV: key tuple -> value dict

    def commit(deltas):
        for d in deltas:
            n = len(next(iter(d.key_cols.values()))) if d.key_cols else 0
            for i in range(n):
                k = tuple(int(d.key_cols[kn][i]) for kn in d.key_order)
                if d.tombstone[i]:
                    store.pop(k, None)
                else:
                    store[k] = {
                        vn: v[i] for vn, v in d.value_cols.items()
                    }

    mv = _drive(ex, chunks[:6], calls, np=np)
    commit(ex.checkpoint_delta())

    # restore into a fresh executor from the durable store
    ex2 = _mk_exec(jnp, calls)
    if store:
        keys = sorted(store)
        key_cols = {
            "k0": np.array([k[0] for k in keys], np.int64),
        }
        value_cols = {
            vn: np.array([store[k][vn] for k in keys])
            for vn in next(iter(store.values()))
        }
        ex2.restore_state("general_over", key_cols, value_cols)
    mv2 = _drive(ex2, chunks[6:], calls, mv=set(mv), np=np)
    assert mv2 == _oracle(rows, calls)


# -- one step a barrier: the epoch's chunks wait and are stepped as one -------

def _all_calls():
    from risingwave_tpu.executors.over_window import WindowCall

    return (
        WindowCall("row_number", None, "rn"),
        WindowCall("rank", "o", "rk"),
        WindowCall("sum", "x", "sx"),
        WindowCall("sum", "x", "fs", frame=(-2, 0)),
        WindowCall("count", "x", "fc", frame=(-2, 0)),
        WindowCall("lag", "x", "lg"),
    )


def _check_epochs(epochs, np, jnp):
    """Three executors over the same rows: one steps once a barrier
    over the epoch's chunks, one has a barrier behind every chunk (a
    step a chunk, which is what the parent ran), one is handed each
    epoch's rows as ONE chunk. The view after every barrier equals the
    oracle's and the chunk-a-step twin's, and the three states are the
    same state."""
    calls = _all_calls()
    ex, twin, one = (_mk_exec(jnp, calls) for _ in range(3))
    mv, twin_mv, one_mv, rows = set(), set(), set(), {}
    for chunks in epochs:
        _drive(ex, chunks, calls, mv=mv, np=np, per_epoch=len(chunks))
        _drive(twin, chunks, calls, mv=twin_mv, np=np)
        flat = [r for ops_rows in chunks for r in ops_rows]
        assert one.apply(_chunk(flat, np, cap=4 * CAP)) == []
        _fold(one_mv, one.on_barrier(None), calls, np)
        rows = _replay(rows, chunks)
        assert mv == _oracle(rows, calls)
        assert mv == twin_mv == one_mv
        assert ex.state_digest() == twin.state_digest() == one.state_digest()
        assert ex._epoch["steps"] == 0 and ex._held == []
    return mv


HAND_MADE = {
    # -old in one chunk, +new in the next: an update of one stream key
    "an_update_split_across_chunks": [
        [[("+", 0, 1, 10, 5), ("+", 1, 1, 20, 6), ("+", 2, 1, 30, 7)]],
        [[("-", 1, 1, 20, 6)], [("+", 1, 1, 20, 60)]],
        [[("-", 0, 1, 10, 5)], [("+", 3, 2, 1, 1)], [("+", 0, 1, 10, 50)]],
    ],
    # a delete, and the very row again in the next chunk: no net change
    "a_delete_and_the_row_again": [
        [[("+", 0, 1, 10, 5), ("+", 1, 1, 20, 6)], [("+", 2, 1, 30, 7)]],
        [[("-", 1, 1, 20, 6)], [("+", 1, 1, 20, 6)]],
        [[("-", 2, 1, 30, 7), ("-", 0, 1, 10, 5)], [("+", 2, 1, 30, 7)],
         [("-", 2, 1, 30, 7)], [("+", 2, 1, 30, 8)]],
    ],
    # a row leaves a partition in one chunk and joins another in the
    # next: the rows it left re-number, and a second move in the same
    # epoch passes through a partition it never shows in
    "a_partition_move_across_chunks": [
        [[("+", 0, 1, 10, 5), ("+", 1, 1, 20, 6), ("+", 2, 1, 30, 7),
          ("+", 3, 2, 5, 1)]],
        [[("-", 1, 1, 20, 6)], [("+", 1, 2, 20, 6)]],
        [[("-", 0, 1, 10, 5)], [("+", 0, 2, 10, 5)], [("-", 0, 2, 10, 5)],
         [("+", 0, 3, 10, 5)]],
    ],
    # the order column moves a row inside its neighbours' frames
    "an_order_move_inside_a_frame": [
        [[("+", i, 1, 10 * i, i + 1) for i in range(6)]],
        [[("-", 4, 1, 40, 5)], [("+", 4, 1, 15, 5)]],
        [[("-", 0, 1, 0, 1)], [("+", 0, 1, 35, 1), ("-", 2, 1, 20, 3)],
         [("+", 2, 1, 36, 3)]],
    ],
    # NULL inputs: they count for nothing in a frame, a frame of
    # nothing else is NULL, and a NULL can become a value
    "null_inputs": [
        [[("+", 0, 1, 10, None), ("+", 1, 1, 20, None)],
         [("+", 2, 1, 30, 7), ("+", 3, 2, 1, None)]],
        [[("-", 1, 1, 20, None)], [("+", 1, 1, 20, 4)]],
        [[("-", 2, 1, 30, 7)], [("+", 2, 1, 30, None)],
         [("-", 3, 2, 1, None), ("+", 3, 2, 1, 9)]],
    ],
}


@_pytest.mark.parametrize("case", sorted(HAND_MADE))
def test_hand_made_epochs_of_several_chunks(case):
    import jax.numpy as jnp
    import numpy as np

    assert _check_epochs(HAND_MADE[case], np, jnp)


@_pytest.mark.parametrize(
    "seed,per_epoch", [(3, 2), (5, 3), (7, 4), (2147483999, 5)]
)
def test_seeded_epochs_of_several_chunks(seed, per_epoch):
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(seed)
    chunks, rows, _ = _random_stream(rng, 3 * per_epoch, {}, 0)
    epochs = [
        chunks[at:at + per_epoch] for at in range(0, len(chunks), per_epoch)
    ]
    mv = _check_epochs(epochs, np, jnp)
    assert {r[0] for r in mv} == set(rows)


def test_checkpoint_restore_between_epochs_of_several_chunks():
    """Nothing kept crosses a barrier: a restore from the deltas staged
    at barriers continues to the state an uninterrupted twin reaches."""
    import jax.numpy as jnp
    import numpy as np

    calls = _all_calls()
    rng = np.random.default_rng(29)
    chunks, rows, _ = _random_stream(rng, 12, {}, 0)
    ex, twin = _mk_exec(jnp, calls), _mk_exec(jnp, calls)
    store = {}
    mv = _drive(ex, chunks[:6], calls, np=np, per_epoch=3)
    for d in ex.checkpoint_delta():
        for i in range(len(d.key_cols["k0"])):
            k = int(d.key_cols["k0"][i])
            if d.tombstone[i]:
                store.pop(k, None)
            else:
                store[k] = {vn: v[i] for vn, v in d.value_cols.items()}
    assert ex._held == []
    keys = sorted(store)
    ex2 = _mk_exec(jnp, calls)
    ex2.restore_state(
        "general_over", {"k0": np.array(keys, np.int64)},
        {vn: np.array([store[k][vn] for k in keys])
         for vn in next(iter(store.values()))},
    )
    assert ex2.state_digest() == ex.state_digest()
    mv2 = _drive(ex2, chunks[6:], calls, mv=set(mv), np=np, per_epoch=3)
    _drive(twin, chunks, calls, np=np, per_epoch=3)
    assert mv2 == _oracle(rows, calls)
    assert ex2.state_digest() == twin.state_digest()


def _steps(table_id):
    from risingwave_tpu.metrics import REGISTRY

    return sum(
        v for k, v in REGISTRY.counter("over_window_steps_total")._values.items()
        if dict(k).get("table_id") == table_id
    )


def test_a_barrier_with_nothing_kept_runs_no_step(monkeypatch):
    import jax.numpy as jnp
    import numpy as np

    from risingwave_tpu.executors import over_window
    from risingwave_tpu.trace import TRACER

    calls = _all_calls()
    ex = _mk_exec(jnp, calls)
    ex.table_id = "general_over_idle"
    _drive(ex, [[("+", 0, 1, 10, 5)]], calls, np=np)
    assert _steps(ex.table_id) == 1

    def no_program(*a, **k):
        raise AssertionError("an idle barrier ran a program")

    for name in ("_general_over_step", "_general_over_lay",
                 "_general_over_emit", "_general_over_commit"):
        monkeypatch.setattr(over_window, name, no_program)
    TRACER.clear()
    assert ex.on_barrier(None) == []
    assert _steps(ex.table_id) == 1
    assert not [
        sp for sp in TRACER.spans() if sp.name in ("over.step", "over.barrier")
    ]


def test_an_epoch_wider_than_the_widest_step_steps_early(monkeypatch):
    """The lanes kept never pass the widest declared step: the chunk
    that would is what makes ``apply`` step, and the view stands."""
    import jax.numpy as jnp
    import numpy as np

    from risingwave_tpu.executors import over_window

    monkeypatch.setattr(over_window, "_EMIT_MAX", 2 * CAP)
    calls = _all_calls()
    ex = _mk_exec(jnp, calls, capacity=1 << 10)
    (limit,) = over_window.step_widths(ex.capacity)
    assert limit == 4 * CAP
    rng = np.random.default_rng(41)
    chunks, rows, _ = _random_stream(rng, 11, {}, 0)
    mv, handed = set(), []
    for ops_rows in chunks:
        outs = ex.apply(_chunk(ops_rows, np))
        handed.append(bool(outs))
        _fold(mv, outs, calls, np)
        assert ex._held_lanes() <= limit
    # four chunks fill the widest step; the fifth and the ninth step them
    assert handed == [i in (4, 8) for i in range(11)]
    assert ex._epoch["steps"] == 2 and ex._epoch["chunks"] == 8
    _fold(mv, ex.on_barrier(None), calls, np)
    assert mv == _oracle(rows, calls)
    assert ex.capacity == 1 << 10  # (the widths stood: nothing grew)
    # a chunk wider than the widest step is stepped alone, as it comes
    wide = [("+", 10_000 + i, 7, i, i) for i in range(5)]
    outs = ex.apply(_chunk(wide, np, cap=8 * CAP))
    assert outs and ex._held == []
    _fold(mv, outs, calls, np)
    assert ex.on_barrier(None) == []
    assert mv == _oracle(_replay(rows, [wide]), calls)
