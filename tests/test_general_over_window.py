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


def _drive(ex, chunks_ops, calls, mv=None, np=None):
    """Push op lists through the executor, maintaining the downstream
    MV from its retract/insert emissions. Returns the MV set."""
    from risingwave_tpu.array.chunk import StreamChunk

    mv = set() if mv is None else mv
    out_names = [c.output for c in calls]
    for ops_rows in chunks_ops:
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
        chunk = StreamChunk.from_numpy(
            cols, CAP, ops=opcodes, nulls=nulls
        )
        for out in ex.apply(chunk):
            d = out.to_numpy()
            for i in range(len(d["id"])):
                x = (
                    None
                    if d.get("x__null", np.zeros(len(d["id"]), bool))[i]
                    else int(d["x"][i])
                )
                vals = tuple(
                    None
                    if d.get(f"{nm}__null", np.zeros(len(d["id"]), bool))[
                        i
                    ]
                    else int(d[nm][i])
                    for nm in out_names
                )
                row = (
                    int(d["id"][i]),
                    int(d["p"][i]),
                    int(d["o"][i]),
                    x,
                ) + vals
                if int(d["__op__"][i]) == 1:  # DELETE
                    assert row in mv, f"retracting absent row {row}"
                    mv.remove(row)
                else:
                    assert row not in mv, f"double insert {row}"
                    mv.add(row)
        ex.on_barrier(None)
    return mv


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
