"""Grouped-agg kernel tests vs a pandas/numpy oracle.

Mirrors the reference's executor-test discipline (hash_agg tests,
src/stream/src/executor/hash_agg.rs tests + test_utils.rs): feed chunks,
flush at barriers, and check the emitted delta stream reconstructs the
oracle's groupby result.
"""

import jax.numpy as jnp
import numpy as np

from risingwave_tpu.ops import agg as agg_mod
from risingwave_tpu.ops import hash_table as ht
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.types import Op

CALLS = (
    AggCall("count_star", None, "cnt"),
    AggCall("sum", "v", "total"),
    AggCall("min", "v", "lo"),
    AggCall("max", "v", "hi"),
)


def _setup(cap=256):
    table = ht.HashTable.create(cap, (jnp.int64,))
    state = agg_mod.create_state(cap, CALLS, {"v": jnp.int64})
    return table, state


def _apply(table, state, keys, vals, signs=None, nulls=None):
    n = len(keys)
    valid = jnp.ones(n, jnp.bool_)
    table, slots, _, _ = ht.lookup_or_insert(
        table, (jnp.asarray(keys, jnp.int64),), valid
    )
    table = ht.set_live(table, slots, jnp.ones(n, jnp.bool_))
    s = jnp.asarray(signs if signs is not None else np.ones(n), jnp.int32)
    nu = {"v": jnp.asarray(nulls, jnp.bool_)} if nulls is not None else {}
    state = agg_mod.apply(
        state, CALLS, slots, s, {"v": jnp.asarray(vals, jnp.int64)}, nu
    )
    return table, state


def _flush_to_host(state, table, out_cap=64):
    state, delta = agg_mod.flush(state, table.keys, out_cap)
    assert not bool(delta["overflow"])
    v = np.asarray(delta["valid"])
    rows = {
        "op": np.asarray(delta["ops"])[v],
        "key": np.asarray(delta["key0"])[v],
    }
    for name in ("cnt", "total", "lo", "hi"):
        rows[name] = np.asarray(delta[name])[v]
    return state, rows


def _replay(snapshot, rows):
    """Apply a delta to a dict snapshot {key: (cnt,total,lo,hi)}."""
    for i in range(len(rows["op"])):
        op, k = rows["op"][i], rows["key"][i]
        vals = tuple(rows[n][i] for n in ("cnt", "total", "lo", "hi"))
        if op in (Op.INSERT, Op.UPDATE_INSERT):
            snapshot[k] = vals
        else:
            assert k in snapshot, "retraction for unknown group"
            del snapshot[k]
    return snapshot


def test_basic_groupby_oracle(rng):
    table, state = _setup()
    keys = rng.integers(0, 20, 300).astype(np.int64)
    vals = rng.integers(-50, 50, 300).astype(np.int64)
    table, state = _apply(table, state, keys, vals)
    state, rows = _flush_to_host(state, table)
    snap = _replay({}, rows)

    import pandas as pd

    df = pd.DataFrame({"k": keys, "v": vals})
    oracle = df.groupby("k")["v"].agg(["count", "sum", "min", "max"])
    assert set(snap) == set(oracle.index)
    for k, (cnt, total, lo, hi) in snap.items():
        row = oracle.loc[k]
        assert cnt == row["count"] and total == row["sum"]
        assert lo == row["min"] and hi == row["max"]


def test_incremental_updates_across_barriers(rng):
    table, state = _setup()
    snap = {}
    all_k, all_v = [], []
    for epoch in range(5):
        keys = rng.integers(0, 10, 50).astype(np.int64)
        vals = rng.integers(0, 100, 50).astype(np.int64)
        all_k.append(keys)
        all_v.append(vals)
        table, state = _apply(table, state, keys, vals)
        state, rows = _flush_to_host(state, table)
        snap = _replay(snap, rows)

    import pandas as pd

    df = pd.DataFrame({"k": np.concatenate(all_k), "v": np.concatenate(all_v)})
    oracle = df.groupby("k")["v"].agg(["count", "sum", "min", "max"])
    assert set(snap) == set(oracle.index)
    for k, (cnt, total, lo, hi) in snap.items():
        row = oracle.loc[k]
        assert (cnt, total, lo, hi) == (
            row["count"],
            row["sum"],
            row["min"],
            row["max"],
        )


def test_retraction_sum_count():
    table, state = _setup()
    # insert 3 rows for key 7, then retract one
    table, state = _apply(table, state, [7, 7, 7], [10, 20, 30])
    state, rows = _flush_to_host(state, table)
    snap = _replay({}, rows)
    assert snap[7][:2] == (3, 60)
    calls_noext = (AggCall("count_star", None, "cnt"), AggCall("sum", "v", "total"))
    # retraction with only sum/count calls (min/max would flag)
    table2 = ht.HashTable.create(256, (jnp.int64,))
    state2 = agg_mod.create_state(256, calls_noext, {"v": jnp.int64})
    v = jnp.ones(3, jnp.bool_)
    table2, slots, _, _ = ht.lookup_or_insert(
        table2, (jnp.asarray([7, 7, 7], jnp.int64),), v
    )
    state2 = agg_mod.apply(
        state2, calls_noext, slots, jnp.asarray([1, 1, 1], jnp.int32),
        {"v": jnp.asarray([10, 20, 30], jnp.int64)}, {},
    )
    state2 = agg_mod.apply(
        state2, calls_noext, slots[:1], jnp.asarray([-1], jnp.int32),
        {"v": jnp.asarray([10], jnp.int64)}, {},
    )
    state2, delta = agg_mod.flush(state2, table2.keys, 8)
    val = np.asarray(delta["valid"])
    assert np.asarray(delta["cnt"])[val][-1] == 2
    assert np.asarray(delta["total"])[val][-1] == 50
    assert not bool(state2.minmax_retracted)


def test_minmax_retraction_flagged():
    table, state = _setup()
    table, state = _apply(table, state, [5], [10])
    table, state = _apply(table, state, [5], [10], signs=[-1])
    assert bool(state.minmax_retracted)


def test_group_death_emits_delete():
    calls = (AggCall("count_star", None, "cnt"),)
    table = ht.HashTable.create(64, (jnp.int64,))
    state = agg_mod.create_state(64, calls, {})
    v = jnp.ones(2, jnp.bool_)
    table, slots, _, _ = ht.lookup_or_insert(
        table, (jnp.asarray([1, 2], jnp.int64),), v
    )
    state = agg_mod.apply(state, calls, slots, jnp.asarray([1, 1], jnp.int32), {}, {})
    state, delta = agg_mod.flush(state, table.keys, 8)
    ops = np.asarray(delta["ops"])[np.asarray(delta["valid"])]
    assert (ops == Op.INSERT).all()
    # retract key 1 entirely -> Delete on next flush
    state = agg_mod.apply(
        state, calls, slots[:1], jnp.asarray([-1], jnp.int32), {}, {}
    )
    state, delta = agg_mod.flush(state, table.keys, 8)
    val = np.asarray(delta["valid"])
    ops = np.asarray(delta["ops"])[val]
    keys = np.asarray(delta["key0"])[val]
    assert list(ops) == [Op.DELETE] and list(keys) == [1]


def test_null_inputs_skipped():
    calls = (
        AggCall("count_star", None, "star"),
        AggCall("count", "v", "cnt"),
        AggCall("sum", "v", "total"),
    )
    table = ht.HashTable.create(64, (jnp.int64,))
    state = agg_mod.create_state(64, calls, {"v": jnp.int64})
    keys = jnp.asarray([1, 1, 1], jnp.int64)
    table, slots, _, _ = ht.lookup_or_insert(table, (keys,), jnp.ones(3, bool))
    state = agg_mod.apply(
        state, calls, slots, jnp.ones(3, jnp.int32),
        {"v": jnp.asarray([10, 99, 20], jnp.int64)},
        {"v": jnp.asarray([False, True, False])},
    )
    state, delta = agg_mod.flush(state, table.keys, 8)
    val = np.asarray(delta["valid"])
    assert np.asarray(delta["star"])[val][-1] == 3  # COUNT(*) counts NULLs
    assert np.asarray(delta["cnt"])[val][-1] == 2  # COUNT(v) skips
    assert np.asarray(delta["total"])[val][-1] == 30  # SUM skips


def test_all_null_inputs_emit_sql_null_outputs():
    """SUM/MIN/MAX over a group with only NULL inputs is SQL NULL, not
    0 / the sentinel (code-review r2 finding #2); COUNT stays 0."""
    calls = (
        AggCall("count", "v", "cnt"),
        AggCall("sum", "v", "total"),
        AggCall("min", "v", "lo"),
    )
    table = ht.HashTable.create(64, (jnp.int64,))
    state = agg_mod.create_state(64, calls, {"v": jnp.int64})
    keys = jnp.asarray([1, 1, 2], jnp.int64)
    table, slots, _, _ = ht.lookup_or_insert(table, (keys,), jnp.ones(3, bool))
    state = agg_mod.apply(
        state, calls, slots, jnp.ones(3, jnp.int32),
        {"v": jnp.asarray([10, 99, 20], jnp.int64)},
        {"v": jnp.asarray([True, True, False])},  # key 1: all-NULL inputs
    )
    state, delta = agg_mod.flush(state, table.keys, 8)
    val = np.asarray(delta["valid"])
    k = np.asarray(delta["key0"])[val]
    res = {
        kk: (c, t, tn, lo, ln)
        for kk, c, t, tn, lo, ln in zip(
            k,
            np.asarray(delta["cnt"])[val],
            np.asarray(delta["total"])[val],
            np.asarray(delta["total__isnull"])[val],
            np.asarray(delta["lo"])[val],
            np.asarray(delta["lo__isnull"])[val],
        )
    }
    assert res[1][0] == 0  # COUNT(v) = 0, not NULL
    assert res[1][2] and res[1][4]  # SUM / MIN are NULL
    assert res[2] == (1, 20, False, 20, False)
    # retraction of the only non-null input turns SUM back to NULL
    state = agg_mod.apply(
        state, calls, slots[2:], jnp.asarray([-1], jnp.int32),
        {"v": jnp.asarray([20], jnp.int64)},
        {"v": jnp.asarray([False])},
    )
    # group 2 still live? row_count 0 -> dead; add a NULL row to keep it
    state = agg_mod.apply(
        state, calls, slots[2:], jnp.asarray([1], jnp.int32),
        {"v": jnp.asarray([0], jnp.int64)},
        {"v": jnp.asarray([True])},
    )
    state, delta = agg_mod.flush(state, table.keys, 8)
    val = np.asarray(delta["valid"])
    k = np.asarray(delta["key0"])[val]
    ops = np.asarray(delta["ops"])[val]
    keep = ops != Op.UPDATE_DELETE
    res2 = dict(zip(k[keep], np.asarray(delta["total__isnull"])[val][keep]))
    assert res2[2]  # SUM(v) for key 2 is NULL again


def test_delete_groups_resets_extremes():
    table, state = _setup()
    table, state = _apply(table, state, [3], [42])
    state, _ = agg_mod.flush(state, table.keys, 8)
    slots, _ = ht.lookup(table, (jnp.asarray([3], jnp.int64),), jnp.ones(1, bool))
    state = agg_mod.delete_groups(state, CALLS, slots)
    state, delta = agg_mod.flush(state, table.keys, 8)
    val = np.asarray(delta["valid"])
    assert list(np.asarray(delta["ops"])[val]) == [Op.DELETE]
    # re-insert into the same slot: min must restart from the sentinel
    table, state = _apply(table, state, [3], [100])
    state, delta = agg_mod.flush(state, table.keys, 8)
    val = np.asarray(delta["valid"])
    assert np.asarray(delta["lo"])[val][-1] == 100
    assert np.asarray(delta["hi"])[val][-1] == 100


def test_float_minmax_nan_total_order():
    # ordered-float totality: NaN is the single LARGEST value, so
    # MIN([NaN, 1.0]) == 1.0 and MAX([NaN, 1.0]) is NaN; an all-NaN
    # group yields NaN for both. (Raw float scatter-min would let NaN
    # poison MIN forever.)
    calls = (AggCall("min", "v", "lo"), AggCall("max", "v", "hi"))
    meta = agg_mod.float_extreme_meta(calls, {"v": jnp.float64})
    table = ht.HashTable.create(64, (jnp.int64,))
    state = agg_mod.create_state(64, calls, {"v": jnp.float64})
    keys = jnp.asarray([1, 1, 2, 2, 3], jnp.int64)
    vals = jnp.asarray([np.nan, 1.0, -0.0, 2.5, np.nan], jnp.float64)
    table, slots, _, _ = ht.lookup_or_insert(table, (keys,), jnp.ones(5, bool))
    state = agg_mod.apply(
        state, calls, slots, jnp.ones(5, jnp.int32), {"v": vals}, {}
    )
    state, delta = agg_mod.flush(state, table.keys, 8, float_extremes=meta)
    v = np.asarray(delta["valid"])
    k = np.asarray(delta["key0"])[v]
    lo = np.asarray(delta["lo"])[v]
    hi = np.asarray(delta["hi"])[v]
    res = {kk: (l, h) for kk, l, h in zip(k, lo, hi)}
    assert res[1][0] == 1.0 and np.isnan(res[1][1])
    assert res[2] == (0.0, 2.5)
    assert np.isnan(res[3][0]) and np.isnan(res[3][1])


def test_flush_overflow_loops():
    calls = (AggCall("count_star", None, "cnt"),)
    table = ht.HashTable.create(256, (jnp.int64,))
    state = agg_mod.create_state(256, calls, {})
    keys = jnp.asarray(np.arange(40, dtype=np.int64))
    table, slots, _, _ = ht.lookup_or_insert(table, (keys,), jnp.ones(40, bool))
    state = agg_mod.apply(state, calls, slots, jnp.ones(40, jnp.int32), {}, {})
    seen = set()
    for _ in range(10):
        state, delta = agg_mod.flush(state, table.keys, 16)
        val = np.asarray(delta["valid"])
        seen |= set(np.asarray(delta["key0"])[val].tolist())
        if not bool(delta["overflow"]):
            break
    assert seen == set(range(40))


def test_apply_stacked_matches_per_chunk(rng):
    """lax.scan batch path must produce bit-identical state to the
    per-chunk path (same kernels, one dispatch)."""
    import functools

    from risingwave_tpu.array.chunk import StreamChunk
    from risingwave_tpu.executors import HashAggExecutor
    from risingwave_tpu.executors.hop_window import hop_step_fn
    from risingwave_tpu.array.chunk import stack_chunks

    calls = (AggCall("count_star", None, "num"),)
    dt = {"auction": jnp.int64, "window_start": jnp.int64, "date_time": jnp.int64}
    a = HashAggExecutor(("auction", "window_start"), calls, dt, capacity=1 << 12)
    b = HashAggExecutor(("auction", "window_start"), calls, dt, capacity=1 << 12)
    pre = functools.partial(
        hop_step_fn,
        ts_col="date_time",
        size_ms=10_000,
        slide_ms=2_000,
        out_start="window_start",
    )

    chunks = []
    for _ in range(6):
        cols = {
            "auction": rng.integers(0, 50, 256).astype(np.int64),
            "date_time": rng.integers(0, 40_000, 256).astype(np.int64),
        }
        chunks.append(StreamChunk.from_numpy(cols, 256))
    for c in chunks:
        a.apply(pre(c))
    b.apply_stacked(stack_chunks(chunks), pre=pre)

    def snap(ex):
        out = {}
        for ch in ex.on_barrier(None):
            d = ch.to_numpy(with_ops=True)
            for i in range(len(d["__op__"])):
                out[(int(d["auction"][i]), int(d["window_start"][i]))] = int(
                    d["num"][i]
                )
        return out

    assert snap(a) == snap(b)
