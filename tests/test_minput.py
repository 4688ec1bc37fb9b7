"""Materialized-input MIN/MAX (ops/minput.py; VERDICT r2 #5) — exact
retractable extremes vs a python multiset oracle, incl. the case that
used to raise at the barrier (reference: aggregation/minput.rs)."""

from collections import Counter, defaultdict

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.hash_agg import HashAggExecutor
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.types import Op

DT = {"g": jnp.int64, "v": jnp.int64}
CAP = 32


def _chunk(rows):
    g = np.array([r[0] for r in rows], np.int64)
    v = np.array([r[1] for r in rows], np.int64)
    ops = np.array([r[2] for r in rows], np.int32)
    return StreamChunk.from_numpy({"g": g, "v": v}, CAP, ops=ops)


def _replay(snap, chunks, keys, outs):
    for c in chunks:
        d = c.to_numpy(with_ops=True)
        for i in range(len(d["__op__"])):
            k = tuple(int(d[n][i]) for n in keys)
            if d["__op__"][i] in (Op.DELETE, Op.UPDATE_DELETE):
                snap.pop(k, None)
            else:
                row = []
                for n in outs:
                    nl = d.get(n + "__isnull")
                    row.append(
                        None if nl is not None and nl[i] else int(d[n][i])
                    )
                snap[k] = tuple(row)
    return snap


def _mk(materialized=True, **kw):
    return HashAggExecutor(
        group_keys=("g",),
        calls=(
            AggCall("count_star", None, "cnt"),
            AggCall("min", "v", "mn", materialized=materialized),
            AggCall("max", "v", "mx", materialized=materialized),
        ),
        schema_dtypes=DT,
        capacity=64,
        out_cap=64,
        **kw,
    )


def _oracle(mult):
    out = {}
    for g, vals in mult.items():
        live = [v for v, c in vals.items() if c > 0]
        n = sum(c for c in vals.values() if c > 0)
        if n:
            out[(g,)] = (n, min(live), max(live))
    return out


def test_retract_current_extreme_falls_back():
    """Delete the max -> flush emits the next-best value (used to raise
    'requires materialized-input extremes')."""
    ex = _mk()
    snap = {}
    _replay(snap, ex.apply(_chunk([(1, 10, Op.INSERT), (1, 30, Op.INSERT),
                                   (1, 20, Op.INSERT)])), ("g",), ("cnt", "mn", "mx"))
    _replay(snap, ex.on_barrier(None), ("g",), ("cnt", "mn", "mx"))
    assert snap == {(1,): (3, 10, 30)}
    _replay(snap, ex.apply(_chunk([(1, 30, Op.DELETE)])), ("g",), ("cnt", "mn", "mx"))
    _replay(snap, ex.on_barrier(None), ("g",), ("cnt", "mn", "mx"))
    assert snap == {(1,): (2, 10, 20)}
    _replay(snap, ex.apply(_chunk([(1, 10, Op.DELETE), (1, 20, Op.DELETE)])),
            ("g",), ("cnt", "mn", "mx"))
    _replay(snap, ex.on_barrier(None), ("g",), ("cnt", "mn", "mx"))
    assert snap == {}


@pytest.mark.parametrize("mode", ["chunk", "stacked"])
def test_random_stream_matches_oracle(mode):
    rng = np.random.default_rng(11)
    ex = _mk()
    mult = defaultdict(Counter)
    snap = {}
    for _ in range(25):
        rows = []
        for _ in range(int(rng.integers(1, 12))):
            g = int(rng.integers(0, 6))
            live = [
                (vv, c) for vv, c in mult[g].items() if c > 0
            ]
            if live and rng.random() < 0.4:
                vv = live[int(rng.integers(len(live)))][0]
                rows.append((g, vv, Op.DELETE))
                mult[g][vv] -= 1
            else:
                vv = int(rng.integers(0, 15))
                rows.append((g, vv, Op.INSERT))
                mult[g][vv] += 1
        if mode == "chunk":
            outs = ex.apply(_chunk(rows))
        else:
            from risingwave_tpu.array.chunk import stack_chunks

            outs = ex.apply_stacked(stack_chunks([_chunk(rows)]))
        _replay(snap, outs, ("g",), ("cnt", "mn", "mx"))
        _replay(snap, ex.on_barrier(None), ("g",), ("cnt", "mn", "mx"))
    assert snap == _oracle(mult)


def test_minput_checkpoint_roundtrip():
    from risingwave_tpu.storage.object_store import MemObjectStore
    from risingwave_tpu.storage.state_table import CheckpointManager

    store = MemObjectStore()
    mgr = CheckpointManager(store)
    ex = _mk(table_id="mi1")
    snap = {}
    _replay(snap, ex.apply(_chunk([(1, 10, Op.INSERT), (1, 30, Op.INSERT),
                                   (2, 5, Op.INSERT)])), ("g",), ("cnt", "mn", "mx"))
    _replay(snap, ex.on_barrier(None), ("g",), ("cnt", "mn", "mx"))
    mgr.commit_epoch(1 << 16, [ex])

    ex2 = _mk(table_id="mi1")
    CheckpointManager(store).recover([ex2])
    # retracting the max AFTER recovery must fall back to 10 — only
    # possible if the multiset state survived the checkpoint
    _replay(snap, ex2.apply(_chunk([(1, 30, Op.DELETE)])), ("g",), ("cnt", "mn", "mx"))
    _replay(snap, ex2.on_barrier(None), ("g",), ("cnt", "mn", "mx"))
    assert snap[(1,)] == (1, 10, 10)
    assert snap[(2,)] == (1, 5, 5)


def test_minput_overflow_and_inconsistency_latch():
    ex = HashAggExecutor(
        group_keys=("g",),
        calls=(AggCall("max", "v", "mx", materialized=True),),
        schema_dtypes=DT,
        capacity=64,
        out_cap=64,
        minput_k=4,
    )
    # 5 distinct values > K=4 latches overflow
    ex.apply(_chunk([(1, v, Op.INSERT) for v in range(5)]))
    with pytest.raises(RuntimeError, match="minput_k|retracted"):
        ex.on_barrier(None)
        ex.finish_barrier()

    ex2 = HashAggExecutor(
        group_keys=("g",),
        calls=(AggCall("max", "v", "mx", materialized=True),),
        schema_dtypes=DT,
        capacity=64,
        out_cap=64,
    )
    ex2.apply(_chunk([(1, 7, Op.DELETE)]))  # never inserted
    with pytest.raises(RuntimeError):
        ex2.on_barrier(None)
        ex2.finish_barrier()


def test_minput_survives_rehash():
    ex = HashAggExecutor(
        group_keys=("g",),
        calls=(AggCall("min", "v", "mn", materialized=True),),
        schema_dtypes=DT,
        capacity=8,  # tiny: force growth
        out_cap=256,
        minput_k=8,
    )
    snap = {}
    rows = [(g, g * 10 + j, Op.INSERT) for g in range(10) for j in range(2)]
    for i in range(0, len(rows), 4):
        _replay(snap, ex.apply(_chunk(rows[i : i + 4])), ("g",), ("mn",))
    _replay(snap, ex.on_barrier(None), ("g",), ("mn",))
    assert ex.table.capacity > 8
    # retract each group's current min; falls back to the +1 value
    for g in range(10):
        _replay(snap, ex.apply(_chunk([(g, g * 10, Op.DELETE)])), ("g",), ("mn",))
    _replay(snap, ex.on_barrier(None), ("g",), ("mn",))
    assert snap == {(g,): (g * 10 + 1,) for g in range(10)}
