"""Sharded q8 end-to-end on the virtual 8-device mesh (VERDICT r2 #2):
vnode-exchanged dedup + join fragments must match the single-chip
pipeline exactly. Plus join-type parity for the sharded join.

Reference model: every fragment runs N actors fed by a hash dispatcher
(dispatch.rs:683); here each fragment is one shard_map program (see
parallel/sharded_join.py)."""

from collections import Counter

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk, stack_chunks
from risingwave_tpu.connectors.nexmark import NexmarkConfig, NexmarkGenerator
from risingwave_tpu.executors.hash_join import HashJoinExecutor
from risingwave_tpu.executors.hop_window import _hop_step
from risingwave_tpu.executors.materialize import MaterializeExecutor
from risingwave_tpu.parallel import (
    ShardedDedup,
    ShardedHashJoin,
    flatten_stacked,
    make_mesh,
)
from risingwave_tpu.executors.dedup import AppendOnlyDedupExecutor
from risingwave_tpu.types import Op

N = 8
WINDOW_MS = 10_000


def _per_shard_chunks(n_epochs=3, events=800, cap=1024):
    """Per-shard person/auction chunk streams (one Nexmark split each),
    tumbled on the host (stateless pre-op, same as the q5 dryrun)."""
    dicts = NexmarkGenerator.make_dictionaries()
    gens = [
        NexmarkGenerator(
            NexmarkConfig(), split_index=i, split_num=N, dictionaries=dicts
        )
        for i in range(N)
    ]
    epochs = []
    for _ in range(n_epochs):
        p_shards, a_shards = [], []
        for g in gens:
            ch = g.next_chunks(events, cap)
            p = ch["person"]
            if p is None:
                p = StreamChunk.from_numpy(
                    {
                        "id": np.zeros(0, np.int64),
                        "name": np.zeros(0, np.int32),
                        "date_time": np.zeros(0, np.int64),
                    },
                    cap,
                )
            else:
                p = p.select(["id", "name", "date_time"])
            a = ch["auction"]
            if a is None:
                a = StreamChunk.from_numpy(
                    {
                        "seller": np.zeros(0, np.int64),
                        "date_time": np.zeros(0, np.int64),
                    },
                    cap,
                )
            else:
                a = a.select(["seller", "date_time"])
            p_shards.append(
                _hop_step(p, "date_time", WINDOW_MS, WINDOW_MS, "starttime")
                .select(["id", "name", "starttime"])
            )
            a_shards.append(
                _hop_step(a, "date_time", WINDOW_MS, WINDOW_MS, "astarttime")
                .select(["seller", "astarttime"])
            )
        epochs.append((stack_chunks(p_shards), p_shards, stack_chunks(a_shards), a_shards))
    return epochs


P_DT = {"id": jnp.int64, "name": jnp.int32, "starttime": jnp.int64}
A_DT = {"seller": jnp.int64, "astarttime": jnp.int64}


@pytest.mark.slow
def test_sharded_q8_matches_single_chip():
    mesh = make_mesh(N)
    sd_p = ShardedDedup(
        mesh, ("id", "name", "starttime"), P_DT, capacity=1 << 10
    )
    sd_a = ShardedDedup(mesh, ("seller", "astarttime"), A_DT, capacity=1 << 10)
    sj = ShardedHashJoin(
        mesh,
        ("id", "starttime"),
        ("seller", "astarttime"),
        P_DT,
        A_DT,
        capacity=1 << 10,
        fanout=8,
        out_cap=1 << 11,
    )
    mview = MaterializeExecutor(
        pk=("id", "starttime"), columns=("name",), table_id="sq8.mview"
    )

    # single-chip oracle: same dedup -> join -> MV chain, fed serially
    o_dp = AppendOnlyDedupExecutor(
        ("id", "name", "starttime"), P_DT, capacity=1 << 12
    )
    o_da = AppendOnlyDedupExecutor(
        ("seller", "astarttime"), A_DT, capacity=1 << 12
    )
    o_j = HashJoinExecutor(
        ("id", "starttime"), ("seller", "astarttime"), P_DT, A_DT,
        capacity=1 << 12, fanout=8, out_cap=1 << 13,
    )
    o_mv = MaterializeExecutor(
        pk=("id", "starttime"), columns=("name",), table_id="oq8.mview"
    )

    for stacked_p, p_shards, stacked_a, a_shards in _per_shard_chunks():
        for c in p_shards:
            for d in o_dp.apply(c):
                for j in o_j.apply_left(d):
                    o_mv.apply(j)
        for c in a_shards:
            for d in o_da.apply(c):
                for j in o_j.apply_right(d):
                    o_mv.apply(j)

        for out in sd_p.apply(stacked_p):
            for j in sj.apply_left(out):
                mview.apply(flatten_stacked(j))
        for out in sd_a.apply(stacked_a):
            for j in sj.apply_right(out):
                mview.apply(flatten_stacked(j))
        sd_p.on_barrier(None)
        sd_a.on_barrier(None)
        sj.on_barrier(None)

    got = mview.snapshot()
    want = o_mv.snapshot()
    assert len(want) > 50
    assert got == want


@pytest.mark.parametrize("join_type", ["left", "full", "left_semi", "left_anti"])
def test_sharded_join_types_match_single(join_type):
    """Random insert streams through sharded vs single-chip join emit
    the same net multiset for every join type."""
    mesh = make_mesh(N)
    L = {"lk": jnp.int64, "lv": jnp.int64}
    R = {"rk": jnp.int64, "rv": jnp.int64}
    sj = ShardedHashJoin(
        mesh, ("lk",), ("rk",), L, R,
        capacity=256, fanout=16, out_cap=1 << 10, join_type=join_type,
    )
    single = HashJoinExecutor(
        ("lk",), ("rk",), L, R,
        capacity=1 << 10, fanout=32, out_cap=1 << 12, join_type=join_type,
    )

    rng = np.random.default_rng(7)
    CAP = 32

    def mk(side):
        k = rng.integers(0, 48, CAP).astype(np.int64)
        v = rng.integers(0, 5, CAP).astype(np.int64)
        names = ("lk", "lv") if side == "l" else ("rk", "rv")
        return StreamChunk.from_numpy({names[0]: k, names[1]: v}, CAP)

    def acc_into(acc, chunks, out_names):
        for c in chunks:
            d = c.to_numpy(with_ops=True)
            for i in range(len(d["__op__"])):
                row = tuple(
                    None
                    if (d.get(n + "__null") is not None and d[n + "__null"][i])
                    else int(d[n][i])
                    for n in out_names
                )
                sign = (
                    1
                    if d["__op__"][i] in (Op.INSERT, Op.UPDATE_INSERT)
                    else -1
                )
                acc[row] += sign

    got, want = Counter(), Counter()
    for step in range(6):
        side = "l" if step % 2 == 0 else "r"
        chunk = mk(side)
        shards = [
            chunk if i == step % N else StreamChunk.from_numpy(
                {k: np.zeros(0, np.int64) for k in chunk.columns}, CAP
            )
            for i in range(N)
        ]
        stacked = stack_chunks(shards)
        if side == "l":
            outs = sj.apply_left(stacked)
            souts = single.apply_left(chunk)
        else:
            outs = sj.apply_right(stacked)
            souts = single.apply_right(chunk)
        acc_into(got, [flatten_stacked(o) for o in outs], sj.out_names)
        acc_into(want, souts, single.out_names)
    sj.on_barrier(None)
    single.on_barrier(None)
    got = {k: v for k, v in got.items() if v}
    want = {k: v for k, v in want.items() if v}
    assert want and got == want
