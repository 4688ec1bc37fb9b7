"""Durable multi-chip state (VERDICT r3 #4): ShardedDedup and
ShardedHashJoin checkpoint through the standard manager and recover
mid-stream with exact parity — including onto a DIFFERENT mesh size,
and interchangeably with the single-chip executors (shared lane
naming).

Reference: state handover via durability across reschedules,
src/meta/src/stream/scale.rs:453 + consistent_hash/vnode.rs:34.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk, stack_chunks
from risingwave_tpu.executors.dedup import AppendOnlyDedupExecutor
from risingwave_tpu.executors.hash_join import HashJoinExecutor
from risingwave_tpu.executors.materialize import MaterializeExecutor
from risingwave_tpu.parallel import (
    ShardedDedup,
    ShardedHashJoin,
    flatten_stacked,
    make_mesh,
)
from risingwave_tpu.storage.object_store import MemObjectStore
from risingwave_tpu.storage.state_table import CheckpointManager

from tests.test_sharded_join import A_DT, P_DT, _per_shard_chunks

N = 8


def _mk_sharded(mesh, capacity=1 << 10):
    sd_p = ShardedDedup(
        mesh, ("id", "name", "starttime"), P_DT, capacity=capacity,
        table_id="sq8.dp",
    )
    sd_a = ShardedDedup(
        mesh, ("seller", "astarttime"), A_DT, capacity=capacity,
        table_id="sq8.da",
    )
    sj = ShardedHashJoin(
        mesh,
        ("id", "starttime"),
        ("seller", "astarttime"),
        P_DT,
        A_DT,
        capacity=capacity,
        fanout=8,
        out_cap=1 << 11,
        table_id="sq8.j",
    )
    mview = MaterializeExecutor(
        pk=("id", "starttime"), columns=("name",), table_id="sq8.mview"
    )
    return sd_p, sd_a, sj, mview


def _run_epoch(sd_p, sd_a, sj, mview, stacked_p, stacked_a):
    for out in sd_p.apply(stacked_p):
        for j in sj.apply_left(out):
            mview.apply(flatten_stacked(j))
    for out in sd_a.apply(stacked_a):
        for j in sj.apply_right(out):
            mview.apply(flatten_stacked(j))
    sd_p.on_barrier(None)
    sd_a.on_barrier(None)
    sj.on_barrier(None)
    mview.on_barrier(None)


def _oracle(epochs):
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
    for _, p_shards, _, a_shards in epochs:
        for c in p_shards:
            for d in o_dp.apply(c):
                for j in o_j.apply_left(d):
                    o_mv.apply(j)
        for c in a_shards:
            for d in o_da.apply(c):
                for j in o_j.apply_right(d):
                    o_mv.apply(j)
    return o_mv.snapshot()


@pytest.mark.parametrize("recover_shards", [N, 4])
@pytest.mark.slow
def test_sharded_q8_kill_and_recover_midstream(recover_shards):
    """Run 2 epochs sharded, checkpoint, KILL, rebuild (possibly on a
    smaller mesh), recover, run 2 more epochs — final MV must equal an
    uninterrupted single-chip run of all 4 epochs."""
    epochs = _per_shard_chunks(n_epochs=4)
    want = _oracle(epochs)
    assert len(want) > 50

    mgr = CheckpointManager(MemObjectStore())
    sd_p, sd_a, sj, mview = _mk_sharded(make_mesh(N))
    for stacked_p, _, stacked_a, _ in epochs[:2]:
        _run_epoch(sd_p, sd_a, sj, mview, stacked_p, stacked_a)
    staged = mgr.stage([sd_p, sd_a, sj, mview])
    assert staged  # all four executors contributed deltas
    mgr.commit_staged(1, staged)
    del sd_p, sd_a, sj, mview  # the "kill"

    sd_p2, sd_a2, sj2, mview2 = _mk_sharded(make_mesh(recover_shards))
    mgr.recover([sd_p2, sd_a2, sj2, mview2])
    for stacked_p, p_shards, stacked_a, a_shards in epochs[2:]:
        if recover_shards == N:
            _run_epoch(sd_p2, sd_a2, sj2, mview2, stacked_p, stacked_a)
        else:
            # re-stack the same per-shard chunks onto the smaller mesh:
            # rows keep their values, so vnode routing stays exact
            for i in range(0, N, recover_shards):
                sp = stack_chunks(p_shards[i : i + recover_shards])
                sa = stack_chunks(a_shards[i : i + recover_shards])
                _run_epoch(sd_p2, sd_a2, sj2, mview2, sp, sa)
    assert mview2.snapshot() == want


@pytest.mark.slow
def test_sharded_join_checkpoint_restores_into_single_chip():
    """Lane-naming compatibility: a sharded join's checkpoint restores
    into a single-chip HashJoinExecutor (and the stream continues with
    identical emissions) — one logical table, any executor layout."""
    mesh = make_mesh(N)
    L = {"lk": jnp.int64, "lv": jnp.int64}
    R = {"rk": jnp.int64, "rv": jnp.int64}
    sj = ShardedHashJoin(
        mesh, ("lk",), ("rk",), L, R,
        capacity=256, fanout=16, out_cap=1 << 10, table_id="xj",
    )
    oracle = HashJoinExecutor(
        ("lk",), ("rk",), L, R,
        capacity=1 << 10, fanout=16, out_cap=1 << 12, table_id="oj",
    )

    rng = np.random.default_rng(11)
    CAP = 32

    def mk(side):
        k = rng.integers(0, 40, CAP).astype(np.int64)
        v = rng.integers(0, 5, CAP).astype(np.int64)
        names = ("lk", "lv") if side == "l" else ("rk", "rv")
        return StreamChunk.from_numpy({names[0]: k, names[1]: v}, CAP)

    def shard_of(chunk, idx):
        shards = [
            chunk
            if i == idx
            else StreamChunk.from_numpy(
                {k: np.zeros(0, np.int64) for k in chunk.columns}, CAP
            )
            for i in range(N)
        ]
        return stack_chunks(shards)

    # phase 1: identical streams into sharded + oracle
    phase2 = []
    for step in range(4):
        side = "l" if step % 2 == 0 else "r"
        c = mk(side)
        if side == "l":
            sj.apply_left(shard_of(c, step % N))
            oracle.apply_left(c)
        else:
            sj.apply_right(shard_of(c, step % N))
            oracle.apply_right(c)
        phase2.append((side, mk(side)))  # pre-generate phase-2 chunks
    sj.on_barrier(None)

    mgr = CheckpointManager(MemObjectStore())
    staged = mgr.stage([sj])
    assert {d.table_id for d in staged} == {"xj.left", "xj.right"}
    mgr.commit_staged(1, staged)

    # restore into a SINGLE-CHIP executor under the sharded table_id
    # fanout must match the checkpoint's bucket width (restore lands
    # rows at their stored in-bucket positions)
    single = HashJoinExecutor(
        ("lk",), ("rk",), L, R,
        capacity=1 << 10, fanout=16, out_cap=1 << 12, table_id="xj",
    )
    mgr.recover([single])

    # phase 2: both see the same further chunks; emissions must agree
    from collections import Counter

    from risingwave_tpu.types import Op

    def acc(counter, chunks, out_names):
        for ch in chunks:
            d = ch.to_numpy(with_ops=True)
            for i in range(len(d["__op__"])):
                row = tuple(int(d[n][i]) for n in out_names)
                sign = (
                    1
                    if d["__op__"][i] in (Op.INSERT, Op.UPDATE_INSERT)
                    else -1
                )
                counter[row] += sign

    got, want = Counter(), Counter()
    for side, c in phase2:
        if side == "l":
            acc(got, single.apply_left(c), single.out_names)
            acc(want, oracle.apply_left(c), oracle.out_names)
        else:
            acc(got, single.apply_right(c), single.out_names)
            acc(want, oracle.apply_right(c), oracle.out_names)
    single.on_barrier(None)
    oracle.on_barrier(None)
    got = {k: v for k, v in got.items() if v}
    want = {k: v for k, v in want.items() if v}
    assert want and got == want
