"""Mesh-parallel retractable GroupTopN (§2.11 'every fragment
parallelizes'): exchange by group key, per-shard top-k, shared diff —
oracle-checked against the single-chip executor with retractions, and
cross-layout checkpoint/restore."""

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk, stack_chunks
from risingwave_tpu.executors.top_n_plain import RetractableGroupTopNExecutor
from risingwave_tpu.parallel import ShardedGroupTopN, make_mesh
from risingwave_tpu.storage.object_store import MemObjectStore
from risingwave_tpu.storage.state_table import CheckpointManager

N = 8
DT = {"g": jnp.int64, "o": jnp.int64, "id": jnp.int64}


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(N)


def _mv(snap, chunks):
    for c in chunks:
        d = c.to_numpy(with_ops=True)
        for i in range(len(d["__op__"])):
            row = (int(d["g"][i]), int(d["o"][i]), int(d["id"][i]))
            if int(d["__op__"][i]) in (1, 3):
                snap.discard(row)
            else:
                snap.add(row)
    return snap


def _mk_sharded(mesh, table_id="stn"):
    return ShardedGroupTopN(
        mesh, ("g",), "o", 3, ("id",), DT, capacity=1 << 9,
        table_id=table_id,
    )


def _mk_single(table_id="stn1"):
    return RetractableGroupTopNExecutor(
        ("g",), "o", 3, ("id",), DT, capacity=1 << 10, table_id=table_id,
    )


def _streams(rng, epochs):
    """Per-epoch: (per-shard chunk list for the sharded exec, flat
    chunk list for the oracle) of mixed inserts/deletes."""
    live = {}
    nid = 0
    out = []
    for _ in range(epochs):
        rows = []
        for _ in range(int(rng.integers(8, 30))):
            if live and rng.random() < 0.3:
                rid = int(rng.choice(list(live)))
                g, o = live.pop(rid)
                rows.append((g, o, rid, 1))
            else:
                g = int(rng.integers(0, 6))
                o = int(rng.integers(0, 100))
                live[nid] = (g, o)
                rows.append((g, o, nid, 0))
                nid += 1
        # split rows round-robin across shards (source splits)
        per_shard = [[] for _ in range(N)]
        for j, r in enumerate(rows):
            per_shard[j % N].append(r)

        def chunk(rs):
            return StreamChunk.from_numpy(
                {
                    "g": np.asarray([r[0] for r in rs], np.int64),
                    "o": np.asarray([r[1] for r in rs], np.int64),
                    "id": np.asarray([r[2] for r in rs], np.int64),
                },
                16,
                ops=np.asarray([r[3] for r in rs], np.int32),
            )

        out.append(
            (
                stack_chunks([chunk(p) for p in per_shard]),
                [chunk(p) for p in per_shard if p],
            )
        )
    return out


def test_sharded_group_top_n_matches_single_chip(mesh):
    sharded = _mk_sharded(mesh)
    single = _mk_single()
    rng = np.random.default_rng(13)
    s_snap, o_snap = set(), set()
    for stacked, flat in _streams(rng, 10):
        sharded.apply(stacked)
        for c in flat:
            single.apply(c)
        _mv(s_snap, sharded.on_barrier(None))
        _mv(o_snap, single.on_barrier(None))
        assert s_snap == o_snap
    assert len(s_snap) > 5


@pytest.mark.slow
def test_sharded_group_top_n_checkpoint_cross_layout(mesh):
    store = MemObjectStore()
    mgr = CheckpointManager(store)
    sharded = _mk_sharded(mesh, table_id="stx")
    rng = np.random.default_rng(29)
    s_snap = set()
    streams = _streams(rng, 8)
    for stacked, _ in streams[:5]:
        sharded.apply(stacked)
        _mv(s_snap, sharded.on_barrier(None))
    mgr.commit_epoch(1 << 16, [sharded])

    # restore into a FRESH sharded executor: continuing matches the
    # uninterrupted run
    sharded2 = _mk_sharded(mesh, table_id="stx")
    CheckpointManager(store).recover([sharded2])
    twin = _mk_sharded(mesh, table_id="stx2")
    # (twin replays all 8 epochs for the expected final state)
    t_snap = set()
    for stacked, _ in streams:
        twin.apply(stacked)
        _mv(t_snap, twin.on_barrier(None))
    s2 = set(s_snap)
    for stacked, _ in streams[5:]:
        sharded2.apply(stacked)
        _mv(s2, sharded2.on_barrier(None))
    assert s2 == t_snap

    # cross-layout: the SAME checkpoint restores into the single-chip
    # executor (shared lane naming)
    single = _mk_single(table_id="stx")
    CheckpointManager(store).recover([single])
    s1 = set(s_snap)
    for _, flat in streams[5:]:
        for c in flat:
            single.apply(c)
        _mv(s1, single.on_barrier(None))
    assert s1 == t_snap
