"""Sharded overflow -> grow/rescale instead of job death (VERDICT r4
next #7): a hot-key epoch that overflows a sharded op's static
capacity (exchange bucket / probe chain / emission cap) is healed by
the watchdog — the op rebuilds at 2x, durable state restores, and the
epoch replays to the exact result. No caller intervention.

Reference: the reschedule path of src/meta/src/stream/scale.rs:453
(capacity is the per-shard analogue of parallelism)."""

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk, stack_chunks
from risingwave_tpu.executors.materialize import MaterializeExecutor
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.parallel import (
    ShardedDedup,
    ShardedHashAgg,
    flatten_stacked,
    make_mesh,
)
from risingwave_tpu.runtime import Pipeline
from risingwave_tpu.runtime.runtime import StreamingRuntime
from risingwave_tpu.storage.object_store import MemObjectStore

pytestmark = pytest.mark.smoke

N = 8


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(N)


def _hot_chunks(rng, n_rows, hot_key=7):
    """Stacked (N, 64) chunk where ONE shard carries n_rows rows of a
    single key — the skew that overflows a static exchange bucket."""
    per_shard = []
    for i in range(N):
        if i == 0:
            cols = {
                "k": np.full(n_rows, hot_key, np.int64),
                "v": rng.integers(0, 10, n_rows).astype(np.int64),
            }
        else:
            cols = {"k": np.zeros(0, np.int64), "v": np.zeros(0, np.int64)}
        per_shard.append(StreamChunk.from_numpy(cols, 64))
    return per_shard


@pytest.mark.slow
def test_hot_key_overflow_heals_via_growth(mesh):
    """bucket_cap=8 cannot absorb a 64-row single-key epoch; the
    watchdog must double capacities until the replay commits, with the
    exact aggregate."""
    agg = ShardedHashAgg(
        mesh,
        ("k",),
        (AggCall("sum", "v", "s"), AggCall("count_star", None, "c")),
        {"k": jnp.int64, "v": jnp.int64},
        capacity=1 << 8,
        out_cap=1 << 8,
        bucket_cap=8,
        table_id="ovf.agg",
    )
    mview = MaterializeExecutor(
        pk=("k",), columns=("s", "c"), table_id="ovf.mview"
    )
    rt = StreamingRuntime(
        MemObjectStore(), async_checkpoint=False, auto_recover=True
    )
    rt.register("ovf", Pipeline([agg, mview]))

    rng = np.random.default_rng(5)
    per_shard = _hot_chunks(rng, 48)
    stacked = stack_chunks(per_shard)
    want_sum = int(np.sum(np.asarray(per_shard[0].to_numpy()["v"])))

    for _attempt in range(6):
        rt.push("ovf", stacked)
        before = rt.mgr.max_committed_epoch
        rt.barrier()
        if rt.mgr.max_committed_epoch > before:
            break
    else:
        raise AssertionError("hot epoch never committed")

    assert rt.auto_recoveries >= 1, "no overflow recovery ever fired"
    assert agg.bucket_cap >= 48, f"bucket never grew: {agg.bucket_cap}"
    got = {k[0]: v for k, v in mview.snapshot().items()}
    assert got == {7: (want_sum, 48)}

    # a second hot epoch at the grown shape commits first try
    before_recoveries = rt.auto_recoveries
    per_shard2 = _hot_chunks(rng, 48)
    want_sum2 = want_sum + int(
        np.sum(np.asarray(per_shard2[0].to_numpy()["v"]))
    )
    rt.push("ovf", stack_chunks(per_shard2))
    before = rt.mgr.max_committed_epoch
    rt.barrier()
    assert rt.mgr.max_committed_epoch > before
    assert rt.auto_recoveries == before_recoveries
    got = {k[0]: v for k, v in mview.snapshot().items()}
    assert got == {7: (want_sum2, 96)}


@pytest.mark.slow
def test_dedup_overflow_heals_and_keeps_exactness(mesh):
    """ShardedDedup with a tiny exchange bucket: the hot epoch heals by
    growth and the first-seen semantics stay exact across the replay
    (durable keys from earlier epochs are NOT re-emitted)."""
    dd = ShardedDedup(
        mesh,
        ("k",),
        {"k": jnp.int64},
        capacity=1 << 8,
        bucket_cap=8,
        table_id="ovfd.dd",
    )
    mview = MaterializeExecutor(pk=("k",), columns=(), table_id="ovfd.mv")

    class Flatten:
        def apply(self, chunk):
            return [flatten_stacked(chunk)]

        def on_barrier(self, b):
            return []

        def emit_watermark(self):
            return None

        def finish_barrier(self):
            return None

    rt = StreamingRuntime(
        MemObjectStore(), async_checkpoint=False, auto_recover=True
    )
    rt.register("ovfd", Pipeline([dd, Flatten(), mview]))

    # epoch 1: smooth keys 0..31, commits clean
    smooth = [
        StreamChunk.from_numpy(
            {"k": np.arange(i * 4, i * 4 + 4, dtype=np.int64)}, 64
        )
        for i in range(N)
    ]
    rt.push("ovfd", stack_chunks(smooth))
    rt.barrier()
    assert len(mview.snapshot()) == 32

    # epoch 2: 48 duplicate rows of one NEW key + dups of old keys
    hot = []
    for i in range(N):
        if i == 0:
            ks = np.full(48, 999, np.int64)
        elif i == 1:
            ks = np.arange(0, 16, dtype=np.int64)  # all durable dups
        else:
            ks = np.zeros(0, np.int64)
        hot.append(StreamChunk.from_numpy({"k": ks}, 64))
    stacked = stack_chunks(hot)
    for _attempt in range(6):
        rt.push("ovfd", stacked)
        before = rt.mgr.max_committed_epoch
        rt.barrier()
        if rt.mgr.max_committed_epoch > before:
            break
    else:
        raise AssertionError("hot epoch never committed")

    assert rt.auto_recoveries >= 1
    snap = {k[0] for k in mview.snapshot()}
    assert snap == set(range(32)) | {999}


def test_growth_gives_up_after_bound(mesh):
    """An overflow that growth cannot cure (here: artificially pinned
    growth rounds) surfaces instead of looping forever."""
    agg = ShardedHashAgg(
        mesh,
        ("k",),
        (AggCall("count_star", None, "c"),),
        {"k": jnp.int64},
        capacity=1 << 8,
        bucket_cap=8,
        table_id="ovfg.agg",
    )
    agg._growth_rounds = 5  # pretend five doublings already happened
    rt = StreamingRuntime(
        MemObjectStore(), async_checkpoint=False, auto_recover=True
    )
    rt.register("ovfg", Pipeline([agg]))
    rng = np.random.default_rng(9)
    rt.push("ovfg", stack_chunks(_hot_chunks(rng, 48)))
    with pytest.raises(RuntimeError, match="capacity doublings"):
        rt.barrier()
