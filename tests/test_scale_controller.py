"""Online re-partitioning: ScaleController reschedules a running
sharded fragment onto a different mesh size with exact state handover.

Reference: src/meta/src/stream/scale.rs:453 (Reschedule), recovery-based
rescale (barrier/recovery.rs:415), auto-parallelism policy.
"""

import jax.numpy as jnp
import pytest

from risingwave_tpu.connectors.nexmark import NexmarkConfig, NexmarkGenerator
from risingwave_tpu.executors import HashAggExecutor
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.parallel import ShardedHashAgg, make_mesh
from risingwave_tpu.parallel.scale import ScaleController
from risingwave_tpu.array.chunk import stack_chunks
from risingwave_tpu.runtime import Pipeline, StreamingRuntime
from risingwave_tpu.storage.object_store import MemObjectStore

CALLS = (AggCall("count_star", None, "cnt"), AggCall("sum", "price", "total"))
DTYPES = {"auction": jnp.int64, "price": jnp.int64}


def _mk_sharded(n_shards, capacity=1 << 10):
    return ShardedHashAgg(
        make_mesh(n_shards),
        ("auction",),
        CALLS,
        DTYPES,
        capacity=capacity,
        out_cap=1 << 9,
        table_id="sagg",
    )


def _replay(snap, chunk):
    d = chunk.to_numpy(with_ops=True)
    for i in range(len(d["__op__"])):
        key = int(d["auction"][i])
        if d["__op__"][i] in (1, 2):
            snap.pop(key, None)
        else:
            snap[key] = (int(d["cnt"][i]), int(d["total"][i]))
    return snap


def _gens(n):
    dicts = NexmarkGenerator.make_dictionaries()
    return [
        NexmarkGenerator(
            NexmarkConfig(), split_index=i, split_num=n, dictionaries=dicts
        )
        for i in range(n)
    ]


@pytest.mark.slow
def test_reschedule_4_to_8_shards_exact():
    """Epochs at 4 shards -> online reschedule to 8 -> more epochs:
    output matches an unrescheduled single-chip twin throughout."""
    rt = StreamingRuntime(MemObjectStore())
    sharded = _mk_sharded(4)
    rt.register("agg", Pipeline([sharded]))
    ctl = ScaleController(rt)

    single = HashAggExecutor(
        ("auction",), CALLS, DTYPES, capacity=1 << 12, out_cap=1 << 11
    )
    snap_s, snap_1 = {}, {}

    def run_epoch(n_feed, sharded_now):
        per_shard = []
        for g in gens[:n_feed]:
            bid = g.next_chunks(300, 512)["bid"].select(["auction", "price"])
            per_shard.append(bid)
            single.apply(bid)
        sharded_now.apply(stack_chunks(per_shard))
        for out in rt.barrier()["agg"]:
            _replay(snap_s, out)
        for out in single.on_barrier(None):
            _replay(snap_1, out)

    gens = _gens(8)
    run_epoch(4, sharded)
    run_epoch(4, sharded)
    assert snap_s == snap_1 and snap_s

    new = ctl.reschedule("agg", lambda old: Pipeline([_mk_sharded(8)]))
    sharded8 = new.executors[0]
    assert sharded8.n_shards == 8
    assert ctl.reschedules == 1

    run_epoch(8, sharded8)
    run_epoch(8, sharded8)
    assert snap_s == snap_1
    # groups really did spread over all 8 shards
    occ = sharded8.shard_occupancy()
    assert (occ > 0).sum() == 8


def test_autoscale_doubles_on_hot_shard():
    rt = StreamingRuntime(MemObjectStore())
    sharded = _mk_sharded(2, capacity=1 << 8)
    rt.register("agg", Pipeline([sharded]))
    ctl = ScaleController(rt)

    gens = _gens(2)
    per_shard = [
        g.next_chunks(300, 512)["bid"].select(["auction", "price"])
        for g in gens
    ]
    sharded.apply(stack_chunks(per_shard))
    rt.barrier()

    new = ctl.autoscale(
        "agg",
        rebuild_at=lambda n: Pipeline([_mk_sharded(n, capacity=1 << 8)]),
        max_shard_load=0.004,  # force the policy to trip (the table
        # may have auto-grown, shrinking relative load)
    )
    assert new is not None
    assert new.executors[0].n_shards == 4

    # and a fragment under the threshold does nothing
    assert (
        ctl.autoscale(
            "agg",
            rebuild_at=lambda n: Pipeline([_mk_sharded(n)]),
            max_shard_load=0.99,
        )
        is None
    )
