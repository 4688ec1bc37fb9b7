"""The byte model of the stream join's step (benchmarks/kernels/
stream_join_step.py) against nexmark_q9's plan: the row, key and pair
widths the new metric file hands the model are the widths of what the
planned join stores and hands on at the width the source selects (every
column of an auction and the four the view reads of a bid, each side's
row id beside them), and the module compiled for a described v5e chip
(nothing runs) takes at least what the model counts for a chunk."""

import importlib.util
import json
import os
import sys

import pytest

from conftest import BENCH, ROOT

sys.path.insert(0, ROOT)

LANES = 8192
CAPACITY = 1 << 21
METRIC = "join.wide_step_roofline_share.catchup"


def _kernel():
    path = os.path.join(BENCH, "kernels", "stream_join_step.py")
    spec = importlib.util.spec_from_file_location("stream_join_step", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _q9_join():
    from risingwave_tpu.frontend import SqlSession
    from risingwave_tpu.runtime import StreamingRuntime
    from risingwave_tpu.sql import Catalog, StreamPlanner
    from risingwave_tpu.storage.object_store import MemObjectStore

    with open(os.path.join(BENCH, "configs", "nexmark_q9.json")) as f:
        config = json.load(f)
    session = SqlSession(Catalog({}), StreamingRuntime(MemObjectStore()))
    for sql in config["ddl"]:
        session.execute(sql)
    planner = StreamPlanner(Catalog(dict(session.catalog.tables)), capacity=256)
    return planner.plan(config["mv_sql"][0]).pipeline.join


def _args():
    with open(os.path.join(BENCH, "layer_metrics", METRIC + ".json")) as f:
        return json.load(f)["args"]


def test_the_metric_files_widths_are_the_planned_joins():
    join, args = _q9_join(), _args()
    width = lambda side: sum(a.dtype.itemsize for a in side.rows.values())  # noqa: E731
    # ten columns of an auction, three of them string codes, its row id
    assert args["left_row_bytes"] == width(join.left) == 8 * 8 + 3 * 4
    # auction, bidder, price, date_time of a bid, its row id
    assert args["right_row_bytes"] == width(join.right) == 5 * 8
    assert args["key_bytes"] == sum(
        k.dtype.itemsize for k in join.left.table.keys
    ) == 8
    assert args["module"] == "^jit_stream_join_step$"
    # a pair as handed on: both rows, what join.epoch's emit_row_bytes says
    assert sum(d.itemsize for d in join._out_dtypes().values()) == (
        args["left_row_bytes"] + args["right_row_bytes"]
    )


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_compiled_step_moves_at_least_what_the_model_counts(one_chip):
    import jax
    import jax.numpy as jnp

    from risingwave_tpu.array.chunk import StreamChunk
    from risingwave_tpu.executors.stream_join import _step

    join, args = _q9_join(), _args()
    left, right = args["left_row_bytes"], args["right_row_bytes"]

    def shape(a):
        lanes = tuple(CAPACITY if d == 256 else d for d in a.shape)
        return jax.ShapeDtypeStruct(lanes, a.dtype, sharding=one_chip)

    def lane(dtype, n=LANES):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    bids = StreamChunk(
        columns={n: lane(join._lint_right[n]) for n in join.right_names},
        valid=lane(jnp.bool_), nulls={}, ops=lane(jnp.int32),
    )
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = _step.lower(
            jax.tree.map(shape, join.right), jax.tree.map(shape, join.left),
            jax.tree.map(shape, join._buf), scalar, bids, lane(jnp.int64, 3),
            scalar,
            own_keys=join.right_keys, own_names=join.right_names,
            other_names=join.left_names, out_cap=join.out_cap,
            cond=join._cond, retract=False, fold=True,
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    mem = compiled.memory_analysis()
    # a full chunk of bids, each pairing with one auction
    floor = _kernel().bytes_moved(0, LANES, LANES, left, right, args["key_bytes"])
    chunk_bytes = LANES * (right + 1 + 4)
    state = mem.argument_size_in_bytes - chunk_bytes
    assert state > 0 and mem.output_size_in_bytes >= state - 4096
    # both sides and the pair buffer are updated in place (donated)
    assert mem.alias_size_in_bytes >= 0.99 * state
    assert floor < mem.argument_size_in_bytes
    # a bid read and stored, its key looked up twice; the narrower row
    # of the pair read, the pair of 116 B written
    assert floor == LANES * ((2 * right + 16) + (right + left + right))
