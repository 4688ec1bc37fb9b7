"""The byte model of the stream join's step (benchmarks/kernels/
stream_join_step.py) against the q4 plan and the module the TPU's
compiler makes of its step: the row, key and pair widths the metric
files hand the model are the widths of the columns the planned join
stores and emits, and the module compiled for a described v5e chip
(nothing runs) takes and leaves at least what the model counts for a
chunk, so the share of the roofline the model gives is a floor."""

import importlib.util
import json
import os
import sys

import pytest

from conftest import BENCH, ROOT

sys.path.insert(0, ROOT)

LANES = 8192
CAPACITY = 1 << 21


def _kernel():
    path = os.path.join(BENCH, "kernels", "stream_join_step.py")
    spec = importlib.util.spec_from_file_location("stream_join_step", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _q4_join():
    from risingwave_tpu.frontend import SqlSession
    from risingwave_tpu.runtime import StreamingRuntime
    from risingwave_tpu.sql import Catalog, StreamPlanner
    from risingwave_tpu.storage.object_store import MemObjectStore

    with open(os.path.join(BENCH, "configs", "nexmark_q4.json")) as f:
        config = json.load(f)
    session = SqlSession(Catalog({}), StreamingRuntime(MemObjectStore()))
    for sql in config["ddl"]:
        session.execute(sql)
    planner = StreamPlanner(Catalog(dict(session.catalog.tables)), capacity=256)
    return planner.plan(config["mv_sql"][0]).pipeline.join


def _args(mix):
    with open(os.path.join(
        BENCH, "layer_metrics", f"join.step_roofline_share.{mix}.json"
    )) as f:
        return json.load(f)["args"]


@pytest.mark.parametrize("mix", ["catchup", "steady"])
def test_the_metric_files_widths_are_the_planned_joins(mix):
    join, args = _q4_join(), _args(mix)
    width = lambda side: sum(a.dtype.itemsize for a in side.rows.values())  # noqa: E731
    assert args["left_row_bytes"] == width(join.left) == 32
    assert args["right_row_bytes"] == width(join.right) == 24
    assert args["key_bytes"] == sum(
        k.dtype.itemsize for k in join.left.table.keys
    ) == 8
    assert args["module"] == "^jit_stream_join_step$"


def test_the_model_counts_rows_keys_and_pairs_and_no_capacity():
    k = _kernel()
    one_bid = k.bytes_moved(0, 1, 1, 32, 24, 8)
    # read + stored, its key looked up twice; the auction's row read,
    # the pair written
    assert one_bid == (2 * 24 + 2 * 8) + (24 + 32 + 24)
    assert k.bytes_moved(3, 5, 7, 32, 24, 8) == (
        3 * k.bytes_moved(1, 0, 0, 32, 24, 8)
        + 5 * k.bytes_moved(0, 1, 0, 32, 24, 8)
        + 7 * k.bytes_moved(0, 0, 1, 32, 24, 8)
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

    join = _q4_join()

    def shape(a):
        lanes = tuple(CAPACITY if d == 256 else d for d in a.shape)
        return jax.ShapeDtypeStruct(lanes, a.dtype, sharding=one_chip)

    def lane(dtype, n=LANES):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    bids = StreamChunk(
        columns={n: lane(jnp.int64) for n in join.right_names},
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
    floor = _kernel().bytes_moved(0, LANES, LANES, 32, 24, 8)
    # the chunk itself is among the arguments at the model's row width
    chunk_bytes = LANES * (24 + 1 + 4)
    state = mem.argument_size_in_bytes - chunk_bytes
    assert state > 0 and mem.output_size_in_bytes >= state - 4096
    # both sides and the pair buffer are updated in place (donated): a
    # step's traffic is its gathers and scatters, not a copy of the state
    assert mem.alias_size_in_bytes >= 0.99 * state
    # and what it has to touch of them is no less than the model's count
    assert floor < mem.argument_size_in_bytes
    assert floor == LANES * 144
