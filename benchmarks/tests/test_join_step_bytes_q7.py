"""The byte model of the stream join's step (benchmarks/kernels/
stream_join_step.py) against nexmark_q7's plan: the row, key and pair
widths the new metric file hands the model are the widths of what the
planned join stores (a bid's four selected columns and its row id under
its price; a window's end and its maximum) and hands on, and the two
modules compiled for a described v5e chip at the cell's capacity
(nothing runs) — the bid side's step, inserts only, and the updating
side's, which holds the retraction walk — each take at least what the
model counts for a chunk."""

import importlib.util
import json
import os
import sys

import pytest

from conftest import BENCH, ROOT

sys.path.insert(0, ROOT)

LANES = 8192
CAPACITY = 1 << 23
METRIC = "join.q7_step_roofline_share.catchup"


def _kernel():
    path = os.path.join(BENCH, "kernels", "stream_join_step.py")
    spec = importlib.util.spec_from_file_location("stream_join_step", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _q7_join():
    from risingwave_tpu.frontend import SqlSession
    from risingwave_tpu.runtime import StreamingRuntime
    from risingwave_tpu.sql import Catalog, StreamPlanner
    from risingwave_tpu.storage.object_store import MemObjectStore

    with open(os.path.join(BENCH, "configs", "nexmark_q7.json")) as f:
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
    join, args = _q7_join(), _args()
    width = lambda side: sum(a.dtype.itemsize for a in side.rows.values())  # noqa: E731
    # auction, price, bidder, date_time of a bid, its row id
    assert args["left_row_bytes"] == width(join.left) == 5 * 8
    # a window's end and its highest price
    assert args["right_row_bytes"] == width(join.right) == 2 * 8
    assert args["key_bytes"] == sum(
        k.dtype.itemsize for k in join.left.table.keys
    ) == 8
    assert args["module"] == "^jit_stream_join_step$"
    assert sum(d.itemsize for d in join._out_dtypes().values()) == (
        args["left_row_bytes"] + args["right_row_bytes"]
    )
    # the raw stream never takes a row back; the aggregate's does
    assert join._retract == {"left": False, "right": True}


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


@pytest.mark.parametrize("name,lanes", [("left", LANES), ("right", 256)])
def test_the_compiled_steps_move_at_least_what_the_model_counts(
    one_chip, name, lanes
):
    import jax
    import jax.numpy as jnp

    from risingwave_tpu.array.chunk import StreamChunk
    from risingwave_tpu.executors.stream_join import _step

    join, args = _q7_join(), _args()
    left, right = args["left_row_bytes"], args["right_row_bytes"]
    other = "right" if name == "left" else "left"
    own_bytes = left if name == "left" else right

    def shape(a):
        dims = tuple(CAPACITY if d == 256 else d for d in a.shape)
        return jax.ShapeDtypeStruct(dims, a.dtype, sharding=one_chip)

    def lane(dtype, n=lanes):
        return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)

    scalar = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    dtypes = join._lint_left if name == "left" else join._lint_right
    names = join.left_names if name == "left" else join.right_names
    chunk = StreamChunk(
        columns={n: lane(dtypes[n]) for n in names},
        valid=lane(jnp.bool_), nulls={}, ops=lane(jnp.int32),
    )
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = _step.lower(
            jax.tree.map(shape, getattr(join, name)),
            jax.tree.map(shape, getattr(join, other)),
            jax.tree.map(shape, join._buf), scalar, chunk,
            lane(jnp.int64, 3), scalar,
            own_keys=join.left_keys if name == "left" else join.right_keys,
            own_names=names,
            other_names=(
                join.right_names if name == "left" else join.left_names
            ),
            out_cap=join.out_cap, cond=join._cond,
            retract=join._retract[name], fold=True,
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    mem = compiled.memory_analysis()
    # a full chunk of rows, each pairing with one row of the other side
    rows = (lanes, 0) if name == "left" else (0, lanes)
    floor = _kernel().bytes_moved(*rows, lanes, left, right, args["key_bytes"])
    chunk_bytes = lanes * (own_bytes + 1 + 4)
    state = mem.argument_size_in_bytes - chunk_bytes
    assert state > 0 and mem.output_size_in_bytes >= state - 4096
    # both sides and the pair buffer are updated in place (donated)
    assert mem.alias_size_in_bytes >= 0.99 * state
    assert floor < mem.argument_size_in_bytes
    # a row read and stored, its key looked up twice; the narrower row
    # of the pair read (16 B), the pair of 56 B written
    assert floor == lanes * ((2 * own_bytes + 16) + (right + left + right))
    # what the chip holds for the join at the cell's capacity: two
    # sides and the pair buffer
    assert state < 2 * 1024**3
