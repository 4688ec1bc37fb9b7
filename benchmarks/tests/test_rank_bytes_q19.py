"""The byte model of the GroupTopN's barrier program (benchmarks/
kernels/group_topk_rank.py) against nexmark_q19's plan: the key and row
widths the new metric file hands the model are those of the Top-N the
planner makes of the source's text — two key lanes, one order lane, a
bid of seven lanes and the rank as handed on — and the one sort of the
module lowered for a described v5e chip (nothing runs) carries one
operand MORE than the model counts (that rank, riding beside the slot),
so the share the metric reads stays a lower bound."""

import importlib.util
import json
import os
import re
import sys

import pytest

from conftest import BENCH, ROOT

sys.path.insert(0, ROOT)

LANES, OUT_LANES = 1 << 21, 1 << 16
WIDTH = {"i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i1": 1}
METRIC = "topn.k_rank_roofline_share.catchup"


def _kernel():
    path = os.path.join(BENCH, "kernels", "group_topk_rank.py")
    spec = importlib.util.spec_from_file_location("group_topk_rank", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _args():
    with open(os.path.join(BENCH, "layer_metrics", METRIC + ".json")) as f:
        return json.load(f)["args"]


@pytest.fixture(scope="module")
def topn():
    from risingwave_tpu.executors.top_n_plain import (
        RetractableGroupTopNExecutor,
    )
    from risingwave_tpu.frontend import SqlSession
    from risingwave_tpu.runtime import StreamingRuntime
    from risingwave_tpu.sql import Catalog, StreamPlanner
    from risingwave_tpu.storage.object_store import MemObjectStore

    with open(os.path.join(BENCH, "configs", "nexmark_q19.json")) as f:
        config = json.load(f)
    session = SqlSession(Catalog({}), StreamingRuntime(MemObjectStore()))
    for sql in config["ddl"]:
        session.execute(sql)
    planner = StreamPlanner(Catalog(dict(session.catalog.tables)), capacity=256)
    (gt,) = [
        ex for ex in planner.plan(config["mv_sql"][0]).pipeline.executors
        if isinstance(ex, RetractableGroupTopNExecutor)
    ]
    return gt


def test_the_metric_files_widths_are_the_planned_topns(topn):
    args = _args()
    keys = [k.dtype.itemsize for k in topn.table.keys]
    orders = [topn.rows[c].dtype.itemsize for c, _ in topn.order]
    assert topn.group_by == ("auction",) and topn.limit == 10
    assert args["key_bytes"] == keys + orders == [8] * 3
    # a bid's seven lanes and the rank as handed on (int32)
    assert args["row_bytes"] == sum(
        a.dtype.itemsize for a in topn.rows.values()
    ) + topn.erank.dtype.itemsize == 5 * 8 + 2 * 4 + 4
    # the programs the numbered Top-N runs keep the names the device
    # trace's readers know
    assert args["module"] == "^jit_(_rank|_diff_gather)$"
    # the spans say the same of the program: a word a digit, the slot,
    # and the rank as handed on, which the model leaves out
    assert topn._sort_operands == _kernel().n_digits(args["key_bytes"]) + 2
    assert topn._row_bytes == args["row_bytes"]


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


def test_the_lowered_programs_move_at_least_what_the_model_counts(
    topn, one_chip
):
    import jax
    import jax.numpy as jnp

    from risingwave_tpu.executors.top_n_plain import _diff_gather, _rank

    def big(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                tuple(LANES if d == 256 else d for d in a.shape), a.dtype,
                sharding=one_chip,
            ),
            tree,
        )

    state = (big(topn.table), big(topn.rows), big(topn.shadow),
             big(topn.emitted), big(topn.epoch_dirty))
    static = dict(k=topn.limit, desc=topn.desc, n_group=len(topn.group_by),
                  order_col=topn.order_col)
    low = _rank.lower(*state, erank=big(topn.erank), **static)
    blocks = re.findall(
        r'"stablehlo\.sort"\([^)]*\)[^\n]*\n\s*\^bb0\(([^)]*)\)', low.as_text()
    )
    widths = [
        tuple(WIDTH[t] for t in re.findall(r"tensor<(\w+)>", block))[::2]
        for block in blocks
    ]
    kernel, args = _kernel(), _args()
    n = kernel.n_digits(args["key_bytes"])
    assert n == 7  # two a lane of the three, one for liveness
    # ONE sort: a word a digit, the slot, the rank as handed on
    assert widths == [(kernel.WORD_BYTES,) * (n + 2)]
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        mem = low.compile().memory_analysis()
        ranked = jax.eval_shape(
            lambda *a: _rank(*a[:-1], erank=a[-1], **static),
            *state, big(topn.erank),
        )
        # the second program compiles for the chip at the cell's sizes
        # too: the numbered body, a round from a device scalar
        diff = _diff_gather.lower(
            *state[:4],
            jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=one_chip), ranked),
            jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip),
            out_lanes=OUT_LANES, erank=big(topn.erank),
            start=jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
            rank_col=topn.rank_col,
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    # arguments: the table's key lanes and liveness, the rows, their
    # shadow, the two flag lanes, the rank lane: what the model says a
    # call reads (the rank lane it counts with the row, twice)
    read = LANES * (
        sum(args["key_bytes"][:2]) + kernel.FLAG_BYTES + 2 * args["row_bytes"]
        - topn.erank.dtype.itemsize
    )
    assert mem.argument_size_in_bytes >= read
    assert kernel.bytes_moved(
        LANES, OUT_LANES, 0, args["key_bytes"], args["row_bytes"]
    ) >= read
    # both chunks carry the rank as a BIGINT beside the bid's lanes
    out = diff.memory_analysis().output_size_in_bytes
    assert out >= 2 * OUT_LANES * (args["row_bytes"] - 4 + 8)
