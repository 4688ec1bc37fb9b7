"""The byte model of the GroupTopN's barrier program (benchmarks/
kernels/group_topk_rank.py) against nexmark_q9's plan: the key and row
widths the new metric file hands the model are those of the Top-N the
planner puts behind the join — three key lanes, BOTH order lanes, a
pair of sixteen lanes — and they make the operands of the one sort of
the module lowered for a described v5e chip (nothing runs)."""

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
METRIC = "topn.wide_rank_roofline_share.catchup"


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

    with open(os.path.join(BENCH, "configs", "nexmark_q9.json")) as f:
        config = json.load(f)
    session = SqlSession(Catalog({}), StreamingRuntime(MemObjectStore()))
    for sql in config["ddl"]:
        session.execute(sql)
    planner = StreamPlanner(Catalog(dict(session.catalog.tables)), capacity=256)
    (gt,) = [
        ex for ex in planner.plan(config["mv_sql"][0]).pipeline.tail
        if isinstance(ex, RetractableGroupTopNExecutor)
    ]
    return gt


def test_the_metric_files_widths_are_the_planned_topns(topn):
    args = _args()
    keys = [k.dtype.itemsize for k in topn.table.keys]
    orders = [topn.rows[c].dtype.itemsize for c, _ in topn.order]
    assert len(orders) == 2  # price DESC, date_time ASC
    assert args["key_bytes"] == keys + orders == [8] * 5
    assert args["row_bytes"] == sum(
        a.dtype.itemsize for a in topn.rows.values()
    ) == 13 * 8 + 3 * 4
    assert args["module"] == "^jit_(_rank|_diff_gather)$"
    # the spans say the same of the program
    assert topn._sort_operands == _kernel().n_digits(args["key_bytes"]) + 1
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


def test_the_lowered_rank_is_one_sort_of_a_word_a_digit(topn, one_chip):
    import jax

    from risingwave_tpu.executors.top_n_plain import _rank

    def big(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                tuple(LANES if d == 256 else d for d in a.shape), a.dtype,
                sharding=one_chip,
            ),
            tree,
        )

    low = _rank.lower(
        big(topn.table), big(topn.rows), big(topn.shadow), big(topn.emitted),
        big(topn.epoch_dirty),
        k=topn.limit, desc=topn.desc, n_group=len(topn.group_by),
        order_col=topn.order_col,
    )
    blocks = re.findall(
        r'"stablehlo\.sort"\([^)]*\)[^\n]*\n\s*\^bb0\(([^)]*)\)', low.as_text()
    )
    widths = [
        tuple(WIDTH[t] for t in re.findall(r"tensor<(\w+)>", block))[::2]
        for block in blocks
    ]
    kernel, args = _kernel(), _args()
    n = kernel.n_digits(args["key_bytes"])
    assert n == 11  # two a lane of the five, one for liveness
    assert widths == [(kernel.WORD_BYTES,) * (n + 1)]
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        mem = low.compile().memory_analysis()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    # arguments: the table's key lanes and liveness, the rows, their
    # shadow, the two flag lanes: what the model says a call reads
    read = LANES * (
        sum(args["key_bytes"][:3]) + kernel.FLAG_BYTES + 2 * args["row_bytes"]
    )
    assert mem.argument_size_in_bytes >= read
    assert kernel.bytes_moved(
        LANES, OUT_LANES, 0, args["key_bytes"], args["row_bytes"]
    ) >= read
