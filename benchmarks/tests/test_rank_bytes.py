"""The byte model of the GroupTopN's barrier program against the module
the TPU's compiler makes of its rank (``jit__rank``; the diff and the
gathers behind it are ``jit__diff_gather``): lowered and compiled here for a
described v5e chip (nothing runs), at a cell's lane count, for q18's
rows. The key widths the metric files give the model make the operands
of the program's one sort, and what the module takes as arguments is at
least what the model says it reads."""

import importlib.util
import json
import os
import re
import sys

import pytest

from conftest import BENCH, ROOT

sys.path.insert(0, ROOT)

LANES, OUT_LANES = 1 << 21, 1 << 14
WIDTH = {"i64": 8, "ui64": 8, "i32": 4, "ui32": 4, "i1": 1}


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


def _kernel():
    path = os.path.join(BENCH, "kernels", "group_topk_rank.py")
    spec = importlib.util.spec_from_file_location("group_topk_rank", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _args(mix):
    with open(os.path.join(
        BENCH, "layer_metrics", f"topn.rank_roofline_share.{mix}.json"
    )) as f:
        return json.load(f)["args"]


@pytest.fixture(scope="module")
def lowered(one_chip):
    """q18's Top-N: (bidder, auction) groups, date_time DESC, the row
    id as the rest of the stream key, seven stored columns."""
    import jax
    import jax.numpy as jnp

    from risingwave_tpu.executors.top_n_plain import (
        RetractableGroupTopNExecutor,
        _rank,
    )

    dtypes = {
        "auction": jnp.int64, "bidder": jnp.int64, "price": jnp.int64,
        "channel": jnp.int32, "date_time": jnp.int64, "extra": jnp.int32,
        "_row_id": jnp.int64,
    }
    ex = RetractableGroupTopNExecutor(
        ("bidder", "auction"), "date_time", 1, ("_row_id",), dtypes,
        desc=True, capacity=256, table_id="q18.gtopn",
    )

    def big(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                tuple(LANES if d == 256 else d for d in a.shape), a.dtype,
                sharding=one_chip,
            ),
            tree,
        )

    return _rank.lower(
        big(ex.table), big(ex.rows), big(ex.shadow), big(ex.emitted),
        big(ex.epoch_dirty),
        k=1, desc=True, n_group=2, order_col="date_time",
    ), sum(jnp.dtype(d).itemsize for d in dtypes.values())


@pytest.mark.parametrize("mix", ["steady", "catchup"])
def test_the_models_widths_are_the_modules(lowered, mix):
    low, row_bytes = lowered
    text = low.as_text()
    # the comparator's block names every operand twice (left, right)
    blocks = re.findall(
        r'"stablehlo\.sort"\([^)]*\)[^\n]*\n\s*\^bb0\(([^)]*)\)', text
    )
    widths = [
        tuple(WIDTH[t] for t in re.findall(r"tensor<(\w+)>", block))[::2]
        for block in blocks
    ]
    kernel, args = _kernel(), _args(mix)
    # ONE sort, of a 32-bit word a digit of the key and the slot,
    # whatever the key is made of (a digit per 4 B of the key lanes
    # and of the order lane, one more for liveness); the compaction's
    # searches are no sorts
    n = kernel.n_digits(args["key_bytes"])
    assert n == 9
    assert widths == [(kernel.WORD_BYTES,) * (n + 1)]
    assert args["row_bytes"] == row_bytes


def test_the_module_reads_at_least_what_the_model_counts(lowered):
    import jax

    low, row_bytes = lowered
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = low.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
    kernel, args = _kernel(), _args("steady")
    mem = compiled.memory_analysis()
    # arguments: the table (keys, liveness and its fingerprints), the
    # rows, their shadow, the two flag lanes
    read = LANES * (
        sum(args["key_bytes"][:-1]) + kernel.FLAG_BYTES + 2 * row_bytes
    )
    assert mem.argument_size_in_bytes >= read
    # the model with no sort made still counts that and no more than
    # the arguments and the scans' and gathers' traffic on top
    assert kernel.bytes_moved(
        LANES, OUT_LANES, 0, args["key_bytes"], row_bytes
    ) >= read
