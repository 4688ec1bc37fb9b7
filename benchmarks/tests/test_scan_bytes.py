"""The byte model of the keyed join's scan against the module the TPU's
compiler makes of it: compiled here for a described v5e chip (nothing
runs), at the two cells' lane counts. What a call takes from HBM and
leaves there is what benchmarks/kernels/keyed_join_scan.py counts, and
the lanes the passes rewrite stay in the chip's fast memory, which is
why the model counts no bytes a pass."""

import importlib.util
import os
import re
import sys

import pytest

from conftest import BENCH, ROOT

sys.path.insert(0, ROOT)

CHANGED_ROWS = 1 << 16  # lanes of a unique-side chunk


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


def _compiled(one_chip, lanes):
    """q5's scan: counts (auction, starttime, num) under one window key,
    the residual num >= maxn, maxn nullable as a MAX is."""
    import jax
    import jax.numpy as jnp

    from risingwave_tpu.executors.keyed_join import (
        KeyedJoinExecutor,
        keyed_join_scan,
    )
    from risingwave_tpu.expr import expr as E

    kj = KeyedJoinExecutor(
        left_keys=("starttime",), right_keys=("starttime_c",),
        left_dtypes={"auction": jnp.int64, "starttime": jnp.int64,
                     "num": jnp.int64},
        right_dtypes={"starttime_c": jnp.int64, "maxn": jnp.int64},
        left_pk=("starttime", "auction"), right_pk=("starttime_c",),
        unique_side="right", condition=E.col("num") >= E.col("maxn"),
        capacity=256,
    )

    def shape(shape_, dtype):
        return jax.ShapeDtypeStruct(shape_, dtype, sharding=one_chip)

    many = jax.tree.map(
        lambda a: shape(
            tuple(lanes if d == 256 else d for d in a.shape), a.dtype
        ),
        kj.left,
    )
    n = CHANGED_ROWS
    row = (
        shape((n,), jnp.bool_),
        {"starttime_c": shape((n,), jnp.int64), "maxn": shape((n,), jnp.int64)},
        {"maxn": shape((n,), jnp.bool_)},
    )
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return keyed_join_scan.lower(
            many, shape((), jnp.int32), row, row,
            key_pairs=kj._key_pairs, cond=kj._cond,
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)


def _kernel():
    path = os.path.join(BENCH, "kernels", "keyed_join_scan.py")
    spec = importlib.util.spec_from_file_location("keyed_join_scan", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("lanes", [1 << 21, 1 << 22])
def test_the_scan_moves_what_the_model_counts(one_chip, lanes):
    compiled = _compiled(one_chip, lanes)
    mem = compiled.memory_analysis()
    kernel = _kernel()
    # the two changed-row sets: live, two int64 values and a NULL flag
    small = 2 * CHANGED_ROWS * (1 + 8 + 8 + 1) + 4096
    read = lanes * (kernel.bytes_per_lane_call(8, 8) - kernel.RESULT_BYTES)
    assert read <= mem.argument_size_in_bytes <= read + small
    wrote = lanes * kernel.RESULT_BYTES
    assert wrote <= mem.output_size_in_bytes <= wrote + 4096
    # the loop carries the two result lanes in the chip's fast memory
    # (memory space 1) and the module copies them to HBM once, after it
    hlo = compiled.as_text()
    carried = re.findall(
        r"ROOT %tuple[.\d]* = \(s32\[\]\{[^}]*\}, "
        rf"s32\[{lanes}\]\{{[^}}]*S\(1\)\}}, s32\[{lanes}\]\{{[^}}]*S\(1\)\}}",
        hlo,
    )
    assert carried, "the result lanes are no longer carried in fast memory"
    assert len(re.findall(r" copy-start\(", hlo)) == 2
    # and so are the key and residual halves the passes compare
    assert len(set(re.findall(
        rf"(custom-call[.\d]*) = u32\[{lanes}\]\{{[^}}]*S\(1\)\}} custom-call\("
        r"[^)]*\), custom_call_target=\"X64Split", hlo,
    ))) == 4
