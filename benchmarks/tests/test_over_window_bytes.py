"""The byte model of the general over-window's steps (benchmarks/
kernels/over_window_step.py) against hand counts and against
nexmark_q6's plan: the widths the metric file hands the model are those
of the executor the planner makes of the source's text, the count is of
rows the spans say and never of the arena's capacity, and a step that
moved exactly the counted bytes at the chip's peak bandwidth reads 100%
through the reader, so nothing slower can read above it."""

import importlib.util
import json
import os
import sys
import types

import pytest

from conftest import BENCH, ROOT

sys.path.insert(0, ROOT)

METRIC = "over.step_roofline_share.catchup"


def _load(*parts):
    path = os.path.join(BENCH, *parts)
    spec = importlib.util.spec_from_file_location(parts[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _args():
    with open(os.path.join(BENCH, "layer_metrics", METRIC + ".json")) as f:
        return json.load(f)["args"]


def test_the_model_against_hand_counts():
    moved = _load("kernels", "over_window_step.py").bytes_moved
    # nothing came, nothing was dirty, nothing went: nothing moves,
    # whatever the arena holds
    assert moved(0, 0, 0, 0, 41, [8, 8], 18) == 0
    # one input row: read from the chunk, written into the arena
    assert moved(1, 0, 0, 0, 41, [8, 8], 18) == 2 * 41
    # one row of a dirty partition: the row read, its 16 B of order
    # words read and written, its two results and their flags written
    assert moved(0, 1, 0, 0, 41, [8, 8], 18) == 41 + 2 * 16 + 18
    # one retracted and one inserted row: each read and written at the
    # width it is handed on at
    assert moved(0, 0, 1, 1, 41, [8, 8], 18) == 2 * 2 * (41 + 18)
    # an epoch of the cell's kind: 2,200 rows in, 60,000 dirty, 4,000 out
    assert moved(2_200, 60_000, 1_500, 2_500, 41, [8, 8], 18) == (
        2 * 2_200 * 41 + 60_000 * 91 + 2 * 4_000 * 59
    )


@pytest.fixture(scope="module")
def over():
    from risingwave_tpu.executors.over_window import GeneralOverWindowExecutor
    from risingwave_tpu.frontend import SqlSession
    from risingwave_tpu.runtime import StreamingRuntime
    from risingwave_tpu.sql import Catalog, StreamPlanner
    from risingwave_tpu.storage.object_store import MemObjectStore

    with open(os.path.join(BENCH, "configs", "nexmark_q6.json")) as f:
        config = json.load(f)
    session = SqlSession(Catalog({}), StreamingRuntime(MemObjectStore()))
    for sql in config["ddl"]:
        session.execute(sql)
    planner = StreamPlanner(Catalog(dict(session.catalog.tables)), capacity=256)
    (ex,) = [
        ex for ex in planner.plan(config["mv_sql"][0]).pipeline.executors
        if isinstance(ex, GeneralOverWindowExecutor)
    ]
    return ex


def test_the_metric_files_widths_are_the_planned_executors(over):
    args = _args()
    assert over.part_keys == ("seller",) and over.order_col == "date_time"
    assert args["key_bytes"] == [
        over.buf[k].dtype.itemsize for k in over.part_keys + (over.order_col,)
    ]
    # two int64 results and a NULL flag each
    assert args["out_bytes"] == sum(
        over.em[c.output].dtype.itemsize + 1 for c in over.calls
    ) == 18
    # the span says the row's width itself: five lanes and one NULL flag
    assert over.row_bytes == 5 * 8 + 1
    import re

    from risingwave_tpu.executors import over_window

    for fn in ("_general_over_step", "_general_over_emit",
               "_general_over_commit"):
        assert hasattr(over_window, fn)
        assert re.search(args["module"], "jit_" + fn)
    with open(os.path.join(
        BENCH, "layer_metrics", "over.device_ms_per_barrier.catchup.json"
    )) as f:
        assert json.load(f)["args"]["module"] == args["module"]


def _span(name, epoch, traced=False, **a):
    return types.SimpleNamespace(
        name=name, epoch=epoch, args=a, traced=traced, t0=float(epoch)
    )


def test_a_step_at_peak_bandwidth_reads_100_and_the_parent_reads_nothing(
    monkeypatch,
):
    reader = _load("readers", "over_step_roofline.py")
    kernel = _load("kernels", "over_window_step.py")
    args = _args()
    counts = dict(in_rows=2_200, dirty_rows=60_000, retract_rows=1_500,
                  insert_rows=2_500)
    spans = [_span("barrier", e, traced=True) for e in (10, 11, 12)]
    for e in (10, 11, 12):
        spans += [
            _span("over.step", e, row_bytes=41, capacity=1 << 22),
            _span("over.step", e, row_bytes=41, capacity=1 << 22),
            _span("over.barrier", e, steps=2, **counts),
        ]
    peak = 819e9
    moved = 2 * kernel.bytes_moved(*counts.values(), 41, [8, 8], 18)
    run = {
        "peaks": {"hbm_bytes_per_s": peak},
        "device_trace": {
            "cycles": 2,
            "modules_in_cycles_s": {
                "jit__general_over_step": 0.75 * moved / peak,
                "jit__general_over_emit": 0.25 * moved / peak,
                "jit__upsert_step_ed": 1.0,  # not the over-window's
            },
        },
    }
    ring = types.SimpleNamespace(ring=lambda: spans)
    monkeypatch.setattr(reader, "_load", lambda path: (
        ring if path.endswith("epoch_spans.py") else kernel
    ))
    assert reader.read(run, args) == pytest.approx(100.0)
    # the program of PR 49 takes half a second a step whatever it moved
    run["device_trace"]["modules_in_cycles_s"]["jit__general_over_step"] = 2.2
    assert 0 < reader.read(run, args) < 1
    # a tree without the spans (the parent), without the modules, or a
    # ring that no longer holds the traced epochs: nothing, no raise
    monkeypatch.setattr(reader, "_load", lambda path: (
        types.SimpleNamespace(ring=lambda: spans[:3])
        if path.endswith("epoch_spans.py") else kernel
    ))
    assert reader.read(run, args) is None
    assert reader.read({"peaks": run["peaks"], "device_trace": None}, args) is None
    run["device_trace"]["modules_in_cycles_s"] = {"jit__rank": 1.0}
    assert reader.read(run, args) is None


def test_the_step_compiles_for_a_described_v5e_at_the_cells_capacity(over):
    """Nothing runs: the three programs lowered and compiled for a
    described v5e at 2^22 lanes and the Top-N's 65,536-lane chunk. The
    step's segments are running extremes because an ``associative_scan``
    of this length did not compile in fifty minutes (PERF.md 6, PR 49)."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    from risingwave_tpu.array.chunk import StreamChunk
    from risingwave_tpu.executors.over_window import (
        _general_over_commit,
        _general_over_emit,
        _general_over_step,
    )

    cap, lanes = 1 << 22, 1 << 16

    def big(tree, small=256, to=cap):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                tuple(to if d == small else d for d in a.shape), a.dtype,
                sharding=chip,
            ),
            tree,
        )

    chunk = big(
        StreamChunk.from_numpy(
            {k: np.zeros(0, np.int64) for k in over.lane_names}, 128
        ),
        128, lanes,
    )
    state = tuple(big(x) for x in (
        over.table, over.buf, over.bnulls, over.present, over.sdirty,
        over.em, over.emnulls, over.em_valid,
    ))
    static = dict(
        calls=over.calls, part_keys=over.part_keys, order_col=over.order_col,
        pk=over.pk, lane_names=over.lane_names,
    )
    cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        t0 = time.monotonic()
        low = _general_over_step.lower(*state, chunk, **static)
        # the order's sort in its loop, the way back, the chunk's own
        assert low.as_text().count("stablehlo.sort") == 3
        step = low.compile()
        assert time.monotonic() - t0 < 600
        mem = step.memory_analysis()
        # every lane of the arena, its shadow and the table is an argument
        assert mem.argument_size_in_bytes >= cap * (
            over.row_bytes + 8 * len(over.em)
        )
        assert mem.temp_size_in_bytes < 2 << 30
        outs = jax.eval_shape(
            lambda *a: _general_over_step(*a, **static), *state, chunk
        )
        delta = tuple(
            jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
                x,
            )
            for x in outs[5:9]
        )
        buf, bnulls, em, emnulls, em_valid = (
            state[1], state[2], state[5], state[6], state[7]
        )
        _general_over_emit.lower(
            buf, bnulls, em, emnulls, *delta,
            jax.ShapeDtypeStruct((), jnp.int32, sharding=chip),
            lanes=1 << 14, lane_names=over.lane_names,
            out_names=over.out_names,
        ).compile()
        _general_over_commit.lower(
            em, emnulls, em_valid, buf, bnulls, *delta,
            lane_names=over.lane_names,
        ).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", cache)
