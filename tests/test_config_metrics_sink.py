"""Config layering, metrics registry, and the sink framework
(reference: config.rs:138, guarded_metrics.rs, sink/mod.rs:337,
compact_chunk.rs)."""

import json

import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.config import RwConfig, load_config
from risingwave_tpu.connectors.sink import (
    BlackholeSink,
    FileSink,
    SinkExecutor,
    compact_rows,
)
from risingwave_tpu.executors import Barrier
from risingwave_tpu.executors.base import Epoch
from risingwave_tpu.metrics import MetricsRegistry
from risingwave_tpu.types import Op


def test_config_layering(tmp_path):
    toml = tmp_path / "rw.toml"
    toml.write_text(
        """
[system]
barrier_interval_ms = 250

[streaming]
chunk_capacity = 8192
future_knob = 7

[brand_new_section]
x = 1
"""
    )
    cfg = load_config(str(toml), overrides={"system.checkpoint_frequency": 5})
    assert cfg.system.barrier_interval_ms == 250
    assert cfg.system.checkpoint_frequency == 5
    assert cfg.streaming.chunk_capacity == 8192
    assert cfg.storage.compact_at == 8  # untouched default
    assert cfg.unrecognized["streaming.future_knob"] == 7
    assert "brand_new_section" in cfg.unrecognized


def test_runtime_from_config(tmp_path):
    from risingwave_tpu.runtime import StreamingRuntime

    cfg = RwConfig()
    cfg.storage.object_store_root = str(tmp_path / "state")
    cfg.system.barrier_interval_ms = 123
    cfg.storage.compact_at = 3
    rt = StreamingRuntime.from_config(cfg)
    assert rt.barrier_interval_ms == 123
    assert rt.mgr.compact_at == 3


def test_metrics_registry():
    reg = MetricsRegistry()
    reg.counter("rows_total").inc(5, fragment="q5")
    reg.counter("rows_total").inc(2, fragment="q5")
    reg.histogram("lat_ms").observe(10.0)
    reg.histogram("lat_ms").observe(30.0)
    assert reg.counter("rows_total").get(fragment="q5") == 7
    assert reg.histogram("lat_ms").percentile(50) == 20.0
    text = reg.render()
    assert 'rows_total{fragment="q5"} 7' in text
    assert "lat_ms_count 2" in text


def test_compact_rows_net_effect():
    rows = [
        ((1,), (10,), Op.INSERT),
        ((1,), (10,), Op.UPDATE_DELETE),
        ((1,), (11,), Op.UPDATE_INSERT),   # 1: insert then update -> (11,)
        ((2,), (20,), Op.DELETE),          # 2: pre-existing delete
        ((3,), (30,), Op.INSERT),
        ((3,), (30,), Op.DELETE),          # 3: appeared+vanished -> nothing
    ]
    out = compact_rows(rows)
    assert out == [((1,), (11,), Op.INSERT), ((2,), None, Op.DELETE)]


def test_sink_executor_file_and_blackhole(tmp_path):
    bh = BlackholeSink()
    ex = SinkExecutor(bh, pk=("k",), columns=("k", "v"))
    chunk = StreamChunk.from_numpy(
        {"k": np.array([1, 2, 1], np.int64), "v": np.array([5, 6, 7], np.int64)},
        8,
        ops=np.array([Op.INSERT, Op.INSERT, Op.UPDATE_DELETE], np.int32),
    )
    ex.apply(chunk)
    ex.on_barrier(Barrier(Epoch(0, 1)))
    ex.finish_barrier()
    # pk 1: insert then update-delete -> vanished within epoch; pk 2 stays
    assert bh.rows_written == 1 and bh.commits == 1

    path = str(tmp_path / "out.jsonl")
    fs = FileSink(path, columns=("k", "v"))
    ex2 = SinkExecutor(fs, pk=("k",), columns=("k", "v"))
    ex2.apply(
        StreamChunk.from_numpy(
            {"k": np.array([9], np.int64), "v": np.array([90], np.int64)}, 4
        )
    )
    ex2.on_barrier(Barrier(Epoch(1, 2)))
    ex2.finish_barrier()
    lines = [json.loads(l) for l in open(path)]
    assert lines[0] == {"op": "insert", "pk": [9], "row": [9, 90]}
    assert lines[1]["op"] == "commit"


def test_metrics_gauge_and_http_exposition():
    import urllib.request

    from risingwave_tpu.metrics import MetricsRegistry

    reg = MetricsRegistry()
    reg.counter("rows_total").inc(5, fragment="q5")
    reg.gauge("state_bytes").set(1234.0)
    reg.histogram("lat_ms").observe(2.0)
    reg.histogram("lat_ms").observe(4.0)
    text = reg.render()
    assert '# TYPE rows_total counter' in text
    assert 'rows_total{fragment="q5"} 5.0' in text
    assert '# TYPE state_bytes gauge' in text
    assert 'lat_ms_count 2' in text and 'quantile="0.5"' in text

    port = reg.serve(0)
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        assert body == text
    finally:
        reg.shutdown()


def test_events_endpoint_and_bounded_histogram():
    import urllib.request

    from risingwave_tpu.event_log import EVENT_LOG
    from risingwave_tpu.metrics import REGISTRY

    EVENT_LOG.clear()
    EVENT_LOG.record("ddl", tag="CREATE_TABLE", sql="CREATE TABLE t (...)")
    EVENT_LOG.record("recovery", mode="auto", cause="Boom()")
    port = REGISTRY.serve(0)
    try:
        doc = json.loads(
            urllib.request.urlopen(
                f"http://127.0.0.1:{port}/events", timeout=5
            ).read().decode()
        )
        kinds = [e["kind"] for e in doc["events"]]
        assert kinds[-2:] == ["ddl", "recovery"]
        assert doc["events"][-2]["tag"] == "CREATE_TABLE"
        # the dashboard renders the same history
        html = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/dashboard", timeout=5
        ).read().decode()
        assert "/events" in html and "recovery" in html
    finally:
        REGISTRY.shutdown()

    # Histogram memory is bounded: quantiles window, totals stay exact
    reg = MetricsRegistry()
    h = reg.histogram("long_run_ms")
    for i in range(3 * h.window):
        h.observe(float(i), stage="upload")
    key = (("stage", "upload"),)
    assert len(h._obs[key]) == h.window
    assert h.count(stage="upload") == 3 * h.window
    assert f'long_run_ms{{stage="upload"}}_count {3 * h.window}' in reg.render()
    # the window sees only the newest observations
    assert h.percentile(0, stage="upload") >= float(2 * h.window)


def test_roofline_fields_and_stage_breakdown():
    """The bench JSON contract: achieved_bw_frac is a measured
    fraction of a configured chip peak, and barrier_stage_ms carries a
    per-stage breakdown once barriers ran."""
    import os

    from risingwave_tpu.epoch_trace import (
        hbm_peak_gbps,
        record_stage,
        roofline,
        stage_breakdown,
    )

    rf = roofline(10 * 10**9, 1.0, device_kind="cpu")
    assert rf["achieved_bw_gbps"] == 10.0
    assert 0.0 < rf["achieved_bw_frac"] <= 1.0
    assert rf["achieved_bw_frac"] == round(10.0 / rf["hbm_peak_gbps"], 6)
    assert roofline(0, 0.0)["achieved_bw_frac"] == 0.0
    # the peak table is keyed by jax's device_kind: the v5e reports
    # itself as "TPU v5 lite"; a kind the table does not know is an
    # error, never a default
    assert hbm_peak_gbps("TPU v5 lite") == 819.0
    with pytest.raises(KeyError, match="TPU v9"):
        hbm_peak_gbps("TPU v9")
    os.environ["RW_HBM_PEAK_GBPS"] = "123.0"
    try:
        assert hbm_peak_gbps("TPU v9") == 123.0
    finally:
        del os.environ["RW_HBM_PEAK_GBPS"]

    record_stage("manifest_commit", 2.0)
    bd = stage_breakdown()
    assert any("stage=manifest_commit" in k for k in bd)
    row = next(v for k, v in bd.items() if "stage=manifest_commit" in k)
    assert {"p50", "p99", "count", "sum"} <= set(row)


def test_tracer_spans_and_chrome_export(tmp_path):
    import json

    from risingwave_tpu.trace import TRACER

    TRACER.clear()
    with TRACER.span("unit.outer", k=1):
        with TRACER.span("unit.inner"):
            pass
    doc = json.loads(TRACER.chrome_trace())
    names = [e["name"] for e in doc["traceEvents"]]
    assert "unit.outer" in names and "unit.inner" in names
    path = tmp_path / "trace.json"
    TRACER.dump(str(path))
    assert json.loads(path.read_text())["traceEvents"]


def test_arrow_roundtrip():
    import numpy as np
    import pyarrow as pa  # noqa: F401 — availability gate

    from risingwave_tpu.array.arrow import chunk_from_arrow, chunk_to_arrow
    from risingwave_tpu.array.chunk import StreamChunk
    from risingwave_tpu.array.dictionary import StringDictionary

    d = StringDictionary()
    codes = d.encode(["alpha", "beta", "alpha"])
    chunk = StreamChunk.from_numpy(
        {
            "k": np.asarray([1, 2, 3], np.int64),
            "s": codes.astype(np.int32),
            "v": np.asarray([1.5, 0.0, -2.25], np.float64),
        },
        8,
        nulls={"v": np.asarray([False, True, False])},
    )
    batch = chunk_to_arrow(chunk, dictionaries={"s": d})
    assert batch.num_rows == 3
    assert batch.column("s").to_pylist() == ["alpha", "beta", "alpha"]
    assert batch.column("v").to_pylist()[1] is None

    dicts = {}
    back = chunk_from_arrow(batch, dictionaries=dicts)
    got = back.to_numpy(False)
    assert got["k"].tolist() == [1, 2, 3]
    assert [dicts["s"].decode_one(c) for c in got["s"].tolist()] == [
        "alpha", "beta", "alpha",
    ]
    assert got["v__null"].tolist() == [False, True, False]
