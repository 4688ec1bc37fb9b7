"""Device-level observability (deviceprof.py + the fused telemetry
lanes): the compiled-artifact roofline must return sane figures on CPU
for every Nexmark query, the in-program telemetry must match the
interpreted twin's per-member counts bit-for-bit at ZERO added
dispatches, the named-scope trace parse must recover all four fused
stages, EpochTrace must prefer modeled bytes over the legacy host
guess (keeping the legacy sum for artifact continuity), recovery must
re-arm deviceprof without orphaned capture windows, and every bench
artifact must carry provenance. CPU-only, tier-1."""

import json

import jax
import jax.numpy as jnp
import pytest

from risingwave_tpu.connectors.nexmark import NexmarkConfig, NexmarkGenerator
from risingwave_tpu.deviceprof import (
    DEVICEPROF,
    FUSED_STAGES,
    analyze_nexmark,
    parse_fused_stages,
)
from risingwave_tpu.epoch_trace import EpochTrace
from risingwave_tpu.profiler import PROFILER
from risingwave_tpu.queries.nexmark_q import build_q5_lite
from risingwave_tpu.ops.bucketing import padding_fraction
from risingwave_tpu.runtime.fused_step import fuse_pipeline

pytestmark = pytest.mark.smoke


@pytest.fixture(autouse=True)
def _clean_deviceprof():
    DEVICEPROF.reset()
    DEVICEPROF.disarm()
    yield
    DEVICEPROF.reset()
    DEVICEPROF.disarm()


def _chunks(epochs, chunks_per_epoch=2, n=400, cap=512):
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=20_000))
    out = []
    for _ in range(epochs):
        ep = []
        while len(ep) < chunks_per_epoch:
            c = gen.next_chunks(n, cap)["bid"]
            if c is not None:
                ep.append(c.select(["auction", "date_time"]))
        out.append(ep)
    return out


# ---------------------------------------------------------------------------
# telemetry lanes: fused vs interpreted twin, bit-for-bit
# ---------------------------------------------------------------------------


def test_fused_telemetry_matches_interpreted_twin_exactly():
    """Per-member telemetry (rows applied, dirty groups, MV rows,
    occupancies) from the fused program's packed lane must equal the
    counts the interpreted twin produces for the same epochs."""
    epochs = _chunks(3)
    fused = build_q5_lite(capacity=1 << 11, state_cleaning=False)
    (wrapper,) = fuse_pipeline(fused.pipeline, label="q5")
    interp = build_q5_lite(capacity=1 << 11, state_cleaning=False)
    # count the rows the interpreted MV actually receives (flush
    # deltas walking the chain at the barrier)
    mv_rows_seen = []
    orig_apply = interp.mview.apply

    def counting_apply(chunk):
        mv_rows_seen.append(int(jnp.sum(chunk.valid.astype(jnp.int32))))
        return orig_apply(chunk)

    interp.mview.apply = counting_apply
    for ep in epochs:
        rows_pushed = 0
        for c in ep:
            fused.pipeline.push(c)
            interp.pipeline.push(c)
            rows_pushed += int(jnp.sum(c.valid.astype(jnp.int32)))
        # interpreted applies landed at push time: the dirty-group
        # count pending at the barrier is the twin of the fused
        # program's pre-flush sample
        interp_dirty = int(jnp.sum(interp.agg.state.dirty.astype(jnp.int32)))
        mv_rows_seen.clear()
        fused.pipeline.barrier()
        interp.pipeline.barrier()
        tel = wrapper._telemetry
        assert tel is not None
        assert tel["rows_in"] == rows_pushed
        assert tel["dirty_groups"] == interp_dirty
        assert tel["mv_rows"] == sum(mv_rows_seen)
        assert tel["occupancy"]["agg"] == int(interp.agg.table.occupancy())
        assert tel["occupancy"]["mv"] == int(interp.mview.table.occupancy())
        # member attribution: pure prefix sees the input rows, the MV
        # sees the flush rows
        rows = tel["member_rows"]
        assert rows["0:HopWindowExecutor"] == rows_pushed
        assert rows["1:HashAggExecutor"] == rows_pushed
        assert rows["2:DeviceMaterializeExecutor"] == sum(mv_rows_seen)
        assert 0.0 < tel["lane_fill_frac"] <= 1.0
        assert 0.0 <= tel["padding_bytes_frac"] < 1.0
    # and the twins stayed bit-identical (the precondition of the
    # comparison above)
    assert fused.mview.snapshot() == interp.mview.snapshot()


def test_telemetry_armed_adds_zero_dispatches_and_syncs():
    """Telemetry + deviceprof armed: the steady fused barrier still
    costs exactly ONE device dispatch (the telemetry rides the
    existing program and the existing staged-scalar read)."""
    DEVICEPROF.arm()
    q5 = build_q5_lite(capacity=1 << 11, state_cleaning=False)
    fuse_pipeline(q5.pipeline, label="q5")
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=50_000))
    bid = gen.next_chunks(1500, 1 << 11)["bid"].select(
        ["auction", "date_time"]
    )

    def epoch():
        q5.pipeline.push(bid)
        q5.pipeline.barrier()

    epoch()
    epoch()  # warm: compiles + analyses land before counting
    PROFILER.reset()
    PROFILER.enable()
    try:
        per = []
        for _ in range(3):
            base = PROFILER.total_dispatches()
            epoch()
            per.append(PROFILER.total_dispatches() - base)
    finally:
        PROFILER.disable()
        PROFILER.reset()
    assert per == [1.0, 1.0, 1.0], per
    # the roofline model populated without touching the dispatch count
    # (analyses are deferred off the dispatch path; flush runs them)
    DEVICEPROF.flush_analyses()
    model = DEVICEPROF.steady_model()
    assert model["modeled_bytes"] > 0
    assert 0.0 <= model["padding_frac"] < 1.0


# ---------------------------------------------------------------------------
# compiled-artifact roofline: sane figures on CPU, all four queries
# ---------------------------------------------------------------------------


def test_cost_memory_analysis_sane_for_all_four_queries():
    rep = analyze_nexmark()
    assert set(rep) == {"q5", "q5u", "q7", "q8"}
    for q, entries in rep.items():
        assert entries, f"{q}: no traceable executors analyzed"
        sane = [
            v
            for v in entries.values()
            if "error" not in v
            and v["flops"] > 0
            and v["bytes_accessed"] > 0
            and v["compile_ms"] > 0
        ]
        assert sane, f"{q}: no sane cost/memory analysis: {entries}"
        errors = {k: v for k, v in entries.items() if "error" in v}
        assert not errors, f"{q}: analysis errors: {errors}"


def test_fused_program_analysis_populates_gauges():
    from risingwave_tpu.metrics import REGISTRY

    DEVICEPROF.arm()
    q5 = build_q5_lite(capacity=1 << 10, state_cleaning=False)
    fuse_pipeline(q5.pipeline, label="q5")
    for ep in _chunks(2):
        for c in ep:
            q5.pipeline.push(c)
        q5.pipeline.barrier()
    progs = DEVICEPROF.report()["programs"]
    assert any(k.startswith("fused:q5|") for k in progs)
    for p in progs.values():
        assert "error" not in p, p
        assert p["bytes_accessed"] > 0 and p["compile_ms"] > 0
        assert p["argument_bytes"] > 0
    # the ISSUE's metric surface: compile_ms{fn,bucket},
    # executable_bytes{fn,bucket}, fused_modeled_bytes{fragment}
    assert REGISTRY.gauges["fused_modeled_bytes"].get(fragment="q5") > 0
    assert any(
        dict(k).get("fn", "").startswith("fused:q5")
        for k in REGISTRY.gauges["compile_ms"]._values
    )
    assert "executable_bytes" in REGISTRY.gauges


# ---------------------------------------------------------------------------
# fused-stage attribution: named-scope capture parse
# ---------------------------------------------------------------------------


def test_trace_parse_produces_all_four_stages(tmp_path):
    trace = {
        "traceEvents": [
            {"name": "jit_fn/fused/apply/reduce", "ph": "X", "dur": 500},
            {"name": "fused/flush", "ph": "X", "dur": 300},
            {"name": "x/fused/mv_write/scatter", "ph": "X", "dur": 120},
            {"name": "fused/scalar_pack", "ph": "B", "ts": 1000},
            {"name": "fused/scalar_pack", "ph": "E", "ts": 1080},
            {"name": "fused:q5", "ph": "X", "dur": 1100},
            {"name": "unrelated_op", "ph": "X", "dur": 999},
        ]
    }
    parsed = parse_fused_stages(trace)
    assert parsed["fragment"] == "q5"
    assert set(parsed["stages_ms"]) == set(FUSED_STAGES)
    assert parsed["stages_ms"]["apply"] == pytest.approx(0.5)
    assert parsed["stages_ms"]["scalar_pack"] == pytest.approx(0.08)
    # gzip'd TensorBoard layout parses identically
    import gzip

    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    with gzip.open(d / "host.trace.json.gz", "wt") as f:
        json.dump(trace, f)
    parsed2 = parse_fused_stages(str(tmp_path))
    assert parsed2["stages_ms"] == parsed["stages_ms"]
    # the metric surface
    from risingwave_tpu.metrics import REGISTRY

    h = REGISTRY.histograms.get("fused_stage_ms")
    assert h is not None
    assert h.count(fragment="q5", stage="apply") >= 2


def test_fused_program_traces_with_named_scopes():
    """The four stage scopes actually appear in the fused program's
    jaxpr/HLO (the precondition for a device capture segmenting it)."""
    q5 = build_q5_lite(capacity=1 << 10, state_cleaning=False)
    (w,) = fuse_pipeline(q5.pipeline, label="q5")
    for c in _chunks(1)[0]:
        q5.pipeline.push(c)
    q5.pipeline.barrier()
    from risingwave_tpu.runtime.fused_step import _fused_barrier_step

    # lower the flush-bearing bucket and look for the scope names in
    # the stable HLO text
    states = (w._agg_state(), w._mv_state())
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), states
    )
    # scope names live in op metadata, which survives into the
    # compiled executable's HLO (exactly what a device trace reports)
    txt = (
        _fused_barrier_step.lower(
            abstract, None, None, w.plan, 1, (256,), False
        )
        .compile()
        .as_text()
    )
    for stage in ("flush", "mv_write", "scalar_pack"):
        assert f"fused/{stage}" in txt, f"named scope fused/{stage} lost"


# ---------------------------------------------------------------------------
# EpochTrace byte accounting + flight-recorder tail
# ---------------------------------------------------------------------------


def _seed_model(modeled=10_000_000, pad=0.9):
    DEVICEPROF.fragments["q5"] = {
        "fn": "fused:q5",
        "bucket": "b",
        "modeled_bytes": modeled,
    }
    DEVICEPROF.telemetry["q5"] = {"padding_bytes_frac": pad}
    # the model is dispatch-gated: only fragments that ran since the
    # last consumed barrier count toward that barrier's bytes
    DEVICEPROF._dispatched.add("q5")


def test_epoch_trace_prefers_modeled_bytes_keeps_legacy():
    _seed_model()
    tr = EpochTrace(7, 1, True)
    tr.chunk_bytes = 1000
    tr.finalize(5000, 4000)
    d = tr.to_dict()
    assert d["hbm_bytes_touched_legacy"] == 2000  # delta 1000 + chunks
    assert d["modeled_bytes"] == 10_000_000
    assert d["hbm_bytes_touched"] == 10_000_000
    assert d["padding_bytes_frac"] == pytest.approx(0.9)
    assert d["useful_bytes"] + d["padding_bytes"] == d["hbm_bytes_touched"]
    assert d["useful_bw_frac"] == pytest.approx(
        d["achieved_bw_frac"] * 0.1, rel=1e-3
    )


def test_idle_barrier_models_zero_traffic():
    """Regression (review finding): the model is consumed per barrier
    — a barrier with NO fused dispatch must model zero bytes, not
    re-report the last program's traffic as phantom bandwidth."""
    _seed_model()
    tr1 = EpochTrace(1, 1, True)
    tr1.finalize(1000, 0)
    assert tr1.modeled_bytes == 10_000_000
    assert tr1.telemetry == {"q5": {"rows": {}, "dirty": 0}}
    # idle barrier: nothing dispatched since tr1 consumed the model
    tr2 = EpochTrace(2, 2, False)
    tr2.chunk_bytes = 64
    tr2.finalize(1000, 1000)
    assert tr2.modeled_bytes == 0
    assert tr2.hbm_bytes_touched == tr2.hbm_bytes_touched_legacy == 64
    assert tr2.telemetry == {}


def test_epoch_trace_falls_back_to_legacy_without_model():
    tr = EpochTrace(8, 1, False)
    tr.chunk_bytes = 500
    tr.finalize(4000, 4000)
    assert tr.modeled_bytes == 0
    assert tr.hbm_bytes_touched == tr.hbm_bytes_touched_legacy == 500


def test_flight_recorder_carries_roofline_tail(tmp_path):
    from risingwave_tpu.blackbox import FlightRecorder, read_segment

    _seed_model()
    DEVICEPROF.telemetry["q5"].update(
        {"member_rows": {"1:HashAggExecutor": 42}, "dirty_groups": 7}
    )
    tr = EpochTrace(1, 1, True)
    tr.finalize(1000, 0)
    rec = FlightRecorder()
    rec.configure(dir=str(tmp_path))
    rec.record_barrier(tr)
    rec.close()
    doc = read_segment(str(tmp_path))
    (r,) = doc["records"]
    assert r["modeled_bytes"] == 10_000_000
    assert r["padding_bytes_frac"] == pytest.approx(0.9)
    assert r["telemetry"]["q5"]["dirty"] == 7
    assert r["telemetry"]["q5"]["rows"]["1:HashAggExecutor"] == 42


def test_blackbox_cli_roofline_column(tmp_path):
    import subprocess
    import sys

    from risingwave_tpu.blackbox import FlightRecorder

    _seed_model()
    tr = EpochTrace(1, 1, True)
    tr.finalize(1000, 0)
    rec = FlightRecorder()
    rec.configure(dir=str(tmp_path))
    rec.record_barrier(tr)
    rec.close()
    out = subprocess.run(
        [
            sys.executable,
            "-m",
            "risingwave_tpu",
            "blackbox",
            str(tmp_path),
            "--roofline",
        ],
        capture_output=True,
        text=True,
    )
    assert out.returncode == 0, out.stderr
    assert "blackbox roofline:" in out.stdout
    assert "modeled" in out.stdout and "padding" in out.stdout
    assert "model=10.0MB" in out.stdout


# ---------------------------------------------------------------------------
# recovery / rebuild re-arms deviceprof; no orphaned captures
# ---------------------------------------------------------------------------


def test_rebuild_rearms_deviceprof_without_orphans():
    from risingwave_tpu.connectors.nexmark import BID_SCHEMA
    from risingwave_tpu.runtime.fragmenter import graph_planned_mv
    from risingwave_tpu.sql import Catalog, StreamPlanner

    DEVICEPROF.arm()
    factory = lambda: StreamPlanner(
        Catalog({"bid": BID_SCHEMA}), capacity=1 << 11
    )
    mv = graph_planned_mv(
        factory,
        "CREATE MATERIALIZED VIEW q5 AS SELECT auction, window_start, "
        "count(*) AS num FROM HOP(bid, date_time, INTERVAL '2' SECOND, "
        "INTERVAL '10' SECOND) GROUP BY auction, window_start",
        parallelism=1,
    )
    try:
        (bid,) = _chunks(1, chunks_per_epoch=1)[0]
        mv.pipeline.push(bid)
        mv.pipeline.barrier()
        assert DEVICEPROF.telemetry, "fused barrier produced no telemetry"
        programs_before = set(DEVICEPROF.report()["programs"])
        assert programs_before
        # recovery hygiene: telemetry drops (stale), analyses survive
        # (the rebuilt fragment re-fuses into the same programs), and
        # no capture window exists to orphan
        DEVICEPROF.on_recovery()
        assert DEVICEPROF.telemetry == {}
        mv.pipeline.rebuild()
        mv.pipeline.push(bid)
        mv.pipeline.barrier()
        assert DEVICEPROF.telemetry, "rebuilt fragment lost telemetry"
        assert set(DEVICEPROF.report()["programs"]) >= programs_before
        assert DEVICEPROF.report()["analysis_errors"] == 0
    finally:
        mv.pipeline.close()


# ---------------------------------------------------------------------------
# padding accounting + provenance
# ---------------------------------------------------------------------------


def test_padding_fraction_weighted():
    assert padding_fraction([]) == 0.0
    assert padding_fraction([(100, 100, 8)]) == 0.0
    assert padding_fraction([(100, 0, 8)]) == 1.0
    # weighting: the wide table's waste dominates
    got = padding_fraction([(100, 50, 30), (100, 100, 10)])
    assert got == pytest.approx(0.375)
    # live beyond capacity clamps (occupancy counts tombstones)
    assert padding_fraction([(64, 1000, 8)]) == 0.0


def test_provenance_stamp():
    from risingwave_tpu.provenance import ENGINE_GENERATION, stamp

    s = stamp()
    assert s["engine_generation"] == ENGINE_GENERATION >= 11
    assert isinstance(s["git_sha"], str) and s["git_sha"]
    assert isinstance(s["pr_tag"], str)
