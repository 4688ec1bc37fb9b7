"""Fusion-feasibility analyzer (analysis/fusion_analyzer.py +
analysis/shape_domain.py): seeded chains must classify exactly —
device-fusible proofs for pure chains, RW-E801 host-sync blockers with
file:line provenance, RW-E803 for the unbucketed-window q7 wedge class
— and the CLI / perf-gate / DDL / bench surfaces must carry the
reports. CPU-only, tier-1."""

import json

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.analysis.fusion_analyzer import (
    analyze_chain,
    analyze_nexmark,
    analyze_pipeline,
    classify_executor,
    report_to_json,
    scan_host_syncs,
)
from risingwave_tpu.analysis.shape_domain import (
    ChunkSpec,
    capacity_bucket,
    trace_signature,
)
from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Executor
from risingwave_tpu.executors.filter import FilterExecutor
from risingwave_tpu.executors.hop_window import HopWindowExecutor
from risingwave_tpu.executors.project import ProjectExecutor
from risingwave_tpu.expr import expr as E

pytestmark = pytest.mark.smoke

BID_SCHEMA = {"auction": "int64", "date_time": "int64", "price": "int64"}


def _spec(**over):
    schema = dict(BID_SCHEMA)
    schema.update(over)
    return ChunkSpec.from_schema(schema, capacity=256)


# ---------------------------------------------------------------------------
# shape domain
# ---------------------------------------------------------------------------


def test_chunk_spec_abstract_traces():
    spec = _spec()
    sig = trace_signature(lambda c: c.mask(c.col("price") > 0), spec)
    assert sig.in_avals and sig.out_avals
    assert not sig.host_calls
    # unknown dtypes refuse to guess
    assert ChunkSpec.from_schema({"a": None}) is None


def test_capacity_bucket_pow2():
    assert capacity_bucket(1) == 1
    assert capacity_bucket(5) == 8
    assert capacity_bucket(1024) == 1024


# ---------------------------------------------------------------------------
# seeded chains
# ---------------------------------------------------------------------------


class HostSyncingExecutor(Executor):
    """Deliberately host-syncing: reads a device scalar per chunk."""

    def apply(self, chunk):
        n = int(jnp.sum(chunk.valid))  # the blocker under test
        if n > 0:
            return [chunk]
        return [chunk]

    def trace_contract(self):
        return {
            "kind": "device",
            "trace_step": lambda c: c,
            "state": None,
            "donate": True,
            "emission": "passthrough",
        }


class UndonatedStatefulExecutor(Executor):
    def __init__(self):
        self.state = jnp.zeros(8, jnp.int64)

    def trace_contract(self):
        return {
            "kind": "device",
            "trace_step": lambda c: c,
            "state": self.state,
            "donate": False,
            "emission": "passthrough",
        }


def test_e801_host_sync_with_exact_provenance():
    chain = [HopWindowExecutor("date_time", 10_000, 2_000),
             HostSyncingExecutor()]
    rep = analyze_chain(chain, _spec(), "seeded")
    assert rep.fusible_prefix == 1  # hop proves; the syncer stops it
    assert not rep.whole_chain_fusible
    e801 = [d for d in rep.diagnostics if d.code == "RW-E801"]
    assert e801, rep.diagnostics
    # exact executor + file:line provenance
    assert all(d.executor == "1:HostSyncingExecutor" for d in e801)
    assert any(
        "test_fusion_analyzer" in d.message and ":" in d.message
        for d in e801
    ), [d.message for d in e801]
    # the scanner names the sync site inside apply
    syncs = scan_host_syncs(HostSyncingExecutor())
    assert any(s.method.endswith(".apply") for s in syncs)


def test_e804_undonated_state():
    ec = classify_executor(UndonatedStatefulExecutor(), _spec(), "f", 0)
    assert any(d.code == "RW-E804" for d in ec.blockers)
    assert not ec.fusible


def test_fully_fusible_chain_whole_fragment_proof():
    chain = [
        HopWindowExecutor("date_time", 10_000, 2_000),
        FilterExecutor(E.col("price") > E.lit(10)),
        ProjectExecutor({"auction": E.col("auction")}),
    ]
    rep = analyze_chain(chain, _spec(), "pure")
    assert rep.whole_chain_fusible, [
        (e.name, e.kind, [d.code for d in e.blockers])
        for e in rep.executors
    ]
    assert rep.fusible_prefix == 3
    assert rep.host_sync_points == 0
    # the proof is positive: every executor traced over the lattice
    assert all(e.signatures >= 1 for e in rep.executors)


def test_e803_q7_window_path():
    """The q7 wedge class statically: the deliberately-UNBUCKETED twin
    (``build_q7(bucketed=False)`` — the legacy unbounded-rehash path)
    must yield RW-E803 with exact executor provenance on both the
    dynamic max filter and the join; the SHIPPED bucketed q7 (the lint
    corpus) must be clean — its executors declare the allocator's pow2
    lattice (array/lattice.py)."""
    from risingwave_tpu.analysis.lint import (
        NEXMARK_SOURCE_SCHEMAS,
        build_nexmark_corpus,
    )
    from risingwave_tpu.queries.nexmark_q import build_q7

    twin = build_q7(
        capacity=1 << 8, agg_capacity=1 << 8, filter_capacity=1 << 8,
        out_cap=1 << 8, bucketed=False,
    )
    reports = analyze_pipeline(
        twin.pipeline, NEXMARK_SOURCE_SCHEMAS["q7"], "q7twin"
    )
    e803 = [
        d
        for r in reports
        for d in r.diagnostics
        if d.code == "RW-E803"
    ]
    assert e803
    provs = {d.executor for d in e803}
    assert any("DynamicMaxFilterExecutor" in p for p in provs), provs
    assert any("HashJoinExecutor" in p for p in provs), provs
    # the shipped (bucketed) corpus q7 walks free of the wedge class —
    # the PR-9 acceptance bar: zero RW-E803/E806 on q7's fragments
    q7 = build_nexmark_corpus(only="q7")["q7"]
    q7_reports = analyze_pipeline(
        q7.pipeline, NEXMARK_SOURCE_SCHEMAS["q7"], "q7"
    )
    assert not [
        d
        for r in q7_reports
        for d in r.diagnostics
        if d.code in ("RW-E803", "RW-E806")
    ]
    # q5's windowed agg declares its two-capacity flush lattice: the
    # SAME window machinery, bucketed, must NOT flag
    q5 = build_nexmark_corpus(only="q5")["q5"]
    q5_reports = analyze_pipeline(
        q5.pipeline, NEXMARK_SOURCE_SCHEMAS["q5"], "q5"
    )
    assert not [
        d
        for r in q5_reports
        for d in r.diagnostics
        if d.code == "RW-E803"
    ]


def test_every_nexmark_fragment_classified():
    """Acceptance shape: every fragment carries a whole-chain fusible
    proof or >=1 named RW-E8xx blocker with executor provenance."""
    out = analyze_nexmark(deep=True)
    # provenance rides every regenerated report (stale-artifact
    # detection, PR 11) under a "_"-prefixed key the ratchet skips
    prov = out.pop("_provenance")
    assert prov["engine_generation"] >= 11
    assert set(out) == {"q5", "q7", "q8"}
    for q, rep in out.items():
        assert rep["fragments"], q
        for fr in rep["fragments"]:
            assert fr["whole_chain_fusible"] or any(
                b["code"].startswith("RW-E8") and b["executor"]
                for b in fr["blockers"]
            ), (q, fr)
    # the fused-step PRs burned the corpus down: q5's hop->agg->MV
    # fragment AND every q7/q8 fragment (filter/dedup sides, the
    # join_tail) carry whole-chain fusible proofs with ZERO host syncs
    # (PR 13: note-based growth planning + cold-tier hooks + the
    # join's declared input schema re-anchoring the join_tail trace)
    for q in ("q5", "q7", "q8"):
        for fr in out[q]["fragments"]:
            assert fr["whole_chain_fusible"], (q, fr)
            assert fr["host_sync_points"] == 0, (q, fr)


def test_opaque_executor_stops_prefix():
    class NoContract(Executor):
        def trace_contract(self):
            return None

    chain = [
        HopWindowExecutor("date_time", 10_000, 2_000),
        NoContract(),
        ProjectExecutor({"auction": E.col("auction")}),
    ]
    rep = analyze_chain(chain, _spec(), "opaque")
    assert rep.fusible_prefix == 1
    assert rep.executors[1].kind == "opaque"


# ---------------------------------------------------------------------------
# surfaces: report JSON, perf gate, DDL, bench, SignatureWatch buckets
# ---------------------------------------------------------------------------


def test_report_json_shape_and_summary():
    chain = [ProjectExecutor({"auction": E.col("auction")})]
    rep = report_to_json([analyze_chain(chain, _spec(), "one")])
    assert rep["summary"]["fragments"] == 1
    assert rep["summary"]["fusible_fragments"] == 1
    fr = rep["fragments"][0]
    assert fr["executors"][0]["executor"] == "ProjectExecutor"
    json.dumps(rep)  # JSON-serializable end to end


def test_fusion_ratchet_clean_and_regression(tmp_path):
    import sys

    sys.path.insert(0, "scripts")
    try:
        from lint_all import _load, run_fusion_gate
    finally:
        sys.path.pop(0)

    v, skipped = run_fusion_gate("FUSION_REPORT.json")
    assert v == [], v  # committed baseline is green
    # injected regression: baseline claims a longer fusible prefix
    # (q5, already whole-chain) and fewer fallback sync points than
    # reality (the q7 agg side's interpreted-path flush read) -> the
    # ratchet trips on both axes. Host-sync counts are ZERO corpus-
    # wide since PR 13, so the sync ratchet is exercised through the
    # fallback ledger.
    base = _load("FUSION_REPORT.json")
    frag = base["q5"]["fragments"][0]
    frag["fusible_prefix"] += 1
    synced = next(
        f
        for f in base["q7"]["fragments"]
        if f.get("fallback_sync_points", 0) > 0
    )
    synced["fallback_sync_points"] = 0
    p = tmp_path / "base.json"
    p.write_text(json.dumps(base))
    v, _ = run_fusion_gate(str(p))
    assert any("fusible prefix regressed" in x for x in v), v
    assert any("fallback-sync points grew" in x for x in v), v
    # unreadable baseline skips, never crashes CI
    v, skipped = run_fusion_gate(str(tmp_path / "nope.json"))
    assert v == [] and skipped


def test_ddl_fusion_findings_and_strict_gate(monkeypatch):
    """Strict-fusion is ON BY DEFAULT now that the bucketing layer
    exists: an unbucketed (E803) window-keyed plan is refused at
    CREATE MV; the shipped bucketed q7 sails through; and
    RW_STRICT_FUSION=0 restores report-only mode."""
    from risingwave_tpu.analysis.diagnostics import PlanLintError
    from risingwave_tpu.analysis.lint import fusion_findings_for_ddl
    from risingwave_tpu.frontend.session import SqlSession
    from risingwave_tpu.queries.nexmark_q import build_q7
    from risingwave_tpu.runtime import StreamingRuntime
    from risingwave_tpu.sql import Catalog

    twin = build_q7(capacity=1 << 8, agg_capacity=1 << 8,
                    filter_capacity=1 << 8, out_cap=1 << 8,
                    bucketed=False)

    class Shim:
        name = "q7"
        pipeline = twin.pipeline

    diags = fusion_findings_for_ddl(Shim())
    assert diags and all(d.code == "RW-E803" for d in diags)

    q7 = build_q7(capacity=1 << 8, agg_capacity=1 << 8,
                  filter_capacity=1 << 8, out_cap=1 << 8)

    class CleanShim:
        name = "q7clean"
        pipeline = q7.pipeline

    assert fusion_findings_for_ddl(CleanShim()) == []

    session = SqlSession(Catalog({}), StreamingRuntime(store=None))
    monkeypatch.delenv("RW_STRICT_FUSION", raising=False)
    # strict by default: the wedge class is refused at CREATE MV
    with pytest.raises(PlanLintError):
        session._fusion_lint(Shim(), strict=True)
    # ... but the bucketed plan is not
    session._fusion_lint(CleanShim(), strict=True)
    # RW_STRICT_FUSION=0: report-only (records, never raises)
    monkeypatch.setenv("RW_STRICT_FUSION", "0")
    session._fusion_lint(Shim(), strict=True)
    assert any(
        d.code == "RW-E803" for _n, d in session.lint_findings
    )
    monkeypatch.setenv("RW_STRICT_FUSION", "1")
    with pytest.raises(PlanLintError):
        session._fusion_lint(Shim(), strict=True)
    # strict_lint=False (e.g. DDL replay) still never refuses
    session._fusion_lint(Shim(), strict=False)


def test_bench_gate_returns_fusion_summary():
    import bench

    fusion = bench._rwlint_gate("q5")
    assert fusion is not None
    assert fusion["summary"]["chain_len_total"] == 3
    assert fusion["fragments"][0]["fusible_prefix"] >= 1
    assert all("blocker_codes" in f for f in fusion["fragments"])


def test_signature_watch_records_shape_bucket():
    from risingwave_tpu.analysis.jax_sanitizer import SignatureWatch
    from risingwave_tpu.metrics import REGISTRY

    watch = SignatureWatch().start()
    ex = ProjectExecutor({"x": E.col("a")})
    watch.observe(ex, StreamChunk.from_numpy({"a": np.arange(4)}, 4))
    watch.mark_stable()
    before = REGISTRY.counter("recompile_hazard_bucket_total").get(
        executor="ProjectExecutor", bucket="32"
    )
    watch.observe(ex, StreamChunk.from_numpy({"a": np.arange(8)}, 32))
    diags = watch.report()
    assert [d.code for d in diags] == ["RW-E403"]
    # the hazard names the capacity bucket and cross-references the
    # static finding class
    assert "bucket" in diags[0].message and "RW-E803" in diags[0].message
    assert (
        REGISTRY.counter("recompile_hazard_bucket_total").get(
            executor="ProjectExecutor", bucket="32"
        )
        == before + 1
    )
    watch.stop()


def test_lint_cli_fusion_report_json(capsys):
    """python -m risingwave_tpu lint --fusion-report --all-nexmark
    --json: classifies every fragment; the bucketed corpus carries
    ZERO RW-E803/E806 (the PR-9 acceptance bar) AND zero RW-E801
    (the PR-13 two-input burn-down: the whole corpus is host-sync
    free on its hot paths)."""
    import argparse

    from risingwave_tpu.analysis.lint import run_cli

    args = argparse.Namespace(
        paths=[],
        all_nexmark=True,
        deep=False,
        json=True,
        fusion_report=True,
    )
    rc = run_cli(args)
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    fus = out["__fusion__"]
    assert "_provenance" in fus  # stamped for stale-artifact detection
    assert set(fus) - {"_provenance"} == {"q5", "q7", "q8"}
    for q in list(fus):
        if q.startswith("_"):
            continue
        assert not any(
            b["code"] in ("RW-E801", "RW-E803", "RW-E806")
            for fr in fus[q]["fragments"]
            for b in fr["blockers"]
        ), q


# ---------------------------------------------------------------------------
# satellite: lint_info coverage on previously-opaque executors
# ---------------------------------------------------------------------------


def test_new_lint_info_coverage_visible_to_verifier():
    """The satellite executors expose real metadata now: a seeded
    missing-column plan is caught (no more silent opacity)."""
    from risingwave_tpu.analysis.diagnostics import LintReport
    from risingwave_tpu.analysis.plan_verifier import _walk_chain, _TableIds
    from risingwave_tpu.executors.simple_agg import SimpleAggExecutor
    from risingwave_tpu.ops.agg import AggCall

    agg = SimpleAggExecutor(
        (AggCall("sum", "missing_col", "s"),),
        {"missing_col": jnp.int64},
        table_id="t.simple",
    )
    rep = LintReport()
    _walk_chain(
        [agg], {"a": jnp.dtype("int64")}, {"a"}, "f", rep, _TableIds(rep)
    )
    assert any(d.code == "RW-E101" for d in rep.diagnostics)


def test_new_lint_info_smoke_all_satellites():
    """Every satellite executor returns a dict (not None, not raising)
    so the verifier and the fusion analyzer both see it."""
    from risingwave_tpu.executors.epoch_batch import (
        EpochBatchedAggExecutor,
    )
    from risingwave_tpu.executors.expand import ExpandExecutor
    from risingwave_tpu.executors.hash_agg import HashAggExecutor
    from risingwave_tpu.executors.lookup import (
        DeltaJoinExecutor,
        IndexArrangement,
    )
    from risingwave_tpu.executors.over_window import (
        OverWindowExecutor,
        WindowCall,
    )
    from risingwave_tpu.executors.project_set import ProjectSetExecutor
    from risingwave_tpu.executors.simple_agg import SimpleAggExecutor
    from risingwave_tpu.executors.sort import SortExecutor
    from risingwave_tpu.executors.temporal_join import (
        TemporalJoinExecutor,
    )
    from risingwave_tpu.ops.agg import AggCall

    dt = {"a": jnp.int64, "t": jnp.int64}
    left = IndexArrangement(("a",), ("t",), ("a", "t"), "t.l")
    right = IndexArrangement(("a",), ("t",), ("a", "t"), "t.r")
    agg = HashAggExecutor(
        group_keys=("a",),
        calls=(AggCall("count_star", None, "n"),),
        schema_dtypes=dt,
        capacity=64,
        table_id="t.agg",
    )
    execs = [
        SimpleAggExecutor(
            (AggCall("count_star", None, "n"),), dt, table_id="t.sa"
        ),
        SortExecutor("t", dt, capacity=64, table_id="t.sort"),
        TemporalJoinExecutor(left, ("a",), ("a",)),
        DeltaJoinExecutor(
            left, right, ("a",), ("a",),
            (("a", "a"),), (("t2", "t"),),
        ),
        OverWindowExecutor(
            ("a",), (WindowCall("count", None, "n"),), dt,
            capacity=64, table_id="t.ow",
        ),
        ExpandExecutor((("a",), ("t",))),
        ProjectSetExecutor(
            "generate_series", out="v", start_col="a", stop_col="t"
        ),
        EpochBatchedAggExecutor([], agg),
    ]
    for ex in execs:
        info = ex.lint_info()
        assert isinstance(info, dict), type(ex).__name__
        # and a trace contract (or an honest host classification)
        contract = ex.trace_contract()
        assert contract is None or contract["kind"] in (
            "device",
            "host",
        ), type(ex).__name__


def test_epoch_batch_lint_info_composes():
    """The wrapper's metadata equals walking its members: requires
    trace back through the prefix, the agg's emits surface, and
    opacity propagates when a member is opaque."""
    from risingwave_tpu.executors.epoch_batch import (
        EpochBatchedAggExecutor,
    )
    from risingwave_tpu.executors.hash_agg import HashAggExecutor
    from risingwave_tpu.ops.agg import AggCall

    hop = HopWindowExecutor("date_time", 10_000, 2_000)
    agg = HashAggExecutor(
        group_keys=("auction", "window_start"),
        calls=(AggCall("count_star", None, "num"),),
        schema_dtypes={
            "auction": jnp.int64,
            "window_start": jnp.int64,
        },
        capacity=64,
        table_id="t.q5agg",
    )
    wrapper = EpochBatchedAggExecutor([hop], agg)
    info = wrapper.lint_info()
    # window_start is hop-computed: the wrapper requires only true
    # input columns
    assert set(info["requires"]) == {"auction", "date_time"}
    assert "num" in info["emits"]
    assert info["table_ids"] == ("t.q5agg",)
    assert info["watermark_map"] == {"date_time": "window_start"}

    class Opaque(Executor):
        def pure_step(self):
            return None

    agg2 = HashAggExecutor(
        group_keys=("auction",),
        calls=(AggCall("count_star", None, "num"),),
        schema_dtypes={"auction": jnp.int64},
        capacity=64,
        table_id="t.q5agg2",
    )
    try:
        w2 = EpochBatchedAggExecutor([Opaque()], agg2)
    except ValueError:
        return  # wrapper refuses opaque prefixes outright: also fine
    assert w2.lint_info() is None
