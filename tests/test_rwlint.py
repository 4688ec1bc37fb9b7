"""rwlint (analysis/): plan-graph verifier + JAX compilation sanitizer.

Positive half: every built-in Nexmark query and graph-mode SQL plan
lints clean, and the DDL-time budget holds. Negative half: ~10 seeded
malformed plans, each rejected AT CREATE-MV TIME with its exact
RW-E### code and fragment/executor provenance — never a runtime crash
or wrong result.
"""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from risingwave_tpu.analysis import PlanLintError, lint_all_nexmark
from risingwave_tpu.analysis.diagnostics import Diagnostic, LintReport
from risingwave_tpu.analysis.jax_sanitizer import (
    RecompileWatch,
    SignatureWatch,
    check_donation,
    check_hash_path_32bit,
    check_promotions,
    sanitize_executors,
    sanitize_hash_kernels,
)
from risingwave_tpu.analysis.lint import lint_pipeline, lint_planned
from risingwave_tpu.analysis.plan_verifier import verify_planned
from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors import HashAggExecutor, ProjectExecutor
from risingwave_tpu.executors.materialize import DeviceMaterializeExecutor
from risingwave_tpu.expr import expr as E
from risingwave_tpu.frontend.session import SqlSession
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.runtime import Pipeline, StreamingRuntime
from risingwave_tpu.runtime.graph import FragmentSpec
from risingwave_tpu.sql import Catalog
from risingwave_tpu.sql.planner import PlannedMV
from risingwave_tpu.types import DataType, Field, Schema

pytestmark = pytest.mark.smoke

I64 = jnp.int64


def _agg(keys=("a",), tid="t.agg", dtypes=None, window_key=None, cap=64):
    return HashAggExecutor(
        group_keys=keys,
        calls=(AggCall("count_star", None, "n"),),
        schema_dtypes=dtypes or {k: I64 for k in keys},
        capacity=cap,
        out_cap=cap,
        table_id=tid,
        window_key=window_key,
    )


def _src_catalog(cols=("a", "b")):
    return Catalog(
        {"src": Schema([Field(c, DataType.INT64) for c in cols])}
    )


def _session(catalog=None, strict=True):
    return SqlSession(
        catalog or _src_catalog(),
        StreamingRuntime(store=None),
        strict_lint=strict,
    )


def _planned(pipeline, name="bad"):
    return PlannedMV(
        name, pipeline, None, {"src": "single"}, schema={"a": I64}
    )


def _ddl_reject(pipeline, code, *, fragment=None, catalog=None):
    """The malformed plan must be refused AT CREATE-MV TIME with the
    exact diagnostic — DDL raises, nothing registers."""
    session = _session(catalog=catalog)
    session.planner.plan = lambda sql: _planned(pipeline)
    with pytest.raises(PlanLintError) as ei:
        session.execute("CREATE MATERIALIZED VIEW bad AS SELECT a FROM src")
    msg = str(ei.value)
    assert code in msg
    if fragment is not None:
        assert f"frag={fragment}" in msg
    assert "bad" not in session.runtime.fragments  # nothing registered
    return msg


class _FakeGraph:
    """GraphPipeline-shaped stub: specs without spawning actor threads
    (a genuinely mis-wired GraphRuntime would crash in _build before
    lint could speak — the verifier runs on the SPEC level)."""

    def __init__(self, specs, sources=None, out="mv"):
        self._specs = list(specs)
        self.graph = None
        self._sources = sources or {"single": specs[0].name}
        self._out = out


# ---------------------------------------------------------------------------
# positive: the shipped plans lint clean
# ---------------------------------------------------------------------------


def test_all_nexmark_builders_clean():
    out = lint_all_nexmark(strict=True)  # strict: errors would raise
    assert set(out) == {"q5", "q7", "q8"}
    assert all(not diags for diags in out.values())


def test_sql_create_mv_lints_clean_and_under_budget():
    session = _session(
        Catalog(
            {
                "bid": Schema(
                    [
                        Field("auction", DataType.INT64),
                        Field("price", DataType.INT64),
                        Field("date_time", DataType.INT64),
                    ]
                )
            }
        )
    )
    from risingwave_tpu.metrics import REGISTRY

    before = REGISTRY.histogram("lint_ms").count()
    session.execute(
        "CREATE MATERIALIZED VIEW v AS SELECT auction, count(*) AS n "
        "FROM bid GROUP BY auction"
    )
    assert not [d for _n, d in session.lint_findings]
    h = REGISTRY.histogram("lint_ms")
    assert h.count() > before  # the DDL hook really ran
    # budget: <50ms per CREATE MV (pure metadata walking)
    t0 = time.perf_counter()
    planned = session.catalog.mvs["v"]
    lint_planned(planned, catalog=session.catalog, strict=True)
    assert (time.perf_counter() - t0) * 1e3 < 50


def test_graph_mode_create_mv_lints_clean():
    session = SqlSession(
        Catalog(
            {
                "bid": Schema(
                    [
                        Field("auction", DataType.INT64),
                        Field("price", DataType.INT64),
                    ]
                )
            }
        ),
        StreamingRuntime(store=None),
        exec_mode="graph",
        parallelism=2,
        strict_lint=True,
    )
    session.execute(
        "CREATE MATERIALIZED VIEW g AS SELECT auction, count(*) AS n "
        "FROM bid GROUP BY auction"
    )
    assert not [d for _n, d in session.lint_findings]


# ---------------------------------------------------------------------------
# negative: seeded malformed plans -> exact RW-E### at DDL time
# ---------------------------------------------------------------------------


def test_e101_schema_mismatch_project_drops_column():
    chain = [
        ProjectExecutor({"x": E.col("a")}),  # drops 'b'
        _agg(keys=("b",)),
    ]
    msg = _ddl_reject(Pipeline(chain), "RW-E101", fragment="bad")
    assert "1:HashAggExecutor" in msg  # executor provenance


def test_e102_dtype_mismatch_vs_declared():
    chain = [_agg(keys=("a",), dtypes={"a": jnp.int32})]  # src says int64
    msg = _ddl_reject(Pipeline(chain), "RW-E102", fragment="bad")
    assert "int32" in msg and "int64" in msg


def test_e201_dispatch_key_missing_upstream():
    specs = [
        FragmentSpec("src", lambda i: [], dispatch=("hash", ["zz"])),
        FragmentSpec(
            "par",
            lambda i: [_agg(keys=("a",))],
            inputs=[("src", 0)],
            parallelism=2,
        ),
    ]
    _ddl_reject(_FakeGraph(specs, out="par"), "RW-E201", fragment="src")


def test_e202_key_misalignment_across_exchange():
    # dispatch hashes 'a' but the parallel agg groups by 'b': rows of
    # one group land on different instances -> split state
    specs = [
        FragmentSpec("src", lambda i: [], dispatch=("hash", ["a"])),
        FragmentSpec(
            "par",
            lambda i: [_agg(keys=("b",))],
            inputs=[("src", 0)],
            parallelism=2,
        ),
    ]
    msg = _ddl_reject(_FakeGraph(specs, out="par"), "RW-E202", fragment="src")
    assert "'a'" in msg and "par" in msg


def test_e203_round_robin_into_keyed_state():
    specs = [
        FragmentSpec("src", lambda i: [], dispatch="round_robin"),
        FragmentSpec(
            "par",
            lambda i: [_agg(keys=("a",))],
            inputs=[("src", 0)],
            parallelism=2,
        ),
    ]
    _ddl_reject(_FakeGraph(specs, out="par"), "RW-E203", fragment="src")


def test_e204_join_key_dtype_mismatch():
    # the real HashJoinExecutor refuses this in its constructor; the
    # verifier must still catch a join-like executor that declares it
    class _BadJoin:
        table_id = "bad.join"

        def lint_info(self):
            return {
                "left_keys": ("k",),
                "right_keys": ("j",),
                "expects_left": {"k": jnp.int64},
                "expects_right": {"j": jnp.int32},
                "emits": {"k": jnp.int64, "j": jnp.int32},
            }

    from risingwave_tpu.runtime.pipeline import TwoInputPipeline

    tp = TwoInputPipeline([], [], _BadJoin(), [])
    rep = [
        d
        for d in verify_planned(
            _planned(tp),
            source_schemas={
                "left": {"k": jnp.int64},
                "right": {"j": jnp.int32},
            },
        )
    ]
    assert any(d.code == "RW-E204" for d in rep)


def test_e501_window_key_unreachable_by_watermarks():
    # 'w' is a COMPUTED project output (not a rename, not a hop window
    # start): no watermark can ever reach it, state grows forever
    chain = [
        ProjectExecutor({"w": E.col("a") + E.col("b"), "g": E.col("b")}),
        _agg(keys=("g", "w"), window_key=("w", 0, False)),
    ]
    _ddl_reject(Pipeline(chain), "RW-E501", fragment="bad")


def test_e601_dangling_channel():
    specs = [
        FragmentSpec("mv", lambda i: [], inputs=[("ghost", 0)]),
    ]
    _ddl_reject(_FakeGraph(specs, out="mv"), "RW-E601", fragment="mv")


def test_e602_duplicate_edge():
    specs = [
        FragmentSpec("src", lambda i: []),
        FragmentSpec(
            "mv", lambda i: [], inputs=[("src", 0), ("src", 0)]
        ),
    ]
    _ddl_reject(_FakeGraph(specs, out="mv"), "RW-E602", fragment="mv")


def test_e603_cyclic_fragment_graph():
    specs = [
        FragmentSpec("x", lambda i: [], inputs=[("y", 0)]),
        FragmentSpec("y", lambda i: [], inputs=[("x", 0)]),
    ]
    msg = _ddl_reject(
        _FakeGraph(specs, sources={"single": "x"}, out="x"), "RW-E603"
    )
    assert "'x'" in msg and "'y'" in msg


def test_e604_unconsumed_fragment():
    specs = [
        FragmentSpec("src", lambda i: []),
        FragmentSpec("mv", lambda i: [], inputs=[("src", 0)]),
        FragmentSpec("stray", lambda i: [], inputs=[("src", 0)]),
    ]
    _ddl_reject(_FakeGraph(specs, out="mv"), "RW-E604", fragment="stray")


def test_e605_missing_out_fragment():
    specs = [FragmentSpec("src", lambda i: [])]
    _ddl_reject(_FakeGraph(specs, out="ghost"), "RW-E605", fragment="ghost")


def test_e606_stateful_fragment_without_rebuildable_boundary():
    """A GraphPipeline whose checkpoint registry does not cover a
    fragment's stateful executor can never be PARTIALLY recovered (its
    state checkpoints nowhere restorable) — refused at DDL time."""
    from risingwave_tpu.runtime.fragmenter import GraphPipeline

    agg = _agg(keys=("a",), tid="orphan.agg")
    specs = [
        FragmentSpec("src", lambda i: []),
        FragmentSpec(
            "work", lambda i, a=agg: [a], inputs=[("src", 0)]
        ),
    ]
    # registry deliberately omits the agg: nothing can restore it
    gp = GraphPipeline(
        specs, {"single": "src"}, "work", [], ckpt_fragments=[]
    )
    try:
        msg = _ddl_reject(gp, "RW-E606", fragment="work")
        assert "orphan.agg" in msg
    finally:
        gp.close()


def test_e606_registry_entry_without_restore_state():
    """A checkpoint-registry entry that checkpoints but never
    implements restore_state is flagged too (its deltas persist into a
    table no recovery path can read back)."""
    from risingwave_tpu.runtime.fragmenter import GraphPipeline
    from risingwave_tpu.storage.state_table import Checkpointable

    class WriteOnlyState(Checkpointable):
        table_id = "writeonly.t"

        def checkpoint_delta(self):
            return []

        # restore_state deliberately NOT implemented

    wo = WriteOnlyState()
    specs = [
        FragmentSpec("src", lambda i: []),
        FragmentSpec("work", lambda i: [], inputs=[("src", 0)]),
    ]
    gp = GraphPipeline(
        specs, {"single": "src"}, "work", [wo], ckpt_fragments=["work"]
    )
    try:
        msg = _ddl_reject(gp, "RW-E606")
        assert "restore_state" in msg and "WriteOnlyState" in msg
    finally:
        gp.close()


def test_e606_negative_fragmenter_plans_are_rebuildable():
    """The fragmenter's own graph plans always carry a complete
    restorable registry — no E606 on the real CREATE-MV path."""
    from risingwave_tpu.runtime.fragmenter import graph_planned_mv
    from risingwave_tpu.sql.planner import StreamPlanner

    catalog = _src_catalog(("a", "b"))
    planned = graph_planned_mv(
        lambda: StreamPlanner(catalog),
        "CREATE MATERIALIZED VIEW g AS SELECT a, count(*) AS n "
        "FROM src GROUP BY a",
        parallelism=2,
    )
    try:
        diags = lint_planned(planned, catalog=catalog, strict=True)
        assert not [d for d in diags if d.code == "RW-E606"]
    finally:
        planned.pipeline.close()


def test_e701_state_pk_not_covered():
    mv = DeviceMaterializeExecutor(
        pk=("missing",),
        columns=("a",),
        schema_dtypes={"missing": I64, "a": I64},
        table_id="bad.mview",
        capacity=64,
    )
    msg = _ddl_reject(Pipeline([mv]), "RW-E701", fragment="bad")
    assert "missing" in msg


def test_e702_duplicate_table_id():
    chain = [
        _agg(keys=("a",), tid="dup.table"),
        _agg(keys=("a",), tid="dup.table"),
    ]
    _ddl_reject(Pipeline(chain), "RW-E702", fragment="bad")


from risingwave_tpu.executors.base import Executor as _ExecutorBase


class _GhostState(_ExecutorBase):
    """Registers a state table but is INVISIBLE to the memory ledger:
    no state_nbytes()/state_bytes() contract, no allocator capacity
    note. The RW-E708 target."""

    def apply(self, chunk):
        return [chunk]

    def lint_info(self):
        return {"table_ids": ("ghost.t",)}


def test_e708_unaccounted_state_reports_only_by_default(monkeypatch):
    """RW-E708 defaults to report-only even in strict sessions
    (promoting it would refuse pre-existing DDL): the CREATE MV goes
    through, the finding lands in lint_findings as a warning."""
    monkeypatch.delenv("RW_STRICT_LINT", raising=False)
    session = _session()
    chain = [_GhostState(), _agg(keys=("a",))]
    session.planner.plan = lambda sql: _planned(Pipeline(chain))
    session.execute("CREATE MATERIALIZED VIEW bad AS SELECT a FROM src")
    assert "bad" in session.runtime.fragments  # DDL accepted
    found = [d for _n, d in session.lint_findings if d.code == "RW-E708"]
    assert found and found[0].severity == "warning"
    assert "ghost.t" in found[0].message


def test_e708_refused_under_explicit_strict_lint(monkeypatch):
    """An EXPLICITLY-set truthy RW_STRICT_LINT (the __main__ opt-in)
    promotes unaccounted state to a refusal."""
    monkeypatch.setenv("RW_STRICT_LINT", "1")
    chain = [_GhostState(), _agg(keys=("a",))]
    msg = _ddl_reject(Pipeline(chain), "RW-E708", fragment="bad")
    assert "ghost.t" in msg and "ledger" in msg


def test_e708_builtin_stateful_executors_are_ledger_visible():
    """Every shipped stateful executor exposes the accounting contract
    the governor budgets from — the Nexmark corpus must walk free of
    RW-E708 (covered by test_all_nexmark_builders_clean) and the
    canonical state-holders answer state_nbytes() directly."""
    from risingwave_tpu.executors.materialize import MaterializeExecutor

    agg = _agg(keys=("a",))
    assert int(agg.state_nbytes()) >= 0
    mv = MaterializeExecutor(pk=("a",), columns=("n",), table_id="m.t")
    assert int(mv.state_nbytes()) >= 0
    dmv = DeviceMaterializeExecutor(
        pk=("a",),
        columns=("n",),
        schema_dtypes={"a": I64, "n": I64},
        table_id="m.d",
        capacity=64,
    )
    assert int(dmv.state_nbytes()) > 0


def test_non_strict_records_instead_of_raising():
    session = _session(strict=False)
    chain = [_agg(keys=("zz",))]  # 'zz' not in src
    session.planner.plan = lambda sql: _planned(Pipeline(chain))
    # non-strict: the DDL goes through, the finding is RECORDED
    session.execute("CREATE MATERIALIZED VIEW bad AS SELECT a FROM src")
    assert any(d.code == "RW-E101" for _n, d in session.lint_findings)
    assert "bad" in session.runtime.fragments


# ---------------------------------------------------------------------------
# Part B: compilation sanitizer
# ---------------------------------------------------------------------------


def test_hash_kernels_are_32bit_clean():
    assert sanitize_hash_kernels() == []


def test_e302_catches_64bit_hash_arithmetic():
    def bad_hash(ks):
        u = ks[0].astype(jnp.uint64)
        return ((u * jnp.uint64(0x9E3779B9)) >> jnp.uint64(32)).astype(
            jnp.uint32
        )

    diags = check_hash_path_32bit(
        bad_hash, (jnp.zeros(8, jnp.int64),), name="bad_hash"
    )
    assert any(d.code == "RW-E302" for d in diags)


def test_e301_catches_implicit_widening():
    def widens(x):
        return x.astype(jnp.int64) * 2

    diags = check_promotions(widens, jnp.zeros(8, jnp.int32), name="w")
    assert [d.code for d in diags] == ["RW-E301"]
    # and an all-64-bit step is NOT flagged (no promotion happened)
    assert check_promotions(lambda x: x * 2, jnp.zeros(8, jnp.int64)) == []


def test_q7_q8_sanitizer_clean():
    """Acceptance: dtype-promotion rules run clean on the q7/q8
    pipelines (every executor exposing a pure step)."""
    from risingwave_tpu.queries.nexmark_q import build_q7, build_q8

    q7 = build_q7(
        capacity=1 << 10,
        agg_capacity=1 << 10,
        filter_capacity=1 << 10,
        out_cap=1 << 10,
    )
    q8 = build_q8(capacity=1 << 10, out_cap=1 << 10)
    assert sanitize_executors(q7.pipeline.executors) == []
    assert sanitize_executors(q8.pipeline.executors) == []


def test_q7_pipeline_runs_clean_under_transfer_guard(monkeypatch):
    """Acceptance: the per-barrier device step holds no implicit
    host transfers (conftest arms RW_TRANSFER_GUARD globally; pin it
    here so the test is self-contained)."""
    monkeypatch.setenv("RW_TRANSFER_GUARD", "1")
    from risingwave_tpu.queries.nexmark_q import build_q7

    q7 = build_q7(
        capacity=1 << 10,
        agg_capacity=1 << 10,
        filter_capacity=1 << 10,
        out_cap=1 << 10,
    )
    rng = np.random.default_rng(11)
    cols = {
        "auction": rng.integers(0, 50, 128).astype(np.int64),
        "bidder": rng.integers(0, 50, 128).astype(np.int64),
        "price": rng.integers(1, 10_000, 128).astype(np.int64),
        "date_time": np.sort(rng.integers(0, 30_000, 128)).astype(np.int64),
    }
    c = StreamChunk.from_numpy(cols, 128)
    q7.pipeline.push_left(c)
    q7.pipeline.push_right(c)
    q7.pipeline.barrier()  # device fence runs under the armed guard
    q7.pipeline.watermark("date_time", 20_000)
    q7.pipeline.barrier()
    assert q7.mview.snapshot() is not None


def test_e401_donation():
    from risingwave_tpu.ops.hash_table import HashTable, lookup_or_insert

    t = HashTable.create(64, (jnp.dtype(jnp.int64),))
    keys = (jnp.zeros(8, jnp.int64),)
    valid = jnp.ones(8, jnp.bool_)
    # the state kernel donates its table: clean
    assert check_donation(lookup_or_insert, t, keys, valid) == []
    # an undonated twin is flagged
    undonated = jax.jit(lambda a, b: a + b)
    diags = check_donation(
        undonated, jnp.zeros(8), jnp.zeros(8), name="undonated"
    )
    assert [d.code for d in diags] == ["RW-E401"]


def test_e403_signature_watch_flags_shape_instability():
    from risingwave_tpu.metrics import REGISTRY

    watch = SignatureWatch().start()
    ex = ProjectExecutor({"x": E.col("a")})
    watch.observe(ex, StreamChunk.from_numpy({"a": np.arange(4)}, 4))
    watch.mark_stable()
    watch.observe(ex, StreamChunk.from_numpy({"a": np.arange(4)}, 4))
    assert watch.report() == []  # same signature: stable
    before = REGISTRY.counter("recompile_hazard_total").get(
        executor="ProjectExecutor"
    )
    watch.observe(ex, StreamChunk.from_numpy({"a": np.arange(8)}, 8))
    diags = watch.report()
    assert [d.code for d in diags] == ["RW-E403"]
    assert "ProjectExecutor" in diags[0].executor
    assert (
        REGISTRY.counter("recompile_hazard_total").get(
            executor="ProjectExecutor"
        )
        == before + 1
    )
    watch.stop()


def test_recompile_watch_counts_new_compiles():
    from risingwave_tpu.metrics import REGISTRY

    @jax.jit
    def f(x):
        return x + 1

    w = RecompileWatch([("f", f)])
    f(jnp.zeros(4))
    w.snapshot()
    assert w.deltas() == {}
    before = REGISTRY.counter("recompiles_total").get(fn="f")
    f(jnp.zeros(8))  # new shape -> new compile
    assert w.deltas(record=True) == {"f": 1}
    assert REGISTRY.counter("recompiles_total").get(fn="f") == before + 1
    # recording consumed the window: a second read never double-counts
    assert w.deltas(record=True) == {}
    assert w.total() == 0
    assert REGISTRY.counter("recompiles_total").get(fn="f") == before + 1


# ---------------------------------------------------------------------------
# CLI + SQL-file surface
# ---------------------------------------------------------------------------


def test_cli_all_nexmark_exits_zero():
    import argparse

    from risingwave_tpu.analysis.lint import run_cli

    rc = run_cli(
        argparse.Namespace(
            paths=[], all_nexmark=True, deep=True, json=True
        )
    )
    assert rc == 0


def test_lint_sql_file(tmp_path):
    from risingwave_tpu.analysis.lint import lint_sql_file

    p = tmp_path / "plan.sql"
    p.write_text(
        "CREATE TABLE bid (auction BIGINT, price BIGINT);\n"
        "CREATE MATERIALIZED VIEW v AS "
        "SELECT auction, count(*) AS n FROM bid GROUP BY auction;\n"
    )
    findings = lint_sql_file(str(p))
    assert all(not diags for diags in findings.values())


def test_lint_sql_file_comment_lines_do_not_swallow_ddl(tmp_path):
    """A `--` comment line shares its ';'-segment with the statement
    that follows it; the segment must still execute AND lint."""
    from risingwave_tpu.analysis.lint import lint_sql_file

    p = tmp_path / "plan.sql"
    p.write_text(
        "-- base tables; with a semicolon in the comment\n"
        "CREATE TABLE bid (auction BIGINT, price BIGINT);\n"
        "CREATE MATERIALIZED VIEW v AS "
        "SELECT auction, count(*) AS n FROM bid GROUP BY auction;\n"
    )
    # pre-fix the whole first segment (comment + CREATE TABLE) was
    # skipped and the MV blew up on the unknown relation
    findings = lint_sql_file(str(p))
    assert all(not diags for diags in findings.values())
    # and a statement directly behind a comment line is NOT silently
    # skipped: it executes (here: surfacing its unknown relation)
    p2 = tmp_path / "hidden.sql"
    p2.write_text(
        "-- hidden\nCREATE MATERIALIZED VIEW w AS SELECT x FROM nope;\n"
    )
    with pytest.raises(Exception, match="nope"):
        lint_sql_file(str(p2))


def test_cli_missing_sql_file_is_usage_error(tmp_path):
    """Exit-code contract: 2 = usage (vs 1 = lint errors), never a raw
    traceback, so CI wrappers can tell the cases apart."""
    import argparse

    from risingwave_tpu.analysis.lint import run_cli

    rc = run_cli(
        argparse.Namespace(
            paths=[str(tmp_path / "typo.sql")],
            all_nexmark=False,
            deep=False,
            json=False,
        )
    )
    assert rc == 2
    # same contract for a file whose SQL the session cannot execute
    bad = tmp_path / "bad.sql"
    bad.write_text("CREATE MATERIALIZED VIEW v AS SELECT x FROM nope;\n")
    rc = run_cli(
        argparse.Namespace(
            paths=[str(bad)], all_nexmark=False, deep=False, json=False
        )
    )
    assert rc == 2


def test_cli_bad_path_keeps_other_findings(tmp_path, capsys):
    """A later unreadable path must not drop findings already
    collected for other targets: exit 2, but the JSON still carries
    every linted target plus the errors."""
    import argparse
    import json as _json

    from risingwave_tpu.analysis.lint import run_cli

    rc = run_cli(
        argparse.Namespace(
            paths=[str(tmp_path / "typo.sql")],
            all_nexmark=True,
            deep=False,
            json=True,
        )
    )
    out = _json.loads(capsys.readouterr().out)
    assert rc == 2
    assert {"q5", "q7", "q8"} <= set(out)
    assert out["__errors__"] and "typo.sql" in out["__errors__"][0]


def test_lint_sql_file_skips_dml(tmp_path):
    """lint runs DDL only: INSERT seeds / smoke SELECTs in a deploy
    file must not execute (or abort the lint)."""
    from risingwave_tpu.analysis.lint import lint_sql_file

    p = tmp_path / "deploy.sql"
    p.write_text(
        "CREATE TABLE t (a BIGINT);\n"
        "INSERT INTO missing_elsewhere VALUES (1);\n"  # would raise
        "SELECT * FROM also_missing;\n"  # would raise
        "CREATE MATERIALIZED VIEW v AS "
        "SELECT a, count(*) AS n FROM t GROUP BY a;\n"
    )
    findings = lint_sql_file(str(p))  # must not abort on the DML
    assert all(not diags for diags in findings.values())


def test_restore_replay_is_never_refused_by_strict_lint(tmp_path):
    """DDL-log replay runs lint in record-only mode: a statement the
    store accepted must restore even under strict_lint (a lint-rule
    change must not brick recovery), and restore() threads the
    configured strictness into the session it returns."""
    from risingwave_tpu.storage.object_store import MemObjectStore

    store = MemObjectStore()
    s = SqlSession(Catalog({}), StreamingRuntime(store), strict_lint=True)
    s.execute("CREATE TABLE t (k BIGINT, v BIGINT)")
    s.execute(
        "CREATE MATERIALIZED VIEW mv AS "
        "SELECT k, sum(v) AS sv FROM t GROUP BY k"
    )
    s.runtime.wait_checkpoints()

    s2 = SqlSession.restore(StreamingRuntime(store), strict_lint=True)
    assert s2.strict_lint is True
    assert "mv" in s2.runtime.fragments
    # replayed DDL linted in record-only mode: strict flag preserved,
    # no PlanLintError even if a (hypothetical) new rule now fires —
    # simulate by replaying a session whose planner yields a bad plan
    bad = PlannedMV(
        "bad2",
        Pipeline([_agg(keys=("missing",), dtypes={"missing": I64})]),
        None,
        {"t": "single"},  # `t` IS in the restored catalog -> E101 fires
        schema={"k": I64},
    )
    s2._replaying = True
    try:
        s2._lint_planned(bad)  # must record, not raise
    finally:
        s2._replaying = False
    assert any(d.code == "RW-E101" for _n, d in s2.lint_findings)
    # same plan outside replay IS refused — strictness survived restore
    with pytest.raises(PlanLintError):
        s2._lint_planned(bad)


def test_graph_duplicate_create_reaps_actor_threads():
    """Graph pipelines spawn actor threads at PLAN time: a CREATE
    refused for ANY reason (here: duplicate name) must reap the doomed
    plan's actors, not leak them for the process lifetime."""
    import threading

    session = SqlSession(
        Catalog({"bid": Schema([Field("auction", DataType.INT64)])}),
        StreamingRuntime(store=None),
        exec_mode="graph",
        parallelism=2,
        strict_lint=True,
    )
    ddl = (
        "CREATE MATERIALIZED VIEW g AS SELECT auction, count(*) AS n "
        "FROM bid GROUP BY auction"
    )
    session.execute(ddl)
    n_live = lambda: sum(
        1 for t in threading.enumerate() if t.name.startswith("actor-")
    )
    before = n_live()
    with pytest.raises(ValueError, match="already exists"):
        session.execute(ddl)  # second plan spawned actors -> reaped
    deadline = time.perf_counter() + 5.0
    while n_live() > before and time.perf_counter() < deadline:
        time.sleep(0.02)
    assert n_live() <= before


def test_broken_lint_info_degrades_loudly_not_silently():
    """An executor whose lint_info() RAISES is not the same as one that
    advertises none: the verifier must surface an RW-E001 warning (not
    refuse the DDL, not stay silent) and go opaque past it."""

    class _Broken(ProjectExecutor):
        def lint_info(self):
            raise AttributeError("_dtypes gone")

    p = Pipeline([_Broken({"a": E.Col("a")})])
    diags = lint_pipeline(
        p, {"single": {"a": I64}}, name="mv", strict=True
    )  # strict: a warning must NOT raise
    assert [d.code for d in diags] == ["RW-E001"]
    assert diags[0].severity == "warning"
    assert "AttributeError" in diags[0].message
    assert "_Broken" in diags[0].executor

    # a JOIN executor's broken lint_info degrades just as loudly
    from risingwave_tpu.analysis.plan_verifier import (
        _TableIds,
        _verify_join,
    )

    class _BrokenJoin:
        def lint_info(self):
            raise RuntimeError("join metadata drifted")

    rep = LintReport()
    _verify_join(
        _BrokenJoin(), {"a": I64}, {"a": I64}, None, None,
        "mv", rep, _TableIds(rep),
    )
    jcodes = [d.code for d in rep.diagnostics]
    assert jcodes == ["RW-E001"], jcodes
    assert "join:_BrokenJoin" in rep.diagnostics[0].executor


def test_diagnostic_codes_are_closed_set():
    with pytest.raises(ValueError):
        Diagnostic("RW-E999", "no such code")
    rep = LintReport()
    rep.add("RW-E101", "x", fragment="f", executor="0:X")
    assert "RW-E101 [frag=f ex=0:X]" in rep.render()
