"""rwlint entry points: the CREATE-MV hook, pipeline linting for
hand-built plans (bench / tests), SQL-file linting, and the CLI driver
behind ``python -m risingwave_tpu lint``.

Cost contract: ``lint_planned`` is pure host-side metadata walking —
no tracing, no XLA — so the DDL path stays O(plan size), well under
the 50ms/query budget (tests/test_rwlint.py holds it). The deep
sanitizer (``--deep``) traces jaxprs and is CLI/test-only.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

from risingwave_tpu.analysis.diagnostics import Diagnostic, PlanLintError
from risingwave_tpu.analysis.plan_verifier import verify_planned


def _record(name: str, diags: List[Diagnostic], elapsed_ms: float) -> None:
    from risingwave_tpu.metrics import REGISTRY

    REGISTRY.histogram("lint_ms").observe(elapsed_ms)
    for d in diags:
        REGISTRY.counter("lint_diagnostics_total").inc(code=d.code)
    errors = [d for d in diags if d.severity == "error"]
    if errors:
        from risingwave_tpu.event_log import EVENT_LOG

        EVENT_LOG.record(
            "lint",
            relation=name,
            errors=len(errors),
            codes=",".join(sorted({d.code for d in errors})),
        )


def lint_planned(
    planned,
    catalog=None,
    source_schemas: Optional[Dict[str, dict]] = None,
    strict: bool = True,
) -> List[Diagnostic]:
    """Verify one PlannedMV; with ``strict``, error findings refuse the
    DDL via PlanLintError. Always records metrics + event log."""
    t0 = time.perf_counter()
    diags = verify_planned(planned, catalog=catalog, source_schemas=source_schemas)
    name = getattr(planned, "name", "mv")
    _record(name, diags, (time.perf_counter() - t0) * 1e3)
    errors = [d for d in diags if d.severity == "error"]
    if strict and errors:
        raise PlanLintError(errors, name=name)
    return diags


def lint_pipeline(
    pipeline,
    source_schemas: Optional[Dict[str, dict]] = None,
    name: str = "mv",
    strict: bool = True,
) -> List[Diagnostic]:
    """Lint a hand-built Pipeline / TwoInputPipeline / GraphPipeline
    (the bench and Python-API surface). ``source_schemas`` maps input
    side ("single"/"left"/"right") -> {col: dtype}."""

    class _Shim:
        pass

    shim = _Shim()
    shim.name = name
    shim.pipeline = pipeline
    shim.inputs = {}
    return lint_planned(
        shim, source_schemas=source_schemas or {}, strict=strict
    )


# ---------------------------------------------------------------------------
# built-in Nexmark query corpus
# ---------------------------------------------------------------------------

_I64 = "int64"
_I32 = "int32"

NEXMARK_SOURCE_SCHEMAS = {
    "q5": {"single": {"auction": _I64, "date_time": _I64}},
    "q7": {
        side: {
            "auction": _I64,
            "bidder": _I64,
            "price": _I64,
            "date_time": _I64,
        }
        for side in ("left", "right")
    },
    "q8": {
        "left": {"id": _I64, "name": _I32, "date_time": _I64},
        "right": {"seller": _I64, "date_time": _I64},
    },
}


def build_nexmark_corpus(capacity: int = 1 << 10, only: str = None):
    """Small-capacity twins of the built-in Nexmark plans — the lint
    corpus shared by ``lint --all-nexmark``, bench's pre-run gate, and
    the test suite (the verifier is static: plan shape is all that
    matters, so tiny capacities keep it fast). ``only`` selects one
    query; unknown names yield {}."""
    from risingwave_tpu.queries.nexmark_q import (
        build_q5_lite,
        build_q7,
        build_q8,
    )

    from risingwave_tpu.analysis.plan_verifier import _host_device

    builders = {
        "q5": lambda: build_q5_lite(capacity=capacity),
        "q7": lambda: build_q7(
            capacity=capacity,
            agg_capacity=capacity,
            filter_capacity=capacity,
            out_cap=capacity,
        ),
        "q8": lambda: build_q8(capacity=capacity, out_cap=capacity),
    }
    names = (only,) if only is not None else tuple(builders)
    # lint-only twins: pin their state allocations to host CPU so a
    # pre-bench gate on a TPU session never transiently touches HBM
    with _host_device():
        return {n: builders[n]() for n in names if n in builders}


def lint_all_nexmark(
    deep: bool = False, strict: bool = False
) -> Dict[str, List[Diagnostic]]:
    """Lint every built-in Nexmark query pipeline. With ``deep``, also
    run the jaxpr sanitizer over each pipeline's executors and the
    shared hash kernels."""
    out: Dict[str, List[Diagnostic]] = {}
    built = build_nexmark_corpus()
    for qname, q in built.items():
        out[qname] = lint_pipeline(
            q.pipeline,
            NEXMARK_SOURCE_SCHEMAS[qname],
            name=qname,
            strict=strict,
        )
    if deep:
        from risingwave_tpu.analysis.jax_sanitizer import (
            sanitize_executors,
            sanitize_hash_kernels,
            sanitize_state_kernels,
        )

        for qname, q in built.items():
            out[qname] = out[qname] + sanitize_executors(
                q.pipeline.executors
            )
        out["hash_kernels"] = sanitize_hash_kernels()
        out["state_kernels"] = sanitize_state_kernels()
    return out


def lint_sql_file(path: str) -> Dict[str, List[Diagnostic]]:
    """Execute a SQL file's DDL through an in-memory session (no object
    store, serial mode) and collect the lint findings of every CREATE
    MATERIALIZED VIEW. Statements split on ';' with `--` comment LINES
    stripped — this is not a SQL lexer: dollar-quoted UDF bodies with
    semicolons, and string literals spanning lines where a continuation
    line starts with `--`, are not supported here."""
    from risingwave_tpu.frontend.session import SqlSession
    from risingwave_tpu.runtime import StreamingRuntime
    from risingwave_tpu.sql import Catalog

    session = SqlSession(
        Catalog({}), StreamingRuntime(store=None), strict_lint=False
    )
    with open(path) as f:
        text = f.read()
    # strip whole `--` comment LINES before splitting on ';' (not
    # trailing comments: `--` may legally appear inside a string
    # literal): a comment must neither swallow the statement sharing
    # its segment nor split one at a ';' inside the comment text
    text = "\n".join(
        ln
        for ln in text.splitlines()
        if not ln.lstrip().startswith("--")
    )
    findings: Dict[str, List[Diagnostic]] = {}
    for raw in text.split(";"):
        # re-strip per segment: a trailing same-line comment
        # ("stmt; -- note") survives the pre-strip and becomes a
        # comment-only residual segment after the split
        stmt = "\n".join(
            ln
            for ln in raw.splitlines()
            if not ln.lstrip().startswith("--")
        ).strip()
        if not stmt:
            continue
        # lint runs DDL only: catalog-shaping statements feed the
        # verifier; DML/queries (bulk INSERT seeds, smoke SELECTs)
        # would do real work and abort the lint on unrelated failures
        if stmt.split(None, 1)[0].upper() not in (
            "CREATE",
            "DROP",
            "ALTER",
            "SET",
        ):
            continue
        before = len(session.lint_findings)
        session.execute(stmt)
        for name, d in session.lint_findings[before:]:
            findings.setdefault(name, []).append(d)
    return findings


# ---------------------------------------------------------------------------
# fusion-feasibility surface (analysis/fusion_analyzer.py)
# ---------------------------------------------------------------------------


def fusion_findings_for_ddl(planned) -> List[Diagnostic]:
    """The CREATE-MV fusion hook: SHALLOW analysis (trace contracts +
    host-sync AST scan, no jaxpr tracing — stays inside the DDL lint
    budget) filtered to the strict-relevant hazard classes: RW-E803
    (unbucketed shape-polymorphic window — the class that wedges real
    TPUs; ROADMAP item 2) and RW-E806 (a declared window_buckets
    lattice the bucketing layer cannot satisfy — the proof is
    vacuous). Full reports are a CLI/CI surface
    (``lint --fusion-report``).

    Graph pipelines are analyzed through their LIVE checkpoint
    registry (every stateful — hence every window-keyed — executor is
    in it) instead of re-shadow-building each fragment spec: the plan
    verifier already paid for one shadow build this DDL; a second one
    per CREATE MV would double the lint cost for nothing E803 needs."""
    from risingwave_tpu.analysis.fusion_analyzer import (
        analyze_chain,
        analyze_planned,
    )

    pipeline = getattr(planned, "pipeline", planned)
    name = getattr(planned, "name", "mv")
    if hasattr(pipeline, "_specs") and hasattr(pipeline, "graph"):
        # a parallel plan's registry holds PartitionedStateViews —
        # analyze one underlying instance (identical plan shape across
        # instances, so one carries the whole contract)
        chain = [
            getattr(e, "_instances", [e])[0]
            for e in getattr(pipeline, "_executors", ())
        ]
        reports = [
            analyze_chain(chain, None, f"{name}:ckpt", deep=False)
        ]
    else:
        reports = analyze_planned(planned, deep=False)
    out: List[Diagnostic] = []
    for rep in reports:
        out.extend(
            d
            for d in rep.diagnostics
            if d.code in ("RW-E803", "RW-E806")
        )
    return out


def run_fusion_report() -> dict:
    """``lint --fusion-report --all-nexmark``: per-query fusion
    reports."""
    from risingwave_tpu.analysis.fusion_analyzer import analyze_nexmark

    return analyze_nexmark(deep=True)


# ---------------------------------------------------------------------------
# mesh-readiness surface (analysis/mesh_analyzer.py)
# ---------------------------------------------------------------------------

# the sharded corpus plans REAL SQL through the planner and shards it
# (runtime.fragmenter.sharded_planned_mv) — the same q5/q7/q8 shapes
# the sharded-equivalence tests and the multichip dry-runs exercise
NEXMARK_SHARDED_SQL = {
    "q5": (
        "CREATE MATERIALIZED VIEW q5 AS "
        "SELECT auction, window_start, count(*) AS num "
        "FROM HOP(bid, date_time, INTERVAL '2' SECOND, "
        "INTERVAL '10' SECOND) "
        "GROUP BY auction, window_start"
    ),
    "q7": (
        "CREATE MATERIALIZED VIEW q7 AS "
        "SELECT b.auction, b.bidder, b.price, b.wstart "
        "FROM (SELECT auction, bidder, price, window_start AS wstart "
        "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND)) AS b "
        "JOIN (SELECT max(price) AS maxprice, window_start AS mwstart "
        "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
        "GROUP BY window_start) AS m "
        "ON b.wstart = m.mwstart AND b.price = m.maxprice"
    ),
    "q8": (
        "CREATE MATERIALIZED VIEW q8 AS "
        "SELECT p.id, p.name, p.starttime "
        "FROM (SELECT id, name, window_start AS starttime "
        "FROM TUMBLE(person, date_time, INTERVAL '10' SECOND) "
        "GROUP BY id, name, window_start) AS p "
        "JOIN (SELECT seller, window_start AS astarttime "
        "FROM TUMBLE(auction, date_time, INTERVAL '10' SECOND) "
        "GROUP BY seller, window_start) AS a "
        "ON p.id = a.seller AND p.starttime = a.astarttime"
    ),
}


def build_sharded_nexmark_corpus(
    n_shards: int = 8, capacity: int = 1 << 11, only: str = None
):
    """The SHARDED Nexmark corpus: q5/q7/q8 planned from SQL and run
    through the mesh sharding pass over an ``n_shards``-device mesh —
    the mesh analyzer's acceptance corpus. Requires that many devices
    (the CLI path arranges the 8-virtual-device sim mesh before any
    backend init; tests get it from conftest's XLA_FLAGS). Small
    capacities: the analysis is static, plan shape is all that
    matters. Callers own ``pipeline.close()`` (graph actors spawn at
    plan time)."""
    from risingwave_tpu.connectors.nexmark import (
        AUCTION_SCHEMA,
        BID_SCHEMA,
        PERSON_SCHEMA,
    )
    from risingwave_tpu.runtime.fragmenter import sharded_planned_mv
    from risingwave_tpu.sql import Catalog
    from risingwave_tpu.sql.planner import StreamPlanner

    catalog = Catalog(
        {
            "bid": BID_SCHEMA,
            "person": PERSON_SCHEMA,
            "auction": AUCTION_SCHEMA,
        }
    )

    def factory():
        return StreamPlanner(catalog, capacity=capacity)

    names = (only,) if only is not None else tuple(NEXMARK_SHARDED_SQL)
    return {
        n: sharded_planned_mv(factory, NEXMARK_SHARDED_SQL[n], n_shards)
        for n in names
        if n in NEXMARK_SHARDED_SQL
    }


def _committed_multichip() -> Optional[dict]:
    """The committed multichip dry-run artifact (PR 18's meshprof
    matrix + phase splits), when present — ranks mesh blockers by
    measured exchange-boundary cost."""
    import json
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(__file__))),
        "MULTICHIP.json",
    )
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def run_mesh_report(n_shards: int = 8) -> dict:
    """``lint --mesh-report``: per-query mesh-readiness reports over
    the sharded corpus, blockers ranked by MULTICHIP.json's measured
    phase splits. The caller must have arranged >= n_shards devices
    (``mesh_domain.ensure_virtual_devices``)."""
    from risingwave_tpu.analysis.mesh_analyzer import (
        analyze_sharded_nexmark,
    )

    return analyze_sharded_nexmark(
        deep=True, multichip=_committed_multichip(), n_shards=n_shards
    )


def mesh_findings_for_ddl(planned) -> List[Diagnostic]:
    """The CREATE-MV mesh hook: SHALLOW analysis (mesh contracts + the
    memoized loop-classified host-routing scan — no tracing, no mesh,
    no devices) of any plan that actually contains mesh-resident
    executors. Plans with none (every serial/graph plan a session
    builds today) cost one O(executors) scan and return [] — the DDL
    budget is untouched. Findings are report-only by default
    (warnings); RW_STRICT_MESH=1 upgrades them to refusals in the
    session hook."""
    from risingwave_tpu.analysis.mesh_analyzer import (
        analyze_sharded_pipeline,
    )
    from risingwave_tpu.runtime.fragmenter import (
        is_mesh_boundary,
        is_mesh_executor,
    )

    pipeline = getattr(planned, "pipeline", planned)
    name = getattr(planned, "name", "mv")
    exs = list(getattr(pipeline, "executors", ()) or ())
    # cheap gate BEFORE the fragment shadow-build: a plan with no mesh
    # executor anywhere cannot have sharded fragments
    if not any(
        is_mesh_executor(e) or is_mesh_boundary(e) for e in exs
    ):
        return []
    out: List[Diagnostic] = []
    for rep in analyze_sharded_pipeline(pipeline, name=name, deep=False):
        for b in rep.blockers:
            out.append(
                Diagnostic(
                    code=b.code,
                    message=f"{b.message} at {b.file}:{b.line}",
                    fragment=rep.fragment,
                    executor=b.executor,
                    severity="warning",
                )
            )
    return out


# ---------------------------------------------------------------------------
# CLI driver (python -m risingwave_tpu lint ...)
# ---------------------------------------------------------------------------


def run_cli(args) -> int:
    """Returns the process exit code: 0 = no error findings."""
    import json as _json

    if getattr(args, "sharing_report", False):
        # the sharing report is its own corpus analysis (it builds the
        # SQL-planned q5u twin next to the hand-built queries) — run it
        # standalone so CI can consume one clean JSON document
        from risingwave_tpu.analysis.sharing import run_sharing_report

        rep = run_sharing_report()
        if args.json:
            print(_json.dumps(rep, default=str))
        else:
            s = rep["summary"]
            print(
                f"sharing: {s['plans']} plan(s), {s['state_tables']} "
                f"keyed state table(s), {s['exact_shareable_groups']} "
                f"exact-shareable group(s), {s['index_opportunities']} "
                f"index opportunity(ies), {s['lattice_mismatches']} "
                "lattice mismatch(es)"
            )
            for t in rep["tables"]:
                print(
                    f"  {t['plan']}:{t['table_id']} [{t['executor']}] "
                    f"keys={t['keys']} index={t['index_fingerprint']} "
                    f"share={t['share_fingerprint']}"
                )
            for o in rep["opportunities"]:
                print(
                    f"  OPPORTUNITY keys={o['keys']}: "
                    f"{', '.join(o['tables'])}"
                )
            for d in rep["diagnostics"]:
                print(f"  {d['code']} [{d['severity']}] {d['message']}")
        # lattice mismatches are warnings (advisory), never exit-fatal
        return 0

    if getattr(args, "mesh_report", False):
        # the mesh report owns its mesh: it sets up the 8-virtual-
        # device sim mesh itself, BEFORE any jax backend init — and
        # refuses loudly (exit 2, the usage/input code) when some
        # earlier import already initialized jax with fewer devices,
        # because silently analyzing a 1-device "mesh" would mint
        # worthless proofs
        from risingwave_tpu.analysis.mesh_domain import (
            DEFAULT_MESH_SHARDS,
            MeshUnavailable,
            ensure_virtual_devices,
        )

        try:
            ensure_virtual_devices(DEFAULT_MESH_SHARDS)
        except MeshUnavailable as e:
            msg = str(e)
            print(
                _json.dumps({"error": msg})
                if args.json
                else f"rwlint: {msg}"
            )
            return 2
        rep = run_mesh_report(n_shards=DEFAULT_MESH_SHARDS)
        if args.json:
            print(_json.dumps(rep, default=str))
        else:
            for q in sorted(rep):
                if q.startswith("_") or q in ("ranking", "top_cost"):
                    continue
                s = rep[q]["summary"]
                print(
                    f"{q} mesh: {s['spmd_fusible_fragments']}/"
                    f"{s['fragments']} fragments SPMD-fusible, "
                    f"{s['host_routed_edges']} host-routed edge(s), "
                    f"blockers {s['blockers_by_code']}"
                )
            top = rep.get("top_cost") or {}
            print(
                f"top cost: phase={top.get('phase')} "
                f"est_ms={top.get('est_ms')} over "
                f"{top.get('blockers')} blocker(s)"
            )
            for r in (rep.get("ranking") or [])[:8]:
                est = r["est_exchange_ms"]
                print(
                    f"  #{r['rank']} {r['code']} [{r['query']} "
                    f"{r['fragment']} {r['executor']}] "
                    f"est={est if est is not None else '-'}ms "
                    f"{r['file']}:{r['line']}"
                )
        # the report is an inventory, not a gate: blockers are the
        # expected state until the collective-exchange arc lands —
        # scripts/lint_all.py's mesh-static ratchet holds the counts
        return 0

    fusion_report = getattr(args, "fusion_report", False)
    if fusion_report and not (args.all_nexmark or args.paths):
        # a bare --fusion-report means "the built-in corpus"
        args.all_nexmark = True
    if fusion_report and not args.all_nexmark:
        # never silently drop the flag: SQL-file fusion analysis is
        # not a surface (the DDL hook covers planned MVs) — exit 2 so
        # CI cannot mistake "no fusion section" for "no blockers"
        msg = (
            "--fusion-report analyzes the built-in corpus: add "
            "--all-nexmark (SQL files get fusion findings through "
            "the CREATE-MV lint hook, not this flag)"
        )
        print(_json.dumps({"error": msg}) if args.json else f"rwlint: {msg}")
        return 2
    if not args.all_nexmark and not args.paths:
        # exit-code contract: 2 = usage/input (CI tells this apart
        # from 1 = lint errors), never an interpreter traceback — and
        # --json consumers get JSON on EVERY exit path
        msg = "nothing to lint: pass SQL files and/or --all-nexmark"
        print(_json.dumps({"error": msg}) if args.json else f"rwlint: {msg}")
        return 2

    findings: Dict[str, List[Diagnostic]] = {}
    usage_errors: List[str] = []
    if args.all_nexmark:
        for name, diags in lint_all_nexmark(deep=args.deep).items():
            findings.setdefault(name, []).extend(diags)
    for path in args.paths:
        try:
            per_file = lint_sql_file(path)
        except OSError as e:
            # keep going: findings already collected for other targets
            # must still be reported, not dropped on a later bad path
            usage_errors.append(f"cannot read {path}: {e}")
            continue
        except Exception as e:  # noqa: BLE001 — bad SQL in the file
            usage_errors.append(f"{path}: {type(e).__name__}: {e}")
            continue
        for name, diags in per_file.items():
            findings.setdefault(f"{path}:{name}", []).extend(diags)
    fusion: Optional[Dict[str, dict]] = None
    if fusion_report and args.all_nexmark:
        fusion = run_fusion_report()
    n_err = 0
    if args.json:
        out = {
            name: [
                {
                    "code": d.code,
                    "severity": d.severity,
                    "fragment": d.fragment,
                    "executor": d.executor,
                    "message": d.message,
                }
                for d in diags
            ]
            for name, diags in findings.items()
        }
        if usage_errors:
            out["__errors__"] = usage_errors
        if fusion is not None:
            out["__fusion__"] = fusion
        print(_json.dumps(out))
        n_err = sum(
            1
            for diags in findings.values()
            for d in diags
            if d.severity == "error"
        )
    else:
        for name in sorted(findings):
            diags = findings[name]
            errs = [d for d in diags if d.severity == "error"]
            n_err += len(errs)
            status = "FAIL" if errs else ("warn" if diags else "ok")
            print(f"{name}: {status}")
            for d in diags:
                print(f"  {d.render()}")
        if fusion is not None:
            for q in sorted(fusion):
                if q.startswith("_"):
                    continue  # _provenance and friends: not a query
                s = fusion[q]["summary"]
                print(
                    f"{q} fusion: {s['fusible_fragments']}/"
                    f"{s['fragments']} fragments fusible, prefix "
                    f"{s['fusible_prefix_total']}/{s['chain_len_total']}"
                    f" executors, {s['host_sync_points']} host-sync "
                    f"point(s), blockers {s['blockers_by_code']}"
                )
                for fr in fusion[q]["fragments"]:
                    for b in fr["blockers"]:
                        print(
                            f"  {b['code']} [frag={fr['fragment']} "
                            f"ex={b['executor']}] {b['message']}"
                        )
        total = len(findings)
        for msg in usage_errors:
            print(f"rwlint: {msg}")
        print(
            f"rwlint: {total} target(s), {n_err} error(s), "
            f"{sum(len(v) for v in findings.values()) - n_err} warning(s)"
        )
    # usage/input problems dominate lint findings in the exit code so
    # CI never mistakes a half-linted run for a clean (or merely
    # finding-bearing) one
    if usage_errors:
        return 2
    return 1 if n_err else 0
