"""Fusion-feasibility analyzer — what blocks the one-jitted-step-per-
barrier refactor, proven statically.

PR 6's profiler showed the north-star gap is a host dispatch wall
(~319ms/barrier of host python vs 0.24ms of device compute). ROADMAP
item 1's fix is fusing each fragment's executor chain into one jitted
``device_step(state, chunk)``. Before that multi-PR refactor starts,
this module answers — per fragment, per executor, with file:line
provenance — three questions:

1. **What is fusible today?** An executor is device-fusible when its
   trace contract (executors/base.py ``trace_contract``) exposes a
   pure step over (state, chunk), the step abstractly traces over the
   declared chunk-size bucket lattice (analysis/shape_domain.py), and
   the AST scan of its hot methods finds no blocking host
   synchronization. The longest fusible executor PREFIX of a chain is
   what the fusion refactor can collapse first.
2. **What blocks fusion, and where?** Every blocker is a stable
   diagnostic with executor + file:line provenance:
   - RW-E801  blocking host sync inside the hot path (device_get /
     .item() / NumPy fallback / blocking scalar reads / Python
     branching on traced values)
   - RW-E802  dynamic (data-dependent) output shape
   - RW-E803  unbucketed shape-polymorphic window (the q7 wedge
     class): a window-keyed executor with no declared bucket lattice
     for its per-window shape domain
   - RW-E804  state not donation-safe for a fused step
   - RW-E805  jaxpr signature count over the bucket lattice exceeds
     the recompile budget
3. **What is it worth?** With measured ``device_dispatches_total``
   counts attached, blockers rank by the dispatches fusing them would
   collapse — the committed FUSION_REPORT.json is the worklist the
   fusion refactor burns down PR by PR.

The same role Shared Arrangements' static dataflow invariants play for
sharing (PAPERS.md), applied to compilability: the TiLT direction
(compile whole time-centric queries) needs a proof of WHERE whole-query
compilation is possible before anyone rewrites executors around it.
"""

from __future__ import annotations

import ast
import inspect
import textwrap
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from risingwave_tpu.analysis.diagnostics import Diagnostic
from risingwave_tpu.analysis.shape_domain import (
    ChunkSpec,
    bucket_lattice,
    recompile_budget,
    trace_signature,
)
from risingwave_tpu.array.lattice import validate_lattice

# ---------------------------------------------------------------------------
# host-sync scanner: AST over an executor's hot methods
# ---------------------------------------------------------------------------

# call markers that BLOCK on a host<->device round-trip. stage_scalars
# is deliberately absent: staging is async — the overlapped
# stage/finish protocol (base.finish_barrier) is the sanctioned read
# and is counted separately, not flagged.
_SYNC_CALLS = {
    "device_get": "jax.device_get (blocking device->host transfer)",
    "device_put": "jax.device_put (blocking host->device transfer)",
    "read_scalars": "blocking packed scalar read (read_scalars)",
    "pull_rows": "blocking device row pull (pull_rows)",
    "finish_scalars": "blocking staged-scalar materialization outside "
    "finish_barrier",
    "to_numpy": "chunk.to_numpy() device pull",
    "snapshot": "host snapshot materialization",
}
_SYNC_ATTRS = {
    "item": ".item() device scalar read",
}
# numpy entry points that silently materialize device arrays
_NP_FALLBACK = {"asarray", "flatnonzero", "array", "concatenate"}
_BRANCH_CASTS = {"int", "bool", "float"}

_HOT_METHODS = (
    "apply",
    "apply_left",
    "apply_right",
    "on_barrier",
    "on_watermark",
)


@dataclass(frozen=True)
class SyncPoint:
    reason: str
    file: str
    line: int
    method: str

    def render(self) -> str:
        return f"{self.reason} at {self.file}:{self.line} (in {self.method})"


class _MethodScanner(ast.NodeVisitor):
    """One method's AST: collect blocking-sync call sites and the local
    names assigned from device-flavored expressions (self.* attributes
    or calls to underscore-prefixed kernels), so ``int(n_closed)``-style
    Python branching on traced values is caught without flagging
    ``int(watermark.value)``-style host arithmetic."""

    def __init__(self, file: str, base_line: int, method: str):
        self.file = file
        self.base = base_line
        self.method = method
        self.out: List[SyncPoint] = []
        self.self_calls: List[str] = []  # self._helper() names for recursion
        self.attr_calls: List[Tuple[str, str]] = []  # (self attr, method)
        self._device_names: set = set()

    def _add(self, node, reason: str) -> None:
        self.out.append(
            SyncPoint(
                reason, self.file, self.base + node.lineno - 1, self.method
            )
        )

    @staticmethod
    def _mentions_device(node) -> bool:
        for n in ast.walk(node):
            if isinstance(n, ast.Attribute) and isinstance(
                n.value, ast.Name
            ) and n.value.id == "self":
                return True
            if isinstance(n, ast.Call):
                f = n.func
                name = (
                    f.id
                    if isinstance(f, ast.Name)
                    else f.attr
                    if isinstance(f, ast.Attribute)
                    else ""
                )
                if name.startswith("_") or name in ("col", "null_of"):
                    return True
                # jnp./jax./lax. results are device arrays by
                # construction
                if isinstance(f, ast.Attribute) and isinstance(
                    f.value, ast.Name
                ) and f.value.id in ("jnp", "jax", "lax"):
                    return True
        return False

    def visit_Assign(self, node):
        if self._mentions_device(node.value):
            for tgt in node.targets:
                for n in ast.walk(tgt):
                    if isinstance(n, ast.Name):
                        self._device_names.add(n.id)
        self.generic_visit(node)

    def visit_Call(self, node):
        f = node.func
        if isinstance(f, ast.Attribute):
            name = f.attr
            if name in _SYNC_ATTRS:
                self._add(node, _SYNC_ATTRS[name])
            elif name in _SYNC_CALLS:
                self._add(node, _SYNC_CALLS[name])
            elif name in _NP_FALLBACK and isinstance(f.value, ast.Name):
                if f.value.id in ("np", "numpy"):
                    self._add(
                        node,
                        f"NumPy fallback on a device value (np.{name})",
                    )
            elif (
                isinstance(f.value, ast.Attribute)
                and isinstance(f.value.value, ast.Name)
                and f.value.value.id == "self"
            ):
                # self.<attr>.<method>(...): one-level delegation (the
                # epoch-batch wrapper's self.agg.apply_stacked)
                self.attr_calls.append((f.value.attr, name))
            elif isinstance(f.value, ast.Name) and f.value.id == "self":
                self.self_calls.append(name)
        elif isinstance(f, ast.Name):
            if f.id in _SYNC_CALLS:
                self._add(node, _SYNC_CALLS[f.id])
            elif f.id in _BRANCH_CASTS and node.args:
                arg = node.args[0]
                if self._is_device_expr(arg):
                    self._add(
                        node,
                        f"Python branching on a traced value "
                        f"({f.id}() of a device scalar)",
                    )
        self.generic_visit(node)

    def _is_device_expr(self, node) -> bool:
        if isinstance(node, ast.Constant):
            return False
        if isinstance(node, ast.Name):
            return node.id in self._device_names
        return self._mentions_device(node)


# class source never changes within a process: memoize the per-method
# parse so the DDL hook's scan cost is paid once, not per CREATE MV
_SCAN_MEMO: Dict[Tuple[type, str], Tuple[tuple, tuple, tuple]] = {}


def _parse_method(cls, method: str):
    """(own sync points, same-class helper names, delegated attr
    calls) of one method — memoized per (class, method)."""
    memo = _SCAN_MEMO.get((cls, method))
    if memo is not None:
        return memo
    empty = ((), (), ())
    fn = getattr(cls, method, None)
    if fn is None or not callable(fn):
        _SCAN_MEMO[(cls, method)] = empty
        return empty
    # skip framework defaults: nothing executor-specific to report
    from risingwave_tpu.executors.base import Executor

    base_fn = getattr(Executor, method, None)
    if base_fn is not None and getattr(fn, "__func__", fn) is getattr(
        base_fn, "__func__", base_fn
    ):
        _SCAN_MEMO[(cls, method)] = empty
        return empty
    try:
        src = textwrap.dedent(inspect.getsource(fn))
        file = inspect.getsourcefile(fn) or "<unknown>"
        base_line = inspect.getsourcelines(fn)[1]
        tree = ast.parse(src)
    except (OSError, TypeError, SyntaxError):
        _SCAN_MEMO[(cls, method)] = empty
        return empty
    sc = _MethodScanner(file, base_line, f"{cls.__name__}.{method}")
    sc.visit(tree)
    out = (
        tuple(sc.out),
        tuple(sc.self_calls),
        tuple(sc.attr_calls),
    )
    _SCAN_MEMO[(cls, method)] = out
    return out


def _scan_method(
    cls,
    method: str,
    seen: set,
    depth: int = 0,
    exclude: Tuple[str, ...] = (),
) -> List[SyncPoint]:
    """Scan one method (and, bounded, the same-class helpers it calls)
    for blocking host syncs, with exact file:line provenance.
    ``exclude`` names helpers the contract declares statically dead on
    this instance's configuration (e.g. a host fallback branch the
    constructor ruled out)."""
    if depth > 3 or (cls, method) in seen or method in exclude:
        return []
    seen.add((cls, method))
    syncs, helpers, _delegated = _parse_method(cls, method)
    out = list(syncs)
    for helper in helpers:
        out.extend(_scan_method(cls, helper, seen, depth + 1, exclude))
    return out


def scan_host_syncs(
    ex,
    extra_methods: Sequence[str] = (),
    exclude: Sequence[str] = (),
) -> List[SyncPoint]:
    """All blocking host-sync points on an executor's hot path (apply
    + barrier/watermark flush + contract-declared extras), found by
    scanning the class source. The finish_barrier staged-scalar
    protocol is exempt by design (the one sanctioned overlapped read
    per barrier)."""
    cls = type(ex)
    seen: set = set()
    out: List[SyncPoint] = []
    delegated: List[Tuple[str, str]] = []
    for m in tuple(_HOT_METHODS) + tuple(extra_methods):
        _syncs, _helpers, attr_calls = _parse_method(cls, m)
        delegated.extend(attr_calls)
        out.extend(_scan_method(cls, m, seen, exclude=tuple(exclude)))
    # one-level delegation through instance attributes (wrapper
    # executors): scan the wrapped object's method too
    for attr, meth in delegated:
        inner = getattr(ex, attr, None)
        if inner is not None and isinstance(inner, object):
            icls = type(inner)
            if hasattr(icls, meth):
                out.extend(_scan_method(icls, meth, seen))
    # de-dup (helpers reachable from several hot methods)
    uniq: Dict[Tuple[str, int, str], SyncPoint] = {}
    for s in out:
        uniq.setdefault((s.file, s.line, s.reason), s)
    return sorted(
        uniq.values(), key=lambda s: (s.file, s.line)
    )


def staged_reads(ex) -> int:
    """1 when the executor participates in the sanctioned overlapped
    stage_scalars/finish_barrier protocol (one concurrent device
    round-trip per barrier — a fused step would keep this read)."""
    from risingwave_tpu.executors.base import Executor

    return int(
        type(ex)._on_barrier_scalars is not Executor._on_barrier_scalars
    )


# ---------------------------------------------------------------------------
# per-executor classification
# ---------------------------------------------------------------------------


@dataclass
class ExecutorClass:
    """One executor's fusion verdict."""

    index: int
    name: str
    kind: str  # "device" | "host" | "opaque"
    fusible: bool
    blockers: List[Diagnostic] = field(default_factory=list)
    sync_points: List[SyncPoint] = field(default_factory=list)
    signatures: int = 0  # distinct jaxpr signatures over the lattice
    staged_reads: int = 0
    # host syncs inside contract-declared ``fallback_syncs`` methods:
    # the fused per-barrier step compiles a device-side replacement
    # for those methods, so their reads exist only on the interpreted
    # fallback path — reported, never a fusibility blocker
    fallback_sync_points: List[SyncPoint] = field(default_factory=list)
    est_dispatches: Optional[float] = None  # measured, when counts given

    def to_json(self) -> dict:
        return {
            "index": self.index,
            "executor": self.name,
            "kind": self.kind,
            "fusible": self.fusible,
            "signatures": self.signatures,
            "staged_reads": self.staged_reads,
            "fallback_sync_points": [
                s.render() for s in self.fallback_sync_points
            ],
            "est_dispatches": self.est_dispatches,
            "blockers": [
                {
                    "code": d.code,
                    "severity": d.severity,
                    "message": d.message,
                }
                for d in self.blockers
            ],
        }


def _prov(idx: int, ex) -> str:
    return f"{idx}:{type(ex).__name__}"


def _lint_info(ex) -> Optional[dict]:
    fn = getattr(ex, "lint_info", None)
    if fn is None:
        return None
    try:
        return fn()
    except Exception:  # noqa: BLE001 — analysis must never crash
        return None


def _contract(ex) -> Optional[dict]:
    fn = getattr(ex, "trace_contract", None)
    if fn is None:
        return None
    try:
        return fn()
    except Exception:  # noqa: BLE001 — analysis must never crash
        return None


def _is_window_keyed(ex, info: Optional[dict]) -> bool:
    if info:
        if info.get("window_key") is not None:
            return True
        if info.get("window_cols"):
            return True
    return getattr(ex, "window_key", None) is not None or bool(
        getattr(ex, "window_cols", None)
    )


def classify_executor(
    ex,
    spec: Optional[ChunkSpec],
    fragment: str,
    index: int,
    deep: bool = True,
) -> ExecutorClass:
    """Classify ONE executor: device-fusible, host-bound (with named
    blockers), or opaque. ``spec`` is the abstract input chunk (None =
    schema unknown upstream — tracing is skipped, contracts + the AST
    scan still apply). ``deep`` enables abstract jaxpr tracing over
    the bucket lattice (CLI/CI); the DDL hook runs shallow."""
    name = type(ex).__name__
    prov = _prov(index, ex)
    info = _lint_info(ex)
    contract = _contract(ex)
    ec = ExecutorClass(index=index, name=name, kind="opaque", fusible=False)
    if spec is None and contract is not None:
        # a contract-declared input schema seeds tracing when nothing
        # threads one in (two-input joins heading a join_tail fragment
        # declare their probe-side schema: the executor knows its own
        # input exactly, the fragment extractor does not)
        decl = contract.get("input_schema")
        if decl:
            spec = ChunkSpec.from_schema(
                decl, nulls=tuple(contract.get("input_nulls", ()))
            )

    def blocker(code: str, message: str, severity: str = "warning"):
        ec.blockers.append(
            Diagnostic(
                code,
                message,
                fragment=fragment,
                executor=prov,
                severity=severity,
            )
        )

    if contract is None:
        # no trace contract: nothing provable — hard-stops the prefix
        return ec

    ec.kind = contract.get("kind", "opaque")
    ec.staged_reads = staged_reads(ex)

    # -- host-sync scan (both kinds: a "device" claim is verified) ----
    # ``fallback_syncs`` methods are scanned SEPARATELY: the fused
    # per-barrier step compiles a device-resident replacement for them
    # (e.g. HashAgg's flush -> fused_step's in-program delta
    # extraction, proven equivalent by the fused-vs-interpreted twin
    # suite), so the fusibility verdict excludes them. NOTE the
    # verdict is a CAPABILITY claim — "this chain can compile into
    # one step" — not a promise the runtime fuses it: fuse_chain may
    # still pick the interpreted/epoch-batched fallback (e.g. an agg
    # feeding an interpreted join), where these reads DO run per
    # barrier. They stay visible as ``fallback_sync_points`` and the
    # fusion ratchet (scripts/lint_all.py) holds them against the
    # baseline (must never grow).
    fallback = tuple(contract.get("fallback_syncs", ()))
    ec.sync_points = scan_host_syncs(
        ex,
        contract.get("hot_methods", ()),
        tuple(contract.get("scan_exclude", ())) + fallback,
    )
    for m in fallback:
        ec.fallback_sync_points.extend(
            _scan_method(type(ex), m, set())
        )
    for s in ec.sync_points:
        blocker("RW-E801", s.render())
    if ec.kind == "host":
        reason = contract.get("host_reason", "host-bound data path")
        if not ec.sync_points:
            blocker("RW-E801", reason)

    # -- emission shape --------------------------------------------------
    emission = contract.get("emission", "passthrough")
    if emission == "data_dependent":
        blocker(
            "RW-E802",
            "emission capacity derives from live-row counts — every "
            "distinct size compiles a fresh downstream program",
        )

    # -- window bucket lattice (RW-E803/E806, the q7 wedge class) --------
    if _is_window_keyed(ex, info):
        wb = contract.get("window_buckets")
        if wb is None:
            blocker(
                "RW-E803",
                "window-keyed shape domain has no declared bucket "
                "lattice: state rebuilds/emissions under window churn "
                "re-trace the fused step without bound",
            )
        else:
            why = validate_lattice(wb)
            if why is not None:
                blocker(
                    "RW-E806",
                    "declared window_buckets lattice is unsatisfiable "
                    f"by the bucketing layer ({why}): the shape-"
                    "stability proof is vacuous",
                )

    # -- donation (RW-E804) ----------------------------------------------
    if contract.get("state") is not None and not contract.get(
        "donate", False
    ):
        blocker(
            "RW-E804",
            "state buffers are not donated by the step kernel — a "
            "fused per-barrier step would hold two live copies in HBM",
        )

    # -- abstract tracing over the bucket lattice ------------------------
    step = contract.get("trace_step")
    if deep and ec.kind == "device" and step is not None and spec is not None:
        sigs = set()
        for bucket in bucket_lattice(spec):
            try:
                sig = trace_signature(step, bucket)
            except Exception as e:  # noqa: BLE001
                kind = type(e).__name__
                if "Tracer" in kind or "Concretization" in kind:
                    blocker(
                        "RW-E801",
                        f"Python branching on traced values: abstract "
                        f"tracing at capacity {bucket.capacity} raised "
                        f"{kind}",
                    )
                else:
                    # untraceable with THIS schema (builder-shaped
                    # input the spec cannot express): degrade to
                    # opaque — no false blocker, no false proof
                    ec.kind = "opaque"
                break
            sigs.add((sig.in_avals, sig.out_avals))
            for h in sig.host_calls:
                blocker(
                    "RW-E801",
                    f"host callback primitive {h!r} inside the traced "
                    "step",
                )
            for t in sig.transfers:
                blocker(
                    "RW-E802",
                    f"transfer primitive {t!r} inside the traced step",
                )
        ec.signatures = len(sigs)
        budget = recompile_budget()
        if ec.signatures > budget:
            blocker(
                "RW-E805",
                f"{ec.signatures} distinct jaxpr signatures across the "
                f"declared buckets > recompile budget {budget}",
            )

    # fusible = a POSITIVE proof: a device contract whose step was
    # actually abstract-traced over the lattice (signatures >= 1) with
    # zero blockers. A device claim that could NOT be traced — no
    # step, no input spec to trace with, or a shallow (DDL) pass that
    # skips tracing — is not evidence and never mints a fusible proof;
    # those passes only surface contract-level hazards (E803 et al).
    ec.fusible = (
        ec.kind == "device"
        and step is not None
        and not ec.blockers
        and ec.signatures >= 1
    )
    return ec


# ---------------------------------------------------------------------------
# schema threading (the abstract interpreter's environment)
# ---------------------------------------------------------------------------


def _thread_spec(
    spec: Optional[ChunkSpec], ex, info: Optional[dict]
) -> Optional[ChunkSpec]:
    """Push a ChunkSpec through one executor using its lint_info
    schema transitions (the same rules plan_verifier applies) — None
    when tracking is lost (opaque / unknown dtypes). An ``emits``
    executor REBUILDS the spec even when the input spec is unknown
    (joins with fully-declared output dtypes re-anchor tracing for
    their tail)."""
    if info is None:
        return None
    emits = info.get("emits")
    if emits is not None:
        schema = {n: dt for n, dt in spec.columns} if spec else {}
        renames = info.get("renames") or {}
        out = {}
        for k, v in emits.items():
            if v is None:
                src = renames.get(k)
                v = schema.get(src) if src is not None else None
            out[k] = v
        from risingwave_tpu.analysis.shape_domain import DEFAULT_BUCKETS

        cap = spec.capacity if spec else DEFAULT_BUCKETS[0]
        # null lanes thread through rename-passthrough outputs only:
        # computed outputs are non-nullable by the chunk contract
        # (with_columns drops stale lanes). Executors minting NEW
        # nullable lanes (outer joins) are not expressible in
        # lint_info — their tail traces the non-nullable variant,
        # which is why `fusible` demands the trace itself, not just
        # this spec, to succeed.
        in_nulls = set(spec.nulls) if spec else set()
        nulls = tuple(
            sorted(
                k
                for k, src in renames.items()
                if k in out and src is not None and src in in_nulls
            )
        )
        return ChunkSpec.from_schema(out, cap, nulls)
    if spec is None:
        return None
    schema = {n: dt for n, dt in spec.columns}
    adds = info.get("adds") or {}
    if adds:
        out = dict(schema)
        for k, v in adds.items():
            out[k] = v
        nulls = tuple(spec.nulls)
        return ChunkSpec.from_schema(out, spec.capacity, nulls)
    return spec


# ---------------------------------------------------------------------------
# fragment / pipeline reports
# ---------------------------------------------------------------------------


@dataclass
class FragmentReport:
    fragment: str
    executors: List[ExecutorClass] = field(default_factory=list)
    fusible_prefix: int = 0
    whole_chain_fusible: bool = False
    host_sync_points: int = 0
    diagnostics: List[Diagnostic] = field(default_factory=list)

    def to_json(self) -> dict:
        return {
            "fragment": self.fragment,
            "fusible_prefix": self.fusible_prefix,
            "chain_len": len(self.executors),
            "whole_chain_fusible": self.whole_chain_fusible,
            "host_sync_points": self.host_sync_points,
            "fallback_sync_points": sum(
                len(e.fallback_sync_points) for e in self.executors
            ),
            "executors": [e.to_json() for e in self.executors],
            "blockers": [
                {
                    "code": d.code,
                    "executor": d.executor,
                    "severity": d.severity,
                    "message": d.message,
                }
                for d in self.diagnostics
            ],
        }


def analyze_chain(
    chain: Sequence[object],
    spec: Optional[ChunkSpec],
    fragment: str,
    deep: bool = True,
) -> FragmentReport:
    rep = FragmentReport(fragment=fragment)
    prefix_intact = True
    for idx, ex in enumerate(chain):
        ec = classify_executor(ex, spec, fragment, idx, deep=deep)
        rep.executors.append(ec)
        rep.diagnostics.extend(ec.blockers)
        rep.host_sync_points += len(ec.sync_points)
        if prefix_intact and ec.fusible:
            rep.fusible_prefix += 1
        else:
            prefix_intact = False
        spec = _thread_spec(spec, ex, _lint_info(ex))
    rep.whole_chain_fusible = rep.fusible_prefix == len(rep.executors) and (
        len(rep.executors) > 0
    )
    return rep


def _spec_from_schema(
    schema: Optional[Dict[str, object]]
) -> Optional[ChunkSpec]:
    if schema is None:
        return None
    return ChunkSpec.from_schema(schema)


def analyze_pipeline(
    pipeline,
    source_schemas: Optional[Dict[str, Dict[str, object]]] = None,
    name: str = "mv",
    deep: bool = True,
) -> List[FragmentReport]:
    """Per-fragment fusion reports for any pipeline shape (serial
    Pipeline, TwoInputPipeline, GraphPipeline) — fragment extraction
    via runtime.fragmenter.fragment_chains."""
    from risingwave_tpu.runtime.fragmenter import fragment_chains

    source_schemas = source_schemas or {}
    out: List[FragmentReport] = []
    for frag, sections in fragment_chains(pipeline).items():
        for side, chain in sections.items():
            if not chain:
                continue
            # only source-fed sections seed an abstract schema; graph
            # fragments fed by other fragments (side "chain") and the
            # join+tail section re-anchor through lint_info emits
            schema = (
                source_schemas.get(side)
                if side in ("single", "left", "right", "both")
                else None
            )
            label = frag if side in ("single", "chain") else f"{frag}/{side}"
            out.append(
                analyze_chain(
                    chain,
                    _spec_from_schema(schema),
                    f"{name}:{label}",
                    deep=deep,
                )
            )
    return out


def analyze_planned(planned, deep: bool = False) -> List[FragmentReport]:
    """The DDL-time surface: shallow by default (contracts + AST scan,
    no tracing — keeps CREATE MV inside the lint budget)."""
    pipeline = getattr(planned, "pipeline", planned)
    return analyze_pipeline(
        pipeline, None, getattr(planned, "name", "mv"), deep=deep
    )


# ---------------------------------------------------------------------------
# measured-dispatch ranking + report assembly
# ---------------------------------------------------------------------------


def attach_costs(
    reports: Sequence[FragmentReport], dispatches: Optional[dict]
) -> None:
    """Annotate executor classes with measured device-dispatch counts
    (``device_dispatches_total``, as ``PROFILER.dispatch_counts()``
    returns them) — turning the static blocker list into a RANKED
    worklist (most dispatches first): fusing a fragment collapses its
    blocked executors' dispatches into one program launch."""
    if not dispatches:
        return
    for rep in reports:
        for ec in rep.executors:
            for lbl, n in dispatches.items():
                # the profiler emits bare executor names; labeled
                # counters use executor=NAME
                if lbl == ec.name or f"executor={ec.name}" in lbl:
                    ec.est_dispatches = (ec.est_dispatches or 0.0) + float(n)
        rep.diagnostics.sort(
            key=lambda d: -(
                next(
                    (
                        e.est_dispatches
                        for e in rep.executors
                        if d.executor == f"{e.index}:{e.name}"
                        and e.est_dispatches is not None
                    ),
                    0.0,
                )
            )
        )


def report_to_json(reports: Sequence[FragmentReport]) -> dict:
    frs = [r.to_json() for r in reports]
    return {
        "fragments": frs,
        "summary": {
            "fragments": len(frs),
            "fusible_fragments": sum(
                1 for r in frs if r["whole_chain_fusible"]
            ),
            "host_sync_points": sum(r["host_sync_points"] for r in frs),
            "fusible_prefix_total": sum(r["fusible_prefix"] for r in frs),
            "chain_len_total": sum(r["chain_len"] for r in frs),
            "blockers_by_code": _count_codes(frs),
        },
    }


def _count_codes(frs: Sequence[dict]) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for r in frs:
        for b in r["blockers"]:
            out[b["code"]] = out.get(b["code"], 0) + 1
    return dict(sorted(out.items()))


def analyze_nexmark(
    deep: bool = True, profile_bench: Optional[dict] = None
) -> Dict[str, dict]:
    """Fusion reports for the built-in Nexmark corpus (the committed
    FUSION_REPORT.json shape). ``profile_bench``: a BENCH JSON dict —
    each query's ``{q}_device_dispatches`` block ranks its blockers."""
    from risingwave_tpu.analysis.lint import (
        NEXMARK_SOURCE_SCHEMAS,
        build_nexmark_corpus,
    )

    out: Dict[str, dict] = {}
    for qname, q in build_nexmark_corpus().items():
        reports = analyze_pipeline(
            q.pipeline, NEXMARK_SOURCE_SCHEMAS[qname], qname, deep=deep
        )
        disp = None
        if profile_bench:
            disp = profile_bench.get(f"{qname}_device_dispatches")
            if disp is None and qname == "q5":
                # the unified-path capture covers q5
                disp = profile_bench.get("q5u_device_dispatches")
        attach_costs(reports, disp)
        out[qname] = report_to_json(reports)
    # provenance rides every regenerated FUSION report ("_"-prefixed:
    # the fusion ratchet in scripts/lint_all.py skips it)
    try:
        from risingwave_tpu.provenance import stamp

        out["_provenance"] = stamp()
    except Exception:  # noqa: BLE001 — provenance is best effort
        pass
    return out
