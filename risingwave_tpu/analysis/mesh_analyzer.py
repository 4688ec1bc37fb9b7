"""Mesh-readiness analyzer — can a sharded fragment's barrier collapse
into ONE SPMD dispatch across the device mesh, proven statically.

ROADMAP item 3 (turn the host-routed exchange into on-device
collectives under ``shard_map``) has measurement — PR 18's meshprof
exchange matrix and phase splits (MULTICHIP.json) — but until now no
static tooling, exactly the state fusion was in before the PR 7
analyzer made the fused-step PRs safe to build.  This module answers,
per sharded fragment, per executor, with file:line provenance:

1. **What is SPMD-fusible today?**  A sharded executor earns a
   positive proof when its ``mesh_contract()`` declares the vnode
   dispatch honestly, its step abstractly traces under ``shard_map``
   over the N-device mesh at every bucket of the chunk lattice
   (``jax.make_jaxpr`` — no XLA, no allocation), and the AST scan of
   its barrier path finds no host-routed reads.  A fragment is
   SPMD-fusible when EVERY chain member proves — the shallow pass
   never mints a proof.
2. **What blocks it, and where?**  Stable RW-E9xx diagnostics:
   - RW-E901  host-routed exchange edge (stack/split/flatten
     boundary, device pulls or NumPy fallbacks on the barrier path)
   - RW-E902  hash-dispatch key not provably a pure function of the
     mesh axis (dispatch outside the consistent-hash ``dest_shard``
     path, axis mismatch, or no declared keys)
   - RW-E903  shard-local step not shard_map-traceable (trace raises,
     or the signature count across the bucket lattice exceeds the
     recompile budget: per-shard shape polymorphism)
   - RW-E904  replicated state mutated shard-locally
   - RW-E905  exchange/flush output shape data-dependent (a host
     recount loop gates the next step)
   - RW-E906  cross-shard reduction order not order-insensitive
   - RW-E907  per-destination dispatch fan-out (one host-driven
     device call per shard — the ×N dispatch wall the multichip
     dry-runs measured)
3. **What is it worth?**  With MULTICHIP.json's measured phase splits
   attached, blockers rank by measured exchange-boundary cost
   (``est_exchange_ms`` / ``est_dispatches_saved``) — the committed
   MESH_REPORT.json is the worklist the collective-exchange arc burns
   down, the way FUSION_REPORT.json drove the fused-step PRs.

The blocker phases group the host lanes the measured matrix exposes:
E901/E907 are the **exchange_route** phase (rows crossing shards
through host memory — MULTICHIP.json's host_split/host_flatten lanes),
E905 is **host_recount**, contract violations are **contract**, trace
failures are **compile**.  ``shard_local`` compute is on-device either
way and is NOT a blocker phase — which is why the static ranking
names the exchange route as the top reclaimable cost, reproducing the
measurement from source alone.
"""

from __future__ import annotations

import ast
import inspect
import os
import textwrap
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from risingwave_tpu.analysis.fusion_analyzer import (
    _BRANCH_CASTS,
    _NP_FALLBACK,
    _SYNC_ATTRS,
    _SYNC_CALLS,
    _lint_info,
    _thread_spec,
)
from risingwave_tpu.analysis.shape_domain import (
    ChunkSpec,
    recompile_budget,
)

# ---------------------------------------------------------------------------
# provenance helpers
# ---------------------------------------------------------------------------


def _rel(path: str) -> str:
    """Repo-relative provenance: committed MESH_REPORT.json must not
    embed the checkout prefix."""
    for marker in ("risingwave_tpu" + os.sep, "tests" + os.sep):
        i = path.find(marker)
        if i >= 0:
            return path[i:].replace(os.sep, "/")
    return os.path.basename(path)


def _class_site(cls) -> Tuple[str, int]:
    try:
        file = inspect.getsourcefile(cls) or "<unknown>"
        line = inspect.getsourcelines(cls)[1]
        return _rel(file), line
    except (OSError, TypeError):
        return "<unknown>", 0


def _method_site(cls, method: str) -> Tuple[str, int]:
    fn = getattr(cls, method, None)
    if fn is None:
        return _class_site(cls)
    try:
        file = inspect.getsourcefile(fn) or "<unknown>"
        line = inspect.getsourcelines(fn)[1]
        return _rel(file), line
    except (OSError, TypeError):
        return _class_site(cls)


# ---------------------------------------------------------------------------
# loop-aware host-routing scanner
# ---------------------------------------------------------------------------

# phase a blocker's cost lands in (the static twin of meshprof's
# measured phase split)
_PHASE_BY_CODE = {
    "RW-E901": "exchange_route",
    "RW-E907": "exchange_route",
    "RW-E905": "host_recount",
    "RW-E902": "contract",
    "RW-E904": "contract",
    "RW-E906": "contract",
    "RW-E903": "compile",
}


@dataclass(frozen=True)
class MeshSync:
    """One host-routing site on the sharded path, with its mechanism:
    ``host_read`` (E901), ``shard_fanout`` (E907 — inside a
    per-destination loop), ``recount`` (E905 — a device read gating a
    flush/drain loop)."""

    reason: str
    file: str
    line: int
    method: str
    kind: str = "host_read"

    def render(self) -> str:
        return f"{self.reason} at {self.file}:{self.line} (in {self.method})"


class _MeshScanner(ast.NodeVisitor):
    """One method's AST with LOOP CONTEXT: the same blocking-sync
    markers the fusion scanner uses, but classified by the loop that
    contains them — a device read inside a per-shard loop is the ×N
    dispatch wall (E907), one that gates a drain loop's exit is a
    host recount (E905), anything else is a host-routed edge (E901)."""

    def __init__(self, file: str, base_line: int, method: str):
        self.file = file
        self.base = base_line
        self.method = method
        self.out: List[MeshSync] = []
        self.self_calls: List[str] = []
        self._device_names: set = set()
        self._loops: List[bool] = []  # stack: is_shard_loop
        self._claimed_lines: set = set()

    # -- device-flavor heuristics (mirrors the fusion scanner) ----------
    def _mentions_device(self, node) -> bool:
        for n in ast.walk(node):
            if isinstance(n, ast.Attribute) and isinstance(
                n.value, ast.Name
            ) and n.value.id == "self":
                return True
            if isinstance(n, ast.Name) and n.id in self._device_names:
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
                if isinstance(f, ast.Attribute) and isinstance(
                    f.value, ast.Name
                ) and f.value.id in ("jnp", "jax", "lax"):
                    return True
        return False

    def _in_shard_loop(self) -> bool:
        return any(self._loops)

    def _kind(self) -> str:
        return "shard_fanout" if self._in_shard_loop() else "host_read"

    def _add(self, node, reason: str, kind: Optional[str] = None) -> None:
        line = self.base + node.lineno - 1
        self.out.append(
            MeshSync(reason, self.file, line, self.method, kind or self._kind())
        )

    # -- assignments feed the device-name environment --------------------
    def visit_Assign(self, node):
        if self._mentions_device(node.value):
            for tgt in node.targets:
                for n in ast.walk(tgt):
                    if isinstance(n, ast.Name):
                        self._device_names.add(n.id)
        self.generic_visit(node)

    # -- loops -----------------------------------------------------------
    @staticmethod
    def _is_shard_iter(node: ast.For) -> bool:
        """Per-destination loops: ``for s in range(self.n_shards)``,
        ``for s in set(dest.tolist())`` and friends."""
        for n in ast.walk(node.iter):
            if isinstance(n, ast.Attribute) and n.attr in (
                "n_shards",
                "tolist",
            ):
                return True
            if isinstance(n, ast.Name) and n.id in ("n_shards", "dest"):
                return True
        return False

    def visit_For(self, node):
        shard = self._is_shard_iter(node)
        if shard and any(
            self._mentions_device(b) for b in node.body
        ):
            self._add(
                node,
                "per-destination dispatch fan-out: one host-driven "
                "device call per shard",
                kind="shard_fanout",
            )
        self._loops.append(shard)
        self.generic_visit(node)
        self._loops.pop()

    def visit_While(self, node):
        if self._device_cast_in(node.test):
            self._add(
                node,
                "drain loop gated by a device read (host recount)",
                kind="recount",
            )
            self._claim_casts(node.test)
        self._loops.append(False)
        self.generic_visit(node)
        self._loops.pop()

    def visit_If(self, node):
        # a device-cast test whose branch exits an enclosing loop =
        # the loop's iteration count is data-dependent (E905): the
        # received/flushed row count reaches the host before the next
        # round can run
        if self._loops and self._device_cast_in(node.test):
            exits = any(
                isinstance(n, (ast.Break, ast.Return, ast.Raise))
                for b in (node.body, node.orelse)
                for stmt in b
                for n in ast.walk(stmt)
            )
            if exits:
                self._add(
                    node,
                    "loop exit gated by a device read (host recount "
                    "of a data-dependent flush/exchange shape)",
                    kind="recount",
                )
                self._claim_casts(node.test)
        self.generic_visit(node)

    def _device_cast_in(self, test) -> bool:
        for n in ast.walk(test):
            if (
                isinstance(n, ast.Call)
                and isinstance(n.func, ast.Name)
                and n.func.id in _BRANCH_CASTS
                and n.args
                and self._is_device_expr(n.args[0])
            ):
                return True
        return False

    def _claim_casts(self, test) -> None:
        """Casts consumed by a recount verdict are not re-reported as
        plain branch syncs."""
        for n in ast.walk(test):
            if isinstance(n, ast.Call):
                self._claimed_lines.add(self.base + n.lineno - 1)

    # -- sync markers ----------------------------------------------------
    def visit_Call(self, node):
        line = self.base + node.lineno - 1
        if line in self._claimed_lines:
            self.generic_visit(node)
            return
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
            elif isinstance(f.value, ast.Name) and f.value.id == "self":
                self.self_calls.append(name)
        elif isinstance(f, ast.Name):
            if f.id in _SYNC_CALLS:
                self._add(node, _SYNC_CALLS[f.id])
            elif f.id in _BRANCH_CASTS and node.args:
                if self._is_device_expr(node.args[0]):
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


# memoized per (class, method) — the DDL hook pays the parse once
_MESH_SCAN_MEMO: Dict[Tuple[type, str], Tuple[tuple, tuple]] = {}


def _parse_mesh_method(cls, method: str):
    memo = _MESH_SCAN_MEMO.get((cls, method))
    if memo is not None:
        return memo
    empty = ((), ())
    fn = getattr(cls, method, None)
    if fn is None or not callable(fn):
        _MESH_SCAN_MEMO[(cls, method)] = empty
        return empty
    from risingwave_tpu.executors.base import Executor

    base_fn = getattr(Executor, method, None)
    if base_fn is not None and getattr(fn, "__func__", fn) is getattr(
        base_fn, "__func__", base_fn
    ):
        _MESH_SCAN_MEMO[(cls, method)] = empty
        return empty
    try:
        src = textwrap.dedent(inspect.getsource(fn))
        file = _rel(inspect.getsourcefile(fn) or "<unknown>")
        base_line = inspect.getsourcelines(fn)[1]
        tree = ast.parse(src)
    except (OSError, TypeError, SyntaxError):
        _MESH_SCAN_MEMO[(cls, method)] = empty
        return empty
    sc = _MeshScanner(file, base_line, f"{cls.__name__}.{method}")
    sc.visit(tree)
    out = (tuple(sc.out), tuple(sc.self_calls))
    _MESH_SCAN_MEMO[(cls, method)] = out
    return out


def _scan_mesh_method(
    cls, method: str, seen: set, depth: int = 0
) -> List[MeshSync]:
    if depth > 3 or (cls, method) in seen:
        return []
    seen.add((cls, method))
    syncs, helpers = _parse_mesh_method(cls, method)
    out = list(syncs)
    for helper in helpers:
        out.extend(_scan_mesh_method(cls, helper, seen, depth + 1))
    return out


def scan_mesh_syncs(ex, methods: Sequence[str]) -> List[MeshSync]:
    """All host-routing sites reachable from ``methods`` (plus the
    same-class helpers they call, bounded), loop-classified, with
    file:line provenance."""
    cls = type(ex)
    seen: set = set()
    out: List[MeshSync] = []
    for m in methods:
        out.extend(_scan_mesh_method(cls, m, seen))
    uniq: Dict[Tuple[str, int, str], MeshSync] = {}
    for s in out:
        uniq.setdefault((s.file, s.line, s.reason), s)
    return sorted(uniq.values(), key=lambda s: (s.file, s.line))


# ---------------------------------------------------------------------------
# per-executor classification
# ---------------------------------------------------------------------------


@dataclass
class MeshBlocker:
    """One E9xx finding with provenance + (once measurement attaches)
    its estimated reclaim."""

    code: str
    message: str
    executor: str
    method: str
    file: str
    line: int
    phase: str = ""
    est_exchange_ms: Optional[float] = None
    est_dispatches_saved: Optional[int] = None

    def __post_init__(self):
        if not self.phase:
            self.phase = _PHASE_BY_CODE.get(self.code, "contract")

    def to_json(self) -> dict:
        return {
            "code": self.code,
            "executor": self.executor,
            "method": self.method,
            "file": self.file,
            "line": self.line,
            "phase": self.phase,
            "est_exchange_ms": self.est_exchange_ms,
            "est_dispatches_saved": self.est_dispatches_saved,
            "message": self.message,
        }


@dataclass
class MeshExecutorClass:
    """One executor's SPMD verdict."""

    index: int
    name: str
    kind: str  # "mesh" | "boundary" | "outside"
    spmd_proven: bool = False
    traced: bool = False
    signatures: int = 0
    collectives: Tuple[str, ...] = ()
    blockers: List[MeshBlocker] = field(default_factory=list)
    sync_points: List[MeshSync] = field(default_factory=list)
    note: str = ""

    def to_json(self) -> dict:
        return {
            "index": self.index,
            "executor": self.name,
            "kind": self.kind,
            "spmd_proven": self.spmd_proven,
            "traced": self.traced,
            "signatures": self.signatures,
            "collectives": list(self.collectives),
            "blockers": [b.to_json() for b in self.blockers],
            "note": self.note or None,
        }


_SYNC_CODE = {
    "host_read": "RW-E901",
    "shard_fanout": "RW-E907",
    "recount": "RW-E905",
}


def classify_mesh_executor(
    ex,
    spec: Optional[ChunkSpec],
    fragment: str,
    index: int,
    deep: bool = True,
) -> MeshExecutorClass:
    """Classify ONE executor of a sharded chain: mesh-resident (proof
    or E9xx blockers), a host boundary adapter (E901 by construction),
    or outside the mesh (prefix ops — the fusion analyzer's problem,
    not a mesh blocker)."""
    from risingwave_tpu.parallel.exchange import DISPATCH_FN
    from risingwave_tpu.runtime.fragmenter import is_mesh_boundary

    name = type(ex).__name__
    prov = f"{index}:{name}"
    ec = MeshExecutorClass(index=index, name=name, kind="outside")

    def blocker(code, message, method="", file="", line=0):
        if not file:
            file, line = _class_site(type(ex))
        ec.blockers.append(
            MeshBlocker(code, message, prov, method, file, line)
        )

    if is_mesh_boundary(ex):
        ec.kind = "boundary"
        file, line = _method_site(type(ex), "apply")
        blocker(
            "RW-E901",
            f"host-routed exchange edge: {name} crosses rows between "
            "flat host chunks and the stacked mesh layout outside the "
            "sharded program",
            method=f"{name}.apply",
            file=file,
            line=line,
        )
        return ec

    getter = getattr(ex, "mesh_contract", None)
    if not callable(getter):
        return ec  # outside the mesh — not this analyzer's question
    try:
        contract = getter()
    except Exception as e:  # noqa: BLE001 — a broken contract is a finding
        ec.kind = "mesh"
        blocker(
            "RW-E001",
            f"mesh_contract() raised {type(e).__name__} — treated as "
            "opaque, nothing provable past this executor",
        )
        return ec
    ec.kind = "mesh"

    # -- E902: dispatch must be the consistent-hash vnode path ----------
    disp = contract.get("dispatch") or {}
    fn = disp.get("fn")
    if fn != DISPATCH_FN:
        blocker(
            "RW-E902",
            f"dispatch fn {fn!r} is not the consistent-hash "
            f"{DISPATCH_FN!r} path: the destination shard is not "
            "provably vnode(key) % n_shards",
        )
    axis = contract.get("axis")
    if disp.get("vnode_axis") != axis:
        blocker(
            "RW-E902",
            f"declared vnode axis {disp.get('vnode_axis')!r} does not "
            f"match the mesh axis {axis!r}: an all_to_all over the "
            "mesh would route rows to the wrong shard",
        )
    keys = disp.get("keys")
    flat_keys: tuple = ()
    if isinstance(keys, dict):
        flat_keys = tuple(k for side in keys.values() for k in side)
        if any(not tuple(side) for side in keys.values()):
            flat_keys = ()
    elif keys:
        flat_keys = tuple(keys)
    if not flat_keys:
        blocker(
            "RW-E902",
            "no dispatch keys declared for keyed sharded state: row "
            "ownership is undefined under the vnode mapping",
        )

    # -- E904: replicated leaves written by the per-shard step ----------
    updates = tuple(contract.get("updates", ()))
    for leaf, placement in (contract.get("state") or {}).items():
        if placement == "replicated" and leaf in updates:
            blocker(
                "RW-E904",
                f"state leaf {leaf!r} is declared replicated across "
                "the mesh but written by the per-shard step: silent "
                "cross-shard divergence",
            )

    # -- E906: merge order ----------------------------------------------
    if not contract.get("order_insensitive", False):
        blocker(
            "RW-E906",
            "cross-shard merge is not declared order-insensitive: the "
            "mesh result cannot be proven bit-identical to the serial "
            "twin",
        )

    # -- E901/E905/E907: the loop-classified host-routing scan ----------
    methods = (
        ("apply", "apply_left", "apply_right")
        + tuple(contract.get("barrier_methods", ()))
        + tuple(contract.get("fanout_methods", ()))
    )
    ec.sync_points = scan_mesh_syncs(ex, methods)
    for s in ec.sync_points:
        ec.blockers.append(
            MeshBlocker(
                _SYNC_CODE[s.kind],
                s.reason,
                prov,
                s.method,
                s.file,
                s.line,
            )
        )

    # -- E903 / positive proof: abstract shard_map trace over the
    #    bucket lattice ---------------------------------------------------
    trace_steps = contract.get("trace_steps")
    n = int(contract.get("n_shards") or 0)
    if spec is None:
        # schema threading lost (e.g. a join_tail section): trace with
        # a lane-free chunk — self-seeded contracts (the join builds
        # its own per-side abstract chunks) still prove; lane-reading
        # steps degrade to an honest note, never a silent skip
        spec = ChunkSpec((), (), 0)
    if deep and trace_steps is not None and n > 0:
        from risingwave_tpu.analysis.mesh_domain import (
            mesh_buckets,
            mesh_trace_signature,
            stacked_chunk,
        )

        sigs: Dict[str, set] = {}
        colls: List[str] = []
        failed = False
        for cap in mesh_buckets():
            abs_chunk = stacked_chunk(spec.with_capacity(cap), n)
            try:
                for label, step, args in trace_steps(abs_chunk):
                    sig = mesh_trace_signature(step, *args)
                    sigs.setdefault(label, set()).add(
                        (sig.in_avals, sig.out_avals)
                    )
                    colls.extend(sig.collectives)
                    for h in sig.host_calls:
                        file, line = _method_site(type(ex), "_build_step")
                        blocker(
                            "RW-E901",
                            f"host callback primitive {h!r} inside the "
                            "sharded program",
                            method=f"{name}._build_step",
                            file=file,
                            line=line,
                        )
            except Exception as e:  # noqa: BLE001
                kind = type(e).__name__
                file, line = _method_site(type(ex), "_build_step")
                if "Tracer" in kind or "Concretization" in kind:
                    blocker(
                        "RW-E903",
                        "shard-local step not shard_map-traceable at "
                        f"capacity {cap}: {kind} (Python branching on "
                        "per-shard values)",
                        method=f"{name}._build_step",
                        file=file,
                        line=line,
                    )
                else:
                    # untraceable with THIS schema: degrade honestly —
                    # no false blocker, no false proof
                    ec.note = (
                        f"abstract trace unavailable at capacity {cap}: "
                        f"{kind}"
                    )
                failed = True
                break
        if not failed and sigs:
            ec.traced = True
            ec.signatures = sum(len(v) for v in sigs.values())
            ec.collectives = tuple(sorted(set(colls)))
            budget = recompile_budget()
            per_label = max(len(v) for v in sigs.values())
            if per_label > budget:
                file, line = _method_site(type(ex), "_build_step")
                blocker(
                    "RW-E903",
                    f"{per_label} distinct shard_map signatures across "
                    f"the declared buckets > recompile budget {budget}: "
                    "per-shard shape polymorphism outside the lattice",
                    method=f"{name}._build_step",
                    file=file,
                    line=line,
                )

    # the positive proof: an honestly-declared mesh contract whose
    # step actually abstract-traced under shard_map over the lattice
    # with zero blockers. Shallow passes and failed traces are not
    # evidence.
    ec.spmd_proven = ec.traced and ec.signatures >= 1 and not ec.blockers
    return ec


# ---------------------------------------------------------------------------
# fragment / pipeline reports
# ---------------------------------------------------------------------------


@dataclass
class MeshFragmentReport:
    fragment: str
    executors: List[MeshExecutorClass] = field(default_factory=list)
    spmd_fusible: bool = False
    proof: Optional[dict] = None

    @property
    def blockers(self) -> List[MeshBlocker]:
        return [b for e in self.executors for b in e.blockers]

    @property
    def host_routed_edges(self) -> int:
        return sum(
            1
            for b in self.blockers
            if b.code in ("RW-E901", "RW-E907")
        )

    def to_json(self) -> dict:
        bl = self.blockers
        return {
            "fragment": self.fragment,
            "chain_len": len(self.executors),
            "mesh_executors": sum(
                1 for e in self.executors if e.kind == "mesh"
            ),
            "spmd_fusible": self.spmd_fusible,
            "proof": self.proof,
            "host_routed_edges": self.host_routed_edges,
            "executors": [e.to_json() for e in self.executors],
            "blockers": [b.to_json() for b in bl],
        }


def analyze_mesh_chain(
    chain: Sequence[object],
    spec: Optional[ChunkSpec],
    fragment: str,
    deep: bool = True,
) -> MeshFragmentReport:
    rep = MeshFragmentReport(fragment=fragment)
    for idx, ex in enumerate(chain):
        ec = classify_mesh_executor(ex, spec, fragment, idx, deep=deep)
        rep.executors.append(ec)
        spec = _thread_spec(spec, ex, _lint_info(ex))
    mesh = [e for e in rep.executors if e.kind == "mesh"]
    rep.spmd_fusible = (
        bool(mesh)
        and all(e.kind == "mesh" for e in rep.executors)
        and all(e.spmd_proven for e in mesh)
    )
    if rep.spmd_fusible:
        rep.proof = {
            "signatures": sum(e.signatures for e in mesh),
            "collectives": sorted(
                {c for e in mesh for c in e.collectives}
            ),
            "executors": [e.name for e in mesh],
        }
    return rep


def analyze_sharded_pipeline(
    pipeline,
    source_schemas: Optional[Dict[str, Dict[str, object]]] = None,
    name: str = "mv",
    deep: bool = True,
) -> List[MeshFragmentReport]:
    """Mesh reports for every SHARDED fragment of a pipeline (fragment
    extraction via runtime.fragmenter.sharded_chains — fragments with
    no mesh-resident executor are the fusion analyzer's territory)."""
    from risingwave_tpu.runtime.fragmenter import sharded_chains

    source_schemas = source_schemas or {}
    out: List[MeshFragmentReport] = []
    for frag, sections in sharded_chains(pipeline).items():
        for side, chain in sections.items():
            if not chain:
                continue
            schema = (
                source_schemas.get(side)
                if side in ("single", "left", "right", "both")
                else None
            )
            spec = (
                ChunkSpec.from_schema(schema) if schema is not None else None
            )
            label = frag if side in ("single", "chain") else f"{frag}/{side}"
            out.append(
                analyze_mesh_chain(
                    chain, spec, f"{name}:{label}", deep=deep
                )
            )
    return out


# ---------------------------------------------------------------------------
# measured-cost ranking (MULTICHIP.json -> est_exchange_ms)
# ---------------------------------------------------------------------------

_HOST_LANES = ("host_split", "host_flatten", "host_other")


def attach_mesh_costs(
    reports: Sequence[MeshFragmentReport],
    mesh_block: Optional[dict],
    n_shards: int = 8,
) -> None:
    """Attach PR 18's measured exchange-boundary cost to the static
    blockers: the meshprof host lanes (host_split + host_flatten +
    host_other ms per barrier set) spread over this query's
    exchange_route blockers, and the ×N dispatch arithmetic on every
    fan-out site. Rank = highest measured reclaim first."""
    host_ms = 0.0
    if mesh_block:
        phases = mesh_block.get("phases_ms") or {}
        host_ms = sum(float(phases.get(k, 0.0)) for k in _HOST_LANES)
    route = [
        b
        for r in reports
        for b in r.blockers
        if b.phase == "exchange_route"
    ]
    share = round(host_ms / len(route), 3) if route and host_ms else None
    for b in route:
        b.est_exchange_ms = share
        if b.code == "RW-E907":
            b.est_dispatches_saved = max(0, n_shards - 1)
    for r in reports:
        for e in r.executors:
            e.blockers.sort(
                key=lambda b: (
                    -(b.est_exchange_ms or 0.0),
                    -(b.est_dispatches_saved or 0),
                    b.code,
                    b.line,
                )
            )


def report_to_json(reports: Sequence[MeshFragmentReport]) -> dict:
    frs = [r.to_json() for r in reports]
    codes: Dict[str, int] = {}
    for r in frs:
        for b in r["blockers"]:
            codes[b["code"]] = codes.get(b["code"], 0) + 1
    return {
        "fragments": frs,
        "summary": {
            "fragments": len(frs),
            "spmd_fusible_fragments": sum(
                1 for r in frs if r["spmd_fusible"]
            ),
            "host_routed_edges": sum(
                r["host_routed_edges"] for r in frs
            ),
            "blockers_by_code": dict(sorted(codes.items())),
        },
    }


def _ranking(per_query: Dict[str, List[MeshFragmentReport]]) -> List[dict]:
    rows = []
    for q, reports in per_query.items():
        for r in reports:
            for b in r.blockers:
                rows.append(
                    {
                        "query": q,
                        "fragment": r.fragment,
                        "executor": b.executor,
                        "code": b.code,
                        "phase": b.phase,
                        "file": b.file,
                        "line": b.line,
                        "est_exchange_ms": b.est_exchange_ms,
                        "est_dispatches_saved": b.est_dispatches_saved,
                        "message": b.message,
                    }
                )
    rows.sort(
        key=lambda r: (
            -(r["est_exchange_ms"] or 0.0),
            -(r["est_dispatches_saved"] or 0),
            r["code"],
            r["query"],
            r["line"],
        )
    )
    for i, r in enumerate(rows):
        r["rank"] = i + 1
    return rows


def _top_cost(rows: List[dict]) -> dict:
    by_phase: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for r in rows:
        by_phase[r["phase"]] = by_phase.get(r["phase"], 0.0) + (
            r["est_exchange_ms"] or 0.0
        )
        counts[r["phase"]] = counts.get(r["phase"], 0) + 1
    top = (
        max(by_phase, key=lambda p: (by_phase[p], counts[p]))
        if by_phase
        else None
    )
    return {
        "phase": top,
        "est_ms": round(by_phase.get(top, 0.0), 3) if top else 0.0,
        "blockers": counts.get(top, 0) if top else 0,
        "phases_est_ms": {
            k: round(v, 3) for k, v in sorted(by_phase.items())
        },
        "source": "MULTICHIP.json phases_ms (host_split + host_flatten "
        "+ host_other per query)",
    }


def analyze_sharded_nexmark(
    deep: bool = True,
    multichip: Optional[dict] = None,
    n_shards: int = 8,
) -> Dict[str, object]:
    """Mesh reports for the sharded Nexmark corpus (q5/q7/q8 on the
    N-virtual-device sim mesh) — the committed MESH_REPORT.json shape.
    ``multichip``: the committed MULTICHIP.json dict; its per-query
    measured phase splits rank the blockers."""
    from risingwave_tpu.analysis.lint import (
        NEXMARK_SOURCE_SCHEMAS,
        build_sharded_nexmark_corpus,
    )

    per_query: Dict[str, List[MeshFragmentReport]] = {}
    out: Dict[str, object] = {}
    mdata = (multichip or {}).get("queries", {})
    for qname, q in build_sharded_nexmark_corpus(n_shards).items():
        try:
            reports = analyze_sharded_pipeline(
                q.pipeline,
                NEXMARK_SOURCE_SCHEMAS[qname],
                qname,
                deep=deep,
            )
            attach_mesh_costs(
                reports,
                (mdata.get(qname) or {}).get("mesh"),
                n_shards=n_shards,
            )
            per_query[qname] = reports
            out[qname] = report_to_json(reports)
        finally:
            close = getattr(q.pipeline, "close", None)
            if close is not None:
                try:
                    close()
                except BaseException:  # noqa: BLE001
                    pass
    rows = _ranking(per_query)
    out["ranking"] = rows
    out["top_cost"] = _top_cost(rows)
    try:
        from risingwave_tpu.provenance import stamp

        out["_provenance"] = stamp()
    except Exception:  # noqa: BLE001 — provenance is best effort
        pass
    return out
