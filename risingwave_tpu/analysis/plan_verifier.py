"""Part A — the static plan-graph verifier.

Walks planner/fragmenter output BEFORE actors spawn and checks the
invariants the runtime otherwise assumes:

- per-channel schema agreement: every column an executor reads exists
  on its input channel with the dtype the executor declared (RW-E101 /
  RW-E102);
- exchange soundness: hash-dispatch keys exist upstream (RW-E201) and
  cover the downstream parallel fragment's keyed state (RW-E202) — the
  Shared-Arrangements alignment invariant; unkeyed dispatch kinds never
  feed parallel keyed state (RW-E203);
- join key dtype agreement across sides (RW-E204);
- watermark reachability: window-keyed state cleaning is only sound
  when a watermark can actually reach the window column — i.e. the
  column traces to a source column or a watermark-producing executor
  through the chain's watermark-translation maps (RW-E501);
- wiring: channels reference real fragments, no duplicate edges, the
  barrier DAG is acyclic, every fragment's output is consumed
  (RW-E6xx);
- state tables: materialize pk coverage (RW-E701), unique table_ids
  within a plan (RW-E702).

Metadata comes from ``Executor.lint_info()`` (executors/base.py).
Executors that expose none are OPAQUE: schema/watermark tracking stops
at them and downstream value-level checks are skipped — the verifier
never guesses, so a diagnostic is always a provable defect.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax.numpy as jnp

from risingwave_tpu.analysis.diagnostics import Diagnostic, LintReport

# schema: col -> dtype (None = present, dtype unknown); whole-schema
# None = opaque (tracking lost)
Schema = Optional[Dict[str, object]]


def _host_device():
    """``jax.default_device(cpu)`` context, or a no-op when the CPU
    backend is unavailable (e.g. JAX_PLATFORMS pinned elsewhere)."""
    import contextlib

    import jax

    try:
        return jax.default_device(jax.devices("cpu")[0])
    except Exception:  # noqa: BLE001
        return contextlib.nullcontext()


def _dt(x) -> Optional[object]:
    if x is None:
        return None
    try:
        return jnp.dtype(x)
    except TypeError:
        return None


def _info_of(
    ex, rep: Optional[LintReport] = None, fragment: str = "", prov: str = ""
) -> Optional[dict]:
    fn = getattr(ex, "lint_info", None)
    if fn is None:
        return None  # legitimately opaque: no metadata advertised
    try:
        return fn()
    except Exception as e:  # noqa: BLE001 — lint must never crash DDL
        # a BROKEN lint_info is not silent opacity: without a signal,
        # every check downstream of this executor quietly regresses
        # while the suite keeps reporting clean (warning, not error —
        # degraded verification must not refuse an honest DDL)
        if rep is not None:
            rep.add(
                "RW-E001",
                f"lint_info() raised {type(e).__name__}: {e}",
                fragment=fragment,
                executor=prov,
                severity="warning",
            )
        return None


def _prov(idx: int, ex) -> str:
    return f"{idx}:{type(ex).__name__}"


def _e708_severity() -> str:
    """RW-E708 is report-only by DEFAULT even though sessions default
    strict (RW_STRICT_LINT unset = strict): promoting it to an error
    for every pre-existing DDL would refuse plans that were legal
    yesterday. Only an EXPLICITLY-set truthy RW_STRICT_LINT (the
    __main__.py opt-in convention) makes unaccounted state a refusal."""
    import os

    v = os.environ.get("RW_STRICT_LINT")
    if v is not None and v.strip().lower() not in ("", "0", "off", "false"):
        return "error"
    return "warning"


def _check_ledger_visible(ex, info, fragment, prov, rep) -> None:
    """RW-E708: an executor that registers state table_ids with the
    runtime but is invisible to the memory governor's ledger — no
    ``state_nbytes()``/``state_bytes()`` accounting contract and no
    allocator-backed capacity note (``_buckets``). Unaccounted device
    state cannot be budgeted, vetoed or spilled: under overload it is
    exactly the state that OOMs the device while the governor reports
    headroom."""
    if not (info.get("table_ids") or ()):
        return
    if (
        hasattr(ex, "state_nbytes")
        or hasattr(ex, "state_bytes")
        or getattr(ex, "_buckets", None) is not None
    ):
        return
    rep.add(
        "RW-E708",
        f"{type(ex).__name__} registers state table(s) "
        f"{tuple(info.get('table_ids') or ())!r} but exposes neither "
        "state_nbytes()/state_bytes() nor an allocator capacity note — "
        "its device state is invisible to the HBM memory ledger",
        fragment=fragment,
        executor=prov,
        severity=_e708_severity(),
    )


def _check_digest_coverage(ex, info, fragment, prov, rep) -> None:
    """RW-E709: an executor that registers state table_ids but has no
    working ``state_digest()`` — its device state sits OUTSIDE the
    integrity layer's corruption checks (no fused-vs-interpreted
    cross-check, no checkpoint digest, no scrub coverage), so a silent
    in-HBM bit-flip there is undetectable by construction. Severity
    follows the E708 convention: report-only unless RW_STRICT_LINT is
    explicitly set truthy."""
    if not (info.get("table_ids") or ()):
        return
    from risingwave_tpu.storage.state_table import Checkpointable

    fn = getattr(type(ex), "state_digest", None)
    if fn is None or fn is Checkpointable.state_digest:
        rep.add(
            "RW-E709",
            f"{type(ex).__name__} registers state table(s) "
            f"{tuple(info.get('table_ids') or ())!r} but implements no "
            "state_digest() — silent corruption of its device state is "
            "invisible to the integrity layer",
            fragment=fragment,
            executor=prov,
            severity=_e708_severity(),
        )
        return
    lanes_fn = getattr(ex, "digest_lanes", None)
    if callable(lanes_fn):
        try:
            from risingwave_tpu.integrity import foldable_dtypes

            bad = list(foldable_dtypes(lanes_fn()[0]))
        except Exception:  # noqa: BLE001 — lanes need built state;
            return  # runtime digest paths still exercise them
        if bad:
            rep.add(
                "RW-E709",
                f"{type(ex).__name__} digest_lanes() exposes lanes the "
                f"fold cannot cover: {bad!r}",
                fragment=fragment,
                executor=prov,
                severity=_e708_severity(),
            )


class _TableIds:
    """Plan-wide table_id uniqueness (RW-E702). Parallel instances of
    one logical fragment share table_ids BY DESIGN (disjoint vnode
    partitions of the same logical table), so collection is keyed by
    (instance, table_id) and duplicates only flag within an instance."""

    def __init__(self, rep: LintReport):
        self.rep = rep
        self.seen: Dict[Tuple[int, str], Tuple[str, str]] = {}

    def add(self, instance: int, tids, fragment: str, executor: str) -> None:
        for tid in tids or ():
            key = (instance, tid)
            if key in self.seen:
                f0, e0 = self.seen[key]
                self.rep.add(
                    "RW-E702",
                    f"state table_id {tid!r} already used by "
                    f"[frag={f0} ex={e0}]",
                    fragment=fragment,
                    executor=executor,
                )
            else:
                self.seen[key] = (fragment, executor)


def _walk_chain(
    chain: Sequence[object],
    schema: Schema,
    wm: Optional[Set[str]],
    fragment: str,
    rep: LintReport,
    tids: _TableIds,
    instance: int = 0,
) -> Tuple[Schema, Optional[Set[str]]]:
    """Push a schema + watermark-capability set through one executor
    chain, checking each executor's declared metadata on the way."""
    for idx, ex in enumerate(chain):
        prov = _prov(idx, ex)
        info = _info_of(ex, rep, fragment, prov)
        tid = getattr(ex, "table_id", None)
        if info is None:
            # opaque executor: record its table id, stop tracking
            tids.add(instance, (tid,) if tid else (), fragment, prov)
            schema, wm = None, None
            continue
        tids.add(instance, info.get("table_ids", ()), fragment, prov)
        _check_ledger_visible(ex, info, fragment, prov, rep)
        _check_digest_coverage(ex, info, fragment, prov, rep)

        expects = {k: _dt(v) for k, v in (info.get("expects") or {}).items()}
        requires = set(info.get("requires") or ()) | set(expects)
        if schema is not None:
            for col in sorted(requires):
                if col not in schema:
                    rep.add(
                        "RW-E101",
                        f"column {col!r} is not produced upstream "
                        f"(channel carries {sorted(schema)})",
                        fragment=fragment,
                        executor=prov,
                    )
                else:
                    want = expects.get(col)
                    have = _dt(schema[col])
                    if want is not None and have is not None and want != have:
                        rep.add(
                            "RW-E102",
                            f"column {col!r} arrives as {have} but the "
                            f"executor declared {want}",
                            fragment=fragment,
                            executor=prov,
                        )
            for col in info.get("state_pk") or ():
                if col not in schema:
                    rep.add(
                        "RW-E701",
                        f"state-table pk column {col!r} is not in the "
                        f"input schema (channel carries {sorted(schema)})",
                        fragment=fragment,
                        executor=prov,
                    )

        wcol = info.get("window_key")
        if wcol is not None and wm is not None and wcol not in wm:
            rep.add(
                "RW-E501",
                f"window-keyed state cleaning on {wcol!r}, but no "
                "watermark can reach it (not a source column, not a "
                "hop-window output, not watermark-filter generated) — "
                "state would grow without bound",
                fragment=fragment,
                executor=prov,
            )

        # schema transition
        emits = info.get("emits")
        if emits is not None:
            prev = schema
            schema = {k: _dt(v) for k, v in emits.items()}
            if prev is not None:
                # rename-only outputs inherit the source column's dtype
                for out, src in (info.get("renames") or {}).items():
                    if (
                        out in schema
                        and schema[out] is None
                        and src is not None
                    ):
                        schema[out] = _dt(prev.get(src))
        elif schema is not None:
            adds = info.get("adds") or {}
            if adds:
                schema = dict(schema)
                for k, v in adds.items():
                    schema[k] = _dt(v)

        # watermark-capability transition
        if wm is not None:
            if emits is not None:
                renames = info.get("renames") or {}
                wm = {
                    out
                    for out, src in renames.items()
                    if src is not None and src in wm
                }
            else:
                for in_col, out_col in (info.get("watermark_map") or {}).items():
                    if in_col in wm:
                        wm = set(wm) | {out_col}
            src = info.get("watermark_src")
            if src is not None:
                wm = set(wm) | {src}
    return schema, wm


def _trace_back(chain_prefix: Sequence[object], name: str) -> Optional[str]:
    """The input-channel column ``name`` is an unmodified copy of, or
    None if computed/renamed-over/opaque (the verifier's twin of the
    fragmenter's ``_trace_source_col``, driven by lint_info)."""
    cur = name
    for ex in reversed(list(chain_prefix)):
        info = _info_of(ex)
        if info is None:
            return None
        emits = info.get("emits")
        if emits is not None:
            src = (info.get("renames") or {}).get(cur)
            if src is None:
                return None
            cur = src
            continue
        if cur in (info.get("adds") or {}):
            return None  # computed in this executor
    return cur


def _join_info(
    join, rep: Optional[LintReport] = None, fragment: str = ""
) -> Optional[dict]:
    return _info_of(
        join, rep, fragment, f"join:{type(join).__name__}"
    )


def _verify_join(
    join,
    lschema: Schema,
    rschema: Schema,
    lwm: Optional[Set[str]],
    rwm: Optional[Set[str]],
    fragment: str,
    rep: LintReport,
    tids: _TableIds,
    instance: int = 0,
) -> Tuple[Schema, Optional[Set[str]]]:
    info = _join_info(join, rep, fragment)
    prov = f"join:{type(join).__name__}"
    if info is None:
        tid = getattr(join, "table_id", None)
        tids.add(instance, (tid,) if tid else (), fragment, prov)
        return None, None
    tids.add(instance, info.get("table_ids", ()), fragment, prov)
    _check_ledger_visible(join, info, fragment, prov, rep)
    _check_digest_coverage(join, info, fragment, prov, rep)
    lkeys = tuple(info.get("left_keys") or ())
    rkeys = tuple(info.get("right_keys") or ())
    for side, schema, expects in (
        ("left", lschema, info.get("expects_left") or {}),
        ("right", rschema, info.get("expects_right") or {}),
    ):
        if schema is None:
            continue
        for col, want in expects.items():
            if col not in schema:
                rep.add(
                    "RW-E101",
                    f"join {side} input lacks column {col!r} "
                    f"(channel carries {sorted(schema)})",
                    fragment=fragment,
                    executor=prov,
                )
            else:
                want, have = _dt(want), _dt(schema[col])
                if want is not None and have is not None and want != have:
                    rep.add(
                        "RW-E102",
                        f"join {side} column {col!r} arrives as {have} "
                        f"but the join declared {want}",
                        fragment=fragment,
                        executor=prov,
                    )
    # per-position key dtype agreement across sides (RW-E204)
    el = info.get("expects_left") or {}
    er = info.get("expects_right") or {}
    for pos, (lk, rk) in enumerate(zip(lkeys, rkeys)):
        ld, rd = _dt(el.get(lk)), _dt(er.get(rk))
        if ld is not None and rd is not None and ld != rd:
            rep.add(
                "RW-E204",
                f"join key position {pos}: left {lk!r} is {ld} but "
                f"right {rk!r} is {rd} — equal keys would hash apart",
                fragment=fragment,
                executor=prov,
            )
    # window-column watermark reachability per side (RW-E501)
    wcols = info.get("window_cols")
    if wcols:
        for col, wm, side in ((wcols[0], lwm, "left"), (wcols[1], rwm, "right")):
            if wm is not None and col not in wm:
                rep.add(
                    "RW-E501",
                    f"join {side} window column {col!r} is not "
                    "watermark-reachable — join state would grow "
                    "without bound",
                    fragment=fragment,
                    executor=prov,
                )
    emits = info.get("emits")
    schema = {k: _dt(v) for k, v in emits.items()} if emits is not None else None
    wm_out: Optional[Set[str]] = None
    if schema is not None and lwm is not None and rwm is not None:
        wm_out = (set(lwm) | set(rwm)) & set(schema)
    return schema, wm_out


# ---------------------------------------------------------------------------
# pipeline-level entry points
# ---------------------------------------------------------------------------


def _first_keyed(chain: Sequence[object]):
    """(index, keys) of the first executor exposing state partition
    keys, or None."""
    for j, ex in enumerate(chain):
        info = _info_of(ex)
        if info is None:
            return None
        if info.get("keys"):
            return j, tuple(info["keys"])
    return None


def verify_serial_pipeline(
    pipeline, source_schemas: Dict[str, Schema], name: str, rep: LintReport
) -> None:
    tids = _TableIds(rep)
    if hasattr(pipeline, "join") and hasattr(pipeline, "left"):
        ls = source_schemas.get("left")
        rs = source_schemas.get("right")
        lwm, rwm = set(ls) if ls else None, set(rs) if rs else None
        if getattr(pipeline, "head", None):
            # one stream through the shared sub-plan, then into both sides
            ls, lwm = _walk_chain(pipeline.head, ls, lwm, name, rep, tids)
            rs, rwm = ls, lwm
        lschema, lwm = _walk_chain(pipeline.left, ls, lwm, name, rep, tids)
        rschema, rwm = _walk_chain(pipeline.right, rs, rwm, name, rep, tids)
        schema, wm = _verify_join(
            pipeline.join, lschema, rschema, lwm, rwm, name, rep, tids
        )
        _walk_chain(pipeline.tail, schema, wm, name, rep, tids)
        return
    if hasattr(pipeline, "executors"):
        ss = source_schemas.get("single")
        _walk_chain(
            pipeline.executors, ss, set(ss) if ss else None, name, rep, tids
        )


def verify_graph_specs(
    specs: Sequence[object],
    out_fragment: str,
    source_fragments: Dict[str, str],  # side -> fragment name
    source_schemas: Dict[str, Schema],  # side -> schema
    rep: LintReport,
    ckpt_executors: Optional[Sequence[object]] = None,
) -> None:
    """Fragment-DAG verification: wiring, acyclicity, exchange key
    alignment, then per-fragment chain walks in topological order.
    With ``ckpt_executors`` (the pipeline's checkpoint registry), also
    checks every fragment's rebuildable boundary (RW-E606)."""
    by_name: Dict[str, object] = {}
    for s in specs:
        if s.name in by_name:
            rep.add(
                "RW-E602",
                f"fragment name {s.name!r} declared twice",
                fragment=s.name,
            )
        by_name[s.name] = s

    # -- wiring ----------------------------------------------------------
    ok_edges: Dict[str, List[Tuple[str, int]]] = {s.name: [] for s in specs}
    consumed: Set[str] = set()
    for s in specs:
        seen: Set[Tuple[str, int]] = set()
        for up, port in s.inputs:
            if up not in by_name:
                rep.add(
                    "RW-E601",
                    f"input channel references unknown fragment {up!r}",
                    fragment=s.name,
                )
                continue
            if (up, port) in seen:
                rep.add(
                    "RW-E602",
                    f"duplicate channel from {up!r} port {port} — the "
                    "consumer would collect every barrier twice",
                    fragment=s.name,
                )
                continue
            seen.add((up, port))
            ok_edges[s.name].append((up, port))
            consumed.add(up)
    for side, frag in source_fragments.items():
        if frag not in by_name:
            rep.add(
                "RW-E605",
                f"declared source fragment {frag!r} (side {side!r}) "
                "does not exist",
                fragment=frag,
            )
    if out_fragment not in by_name:
        rep.add(
            "RW-E605",
            f"declared output fragment {out_fragment!r} does not exist",
            fragment=out_fragment,
        )
    for s in specs:
        if s.name != out_fragment and s.name not in consumed:
            rep.add(
                "RW-E604",
                f"fragment {s.name!r} output is never consumed "
                "(not an input of any fragment, not the output fragment)",
                fragment=s.name,
            )

    # -- acyclicity (Kahn) ----------------------------------------------
    indeg = {s.name: len(ok_edges[s.name]) for s in specs}
    downstream: Dict[str, List[str]] = {s.name: [] for s in specs}
    for s in specs:
        for up, _port in ok_edges[s.name]:
            downstream[up].append(s.name)
    order: List[str] = [n for n, d in indeg.items() if d == 0]
    topo: List[str] = []
    while order:
        n = order.pop()
        topo.append(n)
        for d in downstream[n]:
            indeg[d] -= 1
            if indeg[d] == 0:
                order.append(d)
    if len(topo) < len(by_name):
        cyc = sorted(set(by_name) - set(topo))
        rep.add(
            "RW-E603",
            f"fragment graph contains a cycle through {cyc} — a barrier "
            "injected at the sources can never align",
            fragment=",".join(cyc),
        )
        return  # schema walk needs a topological order

    # -- per-fragment builds + schema walk in topo order -----------------
    tids = _TableIds(rep)
    frag_side = {frag: side for side, frag in source_fragments.items()}
    out_schema: Dict[str, Schema] = {}
    out_wm: Dict[str, Optional[Set[str]]] = {}
    builds: Dict[str, object] = {}
    for name in topo:
        s = by_name[name]
        try:
            # shadow build ONLY to read lint_info (the live actors hold
            # their own, possibly epoch-batch-fused, executors) — pin
            # its state allocations to host CPU so DDL-time lint never
            # transiently doubles HBM state on a device session
            with _host_device():
                built = s.build(0)
        except Exception:  # noqa: BLE001 — builder needs live inputs
            built = None
        builds[name] = built
        # input schema per port: merge upstream outputs (dtype conflicts
        # degrade to unknown rather than guessing)
        port_schema: Dict[int, Schema] = {}
        port_wm: Dict[int, Optional[Set[str]]] = {}
        if not s.inputs:
            side = frag_side.get(name)
            sch = source_schemas.get(side) if side is not None else None
            port_schema[0] = dict(sch) if sch is not None else None
            port_wm[0] = set(sch) if sch is not None else None
        for up, port in ok_edges[name]:
            upsch = out_schema.get(up)
            upwm = out_wm.get(up)
            if port not in port_schema:
                port_schema[port] = (
                    dict(upsch) if upsch is not None else None
                )
                port_wm[port] = set(upwm) if upwm is not None else None
            else:
                cur = port_schema[port]
                if cur is None or upsch is None:
                    port_schema[port] = None
                    port_wm[port] = None
                else:
                    for k, v in upsch.items():
                        if k in cur and _dt(cur[k]) != _dt(v):
                            cur[k] = None
                        else:
                            cur.setdefault(k, v)
                    if port_wm[port] is not None and upwm is not None:
                        port_wm[port] = port_wm[port] & upwm
                    else:
                        port_wm[port] = None
        if isinstance(built, dict):
            if built.get("head"):
                # the shared sub-plan's output feeds both sides
                port_schema[0], port_wm[0] = _walk_chain(
                    built["head"],
                    port_schema.get(0),
                    port_wm.get(0),
                    name,
                    rep,
                    tids,
                )
                port_schema[1], port_wm[1] = port_schema[0], port_wm[0]
            lschema, lwm = _walk_chain(
                built.get("left", []),
                port_schema.get(0),
                port_wm.get(0),
                name,
                rep,
                tids,
            )
            rschema, rwm = _walk_chain(
                built.get("right", []),
                port_schema.get(1),
                port_wm.get(1),
                name,
                rep,
                tids,
            )
            schema, wm = _verify_join(
                built["join"], lschema, rschema, lwm, rwm, name, rep, tids
            )
            schema, wm = _walk_chain(
                built.get("tail", []), schema, wm, name, rep, tids
            )
        elif isinstance(built, (list, tuple)):
            schema, wm = _walk_chain(
                list(built),
                port_schema.get(0),
                port_wm.get(0),
                name,
                rep,
                tids,
            )
        else:
            schema, wm = None, None
        out_schema[name] = schema
        out_wm[name] = wm

    # -- exchange key alignment ------------------------------------------
    for name in topo:
        s = by_name[name]
        kind = s.dispatch
        keys: Sequence[str] = ()
        if isinstance(kind, tuple):
            kind, keys = kind[0], tuple(kind[1] or ())
        upsch = out_schema.get(name)
        if kind == "hash" and upsch is not None:
            for k in keys:
                if k not in upsch:
                    rep.add(
                        "RW-E201",
                        f"hash-dispatch key {k!r} is not in the "
                        f"fragment's output (carries {sorted(upsch)})",
                        fragment=name,
                    )
        for down in downstream[name]:
            d = by_name[down]
            if d.parallelism <= 1:
                continue
            built = builds.get(down)
            port_of = dict((up, p) for up, p in ok_edges[down])
            port = port_of.get(name, 0)
            if isinstance(built, dict):
                chain = list(built.get("head", ())) + list(
                    built.get("left" if port == 0 else "right", [])
                )
                jinfo = _join_info(built.get("join"))
                state_keys = (
                    tuple(
                        (jinfo.get("left_keys") if port == 0 else jinfo.get("right_keys"))
                        or ()
                    )
                    if jinfo is not None
                    else None
                )
                prefix = chain
                prov = f"join:{type(built.get('join')).__name__}"
            elif isinstance(built, (list, tuple)):
                fk = _first_keyed(list(built))
                if fk is None:
                    state_keys = None
                    prefix, prov = [], ""
                else:
                    j, state_keys = fk
                    prefix = list(built)[:j]
                    prov = _prov(j, list(built)[j])
            else:
                continue
            if state_keys is None:
                continue  # no keyed state visible — nothing to misroute
            if kind in ("round_robin", "broadcast"):
                rep.add(
                    "RW-E203",
                    f"{kind} dispatch feeds parallel fragment {down!r} "
                    "which holds keyed state — rows of one key would "
                    "land on several instances",
                    fragment=name,
                    executor=prov,
                )
                continue
            if kind != "hash":
                continue
            traced = {}
            for k in state_keys:
                src = _trace_back(prefix, k)
                if src is not None:
                    traced[src] = k
            for dcol in keys:
                if dcol not in traced:
                    rep.add(
                        "RW-E202",
                        f"dispatch key {dcol!r} does not map to any "
                        f"state key of parallel fragment {down!r} "
                        f"(state keys {list(state_keys)}) — equal-key "
                        "rows could land on different instances",
                        fragment=name,
                        executor=prov,
                    )

    # -- rebuildable boundary per fragment (RW-E606) ----------------------
    if ckpt_executors is not None:
        _check_rebuildable(topo, builds, ckpt_executors, rep)


def _check_rebuildable(
    topo: Sequence[str],
    builds: Dict[str, object],
    ckpt_executors: Sequence[object],
    rep: LintReport,
) -> None:
    """RW-E606: every stateful executor a fragment builds must be
    restorable through the pipeline's checkpoint registry (same
    table_id, with a real ``restore_state``), or a partial recovery of
    that fragment cannot rebuild its state — the plan would only ever
    recover stop-the-world, silently. Flagged at DDL time."""
    from risingwave_tpu.storage.state_table import Checkpointable

    def _tids(ex) -> Tuple[str, ...]:
        fn = getattr(ex, "checkpoint_table_ids", None)
        if fn is None:
            return ()
        try:
            return tuple(fn())
        except Exception:  # noqa: BLE001 — lint must never crash DDL
            return ()

    restorable: Set[str] = set()
    for ex in ckpt_executors:
        if not isinstance(ex, Checkpointable):
            continue
        if type(ex).restore_state is Checkpointable.restore_state:
            rep.add(
                "RW-E606",
                f"checkpoint registry entry {type(ex).__name__} "
                f"(tables {list(_tids(ex))}) does not implement "
                "restore_state — its state checkpoints but can never "
                "be restored",
                executor=type(ex).__name__,
            )
            continue
        restorable |= set(_tids(ex))

    for name in topo:
        built = builds.get(name)
        if built is None:
            continue  # builder needs live inputs: nothing provable
        if isinstance(built, dict):
            chains = (
                list(built.get("head", ()))
                + list(built.get("left", ()))
                + list(built.get("right", ()))
                + ([built["join"]] if built.get("join") is not None else [])
                + list(built.get("tail", ()))
            )
        else:
            chains = list(built)
        for idx, ex in enumerate(chains):
            if not isinstance(ex, Checkpointable):
                continue
            missing = [t for t in _tids(ex) if t not in restorable]
            if missing:
                rep.add(
                    "RW-E606",
                    f"stateful executor's tables {missing} are not "
                    "covered by the pipeline's checkpoint registry — "
                    f"fragment {name!r} has no rebuildable boundary "
                    "(partial recovery cannot restore it)",
                    fragment=name,
                    executor=_prov(idx, ex),
                )


def verify_planned(
    planned,
    catalog=None,
    source_schemas: Optional[Dict[str, Schema]] = None,
) -> List[Diagnostic]:
    """Verify one PlannedMV (serial or graph pipeline). Source schemas
    come from the catalog via ``planned.inputs`` unless given."""
    rep = LintReport()
    name = getattr(planned, "name", "mv")
    pipeline = getattr(planned, "pipeline", planned)
    if source_schemas is None:
        source_schemas = {}
        if catalog is not None:
            for src, side in (getattr(planned, "inputs", None) or {}).items():
                if src not in getattr(catalog, "tables", {}):
                    continue
                sch = catalog.schema_dtypes(src)
                sides = (
                    ("left", "right", "both") if side == "both" else (side,)
                )
                for s in sides:
                    source_schemas[s] = dict(sch)
    if hasattr(pipeline, "_specs") and hasattr(pipeline, "graph"):
        verify_graph_specs(
            pipeline._specs,
            pipeline._out,
            dict(pipeline._sources),
            {
                side: source_schemas.get(side)
                for side in pipeline._sources
            },
            rep,
            # the checkpoint registry, when the pipeline exposes one
            # (GraphPipeline does; spec-level stubs don't) — drives the
            # RW-E606 rebuildable-boundary check
            ckpt_executors=getattr(pipeline, "_executors", None),
        )
    else:
        verify_serial_pipeline(pipeline, source_schemas, name, rep)
    return rep.diagnostics
