"""``rw_`` system tables: the runtime's own state as SQL relations.

Reference: the reference catalog's ``rw_catalog`` schema
(src/frontend/src/catalog/system_catalog/rw_catalog/ — rw_fragments,
rw_materialized_views, rw_ddl_progress, ...): read-only virtual
relations the frontend serves straight from meta/introspection state.
Shared Arrangements' dogfooding argument (PAPERS.md) applies verbatim:
introspection should be served THROUGH the system, off the same
versioned snapshots queries read — so these tables ride the exact
lock-free ``_execute_shared_read`` path PR 12 built for shared MVs.

Each table is a ``SysTable``: a Schema plus a rows() builder over live
process state (runtime fragments, the arrangement registry, the
freshness tracker, epoch traces, permit channels, the event log). The
batch engine only ever calls ``to_numpy()`` on a scan target, so a
SysTable quacks exactly like a MaterializeExecutor snapshot: a dict of
numpy columns, VARCHAR as dictionary codes in the session's
StringDictionary. Builders read with plain attribute access + defensive
copies and NEVER take the runtime lock — a wedged barrier must remain
SELECT-able (that is the point of a stall-forensics surface).

Registration happens once per session under ``_registry_guard``
(``install_sys_tables``); the names are reserved — DDL against ``rw_``
raises in the session.
"""

from __future__ import annotations

import time
from typing import Callable, Dict, List

import numpy as np

from risingwave_tpu.types import DataType, Schema

# (name, dtype) per table; VARCHAR lanes carry dictionary codes like
# every other relation (batch._decode_output decodes them back)
SYS_SCHEMAS: Dict[str, Schema] = {
    "rw_fragments": Schema(
        [
            ("name", DataType.VARCHAR),
            ("kind", DataType.VARCHAR),
            ("executors", DataType.INT64),
            ("fused", DataType.INT64),
            ("epoch", DataType.INT64),
            ("subscribers", DataType.VARCHAR),
        ]
    ),
    "rw_arrangements": Schema(
        [
            ("owner", DataType.VARCHAR),
            ("fragment", DataType.VARCHAR),
            ("refs", DataType.INT64),
            ("shared", DataType.INT64),
            ("published_epoch", DataType.INT64),
            ("readers", DataType.VARCHAR),
        ]
    ),
    "rw_mv_freshness": Schema(
        [
            ("mv", DataType.VARCHAR),
            ("epoch", DataType.INT64),
            ("checkpoint", DataType.INT64),
            ("commit_to_visible_ms", DataType.FLOAT64),
            ("source_to_visible_ms", DataType.FLOAT64),
            ("event_time_lag_ms", DataType.FLOAT64),
            ("staleness_ms", DataType.FLOAT64),
            ("barriers", DataType.INT64),
        ]
    ),
    "rw_barrier_latency": Schema(
        [
            ("epoch", DataType.INT64),
            ("seq", DataType.INT64),
            ("checkpoint", DataType.INT64),
            ("wall_ms", DataType.FLOAT64),
            ("dispatch_ms", DataType.FLOAT64),
            ("device_step_ms", DataType.FLOAT64),
            ("backpressure_fragment", DataType.VARCHAR),
            ("backpressure_ms", DataType.FLOAT64),
        ]
    ),
    "rw_channel_depths": Schema(
        [
            ("fragment", DataType.VARCHAR),
            ("actor", DataType.VARCHAR),
            ("channel", DataType.INT64),
            ("depth", DataType.INT64),
            ("oldest_age_ms", DataType.FLOAT64),
            ("oldest_epoch", DataType.INT64),
        ]
    ),
    "rw_fusion_status": Schema(
        [
            ("fragment", DataType.VARCHAR),
            ("kind", DataType.VARCHAR),
            ("fused", DataType.INT64),
            ("fused_executors", DataType.INT64),
            ("executors", DataType.INT64),
        ]
    ),
    # integrity scrub: one row per checkpoint artifact the current
    # manifest references (plus the manifest pointer itself); status
    # in {ok, corrupt, unverified, unavailable}. A healthy store reads
    # all-ok; no store at all reads empty.
    "rw_integrity": Schema(
        [
            ("artifact", DataType.VARCHAR),
            ("table_id", DataType.VARCHAR),
            ("level", DataType.INT64),
            ("epoch", DataType.INT64),
            ("status", DataType.VARCHAR),
            ("detail", DataType.VARCHAR),
        ]
    ),
    "rw_recovery_events": Schema(
        [
            ("seq", DataType.INT64),
            ("ts_ms", DataType.INT64),
            ("mode", DataType.VARCHAR),
            ("epoch", DataType.INT64),
            ("detail", DataType.VARCHAR),
        ]
    ),
    # memory governor ledger: one row per accounted state table plus a
    # "_total" row carrying the global reconciliation (ledger vs
    # deviceprof-modeled vs sampled memory_stats) and the budget math
    "rw_memory": Schema(
        [
            ("table_id", DataType.VARCHAR),
            ("executor", DataType.VARCHAR),
            ("ledger_bytes", DataType.INT64),
            ("modeled_bytes", DataType.INT64),
            ("sampled_bytes", DataType.INT64),
            ("budget_bytes", DataType.INT64),
            ("headroom_bytes", DataType.INT64),
            ("high_water", DataType.INT64),
            ("pinned", DataType.INT64),
            ("vetoes", DataType.INT64),
        ]
    ),
    # overload ladder + admission credits: one row per fragment credit
    # window (or a single "-" row before any throttling), each carrying
    # the ladder's current rung, score, flap count and last transition
    "rw_overload_state": Schema(
        [
            ("fragment", DataType.VARCHAR),
            ("credit", DataType.FLOAT64),
            ("state", DataType.VARCHAR),
            ("score", DataType.FLOAT64),
            ("flaps", DataType.INT64),
            ("last_from", DataType.VARCHAR),
            ("last_to", DataType.VARCHAR),
            ("last_ts_ms", DataType.INT64),
            ("last_epoch", DataType.INT64),
        ]
    ),
    # mesh observability (ISSUE 18): one row per (sharded table, shard)
    # — key occupancy, rows routed in, state bytes and local-apply wall
    # from the last closed barrier window (MESHPROF.table_snapshot)
    "rw_shards": Schema(
        [
            ("table_id", DataType.VARCHAR),
            ("executor", DataType.VARCHAR),
            ("fragment", DataType.VARCHAR),
            ("shard", DataType.INT64),
            ("occupancy", DataType.INT64),
            ("rows_in", DataType.INT64),
            ("rows_in_total", DataType.INT64),
            ("state_bytes", DataType.INT64),
            ("local_ms", DataType.FLOAT64),
            ("skew_ratio", DataType.FLOAT64),
            ("is_hot", DataType.INT64),
        ]
    ),
    # exchange-cost matrix: one row per (src, dst) shard pair with
    # cumulative and last-barrier routed rows/bytes over all-to-all
    "rw_exchange": Schema(
        [
            ("src", DataType.INT64),
            ("dst", DataType.INT64),
            ("rows_total", DataType.INT64),
            ("bytes_total", DataType.INT64),
            ("rows_last", DataType.INT64),
            ("bytes_last", DataType.INT64),
        ]
    ),
}


# columns whose None is SQL NULL (the others keep their fill values)
_NULLABLE = {("rw_barrier_latency", "device_step_ms")}


class SysTable:
    """A read-only virtual relation over live introspection state.

    Quacks like a registered MV for the batch engine's scan path: the
    only method the engine calls on a ``P.TableRef`` target is
    ``to_numpy()``. A failing builder degrades to an empty relation —
    introspection never turns a SELECT into a 500."""

    def __init__(
        self, name: str, schema: Schema, rows: Callable, session
    ):
        self.name = name
        self.schema = schema
        self._rows = rows
        self._session = session

    def to_numpy(self) -> Dict[str, np.ndarray]:
        try:
            rows = self._rows(self._session)
        except Exception:  # noqa: BLE001 — introspection never faults
            rows = []
        enc = self._session.strings.encode_one
        out: Dict[str, np.ndarray] = {}
        for f in self.schema.fields:
            vals = [r.get(f.name) for r in rows]
            if f.dtype is DataType.VARCHAR:
                out[f.name] = np.asarray(
                    [enc("" if v is None else str(v)) for v in vals],
                    np.int32,
                )
            elif (self.name, f.name) in _NULLABLE and None in vals:
                # the engine's nullable-column convention for a scan's
                # input: an object lane with None cells
                out[f.name] = np.asarray(vals, object)
            elif f.dtype is DataType.FLOAT64:
                out[f.name] = np.asarray(
                    [float(v) if v is not None else -1.0 for v in vals],
                    np.float64,
                )
            else:
                out[f.name] = np.asarray(
                    [int(v) if v is not None else 0 for v in vals],
                    np.int64,
                )
        return out


# -- row builders (one per table) -------------------------------------------


def _fused_count(p) -> int:
    """Fused wrappers visible in a fragment: the in-place serial/two-
    input wrappers plus any inside a graph's actor chains."""
    n = 0
    if getattr(p, "_fused", None) is not None:
        n += 1
    for ex in getattr(p, "executors", ()) or ():
        if type(ex).__name__.startswith("Fused"):
            n += 1
    g = getattr(p, "graph", None)
    if g is not None:
        for a in getattr(g, "actors", ()) or ():
            for ex in getattr(a, "executors", ()) or ():
                if type(ex).__name__.startswith("Fused"):
                    n += 1
    return n


def _rows_fragments(session) -> List[dict]:
    rt = session.runtime
    rows = []
    for name in sorted(getattr(rt, "fragments", {})):
        p = rt.fragments[name]
        subs = [d for d, _s in getattr(rt, "_subs", {}).get(name, ())]
        rows.append(
            {
                "name": name,
                "kind": type(p).__name__,
                "executors": len(getattr(p, "executors", ()) or ()),
                "fused": 1 if _fused_count(p) else 0,
                "epoch": getattr(p, "_epoch", 0),
                "subscribers": ",".join(subs),
            }
        )
    return rows


def _rows_arrangements(session) -> List[dict]:
    reg = getattr(session.runtime, "arrangements", None)
    if reg is None:
        return []
    rows = []
    for arr in list(getattr(reg, "_live", ()) or ()):
        ver = getattr(arr, "version", None)
        rows.append(
            {
                "owner": getattr(arr, "owner", ""),
                "fragment": getattr(arr, "fragment", ""),
                "refs": len(getattr(arr, "refs", ()) or ()),
                "shared": int(
                    len(getattr(arr, "refs", ()) or ()) > 1
                    or getattr(arr, "hidden", False)
                ),
                "published_epoch": getattr(ver, "epoch", 0) or 0,
                "readers": ",".join(sorted(getattr(arr, "refs", ()) or ())),
            }
        )
    rows.sort(key=lambda r: r["owner"])
    return rows


def _rows_mv_freshness(session) -> List[dict]:
    from risingwave_tpu.freshness import FRESHNESS

    now = time.time()
    rows = []
    for r in FRESHNESS.snapshot():
        rows.append(
            {
                "mv": r["mv"],
                "epoch": r["epoch"],
                "checkpoint": int(r["checkpoint"]),
                "commit_to_visible_ms": r["commit_to_visible_ms"],
                "source_to_visible_ms": r["source_to_visible_ms"],
                "event_time_lag_ms": r["event_time_lag_ms"],
                # live staleness: how long ago this MV's snapshot became
                # visible — monotone between barriers, resets at publish
                "staleness_ms": round((now - r["visible_at"]) * 1e3, 3),
                "barriers": r["barriers"],
            }
        )
    return rows


def _rows_barrier_latency(session) -> List[dict]:
    from risingwave_tpu.trace import TRACER, barrier_path

    rt = session.runtime
    traces = list(getattr(rt, "epoch_traces", ()) or ())[-128:]
    spans = TRACER.spans()
    rows = []
    for tr in traces:
        st = getattr(tr, "stages_ms", {}) or {}
        path = barrier_path(getattr(tr, "epoch", 0), spans)
        rows.append(
            {
                "epoch": getattr(tr, "epoch", 0),
                "seq": getattr(tr, "seq", 0),
                "checkpoint": int(getattr(tr, "checkpoint", False)),
                "wall_ms": round(getattr(tr, "wall_ms", 0.0), 3),
                "dispatch_ms": round(st.get("dispatch", 0.0), 3),
                # what of the barrier its critical path spent blocked
                # on the device (its ``device.read`` spans along the
                # slowest actor); NULL once the span ring has let go of
                # the epoch
                "device_step_ms": (
                    None if path is None
                    else round(path["by_kind"]["device_wait"], 3)
                ),
                "backpressure_fragment": getattr(
                    tr, "backpressure_fragment", None
                )
                or "",
                "backpressure_ms": round(
                    getattr(tr, "backpressure_ms", 0.0), 3
                ),
            }
        )
    return rows


def _rows_channel_depths(session) -> List[dict]:
    rt = session.runtime
    rows = []
    for name in sorted(getattr(rt, "fragments", {})):
        g = getattr(rt.fragments[name], "graph", None)
        if g is None:
            continue
        for a in getattr(g, "actors", ()) or ():
            for i, (_port, ch) in enumerate(a.inputs):
                op = ch.oldest_pending()
                rows.append(
                    {
                        "fragment": name,
                        "actor": a.actor_name,
                        "channel": i,
                        "depth": len(ch),
                        "oldest_age_ms": (
                            round(op["age_ms"], 3) if op else None
                        ),
                        "oldest_epoch": op["epoch"] if op else None,
                    }
                )
    return rows


def _rows_fusion_status(session) -> List[dict]:
    rt = session.runtime
    rows = []
    for name in sorted(getattr(rt, "fragments", {})):
        p = rt.fragments[name]
        fused = _fused_count(p)
        rows.append(
            {
                "fragment": name,
                "kind": type(p).__name__,
                "fused": int(fused > 0),
                "fused_executors": fused,
                "executors": len(getattr(p, "executors", ()) or ()),
            }
        )
    return rows


def _rows_recovery_events(session) -> List[dict]:
    from risingwave_tpu.event_log import EVENT_LOG

    rows = []
    for e in EVENT_LOG.events(kind="recovery", limit=256):
        detail = ",".join(
            f"{k}={v}"
            for k, v in sorted(e.items())
            if k not in ("seq", "ts", "kind", "mode", "epoch")
        )
        rows.append(
            {
                "seq": e["seq"],
                "ts_ms": int(e["ts"] * 1000),
                "mode": e.get("mode", ""),
                "epoch": e.get("epoch"),
                "detail": detail,
            }
        )
    return rows


def _rows_integrity(session) -> List[dict]:
    mgr = getattr(session.runtime, "mgr", None)
    if mgr is None:
        return []
    return mgr.scrub()


def _rows_memory(session) -> List[dict]:
    gov = getattr(session.runtime, "memory_governor", None)
    if gov is None:
        return []
    snap = gov.snapshot()
    rows = []
    for t in gov.ledger_snapshot():
        rows.append(
            {
                "table_id": t["table_id"],
                "executor": t["executor"],
                "ledger_bytes": t["ledger_bytes"],
                "modeled_bytes": None,
                "sampled_bytes": None,
                "budget_bytes": None,
                "headroom_bytes": None,
                "high_water": t["high_water"],
                "pinned": int(t["pinned"]),
                "vetoes": t["vetoes"],
            }
        )
    rows.sort(key=lambda r: -r["ledger_bytes"])
    # per-shard breakdown (ISSUE 18): sharded tables get one sub-row
    # per shard after the table rows, keyed "<table_id>/shard<i>"
    shard_rows = []
    for t in gov.ledger_snapshot():
        for i, b in enumerate(t.get("shards") or ()):
            shard_rows.append(
                {
                    "table_id": f"{t['table_id']}/shard{i}",
                    "executor": t["executor"],
                    "ledger_bytes": b,
                    "modeled_bytes": None,
                    "sampled_bytes": None,
                    "budget_bytes": None,
                    "headroom_bytes": None,
                    "high_water": None,
                    "pinned": None,
                    "vetoes": None,
                }
            )
    rows.extend(shard_rows)
    rows.append(
        {
            "table_id": "_total",
            "executor": "-",
            "ledger_bytes": snap["ledger_bytes"],
            "modeled_bytes": snap["modeled_bytes"],
            "sampled_bytes": snap["sampled_bytes"],
            "budget_bytes": snap["budget_bytes"],
            "headroom_bytes": snap["headroom_bytes"],
            "high_water": None,
            "pinned": None,
            "vetoes": snap["vetoes"],
        }
    )
    return rows


def _rows_shards(session) -> List[dict]:
    from risingwave_tpu.parallel.meshprof import MESHPROF

    snap = MESHPROF.table_snapshot()
    last = snap.get("last_barrier") or {}
    skew = last.get("skew") or {}
    rows = []
    for tid, t in (snap.get("tables") or {}).items():
        n = int(t.get("n_shards") or 0)
        rin_last = t.get("rows_in_last") or []
        rin_tot = t.get("rows_in_total") or []
        occ = t.get("occupancy") or []
        sb = t.get("state_bytes_per_shard") or []
        loc = (last.get("shard_local_ms") or []) if last else []
        for i in range(n):
            hot = int(
                skew.get("table_id") == tid and skew.get("shard") == i
            )
            rows.append(
                {
                    "table_id": tid,
                    "executor": t.get("executor", ""),
                    "fragment": t.get("pipeline", ""),
                    "shard": i,
                    "occupancy": occ[i] if i < len(occ) else None,
                    "rows_in": rin_last[i] if i < len(rin_last) else 0,
                    "rows_in_total": (
                        rin_tot[i] if i < len(rin_tot) else 0
                    ),
                    "state_bytes": sb[i] if i < len(sb) else None,
                    "local_ms": loc[i] if i < len(loc) else None,
                    "skew_ratio": t.get("skew_ratio_last"),
                    "is_hot": hot,
                }
            )
    return rows


def _rows_exchange(session) -> List[dict]:
    from risingwave_tpu.parallel.meshprof import MESHPROF

    ex = MESHPROF.table_snapshot().get("exchange") or {}
    rows_m = ex.get("rows") or []
    bytes_m = ex.get("bytes") or []
    rows_l = ex.get("rows_last") or []
    bytes_l = ex.get("bytes_last") or []

    def _cell(m, i, j):
        try:
            return int(m[i][j])
        except (IndexError, TypeError):
            return 0

    out = []
    for i, row in enumerate(rows_m):
        for j in range(len(row)):
            out.append(
                {
                    "src": i,
                    "dst": j,
                    "rows_total": _cell(rows_m, i, j),
                    "bytes_total": _cell(bytes_m, i, j),
                    "rows_last": _cell(rows_l, i, j),
                    "bytes_last": _cell(bytes_l, i, j),
                }
            )
    return out


def _rows_overload_state(session) -> List[dict]:
    gov = getattr(session.runtime, "memory_governor", None)
    if gov is None:
        return []
    lad = gov.ladder.snapshot()
    last = (lad["transitions"] or [{}])[-1]
    base = {
        "state": lad["state"],
        "score": lad["score"],
        "flaps": lad["flaps"],
        "last_from": last.get("from", ""),
        "last_to": last.get("to", ""),
        "last_ts_ms": (
            int(last["ts"] * 1000) if last.get("ts") is not None else None
        ),
        "last_epoch": last.get("epoch"),
    }
    credits = gov.admission.credits
    if not credits:
        return [dict(base, fragment="-", credit=1.0)]
    return [
        dict(base, fragment=frag, credit=c)
        for frag, c in sorted(credits.items())
    ]


_BUILDERS: Dict[str, Callable] = {
    "rw_fragments": _rows_fragments,
    "rw_arrangements": _rows_arrangements,
    "rw_mv_freshness": _rows_mv_freshness,
    "rw_barrier_latency": _rows_barrier_latency,
    "rw_channel_depths": _rows_channel_depths,
    "rw_fusion_status": _rows_fusion_status,
    "rw_integrity": _rows_integrity,
    "rw_recovery_events": _rows_recovery_events,
    "rw_memory": _rows_memory,
    "rw_overload_state": _rows_overload_state,
    "rw_shards": _rows_shards,
    "rw_exchange": _rows_exchange,
}


def install_sys_tables(session) -> None:
    """Register every ``rw_`` relation into the session's catalog +
    batch engine (idempotent; called from SqlSession.__init__ under
    ``_registry_guard``). The catalog entry makes typecheck_select see
    them; the batch entry makes the scan path find them; the
    ``_execute_shared_read`` branch serves them without the session
    lock."""
    for name, schema in SYS_SCHEMAS.items():
        session.catalog.tables[name] = schema
        session.batch.register(
            name, SysTable(name, schema, _BUILDERS[name], session)
        )
