"""Mesh observability — per-shard barrier attribution, exchange-cost
matrix, and hot-shard skew verdicts for the multi-chip path (ISSUE 18).

ROADMAP item 3 (mesh scale-out, exchange as on-device collectives) needs
a measured baseline before the exchange moves into the fused program —
the same play PR 6 ran on the serial path, where the profiler pinned the
319 ms dispatch wall before PR 10 killed it. This module is that
profiler for the sharded graph:

1. **Per-shard barrier attribution.** ``watch(pipeline)`` wraps the
   *instances* of the sharded executors (``ShardedHashAgg``,
   ``ShardedDedup``, ``ShardedHashJoin``, ``ShardedMaterialize``,
   ``ShardedGroupTopN``) plus the host boundary lanes
   (``StackSplitExecutor`` -> ``host_split``, ``FlattenExecutor`` ->
   ``host_flatten``, everything else in the chain -> ``host_other``).
   Each wrapped call is fenced on the executor's small status leaf
   (``dropped``/``flags``/``_em_overflow``) so its wall is a real
   device-inclusive measurement, and a barrier window's attributed
   time is the sum of those walls. Instance wrapping (not class
   wrapping) keeps a serial twin pipeline in the same process
   completely unperturbed — the bit-identity contract.

2. **Exchange-cost matrix.** ``pack_buckets`` already computes every
   shard's per-destination routed-row histogram (it feeds the overflow
   flag), so the sharded executors thread it out of their existing
   jitted step as one tiny extra output (``ex_counts_last``, a stacked
   ``(n_shards, n_shards)`` int32 — row = source shard). The wrapped
   apply just keeps a reference; the window close reads the tiny
   arrays (the barrier already drained the queue), sums them into the
   per-barrier (src, dst) delta, and feeds
   ``exchange_rows_total{src,dst}`` / ``exchange_bytes_total{src,dst}``
   plus the per-barrier traffic matrix on the trace. No second hash
   pass, no extra program on the apply path — armed and unarmed runs
   execute the byte-identical step. Barrier-flush re-exchange traffic
   (agg flush rounds) is NOT counted — the matrix measures
   input-driven exchange, the part the future collective fusion
   ratchets against.

3. **pack/route/unpack phase split.** Per (executor, chunk-cap) the
   close calibrates three one-shot probe programs built from the real
   ``exchange.py`` internals (pack only / pack+route / full exchange,
   outputs kept live through cheap reductions so XLA cannot DCE them),
   takes the min of ``PROBE_REPS`` post-compile runs, and scales by the
   window's apply count; shard-local time is the clamped residual.
   Probes are a one-time cost (``calibration_ms``), never on the steady
   path, and can be disabled (``enable(probes=False)``).

4. **Hot-shard skew verdict.** Per close, each executor's rows-in
   vector (delta-matrix column sums) is tested: max/mean >=
   ``RW_SKEW_RATIO`` with at least ``RW_SKEW_MIN_ROWS`` routed rows
   folds — like PR 16's ``backpressure_fragment`` — into ONE
   ``skew_shard`` verdict per barrier (worst executor wins), a
   ``shard_skew_frac`` gauge, and at most one structured ``skew``
   event per close.

The pipeline hooks (``GraphPipeline.wait_barrier``,
``Pipeline.barrier``, ``TwoInputPipeline.barrier``) call
``pipeline_barrier(pipeline)`` to close the window;
``StreamingRuntime._end_trace`` drains pending windows onto
``EpochTrace.mesh`` (and into ``barrier_stage_ms`` as ``mesh_*`` /
per-shard stages, so the existing dashboards, blackbox ring and
Perfetto lanes pick the sections up). ``rw_shards`` / ``rw_exchange``
system tables read ``table_snapshot()`` — lock-copied host dicts,
never a device sync. Unarmed (the default), nothing is wrapped and the
hot path is untouched.
"""

from __future__ import annotations

import os
import threading
import time
import weakref
from collections import deque
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from risingwave_tpu.event_log import EVENT_LOG
from risingwave_tpu.executors.hash_agg import _build_key_lanes
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.parallel.exchange import (
    dest_shard,
    exchange_chunk,
    exchange_cols,
    pack_buckets,
)

SKEW_RATIO = float(os.environ.get("RW_SKEW_RATIO", "2.0"))
SKEW_MIN_ROWS = int(os.environ.get("RW_SKEW_MIN_ROWS", "64"))
PROBE_REPS = 2  # post-compile timing runs per probe; min is kept

# executor class name -> lane kind. Exchange kinds carry routed-row
# counts + probes; host kinds only contribute wall to the phase split.
_EXCHANGE_KINDS = {
    "ShardedHashAgg": "agg",
    "ShardedDedup": "dedup",
    "ShardedHashJoin": "join",
    "ShardedMaterialize": "mv",
    "ShardedGroupTopN": "top_n",
}
_HOST_KINDS = {
    "StackSplitExecutor": "host_split",
    "FlattenExecutor": "host_flatten",
}

# the small always-present status leaf each sharded class updates every
# apply — blocking on it fences the whole step without touching state
_FENCES = {
    "agg": lambda ex: ex.dropped,
    "dedup": lambda ex: ex.flags,
    "join": lambda ex: ex._em_overflow,
    "mv": lambda ex: ex.state.dropped,
    "top_n": lambda ex: ex.dropped,
}

_PHASES = (
    "pack",
    "route",
    "unpack",
    "shard_local",
    "host_split",
    "host_flatten",
    "host_other",
)


def _key_fn_for(ex, kind: str, arrival: Optional[str]):
    """The exchange-key builder matching what the executor's own
    ``_build_step`` routes on. Captures only immutable tuples — never
    the executor itself (the profiler must not keep dead executors
    alive after kill+recover)."""
    if kind == "agg":
        gk, nb = ex.group_keys, ex.nullable
        return lambda c: _build_key_lanes(c, gk, nb)
    if kind == "dedup":
        ks = ex.keys
    elif kind == "join":
        ks = ex.left_keys if arrival == "l" else ex.right_keys
    elif kind == "mv":
        ks = ex.pk
    else:  # top_n
        ks = ex.group_by
    return lambda c: tuple(c.col(k) for k in ks)


def _build_probe(mesh, axis: str, n_shards: int, bucket_cap: int, key_fn,
                 stage: str):
    """One phase-probe program: the real exchange pipeline cut after
    ``stage`` ("pack" | "route" | "full"), with every produced buffer
    reduced into a scalar so XLA keeps the full work live."""

    def local(chunk):
        c = jax.tree.map(lambda a: a[0], chunk)
        lanes = key_fn(c)
        if stage == "full":
            rc, ovf, _cts = exchange_chunk(c, lanes, n_shards, bucket_cap, axis)
            acc = jnp.sum(rc.valid.astype(jnp.int32)) + ovf.astype(jnp.int32)
            for col in rc.columns.values():
                acc = acc + jnp.sum((col != 0).astype(jnp.int32))
            return acc[None]
        dest = dest_shard(lanes, n_shards)
        bufs, vbuf, ovf, _ = pack_buckets(
            exchange_cols(c), c.valid, dest, n_shards, bucket_cap
        )
        if stage == "route":
            bufs = {
                nm: jax.lax.all_to_all(b, axis, 0, 0, tiled=False)
                for nm, b in bufs.items()
            }
            vbuf = jax.lax.all_to_all(vbuf, axis, 0, 0, tiled=False)
        acc = jnp.sum(vbuf.astype(jnp.int32)) + ovf.astype(jnp.int32)
        for b in bufs.values():
            acc = acc + jnp.sum((b != 0).astype(jnp.int32))
        return acc[None]

    spec = P(axis)
    return jax.jit(
        jax.shard_map(
            local, mesh=mesh, in_specs=(spec,), out_specs=spec,
            check_vma=False,
        )
    )


def _chunk_row_bytes(chunk) -> int:
    """Bytes one exchanged row carries: every column + the ops lane +
    null lanes + the valid bit — the lanes ``exchange_chunk`` actually
    ships through all_to_all."""
    total = chunk.ops.dtype.itemsize + chunk.valid.dtype.itemsize
    for col in chunk.columns.values():
        total += col.dtype.itemsize
    for lane in chunk.nulls.values():
        total += lane.dtype.itemsize
    return int(total)


class _ExecInfo:
    """Per watched executor: weakly referenced (kill+recover must not
    leave orphaned lanes), with the probe caches living here so they
    die with the watch, not with the class."""

    __slots__ = (
        "ref", "kind", "lane", "table_id", "owner", "pipe_name",
        "n_shards", "wrapped", "probe_ms", "templates", "bytes_per_row",
        "occ_cache", "occ_age",
    )

    def __init__(self, ex, kind: str, lane: str, owner: int,
                 pipe_name: str):
        self.ref = weakref.ref(ex)
        self.kind = kind
        self.lane = lane
        self.table_id = getattr(ex, "table_id", type(ex).__name__)
        self.owner = owner
        self.pipe_name = pipe_name
        self.n_shards = int(getattr(ex, "n_shards", 0) or 0)
        self.wrapped: List[str] = []
        self.probe_ms: Dict[Any, tuple] = {}
        self.templates: Dict[Any, Any] = {}
        self.bytes_per_row: Dict[Any, int] = {}
        self.occ_cache = None  # last shard_occupancy read (host int64)
        self.occ_age = 0  # closes since that read


class MeshProfiler:
    """Process singleton (``MESHPROF``). Thread-safe: the sharded graph
    runs executors on FragmentActor threads while the driver closes
    windows from ``wait_barrier``."""

    def __init__(self):
        self._lock = threading.RLock()
        self.enabled = False
        self.probes_enabled = True
        self.calibration_ms = 0.0  # one-time probe compiles/timing
        self.errors = 0
        self.barrier_count = 0
        self.barriers: deque = deque(maxlen=64)  # mesh docs, newest last
        self._pending: deque = deque(maxlen=16)  # awaiting runtime drain
        self._execs: Dict[int, _ExecInfo] = {}  # id(ex) -> info
        self._window: Dict[int, dict] = {}  # id(info) -> open entry
        self._tables: Dict[str, dict] = {}  # table_id -> host snapshot
        self._ex_n = 0
        self._ex_rows = None  # cumulative np (n, n) rows
        self._ex_bytes = None
        self._ex_rows_last = None  # last barrier's delta
        self._ex_bytes_last = None

    # -- arming -----------------------------------------------------------
    def enable(self, probes: bool = True) -> None:
        with self._lock:
            self.enabled = True
            self.probes_enabled = bool(probes)

    def disable(self) -> None:
        with self._lock:
            self.enabled = False
            for info in self._execs.values():
                ex = info.ref()
                if ex is None:
                    continue
                for m in info.wrapped:
                    ex.__dict__.pop(m, None)
            self._execs.clear()
            self._window.clear()
            self._pending.clear()

    def reset_stats(self) -> None:
        """Zero the meters (gates measure deltas across a run)."""
        with self._lock:
            self.calibration_ms = 0.0
            self.errors = 0
            self.barrier_count = 0
            self.barriers.clear()
            self._pending.clear()
            self._tables.clear()
            self._ex_n = 0
            self._ex_rows = self._ex_bytes = None
            self._ex_rows_last = self._ex_bytes_last = None

    def watch(self, pipeline, name: str = "pipeline") -> int:
        """Instance-wrap a pipeline's sharded chain. No-op unless armed
        and the chain actually contains a sharded executor (a serial
        pipeline in the same process stays untouched). Returns the
        number of executors newly wrapped."""
        if not self.enabled:
            return 0
        exs = getattr(pipeline, "executors", None)
        if callable(exs):
            exs = exs()
        exs = list(exs or ())
        if not any(type(e).__name__ in _EXCHANGE_KINDS for e in exs):
            return 0
        n = 0
        with self._lock:
            for ex in exs:
                if id(ex) in self._execs:
                    continue
                cls = type(ex).__name__
                if cls in _EXCHANGE_KINDS:
                    kind, lane = _EXCHANGE_KINDS[cls], "exec"
                elif cls in _HOST_KINDS:
                    kind = lane = _HOST_KINDS[cls]
                else:
                    kind = lane = "host_other"
                try:
                    info = _ExecInfo(ex, kind, lane, id(pipeline), name)
                except TypeError:
                    continue  # not weakref-able: skip, never fault
                if kind in _EXCHANGE_KINDS.values():
                    if kind == "join":
                        self._wrap(info, ex, "apply_left", True, "l")
                        self._wrap(info, ex, "apply_right", True, "r")
                    else:
                        self._wrap(info, ex, "apply", True, None)
                    if hasattr(ex, "on_barrier"):
                        self._wrap(info, ex, "on_barrier", False, None)
                else:
                    self._wrap(info, ex, "apply", False, None)
                self._execs[id(ex)] = info
                n += 1
        return n

    def _wrap(self, info: _ExecInfo, ex, method: str, count: bool,
              arrival: Optional[str]) -> None:
        orig = getattr(ex, method)
        fence = _FENCES.get(info.kind)
        exref = info.ref
        prof = self

        def wrapped(*a, **kw):
            if not prof.enabled:
                return orig(*a, **kw)
            t0 = time.perf_counter()
            ret = orig(*a, **kw)
            try:
                tgt = fence(exref()) if fence is not None else ret
                if tgt is not None:
                    jax.block_until_ready(tgt)
            except Exception:
                pass  # fencing is best-effort; never fault the step
            t1 = time.perf_counter()
            try:
                chunk = a[0] if (count and a) else None
                prof._record(info, t0, t1, chunk, arrival)
            except Exception:
                prof.errors += 1
            return ret

        setattr(ex, method, wrapped)
        info.wrapped.append(method)

    # -- the hot path -----------------------------------------------------
    def _record(self, info: _ExecInfo, t0: float, t1: float, chunk,
                arrival: Optional[str]) -> None:
        with self._lock:
            entry = self._window.get(id(info))
            if entry is None:
                entry = self._window[id(info)] = {
                    "info": info,
                    "t_first": t0,
                    "wall_ms": 0.0,
                    "applies": {},
                    "counts": [],
                }
            entry["t_first"] = min(entry["t_first"], t0)
            entry["wall_ms"] += (t1 - t0) * 1e3
            if chunk is None or getattr(chunk.valid, "ndim", 1) != 2:
                return
            ex = info.ref()
            if ex is None:
                return
            cap = int(chunk.valid.shape[-1])
            capkey = (cap, arrival)
            entry["applies"][capkey] = entry["applies"].get(capkey, 0) + 1
            # the executor's own jitted step already computed this
            # apply's (src, dst) routed-row histogram (pack_buckets
            # feeds it into overflow detection) and threads it out as
            # ``ex_counts_last`` — keep the tiny device ref; the close
            # reads it after the barrier drained the queue. Zero extra
            # programs on the apply path.
            cts = getattr(ex, "ex_counts_last", None)
            if cts is not None:
                entry["counts"].append(cts)
            if capkey not in info.bytes_per_row:
                info.bytes_per_row[capkey] = _chunk_row_bytes(chunk)
            if (
                self.probes_enabled
                and capkey not in info.probe_ms
                and capkey not in info.templates
            ):
                info.templates[capkey] = chunk  # probe calibration input

    # -- window close -----------------------------------------------------
    def pipeline_barrier(self, pipeline) -> Optional[dict]:
        """Close this pipeline's window: read the tiny per-apply count
        outputs, phase split, skew verdict, counters, trace doc.
        Called from the pipeline's barrier (driver thread, actors
        idle). Never faults the barrier."""
        if not self.enabled:
            return None
        with self._lock:
            picked = [
                self._window.pop(k)
                for k in [
                    k
                    for k, e in self._window.items()
                    if e["info"].owner == id(pipeline)
                ]
            ]
        if not picked:
            return None
        doc = None
        cal_ms = 0.0
        try:
            for e in picked:
                cal_ms += self._calibrate(e)
            doc = self._close(picked)
        except Exception:
            self.errors += 1
        if doc is not None:
            with self._lock:
                self.barrier_count += 1
                self.barriers.append(doc)
                self._pending.append(doc)
        self.calibration_ms += cal_ms
        return doc

    def _calibrate(self, entry: dict) -> float:
        """One-time pack/route/unpack probe timing for any (cap,
        arrival) this window exercised and has a template for. Returns
        the wall spent calibrating (booked to ``calibration_ms``)."""
        info = entry["info"]
        if not self.probes_enabled or not info.templates:
            return 0.0
        ex = info.ref()
        if ex is None:
            info.templates.clear()
            return 0.0
        c0 = time.perf_counter()
        for capkey in list(entry["applies"]):
            if capkey in info.probe_ms:
                info.templates.pop(capkey, None)
                continue
            tmpl = info.templates.pop(capkey, None)
            if tmpl is None:
                continue
            cap, arrival = capkey
            bucket_cap = getattr(ex, "bucket_cap", None) or max(
                64, (2 * cap) // info.n_shards
            )
            key_fn = _key_fn_for(ex, info.kind, arrival)
            stages = {}
            try:
                for stage in ("pack", "route", "full"):
                    fn = _build_probe(
                        ex.mesh, ex.axis, info.n_shards, bucket_cap,
                        key_fn, stage,
                    )
                    jax.block_until_ready(fn(tmpl))  # compile + warm
                    best = float("inf")
                    for _ in range(PROBE_REPS):
                        p0 = time.perf_counter()
                        jax.block_until_ready(fn(tmpl))
                        best = min(best, time.perf_counter() - p0)
                    stages[stage] = best * 1e3
            except Exception:
                self.errors += 1
                continue
            pack = stages["pack"]
            route = max(0.0, stages["route"] - stages["pack"])
            unpack = max(0.0, stages["full"] - stages["route"])
            info.probe_ms[capkey] = (pack, route, unpack)
        return (time.perf_counter() - c0) * 1e3

    def _close(self, picked: List[dict]) -> dict:
        t_close = time.perf_counter()
        infos = [e["info"] for e in picked]
        n = max([i.n_shards for i in infos if i.n_shards] or [0])
        wall_ms = (t_close - min(e["t_first"] for e in picked)) * 1e3
        attributed = sum(e["wall_ms"] for e in picked)
        wall_ms = max(wall_ms, attributed)
        phases = {p: 0.0 for p in _PHASES}
        shard_local = np.zeros(max(n, 1))
        rows_in = np.zeros(max(n, 1), np.int64)
        occupancy = np.zeros(max(n, 1), np.int64)
        state_bytes = np.zeros(max(n, 1), np.int64)
        ex_rows = np.zeros((max(n, 1), max(n, 1)), np.int64)
        ex_bytes = np.zeros((max(n, 1), max(n, 1)), np.int64)
        best_skew = None
        c_rows = REGISTRY.counter("exchange_rows_total")
        c_bytes = REGISTRY.counter("exchange_bytes_total")

        for e in picked:
            info = e["info"]
            if info.lane != "exec":
                phases[info.lane] += e["wall_ms"]
                continue
            ex = info.ref()
            # phase split from calibrated probes, scaled by applies
            pack = route = unpack = 0.0
            for capkey, n_app in e["applies"].items():
                p = info.probe_ms.get(capkey)
                if p:
                    pack += p[0] * n_app
                    route += p[1] * n_app
                    unpack += p[2] * n_app
            probe_total = pack + route + unpack
            if probe_total > 0.9 * e["wall_ms"] and probe_total > 0:
                s = 0.9 * e["wall_ms"] / probe_total
                pack, route, unpack = pack * s, route * s, unpack * s
            local = max(0.0, e["wall_ms"] - (pack + route + unpack))
            phases["pack"] += pack
            phases["route"] += route
            phases["unpack"] += unpack
            phases["shard_local"] += local

            # sum this window's per-apply count outputs (tiny (n, n)
            # device arrays the executor's own step produced; the
            # barrier already drained the queue so each read is a
            # 256-byte transfer, not a wait)
            delta = np.zeros((max(n, 1), max(n, 1)), np.int64)
            for cts in e.get("counts", ()):
                try:
                    c = np.asarray(cts, np.int64)
                    if c.shape == delta.shape:
                        delta += c
                except Exception:
                    self.errors += 1
            e["counts"] = ()
            bpr = (
                int(np.mean(list(info.bytes_per_row.values())))
                if info.bytes_per_row
                else 0
            )
            dbytes = delta * bpr
            ex_rows += delta
            ex_bytes += dbytes
            for i, j in zip(*np.nonzero(delta)):
                c_rows.inc(int(delta[i, j]), src=str(int(i)),
                           dst=str(int(j)))
                c_bytes.inc(int(dbytes[i, j]), src=str(int(i)),
                            dst=str(int(j)))

            rin = delta.sum(axis=0)  # rows each dst shard received
            rows_in += rin
            tot = int(rin.sum())
            if tot > 0:
                shard_local += local * (rin / tot)
            elif n:
                shard_local += local / n

            occ = None
            if ex is not None and hasattr(ex, "shard_occupancy"):
                # occupancy drifts slowly but each read is an eager
                # device reduction + sync (~2.5ms on the 8-way CPU
                # sim): refresh every 4th close, reuse in between
                info.occ_age += 1
                if info.occ_cache is None or info.occ_age >= 4:
                    try:
                        fresh = np.asarray(ex.shard_occupancy(), np.int64)
                        info.occ_cache, info.occ_age = fresh, 0
                    except Exception:
                        pass
                occ = info.occ_cache
                if occ is not None and occ.shape[0] == n:
                    occupancy = np.maximum(occupancy, occ)
                else:
                    occ = None
            sb = None
            if ex is not None and hasattr(ex, "state_nbytes_per_shard"):
                try:
                    sb = np.asarray(ex.state_nbytes_per_shard(), np.int64)
                    if sb.shape[0] == n:
                        state_bytes += sb
                except Exception:
                    sb = None

            ratio = 0.0
            if tot >= SKEW_MIN_ROWS and n > 1:
                ratio = float(rin.max() / (tot / n))
                if ratio >= SKEW_RATIO and (
                    best_skew is None or ratio > best_skew["ratio"]
                ):
                    best_skew = {
                        "shard": int(rin.argmax()),
                        "ratio": round(ratio, 3),
                        "frac": round(float(rin.max() / tot), 4),
                        "table_id": info.table_id,
                        "rows": tot,
                    }

            with self._lock:
                t = self._tables.setdefault(
                    info.table_id, {"rows_in_total": [0] * max(n, 1),
                                    "barriers": 0}
                )
                prev_tot = np.asarray(t["rows_in_total"], np.int64)
                if prev_tot.shape[0] != max(n, 1):
                    prev_tot = np.zeros(max(n, 1), np.int64)
                t.update(
                    executor=(
                        type(ex).__name__ if ex is not None else "dead"
                    ),
                    pipeline=info.pipe_name,
                    n_shards=n,
                    rows_in_last=[int(v) for v in rin],
                    rows_in_total=[int(v) for v in prev_tot + rin],
                    occupancy=(
                        [int(v) for v in occ] if occ is not None else None
                    ),
                    state_bytes_per_shard=(
                        [int(v) for v in sb] if sb is not None else None
                    ),
                    local_ms_last=round(local, 3),
                    skew_ratio_last=round(ratio, 3),
                    barriers=t["barriers"] + 1,
                )

        coverage = attributed / wall_ms if wall_ms > 0 else 1.0
        with self._lock:
            if self._ex_rows is None or self._ex_n != n:
                self._ex_n = n
                self._ex_rows = np.zeros((max(n, 1), max(n, 1)), np.int64)
                self._ex_bytes = np.zeros((max(n, 1), max(n, 1)), np.int64)
            self._ex_rows += ex_rows
            self._ex_bytes += ex_bytes
            self._ex_rows_last = ex_rows
            self._ex_bytes_last = ex_bytes

        g = REGISTRY.gauge("shard_skew_frac")
        g.set(best_skew["frac"] if best_skew else 0.0)
        REGISTRY.gauge("mesh_coverage_frac").set(round(coverage, 4))
        REGISTRY.counter("mesh_barriers_total").inc()
        if best_skew:
            REGISTRY.counter("skew_verdicts_total").inc(
                shard=str(best_skew["shard"])
            )
            EVENT_LOG.record(
                "skew",
                table_id=best_skew["table_id"],
                shard=best_skew["shard"],
                ratio=best_skew["ratio"],
                frac=best_skew["frac"],
                rows=best_skew["rows"],
            )
        return {
            "n_shards": n,
            "wall_ms": round(wall_ms, 3),
            "attributed_ms": round(attributed, 3),
            "coverage_frac": round(coverage, 4),
            "phases_ms": {p: round(v, 3) for p, v in phases.items()},
            "shard_local_ms": [round(float(v), 3) for v in shard_local],
            "rows_in": [int(v) for v in rows_in],
            "occupancy": [int(v) for v in occupancy],
            "state_bytes": [int(v) for v in state_bytes],
            "exchange": {
                "rows": ex_rows.tolist(),
                "bytes": ex_bytes.tolist(),
            },
            "skew": best_skew,
        }

    # -- trace feed -------------------------------------------------------
    def observe_barrier(self, runtime, tr) -> None:
        """Runtime barrier hook: drain pending window docs (one per
        sharded pipeline that closed since the last trace) into ONE
        ``tr.mesh`` block + ``barrier_stage_ms`` mesh/per-shard stages.
        Mirrors MemoryGovernor.observe_barrier: enabled-gated,
        exception-proof."""
        if not self.enabled:
            return
        try:
            with self._lock:
                pend = list(self._pending)
                self._pending.clear()
            if not pend:
                return
            tr.mesh = self.fold(pend)
            for ph, ms in tr.mesh["phases_ms"].items():
                if ms > 0:
                    tr.add_stage(f"mesh_{ph}", ms)
            for i, ms in enumerate(tr.mesh["shard_local_ms"]):
                if ms > 0:
                    tr.add_stage("shard_local", ms, fragment=f"shard{i}")
        except Exception:
            self.errors += 1

    @staticmethod
    def fold(docs: List[dict]) -> dict:
        """Fold several per-pipeline window docs into one barrier doc:
        walls/phases/matrices sum, occupancy takes the max level, the
        worst skew verdict wins."""
        if len(docs) == 1:
            return docs[0]
        n = max(d["n_shards"] for d in docs)

        def vec(key):
            out = np.zeros(max(n, 1))
            for d in docs:
                v = np.asarray(d[key], float)
                out[: v.shape[0]] += v
            return out

        ex_rows = np.zeros((max(n, 1), max(n, 1)), np.int64)
        ex_bytes = np.zeros((max(n, 1), max(n, 1)), np.int64)
        occ = np.zeros(max(n, 1), np.int64)
        for d in docs:
            m = np.asarray(d["exchange"]["rows"], np.int64)
            ex_rows[: m.shape[0], : m.shape[1]] += m
            m = np.asarray(d["exchange"]["bytes"], np.int64)
            ex_bytes[: m.shape[0], : m.shape[1]] += m
            o = np.asarray(d["occupancy"], np.int64)
            occ[: o.shape[0]] = np.maximum(occ[: o.shape[0]], o)
        wall = sum(d["wall_ms"] for d in docs)
        att = sum(d["attributed_ms"] for d in docs)
        skews = [d["skew"] for d in docs if d["skew"]]
        return {
            "n_shards": n,
            "wall_ms": round(wall, 3),
            "attributed_ms": round(att, 3),
            "coverage_frac": round(att / wall, 4) if wall > 0 else 1.0,
            "phases_ms": {
                p: round(sum(d["phases_ms"].get(p, 0.0) for d in docs), 3)
                for p in _PHASES
            },
            "shard_local_ms": [
                round(float(v), 3) for v in vec("shard_local_ms")
            ],
            "rows_in": [int(v) for v in vec("rows_in")],
            "occupancy": [int(v) for v in occ],
            "state_bytes": [int(v) for v in vec("state_bytes")],
            "exchange": {
                "rows": ex_rows.tolist(),
                "bytes": ex_bytes.tolist(),
            },
            "skew": (
                max(skews, key=lambda s: s["ratio"]) if skews else None
            ),
        }

    # -- read surfaces ----------------------------------------------------
    def orphans(self) -> List[str]:
        """Window/watch entries whose executor died without a close
        (the PR 5/6/8 orphan-audit surface) — returned, then pruned.
        A clean kill+recover leaves this empty."""
        with self._lock:
            stale = sorted(
                {
                    e["info"].table_id
                    for e in self._window.values()
                    if e["info"].ref() is None
                }
            )
            self._window = {
                k: e
                for k, e in self._window.items()
                if e["info"].ref() is not None
            }
            self._execs = {
                k: i for k, i in self._execs.items() if i.ref() is not None
            }
        return stale

    def table_snapshot(self) -> dict:
        """Lock-copied host dicts for rw_shards / rw_exchange and the
        stall dump — never a device sync, safe from any thread."""
        with self._lock:
            tables = {k: dict(v) for k, v in sorted(self._tables.items())}
            ex = {
                "n_shards": self._ex_n,
                "rows": (
                    self._ex_rows.tolist()
                    if self._ex_rows is not None
                    else []
                ),
                "bytes": (
                    self._ex_bytes.tolist()
                    if self._ex_bytes is not None
                    else []
                ),
                "rows_last": (
                    self._ex_rows_last.tolist()
                    if self._ex_rows_last is not None
                    else []
                ),
                "bytes_last": (
                    self._ex_bytes_last.tolist()
                    if self._ex_bytes_last is not None
                    else []
                ),
            }
            last = self.barriers[-1] if self.barriers else None
            return {
                "enabled": self.enabled,
                "tables": tables,
                "exchange": ex,
                "last_barrier": last,
                "barriers": self.barrier_count,
                "calibration_ms": round(self.calibration_ms, 3),
                "errors": self.errors,
            }


MESHPROF = MeshProfiler()
