"""Barrier-lifecycle observability: per-epoch stage attribution, device
telemetry (measured roofline fraction), and await-tree-style stall
dumps.

Reference: the reference threads ``tracing`` spans through every actor,
dumps await trees on stall (src/utils/runtime/), and attributes barrier
latency per stage in its grafana dashboards. Here every barrier gets an
``EpochTrace``, and it is fed from ``trace.span``: a span opened with
``stage=`` reports its duration to the stage sink bound on its thread
(``trace.bind``) — the epoch's EpochTrace on the barrier's thread and on
the checkpoint worker, a ``StageSums`` on a pushing or an actor thread,
folded into the EpochTrace when the barrier comes. ``add_stage`` mirrors
every stamp into the ``barrier_stage_ms{stage,fragment}`` histogram
(prometheus). From the stages and the state's size it derives
per-barrier HBM telemetry: bytes touched = device-state delta
(utils_heap accounting) + chunk bytes moved, reported as achieved
bandwidth vs the configured chip peak so every bench JSON carries a
MEASURED roofline fraction.

``dump_stalls()`` is the q7-wedge forensic path: when a barrier exceeds
its deadline, snapshot every thread's open span stack, each actor's
input-channel depths and last-collected epoch, and the pending epochs,
to a JSON artifact BEFORE recovery tears the evidence down.
"""

from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Optional

from risingwave_tpu.metrics import REGISTRY

# Device peaks keyed by jax's ``device_kind``, each with its source. The
# roofline fraction is only as honest as this denominator, so a kind
# that is not listed is an error, never a default (RW_HBM_PEAK_GBPS
# names the peak of a chip the table does not know yet).
DEVICE_PEAKS = {
    "TPU v5 lite": {
        "hbm_gbps": 819.0,
        "hbm_gb": 16,
        "source": 'Google Cloud documentation, "TPU v5e"',
    },
    "cpu": {
        "hbm_gbps": 50.0,
        "hbm_gb": None,
        "source": "nominal host DRAM figure so CPU tests can exercise "
        "the accounting; not a device peak",
    },
}


def hbm_peak_gbps(device_kind: Optional[str] = None) -> float:
    env = os.environ.get("RW_HBM_PEAK_GBPS")
    if env:
        return float(env)
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]["hbm_gbps"]
    except KeyError:
        raise KeyError(
            f"no HBM peak known for device_kind {device_kind!r}: add it "
            "to epoch_trace.DEVICE_PEAKS with its source, or set "
            "RW_HBM_PEAK_GBPS"
        ) from None


def roofline(bytes_touched: int, seconds: float, device_kind=None) -> Dict:
    """Measured achieved-bandwidth vs chip peak. ``bytes_touched`` is
    the accounted HBM traffic (state delta + chunks moved); ``seconds``
    the wall time it moved in."""
    peak = hbm_peak_gbps(device_kind)
    bw = (bytes_touched / seconds / 1e9) if seconds > 0 else 0.0
    return {
        "hbm_bytes_touched": int(bytes_touched),
        "achieved_bw_gbps": round(bw, 4),
        "hbm_peak_gbps": peak,
        "achieved_bw_frac": round(bw / peak, 6) if peak else 0.0,
    }


def chunk_nbytes(chunk) -> int:
    """Device bytes one StreamChunk occupies (column lanes + null lanes
    + valid + ops) — the per-push half of 'HBM bytes touched'."""
    total = 0
    for attr in ("columns", "nulls"):
        for arr in getattr(chunk, attr, {}).values():
            total += int(getattr(arr, "nbytes", 0))
    for attr in ("valid", "ops"):
        arr = getattr(chunk, attr, None)
        if arr is not None:
            total += int(getattr(arr, "nbytes", 0))
    return total


def record_stage(stage: str, ms: float, fragment: str = "-") -> None:
    """One stage observation -> the prometheus surface. Every label set
    keeps the same keys (stage, fragment) so exposition stays uniform."""
    REGISTRY.histogram("barrier_stage_ms").observe(
        ms, stage=stage, fragment=fragment
    )


class StageSums:
    """Stage sink of a thread whose epoch has no EpochTrace yet (pushes
    before their barrier) or lives elsewhere (an actor): sums per
    (stage, fragment), handed over with ``take`` when the barrier
    comes."""

    epoch = None

    def __init__(self):
        self._ms: Dict[tuple, float] = {}

    def add_stage(self, stage: str, ms: float, fragment: str = "-") -> None:
        key = (stage, fragment)
        self._ms[key] = self._ms.get(key, 0.0) + ms

    def get(self, stage: str, fragment: str = "-") -> float:
        return self._ms.get((stage, fragment), 0.0)

    def take(self) -> Dict[tuple, float]:
        out, self._ms = self._ms, {}
        return out


@dataclass
class EpochTrace:
    """Everything one barrier did, attributed by lifecycle stage.

    ``stages_ms`` keys, each the sum of the spans that carry it as
    ``stage=`` (span names in brackets; a child key ``parent.child``
    lies inside its parent's time and never changes what the parent
    holds). A key whose span did not run in an epoch reads 0.0; a key
    that is absent was never instrumented on that path. ``compile``
    alone appears only when it happened.

      ingest                 — [push] host time in push() since the
                               previous barrier
      ingest.permit_wait     — [push.permit_wait] of it, blocked on a
                               graph channel's permits
      <root>.device_wait     — [device.read] of ``<root>`` (ingest,
                               dispatch, checkpoint_stage, actor: summed
                               over the actors), the thread blocked on
                               the device: a device->host copy or a
                               block_until_ready, wherever under it
      dispatch               — [barrier.fragment] per-fragment barrier
                               walk: inject, wait, drain, route
      dispatch.drain         — [dispatch.drain] barrier injected -> the
                               fragment's last actor has taken it off
                               its channels (the actors finishing the
                               epoch's queued chunks)
      dispatch.flush         — [dispatch.flush] from there to collected
                               (flush, finish_barrier fence, drain)
      actor_busy.<actor>     — [actor.chunk] an actor's chunk time in
      actor_idle.<actor>       the epoch less its blocked part, [actor.
      actor_blocked.<actor>    idle] its wait on empty inputs, [actor.
                               blocked] its wait for downstream permits
      actor_fence.<actor>    — [actor.fence] finish_barrier: the
                               barrier-only device fence
      actor.mv_apply         — [mv.apply] host-map MV applies
      dispatch.fence         — [pipeline.fence] a serial pipeline's
                               finish_barrier: the barrier-only device
                               fence (block_until_ready + staged-scalar
                               materialization)
      checkpoint_stage       — [checkpoint.stage] delta pull + mark
                               flips (mgr.stage), on the barrier's
                               thread
      checkpoint_stage.marks — [checkpoint.marks, less the pulls inside
                               it] an executor's staging outside its
                               row pull: the dirty/live/stored marks
                               classified and flipped on the device
                               (classify_marks), the count and the
                               changed slots' tombstone bits read down
      checkpoint_stage.pull  — [checkpoint.pull] pull_rows: gather
                               dispatch + the device->host copies, one
                               a piece and (dtype, row shape) of the
                               table's lanes, all started before the
                               first is awaited
      checkpoint_stage.dictionary — [checkpoint.dictionary] the session
                               dictionary's new strings, put as one
                               segment (0.0: none was new)
      upload                 — [checkpoint.upload] SST build + put, on
      manifest_commit          the checkpoint worker; [checkpoint.
                               manifest] version write (the durability
                               point). Land after ``finalize``.
      publish                — [barrier.publish] arrangements.publish:
                               to the point a reader can see the epoch
      bookkeeping            — [barrier.bookkeeping] state_nbytes,
                               finalize, freshness, governors,
                               MESHPROF, flight recorder, event log
      compile                — [compile] jax trace/lower/compile that
                               fell into the epoch, on any thread

    ``wall_ms`` closes in ``finalize`` (the flight recorder, itself
    bookkeeping, reads it): ``publish`` and ``bookkeeping`` lie after
    it, as do the worker's stages.
    """

    epoch: int
    seq: int
    checkpoint: bool
    t_start: float = field(default_factory=time.perf_counter)
    wall_ms: float = 0.0
    stages_ms: Dict[str, float] = field(default_factory=dict)
    chunk_bytes: int = 0
    state_bytes: int = 0
    state_delta_bytes: int = 0
    hbm_bytes_touched: int = 0
    # byte-accounting provenance (PR 11): the legacy host guess
    # (state-delta + chunk bytes — it never saw state-table READ
    # traffic), the compiled-executable model that replaces it when
    # deviceprof has analyzed the barrier's programs, and the modeled
    # traffic's padding/useful decomposition
    hbm_bytes_touched_legacy: int = 0
    modeled_bytes: int = 0
    padding_bytes_frac: float = 0.0
    useful_bytes: int = 0
    padding_bytes: int = 0
    # compact fused telemetry of the fragments that ran THIS barrier
    # (consumed from deviceprof at finalize; the flight recorder's
    # `tel` field — never a stale echo of an earlier barrier)
    telemetry: Dict = field(default_factory=dict)
    achieved_bw_gbps: float = 0.0
    achieved_bw_frac: float = 0.0
    useful_bw_frac: float = 0.0
    committed_at: Optional[float] = None
    # freshness + backpressure (ISSUE 16): wall clock when the barrier
    # opened (the commit->visible anchor), per-MV freshness deltas as
    # published, per-fragment dispatch walls, and the barrier's
    # bottleneck verdict — all host-side, stamped by runtime._end_trace
    barrier_open_wall: Optional[float] = None
    fragment_ms: Dict[str, float] = field(default_factory=dict)
    freshness: Dict = field(default_factory=dict)
    backpressure_fragment: Optional[str] = None
    backpressure_ms: float = 0.0
    backpressure: Dict = field(default_factory=dict)
    # mesh observability (ISSUE 18): per-shard barrier attribution +
    # exchange (src,dst) traffic matrix + hot-shard skew verdict for
    # the multi-chip path, folded by MESHPROF.observe_barrier. None on
    # serial barriers (the common case costs one attribute slot).
    mesh: Optional[Dict] = None
    # a slow barrier's own critical path (trace.barrier_path, cut to
    # its largest rows), set by the runtime's bookkeeping; None else
    slow_path: Optional[Dict] = None

    def add_stage(self, stage: str, ms: float, fragment: str = "-") -> None:
        self.stages_ms[stage] = self.stages_ms.get(stage, 0.0) + ms
        if fragment != "-" and "." not in stage:
            # a child stage lies inside its parent's wall
            self.fragment_ms[fragment] = (
                self.fragment_ms.get(fragment, 0.0) + ms
            )
        record_stage(stage, ms, fragment)

    def declare(self, *stages: str) -> None:
        """These stages are instrumented on this barrier's path: one
        whose span does not run reads 0.0, not absent."""
        for s in stages:
            self.stages_ms.setdefault(s, 0.0)

    def finalize(
        self,
        state_bytes: int,
        prev_state_bytes: int,
        device_kind: Optional[str] = None,
        modeled_bytes: Optional[int] = None,
        padding_frac: Optional[float] = None,
    ) -> None:
        """Close the trace: wall time + device telemetry. Called once
        the barrier's synchronous part is done (async commit stages may
        still land afterwards — they mutate stages_ms in place).

        Byte accounting: ``hbm_bytes_touched`` prefers the MODELED
        bytes of the barrier's compiled programs (deviceprof's XLA
        cost analysis — what the donated program actually reads and
        writes, state-table reads included) and falls back to the
        legacy state-delta + chunk sum, which is always kept as
        ``hbm_bytes_touched_legacy`` for artifact continuity. The
        modeled traffic decomposes into useful vs padding bytes using
        the telemetry lanes' live/capacity accounting, so
        ``achieved_bw_frac`` finally splits into "how busy was HBM"
        (achieved) vs "how much of that was masked-lane waste"
        (padding_bytes_frac -> useful_bw_frac)."""
        self.wall_ms = (time.perf_counter() - self.t_start) * 1e3
        self.state_bytes = int(state_bytes)
        self.state_delta_bytes = abs(int(state_bytes) - int(prev_state_bytes))
        self.hbm_bytes_touched_legacy = (
            self.state_delta_bytes + self.chunk_bytes
        )
        if modeled_bytes is None:
            try:
                from risingwave_tpu.deviceprof import DEVICEPROF

                # CONSUME the barrier's model: only fragments that
                # actually dispatched since the previous barrier count
                # (an idle barrier models zero traffic — no phantom
                # bandwidth), and their telemetry rides this trace
                # into the flight-recorder record
                tail = DEVICEPROF.consume_barrier()
                modeled_bytes = tail["modeled_bytes"]
                self.telemetry = tail["tel"]
                if padding_frac is None:
                    padding_frac = tail["padding_frac"]
            except Exception:  # noqa: BLE001 — accounting never faults
                modeled_bytes = 0
        self.modeled_bytes = int(modeled_bytes or 0)
        self.padding_bytes_frac = float(padding_frac or 0.0)
        self.hbm_bytes_touched = (
            self.modeled_bytes or self.hbm_bytes_touched_legacy
        )
        self.useful_bytes = int(
            self.hbm_bytes_touched * (1.0 - self.padding_bytes_frac)
        )
        self.padding_bytes = self.hbm_bytes_touched - self.useful_bytes
        rf = roofline(
            self.hbm_bytes_touched, self.wall_ms / 1e3, device_kind
        )
        self.achieved_bw_gbps = rf["achieved_bw_gbps"]
        self.achieved_bw_frac = rf["achieved_bw_frac"]
        self.useful_bw_frac = round(
            self.achieved_bw_frac * (1.0 - self.padding_bytes_frac), 6
        )
        REGISTRY.gauge("achieved_bw_frac").set(self.achieved_bw_frac)
        REGISTRY.gauge("useful_bw_frac").set(self.useful_bw_frac)
        REGISTRY.gauge("hbm_bytes_touched").set(float(self.hbm_bytes_touched))

    def to_dict(self) -> Dict:
        return {
            "epoch": self.epoch,
            "seq": self.seq,
            "checkpoint": self.checkpoint,
            "wall_ms": round(self.wall_ms, 3),
            "stages_ms": {k: round(v, 3) for k, v in self.stages_ms.items()},
            "chunk_bytes": self.chunk_bytes,
            "state_bytes": self.state_bytes,
            "state_delta_bytes": self.state_delta_bytes,
            "hbm_bytes_touched": self.hbm_bytes_touched,
            "hbm_bytes_touched_legacy": self.hbm_bytes_touched_legacy,
            "modeled_bytes": self.modeled_bytes,
            "padding_bytes_frac": self.padding_bytes_frac,
            "useful_bytes": self.useful_bytes,
            "padding_bytes": self.padding_bytes,
            "achieved_bw_gbps": self.achieved_bw_gbps,
            "achieved_bw_frac": self.achieved_bw_frac,
            "useful_bw_frac": self.useful_bw_frac,
            "fragment_ms": {
                k: round(v, 3) for k, v in self.fragment_ms.items()
            },
            "freshness": self.freshness,
            "backpressure_fragment": self.backpressure_fragment,
            "backpressure_ms": round(self.backpressure_ms, 3),
            "mesh": self.mesh,
        }


def stage_breakdown() -> Dict[str, Dict[str, float]]:
    """The registry's barrier_stage_ms summary — what bench.py embeds
    in every BENCH_*.json as ``barrier_stage_ms``."""
    h = REGISTRY.histograms.get("barrier_stage_ms")
    return h.summary() if h is not None else {}


# ---------------------------------------------------------------------------
# Stall dumps (await-tree analogue)
# ---------------------------------------------------------------------------

_DUMP_LOCK = threading.Lock()
_DUMP_SEQ = [0]  # same-second dumps must not overwrite each other


def dump_stalls(
    reason: str,
    runtime=None,
    graph=None,
    extra: Optional[Dict] = None,
    path: Optional[str] = None,
) -> str:
    """Snapshot what every thread/actor is doing into a JSON artifact.

    Captures: each thread's open span stack (trace.active_spans), each
    actor's liveness + input-channel depths + last-collected epoch,
    pending (uncollected) epochs with the stuck actors named, per-
    fragment epochs, and the recent event-log tail. Returns the artifact
    path. Never raises — a forensic dump must not worsen the stall."""
    from risingwave_tpu.trace import active_spans

    doc: Dict = {
        "reason": reason,
        "ts": time.time(),
        "pid": os.getpid(),
        "spans": active_spans(),
    }
    try:
        if graph is not None:
            doc["graph"] = graph.stall_snapshot()
        if runtime is not None:
            doc["runtime"] = _runtime_snapshot(runtime)
        from risingwave_tpu.event_log import EVENT_LOG

        doc["recent_events"] = EVENT_LOG.events(limit=20)
        if extra:
            doc["extra"] = extra
        # freshness state + last bottleneck verdict: a stall dump says
        # how STALE every MV already is and which fragment was the
        # bottleneck on the barriers leading in
        from risingwave_tpu.freshness import FRESHNESS

        doc["freshness"] = FRESHNESS.snapshot()
        tr = getattr(runtime, "last_epoch_trace", None)
        if tr is not None and getattr(tr, "backpressure_fragment", None):
            doc["backpressure"] = {
                "fragment": tr.backpressure_fragment,
                "ms": round(tr.backpressure_ms, 3),
                "detail": tr.backpressure,
            }
        # mesh section: when a sharded runtime is active, a stall dump
        # names the hot shard — per-shard occupancy/state depths + the
        # last (src,dst) exchange matrix and skew verdict
        from risingwave_tpu.parallel.meshprof import MESHPROF

        if MESHPROF.enabled:
            msnap = MESHPROF.table_snapshot()
            if msnap.get("tables") or msnap.get("last_barrier"):
                doc["mesh"] = {
                    "tables": msnap.get("tables"),
                    "last_barrier": msnap.get("last_barrier"),
                    "exchange": msnap.get("exchange"),
                }
        if tr is not None and getattr(tr, "mesh", None):
            doc.setdefault("mesh", {})["trace"] = tr.mesh
    except Exception as e:  # partial dump beats no dump
        doc["snapshot_error"] = repr(e)
    try:
        # device-side evidence (q7 wedge forensics): HBM memory stats,
        # live-array census, accounted state tables, in-flight dispatch
        # counters — a wedged TPU leaves data, not just a dead process
        from risingwave_tpu.profiler import device_forensics

        doc["device"] = device_forensics()
    except Exception as e:
        doc["device"] = repr(e)
    try:
        # black-box context: the last barriers BEFORE the stall (what
        # the flight recorder saw) + the sentinel's device classification
        from risingwave_tpu.blackbox import RECORDER, SENTINEL

        doc["blackbox"] = {
            "recorder_tail": RECORDER.snapshot_tail(32),
            "sentinel": SENTINEL.snapshot(),
        }
    except Exception as e:
        doc["blackbox"] = repr(e)
    fallback_err = None
    if path is None:
        d = os.environ.get("RW_STALL_DIR", ".")
        with _DUMP_LOCK:
            _DUMP_SEQ[0] += 1
            seq = _DUMP_SEQ[0]
        path = os.path.join(
            d, f"STALL_DUMP_{int(time.time())}_{seq}.json"
        )
    with _DUMP_LOCK:
        try:
            with open(path, "w") as f:
                json.dump(doc, f, indent=1, default=str)
        except OSError as e:
            # RW_STALL_DIR unwritable: the forensic artifact still must
            # land somewhere — fall back to the system temp dir and say
            # so in the event log (previously a silent "")
            fallback_err = repr(e)
            import tempfile

            path = os.path.join(
                tempfile.gettempdir(), os.path.basename(path)
            )
            try:
                with open(path, "w") as f:
                    json.dump(doc, f, indent=1, default=str)
            except OSError:
                return ""
    try:
        from risingwave_tpu.event_log import EVENT_LOG

        if fallback_err is not None:
            EVENT_LOG.record(
                "stall_dump_fallback", error=fallback_err, path=path
            )
        EVENT_LOG.record("stall_dump", reason=reason, path=path)
    except Exception:
        pass
    REGISTRY.counter("stall_dumps_total").inc()
    return path


def _runtime_snapshot(rt) -> Dict:
    """StreamingRuntime-side stall state: per-fragment epochs, the
    async-lane depth, and graph-backed fragments' actor snapshots."""
    pending = getattr(rt, "_pending_partial", None)
    snap: Dict = {
        "epoch": getattr(rt, "_epoch", None),
        "committed_epoch": rt.mgr.max_committed_epoch if rt.mgr else None,
        "inflight_commits": getattr(rt, "_inflight", 0),
        # partial-recovery provenance: which fragments are fenced for a
        # deferred scoped recovery, and how many partials have run —
        # a wedge mid-partial-recovery is debuggable from this alone
        "partial_recoveries": getattr(rt, "partial_recoveries", 0),
        "pending_partial": (
            sorted(pending["scope"]) if pending is not None else None
        ),
        "fragments": {},
    }
    # shape-stability forensics: a wedge-adjacent stall with pinned
    # executors or accumulated hazards names its own cause
    gov = getattr(rt, "shape_governor", None)
    if gov is not None:
        try:
            snap["shape_governor"] = gov.snapshot()
        except Exception as e:  # noqa: BLE001 — forensics never fault
            snap["shape_governor"] = repr(e)
    for name, p in getattr(rt, "fragments", {}).items():
        frag = {"epoch": getattr(p, "_epoch", None)}
        g = getattr(p, "graph", None)
        if g is not None:  # GraphPipeline: per-actor detail
            try:
                frag["actors"] = g.stall_snapshot()
            except Exception as e:
                frag["actors"] = repr(e)
        snap["fragments"][name] = frag
    return snap
