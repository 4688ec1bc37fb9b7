"""Black-box flight recorder + device-wedge sentinel.

The two failure modes that have destroyed whole TPU bench rounds leave
no evidence today: q7 *wedges the device* (BENCH_TPU_2/3: "device
wedged; stopping" after hanging until the 360s child alarm) and a
client SIGKILLed mid-round leaves zero artifacts. Every
post-mortem so far was reconstructed from healthy-run data. This module
is the always-on answer — telemetry that survives the *process*, not
just the barrier:

- **Flight recorder** (``RECORDER``): a bounded in-memory ring of
  compact per-barrier records (epoch, per-stage ms from EpochTrace,
  dispatch/transfer counters from PROFILER, recompile hazards, channel
  depths, sampled device memory_stats, sentinel state), persisted
  incrementally to an append-only JSONL segment file with a bounded
  fsync cadence — a SIGKILL, OOM, or wedged device still leaves a
  readable black box on disk. ``python -m risingwave_tpu blackbox
  <path>`` reconstructs the last-N-barrier timeline and can emit a
  Perfetto-compatible trace via trace.render_chrome_trace.
- **Device-health sentinel** (``SENTINEL``): a daemon thread that
  issues a tiny jitted heartbeat op through a worker thread with a
  deadline and classifies the device ``ALIVE`` / ``SLOW`` / ``WEDGED``.
  On WEDGED it captures a forensic bundle (every thread's stack via
  ``sys._current_frames``, profiler counters + device forensics, a
  live-array census, the flight-recorder tail) to a durable
  ``WEDGE_*.json`` artifact and arms a structured :class:`DeviceWedged`
  that the runtime's barrier clock and ``GraphRuntime.wait_barrier``
  raise *instead of hanging* — recovery paths treat it like an actor
  fault (clear the wedge, abort the capture window, recover), not a
  process crash.

Hot-path contract (same as profiler.py): everything is gated on one
``enabled``/``running`` attribute check; the recorder's per-barrier
cost is the ``bookkeeping.recorder`` span.

This module must stay importable without touching jax (the reader CLI
parses segments from plain processes):
jax is imported lazily inside the default heartbeat / forensics only.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from risingwave_tpu.config import env_float
from risingwave_tpu.metrics import REGISTRY

__all__ = [
    "RECORDER",
    "SENTINEL",
    "DeviceWedged",
    "FlightRecorder",
    "DeviceSentinel",
    "classify_latency",
    "from_env",
    "configure",
    "read_segment",
]

# sentinel device states (also the `device_state` gauge encoding)
ALIVE, SLOW, WEDGED, UNKNOWN = "ALIVE", "SLOW", "WEDGED", "UNKNOWN"
_STATE_GAUGE = {ALIVE: 0.0, SLOW: 1.0, WEDGED: 2.0, UNKNOWN: -1.0}


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


def classify_latency(
    latency_ms: Optional[float], slow_ms: float, deadline_ms: float
) -> str:
    """The ALIVE/SLOW/WEDGED vocabulary of the sentinel's `device_state`
    events. ``None`` latency means
    the probe never completed (deadline exceeded)."""
    if latency_ms is None or latency_ms >= deadline_ms:
        return WEDGED
    if latency_ms >= slow_ms:
        return SLOW
    return ALIVE


class DeviceWedged(RuntimeError):
    """The device stopped answering heartbeats within the watchdog
    deadline. Structured: carries the sentinel classification, the
    last heartbeat latency, and the forensic-bundle path — the runtime
    raises this at the barrier (and wait_barrier raises it mid-wait)
    instead of hanging until an outer alarm murders the process."""

    def __init__(
        self,
        msg: str,
        state: str = WEDGED,
        latency_ms: Optional[float] = None,
        bundle_path: str = "",
    ):
        super().__init__(msg)
        self.state = state
        self.latency_ms = latency_ms
        self.bundle_path = bundle_path


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


class _Suppress:
    """Re-entrant thread-local suppression window (one tiny object per
    enter — no generator machinery on the barrier path)."""

    __slots__ = ("_tls",)

    def __init__(self, tls):
        self._tls = tls

    def __enter__(self):
        self._tls.suppress = getattr(self._tls, "suppress", 0) + 1
        return self

    def __exit__(self, *exc):
        self._tls.suppress -= 1
        return False


class FlightRecorder:
    """Bounded ring of per-barrier records + incremental append-only
    JSONL segment persistence. Record keys are compact (the segment is
    written on the barrier path); the reader expands them:

      k=h  header: pid, ts, ver, ring
      k=b  barrier: ts, ep(och), seq, ck(pt), wall(ms), st(ages_ms),
           bw (achieved_bw_frac), cb (chunk_bytes), sb (state_bytes),
           d (cumulative device dispatches), x ({d2h,h2d} cumulative),
           hz (cumulative recompile hazards), dep ({fragment: total
           input-channel depth}), sen (sentinel state), mem (sampled
           device memory_stats), mb (modeled bytes per barrier from the
           compiled-executable roofline), pf (padding-bytes fraction of
           the modeled traffic), tel ({fragment: fused telemetry-lane
           scalars: per-member rows + dirty groups}), slow (a slow
           barrier's own critical path: trace.barrier_path's by_kind,
           its largest by_span rows, the other threads' open spans)

    Counters are recorded CUMULATIVE (cheap snapshot, no per-record
    subtraction on the hot path); the reader derives per-barrier
    deltas. The ring is always available in memory (stall dumps and
    wedge bundles embed its tail); the segment file only exists when a
    directory is configured (RW_BLACKBOX_DIR / config [blackbox])."""

    SEGMENT_PREFIX = "BLACKBOX_"
    # a barrier is slow when its wall is over SLOW_MS and over SLOW_X
    # times the median wall of the ring's barriers
    SLOW_MS, SLOW_X = 1000.0, 3.0

    def __init__(self):
        self.enabled = True  # ring recording (in-memory, always cheap)
        self.ring: deque = deque(maxlen=256)
        self._lock = threading.Lock()
        self._tls = threading.local()  # pipeline-record suppression
        self.dir: Optional[str] = None  # None = no disk persistence
        self.fsync_interval_s = 2.0
        self.segment_max_bytes = 8_000_000
        self.mem_sample_every = 8  # device memory_stats cadence
        self._fh = None
        self._path: Optional[str] = None
        self._bytes = 0
        self._last_fsync = 0.0
        self._records = 0
        # distinguishes THIS recorder's headers from a previous
        # incarnation's in the same file (pid reuse appends): rotation
        # headers share the run id, a new process gets a fresh one
        self._run_id = f"{os.getpid()}-{int(time.time() * 1e3)}"

    # -- lifecycle --------------------------------------------------------
    def configure(
        self,
        dir: Optional[str] = None,
        ring: Optional[int] = None,
        fsync_interval_s: Optional[float] = None,
        segment_max_bytes: Optional[int] = None,
        enabled: Optional[bool] = None,
    ) -> "FlightRecorder":
        with self._lock:
            if enabled is not None:
                self.enabled = enabled
            if ring is not None and ring != self.ring.maxlen:
                self.ring = deque(self.ring, maxlen=max(8, int(ring)))
            if fsync_interval_s is not None:
                self.fsync_interval_s = max(0.0, fsync_interval_s)
            if segment_max_bytes is not None:
                self.segment_max_bytes = max(65_536, int(segment_max_bytes))
            if dir is not None and dir != self.dir:
                self._close_locked()
                self.dir = dir or None
        return self

    def close(self) -> None:
        with self._lock:
            self._close_locked()

    def _close_locked(self) -> None:
        if self._fh is not None:
            try:
                self._fh.flush()
                os.fsync(self._fh.fileno())
            except OSError:
                pass
            try:
                self._fh.close()
            except OSError:
                pass
        self._fh = None
        self._path = None
        self._bytes = 0

    @property
    def segment_path(self) -> Optional[str]:
        return self._path

    # -- the hot-path hook ------------------------------------------------
    def record_barrier(self, trace, runtime=None) -> None:
        """One compact record per barrier. ``trace`` is an EpochTrace
        (duck-typed: epoch/seq/checkpoint/wall_ms/stages_ms/...).
        Never raises — the black box must not worsen the barrier."""
        if not self.enabled:
            return
        try:
            rec = self._build_record(trace, runtime)
        except Exception:  # noqa: BLE001 — forensic, never load-bearing
            return
        with self._lock:
            self._records += 1
            # device memory_stats is a PJRT call — sample, don't spam
            sample_mem = self._records % self.mem_sample_every == 1
        if sample_mem:
            mem = _device_memory_stats()
            if mem is not None:
                rec["mem"] = mem
        # publish ONLY once fully built: snapshot_tail hands out the
        # dicts by reference, so a concurrent stall dump / wedge bundle
        # must never see a record mutate mid-serialization
        with self._lock:
            self.ring.append(rec)
        REGISTRY.counter("blackbox_records_total").inc()
        if self.dir is not None:
            self._persist(rec)

    def is_slow(self, wall_ms: float) -> bool:
        """Whether a barrier of this wall stands out of the ring's (ask
        before it is recorded): one comparison when it does not."""
        if wall_ms <= self.SLOW_MS:
            return False
        with self._lock:
            walls = sorted(rec["wall"] for rec in self.ring)
        return bool(walls) and wall_ms > self.SLOW_X * walls[len(walls) // 2]

    def _build_record(self, trace, runtime) -> Dict:
        from risingwave_tpu.profiler import PROFILER

        rec: Dict = {
            "k": "b",
            "ts": round(time.time(), 3),
            "ep": int(getattr(trace, "epoch", 0)),
            "seq": int(getattr(trace, "seq", 0)),
            "ck": bool(getattr(trace, "checkpoint", False)),
            "wall": round(float(getattr(trace, "wall_ms", 0.0)), 3),
            "st": {
                k: round(v, 3)
                for k, v in getattr(trace, "stages_ms", {}).items()
            },
            "bw": getattr(trace, "achieved_bw_frac", 0.0),
            "cb": int(getattr(trace, "chunk_bytes", 0)),
            "sb": int(getattr(trace, "state_bytes", 0)),
        }
        # cumulative counters: dispatches, transfers, recompile hazards
        # (reader derives per-barrier deltas)
        try:
            rec["d"] = int(PROFILER.total_dispatches())
            x = PROFILER.transfer_counts()
            if x.get("d2h") or x.get("h2d"):
                rec["x"] = {k: int(v) for k, v in x.items()}
        except Exception:
            pass
        hz = REGISTRY.counters.get("recompile_hazard_total")
        if hz is not None:
            total = hz.total()
            if total:
                rec["hz"] = int(total)
        # fused-engine tail (PR 11): an EpochTrace finalize() already
        # CONSUMED its barrier's deviceprof model (modeled bytes +
        # telemetry of the fragments that ran in it) — read it off the
        # trace; standalone pipeline barriers (no EpochTrace) consume
        # here instead. Either way a record only ever shows what THIS
        # barrier did — never a stale echo of an earlier one.
        mb = int(getattr(trace, "modeled_bytes", 0))
        pf = float(getattr(trace, "padding_bytes_frac", 0.0))
        tel = getattr(trace, "telemetry", None)
        if tel is None:
            try:
                from risingwave_tpu.deviceprof import DEVICEPROF

                tail = DEVICEPROF.consume_barrier()
                mb = mb or tail["modeled_bytes"]
                pf = pf or tail["padding_frac"]
                tel = tail["tel"]
            except Exception:
                tel = None
        if tel:
            rec["tel"] = tel
        if mb:
            rec["mb"] = mb
            rec["pf"] = pf
        # per-fragment channel depth (graph-backed fragments): the
        # wedge question "where is the data stuck" answered per barrier
        if runtime is not None:
            dep = {}
            for name, p in getattr(runtime, "fragments", {}).items():
                g = getattr(p, "graph", None)
                if g is None:
                    continue
                try:
                    dep[name] = int(
                        sum(
                            len(ch)
                            for a in g.actors
                            for _p, ch in a.inputs
                        )
                    )
                except Exception:
                    continue
            if dep:
                rec["dep"] = dep
        # freshness deltas as published (ISSUE 16): per-MV
        # commit->visible / source->visible / event-time-lag, compacted
        # to cv/sv/lag; plus the barrier's backpressure verdict
        fr = getattr(trace, "freshness", None)
        if fr:
            compact = {}
            for mv, ent in fr.items():
                row = {}
                for key, short in (
                    ("commit_to_visible_ms", "cv"),
                    ("source_to_visible_ms", "sv"),
                    ("event_time_lag_ms", "lag"),
                ):
                    v = ent.get(key)
                    if v is not None:
                        row[short] = round(float(v), 3)
                if row:
                    compact[mv] = row
            if compact:
                rec["fr"] = compact
        slow = getattr(trace, "slow_path", None)
        if slow:
            rec["slow"] = slow
        bpf = getattr(trace, "backpressure_fragment", None)
        if bpf:
            rec["bp"] = {
                "f": bpf,
                "ms": round(
                    float(getattr(trace, "backpressure_ms", 0.0)), 3
                ),
            }
        # mesh observability (ISSUE 18): compact per-shard attribution
        # for sharded barriers — shard count, coverage, phase split,
        # per-shard local ms, (src,dst) row matrix, skew verdict
        msh = getattr(trace, "mesh", None)
        if msh:
            try:
                rec["msh"] = {
                    "n": msh.get("n_shards"),
                    "wall": round(float(msh.get("wall_ms", 0.0)), 3),
                    "att": round(
                        float(msh.get("attributed_ms", 0.0)), 3
                    ),
                    "cov": round(
                        float(msh.get("coverage_frac", 0.0)), 4
                    ),
                    "ph": {
                        k: round(float(v), 3)
                        for k, v in (msh.get("phases_ms") or {}).items()
                        if v
                    },
                    "loc": [
                        round(float(v), 3)
                        for v in (msh.get("shard_local_ms") or [])
                    ],
                    "xm": msh.get("exchange", {}).get("rows"),
                    "skew": msh.get("skew"),
                }
            except Exception:  # noqa: BLE001 — recorder never faults
                pass
        sen = SENTINEL
        if sen.running or sen.state != UNKNOWN:
            rec["sen"] = sen.state
        return rec

    def suppress_pipeline_records(self) -> "_Suppress":
        """Context for drivers that record their own barrier-level
        records (the StreamingRuntime's EpochTrace path, recovery
        replay): fragment-level Pipeline.barrier calls inside it stay
        silent — one barrier, one record, monotonic epochs."""
        return _Suppress(self._tls)

    def record_pipeline_barrier(
        self, epoch: int, dispatch_ms: float, device_ms: float
    ) -> None:
        """Standalone Pipeline/TwoInputPipeline barriers (the bench q7/
        q8 drivers) ride the same black box without an EpochTrace."""
        if not self.enabled or getattr(self._tls, "suppress", 0):
            return
        from types import SimpleNamespace

        self.record_barrier(
            SimpleNamespace(
                epoch=epoch,
                seq=0,
                checkpoint=False,
                wall_ms=dispatch_ms + device_ms,
                # the keys its two spans stamp (pipeline.walk, .fence)
                stages_ms={
                    "dispatch": dispatch_ms,
                    "dispatch.fence": device_ms,
                },
            )
        )

    # -- persistence ------------------------------------------------------
    def _persist(self, rec: Dict) -> None:
        line = json.dumps(rec, separators=(",", ":"), default=str) + "\n"
        with self._lock:
            try:
                if self._fh is None:
                    self._open_locked()
                if self._bytes + len(line) > self.segment_max_bytes:
                    self._rotate_locked()
                self._fh.write(line)
                self._fh.flush()  # survive SIGKILL up to the OS cache
                self._bytes += len(line)
                now = time.monotonic()
                if now - self._last_fsync >= self.fsync_interval_s:
                    t0 = time.perf_counter()
                    os.fsync(self._fh.fileno())
                    REGISTRY.histogram("blackbox_fsync_ms").observe(
                        (time.perf_counter() - t0) * 1e3
                    )
                    REGISTRY.counter("blackbox_fsyncs_total").inc()
                    self._last_fsync = now
            except (OSError, ValueError):
                # unwritable dir / disk full / malformed path: the ring
                # keeps recording; drop persistence, not the barrier
                self._close_locked()
                self.dir = None
                REGISTRY.counter("blackbox_persist_errors_total").inc()

    def _open_locked(self) -> None:
        os.makedirs(self.dir, exist_ok=True)
        self._path = os.path.join(
            self.dir, f"{self.SEGMENT_PREFIX}{os.getpid()}.jsonl"
        )
        self._fh = open(self._path, "a")
        self._bytes = 0
        try:
            self._bytes = os.fstat(self._fh.fileno()).st_size
        except OSError:
            pass
        hdr = {
            "k": "h",
            "pid": os.getpid(),
            "run": self._run_id,
            "ts": round(time.time(), 3),
            "ver": 1,
            "ring": self.ring.maxlen,
        }
        line = json.dumps(hdr, separators=(",", ":")) + "\n"
        self._fh.write(line)
        self._fh.flush()
        self._bytes += len(line)
        self._last_fsync = time.monotonic()

    def _rotate_locked(self) -> None:
        """Bounded disk: the current segment becomes ``<path>.old``
        (replacing any previous rotation) and a fresh segment opens —
        the reader merges both, so the readable window is at least
        ``segment_max_bytes`` of history."""
        path = self._path
        self._close_locked()
        try:
            os.replace(path, path + ".old")
        except OSError:
            pass
        self._open_locked()
        REGISTRY.counter("blackbox_rotations_total").inc()

    # -- read surfaces ----------------------------------------------------
    def snapshot_tail(self, n: int = 32) -> List[Dict]:
        with self._lock:
            return list(self.ring)[-n:]

    def snapshot(self) -> Dict:
        with self._lock:
            return {
                "enabled": self.enabled,
                "records": self._records,
                "ring_len": len(self.ring),
                "segment": self._path,
                "dir": self.dir,
            }


def _device_memory_stats() -> Optional[Dict]:
    """Sampled device HBM stats (None on CPU / failure). Lazy jax
    import — reader-only processes never pay it."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
        if not stats:
            return None
        # keep the load-bearing subset (full stats are verbose)
        keep = (
            "bytes_in_use",
            "peak_bytes_in_use",
            "bytes_limit",
            "largest_free_block_bytes",
            "num_allocs",
        )
        return {k: int(stats[k]) for k in keep if k in stats}
    except Exception:
        return None


# ---------------------------------------------------------------------------
# device-health sentinel
# ---------------------------------------------------------------------------

_HB_LOCK = threading.Lock()
_HB_FN = None
_HB_ARG = None


def _default_heartbeat() -> None:
    """The tiny jitted heartbeat op: one dispatch + one block. If the
    device queue is wedged this blocks — which is exactly the signal
    (the worker thread absorbs the block; the monitor times it out)."""
    global _HB_FN, _HB_ARG
    import jax

    with _HB_LOCK:
        if _HB_FN is None:
            import jax.numpy as jnp

            _HB_FN = jax.jit(lambda x: (x + 1).sum())
            _HB_ARG = jnp.zeros(8, jnp.int32)
    jax.block_until_ready(_HB_FN(_HB_ARG))


class DeviceSentinel:
    """Heartbeat-based device-wedge detector.

    Two threads: ``rw-sentinel`` (monitor — never touches the device)
    requests a beat every ``interval_s`` from ``rw-sentinel-beat`` (the
    worker that actually dispatches the heartbeat op) and waits at most
    ``deadline_s``. A worker stuck inside a device call cannot be
    interrupted from Python, so the monitor classifies WEDGED by
    timeout, captures the forensic bundle while the device evidence is
    still live, arms :class:`DeviceWedged`, and keeps watching: if the
    stuck beat eventually completes (device revived), the state heals
    to ALIVE on the next cycle. While a beat is stuck no new worker is
    spawned — at most the one extra (stuck) thread ever exists.

    ``check()`` is the runtime hook: one attribute read when healthy,
    raises the armed DeviceWedged when not. Recovery calls
    ``clear_wedge()`` (treat-like-an-actor-fault contract: recover,
    don't crash) and ``abort_capture()`` closes an in-flight bundle
    window."""

    def __init__(self):
        self.interval_s = 5.0
        self.slow_ms = 1000.0
        self.deadline_s = 20.0
        self.dir: Optional[str] = None  # default: RECORDER.dir / RW_STALL_DIR
        self.state_file: Optional[str] = None  # heartbeat status JSON
        self.heartbeat_fn: Callable[[], None] = _default_heartbeat
        self.on_wedge: Optional[Callable[[DeviceWedged], None]] = None
        self.state = UNKNOWN
        self.last_latency_ms: Optional[float] = None
        self.beats = 0
        self.wedges = 0
        self.running = False
        self._wedged: Optional[DeviceWedged] = None
        self._capture_open = False  # orphan-audit surface
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._monitor: Optional[threading.Thread] = None
        self._worker: Optional[threading.Thread] = None
        self._beat_req = threading.Event()
        self._beat_done = threading.Event()
        self._beat_err: Optional[BaseException] = None
        self._bundle_seq = 0

    # -- lifecycle --------------------------------------------------------
    def start(
        self,
        interval_s: Optional[float] = None,
        slow_ms: Optional[float] = None,
        deadline_s: Optional[float] = None,
        heartbeat_fn: Optional[Callable[[], None]] = None,
        on_wedge: Optional[Callable[[DeviceWedged], None]] = None,
        dir: Optional[str] = None,
    ) -> "DeviceSentinel":
        with self._lock:
            if interval_s is not None:
                self.interval_s = max(0.01, interval_s)
            if slow_ms is not None:
                self.slow_ms = slow_ms
            if deadline_s is not None:
                self.deadline_s = max(0.05, deadline_s)
            if heartbeat_fn is not None:
                self.heartbeat_fn = heartbeat_fn
            if on_wedge is not None:
                self.on_wedge = on_wedge
            if dir is not None:
                self.dir = dir
            if self.running:
                return self
            self.running = True
            self._stop.clear()
            self._monitor = threading.Thread(
                target=self._monitor_loop, daemon=True, name="rw-sentinel"
            )
            self._monitor.start()
        return self

    def stop(self, join_timeout: float = 5.0) -> None:
        with self._lock:
            if not self.running:
                return
            self.running = False
            self._stop.set()
        if self._monitor is not None:
            self._monitor.join(timeout=join_timeout)
        # the worker exits on the stop flag unless stuck in the device
        # call itself (daemon thread; nothing can unstick it from here)
        w = self._worker
        if w is not None:
            w.join(timeout=join_timeout)
            if not w.is_alive():
                self._worker = None

    # -- runtime hooks ----------------------------------------------------
    def check(self) -> None:
        """Raise the armed DeviceWedged (the barrier-clock hook). One
        attribute read when healthy."""
        w = self._wedged
        if w is not None:
            raise w

    def wedged_error(self) -> Optional[DeviceWedged]:
        return self._wedged

    def clear_wedge(self) -> None:
        """Recovery treats a wedge like an actor fault: clear the armed
        error so the recovered runtime's next barrier proceeds; a still-
        wedged device re-arms on the next missed heartbeat."""
        self._wedged = None

    def abort_capture(self) -> int:
        """Close an in-flight wedge-capture window (recovery hygiene).
        Returns 1 if a window was open."""
        with self._lock:
            was = self._capture_open
            self._capture_open = False
        return int(was)

    def snapshot(self) -> Dict:
        return {
            "running": self.running,
            "state": self.state,
            "last_latency_ms": self.last_latency_ms,
            "beats": self.beats,
            "wedges": self.wedges,
            "wedged": repr(self._wedged) if self._wedged else None,
            "interval_s": self.interval_s,
            "deadline_s": self.deadline_s,
        }

    # -- internals --------------------------------------------------------
    def _ensure_worker(self) -> bool:
        """True iff a worker is available for a new beat. A worker
        still stuck in a previous beat means the device is still
        blocked — don't pile up threads, stay WEDGED."""
        w = self._worker
        if w is not None and w.is_alive():
            return not self._beat_req.is_set()
        self._beat_req.clear()
        self._beat_done.clear()
        self._worker = threading.Thread(
            target=self._worker_loop, daemon=True, name="rw-sentinel-beat"
        )
        self._worker.start()
        return True

    def _worker_loop(self) -> None:
        while not self._stop.is_set():
            if not self._beat_req.wait(timeout=0.2):
                continue
            self._beat_req.clear()
            try:
                self.heartbeat_fn()
                self._beat_err = None
            except BaseException as e:  # noqa: BLE001 — classified below
                self._beat_err = e
            self._beat_done.set()

    def _monitor_loop(self) -> None:
        while not self._stop.wait(timeout=self.interval_s):
            try:
                self._beat_once()
            except Exception:  # noqa: BLE001 — the watchdog never dies
                pass

    def _beat_once(self) -> None:
        if not self._ensure_worker():
            # previous beat still stuck inside the device call: the
            # wedge persists — keep the state + armed error current
            self._transition(WEDGED, None)
            return
        self._beat_done.clear()
        t0 = time.perf_counter()
        self._beat_req.set()
        done = self._beat_done.wait(timeout=self.deadline_s)
        latency_ms = (time.perf_counter() - t0) * 1e3
        self.beats += 1
        if not done:
            self._transition(WEDGED, None)
            return
        if self._beat_err is not None:
            # a raising heartbeat (device runtime error) is as wedged
            # as a silent one, but carries a cause worth keeping
            self._transition(WEDGED, latency_ms, err=self._beat_err)
            return
        self.last_latency_ms = latency_ms
        self._transition(
            classify_latency(latency_ms, self.slow_ms, self.deadline_s * 1e3),
            latency_ms,
        )

    def _transition(
        self,
        new_state: str,
        latency_ms: Optional[float],
        err: Optional[BaseException] = None,
    ) -> None:
        prev = self.state
        self.state = new_state
        REGISTRY.counter("sentinel_heartbeats_total").inc(state=new_state)
        REGISTRY.gauge("device_state").set(_STATE_GAUGE[new_state])
        if new_state != prev:
            try:
                from risingwave_tpu.event_log import EVENT_LOG

                EVENT_LOG.record(
                    "device_state",
                    state=new_state,
                    prev=prev,
                    latency_ms=(
                        round(latency_ms, 1) if latency_ms is not None else None
                    ),
                    source="sentinel",
                )
            except Exception:
                pass
        if new_state == WEDGED:
            if self._wedged is None:
                # first detection of THIS wedge: ARM FIRST, capture
                # after — the forensic bundle touches the (wedged)
                # device and may itself block, and the whole point is
                # that check()/wait_barrier/on_wedge fail fast instead
                # of sitting out an outer alarm
                self.wedges += 1
                wedged = DeviceWedged(
                    "device wedged: heartbeat exceeded "
                    f"{self.deadline_s}s deadline"
                    + (f" ({err!r})" if err is not None else ""),
                    latency_ms=latency_ms,
                )
                self._wedged = wedged
                cb = self.on_wedge
                if cb is not None:
                    try:
                        cb(wedged)
                    except Exception:
                        pass
                wedged.bundle_path = self._capture_wedge_bundle(
                    latency_ms, err
                )
        else:
            # ANY completed heartbeat disarms: the device answers
            # (ALIVE, or SLOW — a slow device is usable), so a
            # stale armed wedge must not keep failing barriers
            self._wedged = None
        # written LAST so the file reflects the wedge counter/bundle
        # the transition just produced
        self._write_state_file(latency_ms)

    def _write_state_file(self, latency_ms: Optional[float]) -> None:
        """One-line status JSON, atomically replaced every beat — the
        surface an outside watcher can tail."""
        path = self.state_file
        if path is None:
            d = self.dir or RECORDER.dir
            if d is None:
                return
            path = os.path.join(d, "SENTINEL_STATE.json")
        doc = {
            "ts": round(time.time(), 3),
            "state": self.state,
            "latency_ms": (
                round(latency_ms, 1) if latency_ms is not None else None
            ),
            "beats": self.beats,
            "wedges": self.wedges,
            "pid": os.getpid(),
        }
        try:
            # overload ladder rung (the memory governor's gauge), so
            # a watcher sees THROTTLED/SHEDDING windows alongside the
            # device heartbeat
            from risingwave_tpu.metrics import REGISTRY
            from risingwave_tpu.runtime.memory_governor import LADDER

            g = REGISTRY.gauges.get("overload_state")
            if g is not None:
                i = int(g.get())
                doc["overload_state"] = (
                    LADDER[i] if 0 <= i < len(LADDER) else str(i)
                )
        except Exception:  # noqa: BLE001 — status stays heartbeat-only
            pass
        try:
            # mesh skew + exchange pressure (ISSUE 18): sharded runs
            # surface the hot-shard fraction and cumulative exchange
            # rows so a watcher can tail skew transitions
            from risingwave_tpu.metrics import REGISTRY as _REG
            from risingwave_tpu.parallel.meshprof import MESHPROF

            if MESHPROF.enabled:
                g = _REG.gauges.get("shard_skew_frac")
                if g is not None:
                    doc["shard_skew_frac"] = round(float(g.get()), 4)
                g = _REG.gauges.get("mesh_coverage_frac")
                if g is not None:
                    doc["mesh_coverage_frac"] = round(float(g.get()), 4)
                snap = MESHPROF.table_snapshot()
                ex = snap.get("exchange") or {}
                if ex.get("rows"):
                    doc["exchange_rows_total"] = int(
                        sum(sum(r) for r in ex["rows"])
                    )
        except Exception:  # noqa: BLE001 — status stays heartbeat-only
            pass
        try:
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, path)
        except OSError:
            pass

    def _capture_wedge_bundle(
        self, latency_ms: Optional[float], err: Optional[BaseException]
    ) -> str:
        """The forensic bundle a wedge leaves behind: thread stacks,
        device forensics, profiler counters, the flight-recorder tail,
        recent events. Durable WEDGE_*.json (tempdir fallback). Never
        raises."""
        import sys
        import traceback

        with self._lock:
            self._capture_open = True
            self._bundle_seq += 1
            seq = self._bundle_seq
        doc: Dict = {
            "reason": (
                f"heartbeat exceeded {self.deadline_s}s deadline"
                if latency_ms is None
                else f"heartbeat classified WEDGED at {latency_ms:.1f}ms"
            ),
            "ts": time.time(),
            "pid": os.getpid(),
            "state": self.state,
            "last_latency_ms": self.last_latency_ms,
            "beats": self.beats,
            "heartbeat_error": repr(err) if err is not None else None,
        }
        try:
            names = {t.ident: t.name for t in threading.enumerate()}
            doc["threads"] = {
                f"{names.get(tid, '?')}({tid})": traceback.format_stack(frame)
                for tid, frame in sys._current_frames().items()
            }
        except Exception as e:
            doc["threads"] = repr(e)
        try:
            from risingwave_tpu.profiler import PROFILER, device_forensics

            doc["device"] = device_forensics()
            doc["profiler"] = PROFILER.snapshot()
        except Exception as e:
            doc["device"] = repr(e)
        doc["recorder_tail"] = RECORDER.snapshot_tail(64)
        try:
            from risingwave_tpu.event_log import EVENT_LOG

            doc["recent_events"] = EVENT_LOG.events(limit=20)
        except Exception:
            pass
        d = self.dir or RECORDER.dir or os.environ.get("RW_STALL_DIR", ".")
        path = os.path.join(d, f"WEDGE_{int(time.time())}_{seq}.json")
        try:
            # broad except + finally: the never-raises contract must
            # hold against serialization failures too (not just
            # OSError), and the capture window must ALWAYS close — a
            # leaked window would trip the orphan audits forever
            try:
                with open(path, "w") as f:
                    json.dump(doc, f, indent=1, default=str)
            except Exception:  # noqa: BLE001
                import tempfile

                path = os.path.join(
                    tempfile.gettempdir(), os.path.basename(path)
                )
                try:
                    with open(path, "w") as f:
                        json.dump(doc, f, indent=1, default=str)
                except Exception:  # noqa: BLE001
                    path = ""
        finally:
            with self._lock:
                self._capture_open = False
        REGISTRY.counter("wedge_dumps_total").inc()
        try:
            from risingwave_tpu.event_log import EVENT_LOG

            EVENT_LOG.record("wedge_dump", path=path, state=self.state)
        except Exception:
            pass
        return path


# ---------------------------------------------------------------------------
# process singletons + config/env plumbing
# ---------------------------------------------------------------------------

RECORDER = FlightRecorder()
SENTINEL = DeviceSentinel()


def from_env() -> None:
    """Honor RW_BLACKBOX_* on the process singletons (the operator's
    no-restart escape hatch; env wins over the [blackbox] config
    section, same precedence as RW_RETRY). No-op when nothing is set —
    runtimes call this on every construction path."""
    raw = os.environ.get("RW_BLACKBOX")
    if raw is not None and raw.strip().lower() in ("0", "off", "false"):
        RECORDER.configure(enabled=False)
    elif raw is not None:
        RECORDER.configure(enabled=True)
    d = os.environ.get("RW_BLACKBOX_DIR")
    if d:
        RECORDER.configure(
            dir=d,
            ring=_env_int("RW_BLACKBOX_RING", RECORDER.ring.maxlen),
            fsync_interval_s=env_float(
                "RW_BLACKBOX_FSYNC_S", RECORDER.fsync_interval_s
            ),
            segment_max_bytes=_env_int(
                "RW_BLACKBOX_SEGMENT_MAX", RECORDER.segment_max_bytes
            ),
        )
    if os.environ.get("RW_BLACKBOX_SENTINEL") == "1" and not SENTINEL.running:
        SENTINEL.start(
            interval_s=env_float(
                "RW_BLACKBOX_HEARTBEAT_S", SENTINEL.interval_s
            ),
            slow_ms=env_float("RW_BLACKBOX_SLOW_MS", SENTINEL.slow_ms),
            deadline_s=env_float(
                "RW_BLACKBOX_DEADLINE_S", SENTINEL.deadline_s
            ),
            dir=d or None,
        )


def configure(cfg) -> None:
    """Apply a config.BlackboxConfig ([blackbox] TOML section); env
    knobs win afterwards."""
    RECORDER.configure(
        enabled=getattr(cfg, "enabled", True),
        dir=getattr(cfg, "dir", "") or None,
        ring=getattr(cfg, "ring_barriers", None),
        fsync_interval_s=getattr(cfg, "fsync_interval_s", None),
        segment_max_bytes=getattr(cfg, "segment_max_bytes", None),
    )
    if getattr(cfg, "sentinel", False) and not SENTINEL.running:
        SENTINEL.start(
            interval_s=getattr(cfg, "sentinel_interval_s", None),
            slow_ms=getattr(cfg, "sentinel_slow_ms", None),
            deadline_s=getattr(cfg, "sentinel_deadline_s", None),
            dir=getattr(cfg, "dir", "") or None,
        )
    from_env()


# ---------------------------------------------------------------------------
# segment reader (the CLI's engine; no jax required)
# ---------------------------------------------------------------------------


def read_segment(path: str, last: Optional[int] = None) -> Dict:
    """Parse a black-box segment (file, or a directory holding
    ``BLACKBOX_*.jsonl``). Tolerates a torn final line (SIGKILL mid-
    write) and merges a rotated ``.old`` sibling. Returns::

        {"header": {...} | None, "records": [expanded...],
         "torn_lines": N, "monotonic": bool, "source": [paths...]}

    Records are expanded to long keys with per-barrier counter deltas
    derived from the cumulative fields."""
    paths: List[str] = []
    if os.path.isdir(path):
        segs = sorted(
            f
            for f in os.listdir(path)
            if f.startswith(FlightRecorder.SEGMENT_PREFIX)
            and f.endswith(".jsonl")
        )
        if not segs:
            raise FileNotFoundError(f"no BLACKBOX_*.jsonl under {path!r}")
        newest = max(
            segs, key=lambda f: os.path.getmtime(os.path.join(path, f))
        )
        path = os.path.join(path, newest)
    if os.path.exists(path + ".old"):
        paths.append(path + ".old")
    paths.append(path)
    header = None
    raw: List[Dict] = []  # barrier records + inline header markers
    torn = 0
    for p in paths:
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    torn += 1  # torn tail (SIGKILL mid-write): expected
                    continue
                if rec.get("k") in ("h", "b"):
                    raw.append(rec)
    records: List[Dict] = []
    prev_d = prev_hz = None
    prev_x: Optional[Dict] = None
    run_start = False  # first barrier after a NEW run's header
    last_run = None
    monotonic = True
    for rec in raw:
        if rec.get("k") == "h":
            # a header from a DIFFERENT run id is a run boundary
            # (append-mode segment + pid reuse stacks two runs in one
            # file): the new run's epochs restart and its cumulative
            # counters reset — neither is a broken timeline. A header
            # with the SAME run id is just a rotation inside one run:
            # deltas and monotonicity continue across it. Headers
            # without a run id (old segments) conservatively reset.
            new_run = rec.get("run") is None or rec.get("run") != last_run
            last_run = rec.get("run")
            header = rec
            if new_run:
                prev_d = prev_hz = None
                prev_x = None
                run_start = True
            continue
        out = {
            "ts": rec.get("ts"),
            "epoch": rec.get("ep"),
            "seq": rec.get("seq"),
            "checkpoint": rec.get("ck"),
            "wall_ms": rec.get("wall"),
            "stages_ms": rec.get("st", {}),
            "achieved_bw_frac": rec.get("bw"),
            "chunk_bytes": rec.get("cb"),
            "state_bytes": rec.get("sb"),
            "sentinel": rec.get("sen"),
        }
        if "dep" in rec:
            out["channel_depths"] = rec["dep"]
        if "fr" in rec:
            out["freshness"] = rec["fr"]
        if "bp" in rec:
            out["backpressure"] = rec["bp"]
        if "slow" in rec:
            out["slow_barrier"] = rec["slow"]
        if "msh" in rec:
            m = rec["msh"]
            out["mesh"] = {
                "n_shards": m.get("n"),
                "wall_ms": m.get("wall"),
                "attributed_ms": m.get("att"),
                "coverage_frac": m.get("cov"),
                "phases_ms": m.get("ph", {}),
                "shard_local_ms": m.get("loc", []),
                "exchange_rows": m.get("xm"),
                "skew": m.get("skew"),
            }
        if "mem" in rec:
            out["memory_stats"] = rec["mem"]
        if "mb" in rec:
            out["modeled_bytes"] = rec["mb"]
            out["padding_bytes_frac"] = rec.get("pf", 0.0)
        if "tel" in rec:
            out["telemetry"] = rec["tel"]
        if "d" in rec:
            out["dispatches_total"] = rec["d"]
            out["dispatches_delta"] = (
                rec["d"] - prev_d if prev_d is not None else rec["d"]
            )
            prev_d = rec["d"]
        if "x" in rec:
            out["transfers_total"] = rec["x"]
            if prev_x is not None:
                out["transfers_delta"] = {
                    k: rec["x"].get(k, 0) - prev_x.get(k, 0)
                    for k in rec["x"]
                }
            prev_x = rec["x"]
        if "hz" in rec:
            out["recompile_hazards_total"] = rec["hz"]
            out["recompile_hazards_delta"] = (
                rec["hz"] - prev_hz if prev_hz is not None else rec["hz"]
            )
            prev_hz = rec["hz"]
        if records and out["epoch"] is not None and not run_start:
            pe = records[-1]["epoch"]
            if pe is not None and out["epoch"] < pe:
                monotonic = False
        run_start = False
        records.append(out)
    if last is not None:
        # truncate AFTER deriving deltas/monotonicity over the whole
        # file: the first displayed record must carry its real
        # per-barrier delta, not the run's cumulative total
        records = records[-last:]
    return {
        "header": header,
        "records": records,
        "torn_lines": torn,
        "monotonic": monotonic,
        "source": paths,
    }


def records_to_trace_events(records: List[Dict]) -> List[tuple]:
    """Expanded reader records -> trace.render_chrome_trace event
    tuples: one slice per stage per barrier, laid out sequentially
    inside the barrier's wall window, carrying the epoch arg so the
    flow-event machinery links barriers across the timeline."""
    events: List[tuple] = []
    for rec in records:
        ts = rec.get("ts")
        wall_ms = rec.get("wall_ms") or 0.0
        if ts is None:
            continue
        t0 = ts - wall_ms / 1e3
        epoch = rec.get("epoch")
        events.append(
            (
                "barrier",
                1,
                t0,
                wall_ms / 1e3,
                {"epoch": epoch, "checkpoint": rec.get("checkpoint")},
            )
        )
        cursor = t0
        for stage, ms in (rec.get("stages_ms") or {}).items():
            events.append(
                ("stage." + stage, 2, cursor, ms / 1e3, {"epoch": epoch})
            )
            cursor += ms / 1e3
    return events
