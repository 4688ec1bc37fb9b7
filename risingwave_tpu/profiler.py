"""Dispatch-wall profiler — per-executor flame attribution for the
host dispatch path.

Reference: the reference gets per-executor latency/throughput metrics
from ``StreamingMetrics`` (executor/monitor/streaming_stats.rs) and
per-await-point attribution from await-tree + `tracing`; Grafana turns
those into the flame view an operator reads when an actor is slow.
Here the analogous question is sharper: BENCH stage data shows the
per-barrier ``dispatch`` stage at ~319ms p99 while ``device_step`` is
0.24ms — the host-side Python walk dominates and the device idles.
This module decomposes that wall:

- ``PROFILER.run(ex, phase, fn, *args)`` times every executor call in
  the dispatch walk into ``executor_ms{executor,fragment,phase}``
  (host-python time) and — in fence mode — ``executor_device_wait_ms``
  (explicit ``jax.block_until_ready`` on the call's outputs, so device
  wait is attributed to the executor that staged it, not smeared into
  the barrier fence).
- A kernel interposer wraps every module-level jitted kernel in
  ``risingwave_tpu.*`` with a counting proxy while profiling:
  ``device_dispatches_total{executor}`` / ``{kernel}`` count one
  Python-level jitted call ≈ one XLA program dispatch — the
  per-operator dispatch tax the fragment-fusion work (ROADMAP item 1)
  must drive toward one-per-barrier.
- Host<->device transfer accounting: ``jax.device_get``/``device_put``
  are wrapped to count ``host_device_transfers_total{direction}``
  ("log+count": implicit transfers stay visible via the armed
  ``jax.transfer_guard``; explicit ones are counted here).
- ``jax.profiler.trace`` capture windows: on-demand
  (``start_capture``) and auto-triggered when a barrier exceeds
  ``slow_barrier_ms`` — the next barrier is captured and a
  ``PROFILE_*`` JSON artifact (executor breakdown + dispatch/transfer
  counters + device forensics) is emitted. Capture windows are
  tracked so recovery can close them (``abort_captures``) — a partial
  recovery must never leave an orphaned profiler session holding the
  device.

Hot-path contract: everything above is gated on ONE ``PROFILER.enabled``
attribute check — profile-mode-off overhead is a single branch per
call site (<1% of a steady-state barrier, asserted in
tests/test_profiler.py).
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

from risingwave_tpu.metrics import REGISTRY

__all__ = ["PROFILER", "DispatchProfiler", "device_forensics"]


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except (TypeError, ValueError):
        return default


# ---------------------------------------------------------------------------
# kernel interposer — count Python-level jitted-kernel dispatches
# ---------------------------------------------------------------------------


class _KernelProxy:
    """Counting wrapper around one module-level jitted kernel. Calls
    delegate to the wrapped function unchanged; attribute access
    (``_cache_size``, ``lower`` — RecompileWatch / check_donation)
    passes through, so holders of a proxy see the original surface."""

    __slots__ = ("_fn", "_kernel", "_prof")

    def __init__(self, fn, kernel: str, prof: "DispatchProfiler"):
        self._fn = fn
        self._kernel = kernel
        self._prof = prof

    def __call__(self, *args, **kwargs):
        self._prof._count_dispatch(self._kernel)
        return self._fn(*args, **kwargs)

    def __getattr__(self, name):
        return getattr(self._fn, name)


def _is_jitted(obj) -> bool:
    """A module-level jit-compiled callable: the PjitFunction surface
    RecompileWatch already relies on (``_cache_size`` + ``lower``)."""
    return (
        callable(obj)
        and not isinstance(obj, _KernelProxy)
        and hasattr(obj, "_cache_size")
        and hasattr(obj, "lower")
    )


# ---------------------------------------------------------------------------
# the profiler
# ---------------------------------------------------------------------------


class DispatchProfiler:
    """Process-wide dispatch-wall profiler. Off by default; the hot
    paths check ``enabled`` once and skip everything below."""

    def __init__(self):
        self.enabled = False
        # fence mode: block_until_ready after each profiled call so
        # device wait is attributed per executor (profiling semantics —
        # values identical, async overlap serialized)
        self.fence = True
        # slow-barrier auto-capture threshold (ms); 0/None = off
        self.slow_barrier_ms: Optional[float] = None
        self.capture_dir: Optional[str] = None
        # arm jax.profiler.trace inside capture windows (heavy; the
        # JSON artifact is always written regardless)
        self.jax_trace = False
        self._tls = threading.local()
        self._lock = threading.Lock()
        # interposer bookkeeping: [(module, attr, original)]
        self._patched: List[Tuple[object, str, object]] = []
        self._jax_patched: List[Tuple[str, object]] = []
        # open jax.profiler/artifact capture windows (orphan audit
        # surface: recovery must leave this empty)
        self.active_captures: List[Dict] = []
        self._capture_armed = False
        # slow-barrier AUTO-captures attempted (manual captures do not
        # consume this budget; attempts count even when the artifact
        # write fails, so an unwritable dir cannot un-bound the loop)
        self._auto_captures = 0
        self.max_auto_captures = 3

    # -- lifecycle --------------------------------------------------------
    def enable(
        self,
        fence: bool = True,
        slow_barrier_ms: Optional[float] = None,
        capture_dir: Optional[str] = None,
        jax_trace: Optional[bool] = None,
    ) -> "DispatchProfiler":
        with self._lock:
            self.fence = fence
            if slow_barrier_ms is not None:
                self.slow_barrier_ms = slow_barrier_ms
            if capture_dir is not None:
                self.capture_dir = capture_dir
            if jax_trace is not None:
                self.jax_trace = jax_trace
            if not self.enabled:
                self._install_interposers()
                self.enabled = True
        return self

    def disable(self) -> None:
        with self._lock:
            if not self.enabled:
                return
            self.enabled = False
            self._remove_interposers()
        self.abort_captures()

    def reset(self) -> None:
        """Zero the profiler's metric surfaces (a bench child resets
        between queries so each query's breakdown stands alone)."""
        for h in ("executor_ms", "executor_device_wait_ms"):
            REGISTRY.histograms.pop(h, None)
        for c in (
            "device_dispatches_total",
            "device_dispatch_kernels_total",
            "host_device_transfers_total",
        ):
            REGISTRY.counters.pop(c, None)

    @classmethod
    def from_env(cls) -> "DispatchProfiler":
        """Honor RW_PROFILE / RW_PROFILE_FENCE / RW_PROFILE_SLOW_MS /
        RW_PROFILE_DIR / RW_PROFILE_JAX_TRACE on the process singleton.
        An EXPLICIT RW_PROFILE=0 disables even a config-enabled
        profiler — the env knob wins in both directions (the operator's
        no-restart escape hatch)."""
        raw = os.environ.get("RW_PROFILE")
        val = (raw or "0").strip().lower()
        if val in ("1", "on", "true"):
            PROFILER.enable(
                fence=os.environ.get("RW_PROFILE_FENCE", "1") != "0",
                slow_barrier_ms=_env_float("RW_PROFILE_SLOW_MS", 0) or None,
                capture_dir=os.environ.get("RW_PROFILE_DIR") or None,
                jax_trace=os.environ.get("RW_PROFILE_JAX_TRACE") == "1",
            )
        elif raw is not None and val in ("0", "off", "false"):
            PROFILER.disable()
        return PROFILER

    def configure(self, cfg) -> "DispatchProfiler":
        """Apply a config.ProfilerConfig (TOML ``[profiler]``); env
        knobs (from_env) win afterwards — the no-restart escape hatch."""
        if getattr(cfg, "enabled", False):
            self.enable(
                fence=cfg.fence,
                slow_barrier_ms=cfg.slow_barrier_capture_ms or None,
                capture_dir=cfg.capture_dir or None,
                jax_trace=cfg.jax_trace,
            )
        return self.from_env()

    # -- interposers ------------------------------------------------------
    def _install_interposers(self) -> None:
        import sys

        import jax

        for name, mod in list(sys.modules.items()):
            if not name.startswith("risingwave_tpu") or mod is None:
                continue
            for attr in list(vars(mod)):
                fn = vars(mod)[attr]
                if _is_jitted(fn):
                    setattr(mod, attr, _KernelProxy(fn, attr, self))
                    self._patched.append((mod, attr, fn))
        # explicit-transfer accounting (device_get/put call sites use
        # `jax.device_get(...)` attribute lookups, so a module-attr
        # wrapper intercepts them; implicit transfers are the armed
        # transfer_guard's job)
        prof = self

        def _get(x, _orig=jax.device_get):
            prof._count_transfer("d2h")
            return _orig(x)

        def _put(x, *a, _orig=jax.device_put, **kw):
            prof._count_transfer("h2d")
            return _orig(x, *a, **kw)

        self._jax_patched = [
            ("device_get", jax.device_get),
            ("device_put", jax.device_put),
        ]
        jax.device_get = _get
        jax.device_put = _put

    def _remove_interposers(self) -> None:
        import jax

        for mod, attr, fn in self._patched:
            # only restore if our proxy is still in place (a reload or
            # another patcher may have replaced it since)
            if isinstance(vars(mod).get(attr), _KernelProxy):
                setattr(mod, attr, fn)
        self._patched = []
        for attr, fn in self._jax_patched:
            setattr(jax, attr, fn)
        self._jax_patched = []

    # -- counters ---------------------------------------------------------
    def _count_dispatch(self, kernel: str) -> None:
        ex = getattr(self._tls, "executor", None) or "-"
        REGISTRY.counter("device_dispatches_total").inc(executor=ex)
        REGISTRY.counter("device_dispatch_kernels_total").inc(kernel=kernel)

    def _count_transfer(self, direction: str) -> None:
        REGISTRY.counter("host_device_transfers_total").inc(
            direction=direction
        )

    @staticmethod
    def _counter_snapshot(name: str) -> Dict:
        """Copy a counter's label->value map under the registry lock —
        forensic readers (stall dumps from watchdog threads) must not
        race a hot-path label insertion mid-iteration."""
        c = REGISTRY.counters.get(name)
        if c is None:
            return {}
        with REGISTRY._lock:
            return dict(c._values)

    def total_dispatches(self) -> float:
        return sum(self._counter_snapshot("device_dispatches_total").values())

    def dispatch_counts(self) -> Dict[str, float]:
        """{executor: dispatches} since enable/reset."""
        return {
            dict(k).get("executor", "-"): v
            for k, v in self._counter_snapshot(
                "device_dispatches_total"
            ).items()
        }

    def kernel_counts(self) -> Dict[str, float]:
        return {
            dict(k).get("kernel", "-"): v
            for k, v in self._counter_snapshot(
                "device_dispatch_kernels_total"
            ).items()
        }

    def transfer_counts(self) -> Dict[str, float]:
        out = {"d2h": 0.0, "h2d": 0.0}
        for k, v in self._counter_snapshot(
            "host_device_transfers_total"
        ).items():
            out[dict(k).get("direction", "-")] = v
        return out

    # -- the hot-path hook ------------------------------------------------
    def run(self, ex, phase: str, fn, *args, **kwargs):
        """Time one executor call. ``phase``: "apply" (data path),
        "flush" (on_barrier) — an apply inside a barrier window is
        relabeled "barrier_apply" so the dispatch-stage decomposition
        separates flush-propagation from ingest-side applies."""
        tls = self._tls
        if phase == "apply" and getattr(tls, "in_barrier", False):
            phase = "barrier_apply"
        name = type(ex).__name__
        frag = getattr(tls, "fragment", None) or "-"
        prev = getattr(tls, "executor", None)
        tls.executor = name
        t0 = time.perf_counter()
        try:
            out = fn(*args, **kwargs)
        finally:
            tls.executor = prev
        t1 = time.perf_counter()
        REGISTRY.histogram("executor_ms").observe(
            (t1 - t0) * 1e3, executor=name, fragment=frag, phase=phase
        )
        if self.fence:
            self._fence_outputs(out)
            wait_ms = (time.perf_counter() - t1) * 1e3
            REGISTRY.histogram("executor_device_wait_ms").observe(
                wait_ms, executor=name, fragment=frag, phase=phase
            )
        return out

    @staticmethod
    def _fence_outputs(out) -> None:
        """block_until_ready on whatever device values the call
        produced (chunk columns/valid lanes). Never raises — a fence
        failure must not change execution."""
        import jax

        try:
            leaves = []
            for c in out if isinstance(out, (list, tuple)) else (out,):
                cols = getattr(c, "columns", None)
                if cols:
                    leaves.extend(cols.values())
                v = getattr(c, "valid", None)
                if v is not None:
                    leaves.append(v)
            if leaves:
                jax.block_until_ready(leaves)
        except Exception:
            pass

    @contextmanager
    def attribute(self, label: str):
        """Attribute device dispatches inside the block to ``label``
        instead of the enclosing executor class — the fused per-barrier
        step reports as ONE ``device_dispatches_total{executor=
        "fused:<fragment>"}`` entry, so dispatches/barrier stays
        auditable after fusion collapses a chain into one program."""
        tls = self._tls
        prev = getattr(tls, "executor", None)
        tls.executor = label
        try:
            yield
        finally:
            tls.executor = prev

    def record_device_wait(
        self, ex, ms: float, phase: str = "finish", fragment: str = None
    ) -> None:
        """Attribute an explicit barrier-fence wait (staged-scalar
        materialization in ``Executor.finish_barrier``) to its executor."""
        REGISTRY.histogram("executor_device_wait_ms").observe(
            ms,
            executor=type(ex).__name__,
            fragment=fragment or getattr(self._tls, "fragment", None) or "-",
            phase=phase,
        )

    @contextmanager
    def barrier_window(self, fragment: Optional[str] = None):
        """Mark the enclosed calls as barrier-walk work (the
        ``dispatch`` stage): applies get relabeled ``barrier_apply``
        and fragment attribution is inherited by nested walks."""
        tls = self._tls
        prev_in, prev_frag = (
            getattr(tls, "in_barrier", False),
            getattr(tls, "fragment", None),
        )
        tls.in_barrier = True
        if fragment is not None:
            tls.fragment = fragment
        try:
            yield
        finally:
            tls.in_barrier, tls.fragment = prev_in, prev_frag

    # -- summaries --------------------------------------------------------
    def executor_summary(self) -> Dict[str, Dict]:
        """The BENCH-JSON surface: executor_ms + device-wait summaries
        (per executor/fragment/phase label set: p50/p99/count/sum)."""
        out: Dict[str, Dict] = {}
        for key, hname in (
            ("executor_ms", "executor_ms"),
            ("executor_device_wait_ms", "executor_device_wait_ms"),
        ):
            h = REGISTRY.histograms.get(hname)
            if h is not None:
                out[key] = h.summary()
        return out

    def top_executors(self, n: int = 5) -> List[Dict]:
        """Ranked dispatch-cost worklist: per executor, total host ms
        (barrier phases + applies) + device wait + dispatch count —
        the fusion worklist for ROADMAP open item 1."""
        totals: Dict[str, Dict[str, float]] = {}
        for hname, field in (
            ("executor_ms", "host_ms"),
            ("executor_device_wait_ms", "device_wait_ms"),
        ):
            h = REGISTRY.histograms.get(hname)
            if h is None:
                continue
            with REGISTRY._lock:
                sums = dict(h._sum)
            for labels, s in sums.items():
                ex = dict(labels).get("executor", "-")
                d = totals.setdefault(
                    ex, {"host_ms": 0.0, "device_wait_ms": 0.0}
                )
                d[field] += s
        for ex, cnt in self.dispatch_counts().items():
            totals.setdefault(
                ex, {"host_ms": 0.0, "device_wait_ms": 0.0}
            )["dispatches"] = cnt
        ranked = sorted(
            (
                {"executor": ex, **{k: round(v, 3) for k, v in d.items()}}
                for ex, d in totals.items()
            ),
            key=lambda d: d.get("host_ms", 0.0) + d.get("device_wait_ms", 0.0),
            reverse=True,
        )
        return ranked[:n]

    def snapshot(self) -> Dict:
        """Forensic view for stall dumps: live dispatch/transfer
        counters + open capture windows."""
        return {
            "enabled": self.enabled,
            "fence": self.fence,
            "dispatches": self.dispatch_counts(),
            "kernels": self.kernel_counts(),
            "transfers": self.transfer_counts(),
            "active_captures": [
                {k: v for k, v in c.items() if k != "session"}
                for c in self.active_captures
            ],
        }

    # -- capture windows --------------------------------------------------
    def _profile_dir(self) -> str:
        return (
            self.capture_dir
            or os.environ.get("RW_PROFILE_DIR")
            or os.environ.get("RW_STALL_DIR")
            or "."
        )

    def start_capture(self, tag: str = "manual") -> Dict:
        """Open a capture window: arms ``jax.profiler.trace`` when
        ``jax_trace`` is on, and registers the window so recovery can
        audit/close it. Returns the window record."""
        d = self._profile_dir()
        with self._lock:
            self._capture_seq = getattr(self, "_capture_seq", 0) + 1
            seq = self._capture_seq
        win = {
            "tag": tag,
            "seq": seq,  # same-second captures must not collide
            "t0": time.perf_counter(),
            "ts": time.time(),
            "dir": d,
            "session": None,
        }
        if self.jax_trace:
            try:
                import jax

                trace_dir = os.path.join(
                    d, f"PROFILE_TRACE_{tag}_{int(win['ts'])}_{seq}"
                )
                jax.profiler.start_trace(trace_dir)
                win["session"] = trace_dir
                win["trace_dir"] = trace_dir
            except Exception as e:  # capture must not break the barrier
                win["trace_error"] = repr(e)
        with self._lock:
            self.active_captures.append(win)
        return win

    def end_capture(self, win: Optional[Dict] = None, extra=None) -> str:
        """Close a capture window and write the ``PROFILE_*`` JSON
        artifact (executor breakdown + counters + device forensics).
        Returns the artifact path ("" if nothing was open)."""
        with self._lock:
            if win is None:
                win = self.active_captures.pop() if self.active_captures else None
            elif win in self.active_captures:
                self.active_captures.remove(win)
        if win is None:
            return ""
        if win.get("session") is not None:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception:
                pass
        doc = {
            "tag": win["tag"],
            "ts": win["ts"],
            "window_s": round(time.perf_counter() - win["t0"], 4),
            "jax_trace_dir": win.get("trace_dir"),
            **self.executor_summary(),
            "device_dispatches_total": self.dispatch_counts(),
            "dispatch_kernels": self.kernel_counts(),
            "transfers": self.transfer_counts(),
            "top_executors": self.top_executors(),
            "device": device_forensics(),
        }
        # provenance: a PROFILE artifact must say which engine wrote it
        # (stale-artifact confusion is mechanically detectable)
        try:
            from risingwave_tpu.provenance import stamp

            doc.update(stamp())
        except Exception:
            pass
        # fused-stage attribution: a jax_trace capture segments the ONE
        # fused program via its named scopes — parse the trace back
        # into the per-stage split (deviceprof leg 3)
        if win.get("trace_dir"):
            try:
                from risingwave_tpu.deviceprof import parse_fused_stages

                parsed = parse_fused_stages(win["trace_dir"])
                if parsed["stages_ms"]:
                    doc["fused_stage_ms"] = parsed
            except Exception:  # noqa: BLE001 — capture must still land
                pass
        # mesh attribution (ISSUE 18): a slow-barrier capture on a
        # sharded runtime names the hot shard and the exchange phase
        # split without a separate reader pass
        try:
            from risingwave_tpu.parallel.meshprof import MESHPROF

            if MESHPROF.enabled and MESHPROF.barriers:
                doc["mesh"] = MESHPROF.barriers[-1]
        except Exception:  # noqa: BLE001 — capture must still land
            pass
        if extra:
            doc.update(extra)
        path = os.path.join(
            win["dir"],
            f"PROFILE_{win['tag']}_{int(win['ts'])}_{win.get('seq', 0)}.json",
        )
        try:
            with open(path, "w") as f:
                json.dump(doc, f, indent=1, default=str)
        except OSError:
            return ""
        try:
            from risingwave_tpu.event_log import EVENT_LOG

            EVENT_LOG.record("profile_capture", tag=win["tag"], path=path)
        except Exception:
            pass
        REGISTRY.counter("profile_captures_total").inc()
        return path

    def abort_captures(self) -> int:
        """Close every open capture window WITHOUT writing artifacts —
        the recovery path's cleanup (an orphaned jax.profiler session
        would hold the device and poison the next capture). Returns the
        number of windows closed."""
        with self._lock:
            wins, self.active_captures = self.active_captures, []
            self._capture_armed = False
        for win in wins:
            if win.get("session") is not None:
                try:
                    import jax

                    jax.profiler.stop_trace()
                except Exception:
                    pass
        return len(wins)

    def observe_barrier(self, wall_ms: float, runtime=None) -> Optional[str]:
        """Slow-barrier auto-capture hook (called by the runtime after
        every barrier). A barrier over ``slow_barrier_ms`` immediately
        emits a PROFILE_* artifact (counters already cover the slow
        window) and a device-forensics stall dump; bounded by
        ``max_auto_captures`` per process so a persistently slow run
        does not flood the working dir."""
        thr = self.slow_barrier_ms
        if (
            not self.enabled
            or not thr
            or wall_ms < thr
            or self._auto_captures >= self.max_auto_captures
        ):
            return None
        # spend the budget on the ATTEMPT: a failing artifact write (or
        # the dump below) must not turn a persistently slow run into an
        # unbounded per-barrier forensic loop
        self._auto_captures += 1
        win = self.start_capture(tag="slow_barrier")
        path = self.end_capture(
            win, extra={"barrier_wall_ms": round(wall_ms, 3)}
        )
        try:
            from risingwave_tpu.epoch_trace import dump_stalls

            dump_stalls(
                f"slow barrier: {wall_ms:.1f}ms >= {thr}ms profile "
                "threshold",
                runtime=runtime,
            )
        except Exception:
            pass
        return path


def device_forensics() -> Dict:
    """Device-side evidence for stall dumps / profile artifacts: HBM
    stats, a live-array census, and the accounted per-table state —
    what a q7 wedge leaves behind instead of a dead process. Never
    raises; every section degrades independently."""
    out: Dict = {}
    try:
        import jax

        dev = jax.local_devices()[0]
        out["platform"] = dev.platform
        try:
            out["memory_stats"] = dev.memory_stats()  # None on CPU
        except Exception as e:
            out["memory_stats"] = repr(e)
        try:
            arrs = jax.live_arrays()
            census: Dict[str, Dict[str, float]] = {}
            total = 0
            for a in arrs:
                key = str(getattr(a, "dtype", "?"))
                nb = int(getattr(a, "nbytes", 0))
                total += nb
                d = census.setdefault(key, {"count": 0, "bytes": 0})
                d["count"] += 1
                d["bytes"] += nb
            out["live_arrays"] = {
                "total_count": len(arrs),
                "total_bytes": total,
                "by_dtype": census,
            }
        except Exception as e:
            out["live_arrays"] = repr(e)
    except Exception as e:
        out["error"] = repr(e)
    try:
        from risingwave_tpu import utils_heap

        # accounted device state by executor/state-table (top 20): the
        # fragment/state-table half of the live-array census
        out["state_tables"] = utils_heap.device_state()[:20]
    except Exception as e:
        out["state_tables"] = repr(e)
    try:
        out["profiler"] = {
            "dispatches": PROFILER.dispatch_counts(),
            "transfers": PROFILER.transfer_counts(),
            "active_captures": len(PROFILER.active_captures),
        }
    except Exception as e:  # degrade independently, like every section
        out["profiler"] = repr(e)
    try:
        # the sentinel's device classification is device evidence too:
        # a forensic artifact should say whether the heartbeat lane
        # considered the device ALIVE/SLOW/WEDGED when it was taken
        from risingwave_tpu.blackbox import SENTINEL

        out["sentinel"] = SENTINEL.snapshot()
    except Exception as e:
        out["sentinel"] = repr(e)
    return out


# the process singleton every hook consults
PROFILER = DispatchProfiler()
