"""Dispatch and transfer counters for the host dispatch path.

The program's one clock is ``trace.span``; this module only counts.

- A kernel interposer wraps every module-level jitted kernel in
  ``risingwave_tpu.*`` with a counting proxy while enabled:
  ``device_dispatches_total{executor}`` / ``{kernel}`` count one
  Python-level jitted call ≈ one XLA program dispatch — the
  per-operator dispatch tax fragment fusion drives toward
  one-per-barrier (held by ``tests/test_fused_step.py::
  test_dispatches_per_barrier``).
- Host<->device transfer accounting: ``jax.device_get``/``device_put``
  are wrapped to count ``host_device_transfers_total{direction}``
  ("log+count": implicit transfers stay visible via the armed
  ``jax.transfer_guard``; explicit ones are counted here).
- ``PROFILER.run(ex, fn, *args)`` and ``PROFILER.attribute(label)``
  name the executor (or fused fragment) that the dispatches inside
  are counted under.

Hot-path contract: everything above is gated on ONE ``PROFILER.enabled``
attribute check per call site.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, List, Tuple

from risingwave_tpu.metrics import REGISTRY

__all__ = ["PROFILER", "DispatchProfiler", "device_forensics"]


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
# the counters
# ---------------------------------------------------------------------------


class DispatchProfiler:
    """Process-wide dispatch/transfer counters. Off by default; the hot
    paths check ``enabled`` once and skip everything below."""

    def __init__(self):
        self.enabled = False
        self._tls = threading.local()
        self._lock = threading.Lock()
        # interposer bookkeeping: [(module, attr, original)]
        self._patched: List[Tuple[object, str, object]] = []
        self._jax_patched: List[Tuple[str, object]] = []

    # -- lifecycle --------------------------------------------------------
    def enable(self) -> "DispatchProfiler":
        with self._lock:
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

    def reset(self) -> None:
        """Zero the counters (a bench child resets between queries so
        each query's counts stand alone)."""
        for c in (
            "device_dispatches_total",
            "device_dispatch_kernels_total",
            "host_device_transfers_total",
        ):
            REGISTRY.counters.pop(c, None)

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

    # -- the hot-path hooks -----------------------------------------------
    def run(self, ex, fn, *args, **kwargs):
        """Call ``fn`` with the device dispatches inside it counted
        under ``ex``'s class name."""
        with self.attribute(type(ex).__name__):
            return fn(*args, **kwargs)

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

    def snapshot(self) -> Dict:
        """Forensic view for stall dumps: the live counters."""
        return {
            "enabled": self.enabled,
            "dispatches": self.dispatch_counts(),
            "kernels": self.kernel_counts(),
            "transfers": self.transfer_counts(),
        }


def device_forensics() -> Dict:
    """Device-side evidence for stall dumps: HBM stats, a live-array
    census, and the accounted per-table state —
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
