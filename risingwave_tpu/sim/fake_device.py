"""Wedgeable fake device — the sim tier for the q7 wedge class.

The real failure (BENCH_TPU_2/3) is a TPU whose dispatch queue stops
answering: every ``block_until_ready`` blocks forever, actors hang
mid-kernel, and the process sits until an outer alarm murders it. A
CPU test cannot wedge XLA on demand, so this module fakes the device
at the two seams the blackbox sentinel and the runtime actually
observe:

- :class:`WedgeableDevice` — a heartbeat target for
  ``DeviceSentinel(heartbeat_fn=dev.heartbeat)``: healthy beats return
  immediately (optionally with injected latency for SLOW coverage);
  ``wedge()`` makes every subsequent beat block until ``unwedge()``,
  exactly like a dispatch into a dead device queue.
- :class:`BlockingKernelExecutor` — a pass-through executor whose
  apply/flush blocks on the same device object when wedged: planted in
  a pipeline it wedges the barrier mid-walk (serial) or mid-actor
  (graph), reproducing "stuck actors" evidence in stall dumps while
  the sentinel independently classifies WEDGED.

``unwedge()`` releases every blocked thread (heartbeat workers, actor
threads) so tests can always tear down cleanly — a real wedge has no
such mercy, which is the point of testing against a fake one.
"""

from __future__ import annotations

import threading
from typing import Optional

from risingwave_tpu.executors.base import Executor

__all__ = ["WedgeableDevice", "BlockingKernelExecutor"]


class WedgeableDevice:
    """A fake device queue with an on/off wedge switch."""

    def __init__(self, latency_s: float = 0.0):
        self.latency_s = latency_s
        self._wedged = threading.Event()
        self._release = threading.Event()
        self._release.set()
        self.beats = 0
        self.blocked = 0
        self._lock = threading.Lock()

    @property
    def wedged(self) -> bool:
        return self._wedged.is_set()

    def wedge(self) -> None:
        """Every call into the device from now on blocks (the dead
        dispatch queue) until :meth:`unwedge`."""
        self._release.clear()
        self._wedged.set()

    def unwedge(self) -> None:
        """Revive the device: blocked callers return, new calls pass."""
        self._wedged.clear()
        self._release.set()

    def call(self, timeout: Optional[float] = None) -> None:
        """One device call: returns after ``latency_s`` when healthy,
        blocks while wedged. ``timeout`` bounds the block for callers
        that must not hang forever even in tests."""
        with self._lock:
            self.beats += 1
        if self.latency_s:
            # injected latency models a SLOW device
            threading.Event().wait(self.latency_s)
        if self._wedged.is_set():
            with self._lock:
                self.blocked += 1
            self._release.wait(timeout=timeout)

    # the DeviceSentinel heartbeat_fn surface
    def heartbeat(self) -> None:
        self.call()


class BlockingKernelExecutor(Executor):
    """Pass-through executor whose hot path dispatches into a
    :class:`WedgeableDevice` — the "blocking fake kernel". Plant it in
    a chain and ``device.wedge()`` to freeze the pipeline exactly where
    a wedged XLA program would: mid-apply or at the barrier flush."""

    def __init__(
        self, device: WedgeableDevice, block_on: str = "barrier"
    ):
        if block_on not in ("apply", "barrier", "both"):
            raise ValueError(f"unknown block site {block_on!r}")
        self.device = device
        self.block_on = block_on

    def apply(self, chunk):
        if self.block_on in ("apply", "both"):
            self.device.call()
        return [chunk]

    def on_barrier(self, b):
        if self.block_on in ("barrier", "both"):
            self.device.call()
        return []
