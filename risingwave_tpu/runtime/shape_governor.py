"""The recompile-storm governor.

Every device-visible shape is drawn from a declared lattice
(``array/lattice.py``, ``ops/bucketing.py``). :class:`ShapeGovernor` is
the runtime back-stop for when stability is violated anyway:
per-barrier ``SignatureWatch`` hazard deltas feed a budget
(``RW_FUSION_RECOMPILE_BUDGET``); exceeding it pins the offending
executor to its max (high-water) bucket — shrink disabled, capacity
immediately restored to the largest bucket it ever used — with a
``shape_governor`` event + metric, instead of letting the re-trace
storm pile onto the device.  A SLOW device heartbeat
(blackbox.DeviceSentinel) drops the budget to zero: the first hazard on
a struggling device throttles proactively, before WEDGED.
"""

from __future__ import annotations

import os
from typing import Dict, List, Optional

__all__ = ["ShapeGovernor"]


class ShapeGovernor:
    """Degrade gracefully instead of wedging when shape stability is
    violated at runtime anyway (a workload the static lattice proof
    did not anticipate, an unbucketed third-party executor, ...).

    Fed per barrier from :data:`analysis.jax_sanitizer.SIGNATURES`
    hazard deltas (one hazard = one post-warmup novel abstract input
    signature = one future re-trace). Cumulative hazards per executor
    CLASS above ``RW_FUSION_RECOMPILE_BUDGET`` pin every instance of
    that class to its max bucket via ``pin_max_bucket()``; while the
    device sentinel reports SLOW the budget is zero (first hazard
    throttles — proactive, before the heartbeat goes WEDGED). Each
    action lands in the meta event log (``shape_governor``) and in
    ``shape_governor_actions_total{executor,action,reason}``."""

    def __init__(
        self,
        budget: Optional[int] = None,
        enabled: Optional[bool] = None,
    ):
        if enabled is None:
            enabled = os.environ.get(
                "RW_SHAPE_GOVERNOR", "1"
            ).strip().lower() not in ("0", "off", "false")
        self.enabled = enabled
        self._budget = budget
        self.hazards: Dict[str, int] = {}
        self.pinned: Dict[str, Dict] = {}

    @property
    def budget(self) -> int:
        if self._budget is not None:
            return self._budget
        from risingwave_tpu.analysis.shape_domain import recompile_budget

        return recompile_budget()

    # -- the per-barrier hook --------------------------------------------
    def observe_barrier(self, target) -> List[str]:
        """Consume this barrier's hazard deltas and act. ``target`` is
        a runtime (``.executors()``) or a plain executor list. Costs
        one attribute check per barrier while SignatureWatch is
        disarmed. Returns the executor class names pinned this call."""
        if not self.enabled:
            return []
        from risingwave_tpu.analysis.jax_sanitizer import SIGNATURES

        if not SIGNATURES.enabled:
            return []
        deltas = SIGNATURES.take_hazard_deltas()
        if not deltas:
            return []
        slow = self._device_slow()
        budget = 0 if slow else self.budget
        acted = []
        for name, n in deltas.items():
            total = self.hazards.get(name, 0) + n
            self.hazards[name] = total
            if name in self.pinned:
                continue
            if total > budget:
                self._pin(
                    target,
                    name,
                    total,
                    "slow_device" if slow else "budget_exceeded",
                )
                acted.append(name)
        return acted

    @staticmethod
    def _device_slow() -> bool:
        try:
            from risingwave_tpu import blackbox

            return blackbox.SENTINEL.state == blackbox.SLOW
        except Exception:  # noqa: BLE001 — the governor never faults
            return False

    def _pin(self, target, name: str, hazards: int, reason: str) -> None:
        from risingwave_tpu.event_log import EVENT_LOG
        from risingwave_tpu.metrics import REGISTRY

        executors = (
            target.executors() if hasattr(target, "executors") else target
        )
        pins: List[Dict] = []
        for ex in executors or ():
            if type(ex).__name__ != name:
                continue
            fn = getattr(ex, "pin_max_bucket", None)
            if fn is None:
                continue
            try:
                pins.append(fn())
            except Exception:  # noqa: BLE001 — throttling is best-effort
                continue
        action = "pin_max_bucket" if pins else "no_pin_surface"
        self.pinned[name] = {
            "hazards": hazards,
            "reason": reason,
            "action": action,
            "pins": pins,
        }
        REGISTRY.counter("shape_governor_actions_total").inc(
            executor=name, action=action, reason=reason
        )
        REGISTRY.gauge("shape_governor_pinned").set(float(len(self.pinned)))
        EVENT_LOG.record(
            "shape_governor",
            executor=name,
            action=action,
            reason=reason,
            hazards=hazards,
            budget=self.budget,
        )

    def snapshot(self) -> Dict:
        return {
            "enabled": self.enabled,
            "budget": self.budget,
            "hazards": dict(self.hazards),
            "pinned": {
                k: {kk: vv for kk, vv in v.items() if kk != "pins"}
                for k, v in self.pinned.items()
            },
        }

    def reset(self) -> None:
        self.hazards.clear()
        self.pinned.clear()
