"""End-to-end freshness tracking + backpressure attribution.

Reference: TiLT's time-centric view (PAPERS.md) — the latency a *user*
experiences is source-ingest -> visible-snapshot, not the processing
cost of any one stage — and the reference's `rw_ddl_progress` /
`rw_fragments` introspection surfaces, which serve system state off the
same versioned store the queries read.

The tracker is the host-side spine of ISSUE 16's tentpole: every
barrier, `runtime._end_trace` (after `arrangements.publish` makes the
epoch's snapshot readable) folds three wall-clock deltas per MV into
windowed histograms and a latest-row table:

- ``mv_freshness_ms{mv}``      barrier-open -> snapshot-visible (the
                               commit->visible SLO the BASELINE north
                               star is written in);
- ``source_to_visible_ms{mv}`` first ingest of the epoch -> visible;
- ``event_time_lag_ms{mv}``    wall clock vs the fragment's
                               low-watermark frontier (event time).

Everything here is host timestamps and dict updates: ZERO added device
dispatches (``tests/test_fused_step.py::test_dispatches_per_barrier
[q5-fused-freshness]``); its host cost is the ``bookkeeping.freshness``
span.

``attribute_backpressure`` is the companion verdict: per-fragment
dispatch walls (EpochTrace.fragment_ms) + per-channel depth and
oldest-pending-epoch AGE (PermitChannel.oldest_pending) folded into one
``backpressure_fragment`` name per barrier — a slow barrier names the
actor that caused it instead of a number.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Dict, List, Optional

from risingwave_tpu.metrics import REGISTRY


class FreshnessTracker:
    """Latest-row + bounded-history store behind ``rw_mv_freshness``,
    the dashboard's freshness table, and dump_stalls."""

    HISTORY = 1024

    def __init__(self):
        self._lock = threading.Lock()
        self._latest: Dict[str, dict] = {}
        self._history: deque = deque(maxlen=self.HISTORY)

    def observe(
        self,
        mv: str,
        epoch: int,
        checkpoint: bool = False,
        commit_to_visible_ms: Optional[float] = None,
        source_to_visible_ms: Optional[float] = None,
        event_time_lag_ms: Optional[float] = None,
    ) -> dict:
        row = {
            "mv": mv,
            "epoch": int(epoch),
            "checkpoint": bool(checkpoint),
            "commit_to_visible_ms": commit_to_visible_ms,
            "source_to_visible_ms": source_to_visible_ms,
            "event_time_lag_ms": event_time_lag_ms,
            "visible_at": time.time(),
        }
        if commit_to_visible_ms is not None:
            REGISTRY.histogram("mv_freshness_ms").observe(
                commit_to_visible_ms, mv=mv
            )
        if source_to_visible_ms is not None:
            REGISTRY.histogram("source_to_visible_ms").observe(
                source_to_visible_ms, mv=mv
            )
        if event_time_lag_ms is not None:
            REGISTRY.histogram("event_time_lag_ms").observe(
                event_time_lag_ms, mv=mv
            )
            REGISTRY.gauge("event_time_lag_ms_last").set(
                event_time_lag_ms, mv=mv
            )
        with self._lock:
            prev = self._latest.get(mv)
            row["barriers"] = (prev["barriers"] + 1) if prev else 1
            self._latest[mv] = row
            self._history.append(row)
        return row

    def snapshot(self) -> List[dict]:
        """Latest row per MV, sorted by name (rw_mv_freshness scan)."""
        with self._lock:
            return [dict(self._latest[m]) for m in sorted(self._latest)]

    def history(self, limit: int = 256) -> List[dict]:
        with self._lock:
            rows = list(self._history)
        return rows[-limit:]

    def drop(self, mv: str) -> None:
        with self._lock:
            self._latest.pop(mv, None)

    def reset(self) -> None:
        with self._lock:
            self._latest.clear()
            self._history.clear()


# the process-default tracker (like metrics.REGISTRY / event_log.EVENT_LOG)
FRESHNESS = FreshnessTracker()


def attribute_backpressure(runtime, trace) -> dict:
    """Fold the barrier's per-fragment dispatch walls + channel
    depth/oldest-pending-age into one bottleneck verdict.

    Returns ``{"fragment": name|None, "ms": float, "detail": {...}}``
    and records ``backpressure_ms{fragment}`` + per-fragment channel
    gauges. Score = fragment dispatch wall + oldest pending age across
    its input channels: a fragment is the bottleneck either because its
    own dispatch dominated the barrier or because work has been sitting
    unconsumed in front of it since an old epoch.
    """
    detail: Dict[str, dict] = {}
    for name, p in getattr(runtime, "fragments", {}).items():
        ent = {
            "dispatch_ms": round(
                getattr(trace, "fragment_ms", {}).get(name, 0.0), 3
            )
        }
        g = getattr(p, "graph", None)
        if g is not None:
            depth = 0
            oldest_age_ms = 0.0
            oldest_epoch = None
            try:
                for a in g.actors:
                    for _port, ch in a.inputs:
                        op = ch.oldest_pending()
                        if op is None:
                            continue
                        depth += len(ch)
                        age = op["age_ms"]
                        if age > oldest_age_ms:
                            oldest_age_ms = age
                            oldest_epoch = op.get("epoch")
            except Exception:
                pass  # attribution never faults a barrier
            ent["channel_depth"] = depth
            ent["oldest_age_ms"] = round(oldest_age_ms, 3)
            if oldest_epoch is not None:
                ent["oldest_epoch"] = oldest_epoch
            REGISTRY.gauge("channel_depth").set(float(depth), fragment=name)
        detail[name] = ent

    def score(e: dict) -> float:
        return e.get("dispatch_ms", 0.0) + e.get("oldest_age_ms", 0.0)

    frag = max(detail, key=lambda n: score(detail[n])) if detail else None
    ms = score(detail[frag]) if frag else 0.0
    if frag is not None:
        REGISTRY.histogram("backpressure_ms").observe(ms, fragment=frag)
    return {"fragment": frag, "ms": round(ms, 3), "detail": detail}
