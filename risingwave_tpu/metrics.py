"""Metrics kernel — counters/histograms with labels.

Reference: src/common/metrics/ (prometheus registry + label-guarded
metrics, guarded_metrics.rs) and the per-executor ``StreamingMetrics``
struct (src/stream/src/executor/monitor/streaming_stats.rs:44).

v0: an in-process registry with the prometheus text exposition format
(``render()``), no HTTP endpoint yet. Counters are plain floats on the
host — metric updates must NEVER force a device sync, so executors
record shapes/capacities and host-side timings only.
"""

from __future__ import annotations

import threading
from collections import defaultdict, deque
from typing import Dict, Tuple

import numpy as np

_Labels = Tuple[Tuple[str, str], ...]


def _labels(kv: Dict[str, str]) -> _Labels:
    return tuple(sorted(kv.items()))


class Counter:
    def __init__(self, registry, name: str):
        self.name = name
        self._values: Dict[_Labels, float] = defaultdict(float)
        self._lock = registry._lock

    def inc(self, value: float = 1.0, **labels: str) -> None:
        with self._lock:
            self._values[_labels(labels)] += value

    def get(self, **labels: str) -> float:
        return self._values.get(_labels(labels), 0.0)

    def total(self) -> float:
        """Sum across every label set, snapshotted under the registry
        lock (safe against a hot-path label insertion mid-iteration) —
        the public surface forensic readers use instead of touching
        ``_values`` directly."""
        with self._lock:
            return sum(self._values.values())


class Histogram:
    """Windowed histogram: quantiles come from a bounded per-label-set
    reservoir (deque of the most recent ``window`` observations) while
    ``_count``/``_sum`` stay exact monotonic totals — a long-running
    node's memory no longer grows with every observation (previously an
    unbounded list per label set)."""

    DEFAULT_WINDOW = 4096

    def __init__(self, registry, name: str, window: int = None):
        self.name = name
        self.window = window or self.DEFAULT_WINDOW
        self._obs: Dict[_Labels, deque] = {}
        self._count: Dict[_Labels, int] = defaultdict(int)
        self._sum: Dict[_Labels, float] = defaultdict(float)
        self._lock = registry._lock

    def observe(self, value: float, **labels: str) -> None:
        key = _labels(labels)
        with self._lock:
            dq = self._obs.get(key)
            if dq is None:
                dq = self._obs[key] = deque(maxlen=self.window)
            dq.append(value)
            self._count[key] += 1
            self._sum[key] += value

    def percentile(self, q: float, **labels: str) -> float:
        obs = self._obs.get(_labels(labels))
        return float(np.percentile(obs, q)) if obs else 0.0

    def count(self, **labels: str) -> int:
        return self._count.get(_labels(labels), 0)

    def summary(self) -> Dict[str, Dict[str, float]]:
        """{label-string: {p50, p99, count, sum}} across every label
        set — the bench's per-stage breakdown surface."""
        with self._lock:
            keys = list(self._obs)
        out = {}
        for key in keys:
            obs = list(self._obs.get(key, ()))
            if not obs:
                continue
            lbl = ",".join(f"{k}={v}" for k, v in key) or "-"
            out[lbl] = {
                "p50": round(float(np.percentile(obs, 50)), 3),
                "p99": round(float(np.percentile(obs, 99)), 3),
                "count": self._count.get(key, len(obs)),
                "sum": round(self._sum.get(key, 0.0), 3),
            }
        return out


class Gauge:
    def __init__(self, registry, name: str):
        self.name = name
        self._values: Dict[_Labels, float] = defaultdict(float)
        self._lock = registry._lock

    def set(self, value: float, **labels: str) -> None:
        with self._lock:
            self._values[_labels(labels)] = value

    def get(self, **labels: str) -> float:
        return self._values.get(_labels(labels), 0.0)


class MetricsRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self.counters: Dict[str, Counter] = {}
        self.histograms: Dict[str, Histogram] = {}
        self.gauges: Dict[str, Gauge] = {}
        self._server = None

    def counter(self, name: str) -> Counter:
        if name not in self.counters:
            self.counters[name] = Counter(self, name)
        return self.counters[name]

    def histogram(self, name: str) -> Histogram:
        if name not in self.histograms:
            self.histograms[name] = Histogram(self, name)
        return self.histograms[name]

    def gauge(self, name: str) -> Gauge:
        if name not in self.gauges:
            self.gauges[name] = Gauge(self, name)
        return self.gauges[name]

    def render(self) -> str:
        """Prometheus text exposition."""
        lines = []
        for name, c in sorted(self.counters.items()):
            lines.append(f"# TYPE {name} counter")
            for labels, v in sorted(c._values.items()):
                lbl = ",".join(f'{k}="{val}"' for k, val in labels)
                lines.append(f"{name}{{{lbl}}} {v}" if lbl else f"{name} {v}")
        for name, g in sorted(self.gauges.items()):
            lines.append(f"# TYPE {name} gauge")
            for labels, v in sorted(g._values.items()):
                lbl = ",".join(f'{k}="{val}"' for k, val in labels)
                lines.append(f"{name}{{{lbl}}} {v}" if lbl else f"{name} {v}")
        for name, h in sorted(self.histograms.items()):
            lines.append(f"# TYPE {name} summary")
            for labels, obs in sorted(h._obs.items()):
                lbl = ",".join(f'{k}="{val}"' for k, val in labels)
                base = f"{name}{{{lbl}}}" if lbl else name
                win = list(obs)  # quantiles over the bounded window
                for q in (0.5, 0.9, 0.99):
                    ql = (
                        f'{{{lbl},quantile="{q}"}}'
                        if lbl
                        else f'{{quantile="{q}"}}'
                    )
                    lines.append(
                        f"{name}{ql} {float(np.percentile(win, q * 100))}"
                    )
                # count/sum are exact totals (monotonic), not windowed
                lines.append(f"{base}_count {h._count.get(labels, len(win))}")
                lines.append(f"{base}_sum {h._sum.get(labels, sum(win))}")
        return "\n".join(lines) + "\n"

    # the scrape-surface name (ISSUE 16 satellite): freshness and
    # backpressure gauges read as plain prometheus text without custom
    # JSON parsing — same exposition render() always produced
    render_prometheus = render

    def render_dashboard(self) -> str:
        """One self-contained HTML ops page (the reference ships a
        React dashboard from the meta node; this collapses the same
        surfaces — fragments, state sizes, barrier health, recovery
        counters — into a static render per request)."""
        from html import escape

        from risingwave_tpu import utils_heap

        rows = []
        rt = utils_heap._runtime_ref() if utils_heap._runtime_ref else None
        frag_rows = ""
        if rt is not None:
            for name in sorted(getattr(rt, "fragments", {})):
                subs = [
                    f"{d}({s})"
                    for d, s in getattr(rt, "_subs", {}).get(name, ())
                ]
                frag_rows += (
                    f"<tr><td>{escape(name)}</td>"
                    f"<td>{escape(', '.join(subs) or '-')}</td></tr>"
                )
            stats = [
                ("epoch", getattr(rt, "_epoch", 0)),
                (
                    "committed epoch",
                    rt.mgr.max_committed_epoch if rt.mgr else 0,
                ),
                ("auto recoveries", getattr(rt, "auto_recoveries", 0)),
                (
                    "partial recoveries",
                    getattr(rt, "partial_recoveries", 0),
                ),
                ("p99 barrier ms", round(rt.p99_barrier_ms(), 2)),
                (
                    "p99 checkpoint sync ms",
                    round(rt.p99_checkpoint_sync_ms(), 2),
                ),
            ]
            rows += [
                f"<tr><td>{escape(str(k))}</td><td>{v}</td></tr>"
                for k, v in stats
            ]
        state_rows = "".join(
            f"<tr><td>{escape(d['executor'])}</td>"
            f"<td>{escape(str(d['table_id']))}</td>"
            f"<td style='text-align:right'>{d['bytes']:,}</td></tr>"
            for d in utils_heap.device_state()[:40]
        )
        # meta event log tail (reference: the dashboard's event log view)
        from risingwave_tpu.event_log import EVENT_LOG

        event_rows = "".join(
            f"<tr><td>{e['seq']}</td><td>{escape(e['kind'])}</td>"
            f"<td>{escape(', '.join(f'{k}={v}' for k, v in e.items() if k not in ('seq', 'ts', 'kind')))}</td></tr>"
            for e in EVENT_LOG.events(limit=25)
        )
        # per-stage barrier attribution (EpochTrace -> barrier_stage_ms)
        stage_rows = ""
        h = self.histograms.get("barrier_stage_ms")
        if h is not None:
            stage_rows = "".join(
                f"<tr><td>{escape(lbl)}</td><td>{s['p50']}</td>"
                f"<td>{s['p99']}</td><td>{s['count']}</td></tr>"
                for lbl, s in sorted(h.summary().items())
            )
        # black box + device sentinel (blackbox.py): the device-health
        # classification and flight-recorder state — the first look
        # when a barrier stalls or the device goes quiet
        bb_rows = ""
        try:
            from risingwave_tpu.blackbox import RECORDER, SENTINEL

            sen = SENTINEL.snapshot()
            rec = RECORDER.snapshot()
            for k, v in (
                ("device state", sen["state"]),
                (
                    "last heartbeat ms",
                    sen["last_latency_ms"]
                    and round(sen["last_latency_ms"], 1),
                ),
                ("heartbeats", sen["beats"]),
                ("wedges", sen["wedges"]),
                ("sentinel running", sen["running"]),
                ("recorder records", rec["records"]),
                ("recorder segment", rec["segment"] or "-"),
            ):
                bb_rows += (
                    f"<tr><td>{escape(str(k))}</td>"
                    f"<td>{escape(str(v))}</td></tr>"
                )
        except Exception:
            bb_rows = ""
        # device roofline + fused telemetry (deviceprof.py): what the
        # compiled programs MODEL (bytes/flops/compile cost per bucket)
        # and what the telemetry lanes MEASURED last barrier — the
        # inside-the-fused-program view PR 10 took away from the
        # per-executor tables above
        dp_rows = tel_rows = ""
        try:
            from risingwave_tpu.deviceprof import DEVICEPROF

            # snapshot WITHOUT flushing: a dashboard page load must
            # never run deferred AOT compiles (seconds on CPU, tens of
            # seconds to minutes on the TPU, possibly mid-measurement)
            rep = DEVICEPROF.report(flush=False)
            for key, p in sorted(rep["programs"].items()):
                if "error" in p:
                    continue
                dp_rows += (
                    f"<tr><td>{escape(key)}</td>"
                    f"<td>{p['compile_ms']}</td>"
                    f"<td style='text-align:right'>{p['bytes_accessed']:,}</td>"
                    f"<td style='text-align:right'>{p['flops']:,.0f}</td>"
                    f"<td style='text-align:right'>{p['temp_bytes']:,}</td></tr>"
                )
            for frag, t in sorted(rep["telemetry"].items()):
                tel_rows += (
                    f"<tr><td>{escape(frag)}</td>"
                    f"<td>{t.get('rows_in', 0)}</td>"
                    f"<td>{t.get('dirty_groups', 0)}</td>"
                    f"<td>{t.get('mv_rows', 0)}</td>"
                    f"<td>{t.get('lane_fill_frac', 0.0)}</td>"
                    f"<td>{t.get('padding_bytes_frac', 0.0)}</td></tr>"
                )
        except Exception:
            dp_rows = tel_rows = ""
        # resilience health: retry pressure + breaker states + degraded
        # mode (resilience.py) — the operator's first look when the
        # store flakes
        res_rows = ""
        for cname in (
            "retries_total",
            "retry_giveups_total",
            "retry_success_after_retry_total",
            "store_fast_fails_total",
            "breaker_transitions_total",
            "degraded_entries_total",
            "degraded_epochs_spilled_total",
            "degraded_epochs_replayed_total",
            "actor_failures_total",
            "partial_recoveries_total",
            "partial_recovery_deferrals_total",
            "replay_buffer_overflows_total",
        ):
            c = self.counters.get(cname)
            if c is None:
                continue
            for labels, v in sorted(c._values.items()):
                lbl = ",".join(f"{k}={val}" for k, val in labels) or "-"
                res_rows += (
                    f"<tr><td>{escape(cname)}</td>"
                    f"<td>{escape(lbl)}</td><td>{v:g}</td></tr>"
                )
        br = self.gauges.get("breaker_state")
        if br is not None:
            names = {0.0: "closed", 1.0: "half_open", 2.0: "open"}
            for labels, v in sorted(br._values.items()):
                lbl = ",".join(f"{k}={val}" for k, val in labels) or "-"
                res_rows += (
                    f"<tr><td>breaker_state</td><td>{escape(lbl)}</td>"
                    f"<td>{escape(names.get(v, str(v)))}</td></tr>"
                )
        # per-MV freshness (freshness.py): the latest commit->visible /
        # source->visible / event-time-lag per MV — the SLO the BASELINE
        # north star is written in
        fresh_rows = ""
        try:
            from risingwave_tpu.freshness import FRESHNESS

            def _f(v):
                return "-" if v is None else f"{v:.1f}"

            fresh_rows = "".join(
                f"<tr><td>{escape(r['mv'])}</td><td>{r['epoch']}</td>"
                f"<td>{_f(r['commit_to_visible_ms'])}</td>"
                f"<td>{_f(r['source_to_visible_ms'])}</td>"
                f"<td>{_f(r['event_time_lag_ms'])}</td>"
                f"<td>{r['barriers']}</td></tr>"
                for r in FRESHNESS.snapshot()
            )
        except Exception:
            fresh_rows = ""
        # memory & overload (runtime/memory_governor.py): the device-
        # state ledger vs budget, the overload ladder's rung and the
        # per-fragment admission credits — the operator's first look
        # when sources start lagging on purpose
        mem_rows = ov_rows = ""
        try:
            gov = getattr(rt, "memory_governor", None) if rt else None
            if gov is not None and gov.enabled:
                snap = gov.snapshot()
                lad, adm = snap["ladder"], snap["admission"]
                for k, v in (
                    ("overload state", lad["state"]),
                    ("pressure score", lad["score"]),
                    ("ladder flaps", lad["flaps"]),
                    ("ledger bytes", f"{snap['ledger_bytes']:,}"),
                    (
                        "budget bytes",
                        f"{snap['budget_bytes']:,}"
                        if snap["budget_bytes"] is not None
                        else "-",
                    ),
                    (
                        "headroom bytes",
                        f"{snap['headroom_bytes']:,}"
                        if snap["headroom_bytes"] is not None
                        else "-",
                    ),
                    ("modeled bytes", f"{snap['modeled_bytes']:,}"),
                    ("sampled bytes", snap["sampled_bytes"] or "-"),
                    ("grow vetoes", snap["vetoes"]),
                    ("spills", snap["spills"]),
                    ("parked polls", adm["parked_polls"]),
                ):
                    mem_rows += (
                        f"<tr><td>{escape(str(k))}</td>"
                        f"<td>{escape(str(v))}</td></tr>"
                    )
                ov_rows = "".join(
                    f"<tr><td>{escape(frag)}</td><td>{c}</td></tr>"
                    for frag, c in sorted(adm["credits"].items())
                )
        except Exception:
            mem_rows = ov_rows = ""
        # backpressure attribution: per-fragment verdict histogram +
        # live channel depths (which fragment slow barriers name)
        bp_rows = ""
        hbp = self.histograms.get("backpressure_ms")
        if hbp is not None:
            depth = self.gauges.get("channel_depth")
            for lbl, s in sorted(hbp.summary().items()):
                frag = lbl.split("=", 1)[-1]
                d = depth.get(fragment=frag) if depth is not None else 0.0
                bp_rows += (
                    f"<tr><td>{escape(frag)}</td><td>{s['p50']}</td>"
                    f"<td>{s['p99']}</td><td>{s['count']}</td>"
                    f"<td>{d:g}</td></tr>"
                )
        # mesh observability (ISSUE 18): per-shard attribution + skew
        # + the (src,dst) exchange matrix for the multi-chip path
        mesh_rows = mesh_xm_rows = ""
        try:
            from risingwave_tpu.parallel.meshprof import MESHPROF

            if MESHPROF.enabled:
                msnap = MESHPROF.table_snapshot()
                lb = msnap.get("last_barrier") or {}
                cov = self.gauges.get("mesh_coverage_frac")
                skg = self.gauges.get("shard_skew_frac")
                for k, v in (
                    ("shards", lb.get("n_shards", "-")),
                    (
                        "last coverage",
                        f"{cov.get():.1%}" if cov is not None else "-",
                    ),
                    (
                        "skew frac (max/mean-1)",
                        f"{skg.get():.3f}" if skg is not None else "-",
                    ),
                    (
                        "last skew verdict",
                        lb.get("skew") or "-",
                    ),
                    (
                        "calibration ms",
                        msnap.get("calibration_ms", 0.0),
                    ),
                    ("errors", msnap.get("errors", 0)),
                ):
                    mesh_rows += (
                        f"<tr><td>{escape(str(k))}</td>"
                        f"<td>{escape(str(v))}</td></tr>"
                    )
                xm = (msnap.get("exchange") or {}).get("rows")
                if xm:
                    n = len(xm)
                    hdr_cells = "".join(
                        f"<th>dst{j}</th>" for j in range(n)
                    )
                    mesh_xm_rows = (
                        f"<tr><th>rows</th>{hdr_cells}</tr>"
                    )
                    for src, row in enumerate(xm):
                        cells = "".join(
                            f"<td style='text-align:right'>{int(v):,}</td>"
                            for v in row
                        )
                        mesh_xm_rows += (
                            f"<tr><td>src{src}</td>{cells}</tr>"
                        )
        except Exception:
            mesh_rows = mesh_xm_rows = ""
        return f"""<!doctype html><html><head><title>risingwave_tpu</title>
<style>body{{font-family:monospace;margin:2em}}table{{border-collapse:collapse;margin:1em 0}}
td,th{{border:1px solid #999;padding:2px 8px}}h2{{margin-top:1.5em}}</style></head><body>
<h1>risingwave_tpu dashboard</h1>
<h2>runtime</h2><table>{''.join(rows) or '<tr><td>no runtime attached</td></tr>'}</table>
<h2>fragments &rarr; subscribers</h2><table>{frag_rows or '<tr><td>none</td></tr>'}</table>
<h2>device state (top 40)</h2><table><tr><th>executor</th><th>table</th><th>bytes</th></tr>{state_rows}</table>
<h2>barrier stages (ms)</h2><table><tr><th>stage</th><th>p50</th><th>p99</th><th>n</th></tr>{stage_rows or '<tr><td>no barriers traced</td></tr>'}</table>
<h2>black box &amp; device sentinel</h2><table>{bb_rows or '<tr><td>blackbox unavailable</td></tr>'}</table>
<h2>device roofline (compiled programs)</h2><table><tr><th>program|bucket</th><th>compile ms</th><th>bytes accessed</th><th>flops</th><th>temp bytes</th></tr>{dp_rows or '<tr><td>deviceprof not armed (RW_DEVICEPROF=1)</td></tr>'}</table>
<h2>fused telemetry (last barrier)</h2><table><tr><th>fragment</th><th>rows in</th><th>dirty groups</th><th>mv rows</th><th>lane fill</th><th>padding frac</th></tr>{tel_rows or '<tr><td>no fused barriers yet</td></tr>'}</table>
<h2>freshness (per MV)</h2><table><tr><th>mv</th><th>epoch</th><th>commit&rarr;visible ms</th><th>source&rarr;visible ms</th><th>event-time lag ms</th><th>barriers</th></tr>{fresh_rows or '<tr><td>no published barriers yet</td></tr>'}</table>
<h2>backpressure attribution</h2><table><tr><th>fragment</th><th>p50 ms</th><th>p99 ms</th><th>verdicts</th><th>channel depth</th></tr>{bp_rows or '<tr><td>no verdicts yet</td></tr>'}</table>
<h2>memory &amp; overload</h2><table>{mem_rows or '<tr><td>governor not armed (RW_HBM_BUDGET_BYTES / RW_OVERLOAD_LADDER)</td></tr>'}</table>
<table><tr><th>fragment</th><th>admission credit</th></tr>{ov_rows or '<tr><td>no credit windows derived</td></tr>'}</table>
<h2>mesh (multi-chip)</h2><table>{mesh_rows or '<tr><td>mesh profiler not armed (MESHPROF.enable())</td></tr>'}</table>
<table>{mesh_xm_rows or '<tr><td>no exchange traffic recorded</td></tr>'}</table>
<h2>resilience</h2><table><tr><th>metric</th><th>labels</th><th>value</th></tr>{res_rows or '<tr><td>no retries / breakers yet</td></tr>'}</table>
<h2>events (last 25)</h2><table><tr><th>#</th><th>kind</th><th>detail</th></tr>{event_rows or '<tr><td>none</td></tr>'}</table>
<p><a href="/metrics">/metrics</a> (prometheus text, <code>render_prometheus()</code>) &middot; <a href="/heap">/heap</a> &middot; <a href="/events">/events</a></p>
</body></html>"""

    def serve(self, port: int = 0) -> int:
        """Expose ``/metrics`` over HTTP (the prometheus scrape surface
        the reference serves from each node). Returns the bound port."""
        import http.server

        registry = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (stdlib API name)
                path = self.path.rstrip("/")
                if path == "/heap":
                    # heap profile: device-state accounting + host
                    # tracemalloc top (utils_heap; jeprof analogue)
                    from risingwave_tpu import utils_heap

                    body = utils_heap.render().encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "text/plain")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if path == "/events":
                    # meta event log (reference: risectl meta event-log
                    # / the dashboard's event view) as JSON
                    from risingwave_tpu.event_log import EVENT_LOG

                    body = EVENT_LOG.to_json().encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if path in ("", "/dashboard"):
                    # the ops dashboard (reference: the meta dashboard
                    # UI, collapsed to one self-contained page)
                    body = registry.render_dashboard().encode()
                    self.send_response(200)
                    self.send_header(
                        "Content-Type", "text/html; charset=utf-8"
                    )
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                if path != "/metrics":
                    self.send_response(404)
                    self.end_headers()
                    return
                body = registry.render().encode()
                self.send_response(200)
                self.send_header(
                    "Content-Type", "text/plain; version=0.0.4"
                )
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # quiet
                pass

        self._server = http.server.ThreadingHTTPServer(
            ("127.0.0.1", port), Handler
        )
        t = threading.Thread(target=self._server.serve_forever, daemon=True)
        t.start()
        return self._server.server_address[1]

    def shutdown(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server = None


# the process-default registry (reference: GLOBAL_METRICS_REGISTRY)
REGISTRY = MetricsRegistry()


def render_prometheus() -> str:
    """Module-level scrape shorthand: the default registry's prometheus
    text exposition (``metrics.render_prometheus()``)."""
    return REGISTRY.render_prometheus()


def record_recompiles(deltas: Dict[str, int]) -> None:
    """Per-kernel compiled-fn cache misses (analysis.RecompileWatch
    deltas) -> ``recompiles_total{fn=...}``. Steady-state epochs must
    keep this flat: every increment is a re-trace of a fused step —
    up to minutes each on the TPU, the recompile-storm failure mode the
    fixed-capacity chunk design exists to prevent."""
    c = REGISTRY.counter("recompiles_total")
    for fn, d in deltas.items():
        if d:
            c.inc(d, fn=fn)
