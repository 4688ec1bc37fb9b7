"""Device-level observability for the fused engine: compiled-artifact
roofline, in-program telemetry lanes, and fused-stage attribution.

PR 10 collapsed the barrier into ONE donated device program — and
blinded every host-side observability layer doing it: the dispatch
profiler sees one opaque ``fused:<frag>`` dispatch, and
``achieved_bw_frac`` was computed from host byte guesses
(state-delta + chunk bytes) that describe nothing the donated program
actually reads or writes. This module is the "compile the whole query,
then explain where the cycles went" discipline (PAPERS.md: TiLT) with
the padded-lane waste accounting of region-based SIMD state layouts —
three legs:

1. **Compiled-artifact roofline** (:func:`analyze_lowerable`,
   ``DEVICEPROF.ensure_program``): every fused program / compiled
   kernel bucket is introspected once via
   ``jit(...).lower(...).compile()`` cost+memory analysis — FLOPs,
   bytes accessed, argument/output/temp HBM footprint, compile ms,
   executable size — feeding ``compile_ms{fn,bucket}`` /
   ``executable_bytes{fn,bucket}`` / ``fused_modeled_bytes{fragment}``
   gauges and the per-barrier MODELED bytes figure EpochTrace now
   prefers over the legacy host guess. Bytes decompose into useful vs
   padding using the bucketing layer's live/capacity lane accounting
   (the telemetry lanes provide live counts at zero extra reads).
2. **In-program telemetry** (``DEVICEPROF.note_telemetry``): the fused
   step packs device-computed per-member stats (rows applied, dirty
   groups, state occupancy, masked-lane fill) into the SAME staged
   scalar lane the barrier already reads — per-member visibility at
   zero extra dispatches and zero new host syncs. The wrapper calls
   ``note_telemetry`` when the pack materializes; gauges:
   ``fused_member_rows{fragment,member}``,
   ``fused_dirty_groups{fragment}``, ``fused_lane_fill_frac{fragment}``,
   ``padding_bytes_frac{fragment}``.
3. **Fused-stage attribution** (:func:`parse_fused_stages`): the fused
   program's apply / flush / mv_write / scalar_pack phases are wrapped
   in ``jax.named_scope`` (runtime/fused_step), so a ``jax.profiler``
   trace segments the ONE program; the offline parser aggregates
   trace events back into ``fused_stage_ms{fragment,stage}`` — the
   68/31-style stage split that ranked the original fusion worklist,
   now measured INSIDE the device program.

Hot-path contract (profiler.py/blackbox.py discipline): program
analysis is gated on ONE ``DEVICEPROF.enabled`` check (an analysis is
one extra AOT compile per distinct program bucket — arm it in bench /
tests, not in the steady serve path); telemetry recording always rides
(a dict build + a few gauge sets per barrier, no device dispatch of
its own). Module import stays jax-free so
reader CLIs can parse traces from plain processes; jax is imported
lazily inside the analysis path only.
"""

from __future__ import annotations

import gzip
import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

from risingwave_tpu.metrics import REGISTRY

__all__ = [
    "DEVICEPROF",
    "DeviceProfiler",
    "FUSED_STAGES",
    "analyze_lowerable",
    "analyze_nexmark",
    "parse_fused_stages",
]

# the fused program's named-scope stages (runtime/fused_step wraps its
# phases in jax.named_scope("fused/<stage>"))
FUSED_STAGES = ("apply", "flush", "mv_write", "scalar_pack")


# ---------------------------------------------------------------------------
# leg 1: compiled-artifact introspection
# ---------------------------------------------------------------------------


def analyze_lowerable(lower_fn: Callable[[], object]) -> Dict:
    """Compile the thunk's lowered program and introspect the
    executable: XLA cost analysis (flops, bytes accessed) + memory
    analysis (argument/output/temp footprint, generated code size),
    with the wall-clock compile cost. ``lower_fn`` returns a
    ``jax.stages.Lowered`` (e.g. ``jitted.lower(*abstract_args)``) —
    abstract ShapeDtypeStruct args keep this allocation-free."""
    t0 = time.perf_counter()
    compiled = lower_fn().compile()
    compile_ms = (time.perf_counter() - t0) * 1e3
    cost = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    out = {
        "compile_ms": round(compile_ms, 3),
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": int(cost.get("bytes accessed", 0.0)),
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "executable_bytes": int(ma.generated_code_size_in_bytes),
    }
    # the modeled-bytes-per-dispatch figure: XLA's own accounting of
    # what the program touches; fall back to the HBM footprint when a
    # backend reports no per-op byte costs
    if not out["bytes_accessed"]:
        out["bytes_accessed"] = (
            out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
        )
    return out


class DeviceProfiler:
    """Process-wide device-program observability registry.

    ``programs`` maps (fn, bucket) -> one compiled-artifact analysis;
    ``fragments`` maps fragment label -> the modeled bytes of the
    LAST program bucket that fragment dispatched (the per-barrier
    modeled-traffic figure); ``telemetry`` holds each fragment's last
    packed-lane telemetry. All reads are cheap snapshots for
    bench / dashboard / flight-recorder consumers."""

    def __init__(self):
        self.enabled = False  # gates ANALYSIS (one AOT compile/bucket)
        self._lock = threading.Lock()
        self.programs: Dict[tuple, Dict] = {}
        self.fragments: Dict[str, Dict] = {}
        self.telemetry: Dict[str, Dict] = {}
        self.analysis_errors = 0
        # analyses DEFERRED off the dispatch path: ensure_program only
        # enqueues the (abstract) lower thunk; the AOT compile runs at
        # flush_analyses() — report/roofline time, never inside a
        # measured barrier (a bucket's analysis compile is ~1-2s on
        # CPU, minutes cold on the TPU)
        self._pending: Dict[tuple, tuple] = {}
        # fragments that DISPATCHED since the last consumed barrier:
        # the model only attributes a fragment's modeled bytes to
        # barriers it actually ran in (an idle barrier must model ZERO
        # traffic, or achieved_bw_frac reports phantom bandwidth)
        self._dispatched: set = set()

    # -- lifecycle --------------------------------------------------------
    def arm(self) -> "DeviceProfiler":
        self.enabled = True
        return self

    def disarm(self) -> None:
        self.enabled = False

    def reset(self) -> None:
        with self._lock:
            self.programs.clear()
            self.fragments.clear()
            self.telemetry.clear()
            self._pending.clear()
            self._dispatched.clear()
            self.analysis_errors = 0

    def from_env(self) -> "DeviceProfiler":
        """RW_DEVICEPROF=1 arms analysis; =0 disarms (env wins in both
        directions)."""
        raw = os.environ.get("RW_DEVICEPROF")
        if raw is None:
            return self
        if raw.strip().lower() in ("1", "on", "true"):
            self.arm()
        elif raw.strip().lower() in ("0", "off", "false"):
            self.disarm()
        return self

    def on_recovery(self) -> None:
        """Recovery/rebuild hook (runtime calls this next to
        SENTINEL.abort_capture): drop per-barrier telemetry — the
        rebuilt fragments' first barrier repopulates it — but KEEP the
        program analyses: recovery re-fuses into the same compiled
        programs (FusedPlan is value-hashable), so the roofline stays
        valid. Deviceprof opens no device sessions, so there is no
        capture window to orphan."""
        with self._lock:
            self.telemetry.clear()

    # -- leg 1: program analysis ------------------------------------------
    def ensure_program(
        self,
        fn: str,
        bucket: str,
        lower_fn: Callable[[], object],
        fragment: Optional[str] = None,
    ) -> Optional[Dict]:
        """Register one (fn, bucket) program for analysis. The hot
        path only ENQUEUES the abstract lower thunk (a dict insert);
        the AOT compile runs at :meth:`flush_analyses` — report /
        roofline time, never inside a measured barrier. With
        ``fragment``, the bucket's modeled bytes become that
        fragment's per-barrier traffic figure once analyzed. Never
        raises — observability must not change execution."""
        if not self.enabled:
            return None
        key = (fn, bucket)
        with self._lock:
            if fragment is not None:
                self._dispatched.add(fragment)
            hit = self.programs.get(key)
            if hit is None:
                if key not in self._pending:
                    self._pending[key] = (lower_fn, fragment)
                elif fragment is not None:
                    self._pending[key] = (self._pending[key][0], fragment)
                return None
        if fragment is not None and "error" not in hit:
            self._bind_fragment(key, fragment, hit)
        return hit

    def flush_analyses(self) -> int:
        """Run every deferred program analysis (one AOT lower+compile
        per new bucket — ~1-2s on CPU, minutes cold on the TPU).
        Call OUTSIDE timed windows: bench calls it before collecting
        roofline fields, the perf gate before checking, report() for
        ad-hoc reads. Returns the number of programs analyzed."""
        if not self.enabled:
            return 0
        with self._lock:
            pending, self._pending = dict(self._pending), {}
        done = 0
        for key, (lower_fn, fragment) in pending.items():
            fn, bucket = key
            try:
                hit = analyze_lowerable(lower_fn)
                done += 1
            except Exception as e:  # noqa: BLE001 — never fault
                hit = {"error": repr(e)}
                self.analysis_errors += 1
            with self._lock:
                self.programs[key] = hit
            if "error" not in hit:
                REGISTRY.gauge("compile_ms").set(
                    hit["compile_ms"], fn=fn, bucket=bucket
                )
                REGISTRY.gauge("executable_bytes").set(
                    float(hit["executable_bytes"]), fn=fn, bucket=bucket
                )
                if fragment is not None:
                    self._bind_fragment(key, fragment, hit)
        return done

    def _bind_fragment(self, key: tuple, fragment: str, hit: Dict) -> None:
        with self._lock:
            self.fragments[fragment] = {
                "fn": key[0],
                "bucket": key[1],
                "modeled_bytes": hit["bytes_accessed"],
            }
        REGISTRY.gauge("fused_modeled_bytes").set(
            float(hit["bytes_accessed"]), fragment=fragment
        )

    # -- leg 2: telemetry -------------------------------------------------
    def note_telemetry(self, fragment: str, tel: Dict) -> None:
        """One fragment-barrier's packed-lane telemetry (host side of
        the staged read the barrier already pays — zero device IO
        here). ``tel`` carries ``member_rows`` ({member: rows}),
        ``dirty_groups``, ``occupancy`` ({member: live}),
        ``lanes_total``/``rows_in`` (masked-lane fill), and
        ``padding_bytes_frac`` (live-vs-capacity over the members'
        state lanes, weighted by state bytes)."""
        with self._lock:
            self.telemetry[fragment] = tel
            self._dispatched.add(fragment)
        g = REGISTRY.gauge("fused_member_rows")
        for member, rows in (tel.get("member_rows") or {}).items():
            g.set(float(rows), fragment=fragment, member=member)
        if "dirty_groups" in tel:
            REGISTRY.gauge("fused_dirty_groups").set(
                float(tel["dirty_groups"]), fragment=fragment
            )
        if "lane_fill_frac" in tel:
            REGISTRY.gauge("fused_lane_fill_frac").set(
                tel["lane_fill_frac"], fragment=fragment
            )
        if "padding_bytes_frac" in tel:
            REGISTRY.gauge("padding_bytes_frac").set(
                tel["padding_bytes_frac"], fragment=fragment
            )

    # -- read surfaces ----------------------------------------------------
    def barrier_model(self, consume: bool = False) -> Dict:
        """The per-barrier modeled-traffic figure EpochTrace consumes:
        modeled bytes across the fused fragments that DISPATCHED since
        the last consumed barrier (each fragment's last analyzed
        bucket) and the telemetry-weighted padding fraction. An idle
        barrier — no fused dispatch since the last consume — models
        ZERO traffic, never phantom bandwidth. ``consume`` clears the
        dispatched set (once per barrier, by its trace)."""
        with self._lock:
            active = set(self._dispatched)
            if consume:
                self._dispatched.clear()
            frags = {
                k: dict(v)
                for k, v in self.fragments.items()
                if k in active
            }
            tel = {k: dict(v) for k, v in self.telemetry.items()}
        total = 0
        weighted = 0.0
        for name, f in frags.items():
            mb = int(f.get("modeled_bytes", 0))
            total += mb
            frac = (tel.get(name) or {}).get("padding_bytes_frac")
            if frac is not None:
                weighted += mb * float(frac)
        return {
            "modeled_bytes": total,
            "padding_frac": round(weighted / total, 6) if total else 0.0,
            "fragments": sorted(active),
        }

    def steady_model(self) -> Dict:
        """The steady-state per-barrier figure over ALL analyzed
        fragments (each one's last bucket), regardless of the
        per-barrier dispatch gating — what bench/gate report AFTER a
        run whose barriers already consumed their own models."""
        with self._lock:
            frags = {k: dict(v) for k, v in self.fragments.items()}
            tel = {k: dict(v) for k, v in self.telemetry.items()}
        mb = sum(int(f.get("modeled_bytes", 0)) for f in frags.values())
        weighted = sum(
            int(f.get("modeled_bytes", 0))
            * float((tel.get(n) or {}).get("padding_bytes_frac", 0.0))
            for n, f in frags.items()
        )
        return {
            "modeled_bytes": mb,
            "padding_frac": round(weighted / mb, 6) if mb else 0.0,
        }

    def consume_barrier(self) -> Dict:
        """One barrier's deviceprof tail, CONSUMED: the modeled-bytes
        model plus the compact telemetry of the fragments that ran in
        it (flight-recorder ``tel`` shape). EpochTrace.finalize calls
        this once per barrier; fragments that did not dispatch again
        stop appearing — a post-mortem timeline never shows a fragment
        applying rows on barriers it never ran in."""
        model = self.barrier_model(consume=True)
        with self._lock:
            tel = {
                frag: {
                    "rows": t.get("member_rows", {}),
                    "dirty": t.get("dirty_groups", 0),
                }
                for frag, t in self.telemetry.items()
                if frag in model["fragments"]
            }
        return {
            "modeled_bytes": model["modeled_bytes"],
            "padding_frac": model["padding_frac"],
            "tel": tel,
        }

    def report(self, flush: bool = True) -> Dict:
        """The BENCH-JSON / dashboard surface. ``flush`` runs deferred
        analyses first (one AOT compile per pending bucket) — callers
        on a live serving path (the dashboard HTTP handler) pass
        ``flush=False`` and render the snapshot as-is: a page load
        must never compile, least of all concurrently with a measured
        barrier loop."""
        if flush:
            self.flush_analyses()
        with self._lock:
            programs = {
                f"{fn}|{bucket}": dict(v)
                for (fn, bucket), v in self.programs.items()
            }
            fragments = {k: dict(v) for k, v in self.fragments.items()}
            telemetry = {k: dict(v) for k, v in self.telemetry.items()}
        return {
            "enabled": self.enabled,
            "programs": programs,
            "fragments": fragments,
            "telemetry": telemetry,
            "analysis_errors": self.analysis_errors,
        }

    def roofline_fields(
        self, prefix: str, n_barriers: int, seconds: float
    ) -> Dict:
        """Bench integration: the ``{q}_roofline`` artifact block —
        modeled bytes per barrier from the compiled executable,
        decomposed into useful vs padding traffic, with the measured
        achieved/useful bandwidth fractions over the run."""
        from risingwave_tpu.epoch_trace import hbm_peak_gbps

        rep = self.report()  # flushes deferred compiles OUTSIDE the timer
        model = self.steady_model()
        mb = model["modeled_bytes"]
        frac = model["padding_frac"]
        useful = int(mb * (1.0 - frac))
        peak = hbm_peak_gbps()
        total_bytes = mb * max(n_barriers, 0)
        bw = total_bytes / seconds / 1e9 if seconds > 0 else 0.0
        achieved = bw / peak if peak else 0.0
        return {
            f"{prefix}_roofline": {
                "modeled_bytes_per_barrier": mb,
                "useful_bytes_per_barrier": useful,
                "padding_bytes_per_barrier": mb - useful,
                "padding_bytes_frac": frac,
                "achieved_bw_frac": round(achieved, 6),
                "useful_bw_frac": round(achieved * (1.0 - frac), 6),
                "hbm_peak_gbps": peak,
                "programs": rep["programs"],
                "telemetry": rep["telemetry"],
            }
        }


# ---------------------------------------------------------------------------
# leg 3: fused-stage attribution (offline trace-event parser)
# ---------------------------------------------------------------------------


def _iter_trace_events(source):
    """Yield chrome-trace event dicts from a dict, a JSON(.gz) file,
    or a directory (scanned recursively for ``*.trace.json.gz`` — the
    jax.profiler TensorBoard layout — and plain ``*.json`` traces)."""
    if isinstance(source, dict):
        yield from source.get("traceEvents", [])
        return
    if os.path.isdir(source):
        hits: List[str] = []
        for dirpath, _dirs, files in os.walk(source):
            for f in files:
                if f.endswith(".trace.json.gz") or f.endswith(
                    ".trace.json"
                ):
                    hits.append(os.path.join(dirpath, f))
        for p in sorted(hits):
            yield from _iter_trace_events(p)
        return
    opener = gzip.open if source.endswith(".gz") else open
    with opener(source, "rt") as f:
        doc = json.load(f)
    yield from (doc or {}).get("traceEvents", [])


def parse_fused_stages(source, record: bool = True) -> Dict:
    """Aggregate a jax profiler capture's trace events back into the
    fused program's stage split.

    Any complete ("X") or begin/end ("B"/"E") event whose name carries
    a ``fused/<stage>`` scope contributes its duration to that stage;
    ``fused:<label>`` host annotations (the wrapper's TraceAnnotation
    around the dispatch) attribute the whole parse to a fragment when
    exactly one label appears, else "-". Durations land in
    ``fused_stage_ms{fragment,stage}`` (unless ``record=False``) and
    come back as ``{"fragment": ..., "stages_ms": {stage: ms}}`` —
    the device-side 68/31 split, per stage, per capture."""
    stages: Dict[str, float] = {}
    labels = set()
    open_begins: Dict[tuple, float] = {}
    for ev in _iter_trace_events(source):
        name = str(ev.get("name", ""))
        if "fused:" in name:
            labels.add(name.split("fused:", 1)[1].split("/")[0].strip())
            continue
        if "fused/" not in name:
            continue
        stage = name.split("fused/", 1)[1].split("/")[0].strip()
        if not stage:
            continue
        ph = ev.get("ph", "X")
        if ph == "X":
            stages[stage] = stages.get(stage, 0.0) + float(
                ev.get("dur", 0.0)
            )
        elif ph == "B":
            open_begins[(stage, ev.get("tid"), ev.get("pid"))] = float(
                ev.get("ts", 0.0)
            )
        elif ph == "E":
            t0 = open_begins.pop(
                (stage, ev.get("tid"), ev.get("pid")), None
            )
            if t0 is not None:
                stages[stage] = stages.get(stage, 0.0) + (
                    float(ev.get("ts", 0.0)) - t0
                )
    fragment = labels.pop() if len(labels) == 1 else "-"
    stages_ms = {k: round(v / 1e3, 4) for k, v in stages.items()}
    if record:
        h = REGISTRY.histogram("fused_stage_ms")
        for stage, ms in stages_ms.items():
            h.observe(ms, fragment=fragment, stage=stage)
    return {"fragment": fragment, "stages_ms": stages_ms}


# ---------------------------------------------------------------------------
# corpus analyzer: per-executor compiled-step roofline on CPU
# ---------------------------------------------------------------------------


def analyze_executor_steps(
    chain: Sequence[object],
    spec,
    fragment: str,
    capacities: Sequence[int] = (),
) -> Dict[str, Dict]:
    """Cost/memory-analyze every traceable executor step in one chain
    over its abstract input spec (the fusion analyzer's schema
    threading, reused): ``{executor_label: analysis}``. Executors
    without a trace contract (or with an unknown upstream schema) are
    skipped — the analyzer never guesses a lane width."""
    import jax

    from risingwave_tpu.analysis.fusion_analyzer import (
        _contract,
        _lint_info,
        _thread_spec,
    )

    out: Dict[str, Dict] = {}
    for idx, ex in enumerate(chain):
        contract = _contract(ex)
        step = (contract or {}).get("trace_step")
        if step is not None and spec is not None:
            caps = tuple(capacities) or (spec.capacity,)
            for cap in caps:
                label = f"{fragment}/{idx}:{type(ex).__name__}@{cap}"
                abstract = spec.with_capacity(cap).abstract()
                try:
                    out[label] = analyze_lowerable(
                        lambda s=step, a=abstract: jax.jit(s).lower(a)
                    )
                except Exception as e:  # noqa: BLE001 — skip, don't fault
                    out[label] = {"error": repr(e)}
        spec = _thread_spec(spec, ex, _lint_info(ex))
    return out


def analyze_nexmark(
    only: Optional[str] = None, capacity: int = 1 << 8
) -> Dict[str, Dict[str, Dict]]:
    """Compiled-step roofline over the Nexmark corpus twins (q5/q7/q8
    plus the planner-built q5u): per executor, per fragment section,
    the XLA cost/memory analysis of its traceable step — runs whole on
    CPU (abstract lowering, no device state touched). The test-suite
    sanity bar: every query yields at least one analysis with nonzero
    flops and bytes accessed."""
    from risingwave_tpu.analysis.fusion_analyzer import _spec_from_schema
    from risingwave_tpu.analysis.lint import (
        NEXMARK_SOURCE_SCHEMAS,
        build_nexmark_corpus,
    )
    from risingwave_tpu.runtime.fragmenter import fragment_chains

    names = (only,) if only else ("q5", "q5u", "q7", "q8")
    built = {}
    for q in names:
        if q == "q5u":
            # the unified path's plan (SQL -> planner), same engine
            from risingwave_tpu.connectors.nexmark import BID_SCHEMA
            from risingwave_tpu.sql import Catalog, StreamPlanner

            built["q5u"] = StreamPlanner(
                Catalog({"bid": BID_SCHEMA}), capacity=capacity
            ).plan(
                "CREATE MATERIALIZED VIEW q5 AS SELECT auction, "
                "window_start, count(*) AS num FROM HOP(bid, date_time, "
                "INTERVAL '2' SECOND, INTERVAL '10' SECOND) "
                "GROUP BY auction, window_start"
            )
        else:
            built.update(build_nexmark_corpus(capacity=capacity, only=q))
    out: Dict[str, Dict[str, Dict]] = {}
    for q, planned in built.items():
        schemas = NEXMARK_SOURCE_SCHEMAS.get(
            "q5" if q == "q5u" else q, {}
        )
        rep: Dict[str, Dict] = {}
        for frag, sections in fragment_chains(planned.pipeline).items():
            for side, chain in sections.items():
                if not chain:
                    continue
                spec = _spec_from_schema(
                    schemas.get(side)
                    if side in ("single", "left", "right", "both")
                    else None
                )
                rep.update(
                    analyze_executor_steps(chain, spec, f"{frag}/{side}")
                )
        out[q] = rep
    return out


# the process singleton (profiler.PROFILER / blackbox.RECORDER idiom)
DEVICEPROF = DeviceProfiler()
