#!/usr/bin/env python
"""Nexmark q5 counts (q5-lite)/q7/q8 throughput bench (the BASELINE.md
headline path; "q5" below is the hop-window bid count core, not the whole
"hot items" query).

Measures the streaming pipelines in events/sec on the TPU (``--smoke``
runs a small tier on the CPU backend), against vectorized single-core
numpy "CPU actors" doing identical work (our stand-in for the
reference's per-actor CPU throughput; the reference publishes no
absolute numbers, BASELINE.md). Each query runs in its own child
process; the parent stays off JAX, so children hold the chip one at a
time.

Prints ONE JSON line:
  {"metric": "...", "value": N, "unit": "...", "vs_baseline": N, ...}
and exits non-zero when the device asked for is missing, a child fails
or times out, or any ``*_correct`` field is false.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def cpu_actor_baseline(host_chunks, window_ms, slide_ms):
    """Single-threaded numpy actor: hop-expand + dict groupby-count per
    chunk, barrier no-op (state already materialized). Vectorized with
    np.unique — a strong CPU actor, not a per-row straw man."""
    import numpy as np

    factor = window_ms // slide_ms
    counts = {}
    t0 = time.perf_counter()
    n_rows = 0
    for cols in host_chunks:
        auction = cols["auction"]
        ts = cols["date_time"]
        n_rows += len(ts)
        first = ((ts - window_ms) // slide_ms + 1) * slide_ms
        for k in range(factor):
            ws = first + k * slide_ms
            ok = ws <= ts
            pairs = np.stack([auction[ok], ws[ok]], axis=1)
            uniq, cnt = np.unique(pairs, axis=0, return_counts=True)
            for (a, w), c in zip(uniq, cnt):
                counts[(a, w)] = counts.get((a, w), 0) + int(c)
    dt = time.perf_counter() - t0
    return n_rows / dt, counts


def cpu_actor_q8(stream, window_ms):
    """Single-threaded q8 actor: per-side tumble + dedup dicts + probe
    of the other side's seen-set — the row-loop shape of a reference
    CPU actor. ``stream`` is [(side, cols_dict), ...] in arrival order."""
    pseen, aseen, out = {}, set(), {}
    t0 = time.perf_counter()
    n_rows = 0
    for side, cols in stream:
        ws = (cols["date_time"] // window_ms) * window_ms
        if side == "p":
            n_rows += len(ws)
            for i, w, nm in zip(
                cols["id"].tolist(), ws.tolist(), cols["name"].tolist()
            ):
                k = (i, w)
                if k not in pseen:
                    pseen[k] = nm
                    if k in aseen:
                        out[k] = nm
        else:
            n_rows += len(ws)
            for s, w in zip(cols["seller"].tolist(), ws.tolist()):
                k = (s, w)
                if k not in aseen:
                    aseen.add(k)
                    if k in pseen:
                        out[k] = pseen[k]
    dt = time.perf_counter() - t0
    return n_rows / dt, out


def _rwlint_gate(query: str):
    """Static plan verification BEFORE the bench runs (strict): a
    provably-broken plan fails the child with RW-E### diagnostics
    instead of burning a tier on wrong numbers. Lints the same
    small-capacity twin `lint --all-nexmark` verifies (the verifier is
    static, so plan shape is all that matters — analysis/).

    Also runs the fusion-feasibility analyzer over the same twin and
    returns its summary, so every BENCH JSON carries static blocker
    evidence (``{q}_fusion``) next to the dynamic profiler evidence —
    a TPU round's artifact shows WHAT was measured and WHY the
    dispatch wall is still there, in one file."""
    from risingwave_tpu.analysis.lint import (
        NEXMARK_SOURCE_SCHEMAS,
        build_nexmark_corpus,
        lint_pipeline,
    )

    built = build_nexmark_corpus(only=query)
    if query not in built:
        return None
    lint_pipeline(
        built[query].pipeline,
        NEXMARK_SOURCE_SCHEMAS[query],
        name=query,
        strict=True,
    )
    try:
        from risingwave_tpu.analysis.fusion_analyzer import (
            analyze_pipeline,
            report_to_json,
        )

        rep = report_to_json(
            analyze_pipeline(
                built[query].pipeline,
                NEXMARK_SOURCE_SCHEMAS[query],
                query,
                deep=True,
            )
        )
    except Exception:  # noqa: BLE001 — evidence, not a gate
        return None
    return {
        "summary": rep["summary"],
        "fragments": [
            {
                "fragment": f["fragment"],
                "fusible_prefix": f["fusible_prefix"],
                "chain_len": f["chain_len"],
                "whole_chain_fusible": f["whole_chain_fusible"],
                "host_sync_points": f["host_sync_points"],
                "blocker_codes": sorted(
                    {b["code"] for b in f["blockers"]}
                ),
            }
            for f in rep["fragments"]
        ],
    }


def _recompile_watch():
    """Armed AFTER the warmup pass: steady-state kernel cache deltas
    land in the BENCH JSON (``*_recompiles``) and in
    ``recompiles_total{fn=...}`` — nonzero means the run was re-tracing
    fused steps mid-measurement."""
    from risingwave_tpu.analysis.jax_sanitizer import RecompileWatch

    w = RecompileWatch()
    w.snapshot()
    return w


_BENCH_GOV = None


def _shape_watch_begin():
    """Arm SignatureWatch BEFORE warmup (the warmup pass registers the
    legitimate signature set; only post-warmup novelty is a hazard)
    plus a fresh ShapeGovernor for this query. RW_BENCH_SHAPEWATCH=0
    opts out."""
    global _BENCH_GOV
    import os

    if os.environ.get("RW_BENCH_SHAPEWATCH", "1") == "0":
        _BENCH_GOV = None
        return
    from risingwave_tpu.analysis.jax_sanitizer import SIGNATURES
    from risingwave_tpu.runtime.shape_governor import ShapeGovernor

    SIGNATURES.start()
    _BENCH_GOV = ShapeGovernor()


def _shape_watch_stable():
    """End of warmup: every later novel abstract input signature is a
    recompile hazard (the governor may pin on it)."""
    from risingwave_tpu.analysis.jax_sanitizer import SIGNATURES

    if SIGNATURES.enabled:
        SIGNATURES.mark_stable()


def _shape_fields(prefix, executors):
    """Steady-state shape evidence for the BENCH JSON: post-warmup
    recompile-hazard count (zero in steady state), governor actions,
    and the padding overhead of the bucketed state buffers
    (wasted-lane fraction — the price paid for shape stability)."""
    from risingwave_tpu.analysis.jax_sanitizer import SIGNATURES
    from risingwave_tpu.ops.bucketing import padding_stats

    out = {f"{prefix}_padding": padding_stats(executors)}
    if SIGNATURES.enabled:
        if _BENCH_GOV is not None:
            # final sweep so trailing-barrier hazards still pin + count
            _BENCH_GOV.observe_barrier(list(executors))
            out[f"{prefix}_shape_governor"] = _BENCH_GOV.snapshot()
        out[f"{prefix}_recompile_hazards"] = SIGNATURES.hazard_total()
        SIGNATURES.stop()
    return out


def _governor_tick(executors):
    """Per-barrier governor hook for the raw-pipeline bench paths (the
    unified q5u path rides StreamingRuntime's built-in hook)."""
    if _BENCH_GOV is not None:
        _BENCH_GOV.observe_barrier(executors)


def _arm_fusion(pipeline, label):
    """Arm the fused per-barrier step (runtime/fused_step) on a bench
    pipeline — serial pipelines fuse here; the unified q5u path fuses
    inside the graph runtime automatically. RW_FUSED_STEP=0 opts out
    (the interpreted-twin baseline runs)."""
    from risingwave_tpu.runtime.fused_step import fuse_pipeline, fused_enabled

    if not fused_enabled():
        return []
    return fuse_pipeline(pipeline, label=label)


def _fused_fields(prefix, pipeline):
    """Every BENCH JSON carries ``{q}_fused_fragments`` (count +
    whole-chain flag + fragment labels): the artifact says how much of
    the measured pipeline ran as one donated device program."""
    from risingwave_tpu.runtime.fused_step import fused_fragments

    return {f"{prefix}_fused_fragments": fused_fragments(pipeline)}


def _freshness_fields(prefix, pipeline):
    """Every BENCH JSON carries ``{q}_freshness``: p50/p99/n per lane
    (commit->visible, source->visible, event-time lag) summarized from
    the pipeline's own per-barrier FreshnessSurface samples — the
    artifact records how fresh the MV actually was while the bench ran."""
    samples = list(getattr(pipeline, "freshness_samples", ()) or ())
    out = {}
    for lane in (
        "commit_to_visible_ms",
        "source_to_visible_ms",
        "event_time_lag_ms",
    ):
        vals = sorted(
            s[lane]
            for s in samples
            if isinstance(s.get(lane), (int, float))
        )
        if vals:
            out[lane] = {
                "n": len(vals),
                "p50": round(vals[len(vals) // 2], 3),
                "p99": round(
                    vals[min(len(vals) - 1, int(0.99 * len(vals)))], 3
                ),
            }
        else:
            out[lane] = {"n": 0}
    return {f"{prefix}_freshness": out}


def _expand(executors):
    """Fused wrappers hide their members from plain executor lists;
    padding/governor surfaces need the members themselves."""
    from risingwave_tpu.runtime.fused_step import expand_fused

    return expand_fused(executors)


def _arm_deviceprof():
    """Arm the compiled-artifact roofline (deviceprof): every fused
    program bucket the measured run dispatches gets introspected ONCE
    via AOT lower+compile — FLOPs, bytes accessed, HBM footprint,
    compile ms, executable size — so the artifact's byte accounting
    comes from the executable, not host guesses. Armed BEFORE warmup
    so steady-state buckets analyze during warmup, not mid-measurement
    (a cache miss there costs one extra compile). RW_BENCH_DEVICEPROF=0
    opts out."""
    import os

    if os.environ.get("RW_BENCH_DEVICEPROF", "1") == "0":
        return None
    from risingwave_tpu.deviceprof import DEVICEPROF

    DEVICEPROF.reset()
    return DEVICEPROF.arm()


def _roofline_fields(prefix, n_barriers, seconds):
    """The ``{q}_roofline`` BENCH block: modeled bytes per barrier
    from the compiled executable, decomposed into useful vs padding
    traffic via the telemetry lanes — the explanation half of
    ``achieved_bw_frac``."""
    from risingwave_tpu.deviceprof import DEVICEPROF

    if not DEVICEPROF.enabled:
        return {}
    return DEVICEPROF.roofline_fields(prefix, n_barriers, seconds)


def _provenance_fields():
    """git_sha / pr_tag / engine_generation for every artifact."""
    from risingwave_tpu.provenance import stamp

    return stamp()


def _profile_begin():
    """Arm the dispatch/transfer counters for the measured run: every
    BENCH JSON carries dispatches-per-barrier/row per executor and
    host<->device transfer counts. Opt out with RW_BENCH_PROFILE=0."""
    import os

    if os.environ.get("RW_BENCH_PROFILE", "1") == "0":
        return None
    from risingwave_tpu.profiler import PROFILER

    PROFILER.reset()
    return PROFILER.enable()


def _profile_fields(prefix, prof, n_barriers, rows):
    """Collect the counters into BENCH-JSON fields, print the
    operator-readable top-5 dispatching executors, and disarm."""
    if prof is None:
        return {}
    total = prof.total_dispatches()
    counts = prof.dispatch_counts()
    fields = {
        f"{prefix}_device_dispatches": counts,
        f"{prefix}_dispatches_per_barrier": round(
            total / max(n_barriers, 1), 2
        ),
        f"{prefix}_dispatches_per_row": round(total / max(rows, 1), 6),
        f"{prefix}_transfers": prof.transfer_counts(),
    }
    print(f"[{prefix}] top dispatching executors:", file=sys.stderr)
    for ex, n in sorted(counts.items(), key=lambda kv: -kv[1])[:5]:
        print(f"  {ex:<28} dispatches {n:>6.0f}", file=sys.stderr)
    prof.disable()
    return fields


def _arm_blackbox(smoke: bool) -> None:
    """Child-mode black box: the flight recorder persists every barrier
    to an append-only BLACKBOX_*.jsonl (so a SIGKILLed/wedged child
    still leaves a per-barrier timeline on disk), and — on a real
    device — the wedge sentinel heartbeats the device and converts a
    wedge into a prompt structured ``DeviceWedged`` (via the existing
    SIGALRM unwind) instead of sitting out the full child alarm.
    Smoke/CPU runs keep the in-memory ring only (no repo litter)."""
    import os
    import signal

    from risingwave_tpu import blackbox

    if os.environ.get("RW_BENCH_BLACKBOX", "1") == "0":
        return
    if not smoke:
        blackbox.RECORDER.configure(
            dir=os.environ.get("RW_BLACKBOX_DIR", "."),
            fsync_interval_s=2.0,
        )

        def on_wedge(err):
            # the main thread may be blocked inside a device call no
            # Python raise can reach: ride the child's SIGALRM handler
            # (see _expire — it surfaces the sentinel's DeviceWedged)
            signal.alarm(5)

        blackbox.SENTINEL.start(
            interval_s=float(os.environ.get("RW_BLACKBOX_HEARTBEAT_S", 10)),
            slow_ms=float(os.environ.get("RW_BLACKBOX_SLOW_MS", 2000)),
            deadline_s=float(os.environ.get("RW_BLACKBOX_DEADLINE_S", 60)),
            on_wedge=on_wedge,
            dir=os.environ.get("RW_BLACKBOX_DIR", "."),
        )


def _state_cap(expected_rows: int, floor: int) -> int:
    """Table capacity whose growth margin covers the expected volume:
    growth REBUILDS tables at new capacities, and every new capacity
    recompiles the fused step programs (~30s each on TPU) — size state
    up front so a bench run never grows mid-flight."""
    cap = floor
    while expected_rows * 2.5 > cap:
        cap *= 2
    return cap


def bench_q8(gen_cfg, epochs, events_per_epoch, chunk_events):
    """Returns the q8 result dict (device run + CPU actor baseline)."""
    import jax
    import numpy as np

    from risingwave_tpu.array.chunk import StreamChunk
    from risingwave_tpu.connectors.nexmark import NexmarkConfig, NexmarkGenerator
    from risingwave_tpu.queries.nexmark_q import Q8_WINDOW_MS, build_q8

    fusion = _rwlint_gate("q8")  # static: fail BEFORE the event stream
    _shape_watch_begin()  # dynamic: warmup registers the legal shapes
    gen = NexmarkGenerator(NexmarkConfig(**gen_cfg))
    host_stream = []  # [(side, cols)] in arrival order, per epoch
    epochs_stream = []
    total_rows = 0
    for _ in range(epochs):
        # one person + one auction chunk per epoch: persons/auctions are
        # 2%/6% of the event stream, so per-generator-call chunks would
        # be tens of rows — all dispatch overhead. Batching per epoch is
        # result-identical (append-only dedup + inner join is
        # order-insensitive at barrier granularity).
        p_parts, a_parts = [], []
        done = 0
        while done < events_per_epoch:
            n = min(chunk_events, events_per_epoch - done)
            done += n
            ev = gen.next_events(n)
            p, a = ev["person"], ev["auction"]
            if p and len(p["id"]):
                p_parts.append(p)
            if a and len(a["seller"]):
                a_parts.append(a)
        per_epoch = []
        if p_parts:
            cols = {
                k: np.concatenate([p[k] for p in p_parts])
                for k in ("id", "name", "date_time")
            }
            per_epoch.append(("p", cols))
            total_rows += len(cols["id"])
        if a_parts:
            cols = {
                k: np.concatenate([a[k] for a in a_parts])
                for k in ("seller", "date_time")
            }
            per_epoch.append(("a", cols))
            total_rows += len(cols["seller"])
        epochs_stream.append(per_epoch)
        host_stream.extend(per_epoch)

    def _cap(side):
        mx = max(
            (len(c["id" if s == "p" else "seller"]) for ep in epochs_stream
             for s, c in ep if s == side),
            default=64,
        )
        return 1 << (mx - 1).bit_length()

    p_cap, a_cap = _cap("p"), _cap("a")

    cpu_rows_s, cpu_out = cpu_actor_q8(host_stream, Q8_WINDOW_MS)

    def dev_chunks():
        return [
            [
                (
                    side,
                    StreamChunk.from_numpy(
                        cols, p_cap if side == "p" else a_cap
                    ),
                )
                for side, cols in ep
            ]
            for ep in epochs_stream
        ]

    chunks = dev_chunks()
    # q8 state accumulates across the run (no watermarks driven here):
    # persons+auctions ~8%% of events, all retained
    c8 = _state_cap(int(epochs * events_per_epoch * 0.09), 1 << 16)
    _arm_deviceprof()  # roofline: analyze buckets from warmup on
    q8 = build_q8(capacity=c8, fanout=8, out_cap=1 << 14)
    _arm_fusion(q8.pipeline, "q8")
    # warmup epoch compiles every kernel, then fresh state + warm caches
    # warm over ALL epochs' chunk layouts (the fused two-input program
    # compiles per batch-count family — see the q7 warmup note)
    for ep in chunks:
        for side, c in ep:
            (q8.pipeline.push_left if side == "p" else q8.pipeline.push_right)(c)
        q8.pipeline.barrier()
    q8 = build_q8(capacity=c8, fanout=8, out_cap=1 << 14)
    _arm_fusion(q8.pipeline, "q8")
    recompiles = _recompile_watch()
    _shape_watch_stable()  # post-warmup novelty = recompile hazard
    from risingwave_tpu.metrics import REGISTRY

    REGISTRY.histograms.pop("barrier_stage_ms", None)  # drop warmup obs
    prof = _profile_begin()

    barrier_times = []
    t0 = time.perf_counter()
    for ep in chunks:
        for side, c in ep:
            (q8.pipeline.push_left if side == "p" else q8.pipeline.push_right)(c)
        tb = time.perf_counter()
        q8.pipeline.barrier()
        barrier_times.append(time.perf_counter() - tb)
        _governor_tick(
            _expand(list(q8.pipeline.left) + list(q8.pipeline.right))
            + [q8.join]
        )
    jax.block_until_ready(q8.join.left.row_valid)
    dt = time.perf_counter() - t0

    got = {k: v[0] for k, v in q8.mview.snapshot().items()}
    ok = got == cpu_out
    if not ok:
        print(
            f"Q8 MISMATCH: device {len(got)} rows vs cpu {len(cpu_out)}",
            file=sys.stderr,
        )
    from risingwave_tpu.epoch_trace import stage_breakdown

    return {
        "q8_throughput": round(total_rows / dt, 1),
        "q8_unit": "persons+auctions/sec",
        "q8_vs_baseline": round((total_rows / dt) / cpu_rows_s, 3),
        "q8_cpu_actor_rows_per_sec": round(cpu_rows_s, 1),
        "q8_p99_barrier_ms": round(
            float(np.percentile(np.asarray(barrier_times) * 1e3, 99)), 2
        ),
        "q8_correct": ok,
        "q8_recompiles": recompiles.deltas(),
        "q8_fusion": fusion,
        "q8_barrier_stage_ms": stage_breakdown(),
        **_profile_fields("q8", prof, len(barrier_times), total_rows),
        **_fused_fields("q8", q8.pipeline),
        **_freshness_fields("q8", q8.pipeline),
        **_roofline_fields("q8", len(barrier_times), dt),
        **_shape_fields(
            "q8",
            _expand(
                list(q8.pipeline.left)
                + list(q8.pipeline.right)
                + [q8.join]
                + list(q8.pipeline.tail)
            ),
        ),
    }


def cpu_actor_q7(chunks, window_ms):
    """Single-threaded q7 actor with the same dynamic-filter smarts the
    device plan uses (rows below their window's running max drop)."""
    wmax, bids_at = {}, {}
    t0 = time.perf_counter()
    n_rows = 0
    for cols in chunks:
        ws = (cols["date_time"] // window_ms) * window_ms
        n_rows += len(ws)
        for a, b, p, w in zip(
            cols["auction"].tolist(),
            cols["bidder"].tolist(),
            cols["price"].tolist(),
            ws.tolist(),
        ):
            cur = wmax.get(w, -1)
            if p >= cur:
                bids_at.setdefault((w, p), []).append((a, b))
                if p > cur:
                    wmax[w] = p
    out = {
        (w, a, b): (p,)
        for w, p in wmax.items()
        for (a, b) in bids_at.get((w, p), ())
    }
    dt = time.perf_counter() - t0
    return n_rows / dt, out


def bench_q7(gen_cfg, epochs, events_per_epoch, chunk_events):
    import jax
    import numpy as np

    from risingwave_tpu.array.chunk import StreamChunk
    from risingwave_tpu.connectors.nexmark import NexmarkConfig, NexmarkGenerator
    from risingwave_tpu.queries.nexmark_q import build_q7

    fusion = _rwlint_gate("q7")  # static: fail BEFORE the event stream
    _shape_watch_begin()  # dynamic: warmup registers the legal shapes
    window_ms = 10_000
    gen = NexmarkGenerator(NexmarkConfig(**gen_cfg))
    host_epochs = []
    total_bids = 0
    for _ in range(epochs):
        per_epoch = []
        done = 0
        while done < events_per_epoch:
            n = min(chunk_events, events_per_epoch - done)
            done += n
            b = gen.next_events(n)["bid"]
            if b and len(b["auction"]):
                per_epoch.append(
                    {k: b[k] for k in ("auction", "bidder", "price", "date_time")}
                )
                total_bids += len(b["auction"])
        host_epochs.append(per_epoch)

    flat = [c for ep in host_epochs for c in ep]
    cpu_rows_s, cpu_out = cpu_actor_q7(flat, window_ms)

    cap = chunk_events
    mk = lambda: [
        [StreamChunk.from_numpy(c, cap) for c in ep] for ep in host_epochs
    ]

    def run(q7, chunks):
        execs = _expand(
            list(q7.pipeline.left)
            + list(q7.pipeline.right)
            + [q7.join]
            + list(q7.pipeline.tail)
        )
        barrier_times = []
        max_ts = 0
        t0 = time.perf_counter()
        for ep_i, ep in enumerate(chunks):
            for c in ep:
                q7.pipeline.push_left(c)
                q7.pipeline.push_right(c)
            max_ts = max(
                max_ts, int(host_epochs[ep_i][-1]["date_time"].max())
            )
            tb = time.perf_counter()
            q7.pipeline.barrier()
            barrier_times.append(time.perf_counter() - tb)
            # recompile-storm governor: hazard deltas per barrier; over
            # budget (or SLOW sentinel) pins the offender's buckets
            _governor_tick(execs)
            q7.pipeline.watermark("date_time", max_ts)
        jax.block_until_ready(q7.join.left.row_valid)
        return time.perf_counter() - t0, barrier_times

    # watermarks bound q7 state to open windows, but the growth
    # heuristic is volume-driven: margin must cover one epoch's pushes
    c7 = _state_cap(events_per_epoch, 1 << 16)
    _arm_deviceprof()  # roofline: analyze buckets from warmup on

    def mk_q7():
        q7 = build_q7(
            capacity=c7,
            fanout=16,
            out_cap=1 << 14,
            agg_capacity=c7,
            filter_capacity=c7,
        )
        _arm_fusion(q7.pipeline, "q7")
        return q7

    q7 = mk_q7()
    # warm over ALL epochs' chunk layouts: the fused two-input program
    # compiles per (batch count, chunk signature) family, and a
    # 2-epoch smoke tier would otherwise pay a fresh compile INSIDE
    # the measured window whenever epoch 2's chunk count differs
    run(q7, mk())

    recompiles = _recompile_watch()
    _shape_watch_stable()  # post-warmup novelty = recompile hazard
    # build + host->device conversion BEFORE arming the profiler: the
    # measured dispatch/transfer counts describe steady-state barriers,
    # not one-time construction (same protocol as q5/q8)
    q7 = mk_q7()
    chunks7 = mk()
    from risingwave_tpu.metrics import REGISTRY

    REGISTRY.histograms.pop("barrier_stage_ms", None)  # drop warmup obs
    prof = _profile_begin()
    dt, barrier_times = run(q7, chunks7)

    got = q7.mview.snapshot()
    ok = got == cpu_out
    if not ok:
        print(
            f"Q7 MISMATCH: device {len(got)} rows vs cpu {len(cpu_out)}",
            file=sys.stderr,
        )
    from risingwave_tpu.epoch_trace import stage_breakdown

    return {
        "q7_throughput": round(total_bids / dt, 1),
        "q7_unit": "bids/sec",
        "q7_vs_baseline": round((total_bids / dt) / cpu_rows_s, 3),
        "q7_cpu_actor_rows_per_sec": round(cpu_rows_s, 1),
        "q7_p99_barrier_ms": round(
            float(np.percentile(np.asarray(barrier_times) * 1e3, 99)), 2
        ),
        "q7_correct": ok,
        "q7_recompiles": recompiles.deltas(),
        "q7_fusion": fusion,
        "q7_barrier_stage_ms": stage_breakdown(),
        **_profile_fields("q7", prof, len(barrier_times), total_bids),
        **_fused_fields("q7", q7.pipeline),
        **_freshness_fields("q7", q7.pipeline),
        **_roofline_fields("q7", len(barrier_times), dt),
        # AFTER profiler disarm: padding stats read device occupancy
        # counters and must not pollute the steady-state transfer counts
        **_shape_fields(
            "q7",
            _expand(
                list(q7.pipeline.left)
                + list(q7.pipeline.right)
                + [q7.join]
                + list(q7.pipeline.tail)
            ),
        ),
    }


Q5_SQL = (
    "CREATE MATERIALIZED VIEW q5 AS "
    "SELECT auction, window_start, count(*) AS num "
    "FROM HOP(bid, date_time, INTERVAL '2' SECOND, INTERVAL '10' SECOND) "
    "GROUP BY auction, window_start"
)


def bench_q5_unified(epochs, events_per_epoch, chunk_events):
    """The SAME q5 counts (q5-lite) as SQL through the UNIFIED path: planner -> actor
    graph (dispatchers, permit channels, FragmentActor threads) — the
    one-path-from-SQL-to-execution evidence, measured."""
    import numpy as np

    from risingwave_tpu.array.chunk import StreamChunk
    from risingwave_tpu.connectors.nexmark import (
        BID_SCHEMA,
        NexmarkConfig,
        NexmarkGenerator,
    )
    from risingwave_tpu.queries.nexmark_q import Q5_SLIDE_MS, Q5_WINDOW_MS
    from risingwave_tpu.runtime.fragmenter import graph_planned_mv
    from risingwave_tpu.sql import Catalog, StreamPlanner

    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=10_000))
    host_chunks = []
    for _ in range(epochs):
        per_epoch, done = [], 0
        while done < events_per_epoch:
            n = min(chunk_events, events_per_epoch - done)
            done += n
            bid = gen.next_events(n)["bid"]
            if bid and len(bid["auction"]):
                per_epoch.append(
                    {"auction": bid["auction"], "date_time": bid["date_time"]}
                )
        host_chunks.append(per_epoch)
    flat = [c for ep in host_chunks for c in ep]
    total_bids = sum(len(c["auction"]) for c in flat)
    cpu_rows_s, cpu_counts = cpu_actor_baseline(
        flat, Q5_WINDOW_MS, Q5_SLIDE_MS
    )

    _shape_watch_begin()  # warmup registers the legal shape set
    _arm_deviceprof()  # roofline: analyze buckets from warmup on
    c5 = _state_cap(2 * events_per_epoch, 1 << 16)
    catalog = Catalog({"bid": BID_SCHEMA})
    factory = lambda: StreamPlanner(catalog, capacity=c5)
    mv = graph_planned_mv(factory, Q5_SQL, parallelism=1)
    cap = chunk_events
    mk = lambda: [
        [StreamChunk.from_numpy(c, cap) for c in ep] for ep in host_chunks
    ]
    # warmup epoch compiles, then a fresh graph + warm caches
    for c in (StreamChunk.from_numpy(x, cap) for x in host_chunks[0]):
        mv.pipeline.push(c)
    mv.pipeline.barrier()
    mv.pipeline.close()
    mv = graph_planned_mv(factory, Q5_SQL, parallelism=1)
    _shape_watch_stable()  # post-warmup novelty = recompile hazard
    # drop warmup-epoch observations (first-epoch compile would
    # dominate the reported per-stage p99 and defeat the breakdown)
    from risingwave_tpu.metrics import REGISTRY

    REGISTRY.histograms.pop("barrier_stage_ms", None)

    dev_epochs = mk()  # host->device conversion OUTSIDE the timer
    prof = _profile_begin()  # armed after build+conversion (steady state)
    barrier_times = []
    t0 = time.perf_counter()
    for ep in dev_epochs:
        for c in ep:
            mv.pipeline.push(c)
        tb = time.perf_counter()
        mv.pipeline.barrier()
        barrier_times.append(time.perf_counter() - tb)
        _governor_tick(_expand(list(mv.pipeline.executors)))
    dt = time.perf_counter() - t0
    # measured roofline: HBM bytes
    # actually moved this run = chunks pushed + live executor state
    from risingwave_tpu.epoch_trace import chunk_nbytes, roofline

    moved = sum(chunk_nbytes(c) for ep in dev_epochs for c in ep) + sum(
        ex.state_nbytes()
        for ex in mv.pipeline.executors
        if hasattr(ex, "state_nbytes")
    )
    rf = roofline(moved, dt)
    # snapshot the per-stage breakdown NOW: it must describe the sync
    # run next to whose p99 it is reported, not blend in the pipelined
    # phase's admission-mode observations below
    from risingwave_tpu.epoch_trace import stage_breakdown

    stages_sync = stage_breakdown()
    # per-executor decomposition of the sync run's dispatch stage (the
    # pipelined phase below runs unprofiled — the breakdown must
    # describe the same run as stages_sync)
    prof_fields = _profile_fields("q5u", prof, len(barrier_times), total_bids)
    # before close(): fused evidence scans live actors, padding stats
    # read live executor occupancy
    fused_fields = _fused_fields("q5u", mv.pipeline)
    fresh_fields = _freshness_fields("q5u", mv.pipeline)
    shape_fields = _shape_fields("q5u", _expand(list(mv.pipeline.executors)))
    roofline_fields = _roofline_fields("q5u", len(barrier_times), dt)
    snap = mv.mview.snapshot()  # {(auction, window_start): (num,)}
    ok = snap == {k: (v,) for k, v in cpu_counts.items()}
    mv.pipeline.close()

    # pipelined barriers: admit every epoch without draining (the
    # reference's in-flight barriers, barrier/mod.rs:538) — epoch N+1's
    # pushes overlap epoch N's flush inside the actors
    mvp = graph_planned_mv(factory, Q5_SQL, parallelism=1)
    try:
        dev_epochs = mk()
        tp0 = time.perf_counter()
        pending = []
        for ep in dev_epochs:
            for c in ep:
                mvp.pipeline.push(c)
            pending.append(mvp.pipeline.barrier_nowait())
        for e in pending:
            mvp.pipeline.wait_barrier(e)
        dtp = time.perf_counter() - tp0
        snap_p = mvp.mview.snapshot()
        ok = ok and snap_p == {k: (v,) for k, v in cpu_counts.items()}
    finally:
        mvp.pipeline.close()  # actor threads must release the chip
    if not ok:
        print(
            f"Q5U MISMATCH: {len(snap)} groups vs {len(cpu_counts)}",
            file=sys.stderr,
        )
    best = max(total_bids / dt, total_bids / dtp)
    return {
        "q5u_throughput": round(best, 1),
        "q5u_unit": "bids/sec",
        "q5u_vs_baseline": round(best / cpu_rows_s, 3),
        "q5u_sync_throughput": round(total_bids / dt, 1),
        "q5u_pipelined_throughput": round(total_bids / dtp, 1),
        "q5u_p99_barrier_ms": round(
            float(np.percentile(np.asarray(barrier_times) * 1e3, 99)), 2
        ),
        "q5u_correct": ok,
        "q5u_cpu_actor_rows_per_sec": round(cpu_rows_s, 1),
        "q5u_total_bids": total_bids,
        # barrier-lifecycle observability: where each barrier's time
        # went (per stage, sync run only) + the measured roofline
        "barrier_stage_ms": stages_sync,
        "achieved_bw_frac": rf["achieved_bw_frac"],
        "achieved_bw_gbps": rf["achieved_bw_gbps"],
        "hbm_peak_gbps": rf["hbm_peak_gbps"],
        "hbm_bytes_touched": rf["hbm_bytes_touched"],
        **prof_fields,
        **fused_fields,
        **fresh_fields,
        **shape_fields,
        **roofline_fields,
    }


def bench_q5(args_epochs, events_per_epoch, chunk_events, agg_mode):
    import jax

    fusion = _rwlint_gate("q5")  # static: fail BEFORE the event stream
    _shape_watch_begin()  # dynamic: warmup registers the legal shapes
    _arm_deviceprof()  # roofline: analyze buckets from warmup on

    import numpy as np

    from risingwave_tpu.connectors.nexmark import NexmarkConfig, NexmarkGenerator
    from risingwave_tpu.queries.nexmark_q import (
        Q5_SLIDE_MS,
        Q5_WINDOW_MS,
        build_q5_lite,
    )

    epochs = args_epochs
    device = jax.devices()[0]
    platform = device.platform

    # -- pre-generate the workload (host) --------------------------------
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=10_000))
    host_chunks = []  # numpy column dicts, one per push
    for _ in range(epochs):
        done = 0
        per_epoch = []
        while done < events_per_epoch:
            n = min(chunk_events, events_per_epoch - done)
            done += n
            ev = gen.next_events(n)
            bid = ev["bid"]
            if bid and len(bid["auction"]):
                per_epoch.append(
                    {
                        "auction": bid["auction"],
                        "date_time": bid["date_time"],
                    }
                )
        host_chunks.append(per_epoch)
    flat_host = [c for ep in host_chunks for c in ep]
    total_bids = sum(len(c["auction"]) for c in flat_host)

    # -- CPU actor baseline ----------------------------------------------
    cpu_rows_s, cpu_counts = cpu_actor_baseline(
        flat_host, Q5_WINDOW_MS, Q5_SLIDE_MS
    )

    # -- device pipeline --------------------------------------------------
    import functools

    from risingwave_tpu.array.chunk import StreamChunk
    from risingwave_tpu.executors.hop_window import hop_step_fn
    from risingwave_tpu.array.chunk import stack_chunks

    cap = chunk_events  # bids per chunk <= events per chunk
    # one fused lax.scan per epoch: hop + agg over every chunk in ONE
    # device dispatch (per-chunk Python dispatch dominates on TPU)
    pre = functools.partial(
        hop_step_fn,
        ts_col="date_time",
        size_ms=Q5_WINDOW_MS,
        slide_ms=Q5_SLIDE_MS,
        out_start="window_start",
    )

    c5 = _state_cap(2 * events_per_epoch, 1 << 18)

    def run_q5(epochs_chunks, q5=None):
        from risingwave_tpu.profiler import PROFILER

        if q5 is None:
            q5 = build_q5_lite(capacity=c5, state_cleaning=False)
            _arm_fusion(q5.pipeline, "q5")
        barrier_times = []
        t0 = time.perf_counter()
        for stacked in epochs_chunks:
            if PROFILER.enabled:
                # apply_stacked bypasses the chain walk — attribute its
                # dispatches to the agg executor explicitly
                PROFILER.run(
                    q5.agg, q5.agg.apply_stacked,
                    stacked, pre=pre, mode=agg_mode,
                )
            else:
                q5.agg.apply_stacked(stacked, pre=pre, mode=agg_mode)
            tb = time.perf_counter()
            q5.pipeline.barrier()
            barrier_times.append(time.perf_counter() - tb)
        jax.block_until_ready(q5.agg.state.row_count)
        return q5, time.perf_counter() - t0, barrier_times

    def mk_stacked():
        return [
            stack_chunks([StreamChunk.from_numpy(c, cap) for c in ep])
            for ep in host_chunks
        ]

    run_q5(mk_stacked()[:1])  # warmup: compile epoch step + flush
    from risingwave_tpu.metrics import REGISTRY

    REGISTRY.histograms.pop("barrier_stage_ms", None)  # drop warmup obs
    recompiles = _recompile_watch()
    _shape_watch_stable()  # post-warmup novelty = recompile hazard
    # build + conversion outside the profiled window (steady-state
    # dispatch counts, not construction)
    stacked = mk_stacked()
    q5_fresh = build_q5_lite(capacity=c5, state_cleaning=False)
    _arm_fusion(q5_fresh.pipeline, "q5")
    prof = _profile_begin()
    q5, dt, barrier_times = run_q5(stacked, q5_fresh)

    rows_s = total_bids / dt
    p99_barrier_ms = float(np.percentile(np.asarray(barrier_times) * 1e3, 99))

    # measured roofline: bytes this run moved through HBM (epoch-stacked
    # input chunks + the live agg/MV state) over the measured wall time
    from risingwave_tpu.epoch_trace import chunk_nbytes, roofline, stage_breakdown

    moved = sum(chunk_nbytes(s) for s in stacked) + sum(
        ex.state_nbytes()
        for ex in q5.pipeline.executors
        if hasattr(ex, "state_nbytes")
    )
    rf = roofline(moved, dt)

    # -- correctness cross-check vs the CPU actor ------------------------
    mv = {k: v[0] for k, v in q5.mview.snapshot().items()}
    ok = mv == {k: v for k, v in cpu_counts.items()}
    if not ok:
        print(
            f"MISMATCH: device MV {len(mv)} groups vs cpu {len(cpu_counts)}",
            file=sys.stderr,
        )

    return {
        "metric": "nexmark_q5_lite_throughput",
        "value": round(rows_s, 1),
        "unit": "bids/sec",
        "vs_baseline": round(rows_s / cpu_rows_s, 3),
        "platform": platform,
        "cpu_actor_rows_per_sec": round(cpu_rows_s, 1),
        "p99_barrier_ms": round(p99_barrier_ms, 2),
        "total_bids": total_bids,
        "epochs": epochs,
        "agg_mode": agg_mode,
        "correct": ok,
        "q5_achieved_bw_frac": rf["achieved_bw_frac"],
        "q5_achieved_bw_gbps": rf["achieved_bw_gbps"],
        "q5_hbm_peak_gbps": rf["hbm_peak_gbps"],
        "q5_barrier_stage_ms": stage_breakdown(),
        "q5_recompiles": recompiles.deltas(),
        "q5_fusion": fusion,
        **_profile_fields("q5", prof, len(barrier_times), total_bids),
        **_fused_fields("q5", q5.pipeline),
        **_freshness_fields("q5", q5.pipeline),
        **_shape_fields("q5", _expand(list(q5.pipeline.executors))),
        **_roofline_fields("q5", len(barrier_times), dt),
    }


# ---------------------------------------------------------------------------
# Orchestration: each query benches in its own SUBPROCESS, so one kernel
# fault or hang costs that query alone and the chip is held by one
# process at a time (the parent never touches JAX). Tiers run
# breadth-first: every query lands a smoke_dev number before anything
# escalates. A failed or timed-out child, or a result that disagrees
# with its CPU actor, makes the whole run exit non-zero.
# ---------------------------------------------------------------------------

TIERS = {
    # (epochs, events_per_epoch, chunk_events, timeout_s)
    "full": (10, 200_000, 8_192, 600),
    "mid": (5, 50_000, 4_096, 360),
    "smoke_dev": (2, 10_000, 2_048, 240),
}
TIER_ORDER = ["smoke_dev", "mid", "full"]  # breadth-first escalation
PARTIAL_PATH = "BENCH_partial.json"


def _write_artifact(merged: dict) -> None:
    import os

    tmp = PARTIAL_PATH + ".tmp"
    with open(tmp, "w") as f:
        json.dump(merged, f)
    os.replace(tmp, PARTIAL_PATH)


def _child_timeout(query: str, tier: str) -> int:
    """Per-(query, tier) child time limit. q7's compile stack (grouped-
    max DynamicFilter + retracting join) is the deepest; q5u compiles
    one program per executor (vs q5's single fused program) and
    measures the run TWICE (sync + pipelined)."""
    base = TIERS[tier][3]
    mult = {"q7": 2.5, "q5u": 2.0}.get(query, 1.0)
    return int(base * mult)


def _run_child(query: str, tier: str, smoke: bool, agg_mode: str):
    """Run one (query, tier) in a subprocess. The child arms
    signal.alarm(timeout) and unwinds with a structured error on
    expiry; the parent waits a grace period longer and then kills it.
    Returns (result dict, None) or (None, error string)."""
    import os
    import subprocess

    epochs, events, chunk, _ = TIERS[tier]
    timeout_s = _child_timeout(query, tier)
    cmd = [
        sys.executable,
        os.path.abspath(__file__),
        "--only",
        query,
        "--epochs",
        str(epochs),
        "--events-per-epoch",
        str(events),
        "--chunk-events",
        str(chunk),
        "--agg-mode",
        agg_mode,
        "--alarm-s",
        str(timeout_s),
    ]
    if smoke:
        cmd.append("--smoke")
    env = dict(os.environ)
    # the barrier deadman must outlast a cold first-epoch XLA compile;
    # the child's own alarm stays the real backstop, so give the
    # deadman everything up to 30s before it
    env.setdefault("RW_BARRIER_TIMEOUT_S", str(max(timeout_s - 30, 120)))
    try:
        proc = subprocess.run(
            cmd,
            capture_output=True,
            text=True,
            env=env,
            timeout=timeout_s + 45,
        )
    except subprocess.TimeoutExpired:
        return None, f"{query}/{tier}: timeout after {timeout_s}s"
    out = proc.stdout or ""
    if proc.returncode != 0:
        tail = (proc.stderr or "")[-400:]
        return None, f"{query}/{tier}: rc={proc.returncode}: {tail}"
    for line in reversed(out.strip().splitlines()):
        try:
            return json.loads(line), None
        except json.JSONDecodeError:
            continue
    return None, f"{query}/{tier}: no JSON in output"


def _bench_one(query: str, epochs, events, chunk, agg_mode):
    gen_cfg = {"first_event_rate": 10_000}
    if query == "q5":
        return bench_q5(epochs, events, chunk, agg_mode)
    if query == "q5u":
        return bench_q5_unified(epochs, events, chunk)
    if query == "q8":
        return bench_q8(gen_cfg, epochs, events, chunk)
    if query == "q7":
        return bench_q7(gen_cfg, epochs, events, chunk)
    raise ValueError(query)


def _finish(result: dict, errors=()) -> int:
    """Print the ONE JSON line; non-zero when a child failed or a
    ``correct`` / ``*_correct`` field is false."""
    errors = list(errors) + [
        f"{k} is false"
        for k, v in sorted(result.items())
        if (k == "correct" or k.endswith("_correct")) and v is False
    ]
    if errors:
        result["errors"] = errors
    print(json.dumps(result))
    for e in errors:
        print(f"bench: {e}", file=sys.stderr)
    return 1 if errors else 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="small run on CPU")
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--events-per-epoch", type=int, default=None)
    ap.add_argument("--chunk-events", type=int, default=None)
    ap.add_argument(
        "--only", choices=["q5", "q7", "q8", "q5u"], default=None
    )
    ap.add_argument(
        "--agg-mode",
        choices=["reduce", "scan"],
        default="reduce",
        help="epoch pre-reduction (fast) vs per-chunk lax.scan",
    )
    ap.add_argument(
        "--no-subprocess",
        action="store_true",
        help="run all queries in-process (debug aid)",
    )
    ap.add_argument(
        "--alarm-s",
        type=int,
        default=None,
        help="child self-timeout: unwind with a structured error "
        "instead of sitting until the parent kills the child",
    )
    ap.add_argument(
        "--multichip",
        type=int,
        nargs="?",
        const=8,
        default=None,
        metavar="N",
        help="run the N-virtual-device sharded dryrun (q5/q8/q7 MV "
        "parity vs serial + mid-stream kill/recover) with MESHPROF "
        "armed and stamp the structured MULTICHIP.json artifact: "
        "provenance + per-query per-shard attribution, exchange "
        "matrix, and skew verdicts",
    )
    args = ap.parse_args()

    if args.alarm_s:
        import signal

        alarm_deadline = time.monotonic() + args.alarm_s

        def _expire(signum, frame):
            # a sentinel-detected wedge surfaces as the STRUCTURED
            # DeviceWedged (forensic bundle already on disk) rather
            # than a generic timeout; either way python unwinds and
            # the parent reads rc != 0
            from risingwave_tpu import blackbox

            wedged = blackbox.SENTINEL.wedged_error()
            if wedged is not None:
                raise wedged
            remaining = alarm_deadline - time.monotonic()
            if remaining > 1:
                # the sentinel's on_wedge pulled the alarm forward but
                # the wedge HEALED before it fired (a completed beat
                # disarms): restore the original budget, don't kill a
                # healthy run with a misleading timeout
                signal.alarm(int(remaining) + 1)
                return
            raise TimeoutError(f"self-timeout after {args.alarm_s}s")

        signal.signal(signal.SIGALRM, _expire)
        signal.alarm(args.alarm_s)

    if args.multichip:
        # the sharded dryrun is self-contained (forces virtual CPU
        # devices + arms MESHPROF internally); the artifact carries
        # the structured mesh doc (per-shard attribution and skew).
        # A failing dryrun raises:
        # no artifact, non-zero exit.
        import os

        import __graft_entry__ as graft

        doc = {"multichip": True, "ts": time.time()}
        doc.update(_provenance_fields())
        try:
            doc.update(graft.dryrun_multichip(args.multichip))
        finally:
            # imported here: the dryrun must set its virtual-device
            # flag before anything initializes the backend
            from risingwave_tpu.parallel.meshprof import MESHPROF

            MESHPROF.disable()
        doc["ok"] = True
        tmp = "MULTICHIP.json.tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, "MULTICHIP.json")
        print(json.dumps(doc))
        return 0

    in_process = (
        args.only
        or args.no_subprocess
        or args.epochs
        or args.events_per_epoch
        or args.chunk_events
    )
    if in_process:
        # this process runs the queries itself: bind it to the device
        # the mode stands for, and fail here when jax found another
        from risingwave_tpu.config import enable_compile_cache, select_device

        select_device("cpu" if args.smoke else "tpu")
        enable_compile_cache()

    if args.only:
        # child mode: one query, one shape, in-process — with the
        # black box armed so even a kill leaves per-barrier telemetry
        # and a forensic bundle behind
        _arm_blackbox(args.smoke)
        epochs = args.epochs or 3
        events = args.events_per_epoch or 20_000
        chunk = args.chunk_events or 2_048
        result = _bench_one(args.only, epochs, events, chunk, args.agg_mode)
        from risingwave_tpu import blackbox

        if blackbox.RECORDER.segment_path:
            result[f"{args.only}_blackbox_segment"] = (
                blackbox.RECORDER.segment_path
            )
        blackbox.SENTINEL.stop()
        blackbox.RECORDER.close()
        result.update(_provenance_fields())
        return _finish(result)

    if in_process:
        epochs = args.epochs or (3 if args.smoke else 10)
        events = args.events_per_epoch or (
            20_000 if args.smoke else 200_000
        )
        chunk = args.chunk_events or (2_048 if args.smoke else 8_192)
        result = _bench_one("q5", epochs, events, chunk, args.agg_mode)
        for q in ("q8", "q7"):
            result.update(_bench_one(q, epochs, events, chunk, args.agg_mode))
        result.setdefault(
            "achieved_bw_frac", result.get("q5_achieved_bw_frac", 0.0)
        )
        result.setdefault(
            "barrier_stage_ms", result.get("q5_barrier_stage_ms", {})
        )
        result.update(_provenance_fields())
        return _finish(result)

    # orchestrator: breadth-first tiers, one child per (query, tier)
    t_start = time.perf_counter()
    tiers = ["smoke_dev"] if args.smoke else TIER_ORDER
    merged = {}
    errors = []
    failed: set = set()  # queries that failed — don't escalate those
    # q5u FIRST: the unified SQL->actor path is the headline system
    # (the benched system must be the built system); q5 (apply_stacked
    # direct) stays as the fusion oracle. q7 runs last in every tier:
    # its compile stack is the deepest and the likeliest to time out.
    schedule = [(t, q) for t in tiers for q in ("q5u", "q5", "q8", "q7")]
    for tier, query in schedule:
        if query in failed:
            continue
        sub, err = _run_child(query, tier, args.smoke, args.agg_mode)
        if sub is None:
            errors.append(err)
            failed.add(query)
            continue
        sub[f"{query}_tier" if query != "q5" else "tier"] = tier
        merged.update(sub)  # larger tier overwrites smaller
    if "value" in merged:
        # keep the apply_stacked (fusion-oracle) number visible next to
        # the headline before q5u overwrites the driver fields
        merged["q5_stacked_throughput"] = merged["value"]
    if "q5u_throughput" in merged:
        # HEADLINE = the unified SQL->planner->actor-graph path: the
        # number the driver records measures the actual system
        merged["metric"] = "nexmark_q5_unified_throughput"
        merged["value"] = merged["q5u_throughput"]
        merged["unit"] = "bids/sec"
        merged["vs_baseline"] = merged["q5u_vs_baseline"]
    if "achieved_bw_frac" not in merged and "q5_achieved_bw_frac" in merged:
        # q5u failed but the stacked oracle landed: its measured
        # roofline keeps the headline fields populated
        merged["achieved_bw_frac"] = merged["q5_achieved_bw_frac"]
        merged.setdefault(
            "barrier_stage_ms", merged.get("q5_barrier_stage_ms", {})
        )
    merged["bench_wall_s"] = round(time.perf_counter() - t_start, 1)
    merged.update(_provenance_fields())
    rc = _finish(merged, errors)
    _write_artifact(merged)
    return rc


if __name__ == "__main__":
    sys.exit(main())
