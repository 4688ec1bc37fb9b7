#!/usr/bin/env python
"""perf_gate — the dispatch-cost regression gate.

Two modes, both against the committed budgets
(``scripts/perf_budgets.json``):

1. ``--bench BENCH_*.json`` (default: BENCH_partial.json): pure-JSON
   comparison of a bench artifact's stage/executor p99s,
   dispatches-per-row and dispatches-per-barrier against the budgets.
   No jax import — runs in ~100ms, safe anywhere. Fields a (seed)
   artifact does not carry are SKIPPED with a note, never failed: the
   gate tightens as artifacts grow richer, it does not brick old ones.

2. ``--smoke``: a CPU-cheap q5 steady-state microbench run in-process
   with the dispatch-wall profiler armed — asserts the steady-state
   device-dispatch count per barrier and the host-python ms/row stay
   under budget. This is the tier-1 CI smoke: the fragment-fusion work
   (ROADMAP open item 1) drives dispatches-per-barrier toward 1; this
   gate makes sure nothing silently drives it the other way.

Exit code: 0 = within budget, 1 = regression, 2 = usage/IO error.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_BUDGETS = os.path.join(ROOT, "scripts", "perf_budgets.json")
DEFAULT_BENCH = os.path.join(ROOT, "BENCH_partial.json")


def _load(path: str):
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# mode 1: bench-artifact comparison (pure JSON)
# ---------------------------------------------------------------------------


def _stage_p99(bench: dict, stage: str) -> float:
    """Max p99 of one stage across every fragment label set, over all
    ``*barrier_stage_ms`` blocks in the artifact."""
    worst = 0.0
    for key, block in bench.items():
        if not key.endswith("barrier_stage_ms") or not isinstance(block, dict):
            continue
        for lbl, row in block.items():
            if f"stage={stage}" in lbl and isinstance(row, dict):
                worst = max(worst, float(row.get("p99", 0.0)))
    return worst


def check_bench(bench: dict, budgets: dict, verbose=True):
    """Returns (violations, skipped) lists of strings."""
    b = budgets.get("bench", {})
    violations, skipped = [], []

    def note(msg):
        if verbose:
            print(f"[perf_gate] {msg}")

    for stage, mx in b.get("stage_p99_ms", {}).items():
        got = _stage_p99(bench, stage)
        if got == 0.0:
            skipped.append(f"stage {stage}: no observations in artifact")
            continue
        if got > mx:
            violations.append(
                f"stage {stage} p99 {got:.2f}ms > budget {mx}ms"
            )
        else:
            note(f"stage {stage} p99 {got:.2f}ms <= {mx}ms ok")
    for key, mx in b.get("scalar_max", {}).items():
        if key not in bench:
            skipped.append(f"{key}: absent from artifact")
            continue
        got = float(bench[key])
        if got > mx:
            violations.append(f"{key} = {got} > budget {mx}")
        else:
            note(f"{key} = {got} <= {mx} ok")
    for q, mx in b.get("dispatches_per_row_max", {}).items():
        key = f"{q}_dispatches_per_row"
        if key not in bench:
            skipped.append(f"{key}: absent from artifact")
            continue
        got = float(bench[key])
        if got > mx:
            violations.append(
                f"{q}: {got} device dispatches/row > budget {mx} "
                "(per-op dispatch regression — see PROFILE.md worklist)"
            )
        else:
            note(f"{q}: {got} dispatches/row <= {mx} ok")
    for q, mx in b.get("dispatches_per_barrier_max", {}).items():
        key = f"{q}_dispatches_per_barrier"
        if key not in bench:
            skipped.append(f"{key}: absent from artifact")
            continue
        got = float(bench[key])
        if got > mx:
            violations.append(
                f"{q}: {got} device dispatches/barrier > budget {mx}"
            )
        else:
            note(f"{q}: {got} dispatches/barrier <= {mx} ok")
    # steady-state recompile-hazard budget (PR 9): after warmup, ZERO
    # novel abstract input signatures per query — a nonzero count means
    # a shape escaped the bucket lattice and the run was re-tracing
    for q, mx in b.get("recompile_hazards_max", {}).items():
        key = f"{q}_recompile_hazards"
        if key not in bench:
            skipped.append(f"{key}: absent from artifact")
            continue
        got = float(bench[key])
        if got > mx:
            violations.append(
                f"{q}: {got:.0f} post-warmup recompile hazards > budget "
                f"{mx} (shape escaped the bucket lattice — see "
                f"{q}_shape_governor in the artifact)"
            )
        else:
            note(f"{q}: {got:.0f} recompile hazards <= {mx} ok")
    # padding-overhead backstop: the price of bucketed shapes is
    # masked dead lanes; a pathological wasted-lane fraction (e.g. the
    # governor pinning everything at a huge bucket) must not land
    # silently. Calibrated loose: pow2 tables at <=50% load are >=50%
    # padding BY DESIGN.
    for q, mx in b.get("padding_wasted_frac_max", {}).items():
        blk = bench.get(f"{q}_padding")
        if not isinstance(blk, dict) or "wasted_lane_frac" not in blk:
            skipped.append(f"{q}_padding: absent from artifact")
            continue
        got = float(blk["wasted_lane_frac"])
        if got > mx:
            violations.append(
                f"{q}: padded-state wasted-lane fraction {got} > "
                f"budget {mx}"
            )
        else:
            note(f"{q}: wasted-lane fraction {got} <= {mx} ok")
    # roofline budgets (PR 11): the modeled-traffic padding fraction
    # per query (the price of bucketed shapes, now measured from the
    # compiled executable + telemetry lanes instead of a device scan)
    # and the per-bucket compile cost of every analyzed program
    rb = budgets.get("roofline", {})
    for q, mx in rb.get("padding_bytes_frac_max", {}).items():
        blk = bench.get(f"{q}_roofline")
        if not isinstance(blk, dict) or "padding_bytes_frac" not in blk:
            skipped.append(f"{q}_roofline: absent from artifact")
            continue
        got = float(blk["padding_bytes_frac"])
        if got > mx:
            violations.append(
                f"{q}: modeled padding-bytes fraction {got} > budget "
                f"{mx} (masked-lane waste dominates the fused "
                "program's traffic)"
            )
        else:
            note(f"{q}: padding-bytes fraction {got} <= {mx} ok")
    cms = rb.get("compile_ms_max")
    if cms:
        for q in ("q5", "q5u", "q7", "q8"):
            blk = bench.get(f"{q}_roofline")
            progs = (blk or {}).get("programs")
            if not isinstance(progs, dict):
                continue
            for key, p in progs.items():
                got = float(p.get("compile_ms", 0.0))
                if got > cms:
                    violations.append(
                        f"{q}: program {key} compiled in {got:.0f}ms > "
                        f"budget {cms}ms per bucket"
                    )
                else:
                    note(f"{q}: {key} compile {got:.0f}ms <= {cms}ms ok")
    # executor-attribution coverage: when the artifact carries the
    # per-executor decomposition it must actually explain the dispatch
    # stage (≥ coverage_min of the stage total), or the breakdown has
    # rotted into decoration
    cov_min = b.get("executor_coverage_min")
    if cov_min:
        for q in ("q5", "q5u", "q7", "q8"):
            blk = bench.get(f"{q}_executor_ms")
            if not isinstance(blk, dict):
                skipped.append(f"{q}_executor_ms: absent from artifact")
                continue
            cov = executor_coverage(bench, q)
            if cov is None:
                skipped.append(f"{q}: no dispatch-stage data to cover")
            elif cov < cov_min:
                violations.append(
                    f"{q}: executor attribution covers only "
                    f"{cov:.0%} of the dispatch stage (< {cov_min:.0%})"
                )
            else:
                note(f"{q}: executor attribution covers {cov:.0%} ok")
    # freshness fields (PR 16): bench artifacts stamp a {q}_freshness
    # block (p50/p99/n per lane) from the pipeline's own samples; when
    # present, the commit->visible p99 is held to the SLO budget and an
    # empty sample set is a violation (the lane went dark), while an
    # absent block is a skip (older artifacts stay comparable)
    fb = budgets.get("freshness", {})
    fmx = fb.get("bench_commit_to_visible_p99_ms_max")
    if fmx:
        for q in ("q5", "q5u", "q7", "q8"):
            blk = bench.get(f"{q}_freshness")
            if not isinstance(blk, dict):
                skipped.append(f"{q}_freshness: absent from artifact")
                continue
            c2v = blk.get("commit_to_visible_ms") or {}
            if not c2v.get("n"):
                violations.append(
                    f"{q}: {q}_freshness stamped but carries no "
                    "commit->visible samples — the lane went dark"
                )
                continue
            got = float(c2v.get("p99", 0.0))
            if got > fmx:
                violations.append(
                    f"{q}: commit->visible p99 {got}ms > budget "
                    f"bench_commit_to_visible_p99_ms_max={fmx}"
                )
            else:
                note(f"{q}: commit->visible p99 {got}ms <= {fmx}ms ok")
    return violations, skipped


def executor_coverage(bench: dict, q: str):
    """Fraction of the query's dispatch-stage total explained by its
    per-executor (flush + barrier_apply, host + device-wait) sums."""
    stage_key = "barrier_stage_ms" if q == "q5u" else f"{q}_barrier_stage_ms"
    stages = bench.get(stage_key) or {}
    disp = sum(
        float(row.get("sum", 0.0))
        for lbl, row in stages.items()
        if "stage=dispatch" in lbl and isinstance(row, dict)
    )
    if disp <= 0:
        return None
    blk = bench.get(f"{q}_executor_ms") or {}
    covered = 0.0
    for hist in ("executor_ms", "executor_device_wait_ms"):
        for lbl, row in (blk.get(hist) or {}).items():
            if ("phase=flush" in lbl or "phase=barrier_apply" in lbl) and (
                isinstance(row, dict)
            ):
                covered += float(row.get("sum", 0.0))
    return covered / disp


# ---------------------------------------------------------------------------
# mode 3: fusion-feasibility regression gate (static, CPU, in-process)
# ---------------------------------------------------------------------------

DEFAULT_FUSION_BASELINE = os.path.join(ROOT, "FUSION_REPORT.json")


def run_fusion_gate(
    budgets: dict,
    baseline_path: str = None,
    current_path: str = None,
):
    """Re-run the fusion analyzer over the Nexmark corpus and compare
    against the committed FUSION_REPORT.json baseline: per fragment,
    the fusible executor prefix must not SHRINK and the host-sync
    count must not GROW (plus the optional absolute per-fragment
    ``max_host_sync_points`` budget). This is the ratchet for ROADMAP
    item 1 — every fusion PR moves prefixes up and sync counts down,
    and nothing moves them back silently. Returns (violations,
    skipped)."""
    baseline_path = baseline_path or DEFAULT_FUSION_BASELINE
    try:
        baseline = _load(baseline_path)
    except (OSError, json.JSONDecodeError) as e:
        return [], [f"fusion baseline unreadable ({e}) — gate skipped"]
    if current_path:
        # reuse an analysis another CI stage already paid for (the
        # `lint --fusion-report --json` output, or its __fusion__ key)
        try:
            current = _load(current_path)
        except (OSError, json.JSONDecodeError) as e:
            return [f"fusion current-report unreadable: {e}"], []
        current = current.get("__fusion__", current)
    else:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if ROOT not in sys.path:
            sys.path.insert(0, ROOT)
        import jax

        jax.config.update("jax_platforms", "cpu")
        from risingwave_tpu.analysis.fusion_analyzer import (
            analyze_nexmark,
        )

        current = analyze_nexmark(deep=True)
    fb = budgets.get("fusion", {})
    max_sync = fb.get("max_host_sync_points", {})
    violations, skipped = [], []
    for q, base_rep in baseline.items():
        if q.startswith("_"):
            continue
        if q not in current:
            # a vanished query loses ALL its ratchet coverage — that
            # is a regression, not a skip (fragments two checks below
            # get the same treatment)
            violations.append(
                f"fusion: query {q!r} vanished from the analysis "
                "(baseline still lists it)"
            )
            continue
        base_frags = {
            f["fragment"]: f for f in base_rep.get("fragments", ())
        }
        cur_frags = {
            f["fragment"]: f for f in current[q]["fragments"]
        }
        for name, bf in base_frags.items():
            cf = cur_frags.get(name)
            if cf is None:
                violations.append(
                    f"fusion {q}: fragment {name!r} vanished from the "
                    "analysis (baseline still lists it)"
                )
                continue
            if cf["fusible_prefix"] < bf["fusible_prefix"]:
                violations.append(
                    f"fusion {q}/{name}: fusible prefix regressed "
                    f"{bf['fusible_prefix']} -> {cf['fusible_prefix']}"
                )
            if cf["host_sync_points"] > bf["host_sync_points"]:
                violations.append(
                    f"fusion {q}/{name}: host-sync points grew "
                    f"{bf['host_sync_points']} -> "
                    f"{cf['host_sync_points']}"
                )
            # fallback syncs are outside the fusibility verdict (the
            # fused step compiles them away) but still run per barrier
            # wherever the fallback path executes (e.g. an epoch-
            # batched agg feeding a join) — a regression adding reads
            # there must not slip past the gate
            if cf.get("fallback_sync_points", 0) > bf.get(
                "fallback_sync_points", 0
            ):
                violations.append(
                    f"fusion {q}/{name}: fallback-sync points grew "
                    f"{bf.get('fallback_sync_points', 0)} -> "
                    f"{cf.get('fallback_sync_points', 0)}"
                )
            if bf.get("whole_chain_fusible") and not cf.get(
                "whole_chain_fusible"
            ):
                violations.append(
                    f"fusion {q}/{name}: whole-chain fusible proof lost"
                )
        mx = max_sync.get(q)
        if mx is not None:
            total = current[q]["summary"]["host_sync_points"]
            if total > mx:
                violations.append(
                    f"fusion {q}: {total} host-sync points > budget {mx}"
                )
        # shape-stability ratchet (PR 9): per-code blocker ceilings —
        # RW-E803/E806 are pinned at ZERO for the whole corpus (q7's
        # wedge class must never return), and no code may regress
        # above its committed-baseline count
        cur_codes = current[q]["summary"].get("blockers_by_code", {})
        base_codes = base_rep.get("summary", {}).get(
            "blockers_by_code", {}
        )
        for code, mx in fb.get("max_blocker_codes", {}).items():
            got = int(cur_codes.get(code, 0))
            if got > mx:
                violations.append(
                    f"fusion {q}: {got} {code} finding(s) > budget {mx}"
                    + (
                        " (the q7 wedge class regressed: an executor "
                        "lost its window_buckets lattice)"
                        if code in ("RW-E803", "RW-E806")
                        else ""
                    )
                )
        for code, n in cur_codes.items():
            if int(n) > int(base_codes.get(code, 0)):
                violations.append(
                    f"fusion {q}: blocker {code} count grew "
                    f"{base_codes.get(code, 0)} -> {n} vs baseline"
                )
    return violations, skipped


# ---------------------------------------------------------------------------
# mode 3b: mesh-readiness regression gate (static, CPU, subprocess)
# ---------------------------------------------------------------------------

DEFAULT_MESH_BASELINE = os.path.join(ROOT, "MESH_REPORT.json")


def run_mesh_static_gate(
    budgets: dict,
    baseline_path: str = None,
    current_path: str = None,
):
    """Re-run the mesh analyzer over the sharded corpus and compare
    against the committed MESH_REPORT.json baseline: per fragment, the
    host-routed exchange-edge count (RW-E901 + RW-E907) must not GROW
    and an SPMD-fusibility proof must not be LOST; per query, no E9xx
    code's blocker count may grow past its committed count. This is
    the ratchet for ROADMAP item 3 — the collective-exchange arc moves
    edge counts down and proofs up, and nothing moves them back
    silently. Without ``current_path`` the analysis runs in a fresh
    subprocess (``lint --mesh-report`` owns its 8-virtual-device
    mesh, which cannot be conjured after this process touched jax).
    Returns (violations, skipped)."""
    baseline_path = baseline_path or DEFAULT_MESH_BASELINE
    try:
        baseline = _load(baseline_path)
    except (OSError, json.JSONDecodeError) as e:
        return [], [f"mesh baseline unreadable ({e}) — gate skipped"]
    if current_path:
        try:
            current = _load(current_path)
        except (OSError, json.JSONDecodeError) as e:
            return [f"mesh current-report unreadable: {e}"], []
        current = current.get("__mesh__", current)
    else:
        import subprocess

        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # the child claims its own mesh
        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "risingwave_tpu",
                "lint",
                "--mesh-report",
                "--json",
            ],
            capture_output=True,
            text=True,
            cwd=ROOT,
            env=env,
        )
        if proc.returncode != 0:
            return [
                "mesh: `lint --mesh-report` failed "
                f"(exit {proc.returncode}): "
                f"{(proc.stderr or proc.stdout).strip()[-400:]}"
            ], []
        try:
            current = json.loads(proc.stdout)
        except json.JSONDecodeError as e:
            return [f"mesh: analyzer emitted unparsable JSON: {e}"], []
    violations, skipped = [], []
    for q, base_rep in baseline.items():
        if q.startswith("_") or q in ("ranking", "top_cost"):
            continue
        if q not in current:
            violations.append(
                f"mesh: query {q!r} vanished from the analysis "
                "(baseline still lists it)"
            )
            continue
        base_frags = {
            f["fragment"]: f for f in base_rep.get("fragments", ())
        }
        cur_frags = {
            f["fragment"]: f for f in current[q]["fragments"]
        }
        for name, bf in base_frags.items():
            cf = cur_frags.get(name)
            if cf is None:
                violations.append(
                    f"mesh {q}: fragment {name!r} vanished from the "
                    "analysis (baseline still lists it)"
                )
                continue
            if cf["host_routed_edges"] > bf["host_routed_edges"]:
                violations.append(
                    f"mesh {q}/{name}: host-routed exchange edges grew "
                    f"{bf['host_routed_edges']} -> "
                    f"{cf['host_routed_edges']}"
                )
            if bf.get("spmd_fusible") and not cf.get("spmd_fusible"):
                violations.append(
                    f"mesh {q}/{name}: SPMD-fusibility proof lost"
                )
        # per-code ratchet: no E9xx class may grow past its committed
        # count (the committed blockers are the worklist, not a quota)
        cur_codes = current[q]["summary"].get("blockers_by_code", {})
        base_codes = base_rep.get("summary", {}).get(
            "blockers_by_code", {}
        )
        for code, n in cur_codes.items():
            if int(n) > int(base_codes.get(code, 0)):
                violations.append(
                    f"mesh {q}: blocker {code} count grew "
                    f"{base_codes.get(code, 0)} -> {n} vs baseline"
                )
        bsum = base_rep.get("summary", {})
        csum = current[q]["summary"]
        if csum.get("spmd_fusible_fragments", 0) < bsum.get(
            "spmd_fusible_fragments", 0
        ):
            violations.append(
                f"mesh {q}: SPMD-fusible fragments shrank "
                f"{bsum.get('spmd_fusible_fragments', 0)} -> "
                f"{csum.get('spmd_fusible_fragments', 0)}"
            )
    return violations, skipped


# ---------------------------------------------------------------------------
# mode 4: black-box recorder gate (host cost + crash-survival smoke)
# ---------------------------------------------------------------------------


def run_blackbox_gate(budgets: dict):
    """Two checks so the black box can never silently rot:

    1. Recorder cost microbench: N records through the REAL
       record+persist path (worst case: fsync every record) — the host
       ms/barrier the recorder adds and the fsync-stall p99 must stay
       under ``blackbox.host_ms_per_barrier_max`` /
       ``fsync_p99_ms_max`` (the recorder rides EVERY barrier; the
       <1%-of-steady-barrier contract from PROFILE.md round 10).
    2. Reader smoke (write ring -> kill -> parse): a subprocess writes
       a segment in a loop, the parent SIGKILLs it mid-write (safe: a
       CPU-pinned process that holds no chip) and the reader CLI
       must still reconstruct a monotonic timeline.

    Returns (violations, report)."""
    import signal
    import subprocess
    import tempfile
    import time
    from types import SimpleNamespace

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from risingwave_tpu.blackbox import FlightRecorder, read_segment
    from risingwave_tpu.metrics import REGISTRY

    bb = budgets.get("blackbox", {})
    violations = []
    report = {}
    with tempfile.TemporaryDirectory() as tmp:
        rec = FlightRecorder()
        rec.configure(dir=tmp, fsync_interval_s=0.0)  # worst case
        REGISTRY.histograms.pop("blackbox_fsync_ms", None)
        n = 300
        t0 = time.perf_counter()
        for i in range(n):
            rec.record_barrier(
                SimpleNamespace(
                    epoch=i + 1,
                    seq=i + 1,
                    checkpoint=i % 4 == 0,
                    wall_ms=10.0,
                    stages_ms={"ingest": 1.0, "dispatch": 8.0},
                    achieved_bw_frac=0.01,
                    chunk_bytes=1 << 20,
                    state_bytes=1 << 22,
                )
            )
        ms_per_rec = (time.perf_counter() - t0) / n * 1e3
        rec.close()
        h = REGISTRY.histograms.get("blackbox_fsync_ms")
        fsync_p99 = h.percentile(99) if h is not None else 0.0
        report["host_ms_per_barrier"] = round(ms_per_rec, 4)
        report["fsync_p99_ms"] = round(fsync_p99, 3)
        mx = bb.get("host_ms_per_barrier_max")
        if mx is not None and ms_per_rec > mx:
            violations.append(
                f"blackbox: {ms_per_rec:.3f} recorder ms/barrier > "
                f"budget {mx} (the recorder rides EVERY barrier)"
            )
        mx = bb.get("fsync_p99_ms_max")
        if mx is not None and fsync_p99 > mx:
            violations.append(
                f"blackbox: fsync stall p99 {fsync_p99:.1f}ms > budget {mx}"
            )
        doc = read_segment(tmp)
        if len(doc["records"]) != n or not doc["monotonic"]:
            violations.append(
                f"blackbox: clean segment misparsed "
                f"({len(doc['records'])}/{n} records, "
                f"monotonic={doc['monotonic']})"
            )
    # -- reader smoke: write ring -> SIGKILL -> parse --------------------
    with tempfile.TemporaryDirectory() as tmp:
        child_code = (
            "import os, sys\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "sys.path.insert(0, %r)\n"
            "from types import SimpleNamespace\n"
            "from risingwave_tpu.blackbox import FlightRecorder\n"
            "rec = FlightRecorder()\n"
            "rec.configure(dir=%r, fsync_interval_s=0.1)\n"
            "i = 0\n"
            "while True:\n"
            "    i += 1\n"
            "    rec.record_barrier(SimpleNamespace(\n"
            "        epoch=i, seq=i, checkpoint=False, wall_ms=1.0,\n"
            "        stages_ms={'dispatch': 1.0}, achieved_bw_frac=0,\n"
            "        chunk_bytes=0, state_bytes=0))\n"
            "    if i == 40:\n"
            "        print('WROTE40', flush=True)\n"
        ) % (ROOT, tmp)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, "-c", child_code],
            stdout=subprocess.PIPE,
            text=True,
            env=env,
        )
        try:
            line = proc.stdout.readline()
            # mid-write murder: exactly the r04/r05 failure mode
            proc.send_signal(signal.SIGKILL)
            proc.wait(timeout=30)
        except Exception:
            proc.kill()
            proc.wait(timeout=10)
            line = ""
        if "WROTE40" not in line:
            violations.append("blackbox: reader-smoke child never wrote")
        else:
            cli = subprocess.run(
                [
                    sys.executable,
                    "-m",
                    "risingwave_tpu",
                    "blackbox",
                    tmp,
                    "--json",
                ],
                capture_output=True,
                text=True,
                env=env,
                cwd=ROOT,
            )
            ok = False
            if cli.returncode == 0:
                try:
                    doc = json.loads(cli.stdout.strip().splitlines()[-1])
                    ok = doc["monotonic"] and len(doc["records"]) >= 40
                    report["killed_segment_records"] = len(doc["records"])
                except (ValueError, KeyError, IndexError):
                    ok = False
            if not ok:
                violations.append(
                    "blackbox: reader CLI failed to reconstruct a "
                    f"SIGKILLed segment (rc={cli.returncode}, "
                    f"stderr={cli.stderr[-200:]!r})"
                )
    return violations, report


# ---------------------------------------------------------------------------
# mode 5: device-roofline gate (telemetry overhead + modeled bytes)
# ---------------------------------------------------------------------------


def _q5_steady_setup(events: int, fused: bool):
    """The q5 steady-state harness SHARED by the smoke and roofline
    gates: one pipeline, optional fusion, one fixed chunk pushed every
    epoch (fresh keys would grow the table — a legitimate recompile,
    not the regression these gates hunt). Returns ``(q5, wrappers,
    epoch_fn, rows_per_epoch)``."""
    from risingwave_tpu.connectors.nexmark import (
        NexmarkConfig,
        NexmarkGenerator,
    )
    from risingwave_tpu.queries.nexmark_q import build_q5_lite

    q5 = build_q5_lite(capacity=1 << 12, state_cleaning=False)
    wrappers = []
    if fused:
        from risingwave_tpu.runtime.fused_step import fuse_pipeline

        wrappers = fuse_pipeline(q5.pipeline, label="q5")
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=50_000))
    bid = gen.next_chunks(events, 1 << 11)["bid"].select(
        ["auction", "date_time"]
    )
    rows = int(bid.valid.sum())

    def epoch():
        q5.pipeline.push(bid)
        q5.pipeline.barrier()

    return q5, wrappers, epoch, rows


def run_roofline_gate(budgets: dict, epochs: int = 4, events: int = 2_000):
    """Three checks so the device-observability layer can never
    silently rot or get expensive:

    1. Telemetry host overhead: the fused telemetry lanes ride the
       existing staged-scalar read, so their ONLY cost is host-side
       decode+record — measured here against the steady fused-barrier
       wall and budgeted < ``telemetry_overhead_frac_max`` (the <1%
       contract).
    2. Modeled bytes exist: an armed deviceprof must produce a nonzero
       modeled-traffic figure for the fused q5 program (the byte
       accounting the roofline replaces host guesses with).
    3. Dispatch neutrality: telemetry+analysis armed, the steady fused
       barrier still costs exactly ONE device dispatch.

    Returns (violations, report)."""
    import time

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    jax.config.update("jax_platforms", "cpu")
    from risingwave_tpu.deviceprof import DEVICEPROF
    from risingwave_tpu.profiler import PROFILER

    rb = budgets.get("roofline", {})
    violations, report = [], {}
    DEVICEPROF.reset()
    DEVICEPROF.arm()
    _q5, _wrappers, epoch, _rows = _q5_steady_setup(events, fused=True)
    try:
        epoch()
        epoch()  # warm: compiles land outside the window
        DEVICEPROF.flush_analyses()  # deferred AOT analyses too
        DEVICEPROF.telemetry_host_ms = 0.0
        PROFILER.reset()
        PROFILER.enable(fence=False)
        per = []
        t0 = time.perf_counter()
        for _ in range(epochs):
            base = PROFILER.total_dispatches()
            epoch()
            per.append(PROFILER.total_dispatches() - base)
        wall_ms = (time.perf_counter() - t0) * 1e3
    finally:
        PROFILER.disable()
        PROFILER.reset()
    tel_ms = DEVICEPROF.telemetry_host_ms
    frac = tel_ms / wall_ms if wall_ms > 0 else 0.0
    DEVICEPROF.flush_analyses()  # any bucket the steady window minted
    model = DEVICEPROF.steady_model()
    report = {
        "telemetry_host_ms": round(tel_ms, 4),
        "steady_wall_ms": round(wall_ms, 2),
        "telemetry_overhead_frac": round(frac, 5),
        "modeled_bytes": model["modeled_bytes"],
        "padding_frac": model["padding_frac"],
        "dispatches_per_barrier": per,
    }
    mx = rb.get("telemetry_overhead_frac_max")
    if mx is not None and frac > mx:
        violations.append(
            f"roofline: telemetry host overhead {frac:.4f} of the "
            f"steady barrier > budget {mx} (the lanes must ride the "
            "existing staged read, not become a new cost)"
        )
    if not model["modeled_bytes"]:
        violations.append(
            "roofline: armed deviceprof produced NO modeled bytes for "
            "the fused q5 program — the byte accounting regressed to "
            "host guesses"
        )
    if per and max(per) > 1:
        violations.append(
            f"roofline: telemetry armed, steady fused barrier costs "
            f"{max(per):.0f} dispatches (must stay 1 — observability "
            "added a dispatch)"
        )
    DEVICEPROF.disarm()
    DEVICEPROF.reset()
    return violations, report


def run_serving_gate(budgets: dict):
    """The shared-arrangement serving gate (ROADMAP item 4, PR 12):
    run a CI-scale registration storm + concurrent-reader serving
    phase in-process (scripts/bench_serving.run_serving) and hold the
    structural invariants:

    - compile count bounded by plan-shape families, NOT MV count
      (constant lifting + arrangement attach);
    - arrangements == families (every further CREATE attached);
    - N shared MVs hold ~1x one private MV's device state
      (bytes_per_mv_ratio);
    - barrier p99 stays flat after the storm and bounded under
      concurrent reader load;
    - registry publish overhead < 1%% of the steady barrier;
    - zero reader errors (the lock-free path never serves torn or
      failed reads)."""
    b = budgets.get("serving", {})
    sys.path.insert(0, os.path.join(ROOT, "scripts"))
    from bench_serving import run_serving

    rep = run_serving(
        mvs=int(b.get("storm_mvs", 48)),
        families=int(b.get("families", 3)),
        readers=int(b.get("readers", 8)),
        read_seconds=float(b.get("read_seconds", 1.2)),
        exec_mode="graph",
        verbose=False,
    )
    v = []

    def gate(metric, budget_key, default, cmp="<="):
        if budget_key not in b and default is None:
            return
        budget = float(b.get(budget_key, default))
        val = float(rep[metric])
        bad = val > budget if cmp == "<=" else val < budget
        if bad:
            v.append(
                f"serving {metric} {val} violates budget "
                f"{budget_key}={budget}"
            )

    if rep["compile_programs"] < 0:
        # the compile-count invariant is this gate's headline: an
        # unreadable jit cache must fail loudly, not pass vacuously
        v.append(
            "serving compile_programs unreadable (jax jit cache API "
            "changed?) — the O(families) compile invariant cannot be "
            "gated"
        )
    else:
        gate("compile_programs", "compile_programs_max", 10)
    gate("arrangements", "arrangements_max", rep["families"])
    gate("bytes_per_mv_ratio", "bytes_per_mv_ratio_max", 0.2)
    gate(
        "barrier_p99_ms_post_storm", "post_storm_barrier_p99_ms_max", 150
    )
    gate(
        "barrier_p99_ms_under_read_load",
        "under_read_barrier_p99_ms_max",
        400,
    )
    gate("reader_p99_ms", "reader_p99_ms_max", 150)
    gate("registry_overhead_frac", "registry_overhead_frac_max", 0.01)
    gate("reads_per_s", "reads_per_s_min", 50, cmp=">=")
    gate("reader_error_count", "reader_errors_max", 0)
    if rep["arrangement_refs"] != rep["mvs"]:
        v.append(
            f"serving arrangement_refs {rep['arrangement_refs']} != "
            f"storm mvs {rep['mvs']} (an attach was lost)"
        )
    return v, rep


# ---------------------------------------------------------------------------
# mode 7: end-to-end freshness SLO gate (commit->visible, CPU, in-process)
# ---------------------------------------------------------------------------


def run_freshness_gate(budgets: dict, epochs: int = 6, events: int = 2_000):
    """The end-to-end freshness SLO gate (ROADMAP observability, PR 16):
    drive the fused q5 chain through a REAL StreamingRuntime — so every
    barrier runs the full _begin_trace -> dispatch -> publish ->
    _observe_freshness lifecycle, not a bare pipeline.barrier() — and
    hold five contracts:

    1. Commit->visible SLO: p99 of the per-barrier barrier-open ->
       snapshot-visible wall stays under
       ``commit_to_visible_p99_ms_max`` (the SLO the north star's
       "<1s freshness" claim is written in, at CPU smoke scale).
    2. The frontier is threaded: with a watermark injected every epoch,
       every steady barrier lands an ``event_time_lag_ms`` sample,
       p99-bounded by ``event_time_lag_p99_ms_max``.
    3. Dispatch neutrality: freshness armed, the steady fused barrier
       still costs at most ``fused_dispatches_per_barrier_max`` device
       dispatches (host-timestamps-only contract: tracking may never
       add a dispatch).
    4. Tracking overhead: FRESHNESS.host_ms (observe + backpressure
       attribution, self-measured) < ``tracking_overhead_frac_max`` of
       the steady window wall (the same <1% budget the blackbox ring
       and telemetry lanes live under).
    5. Attribution exists: the barrier trace names a
       ``backpressure_fragment`` (a slow barrier must name its
       bottleneck, not just a number).

    Returns (violations, report)."""
    import time

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    jax.config.update("jax_platforms", "cpu")
    from risingwave_tpu.connectors.nexmark import (
        NexmarkConfig,
        NexmarkGenerator,
    )
    from risingwave_tpu.freshness import FRESHNESS
    from risingwave_tpu.profiler import PROFILER
    from risingwave_tpu.queries.nexmark_q import build_q5_lite
    from risingwave_tpu.runtime import StreamingRuntime
    from risingwave_tpu.runtime.fused_step import fuse_pipeline

    fb = budgets.get("freshness", {})
    violations, report = [], {}
    q5 = build_q5_lite(capacity=1 << 12, state_cleaning=False)
    wrappers = fuse_pipeline(q5.pipeline, label="q5")
    if not wrappers:
        violations.append(
            "freshness: q5 did not fuse — the gate must measure the "
            "fused path (de-fusion regression)"
        )
        return violations, report
    rt = StreamingRuntime(store=None)
    rt.register("q5_mv", q5.pipeline)
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=50_000))
    bid = gen.next_chunks(events, 1 << 11)["bid"].select(
        ["auction", "date_time"]
    )

    def epoch(measure=None):
        # one fixed chunk per epoch (fresh keys would grow the table —
        # a legitimate recompile, not what this gate hunts) + a wall-
        # clock watermark so the event-time frontier advances. The
        # watermark WALK costs its own hop-executor dispatch (data-
        # plane work, identical with tracking off), so the neutrality
        # window brackets rt.barrier() alone: the full _begin_trace ->
        # dispatch -> publish -> _observe_freshness lifecycle.
        rt.push("q5_mv", bid)
        q5.pipeline.watermark("date_time", int(time.time() * 1000))
        if measure is None:
            rt.barrier()
        else:
            base = PROFILER.total_dispatches()
            rt.barrier()
            measure.append(PROFILER.total_dispatches() - base)

    epoch()
    epoch()  # warm: compiles + first-flush paths land outside the window
    FRESHNESS.reset()
    PROFILER.reset()
    PROFILER.enable(fence=False)
    try:
        per = []
        t0 = time.perf_counter()
        for _ in range(epochs):
            epoch(measure=per)
        wall_ms = (time.perf_counter() - t0) * 1e3
    finally:
        PROFILER.disable()
        PROFILER.reset()

    rows = [r for r in FRESHNESS.history(limit=4096) if r["mv"] == "q5_mv"]

    def _p99(key):
        vals = sorted(
            r[key] for r in rows if isinstance(r.get(key), (int, float))
        )
        if not vals:
            return None
        return vals[min(len(vals) - 1, int(0.99 * len(vals)))]

    c2v_p99 = _p99("commit_to_visible_ms")
    s2v_p99 = _p99("source_to_visible_ms")
    lag_p99 = _p99("event_time_lag_ms")
    frac = FRESHNESS.host_ms / wall_ms if wall_ms > 0 else 0.0
    tr = rt.last_epoch_trace
    bp_frag = getattr(tr, "backpressure_fragment", None) if tr else None
    report = {
        "freshness_samples": len(rows),
        "commit_to_visible_p99_ms": c2v_p99,
        "source_to_visible_p99_ms": s2v_p99,
        "event_time_lag_p99_ms": lag_p99,
        "tracking_host_ms": round(FRESHNESS.host_ms, 4),
        "steady_wall_ms": round(wall_ms, 2),
        "tracking_overhead_frac": round(frac, 5),
        "dispatches_per_barrier": per,
        "backpressure_fragment": bp_frag,
    }
    if len(rows) < epochs:
        violations.append(
            f"freshness: only {len(rows)} samples for {epochs} steady "
            "barriers — the runtime stopped observing freshness"
        )
    for key, val in (
        ("commit_to_visible_p99_ms_max", c2v_p99),
        ("source_to_visible_p99_ms_max", s2v_p99),
        ("event_time_lag_p99_ms_max", lag_p99),
    ):
        mx = fb.get(key)
        if mx is None:
            continue
        if val is None:
            violations.append(
                f"freshness: no samples to hold {key} against — the "
                f"{key.replace('_p99_ms_max', '')} lane went dark"
            )
        elif val > mx:
            violations.append(
                f"freshness: p99 {val:.1f}ms > budget {key}={mx} (SLO "
                "violated at CPU smoke scale)"
            )
    mx = fb.get("fused_dispatches_per_barrier_max")
    if mx is not None and per and max(per) > mx:
        violations.append(
            f"freshness: tracking armed, steady fused barrier costs "
            f"{max(per):.0f} dispatches > budget {mx} — freshness "
            "tracking added a device dispatch"
        )
    mx = fb.get("tracking_overhead_frac_max")
    if mx is not None and frac > mx:
        violations.append(
            f"freshness: host tracking overhead {frac:.4f} of the "
            f"steady barrier > budget {mx} (must stay host-cheap)"
        )
    if bp_frag is None:
        violations.append(
            "freshness: no backpressure_fragment verdict on the last "
            "barrier trace — attribution went dark"
        )
    return violations, report


def _overload_workload():
    """A compact governed workload for the overload gate: skewed-key
    storm source (offset-addressed, checkpointable) -> HashAgg(count,
    sum) -> host MV on a real StreamingRuntime, with the agg wired to
    the cold tier and a lagging commit lane — the same physics the
    tier-1 chaos tests drive, at CI scale. Returns a ``make`` thunk
    satisfying the OverloadChaosRunner workload contract."""
    import numpy as np
    import jax.numpy as jnp

    from risingwave_tpu.array.chunk import StreamChunk
    from risingwave_tpu.executors.hash_agg import HashAggExecutor
    from risingwave_tpu.executors.materialize import MaterializeExecutor
    from risingwave_tpu.ops.agg import AggCall
    from risingwave_tpu.runtime import SourceManager, StreamingRuntime
    from risingwave_tpu.runtime.pipeline import Pipeline
    from risingwave_tpu.storage.object_store import MemObjectStore
    from risingwave_tpu.storage.state_table import (
        CheckpointManager,
        Checkpointable,
        StateDelta,
    )

    cap = 1 << 9

    class _Split:
        split_id = "storm-0"

    class _Storm(Checkpointable):
        table_id = "storm.src"

        def __init__(self, seed, hot=48):
            self.seed = seed
            self.hot = hot
            self.offset = 0
            self._committed = 0
            self.splits = [_Split()]

        def discover(self):
            pass

        def _key(self, i):
            h = (i * 2654435761 + self.seed * 40503) & 0xFFFFFFFF
            if h % 3 == 0:
                return h % self.hot
            return self.hot + (h % (256 + i // 3))

        def poll(self, max_rows_per_split, capacity, only=None):
            n, chunks = int(max_rows_per_split), []
            while n > 0:
                take = min(n, capacity)
                idx = np.arange(
                    self.offset, self.offset + take, dtype=np.int64
                )
                keys = np.asarray(
                    [self._key(int(i)) for i in idx], np.int64
                )
                chunks.append(
                    StreamChunk.from_numpy(
                        {"k": keys, "v": (idx % 97).astype(np.int64)},
                        capacity,
                    )
                )
                self.offset += take
                n -= take
            return chunks

        def checkpoint_delta(self):
            if self.offset == self._committed:
                return []
            self._committed = self.offset
            return [
                StateDelta(
                    "storm.src",
                    {"k": np.zeros(1, np.int64)},
                    {"offset": np.asarray([self.offset], np.int64)},
                    np.zeros(1, bool),
                    ("k",),
                )
            ]

        def restore_state(self, table_id, key_cols, value_cols):
            off = value_cols.get("offset") if value_cols else None
            self.offset = (
                int(off[0]) if off is not None and len(off) else 0
            )
            self._committed = self.offset

    class _Governed:
        K_COMMIT = 8

        def __init__(self, seed):
            self.agg = HashAggExecutor(
                group_keys=("k",),
                calls=(
                    AggCall("count_star", None, "cnt"),
                    AggCall("sum", "v", "s"),
                ),
                schema_dtypes={"k": jnp.int64, "v": jnp.int64},
                capacity=cap,
                out_cap=1 << 11,
                table_id="storm.agg",
            )
            self.mview = MaterializeExecutor(
                pk=("k",), columns=("cnt", "s"), table_id="storm.mv"
            )
            self.runtime = StreamingRuntime(store=None)
            self.runtime.register(
                "storm", Pipeline([self.agg, self.mview])
            )
            self.sources = SourceManager()
            self.src = _Storm(seed)
            self.sources.register("bids", self.src)
            self.fragment_of = {"bids": "storm"}
            self.mgr = CheckpointManager(MemObjectStore())
            self.agg.cold_reader = lambda keys: self.mgr.get_rows(
                "storm.agg", keys
            )
            self._epoch = 0

        def ingest(self, max_rows):
            if max_rows <= 0:
                return 0
            before = self.src.offset
            for ch in self.sources.poll(
                "bids", max_rows_per_split=max_rows, capacity=cap
            ):
                self.runtime.push("storm", ch)
            return self.src.offset - before

        def barrier(self):
            self.runtime.barrier()
            self._epoch += 1
            if self._epoch % self.K_COMMIT == 0:
                self.mgr.commit_epoch(
                    self._epoch << 16,
                    [self.agg, self.mview, self.src],
                )

        def drain(self):
            self._epoch += 1
            self.mgr.commit_epoch(
                self._epoch << 16, [self.agg, self.mview, self.src]
            )

        def mv(self):
            return self.mview.snapshot()

    return _Governed


def run_overload_gate(
    budgets: dict, storm_rows: int = 4_000, burst_rows: int = 1_000
):
    """The overload-protection gate (ROADMAP robustness, PR 17), two
    legs:

    1. CHAOS LEG — the seeded OverloadChaosRunner at CI scale: a
       bursty skewed-key storm against the memory-governed runtime.
       The runner itself enforces zero OOM (ledger <= budget on every
       governed barrier), zero wedge (lag, never loss), and descent
       back to NORMAL; the gate additionally holds the governed MV
       bit-identical to the unthrottled twin, bounds ladder flapping
       (``throttle_flaps_max``) and bounds how many post-storm
       barriers recovery may take (``recover_within_barriers_max``).
    2. STEADY LEG — a calm governed run with generous budget: the
       governor's self-measured host_ms must stay under
       ``governor_overhead_frac_max`` of the steady barrier wall (the
       same <1% class as freshness tracking and the blackbox ring),
       and the ledger must reconcile against an independent
       ``state_nbytes()`` walk within ``ledger_drift_frac_max`` (a
       stale or double-charged ledger is an OOM-by-lies).

    Returns (violations, report)."""
    import time

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    jax.config.update("jax_platforms", "cpu")
    from risingwave_tpu.sim import OverloadChaosRunner, chaos_seed

    ob = budgets.get("overload", {})
    violations, report = [], {}
    make = _overload_workload()
    seed = chaos_seed(11)

    # -- leg 1: the storm ------------------------------------------------
    runner = OverloadChaosRunner(
        make=lambda: make(seed),
        seed=seed,
        storm_rows=storm_rows,
        burst_rows=burst_rows,
        drain_epochs=40,
        max_epochs=300,
        # how deep the ladder stacks before relief lands is scale-
        # dependent; the gate requires the ladder to BITE (>=2 states,
        # runner-enforced) and to fully recover, not a fixed depth
        require_full_ladder=False,
    )
    try:
        got, want = runner.run()
    except RuntimeError as e:
        # the runner's own contract failed: OOM, wedge, or no recovery
        violations.append(f"overload: {e}")
        return violations, report
    rep = runner.report
    report.update(
        {
            "states_seen": rep.get("states_seen"),
            "storm_epochs": rep.get("epochs"),
            "drain_barriers": rep.get("drain_barriers"),
            "budget_bytes": rep.get("budget"),
            "ledger_high": rep.get("ledger_high"),
            "vetoes": rep.get("vetoes"),
            "spills": rep.get("spills"),
            "parked_polls": rep.get("parked_polls"),
            "flaps": rep.get("flaps"),
        }
    )
    if got != want:
        violations.append(
            "overload: governed MV diverged from the unthrottled twin "
            "— admission control broke exactly-once"
        )
    mx = ob.get("throttle_flaps_max")
    if mx is not None and rep.get("flaps", 0) > mx:
        violations.append(
            f"overload: ladder flapped {rep['flaps']}x > budget {mx} "
            "(thrashing between rungs — hysteresis regressed)"
        )
    mx = ob.get("recover_within_barriers_max")
    if mx is not None and rep.get("drain_barriers", 0) > mx:
        violations.append(
            f"overload: {rep['drain_barriers']} post-storm barriers to "
            f"reach NORMAL > budget {mx} (recovery stalled)"
        )

    # -- leg 2: steady overhead + ledger reconciliation ------------------
    obj = make(seed)
    gov = obj.runtime.memory_governor
    gov.budget_bytes = 1 << 30  # generous: governed but never pressed
    gov.enabled = True
    obj.sources.attach_admission(gov.admission, obj.fragment_of)
    obj.ingest(512)
    obj.barrier()
    obj.barrier()  # warm: compiles + gate attachment out of the window
    gov.host_ms = 0.0
    epochs = 24
    t0 = time.perf_counter()
    for _ in range(epochs):
        obj.ingest(256)
        obj.barrier()
    wall_ms = (time.perf_counter() - t0) * 1e3
    frac = gov.host_ms / wall_ms if wall_ms > 0 else 0.0
    walk = 0
    for ex in obj.runtime.executors():
        fn = getattr(ex, "state_nbytes", None)
        if fn is not None:
            try:
                walk += int(fn())
            except Exception:  # noqa: BLE001
                pass
    drift = (
        abs(gov.ledger_total - walk) / walk if walk > 0 else 0.0
    )
    report.update(
        {
            "steady_wall_ms": round(wall_ms, 2),
            "governor_host_ms": round(gov.host_ms, 4),
            "governor_overhead_frac": round(frac, 5),
            "ledger_bytes": gov.ledger_total,
            "ledger_walk_bytes": walk,
            "ledger_drift_frac": round(drift, 5),
        }
    )
    mx = ob.get("governor_overhead_frac_max")
    if mx is not None and frac > mx:
        violations.append(
            f"overload: governor host overhead {frac:.4f} of the "
            f"steady barrier > budget {mx} (the ledger walk must stay "
            "host-cheap)"
        )
    mx = ob.get("ledger_drift_frac_max")
    if mx is not None and drift > mx:
        violations.append(
            f"overload: ledger {gov.ledger_total}B vs independent "
            f"state_nbytes walk {walk}B — drift {drift:.4f} > budget "
            f"{mx} (a lying ledger un-guards the budget)"
        )
    return violations, report


def run_mesh_gate(budgets: dict):
    """The mesh-observability gate (ROADMAP multi-chip, ISSUE 18), run
    in a SUBPROCESS: the child pins ``JAX_PLATFORMS=cpu`` with
    ``--xla_force_host_platform_device_count=8`` so a REAL 8-virtual-
    device mesh drives the sharded q5/q8 fragments — while this
    parent's other gates never see the forced device count. Contracts
    (child-measured, parent-compared against ``budgets["mesh"]``):

    1. Attribution coverage: per-shard + exchange-phase attribution
       covers >= ``attribution_coverage_min`` of the measured sharded
       barrier wall on q5 AND q8.
    2. Bit-identity: the telemetry-armed q5 run's MV content equals an
       unarmed twin fed identical chunks (observability may never
       touch results).
    3. Overhead: MESHPROF's self-measured host_ms over the steady
       armed window < ``mesh_overhead_frac_max`` of the window wall
       (calibration probes are booked separately and excluded).
    4. Skew teeth: a seeded constant-key workload fires a hot-shard
       verdict naming exactly the shard the router sends the key to.
    5. Zero profiler errors.

    Returns (violations, report)."""
    import subprocess

    mb = budgets.get("mesh", {})
    violations, report = [], {}
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    cmd = [
        sys.executable,
        os.path.abspath(__file__),
        "--mesh-child",
    ]
    try:
        proc = subprocess.run(
            cmd,
            env=env,
            cwd=ROOT,
            capture_output=True,
            text=True,
            timeout=900,
        )
    except subprocess.TimeoutExpired:
        violations.append("mesh: child timed out (900s)")
        return violations, report
    tail = None
    for line in proc.stdout.splitlines():
        if line.startswith("MESH_CHILD_JSON: "):
            tail = line[len("MESH_CHILD_JSON: "):]
    if tail is None:
        violations.append(
            f"mesh: child produced no report (rc {proc.returncode}); "
            f"stderr tail: {proc.stderr[-500:]!r}"
        )
        return violations, report
    try:
        report = json.loads(tail)
    except json.JSONDecodeError as e:
        violations.append(f"mesh: unparseable child report: {e}")
        return violations, report
    if report.get("fatal"):
        violations.append(f"mesh: child failed: {report['fatal']}")
        return violations, report

    mn = mb.get("attribution_coverage_min")
    if mn is not None:
        for q in ("q5", "q8"):
            cov = report.get(f"{q}_coverage_frac")
            if cov is None:
                violations.append(f"mesh: no {q} coverage measured")
            elif cov < mn:
                violations.append(
                    f"mesh: {q} attribution covers {cov:.1%} of the "
                    f"sharded barrier wall < budget {mn:.0%} (per-"
                    "shard/exchange accounting lost track of the wall)"
                )
    if mb.get("require_bit_identical") and not report.get(
        "bit_identical"
    ):
        violations.append(
            "mesh: telemetry-armed q5 MV diverged from the unarmed "
            "twin — observability touched results"
        )
    mx = mb.get("mesh_overhead_frac_max")
    frac = report.get("overhead_frac")
    if mx is not None and frac is not None and frac > mx:
        violations.append(
            f"mesh: profiler host overhead {frac:.4f} of the steady "
            f"armed barrier > budget {mx} (per-shard accounting must "
            "stay host-cheap)"
        )
    if mb.get("require_skew_verdict"):
        if not report.get("skew_detected"):
            violations.append(
                "mesh: seeded constant-key workload fired NO hot-"
                "shard verdict (skew detection regressed)"
            )
        elif report.get("skew_shard") != report.get("expected_shard"):
            violations.append(
                f"mesh: skew verdict named shard "
                f"{report.get('skew_shard')} but the router sends the "
                f"seeded key to shard {report.get('expected_shard')}"
            )
    mx = mb.get("errors_max")
    if mx is not None and report.get("errors", 0) > mx:
        violations.append(
            f"mesh: {report['errors']} profiler errors > budget {mx}"
        )
    return violations, report


def run_mesh_child() -> int:
    """In-process body of the mesh gate (``--mesh-child``): assumes the
    parent exported the 8-virtual-device CPU env. Prints one
    ``MESH_CHILD_JSON:`` line; exit code 0 unless the workload itself
    crashed (budget comparison happens in the parent)."""
    import time

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    report: dict = {}
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np

        jax.config.update("jax_platforms", "cpu")
        if jax.device_count() < 8:
            raise RuntimeError(
                f"need 8 virtual devices, got {jax.device_count()}"
            )
        from risingwave_tpu.connectors.nexmark import (
            AUCTION_SCHEMA,
            BID_SCHEMA,
            PERSON_SCHEMA,
            NexmarkConfig,
            NexmarkGenerator,
        )
        from risingwave_tpu.parallel.exchange import dest_shard
        from risingwave_tpu.parallel.meshprof import MESHPROF, _key_fn_for
        from risingwave_tpu.parallel.sharded_agg import ShardedHashAgg
        from risingwave_tpu.runtime.fragmenter import sharded_planned_mv
        from risingwave_tpu.sql import Catalog, StreamPlanner

        q5_sql = (
            "CREATE MATERIALIZED VIEW q5 AS "
            "SELECT auction, window_start, count(*) AS num "
            "FROM HOP(bid, date_time, INTERVAL '2' SECOND, "
            "INTERVAL '10' SECOND) GROUP BY auction, window_start"
        )
        q8_sql = (
            "CREATE MATERIALIZED VIEW q8 AS "
            "SELECT p.id, p.name, p.starttime FROM "
            "(SELECT id, name, window_start AS starttime "
            " FROM TUMBLE(person, date_time, INTERVAL '10' SECOND) "
            " GROUP BY id, name, window_start) AS p "
            "JOIN "
            "(SELECT seller, window_start AS astarttime "
            " FROM TUMBLE(auction, date_time, INTERVAL '10' SECOND) "
            " GROUP BY seller, window_start) AS a "
            "ON p.id = a.seller AND p.starttime = a.astarttime"
        )
        catalog = Catalog(
            {
                "bid": BID_SCHEMA,
                "person": PERSON_SCHEMA,
                "auction": AUCTION_SCHEMA,
            }
        )

        def factory():
            return lambda: StreamPlanner(catalog, capacity=1 << 12)

        gen = NexmarkGenerator(NexmarkConfig())
        bids = []
        while len(bids) < 6:
            c = gen.next_chunks(1_500, 1 << 11)["bid"]
            if c is not None:
                bids.append(c)

        # -- leg 1: unarmed q5 twin (the bit-identity baseline) -------
        unarmed = sharded_planned_mv(factory(), q5_sql, n_shards=8)
        try:
            for c in bids:
                unarmed.pipeline.push(c)
                unarmed.pipeline.barrier()
            want = unarmed.mview.snapshot()
        finally:
            unarmed.pipeline.close()

        # -- leg 2: armed q5 — coverage + overhead + bit-identity -----
        MESHPROF.enable(probes=True)
        q5 = sharded_planned_mv(factory(), q5_sql, n_shards=8)
        MESHPROF.watch(q5.pipeline, name="q5")
        try:
            for c in bids[:2]:  # warm: compiles + probe calibration
                q5.pipeline.push(c)
                q5.pipeline.barrier()
            MESHPROF.host_ms = 0.0
            t0 = time.perf_counter()
            for c in bids[2:]:
                q5.pipeline.push(c)
                q5.pipeline.barrier()
            steady_ms = (time.perf_counter() - t0) * 1e3
            got = q5.mview.snapshot()
        finally:
            q5.pipeline.close()
        doc = MESHPROF.barriers[-1]
        report["q5_coverage_frac"] = doc["coverage_frac"]
        report["q5_wall_ms"] = doc["wall_ms"]
        report["q5_phases_ms"] = doc["phases_ms"]
        report["q5_shard_local_ms"] = doc["shard_local_ms"]
        report["q5_exchange_rows"] = doc["exchange"]["rows"]
        report["bit_identical"] = got == want
        report["steady_wall_ms"] = round(steady_ms, 2)
        report["mesh_host_ms"] = round(MESHPROF.host_ms, 3)
        report["calibration_ms"] = round(MESHPROF.calibration_ms, 2)
        report["overhead_frac"] = round(
            MESHPROF.host_ms / steady_ms if steady_ms > 0 else 0.0, 5
        )

        # -- leg 3: armed q8 (join shape) — coverage ------------------
        MESHPROF.reset_stats()
        MESHPROF.enable(probes=False)
        q8 = sharded_planned_mv(factory(), q8_sql, n_shards=8)
        MESHPROF.watch(q8.pipeline, name="q8")
        gen8 = NexmarkGenerator(NexmarkConfig())
        try:
            for _ in range(4):
                chunks = gen8.next_chunks(2_000, 2048)
                if chunks["person"] is not None:
                    q8.pipeline.push_left(chunks["person"])
                if chunks["auction"] is not None:
                    q8.pipeline.push_right(chunks["auction"])
                q8.pipeline.barrier()
        finally:
            q8.pipeline.close()
        doc8 = MESHPROF.barriers[-1]
        report["q8_coverage_frac"] = doc8["coverage_frac"]
        report["q8_wall_ms"] = doc8["wall_ms"]

        # -- leg 4: seeded skew — constant grouping key ---------------
        MESHPROF.reset_stats()
        hot_sql = (
            "CREATE MATERIALIZED VIEW hot AS "
            "SELECT auction, count(*) AS n FROM bid GROUP BY auction"
        )
        hot = sharded_planned_mv(factory(), hot_sql, n_shards=8)
        MESHPROF.watch(hot.pipeline, name="hot")
        agg = next(
            ex
            for ex in hot.pipeline.executors
            if isinstance(ex, ShardedHashAgg)
        )
        skew_key = 1007
        try:
            for c in bids[:3]:
                auc = np.asarray(c.col("auction"))
                c = c.with_columns(
                    auction=jnp.asarray(
                        np.full(auc.shape, skew_key, auc.dtype)
                    )
                )
                if "expected_shard" not in report:
                    kf = _key_fn_for(agg, "agg", None)
                    dest = np.asarray(dest_shard(kf(c), 8))
                    live = np.asarray(c.valid)
                    report["expected_shard"] = int(dest[live][0])
                hot.pipeline.push(c)
                hot.pipeline.barrier()
        finally:
            hot.pipeline.close()
        sk = MESHPROF.barriers[-1]["skew"]
        report["skew_detected"] = sk is not None
        report["skew_shard"] = sk["shard"] if sk else None
        report["skew_ratio"] = sk["ratio"] if sk else None
        report["errors"] = MESHPROF.errors
        MESHPROF.disable()
    except Exception as e:  # noqa: BLE001 — parent turns this into a violation
        report["fatal"] = repr(e)
    print(f"MESH_CHILD_JSON: {json.dumps(report)}")
    return 0


def _engine_generation() -> int:
    """Load provenance.py BY PATH: the pure-JSON gate mode must stay
    jax-free, and importing the package would pull jax in via
    __init__."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_rw_provenance",
        os.path.join(ROOT, "risingwave_tpu", "provenance.py"),
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.ENGINE_GENERATION


def generation_warnings(artifact: dict, label: str):
    """Provenance check: ratcheting against an artifact written by an
    OLDER engine generation is exactly the stale-artifact confusion
    that cost a re-anchor — warn loudly (not a violation: old
    artifacts stay comparable for the fields they carry)."""
    ENGINE_GENERATION = _engine_generation()
    # bench artifacts stamp at top level; fusion reports under the
    # "_"-prefixed key the ratchet loop skips
    prov = artifact.get("_provenance") or artifact
    gen = prov.get("engine_generation")
    if gen is None:
        return [
            f"{label}: no engine_generation stamp (predates PR 11 "
            "provenance) — treat its numbers as a DIFFERENT engine's"
        ]
    if int(gen) < ENGINE_GENERATION:
        return [
            f"{label}: written by engine generation {gen} < current "
            f"{ENGINE_GENERATION} (sha {prov.get('git_sha', '?')[:12]}"
            f", tag {prov.get('pr_tag', '?')}) — numbers may not "
            "be comparable"
        ]
    return []


# ---------------------------------------------------------------------------
# mode 2: steady-state smoke microbench (CPU, in-process)
# ---------------------------------------------------------------------------


def _smoke_leg(budgets: dict, fused: bool, epochs: int, events: int):
    """One q5 steady-state microbench leg (interpreted or fused) with
    the profiler armed. Returns (violations, report)."""
    from risingwave_tpu.metrics import REGISTRY
    from risingwave_tpu.profiler import PROFILER

    sb = budgets.get("smoke", {})
    leg = "fused" if fused else "smoke"
    _q5, wrappers, epoch, rows = _q5_steady_setup(events, fused)
    epoch()
    epoch()  # warm: compiles + first-flush paths
    PROFILER.reset()
    PROFILER.enable(fence=False)  # count + host-attribute, no fencing
    try:
        per_epoch = []
        for _ in range(epochs):
            base = PROFILER.total_dispatches()
            epoch()
            per_epoch.append(PROFILER.total_dispatches() - base)
        h = REGISTRY.histograms.get("executor_ms")
        host_ms = sum(h._sum.values()) if h is not None else 0.0
        fused_labels = [
            k for k in PROFILER.dispatch_counts() if k.startswith("fused:")
        ]
    finally:
        PROFILER.disable()
        PROFILER.reset()
    dpb = max(per_epoch) if per_epoch else 0.0
    ms_per_row = host_ms / max(rows * epochs, 1)
    report = {
        f"{leg}_dispatches_per_barrier": per_epoch,
        f"{leg}_python_ms_per_row": round(ms_per_row, 5),
        "rows_per_epoch": rows,
    }
    violations = []
    mx = sb.get(
        "fused_dispatches_per_barrier_max"
        if fused
        else "dispatches_per_barrier_max"
    )
    if mx is not None and dpb > mx:
        violations.append(
            f"{leg}: {dpb} device dispatches/barrier > budget {mx}"
        )
    mx = sb.get("python_ms_per_row_max")
    if mx is not None and ms_per_row > mx:
        violations.append(
            f"{leg}: {ms_per_row:.5f} host-python ms/row > budget {mx}"
        )
    if len(set(per_epoch)) > 1:
        violations.append(
            f"{leg}: steady-state dispatch count not stable: {per_epoch} "
            "(shape-unstable epoch — recompile hazard)"
        )
    if fused:
        # a silently de-fused fragment would fall back to interpretation
        # and only get SLOWER — fail CI loudly instead
        report["fused_fragments"] = len(wrappers)
        report["fused_whole_chain"] = bool(wrappers) and all(
            w.covers_whole_chain for w in wrappers
        )
        if not wrappers or not report["fused_whole_chain"]:
            violations.append(
                "fused: the q5 chain did not fuse whole "
                f"({len(wrappers)} wrappers) — fragment silently de-fused"
            )
        elif not fused_labels:
            violations.append(
                "fused: no fused:<fragment> dispatch attribution recorded "
                "— the fused program never ran (de-fused fallback?)"
            )
    return violations, report


def _two_input_leg(budgets: dict, query: str, epochs: int = 3):
    """One fused two-input steady-state leg (q7 or q8): the whole
    side-chains x join x MV barrier must cost at most
    ``two_input_dispatches_per_barrier_max`` device dispatches (the
    de-fusion tripwire: a silently-interpreted q7 costs ~31), with the
    ``fused:`` attribution present and the pipeline actually carrying
    the whole-fusion wrapper."""
    from risingwave_tpu.connectors.nexmark import (
        NexmarkConfig,
        NexmarkGenerator,
    )
    from risingwave_tpu.profiler import PROFILER
    from risingwave_tpu.queries.nexmark_q import build_q7, build_q8
    from risingwave_tpu.runtime.fused_step import fuse_pipeline

    sb = budgets.get("smoke", {})
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=10_000))
    if query == "q7":
        q = build_q7(
            capacity=1 << 13,
            agg_capacity=1 << 11,
            filter_capacity=1 << 11,
            out_cap=1 << 11,
        )

        def epoch(measure):
            bid = None
            while bid is None:
                bid = gen.next_chunks(1000, 1024)["bid"]
            bid = bid.select(["auction", "bidder", "price", "date_time"])
            q.pipeline.push_left(bid)
            q.pipeline.push_right(bid)
            mx = int(bid.to_numpy()["date_time"].max())
            if measure is not None:
                base = PROFILER.total_dispatches()
                q.pipeline.barrier()
                measure.append(PROFILER.total_dispatches() - base)
            else:
                q.pipeline.barrier()
            q.pipeline.watermark("date_time", mx)
    else:
        q = build_q8(capacity=1 << 12, out_cap=1 << 11)

        def epoch(measure):
            ev = gen.next_chunks(2000, 4096)
            p, a = ev["person"], ev["auction"]
            if p is not None:
                q.pipeline.push_left(
                    p.select(["id", "name", "date_time"])
                )
            if a is not None:
                q.pipeline.push_right(a.select(["seller", "date_time"]))
            if measure is not None:
                base = PROFILER.total_dispatches()
                q.pipeline.barrier()
                measure.append(PROFILER.total_dispatches() - base)
            else:
                q.pipeline.barrier()

    wrappers = fuse_pipeline(q.pipeline, label=query)
    violations, report = [], {}
    fused_whole = (
        getattr(q.pipeline, "_fused", None) is not None
        and len(wrappers) == 1
        and wrappers[0].covers_whole_chain
    )
    report[f"{query}_fused_whole_chain"] = fused_whole
    if not fused_whole:
        violations.append(
            f"{query}: two-input pipeline did not fuse whole "
            "(silent de-fusion — see fusion_refusals())"
        )
        return violations, report
    for _ in range(4):
        epoch(None)  # warm: compiles + growth transitions
    PROFILER.reset()
    PROFILER.enable(fence=False)
    try:
        per = []
        for _ in range(epochs):
            epoch(per)
        fused_labels = [
            k
            for k in PROFILER.dispatch_counts()
            if k.startswith("fused:")
        ]
    finally:
        PROFILER.disable()
        PROFILER.reset()
    report[f"{query}_dispatches_per_barrier"] = per
    mx = sb.get("two_input_dispatches_per_barrier_max")
    if mx is not None and per and max(per) > mx:
        violations.append(
            f"{query}: {max(per)} device dispatches/barrier > budget "
            f"{mx} (two-input de-fusion regression)"
        )
    if not fused_labels:
        violations.append(
            f"{query}: no fused:<fragment> dispatch attribution — the "
            "two-input program never ran"
        )
    return violations, report


def _pipelining_leg(budgets: dict):
    """K-barrier pipelining microbench (q8, K=1 vs K=2): mid-window
    barriers must defer the blocking staged-scalar read, so their
    host barrier-call latency sits WELL below the K=1 per-barrier
    latency (``k_midwindow_barrier_p50_frac_max``); the full host
    ms/row of both modes is reported for the PROFILE ledger."""
    import time

    import numpy as np

    from risingwave_tpu.connectors.nexmark import (
        NexmarkConfig,
        NexmarkGenerator,
    )
    from risingwave_tpu.queries.nexmark_q import build_q8
    from risingwave_tpu.runtime.fused_step import fuse_pipeline

    sb = budgets.get("smoke", {})

    def run(depth, nb=16, warm=8):
        gen = NexmarkGenerator(NexmarkConfig(first_event_rate=10_000))
        q8 = build_q8(capacity=1 << 12, out_cap=1 << 11)
        (w,) = fuse_pipeline(
            q8.pipeline, label="q8", pipeline_depth=depth
        )
        lat = []
        rows = 0

        def epoch(measure):
            nonlocal rows
            ev = gen.next_chunks(2000, 4096)
            p, a = ev["person"], ev["auction"]
            if p is not None:
                c = p.select(["id", "name", "date_time"])
                q8.pipeline.push_left(c)
                if measure:
                    rows += int(c.to_numpy()["id"].shape[0])
            if a is not None:
                c = a.select(["seller", "date_time"])
                q8.pipeline.push_right(c)
                if measure:
                    rows += int(c.to_numpy()["seller"].shape[0])
            t0 = time.perf_counter()
            q8.pipeline.barrier()
            if measure:
                lat.append((time.perf_counter() - t0) * 1e3)

        for _ in range(warm):
            epoch(False)
        w.finish_barrier(force=True)
        t0 = time.perf_counter()
        for _ in range(nb - warm):
            epoch(True)
        w.finish_barrier(force=True)
        wall_ms = (time.perf_counter() - t0) * 1e3
        return lat, wall_ms / max(rows, 1), w.depth

    lat1, row_ms1, _ = run(1)
    lat2, row_ms2, depth = run(2)
    # non-boundary barriers only: under K=2 every other barrier defers
    midwindow = lat2[0::2]
    p50_k1 = float(np.percentile(lat1, 50))
    p50_mid = float(np.percentile(midwindow, 50))
    report = {
        "pipelining_depth": depth,
        "k1_barrier_p50_ms": round(p50_k1, 3),
        "k2_midwindow_barrier_p50_ms": round(p50_mid, 3),
        "k1_host_ms_per_row": round(row_ms1, 6),
        "k2_host_ms_per_row": round(row_ms2, 6),
    }
    violations = []
    frac = sb.get("k_midwindow_barrier_p50_frac_max")
    if frac is not None and p50_k1 > 0 and p50_mid > p50_k1 * frac:
        violations.append(
            f"pipelining: K=2 mid-window barrier p50 {p50_mid:.2f}ms "
            f"not below {frac} x K=1 p50 {p50_k1:.2f}ms — the deferred "
            "finish stopped deferring"
        )
    return violations, report


def run_smoke(budgets: dict, epochs: int = 4, events: int = 2_000):
    """Steady state with the profiler armed, FOUR legs: the q5
    interpreted per-executor walk (bounded device dispatches per
    barrier + host-python ms per row), the q5 fused per-barrier step
    (tighter budget + de-fusion tripwire), the fused TWO-INPUT legs
    (q7/q8: whole side-chains x join x MV barriers at <=
    ``two_input_dispatches_per_barrier_max`` dispatches — q7 costs ~31
    interpreted), and the K-barrier pipelining microbench (mid-window
    barriers must actually defer the blocking read). Returns
    (violations, report dict)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if ROOT not in sys.path:  # runnable as a script from anywhere
        sys.path.insert(0, ROOT)
    import jax

    jax.config.update("jax_platforms", "cpu")
    violations, report = _smoke_leg(budgets, False, epochs, events)
    v2, r2 = _smoke_leg(budgets, True, epochs, events)
    violations += v2
    report.update(r2)
    for q in ("q7", "q8"):
        v3, r3 = _two_input_leg(budgets, q)
        violations += v3
        report.update(r3)
    v4, r4 = _pipelining_leg(budgets)
    violations += v4
    report.update(r4)
    return violations, report


def run_integrity_gate(budgets: dict, epochs: int = 4):
    """The end-to-end state-integrity gate, four legs:

    1. Dispatch neutrality: the device digest lanes are ALWAYS-ON in
       the fused programs; the steady fused q5 barrier must still cost
       at most ``q5_dispatches_per_barrier_max`` device dispatches,
       and the q7/q8 two-input barriers must hold the smoke tier's
       ``two_input_dispatches_per_barrier_max`` — the digests ride the
       existing staged scalar read or they don't ship.
    2. Host overhead: crc verification + host digests on the commit
       path (``RW_STATE_DIGEST=1``) must stay under
       ``host_overhead_frac_max`` of the steady barrier+commit wall.
    3. Scrub smoke: the committed fixture scrubs all-ok; ONE flipped
       byte at rest must be detected (corrupt + quarantined) by the
       next scrub.
    4. Verified recovery at CI scale: corrupt the NEWEST committed SST
       at rest; a fresh manager must walk back to the newest fully-
       verifying epoch, restore its exact row image, and emit a
       ``state_corruption`` event naming the quarantined artifact.

    Returns (violations, report)."""
    import time

    import numpy as np

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    import jax

    jax.config.update("jax_platforms", "cpu")
    from risingwave_tpu import integrity
    from risingwave_tpu.event_log import EVENT_LOG
    from risingwave_tpu.profiler import PROFILER
    from risingwave_tpu.storage.object_store import MemObjectStore
    from risingwave_tpu.storage.state_table import (
        CheckpointManager,
        Checkpointable,
        StateDelta,
    )

    ib = budgets.get("integrity", {})
    violations, report = [], {}

    # -- legs 1+2: fused q5 steady window with per-epoch commits ----------
    prev = os.environ.get("RW_STATE_DIGEST")
    os.environ["RW_STATE_DIGEST"] = "1"
    try:
        q5, wrappers, epoch, _rows = _q5_steady_setup(2_000, fused=True)
        store = MemObjectStore()
        mgr = CheckpointManager(store)
        # the fused wrapper replaces pipeline.executors; the MEMBER
        # objects stay the checkpointing system of record
        members = wrappers[0].members if wrappers else q5.pipeline.executors

        def commit(ep):
            mgr.commit_staged(ep << 16, mgr.stage(members))

        epoch()
        commit(1)
        epoch()
        commit(2)  # warm: compiles + first-flush outside the window
        integrity.reset_host_ms()
        PROFILER.reset()
        PROFILER.enable(fence=False)
        per = []
        t0 = time.perf_counter()
        try:
            for i in range(epochs):
                base = PROFILER.total_dispatches()
                epoch()
                per.append(PROFILER.total_dispatches() - base)
                commit(3 + i)
            wall_ms = (time.perf_counter() - t0) * 1e3
        finally:
            PROFILER.disable()
            PROFILER.reset()
        host = integrity.host_ms()
        frac = host / wall_ms if wall_ms > 0 else 0.0
        digs = wrappers[0].last_digests if wrappers else {}
        report.update(
            {
                "q5_dispatches_per_barrier": per,
                "integrity_host_ms": round(host, 3),
                "steady_wall_ms": round(wall_ms, 2),
                "host_overhead_frac": round(frac, 5),
                "fused_digest_lanes": sorted(digs),
            }
        )
        mx = ib.get("q5_dispatches_per_barrier_max")
        if mx is not None and per and max(per) > mx:
            violations.append(
                f"integrity: digest lanes armed, steady fused q5 "
                f"barrier costs {max(per)} dispatches > budget {mx} — "
                "the digest fold added a dispatch"
            )
        mx = ib.get("host_overhead_frac_max")
        if mx is not None and frac > mx:
            violations.append(
                f"integrity: digest+checksum host overhead {frac:.4f} "
                f"of the steady barrier+commit wall > budget {mx}"
            )
        if not ("agg" in digs and "mv" in digs):
            violations.append(
                "integrity: fused q5 decoded no agg/mv digest "
                f"(got {sorted(digs)!r}) — the digest lane is dead"
            )

        # -- leg 3: scrub smoke over the fixture just committed ----------
        bad = [r for r in mgr.scrub() if r["status"] != "ok"]
        if bad:
            violations.append(
                f"integrity: clean fixture scrubbed dirty: {bad!r}"
            )
        sst = [p for p in store.list("hummock/sst/")][0]
        blob = bytearray(store.read(sst))
        blob[len(blob) // 2] ^= 0x10
        store.put(sst, bytes(blob))
        hits = [
            r
            for r in mgr.scrub()
            if r["status"] == "corrupt" and r["artifact"] == sst
        ]
        report["scrub_detected_flip"] = bool(hits)
        if not hits:
            violations.append(
                f"integrity: scrub missed a flipped byte in {sst}"
            )
    finally:
        if prev is None:
            os.environ.pop("RW_STATE_DIGEST", None)
        else:
            os.environ["RW_STATE_DIGEST"] = prev

    # -- leg 4: corrupted-newest-SST verified recovery --------------------
    os.environ["RW_STATE_DIGEST"] = "1"
    try:
        store2 = MemObjectStore()
        m2 = CheckpointManager(store2)
        for ep in (1, 2, 3):
            d = StateDelta(
                "t.gate",
                {"k": np.arange(6, dtype=np.int64)},
                {"v": np.arange(6, dtype=np.int64) * ep},
                np.zeros(6, bool),
                ("k",),
            )
            m2.commit_staged(ep << 16, [d])
        newest = max(store2.list("hummock/sst/"))
        blob = bytearray(store2.read(newest))
        blob[len(blob) // 2] ^= 0x10
        store2.put(newest, bytes(blob))

        class _Sink(Checkpointable):
            table_id = "t.gate"
            image = None

            def restore_state(self, table_id, keys, values):
                self.image = (keys, values)

        sink = _Sink()
        m3 = CheckpointManager(store2)
        m3.recover([sink])
        landed = m3.max_committed_epoch >> 16
        report["recovery_landed_epoch"] = landed
        if landed != 2:
            violations.append(
                f"integrity: recovery landed on epoch {landed}, "
                "expected walk-back to 2 (newest fully-verifying)"
            )
        want = np.arange(6, dtype=np.int64) * 2
        got = (
            np.asarray(sink.image[1]["v"])
            if sink.image is not None
            else None
        )
        if got is None or not np.array_equal(np.sort(got), want):
            violations.append(
                f"integrity: recovered row image wrong: {got!r} "
                f"(want permutation of {want!r})"
            )
        named = [
            e
            for e in EVENT_LOG.events(kind="state_corruption")
            if e.get("artifact") == newest
        ]
        report["corruption_event_named_artifact"] = bool(named)
        if not named:
            violations.append(
                "integrity: no state_corruption event names the "
                f"corrupted artifact {newest}"
            )
    finally:
        if prev is None:
            os.environ.pop("RW_STATE_DIGEST", None)
        else:
            os.environ["RW_STATE_DIGEST"] = prev

    # -- leg 1 (cont.): two-input dispatch neutrality ---------------------
    for q in ("q7", "q8"):
        v, r = _two_input_leg(budgets, q)
        violations += [f"integrity/{x}" for x in v]
        report.update({f"integrity_{k}": val for k, val in r.items()})
    return violations, report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--bench", default=None, help="BENCH JSON artifact")
    ap.add_argument("--budgets", default=DEFAULT_BUDGETS)
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="run the CPU steady-state microbench gate",
    )
    ap.add_argument(
        "--fusion",
        action="store_true",
        help="re-run the fusion analyzer and fail on fusible-prefix "
        "or host-sync-count regressions vs FUSION_REPORT.json",
    )
    ap.add_argument(
        "--fusion-baseline",
        default=None,
        help="baseline report (default: FUSION_REPORT.json)",
    )
    ap.add_argument(
        "--blackbox",
        action="store_true",
        help="gate the flight recorder: host ms/barrier + fsync-stall "
        "budgets, and the write-ring -> SIGKILL -> reader-CLI smoke",
    )
    ap.add_argument(
        "--roofline",
        action="store_true",
        help="gate the device-observability layer: fused telemetry "
        "host overhead < 1%% of the steady barrier, modeled bytes "
        "present, dispatches/barrier still 1 (plus the artifact "
        "padding/compile budgets, which always run with --bench)",
    )
    ap.add_argument(
        "--serving",
        action="store_true",
        help="gate the shared-arrangement serving tier: CI-scale "
        "registration storm (compile count O(families), flat barrier "
        "p99, ~1x shared device state) + concurrent pgwire readers "
        "(p99 + zero errors + registry overhead < 1%% of the barrier)",
    )
    ap.add_argument(
        "--freshness",
        action="store_true",
        help="gate end-to-end freshness SLOs: runtime-driven fused q5, "
        "p99 barrier-commit->visible under budget, event-time lag "
        "bounded with the watermark frontier threaded, dispatches/"
        "barrier unchanged with tracking armed, and tracking host "
        "overhead < 1%% of the steady barrier",
    )
    ap.add_argument(
        "--overload",
        action="store_true",
        help="gate overload protection: seeded chaos storm against the "
        "memory-governed runtime (zero OOM, zero wedge, MV bit-"
        "identical to the unthrottled twin, bounded flaps + recovery) "
        "plus the steady leg (governor host overhead < 1%% of the "
        "barrier, ledger reconciles against state_nbytes)",
    )
    ap.add_argument(
        "--integrity",
        action="store_true",
        help="gate the state-integrity layer: digest-lane dispatch "
        "neutrality on fused q5/q7/q8, digest+checksum host overhead "
        "< 1%% of the steady barrier+commit wall, scrub flip "
        "detection, and corrupted-newest-SST walk-back recovery with "
        "the state_corruption event naming the quarantined artifact",
    )
    ap.add_argument(
        "--mesh",
        action="store_true",
        help="gate the mesh-observability layer on a real 8-virtual-"
        "device sim: per-shard attribution covers >=90%% of the "
        "sharded barrier wall on q5 and q8, armed-vs-unarmed MVs are "
        "bit-identical, a seeded skewed workload yields the correct "
        "skew_shard verdict, and mesh telemetry host overhead stays "
        "< 1%% of the steady barrier",
    )
    ap.add_argument(
        "--mesh-child",
        action="store_true",
        help=argparse.SUPPRESS,  # internal: 8-device subprocess leg
    )
    ap.add_argument(
        "--fusion-current",
        default=None,
        help="reuse an existing `lint --fusion-report --json` output "
        "as the current analysis instead of re-tracing (CI passes "
        "the stage-3 artifact here)",
    )
    ap.add_argument(
        "--mesh-static",
        action="store_true",
        help="re-run the mesh-readiness analyzer over the sharded "
        "corpus and fail on host-routed-edge growth, per-code E9xx "
        "blocker growth, or lost SPMD-fusibility proofs vs "
        "MESH_REPORT.json",
    )
    ap.add_argument(
        "--mesh-baseline",
        default=None,
        help="baseline report (default: MESH_REPORT.json)",
    )
    ap.add_argument(
        "--mesh-current",
        default=None,
        help="reuse an existing `lint --mesh-report --json` output as "
        "the current analysis instead of re-analyzing (CI passes the "
        "lint-stage artifact here)",
    )
    args = ap.parse_args(argv)
    if args.mesh_child:
        return run_mesh_child()
    try:
        budgets = _load(args.budgets)
    except (OSError, json.JSONDecodeError) as e:
        print(f"[perf_gate] cannot read budgets: {e}", file=sys.stderr)
        return 2
    violations = []
    if args.smoke:
        v, report = run_smoke(budgets)
        print(f"[perf_gate] smoke: {json.dumps(report)}")
        violations += v
    if args.blackbox:
        v, report = run_blackbox_gate(budgets)
        print(f"[perf_gate] blackbox: {json.dumps(report)}")
        violations += v
    if args.roofline:
        v, report = run_roofline_gate(budgets)
        print(f"[perf_gate] roofline: {json.dumps(report)}")
        violations += v
    if args.serving:
        v, report = run_serving_gate(budgets)
        print(f"[perf_gate] serving: {json.dumps(report)}")
        violations += v
    if args.freshness:
        v, report = run_freshness_gate(budgets)
        print(f"[perf_gate] freshness: {json.dumps(report)}")
        violations += v
    if args.overload:
        v, report = run_overload_gate(budgets)
        print(f"[perf_gate] overload: {json.dumps(report)}")
        violations += v
    if args.integrity:
        v, report = run_integrity_gate(budgets)
        print(f"[perf_gate] integrity: {json.dumps(report)}")
        violations += v
    if args.mesh:
        v, report = run_mesh_gate(budgets)
        print(f"[perf_gate] mesh: {json.dumps(report)}")
        violations += v
    if args.mesh_static or args.mesh_current:
        try:
            mbase = _load(args.mesh_baseline or DEFAULT_MESH_BASELINE)
            for w in generation_warnings(mbase, "mesh baseline"):
                print(f"[perf_gate] WARNING: {w}")
        except (OSError, json.JSONDecodeError):
            pass  # run_mesh_static_gate reports unreadable baselines
        v, skipped = run_mesh_static_gate(
            budgets, args.mesh_baseline, args.mesh_current
        )
        for s in skipped:
            print(f"[perf_gate] skip: {s}")
        violations += v
    if args.fusion or args.fusion_current:
        try:
            baseline = _load(args.fusion_baseline or DEFAULT_FUSION_BASELINE)
            for w in generation_warnings(baseline, "fusion baseline"):
                print(f"[perf_gate] WARNING: {w}")
        except (OSError, json.JSONDecodeError):
            pass  # run_fusion_gate reports unreadable baselines itself
        v, skipped = run_fusion_gate(
            budgets, args.fusion_baseline, args.fusion_current
        )
        for s in skipped:
            print(f"[perf_gate] skip: {s}")
        violations += v
    bench_path = args.bench or DEFAULT_BENCH
    # --smoke without an explicit artifact still gates the committed
    # baseline when one exists (CI runs both checks in one call)
    if args.bench or not args.smoke or os.path.exists(bench_path):
        try:
            bench = _load(bench_path)
        except (OSError, json.JSONDecodeError) as e:
            print(f"[perf_gate] cannot read bench: {e}", file=sys.stderr)
            return 2
        for w in generation_warnings(
            bench, os.path.basename(bench_path)
        ):
            print(f"[perf_gate] WARNING: {w}")
        v, skipped = check_bench(bench, budgets)
        for s in skipped:
            print(f"[perf_gate] skip: {s}")
        violations += v
    for v in violations:
        print(f"[perf_gate] REGRESSION: {v}", file=sys.stderr)
    print(f"[perf_gate] {'FAIL' if violations else 'ok'}")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
