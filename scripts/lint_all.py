#!/usr/bin/env python
"""The one-liner CI gate: ruff + rwlint over every built-in query +
the static ratchets.

    python scripts/lint_all.py

Stages (all must pass; exit code is the OR of their failures):

1. ruff (pyflakes+bugbear, ruff.toml) over risingwave_tpu/, tests/,
   scripts/, bench.py — or, when ruff is not installed (the bench
   image does not ship it), a built-in AST unused-import scan (the
   F401 class) + byte-compilation of every file (syntax errors).
2. ``python -m risingwave_tpu lint --all-nexmark --deep`` — the static
   plan verifier + jaxpr sanitizer over q5/q7/q8.
3. ``python -m risingwave_tpu lint --all-nexmark --fusion-report`` —
   the fusion-feasibility analyzer: per-fragment fusible prefixes +
   RW-E8xx blockers with provenance.
3b. ``python -m risingwave_tpu lint --mesh-report`` — the mesh-
   readiness analyzer over the sharded q5/q7/q8 corpus (fresh
   subprocess owning the 8-virtual-device sim mesh): per-fragment
   SPMD-fusibility proofs + RW-E9xx blockers with provenance.
4. The two static ratchets, in-process, against the reports stages
   3/3b just wrote: the fusion ratchet vs FUSION_REPORT.json (fusible
   prefixes must not shrink, host-sync and fallback-sync counts must
   not grow, per-code blocker counts must not grow, whole-chain proofs
   must not be lost) and the mesh-static ratchet vs MESH_REPORT.json
   (host-routed exchange edges and per-code E9xx blocker counts must
   not grow, SPMD proofs must not shrink).

No stage holds a clock: how fast the system runs is the driver's
measurement on the chip (``PERF_LEDGER.jsonl``).
"""

from __future__ import annotations

import ast
import json
import os
import py_compile
import shutil
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TARGETS = ["risingwave_tpu", "tests", "scripts", "bench.py"]


def _py_files():
    for t in TARGETS:
        p = os.path.join(ROOT, t)
        if os.path.isfile(p):
            yield p
            continue
        for dirpath, dirnames, filenames in os.walk(p):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for f in filenames:
                if f.endswith(".py"):
                    yield os.path.join(dirpath, f)


def _unused_imports(path: str) -> list:
    """F401-class scan: imported names never referenced. Conservative:
    __init__.py re-exports, `_` names, and __all__-listed names pass."""
    if os.path.basename(path) == "__init__.py":
        return []
    with open(path) as f:
        src = f.read()
    try:
        tree = ast.parse(src, path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: syntax error: {e.msg}"]
    imported = {}  # name -> lineno
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                name = (a.asname or a.name).split(".")[0]
                imported[name] = node.lineno
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue  # feature declarations, not names
            for a in node.names:
                if a.name == "*":
                    continue
                imported[a.asname or a.name] = node.lineno
    if not imported:
        return []
    used = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            used.add(node.id)
        elif isinstance(node, ast.Attribute):
            pass  # handled via the root Name
    # names echoed in strings count (doctests, __all__, noqa-ish use)
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            used.update(node.value.replace(".", " ").split())
    out = []
    for name, lineno in sorted(imported.items(), key=lambda kv: kv[1]):
        if name.startswith("_") or name in used:
            continue
        line = src.splitlines()[lineno - 1]
        if "noqa" in line:
            continue
        out.append(f"{path}:{lineno}: unused import {name!r}")
    return out


def stage_host_lint() -> int:
    ruff = shutil.which("ruff")
    if ruff is not None:
        print(f"[lint_all] ruff ({ruff})")
        return subprocess.call(
            [ruff, "check", *TARGETS], cwd=ROOT
        )
    print("[lint_all] ruff not installed — built-in fallback "
          "(unused-import scan + byte-compile)")
    import tempfile

    rc = 0
    findings = []
    with tempfile.TemporaryDirectory() as tmp:
        for path in _py_files():
            try:
                py_compile.compile(
                    path, doraise=True,
                    cfile=os.path.join(tmp, "out.pyc"),
                )
            except py_compile.PyCompileError as e:
                findings.append(str(e))
                rc = 1
            findings.extend(_unused_imports(path))
    for f in findings:
        print(f)
    if findings:
        rc = 1
    return rc


def stage_rwlint() -> int:
    print("[lint_all] rwlint --all-nexmark --deep")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.call(
        [sys.executable, "-m", "risingwave_tpu", "lint",
         "--all-nexmark", "--deep"],
        cwd=ROOT,
        env=env,
    )


def stage_fusion_report(out_path: str) -> int:
    """Produce the fusion analysis ONCE (JSON to ``out_path``); the
    fusion ratchet reads it instead of paying for a second corpus
    build + jaxpr trace."""
    print("[lint_all] rwlint --fusion-report (fusion feasibility)")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    try:
        with open(out_path, "w") as f:
            rc = subprocess.call(
                [sys.executable, "-m", "risingwave_tpu", "lint",
                 "--all-nexmark", "--fusion-report", "--json"],
                cwd=ROOT,
                env=env,
                stdout=f,
            )
    except OSError as e:
        print(f"[lint_all] cannot write {out_path}: {e}")
        return 1
    if rc == 0:
        try:
            with open(out_path) as f:
                fus = json.load(f).get("__fusion__", {})
            for q in sorted(fus):
                if q.startswith("_"):
                    continue  # _provenance and friends: not a query
                s = fus[q]["summary"]
                print(
                    f"[lint_all]   {q}: "
                    f"{s['fusible_fragments']}/{s['fragments']} "
                    f"fragments fusible, "
                    f"{s['host_sync_points']} host-sync point(s), "
                    f"blockers {s['blockers_by_code']}"
                )
        except (OSError, ValueError, KeyError):
            pass
    return rc


def stage_mesh_report(out_path: str) -> int:
    """Produce the mesh-readiness analysis ONCE (JSON to ``out_path``)
    in a fresh subprocess — ``lint --mesh-report`` claims its own
    8-virtual-device mesh, which cannot be conjured in a process that
    already initialized jax. The mesh-static ratchet reads it."""
    print("[lint_all] rwlint --mesh-report (SPMD mesh readiness)")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # the child claims its own mesh
    try:
        with open(out_path, "w") as f:
            rc = subprocess.call(
                [sys.executable, "-m", "risingwave_tpu", "lint",
                 "--mesh-report", "--json"],
                cwd=ROOT,
                env=env,
                stdout=f,
            )
    except OSError as e:
        print(f"[lint_all] cannot write {out_path}: {e}")
        return 1
    if rc == 0:
        try:
            with open(out_path) as f:
                rep = json.load(f)
            for q in sorted(rep):
                if q.startswith("_") or q in ("ranking", "top_cost"):
                    continue
                s = rep[q]["summary"]
                print(
                    f"[lint_all]   {q}: "
                    f"{s['spmd_fusible_fragments']}/{s['fragments']} "
                    f"fragments SPMD-fusible, "
                    f"{s['host_routed_edges']} host-routed edge(s), "
                    f"blockers {s['blockers_by_code']}"
                )
            top = rep.get("top_cost") or {}
            print(
                f"[lint_all]   top cost: phase={top.get('phase')} "
                f"est_ms={top.get('est_ms')}"
            )
        except (OSError, ValueError, KeyError):
            pass
    return rc


# ---------------------------------------------------------------------------
# the two static ratchets (no clock: counts against committed reports)
# ---------------------------------------------------------------------------

DEFAULT_FUSION_BASELINE = os.path.join(ROOT, "FUSION_REPORT.json")
DEFAULT_MESH_BASELINE = os.path.join(ROOT, "MESH_REPORT.json")
# absolute ceilings on top of the no-regression compare: q5/q7/q8 are
# pinned at ZERO host syncs (every fragment whole_chain_fusible), and
# the shape-stability wedge class (RW-E803/E806) at ZERO for the corpus
MAX_HOST_SYNC_POINTS = {"q5": 0, "q7": 0, "q8": 0}
MAX_BLOCKER_CODES = {"RW-E803": 0, "RW-E806": 0}


def _load(path: str):
    with open(path) as f:
        return json.load(f)


def run_fusion_gate(baseline_path: str = None, current_path: str = None):
    """Re-run the fusion analyzer over the Nexmark corpus and compare
    against the committed FUSION_REPORT.json baseline: per fragment,
    the fusible executor prefix must not SHRINK and the host-sync
    count must not GROW (plus the absolute per-query
    ``MAX_HOST_SYNC_POINTS`` ceiling). This is the ratchet for ROADMAP
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
            # there must not slip past the ratchet
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
        mx = MAX_HOST_SYNC_POINTS.get(q)
        if mx is not None:
            total = current[q]["summary"]["host_sync_points"]
            if total > mx:
                violations.append(
                    f"fusion {q}: {total} host-sync points > ceiling {mx}"
                )
        # shape-stability ratchet (PR 9): per-code blocker ceilings —
        # RW-E803/E806 are pinned at ZERO for the whole corpus (q7's
        # wedge class must never return), and no code may regress
        # above its committed-baseline count
        cur_codes = current[q]["summary"].get("blockers_by_code", {})
        base_codes = base_rep.get("summary", {}).get(
            "blockers_by_code", {}
        )
        for code, mx in MAX_BLOCKER_CODES.items():
            got = int(cur_codes.get(code, 0))
            if got > mx:
                violations.append(
                    f"fusion {q}: {got} {code} finding(s) > ceiling {mx}"
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


def run_mesh_static_gate(
    baseline_path: str = None, current_path: str = None
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


def stage_ratchets(
    fusion_current: str = None, mesh_current: str = None
) -> int:
    """Both ratchets against the reports stages 3/3b just wrote (a
    failed stage passes None: the ratchet re-analyzes)."""
    print("[lint_all] fusion ratchet vs FUSION_REPORT.json + "
          "mesh-static ratchet vs MESH_REPORT.json")
    violations = []
    for name, (v, skipped) in (
        ("fusion", run_fusion_gate(current_path=fusion_current)),
        ("mesh", run_mesh_static_gate(current_path=mesh_current)),
    ):
        for s in skipped:
            print(f"[lint_all] {name} ratchet skip: {s}")
        violations += v
    for v in violations:
        print(f"[lint_all] REGRESSION: {v}", file=sys.stderr)
    return 1 if violations else 0


def main() -> int:
    import tempfile

    rc = stage_host_lint()
    rc |= stage_rwlint()
    with tempfile.TemporaryDirectory() as tmp:
        fusion_json = os.path.join(tmp, "fusion_report.json")
        frc = stage_fusion_report(fusion_json)
        rc |= frc
        mesh_json = os.path.join(tmp, "mesh_report.json")
        mrc = stage_mesh_report(mesh_json)
        rc |= mrc
        rc |= stage_ratchets(
            fusion_json if frc == 0 else None,
            mesh_json if mrc == 0 else None,
        )
    print(f"[lint_all] {'FAIL' if rc else 'ok'}")
    return rc


if __name__ == "__main__":
    sys.exit(main())
