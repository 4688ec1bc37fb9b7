"""chip_smoke.py refuses to run without a TPU; its explicit CPU dry run
drives the same stage code at a tiny size and exits cleanly."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, timeout):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py"), *args],
        capture_output=True,
        text=True,
        cwd=ROOT,
        env=env,
        timeout=timeout,
    )


def test_refuses_to_start_without_a_tpu():
    r = _smoke(timeout=60)
    assert r.returncode != 0
    assert "platform='cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_cpu_dry_run_passes_and_exits_cleanly():
    r = _smoke("--dry-run-cpu", timeout=600)
    # a graph pipeline left open aborts at interpreter exit: rc != 0
    assert r.returncode == 0, r.stderr[-2000:]
    lines = r.stdout.strip().splitlines()
    assert lines[0].startswith("platform=cpu")
    last = json.loads(lines[-1])
    assert last["ok"] is True and last["dry_run"] is True
    assert last["device"]["platform"] == "cpu"
    stages = [json.loads(ln) for ln in lines if ln.startswith('{"stage"')]
    assert [s["stage"] for s in stages] == ["served", "q7"]
    assert stages[0]["q5_correct"] and stages[0]["q8_correct"]
    assert stages[1]["q7_correct"]
