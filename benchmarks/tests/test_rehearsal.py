"""The rehearsals of the on-chip guide: every cell end to end at a tiny
size on the CPU backend, the controls (a broken guarantee has to come
out as not correct), and the refusal to run without a TPU."""

import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def run(*args, env=None):
    e = dict(os.environ, JAX_PLATFORMS="cpu")
    e.update(env or {})
    p = subprocess.run(
        [sys.executable, "benchmarks/run.py", *args],
        cwd=ROOT, env=e, capture_output=True, text=True, timeout=600,
    )
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, lines


def last_json(lines):
    try:
        return json.loads(lines[-1])
    except (IndexError, ValueError):
        return None


def dry(cell, *more, seconds="6"):
    return run("--workload", cell, "--seed", "2147483999", "--seconds",
               seconds, "--trace", "0", "--dry-run-cpu", *more)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_on_cpu(cell):
    p, lines = dry(cell)
    assert p.returncode == 0, p.stderr[-2000:]
    doc = last_json(lines)
    assert doc["correct"] is True and doc["failed"] == 0
    assert doc["device"]["platform"] == "cpu"  # never a device number
    want = {
        m["name"] for m in BENCH["end_to_end"]
        if "workloads" not in m or cell in m["workloads"]
    }
    assert set(doc["metrics"]) == want
    assert all(v["value"] > 0 for v in doc["metrics"].values())
    assert any(ln.startswith("CHECK mv_rows_differing=0 limit=0") for ln in lines)


def test_traced_run_reports_the_cells_layer_metrics():
    cell = "nexmark_q8.steady"
    p, lines = run("--workload", cell, "--seed", "7", "--seconds", "6",
                   "--trace", "1", "--dry-run-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    doc = last_json(lines)
    listed = {
        m["name"] for m in BENCH["per_layer"]
        if "workloads" not in m or cell in m["workloads"]
    }
    # the CPU backend has no device plane: the device readers find
    # nothing and are left out, every host-side metric is there
    device = {m["name"] for m in BENCH["per_layer"]
              if m["source"] == "device_trace"}
    assert set(doc["metrics"]) == listed - device


@pytest.mark.parametrize(
    "cell,fault,line",
    [
        # at-least-once instead of exactly-once: one chunk delivered twice
        # (q8's view is a set, which a repeat does not change: the
        # stream's own table holds the rows twice)
        ("nexmark_q8.catchup", "dup_chunk", "tables != events pushed"),
        # at-most-once: part of the batch left out
        ("nexmark_q8.catchup", "drop_chunk", "MV != reference"),
        ("nexmark_q8.steady", "drop_chunk", "MV != reference"),
        # a rarer flush than the configuration states
        ("nexmark_q8.steady", "rare_checkpoint", "committed epoch"),
    ],
)
def test_a_broken_guarantee_is_not_correct(cell, fault, line):
    p, lines = dry(cell, "--fault", fault)
    assert p.returncode == 0, p.stderr[-2000:]
    doc = last_json(lines)
    assert doc["correct"] is False
    assert any(ln.startswith("NOT CORRECT") and line in ln for ln in lines)
    if fault == "drop_chunk":
        # the probes after the fault show no boundary's value either
        assert doc["failed"] > 0


def test_no_tpu_no_result():
    p, lines = run("--workload", CELLS[0], "--seed", "1", "--seconds", "5",
                   "--trace", "0")
    assert p.returncode != 0
    assert last_json(lines) is None
    assert "tpu" in p.stderr


def test_unknown_workload_is_refused():
    p, lines = run("--workload", "nope", "--seed", "1", "--seconds", "5",
                   "--trace", "0", "--dry-run-cpu")
    assert p.returncode != 0 and last_json(lines) is None
