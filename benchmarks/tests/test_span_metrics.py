"""The per-layer metrics that read the program's span tree: a traced
rehearsal of the backlog cell reports every host-side one, and the
residual reader on made epochs."""

import json
import os

from conftest import ROOT
from test_rehearsal import BENCH, last_json, run

import run as harness

barrier_residual = harness.load_module("readers", "barrier_residual.py")


def test_traced_catchup_reports_the_cells_layer_metrics():
    cell = "nexmark_q8.catchup"
    p, lines = run("--workload", cell, "--seed", "2147484001", "--seconds",
                   "6", "--trace", "1", "--dry-run-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    doc = last_json(lines)
    assert doc["correct"] is True
    listed = {
        m["name"] for m in BENCH["per_layer"]
        if "workloads" not in m or cell in m["workloads"]
    }
    device = {m["name"] for m in BENCH["per_layer"]
              if m["source"] == "device_trace"}
    assert set(doc["metrics"]) == listed - device
    for name in ("checkpoint.stage_ms_per_barrier.catchup",
                 "checkpoint.dictionary_ms_per_barrier.catchup",
                 "checkpoint.pull_ms_per_barrier.catchup",
                 "dispatch.drain_ms_per_barrier.catchup"):
        assert doc["metrics"][name]["value"] > 0, name
    # a child stage lies inside its parent
    m = {k: v["value"] for k, v in doc["metrics"].items()}
    assert (m["checkpoint.dictionary_ms_per_barrier.catchup"]
            <= m["checkpoint.stage_ms_per_barrier.catchup"])
    assert (m["checkpoint.stage_ms_per_barrier.catchup"]
            <= m["checkpoint.ms_per_barrier.catchup"])


def test_span_metric_files_name_readers_that_exist():
    d = os.path.join(ROOT, "benchmarks", "layer_metrics")
    for m in BENCH["per_layer"]:
        with open(os.path.join(d, m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["name"] == m["name"]
        assert os.path.exists(
            os.path.join(ROOT, "benchmarks", "readers", spec["reader"] + ".py")
        )


def _epoch(wall_s, **stages):
    return {"events": 100, "t_inject": 10.0, "t_return": 10.0 + wall_s,
            "stages_ms": stages}


ARGS = {"stages": ["dispatch", "checkpoint_stage", "publish", "bookkeeping"]}


def test_barrier_residual_is_the_median_of_wall_less_stages():
    run_ = {"epochs": [
        _epoch(0.100, dispatch=60.0, checkpoint_stage=30.0, publish=1.0,
               bookkeeping=4.0),  # 5 ms left
        _epoch(0.200, dispatch=60.0, checkpoint_stage=30.0, publish=1.0,
               bookkeeping=4.0),  # 105 ms left: a stall
        _epoch(0.098, dispatch=60.0, checkpoint_stage=30.0, publish=1.0,
               bookkeeping=4.0, ingest=500.0),  # 3 ms; ingest is not named
    ]}
    assert abs(barrier_residual.read(run_, ARGS) - 5.0) < 1e-6


def test_barrier_residual_missing_stage_gives_none():
    # a program that does not instrument a named stage: nothing to say
    run_ = {"epochs": [_epoch(0.1, dispatch=60.0, checkpoint_stage=30.0)]}
    assert barrier_residual.read(run_, ARGS) is None
    # a stage written in one epoch only (a barrier that did not
    # checkpoint) reads 0.0 in the others
    run_ = {"epochs": [
        _epoch(0.1, dispatch=60.0, publish=1.0, bookkeeping=4.0),
        _epoch(0.1, dispatch=60.0, checkpoint_stage=30.0, publish=1.0,
               bookkeeping=4.0),
    ]}
    assert abs(barrier_residual.read(run_, ARGS) - 20.0) < 1e-6
    # no barrier with stages at all
    assert barrier_residual.read({"epochs": []}, ARGS) is None
