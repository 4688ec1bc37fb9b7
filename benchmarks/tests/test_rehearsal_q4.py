"""The controls on nexmark_q4.catchup: each guarantee the configuration
states, broken under the harness, has to come out as not correct; and a
traced run reports the host-side per-layer metrics the cell lists. (Both
q4 cells end to end are cases of test_rehearsal.py, which runs every
cell BENCHMARK.json lists.)"""

import json
import os

import pytest

from conftest import ROOT
from test_rehearsal import dry, last_json, run

CELL = "nexmark_q4.catchup"


@pytest.mark.parametrize(
    "fault,line",
    [
        # a chunk delivered twice, or not at all. The view is five
        # categories' sums of highest bids, which 512 bids more or fewer
        # need not move (a repeated bid never does: MAX is the same);
        # what always shows it is the stream's own table, which holds
        # the rows twice or lacks them (the second comparison of five)
        ("dup_chunk", "tables != events pushed"),
        ("drop_chunk", "tables != events pushed"),
        # checkpoints a thousand barriers apart: the fourth comparison,
        # the committed epoch behind the newest barrier's
        ("rare_checkpoint", "committed epoch"),
    ],
)
def test_a_broken_guarantee_is_not_correct(fault, line):
    p, lines = dry(CELL, "--fault", fault)
    assert p.returncode == 0, p.stderr[-2000:]
    doc = last_json(lines)
    assert doc["correct"] is False
    assert any(ln.startswith("NOT CORRECT") and line in ln for ln in lines)
    nonzero = [
        ln for ln in lines
        if ln.startswith("CHECK ") and "limit=0" in ln
        and not ln.split()[1].endswith("=0")
    ]
    assert nonzero  # a count read against its limit of 0


def test_traced_run_reports_the_new_cells_host_side_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    p, lines = run("--workload", CELL, "--seed", "7", "--seconds", "6",
                   "--trace", "1", "--dry-run-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    doc = last_json(lines)
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    device = {m["name"] for m in bench["per_layer"]
              if m["source"] == "device_trace"}
    assert set(doc["metrics"]) == listed - device
    # nearly every pair lies inside its auction's lifetime
    assert doc["metrics"]["join.residual_kept_share.catchup"]["value"] > 90
    # every auction's highest bid that rose retracts its old row
    assert doc["metrics"]["retract.rows_per_event.catchup"]["value"] > 0
    assert doc["metrics"]["join.key_rows_max.catchup"]["value"] > 16
    # every pushed event is a row one side keeps
    assert 0.9 < doc["metrics"]["join.stored_rows_per_event.catchup"]["value"] < 1.1
