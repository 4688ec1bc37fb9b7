"""The controls on nexmark_q5.catchup: each guarantee the configuration
states, broken under the harness, has to come out as not correct. (Both
q5 cells end to end are cases of test_rehearsal.py, which runs every
cell BENCHMARK.json lists.)"""

import pytest

from test_rehearsal import dry, last_json

CELL = "nexmark_q5.catchup"


@pytest.mark.parametrize(
    "fault,line",
    [
        # a chunk of bids delivered twice, or not at all: the view is the
        # windows' winners, which 512 bids more or fewer need not change
        # (the counts they changed are not in it), so what always shows
        # it is the bid table's own count; the probes and the view show
        # it on most seeds
        ("dup_chunk", "tables != events pushed"),
        ("drop_chunk", "tables != events pushed"),
        ("rare_checkpoint", "committed epoch"),
    ],
)
def test_a_broken_guarantee_is_not_correct(fault, line):
    p, lines = dry(CELL, "--fault", fault)
    assert p.returncode == 0, p.stderr[-2000:]
    doc = last_json(lines)
    assert doc["correct"] is False
    assert any(ln.startswith("NOT CORRECT") and line in ln for ln in lines)


def test_traced_run_reports_the_new_cells_host_side_metrics():
    import json
    import os

    from conftest import ROOT
    from test_rehearsal import run

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
    assert 0 < doc["metrics"]["join.residual_kept_share.catchup"]["value"] < 100
    assert doc["metrics"]["retract.rows_per_event.catchup"]["value"] > 0
