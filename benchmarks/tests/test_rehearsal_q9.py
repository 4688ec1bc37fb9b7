"""nexmark_q9.catchup on the CPU: the controls (each guarantee the
configuration states, broken under the harness, has to come out as not
correct, by a count read against limit 0) and the traced run's host-side
metrics. (The cell end to end is a case of test_rehearsal.py, which runs
every cell BENCHMARK.json lists.)"""

import json
import os
import re

import pytest

from conftest import ROOT
from test_rehearsal import dry, last_json, run

CELL = "nexmark_q9.catchup"


def _check(lines, name):
    (ln,) = [x for x in lines if x.startswith(f"CHECK {name}=")]
    return int(re.match(rf"CHECK {name}=(\d+) limit=0", ln).group(1))


@pytest.mark.parametrize(
    "fault,line,count",
    [
        # a chunk delivered twice pairs its bids twice, and a copy ties
        # with its original and loses on arrival: the view stands, the
        # stream's own table holds the rows twice. A dropped chunk's
        # rows are missing from their table, and the winners among them
        # from the view
        ("dup_chunk", "tables != events pushed", "table_rows_differing"),
        ("drop_chunk", "tables != events pushed", "table_rows_differing"),
        ("rare_checkpoint", "committed epoch", "uncommitted_epochs"),
    ],
)
def test_a_broken_guarantee_is_not_correct(fault, line, count):
    p, lines = dry(CELL, "--fault", fault)
    assert p.returncode == 0, p.stderr[-2000:]
    doc = last_json(lines)
    assert doc["correct"] is False
    assert any(ln.startswith("NOT CORRECT") and line in ln for ln in lines)
    assert _check(lines, count) > 0


def test_traced_run_reports_the_new_cells_host_side_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    p, lines = run("--workload", CELL, "--seed", "7", "--seconds", "6",
                   "--trace", "1", "--dry-run-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    doc = last_json(lines)
    assert doc["correct"] is True
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    device = {m["name"] for m in bench["per_layer"]
              if m["source"] == "device_trace"}
    assert set(doc["metrics"]) == listed - device
    value = lambda name: doc["metrics"][f"{name}.catchup"]["value"]  # noqa: E731
    # nearly every pair lies inside its auction's lifetime
    assert value("join.residual_kept_share") > 90
    # an auction whose winner changed in an epoch retracts its old row
    assert value("retract.rows_per_event") > 0
    assert value("join.key_rows_max") > 16
    # every pushed event is a row one side keeps
    assert 0.9 < value("join.stored_rows_per_event") < 1.1
    # an epoch's bids touch the auctions still open: far fewer groups
    # than events
    assert 0 < value("topn.touched_groups_per_event") < 0.5
    assert value("view.apply_ms_per_barrier") > 0
    assert any(ln.startswith("window_s=") and "window_programs=0" in ln
               for ln in lines)
