"""nexmark_q7.catchup on the CPU: the controls (each guarantee the
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

CELL = "nexmark_q7.catchup"


def _check(lines, name):
    (ln,) = [x for x in lines if x.startswith(f"CHECK {name}=")]
    return int(re.match(rf"CHECK {name}=(\d+) limit=0", ln).group(1))


@pytest.mark.parametrize(
    "fault,line,count",
    [
        # the view holds a row a winner a window: a chunk delivered
        # twice or not at all shows there only if it held a winner, and
        # always in the stream's own table
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
    # a maximum that rises takes its old winners out: a pair or two a
    # barrier, and as many lanes of the updating side left dead
    assert 0 < value("join.retract_pairs_per_barrier") < 16
    assert 0 < value("join.dead_lanes_per_barrier") < 4
    # an epoch's bids fall on the one or two windows open
    assert 40 < value("agg.group_rows_max_share") <= 100
    # a price holds as many rows as bids carried it
    assert value("join.key_rows_max") > 16
    # every bid is a row the stored side keeps (the other side's rows
    # are a handful)
    assert 0.99 < value("join.stored_rows_per_event") < 1.01
    assert value("join.residual_kept_share") > 50
    assert 0 < value("retract.rows_per_event") < 0.01
    assert any(ln.startswith("window_s=") and "window_programs=0" in ln
               for ln in lines)
