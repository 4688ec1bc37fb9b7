"""nexmark_q18's cells on the CPU: the controls (each guarantee the
configuration states, broken under the harness, has to come out as not
correct, by a count read against limit 0) and the traced run's host-side
metrics. (Both cells end to end are cases of test_rehearsal.py, which
runs every cell BENCHMARK.json lists.)"""

import json
import os
import re

import pytest

from conftest import ROOT
from test_rehearsal import dry, last_json, run

CELL = "nexmark_q18.catchup"


def _check(lines, name):
    (ln,) = [x for x in lines if x.startswith(f"CHECK {name}=")]
    return int(re.match(rf"CHECK {name}=(\d+) limit=0", ln).group(1))


@pytest.mark.parametrize(
    "fault,line,count",
    [
        # the view is nearly a copy of the stream: a chunk of bids
        # delivered twice leaves it as it was (the rows' later copies
        # tie with the earlier on date_time and lose), so what shows it
        # is the bid table's own count; a dropped chunk's pairs are
        # missing from the view as well
        ("dup_chunk", "tables != events pushed", "table_rows_differing"),
        ("drop_chunk", "MV != reference", "mv_rows_differing"),
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


@pytest.mark.parametrize("mix", ["steady", "catchup"])
def test_traced_run_reports_the_new_cells_host_side_metrics(mix):
    cell = f"nexmark_q18.{mix}"
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    p, lines = run("--workload", cell, "--seed", "7", "--seconds", "6",
                   "--trace", "1", "--dry-run-cpu")
    assert p.returncode == 0, p.stderr[-2000:]
    doc = last_json(lines)
    assert doc["correct"] is True
    listed = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]}
    device = {m["name"] for m in bench["per_layer"]
              if m["source"] == "device_trace"}
    assert set(doc["metrics"]) == listed - device
    assert doc["metrics"][f"retract.rows_per_event.{mix}"]["value"] > 0
    groups = doc["metrics"][f"topn.touched_groups_per_event.{mix}"]["value"]
    assert 0 < groups <= 1
    assert doc["metrics"][f"topn.diff_ms_per_barrier.{mix}"]["value"] > 0
    assert any(ln.startswith("window_s=") and "window_programs=0" in ln
               for ln in lines)
