"""nexmark_q6.catchup on the CPU: the controls (each guarantee the
configuration states, broken under the harness, has to come out as not
correct, by a count read against limit 0) and the traced run's host-side
metrics, the general over-window's among them. (The cell end to end is a
case of test_rehearsal.py, which runs every cell BENCHMARK.json lists.)"""

import json
import os
import re

import pytest

from conftest import ROOT
from test_rehearsal import dry, last_json, run

CELL = "nexmark_q6.catchup"


def _check(lines, name):
    (ln,) = [x for x in lines if x.startswith(f"CHECK {name}=")]
    return int(re.match(rf"CHECK {name}=(\d+) limit=0", ln).group(1))


@pytest.mark.parametrize(
    "fault,line,count",
    [
        # a chunk delivered twice pairs its bids twice, and a copy ties
        # with its original on the price and loses on arrival: the view
        # stands, the stream's own table holds the rows twice. A dropped
        # chunk's rows are missing from their table, and the lowest bids
        # among them from the sellers' sums
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
    over = {m for m in listed if m.startswith("over.")}
    assert len(over) == 7 and len(over - device) == 5
    value = lambda name: doc["metrics"][f"{name}.catchup"]["value"]  # noqa: E731
    # the Top-N hands on a retract chunk and an insert chunk a barrier
    assert value("over.steps_per_barrier") == 2
    # one row an auction that got its first bid, a U-/U+ an undercut one:
    # far fewer than events, and never more than the Top-N handed on
    assert 0 < value("over.input_rows_per_event") < 0.5
    # a hot seller's partition is dirty every epoch, whole
    assert value("over.dirty_rows_per_event") > value("over.input_rows_per_event")
    # an insert that is not its partition's last moves the frames behind it
    assert value("over.emitted_rows_per_event") >= value("over.input_rows_per_event")
    assert 0 < value("over.emit_filled_share") <= 100
    assert value("join.residual_kept_share") > 90
    assert value("retract.rows_per_event") > 0
    assert 0 < value("topn.touched_groups_per_event") < 0.5
    assert value("view.apply_ms_per_barrier") > 0
    # nothing compiles in the window, and no store grows in it: the dry
    # run's capacity holds its stores under half full (pushed / capacity)
    (win,) = [ln for ln in lines if ln.startswith("window_s=")]
    assert "window_programs=0" in win
    assert float(re.search(r"pushed_per_lane=(\S+)", win).group(1)) < 0.5
