"""nexmark_q19.catchup on the CPU: the controls (each guarantee the
configuration states, broken under the harness, has to come out as not
correct, by a count read against limit 0; and a view whose rows are
right and whose ranks are stale may not pass either) and the traced
run's host-side metrics. (The cell end to end is a case of
test_rehearsal.py, which runs every cell BENCHMARK.json lists.)"""

import json
import os
import re
import subprocess
import sys

import pytest

from conftest import ROOT
from test_rehearsal import dry, last_json, run

CELL = "nexmark_q19.catchup"


def _check(lines, name):
    (ln,) = [x for x in lines if x.startswith(f"CHECK {name}=")]
    return int(re.match(rf"CHECK {name}=(\d+) limit=0", ln).group(1))


@pytest.mark.parametrize(
    "fault,line,count",
    [
        # a chunk of bids delivered twice: the copies tie with their
        # originals on price, rank right behind them and push others
        # down or out, so the view shows it as well as the table
        ("dup_chunk", "MV != reference", "mv_rows_differing"),
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


# the harness as it is, over a program whose diff is blind to the rank:
# every row taken to stand at the rank it has now, so no row is handed
# on again for its rank alone
STALE_RANKS = """
import sys
sys.path[:0] = [{root!r} + "/benchmarks", {root!r}]
import jax.numpy as jnp
from risingwave_tpu.executors import top_n_plain
real = top_n_plain._diff_gather
def blind(table, rows, shadow, emitted, ranked, *rest, **numbered):
    packed_s, in_topk_s, seg_start, passes, erank_s = ranked
    now = jnp.arange(table.capacity, dtype=jnp.int32) - seg_start + 1
    ranked = (packed_s, in_topk_s, seg_start, passes,
              jnp.where(in_topk_s, now, 0))
    return real(table, rows, shadow, emitted, ranked, *rest, **numbered)
top_n_plain._diff_gather = blind
sys.argv = ["benchmarks/run.py"] + {argv!r}
import runpy
runpy.run_path("benchmarks/run.py", run_name="__main__")
"""


def test_stale_ranks_are_not_correct():
    argv = ["--workload", CELL, "--seed", "2147483999", "--seconds", "6",
            "--trace", "0", "--dry-run-cpu"]
    p = subprocess.run(
        [sys.executable, "-c", STALE_RANKS.format(root=ROOT, argv=argv)],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600,
    )
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    assert p.returncode == 0, p.stderr[-2000:]
    doc = last_json(lines)
    assert doc["correct"] is False
    assert any(ln.startswith("NOT CORRECT: MV != reference") for ln in lines)
    # the rows are all there: as many as the reference has
    (ln,) = [x for x in lines if x.startswith("CHECK mv_rows_differing=")]
    system, reference = map(int, re.search(
        r"system (\d+) rows, reference (\d+) rows", ln).groups())
    assert system == reference and _check(lines, "mv_rows_differing") > 0
    # and every probe's sum(rank_number) is off
    assert _check(lines, "probes_unsound") > 0


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
    # rows move for their rank alone, and each is a U- and a U+ of the
    # delta, which holds the entering and the leaving rows besides
    moved, emitted = (value("topn.rank_moved_rows_per_event"),
                      value("topn.emitted_rows_per_event"))
    assert moved > 0.05 and emitted > 2 * moved
    # every row that moved is a retraction on each of the three edges
    # it crosses (out of the Top-N, of the project, of the view)
    assert value("retract.rows_per_event") >= 3 * moved
    assert 0 < value("topn.touched_groups_per_event") < 0.5
    assert value("topn.diff_ms_per_barrier") > 0
    assert value("view.apply_ms_per_barrier") > 0
    assert any(ln.startswith("window_s=") and "window_programs=0" in ln
               for ln in lines)
