"""The reader PR 51 brought: seconds the run's largest device
operations spend under given named scopes, the operations named by the
program's own map (``risingwave_tpu.trace.name_ops``). A recorded
``device_ops`` list (the ledger's, PR 50, nexmark_q18.catchup) over a
stub map; no trace, no device."""

import importlib.util
import os
import sys

from conftest import BENCH, ROOT

sys.path.insert(0, ROOT)
from risingwave_tpu import trace  # noqa: E402


def _reader(name):
    path = os.path.join(BENCH, "readers", name + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ROWS = [
    ["jit__upsert_step_ed/while.17", 2.024453583],
    ["jit__upsert_step_ed/while.18", 0.915276202],
    ["jit__upsert_step_ed/fusion.152", 0.384323646],
    ["jit__upsert_step_ed/fusion.151", 0.382990367],
    ["jit__upsert_step_ed/fusion.153", 0.382101905],
    ["jit__diff_gather/while.11", 0.292609342],
    ["jit__upsert_step_ed/fusion.173", 0.266553241],
    ["jit__upsert_step_ed/fusion.174", 0.256316886],
    ["jit__upsert_step_ed/fusion.46", 0.064734954],
    ["jit__upsert_step_ed/fusion.44", 0.060343562],
]


def _op(scope, within=None, opcode="fusion"):
    return {"scope": scope, "source": "x.py:1", "opcode": opcode,
            "within": within}


MAP = {
    "jit__upsert_step_ed": [{
        "shapes": "",
        "ops": {
            "while.17": _op("topn/rows/hash/probe", opcode="while"),
            "while.18": _op("topn/groups/hash/probe", opcode="while"),
            # three of the row loop's body, one of the group loop's
            "fusion.151": _op("topn/rows/hash/probe/match", "while.17"),
            "fusion.152": _op("topn/rows/hash/probe/match", "while.17"),
            "fusion.153": _op("topn/rows/hash/probe/write", "while.17"),
            "fusion.173": _op("topn/groups/hash/probe/match", "while.18"),
            # beside the loops
            "fusion.174": _op("topn/rows"),
            "fusion.46": _op("topn/marks"),
            "fusion.44": _op(""),
        },
    }],
    "jit__diff_gather": [{
        "shapes": "",
        "ops": {"while.11": _op("topn/diff/insert", opcode="while")},
    }],
}
ARGS = {"scopes": ["hash/probe", "hash/lookup"]}


def _run(rows):
    return {"device_trace": {"breakdown": {"device_ops": rows}}}


def test_the_probes_seconds_count_each_loop_once(monkeypatch):
    read = _reader("device_scope").read
    monkeypatch.setattr(trace, "program_ops", lambda module=None: MAP)
    # the two loops; their bodies' fusions are nested and left out
    assert read(_run(ROWS), ARGS) == 2.024453583 + 0.915276202
    # a body's fusion whose loop is not among the rows stands for itself
    assert read(_run(ROWS[1:]), ARGS) == (
        0.915276202 + 0.384323646 + 0.382990367 + 0.382101905
    )
    # another scope: the rows beside the loops
    assert read(_run(ROWS), {"scopes": ["topn/marks"]}) == 0.064734954
    # a scope is held whole: ``hash/prob`` is no part of ``hash/probe``
    assert read(_run(ROWS), {"scopes": ["hash/prob"]}) == 0.0
    # the map names the rows and none is a probe: a number, and 0.0
    assert read(_run([ROWS[5], ROWS[8]]), ARGS) == 0.0


def test_no_map_no_trace_no_number(monkeypatch):
    read = _reader("device_scope").read
    # no device trace, or one that holds no operation
    assert read({}, ARGS) is None
    assert read({"device_trace": None}, ARGS) is None
    assert read(_run([]), ARGS) is None
    # the program names none of the rows (another process compiled them)
    monkeypatch.setattr(trace, "program_ops", lambda module=None: {})
    assert read(_run(ROWS), ARGS) is None
    # a program from before the map (the parent): no name_ops, no raise
    monkeypatch.delattr(trace, "name_ops")
    assert read(_run(ROWS), ARGS) is None


def test_the_eight_metrics_read_what_the_program_writes():
    """The data files' span, args and scopes are the program's own."""
    import json

    from risingwave_tpu.ops.hash_table import PROBE_STATS

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    mine = [m for m in bench["per_layer"] if m["name"].startswith("hash.")]
    assert len(mine) == 8
    cells = {w["name"] for w in bench["workloads"]}
    for m in mine:
        with open(os.path.join(
            BENCH, "layer_metrics", m["name"] + ".json"
        )) as f:
            spec = json.load(f)
        assert spec["name"] == m["name"]
        assert set(m["workloads"]) <= cells
        traffic = m["name"].rsplit(".", 1)[1]
        assert all(c.endswith("." + traffic) for c in m["workloads"])
        if spec["reader"] == "device_scope":
            assert set(spec["args"]["scopes"]) <= set(trace.SCOPES)
            assert set(m["workloads"]) == {
                c for c in cells if c.endswith("." + traffic)
            }
            continue
        assert spec["reader"] == "epoch_spans"
        sums = [spec["args"]["numerator"]]
        if spec["args"]["denominator"] != "events":
            sums += spec["args"]["denominator"]
        for s in sums:
            assert s["span"] == "hash.probes"
            assert s["arg"] in PROBE_STATS + ("calls",)
        # q8's kernels are not counted yet
        assert not any(c.startswith("nexmark_q8.") for c in m["workloads"])
