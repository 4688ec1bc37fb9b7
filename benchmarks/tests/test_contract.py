"""BENCHMARK.json against the benchmark's contract, and against the
files it names."""

import json
import os
import re

from conftest import BENCH, ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    DOC = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_shape_and_names():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert DOC["paths"] == ["benchmarks"]
    assert 1 <= DOC["run_seconds"] <= 51
    names = []
    for c in DOC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"]
        assert conf["reduced"] == c["reduced"]
        assert os.path.isfile(
            os.path.join(BENCH, "configs", conf["reference"] + ".py"))
        names.append(c["name"])
    for w in DOC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in names and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert os.path.isfile(
            os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
    every = (
        [c["name"] for c in DOC["configs"]]
        + [w["name"] for w in DOC["workloads"]]
        + [m["name"] for m in DOC["end_to_end"] + DOC["per_layer"]]
    )
    assert all(NAME.match(n) for n in every)
    assert len(set(every)) == len(every)
    used = {w["config"] for w in DOC["workloads"]}
    assert used == set(names)


def test_metrics():
    cells = {w["name"] for w in DOC["workloads"]}
    e2e = {m["name"]: m for m in DOC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in DOC["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in DOC["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells
            moved = e2e[m["moves"]]
            assert cell in moved.get("workloads", cells)
        with open(os.path.join(BENCH, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.isfile(
            os.path.join(BENCH, "readers", spec["reader"] + ".py"))
    for cell in cells:
        assert any(cell in m.get("workloads", cells) for m in DOC["per_layer"])
        assert sum(cell in m.get("workloads", cells)
                   for m in DOC["end_to_end"]) >= 2


def test_a_full_check_fits_its_allowance_with_24_cells():
    rs = DOC["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
