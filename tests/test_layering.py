"""The package's layer order, held where a PR cannot miss it.

Every ``risingwave_tpu.*`` import of every module — module-level and
deferred alike — is read off the source with ``ast`` and ranked by the
top-level unit it starts in and the one it points at. An import may
point down the order or stay inside its rank; one that points up must be
an edge of ``ALLOWED``, with the ROADMAP debt that owns it (D20 lists the
same edges). A case fails when an unlisted upward edge appears AND when
a listed edge is no longer there, so the list can only shrink and cannot
go stale. One more case imports ``risingwave_tpu.executors`` in a fresh
interpreter and names what must not have come with it.
"""

import ast
import pathlib
import subprocess
import sys

import pytest

pytestmark = pytest.mark.smoke

PKG = pathlib.Path(__file__).resolve().parents[1] / "risingwave_tpu"

# bottom up; the units of one rank may import each other. ``trace``
# (which imports ``epoch_trace`` as it loads), ``metrics``,
# ``event_log``, ``integrity`` and ``resilience`` are importable from
# any layer, which puts them at the bottom: what THEY import of the
# layers above is an upward edge like any other.
ORDER = (
    (
        "types", "config", "utils_heap", "utils_sync_point", "native",
        "udf_server", "trace", "epoch_trace", "metrics", "event_log",
        "integrity", "resilience",
    ),
    ("array", "expr"),
    ("ops",),
    ("storage",),
    ("executors", "connectors"),
    ("parallel",),
    # the observers watch a runtime, and rank with it
    (
        "runtime", "blackbox", "deviceprof", "profiler", "freshness",
        "provenance",
    ),
    ("sql",),
    ("batch", "frontend", "cluster"),
    ("analysis", "sim", "queries", "__main__"),
)
RANK = {unit: i for i, units in enumerate(ORDER) for unit in units}

# (importing module, imported module) -> the debt that owns the edge.
# Nothing here starts in array, expr, ops, storage or executors, and
# nothing of parallel points at runtime.
ALLOWED = {
    # runtime <-> sql: the DML manager and the arrangements parse SQL,
    # the fragmenter takes the planner's PlannedMV apart (D13: one
    # session path, planned once)
    ("runtime.dml", "sql.parser"): "D13",
    ("runtime.arrangements", "sql.parser"): "D13",
    ("runtime.fragmenter", "sql.planner"): "D13",
    # transfer_guard and SIGNATURES are hot-path utilities filed under
    # an analyzer; the governor's budget and the fragmenter's device
    # check are the analyzers' own (D8)
    ("runtime.graph", "analysis.jax_sanitizer"): "D8",
    ("runtime.pipeline", "analysis.jax_sanitizer"): "D8",
    ("runtime.runtime", "analysis.jax_sanitizer"): "D8",
    ("runtime.shape_governor", "analysis.jax_sanitizer"): "D8",
    ("runtime.shape_governor", "analysis.shape_domain"): "D8",
    ("runtime.fragmenter", "analysis.plan_verifier"): "D8",
    ("frontend.session", "analysis.lint"): "D8",
    ("frontend.session", "analysis.diagnostics"): "D8",
    # the sharded copies declare their mesh contracts to the analyzer
    ("parallel.sharded_agg", "analysis.mesh_domain"): "D2",
    ("parallel.sharded_join", "analysis.mesh_domain"): "D2",
    ("parallel.sharded_mv", "analysis.mesh_domain"): "D2",
    ("parallel.sharded_top_n", "analysis.mesh_domain"): "D2",
    # the telemetry ring: the free modules reach up into the observers
    ("epoch_trace", "blackbox"): "D4",
    ("epoch_trace", "deviceprof"): "D4",
    ("epoch_trace", "freshness"): "D4",
    ("epoch_trace", "profiler"): "D4",
    ("epoch_trace", "parallel.meshprof"): "D4",
    ("metrics", "blackbox"): "D4",
    ("metrics", "deviceprof"): "D4",
    ("metrics", "freshness"): "D4",
    ("metrics", "parallel.meshprof"): "D4",
    ("deviceprof", "analysis.fusion_analyzer"): "D4",
    ("deviceprof", "analysis.lint"): "D4",
    ("deviceprof", "sql"): "D4",
    # the degraded-mode spill rebuilds StateDelta rows
    ("resilience", "storage.state_table"): "D4",
}


def _modules():
    """Dotted name (less the package's own) -> path, of every module."""
    out = {}
    for path in sorted(PKG.rglob("*.py")):
        parts = list(path.relative_to(PKG).with_suffix("").parts)
        if parts[-1] == "__init__":
            parts.pop()
        out[".".join(parts)] = path
    return out


MODULES = _modules()
UNITS = sorted({name.split(".")[0] for name in MODULES if name})


def _imports(name, path):
    """Every module of the package that ``name`` imports, anywhere in
    its source."""
    package = name if path.name == "__init__.py" else name.rpartition(".")[0]
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            targets = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = ["risingwave_tpu"] + (package.split(".") if package else [])
                up = up[: len(up) - node.level + 1]
                base = ".".join(up + ([node.module] if node.module else []))
            # ``from pkg import submodule`` imports the submodule
            targets = [
                f"{base}.{a.name}"
                if f"{base}.{a.name}".partition(".")[2] in MODULES
                else base
                for a in node.names
            ]
        else:
            continue
        for target in targets:
            head, _, rest = target.partition(".")
            if head == "risingwave_tpu" and rest:
                found.add(rest)
    return found


def _upward_edges(unit):
    edges = set()
    for name, path in MODULES.items():
        if name.split(".")[0] != unit:
            continue
        for target in _imports(name, path):
            if RANK[target.split(".")[0]] > RANK[unit]:
                edges.add((name, target))
    return edges


@pytest.mark.parametrize("unit", UNITS)
def test_imports_point_down_the_order(unit):
    assert unit in RANK, f"{unit} has no place in ORDER: give it one"
    found = _upward_edges(unit)
    listed = {edge for edge in ALLOWED if edge[0].split(".")[0] == unit}
    assert not found - listed, (
        f"new upward imports out of {unit}: {sorted(found - listed)}"
    )
    assert not listed - found, (
        f"ALLOWED lists edges that are gone, take them out: "
        f"{sorted(listed - found)}"
    )


def test_allow_list_is_of_this_package():
    """No edge of a unit the per-unit cases never visit, every edge with
    its debt, and the lower layers' list empty."""
    closed = {"array", "expr", "ops", "storage", "executors"}
    for (src, dst), debt in ALLOWED.items():
        assert src in MODULES and dst in MODULES, (src, dst)
        assert debt.startswith("D"), (src, dst, debt)
        assert src.split(".")[0] not in closed, (src, dst)
        assert not (
            src.startswith("parallel.") and dst.startswith("runtime")
        ), (src, dst)


def test_executors_import_nothing_above_them():
    """``import risingwave_tpu.executors`` in a fresh interpreter leaves
    the runtime, the mesh, the planner, the analyzers, the front ends
    and the observers unloaded."""
    above = (
        "runtime", "parallel", "sql", "analysis", "frontend", "batch",
        "cluster", "blackbox", "profiler", "freshness",
    )
    code = (
        "import sys, risingwave_tpu.executors\n"
        "print(*sorted(m for m in sys.modules "
        "if m.startswith('risingwave_tpu.')))"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    loaded = [
        m for m in out.stdout.split()
        if m.split(".")[1] in above
    ]
    assert not loaded, loaded
