"""What the device's operations are: the named scopes of the unfused
kernels come from one table (``trace.SCOPES``), the program reads the
instruction -> scope map off its own loaded executables
(``trace.program_ops``) and names a run's largest operations from it
(``trace.name_ops``). CPU, small capacities; names, scopes and nesting,
never a time."""

import glob
import json
import os
import re

import jax.numpy as jnp
import pytest

from risingwave_tpu import trace
from risingwave_tpu.__main__ import driven_session
from risingwave_tpu.ops.hash_table import HashTable, lookup_or_insert
from risingwave_tpu.trace import TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- the vocabulary -------------------------------------------------------


def test_every_scope_the_kernels_open_is_a_line_of_the_table():
    """A scope is opened by its whole name, or inside a loop's body by
    the words that follow the loop's own scope in the table."""
    tails = {
        sc[len(up) + 1:] for sc in trace.SCOPES for up in trace.SCOPES
        if sc.startswith(up + "/")
    }
    opened = {}
    for path in glob.glob(
        os.path.join(ROOT, "risingwave_tpu", "**", "*.py"), recursive=True
    ):
        if path.endswith(os.path.join("runtime", "fused_step.py")):
            continue  # its ``fused/*`` stages: no cell enters it
        with open(path) as f:
            # (a call, not a mention in a comment: ``jax.`` before it)
            for name in re.findall(
                r'jax\.named_scope\(\s*"([a-z0-9_/]+)"\)', f.read()
            ):
                opened.setdefault(name, path)
    assert opened, "no kernel opens a scope"
    unknown = {
        name: path for name, path in opened.items()
        if name not in trace.SCOPES and name not in tails
    }
    assert not unknown
    for sc in trace.SCOPES:
        assert re.fullmatch(r"[a-z0-9_]+(/[a-z0-9_]+)+", sc), sc


@pytest.mark.parametrize(
    "op_name, scope",
    [
        (
            "jit(_upsert_step_ed)/topn/rows/jit(lookup_or_insert)/hash/"
            "probe/while/body/match/jit(_where)/select_n",
            "topn/rows/hash/probe/match",
        ),
        ("jit(lookup_or_insert)/hash/probe/while", "hash/probe"),
        ("jit(f)/hash/probe/while/cond/lt", "hash/probe"),
        # jax's own lowerings close a name with other words, or none
        (
            "jit(join_step_fn)/join/bucket/apply_side/jit(cumsum)/"
            "apply_side/reduce_window_sum",
            "join/bucket/apply_side",
        ),
        ("jit(_general_over_step)/over/frame", "over/frame"),
        # a sort in a branch of a loop of the rank
        (
            "jit(_rank)/topn/rank/sort/while/body/cond/branch_1_fun/sort",
            "topn/rank/sort",
        ),
        ("jit(f)/agg/apply/x64/split/bitcast_convert_type;jit(f)/mul",
         "agg/apply/x64/split"),
        ("args[0]", ""),
        ("jit(f)/jit(cumsum)/f/reduce_window_sum", ""),
    ],
)
def test_a_scope_is_cut_out_of_an_op_name(op_name, scope):
    assert trace.scope_of(op_name) == scope
    assert trace.split_scopes(scope) is not None


def test_a_path_splits_into_the_tables_scopes_or_not_at_all():
    assert trace.split_scopes("topn/rows/hash/probe/match") == [
        "topn/rows", "hash/probe/match",
    ]
    assert trace.split_scopes("") == []
    assert trace.split_scopes("topn/rows/nonsense") is None
    assert trace.split_scopes("probe") is None


# -- the five hot programs -----------------------------------------------

HOT = {
    # configuration -> (XLA module, scopes its optimized HLO must hold)
    "nexmark_q18": (
        "jit__upsert_step_ed",
        {"topn/rows/hash/probe", "topn/groups/hash/probe", "topn/marks",
         "topn/rows/hash/probe/match", "topn/rows/hash/set_live"},
    ),
    "nexmark_q5": (
        "jit__agg_epoch_reduced_mi",
        {"agg/reduce_by_key/sort", "agg/reduce_by_key/combine",
         "hash/probe", "hash/lookup", "agg/apply", "agg/minput"},
    ),
    "nexmark_q4": (
        "jit_stream_join_step",
        {"join/stream/probe", "join/stream/probe/hash/lookup",
         "join/stream/chain", "join/stream/emit",
         "join/stream/fold/hash/probe"},
    ),
    "nexmark_q8": (
        "jit_join_step_fn",
        {"join/bucket/probe/hash/lookup", "join/bucket/emit",
         "join/bucket/apply_side/hash/probe"},
    ),
    "nexmark_q6": (
        "jit__general_over_step",
        {"over/arena/hash/probe", "over/sort", "over/frame", "over/diff"},
    ),
}


def _config(name):
    path = os.path.join(ROOT, "benchmarks", "configs", name + ".json")
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def programs():
    """``program_ops`` after a session of each hot configuration ran
    its route once at a small capacity."""
    for name in HOT:
        with driven_session(_config(name), 1024):
            pass
    return trace.program_ops()


@pytest.mark.parametrize("config", sorted(HOT))
def test_a_hot_programs_scopes_are_the_tables(programs, config):
    module, expected = HOT[config]
    assert module in programs, sorted(programs)
    found = set()
    for variant in programs[module]:
        assert variant["shapes"]
        for op in variant["ops"].values():
            assert {"scope", "source", "opcode", "within"} <= set(op)
            assert set(op) <= {"scope", "source", "opcode", "within", "scope_from"}
            parts = trace.split_scopes(op["scope"])
            assert parts is not None, op
            # (a scope opened inside itself says nothing twice)
            assert all(a != b for a, b in zip(parts, parts[1:])), op
            found.add(op["scope"])
    assert expected <= found, expected - found


def test_the_other_programs_of_the_cells_are_named_too(programs):
    """The flush, the keyed join, dedup, the rank and the diff carry
    their scopes; a program with no scope of the table has none."""
    want = {
        "jit_flush": "agg/flush/select",
        "jit_flat_many_step": "join/keyed/probe/hash/lookup",
        "jit_flat_emit": "join/keyed/pick",
        "jit_dedup_step_fn": "dedup/seen/hash/probe",
        "jit__general_over_emit": "over/emit",
        "jit__epoch_reduced_fn": "agg/reduce_by_key/sort",
    }
    for module, scope in want.items():
        scopes = {
            op["scope"] for v in programs[module] for op in v["ops"].values()
        }
        assert scope in scopes, (module, sorted(scopes))


# -- program_ops ----------------------------------------------------------


def _probe_once(capacity=256, lanes=64):
    table = HashTable.create(capacity, (jnp.int64,))
    keys = (jnp.arange(lanes, dtype=jnp.int64) * 7 + 3,)
    return lookup_or_insert(table, keys, jnp.ones(lanes, jnp.bool_))


def test_program_ops_names_the_probe_loop_and_what_runs_inside_it():
    _probe_once()
    variants = trace.program_ops("jit_lookup_or_insert")["jit_lookup_or_insert"]
    assert set(trace.program_ops("jit_lookup_or_insert")) == {
        "jit_lookup_or_insert"
    }
    for variant in variants:
        ops = variant["ops"]
        loops = [n for n, op in ops.items() if op["opcode"] == "while"]
        assert len(loops) == 1
        (loop,) = loops
        assert ops[loop]["scope"] == "hash/probe"
        assert ops[loop]["source"].startswith("hash_table.py:")
        assert ops[loop]["within"] is None

        def under(name):
            up = ops[name]["within"]
            while up is not None and up != loop:
                up = ops[up]["within"]
            return up == loop

        for part in ("match", "elect", "write", "twins"):
            inside = [
                n for n, op in ops.items()
                if op["scope"] == "hash/probe/" + part
            ]
            assert inside and all(under(n) for n in inside), part
        # what runs before the loop is not inside it
        hashing = [n for n, op in ops.items()
                   if (op["source"] or "").startswith("hashing.py")]
        assert hashing and not any(under(n) for n in hashing)


def test_program_ops_keeps_every_variant_of_a_module_with_its_shapes():
    _probe_once(256, 64)
    _probe_once(512, 128)
    variants = trace.program_ops("jit_lookup_or_insert")["jit_lookup_or_insert"]
    shapes = [v["shapes"] for v in variants]
    assert len(set(shapes)) == len(shapes) >= 2
    assert any("[512]" in s and "[128]" in s for s in shapes)
    assert any("[256]" in s and "[64]" in s for s in shapes)


def test_program_ops_compiles_and_records_nothing():
    """Asked or not, the map costs no program: no compile span enters
    the ring while it is made, and a session that never asks runs the
    programs it ran."""
    _probe_once()
    TRACER.clear()
    before = sorted(trace.program_ops())
    assert trace.program_ops("no_such_module") == {}
    assert sorted(trace.program_ops()) == before
    assert [sp.name for sp in TRACER.spans()] == []


# -- name_ops -------------------------------------------------------------

STUB = {
    "jit_step": [{
        "shapes": "(s32[8]{0})->s32[8]{0}",
        "ops": {
            "while.17": {"scope": "topn/rows/hash/probe", "opcode": "while",
                         "source": "hash_table.py:221", "within": None},
            "fusion.151": {"scope": "topn/rows/hash/probe/match",
                           "source": "hash_table.py:221", "opcode": "fusion",
                           "within": "while.17"},
            "gather.3": {"scope": "topn/rows/hash/probe/match",
                         "source": "hash_table.py:221", "opcode": "gather",
                         "within": "fusion.151"},
            "fusion.9": {"scope": "topn/marks", "opcode": "fusion",
                         "source": "top_n_plain.py:405", "within": None},
        },
    }],
}


def test_name_ops_marks_a_row_whose_loop_is_among_the_rows():
    rows = [["jit_step/while.17", 2.0], ["jit_step/gather.3", 0.4],
            ["jit_step/fusion.9", 0.3], ["jit_other/fusion.1", 0.2],
            ["-/copy.1", 0.1]]
    named = trace.name_ops(rows, STUB)
    assert [r["op"] for r in named] == [r[0] for r in rows]
    assert [r["seconds"] for r in named] == [r[1] for r in rows]
    loop, inner, marks, other, outside = named
    assert loop["scope"] == "topn/rows/hash/probe" and not loop["nested"]
    assert loop["source"] == "hash_table.py:221" and loop["opcode"] == "while"
    # two levels up: gather.3 is in fusion.151, which is in while.17
    assert inner["within"] == "fusion.151" and inner["nested"]
    assert marks["scope"] == "topn/marks" and not marks["nested"]
    for row in (other, outside):
        assert row["scope"] is None and not row["nested"]
        assert "ambiguous" not in row
    # without the loop among them, its body's rows stand for themselves
    alone = trace.name_ops(rows[1:], STUB)
    assert not alone[0]["nested"]
    assert sum(r["seconds"] for r in named if not r["nested"]) == 2.6


def test_name_ops_says_where_two_executables_of_one_name_disagree():
    other = {
        "shapes": "(s32[16]{0})->s32[16]{0}",
        "ops": {
            # the same loop under the same name, another under fusion.9's
            "while.17": dict(STUB["jit_step"][0]["ops"]["while.17"]),
            "fusion.9": {"scope": "topn/groups", "opcode": "fusion",
                         "source": "top_n_plain.py:415", "within": None},
        },
    }
    both = {"jit_step": STUB["jit_step"] + [other]}
    loop, fusion, only_one = trace.name_ops(
        [["jit_step/while.17", 1.0], ["jit_step/fusion.9", 0.5],
         ["jit_step/fusion.151", 0.2]], both,
    )
    assert "ambiguous" not in loop and loop["scope"] == "topn/rows/hash/probe"
    assert fusion["ambiguous"] and fusion["scope"] is None
    assert sorted(c["scope"] for c in fusion["candidates"]) == [
        "topn/groups", "topn/marks",
    ]
    assert "ambiguous" not in only_one and only_one["nested"]
    # two steps of one loop's body: the loop is what they share
    other["ops"]["fusion.151"] = {
        "scope": "topn/rows/hash/probe/write", "source": "hash_table.py:221",
        "opcode": "fusion", "within": "while.17",
    }
    (body,) = trace.name_ops([["jit_step/fusion.151", 0.2]], both)
    assert body["ambiguous"] and body["scope"] == "topn/rows/hash/probe"


# -- the parser, on a module written by hand ------------------------------

HLO = """HloModule jit_f, is_scheduled=true, entry_computation_layout={(s64[8]{0})->u32[8]{0}}

FileNames
1 "/somewhere/risingwave_tpu/ops/hash_table.py"

FunctionNames
1 "f"

FileLocations
1 {file_name_id=1 function_name_id=1 line=7 end_line=7 column=0 end_column=1}

StackFrames
1 {file_location_id=1 parent_frame_id=1}

%fused (p: u32[8]) -> u32[8] {
  %p = u32[8]{0} parameter(0)
  ROOT %add.1 = u32[8]{0} add(%p, %p), metadata={op_name="jit(f)/hash/probe/while/body/match/add" stack_frame_id=1}
}

%branch (q: (u32[8], s32[])) -> u32[8] {
  %q = (u32[8]{0}, s32[]) parameter(0)
  ROOT %get-tuple-element.9 = u32[8]{0} get-tuple-element(%q), index=0
}

ENTRY %main.4 (x: s64[8]) -> u32[8] {
  %x = s64[8]{0:T(1024)} parameter(0), metadata={op_name="args[0]"}
  %custom-call.1 = u32[8]{0:T(1024)S(1)} custom-call(%x), custom_call_target="X64SplitLow"
  %copy.2 = u32[8]{0} copy(%custom-call.1)
  %fusion.3 = u32[8]{0} fusion(%copy.2), kind=kLoop, calls=%fused, metadata={op_name="jit(f)/hash/probe/while/body/match/add" stack_frame_id=1}
  %tuple.5 = (u32[8]{0}, s32[]) tuple(%fusion.3, %fusion.3)
  ROOT %conditional.6 = u32[8]{0} conditional(%tuple.5, %tuple.5), branch_computations={%branch, %branch}, metadata={op_name="jit(f)/topn/rank/sort/cond" source_file="/x/top_n_plain.py" source_line=543}
}
"""


def test_the_parser_reads_names_sources_holders_and_lends_scopes():
    ops = trace.parse_hlo(HLO)
    assert set(ops) == {
        "p", "add.1", "q", "get-tuple-element.9", "x", "custom-call.1",
        "copy.2", "fusion.3", "tuple.5", "conditional.6",
    }
    assert ops["fusion.3"] == {
        "scope": "hash/probe/match", "source": "hash_table.py:7",
        "opcode": "fusion", "within": None,
    }
    assert ops["add.1"]["within"] == "fusion.3"
    assert ops["add.1"]["scope"] == "hash/probe/match"
    # a tuple's shape, a source named outright, a branch's holder
    assert ops["conditional.6"]["opcode"] == "conditional"
    assert ops["conditional.6"]["scope"] == "topn/rank/sort"
    assert ops["conditional.6"]["source"] == "top_n_plain.py:543"
    assert ops["get-tuple-element.9"]["within"] == "conditional.6"
    # what the compiler made takes the scope of what reads it, two deep
    assert ops["copy.2"]["scope"] == "hash/probe/match"
    assert ops["copy.2"]["scope_from"] == "user"
    assert ops["custom-call.1"]["scope"] == "hash/probe/match"
    assert ops["custom-call.1"]["opcode"] == "custom-call"
    assert ops["custom-call.1"]["source"] is None
    # a parameter is nobody's, whatever reads it
    assert ops["x"]["scope"] == "" and "scope_from" not in ops["x"]
    assert "scope_from" not in ops["fusion.3"]
