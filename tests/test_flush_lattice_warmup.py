"""The flush lattice exists before a stream meets it (PR 30). When a
graph-mode view is created, every aggregate's declared flush sizes
(``array/lattice.flush_lattice``) are sent as chunks with no valid
row down what follows the aggregate inside its actor
(``FragmentActor.warm_flush_lattice``). Three things are held here, on
NEXmark q5 as its source writes it: the pass leaves no mark; a size
first met inside the stream then compiles nothing; and the view over a
stream whose epochs sweep the count across every edge of the lattice is
the numpy recompute."""

import numpy as np
import pytest

from risingwave_tpu.executors.hash_agg import HashAggExecutor
from risingwave_tpu.executors.keyed_join import KeyedJoinExecutor
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.runtime.graph import FragmentActor
from risingwave_tpu.trace import TRACER

from test_nexmark_q5_sql import Served, hot_items


def _marks(served):
    """Everything the pass may not move, per checkpointed executor."""
    out = {}
    for ex in served.rt.fragments["q5"]._executors:
        m = {"nbytes": ex.state_nbytes() if hasattr(ex, "state_nbytes") else 0}
        if isinstance(ex, HashAggExecutor):
            m.update(
                bounds=(ex._dirty_bound, ex._insert_bound, ex._occ_note),
                capacity=ex.table.capacity,
                claimed=int(ex.table.occupancy()),
                dirty=int(np.asarray(ex.state.dirty | ex.state.sdirty).sum()),
            )
        elif isinstance(ex, KeyedJoinExecutor):
            m.update(
                bounds=dict(ex._bound),
                capacity=(ex.left.capacity, ex.right.capacity),
                claimed=(int(ex.left.table.occupancy()),
                         int(ex.right.table.occupancy())),
                dirty=int(np.asarray(ex.left.sdirty).sum()
                          + np.asarray(ex.right.sdirty).sum()),
                latches=(bool(ex._overflow), bool(ex._null_key),
                         np.asarray(ex._counts).tolist(), len(ex._probes)),
            )
        if hasattr(ex, "checkpoint_delta"):
            m["staged"] = len(ex.checkpoint_delta())
        out[getattr(ex, "table_id", type(ex).__name__)] = m
    return out


def _one_epoch(served, n=100):
    bids = {
        "auction": np.arange(n, dtype=np.int64),
        "bidder": np.zeros(n, np.int64),
        "price": np.ones(n, np.int64),
        "date_time": np.full(n, 20_000, np.int64),
    }
    served.push(bids, 0, n)
    served.rt.barrier()
    return hot_items(bids["auction"], bids["date_time"])


def test_the_warm_up_leaves_no_mark(tmp_path, monkeypatch):
    """The same plan built with and without the pass reads the same
    after CREATE MATERIALIZED VIEW, and a checkpoint stages nothing for
    either."""
    pulled = REGISTRY.counter("checkpoint_pull_rows_total")
    copied = REGISTRY.counter("checkpoint_pull_copies_total")
    before, copies_before = pulled.total(), copied.total()
    warmed = Served(tmp_path / "warmed", 256)
    ran = [sp for sp in TRACER.spans() if sp.name == "actor.warm"]
    assert [sp.args["lanes"] for sp in ran[-2:]] == 2 * [[256, 2048, 8192]]
    monkeypatch.setattr(FragmentActor, "warm_flush_lattice", lambda self: None)
    plain = Served(tmp_path / "plain", 256)
    monkeypatch.undo()
    try:
        got = _marks(warmed)
        assert got == _marks(plain)
        assert {"q5.agg2", "q5.agg4", "q5.join5", "q5.mview"} <= set(got)
        assert all(m.get("staged", 0) == 0 for m in got.values())
        assert warmed.read() == plain.read() == set()
        # an empty barrier, a checkpoint: nothing was there to stage
        warmed.rt.barrier()
        warmed.rt.wait_checkpoints()
        assert pulled.total() == before
        assert copied.total() == copies_before
        # after the first rows both plans hold the same
        assert _one_epoch(warmed) == warmed.read()
        assert _one_epoch(plain) == plain.read()
        assert _marks(warmed) == _marks(plain)
    finally:
        warmed.close()
        plain.close()


# the join's and the tail's programs, which take a flush chunk's width
_LATTICE_PROGRAMS = (
    "flat_many_step", "flat_emit", "flat_unique_upsert", "keyed_join_scan",
    "_project_step", "_add_edge_rows", "dynamic_slice",
)


def _compiled(spans, epoch, programs_only=False):
    """``compile`` spans of an epoch by function: everything traced,
    lowered or compiled, or the compiled executables alone."""
    return [
        sp.args.get("fun_name", "") for sp in spans
        if sp.name == "compile" and sp.epoch == epoch and (
            not programs_only
            or sp.args.get("event") == "backend_compile_duration"
        )
    ]


# auctions an epoch (five groups each, so ten lanes), the lanes of the
# count's flush chunk, and what the epoch may compile: "other" = anything
# but a program of the join or the tail (the stream's first epochs
# compile what follows the pushed chunks' shapes; 65,536 lanes make the
# MAX grow its table, its host bound counting lanes, which compiles the
# MAX's programs anew as growth does, and the size after it meets the
# grown table); "staging" = only the gather of a checkpoint's staged
# rows, whose pieces have two sizes of their own, by rows (256 or
# blocks of 4,096); "nothing" = no compile span of any kind
_SWEEPS = {
    # the quarter size twice, meeting both staging pieces; then 256
    # first met, the full size, and the quarter on the grown table
    "thin_first": (1 << 18, 3, [
        (40, 16384, "other"), (300, 16384, "other"), (20, 256, "nothing"),
        (1200, 16384, "nothing"), (2000, 65536, "other"),
        (100, 16384, "other"),
    ]),
    # 256 twice; then the quarter size first met
    "empty_first": (1 << 17, 3, [
        (20, 256, "other"), (10, 256, "nothing"), (300, 16384, "staging"),
        (800, 16384, "nothing"),
    ]),
}


@pytest.mark.parametrize("sweep", list(_SWEEPS))
def test_every_size_is_compiled_at_creation_and_the_view_stays_exact(
    tmp_path, sweep
):
    """A capacity of its own a sweep (the process keeps compiled
    programs, so a shape another test built would prove nothing), large
    enough that no table but the MAX's has to grow; the count's flush
    has the sizes 256 / 16,384 / 65,536. Epochs of one timestamp each."""
    capacity, many_widths, plan = _SWEEPS[sweep]
    chunks = REGISTRY.counter("agg_flush_chunks_total")
    rows = REGISTRY.counter("agg_flush_rows_total")
    TRACER.clear()
    served = Served(tmp_path, 2048, capacity=capacity)
    try:
        spans = TRACER.spans()
        warm = {sp.sid: sp for sp in spans if sp.name == "actor.warm"}
        assert [sp.args["lanes"] for sp in warm.values()] == [
            [256, 16384, 65536],  # the MAX's, then the count's
            [256, 16384, 65536],
        ]
        built = [
            sp.args["fun_name"] for sp in spans if sp.name == "compile"
            and sp.parent in warm
            and sp.args.get("event") == "backend_compile_duration"
        ]
        # the pass built the many side's step at its widths here (so
        # none was in the process before), none of the unique side's,
        # which costs passes and not lanes
        assert built.count("jit(flat_many_step)") == many_widths
        assert built.count("jit(flat_unique_upsert)") == 0
        if sweep == "thin_first":
            # and two of the MAX's step: the third would grow its table
            # first, so it never runs at this capacity (the MAX's table
            # is 65,536 groups at either capacity: the first sweep to
            # run builds them)
            assert built.count("jit(_agg_epoch_reduced_mi)") == 2
        met0 = dict(chunks._values)
        rows0 = rows.get(table_id="q5.agg2")
        auction, date_time, base = [], [], 0
        for i, (n, lanes, may) in enumerate(plan):
            TRACER.clear()
            bids = {
                "auction": np.arange(base, base + n, dtype=np.int64),
                "bidder": np.zeros(n, np.int64),
                "price": np.ones(n, np.int64),
                "date_time": np.full(n, 20_000 + 2_000 * i, np.int64),
            }
            base += n
            served.push(bids, 0, n)
            served.rt.barrier()
            auction.append(bids["auction"])
            date_time.append(bids["date_time"])
            spans = TRACER.spans()
            flushed = [sp for sp in spans if sp.name == "agg.flush"
                       and sp.args["table_id"] == "q5.agg2"]
            assert [(sp.args["rows"], sp.args["lanes"]) for sp in flushed] == [
                (10 * n, lanes)
            ], f"epoch {i}"
            epoch = flushed[0].epoch
            if may == "nothing":
                names = _compiled(spans, epoch)
                assert names == [], f"epoch {i}: {sorted(set(names))}"
            elif may == "staging":
                assert set(_compiled(spans, epoch, True)) <= {"jit(_gather)"}
            else:
                assert not [
                    nm for nm in _compiled(spans, epoch, True)
                    if any(p in nm for p in _LATTICE_PROGRAMS)
                ], f"epoch {i}"
            assert served.read() == hot_items(
                np.concatenate(auction), np.concatenate(date_time)
            ), f"epoch {i}"
        met = {
            dict(k)["lanes"]: v - met0.get(k, 0)
            for k, v in chunks._values.items()
            if dict(k)["table_id"] == "q5.agg2"
        }
        want = {}
        for _n, lanes, _may in plan:
            want[str(lanes)] = want.get(str(lanes), 0) + 1
        assert {k: v for k, v in met.items() if v} == want
        assert rows.get(table_id="q5.agg2") - rows0 == 10 * base
    finally:
        served.close()


def test_the_flush_over_the_steps_list_is_compiled_at_creation(tmp_path):
    """PR 34: a barrier's flush ranges over the list of the slots the
    epoch's steps wrote, cut to a declared length
    (``lattice.touched_lattice``), one program a length. Creating the
    view compiles them all beside the table walk's, so a stream's first
    barrier compiles no flush, and goes by the list. (The count's table
    at a capacity no other test of the process builds.)"""
    TRACER.clear()
    served = Served(tmp_path, 2048, capacity=1 << 19)
    try:
        count = next(
            ex for ex in served.rt.fragments["q5"]._executors
            if getattr(ex, "table_id", "") == "q5.agg2"
        )
        assert count.touched_sizes() == (1 << 14, 1 << 16, 1 << 18)
        flushes = [
            sp for sp in TRACER.spans() if sp.name == "compile"
            and sp.args.get("fun_name") == "jit(flush)"
            and sp.args.get("event") == "backend_compile_duration"
        ]
        # the table walk and the three lengths, at the least the count's
        assert len(flushes) >= 1 + len(count.touched_sizes())
        TRACER.clear()
        assert _one_epoch(served) == served.read()
        spans = TRACER.spans()
        assert not [
            sp for sp in spans if sp.name == "compile"
            and "flush" in sp.args.get("fun_name", "")
        ]
        rounds = [sp.args for sp in spans if sp.name == "agg.flush"]
        assert {a["table_id"] for a in rounds} == {"q5.agg2", "q5.agg4"}
        assert all(
            (a["path"], a["walked"], a["table_round"]) == ("touched", 1 << 14, 0)
            for a in rounds
        )
    finally:
        served.close()
