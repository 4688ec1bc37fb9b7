"""A pushed chunk as wide as what it holds (PR 32). ``StreamingRuntime.
push`` cuts a host-built chunk to the smallest size of ``array/
lattice.push_lattice`` that holds its rows, from the count
``StreamChunk.from_numpy`` leaves on the chunk, where the fragment and
what it is routed on to declare that they take such widths. Held here,
on NEXmark q8 as the benchmark's configuration writes it: the answers
are those of the uncut feed; the cut reads nothing off the device; every
size is compiled when the runtime first learns the width a feeder
builds, so a size first met later compiles nothing, and that pass leaves
no mark; an epoch-batched head and a fused fragment get the chunk
untouched; the counter and the span say what was sent; and the size
chosen."""

import json
import os
import sys

import jax
import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.dedup import AppendOnlyDedupExecutor
from risingwave_tpu.executors.hash_join import HashJoinExecutor
from risingwave_tpu.frontend import SqlSession
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.sql import Catalog
from risingwave_tpu.storage.object_store import LocalFsObjectStore
from risingwave_tpu.trace import TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
import nexmark_gen  # noqa: E402  (the benchmark's generator: Beam's records)
from system import System  # noqa: E402  (the benchmark's way into the program)

with open(os.path.join(ROOT, "benchmarks", "configs", "nexmark_q8.json")) as f:
    Q8 = json.load(f)
FULL = 8192
PERSON_COLS = "id, name, email_address, credit_card, city, state, date_time, extra"
AUCTION_COLS = (
    "id, item_name, description, initial_bid, reserve, date_time, expires, "
    "seller, category, extra"
)


class Feed:
    """The q8 deployment at a test's size, fed chunks built at ``FULL``
    lanes that hold as many rows as a case says."""

    def __init__(self, tmp_path, seed=7, capacity=1 << 15):
        self.system = System(
            Q8, capacity, FULL, str(tmp_path), nexmark_gen.VOCAB,
            nexmark_gen.TEXT,
        )
        self.rt = self.system.runtime
        gen = nexmark_gen.Generator(seed, Q8["generator"])
        self.events = gen.events(0, 400_000, Q8["streams"])
        self.at = {"person": 0, "auction": 0}

    def push(self, stream, rows):
        lo = self.at[stream]
        self.at[stream] = lo + rows
        cols = {
            k: v[lo:lo + rows]
            for k, v in self.events[stream].items() if k != "eid"
        }
        self.system.push(stream, cols, rows)

    def epoch(self, *pushes):
        self.system.begin_epoch()
        for stream, rows in pushes:
            self.push(stream, rows)
        self.system.barrier()
        self.system.end_epoch()

    def read(self, sql):
        out, _ = self.system.session.execute(sql)
        names = list(out)
        return sorted(zip(*(np.asarray(out[n]).tolist() for n in names)))

    def answers(self):
        """The view, both tables (every column but the hidden row id,
        which counts the lanes a table was handed), and the digest of
        every state table of the view's fragment."""
        return {
            "q8": self.read("SELECT id, name, starttime FROM q8"),
            "person": self.read(f"SELECT {PERSON_COLS} FROM person"),
            "auction": self.read(f"SELECT {AUCTION_COLS} FROM auction"),
            "digests": {
                ex.table_id: ex.state_digest()
                for ex in self.rt.fragments["q8"].executors
                if hasattr(ex, "state_digest")
            },
        }

    def close(self):
        self.system.close()


# 550 / 1,650 rows an epoch are the steady cell's chunks, 2,048 / 6,144
# the backlog's: the first three fit the quarter size, the last does not
_FEED = [
    [("person", 550), ("auction", 1650)],
    [("person", 2048), ("auction", 6144)],
    [("person", 550), ("auction", 1650), ("person", 2048), ("auction", 6144)],
]


def _lanes_sent():
    return {
        dict(k)["fragment"] + ":" + dict(k)["lanes"]: v
        for k, v in REGISTRY.counter("push_chunks_total")._values.items()
    }


def test_the_cut_feed_gives_the_uncut_feeds_answers(tmp_path, monkeypatch):
    """(a) and (e): the same feed into the plan as it declares itself
    and into one built without the join's declaration (so nothing on
    the route is cut): the same view, tables and state digests; the
    counter and the span's ``capacity=`` read what was sent."""
    before = _lanes_sent()
    TRACER.clear()
    cut = Feed(tmp_path / "cut")
    try:
        for pushes in _FEED:
            cut.epoch(*pushes)
        got = cut.answers()
        sent = {
            k: v - before.get(k, 0) for k, v in _lanes_sent().items()
            if v != before.get(k, 0)
        }
        pushes = [
            (sp.args["fragment"], sp.args["rows"], sp.args["capacity"])
            for sp in TRACER.spans() if sp.name == "push"
        ]
    finally:
        cut.close()
    assert sent == {
        "person:2048": 4, "auction:2048": 2, "auction:8192": 2,
    }
    assert pushes == [
        ("person", 550, 2048), ("auction", 1650, 2048),
        ("person", 2048, 2048), ("auction", 6144, 8192),
        ("person", 550, 2048), ("auction", 1650, 2048),
        ("person", 2048, 2048), ("auction", 6144, 8192),
    ]
    monkeypatch.setattr(HashJoinExecutor, "per_chunk_step", False)
    before = _lanes_sent()
    plain = Feed(tmp_path / "plain")
    try:
        assert plain.rt.fragments["q8"].push_widths(FULL) == (FULL,)
        for pushes in _FEED:
            plain.epoch(*pushes)
        want = plain.answers()
        sent = {
            k: v - before.get(k, 0) for k, v in _lanes_sent().items()
            if v != before.get(k, 0)
        }
    finally:
        plain.close()
    assert sent == {"person:8192": 4, "auction:8192": 4}
    assert len(want["q8"]) > 100
    assert len(want["person"]) == 2 * (550 + 2048)
    assert set(want["digests"]) >= {"q8.join5", "q8.mview"}
    assert got == want


def test_a_recovery_between_cut_chunks_keeps_the_answers(tmp_path, monkeypatch):
    """The state rebuilt from the checkpoints of cut chunks, fed more
    of them, equals the uncut feed that never recovered: the view, the
    tables, the digests."""
    cut = Feed(tmp_path / "cut")
    try:
        cut.epoch(*_FEED[0])
        cut.epoch(*_FEED[1])
        cut.rt.wait_checkpoints()
        cut.rt.recover()
        cut.epoch(*_FEED[2])
        got = cut.answers()
    finally:
        cut.close()
    monkeypatch.setattr(HashJoinExecutor, "per_chunk_step", False)
    plain = Feed(tmp_path / "plain")
    try:
        for pushes in _FEED:
            plain.epoch(*pushes)
        assert got == plain.answers()
    finally:
        plain.close()


def test_the_cut_reads_nothing_off_the_device(tmp_path):
    """(b): once the route's widths are settled, cutting a chunk is a
    dictionary lookup and one jitted slice of arrays the device holds:
    no transfer either way."""
    feed = Feed(tmp_path)
    try:
        feed.epoch(("person", 100), ("auction", 300))
        cols = {
            k: v[:550] for k, v in feed.events["auction"].items()
            if k in ("id", "initial_bid", "reserve", "seller", "category")
        }
        chunk = StreamChunk.from_numpy(cols, FULL)
        feed.rt._push_plans[("auction", "single", FULL)] = (2048, FULL)
        with jax.transfer_guard("disallow"):
            cut = feed.rt._cut_to_rows("auction", chunk, "single")
        assert (cut.capacity, cut.host_rows) == (2048, 550)
        np.testing.assert_array_equal(
            np.asarray(cut.col("seller"))[:550], cols["seller"]
        )
        assert int(np.asarray(cut.valid).sum()) == 550
    finally:
        feed.close()


# the programs of the route that take a pushed chunk's width
_WIDTH_PROGRAMS = (
    "join_step_fn", "dedup_step", "_project_step", "_hop", "_add_edge_rows",
    "_leading_lanes", "_upsert_step",
)


def _compiles(spans, epoch):
    return [
        sp.args.get("fun_name", "") for sp in spans
        if sp.name == "compile" and sp.epoch == epoch
    ]


def _marks(feed):
    """Everything the warm-up pass may not move."""
    out = {}
    for ex in feed.rt.fragments["q8"].executors:
        if isinstance(ex, HashJoinExecutor):
            out[ex.table_id] = dict(
                bounds=dict(ex._bound),
                capacity=(ex.left.capacity, ex.right.capacity),
                claimed=(int(ex.left.table.occupancy()),
                         int(ex.right.table.occupancy())),
                dirty=int(np.asarray(ex.left.sdirty).sum()
                          + np.asarray(ex.right.sdirty).sum()),
                overflow=bool(ex._em_overflow),
            )
        elif isinstance(ex, AppendOnlyDedupExecutor):
            out[ex.table_id] = dict(
                bound=ex._bound,
                capacity=ex.table.capacity,
                claimed=int(ex.table.occupancy()),
                dirty=int(np.asarray(ex.sdirty).sum()),
            )
    for name in ("person", "auction"):
        gen, mv = feed.rt.fragments[name].executors
        out[name] = dict(base=gen._base, rows=len(mv.snapshot()))
    return out


def test_every_width_is_compiled_when_the_route_first_meets_a_capacity(
    tmp_path, monkeypatch
):
    """(c): the first chunk built at 8,192 lanes settles the route's
    widths and sends a chunk with no valid row of both of them the same
    way, so the epoch that first meets the quarter size compiles no
    program that takes a chunk's width; and the pass leaves no mark:
    the same epochs without it read the same everywhere. A capacity of
    its own (the process keeps compiled programs, so a shape another
    test built would prove nothing), large enough that no table grows."""
    capacity = 1 << 18
    TRACER.clear()
    warmed = Feed(tmp_path / "warmed", capacity=capacity)
    try:
        # both streams' first chunks are too full to cut
        warmed.epoch(("person", 3000), ("auction", 6144))
        spans = TRACER.spans()
        warm = {sp.sid: sp for sp in spans if sp.name == "actor.warm"}
        assert sorted(
            (sp.args["port"], sp.args["lanes"]) for sp in warm.values()
            if sp.args["actor"] == "join#0"
        ) == [(0, [2048]), (0, [FULL]), (1, [2048]), (1, [FULL])]
        built = [
            sp.args["fun_name"] for sp in spans if sp.name == "compile"
            and sp.parent in warm
            and sp.args.get("event") == "backend_compile_duration"
        ]
        # the pass built each side's step at both widths here (so none
        # was in the process before)
        assert built.count("jit(join_step_fn)") == 4
        assert built.count("jit(dedup_step_fn)") == 4
        warmed.epoch(("person", 3000), ("auction", 6144))
        # the quarter size, first met by a row
        warmed.epoch(("person", 550), ("auction", 1650))
        met = _compiles(TRACER.spans(), warmed.rt.epoch)
        assert not [f for f in met if any(p in f for p in _WIDTH_PROGRAMS)], met
        got = _marks(warmed)
    finally:
        warmed.close()
    monkeypatch.setattr(StreamingRuntime, "_warm_into", lambda *a: None)
    plain = Feed(tmp_path / "plain", capacity=capacity)
    try:
        plain.epoch(("person", 3000), ("auction", 6144))
        plain.epoch(("person", 3000), ("auction", 6144))
        plain.epoch(("person", 550), ("auction", 1650))
        assert got == _marks(plain)
        assert got["person"]["rows"] == 6550
        assert got["q8.join5"]["capacity"] == (capacity, capacity)
    finally:
        plain.close()


def _spy_on_push(monkeypatch, pipeline):
    seen = []
    push = pipeline.push

    def spy(chunk, *a, **kw):
        seen.append(chunk)
        return push(chunk, *a, **kw)

    monkeypatch.setattr(pipeline, "push", spy)
    return seen


def test_an_epoch_batched_head_gets_the_chunk_untouched(tmp_path, monkeypatch):
    """(d): q5's head stacks an epoch's chunks into one program, so the
    view takes the full width only, and with it the table it reads."""
    from test_nexmark_q5_sql import Served

    served = Served(tmp_path, 2048)
    try:
        assert served.rt.fragments["bid"].push_widths(2048) == (512, 2048)
        assert served.rt.fragments["q5"].push_widths(2048) == (2048,)
        seen = _spy_on_push(monkeypatch, served.rt.fragments["bid"])
        bids = {
            "auction": np.arange(100, dtype=np.int64),
            "bidder": np.zeros(100, np.int64),
            "price": np.ones(100, np.int64),
            "date_time": np.full(100, 20_000, np.int64),
        }
        # (the counter is the process's: another file's fragment ``bid``
        # may have sent its own chunks in this worker before)
        before = _lanes_sent().get("bid:2048", 0)
        cut_before = _lanes_sent().get("bid:512", 0)
        served.push(bids, 0, 100)
        served.rt.barrier()
        (chunk,) = seen
        assert (chunk.capacity, chunk.host_rows) == (2048, 100)
        assert _lanes_sent()["bid:2048"] == before + 1
        assert _lanes_sent().get("bid:512", 0) == cut_before
    finally:
        served.close()


def test_a_fused_fragment_gets_the_chunk_untouched(tmp_path, monkeypatch):
    """(d): a fused barrier program is keyed by its epoch's chunks at
    one width; the chunk that reaches it is the one that was pushed."""
    from risingwave_tpu.queries.nexmark_q import build_q5_lite
    from risingwave_tpu.runtime.fused_step import fuse_pipeline

    q5 = build_q5_lite(capacity=1 << 12)
    assert fuse_pipeline(q5.pipeline, label="q5")
    rt = StreamingRuntime(LocalFsObjectStore(str(tmp_path)))
    rt.register("q5", q5.pipeline)
    assert q5.pipeline.push_widths(2048) == (2048,)
    seen = _spy_on_push(monkeypatch, q5.pipeline)
    cols = {
        "auction": np.arange(100, dtype=np.int64),
        "bidder": np.zeros(100, np.int64),
        "price": np.ones(100, np.int64),
        "date_time": np.full(100, 20_000, np.int64),
    }
    chunk = StreamChunk.from_numpy(cols, 2048)
    rt.push("q5", chunk)
    rt.barrier()
    assert seen == [chunk] and seen[0].columns is chunk.columns
    assert len(q5.mview.snapshot()) > 0


@pytest.fixture(scope="module")
def table(tmp_path_factory):
    """A table fragment (row ids into a host-map table): it takes the
    lattice, and nothing is subscribed behind it."""
    rt = StreamingRuntime(
        LocalFsObjectStore(str(tmp_path_factory.mktemp("table")))
    )
    session = SqlSession(Catalog({}), rt, capacity=1 << 12)
    session.execute("CREATE TABLE t (a BIGINT)")
    yield rt
    session.close()


@pytest.mark.parametrize("capacity,rows,lanes", [
    (8192, None, 8192), (8192, 1, 2048), (8192, 550, 2048),
    (8192, 2048, 2048), (8192, 2049, 8192), (8192, 6144, 8192),
    (8192, 8192, 8192), (4096, 1, 1024), (4096, 1024, 1024),
    (4096, 1025, 4096), (512, 1, 512), (1000, 1, 1000),
])
def test_the_size_chosen(table, capacity, rows, lanes):
    """(f): the smallest declared size that holds the rows; a chunk a
    device step derived, a capacity under four times ``PUSH_SMALL`` and
    one that is no power of two go in as they are."""
    a = np.arange(rows or 7, dtype=np.int64)
    chunk = StreamChunk.from_numpy({"a": a}, capacity)
    if rows is None:
        chunk = StreamChunk.from_data(chunk)  # no count on it
    cut = table._cut_to_rows("t", chunk, "single")
    assert cut.capacity == lanes
    if lanes == capacity:
        assert cut is chunk
    else:
        assert cut.host_rows == rows
        np.testing.assert_array_equal(np.asarray(cut.col("a"))[:rows], a)
        assert int(np.asarray(cut.valid).sum()) == rows
