"""One span tree per epoch (trace.span as the spine of EpochTrace, the
``barrier_stage_ms`` histogram and the profiler's xplane): structure and
counts, never milliseconds or ratios of times."""

import glob
import json
import os
import threading
import time

import numpy as np
import pytest

from risingwave_tpu import trace
from risingwave_tpu import utils_sync_point as sync_point
from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.event_log import EVENT_LOG
from risingwave_tpu.executors.base import Executor
from risingwave_tpu.frontend import SqlSession
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.runtime.fragmenter import GraphPipeline
from risingwave_tpu.runtime.graph import FragmentSpec
from risingwave_tpu.sql import Catalog
from risingwave_tpu.storage.object_store import LocalFsObjectStore, MemObjectStore
from risingwave_tpu.trace import TRACER

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DDL = [
    "CREATE TABLE person (id BIGINT, name VARCHAR, date_time TIMESTAMP)",
    "CREATE TABLE auction (id BIGINT, seller BIGINT, date_time TIMESTAMP)",
    "CREATE MATERIALIZED VIEW q8 AS SELECT p.id, p.name, p.starttime FROM "
    "(SELECT id, name, window_start AS starttime FROM TUMBLE(person, "
    "date_time, INTERVAL '10' SECOND) GROUP BY id, name, window_start) AS p "
    "JOIN (SELECT seller, window_start AS astarttime FROM TUMBLE(auction, "
    "date_time, INTERVAL '10' SECOND) GROUP BY seller, window_start) AS a "
    "ON p.id = a.seller AND p.starttime = a.astarttime",
]
CHUNK = 256


class Q8:
    """A q8-shaped graph session with a checkpoint on every barrier,
    fed the way an INSERT is routed (``session.dml._targets``)."""

    def __init__(self, state_dir):
        self.rt = StreamingRuntime(
            LocalFsObjectStore(str(state_dir)), checkpoint_frequency=1
        )
        self.session = SqlSession(
            Catalog({}), self.rt, capacity=1 << 12, exec_mode="graph"
        )
        for sql in DDL:
            self.session.execute(sql)
        self.next_id = 0

    def _push(self, stream, cols):
        cols = dict(cols)
        if "name" in cols:
            cols["name"] = self.session.strings.encode(cols["name"])
        chunk = StreamChunk.from_numpy(
            cols, CHUNK, schema=self.session.catalog.tables[stream]
        )
        with self.rt.lock:
            for frag, side in self.session.dml._targets.get(stream, ()):
                self.rt.push(frag, chunk, side)

    def epoch(self, rows=50, pushes=1):
        """One epoch of ``pushes`` x ``rows`` new persons, each selling
        one auction."""
        for _ in range(pushes):
            ids = np.arange(self.next_id, self.next_id + rows, dtype=np.int64)
            self.next_id += rows
            ts = ids * 100_000
            self._push("person", {
                "id": ids, "name": [f"n{i}" for i in ids], "date_time": ts,
            })
            self._push("auction", {
                "id": ids + 1_000_000, "seller": ids, "date_time": ts,
            })
        self.rt.barrier()
        self.rt.wait_checkpoints()
        return self.rt.last_epoch_trace

    def close(self):
        self.session.close()
        for p in self.rt.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()


@pytest.fixture(scope="module")
def q8(tmp_path_factory):
    system = Q8(tmp_path_factory.mktemp("span_tree_state"))
    yield system
    system.close()


def _thread_name(sp):
    return trace._thread_names()[sp.tid]


def _stage_keys_the_benchmark_reads():
    """Every stage key an epoch_stage / barrier_residual metric file of
    a ``nexmark_q8`` cell names: the plan of this file's sessions (a
    metric of another plan's cells alone, as ``topn.diff_ms_per_barrier``
    is q18's, names a stage only that plan's executors write).
    ``device_step`` is a key the program never wrote and no longer
    lists: the outside metric that adds it reads 0.0 for it until a
    benchmark PR drops the term."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        q8_metrics = {
            m["name"] for m in json.load(f)["per_layer"]
            if any(w.startswith("nexmark_q8.") for w in m["workloads"])
        }
    keys = set()
    for path in glob.glob(
        os.path.join(ROOT, "benchmarks", "layer_metrics", "*.json")
    ):
        with open(path) as f:
            spec = json.load(f)
        if spec["name"] in q8_metrics and spec["reader"] in (
            "epoch_stage", "barrier_residual"
        ):
            keys.update(spec["args"]["stages"])
    return keys - {"device_step"}


# -- (a) the tree of one epoch ------------------------------------------


def test_three_barriers_every_span_has_epoch_parent_and_stage(q8):
    q8.epoch()  # the first barrier's compiles stay out of the ring
    TRACER.clear()
    traces = [q8.epoch() for _ in range(3)]
    epochs = [tr.epoch for tr in traces]
    spans = TRACER.spans()
    by_sid = {sp.sid: sp for sp in spans}
    roots = [sp for sp in spans if sp.name == "barrier"]
    assert [sp.epoch for sp in roots] == epochs
    # every span belongs to an epoch; one without is of the epoch that
    # is still open (an actor's wait after the last barrier). The ring is
    # the process's: an actor that an earlier test file on this worker
    # left running idles on, meets no barrier, and so never has an epoch
    mine = {sp.tid for sp in spans if sp.epoch is not None}
    for sp in spans:
        if sp.tid not in mine:
            assert sp.name == "actor.idle" and sp.epoch is None, sp.name
        elif sp.epoch is None:
            assert sp.t0 >= roots[-1].t0, (sp.name, _thread_name(sp))
            assert sp.name in ("actor.idle",), sp.name
        else:
            assert sp.epoch in epochs, (sp.name, sp.epoch)
    # unless a thread's root, a parent on its own thread that encloses it
    children = 0
    for sp in spans:
        if sp.parent is None:
            continue
        up = by_sid[sp.parent]
        children += 1
        assert up.tid == sp.tid, (sp.name, up.name)
        assert up.t0 <= sp.t0 and sp.t0 + sp.dur <= up.t0 + up.dur, (
            sp.name, up.name,
        )
        if sp.epoch is not None and up.epoch is not None:
            assert sp.epoch == up.epoch, (sp.name, up.name)
    assert children > len(spans) // 2
    # one epoch is selected by one identifier, on every thread
    mine = [sp for sp in spans if sp.epoch == epochs[1]]
    threads = {_thread_name(sp) for sp in mine}
    assert "MainThread" in threads
    assert any("worker_loop" in t for t in threads)  # checkpoint worker
    assert {"actor-join#0", "actor-left_src#0", "actor-right_src#0"} <= threads
    names = {sp.name for sp in mine}
    assert {
        "push", "barrier", "barrier.fragment", "dispatch.drain",
        "dispatch.flush", "checkpoint.stage", "checkpoint.marks",
        "checkpoint.pull", "checkpoint.dictionary", "barrier.publish",
        "barrier.bookkeeping", "actor.chunk", "actor.join_step",
        "mv.apply", "actor.idle", "actor.barrier", "actor.fence",
        "checkpoint.queue_wait", "checkpoint.commit", "checkpoint.upload",
        "upload.put", "checkpoint.manifest", "device.read",
    } <= names, names
    # the tree hangs together by name
    parent_name = lambda sp: by_sid[sp.parent].name if sp.parent else None
    for sp in mine:
        want = {
            "barrier.fragment": "barrier",
            "dispatch.drain": "barrier.fragment",
            "dispatch.flush": "barrier.fragment",
            "checkpoint.stage": "barrier",
            "checkpoint.marks": "checkpoint.stage",
            "checkpoint.pull": "checkpoint.marks",
            "checkpoint.dictionary": "checkpoint.stage",
            "barrier.publish": "barrier",
            "barrier.bookkeeping": "barrier",
            "actor.fence": "actor.barrier",
            "actor.join_step": "actor.chunk",
            "checkpoint.upload": "checkpoint.commit",
            "upload.put": "checkpoint.upload",
            "checkpoint.manifest": "checkpoint.commit",
        }.get(sp.name)
        if want is not None:
            assert parent_name(sp) == want, (sp.name, parent_name(sp))
    # counts at the same places: rows a pull moved, strings and bytes
    # the dictionary wrote (its new ones, of all it holds), bytes an
    # upload put, rows against lanes
    pulls = [sp for sp in mine if sp.name == "checkpoint.pull"]
    assert all(
        0 < sp.args["rows"] <= sp.args["padded_rows"] and sp.args["table_id"]
        for sp in pulls
    )
    (dic,) = [sp for sp in mine if sp.name == "checkpoint.dictionary"]
    assert dic.args["strings"] == dic.args["new_strings"] == 50
    assert dic.args["total_strings"] >= 150 and dic.args["bytes"] > 0
    assert all(
        sp.args["bytes"] > 0 for sp in mine if sp.name == "checkpoint.upload"
    )
    chunks = [sp for sp in mine if sp.name == "actor.chunk"]
    assert {sp.args["rows"] for sp in chunks} == {50}
    assert {sp.args["capacity"] for sp in chunks} == {CHUNK}


def test_every_checkpointing_barrier_has_the_benchmarks_stage_keys(q8):
    traces = [q8.epoch() for _ in range(3)]
    wanted = _stage_keys_the_benchmark_reads()
    assert {"checkpoint_stage.dictionary", "ingest.permit_wait",
            "dispatch.drain", "publish", "bookkeeping"} <= wanted
    for tr in traces:
        assert tr.checkpoint
        st = tr.stages_ms
        assert wanted <= set(st), wanted - set(st)
        assert "device_step" not in st  # graph mode never writes it
        assert "compile" not in st  # appears only when it happened
        # no permit was waited for: 0.0, not absent
        assert st["ingest.permit_wait"] == 0.0
        # children never sum above their parent stage (a stage's
        # device_wait cuts across its other children: left out)
        for parent in {k.rsplit(".", 1)[0] for k in st if "." in k}:
            if parent in st:
                kids = sum(
                    v for k, v in st.items()
                    if k.rsplit(".", 1)[0] == parent and k != parent
                    and not k.endswith(".device_wait")
                )
                assert kids <= st[parent] + 1e-6, (parent, st)
        # what the barrier's thread stamped before finalize lies in wall_ms
        assert st["dispatch"] + st["checkpoint_stage"] <= tr.wall_ms + 1e-6
        # per actor: busy, idle, blocked, fence, under its unique label
        for actor in ("q8/join#0", "q8/left_src#0", "q8/right_src#0"):
            for key in ("actor_busy", "actor_idle", "actor_blocked",
                        "actor_fence"):
                assert f"{key}.{actor}" in st, (key, actor)
        assert st["actor_busy.q8/join#0"] > 0.0
        assert st["actor_blocked.q8/join#0"] == 0.0


def test_one_stamp_per_stage_in_the_histogram(q8):
    """``barrier_stage_ms{stage,fragment}`` is fed from the same spans:
    one observation of ``dispatch`` per fragment and barrier (the actor
    no longer stamps it a second time), and ``span_ms`` is gone."""
    REGISTRY.histograms.pop("barrier_stage_ms", None)
    q8.epoch()
    q8.epoch()
    h = REGISTRY.histograms["barrier_stage_ms"]
    counts = {
        dict(k)["fragment"]: n for k, n in h._count.items()
        if dict(k)["stage"] == "dispatch"
    }
    assert counts == {"person": 2, "auction": 2, "q8": 2}, counts
    stages = {dict(k)["stage"] for k in h._count}
    assert {"ingest", "dispatch.drain", "checkpoint_stage.pull", "upload",
            "manifest_commit", "publish", "bookkeeping"} <= stages
    assert "device_step" not in stages
    assert "span_ms" not in REGISTRY.histograms


# -- (b) backpressure lands where it is spent ---------------------------


class _Hold(Executor):
    def apply(self, chunk):
        sync_point.hit("span_tree:hold")
        return [chunk]


def test_permit_wait_and_blocked_actors_land_on_the_right_names():
    gp = GraphPipeline(
        [
            FragmentSpec("src", lambda i: []),
            FragmentSpec("mid", lambda i: [], inputs=[("src", 0)]),
            FragmentSpec("sink", lambda i: [_Hold()], inputs=[("mid", 0)]),
        ],
        {"single": "src"},
        "sink",
        [],
    )
    cap = 16
    for a in gp.graph.actors:  # a channel of few permits: one chunk
        for _port, ch in a.inputs:
            ch._budget = ch._avail = cap
    rt = StreamingRuntime(MemObjectStore(), async_checkpoint=False)
    rt.register("g", gp)
    release = threading.Event()
    sync_point.activate("span_tree:hold", lambda: release.wait(30))

    def _release_once_everyone_waits():
        # the consumer is held; upstream of it every channel fills and
        # every sender waits — then, and only then, let go
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            open_now = {
                fr["span"]
                for stack in trace.active_spans().values()
                for fr in stack
            }
            blocked = [
                t for t, stack in trace.active_spans().items()
                if any(fr["span"] == "actor.blocked" for fr in stack)
            ]
            if "push.permit_wait" in open_now and len(blocked) == 2:
                break
            time.sleep(0.005)
        release.set()

    waiter = threading.Thread(target=_release_once_everyone_waits)
    waiter.start()
    waits = REGISTRY.counter("permits_waited_total")
    pushed_before = waits.get(sender="push")
    TRACER.clear()
    try:
        chunk = StreamChunk.from_numpy(
            {"k": np.arange(4, dtype=np.int64)}, cap
        )
        for _ in range(8):
            rt.push("g", chunk)
        rt.barrier()
    finally:
        release.set()
        waiter.join()
        sync_point.deactivate("span_tree:hold")
        gp.close()
    st = rt.last_epoch_trace.stages_ms
    assert 0.0 < st["ingest.permit_wait"] <= st["ingest"]
    assert st["actor_blocked.g/src#0"] > 0.0
    assert st["actor_blocked.g/mid#0"] > 0.0
    assert st["actor_blocked.g/sink#0"] == 0.0  # nothing downstream of it
    assert st["actor_busy.g/sink#0"] > 0.0  # where the time was spent
    for actor in ("src", "mid", "sink"):
        assert st[f"actor_idle.g/{actor}#0"] >= 0.0
    assert waits.get(sender="push") > pushed_before
    assert waits.get(sender="actor") > 0
    spans = TRACER.spans()
    by_sid = {sp.sid: sp for sp in spans}
    where = {}
    for sp in spans:
        if sp.name in ("push.permit_wait", "actor.blocked"):
            where.setdefault(sp.name, set()).add(_thread_name(sp))
            assert sp.epoch == rt.last_epoch_trace.epoch
            assert sp.args["permits"] > 0
    assert where["push.permit_wait"] == {"MainThread"}
    assert where["actor.blocked"] == {"actor-src#0", "actor-mid#0"}
    for sp in spans:
        if sp.name == "push.permit_wait":
            assert by_sid[sp.parent].name == "push"
        if sp.name == "actor.blocked":
            assert by_sid[sp.parent].name == "actor.chunk"


# -- (c) a compile inside a barrier is named ----------------------------


def test_compile_in_a_barrier_is_a_span_a_stage_and_an_event(q8):
    q8.epoch()
    TRACER.clear()
    seen = {e["seq"] for e in EVENT_LOG.events(limit=100_000)}
    # an epoch that meets the other padded size: 300 changed rows where
    # the epochs before staged 50 (pull_rows moves rows in pieces of 256
    # or of 4,096 lanes and compiles one gather per size)
    tr = q8.epoch(rows=150, pushes=2)
    assert tr.stages_ms.get("compile", 0.0) > 0.0
    compiles = [sp for sp in TRACER.spans() if sp.name == "compile"]
    assert compiles and all(sp.epoch == tr.epoch for sp in compiles)
    by_sid = {sp.sid: sp for sp in TRACER.spans()}
    gathers = [
        sp for sp in compiles
        if "gather" in sp.args["fun_name"]
        and sp.args["event"] == "backend_compile_duration"
    ]
    assert gathers, [sp.args for sp in compiles]
    for sp in gathers:
        # child of the stage it fell in
        assert by_sid[sp.parent].name == "checkpoint.pull"
        assert sp.args["within"] == "checkpoint_stage.pull"
    new = [
        e for e in EVENT_LOG.events(limit=100_000)
        if e["seq"] not in seen and e["kind"] == "compile"
    ]
    named = [e for e in new if "gather" in e["fun_name"]]
    assert len(named) == len(gathers)
    for e in named:
        assert e["epoch"] == tr.epoch
        assert e["stage"] == "checkpoint_stage.pull"
        assert e["ms"] > 0
    # one entry per executable: tracing and lowering have none
    assert len(new) == sum(
        sp.args["event"] == "backend_compile_duration" for sp in compiles
    )
    # the next epoch of the old size compiles nothing and says so
    assert "compile" not in q8.epoch().stages_ms


# -- (d) the same spans on the profiler's clock --------------------------


def test_profiler_session_holds_rw_events_of_one_whole_epoch(q8, tmp_path):
    import jax
    from jax.profiler import ProfileData

    q8.epoch()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        q8.epoch()
        tr = q8.epoch()
    finally:
        jax.profiler.stop_trace()
    (pb,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    # one line of the host plane per OS thread (all named alike)
    by_thread = {}
    for p, plane in enumerate(ProfileData.from_file(pb).planes):
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("rw/"):
                    by_thread.setdefault((p, i), []).append(
                        (ev.name, {k: v for k, v in ev.stats})
                    )
    whole = {
        thread: {n for n, st in evs if st.get("epoch") == tr.epoch}
        for thread, evs in by_thread.items()
    }
    barrier = [t for t, names in whole.items() if "rw/barrier" in names]
    assert len(barrier) == 1
    assert {"rw/barrier.fragment", "rw/dispatch.drain", "rw/dispatch.flush",
            "rw/checkpoint.stage", "rw/checkpoint.pull",
            "rw/checkpoint.dictionary", "rw/barrier.publish",
            "rw/barrier.bookkeeping"} <= whole[barrier[0]]
    actors = [t for t, names in whole.items() if "rw/actor.barrier" in names]
    assert len(actors) == 3  # left_src, right_src, join: each its thread
    assert all("rw/actor.fence" in whole[t] for t in actors)
    # what a span waits for is an argument of its event (a wait that
    # began before its barrier has no epoch yet: every event counts)
    waits = {}
    for evs in by_thread.values():
        for name, st in evs:
            waits.setdefault(name, set()).add(st.get("wait"))
    assert waits["rw/dispatch.flush"] == waits["rw/dispatch.drain"] == {"actor"}
    assert waits["rw/device.read"] == {"device"}
    assert waits["rw/actor.idle"] == {"queue"}
    assert waits["rw/dictionary.put"] == waits["rw/upload.put"] == {"io"}
    assert waits["rw/barrier"] == waits["rw/actor.barrier"] == {None}
    workers = [t for t, n in whole.items() if "rw/checkpoint.commit" in n]
    assert len(workers) == 1 and workers[0] not in actors + barrier
    assert {"rw/checkpoint.upload", "rw/checkpoint.manifest"} <= whole[
        workers[0]
    ]
    # spans opened before their barrier say which epoch they follow
    pushes = [st for n, st in by_thread[barrier[0]] if n == "rw/push"]
    assert pushes and all("epoch" in st or "after" in st for st in pushes)
    chunk_threads = [
        t for t, evs in by_thread.items()
        if any(n == "rw/actor.chunk" for n, _ in evs)
    ]
    assert set(chunk_threads) == set(actors)


def test_no_profiler_session_no_annotation(q8, monkeypatch):
    made = []

    class Counting(trace.TraceAnnotation):
        def __init__(self, *a, **kw):
            made.append(a)
            super().__init__(*a, **kw)

    monkeypatch.setattr(trace, "TraceAnnotation", Counting)
    assert not trace.TraceAnnotation.is_enabled()
    TRACER.clear()
    q8.epoch()
    assert len(TRACER.spans()) > 30  # the ring is always on
    assert made == []  # with no session an annotation is a flag test


# -- the primitive itself -------------------------------------------------


def test_live_stacks_are_per_thread_and_snapshotable():
    inside, go = threading.Event(), threading.Event()

    def work():
        with trace.span("unit.outer", k=1):
            with trace.span("unit.inner"):
                inside.set()
                go.wait(10)

    t = threading.Thread(target=work, name="span-unit")
    t.start()
    assert inside.wait(10)
    try:
        mine = [
            v for k, v in trace.active_spans().items()
            if k.startswith("span-unit(")
        ]
        assert [fr["span"] for fr in mine[0]] == ["unit.outer", "unit.inner"]
        assert mine[0][0]["args"] == {"k": 1}
    finally:
        go.set()
        t.join()
    assert not any(
        k.startswith("span-unit(") for k in trace.active_spans()
    )


def test_stage_goes_to_the_bound_sink_and_epoch_to_waiting_spans():
    from risingwave_tpu.epoch_trace import EpochTrace, StageSums

    TRACER.clear()
    sums = StageSums()
    with trace.bind(sums):
        with trace.span("unit.early", stage="unit_stage"):
            pass
        with trace.span("unit.early", stage="unit_stage", fragment="f"):
            pass
    early = [sp for sp in TRACER.spans() if sp.name == "unit.early"]
    assert [sp.epoch for sp in early] == [None, None]  # epoch still open
    assert set(sums.take()) == {("unit_stage", "-"), ("unit_stage", "f")}
    tr = EpochTrace(epoch=77, seq=1, checkpoint=False)
    trace.close_epoch(77)
    assert [sp.epoch for sp in early] == [77, 77]
    with trace.bind(tr):
        with trace.span("unit.late", stage="unit_stage.child", fragment="f"):
            pass
        trace.add_stage("unit_stage", 1.5)
    (late,) = [sp for sp in TRACER.spans() if sp.name == "unit.late"]
    assert late.epoch == 77
    assert tr.stages_ms["unit_stage"] == 1.5
    assert tr.stages_ms["unit_stage.child"] >= 0.0
    assert tr.fragment_ms == {}  # a child stage is inside its parent's wall
    tr.declare("unit_stage", "never_ran")
    assert tr.stages_ms["never_ran"] == 0.0 and tr.stages_ms["unit_stage"] == 1.5
    doc = json.loads(TRACER.chrome_trace())
    mine = [e for e in doc["traceEvents"] if e.get("name") == "unit.late"]
    assert mine[0]["args"]["epoch"] == 77 and "sid" in mine[0]["args"]
