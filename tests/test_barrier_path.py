"""A barrier's critical path out of the span ring (trace.barrier_path):
what a span says it waits for, the ``device.read`` span around every
blocking read, the walk along the slowest actor, and the path a slow
barrier writes of itself. Structure, kinds and sums; never a rate."""

import json
import logging
import os
import subprocess
import sys
import time
from types import SimpleNamespace

import jax.numpy as jnp
import pytest

from risingwave_tpu import blackbox, trace
from risingwave_tpu.event_log import EVENT_LOG
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.ops.hash_table import read_scalars
from risingwave_tpu.trace import TRACER

from test_span_tree import Q8


@pytest.fixture(scope="module")
def q8(tmp_path_factory):
    system = Q8(tmp_path_factory.mktemp("barrier_path_state"))
    system.epoch()  # the first barrier's compiles
    yield system
    system.close()


def _sp(sid, name, tid, t0, t1, parent=None, wait=None, **args):
    return SimpleNamespace(
        sid=sid, name=name, tid=tid, t0=t0, dur=t1 - t0, parent=parent,
        wait=wait, args=args, epoch=7,
    )


def _root(epoch):
    (sp,) = [
        sp for sp in TRACER.spans()
        if sp.name == "barrier" and sp.epoch == epoch
    ]
    return sp


# -- (a) a span says what it waits for -----------------------------------


def test_a_span_keeps_its_wait_and_an_unknown_one_is_refused():
    TRACER.clear()
    with trace.span("unit.waits", wait="io"):
        (frame,) = [
            stack for t, stack in trace.active_spans().items()
            if any(fr["span"] == "unit.waits" for fr in stack)
        ]
        assert frame[-1]["wait"] == "io"
    (sp,) = TRACER.spans()
    assert sp.wait == "io" and sp.as_event()[4]["wait"] == "io"
    with trace.span("unit.works") as sp:
        pass
    assert sp.wait is None and "wait" not in sp.as_event()[4]
    with pytest.raises(ValueError, match="unknown wait"):
        trace.span("unit.bad", wait="the moon")
    assert set(trace.WAITS) == {"device", "actor", "permit", "queue", "io"}


def test_finish_scalars_leaves_a_device_read_under_the_callers_span():
    TRACER.clear()
    with trace.span("unit.caller") as up:
        got = read_scalars(jnp.int32(3), jnp.int32(4), what="unit.pair")
    assert got == [3, 4]
    (read,) = [sp for sp in TRACER.spans() if sp.name == "device.read"]
    assert read.wait == "device" and read.parent == up.sid
    assert read.args == {"what": "unit.pair", "lanes": 2}
    # no stage is open: the outermost span's name gives the root
    assert read.stage == "unit.device_wait"
    with trace.span("unit.outer", stage="ingest"):
        with trace.span("unit.inner", stage="actor.mv_apply"):
            read_scalars(jnp.int32(1))
    # the outermost stage: the wait lies in the ingest's time
    assert TRACER.spans()[-3].stage == "ingest.device_wait"
    read_scalars(jnp.int32(1))  # under no span: a span, no stage
    assert TRACER.spans()[-1].name == "device.read"
    assert TRACER.spans()[-1].stage is None


def test_the_marked_waits_of_one_epoch(q8):
    TRACER.clear()
    tr = q8.epoch()
    waits = {}
    for sp in TRACER.spans():
        if sp.epoch == tr.epoch:
            waits.setdefault(sp.name, set()).add(sp.wait)
    assert waits["dispatch.drain"] == waits["dispatch.flush"] == {"actor"}
    assert waits["actor.idle"] == waits["checkpoint.queue_wait"] == {"queue"}
    assert waits["dictionary.put"] == waits["upload.put"] == {"io"}
    assert waits["checkpoint.manifest"] == {"io"}
    assert waits["device.read"] == {"device"}
    for name in ("barrier", "barrier.fragment", "checkpoint.stage",
                 "checkpoint.marks", "checkpoint.pull", "actor.chunk",
                 "actor.barrier", "actor.fence", "push"):
        assert waits[name] == {None}, name
    # every blocking read the plan makes says what it read
    by_sid = {sp.sid: sp for sp in TRACER.spans()}
    what = {}
    for sp in TRACER.spans():
        if sp.name == "device.read" and sp.epoch == tr.epoch:
            what.setdefault(sp.args["what"], set()).add(
                by_sid[sp.parent].name
            )
    assert what["checkpoint.marks"] == {"checkpoint.marks"}
    assert what["pull_rows"] == {"checkpoint.pull"}
    assert what["edge_rows"] == {"actor.fence"}
    assert what["scalars"] == {"executor.device_step"}
    assert {"chunk.valid", "chunk.lanes", "chunk.ops"} <= set(what)
    assert what["chunk.lanes"] == {"mv.to_numpy"}


def test_the_new_stage_keys_are_in_the_epochs_trace(q8):
    tr = q8.epoch()
    st = tr.stages_ms
    # the table fragments' copies under a push wait for the device
    assert 0.0 < st["ingest.device_wait"] <= st["ingest"]
    assert 0.0 < st["checkpoint_stage.marks"]
    assert 0.0 < st["checkpoint_stage.device_wait"]
    # marks lies beside the pulls, not over them
    assert (
        st["checkpoint_stage.marks"] + st["checkpoint_stage.pull"]
        + st["checkpoint_stage.dictionary"]
        <= st["checkpoint_stage"] + 1e-6
    )
    assert st["checkpoint_stage.device_wait"] <= (
        st["checkpoint_stage.marks"] + st["checkpoint_stage.pull"] + 1e-6
    )
    assert st["actor.device_wait"] > 0.0  # summed over the actors
    assert "device_step" not in st


def test_checkpoint_marks_says_what_it_classified_and_what_it_read(q8):
    """The marks are classified where they live: the span carries the
    lanes its tables' mark lanes hold, the slots classified changed and
    the bytes its own ``device.read``s copied down (the pulls' reads are
    ``checkpoint.pull``'s) - a count and a byte a changed slot, not
    whole lanes."""
    read_bytes = REGISTRY.counter("checkpoint_marks_read_bytes_total")
    before = read_bytes.total()
    TRACER.clear()
    tr = q8.epoch()
    spans = [sp for sp in TRACER.spans() if sp.epoch == tr.epoch]
    marks = [sp for sp in spans if sp.name == "checkpoint.marks"]
    assert len(marks) >= 3  # the join's two sides, the view, the tables
    total = own_ms = 0.0
    for sp in marks:
        assert {"table_id", "capacity", "selected", "read_bytes"} <= set(
            sp.args
        )
        reads = [
            k for k in spans if k.name == "device.read" and k.parent == sp.sid
        ]
        assert {k.args["what"] for k in reads} <= {"checkpoint.marks"}
        assert sum(k.args["bytes"] for k in reads) == sp.args["read_bytes"]
        total += sp.args["read_bytes"]
        pulls = [
            k for k in spans
            if k.name == "checkpoint.pull" and k.parent == sp.sid
        ]
        own_ms += (sp.dur - sum(k.dur for k in pulls)) * 1e3
    assert read_bytes.total() - before == total
    # 50 persons and 50 auctions an epoch in tables of 4,096 lanes and
    # more: each table's marks cost its count and a padded byte a row
    slotted = [sp for sp in marks if sp.args["capacity"]]
    assert slotted and all(
        0 < sp.args["selected"] <= 100
        and sp.args["read_bytes"] < sp.args["capacity"]
        for sp in slotted
    )
    assert tr.stages_ms["checkpoint_stage.marks"] == pytest.approx(own_ms)


def test_checkpoint_pull_says_in_how_many_arrays_its_rows_left(q8):
    """A table's rows leave the device in one array a piece and (dtype,
    row shape) of its lanes: q8's bucket sides keep key lanes and
    (capacity, fanout) lanes of three types, four arrays in all, its
    dedups and tables fewer; each pull waits in one ``device.read``."""
    copies = REGISTRY.counter("checkpoint_pull_copies_total")
    before = copies.total()
    TRACER.clear()
    tr = q8.epoch()
    spans = [sp for sp in TRACER.spans() if sp.epoch == tr.epoch]
    pulls = [sp for sp in spans if sp.name == "checkpoint.pull"]
    assert len(pulls) >= 3
    for sp in pulls:
        # 50 persons and 50 auctions an epoch: one piece of 256 lanes
        assert sp.args["padded_rows"] == 256
        assert 1 <= sp.args["copies"] <= 4
        reads = [
            k for k in spans if k.name == "device.read" and k.parent == sp.sid
        ]
        assert [k.args["what"] for k in reads] == ["pull_rows"]
    assert max(sp.args["copies"] for sp in pulls) == 4  # a bucket side
    assert copies.total() - before == sum(sp.args["copies"] for sp in pulls)


def test_a_traced_rehearsal_of_q4_catchup_reports_marks_bytes_per_event():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    env.pop("XLA_FLAGS", None)
    run = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"),
         "--workload", "nexmark_q4.catchup", "--seed", "3900000039",
         "--seconds", "4", "--trace", "1", "--dry-run-cpu"],
        capture_output=True, text=True, cwd=root, env=env, timeout=600,
    )
    assert run.returncode == 0, run.stderr[-2000:]
    doc = json.loads(run.stdout.strip().splitlines()[-1])
    assert doc["correct"] is True and doc["device"]["platform"] == "cpu"
    got = doc["metrics"]["checkpoint.marks_bytes_per_event.catchup"]
    # two aggregates' counts and padded tombstone bytes an epoch of
    # 2,048 events: under a byte an event where five lanes of the
    # session's capacity were 1,280 (a count made by the program; no
    # device number comes from a dry run)
    assert got["unit"] == "bytes/event" and 0.0 < got["value"] < 64.0


# -- (b) the ring reduced to a barrier's critical path -------------------


def test_three_barriers_sum_to_their_wall_and_cross_the_join_actor(q8):
    """What holds of a live barrier's path whatever the host's clock and
    scheduler did: the sums, the kinds, the order, the actor crossed and
    the rows of the barrier's OWN thread. Which of an actor's spans fall
    inside the barrier thread's waits is the scheduler's doing (on a
    loaded host the join actor has collected before ``dispatch.flush``
    begins to wait, and its ``actor.barrier`` row is then not on the
    path: 4 runs of 18 beside ten busy loops): those spans are held to
    be in the epoch's ring, with their kinds, and the path over such a
    ring is reckoned with hand-written times in the test below."""
    TRACER.clear()
    for tr in [q8.epoch(pushes=2) for _ in range(3)]:
        path = trace.barrier_path(tr.epoch)
        root = _root(tr.epoch)
        assert path["wall_ms"] == pytest.approx(root.dur * 1e3)
        assert set(path["by_kind"]) == set(trace.KINDS)
        total = sum(path["by_kind"].values())
        assert abs(total - path["wall_ms"]) <= 0.02 * path["wall_ms"]
        assert sum(ms for _n, _k, ms in path["by_span"]) == pytest.approx(
            total
        )
        by_kind = dict.fromkeys(trace.KINDS, 0.0)
        for _n, kind, ms in path["by_span"]:
            by_kind[kind] += ms
        assert by_kind == pytest.approx(path["by_kind"])
        assert "q8/join#0" in path["actors"]
        rows = {(n, k) for n, k, _ms in path["by_span"]}
        # the barrier's own thread: its work, its read, its put
        assert ("barrier", "host") in rows
        assert ("device.read[checkpoint.marks]", "device_wait") in rows
        assert ("dictionary.put", "io") in rows  # the dictionary's put
        assert not any(k == "actor" for _n, k in rows)
        sizes = [ms for _n, _k, ms in path["by_span"]]
        assert sizes == sorted(sizes, reverse=True)
        # the join actor's spans the path may cross, in the epoch's ring
        mine = [sp for sp in TRACER.spans() if sp.epoch == tr.epoch]
        (fence,) = [
            sp for sp in mine if sp.name == "actor.barrier"
            and sp.args.get("actor") == "join#0"
        ]
        assert fence.wait is None and fence.args["graph"] == "q8"
        reads = {
            sp.args["what"] for sp in mine
            if sp.name == "device.read" and sp.tid == fence.tid
        }
        assert "edge_rows" in reads
        assert all(
            sp.wait == "device" for sp in mine if sp.name == "device.read"
        )
    # an epoch the ring never held, and one it has let go of
    assert trace.barrier_path(tr.epoch + 12345) is None
    TRACER.clear()
    assert trace.barrier_path(tr.epoch) is None


def test_a_q8_shaped_barrier_by_hand_has_the_actors_rows_on_its_path():
    """The live test's barrier with hand-written times: the barrier's
    thread drains, waits for the join actor's collect, stages and puts;
    the join actor steps a chunk, then fences and reads its edge rows
    while the barrier's thread waits for it. Every kind that such a
    barrier has is on the path, and next to nothing is unattributed."""
    src = dict(graph="q8", actor="left_src#0", upstream=())
    join = dict(graph="q8", actor="join#0", upstream=("left_src#0",))
    ring = [
        _sp(1, "barrier", 1, 0.0, 10.0),
        _sp(2, "barrier.fragment", 1, 0.1, 6.0, parent=1, fragment="q8"),
        _sp(3, "dispatch.drain", 1, 0.2, 2.0, parent=2, wait="actor",
            fragment="q8"),
        _sp(4, "dispatch.flush", 1, 2.0, 5.8, parent=2, wait="actor",
            fragment="q8"),
        _sp(5, "checkpoint.stage", 1, 6.0, 9.0, parent=1),
        _sp(6, "device.read", 1, 6.5, 7.5, parent=5, wait="device",
            what="checkpoint.marks"),
        _sp(7, "dictionary.put", 1, 8.0, 8.6, parent=5, wait="io"),
        # the source forwards the barrier early
        _sp(10, "actor.barrier", 2, 0.3, 0.6, **src),
        # the join actor: a chunk, the barrier off its inputs at 1.9,
        # the fence with its read of the edges' rows
        _sp(20, "actor.chunk", 3, 0.4, 1.8),
        _sp(21, "actor.join_step", 3, 0.5, 1.7, parent=20),
        _sp(22, "actor.barrier", 3, 1.9, 5.5, **join),
        _sp(23, "actor.fence", 3, 2.0, 5.0, parent=22),
        _sp(24, "device.read", 3, 3.0, 4.5, parent=23, wait="device",
            what="edge_rows"),
    ]
    path = trace.barrier_path(7, ring)
    assert path["wall_ms"] == pytest.approx(10_000.0)
    assert sum(path["by_kind"].values()) == pytest.approx(10_000.0)
    assert path["actors"] == ["q8/join#0"]
    rows = {(n, k): ms for n, k, ms in path["by_span"]}
    # the actor's work stands where the barrier's thread only waited
    assert rows[("actor.barrier", "host")] == pytest.approx(500)
    assert rows[("actor.fence", "host")] == pytest.approx(1500)
    assert rows[("device.read[edge_rows]", "device_wait")] == pytest.approx(
        1500
    )
    assert rows[("actor.join_step", "host")] == pytest.approx(1200)
    assert rows[("device.read[checkpoint.marks]", "device_wait")] == (
        pytest.approx(1000)
    )
    assert rows[("dictionary.put", "io")] == pytest.approx(600)
    assert not any(k == "actor" for _n, k in rows)
    assert path["by_kind"]["host"] > 0.0
    assert path["by_kind"]["device_wait"] == pytest.approx(2500)
    assert path["by_kind"]["io"] == pytest.approx(600)
    assert path["by_kind"]["unattributed"] < 0.5 * path["wall_ms"]
    sizes = [ms for _n, _k, ms in path["by_span"]]
    assert sizes == sorted(sizes, reverse=True)


@pytest.mark.parametrize(
    "span_held, began, ended, expect",
    [
        (True, True, True, True),
        # the session began after the caller's annotation, before the span
        (True, False, True, False),
        # it ended once the span had closed, before the caller's did
        (True, True, False, False),
        # a span no session held stays so, whatever the call's ends saw
        (False, True, True, False),
    ],
)
def test_whole_call_holds_the_roots_flag_to_the_calls_ends(
    monkeypatch, span_held, began, ended, expect
):
    root = SimpleNamespace(traced=span_held)
    monkeypatch.setattr(trace, "_profiling", lambda: ended)
    trace.whole_call(root, began)
    assert root.traced is expect
    trace.whole_call(None, began)  # a barrier that opened no root span


def test_a_session_that_begins_inside_the_call_leaves_the_root_untraced(
    q8, monkeypatch, tmp_path
):
    """A reader of the ring counts the traced ``barrier`` roots against
    the annotations its caller wrapped around ``Runtime.barrier()``: one
    session over whole calls marks their roots, and a call whose first
    statement saw no session does not, though its span has its event."""
    import jax
    import risingwave_tpu.runtime.runtime as runtime_mod

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        whole = q8.epoch()
        monkeypatch.setattr(runtime_mod, "profiling", lambda: False)
        late = q8.epoch()
        monkeypatch.undo()
    finally:
        jax.profiler.stop_trace()
    after = q8.epoch()
    assert _root(whole.epoch).traced is True
    assert _root(late.epoch).traced is False
    assert _root(after.epoch).traced is False
    assert q8.rt._barrier_root is None


def test_the_path_follows_the_later_actor_and_clips_at_the_interval():
    a = dict(graph="g", actor="a#0", upstream=())
    b = dict(graph="g", actor="b#0", upstream=("a#0",))
    ring = [
        # the barrier's thread
        _sp(1, "barrier", 1, 0.0, 10.0),
        _sp(2, "barrier.fragment", 1, 0.0, 9.0, parent=1),
        _sp(3, "dispatch.drain", 1, 0.0, 3.0, parent=2, wait="actor",
            fragment="g"),
        _sp(4, "dispatch.flush", 1, 3.0, 8.5, parent=2, wait="actor",
            fragment="g"),
        # actor a: a chunk, then its barrier; done long before b
        _sp(10, "actor.chunk", 2, 0.2, 1.0),
        _sp(11, "actor.barrier", 2, 1.0, 2.8, **a),
        # actor b: waits for a (from before the barrier began), then a
        # barrier that reads the device for two of its five seconds
        _sp(20, "actor.idle", 3, -1.0, 2.9, wait="queue"),
        _sp(21, "actor.barrier", 3, 3.0, 8.0, **b),
        _sp(22, "device.read", 3, 5.0, 7.0, parent=21, wait="device",
            what="unit"),
        # another graph's actor, slower than both: not this wait's
        _sp(30, "actor.barrier", 4, 0.0, 9.9, graph="h", actor="z#0",
            upstream=()),
    ]
    path = trace.barrier_path(7, ring)
    assert path["wall_ms"] == pytest.approx(10_000.0)
    assert sum(path["by_kind"].values()) == pytest.approx(10_000.0)
    assert path["actors"] == ["g/b#0", "g/a#0"]
    rows = {(n, k): ms for n, k, ms in path["by_span"]}
    # b's barrier, less the read inside it; a's barrier and chunk where
    # b only waited for them; the wait clipped at the barrier's start
    assert rows[("actor.barrier", "host")] == pytest.approx(3000 + 1800)
    assert rows[("device.read[unit]", "device_wait")] == pytest.approx(2000)
    assert rows[("actor.chunk", "host")] == pytest.approx(800)
    assert rows[("actor.idle", "queue")] == pytest.approx(100)
    assert rows[("(no span)", "unattributed")] == pytest.approx(200 + 100)
    # from the last actor's release on it is the waiter's own time
    assert rows[("dispatch.flush", "host")] == pytest.approx(500)
    assert rows[("barrier.fragment", "host")] == pytest.approx(500)
    assert rows[("barrier", "host")] == pytest.approx(1000)
    assert path["by_kind"]["device_wait"] == pytest.approx(2000)
    assert path["by_kind"]["queue"] == pytest.approx(100)
    assert path["by_kind"]["host"] == pytest.approx(7600)
    # a source's wait is the queue's: nothing upstream to follow
    ring[6] = _sp(20, "actor.idle", 3, -1.0, 2.9, wait="queue")
    ring[7] = _sp(21, "actor.barrier", 3, 3.0, 8.0,
                  **dict(b, upstream=()))
    alone = trace.barrier_path(7, ring)
    assert alone["actors"] == ["g/b#0"]
    assert alone["by_kind"]["queue"] == pytest.approx(2900)
    assert sum(alone["by_kind"].values()) == pytest.approx(10_000.0)


def test_rw_barrier_latency_reads_the_paths_device_wait(q8):
    tr = q8.epoch()
    sql = "SELECT epoch, dispatch_ms, device_step_ms FROM rw_barrier_latency"
    cols, _tag = q8.session.execute(sql)
    (i,) = [i for i, e in enumerate(cols["epoch"]) if int(e) == tr.epoch]
    path = trace.barrier_path(tr.epoch)
    assert float(cols["device_step_ms"][i]) == pytest.approx(
        path["by_kind"]["device_wait"], abs=2e-3
    )
    assert float(cols["device_step_ms"][i]) > 0.0
    assert abs(float(cols["dispatch_ms"][i]) - tr.stages_ms["dispatch"]) < 2e-3
    # once the ring has let go of the epoch: NULL, not a number
    TRACER.clear()
    cols, _tag = q8.session.execute(sql)
    (i,) = [i for i, e in enumerate(cols["epoch"]) if int(e) == tr.epoch]
    assert cols["device_step_ms"][i] is None


# -- a slow barrier writes its own path -----------------------------------


def test_a_held_barrier_leaves_a_slow_barrier_event_naming_the_span(
    q8, monkeypatch, caplog
):
    blackbox.RECORDER.ring.clear()
    for _ in range(3):
        q8.epoch()
    (actor,) = [
        a for a in q8.rt.fragments["q8"].graph.actors
        if a.actor_name == "join#0"
    ]
    ex = actor.join_exec
    finish = ex.finish_barrier

    def held():
        time.sleep(1.25)
        finish()

    seen = {e["seq"] for e in EVENT_LOG.events(limit=100_000)}
    monkeypatch.setattr(ex, "finish_barrier", held)
    with caplog.at_level(logging.WARNING, logger="risingwave_tpu"):
        tr = q8.epoch()
        monkeypatch.setattr(ex, "finish_barrier", finish)
        q8.epoch()  # an ordinary barrier: nothing
    (ev,) = [
        e for e in EVENT_LOG.events(limit=100_000)
        if e["seq"] not in seen and e["kind"] == "slow_barrier"
    ]
    assert ev["epoch"] == tr.epoch and ev["wall_ms"] > 1250
    name, kind, ms = ev["by_span"][0]
    assert (name, kind) == ("actor.fence", "host") and ms >= 1250
    assert len(ev["by_span"]) <= 8
    assert sum(ev["by_kind"].values()) == pytest.approx(
        ev["wall_ms"], rel=0.02
    )
    assert "q8/join#0" in ev["actors"]
    # the other threads' open spans, not the barrier's own
    assert not any(t.startswith("MainThread(") for t in ev["threads"])
    (line,) = [
        r.getMessage() for r in caplog.records
        if r.getMessage().startswith("slow_barrier ")
    ]
    doc = json.loads(line[len("slow_barrier "):])
    assert doc["epoch"] == tr.epoch and doc["by_span"][0][0] == "actor.fence"
    # and in the barrier's flight record, for a reader of the segment
    (rec,) = [r for r in blackbox.RECORDER.ring if r["ep"] == tr.epoch]
    assert rec["slow"]["by_span"][0][0] == "actor.fence"
    assert tr.slow_path is not None
    assert q8.rt.last_epoch_trace.slow_path is None


def test_is_slow_is_one_comparison_under_a_second():
    rec = blackbox.FlightRecorder()
    assert not rec.is_slow(5000.0)  # nothing to stand out of
    rec.ring.extend({"wall": w} for w in (300.0, 350.0, 400.0, 9000.0))
    assert not rec.is_slow(999.0)
    assert not rec.is_slow(1100.0)  # over 1 s, not over 3 x 400
    assert rec.is_slow(1201.0)


def test_the_cold_merge_at_a_barrier_is_a_span_and_its_read_a_device_read():
    """An aggregate over a store that has evicted looks every group new
    since the last checkpoint up in the store at every barrier, after
    reading the candidates' lane off the device: both are named in the
    ring."""
    import numpy as np

    from risingwave_tpu.array.chunk import StreamChunk
    from risingwave_tpu.executors.hash_agg import HashAggExecutor
    from risingwave_tpu.ops.agg import AggCall
    from risingwave_tpu.storage.object_store import MemObjectStore
    from risingwave_tpu.storage.state_table import CheckpointManager

    agg = HashAggExecutor(
        group_keys=("k",),
        calls=(AggCall("count_star", None, "cnt"),),
        schema_dtypes={"k": np.dtype(np.int64)},
        capacity=1 << 10,
        out_cap=1 << 8,
        table_id="unit.cold",
    )
    asked = []

    def nothing_cold(keys):
        asked.append(len(keys["k0"]))
        return np.zeros(len(keys["k0"]), bool), {}

    agg.cold_reader = nothing_cold
    # the merge engages once a group has been evicted: three durable
    # groups, dropped from the table
    agg.apply(
        StreamChunk.from_numpy({"k": np.arange(100, 103, dtype=np.int64)}, 16)
    )
    agg.on_barrier(None)
    CheckpointManager(MemObjectStore()).commit_epoch(1 << 16, [agg])
    assert agg.evict_cold() == 3
    agg.apply(StreamChunk.from_numpy({"k": np.arange(7, dtype=np.int64)}, 16))
    TRACER.clear()
    with trace.span("actor.barrier"):
        agg.on_barrier(None)
    spans = TRACER.spans()
    by_sid = {sp.sid: sp for sp in spans}
    (merge,) = [sp for sp in spans if sp.name == "agg.merge_cold"]
    assert merge.args == {
        "table_id": "unit.cold", "barrier": 1, "ran": 1,
        "candidates": 7, "found": 0,
    }
    assert by_sid[merge.parent].name == "actor.barrier"
    kids = {
        (sp.name, sp.args.get("what"), sp.wait)
        for sp in spans if sp.parent == merge.sid
    }
    assert ("device.read", "agg.merge_cold", "device") in kids
    assert ("agg.cold_lookup", None, None) in kids
    assert asked == [7]
