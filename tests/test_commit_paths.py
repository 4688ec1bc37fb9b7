"""Unified commit path + thread-safety + sink durability (VERDICT r2 #3,
ADVICE r2). Reference contracts:
- one commit implementation for sync and async lanes
  (src/storage/src/hummock/event_handler/uploader.rs:548,
  src/meta/src/hummock/manager/commit_epoch.rs:93);
- compaction off the commit path (compactor_runner.rs:62);
- sink commits never ahead of durability (executor/sink.rs:40).
"""

import threading

import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.connectors.sink import BlackholeSink, SinkExecutor
from risingwave_tpu.executors.materialize import MaterializeExecutor
from risingwave_tpu.runtime.pipeline import Pipeline
from risingwave_tpu.runtime.runtime import StreamingRuntime
from risingwave_tpu.storage.object_store import MemObjectStore
from risingwave_tpu.storage.state_table import CheckpointManager, StateDelta


def _chunk(ids, vals, cap=8):
    return StreamChunk.from_numpy(
        {"id": np.asarray(ids, np.int64), "v": np.asarray(vals, np.int64)},
        cap,
    )


def _mk_runtime(**kw):
    store = MemObjectStore()
    rt = StreamingRuntime(store, checkpoint_frequency=1, **kw)
    mv = MaterializeExecutor(pk=["id"], columns=["v"], table_id="mv1")
    sink = BlackholeSink()
    se = SinkExecutor(sink, pk=["id"], columns=["v"])
    rt.register("f", Pipeline([mv, se]))
    return rt, store, mv, sink, se


def test_async_and_sync_commits_share_validation():
    """The async lane must enforce the same duplicate-table_id check as
    the sync path (it previously skipped it)."""
    store = MemObjectStore()
    rt = StreamingRuntime(store, checkpoint_frequency=1)
    a = MaterializeExecutor(pk=["id"], columns=["v"], table_id="dup")
    b = MaterializeExecutor(pk=["id"], columns=["v"], table_id="dup")
    rt.register("f", Pipeline([a]))
    rt.register("g", Pipeline([b]))
    for ex in (a, b):
        ex.apply(_chunk([1], [10]))
    with pytest.raises(ValueError, match="duplicate table_id"):
        rt.barrier()


def test_async_commit_epoch_monotonicity_enforced():
    store = MemObjectStore()
    mgr = CheckpointManager(store)
    d = StateDelta(
        "t", {"k": np.array([1])}, {"v": np.array([2])},
        np.array([False]), ("k",),
    )
    mgr.commit_staged(100, [d])
    with pytest.raises(ValueError, match="<= committed"):
        mgr.commit_staged(100, [d])
    with pytest.raises(ValueError, match="<= committed"):
        mgr.commit_staged(50, [d])


def test_concurrent_barriers_flush_compaction_stress():
    """Barriers racing FLUSH racing compaction: drive many epochs with a
    tiny compact_at so compaction constantly rewrites runs while the
    async lane commits; every row must survive recovery."""
    rt, store, mv, sink, se = _mk_runtime(compact_at=2)
    stop = threading.Event()
    flush_err = []

    def flusher():
        while not stop.is_set():
            try:
                rt.wait_checkpoints()
            except Exception as e:  # pragma: no cover
                flush_err.append(e)
                return

    t = threading.Thread(target=flusher)
    t.start()
    n = 30
    for i in range(n):
        mv.apply(_chunk([i, i + 1000], [i, -i]))
        se.apply(_chunk([i], [i]))
        rt.barrier()
    rt.wait_checkpoints()
    stop.set()
    t.join()
    assert not flush_err
    rt.wait_compaction()

    # recover into a twin and compare the full MV
    mv2 = MaterializeExecutor(pk=["id"], columns=["v"], table_id="mv1")
    mgr2 = CheckpointManager(store)
    mgr2.recover([mv2])
    assert mv2.snapshot() == mv.snapshot()
    assert len(mv2.snapshot()) == 2 * n


def test_sink_commit_deferred_until_durable():
    """With a checkpoint store, sink delivery happens only after the
    epoch's manifest persisted — on_barrier alone delivers nothing."""
    rt, store, mv, sink, se = _mk_runtime(async_checkpoint=False)
    assert se.deliver_on_durable
    se.apply(_chunk([1], [10]))
    rt.barrier()  # sync path: durable inside barrier -> delivered after
    assert sink.rows_written == 1
    assert sink.commits == 1


def test_sink_deferred_async_delivers_after_wait():
    rt, store, mv, sink, se = _mk_runtime()
    se.apply(_chunk([1], [10]))
    se.apply(_chunk([2], [20]))
    rt.barrier()
    rt.wait_checkpoints()
    assert sink.rows_written == 2
    assert sink.commits >= 1


def test_sink_standalone_immediate():
    """No runtime/store: old behavior — write at barrier, commit at
    checkpoint barrier (documented at-least-once standalone mode)."""
    sink = BlackholeSink()
    se = SinkExecutor(sink, pk=["id"], columns=["v"])
    p = Pipeline([se])
    se.apply(_chunk([1], [10]))
    p.barrier()
    assert sink.rows_written == 1
    assert sink.commits == 1


def test_materialize_pending_bounded_without_checkpoint():
    """Native-path _pending must not grow with stream length when no
    checkpoint manager drains it (barrier compacts to net effect)."""
    mv = MaterializeExecutor(pk=["id"], columns=["v"], table_id="m")
    p = Pipeline([mv])
    for i in range(50):
        mv.apply(_chunk([1, 2], [i, i]))
        p.barrier()
    assert mv._backend == "native"
    assert len(mv._pending) <= 2  # one net batch + at most one new chunk
    total_rows = sum(len(k) for k, _, _ in mv._pending)
    assert total_rows <= 4


def test_compaction_cas_preserves_racing_commit():
    """compact_once must not drop SSTs committed while it merged."""
    store = MemObjectStore()
    mgr = CheckpointManager(store, compact_at=2)

    def delta(i):
        return StateDelta(
            "t",
            {"k": np.array([i], np.int64)},
            {"v": np.array([i * 10], np.int64)},
            np.array([False]),
            ("k",),
        )

    mgr.commit_staged(1 << 16, [delta(1)])
    mgr.commit_staged(2 << 16, [delta(2)])
    # simulate a commit landing between compaction's read and its swap:
    orig_read = mgr.store.read
    raced = {"done": False}

    def racing_read(path):
        blob = orig_read(path)
        if not raced["done"]:
            raced["done"] = True
            mgr.commit_staged(3 << 16, [delta(3)])
        return blob

    mgr.store.read = racing_read
    assert mgr.compact_once("t", 2 << 16)
    mgr.store.read = orig_read
    keys, vals = mgr.read_table("t")
    assert sorted(keys["k"].tolist()) == [1, 2, 3]


def test_native_library_survives_concurrent_first_builds(tmp_path):
    """Several processes that find no built library (a fresh checkout
    under ``pytest -n 6``) each build it and all load it: none falls
    back to the Python map. They used to share one temporary file, and
    the loser of that race kept the Python backend for its whole life,
    which is what made the test above unsteady in a worker that lost."""
    import subprocess
    import sys

    code = (
        "import sys; from risingwave_tpu import native; "
        "native._BUILD_DIR = sys.argv[1]; "
        "print(native.get_lib() is not None)"
    )
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", code, str(tmp_path / "build")],
            stdout=subprocess.PIPE, text=True,
        )
        for _ in range(5)
    ]
    assert [p.communicate(timeout=300)[0].strip() for p in procs] == ["True"] * 5
    built = [f for f in (tmp_path / "build").iterdir()]
    assert len(built) == 1 and built[0].suffix == ".so"
