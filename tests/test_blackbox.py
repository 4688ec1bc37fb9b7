"""Black-box flight recorder + device-wedge sentinel (blackbox.py).

The two failure modes the subsystem exists for, reproduced in the sim
tier: a SIGKILLed pipeline must leave a parseable, monotonic black box
on disk, and a wedged (fake) device must convert today's indefinite
hang into a structured ``DeviceWedged`` within the watchdog budget,
leaving a well-formed ``WEDGE_*.json`` forensic bundle."""

import json
import os
import signal
import subprocess
import sys
import threading
import time
from types import SimpleNamespace

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu import blackbox
from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.blackbox import (
    DeviceSentinel,
    DeviceWedged,
    FlightRecorder,
    classify_latency,
    read_segment,
)
from risingwave_tpu.executors.hash_agg import HashAggExecutor
from risingwave_tpu.executors.materialize import MaterializeExecutor
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.runtime.pipeline import Pipeline
from risingwave_tpu.runtime.runtime import StreamingRuntime
from risingwave_tpu.sim import BlockingKernelExecutor, WedgeableDevice
from risingwave_tpu.storage.object_store import MemObjectStore

pytestmark = pytest.mark.smoke

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _trace(i, ckpt=False, wall=10.0):
    return SimpleNamespace(
        epoch=i,
        seq=i,
        checkpoint=ckpt,
        wall_ms=wall,
        stages_ms={"ingest": 1.0, "dispatch": wall - 1.0},
        achieved_bw_frac=0.01,
        chunk_bytes=1 << 16,
        state_bytes=1 << 20,
    )


def _mk_pipeline(tid):
    agg = HashAggExecutor(
        group_keys=("k",),
        calls=(AggCall("sum", "v", "s"),),
        schema_dtypes={"k": jnp.int64, "v": jnp.int64},
        capacity=1 << 8,
        table_id=f"{tid}.agg",
    )
    mv = MaterializeExecutor(pk=("k",), columns=("s",), table_id=f"{tid}.mv")
    return Pipeline([agg, mv]), mv


def _chunk(rng, n=8):
    return StreamChunk.from_numpy(
        {
            "k": rng.integers(0, 4, n).astype(np.int64),
            "v": rng.integers(0, 40, n).astype(np.int64),
        },
        16,
    )


# ---------------------------------------------------------------------------
# flight recorder: ring + segment + reader
# ---------------------------------------------------------------------------


def test_recorder_segment_roundtrip_rotation_and_torn_tail(tmp_path):
    """Records round-trip through the JSONL segment; rotation keeps the
    readable window bounded-but-merged; a torn final line (SIGKILL
    mid-write) is tolerated, not fatal."""
    rec = FlightRecorder()
    rec.configure(
        dir=str(tmp_path), fsync_interval_s=0.0, segment_max_bytes=66_000
    )
    for i in range(600):
        rec.record_barrier(_trace(i + 1, ckpt=i % 4 == 0))
    path = rec.segment_path
    rec.close()
    assert os.path.exists(path + ".old")  # rotation happened
    # torn tail: a record cut mid-write by a SIGKILL
    with open(path, "a") as f:
        f.write('{"k":"b","ep":9999,"se')
    doc = read_segment(str(tmp_path))
    assert doc["torn_lines"] == 1
    assert doc["monotonic"]
    recs = doc["records"]
    # the merged (.old + current) window holds a contiguous tail
    assert len(recs) >= 100
    assert recs[-1]["epoch"] == 600
    epochs = [r["epoch"] for r in recs]
    assert epochs == sorted(epochs)
    assert recs[-1]["stages_ms"]["dispatch"] == 9.0
    assert doc["header"]["pid"] == os.getpid()


def test_recorder_unwritable_dir_degrades_to_ring_only(tmp_path):
    """An unwritable blackbox dir must not poison barriers: the
    recorder drops persistence (counted) and the ring keeps going."""
    rec = FlightRecorder()
    rec.configure(dir=str(tmp_path / "nope" / "\0bad"), fsync_interval_s=0)
    for i in range(5):
        rec.record_barrier(_trace(i + 1))
    assert len(rec.snapshot_tail(10)) == 5  # ring survived
    assert rec.dir is None  # persistence dropped, not retried per record


def test_runtime_barriers_feed_ring_and_pipeline_records_dedupe():
    """A runtime-driven barrier records exactly ONE ring record (the
    EpochTrace), not one per fragment pipeline — and epochs are
    monotonic across commits."""
    rt = StreamingRuntime(MemObjectStore(), async_checkpoint=False)
    p, _mv = _mk_pipeline("bb.dedupe")
    rt.register("mv", p)
    rng = np.random.default_rng(3)
    before = blackbox.RECORDER.snapshot()["records"]
    for _ in range(3):
        rt.push("mv", _chunk(rng))
        rt.barrier()
    after = blackbox.RECORDER.snapshot()["records"]
    assert after - before == 3  # one record per barrier, no doubles
    tail = blackbox.RECORDER.snapshot_tail(3)
    assert [r["seq"] for r in tail] == [1, 2, 3]
    assert all("dispatch" in r["st"] for r in tail), tail


def test_sigkill_mid_run_leaves_parseable_blackbox(tmp_path):
    """The r04/r05 failure mode: a pipeline murdered with SIGKILL mid-
    run still leaves a black box that replays a complete, monotonic
    epoch timeline up to the kill — via the in-process reader AND the
    ``python -m risingwave_tpu blackbox`` CLI (with a Perfetto trace)."""
    child = tmp_path / "child.py"
    child.write_text(
        "import os, sys\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        f"sys.path.insert(0, {str(REPO)!r})\n"
        "import jax; jax.config.update('jax_platforms', 'cpu')\n"
        "import numpy as np\n"
        "import jax.numpy as jnp\n"
        "from risingwave_tpu.array.chunk import StreamChunk\n"
        "from risingwave_tpu.executors.hash_agg import HashAggExecutor\n"
        "from risingwave_tpu.executors.materialize import "
        "MaterializeExecutor\n"
        "from risingwave_tpu.ops.agg import AggCall\n"
        "from risingwave_tpu.runtime.pipeline import Pipeline\n"
        "from risingwave_tpu.runtime.runtime import StreamingRuntime\n"
        "from risingwave_tpu.storage.object_store import MemObjectStore\n"
        "# RW_BLACKBOX_DIR env arms persistence on construction\n"
        "rt = StreamingRuntime(MemObjectStore(), async_checkpoint=False)\n"
        "agg = HashAggExecutor(group_keys=('k',),\n"
        "    calls=(AggCall('sum', 'v', 's'),),\n"
        "    schema_dtypes={'k': jnp.int64, 'v': jnp.int64},\n"
        "    capacity=1 << 8, table_id='kill.agg')\n"
        "mv = MaterializeExecutor(pk=('k',), columns=('s',),\n"
        "    table_id='kill.mv')\n"
        "rt.register('mv', Pipeline([agg, mv]))\n"
        "rng = np.random.default_rng(7)\n"
        "i = 0\n"
        "while True:\n"
        "    i += 1\n"
        "    c = StreamChunk.from_numpy(\n"
        "        {'k': rng.integers(0, 4, 8).astype(np.int64),\n"
        "         'v': rng.integers(0, 40, 8).astype(np.int64)}, 16)\n"
        "    rt.push('mv', c)\n"
        "    rt.barrier()\n"
        "    print(f'B {i}', flush=True)\n"
    )
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        RW_BLACKBOX_DIR=str(tmp_path),
        RW_BLACKBOX_FSYNC_S="0",
    )
    proc = subprocess.Popen(
        [sys.executable, str(child)],
        stdout=subprocess.PIPE,
        text=True,
        env=env,
    )
    barriers = 0
    try:
        deadline = time.time() + 120
        while barriers < 6 and time.time() < deadline:
            line = proc.stdout.readline()
            if line.startswith("B "):
                barriers = int(line.split()[1])
        assert barriers >= 6, f"child made no progress ({barriers})"
    finally:
        # SIGKILL mid-barrier-loop: safe — a CPU-pinned child that holds
        # no chip
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    doc = read_segment(str(tmp_path))
    recs = doc["records"]
    assert doc["monotonic"]
    # complete timeline up to the kill: every barrier the child
    # reported is in the box (the kill may race ONE in-flight record)
    assert len(recs) >= barriers - 1
    seqs = [r["seq"] for r in recs]
    assert seqs == list(range(1, len(recs) + 1))  # no holes
    epochs = [r["epoch"] for r in recs]
    assert epochs == sorted(epochs) and len(set(epochs)) == len(epochs)
    # reader CLI on the same dead segment (+ Perfetto trace)
    trace_out = tmp_path / "trace.json"
    cli = subprocess.run(
        [
            sys.executable,
            "-m",
            "risingwave_tpu",
            "blackbox",
            str(tmp_path),
            "--trace",
            str(trace_out),
        ],
        capture_output=True,
        text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        cwd=REPO,
    )
    assert cli.returncode == 0, cli.stderr
    assert f"{len(recs)} barrier(s)" in cli.stdout
    tr = json.loads(trace_out.read_text())
    assert any(e.get("ph") == "X" for e in tr["traceEvents"])
    assert any(e.get("cat") == "epoch" for e in tr["traceEvents"])


# ---------------------------------------------------------------------------
# device-wedge sentinel
# ---------------------------------------------------------------------------


def test_classify_latency_vocabulary():
    assert classify_latency(10, 100, 1000) == "ALIVE"
    assert classify_latency(200, 100, 1000) == "SLOW"
    assert classify_latency(1000, 100, 1000) == "WEDGED"
    assert classify_latency(None, 100, 1000) == "WEDGED"


def _sentinel_threads():
    return [
        t
        for t in threading.enumerate()
        if t.name.startswith("rw-sentinel") and t.is_alive()
    ]


def test_wedge_sentinel_fires_within_budget_with_forensic_bundle(tmp_path):
    """A wedged fake device flips the sentinel to WEDGED within a few
    deadlines, arms a structured DeviceWedged, and leaves a well-formed
    WEDGE_*.json (thread stacks, device forensics, recorder tail);
    unwedging heals back to ALIVE and disarms; stop() leaves no orphan
    sentinel threads."""
    dev = WedgeableDevice()
    sen = DeviceSentinel()
    sen.start(
        interval_s=0.05,
        slow_ms=50,
        deadline_s=0.2,
        heartbeat_fn=dev.heartbeat,
        dir=str(tmp_path),
    )
    try:
        deadline = time.time() + 5
        while sen.state != "ALIVE" and time.time() < deadline:
            time.sleep(0.02)
        assert sen.state == "ALIVE", sen.snapshot()
        dev.wedge()
        t0 = time.time()
        while sen.wedged_error() is None and time.time() - t0 < 5:
            time.sleep(0.02)
        detect_s = time.time() - t0
        w = sen.wedged_error()
        assert w is not None, sen.snapshot()
        assert isinstance(w, DeviceWedged)
        # within the watchdog budget: a handful of deadline windows,
        # nothing near the old 360s hang
        assert detect_s < 3.0, detect_s
        # the error ARMS before the bundle capture completes (fail-fast
        # first; forensics may touch the wedged device): poll briefly
        deadline = time.time() + 5
        while not w.bundle_path and time.time() < deadline:
            time.sleep(0.02)
        assert w.bundle_path
        bundle = json.load(open(w.bundle_path))
        assert bundle["state"] == "WEDGED"
        assert "threads" in bundle and "device" in bundle
        assert "recorder_tail" in bundle
        assert any("rw-sentinel" in k for k in bundle["threads"])
        # the heartbeat status file tracks the wedge (the surface
        # a watcher tails); written after
        # the capture, so poll briefly
        deadline = time.time() + 5
        st = {}
        while st.get("state") != "WEDGED" and time.time() < deadline:
            st = json.load(open(tmp_path / "SENTINEL_STATE.json"))
            time.sleep(0.02)
        assert st["state"] == "WEDGED" and st["wedges"] == 1
        assert REGISTRY.gauge("device_state").get() == 2.0
        # device_state transition landed in the meta event log
        from risingwave_tpu.event_log import EVENT_LOG

        trans = [
            e
            for e in EVENT_LOG.events(kind="device_state")
            if e.get("source") == "sentinel" and e.get("state") == "WEDGED"
        ]
        assert trans, "no device_state WEDGED event recorded"
        dev.unwedge()
        deadline = time.time() + 5
        while sen.state != "ALIVE" and time.time() < deadline:
            time.sleep(0.02)
        assert sen.state == "ALIVE"
        assert sen.wedged_error() is None  # healed => disarmed
    finally:
        dev.unwedge()
        sen.stop()
    deadline = time.time() + 5
    while _sentinel_threads() and time.time() < deadline:
        time.sleep(0.05)
    assert _sentinel_threads() == []  # no orphaned sentinel threads


def test_runtime_barrier_raises_device_wedged_and_recovery_clears(
    tmp_path,
):
    """The runtime contract: an armed wedge surfaces at the next
    barrier as DeviceWedged (not a hang); with auto_recover it is
    routed like an actor fault — recovered, capture window aborted,
    wedge cleared — and once the device heals the stream commits
    again."""
    dev = WedgeableDevice()
    saved_sentinel = blackbox.SENTINEL  # fresh instance: no config leak
    blackbox.SENTINEL = blackbox.DeviceSentinel()
    blackbox.SENTINEL.start(
        interval_s=0.05,
        slow_ms=50,
        deadline_s=0.2,
        heartbeat_fn=dev.heartbeat,
        dir=str(tmp_path),
    )
    rt = StreamingRuntime(
        MemObjectStore(), async_checkpoint=False, auto_recover=True
    )
    p, mv = _mk_pipeline("bb.wedge")
    rt.register("mv", p)
    rng = np.random.default_rng(11)
    try:
        rt.push("mv", _chunk(rng))
        rt.barrier()  # healthy commit
        dev.wedge()
        t0 = time.time()
        while blackbox.SENTINEL.wedged_error() is None and time.time() - t0 < 5:
            time.sleep(0.02)
        assert blackbox.SENTINEL.wedged_error() is not None
        # auto_recover: the wedge is treated like an actor fault — the
        # barrier recovers in place (returns {}) instead of crashing
        before = rt.auto_recoveries
        outs = rt.barrier()
        assert outs == {}
        assert rt.auto_recoveries == before + 1
        assert rt.last_recovery_mode == "full"
        assert isinstance(rt.last_failure, DeviceWedged)
        # recovery hygiene: no open capture window survived (the wedge
        # itself legitimately RE-ARMS while the device stays down —
        # the consecutive-recovery ladder owns that case)
        assert blackbox.SENTINEL.abort_capture() == 0
        # device heals -> the stream is live again
        dev.unwedge()
        deadline = time.time() + 5
        while blackbox.SENTINEL.state != "ALIVE" and time.time() < deadline:
            time.sleep(0.02)
        rt.push("mv", _chunk(rng))
        rt.barrier()
        assert rt.mgr.max_committed_epoch > 0
    finally:
        dev.unwedge()
        blackbox.SENTINEL.stop()
        blackbox.SENTINEL = saved_sentinel


def test_wait_barrier_converts_hang_into_device_wedged(tmp_path):
    """The q7 wedge shape: an actor stuck inside a blocking fake
    kernel would previously hang wait_barrier for the full timeout;
    with the sentinel wedged, wait_barrier raises the structured
    DeviceWedged within ~a slice — and dumps a stall artifact naming
    the stuck actors first."""
    from risingwave_tpu.runtime.graph import FragmentSpec, GraphRuntime

    dev = WedgeableDevice()
    blocker = BlockingKernelExecutor(dev, block_on="barrier")
    g = GraphRuntime(
        [
            FragmentSpec("src", lambda i: []),
            FragmentSpec("work", lambda i: [blocker], inputs=[("src", 0)]),
        ]
    ).start()
    saved_sentinel = blackbox.SENTINEL  # fresh instance: no config leak
    blackbox.SENTINEL = blackbox.DeviceSentinel()
    blackbox.SENTINEL.start(
        interval_s=0.05,
        slow_ms=50,
        deadline_s=0.2,
        heartbeat_fn=dev.heartbeat,
        dir=str(tmp_path),
    )
    stall_dir = os.environ.get("RW_STALL_DIR")
    os.environ["RW_STALL_DIR"] = str(tmp_path)
    try:
        dev.wedge()  # kernel AND heartbeats block: the real wedge shape
        t0 = time.time()
        while blackbox.SENTINEL.wedged_error() is None and time.time() - t0 < 5:
            time.sleep(0.02)
        assert blackbox.SENTINEL.wedged_error() is not None
        b = g.inject_barrier_nowait()
        t0 = time.perf_counter()
        with pytest.raises(DeviceWedged):
            g.wait_barrier(b.epoch.curr, timeout=30.0)
        waited = time.perf_counter() - t0
        # structured failure in ~a wait slice, nowhere near the 30s
        # deadman (let alone the 360s the real wedge burned)
        assert waited < 10.0, waited
        # the stall dump is captured on a side thread (fail-fast first,
        # forensics best-effort): poll briefly for the artifact
        deadline = time.time() + 10
        dumps = []
        while not dumps and time.time() < deadline:
            dumps = [
                f
                for f in os.listdir(tmp_path)
                if f.startswith("STALL_DUMP_")
            ]
            time.sleep(0.05)
        assert dumps, "wedge left no stall artifact"
        doc = json.load(open(tmp_path / dumps[0]))
        assert "device wedged" in doc["reason"]
        assert "blackbox" in doc  # recorder tail + sentinel snapshot
    finally:
        if stall_dir is None:
            os.environ.pop("RW_STALL_DIR", None)
        else:
            os.environ["RW_STALL_DIR"] = stall_dir
        dev.unwedge()
        blackbox.SENTINEL.stop()
        blackbox.SENTINEL = saved_sentinel
        g.stop()


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


def test_blackbox_config_section_and_env_precedence(tmp_path, monkeypatch):
    """[blackbox] TOML parses into the config dataclass; RW_BLACKBOX=0
    (the env escape hatch) wins over an enabled config."""
    from risingwave_tpu.config import load_config

    cfg_path = tmp_path / "rw.toml"
    cfg_path.write_text(
        "[blackbox]\n"
        "ring_barriers = 64\n"
        "fsync_interval_s = 0.5\n"
        "sentinel_deadline_s = 7.5\n"
    )
    cfg = load_config(str(cfg_path))
    assert cfg.blackbox.ring_barriers == 64
    assert cfg.blackbox.fsync_interval_s == 0.5
    assert cfg.blackbox.sentinel_deadline_s == 7.5
    assert cfg.blackbox.enabled and not cfg.blackbox.sentinel
    rec = FlightRecorder()
    saved_recorder = blackbox.RECORDER
    blackbox.RECORDER = rec
    try:
        monkeypatch.setenv("RW_BLACKBOX", "0")
        blackbox.configure(cfg.blackbox)
        assert rec.enabled is False  # env beat the config's enabled=True
        assert rec.ring.maxlen == 64
        monkeypatch.setenv("RW_BLACKBOX", "1")
        monkeypatch.setenv("RW_BLACKBOX_DIR", str(tmp_path))
        monkeypatch.setenv("RW_BLACKBOX_RING", "32")
        blackbox.from_env()
        assert rec.enabled is True
        assert rec.dir == str(tmp_path)
        assert rec.ring.maxlen == 32
    finally:
        rec.close()
        blackbox.RECORDER = saved_recorder
