"""Fragment-scoped partial recovery (the blast-radius contract).

Reference contrast: the reference's failed-barrier recovery
(barrier/recovery.rs:353) restarts the WHOLE dataflow from
max_committed_epoch. Here an actor death is attributed to its fragment
by the graph supervisor (runtime/graph.py), only the downstream-closure
blast radius is fenced/rebuilt/restored/replayed, and every un-faulted
MV keeps its live state and keeps answering query() through the
recovery window. The escalation ladder (partial x3 -> full -> raise)
and the degraded-mode composition (store down => recovery DEFERS, never
wedges) are asserted here too.
"""

import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu import utils_sync_point as sync_point
from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.event_log import EVENT_LOG
from risingwave_tpu.executors.hash_agg import HashAggExecutor
from risingwave_tpu.executors.materialize import MaterializeExecutor
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.resilience import (
    CircuitBreaker,
    RetryPolicy,
    TransientStoreError,
)
from risingwave_tpu.runtime.fragmenter import (
    GraphPipeline,
    PartitionedStateView,
)
from risingwave_tpu.runtime.graph import (
    FragmentSpec,
    GraphRuntime,
    _default_barrier_timeout,
)
from risingwave_tpu.runtime.runtime import StreamingRuntime
from risingwave_tpu.sim import CrashingExecutor
from risingwave_tpu.storage.object_store import MemObjectStore, ObjectStore

pytestmark = pytest.mark.smoke


# ---------------------------------------------------------------------------
# workload builders
# ---------------------------------------------------------------------------


def _mk_agg(tid):
    return HashAggExecutor(
        group_keys=("k",),
        calls=(AggCall("sum", "v", "s"), AggCall("count_star", None, "c")),
        schema_dtypes={"k": jnp.int64, "v": jnp.int64},
        capacity=1 << 8,
        table_id=tid,
    )


def _mk_mview(tid):
    return MaterializeExecutor(pk=("k",), columns=("s", "c"), table_id=tid)


def build_singleton_mv(name, crash=None):
    """One-fragment graph MV (blast radius == whole graph: any partial
    recovery of it is a full-graph rebuild, scoped at the MV level)."""
    agg, mv = _mk_agg(f"{name}.agg"), _mk_mview(f"{name}.mview")
    chain = ([crash] if crash is not None else []) + [agg, mv]
    specs = [
        FragmentSpec("src", lambda i: []),
        FragmentSpec(
            "work", lambda i, c=tuple(chain): list(c), inputs=[("src", 0)]
        ),
    ]
    gp = GraphPipeline(
        specs, {"single": "src"}, "work", chain,
        ckpt_fragments=["work"] * len(chain),
    )
    return gp, mv


def build_parallel_mv(name, crash):
    """src --hash(k)--> par x2 --> mat, with the crash executor inside
    par#0's chain: the blast radius is {par, mat}, the src actors stay
    alive — the scoped INTRA-graph rebuild path."""
    aggs = [_mk_agg(f"{name}.agg") for _ in range(2)]
    mv = _mk_mview(f"{name}.mview")
    chains = [[crash, aggs[0]], [aggs[1]]]
    specs = [
        FragmentSpec("src", lambda i: [], dispatch=("hash", ["k"])),
        FragmentSpec(
            "par", lambda i: list(chains[i]), inputs=[("src", 0)],
            parallelism=2,
        ),
        FragmentSpec("mat", lambda i: [mv], inputs=[("par", 0)]),
    ]
    view = PartitionedStateView(aggs, {f"{name}.agg": (0,)})
    gp = GraphPipeline(
        specs, {"single": "src"}, "mat", [view, mv],
        ckpt_fragments=["par", "mat"],
    )
    return gp, mv


def _chunks(seed, n_epochs):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_epochs):
        n = int(rng.integers(4, 12))
        ks = rng.integers(0, 8, n).astype(np.int64)
        vs = rng.integers(0, 50, n).astype(np.int64)
        out.append(StreamChunk.from_numpy({"k": ks, "v": vs}, 16))
    return out


def _fault_free(chunks):
    rt = StreamingRuntime(MemObjectStore(), async_checkpoint=False)
    gpa, mva = build_singleton_mv("mv_a")
    gpb, mvb = build_parallel_mv("mv_b", CrashingExecutor("idle"))
    rt.register("mv_a", gpa)
    rt.register("mv_b", gpb)
    for c in chunks:
        rt.push("mv_a", c)
        rt.push("mv_b", c)
        rt.barrier()
    rt.wait_checkpoints()
    want = dict(mva.snapshot()), dict(mvb.snapshot())
    gpa.close()
    gpb.close()
    return want


# ---------------------------------------------------------------------------
# headline: scoped failover keeps the healthy MV hot
# ---------------------------------------------------------------------------


def test_partial_recovery_scopes_to_failed_fragment():
    """A seeded actor crash in mv_b's parallel fragment recovers ONLY
    mv_b's subtree (partial event, recovery_scope_fragments < total),
    while mv_a answers query() INSIDE the recovery window with no
    barrier gap anywhere near RW_BARRIER_TIMEOUT_S; post-recovery both
    MVs are bit-identical to a fault-free run."""
    chunks = _chunks(11, 6)
    want_a, want_b = _fault_free(chunks)

    rt = StreamingRuntime(
        MemObjectStore(), async_checkpoint=False, auto_recover=True
    )
    crash = CrashingExecutor("mv_b")
    gpa, mva = build_singleton_mv("mv_a")
    gpb, mvb = build_parallel_mv("mv_b", crash)
    rt.register("mv_a", gpa)
    rt.register("mv_b", gpb)

    graph_b0 = gpb.graph
    src_actors0 = [a for a in gpb.graph.actors if a.actor_name.startswith("src#")]
    seq0 = max((e["seq"] for e in EVENT_LOG.events()), default=0)
    scope_hist0 = REGISTRY.histogram("recovery_downtime_ms").count(
        fragment="mv_a"
    )

    # mid-recovery probe: fires inside the recovery window, right after
    # mv_b's subtree restored and before it rejoins — the healthy MV
    # must answer query() NOW
    window_queries = []
    expect_a_keys = set()

    def _query_healthy():
        snap = mva.snapshot()
        window_queries.append(len(snap))
        assert set(snap) == expect_a_keys  # mv_a state is LIVE, not rolled back

    sync_point.activate("partial_recovery:mv_b", _query_healthy)
    barrier_gaps = []
    try:
        t_last = time.monotonic()
        for i, c in enumerate(chunks):
            if i == 3:
                crash.arm("apply", after=1)  # mid-epoch murder
            rt.push("mv_a", c)
            rt.push("mv_b", c)
            for k in np.asarray(c.col("k"))[np.asarray(c.valid)].tolist():
                expect_a_keys.add((int(k),))
            before = rt.mgr.max_committed_epoch
            rt.barrier()
            if rt.mgr.max_committed_epoch == before:  # recovered, not committed
                assert rt.last_recovery_mode == "partial"
                rt.barrier()  # replayed window commits at the next boundary
                assert rt.mgr.max_committed_epoch > before
            barrier_gaps.append(time.monotonic() - t_last)
            t_last = time.monotonic()
        rt.wait_checkpoints()
    finally:
        sync_point.deactivate("partial_recovery:mv_b")

    # the crash fired exactly once and recovery was PARTIAL, not full
    assert crash.kills == 1
    assert rt.auto_recoveries == 1 and rt.partial_recoveries == 1
    evs = [e for e in EVENT_LOG.events("recovery") if e["seq"] > seq0]
    modes = [e["mode"] for e in evs]
    assert "partial" in modes and "partial_done" in modes
    assert "auto" not in modes and "restore" not in modes  # never full
    partial = next(e for e in evs if e["mode"] == "partial")
    assert partial["fragments"] == ["mv_b"]
    assert partial["scope"] == 1 < partial["total"] == 2
    assert REGISTRY.gauge("recovery_scope_fragments").get() == 1.0

    # the healthy MV answered query() inside the window...
    assert window_queries and window_queries[0] > 0
    # ...and never saw a barrier gap approaching the deadman
    assert max(barrier_gaps) < _default_barrier_timeout()
    # recovery downtime is attributed per affected MV only
    assert REGISTRY.histogram("recovery_downtime_ms").count(fragment="mv_b") >= 1
    assert (
        REGISTRY.histogram("recovery_downtime_ms").count(fragment="mv_a")
        == scope_hist0
    )

    # the rebuild was SCOPED: same graph object, src actors survived
    assert gpb.graph is graph_b0
    assert all(a.is_alive() for a in src_actors0)
    # the healthy MV's graph was never touched
    assert all(a.is_alive() for a in gpa.graph.actors)

    # bit-identical convergence for BOTH MVs
    assert dict(mva.snapshot()) == want_a
    assert dict(mvb.snapshot()) == want_b
    gpa.close()
    gpb.close()


def test_manual_scoped_recover_fragments_kwarg():
    """recover(fragments=...) restores + replays ONLY the named
    fragments; the other MV's live (uncommitted) state is untouched."""
    chunks = _chunks(23, 3)
    rt = StreamingRuntime(
        MemObjectStore(), async_checkpoint=False, auto_recover=False
    )
    gpa, mva = build_singleton_mv("mv_a")
    gpb, mvb = build_parallel_mv("mv_b", CrashingExecutor("idle"))
    rt.register("mv_a", gpa)
    rt.register("mv_b", gpb)
    for c in chunks[:2]:
        rt.push("mv_a", c)
        rt.push("mv_b", c)
        rt.barrier()
    # push an UNCOMMITTED chunk, then scoped-recover mv_b only
    rt.push("mv_a", chunks[2])
    rt.push("mv_b", chunks[2])
    rt.recover(fragments=["mv_b"])
    rt.barrier()
    rt.wait_checkpoints()
    want_a, want_b = _fault_free(chunks)
    assert dict(mvb.snapshot()) == want_b  # replayed from the buffer
    assert dict(mva.snapshot()) == want_a  # live state never rolled back
    with pytest.raises(KeyError):
        rt.recover(fragments=["nope"])
    gpa.close()
    gpb.close()


# ---------------------------------------------------------------------------
# escalation ladder: partial x3 -> full -> deterministic-fault raise
# ---------------------------------------------------------------------------


def test_escalation_partial_to_full_to_raise():
    rt = StreamingRuntime(
        MemObjectStore(), async_checkpoint=False, auto_recover=True
    )
    crash = CrashingExecutor("boom")
    gpa, _mva = build_singleton_mv("mv_a")
    gpb, _mvb = build_singleton_mv("mv_b", crash=crash)
    rt.register("mv_a", gpa)
    rt.register("mv_b", gpb)
    rng = np.random.default_rng(7)

    def chunk():
        n = int(rng.integers(4, 10))
        return StreamChunk.from_numpy(
            {"k": rng.integers(0, 4, n).astype(np.int64),
             "v": rng.integers(0, 40, n).astype(np.int64)}, 16,
        )

    for _ in range(2):
        c = chunk()
        rt.push("mv_a", c)
        rt.push("mv_b", c)
        rt.barrier()
    seq0 = max((e["seq"] for e in EVENT_LOG.events()), default=0)
    crash.always = True  # DETERMINISTIC fault: every barrier kills
    with pytest.raises(RuntimeError, match="deterministic"):
        for _ in range(10):
            c = chunk()
            rt.push("mv_a", c)
            rt.push("mv_b", c)
            rt.barrier()
    modes = [
        e["mode"]
        for e in EVENT_LOG.events("recovery")
        if e["seq"] > seq0
    ]
    # three consecutive partial attempts, then full recoveries, then
    # the raise (the full path's consecutive budget)
    assert modes.count("partial") == 3
    assert modes.count("auto") == 3
    assert modes.index("auto") > modes.index("partial")
    gpa.close()
    gpb.close()


# ---------------------------------------------------------------------------
# degraded-mode composition: store down => partial recovery DEFERS
# ---------------------------------------------------------------------------


class _DownableStore(ObjectStore):
    """Store with a hard-down switch (transient classification, so the
    resilience layer absorbs it until the budget/ breaker trips)."""

    def __init__(self, inner):
        self.inner = inner
        self.down = False

    def _gate(self):
        if self.down:
            raise TransientStoreError("store down (injected)")

    def put(self, p, d):
        self._gate()
        self.inner.put(p, d)

    def read(self, p):
        self._gate()
        return self.inner.read(p)

    def read_range(self, p, o, ln):
        self._gate()
        return self.inner.read_range(p, o, ln)

    def exists(self, p):
        self._gate()
        return self.inner.exists(p)

    def list(self, p):
        self._gate()
        return self.inner.list(p)

    def delete(self, p):
        self._gate()
        self.inner.delete(p)


def test_partial_recovery_defers_while_store_unavailable():
    """Actor crash while the store is DOWN: the restore cannot read the
    checkpoint, so partial recovery defers — the blast radius stays
    fenced (inputs park in the replay buffer), healthy fragments keep
    committing (degraded spill) and answering query(), and the barrier
    clock completes the recovery once the store heals. Nothing wedges,
    nothing double-applies."""
    down = _DownableStore(MemObjectStore())
    rt = StreamingRuntime(
        down,
        async_checkpoint=False,
        auto_recover=True,
        retry_policy=RetryPolicy(
            max_attempts=2, base_backoff_s=1e-4, max_backoff_s=1e-3,
            deadline_s=0.2,
        ),
        breaker=CircuitBreaker(
            "object_store", failure_threshold=1, cooldown_s=0.05
        ),
    )
    crash = CrashingExecutor("boom")
    gpa, mva = build_singleton_mv("mv_a")
    gpb, mvb = build_singleton_mv("mv_b", crash=crash)
    rt.register("mv_a", gpa)
    rt.register("mv_b", gpb)
    fed = []
    rng = np.random.default_rng(5)

    def feed():
        n = int(rng.integers(4, 10))
        c = StreamChunk.from_numpy(
            {"k": rng.integers(0, 4, n).astype(np.int64),
             "v": rng.integers(0, 40, n).astype(np.int64)}, 16,
        )
        fed.append(c)
        rt.push("mv_a", c)
        rt.push("mv_b", c)

    for _ in range(2):
        feed()
        rt.barrier()
    down.down = True
    crash.arm("apply", after=1)
    feed()
    rt.barrier()
    assert rt._pending_partial is not None  # deferred, not wedged
    assert rt.last_recovery_mode == "partial"
    # healthy MV keeps flowing and answering while deferred
    before_keys = len(mva.snapshot())
    feed()
    rt.barrier()
    assert len(mva.snapshot()) >= before_keys > 0
    # heal -> the barrier clock resumes and completes the recovery
    down.down = False
    deadline = time.time() + 20
    while rt._pending_partial is not None and time.time() < deadline:
        time.sleep(0.06)  # past the breaker cooldown
        rt.barrier()
    assert rt._pending_partial is None, "deferred recovery never resumed"
    rt.barrier()
    rt.wait_checkpoints()
    # convergence against a fault-free twin over the same feed
    rt2 = StreamingRuntime(MemObjectStore(), async_checkpoint=False)
    gpa2, mva2 = build_singleton_mv("mv_a")
    gpb2, mvb2 = build_singleton_mv("mv_b")
    rt2.register("mv_a", gpa2)
    rt2.register("mv_b", gpb2)
    for c in fed:
        rt2.push("mv_a", c)
        rt2.push("mv_b", c)
        rt2.barrier()
    assert dict(mvb.snapshot()) == dict(mvb2.snapshot())
    assert dict(mva.snapshot()) == dict(mva2.snapshot())
    for gp in (gpa, gpb, gpa2, gpb2):
        gp.close()


def test_deferred_resume_respects_per_fragment_durable_coverage():
    """checkpoint_frequency > 1: a fenced fragment's non-checkpoint
    barrier markers are NOT durably covered, and healthy-only commits
    during the deferral advance the global epoch past them. The resume
    must replay from the FRAGMENT's durable coverage, not the global
    committed epoch — otherwise the non-checkpoint window is silently
    lost."""
    down = _DownableStore(MemObjectStore())
    rt = StreamingRuntime(
        down,
        async_checkpoint=False,
        auto_recover=True,
        checkpoint_frequency=2,
        retry_policy=RetryPolicy(
            max_attempts=2, base_backoff_s=1e-4, max_backoff_s=1e-3,
            deadline_s=0.2,
        ),
        breaker=CircuitBreaker(
            "object_store", failure_threshold=1, cooldown_s=0.05
        ),
    )
    crash = CrashingExecutor("boom")
    gpa, mva = build_singleton_mv("mv_a")
    gpb, mvb = build_singleton_mv("mv_b", crash=crash)
    rt.register("mv_a", gpa)
    rt.register("mv_b", gpb)
    fed = []
    rng = np.random.default_rng(29)

    def feed():
        n = int(rng.integers(4, 10))
        c = StreamChunk.from_numpy(
            {"k": rng.integers(0, 4, n).astype(np.int64),
             "v": rng.integers(0, 40, n).astype(np.int64)}, 16,
        )
        fed.append(c)
        rt.push("mv_a", c)
        rt.push("mv_b", c)

    for _ in range(3):  # barriers 1(n) 2(ckpt) 3(n): marker 3 un-covered
        feed()
        rt.barrier()
    down.down = True
    crash.arm("apply", after=1)
    feed()
    rt.barrier()  # crash -> partial defers (store down)
    assert rt._pending_partial is not None
    # healthy-only barriers while deferred (commits degrade -> spill)
    for _ in range(2):
        feed()
        rt.barrier()
    down.down = False
    deadline = time.time() + 20
    while rt._pending_partial is not None and time.time() < deadline:
        time.sleep(0.06)
        rt.barrier()  # spill replays durably FIRST, then the resume
    assert rt._pending_partial is None
    rt.barrier()
    rt.wait_checkpoints()
    rt2 = StreamingRuntime(
        MemObjectStore(), async_checkpoint=False, checkpoint_frequency=2
    )
    gpa2, mva2 = build_singleton_mv("mv_a")
    gpb2, mvb2 = build_singleton_mv("mv_b")
    rt2.register("mv_a", gpa2)
    rt2.register("mv_b", gpb2)
    for c in fed:
        rt2.push("mv_a", c)
        rt2.push("mv_b", c)
        rt2.barrier()
    rt2.wait_checkpoints()
    assert dict(mvb.snapshot()) == dict(mvb2.snapshot())
    assert dict(mva.snapshot()) == dict(mva2.snapshot())
    for gp in (gpa, gpb, gpa2, gpb2):
        gp.close()


def test_manual_scoped_recover_refuses_lost_replay_window():
    """recover(fragments=...) must enforce the same replay-window guard
    as the auto path: a fragment whose buffer overflowed cannot be
    scope-recovered (that would silently drop its un-durable window)."""
    rt = StreamingRuntime(MemObjectStore(), async_checkpoint=False)
    gpa, _ = build_singleton_mv("mv_a")
    gpb, _ = build_singleton_mv("mv_b")
    rt.register("mv_a", gpa)
    rt.register("mv_b", gpb)
    c = StreamChunk.from_numpy(
        {"k": np.array([1], np.int64), "v": np.array([2], np.int64)}, 16
    )
    rt.push("mv_a", c)
    rt.push("mv_b", c)
    rt.barrier()
    rt.wait_checkpoints()
    # simulate the overflow: window lost until re-anchored durably
    with rt._replay_lock:
        rt._replay["mv_b"] = []
        rt._replay_floor["mv_b"] = None
    with pytest.raises(RuntimeError, match="replay window lost"):
        rt.recover(fragments=["mv_b"])
    gpa.close()
    gpb.close()


# ---------------------------------------------------------------------------
# satellite: the graph supervisor's attribution + fencing, unit-level
# ---------------------------------------------------------------------------


def test_supervisor_blast_radius_and_stall_provenance():
    """Fragment attribution + downstream-closure blast radius land in
    the supervisor state AND the stall snapshot (debuggable from the
    artifact alone); fragments outside the blast keep their actors."""

    class Boom:
        def apply(self, chunk):
            return [chunk]

        def on_barrier(self, b):
            raise ValueError("kaboom")

        def on_watermark(self, wm):
            return wm, []

        def emit_watermark(self):
            return None

        def pure_step(self):
            return None

        def finish_barrier(self):
            pass

        def lint_info(self):
            return None

    g = GraphRuntime(
        [
            FragmentSpec("src", lambda i: []),
            FragmentSpec("mid", lambda i: [Boom()], inputs=[("src", 0)]),
            FragmentSpec("leaf", lambda i: [], inputs=[("mid", 0)]),
            FragmentSpec("other", lambda i: [], inputs=[("src", 0)]),
        ],
        epoch_batch=False,
    ).start()
    assert g.blast_radius("mid") == {"mid", "leaf"}
    assert g.downstream_closure("src") == {"mid", "leaf", "other"}
    with pytest.raises(RuntimeError):
        g.inject_barrier(timeout=30)
    snap = g.stall_snapshot()
    assert snap["failed_fragments"] == ["mid"]
    assert snap["blast_radius"] == ["leaf", "mid"]
    assert any("kaboom" in v for v in snap["actor_errors"].values())
    by_name = {a["actor"]: a for a in snap["actors"]}
    assert by_name["mid#0"]["fragment"] == "mid"
    assert by_name["leaf#0"]["fenced"] and by_name["mid#0"]["fenced"]
    assert not by_name["other#0"]["fenced"]
    # fragments OUTSIDE the blast radius keep their actors running
    deadline = time.time() + 5
    while time.time() < deadline and by_name["leaf#0"]["alive"]:
        time.sleep(0.02)
        by_name = {a["actor"]: a for a in g.stall_snapshot()["actors"]}
    assert not by_name["leaf#0"]["alive"]  # fenced subtree exited
    assert by_name["other#0"]["alive"] and by_name["src#0"]["alive"]
    g.stop()


def test_scoped_rebuild_rejects_unsound_scopes():
    g = GraphRuntime(
        [
            FragmentSpec("src", lambda i: []),
            FragmentSpec("a", lambda i: [], inputs=[("src", 0)]),
            FragmentSpec("b", lambda i: [], inputs=[("a", 0)]),
        ],
        epoch_batch=False,
    ).start()
    with pytest.raises(ValueError, match="source"):
        g.rebuild_scoped({"src", "a", "b"})
    with pytest.raises(ValueError, match="downstream-closed"):
        g.rebuild_scoped({"a"})  # leaves b consuming a dead edge
    with pytest.raises(KeyError):
        g.rebuild_scoped({"ghost"})
    g.stop()


# ---------------------------------------------------------------------------
# satellite: stall-watchdog timers never orphan across recoveries
# ---------------------------------------------------------------------------


def _watchdog_threads():
    return [
        t
        for t in threading.enumerate()
        if t.name == "rw-stall-watchdog" and t.is_alive()
    ]


def _sentinel_threads():
    return [
        t
        for t in threading.enumerate()
        if t.name.startswith("rw-sentinel") and t.is_alive()
    ]


def test_no_orphan_stall_watchdog_timers_across_recoveries():
    """Every barrier arms a stall-watchdog Timer; success, partial
    recovery, full recovery, AND the escalation raise must all cancel
    it — repeated recoveries may not pile up live timers. Same audit
    for profiler capture windows and (PR 8) the blackbox sentinel: a
    capture open when the fault fires must be closed by recovery, the
    sentinel's wedge-capture window must never survive a recovery, and
    stopping the sentinel must leave no rw-sentinel threads."""
    from risingwave_tpu import blackbox
    from risingwave_tpu.profiler import PROFILER

    # a healthy sentinel rides across every recovery below — a FRESH
    # instance swapped in for the singleton, so the tuned heartbeat/
    # interval never leak into later tests (restored in the finally)
    saved_sentinel = blackbox.SENTINEL
    blackbox.SENTINEL = blackbox.DeviceSentinel()
    blackbox.SENTINEL.start(
        interval_s=0.05, slow_ms=1e6, deadline_s=5.0,
        heartbeat_fn=lambda: None,
    )
    rt = StreamingRuntime(
        MemObjectStore(), async_checkpoint=False, auto_recover=True
    )
    rt.stall_dump_after_s = 30.0  # real timers, armed per barrier
    PROFILER.enable()  # counters armed across the faults
    crash = CrashingExecutor("boom")
    gpa, _ = build_singleton_mv("mv_a")
    gpb, _ = build_singleton_mv("mv_b", crash=crash)
    rt.register("mv_a", gpa)
    rt.register("mv_b", gpb)
    rng = np.random.default_rng(9)
    try:
        for i in range(6):
            n = int(rng.integers(4, 10))
            c = StreamChunk.from_numpy(
                {"k": rng.integers(0, 4, n).astype(np.int64),
                 "v": rng.integers(0, 40, n).astype(np.int64)}, 16,
            )
            if i in (2, 4):
                crash.arm("apply", after=1)
            rt.push("mv_a", c)
            rt.push("mv_b", c)
            rt.barrier()
        # drive the raise path too (its finally must also cancel)
        crash.always = True
        with pytest.raises(RuntimeError):
            for _ in range(10):
                rt.push("mv_b", c)
                rt.barrier()
        assert rt.auto_recoveries >= 3
        deadline = time.time() + 5
        while time.time() < deadline and _watchdog_threads():
            time.sleep(0.05)  # canceled Timers exit, not at expiry
        assert _watchdog_threads() == []
        # blackbox sentinel audit: recoveries never left a wedge-
        # capture window open, no spurious wedge was armed, and the
        # sentinel kept beating across every recovery
        assert blackbox.SENTINEL.abort_capture() == 0
        assert blackbox.SENTINEL.wedged_error() is None
        assert blackbox.SENTINEL.beats > 0
    finally:
        PROFILER.disable()
        PROFILER.reset()
        gpa.close()
        gpb.close()
        blackbox.SENTINEL.stop()
        blackbox.SENTINEL = saved_sentinel
    deadline = time.time() + 5
    while time.time() < deadline and _sentinel_threads():
        time.sleep(0.05)
    assert _sentinel_threads() == []  # stop() reaps sentinel threads
