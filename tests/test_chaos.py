"""Chaos tier (VERDICT r2 #10; madsim recovery suites analogue):
random kill-and-recover at arbitrary commit writes — including between
SST uploads and the manifest commit — must converge to exactly the
undisturbed run's MV; with the FlakyStore storm layered on, transient
faults are absorbed by the resilience layer and convergence still
holds byte-for-byte.

Replay a failing schedule: every failure message carries the seed;
rerun with ``RW_CHAOS_SEED=<seed>`` to reproduce it deterministically.
"""

import pytest

from risingwave_tpu.connectors.nexmark import NexmarkConfig
from risingwave_tpu.connectors.source import NexmarkSourceExecutor
from risingwave_tpu.queries.nexmark_q import build_q5_lite, build_q8
from risingwave_tpu.sim import ChaosRunner, chaos_seed
from risingwave_tpu.storage.object_store import MemObjectStore
from risingwave_tpu.storage.state_table import CheckpointManager

EVENTS, CAP = 900, 1024


def _assert_converged(runner, got, want):
    """Convergence check that prints the fault-schedule seed on
    failure (satellite: replay with RW_CHAOS_SEED=<seed>)."""
    assert got == want, (
        f"chaos run diverged from the undisturbed twin "
        f"(seed={runner.seed}; rerun with RW_CHAOS_SEED={runner.seed} "
        f"to replay this schedule: crashes={runner.crashes} "
        f"giveups={runner.giveups} faults={runner.faults_injected})"
    )


class _Q5:
    def __init__(self):
        self.source = NexmarkSourceExecutor(NexmarkConfig(), split_num=2)
        self.q5 = build_q5_lite(capacity=1 << 12, state_cleaning=False)

    @property
    def executors(self):
        return self.q5.pipeline.executors + [self.source]

    def feed(self):
        for bid in self.source.poll(EVENTS, CAP)["bid"]:
            self.q5.pipeline.push(bid.select(["auction", "date_time"]))
        self.q5.pipeline.barrier()


class _Q8:
    def __init__(self):
        self.source = NexmarkSourceExecutor(NexmarkConfig(), split_num=2)
        self.q8 = build_q8(capacity=1 << 12, state_cleaning=False)

    @property
    def executors(self):
        return self.q8.pipeline.executors + [self.source]

    def feed(self):
        polled = self.source.poll(EVENTS, CAP)
        for p in polled["person"]:
            self.q8.pipeline.push_left(p)
        for a in polled["auction"]:
            self.q8.pipeline.push_right(a)
        self.q8.pipeline.barrier()


def _undisturbed(cls, n_epochs):
    obj = cls()
    mgr = CheckpointManager(MemObjectStore())
    for i in range(n_epochs):
        obj.feed()
        mgr.commit_epoch((i + 1) << 16, obj.executors)
    return obj


@pytest.mark.parametrize("cls,snap,seed", [
    (_Q5, lambda o: o.q5.mview.snapshot(), 1),
    (_Q5, lambda o: o.q5.mview.snapshot(), 2),
    (_Q8, lambda o: o.q8.mview.snapshot(), 3),
    (_Q8, lambda o: o.q8.mview.snapshot(), 4),
])
def test_chaos_converges_to_undisturbed(cls, snap, seed):
    seed = chaos_seed(seed)
    n_epochs = 6
    want = snap(_undisturbed(cls, n_epochs))
    runner = ChaosRunner(
        make=cls, feed=lambda o: o.feed(), seed=seed, crash_prob=0.45
    )
    obj = runner.run(n_epochs)
    assert runner.crashes >= 1, "chaos run never crashed — raise crash_prob"
    _assert_converged(runner, snap(obj), want)
    assert len(want) > 50


def test_flaky_storm_converges_to_undisturbed():
    """The acceptance bar: a >=20% transient-error storm (seeded) over
    the full ingest->barrier->crash->recover loop converges to the
    byte-identical undisturbed result; every retry is deadline-bounded
    (the runner's policy), and the storm actually fired."""
    from risingwave_tpu.metrics import REGISTRY

    seed = chaos_seed(5)
    n_epochs = 5
    want = _undisturbed(_Q5, n_epochs).q5.mview.snapshot()
    retries0 = REGISTRY.counter("retries_total").get(op="store.put")
    runner = ChaosRunner(
        make=_Q5,
        feed=lambda o: o.feed(),
        seed=seed,
        crash_prob=0.3,
        flaky_rate=0.25,
    )
    obj = runner.run(n_epochs)
    assert runner.faults_injected > 0, "the flaky storm never fired"
    _assert_converged(runner, obj.q5.mview.snapshot(), want)
    # the storm was absorbed by BOUNDED retries (the runner's policy
    # carries a deadline; a giveup recovers like a crash, never spins)
    # and the retry pressure is visible in the metrics
    assert (
        REGISTRY.counter("retries_total").get(op="store.put") > retries0
    )
    assert len(want) > 50


def test_crash_lands_mid_retry_loop():
    """FlakyStore composes with CrashingStore: a transient fault on
    attempt 1 and the armed crash on attempt 2 means the process dies
    INSIDE the retry loop — and CrashPoint must pass straight through
    (a retry loop may never 'handle' a death)."""
    from risingwave_tpu.resilience import RetryingObjectStore, RetryPolicy
    from risingwave_tpu.sim import CrashingStore, CrashPoint, FlakyStore

    crashing = CrashingStore(MemObjectStore())
    crashing.arm(1)  # first write that REACHES the store crashes
    # seed 1's first two draws are 0.134, 0.847: at rate .5 attempt 1
    # faults before reaching the store, attempt 2 passes through
    flaky = FlakyStore(crashing, rate=0.5, seed=1)
    rs = RetryingObjectStore(
        flaky,
        RetryPolicy(max_attempts=5, base_backoff_s=1e-4, deadline_s=2.0),
    )
    with pytest.raises(CrashPoint):
        rs.put("a", b"x")
    assert flaky.faults == 1  # the retry actually happened first


@pytest.mark.slow
def test_flaky_fault_storm_heavy():
    """Fault storm at higher rate + injected latency over the join
    workload (q8), composed with crashes — long-haul convergence."""
    seed = chaos_seed(13)
    n_epochs = 6
    want = _undisturbed(_Q8, n_epochs).q8.mview.snapshot()
    runner = ChaosRunner(
        make=_Q8,
        feed=lambda o: o.feed(),
        seed=seed,
        crash_prob=0.4,
        flaky_rate=0.35,
    )
    obj = runner.run(n_epochs)
    assert runner.faults_injected > 0
    assert runner.crashes >= 1
    _assert_converged(runner, obj.q8.mview.snapshot(), want)


# ---------------------------------------------------------------------------
# actor-kill chaos (partial recovery's madsim analogue): murder random
# ACTORS mid-epoch — not the store — and converge bit-identically
# ---------------------------------------------------------------------------


class _ActorKillWorkload:
    """Two graph MVs over one deterministic chunk stream; CrashingExecutors
    planted in mv_b's parallel fragment are the runner's kill targets.
    A kill's blast radius is mv_b only — mv_a must stay hot."""

    def __init__(self, seed=101, n_epochs=8):
        import jax.numpy as jnp
        import numpy as np

        from risingwave_tpu.array.chunk import StreamChunk
        from risingwave_tpu.executors.hash_agg import HashAggExecutor
        from risingwave_tpu.executors.materialize import MaterializeExecutor
        from risingwave_tpu.ops.agg import AggCall
        from risingwave_tpu.runtime.fragmenter import (
            GraphPipeline,
            PartitionedStateView,
        )
        from risingwave_tpu.runtime.graph import FragmentSpec
        from risingwave_tpu.runtime.runtime import StreamingRuntime
        from risingwave_tpu.sim import CrashingExecutor

        def mk_agg(tid):
            return HashAggExecutor(
                group_keys=("k",),
                calls=(AggCall("sum", "v", "s"), AggCall("count_star", None, "c")),
                schema_dtypes={"k": jnp.int64, "v": jnp.int64},
                capacity=1 << 8,
                table_id=tid,
            )

        self.runtime = StreamingRuntime(
            MemObjectStore(), async_checkpoint=False, auto_recover=True
        )
        agg_a, self.mva = mk_agg("ka.agg"), MaterializeExecutor(
            pk=("k",), columns=("s", "c"), table_id="ka.mview"
        )
        chain_a = [agg_a, self.mva]
        gpa = GraphPipeline(
            [
                FragmentSpec("src", lambda i: []),
                FragmentSpec(
                    "work", lambda i, c=tuple(chain_a): list(c),
                    inputs=[("src", 0)],
                ),
            ],
            {"single": "src"}, "work", chain_a,
            ckpt_fragments=["work"] * len(chain_a),
        )
        self.crash_points = [CrashingExecutor("p0"), CrashingExecutor("p1")]
        aggs_b = [mk_agg("kb.agg") for _ in range(2)]
        self.mvb = MaterializeExecutor(
            pk=("k",), columns=("s", "c"), table_id="kb.mview"
        )
        chains = [
            [self.crash_points[0], aggs_b[0]],
            [self.crash_points[1], aggs_b[1]],
        ]
        gpb = GraphPipeline(
            [
                FragmentSpec("src", lambda i: [], dispatch=("hash", ["k"])),
                FragmentSpec(
                    "par", lambda i: list(chains[i]), inputs=[("src", 0)],
                    parallelism=2,
                ),
                FragmentSpec("mat", lambda i: [self.mvb], inputs=[("par", 0)]),
            ],
            {"single": "src"}, "mat",
            [PartitionedStateView(aggs_b, {"kb.agg": (0,)}), self.mvb],
            ckpt_fragments=["par", "mat"],
        )
        self.runtime.register("mv_a", gpa)
        self.runtime.register("mv_b", gpb)
        rng = np.random.default_rng(seed)
        self.chunks = []
        for _ in range(n_epochs):
            n = int(rng.integers(4, 12))
            self.chunks.append(
                StreamChunk.from_numpy(
                    {
                        "k": rng.integers(0, 8, n).astype("int64"),
                        "v": rng.integers(0, 50, n).astype("int64"),
                    },
                    16,
                )
            )

    def feed(self, i):
        c = self.chunks[i]
        self.runtime.push("mv_a", c)
        self.runtime.push("mv_b", c)
        self.runtime.barrier()

    def snapshots(self):
        return dict(self.mva.snapshot()), dict(self.mvb.snapshot())


def test_actor_kill_chaos_converges_to_undisturbed():
    """ChaosRunner's actor-kill mode at a tier-1-friendly rate: random
    actor murders mid-epoch (apply AND barrier sites), recovered by the
    fragment-scoped supervisor — both MVs bit-identical to the
    fault-free twin, with at least one PARTIAL recovery exercised."""
    from risingwave_tpu.sim import ActorChaosRunner

    from risingwave_tpu.profiler import PROFILER

    seed = chaos_seed(21)
    n_epochs = 6
    twin = _ActorKillWorkload()
    for i in range(n_epochs):
        twin.feed(i)
    want = twin.snapshots()

    # dispatch counters armed across the storm (recoveries rebuild
    # under the kernel interposer); the blackbox sentinel rides the
    # same storm — actor kills must neither arm a spurious wedge nor
    # orphan its capture window (PR 8 audit)
    from risingwave_tpu import blackbox

    PROFILER.enable()
    saved_sentinel = blackbox.SENTINEL  # fresh instance: no config leak
    blackbox.SENTINEL = blackbox.DeviceSentinel()
    blackbox.SENTINEL.start(
        interval_s=0.05, slow_ms=1e6, deadline_s=5.0,
        heartbeat_fn=lambda: None,
    )
    try:
        runner = ActorChaosRunner(
            _ActorKillWorkload, seed=seed, kill_prob=0.45, kill_site="mixed"
        )
        obj = runner.run(n_epochs)
        # actor faults are NOT device wedges: nothing armed, no window
        assert blackbox.SENTINEL.wedged_error() is None
        assert blackbox.SENTINEL.abort_capture() == 0
    finally:
        PROFILER.disable()
        PROFILER.reset()
        blackbox.SENTINEL.stop()
        blackbox.SENTINEL = saved_sentinel
    kills = sum(cp.kills for cp in obj.crash_points)
    assert kills >= 1, (
        f"no actor was ever killed — raise kill_prob (seed={seed})"
    )
    got = obj.snapshots()
    assert got == want, (
        f"actor-kill chaos diverged from the fault-free twin "
        f"(seed={seed}; rerun with RW_CHAOS_SEED={seed}: "
        f"kills={kills} armed={runner.kills_armed} "
        f"recoveries={obj.runtime.auto_recoveries} "
        f"partial={obj.runtime.partial_recoveries})"
    )
    assert obj.runtime.partial_recoveries >= 1  # the scoped path ran


@pytest.mark.slow
def test_actor_kill_storm_q8_heavy():
    """Heavy-kill storm over the q8 join graph: crash points in both
    join-side chains, high kill rate, mixed sites — the partial-recovery
    replay must keep join state exactly-once and converge."""
    from risingwave_tpu.connectors.nexmark import NexmarkGenerator
    from risingwave_tpu.queries.nexmark_q import build_q5_lite
    from risingwave_tpu.runtime.fragmenter import GraphPipeline
    from risingwave_tpu.runtime.graph import FragmentSpec
    from risingwave_tpu.runtime.runtime import StreamingRuntime
    from risingwave_tpu.sim import ActorChaosRunner, CrashingExecutor

    seed = chaos_seed(33)
    n_epochs = 6

    class _Q8Kill:
        def __init__(self):
            self.runtime = StreamingRuntime(
                MemObjectStore(), async_checkpoint=False, auto_recover=True
            )
            self.q8 = build_q8(capacity=1 << 12, state_cleaning=False)
            tp = self.q8.pipeline
            self.crash_points = [
                CrashingExecutor("q8l"), CrashingExecutor("q8r"),
            ]
            build = {
                "left": [self.crash_points[0]] + tp.left,
                "right": [self.crash_points[1]] + tp.right,
                "join": tp.join,
                "tail": tp.tail,
            }
            specs = [
                FragmentSpec("p", lambda i: []),
                FragmentSpec("a", lambda i: []),
                FragmentSpec(
                    "join", lambda i, b=build: dict(b),
                    inputs=[("p", 0), ("a", 1)],
                ),
            ]
            gp = GraphPipeline(
                specs, {"left": "p", "right": "a"}, "join", tp.executors,
                ckpt_fragments=["join"] * len(tp.executors),
            )
            # a second, independent MV keeps the runtime multi-fragment
            # so q8's blast radius stays a strict subset (partial path)
            self.q5 = build_q5_lite(capacity=1 << 12, state_cleaning=False)
            c5 = list(self.q5.pipeline.executors)
            gp5 = GraphPipeline(
                [
                    FragmentSpec("src", lambda i: []),
                    FragmentSpec(
                        "work", lambda i, c=tuple(c5): list(c),
                        inputs=[("src", 0)],
                    ),
                ],
                {"single": "src"}, "work", c5,
                ckpt_fragments=["work"] * len(c5),
            )
            self.runtime.register("q8", gp)
            self.runtime.register("q5", gp5)
            gen = NexmarkGenerator(NexmarkConfig(first_event_rate=25_000))
            self.feeds = []
            while len(self.feeds) < n_epochs:
                ch = gen.next_chunks(6_000, 1 << 13)
                if ch["person"] is None or ch["auction"] is None or ch["bid"] is None:
                    continue
                self.feeds.append(ch)

        def feed(self, i):
            ch = self.feeds[i]
            self.runtime.push("q8", ch["person"], side="left")
            self.runtime.push("q8", ch["auction"], side="right")
            self.runtime.push(
                "q5", ch["bid"].select(["auction", "date_time"])
            )
            self.runtime.barrier()

        def snapshots(self):
            return (
                dict(self.q8.mview.snapshot()),
                dict(self.q5.mview.snapshot()),
            )

    twin = _Q8Kill()
    for i in range(n_epochs):
        twin.feed(i)
    want = twin.snapshots()
    assert len(want[0]) > 20

    runner = ActorChaosRunner(
        _Q8Kill, seed=seed, kill_prob=0.6, kill_site="mixed"
    )
    obj = runner.run(n_epochs, max_attempts=300)
    kills = sum(cp.kills for cp in obj.crash_points)
    assert kills >= 1
    got = obj.snapshots()
    assert got == want, (
        f"q8 heavy-kill storm diverged (seed={seed}; rerun with "
        f"RW_CHAOS_SEED={seed}: kills={kills} "
        f"recoveries={obj.runtime.auto_recoveries} "
        f"partial={obj.runtime.partial_recoveries})"
    )


def test_dead_store_serves_nothing():
    """CrashingStore sim fidelity: once dead, EVERY op raises — a
    killed process cannot answer reads/exists/list either."""
    from risingwave_tpu.sim import CrashingStore, CrashPoint

    disk = MemObjectStore()
    disk.put("p", b"x")
    store = CrashingStore(disk)
    assert store.read("p") == b"x"  # alive: reads pass through
    store.arm(1)
    with pytest.raises(CrashPoint):
        store.put("q", b"y")
    for op in (
        lambda: store.read("p"),
        lambda: store.read_range("p", 0, 1),
        lambda: store.exists("p"),
        lambda: store.list(""),
        lambda: store.put("r", b"z"),
        lambda: store.delete("p"),
    ):
        with pytest.raises(CrashPoint):
            op()
    assert disk.read("p") == b"x"  # the durable bytes are untouched


def test_crash_exactly_between_sst_and_manifest():
    """Pin the crash to the torn-upload window: the SST is uploaded,
    the manifest is not — recovery must land on the PREVIOUS epoch and
    replay produces the undisturbed result."""
    from risingwave_tpu.sim import CrashingStore, CrashPoint

    want = _undisturbed(_Q5, 3).q5.mview.snapshot()

    disk = MemObjectStore()
    obj = _Q5()
    store = CrashingStore(disk)
    mgr = CheckpointManager(store)
    obj.feed()
    mgr.commit_epoch(1 << 16, obj.executors)
    obj.feed()
    # next writes: 1 source-offset SST + agg/mv SSTs + manifest; arm so
    # the MANIFEST put dies (count the tables staged: offsets, agg, mv)
    n_tables = 3
    store.arm(n_tables + 1)
    with pytest.raises(CrashPoint):
        mgr.commit_epoch(2 << 16, obj.executors)

    obj2 = _Q5()
    mgr2 = CheckpointManager(CrashingStore(disk))
    mgr2.recover(obj2.executors)
    assert mgr2.max_committed_epoch == 1 << 16  # epoch 2 rolled back
    for i in (2, 3):
        obj2.feed()
        mgr2.commit_epoch(i << 16, obj2.executors)
    assert obj2.q5.mview.snapshot() == want
