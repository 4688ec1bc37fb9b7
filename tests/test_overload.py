"""Overload chaos + recovery composition (PR 17 acceptance): the
seeded OverloadChaosRunner must drive the degradation ladder through
its FULL arc and back with zero OOM and zero wedge, the device-state
ledger must never exceed the HBM budget, and the governed run's final
MV must be BIT-IDENTICAL to an unthrottled fault-free twin — lag,
never loss. Composition: a process kill + store outage landing while
the ladder is raised must recover exactly-once with credits re-derived
on the rebuilt runtime.

Replay a failing schedule: every failure message carries the seed;
rerun with ``RW_CHAOS_SEED=<seed>``.
"""

import numpy as np
import jax.numpy as jnp
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.hash_agg import HashAggExecutor
from risingwave_tpu.executors.materialize import MaterializeExecutor
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.runtime import SourceManager, StreamingRuntime
from risingwave_tpu.runtime.memory_governor import (
    DEGRADED,
    NORMAL,
    SHEDDING,
    THROTTLED,
    OverloadLadder,
)
from risingwave_tpu.runtime.pipeline import Pipeline
from risingwave_tpu.sim import OverloadChaosRunner, chaos_seed
from risingwave_tpu.storage.object_store import MemObjectStore
from risingwave_tpu.storage.state_table import (
    Checkpointable,
    CheckpointManager,
    StateDelta,
)

CAP = 1 << 9


class _Split:
    def __init__(self, split_id):
        self.split_id = split_id


class _StormSource(Checkpointable):
    """Deterministic skewed key storm, offset-addressed: event i draws
    its key from a cardinality that RAMPS with the offset — riding
    successive pow2 capacities of the agg's bucket lattice — mixed
    with a small hot set that keeps re-touching (and so re-faulting)
    cold-evicted groups. Both passes see the identical event prefix
    regardless of how admission chunks the polls (lag, never loss);
    offsets checkpoint like any connector's."""

    table_id = "storm.src"

    def __init__(self, seed, hot=48):
        self.seed = seed
        self.hot = hot
        self.offset = 0
        self._committed = 0
        self.splits = [_Split("storm-0")]

    def discover(self):
        pass

    def _key(self, i):
        h = (i * 2654435761 + self.seed * 40503) & 0xFFFFFFFF
        if h % 3 == 0:
            return h % self.hot
        card = 256 + i // 3
        return self.hot + (h % card)

    def poll(self, max_rows_per_split, capacity, only=None):
        n = int(max_rows_per_split)
        chunks = []
        while n > 0:
            take = min(n, capacity)
            idx = np.arange(self.offset, self.offset + take, dtype=np.int64)
            keys = np.asarray(
                [self._key(int(i)) for i in idx], np.int64
            )
            chunks.append(
                StreamChunk.from_numpy(
                    {"k": keys, "v": (idx % 97).astype(np.int64)},
                    capacity,
                )
            )
            self.offset += take
            n -= take
        return chunks

    # -- exactly-once: offsets travel with the checkpoint ---------------
    def checkpoint_delta(self):
        if self.offset == self._committed:
            return []
        self._committed = self.offset
        return [
            StateDelta(
                "storm.src",
                {"k": np.zeros(1, np.int64)},
                {"offset": np.asarray([self.offset], np.int64)},
                np.zeros(1, bool),
                ("k",),
            )
        ]

    def restore_state(self, table_id, key_cols, value_cols):
        # an empty committed table means NOTHING is durable: rewind to
        # zero, not "keep the live offset" (the rows behind it rolled
        # back with the failed commit and must replay)
        off = value_cols.get("offset") if value_cols else None
        self.offset = int(off[0]) if off is not None and len(off) else 0
        self._committed = self.offset


class _GovernedAgg:
    """The chaos workload: storm source -> HashAgg(count, sum) ->
    host-map MV, on a real StreamingRuntime (so the governor rides the
    barrier clock) with the agg wired to the cold tier (so relief can
    actually spill) and a commit lane that lands every K barriers (so
    durability LAGS the storm — the honest overload physics: dirty
    groups cannot spill until the commit catches up)."""

    K_COMMIT = 8

    def __init__(self, seed, store=None):
        self.agg = HashAggExecutor(
            group_keys=("k",),
            calls=(
                AggCall("count_star", None, "cnt"),
                AggCall("sum", "v", "s"),
            ),
            schema_dtypes={"k": jnp.int64, "v": jnp.int64},
            capacity=CAP,
            out_cap=1 << 11,
            table_id="storm.agg",
        )
        self.mview = MaterializeExecutor(
            pk=("k",), columns=("cnt", "s"), table_id="storm.mv"
        )
        self.runtime = StreamingRuntime(store=None)
        self.runtime.register("storm", Pipeline([self.agg, self.mview]))
        self.sources = SourceManager()
        self.src = _StormSource(seed)
        self.sources.register("bids", self.src)
        self.fragment_of = {"bids": "storm"}
        self.mgr = CheckpointManager(store if store is not None else MemObjectStore())
        self.agg.cold_reader = lambda keys: self.mgr.get_rows(
            "storm.agg", keys
        )
        self._epoch = 0

    @property
    def executors(self):
        return [self.agg, self.mview, self.src]

    def ingest(self, max_rows):
        if max_rows <= 0:
            return 0
        before = self.src.offset
        for ch in self.sources.poll(
            "bids", max_rows_per_split=max_rows, capacity=CAP
        ):
            self.runtime.push("storm", ch)
        return self.src.offset - before

    def barrier(self):
        self.runtime.barrier()
        self._epoch += 1
        if self._epoch % self.K_COMMIT == 0:
            self.mgr.commit_epoch(self._epoch << 16, self.executors)

    def drain(self):
        # flush the commit lane NOW: every group turns durable, so the
        # next relief pass can spill the whole working set
        self._epoch += 1
        self.mgr.commit_epoch(self._epoch << 16, self.executors)

    def mv(self):
        return self.mview.snapshot()


def test_overload_chaos_full_ladder_and_bit_identity():
    seed = chaos_seed(11)
    runner = OverloadChaosRunner(
        make=lambda: _GovernedAgg(seed),
        seed=seed,
        storm_rows=9_000,
        burst_rows=2_000,
    )
    got, want = runner.run()
    # the runner already asserted: every rung visited, back to NORMAL,
    # ledger <= budget on every governed barrier, no wedge
    assert got == want, (
        f"governed run diverged from the unthrottled twin "
        f"(seed={seed}; report={runner.report})"
    )
    assert len(want) > 200
    # admission actually bit: the governed pass lagged (more barriers
    # than the twin's storm epochs) and DEGRADED parked the source
    assert runner.report["parked_polls"] > 0, runner.report
    assert runner.report["spills"] > 0, runner.report


def test_overload_chaos_bounded_flaps_and_recovery():
    """HOW WELL the ladder degrades, at CI scale (a 4k-row storm in 1k
    bursts): hysteresis must not thrash the rungs (at most 3
    re-escalations within a cooldown of a descent) and relief + lazy
    shrink must converge (NORMAL within 60 post-storm barriers; the
    runner itself enforces zero OOM, zero wedge and the descent)."""
    seed = chaos_seed(11)
    runner = OverloadChaosRunner(
        make=lambda: _GovernedAgg(seed),
        seed=seed,
        storm_rows=4_000,
        burst_rows=1_000,
        drain_epochs=40,
        max_epochs=300,
        # how deep the ladder stacks before relief lands depends on
        # scale; it must BITE (>= 2 states, runner-enforced) and recover
        require_full_ladder=False,
    )
    got, want = runner.run()
    assert got == want, runner.report
    assert runner.report["flaps"] <= 3, runner.report
    assert runner.report["drain_barriers"] <= 60, runner.report


def test_overload_chaos_deterministic_replay():
    """Same seed -> same ladder walk and same report shape (the replay
    contract RW_CHAOS_SEED rests on)."""
    seed = chaos_seed(13)

    def once():
        r = OverloadChaosRunner(
            make=lambda: _GovernedAgg(seed),
            seed=seed,
            storm_rows=9_000,
            burst_rows=2_000,
            require_full_ladder=False,  # replay contract, not depth
        )
        got, want = r.run()
        assert got == want
        return r.report

    a, b = once(), once()
    assert a["states_seen"] == b["states_seen"]
    assert a["epochs"] == b["epochs"]
    assert a["budget"] == b["budget"]


# ---------------------------------------------------------------------------
# recovery x overload composition
# ---------------------------------------------------------------------------


def _arm(obj, budget, cooldown=2):
    gov = obj.runtime.memory_governor
    gov.budget_bytes = budget
    gov.enabled = True
    gov.ladder = OverloadLadder(
        throttle_at=0.30, shed_at=0.55, degrade_at=0.90, cooldown=cooldown
    )
    gov.spill_at = 0.5  # relieve aggressively: DEGRADED must not freeze
    obj.sources.attach_admission(gov.admission, obj.fragment_of)
    return gov


def test_recovery_during_throttle_keeps_exactly_once():
    """A process kill landing while the ladder is RAISED: rebuild from
    the store, re-arm the governor (fresh instance — the ladder is
    control state, not data state), and the run must still converge to
    the undisturbed twin's MV with credits re-derived on the rebuilt
    runtime."""
    seed = chaos_seed(17)
    rows_per_epoch, epochs = 1_200, 9

    def feed_all(obj, n_epochs, barrier_budget=300):
        barriers = 0
        for _ in range(n_epochs):
            want = rows_per_epoch
            while want > 0:
                got = obj.ingest(want)
                obj.barrier()  # parked barriers still run the commit
                want -= got    # lane, so relief eventually unfreezes
                barriers += 1
                if barriers > barrier_budget:
                    pytest.fail(
                        f"wedged: ingest stalled (seed={seed}, "
                        f"state={obj.runtime.memory_governor.ladder.state})"
                    )

    # undisturbed, unthrottled twin
    twin = _GovernedAgg(seed)
    feed_all(twin, epochs)
    twin.drain()
    twin.barrier()
    want = twin.mv()

    # governed run with a mid-run kill: everything live is abandoned,
    # the store's committed bytes are the only survivors
    disk = MemObjectStore()
    obj = _GovernedAgg(seed, store=disk)
    # budget ~ the twin's final footprint: tight enough to raise the
    # ladder well before the run completes
    peak = OverloadChaosRunner._footprint(twin.runtime)
    gov = _arm(obj, int(peak * 1.1))
    feed_all(obj, 4)
    assert gov.ladder.state != NORMAL, (
        f"ladder never raised before the kill (seed={seed}, "
        f"state={gov.ladder.state}, score={gov.ladder.last_score})"
    )
    raised_state = gov.ladder.state
    assert raised_state in (THROTTLED, SHEDDING, DEGRADED)

    # KILL: drop the object mid-window (uncommitted epochs vanish),
    # rebuild from the store, recover offsets + state, re-arm
    obj2 = _GovernedAgg(seed, store=disk)
    obj2.mgr.recover(obj2.executors)
    obj2._epoch = obj2.mgr.max_committed_epoch >> 16
    committed_offset = obj2.src.offset
    assert committed_offset < rows_per_epoch * 4, "kill landed too late"
    gov2 = _arm(obj2, int(peak * 1.1))
    # the epochs the kill rolled back replay from the anchored offset
    # (exactly-once: offsets travel with the commit)
    remaining = rows_per_epoch * epochs - committed_offset
    while remaining > 0:
        got = obj2.ingest(min(remaining, rows_per_epoch))
        obj2.barrier()
        remaining -= got
    obj2.drain()
    for _ in range(30):
        obj2.barrier()
        if gov2.ladder.state == NORMAL:
            break
    assert obj2.mv() == want, (
        f"recovery during {raised_state} diverged (seed={seed}; "
        f"rerun with RW_CHAOS_SEED={seed})"
    )
    # credits re-derived on the REBUILT runtime (fresh controller)
    assert gov2.admission.rederives > 0
    assert "storm" in gov2.admission.credits


def test_store_outage_during_shed_parks_then_recovers():
    """Store down while the ladder is raised: commits fail, relief
    cannot spill (nothing new turns durable), the ladder holds its
    rung — and once the store returns, the commit lands, spill frees
    the working set and the ladder descends. Exactly-once holds
    because each failed commit follows the manager's contract (mark
    flips are eager — a commit failure REQUIRES recover(), never a
    retry against live state): state rolls back to the last good
    manifest and the source offset rewinds with it (lag, never
    loss)."""
    seed = chaos_seed(19)
    twin = _GovernedAgg(seed)
    for _ in range(6):
        twin.ingest(1_000)
        twin.barrier()
    twin.drain()
    twin.barrier()
    want = twin.mv()
    peak = OverloadChaosRunner._footprint(twin.runtime)

    disk = MemObjectStore()
    obj = _GovernedAgg(seed, store=disk)
    down = {"on": False}

    class _Gate(MemObjectStore):
        def put(self, path, data):
            if down["on"]:
                raise RuntimeError("store down")
            return disk.put(path, data)

        def read(self, path):
            return disk.read(path)

        def read_range(self, path, off, length):
            return disk.read_range(path, off, length)

        def exists(self, path):
            return disk.exists(path)

        def list(self, prefix):
            return disk.list(prefix)

        def delete(self, path):
            return disk.delete(path)

    obj.mgr = CheckpointManager(_Gate())
    obj.agg.cold_reader = lambda keys: obj.mgr.get_rows("storm.agg", keys)
    gov = _arm(obj, int(peak * 1.1))

    target = 6_000
    down["on"] = True  # outage from the start: nothing turns durable
    barriers = 0
    failed_commits = 0
    while obj.src.offset < target:
        obj.ingest(min(1_000, target - obj.src.offset))
        try:
            obj.barrier()
        except RuntimeError:
            # the commit failed mid-outage. Contract (CheckpointManager
            # docstring): mark flips are eager, so live state is now
            # invalid — recover from the last good manifest. The source
            # offset rewinds with the commit, so the rolled-back rows
            # replay from their anchored offsets.
            failed_commits += 1
            obj.mgr.recover(obj.executors)
            obj._epoch = obj.mgr.max_committed_epoch >> 16
        barriers += 1
        if barriers == 12:
            down["on"] = False  # store returns mid-run
        if barriers > 200:
            pytest.fail(
                f"wedged under store outage (seed={seed}, "
                f"offset={obj.src.offset}, state={gov.ladder.state})"
            )
    assert failed_commits > 0, "outage never hit a commit"
    obj.drain()
    for _ in range(40):
        obj.barrier()
        if gov.ladder.state == NORMAL:
            break
    assert obj.mv() == want, f"store outage diverged (seed={seed})"
    assert gov.ladder.state == NORMAL
