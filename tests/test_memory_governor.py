"""Memory governor unit surface (PR 17): the degradation ladder's
hysteresis, credit-based admission, the BucketAllocator grow-gate veto
contract (hysteresis ticks ONCE across a veto/release cycle — the
regression the PR fixes), dormancy by default, and the zero-row poll
anchoring exactly-once rests on.
"""

import numpy as np
import pytest

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.runtime import SourceManager
from risingwave_tpu.ops.bucketing import BucketAllocator, BucketPolicy
from risingwave_tpu.runtime.memory_governor import (
    DEGRADED,
    LADDER,
    NORMAL,
    SHEDDING,
    THROTTLED,
    AdmissionController,
    MemoryGovernor,
    OverloadLadder,
)

pytestmark = pytest.mark.smoke


# ---------------------------------------------------------------------------
# the ladder
# ---------------------------------------------------------------------------


def _ladder(cooldown=3):
    return OverloadLadder(
        throttle_at=0.75, shed_at=0.90, degrade_at=0.98, cooldown=cooldown
    )


def test_ladder_escalates_immediately_possibly_multiple_rungs():
    lad = _ladder()
    assert lad.step(0.5) == NORMAL
    # a single spike jumps straight to the matching rung
    assert lad.step(0.99) == DEGRADED
    assert [t["to"] for t in lad.transitions] == [DEGRADED]


def test_ladder_descends_one_rung_per_cooldown_of_calm():
    lad = _ladder(cooldown=3)
    lad.step(0.99)
    assert lad.state == DEGRADED
    # calm must be CONSECUTIVE: an interleaved hot barrier resets it
    lad.step(0.1)
    lad.step(0.1)
    lad.step(0.97)  # below degrade_at*0.85? no: 0.97 > 0.833 -> resets
    assert lad.state == DEGRADED
    for _ in range(3):
        lad.step(0.1)
    assert lad.state == SHEDDING  # ONE rung, not straight to NORMAL
    for _ in range(3):
        lad.step(0.1)
    assert lad.state == THROTTLED
    for _ in range(3):
        lad.step(0.1)
    assert lad.state == NORMAL


def test_ladder_flap_is_reescalation_within_cooldown_of_descent():
    lad = _ladder(cooldown=2)
    lad.step(0.80)  # THROTTLED
    lad.step(0.1)
    lad.step(0.1)  # descends to NORMAL
    assert lad.state == NORMAL and lad.flaps == 0
    lad.step(0.80)  # right back up: a flap
    assert lad.state == THROTTLED
    assert lad.flaps == 1


def test_ladder_exit_threshold_is_sticky():
    """Scores in the (exit, enter) hysteresis band hold the rung
    forever — boundary-riding load cannot flap the ladder."""
    lad = _ladder(cooldown=2)
    lad.step(0.80)
    for _ in range(20):
        lad.step(0.70)  # above exit 0.75*0.85=0.6375, below enter
    assert lad.state == THROTTLED
    assert lad.flaps == 0


# ---------------------------------------------------------------------------
# credits
# ---------------------------------------------------------------------------


def test_degraded_parks_immediately_and_recovers_stepwise():
    adm = AdmissionController(recover_step=0.25)
    adm.rederive(DEGRADED, 1.0, fragments=("q5",))
    assert adm.credits["q5"] == 0.0  # parked NOW, no trickle
    assert adm.admit_rows("q5", 1_000) == 0
    assert adm.parked_polls == 1
    # recovery is bounded per barrier: 0 -> .25 -> .5 -> ...
    adm.rederive(NORMAL, 0.0, fragments=("q5",))
    assert adm.credits["q5"] == 0.25
    adm.rederive(NORMAL, 0.0, fragments=("q5",))
    assert adm.credits["q5"] == 0.5
    # a nonzero credit always admits at least one row
    assert adm.admit_rows("q5", 1) == 1


def test_bottleneck_fragment_clamped_one_extra_halving():
    adm = AdmissionController()
    adm.rederive(THROTTLED, 0.8, bottleneck="hot", fragments=("hot", "ok"))
    # movement is damped to one halving per barrier; the bottleneck's
    # LOWER target (base 0.5 halved again) lands on the next rederive
    assert adm.credits["ok"] == 0.5
    assert adm.credits["hot"] == 0.5
    adm.rederive(THROTTLED, 0.8, bottleneck="hot", fragments=("hot", "ok"))
    assert adm.credits["ok"] == 0.5
    assert adm.credits["hot"] == 0.25


def test_unmapped_source_gets_the_tightest_window():
    adm = AdmissionController()
    adm.rederive(SHEDDING, 0.9, fragments=("a", "b"))
    adm.credits["a"] = 0.75
    assert adm.credit("unknown") == min(adm.credits.values())
    assert adm.credit(None) == min(adm.credits.values())
    # with no credits derived at all, admission is wide open
    assert AdmissionController().credit("anything") == 1.0


# ---------------------------------------------------------------------------
# the grow-gate veto contract (the PR's bug fix, at a lattice boundary)
# ---------------------------------------------------------------------------


def _alloc():
    return BucketAllocator(BucketPolicy(min_cap=64, max_cap=1024))


def test_vetoed_grow_leaves_hysteresis_untouched_then_ticks_once():
    """A vetoed grow that later succeeds must apply its pending-shrink
    and streak resets exactly once — at the grow that actually runs.
    Regression: the veto path used to reset them on refusal too, so a
    veto/release cycle double-ticked the hysteresis and a buffer
    sitting at a lattice boundary lost its earned shrink."""
    alloc = _alloc()
    # earn a pending shrink: calm barriers at low occupancy on a big cap
    for _ in range(alloc.policy.patience):
        alloc.note_barrier(512, 8)
    assert alloc._pending_shrink is not None
    streak = alloc._streak

    denies = {"on": True}
    alloc.grow_gate = lambda cap, new_cap: not denies["on"]

    # boundary-riding load asks to grow 512 -> 1024; the gate refuses
    assert alloc.plan(512, incoming=300, claimed=300, survivors=300) is None
    assert alloc.vetoes == 1
    assert alloc._veto_hold is True
    # hysteresis state UNTOUCHED by the refusal
    assert alloc._pending_shrink is not None
    assert alloc._streak == streak

    # barrier: hold clears (occupancy high -> shrink state resets here,
    # by the normal note_barrier rules, not by the veto)
    alloc.note_barrier(512, 300)
    assert alloc._veto_hold is False

    # released: the SAME grow now succeeds and ticks the resets once
    denies["on"] = False
    assert alloc.plan(512, incoming=300, claimed=300, survivors=300) == 1024
    assert alloc._pending_shrink is None
    assert alloc._streak == 0
    assert alloc.vetoes == 1  # no further veto counted


def test_veto_hold_stops_per_chunk_reasking_until_the_barrier():
    alloc = _alloc()
    alloc.grow_gate = lambda cap, new_cap: False
    assert alloc.plan(512, incoming=300, claimed=300, survivors=300) is None
    assert alloc._veto_hold is True
    # the apply path's pre-check goes quiet for the rest of the epoch
    assert not alloc.should_plan(512, bound=300, incoming=300)
    alloc.note_barrier(512, 300)  # re-probe on the barrier clock
    assert alloc.should_plan(512, bound=300, incoming=300)


def test_same_cap_compaction_is_never_vetoed():
    """A tombstone compaction (new_cap == cap) frees memory — the gate
    must only see GENUINE growth."""
    alloc = _alloc()
    calls = []
    alloc.grow_gate = lambda cap, new_cap: calls.append((cap, new_cap)) or False
    # claimed rides above grow_at but survivors fit the same bucket
    out = alloc.plan(512, incoming=0, claimed=400, survivors=100)
    assert out == 512  # pure compaction planned
    assert calls == []  # gate never consulted
    assert alloc.vetoes == 0


def test_bump_stays_ungated():
    """The mid-epoch overflow guard must never be vetoed: it exists to
    prevent data loss NOW; the governor reconciles next barrier."""
    alloc = _alloc()
    alloc.grow_gate = lambda cap, new_cap: False
    assert alloc.bump(512) == 1024
    assert alloc.vetoes == 0


def test_broken_gate_never_wedges_growth():
    alloc = _alloc()

    def boom(cap, new_cap):
        raise RuntimeError("gate crashed")

    alloc.grow_gate = boom
    assert alloc.plan(512, incoming=300, claimed=300, survivors=300) == 1024


# ---------------------------------------------------------------------------
# the governor
# ---------------------------------------------------------------------------


def test_governor_dormant_by_default(monkeypatch):
    monkeypatch.delenv("RW_HBM_BUDGET_BYTES", raising=False)
    monkeypatch.delenv("RW_HBM_BUDGET_FRAC", raising=False)
    monkeypatch.delenv("RW_OVERLOAD_LADDER", raising=False)
    gov = MemoryGovernor()
    assert gov.enabled is False
    # observe_barrier is a no-op: no ledger walk, no gating
    gov.observe_barrier(runtime=None, tr=None)
    assert gov._barriers == 0
    assert gov.authorize_grow("t", 64, 128, 8.0) is True


def test_authorize_grow_vetoes_at_budget_and_charges_optimistically():
    gov = MemoryGovernor(budget_bytes=10_000)
    gov.ledger_total = 9_000
    # projected 9_000 + 128*16 = 11_048 > budget -> veto + relief flag
    assert gov.authorize_grow("t", 128, 256, 16.0) is False
    assert gov.vetoes == 1
    assert gov._relief_wanted is True
    assert gov.ledger_total == 9_000  # refusal charges nothing
    # within budget: allowed, and the headroom is claimed immediately
    # so a second same-barrier grow cannot double-spend it
    assert gov.authorize_grow("t", 64, 128, 8.0) is True
    assert gov.ledger_total == 9_000 + 64 * 8
    assert gov.authorize_grow("u", 128, 256, 8.0) is False


def test_pressure_score_combines_memory_and_queue_age():
    gov = MemoryGovernor(budget_bytes=1_000)
    gov.queue_ms_budget = 1_000.0
    gov.ledger_total = 500

    class _Tr:
        backpressure = {"f": {"oldest_age_ms": 1_000.0}}

    # queue at budget lands ON the degrade threshold (same scale)
    assert gov._pressure_score(_Tr()) == pytest.approx(
        gov.ladder.degrade_at
    )
    _Tr.backpressure = {"f": {"oldest_age_ms": 0.0}}
    assert gov._pressure_score(_Tr()) == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# zero-row poll anchoring (exactly-once under parking)
# ---------------------------------------------------------------------------


class _CountingSource:
    def __init__(self):
        self.offset = 0
        self.splits = [type("S", (), {"split_id": "s0"})()]

    def discover(self):
        pass

    def poll(self, max_rows_per_split, capacity, only=None):
        n = int(max_rows_per_split)
        chunks = []
        while n > 0:
            take = min(n, capacity)
            cols = {
                "k": np.arange(
                    self.offset, self.offset + take, dtype=np.int64
                )
            }
            chunks.append(StreamChunk.from_numpy(cols, capacity))
            self.offset += take
            n -= take
        return chunks


def test_parked_source_polls_zero_rows_and_offsets_anchor():
    mgr = SourceManager()
    src = _CountingSource()
    mgr.register("bids", src)
    adm = AdmissionController()
    mgr.attach_admission(adm, {"bids": "frag"})

    adm.rederive(DEGRADED, 1.0, fragments=("frag",))
    assert mgr.poll("bids", max_rows_per_split=500, capacity=64) == []
    assert src.offset == 0  # anchored: the parked poll moved nothing
    assert adm.parked_polls == 1

    # credit recovers -> the SAME rows flow from the anchored offset
    for _ in range(4):
        adm.rederive(NORMAL, 0.0, fragments=("frag",))
    chunks = mgr.poll("bids", max_rows_per_split=500, capacity=64)
    assert chunks and src.offset == 500


def test_throttled_credit_scales_the_poll_window():
    mgr = SourceManager()
    src = _CountingSource()
    mgr.register("bids", src)
    adm = AdmissionController()
    mgr.attach_admission(adm, {"bids": "frag"})
    adm.rederive(THROTTLED, 0.8, fragments=("frag",))
    mgr.poll("bids", max_rows_per_split=1_000, capacity=64)
    assert src.offset == 500  # credit 0.5 halves the window


def test_ladder_constants_are_the_public_contract():
    assert LADDER == (NORMAL, THROTTLED, SHEDDING, DEGRADED)
