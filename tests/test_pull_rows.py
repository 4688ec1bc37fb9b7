"""``pull_rows``: a piece's lanes leave the device as one array a
(dtype, row shape) they share, every copy started before the first is
awaited. The rule it replaced — one gather and one copy a lane,
``np.asarray(lane)[sel]`` — stays here as the reference, and what
arrives has to equal it bit for bit, dtype and shape kept."""

import jax.numpy as jnp
import numpy as np
import pytest

from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.array.lattice import (
    DELTA_BLOCK,
    DELTA_SMALL,
    delta_blocks,
)
from risingwave_tpu.storage import state_table
from risingwave_tpu.storage.state_table import classify_marks, pull_rows
from risingwave_tpu.trace import TRACER

CAP, FANOUT = 1 << 14, 4
COUNTS = [
    0, 1, DELTA_SMALL - 1, DELTA_SMALL, DELTA_SMALL + 1,
    DELTA_BLOCK, DELTA_BLOCK + 1, 3 * DELTA_BLOCK + 5,
]


def _mixed_lanes(rng):
    """Lanes of every type a table keeps, with the values a cast or a
    trip through another type would lose."""
    i64 = rng.integers(-(2**62), 2**62, CAP)
    i64[:4] = [2**53 + 1, -(2**53) - 1, np.iinfo(np.int64).max,
               np.iinfo(np.int64).min]
    f64 = rng.standard_normal(CAP)
    f64[:4] = [-0.0, np.inf, -np.inf, 0.0]
    f64 = f64.view(np.uint64)
    f64[4:8] = [0x7FF8_0000_DEAD_BEEF, 0xFFF0_0000_0000_0001,
                0x7FF0_0000_0000_0001, 0x0000_0000_0000_0001]
    f32 = rng.standard_normal(CAP).astype(np.float32)
    f32[:3] = [-0.0, np.inf, -np.inf]
    f32 = f32.view(np.uint32)
    f32[3:6] = [0x7FC0_BEEF, 0xFF80_0001, 0x0000_0001]
    return {
        "k0": i64,
        "k1": rng.integers(-(2**31), 2**31, CAP).astype(np.int32),
        "fp": rng.integers(0, 2**32, CAP).astype(np.uint32),
        "r_price": f64.view(np.float64),
        "r_ratio": f32.view(np.float32),
        "live": rng.random(CAP) < 0.5,
        "r_more": rng.integers(-(2**62), 2**62, CAP),
        "bucket": rng.integers(-(2**62), 2**62, (CAP, FANOUT)),
        "bucket_valid": rng.random((CAP, FANOUT)) < 0.5,
        "deg": rng.integers(0, 2**31, (CAP, FANOUT)).astype(np.int32),
    }


def _chosen(rng, n):
    """``n`` slots ascending, the lanes' first (the special values) among
    them where ``n`` holds them."""
    first = np.arange(min(n, 8))
    rest = 8 + rng.choice(CAP - 8, n - len(first), replace=False)
    return np.sort(np.concatenate([first, rest])).astype(np.int64)


def _as_marks(slots):
    sdirty = np.zeros(CAP, bool)
    sdirty[slots] = True
    marks = classify_marks(
        jnp.asarray(sdirty), jnp.ones(CAP, jnp.bool_),
        jnp.zeros(CAP, jnp.bool_),
    )
    assert len(marks) == len(slots)
    return marks


def _same_bits(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = np.ascontiguousarray(got), np.ascontiguousarray(want)
    assert got.view(np.uint8).tobytes() == want.view(np.uint8).tobytes()


@pytest.mark.parametrize("table_id", [None, "t.pull"])
@pytest.mark.parametrize("by", ["marks", "host_slots"])
@pytest.mark.parametrize("n", COUNTS)
def test_the_pull_equals_the_lane_by_lane_rule_bit_for_bit(n, by, table_id):
    rng = np.random.default_rng(n)
    host = _mixed_lanes(rng)
    lanes = {k: jnp.asarray(a) for k, a in host.items()}
    slots = _chosen(rng, n)
    sel = _as_marks(slots) if by == "marks" else slots
    rows = REGISTRY.counter("checkpoint_pull_rows_total")
    before = rows.total()
    got = pull_rows(lanes, sel, table_id)
    assert list(got) == list(host)  # the lanes' order is the caller's
    for k, a in host.items():
        assert lanes[k].dtype == a.dtype  # nothing widened on the way up
        _same_bits(got[k], a[slots])
    assert rows.total() - before == (n if table_id else 0)


def _pulls(table_id):
    return [
        sp for sp in TRACER.spans()
        if sp.name == "checkpoint.pull" and sp.args["table_id"] == table_id
    ]


def test_copies_are_pieces_by_groups_and_two_programs_a_lane_set():
    """A Top-N store of q9's shape (3 key lanes and 16 row lanes: 16
    int64, 3 int32) leaves in two arrays a piece, not nineteen; and as
    the count crosses 256 and 4,096 the lane set compiles its two gather
    programs and no third."""
    rng = np.random.default_rng(19)
    lanes = {f"k{i}": rng.integers(0, 2**31, CAP).astype(np.int32)
             for i in range(3)}
    lanes.update({f"r_{i}": rng.integers(-(2**62), 2**62, CAP)
                  for i in range(16)})
    device = {k: jnp.asarray(a) for k, a in lanes.items()}
    copies = REGISTRY.counter("checkpoint_pull_copies_total")
    programs = state_table._gather._cache_size()
    TRACER.clear()
    counts = [1, DELTA_SMALL, DELTA_SMALL + 1, DELTA_BLOCK,
              DELTA_BLOCK + 1, 3 * DELTA_BLOCK + 5, 7]
    before = copies.total()
    for n in counts:
        slots = _chosen(rng, n)
        got = pull_rows(device, slots, "t.topn")
        _same_bits(got["r_7"], lanes["r_7"][slots])
    assert state_table._gather._cache_size() - programs == 2
    spans = _pulls("t.topn")
    assert len(spans) == len(counts)
    for n, sp in zip(counts, spans):
        block, pieces = delta_blocks(n)
        assert sp.args["rows"] == n
        assert sp.args["padded_rows"] == block * pieces
        assert sp.args["copies"] == 2 * pieces < len(lanes) * pieces
        reads = [
            c for c in TRACER.spans()
            if c.name == "device.read" and c.parent == sp.sid
        ]
        assert [c.args["what"] for c in reads] == ["pull_rows"]
        # what the one read waited for is what the pieces hold, padded
        assert reads[0].args["bytes"] == block * pieces * (16 * 8 + 3 * 4)
    assert copies.total() - before == sum(sp.args["copies"] for sp in spans)
    # a read that is no checkpoint is counted under no table
    before = copies.total()
    pull_rows(device, _chosen(rng, 300))
    assert copies.total() == before and len(_pulls("t.topn")) == len(counts)


def test_a_bucket_sides_two_dimensional_lanes_are_groups_of_their_own():
    rng = np.random.default_rng(8)
    host = _mixed_lanes(rng)
    device = {k: jnp.asarray(a) for k, a in host.items()}
    TRACER.clear()
    slots = _chosen(rng, DELTA_BLOCK + 1)
    got = pull_rows(device, slots, "t.join")
    assert got["bucket"].shape == (len(slots), FANOUT)
    (sp,) = _pulls("t.join")
    # int64, int32, uint32, float64, float32, bool flat; int64, bool,
    # int32 by (capacity, fanout): nine arrays a piece for ten lanes
    assert sp.args["copies"] == 9 * 2
