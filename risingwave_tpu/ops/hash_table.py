"""Device-resident open-addressing hash table — the state substrate.

Reference roles replaced:
- ``JoinHashMap`` (src/stream/src/executor/join/hash_join.rs:157)
- HashAgg's dirty-group map / ``AggGroupCache``
  (src/stream/src/executor/hash_agg.rs:49-62)
- GroupTopN's per-group cache (src/stream/src/executor/top_n/group_top_n.rs:63)

Those are CPU pointer-chasing hash maps; on TPU the equivalent must be a
*flat array program*: a power-of-two slot table in HBM, linear probing,
and a batched insert that resolves intra-chunk collisions without locks.

Insert algorithm ("scatter-claim-verify"): all rows probe in lockstep.
At probe step t each unresolved row computes its candidate slot
``(h + t) & mask``. Rows whose candidate already holds their fingerprint
resolve to it. Rows pointing at an EMPTY slot *claim* it with one scatter
(XLA picks an arbitrary winner per slot among duplicates); re-reading the
slot tells each row whether it (or a same-key twin) won — losers advance
to the next probe step. The loop is a ``lax.fori_loop`` with a static
bound, so the whole thing jits into one fused program with no
data-dependent shapes.

Keys are stored as fingerprints (two independent 32-bit hashes, see
ops/hashing.hash128) plus the raw key lanes for exact verification —
fingerprint match alone would admit false merges at ~2^-64 rates, but
exact lanes make collisions impossible, matching the reference's exact
`HashKey` equality (src/common/src/hash/key.rs).

Deletion marks slots TOMBSTONE; tombstones are *not* reusable by insert
within an epoch (they still break probe chains only at rehash), and the
host-side StateTable rebuilds/rehashes the table when live+tombstone load
crosses the resize threshold — the TPU analogue of the reference growing
its hash maps on the heap.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from risingwave_tpu.ops.hashing import hash128
from risingwave_tpu.trace import device_read, span

EMPTY = jnp.uint32(0)  # slot status: fingerprint 0 reserved for "empty"
TOMBSTONE_FLAG = 0x1  # bit in `status` lane

# Static probe bound. With load factor <= 0.5 the expected max probe
# length for linear probing is O(log n); 64 is comfortably beyond it for
# the table sizes we run (2^14..2^20) and keeps the fori_loop cheap.
MAX_PROBE = 64


@jax.tree_util.register_pytree_node_class
@dataclass
class HashTable:
    """A set of key slots; payload arrays live next to it, indexed by slot.

    Arrays (all length = capacity, power of two):
      fp1, fp2   uint32 fingerprints (fp1 == 0 means EMPTY slot)
      keys       (n_key_cols, capacity) raw key lanes for exact equality
      live       bool — True once inserted, False again when deleted
    """

    fp1: jnp.ndarray
    fp2: jnp.ndarray
    keys: Tuple[jnp.ndarray, ...]
    live: jnp.ndarray

    def tree_flatten(self):
        return ((self.fp1, self.fp2, self.keys, self.live), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def capacity(self) -> int:
        return self.fp1.shape[0]

    @staticmethod
    def create(capacity: int, key_dtypes: Sequence[jnp.dtype]) -> "HashTable":
        if capacity & (capacity - 1):
            raise ValueError("capacity must be a power of two")
        return HashTable(
            fp1=jnp.zeros(capacity, jnp.uint32),
            fp2=jnp.zeros(capacity, jnp.uint32),
            keys=tuple(jnp.zeros(capacity, d) for d in key_dtypes),
            live=jnp.zeros(capacity, jnp.bool_),
        )

    def occupancy(self) -> jnp.ndarray:
        """Slots ever claimed (live + tombstones) — drives host rehash."""
        return jnp.sum((self.fp1 != EMPTY).astype(jnp.int32))

    def num_live(self) -> jnp.ndarray:
        return jnp.sum(self.live.astype(jnp.int32))


def _keys_match(table: HashTable, slot: jnp.ndarray, key_cols) -> jnp.ndarray:
    ok = jnp.ones(slot.shape, jnp.bool_)
    for tk, k in zip(table.keys, key_cols):
        stored = tk[slot]
        eq = stored == k
        if jnp.issubdtype(tk.dtype, jnp.floating):
            # ordered-float total equality: NaN == NaN (reference treats
            # float keys via total ordering, src/common/src/types/). IEEE
            # `==` would make a NaN key unresolvable: it claims a slot,
            # fails its own verify, and re-claims forever — leaking
            # MAX_PROBE slots and returning -1 (a bogus rehash signal).
            eq |= jnp.isnan(stored) & jnp.isnan(k)
        ok &= eq
    return ok


def _probe_or_insert(table, key_cols, valid, insert_missing: bool):
    """The one probe loop behind ``lookup_or_insert`` and its counted
    twin: (table', slots, found, inserted, rounds), ``rounds`` the
    loop's own counter when it stopped."""
    cap = table.capacity
    mask = jnp.uint32(cap - 1)
    h1, h2 = hash128(key_cols)
    # fingerprint 0 is reserved for EMPTY: remap to 1
    fp1 = jnp.where(h1 == 0, jnp.uint32(1), h1)
    fp2 = h2

    n = valid.shape[0]
    slots = jnp.full(n, -1, jnp.int32)
    found = jnp.zeros(n, jnp.bool_)
    inserted = jnp.zeros(n, jnp.bool_)
    unresolved = valid
    # claim scratch is allocated ONCE and carried through the loop —
    # refilling O(capacity) per probe step would dominate the insert for
    # big tables. Entries are wiped after each election (O(n) scatter).
    claim = jnp.full(cap, n, jnp.int32)

    def body(t, carry):
        # (scopes inside the loop are relative to ``hash/probe``, which
        # the loop's own name stack begins with: ``hash/probe/match``)
        table, slots, found, inserted, unresolved, claim = carry
        cand = ((h1 + jnp.uint32(t)) & mask).astype(jnp.int32)

        with jax.named_scope("match"):
            slot_fp1 = table.fp1[cand]
            slot_fp2 = table.fp2[cand]
            is_empty = slot_fp1 == EMPTY
            fp_match = (slot_fp1 == fp1) & (slot_fp2 == fp2)
            exact = fp_match & _keys_match(table, cand, key_cols)

            # 1) resolve matches (live or tombstoned — caller reads `live`)
            hit = unresolved & exact
            slots = jnp.where(hit, cand, slots)
            found = found | (hit & table.live[cand])
            unresolved = unresolved & ~hit

        if insert_missing:
            # 2) elect ONE winner per contended empty slot with a single
            # scatter of the row index; the winner then writes fp + every
            # key lane uncontended. (Four independent scatters could pick
            # different winners per lane, leaving a torn chimera slot that
            # matches no key and leaks capacity.)
            # Index lanes are EXPLICIT int32 (rwlint RW-E30x dtype
            # audit): weak python-int sentinels must never promote the
            # probe arithmetic under a different default-int regime.
            with jax.named_scope("elect"):
                want = unresolved & is_empty
                idx = jnp.where(want, cand, jnp.int32(cap))  # cap = drop lane
                row_ids = jnp.arange(n, dtype=jnp.int32)
                claim = claim.at[idx].set(row_ids, mode="drop")
                won = want & (claim[cand] == row_ids)
                # wipe this round's entries so the scratch stays all-sentinel
                claim = claim.at[idx].set(n, mode="drop")
            with jax.named_scope("write"):
                widx = jnp.where(won, cand, jnp.int32(cap))
                new_fp1 = table.fp1.at[widx].set(fp1, mode="drop")
                new_fp2 = table.fp2.at[widx].set(fp2, mode="drop")
                new_keys = tuple(
                    tk.at[widx].set(k, mode="drop")
                    for tk, k in zip(table.keys, key_cols)
                )
                table = HashTable(new_fp1, new_fp2, new_keys, table.live)
            # 3) same-key twins of the winner resolve to the slot too
            with jax.named_scope("twins"):
                landed = (
                    want
                    & (table.fp1[cand] == fp1)
                    & (table.fp2[cand] == fp2)
                    & _keys_match(table, cand, key_cols)
                )
                slots = jnp.where(landed, cand, slots)
                inserted = inserted | landed
                unresolved = unresolved & ~landed
            # NOTE: a winner and its same-key twins all get `inserted`;
            # dedup is by first-occurrence masks downstream, slot identity
            # is what matters for correctness.

        # rows that neither matched nor claimed advance to probe t+1
        return table, slots, found, inserted, unresolved, claim

    # while_loop with early exit: at load <= 0.5 nearly every row
    # resolves within a handful of probes, and each probe step costs
    # ~a dozen gathers/scatters — running the full static MAX_PROBE
    # bound (fori_loop) made every insert pay 64 steps regardless
    # (observed 20-50x slowdowns on real TPU, BENCH_r02 fault analysis)
    def cond(carry):
        t = carry[0]
        unresolved = carry[5]
        return (t < MAX_PROBE) & jnp.any(unresolved)

    def wbody(carry):
        t, table, slots, found, inserted, unresolved, claim = carry
        table, slots, found, inserted, unresolved, claim = body(
            t, (table, slots, found, inserted, unresolved, claim)
        )
        return (t + 1, table, slots, found, inserted, unresolved, claim)

    with jax.named_scope("hash/probe"):
        rounds, table, slots, found, inserted, _, _ = jax.lax.while_loop(
            cond,
            wbody,
            (jnp.int32(0), table, slots, found, inserted, unresolved, claim),
        )
    return table, slots, found, inserted, rounds


@partial(jax.jit, static_argnames=("insert_missing",), donate_argnums=(0,))
def lookup_or_insert(
    table: HashTable,
    key_cols: Tuple[jnp.ndarray, ...],
    valid: jnp.ndarray,
    insert_missing: bool = True,
):
    """Batched find-or-insert. Returns (table', slots, found, inserted).

    slots[i] == -1 iff row i is invalid, or the key was absent and
    ``insert_missing`` is False, or the table overflowed MAX_PROBE
    (callers treat -1 slots of valid rows as an overflow signal and
    trigger a host-side rehash; see state/state_table.py).
    """
    return _probe_or_insert(table, key_cols, valid, insert_missing)[:4]


# what ``lookup_or_insert_counted`` says of one call, in this order
PROBE_STATS = ("rounds", "lane_rounds", "keys", "new_keys")


@partial(jax.jit, static_argnames=("insert_missing",), donate_argnums=(0,))
def lookup_or_insert_counted(
    table: HashTable,
    key_cols: Tuple[jnp.ndarray, ...],
    valid: jnp.ndarray,
    insert_missing: bool = True,
):
    """``lookup_or_insert`` over the same loop, which also says what the
    loop did: (table', slots, found, inserted, stats), ``stats`` an
    int32 vector of ``PROBE_STATS`` — the rounds the loop ran (the
    longest chain any row walked, + 1), rounds x the lanes every round
    ranged over, the valid rows, and those that claimed a slot or were
    a twin of one that did. An executor adds it to a vector it keeps on
    the device and reads it with its barrier's own status."""
    table, slots, found, inserted, rounds = _probe_or_insert(
        table, key_cols, valid, insert_missing
    )
    stats = jnp.stack([
        rounds,
        rounds * jnp.int32(valid.shape[0]),
        jnp.sum(valid, dtype=jnp.int32),
        jnp.sum(inserted, dtype=jnp.int32),
    ])
    return table, slots, found, inserted, stats


@jax.jit
def lookup(table: HashTable, key_cols, valid):
    """Read-only probe: returns (slots, found_live). slots -1 if absent."""
    cap = table.capacity
    mask = jnp.uint32(cap - 1)
    h1, h2 = hash128(key_cols)
    fp1 = jnp.where(h1 == 0, jnp.uint32(1), h1)
    fp2 = h2
    n = valid.shape[0]

    def body(t, carry):
        slots, found, unresolved = carry
        cand = ((h1 + jnp.uint32(t)) & mask).astype(jnp.int32)
        slot_fp1 = table.fp1[cand]
        exact = (
            (slot_fp1 == fp1)
            & (table.fp2[cand] == fp2)
            & _keys_match(table, cand, key_cols)
        )
        hit = unresolved & exact
        slots = jnp.where(hit, cand, slots)
        found = found | (hit & table.live[cand])
        # probe chain ends at a truly EMPTY slot -> key absent
        dead_end = unresolved & (slot_fp1 == EMPTY)
        unresolved = unresolved & ~hit & ~dead_end
        return slots, found, unresolved

    slots = jnp.full(n, -1, jnp.int32)
    found = jnp.zeros(n, jnp.bool_)

    def cond(carry):
        t, _, _, unresolved = carry
        return (t < MAX_PROBE) & jnp.any(unresolved)

    def wbody(carry):
        t, slots, found, unresolved = carry
        slots, found, unresolved = body(t, (slots, found, unresolved))
        return (t + 1, slots, found, unresolved)

    with jax.named_scope("hash/lookup"):
        _, slots, found, _ = jax.lax.while_loop(
            cond, wbody, (jnp.int32(0), slots, found, valid)
        )
    return slots, found


def set_live(table: HashTable, slots: jnp.ndarray, live_value: jnp.ndarray) -> HashTable:
    """Mark slots live/dead (dead = logical delete, slot stays claimed)."""
    cap = table.capacity
    with jax.named_scope("hash/set_live"):
        idx = jnp.where(slots >= 0, slots, jnp.int32(cap))
        new_live = table.live.at[idx].set(live_value, mode="drop")
    return HashTable(table.fp1, table.fp2, table.keys, new_live)


def note_probes(table, table_id, calls, stats, capacity, **more) -> None:
    """One record a barrier and counted table in the span ring: the span
    ``hash.probes`` (``table`` = which of the executor's tables,
    ``calls`` = the epoch's counted steps, ``stats`` = their
    ``PROBE_STATS`` summed, as the barrier's own status read brought
    them; ``capacity``, and ``claimed`` where the executor reads it).
    Nothing where no counted step ran."""
    if calls:
        with span(
            "hash.probes", table=table, table_id=table_id, calls=calls,
            **dict(zip(PROBE_STATS, map(int, stats))), capacity=capacity,
            **more,
        ):
            pass


def stage_scalars(*xs):
    """Pack scalars into one device array and START its async D2H copy
    (finish with ``finish_scalars``). Lets every executor's barrier
    read overlap in flight instead of paying a round-trip each."""
    arr = jnp.stack([jnp.asarray(x).astype(jnp.int64) for x in xs])
    try:
        arr.copy_to_host_async()
    except AttributeError:  # backend without async copies
        pass
    return arr


def finish_scalars(arr, what: str = "scalars") -> list:
    """Blocking counterpart: materialize a staged pack (the span
    ``device.read``; ``what`` names the read for a barrier's path).

    Uses ``jax.device_get`` — an EXPLICIT transfer — because this runs
    inside the per-barrier device step, which tests arm with
    ``jax.transfer_guard("disallow")`` (RW_TRANSFER_GUARD): the one
    sanctioned D2H read per barrier must not trip the guard that
    exists to catch the unsanctioned ones."""
    with device_read(what, lanes=arr.shape[0]):
        host = jax.device_get(arr)
    return host.tolist()


def read_scalars(*xs, what: str = "scalars") -> list:
    """ONE packed, blocking device->host read of several scalars
    (latches, occupancy counters) — stage + finish in one call."""
    return finish_scalars(stage_scalars(*xs), what)


def plan_rehash(
    cap: int, incoming: int, claimed: int, survivors: int, grow_at: float = 0.5
):
    """The shared growth policy behind every host-side ``_maybe_grow``
    (HashAgg / Dedup / HashJoin sides): given true occupancy, decide
    whether to rebuild and at what capacity.

    Returns None (no rebuild: the next chunk still fits under the load
    factor) or the new capacity — sized from ``survivors`` (what the
    rebuild will actually keep), NOT from pre-rebuild occupancy, so
    steady-state tombstone churn compacts in place instead of doubling
    forever. ``new_cap == cap`` is a pure tombstone compaction.
    """
    if claimed + incoming <= cap * grow_at:
        return None
    new_cap = cap
    while survivors + incoming > new_cap * grow_at:
        new_cap *= 2
    return new_cap


def last_occurrence_mask(slots: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """True for the LAST valid row of each distinct slot in the batch —
    pk-conflict "last write wins" (materialize.rs:192 Overwrite) needs a
    deterministic winner; XLA scatter picks an arbitrary one among
    duplicate indices."""
    return first_occurrence_mask(slots[::-1], valid[::-1])[::-1]


def first_occurrence_mask(slots: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """True for the first valid row of each distinct slot in the batch.

    Used to dedupe per-group work (e.g. one U-/U+ emission per group per
    chunk, mirroring the reference's per-barrier dirty-group flush,
    hash_agg.rs:406). Sort-based, shape-static.
    """
    n = slots.shape[0]
    order = jnp.argsort(
        jnp.where(valid & (slots >= 0), slots, jnp.int32(2**30)), stable=True
    )
    s_sorted = slots[order]
    v_sorted = (valid & (slots >= 0))[order]
    first_sorted = v_sorted & jnp.concatenate(
        [jnp.ones(1, jnp.bool_), s_sorted[1:] != s_sorted[:-1]]
    )
    mask = jnp.zeros(n, jnp.bool_).at[order].set(first_sorted)
    return mask
