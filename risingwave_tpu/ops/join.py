"""Two-sided streaming join state kernel — the core of HashJoin.

Reference roles replaced:
- ``JoinHashMap`` — per-key row lists with cached entry state
  (src/stream/src/executor/join/hash_join.rs:157);
- the per-row probe/emit loop of ``hash_eq_match`` / ``execute_inner``
  (src/stream/src/executor/hash_join.rs:462-729).

The reference keeps, per join key, a heap ``Vec`` of rows (plus degree
counters) behind an LRU cache over a state table. On TPU the state must
be a flat array program, so a join side is TWO levels of static arrays:

    key table  : ops/hash_table.HashTable over the join-key lanes —
                 maps a key to a slot s in [0, capacity)
    row buckets: per payload column, a (capacity, fanout) array;
                 bucket s holds every live row whose key owns slot s,
                 with a (capacity, fanout) ``row_valid`` mask

Insert scatters each row into the first free bucket position; delete
finds the matching stored row (exact multi-column equality, NULL==NULL)
and clears it; probe gathers the *other* side's whole bucket per probe
row — a (chunk, fanout) gather — and emits one output pair per live
match. All three are batched over the chunk with no host round trips,
and intra-chunk collisions (two rows of one key in one chunk) are
resolved by an O(n log n) intra-chunk rank, not a serial loop.

Fanout is the static per-key row bound (the reference's Vec grows on
the heap; we latch ``overflow`` and the host executor rebuilds with a
doubled fanout — same contract as hash-table growth). Inner joins need
no degree state; degrees for outer joins ride the same bucket layout as
an extra int lane when those join types land.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from risingwave_tpu.ops.hash_table import (
    HashTable,
    lookup,
    lookup_or_insert,
    set_live,
)
from risingwave_tpu.ops.hashing import hash128


@jax.tree_util.register_pytree_node_class
@dataclass
class JoinSide:
    """One side's state: key table + row buckets (see module doc).

    ``rows``/``row_nulls`` map payload column name -> (capacity, fanout)
    arrays; ``row_valid`` marks occupied bucket entries. ``overflow``
    latches bucket exhaustion; ``inconsistent`` latches a delete that
    matched no stored row (the reference's consistency sanity check,
    src/stream/src/executor/mod.rs update_check wrapper).
    """

    table: HashTable
    rows: Dict[str, jnp.ndarray]
    row_nulls: Dict[str, jnp.ndarray]
    row_valid: jnp.ndarray
    overflow: jnp.ndarray  # () bool
    inconsistent: jnp.ndarray  # () bool
    sdirty: jnp.ndarray  # (capacity,) bool — changed since last checkpoint
    stored: jnp.ndarray  # (capacity,) bool — persisted in the object store
    degree: jnp.ndarray  # (capacity, fanout) int32 — matches on other side

    def tree_flatten(self):
        names = tuple(sorted(self.rows))
        null_names = tuple(sorted(self.row_nulls))
        children = (
            self.table,
            tuple(self.rows[n] for n in names),
            tuple(self.row_nulls[n] for n in null_names),
            self.row_valid,
            self.overflow,
            self.inconsistent,
            self.sdirty,
            self.stored,
            self.degree,
        )
        return children, (names, null_names)

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, null_names = aux
        (table, rows, nulls, row_valid, overflow, inconsistent, sdirty,
         stored, degree) = children
        return cls(
            table=table,
            rows=dict(zip(names, rows)),
            row_nulls=dict(zip(null_names, nulls)),
            row_valid=row_valid,
            overflow=overflow,
            inconsistent=inconsistent,
            sdirty=sdirty,
            stored=stored,
            degree=degree,
        )

    @property
    def capacity(self) -> int:
        return self.row_valid.shape[0]

    @property
    def fanout(self) -> int:
        return self.row_valid.shape[1]

    @staticmethod
    def create(
        capacity: int,
        fanout: int,
        key_dtypes: Sequence[jnp.dtype],
        payload_dtypes: Dict[str, jnp.dtype],
        nullable: Sequence[str] = (),
    ) -> "JoinSide":
        return JoinSide(
            table=HashTable.create(capacity, key_dtypes),
            rows={
                n: jnp.zeros((capacity, fanout), d)
                for n, d in payload_dtypes.items()
            },
            row_nulls={
                n: jnp.zeros((capacity, fanout), jnp.bool_) for n in nullable
            },
            row_valid=jnp.zeros((capacity, fanout), jnp.bool_),
            overflow=jnp.zeros((), jnp.bool_),
            inconsistent=jnp.zeros((), jnp.bool_),
            sdirty=jnp.zeros(capacity, jnp.bool_),
            stored=jnp.zeros(capacity, jnp.bool_),
            degree=jnp.zeros((capacity, fanout), jnp.int32),
        )


def _intra_chunk_rank(
    slots: jnp.ndarray, h1: jnp.ndarray, h2: jnp.ndarray, m: jnp.ndarray
) -> jnp.ndarray:
    """rank[i] = #earlier masked rows with the same (slot, h1, h2).

    Insert ranking passes constant h1/h2 (group by SLOT alone: every
    insert into a bucket needs a distinct free position, whatever its
    content); delete ranking passes the row fingerprint (identical
    delete rows clear distinct matching entries, while distinct rows
    sharing a bucket rank independently against their own matches).
    Sort-based, shape-static; stable so ranks follow chunk order.
    """
    n = slots.shape[0]
    big = jnp.int64(1) << 62
    with jax.named_scope("x64/combine"):
        key = (
            slots.astype(jnp.int64) << jnp.int64(32)
            | h1.astype(jnp.int64)
        )
    key = jnp.where(m, key, big)
    # lexsort by (h2, composite) — h2 breaks 32-bit h1 ties
    order = jnp.lexsort((h2.astype(jnp.int64), key))
    k_sorted = key[order]
    h2_sorted = h2[order]
    seq = jnp.arange(n, dtype=jnp.int32)
    is_new = jnp.concatenate(
        [
            jnp.ones(1, jnp.bool_),
            (k_sorted[1:] != k_sorted[:-1]) | (h2_sorted[1:] != h2_sorted[:-1]),
        ]
    )
    # start index of each run, propagated forward (starts are increasing)
    start = jnp.where(is_new, seq, jnp.int32(0))
    start = jax.lax.associative_scan(jnp.maximum, start)
    rank_sorted = seq - start
    return jnp.zeros(n, jnp.int32).at[order].set(rank_sorted)


def _row_fingerprint(payload_cols, payload_nulls, names):
    """64 bits over all payload lanes (values canonicalized under NULL)
    — only used to RANK same-bucket rows; equality stays exact."""
    lanes = []
    for name in names:
        col = payload_cols[name]
        null = payload_nulls.get(name)
        if null is not None:
            col = jnp.where(null, jnp.zeros((), col.dtype), col)
            lanes.append(null)
        lanes.append(col)
    return hash128(tuple(lanes))


def _entry_matches(side: JoinSide, slots, payload_cols, payload_nulls, names):
    """(n, fanout) exact row equality against bucket entries (NULL==NULL)."""
    sl = jnp.maximum(slots, 0)
    ok = side.row_valid[sl]
    for name in names:
        stored = side.rows[name][sl]  # (n, fanout)
        val = payload_cols[name][:, None]
        eq = stored == val
        if jnp.issubdtype(stored.dtype, jnp.floating):
            eq |= jnp.isnan(stored) & jnp.isnan(val)
        snull = side.row_nulls.get(name)
        if snull is not None:
            stored_null = snull[sl]
            row_null = payload_nulls.get(name)
            if row_null is None:
                row_null = jnp.zeros(val.shape, jnp.bool_)
            else:
                row_null = row_null[:, None]
            eq = jnp.where(stored_null | row_null, stored_null == row_null, eq)
        ok &= eq
    return ok


@jax.named_scope("join/bucket/apply_side")
def apply_side(
    side: JoinSide,
    key_cols: Tuple[jnp.ndarray, ...],
    payload_cols: Dict[str, jnp.ndarray],
    payload_nulls: Dict[str, jnp.ndarray],
    valid: jnp.ndarray,
    signs: jnp.ndarray,
    names: Tuple[str, ...],
    init_degree: Optional[jnp.ndarray] = None,
):
    """Apply one chunk to its own side: inserts then deletes.

    ``signs``: +1 insert / -1 delete per row (0 = skip). Rows are
    multiset entries; inserts fill the first free bucket positions,
    deletes clear the rank-th matching entry (so an insert+delete of
    the same row in one chunk nets out). ``init_degree`` (outer joins)
    seeds each inserted row's degree — its current match count on the
    other side (reference degree table, join/hash_join.rs:157).
    Returns the updated side.
    """
    ins = valid & (signs > 0)
    dele = valid & (signs < 0)
    touch = ins | dele

    # slot per row (deletes of absent keys fall through to inconsistent)
    table, slots, _, _ = lookup_or_insert(side.table, key_cols, touch)
    sdirty = side.sdirty.at[
        jnp.where(touch & (slots >= 0), slots, side.capacity)
    ].set(True, mode="drop")
    side = JoinSide(
        table, side.rows, side.row_nulls, side.row_valid,
        side.overflow | jnp.any(touch & (slots < 0)), side.inconsistent,
        sdirty, side.stored, side.degree,
    )

    h1, h2 = _row_fingerprint(payload_cols, payload_nulls, names)
    cap, fanout = side.capacity, side.fanout
    n = valid.shape[0]
    sl = jnp.maximum(slots, 0)

    # ---- inserts: rank-th free position in the bucket (rank by slot
    # only — ANY two inserts into one bucket need distinct positions) --
    zero = jnp.zeros_like(h1)
    rank_i = _intra_chunk_rank(slots, zero, zero, ins)
    bv = side.row_valid[sl]  # (n, fanout)
    free_rank = jnp.cumsum((~bv).astype(jnp.int32), axis=1)
    one_hot = (~bv) & (free_rank == (rank_i + 1)[:, None]) & ins[:, None]
    pos = jnp.argmax(one_hot, axis=1).astype(jnp.int32)
    placed = jnp.any(one_hot, axis=1) & ins & (slots >= 0)
    overflow = side.overflow | jnp.any(ins & (slots >= 0) & ~placed)

    flat_idx = jnp.where(placed, sl * fanout + pos, cap * fanout)
    rows = {
        name: side.rows[name]
        .reshape(-1)
        .at[flat_idx]
        .set(payload_cols[name], mode="drop")
        .reshape(cap, fanout)
        for name in names
    }
    row_nulls = {}
    for name, lane in side.row_nulls.items():
        src = payload_nulls.get(name)
        if src is None:
            src = jnp.zeros(n, jnp.bool_)
        row_nulls[name] = (
            lane.reshape(-1).at[flat_idx].set(src, mode="drop").reshape(cap, fanout)
        )
    row_valid = (
        side.row_valid.reshape(-1)
        .at[flat_idx]
        .set(True, mode="drop")
        .reshape(cap, fanout)
    )
    deg0 = (
        init_degree.astype(jnp.int32)
        if init_degree is not None
        else jnp.zeros(n, jnp.int32)
    )
    degree = (
        side.degree.reshape(-1)
        .at[flat_idx]
        .set(deg0, mode="drop")
        .reshape(cap, fanout)
    )
    side = JoinSide(
        side.table, rows, row_nulls, row_valid, overflow, side.inconsistent,
        side.sdirty, side.stored, degree,
    )

    # ---- deletes: rank-th matching entry -------------------------------
    rank_d = _intra_chunk_rank(slots, h1, h2, dele)
    match = _entry_matches(side, slots, payload_cols, payload_nulls, names)
    match = match & dele[:, None] & (slots >= 0)[:, None]
    mrank = jnp.cumsum(match.astype(jnp.int32), axis=1)
    one_hot_d = match & (mrank == (rank_d + 1)[:, None])
    dpos = jnp.argmax(one_hot_d, axis=1).astype(jnp.int32)
    hit = jnp.any(one_hot_d, axis=1)
    inconsistent = side.inconsistent | jnp.any(dele & (slots >= 0) & ~hit)

    dflat = jnp.where(hit, sl * fanout + dpos, cap * fanout)
    row_valid = (
        side.row_valid.reshape(-1)
        .at[dflat]
        .set(False, mode="drop")
        .reshape(cap, fanout)
    )
    degree = (
        side.degree.reshape(-1)
        .at[dflat]
        .set(jnp.int32(0), mode="drop")
        .reshape(cap, fanout)
    )

    # key liveness = bucket non-empty (drives rehash survival + probes)
    touched_slots = jnp.where(touch & (slots >= 0), slots, -1)
    any_live = jnp.any(row_valid[sl], axis=1)
    table = set_live(side.table, touched_slots, any_live)
    return JoinSide(
        table, side.rows, side.row_nulls, row_valid, side.overflow,
        inconsistent, side.sdirty, side.stored, degree,
    )


@jax.named_scope("join/bucket/degree")
def degree_apply(
    other: JoinSide,
    match: jnp.ndarray,  # (n, fanout) live matches of this chunk's rows
    sl: jnp.ndarray,  # (n,) probed slots (clamped >= 0)
    signs: jnp.ndarray,  # (n,) ±1/0 per probe row
):
    """Bump the OTHER side's per-row degrees by this chunk's net effect
    and report transitions (reference: degree table updates inside
    hash_eq_match, join/hash_join.rs).

    Returns ``(other', trans_pid, went_pos, went_zero)``:
      trans_pid   (n*fanout,) int32 — flat (slot*fanout+pos) id of each
                  DISTINCT matched stored row, on representative lanes;
                  sentinel cap*fanout elsewhere
      went_pos    bool — degree crossed 0 -> >0 (matched for the first
                  time: outer joins retract the NULL-padded row)
      went_zero   bool — degree crossed >0 -> 0 (NULL-pad comes back)
    """
    cap, fanout = other.capacity, other.fanout
    n = match.shape[0]
    sent = jnp.int32(cap * fanout)
    pos_j = jnp.arange(fanout, dtype=jnp.int32)[None, :]
    pid = jnp.where(match, sl[:, None] * fanout + pos_j, sent).reshape(-1)
    delta = jnp.broadcast_to(signs[:, None], (n, fanout)).reshape(-1)
    delta = jnp.where(pid != sent, delta, 0).astype(jnp.int32)

    # distinct pids via sort + segment sum (multiple probe rows can hit
    # the same stored row in one chunk; the TRANSITION is per stored
    # row, over the chunk's net delta)
    spid, sdelta = jax.lax.sort((pid, delta), num_keys=1)
    boundary = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), spid[1:] != spid[:-1]]
    )
    seg_id = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    net = jax.ops.segment_sum(
        sdelta, seg_id, num_segments=spid.shape[0]
    )[seg_id]
    rep = boundary & (spid != sent)

    flat_deg = other.degree.reshape(-1)
    old = flat_deg[jnp.minimum(spid, sent - 1)]
    upd_idx = jnp.where(rep, spid, sent)
    new_flat = flat_deg.at[upd_idx].add(jnp.where(rep, net, 0), mode="drop")
    other = JoinSide(
        other.table, other.rows, other.row_nulls, other.row_valid,
        other.overflow, other.inconsistent, other.sdirty, other.stored,
        new_flat.reshape(cap, fanout),
    )
    new = old + net
    went_pos = rep & (old == 0) & (new > 0)
    went_zero = rep & (old > 0) & (new <= 0)
    trans_pid = jnp.where(rep, spid, sent)
    return other, trans_pid, went_pos, went_zero


@jax.named_scope("join/bucket/emit")
def gather_flat(
    side: JoinSide, pid: jnp.ndarray, names: Sequence[str]
) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
    """Gather payload at flat (slot*fanout+pos) ids (sentinel-safe)."""
    cap, fanout = side.capacity, side.fanout
    safe = jnp.minimum(pid, cap * fanout - 1)
    cols = {n: side.rows[n].reshape(-1)[safe] for n in names}
    nulls = {
        n: lane.reshape(-1)[safe] for n, lane in side.row_nulls.items()
    }
    return cols, nulls


@jax.named_scope("join/bucket/probe")
def probe_side(
    other: JoinSide,
    key_cols: Tuple[jnp.ndarray, ...],
    valid: jnp.ndarray,
):
    """Probe the other side: returns (slots, match) where match is the
    (n, fanout) mask of live stored rows joining each probe row."""
    slots, found = lookup(other.table, key_cols, valid)
    sl = jnp.maximum(slots, 0)
    match = other.row_valid[sl] & (found & valid)[:, None]
    return sl, match


@jax.named_scope("join/bucket/probe")
def gather_matches(
    other: JoinSide, sl: jnp.ndarray, names: Sequence[str]
) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
    """Gather (n, fanout) bucket payloads for probed slots."""
    cols = {n: other.rows[n][sl] for n in names}
    nulls = {n: lane[sl] for n, lane in other.row_nulls.items()}
    return cols, nulls


@jax.named_scope("join/bucket/emit")
def compact_pairs(
    flat_cols: Dict[str, jnp.ndarray],
    flat_nulls: Dict[str, jnp.ndarray],
    flat_ops: jnp.ndarray,
    flat_valid: jnp.ndarray,
    out_cap: int,
):
    """Compact sparse (n*fanout) join pairs into a fixed out_cap chunk.

    Returns (cols, nulls, ops, valid, overflow). Order-stable: pair i
    lands before pair j if i < j (cumsum positions), matching the
    reference's emission order per probe chunk.
    """
    pos = jnp.cumsum(flat_valid.astype(jnp.int32)) - 1
    overflow = jnp.any(flat_valid & (pos >= out_cap))
    idx = jnp.where(flat_valid & (pos < out_cap), pos, out_cap)

    def scatter(src, dtype=None):
        buf = jnp.zeros(out_cap, dtype or src.dtype)
        return buf.at[idx].set(src, mode="drop")

    cols = {n: scatter(a) for n, a in flat_cols.items()}
    nulls = {n: scatter(a) for n, a in flat_nulls.items()}
    ops = scatter(flat_ops)
    valid = jnp.zeros(out_cap, jnp.bool_).at[idx].set(flat_valid, mode="drop")
    return cols, nulls, ops, valid, overflow


@partial(jax.jit, static_argnames=("new_cap", "new_fanout"))
def regrow(side: JoinSide, new_cap: int, new_fanout: int) -> JoinSide:
    """Rebuild into a larger table and/or wider buckets, dropping
    tombstoned keys and compacting bucket holes (the heap-growth
    analogue; cf. executors/hash_agg._rehash)."""
    cap, fanout = side.capacity, side.fanout
    # live keys survive; sdirty dead keys survive too (the next
    # checkpoint needs their key lanes to write tombstones)
    keep = (side.table.live | side.sdirty) & (side.table.fp1 != jnp.uint32(0))

    new_table = HashTable.create(new_cap, tuple(k.dtype for k in side.table.keys))
    new_table, new_slots, _, _ = lookup_or_insert(new_table, side.table.keys, keep)
    new_table = set_live(
        new_table, jnp.where(keep, new_slots, -1), side.table.live
    )
    nidx = jnp.where(keep, new_slots, new_cap)
    new_sdirty = jnp.zeros(new_cap, jnp.bool_).at[nidx].set(
        side.sdirty, mode="drop"
    )
    new_stored = jnp.zeros(new_cap, jnp.bool_).at[nidx].set(
        side.stored, mode="drop"
    )

    # compact each bucket's live entries to the front of the new bucket
    entry_pos = jnp.cumsum(side.row_valid.astype(jnp.int32), axis=1) - 1
    entry_ok = side.row_valid & keep[:, None] & (entry_pos < new_fanout)
    dest_slot = jnp.broadcast_to(new_slots[:, None], (cap, fanout))
    flat_idx = jnp.where(
        entry_ok,
        dest_slot * new_fanout + entry_pos,
        new_cap * new_fanout,
    ).reshape(-1)

    def move(src, dtype):
        buf = jnp.zeros(new_cap * new_fanout, dtype)
        return (
            buf.at[flat_idx].set(src.reshape(-1), mode="drop")
            .reshape(new_cap, new_fanout)
        )

    rows = {n: move(a, a.dtype) for n, a in side.rows.items()}
    row_nulls = {n: move(a, jnp.bool_) for n, a in side.row_nulls.items()}
    row_valid = move(side.row_valid & entry_ok, jnp.bool_)
    degree = move(side.degree, jnp.int32)
    return JoinSide(
        new_table, rows, row_nulls, row_valid, side.overflow,
        side.inconsistent, new_sdirty, new_stored, degree,
    )


@partial(jax.jit, static_argnames=("key_index",))
def expire_keys(side: JoinSide, key_index: int, cutoff: jnp.ndarray) -> JoinSide:
    """Watermark state cleaning: drop every key whose key lane
    ``key_index`` < cutoff (reference: state cleaning via table
    watermarks, state_table.rs:1133 + skip_watermark.rs)."""
    lane = side.table.keys[key_index]
    expired = side.table.live & (lane < cutoff)
    slots = jnp.where(expired, jnp.arange(side.capacity, dtype=jnp.int32), -1)
    table = set_live(side.table, slots, False)
    row_valid = side.row_valid & ~expired[:, None]
    degree = jnp.where(expired[:, None], jnp.int32(0), side.degree)
    return JoinSide(
        table, side.rows, side.row_nulls, row_valid, side.overflow,
        side.inconsistent, side.sdirty | expired, side.stored, degree,
    )


# ---------------------------------------------------------------------------
# Flat sides: one lane a row, under the side's own stream key
# ---------------------------------------------------------------------------
# The bucket layout above bounds the rows of one join key by ``fanout``.
# An updating stream joined on a coarser key than its own (counts per
# (window, auction) joined on the window alone) has neither a bound on
# the rows of one key nor any use for buckets: an update names its row
# by the stream key, so the row is rewritten in place by a fan-out-1
# lookup. A ``FlatSide`` is a HashTable over the stream key with every
# column of the row beside it, and a lane of NULL flags for each column
# that has been seen to carry one. A side that is unique per join key (its
# stream key lies within the join key) is probed by the same lookup;
# the many side is probed by a masked scan of its lanes, one pass per
# changed row of the unique side.


@jax.tree_util.register_pytree_node_class
@dataclass
class FlatSide:
    table: HashTable  # over the stream key; ``live`` marks stored rows
    rows: Dict[str, jnp.ndarray]  # column -> (capacity,)
    row_nulls: Dict[str, jnp.ndarray]  # nullable column -> (capacity,) NULL
    sdirty: jnp.ndarray  # changed since the last checkpoint
    stored: jnp.ndarray  # in the checkpoint store
    dropped: jnp.ndarray  # () latch: the table overflowed MAX_PROBE

    def tree_flatten(self):
        names = tuple(sorted(self.rows))
        null_names = tuple(sorted(self.row_nulls))
        return (
            (self.table, tuple(self.rows[n] for n in names),
             tuple(self.row_nulls[n] for n in null_names), self.sdirty,
             self.stored, self.dropped),
            (names, null_names),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, null_names = aux
        table, rows, nulls, sdirty, stored, dropped = children
        return cls(
            table, dict(zip(names, rows)), dict(zip(null_names, nulls)),
            sdirty, stored, dropped,
        )

    @property
    def capacity(self) -> int:
        return self.table.capacity

    @staticmethod
    def create(
        capacity: int, pk_dtypes, row_dtypes: Dict[str, object], nullable=()
    ):
        return FlatSide(
            HashTable.create(capacity, pk_dtypes),
            {n: jnp.zeros(capacity, d) for n, d in row_dtypes.items()},
            {n: jnp.zeros(capacity, jnp.bool_) for n in nullable},
            jnp.zeros(capacity, jnp.bool_),
            jnp.zeros(capacity, jnp.bool_),
            jnp.zeros((), jnp.bool_),
        )

    def with_null_lanes(self, names) -> "FlatSide":
        """Null lanes (no row NULL yet) for columns that had none."""
        lanes = {n: jnp.zeros(self.capacity, jnp.bool_) for n in names}
        return replace(self, row_nulls={**lanes, **self.row_nulls})


def _not_null(mask, nulls, names):
    """``mask`` without the lanes where one of ``names`` is NULL."""
    for n in names:
        null = nulls.get(n)
        if null is not None:
            mask = mask & ~null
    return mask


@jax.named_scope("join/keyed/upsert")
def _flat_upsert(side: FlatSide, chunk, pk: Tuple[str, ...]):
    """The chunk's rows applied to their lanes in order: the last row
    of a stream key wins (an insert stores it, a delete clears it). A
    row with a NULL in its stream key is not stored. Returns (side',
    slots, ok)."""
    from risingwave_tpu.ops.hash_table import last_occurrence_mask

    valid = _not_null(chunk.valid, chunk.nulls, pk)
    table, slots, _, _ = lookup_or_insert(
        side.table, tuple(chunk.col(k) for k in pk), valid
    )
    ok = valid & (slots >= 0)
    dropped = side.dropped | jnp.any(valid & (slots < 0))
    last = last_occurrence_mask(slots, ok)
    idx = jnp.where(last, slots, jnp.int32(table.capacity))
    rows = {
        n: a.at[idx].set(chunk.col(n).astype(a.dtype), mode="drop")
        for n, a in side.rows.items()
    }
    no_null = jnp.zeros(chunk.valid.shape, jnp.bool_)
    row_nulls = {
        n: a.at[idx].set(chunk.nulls.get(n, no_null), mode="drop")
        for n, a in side.row_nulls.items()
    }
    table = set_live(table, jnp.where(last, slots, -1), chunk.signs() > 0)
    sdirty = side.sdirty.at[idx].set(True, mode="drop")
    return (
        FlatSide(table, rows, row_nulls, sdirty, side.stored, dropped),
        slots, ok,
    )


def _keep_pairs(cond, cols, nulls, hit):
    """The pairs among ``hit`` that the residual predicate keeps (a
    predicate that is NULL keeps nothing)."""
    if cond is None:
        return hit
    from risingwave_tpu.array.chunk import DataChunk

    v, null = cond.value.eval(DataChunk(cols, hit, nulls))
    keep = hit & v.astype(jnp.bool_)
    return keep if null is None else keep & ~null


def _first_set(mask, size: int):
    """(lanes of the first ``size`` set bits of ``mask``, in order; how
    many bits are set). A prefix sum and ``size`` binary searches:
    ``jnp.nonzero`` scatter-adds every lane of the mask, which on the
    chip cost 0.25 s for 2^22 lanes where this costs a few ms."""
    csum = jnp.cumsum(mask.astype(jnp.int32))
    at = jnp.searchsorted(
        csum, jnp.arange(1, size + 1, dtype=jnp.int32), side="left"
    )
    return jnp.minimum(at, mask.shape[0] - 1).astype(jnp.int32), csum[-1]


def _compact(cols, nulls, ops, keep):
    """Kept rows moved to the front in their order (a host pull then
    copies a prefix, not the capacity)."""
    n = keep.shape[0]
    at, count = _first_set(keep, n)
    valid = jnp.arange(n, dtype=jnp.int32) < count
    return (
        {k: a[at] for k, a in cols.items()},
        {k: a[at] for k, a in nulls.items()},
        ops[at], valid,
    )


def flat_many_step(
    many: FlatSide,
    unique: FlatSide,
    chunk,
    many_pk: Tuple[str, ...],
    unique_pk_from: Tuple[str, ...],
    key_pairs: Tuple[Tuple[str, str], ...],
    cond,
):
    """A chunk of the many side: every row looks its join key up on the
    unique side (at most one match), the pair passes the residual or
    not, and the row is stored under its own stream key.
    ``unique_pk_from`` names, per column of the unique side's stream
    key, the many side's column it is joined to; ``key_pairs`` is every
    (many column, unique column) of the equi key. Returns (many',
    columns, nulls, ops, valid, [pairs matched, pairs kept])."""
    from risingwave_tpu.types import mend_update_pairs

    with jax.named_scope("join/keyed/probe"):
        # a NULL key matches nothing
        key_ok = _not_null(chunk.valid, chunk.nulls, [mc for mc, _ in key_pairs])
        uslots, found = lookup(
            unique.table, tuple(chunk.col(c) for c in unique_pk_from), key_ok
        )
        g = jnp.maximum(uslots, 0)
        hit = found & key_ok
        for mc, uc in key_pairs:
            hit &= unique.rows[uc][g] == chunk.col(mc)
            if uc in unique.row_nulls:
                hit &= ~unique.row_nulls[uc][g]
        cols = {n: chunk.col(n) for n in many.rows}
        cols.update({n: a[g] for n, a in unique.rows.items()})
        nulls = {n: chunk.nulls[n] for n in many.rows if n in chunk.nulls}
        nulls.update({n: a[g] for n, a in unique.row_nulls.items()})
        keep = _keep_pairs(cond, cols, nulls, hit)
        # a U-/U+ pair of which one half is not emitted is a bare op
        ops = mend_update_pairs(chunk.ops, keep)
    with jax.named_scope("join/keyed/emit"):
        out_cols, out_nulls, out_ops, out_valid = _compact(cols, nulls, ops, keep)
    many, _, _ = _flat_upsert(many, chunk, many_pk)
    counts = jnp.stack([jnp.sum(hit), jnp.sum(keep)]).astype(jnp.int64)
    return many, out_cols, out_nulls, out_ops, out_valid, counts


def flat_unique_upsert(unique: FlatSide, chunk, pk: Tuple[str, ...]):
    """A chunk of the unique side, reduced to its changed rows: per
    distinct stream key the row it replaces and the row it leaves,
    gathered to the front. Returns (unique', changed rows, old, new);
    ``old`` / ``new`` = (live, values, NULL flags) of the row that went
    and of the row that came."""
    from risingwave_tpu.ops.hash_table import first_occurrence_mask

    before = unique
    unique, slots, ok = _flat_upsert(unique, chunk, pk)
    rep = first_occurrence_mask(slots, ok)
    n = rep.shape[0]
    at, changed = _first_set(rep, n)
    g = jnp.maximum(slots, 0)[at]
    is_rep = jnp.arange(n, dtype=jnp.int32) < changed
    old_live = before.table.live[g] & is_rep
    new_live = unique.table.live[g] & is_rep
    old_vals = {k: a[g] for k, a in before.rows.items()}
    new_vals = {k: a[g] for k, a in unique.rows.items()}
    old_nulls = {k: a[g] for k, a in before.row_nulls.items()}
    new_nulls = {k: a[g] for k, a in unique.row_nulls.items()}
    same = old_live & new_live
    for k in old_vals:
        eq = old_vals[k] == new_vals[k]
        if k in old_nulls:
            either = old_nulls[k] | new_nulls[k]
            eq = jnp.where(either, old_nulls[k] == new_nulls[k], eq)
        same &= eq
    return (
        unique, changed,
        (old_live & ~same, old_vals, old_nulls),
        (new_live & ~same, new_vals, new_nulls),
    )


@jax.named_scope("join/keyed/scan")
def flat_scan(
    many: FlatSide,
    changed,
    old,
    new,
    key_pairs: Tuple[Tuple[str, str], ...],
    cond,
):
    """The many side's lanes under a mask, one pass a changed row of
    the unique side: which lanes pair with the row that went (to be
    retracted) and with the row that came (to be inserted), after the
    residual. A lane has one join key, so at most one changed row each
    way. Returns (d_src, i_src, [pairs matched, pairs kept]): per lane
    the changed row's index, or -1."""
    cap = many.capacity
    live = _not_null(
        many.table.live, many.row_nulls, [mc for mc, _ in key_pairs]
    )

    def side(j, row):
        alive, vals, vnulls = row
        hit = live & alive[j]
        for mc, uc in key_pairs:
            hit &= many.rows[mc] == vals[uc][j]
            if uc in vnulls:
                hit &= ~vnulls[uc][j]
        cols = dict(many.rows)
        cols.update(
            {n: jnp.broadcast_to(a[j], (cap,)) for n, a in vals.items()}
        )
        nulls = dict(many.row_nulls)
        nulls.update(
            {n: jnp.broadcast_to(a[j], (cap,)) for n, a in vnulls.items()}
        )
        return hit, _keep_pairs(cond, cols, nulls, hit)

    def body(j, carry):
        d_src, i_src, counts = carry
        d_hit, d_keep = side(j, old)
        i_hit, i_keep = side(j, new)
        counts = counts + jnp.stack([
            jnp.sum(d_hit) + jnp.sum(i_hit),
            jnp.sum(d_keep) + jnp.sum(i_keep),
        ]).astype(jnp.int64)
        return (
            jnp.where(d_keep, j, d_src),
            jnp.where(i_keep, j, i_src),
            counts,
        )

    none = jnp.full(cap, -1, jnp.int32)
    return jax.lax.fori_loop(
        0, changed, body, (none, none, jnp.zeros(2, jnp.int64))
    )


def flat_emit(many: FlatSide, d_src, i_src, old, new, out_cap: int):
    """The scan's pairs as one chunk: the retractions, then the
    inserts, lanes in order. Returns (columns, nulls, ops, valid,
    overflow); ``overflow`` when either kind has more than ``out_cap``
    pairs."""
    from risingwave_tpu.types import Op

    @jax.named_scope("join/keyed/pick")
    def pick(src, row):
        _, vals, vnulls = row
        at, count = _first_set(src >= 0, out_cap)
        cols = {n: a[at] for n, a in many.rows.items()}
        cols.update({n: a[src[at]] for n, a in vals.items()})
        nulls = {n: a[at] for n, a in many.row_nulls.items()}
        nulls.update({n: a[src[at]] for n, a in vnulls.items()})
        return cols, nulls, jnp.arange(out_cap, dtype=jnp.int32) < count, count

    d_cols, d_nulls, d_valid, d_n = pick(d_src, old)
    i_cols, i_nulls, i_valid, i_n = pick(i_src, new)
    with jax.named_scope("join/keyed/emit"):
        cols = {n: jnp.concatenate([d_cols[n], i_cols[n]]) for n in d_cols}
        nulls = {n: jnp.concatenate([d_nulls[n], i_nulls[n]]) for n in d_nulls}
        ops = jnp.concatenate([
            jnp.full(out_cap, Op.DELETE, jnp.int32),
            jnp.full(out_cap, Op.INSERT, jnp.int32),
        ])
        cols, nulls, ops, valid = _compact(
            cols, nulls, ops, jnp.concatenate([d_valid, i_valid])
        )
    return cols, nulls, ops, valid, (d_n > out_cap) | (i_n > out_cap)


def flat_regrow(side: FlatSide, new_cap: int) -> FlatSide:
    """Rebuild at ``new_cap`` keeping the rows that are stored or owe
    the checkpoint a tombstone."""
    keep = side.table.live | side.sdirty
    fresh = FlatSide.create(
        new_cap,
        tuple(k.dtype for k in side.table.keys),
        {n: a.dtype for n, a in side.rows.items()},
        tuple(side.row_nulls),
    )
    table, slots, _, _ = lookup_or_insert(fresh.table, side.table.keys, keep)
    idx = jnp.where(keep & (slots >= 0), slots, jnp.int32(new_cap))
    table = set_live(table, jnp.where(keep, slots, -1), side.table.live)
    move = lambda dst, src: dst.at[idx].set(src, mode="drop")  # noqa: E731
    return FlatSide(
        table,
        {n: move(fresh.rows[n], a) for n, a in side.rows.items()},
        {n: move(fresh.row_nulls[n], a) for n, a in side.row_nulls.items()},
        move(fresh.sdirty, side.sdirty),
        move(fresh.stored, side.stored),
        side.dropped | jnp.any(keep & (slots < 0)),
    )
