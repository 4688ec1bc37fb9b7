"""Chained join sides — a stream-to-stream equi-join with no bound on
the rows of one key.

The bucket layout (ops/join.py, ``JoinSide``) gives every key
``fanout`` row positions, so one hot key sizes every key's bucket and a
key that outgrows it raises; the flat layout (``FlatSide``) needs one
side unique per join key and scans the other a changed row. A fact
stream joined to the stream of the entities it names (NEXmark q4: bid
to auction) has neither: both sides take thousands of new keys a
second, and a key holds as many rows as arrive for it.

A ``ChainSide`` is a row store and an index over it:

    row store : per stored column a (row_cap,) lane, appended at
                ``n_rows``; ``row_valid`` marks the rows not yet
                retracted; ``nxt`` links a row to the next older row
                of its key (-1: none)
    key table : ops/hash_table.HashTable over the join key; per slot
                ``head`` (the key's newest row) and ``count`` (its
                valid rows)

so memory follows the rows stored, not capacity x the fullest key. An
insert appends and links (one sort of the chunk by slot resolves the
rows of one key inside a chunk); a delete walks its key's chain to the
first stored row equal to it, column by column (the rank-th for
identical deletes in one chunk), and clears it; a probe walks the
other side's chain of each probing row, one gather a step, and writes
its pairs at the offsets a prefix sum of ``count`` gives: the walk is
as long as the longest chain probed, not the fullest key stored.

A row keeps its position for as long as the side lives (growth pads
the lanes; a retracted row's lane is not reused), so the checkpoint
store keys a row by its position and a restore puts it back there;
the key table and the chains are rebuilt from the rows
(``chain_relink``).
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from risingwave_tpu.ops.hash_table import (
    HashTable,
    lookup,
    lookup_or_insert,
    set_live,
)
from risingwave_tpu.ops.join import (
    _intra_chunk_rank,
    _keep_pairs,
    _not_null,
    _row_fingerprint,
)
from risingwave_tpu.types import Op

_NO_SLOT = jnp.iinfo(jnp.int32).max


@jax.tree_util.register_pytree_node_class
@dataclass
class ChainSide:
    table: HashTable  # over the join key; ``live`` = the key has a row
    head: jnp.ndarray  # (key_cap,) int32 newest row of the key, -1 none
    count: jnp.ndarray  # (key_cap,) int32 valid rows under the key
    rows: Dict[str, jnp.ndarray]  # column -> (row_cap,)
    row_nulls: Dict[str, jnp.ndarray]  # nullable column -> (row_cap,) NULL
    row_valid: jnp.ndarray  # (row_cap,) bool
    nxt: jnp.ndarray  # (row_cap,) int32 next older row of the key, -1
    n_rows: jnp.ndarray  # () int32 rows ever appended
    rdirty: jnp.ndarray  # (row_cap,) changed since the last checkpoint
    stored: jnp.ndarray  # (row_cap,) in the checkpoint store
    overflow: jnp.ndarray  # () latch: key table or row store full
    inconsistent: jnp.ndarray  # () latch: a delete matched no stored row

    def tree_flatten(self):
        names = tuple(sorted(self.rows))
        null_names = tuple(sorted(self.row_nulls))
        return (
            (self.table, self.head, self.count,
             tuple(self.rows[n] for n in names),
             tuple(self.row_nulls[n] for n in null_names),
             self.row_valid, self.nxt, self.n_rows, self.rdirty,
             self.stored, self.overflow, self.inconsistent),
            (names, null_names),
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        names, null_names = aux
        (table, head, count, rows, nulls, row_valid, nxt, n_rows, rdirty,
         stored, overflow, inconsistent) = children
        return cls(
            table, head, count, dict(zip(names, rows)),
            dict(zip(null_names, nulls)), row_valid, nxt, n_rows, rdirty,
            stored, overflow, inconsistent,
        )

    @property
    def key_cap(self) -> int:
        return self.table.capacity

    @property
    def row_cap(self) -> int:
        return self.row_valid.shape[0]

    @staticmethod
    def create(
        key_cap: int, row_cap: int, key_dtypes, row_dtypes: Dict[str, object],
        nullable=(),
    ) -> "ChainSide":
        return ChainSide(
            HashTable.create(key_cap, key_dtypes),
            jnp.full(key_cap, -1, jnp.int32),
            jnp.zeros(key_cap, jnp.int32),
            {n: jnp.zeros(row_cap, d) for n, d in row_dtypes.items()},
            {n: jnp.zeros(row_cap, jnp.bool_) for n in nullable},
            jnp.zeros(row_cap, jnp.bool_),
            jnp.full(row_cap, -1, jnp.int32),
            jnp.zeros((), jnp.int32),
            jnp.zeros(row_cap, jnp.bool_),
            jnp.zeros(row_cap, jnp.bool_),
            jnp.zeros((), jnp.bool_),
            jnp.zeros((), jnp.bool_),
        )

    def with_null_lanes(self, names) -> "ChainSide":
        """Null lanes (no row NULL yet) for columns that had none."""
        lanes = {n: jnp.zeros(self.row_cap, jnp.bool_) for n in names}
        return replace(self, row_nulls={**lanes, **self.row_nulls})


def _cumsum32(a):
    return jnp.cumsum(a.astype(jnp.int32), dtype=jnp.int32)


def _link(slots, ok, pos, head, key_cap: int):
    """The chain links of the rows ``ok`` appended at ``pos``: a row
    points at the row of its key appended just before it in this chunk,
    the first of them at the key's old head; the last becomes the head.
    Returns (nxt value per lane, head')."""
    n = slots.shape[0]
    skey = jnp.where(ok, slots, _NO_SLOT)
    order = jnp.argsort(skey, stable=True).astype(jnp.int32)
    ks = skey[order]
    real = ks != _NO_SLOT
    same_prev = jnp.concatenate([jnp.zeros(1, jnp.bool_), ks[1:] == ks[:-1]])
    prev_lane = jnp.concatenate([jnp.zeros(1, jnp.int32), order[:-1]])
    old_head = head[jnp.minimum(ks, key_cap - 1)]
    nxt_sorted = jnp.where(same_prev, pos[prev_lane], old_head)
    last = jnp.concatenate([ks[:-1] != ks[1:], jnp.ones(1, jnp.bool_)]) & real
    head = head.at[jnp.where(last, ks, key_cap)].set(pos[order], mode="drop")
    nxt_lane = jnp.zeros(n, jnp.int32).at[order].set(nxt_sorted)
    return nxt_lane, head


def _rows_equal(side: ChainSide, p, chunk, names):
    """Per lane: the stored row at ``p`` equals the lane's row, column
    by column (NULL == NULL, NaN == NaN)."""
    ok = jnp.ones(p.shape, jnp.bool_)
    for name in names:
        stored = side.rows[name][p]
        val = chunk.col(name).astype(stored.dtype)
        eq = stored == val
        if jnp.issubdtype(stored.dtype, jnp.floating):
            eq |= jnp.isnan(stored) & jnp.isnan(val)
        snull = side.row_nulls.get(name)
        if snull is not None:
            s_null = snull[p]
            r_null = chunk.nulls.get(name)
            if r_null is None:
                r_null = jnp.zeros(p.shape, jnp.bool_)
            eq = jnp.where(s_null | r_null, s_null == r_null, eq)
        ok &= eq
    return ok


@jax.named_scope("join/stream/fold")
def chain_apply(side: ChainSide, chunk, key_cols, valid, signs, names, retract):
    """A chunk folded into its own side: the inserts appended and
    linked, then (``retract``) each delete's row found on its key's
    chain and cleared, so an insert and a delete of one row inside a
    chunk net out. A side declared append-only (``retract`` False)
    latches ``inconsistent`` on a delete. Returns the side."""
    key_cap, row_cap = side.key_cap, side.row_cap
    n = valid.shape[0]
    ins = valid & (signs > 0)
    dele = valid & (signs < 0)
    touch = (ins | dele) if retract else ins
    inconsistent = side.inconsistent
    if not retract:
        inconsistent = inconsistent | jnp.any(dele)

    table, slots, _, _ = lookup_or_insert(side.table, key_cols, touch)
    ok = touch & (slots >= 0)
    overflow = side.overflow | jnp.any(touch & (slots < 0))
    g = jnp.maximum(slots, 0)

    # ---- inserts: append, link ----------------------------------------
    ins_ok = ins & ok
    rank = _cumsum32(ins_ok) - ins_ok.astype(jnp.int32)
    pos = side.n_rows + rank
    fits = ins_ok & (pos < row_cap)
    overflow = overflow | jnp.any(ins_ok & ~fits)
    nxt_lane, head = _link(slots, fits, pos, side.head, key_cap)
    widx = jnp.where(fits, pos, row_cap)
    rows = {
        name: a.at[widx].set(chunk.col(name).astype(a.dtype), mode="drop")
        for name, a in side.rows.items()
    }
    no_null = jnp.zeros(n, jnp.bool_)
    row_nulls = {
        name: a.at[widx].set(chunk.nulls.get(name, no_null), mode="drop")
        for name, a in side.row_nulls.items()
    }
    row_valid = side.row_valid.at[widx].set(True, mode="drop")
    rdirty = side.rdirty.at[widx].set(True, mode="drop")
    nxt = side.nxt.at[widx].set(nxt_lane, mode="drop")
    count = side.count.at[jnp.where(fits, slots, key_cap)].add(1, mode="drop")
    n_rows = side.n_rows + jnp.sum(fits, dtype=jnp.int32)
    side = ChainSide(
        table, head, count, rows, row_nulls, row_valid, nxt, n_rows, rdirty,
        side.stored, overflow, inconsistent,
    )

    # ---- deletes: the rank-th stored row equal to the lane's ----------
    if retract:
        with jax.named_scope("retract"):
            del_ok = dele & ok
            h1, h2 = _row_fingerprint(
                {name: chunk.col(name) for name in names}, chunk.nulls, names
            )
            want = _intra_chunk_rank(slots, h1, h2, del_ok)

            def walking(c):
                return jnp.any(c[0] >= 0)

            def walk(c):
                ptr, seen, hit = c
                p = jnp.maximum(ptr, 0)
                eq = (ptr >= 0) & side.row_valid[p] & _rows_equal(
                    side, p, chunk, names
                )
                take = eq & (seen == want)
                hit = jnp.where(take, p, hit)
                seen = seen + eq.astype(jnp.int32)
                ptr = jnp.where((ptr >= 0) & ~take, side.nxt[p], -1)
                return ptr, seen, hit

            _, _, hit = jax.lax.while_loop(
                walking, walk,
                (
                    jnp.where(del_ok, side.head[g], -1),
                    jnp.zeros(n, jnp.int32),
                    jnp.full(n, -1, jnp.int32),
                ),
            )
            found = hit >= 0
            didx = jnp.where(found, hit, row_cap)
            side = replace(
                side,
                row_valid=side.row_valid.at[didx].set(False, mode="drop"),
                rdirty=side.rdirty.at[didx].set(True, mode="drop"),
                count=side.count.at[jnp.where(found, slots, key_cap)].add(
                    -1, mode="drop"
                ),
                inconsistent=side.inconsistent | jnp.any(del_ok & ~found),
            )

    # a key is live while it holds a row (a probe stops at a dead key)
    table = set_live(
        side.table, jnp.where(ok, slots, -1), side.count[g] > 0
    )
    return replace(side, table=table)


def chain_probe(other: ChainSide, key_cols, active, start, out_cap: int):
    """Every ``active`` lane's matches on the other side: the pairs
    numbered ``start`` to ``start + out_cap`` of the chunk's, as
    ``out_cap`` pair lanes: (the probing lane, the stored row, which
    pair lanes hold a pair, pairs of the chunk in all, chain steps
    walked). Pairs are numbered in lane order, a lane's newest match
    first."""
    with jax.named_scope("join/stream/probe"):
        n = active.shape[0]
        slots, found = lookup(other.table, key_cols, active)
        g = jnp.maximum(slots, 0)
        hit = found & active
        mc = jnp.where(hit, other.count[g], 0)
        off = _cumsum32(mc) - mc - start
        total = jnp.sum(mc, dtype=jnp.int32)
        lanes = jnp.arange(n, dtype=jnp.int32)

    with jax.named_scope("join/stream/chain"):
        def walking(c):
            return jnp.any(c[0] >= 0)

        def walk(c):
            ptr, j, src_lane, src_row, steps = c
            p = jnp.maximum(ptr, 0)
            live = (ptr >= 0) & other.row_valid[p]
            at = off + j
            idx = jnp.where(live & (at >= 0) & (at < out_cap), at, out_cap)
            src_lane = src_lane.at[idx].set(lanes, mode="drop")
            src_row = src_row.at[idx].set(p, mode="drop")
            j = j + live.astype(jnp.int32)
            # a lane stops once it has every valid row of its key
            ptr = jnp.where((ptr >= 0) & (j < mc), other.nxt[p], -1)
            return ptr, j, src_lane, src_row, steps + 1

        _, _, src_lane, src_row, steps = jax.lax.while_loop(
            walking, walk,
            (
                jnp.where(mc > 0, other.head[g], -1),
                jnp.zeros(n, jnp.int32),
                jnp.zeros(out_cap, jnp.int32),
                jnp.zeros(out_cap, jnp.int32),
                jnp.zeros((), jnp.int32),
            ),
        )
    pair = jnp.arange(out_cap, dtype=jnp.int32) < (total - start)
    return src_lane, src_row, pair, total, steps


def stream_join_step(
    own: ChainSide,
    other: ChainSide,
    buf,
    cursor,
    chunk,
    counts,
    start,
    own_keys: Tuple[str, ...],
    own_names: Tuple[str, ...],
    other_names: Tuple[str, ...],
    out_cap: int,
    cond,
    retract: bool,
    fold: bool,
):
    """One chunk of an INNER join: its rows probe the other side, the
    residual ``cond`` keeps a pair or not, the kept pairs are appended
    to the epoch's pair buffer ``buf`` (a chunk; ``cursor`` = the pairs
    it holds) and (``fold``) the rows are folded into their own side.
    A step takes the chunk's pairs ``start`` to ``start + out_cap``;
    the host reads the total and runs the rest, unfolded. ``counts`` =
    the epoch's [pairs matched, pairs kept, probe lanes (chunk lanes x
    chain steps walked)]. Returns (own', other, buf', cursor',
    counts', the chunk's pairs in all)."""
    key_cols = tuple(chunk.col(k) for k in own_keys)
    # a NULL key matches nothing and needs no state
    valid = _not_null(chunk.valid, chunk.nulls, own_keys)
    signs = chunk.effective_signs()
    active = valid & (signs != 0)

    src_lane, src_row, pair, total, steps = chain_probe(
        other, key_cols, active, start, out_cap
    )
    with jax.named_scope("join/stream/emit"):
        cols = {name: chunk.col(name)[src_lane] for name in own_names}
        cols.update({name: other.rows[name][src_row] for name in other_names})
        nulls = {
            name: lane[src_lane]
            for name, lane in chunk.nulls.items()
            if name in own_names
        }
        nulls.update({name: a[src_row] for name, a in other.row_nulls.items()})
        keep = _keep_pairs(cond, cols, nulls, pair)
        ops = jnp.where(
            signs[src_lane] > 0, jnp.int32(Op.INSERT), jnp.int32(Op.DELETE)
        )
        kept = jnp.sum(keep, dtype=jnp.int32)
        counts = counts + jnp.stack([
            jnp.sum(pair), kept, steps * chunk.valid.shape[0]
        ]).astype(jnp.int64)

        # the kept pairs, in order, behind what the buffer holds
        room = buf.valid.shape[0]
        at = cursor + _cumsum32(keep) - 1
        idx = jnp.where(keep & (at < room), at, room)
        no_null = jnp.zeros(out_cap, jnp.bool_)
        buf = type(buf)(
            columns={
                name: a.at[idx].set(cols[name].astype(a.dtype), mode="drop")
                for name, a in buf.columns.items()
            },
            valid=buf.valid.at[idx].set(True, mode="drop"),
            nulls={
                name: a.at[idx].set(nulls.get(name, no_null), mode="drop")
                for name, a in buf.nulls.items()
            },
            ops=buf.ops.at[idx].set(ops, mode="drop"),
        )
    if fold:
        own = chain_apply(
            own, chunk, key_cols, valid, signs, own_names, retract
        )
    return own, other, buf, cursor + kept, counts, total


def chain_relink(side: ChainSide, key_cap: int, key_names) -> ChainSide:
    """The key table, heads, counts and links rebuilt from the stored
    rows, at ``key_cap``: after a restore, or when the key table grows
    (its dead keys go). Chains run by row position, newest first."""
    row_cap = side.row_cap
    live = side.row_valid
    table = HashTable.create(key_cap, tuple(k.dtype for k in side.table.keys))
    table, slots, _, _ = lookup_or_insert(
        table, tuple(side.rows[k] for k in key_names), live
    )
    ok = live & (slots >= 0)
    pos = jnp.arange(row_cap, dtype=jnp.int32)
    nxt, head = _link(
        slots, ok, pos, jnp.full(key_cap, -1, jnp.int32), key_cap
    )
    count = jnp.zeros(key_cap, jnp.int32).at[
        jnp.where(ok, slots, key_cap)
    ].add(1, mode="drop")
    table = set_live(table, jnp.where(ok, slots, -1), True)
    return replace(
        side, table=table, head=head, count=count,
        nxt=jnp.where(ok, nxt, -1),
        overflow=side.overflow | jnp.any(live & (slots < 0)),
    )


def chain_grow(
    side: ChainSide, key_cap: int, row_cap: int, key_names
) -> ChainSide:
    """The side at a larger row store and/or key table. Rows keep
    their positions (the checkpoint store names a row by it)."""
    old = side.row_cap
    if row_cap != old:
        def pad(a, fill=0):
            return jnp.full(row_cap, fill, a.dtype).at[:old].set(a)

        side = replace(
            side,
            rows={n: pad(a) for n, a in side.rows.items()},
            row_nulls={n: pad(a) for n, a in side.row_nulls.items()},
            row_valid=pad(side.row_valid),
            nxt=pad(side.nxt, -1),
            rdirty=pad(side.rdirty),
            stored=pad(side.stored),
        )
    if key_cap != side.key_cap:
        side = chain_relink(side, key_cap, key_names)
    return side
