"""KeyedJoin executor — an INNER equi-join whose sides are stored flat,
one lane a row, under their own stream keys.

Reference: src/stream/src/executor/hash_join.rs (the same change-stream
semantics: each arriving row probes the other side and is stored on its
own; a non-equi ``cond`` is evaluated on every matched pair inside the
join, hash_join.rs ``cond``). What differs from HashJoinExecutor is the
state layout (ops/join.py, "Flat sides"), for the join shape the bucket
layout has no answer for: one side unique per join key (its stream key
lies within the equi key — an aggregate joined back on its group key),
the other an updating stream that can hold thousands of rows under one
join key and rewrites them in place (NEXmark q5: counts per (window,
auction) joined to the window's maximum on the window alone).

- a chunk of the **many** side is a fan-out-1 lookup of every row on the
  unique side, the residual on the pairs, and an upsert under the row's
  own stream key: ``flat_many_step``, one dispatch;
- a chunk of the **unique** side is reduced to its changed rows (the row
  that went, the row that came, per distinct key); the many side's
  lanes are scanned under a mask once a changed row
  (``keyed_join_scan``), the kept pairs gathered into one chunk:
  retractions first, then inserts. Rows of the chunk that undo each
  other inside it emit nothing.

Static shapes: a many-side chunk emits into its own capacity; a
unique-side chunk into ``2 * out_cap`` lanes, ``out_cap`` following the
capacity (``_out_cap``), and more kept pairs than that of one kind
latch an overflow that raises at the barrier (the capacity contract
shared with HashJoin / HashAgg). Both tables grow 2x past half load,
between chunks, from one packed read.

NULLs: a column that arrives with a lane of NULL flags gets one in the
stored side too (added when first seen), is emitted with it, and the
residual sees it (a NULL predicate keeps nothing). A NULL join key
matches nothing. A row with a NULL in its stream key is not stored; if
that column is not a join key the row would still have to pair, which
this layout cannot do: it latches and raises at the barrier.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor, Watermark
from risingwave_tpu.executors.filter import note_residual_rows
from risingwave_tpu.expr.expr import StaticTree
from risingwave_tpu.ops.hash_table import (
    lookup_or_insert,
    read_scalars,
    set_live,
    stage_scalars,
)
from risingwave_tpu.ops.join import (
    FlatSide,
    flat_emit,
    flat_many_step,
    flat_regrow,
    flat_scan,
    flat_unique_upsert,
)
from risingwave_tpu.storage.state_table import (
    Checkpointable,
    StateDelta,
    classify_marks,
    grow_pow2,
    pull_rows,
)
from risingwave_tpu.trace import span

GROW_AT = 0.5

_STATIC = ("many_pk", "unique_pk_from", "key_pairs", "cond")
_many_step = jax.jit(
    flat_many_step, static_argnames=_STATIC, donate_argnums=(0,)
)
_unique_upsert = jax.jit(
    flat_unique_upsert, static_argnames=("pk",), donate_argnums=(0,)
)
_regrow = jax.jit(flat_regrow, static_argnames=("new_cap",))


@partial(jax.jit, static_argnames=("key_pairs", "cond"))
def keyed_join_scan(many, changed, old, new, key_pairs, cond):
    """Its own XLA module (``jit_keyed_join_scan``), so that a device
    trace times the scan apart from the upsert before it and the gather
    after it (benchmarks/kernels/keyed_join_scan.py keeps the count of
    the bytes the compiled module moves)."""
    return flat_scan(many, changed, old, new, key_pairs, cond)


@jax.jit
def _any_null(latch, valid, nulls):
    for null in nulls:
        latch = latch | jnp.any(valid & null)
    return latch


_emit = jax.jit(flat_emit, static_argnames=("out_cap",))


def _out_cap(capacity: int) -> int:
    """Kept pairs of one kind a unique-side chunk may emit: 2^14, and
    1/256 of the many side's lanes where that is more (the chunk it
    emits into is twice this, and everything downstream is sized by
    it). Grows with the table; more pairs than this raise at the
    barrier."""
    return min(capacity, max(1 << 14, capacity >> 8))


class KeyedJoinExecutor(Executor, Checkpointable):
    """Args:
      left_keys / right_keys: equi-join columns, positionally paired,
        dtypes equal pair by pair.
      left_dtypes / right_dtypes: column -> dtype per side; every
        column is stored and emitted; names disjoint across sides.
      left_pk / right_pk: each side's stream key (what names a row of
        its change stream).
      unique_side: "left" | "right" — the side whose stream key lies
        within its join columns.
      condition: optional Expr over both sides' columns, the residual.
      capacity: both tables' starting capacity.
    """

    join_type = "inner"
    layout = "flat"
    window_cols = None

    def __init__(
        self,
        left_keys: Sequence[str],
        right_keys: Sequence[str],
        left_dtypes: Dict[str, object],
        right_dtypes: Dict[str, object],
        left_pk: Sequence[str],
        right_pk: Sequence[str],
        unique_side: str,
        condition=None,
        capacity: int = 1 << 15,
        table_id: str = "keyed_join",
    ):
        if set(left_dtypes) & set(right_dtypes):
            raise ValueError(
                "overlapping output columns: "
                f"{set(left_dtypes) & set(right_dtypes)}"
            )
        if unique_side not in ("left", "right"):
            raise ValueError(f"unique_side {unique_side!r}")
        self.table_id = table_id
        self.left_keys, self.right_keys = tuple(left_keys), tuple(right_keys)
        self.left_names = tuple(sorted(left_dtypes))
        self.right_names = tuple(sorted(right_dtypes))
        self.out_names = self.left_names + self.right_names
        self.left_pk, self.right_pk = tuple(left_pk), tuple(right_pk)
        self.unique_side = unique_side
        self._lint_left = {n: jnp.dtype(d) for n, d in left_dtypes.items()}
        self._lint_right = {n: jnp.dtype(d) for n, d in right_dtypes.items()}
        for lk, rk in zip(self.left_keys, self.right_keys):
            if self._lint_left[lk] != self._lint_right[rk]:
                raise ValueError(
                    f"join key dtype mismatch: {lk} {self._lint_left[lk]} "
                    f"vs {rk} {self._lint_right[rk]}"
                )
        ukeys = self.left_keys if unique_side == "left" else self.right_keys
        upk = self.left_pk if unique_side == "left" else self.right_pk
        if not upk or not set(upk) <= set(ukeys):
            raise ValueError(
                f"the {unique_side} side is not unique per join key: its "
                f"stream key {upk} does not lie within {ukeys}"
            )
        if not (self.right_pk if unique_side == "left" else self.left_pk):
            raise ValueError("the many side needs a stream key")
        self.condition = condition
        self._cond = StaticTree(condition) if condition is not None else None
        self.left = FlatSide.create(
            capacity,
            tuple(self._lint_left[k] for k in self.left_pk),
            self._lint_left,
        )
        self.right = FlatSide.create(
            capacity,
            tuple(self._lint_right[k] for k in self.right_pk),
            self._lint_right,
        )
        # (many column, unique column) of every equi pair, and per
        # column of the unique side's stream key the many column it is
        # joined to: what a many-side row looks up
        pairs = tuple(zip(self.left_keys, self.right_keys))
        if unique_side == "left":
            pairs = tuple((r, l) for l, r in pairs)
        self._key_pairs = pairs
        to_many = {u: m for m, u in pairs}
        self._unique_pk_from = tuple(to_many[k] for k in upk)
        # host bound on claimed slots per side (chunk capacities since
        # the last true reading) and the counts a barrier reports
        self._bound = {"left": 0, "right": 0}
        self._counts = jnp.zeros(4, jnp.int64)  # matched, kept, passes, lanes
        self._overflow = jnp.zeros((), jnp.bool_)
        self._null_key = jnp.zeros((), jnp.bool_)
        self._probes: List[object] = []  # this epoch's scan spans

    # -- plan verifier ---------------------------------------------------
    def lint_info(self):
        dtypes = {**self._lint_left, **self._lint_right}
        return {
            "left_keys": self.left_keys,
            "right_keys": self.right_keys,
            "expects_left": dict(self._lint_left),
            "expects_right": dict(self._lint_right),
            "emits": {n: dtypes[n] for n in self.out_names},
            "table_ids": (self.table_id,),
            "window_cols": None,
        }

    def trace_contract(self):
        return None  # interpreted only: the fused programs do not know it

    # -- data ------------------------------------------------------------
    def apply_left(self, chunk: StreamChunk) -> List[StreamChunk]:
        return self._apply("left", chunk)

    def apply_right(self, chunk: StreamChunk) -> List[StreamChunk]:
        return self._apply("right", chunk)

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        raise TypeError("KeyedJoin is two-input: use apply_left/apply_right")

    def _pk(self, name: str):
        return self.left_pk if name == "left" else self.right_pk

    def _admit_nulls(self, name: str, chunk: StreamChunk) -> None:
        """A column that brings NULL flags gets a lane for them; a NULL
        in a stream-key column that is no join key is latched."""
        side, pk = getattr(self, name), self._pk(name)
        new = [
            n for n in chunk.nulls
            if n in side.rows and n not in pk and n not in side.row_nulls
        ]
        if new:
            setattr(self, name, side.with_null_lanes(new))
        keys = self.left_keys if name == "left" else self.right_keys
        bad = tuple(
            chunk.nulls[c] for c in pk if c in chunk.nulls and c not in keys
        )
        if bad:
            self._null_key = _any_null(self._null_key, chunk.valid, bad)

    def _apply(self, name: str, chunk: StreamChunk) -> List[StreamChunk]:
        self._maybe_grow(name, chunk.capacity)
        self._bound[name] += chunk.capacity
        self._admit_nulls(name, chunk)
        if name == self.unique_side:
            return self._apply_unique(name, chunk)
        out, counts = self._many_step(name, chunk)
        self._counts = self._counts.at[:2].add(counts)
        return [out]

    def warm_side(self, name: str, chunk: StreamChunk) -> List[StreamChunk]:
        """``Executor.warm`` for one side's chunk. The MANY side: its
        step over a chunk with no valid row, which stores, rewrites and
        pairs nothing; no bound moves, nothing grows, no count or latch
        is kept. The UNIQUE side runs nothing: what a chunk costs there
        is a scan pass a changed row, not its lanes, and its three
        programs a width are the slowest to build (1.3 s a width from
        the cache, 15 s cold at 2^22 lanes, against a start: PERF.md 6,
        PR 30), so a width that side first meets compiles then. Either
        side admits a lane of NULL flags the chunk brings, as by any
        chunk (the side has then seen the column: schema, not rows), so
        that the many side's steps compiled here are the ones the
        stream runs once the unique side has seen its own."""
        self._admit_nulls(name, chunk)
        side = getattr(self, name)
        if (
            name == self.unique_side
            or self._bound[name] + chunk.capacity > side.capacity * GROW_AT
        ):
            # (_maybe_grow would read, and with this many lanes regrow
            # the side first: its step never runs at this capacity)
            return []
        return [self._many_step(name, chunk)[0]]

    def _many_step(self, name: str, chunk: StreamChunk):
        other = "left" if name == "right" else "right"
        many, cols, nulls, ops, valid, counts = _many_step(
            getattr(self, name),
            getattr(self, other),
            chunk,
            many_pk=self._pk(name),
            unique_pk_from=self._unique_pk_from,
            key_pairs=self._key_pairs,
            cond=self._cond,
        )
        setattr(self, name, many)
        return StreamChunk(columns=cols, valid=valid, nulls=nulls, ops=ops), counts

    def _apply_unique(self, name: str, chunk: StreamChunk) -> List[StreamChunk]:
        other = "left" if name == "right" else "right"
        many = getattr(self, other)
        # the many-side probe: one masked pass over its lanes a changed
        # row; rows scanned and matched are known at the barrier and
        # written into this span's args then
        with span(
            "join.many_side_probe",
            join=self.table_id,
            lanes=many.capacity,
            capacity=chunk.capacity,
        ) as sp:
            unique, changed, old, new = _unique_upsert(
                getattr(self, name), chunk, pk=self._pk(name)
            )
            setattr(self, name, unique)
            d_src, i_src, counts = keyed_join_scan(
                many, changed, old, new,
                key_pairs=self._key_pairs, cond=self._cond,
            )
            cols, nulls, ops, valid, overflow = _emit(
                many, d_src, i_src, old, new, out_cap=_out_cap(many.capacity)
            )
        self._probes.append(sp)
        passes = changed.astype(jnp.int64)
        self._counts = self._counts + jnp.concatenate(
            [counts, jnp.stack([passes, passes * many.capacity])]
        )
        self._overflow = self._overflow | overflow
        return [StreamChunk(columns=cols, valid=valid, nulls=nulls, ops=ops)]

    def _maybe_grow(self, name: str, incoming: int) -> None:
        side = getattr(self, name)
        cap = side.capacity
        if self._bound[name] + incoming <= cap * GROW_AT:
            return
        # ONE packed read of what is truly claimed and what a rebuild
        # would keep; tombstones are not reusable, so a rebuild at the
        # same capacity is a compaction
        claimed, keep = read_scalars(
            side.table.occupancy(),
            jnp.sum((side.table.live | side.sdirty).astype(jnp.int32)),
        )
        if claimed + incoming > cap * GROW_AT:
            new_cap = grow_pow2(keep + incoming, cap, GROW_AT)
            setattr(self, name, _regrow(side, new_cap=new_cap))
            claimed = keep
        self._bound[name] = int(claimed)

    def state_nbytes(self) -> int:
        return sum(
            leaf.nbytes for leaf in jax.tree.leaves((self.left, self.right))
        )

    # -- control ---------------------------------------------------------
    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        self._staged_scalars = stage_scalars(
            self._overflow,
            self.left.dropped | self.right.dropped,
            self._null_key,
            self.left.table.occupancy(),
            self.right.table.occupancy(),
            *self._counts,
        )
        self._counts = jnp.zeros(4, jnp.int64)
        if barrier is None:  # direct drive: checks fire inline
            self.finish_barrier()
        return []

    def _on_barrier_scalars(self, vals) -> None:
        (overflow, dropped, null_key, lclaimed, rclaimed, matched, kept,
         passes, lanes) = vals
        self._bound = {"left": int(lclaimed), "right": int(rclaimed)}
        note_residual_rows(
            self.table_id, kept, matched - kept,
            rows_scanned=int(lanes), passes=int(passes),
            probe_lanes=sum(sp.args["lanes"] for sp in self._probes),
        )
        for sp in self._probes:
            # the epoch's totals, on each of its probes' spans
            sp.args.update(
                epoch_rows_scanned=int(lanes), epoch_pairs_matched=int(matched),
                epoch_pairs_kept=int(kept),
            )
        self._probes = []
        if dropped:
            raise RuntimeError(
                "keyed join table overflowed MAX_PROBE mid-epoch; grow capacity"
            )
        if overflow:
            raise RuntimeError(
                "keyed join: one unique-side chunk kept more pairs of one "
                f"kind than it can emit ({_out_cap(self._many().capacity)}, "
                "which follows the capacity); grow capacity"
            )
        if null_key:
            raise RuntimeError(
                "keyed join: NULL in a stream-key column that is not a join "
                "key; this layout cannot store such a row"
            )

    def _many(self) -> FlatSide:
        return self.right if self.unique_side == "left" else self.left

    def on_watermark(self, watermark: Watermark):
        return watermark, []

    # -- integrity -------------------------------------------------------
    def digest_lanes(self):
        lanes = {f"l_{n}": a for n, a in self.left.rows.items()}
        lanes.update({f"r_{n}": a for n, a in self.right.rows.items()})
        lanes.update({f"ln_{n}": a for n, a in self.left.row_nulls.items()})
        lanes.update({f"rn_{n}": a for n, a in self.right.row_nulls.items()})
        return lanes, self.left.table.live, self.right.table.live

    def state_digest(self) -> int:
        from risingwave_tpu.integrity import host_digest

        out = 0
        for side in (self.left, self.right):
            lanes = {n: np.asarray(a) for n, a in side.rows.items()}
            for n, a in side.row_nulls.items():
                null = np.asarray(a)
                lanes[n] = np.where(null, 0, lanes[n])
                lanes[f"n_{n}"] = null
            out ^= host_digest(lanes, np.asarray(side.table.live))
        return out

    # -- checkpoint/restore ----------------------------------------------
    def checkpoint_table_ids(self):
        return [f"{self.table_id}.left", f"{self.table_id}.right"]

    def checkpoint_delta(self):
        out = []
        for name in ("left", "right"):
            side = getattr(self, name)
            marks = classify_marks(side.sdirty, side.table.live, side.stored)
            setattr(self, name, FlatSide(
                side.table, side.rows, side.row_nulls,
                marks.sdirty, marks.stored, side.dropped,
            ))
            if not len(marks):
                continue
            lanes = {f"k{i}": k for i, k in enumerate(side.table.keys)}
            key_names = tuple(lanes)
            lanes.update({f"r_{n}": a for n, a in side.rows.items()})
            lanes.update({f"n_{n}": a for n, a in side.row_nulls.items()})
            pulled = pull_rows(lanes, marks)
            out.append(
                StateDelta(
                    f"{self.table_id}.{name}",
                    {k: pulled[k] for k in key_names},
                    {k: v for k, v in pulled.items() if k not in key_names},
                    marks.tombstone,
                    key_names,
                )
            )
        return out

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        name = "left" if table_id.endswith(".left") else "right"
        side = getattr(self, name)
        n = len(next(iter(key_cols.values()))) if key_cols else 0
        # a null lane for every column the store holds NULL flags of
        nullable = sorted(
            set(side.row_nulls)
            | {k[2:] for k in value_cols if k.startswith("n_")}
        )
        fresh = FlatSide.create(
            grow_pow2(n, side.capacity, GROW_AT),
            tuple(k.dtype for k in side.table.keys),
            {nm: a.dtype for nm, a in side.rows.items()},
            nullable,
        )
        if n:
            keys = tuple(
                jnp.asarray(np.asarray(key_cols[f"k{i}"], dtype=k.dtype))
                for i, k in enumerate(side.table.keys)
            )
            table, slots, _, _ = lookup_or_insert(
                fresh.table, keys, jnp.ones(n, jnp.bool_)
            )
            fresh = FlatSide(
                set_live(table, slots, True),
                {
                    nm: a.at[slots].set(
                        jnp.asarray(value_cols[f"r_{nm}"]).astype(a.dtype)
                    )
                    for nm, a in fresh.rows.items()
                },
                {
                    nm: a.at[slots].set(jnp.asarray(value_cols[f"n_{nm}"]))
                    if f"n_{nm}" in value_cols else a
                    for nm, a in fresh.row_nulls.items()
                },
                fresh.sdirty,
                fresh.stored.at[slots].set(True),
                fresh.dropped,
            )
        setattr(self, name, fresh)
        self._bound[name] = n
