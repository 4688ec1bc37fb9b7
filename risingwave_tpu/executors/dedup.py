"""Append-only dedup executor — streaming DISTINCT on a key.

Reference: src/stream/src/executor/dedup/append_only_dedup.rs — emits
each pk's FIRST row and drops later duplicates; state is the set of
seen pks, cleaned by watermark.

TPU re-design: the seen-set is ops/hash_table.HashTable; one jitted
step does batched lookup-or-insert and emits rows that claimed a new
slot (intra-chunk twins dedupe via first_occurrence_mask). Append-only
by contract: a DELETE in the input latches ``inconsistent`` and raises
at the barrier, like the reference's append-only executors.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor, Watermark
from risingwave_tpu.ops.hash_table import HashTable, first_occurrence_mask, lookup_or_insert, read_scalars, stage_scalars, set_live
from risingwave_tpu.ops.bucketing import (
    BucketAllocator,
    BucketPolicy,
    needs_plan,
    plan_capacity,
)
from risingwave_tpu.storage.state_table import (
    Checkpointable,
    StateDelta,
    classify_marks,
    grow_pow2,
    pull_rows,
)

GROW_AT = 0.5
# mid-epoch rebuild only when the HOST insert bound nears the table
# itself (MAX_PROBE overflow risk); ordinary growth resolves at the
# barrier from the true occupancy note (HashAgg's twin constant)
HARD_GROW_AT = 0.75


def dedup_step_fn(
    table: HashTable, sdirty, chunk: StreamChunk, keys: Tuple[str, ...]
):
    key_cols = tuple(chunk.col(k) for k in keys)
    signs = chunk.effective_signs()
    saw_delete = jnp.any(chunk.valid & (signs < 0))
    valid = chunk.valid & (signs > 0)
    with jax.named_scope("dedup/seen"):
        table, slots, _, inserted = lookup_or_insert(table, key_cols, valid)
        table = set_live(table, jnp.where(inserted, slots, -1), True)
        sdirty = sdirty.at[
            jnp.where(inserted, slots, table.capacity)
        ].set(True, mode="drop")
        dropped = jnp.any(valid & (slots < 0))
    with jax.named_scope("dedup/first"):
        # `inserted` marks a claim's winner AND its same-key twins; keep one
        emit = inserted & first_occurrence_mask(slots, inserted)
    return table, sdirty, chunk.mask(emit), saw_delete, dropped


_dedup_step = partial(
    jax.jit, static_argnames=("keys",), donate_argnums=(0, 1)
)(dedup_step_fn)


@partial(jax.jit, static_argnames=("new_cap",))
def _rebuild(table: HashTable, sdirty, stored, new_cap: int):
    keep = table.live | sdirty  # sdirty dead keys carry pending tombstones
    new = HashTable.create(new_cap, tuple(k.dtype for k in table.keys))
    new, slots, _, _ = lookup_or_insert(new, table.keys, keep)
    new = set_live(new, jnp.where(keep, slots, -1), table.live)
    idx = jnp.where(keep, slots, new_cap)
    new_sdirty = jnp.zeros(new_cap, jnp.bool_).at[idx].set(sdirty, mode="drop")
    new_stored = jnp.zeros(new_cap, jnp.bool_).at[idx].set(stored, mode="drop")
    return new, new_sdirty, new_stored


class AppendOnlyDedupExecutor(Executor, Checkpointable):
    """DISTINCT ON (keys): first row per key passes, duplicates drop.

    ``window_key``: optional (column, retention_ms) — a watermark on
    that key column marks seen-set entries below ``wm - retention``
    dead; the next table rebuild reclaims them (until then late
    duplicates stay suppressed — strictly more exact than the
    reference's cache eviction, never less).
    """

    def __init__(
        self,
        keys: Sequence[str],
        schema_dtypes: Dict[str, object],
        capacity: int = 1 << 16,
        window_key: Optional[Tuple[str, int]] = None,
        table_id: str = "dedup",
        bucket_policy: Optional[BucketPolicy] = None,
        bucketed: bool = True,
    ):
        self.keys = tuple(keys)
        self.table_id = table_id
        self.table = HashTable.create(
            capacity, tuple(jnp.dtype(schema_dtypes[k]) for k in self.keys)
        )
        self.sdirty = jnp.zeros(capacity, jnp.bool_)
        self.stored = jnp.zeros(capacity, jnp.bool_)
        self.window_key = window_key
        # shape-stability: capacities drawn from a declared pow2
        # lattice (ops/bucketing) — ``bucketed=False`` is the
        # legacy unbounded-rehash twin (tests, soak baselines)
        self._buckets = (
            BucketAllocator(
                bucket_policy or BucketPolicy.from_capacity(capacity, grow_at=GROW_AT)
            )
            if bucketed
            else None
        )
        self._bound = 0
        self._occ_note = 0  # true claimed at the last barrier (staged)
        self._grew_midepoch = False  # one overflow-guard bump per epoch
        self._saw_delete = jnp.zeros((), jnp.bool_)
        self._dropped = jnp.zeros((), jnp.bool_)

    def lint_info(self):
        expects = {
            k: lane.dtype for k, lane in zip(self.keys, self.table.keys)
        }
        return {
            "expects": expects,
            "keys": self.keys,
            "table_ids": (self.table_id,),
            "window_key": self.window_key[0] if self.window_key else None,
        }

    def trace_contract(self):
        contract = {
            "kind": "device",
            "trace_step": lambda c: _dedup_step(
                self.table, self.sdirty, c, self.keys
            ),
            "state": (self.table, self.sdirty),
            "donate": True,
            "emission": "passthrough",
            # the seen-set's capacities are drawn from the allocator's
            # declared pow2 lattice: window churn is bounded to one
            # trace per bucket (None only on the legacy unbucketed twin)
            "window_buckets": (
                self._buckets.lattice if self._buckets is not None else None
            ),
        }
        if self._buckets is not None:
            # the interpreted growth path's packed read exists only
            # where interpretation runs: the fused program's wrapper
            # plans from barrier notes instead (_grow_hint) — the
            # analyzer scores it as fallback-only, not a blocker
            contract["fallback_syncs"] = ("_maybe_grow",)
        return contract

    def pin_max_bucket(self):
        """ShapeGovernor hook: freeze the seen-set at its high-water
        bucket (shrink disabled; applied by the next apply)."""
        if self._buckets is None:
            return {"pinned": False}
        return {
            "table_id": self.table_id,
            "pinned_cap": self._buckets.pin(),
        }

    def padding_stats(self):
        return {
            "capacity": self.table.capacity,
            "live": int(self.table.num_live()),
        }

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        for k in self.keys:
            if k in chunk.nulls:
                raise ValueError(
                    f"dedup key {k!r} carries a null lane (unsupported)"
                )
        self._maybe_grow(chunk.capacity)
        self._bound += chunk.capacity
        self.table, self.sdirty, out, saw_delete, dropped = _dedup_step(
            self.table, self.sdirty, chunk, self.keys
        )
        self._saw_delete = self._saw_delete | saw_delete
        self._dropped = self._dropped | dropped
        return [out]

    # one step a chunk at the chunk's own width: takes the push lattice
    per_chunk_step = True

    def warm(self, chunk: StreamChunk) -> Optional[List[StreamChunk]]:
        """``Executor.warm``: the step's program over a chunk with no
        valid row, which claims no slot and passes no row on; no host
        bound moves, nothing grows and no latch is kept. Where the
        next chunk of this width would plan a growth first, its step
        never runs at this capacity: the pass stops here."""
        if any(k in chunk.nulls for k in self.keys) or needs_plan(
            self._buckets, self.table.capacity, self._bound,
            chunk.capacity, GROW_AT,
        ):
            return None
        self.table, self.sdirty, out, _, _ = _dedup_step(
            self.table, self.sdirty, chunk, self.keys
        )
        return [out]

    def _grow_hint(self, incoming: int):
        """The FUSED wrapper's pre-dispatch growth bookkeeping: ZERO
        device reads. The host bound counts padded chunk capacities —
        letting the exact planner size from it over-grows by buckets —
        so the fused path bumps ONE bucket, at most once per epoch,
        purely as MAX_PROBE headroom (BucketAllocator.bump); ordinary
        growth/shrink resolves at the barrier from the staged true
        occupancy note (_on_barrier_scalars). A genuinely faster
        blow-up still trips the overflow latch, the existing
        contract."""
        if self._buckets is None:
            return self._maybe_grow(incoming)
        cap = self.table.capacity
        self._bound = min(self._bound, cap)
        if self._grew_midepoch or (
            self._bound + incoming <= cap * HARD_GROW_AT
        ):
            return
        new_cap = self._buckets.bump(cap)
        if new_cap is not None:
            self.table, self.sdirty, self.stored = _rebuild(
                self.table, self.sdirty, self.stored, new_cap
            )
            self._bound = min(self._bound, new_cap)
        self._grew_midepoch = True

    def _maybe_grow(self, incoming: int):
        """INTERPRETED-path growth: the exact legacy policy — when the
        load-factor trigger (or a pending shrink / governor-pin
        wakeup) trips, ONE packed blocking read learns the true
        occupancy and plans from it. Declared under the contract's
        ``fallback_syncs`` on bucketed instances: the fused per-
        barrier program never calls this method (the wrapper's
        _grow_hint + barrier-note planning are its replacement), so
        the read runs only where interpretation runs — the analyzer
        scores it as fallback_sync_points, outside the fusibility
        verdict (the HashAgg _flush_all discipline)."""
        cap = self.table.capacity
        if not needs_plan(self._buckets, cap, self._bound, incoming, GROW_AT):
            return
        # ONE packed read: device round-trips dominate
        claimed, survivors = read_scalars(
            self.table.occupancy(),
            jnp.sum((self.table.live | self.sdirty).astype(jnp.int32)),
        )
        new_cap = plan_capacity(
            self._buckets, cap, incoming, claimed, survivors, GROW_AT
        )
        if new_cap is not None:
            self.table, self.sdirty, self.stored = _rebuild(
                self.table, self.sdirty, self.stored, new_cap
            )
            claimed = int(self.table.occupancy())
        self._bound = claimed

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        # staged read; finish_barrier materializes after the walk
        self._staged_scalars = stage_scalars(
            self._saw_delete,
            self._dropped,
            self.table.occupancy(),
            jnp.sum((self.table.live | self.sdirty).astype(jnp.int32)),
        )
        if barrier is None:  # direct drive: checks fire inline
            self.finish_barrier()
        return []

    def _on_barrier_scalars(self, vals) -> None:
        saw_delete, dropped, claimed, survivors = vals
        self._grew_midepoch = False
        epoch_inc = max(self._bound - self._occ_note, 0)
        self._occ_note = int(claimed)
        self._bound = int(claimed)
        if self._buckets is not None:
            cap = self.table.capacity
            self._buckets.note_barrier(cap, int(claimed))
            new_cap = self._buckets.plan(
                cap,
                0,
                int(claimed),
                int(survivors),
                margin=max(int(claimed), epoch_inc),
            )
            if new_cap is not None and new_cap != cap:
                self.table, self.sdirty, self.stored = _rebuild(
                    self.table, self.sdirty, self.stored, new_cap
                )
        if saw_delete:
            raise RuntimeError("append-only dedup received a DELETE")
        if dropped:
            raise RuntimeError("dedup table overflowed MAX_PROBE; grow capacity")

    def on_watermark(self, watermark: Watermark):
        if self.window_key is None or watermark.column != self.window_key[0]:
            return watermark, []
        cutoff = jnp.asarray(
            watermark.value - self.window_key[1], jnp.int64
        )
        lane = self.table.keys[self.keys.index(self.window_key[0])]
        expired = self.table.live & (lane < cutoff)
        slots = jnp.where(
            expired, jnp.arange(self.table.capacity, dtype=jnp.int32), -1
        )
        self.table = set_live(self.table, slots, False)
        self.sdirty = self.sdirty | expired
        return watermark, []

    # -- integrity --------------------------------------------------------
    def digest_lanes(self):
        from risingwave_tpu.integrity import dedup_lanes

        return dedup_lanes(self.table)

    def state_digest(self) -> int:
        """Host twin of the fused digest lane (integrity.dedup_lanes)."""
        from risingwave_tpu.integrity import host_digest

        return host_digest(*self.digest_lanes())

    # -- checkpoint/restore ----------------------------------------------
    def checkpoint_delta(self):
        import numpy as np

        marks = classify_marks(self.sdirty, self.table.live, self.stored)
        self.sdirty, self.stored = marks.sdirty, marks.stored
        if not len(marks):
            return []
        lanes = {f"k{i}": l for i, l in enumerate(self.table.keys)}
        keys = pull_rows(lanes, marks)
        return [
            StateDelta(
                self.table_id,
                keys,
                {},
                marks.tombstone,
                tuple(f"k{i}" for i in range(len(self.table.keys))),
            )
        ]

    def restore_state(self, table_id, key_cols, value_cols):
        import numpy as np

        n = len(next(iter(key_cols.values()))) if key_cols else 0
        key_dtypes = tuple(k.dtype for k in self.table.keys)
        cap = grow_pow2(n, self.table.capacity, GROW_AT)
        table = HashTable.create(cap, key_dtypes)
        self.sdirty = jnp.zeros(cap, jnp.bool_)
        self.stored = jnp.zeros(cap, jnp.bool_)
        if n:
            lanes = tuple(
                jnp.asarray(np.asarray(key_cols[f"k{i}"], dtype=d))
                for i, d in enumerate(key_dtypes)
            )
            table, slots, _, _ = lookup_or_insert(
                table, lanes, jnp.ones(n, jnp.bool_)
            )
            table = set_live(table, slots, True)
            self.stored = self.stored.at[slots].set(True)
        self.table = table
        self._bound = int(n)
        self._saw_delete = jnp.zeros((), jnp.bool_)
        self._dropped = jnp.zeros((), jnp.bool_)
