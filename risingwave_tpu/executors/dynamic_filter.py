"""Dynamic filter against a per-group running extreme.

Reference: src/stream/src/executor/dynamic_filter.rs:40 — filters the
left input against a dynamically-changing right-side value. This is the
grouped, append-only specialization the reference's q7 plan leans on:
pass a row iff ``value >= max-so-far(group)``.

Why it exists: q7 joins bids against the per-window MAX. Storing every
bid in the join would need per-(window, price) bucket fanout sized for
the duplication of the Nexmark price distribution's low end (~50+ at
p=100), almost all of it dead weight — a bid below its window's
current max can NEVER match a future max (append-only max is
monotone), so dropping it early is semantics-preserving. What remains
in the join is the ascending-maxima chain + ties: O(log prices) per
window instead of O(bids).

The comparison uses the max BEFORE the current chunk (conservative:
same-chunk stragglers pass and are dropped by the join probe instead),
then folds the chunk into the running max — one fused jit step.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor, Watermark
from risingwave_tpu.ops.hash_table import HashTable, lookup_or_insert, read_scalars, stage_scalars, set_live
from risingwave_tpu.array.lattice import emission_bucket
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


def filter_step_fn(
    table: HashTable,
    maxes: jnp.ndarray,
    sdirty: jnp.ndarray,
    chunk: StreamChunk,
    group_col: str,
    value_col: str,
):
    keys = (chunk.col(group_col),)
    value = chunk.col(value_col)
    signs = chunk.effective_signs()
    saw_delete = jnp.any(chunk.valid & (signs < 0))
    valid = chunk.valid & (signs > 0)

    table, slots, _, inserted = lookup_or_insert(table, keys, valid)
    table = set_live(table, jnp.where(inserted, slots, -1), True)
    dropped = jnp.any(valid & (slots < 0))
    sl = jnp.maximum(slots, 0)

    # pass iff >= the PRE-chunk max of the row's group (new groups pass)
    ok = valid & (inserted | (value >= maxes[sl]))
    # then fold this chunk in: scatter-max (new groups start at value)
    cap = maxes.shape[0]
    idx = jnp.where(valid, slots, cap)
    init = jnp.iinfo(maxes.dtype).min
    cleared = maxes.at[idx].set(
        jnp.where(inserted, init, maxes[sl]), mode="drop"
    )
    maxes = cleared.at[idx].max(value, mode="drop")
    sdirty = sdirty.at[idx].set(True, mode="drop")
    return table, maxes, sdirty, chunk.mask(ok), saw_delete, dropped


# the un-jitted body (filter_step_fn) is what the fused two-input
# program scans over a stacked epoch (runtime/fused_step); this jitted
# form is the interpreted per-chunk path
_filter_step = partial(
    jax.jit, static_argnames=("group_col", "value_col"), donate_argnums=(0, 1, 2)
)(filter_step_fn)


@partial(jax.jit, static_argnames=("new_cap",))
def _rebuild(table: HashTable, maxes: jnp.ndarray, sdirty, stored, new_cap: int):
    keep = table.live | sdirty
    new = HashTable.create(new_cap, tuple(k.dtype for k in table.keys))
    new, slots, _, _ = lookup_or_insert(new, table.keys, keep)
    new = set_live(new, jnp.where(keep, slots, -1), table.live)
    idx = jnp.where(keep, slots, new_cap)
    new_maxes = jnp.full(new_cap, jnp.iinfo(maxes.dtype).min, maxes.dtype)
    new_maxes = new_maxes.at[idx].set(maxes, mode="drop")
    new_sdirty = jnp.zeros(new_cap, jnp.bool_).at[idx].set(sdirty, mode="drop")
    new_stored = jnp.zeros(new_cap, jnp.bool_).at[idx].set(stored, mode="drop")
    return new, new_maxes, new_sdirty, new_stored


class DynamicMaxFilterExecutor(Executor, Checkpointable):
    """Append-only: pass rows with ``value_col >= running max`` of their
    ``group_col`` group. Conservative (may pass superseded rows; never
    drops a row that could still match a future group max)."""

    def __init__(
        self,
        group_col: str,
        value_col: str,
        schema_dtypes: Dict[str, object],
        capacity: int = 1 << 14,
        window_key: Optional[Tuple[str, int]] = None,
        table_id: str = "dynfilter",
        bucket_policy: Optional[BucketPolicy] = None,
        bucketed: bool = True,
    ):
        self.group_col = group_col
        self.value_col = value_col
        self.table_id = table_id
        self.table = HashTable.create(
            capacity, (jnp.dtype(schema_dtypes[group_col]),)
        )
        vdtype = jnp.dtype(schema_dtypes[value_col])
        self.maxes = jnp.full(capacity, jnp.iinfo(vdtype).min, vdtype)
        self.sdirty = jnp.zeros(capacity, jnp.bool_)
        self.stored = jnp.zeros(capacity, jnp.bool_)
        self.window_key = window_key
        # shape-stability: the per-window max state walks a declared
        # pow2 bucket lattice (grow-eager/shrink-lazy hysteresis);
        # bucketed=False keeps the legacy unbounded-rehash twin (the
        # RW-E803 wedge class, for tests and soak baselines)
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
        return {
            "expects": {
                self.group_col: self.table.keys[0].dtype,
                self.value_col: self.maxes.dtype,
            },
            "keys": (self.group_col,),
            "table_ids": (self.table_id,),
            "window_key": self.window_key[0] if self.window_key else None,
        }

    def trace_contract(self):
        contract = {
            "kind": "device",
            "trace_step": lambda c: _filter_step(
                self.table,
                self.maxes,
                self.sdirty,
                c,
                self.group_col,
                self.value_col,
            ),
            "state": (self.table, self.maxes),
            "donate": True,
            "emission": "passthrough",
            # the per-window max state draws its capacities from the
            # allocator's declared pow2 lattice — the q7 pre-filter is
            # off the wedge class (None only on the unbucketed twin)
            "window_buckets": (
                self._buckets.lattice if self._buckets is not None else None
            ),
        }
        if self._buckets is not None:
            # the interpreted growth path's packed read exists only
            # where interpretation runs (the fused wrapper plans from
            # barrier notes instead) — fallback-only, not a blocker
            contract["fallback_syncs"] = ("_maybe_grow",)
        return contract

    def pin_max_bucket(self):
        """ShapeGovernor hook: freeze the max-state at its high-water
        bucket (shrink disabled; regrow applied by the next apply)."""
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
        if self.group_col in chunk.nulls or self.value_col in chunk.nulls:
            raise ValueError("dynamic filter columns must be non-nullable")
        self._maybe_grow(chunk.capacity)
        self._bound += chunk.capacity
        (
            self.table,
            self.maxes,
            self.sdirty,
            out,
            saw_delete,
            dropped,
        ) = _filter_step(
            self.table,
            self.maxes,
            self.sdirty,
            chunk,
            self.group_col,
            self.value_col,
        )
        self._saw_delete = self._saw_delete | saw_delete
        self._dropped = self._dropped | dropped
        return [out]

    def _grow_hint(self, incoming: int):
        """The FUSED wrapper's pre-dispatch growth bookkeeping: ZERO
        device reads — one emergency bucket bump per epoch at most
        (BucketAllocator.bump; the host bound counts padded chunk
        capacities, so exact sizing from it over-grows); ordinary
        growth/shrink resolves at the barrier from the staged true
        occupancy note."""
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
            self.table, self.maxes, self.sdirty, self.stored = _rebuild(
                self.table, self.maxes, self.sdirty, self.stored, new_cap
            )
            self._bound = min(self._bound, new_cap)
        self._grew_midepoch = True

    def _maybe_grow(self, incoming: int):
        """INTERPRETED-path growth: the exact legacy policy (one
        packed blocking read when the trigger trips). Declared under
        ``fallback_syncs`` on bucketed instances — the fused program
        replaces it with _grow_hint + barrier-note planning, so the
        read runs only where interpretation runs."""
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
            self.table, self.maxes, self.sdirty, self.stored = _rebuild(
                self.table, self.maxes, self.sdirty, self.stored, new_cap
            )
            claimed = int(self.table.occupancy())
        self._bound = claimed

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
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
            # barrier-boundary planning from the TRUE note: grow past
            # the load factor, apply pending lazy shrink, honor a
            # governor pin — zero mid-epoch device reads. The margin
            # keeps a shrink from landing below what the mid-epoch
            # overflow guard would immediately regrow.
            new_cap = self._buckets.plan(
                cap,
                0,
                int(claimed),
                int(survivors),
                margin=max(int(claimed), epoch_inc),
            )
            if new_cap is not None and new_cap != cap:
                (
                    self.table,
                    self.maxes,
                    self.sdirty,
                    self.stored,
                ) = _rebuild(
                    self.table, self.maxes, self.sdirty, self.stored, new_cap
                )
        if saw_delete:
            raise RuntimeError("dynamic max filter received a DELETE")
        if dropped:
            raise RuntimeError(
                "dynamic filter table overflowed MAX_PROBE; grow capacity"
            )

    def on_watermark(self, watermark: Watermark):
        if self.window_key is None or watermark.column != self.window_key[0]:
            return watermark, []
        cutoff = jnp.asarray(watermark.value - self.window_key[1], jnp.int64)
        lane = self.table.keys[0]
        expired = self.table.live & (lane < cutoff)
        slots = jnp.where(
            expired, jnp.arange(self.table.capacity, dtype=jnp.int32), -1
        )
        self.table = set_live(self.table, slots, False)
        self.sdirty = self.sdirty | expired
        return watermark, []

    # -- integrity --------------------------------------------------------
    def digest_lanes(self):
        from risingwave_tpu.integrity import filter_lanes

        return filter_lanes(self.table, self.maxes)

    def state_digest(self) -> int:
        """Host twin of the fused digest lane (integrity.filter_lanes)."""
        from risingwave_tpu.integrity import host_digest

        return host_digest(*self.digest_lanes())

    # -- checkpoint/restore ----------------------------------------------
    def checkpoint_delta(self):
        import numpy as np

        marks = classify_marks(self.sdirty, self.table.live, self.stored)
        self.sdirty, self.stored = marks.sdirty, marks.stored
        if not len(marks):
            return []
        pulled = pull_rows(
            {"k0": self.table.keys[0], "max": self.maxes}, marks
        )
        keys = {"k0": pulled["k0"]}
        vals = {"max": pulled["max"]}
        return [
            StateDelta(self.table_id, keys, vals, marks.tombstone, ("k0",))
        ]

    def restore_state(self, table_id, key_cols, value_cols):
        import numpy as np

        n = len(next(iter(key_cols.values()))) if key_cols else 0
        kd = self.table.keys[0].dtype
        vdtype = self.maxes.dtype
        cap = grow_pow2(n, self.table.capacity, GROW_AT)
        table = HashTable.create(cap, (kd,))
        maxes = jnp.full(cap, jnp.iinfo(vdtype).min, vdtype)
        self.sdirty = jnp.zeros(cap, jnp.bool_)
        self.stored = jnp.zeros(cap, jnp.bool_)
        if n:
            lanes = (jnp.asarray(np.asarray(key_cols["k0"], dtype=kd)),)
            table, slots, _, _ = lookup_or_insert(
                table, lanes, jnp.ones(n, jnp.bool_)
            )
            table = set_live(table, slots, True)
            maxes = maxes.at[slots].set(
                jnp.asarray(value_cols["max"].astype(vdtype))
            )
            self.stored = self.stored.at[slots].set(True)
        self.table, self.maxes = table, maxes
        self._bound = int(n)
        self._saw_delete = jnp.zeros((), jnp.bool_)
        self._dropped = jnp.zeros((), jnp.bool_)


# ---------------------------------------------------------------------------
# General dynamic filter (comparator, both directions)
# ---------------------------------------------------------------------------

_CMP = {
    ">": lambda v, rv: v > rv,
    ">=": lambda v, rv: v >= rv,
    "<": lambda v, rv: v < rv,
    "<=": lambda v, rv: v <= rv,
}


@partial(
    jax.jit,
    static_argnames=("op", "pk", "names", "value_col"),
    donate_argnums=(0, 1, 2, 3),
)
def _dyn_left_step(
    table, rows, passing, sdirty, chunk, rv, rv_valid, op, pk, names,
    value_col,
):
    """Store the left chunk's rows and pass through the comparator
    against the CURRENT right value (right moves apply at the barrier,
    dynamic_filter.rs semantics, so cmp(value, rv) == the row's emitted
    status for every stored row)."""
    keys = tuple(chunk.col(k) for k in pk)
    signs = chunk.effective_signs()
    active = chunk.valid & (signs != 0)
    table, slots, _, _ = lookup_or_insert(table, keys, active)
    dropped = jnp.any(active & (slots < 0))
    idx = jnp.where(active, slots, table.capacity)
    rows = {
        n: rows[n].at[idx].set(chunk.col(n), mode="drop") for n in names
    }
    table = set_live(table, jnp.where(active, slots, -1), signs > 0)
    sdirty = sdirty.at[idx].set(True, mode="drop")
    ok = chunk.valid & rv_valid & _CMP[op](chunk.col(value_col), rv)
    passing = passing.at[idx].set(ok & (signs > 0), mode="drop")
    return table, rows, passing, sdirty, chunk.mask(ok), dropped


@partial(jax.jit, static_argnames=("op", "value_col"), donate_argnums=(2,))
def _dyn_rv_diff(table, rows, passing, rv, rv_valid, op, value_col):
    """The right value moved: recompute the pass set; rows whose status
    flipped are the emission delta (promotions AND retractions — both
    directions of movement)."""
    mask_new = table.live & rv_valid & _CMP[op](rows[value_col], rv)
    changed = mask_new != passing
    return mask_new, changed


class DynamicFilterExecutor(Executor, Checkpointable):
    """General dynamic filter (dynamic_filter.rs:40): emits left rows
    satisfying ``value_col <op> right_value`` where the right side is a
    1-row change stream (e.g. a SimpleAgg MAX). Right moves apply at
    the barrier and re-emit/retract previously filtered/passed rows
    from the device row store — BOTH directions, full retraction."""

    def __init__(
        self,
        value_col: str,
        op: str,
        pk: Sequence[str],
        schema_dtypes: Dict[str, object],
        capacity: int = 1 << 14,
        table_id: str = "dynfilter_general",
        bucket_policy: Optional[BucketPolicy] = None,
        bucketed: bool = True,
    ):
        if op not in _CMP:
            raise ValueError(f"unsupported comparator {op!r}")
        self._buckets = (
            BucketAllocator(
                bucket_policy or BucketPolicy.from_capacity(capacity, grow_at=GROW_AT)
            )
            if bucketed
            else None
        )
        self.op = op
        self.value_col = value_col
        self.pk = tuple(pk)
        self.names = tuple(sorted(schema_dtypes))
        self._dtypes = {n: jnp.dtype(schema_dtypes[n]) for n in self.names}
        self.table = HashTable.create(
            capacity, tuple(self._dtypes[k] for k in self.pk)
        )
        self.rows = {
            n: jnp.zeros(capacity, self._dtypes[n]) for n in self.names
        }
        self.passing = jnp.zeros(capacity, jnp.bool_)
        self.sdirty = jnp.zeros(capacity, jnp.bool_)
        self.stored = jnp.zeros(capacity, jnp.bool_)
        vd = self._dtypes[self.value_col]
        self.rv = jnp.zeros((), vd)
        self.rv_valid = jnp.zeros((), jnp.bool_)
        self._staged_rv = None  # (device value, device valid) pending
        self._rv_dirty = True  # first checkpoint must persist the rv
        self.table_id = table_id
        self._bound = 0
        self._dropped = jnp.zeros((), jnp.bool_)

    # -- left input -------------------------------------------------------
    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        return self.apply_left(chunk)

    def apply_left(self, chunk: StreamChunk) -> List[StreamChunk]:
        for c in self.pk + (self.value_col,):
            if c in chunk.nulls:
                raise ValueError(
                    f"dynamic filter column {c!r} cannot be NULL"
                )
        self._maybe_grow(chunk.capacity)
        self._bound += chunk.capacity
        (
            self.table,
            self.rows,
            self.passing,
            self.sdirty,
            out,
            dropped,
        ) = _dyn_left_step(
            self.table,
            self.rows,
            self.passing,
            self.sdirty,
            chunk,
            self.rv,
            self.rv_valid,
            self.op,
            self.pk,
            self.names,
            self.value_col,
        )
        self._dropped = self._dropped | dropped
        return [out]

    # -- right input (1-row change stream) --------------------------------
    def apply_right(self, chunk: StreamChunk) -> List[StreamChunk]:
        signs = chunk.effective_signs()
        ins = chunk.valid & (signs > 0)
        dels = chunk.valid & (signs < 0)
        pos = jnp.arange(chunk.capacity, dtype=jnp.int32)
        last_ins = jnp.max(jnp.where(ins, pos, -1))
        last_del = jnp.max(jnp.where(dels, pos, -1))
        has_ins = last_ins >= 0
        v = chunk.col(self.value_col)[jnp.maximum(last_ins, 0)]
        if self._staged_rv is None:
            prev_v, prev_valid = self.rv, self.rv_valid
        else:
            prev_v, prev_valid = self._staged_rv
        # rows apply IN ORDER (dynamic_filter.rs): the LAST op decides
        # validity — an insert followed by its own retraction nets out
        # to no right value
        new_v = jnp.where(has_ins, v.astype(self.rv.dtype), prev_v)
        new_valid = jnp.where(
            last_ins > last_del,
            True,
            jnp.where(last_del > last_ins, False, prev_valid),
        )
        self._staged_rv = (new_v, new_valid)
        return []

    def pin_max_bucket(self):
        """ShapeGovernor hook (see DynamicMaxFilterExecutor)."""
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

    def _maybe_grow(self, incoming: int):
        cap = self.table.capacity
        if not needs_plan(self._buckets, cap, self._bound, incoming, GROW_AT):
            return
        claimed, survivors = read_scalars(
            self.table.occupancy(),
            jnp.sum((self.table.live | self.sdirty).astype(jnp.int32)),
        )
        new_cap = plan_capacity(
            self._buckets, cap, incoming, claimed, survivors, GROW_AT
        )
        if new_cap is not None:
            keep = self.table.live | self.sdirty
            new = HashTable.create(
                new_cap, tuple(k.dtype for k in self.table.keys)
            )
            new, slots, _, _ = lookup_or_insert(new, self.table.keys, keep)
            new = set_live(
                new, jnp.where(keep, slots, -1), self.table.live
            )
            idx = jnp.where(keep, slots, new_cap)

            def move(a):
                return (
                    jnp.zeros(new_cap, a.dtype).at[idx].set(a, mode="drop")
                )

            self.rows = {n: move(a) for n, a in self.rows.items()}
            self.passing = move(self.passing)
            self.sdirty = move(self.sdirty)
            self.stored = move(self.stored)
            self.table = new
            claimed = int(self.table.occupancy())
        self._bound = claimed

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        if bool(self._dropped):
            raise RuntimeError(
                "dynamic filter row store overflowed; grow capacity"
            )
        if self._buckets is not None:
            # host-tracked bound (an upper estimate of claimed): lazy
            # shrink stays conservative without an extra device read
            self._buckets.note_barrier(self.table.capacity, self._bound)
        if self._staged_rv is None:
            return []
        self.rv, self.rv_valid = self._staged_rv
        self._staged_rv = None
        self._rv_dirty = True
        mask_new, changed = _dyn_rv_diff(
            self.table,
            self.rows,
            self.passing,
            self.rv,
            self.rv_valid,
            self.op,
            self.value_col,
        )
        self.passing = mask_new
        # flipped rows must re-stage: a checkpoint persisting the new
        # rv with the OLD pass flags would double-retract (or lose)
        # rows after recovery when the rv moves again
        self.sdirty = self.sdirty | changed
        sel = np.flatnonzero(np.asarray(changed))
        if not len(sel):
            return []
        lanes = {n: self.rows[n] for n in self.names}
        lanes["__now__"] = mask_new
        pulled = pull_rows(lanes, sel)
        from risingwave_tpu.types import Op

        now = np.asarray(pulled["__now__"])
        outs = []
        for promote in (False, True):
            m = now == promote
            if not m.any():
                continue
            cols = {
                n: np.asarray(pulled[n])[m].astype(self._dtypes[n])
                for n in self.names
            }
            outs.append(
                StreamChunk.from_numpy(
                    cols,
                    # pow2-padded emission (masked lanes): downstream
                    # programs see a log-bounded capacity set, not one
                    # shape per distinct flip count (legacy max(2, n)
                    # on the unbucketed twin)
                    emission_bucket(int(m.sum()))
                    if self._buckets is not None
                    else max(2, int(m.sum())),
                    ops=np.full(
                        int(m.sum()),
                        int(Op.INSERT if promote else Op.DELETE),
                        np.int32,
                    ),
                )
            )
        return outs

    # -- integrity --------------------------------------------------------
    def digest_lanes(self):
        lanes = {f"k{i}": k for i, k in enumerate(self.table.keys)}
        live = self.table.live
        for n in self.names:
            lanes[f"r_{n}"] = self.rows[n]
        lanes["pass"] = self.passing
        # the 1-row right value folds in as broadcast scalars so the
        # fold stays a single masked reduction
        lanes["rv"] = jnp.where(
            live, self.rv, jnp.zeros((), self.rv.dtype)
        )
        lanes["rvv"] = jnp.where(live, self.rv_valid, False)
        return lanes, live

    def state_digest(self) -> int:
        from risingwave_tpu.integrity import host_digest

        return host_digest(*self.digest_lanes())

    # -- checkpoint/restore ----------------------------------------------
    def checkpoint_table_ids(self):
        return [f"{self.table_id}.rows", f"{self.table_id}.rv"]

    def checkpoint_delta(self):
        out = []
        marks = classify_marks(self.sdirty, self.table.live, self.stored)
        self.sdirty, self.stored = marks.sdirty, marks.stored
        if len(marks):
            lanes = {
                f"k{i}": lane for i, lane in enumerate(self.table.keys)
            }
            key_names = tuple(lanes)
            for n in self.names:
                lanes[f"r_{n}"] = self.rows[n]
            lanes["pass"] = self.passing
            pulled = pull_rows(lanes, marks)
            keys = {k: pulled[k] for k in key_names}
            vals = {k: v for k, v in pulled.items() if k not in key_names}
            out.append(
                StateDelta(
                    f"{self.table_id}.rows", keys, vals, marks.tombstone,
                    key_names,
                )
            )
        if self._rv_dirty:
            # the right value: a 1-row table
            rv, rvv = np.asarray(self.rv), bool(self.rv_valid)
            out.append(
                StateDelta(
                    f"{self.table_id}.rv",
                    {"k0": np.zeros(1, np.int64)},
                    {"rv": rv[None], "rv_valid": np.asarray([rvv])},
                    np.zeros(1, bool),
                    ("k0",),
                )
            )
            self._rv_dirty = False
        return out

    def restore_state(self, table_id, key_cols, value_cols):
        if table_id.endswith(".rv"):
            if key_cols:
                self.rv = jnp.asarray(
                    value_cols["rv"][0].astype(self.rv.dtype)
                )
                self.rv_valid = jnp.asarray(bool(value_cols["rv_valid"][0]))
            return
        n = len(next(iter(key_cols.values()))) if key_cols else 0
        cap = grow_pow2(n, self.table.capacity, GROW_AT)
        key_dtypes = tuple(k.dtype for k in self.table.keys)
        table = HashTable.create(cap, key_dtypes)
        rows = {nm: jnp.zeros(cap, self._dtypes[nm]) for nm in self.names}
        self.passing = jnp.zeros(cap, jnp.bool_)
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
            rows = {
                nm: a.at[slots].set(
                    jnp.asarray(
                        np.asarray(value_cols[f"r_{nm}"]).astype(a.dtype)
                    )
                )
                for nm, a in rows.items()
            }
            self.passing = self.passing.at[slots].set(
                jnp.asarray(value_cols["pass"].astype(bool))
            )
            self.stored = self.stored.at[slots].set(True)
        self.table = table
        self.rows = rows
        self._bound = int(n)
        self._dropped = jnp.zeros((), jnp.bool_)
        self._staged_rv = None
