"""Plain TopN — retractable ORDER BY ... LIMIT n maintenance.

Reference: src/stream/src/executor/top_n/top_n_plain.rs:77 — keeps all
input rows in a state table ordered by (order key, pk) and emits
deltas so downstream always holds exactly the current top n.

TPU re-design: the row store is a pk-keyed slot table (HashTable +
one lane per column); inserts/deletes are one fused scatter step per
chunk. The barrier ranks live rows ON DEVICE (ordered-float/int total
order + pk tiebreak via lexsort), pulls only the top n rows, and
diffs them against the host mirror of the previously-emitted top n —
so per-barrier host traffic is O(n), not O(state).
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor
from risingwave_tpu.ops.hash_table import (
    HashTable,
    lookup_or_insert,
    set_live,
)
from risingwave_tpu.runtime.bucketing import (
    BucketAllocator,
    BucketPolicy,
    emission_bucket,
    lattice_between,
    needs_plan,
    plan_capacity,
    pow2_at_least,
)
from risingwave_tpu.storage.state_table import (
    Checkpointable,
    StateDelta,
    grow_pow2,
    pull_rows,
    stage_marks,
)
from risingwave_tpu.types import Op

GROW_AT = 0.5


@partial(jax.jit, static_argnames=("pk", "names"), donate_argnums=(0, 1, 2))
def _upsert_step(table, rows, sdirty, chunk: StreamChunk, pk, names):
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
    return table, rows, sdirty, dropped


def _order_key_u64(v, desc: bool):
    """Map an order lane to an unsigned memcomparable key (the same
    transform the SST sort uses) so int/float/asc/desc all reduce to
    one uint64 comparison."""
    if jnp.issubdtype(v.dtype, jnp.floating):
        from risingwave_tpu.ops.agg import _float_to_order_key

        key = _float_to_order_key(v).astype(jnp.uint64)
    elif jnp.issubdtype(v.dtype, jnp.unsignedinteger):
        key = v.astype(jnp.uint64)
    else:
        key = jax.lax.bitcast_convert_type(
            v.astype(jnp.int64), jnp.uint64
        ) ^ (jnp.uint64(1) << jnp.uint64(63))
    return ~key if desc else key


@partial(jax.jit, static_argnames=("n", "desc"))
def _rank_top(table: HashTable, order_lane, n: int, desc: bool):
    """Indices of the top-n live rows by (order, pk-lanes) total order.
    Liveness is its own LEADING sort key: a dead-row sentinel value
    would collide with a legitimate INT64 extreme order value and let
    dead slots displace live rows."""
    live_last = (~table.live).astype(jnp.int32)
    key = _order_key_u64(order_lane, desc)
    sort_ops = jax.lax.sort(
        (live_last, key) + tuple(k for k in table.keys)
        + (jnp.arange(table.capacity, dtype=jnp.int32),),
        num_keys=2 + len(table.keys),
    )
    idx = sort_ops[-1][:n]
    alive = table.live[idx]
    return idx, alive


class TopNExecutor(Executor, Checkpointable):
    """ORDER BY order_col [DESC] LIMIT n with full retraction support."""

    def __init__(
        self,
        order_col: str,
        limit: int,
        pk: Sequence[str],
        schema_dtypes: Dict[str, object],
        desc: bool = False,
        capacity: int = 1 << 14,
        table_id: str = "top_n",
        bucket_policy: Optional[BucketPolicy] = None,
        bucketed: bool = True,
    ):
        self._buckets = (
            BucketAllocator(
                bucket_policy or BucketPolicy.from_capacity(capacity, grow_at=GROW_AT)
            )
            if bucketed
            else None
        )
        self.order_col = order_col
        self.limit = int(limit)
        self.desc = desc
        self.pk = tuple(pk)
        self.names = tuple(sorted(schema_dtypes))
        self._dtypes = {n: jnp.dtype(schema_dtypes[n]) for n in self.names}
        self.table = HashTable.create(
            capacity, tuple(self._dtypes[k] for k in self.pk)
        )
        self.rows = {
            n: jnp.zeros(capacity, self._dtypes[n]) for n in self.names
        }
        self.sdirty = jnp.zeros(capacity, jnp.bool_)
        self.stored = jnp.zeros(capacity, jnp.bool_)
        self.table_id = table_id
        self._bound = 0
        self._dropped = jnp.zeros((), jnp.bool_)
        self._emitted: Dict[Tuple, Tuple] = {}  # pk -> full row

    def lint_info(self):
        return {
            "expects": dict(self._dtypes),
            "emits": dict(self._dtypes),
            "renames": {n: n for n in self.names},
            "state_pk": tuple(self.pk),
            "table_ids": (self.table_id,),
        }

    def trace_contract(self):
        return {
            "kind": "device",
            "trace_step": lambda c: _upsert_step(
                self.table, self.rows, self.sdirty, c, self.pk, self.names
            ),
            "state": (self.table, self.rows),
            "donate": True,
            # the barrier diff against the host mirror now pads its
            # emissions to pow2 buckets (<= limit rows per op chunk):
            # a declared, closed capacity set instead of one shape per
            # distinct delta count (data_dependent on the legacy twin)
            **(
                {
                    "emission": "bucketed",
                    "emission_caps": lattice_between(
                        2, pow2_at_least(max(self.limit, 2))
                    ),
                }
                if self._buckets is not None
                else {"emission": "data_dependent"}
            ),
        }

    def pin_max_bucket(self):
        """ShapeGovernor hook: freeze the row store at its high-water
        bucket (shrink disabled)."""
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
        for k in self.pk + (self.order_col,):
            if k in chunk.nulls:
                raise ValueError(f"TopN key column {k!r} cannot be NULL")
        self._maybe_grow(chunk.capacity)
        self._bound += chunk.capacity
        self.table, self.rows, self.sdirty, dropped = _upsert_step(
            self.table, self.rows, self.sdirty, chunk, self.pk, self.names
        )
        self._dropped = self._dropped | dropped
        return []

    def _maybe_grow(self, incoming: int):
        cap = self.table.capacity
        if not needs_plan(self._buckets, cap, self._bound, incoming, GROW_AT):
            return
        claimed = int(self.table.occupancy())
        survivors = int(
            jnp.sum((self.table.live | self.sdirty).astype(jnp.int32))
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
            new = set_live(new, jnp.where(keep, slots, -1), self.table.live)
            idx = jnp.where(keep, slots, new_cap)

            def move(a, init_dtype):
                return (
                    jnp.zeros(new_cap, init_dtype)
                    .at[idx]
                    .set(a, mode="drop")
                )

            self.rows = {
                n: move(a, a.dtype) for n, a in self.rows.items()
            }
            self.sdirty = move(self.sdirty, jnp.bool_)
            self.stored = move(self.stored, jnp.bool_)
            self.table = new
            claimed = int(self.table.occupancy())
        self._bound = claimed

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        if self._buckets is not None:
            self._buckets.note_barrier(self.table.capacity, self._bound)
        if bool(self._dropped):
            raise RuntimeError("TopN row store overflowed; grow capacity")
        idx, alive = _rank_top(
            self.table, self.rows[self.order_col], self.limit, self.desc
        )
        # pull exactly n rows (one packed gather)
        lanes = {n: self.rows[n][idx] for n in self.names}
        lanes["__alive__"] = alive
        pulled = {k: np.asarray(v) for k, v in lanes.items()}
        top: Dict[Tuple, Tuple] = {}
        for i in range(self.limit):
            if not pulled["__alive__"][i]:
                break  # dead rows rank last: first dead = end of live
            pkv = tuple(pulled[k][i].item() for k in self.pk)
            top[pkv] = tuple(pulled[n][i].item() for n in self.names)
        outs = []
        dels = [v for k, v in self._emitted.items() if top.get(k) != v]
        ins = [v for k, v in top.items() if self._emitted.get(k) != v]
        for vals, op in ((dels, Op.DELETE), (ins, Op.INSERT)):
            if not vals:
                continue
            cols = {
                n: np.asarray([r[j] for r in vals], self._dtypes[n])
                for j, n in enumerate(self.names)
            }
            outs.append(
                StreamChunk.from_numpy(
                    cols,
                    # pow2-padded emission: a closed downstream shape set
                    emission_bucket(len(vals))
                    if self._buckets is not None
                    else max(2, len(vals)),
                    ops=np.full(len(vals), int(op), np.int32),
                )
            )
        self._emitted = top
        return outs

    # -- integrity --------------------------------------------------------
    def digest_lanes(self):
        lanes = {f"k{i}": k for i, k in enumerate(self.table.keys)}
        for n in self.names:
            lanes[f"r_{n}"] = self.rows[n]
        return lanes, self.table.live

    def state_digest(self) -> int:
        from risingwave_tpu.integrity import host_digest

        return host_digest(*self.digest_lanes())

    # -- checkpoint -------------------------------------------------------
    def checkpoint_delta(self) -> List[StateDelta]:
        sdirty = np.asarray(self.sdirty)
        if not sdirty.any():
            return []
        upsert, tomb, sel = stage_marks(
            sdirty, np.asarray(self.table.live), np.asarray(self.stored)
        )
        lanes = {f"k{i}": lane for i, lane in enumerate(self.table.keys)}
        key_names = tuple(lanes)
        for n in self.names:
            lanes[f"r_{n}"] = self.rows[n]
        pulled = pull_rows(lanes, sel)
        keys = {k: pulled[k] for k in key_names}
        vals = {k: v for k, v in pulled.items() if k not in key_names}
        self.stored = (self.stored | jnp.asarray(upsert)) & ~jnp.asarray(tomb)
        self.sdirty = jnp.zeros_like(self.sdirty)
        return [StateDelta(self.table_id, keys, vals, tomb[sel], key_names)]

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        n = len(next(iter(key_cols.values()))) if key_cols else 0
        cap = grow_pow2(n, self.table.capacity, GROW_AT)
        key_dtypes = tuple(k.dtype for k in self.table.keys)
        table = HashTable.create(cap, key_dtypes)
        rows = {nm: jnp.zeros(cap, self._dtypes[nm]) for nm in self.names}
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
            self.stored = self.stored.at[slots].set(True)
        self.table = table
        self.rows = rows
        self._bound = int(n)
        self._dropped = jnp.zeros((), jnp.bool_)
        # downstream MV was restored consistently; recompute its view
        idx, alive = _rank_top(
            table, rows[self.order_col], self.limit, self.desc
        )
        pulled = {nm: np.asarray(rows[nm][idx]) for nm in self.names}
        al = np.asarray(alive)
        self._emitted = {}
        for i in range(self.limit):
            if not al[i]:
                break
            pkv = tuple(pulled[k][i].item() for k in self.pk)
            self._emitted[pkv] = tuple(
                pulled[nm][i].item() for nm in self.names
            )


# ---------------------------------------------------------------------------
# Retractable GroupTopN
# ---------------------------------------------------------------------------


@partial(
    jax.jit, static_argnames=("pk", "names"), donate_argnums=(0, 1, 2, 3)
)
def _upsert_step_ed(table, rows, sdirty, epoch_dirty, chunk, pk, names):
    """_upsert_step that also marks epoch_dirty (cleared per barrier)
    in the same scatter — one probe, two mark lanes."""
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
    epoch_dirty = epoch_dirty.at[idx].set(True, mode="drop")
    return table, rows, sdirty, epoch_dirty, dropped


@partial(
    jax.jit,
    static_argnames=("k", "desc", "group_names", "order_col"),
    donate_argnums=(),
)
def _group_topk_mask(
    table: HashTable,
    rows: Dict[str, jnp.ndarray],
    epoch_dirty: jnp.ndarray,
    k: int,
    desc: bool,
    group_names: Tuple[str, ...],
    order_col: str,
):
    """Per-slot masks: is the row in its group's current top-k, and
    does its group contain an epoch-dirty row (so its top-k must be
    re-pulled)? One device sort over (group lanes, order key, pk)."""
    cap = table.capacity
    # liveness as its own sort key within the group (a dead-row
    # sentinel would collide with INT64-extreme order values)
    live_last = (~table.live).astype(jnp.int32)
    okey = _order_key_u64(rows[order_col], desc)
    glanes = tuple(rows[g] for g in group_names)
    sort_in = glanes + (live_last, okey) + tuple(table.keys) + (
        jnp.arange(cap, dtype=jnp.int32),
    )
    sorted_all = jax.lax.sort(
        sort_in, num_keys=len(glanes) + 2 + len(table.keys)
    )
    slot_s = sorted_all[-1]
    live_s = table.live[slot_s]
    dirty_s = epoch_dirty[slot_s]
    boundary = jnp.zeros(cap, jnp.bool_).at[0].set(True)
    for lane in sorted_all[: len(glanes)]:
        boundary = boundary | jnp.concatenate(
            [jnp.ones(1, jnp.bool_), lane[1:] != lane[:-1]]
        )
    gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    idx = jnp.arange(cap, dtype=jnp.int32)
    seg_start = jax.ops.segment_max(
        jnp.where(boundary, idx, 0), gid, num_segments=cap
    )[gid]
    in_topk_s = live_s & ((idx - seg_start) < k)
    gdirty_s = (
        jax.ops.segment_max(
            dirty_s.astype(jnp.int32), gid, num_segments=cap
        )[gid]
        > 0
    )
    in_topk = jnp.zeros(cap, jnp.bool_).at[slot_s].set(in_topk_s)
    gdirty = jnp.zeros(cap, jnp.bool_).at[slot_s].set(gdirty_s)
    return in_topk, gdirty


def _diff_touched_groups(
    table, rows, in_topk, epoch_dirty, group_by, pk, names, gdirty,
    emitted,
):
    """Pull touched groups' top-k (+ the epoch-dirty rows naming
    fully-emptied groups) and diff against the host mirror of what was
    emitted; updates ``emitted`` in place. Shared by the single-chip
    and the sharded executor (one shard = one call over its slices)."""
    mask = np.asarray((gdirty & in_topk) | epoch_dirty)
    sel = np.flatnonzero(mask)
    lanes = {n: rows[n] for n in names}
    lanes["__topk__"] = in_topk
    lanes["__live__"] = table.live
    pulled = pull_rows(lanes, sel)
    new_top: Dict[Tuple, Dict[Tuple, Tuple]] = {}
    changed: set = set()
    for i in range(len(sel)):
        g = tuple(pulled[c][i].item() for c in group_by)
        changed.add(g)
        if pulled["__topk__"][i] and pulled["__live__"][i]:
            pkv = tuple(pulled[c][i].item() for c in pk)
            new_top.setdefault(g, {})[pkv] = tuple(
                pulled[n][i].item() for n in names
            )
    dels, ins = [], []
    for g in changed:
        old = emitted.get(g, {})
        new = new_top.get(g, {})
        dels.extend(v for p, v in old.items() if new.get(p) != v)
        ins.extend(v for p, v in new.items() if old.get(p) != v)
        if new:
            emitted[g] = new
        else:
            emitted.pop(g, None)
    return dels, ins


def _emit_diffs(dels, ins, names, dtypes, bucketed=True) -> List[StreamChunk]:
    outs = []
    for vals, op in ((dels, Op.DELETE), (ins, Op.INSERT)):
        if not vals:
            continue
        cols = {
            n: np.asarray([r[j] for r in vals], dtypes[n])
            for j, n in enumerate(names)
        }
        outs.append(
            StreamChunk.from_numpy(
                cols,
                # pow2-padded emission (masked lanes): downstream sees
                # a log-bounded capacity set, not one per delta count;
                # the bucketed=False twin keeps the legacy max(2, n)
                # shape per distinct count (RW-E803 baseline behavior)
                emission_bucket(len(vals))
                if bucketed
                else max(2, len(vals)),
                ops=np.full(len(vals), int(op), np.int32),
            )
        )
    return outs


class RetractableGroupTopNExecutor(Executor, Checkpointable):
    """GROUP BY g ORDER BY o LIMIT k with full retraction support
    (group_top_n.rs:63): deletes/updates crossing a group's top-k
    boundary re-emit the displaced/promoted rows exactly.

    TPU re-design: ONE pk-keyed row store holds every input row; the
    barrier ranks rows within groups on device (one fused sort +
    segmented scan), pulls only the top-k rows of groups TOUCHED this
    epoch, and diffs them against a per-group host mirror of what was
    emitted — per-barrier host traffic is O(changed groups x k), never
    O(state)."""

    def __init__(
        self,
        group_by: Sequence[str],
        order_col: str,
        limit: int,
        pk: Sequence[str],
        schema_dtypes: Dict[str, object],
        desc: bool = False,
        capacity: int = 1 << 14,
        window_key: Optional[Tuple[str, int]] = None,
        table_id: str = "group_top_n",
        bucket_policy: Optional[BucketPolicy] = None,
        bucketed: bool = True,
    ):
        self._buckets = (
            BucketAllocator(
                bucket_policy or BucketPolicy.from_capacity(capacity, grow_at=GROW_AT)
            )
            if bucketed
            else None
        )
        self.group_by = tuple(group_by)
        self.order_col = order_col
        self.limit = int(limit)
        self.desc = desc
        self.pk = tuple(pk)
        # row identity INCLUDES the group (group_top_n.rs keys state by
        # group key + pk): a row "moving" groups is two distinct rows,
        # so the old group's retraction is never lost
        self.store_keys = self.group_by + tuple(
            c for c in self.pk if c not in self.group_by
        )
        self.names = tuple(sorted(schema_dtypes))
        self._dtypes = {n: jnp.dtype(schema_dtypes[n]) for n in self.names}
        self.table = HashTable.create(
            capacity, tuple(self._dtypes[c] for c in self.store_keys)
        )
        self.rows = {
            n: jnp.zeros(capacity, self._dtypes[n]) for n in self.names
        }
        self.sdirty = jnp.zeros(capacity, jnp.bool_)
        self.stored = jnp.zeros(capacity, jnp.bool_)
        self.epoch_dirty = jnp.zeros(capacity, jnp.bool_)
        if window_key is not None and window_key[0] not in self.group_by:
            raise ValueError(
                "window_key must be one of the group columns (a closed "
                "window bounds its groups)"
            )
        self.window_key = window_key
        self.table_id = table_id
        self._bound = 0
        self._dropped = jnp.zeros((), jnp.bool_)
        # group tuple -> {pk tuple -> full row tuple} of EMITTED rows
        self._emitted: Dict[Tuple, Dict[Tuple, Tuple]] = {}

    def lint_info(self):
        return {
            "expects": dict(self._dtypes),
            "emits": dict(self._dtypes),
            "renames": {n: n for n in self.names},
            "keys": self.group_by,
            "state_pk": tuple(self.store_keys),
            "table_ids": (self.table_id,),
            "window_key": self.window_key[0] if self.window_key else None,
        }

    def trace_contract(self):
        return {
            "kind": "device",
            "trace_step": lambda c: _upsert_step_ed(
                self.table,
                self.rows,
                self.sdirty,
                self.epoch_dirty,
                c,
                self.store_keys,
                self.names,
            ),
            "state": (self.table, self.rows),
            "donate": True,
            # the barrier ranks on device but diffs against a host
            # mirror; emissions are pow2-padded (bucketed) and the row
            # store walks the allocator's declared lattice (legacy
            # data_dependent/None only on the unbucketed twin)
            **(
                {
                    "emission": "bucketed",
                    "emission_caps": lattice_between(
                        2, self._buckets.policy.max_cap
                    ),
                    "window_buckets": self._buckets.lattice,
                }
                if self._buckets is not None
                else {
                    "emission": "data_dependent",
                    "window_buckets": None,
                }
            ),
        }

    def pin_max_bucket(self):
        """ShapeGovernor hook: freeze the row store at its high-water
        bucket (shrink disabled)."""
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
        for c in self.pk + self.group_by + (self.order_col,):
            if c in chunk.nulls:
                raise ValueError(f"GroupTopN key column {c!r} cannot be NULL")
        self._maybe_grow(chunk.capacity)
        self._bound += chunk.capacity
        (
            self.table,
            self.rows,
            self.sdirty,
            self.epoch_dirty,
            dropped,
        ) = _upsert_step_ed(
            self.table,
            self.rows,
            self.sdirty,
            self.epoch_dirty,
            chunk,
            self.store_keys,
            self.names,
        )
        self._dropped = self._dropped | dropped
        return []

    def _maybe_grow(self, incoming: int):
        cap = self.table.capacity
        if not needs_plan(self._buckets, cap, self._bound, incoming, GROW_AT):
            return
        from risingwave_tpu.ops.hash_table import read_scalars

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
                new_cap, tuple(x.dtype for x in self.table.keys)
            )
            new, slots, _, _ = lookup_or_insert(new, self.table.keys, keep)
            new = set_live(new, jnp.where(keep, slots, -1), self.table.live)
            idx = jnp.where(keep, slots, new_cap)

            def move(a):
                return (
                    jnp.zeros(new_cap, a.dtype).at[idx].set(a, mode="drop")
                )

            self.rows = {n: move(a) for n, a in self.rows.items()}
            self.sdirty = move(self.sdirty)
            self.stored = move(self.stored)
            self.epoch_dirty = move(self.epoch_dirty)
            self.table = new
            claimed = int(self.table.occupancy())
        self._bound = claimed

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        from risingwave_tpu.ops.hash_table import read_scalars

        # ONE packed read for the latch + the dirty short-circuit +
        # occupancy (device round-trips dominate)
        dropped, any_dirty, claimed = read_scalars(
            self._dropped, jnp.any(self.epoch_dirty), self.table.occupancy()
        )
        self._bound = int(claimed)
        if self._buckets is not None:
            self._buckets.note_barrier(self.table.capacity, int(claimed))
        if dropped:
            raise RuntimeError("GroupTopN row store overflowed; grow capacity")
        if not any_dirty:
            return []
        in_topk, gdirty = _group_topk_mask(
            self.table,
            self.rows,
            self.epoch_dirty,
            self.limit,
            self.desc,
            self.group_by,
            self.order_col,
        )
        # pull the top-k of touched groups PLUS the epoch-dirty rows
        # themselves (deleted rows name fully-emptied groups)
        dels, ins = _diff_touched_groups(
            self.table, self.rows, in_topk, self.epoch_dirty,
            self.group_by, self.pk, self.names, gdirty, self._emitted,
        )
        self.epoch_dirty = jnp.zeros_like(self.epoch_dirty)
        return _emit_diffs(
            dels,
            ins,
            self.names,
            self._dtypes,
            bucketed=self._buckets is not None,
        )

    def on_watermark(self, watermark):
        """Window-bounded groups expire silently below the watermark
        (EOWC-final: the MV keeps the closed window's final top-k)."""
        if self.window_key is None or watermark.column != self.window_key[0]:
            return watermark, []
        cutoff = jnp.asarray(
            watermark.value - self.window_key[1], jnp.int64
        )
        lane = self.rows[self.window_key[0]]
        expired = self.table.live & (lane < cutoff)
        slots = jnp.where(
            expired, jnp.arange(self.table.capacity, dtype=jnp.int32), -1
        )
        self.table = set_live(self.table, slots, False)
        self.sdirty = self.sdirty | expired
        # closed groups leave the mirror without emitting retractions
        gi = self.group_by.index(self.window_key[0])
        cut = int(watermark.value - self.window_key[1])
        for g in [g for g in self._emitted if g[gi] < cut]:
            del self._emitted[g]
        return watermark, []

    # -- checkpoint/restore (pk-keyed row store, plain-TopN layout) -------
    def digest_lanes(self):
        lanes = {f"k{i}": k for i, k in enumerate(self.table.keys)}
        for n in self.names:
            lanes[f"r_{n}"] = self.rows[n]
        return lanes, self.table.live

    def state_digest(self) -> int:
        from risingwave_tpu.integrity import host_digest

        return host_digest(*self.digest_lanes())

    def checkpoint_delta(self) -> List[StateDelta]:
        sdirty = np.asarray(self.sdirty)
        if not sdirty.any():
            return []
        upsert, tomb, sel = stage_marks(
            sdirty, np.asarray(self.table.live), np.asarray(self.stored)
        )
        lanes = {f"k{i}": lane for i, lane in enumerate(self.table.keys)}
        key_names = tuple(lanes)
        for n in self.names:
            lanes[f"r_{n}"] = self.rows[n]
        pulled = pull_rows(lanes, sel)
        keys = {x: pulled[x] for x in key_names}
        vals = {x: v for x, v in pulled.items() if x not in key_names}
        self.stored = (self.stored | jnp.asarray(upsert)) & ~jnp.asarray(tomb)
        self.sdirty = jnp.zeros_like(self.sdirty)
        return [StateDelta(self.table_id, keys, vals, tomb[sel], key_names)]

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        n = len(next(iter(key_cols.values()))) if key_cols else 0
        cap = grow_pow2(n, self.table.capacity, GROW_AT)
        key_dtypes = tuple(x.dtype for x in self.table.keys)
        table = HashTable.create(cap, key_dtypes)
        rows = {nm: jnp.zeros(cap, self._dtypes[nm]) for nm in self.names}
        self.sdirty = jnp.zeros(cap, jnp.bool_)
        self.stored = jnp.zeros(cap, jnp.bool_)
        self.epoch_dirty = jnp.zeros(cap, jnp.bool_)
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
            self.stored = self.stored.at[slots].set(True)
        self.table = table
        self.rows = rows
        self._bound = int(n)
        self._dropped = jnp.zeros((), jnp.bool_)
        # rebuild the emitted mirror: every group's current top-k (the
        # downstream MV restored to exactly this view)
        self._emitted = {}
        if n:
            in_topk, _ = _group_topk_mask(
                self.table,
                self.rows,
                jnp.ones(cap, jnp.bool_),
                self.limit,
                self.desc,
                self.group_by,
                self.order_col,
            )
            sel = np.flatnonzero(np.asarray(in_topk))
            pulled = pull_rows(
                {nm: self.rows[nm] for nm in self.names}, sel
            )
            for i in range(len(sel)):
                g = tuple(pulled[c][i].item() for c in self.group_by)
                pkv = tuple(pulled[c][i].item() for c in self.pk)
                self._emitted.setdefault(g, {})[pkv] = tuple(
                    pulled[nm][i].item() for nm in self.names
                )
