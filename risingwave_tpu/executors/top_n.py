"""GroupTopN executor — per-group top-k band maintenance.

Reference: src/stream/src/executor/top_n/ — ``group_top_n.rs:63`` with
``top_n_cache.rs`` band logic and the append-only specialization
(``top_n_appendonly.rs``). This is the APPEND-ONLY variant (the
reference planner picks it for insert-only inputs, e.g. Nexmark
queries); retractable GroupTopN needs state-table refill below the
band and lands with the batch read path.

TPU re-design: no per-group cache objects — group bands are fixed-
shape device arrays: ``order``/payload/(capacity, k) with a validity
mask, maintained by ONE fused kernel per chunk:

1. each row finds its group slot (ops/hash_table);
2. the chunk's rows and the TOUCHED groups' current bands merge into
   one (n*(k+1),) array which is lexsorted by (slot, order-key);
3. rank-within-group < k survives; survivors scatter back as the new
   band; band rows that fell out emit DELETE, chunk rows that entered
   emit INSERT — exactly the reference's cache-delta emission.

The order key is one int64 lane; DESC encodes as bitwise-NOT (~x is
exact two's-complement negation-minus-one, total-order preserving).
Ties favor incumbents (stable sort places band entries first), which
minimizes churn — the reference's cache behaves the same way.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor, Watermark
from risingwave_tpu.ops.hash_table import (
    HashTable,
    first_occurrence_mask,
    lookup_or_insert,
    set_live,
)
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
from risingwave_tpu.types import Op

GROW_AT = 0.5


@partial(
    jax.jit,
    static_argnames=("group_keys", "order_col", "desc", "k", "payload", "out_cap"),
    donate_argnums=(0, 1),
)
def _topn_step(
    table: HashTable,
    state: Dict[str, jnp.ndarray],  # order/band_valid/sdirty + payload lanes
    chunk: StreamChunk,
    group_keys: Tuple[str, ...],
    order_col: str,
    desc: bool,
    k: int,
    payload: Tuple[str, ...],
    out_cap: int,
):
    key_cols = tuple(chunk.col(g) for g in group_keys)
    signs = chunk.effective_signs()
    saw_delete = jnp.any(chunk.valid & (signs < 0))
    valid = chunk.valid & (signs > 0)

    table, slots, _, _ = lookup_or_insert(table, key_cols, valid)
    table = set_live(table, jnp.where(valid, slots, -1), True)
    dropped = jnp.any(valid & (slots < 0))
    valid = valid & (slots >= 0)
    cap = table.capacity
    n = valid.shape[0]
    sl = jnp.maximum(slots, 0)
    sdirty = state["sdirty"].at[jnp.where(valid, slots, cap)].set(
        True, mode="drop"
    )

    order_in = chunk.col(order_col).astype(jnp.int64)
    if desc:
        order_in = ~order_in

    # ---- build the combined (band ∪ chunk) array, length n*(k+1) -----
    fmask = first_occurrence_mask(slots, valid)  # one band copy per group
    band_order = state["order"][sl]  # (n, k)
    band_vld = state["band_valid"][sl] & fmask[:, None]

    big = jnp.int64(1) << 62
    c_slot = jnp.concatenate(
        [jnp.repeat(sl, k), sl]
    )  # band entries then chunk rows
    c_valid = jnp.concatenate([band_vld.reshape(-1), valid])
    c_order = jnp.concatenate([band_order.reshape(-1), order_in])
    c_origin = jnp.concatenate(  # 0 = incumbent band, 1 = chunk row
        [jnp.zeros(n * k, jnp.bool_), jnp.ones(n, jnp.bool_)]
    )
    # band entry i's source position for payload gather:
    band_src = jnp.concatenate(
        [jnp.repeat(sl, k) * k + jnp.tile(jnp.arange(k), n), jnp.zeros(n, jnp.int32)]
    )
    chunk_src = jnp.concatenate([jnp.zeros(n * k, jnp.int32), jnp.arange(n, dtype=jnp.int32)])

    skey = jnp.where(c_valid, c_slot.astype(jnp.int64), big)
    okey = jnp.where(c_valid, c_order, big)
    perm = jnp.lexsort((okey, skey))  # by slot, then order; stable

    s_sorted = skey[perm]
    seq = jnp.arange(n * (k + 1), dtype=jnp.int32)
    is_new = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), s_sorted[1:] != s_sorted[:-1]]
    )
    start = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_new, seq, jnp.int32(0))
    )
    rank = seq - start
    kept_sorted = (rank < k) & (s_sorted < big)
    kept = jnp.zeros(n * (k + 1), jnp.bool_).at[perm].set(kept_sorted)
    new_pos = jnp.zeros(n * (k + 1), jnp.int32).at[perm].set(rank)

    # ---- write the new bands (clear touched groups, scatter kept) ----
    touched = jnp.where(valid & fmask, slots, cap)
    clear_valid = state["band_valid"].at[touched].set(False, mode="drop")
    dst = jnp.where(kept, c_slot * k + new_pos, cap * k)

    def band_scatter(dst_arr_flat, values):
        return dst_arr_flat.at[dst].set(values, mode="drop")

    new_band_valid = band_scatter(
        clear_valid.reshape(-1), jnp.ones(n * (k + 1), jnp.bool_)
    ).reshape(cap, k)
    gathered = {}
    new_state = {"band_valid": new_band_valid, "sdirty": sdirty}
    for name in ("order",) + payload:
        lane2d = state[name]
        src_col = order_in if name == "order" else chunk.col(name)
        c_vals = jnp.where(
            c_origin,
            src_col[chunk_src],
            lane2d.reshape(-1)[band_src],
        )
        gathered[name] = c_vals
        new_state[name] = band_scatter(
            lane2d.reshape(-1), c_vals
        ).reshape(cap, k)
    new_state["stored"] = state["stored"]

    # ---- emissions: chunk rows entering, band rows leaving ------------
    emit_ins = kept & c_origin & c_valid
    emit_del = ~kept & ~c_origin & c_valid
    emit = emit_ins | emit_del
    pos = jnp.cumsum(emit.astype(jnp.int32)) - 1
    overflow = jnp.any(emit & (pos >= out_cap))
    eidx = jnp.where(emit & (pos < out_cap), pos, out_cap)

    def compact(src):
        return jnp.zeros(out_cap, src.dtype).at[eidx].set(src, mode="drop")

    out_cols = {}
    for i, g in enumerate(group_keys):
        out_cols[g] = compact(table.keys[i][c_slot])
    for name in ("order",) + payload:
        if name == "order":
            ov = gathered[name]  # decode DESC's bitwise-NOT back
            out_cols[order_col] = compact(~ov if desc else ov)
        else:
            out_cols[name] = compact(gathered[name])
    out_ops = compact(
        jnp.where(emit_ins, jnp.int32(Op.INSERT), jnp.int32(Op.DELETE))
    )
    out_valid = jnp.zeros(out_cap, jnp.bool_).at[eidx].set(emit, mode="drop")
    out = StreamChunk(
        columns=out_cols, valid=out_valid, nulls={}, ops=out_ops
    )
    return table, new_state, out, saw_delete, dropped, overflow


@partial(jax.jit, static_argnames=("new_cap",))
def _topn_rebuild(table: HashTable, state: Dict[str, jnp.ndarray], new_cap: int):
    keep = (table.live | state["sdirty"]) & (table.fp1 != jnp.uint32(0))
    new_table = HashTable.create(new_cap, tuple(x.dtype for x in table.keys))
    new_table, slots, _, _ = lookup_or_insert(new_table, table.keys, keep)
    new_table = set_live(new_table, jnp.where(keep, slots, -1), table.live)
    idx = jnp.where(keep, slots, new_cap)
    k = state["band_valid"].shape[1]
    new_state = {}
    for name, a in state.items():
        if a.ndim == 2:
            buf = jnp.zeros((new_cap + 1, k), a.dtype)
            new_state[name] = buf.at[idx].set(a, mode="drop")[:new_cap]
        else:
            buf = jnp.zeros(new_cap, a.dtype)
            new_state[name] = buf.at[idx].set(a, mode="drop")
    return new_table, new_state


class GroupTopNExecutor(Executor, Checkpointable):
    """Append-only per-group TOP k BY order_col [DESC].

    Emits the top-k delta stream: INSERT when a row enters its group's
    top k, DELETE when a newcomer pushes it out. The emitted chunk
    carries the group keys, the order column, and the payload columns.
    """

    def __init__(
        self,
        group_keys: Sequence[str],
        order_col: str,
        k: int,
        schema_dtypes: Dict[str, object],
        payload: Sequence[str] = (),
        desc: bool = True,
        capacity: int = 1 << 14,
        out_cap: int = 1 << 13,
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
        self.group_keys = tuple(group_keys)
        self.order_col = order_col
        self.k = k
        self.desc = desc
        self.payload = tuple(p for p in payload if p != order_col)
        self.out_cap = out_cap
        self.window_key = window_key
        self.table_id = table_id
        self._dtypes = dict(schema_dtypes)
        self.table = HashTable.create(
            capacity, tuple(jnp.dtype(self._dtypes[g]) for g in self.group_keys)
        )
        self.state = {
            "order": jnp.zeros((capacity, k), jnp.int64),
            "band_valid": jnp.zeros((capacity, k), jnp.bool_),
            "sdirty": jnp.zeros(capacity, jnp.bool_),
            "stored": jnp.zeros(capacity, jnp.bool_),
        }
        for p in self.payload:
            self.state[p] = jnp.zeros(
                (capacity, k), jnp.dtype(self._dtypes[p])
            )
        self._bound = 0
        self._saw_delete = jnp.zeros((), jnp.bool_)
        self._dropped = jnp.zeros((), jnp.bool_)
        self._overflow = jnp.zeros((), jnp.bool_)

    def lint_info(self):
        cols = self.group_keys + (self.order_col,) + self.payload
        return {
            "expects": {
                c: self._dtypes[c] for c in cols if c in self._dtypes
            },
            "emits": {c: self._dtypes.get(c) for c in cols},
            "renames": {c: c for c in cols},
            "keys": self.group_keys,
            "table_ids": (self.table_id,),
            "window_key": self.window_key[0] if self.window_key else None,
        }

    def trace_contract(self):
        return {
            "kind": "device",
            "trace_step": lambda c: _topn_step(
                self.table,
                self.state,
                c,
                self.group_keys,
                self.order_col,
                self.desc,
                self.k,
                self.payload,
                self.out_cap,
            ),
            "state": (self.table, self.state),
            "donate": True,
            "emission": "fixed",
            "emission_caps": (self.out_cap,),
            # group table + band capacities walk the allocator's
            # declared pow2 lattice (None only on the unbucketed twin)
            "window_buckets": (
                self._buckets.lattice if self._buckets is not None else None
            ),
        }

    def pin_max_bucket(self):
        """ShapeGovernor hook: freeze the group bands at their
        high-water bucket (shrink disabled)."""
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
        for c in self.group_keys + (self.order_col,) + self.payload:
            if c in chunk.nulls:
                raise ValueError(f"TopN column {c!r} carries NULLs (unsupported)")
        self._maybe_grow(chunk.capacity)
        self._bound += chunk.capacity
        self.table, self.state, out, saw_delete, dropped, overflow = _topn_step(
            self.table,
            self.state,
            chunk,
            self.group_keys,
            self.order_col,
            self.desc,
            self.k,
            self.payload,
            self.out_cap,
        )
        self._saw_delete = self._saw_delete | saw_delete
        self._dropped = self._dropped | dropped
        self._overflow = self._overflow | overflow
        return [out]

    def _maybe_grow(self, incoming: int):
        cap = self.table.capacity
        if not needs_plan(self._buckets, cap, self._bound, incoming, GROW_AT):
            return
        claimed = int(self.table.occupancy())
        survivors = int(
            jnp.sum((self.table.live | self.state["sdirty"]).astype(jnp.int32))
        )
        new_cap = plan_capacity(
            self._buckets, cap, incoming, claimed, survivors, GROW_AT
        )
        if new_cap is not None:
            self.table, self.state = _topn_rebuild(
                self.table, self.state, new_cap
            )
            claimed = int(self.table.occupancy())
        self._bound = claimed

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        if self._buckets is not None:
            # host-tracked bound (upper estimate): shrink stays lazy
            # and conservative without an extra device read
            self._buckets.note_barrier(self.table.capacity, self._bound)
        if bool(self._saw_delete):
            raise RuntimeError("append-only TopN received a DELETE")
        if bool(self._dropped):
            raise RuntimeError("TopN group table overflowed; grow capacity")
        if bool(self._overflow):
            raise RuntimeError("TopN emission overflowed out_cap")
        return []

    def on_watermark(self, watermark: Watermark):
        if self.window_key is None or watermark.column != self.window_key[0]:
            return watermark, []
        cutoff = jnp.asarray(watermark.value - self.window_key[1], jnp.int64)
        lane = self.table.keys[self.group_keys.index(self.window_key[0])]
        expired = self.table.live & (lane < cutoff)
        slots = jnp.where(
            expired, jnp.arange(self.table.capacity, dtype=jnp.int32), -1
        )
        self.table = set_live(self.table, slots, False)
        self.state = dict(self.state)
        self.state["band_valid"] = self.state["band_valid"] & ~expired[:, None]
        self.state["sdirty"] = self.state["sdirty"] | expired
        return watermark, []

    # -- integrity --------------------------------------------------------
    def digest_lanes(self):
        bv = self.state["band_valid"]
        lanes = {f"k{i}": x for i, x in enumerate(self.table.keys)}
        lanes["bv"] = bv
        # band entries pre-masked by band_valid: stale bytes in vacated
        # band positions must not shift the digest
        lanes["order"] = jnp.where(bv, self.state["order"], 0)
        for p in self.payload:
            a = self.state[p]
            lanes[f"p_{p}"] = jnp.where(bv, a, jnp.zeros((), a.dtype))
        return lanes, self.table.live

    def state_digest(self) -> int:
        from risingwave_tpu.integrity import host_digest

        return host_digest(*self.digest_lanes())

    # -- checkpoint/restore ----------------------------------------------
    def checkpoint_delta(self):
        marks = classify_marks(
            self.state["sdirty"], self.table.live, self.state["stored"]
        )
        self.state = dict(
            self.state, sdirty=marks.sdirty, stored=marks.stored
        )
        if not len(marks):
            return []
        lanes = {f"k{i}": x for i, x in enumerate(self.table.keys)}
        key_names = tuple(lanes)
        lanes["bv"] = self.state["band_valid"]
        lanes["order"] = self.state["order"]
        for p in self.payload:
            lanes[f"p_{p}"] = self.state[p]
        pulled = pull_rows(lanes, marks)
        keys = {x: pulled[x] for x in key_names}
        vals = {x: v for x, v in pulled.items() if x not in key_names}
        return [
            StateDelta(self.table_id, keys, vals, marks.tombstone, key_names)
        ]

    def restore_state(self, table_id, key_cols, value_cols):
        n = len(next(iter(key_cols.values()))) if key_cols else 0
        cap = grow_pow2(n, self.table.capacity, GROW_AT)
        k = self.k
        key_dtypes = tuple(x.dtype for x in self.table.keys)
        table = HashTable.create(cap, key_dtypes)
        state = {
            "order": jnp.zeros((cap, k), jnp.int64),
            "band_valid": jnp.zeros((cap, k), jnp.bool_),
            "sdirty": jnp.zeros(cap, jnp.bool_),
            "stored": jnp.zeros(cap, jnp.bool_),
        }
        for p in self.payload:
            state[p] = jnp.zeros((cap, k), jnp.dtype(self._dtypes[p]))
        if n:
            lanes = tuple(
                jnp.asarray(np.asarray(key_cols[f"k{i}"], dtype=d))
                for i, d in enumerate(key_dtypes)
            )
            table, slots, _, _ = lookup_or_insert(
                table, lanes, jnp.ones(n, jnp.bool_)
            )
            table = set_live(table, slots, True)
            state["band_valid"] = state["band_valid"].at[slots].set(
                jnp.asarray(value_cols["bv"])
            )
            state["order"] = state["order"].at[slots].set(
                jnp.asarray(value_cols["order"])
            )
            for p in self.payload:
                state[p] = state[p].at[slots].set(
                    jnp.asarray(value_cols[f"p_{p}"].astype(state[p].dtype))
                )
            state["stored"] = state["stored"].at[slots].set(True)
        self.table, self.state = table, state
        self._bound = int(n)
        self._saw_delete = jnp.zeros((), jnp.bool_)
        self._dropped = jnp.zeros((), jnp.bool_)
        self._overflow = jnp.zeros((), jnp.bool_)
