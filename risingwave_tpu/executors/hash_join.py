"""HashJoin executor — streaming two-sided equi-join with retraction.

Reference: src/stream/src/executor/hash_join.rs:129 (3,252 LoC) +
executor/join/hash_join.rs:157 (JoinHashMap + degree table). Semantics
matched for INNER / LEFT / RIGHT / FULL OUTER / LEFT|RIGHT SEMI /
LEFT|RIGHT ANTI:
- each arriving chunk updates its own side's multiset state and probes
  the other side, emitting one output row per (probe row, stored match)
  with the probe row's sign (execute_inner / hash_eq_match,
  hash_join.rs:462-729);
- outer/semi/anti variants ride per-stored-row DEGREE state: a row's
  degree is its current match count on the other side; zero-crossings
  drive NULL-pad retraction/revival (outer) or bare-row emission
  (semi/anti) — the reference's degree table semantics
  (join/hash_join.rs:157) realized as one extra (capacity, fanout)
  int32 lane updated by batched scatter (ops/join.degree_apply);
- barrier-aligned two-input operator: the runtime feeds chunks in
  arrival order via ``apply_left`` / ``apply_right`` and calls
  ``on_barrier`` once both inputs hit the barrier (barrier_align.rs);
- watermark on the window column cleans closed-window state on both
  sides (state cleaning via table watermarks, state_table.rs:1133).

TPU re-design: no per-key Vec + LRU cache — each side is a JoinSide
(ops/join.py): a device hash table over the join key plus fixed-fanout
row buckets, so one chunk's insert+delete+probe+emit runs as one fused
jitted program per side. Output pairs are compacted into fixed
``out_cap`` chunks (static shapes; overflow latches and raises at the
barrier, the capacity-growth contract shared with HashAgg).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor, Watermark
from risingwave_tpu.ops.hash_table import read_scalars, stage_scalars
from risingwave_tpu.ops.hash_table import lookup_or_insert, set_live
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
    host_key_view,
    lanes_from_host_keys,
    pull_rows,
)
from risingwave_tpu.ops.join import (
    JoinSide,
    apply_side,
    compact_pairs,
    degree_apply,
    expire_keys,
    gather_flat,
    gather_matches,
    probe_side,
    regrow,
)
from risingwave_tpu.types import Op

GROW_AT = 0.5
# mid-epoch rebuild only when the HOST insert bound nears the table
# itself (MAX_PROBE overflow risk); ordinary growth resolves at the
# barrier from the true occupancy note (HashAgg's twin constant)
HARD_GROW_AT = 0.75


JOIN_TYPES = (
    "inner",
    "left",
    "right",
    "full",
    "left_semi",
    "left_anti",
    "right_semi",
    "right_anti",
)


def join_step_fn(
    own: JoinSide,
    other: JoinSide,
    chunk: StreamChunk,
    own_keys: Tuple[str, ...],
    other_keys: Tuple[str, ...],
    own_names: Tuple[str, ...],
    other_names: Tuple[str, ...],
    out_cap: int,
    join_type: str = "inner",
    arrival: str = "l",
    out_names: Tuple[str, ...] = (),
):
    """One chunk through its own side + probe of the other side, with
    the full join-type matrix (reference hash_join.rs:129 inner/outer/
    semi/anti variants + degree tables join/hash_join.rs:157).

    Emission groups (all static-shape, compacted together):
    1. PAIRS (inner/outer): one row per (probe row, stored match),
       probe row's sign.
    2. OWN NULL-PAD / SEMI / ANTI on arrival: probe rows judged by
       their CURRENT match count mc (outer: mc==0 -> row + NULLs; semi:
       mc>0 -> row; anti: mc==0 -> row), probe row's sign.
    3. TRANSITIONS on the other side's stored rows whose degree crossed
       zero (degree_apply): outer -> retract/revive the NULL-padded
       row; semi/anti -> emit/retract the bare row.

    Returns (own', other', out_cols, out_nulls, out_ops, out_valid,
    overflow).
    """
    semi_anti = join_type.endswith("semi") or join_type.endswith("anti")
    drive = "l" if join_type.startswith("left") else "r"
    pairs_on = not semi_anti
    own_outer = join_type == "full" or (
        (join_type == "left" and arrival == "l")
        or (join_type == "right" and arrival == "r")
    )
    other_outer = join_type == "full" or (
        (join_type == "left" and arrival == "r")
        or (join_type == "right" and arrival == "l")
    )
    need_degree = join_type != "inner"

    key_cols = tuple(chunk.col(k) for k in own_keys)
    # SQL equi-join: NULL keys match nothing and need no state
    key_ok = jnp.ones(chunk.capacity, jnp.bool_)
    for k in own_keys:
        lane = chunk.nulls.get(k)
        if lane is not None:
            key_ok &= ~lane
    valid = chunk.valid & key_ok
    signs = chunk.effective_signs()
    active = valid & (signs != 0)

    # probe the other side (read-only) and stage the emissions
    sl, match = probe_side(other, key_cols, active)
    o_cols, o_nulls = gather_matches(other, sl, other_names)
    mc = jnp.sum(match.astype(jnp.int32), axis=1)

    with jax.named_scope("join/bucket/emit"):
        n, fanout = match.shape
        flatm = lambda a: a.reshape(n * fanout)
        bcast = lambda a: jnp.broadcast_to(a[:, None], (n, fanout))

        groups = []  # (cols, nulls, ops, valid) of flat lanes

        if pairs_on:
            g_cols = {name: flatm(bcast(chunk.col(name))) for name in own_names}
            g_cols.update({name: flatm(o_cols[name]) for name in other_names})
            g_nulls = {
                name: flatm(bcast(lane))
                for name, lane in chunk.nulls.items()
                if name in own_names
            }
            g_nulls.update({name: flatm(lane) for name, lane in o_nulls.items()})
            g_ops = flatm(
                bcast(
                    jnp.where(
                        signs > 0, jnp.int32(Op.INSERT), jnp.int32(Op.DELETE)
                    )
                )
            )
            groups.append((g_cols, g_nulls, g_ops, flatm(match)))

        # group 2: judged by current match count, on arrival rows
        if own_outer or (semi_anti and arrival == drive):
            if own_outer:
                cond = active & (mc == 0)
            elif join_type.endswith("semi"):
                cond = active & (mc > 0)
            else:  # anti
                cond = active & (mc == 0)
            g_cols = {name: chunk.col(name) for name in own_names}
            g_nulls = {
                name: lane
                for name, lane in chunk.nulls.items()
                if name in own_names
            }
            if own_outer:  # NULL-pad the other side
                for name in other_names:
                    g_cols[name] = jnp.zeros(n, other.rows[name].dtype)
                    g_nulls[name] = jnp.ones(n, jnp.bool_)
            g_ops = jnp.where(
                signs > 0, jnp.int32(Op.INSERT), jnp.int32(Op.DELETE)
            )
            groups.append((g_cols, g_nulls, g_ops, cond))

    # degree maintenance + group 3: zero-crossing transitions
    if need_degree:
        other, trans_pid, went_pos, went_zero = degree_apply(
            other, match, sl, jnp.where(active, signs, 0)
        )
        emit_trans = other_outer or (semi_anti and arrival != drive)
        if emit_trans:
            t_cols, t_nulls = gather_flat(other, trans_pid, other_names)
            with jax.named_scope("join/bucket/emit"):
                g_cols = dict(t_cols)
                g_nulls = dict(t_nulls)
                if other_outer:  # NULL-pad the arrival side
                    for name in own_names:
                        g_cols[name] = jnp.zeros(
                            trans_pid.shape[0], chunk.col(name).dtype
                        )
                        g_nulls[name] = jnp.ones(trans_pid.shape[0], jnp.bool_)
                if other_outer or join_type.endswith("anti"):
                    # matched for the first time -> retract pad/bare row;
                    # unmatched again -> emit it
                    g_ops = jnp.where(
                        went_pos, jnp.int32(Op.DELETE), jnp.int32(Op.INSERT)
                    )
                else:  # semi: matched -> emit; unmatched -> retract
                    g_ops = jnp.where(
                        went_pos, jnp.int32(Op.INSERT), jnp.int32(Op.DELETE)
                    )
                groups.append((g_cols, g_nulls, g_ops, went_pos | went_zero))

    with jax.named_scope("join/bucket/emit"):
        # concatenate groups into one flat emission (schema = out_names)
        flat_cols: Dict[str, jnp.ndarray] = {}
        flat_nulls: Dict[str, jnp.ndarray] = {}
        col_dtype = {}
        for g_cols, _, _, _ in groups:
            for name, a in g_cols.items():
                col_dtype.setdefault(name, a.dtype)
        null_names = set()
        for _, g_nulls, _, _ in groups:
            null_names.update(g_nulls)
        for name in out_names:
            parts, nparts = [], []
            for g_cols, g_nulls, _, _ in groups:
                m = next(iter(g_cols.values())).shape[0]
                if name in g_cols:
                    parts.append(g_cols[name])
                else:
                    parts.append(jnp.zeros(m, col_dtype[name]))
                if name in null_names:
                    nparts.append(g_nulls.get(name, jnp.zeros(m, jnp.bool_)))
            flat_cols[name] = jnp.concatenate(parts)
            if nparts:
                flat_nulls[name] = jnp.concatenate(nparts)
        flat_ops = jnp.concatenate([g[2] for g in groups])
        flat_valid = jnp.concatenate([g[3] for g in groups])

    out_cols, out_nulls, out_ops, out_valid, em_overflow = compact_pairs(
        flat_cols, flat_nulls, flat_ops, flat_valid, out_cap
    )

    # then fold the chunk into our own state (seeding degrees with the
    # current match count for outer/semi/anti)
    payload = {name: chunk.col(name) for name in own_names}
    pnulls = {
        name: lane for name, lane in chunk.nulls.items() if name in own_names
    }
    own = apply_side(
        own,
        key_cols,
        payload,
        pnulls,
        valid,
        signs,
        own_names,
        init_degree=mc if need_degree else None,
    )
    return own, other, out_cols, out_nulls, out_ops, out_valid, em_overflow


_join_step = partial(
    jax.jit,
    static_argnames=(
        "own_keys",
        "other_keys",
        "own_names",
        "other_names",
        "out_cap",
        "join_type",
        "arrival",
        "out_names",
    ),
    donate_argnums=(0, 1),
)(join_step_fn)


class HashJoinExecutor(Executor, Checkpointable):
    """Streaming INNER equi-join.

    Args:
      left_keys / right_keys: equi-join column names, positionally
        paired; dtypes of each pair must match (the hash is computed on
        raw lanes).
      left_dtypes / right_dtypes: column name -> dtype per side; ALL
        listed columns are stored as state and emitted. Names across the
        two sides must be disjoint (rename upstream).
      capacity: per-side key-table capacity (grows 2x at 50% load).
      fanout: per-key stored-row bound (grows 2x when exceeded... at
        the next barrier's raise; size for the workload's key skew).
      out_cap: per-chunk emission capacity.
      left_nullable / right_nullable: nullable payload columns.
      window_cols: optional (left_col, right_col) event-window lanes —
        a watermark on either clears state of both sides below it.
    """

    layout = "bucket"

    def __init__(
        self,
        left_keys: Sequence[str],
        right_keys: Sequence[str],
        left_dtypes: Dict[str, object],
        right_dtypes: Dict[str, object],
        capacity: int = 1 << 15,
        fanout: int = 16,
        out_cap: int = 1 << 14,
        left_nullable: Sequence[str] = (),
        right_nullable: Sequence[str] = (),
        window_cols: Optional[Tuple[str, str]] = None,
        join_type: str = "inner",
        table_id: str = "hash_join",
        bucket_policy: Optional[BucketPolicy] = None,
        bucketed: bool = True,
    ):
        self.table_id = table_id
        if join_type not in JOIN_TYPES:
            raise ValueError(f"unknown join type {join_type!r}")
        self.join_type = join_type
        if set(left_dtypes) & set(right_dtypes):
            raise ValueError(
                f"overlapping output columns: {set(left_dtypes) & set(right_dtypes)}"
            )
        self.left_keys = tuple(left_keys)
        self.right_keys = tuple(right_keys)
        self.left_names = tuple(sorted(left_dtypes))
        self.right_names = tuple(sorted(right_dtypes))
        if join_type.endswith("semi") or join_type.endswith("anti"):
            self.out_names = (
                self.left_names
                if join_type.startswith("left")
                else self.right_names
            )
        else:
            self.out_names = self.left_names + self.right_names
        self.out_cap = out_cap
        self.window_cols = window_cols
        self.left_nullable = tuple(left_nullable)
        self.right_nullable = tuple(right_nullable)

        lk_dtypes = tuple(jnp.dtype(left_dtypes[k]) for k in self.left_keys)
        rk_dtypes = tuple(jnp.dtype(right_dtypes[k]) for k in self.right_keys)
        if lk_dtypes != rk_dtypes:
            raise ValueError(f"join key dtype mismatch: {lk_dtypes} vs {rk_dtypes}")
        # declared per-side input dtypes, kept for the plan verifier
        self._lint_left_dtypes = {
            n: jnp.dtype(d) for n, d in left_dtypes.items()
        }
        self._lint_right_dtypes = {
            n: jnp.dtype(d) for n, d in right_dtypes.items()
        }

        self.left = JoinSide.create(
            capacity,
            fanout,
            lk_dtypes,
            {n: jnp.dtype(left_dtypes[n]) for n in self.left_names},
            nullable=left_nullable,
        )
        self.right = JoinSide.create(
            capacity,
            fanout,
            rk_dtypes,
            {n: jnp.dtype(right_dtypes[n]) for n in self.right_names},
            nullable=right_nullable,
        )
        # shape-stability: each side's key table walks a declared pow2
        # bucket lattice (one allocator per side — the sides churn
        # independently); bucketed=False keeps the legacy unbounded-
        # rehash twin (the RW-E803 wedge class under window churn)
        if bucketed:
            policy = bucket_policy or BucketPolicy.from_capacity(capacity, grow_at=GROW_AT)
            self._buckets = {
                "l": BucketAllocator(policy),
                "r": BucketAllocator(policy),
            }
        else:
            self._buckets = None
        self._bound = {"l": 0, "r": 0}
        self._occ_note = {"l": 0, "r": 0}  # true claimed at last barrier
        self._grew_midepoch = {"l": False, "r": False}  # one bump/epoch
        self._em_overflow = jnp.zeros((), jnp.bool_)
        self._wm = {"l": None, "r": None, "out": None}
        # cold tier (state >> HBM): the runtime wires cold_get_rows to
        # CheckpointManager.get_rows; evicted durable keys are recorded
        # host-side per side and fault back in when touched. The
        # property setter binds the host-side fault-in/expire HOOKS —
        # while unarmed (None) the hot path is provably host-sync free
        # (the NumPy helpers are unreachable), the HashAgg discipline.
        self._evicted = {"left": set(), "right": set()}
        self._cold_tombstones: Dict[str, list] = {}
        self._cold_apply_hook = None  # _fault_in when armed
        self._cold_expire_hook = None  # _expire_evicted when armed
        self.cold_get_rows = None

    @property
    def cold_get_rows(self):
        return self._cold_get_rows

    @cold_get_rows.setter
    def cold_get_rows(self, fn) -> None:
        self._cold_get_rows = fn
        armed = fn is not None
        self._cold_apply_hook = self._fault_in if armed else None
        self._cold_expire_hook = self._expire_evicted if armed else None

    def lint_info(self):
        dtypes = dict(self._lint_left_dtypes)
        dtypes.update(self._lint_right_dtypes)
        return {
            "left_keys": self.left_keys,
            "right_keys": self.right_keys,
            "expects_left": dict(self._lint_left_dtypes),
            "expects_right": dict(self._lint_right_dtypes),
            "emits": {n: dtypes.get(n) for n in self.out_names},
            "table_ids": (self.table_id,),
            "window_cols": self.window_cols,
        }

    def trace_contract(self):
        contract = {
            "kind": "device",
            "trace_step": lambda c: _join_step(
                self.left,
                self.right,
                c,
                self.left_keys,
                self.right_keys,
                self.left_names,
                self.right_names,
                self.out_cap,
                self.join_type,
                "l",
                self.out_names,
            ),
            "state": (self.left, self.right),
            "donate": True,
            "emission": "fixed",
            "emission_caps": (self.out_cap,),
            # the trace_step probes as a LEFT arrival: its input schema
            # is the declared left side — the analyzer seeds tracing
            # from this when the join heads a fragment (join_tail
            # sections have no source schema to thread)
            "input_schema": dict(self._lint_left_dtypes),
            "input_nulls": self.left_nullable,
            # two-input fusibility: the fused two-input program
            # (runtime/fused_step) can absorb this join — per-side
            # probe/build kernels are mask-aware (padded rows provably
            # inert, proven by the masked-lane twin tests), so bucket-
            # padded flush lanes cost one masked device op. Requires
            # the bucket lattice on both sides (the unbucketed twin is
            # the RW-E803 wedge class and stays interpreted).
            "two_input": True,
            "two_input_fusible": self._buckets is not None,
            # both JoinSides draw their capacities from the declared
            # pow2 lattice: the window-churn expiry/growth cycle costs
            # at most one trace per bucket per side (None only on the
            # legacy unbucketed twin — the RW-E803 wedge class)
            "window_buckets": (
                self._buckets["l"].lattice
                if self._buckets is not None
                else None
            ),
        }
        if self._buckets is not None:
            # the interpreted growth path's packed read exists only
            # where interpretation runs (the fused wrapper plans from
            # barrier notes instead) — fallback-only, not a blocker
            contract["fallback_syncs"] = ("_maybe_grow",)
        if self._cold_get_rows is not None:
            # an ARMED cold tier splices host fault-in/expire back into
            # the data path — scan it honestly (the corpus twins the
            # analyzer proves are never armed)
            contract["hot_methods"] = ("_fault_in", "_expire_evicted")
        return contract

    def pin_max_bucket(self):
        """ShapeGovernor hook: freeze BOTH sides at their high-water
        buckets (shrink disabled; regrow applied on the next apply)."""
        if self._buckets is None:
            return {"pinned": False}
        return {
            "table_id": self.table_id,
            "pinned_cap_left": self._buckets["l"].pin(),
            "pinned_cap_right": self._buckets["r"].pin(),
        }

    def padding_stats(self):
        return {
            "capacity": self.left.capacity + self.right.capacity,
            "live": int(self.left.table.num_live())
            + int(self.right.table.num_live()),
        }

    # -- data ------------------------------------------------------------
    def apply_left(self, chunk: StreamChunk) -> List[StreamChunk]:
        return self._apply("l", chunk)

    def apply_right(self, chunk: StreamChunk) -> List[StreamChunk]:
        return self._apply("r", chunk)

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        raise TypeError("HashJoin is two-input: use apply_left/apply_right")

    def _apply(self, side: str, chunk: StreamChunk) -> List[StreamChunk]:
        if self._cold_apply_hook is not None:
            # merge-on-return BEFORE the step: an arriving chunk probes
            # the other side and appends to its own — both sides' cold
            # buckets for its keys must be resident or matches are lost
            self._cold_apply_hook(side, chunk)
        own = self.left if side == "l" else self.right
        own = self._maybe_grow(side, own, chunk.capacity)
        out, em_overflow = self._step(side, own, chunk)
        self._bound[side] += chunk.capacity
        # latch on device; checked once per barrier (a bool() here would
        # force a host sync on every chunk and stall the pipeline)
        self._em_overflow = self._em_overflow | em_overflow
        return [out]

    def _step(self, side: str, own: JoinSide, chunk: StreamChunk):
        """One chunk's join step over ``own`` (this side, grown if it
        had to be) and the other side; both sides are kept. Returns
        (the pairs' chunk, the emission-overflow flag)."""
        other = self.right if side == "l" else self.left
        own_keys = self.left_keys if side == "l" else self.right_keys
        other_keys = self.right_keys if side == "l" else self.left_keys
        own_names = self.left_names if side == "l" else self.right_names
        other_names = self.right_names if side == "l" else self.left_names

        own, other, cols, nulls, ops, valid, em_overflow = _join_step(
            own,
            other,
            chunk,
            own_keys,
            other_keys,
            own_names,
            other_names,
            self.out_cap,
            self.join_type,
            side,
            self.out_names,
        )
        if side == "l":
            self.left, self.right = own, other
        else:
            self.right, self.left = own, other
        out = StreamChunk(columns=cols, valid=valid, nulls=nulls, ops=ops)
        return out, em_overflow

    # one step a chunk at the chunk's own width: takes the push lattice
    per_chunk_step = True

    def warm_side(self, name: str, chunk: StreamChunk) -> List[StreamChunk]:
        """``Executor.warm`` for one side's chunk ("left" / "right"):
        the side's step over a chunk with no valid row, which stores,
        clears and pairs nothing; no host bound moves, nothing grows,
        no latch is kept, and no cold bucket is faulted in (the chunk
        names no key). Nothing runs where the next chunk of this width
        would first plan a growth: its step never runs at this
        capacity."""
        side = "l" if name == "left" else "r"
        own = self.left if side == "l" else self.right
        alloc = self._buckets[side] if self._buckets is not None else None
        if needs_plan(
            alloc, own.capacity, self._bound[side], chunk.capacity, GROW_AT
        ):
            return []
        return [self._step(side, own, chunk)[0]]

    def _grow_hint(self, side: str, own: JoinSide, incoming: int) -> JoinSide:
        """The FUSED wrapper's pre-dispatch growth bookkeeping: ZERO
        device reads — one emergency bucket bump per side per epoch at
        most (BucketAllocator.bump; the host bound counts padded
        chunk capacities, so exact sizing from it over-grows);
        ordinary growth/shrink resolves at the barrier from the
        staged true occupancy+survivor notes."""
        if self._buckets is None:
            return self._maybe_grow(side, own, incoming)
        cap = own.capacity
        bound = min(self._bound[side], cap)
        self._bound[side] = bound
        if self._grew_midepoch[side] or (
            bound + incoming <= cap * HARD_GROW_AT
        ):
            return own
        new_cap = self._buckets[side].bump(cap)
        if new_cap is not None:
            own = regrow(own, new_cap, own.fanout)
            self._bound[side] = min(bound, new_cap)
        self._grew_midepoch[side] = True
        return own

    def _maybe_grow(self, side: str, own: JoinSide, incoming: int) -> JoinSide:
        """INTERPRETED-path growth: the exact legacy policy (one
        packed blocking read when the trigger trips). Declared under
        ``fallback_syncs`` on bucketed instances — the fused program
        replaces it with _grow_hint + barrier-note planning, so the
        read runs only where interpretation runs."""
        cap = own.capacity
        alloc = self._buckets[side] if self._buckets is not None else None
        if not needs_plan(alloc, cap, self._bound[side], incoming, GROW_AT):
            return own
        # ONE packed read: device round-trips dominate
        claimed, survivors = read_scalars(
            own.table.occupancy(),
            jnp.sum((own.table.live | own.sdirty).astype(jnp.int32)),
        )
        new_cap = plan_capacity(
            alloc, cap, incoming, claimed, survivors, GROW_AT
        )
        if new_cap is not None:
            own = regrow(own, new_cap, own.fanout)
            claimed = int(own.table.occupancy())
        self._bound[side] = claimed
        return own

    # -- cold tier (state >> HBM; join/hash_join.rs:157 LRU-over-
    # Hummock analogue: durable buckets leave HBM, fault back on touch)
    def state_nbytes(self) -> int:
        return sum(
            leaf.nbytes
            for leaf in jax.tree.leaves((self.left, self.right))
        )

    def evict_cold(self) -> int:
        """Free every fully-durable key's bucket from HBM, shrinking
        each side to its hot set. Returns keys evicted."""
        if self.cold_get_rows is None:
            raise RuntimeError("evict_cold needs cold_get_rows (runtime)")
        # non-integer key lanes ride the host-side evicted set as exact
        # bit patterns (host_key_view) — VARCHAR keys are dictionary
        # codes (integers) and float keys bit-cast losslessly
        return self._evict_side("left") + self._evict_side("right")

    def _evict_side(self, name: str) -> int:
        import dataclasses

        side = getattr(self, name)
        claimed = side.table.fp1 != jnp.uint32(0)
        durable = claimed & side.stored & ~side.sdirty
        n_evict = int(jnp.sum(durable.astype(jnp.int32)))
        if n_evict == 0:
            return 0
        # record evicted keys host-side: the membership check is what
        # lets the hot path skip cold lookups for genuinely-new keys
        sel = np.flatnonzero(np.asarray(durable))
        keys = pull_rows(
            {f"k{i}": l for i, l in enumerate(side.table.keys)}, sel
        )
        lanes = [
            host_key_view(np.asarray(keys[f"k{i}"]))
            for i in range(len(side.table.keys))
        ]
        ev = self._evicted[name]
        for j in range(len(sel)):
            ev.add(tuple(int(a[j]) for a in lanes))
        # rebuild the side holding only the hot keys (eviction must
        # actually free HBM, not just slots)
        hot = claimed & ~durable
        hsel = np.flatnonzero(np.asarray(hot))
        n_hot = len(hsel)
        new_cap = grow_pow2(n_hot, 1 << 10, GROW_AT)
        fresh = JoinSide.create(
            new_cap,
            side.fanout,
            tuple(k.dtype for k in side.table.keys),
            {nm: a.dtype for nm, a in side.rows.items()},
            nullable=tuple(side.row_nulls),
        )
        if n_hot:
            pull = {f"k{i}": l for i, l in enumerate(side.table.keys)}
            pull["rv"] = side.row_valid
            pull["deg"] = side.degree
            pull["live"] = side.table.live
            pull["sd"] = side.sdirty
            pull["st"] = side.stored
            for nm, a in side.rows.items():
                pull[f"r_{nm}"] = a
            for nm, a in side.row_nulls.items():
                pull[f"n_{nm}"] = a
            rows = pull_rows(pull, hsel)
            jl = tuple(
                jnp.asarray(rows[f"k{i}"])
                for i in range(len(side.table.keys))
            )
            table, slots, _, _ = lookup_or_insert(
                fresh.table, jl, jnp.ones(n_hot, jnp.bool_)
            )
            table = set_live(table, slots, jnp.asarray(rows["live"]))
            fresh = dataclasses.replace(
                fresh,
                table=table,
                rows={
                    nm: a.at[slots].set(jnp.asarray(rows[f"r_{nm}"]))
                    for nm, a in fresh.rows.items()
                },
                row_nulls={
                    nm: a.at[slots].set(jnp.asarray(rows[f"n_{nm}"]))
                    for nm, a in fresh.row_nulls.items()
                },
                row_valid=fresh.row_valid.at[slots].set(
                    jnp.asarray(rows["rv"])
                ),
                degree=fresh.degree.at[slots].set(
                    jnp.asarray(rows["deg"])
                ),
                sdirty=fresh.sdirty.at[slots].set(jnp.asarray(rows["sd"])),
                stored=fresh.stored.at[slots].set(jnp.asarray(rows["st"])),
                overflow=side.overflow,
                inconsistent=side.inconsistent,
            )
        setattr(self, name, fresh)
        self._bound["l" if name == "left" else "r"] = int(
            fresh.table.occupancy()
        )
        return n_evict

    def _expire_evicted(self, name: str, pos: int, cutoff: int) -> None:
        """Watermark closes EVICTED keys too: they leave the evicted
        set (never fault back) and their store rows tombstone at the
        next checkpoint — recovery must not resurrect closed windows
        (expire_keys only reaches resident slots)."""
        side = getattr(self, name)
        dt = np.dtype(side.table.keys[pos].dtype)
        if dt.kind == "f":
            # evicted tuples hold bit patterns (host_key_view): convert
            # back to the numeric domain for the watermark comparison
            itype = np.int32 if dt.itemsize == 4 else np.int64
            conv = lambda x: float(np.array(x, itype).view(dt))
        else:
            conv = lambda x: x
        ev = self._evicted[name]
        closed = {t for t in ev if conv(t[pos]) < cutoff}
        if closed:
            ev.difference_update(closed)
            self._cold_tombstones.setdefault(name, []).extend(closed)

    def _fault_in(self, side: str, chunk: StreamChunk) -> None:
        if not (self._evicted["left"] or self._evicted["right"]):
            return  # armed but nothing evicted: never pull the chunk
        own_keys = self.left_keys if side == "l" else self.right_keys
        cols = [
            host_key_view(np.asarray(chunk.col(k))) for k in own_keys
        ]
        valid = np.asarray(chunk.valid)
        touched = {
            tuple(int(c[i]) for c in cols) for i in np.flatnonzero(valid)
        }
        for name in ("left", "right"):
            hits = touched & self._evicted[name]
            if hits:
                self._restore_cold_keys(name, sorted(hits))

    def _restore_cold_keys(self, name: str, key_tuples) -> None:
        import dataclasses

        letter = "l" if name == "left" else "r"
        side = getattr(self, name)
        n = len(key_tuples)
        side = self._maybe_grow(letter, side, n)
        lanes_np = lanes_from_host_keys(
            key_tuples, [k.dtype for k in side.table.keys]
        )
        found, vals = self.cold_get_rows(
            f"{self.table_id}.{name}", dict(lanes_np)
        )
        nt = int(found.sum())
        if nt:
            jl = tuple(
                jnp.asarray(lanes_np[f"k{i}"][found])
                for i in range(len(side.table.keys))
            )
            table, slots, _, _ = lookup_or_insert(
                side.table, jl, jnp.ones(nt, jnp.bool_)
            )
            table = set_live(table, slots, True)
            side = dataclasses.replace(
                side,
                table=table,
                rows={
                    nm: a.at[slots].set(
                        jnp.asarray(
                            vals[f"r_{nm}"][found].astype(a.dtype)
                        )
                    )
                    for nm, a in side.rows.items()
                },
                row_nulls={
                    nm: a.at[slots].set(
                        jnp.asarray(vals[f"n_{nm}"][found].astype(bool))
                    )
                    for nm, a in side.row_nulls.items()
                },
                row_valid=side.row_valid.at[slots].set(
                    jnp.asarray(vals["rv"][found].astype(bool))
                ),
                degree=(
                    side.degree.at[slots].set(
                        jnp.asarray(vals["deg"][found].astype(np.int32))
                    )
                    if "deg" in vals  # legacy pre-degree checkpoints
                    else side.degree
                ),
                stored=side.stored.at[slots].set(True),
            )
        setattr(self, name, side)
        self._bound[letter] += nt
        self._evicted[name].difference_update(key_tuples)

    # -- control ---------------------------------------------------------
    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        self._staged_scalars = stage_scalars(
            self._em_overflow,
            self.left.overflow,
            self.left.inconsistent,
            self.right.overflow,
            self.right.inconsistent,
            self.left.table.occupancy(),
            self.right.table.occupancy(),
            jnp.sum((self.left.table.live | self.left.sdirty).astype(jnp.int32)),
            jnp.sum((self.right.table.live | self.right.sdirty).astype(jnp.int32)),
        )
        if barrier is None:  # direct drive: checks fire inline
            self.finish_barrier()
        return []

    def _plan_side_at_barrier(
        self, side: str, claimed: int, survivors: int
    ) -> None:
        """Barrier-boundary capacity planning from the TRUE occupancy
        note (grow past the load factor, apply pending lazy shrink,
        honor a governor pin) — zero mid-epoch device reads."""
        own = self.left if side == "l" else self.right
        cap = own.capacity
        epoch_inc = max(self._bound[side] - self._occ_note[side], 0)
        self._occ_note[side] = claimed
        self._bound[side] = claimed
        alloc = self._buckets[side]
        alloc.note_barrier(cap, claimed)
        new_cap = alloc.plan(
            cap, 0, claimed, survivors, margin=max(claimed, epoch_inc)
        )
        if new_cap is not None and new_cap != cap:
            own = regrow(own, new_cap, own.fanout)
            if side == "l":
                self.left = own
            else:
                self.right = own

    def _on_barrier_scalars(self, vals) -> None:
        em, lo, li, ro, ri, cl, cr, sl, sr = vals
        self._grew_midepoch = {"l": False, "r": False}
        if self._buckets is not None:
            self._plan_side_at_barrier("l", int(cl), int(sl))
            self._plan_side_at_barrier("r", int(cr), int(sr))
        else:
            self._bound["l"] = int(cl)
            self._bound["r"] = int(cr)
        if em:
            raise RuntimeError(
                "join emission overflowed out_cap within one chunk; "
                "raise out_cap or shrink source chunks"
            )
        for name, ovf, inc in (("left", lo, li), ("right", ro, ri)):
            if ovf:
                raise RuntimeError(
                    f"{name} join side overflowed (bucket fanout or probe "
                    "chain); grow fanout/capacity"
                )
            if inc:
                raise RuntimeError(
                    f"{name} join side saw a DELETE matching no stored row "
                    "(inconsistent input stream)"
                )

    def on_watermark(self, watermark: Watermark):
        """Expire the matching side's closed windows; emit a downstream
        watermark on the LEFT window column once BOTH sides passed a new
        minimum (the reference's per-input watermark alignment on
        joins: output wm = min over inputs)."""
        if self.window_cols is None or watermark.column not in self.window_cols:
            return watermark, []
        cutoff = jnp.asarray(watermark.value, jnp.int64)
        if watermark.column == self.window_cols[0]:
            pos = self._key_index("l", self.window_cols[0])
            self.left = expire_keys(self.left, pos, cutoff)
            if self._cold_expire_hook is not None:
                self._cold_expire_hook("left", pos, int(watermark.value))
            self._wm["l"] = watermark.value
        else:
            pos = self._key_index("r", self.window_cols[1])
            self.right = expire_keys(self.right, pos, cutoff)
            if self._cold_expire_hook is not None:
                self._cold_expire_hook("right", pos, int(watermark.value))
            self._wm["r"] = watermark.value
        if self._wm["l"] is None or self._wm["r"] is None:
            return None, []
        aligned = min(self._wm["l"], self._wm["r"])
        if self._wm["out"] is not None and aligned <= self._wm["out"]:
            return None, []
        self._wm["out"] = aligned
        return Watermark(self.window_cols[0], aligned), []

    def _key_index(self, side: str, name: str) -> int:
        keys = self.left_keys if side == "l" else self.right_keys
        return keys.index(name)


# -- checkpoint/restore (StateTable integration) -------------------------
def _side_delta(side: JoinSide, table_id: str):
    """Stage one side's changed keys: the whole bucket rides as 2D
    value lanes (rows re-land at the same in-bucket positions on
    restore, so emitted pair identity is stable). Marks flip eagerly
    (see StateDelta's durability contract). Returns (delta, new_side),
    the delta None where no key changed."""
    import numpy as np

    marks = classify_marks(side.sdirty, side.table.live, side.stored)
    side = dataclasses.replace(side, sdirty=marks.sdirty, stored=marks.stored)
    if not len(marks):
        return None, side
    lanes = {
        f"k{i}": lane for i, lane in enumerate(side.table.keys)
    }
    key_names = tuple(lanes)
    lanes["rv"] = side.row_valid
    lanes["deg"] = side.degree
    for n, a in side.rows.items():
        lanes[f"r_{n}"] = a
    for n, a in side.row_nulls.items():
        lanes[f"n_{n}"] = a
    pulled = pull_rows(lanes, marks)
    keys = {k: pulled[k] for k in key_names}
    vals = {k: v for k, v in pulled.items() if k not in key_names}
    delta = StateDelta(table_id, keys, vals, marks.tombstone, key_names)
    return delta, side


def _side_restore(side: JoinSide, key_cols, value_cols) -> JoinSide:
    """Rebuild a JoinSide from recovered rows (fresh table, same
    capacity/fanout unless growth is needed)."""
    import numpy as np

    n = len(next(iter(key_cols.values()))) if key_cols else 0
    fanout = side.fanout
    if n and "rv" in value_cols and value_cols["rv"].shape[1] != fanout:
        raise ValueError(
            f"checkpoint bucket fanout {value_cols['rv'].shape[1]} != "
            f"executor fanout {fanout}: restore lands rows at their "
            "stored in-bucket positions — configure the same fanout"
        )
    cap = grow_pow2(n, side.capacity, GROW_AT)
    fresh = JoinSide.create(
        cap,
        fanout,
        tuple(k.dtype for k in side.table.keys),
        {name: a.dtype for name, a in side.rows.items()},
        nullable=tuple(side.row_nulls),
    )
    if not n:
        return fresh
    lanes = tuple(
        jnp.asarray(np.asarray(key_cols[f"k{i}"], dtype=d.dtype))
        for i, d in enumerate(side.table.keys)
    )
    table, slots, _, _ = lookup_or_insert(
        fresh.table, lanes, jnp.ones(n, jnp.bool_)
    )
    table = set_live(table, slots, True)

    def put2d(dst, src):
        return dst.at[slots].set(jnp.asarray(src))

    rows = {
        name: put2d(a, value_cols[f"r_{name}"].astype(a.dtype))
        for name, a in fresh.rows.items()
    }
    row_nulls = {
        name: put2d(a, value_cols[f"n_{name}"])
        for name, a in fresh.row_nulls.items()
    }
    row_valid = put2d(fresh.row_valid, value_cols["rv"])
    # older checkpoints predate the degree lane; default to zeros
    degree = (
        put2d(fresh.degree, value_cols["deg"].astype(jnp.int32))
        if "deg" in value_cols
        else fresh.degree
    )
    stored = fresh.stored.at[slots].set(True)
    return JoinSide(
        table,
        rows,
        row_nulls,
        row_valid,
        jnp.zeros((), jnp.bool_),
        jnp.zeros((), jnp.bool_),
        jnp.zeros(cap, jnp.bool_),
        stored,
        degree,
    )


def _join_checkpoint_table_ids(self):
    return [f"{self.table_id}.left", f"{self.table_id}.right"]


def _join_checkpoint_delta(self):
    out = []
    for name in ("left", "right"):
        delta, side = _side_delta(
            getattr(self, name), f"{self.table_id}.{name}"
        )
        setattr(self, name, side)
        if delta is not None:
            out.append(delta)
    # watermark-closed EVICTED keys: their buckets live only in the
    # store — stage explicit tombstones so recovery cannot resurrect
    # closed windows (resident expiry tombstones ride _side_delta)
    pending = getattr(self, "_cold_tombstones", None)
    if pending:
        from risingwave_tpu.ops.hash_table import lookup as _ht_lookup

        by_tid = {d.table_id: d for d in out}
        for name, tuples in pending.items():
            if not tuples:
                continue
            side = getattr(self, name)
            # a key re-created AFTER its window closed (late arrival) is
            # RESIDENT again: its upsert (or its own tombstone) stages
            # via _side_delta — a cold tombstone in the same delta would
            # make point reads and merge reads disagree on the key
            lanes_np = lanes_from_host_keys(
                tuples, [k.dtype for k in side.table.keys]
            )
            lanes_j = tuple(
                jnp.asarray(lanes_np[f"k{i}"])
                for i in range(len(side.table.keys))
            )
            slots, _found = _ht_lookup(
                side.table, lanes_j, jnp.ones(len(tuples), jnp.bool_)
            )
            resident = np.asarray(slots) >= 0
            tuples = [t for t, r in zip(tuples, resident) if not r]
            if not tuples:
                continue
            tid = f"{self.table_id}.{name}"
            keys = lanes_from_host_keys(
                tuples, [k.dtype for k in side.table.keys]
            )
            nvals = {}
            nrows = len(tuples)
            nvals["rv"] = np.zeros(
                (nrows, side.fanout), side.row_valid.dtype
            )
            nvals["deg"] = np.zeros((nrows, side.fanout), np.int32)
            for nm, a in side.rows.items():
                nvals[f"r_{nm}"] = np.zeros((nrows,) + a.shape[1:], a.dtype)
            for nm, a in side.row_nulls.items():
                nvals[f"n_{nm}"] = np.zeros((nrows,) + a.shape[1:], a.dtype)
            tomb = np.ones(nrows, bool)
            prev = by_tid.get(tid)
            if prev is None:
                out.append(
                    StateDelta(
                        tid, keys, nvals, tomb, tuple(keys)
                    )
                )
            else:
                merged_keys = {
                    k: np.concatenate([prev.key_cols[k], keys[k]])
                    for k in prev.key_cols
                }
                merged_vals = {
                    k: np.concatenate([prev.value_cols[k], nvals[k]])
                    for k in prev.value_cols
                }
                out[out.index(prev)] = StateDelta(
                    tid,
                    merged_keys,
                    merged_vals,
                    np.concatenate([prev.tombstone, tomb]),
                    prev.key_order,
                )
        self._cold_tombstones = {}
    return out


def _join_restore_state(self, table_id, key_cols, value_cols):
    if table_id.endswith(".left"):
        self.left = _side_restore(self.left, key_cols, value_cols)
        self._bound["l"] = int(self.left.table.occupancy())
    else:
        self.right = _side_restore(self.right, key_cols, value_cols)
        self._bound["r"] = int(self.right.table.occupancy())
    # a full restore materializes EVERYTHING the store holds — no key
    # is cold anymore
    self._evicted = {"left": set(), "right": set()}


def _join_digest_lanes(self):
    """Both sides folded as one lane set (``l_``/``r_`` prefixes keep
    the seeds distinct); bucket lanes are pre-masked by row_valid
    inside integrity.join_side_lanes."""
    from risingwave_tpu.integrity import join_side_lanes

    ll, llive = join_side_lanes(self.left, jnp.where)
    rl, rlive = join_side_lanes(self.right, jnp.where)
    lanes = {f"l_{k}": v for k, v in ll.items()}
    lanes.update({f"r_{k}": v for k, v in rl.items()})
    return lanes, llive, rlive


def _join_state_digest(self) -> int:
    """Host twin of the fused per-side digest lanes: the two sides'
    digests XOR together (each side digest is what the fused program
    stages, so cross-checks stay per-side)."""
    from risingwave_tpu.integrity import host_digest, join_side_lanes

    import numpy as np

    ld = host_digest(*join_side_lanes(self.left, np.where))
    rd = host_digest(*join_side_lanes(self.right, np.where))
    return ld ^ rd


HashJoinExecutor.checkpoint_table_ids = _join_checkpoint_table_ids
HashJoinExecutor.checkpoint_delta = _join_checkpoint_delta
HashJoinExecutor.restore_state = _join_restore_state
HashJoinExecutor.digest_lanes = _join_digest_lanes
HashJoinExecutor.state_digest = _join_state_digest
