"""Mesh-parallel HashJoin + append-only Dedup fragments.

Reference roles replaced (SURVEY.md §2.11; VERDICT r2 #2):
- N parallel HashJoin actors each owning the vnode slice of both join
  sides (src/stream/src/executor/hash_join.rs:129 distributed by
  HashDataDispatcher, dispatch.rs:683);
- N parallel AppendOnlyDedup actors (dedup/append_only_dedup.rs).

TPU re-design: state is STACKED — every per-slot array gains a leading
``(n_shards,)`` axis sharded over the mesh — and each ``apply`` is ONE
jitted ``shard_map`` program: vnode exchange (``parallel.exchange``)
followed by the *same single-chip kernel* (``join_step_fn`` /
``dedup_step_fn``) on the received rows. Because every join key lives
on exactly one shard, per-shard emissions are disjoint and exact; the
stacked output chunks flow on-device to the next sharded fragment (or
flatten to the host materializer).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor
from risingwave_tpu.executors.dedup import dedup_step_fn
from risingwave_tpu.executors.hash_join import (
    JOIN_TYPES,
    _side_restore,
    join_step_fn,
)
from risingwave_tpu.ops.hash_table import HashTable, lookup_or_insert, set_live
from risingwave_tpu.ops.join import JoinSide
from risingwave_tpu.parallel.exchange import dest_shard, exchange_chunk
from risingwave_tpu.storage.state_table import (
    Checkpointable,
    StateDelta,
    classify_marks,
    grow_pow2,
    pull_rows,
)

GROW_AT = 0.5


def stack_for_mesh(tree, mesh: Mesh, axis: str):
    """Replicate a single-chip state pytree into stacked (n_shards, ...)
    arrays laid out one-slice-per-device over ``mesh``."""
    n = mesh.devices.size

    def stack(a):
        return jnp.broadcast_to(a[None], (n,) + a.shape)

    return jax.device_put(
        jax.tree.map(stack, tree), NamedSharding(mesh, P(axis))
    )


def flatten_stacked(chunk: StreamChunk) -> StreamChunk:
    """(n_shards, cap) stacked chunk -> flat (n_shards*cap,) chunk (host
    boundary: feed the single materializer / sinks)."""
    return jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), chunk)


def track_bucket_cap(ex, bucket_cap: int) -> None:
    """Record the LARGEST exchange bucket any built step implied — the
    growth escape must never rebuild smaller than what overflowed."""
    ex._built_bucket_cap = max(
        getattr(ex, "_built_bucket_cap", None) or 0, bucket_cap
    )


def double_bucket_cap(ex) -> None:
    """The shared capacity-escape idiom: pin bucket_cap to 2x the
    largest bucket in effect (explicit setting wins over the implied
    per-chunk default)."""
    cur = (
        ex.bucket_cap
        if ex.bucket_cap is not None
        else getattr(ex, "_built_bucket_cap", None)
    )
    if cur is not None:
        ex.bucket_cap = 2 * cur


class ShardedDedup(Executor, Checkpointable):
    """Mesh-parallel DISTINCT: exchange by dedup key, local seen-set.

    ``apply`` takes a stacked (n_shards, cap) chunk and returns ONE
    stacked output chunk (capacity n_shards*bucket_cap per shard) of
    first-seen rows, still sharded by dedup-key vnode.
    """

    def __init__(
        self,
        mesh: Mesh,
        keys: Sequence[str],
        schema_dtypes: Dict[str, object],
        capacity: int = 1 << 16,
        bucket_cap: Optional[int] = None,
        table_id: str = "sharded_dedup",
    ):
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_shards = mesh.devices.size
        self.keys = tuple(keys)
        self.bucket_cap = bucket_cap
        self.table_id = table_id
        table1 = HashTable.create(
            capacity, tuple(jnp.dtype(schema_dtypes[k]) for k in self.keys)
        )
        self.table = stack_for_mesh(table1, mesh, self.axis)
        self.sdirty = stack_for_mesh(
            jnp.zeros(capacity, jnp.bool_), mesh, self.axis
        )
        self.stored = stack_for_mesh(
            jnp.zeros(capacity, jnp.bool_), mesh, self.axis
        )
        self.flags = stack_for_mesh(
            jnp.zeros(2, jnp.bool_), mesh, self.axis
        )  # [saw_delete, dropped|overflow]
        self._step = None
        self._built_bucket_cap: Optional[int] = None
        self.ex_counts_last = None  # (n, n) routed-row histogram, device

    def _build_step(self, chunk_cap: int):
        n, axis, keys = self.n_shards, self.axis, self.keys
        bucket_cap = self.bucket_cap or max(64, (2 * chunk_cap) // n)
        track_bucket_cap(self, bucket_cap)

        def local(table, sdirty, flags, chunk):
            table, sdirty, flags, chunk = jax.tree.map(
                lambda a: a[0], (table, sdirty, flags, chunk)
            )
            lanes = tuple(chunk.col(k) for k in keys)
            rchunk, ex_ovf, ex_counts = exchange_chunk(
                chunk, lanes, n, bucket_cap, axis
            )
            table, sdirty, out, saw_delete, dropped = dedup_step_fn(
                table, sdirty, rchunk, keys
            )
            flags = flags | jnp.stack([saw_delete, dropped | ex_ovf])
            ex = lambda t: jax.tree.map(lambda a: a[None], t)
            return ex(table), ex(sdirty), ex(flags), ex(out), ex_counts[None]

        spec = P(self.axis)
        return jax.jit(
            jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=(spec,) * 4,
                out_specs=(spec,) * 5,
                check_vma=False,
            ),
            donate_argnums=(0, 1, 2),
        )

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        if self._step is None:
            self._step = self._build_step(chunk.valid.shape[-1])
        self.table, self.sdirty, self.flags, out, self.ex_counts_last = (
            self._step(self.table, self.sdirty, self.flags, chunk)
        )
        return [out]

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        flags = jnp.any(self.flags, axis=0)
        if bool(flags[0]):
            raise RuntimeError("append-only sharded dedup received a DELETE")
        if bool(flags[1]):
            raise RuntimeError(
                "sharded dedup overflowed (probe chain or exchange bucket); "
                "grow capacity/bucket_cap"
            )
        return []

    # -- static contracts (analysis/) -------------------------------------
    def lint_info(self):
        return {
            "expects": {
                k: lane.dtype for k, lane in zip(self.keys, self.table.keys)
            },
            "keys": self.keys,
            "table_ids": (self.table_id,),
            "window_key": None,
        }

    def trace_contract(self):
        return {
            "kind": "host",
            "host_reason": "mesh-resident sharded step: per-fragment "
            "SPMD fusion is tracked by the mesh analyzer (RW-E9xx), "
            "not the single-chip fuser",
            "state": (self.table, self.sdirty, self.flags),
            "donate": True,
            "emission": "stacked",
            "fallback_syncs": ("on_barrier", "shard_occupancy"),
        }

    def mesh_contract(self):
        def trace_steps(abs_chunk):
            from risingwave_tpu.analysis.mesh_domain import abstract_tree

            step = self._build_step(int(abs_chunk.valid.shape[-1]))
            return [
                (
                    "apply",
                    step,
                    (
                        abstract_tree(self.table),
                        abstract_tree(self.sdirty),
                        abstract_tree(self.flags),
                        abs_chunk,
                    ),
                )
            ]

        return {
            "axis": self.axis,
            "n_shards": self.n_shards,
            "state": {
                "table": "sharded",
                "sdirty": "sharded",
                "flags": "sharded",
            },
            "updates": ("table", "sdirty", "flags"),
            "dispatch": {
                "fn": "dest_shard",
                "keys": self.keys,
                "vnode_axis": self.axis,
            },
            "exchange": "all_to_all",
            "donate": True,
            "order_insensitive": True,  # first-seen is per-slot, and
            # slot ownership is deterministic under the vnode route
            "trace_steps": trace_steps,
            "barrier_methods": ("on_barrier", "shard_occupancy"),
            "emission": "stacked",
        }

    # -- capacity escape (watchdog replay, scale.rs:453 analogue) ---------
    def capacity_overflow_latched(self) -> bool:
        return bool(jnp.any(self.flags, axis=0)[1])

    def grow_for_replay(self) -> None:
        """Double probe capacity + exchange bucket and reset device
        state at the new shapes; the watchdog's recover() restores
        durable rows into them before the poisoned epoch replays."""
        cap = 2 * self.table.keys[0].shape[-1]
        double_bucket_cap(self)
        key_dtypes = tuple(k.dtype for k in self.table.keys)
        self.table = stack_for_mesh(
            HashTable.create(cap, key_dtypes), self.mesh, self.axis
        )
        z = jnp.zeros(cap, jnp.bool_)
        self.sdirty = stack_for_mesh(z, self.mesh, self.axis)
        self.stored = stack_for_mesh(z, self.mesh, self.axis)
        self.flags = stack_for_mesh(
            jnp.zeros(2, jnp.bool_), self.mesh, self.axis
        )
        self._step = None

    # -- integrity --------------------------------------------------------
    def state_digest(self) -> int:
        """Shard-flattened dedup fold: slot-order invariance makes this
        digest equal to the single-chip twin's for the same key set."""
        from risingwave_tpu.integrity import host_digest

        def flat(a):
            a = np.asarray(a)
            return a.reshape((-1,) + a.shape[2:])

        lanes = {f"k{i}": flat(k) for i, k in enumerate(self.table.keys)}
        return host_digest(lanes, flat(self.table.live))

    # -- checkpoint/restore (one logical table across shards) ------------
    def checkpoint_delta(self) -> List[StateDelta]:
        """Same lane naming as the single-chip dedup (k{i}), keys
        globally unique across shards — either executor can restore the
        other's checkpoint."""
        marks = classify_marks(self.sdirty, self.table.live, self.stored)
        self.sdirty, self.stored = marks.sdirty, marks.stored
        if not len(marks):
            return []
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        lanes = {f"k{i}": flat(l) for i, l in enumerate(self.table.keys)}
        key_names = tuple(lanes)
        keys = pull_rows(lanes, marks)
        return [
            StateDelta(self.table_id, keys, {}, marks.tombstone, key_names)
        ]

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        """Re-partition recovered keys by vnode and rebuild every shard
        (works across mesh sizes: a key's shard is vnode % n_shards)."""
        n_rows = len(next(iter(key_cols.values()))) if key_cols else 0
        key_dtypes = tuple(k.dtype for k in self.table.keys)
        cap = self.table.keys[0].shape[-1]
        lanes = dest = None
        if n_rows:
            lanes = tuple(
                jnp.asarray(np.asarray(key_cols[f"k{i}"], dtype=d))
                for i, d in enumerate(key_dtypes)
            )
            dest = np.asarray(dest_shard(lanes, self.n_shards))
            cap = grow_pow2(
                int(np.bincount(dest, minlength=self.n_shards).max()),
                cap,
                GROW_AT,
            )
        tables, stores = [], []
        for k in range(self.n_shards):
            t = HashTable.create(cap, key_dtypes)
            stored = jnp.zeros(cap, jnp.bool_)
            if n_rows:
                sel = np.flatnonzero(dest == k)
                if len(sel):
                    sub = tuple(l[jnp.asarray(sel)] for l in lanes)
                    t, slots, _, _ = lookup_or_insert(
                        t, sub, jnp.ones(len(sel), jnp.bool_)
                    )
                    t = set_live(t, slots, True)
                    stored = stored.at[slots].set(True)
            tables.append(t)
            stores.append(stored)
        sharding = NamedSharding(self.mesh, P(self.axis))
        stack = lambda *xs: jnp.stack(xs)
        self.table = jax.device_put(jax.tree.map(stack, *tables), sharding)
        self.stored = jax.device_put(jnp.stack(stores), sharding)
        self.sdirty = jax.device_put(
            jnp.zeros_like(self.stored), sharding
        )
        self.flags = stack_for_mesh(
            jnp.zeros(2, jnp.bool_), self.mesh, self.axis
        )
        self._step = None  # capacity may have changed: recompile


class ShardedHashJoin(Executor, Checkpointable):
    """Mesh-parallel streaming equi-join, all join types.

    Both sides' state is stacked over the mesh; each arrival runs one
    shard_map program: exchange the chunk by its own-side join key
    (both sides share the vnode hash on positionally-paired keys, so a
    key's left AND right rows land on the same shard), then the
    single-chip ``join_step_fn`` against the local slices. Emissions
    come back stacked (n_shards, out_cap).
    """

    def __init__(
        self,
        mesh: Mesh,
        left_keys: Sequence[str],
        right_keys: Sequence[str],
        left_dtypes: Dict[str, object],
        right_dtypes: Dict[str, object],
        capacity: int = 1 << 14,
        fanout: int = 8,
        out_cap: int = 1 << 12,
        bucket_cap: Optional[int] = None,
        left_nullable: Sequence[str] = (),
        right_nullable: Sequence[str] = (),
        join_type: str = "inner",
        table_id: str = "sharded_join",
    ):
        if join_type not in JOIN_TYPES:
            raise ValueError(f"unknown join type {join_type!r}")
        self.table_id = table_id
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_shards = mesh.devices.size
        self.join_type = join_type
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
        self.bucket_cap = bucket_cap

        lk = tuple(jnp.dtype(left_dtypes[k]) for k in self.left_keys)
        rk = tuple(jnp.dtype(right_dtypes[k]) for k in self.right_keys)
        if lk != rk:
            raise ValueError(f"join key dtype mismatch: {lk} vs {rk}")
        left1 = JoinSide.create(
            capacity,
            fanout,
            lk,
            {n: jnp.dtype(left_dtypes[n]) for n in self.left_names},
            nullable=left_nullable,
        )
        right1 = JoinSide.create(
            capacity,
            fanout,
            rk,
            {n: jnp.dtype(right_dtypes[n]) for n in self.right_names},
            nullable=right_nullable,
        )
        self._lint_left_nulls = tuple(left_nullable)
        self._lint_right_nulls = tuple(right_nullable)
        self._lint_left_dtypes = {
            n: jnp.dtype(left_dtypes[n]) for n in self.left_names
        }
        self._lint_right_dtypes = {
            n: jnp.dtype(right_dtypes[n]) for n in self.right_names
        }
        self.left = stack_for_mesh(left1, mesh, self.axis)
        self.right = stack_for_mesh(right1, mesh, self.axis)
        self._em_overflow = stack_for_mesh(
            jnp.zeros((), jnp.bool_), mesh, self.axis
        )
        self._steps: Dict[Tuple[str, int], object] = {}
        self._built_bucket_cap: Optional[int] = None
        self.ex_counts_last = None  # (n, n) routed-row histogram, device

    def _build_step(self, arrival: str, chunk_cap: int):
        n, axis = self.n_shards, self.axis
        bucket_cap = self.bucket_cap or max(64, (2 * chunk_cap) // n)
        track_bucket_cap(self, bucket_cap)
        own_keys = self.left_keys if arrival == "l" else self.right_keys
        other_keys = self.right_keys if arrival == "l" else self.left_keys
        own_names = self.left_names if arrival == "l" else self.right_names
        other_names = self.right_names if arrival == "l" else self.left_names
        join_type, out_cap, out_names = (
            self.join_type,
            self.out_cap,
            self.out_names,
        )

        def local(own, other, em_ovf, chunk):
            own, other, em_ovf, chunk = jax.tree.map(
                lambda a: a[0], (own, other, em_ovf, chunk)
            )
            lanes = tuple(chunk.col(k) for k in own_keys)
            rchunk, ex_ovf, ex_counts = exchange_chunk(
                chunk, lanes, n, bucket_cap, axis
            )
            own, other, cols, nulls, ops, valid, ovf = join_step_fn(
                own,
                other,
                rchunk,
                own_keys,
                other_keys,
                own_names,
                other_names,
                out_cap,
                join_type,
                arrival,
                out_names,
            )
            out = StreamChunk(columns=cols, valid=valid, nulls=nulls, ops=ops)
            em_ovf = em_ovf | ovf | ex_ovf
            ex = lambda t: jax.tree.map(lambda a: a[None], t)
            return ex(own), ex(other), ex(em_ovf), ex(out), ex_counts[None]

        spec = P(self.axis)
        return jax.jit(
            jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=(spec,) * 4,
                out_specs=(spec,) * 5,
                check_vma=False,
            ),
            donate_argnums=(0, 1, 2),
        )

    def _apply(self, arrival: str, chunk: StreamChunk) -> List[StreamChunk]:
        key = (arrival, chunk.valid.shape[-1])
        step = self._steps.get(key)
        if step is None:
            step = self._steps[key] = self._build_step(*key)
        own, other = (
            (self.left, self.right)
            if arrival == "l"
            else (self.right, self.left)
        )
        own, other, self._em_overflow, out, self.ex_counts_last = step(
            own, other, self._em_overflow, chunk
        )
        if arrival == "l":
            self.left, self.right = own, other
        else:
            self.right, self.left = own, other
        return [out]

    def apply_left(self, chunk: StreamChunk) -> List[StreamChunk]:
        return self._apply("l", chunk)

    def apply_right(self, chunk: StreamChunk) -> List[StreamChunk]:
        return self._apply("r", chunk)

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        raise TypeError("ShardedHashJoin is two-input: use apply_left/right")

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        if bool(jnp.any(self._em_overflow)):
            raise RuntimeError(
                "sharded join emission/exchange overflowed; raise out_cap "
                "or bucket_cap"
            )
        for name, side in (("left", self.left), ("right", self.right)):
            if bool(jnp.any(side.overflow)):
                raise RuntimeError(
                    f"{name} sharded join side overflowed (fanout/probe); "
                    "grow fanout/capacity"
                )
            if bool(jnp.any(side.inconsistent)):
                raise RuntimeError(
                    f"{name} sharded join side saw a DELETE matching no "
                    "stored row"
                )
        return []

    # -- static contracts (analysis/) -------------------------------------
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
        }

    def trace_contract(self):
        return {
            "kind": "host",
            "host_reason": "mesh-resident sharded step: per-fragment "
            "SPMD fusion is tracked by the mesh analyzer (RW-E9xx), "
            "not the single-chip fuser",
            "state": (self.left, self.right),
            "donate": True,
            "emission": "fixed",
            "emission_caps": (self.out_cap,),
            "two_input": True,
            "two_input_fusible": False,
            "fallback_syncs": ("on_barrier", "shard_occupancy"),
        }

    def mesh_contract(self):
        def trace_steps(abs_chunk):
            # self-seeded: each arrival's chunk carries that SIDE's
            # lanes (the threaded source spec can't describe both), so
            # build the abstract chunks from the declared schemas and
            # take only capacity/shard count from the caller's chunk
            from risingwave_tpu.analysis.mesh_domain import (
                abstract_tree,
                stacked_schema_chunk,
            )

            cap = int(abs_chunk.valid.shape[-1])
            n = (
                int(abs_chunk.valid.shape[0])
                if getattr(abs_chunk.valid, "ndim", 1) > 1
                else self.n_shards
            )
            left = abstract_tree(self.left)
            right = abstract_tree(self.right)
            em = abstract_tree(self._em_overflow)
            lchunk = stacked_schema_chunk(
                self._lint_left_dtypes, self._lint_left_nulls, cap, n
            )
            rchunk = stacked_schema_chunk(
                self._lint_right_dtypes, self._lint_right_nulls, cap, n
            )
            return [
                (
                    "apply_left",
                    self._build_step("l", cap),
                    (left, right, em, lchunk),
                ),
                (
                    "apply_right",
                    self._build_step("r", cap),
                    (right, left, em, rchunk),
                ),
            ]

        return {
            "axis": self.axis,
            "n_shards": self.n_shards,
            "state": {
                "left": "sharded",
                "right": "sharded",
                "_em_overflow": "sharded",
            },
            "updates": ("left", "right", "_em_overflow"),
            "dispatch": {
                "fn": "dest_shard",
                "keys": {"l": self.left_keys, "r": self.right_keys},
                "vnode_axis": self.axis,
            },
            "exchange": "all_to_all",
            "donate": True,
            "order_insensitive": True,  # emission slots are ordered by
            # (bucket lane, stored slot), both deterministic
            "trace_steps": trace_steps,
            "barrier_methods": ("on_barrier", "shard_occupancy"),
            "emission": "stacked",
        }

    # -- capacity escape (watchdog replay, scale.rs:453 analogue) ---------
    def capacity_overflow_latched(self) -> bool:
        if bool(jnp.any(self._em_overflow)):
            return True
        return any(
            bool(jnp.any(getattr(self, s).overflow))
            for s in ("left", "right")
        )

    def grow_for_replay(self) -> None:
        """Double the overflowed dimension (emission/bucket caps on the
        exchange latch; capacity+fanout on a side latch) and reset both
        sides empty at the new shapes — the mid-epoch state is poisoned
        either way, and recover() restores the durable rows before the
        epoch replays."""
        if bool(jnp.any(self._em_overflow)):
            self.out_cap *= 2
            double_bucket_cap(self)
        side_ovf = any(
            bool(jnp.any(getattr(self, s).overflow))
            for s in ("left", "right")
        )
        f = 2 if side_ovf else 1  # key lanes pair: grow both sides
        for name in ("left", "right"):
            proto = jax.tree.map(lambda a: a[0], getattr(self, name))
            side1 = JoinSide.create(
                proto.capacity * f,
                proto.fanout * f,
                tuple(k.dtype for k in proto.table.keys),
                {nm: a.dtype for nm, a in proto.rows.items()},
                nullable=tuple(proto.row_nulls),
            )
            setattr(
                self, name, stack_for_mesh(side1, self.mesh, self.axis)
            )
        self._em_overflow = stack_for_mesh(
            jnp.zeros((), jnp.bool_), self.mesh, self.axis
        )
        self._steps = {}

    # -- integrity --------------------------------------------------------
    def state_digest(self) -> int:
        """Shard-flattened twin of the single-chip join digest (the
        per-side folds XOR, like HashJoinExecutor.state_digest)."""
        from types import SimpleNamespace

        from risingwave_tpu.integrity import host_digest, join_side_lanes

        def flat(a):
            a = np.asarray(a)
            return a.reshape((-1,) + a.shape[2:])

        def flat_side(side):
            table = SimpleNamespace(
                keys=tuple(flat(k) for k in side.table.keys),
                live=flat(side.table.live),
            )
            return SimpleNamespace(
                table=table,
                rows={n: flat(a) for n, a in side.rows.items()},
                row_nulls={
                    n: flat(a) for n, a in side.row_nulls.items()
                },
                row_valid=flat(side.row_valid),
                degree=flat(side.degree),
            )

        ld = host_digest(*join_side_lanes(flat_side(self.left), np.where))
        rd = host_digest(
            *join_side_lanes(flat_side(self.right), np.where)
        )
        return ld ^ rd

    # -- checkpoint/restore (two logical tables across shards) -----------
    def checkpoint_table_ids(self) -> List[str]:
        return [f"{self.table_id}.left", f"{self.table_id}.right"]

    def checkpoint_delta(self) -> List[StateDelta]:
        """Same lane naming as the single-chip join (_side_delta):
        k{i} key lanes + rv/deg/r_*/n_* 2D bucket lanes, each side ONE
        logical table; keys are globally unique across shards."""
        out = []
        for name in ("left", "right"):
            d = self._sharded_side_delta(name)
            if d is not None:
                out.append(d)
        return out

    def _sharded_side_delta(self, name: str) -> Optional[StateDelta]:
        side = getattr(self, name)
        marks = classify_marks(side.sdirty, side.table.live, side.stored)
        setattr(
            self,
            name,
            dataclasses.replace(
                side, sdirty=marks.sdirty, stored=marks.stored
            ),
        )
        if not len(marks):
            return None
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        lanes = {f"k{i}": flat(l) for i, l in enumerate(side.table.keys)}
        key_names = tuple(lanes)
        lanes["rv"] = flat(side.row_valid)
        lanes["deg"] = flat(side.degree)
        for nm, a in side.rows.items():
            lanes[f"r_{nm}"] = flat(a)
        for nm, a in side.row_nulls.items():
            lanes[f"n_{nm}"] = flat(a)
        pulled = pull_rows(lanes, marks)
        keys = {k: pulled[k] for k in key_names}
        vals = {k: v for k, v in pulled.items() if k not in key_names}
        return StateDelta(
            f"{self.table_id}.{name}", keys, vals, marks.tombstone, key_names
        )

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        """Re-partition one side's recovered rows by vnode (the same
        positional-key hash the exchange uses) and rebuild every shard
        with the single-chip _side_restore — works across mesh sizes."""
        name = "left" if table_id.endswith(".left") else "right"
        side = getattr(self, name)
        proto = jax.tree.map(lambda a: a[0], side)
        n_rows = len(next(iter(key_cols.values()))) if key_cols else 0
        dest = None
        if n_rows:
            lanes = tuple(
                jnp.asarray(np.asarray(key_cols[f"k{i}"], dtype=k.dtype))
                for i, k in enumerate(proto.table.keys)
            )
            dest = np.asarray(dest_shard(lanes, self.n_shards))
            # uniform per-shard capacity: _side_restore grows from the
            # template's capacity, so pre-grow the template to the
            # hottest shard's need and every shard lands on one shape
            cap = grow_pow2(
                int(np.bincount(dest, minlength=self.n_shards).max()),
                proto.capacity,
                GROW_AT,
            )
        else:
            cap = proto.capacity
        template = JoinSide.create(
            cap,
            proto.fanout,
            tuple(k.dtype for k in proto.table.keys),
            {nm: a.dtype for nm, a in proto.rows.items()},
            nullable=tuple(proto.row_nulls),
        )
        sides = []
        for k in range(self.n_shards):
            if n_rows:
                m = dest == k
                sub_k = {kk: v[m] for kk, v in key_cols.items()}
                sub_v = {kk: v[m] for kk, v in value_cols.items()}
            else:
                sub_k, sub_v = {}, {}
            sides.append(_side_restore(template, sub_k, sub_v))
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *sides)
        setattr(
            self,
            name,
            jax.device_put(stacked, NamedSharding(self.mesh, P(self.axis))),
        )
        self._em_overflow = stack_for_mesh(
            jnp.zeros((), jnp.bool_), self.mesh, self.axis
        )
        self._steps = {}  # capacities may have changed: recompile


# -- mesh observability surface (meshprof / scale / memory governor) ------
def stacked_state_nbytes_per_shard(self) -> List[int]:
    """Uniform split of the stacked device state: every per-slot array
    carries the same ``(n_shards, ...)`` shape, so per-shard bytes are
    exactly total/n with NO device read — the rw_memory per-shard rows
    and meshprof's state_bytes lane."""
    n = self.n_shards
    return [self.state_nbytes() // n] * n


def _sharded_dedup_state_nbytes(self) -> int:
    return int(
        sum(
            leaf.nbytes
            for leaf in jax.tree.leaves(
                (self.table, self.sdirty, self.flags)
            )
        )
    )


def _sharded_dedup_shard_occupancy(self):
    """Per-shard claimed-slot counts (autoscale + skew input). One
    packed device read."""
    return np.asarray(
        jnp.sum((self.table.fp1 != jnp.uint32(0)).astype(jnp.int32), axis=1)
    )


def _sharded_join_state_nbytes(self) -> int:
    return int(
        sum(
            leaf.nbytes
            for leaf in jax.tree.leaves((self.left, self.right))
        )
    )


def _sharded_join_shard_occupancy(self):
    occ = jnp.sum(
        (self.left.table.fp1 != jnp.uint32(0)).astype(jnp.int32), axis=1
    ) + jnp.sum(
        (self.right.table.fp1 != jnp.uint32(0)).astype(jnp.int32), axis=1
    )
    return np.asarray(occ)


ShardedDedup.state_nbytes = _sharded_dedup_state_nbytes
ShardedDedup.state_nbytes_per_shard = stacked_state_nbytes_per_shard
ShardedDedup.shard_occupancy = _sharded_dedup_shard_occupancy
ShardedHashJoin.state_nbytes = _sharded_join_state_nbytes
ShardedHashJoin.state_nbytes_per_shard = stacked_state_nbytes_per_shard
ShardedHashJoin.shard_occupancy = _sharded_join_shard_occupancy
