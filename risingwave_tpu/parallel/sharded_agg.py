"""Vnode-sharded HashAgg: hash exchange + grouped state on a mesh.

Reference roles replaced (SURVEY.md §2.11, §3.3):
- ``HashDataDispatcher`` — rows route to the downstream actor owning
  their key's vnode (src/stream/src/executor/dispatch.rs:683,
  vnode mapping src/common/src/hash/consistent_hash/vnode.rs:34);
- the exchange channel / gRPC GetStream between actors
  (src/stream/src/executor/exchange/permit.rs:35) — here a single
  ``lax.all_to_all`` over the mesh's ICI links inside the jit step;
- N parallel HashAgg actors, each owning its vnode slice of group
  state (src/stream/src/executor/hash_agg.rs:62).

Design: state lives STACKED — every per-slot array gains a leading
``(n_shards,)`` axis sharded over the mesh. The step runs under
``shard_map``; inside, each shard:

1. computes each local row's destination shard ``vnode(key) % n``;
2. packs rows into per-destination buckets of static capacity
   (compaction by cumulative count — no sort on the hot path);
3. exchanges buckets with ``lax.all_to_all`` (the ICI shuffle);
4. runs the SAME single-chip kernels (lookup_or_insert + agg apply)
   on the received rows against its local slot table.

Each group key lives on exactly one shard, so per-barrier flush is
shard-local and the concatenated deltas are globally exact. Bucket
overflow (static capacity exceeded by a skewed chunk) latches the
``dropped`` flag — the same correctness backstop as MAX_PROBE
overflow, surfaced at the next barrier.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor
from risingwave_tpu.executors.hash_agg import (
    _build_key_lanes,
    _rehash,
    build_restored_agg,
)
from risingwave_tpu.ops import agg as agg_ops
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.ops.hash_table import HashTable, lookup_or_insert, set_live
from risingwave_tpu.parallel.sharded_join import (
    double_bucket_cap,
    stacked_state_nbytes_per_shard,
    track_bucket_cap,
)
from risingwave_tpu.parallel.exchange import (
    dest_shard as _dest_shard,
    exchange_chunk,
    pack_buckets as _pack_buckets,
)
from risingwave_tpu.storage.state_table import (
    Checkpointable,
    StateDelta,
    classify_marks,
    pull_rows,
)

GROW_AT = 0.5


def make_mesh(n_devices: Optional[int] = None, axis: str = "shard") -> Mesh:
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.asarray(devs), (axis,))


class ShardedHashAgg(Executor, Checkpointable):
    """Mesh-parallel HashAgg with on-device hash exchange.

    The executor owns stacked (n_shards, capacity) state sharded over
    ``mesh``; ``apply`` expects stacked (n_shards, chunk_cap) input
    chunks (each shard's source slice — e.g. one Nexmark split per
    shard); flush returns host-side StreamChunks.

    Capacity is per-shard and GROWS 2x when the per-shard insert bound
    trips 50% load (per-shard rehash under one shard_map program).
    Checkpoints stage ONE table of all shards' changed rows (keys are
    globally unique — each lives on exactly one shard); restore
    re-partitions rows by vnode, so recovery works across DIFFERENT
    mesh sizes (vnode.rs:34 remap semantics).
    """

    def __init__(
        self,
        mesh: Mesh,
        group_keys: Sequence[str],
        calls: Sequence[AggCall],
        schema_dtypes: Dict[str, object],
        capacity: int = 1 << 16,
        out_cap: int = 1 << 14,
        bucket_cap: Optional[int] = None,
        chunk_cap: Optional[int] = None,
        nullable_keys: Sequence[str] = (),
        table_id: str = "sharded_agg",
        stacked_out: bool = False,
    ):
        self.table_id = table_id
        # stacked_out keeps barrier-flush deltas as STACKED device
        # chunks — required when the flush feeds another sharded op
        # (e.g. a join side: q7's per-window MAX change stream) instead
        # of crossing the host boundary
        self.stacked_out = stacked_out
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_shards = mesh.devices.size
        self.group_keys = tuple(group_keys)
        self.calls = tuple(calls)
        if any(c.materialized for c in self.calls):
            raise NotImplementedError(
                "materialized MIN/MAX is single-chip only for now"
            )
        self.nullable = tuple(k in set(nullable_keys) for k in self.group_keys)
        self.capacity = capacity
        self.out_cap = out_cap
        self._dtypes = dict(schema_dtypes)
        self._float_extremes = agg_ops.float_extreme_meta(
            self.calls, {k: jnp.dtype(v) for k, v in self._dtypes.items()}
        )
        self.bucket_cap = bucket_cap

        key_dtypes = []
        for k, nb in zip(self.group_keys, self.nullable):
            key_dtypes.append(jnp.dtype(self._dtypes[k]))
            if nb:
                key_dtypes.append(jnp.dtype(jnp.bool_))
        table1 = HashTable.create(capacity, key_dtypes)
        state1 = agg_ops.create_state(capacity, self.calls, self._dtypes)

        def stack(a):
            return jnp.broadcast_to(a[None], (self.n_shards,) + a.shape)

        shard0 = NamedSharding(mesh, P(self.axis))
        self._shard0 = shard0
        self._key_dtypes = tuple(key_dtypes)
        self.table = jax.device_put(jax.tree.map(stack, table1), shard0)
        self.state = jax.device_put(jax.tree.map(stack, state1), shard0)
        self.dropped = jax.device_put(
            jnp.zeros(self.n_shards, jnp.bool_), shard0
        )
        self._step = None  # built lazily (needs bucket_cap from chunk)
        self._insert_bound = 0  # per-shard upper bound of claimed slots
        self._built_bucket_cap: Optional[int] = None
        self.ex_counts_last = None  # (n, n) routed-row histogram, device

    # -- the sharded step -------------------------------------------------
    def _build_step(self, chunk_cap: int):
        n_shards = self.n_shards
        bucket_cap = self.bucket_cap or max(64, (2 * chunk_cap) // n_shards)
        track_bucket_cap(self, bucket_cap)
        calls, group_keys, nullable = self.calls, self.group_keys, self.nullable
        axis = self.axis

        def local_step(table, state, dropped, chunk: StreamChunk):
            # shard_map gives each shard its (1, ...) slice; drop the axis
            table = jax.tree.map(lambda a: a[0], table)
            state = jax.tree.map(lambda a: a[0], state)
            dropped = dropped[0]
            chunk = jax.tree.map(lambda a: a[0], chunk)

            # 1-3) vnode route + bucket pack + all_to_all ICI shuffle
            keys = _build_key_lanes(chunk, group_keys, nullable)
            rchunk, overflow, ex_counts = exchange_chunk(
                chunk, keys, n_shards, bucket_cap, axis
            )

            # 4) local agg over the received rows
            rkeys = _build_key_lanes(rchunk, group_keys, nullable)
            table, slots, _, _ = lookup_or_insert(table, rkeys, rchunk.valid)
            signs = rchunk.effective_signs()
            dropped = (
                dropped
                | overflow
                | jnp.any(rchunk.valid & (slots < 0))
            )
            values = {
                c.input: rchunk.col(c.input) for c in calls if c.input is not None
            }
            in_nulls = {
                c.input: rchunk.nulls[c.input]
                for c in calls
                if c.input is not None and c.input in rchunk.nulls
            }
            state = agg_ops.apply(state, calls, slots, signs, values, in_nulls)
            table = set_live(table, slots, state.row_count[slots] > 0)

            expand = lambda a: a[None]
            return (
                jax.tree.map(expand, table),
                jax.tree.map(expand, state),
                dropped[None],
                ex_counts[None],  # (1, n): this shard's routing row
            )

        spec = P(self.axis)
        shmapped = jax.shard_map(
            local_step,
            mesh=self.mesh,
            in_specs=(spec, spec, spec, spec),
            out_specs=(spec, spec, spec, spec),
            check_vma=False,
        )
        return jax.jit(shmapped, donate_argnums=(0, 1))

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        """``chunk`` must be stacked: every array (n_shards, chunk_cap),
        sharded or shardable over the mesh axis."""
        for k, nb in zip(self.group_keys, self.nullable):
            if not nb and k in chunk.nulls:
                raise ValueError(
                    f"group key {k!r} carries a null lane but was not "
                    "declared in nullable_keys"
                )
        chunk_cap = chunk.valid.shape[-1]
        if self._step is None:
            self._step = self._build_step(chunk_cap)
        # worst case a shard receives every row of the exchange
        bucket_cap = self.bucket_cap or max(64, (2 * chunk_cap) // self.n_shards)
        self._maybe_grow(self.n_shards * bucket_cap)
        self._insert_bound += self.n_shards * bucket_cap
        self.table, self.state, self.dropped, self.ex_counts_last = (
            self._step(self.table, self.state, self.dropped, chunk)
        )
        return []

    def _maybe_grow(self, incoming: int) -> None:
        """Per-shard 2x rehash when the insert bound trips GROW_AT load
        (the single-chip growth contract, applied per shard under one
        shard_map program)."""
        cap = self.capacity
        if self._insert_bound + incoming <= cap * GROW_AT:
            return
        claimed = int(jnp.max(jnp.sum(
            (self.table.fp1 != jnp.uint32(0)).astype(jnp.int32), axis=1
        )))
        keep = (
            self.table.live
            | self.state.emitted_valid
            | self.state.dirty
            | self.state.sdirty
        ) & (self.table.fp1 != jnp.uint32(0))
        survivors = int(jnp.max(jnp.sum(keep.astype(jnp.int32), axis=1)))
        from risingwave_tpu.ops.hash_table import plan_rehash

        new_cap = plan_rehash(cap, incoming, claimed, survivors, GROW_AT)
        if new_cap is not None:
            calls = self.calls
            spec = P(self.axis)

            def local(table, state):
                table = jax.tree.map(lambda a: a[0], table)
                state = jax.tree.map(lambda a: a[0], state)
                t2, s2, _ = _rehash(table, state, {}, calls, new_cap)
                ex = lambda t: jax.tree.map(lambda a: a[None], t)
                return ex(t2), ex(s2)

            grow = jax.jit(
                jax.shard_map(
                    local,
                    mesh=self.mesh,
                    in_specs=(spec, spec),
                    out_specs=(spec, spec),
                    check_vma=False,
                ),
                donate_argnums=(0, 1),
            )
            self.table, self.state = grow(self.table, self.state)
            self.capacity = new_cap
            claimed = int(jnp.max(jnp.sum(
                (self.table.fp1 != jnp.uint32(0)).astype(jnp.int32), axis=1
            )))
        self._insert_bound = claimed

    # -- capacity escape (watchdog replay, scale.rs:453 analogue) ---------
    def capacity_overflow_latched(self) -> bool:
        return bool(jnp.any(self.dropped))

    def grow_for_replay(self) -> None:
        """Double the skew-sensitive capacities (exchange bucket,
        emission cap, probe table) and reset device state; recover()
        restores the durable rows before the poisoned epoch replays."""
        double_bucket_cap(self)
        self.out_cap *= 2
        self.capacity *= 2
        table1 = HashTable.create(self.capacity, self._key_dtypes)
        state1 = agg_ops.create_state(
            self.capacity, self.calls, self._dtypes
        )
        stack = lambda a: jnp.broadcast_to(
            a[None], (self.n_shards,) + a.shape
        )
        self.table = jax.device_put(
            jax.tree.map(stack, table1), self._shard0
        )
        self.state = jax.device_put(
            jax.tree.map(stack, state1), self._shard0
        )
        self.dropped = jax.device_put(
            jnp.zeros(self.n_shards, jnp.bool_), self._shard0
        )
        self._insert_bound = 0
        self._step = None
        if hasattr(self, "_flush"):
            del self._flush

    # -- barrier flush ----------------------------------------------------
    def _build_flush(self):
        out_cap, fx = self.out_cap, self._float_extremes

        def local_flush(state, table_keys):
            state = jax.tree.map(lambda a: a[0], state)
            table_keys = jax.tree.map(lambda a: a[0], table_keys)
            state, delta = agg_ops.flush(state, table_keys, out_cap, fx)
            expand = lambda a: a[None]
            return jax.tree.map(expand, state), jax.tree.map(expand, delta)

        spec = P(self.axis)
        return jax.jit(
            jax.shard_map(
                local_flush,
                mesh=self.mesh,
                in_specs=(spec, spec),
                out_specs=(spec, spec),
                check_vma=False,
            )
        )

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        if bool(jnp.any(self.dropped)):
            raise RuntimeError(
                "sharded agg overflowed (bucket or probe); grow capacities"
            )
        if not hasattr(self, "_flush"):
            self._flush = self._build_flush()
        outs: List[StreamChunk] = []
        # each round drains up to out_cap dirty groups per shard, so
        # capacity/out_cap rounds always suffice; a persistently-set
        # overflow flag (kernel bug) must raise, not hang (ADVICE r2)
        max_rounds = max(2, self.capacity // max(1, self.out_cap)) + 2
        for _ in range(max_rounds):
            self.state, delta = self._flush(self.state, self.table.keys)
            outs.append(self._delta_to_chunk(delta))
            if not bool(jnp.any(delta["overflow"])):
                return outs
        raise RuntimeError(
            f"sharded agg flush did not drain in {max_rounds} rounds — "
            "overflow flag appears stuck"
        )

    def _delta_to_chunk(self, delta) -> StreamChunk:
        """Stacked (n_shards, 2*out_cap) delta -> one flat StreamChunk
        (or, with ``stacked_out``, a stacked device chunk that flows
        straight into the next sharded op with no host round-trip)."""
        if self.stacked_out:
            flat = lambda a: a  # keep the shard axis + device residency
        else:
            flat = lambda a: np.asarray(a).reshape(-1)
        cols, nulls = {}, {}
        i = 0
        for name, nb in zip(self.group_keys, self.nullable):
            cols[name] = flat(delta[f"key{i}"])
            i += 1
            if nb:
                nulls[name] = flat(delta[f"key{i}"])
                i += 1
        for c in self.calls:
            cols[c.output] = flat(delta[c.output])
            lane = delta.get(c.output + "__isnull")
            if lane is not None:
                nulls[c.output] = flat(lane)
        return StreamChunk(
            columns={k: jnp.asarray(v) for k, v in cols.items()},
            valid=jnp.asarray(flat(delta["valid"])),
            nulls={k: jnp.asarray(v) for k, v in nulls.items()},
            ops=jnp.asarray(flat(delta["ops"])),
        )

    # -- static contracts (analysis/) -------------------------------------
    def lint_info(self):
        emits = {k: self._dtypes.get(k) for k in self.group_keys}
        renames = {k: k for k in self.group_keys}
        requires = set(self.group_keys)
        for c in self.calls:
            if c.input is not None:
                requires.add(c.input)
            if c.kind in ("count", "count_star"):
                out_dt = jnp.int64
            elif c.kind in ("min", "max") and c.input in self._dtypes:
                out_dt = self._dtypes[c.input]
            else:
                out_dt = None  # sum/avg widen by kind-specific rules
            emits[c.output] = out_dt
            renames[c.output] = None
        return {
            "requires": tuple(sorted(requires)),
            "expects": {
                k: self._dtypes[k]
                for k in sorted(requires)
                if k in self._dtypes
            },
            "emits": emits,
            "renames": renames,
            "keys": self.group_keys,
            "table_ids": (self.table_id,),
            "window_key": None,
        }

    def trace_contract(self):
        # mesh-resident: the per-chunk step IS one jitted shard_map
        # dispatch, but single-chip fusion cannot absorb it — whether
        # the whole sharded fragment collapses into one SPMD dispatch
        # is the mesh analyzer's E9xx question (mesh_contract below).
        # The host reads (flush drain, growth planning, occupancy) are
        # declared as fallback_syncs so the fusion corpus accounts for
        # the parallel path instead of skipping it as opaque.
        full = self.out_cap
        return {
            "kind": "host",
            "host_reason": "mesh-resident sharded step: per-fragment "
            "SPMD fusion is tracked by the mesh analyzer (RW-E9xx), "
            "not the single-chip fuser",
            "state": (self.table, self.state),
            "donate": True,
            "emission": "bucketed",
            "emission_caps": (
                (full,) if self.stacked_out else (self.n_shards * full,)
            ),
            "fallback_syncs": (
                "on_barrier",
                "_delta_to_chunk",
                "_maybe_grow",
                "shard_occupancy",
            ),
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
                        abstract_tree(self.state),
                        abstract_tree(self.dropped),
                        abs_chunk,
                    ),
                )
            ]

        return {
            "axis": self.axis,
            "n_shards": self.n_shards,
            "state": {
                "table": "sharded",
                "state": "sharded",
                "dropped": "sharded",
            },
            "updates": ("table", "state", "dropped"),
            "dispatch": {
                "fn": "dest_shard",
                "keys": self.group_keys,
                "vnode_axis": self.axis,
            },
            "exchange": "all_to_all",
            "donate": True,
            # per-slot merges apply in received-bucket order, which the
            # deterministic all_to_all layout fixes per (src, lane)
            "order_insensitive": True,
            "trace_steps": trace_steps,
            "barrier_methods": (
                "on_barrier",
                "_delta_to_chunk",
                "_maybe_grow",
                "shard_occupancy",
            ),
            "emission": "stacked" if self.stacked_out else "host",
        }


def _sharded_agg_shard_occupancy(self):
    """Per-shard claimed-slot counts (autoscale policy input,
    parallel/scale.py). One packed device read."""
    return np.asarray(
        jnp.sum((self.table.fp1 != jnp.uint32(0)).astype(jnp.int32), axis=1)
    )


def _sharded_agg_checkpoint_delta(self) -> List[StateDelta]:
    """Stage ALL shards' changed rows as ONE table (keys are globally
    unique across shards); same lane naming as the single-chip agg so
    either executor can restore the other's checkpoint."""
    marks = classify_marks(
        self.state.sdirty,
        (self.table.live, self.state.emitted_valid, self.state.dirty),
        self.state.stored,
    )
    # eager flip — see StateDelta's durability contract
    self.state = dataclasses.replace(
        self.state, sdirty=marks.sdirty, stored=marks.stored
    )
    if not len(marks):
        return []
    flat = lambda a: a.reshape((-1,) + a.shape[2:])
    lanes = {f"k{i}": flat(lane) for i, lane in enumerate(self.table.keys)}
    key_names = tuple(lanes)
    lanes["row_count"] = flat(self.state.row_count)
    for n, a in self.state.accums.items():
        lanes[f"acc_{n}"] = flat(a)
        lanes[f"em_{n}"] = flat(self.state.emitted[n])
    for n, a in self.state.nonnull.items():
        lanes[f"nn_{n}"] = flat(a)
        lanes[f"ei_{n}"] = flat(self.state.emitted_isnull[n])
    lanes["ev"] = flat(self.state.emitted_valid)
    pulled = pull_rows(lanes, marks)
    keys = {k: pulled[k] for k in key_names}
    vals = {k: v for k, v in pulled.items() if k not in key_names}
    return [StateDelta(self.table_id, keys, vals, marks.tombstone, key_names)]


def _sharded_agg_restore_state(self, table_id, key_cols, value_cols) -> None:
    """Re-partition recovered rows by vnode and rebuild every shard —
    works across mesh sizes (a key's shard is vnode % n_shards, so a
    different mesh just remaps vnodes; vnode.rs:34)."""
    n = len(next(iter(key_cols.values()))) if key_cols else 0
    if n:
        lanes = tuple(
            jnp.asarray(np.asarray(key_cols[f"k{i}"], dtype=d))
            for i, d in enumerate(self._key_dtypes)
        )
        dest = np.asarray(_dest_shard(lanes, self.n_shards))
    cap = self.capacity
    while n and max(
        np.bincount(dest, minlength=self.n_shards).max(), 1
    ) > cap * GROW_AT:
        cap *= 2
    tables, states = [], []
    for k in range(self.n_shards):
        sel = np.flatnonzero(dest == k) if n else np.zeros(0, np.int64)
        t, s, _ = build_restored_agg(
            cap, self.calls, self._dtypes, self._key_dtypes,
            key_cols, value_cols, sel=sel,
        )
        tables.append(t)
        states.append(s)
    stack = lambda *xs: jnp.stack(xs)
    self.table = jax.device_put(
        jax.tree.map(stack, *tables), self._shard0
    )
    self.state = jax.device_put(
        jax.tree.map(stack, *states), self._shard0
    )
    self.capacity = cap
    self.dropped = jax.device_put(
        jnp.zeros(self.n_shards, jnp.bool_), self._shard0
    )
    self._insert_bound = int(
        np.bincount(dest, minlength=self.n_shards).max()
    ) if n else 0


def _sharded_agg_state_nbytes(self) -> int:
    """Stacked device bytes across all shards (memory-governor ledger
    + meshprof state_bytes lane)."""
    return int(
        sum(
            leaf.nbytes
            for leaf in jax.tree.leaves((self.table, self.state))
        )
    )


def _sharded_agg_state_digest(self) -> int:
    """Shard-flattened agg fold (integrity.agg_lanes over the stacked
    pytree): equal to the single-chip twin's digest for the same
    logical groups — slot order and shard placement cancel out."""
    from risingwave_tpu.integrity import agg_lanes, host_digest

    lanes, live = agg_lanes(self.table, self.state)

    def flat(a):
        a = np.asarray(a)
        return a.reshape((-1,) + a.shape[2:])

    return host_digest({k: flat(v) for k, v in lanes.items()}, flat(live))


ShardedHashAgg.checkpoint_delta = _sharded_agg_checkpoint_delta
ShardedHashAgg.shard_occupancy = _sharded_agg_shard_occupancy
ShardedHashAgg.restore_state = _sharded_agg_restore_state
ShardedHashAgg.state_nbytes = _sharded_agg_state_nbytes
ShardedHashAgg.state_digest = _sharded_agg_state_digest
ShardedHashAgg.state_nbytes_per_shard = stacked_state_nbytes_per_shard
