"""Mesh-sharded materialized view: pk-partitioned device MV state.

Reference roles replaced (SURVEY.md §2.11; VERDICT r4 #6):
- N parallel MaterializeExecutor actors each owning the vnode slice of
  the MV's pk space (src/stream/src/executor/mview/materialize.rs:44,
  distributed by the fragment's hash exchange, dispatch.rs:683);
- the batch-read storage table serving point/snapshot reads over those
  slices (src/storage/src/table/batch_table/).

TPU re-design: the MV's pk hash table + value lanes gain a leading
``(n_shards,)`` axis sharded over the mesh; each ``apply`` is ONE
jitted ``shard_map`` program — vnode exchange by pk
(``parallel.exchange``) then the single-chip ``mv_step_fn`` kernel on
the received rows. Every pk lives on exactly one shard, so snapshots
concatenate and checkpoints are one logical table (same ``k{j}``/
``v{j}``/``n_{c}`` lane naming as DeviceMaterializeExecutor — either
executor can restore the other's checkpoint, and restore re-partitions
rows by vnode so recovery works across mesh sizes, vnode.rs:34).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor
from risingwave_tpu.executors.materialize import (
    MvDeviceReadMixin,
    MvDeviceState,
    mv_step_fn,
)
from risingwave_tpu.ops.hash_table import HashTable, lookup, lookup_or_insert
from risingwave_tpu.parallel.exchange import dest_shard, exchange_chunk
from risingwave_tpu.parallel.sharded_join import (
    double_bucket_cap,
    stack_for_mesh,
    stacked_state_nbytes_per_shard,
    track_bucket_cap,
)
from risingwave_tpu.storage.state_table import (
    Checkpointable,
    StateDelta,
    classify_marks,
    grow_pow2,
    pull_rows,
)

GROW_AT = 0.5


class ShardedMaterialize(MvDeviceReadMixin, Executor, Checkpointable):
    """Vnode-partitioned device MV over a jax Mesh.

    ``apply`` expects STACKED (n_shards, cap) chunks (a sharded join's
    emissions or a sharded agg's stacked flush); rows route to the
    shard owning their pk vnode on ICI, then upsert locally with the
    single-chip kernel. Passes its input through unchanged (the
    Materialize contract — downstream sinks/subscribers see the same
    change stream).

    Schema constraint: fixed-width non-nullable pk lanes (the same
    constraint as DeviceMaterializeExecutor; NULLs in VALUE columns
    ride per-column null lanes).
    """

    def __init__(
        self,
        mesh: Mesh,
        pk: Sequence[str],
        columns: Sequence[str],
        schema_dtypes: Dict[str, object],
        table_id: str = "mview",
        capacity: int = 1 << 16,
        nullable: Sequence[str] = (),
        bucket_cap: Optional[int] = None,
    ):
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_shards = mesh.devices.size
        self.pk = tuple(pk)
        self.columns = tuple(columns)
        self.table_id = table_id
        self.capacity = capacity
        self.bucket_cap = bucket_cap
        self.dtypes = {
            n: jnp.dtype(schema_dtypes[n]) for n in self.pk + self.columns
        }
        table1 = HashTable.create(
            capacity, tuple(self.dtypes[k] for k in self.pk)
        )
        state1 = MvDeviceState(
            values={
                c: jnp.zeros(capacity, self.dtypes[c]) for c in self.columns
            },
            vnulls={
                c: jnp.zeros(capacity, jnp.bool_)
                for c in nullable
                if c in self.columns
            },
            sdirty=jnp.zeros(capacity, jnp.bool_),
            stored=jnp.zeros(capacity, jnp.bool_),
            dropped=jnp.zeros((), jnp.bool_),
        )
        self.table = stack_for_mesh(table1, mesh, self.axis)
        self.state = stack_for_mesh(state1, mesh, self.axis)
        self._steps: Dict[int, object] = {}
        self.checkpoint_enabled = False
        self.ex_counts_last = None  # (n, n) routed-row histogram, device

    # -- the sharded step -------------------------------------------------
    def _build_step(self, chunk_cap: int):
        n, axis, pk, cols = self.n_shards, self.axis, self.pk, self.columns
        bucket_cap = self.bucket_cap or max(64, (2 * chunk_cap) // n)
        track_bucket_cap(self, bucket_cap)

        def local(table, state, chunk):
            table, state, chunk = jax.tree.map(
                lambda a: a[0], (table, state, chunk)
            )
            lanes = tuple(chunk.col(k) for k in pk)
            rchunk, ex_ovf, ex_counts = exchange_chunk(
                chunk, lanes, n, bucket_cap, axis
            )
            table, state = mv_step_fn(table, state, rchunk, pk, cols)
            state = MvDeviceState(
                state.values,
                state.vnulls,
                state.sdirty,
                state.stored,
                state.dropped | ex_ovf,
            )
            ex = lambda t: jax.tree.map(lambda a: a[None], t)
            return ex(table), ex(state), ex_counts[None]

        spec = P(self.axis)
        return jax.jit(
            jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=(spec,) * 3,
                out_specs=(spec,) * 3,
                check_vma=False,
            ),
            donate_argnums=(0, 1),
        )

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        cap = chunk.valid.shape[-1]
        step = self._steps.get(cap)
        if step is None:
            step = self._steps[cap] = self._build_step(cap)
        self.table, self.state, self.ex_counts_last = step(
            self.table, self.state, chunk
        )
        return [chunk]

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        if bool(jnp.any(self.state.dropped)):
            raise RuntimeError(
                "sharded MV overflowed (probe chain or exchange bucket); "
                "grow capacity/bucket_cap"
            )
        return []

    # -- static contracts (analysis/) -------------------------------------
    def lint_info(self):
        return {
            "expects": dict(self.dtypes),
            "state_pk": tuple(self.pk),
            "keys": self.pk,
            "table_ids": (self.table_id,),
        }

    def trace_contract(self):
        return {
            "kind": "host",
            "host_reason": "mesh-resident sharded step: per-fragment "
            "SPMD fusion is tracked by the mesh analyzer (RW-E9xx), "
            "not the single-chip fuser",
            "state": (self.table, self.state),
            "donate": True,
            "emission": "passthrough",
            "fallback_syncs": (
                "on_barrier",
                "_host_rows",
                "get_rows",
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
                        abs_chunk,
                    ),
                )
            ]

        return {
            "axis": self.axis,
            "n_shards": self.n_shards,
            "state": {"table": "sharded", "state": "sharded"},
            "updates": ("table", "state"),
            "dispatch": {
                "fn": "dest_shard",
                "keys": self.pk,
                "vnode_axis": self.axis,
            },
            "exchange": "all_to_all",
            "donate": True,
            "order_insensitive": True,  # pk upserts: last writer per
            # slot, and arrival order within a chunk is preserved by
            # the bucket layout
            "trace_steps": trace_steps,
            "barrier_methods": ("on_barrier", "shard_occupancy"),
            # the serving reads fan out one device probe per
            # destination shard — the E907 scan targets
            "fanout_methods": ("get_rows", "_host_rows"),
            "emission": "passthrough",
        }

    # -- capacity escape (watchdog replay, scale.rs:453 analogue) ---------
    def capacity_overflow_latched(self) -> bool:
        return bool(jnp.any(self.state.dropped))

    def grow_for_replay(self) -> None:
        """Double pk-table capacity + exchange bucket and reset device
        state at the new shapes; recover() restores the durable rows
        before the poisoned epoch replays."""
        self.capacity *= 2
        double_bucket_cap(self)
        nullable = tuple(self.state.vnulls)
        table1 = HashTable.create(
            self.capacity, tuple(self.dtypes[k] for k in self.pk)
        )
        state1 = MvDeviceState(
            values={
                c: jnp.zeros(self.capacity, self.dtypes[c])
                for c in self.columns
            },
            vnulls={
                c: jnp.zeros(self.capacity, jnp.bool_) for c in nullable
            },
            sdirty=jnp.zeros(self.capacity, jnp.bool_),
            stored=jnp.zeros(self.capacity, jnp.bool_),
            dropped=jnp.zeros((), jnp.bool_),
        )
        self.table = stack_for_mesh(table1, self.mesh, self.axis)
        self.state = stack_for_mesh(state1, self.mesh, self.axis)
        self._steps = {}

    def state_nbytes(self) -> int:
        return sum(
            leaf.nbytes for leaf in jax.tree.leaves((self.table, self.state))
        )

    # -- reads ------------------------------------------------------------
    def _host_rows(self):
        """Flatten the shard axis (pks are globally unique) and pull
        every live row — the same one-bulk-transfer contract as
        DeviceMaterializeExecutor._host_rows."""
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        live = np.asarray(self.table.live).reshape(-1)
        sel = np.flatnonzero(live)
        lanes = {f"k{j}": flat(k) for j, k in enumerate(self.table.keys)}
        lanes.update(
            {
                f"v{j}": flat(self.state.values[c])
                for j, c in enumerate(self.columns)
            }
        )
        lanes.update(
            {f"n_{c}": flat(lane) for c, lane in self.state.vnulls.items()}
        )
        return sel, pull_rows(lanes, sel)

    # snapshot()/to_numpy() come from MvDeviceReadMixin

    def get_rows(self, key_tuples):
        """Point reads by pk (batch-table get_row analogue): route each
        key to its owning shard, probe that shard's slice read-only,
        and pull ONLY the matching slots — O(keys), not O(table)."""
        if not key_tuples:
            return []
        lanes = tuple(
            jnp.asarray(
                np.asarray([k[j] for k in key_tuples]),
                self.dtypes[self.pk[j]],
            )
            for j in range(len(self.pk))
        )
        dest = np.asarray(dest_shard(lanes, self.n_shards))
        out: List[Optional[tuple]] = [None] * len(key_tuples)
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        cap = self.table.live.shape[-1]
        for s in set(dest.tolist()):
            m = np.flatnonzero(dest == s)
            dsel = jnp.asarray(m)
            sub = tuple(l[dsel] for l in lanes)
            shard_table = jax.tree.map(lambda a: a[s], self.table)
            slots, found = lookup(
                shard_table, sub, jnp.ones(len(m), jnp.bool_)
            )
            hit = np.asarray(found & (slots >= 0))
            gsel = s * cap + np.asarray(slots)[hit]
            if not len(gsel):
                continue
            pulled = pull_rows(
                {
                    **{
                        f"v{j}": flat(self.state.values[c])
                        for j, c in enumerate(self.columns)
                    },
                    **{
                        f"n_{c}": flat(lane)
                        for c, lane in self.state.vnulls.items()
                    },
                },
                gsel,
            )
            for r, i in enumerate(m[hit]):
                out[i] = tuple(
                    None
                    if (f"n_{c}" in pulled and pulled[f"n_{c}"][r])
                    else pulled[f"v{j}"][r].item()
                    for j, c in enumerate(self.columns)
                )
        return out

    # -- integrity --------------------------------------------------------
    def state_digest(self) -> int:
        """Shard-flattened MV fold (integrity.mv_lanes): equal to the
        single-chip twin's digest for the same row set."""
        from risingwave_tpu.integrity import host_digest, mv_lanes

        lanes, live = mv_lanes(self.table, self.state)

        def flat(a):
            a = np.asarray(a)
            return a.reshape((-1,) + a.shape[2:])

        return host_digest(
            {k: flat(v) for k, v in lanes.items()}, flat(live)
        )

    # -- checkpoint/restore (one logical table across shards) ------------
    def checkpoint_delta(self) -> List[StateDelta]:
        marks = classify_marks(
            self.state.sdirty, self.table.live, self.state.stored
        )
        self.state.sdirty, self.state.stored = marks.sdirty, marks.stored
        if not len(marks):
            return []
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        lanes = {f"k{j}": flat(k) for j, k in enumerate(self.table.keys)}
        lanes.update(
            {
                f"v{j}": flat(self.state.values[c])
                for j, c in enumerate(self.columns)
            }
        )
        lanes.update(
            {f"n_{c}": flat(lane) for c, lane in self.state.vnulls.items()}
        )
        rows = pull_rows(lanes, marks)
        key_cols = {f"k{j}": rows[f"k{j}"] for j in range(len(self.pk))}
        value_cols = {
            f"v{j}": rows[f"v{j}"] for j in range(len(self.columns))
        }
        for c in self.state.vnulls:
            value_cols[f"n_{c}"] = rows[f"n_{c}"].astype(np.uint8)
        return [
            StateDelta(
                self.table_id,
                key_cols,
                value_cols,
                marks.tombstone,
                tuple(f"k{j}" for j in range(len(self.pk))),
            )
        ]

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        n = len(next(iter(key_cols.values()))) if key_cols else 0
        key_dtypes = tuple(self.dtypes[k] for k in self.pk)
        cap = self.capacity
        lanes = dest = None
        if n:
            lanes = tuple(
                jnp.asarray(np.asarray(key_cols[f"k{j}"], dtype=d))
                for j, d in enumerate(key_dtypes)
            )
            dest = np.asarray(dest_shard(lanes, self.n_shards))
            cap = grow_pow2(
                int(np.bincount(dest, minlength=self.n_shards).max()),
                cap,
                GROW_AT,
            )
        vn_names = tuple(self.state.vnulls)
        tables, states = [], []
        for s in range(self.n_shards):
            t = HashTable.create(cap, key_dtypes)
            values = {c: jnp.zeros(cap, self.dtypes[c]) for c in self.columns}
            vnulls = {c: jnp.zeros(cap, jnp.bool_) for c in vn_names}
            stored = jnp.zeros(cap, jnp.bool_)
            if n:
                sel = np.flatnonzero(dest == s)
                if len(sel):
                    dsel = jnp.asarray(sel)
                    sub = tuple(l[dsel] for l in lanes)
                    t, slots, _, _ = lookup_or_insert(
                        t, sub, jnp.ones(len(sel), jnp.bool_)
                    )
                    live = t.live.at[slots].set(True)
                    t = HashTable(t.fp1, t.fp2, t.keys, live)
                    for j, c in enumerate(self.columns):
                        values[c] = values[c].at[slots].set(
                            jnp.asarray(
                                np.asarray(value_cols[f"v{j}"])[sel].astype(
                                    self.dtypes[c]
                                )
                            )
                        )
                    for c in vn_names:
                        lane = value_cols.get(f"n_{c}")
                        if lane is not None:
                            vnulls[c] = vnulls[c].at[slots].set(
                                jnp.asarray(
                                    np.asarray(lane)[sel].astype(bool)
                                )
                            )
                    stored = stored.at[slots].set(True)
            tables.append(t)
            states.append(
                MvDeviceState(
                    values,
                    vnulls,
                    jnp.zeros(cap, jnp.bool_),
                    stored,
                    jnp.zeros((), jnp.bool_),
                )
            )
        sharding = NamedSharding(self.mesh, P(self.axis))
        stack = lambda *xs: jnp.stack(xs)
        self.table = jax.device_put(jax.tree.map(stack, *tables), sharding)
        self.state = jax.device_put(jax.tree.map(stack, *states), sharding)
        self.capacity = cap
        self._steps = {}  # capacity may have changed: recompile


# -- mesh observability surface (meshprof / scale / memory governor) ------
def _sharded_mv_shard_occupancy(self):
    """Per-shard claimed pk-slot counts (autoscale + skew input). One
    packed device read."""
    return np.asarray(
        jnp.sum((self.table.fp1 != jnp.uint32(0)).astype(jnp.int32), axis=1)
    )


ShardedMaterialize.shard_occupancy = _sharded_mv_shard_occupancy
ShardedMaterialize.state_nbytes_per_shard = stacked_state_nbytes_per_shard
