"""Mesh-parallel retractable GroupTopN.

Reference role: N parallel GroupTopN actors each owning the groups
whose vnode lands on them (src/stream/src/executor/top_n/group_top_n.rs
distributed by HashDataDispatcher). Groups are DISJOINT across shards
(the exchange routes by the group columns), so each shard's per-group
top-k is globally exact and the barrier emissions concatenate.

Structure mirrors ShardedDedup: stacked per-shard state, ``apply`` is
one shard_map program (vnode exchange + the single-chip
``_upsert_step_ed`` kernel); the barrier runs the pure ranking kernel
per shard and the SHARED host diff (``_diff_touched_groups``) against
per-shard emitted mirrors — host traffic stays O(changed groups x k)
per shard. Checkpoints use the single-chip lane naming (k{i} + r_*),
keys globally unique across shards, so either executor restores the
other's checkpoint (cross-layout recovery)."""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor
from risingwave_tpu.executors.top_n_plain import (
    _order_key_u64,
    _upsert_step_ed,
)
from risingwave_tpu.ops.hash_table import (
    HashTable,
    lookup_or_insert,
    set_live,
)
from risingwave_tpu.parallel.exchange import dest_shard, exchange_chunk
from risingwave_tpu.parallel.sharded_join import (
    double_bucket_cap,
    stack_for_mesh,
    stacked_state_nbytes_per_shard,
    track_bucket_cap,
)
from risingwave_tpu.array.lattice import emission_bucket
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
    emitted; updates ``emitted`` in place (one shard = one call over
    its slices). The single-chip executor diffs on the device instead
    (executors/top_n_plain._rank_diff)."""
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


class ShardedGroupTopN(Executor, Checkpointable):
    """GROUP BY g ORDER BY o LIMIT k over a device mesh."""

    def __init__(
        self,
        mesh,
        group_by: Sequence[str],
        order_col: str,
        limit: int,
        pk: Sequence[str],
        schema_dtypes: Dict[str, object],
        desc: bool = False,
        capacity: int = 1 << 12,
        bucket_cap: Optional[int] = None,
        table_id: str = "sharded_group_top_n",
    ):
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.n_shards = mesh.devices.size
        self.group_by = tuple(group_by)
        self.order_col = order_col
        self.limit = int(limit)
        self.desc = desc
        self.pk = tuple(pk)
        self.store_keys = self.group_by + tuple(
            c for c in self.pk if c not in self.group_by
        )
        self.names = tuple(sorted(schema_dtypes))
        self._dtypes = {n: jnp.dtype(schema_dtypes[n]) for n in self.names}
        self.bucket_cap = bucket_cap
        self.table_id = table_id
        table1 = HashTable.create(
            capacity, tuple(self._dtypes[c] for c in self.store_keys)
        )
        self.table = stack_for_mesh(table1, mesh, self.axis)
        z = jnp.zeros(capacity, jnp.bool_)
        self.rows = stack_for_mesh(
            {n: jnp.zeros(capacity, self._dtypes[n]) for n in self.names},
            mesh,
            self.axis,
        )
        self.sdirty = stack_for_mesh(z, mesh, self.axis)
        self.stored = stack_for_mesh(z, mesh, self.axis)
        self.epoch_dirty = stack_for_mesh(z, mesh, self.axis)
        self.dropped = stack_for_mesh(jnp.zeros((), jnp.bool_), mesh, self.axis)
        self._step = None
        self._built_bucket_cap: Optional[int] = None
        self.ex_counts_last = None  # (n, n) routed-row histogram, device
        # per-shard host mirrors of what was emitted
        self._emitted: List[Dict[Tuple, Dict[Tuple, Tuple]]] = [
            {} for _ in range(self.n_shards)
        ]

    # -- the sharded step -------------------------------------------------
    def _build_step(self, chunk_cap: int):
        n, axis = self.n_shards, self.axis
        bucket_cap = self.bucket_cap or max(64, (2 * chunk_cap) // n)
        track_bucket_cap(self, bucket_cap)
        group_by, store_keys, names = (
            self.group_by,
            self.store_keys,
            self.names,
        )

        def local(table, rows, sdirty, edirty, dropped, chunk):
            table, rows, sdirty, edirty, dropped, chunk = jax.tree.map(
                lambda a: a[0],
                (table, rows, sdirty, edirty, dropped, chunk),
            )
            lanes = tuple(chunk.col(g) for g in group_by)
            rchunk, ex_ovf, ex_counts = exchange_chunk(
                chunk, lanes, n, bucket_cap, axis
            )
            table, rows, sdirty, edirty, dr = _upsert_step_ed(
                table, rows, sdirty, edirty, rchunk, store_keys, names
            )
            dropped = dropped | dr | ex_ovf
            ex = lambda t: jax.tree.map(lambda a: a[None], t)
            return (
                ex(table), ex(rows), ex(sdirty), ex(edirty), ex(dropped),
                ex_counts[None],
            )

        spec = P(self.axis)
        return jax.jit(
            jax.shard_map(
                local,
                mesh=self.mesh,
                in_specs=(spec,) * 6,
                out_specs=(spec,) * 6,
                check_vma=False,
            ),
            donate_argnums=(0, 1, 2, 3, 4),
        )

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        if self._step is None:
            self._step = self._build_step(chunk.valid.shape[-1])
        (
            self.table,
            self.rows,
            self.sdirty,
            self.epoch_dirty,
            self.dropped,
            self.ex_counts_last,
        ) = self._step(
            self.table,
            self.rows,
            self.sdirty,
            self.epoch_dirty,
            self.dropped,
            chunk,
        )
        return []

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        # ONE packed device->host read per barrier (device
        # round-trips dominate; the single-chip executor packs the
        # same way): latch + per-shard dirty vector together
        packed = np.asarray(
            jnp.concatenate(
                [
                    jnp.any(self.dropped)[None],
                    jnp.any(self.epoch_dirty, axis=-1),
                ]
            )
        )
        if bool(packed[0]):
            raise RuntimeError(
                "sharded GroupTopN overflowed (probe or exchange bucket)"
            )
        shard_dirty = packed[1:]
        if not shard_dirty.any():
            return []
        dels: list = []
        ins: list = []
        for s in range(self.n_shards):
            if not shard_dirty[s]:
                continue
            table_s = jax.tree.map(lambda a: a[s], self.table)
            rows_s = {n: a[s] for n, a in self.rows.items()}
            edirty_s = self.epoch_dirty[s]
            in_topk, gdirty = _group_topk_mask(
                table_s,
                rows_s,
                edirty_s,
                self.limit,
                self.desc,
                self.group_by,
                self.order_col,
            )
            d, i = _diff_touched_groups(
                table_s, rows_s, in_topk, edirty_s, self.group_by,
                self.pk, self.names, gdirty, self._emitted[s],
            )
            dels.extend(d)
            ins.extend(i)
        self.epoch_dirty = stack_for_mesh(
            jnp.zeros(self.epoch_dirty.shape[-1], jnp.bool_),
            self.mesh,
            self.axis,
        )
        return _emit_diffs(dels, ins, self.names, self._dtypes)

    # -- static contracts (analysis/) -------------------------------------
    def lint_info(self):
        cols = self.names
        return {
            "expects": {c: self._dtypes[c] for c in cols},
            "emits": {c: self._dtypes.get(c) for c in cols},
            "renames": {c: c for c in cols},
            "keys": self.group_by,
            "table_ids": (self.table_id,),
            "window_key": None,
        }

    def trace_contract(self):
        return {
            "kind": "host",
            "host_reason": "mesh-resident sharded step: per-fragment "
            "SPMD fusion is tracked by the mesh analyzer (RW-E9xx), "
            "not the single-chip fuser",
            "state": (self.table, self.rows),
            "donate": True,
            # the barrier diff emits exactly the touched top-k rows —
            # a host-built, count-dependent chunk
            "emission": "data_dependent",
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
                        abstract_tree(self.rows),
                        abstract_tree(self.sdirty),
                        abstract_tree(self.epoch_dirty),
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
                "rows": "sharded",
                "sdirty": "sharded",
                "epoch_dirty": "sharded",
                "dropped": "sharded",
            },
            "updates": ("table", "rows", "sdirty", "epoch_dirty", "dropped"),
            "dispatch": {
                "fn": "dest_shard",
                "keys": self.group_by,
                "vnode_axis": self.axis,
            },
            "exchange": "all_to_all",
            "donate": True,
            "order_insensitive": True,  # top-k membership is an
            # order-statistic of the stored set, not of arrival order
            "trace_steps": trace_steps,
            # the barrier walk pulls each dirty shard's slice to host
            # and diffs against the _emitted mirrors — the E901/E907
            # scan targets
            "barrier_methods": ("on_barrier", "shard_occupancy"),
            "emission": "host",
        }

    # -- capacity escape ---------------------------------------------------
    def capacity_overflow_latched(self) -> bool:
        return bool(jnp.any(self.dropped))

    def grow_for_replay(self) -> None:
        cap = 2 * self.table.keys[0].shape[-1]
        double_bucket_cap(self)
        table1 = HashTable.create(
            cap, tuple(self._dtypes[c] for c in self.store_keys)
        )
        self.table = stack_for_mesh(table1, self.mesh, self.axis)
        z = jnp.zeros(cap, jnp.bool_)
        self.rows = stack_for_mesh(
            {n: jnp.zeros(cap, self._dtypes[n]) for n in self.names},
            self.mesh,
            self.axis,
        )
        self.sdirty = stack_for_mesh(z, self.mesh, self.axis)
        self.stored = stack_for_mesh(z, self.mesh, self.axis)
        self.epoch_dirty = stack_for_mesh(z, self.mesh, self.axis)
        self.dropped = stack_for_mesh(
            jnp.zeros((), jnp.bool_), self.mesh, self.axis
        )
        self._emitted = [{} for _ in range(self.n_shards)]
        self._step = None

    # -- integrity --------------------------------------------------------
    def state_digest(self) -> int:
        """Shard-flattened row-store fold (single-chip lane naming)."""
        from risingwave_tpu.integrity import host_digest

        def flat(a):
            a = np.asarray(a)
            return a.reshape((-1,) + a.shape[2:])

        lanes = {f"k{i}": flat(k) for i, k in enumerate(self.table.keys)}
        for n in self.names:
            lanes[f"r_{n}"] = flat(self.rows[n])
        return host_digest(lanes, flat(self.table.live))

    # -- checkpoint/restore (single-chip lane naming) ---------------------
    def checkpoint_delta(self) -> List[StateDelta]:
        marks = classify_marks(self.sdirty, self.table.live, self.stored)
        self.sdirty, self.stored = marks.sdirty, marks.stored
        if not len(marks):
            return []
        flat = lambda a: a.reshape((-1,) + a.shape[2:])
        lanes = {f"k{i}": flat(l) for i, l in enumerate(self.table.keys)}
        key_names = tuple(lanes)
        for n in self.names:
            lanes[f"r_{n}"] = flat(self.rows[n])
        pulled = pull_rows(lanes, marks)
        keys = {k: pulled[k] for k in key_names}
        vals = {k: v for k, v in pulled.items() if k not in key_names}
        return [
            StateDelta(self.table_id, keys, vals, marks.tombstone, key_names)
        ]

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        """Re-partition recovered rows by GROUP-column vnode and
        rebuild every shard; emitted mirrors rebuild from the restored
        top-k at the next barrier touch (rows restore epoch-clean)."""
        from jax.sharding import NamedSharding

        n_rows = len(next(iter(key_cols.values()))) if key_cols else 0
        key_dtypes = tuple(self._dtypes[c] for c in self.store_keys)
        cap = self.table.keys[0].shape[-1]
        glanes = dest = None
        if n_rows:
            lanes = tuple(
                jnp.asarray(np.asarray(key_cols[f"k{i}"], dtype=d))
                for i, d in enumerate(key_dtypes)
            )
            glanes = lanes[: len(self.group_by)]
            dest = np.asarray(dest_shard(glanes, self.n_shards))
            cap = grow_pow2(
                int(np.bincount(dest, minlength=self.n_shards).max()),
                cap,
                GROW_AT,
            )
        tables, rowstacks, stores = [], [], []
        for s in range(self.n_shards):
            t = HashTable.create(cap, key_dtypes)
            rws = {n: jnp.zeros(cap, self._dtypes[n]) for n in self.names}
            stored = jnp.zeros(cap, jnp.bool_)
            if n_rows:
                sel = np.flatnonzero(dest == s)
                if len(sel):
                    dsel = jnp.asarray(sel)
                    sub = tuple(
                        jnp.asarray(np.asarray(key_cols[f"k{i}"]))[dsel]
                        .astype(d)
                        for i, d in enumerate(key_dtypes)
                    )
                    t, slots, _, _ = lookup_or_insert(
                        t, sub, jnp.ones(len(sel), jnp.bool_)
                    )
                    t = set_live(t, slots, True)
                    stored = stored.at[slots].set(True)
                    for n in self.names:
                        rws[n] = rws[n].at[slots].set(
                            jnp.asarray(
                                np.asarray(value_cols[f"r_{n}"])[sel]
                            ).astype(self._dtypes[n])
                        )
            tables.append(t)
            rowstacks.append(rws)
            stores.append(stored)
        sharding = NamedSharding(self.mesh, P(self.axis))
        stack = lambda *xs: jnp.stack(xs)
        self.table = jax.device_put(
            jax.tree.map(stack, *tables), sharding
        )
        self.rows = jax.device_put(
            jax.tree.map(stack, *rowstacks), sharding
        )
        self.stored = jax.device_put(jnp.stack(stores), sharding)
        z = jnp.zeros(cap, jnp.bool_)
        self.sdirty = stack_for_mesh(z, self.mesh, self.axis)
        self.epoch_dirty = stack_for_mesh(z, self.mesh, self.axis)
        self.dropped = stack_for_mesh(
            jnp.zeros((), jnp.bool_), self.mesh, self.axis
        )
        # restored rows were DURABLE (emitted before the checkpoint):
        # rebuild the mirrors to every group's current top-k (the
        # downstream MV restored to exactly this view) so post-recovery
        # diffs don't re-emit the standing rows — the single-chip
        # restore's pattern, per shard
        self._emitted = [{} for _ in range(self.n_shards)]
        for s in range(self.n_shards):
            table_s = jax.tree.map(lambda a: a[s], self.table)
            rows_s = {n: a[s] for n, a in self.rows.items()}
            if not bool(jnp.any(table_s.live)):
                continue
            in_topk, _ = _group_topk_mask(
                table_s,
                rows_s,
                jnp.ones(cap, jnp.bool_),
                self.limit,
                self.desc,
                self.group_by,
                self.order_col,
            )
            sel = np.flatnonzero(np.asarray(in_topk))
            pulled = pull_rows({n: rows_s[n] for n in self.names}, sel)
            mirror = self._emitted[s]
            for i in range(len(sel)):
                g = tuple(pulled[c][i].item() for c in self.group_by)
                pkv = tuple(pulled[c][i].item() for c in self.pk)
                mirror.setdefault(g, {})[pkv] = tuple(
                    pulled[n][i].item() for n in self.names
                )
        self._step = None


# -- mesh observability surface (meshprof / scale / memory governor) ------
def _sharded_top_n_state_nbytes(self) -> int:
    return int(
        sum(
            leaf.nbytes
            for leaf in jax.tree.leaves(
                (
                    self.table,
                    self.rows,
                    self.sdirty,
                    self.stored,
                    self.epoch_dirty,
                )
            )
        )
    )


def _sharded_top_n_shard_occupancy(self):
    """Per-shard claimed group-slot counts (autoscale + skew input).
    One packed device read."""
    return np.asarray(
        jnp.sum((self.table.fp1 != jnp.uint32(0)).astype(jnp.int32), axis=1)
    )


ShardedGroupTopN.state_nbytes = _sharded_top_n_state_nbytes
ShardedGroupTopN.state_nbytes_per_shard = stacked_state_nbytes_per_shard
ShardedGroupTopN.shard_occupancy = _sharded_top_n_shard_occupancy
