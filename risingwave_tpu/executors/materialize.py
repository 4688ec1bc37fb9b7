"""Materialize executor — applies the change stream to a queryable MV.

Reference: src/stream/src/executor/mview/materialize.rs:44 — applies
chunks to the MV StateTable with pk-conflict handling (:192-230).

Two host backends behind one API (the reference's row map is native
Rust; ours is native C++ where the layout allows):
- NATIVE (risingwave_tpu/native.py): all pk/value columns are
  NULL-free integers -> a C++ unordered_map applies each delta batch
  at ~ns/row, and checkpoint staging is pure numpy net-effect over the
  buffered batches (no per-row Python at all). Integer lanes widen to
  int64 in the map (dictionary codes included), which is lossless.
- PYTHON dict fallback: any other layout (floats, NULLs) — identical
  semantics, interpreter speed.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Executor
from risingwave_tpu.storage.state_table import Checkpointable, StateDelta
from risingwave_tpu.trace import span
from risingwave_tpu.types import Op


def _last_per_key(keys: np.ndarray) -> np.ndarray:
    """Indices of the LAST occurrence of each distinct key row (stable
    sort on key columns, keep run ends)."""
    if keys.shape[1] == 0:
        # pk = (): a single-row table; the last op wins outright
        return np.asarray([len(keys) - 1]) if len(keys) else np.zeros(0, np.int64)
    order = np.lexsort(
        tuple(keys[:, j] for j in reversed(range(keys.shape[1])))
    )
    ks = keys[order]
    is_last = np.ones(len(order), bool)
    if len(order) > 1:
        same = (ks[1:] == ks[:-1]).all(axis=1)
        is_last[:-1] = ~same
    return order[is_last]


class MaterializeExecutor(Executor, Checkpointable):
    def __init__(
        self,
        pk: Sequence[str],
        columns: Sequence[str],
        table_id: str = "mview",
        conflict_resolve: bool = False,
    ):
        self.pk = tuple(pk)
        self.columns = tuple(columns)
        self.rows: Dict[Tuple, Tuple] = {}
        self.table_id = table_id
        # ConflictBehavior::Overwrite with DOWNSTREAM-CORRECT emission
        # (materialize.rs:192-230): an insert on an existing pk emits
        # UpdateDelete(stored) + UpdateInsert(new); a delete emits the
        # STORED row; a delete of an absent pk is dropped. User-pk
        # tables set this so MVs over them see real retractions.
        self.conflict_resolve = bool(conflict_resolve)
        self._changed: set = set()  # python path: pks since checkpoint
        self._dtypes: Dict[str, np.dtype] = {}
        self._native = None  # NativeMvMap once eligible
        self._backend: Optional[str] = None
        self._pending: List[Tuple[np.ndarray, np.ndarray, np.ndarray]] = []
        # set by StreamingRuntime.register when a checkpoint store will
        # drain _pending every checkpoint barrier
        self.checkpoint_enabled = False

    def lint_info(self):
        return {
            "requires": tuple(self.columns),
            "state_pk": tuple(
                c for c in self.pk if c != "_row_id"
            ),  # _row_id is generated upstream by RowIdGen
            "table_ids": (self.table_id,),
        }

    def state_nbytes(self) -> int:
        """Memory-ledger contract: a host-map MV holds NO device
        bytes — only the host row store (estimated at 8B per pk/value
        cell so the ledger can still rank it)."""
        width = len(self.pk) + len(self.columns)
        n = len(self._native) if self._native is not None else len(self.rows)
        return int(n) * width * 8

    def trace_contract(self):
        return {
            "kind": "host",
            "trace_step": None,
            "state": None,
            "donate": False,
            "emission": "passthrough",
            "host_reason": "host-map materializer: python dict row "
            "store pulls every chunk to host (device-resident MVs use "
            "DeviceMaterializeExecutor)",
        }

    # -- backend selection ----------------------------------------------
    _force_python = False  # subclasses needing row hooks pin the dict

    def _pick_backend(self, chunk: StreamChunk, data) -> None:
        if self._force_python or self.conflict_resolve:
            # conflict resolution reads stored rows per key — the
            # python dict is the value store
            self._backend = "python"
            return
        names = self.pk + self.columns
        eligible = all(
            np.issubdtype(data[name].dtype, np.integer)
            and name not in chunk.nulls
            for name in names
        )
        if eligible:
            try:
                from risingwave_tpu.native import NativeMvMap

                self._native = NativeMvMap(len(self.pk), len(self.columns))
                self._backend = "native"
                return
            except (RuntimeError, OSError):
                pass
        self._backend = "python"

    # -- data ------------------------------------------------------------
    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        # host-map MV: the chunk comes to the host (waiting for the
        # step that made it), then the map applies it row by row
        with span(
            "mv.apply", stage="actor.mv_apply", table_id=self.table_id
        ) as sp:
            return self._apply(chunk, sp)

    # one host apply a chunk at the chunk's own width: takes the push
    # lattice (a table fragment copies a quarter of the lanes)
    per_chunk_step = True

    def warm(self, chunk: StreamChunk) -> List[StreamChunk]:
        """Executor.warm: the chunk's copy to the host; with no valid
        row ``_apply`` returns before it touches the map."""
        return self._apply(chunk)

    def _apply(self, chunk: StreamChunk, sp=None) -> List[StreamChunk]:
        with span("mv.to_numpy"):
            data = chunk.to_numpy(with_ops=True)
        ops = data["__op__"]
        n = len(ops)
        if sp is not None:
            sp.args["rows"] = n  # the chunk's valid rows
        if n == 0:
            return [chunk]
        for name in self.pk + self.columns:
            if name not in self._dtypes:
                self._dtypes[name] = data[name].dtype
        if self._backend is None:
            self._pick_backend(chunk, data)
        if self._backend == "native" and any(
            nm in chunk.nulls for nm in self.pk + self.columns
        ):
            # the int matrix cannot represent NULL cells (a later
            # UPDATE ... SET c = NULL on an all-int table): migrate to
            # the python dict, folding un-drained pending deltas into
            # the changed-key set so checkpointing stays exact
            self._demote_to_python()
        is_del = (ops == Op.DELETE) | (ops == Op.UPDATE_DELETE)
        if self._backend == "native":
            keys = (
                np.stack([data[nm] for nm in self.pk], axis=1).astype(np.int64)
                if self.pk
                else np.zeros((n, 0), np.int64)
            )
            vals = (
                np.stack([data[nm] for nm in self.columns], axis=1).astype(
                    np.int64
                )
                if self.columns
                else np.zeros((n, 0), np.int64)
            )
            self._native.apply(keys, vals, is_del)
            self._pending.append((keys, vals, is_del.astype(np.uint8)))
            return [chunk]
        if self.conflict_resolve:
            return self._apply_resolve(data, ops, n)
        self._apply_python(data, ops, is_del, n)
        return [chunk]

    def _demote_to_python(self) -> None:
        keys, vals = self._native.dump()
        self.rows = {
            tuple(k): tuple(v) for k, v in zip(keys.tolist(), vals.tolist())
        }
        for pk_arr, _, _ in self._pending:
            for kt in map(tuple, pk_arr.tolist()):
                self._changed.add(kt)
        self._pending = []
        self._native = None
        self._backend = "python"

    def _apply_resolve(self, data, ops, n) -> List[StreamChunk]:
        """Row-ordered conflict resolution against the stored map; the
        returned chunk is what downstream operators must see to stay
        consistent with this table (retractions included)."""
        names = self.pk + self.columns
        cols_l = self._null_folded(data, names)
        out_rows: List[Tuple[int, Tuple, Tuple]] = []
        for i in range(n):
            k = tuple(cols_l[nm][i] for nm in self.pk)
            self._changed.add(k)
            if ops[i] in (Op.INSERT, Op.UPDATE_INSERT):
                v = tuple(cols_l[nm][i] for nm in self.columns)
                old = self.rows.get(k)
                if old is not None:
                    out_rows.append((int(Op.UPDATE_DELETE), k, old))
                    out_rows.append((int(Op.UPDATE_INSERT), k, v))
                else:
                    op = (
                        int(Op.UPDATE_INSERT)
                        if ops[i] == Op.UPDATE_INSERT
                        else int(Op.INSERT)
                    )
                    out_rows.append((op, k, v))
                self.rows[k] = v
            else:
                old = self.rows.pop(k, None)
                if old is None:
                    continue  # delete of an absent pk: dropped
                op = (
                    int(Op.UPDATE_DELETE)
                    if ops[i] == Op.UPDATE_DELETE
                    else int(Op.DELETE)
                )
                out_rows.append((op, k, old))
        if not out_rows:
            return []
        m = len(out_rows)
        cap = max(2, 1 << (m - 1).bit_length())
        cols: Dict[str, np.ndarray] = {}
        nulls: Dict[str, np.ndarray] = {}
        for j, nm in enumerate(names):
            pk_n = len(self.pk)
            vals = [
                (r[1][j] if j < pk_n else r[2][j - pk_n]) for r in out_rows
            ]
            mask = np.asarray([v is None for v in vals], bool)
            dt = self._dtypes.get(nm, np.dtype(np.int64))
            cols[nm] = np.asarray(
                [0 if v is None else v for v in vals], dt
            )
            if mask.any():
                nulls[nm] = mask
        out_ops = np.asarray([r[0] for r in out_rows], np.int32)
        return [
            StreamChunk.from_numpy(
                cols, cap, ops=out_ops, nulls=nulls or None
            )
        ]

    @staticmethod
    def _null_folded(data, names):
        """{name: python list with __null-masked cells folded to None}
        — the one place the NULL-lane representation is interpreted."""
        out = {}
        for name in names:
            col = data[name].tolist()
            nl = data.get(name + "__null")
            if nl is not None:
                col = [None if isnull else v for v, isnull in zip(col, nl)]
            out[name] = col
        return out

    def _apply_python(self, data, ops, is_del, n):
        # NULL pk components fold into the key tuple as None (SQL NULL
        # group keys are distinct; reference pk serde writes a null tag
        # first, row_serde_util.rs). "Last op per pk wins" replaces the
        # per-row loop.
        def tuples(names):
            if not names:
                return [()] * n
            folded = self._null_folded(data, names)
            return list(zip(*(folded[name] for name in names)))

        keys = tuples(self.pk)
        vals = tuples(self.columns)
        self._changed.update(keys)
        last = {k: i for i, k in enumerate(keys)}
        if is_del.any():
            rows = self.rows
            keys_u = list(last.keys())
            idx = np.fromiter(last.values(), dtype=np.int64, count=len(last))
            dmask = is_del[idx]
            for j in np.flatnonzero(dmask):
                rows.pop(keys_u[j], None)  # ConflictBehavior::Overwrite
            rows.update(
                (keys_u[j], vals[idx[j]]) for j in np.flatnonzero(~dmask)
            )
        else:
            self.rows.update((k, vals[i]) for k, i in last.items())

    # -- reads ------------------------------------------------------------
    def snapshot(self) -> Dict[Tuple, Tuple]:
        if self._backend == "native":
            keys, vals = self._native.dump()
            return {
                tuple(k): tuple(v)
                for k, v in zip(keys.tolist(), vals.tolist())
            }
        return dict(self.rows)

    def to_numpy(self) -> Dict[str, np.ndarray]:
        """Snapshot as column arrays (pk cols + value cols)."""
        if self._backend == "native":
            keys, vals = self._native.dump()
            out = {}
            for j, name in enumerate(self.pk):
                out[name] = keys[:, j]
            for j, name in enumerate(self.columns):
                out[name] = vals[:, j]
            return out
        keys = list(self.rows)
        out = {}
        for j, name in enumerate(self.pk):
            out[name] = np.array([k[j] for k in keys])
        for j, name in enumerate(self.columns):
            out[name] = np.array([self.rows[k][j] for k in keys])
        return out

    # -- barrier ---------------------------------------------------------
    def on_barrier(self, barrier) -> List[StreamChunk]:
        """Compact the native path's pending delta buffer to its net
        effect per pk (last op wins). Keeps memory bounded by distinct
        keys touched since the last checkpoint instead of total stream
        length — pipelines driven without a CheckpointManager (bench,
        store=None runtimes) never drain _pending otherwise (ADVICE r2
        medium). Runtime-managed executors skip this: checkpoint
        staging drains _pending with the same net-effect pass, so
        compacting here would sort the same rows twice per barrier."""
        if not self.checkpoint_enabled and len(self._pending) > 1:
            self._pending = [self._net_pending()]
        return []

    def _net_pending(self):
        """Fold _pending batches into one (keys, vals, dels) net batch."""
        keys = np.concatenate([k for k, _, _ in self._pending])
        vals = np.concatenate([v for _, v, _ in self._pending])
        dels = np.concatenate([d for _, _, d in self._pending])
        sel = _last_per_key(keys)
        return keys[sel], vals[sel], dels[sel]

    # -- checkpoint/restore ----------------------------------------------
    def checkpoint_delta(self):
        """Persist rows whose pk changed since the last checkpoint
        (reference: the MV's own StateTable commit). Native path: pure
        numpy net-effect over the buffered delta batches — last
        occurrence per pk wins; its is_del becomes the tombstone."""
        if self._backend == "native":
            return self._native_delta()
        return self._python_delta()

    def _native_delta(self):
        if not self._pending:
            return []
        keys = np.concatenate([k for k, _, _ in self._pending])
        vals = np.concatenate([v for _, v, _ in self._pending])
        dels = np.concatenate([d for _, _, d in self._pending])
        self._pending = []
        if len(keys) == 0:
            return []
        sel = _last_per_key(keys)
        key_cols = {
            f"k{j}": keys[sel, j].astype(self._dtypes[self.pk[j]])
            for j in range(len(self.pk))
        }
        value_cols = {
            f"v{j}": vals[sel, j].astype(self._dtypes[self.columns[j]])
            for j in range(len(self.columns))
        }
        return [
            StateDelta(
                self.table_id,
                key_cols,
                value_cols,
                dels[sel].astype(bool),
                tuple(f"k{j}" for j in range(len(self.pk))),
            )
        ]

    def _python_delta(self):
        if not self._changed:
            return []
        ups, tombs = [], []
        for k in self._changed:
            if any(v is None for v in k):
                raise ValueError("NULL pk persistence not supported yet")
            row = self.rows.get(k)
            if row is None:
                tombs.append(k)
            else:
                ups.append((k, row))
        n = len(ups) + len(tombs)
        key_cols = {}
        for j, name in enumerate(self.pk):
            key_cols[f"k{j}"] = np.array(
                [k[j] for k, _ in ups] + [k[j] for k in tombs],
                dtype=self._dtypes[name],
            )
        value_cols = {}
        for j, name in enumerate(self.columns):
            pad = np.zeros(len(tombs), dtype=self._dtypes[name])
            vals = [r[j] for _, r in ups]
            value_cols[f"v{j}"] = np.concatenate(
                [
                    np.array(
                        [0 if v is None else v for v in vals],
                        dtype=self._dtypes[name],
                    ),
                    pad,
                ]
            ) if ups else pad
            # NULL cells persist as a bool companion lane (restore
            # reads it back). Emitted UNCONDITIONALLY: SST merges for
            # one table_id need every delta to carry the same lane set
            value_cols[f"vn{j}"] = np.array(
                [v is None for v in vals] + [False] * len(tombs), bool
            )
        tombstone = np.zeros(n, bool)
        tombstone[len(ups):] = True
        self._changed.clear()
        return [
            StateDelta(
                self.table_id,
                key_cols,
                value_cols,
                tombstone,
                tuple(f"k{j}" for j in range(len(self.pk))),
            )
        ]

    def state_digest(self) -> int:
        """Durable logical state = the row map (backend-independent:
        native and python snapshots digest identically)."""
        from risingwave_tpu.integrity import host_obj_digest

        return host_obj_digest(
            sorted(self.snapshot().items(), key=repr)
        )

    def restore_state(self, table_id, key_cols, value_cols):
        self.rows = {}
        self._changed = set()
        self._pending = []
        self._native = None
        self._backend = None
        if not key_cols:
            return
        n = len(next(iter(key_cols.values())))
        ints = (
            not self._force_python
            and not self.conflict_resolve  # resolve reads the dict
            and all(
                np.issubdtype(np.asarray(a).dtype, np.integer)
                for a in list(key_cols.values()) + list(value_cols.values())
            )  # vn{j} NULL companions are bool -> python path
        )
        if ints:
            try:
                from risingwave_tpu.native import NativeMvMap

                self._native = NativeMvMap(len(self.pk), len(self.columns))
                self._backend = "native"
                keys = (
                    np.stack(
                        [key_cols[f"k{j}"] for j in range(len(self.pk))], axis=1
                    ).astype(np.int64)
                    if self.pk
                    else np.zeros((n, 0), np.int64)
                )
                vals = (
                    np.stack(
                        [value_cols[f"v{j}"] for j in range(len(self.columns))],
                        axis=1,
                    ).astype(np.int64)
                    if self.columns
                    else np.zeros((n, 0), np.int64)
                )
                for j in range(len(self.pk)):
                    self._dtypes.setdefault(
                        self.pk[j], np.asarray(key_cols[f"k{j}"]).dtype
                    )
                for j in range(len(self.columns)):
                    self._dtypes.setdefault(
                        self.columns[j], np.asarray(value_cols[f"v{j}"]).dtype
                    )
                self._native.apply(keys, vals, np.zeros(n, np.uint8))
                return
            except (RuntimeError, OSError):
                self._backend = None
        self._backend = "python"
        nls = [
            value_cols.get(f"vn{j}") for j in range(len(self.columns))
        ]
        for i in range(n):
            k = tuple(
                key_cols[f"k{j}"][i].item() for j in range(len(self.pk))
            )
            v = tuple(
                None
                if nls[j] is not None and bool(nls[j][i])
                else value_cols[f"v{j}"][i].item()
                for j in range(len(self.columns))
            )
            self.rows[k] = v


# ---------------------------------------------------------------------------
# Device-resident MV (the TPU-first materialize)
# ---------------------------------------------------------------------------

import jax
import jax.numpy as jnp
from dataclasses import dataclass
from functools import partial

from risingwave_tpu.ops.hash_table import HashTable, last_occurrence_mask, lookup_or_insert, stage_scalars
from risingwave_tpu.ops.bucketing import BucketAllocator, BucketPolicy
from risingwave_tpu.storage.state_table import (
    classify_marks,
    grow_pow2,
    pull_rows,
)

GROW_AT = 0.5
# mid-epoch rebuild only when the HOST insert bound nears the table
# itself (MAX_PROBE overflow risk); ordinary growth resolves at the
# barrier from the true occupancy note (see HashAgg's twin constant)
HARD_GROW_AT = 0.75


@jax.tree_util.register_pytree_node_class
@dataclass
class MvDeviceState:
    """Value lanes + checkpoint marks, slot-indexed next to the pk table."""

    values: dict  # name -> (capacity,) lane
    vnulls: dict  # name -> (capacity,) bool lane (SQL NULL)
    sdirty: jnp.ndarray  # touched since last checkpoint stage
    stored: jnp.ndarray  # durable in the state store
    dropped: jnp.ndarray  # bool scalar: overflow latch

    def tree_flatten(self):
        vn = tuple(sorted(self.values))
        nn = tuple(sorted(self.vnulls))
        children = (
            tuple(self.values[k] for k in vn)
            + tuple(self.vnulls[k] for k in nn)
            + (self.sdirty, self.stored, self.dropped)
        )
        return children, (vn, nn)

    @classmethod
    def tree_unflatten(cls, aux, children):
        vn, nn = aux
        values = dict(zip(vn, children[: len(vn)]))
        vnulls = dict(zip(nn, children[len(vn) : len(vn) + len(nn)]))
        sdirty, stored, dropped = children[-3:]
        return cls(values, vnulls, sdirty, stored, dropped)


def mv_step_fn(table, state, chunk, pk, cols):
    """One chunk applied to the device MV: find-or-insert pk, last row
    per pk wins (Overwrite conflict behavior), deletes flip live off.
    Entirely on device — zero host syncs (the device-resident contract).
    Un-jitted so sharded wrappers can call it inside shard_map
    (parallel/sharded_mv.py); the single-chip executor uses the jitted
    ``_mv_step`` below."""
    keys = tuple(chunk.col(k) for k in pk)
    table, slots, found, inserted = lookup_or_insert(table, keys, chunk.valid)
    dropped = state.dropped | jnp.any(chunk.valid & (slots < 0))
    last = last_occurrence_mask(slots, chunk.valid)
    is_del = (chunk.ops == 1) | (chunk.ops == 2)  # DELETE | UPDATE_DELETE
    cap = table.capacity
    lidx = jnp.where(last, slots, cap)
    live = table.live.at[lidx].set(~is_del, mode="drop")
    table = HashTable(table.fp1, table.fp2, table.keys, live)
    uidx = jnp.where(last & ~is_del, slots, cap)
    values = {
        c: state.values[c].at[uidx].set(
            chunk.col(c).astype(state.values[c].dtype), mode="drop"
        )
        for c in cols
    }
    vnulls = {
        c: state.vnulls[c].at[uidx].set(chunk.null_of(c), mode="drop")
        for c in state.vnulls
    }
    sdirty = state.sdirty.at[lidx].set(True, mode="drop")
    return table, MvDeviceState(values, vnulls, sdirty, state.stored, dropped)


_mv_step = partial(jax.jit, static_argnames=("pk", "cols"), donate_argnums=(0, 1))(
    mv_step_fn
)


@partial(jax.jit, static_argnames=("new_cap",), donate_argnums=())
def _mv_rebuild(table, state, new_cap):
    """Re-insert surviving slots into a fresh table (host-decided
    capacity; the TPU analogue of growing the MV cache)."""
    keep = table.live | state.sdirty | state.stored
    new_table = HashTable.create(new_cap, tuple(k.dtype for k in table.keys))
    new_table, slots, _, _ = lookup_or_insert(new_table, table.keys, keep)
    idx = jnp.where(keep, slots, new_cap)
    live = new_table.live.at[idx].set(table.live, mode="drop")
    new_table = HashTable(new_table.fp1, new_table.fp2, new_table.keys, live)
    put = lambda a: jnp.zeros(new_cap, a.dtype).at[idx].set(a, mode="drop")
    values = {c: put(state.values[c]) for c in state.values}
    vnulls = {c: put(state.vnulls[c]) for c in state.vnulls}
    sdirty = jnp.zeros(new_cap, jnp.bool_).at[idx].set(state.sdirty, mode="drop")
    stored = jnp.zeros(new_cap, jnp.bool_).at[idx].set(state.stored, mode="drop")
    return new_table, MvDeviceState(
        values, vnulls, sdirty, stored, jnp.zeros((), jnp.bool_)
    )


class MvDeviceReadMixin:
    """Read surface over a ``_host_rows()`` provider — shared by the
    single-chip device MV and the mesh-sharded one
    (parallel/sharded_mv.py) so the k{j}/v{j}/n_{c} lane naming and
    NULL decoding live in exactly one place."""

    def snapshot(self):
        """pk tuple -> value tuple (NULL -> None), matching the host-map
        executors' interface. One bulk transfer, on demand."""
        _, rows = self._host_rows()
        n = len(rows["k0"]) if self.pk else 0
        out = {}
        for i in range(n):
            k = tuple(rows[f"k{j}"][i].item() for j in range(len(self.pk)))
            v = tuple(
                None
                if (f"n_{c}" in rows and rows[f"n_{c}"][i])
                else rows[f"v{j}"][i].item()
                for j, c in enumerate(self.columns)
            )
            out[k] = v
        return out

    def to_numpy(self):
        _, rows = self._host_rows()
        out = {}
        for j, name in enumerate(self.pk):
            out[name] = rows[f"k{j}"]
        for j, name in enumerate(self.columns):
            out[name] = rows[f"v{j}"]
            if f"n_{name}" in rows:
                out[name + "__null"] = rows[f"n_{name}"]
        return out


class DeviceMaterializeExecutor(MvDeviceReadMixin, Executor, Checkpointable):
    """Device-resident MV: pk-keyed hash table + value lanes in HBM.

    Reference: src/stream/src/executor/mview/materialize.rs:44 with
    ConflictBehavior::Overwrite (:192-230). The host-map backends above
    pull every chunk to the host — on the TPU that is ~100ms per
    chunk; this executor applies deltas entirely on device and reaches
    the host only at snapshot/checkpoint time (the "columnar MV staged
    in HBM" north star, BASELINE.md).

    Schema constraint: pk and value lanes must be fixed-width device
    dtypes (ints/floats/bool — varchar/jsonb ride their dictionary
    codes). NULLs in VALUE columns ride per-column null lanes; NULL pk
    components are not supported (the reference serializes a null tag;
    here use the host-map executor for nullable pks).
    """

    def __init__(
        self,
        pk,
        columns,
        schema_dtypes,
        table_id: str = "mview",
        capacity: int = 1 << 16,
        nullable=(),
    ):
        self.pk = tuple(pk)
        self.columns = tuple(columns)
        self.table_id = table_id
        self.dtypes = {n: jnp.dtype(schema_dtypes[n]) for n in pk + tuple(columns)}
        self.table = HashTable.create(
            capacity, tuple(self.dtypes[k] for k in self.pk)
        )
        self.state = MvDeviceState(
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
        self._bound = 0
        self._occ_note = 0  # true claimed at the last barrier (staged read)
        # shape-stability: capacity walks the allocator's pow2 lattice;
        # growth decisions consume the occupancy note staged at the
        # previous barrier instead of a synchronous device read
        self._buckets = BucketAllocator(
            BucketPolicy.from_capacity(capacity, grow_at=GROW_AT)
        )
        self.checkpoint_enabled = False

    def lint_info(self):
        return {
            "expects": dict(self.dtypes),
            "state_pk": tuple(self.pk),
            "table_ids": (self.table_id,),
        }

    def trace_contract(self):
        return {
            "kind": "device",
            "trace_step": lambda c: _mv_step(
                self.table, self.state, c, self.pk, self.columns
            ),
            "state": (self.table, self.state),
            "donate": True,
            "emission": "passthrough",
        }

    def padding_stats(self):
        """Wasted-lane accounting (ops/bucketing.padding_stats —
        bench/PROFILE surface; reads device occupancy)."""
        return {
            "capacity": self.table.capacity,
            "live": int(self.table.num_live()),
        }

    # -- data -------------------------------------------------------------
    def apply(self, chunk: StreamChunk):
        self._maybe_grow(chunk.capacity)  # also advances the insert bound
        self.table, self.state = _mv_step(
            self.table, self.state, chunk, self.pk, self.columns
        )
        return [chunk]

    def warm(self, chunk: StreamChunk):
        """Executor.warm: the step alone — no bound, no growth; not
        even the step where ``_maybe_grow`` would rebuild the table
        before a chunk of these lanes (it never runs at this
        capacity)."""
        cap = self.table.capacity
        if min(self._bound, cap) + chunk.capacity <= cap * HARD_GROW_AT:
            self.table, self.state = _mv_step(
                self.table, self.state, chunk, self.pk, self.columns
            )
        return [chunk]

    def _maybe_grow(self, incoming: int) -> None:
        """Capacity planning with ZERO device reads on the hot path.

        Agg/join flush chunks arrive padded (few live rows at a large
        capacity), so the host bound wildly overstates inserts
        mid-epoch. The old code paid a blocking ``read_scalars``
        round-trip to learn the truth (RW-E801 ×3 on the fusion
        worklist); now ordinary growth resolves AT THE BARRIER from
        the staged occupancy note (``_on_barrier_scalars`` plans with
        true claimed), and the only mid-epoch rebuild is the overflow
        guard: a bound nearing the table itself rebuilds
        pessimistically BEFORE the MAX_PROBE latch can trip."""
        cap = self.table.capacity
        # occupancy can never exceed the table: clamping the carried
        # bound at the capacity stops padded flush chunks (whose
        # capacities wildly overstate live rows) from accreting an
        # unbounded bound across chunks and ratcheting growth step
        # after step (code-review finding)
        claimed = min(self._bound, cap)
        self._bound = claimed + incoming
        if self._bound <= cap * HARD_GROW_AT:
            return
        # no extra margin: the 0.75 guard vs 0.5 sizing gap IS the
        # hysteresis, so the guard cannot re-trip right after a rebuild
        new_cap = self._buckets.plan(cap, incoming, claimed, claimed)
        if new_cap is not None and new_cap != cap:
            self.table, self.state = _mv_rebuild(
                self.table, self.state, new_cap
            )

    def pin_max_bucket(self):
        """ShapeGovernor hook: freeze the MV table at its high-water
        bucket (shrink disabled; regrow applied by the next apply)."""
        return {
            "table_id": self.table_id,
            "pinned_cap": self._buckets.pin(),
        }

    # -- control ----------------------------------------------------------
    def on_barrier(self, barrier) -> list:
        self._staged_scalars = stage_scalars(
            self.state.dropped, self.table.occupancy()
        )
        if barrier is None:  # direct drive: checks fire inline
            self.finish_barrier()
        return []

    def _on_barrier_scalars(self, vals) -> None:
        dropped, claimed = vals
        # occupancy refreshes the growth bound so steady state has no
        # mid-epoch refresh syncs; barrier-boundary planning from the
        # TRUE note: grow past the load factor, apply pending lazy
        # shrink, honor a governor pin — all between epochs
        epoch_inc = max(self._bound - self._occ_note, 0)
        self._occ_note = int(claimed)
        self._bound = int(claimed)
        cap = self.table.capacity
        self._buckets.note_barrier(cap, int(claimed))
        # margin: the larger of true occupancy and last epoch's insert
        # bound — a shrink can never land below what the mid-epoch
        # overflow guard would immediately regrow
        new_cap = self._buckets.plan(
            cap, 0, int(claimed), int(claimed),
            margin=max(int(claimed), epoch_inc),
        )
        if new_cap is not None and new_cap != cap:
            self.table, self.state = _mv_rebuild(
                self.table, self.state, new_cap
            )
        if dropped:
            raise RuntimeError(
                "device MV hash table overflowed MAX_PROBE; grow capacity"
            )

    def state_nbytes(self) -> int:
        return sum(
            leaf.nbytes
            for leaf in jax.tree.leaves((self.table, self.state))
        )

    # -- reads ------------------------------------------------------------
    def _host_rows(self):
        live = np.asarray(self.table.live)
        sel = np.flatnonzero(live)
        lanes = {f"k{j}": k for j, k in enumerate(self.table.keys)}
        lanes.update(
            {f"v{j}": self.state.values[c] for j, c in enumerate(self.columns)}
        )
        lanes.update(
            {f"n_{c}": lane for c, lane in self.state.vnulls.items()}
        )
        return sel, pull_rows(lanes, sel)

    # snapshot()/to_numpy() come from MvDeviceReadMixin

    # -- integrity --------------------------------------------------------
    def digest_lanes(self):
        from risingwave_tpu.integrity import mv_lanes

        return mv_lanes(self.table, self.state)

    def state_digest(self) -> int:
        """Host twin of the fused digest lane (integrity.mv_lanes)."""
        from risingwave_tpu.integrity import host_digest

        return host_digest(*self.digest_lanes())

    # -- checkpoint/restore -----------------------------------------------
    def checkpoint_delta(self):
        marks = classify_marks(
            self.state.sdirty, self.table.live, self.state.stored
        )
        # eager mark flip (same discipline as the other executors: the
        # runtime stages on the main thread before the async commit)
        self.state.sdirty, self.state.stored = marks.sdirty, marks.stored
        if not len(marks):
            return []
        lanes = {f"k{j}": k for j, k in enumerate(self.table.keys)}
        lanes.update(
            {f"v{j}": self.state.values[c] for j, c in enumerate(self.columns)}
        )
        lanes.update(
            {f"n_{c}": lane for c, lane in self.state.vnulls.items()}
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

    def restore_state(self, table_id, key_cols, value_cols):
        n = len(next(iter(key_cols.values()))) if key_cols else 0
        cap = grow_pow2(n, 1 << 10, GROW_AT)
        self.table = HashTable.create(
            cap, tuple(self.dtypes[k] for k in self.pk)
        )
        self.state = MvDeviceState(
            values={c: jnp.zeros(cap, self.dtypes[c]) for c in self.columns},
            vnulls={c: jnp.zeros(cap, jnp.bool_) for c in self.state.vnulls},
            sdirty=jnp.zeros(cap, jnp.bool_),
            stored=jnp.zeros(cap, jnp.bool_),
            dropped=jnp.zeros((), jnp.bool_),
        )
        self._bound = 0
        if n == 0:
            return
        cols = {
            name: np.asarray(key_cols[f"k{j}"]).astype(self.dtypes[name])
            for j, name in enumerate(self.pk)
        }
        nulls = {}
        for j, name in enumerate(self.columns):
            cols[name] = np.asarray(value_cols[f"v{j}"]).astype(
                self.dtypes[name]
            )
            if f"n_{name}" in value_cols:
                nulls[name] = np.asarray(value_cols[f"n_{name}"]).astype(bool)
        chunk = StreamChunk.from_numpy(cols, cap, nulls=nulls or None)
        self.table, self.state = _mv_step(
            self.table, self.state, chunk, self.pk, self.columns
        )
        # restored rows are durable, not dirty
        self.state.stored = self.state.sdirty
        self.state.sdirty = jnp.zeros_like(self.state.sdirty)
        self._bound = n
