"""Sort executor — emit-on-window-close ordered output.

Reference: src/stream/src/executor/sort.rs:20 + sort_buffer.rs — rows
buffer in a state table until the watermark passes their timestamp,
then emit in timestamp order (the EOWC building block; downstream
operators see an append-only, time-ordered stream).

TPU re-design: the buffer is a fixed-capacity slot arena in HBM.
Append is a cumsum-compacted scatter into free slots; a watermark
emits the closed prefix with ONE device argsort over (ts, seq) —
seq (arrival order) breaks ties deterministically — and frees the
slots. No per-row host work; the host sees only the overflow latch
once per barrier.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor, Watermark
from risingwave_tpu.ops.hash_table import stage_scalars
from risingwave_tpu.storage.state_table import (
    Checkpointable,
    StateDelta,
    pull_rows,
    read_marks,
)


@partial(jax.jit, static_argnames=("names",), donate_argnums=(0, 1, 2, 3))
def _sort_append(buf, bnulls, valid, seq, next_seq, chunk, names):
    """Scatter the chunk's live rows into free buffer slots."""
    cap = valid.shape[0]
    free = ~valid
    # position of each free slot among free slots; position of each
    # incoming row among incoming rows — row i claims the i-th free slot
    free_rank = jnp.cumsum(free.astype(jnp.int32)) - 1
    slot_of_rank = jnp.full(cap, cap, jnp.int32)
    slot_of_rank = slot_of_rank.at[
        jnp.where(free, free_rank, cap)
    ].set(jnp.arange(cap, dtype=jnp.int32), mode="drop")
    live = chunk.valid
    row_rank = jnp.cumsum(live.astype(jnp.int32)) - 1
    n_free = jnp.sum(free.astype(jnp.int32))
    overflow = jnp.sum(live.astype(jnp.int32)) > n_free
    dest = jnp.where(
        live & (row_rank < n_free), slot_of_rank[row_rank], cap
    )
    new_buf = {
        n: buf[n].at[dest].set(
            chunk.col(n).astype(buf[n].dtype), mode="drop"
        )
        for n in names
    }
    new_nulls = {
        n: bnulls[n].at[dest].set(chunk.null_of(n), mode="drop")
        for n in bnulls
    }
    new_valid = valid.at[dest].set(live, mode="drop")
    order = next_seq + row_rank.astype(jnp.int64)
    new_seq = seq.at[dest].set(order, mode="drop")
    next_seq = next_seq + jnp.sum(live.astype(jnp.int64))
    return new_buf, new_nulls, new_valid, new_seq, next_seq, overflow


@partial(jax.jit, static_argnames=("names", "ts_col"), donate_argnums=(2, ))
def _sort_emit(buf, bnulls, valid, seq, cutoff, names, ts_col):
    """Emit rows with ts < cutoff in (ts, seq) order; free their slots."""
    cap = valid.shape[0]
    ts = buf[ts_col]
    closed = valid & (ts < cutoff)
    big = jnp.int64(1) << 62
    # (ts, seq) two-key sort via two stable passes (packing both keys
    # into one int64 would overflow epoch-ms timestamps); open rows
    # sink to the end via the sentinel
    order1 = jnp.argsort(seq, stable=True)
    ts_sorted = jnp.where(closed, ts, big)[order1]
    order = order1[jnp.argsort(ts_sorted, stable=True)]
    out_cols = {n: buf[n][order] for n in names}
    out_nulls = {n: bnulls[n][order] for n in bnulls}
    out_valid = closed[order]
    new_valid = valid & ~closed
    return (
        out_cols,
        out_nulls,
        out_valid,
        new_valid,
        jnp.sum(closed.astype(jnp.int32)),
    )


class ArenaBufferedExecutor(Executor, Checkpointable):
    """Shared EOWC arena: a fixed-capacity slot buffer in HBM holding
    open (not-yet-closed) rows keyed by arrival seq. Subclasses decide
    WHEN rows close and WHAT to emit (SortExecutor: ordered rows;
    EowcOverWindowExecutor: window-function outputs over complete
    partitions). One arena lifecycle — append, overflow/append-only
    latches, seq-keyed incremental checkpoints — lives here."""

    _arena_name = "EOWC arena"

    def __init__(
        self,
        schema_dtypes: Dict[str, object],
        capacity: int = 1 << 14,
        nullable: Sequence[str] = (),
        table_id: str = "arena",
    ):
        self.table_id = table_id
        self.names = tuple(schema_dtypes)
        self.capacity = capacity
        self.buf = {
            n: jnp.zeros(capacity, jnp.dtype(d))
            for n, d in schema_dtypes.items()
        }
        self.bnulls = {
            n: jnp.zeros(capacity, jnp.bool_)
            for n in nullable
            if n in self.names
        }
        self.valid = jnp.zeros(capacity, jnp.bool_)
        self.seq = jnp.zeros(capacity, jnp.int64)
        self.next_seq = jnp.zeros((), jnp.int64)
        self._overflow = jnp.zeros((), jnp.bool_)
        self._saw_delete = jnp.zeros((), jnp.bool_)

    def lint_info(self):
        return {
            "requires": tuple(self.names),
            "expects": {n: self.buf[n].dtype for n in self.names},
            "table_ids": (self.table_id,),
        }

    def state_nbytes(self) -> int:
        """Device bytes held (host-side estimate; no sync)."""
        return sum(
            leaf.nbytes
            for leaf in jax.tree.leaves(
                (self.buf, self.bnulls, self.valid, self.seq)
            )
        )

    def trace_contract(self):
        return {
            "kind": "device",
            "trace_step": lambda c: _sort_append(
                self.buf,
                self.bnulls,
                self.valid,
                self.seq,
                self.next_seq,
                c,
                self.names,
            ),
            "state": (self.buf, self.valid, self.seq),
            "donate": True,
            # window-close emissions are arena-capacity chunks: one
            # declared bucket
            "emission": "fixed",
            "emission_caps": (self.capacity,),
            "window_buckets": (self.capacity,),
        }

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        self._saw_delete = self._saw_delete | jnp.any(
            chunk.valid & (chunk.signs() < 0)
        )
        (
            self.buf,
            self.bnulls,
            self.valid,
            self.seq,
            self.next_seq,
            ovf,
        ) = _sort_append(
            self.buf,
            self.bnulls,
            self.valid,
            self.seq,
            self.next_seq,
            chunk,
            self.names,
        )
        self._overflow = self._overflow | ovf
        return []  # rows surface only when their time closes

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        self._staged_scalars = stage_scalars(
            self._saw_delete, self._overflow
        )
        if barrier is None:  # direct drive: checks fire inline
            self.finish_barrier()
        return []

    def _on_barrier_scalars(self, vals) -> None:
        saw_delete, overflow = vals
        if saw_delete:
            raise RuntimeError(
                f"{self._arena_name} requires append-only input"
            )
        if overflow:
            raise RuntimeError(
                f"{self._arena_name} overflowed; grow capacity or "
                "advance watermarks faster"
            )


    # -- integrity --------------------------------------------------------
    def digest_lanes(self):
        lanes = {f"c_{n}": self.buf[n] for n in self.names}
        for n, a in self.bnulls.items():
            lanes[f"cn_{n}"] = a
        lanes["seq"] = self.seq
        return lanes, self.valid

    def state_digest(self) -> int:
        from risingwave_tpu.integrity import host_digest

        return host_digest(*self.digest_lanes())

    # -- checkpoint/restore ----------------------------------------------
    def checkpoint_delta(self) -> List[StateDelta]:
        """Incremental staging keyed by seq: upsert only rows APPENDED
        since the last checkpoint, tombstone only rows that left (the
        Checkpointable O(changed) contract). The seq lane of live rows
        is pulled to diff against the previously-stored set — a freed
        slot may already hold a new row, so slot marks alone cannot
        name the departed seqs."""
        (valid_np,) = read_marks(self.valid)
        sel_all = np.flatnonzero(valid_np)
        seq_rows = pull_rows({"k0": self.seq}, sel_all)
        cur = (
            np.asarray(seq_rows["k0"], np.int64)
            if len(sel_all)
            else np.zeros(0, np.int64)
        )
        prev = getattr(self, "_stored_seqs", np.zeros(0, np.int64))
        new_mask = ~np.isin(cur, prev)
        sel_new = sel_all[new_mask]
        gone = np.setdiff1d(prev, cur)
        self._stored_seqs = cur
        n_up, n_del = len(sel_new), len(gone)
        if n_up + n_del == 0:
            return []
        lanes = {"k0": self.seq}
        lanes.update({f"v_{n}": self.buf[n] for n in self.names})
        lanes.update({f"n_{n}": l for n, l in self.bnulls.items()})
        rows = pull_rows(lanes, sel_new)
        key_cols = {
            "k0": np.concatenate(
                [np.asarray(rows["k0"], np.int64), gone]
            )
        }
        value_cols = {}
        for n in self.names:
            vals = np.asarray(rows[f"v_{n}"])
            value_cols[f"v_{n}"] = np.concatenate(
                [vals, np.zeros(n_del, vals.dtype)]
            )
        for n in self.bnulls:
            value_cols[f"n_{n}"] = np.concatenate(
                [
                    np.asarray(rows[f"n_{n}"]).astype(np.uint8),
                    np.zeros(n_del, np.uint8),
                ]
            )
        tomb = np.zeros(n_up + n_del, bool)
        tomb[n_up:] = True
        return [StateDelta(self.table_id, key_cols, value_cols, tomb, ("k0",))]

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        n = len(next(iter(key_cols.values()))) if key_cols else 0
        # recovery clears the error latches: the restored state is
        # valid even when a latched overflow/delete caused the recovery
        self._overflow = jnp.zeros((), jnp.bool_)
        self._saw_delete = jnp.zeros((), jnp.bool_)
        if n > self.capacity:
            # silent scatter-drop would lose buffered rows forever:
            # grow the arena to hold the checkpoint
            cap = self.capacity
            while n > cap:
                cap *= 2
            self.capacity = cap
            self.buf = {
                k: jnp.zeros(cap, v.dtype) for k, v in self.buf.items()
            }
            self.bnulls = {
                k: jnp.zeros(cap, jnp.bool_) for k in self.bnulls
            }
        cap = self.capacity
        self.valid = jnp.zeros(cap, jnp.bool_)
        self.seq = jnp.zeros(cap, jnp.int64)
        for nme in self.names:
            self.buf[nme] = jnp.zeros_like(self.buf[nme])
        if n == 0:
            self.next_seq = jnp.zeros((), jnp.int64)
            self._stored_seqs = np.zeros(0, np.int64)
            return
        seqs = np.asarray(key_cols["k0"], np.int64)
        idx = jnp.arange(n, dtype=jnp.int32)
        self.seq = self.seq.at[idx].set(jnp.asarray(seqs))
        for i, nme in enumerate(self.names):
            vals = np.asarray(value_cols[f"v_{nme}"])
            self.buf[nme] = (
                self.buf[nme].at[idx].set(
                    jnp.asarray(vals.astype(self.buf[nme].dtype))
                )
            )
        for nme in self.bnulls:
            if f"n_{nme}" in value_cols:
                self.bnulls[nme] = (
                    self.bnulls[nme]
                    .at[idx]
                    .set(jnp.asarray(value_cols[f"n_{nme}"].astype(bool)))
                )
        self.valid = self.valid.at[idx].set(True)
        self.next_seq = jnp.asarray(int(seqs.max()) + 1, jnp.int64)
        self._stored_seqs = seqs


class SortExecutor(ArenaBufferedExecutor):
    """EOWC sort: buffer until the ``ts_col`` watermark closes rows,
    then emit in (ts, arrival) order. Append-only input."""

    _arena_name = "EOWC sort buffer"

    def __init__(
        self,
        ts_col: str,
        schema_dtypes: Dict[str, object],
        capacity: int = 1 << 14,
        nullable: Sequence[str] = (),
        table_id: str = "sort",
    ):
        super().__init__(schema_dtypes, capacity, nullable, table_id)
        self.ts_col = ts_col

    def lint_info(self):
        info = super().lint_info()
        # EOWC contract: rows only ever leave the arena when a
        # watermark on ts_col closes them — an unreachable ts_col
        # means the buffer grows forever and nothing is emitted
        info["window_key"] = self.ts_col
        return info

    def on_watermark(self, watermark: Watermark):
        if watermark.column != self.ts_col:
            return watermark, []
        cutoff = jnp.asarray(watermark.value, jnp.int64)
        out_cols, out_nulls, out_valid, self.valid, n_closed = _sort_emit(
            self.buf, self.bnulls, self.valid, self.seq, cutoff,
            self.names, self.ts_col,
        )
        # one scalar read per watermark: an all-invalid capacity-wide
        # chunk would cost O(capacity) device work in EVERY downstream
        # stage, and EOWC emissions are empty most barriers — the
        # small sync is the cheaper side of the trade
        if int(n_closed) == 0:
            return watermark, []
        chunk = StreamChunk(
            columns=out_cols,
            valid=out_valid,
            nulls=out_nulls,
            ops=jnp.zeros(self.capacity, jnp.int32),
        )
        return watermark, [chunk]
