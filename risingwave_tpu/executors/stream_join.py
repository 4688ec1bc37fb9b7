"""StreamJoin executor — an INNER stream-to-stream equi-join with no
bound on the rows a key holds on either side.

Reference: src/stream/src/executor/hash_join.rs (the same change-stream
semantics as HashJoinExecutor: each arriving row probes the other side
and emits a pair per stored match with its own sign, then is stored on
its own side; a non-equi ``cond`` is evaluated on every matched pair
inside the join, hash_join.rs ``cond``; a retraction names its row by
its columns, the multiset contract of ``ops/join.apply_side``). What
differs is the state layout (ops/stream_join.py, ``ChainSide``): a row
store that follows the rows it holds, a key table with the head of
each key's chain. The planner takes it for an INNER join whose sides
are not both unique per join key (NEXmark q4: bids joined to their
auctions); outer, semi and anti joins keep the bucket layout, whose
degree lane this one does not carry.

Emission. A chunk's pairs are not handed on chunk by chunk: the step
appends them to the epoch's pair buffer on the device, and the barrier
(or a watermark, or a buffer about to fill) hands the buffer on as one
chunk cut to a declared lattice (``emission_caps``), every size of
which is compiled when the view is created (``warm_emissions``). What
follows the join — an aggregate's epoch program — therefore meets one
chunk an epoch at one of three widths, whatever the pushes were. The
host reads two numbers a chunk (its pairs in all, the buffer's fill):
pairs beyond one step's ``out_cap`` run as further steps over the same
chunk, a buffer that could not take another step's pairs is handed on
early, so no count of pairs raises.

Growth: the row store doubles when the host's bound on appended rows
would pass it, the key table when claimed keys would pass half of it,
between chunks, from one packed read (span ``join.regrow``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor, Watermark
from risingwave_tpu.executors.filter import note_residual_rows
from risingwave_tpu.expr.expr import StaticTree
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.ops.hash_table import read_scalars, stage_scalars
from risingwave_tpu.ops.stream_join import (
    ChainSide,
    chain_grow,
    chain_relink,
    stream_join_step,
)
from risingwave_tpu.storage.state_table import (
    Checkpointable,
    StateDelta,
    classify_marks,
    grow_pow2,
    pull_rows,
)
from risingwave_tpu.trace import device_read, span
from risingwave_tpu.types import Op

GROW_AT = 0.5

_step = jax.jit(
    stream_join_step,
    static_argnames=(
        "own_keys", "own_names", "other_names", "out_cap", "cond",
        "retract", "fold",
    ),
    donate_argnums=(0, 1, 2),
)
_grow = jax.jit(
    chain_grow, static_argnames=("key_cap", "row_cap", "key_names")
)
_relink = jax.jit(chain_relink, static_argnames=("key_cap", "key_names"))


@jax.jit
def _leading(buf: StreamChunk, fill, lanes_like):
    """The buffer's first lanes as a chunk of ``lanes_like``'s width."""
    n = lanes_like.shape[0]
    return StreamChunk(
        columns={k: a[:n] for k, a in buf.columns.items()},
        valid=jnp.arange(n, dtype=jnp.int32) < fill,
        nulls={k: a[:n] for k, a in buf.nulls.items()},
        ops=buf.ops[:n],
    )


@jax.jit
def _count_deletes(n, out: StreamChunk):
    """``n`` + the pairs ``out`` takes back."""
    return n + jnp.sum(
        out.valid & (out.ops == jnp.int32(Op.DELETE)), dtype=jnp.int64
    )


@jax.jit
def _side_stats(side: ChainSide):
    return (
        side.overflow, side.inconsistent, side.n_rows,
        side.table.occupancy(), jnp.sum(side.count), jnp.max(side.count),
    )


class StreamJoinExecutor(Executor, Checkpointable):
    """Args:
      left_keys / right_keys: equi-join columns, positionally paired,
        dtypes equal pair by pair.
      left_dtypes / right_dtypes: column -> dtype per side; every
        column is stored and emitted; names disjoint across sides.
      condition: optional Expr over both sides' columns, the residual.
      left_append_only / right_append_only: the side's change stream
        carries inserts only (a delete on it is an inconsistent
        stream); its step then holds no retraction code and its
        checkpoint reads no marks.
      capacity: both sides' starting row store and key table.
      out_cap: pairs one step takes; the pair buffer holds 4 x this.
    """

    join_type = "inner"
    layout = "chain"
    window_cols = None

    def __init__(
        self,
        left_keys: Sequence[str],
        right_keys: Sequence[str],
        left_dtypes: Dict[str, object],
        right_dtypes: Dict[str, object],
        condition=None,
        left_append_only: bool = False,
        right_append_only: bool = False,
        capacity: int = 1 << 15,
        out_cap: int = 1 << 13,
        table_id: str = "stream_join",
    ):
        if set(left_dtypes) & set(right_dtypes):
            raise ValueError(
                "overlapping output columns: "
                f"{set(left_dtypes) & set(right_dtypes)}"
            )
        self.table_id = table_id
        self.left_keys, self.right_keys = tuple(left_keys), tuple(right_keys)
        self.left_names = tuple(sorted(left_dtypes))
        self.right_names = tuple(sorted(right_dtypes))
        self.out_names = self.left_names + self.right_names
        self._lint_left = {n: jnp.dtype(d) for n, d in left_dtypes.items()}
        self._lint_right = {n: jnp.dtype(d) for n, d in right_dtypes.items()}
        for lk, rk in zip(self.left_keys, self.right_keys):
            if self._lint_left[lk] != self._lint_right[rk]:
                raise ValueError(
                    f"join key dtype mismatch: {lk} {self._lint_left[lk]} "
                    f"vs {rk} {self._lint_right[rk]}"
                )
        self.condition = condition
        self._cond = StaticTree(condition) if condition is not None else None
        self._retract = {
            "left": not left_append_only, "right": not right_append_only
        }
        self.out_cap = int(out_cap)
        # one pair's lanes as the join hands it on: every column of both
        self._emit_row_bytes = sum(
            d.itemsize for d in self._out_dtypes().values()
        )
        self.left = ChainSide.create(
            capacity, capacity,
            tuple(self._lint_left[k] for k in self.left_keys),
            self._lint_left,
        )
        self.right = ChainSide.create(
            capacity, capacity,
            tuple(self._lint_right[k] for k in self.right_keys),
            self._lint_right,
        )
        self._buf = self._empty(4 * self.out_cap)
        self._fill = 0  # pairs the buffer holds (the host reads it)
        self._cursor = jnp.zeros((), jnp.int32)
        # host bounds since the last true reading: rows appended, keys
        # claimed (chunk capacities: every lane could be a new key)
        self._rows_bound = {"left": 0, "right": 0}
        self._keys_bound = {"left": 0, "right": 0}
        # rows a side held at its last checkpoint / barrier (an
        # append-only side's delta is the rows between the two)
        self._rows_ckpt = {"left": 0, "right": 0}
        self._rows_now = {"left": 0, "right": 0}
        self._counts = jnp.zeros(3, jnp.int64)  # matched, kept, probe lanes
        # what an updating side (one whose stream takes rows back)
        # costs: the pairs handed on as DELETEs this epoch, counted on
        # the device where a side retracts (None: no side does, and no
        # pair is ever taken back), and the lanes each side has left
        # dead so far (a retracted row's lane is not reused)
        self._retracted_pairs = (
            jnp.zeros((), jnp.int64) if any(self._retract.values()) else None
        )
        self._dead = {"left": 0, "right": 0}

    # -- the pair buffer ---------------------------------------------------
    def _out_dtypes(self):
        return {**self._lint_left, **self._lint_right}

    def _null_names(self):
        return sorted(set(self.left.row_nulls) | set(self.right.row_nulls))

    def _empty(self, lanes: int) -> StreamChunk:
        return StreamChunk(
            columns={
                n: jnp.zeros(lanes, d) for n, d in self._out_dtypes().items()
            },
            valid=jnp.zeros(lanes, jnp.bool_),
            nulls={n: jnp.zeros(lanes, jnp.bool_) for n in self._null_names()},
            ops=jnp.zeros(lanes, jnp.int32),
        )

    @property
    def emission_caps(self):
        """The widths a flush hands on: the buffer, its half and its
        quarter (one step's pairs). Three sizes and steps of two: an
        aggregate's epoch program behind the join sorts the chunk it is
        handed, and on the chip such a program takes 4 s to compile at
        4,096 lanes, 36 s at 16,384 and minutes at 65,536 (PERF.md 6,
        PR 33), so the buffer is no wider than a backlog's epoch needs
        and no size is more than twice what it holds."""
        full = 4 * self.out_cap
        return tuple(sorted({max(full // 4, 1), max(full // 2, 1), full}))

    def warm_emissions(self) -> List[StreamChunk]:
        # (through the cut itself, so that its program exists too, and
        # the count of the pairs it takes back where a side retracts)
        outs = [self._cut(w, 0) for w in self.emission_caps]
        if self._retracted_pairs is not None:
            for out in outs:
                _count_deletes(self._retracted_pairs, out)
        return outs

    def _cut(self, width: int, fill: int) -> StreamChunk:
        return _leading(
            self._buf, jnp.int32(fill), jnp.zeros(width, jnp.bool_)
        )

    def _flush(self) -> List[StreamChunk]:
        if not self._fill:
            return []
        width = next(w for w in self.emission_caps if w >= self._fill)
        out = self._cut(width, self._fill)
        if self._retracted_pairs is not None:
            self._retracted_pairs = _count_deletes(self._retracted_pairs, out)
        # (the lanes stay: a pair lane is written whole when it is taken)
        self._cursor = jnp.zeros((), jnp.int32)
        self._fill = 0
        return [out]

    # -- plan verifier ---------------------------------------------------
    def lint_info(self):
        dtypes = self._out_dtypes()
        return {
            "left_keys": self.left_keys,
            "right_keys": self.right_keys,
            "expects_left": dict(self._lint_left),
            "expects_right": dict(self._lint_right),
            "emits": {n: dtypes[n] for n in self.out_names},
            "table_ids": (self.table_id,),
            "window_cols": None,
        }

    def trace_contract(self):
        return None  # interpreted only: the fused programs do not know it

    # -- data ------------------------------------------------------------
    def apply_left(self, chunk: StreamChunk) -> List[StreamChunk]:
        return self._apply("left", chunk)

    def apply_right(self, chunk: StreamChunk) -> List[StreamChunk]:
        return self._apply("right", chunk)

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        raise TypeError("StreamJoin is two-input: use apply_left/apply_right")

    def _admit_nulls(self, name: str, chunk: StreamChunk) -> None:
        """A column that brings NULL flags gets a lane for them, in the
        side's store and in the pair buffer."""
        side = getattr(self, name)
        new = [
            n for n in chunk.nulls
            if n in side.rows and n not in side.row_nulls
        ]
        if new:
            setattr(self, name, side.with_null_lanes(new))
            lanes = self._buf.valid.shape[0]
            self._buf = StreamChunk(
                columns=self._buf.columns,
                valid=self._buf.valid,
                nulls={
                    **{n: jnp.zeros(lanes, jnp.bool_) for n in new},
                    **self._buf.nulls,
                },
                ops=self._buf.ops,
            )

    def _run(self, name: str, chunk: StreamChunk, start: int, fold: bool):
        other = "left" if name == "right" else "right"
        own, oth, self._buf, self._cursor, self._counts, total = _step(
            getattr(self, name),
            getattr(self, other),
            self._buf,
            self._cursor,
            chunk,
            self._counts,
            jnp.int32(start),
            own_keys=self.left_keys if name == "left" else self.right_keys,
            own_names=self.left_names if name == "left" else self.right_names,
            other_names=(
                self.right_names if name == "left" else self.left_names
            ),
            out_cap=self.out_cap,
            cond=self._cond,
            retract=self._retract[name],
            fold=fold,
        )
        setattr(self, name, own)
        setattr(self, other, oth)
        return total

    def _apply(self, name: str, chunk: StreamChunk) -> List[StreamChunk]:
        self._maybe_grow(name, chunk.capacity)
        self._rows_bound[name] += chunk.capacity
        self._keys_bound[name] += chunk.capacity
        self._admit_nulls(name, chunk)
        outs: List[StreamChunk] = []
        start, total = 0, 1
        while start < total:
            if self._fill + self.out_cap > self._buf.valid.shape[0]:
                outs.extend(self._flush())
            total_dev = self._run(name, chunk, start, fold=start == 0)
            # the two numbers a chunk costs the host: its pairs in all
            # (more than a step takes: another step) and the buffer's
            # fill (what the next step may still append)
            total, self._fill = read_scalars(
                total_dev, self._cursor, what="join.step"
            )
            start += self.out_cap
        return outs

    def warm_side(self, name: str, chunk: StreamChunk) -> List[StreamChunk]:
        """``Executor.warm`` for one side's chunk: the side's step and
        its continuation over a chunk with no valid row, which store,
        clear and pair nothing, into a buffer of their own; no bound
        moves, nothing grows, no count is kept. What the join hands on
        does not follow its input (``warm_emissions``), so nothing is
        returned."""
        self._admit_nulls(name, chunk)
        if self._needs_grow(name, chunk.capacity):
            return []  # its step never runs at this capacity
        keep = (self._buf, self._cursor, self._counts, self._fill)
        self._buf = self._empty(4 * self.out_cap)
        self._cursor = jnp.zeros((), jnp.int32)
        for start, fold in ((0, True), (self.out_cap, False)):
            self._run(name, chunk, start, fold)
        self._buf, self._cursor, self._counts, self._fill = keep
        return []

    # -- growth ------------------------------------------------------------
    def _needs_grow(self, name: str, incoming: int) -> bool:
        side = getattr(self, name)
        return (
            self._rows_bound[name] + incoming > side.row_cap
            or self._keys_bound[name] + incoming > side.key_cap * GROW_AT
        )

    def _maybe_grow(self, name: str, incoming: int) -> None:
        if not self._needs_grow(name, incoming):
            return
        side = getattr(self, name)
        # ONE packed read of what is truly appended and claimed
        n_rows, claimed = read_scalars(
            side.n_rows, side.table.occupancy(), what="join.occupancy"
        )
        row_cap, key_cap = side.row_cap, side.key_cap
        while n_rows + incoming > row_cap:
            row_cap *= 2
        key_cap = grow_pow2(claimed + incoming, key_cap, GROW_AT)
        if (row_cap, key_cap) != (side.row_cap, side.key_cap):
            with span(
                "join.regrow", join=self.table_id, side=name,
                **{"from": [side.key_cap, side.row_cap],
                   "to": [key_cap, row_cap]},
            ):
                grown = _grow(
                    side, key_cap=key_cap, row_cap=row_cap,
                    key_names=self._keys(name),
                )
                with device_read("join.regrow"):
                    jax.block_until_ready(grown.row_valid)
            REGISTRY.counter("join_regrows_total").inc(
                1, join=self.table_id, side=name
            )
            setattr(self, name, grown)
            if key_cap != side.key_cap:  # a rebuilt table drops dead keys
                claimed = int(grown.table.occupancy())
        self._rows_bound[name] = int(n_rows)
        self._keys_bound[name] = int(claimed)

    def _keys(self, name: str):
        return self.left_keys if name == "left" else self.right_keys

    def state_nbytes(self) -> int:
        return sum(
            leaf.nbytes
            for leaf in jax.tree.leaves((self.left, self.right, self._buf))
        )

    # -- control ---------------------------------------------------------
    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        outs = self._flush()
        took_back = ()
        if self._retracted_pairs is not None:
            took_back = (self._retracted_pairs,)
            self._retracted_pairs = jnp.zeros((), jnp.int64)
        self._staged_scalars = stage_scalars(
            *_side_stats(self.left), *_side_stats(self.right), *self._counts,
            *took_back,
        )
        self._counts = jnp.zeros(3, jnp.int64)
        if barrier is None:  # direct drive: checks fire inline
            self.finish_barrier()
        return outs

    def _on_barrier_scalars(self, vals) -> None:
        stats = {"left": vals[0:6], "right": vals[6:12]}
        matched, kept, probe_lanes = (int(v) for v in vals[12:15])
        retract_pairs = int(vals[15]) if len(vals) > 15 else 0
        held, fullest = {}, {}
        # the rows the updating sides took this epoch, inserts (rows
        # appended) and deletes (lanes newly dead) alike
        retract_rows = dead_lanes = 0
        for name, (_, _, n_rows, claimed, rows, key_max) in stats.items():
            dead = int(n_rows) - int(rows)
            if self._retract[name]:
                retract_rows += int(n_rows) - self._rows_now[name]
                retract_rows += dead - self._dead[name]
            dead_lanes += dead - self._dead[name]
            self._dead[name] = dead
            REGISTRY.gauge("join_dead_lanes").set(
                dead, join=self.table_id, side=name
            )
            self._rows_bound[name] = self._rows_now[name] = int(n_rows)
            self._keys_bound[name] = int(claimed)
            held[name], fullest[name] = int(rows), int(key_max)
            REGISTRY.gauge("join_side_rows").set(
                held[name], join=self.table_id, side=name
            )
            REGISTRY.gauge("join_key_rows_max").set(
                fullest[name], join=self.table_id, side=name
            )
        note_residual_rows(
            self.table_id, kept, matched - kept, layout=self.layout,
            left_rows=held["left"], right_rows=held["right"],
            key_rows_max=max(fullest.values()), probe_lanes=probe_lanes,
            emit_row_bytes=self._emit_row_bytes,
            retract_rows=retract_rows, retract_pairs=retract_pairs,
            dead_lanes=dead_lanes,
        )
        REGISTRY.counter("join_retract_rows_total").inc(
            retract_rows, join=self.table_id
        )
        REGISTRY.counter("join_retract_pairs_total").inc(
            retract_pairs, join=self.table_id
        )
        for name, (overflow, inconsistent, *_rest) in stats.items():
            if overflow:
                raise RuntimeError(
                    f"{name} join side overflowed between growths (key "
                    "table probe chain or row store); grow capacity"
                )
            if inconsistent:
                raise RuntimeError(
                    f"{name} join side saw a DELETE matching no stored row "
                    "(inconsistent input stream)"
                )

    def on_watermark(self, watermark: Watermark):
        # the buffered pairs precede the watermark in stream order
        return watermark, self._flush()

    # -- integrity -------------------------------------------------------
    def digest_lanes(self):
        lanes = {f"l_{n}": a for n, a in self.left.rows.items()}
        lanes.update({f"r_{n}": a for n, a in self.right.rows.items()})
        lanes.update({f"ln_{n}": a for n, a in self.left.row_nulls.items()})
        lanes.update({f"rn_{n}": a for n, a in self.right.row_nulls.items()})
        return lanes, self.left.row_valid, self.right.row_valid

    def state_digest(self) -> int:
        from risingwave_tpu.integrity import host_digest

        out = 0
        for side in (self.left, self.right):
            lanes = {n: np.asarray(a) for n, a in side.rows.items()}
            for n, a in side.row_nulls.items():
                null = np.asarray(a)
                lanes[n] = np.where(null, 0, lanes[n])
                lanes[f"n_{n}"] = null
            out ^= host_digest(lanes, np.asarray(side.row_valid))
        return out

    # -- checkpoint/restore ----------------------------------------------
    # A row is keyed by its position in the row store ("k0"): it keeps
    # it for as long as the side lives, and a restore puts it back.
    def checkpoint_table_ids(self):
        return [f"{self.table_id}.left", f"{self.table_id}.right"]

    def checkpoint_delta(self):
        out = []
        for name in ("left", "right"):
            side = getattr(self, name)
            if self._retract[name]:
                # the rows of the selection and, a table keyed by its
                # slots, the slots themselves
                sel = classify_marks(side.rdirty, side.row_valid, side.stored)
                setattr(self, name, dataclasses.replace(
                    side, rdirty=sel.sdirty, stored=sel.stored
                ))
                if not len(sel):
                    continue
                k0, tombstone = sel.slots(), sel.tombstone
            else:
                # inserts only: the rows appended since the last
                # checkpoint, as the barrier's read counted them
                sel = np.arange(self._rows_ckpt[name], self._rows_now[name])
                if not len(sel):
                    continue
                k0, tombstone = sel.astype(np.int64), np.zeros(len(sel), bool)
            self._rows_ckpt[name] = self._rows_now[name]
            lanes = {f"r_{n}": a for n, a in side.rows.items()}
            lanes.update({f"n_{n}": a for n, a in side.row_nulls.items()})
            out.append(
                StateDelta(
                    f"{self.table_id}.{name}",
                    {"k0": k0},
                    pull_rows(lanes, sel),
                    tombstone,
                    ("k0",),
                )
            )
        return out

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        name = "left" if table_id.endswith(".left") else "right"
        side = getattr(self, name)
        at = (
            np.asarray(key_cols["k0"], np.int64)
            if key_cols else np.zeros(0, np.int64)
        )
        n_rows = int(at.max()) + 1 if len(at) else 0
        row_cap = side.row_cap
        while n_rows > row_cap:
            row_cap *= 2
        nullable = sorted(
            set(side.row_nulls)
            | {k[2:] for k in value_cols if k.startswith("n_")}
        )
        fresh = ChainSide.create(
            side.key_cap, row_cap,
            tuple(k.dtype for k in side.table.keys),
            {nm: a.dtype for nm, a in side.rows.items()},
            nullable,
        )
        if len(at):
            idx = jnp.asarray(at.astype(np.int32))
            fresh = dataclasses.replace(
                fresh,
                rows={
                    nm: a.at[idx].set(
                        jnp.asarray(value_cols[f"r_{nm}"]).astype(a.dtype)
                    )
                    for nm, a in fresh.rows.items()
                },
                row_nulls={
                    nm: a.at[idx].set(jnp.asarray(value_cols[f"n_{nm}"]))
                    if f"n_{nm}" in value_cols else a
                    for nm, a in fresh.row_nulls.items()
                },
                row_valid=fresh.row_valid.at[idx].set(True),
                stored=fresh.stored.at[idx].set(True),
                n_rows=jnp.int32(n_rows),
            )
            keys = len(np.unique(
                np.stack(
                    [np.asarray(value_cols[f"r_{k}"]) for k in self._keys(name)]
                ),
                axis=1,
            ).T)
            fresh = _relink(
                fresh,
                key_cap=grow_pow2(keys, side.key_cap, GROW_AT),
                key_names=self._keys(name),
            )
        setattr(self, name, fresh)
        self._rows_bound[name] = self._rows_now[name] = n_rows
        self._rows_ckpt[name] = n_rows
        self._dead[name] = n_rows - len(at)  # (the store keeps live rows)
        self._keys_bound[name] = int(fresh.table.occupancy())
