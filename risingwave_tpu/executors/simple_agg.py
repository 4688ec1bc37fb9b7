"""SimpleAgg — global (ungrouped) streaming aggregation.

Reference: src/stream/src/executor/simple_agg.rs (+ the per-chunk
pre-reduction of stateless_simple_agg.rs, which the epoch-reduce path
already fuses). SQL `SELECT count(*), sum(x) FROM t` with no GROUP BY:
exactly one output row, present even before any input (count 0 / NULL
sums), updated with U-/U+ pairs.

TPU re-design: one slot of the same slot-indexed AggState the grouped
executor uses (capacity 2: slot 0 = THE group, slot 1 = scatter drop
lane), no hash table — every valid row scatters into slot 0. The
barrier pulls exactly one row (one packed transfer) and diffs it
against the host mirror of what downstream last saw."""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor
from risingwave_tpu.ops import agg as agg_ops
from risingwave_tpu.ops.agg import AggCall, _order_key_to_float
from risingwave_tpu.storage.state_table import (
    Checkpointable,
    StateDelta,
    pull_rows,
    read_marks,
)
from risingwave_tpu.types import Op


@partial(jax.jit, static_argnames=("calls",), donate_argnums=(0,))
def _simple_step(state, chunk: StreamChunk, calls):
    signs = chunk.effective_signs()
    active = chunk.valid & (signs != 0)
    slots = jnp.where(active, jnp.int32(0), jnp.int32(-1))
    values = {c.input: chunk.col(c.input) for c in calls if c.input is not None}
    nulls = {
        c.input: chunk.nulls[c.input]
        for c in calls
        if c.input is not None and c.input in chunk.nulls
    }
    return agg_ops.apply(state, calls, slots, signs, values, nulls)


class SimpleAggExecutor(Executor, Checkpointable):
    """Global aggregation: one always-present output row (pk = ())."""

    def __init__(
        self,
        calls: Sequence[AggCall],
        schema_dtypes: Dict[str, object],
        table_id: str = "simple_agg",
    ):
        if any(c.materialized for c in calls):
            raise NotImplementedError(
                "materialized global MIN/MAX not wired yet (grouped "
                "HashAgg supports it)"
            )
        self.table_id = table_id
        self.calls = tuple(calls)
        self._dtypes = dict(schema_dtypes)
        self.state = agg_ops.create_state(2, self.calls, self._dtypes)
        self._float_decode = dict(
            agg_ops.float_extreme_meta(
                self.calls, {k: jnp.dtype(v) for k, v in self._dtypes.items()}
            )
        )
        self._last: Optional[Tuple] = None  # what downstream has

    def lint_info(self):
        requires = sorted(
            {c.input for c in self.calls if c.input is not None}
        )
        emits = {}
        for c in self.calls:
            if c.kind in ("count", "count_star"):
                emits[c.output] = jnp.int64
            elif c.kind in ("min", "max") and c.input in self._dtypes:
                emits[c.output] = self._dtypes[c.input]
            else:
                emits[c.output] = None  # sum/avg widen by kind rules
        return {
            "requires": tuple(requires),
            "expects": {
                k: self._dtypes[k] for k in requires if k in self._dtypes
            },
            "emits": emits,
            "renames": {k: None for k in emits},  # all computed
            "table_ids": (self.table_id,),
        }

    def state_nbytes(self) -> int:
        """Device bytes held (host-side estimate; no sync)."""
        return sum(
            getattr(leaf, "nbytes", 0)
            for leaf in jax.tree.leaves(self.state)
        )

    def trace_contract(self):
        return {
            "kind": "device",
            "trace_step": lambda c: _simple_step(
                self.state, c, self.calls
            ),
            "state": self.state,
            "donate": True,
            # _row_chunk sizes its emission by the rows emitted
            # (max(2, len(ops))) — data-dependent output shape
            "emission": "data_dependent",
        }

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        self.state = _simple_step(self.state, chunk, self.calls)
        return []

    def _current_row(self) -> Tuple:
        """(value | None per call) — ONE packed one-row device pull."""
        lanes = {"mret": self.state.minmax_retracted.reshape(1)}
        for c in self.calls:
            lanes[f"a_{c.output}"] = self.state.accums[c.output]
            if c.output in self.state.nonnull:
                lanes[f"n_{c.output}"] = self.state.nonnull[c.output]
        pulled = {
            k: np.asarray(v if v.shape[0] == 1 else v[:1])
            for k, v in pull_rows(lanes, np.asarray([0])).items()
        }
        if bool(pulled["mret"][0]):
            raise RuntimeError(
                "retraction hit an append-only global MIN/MAX; use the "
                "grouped executor's materialized extremes"
            )
        row = []
        for c in self.calls:
            v = pulled[f"a_{c.output}"][0]
            if c.output in self.state.nonnull:
                if int(pulled[f"n_{c.output}"][0]) == 0:
                    row.append(None)
                    continue
                if c.output in self._float_decode:
                    v = float(
                        _order_key_to_float(
                            jnp.asarray(v),
                            jnp.dtype(self._float_decode[c.output]),
                        )
                    )
            row.append(v.item() if hasattr(v, "item") else v)
        return tuple(row)

    def _row_chunk(self, rows_ops) -> StreamChunk:
        cols = {c.output: [] for c in self.calls}
        nulls = {
            c.output: [] for c in self.calls if c.output in self.state.nonnull
        }
        ops = []
        for row, op in rows_ops:
            ops.append(op)
            for c, v in zip(self.calls, row):
                cols[c.output].append(0 if v is None else v)
                if c.output in nulls:
                    nulls[c.output].append(v is None)
        np_cols = {}
        for c in self.calls:
            dt = np.asarray(self.state.accums[c.output][:1]).dtype
            if c.output in self._float_decode:
                dt = np.dtype(self._float_decode[c.output])
            np_cols[c.output] = np.asarray(cols[c.output], dt)
        return StreamChunk.from_numpy(
            np_cols,
            max(2, len(ops)),
            ops=np.asarray(ops, np.int32),
            nulls={k: np.asarray(v, bool) for k, v in nulls.items()},
        )

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        cur = self._current_row()
        if self._last is None:
            self._last = cur
            return [self._row_chunk([(cur, Op.INSERT)])]
        if cur == self._last:
            return []
        out = self._row_chunk(
            [(self._last, Op.UPDATE_DELETE), (cur, Op.UPDATE_INSERT)]
        )
        self._last = cur
        return [out]

    # -- integrity --------------------------------------------------------
    def digest_lanes(self):
        lanes = {"row_count": self.state.row_count}
        for n, a in self.state.accums.items():
            lanes[f"acc_{n}"] = a
        for n, a in self.state.nonnull.items():
            lanes[f"nn_{n}"] = a
        return lanes, None

    def state_digest(self) -> int:
        from risingwave_tpu.integrity import host_digest

        return host_digest(*self.digest_lanes())

    # -- checkpoint -------------------------------------------------------
    def checkpoint_delta(self) -> List[StateDelta]:
        if not bool(read_marks(self.state.sdirty[:1])[0][0]):
            return []
        lanes = {"row_count": self.state.row_count}
        for n, a in self.state.accums.items():
            lanes[f"acc_{n}"] = a
        for n, a in self.state.nonnull.items():
            lanes[f"nn_{n}"] = a
        pulled = pull_rows(lanes, np.asarray([0]))
        self.state.sdirty = jnp.zeros_like(self.state.sdirty)
        return [
            StateDelta(
                self.table_id,
                {"k0": np.zeros(1, np.int64)},
                pulled,
                np.zeros(1, bool),
                ("k0",),
            )
        ]

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        state = agg_ops.create_state(2, self.calls, self._dtypes)
        self._last = None
        if key_cols and len(key_cols["k0"]):

            def put(dst, src):
                return dst.at[0].set(
                    jnp.asarray(np.asarray(src)[0]).astype(dst.dtype)
                )

            state.row_count = put(state.row_count, value_cols["row_count"])
            for n in state.accums:
                state.accums[n] = put(state.accums[n], value_cols[f"acc_{n}"])
            for n in state.nonnull:
                state.nonnull[n] = put(state.nonnull[n], value_cols[f"nn_{n}"])
            self.state = state
            # downstream (the restored MV) already holds the last
            # emitted row = the restored aggregate values
            self._last = self._current_row()
        else:
            self.state = state
