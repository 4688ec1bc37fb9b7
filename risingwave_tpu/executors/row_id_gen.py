"""RowIdGen executor — hidden serial pk for pk-less streams.

Reference: src/stream/src/executor/row_id_gen.rs — assigns a serial
row id per vnode so append-only tables without a user pk still have a
stable one. Here: ids are ``base + lane`` per chunk with a host-side
base counter. The counter CHECKPOINTS (the reference persists row-id
state the same way): a recovered pipeline continues the id sequence
instead of colliding with restored MV pks.
"""

from __future__ import annotations

from typing import List

import jax.numpy as jnp
import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Executor
from risingwave_tpu.storage.state_table import Checkpointable, StateDelta


class RowIdGenExecutor(Executor, Checkpointable):
    def __init__(self, out_col: str = "_row_id", table_id: str = "row_id_gen"):
        self.out_col = out_col
        self.table_id = table_id
        self._base = 0
        self._committed = -1

    def lint_info(self):
        import jax.numpy as jnp

        return {
            "adds": {self.out_col: jnp.int64},
            "table_ids": (self.table_id,),
        }

    def state_nbytes(self) -> int:
        """Memory-ledger contract: the only state is two host
        counters — no device bytes beyond the bookkeeping."""
        return 16

    def trace_contract(self):
        return {
            "kind": "device",
            # same math as apply with the host counter as a traced
            # zero-d base — the counter is trivially convertible to
            # carried device state in a fused step
            "trace_step": lambda c: c.with_columns(
                **{
                    self.out_col: jnp.zeros((), jnp.int64)
                    + jnp.arange(c.capacity, dtype=jnp.int64)
                }
            ),
            "state": None,
            "donate": True,
            "emission": "passthrough",
        }

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        if self.out_col in chunk.columns:
            # DML deletes/updates address existing rows BY id — never
            # reassign (reference row_id_gen.rs only fills fresh
            # inserts; deletes carry the stored row)
            return [chunk]
        out = self._with_ids(chunk)
        self._base += chunk.capacity
        return [out]

    def _with_ids(self, chunk: StreamChunk) -> StreamChunk:
        ids = self._base + jnp.arange(chunk.capacity, dtype=jnp.int64)
        return chunk.with_columns(**{self.out_col: ids})

    # one step a chunk at the chunk's own width: takes the push lattice
    per_chunk_step = True

    def warm(self, chunk: StreamChunk) -> List[StreamChunk]:
        """``Executor.warm``: the ids' program for a chunk of this
        width; the counter stays where it is."""
        if self.out_col in chunk.columns:
            return [chunk]
        return [self._with_ids(chunk)]

    # -- integrity --------------------------------------------------------
    def state_digest(self) -> int:
        """Durable logical state is the id watermark (one counter)."""
        from risingwave_tpu.integrity import host_obj_digest

        return host_obj_digest({"base": int(self._base)})

    # -- checkpoint/restore ----------------------------------------------
    def checkpoint_delta(self) -> List[StateDelta]:
        if self._base == self._committed:
            return []
        self._committed = self._base
        return [
            StateDelta(
                self.table_id,
                {"k": np.zeros(1, np.int64)},
                {"base": np.asarray([self._base], np.int64)},
                np.zeros(1, bool),
                ("k",),
            )
        ]

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        if key_cols:
            self._base = int(value_cols["base"][0])
            self._committed = self._base
