"""Filter executor — predicate over visibility, zero data movement.

Reference: src/stream/src/executor/filter.rs (234 LoC). The reference
also downgrades broken UpdateDelete/UpdateInsert pairs (where only one
half passes) to plain Delete/Insert; with columnar ops that is a pure
elementwise op-lane rewrite, done here in the same fused step.
"""

from __future__ import annotations

from functools import partial
from typing import List

import jax
import jax.numpy as jnp

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Executor
from risingwave_tpu.expr import Expr
from risingwave_tpu.expr.expr import StaticTree
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.trace import span
from risingwave_tpu.types import mend_update_pairs


@partial(jax.jit, static_argnames=("pred",))
def _filter_step(chunk: StreamChunk, pred: "StaticTree") -> StreamChunk:
    # pred rides as a STRUCTURALLY-keyed static: a bare Expr static
    # collides in the jit cache (Expr.__eq__ builds a truthy BinOp)
    keep_v, keep_n = pred.value.eval(chunk)
    keep = keep_v.astype(jnp.bool_)
    if keep_n is not None:
        keep = keep & ~keep_n  # NULL predicate drops the row (SQL WHERE)
    out = chunk.mask(keep)

    # a U-/U+ pair of which exactly one half survives is a plain op
    new_ops = mend_update_pairs(out.ops, out.valid)
    return StreamChunk(out.columns, out.valid, out.nulls, new_ops)


class FilterExecutor(Executor):
    def __init__(self, pred: Expr):
        self._spred = StaticTree(pred)
        self.pred = pred

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        return [_filter_step(chunk, self._spred)]

    def lint_info(self):
        from risingwave_tpu.expr.expr import collect_columns

        return {"requires": tuple(sorted(collect_columns(self.pred)))}

    def pure_step(self):
        # the fused-chain contract (runtime/fused_step + epoch_batch):
        # a module-level partial with hashable bound args, so the predicate
        # traces into the fused per-barrier program and compiles once
        # per plan shape, not once per executor instance
        return partial(_filter_step, pred=self._spred)


@jax.jit
def _count_kept(counts, before: StreamChunk, after: StreamChunk):
    kept = jnp.sum(after.valid.astype(jnp.int64))
    return counts + jnp.stack(
        [kept, jnp.sum(before.valid.astype(jnp.int64)) - kept]
    )


def note_residual_rows(table_id: str, kept: int, dropped: int, **more) -> None:
    """What an inner join's residual predicate did with the pairs its
    equi key matched, once an epoch: ``join_residual_rows_total{join,
    outcome}``, and a ``join.epoch`` span (join, pairs_kept,
    pairs_dropped and whatever else the join's layout counts), which
    unlike a counter belongs to its epoch."""
    c = REGISTRY.counter("join_residual_rows_total")
    c.inc(kept, join=table_id, outcome="kept")
    c.inc(dropped, join=table_id, outcome="dropped")
    with span(
        "join.epoch", join=table_id, pairs_kept=int(kept),
        pairs_dropped=int(dropped), **more,
    ):
        pass


class ResidualFilterExecutor(FilterExecutor):
    """The non-equi conjuncts of an INNER JOIN's ON, over the equi
    join's change stream: sigma(A JOIN B) is exactly a filter there,
    and a U-/U+ pair of which one half fails becomes a bare delete or
    insert (``_filter_step``). Counts the pairs it keeps and drops on
    the device and reports them once a barrier."""

    def __init__(self, pred: Expr, join_table_id: str):
        super().__init__(pred)
        self.join_table_id = join_table_id
        self._counts = jnp.zeros(2, jnp.int64)

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        out = _filter_step(chunk, self._spred)
        self._counts = _count_kept(self._counts, chunk, out)
        return [out]

    def on_barrier(self, barrier) -> List[StreamChunk]:
        from risingwave_tpu.ops.hash_table import stage_scalars

        self._staged_scalars = stage_scalars(*self._counts)
        self._counts = jnp.zeros(2, jnp.int64)
        if barrier is None:
            self.finish_barrier()
        return []

    def _on_barrier_scalars(self, vals) -> None:
        note_residual_rows(self.join_table_id, *vals)

    def pure_step(self):
        return None  # it counts: not a pure chunk -> chunk step
