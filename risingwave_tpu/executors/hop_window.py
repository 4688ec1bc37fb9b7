"""Hop (sliding) window executor — row expansion.

Reference: src/stream/src/executor/hop_window.rs — each input row falls
into ``size/slide`` overlapping windows and is emitted once per window
with (window_start, window_end) columns attached.

TPU re-design: the expansion factor is static, so a chunk of capacity C
becomes one chunk of capacity C * factor by tiling every lane and
computing each copy's window start arithmetically — no loops, no
dynamic shapes. Rows whose k-th window would not contain their
timestamp are masked invalid (only possible for negative timestamps;
kept for safety).
"""

from __future__ import annotations

from functools import partial
from typing import List, Optional

import jax
import jax.numpy as jnp

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Executor


def hop_step_fn(
    chunk: StreamChunk, ts_col: str, size_ms: int, slide_ms: int,
    out_start: str, out_end: Optional[str] = None,
) -> StreamChunk:
    factor = -(-size_ms // slide_ms)  # ceil
    cap = chunk.capacity

    # block layout: copy k of every row forms one contiguous cap-sized
    # block, so adjacent rows STAY adjacent within each block — the
    # U-/U+ update-pair invariant (stream_chunk.rs:45) that FilterExecutor
    # and sinks rely on survives the expansion (jnp.repeat would tear
    # every pair apart; code-review r2).
    def tile(a):
        return jnp.tile(a, factor)

    ts = chunk.col(ts_col)
    # earliest aligned window start strictly greater than ts - size
    first = (jnp.floor_divide(ts - size_ms, slide_ms) + 1) * slide_ms
    k = jnp.repeat(jnp.arange(factor, dtype=ts.dtype), cap)
    starts = tile(first) + k * slide_ms
    in_window = starts <= tile(ts)  # start + size > ts holds by choice of first

    cols = {n: tile(a) for n, a in chunk.columns.items()}
    cols[out_start] = starts
    if out_end is not None:  # only where the query names it
        cols[out_end] = starts + size_ms
    # a pre-existing null lane on an output column must not survive the
    # replacement (freshly computed bounds are never NULL)
    nulls = {
        n: tile(a) for n, a in chunk.nulls.items()
        if n not in (out_start, out_end)
    }
    valid = tile(chunk.valid) & in_window
    ops = tile(chunk.ops)
    return StreamChunk(cols, valid, nulls, ops)


_hop_step = partial(
    jax.jit,
    static_argnames=("ts_col", "size_ms", "slide_ms", "out_start", "out_end"),
)(hop_step_fn)


class HopWindowExecutor(Executor):
    def __init__(
        self,
        ts_col: str,
        size_ms: int,
        slide_ms: int,
        out_start: str = "window_start",
        out_end: Optional[str] = None,
    ):
        if size_ms % slide_ms:
            raise ValueError("size must be a multiple of slide")
        self.ts_col = ts_col
        self.size_ms = size_ms
        self.slide_ms = slide_ms
        self.out_start = out_start
        self.out_end = out_end

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        return [
            _hop_step(
                chunk, self.ts_col, self.size_ms, self.slide_ms,
                self.out_start, self.out_end,
            )
        ]

    def lint_info(self):
        import jax.numpy as jnp

        return {
            "requires": (self.ts_col,),
            "adds": {
                n: jnp.int64
                for n in (self.out_start, self.out_end) if n is not None
            },
            "watermark_map": {self.ts_col: self.out_start},
        }

    def pure_step(self):
        # the fused-chain contract (runtime/fused_step + epoch_batch):
        # a module-level partial with hashable bound args, so the hop expansion
        # traces into the fused per-barrier program and compiles once
        # per plan shape, not once per executor instance
        return partial(
            hop_step_fn,
            ts_col=self.ts_col,
            size_ms=self.size_ms,
            slide_ms=self.slide_ms,
            out_start=self.out_start,
            out_end=self.out_end,
        )

    def on_watermark(self, watermark):
        """Translate an event-time watermark into a window_start
        watermark: a future row (ts >= wm) lands only in windows with
        start >= first_start(wm)."""
        from risingwave_tpu.executors.base import Watermark

        if watermark.column != self.ts_col:
            return watermark, []
        first = ((watermark.value - self.size_ms) // self.slide_ms + 1) * self.slide_ms
        return Watermark(self.out_start, first), []
