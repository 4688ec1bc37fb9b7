"""risingwave_tpu — a TPU-native streaming-SQL dataflow framework.

A ground-up re-design of the capabilities of RisingWave (reference:
/root/reference, Rust) for TPU hardware via JAX/XLA/Pallas:

- Columnar ``StreamChunk`` batches (reference: src/common/src/array/
  stream_chunk.rs:98) become padded, fixed-capacity device arrays with
  validity + op masks so every operator compiles once under ``jax.jit``.
- Stateful streaming operators (HashAgg / HashJoin / TopN; reference:
  src/stream/src/executor/) are pure functions
  ``(state, chunk) -> (state', delta)`` over device-resident,
  open-addressing hash-table state in HBM.
- The epoch/barrier checkpoint model (reference: docs/checkpoint.md,
  src/meta/src/barrier/) is a host-driven step loop: a fragment is a
  jit-compiled per-epoch step function; a barrier is a step boundary at
  which state tables commit epoch deltas into a Hummock-style LSM
  (host <-> HBM staging).
- Parallelism is vnode hash partitioning (256 vnodes, reference:
  src/common/src/hash/consistent_hash/vnode.rs:54) mapped onto a
  ``jax.sharding.Mesh``: the hash exchange between fragments is an
  on-device all-to-all inside a ``shard_map``-ped step, riding ICI.
"""

__version__ = "0.2.0"

import jax as _jax

# SQL semantics demand real 64-bit integers (BIGINT ids in every Nexmark
# stream) and real f64 accumulation (SUM over DOUBLE). Without this flag
# jnp silently truncates int64 -> int32, which merges distinct group/join
# keys. XLA:TPU emulates 64-bit lanes with
# 32-bit pairs; the hot hash path bit-splits to u32 lanes up front, so
# only wide aggregation payloads pay the emulation cost.
#
# This is a process-global setting: importing risingwave_tpu opts the
# whole process into x64 (framework-style, like importing torch sets its
# global state). Embedders co-hosting other x32 JAX code should isolate
# processes; flipping the flag back off after import silently re-enables
# BIGINT truncation and is unsupported.
_jax.config.update("jax_enable_x64", True)

from risingwave_tpu.types import DataType, Field, Op, Schema
from risingwave_tpu.array.chunk import DataChunk, StreamChunk
from risingwave_tpu.array.dictionary import StringDictionary

__all__ = [
    "DataType",
    "Field",
    "Op",
    "Schema",
    "DataChunk",
    "StreamChunk",
    "StringDictionary",
    "__version__",
]
