"""Abstract shape domain for the fusion analyzer.

The device-fusibility question is at heart a SHAPE question: XLA
compiles one program per abstract input signature (shapes + dtypes),
so a fragment chain fuses into one per-barrier step iff every
executor's step is traceable AND its signature set over the chunk
sizes it will actually see is small and closed (array/chunk.py:
fixed-capacity chunks are the whole design).  This module is the
static twin of that contract:

- ``ChunkSpec``: an abstract StreamChunk — columns/dtypes/null lanes/
  capacity, no data.  ``abstract()`` materializes it as a pytree of
  ``jax.ShapeDtypeStruct`` leaves, which is what ``jax.eval_shape`` /
  ``jax.make_jaxpr`` need to trace an executor's step WITHOUT running
  it (and without allocating device memory).
- ``bucket_lattice()``: the declared chunk-size buckets.  The runtime
  quantizes chunk capacities (epoch batching pads the stacked axis to
  a power of two; hash_agg's flush emits exactly two capacities), so
  compiled-program counts are bounded by the lattice size — an
  executor is shape-stable iff tracing it at every bucket yields one
  jaxpr signature per bucket (RW-E803's proof obligation).
- ``trace_signature()``: the jaxpr fingerprint of one (step, spec)
  pair — primitive sequence + in/out avals.  Two buckets that
  fingerprint identically share a compiled program; the number of
  DISTINCT fingerprints across the lattice is the recompile bill a
  fused step would pay (RW-E805's budget).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from risingwave_tpu.array.chunk import StreamChunk

# default chunk-size bucket lattice: two pow2 capacities are enough to
# PROVE per-bucket signature stability (a data-dependent shape shows up
# as extra signatures at either bucket); override for wider sweeps
DEFAULT_BUCKETS = (1 << 8, 1 << 10)

# distinct jaxpr signatures one executor may contribute to a fused
# per-barrier step across the whole lattice before the analyzer calls
# it a recompile bill (RW-E805). A recompile is minutes cold on the
# TPU, so the budget is deliberately tight.
DEFAULT_RECOMPILE_BUDGET = 8


def declared_buckets() -> Tuple[int, ...]:
    """The lattice under analysis: ``RW_FUSION_BUCKETS`` (comma-
    separated capacities) or the default two-bucket pow2 probe."""
    env = os.environ.get("RW_FUSION_BUCKETS", "").strip()
    if not env:
        return DEFAULT_BUCKETS
    try:
        caps = tuple(
            sorted({int(x) for x in env.split(",") if x.strip()})
        )
    except ValueError:
        return DEFAULT_BUCKETS
    return caps or DEFAULT_BUCKETS


def recompile_budget() -> int:
    try:
        return int(
            os.environ.get(
                "RW_FUSION_RECOMPILE_BUDGET", DEFAULT_RECOMPILE_BUDGET
            )
        )
    except ValueError:
        return DEFAULT_RECOMPILE_BUDGET


@dataclass(frozen=True)
class ChunkSpec:
    """Abstract StreamChunk: (column name -> dtype), null-lane names,
    capacity. Dtypes are stored as strings so specs hash/compare."""

    columns: Tuple[Tuple[str, str], ...]
    nulls: Tuple[str, ...] = ()
    capacity: int = DEFAULT_BUCKETS[0]

    @staticmethod
    def from_schema(
        schema: Dict[str, object],
        capacity: int = DEFAULT_BUCKETS[0],
        nulls: Sequence[str] = (),
    ) -> Optional["ChunkSpec"]:
        """None when any dtype is unknown — the analyzer never guesses
        a lane width (a wrong dtype would trace a DIFFERENT program
        than the runtime compiles, proving nothing)."""
        cols = []
        for name in sorted(schema):
            dt = schema[name]
            if dt is None:
                return None
            try:
                cols.append((name, str(jnp.dtype(dt))))
            except TypeError:
                return None
        return ChunkSpec(tuple(cols), tuple(sorted(nulls)), capacity)

    def with_capacity(self, capacity: int) -> "ChunkSpec":
        return ChunkSpec(self.columns, self.nulls, capacity)

    def names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.columns)

    def abstract(self) -> StreamChunk:
        """The spec as a StreamChunk of ``ShapeDtypeStruct`` leaves —
        a valid pytree for eval_shape/make_jaxpr (StreamChunk's
        flatten/unflatten never looks at leaf values)."""
        cap = self.capacity
        sds = lambda dt: jax.ShapeDtypeStruct((cap,), jnp.dtype(dt))
        return StreamChunk(
            columns={n: sds(dt) for n, dt in self.columns},
            valid=sds(jnp.bool_),
            nulls={n: sds(jnp.bool_) for n in self.nulls},
            ops=sds(jnp.int32),
        )


def bucket_lattice(
    spec: ChunkSpec, buckets: Optional[Sequence[int]] = None
) -> Tuple[ChunkSpec, ...]:
    """The spec at every declared capacity bucket."""
    caps = tuple(buckets) if buckets is not None else declared_buckets()
    return tuple(spec.with_capacity(c) for c in caps)


def push_lattice_specs(
    spec: ChunkSpec, executors: Sequence[object]
) -> Tuple[ChunkSpec, ...]:
    """The spec at every width a chain of executors takes a host-built
    chunk built at ``spec.capacity`` lanes: the push lattice
    (``array/lattice.push_lattice``) where every executor declares
    it (``Executor.push_widths``), the full width alone where one does
    not. The same set ``StreamingRuntime.push`` cuts to: both read
    ``pipeline.chain_push_widths``."""
    # (deferred: the runtime package imports the analysis package)
    from risingwave_tpu.runtime.pipeline import chain_push_widths

    return bucket_lattice(spec, chain_push_widths(executors, spec.capacity))


def capacity_bucket(capacity: int) -> int:
    """Pow2 bucket of a concrete chunk capacity — the dynamic twin
    (SignatureWatch records this per hazard so runtime events
    cross-reference static RW-E803 findings)."""
    if capacity <= 1:
        return 1
    return 1 << (int(capacity) - 1).bit_length()


# primitives whose presence inside a traced step proves the step is
# NOT device-resident: the fused program would bounce through the host
# every barrier
HOST_PRIMITIVES = frozenset(
    {"pure_callback", "io_callback", "callback", "debug_callback"}
)
TRANSFER_PRIMITIVES = frozenset({"device_put"})


@dataclass(frozen=True)
class TraceSignature:
    """Fingerprint of one abstract trace: what the jit cache would key
    on (in/out avals) plus the primitive sequence (program identity)."""

    in_avals: Tuple[str, ...]
    out_avals: Tuple[str, ...]
    primitives: Tuple[str, ...] = field(hash=False, default=())
    host_calls: Tuple[str, ...] = ()
    transfers: Tuple[str, ...] = ()


def _fmt_aval(v) -> str:
    aval = getattr(v, "aval", v)
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", "?")
    return f"{dtype}[{','.join(map(str, shape))}]"


def trace_signature(step, spec: ChunkSpec) -> TraceSignature:
    """Abstractly trace ``step(chunk)`` at one bucket. Raises whatever
    tracing raises (TracerBoolConversionError & friends are the
    analyzer's evidence of Python branching on traced values)."""
    jaxpr = jax.make_jaxpr(step)(spec.abstract())
    core = jaxpr.jaxpr
    prims: list = []
    hosts: list = []
    transfers: list = []

    def visit(j):
        for eqn in j.eqns:
            name = eqn.primitive.name
            prims.append(name)
            if name in HOST_PRIMITIVES:
                hosts.append(name)
            if name in TRANSFER_PRIMITIVES:
                transfers.append(name)
            for p in eqn.params.values():
                sub = getattr(p, "jaxpr", None)
                if sub is not None:
                    visit(sub)
                elif isinstance(p, (tuple, list)):
                    for q in p:
                        if hasattr(q, "jaxpr"):
                            visit(q.jaxpr)

    visit(core)
    return TraceSignature(
        in_avals=tuple(_fmt_aval(v) for v in core.invars),
        out_avals=tuple(_fmt_aval(v) for v in core.outvars),
        primitives=tuple(prims),
        host_calls=tuple(hosts),
        transfers=tuple(transfers),
    )


def out_chunk_capacities(step, spec: ChunkSpec) -> Tuple[int, ...]:
    """Capacities of the StreamChunk outputs of ``step`` at one bucket
    (eval_shape only — the cheap query when the full jaxpr is not
    needed). Non-chunk outputs are ignored."""
    out = jax.eval_shape(step, spec.abstract())
    caps = []

    def walk(x):
        if isinstance(x, StreamChunk):
            caps.append(int(x.valid.shape[-1]))
        elif isinstance(x, (tuple, list)):
            for y in x:
                walk(y)

    walk(out)
    return tuple(caps)
