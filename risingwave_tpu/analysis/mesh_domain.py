"""Abstract mesh domain for the mesh-readiness analyzer.

The SPMD-fusibility question (ROADMAP item 3) is a PLACEMENT question
layered on the shape question `shape_domain.py` already answers: a
sharded fragment's barrier collapses into ONE dispatch iff its step is
a single ``shard_map``-ed program over the mesh — state stacked along
the shard axis, rows crossing shards only through in-program
collectives (``lax.all_to_all``), and nothing about the program
depending on which shard runs it.  This module is the static twin of
that contract:

- ``ensure_virtual_devices()``: the lint CLI's mesh bootstrap.  The
  analyzer traces against a REAL ``Mesh`` of N virtual host devices
  (``xla_force_host_platform_device_count``) because the sharded
  executors build their stacked state against one; the flag only
  applies before the JAX backend initializes, so this either installs
  it in time or raises ``MeshUnavailable`` LOUDLY (exit 2 in the CLI)
  instead of tracing a 1-device mesh and proving nothing.
- stacked abstraction helpers: the executors' live state already
  carries the leading ``(n_shards, ...)`` axis, so its abstract twin
  is just ``ShapeDtypeStruct`` leaves of the same shape — no
  allocation, the `shape_domain.py` discipline.  Chunks get the
  leading axis added (``stacked_chunk``).
- ``mesh_trace_signature()``: the jaxpr fingerprint of one shard_map-
  ed step — in/out avals + primitives, with the COLLECTIVE primitives
  (the on-device exchange evidence) and host/transfer primitives (the
  anti-evidence) pulled out.  A positive SPMD proof requires at least
  the tracing to succeed and the program to be collective-clean or
  collective-only — host callbacks inside the mesh program are an
  immediate E901.
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass, field
from typing import Optional, Tuple

# NOTE: jax is imported lazily inside functions wherever the import
# could race the backend-init check (ensure_virtual_devices must run
# BEFORE anything touches jax.devices()).

DEFAULT_MESH_SHARDS = 8
MESH_AXIS = "shard"

_FLAG = "xla_force_host_platform_device_count"

# primitives that prove rows cross shards ON DEVICE (the collective
# exchange the scale-out arc wants); their presence inside a sharded
# step is positive evidence, not a blocker
COLLECTIVE_PRIMITIVES = frozenset(
    {
        "all_to_all",
        "all_gather",
        "psum",
        "psum2",  # shard_map's check_rep rewrite of psum
        "psum_invariant",  # the same rewrite's name since jax 0.9
        "pmax",
        "pmin",
        "ppermute",
        "reduce_scatter",
        "axis_index",
    }
)
# shard_map's rewrites of psum report as psum
_PSUM_ALIASES = {"psum2": "psum", "psum_invariant": "psum"}


class MeshUnavailable(RuntimeError):
    """The N-virtual-device mesh cannot be set up in this process
    (JAX backend already initialized without the device-count flag).
    The lint CLI maps this to exit code 2 — loud, never a silent
    1-device "proof"."""


def _jax_initialized() -> bool:
    """True iff a JAX backend has already been instantiated in this
    process — past that point ``xla_force_host_platform_device_count``
    is inert."""
    mod = sys.modules.get("jax._src.xla_bridge")
    if mod is None:
        return False
    backends = getattr(mod, "_backends", None)
    return bool(backends)


def ensure_virtual_devices(n: int = DEFAULT_MESH_SHARDS) -> None:
    """Make >= ``n`` host devices available, or raise MeshUnavailable.

    Idempotent: if the flag is already in XLA_FLAGS (conftest.py sets
    it for the test suite) or the initialized backend already exposes
    enough devices, this is a no-op check."""
    flags = os.environ.get("XLA_FLAGS", "")
    if _FLAG not in flags:
        if _jax_initialized():
            import jax

            have = len(jax.devices())
            if have >= n:
                return
            raise MeshUnavailable(
                f"JAX backend already initialized with {have} device(s); "
                f"--{_FLAG}={n} cannot apply anymore. Run "
                "`lint --mesh-report` in a fresh process (it sets the "
                "flag itself before touching JAX)."
            )
        os.environ["XLA_FLAGS"] = (flags + f" --{_FLAG}={n}").strip()
    import jax

    have = len(jax.devices())
    if have < n:
        raise MeshUnavailable(
            f"requested {n} virtual host devices but the backend "
            f"initialized with {have} — --{_FLAG} was present too late "
            "or another platform won. Run `lint --mesh-report` in a "
            "fresh process."
        )


def virtual_mesh(n: int = DEFAULT_MESH_SHARDS, axis: str = MESH_AXIS):
    """A real N-device mesh over the virtual host devices (the "sim
    mesh" the sharded Nexmark corpus builds against)."""
    ensure_virtual_devices(n)
    import jax
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:n]), (axis,))


def abstract_tree(tree):
    """A pytree's abstract twin: every array leaf becomes a
    ``ShapeDtypeStruct`` of the same shape/dtype (state is already
    stacked ``(n_shards, ...)`` in the sharded executors, so no axis
    surgery). Non-array leaves (ints, None) pass through."""
    import jax

    def leaf(a):
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is None or dtype is None:
            return a
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    return jax.tree.map(leaf, tree)


def stacked_chunk(spec, n: int):
    """A ``shape_domain.ChunkSpec`` as a stacked abstract StreamChunk:
    ``(n, capacity)`` ShapeDtypeStruct lanes — what a shard_map-ed
    step's chunk argument looks like from outside the mesh."""
    import jax
    import jax.numpy as jnp

    from risingwave_tpu.array.chunk import StreamChunk

    cap = spec.capacity
    sds = lambda dt: jax.ShapeDtypeStruct((n, cap), jnp.dtype(dt))
    return StreamChunk(
        columns={name: sds(dt) for name, dt in spec.columns},
        valid=sds(jnp.bool_),
        nulls={name: sds(jnp.bool_) for name in spec.nulls},
        ops=sds(jnp.int32),
    )


def stacked_schema_chunk(dtypes, nullable, cap: int, n: int):
    """A stacked abstract StreamChunk straight from a declared
    ``{name: dtype}`` schema — for executors whose input lanes are
    self-declared (e.g. a join side's arrival chunk) rather than
    threaded from the source spec."""
    import jax
    import jax.numpy as jnp

    from risingwave_tpu.array.chunk import StreamChunk

    sds = lambda dt: jax.ShapeDtypeStruct((n, cap), jnp.dtype(dt))
    return StreamChunk(
        columns={k: sds(dt) for k, dt in dtypes.items()},
        valid=sds(jnp.bool_),
        nulls={k: sds(jnp.bool_) for k in nullable},
        ops=sds(jnp.int32),
    )


@dataclass(frozen=True)
class MeshSignature:
    """Fingerprint of one abstract shard_map trace: jit-cache identity
    (in/out avals) + primitive census with the mesh-relevant classes
    pulled out."""

    in_avals: Tuple[str, ...]
    out_avals: Tuple[str, ...]
    primitives: Tuple[str, ...] = field(hash=False, default=())
    collectives: Tuple[str, ...] = ()
    host_calls: Tuple[str, ...] = ()
    transfers: Tuple[str, ...] = ()


def _fmt_aval(v) -> str:
    aval = getattr(v, "aval", v)
    shape = getattr(aval, "shape", ())
    dtype = getattr(aval, "dtype", "?")
    return f"{dtype}[{','.join(map(str, shape))}]"


def mesh_trace_signature(step, *abstract_args) -> MeshSignature:
    """Abstractly trace ``step(*abstract_args)`` (a shard_map-ed
    callable over ShapeDtypeStruct pytrees — no XLA, no allocation).
    Raises whatever tracing raises; TracerBoolConversionError &
    friends are the analyzer's E903 evidence."""
    import jax

    from risingwave_tpu.analysis.shape_domain import (
        HOST_PRIMITIVES,
        TRANSFER_PRIMITIVES,
    )

    jaxpr = jax.make_jaxpr(step)(*abstract_args)
    core = jaxpr.jaxpr
    prims: list = []
    colls: list = []
    hosts: list = []
    transfers: list = []

    def visit(j):
        for eqn in j.eqns:
            name = eqn.primitive.name
            prims.append(name)
            if name in COLLECTIVE_PRIMITIVES:
                colls.append(_PSUM_ALIASES.get(name, name))
            if name in HOST_PRIMITIVES:
                hosts.append(name)
            if name in TRANSFER_PRIMITIVES:
                transfers.append(name)
            for p in eqn.params.values():
                for q in p if isinstance(p, (tuple, list)) else (p,):
                    if hasattr(q, "eqns"):
                        visit(q)  # open Jaxpr (shard_map, while, scan)
                    elif hasattr(q, "jaxpr"):
                        visit(q.jaxpr)  # ClosedJaxpr (pjit, cond)

    visit(core)
    return MeshSignature(
        in_avals=tuple(_fmt_aval(v) for v in core.invars),
        out_avals=tuple(_fmt_aval(v) for v in core.outvars),
        primitives=tuple(prims),
        collectives=tuple(colls),
        host_calls=tuple(hosts),
        transfers=tuple(transfers),
    )


def mesh_buckets(chunk_caps: Optional[Tuple[int, ...]] = None):
    """The chunk-capacity lattice the mesh proof sweeps — the shared
    fusion lattice unless overridden (``RW_MESH_BUCKETS``)."""
    env = os.environ.get("RW_MESH_BUCKETS", "").strip()
    if env:
        try:
            caps = tuple(sorted({int(x) for x in env.split(",") if x.strip()}))
            if caps:
                return caps
        except ValueError:
            pass
    if chunk_caps:
        return tuple(chunk_caps)
    from risingwave_tpu.analysis.shape_domain import declared_buckets

    return declared_buckets()
