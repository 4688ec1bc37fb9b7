"""Vectorized hashing: compound keys and vnode partitioning.

Reference:
- src/common/src/hash/consistent_hash/vnode.rs:34,54-56 — 256 virtual
  nodes (``VirtualNode::BITS = 8``); a row maps to a vnode by hashing its
  distribution key; vnode -> worker via a mapping owned by the control
  plane (docs/consistent-hash.md).
- src/common/src/hash/key.rs — pre-serialized compound hash keys.

TPU re-design: keys are never serialized to bytes on device. A compound
key is a tuple of typed lanes; 64-bit columns are bit-split into (lo, hi)
uint32 lane pairs up front so the mixing chain itself runs entirely in
uint32 vector ops (VPU-friendly) while every key bit still reaches every
mix. The 64-bit reference hash (XxHash64) is replaced by two
independently-seeded 32-bit mixes when a wider fingerprint is needed
(see ``hash128``).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

VNODE_COUNT = 256  # parity with VirtualNode::COUNT (vnode.rs:54-56)


def _mix32(h: jnp.ndarray) -> jnp.ndarray:
    """fmix32 from murmur3 — avalanche finalizer on uint32 lanes."""
    h = h.astype(jnp.uint32)
    h ^= h >> 16
    h = h * jnp.uint32(0x85EBCA6B)
    h ^= h >> 13
    h = h * jnp.uint32(0xC2B2AE35)
    h ^= h >> 16
    return h


@jax.named_scope("x64/split")
def _split64(col: jnp.ndarray) -> list[jnp.ndarray]:
    """64-bit column -> (lo, hi) uint32 lanes via ONE bitcast.

    ``bitcast_convert_type`` to a narrower dtype appends a minor-most
    dim whose index 0 is the least-significant word — bit-identical to
    the old ``& 0xFFFFFFFF`` / ``>> 32`` split, but with ZERO 64-bit
    arithmetic: the hash chain stays valid under any ``jax_enable_x64``
    / platform promotion regime (rwlint RW-E302 guards this)."""
    bits = jax.lax.bitcast_convert_type(col, jnp.uint32)
    return [bits[..., 0], bits[..., 1]]


def _to_u32_lanes(col: jnp.ndarray) -> list[jnp.ndarray]:
    """Bit-cast any supported column dtype to one or more uint32 lane sets.

    64-bit columns yield BOTH halves as separate lanes (lo, hi) so the
    full 64 bits of the key flow into every downstream mix — folding to a
    single u32 would make the "independent" fingerprints of ``hash128``
    collide together for int64 ids, the most common key type in Nexmark.
    Everything downstream of this function is
    EXPLICITLY uint32: no 64-bit op may appear in the mixing chain.
    """
    if col.dtype == jnp.bool_:
        return [col.astype(jnp.uint32)]
    if col.dtype == jnp.float32:
        # canonicalize -0.0 to +0.0 and all NaN payloads to one NaN so
        # equal-under-total-order SQL values hash equally (the reference
        # uses ordered-float total ordering, src/common/src/types/)
        col = jnp.where(col == 0.0, jnp.float32(0.0), col)
        col = jnp.where(jnp.isnan(col), jnp.float32(jnp.nan), col)
        return [jax.lax.bitcast_convert_type(col, jnp.uint32)]
    if col.dtype == jnp.float64:
        col = jnp.where(col == 0.0, jnp.float64(0.0), col)
        col = jnp.where(jnp.isnan(col), jnp.float64(jnp.nan), col)
        return _split64(col)
    if col.dtype in (jnp.int64, jnp.uint64):
        return _split64(col)
    return [col.astype(jnp.uint32)]


def hash_columns(cols: Sequence[jnp.ndarray], seed: int = 0) -> jnp.ndarray:
    """Hash a compound key column-set to uint32, row-wise.

    Equivalent role to ``HashKey::hash`` over the distribution/group key
    (reference: src/common/src/hash/key.rs); boost-style hash_combine
    chains the per-lane mixes.
    """
    h = jnp.full(cols[0].shape, jnp.uint32(0x811C9DC5 ^ seed), dtype=jnp.uint32)
    for c in cols:
        for lanes in _to_u32_lanes(c):
            h = h ^ (_mix32(lanes) + jnp.uint32(0x9E3779B9) + (h << 6) + (h >> 2))
    return _mix32(h)


def hash128(cols: Sequence[jnp.ndarray]) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Two independent 32-bit hashes (fingerprint + probe seed)."""
    return hash_columns(cols, seed=0), hash_columns(cols, seed=0x5BD1E995)


def group_key_lanes(chunk, names: Sequence[str]) -> tuple[jnp.ndarray, ...]:
    """Key lanes for GROUP BY / distribution with SQL NULL semantics.

    SQL GROUP BY puts all NULLs in ONE group, distinct from every real
    value (reference: hash keys serialize a null tag before the datum,
    src/common/src/hash/key.rs). We realize that as: canonicalize the
    value lane to its zero where NULL (so NULL rows agree bit-for-bit)
    and append the bool null lane itself as an extra key lane (so the
    NULL group never merges with the real zero-valued group).

    The returned tuple plugs directly into hash_columns / hash128 and
    into HashTable key columns — exact-compare over these lanes IS
    SQL group-key equality.

    NOTE: equi-JOIN keys have different semantics (NULL matches nothing);
    join operators must pre-filter null-keyed rows instead.
    """
    lanes = []
    for name in names:
        col = chunk.col(name)
        if chunk.is_nullable(name):
            null = chunk.nulls[name]
            zero = jnp.zeros((), dtype=col.dtype)
            lanes.append(jnp.where(null, zero, col))
            lanes.append(null)
        else:
            lanes.append(col)
    return tuple(lanes)


def vnode_of(cols: Sequence[jnp.ndarray]) -> jnp.ndarray:
    """Row -> virtual node in [0, 256) (reference: vnode.rs:34,

    TableDistribution::compute_vnode, src/common/src/hash/table_distribution.rs).
    """
    return (hash_columns(cols, seed=0xC0FFEE) % VNODE_COUNT).astype(jnp.int32)
