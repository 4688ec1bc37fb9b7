"""End-to-end state integrity: digests, checksums, corruption faults.

The reference's Hummock checksums every SST block (xxhash64 in the
block footer, verified on every read) because LSM state written once
and read for weeks makes silent corruption permanent. This module is
that contract for the TPU port, three layers deep:

1. **Device digests** — an order-insensitive fold over an executor's
   durable state lanes (masked sum/XOR of per-slot uint32 hashes, so
   pow2-lattice padding and slot placement provably cancel out).
   Computed INSIDE the fused barrier programs (rides the existing
   staged int64 scalar lane — zero extra dispatches) and by a
   bit-identical numpy twin on the host, so fused-vs-interpreted
   bit-identity gets a per-barrier digest cross-check for free.
2. **Checksummed storage** — every SST blob/block and the manifest
   carry ``zlib.crc32`` content checksums written at build time and
   verified on every read path (see storage/state_table.py,
   storage/block_sst.py, storage/meta_backup.py).
3. **Quarantine + verified recovery** — a mismatch raises
   ``StateCorruption`` (a RuntimeError sibling of ``DeviceWedged``:
   deliberately NOT OSError/ValueError, so the resilience layer's
   transient-retry classifier never spins on a wrong byte), the
   artifact is copied aside under ``quarantine/`` (never deleted),
   and recovery walks back to the newest manifest whose
   checksum chain fully verifies.

Digest algorithm (the one contract both jax and numpy must honor):

- per lane, slots are split into little-endian uint32 words
  (``bitcast_convert_type`` on device, ``ndarray.view`` on host; bool
  and sub-4-byte ints promote via ``astype(uint32)`` first);
- a per-slot running hash ``h`` mixes the lane-name seed
  (``crc32(name)``) then every word column:
  ``h = (h ^ w) * 0x9E3779B1; h ^= h >> 15`` — strictly uint32
  (the RW-E302 rule: no 64-bit arithmetic in hash paths);
- lanes fold in sorted-name order, dead slots mask to 0, and the
  reduction is (wrapping uint32 sum, uint32 xor) packed as
  ``(sum << 32) | xor`` in one uint64 — commutative over slots, so
  the digest is invariant under rehash, growth and row order.
"""

from __future__ import annotations

import json
import os
import zlib
from typing import Dict, Iterable, Optional, Tuple

import numpy as np

GOLD = 0x9E3779B1  # 2**32 / golden ratio — Fibonacci-hash multiplier
MANIFEST_FORMAT = 2
QUARANTINE_PREFIX = "quarantine"
U64_MASK = (1 << 64) - 1


def digest_enabled() -> bool:
    """Manifest-level table digests are opt-in (``RW_STATE_DIGEST=1``):
    they re-read every table at commit (a whole-table device pull +
    store scan), which the truncated tier-1 window cannot afford by
    default. The fused digest LANES are always on — they ride the
    existing scalar read and cost zero extra dispatches."""
    v = os.environ.get("RW_STATE_DIGEST", "")
    return v.strip().lower() not in ("", "0", "off", "false")


class StateCorruption(RuntimeError):
    """A checksum or digest mismatch: the bytes parse but are WRONG.

    RuntimeError on purpose — ``CheckpointManager._read_transient``
    classifies ``(OSError, ValueError)`` as retryable store weather,
    and a wrong byte must never ride that loop (retrying corruption
    burns the budget and then misclassifies the fault). The artifact
    named here has already been copied to ``quarantine/`` when a store
    was at hand (forensics keep the evidence; recovery walks back)."""

    def __init__(
        self,
        artifact: str,
        kind: str,
        detail: str = "",
        expected=None,
        actual=None,
        quarantined: Optional[str] = None,
    ):
        self.artifact = artifact
        self.kind = kind
        self.detail = detail
        self.expected = expected
        self.actual = actual
        self.quarantined = quarantined
        msg = f"state corruption in {artifact!r} [{kind}]"
        if expected is not None or actual is not None:
            msg += f" expected={expected!r} actual={actual!r}"
        if detail:
            msg += f": {detail}"
        if quarantined:
            msg += f" (quarantined at {quarantined!r})"
        super().__init__(msg)


# ---------------------------------------------------------------------------
# corruption accounting
# ---------------------------------------------------------------------------

_COUNTS = {"corruptions": 0}


def corruption_count() -> int:
    return _COUNTS["corruptions"]


def note_corruption(exc: "StateCorruption") -> None:
    _COUNTS["corruptions"] += 1
    try:
        from risingwave_tpu.event_log import EVENT_LOG

        EVENT_LOG.record(
            "state_corruption",
            artifact=exc.artifact,
            fault=exc.kind,
            quarantined=exc.quarantined,
            detail=exc.detail[:200],
        )
        from risingwave_tpu.metrics import REGISTRY

        REGISTRY.counter("integrity_corruptions_total").inc(
            kind=exc.kind
        )
    except Exception:  # noqa: BLE001 — observability never masks the fault
        pass


# ---------------------------------------------------------------------------
# crc layer
# ---------------------------------------------------------------------------


def crc32_bytes(data: bytes) -> int:
    return zlib.crc32(data) & 0xFFFFFFFF


def verify_crc(
    data: bytes, expected: int, artifact: str, kind: str = "crc"
) -> None:
    """Verify ``data`` against a build-time crc; raise StateCorruption
    (NOT quarantined here — the caller owns the store handle)."""
    got = crc32_bytes(data)
    if got != (expected & 0xFFFFFFFF):
        raise StateCorruption(
            artifact, kind, expected=expected, actual=got
        )


def quarantine(store, path: str, data: Optional[bytes] = None) -> Optional[str]:
    """Copy the corrupt artifact aside for forensics — NEVER delete the
    original (walk-back recovery simply stops referencing it). Returns
    the quarantine path, or None when even the copy failed (a dead
    store must not turn detection into a crash)."""
    qpath = f"{QUARANTINE_PREFIX}/{path}"
    try:
        if data is None:
            data = store.read(path)
        store.put(qpath, data)
        return qpath
    except Exception:  # noqa: BLE001
        return None


def raise_corruption(
    store,
    artifact: str,
    kind: str,
    data: Optional[bytes] = None,
    detail: str = "",
    expected=None,
    actual=None,
):
    """Quarantine + event + raise, in one motion (the storage layer's
    single exit ramp for a detected wrong byte)."""
    q = quarantine(store, artifact, data) if store is not None else None
    exc = StateCorruption(
        artifact, kind, detail=detail, expected=expected, actual=actual,
        quarantined=q,
    )
    note_corruption(exc)
    raise exc


# ---------------------------------------------------------------------------
# manifest envelope (format 2): {"format": 2, "crc32": c, "payload": version}
# ---------------------------------------------------------------------------


def encode_manifest(version: dict) -> bytes:
    payload = json.dumps(version, sort_keys=True)
    return json.dumps(
        {
            "format": MANIFEST_FORMAT,
            "crc32": crc32_bytes(payload.encode()),
            "payload": version,
        }
    ).encode()


def decode_manifest(raw: bytes, artifact: str = "MANIFEST") -> dict:
    """Decode + verify a manifest blob. Raises StateCorruption on a
    torn tail (truncated JSON — the mid-write crash window) or a crc
    mismatch. A pre-envelope (format-1) manifest decodes as-is: those
    bytes predate the integrity layer and carry no checksum to hold
    them to."""
    try:
        doc = json.loads(raw.decode())
    except (ValueError, UnicodeDecodeError) as e:
        raise StateCorruption(
            artifact, "torn-manifest", detail=str(e)
        ) from None
    if (
        isinstance(doc, dict)
        and doc.get("format") == MANIFEST_FORMAT
        and "payload" in doc
    ):
        payload = doc["payload"]
        want = doc.get("crc32")
        got = crc32_bytes(json.dumps(payload, sort_keys=True).encode())
        if got != want:
            raise StateCorruption(
                artifact, "manifest-crc", expected=want, actual=got
            )
        return payload
    if isinstance(doc, dict) and not any(
        k in doc for k in ("format", "crc32", "payload")
    ):
        return doc  # legacy format-1: no envelope, no checksum
    # envelope fields present but the envelope does not verify as one:
    # a flipped bit in "format" or "payload" must not launder the blob
    # through the legacy path (the storm test's find)
    raise StateCorruption(
        artifact,
        "manifest-format",
        detail="envelope fields present but malformed",
    )


# ---------------------------------------------------------------------------
# digest fold — numpy twin (bit-identical to the jax fold below)
# ---------------------------------------------------------------------------


def lane_seed(name: str) -> int:
    return crc32_bytes(name.encode("utf-8"))


def _np_slot_words(arr: np.ndarray) -> np.ndarray:
    """(capacity, ...) lane -> (capacity, words) little-endian uint32
    view, matching XLA's bitcast_convert_type minor-dim word order."""
    a = np.ascontiguousarray(arr)
    n = a.shape[0] if a.ndim else 0
    if a.dtype == np.bool_ or a.dtype.itemsize < 4:
        a = a.astype(np.uint32)
    if a.ndim > 1:
        a = np.ascontiguousarray(a.reshape(n, -1))
    w = a.view(np.uint32)
    return w.reshape(n, -1)


def _np_mix(h: np.ndarray, w) -> np.ndarray:
    h = (h ^ w) * np.uint32(GOLD)
    return h ^ (h >> np.uint32(15))


def host_digest(lanes: Dict[str, np.ndarray], live=None) -> int:
    """The numpy fold: returns the packed ``(sum<<32)|xor`` digest as a
    python int in [0, 2**64). Bit-identical to ``device_digest``."""
    names = sorted(lanes)
    if not names:
        return 0
    first = np.asarray(lanes[names[0]])
    n = first.shape[0] if first.ndim else 0
    h = np.zeros(n, np.uint32)
    for name in names:
        h = _np_mix(h, np.uint32(lane_seed(name)))
        w = _np_slot_words(np.asarray(lanes[name]))
        for j in range(w.shape[1]):
            h = _np_mix(h, w[:, j])
    if live is not None:
        h = np.where(np.asarray(live, dtype=bool), h, np.uint32(0))
    s = int(h.astype(np.uint64).sum()) & 0xFFFFFFFF
    x = int(np.bitwise_xor.reduce(h)) if n else 0
    return (s << 32) | x


def host_rows_digest(
    keys: Dict[str, np.ndarray], values: Dict[str, np.ndarray]
) -> int:
    """Digest of a table's durable ROW IMAGE (what ``read_table``
    returns): the manifest-level digest. Order-insensitive over rows,
    so compaction/merge order cannot move it."""
    lanes = dict(keys)
    lanes.update(values)
    return host_digest(lanes, live=None)


# ---------------------------------------------------------------------------
# digest fold — jax twin (runs INSIDE the fused barrier programs)
# ---------------------------------------------------------------------------


def device_digest(lanes: dict, live=None):
    """The jax fold: same contract as ``host_digest``, returns a ()
    int64 scalar (the uint64 pack bitcast, so it rides the existing
    staged int64 scalar lane unchanged). Decode host-side with
    ``digest_from_scalar``."""
    import jax
    import jax.numpy as jnp

    names = sorted(lanes)
    if not names:
        return jnp.zeros((), jnp.int64)
    first = lanes[names[0]]
    n = first.shape[0] if first.ndim else 0

    def mix(h, w):
        h = (h ^ w) * jnp.uint32(GOLD)
        return h ^ (h >> jnp.uint32(15))

    h = jnp.zeros(n, jnp.uint32)
    for name in names:
        h = mix(h, jnp.uint32(lane_seed(name)))
        a = lanes[name]
        if a.dtype == jnp.bool_ or a.dtype.itemsize < 4:
            a = a.astype(jnp.uint32)
        if a.ndim > 1:
            a = a.reshape(n, -1)
        w = jax.lax.bitcast_convert_type(a, jnp.uint32)
        w = w.reshape(n, -1)
        for j in range(w.shape[1]):
            h = mix(h, w[:, j])
    if live is not None:
        h = jnp.where(live, h, jnp.uint32(0))
    s = jnp.sum(h, dtype=jnp.uint32)
    x = jax.lax.reduce(
        h, jnp.uint32(0), jax.lax.bitwise_xor, (0,)
    )
    packed = (s.astype(jnp.uint64) << jnp.uint64(32)) | x.astype(
        jnp.uint64
    )
    return jax.lax.bitcast_convert_type(packed, jnp.int64)


def digest_from_scalar(v) -> int:
    """Decode a staged int64 digest scalar back to the uint64 domain
    (the host fold's return type) for equality compares."""
    return int(v) & U64_MASK


# ---------------------------------------------------------------------------
# per-executor-kind lane builders — SHARED by the fused programs (jax
# arrays in, device_digest) and the host twins (device buffers viewed
# via np.asarray, host_digest). Coverage contract: DURABLE LOGICAL
# content only — bookkeeping lanes (dirty/sdirty/stored/latches) differ
# legitimately after a restore and are excluded by construction.
# ---------------------------------------------------------------------------


def agg_lanes(table, state) -> Tuple[dict, object]:
    """HashAgg: keys + row_count + accums + nonnull + emitted
    snapshots. Mask = live | emitted_valid (a zero-count group whose
    emitted snapshot still matters keeps its slot)."""
    lanes = {f"k{i}": k for i, k in enumerate(table.keys)}
    lanes["row_count"] = state.row_count
    for nm, a in state.accums.items():
        lanes[f"acc_{nm}"] = a
    for nm, a in state.nonnull.items():
        lanes[f"nn_{nm}"] = a
    for nm, a in state.emitted.items():
        lanes[f"em_{nm}"] = a
    for nm, a in state.emitted_isnull.items():
        lanes[f"ei_{nm}"] = a
    lanes["ev"] = state.emitted_valid
    return lanes, table.live | state.emitted_valid


def mv_lanes(table, state) -> Tuple[dict, object]:
    """Device MV: pk lanes + value lanes + null lanes, live rows."""
    lanes = {f"k{i}": k for i, k in enumerate(table.keys)}
    for nm, a in state.values.items():
        lanes[f"v_{nm}"] = a
    for nm, a in state.vnulls.items():
        lanes[f"n_{nm}"] = a
    return lanes, table.live


def dedup_lanes(table) -> Tuple[dict, object]:
    """Append-only dedup: the seen-set IS the state — just keys."""
    return {f"k{i}": k for i, k in enumerate(table.keys)}, table.live


def filter_lanes(table, maxes) -> Tuple[dict, object]:
    """DynamicMaxFilter: key lanes + per-key max."""
    lanes = {f"k{i}": k for i, k in enumerate(table.keys)}
    lanes["max"] = maxes
    return lanes, table.live


def join_side_lanes(side, where) -> Tuple[dict, object]:
    """One join side: keys + bucket payload rows + degrees, with
    bucket entries masked by ``row_valid`` BEFORE the fold (stale
    bytes in vacated bucket slots must not shift the digest). Pass
    ``jnp.where`` or ``np.where`` as ``where`` — the builder is
    backend-agnostic."""
    lanes = {f"k{i}": k for i, k in enumerate(side.table.keys)}
    rv = side.row_valid
    for nm, a in side.rows.items():
        zero = np.zeros((), np.asarray(a).dtype) if isinstance(
            a, np.ndarray
        ) else a.dtype.type(0)
        lanes[f"r_{nm}"] = where(rv, a, zero)
    for nm, a in side.row_nulls.items():
        lanes[f"rn_{nm}"] = where(rv, a, False)
    lanes["rv"] = rv
    lanes["deg"] = where(rv, side.degree, 0)
    return lanes, side.table.live


def host_obj_digest(obj) -> int:
    """Digest of an arbitrary host-side state object via its canonical
    JSON bytes (sort_keys, default=str). For executors whose state is
    python dicts/scalars rather than device lanes — deterministic, but
    NOT the lane fold (lint's RW-E709 accepts either contract)."""
    blob = json.dumps(obj, sort_keys=True, default=str).encode()
    c = crc32_bytes(blob)
    c2 = crc32_bytes(blob[::-1])
    return (c << 32) | c2


def foldable_dtypes(lanes: Dict[str, object]) -> Iterable[str]:
    """Names of lanes whose dtype the fold CANNOT cover (non-numeric,
    object arrays, ...) — the RW-E709 leaf check."""
    bad = []
    for name, a in lanes.items():
        kind = getattr(getattr(a, "dtype", None), "kind", "O")
        if kind not in ("b", "i", "u", "f"):
            bad.append(name)
    return bad
