"""Shared arrangements — one refcounted device index serving N MVs.

Reference: *Shared Arrangements* (PAPERS.md, arxiv 1812.02639) — in
timely/differential, operators PUBLISH their maintained indexes and
later queries ATTACH to the published arrangement instead of building
a private twin; the arrangement is refcounted and torn down when the
last reader departs. RisingWave realizes the same idea through
`CREATE INDEX` + delta joins (shared `IndexArrangement`s) but every
`CREATE MATERIALIZED VIEW` still builds private state.

TPU re-design: device state is the scarce resource (HBM) and — post
PR 10 — every private MV also means a private compiled program. This
module closes both gaps at the DDL boundary:

- at CREATE-MV time the session computes a **share-key fingerprint**
  over the statement's structural identity (normalized SELECT AST,
  input relation schemas + watermark specs, capacity / exec-mode /
  parallelism knobs, the bucket-lattice environment). A registry HIT
  attaches the new MV name to the existing refcounted arrangement:
  zero new executors, zero new HBM, zero new compiles — the 1000-MV
  registration storm costs O(distinct shapes), not O(MVs).
- one **writer** (the first MV's pipeline) owns all updates;
  **subscribers** read a per-barrier *published version*: an immutable
  snapshot pointer swapped at the barrier boundary, so a reader can
  never observe a mid-barrier torn state (the concurrent-stateful-
  streaming serving contract, arxiv 1904.03800). Readers that arrive
  mid-epoch get the last published version or a lock-held interim
  snapshot — consistent either way.
- refcounts drop on DROP MV; the arrangement frees (device state,
  fragment, actors) only at zero. Dropping the OWNER while
  subscribers live hands the fragment off to an internal name — the
  writer keeps streaming for its remaining readers.

Publish discipline (the <1%-of-barrier overhead contract): publishing
is a pointer swap; the snapshot itself materializes EAGERLY at the
barrier only while readers are active (`read_demand`), and LAZILY
under the runtime lock when the state provably still sits at the
barrier boundary (`write_gen` unchanged). With no readers the
steady-barrier cost is one attribute check per arrangement.

Checkpoint/restore need no new machinery: only the writer's executors
exist, so a shared arrangement stages ONCE (owner-tagged by its
table_ids) and a restore replaying the DDL log re-attaches every
subscriber to the same arrangement. Partial recovery's blast radius
for the owner fragment covers all subscribers by construction — they
have no fragments of their own, and `on_recovery` re-publishes off
the restored state so no reader serves rolled-back snapshots.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from risingwave_tpu.event_log import EVENT_LOG
from risingwave_tpu.metrics import REGISTRY

__all__ = [
    "Arrangement",
    "ArrangementRegistry",
    "DetachResult",
    "SharedArrangementReader",
    "plan_share_fingerprint",
    "shared_enabled",
]


def shared_enabled() -> bool:
    """RW_SHARED_ARRANGEMENTS=0 is the kill switch: every CREATE MV
    then builds private state (the pre-PR-12 behavior)."""
    return os.environ.get(
        "RW_SHARED_ARRANGEMENTS", "1"
    ).strip().lower() not in ("0", "off", "false")


# the bucket-lattice environment is part of the share key: two plans
# whose window-keyed state would bucket differently must NOT share one
# device index (the lattice IS the compiled shape family — PR 9)
_LATTICE_ENV = (
    "RW_BUCKET_MAX_STEPS",
    "RW_BUCKET_SHRINK_AT",
    "RW_BUCKET_SHRINK_PATIENCE",
)


def _lattice_env_sig() -> Tuple:
    return tuple((k, os.environ.get(k, "")) for k in _LATTICE_ENV)


def _referenced_relations(node, out: set) -> None:
    """Every relation name a SELECT reads (TableRef / WindowTVF /
    joins / subqueries — the parser AST is frozen dataclasses, so a
    generic field walk covers future node kinds too)."""
    import dataclasses as _dc

    from risingwave_tpu.sql import parser as P

    if isinstance(node, P.TableRef):
        out.add(node.name)
        return
    if _dc.is_dataclass(node) and not isinstance(node, type):
        for f in _dc.fields(node):
            _referenced_relations(getattr(node, f.name), out)
        return
    if isinstance(node, (tuple, list)):
        for v in node:
            _referenced_relations(v, out)


def plan_share_fingerprint(
    stmt,
    catalog,
    *,
    capacity: int,
    exec_mode: str,
    parallelism: int,
    session_token: int = 0,
) -> Optional[Tuple]:
    """The share key of one CREATE MATERIALIZED VIEW: structurally
    identical statements over identical input schemas produce EQUAL
    fingerprints (the parser AST is frozen dataclasses — value
    hashing is exact, including literal values: sharing requires
    identical results, not merely identical shapes).

    Conservative by design: a None means "do not share" (unknown
    relations, UNION ALL's separate execution path). ``session_token``
    scopes string-literal code assignment — two sessions' dictionaries
    may encode the same literal differently, so sharing never crosses
    a dictionary boundary."""
    from risingwave_tpu.sql import parser as P

    select = getattr(stmt, "select", stmt)
    if isinstance(select, P.UnionAll):
        return None
    rels: set = set()
    _referenced_relations(getattr(select, "from_", None), rels)
    _referenced_relations(getattr(select, "where", None), rels)
    _referenced_relations(tuple(getattr(select, "items", ())), rels)
    if not rels:
        return None
    schemas = []
    for r in sorted(rels):
        sch = catalog.tables.get(r)
        if sch is None:
            return None  # unknown relation: the normal path will raise
        schemas.append(
            (
                r,
                tuple(
                    (f.name, f.dtype.name, getattr(f, "scale", None))
                    for f in sch.fields
                ),
                catalog.watermarks.get(r),
                bool(catalog.is_mv(r)),
            )
        )
    try:
        return (
            "arr-v1",
            select,
            bool(getattr(stmt, "emit_on_window_close", False)),
            tuple(schemas),
            capacity,
            exec_mode,
            parallelism,
            bool(getattr(catalog, "enable_delta_join", False)),
            _lattice_env_sig(),
            session_token,
        )
    except TypeError:  # an unhashable AST corner: never share it
        return None


class _Version:
    """One published snapshot: immutable once materialized. ``cols``
    is None until someone needs it (lazy) or readers were active at
    publish time (eager); ``write_gen`` records the runtime's write
    counter at the barrier so a lazy materialization can PROVE the
    live state still sits exactly at this barrier boundary."""

    __slots__ = ("epoch", "cols", "write_gen")

    def __init__(self, epoch: Optional[int], cols, write_gen: int):
        self.epoch = epoch
        self.cols = cols
        self.write_gen = write_gen


class Arrangement:
    """One refcounted, barrier-versioned shared device arrangement."""

    def __init__(
        self,
        arr_id: int,
        fingerprint: Tuple,
        planned,
        schema,
        owner: str,
    ):
        self.id = arr_id
        self.fingerprint = fingerprint
        self.planned = planned  # the writer's PlannedMV (pipeline+mview)
        self.schema = schema  # catalog Schema of the MV's output
        self.owner = owner  # original owner MV name (provenance)
        # current runtime fragment names backing this arrangement
        # (owner fragment first, then lowered-join aux fragments);
        # renamed in place on an owner-drop handoff
        self.fragments: List[str] = [owner] + [
            sub.name for sub in getattr(planned, "aux", ())
        ]
        self.refs: set = {owner}
        self.version: Optional[_Version] = None
        self.stable: Optional[_Version] = None  # last MATERIALIZED one
        self.read_demand = False
        # reads since the last publish (fast-path included): while
        # readers are ACTIVE the publish materializes eagerly inside
        # the barrier, so steady serving never touches the runtime
        # lock — without this the demand flag would oscillate (only
        # lock-fallback reads set it) and every other barrier would
        # push readers back onto the lock
        self._reads_since_publish = 0
        self.hidden = False  # owner dropped, writer runs under alias

    @property
    def mview(self):
        return self.planned.mview

    @property
    def fragment(self) -> str:
        """The writer fragment's CURRENT runtime name."""
        return self.fragments[0]

    # -- publish / read ---------------------------------------------------
    def _snapshot_cols(self) -> Dict[str, np.ndarray]:
        return dict(self.mview.to_numpy())

    def publish(self, epoch: int, write_gen: int) -> None:
        """Swap in this barrier's version (caller holds the runtime
        lock via the barrier). Materializes only while readers are
        active — otherwise a pointer swap."""
        demand = self.read_demand or self._reads_since_publish > 0
        self._reads_since_publish = 0
        if demand:
            s = self.stable
            if s is not None and s.write_gen == write_gen:
                # nothing entered the runtime since the last snapshot:
                # republish the same (immutable) cols at the new epoch
                v = _Version(epoch, s.cols, write_gen)
                self.stable = v
                self.read_demand = False
                self.version = v
                return
            t0 = time.perf_counter()
            v = _Version(epoch, self._snapshot_cols(), write_gen)
            self.stable = v
            self.read_demand = False
            REGISTRY.histogram("arrangement_publish_ms").observe(
                (time.perf_counter() - t0) * 1e3, fragment=self.fragment
            )
        else:
            v = _Version(epoch, None, write_gen)
        self.version = v

    def read(self, runtime) -> Tuple[Optional[int], Dict[str, np.ndarray]]:
        """A snapshot-consistent read: never torn, labeled with the
        barrier epoch it corresponds to (None for a lock-held interim
        snapshot before the first barrier-aligned one exists)."""
        REGISTRY.counter("arrangement_shared_reads_total").inc()
        self._reads_since_publish += 1
        v = self.version
        if v is not None and v.cols is not None:
            return v.epoch, v.cols  # lock-free steady path
        with runtime.lock:
            v = self.version
            if v is not None and v.cols is not None:
                return v.epoch, v.cols
            self.read_demand = True  # the next publish materializes
            if v is not None and v.write_gen == runtime._write_gen:
                # nothing entered the runtime since the barrier: the
                # live state IS the published version — materialize it
                v.cols = self._snapshot_cols()
                self.stable = v
                return v.epoch, v.cols
            s = self.stable
            if s is not None:
                return s.epoch, s.cols
            # cold start under mid-epoch writes: a lock-held interim
            # snapshot (atomic, not barrier-aligned — epoch=None; not
            # cached as stable so barrier-aligned reads stay exact)
            return None, self._snapshot_cols()


class SharedArrangementReader:
    """The batch-engine facade bound to one subscriber MV name: every
    ``to_numpy()`` is a published-version read (lock-free once the
    version materialized), so `query()` never holds the runtime lock
    across the scan and never sees a torn mid-barrier state."""

    def __init__(self, registry: "ArrangementRegistry", name: str):
        self._registry = registry
        self._name = name

    @property
    def _arr(self) -> Arrangement:
        arr = self._registry._by_name.get(self._name)
        if arr is None:
            raise KeyError(
                f"shared arrangement for {self._name!r} is gone (dropped)"
            )
        return arr

    @property
    def pk(self):
        return self._arr.mview.pk

    @property
    def columns(self):
        return self._arr.mview.columns

    def read_versioned(self):
        """(epoch, cols) — the serving tier's labeled read."""
        return self._arr.read(self._registry.runtime)

    def to_numpy(self) -> Dict[str, np.ndarray]:
        _, cols = self.read_versioned()
        return dict(cols)

    def snapshot(self):
        """pk tuple -> value tuple, decoded off the published version
        (the host-map executors' interface, for backfill/probes)."""
        arr = self._arr
        cols = self.to_numpy()
        pk = tuple(arr.mview.pk)
        value_cols = tuple(arr.mview.columns)
        n = len(next(iter(cols.values()))) if cols else 0
        out = {}
        for i in range(n):
            k = tuple(np.asarray(cols[c])[i].item() for c in pk)
            v = tuple(
                None
                if f"{c}__null" in cols and bool(cols[f"{c}__null"][i])
                else np.asarray(cols[c])[i].item()
                for c in value_cols
            )
            out[k] = v
        return out


class DetachResult:
    """What a DROP of ``name`` means for its arrangement (the session
    finishes the catalog/runtime side per kind):

    - ``none``         not arrangement-tracked: normal drop path
    - ``owner_free``   owner dropped, no subscribers: normal drop path
                       (the arrangement record is already gone)
    - ``handoff``      owner dropped, subscribers live: the writer
                       fragment was renamed (``renames``) and keeps
                       running — do NOT unregister it
    - ``subscriber``   a subscriber dropped, others (or the owner)
                       remain: catalog cleanup only
    - ``subscriber_free`` the LAST reference dropped and it was a
                       subscriber: tear the hidden writer down
                       (``arrangement.fragments`` names)
    """

    __slots__ = ("kind", "arrangement", "renames")

    def __init__(self, kind: str, arrangement=None, renames=()):
        self.kind = kind
        self.arrangement = arrangement
        self.renames = tuple(renames)


class ArrangementRegistry:
    """Per-runtime registry: fingerprint -> arrangement, plus the MV
    name -> arrangement index for reads/drops. All mutation happens
    under the runtime lock (DDL path); ``publish`` runs inside the
    barrier; reads synchronize only through the version pointer."""

    def __init__(self, runtime):
        import weakref

        self._runtime_ref = weakref.ref(runtime)
        self._by_fp: Dict[Tuple, Arrangement] = {}
        self._by_name: Dict[str, Arrangement] = {}
        self._facades: Dict[str, SharedArrangementReader] = {}
        self._live: List[Arrangement] = []
        self._next_id = 0
        self._lock = threading.RLock()
        self.attaches = 0
        self.frees = 0
        # overload-ladder SHEDDING hook (runtime/memory_governor.py):
        # while set, publish is pointer-swap-only — eager in-barrier
        # materialization pauses, readers fall back to the lock path
        # (lazy per-demand snapshots / the last stable version: a
        # lagged-but-consistent view) and demand re-latches once the
        # ladder recovers below SHEDDING
        self.shed_eager = False

    @property
    def runtime(self):
        rt = self._runtime_ref()
        if rt is None:
            raise RuntimeError("runtime is gone")
        return rt

    @property
    def enabled(self) -> bool:
        return shared_enabled()

    # -- registration -----------------------------------------------------
    def lookup(self, fingerprint: Tuple) -> Optional[Arrangement]:
        arr = self._by_fp.get(fingerprint)
        if arr is None:
            return None
        # sanity: the writer fragment must still be live in the runtime
        if arr.fragment not in self.runtime.fragments:
            return None
        return arr

    def adopt(self, fingerprint: Tuple, planned, schema) -> Arrangement:
        """Record a freshly-registered MV as the owner of a (so far
        unshared) arrangement — the share target for later identical
        CREATEs."""
        with self._lock:
            stale = self._by_fp.get(fingerprint)
            if stale is not None:
                # a prior owner vanished without a session-level DROP
                # (direct runtime surgery): its record must not shadow
                # the new live arrangement
                self._forget(stale)
            self._next_id += 1
            arr = Arrangement(
                self._next_id, fingerprint, planned, schema, planned.name
            )
            self._by_fp[fingerprint] = arr
            self._by_name[planned.name] = arr
            self._live.append(arr)
            self._gauges()
            return arr

    def attach(self, arr: Arrangement, name: str) -> SharedArrangementReader:
        """Refcount++ and bind ``name`` to the arrangement's published
        versions. O(1): no executors, no state, no compiles."""
        with self._lock:
            arr.refs.add(name)
            self._by_name[name] = arr
            facade = SharedArrangementReader(self, name)
            self._facades[name] = facade
            arr.read_demand = True  # first publish must be readable
            self.attaches += 1
            REGISTRY.counter("arrangement_attaches_total").inc()
            self._gauges()
        EVENT_LOG.record(
            "arrangement_attach",
            name=name,
            owner=arr.owner,
            fragment=arr.fragment,
            refs=len(arr.refs),
        )
        return facade

    def reader(self, name: str) -> Optional[SharedArrangementReader]:
        return self._facades.get(name)

    def serves(self, name: str) -> bool:
        """True when ``name`` reads through a published-version facade
        (subscribers; owners keep their live locked read path)."""
        return name in self._facades

    def fragment_for(self, name: str) -> Optional[str]:
        """The runtime fragment actually backing an attached MV name
        (MV-on-shared-MV subscriptions route here)."""
        arr = self._by_name.get(name)
        if arr is None or name not in self._facades:
            return None
        return arr.fragment

    def refcount(self, name: str) -> int:
        arr = self._by_name.get(name)
        return len(arr.refs) if arr is not None else 0

    # -- teardown ---------------------------------------------------------
    def detach(self, name: str) -> DetachResult:
        """Refcount--; see DetachResult for what the caller must do."""
        with self._lock:
            arr = self._by_name.pop(name, None)
            if arr is None:
                return DetachResult("none")
            arr.refs.discard(name)
            was_subscriber = self._facades.pop(name, None) is not None
            if not arr.refs:
                self._forget(arr)
                return DetachResult(
                    "subscriber_free" if was_subscriber else "owner_free",
                    arrangement=arr,
                )
            if was_subscriber:
                self._gauges()
                return DetachResult("subscriber", arrangement=arr)
            # the OWNER name dropped with subscribers still attached:
            # hand the writer off to internal names so the user-visible
            # name frees up while the fragment keeps streaming
            renames = []
            rt = self.runtime
            for i, frag in enumerate(list(arr.fragments)):
                if frag not in rt.fragments:
                    continue  # already torn down out-of-band
                alias = f"__arr{arr.id}.{frag}"
                rt.rename_fragment(frag, alias)
                arr.fragments[i] = alias
                renames.append((frag, alias))
            arr.hidden = True
            self._gauges()
            EVENT_LOG.record(
                "arrangement_handoff",
                name=name,
                fragment=arr.fragment,
                refs=len(arr.refs),
            )
            return DetachResult("handoff", arrangement=arr, renames=renames)

    def _forget(self, arr: Arrangement) -> None:
        self._by_fp.pop(arr.fingerprint, None)
        if arr in self._live:
            self._live.remove(arr)
        for n in list(self._by_name):
            if self._by_name[n] is arr:
                del self._by_name[n]
        self.frees += 1
        REGISTRY.counter("arrangement_frees_total").inc()
        self._gauges()
        EVENT_LOG.record(
            "arrangement_free", owner=arr.owner, fragment=arr.fragment
        )

    def _gauges(self) -> None:
        REGISTRY.gauge("arrangements_live").set(float(len(self._live)))
        REGISTRY.gauge("arrangement_refs_total").set(
            float(sum(len(a.refs) for a in self._live))
        )

    # -- barrier / recovery hooks ----------------------------------------
    def publish(self, epoch: int) -> None:
        """Barrier-boundary version swap for every live arrangement
        (called from the runtime's trace finalization, under the
        barrier). Shared-reader overhead when nobody reads: one list
        walk of pointer swaps."""
        if not self._live:
            return
        rt = self._runtime_ref()
        if rt is None:
            return
        gen = rt._write_gen
        if self.shed_eager:
            # SHEDDING: no in-barrier materialization — swap the
            # version pointer only. Read demand stays latched in the
            # arrangement, so the first post-shed publish materializes
            # again for its readers.
            for arr in self._live:
                arr._reads_since_publish = 0
                arr.version = _Version(epoch, None, gen)
            return
        for arr in self._live:
            arr.publish(epoch, gen)

    def on_recovery(self, epoch: int) -> None:
        """State rolled back: stale published snapshots must not serve
        (they may postdate the restored epoch). Fresh versions
        materialize off the restored state at the next read/publish."""
        rt = self._runtime_ref()
        gen = rt._write_gen if rt is not None else 0
        for arr in self._live:
            arr.stable = None
            arr.version = _Version(epoch, None, gen)
            arr.read_demand = bool(
                len(arr.refs) > 1 or arr.hidden
            )

    # -- introspection ----------------------------------------------------
    def stats(self) -> Dict:
        with self._lock:
            return {
                "arrangements": len(self._live),
                "refs": sum(len(a.refs) for a in self._live),
                "shared": sum(
                    1
                    for a in self._live
                    if len(a.refs) > 1 or a.hidden
                ),
                "attaches": self.attaches,
                "frees": self.frees,
                "by_owner": {
                    a.owner: sorted(a.refs) for a in self._live
                },
            }
