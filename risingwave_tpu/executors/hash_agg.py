"""HashAgg executor — grouped streaming aggregation with retraction.

Reference: src/stream/src/executor/hash_agg.rs:62 (675 LoC) +
executor/aggregation/{agg_group,agg_state}.rs. Semantics matched:
- apply_chunk (hash_agg.rs:326): every visible row updates its group by
  its retraction sign; groups are created on first touch;
- flush_data (hash_agg.rs:406): on barrier, each dirty group emits
  I / (U-,U+) / D against what downstream last saw;
- watermark-driven state cleaning of closed windows
  (state_table.rs:1133, iterator/skip_watermark.rs).

TPU re-design: the group map is ops/hash_table.HashTable (slots in
HBM); agg state is slot-indexed arrays (ops/agg.AggState). One fused
jit step does lookup-or-insert + masked scatter updates for a whole
chunk. The host only:
- tracks an insert upper bound to trigger pre-emptive RESIZE (the
  reference grows its heap maps freely; we rebuild into a 2x table and
  re-scatter state, reclaiming tombstones — the contract promised by
  ops/hash_table.py:121);
- reads one device flag per barrier to assert no row overflowed
  MAX_PROBE mid-epoch (cannot happen while load < 50%).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor, Watermark
from risingwave_tpu.storage.state_table import (
    Checkpointable,
    StateDelta,
    classify_marks,
    grow_pow2,
    host_key_view,
    lanes_from_host_keys,
    pull_rows,
)
from risingwave_tpu.ops import agg as agg_ops
from risingwave_tpu.ops import minput as mi_ops
from risingwave_tpu.ops.agg import AggCall, AggState
from risingwave_tpu.ops.hash_table import (
    PROBE_STATS,
    HashTable,
    lookup,
    lookup_or_insert,
    lookup_or_insert_counted,
    note_probes,
    set_live,
    stage_scalars,
)
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.array.lattice import (
    TOUCHED_MAX,
    flush_lattice,
    flush_lattice_pad,
    touched_lattice,
)
from risingwave_tpu.ops.bucketing import BucketAllocator, BucketPolicy
from risingwave_tpu.trace import device_read, span

GROW_AT = 0.5  # rehash when claimed slots may exceed this load factor
# mid-epoch rebuild only when the HOST insert bound nears the table
# itself (genuine MAX_PROBE overflow risk): padded upstream chunks
# (agg/join full-pad emissions) make the mid-epoch bound wildly
# pessimistic, so ordinary load-factor growth resolves at the barrier
# from the TRUE occupancy note instead. MAX_PROBE=64 keeps inserts
# safe well past this load.
HARD_GROW_AT = 0.75


def _build_key_lanes(
    chunk: StreamChunk, group_keys: Tuple[str, ...], nullable: Tuple[bool, ...]
):
    """Group-key lanes with SQL NULL-group semantics (one NULL group per
    key, distinct from the zero value — see ops/hashing.group_key_lanes).
    Nullability is DECLARED at executor build time so lane count/order is
    static even when a particular chunk carries no null lane."""
    lanes = []
    for name, nb in zip(group_keys, nullable):
        col = chunk.col(name)
        if nb:
            null = chunk.nulls.get(name)
            if null is None:
                null = jnp.zeros(chunk.capacity, jnp.bool_)
            lanes.append(jnp.where(null, jnp.zeros((), col.dtype), col))
            lanes.append(null)
        else:
            lanes.append(col)
    return tuple(lanes)


@jax.named_scope("agg/minput")
def _minput_pass(state, minput, mi_bad, calls, slots, signs, chunk):
    """Fold a row batch into every materialized MIN/MAX multiset and
    write each touched group's new extreme / live count back into the
    ordinary accumulator lanes (so flush is unchanged)."""
    cap = state.capacity
    for c in calls:
        if not c.materialized:
            continue
        v = chunk.col(c.input)
        notnull = ~chunk.nulls.get(c.input, jnp.zeros(v.shape, jnp.bool_))
        vals, cnt = minput[c.output]
        vals, cnt, rep_slots, extreme, total, ovf, inc = mi_ops.minput_apply(
            vals, cnt, slots, signs, v, notnull, c.kind
        )
        minput[c.output] = (vals, cnt)
        idx = jnp.where(rep_slots >= 0, rep_slots, cap)
        state.accums[c.output] = (
            state.accums[c.output].at[idx].set(extreme, mode="drop")
        )
        state.nonnull[c.output] = (
            state.nonnull[c.output].at[idx].set(total, mode="drop")
        )
        mi_bad = mi_bad | ovf | inc
    return state, minput, mi_bad


def agg_step_fn(
    table: HashTable,
    state: AggState,
    dropped: jnp.ndarray,
    chunk: StreamChunk,
    calls: Tuple[AggCall, ...],
    group_keys: Tuple[str, ...],
    nullable: Tuple[bool, ...],
    minput=None,
    mi_bad=None,
    touched=None,
    at=None,
):
    """One chunk through the group map + agg update (pure; jit it).

    With ``minput`` (materialized MIN/MAX multisets, ops/minput.py) the
    same dispatch also folds the batch into those and returns
    ``(table, state, dropped, minput, mi_bad)``; otherwise the classic
    3-tuple. With ``touched`` (the executor's list of the slots its
    steps wrote since the last flush; ``at`` its cursor) the chunk's
    slots are appended there and the list is handed back last."""
    keys = _build_key_lanes(chunk, group_keys, nullable)
    table, slots, _, _ = lookup_or_insert(table, keys, chunk.valid)
    signs = chunk.effective_signs()
    dropped = dropped | jnp.any(chunk.valid & (slots < 0))
    values = {c.input: chunk.col(c.input) for c in calls if c.input is not None}
    nulls = {
        c.input: chunk.nulls[c.input]
        for c in calls
        if c.input is not None and c.input in chunk.nulls
    }
    state = agg_ops.apply(state, calls, slots, signs, values, nulls)
    table = set_live(table, slots, state.row_count[slots] > 0)
    out = (table, state, dropped)
    if minput is not None:
        # (the pass writes the slots ``apply`` dirtied, no others)
        state, minput, mi_bad = _minput_pass(
            state, dict(minput), mi_bad, calls, slots, signs, chunk
        )
        out = (table, state, dropped, minput, mi_bad)
    if touched is not None:
        out += (agg_ops.note_touched(touched, at, slots),)
    return out


_agg_step = jax.jit(
    agg_step_fn,
    static_argnames=("calls", "group_keys", "nullable"),
    donate_argnums=(0, 1),
    donate_argnames=("touched",),
)


@partial(
    jax.jit,
    static_argnames=("calls", "group_keys", "nullable"),
    donate_argnums=(0, 1, 3, 4),
    donate_argnames=("touched",),
)
def _agg_step_mi(
    table, state, dropped, minput, mi_bad, chunk, calls, group_keys,
    nullable, touched=None, at=None,
):
    return agg_step_fn(
        table, state, dropped, chunk, calls, group_keys, nullable,
        minput, mi_bad, touched, at,
    )


@partial(
    jax.jit,
    static_argnames=("calls", "group_keys", "nullable", "pre"),
    donate_argnums=(0, 1),
)
def _agg_scan(
    table, state, dropped, stacked, calls, group_keys, nullable, pre
):
    """lax.scan over a (n_chunks, ...) stacked chunk batch — one fused
    device program per epoch (see HashAggExecutor.apply_stacked)."""

    def body(carry, chunk):
        table, state, dropped = carry
        if pre is not None:
            chunk = pre(chunk)
        table, state, dropped = agg_step_fn(
            table, state, dropped, chunk, calls, group_keys, nullable
        )
        return (table, state, dropped), None

    (table, state, dropped), _ = jax.lax.scan(
        body, (table, state, dropped), stacked
    )
    return table, state, dropped


def _epoch_reduced_fn(
    table, state, dropped, stacked, calls, group_keys, nullable, pre,
    minput=None, mi_bad=None, touched=None, at=None, probes=None,
):
    """The TPU-first epoch path: vmap the stateless prefix over the
    chunk axis, flatten the whole epoch into one row batch, pre-reduce
    by key (sort + segment combine, ops/agg.reduce_by_key), then touch
    the hash table ONCE per distinct key.

    Replaces the lax.scan of per-chunk probe loops: the scan serialized
    n_chunks × MAX_PROBE gather/scatter rounds, which real-TPU profiling
    (BENCH_r02 fault analysis) showed running 20-50x slower than the
    CPU actor. Commutativity across one epoch's rows makes the
    reordering exact (sum/count; append-only min/max latch retractions
    either way). ``touched`` / ``at`` as in ``agg_step_fn``: the
    distinct keys' slots, one lane a row of the batch. With ``probes``
    (the executor's running ``PROBE_STATS``) the one probe is the
    counted one, and what it did is added and handed back last."""
    if pre is not None:
        chunks = jax.vmap(pre)(stacked)
    else:
        chunks = stacked
    flat = jax.tree.map(
        lambda a: a.reshape((-1,) + a.shape[2:]), chunks
    )
    keys = _build_key_lanes(flat, group_keys, nullable)
    signs = flat.effective_signs()
    values = {c.input: flat.col(c.input) for c in calls if c.input is not None}
    nulls = {
        c.input: flat.nulls[c.input]
        for c in calls
        if c.input is not None and c.input in flat.nulls
    }
    sorted_keys, rep_valid, w, reduced, mret = agg_ops.reduce_by_key(
        keys, signs, calls, values, nulls
    )
    if probes is None:
        table, slots, _, _ = lookup_or_insert(table, sorted_keys, rep_valid)
    else:
        table, slots, _, _, stats = lookup_or_insert_counted(
            table, sorted_keys, rep_valid
        )
    dropped = dropped | jnp.any(rep_valid & (slots < 0))
    state = agg_ops.apply_reduced(
        state, calls, slots, rep_valid, w, reduced, mret
    )
    table = set_live(
        table,
        jnp.where(rep_valid, slots, -1),
        state.row_count[jnp.where(slots >= 0, slots, 0)] > 0,
    )
    out = (table, state, dropped)
    if minput is not None:
        # materialized MIN/MAX: re-probe (read-only) for EVERY flat
        # row's slot — the rep insert above guarantees hits — then fold
        # the raw rows into the multisets
        row_signs = flat.effective_signs()
        row_slots, _ = lookup(table, keys, flat.valid & (row_signs != 0))
        state, minput, mi_bad = _minput_pass(
            state, dict(minput), mi_bad, calls, row_slots, row_signs, flat
        )
        out = (table, state, dropped, minput, mi_bad)
    if touched is not None:
        out += (
            agg_ops.note_touched(
                touched, at, jnp.where(rep_valid, slots, -1)
            ),
        )
    if probes is not None:
        out += (probes + stats,)
    return out


_agg_epoch_reduced = partial(
    jax.jit,
    static_argnames=("calls", "group_keys", "nullable", "pre"),
    donate_argnums=(0, 1),
    donate_argnames=("touched",),
)(_epoch_reduced_fn)


@partial(
    jax.jit,
    static_argnames=("calls", "group_keys", "nullable", "pre"),
    donate_argnums=(0, 1, 8, 9),
    donate_argnames=("touched",),
)
def _agg_epoch_reduced_mi(
    table, state, dropped, stacked, calls, group_keys, nullable, pre,
    minput, mi_bad, touched=None, at=None, probes=None,
):
    return _epoch_reduced_fn(
        table, state, dropped, stacked, calls, group_keys, nullable, pre,
        minput, mi_bad, touched, at, probes,
    )


@partial(jax.jit, static_argnames=("pre",))
def _visible_rows(stacked, pre):
    """The rows of an epoch's batch that count (a sign of their own)
    once ``pre`` has run over it."""
    chunks = jax.vmap(pre)(stacked) if pre is not None else stacked
    flat = jax.tree.map(lambda a: a.reshape((-1,) + a.shape[2:]), chunks)
    return jnp.sum(flat.effective_signs() != 0, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("lanes",))
def _listed_rows_max(so_far, touched, at, visible, lanes: int):
    """``so_far`` or, if larger, the rows of the largest group among
    the ``lanes`` lanes ``_epoch_reduced_fn`` listed at ``at``: a
    distinct key stands at the first lane of its run in sort order and
    the ``visible`` rows come first, so its rows are the lanes up to
    the next listed one, or up to ``visible``."""
    listed = jax.lax.dynamic_slice(touched, (at,), (lanes,)) >= 0
    pos = jnp.arange(lanes, dtype=jnp.int32)
    nxt = jax.lax.cummin(jnp.where(listed, pos, lanes), reverse=True)
    end = jnp.minimum(
        jnp.concatenate([nxt[1:], jnp.full(1, lanes, jnp.int32)]), visible
    )
    return jnp.maximum(so_far, jnp.max(jnp.where(listed, end - pos, 0)))


@partial(jax.jit, static_argnames=("calls", "new_cap"))
def _rehash(
    table: HashTable,
    state: AggState,
    minput,
    calls: Tuple[AggCall, ...],
    new_cap: int,
):
    """Rebuild into a fresh (usually larger) table, dropping reclaimable
    tombstones, and re-scatter all slot-indexed state.

    A slot must survive iff it still matters to anyone:
      live (row_count>0) | emitted_valid (downstream saw it; a future
      delete must retract it) | dirty (unflushed change pending) |
      sdirty (unpersisted change — its KEY must survive so the next
      checkpoint can name the upsert/tombstone).
    """
    keep = table.live | state.emitted_valid | state.dirty | state.sdirty
    keep = keep & (table.fp1 != jnp.uint32(0))

    new_table = HashTable.create(new_cap, tuple(k.dtype for k in table.keys))
    new_table, new_slots, _, _ = lookup_or_insert(new_table, table.keys, keep)
    idx = jnp.where(keep, new_slots, new_cap)

    def rescatter(src, init):
        dst = jnp.full(new_cap, init, src.dtype)
        return dst.at[idx].set(src, mode="drop")

    new_table = set_live(new_table, jnp.where(keep, new_slots, -1), table.live)

    kinds = {c.output: c.kind for c in calls}
    accums = {
        n: rescatter(a, agg_ops.accum_init(kinds[n], a.dtype))
        for n, a in state.accums.items()
    }
    emitted = {n: rescatter(a, jnp.zeros((), a.dtype)) for n, a in state.emitted.items()}
    new_state = AggState(
        row_count=rescatter(state.row_count, jnp.zeros((), jnp.int64)),
        accums=accums,
        nonnull={
            n: rescatter(a, jnp.zeros((), jnp.int64))
            for n, a in state.nonnull.items()
        },
        emitted=emitted,
        emitted_isnull={
            n: rescatter(a, jnp.zeros((), jnp.bool_))
            for n, a in state.emitted_isnull.items()
        },
        emitted_valid=rescatter(state.emitted_valid, jnp.zeros((), jnp.bool_)),
        dirty=rescatter(state.dirty, jnp.zeros((), jnp.bool_)),
        minmax_retracted=state.minmax_retracted,
        sdirty=rescatter(state.sdirty, jnp.zeros((), jnp.bool_)),
        stored=rescatter(state.stored, jnp.zeros((), jnp.bool_)),
    )
    new_minput = {
        name: mi_ops.minput_rescatter(v, c, keep, new_slots, new_cap)
        for name, (v, c) in minput.items()
    }
    return new_table, new_state, new_minput


@partial(jax.jit, static_argnames=("calls", "new_cap"))
def _evict(
    table: HashTable,
    state: AggState,
    minput,
    calls: Tuple[AggCall, ...],
    new_cap: int,
):
    """Drop fully-durable groups from HBM (the LRU-eviction analogue —
    reference: stream executors spill via state-table LRU caches over
    Hummock, hash_agg.rs:49). A group is evictable iff the object store
    holds its exact state: stored & ~sdirty & ~dirty. Its key leaves
    the table entirely; if the group is touched again, the slot
    re-inserts fresh and the next barrier's cold-merge folds the
    durable state back in (see _merge_cold)."""
    hot = (
        (table.live | state.emitted_valid | state.dirty | state.sdirty)
        & (table.fp1 != jnp.uint32(0))
        & ~(state.stored & ~state.sdirty & ~state.dirty)
    )
    n_evicted = jnp.sum(
        ((table.live | state.emitted_valid) & ~hot).astype(jnp.int32)
    )
    new_table = HashTable.create(new_cap, tuple(k.dtype for k in table.keys))
    new_table, new_slots, _, _ = lookup_or_insert(new_table, table.keys, hot)
    idx = jnp.where(hot, new_slots, new_cap)

    def rescatter(src, init):
        dst = jnp.full(new_cap, init, src.dtype)
        return dst.at[idx].set(src, mode="drop")

    new_table = set_live(new_table, jnp.where(hot, new_slots, -1), table.live)
    kinds = {c.output: c.kind for c in calls}
    new_state = AggState(
        row_count=rescatter(state.row_count, jnp.zeros((), jnp.int64)),
        accums={
            n: rescatter(a, agg_ops.accum_init(kinds[n], a.dtype))
            for n, a in state.accums.items()
        },
        nonnull={
            n: rescatter(a, jnp.zeros((), jnp.int64))
            for n, a in state.nonnull.items()
        },
        emitted={
            n: rescatter(a, jnp.zeros((), a.dtype))
            for n, a in state.emitted.items()
        },
        emitted_isnull={
            n: rescatter(a, jnp.zeros((), jnp.bool_))
            for n, a in state.emitted_isnull.items()
        },
        emitted_valid=rescatter(state.emitted_valid, jnp.zeros((), jnp.bool_)),
        dirty=rescatter(state.dirty, jnp.zeros((), jnp.bool_)),
        minmax_retracted=state.minmax_retracted,
        sdirty=rescatter(state.sdirty, jnp.zeros((), jnp.bool_)),
        stored=rescatter(state.stored, jnp.zeros((), jnp.bool_)),
    )
    new_minput = {
        name: mi_ops.minput_rescatter(v, c, hot, new_slots, new_cap)
        for name, (v, c) in minput.items()
    }
    return new_table, new_state, new_minput, n_evicted


@partial(jax.jit, static_argnames=("calls", "key_index", "emit_deletes"))
def _expire(
    table: HashTable,
    state: AggState,
    cutoff: jnp.ndarray,
    calls: Tuple[AggCall, ...],
    key_index: int,
    emit_deletes: bool,
):
    """Close every live group whose window-key lane < cutoff."""
    lane = table.keys[key_index]
    expired = table.live & (lane < cutoff)
    slots = jnp.where(expired, jnp.arange(table.capacity, dtype=jnp.int32), -1)
    if emit_deletes:
        state = agg_ops.delete_groups(state, calls, slots)
    else:
        state = agg_ops.forget_groups(state, calls, slots)
    table = set_live(table, slots, False)
    return table, state


def delta_to_chunk(
    delta: dict,
    group_keys: Tuple[str, ...],
    nullable: Tuple[bool, ...],
    calls: Tuple[AggCall, ...],
    pad: Optional[int] = None,
) -> StreamChunk:
    """``agg_ops.flush`` delta dict -> StreamChunk, optionally sliced
    to ``pad`` lanes. The ONE decoder of the flush delta lane-naming
    contract (key{i} interleaving, ``<output>__isnull`` companions,
    ops/valid lanes): the interpreted ``_delta_to_chunk`` slicing and
    the fused per-barrier program's in-trace twin
    (runtime/fused_step._fused_barrier_fn) both call it, so the two
    paths cannot drift apart. Pure over jnp arrays — traceable."""
    sl = (lambda a: a[:pad]) if pad is not None else (lambda a: a)
    cols, nulls = {}, {}
    i = 0
    for name, nb in zip(group_keys, nullable):
        cols[name] = sl(delta[f"key{i}"])
        i += 1
        if nb:
            nulls[name] = sl(delta[f"key{i}"])
            i += 1
    for c in calls:
        cols[c.output] = sl(delta[c.output])
        lane = delta.get(c.output + "__isnull")
        if lane is not None:
            nulls[c.output] = sl(lane)
    return StreamChunk(
        columns=cols, valid=sl(delta["valid"]), nulls=nulls,
        ops=sl(delta["ops"]),
    )


class HashAggExecutor(Executor, Checkpointable):
    """Streaming GROUP BY.

    Args:
      group_keys: grouping column names (re-emitted on flush).
      calls: aggregate calls.
      schema_dtypes: input column name -> np/jnp dtype (for state init).
      capacity: initial group-table capacity (power of two; grows 2x).
      out_cap: max dirty groups emitted per flush round.
      nullable_keys: subset of group_keys that can carry SQL NULL.
      window_key: optional (column, retention_ms, emit_deletes) triple —
        on watermark wm for that column, groups with key < wm -
        retention are closed (state cleaned); with emit_deletes they
        are retracted downstream, otherwise finalized silently (EOWC).
    """

    def __init__(
        self,
        group_keys: Sequence[str],
        calls: Sequence[AggCall],
        schema_dtypes: Dict[str, object],
        capacity: int = 1 << 16,
        out_cap: int = 1 << 15,
        nullable_keys: Sequence[str] = (),
        window_key: Optional[Tuple[str, int, bool]] = None,
        table_id: str = "hash_agg",
        minput_k: int = 32,
    ):
        self.table_id = table_id
        self.group_keys = tuple(group_keys)
        self.calls = tuple(calls)
        self.out_cap = out_cap
        self._dtypes = dict(schema_dtypes)
        self.nullable = tuple(k in set(nullable_keys) for k in self.group_keys)
        key_dtypes = []
        for k, nb in zip(self.group_keys, self.nullable):
            key_dtypes.append(jnp.dtype(self._dtypes[k]))
            if nb:
                key_dtypes.append(jnp.dtype(jnp.bool_))
        self.table = HashTable.create(capacity, key_dtypes)
        self.state = agg_ops.create_state(capacity, self.calls, self._dtypes)
        self.dropped = jnp.zeros((), jnp.bool_)
        self._insert_bound = 0  # host-side upper bound of claimed slots
        self._occ_note = 0  # true claimed at the last barrier (staged read)
        # host-side upper bound of dirty (unflushed) groups: rows
        # absorbed since the last flush + conservatively the whole
        # table on a retracting expiry. Drives the fixed flush-round
        # count so the per-barrier flush needs ZERO device reads (the
        # old status-read loop was RW-E801 at the top of the fusion
        # worklist).
        self._dirty_bound = 0
        # the flush's list: the slots the steps wrote since the last
        # flush, appended on the device by the step programs
        # (agg_ops.note_touched) at the cursor the host keeps here, the
        # lanes they appended (every lane a step ranged over, so what
        # ``_dirty_bound`` counts). None = the list no longer names
        # every dirty slot (they moved, or something dirtied the table
        # wholesale): the next flush walks the table and starts it anew
        self._touched = jnp.full(TOUCHED_MAX, -1, jnp.int32)
        self._touched_lanes: Optional[int] = 0
        # the rows of the largest group a step of this epoch met, on
        # the device (``_note_group_rows``); None = some step did not say
        self._group_rows = jnp.zeros((), jnp.int32)
        # what the epoch-batched steps' probes did since the last flush
        # (``PROBE_STATS``, summed on the device and read with the
        # flush's first status) and how many such steps ran; what was
        # read waits for ``_on_barrier_scalars``, which knows ``claimed``
        self._probes = self._no_probes = jax.device_put(
            np.zeros(len(PROBE_STATS), np.int32)
        )
        self._probe_calls = 0
        self._probes_read = None
        # lanes a chunk holds after the traced-in prefix, per chunk shape
        self._lanes_after_pre: Dict[tuple, int] = {}
        # shape-stability: capacity walks the allocator's pow2 lattice;
        # growth decisions consume the occupancy note staged at the
        # previous barrier (see _maybe_grow) instead of a synchronous
        # device read
        self._buckets = BucketAllocator(
            BucketPolicy.from_capacity(capacity, grow_at=GROW_AT)
        )
        self.window_key = window_key
        self._float_extremes = agg_ops.float_extreme_meta(
            self.calls, {k: jnp.dtype(v) for k, v in self._dtypes.items()}
        )
        # materialized-input MIN/MAX multisets (minput.rs analogue)
        self.minput_k = minput_k
        self.minput = mi_ops.create_minput(
            capacity, minput_k, self.calls, self._dtypes
        )
        self.mi_bad = jnp.zeros((), jnp.bool_)
        # cold tier: set by the runtime to CheckpointManager.get_rows so
        # evicted (durable) groups fold back in on their next touch.
        # Assigning the ``cold_reader`` property binds the cold-tier
        # hooks below; while it is None the hot path (apply/on_barrier/
        # on_watermark) is provably host-sync free — the fault-in /
        # merge helpers with their NumPy fallbacks are unreachable, so
        # the fusion analyzer's AST scan of the hot methods holds for
        # exactly the configurations it analyzes.
        self._cold_reader = None
        self._cold_apply_hook = None  # _fault_in when armed
        self._cold_stacked_hook = None  # _fault_in_all when armed
        self._cold_barrier_hook = None  # _merge_cold when armed
        self._cold_expire_hook = None  # _expire_evicted when armed
        # with minput, merge-at-barrier cannot fold multisets back in
        # (a delete pre-merge would falsely latch inconsistent), so
        # evicted keys fault in ON TOUCH via this host-side set
        self._evicted: set = set()
        # whether ``evict_cold`` has dropped any group since this state
        # was built or restored. Only an eviction takes a key out of
        # the table and leaves its row in the store (a rebuild keeps
        # every group that is live, emitted, dirty or unpersisted; an
        # expiry tombstones what it closes; a restore brings every
        # durable group back resident), so until one has, the barrier's
        # merge has nothing to find and reads nothing (_merge_cold)
        self._has_evicted = False

    @property
    def cold_reader(self):
        return self._cold_reader

    @cold_reader.setter
    def cold_reader(self, fn) -> None:
        self._cold_reader = fn
        armed = fn is not None
        self._cold_apply_hook = self._fault_in if armed else None
        self._cold_stacked_hook = self._fault_in_all if armed else None
        self._cold_barrier_hook = self._merge_cold if armed else None
        self._cold_expire_hook = self._expire_evicted if armed else None

    def lint_info(self):
        emits = {k: self._dtypes.get(k) for k in self.group_keys}
        renames = {k: k for k in self.group_keys}
        requires = set(self.group_keys)
        for c in self.calls:
            if c.input is not None:
                requires.add(c.input)
            if c.kind in ("count", "count_star"):
                out_dt = jnp.int64
            elif c.kind in ("min", "max") and c.input in self._dtypes:
                out_dt = self._dtypes[c.input]
            else:
                out_dt = None  # sum/avg widen by kind-specific rules
            emits[c.output] = out_dt
            renames[c.output] = None
        return {
            "requires": tuple(sorted(requires)),
            "expects": {
                k: self._dtypes[k]
                for k in sorted(requires)
                if k in self._dtypes
            },
            "emits": emits,
            "renames": renames,
            "keys": self.group_keys,
            "table_ids": (self.table_id,),
            "window_key": self.window_key[0] if self.window_key else None,
        }

    def trace_contract(self):
        # the interpreted flush cuts every delta chunk to one of these
        # sizes (_delta_to_chunk) — they ARE the declared bucket
        # lattice that keeps the windowed agg shape-stable; the fused
        # programs use its two ends (lattice.flush_pad)
        caps = self.flush_sizes()
        contract = {
            "kind": "device",
            "trace_step": lambda c: _agg_step(
                self.table,
                self.state,
                self.dropped,
                c,
                self.calls,
                self.group_keys,
                self.nullable,
            ),
            "state": (self.table, self.state),
            "donate": True,
            "emission": "bucketed",
            "emission_caps": caps,
            "window_buckets": caps,
            # the lengths of the steps' list a flush program ranges over
            "flush_walks": self.touched_sizes(),
            # the interpreted flush pays one packed status read per
            # round; the fused per-barrier step compiles its own
            # device-side flush (runtime/fused_step._fused_barrier_fn)
            # and never calls this method — the analyzer scores its
            # syncs as fallback-only, outside the fusibility verdict
            "fallback_syncs": ("_flush_all",),
        }
        if self._cold_reader is not None:
            # the cold tier splices host-side fault-in/merge back into
            # the data path: an ARMED instance must be scanned honestly
            # (the corpus twins the analyzer proves are never armed)
            contract["hot_methods"] = (
                "_fault_in",
                "_fault_in_all",
                "_merge_cold",
                "_expire_evicted",
            )
        return contract

    def _round_cap(self) -> int:
        """Groups one flush round drains at most: ``out_cap``, or the
        whole table where that is smaller (the delta has twice as many
        lanes)."""
        return min(self.out_cap, self.table.capacity)

    def flush_sizes(self) -> Tuple[int, ...]:
        """The sizes a flush chunk of this aggregate can have."""
        return flush_lattice(self._round_cap())

    def touched_sizes(self) -> Tuple[int, ...]:
        """The lengths of the steps' list a flush can range over."""
        return touched_lattice(self.table.capacity)

    def pin_max_bucket(self):
        """ShapeGovernor hook: freeze the group table at its high-water
        bucket (shrink disabled; regrow applied by the next apply)."""
        return {
            "table_id": self.table_id,
            "pinned_cap": self._buckets.pin(),
        }

    def padding_stats(self):
        """Wasted-lane accounting (ops/bucketing.padding_stats —
        bench/PROFILE surface; reads device occupancy)."""
        import jax.numpy as jnp

        return {
            "capacity": self.table.capacity,
            "live": int(jnp.sum(self.table.live.astype(jnp.int32))),
        }

    # -- data ------------------------------------------------------------
    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        for k, nb in zip(self.group_keys, self.nullable):
            if not nb and k in chunk.nulls:
                raise ValueError(
                    f"group key {k!r} carries a null lane but was not "
                    "declared in nullable_keys"
                )
        if self._cold_apply_hook is not None:
            self._cold_apply_hook(chunk)
        self._maybe_grow(chunk.capacity)
        self._insert_bound += chunk.capacity
        self._dirty_bound += chunk.capacity
        at = self._touched_at(chunk.capacity)
        self._group_rows = None  # (a lane a row: no run to measure)
        # the step's enqueue (the device runs it asynchronously), as
        # actor.join_step is for a join
        with span("actor.agg_step", table_id=self.table_id):
            self._step(chunk, at)
        return []

    def _touched_at(self, lanes: int, advance: bool = True) -> int:
        """The list's cursor for a step over ``lanes`` lanes, moved past
        them (``advance``; the warm-up pass writes no slot and leaves
        it). A step the largest declared length has no room for gives
        the list up; its write then lands where nothing reads."""
        at = self._touched_lanes
        if at is None or at + lanes > self.touched_sizes()[-1]:
            self._touched_lanes = None
            return 0
        if advance:
            self._touched_lanes = at + lanes
        return at

    def _step(self, chunk: StreamChunk, at: int) -> None:
        if self.minput:
            (
                self.table,
                self.state,
                self.dropped,
                self.minput,
                self.mi_bad,
                self._touched,
            ) = _agg_step_mi(
                self.table,
                self.state,
                self.dropped,
                self.minput,
                self.mi_bad,
                chunk,
                self.calls,
                self.group_keys,
                self.nullable,
                touched=self._touched,
                at=at,
            )
        else:
            self.table, self.state, self.dropped, self._touched = _agg_step(
                self.table,
                self.state,
                self.dropped,
                chunk,
                self.calls,
                self.group_keys,
                self.nullable,
                touched=self._touched,
                at=at,
            )

    def apply_stacked(
        self, stacked: StreamChunk, pre=None, mode: str = "reduce"
    ) -> List[StreamChunk]:
        """Apply a whole BATCH of chunks in one device dispatch.

        ``stacked`` carries a leading (n_chunks,) axis on every lane
        (see array.chunk stacking). ``pre`` is an optional pure
        chunk->chunk function (e.g. the hop expansion) traced into the
        same program, fusing the upstream stateless operators.

        ``mode``:
          "reduce" (default): flatten the epoch, sort + segment-reduce
            by key, touch the table once per distinct key
            (_agg_epoch_reduced) — the fast path on real TPU;
          "scan": lax.scan of the per-chunk step (state as carry) —
            kept for differential testing and for plans that need
            strict intra-epoch chunk ordering.
        """
        if self._cold_stacked_hook is not None:
            # the epoch-batched path cannot see per-chunk keys before
            # the fused program runs (pre is traced in): restore every
            # evicted group up front — correct, if conservative
            self._cold_stacked_hook()
        lanes = self._stacked_lanes(stacked, pre)
        self._maybe_grow(lanes)
        self._insert_bound += lanes
        self._dirty_bound += lanes
        at = self._touched_at(lanes)
        with span(
            "actor.agg_step",
            table_id=self.table_id,
            chunks=int(stacked.valid.shape[0]),
        ):
            self._step_stacked(stacked, pre, mode, at)
            self._probe_calls += int(mode == "reduce")
            self._note_group_rows(stacked, pre, mode, at, lanes)
        return []

    def _note_group_rows(self, stacked, pre, mode, at: int, lanes: int):
        """The rows of the largest group of the batch just stepped,
        kept as the epoch's maximum on the device (``agg.flush`` reads
        it): ``_epoch_reduced_fn`` lists the batch's distinct keys at
        the first lane of their run in sort order, the rows that count
        first, so a group's rows are the lanes to the next listed one.
        Two small programs beside the step's own, which stays as it
        was. None where a step kept no such list (the per-chunk step,
        the scan, a list given up): the epoch then has no such count."""
        if self._group_rows is None:
            return
        if mode != "reduce" or self._touched_lanes is None:
            self._group_rows = None
            return
        self._group_rows = _listed_rows_max(
            self._group_rows, self._touched, at,
            _visible_rows(stacked, pre), lanes=lanes,
        )

    def _stacked_lanes(self, stacked: StreamChunk, pre) -> int:
        """Lanes the batch holds once ``pre`` has run (a hop multiplies
        them): the host's bound on what the step can insert. One
        abstract trace of ``pre`` a chunk shape, not one an epoch."""
        n_chunks, cap = stacked.valid.shape[:2]
        if pre is None:
            return n_chunks * cap
        key = (pre, jax.tree.structure(stacked), cap)
        lanes = self._lanes_after_pre.get(key)
        if lanes is None:
            probe = jax.eval_shape(
                pre,
                jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                    stacked,
                ),
            )
            lanes = self._lanes_after_pre[key] = probe.valid.shape[0]
        return n_chunks * lanes

    def _step_stacked(self, stacked, pre, mode, at: int) -> None:
        if self.minput:
            if mode != "reduce":
                raise ValueError(
                    "materialized MIN/MAX supports apply_stacked only in "
                    "'reduce' mode (use apply for per-chunk ordering)"
                )
            (
                self.table,
                self.state,
                self.dropped,
                self.minput,
                self.mi_bad,
                self._touched,
                self._probes,
            ) = _agg_epoch_reduced_mi(
                self.table,
                self.state,
                self.dropped,
                stacked,
                self.calls,
                self.group_keys,
                self.nullable,
                pre,
                self.minput,
                self.mi_bad,
                touched=self._touched,
                at=at,
                probes=self._probes,
            )
            return
        if mode != "reduce":
            # the scan keeps no list of what its chunks wrote
            self._touched_lanes = None
            self.table, self.state, self.dropped = _agg_scan(
                self.table,
                self.state,
                self.dropped,
                stacked,
                self.calls,
                self.group_keys,
                self.nullable,
                pre,
            )
            return
        (
            self.table, self.state, self.dropped, self._touched, self._probes
        ) = _agg_epoch_reduced(
            self.table,
            self.state,
            self.dropped,
            stacked,
            self.calls,
            self.group_keys,
            self.nullable,
            pre,
            touched=self._touched,
            at=at,
            probes=self._probes,
        )

    def _survivor_count(self):
        """Device scalar: what a rebuild keeps (live | emitted | dirty |
        sdirty — sdirty must count or pending-tombstone keys overflow
        the new table)."""
        return jnp.sum(
            (
                self.table.live
                | self.state.emitted_valid
                | self.state.dirty
                | self.state.sdirty
            ).astype(jnp.int32)
        )

    def _maybe_grow(self, incoming: int):
        """Capacity planning with ZERO device reads on the hot path.

        The old code refreshed the bound with a blocking
        ``read_scalars`` round-trip when the load-factor trigger
        tripped (~100ms on the TPU; RW-E801 ×2 at the top of
        the fusion worklist). Now ordinary growth resolves AT THE
        BARRIER from the staged occupancy note — the bucketing
        allocator's true claimed count (see ``_on_barrier_scalars``) —
        and the only mid-epoch rebuild is the overflow guard: when the
        host insert bound (note + inserts since, a true upper bound)
        nears the table itself, rebuild pessimistically BEFORE the
        MAX_PROBE latch can trip. Padded upstream chunks overstate the
        bound, so the guard threshold is deliberately high; one epoch
        of margin in the NEED sizing makes the rebuild converge in one
        step, and the barrier-note lazy shrink reclaims overshoot."""
        cap = self.table.capacity
        # occupancy can never exceed the table: clamp the carried
        # bound so padded upstream chunks cannot accrete an unbounded
        # bound across chunks and ratchet growth step after step (the
        # caller adds this chunk's incoming after we return)
        self._insert_bound = min(self._insert_bound, cap)
        if self._insert_bound + incoming <= cap * HARD_GROW_AT:
            return
        claimed = self._insert_bound
        # no extra margin: the 0.75 guard vs 0.5 sizing gap IS the
        # hysteresis, so the guard cannot re-trip right after a rebuild
        new_cap = self._buckets.plan(cap, incoming, claimed, claimed)
        if new_cap is not None and new_cap != cap:
            self._rebuild(new_cap)
            self._insert_bound = min(claimed, new_cap)

    def _rebuild(self, new_cap: int) -> None:
        """Rehash into a table of ``new_cap`` slots."""
        self.table, self.state, self.minput = _rehash(
            self.table, self.state, self.minput, self.calls, new_cap
        )
        self._slots_moved()

    def _slots_moved(self) -> None:
        """The table was rebuilt: a list that names any slot names it
        wrongly from here on (an empty one stays as good as it was)."""
        if self._touched_lanes:
            self._touched_lanes = None

    # -- control ---------------------------------------------------------
    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        # STAGE the packed latch+occupancy read (async D2H) and defer
        # the blocking materialization to finish_barrier — every
        # executor's transfer is then in flight concurrently, so a
        # chain pays ~one device round-trip per barrier, with
        # values sampled at this executor's position of the walk
        # (staged AFTER the flush, which changes none of them: the
        # latches are monotonic and flush never claims slots).
        # NOTE: with a tripped latch the flush below still emits and
        # pollutes downstream IN-PROCESS state before finish_barrier
        # raises — covered by the existing contract that any barrier
        # error requires recover() (runtime.py module docstring); the
        # epoch is never checkpointed and sinks never deliver it
        # (SinkExecutor delivery also lives in finish_barrier).
        if self._cold_barrier_hook is not None:
            self._cold_barrier_hook()
        outs = self._flush_all()
        self._staged_scalars = stage_scalars(
            self.dropped,
            self.state.minmax_retracted,
            self.mi_bad,
            self.table.occupancy(),
            # distinct values the materialized MIN/MAX calls hold
            sum(
                (jnp.sum(cnt > 0) for _, cnt in self.minput.values()),
                jnp.zeros((), jnp.int64),
            ),
        )
        if barrier is None:  # direct drive: checks fire inline
            self.finish_barrier()
        return outs

    def _on_barrier_scalars(self, vals) -> None:
        # (the fused program hands its four latches and no count)
        dropped, mret, mi_bad, claimed, *held = vals
        if self.minput and held:
            REGISTRY.gauge("minput_values").set(
                held[0], table_id=self.table_id
            )
            if mi_bad:
                REGISTRY.counter("minput_overflows_total").inc(
                    table_id=self.table_id
                )
        # occupancy refreshes _insert_bound so the NEXT epoch's
        # _maybe_grow decides without any round-trip (the allocator's
        # occupancy note), and feeds the lazy-shrink streak
        epoch_inc = max(self._insert_bound - self._occ_note, 0)
        self._occ_note = int(claimed)
        if self._probes_read is not None:
            (calls, did), self._probes_read = self._probes_read, None
            note_probes(
                "agg", self.table_id, calls, did, self.table.capacity,
                claimed=int(claimed),
            )
        self._insert_bound = int(claimed)
        self._plan_at_barrier(int(claimed), epoch_inc)
        if dropped:
            raise RuntimeError(
                "hash table overflowed MAX_PROBE mid-epoch; grow capacity"
            )
        if mret:
            # the append-only MIN/MAX kernel cannot undo a retraction;
            # emitting would be silently wrong (agg.py latches the flag
            # for exactly this host-side rejection; the reference instead
            # keeps sorted per-group input state, minput.rs)
            raise RuntimeError(
                "row-level retraction hit an append-only MIN/MAX aggregate; "
                "set AggCall(materialized=True) for materialized-input "
                "extremes"
            )
        if mi_bad:
            raise RuntimeError(
                "materialized MIN/MAX state overflowed minput_k distinct "
                "values per group, or a value was retracted that was never "
                "inserted"
            )

    def _plan_at_barrier(self, claimed: int, epoch_inc: int) -> None:
        """Barrier-boundary capacity planning from the TRUE occupancy
        note: grow past the load factor, apply the allocator's pending
        lazy shrink, honor a governor pin — all between epochs, zero
        mid-epoch device reads. The margin keeps both growth and the
        shrink's regrow guard honest against next epoch's volume (the
        larger of true occupancy and the last epoch's insert bound),
        so a shrink can never land below what the mid-epoch overflow
        guard would immediately regrow."""
        cap = self.table.capacity
        self._buckets.note_barrier(cap, claimed)
        new_cap = self._buckets.plan(
            cap, 0, claimed, claimed, margin=max(claimed, epoch_inc)
        )
        if new_cap is not None and new_cap != cap:
            self._rebuild(new_cap)

    # -- cold tier (state >> HBM) -----------------------------------------
    def state_nbytes(self) -> int:
        """Device bytes held (host-side estimate; no sync)."""
        return sum(
            leaf.nbytes
            for leaf in jax.tree.leaves((self.table, self.state, self.minput))
        )

    def evict_cold(self) -> int:
        """Free every fully-durable group from HBM (LRU-spill analogue;
        see _evict). Returns groups evicted. Requires a cold_reader so
        evicted groups can come back."""
        if self.cold_reader is None:
            raise RuntimeError("evict_cold needs a cold_reader (runtime)")
        if self.minput:
            # multisets cannot cold-MERGE (a pre-merge delete would
            # falsely latch inconsistent): record the evicted keys so
            # they fault back in ON TOUCH, state-exact, before any
            # post-eviction row lands on the group
            claimed = np.asarray(self.table.fp1) != 0
            durable = np.asarray(
                self.state.stored & ~self.state.sdirty & ~self.state.dirty
            )
            sel = np.flatnonzero(claimed & durable)
            if len(sel):
                pulled = pull_rows(
                    {
                        f"k{i}": l
                        for i, l in enumerate(self.table.keys)
                    },
                    sel,
                )
                views = [
                    host_key_view(np.asarray(pulled[f"k{i}"]))
                    for i in range(len(self.table.keys))
                ]
                for j in range(len(sel)):
                    self._evicted.add(
                        tuple(int(a[j]) for a in views)
                    )
        # shrink to fit the surviving hot set — eviction must actually
        # free HBM, not just slots
        hot = (
            (
                self.table.live
                | self.state.emitted_valid
                | self.state.dirty
                | self.state.sdirty
            )
            & (self.table.fp1 != jnp.uint32(0))
            & ~(self.state.stored & ~self.state.sdirty & ~self.state.dirty)
        )
        n_hot = int(jnp.sum(hot.astype(jnp.int32)))
        new_cap = grow_pow2(n_hot, 1 << 10, GROW_AT)
        self.table, self.state, self.minput, n = _evict(
            self.table, self.state, self.minput, self.calls, new_cap
        )
        n = int(n)
        if n:
            self._has_evicted = True
        self._insert_bound = int(self.table.occupancy())
        self._slots_moved()
        return n

    # -- fault-in on touch (the minput-compatible cold path) -------------
    def _chunk_key_tuples(self, chunk: StreamChunk) -> set:
        """Canonical host tuples of the chunk's group keys, in the
        table's key-lane layout (value [+ null flag] per key)."""
        valid = np.asarray(chunk.valid)
        sel = np.flatnonzero(valid)
        views = []
        for k, nb in zip(self.group_keys, self.nullable):
            a = np.asarray(chunk.col(k))
            if nb:
                nl = (
                    np.asarray(chunk.nulls[k])
                    if k in chunk.nulls
                    else np.zeros(len(a), bool)
                )
                a = np.where(nl, np.zeros((), a.dtype), a)
                views.append(host_key_view(a))
                views.append(nl.astype(np.int64))
            else:
                views.append(host_key_view(a))
        return {tuple(int(v[i]) for v in views) for i in sel}

    def _fault_in(self, chunk: StreamChunk) -> None:
        if not self._evicted:
            return  # nothing evicted: never pull the chunk to host
        hits = self._chunk_key_tuples(chunk) & self._evicted
        if hits:
            self._restore_cold_groups(sorted(hits))

    def _fault_in_all(self) -> None:
        if self._evicted:
            self._restore_cold_groups(sorted(self._evicted))

    def _restore_cold_groups(self, key_tuples) -> None:
        """State-exact restore of evicted groups BEFORE any new row
        lands on them (merge-at-barrier cannot fold minput multisets:
        a pre-merge delete would falsely latch inconsistent)."""
        dtypes = [k.dtype for k in self.table.keys]
        lanes_np = lanes_from_host_keys(key_tuples, dtypes)
        found, vals = self.cold_reader(lanes_np)
        self._evicted.difference_update(key_tuples)
        nt = int(found.sum())
        if not nt:
            return
        self._maybe_grow(nt)
        self._insert_bound += nt
        # groups come back outside any step, and the growth above may
        # have moved every slot: the next flush walks the table
        self._touched_lanes = None
        key_lanes = tuple(
            jnp.asarray(lanes_np[f"k{i}"][found])
            for i in range(len(dtypes))
        )
        cold = {k: jnp.asarray(np.asarray(v)[found]) for k, v in vals.items()}
        self.table, self.state, self.minput, ovf = _fault_in_scatter(
            self.table, self.state, self.minput, key_lanes, cold,
            self.calls,
        )
        self.dropped = self.dropped | ovf

    def _merge_cold(self, land=None) -> int:
        """Fold durable state into groups (re)created since the last
        checkpoint: candidates are sdirty & ~stored; a cold-store hit
        means the key was evicted earlier and its persisted accumulators
        must combine with what accrued since (merge-on-return; the
        reference reloads through its state-table cache instead).

        A hit takes an eviction (``_has_evicted``). Until there has
        been one this returns at once: no lane is copied, no key
        pulled, the store is not asked, and the barrier's flush is
        enqueued behind the epoch's steps with nothing read before it.
        ``land``: a fused barrier still holds the epoch's rows when it
        calls here, and steps and flushes in one program; where the
        merge runs it has them stepped first (the slots it folds into
        are theirs), where it does not they stay one program.

        The span ``agg.merge_cold`` (table_id; barrier = 1, ran = 1
        where the merge ran, 0 where it was skipped; candidates = groups
        new since the last checkpoint, found = those the cold store
        held), at every barrier of an aggregate over a store, and the
        counter ``agg_cold_merge_total{table_id, outcome}`` (``ran`` |
        ``skipped``). Where it runs it reads the candidate lane off the
        device, waiting out the epoch's steps, and looks every
        candidate's key up in the store."""
        ran = self._has_evicted
        REGISTRY.counter("agg_cold_merge_total").inc(
            table_id=self.table_id, outcome="ran" if ran else "skipped"
        )
        with span(
            "agg.merge_cold",
            table_id=self.table_id,
            barrier=1,
            ran=int(ran),
            candidates=0,
            found=0,
        ) as sp:
            if not ran:
                return 0
            if land is not None:
                land()
            cand = self.state.sdirty & ~self.state.stored
            with device_read("agg.merge_cold", lanes=cand.shape[0]):
                cand = np.asarray(cand)
            sel = np.flatnonzero(cand)
            sp.args["candidates"] = len(sel)
            if not len(sel):
                return 0
            lanes = {f"k{i}": lane for i, lane in enumerate(self.table.keys)}
            keys = pull_rows(lanes, sel)
            with span("agg.cold_lookup", keys=len(sel)):
                found, vals = self.cold_reader(keys)
            if not found.any():
                return 0
            sp.args["found"] = int(found.sum())
            hit = sel[found]
            cold = {k: v[found] for k, v in vals.items()}
            self.state = _cold_merge(
                self.state, jnp.asarray(hit.astype(np.int32)),
                {k: jnp.asarray(v) for k, v in cold.items()},
                self.calls,
            )
            # merged slots are dirtied, by no step: the bound now passes
            # the list's lanes, and the flush walks the table (_flush_walk)
            self._dirty_bound += int(found.sum())
            # liveness may have flipped (e.g. deletes landed on a fresh
            # slot before the merge restored the cold row_count)
            slots = jnp.asarray(hit.astype(np.int32))
            self.table = set_live(
                self.table, slots, self.state.row_count[slots] > 0
            )
            return int(found.sum())

    def flush_rounds(self) -> int:
        """Upper bound of flush rounds this barrier needs, from the
        HOST dirty bound (each round drains up to out_cap dirty
        groups). The fused per-barrier step compiles this many rounds
        into its program — zero device reads; a trailing round on an
        over-estimate emits an all-invalid chunk, a no-op downstream."""
        bound = min(self._dirty_bound, self.table.capacity)
        return max(1, -(-bound // self.out_cap))

    def _flush_all(self) -> List[StreamChunk]:
        """INTERPRETED-path flush: delta chunks cut to the declared
        lattice (``_delta_to_chunk``), one packed status read per
        round. The fused step replaces this whole method with
        device-side delta extraction (its program flushes, slices by
        the host dirty bound and feeds the device MV without any host
        read) — the contract declares it under ``fallback_syncs`` so
        the fusion analyzer scores the read as fallback-only, not a
        fusibility blocker. Interpreted consumers (joins, host
        materializers) pay for every lane they are handed, so the
        slice follows the exact count the read brings anyway: the
        span's rows / lanes is the filled share of what is handed on
        (rows = the 2 lanes a drained group, its U-/U+ pair; a group's
        first emission leaves its U- lane masked).

        Every round ranges over the steps' list where it names every
        dirty slot (``_flush_walk``), else over the table: the span's
        ``path`` / ``walked``; ``table_round`` over ``round`` is the
        share of rounds that walked a table."""
        outs = []
        walk = self._flush_walk()
        listed = {} if walk is None else dict(
            touched=self._touched, n_touched=self._touched_lanes, walk=walk
        )
        path = "table" if walk is None else "touched"
        # the rows of the epoch's largest group, where every step said
        # (``_note_group_rows``): it comes with the first round's status,
        # in the one read
        rows_max, self._group_rows = (
            self._group_rows, jnp.zeros((), jnp.int32)
        )
        # and what the epoch's counted probes did, where any ran
        calls, self._probe_calls = self._probe_calls, 0
        probes = None
        if calls:
            probes, self._probes = self._probes, self._no_probes
        while True:
            with span(
                "agg.flush",
                table_id=self.table_id,
                path=path,
                walked=self.table.capacity if walk is None else walk,
                round=1,
                table_round=int(walk is None),
            ) as sp:
                self.state, delta = agg_ops.flush(
                    self.state,
                    self.table.keys,
                    self.out_cap,
                    self._float_extremes,
                    **listed,
                )
                with device_read(
                    "agg.flush.status",
                    lanes=2 + (0 if probes is None else probes.size),
                ):
                    status, largest, did = jax.device_get(
                        (delta["status"], rows_max, probes)
                    )
                if did is not None:
                    self._probes_read, probes = (calls, did), None
                n_take, overflow = status.tolist()
                chunk = self._delta_to_chunk(delta, n_take)
                sp.args.update(rows=2 * n_take, lanes=chunk.capacity)
                if largest is not None:
                    rows_max = None
                    sp.args.update(group_rows_max=largest.tolist())
                    REGISTRY.gauge("agg_group_rows_max").set(
                        largest.tolist(), table_id=self.table_id
                    )
            REGISTRY.counter("agg_flush_rounds_total").inc(
                table_id=self.table_id, path=path
            )
            REGISTRY.counter("agg_flush_chunks_total").inc(
                table_id=self.table_id, lanes=str(chunk.capacity)
            )
            REGISTRY.counter("agg_flush_rows_total").inc(
                2 * n_take, table_id=self.table_id
            )
            outs.append(chunk)
            if not overflow:
                break
        # nothing is dirty: the bound and the list start over
        self._dirty_bound = 0
        self._touched_lanes = 0
        return outs

    def _flush_walk(self) -> Optional[int]:
        """The declared length of the steps' list this barrier's flush
        ranges over, or None where it has to walk the table: the list
        was given up (``_touched_lanes`` None), or the host's dirty
        bound counts lanes no step listed (a cold merge, a fused
        program that stepped this state, a retracting expiry)."""
        lanes = self._touched_lanes
        if lanes is None or lanes != self._dirty_bound:
            return None
        return next((s for s in self.touched_sizes() if s >= lanes), None)

    # -- the lattice, before it is met -----------------------------------
    def warm_emissions(self) -> List[StreamChunk]:
        """One all-invalid delta chunk of every lattice size, for the
        actor's warm-up pass. A flush of a state with nothing dirty:
        it drains no group and snapshots nothing, and the chunks are
        what ``_flush_all`` would hand on, lane for lane."""
        if self._dirty_bound:
            raise RuntimeError(
                f"{self.table_id}: warm-up after rows arrived"
            )
        self.state, delta = agg_ops.flush(
            self.state, self.table.keys, self.out_cap, self._float_extremes
        )
        # and the flush over the steps' list, of every declared length
        for walk in self.touched_sizes():
            self.state, _ = agg_ops.flush(
                self.state,
                self.table.keys,
                self.out_cap,
                self._float_extremes,
                touched=self._touched,
                n_touched=0,
                walk=walk,
            )
        return [
            delta_to_chunk(
                delta, self.group_keys, self.nullable, self.calls, pad
            )
            for pad in self.flush_sizes()
        ]

    def warm(self, chunk: StreamChunk) -> List[StreamChunk]:
        """``apply`` for the warm-up pass: the step's program over a
        chunk with no valid row, which claims no slot and dirties no
        group; no host bound moves and nothing grows."""
        if self._would_grow(chunk.capacity):
            return []
        self._step(chunk, self._touched_at(chunk.capacity, advance=False))
        return []

    def warm_stacked(self, stacked: StreamChunk, pre, mode) -> None:
        """``apply_stacked`` likewise (the epoch-batched path)."""
        lanes = self._stacked_lanes(stacked, pre)
        if self._would_grow(lanes):
            return
        self._step_stacked(
            stacked, pre, mode, self._touched_at(lanes, advance=False)
        )
        if mode == "reduce":
            _listed_rows_max(
                jnp.zeros((), jnp.int32), self._touched, 0,
                _visible_rows(stacked, pre), lanes=lanes,
            )

    def _would_grow(self, incoming: int) -> bool:
        """Whether ``_maybe_grow`` would rebuild the table before a
        chunk of ``incoming`` lanes (its bound counts lanes, masked or
        not): the step then never runs at this capacity, so the
        warm-up does not compile it here."""
        return (
            self._insert_bound + incoming
            > self.table.capacity * HARD_GROW_AT
        )

    def cleaning_watermarks(self):
        """[(table_id, storage key name, cutoff)] — consumed by the
        runtime at checkpoint (skip-watermark compaction)."""
        wm = getattr(self, "_cleaning_watermark", None)
        return [(self.table_id, wm[0], wm[1])] if wm else []

    def _expire_evicted(self, watermark: Watermark) -> None:
        """A cold-evicted group past the cutoff must still close —
        fault expiring groups back in so the normal expiry path
        retracts/tombstones them (the join's analogue; expiry is rare,
        the fault-in cost is fine). Reached only through the cold-tier
        hook: the unarmed hot path never touches this host code."""
        if not self._evicted:
            return
        colname, retention, _emit = self.window_key
        ki = self._key_lane_index(colname)
        cut = int(watermark.value) - retention
        dt = np.dtype(self.table.keys[ki].dtype)
        if dt.kind == "f":
            # evicted tuples hold host_key_view bit patterns:
            # compare in the numeric domain (hash_join does the
            # same in _expire_evicted)
            itype = np.int32 if dt.itemsize == 4 else np.int64
            conv = lambda x: float(np.array(x, itype).view(dt))
        else:
            conv = lambda x: x
        expiring = [t for t in self._evicted if conv(t[ki]) < cut]
        if expiring:
            self._restore_cold_groups(sorted(expiring))

    def on_watermark(self, watermark: Watermark):
        if self.window_key is None or watermark.column != self.window_key[0]:
            return watermark, []
        colname, retention, emit_deletes = self.window_key
        if self._cold_expire_hook is not None:
            self._cold_expire_hook(watermark)
        outs: List[StreamChunk] = []
        if not emit_deletes:
            # EOWC finalization silently frees state — any dirty (not yet
            # flushed) updates on expiring groups must reach downstream
            # FIRST or they'd be lost (code-review r2 finding #1).
            outs = self._flush_all()
        cutoff = jnp.asarray(watermark.value - retention, dtype=jnp.int64)
        key_index = self._key_lane_index(colname)
        # storage-side skip-watermark cleaning (state_table.rs:1133):
        # the runtime forwards this to the checkpoint manager so
        # compaction drops expired keys from durable SSTs — the EOWC
        # path (emit_deletes=False) frees device state WITHOUT
        # tombstones, and only this watermark reclaims its storage
        self._cleaning_watermark = (
            f"k{key_index}",
            int(watermark.value) - retention,
        )
        if self.minput:
            lane = self.table.keys[key_index]
            expired = self.table.live & (lane < cutoff)
            slots = jnp.where(
                expired, jnp.arange(self.table.capacity, dtype=jnp.int32), -1
            )
            self.minput = {
                name: mi_ops.minput_clear(v, c, slots)
                for name, (v, c) in self.minput.items()
            }
        if emit_deletes:
            # retracting expiry can dirty up to every live group; the
            # host cannot count them without a sync — bound by capacity
            # (flush_rounds clamps there anyway), and no list names them
            self._dirty_bound = self.table.capacity
            self._touched_lanes = None
        self.table, self.state = _expire(
            self.table, self.state, cutoff, self.calls, key_index, emit_deletes
        )
        return watermark, outs

    # -- helpers ---------------------------------------------------------
    def _key_lane_index(self, name: str) -> int:
        """Index of a group key's VALUE lane in the table's key tuple
        (null lanes of earlier nullable keys shift later lanes)."""
        i = 0
        for k, nb in zip(self.group_keys, self.nullable):
            if k == name:
                return i
            i += 2 if nb else 1
        raise KeyError(name)

    def _delta_to_chunk(self, delta, n_take: Optional[int] = None) -> StreamChunk:
        if n_take is None:
            pad = None
        else:
            # every emitted row sits in the first 2*n_take slots (dirty
            # slots compact to the front); slice before handing on, to
            # the smallest size of the declared lattice that holds
            # them (lattice.flush_lattice: 256, a quarter, the full 2*out_cap).
            # Every DOWNSTREAM device program (join step, aggregate
            # step, MV) compiles once per input capacity and walks
            # every lane of it, masked or not: a bare pow2 of the
            # count compiled on first sight of each size, the old
            # {256, full} pair made 5,000 rows cost what 32,768 do.
            # The lattice is closed, declared (trace_contract) and
            # compiled when the view is created (the actor's
            # warm_flush_lattice), so neither happens.
            pad = flush_lattice_pad(self._round_cap(), n_take)
        return delta_to_chunk(
            delta, self.group_keys, self.nullable, self.calls, pad
        )


@partial(jax.jit, static_argnames=("calls",), donate_argnums=(0, 1, 2))
def _fault_in_scatter(table, state, minput, key_lanes, cold, calls):
    """Insert evicted keys back and scatter their FULL durable state
    (accums + emitted snapshots + minput multisets) — byte-identical to
    the pre-eviction slot, before any post-eviction row touches it."""
    n = key_lanes[0].shape[0]
    table, slots, _, _ = lookup_or_insert(
        table, key_lanes, jnp.ones(n, jnp.bool_)
    )
    overflow = jnp.any(slots < 0)
    idx = jnp.where(slots >= 0, slots, table.capacity)
    rc = cold["row_count"].astype(state.row_count.dtype)

    def put(a, lane, cast=True):
        v = cold[lane]
        return a.at[idx].set(
            v.astype(a.dtype) if cast else v, mode="drop"
        )

    new_state = AggState(
        row_count=state.row_count.at[idx].set(rc, mode="drop"),
        accums={
            nm: put(a, f"acc_{nm}") for nm, a in state.accums.items()
        },
        nonnull={
            nm: put(a, f"nn_{nm}") for nm, a in state.nonnull.items()
        },
        emitted={
            nm: put(a, f"em_{nm}") for nm, a in state.emitted.items()
        },
        emitted_isnull={
            nm: put(a, f"ei_{nm}")
            for nm, a in state.emitted_isnull.items()
        },
        emitted_valid=put(state.emitted_valid, "ev"),
        dirty=state.dirty,  # restored groups carry no unflushed change
        minmax_retracted=state.minmax_retracted,
        sdirty=state.sdirty,
        stored=state.stored.at[idx].set(True, mode="drop"),
    )
    table = set_live(table, jnp.where(slots >= 0, slots, -1), rc > 0)
    new_minput = {
        name: (
            v.at[idx].set(
                cold[f"miv_{name}"].astype(v.dtype), mode="drop"
            ),
            c.at[idx].set(
                cold[f"mic_{name}"].astype(c.dtype), mode="drop"
            ),
        )
        for name, (v, c) in minput.items()
    }
    return table, new_state, new_minput, overflow


@partial(jax.jit, static_argnames=("calls",), donate_argnums=(0,))
def _cold_merge(state: AggState, slots, cold, calls):
    """Combine persisted group state into freshly-recreated slots.
    Additive kinds add; extremes min/max in the raw (order-key) lane
    domain; emitted snapshots REPLACE (the fresh slot never emitted)."""
    idx = slots
    row_count = state.row_count.at[idx].add(cold["row_count"])
    accums = dict(state.accums)
    nonnull = dict(state.nonnull)
    for c in calls:
        acc = accums[c.output]
        cv = cold[f"acc_{c.output}"].astype(acc.dtype)
        if c.kind in ("count_star", "count", "sum"):
            accums[c.output] = acc.at[idx].add(cv)
        elif c.kind == "min":
            accums[c.output] = acc.at[idx].min(cv)
        else:
            accums[c.output] = acc.at[idx].max(cv)
        if c.output in nonnull:
            nonnull[c.output] = nonnull[c.output].at[idx].add(
                cold[f"nn_{c.output}"]
            )
    emitted = {
        n: a.at[idx].set(cold[f"em_{n}"].astype(a.dtype))
        for n, a in state.emitted.items()
    }
    emitted_isnull = {
        n: a.at[idx].set(cold[f"ei_{n}"])
        for n, a in state.emitted_isnull.items()
    }
    return AggState(
        row_count=row_count,
        accums=accums,
        nonnull=nonnull,
        emitted=emitted,
        emitted_isnull=emitted_isnull,
        emitted_valid=state.emitted_valid.at[idx].set(cold["ev"]),
        dirty=state.dirty.at[idx].set(True),
        minmax_retracted=state.minmax_retracted,
        sdirty=state.sdirty.at[idx].set(True),
        stored=state.stored.at[idx].set(True),
    )


# -- checkpoint/restore (StateTable integration) -------------------------
def _agg_checkpoint_delta(self) -> List[StateDelta]:
    """Stage rows changed since the last checkpoint (device -> host).

    upsert  = sdirty & alive        (new/changed group state)
    tombstone = sdirty & stored & dead  (a persisted group died)
    Rows carry the FULL slot state (accums + emitted snapshots), so
    restore rebuilds byte-identical operator state. Only the selected
    rows cross the device boundary (pull_rows).
    """
    marks = classify_marks(
        self.state.sdirty,
        (self.table.live, self.state.emitted_valid, self.state.dirty),
        self.state.stored,
    )
    # eager flip — see StateDelta's durability contract
    self.state = dataclasses.replace(
        self.state, sdirty=marks.sdirty, stored=marks.stored
    )
    if not len(marks):
        return []
    lanes = {
        f"k{i}": lane for i, lane in enumerate(self.table.keys)
    }
    key_names = tuple(lanes)
    lanes["row_count"] = self.state.row_count
    for n, a in self.state.accums.items():
        lanes[f"acc_{n}"] = a
        lanes[f"em_{n}"] = self.state.emitted[n]
    for n, a in self.state.nonnull.items():
        lanes[f"nn_{n}"] = a
        lanes[f"ei_{n}"] = self.state.emitted_isnull[n]
    for n, (v, c) in self.minput.items():
        lanes[f"miv_{n}"] = v  # 2D (rows re-land whole)
        lanes[f"mic_{n}"] = c
    lanes["ev"] = self.state.emitted_valid
    pulled = pull_rows(lanes, marks)
    keys = {k: pulled[k] for k in key_names}
    vals = {k: v for k, v in pulled.items() if k not in key_names}
    return [
        StateDelta(
            self.table_id,
            keys,
            vals,
            marks.tombstone,
            # positional lane order, NOT sorted() ("k10" < "k2" lexically)
            key_names,
        )
    ]


def build_restored_agg(
    cap: int,
    calls,
    dtypes,
    key_dtypes,
    key_cols,
    value_cols,
    minput_k: int = 32,
    sel: Optional[np.ndarray] = None,
):
    """Rebuild (table, state, minput) at capacity ``cap`` from recovered
    rows (optionally the ``sel`` subset — the sharded restore partitions
    rows by vnode and rebuilds each shard with this same core)."""
    if not key_cols:
        idx = np.zeros(0, np.int64)
    elif sel is None:
        idx = np.arange(len(next(iter(key_cols.values()))))
    else:
        idx = np.asarray(sel)
    n = len(idx)
    table = HashTable.create(cap, key_dtypes)
    state = agg_ops.create_state(cap, calls, dtypes)
    minput = mi_ops.create_minput(cap, minput_k, calls, dtypes)
    if not n:
        return table, state, minput
    lanes = tuple(
        jnp.asarray(np.asarray(key_cols[f"k{i}"], dtype=d)[idx])
        for i, d in enumerate(key_dtypes)
    )
    valid = jnp.ones(n, jnp.bool_)
    table, slots, _, _ = lookup_or_insert(table, lanes, valid)

    def put(dst, src):
        return dst.at[slots].set(jnp.asarray(np.asarray(src)[idx]))

    row_count = put(state.row_count, value_cols["row_count"])
    accums = {
        name: put(a, np.asarray(value_cols[f"acc_{name}"]).astype(a.dtype))
        for name, a in state.accums.items()
    }
    emitted = {
        name: put(a, np.asarray(value_cols[f"em_{name}"]).astype(a.dtype))
        for name, a in state.emitted.items()
    }
    nonnull = {
        name: put(a, value_cols[f"nn_{name}"])
        for name, a in state.nonnull.items()
    }
    e_isnull = {
        name: put(a, value_cols[f"ei_{name}"])
        for name, a in state.emitted_isnull.items()
    }
    emitted_valid = put(state.emitted_valid, value_cols["ev"])
    minput = {
        name: (
            put(v, np.asarray(value_cols[f"miv_{name}"]).astype(v.dtype)),
            put(c, np.asarray(value_cols[f"mic_{name}"]).astype(c.dtype)),
        )
        for name, (v, c) in minput.items()
    }
    stored = state.stored.at[slots].set(True)
    state = AggState(
        row_count=row_count,
        accums=accums,
        nonnull=nonnull,
        emitted=emitted,
        emitted_isnull=e_isnull,
        emitted_valid=emitted_valid,
        dirty=jnp.zeros(cap, jnp.bool_),
        minmax_retracted=jnp.zeros((), jnp.bool_),
        sdirty=jnp.zeros(cap, jnp.bool_),
        stored=stored,
    )
    table = set_live(table, slots, row_count[slots] > 0)
    return table, state, minput


def _agg_restore_state(self, table_id, key_cols, value_cols) -> None:
    """Rebuild device table + state from recovered rows."""
    n = len(next(iter(key_cols.values()))) if key_cols else 0
    key_dtypes = tuple(k.dtype for k in self.table.keys)
    cap = grow_pow2(n, self.table.capacity, GROW_AT)
    self.table, self.state, self.minput = build_restored_agg(
        cap,
        self.calls,
        self._dtypes,
        key_dtypes,
        key_cols,
        value_cols,
        self.minput_k,
    )
    self.dropped = jnp.zeros((), jnp.bool_)
    self.mi_bad = jnp.zeros((), jnp.bool_)
    self._insert_bound = int(n)
    self._dirty_bound = 0  # restored groups carry no unflushed change
    self._touched_lanes = None  # a table the steps' list never saw
    # recovery restored every durable group as RESIDENT state
    self._evicted = set()
    self._has_evicted = False


def _agg_digest_lanes(self):
    from risingwave_tpu.integrity import agg_lanes

    return agg_lanes(self.table, self.state)


def _agg_state_digest(self) -> int:
    """Host twin of the fused digest lane (integrity.agg_lanes fold)."""
    from risingwave_tpu.integrity import host_digest

    lanes, live = _agg_digest_lanes(self)
    return host_digest(lanes, live)


HashAggExecutor.checkpoint_delta = _agg_checkpoint_delta
HashAggExecutor.restore_state = _agg_restore_state
HashAggExecutor.digest_lanes = _agg_digest_lanes
HashAggExecutor.state_digest = _agg_state_digest
