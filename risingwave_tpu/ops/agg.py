"""Grouped aggregation state kernel — the core of HashAgg.

Reference roles replaced:
- per-group agg state + apply_chunk
  (src/stream/src/executor/hash_agg.rs:326, executor/aggregation/
  {agg_group.rs, agg_state.rs})
- dirty-group tracking + per-barrier flush_data emitting one
  retraction/update row pair per changed group (hash_agg.rs:406).

TPU re-design: agg state is NOT a map of per-group objects — it is a
struct-of-arrays indexed by hash-table slot (ops/hash_table.py assigns
slots). Applying a chunk is a handful of masked segment-scatters:

    count[slot]  += sign                  (COUNT(*) / group liveness)
    sum[slot]    += sign * value          (SUM / COUNT(col))
    min[slot]     = min(min[slot], value) (append-only MIN/MAX)

so a whole chunk of any size updates all its groups in O(chunk) scatter
work with zero host round-trips, and the whole thing fuses under jit.

SQL NULL outputs: SUM/MIN/MAX over a group whose inputs are all NULL is
NULL (COUNT is 0). Each such call keeps a per-group non-null input
counter; flush emits a null lane from ``counter == 0`` (reference:
agg_state.rs null handling / Datum outputs).

Retraction: sum/count invert exactly via the sign. MIN/MAX cannot be
retracted without per-group materialized input (reference keeps a sorted
state table per extreme agg call, executor/aggregation/minput.rs); this
kernel maintains them append-only and *flags* any retraction touching a
MIN/MAX call in ``state.minmax_retracted`` so the host can reject or
escalate (windowed Nexmark plans delete whole groups, never individual
rows, so the append-only path covers q5/q7/q8).

Flush: per-barrier delta emission compacts dirty slots to the front
(static shapes) and emits, per dirty group:

    previously emitted & still live  -> (U-, old row) + (U+, new row)
    previously emitted & dead        -> (D,  old row)
    never emitted      & live        -> (I,  new row)

matching the reference's AggChangesEmitter semantics (hash_agg.rs:406).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from risingwave_tpu.types import Op

KINDS = ("count_star", "count", "sum", "min", "max")
# kinds whose SQL result is NULL when no non-NULL input exists
NULLABLE_KINDS = ("sum", "min", "max")


@dataclass(frozen=True)
class AggCall:
    """One aggregate call: kind + input column -> output column.

    Mirrors the reference's ``AggCall`` (src/expr/core/src/aggregate/)
    narrowed to the kernel-supported kinds. ``materialized`` selects the
    materialized-input MIN/MAX state (ops/minput.py, reference
    minput.rs) so row-level retractions are exact; append-only plans
    leave it False and pay no extra state.
    """

    kind: str
    input: Optional[str]  # None for count_star
    output: str
    materialized: bool = False

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unsupported agg kind {self.kind!r}")
        if (self.input is None) != (self.kind == "count_star"):
            raise ValueError(f"{self.kind} input mismatch")
        if self.materialized and self.kind not in ("min", "max"):
            raise ValueError("materialized only applies to min/max")


def _extreme_init(dtype, kind: str):
    info = jnp.iinfo(dtype)
    return jnp.array(info.max if kind == "min" else info.min, dtype)


def accum_init(kind: str, dtype) -> jnp.ndarray:
    """The empty-group accumulator value for one agg kind (scalar)."""
    if kind in ("min", "max"):
        return _extreme_init(dtype, kind)
    return jnp.zeros((), dtype)


# -- ordered-float total-order encoding ---------------------------------
# Float MIN/MAX accumulators are stored as UNSIGNED total-order keys, not
# floats: scatter-min over raw floats lets one NaN poison a group forever
# (min(NaN, x) = NaN and append-only extremes can never retract it). The
# reference's ordered-float total ordering (src/common/src/types/, also
# used for the minput.rs sorted state) places NaN as the single largest
# value; the classic bit trick below realizes exactly that ordering on
# integer lanes, which the TPU scatters natively.

_FLOAT_ORDER = {
    jnp.dtype(jnp.float32): (jnp.uint32, jnp.uint32(1) << 31),
    jnp.dtype(jnp.float64): (jnp.uint64, jnp.uint64(1) << 63),
}


def _float_to_order_key(v: jnp.ndarray) -> jnp.ndarray:
    udtype, sign = _FLOAT_ORDER[jnp.dtype(v.dtype)]
    # canonicalize: one zero, one (positive quiet) NaN
    v = jnp.where(v == 0.0, jnp.zeros((), v.dtype), v)
    v = jnp.where(jnp.isnan(v), jnp.full((), jnp.nan, v.dtype), v)
    bits = jax.lax.bitcast_convert_type(v, udtype)
    neg = (bits & sign) != 0
    return jnp.where(neg, ~bits, bits | sign)


def _order_key_to_float(k: jnp.ndarray, float_dtype) -> jnp.ndarray:
    udtype, sign = _FLOAT_ORDER[jnp.dtype(float_dtype)]
    was_pos = (k & sign) != 0
    bits = jnp.where(was_pos, k & ~sign, ~k)
    return jax.lax.bitcast_convert_type(bits.astype(udtype), float_dtype)


@jax.tree_util.register_pytree_node_class
@dataclass
class AggState:
    """Slot-indexed aggregation state (all arrays length = capacity).

    ``row_count`` is the implicit COUNT(*) that determines group
    liveness (reference: AggGroup keeps row_count to decide emit vs
    delete, agg_group.rs). ``accums[name]`` holds one accumulator lane
    per AggCall output; ``nonnull[name]`` counts non-NULL inputs for
    NULLABLE_KINDS calls (0 -> SQL NULL output). ``emitted*`` snapshot
    what downstream has seen, so flush can produce exact U-/U+
    retractions. ``dirty`` marks slots touched since the last flush.
    ``minmax_retracted`` latches the unsupported-retraction condition
    for host-side checking.

    Storage lanes (the memtable-dirty analogue, mem_table.rs):
    ``sdirty`` marks slots changed since the last CHECKPOINT (cleared
    by StateTable commit); ``stored`` marks slots present in the object
    store (drives tombstone emission when a stored group dies).
    """

    row_count: jnp.ndarray  # int64
    accums: Dict[str, jnp.ndarray]
    nonnull: Dict[str, jnp.ndarray]  # int64, subset of accum names
    emitted: Dict[str, jnp.ndarray]
    emitted_isnull: Dict[str, jnp.ndarray]  # bool, same keys as nonnull
    emitted_valid: jnp.ndarray  # bool
    dirty: jnp.ndarray  # bool
    minmax_retracted: jnp.ndarray  # () bool
    sdirty: jnp.ndarray  # bool — changed since last checkpoint
    stored: jnp.ndarray  # bool — persisted in the object store

    def tree_flatten(self):
        anames = tuple(sorted(self.accums))
        nnames = tuple(sorted(self.nonnull))
        children = (
            self.row_count,
            tuple(self.accums[n] for n in anames),
            tuple(self.nonnull[n] for n in nnames),
            tuple(self.emitted[n] for n in anames),
            tuple(self.emitted_isnull[n] for n in nnames),
            self.emitted_valid,
            self.dirty,
            self.minmax_retracted,
            self.sdirty,
            self.stored,
        )
        return children, (anames, nnames)

    @classmethod
    def tree_unflatten(cls, aux, children):
        anames, nnames = aux
        (
            row_count,
            accums,
            nonnull,
            emitted,
            e_isnull,
            emitted_valid,
            dirty,
            mr,
            sdirty,
            stored,
        ) = children
        return cls(
            row_count=row_count,
            accums=dict(zip(anames, accums)),
            nonnull=dict(zip(nnames, nonnull)),
            emitted=dict(zip(anames, emitted)),
            emitted_isnull=dict(zip(nnames, e_isnull)),
            emitted_valid=emitted_valid,
            dirty=dirty,
            minmax_retracted=mr,
            sdirty=sdirty,
            stored=stored,
        )

    @property
    def capacity(self) -> int:
        return self.row_count.shape[0]


def _accum_dtype(call: AggCall, input_dtype) -> jnp.dtype:
    if call.kind in ("count_star", "count"):
        return jnp.int64
    if call.kind == "sum" and jnp.issubdtype(input_dtype, jnp.integer):
        return jnp.int64  # SQL SUM(int) widens to bigint
    if call.kind in ("min", "max") and jnp.issubdtype(input_dtype, jnp.floating):
        return _FLOAT_ORDER[jnp.dtype(input_dtype)][0]  # total-order key
    return input_dtype


def float_extreme_meta(calls: Sequence[AggCall], input_dtypes) -> tuple:
    """Static metadata for flush(): which outputs are float extremes and
    their original float dtype (needed to decode order keys back)."""
    out = []
    for c in calls:
        if c.kind in ("min", "max") and jnp.issubdtype(
            input_dtypes.get(c.input, jnp.int64), jnp.floating
        ):
            out.append((c.output, str(jnp.dtype(input_dtypes[c.input]))))
    return tuple(out)


def create_state(capacity: int, calls: Sequence[AggCall], input_dtypes) -> AggState:
    """``input_dtypes`` maps input column name -> jnp dtype."""
    accums, nonnull, emitted, e_isnull = {}, {}, {}, {}
    for c in calls:
        dt = _accum_dtype(c, None if c.input is None else input_dtypes[c.input])
        accums[c.output] = jnp.full(capacity, accum_init(c.kind, dt), dt)
        emitted[c.output] = jnp.zeros(capacity, dt)
        if c.kind in NULLABLE_KINDS:
            nonnull[c.output] = jnp.zeros(capacity, jnp.int64)
            e_isnull[c.output] = jnp.zeros(capacity, jnp.bool_)
    return AggState(
        row_count=jnp.zeros(capacity, jnp.int64),
        accums=accums,
        nonnull=nonnull,
        emitted=emitted,
        emitted_isnull=e_isnull,
        emitted_valid=jnp.zeros(capacity, jnp.bool_),
        dirty=jnp.zeros(capacity, jnp.bool_),
        minmax_retracted=jnp.zeros((), jnp.bool_),
        sdirty=jnp.zeros(capacity, jnp.bool_),
        stored=jnp.zeros(capacity, jnp.bool_),
    )


@jax.named_scope("agg/apply")
def apply(
    state: AggState,
    calls: Tuple[AggCall, ...],
    slots: jnp.ndarray,  # (n,) int32, -1 = skip
    signs: jnp.ndarray,  # (n,) int32 in {-1, 0, +1}; 0 for padding
    values: Dict[str, jnp.ndarray],
    nulls: Dict[str, jnp.ndarray],  # input-null lanes (may be absent)
) -> AggState:
    """Apply one chunk's rows to the state (pure; jit-composable).

    ``signs`` must already fold visibility (StreamChunk.effective_signs).
    NULL inputs contribute to nothing but COUNT(*) (SQL: aggregates skip
    NULLs; reference agg_state.rs null handling).
    """
    cap = state.capacity
    active = (slots >= 0) & (signs != 0)
    idx = jnp.where(active, slots, cap)  # cap = drop lane
    w = jnp.where(active, signs, 0).astype(jnp.int64)

    row_count = state.row_count.at[idx].add(w, mode="drop")
    dirty = state.dirty.at[idx].set(True, mode="drop")
    sdirty = state.sdirty.at[idx].set(True, mode="drop")

    accums = dict(state.accums)
    nonnull = dict(state.nonnull)
    mr = state.minmax_retracted
    for c in calls:
        acc = accums[c.output]
        if c.kind == "count_star":
            accums[c.output] = acc.at[idx].add(w, mode="drop")
            continue
        v = values[c.input]
        notnull = ~nulls.get(c.input, jnp.zeros(v.shape, jnp.bool_))
        wn = jnp.where(notnull, w, 0)
        if c.kind == "count":
            accums[c.output] = acc.at[idx].add(wn, mode="drop")
        elif c.kind == "sum":
            contrib = jnp.where(notnull, v.astype(acc.dtype) * w.astype(acc.dtype), 0)
            accums[c.output] = acc.at[idx].add(contrib, mode="drop")
            nonnull[c.output] = nonnull[c.output].at[idx].add(wn, mode="drop")
        elif c.materialized:
            # materialized-input MIN/MAX: the minput pass (ops/minput.py)
            # owns accum + nonnull maintenance; retraction is exact, so
            # no latch here
            continue
        else:  # min / max — append-only
            sentinel = accum_init(c.kind, acc.dtype)
            use = active & notnull & (w > 0)
            if jnp.issubdtype(v.dtype, jnp.floating):
                v = _float_to_order_key(v)  # NaN-safe total order
            vv = jnp.where(use, v.astype(acc.dtype), sentinel)
            uidx = jnp.where(use, slots, cap)
            if c.kind == "min":
                accums[c.output] = acc.at[uidx].min(vv, mode="drop")
            else:
                accums[c.output] = acc.at[uidx].max(vv, mode="drop")
            nonnull[c.output] = (
                nonnull[c.output]
                .at[uidx]
                .add(jnp.where(use, jnp.int64(1), jnp.int64(0)), mode="drop")
            )
            mr = mr | jnp.any(active & notnull & (w < 0))

    return AggState(
        row_count=row_count,
        accums=accums,
        nonnull=nonnull,
        emitted=state.emitted,
        emitted_isnull=state.emitted_isnull,
        emitted_valid=state.emitted_valid,
        dirty=dirty,
        minmax_retracted=mr,
        sdirty=sdirty,
        stored=state.stored,
    )


def reduce_by_key(
    key_lanes: Tuple[jnp.ndarray, ...],
    signs: jnp.ndarray,
    calls: Tuple[AggCall, ...],
    values: Dict[str, jnp.ndarray],
    nulls: Dict[str, jnp.ndarray],
):
    """Pre-reduce a row batch by group key (pure; jit-composable).

    The TPU-first answer to per-row hash probing: ``lax.sort`` (a
    vectorized compare-exchange network — no serialized gathers)
    clusters equal keys, segments split at any exact key change, and
    every aggregate contribution is segment-reduced, so the hash table
    downstream is probed and scattered once per DISTINCT key instead of
    once per row. This is the StatelessSimpleAgg-before-shuffle shape
    (src/stream/src/executor/stateless_simple_agg.rs) fused into the
    operator, applied per epoch rather than per actor.

    All agg kinds here are commutative across rows of one epoch batch
    (sum/count exactly; min/max append-only with the retraction latch),
    so reordering by sort is semantics-preserving.

    Returns ``(sorted_keys, rep_valid, w, reduced, minmax_ret)``:
      sorted_keys  key lanes in sort order (feed to lookup_or_insert)
      rep_valid    bool (n,) — True on each segment's first row
      w            int64 (n,) — Σ sign per segment, on rep rows
      reduced      dict of per-call reduced lanes (on rep rows):
                   'cnt_<out>' / 'sum_<out>' / 'nn_<out>' /
                   'ext_<out>' / 'nnp_<out>'
      minmax_ret   () bool — a retraction touched a MIN/MAX call
    """
    from risingwave_tpu.ops.hashing import hash128

    with jax.named_scope("agg/reduce_by_key/sort"):
        n = signs.shape[0]
        h1, h2 = hash128(key_lanes)
        vmask = signs != 0
        # invisible rows sort to the end (max fingerprint) and never become
        # segment representatives
        h1s = jnp.where(vmask, h1, jnp.uint32(0xFFFFFFFF))
        h2s = jnp.where(vmask, h2, jnp.uint32(0xFFFFFFFF))

        val_names = tuple(sorted(values))
        null_names = tuple(sorted(nulls))
        operands = (
            [h1s, h2s]
            + list(key_lanes)
            + [signs.astype(jnp.int32), vmask]
            + [values[nm] for nm in val_names]
            + [nulls[nm] for nm in null_names]
        )
        sorted_ops = jax.lax.sort(tuple(operands), num_keys=2)
        h1s, h2s = sorted_ops[0], sorted_ops[1]
        nk = len(key_lanes)
        sorted_keys = tuple(sorted_ops[2 : 2 + nk])
        s_sign = sorted_ops[2 + nk].astype(jnp.int64)
        s_vmask = sorted_ops[3 + nk]
        s_vals = {
            nm: sorted_ops[4 + nk + i] for i, nm in enumerate(val_names)
        }
        s_nulls = {
            nm: sorted_ops[4 + nk + len(val_names) + i]
            for i, nm in enumerate(null_names)
        }

    # segment boundary: first row, or ANY exact lane change (fingerprint
    # collisions between different keys split correctly because the raw
    # key lanes participate)
    with jax.named_scope("agg/reduce_by_key/combine"):
        def lane_change(lane):
            return jnp.concatenate(
                [jnp.ones(1, jnp.bool_), lane[1:] != lane[:-1]]
            )

        boundary = lane_change(h1s) | lane_change(h2s) | lane_change(s_vmask)
        for lane in sorted_keys:
            ch = lane_change(lane)
            if jnp.issubdtype(lane.dtype, jnp.floating):
                both_nan = jnp.concatenate(
                    [
                        jnp.zeros(1, jnp.bool_),
                        jnp.isnan(lane[1:]) & jnp.isnan(lane[:-1]),
                    ]
                )
                ch = ch & ~both_nan  # NaN == NaN for grouping (total order)
            boundary = boundary | ch
        rep_valid = boundary & s_vmask
        seg_id = jnp.cumsum(boundary.astype(jnp.int32)) - 1

        def segsum(x):
            return jax.ops.segment_sum(x, seg_id, num_segments=n)[seg_id]

        w = segsum(s_sign)
        reduced: Dict[str, jnp.ndarray] = {}
        minmax_ret = jnp.zeros((), jnp.bool_)
        for c in calls:
            if c.kind == "count_star":
                continue  # uses w directly
            v = s_vals[c.input]
            notnull = ~s_nulls.get(c.input, jnp.zeros(v.shape, jnp.bool_))
            wn = jnp.where(notnull, s_sign, 0)
            if c.kind == "count":
                reduced[f"cnt_{c.output}"] = segsum(wn)
            elif c.kind == "sum":
                acc_dt = _accum_dtype(c, v.dtype)
                contrib = jnp.where(
                    notnull, v.astype(acc_dt) * s_sign.astype(acc_dt), 0
                )
                reduced[f"sum_{c.output}"] = segsum(contrib)
                reduced[f"nn_{c.output}"] = segsum(wn)
            elif c.materialized:
                continue  # minput pass maintains these (ops/minput.py)
            else:  # min / max (append-only)
                use = s_vmask & notnull & (s_sign > 0)
                if jnp.issubdtype(v.dtype, jnp.floating):
                    v = _float_to_order_key(v)
                acc_dt = _accum_dtype(c, s_vals[c.input].dtype)
                sentinel = accum_init(c.kind, acc_dt)
                vv = jnp.where(use, v.astype(acc_dt), sentinel)
                seg_red = (
                    jax.ops.segment_min
                    if c.kind == "min"
                    else jax.ops.segment_max
                )(vv, seg_id, num_segments=n)
                reduced[f"ext_{c.output}"] = seg_red[seg_id]
                reduced[f"nnp_{c.output}"] = segsum(
                    jnp.where(use, jnp.int64(1), jnp.int64(0))
                )
                minmax_ret = minmax_ret | jnp.any(s_vmask & notnull & (s_sign < 0))
    return sorted_keys, rep_valid, w, reduced, minmax_ret


@jax.named_scope("agg/apply")
def apply_reduced(
    state: AggState,
    calls: Tuple[AggCall, ...],
    slots: jnp.ndarray,
    rep_valid: jnp.ndarray,
    w: jnp.ndarray,
    reduced: Dict[str, jnp.ndarray],
    minmax_ret: jnp.ndarray,
) -> AggState:
    """Apply ``reduce_by_key`` output to the state: one scatter per
    lane, indices touched once per distinct key."""
    cap = state.capacity
    active = rep_valid & (slots >= 0)
    idx = jnp.where(active, slots, cap)
    ww = jnp.where(active, w, 0)

    row_count = state.row_count.at[idx].add(ww, mode="drop")
    dirty = state.dirty.at[idx].set(True, mode="drop")
    sdirty = state.sdirty.at[idx].set(True, mode="drop")

    accums = dict(state.accums)
    nonnull = dict(state.nonnull)
    for c in calls:
        acc = accums[c.output]
        if c.kind == "count_star":
            accums[c.output] = acc.at[idx].add(ww, mode="drop")
        elif c.kind == "count":
            accums[c.output] = acc.at[idx].add(
                jnp.where(active, reduced[f"cnt_{c.output}"], 0), mode="drop"
            )
        elif c.kind == "sum":
            accums[c.output] = acc.at[idx].add(
                jnp.where(active, reduced[f"sum_{c.output}"], 0).astype(
                    acc.dtype
                ),
                mode="drop",
            )
            nonnull[c.output] = nonnull[c.output].at[idx].add(
                jnp.where(active, reduced[f"nn_{c.output}"], 0), mode="drop"
            )
        elif c.materialized:
            continue  # minput pass maintains these (ops/minput.py)
        else:  # min / max
            sentinel = accum_init(c.kind, acc.dtype)
            ext = jnp.where(
                active, reduced[f"ext_{c.output}"].astype(acc.dtype), sentinel
            )
            if c.kind == "min":
                accums[c.output] = acc.at[idx].min(ext, mode="drop")
            else:
                accums[c.output] = acc.at[idx].max(ext, mode="drop")
            nonnull[c.output] = nonnull[c.output].at[idx].add(
                jnp.where(active, reduced[f"nnp_{c.output}"], 0), mode="drop"
            )

    return AggState(
        row_count=row_count,
        accums=accums,
        nonnull=nonnull,
        emitted=state.emitted,
        emitted_isnull=state.emitted_isnull,
        emitted_valid=state.emitted_valid,
        dirty=dirty,
        minmax_retracted=state.minmax_retracted | minmax_ret,
        sdirty=sdirty,
        stored=state.stored,
    )


def _reset_groups(
    state: AggState,
    calls: Tuple[AggCall, ...],
    slots: jnp.ndarray,
    *,
    mark_dirty: bool,
) -> AggState:
    """Zero out groups' accumulators.

    ``mark_dirty=True`` (delete_groups): the next flush emits a Delete
    for each previously-emitted group — windowed retraction.
    ``mark_dirty=False`` (forget_groups): silent finalization — the
    flush emits nothing; downstream keeps the last emitted row as the
    window's final result while the operator frees the state (EOWC
    cleanup; reference hash_agg.rs emit-on-window-close mode +
    state_table.rs:1133 watermark cleaning). Callers must flush dirty
    groups FIRST or pending updates would be silently discarded.
    """
    cap = state.capacity
    idx = jnp.where(slots >= 0, slots, cap)
    row_count = state.row_count.at[idx].set(0, mode="drop")
    sdirty = state.sdirty.at[idx].set(True, mode="drop")
    if mark_dirty:
        dirty = state.dirty.at[idx].set(True, mode="drop")
        emitted_valid = state.emitted_valid
    else:
        dirty = state.dirty.at[idx].set(False, mode="drop")
        emitted_valid = state.emitted_valid.at[idx].set(False, mode="drop")
    kinds = {c.output: c.kind for c in calls}
    accums = {
        name: acc.at[idx].set(accum_init(kinds[name], acc.dtype), mode="drop")
        for name, acc in state.accums.items()
    }
    nonnull = {
        name: nn.at[idx].set(0, mode="drop") for name, nn in state.nonnull.items()
    }
    return AggState(
        row_count=row_count,
        accums=accums,
        nonnull=nonnull,
        emitted=state.emitted,
        emitted_isnull=state.emitted_isnull,
        emitted_valid=emitted_valid,
        dirty=dirty,
        minmax_retracted=state.minmax_retracted,
        sdirty=sdirty,
        stored=state.stored,
    )


def delete_groups(
    state: AggState, calls: Tuple[AggCall, ...], slots: jnp.ndarray
) -> AggState:
    """Drop whole groups (window expiry) WITH downstream retraction."""
    return _reset_groups(state, calls, slots, mark_dirty=True)


def forget_groups(
    state: AggState, calls: Tuple[AggCall, ...], slots: jnp.ndarray
) -> AggState:
    """Silently free groups (EOWC finalization). See _reset_groups."""
    return _reset_groups(state, calls, slots, mark_dirty=False)


def note_touched(touched: jnp.ndarray, at, slots: jnp.ndarray) -> jnp.ndarray:
    """Append the slots a step wrote (-1 = none) to the flush's list at
    lane ``at`` (pure; jit-composable, in place where ``touched`` is
    donated). The host keeps the cursor and answers for the room: a
    start past ``len(touched) - len(slots)`` would be clamped onto
    earlier entries, so where it counts more lanes than the list holds
    it gives the list up and flushes by the table."""
    if slots.shape[0] > touched.shape[0]:
        return touched
    return jax.lax.dynamic_update_slice(
        touched, slots.astype(touched.dtype), (at,)
    )


def _dirty_head_listed(dirty, touched, n_touched, walk: int, m: int):
    """The first ``m`` dirty slots, ascending, and how many are dirty,
    from the first ``n_touched`` of ``walk`` lanes of the steps' list:
    nothing here ranges over the table. A slot the steps wrote twice
    is listed twice and one already flushed is listed still, so the
    list is cut to its dirty entries, sorted, made unique, and sorted
    again to close the gaps. ``cap`` stands for no slot."""
    cap = dirty.shape[0]
    lst = touched[:walk]
    listed = (jnp.arange(walk) < n_touched) & (lst >= 0)
    listed = listed & dirty[jnp.where(listed, lst, 0)]
    s = jax.lax.sort(jnp.where(listed, lst, cap), is_stable=False)
    first = jnp.concatenate([jnp.ones(1, jnp.bool_), s[1:] != s[:-1]])
    uniq = first & (s < cap)
    n_dirty = jnp.sum(uniq.astype(jnp.int32))
    s = jax.lax.sort(jnp.where(uniq, s, cap), is_stable=False)
    if walk < m:
        s = jnp.concatenate([s, jnp.full(m - walk, cap, s.dtype)])
    return s[:m], n_dirty


@partial(
    jax.jit,
    static_argnames=("out_cap", "float_extremes", "walk"),
    donate_argnums=(0,),
)
def flush(
    state: AggState,
    table_keys: Tuple[jnp.ndarray, ...],
    out_cap: int,
    float_extremes: tuple = (),
    touched: Optional[jnp.ndarray] = None,
    n_touched=None,
    walk: Optional[int] = None,
):
    """Emit the per-barrier delta for dirty groups (hash_agg.rs:406).

    Returns ``(state', delta)`` where delta is a dict of fixed-capacity
    (2 * out_cap) arrays:
      ``ops``                int32 Op lane
      ``valid``              bool row-validity lane
      ``key<i>``             the i-th group-key lane (from table_keys)
      ``<output>``           one lane per agg output
      ``<output>__isnull``   bool SQL-NULL lane (NULLABLE_KINDS only)
      ``overflow``           () bool — True if more than out_cap dirty
                             groups existed; host must flush again.

    Old (U-/D) rows carry the previously-emitted accums; new (U+/I)
    rows carry the current ones. Rows interleave (old_i, new_i) so
    downstream sees retraction-before-insert per group, matching
    StreamChunk update-pair ordering (stream_chunk.rs:45).

    ``float_extremes`` (static, from ``float_extreme_meta``) lists agg
    outputs stored as float total-order keys; their lanes are decoded
    back to floats on emission.

    Which slots are dirty is read one of two ways, and the groups come
    out in ascending slot order either way, lane for lane the same
    (the reference's flush_data walks its set of dirty groups, never
    the state table). With ``touched`` — the list the steps kept of
    the slots they wrote since the last flush (``note_touched``), its
    first ``n_touched`` lanes filled, ``walk`` (static) a declared
    length at or above that — the program ranges over ``walk`` lanes
    and over no lane of the table but the ``out_cap`` it gathers. The
    caller holds that every dirty slot is on the list. Without it the
    table is sorted by its dirty bit, as many lanes as it has.
    """
    with jax.named_scope("agg/flush/select"):
        cap = state.capacity
        m = min(out_cap, cap)
        if touched is None:
            # compact dirty slot ids to the front: sort puts False (0) last
            order = jnp.argsort(~state.dirty, stable=True)
            n_dirty = jnp.sum(state.dirty.astype(jnp.int32))
            slot_ids = order[:m]
        else:
            slot_ids, n_dirty = _dirty_head_listed(
                state.dirty, touched, n_touched, walk, m
            )
        # the dirty slots come first either way (over the table this is
        # dirty[order][:m], without gathering every lane to keep m of them)
        take = jnp.arange(m) < n_dirty
        slot_ids = jnp.where(take, slot_ids, 0)
        overflow = n_dirty > out_cap

    with jax.named_scope("agg/flush/gather"):
        live = take & (state.row_count[slot_ids] > 0)
        was = take & state.emitted_valid[slot_ids]

        minus_valid = was  # emit old row as U- or D
        plus_valid = live  # emit new row as U+ or I
        minus_op = jnp.where(live, jnp.int32(Op.UPDATE_DELETE), jnp.int32(Op.DELETE))
        plus_op = jnp.where(was, jnp.int32(Op.UPDATE_INSERT), jnp.int32(Op.INSERT))

        def interleave(a, b):
            return jnp.stack([a, b], axis=1).reshape(-1)

        delta = {
            "ops": interleave(minus_op, plus_op),
            "valid": interleave(minus_valid, plus_valid),
            "overflow": overflow,
            # [n dirty slots taken, overflow] — ONE host read serves both
            # the emit-size slice and the continue-flush check (each device
            # read is a full round-trip on the TPU)
            "status": jnp.stack(
                [jnp.sum(take.astype(jnp.int32)), overflow.astype(jnp.int32)]
            ),
        }
        for i, lane in enumerate(table_keys):
            kv = lane[slot_ids]
            delta[f"key{i}"] = interleave(kv, kv)
        decode = dict(float_extremes)
        for name, acc in state.accums.items():
            old = state.emitted[name][slot_ids]
            new = acc[slot_ids]
            if name in decode:
                old = _order_key_to_float(old, jnp.dtype(decode[name]))
                new = _order_key_to_float(new, jnp.dtype(decode[name]))
            delta[name] = interleave(old, new)
        for name, nn in state.nonnull.items():
            old_isnull = state.emitted_isnull[name][slot_ids]
            new_isnull = nn[slot_ids] == 0
            delta[name + "__isnull"] = interleave(old_isnull, new_isnull)

    with jax.named_scope("agg/flush/snapshot"):
        # snapshot what we just emitted (only for flushed slots)
        fidx = jnp.where(take, slot_ids, cap)
        emitted = {
            name: state.emitted[name]
            .at[fidx]
            .set(state.accums[name][slot_ids], mode="drop")
            for name in state.accums
        }
        emitted_isnull = {
            name: state.emitted_isnull[name]
            .at[fidx]
            .set(state.nonnull[name][slot_ids] == 0, mode="drop")
            for name in state.nonnull
        }
        emitted_valid = state.emitted_valid.at[fidx].set(
            state.row_count[slot_ids] > 0, mode="drop"
        )
        dirty = state.dirty.at[fidx].set(False, mode="drop")

    state = AggState(
        row_count=state.row_count,
        accums=state.accums,
        nonnull=state.nonnull,
        emitted=emitted,
        emitted_isnull=emitted_isnull,
        emitted_valid=emitted_valid,
        dirty=dirty,
        minmax_retracted=state.minmax_retracted,
        sdirty=state.sdirty,
        stored=state.stored,
    )
    return state, delta
