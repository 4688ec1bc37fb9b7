"""OverWindow — window functions over partitions (append-only).

Reference: src/stream/src/executor/over_window/general.rs:49 — per
partition, per order-key window functions; the general executor
retracts and re-emits affected frames on any change. This executor is
the APPEND-ONLY + arrival-ordered specialization (RW's planner also
specializes this case): each row gets its window value at arrival and
is never revisited — exactly right for ROW_NUMBER / running COUNT /
running SUM over monotonically arriving streams.

TPU re-design: partition state is a hash table + per-slot running
accumulators. One fused step per chunk: lookup partitions, sort rows
by (slot, arrival) to rank intra-chunk duplicates, gather partition
bases, segment-prefix-scan the chunk's own contribution, scatter the
updated accumulators back — no per-row host work.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.array.lattice import push_lattice
from risingwave_tpu.executors.base import Barrier, Executor
from risingwave_tpu.ops.hash_table import (
    HashTable,
    first_occurrence_mask,
    last_occurrence_mask,
    lookup_or_insert,
    plan_rehash,
    set_live,
)
from risingwave_tpu.executors.sort import ArenaBufferedExecutor
from risingwave_tpu.executors.top_n_plain import (
    _EMIT_FLOOR,
    _count_set,
    _digits,
    _in_turns,
    _order_by_words,
    _packed_words,
    _put,
)
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.storage.state_table import (
    Checkpointable,
    StateDelta,
    classify_marks,
    grow_pow2,
    pull_rows,
)
from risingwave_tpu.trace import device_read, span
from risingwave_tpu.types import Op

GROW_AT = 0.5

KINDS = (
    "row_number",
    "count",
    "sum",
    "min",
    "max",
    "lag",
    "lead",
    "rank",
    "dense_rank",
)


@dataclass(frozen=True)
class WindowCall:
    """One window function call.

    ``frame``: optional static ROWS frame (lo, hi) offsets relative to
    the current row (e.g. (-2, 0) = 2 PRECEDING..CURRENT ROW) for
    sum/min/max/count in the EOWC executor; None = UNBOUNDED PRECEDING
    ..CURRENT ROW (running). ``offset``: lead/lag distance."""

    kind: str
    # None for row_number / count(*); a count WITH an input counts the
    # rows whose input is not NULL (the general executor alone)
    input: Optional[str]
    output: str
    frame: Optional[Tuple[int, int]] = None
    offset: int = 1

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unsupported window kind {self.kind!r}")
        if self.kind != "count" and (self.input is None) != (
            self.kind == "row_number"
        ):
            raise ValueError(f"{self.kind} input mismatch")
        if self.frame is not None:
            lo, hi = self.frame
            if lo > hi:
                raise ValueError(f"frame {self.frame}: lo > hi")
            if hi - lo + 1 > 64:
                raise ValueError(
                    "ROWS frames wider than 64 are not supported (a step "
                    "combines one shifted copy of the lane per frame row)"
                )
            if self.kind not in ("sum", "min", "max", "count"):
                raise ValueError(f"{self.kind} does not take a frame")
        if self.offset < 1:
            raise ValueError("lead/lag offset must be >= 1")


def _no_counted_input(calls) -> None:
    for c in calls:
        if c.kind == "count" and c.input is not None:
            raise ValueError(
                f"COUNT({c.input}) OVER counts non-NULL inputs: the "
                "general executor's (GeneralOverWindowExecutor)"
            )


def _accum_names(call: "WindowCall"):
    """Accumulator lanes per call (lag keeps last-value + flags;
    min/max keep a presence flag so sentinel-valued inputs are not
    misread as NULL; rank/dense_rank keep (last rank, row count, dense
    count, last order value, presence))."""
    if call.kind == "lag":
        return (call.output, call.output + "#has", call.output + "#null")
    if call.kind in ("min", "max"):
        return (call.output, call.output + "#has")
    if call.kind in ("rank", "dense_rank"):
        return (
            call.output,
            call.output + "#cnt",
            call.output + "#dense",
            call.output + "#last",
            call.output + "#has",
        )
    return (call.output,)


def _accum_init(call: "WindowCall") -> int:
    if call.kind == "min":
        return jnp.iinfo(jnp.int64).max
    if call.kind == "max":
        return jnp.iinfo(jnp.int64).min
    return 0


@partial(
    jax.jit, static_argnames=("calls", "part_keys"), donate_argnums=(0, 1, 2)
)
def _over_step(
    table: HashTable,
    accums: Dict[str, jnp.ndarray],
    sdirty: jnp.ndarray,
    chunk: StreamChunk,
    calls: Tuple[WindowCall, ...],
    part_keys: Tuple[str, ...],
):
    n = chunk.capacity
    keys = tuple(chunk.col(k) for k in part_keys)
    signs = chunk.effective_signs()
    active = chunk.valid & (signs > 0)
    saw_delete = jnp.any(chunk.valid & (signs < 0))
    table, slots, _, _ = lookup_or_insert(table, keys, active)
    dropped = jnp.any(active & (slots < 0))
    table = set_live(table, jnp.where(active, slots, -1), True)
    sdirty = sdirty.at[jnp.where(active, slots, -1)].set(True, mode="drop")
    ooo = jnp.zeros((), jnp.bool_)  # out-of-order arrival (rank kinds)

    # rank rows of one partition within the chunk (arrival order)
    skey = jnp.where(active, slots, table.capacity).astype(jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)
    val_lanes = {
        c.input: chunk.col(c.input).astype(jnp.int64)
        for c in calls
        if c.input is not None
    }
    null_lanes = {
        c.input: chunk.nulls[c.input]
        for c in calls
        if c.input is not None and c.input in chunk.nulls
    }
    names = tuple(sorted(val_lanes))
    nnames = tuple(sorted(null_lanes))
    sorted_ops = jax.lax.sort(
        (skey, pos)
        + tuple(val_lanes[m] for m in names)
        + tuple(null_lanes[m] for m in nnames),
        num_keys=2,
    )
    s_slot, s_pos = sorted_ops[0], sorted_ops[1]
    s_vals = {m: sorted_ops[2 + i] for i, m in enumerate(names)}
    s_nulls = {
        m: sorted_ops[2 + len(names) + i] for i, m in enumerate(nnames)
    }
    boundary = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), s_slot[1:] != s_slot[:-1]]
    )
    gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    arange = jnp.arange(n, dtype=jnp.int64)
    seg_start = jax.ops.segment_max(
        jnp.where(boundary, arange, 0), gid, num_segments=n
    )[gid]
    rank = arange - seg_start  # 0-based within (partition, chunk)
    s_active = s_slot < table.capacity
    gslot = jnp.where(s_active, s_slot, 0)

    # segment end == next segment's start (derive from boundary)
    is_last = jnp.concatenate([boundary[1:], jnp.ones(1, jnp.bool_)])
    MAXI = jnp.iinfo(jnp.int64).max
    MINI = jnp.iinfo(jnp.int64).min

    def seg_prefix_extreme(v, kind):
        """Inclusive segmented prefix min/max via an associative scan
        with a boundary-reset flag (the classic segmented-scan
        combine)."""
        comb = jnp.minimum if kind == "min" else jnp.maximum

        def op(a, b):
            fa, va = a
            fb, vb = b
            return fa | fb, jnp.where(fb, vb, comb(va, vb))

        _, out = jax.lax.associative_scan(op, (boundary, v))
        return out

    out_sorted: Dict[str, jnp.ndarray] = {}
    out_nulls_sorted: Dict[str, jnp.ndarray] = {}
    new_accums = dict(accums)
    for c in calls:
        acc = new_accums[c.output]
        base = acc[gslot]
        upd = jnp.where(s_active & is_last, gslot, table.capacity)
        if c.kind in ("row_number", "count"):
            o = base + rank + 1
            contrib = jnp.where(s_active, jnp.int64(1), jnp.int64(0))
            totals = jax.ops.segment_sum(contrib, gid, num_segments=n)[gid]
            new_accums[c.output] = acc.at[upd].add(totals, mode="drop")
        elif c.kind == "sum":
            # running sum (NULL inputs contribute 0, SQL skips them)
            v = s_vals[c.input]
            nn = ~s_nulls.get(c.input, jnp.zeros(n, jnp.bool_))
            v = jnp.where(s_active & nn, v, 0)
            # inclusive prefix within the segment (sentinel, not 0: the
            # boundary's exclusive prefix may be negative)
            csum = jnp.cumsum(v)
            seg_base = jax.ops.segment_max(
                jnp.where(boundary, csum - v, MINI),
                gid,
                num_segments=n,
            )[gid]
            o = base + (csum - seg_base)
            totals = jax.ops.segment_sum(v, gid, num_segments=n)[gid]
            new_accums[c.output] = acc.at[upd].add(totals, mode="drop")
        elif c.kind in ("min", "max"):
            sent = MAXI if c.kind == "min" else MINI
            comb = jnp.minimum if c.kind == "min" else jnp.maximum
            v = s_vals[c.input]
            nn = ~s_nulls.get(c.input, jnp.zeros(n, jnp.bool_))
            real = s_active & nn
            v = jnp.where(real, v, sent)
            pref = seg_prefix_extreme(v, c.kind)
            o = comb(base, pref)
            # presence via a companion lane, NOT sentinel equality: a
            # legitimate input equal to the int64 extreme must not be
            # misclassified as NULL (its value still combines right —
            # min(x, +inf) = x)
            has = new_accums[c.output + "#has"]
            pref_has = (
                jnp.cumsum(real.astype(jnp.int64))
                - jax.ops.segment_max(
                    jnp.where(
                        boundary,
                        jnp.cumsum(real.astype(jnp.int64))
                        - real.astype(jnp.int64),
                        MINI,
                    ),
                    gid,
                    num_segments=n,
                )[gid]
            ) > 0
            out_nulls_sorted[c.output] = ~((has[gslot] != 0) | pref_has)
            seg_fn = (
                jax.ops.segment_min if c.kind == "min" else jax.ops.segment_max
            )
            seg_ext = seg_fn(v, gid, num_segments=n)[gid]
            if c.kind == "min":
                new_accums[c.output] = acc.at[upd].min(seg_ext, mode="drop")
            else:
                new_accums[c.output] = acc.at[upd].max(seg_ext, mode="drop")
            seg_any = (
                jax.ops.segment_sum(
                    real.astype(jnp.int64), gid, num_segments=n
                )[gid]
                > 0
            )
            new_accums[c.output + "#has"] = (
                has.at[upd].max(seg_any.astype(jnp.int64), mode="drop")
            )
        elif c.kind in ("rank", "dense_rank"):
            # arrival order must be the ORDER BY order (the append-only
            # specialization's contract): order values non-decreasing
            # per partition — enforced by the ooo latch
            v = s_vals[c.input]
            prev_v = jnp.concatenate([jnp.zeros(1, v.dtype), v[:-1]])
            vb = boundary | (v != prev_v)  # value-group starts
            # 1-based count of value groups within the segment
            cum_vb_all = jnp.cumsum(vb.astype(jnp.int64))
            seg_vb_base = jax.ops.segment_max(
                jnp.where(boundary, cum_vb_all - 1, MINI),
                gid,
                num_segments=n,
            )[gid]
            cum_vb = cum_vb_all - seg_vb_base
            # arrival index (0-based, in-segment) of each value group's
            # first row — the rank numerator for its whole group
            grp_start = seg_prefix_extreme(
                jnp.where(vb, rank, MINI), "max"
            )
            has = new_accums[c.output + "#has"][gslot] != 0
            lastv = new_accums[c.output + "#last"][gslot]
            cnt0 = new_accums[c.output + "#cnt"][gslot]
            dense0 = new_accums[c.output + "#dense"][gslot]
            rank0 = new_accums[c.output][gslot]
            first_group = cum_vb == 1
            eq_carry = has & (v == lastv) & first_group
            ooo = ooo | jnp.any(
                (s_active & ~boundary & (v < prev_v))
                | (s_active & boundary & has & (v < lastv))
            )
            ranked = jnp.where(eq_carry, rank0, cnt0 + grp_start + 1)
            first_eq = (
                jax.ops.segment_max(
                    jnp.where(boundary, eq_carry.astype(jnp.int64), 0),
                    gid,
                    num_segments=n,
                )[gid]
                > 0
            )
            dense_row = dense0 + cum_vb - jnp.where(first_eq, 1, 0)
            o = ranked if c.kind == "rank" else dense_row
            contrib = jnp.where(s_active, jnp.int64(1), jnp.int64(0))
            totals = jax.ops.segment_sum(contrib, gid, num_segments=n)[gid]
            new_accums[c.output] = acc.at[upd].set(ranked, mode="drop")
            new_accums[c.output + "#cnt"] = (
                new_accums[c.output + "#cnt"]
                .at[upd]
                .add(totals, mode="drop")
            )
            new_accums[c.output + "#dense"] = (
                new_accums[c.output + "#dense"]
                .at[upd]
                .set(dense_row, mode="drop")
            )
            new_accums[c.output + "#last"] = (
                new_accums[c.output + "#last"].at[upd].set(v, mode="drop")
            )
            new_accums[c.output + "#has"] = (
                new_accums[c.output + "#has"]
                .at[upd]
                .set(jnp.int64(1), mode="drop")
            )
        else:  # lag(1): previous row's value within the partition
            v = s_vals[c.input]
            vnull = s_nulls.get(c.input, jnp.zeros(n, jnp.bool_))
            prev_v = jnp.concatenate([jnp.zeros(1, v.dtype), v[:-1]])
            prev_null = jnp.concatenate(
                [jnp.zeros(1, jnp.bool_), vnull[:-1]]
            )
            first = rank == 0
            # pre-update state: the partition's stored last value
            prev_has = new_accums[c.output + "#has"][gslot] != 0
            prev_stored_null = new_accums[c.output + "#null"][gslot] != 0
            o = jnp.where(first, base, prev_v)
            out_nulls_sorted[c.output] = jnp.where(
                first, ~prev_has | prev_stored_null, prev_null
            )
            # store the segment's LAST value (+ its nullness) per slot
            lastv = jax.ops.segment_max(
                jnp.where(is_last, v, MINI), gid, num_segments=n
            )[gid]
            lastn = jax.ops.segment_max(
                jnp.where(is_last, vnull.astype(jnp.int64), 0),
                gid,
                num_segments=n,
            )[gid]
            new_accums[c.output] = acc.at[upd].set(lastv, mode="drop")
            new_accums[c.output + "#null"] = (
                new_accums[c.output + "#null"]
                .at[upd]
                .set(lastn, mode="drop")
            )
            new_accums[c.output + "#has"] = (
                new_accums[c.output + "#has"]
                .at[upd]
                .set(jnp.int64(1), mode="drop")
            )
        out_sorted[c.output] = o

    # unsort back to arrival positions
    cols = dict(chunk.columns)
    out_nulls = dict(chunk.nulls)
    for name, o in out_sorted.items():
        buf = jnp.zeros(n, jnp.int64)
        cols[name] = buf.at[s_pos].set(o)
    for name, lane in out_nulls_sorted.items():
        nbuf = jnp.zeros(n, jnp.bool_)
        out_nulls[name] = nbuf.at[s_pos].set(lane)
    out = StreamChunk(
        columns=cols, valid=chunk.valid & active, nulls=out_nulls,
        ops=chunk.ops,
    )
    return table, new_accums, sdirty, out, saw_delete, dropped, ooo


# ---------------------------------------------------------------------------
# EOWC over-window: complete-partition batch compute at window close
# ---------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=("names", "calls", "part_keys", "order_col", "win_col"),
)
def _eowc_over_emit(
    buf,
    bnulls,
    valid,
    seq,
    cutoff,
    names: Tuple[str, ...],
    calls: Tuple[WindowCall, ...],
    part_keys: Tuple[str, ...],
    order_col: str,
    win_col: str,
):
    """Sort closed rows by (partition, order, seq) and compute EVERY
    window call on the complete partitions in one program. Closed
    partitions are final (watermark contract), so lead/FOLLOWING frames
    need no hold-back: beyond-partition-end is NULL / clipped, exactly
    SQL's frame semantics on a finished window."""
    cap = valid.shape[0]
    closed = valid & (buf[win_col] < cutoff)
    open_flag = (~closed).astype(jnp.int32)
    sort_in = (
        (open_flag,)
        + tuple(buf[k] for k in part_keys)
        + (buf[order_col], seq)
        + (jnp.arange(cap, dtype=jnp.int32),)
    )
    nk = 3 + len(part_keys)
    sorted_all = jax.lax.sort(sort_in, num_keys=nk)
    order_idx = sorted_all[-1]  # original slot of each sorted position
    closed_s = closed[order_idx]
    s = lambda a: a[order_idx]
    pk_s = [s(buf[k]) for k in part_keys]
    v_order = s(buf[order_col])

    idx = jnp.arange(cap, dtype=jnp.int64)
    prev_ne = jnp.zeros(cap, jnp.bool_)
    for lane in pk_s:
        prev_ne = prev_ne | jnp.concatenate(
            [jnp.ones(1, jnp.bool_), lane[1:] != lane[:-1]]
        )
    trans = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), closed_s[1:] != closed_s[:-1]]
    )
    boundary = prev_ne | trans
    boundary = boundary.at[0].set(True)
    gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    seg_start = jax.ops.segment_max(
        jnp.where(boundary, idx, 0), gid, num_segments=cap
    )[gid]
    in_seg = idx - seg_start  # 0-based index within the partition

    def shifted(vals, nullm, d):
        """(value, isnull) of the row d positions away within the SAME
        closed partition; beyond it -> (0, NULL)."""
        j = idx + d
        jc = jnp.clip(j, 0, cap - 1)
        ok = (
            (j >= 0)
            & (j < cap)
            & (gid[jc] == gid)
            & closed_s[jc]
            & closed_s
        )
        return (
            jnp.where(ok, vals[jc], 0),
            jnp.where(ok, nullm[jc], True),
        )

    MAXI = jnp.iinfo(jnp.int64).max
    MINI = jnp.iinfo(jnp.int64).min
    out_sorted: Dict[str, jnp.ndarray] = {}
    out_nulls_sorted: Dict[str, jnp.ndarray] = {}
    zero_nulls = jnp.zeros(cap, jnp.bool_)
    for c in calls:
        if c.input is not None:
            v = s(buf[c.input]).astype(jnp.int64)
            vnull = s(bnulls[c.input]) if c.input in bnulls else zero_nulls
        if c.kind == "row_number":
            o, onull = in_seg + 1, zero_nulls
        elif c.kind in ("rank", "dense_rank"):
            pv = jnp.concatenate([jnp.zeros(1, v_order.dtype), v_order[:-1]])
            vb = boundary | (v_order != pv)
            cum_vb_all = jnp.cumsum(vb.astype(jnp.int64))
            seg_vb = jax.ops.segment_max(
                jnp.where(boundary, cum_vb_all - 1, MINI),
                gid,
                num_segments=cap,
            )[gid]
            if c.kind == "dense_rank":
                o = cum_vb_all - seg_vb
            else:
                # segmented prefix max with boundary reset: a plain max
                # scan would leak a previous partition's group starts
                def reset_max(a, b):
                    fa, va = a
                    fb, vb_ = b
                    return fa | fb, jnp.where(fb, vb_, jnp.maximum(va, vb_))

                _, grp_start = jax.lax.associative_scan(
                    reset_max, (boundary, jnp.where(vb, in_seg, MINI))
                )
                o = grp_start + 1
            onull = zero_nulls
        elif c.kind in ("lead", "lag"):
            d = c.offset if c.kind == "lead" else -c.offset
            o, onull = shifted(v, vnull, d)
        elif c.frame is not None:
            lo, hi = c.frame
            if c.kind == "count":
                v, vnull = jnp.ones(cap, jnp.int64), zero_nulls
            ident = (
                MAXI if c.kind == "min" else MINI if c.kind == "max" else 0
            )
            comb = (
                jnp.minimum
                if c.kind == "min"
                else jnp.maximum
                if c.kind == "max"
                else (lambda a, b: a + b)
            )
            acc = jnp.full(cap, ident, jnp.int64)
            any_real = zero_nulls
            for d in range(lo, hi + 1):
                sv, sn = shifted(v, vnull, d)
                real = ~sn
                acc = comb(acc, jnp.where(real, sv, ident))
                any_real = any_real | real
            if c.kind == "count":
                o, onull = acc, zero_nulls
            else:
                o, onull = acc, ~any_real
        else:
            # running UNBOUNDED PRECEDING .. CURRENT ROW
            if c.kind == "count":
                real = closed_s
                vv = jnp.ones(cap, jnp.int64)
            else:
                real = closed_s & ~vnull
                vv = v
            if c.kind == "sum" or c.kind == "count":
                vv = jnp.where(real, vv, 0)
                csum = jnp.cumsum(vv)
                base = jax.ops.segment_max(
                    jnp.where(boundary, csum - vv, MINI),
                    gid,
                    num_segments=cap,
                )[gid]
                o, onull = csum - base, zero_nulls
            else:
                sent = MAXI if c.kind == "min" else MINI
                vv = jnp.where(real, vv, sent)

                def op(a, b):
                    fa, va, ra = a
                    fb, vb_, rb = b
                    cmb = (
                        jnp.minimum if c.kind == "min" else jnp.maximum
                    )
                    return (
                        fa | fb,
                        jnp.where(fb, vb_, cmb(va, vb_)),
                        jnp.where(fb, rb, ra | rb),
                    )

                _, o, has = jax.lax.associative_scan(
                    op, (boundary, vv, real)
                )
                onull = ~has
        out_sorted[c.output] = o
        out_nulls_sorted[c.output] = onull

    out_cols = {n: s(buf[n]) for n in names}
    out_cols.update(out_sorted)
    out_nulls = {n: s(bnulls[n]) for n in bnulls}
    out_nulls.update(out_nulls_sorted)
    new_valid = valid & ~closed
    return (
        out_cols,
        out_nulls,
        closed_s,
        new_valid,
        jnp.sum(closed.astype(jnp.int32)),
    )


class EowcOverWindowExecutor(ArenaBufferedExecutor):
    """Emit-on-window-close window functions (over_window/eowc.rs:88):
    rows buffer in a device arena until the watermark closes their
    window column; complete partitions then compute EVERY call — incl.
    lead/lag and static ROWS frames — in one fused sorted-segment
    program. The partition key must include the window column (the EOWC
    contract: a closed partition receives no further rows)."""

    def __init__(
        self,
        partition_by: Sequence[str],
        order_col: str,
        calls: Sequence[WindowCall],
        schema_dtypes: Dict[str, object],
        win_col: Optional[str] = None,
        capacity: int = 1 << 14,
        nullable: Sequence[str] = (),
        table_id: str = "eowc_over",
    ):
        self.part_keys = tuple(partition_by)
        self.order_col = order_col
        self.win_col = win_col or self.part_keys[0]
        if self.win_col not in self.part_keys:
            raise ValueError(
                "the window column must be one of the partition keys "
                "(a closed partition may receive no further rows)"
            )
        self.calls = tuple(calls)
        _no_counted_input(self.calls)
        for c in self.calls:
            if (
                c.kind in ("rank", "dense_rank")
                and c.input != self.order_col
            ):
                raise ValueError(
                    f"{c.kind} ranks by the executor's order column "
                    f"{self.order_col!r}; got input {c.input!r}"
                )
        super().__init__(schema_dtypes, capacity, nullable, table_id)

    def lint_info(self):
        info = super().lint_info()
        # complete-partition compute appends every call's output lane
        info["adds"] = {c.output: jnp.int64 for c in self.calls}
        info["keys"] = self.part_keys
        # EOWC contract: partitions only close when a watermark on the
        # window column passes them
        info["window_key"] = self.win_col
        return info

    def trace_contract(self):
        contract = super().trace_contract()
        contract["hot_methods"] = ("on_watermark",)
        return contract

    def on_watermark(self, watermark):
        if watermark.column != self.win_col:
            return watermark, []
        cutoff = jnp.asarray(watermark.value, jnp.int64)
        out_cols, out_nulls, out_valid, self.valid, n_closed = (
            _eowc_over_emit(
                self.buf,
                self.bnulls,
                self.valid,
                self.seq,
                cutoff,
                self.names,
                self.calls,
                self.part_keys,
                self.order_col,
                self.win_col,
            )
        )
        if int(n_closed) == 0:
            return watermark, []
        chunk = StreamChunk(
            columns=out_cols,
            valid=out_valid,
            nulls=out_nulls,
            ops=jnp.zeros(self.capacity, jnp.int32),
        )
        return watermark, [chunk]

    _arena_name = "EOWC over-window arena"


class OverWindowExecutor(Executor, Checkpointable):
    """Append-only window functions: ROW_NUMBER / running COUNT / SUM /
    MIN / MAX / LAG / RANK / DENSE_RANK per partition in arrival order
    (rank kinds require arrival order == ORDER BY order; violations
    latch and raise at the barrier). Checkpointable: partition keys +
    every accumulator lane persist as one state table, so a window MV
    survives recovery bit-exactly."""

    def __init__(
        self,
        partition_by: Sequence[str],
        calls: Sequence[WindowCall],
        schema_dtypes: Dict[str, object],
        capacity: int = 1 << 14,
        table_id: str = "over_window",
    ):
        self.part_keys = tuple(partition_by)
        self.calls = tuple(calls)
        _no_counted_input(self.calls)
        for c in self.calls:
            if c.kind == "lead" or c.frame is not None:
                raise ValueError(
                    f"{c.kind}/frames need future rows: use "
                    "EowcOverWindowExecutor (emit on window close)"
                )
            if c.kind == "lag" and c.offset != 1:
                raise ValueError(
                    "streaming lag supports offset=1 only; use "
                    "EowcOverWindowExecutor for lag(k)"
                )
        self.table_id = table_id
        self._dtypes = {
            k: jnp.dtype(v) for k, v in schema_dtypes.items()
        }
        self.table = HashTable.create(
            capacity,
            tuple(jnp.dtype(schema_dtypes[k]) for k in self.part_keys),
        )
        self.accums = {}
        self._accum_inits = {}
        for c in self.calls:
            for name in _accum_names(c):
                init = _accum_init(c) if name == c.output else 0
                self._accum_inits[name] = init
                self.accums[name] = jnp.full(capacity, init, jnp.int64)
        self.sdirty = jnp.zeros(capacity, jnp.bool_)
        self.stored = jnp.zeros(capacity, jnp.bool_)
        self._bound = 0
        self._saw_delete = jnp.zeros((), jnp.bool_)
        self._dropped = jnp.zeros((), jnp.bool_)
        self._ooo = jnp.zeros((), jnp.bool_)

    def lint_info(self):
        requires = set(self.part_keys)
        for c in self.calls:
            if c.input is not None:
                requires.add(c.input)
        return {
            "requires": tuple(sorted(requires)),
            "expects": {
                k: self._dtypes[k]
                for k in sorted(requires)
                if k in self._dtypes
            },
            "adds": {c.output: jnp.int64 for c in self.calls},
            "keys": self.part_keys,
            "table_ids": (self.table_id,),
        }

    def state_nbytes(self) -> int:
        """Device bytes held (host-side estimate; no sync)."""
        return sum(
            leaf.nbytes
            for leaf in jax.tree.leaves(
                (self.table, self.accums, self.sdirty, self.stored)
            )
        )

    def trace_contract(self):
        return {
            "kind": "device",
            "trace_step": lambda c: _over_step(
                self.table,
                self.accums,
                self.sdirty,
                c,
                self.calls,
                self.part_keys,
            ),
            "state": (self.table, self.accums),
            "donate": True,
            "emission": "passthrough",
        }

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        for c in self.calls:
            if c.kind in ("rank", "dense_rank") and c.input in chunk.nulls:
                raise ValueError(
                    f"rank order column {c.input!r} carries a null lane "
                    "(NULL ordering unsupported)"
                )
        self._maybe_grow(chunk.capacity)
        self._bound += chunk.capacity
        self.table, self.accums, self.sdirty, out, sd, dr, ooo = _over_step(
            self.table, self.accums, self.sdirty, chunk, self.calls,
            self.part_keys,
        )
        self._saw_delete = self._saw_delete | sd
        self._dropped = self._dropped | dr
        self._ooo = self._ooo | ooo
        return [out]

    def _maybe_grow(self, incoming: int):
        cap = self.table.capacity
        if self._bound + incoming <= cap * GROW_AT:
            return
        claimed = int(self.table.occupancy())
        new_cap = plan_rehash(cap, incoming, claimed, claimed, GROW_AT)
        if new_cap is not None:
            keep = self.table.fp1 != jnp.uint32(0)
            new = HashTable.create(
                new_cap, tuple(k.dtype for k in self.table.keys)
            )
            new, slots, _, _ = lookup_or_insert(new, self.table.keys, keep)
            new = set_live(new, jnp.where(keep, slots, -1), self.table.live)
            idx = jnp.where(keep, slots, new_cap)
            self.accums = {
                # unclaimed slots must keep each lane's INIT value (a
                # zero base would corrupt running min/max for new
                # partitions landing there)
                name: jnp.full(new_cap, self._accum_inits[name], jnp.int64)
                .at[idx]
                .set(a, mode="drop")
                for name, a in self.accums.items()
            }
            self.sdirty = (
                jnp.zeros(new_cap, jnp.bool_)
                .at[idx]
                .set(self.sdirty, mode="drop")
            )
            self.stored = (
                jnp.zeros(new_cap, jnp.bool_)
                .at[idx]
                .set(self.stored, mode="drop")
            )
            self.table = new
            claimed = int(self.table.occupancy())
        self._bound = claimed

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        from risingwave_tpu.ops.hash_table import stage_scalars

        self._staged_scalars = stage_scalars(
            self._saw_delete, self._dropped, self._ooo
        )
        if barrier is None:  # direct drive: checks fire inline
            self.finish_barrier()
        return []

    def _on_barrier_scalars(self, vals) -> None:
        sd, dr, ooo = vals
        if sd:
            raise RuntimeError(
                "append-only OverWindow received a DELETE (the general "
                "retractable executor is not implemented)"
            )
        if dr:
            raise RuntimeError("OverWindow partition table overflowed")
        if ooo:
            raise RuntimeError(
                "rank/dense_rank saw out-of-order arrivals: the "
                "append-only OverWindow requires arrival order to match "
                "ORDER BY (sort upstream, e.g. with the EOWC sort)"
            )

    # -- integrity --------------------------------------------------------
    def digest_lanes(self):
        lanes = {f"k{i}": k for i, k in enumerate(self.table.keys)}
        for name, a in self.accums.items():
            lanes[f"acc_{name}"] = a
        return lanes, self.table.fp1 != 0

    def state_digest(self) -> int:
        from risingwave_tpu.integrity import host_digest

        return host_digest(*self.digest_lanes())

    # -- checkpoint/restore ----------------------------------------------
    def checkpoint_delta(self) -> List[StateDelta]:
        # partitions never die in the append-only executor: alive =
        # every claimed slot (fp1 != 0), so there are no tombstones
        marks = classify_marks(self.sdirty, self.table.fp1, self.stored)
        self.sdirty, self.stored = marks.sdirty, marks.stored
        if not len(marks):
            return []
        lanes = {f"k{i}": l for i, l in enumerate(self.table.keys)}
        key_names = tuple(lanes)
        for name, a in self.accums.items():
            lanes[f"acc_{name}"] = a
        pulled = pull_rows(lanes, marks)
        keys = {k: pulled[k] for k in key_names}
        vals = {k: v for k, v in pulled.items() if k not in key_names}
        return [
            StateDelta(self.table_id, keys, vals, marks.tombstone, key_names)
        ]

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        n = len(next(iter(key_cols.values()))) if key_cols else 0
        key_dtypes = tuple(k.dtype for k in self.table.keys)
        cap = grow_pow2(n, self.table.capacity, GROW_AT)
        table = HashTable.create(cap, key_dtypes)
        self.accums = {
            name: jnp.full(cap, self._accum_inits[name], jnp.int64)
            for name in self.accums
        }
        self.sdirty = jnp.zeros(cap, jnp.bool_)
        self.stored = jnp.zeros(cap, jnp.bool_)
        if n:
            lanes = tuple(
                jnp.asarray(np.asarray(key_cols[f"k{i}"], dtype=d))
                for i, d in enumerate(key_dtypes)
            )
            table, slots, _, _ = lookup_or_insert(
                table, lanes, jnp.ones(n, jnp.bool_)
            )
            table = set_live(table, slots, True)
            self.stored = self.stored.at[slots].set(True)
            for name in self.accums:
                self.accums[name] = (
                    self.accums[name]
                    .at[slots]
                    .set(jnp.asarray(value_cols[f"acc_{name}"]))
                )
        self.table = table
        self._bound = int(n)
        self._saw_delete = jnp.zeros((), jnp.bool_)
        self._dropped = jnp.zeros((), jnp.bool_)
        self._ooo = jnp.zeros((), jnp.bool_)


# ---------------------------------------------------------------------------
# General (retractable) over-window
# ---------------------------------------------------------------------------

# What a step hands on is sized from what it changed: the smallest of
# ``_EMIT_FLOOR`` x 4^i up to ``_EMIT_MAX`` that holds the larger delta
# (never more than the arena), and a delta beyond ``_EMIT_MAX`` goes in
# further rounds of that size. Both sizes are compiled, with what
# follows the executor, when a graph-mode view is created.
_EMIT_MAX = 1 << 16


def emission_sizes(capacity: int) -> Tuple[int, ...]:
    """The chunk sizes a step's two deltas are handed on in."""
    sizes, lanes = [], _EMIT_FLOOR
    while lanes < min(capacity, _EMIT_MAX):
        sizes.append(lanes)
        lanes *= 4
    return tuple(sizes) + (min(capacity, _EMIT_MAX),)


def step_widths(capacity: int) -> Tuple[int, ...]:
    """The widths a step is compiled at: twice each emission size. The
    epoch's chunks are laid end to end into the smallest that holds
    them (``_general_over_lay``), and the largest is the most the
    executor holds before it steps. A Top-N's barrier hands on a retract
    and an insert chunk, each of the smallest of ITS sizes that holds
    its rows: two of one size fill a width, else lanes are left over."""
    return tuple(2 * lanes for lanes in emission_sizes(capacity))


@partial(
    jax.jit,
    static_argnames=("width", "lanes", "nullable"),
    donate_argnums=(0,),
)
@jax.named_scope("over/lay")
def _general_over_lay(
    laid: Optional[StreamChunk],
    chunk: StreamChunk,
    at,
    width: int,
    lanes: Tuple[Tuple[str, object], ...],
    nullable: Tuple[str, ...],
) -> StreamChunk:
    """``chunk``'s rows at lanes ``at`` and on of the epoch's one chunk
    of ``width`` lanes (made here, with no valid row, where ``laid`` is
    None): the columns the arena keeps, in its types, and a NULL lane
    for each column that may hold one, whatever else the chunk carries.
    One program a pair of widths; where the chunk lands is a value."""
    piece = StreamChunk(
        columns={name: chunk.col(name).astype(d) for name, d in lanes},
        valid=chunk.valid,
        nulls={
            name: chunk.nulls.get(name, jnp.zeros(chunk.capacity, jnp.bool_))
            for name in nullable
        },
        ops=chunk.ops,
    )
    if laid is None:
        laid = jax.tree.map(lambda a: jnp.zeros(width, a.dtype), piece)
    return _put(laid, piece, at)


def _shift(a, d: int, fill):
    """``a[i + d]`` at lane i, ``fill`` where that lies outside: a
    static slice, where a gather of every lane would cost the device
    tens of milliseconds a frame row."""
    if d == 0:
        return a
    pad = jnp.full(abs(d), fill, a.dtype)
    if d > 0:
        return jnp.concatenate([a[d:], pad])
    return jnp.concatenate([pad, a[:d]])


def _seg_any(seg_start, last, x):
    """Whether any lane of each lane's segment holds ``x``, from where
    the segments start (a lane a lane) and which lanes end one: the
    nearest lane before and the nearest after that holds it, each
    against the segment's own bounds. Four running extremes and no
    gather; an ``associative_scan`` of this length takes the TPU's
    compiler the better part of an hour (PERF.md 6, PR 49)."""
    lanes = x.shape[0]
    at = jnp.arange(lanes, dtype=jnp.int32)
    seg_end = jax.lax.cummin(jnp.where(last, at, lanes), reverse=True)
    before = jax.lax.cummax(jnp.where(x, at, -1))
    after = jax.lax.cummin(jnp.where(x, at, lanes), reverse=True)
    return (before >= seg_start) | (after <= seg_end)


@partial(
    jax.jit,
    static_argnames=("calls", "part_keys", "order_col", "pk", "lane_names"),
    donate_argnums=(0, 1, 2, 3, 4),
)
def _general_over_step(
    table: HashTable,
    buf: Dict[str, jnp.ndarray],
    bnulls: Dict[str, jnp.ndarray],
    present: jnp.ndarray,
    sdirty: jnp.ndarray,
    em: Dict[str, jnp.ndarray],
    emnulls: Dict[str, jnp.ndarray],
    em_valid: jnp.ndarray,
    chunk: StreamChunk,
    calls: Tuple[WindowCall, ...],
    part_keys: Tuple[str, ...],
    order_col: str,
    pk: Tuple[str, ...],
    lane_names: Tuple[str, ...],
):
    """One retractable over-window step (general.rs:49 the TPU way):
    apply the chunk's inserts/deletes to the pk-keyed row arena, mark
    every touched partition dirty, order the arena by (partition, the
    order column, the stream key) and recompute EVERY window call over
    the dirty partitions, then diff against the previously-emitted
    lanes. ``chunk`` is an epoch's chunks laid end to end in the order
    they came (``_general_over_lay``): of a stream key's rows the last
    stands (``last_occurrence_mask``), a delete may meet a row the
    same chunk brought (``_chunk_dup``) and a row that left a
    partition dirties it through a ghost entry, so what is diffed is
    the epoch's net change whatever chunks it came in. The reference
    walks per-row affected frame ranges (frame_finder.rs); here whole
    partitions are recomputed in one sorted-segment program and the
    diff comes out the same. What the program costs is the arena's
    capacity, whatever the chunk held (PERF.md 6, PR 49): the order is
    three two-operand sorts and as many gathers of every lane, the
    lanes the calls read are gathered into it and their results
    gathered back, and the scans between are next to nothing — which
    is why it runs once a barrier and not once a chunk (PR 50).

    Returns the arena's new state, each call's values and NULL flags a
    slot, the slots to retract and to insert (``_general_over_emit``
    gathers them, ``_general_over_commit`` adopts them) and ``status``
    = [dropped latch, bad-delete latch, valid rows of the chunk, rows
    to retract, rows to insert, dirty partitions, rows they hold]."""
    with jax.named_scope("over/arena"):
        cap = present.shape[0]
        n = chunk.capacity
        total = cap + n  # sort domain: arena + ghost entries (one per row)
        rows_active = chunk.valid
        signs = chunk.effective_signs()
        is_ins = signs > 0
        is_del = rows_active & (signs < 0)

        keys = tuple(chunk.col(k) for k in pk)
        table, slots, found, _ = lookup_or_insert(table, keys, rows_active)
        gslots = jnp.clip(slots, 0, cap - 1)
        dropped = jnp.any(rows_active & (slots < 0))
        pre_present = present[gslots]
        dup = _chunk_dup(slots, rows_active)
        # a DELETE must target a currently-present pk (or one produced
        # earlier in this very chunk); anything else is upstream
        # inconsistency (the reference's consistency check)
        bad_delete = jnp.any(
            is_del & ~dup & ~(slots < 0) & ~(found & pre_present)
        )

        # last occurrence per pk wins (within-chunk -old/+new updates);
        # the table's live lane tracks the final presence so dead slots are
        # reclaimed at the next rehash
        writer = last_occurrence_mask(slots, rows_active)
        table = set_live(table, jnp.where(writer, slots, -1), is_ins)

        # ghost entries: a same-chunk partition-key move leaves the OLD
        # partition with no touched member (the slot now sorts under its
        # new partition), so its remaining rows would keep stale window
        # values. Emit one non-live ghost per moved row under the OLD
        # (emitted) partition keys purely to carry the dirty mark there.
        moved = jnp.zeros(n, jnp.bool_)
        for k in part_keys:
            moved = moved | (
                em[k][gslots] != chunk.col(k).astype(jnp.int64)
            )
        ghost = writer & is_ins & em_valid[gslots] & moved

        target = jnp.where(writer, slots, cap)
        present = present.at[target].set(is_ins, mode="drop")
        for name in lane_names:
            buf[name] = (
                buf[name]
                .at[target]
                .set(chunk.col(name).astype(buf[name].dtype), mode="drop")
            )
            if name in bnulls:
                lane = chunk.nulls.get(name, jnp.zeros(n, jnp.bool_))
                bnulls[name] = bnulls[name].at[target].set(lane, mode="drop")
        touched = (
            jnp.zeros(cap, jnp.bool_)
            .at[jnp.where(rows_active, slots, cap)]
            .set(True, mode="drop")
        )
        sdirty = sdirty | touched

    with jax.named_scope("over/sort"):
        # ---- order the arena: members = rows needing compute or
        # retraction, by (partition, live rows first, the order column, the
        # stream key), every other lane behind them. The key's digits are
        # packed into as many 32-bit words as they hold information (a
        # lane that is no member reads a member's values, so that it
        # widens no digit's range)
        member = present | em_valid
        member_e = jnp.concatenate([member, ghost])
        present_e = jnp.concatenate([present, jnp.zeros(n, jnp.bool_)])
        MINI = jnp.iinfo(jnp.int64).min

        def keyed(own, emitted, ghosts):
            """A key lane over the sort domain: a row's own value where it
            is present, else the one it was handed on with; the ghosts'."""
            lane = jnp.concatenate(
                [
                    jnp.where(present, own.astype(jnp.int64), emitted),
                    ghosts.astype(jnp.int64),
                ]
            )
            fill = jnp.max(jnp.where(member_e, lane, MINI))
            return jnp.where(member_e, lane, fill)

        plane_e = tuple(
            keyed(buf[k], em[k], em[k][gslots]) for k in part_keys
        )
        order_e = keyed(buf[order_col], em[order_col], em[order_col][gslots])
        digits: Tuple[jnp.ndarray, ...] = ()
        for k, lane in reversed(tuple(zip(pk, table.keys))):
            digits += _digits(keyed(lane, lane.astype(jnp.int64), chunk.col(k)))
        digits += _digits(order_e)
        digits += ((~present_e).astype(jnp.uint32),)  # live rows first
        for lane in reversed(plane_e):
            digits += _digits(lane)
        digits += ((~member_e).astype(jnp.uint32),)
        # (one two-operand sort a word that differs between lanes: what a
        # sort of many operands costs the TPU's compiler is said there)
        s_idx, _ = _order_by_words(jnp.stack(_packed_words(digits)[0]))
        # where each slot stands in the order (the ghosts', past ``cap``,
        # are not asked for)
        inv = jax.lax.sort(
            (s_idx, jnp.arange(total, dtype=jnp.int32)), num_keys=1
        )[1][:cap]

    with jax.named_scope("over/frame"):
        def s(a, fill=0):
            """Gather an arena lane into the sorted domain (ghost entries
            read the fill value — they are never live)."""
            return jnp.concatenate(
                [a, jnp.full(n, fill, a.dtype)]
            )[s_idx]

        # one gather for every flag: member, live, touched, and each input
        # column's NULL
        null_cols = tuple(
            dict.fromkeys(
                c.input for c in calls
                if c.input is not None and c.input in bnulls
            )
        )
        flags_e = (
            member_e.astype(jnp.int32)
            | (present_e.astype(jnp.int32) << 1)
            | (jnp.concatenate([touched, ghost]).astype(jnp.int32) << 2)
        )
        for i, name in enumerate(null_cols):
            flags_e = flags_e | (
                jnp.concatenate([bnulls[name], jnp.ones(n, jnp.bool_)])
                .astype(jnp.int32) << (3 + i)
            )
        flags_s = flags_e[s_idx]
        member_s = (flags_s & 1) > 0
        live_s = (flags_s & 2) > 0
        touched_s = (flags_s & 4) > 0
        plane_s = [p[s_idx] for p in plane_e]

        arange = jnp.arange(total, dtype=jnp.int32)
        first = jnp.zeros(total, jnp.bool_).at[0].set(True)
        boundary = first | jnp.concatenate(
            [jnp.ones(1, jnp.bool_), member_s[1:] != member_s[:-1]]
        )
        for lane in plane_s:
            boundary = boundary | jnp.concatenate(
                [jnp.ones(1, jnp.bool_), lane[1:] != lane[:-1]]
            )
        gid = jnp.cumsum(boundary.astype(jnp.int32)) - 1
        # where each lane's segment starts, and what lies there
        seg_start = jax.lax.cummax(jnp.where(boundary, arange, 0))
        in_seg = (arange - seg_start).astype(jnp.int64)
        last = jnp.concatenate([boundary[1:], jnp.ones(1, jnp.bool_)])
        dirty_s = _seg_any(seg_start, last, touched_s) & member_s

        MAXI = jnp.iinfo(jnp.int64).max
        zero_nulls = jnp.zeros(total, jnp.bool_)

        def shifted(vals, nullm, d):
            ok = (_shift(gid, d, -1) == gid) & _shift(live_s, d, False) & live_s
            return (
                jnp.where(ok, _shift(vals, d, 0), 0),
                jnp.where(ok, _shift(nullm, d, True), True),
            )

        gathered: Dict[str, jnp.ndarray] = {}
        out_sorted: Dict[str, jnp.ndarray] = {}
        out_nulls_sorted: Dict[str, jnp.ndarray] = {}
        for c in calls:
            if c.input is not None:
                if c.input not in gathered:
                    gathered[c.input] = s(buf[c.input]).astype(jnp.int64)
                v = gathered[c.input]
                vnull = (
                    (flags_s & (8 << null_cols.index(c.input))) > 0
                    if c.input in null_cols
                    else zero_nulls
                )
            if c.kind == "row_number":
                o, onull = in_seg + 1, zero_nulls
            elif c.kind in ("rank", "dense_rank"):
                if order_col not in gathered:
                    gathered[order_col] = order_e[s_idx]
                v_order = gathered[order_col]
                pv = jnp.concatenate(
                    [jnp.zeros(1, v_order.dtype), v_order[:-1]]
                )
                vb = boundary | (v_order != pv)
                if c.kind == "dense_rank":
                    cum_vb = jnp.cumsum(vb.astype(jnp.int64))
                    o = cum_vb - cum_vb[seg_start] + 1
                else:
                    # where the run of rows equal in the order began
                    run_start = jax.lax.cummax(jnp.where(vb, arange, 0))
                    o = (run_start - seg_start).astype(jnp.int64) + 1
                onull = zero_nulls
            elif c.kind in ("lead", "lag"):
                d = c.offset if c.kind == "lead" else -c.offset
                o, onull = shifted(v, vnull, d)
            elif c.frame is not None:
                lo, hi = c.frame
                if c.kind == "count":
                    v = jnp.ones(total, jnp.int64)
                    vnull = zero_nulls if c.input is None else vnull
                ident = (
                    MAXI if c.kind == "min" else MINI if c.kind == "max" else 0
                )
                comb = (
                    jnp.minimum
                    if c.kind == "min"
                    else jnp.maximum
                    if c.kind == "max"
                    else (lambda a, b: a + b)
                )
                acc = jnp.full(total, ident, jnp.int64)
                any_real = zero_nulls
                for d in range(lo, hi + 1):
                    sv, sn = shifted(v, vnull, d)
                    real = ~sn
                    acc = comb(acc, jnp.where(real, sv, ident))
                    any_real = any_real | real
                if c.kind == "count":
                    o, onull = acc, zero_nulls
                else:
                    o, onull = acc, ~any_real
            else:
                # running UNBOUNDED PRECEDING .. CURRENT ROW
                if c.kind == "count":
                    real = live_s if c.input is None else live_s & ~vnull
                    vv = jnp.ones(total, jnp.int64)
                else:
                    real = live_s & ~vnull
                    vv = v
                if c.kind in ("sum", "count"):
                    vv = jnp.where(real, vv, 0)
                    csum = jnp.cumsum(vv)
                    o = csum - (csum - vv)[seg_start]
                    onull = zero_nulls
                else:
                    sent = MAXI if c.kind == "min" else MINI
                    vv = jnp.where(real, vv, sent)

                    def op(a, b):
                        fa, va, ra = a
                        fb, vb_, rb = b
                        cmb = jnp.minimum if c.kind == "min" else jnp.maximum
                        return (
                            fa | fb,
                            jnp.where(fb, vb_, cmb(va, vb_)),
                            jnp.where(fb, rb, ra | rb),
                        )

                    _, o, has = jax.lax.associative_scan(
                        op, (boundary, vv, real)
                    )
                    onull = ~has
            out_sorted[c.output] = o
            out_nulls_sorted[c.output] = onull

    with jax.named_scope("over/diff"):
        # ---- back to slots (a gather at each slot's place in the order;
        # one for the flags); diff against the emitted lanes
        out_flags = dirty_s.astype(jnp.int32)
        for i, c in enumerate(calls):
            out_flags = out_flags | (
                out_nulls_sorted[c.output].astype(jnp.int32) << (1 + i)
            )
        out_flags = out_flags[inv]
        dirty_slot = (out_flags & 1) > 0
        new_out = {name: o[inv] for name, o in out_sorted.items()}
        new_out_nulls = {
            c.output: (out_flags & (2 << i)) > 0 for i, c in enumerate(calls)
        }
        both = present & em_valid
        changed = jnp.zeros(cap, jnp.bool_)
        for name in lane_names:
            differs = buf[name].astype(jnp.int64) != em[name]
            if name in bnulls:
                # compare values only where both sides are non-NULL — the
                # cell under a NULL flag is an arbitrary fill
                cn, en = bnulls[name], emnulls[name]
                differs = (~cn & ~en & differs) | (cn != en)
            changed = changed | differs
        for c in calls:
            nn, en = new_out_nulls[c.output], emnulls[c.output]
            changed = changed | (
                jnp.where(~nn, new_out[c.output], 0)
                != jnp.where(~en, em[c.output], 0)
            ) | (nn != en)
        changed = changed & both
        retract = em_valid & dirty_slot & (~present | changed)
        insert = present & dirty_slot & (~em_valid | changed)
        sdirty = sdirty | retract | insert

        def count(mask):
            return jnp.sum(mask, dtype=jnp.int32)

        status = jnp.stack(
            [
                dropped.astype(jnp.int32),
                bad_delete.astype(jnp.int32),
                count(rows_active),
                count(retract),
                count(insert),
                count(boundary & dirty_s),
                count(dirty_s & live_s),
            ]
        )
    return (
        table, buf, bnulls, present, sdirty,
        new_out, new_out_nulls, retract, insert, status,
    )


@partial(jax.jit, static_argnames=("lanes", "lane_names", "out_names"))
@jax.named_scope("over/emit")
def _general_over_emit(
    buf, bnulls, em, emnulls, new_out, new_out_nulls, retract, insert,
    start, lanes: int, lane_names, out_names,
):
    """The ``start``-th to ``start + lanes``-th rows of a step's two
    deltas as two chunks of ``lanes`` lanes: the retractions as they
    were handed on (``em``), the insertions as they now stand. The rows
    are found and gathered a block a turn for as many turns as hold one
    (``top_n_plain._in_turns``): the lanes past the delta keep their
    zeros and cost nothing. Changes no state."""

    def delta(mask, cols, nulls, op):
        def turn(carry, at, pos, valid):
            block = (
                {name: jnp.where(valid, a[pos], 0)
                 for name, a in cols.items()},
                {name: a[pos] & valid for name, a in nulls.items()},
                valid,
            )
            return _put(carry, block, at)

        empty = (
            {name: jnp.zeros(lanes, jnp.int64) for name in cols},
            {name: jnp.zeros(lanes, jnp.bool_) for name in nulls},
            jnp.zeros(lanes, jnp.bool_),
        )
        (cols, nulls, valid), _ = _in_turns(
            _count_set(mask), lanes, start, turn, empty
        )
        return StreamChunk(
            columns=cols, valid=valid, nulls=nulls,
            ops=jnp.full(lanes, int(op), jnp.int32),
        )

    names = lane_names + out_names
    now = {name: buf[name].astype(jnp.int64) for name in lane_names}
    now.update(new_out)
    return (
        delta(retract, {name: em[name] for name in names}, emnulls,
              Op.DELETE),
        delta(insert, now, {**bnulls, **new_out_nulls}, Op.INSERT),
    )


@partial(
    jax.jit,
    static_argnames=("lane_names",),
    donate_argnums=(0, 1, 2),
)
@jax.named_scope("over/commit")
def _general_over_commit(
    em, emnulls, em_valid, buf, bnulls, new_out, new_out_nulls,
    retract, insert, lane_names,
):
    """Emitted state := what downstream now holds."""
    for name in lane_names:
        em[name] = jnp.where(insert, buf[name].astype(jnp.int64), em[name])
        if name in bnulls:
            emnulls[name] = jnp.where(insert, bnulls[name], emnulls[name])
    for name, o in new_out.items():
        em[name] = jnp.where(insert, o, em[name])
        emnulls[name] = jnp.where(
            insert, new_out_nulls[name], emnulls[name]
        )
    return em, emnulls, (em_valid & ~retract) | insert


def _chunk_dup(slots: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Rows whose slot already appeared earlier in the chunk (a delete
    may legitimately target a row inserted earlier in the same chunk,
    which lookup_or_insert reports as freshly inserted)."""
    return valid & ~first_occurrence_mask(slots, valid)


class GeneralOverWindowExecutor(Executor, Checkpointable):
    """General (retractable) window functions over partitions.

    Reference: src/stream/src/executor/over_window/general.rs:49 —
    handles inserts, deletes and updates ANYWHERE in the ORDER BY
    order, retracting and re-emitting every row whose window value
    changes. The reference computes per-row affected frame ranges
    (frame_finder.rs); the TPU re-design keeps all rows in a pk-keyed
    device arena and recomputes complete dirty partitions in one
    sorted-segment program per chunk, and the diff against the
    previously-emitted lanes yields the exact minimal retract/re-emit
    set. Rows of a partition equal in the order column stand in the
    order of their stream key ``pk``, as upstream's do.

    The recompute is NOT free: a step costs what the arena's CAPACITY
    costs, whatever the chunks held — at 2^22 lanes on a v5e 0.69 s of
    device time for 1,000 rows in a chunk of 65,536 lanes, most of it
    the order's sorts and the capacity-wide gathers around them
    (PERF.md 6, PR 49), and 0.84 s for 2,000 rows in one of 131,072:
    the chunk's part is 0.15 s for every 65,536 lanes, valid or not,
    each probed, scattered and gathered (PERF.md 6, PR 50). So it
    runs once a BARRIER: ``apply`` keeps the chunk and hands on nothing,
    ``on_barrier`` lays the epoch's chunks end to end, in the order
    they came, as one chunk of a declared width (``step_widths``) and
    steps over that, and the step's own netting of a stream key's
    ``-old / +new`` gives the epoch's net delta. Behind a Top-N, whose
    barrier hands on a retract and an insert chunk, that is one step
    where the parent ran two (``nexmark_q6.catchup``: 1,381 -> 840 ms
    of the step's device time an epoch of 32,768 events, 14,915 ->
    20,119 events/s; PERF.md 5 and 6, PR 50). No more lanes are
    kept than the widest step takes: a chunk that would pass that makes
    ``apply`` step what is kept first (an epoch wider than
    ``step_widths``' largest takes more steps than one), and a barrier
    that kept nothing runs no program. Nothing kept crosses a barrier,
    so a checkpoint reads the arena after the epoch's last step. What
    the step hands on follows what changed (``emission_sizes``).

    Supports every WindowCall kind including lead/lag(k), static ROWS
    frames and COUNT(col) (deletes may reopen any frame, so the general
    executor has no hold-back constraint — it simply recomputes).
    Checkpointable: current rows + emitted rows persist; recovery is
    bit-exact."""

    def __init__(
        self,
        partition_by: Sequence[str],
        order_col: str,
        pk: Sequence[str],
        calls: Sequence[WindowCall],
        schema_dtypes: Dict[str, object],
        capacity: int = 1 << 12,
        nullable: Sequence[str] = (),
        table_id: str = "general_over",
    ):
        self.part_keys = tuple(partition_by)
        self.order_col = order_col
        self.pk = tuple(pk)
        self.calls = tuple(calls)
        for c in self.calls:
            if c.kind in ("rank", "dense_rank") and c.input != order_col:
                raise ValueError(
                    f"{c.kind} ranks by the executor's order column "
                    f"{order_col!r}; got input {c.input!r}"
                )
        for nm, d in schema_dtypes.items():
            if not jnp.issubdtype(jnp.dtype(d), jnp.integer):
                raise ValueError(
                    f"general OverWindow lane {nm!r} has non-integer "
                    f"dtype {d}: emitted/diffed lanes are carried as "
                    "int64 (dictionary- or scale-encode upstream)"
                )
        self.lane_names = tuple(schema_dtypes)
        self.out_names = tuple(c.output for c in self.calls)
        self.schema_dtypes = dict(schema_dtypes)
        self.nullable = tuple(nullable)
        self.table_id = table_id
        self._frame_rows = max(
            (c.frame[1] - c.frame[0] + 1 for c in self.calls if c.frame),
            default=0,
        )
        self._lay_lanes = tuple(
            (n, jnp.dtype(d)) for n, d in schema_dtypes.items()
        )
        self._alloc(capacity)
        self._dropped = False
        self._bad_delete = False
        self._bound = 0
        self._epoch = _zero_epoch()
        # the epoch's chunks as they came
        self._held: List[StreamChunk] = []

    def push_widths(self, capacity: int) -> Tuple[int, ...]:
        """Any width of the push lattice: a chunk is kept as it comes
        and laid into a step's own width, and ``warm`` knows how."""
        return push_lattice(capacity)

    def lint_info(self):
        requires = set(self.part_keys) | set(self.pk) | {self.order_col}
        for c in self.calls:
            if c.input is not None:
                requires.add(c.input)
        return {
            "requires": tuple(sorted(requires)),
            "expects": {
                k: self.schema_dtypes[k]
                for k in sorted(requires)
                if k in self.schema_dtypes
            },
            "adds": {c.output: jnp.int64 for c in self.calls},
            "keys": self.part_keys,
            "state_pk": tuple(self.pk),
            "table_ids": (self.table_id,),
        }

    def _step(self, chunk: StreamChunk):
        return _general_over_step(
            self.table, self.buf, self.bnulls, self.present, self.sdirty,
            self.em, self.emnulls, self.em_valid, chunk,
            self.calls, self.part_keys, self.order_col, self.pk,
            self.lane_names,
        )

    def trace_contract(self):
        return {
            "kind": "device",
            "trace_step": self._step,
            "state": (self.table, self.buf, self.em),
            "donate": True,
            # the two deltas go on in chunks sized from their rows
            "emission": "bucketed",
            "emission_caps": emission_sizes(self.capacity),
        }

    def _alloc(self, cap: int):
        self.table = HashTable.create(
            cap, tuple(jnp.dtype(self.schema_dtypes[k]) for k in self.pk)
        )
        self.buf = {
            n: jnp.zeros(cap, jnp.dtype(d))
            for n, d in self.schema_dtypes.items()
        }
        self.bnulls = {n: jnp.zeros(cap, jnp.bool_) for n in self.nullable}
        self.present = jnp.zeros(cap, jnp.bool_)
        self.em = {
            n: jnp.zeros(cap, jnp.int64)
            for n in self.lane_names + self.out_names
        }
        self.emnulls = {
            n: jnp.zeros(cap, jnp.bool_)
            for n in self.nullable + self.out_names
        }
        self.em_valid = jnp.zeros(cap, jnp.bool_)
        self.sdirty = jnp.zeros(cap, jnp.bool_)
        self.stored = jnp.zeros(cap, jnp.bool_)

    @property
    def capacity(self) -> int:
        return self.present.shape[0]

    @property
    def row_bytes(self) -> int:
        """One input row's lanes, as the arena stores them."""
        return sum(a.dtype.itemsize for a in self.buf.values()) + len(
            self.bnulls
        )

    def state_nbytes(self) -> int:
        """Device bytes held (host-side estimate; no sync)."""
        return sum(
            leaf.nbytes
            for leaf in jax.tree.leaves((
                self.table, self.buf, self.bnulls, self.present,
                self.em, self.emnulls, self.em_valid,
                self.sdirty, self.stored,
            ))
        )

    def _laid(self, chunks: Sequence[StreamChunk], width: int):
        """``chunks`` end to end as one chunk of ``width`` lanes."""
        laid, at = None, 0
        for chunk in chunks:
            laid = _general_over_lay(
                laid, chunk, at, width, self._lay_lanes, self.nullable
            )
            at += chunk.capacity
        return laid

    def _run(self, chunk: StreamChunk, sizes=None):
        """The step's programs for ``chunk``, the epoch's chunks laid
        out as one: the arena's step, ONE read of its seven counts
        (which waits for it: what is handed on is sized from them, and
        the view behind this executor reads the chunks at once anyway),
        a round of the two deltas for every ``lanes`` rows the larger
        holds (every retraction before any insertion), and the adoption
        of what was handed on. ``sizes``: the warm-up's, a round of
        each whatever the counts."""
        (
            self.table, self.buf, self.bnulls, self.present, self.sdirty,
            new_out, new_nulls, retract, insert, status,
        ) = self._step(chunk)
        with device_read("over.status", lanes=status.shape[0]):
            status = jax.device_get(status).tolist()
        if sizes is None:
            rows = max(status[3], status[4])  # the larger delta
            caps = emission_sizes(self.capacity)
            lanes = next((c for c in caps if c >= rows), caps[-1])
            rounds = [(lanes, at) for at in range(0, max(rows, 1), lanes)]
        else:
            rounds = [(size, 0) for size in sizes]
        pairs = [
            _general_over_emit(
                self.buf, self.bnulls, self.em, self.emnulls, new_out,
                new_nulls, retract, insert, jnp.int32(at), size,
                self.lane_names, self.out_names,
            )
            for size, at in rounds
        ]
        self.em, self.emnulls, self.em_valid = _general_over_commit(
            self.em, self.emnulls, self.em_valid, self.buf, self.bnulls,
            new_out, new_nulls, retract, insert, self.lane_names,
        )
        return status, [r for r, _ in pairs] + [i for _, i in pairs]

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        """Keep the chunk for the barrier's step. Hands on nothing, but
        in an epoch wider than the widest step: what is kept is stepped
        before the chunk that would pass that width joins it."""
        for c in self.calls:
            if c.kind in ("rank", "dense_rank") and c.input in chunk.nulls:
                raise ValueError(
                    f"rank order column {c.input!r} carries a null lane "
                    "(NULL ordering unsupported)"
                )
        limit = step_widths(self.capacity)[-1]
        outs: List[StreamChunk] = []
        if self._held_lanes() + chunk.capacity > limit:
            outs = self._step_held()
        self._held.append(chunk)
        if chunk.capacity > limit:  # wider than it alone: as it comes
            outs += self._step_held()
        return outs

    def _held_lanes(self) -> int:
        # (known on the host: no read)
        return sum(c.capacity for c in self._held)

    def _step_held(self) -> List[StreamChunk]:
        """ONE step over every chunk kept, and what it hands on;
        nothing, and no program, where none is kept."""
        chunks, lanes = self._held, self._held_lanes()
        if not chunks:
            return []
        self._held = []
        self._maybe_grow(lanes)
        # the smallest declared width that holds them; their own where
        # none does (one chunk wider than all of them)
        width = next(
            (w for w in step_widths(self.capacity) if w >= lanes), lanes
        )
        with span(
            "over.step", table_id=self.table_id, capacity=self.capacity,
            chunk_lanes=width, calls=len(self.calls),
            frame_rows=self._frame_rows,
            row_bytes=self.row_bytes,
        ) as sp:
            status, outs = self._run(self._laid(chunks, width))
            dropped, bad, in_rows, n_ret, n_ins, parts, rows = status
            emit_lanes = sum(c.capacity for c in outs)
            sp.args.update(
                in_rows=in_rows, retract_rows=n_ret, insert_rows=n_ins,
                emit_lanes=emit_lanes,
            )
        # every valid row claims at most one slot
        self._bound += in_rows
        self._dropped |= bool(dropped)
        self._bad_delete |= bool(bad)
        e = self._epoch
        e["steps"] += 1
        e["chunks"] += len(chunks)
        e["in_rows"] += in_rows
        e["dirty_partitions"] += parts
        e["dirty_rows"] += rows
        e["retract_rows"] += n_ret
        e["insert_rows"] += n_ins
        e["emit_lanes"] += emit_lanes
        return outs

    # -- the warm-up pass --------------------------------------------------
    def warm(self, chunk: StreamChunk) -> List[StreamChunk]:
        """The programs a chunk of this shape can end in: its place in
        every declared width that holds it, as an epoch's first chunk
        and as a later one, a step at each of those widths, and a round
        of every emission size. A chunk with no valid row touches no
        slot, so the arena it hands back is the arena it was given."""
        widths = [
            w for w in step_widths(self.capacity) if w >= chunk.capacity
        ] or [chunk.capacity]
        for width in widths:
            # (a later chunk's program over the first one's lanes: there
            # is room for that at every width)
            laid = _general_over_lay(
                self._laid([chunk], width), chunk, 0, width,
                self._lay_lanes, self.nullable,
            )
            last = width == widths[-1]
            outs = self._run(
                laid, sizes=emission_sizes(self.capacity) if last else ()
            )[1]
        return outs

    def _maybe_grow(self, incoming: int):
        cap = self.capacity
        if self._bound + incoming <= cap * GROW_AT:
            return
        claimed = int(self.table.occupancy())
        survivors = int(
            jnp.sum(self.table.live | self.sdirty | self.stored)
        )
        new_cap = plan_rehash(cap, incoming, claimed, survivors, GROW_AT)
        if new_cap is not None:
            self._rehash(new_cap)
            claimed = int(self.table.occupancy())
        self._bound = claimed

    def _rehash(self, new_cap: int):
        # a slot survives iff someone still cares: live row, unflushed
        # emission-state change (sdirty), or a durable row whose
        # tombstone has not been staged yet (stored) — delete/insert
        # churn with fresh pks compacts instead of growing forever
        keep = (self.table.live | self.sdirty | self.stored) & (
            self.table.fp1 != jnp.uint32(0)
        )
        new = HashTable.create(
            new_cap, tuple(k.dtype for k in self.table.keys)
        )
        new, slots, _, _ = lookup_or_insert(new, self.table.keys, keep)
        new = set_live(new, jnp.where(keep, slots, -1), self.table.live)
        idx = jnp.where(keep, slots, new_cap)

        def mv(a, fill=0):
            return (
                jnp.full(new_cap, fill, a.dtype).at[idx].set(a, mode="drop")
            )

        self.buf = {n: mv(a) for n, a in self.buf.items()}
        self.bnulls = {n: mv(a) for n, a in self.bnulls.items()}
        self.present = mv(self.present)
        self.em = {n: mv(a) for n, a in self.em.items()}
        self.emnulls = {n: mv(a) for n, a in self.emnulls.items()}
        self.em_valid = mv(self.em_valid)
        self.sdirty = mv(self.sdirty)
        self.stored = mv(self.stored)
        self.table = new

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        # the epoch's step: before the latches are raised, and before
        # the checkpoint reads the arena
        outs = self._step_held()
        e, self._epoch = self._epoch, _zero_epoch()
        if e["steps"]:
            # the epoch's steps, from the counts each of them read
            with span(
                "over.barrier", table_id=self.table_id,
                emitted_rows=e["retract_rows"] + e["insert_rows"], **e,
            ):
                pass
            count = REGISTRY.counter
            count("over_window_steps_total").inc(
                e["steps"], table_id=self.table_id
            )
            count("over_window_buffered_chunks_total").inc(
                e["chunks"], table_id=self.table_id
            )
            count("over_window_input_rows_total").inc(
                e["in_rows"], table_id=self.table_id
            )
            count("over_window_emitted_rows_total").inc(
                e["retract_rows"] + e["insert_rows"], table_id=self.table_id
            )
        if self._dropped:
            raise RuntimeError("general OverWindow row arena overflowed")
        if self._bad_delete:
            raise RuntimeError(
                "general OverWindow received a DELETE for an unknown pk "
                "(inconsistent upstream)"
            )
        return outs

    # -- integrity --------------------------------------------------------
    def _lanes(self):
        """Every lane a slot's state is made of, by its checkpoint name
        (the key lanes first)."""
        lanes = {f"k{i}": k for i, k in enumerate(self.table.keys)}
        for n in self.lane_names:
            lanes[f"c_{n}"] = self.buf[n]
        for n, a in self.bnulls.items():
            lanes[f"cn_{n}"] = a
        for n, a in self.em.items():
            lanes[f"e_{n}"] = a
        for n, a in self.emnulls.items():
            lanes[f"en_{n}"] = a
        lanes["present"] = self.present
        return lanes

    def digest_lanes(self):
        return self._lanes(), self.present | self.em_valid

    def state_digest(self) -> int:
        from risingwave_tpu.integrity import host_digest

        return host_digest(*self.digest_lanes())

    # -- checkpoint/restore ----------------------------------------------
    def checkpoint_delta(self) -> List[StateDelta]:
        marks = classify_marks(
            self.sdirty, (self.present, self.em_valid), self.stored
        )
        self.sdirty, self.stored = marks.sdirty, marks.stored
        if not len(marks):
            return []
        key_names = tuple(f"k{i}" for i in range(len(self.table.keys)))
        pulled = pull_rows(self._lanes(), marks)
        keys = {k: pulled[k] for k in key_names}
        vals = {k: v for k, v in pulled.items() if k not in key_names}
        return [
            StateDelta(self.table_id, keys, vals, marks.tombstone, key_names)
        ]

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        n = len(next(iter(key_cols.values()))) if key_cols else 0
        cap = grow_pow2(max(n, 1), self.capacity, GROW_AT)
        self._alloc(cap)
        if n:
            key_dtypes = tuple(k.dtype for k in self.table.keys)
            lanes = tuple(
                jnp.asarray(np.asarray(key_cols[f"k{i}"], dtype=d))
                for i, d in enumerate(key_dtypes)
            )
            self.table, slots, _, _ = lookup_or_insert(
                self.table, lanes, jnp.ones(n, jnp.bool_)
            )
            self.table = set_live(self.table, slots, True)
            self.stored = self.stored.at[slots].set(True)

            def put(lane, values):
                return lane.at[slots].set(
                    jnp.asarray(np.asarray(values, lane.dtype))
                )

            # at a barrier everything current has been handed on
            self.present = put(self.present, value_cols["present"])
            self.em_valid = put(self.em_valid, value_cols["present"])
            for nme in self.lane_names:
                self.buf[nme] = put(self.buf[nme], value_cols[f"c_{nme}"])
            for prefix, group in (
                ("cn_", self.bnulls), ("e_", self.em), ("en_", self.emnulls)
            ):
                for nme in group:
                    if prefix + nme in value_cols:
                        group[nme] = put(
                            group[nme], value_cols[prefix + nme]
                        )
        self._bound = int(n)
        self._dropped = self._bad_delete = False
        self._epoch = _zero_epoch()
        self._held = []


def _zero_epoch() -> Dict[str, int]:
    """What ``over.barrier`` says of an epoch's steps."""
    return dict.fromkeys(
        (
            "steps", "chunks", "in_rows", "dirty_partitions", "dirty_rows",
            "retract_rows", "insert_rows", "emit_lanes",
        ),
        0,
    )
