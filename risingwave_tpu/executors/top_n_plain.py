"""Plain TopN — retractable ORDER BY ... LIMIT n maintenance.

Reference: src/stream/src/executor/top_n/top_n_plain.rs:77 — keeps all
input rows in a state table ordered by (order key, pk) and emits
deltas so downstream always holds exactly the current top n.

TPU re-design: the row store is a pk-keyed slot table (HashTable +
one lane per column); inserts/deletes are one fused scatter step per
chunk. The barrier ranks live rows ON DEVICE (ordered-float/int total
order + pk tiebreak via lexsort), pulls only the top n rows, and
diffs them against the host mirror of the previously-emitted top n —
so per-barrier host traffic is O(n), not O(state).
"""

from __future__ import annotations

import math
from functools import partial, reduce
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Barrier, Executor
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.ops.hash_table import (
    PROBE_STATS,
    HashTable,
    lookup_or_insert,
    lookup_or_insert_counted,
    note_probes,
    set_live,
)
from risingwave_tpu.array.lattice import (
    TOUCHED_MAX,
    emission_bucket,
    lattice_between,
    pow2_at_least,
)
from risingwave_tpu.ops.agg import note_touched
from risingwave_tpu.ops.bucketing import (
    BucketAllocator,
    BucketPolicy,
    needs_plan,
    plan_capacity,
)
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


@partial(jax.jit, static_argnames=("pk", "names"), donate_argnums=(0, 1, 2))
def _upsert_step(table, rows, sdirty, chunk: StreamChunk, pk, names):
    keys = tuple(chunk.col(k) for k in pk)
    signs = chunk.effective_signs()
    active = chunk.valid & (signs != 0)
    table, slots, _, _ = lookup_or_insert(table, keys, active)
    dropped = jnp.any(active & (slots < 0))
    idx = jnp.where(active, slots, table.capacity)
    rows = {
        n: rows[n].at[idx].set(chunk.col(n), mode="drop") for n in names
    }
    table = set_live(table, jnp.where(active, slots, -1), signs > 0)
    sdirty = sdirty.at[idx].set(True, mode="drop")
    return table, rows, sdirty, dropped


def _order_key_u64(v, desc: bool):
    """Map an order lane to an unsigned memcomparable key (the same
    transform the SST sort uses) so int/float/asc/desc all reduce to
    one uint64 comparison."""
    if jnp.issubdtype(v.dtype, jnp.floating):
        from risingwave_tpu.ops.agg import _float_to_order_key

        key = _float_to_order_key(v).astype(jnp.uint64)
    elif jnp.issubdtype(v.dtype, jnp.unsignedinteger):
        key = v.astype(jnp.uint64)
    else:
        key = jax.lax.bitcast_convert_type(
            v.astype(jnp.int64), jnp.uint64
        ) ^ (jnp.uint64(1) << jnp.uint64(63))
    return ~key if desc else key


@partial(jax.jit, static_argnames=("n", "desc"))
def _rank_top(table: HashTable, order_lane, n: int, desc: bool):
    """Indices of the top-n live rows by (order, pk-lanes) total order.
    Liveness is its own LEADING sort key: a dead-row sentinel value
    would collide with a legitimate INT64 extreme order value and let
    dead slots displace live rows."""
    live_last = (~table.live).astype(jnp.int32)
    key = _order_key_u64(order_lane, desc)
    sort_ops = jax.lax.sort(
        (live_last, key) + tuple(k for k in table.keys)
        + (jnp.arange(table.capacity, dtype=jnp.int32),),
        num_keys=2 + len(table.keys),
    )
    idx = sort_ops[-1][:n]
    alive = table.live[idx]
    return idx, alive


class TopNExecutor(Executor, Checkpointable):
    """ORDER BY order_col [DESC] LIMIT n with full retraction support."""

    def __init__(
        self,
        order_col: str,
        limit: int,
        pk: Sequence[str],
        schema_dtypes: Dict[str, object],
        desc: bool = False,
        capacity: int = 1 << 14,
        table_id: str = "top_n",
        bucket_policy: Optional[BucketPolicy] = None,
        bucketed: bool = True,
    ):
        self._buckets = (
            BucketAllocator(
                bucket_policy or BucketPolicy.from_capacity(capacity, grow_at=GROW_AT)
            )
            if bucketed
            else None
        )
        self.order_col = order_col
        self.limit = int(limit)
        self.desc = desc
        self.pk = tuple(pk)
        self.names = tuple(sorted(schema_dtypes))
        self._dtypes = {n: jnp.dtype(schema_dtypes[n]) for n in self.names}
        self.table = HashTable.create(
            capacity, tuple(self._dtypes[k] for k in self.pk)
        )
        self.rows = {
            n: jnp.zeros(capacity, self._dtypes[n]) for n in self.names
        }
        self.sdirty = jnp.zeros(capacity, jnp.bool_)
        self.stored = jnp.zeros(capacity, jnp.bool_)
        self.table_id = table_id
        self._bound = 0
        self._dropped = jnp.zeros((), jnp.bool_)
        self._emitted: Dict[Tuple, Tuple] = {}  # pk -> full row

    def lint_info(self):
        return {
            "expects": dict(self._dtypes),
            "emits": dict(self._dtypes),
            "renames": {n: n for n in self.names},
            "state_pk": tuple(self.pk),
            "table_ids": (self.table_id,),
        }

    def trace_contract(self):
        return {
            "kind": "device",
            "trace_step": lambda c: _upsert_step(
                self.table, self.rows, self.sdirty, c, self.pk, self.names
            ),
            "state": (self.table, self.rows),
            "donate": True,
            # the barrier diff against the host mirror now pads its
            # emissions to pow2 buckets (<= limit rows per op chunk):
            # a declared, closed capacity set instead of one shape per
            # distinct delta count (data_dependent on the legacy twin)
            **(
                {
                    "emission": "bucketed",
                    "emission_caps": lattice_between(
                        2, pow2_at_least(max(self.limit, 2))
                    ),
                }
                if self._buckets is not None
                else {"emission": "data_dependent"}
            ),
        }

    def pin_max_bucket(self):
        """ShapeGovernor hook: freeze the row store at its high-water
        bucket (shrink disabled)."""
        if self._buckets is None:
            return {"pinned": False}
        return {
            "table_id": self.table_id,
            "pinned_cap": self._buckets.pin(),
        }

    def padding_stats(self):
        return {
            "capacity": self.table.capacity,
            "live": int(self.table.num_live()),
        }

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        for k in self.pk + (self.order_col,):
            if k in chunk.nulls:
                raise ValueError(f"TopN key column {k!r} cannot be NULL")
        self._maybe_grow(chunk.capacity)
        self._bound += chunk.capacity
        self.table, self.rows, self.sdirty, dropped = _upsert_step(
            self.table, self.rows, self.sdirty, chunk, self.pk, self.names
        )
        self._dropped = self._dropped | dropped
        return []

    def _maybe_grow(self, incoming: int):
        cap = self.table.capacity
        if not needs_plan(self._buckets, cap, self._bound, incoming, GROW_AT):
            return
        claimed = int(self.table.occupancy())
        survivors = int(
            jnp.sum((self.table.live | self.sdirty).astype(jnp.int32))
        )
        new_cap = plan_capacity(
            self._buckets, cap, incoming, claimed, survivors, GROW_AT
        )
        if new_cap is not None:
            keep = self.table.live | self.sdirty
            new = HashTable.create(
                new_cap, tuple(k.dtype for k in self.table.keys)
            )
            new, slots, _, _ = lookup_or_insert(new, self.table.keys, keep)
            new = set_live(new, jnp.where(keep, slots, -1), self.table.live)
            idx = jnp.where(keep, slots, new_cap)

            def move(a, init_dtype):
                return (
                    jnp.zeros(new_cap, init_dtype)
                    .at[idx]
                    .set(a, mode="drop")
                )

            self.rows = {
                n: move(a, a.dtype) for n, a in self.rows.items()
            }
            self.sdirty = move(self.sdirty, jnp.bool_)
            self.stored = move(self.stored, jnp.bool_)
            self.table = new
            claimed = int(self.table.occupancy())
        self._bound = claimed

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        if self._buckets is not None:
            self._buckets.note_barrier(self.table.capacity, self._bound)
        if bool(self._dropped):
            raise RuntimeError("TopN row store overflowed; grow capacity")
        idx, alive = _rank_top(
            self.table, self.rows[self.order_col], self.limit, self.desc
        )
        # pull exactly n rows (one packed gather)
        lanes = {n: self.rows[n][idx] for n in self.names}
        lanes["__alive__"] = alive
        pulled = {k: np.asarray(v) for k, v in lanes.items()}
        top: Dict[Tuple, Tuple] = {}
        for i in range(self.limit):
            if not pulled["__alive__"][i]:
                break  # dead rows rank last: first dead = end of live
            pkv = tuple(pulled[k][i].item() for k in self.pk)
            top[pkv] = tuple(pulled[n][i].item() for n in self.names)
        outs = []
        dels = [v for k, v in self._emitted.items() if top.get(k) != v]
        ins = [v for k, v in top.items() if self._emitted.get(k) != v]
        for vals, op in ((dels, Op.DELETE), (ins, Op.INSERT)):
            if not vals:
                continue
            cols = {
                n: np.asarray([r[j] for r in vals], self._dtypes[n])
                for j, n in enumerate(self.names)
            }
            outs.append(
                StreamChunk.from_numpy(
                    cols,
                    # pow2-padded emission: a closed downstream shape set
                    emission_bucket(len(vals))
                    if self._buckets is not None
                    else max(2, len(vals)),
                    ops=np.full(len(vals), int(op), np.int32),
                )
            )
        self._emitted = top
        return outs

    # -- integrity --------------------------------------------------------
    def digest_lanes(self):
        lanes = {f"k{i}": k for i, k in enumerate(self.table.keys)}
        for n in self.names:
            lanes[f"r_{n}"] = self.rows[n]
        return lanes, self.table.live

    def state_digest(self) -> int:
        from risingwave_tpu.integrity import host_digest

        return host_digest(*self.digest_lanes())

    # -- checkpoint -------------------------------------------------------
    def checkpoint_delta(self) -> List[StateDelta]:
        marks = classify_marks(self.sdirty, self.table.live, self.stored)
        self.sdirty, self.stored = marks.sdirty, marks.stored
        if not len(marks):
            return []
        lanes = {f"k{i}": lane for i, lane in enumerate(self.table.keys)}
        key_names = tuple(lanes)
        for n in self.names:
            lanes[f"r_{n}"] = self.rows[n]
        pulled = pull_rows(lanes, marks)
        keys = {k: pulled[k] for k in key_names}
        vals = {k: v for k, v in pulled.items() if k not in key_names}
        return [
            StateDelta(self.table_id, keys, vals, marks.tombstone, key_names)
        ]

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        n = len(next(iter(key_cols.values()))) if key_cols else 0
        cap = grow_pow2(n, self.table.capacity, GROW_AT)
        key_dtypes = tuple(k.dtype for k in self.table.keys)
        table = HashTable.create(cap, key_dtypes)
        rows = {nm: jnp.zeros(cap, self._dtypes[nm]) for nm in self.names}
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
            rows = {
                nm: a.at[slots].set(
                    jnp.asarray(
                        np.asarray(value_cols[f"r_{nm}"]).astype(a.dtype)
                    )
                )
                for nm, a in rows.items()
            }
            self.stored = self.stored.at[slots].set(True)
        self.table = table
        self.rows = rows
        self._bound = int(n)
        self._dropped = jnp.zeros((), jnp.bool_)
        # downstream MV was restored consistently; recompute its view
        idx, alive = _rank_top(
            table, rows[self.order_col], self.limit, self.desc
        )
        pulled = {nm: np.asarray(rows[nm][idx]) for nm in self.names}
        al = np.asarray(alive)
        self._emitted = {}
        for i in range(self.limit):
            if not al[i]:
                break
            pkv = tuple(pulled[k][i].item() for k in self.pk)
            self._emitted[pkv] = tuple(
                pulled[nm][i].item() for nm in self.names
            )


# ---------------------------------------------------------------------------
# Retractable GroupTopN
# ---------------------------------------------------------------------------


@partial(
    jax.jit,
    static_argnames=("pk", "names", "n_group"),
    donate_argnums=(0, 1, 2, 3),
    donate_argnames=("groups", "listed"),
)
def _upsert_step_ed(
    table, rows, sdirty, epoch_dirty, chunk, pk, names,
    groups=None, listed=None, at=None, n_group=0, probes=None,
):
    """_upsert_step that also marks epoch_dirty (cleared per barrier)
    in the same scatter — one probe, two mark lanes. With ``groups``
    (a ``_Groups``; the first ``n_group`` lanes of ``pk`` are the group's
    columns) each row's group is probed beside it and kept by the row's
    slot, and the slots the step wrote are appended to ``listed`` at
    lane ``at`` (``note_touched``): what a barrier's rank over the
    epoch's rows alone starts from. Returns ``groups`` and ``listed``
    then too. With ``probes`` (int32, a row of ``PROBE_STATS`` a table:
    the row store, the groups) both probes are the counted one and what
    they did is added to it and handed back last."""
    keys = tuple(chunk.col(k) for k in pk)
    signs = chunk.effective_signs()
    active = chunk.valid & (signs != 0)
    probe = lookup_or_insert if probes is None else lookup_or_insert_counted
    with jax.named_scope("topn/rows"):
        table, slots, _, _, *row_stats = probe(table, keys, active)
        dropped = jnp.any(active & (slots < 0))
        idx = jnp.where(active, slots, table.capacity)
        rows = {
            n: rows[n].at[idx].set(chunk.col(n), mode="drop") for n in names
        }
        table = set_live(table, jnp.where(active, slots, -1), signs > 0)
    with jax.named_scope("topn/marks"):
        sdirty = sdirty.at[idx].set(True, mode="drop")
        epoch_dirty = epoch_dirty.at[idx].set(True, mode="drop")
    if groups is None:
        return table, rows, sdirty, epoch_dirty, dropped
    with jax.named_scope("topn/groups"):
        gtable, gslots, _, _, *group_stats = probe(
            groups.table, keys[:n_group], active
        )
        dropped = dropped | jnp.any(active & (gslots < 0))
        groups = _Groups(
            gtable, groups.of_row.at[idx].set(gslots, mode="drop")
        )
    with jax.named_scope("topn/marks"):
        listed = note_touched(listed, at, jnp.where(active, slots, -1))
    out = (table, rows, sdirty, epoch_dirty, dropped, groups, listed)
    if probes is not None:
        out += (probes + jnp.stack(row_stats + group_stats),)
    return out


class _Groups(NamedTuple):
    """The groups that hold a row, beside the row store and as large."""

    table: HashTable  # keyed by the group's columns
    of_row: jnp.ndarray  # by row slot: its group's slot, -1 = no row yet


class _Tops(NamedTuple):
    """Each group's top k as of the last barrier, a chain through the
    row slots in rank order: the rows the lane ``emitted`` marks, found
    from the group and not by a pass over the store."""

    head: jnp.ndarray  # by group slot: its first row's slot, -1 = none
    after: jnp.ndarray  # by row slot: the next of its group's top k, -1


class _Ranked(NamedTuple):
    """``_rank``'s lanes, in sorted order: all of the store's, or the
    candidates of the epoch's groups."""

    packed: jnp.ndarray  # the slot, under four flags
    in_topk: jnp.ndarray
    seg_start: jnp.ndarray  # the position the lane's group starts at
    passes: jnp.ndarray  # the sorts the ranking took
    erank: Optional[jnp.ndarray] = None  # the rank as handed on
    group: Optional[jnp.ndarray] = None  # the group's slot
    # the candidates cannot answer (a scalar): rank the store
    full_rank: Optional[jnp.ndarray] = None


# The barrier of the retractable GroupTopN, on the device. A row store
# keeps every input row, and a barrier ranks what the epoch could have
# moved: the rows its chunks wrote and the top k, as the last barrier
# left them, of those rows' groups (``_Tops``; ``_candidates``), in one
# sort over (group, liveness, order key, stream key) of a few tens of
# thousands of lanes. A group's new top k lies among those unless a row
# of its old top k was deleted or rewritten while rows stand behind it,
# which the program sees in its input (``_Ranked.full_rank``); that
# barrier, and the first after a restore or a re-slotted store, ranks
# all of the store's lanes by the same sort, as every barrier once did.
# What the barrier then needs is the difference between the rows that
# are in their group's top-k NOW and the rows the executor has handed on
# (the lane ``emitted``), and that difference is two masks in the sorted
# order. So the rows to retract and the rows to insert are compacted and
# gathered on the device too (``_diff_gather``), into two chunks of
# ``out_lanes`` lanes, and the host reads ten counts. ``shadow`` keeps
# every column as it was when the row was last handed on: an UPDATE
# overwrites a stored row in place, and its retraction has to carry the
# old values.
_SLOT_MASK = (1 << 27) - 1  # a slot (ABS_MAX_CAP is 2^26), under four flags
_EMITTED_BIT, _DIRTY_BIT, _REDO_BIT, _LIVE_BIT = 30, 29, 28, 27
_EMIT_FLOOR = 1 << 14  # the smallest emission size, x4 steps above it


def _n_digits(dtype) -> int:
    """How many 32-bit digits ``_digits`` cuts a key lane into."""
    dtype = jnp.dtype(dtype)
    narrow = dtype.itemsize <= 4 and not jnp.issubdtype(dtype, jnp.floating)
    return 1 if narrow else 2


def _digits(lane) -> Tuple[jnp.ndarray, ...]:
    """A key lane as unsigned 32-bit digits, least significant first,
    whose lexicographic order (most significant first) is the lane's."""
    if _n_digits(lane.dtype) == 1:
        if jnp.issubdtype(lane.dtype, jnp.unsignedinteger) or (
            lane.dtype == jnp.bool_
        ):
            return (lane.astype(jnp.uint32),)
        return (
            jax.lax.bitcast_convert_type(lane.astype(jnp.int32), jnp.uint32)
            ^ jnp.uint32(1 << 31),
        )
    key = _order_key_u64(lane, False)
    with jax.named_scope("x64/split"):
        return (
            (key & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
            (key >> jnp.uint64(32)).astype(jnp.uint32),
        )


def _packed_words(digits):
    """The digits (least significant first) as ONE integer a lane,
    packed: each digit gives only the bits its values' range over the
    lanes needs (none when every lane shares it: the high half of most
    64-bit ids and of a run's event times), most significant digit on
    top, cut into 32-bit words, least significant first. The order of
    the lanes by that integer is their order by the digits, and it has
    as many words that differ between lanes as the key has bits of
    information, rounded up to 32: three for q18's nine digits.
    Returns (as many words as digits; the bit each digit starts at)."""
    low = [jnp.min(d) for d in digits]
    offsets = [jnp.zeros((), jnp.int32)]
    for d, lo in zip(digits, low):
        width = 32 - jax.lax.clz(jnp.max(d) - lo).astype(jnp.int32)
        offsets.append(offsets[-1] + width)
    words = []
    for w in range(len(digits)):
        word = jnp.zeros_like(digits[0])
        for d, lo, off in zip(digits, low, offsets):
            at, shift = off // 32, (off % 32).astype(jnp.uint32)
            v = d - lo
            # the digit's low part in its own word, what the shift
            # pushed out in the next
            spill = jnp.where(shift > 0, v >> (32 - jnp.maximum(shift, 1)), 0)
            word = word | jnp.where(at == w, v << shift, 0)
            word = word | jnp.where(at + 1 == w, spill, 0)
        words.append(word)
    return tuple(words), offsets


def _sort_by_words(words, payloads):
    """``words`` (least significant first) and the ``payloads`` in the
    lanes' order by the words: one stable sort a word, keyed on it and
    carrying every other word and the payloads along, in a loop, so the
    program holds ONE sort whatever the key is made of. (A sort keyed on
    all of q18's key lanes at once, six operands and 64-bit compares,
    took the TPU's compiler ten minutes at 2^21 lanes; a loop of
    two-operand sorts that gathered each digit into the current order
    compiled in half a minute and spent three quarters of its time in
    the gathers: 40 ms a gather of 2^22 32-bit lanes against 12 ms a
    sort.) A word every lane shares is skipped on the device. Returns
    (words, payloads, sorts made)."""
    n = len(words)

    def one(_, carry):
        ops, passes = carry

        def sort(ops):
            return jax.lax.sort(ops, num_keys=1, is_stable=True)

        shared = jnp.all(ops[0] == ops[0][0])
        ops = jax.lax.cond(shared, lambda ops: ops, sort, ops)
        passes = passes + (~shared).astype(jnp.int32)
        # the next word to the front; after ``n`` turns all are back
        return ops[1:n] + ops[:1] + ops[n:], passes

    ops, passes = jax.lax.fori_loop(
        0, n, one, (tuple(words) + tuple(payloads), jnp.zeros((), jnp.int32))
    )
    return ops[:n], ops[n:], passes


def _sort_by_words_gathered(words, payloads):
    """``_sort_by_words`` for a few tens of thousands of lanes (a
    barrier's candidates): the loop's sort carries the order alone and
    each word is gathered into it, a two-operand sort where one of a
    dozen. The TPU's compiler takes a sort by its operands — 25 s for
    one here, 284 s for eleven, whatever the lanes (the chip's compiler
    for a described v5e, PR 46) — and this program is compiled once a
    candidate size; a gather of 65,536 lanes costs the device a
    millisecond, where one of 2^22 cost 40."""
    stacked = jnp.stack(words)
    order, passes = _order_by_words(stacked)
    return (
        tuple(stacked[:, order]), tuple(p[order] for p in payloads), passes
    )


def _order_by_words(stacked):
    """The lanes' order by the words ``stacked`` (a row a word, least
    significant first), and the sorts it took."""
    lanes = stacked.shape[1]

    def one(i, carry):
        order, passes = carry
        word = stacked[i]
        shared = jnp.all(word == word[0])

        def sort(order):
            return jax.lax.sort(
                (word[order], order), num_keys=1, is_stable=True
            )[1]

        order = jax.lax.cond(shared, lambda order: order, sort, order)
        return order, passes + (~shared).astype(jnp.int32)

    return jax.lax.fori_loop(
        0, stacked.shape[0], one,
        (jnp.arange(lanes, dtype=jnp.int32), jnp.zeros((), jnp.int32)),
    )


def _rank_sorted(
    keys, live, order_lanes, flags, k, descs, n_group, carried=(),
    slots=None, valid=None,
):
    """The lanes ranked by (group lanes, dead last, the order keys, the
    rest of the stream key). ``keys`` / ``live``: the store's key lanes
    and liveness, whole or gathered at ``slots`` (the candidates' slots;
    ``valid``: which lanes hold one, the others rank behind every
    group). ``order_lanes`` / ``descs``: the order keys' lanes and
    their directions, most significant first (``_order_of``). Returns
    (``_Ranked``'s first four): each lane's slot with ``flags`` (bits
    above ``_SLOT_MASK``) carried along, whether it is in its group's
    top-k, the position its group starts at, the sorts the ranking
    took; and then every lane of ``carried`` (operands that ride along
    the sort beside the slot: a capacity-wide gather into the sorted
    order costs three sorts)."""
    with jax.named_scope("topn/rank/digits"):
        lanes = live.shape[0]
        if slots is None:
            slots = jnp.arange(lanes, dtype=jnp.int32)
        # liveness as its own sort key within the group (a dead-row
        # sentinel would collide with INT64-extreme order values)
        live_last = (~live).astype(jnp.uint32)
        okeys = tuple(
            _order_key_u64(lane, d) for lane, d in zip(order_lanes, descs)
        )
        digits: Tuple[jnp.ndarray, ...] = ()
        for lane in reversed(keys[n_group:]):
            digits += _digits(lane)
        # a later order key's digits lie below an earlier one's
        for okey in reversed(okeys):
            digits += _digits(okey)
        digits += (live_last,)
        n_below = len(digits)  # the digits below the group's
        for lane in reversed(keys[:n_group]):
            digits += _digits(lane)
        if valid is not None:
            # on top: the lanes that hold no candidate are one group, last
            digits += ((~valid).astype(jnp.uint32),)
        words, offsets = _packed_words(digits)
        flags = flags | (live.astype(jnp.int32) << _LIVE_BIT)
    with jax.named_scope("topn/rank/sort"):
        sort = _sort_by_words if valid is None else _sort_by_words_gathered
        words, (packed_s, *carried_s), passes = sort(
            words, (slots | flags,) + tuple(carried)
        )
    with jax.named_scope("topn/rank/segments"):
        # a group starts where a bit of the group's digits differs from the
        # lane before: the bits from ``offsets[n_below]`` up
        group_bit = offsets[n_below]
        differs = jnp.zeros(lanes - 1, jnp.bool_)
        for w, word in enumerate(words):
            below = jnp.clip(group_bit - 32 * w, 0, 32).astype(jnp.uint32)
            mask = jnp.where(
                below >= 32,
                jnp.uint32(0),
                ~((jnp.uint32(1) << jnp.minimum(below, 31)) - jnp.uint32(1)),
            )
            differs = differs | (((word[1:] ^ word[:-1]) & mask) != 0)
        boundary = jnp.concatenate([jnp.ones(1, jnp.bool_), differs])
        pos = jnp.arange(lanes, dtype=jnp.int32)
        seg_start = jax.lax.cummax(jnp.where(boundary, pos, 0))
        live_s = ((packed_s >> _LIVE_BIT) & 1) > 0
        in_topk_s = live_s & ((pos - seg_start) < k)
    return (packed_s, in_topk_s, seg_start, passes) + tuple(carried_s)


# What the diff hands on is the set lanes of two masks over the sorted
# order, in order, and a barrier's delta is a few thousand of a store's
# millions of lanes. So a mask is counted in two levels once (a dense
# pass: ``_WORD`` lanes a word, ``_ROW`` words a row, a running count
# over the rows alone) and the wanted lanes are then found a block at a
# time, each from its row's counts and its word's bits: nothing runs the
# capacity's length but that one pass, and no search runs at all.
_WORD = 32  # lanes of a mask in one word
_ROW = 128  # words in one row of counts
# Lanes a turn of the gathers' loops moves. A lane of a turn costs what
# a row does whether it holds one (a scatter into a 64-bit lane some 50
# ns a lane a column on a v5e), a turn some 2 ms before it moves a row:
# at 8,192 the three NEXmark Top-Ns' second program ran 36.7 / 32.0 /
# 43.0 ms, at 16,384 45.8 / 43.5 / 54.3, at 32,768 42.1 / 67.0 / 69.7
# (PERF.md, PR 42).
_GATHER_LANES = 1 << 13


class _SetLanes(NamedTuple):
    """A mask counted for ``_compact``."""

    words: jnp.ndarray  # the mask, lane i the bit i % _WORD of word i // _WORD
    counts: jnp.ndarray  # (rows, words a row): each word's set lanes
    row_end: jnp.ndarray  # the set lanes up to each row's end

    @property
    def total(self):
        return self.row_end[-1]


def _mask_words(mask):
    """``mask`` as words of ``_WORD`` lanes (of all its lanes, where it
    has fewer), lowest lane in the lowest bit."""
    width = min(_WORD, mask.shape[0])
    bits = mask.reshape(-1, width).astype(jnp.uint32) << jnp.arange(
        width, dtype=jnp.uint32
    )
    return jnp.sum(bits, axis=1, dtype=jnp.uint32)


def _count_set(mask) -> _SetLanes:
    """The one pass over a mask's whole length."""
    words = _mask_words(mask)
    counts = jax.lax.population_count(words).astype(jnp.int32)
    counts = counts.reshape(-1, min(_ROW, counts.shape[0]))
    return _SetLanes(words, counts, jnp.cumsum(jnp.sum(counts, axis=1)))


def _nth_bit(word, nth):
    """The position of the ``nth`` set bit (from 1, lowest first) of
    each ``word``: five halvings by the count of the lower half."""
    at = jnp.zeros_like(word)
    for width in (16, 8, 4, 2, 1):
        low = jax.lax.population_count(
            (word >> at) & jnp.uint32((1 << width) - 1)
        )
        above = nth > low
        nth = jnp.where(above, nth - low, nth)
        at = jnp.where(above, at + width, at)
    return at.astype(jnp.int32)


def _compact(lanes: _SetLanes, out_lanes: int, start):
    """Positions of the set lanes numbered ``start`` to ``start +
    out_lanes`` (from 0, in order) of a counted mask, and which output
    lanes hold one (the others read position 0)."""
    want = jnp.arange(1, out_lanes + 1, dtype=jnp.int32) + start
    valid = want <= lanes.total
    # the row: the first whose end reaches the wanted lane; what lies
    # before it is the largest end that does not
    short = lanes.row_end[None, :] < want[:, None]
    row = jnp.minimum(
        jnp.sum(short, axis=1, dtype=jnp.int32), lanes.row_end.shape[0] - 1
    )
    want = want - jnp.max(jnp.where(short, lanes.row_end[None, :], 0), axis=1)
    # the word: the same along the row's own counts
    counts = lanes.counts[row]
    short = jnp.cumsum(counts, axis=1) < want[:, None]
    word = jnp.minimum(
        jnp.sum(short, axis=1, dtype=jnp.int32), counts.shape[1] - 1
    )
    want = want - jnp.sum(jnp.where(short, counts, 0), axis=1)
    word = row * counts.shape[1] + word
    pos = word * _WORD + _nth_bit(lanes.words[word], want.astype(jnp.uint32))
    return jnp.where(valid, pos, 0), valid


def _fetch(lane, at, fill=0):
    """``lane`` at the slots ``at``; ``fill`` where ``at`` holds none
    (below 0: -1 would read the last lane)."""
    return lane.at[jnp.where(at >= 0, at, lane.shape[0])].get(
        mode="fill", fill_value=fill
    )


def _candidates(listed, n_listed, groups: _Groups, tops: _Tops,
                k: int, lanes: int):
    """The slots a barrier has to rank, each once, ascending, in
    ``lanes`` lanes (-1 past them): the rows the epoch's steps wrote
    (the first ``n_listed`` of ``listed``, which holds a row as often as
    it was written) and the top k of their groups as the last barrier
    left it. Two sorts of one operand over ``lanes x (1 + k)`` lanes,
    the second to close the gaps the first one's repeats leave (one
    sort in the program, in a loop of two turns:
    ``_sort_by_words_gathered`` says what a sort costs to compile).
    Also: whether the lane's group had k rows in its chain (rows may
    then stand behind them in the store), and how many slots there
    were (more than ``lanes``: not all are here)."""
    row = jnp.where(
        jnp.arange(lanes, dtype=jnp.int32) < n_listed, listed[:lanes], -1
    )

    def step(at, _):
        return _fetch(tops.after, at, -1), at

    _, chain = jax.lax.scan(
        step, _fetch(tops.head, _fetch(groups.of_row, row, -1), -1), None,
        length=k,
    )
    whole = jnp.tile(chain[k - 1] >= 0, 1 + k).astype(jnp.int32)
    both = jnp.concatenate([row, chain.reshape(-1)])
    none = jnp.iinfo(jnp.int32).max
    # a slot's group says ``whole``, so a slot's repeats are equal
    def sift(_, s):
        s = jax.lax.sort(s)
        again = jnp.concatenate([jnp.zeros(1, jnp.bool_), s[1:] == s[:-1]])
        return jnp.where(again, none, s)

    s = jax.lax.fori_loop(
        0, 2, sift, jnp.where(both >= 0, both * 2 + whole, none)
    )
    n = jnp.sum((s != none).astype(jnp.int32))
    s = s[:lanes]
    return jnp.where(s != none, s >> 1, -1), (s != none) & ((s & 1) > 0), n


@partial(
    jax.jit,
    static_argnames=("k", "desc", "n_group", "order_col", "cand_lanes"),
)
def _rank(
    table: HashTable,
    rows: Dict[str, jnp.ndarray],
    shadow: Dict[str, jnp.ndarray],
    emitted: jnp.ndarray,
    epoch_dirty: jnp.ndarray,
    k: int,
    desc: Union[bool, Tuple[bool, ...]],
    n_group: int,
    order_col: Union[str, Tuple[str, ...]],
    erank: Optional[jnp.ndarray] = None,
    groups: Optional[_Groups] = None,
    tops: Optional[_Tops] = None,
    listed: Optional[jnp.ndarray] = None,
    n_listed=None,
    cand_lanes: Optional[int] = None,
) -> _Ranked:
    """The barrier's first program: the ranking, with what the diff
    needs carried along. ``order_col`` / ``desc``: the order key and
    its direction, or a tuple of each for an order of several keys,
    most significant first. One program a store capacity ranks every
    lane; with ``cand_lanes`` one a candidate size ranks the epoch's
    candidates (``_candidates``, from ``tops`` and ``listed``) in that
    many lanes, and says in ``full_rank`` if they cannot answer: more
    of them than lanes, or a row of a group's old top k gone or
    rewritten where the chain was whole, so that the next row may stand
    in the store behind it. Returns ``_Ranked``: (slot | flags, in its
    group's top-k, where its group starts) in sorted order and the
    sorts made; for a Top-N that hands the rank on ``erank`` (the rank
    each row was handed on with) and with ``groups`` each lane's
    group's slot, which ride along the sort beside the slot."""
    order_cols, descs = _order_of(order_col, desc)
    if cand_lanes is None:
        at = valid = None

        def take(lane, fill=0):
            return lane
    else:
        with jax.named_scope("topn/rank/candidates"):
            at, whole, n_cand = _candidates(
                listed, n_listed, groups, tops, k, cand_lanes
            )
        valid = at >= 0

        def take(lane, fill=0):
            return _fetch(lane, at, fill)

    with jax.named_scope("topn/rank/gather"):
        live, handed, dirty = map(take, (table.live, emitted, epoch_dirty))

    def rewritten():
        return dirty & _any_differs(
            {n: take(a) for n, a in rows.items()},
            {n: take(a) for n, a in shadow.items()},
        )

    with jax.named_scope("topn/rank/rewritten"):
        if cand_lanes is None:
            redo = rewritten()
        else:
            # only a row that was handed on is asked whether it was
            # rewritten: an epoch of new rows reads no column for it
            redo = jax.lax.cond(
                jnp.any(handed & dirty), rewritten, lambda: jnp.zeros_like(dirty)
            )
    flags = (
        (handed.astype(jnp.int32) << _EMITTED_BIT)
        | (dirty.astype(jnp.int32) << _DIRTY_BIT)
        | (redo.astype(jnp.int32) << _REDO_BIT)
    )
    with jax.named_scope("topn/rank/gather"):
        carried = () if erank is None else (take(erank),)
        if groups is not None:
            carried += (take(groups.of_row, -1),)
        keys = tuple(take(lane) for lane in table.keys)
        order_lanes = tuple(take(rows[c]) for c in order_cols)
    packed, in_topk, seg_start, passes, *carried = _rank_sorted(
        keys, live, order_lanes, flags, k, descs, n_group,
        carried=carried,
        slots=None if at is None else jnp.where(valid, at, _SLOT_MASK),
        valid=valid,
    )
    return _Ranked(
        packed, in_topk, seg_start, passes,
        erank=None if erank is None else carried[0],
        group=None if groups is None else carried[-1],
        full_rank=None if cand_lanes is None else (
            (n_cand > cand_lanes)
            | jnp.any(handed & (~live | redo) & whole)
        ),
    )


def _order_of(order_col, desc):
    """The order keys' columns and directions as two tuples, most
    significant first, from one column and its flag or a tuple of each."""
    if isinstance(order_col, str):
        return (order_col,), (bool(desc),)
    return tuple(order_col), tuple(desc)


def _touched_groups(dirty_s, seg_start):
    """Groups (in sorted order) that hold a dirty row: the dirty rows
    with no dirty row before them in their group. The dirty row before
    a lane is read off its word's lower bits, and where they hold none
    off a running maximum over the words."""
    words = _mask_words(dirty_s)
    width = dirty_s.shape[0] // words.shape[0]
    first = jnp.arange(words.shape[0], dtype=jnp.int32) * width

    def last(bits):  # the highest set lane of each word; below 0: none
        return 31 - jax.lax.clz(bits).astype(jnp.int32)

    word_last = jnp.where(words != 0, first + last(words), -1)
    earlier = jnp.concatenate(
        [jnp.full(1, -1, jnp.int32), jax.lax.cummax(word_last)[:-1]]
    )
    below = words[:, None] & (
        (jnp.uint32(1) << jnp.arange(width, dtype=jnp.uint32)) - 1
    )
    before = jnp.where(
        below != 0, first[:, None] + last(below), earlier[:, None]
    )
    firsts = dirty_s.reshape(-1, width) & (
        before < seg_start.reshape(-1, width)
    )
    return jnp.sum(firsts.astype(jnp.int32))


def _in_turns(lanes: _SetLanes, out_lanes: int, start, turn, carry):
    """``carry = turn(carry, at, pos, valid)`` over the set lanes
    numbered ``start`` to ``start + out_lanes`` of a counted mask, a
    block of ``_GATHER_LANES`` a turn (``at``: where the block lies in
    the ``out_lanes``; ``pos``, ``valid``: ``_compact``'s), for as many
    turns as hold a set lane: the trip count is a value on the device.
    Returns (carry, the lanes the turns covered)."""
    block = math.gcd(_GATHER_LANES, out_lanes)
    turns = -(-jnp.clip(lanes.total - start, 0, out_lanes) // block)

    def one(t, carry):
        at = t * block
        return turn(carry, at, *_compact(lanes, block, start + at))

    return jax.lax.fori_loop(0, turns, one, carry), turns * block


def _put(tree, block, at):
    """``block``'s leaves written into ``tree``'s from lane ``at`` on."""
    return jax.tree.map(
        lambda a, b: jax.lax.dynamic_update_slice(a, b, (at,)), tree, block
    )


def _delta_chunks(ret_cols, ret_valid, ins_cols, ins_valid, out_lanes: int):
    """The two chunks a barrier (or a round of one) hands on."""
    return tuple(
        StreamChunk(
            columns=cols,
            valid=valid,
            nulls={},
            ops=jnp.full(out_lanes, int(op), jnp.int32),
        )
        for cols, valid, op in (
            (ret_cols, ret_valid, Op.DELETE),
            (ins_cols, ins_valid, Op.INSERT),
        )
    )


@partial(
    jax.jit,
    static_argnames=("out_lanes", "rank_col"),
    donate_argnums=(2, 3),
    donate_argnames=("erank", "tops"),
)
def _diff_gather(
    table: HashTable,
    rows: Dict[str, jnp.ndarray],
    shadow: Dict[str, jnp.ndarray],
    emitted: jnp.ndarray,
    ranked: _Ranked,
    dropped: jnp.ndarray,
    out_lanes: int,
    erank: Optional[jnp.ndarray] = None,
    start: Optional[jnp.ndarray] = None,
    rank_col: Optional[str] = None,
    tops: Optional[_Tops] = None,
):
    """The barrier's second program, one per emission size: the ranking
    diffed against what was handed on, and both deltas gathered. (With
    ``rank_col`` the rank is handed on as that column and this is
    ``_diff_gather_numbered``, below: the same program to the device
    trace and to what reads it, another body.)

    A row is retracted when it was handed on and is no longer in its
    group's top-k, or was rewritten this epoch with other values
    (``redo``: then it is inserted again too); a row is inserted when
    it is in the top-k and was not handed on. Only a group an epoch
    touched can differ, and a touched row displaces or promotes at most
    one other, so neither delta passes the lanes the epoch's chunks
    held: ``out_lanes`` is sized from that on the host, and ``status``
    says if it ever did not hold (a raise, not a silent cut). The rows
    are gathered a block a turn (``_in_turns``) for as many turns as
    the delta has rows, not for ``out_lanes``: the lanes past them keep
    their zeros.

    ``ranked`` holds the store's lanes or an epoch's candidates
    (``_rank``). Where it says ``full_rank`` the candidates could not
    answer: nothing is diffed, gathered or written, and the caller
    ranks the store. With ``tops`` every group ``ranked`` holds has its
    chain rewritten from the ranking (``_relink``).

    Returns (emitted, shadow, retractions, insertions, status, tops)
    with status = [retract rows, insert rows, touched groups, overflow,
    dropped latch, slots claimed, live rows, sorts made, lanes the
    gathers' turns covered, ``full_rank``]."""
    if rank_col is not None:
        return _diff_gather_numbered(
            table, rows, shadow, emitted, erank, ranked, dropped, start,
            rank_col, out_lanes, tops,
        )
    with jax.named_scope("topn/diff/masks"):
        cap = table.capacity
        packed_s, in_topk_s, seg_start, passes = ranked[:4]
        stay = _stay(ranked)
        slot_s = packed_s & _SLOT_MASK
        emitted_s = ((packed_s >> _EMITTED_BIT) & 1) > 0
        dirty_s = ((packed_s >> _DIRTY_BIT) & 1) > 0
        redo_s = ((packed_s >> _REDO_BIT) & 1) > 0
        ret_s = emitted_s & (~in_topk_s | redo_s) & ~stay
        ins_s = in_topk_s & (~emitted_s | redo_s) & ~stay
        groups = _touched_groups(dirty_s, seg_start)
        ret_set, ins_set = _count_set(ret_s), _count_set(ins_s)

    # every retraction reads ``shadow`` before an insertion renews it
    def retract(carry, at, pos, valid):
        emitted, cols, valids = carry
        slot = jnp.where(valid, slot_s[pos], cap)
        block = {n: a.at[slot].get(mode="fill", fill_value=0)
                 for n, a in shadow.items()}
        emitted = emitted.at[slot].set(False, mode="drop")
        return emitted, _put(cols, block, at), _put(valids, valid, at)

    def insert(carry, at, pos, valid):
        emitted, shadow, cols, valids = carry
        slot = jnp.where(valid, slot_s[pos], cap)
        block = {n: a.at[slot].get(mode="fill", fill_value=0)
                 for n, a in rows.items()}
        emitted = emitted.at[slot].set(True, mode="drop")
        shadow = {n: a.at[slot].set(block[n], mode="drop")
                  for n, a in shadow.items()}
        return emitted, shadow, _put(cols, block, at), _put(valids, valid, at)

    empty = (
        {n: jnp.zeros(out_lanes, a.dtype) for n, a in rows.items()},
        jnp.zeros(out_lanes, jnp.bool_),
    )
    with jax.named_scope("topn/diff/retract"):
        (emitted, ret_cols, ret_valid), ret_lanes = _in_turns(
            ret_set, out_lanes, 0, retract, (emitted,) + empty
        )
    with jax.named_scope("topn/diff/insert"):
        (emitted, shadow, ins_cols, ins_valid), ins_lanes = _in_turns(
            ins_set, out_lanes, 0, insert, (emitted, shadow) + empty
        )
    with jax.named_scope("topn/diff/status"):
        n_ret, n_ins = ret_set.total, ins_set.total
        status = jnp.stack(
            [
                n_ret,
                n_ins,
                groups,
                ((n_ret > out_lanes) | (n_ins > out_lanes)).astype(jnp.int32),
                dropped.astype(jnp.int32),
                table.occupancy(),
                table.num_live(),
                passes,
                ret_lanes + ins_lanes,
                stay.astype(jnp.int32),
            ]
        )
    chunks = _delta_chunks(
        ret_cols, ret_valid, ins_cols, ins_valid, out_lanes
    )
    tops = _relink(tops, ranked, cap)
    return emitted, shadow, chunks[0], chunks[1], status, tops


def _stay(ranked: _Ranked):
    """A scalar: the ranking is of candidates that cannot answer, and
    nothing may be written from it."""
    if ranked.full_rank is None:
        return jnp.zeros((), jnp.bool_)
    return ranked.full_rank


@jax.named_scope("topn/diff/relink")
def _relink(tops: Optional[_Tops], ranked: _Ranked, cap: int):
    """Every group of ``ranked`` (its lanes lie together, first-ranked
    first) has its chain rewritten: the head from the lane the group
    starts at, each row of the top k pointing at the lane after it.
    Over all of the store's lanes the heads start from none, so that a
    group no lane stands for keeps no row. A lane that writes nothing
    scatters to a place of its own past the end: the indices are
    unique, and said to be."""
    if tops is None:
        return None
    packed_s, in_topk_s, seg_start = ranked[:3]
    pos = jnp.arange(packed_s.shape[0], dtype=jnp.int32)
    slot_s = packed_s & _SLOT_MASK
    write = ~_stay(ranked)
    follows = jnp.concatenate(
        [in_topk_s[1:] & (seg_start[1:] == seg_start[:-1]),
         jnp.zeros(1, jnp.bool_)]
    )
    after_s = jnp.where(follows, jnp.roll(slot_s, -1), -1)
    head = tops.head
    if ranked.full_rank is None:
        head = jnp.full_like(head, -1)
    head = head.at[
        jnp.where(
            write & (seg_start == pos) & (ranked.group >= 0),
            ranked.group, cap + pos,
        )
    ].set(jnp.where(in_topk_s, slot_s, -1), mode="drop", unique_indices=True)
    after = tops.after.at[
        jnp.where(write & in_topk_s, slot_s, cap + pos)
    ].set(after_s, mode="drop", unique_indices=True)
    return _Tops(head, after)


# The same barrier for a Top-N whose rank is a column of its output
# (NEXmark q19: ROW_NUMBER() selected beside the row). A row then has to
# be handed on again when its rank moved and nothing else of it did, so
# the rank as handed on is kept beside ``shadow`` (``erank``, 0 = not
# handed on), rides along ``_rank``'s sort beside the slot, and joins
# the diff. One input row can now move up to k rows each way, so a delta
# may pass the lanes its epoch's chunks held: the gathers are cut into
# rounds of ``out_lanes`` (``start``, a device scalar: one program
# whatever the round), every round computed from the SAME ranking. The
# diff is this body and not the one above with switches all through it,
# so that a Top-N that hands on no rank compiles what it always did.
def _diff_gather_numbered(
    table: HashTable,
    rows: Dict[str, jnp.ndarray],
    shadow: Dict[str, jnp.ndarray],
    emitted: jnp.ndarray,
    erank: jnp.ndarray,
    ranked,
    dropped: jnp.ndarray,
    start: jnp.ndarray,
    rank_col: str,
    out_lanes: int,
    tops: Optional[_Tops] = None,
):
    """``_diff_gather`` for a rank that is handed on as ``rank_col``
    (BIGINT, 1-based): a row is retracted and inserted again when its
    rank differs from the one it was handed on with, too. One round:
    the retractions and the insertions numbered ``start`` to ``start +
    out_lanes`` of the whole delta, which ``status`` counts; the host
    asks for further rounds while a count passes what it has.

    Rounds read nothing an earlier round wrote: the masks come from
    ``ranked`` alone, a retraction's old values from ``shadow`` at
    slots no other round writes (a row that is retracted AND inserted
    has its shadow renewed by the round that retracts it, after the
    gather; a row only inserted is retracted by none), and ``emitted``
    / ``erank`` are cleared only for a row that leaves for good.

    Returns (emitted, erank, shadow, retractions, insertions, status,
    tops) with status = [retract rows, insert rows, touched groups,
    rows moved for their rank alone, dropped latch, slots claimed, live
    rows, sorts made, lanes the gathers' turns covered, ``full_rank``].
    (Every round rewrites the chains, from the same ranking, the same.)"""
    with jax.named_scope("topn/diff/masks"):
        cap = table.capacity
        packed_s, in_topk_s, seg_start, passes, erank_s = ranked[:5]
        stay = _stay(ranked)
        slot_s = packed_s & _SLOT_MASK
        emitted_s = ((packed_s >> _EMITTED_BIT) & 1) > 0
        dirty_s = ((packed_s >> _DIRTY_BIT) & 1) > 0
        redo_s = ((packed_s >> _REDO_BIT) & 1) > 0
        pos = jnp.arange(packed_s.shape[0], dtype=jnp.int32)
        rank_s = jnp.where(in_topk_s, pos - seg_start + 1, 0)
        moved_s = emitted_s & in_topk_s & ~redo_s & (erank_s != rank_s) & ~stay
        again_s = redo_s | moved_s
        ret_s = emitted_s & (~in_topk_s | again_s) & ~stay
        ins_s = in_topk_s & (~emitted_s | again_s) & ~stay
        groups = _touched_groups(dirty_s, seg_start)
        ret_set, ins_set = _count_set(ret_s), _count_set(ins_s)

    def retract(carry, at, pos, valid):
        emitted, erank, shadow, cols, valids = carry
        slot = jnp.where(valid, slot_s[pos], cap)
        # retracted and inserted again / gone for good
        both_slot = jnp.where(ins_s[pos], slot, cap)
        gone_slot = jnp.where(ins_s[pos], cap, slot)
        block = {n: a.at[slot].get(mode="fill", fill_value=0)
                 for n, a in shadow.items()}
        block[rank_col] = jnp.where(valid, erank_s[pos], 0).astype(jnp.int64)
        emitted = emitted.at[gone_slot].set(False, mode="drop")
        erank = erank.at[gone_slot].set(0, mode="drop")
        shadow = {
            n: a.at[both_slot].set(
                rows[n].at[both_slot].get(mode="fill", fill_value=0),
                mode="drop",
            )
            for n, a in shadow.items()
        }
        return (emitted, erank, shadow, _put(cols, block, at),
                _put(valids, valid, at))

    def insert(carry, at, pos, valid):
        emitted, erank, shadow, cols, valids = carry
        slot = jnp.where(valid, slot_s[pos], cap)
        # new downstream: no round retracts it, so none reads its shadow
        fresh_slot = jnp.where(ret_s[pos], cap, slot)
        rank = jnp.where(valid, rank_s[pos], 0)
        block = {n: a.at[slot].get(mode="fill", fill_value=0)
                 for n, a in rows.items()}
        emitted = emitted.at[slot].set(True, mode="drop")
        erank = erank.at[slot].set(rank, mode="drop")
        shadow = {n: a.at[fresh_slot].set(block[n], mode="drop")
                  for n, a in shadow.items()}
        block[rank_col] = rank.astype(jnp.int64)
        return (emitted, erank, shadow, _put(cols, block, at),
                _put(valids, valid, at))

    empty = (
        {n: jnp.zeros(out_lanes, a.dtype) for n, a in rows.items()}
        | {rank_col: jnp.zeros(out_lanes, jnp.int64)},
        jnp.zeros(out_lanes, jnp.bool_),
    )
    with jax.named_scope("topn/diff/retract"):
        (emitted, erank, shadow, ret_cols, ret_valid), ret_lanes = _in_turns(
            ret_set, out_lanes, start, retract, (emitted, erank, shadow) + empty
        )
    with jax.named_scope("topn/diff/insert"):
        (emitted, erank, shadow, ins_cols, ins_valid), ins_lanes = _in_turns(
            ins_set, out_lanes, start, insert, (emitted, erank, shadow) + empty
        )
    with jax.named_scope("topn/diff/status"):
        status = jnp.stack(
            [
                ret_set.total,
                ins_set.total,
                groups,
                jnp.sum(moved_s.astype(jnp.int32)),
                dropped.astype(jnp.int32),
                table.occupancy(),
                table.num_live(),
                passes,
                ret_lanes + ins_lanes,
                stay.astype(jnp.int32),
            ]
        )
    chunks = _delta_chunks(
        ret_cols, ret_valid, ins_cols, ins_valid, out_lanes
    )
    tops = _relink(tops, ranked, cap)
    return emitted, erank, shadow, chunks[0], chunks[1], status, tops


@jax.jit
def _count_valid(total, valid):
    """A chunk's valid rows onto the epoch's running count (on the
    device: the barrier reads it with its status)."""
    return total + jnp.sum(valid, dtype=jnp.int32)


def _any_differs(rows, shadow):
    """Per lane: does any column of ``rows`` differ from ``shadow``'s."""
    return reduce(jnp.logical_or, (a != shadow[n] for n, a in rows.items()))


@partial(jax.jit, static_argnames=("k", "descs", "n_group"))
def _topk_ranks(table: HashTable, order_lanes, k: int, descs, n_group: int):
    """Per slot: the row's rank in its group where it is in the top-k,
    else 0 (a restore's rebuild of ``emitted`` and ``erank``; the
    barrier never leaves the sorted order). ``order_lanes`` / ``descs``
    as ``_rank_sorted`` takes them."""
    cap = table.capacity
    packed_s, in_topk_s, seg_start, _ = _rank_sorted(
        table.keys, table.live, order_lanes, jnp.zeros(cap, jnp.int32), k,
        descs, n_group,
    )
    rank_s = jnp.arange(cap, dtype=jnp.int32) - seg_start + 1
    return jnp.zeros(cap, jnp.int32).at[packed_s & _SLOT_MASK].set(
        jnp.where(in_topk_s, rank_s, 0)
    )


def emission_lanes(epoch_lanes: int, capacity: int) -> int:
    """Lanes of the two chunks a barrier's second program gathers its
    deltas into: the smallest of ``_EMIT_FLOOR`` x 4^i that holds the
    lanes the epoch's chunks held (what bounds either delta, and all
    the host knows before the counts are read), and never more than the
    store. What is handed on is cut to the rows the status then counts
    (``RetractableGroupTopNExecutor._cut``)."""
    lanes = _EMIT_FLOOR
    while lanes < epoch_lanes:
        lanes *= 4
    return min(lanes, capacity)


def candidate_lanes(epoch_lanes: int, capacity: int, k: int) -> Optional[int]:
    """Lanes of the barrier's rank over an epoch's candidates: the
    emission sizes' smallest that holds twice the lanes the epoch's
    chunks held (each of their rows and, where k is 1, its group's old
    first; the program says when a larger k's did not fit). None where
    the store is to be ranked: the candidates' sorts, of that many
    lanes x (1 + k), would cover no fewer lanes than the store has, or
    they are more than the list of the epoch's slots holds."""
    lanes = emission_lanes(2 * epoch_lanes, capacity)
    if lanes > TOUCHED_MAX or lanes * (1 + k) >= capacity:
        return None
    return lanes


class RetractableGroupTopNExecutor(Executor, Checkpointable):
    """GROUP BY g ORDER BY o [DESC], ... LIMIT k with full retraction
    support (group_top_n.rs:63): deletes/updates crossing a group's
    top-k boundary re-emit the displaced/promoted rows exactly.
    ``order_col``: one column (its direction in ``desc``), or the
    order keys as (column, desc) pairs, most significant first; rows
    equal in every order key rank by the stream key ``pk``.

    TPU re-design: ONE pk-keyed row store holds every input row, and
    beside it every group's top k is kept as a chain through the row
    slots (``_Tops``, found through ``_Groups``: the reference's
    ``TopNCache`` beside its state table, in lanes; derived like
    ``emitted``, never checkpointed). A step probes each row's group
    beside the row and lists the slots it wrote. The barrier ranks, on
    the device, the listed rows and the chains of their groups — what
    the epoch touched, not the store — diffs the ranking against the
    lane of rows it has handed on (``emitted``; their values as handed
    on in ``shadow``), gathers the rows to retract and to insert into
    two chunks of a declared size and rewrites the chains, in two
    programs (``_rank``, ``_diff_gather``). The host reads ten counts
    and the epoch's input rows a barrier, in one read, walks no row,
    and hands each chunk on at the smallest declared size that holds
    the rows the read counted (``_cut``). Where the candidates cannot
    answer — a row of a group's top k was deleted or rewritten and
    rows stand behind it in the store, which the program sees and says
    in that read; or the chains are cold (after ``restore_state`` or a
    re-slotted store), or the epoch's lanes x (1 + k) are no fewer
    than the store's — the same two programs rank all of the store's
    lanes, exactly, and rewrite every chain from that.

    ``rank_col``: the name under which the row's rank in its group
    (ROW_NUMBER(): BIGINT, 1-based, ties by the stream key) is handed
    on as a column, or None. With it the rank as handed on is kept too
    (``erank``), a row whose rank moved is retracted and inserted again
    though nothing else of it changed (``_rank`` carries ``erank``
    along its sort, ``_diff_gather`` takes ``_diff_gather_numbered``
    for its body), and the second program runs once more for every
    ``lanes`` rows by which a delta passes the chunks' size (a row that
    enters at the top moves up to k - 1 others), the retractions of all
    rounds handed on before the insertions. Without it either delta is
    bounded by the lanes the epoch's chunks held, the pair runs once,
    and the two programs are what they were before a rank could be a
    column."""

    def __init__(
        self,
        group_by: Sequence[str],
        order_col: Union[str, Sequence[Tuple[str, bool]]],
        limit: int,
        pk: Sequence[str],
        schema_dtypes: Dict[str, object],
        desc: bool = False,
        capacity: int = 1 << 14,
        window_key: Optional[Tuple[str, int]] = None,
        table_id: str = "group_top_n",
        bucket_policy: Optional[BucketPolicy] = None,
        bucketed: bool = True,
        upstream: str = "unknown",
        rank_col: Optional[str] = None,
    ):
        self._buckets = (
            BucketAllocator(
                bucket_policy or BucketPolicy.from_capacity(capacity, grow_at=GROW_AT)
            )
            if bucketed
            else None
        )
        if rank_col is not None and rank_col in schema_dtypes:
            raise ValueError(
                f"rank column {rank_col!r} is a column of the input"
            )
        self.rank_col = rank_col
        self.group_by = tuple(group_by)
        self.order: Tuple[Tuple[str, bool], ...] = (
            ((order_col, bool(desc)),)
            if isinstance(order_col, str)
            else tuple((c, bool(d)) for c, d in order_col)
        )
        # ``_rank``'s static arguments: one key goes as the column and
        # its direction (the program q18 runs), several as two tuples
        if len(self.order) == 1:
            ((self.order_col, self.desc),) = self.order
        else:
            self.order_col, self.desc = map(tuple, zip(*self.order))
        self.limit = int(limit)
        self.pk = tuple(pk)
        # the kind of executor that feeds it (the planner says), a label
        # of the counter of its input rows
        self.upstream = upstream
        # row identity INCLUDES the group (group_top_n.rs keys state by
        # group key + pk): a row "moving" groups is two distinct rows,
        # so the old group's retraction is never lost
        self.store_keys = self.group_by + tuple(
            c for c in self.pk if c not in self.group_by
        )
        self.names = tuple(sorted(schema_dtypes))
        self._dtypes = {n: jnp.dtype(schema_dtypes[n]) for n in self.names}
        self.table = HashTable.create(
            capacity, tuple(self._dtypes[c] for c in self.store_keys)
        )
        self.rows = {
            n: jnp.zeros(capacity, self._dtypes[n]) for n in self.names
        }
        self.sdirty = jnp.zeros(capacity, jnp.bool_)
        self.stored = jnp.zeros(capacity, jnp.bool_)
        self.epoch_dirty = jnp.zeros(capacity, jnp.bool_)
        # rows handed on and still standing downstream, and every
        # column as it was handed on (neither is checkpointed: a
        # restore ranks the restored rows)
        self.emitted = jnp.zeros(capacity, jnp.bool_)
        self.shadow = {
            n: jnp.zeros(capacity, self._dtypes[n]) for n in self.names
        }
        # the rank each row was handed on with, 0 = not handed on
        # (derived like ``emitted``; only where the rank is a column)
        self.erank = (
            jnp.zeros(capacity, jnp.int32) if rank_col is not None else None
        )
        # the groups and each one's top k (derived too: ``_regroup``)
        self.groups, self.tops = self._regroup(self.table, None)
        # the slots the epoch's steps wrote, from lane 0 (``_step``);
        # its cursor, None from the step the list had no room for until
        # the barrier (which then ranks the store)
        self.listed = jnp.full(TOUCHED_MAX, -1, jnp.int32)
        self._listed_lanes: Optional[int] = 0
        # the chains do not say the groups' top k: the next barrier
        # ranks the store and rewrites them all
        self._cold = False
        # lanes of the chunks applied since the last barrier: what the
        # barrier's programs are sized from (``emission_lanes``,
        # ``candidate_lanes``) before its one read says what the delta
        # holds
        self._epoch_lanes = 0
        # valid rows of those chunks, counted on the device
        self._in_rows = jnp.zeros((), jnp.int32)
        # what the epoch's steps' two probes did (``PROBE_STATS`` a
        # table: the row store, the groups), summed on the device and
        # read with the barrier's status; the steps, counted here
        self._probes = self._no_probes = jax.device_put(
            np.zeros((2, len(PROBE_STATS)), np.int32)
        )
        self._probe_calls = 0
        # what ``topn.rank`` says of the program: the operands of a sort
        # (a word a digit of the store's key lanes, of the order keys —
        # 64 bits each — and of liveness, and the slot) and a row's bytes
        self._sort_operands = (
            sum(_n_digits(self._dtypes[c]) for c in self.store_keys)
            + 2 * len(self.order) + 1 + 1
        )
        self._row_bytes = sum(d.itemsize for d in self._dtypes.values())
        if rank_col is not None:
            # the rank as handed on rides along every sort, and is a
            # lane of the store
            self._sort_operands += 1
            self._row_bytes += self.erank.dtype.itemsize
        if window_key is not None and window_key[0] not in self.group_by:
            raise ValueError(
                "window_key must be one of the group columns (a closed "
                "window bounds its groups)"
            )
        self.window_key = window_key
        self.table_id = table_id
        self._bound = 0
        self._dropped = jnp.zeros((), jnp.bool_)

    def lint_info(self):
        emits = dict(self._dtypes)
        if self.rank_col is not None:
            # the rank lane: made here, no column of the input
            emits[self.rank_col] = jnp.dtype(jnp.int64)
        return {
            "expects": dict(self._dtypes),
            "emits": emits,
            "renames": {n: n for n in self.names},
            "keys": self.group_by,
            "state_pk": tuple(self.store_keys),
            "table_ids": (self.table_id,),
            "window_key": self.window_key[0] if self.window_key else None,
        }

    def trace_contract(self):
        return {
            "kind": "device",
            "trace_step": lambda c: _upsert_step_ed(
                self.table,
                self.rows,
                self.sdirty,
                self.epoch_dirty,
                c,
                self.store_keys,
                self.names,
                groups=self.groups,
                listed=self.listed,
                at=0,
                n_group=len(self.group_by),
            ),
            "state": (self.table, self.rows, self.groups),
            "donate": True,
            # the barrier ranks, diffs and gathers on the device into
            # chunks of a declared size (emission_lanes: the sizes below
            # are compiled when a graph-mode view is created, larger x4
            # steps when an epoch first needs one) and hands each on at
            # the smallest of the sizes below that holds its rows
            # (``_cut``): two of them, or with the rank a column
            # (``rank_lane``) two a round, as many rounds as the delta
            # takes at the gathers' size; the row store walks the
            # allocator's declared lattice
            "emission": "bucketed",
            "emission_caps": self.emission_sizes(),
            "rank_lane": self.rank_col,
            "window_buckets": (
                self._buckets.lattice if self._buckets is not None else None
            ),
        }

    # epochs whose barrier programs a view's creation compiles: one of
    # every pair of sizes (the candidates', the emissions') an epoch of
    # up to 2^15 lanes (4 chunks of 8,192) can take. A program costs a
    # start some 0.7 s to load even from a warm cache (PERF.md, PR 46),
    # so an epoch of up to 2^16 lanes finds its emission size compiled,
    # as before, and the candidates' next size up (262,144 lanes) is
    # compiled when an epoch first needs it, as larger emissions are
    _WARM_EPOCHS = (1, 1 << 14, 1 << 15)

    def emission_sizes(self) -> Tuple[int, ...]:
        """The emission sizes a view's creation compiles: what epochs
        of up to 2^16 lanes hand on."""
        cap = self.table.capacity
        return tuple(
            sorted({emission_lanes(n, cap) for n in self._WARM_EPOCHS})
        )

    def state_nbytes(self) -> int:
        """Device bytes held (host-side estimate; no sync)."""
        return sum(
            leaf.nbytes
            for leaf in jax.tree.leaves(
                (self.table, self.rows, self.shadow, self.emitted,
                 self.erank, self.epoch_dirty, self.sdirty, self.stored,
                 self.groups, self.tops, self.listed)
            )
        )

    def pin_max_bucket(self):
        """ShapeGovernor hook: freeze the row store at its high-water
        bucket (shrink disabled)."""
        if self._buckets is None:
            return {"pinned": False}
        return {
            "table_id": self.table_id,
            "pinned_cap": self._buckets.pin(),
        }

    def padding_stats(self):
        return {
            "capacity": self.table.capacity,
            "live": int(self.table.num_live()),
        }

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        for c in self.pk + self.group_by + tuple(c for c, _ in self.order):
            if c in chunk.nulls:
                raise ValueError(f"GroupTopN key column {c!r} cannot be NULL")
        self._maybe_grow(chunk.capacity)
        self._bound += chunk.capacity
        self._epoch_lanes += chunk.capacity
        # the step's enqueue (the device runs it asynchronously), as
        # actor.agg_step is for an aggregate
        at = self._listed_lanes
        if at is not None and at + chunk.capacity > self.listed.shape[0]:
            at = None
        self._listed_lanes = None if at is None else at + chunk.capacity
        with span("actor.topn_step", table_id=self.table_id):
            # (a list given up: the write lands where nothing reads)
            self._step(chunk, at or 0)
            self._probe_calls += 1
            self._in_rows = _count_valid(self._in_rows, chunk.valid)
        return []

    def _step(self, chunk: StreamChunk, at: int) -> None:
        (
            self.table,
            self.rows,
            self.sdirty,
            self.epoch_dirty,
            dropped,
            self.groups,
            self.listed,
            self._probes,
        ) = _upsert_step_ed(
            self.table,
            self.rows,
            self.sdirty,
            self.epoch_dirty,
            chunk,
            self.store_keys,
            self.names,
            groups=self.groups,
            listed=self.listed,
            at=at,
            n_group=len(self.group_by),
            probes=self._probes,
        )
        self._dropped = self._dropped | dropped

    def _regroup(self, table: HashTable, keep: Optional[jnp.ndarray]):
        """``_Groups`` of the rows ``keep`` marks in ``table`` (None:
        an empty store), and chains that hold no row yet: what a
        re-slotted or restored store starts from."""
        cap, n = table.capacity, len(self.group_by)
        gtable = HashTable.create(cap, tuple(x.dtype for x in table.keys[:n]))
        of_row = jnp.full(cap, -1, jnp.int32)
        if keep is not None:
            gtable, of_row, _, _ = lookup_or_insert(
                gtable, table.keys[:n], keep
            )
        none = jnp.full(cap, -1, jnp.int32)
        return _Groups(gtable, of_row), _Tops(none, jnp.copy(none))

    # -- the sizes, before they are met ----------------------------------
    def warm_emissions(self) -> List[StreamChunk]:
        """One chunk with no valid row of every declared emission size,
        for the actor's warm-up pass; each comes out of the barrier's
        own programs over a store nothing has dirtied — the rank over
        candidates at every size ``_WARM_EPOCHS`` can take, and the
        rank over the store — so those are compiled too."""
        if self._epoch_lanes:
            raise RuntimeError(f"{self.table_id}: warm-up after rows arrived")
        cap = self.table.capacity
        outs: Dict[Optional[int], set] = {}
        for n in self._WARM_EPOCHS:
            for cand in (candidate_lanes(n, cap, self.limit), None):
                outs.setdefault(cand, set()).add(emission_lanes(n, cap))
        chunks = {}
        for cand, sizes in outs.items():
            # one ranking a length, diffed at each emission size
            ranked = self._ranked(cand, self.epoch_dirty, 0)
            for out in sizes:
                chunks[out] = self._round(ranked, 0, out)[0]
        for out, chunk in chunks.items():
            # the cut of a delta that a smaller size holds (``_cut``)
            for size in chunks:
                if size < out:
                    chunk.leading(size)
        return [chunks[out] for out in sorted(chunks)]

    # one upsert step a chunk at the chunk's own width: takes the push
    # lattice (the rank is sized by the lanes the epoch's chunks held)
    per_chunk_step = True

    def warm(self, chunk: StreamChunk) -> Optional[List[StreamChunk]]:
        """``apply`` for the warm-up pass: the step's program over a
        chunk with no valid row, which claims no slot and dirties no
        row; no host bound moves and nothing grows."""
        if needs_plan(
            self._buckets, self.table.capacity, self._bound,
            chunk.capacity, GROW_AT,
        ):
            return []
        self._step(chunk, 0)
        # (compiled for the width; no valid row, so the count stands)
        _count_valid(self._in_rows, chunk.valid)
        return []

    def _maybe_grow(self, incoming: int):
        cap = self.table.capacity
        if not needs_plan(self._buckets, cap, self._bound, incoming, GROW_AT):
            return
        from risingwave_tpu.ops.hash_table import read_scalars

        claimed, survivors = read_scalars(
            self.table.occupancy(),
            jnp.sum((self.table.live | self.sdirty).astype(jnp.int32)),
        )
        new_cap = plan_capacity(
            self._buckets, cap, incoming, claimed, survivors, GROW_AT
        )
        if new_cap is not None:
            keep = self.table.live | self.sdirty
            new = HashTable.create(
                new_cap, tuple(x.dtype for x in self.table.keys)
            )
            new, slots, _, _ = lookup_or_insert(new, self.table.keys, keep)
            new = set_live(new, jnp.where(keep, slots, -1), self.table.live)
            idx = jnp.where(keep, slots, new_cap)

            def move(a):
                return (
                    jnp.zeros(new_cap, a.dtype).at[idx].set(a, mode="drop")
                )

            self.rows = {n: move(a) for n, a in self.rows.items()}
            self.shadow = {n: move(a) for n, a in self.shadow.items()}
            self.sdirty = move(self.sdirty)
            self.stored = move(self.stored)
            self.epoch_dirty = move(self.epoch_dirty)
            self.emitted = move(self.emitted)
            if self.erank is not None:
                self.erank = move(self.erank)
            self.table = new
            # the groups' slots and the chains named the old slots
            self.groups, self.tops = self._regroup(new, move(keep))
            self._listed_lanes, self._cold = None, True
            claimed = int(self.table.occupancy())
        self._bound = claimed

    def _rank_diff(
        self, out_lanes: int, cand_lanes: Optional[int], dirty, n_listed: int
    ):
        """The barrier's two programs at one emission size: the rank
        (``_ranked``), then the diff and the gathers (one program an
        emission size and a ranking's length). Returns (retractions,
        insertions, status on the device, the ranking: what a further
        round of a numbered Top-N is computed from)."""
        ranked = self._ranked(cand_lanes, dirty, n_listed)
        return self._round(ranked, 0, out_lanes) + (ranked,)

    def _ranked(self, cand_lanes: Optional[int], dirty, n_listed: int):
        """The barrier's first program: over ``cand_lanes`` candidates
        of the ``n_listed`` listed slots (one program a size), or with
        None over the store (one a capacity). ``dirty``: the epoch's
        dirty lane."""
        return _rank(
            self.table,
            self.rows,
            self.shadow,
            self.emitted,
            dirty,
            self.limit,
            self.desc,
            len(self.group_by),
            self.order_col,
            self.erank,
            groups=self.groups,
            **(
                {}
                if cand_lanes is None
                else dict(
                    tops=self.tops, listed=self.listed, n_listed=n_listed,
                    cand_lanes=cand_lanes,
                )
            ),
        )

    def _round(self, ranked: _Ranked, start: int, out_lanes: int):
        """One round of the delta: the retractions and the insertions
        from the ``start``-th on (a Top-N that hands on no rank makes
        one). (retractions, insertions, status on the device)."""
        numbered = (
            {}
            if self.rank_col is None
            else dict(
                erank=self.erank, start=jnp.asarray(start, jnp.int32),
                rank_col=self.rank_col,
            )
        )
        self.emitted, *erank, self.shadow, ret, ins, status, self.tops = (
            _diff_gather(
                self.table,
                self.rows,
                self.shadow,
                self.emitted,
                ranked,
                self._dropped,
                out_lanes,
                tops=self.tops,
                **numbered,
            )
        )
        if erank:
            (self.erank,) = erank
        return ret, ins, status

    def _cut(self, chunk: StreamChunk, rows: int) -> StreamChunk:
        """``chunk``, a delta's ``rows`` in its leading lanes
        (``_diff_gather`` packs them so), at the smallest declared
        emission size that holds them: what follows the Top-N pays for
        a chunk's lanes, rows or not (the general over-window's step
        probes, scatters and gathers every one: PERF.md 6, PR 50 and
        52), and the gathers' size was fixed from the epoch's lanes
        before the status counted the delta. The same rows, ops and
        order; a delta that no smaller size holds goes on as it is."""
        size = next(
            (s for s in self.emission_sizes() if s >= rows), chunk.capacity
        )
        return chunk.leading(size) if size < chunk.capacity else chunk

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        cap = self.table.capacity
        if not self._epoch_lanes:
            # no chunk since the last barrier: nothing is dirty, the
            # latch and the occupancy stand as they were read then
            if self._buckets is not None:
                self._buckets.note_barrier(cap, self._bound)
            return []
        lanes = emission_lanes(self._epoch_lanes, cap)
        # the candidates' lanes; None (the store is ranked) where the
        # chains are cold, the list was given up or the sizes say so
        cand = (
            None
            if self._cold or self._listed_lanes is None
            else candidate_lanes(self._epoch_lanes, cap, self.limit)
        )
        numbered = self.rank_col is not None
        with span(
            "topn.rank", table_id=self.table_id, lanes=lanes, capacity=cap,
            order_keys=len(self.order), words=self._sort_operands,
            row_bytes=self._row_bytes, limit=self.limit,
            rank_emitted=numbered, candidates=cand or 0,
        ):
            dirty = self.epoch_dirty
            ret, ins, status, ranked = self._rank_diff(
                lanes, cand, dirty, self._listed_lanes or 0
            )
            self.epoch_dirty = jnp.zeros_like(dirty)
            self._epoch_lanes = self._listed_lanes = 0
            fed, self._in_rows = self._in_rows, jnp.zeros((), jnp.int32)
            # (one array of zeros for good: no program makes another)
            probes, self._probes = self._probes, self._no_probes
            calls, self._probe_calls = self._probe_calls, 0
        # ONE read for the counts, the latch, the occupancy, the epoch's
        # input rows and what its probes did; it waits for the rank
        with span("topn.pull", table_id=self.table_id) as sp:
            with device_read("topn.status", lanes=11 + probes.size):
                status, fed, probes = jax.device_get((status, fed, probes))
            *counts, sorts, gathered, full_rank = status.tolist()
            touched_passes = 0 if cand is None else sorts
            if full_rank:
                # the candidates could not answer, and their programs
                # wrote nothing: the store, by the same two
                ret, ins, status, ranked = self._rank_diff(
                    lanes, None, dirty, 0
                )
                with device_read("topn.status", lanes=10):
                    *counts, sorts, gathered, _ = jax.device_get(
                        status
                    ).tolist()
            full_rank = int(full_rank or cand is None)
            passes = sorts if full_rank else 0
            # (a rank over the store rewrites every chain)
            self._cold = False
            # (the fourth count: of a numbered Top-N the rows that moved
            # for their rank alone; else whether a delta passed ``lanes``)
            n_ret, n_ins, groups, fourth, dropped, claimed, live = counts
            moved, overflow = (fourth, 0) if numbered else (0, fourth)
            # a numbered delta beyond the chunks' size takes more rounds,
            # whose turns follow from the counts as the first's did
            rounds = max(1, -(-max(n_ret, n_ins) // lanes)) if numbered else 1
            block = math.gcd(_GATHER_LANES, lanes)
            gathered += sum(
                -(-min(n - r * lanes, lanes) // block) * block
                for r in range(1, rounds)
                for n in (n_ret, n_ins)
                if n > r * lanes
            )
            # ``passes``: the sorts over the store's capacity (none
            # where the candidates answered); ``ranked_lanes``: the
            # lanes this barrier's ranking sorts ran over;
            # ``sifted_lanes``: what the candidates' own two sorts, of
            # one operand, ran over to find them
            ranked_lanes = (cand or 0) + (cap if full_rank else 0)
            sp.args.update(
                rows=n_ret + n_ins, groups=groups, passes=passes,
                rank_moved_rows=moved, rounds=rounds, gather_lanes=gathered,
                touched_passes=touched_passes, ranked_lanes=ranked_lanes,
                sifted_lanes=(cand or 0) * (1 + self.limit),
                full_rank=full_rank, rank_calls=1,
            )
        with span(
            "topn.diff",
            stage="topn_diff",
            table_id=self.table_id,
            groups=groups,
            retract_rows=n_ret,
            insert_rows=n_ins,
            rank_moved_rows=moved,
            rounds=rounds,
        ) as sp:
            self._bound = int(claimed)
            if self._buckets is not None:
                self._buckets.note_barrier(cap, self._bound)
            note_probes(
                "topn.rows", self.table_id, calls, probes[0], cap,
                claimed=claimed,
            )
            note_probes("topn.groups", self.table_id, calls, probes[1], cap)
            if dropped:
                raise RuntimeError(
                    "GroupTopN row store overflowed; grow capacity"
                )
            if overflow:
                # no rank handed on: a row that comes or goes moves at
                # most one other across the top-k's edge, so this is a
                # fault of the program and not a size to grow
                raise RuntimeError(
                    f"{self.table_id}: a barrier's delta ({n_ret} "
                    f"retractions, {n_ins} insertions) passed the "
                    f"{lanes} lanes its epoch's chunks held, which bound "
                    "it where no rank is handed on"
                )
            REGISTRY.counter("group_topn_touched_groups_total").inc(
                groups, table_id=self.table_id
            )
            REGISTRY.counter("group_topn_input_rows_total").inc(
                int(fed), table_id=self.table_id, upstream=self.upstream
            )
            emitted = REGISTRY.counter("group_topn_emitted_rows_total")
            emitted.inc(n_ret, table_id=self.table_id, op="retract")
            emitted.inc(n_ins, table_id=self.table_id, op="insert")
            REGISTRY.counter("group_topn_gathered_lanes_total").inc(
                gathered, table_id=self.table_id
            )
            REGISTRY.counter("group_topn_ranked_lanes_total").inc(
                ranked_lanes, table_id=self.table_id
            )
            REGISTRY.counter("group_topn_full_ranks_total").inc(
                full_rank, table_id=self.table_id
            )
            if numbered:
                REGISTRY.counter("group_topn_rank_moved_rows_total").inc(
                    moved, table_id=self.table_id
                )
            REGISTRY.gauge("group_topn_rows").set(
                float(live), table_id=self.table_id
            )
            rets, inss = [ret], [ins]
            for r in range(1, rounds):
                ret, ins, _ = self._round(ranked, r * lanes, lanes)
                rets.append(ret)
                inss.append(ins)
            # each round's chunk that holds a row, at the size its rows
            # take (a round before the last is full). Retractions first:
            # an UPDATE's old row leaves the view before its new one
            # enters under the same key (so every round's retractions
            # before any round's insertions)
            handed: List[StreamChunk] = []
            emit_lanes = REGISTRY.counter("group_topn_emitted_lanes_total")
            for op, n, chunks in (
                ("retract", n_ret, rets), ("insert", n_ins, inss)
            ):
                cut = [
                    self._cut(c, min(n - r * lanes, lanes))
                    for r, c in enumerate(chunks[: -(-n // lanes)])
                ]
                emit_lanes.inc(
                    sum(c.capacity for c in cut),
                    table_id=self.table_id, op=op,
                )
                handed += cut
            sp.args.update(emit_lanes=sum(c.capacity for c in handed))
            return handed

    def on_watermark(self, watermark):
        """Window-bounded groups expire silently below the watermark
        (EOWC-final: the MV keeps the closed window's final top-k)."""
        if self.window_key is None or watermark.column != self.window_key[0]:
            return watermark, []
        cutoff = jnp.asarray(
            watermark.value - self.window_key[1], jnp.int64
        )
        lane = self.rows[self.window_key[0]]
        expired = self.table.live & (lane < cutoff)
        slots = jnp.where(
            expired, jnp.arange(self.table.capacity, dtype=jnp.int32), -1
        )
        self.table = set_live(self.table, slots, False)
        self.sdirty = self.sdirty | expired
        # closed groups leave ``emitted`` without a retraction
        self.emitted = self.emitted & ~expired
        if self.erank is not None:
            self.erank = jnp.where(expired, 0, self.erank)
        return watermark, []

    # -- checkpoint/restore (pk-keyed row store, plain-TopN layout) -------
    def digest_lanes(self):
        lanes = {f"k{i}": k for i, k in enumerate(self.table.keys)}
        for n in self.names:
            lanes[f"r_{n}"] = self.rows[n]
        return lanes, self.table.live

    def state_digest(self) -> int:
        from risingwave_tpu.integrity import host_digest

        return host_digest(*self.digest_lanes())

    def checkpoint_delta(self) -> List[StateDelta]:
        marks = classify_marks(self.sdirty, self.table.live, self.stored)
        self.sdirty, self.stored = marks.sdirty, marks.stored
        if not len(marks):
            return []
        lanes = {f"k{i}": lane for i, lane in enumerate(self.table.keys)}
        key_names = tuple(lanes)
        for n in self.names:
            lanes[f"r_{n}"] = self.rows[n]
        pulled = pull_rows(lanes, marks)
        keys = {x: pulled[x] for x in key_names}
        vals = {x: v for x, v in pulled.items() if x not in key_names}
        return [
            StateDelta(self.table_id, keys, vals, marks.tombstone, key_names)
        ]

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        n = len(next(iter(key_cols.values()))) if key_cols else 0
        cap = grow_pow2(n, self.table.capacity, GROW_AT)
        key_dtypes = tuple(x.dtype for x in self.table.keys)
        table = HashTable.create(cap, key_dtypes)
        rows = {nm: jnp.zeros(cap, self._dtypes[nm]) for nm in self.names}
        self.sdirty = jnp.zeros(cap, jnp.bool_)
        self.stored = jnp.zeros(cap, jnp.bool_)
        self.epoch_dirty = jnp.zeros(cap, jnp.bool_)
        if n:
            lanes = tuple(
                jnp.asarray(np.asarray(key_cols[f"k{i}"], dtype=d))
                for i, d in enumerate(key_dtypes)
            )
            table, slots, _, _ = lookup_or_insert(
                table, lanes, jnp.ones(n, jnp.bool_)
            )
            table = set_live(table, slots, True)
            rows = {
                nm: a.at[slots].set(
                    jnp.asarray(
                        np.asarray(value_cols[f"r_{nm}"]).astype(a.dtype)
                    )
                )
                for nm, a in rows.items()
            }
            self.stored = self.stored.at[slots].set(True)
        self.table = table
        self.rows = rows
        # the groups anew; the chains wait for the next barrier's rank
        self.groups, self.tops = self._regroup(
            table, table.live if n else None
        )
        self._cold = bool(n)
        self._bound = int(n)
        self._epoch_lanes = self._listed_lanes = 0
        self._in_rows = jnp.zeros((), jnp.int32)
        self._dropped = jnp.zeros((), jnp.bool_)
        # every group's current top-k stands downstream (the MV was
        # restored to exactly this view), with the values the rows hold
        ranks = (
            _topk_ranks(
                table, tuple(rows[c] for c, _ in self.order), self.limit,
                tuple(d for _, d in self.order), len(self.group_by),
            )
            if n
            else jnp.zeros(cap, jnp.int32)
        )
        self.emitted = ranks > 0
        if self.erank is not None:
            # ... under the ranks the restored rows have
            self.erank = ranks
        self.shadow = {nm: jnp.copy(a) for nm, a in rows.items()}
