"""Fused device-resident barrier step — compile a fragment's fusible
executor run into ONE donated jitted program per barrier.

PR 6's profiler pinned the 10x-throughput gap on the host dispatch
wall (~319ms/barrier of Python walking executor chains vs 0.24ms of
device compute), and the fusion analyzer's FUSION_REPORT.json named
the blockers per executor. This module is the engine that cashes the
analysis in (ROADMAP item 1, the TiLT direction from PAPERS.md:
compile whole time-centric queries instead of interpreting
per-operator):

- :func:`fuse_chain` rewrites an actor chain's maximal fusible run —
  ``stateless-pure*  [HashAgg]  stateless-pure*  [DeviceMaterialize]
  stateless-pure*`` — into a :class:`FusedChainExecutor`. Anything
  the run cannot absorb (joins, dedup, host materializers, watermark
  generators, subclasses) passes through untouched and keeps the
  per-executor interpreted path: interpretation IS the automatic
  fallback, per run, not per process.
- :class:`FusedChainExecutor` buffers the epoch's chunks (the
  EpochBatchedAgg discipline: pow2-padded stacked batches, signature
  changes flush) and, at the barrier, runs ONE jitted
  ``fused_step(state_pytree, chunks) -> (state_pytree, deltas,
  scalars)`` with ``donate_argnums`` on the state pytree — keyed agg
  state and the device MV live in HBM across barriers; the host
  touches only ingest and the staged-scalar commit read.
- State ownership never moves: the member executors keep their state
  between programs (the wrapper reads it per barrier and writes the
  donated program's outputs back), so checkpoint/restore, recovery
  rebuilds, cold-tier hooks, snapshots and the shape governor all
  keep working against the original objects.

Compile discipline: the program's statics are value-hashable
(:class:`FusedPlan` hashes the member steps' ``functools.partial``
keys, the ComposedSteps contract), so graph rebuilds and recovery
re-fuse into the SAME compiled program; distinct (flush_rounds, pads,
has_data) combinations are a small closed set in steady state.
"""

from __future__ import annotations

import os
from contextlib import nullcontext
from dataclasses import dataclass, fields as _dc_fields
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from risingwave_tpu.array.chunk import StreamChunk, stack_chunks
from risingwave_tpu.executors.base import Barrier, Executor, Watermark
from risingwave_tpu.executors.dedup import (
    AppendOnlyDedupExecutor,
    dedup_step_fn,
)
from risingwave_tpu.executors.dynamic_filter import (
    DynamicMaxFilterExecutor,
    filter_step_fn,
)
from risingwave_tpu.executors.epoch_batch import (
    ComposedSteps,
    _compose_lint_infos,
)
from risingwave_tpu.executors.hash_agg import (
    HashAggExecutor,
    _epoch_reduced_fn,
    delta_to_chunk,
)
from risingwave_tpu.executors.hash_join import (
    HashJoinExecutor,
    join_step_fn,
)
from risingwave_tpu.executors.materialize import (
    DeviceMaterializeExecutor,
    mv_step_fn,
)
from risingwave_tpu import integrity
from risingwave_tpu.expr.expr import StaticTree, lift_literals, param_scope
from risingwave_tpu.ops import agg as agg_ops
from risingwave_tpu.ops.bucketing import padding_fraction
from risingwave_tpu.trace import span
from risingwave_tpu.profiler import PROFILER
from risingwave_tpu.array.lattice import flush_pad_schedule

__all__ = [
    "FusedChainExecutor",
    "FusedTwoInputExecutor",
    "expand_fused",
    "fuse_chain",
    "fuse_pipeline",
    "fuse_two_input",
    "fused_cache_stats",
    "fused_enabled",
    "fused_fragments",
    "fusion_refusals",
    "lift_enabled",
    "lift_plan",
    "pipeline_depth",
    "two_input_enabled",
]


def fused_enabled() -> bool:
    """RW_FUSED_STEP=0 is the kill switch: the graph runtime then
    falls back to the per-epoch batched (still interpreted) path."""
    return os.environ.get("RW_FUSED_STEP", "1").strip().lower() not in (
        "0",
        "off",
        "false",
    )


def lift_enabled() -> bool:
    """RW_FUSED_LIFT=0 disables multi-tenant constant lifting: every
    parameter variant then compiles its own fused program (the
    pre-PR-12 behavior)."""
    return os.environ.get("RW_FUSED_LIFT", "1").strip().lower() not in (
        "0",
        "off",
        "false",
    )


def two_input_enabled() -> bool:
    """RW_FUSED_TWO_INPUT=0 disables whole-pipeline two-input fusion:
    two-input pipelines then fall back to the PR 10 per-chain policy
    (epoch-batched agg side, interpreted join, fused-or-interpreted MV
    tail) — the differential-testing twin of the fused path."""
    return os.environ.get(
        "RW_FUSED_TWO_INPUT", "1"
    ).strip().lower() not in ("0", "off", "false")


def pipeline_depth(explicit: Optional[int] = None) -> int:
    """K-barrier device pipelining depth: the fused wrapper defers its
    blocking staged-scalar materialization (and latch checks, telemetry
    decode, input retirement) to every K-th barrier, so K consecutive
    barriers' donated programs sit queued on the device back-to-back
    with ZERO host synchronization between them — the host enqueues
    barrier N+1 while N still runs and leaves the steady state
    entirely. Watermark/checkpoint walks stay at the K-boundary;
    members remain the system of record with per-barrier state
    write-back (the written-back arrays are futures of the in-flight
    program, so recovery/governor/cold-tier contracts see exactly the
    state they always did once they materialize). K=1 (default) is the
    per-barrier fused behavior."""
    if explicit is not None:
        return max(1, int(explicit))
    try:
        return max(1, int(os.environ.get("RW_FUSED_PIPELINE_DEPTH", "1")))
    except ValueError:
        return 1


# ---------------------------------------------------------------------------
# fusion-refusal provenance (the anti-silent-fallback contract)
# ---------------------------------------------------------------------------

_REFUSALS: List[dict] = []
_REFUSALS_CAP = 256  # bounded: graph rebuilds re-refuse per spawn


def _refuse(label: str, reason: str, executor: Optional[str] = None):
    """Record WHY a chain/pipeline was left interpreted (RW-E807):
    fusion policy must never fall back silently — every refusal
    carries fragment + executor provenance, queryable via
    :func:`fusion_refusals` and mirrored into the meta event log."""
    rec = {
        "code": "RW-E807",
        "fragment": label,
        "executor": executor,
        "message": reason,
    }
    if len(_REFUSALS) >= _REFUSALS_CAP:
        del _REFUSALS[: _REFUSALS_CAP // 2]
    _REFUSALS.append(rec)
    try:
        from risingwave_tpu.event_log import EVENT_LOG

        EVENT_LOG.record("fusion_refused", **rec)
    except Exception:  # noqa: BLE001 — provenance is best effort
        pass
    return None


def fusion_refusals(clear: bool = False) -> List[dict]:
    """Every recorded fusion refusal (RW-E807 provenance) since process
    start (or the last ``clear=True`` call)."""
    out = list(_REFUSALS)
    if clear:
        _REFUSALS.clear()
    return out


# ---------------------------------------------------------------------------
# static plan (jit cache key)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AggStatics:
    """The HashAgg member's jit statics (all value-hashable)."""

    calls: tuple
    group_keys: tuple
    nullable: tuple
    out_cap: int
    float_extremes: tuple
    has_minput: bool


@dataclass(frozen=True)
class FusedPlan:
    """The fused program's static shape: pure-step segments around at
    most one HashAgg and at most one DeviceMaterialize (agg strictly
    before mv). ``pre``/``mid``/``post`` are ComposedSteps (value-
    hashable compositions of the members' ``pure_step()`` partials),
    so two plans over equal step sequences share one compiled
    program."""

    pre: Optional[ComposedSteps]
    agg: Optional[AggStatics]
    mid: Optional[ComposedSteps]
    mv_pk: Optional[tuple]
    mv_cols: Optional[tuple]
    post: Optional[ComposedSteps]

    @property
    def has_mv(self) -> bool:
        return self.mv_pk is not None


def _delta_chunk(delta: dict, a: AggStatics, pad: Optional[int]) -> StreamChunk:
    """The flush delta -> chunk decode, shared with the interpreted
    path (hash_agg.delta_to_chunk is the one lane-contract decoder),
    with the host-chosen static pad slice."""
    return delta_to_chunk(delta, a.group_keys, a.nullable, a.calls, pad)


def _fused_barrier_fn(
    states, stacked, params, plan, flush_rounds, pads, has_data
):
    """The whole fragment-barrier as one pure function over
    ``states = (agg_state, mv_state)``:

    data phase  — the epoch's stacked chunks through the pure prefix
                  into the agg's flatten+reduce epoch path (ONE table
                  touch per distinct key), or — agg-less runs —
                  through the steps into the device MV as one
                  flattened batch;
    flush phase — ``flush_rounds`` device flushes of the agg's dirty
                  groups, each delta walking mid-steps -> device MV ->
                  post-steps (the fragment's per-barrier emission);
    scalars     — the members' barrier latches + occupancy counters
                  PLUS the device-computed telemetry lane (rows
                  applied, dirty groups drained, MV rows written) —
                  all packed into one int64 lane for the overlapped
                  finish_barrier read: per-member visibility at zero
                  extra dispatches and zero new host syncs.

    Each phase carries a ``jax.named_scope`` (fused/apply, fused/flush,
    fused/mv_write, fused/scalar_pack) so a ``jax_trace`` capture
    segments the ONE compiled program back into stages
    (deviceprof.parse_fused_stages).
    """
    # lifted-literal parameter vectors (``params``) bind for the whole
    # trace: plan segments containing LiftedLit slots read them as a
    # RUNTIME operand, so K parameter variants of one plan shape share
    # this single compiled program (multi-tenant compile sharing)
    with param_scope(params):
        return _fused_barrier_body(
            states, stacked, plan, flush_rounds, pads, has_data
        )


def _fused_barrier_body(states, stacked, plan, flush_rounds, pads, has_data):
    agg_st, mv_st = states
    outs: List[StreamChunk] = []
    mv_rows = jnp.zeros((), jnp.int32)

    def _through_mv(chunk):
        nonlocal mv_st, mv_rows
        if plan.mid is not None:
            chunk = plan.mid(chunk)
        if plan.has_mv:
            with jax.named_scope("fused/mv_write"):
                mv_rows = mv_rows + jnp.sum(chunk.valid.astype(jnp.int32))
                mtable, mstate = mv_st
                mtable, mstate = mv_step_fn(
                    mtable, mstate, chunk, plan.mv_pk, plan.mv_cols
                )
                mv_st = (mtable, mstate)
        if plan.post is not None:
            chunk = plan.post(chunk)
        return chunk

    rows_in = jnp.zeros((), jnp.int32)
    if has_data:
        rows_in = jnp.sum(stacked.valid.astype(jnp.int32))
        with jax.named_scope("fused/apply"):
            if plan.agg is not None:
                a = plan.agg
                table, st, dropped, minput, mi_bad = agg_st
                if a.has_minput:
                    table, st, dropped, minput, mi_bad = _epoch_reduced_fn(
                        table, st, dropped, stacked, a.calls, a.group_keys,
                        a.nullable, plan.pre, minput, mi_bad,
                    )
                else:
                    table, st, dropped = _epoch_reduced_fn(
                        table, st, dropped, stacked, a.calls, a.group_keys,
                        a.nullable, plan.pre,
                    )
                agg_st = (table, st, dropped, minput, mi_bad)
            else:
                chunks = (
                    jax.vmap(plan.pre)(stacked)
                    if plan.pre is not None
                    else stacked
                )
                # flatten the epoch into one batch: the MV's last-
                # occurrence-per-pk mask makes one flat step equivalent
                # to applying the chunks in order
                flat = jax.tree.map(
                    lambda x: x.reshape((-1,) + x.shape[2:]), chunks
                )
                outs.append(_through_mv(flat))

    # dirty groups pending at the barrier, sampled AFTER the epoch's
    # applies and BEFORE the flush drains them — the device-computed
    # twin of the interpreted agg's jnp.sum(state.dirty) at flush time
    dirty_groups = jnp.zeros((), jnp.int32)
    if plan.agg is not None:
        dirty_groups = jnp.sum(agg_st[1].dirty.astype(jnp.int32))

    if plan.agg is not None and flush_rounds:
        a = plan.agg
        table, st, dropped, minput, mi_bad = agg_st
        with jax.named_scope("fused/flush"):
            for r in range(flush_rounds):
                st, delta = agg_ops.flush(
                    st, table.keys, a.out_cap, a.float_extremes
                )
                outs.append(_through_mv(_delta_chunk(delta, a, pads[r])))
        agg_st = (table, st, dropped, minput, mi_bad)

    with jax.named_scope("fused/scalar_pack"):
        scal = []
        if plan.agg is not None:
            table, st, dropped, minput, mi_bad = agg_st
            scal += [dropped, st.minmax_retracted, mi_bad, table.occupancy()]
        if plan.has_mv:
            mtable, mstate = mv_st
            scal += [mstate.dropped, mtable.occupancy()]
        if scal:
            # telemetry tail rides the same staged read the barrier
            # already pays: rows applied, dirty groups, MV rows
            scal += [rows_in, dirty_groups, mv_rows]
            # state digests ride the SAME lane (integrity layer): the
            # fused twin of each member's host state_digest(), decoded
            # in _on_barrier_scalars — zero extra dispatches
            with jax.named_scope("fused/digest"):
                if plan.agg is not None:
                    table, st = agg_st[0], agg_st[1]
                    scal.append(
                        integrity.device_digest(
                            *integrity.agg_lanes(table, st)
                        )
                    )
                if plan.has_mv:
                    mtable, mstate = mv_st
                    scal.append(
                        integrity.device_digest(
                            *integrity.mv_lanes(mtable, mstate)
                        )
                    )
        packed = (
            jnp.stack([jnp.asarray(x).astype(jnp.int64) for x in scal])
            if scal
            else None
        )
    return (agg_st, mv_st), tuple(outs), packed


_fused_barrier_step = partial(
    jax.jit,
    static_argnames=("plan", "flush_rounds", "pads", "has_data"),
    donate_argnums=(0,),
)(_fused_barrier_fn)


# ---------------------------------------------------------------------------
# multi-tenant compile sharing: lift per-MV constants to runtime operands
# ---------------------------------------------------------------------------

_LIFT_STATS = {"lifted": 0, "rejected": 0}


def lift_plan(plan: FusedPlan):
    """Rewrite the plan's pure segments with numeric literals lifted
    into parameter slots. Returns ``(lifted_plan, params)`` — params
    being the ``{"i": int64[...], "f": float64[...]}`` operand the
    fused program receives at dispatch — or ``(None, None)`` when the
    plan carries no liftable constants. Two plans that differ only in
    literal VALUES produce EQUAL lifted plans (same slot structure),
    so the jit cache serves both from one compiled executable."""
    ints: List[int] = []
    floats: List[float] = []

    def lift_arg(a):
        if isinstance(a, StaticTree):
            return StaticTree(lift_literals(a.value, ints, floats))
        return a

    def lift_steps(cs: Optional[ComposedSteps]) -> Optional[ComposedSteps]:
        if cs is None:
            return None
        return ComposedSteps(
            [
                partial(
                    s.func,
                    *(lift_arg(a) for a in s.args),
                    **{k: lift_arg(v) for k, v in s.keywords.items()},
                )
                for s in cs.steps
            ]
        )

    import dataclasses as _dc

    lifted = _dc.replace(
        plan,
        pre=lift_steps(plan.pre),
        mid=lift_steps(plan.mid),
        post=lift_steps(plan.post),
    )
    if not ints and not floats:
        return None, None
    params = {
        "i": jnp.asarray(ints, jnp.int64),
        "f": jnp.asarray(floats, jnp.float64),
    }
    return lifted, params


def fused_cache_stats() -> dict:
    """The compile-sharing evidence: how many distinct fused programs
    the process actually compiled (jit cache entries) vs how many
    wrappers lifted constants into a shared shape."""
    try:
        compiled = int(_fused_barrier_step._cache_size())
    except Exception:  # noqa: BLE001 — jax-internal surface
        compiled = -1
    return {
        "compiled_programs": compiled,
        "plans_lifted": _LIFT_STATS["lifted"],
        "plans_lift_rejected": _LIFT_STATS["rejected"],
    }


# ---------------------------------------------------------------------------
# the wrapper executor
# ---------------------------------------------------------------------------


def _is_pure(ex: Executor) -> bool:
    """A stateless member the fused program can absorb: pure step, no
    generated watermarks, no barrier behavior (the wrapper never calls
    member.on_barrier for pure members)."""
    return (
        ex.pure_step() is not None
        and type(ex).emit_watermark is Executor.emit_watermark
        and type(ex).on_barrier is Executor.on_barrier
    )


class FusedChainExecutor(Executor):
    """One fusible run ``[pure*, HashAgg?, pure*, DeviceMaterialize?,
    pure*]`` executed as a single donated device program per barrier.

    Drop-in chain element (the EpochBatchedAggExecutor integration
    contract): ``apply`` buffers, ``on_barrier`` runs the program and
    returns the fragment's per-barrier emission, ``finish_barrier``
    materializes the packed member scalars and runs every member's
    latch checks at their original raise points. The member executor
    OBJECTS stay the system of record — checkpoint registries,
    recovery restores, the cold tier and the shape governor all keep
    talking to them; this wrapper is an execution strategy, not a
    state owner.
    """

    def __init__(
        self,
        members: Sequence[Executor],
        label: str = "fragment",
        covers_whole_chain: bool = False,
    ):
        self.members = list(members)
        self.label = label
        self.covers_whole_chain = covers_whole_chain
        self.agg: Optional[HashAggExecutor] = None
        self.mv: Optional[DeviceMaterializeExecutor] = None
        pre: List[Executor] = []
        mid: List[Executor] = []
        post: List[Executor] = []
        for ex in self.members:
            if type(ex) is HashAggExecutor:
                if self.agg is not None or self.mv is not None:
                    raise ValueError(
                        "fused run supports one HashAgg, before the MV"
                    )
                self.agg = ex
            elif type(ex) is DeviceMaterializeExecutor:
                if self.mv is not None:
                    raise ValueError("fused run supports one device MV")
                self.mv = ex
            elif _is_pure(ex):
                (post if self.mv is not None
                 else mid if self.agg is not None
                 else pre).append(ex)
            else:
                raise ValueError(f"{type(ex).__name__} is not fusible")
        steps = lambda exs: (
            ComposedSteps([e.pure_step() for e in exs]) if exs else None
        )
        agg_statics = None
        if self.agg is not None:
            agg_statics = AggStatics(
                calls=self.agg.calls,
                group_keys=self.agg.group_keys,
                nullable=self.agg.nullable,
                out_cap=self.agg.out_cap,
                float_extremes=self.agg._float_extremes,
                has_minput=bool(self.agg.minput),
            )
        self.plan = FusedPlan(
            pre=steps(pre),
            agg=agg_statics,
            mid=steps(mid),
            mv_pk=self.mv.pk if self.mv is not None else None,
            mv_cols=self.mv.columns if self.mv is not None else None,
            post=steps(post),
        )
        # multi-tenant compile sharing: literals lifted to runtime
        # operands, accepted only after a dtype-equivalence proof at
        # the first data barrier (weak-vs-strong scalar promotion can
        # change result dtypes — correctness beats sharing)
        self._exec_plan = self.plan
        self._params = None
        self._lift_state = "off"
        if lift_enabled():
            lifted, params = lift_plan(self.plan)
            if lifted is not None:
                self._lift_candidate = (lifted, params)
                self._lift_state = "pending"
        self._buf: List[StreamChunk] = []
        self._sig = None
        # telemetry bookkeeping: padded lane count of the last staged
        # program's stacked input (masked-lane fill denominator) and
        # the last materialized telemetry dict (deviceprof mirror)
        self._last_lanes = 0
        self._telemetry: Optional[dict] = None
        # device digests decoded at the last barrier (integrity layer):
        # member key -> uint64 fold, the fused twin of state_digest()
        self.last_digests: dict = {}
        # the previous program's consumed inputs, held until the
        # barrier fence: dropping a buffer an in-flight async program
        # still reads BLOCKS the host until the program completes (the
        # deallocation sync) — exactly the dispatch-wall stall the
        # fused step exists to remove. finish_barrier (which awaits the
        # program anyway) retires them instead.
        self._retired = None

    # -- static metadata --------------------------------------------------
    def lint_info(self):
        infos = []
        for m in self.members:
            fn = getattr(m, "lint_info", None)
            info = fn() if fn is not None else None
            if info is None:
                return None  # opacity propagates; never guess
            infos.append(info)
        return _compose_lint_infos(infos)

    # -- data path --------------------------------------------------------
    @staticmethod
    def _signature(c: StreamChunk):
        return (
            c.capacity,
            tuple(sorted((k, str(v.dtype)) for k, v in c.columns.items())),
            tuple(sorted(c.nulls)),
        )

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        outs: List[StreamChunk] = []
        sig = self._signature(chunk)
        if self._sig is not None and sig != self._sig:
            # shape change mid-epoch: flush the homogeneous batch (the
            # stacking discipline); any MV passthrough surfaces here
            outs = self._run(flush=False, stage=False)
        self._sig = sig
        self._buf.append(chunk)
        return outs

    # -- control path -----------------------------------------------------
    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        outs: List[StreamChunk] = []
        if self.agg is not None and self.agg._cold_barrier_hook is not None:
            # a merge folds into slots the buffered rows create: where
            # one runs they are stepped first, in a program of their own
            self.agg._cold_barrier_hook(
                lambda: outs.extend(self._run(flush=False, stage=False))
            )
        outs += self._run(flush=True, stage=True)
        if barrier is None:  # direct drive: checks fire inline
            self.finish_barrier()
        return outs

    def on_watermark(self, watermark: Watermark):
        # buffered rows precede the watermark in stream order; the
        # watermark itself walks the members interpreted (state lives
        # in the members between programs, so interop is exact)
        from risingwave_tpu.runtime.pipeline import _walk_watermark

        outs: List[StreamChunk] = []
        if self._buf:
            outs = self._run(flush=False, stage=False)
        wm, o = _walk_watermark(self.members, watermark)
        return wm, outs + o

    def finish_barrier(self) -> None:
        super().finish_barrier()
        for m in self.members:
            m.finish_barrier()  # no-op: members never stage under fusion
        # the fence above awaited the program: retiring its inputs is
        # now a plain free, not a hidden synchronization point
        self._retired = None

    def _on_barrier_scalars(self, vals) -> None:
        # telemetry FIRST: a tripped member latch raises below, and the
        # flight recorder must still see what the barrier did
        base = (4 if self.agg is not None else 0) + (
            2 if self.mv is not None else 0
        )
        if len(vals) >= base + 3:
            self._note_telemetry(vals, vals[base:base + 3])
        # digest tail (after the 3 telemetry scalars): the fused twin
        # of each member's state_digest(), in member order agg -> mv
        digs = {}
        j = base + 3
        if self.agg is not None and j < len(vals):
            digs["agg"] = integrity.digest_from_scalar(vals[j])
            j += 1
        if self.mv is not None and j < len(vals):
            digs["mv"] = integrity.digest_from_scalar(vals[j])
        self.last_digests = digs
        self._note_digests(digs)
        i = 0
        if self.agg is not None:
            self.agg._on_barrier_scalars(tuple(vals[0:4]))
            i = 4
        if self.mv is not None:
            self.mv._on_barrier_scalars(tuple(vals[i:i + 2]))

    def _note_digests(self, digs) -> None:
        """Land the per-barrier device digests in the telemetry dict
        (flight recorder + EpochTrace read it from there). Forensic,
        never load-bearing."""
        try:
            if digs and self._telemetry is not None:
                self._telemetry["state_digests"] = {
                    k: f"{v:016x}" for k, v in digs.items()
                }
        except Exception:  # noqa: BLE001
            pass

    def _note_telemetry(self, vals, tail) -> None:
        """Decode the packed telemetry lane into the deviceprof
        registry (host-side bookkeeping over values the barrier read
        anyway — zero extra device IO; never faults the barrier)."""
        try:
            rows_in, dirty_groups, mv_rows = (int(x) for x in tail)
            member_rows = {}
            occupancy = {}
            seen_agg = False
            for idx, m in enumerate(self.members):
                name = f"{idx}:{type(m).__name__}"
                if m is self.agg:
                    member_rows[name] = rows_in
                    occupancy["agg"] = int(vals[3])
                    seen_agg = True
                elif m is self.mv:
                    member_rows[name] = mv_rows
                    occupancy["mv"] = int(
                        vals[5 if self.agg is not None else 1]
                    )
                else:
                    # pure members see the input rows before the agg
                    # collapses them, the flush-delta rows after
                    member_rows[name] = mv_rows if seen_agg else rows_in
            # padded-lane waste over the members' state tables, from
            # the occupancies that rode the packed read (live lanes)
            # weighted by each member's state bytes — the live/capacity
            # accounting ops/bucketing.padding_stats reads from the
            # device, here for free
            pad_frac = padding_fraction(
                (ex.table.capacity, occupancy[key], ex.state_nbytes())
                for key, ex in (("agg", self.agg), ("mv", self.mv))
                if ex is not None and key in occupancy
            )
            lanes = self._last_lanes
            tel = {
                "rows_in": rows_in,
                "dirty_groups": dirty_groups,
                "mv_rows": mv_rows,
                "member_rows": member_rows,
                "occupancy": occupancy,
                "lanes_total": lanes,
                "lane_fill_frac": (
                    round(rows_in / lanes, 6) if lanes else 0.0
                ),
                "padding_bytes_frac": pad_frac,
            }
            self._telemetry = tel
            from risingwave_tpu.deviceprof import DEVICEPROF

            DEVICEPROF.note_telemetry(self.label, tel)
        except Exception:  # noqa: BLE001 — forensic, never load-bearing
            pass

    def _prove_lift(self, states, stacked, flush_rounds, pads) -> None:
        """Accept the lifted plan only when it is provably
        dtype-equivalent to the baked one over THIS input signature:
        abstract-trace both programs (eval_shape — no XLA) and compare
        every output aval. A weak-typed literal promoting differently
        than its strong int64/float64 parameter slot shows up here as
        a dtype mismatch — fall back to the baked plan for good."""
        lifted, params = self._lift_candidate
        ok = False
        try:
            abstract = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (states, stacked),
            )
            pav = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params
            )
            base = jax.eval_shape(
                lambda s, c: _fused_barrier_fn(
                    s, c, None, self.plan, flush_rounds, pads, True
                ),
                abstract[0],
                abstract[1],
            )
            lift = jax.eval_shape(
                lambda s, c, p: _fused_barrier_fn(
                    s, c, p, lifted, flush_rounds, pads, True
                ),
                abstract[0],
                abstract[1],
                pav,
            )
            ok = jax.tree.structure(base) == jax.tree.structure(
                lift
            ) and all(
                x.shape == y.shape and x.dtype == y.dtype
                for x, y in zip(
                    jax.tree.leaves(base), jax.tree.leaves(lift)
                )
            )
        except Exception:  # noqa: BLE001 — any trace surprise: keep baked
            ok = False
        if ok:
            self._exec_plan, self._params = lifted, params
            self._lift_state = "on"
            _LIFT_STATS["lifted"] += 1
        else:
            self._lift_state = "off"
            _LIFT_STATS["rejected"] += 1

    def _deviceprof_hook(
        self, states, stacked, flush_rounds, pads, has_data
    ) -> None:
        """Compiled-artifact roofline: analyze this (plan, bucket)
        combination ONCE via AOT lower+compile over abstract args —
        FLOPs / bytes-accessed / HBM footprint / compile ms for the
        exact program this barrier dispatches. Gated on the one
        DEVICEPROF.enabled check; never raises."""
        from risingwave_tpu.deviceprof import DEVICEPROF

        if not DEVICEPROF.enabled:
            return
        try:
            shape = (
                "x".join(map(str, stacked.valid.shape[:2]))
                if has_data
                else "-"
            )
            # member table capacities are part of the program's input
            # avals: growth mints a NEW compiled program, so it must
            # mint a new bucket too or the fragment keeps reporting
            # the pre-growth executable's modeled bytes
            caps = ".".join(
                str(ex.table.capacity)
                for ex in (self.agg, self.mv)
                if ex is not None
            )
            bucket = (
                f"fr{flush_rounds}_p{'.'.join(map(str, pads)) or '-'}"
                f"_d{int(has_data)}_n{shape}_c{caps or '-'}"
            )
            abstract = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (states, stacked),
            )
            # the deferred thunk closes over LOCALS only (abstract
            # shapes + the plan AS DISPATCHED): capturing self would
            # pin the whole executor (and its retired device buffers)
            # in the pending queue, and a post-rebuild plan mutation
            # would lower a program that no longer matches this bucket
            plan = self._exec_plan
            pav = (
                jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    self._params,
                )
                if self._params is not None
                else None
            )
            DEVICEPROF.ensure_program(
                f"fused:{self.label}",
                bucket,
                lambda: _fused_barrier_step.lower(
                    abstract[0],
                    abstract[1],
                    pav,
                    plan,
                    flush_rounds,
                    pads,
                    has_data,
                ),
                fragment=self.label,
            )
        except Exception:  # noqa: BLE001 — observability never faults
            pass

    # -- the program ------------------------------------------------------
    def _run(self, flush: bool, stage: bool) -> List[StreamChunk]:
        buf, self._buf, self._sig = self._buf, [], None
        has_data = bool(buf)
        stacked = None
        if has_data:
            n = len(buf)
            target = 1 << (n - 1).bit_length() if n > 1 else 1
            if target > n:
                c0 = buf[0]
                empty = StreamChunk(
                    c0.columns, jnp.zeros_like(c0.valid), c0.nulls, c0.ops
                )
                buf = buf + [empty] * (target - n)
            stacked = stack_chunks(buf)
            probe = jax.eval_shape(
                self.plan.pre if self.plan.pre is not None else (lambda c: c),
                jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                    stacked,
                ),
            )
            incoming = len(buf) * probe.valid.shape[0]
            # host bookkeeping BEFORE the program: growth may rebuild
            # member state, and the program must see the final buffers
            if self.agg is not None:
                if self.agg._cold_stacked_hook is not None:
                    self.agg._cold_stacked_hook()
                self.agg._maybe_grow(incoming)
                self.agg._insert_bound += incoming
                self.agg._dirty_bound += incoming
            elif self.mv is not None:
                self.mv._maybe_grow(incoming)
        # the round count must be derived AFTER the buffered epoch's
        # incoming landed in the dirty bound — deriving it earlier
        # under-flushes any epoch touching more distinct groups than
        # one round drains (silent MV divergence; code-review finding).
        # Rounds and pads come from the PLAN's out_cap (the value the
        # compiled flush actually drains per round), never the agg's
        # live attribute: a post-fuse out_cap mutation must not
        # desynchronize the slice from the program.
        flush_rounds = 0
        pads: Tuple[int, ...] = ()
        if flush and self.agg is not None:
            out_cap = self.plan.agg.out_cap
            bound = min(self.agg._dirty_bound, self.agg.table.capacity)
            flush_rounds = max(1, -(-bound // out_cap))
            # the fused pads: {small, full} (lattice.flush_pad's
            # rule), from the host dirty bound. The interpreted
            # _flush_all used to share this pair; since PR 30 it cuts
            # to lattice.flush_lattice from the exact count it
            # reads. This program knows only the bound, too loose to
            # pick a small size, and bakes every round's pad into one
            # executable, so it keeps the pair: a fragment is either
            # fused or interpreted, the two compile sets never meet
            full = 2 * out_cap
            small = min(256, full)
            pads = tuple(
                (
                    small
                    if 2 * min(
                        max(bound - r * out_cap, 0), out_cap
                    ) <= small
                    else full
                )
                for r in range(flush_rounds)
            )
            if self.mv is not None:
                for p in pads:
                    self.mv._maybe_grow(p)
        if not has_data and not flush_rounds and (
            not stage or (self.agg is None and self.mv is None)
        ):
            return []  # nothing to run, nothing to stage
        states = (self._agg_state(), self._mv_state())
        if stage:
            self._last_lanes = (
                int(stacked.valid.shape[0] * stacked.valid.shape[1])
                if has_data
                else 0
            )
        if self._lift_state == "pending" and has_data:
            self._prove_lift(states, stacked, flush_rounds, pads)
        self._deviceprof_hook(states, stacked, flush_rounds, pads, has_data)
        # attribution contexts: dispatch counting (PROFILER.attribute)
        # and the program's span — under a profiler session the
        # annotation "rw/fused:<label>", so the device trace carries the
        # fragment label next to the program's fused/<stage> named scopes
        attr = nullcontext()
        if PROFILER.enabled:
            attr = PROFILER.attribute(f"fused:{self.label}")
        with attr, span(f"fused:{self.label}"):
            (agg_st, mv_st), outs, packed = _fused_barrier_step(
                states,
                stacked,
                self._params,
                self._exec_plan,
                flush_rounds,
                pads,
                has_data,
            )
        if self.agg is not None:
            (
                self.agg.table,
                self.agg.state,
                self.agg.dropped,
                self.agg.minput,
                self.agg.mi_bad,
            ) = agg_st
            if flush_rounds:
                self.agg._dirty_bound = 0
        if self.mv is not None:
            self.mv.table, self.mv.state = mv_st
        if stage and packed is not None:
            try:
                packed.copy_to_host_async()
            except AttributeError:  # backend without async copies
                pass
            self._staged_scalars = packed
        # keep the program's input refs alive past this frame: their
        # deallocation would synchronize on the still-running program
        self._retired = (buf, stacked, states)
        return list(outs)

    def _agg_state(self):
        if self.agg is None:
            return ()
        return (
            self.agg.table,
            self.agg.state,
            self.agg.dropped,
            self.agg.minput,
            self.agg.mi_bad,
        )

    def _mv_state(self):
        if self.mv is None:
            return ()
        return (self.mv.table, self.mv.state)


# ---------------------------------------------------------------------------
# the two-input fused program (q7/q8: side chains + join + MV, one
# donated device program per barrier)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class SidePlan:
    """One input side's statics: a pure prefix (ComposedSteps) feeding
    at most one stateful member — the two-input shapes' side chains:
    q7 ``hop -> DynamicMaxFilter`` (left) / ``hop -> HashAgg`` (right),
    q8 ``hop -> dedup`` (both)."""

    pre: Optional[ComposedSteps]
    kind: Optional[str]  # None | "filter" | "dedup" | "agg"
    keys: tuple = ()  # filter: (group_col, value_col); dedup: key names
    agg: Optional[AggStatics] = None


@dataclass(frozen=True)
class TwoInputPlan:
    """The fused two-input program's static shape (jit cache key):
    two side plans around one hash join, then a pure/mv/pure tail.
    Value-hashable (ComposedSteps contract), so rebuilds and recovery
    re-fuse into the SAME compiled program."""

    left: SidePlan
    right: SidePlan
    j_left_keys: tuple
    j_right_keys: tuple
    j_left_names: tuple
    j_right_names: tuple
    j_out_names: tuple
    j_out_cap: int
    j_type: str
    tail_pre: Optional[ComposedSteps]
    mv_pk: Optional[tuple]
    mv_cols: Optional[tuple]
    tail_post: Optional[ComposedSteps]

    def __hash__(self):
        # hashed as a STATIC jit argument on every barrier dispatch:
        # cache it (frozen dataclasses re-derive the field-tuple hash
        # per call; equality stays field-based for program sharing)
        h = self.__dict__.get("_hash")
        if h is None:
            h = hash(tuple(getattr(self, f.name) for f in _dc_fields(self)))
            object.__setattr__(self, "_hash", h)
        return h


def _two_input_side_scan(st, jl, jr, seg, side_plan, plan, arrival):
    """lax.scan one side's homogeneous stacked batch through the side's
    stateful step (if any) and the join arrival step, chunk by chunk in
    arrival order — the DynamicMaxFilter's pass-iff->=pre-chunk-max
    decision and the join's per-chunk ``out_cap`` emission compaction
    are both order-dependent, so the scan preserves the interpreted
    walk's exact semantics (bit-identity, not just epoch-equivalence).
    Returns ``(st, jl, jr, flat_emission, (saw_delete, dropped),
    em_overflow)`` with the per-chunk emissions flattened in order."""
    own_keys = plan.j_left_keys if arrival == "l" else plan.j_right_keys
    other_keys = plan.j_right_keys if arrival == "l" else plan.j_left_keys
    own_names = plan.j_left_names if arrival == "l" else plan.j_right_names
    other_names = plan.j_right_names if arrival == "l" else plan.j_left_names
    jown, jother = (jl, jr) if arrival == "l" else (jr, jl)
    F = jnp.zeros((), jnp.bool_)

    def body(carry, chunk):
        st, jown, jother, sd, dp, ovf = carry
        if side_plan.pre is not None:
            chunk = side_plan.pre(chunk)
        if side_plan.kind == "filter":
            table, maxes, sdirty = st
            table, maxes, sdirty, chunk, d1, d2 = filter_step_fn(
                table,
                maxes,
                sdirty,
                chunk,
                side_plan.keys[0],
                side_plan.keys[1],
            )
            st = (table, maxes, sdirty)
            sd, dp = sd | d1, dp | d2
        elif side_plan.kind == "dedup":
            table, sdirty = st
            table, sdirty, chunk, d1, d2 = dedup_step_fn(
                table, sdirty, chunk, side_plan.keys
            )
            st = (table, sdirty)
            sd, dp = sd | d1, dp | d2
        jown, jother, cols, nulls, ops, valid, o = join_step_fn(
            jown,
            jother,
            chunk,
            own_keys,
            other_keys,
            own_names,
            other_names,
            plan.j_out_cap,
            plan.j_type,
            arrival,
            plan.j_out_names,
        )
        em = StreamChunk(columns=cols, valid=valid, nulls=nulls, ops=ops)
        return (st, jown, jother, sd, dp, ovf | o), em

    # segments arrive as pow2-padded chunk TUPLES and stack INSIDE the
    # traced program: host-eager jnp.stack cost ~9ms/barrier of pure
    # dispatch overhead on the q7 smoke tier — in-trace it fuses into
    # the compiled program for free
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *seg)
    (st, jown, jother, sd, dp, ovf), ems = jax.lax.scan(
        body, (st, jown, jother, F, F, F), stacked
    )
    jl, jr = (jown, jother) if arrival == "l" else (jother, jown)
    flat = jax.tree.map(lambda x: x.reshape((-1,) + x.shape[2:]), ems)
    return st, jl, jr, flat, (sd, dp), ovf


def _fused_two_input_fn(
    states, left_batches, right_batches, params, plan, flush_rounds, pads
):
    """The whole two-input fragment-barrier as one pure function over
    ``states = (left_state, right_state, (join_left, join_right),
    mv_state, latches)``:

    apply phase — the epoch's buffered LEFT batches scan through the
                  left side's step + the join's left-arrival kernel
                  (probe right, fold into left), each batch's per-chunk
                  ``out_cap`` emissions walking tail -> device MV; then
                  the RIGHT batches likewise (or, agg sides, into the
                  agg's flatten+reduce epoch path);
    flush phase — ``flush_rounds`` device flushes of the agg's dirty
                  groups, each delta PADDED TO A LATTICE BUCKET with a
                  validity mask (array/lattice.flush_pad — the
                  "padded flush made the join 80x slower" objection
                  predates masked lanes: the join's probe/build kernels
                  treat masked rows as provably inert, so the pad costs
                  one masked device op instead of an interpreted
                  consumer's compute), probing the join as a
                  right-arrival and walking tail -> MV;
    scalars     — every member's latches + occupancy/survivor counters
                  PLUS the device-computed telemetry lane (left/right
                  rows, join emissions, dirty groups, MV rows) packed
                  into ONE int64 lane for the (possibly K-deferred)
                  overlapped finish read.

    Interpreted-twin equivalence: mid-epoch, left applies touch only
    {left step state, join.left, MV} and right applies only {right
    step state, join.right-or-agg} — disjoint — and the join's
    barrier-time flush deltas probe a left side that already absorbed
    the whole epoch either way, so batching sides in (left, right,
    flush) order reproduces the interpreted walk's emissions exactly
    for the per-barrier MV.
    """
    with param_scope(params):
        return _fused_two_input_body(
            states, left_batches, right_batches, plan, flush_rounds, pads
        )


def _fused_two_input_body(
    states, left_batches, right_batches, plan, flush_rounds, pads
):
    l_st, r_st, (jl, jr), mv_st, latches = states
    l_saw, l_drop, r_saw, r_drop, em_latch = latches
    Z = jnp.zeros((), jnp.int64)
    rows_l = rows_r = join_rows = mv_rows = Z
    em_ovf = em_latch
    outs: List[StreamChunk] = []

    def through_tail(chunk):
        nonlocal mv_st, mv_rows, join_rows
        join_rows = join_rows + jnp.sum(chunk.valid.astype(jnp.int64))
        if plan.tail_pre is not None:
            chunk = plan.tail_pre(chunk)
        if plan.mv_pk is not None:
            with jax.named_scope("fused/mv_write"):
                mv_rows = mv_rows + jnp.sum(chunk.valid.astype(jnp.int64))
                mtable, mstate = mv_st
                mtable, mstate = mv_step_fn(
                    mtable, mstate, chunk, plan.mv_pk, plan.mv_cols
                )
                mv_st = (mtable, mstate)
        if plan.tail_post is not None:
            chunk = plan.tail_post(chunk)
        return chunk

    with jax.named_scope("fused/apply"):
        for seg in left_batches:
            for c in seg:
                rows_l = rows_l + jnp.sum(c.valid.astype(jnp.int64))
            l_st, jl, jr, flat, fl, ovf = _two_input_side_scan(
                l_st, jl, jr, seg, plan.left, plan, "l"
            )
            l_saw, l_drop = l_saw | fl[0], l_drop | fl[1]
            em_ovf = em_ovf | ovf
            outs.append(through_tail(flat))
        for seg in right_batches:
            for c in seg:
                rows_r = rows_r + jnp.sum(c.valid.astype(jnp.int64))
            if plan.right.kind == "agg":
                stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *seg)
                a = plan.right.agg
                table, ast, dropped, minput, mi_bad = r_st
                if a.has_minput:
                    table, ast, dropped, minput, mi_bad = _epoch_reduced_fn(
                        table, ast, dropped, stacked, a.calls,
                        a.group_keys, a.nullable, plan.right.pre,
                        minput, mi_bad,
                    )
                else:
                    table, ast, dropped = _epoch_reduced_fn(
                        table, ast, dropped, stacked, a.calls,
                        a.group_keys, a.nullable, plan.right.pre,
                    )
                r_st = (table, ast, dropped, minput, mi_bad)
            else:
                r_st, jl, jr, flat, fr, ovf = _two_input_side_scan(
                    r_st, jl, jr, seg, plan.right, plan, "r"
                )
                r_saw, r_drop = r_saw | fr[0], r_drop | fr[1]
                em_ovf = em_ovf | ovf
                outs.append(through_tail(flat))

    # dirty groups pending at the barrier, sampled AFTER the epoch's
    # applies and BEFORE the flush drains them (telemetry twin)
    dirty_groups = Z
    if plan.right.kind == "agg":
        dirty_groups = jnp.sum(r_st[1].dirty.astype(jnp.int64))

    if flush_rounds and plan.right.kind == "agg":
        a = plan.right.agg
        table, ast, dropped, minput, mi_bad = r_st
        with jax.named_scope("fused/flush"):
            for r in range(flush_rounds):
                ast, delta = agg_ops.flush(
                    ast, table.keys, a.out_cap, a.float_extremes
                )
                chunk = delta_to_chunk(
                    delta, a.group_keys, a.nullable, a.calls, pads[r]
                )
                jr, jl, cols, nulls, ops, valid, o = join_step_fn(
                    jr,
                    jl,
                    chunk,
                    plan.j_right_keys,
                    plan.j_left_keys,
                    plan.j_right_names,
                    plan.j_left_names,
                    plan.j_out_cap,
                    plan.j_type,
                    "r",
                    plan.j_out_names,
                )
                em_ovf = em_ovf | o
                outs.append(
                    through_tail(
                        StreamChunk(
                            columns=cols, valid=valid, nulls=nulls, ops=ops
                        )
                    )
                )
        r_st = (table, ast, dropped, minput, mi_bad)

    with jax.named_scope("fused/scalar_pack"):
        scal = []

        def side_scal(st, kind, saw, drop):
            if kind in ("filter", "dedup"):
                table = st[0]
                sdirty = st[2] if kind == "filter" else st[1]
                scal.extend(
                    [
                        saw,
                        drop,
                        table.occupancy(),
                        jnp.sum((table.live | sdirty).astype(jnp.int32)),
                    ]
                )
            elif kind == "agg":
                table, ast, dropped, _minput, mi_bad = st
                scal.extend(
                    [dropped, ast.minmax_retracted, mi_bad,
                     table.occupancy()]
                )

        side_scal(l_st, plan.left.kind, l_saw, l_drop)
        side_scal(r_st, plan.right.kind, r_saw, r_drop)
        scal += [
            em_ovf,
            jl.overflow,
            jl.inconsistent,
            jr.overflow,
            jr.inconsistent,
            jl.table.occupancy(),
            jr.table.occupancy(),
            jnp.sum((jl.table.live | jl.sdirty).astype(jnp.int32)),
            jnp.sum((jr.table.live | jr.sdirty).astype(jnp.int32)),
        ]
        if plan.mv_pk is not None:
            mtable, mstate = mv_st
            scal += [mstate.dropped, mtable.occupancy()]
        # telemetry tail rides the same staged read the barrier pays
        # anyway: zero extra lanes dispatched, zero new host syncs
        scal += [rows_l, rows_r, join_rows, dirty_groups, mv_rows]
        # state digests ride the SAME lane (integrity layer): fused
        # twins of the members' state_digest(), decoded per the
        # _scalar_layout "dig" tail — zero extra dispatches
        with jax.named_scope("fused/digest"):
            def side_digest(st, kind):
                if kind == "filter":
                    scal.append(
                        integrity.device_digest(
                            *integrity.filter_lanes(st[0], st[1])
                        )
                    )
                elif kind == "dedup":
                    scal.append(
                        integrity.device_digest(
                            *integrity.dedup_lanes(st[0])
                        )
                    )
                elif kind == "agg":
                    scal.append(
                        integrity.device_digest(
                            *integrity.agg_lanes(st[0], st[1])
                        )
                    )

            side_digest(l_st, plan.left.kind)
            side_digest(r_st, plan.right.kind)
            scal.append(
                integrity.device_digest(
                    *integrity.join_side_lanes(jl, jnp.where)
                )
            )
            scal.append(
                integrity.device_digest(
                    *integrity.join_side_lanes(jr, jnp.where)
                )
            )
            if plan.mv_pk is not None:
                mtable, mstate = mv_st
                scal.append(
                    integrity.device_digest(
                        *integrity.mv_lanes(mtable, mstate)
                    )
                )
        packed = jnp.stack(
            [jnp.asarray(x).astype(jnp.int64) for x in scal]
        )
    latches_out = (l_saw, l_drop, r_saw, r_drop, em_ovf)
    return (l_st, r_st, (jl, jr), mv_st, latches_out), tuple(outs), packed


_fused_two_input_step = partial(
    jax.jit,
    static_argnames=("plan", "flush_rounds", "pads"),
    donate_argnums=(0,),
)(_fused_two_input_fn)


_ZERO_VALID_CACHE: dict = {}


def _zero_valid(shape) -> jnp.ndarray:
    """A cached all-False valid lane for pad chunks: padding is a
    steady-state per-barrier operation and the zero lane is immutable
    and never donated — minting a fresh device buffer per barrier was
    measurable eager-dispatch cost."""
    arr = _ZERO_VALID_CACHE.get(shape)
    if arr is None:
        arr = jnp.zeros(shape, jnp.bool_)
        _ZERO_VALID_CACHE[shape] = arr
    return arr


def _pad_segment(seg: List[StreamChunk]) -> Tuple[StreamChunk, ...]:
    """Pow2-pad a homogeneous chunk list (the epoch-batch compile
    discipline: at most log2(max chunks/epoch) distinct batch shapes
    per chunk signature). The chunks stay a TUPLE — the fused program
    stacks them in-trace, where the stack fuses into the compiled
    program instead of costing host-eager dispatches."""
    n = len(seg)
    target = 1 << (n - 1).bit_length() if n > 1 else 1
    if target > n:
        c0 = seg[0]
        empty = StreamChunk(
            c0.columns, _zero_valid(c0.valid.shape), c0.nulls, c0.ops
        )
        seg = seg + [empty] * (target - n)
    return tuple(seg)


class FusedTwoInputExecutor(Executor):
    """A whole two-input pipeline — ``pure* [filter|dedup|agg]`` per
    side, HashJoin, ``pure* [DeviceMV] pure*`` tail — executed as ONE
    donated device program per barrier (q7/q8's shape; the TiLT
    endgame: compile the query, not the operators).

    Driver contract (TwoInputPipeline routes here when armed):
    ``buffer_left``/``buffer_right`` stage raw source chunks,
    ``on_barrier`` dispatches the barrier program and returns the
    fragment's emission, ``finish_barrier`` materializes the packed
    member scalars and fires every member's latch checks at their
    original raise points — deferred to every K-th barrier under
    ``RW_FUSED_PIPELINE_DEPTH=K`` (K barriers' programs queue on the
    device back-to-back with zero host syncs between them).

    The member executor OBJECTS stay the system of record: state is
    written back after every program (as async futures of the in-flight
    dispatch), so checkpoint/restore, recovery, the shape governor and
    the cold tier keep talking to the originals, and the interpreted
    watermark walk interoperates exactly.
    """

    def __init__(
        self,
        members: Sequence[Executor],
        plan: TwoInputPlan,
        l_stateful: Optional[Executor],
        r_stateful: Optional[Executor],
        join: HashJoinExecutor,
        mv: Optional[DeviceMaterializeExecutor],
        label: str = "fragment",
        depth: Optional[int] = None,
        n_left: Optional[int] = None,
    ):
        self.members = list(members)
        self.plan = plan
        # index boundary between the left and right chains inside
        # ``members`` (telemetry row attribution)
        self._n_left = n_left if n_left is not None else len(members)
        self.l_stateful = l_stateful
        self.r_stateful = r_stateful
        self.agg = r_stateful if type(r_stateful) is HashAggExecutor else None
        self.join = join
        self.mv = mv
        self.label = label
        self.covers_whole_chain = True
        self.depth = pipeline_depth(depth)
        self._segs = {"l": [], "r": []}  # homogeneous chunk segments
        self._sig = {"l": None, "r": None}
        self._probe_caps = {}  # (side, chunk sig) -> post-pre capacity
        self._pending: List = []  # staged packed scalars (K-deferred)
        self._retired: List = []  # program inputs held to the K-fence
        self._barriers = 0
        self._last_lanes = 0
        self._telemetry: Optional[dict] = None
        self.last_digests: dict = {}

    # -- data path --------------------------------------------------------
    def buffer_left(self, chunk: StreamChunk) -> List[StreamChunk]:
        return self._buffer("l", chunk)

    def buffer_right(self, chunk: StreamChunk) -> List[StreamChunk]:
        return self._buffer("r", chunk)

    def _buffer(self, side: str, chunk: StreamChunk) -> List[StreamChunk]:
        sig = FusedChainExecutor._signature(chunk)
        segs = self._segs[side]
        if not segs or self._sig[side] != sig:
            segs.append([])
            self._sig[side] = sig
        segs[-1].append(chunk)
        return []

    def flush_data(self) -> List[StreamChunk]:
        """Apply everything buffered WITHOUT the agg flush (the
        pre-watermark data barrier: buffered rows precede the watermark
        in stream order, and the watermark walk then runs over member
        state interpreted)."""
        if not self._segs["l"] and not self._segs["r"]:
            return []
        return self._run(flush=False, stage=False)

    # -- control path -----------------------------------------------------
    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        outs: List[StreamChunk] = []
        if self.agg is not None and self.agg._cold_barrier_hook is not None:
            # as FusedChainExecutor.on_barrier
            self.agg._cold_barrier_hook(
                lambda: outs.extend(self.flush_data())
            )
        outs += self._run(flush=True, stage=True)
        self._barriers += 1
        if barrier is None:  # direct drive: checks fire inline
            self.finish_barrier(force=True)
        return outs

    def on_watermark(self, watermark: Watermark):
        # handled at the pipeline level (flush_data + interpreted
        # member walk); kept for Executor-protocol completeness
        outs = self.flush_data()
        return watermark, outs

    def finish_barrier(self, force: bool = False) -> None:
        """Materialize every pending barrier's packed scalars and run
        the member latch checks — at the K-boundary (or forced: direct
        drive, checkpoint staging, close). Between boundaries the host
        never blocks on the device: barrier N+1's program is enqueued
        while N still runs."""
        if not self._pending:
            return
        if not force and (self._barriers % self.depth) != 0:
            return
        from risingwave_tpu.ops.hash_table import finish_scalars

        pending, self._pending = self._pending, []
        retired, self._retired = self._retired, []
        try:
            for i, packed in enumerate(pending):
                with span(
                    "executor.device_step", executor=type(self).__name__
                ):
                    vals = finish_scalars(packed)
                # member scalars decode from the LAST pack only: the
                # latch lanes are monotonic and CARRIED through the
                # chained programs (each barrier's latches_in are the
                # previous write-back), so the final pack subsumes
                # every earlier one — and one K-window must feed the
                # bucket allocators ONE hysteresis observation, not K
                # at once (K stale notes burned the lazy-shrink
                # patience in a single boundary and flapped capacities
                # across the window — the exact oscillation PR 9's
                # hysteresis exists to prevent). Earlier packs still
                # decode their telemetry lanes (per-barrier forensics).
                self._on_barrier_scalars(
                    vals, members=(i == len(pending) - 1)
                )
        finally:
            for m in self.members:
                m.finish_barrier()  # no-op: members never stage here
            del retired  # the fence above ran: retiring is a plain free

    def lint_info(self):
        return None  # the pipeline's chains stay the lint surface

    # -- scalar decode ----------------------------------------------------
    def _scalar_layout(self):
        layout = []
        if self.l_stateful is not None:
            layout.append(("l", 4))
        if self.r_stateful is not None:
            layout.append(("r", 4))
        layout.append(("join", 9))
        if self.mv is not None:
            layout.append(("mv", 2))
        layout.append(("tel", 5))
        # digest tail mirrors the pack's fused/digest scope exactly:
        # one per stateful side, both join sides, one for the MV
        n_dig = 2
        if self.l_stateful is not None:
            n_dig += 1
        if self.r_stateful is not None:
            n_dig += 1
        if self.mv is not None:
            n_dig += 1
        layout.append(("dig", n_dig))
        return layout

    def _on_barrier_scalars(self, vals, members: bool = True) -> None:
        i = 0
        slices = {}
        for name, width in self._scalar_layout():
            slices[name] = tuple(vals[i : i + width])
            i += width
        # telemetry FIRST: a tripped member latch raises below, and the
        # flight recorder must still see what the barrier did
        self._note_telemetry(slices)
        self._note_digests(slices.get("dig", ()))
        if not members:
            return
        if self.l_stateful is not None:
            self.l_stateful._on_barrier_scalars(slices["l"])
        if self.r_stateful is not None:
            self.r_stateful._on_barrier_scalars(slices["r"])
        self.join._on_barrier_scalars(slices["join"])
        if self.mv is not None:
            self.mv._on_barrier_scalars(slices["mv"])

    def _note_digests(self, dig) -> None:
        """Decode the fused digest tail (integrity layer twins of the
        members' state_digest()) — forensic, never load-bearing."""
        try:
            names = []
            if self.l_stateful is not None:
                names.append("left")
            if self.r_stateful is not None:
                names.append("right")
            names += ["join_left", "join_right"]
            if self.mv is not None:
                names.append("mv")
            digs = {
                n: integrity.digest_from_scalar(v)
                for n, v in zip(names, dig)
            }
            if digs:
                self.last_digests = digs
                if self._telemetry is not None:
                    self._telemetry["state_digests"] = {
                        k: f"{v:016x}" for k, v in digs.items()
                    }
        except Exception:  # noqa: BLE001 — forensic, never load-bearing
            pass

    def _note_telemetry(self, slices) -> None:
        """Decode the packed telemetry lane into the deviceprof
        registry (host bookkeeping over values the barrier read anyway
        — zero extra device IO; never faults the barrier)."""
        try:
            rows_l, rows_r, join_rows, dirty_groups, mv_rows = (
                int(x) for x in slices["tel"]
            )
            member_rows = {}
            occupancy = {}
            for idx, m in enumerate(self.members):
                name = f"{idx}:{type(m).__name__}"
                if m is self.join:
                    member_rows[name] = join_rows
                elif m is self.mv or idx > self.members.index(self.join):
                    member_rows[name] = mv_rows
                elif idx >= self._n_left:
                    member_rows[name] = rows_r
                else:
                    member_rows[name] = rows_l
            occupancy["join_left"] = int(slices["join"][5])
            occupancy["join_right"] = int(slices["join"][6])

            def side_occ(ex, lanes):
                # agg lanes: [dropped, mret, mi_bad, occupancy];
                # filter/dedup: [saw, drop, occupancy, survivors]
                return int(
                    lanes[3] if type(ex) is HashAggExecutor else lanes[2]
                )

            if self.l_stateful is not None:
                occupancy["left"] = side_occ(self.l_stateful, slices["l"])
            if self.r_stateful is not None:
                occupancy["right"] = side_occ(self.r_stateful, slices["r"])
            if self.mv is not None:
                occupancy["mv"] = int(slices["mv"][1])
            def nbytes(ex):
                return sum(
                    leaf.nbytes
                    for leaf in jax.tree.leaves(
                        getattr(ex, "table", None)
                        if type(ex).__name__ not in ("HashJoinExecutor",)
                        else (ex.left, ex.right)
                    )
                    if hasattr(leaf, "nbytes")
                )

            entries = [
                (
                    self.join.left.capacity,
                    occupancy["join_left"],
                    sum(
                        leaf.nbytes
                        for leaf in jax.tree.leaves(self.join.left)
                    ),
                ),
                (
                    self.join.right.capacity,
                    occupancy["join_right"],
                    sum(
                        leaf.nbytes
                        for leaf in jax.tree.leaves(self.join.right)
                    ),
                ),
            ]
            for key, ex in (
                ("left", self.l_stateful),
                ("right", self.r_stateful),
            ):
                if ex is not None and key in occupancy:
                    entries.append(
                        (
                            ex.table.capacity,
                            occupancy[key],
                            nbytes(ex),
                        )
                    )
            if self.mv is not None and "mv" in occupancy:
                entries.append(
                    (
                        self.mv.table.capacity,
                        occupancy["mv"],
                        self.mv.state_nbytes(),
                    )
                )
            pad_frac = padding_fraction(entries)
            lanes = self._last_lanes
            rows_in = rows_l + rows_r
            tel = {
                "rows_in": rows_in,
                "rows_left": rows_l,
                "rows_right": rows_r,
                "join_rows": join_rows,
                "dirty_groups": dirty_groups,
                "mv_rows": mv_rows,
                "member_rows": member_rows,
                "occupancy": occupancy,
                "lanes_total": lanes,
                "lane_fill_frac": (
                    round(rows_in / lanes, 6) if lanes else 0.0
                ),
                "padding_bytes_frac": pad_frac,
            }
            self._telemetry = tel
            from risingwave_tpu.deviceprof import DEVICEPROF

            DEVICEPROF.note_telemetry(self.label, tel)
        except Exception:  # noqa: BLE001 — forensic, never load-bearing
            pass

    # -- member state plumbing --------------------------------------------
    def _side_state(self, ex):
        if ex is None:
            return ()
        if type(ex) is DynamicMaxFilterExecutor:
            return (ex.table, ex.maxes, ex.sdirty)
        if type(ex) is AppendOnlyDedupExecutor:
            return (ex.table, ex.sdirty)
        return (ex.table, ex.state, ex.dropped, ex.minput, ex.mi_bad)

    def _write_side_state(self, ex, st) -> None:
        if ex is None:
            return
        if type(ex) is DynamicMaxFilterExecutor:
            ex.table, ex.maxes, ex.sdirty = st
        elif type(ex) is AppendOnlyDedupExecutor:
            ex.table, ex.sdirty = st
        else:
            ex.table, ex.state, ex.dropped, ex.minput, ex.mi_bad = st

    def _latches(self):
        def pair(ex):
            if ex is None or type(ex) is HashAggExecutor:
                # fresh zero buffers per slot: the states pytree is
                # DONATED whole, and donating one buffer twice is an
                # XLA error
                return (
                    jnp.zeros((), jnp.bool_),
                    jnp.zeros((), jnp.bool_),
                )
            return (ex._saw_delete, ex._dropped)

        return pair(self.l_stateful) + pair(self.r_stateful) + (
            self.join._em_overflow,
        )

    def _write_latches(self, latches) -> None:
        l_saw, l_drop, r_saw, r_drop, em = latches
        for ex, saw, drop in (
            (self.l_stateful, l_saw, l_drop),
            (self.r_stateful, r_saw, r_drop),
        ):
            if ex is not None and type(ex) is not HashAggExecutor:
                ex._saw_delete, ex._dropped = saw, drop
        self.join._em_overflow = em

    # -- the program ------------------------------------------------------
    def _prepare_side(self, side: str, side_plan: SidePlan):
        """Stack the side's buffered segments and run the members' host
        growth bookkeeping (rebuilds must land BEFORE states are read).
        Returns (batches, post_pre_rows)."""
        segs, self._segs[side] = self._segs[side], []
        self._sig[side] = None
        batches = []
        rows = 0
        ex = self.l_stateful if side == "l" else self.r_stateful
        for seg in segs:
            if not seg:
                continue
            padded = _pad_segment(seg)
            key = (side, FusedChainExecutor._signature(seg[0]))
            cap = self._probe_caps.get(key)
            if cap is None:
                # the post-pre row capacity (hop expansion factor),
                # memoized per chunk signature: re-tracing the pure
                # prefix abstractly EVERY barrier was measurable host
                # dispatch cost
                probe = jax.eval_shape(
                    side_plan.pre
                    if side_plan.pre is not None
                    else (lambda c: c),
                    jax.tree.map(
                        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        seg[0],
                    ),
                )
                cap = probe.valid.shape[0]
                self._probe_caps[key] = cap
            rows += len(padded) * cap
            batches.append(padded)
        if ex is not None and rows:
            if type(ex) is HashAggExecutor:
                if ex._cold_stacked_hook is not None:
                    ex._cold_stacked_hook()
                ex._maybe_grow(rows)
                ex._insert_bound += rows
                ex._dirty_bound += rows
            else:
                ex._grow_hint(rows)
                ex._bound += rows
        return tuple(batches), rows

    def _run(self, flush: bool, stage: bool) -> List[StreamChunk]:
        if self.join._cold_apply_hook is not None:
            # armed cold tier: the program probes the join sides
            # directly, so every evicted bucket must be RESIDENT before
            # dispatch or matches are silently lost — restore them all
            # up front (conservative, the agg _cold_stacked_hook
            # discipline; code-review finding)
            for name in ("left", "right"):
                ev = self.join._evicted[name]
                if ev:
                    self.join._restore_cold_keys(name, sorted(ev))
        left_batches, l_rows = self._prepare_side("l", self.plan.left)
        right_batches, r_rows = self._prepare_side("r", self.plan.right)
        has_data = bool(left_batches or right_batches)

        flush_rounds = 0
        pads: Tuple[int, ...] = ()
        if flush and self.agg is not None:
            # rounds/pads from the PLAN's out_cap (the value the
            # compiled flush drains per round) AFTER the buffered epoch
            # landed in the dirty bound — the single-input lessons
            pads = flush_pad_schedule(
                self.agg._dirty_bound,
                self.agg.table.capacity,
                self.plan.right.agg.out_cap,
            )
            flush_rounds = len(pads)
        if not has_data and not flush_rounds and not stage:
            return []

        # join-side insert bounds: left arrivals fold into the left
        # side; right arrivals (scanned side or flush deltas) into the
        # right
        join = self.join
        if l_rows:
            join.left = join._grow_hint("l", join.left, l_rows)
            join._bound["l"] += l_rows
        r_join_rows = (
            sum(pads) if self.agg is not None else r_rows
        )
        if r_join_rows:
            join.right = join._grow_hint("r", join.right, r_join_rows)
            join._bound["r"] += r_join_rows
        if self.mv is not None:
            # every emission chunk reaching the MV has j_out_cap lanes
            # — INCLUDING flush rounds (a small-pad delta can still
            # match up to out_cap join rows), so the flush contribution
            # is rounds * out_cap, not the delta pad sum: the MV's
            # insert bound must stay a true upper bound or its
            # MAX_PROBE pre-grow guard goes blind (code-review finding)
            em_rows = (
                sum(len(seg) for seg in left_batches)
                + (
                    0
                    if self.agg is not None
                    else sum(len(seg) for seg in right_batches)
                )
                + flush_rounds
            ) * self.plan.j_out_cap
            if em_rows:
                self.mv._maybe_grow(em_rows)

        states = (
            self._side_state(self.l_stateful),
            self._side_state(self.r_stateful),
            (join.left, join.right),
            (self.mv.table, self.mv.state) if self.mv is not None else (),
            self._latches(),
        )
        if stage:
            self._last_lanes = sum(
                len(seg) * int(seg[0].valid.shape[0])
                for seg in left_batches + right_batches
            )
        self._deviceprof_hook(
            states, left_batches, right_batches, flush_rounds, pads
        )
        attr = nullcontext()
        if PROFILER.enabled:
            attr = PROFILER.attribute(f"fused:{self.label}")
        with attr, span(f"fused:{self.label}"):
            (l_st, r_st, (jl, jr), mv_st, latches), outs, packed = (
                _fused_two_input_step(
                    states,
                    left_batches,
                    right_batches,
                    None,
                    self.plan,
                    flush_rounds,
                    pads,
                )
            )
        self._write_side_state(self.l_stateful, l_st)
        self._write_side_state(self.r_stateful, r_st)
        join.left, join.right = jl, jr
        if self.mv is not None:
            self.mv.table, self.mv.state = mv_st
        self._write_latches(latches)
        if self.agg is not None and flush_rounds:
            self.agg._dirty_bound = 0
        if stage:
            try:
                packed.copy_to_host_async()
            except AttributeError:  # backend without async copies
                pass
            self._pending.append(packed)
        # keep the program's input refs alive past this frame: their
        # deallocation would synchronize on the still-running program
        # (held to the K-boundary fence under pipelining)
        self._retired.append((left_batches, right_batches, states, outs))
        return list(outs)

    def _deviceprof_hook(
        self, states, left_batches, right_batches, flush_rounds, pads
    ) -> None:
        """Compiled-artifact roofline for the two-input program:
        analyze each (plan, bucket) combination ONCE via AOT
        lower+compile over abstract args (deferred off the dispatch
        path). Never raises."""
        from risingwave_tpu.deviceprof import DEVICEPROF

        if not DEVICEPROF.enabled:
            return
        try:
            def shapes(batches):
                return ".".join(
                    f"{len(seg)}x{seg[0].valid.shape[0]}"
                    for seg in batches
                ) or "-"

            caps = ".".join(
                str(c)
                for c in (
                    self.join.left.capacity,
                    self.join.right.capacity,
                )
                + (
                    (self.mv.table.capacity,)
                    if self.mv is not None
                    else ()
                )
            )
            bucket = (
                f"fr{flush_rounds}_p{'.'.join(map(str, pads)) or '-'}"
                f"_l{shapes(left_batches)}_r{shapes(right_batches)}"
                f"_c{caps}"
            )
            abstract = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (states, left_batches, right_batches),
            )
            plan = self.plan
            DEVICEPROF.ensure_program(
                f"fused:{self.label}",
                bucket,
                lambda: _fused_two_input_step.lower(
                    abstract[0],
                    abstract[1],
                    abstract[2],
                    None,
                    plan,
                    flush_rounds,
                    pads,
                ),
                fragment=self.label,
            )
        except Exception:  # noqa: BLE001 — observability never faults
            pass


# ---------------------------------------------------------------------------
# chain rewriting
# ---------------------------------------------------------------------------


def _parse_side(chain, label: str, side: str):
    """Split one input-side chain into ``(pure prefix, stateful
    member)`` for the two-input plan, or None (with RW-E807
    provenance) when the side cannot be absorbed."""
    pres: List[Executor] = []
    stateful = None
    for ex in chain:
        if stateful is not None:
            return _refuse(
                f"{label}/{side}",
                "executors after the side's stateful member are not "
                "absorbable by the two-input program",
                executor=type(ex).__name__,
            )
        if _is_pure(ex):
            pres.append(ex)
        elif type(ex) in (
            DynamicMaxFilterExecutor,
            AppendOnlyDedupExecutor,
            HashAggExecutor,
        ):
            stateful = ex
        else:
            return _refuse(
                f"{label}/{side}",
                "not fusible in a two-input side chain",
                executor=type(ex).__name__,
            )
    if stateful is not None and type(stateful) is not HashAggExecutor:
        if stateful._buckets is None:
            return _refuse(
                f"{label}/{side}",
                "side state is not on a bucket lattice (the legacy "
                "unbucketed twin — the RW-E803 wedge class stays "
                "interpreted)",
                executor=type(stateful).__name__,
            )
    return pres, stateful


def _side_plan(pres, stateful) -> SidePlan:
    pre = (
        ComposedSteps([p.pure_step() for p in pres]) if pres else None
    )
    if stateful is None:
        return SidePlan(pre=pre, kind=None)
    if type(stateful) is DynamicMaxFilterExecutor:
        return SidePlan(
            pre=pre,
            kind="filter",
            keys=(stateful.group_col, stateful.value_col),
        )
    if type(stateful) is AppendOnlyDedupExecutor:
        return SidePlan(pre=pre, kind="dedup", keys=stateful.keys)
    return SidePlan(
        pre=pre,
        kind="agg",
        agg=AggStatics(
            calls=stateful.calls,
            group_keys=stateful.group_keys,
            nullable=stateful.nullable,
            out_cap=stateful.out_cap,
            float_extremes=stateful._float_extremes,
            has_minput=bool(stateful.minput),
        ),
    )


def fuse_two_input(
    pipeline, label: str = "mv", depth: Optional[int] = None
) -> Optional[FusedTwoInputExecutor]:
    """Plan whole-pipeline fusion for a TwoInputPipeline — q7's
    ``hop -> maxagg -> [flush] -> DynamicMaxFilter x HashJoin -> mv``
    and q8's ``dedup x join -> mv`` shapes — or None with RW-E807
    provenance (never a silent interpret fallback). Requirements, each
    refused with provenance when unmet:

    - the sides do not start with a shared sub-plan (``head``);
    - the join is a bucketed HashJoin whose trace contract declares
      ``two_input_fusible`` (both sides' capacities on the declared
      pow2 lattice — flush lanes pad to lattice buckets with masks,
      so the emission shape family is closed);
    - each side is ``pure*`` + at most one of {DynamicMaxFilter,
      AppendOnlyDedup, HashAgg} (bucketed), the agg (at most one, and
      on the right side) flushing INTO the join as lattice-padded
      masked right-arrivals — `_flush_all`'s exact-slicing status read
      never runs on this path;
    - the tail is ``pure* [DeviceMaterialize] pure*``.
    """
    join = getattr(pipeline, "join", None)
    if type(join) is not HashJoinExecutor:
        return _refuse(
            label,
            "two-input executor is not a HashJoin",
            executor=type(join).__name__,
        )
    if getattr(pipeline, "head", None):
        return _refuse(
            label,
            "the sides start with a shared sub-plan (one input fanned "
            "into both): the two-input program has an arrival batch a side",
            executor=type(pipeline.head[-1]).__name__,
        )
    contract = join.trace_contract()
    if not contract.get("two_input_fusible"):
        return _refuse(
            label,
            "join does not declare bucketed two-input fusibility "
            "(unbucketed sides: lattice-incompatible)",
            executor=type(join).__name__,
        )
    left = _parse_side(pipeline.left, label, "left")
    if left is None:
        return None
    right = _parse_side(pipeline.right, label, "right")
    if right is None:
        return None
    l_pres, l_stateful = left
    r_pres, r_stateful = right
    aggs = [
        e
        for e in (l_stateful, r_stateful)
        if type(e) is HashAggExecutor
    ]
    if len(aggs) > 1:
        return _refuse(label, "two agg sides are not fusible")
    if aggs and type(l_stateful) is HashAggExecutor:
        # one flush phase, ordered after both sides' applies: the agg
        # must sit on the RIGHT side (q7's shape); a left-side agg
        # would need its flush deltas applied as left arrivals BEFORE
        # the right batches to match the interpreted barrier order
        return _refuse(
            label,
            "agg on the left side: flush ordering not supported yet "
            "(swap the inputs)",
            executor="HashAggExecutor",
        )
    # tail: pure* [DeviceMaterialize] pure*
    tail_pre: List[Executor] = []
    tail_post: List[Executor] = []
    mv = None
    for ex in pipeline.tail:
        if type(ex) is DeviceMaterializeExecutor and mv is None:
            mv = ex
        elif _is_pure(ex):
            (tail_post if mv is not None else tail_pre).append(ex)
        else:
            return _refuse(
                f"{label}/tail",
                "not fusible in the two-input tail",
                executor=type(ex).__name__,
            )
    steps = lambda exs: (
        ComposedSteps([e.pure_step() for e in exs]) if exs else None
    )
    plan = TwoInputPlan(
        left=_side_plan(l_pres, l_stateful),
        right=_side_plan(r_pres, r_stateful),
        j_left_keys=join.left_keys,
        j_right_keys=join.right_keys,
        j_left_names=join.left_names,
        j_right_names=join.right_names,
        j_out_names=join.out_names,
        j_out_cap=join.out_cap,
        j_type=join.join_type,
        tail_pre=steps(tail_pre),
        mv_pk=mv.pk if mv is not None else None,
        mv_cols=mv.columns if mv is not None else None,
        tail_post=steps(tail_post),
    )
    members = (
        list(pipeline.left)
        + list(pipeline.right)
        + [join]
        + list(pipeline.tail)
    )
    return FusedTwoInputExecutor(
        members,
        plan,
        l_stateful,
        r_stateful,
        join,
        mv,
        label=label,
        depth=depth,
        n_left=len(pipeline.left),
    )


def fuse_chain(
    chain: Sequence[Executor],
    label: str = "fragment",
    defer_pure: bool = False,
    upstream: Optional[Executor] = None,
) -> List[Executor]:
    """Rewrite every maximal fusible run in an actor chain into a
    FusedChainExecutor; everything else passes through untouched (the
    interpreted fallback, per run, not per process).

    A run fuses when the whole per-barrier data path — agg apply,
    flush-delta extraction AND the device-MV write — lands inside one
    donated program (the q5 shape: ``pure* agg pure* mv pure*``):
    the flush never leaves the device, so its bound-padded delta
    capacity costs one masked device op, not an interpreted
    consumer's compute.

    Everything else keeps today's paths:

    - agg WITHOUT a downstream device MV in the run: the flush chunk
      EXITS to an interpreted consumer (a join) that wants the
      exact-sliced small chunks only the interpreted flush's status
      read can produce — fall back to the per-epoch batched wrapper
      (one fused apply program per epoch, interpreted exact flush).
      (A FUSIBLE two-input consumer absorbs the flush instead — see
      fuse_two_input, which runs before this per-chain pass.)
    - device MV without an agg (join-fed MV tails): fusible IFF the
      feeder's declared emission shape family is CLOSED ("fixed" /
      "bucketed" trace contract — a bucketed join emits one out_cap
      shape, a bucketed dynamic filter a pow2 lattice), so stacking
      its chunks is compile-bounded. The old hard carve-out ("stacking
      heterogeneous join emissions = compile storm") is replaced by
      this lattice-compatibility check; a refusal records RW-E807
      provenance (fusion_refusals) — never a silent fallback. The
      feeder is the nearest unfused upstream in the chain, or the
      caller-passed ``upstream`` executor for chain-head runs.
    - pure-only runs >= 2 fuse only with ``defer_pure`` (they emit
      during ``apply`` interpreted; deferring to the barrier is only
      epoch-equivalent, so it is opt-in)."""
    from risingwave_tpu.executors.epoch_batch import (
        EpochBatchedAggExecutor,
    )

    out: List[Executor] = []
    run: List[Executor] = []
    feeder = upstream

    def _feeder_emission():
        if feeder is None:
            return "unknown"
        fn = getattr(feeder, "trace_contract", None)
        try:
            contract = fn() if fn is not None else None
        except Exception:  # noqa: BLE001 — policy must never crash
            contract = None
        if contract is None:
            return "unknown"
        return contract.get("emission", "unknown")

    def close() -> None:
        nonlocal run
        if not run:
            return
        agg_idx = next(
            (
                i
                for i, m in enumerate(run)
                if type(m) is HashAggExecutor
            ),
            None,
        )
        has_mv = any(
            type(m) is DeviceMaterializeExecutor for m in run
        )
        has_mv_after_agg = agg_idx is not None and any(
            type(m) is DeviceMaterializeExecutor for m in run[agg_idx:]
        )
        if has_mv_after_agg:
            out.append(FusedChainExecutor(run, label=label))
        elif agg_idx is not None:
            # flush exits to an interpreted consumer: epoch-batch the
            # [pure*, agg] head, pass the tail pures through raw
            out.append(
                EpochBatchedAggExecutor(run[:agg_idx], run[agg_idx])
            )
            out.extend(run[agg_idx + 1 :])
        elif has_mv:
            em = _feeder_emission()
            if em in ("fixed", "bucketed"):
                out.append(FusedChainExecutor(run, label=label))
            else:
                _refuse(
                    label,
                    "join-fed MV tail left interpreted: feeder "
                    f"emission shape family is {em!r}, not a closed "
                    "fixed/bucketed lattice (stacking would mint one "
                    "program per distinct batch shape)",
                    executor=(
                        type(feeder).__name__
                        if feeder is not None
                        else None
                    ),
                )
                out.extend(run)
        elif defer_pure and len(run) >= 2:
            out.append(FusedChainExecutor(run, label=label))
        else:
            out.extend(run)
        run = []

    for ex in chain:
        if type(ex) is HashAggExecutor:
            if any(
                type(m) in (HashAggExecutor, DeviceMaterializeExecutor)
                for m in run
            ):
                close()
            run.append(ex)
        elif type(ex) is DeviceMaterializeExecutor:
            if any(type(m) is DeviceMaterializeExecutor for m in run):
                close()
            run.append(ex)
        elif _is_pure(ex):
            run.append(ex)
        else:
            close()
            out.append(ex)
            feeder = ex
    close()
    if (
        len(out) == 1
        and isinstance(out[0], FusedChainExecutor)
        and len(out[0].members) == len(list(chain))
    ):
        out[0].covers_whole_chain = True
    return out


def fuse_pipeline(
    pipeline,
    label: str = "mv",
    defer_pure: bool = False,
    pipeline_depth: Optional[int] = None,
):
    """Arm fusion on a SERIAL Pipeline / TwoInputPipeline in place
    (bench drivers and twin tests; the graph runtime fuses its actor
    chains automatically). Returns the wrappers created.

    Two-input pipelines fuse WHOLE first (fuse_two_input: side chains
    + join + MV tail into one donated program per barrier, with
    ``RW_FUSED_PIPELINE_DEPTH``/``pipeline_depth`` K-barrier device
    pipelining); when that is refused (RW-E807 provenance recorded)
    each chain falls back to the per-chain policy, with the join's
    contract passed as the tail's upstream so a lattice-compatible
    join-fed MV tail still fuses.

    Note: a serial pipeline's ``executors`` enumeration then yields
    wrappers instead of members — use on driver-owned pipelines, not
    runtime-registered ones; a two-input pipeline's chains are NOT
    rewritten under whole fusion (members stay enumerable), the
    wrapper rides ``pipeline._fused``."""
    created: List[Executor] = []

    def rewrite(chain, lbl, upstream=None):
        new = fuse_chain(
            chain, label=lbl, defer_pure=defer_pure, upstream=upstream
        )
        created.extend(
            e for e in new if isinstance(e, FusedChainExecutor)
        )
        return new

    if hasattr(pipeline, "join") and hasattr(pipeline, "left"):
        if two_input_enabled():
            w = fuse_two_input(
                pipeline, label=label, depth=pipeline_depth
            )
            if w is not None:
                pipeline._fused = w
                return [w]
        pipeline.head = rewrite(pipeline.head, f"{label}/head")
        pipeline.left = rewrite(pipeline.left, f"{label}/left")
        pipeline.right = rewrite(pipeline.right, f"{label}/right")
        pipeline.tail = rewrite(
            pipeline.tail, f"{label}/tail", upstream=pipeline.join
        )
    elif hasattr(pipeline, "executors"):
        pipeline.executors = rewrite(pipeline.executors, label)
    return created


def expand_fused(executors) -> List[Executor]:
    """Flatten fused wrappers back to their member executors (bench
    padding/governor surfaces read per-executor state)."""
    out: List[Executor] = []
    for ex in executors or ():
        if isinstance(ex, (FusedChainExecutor, FusedTwoInputExecutor)):
            out.extend(ex.members)
        else:
            out.append(ex)
    return out


def fused_fragments(pipeline) -> dict:
    """BENCH-JSON evidence: how much of the pipeline actually fused
    (count + whole-chain flag + labels). Accepts serial pipelines,
    two-input pipelines under whole fusion (the ``_fused`` wrapper)
    and GraphPipeline (scans the live actors)."""
    fused = getattr(pipeline, "_fused", None)
    if isinstance(fused, FusedTwoInputExecutor):
        return {
            "count": 1,
            "whole_chain": fused.covers_whole_chain,
            "fragments": [
                f"{fused.label}[{len(fused.members)}]"
            ],
            "pipeline_depth": fused.depth,
        }
    graph = getattr(pipeline, "graph", None)
    exs = graph.executors if graph is not None else (
        list(getattr(pipeline, "executors", []) or [])
    )
    wrappers = [e for e in exs if isinstance(e, FusedChainExecutor)]
    return {
        "count": len(wrappers),
        "whole_chain": bool(wrappers)
        and all(w.covers_whole_chain for w in wrappers),
        "fragments": sorted(
            {f"{w.label}[{len(w.members)}]" for w in wrappers}
        ),
    }
