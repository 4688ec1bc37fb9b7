"""Planner output -> actor-graph execution: the unified runtime path.

The reference has ONE path from SQL to running operators: the frontend
fragments the stream plan at exchange edges
(src/frontend/src/stream_fragmenter/mod.rs:26-60), meta expands
fragments x parallelism into actors with dispatchers and vnode mappings
(src/meta/src/stream/stream_graph/actor.rs:648,
stream_graph/schedule.rs:131), and compute nodes run them over permit
channels (src/stream/src/executor/dispatch.rs:683). This module is that
path for the TPU build: it takes the StreamPlanner's executor chains
and re-expresses them as a ``GraphRuntime`` fragment graph —

  source frag --hash(dist cols)--> parallel frag x N --simple--> mat frag

- Each parallel instance is an independently planned, fresh executor
  chain (the actor build step, stream_manager.rs:89 create_nodes).
- Keyed state is hash-partitioned by a dispatch-key subset of the
  stateful executor's keys that traces back to source columns; one
  logical state table spans all instances with disjoint vnode ownership
  (consistent_hash/vnode.rs:34) via ``PartitionedStateView``.
- The facade ``GraphPipeline`` exposes the serial Pipeline surface
  (push/barrier/watermark/executors), so the SAME StreamingRuntime
  checkpoint/recovery/barrier machinery drives both execution modes.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.executors.base import Executor
from risingwave_tpu.executors.dedup import AppendOnlyDedupExecutor
from risingwave_tpu.executors.filter import FilterExecutor
from risingwave_tpu.executors.hash_agg import HashAggExecutor
from risingwave_tpu.executors.hop_window import HopWindowExecutor
from risingwave_tpu.executors.project import ProjectExecutor
from risingwave_tpu.expr import expr as E
from risingwave_tpu.ops.hashing import VNODE_COUNT, hash_columns
from risingwave_tpu.parallel.meshprof import MESHPROF
from risingwave_tpu.runtime.graph import FragmentSpec, GraphRuntime
from risingwave_tpu.runtime.pipeline import (
    FreshnessSurface,
    Pipeline,
    TwoInputPipeline,
    chain_push_widths,
)
from risingwave_tpu.storage.state_table import Checkpointable, StateDelta

# stateless executors a hash exchange may commute past (rows travel
# independently; no cross-row state): anything else ends the parallel
# prefix and runs in the singleton tail fragment
_PARALLEL_STATELESS = (FilterExecutor, ProjectExecutor, HopWindowExecutor)
# keyed stateful executors whose state partitions cleanly by a subset
# of their key tuple (HashAgg dirty-group state, append-only dedup)
_KEYED = (HashAggExecutor, AppendOnlyDedupExecutor)


def _keys_of(ex) -> Tuple[str, ...]:
    return tuple(getattr(ex, "group_keys", None) or getattr(ex, "keys", ()))


def _trace_source_col(chain: Sequence[Executor], name: str) -> Optional[str]:
    """Walk ``name`` backwards through a chain prefix to the source
    column it is an UNMODIFIED copy of (None if computed/renamed-over/
    untraceable). Conservative: only executors whose column flow we
    fully understand participate."""
    cur = name
    for ex in reversed(list(chain)):
        if isinstance(ex, ProjectExecutor):
            expr = dict(ex.outputs).get(cur)
            if not isinstance(expr, E.Col):
                return None
            cur = expr.name
        elif isinstance(ex, HopWindowExecutor):
            if cur == ex.out_start:
                return None  # computed column
        elif isinstance(ex, FilterExecutor):
            pass
        elif isinstance(ex, _KEYED):
            if cur not in _keys_of(ex):
                return None  # agg/dedup emit only their key columns
        else:
            return None
    return cur


def _key_lane_index(ex, pos: int) -> Optional[int]:
    """Checkpoint key-lane index (k{i}) of key POSITION ``pos``: HashAgg
    interleaves a bool null-indicator lane after each NULLABLE group
    key, so lane != position when nullable keys precede. A nullable
    dispatch key itself is disqualified (the dispatcher hashes the raw
    value lane; NULL rows would route by fill garbage)."""
    nb = getattr(ex, "nullable", None)
    if nb is None:
        return pos
    if nb[pos]:
        return None
    return pos + sum(1 for q in range(pos) if nb[q])


def _view_positions(
    chain_before: Sequence[Executor],
    ex,
    dispatch_srcs: Sequence[str],
) -> Optional[Tuple[int, ...]]:
    """For a keyed executor whose input has passed ``chain_before``:
    the checkpoint key-LANE index of each dispatch source column, in
    dispatch order (restore routing must hash the same values in the
    same order as the upstream HashDispatcher). None if any dispatch
    column is not one of the executor's (non-nullable) keys."""
    key_tuple = _keys_of(ex)
    out = []
    for s in dispatch_srcs:
        q = next(
            (
                qi
                for qi, k in enumerate(key_tuple)
                if _trace_source_col(chain_before, k) == s
            ),
            None,
        )
        if q is None:
            return None
        lane = _key_lane_index(ex, q)
        if lane is None:
            return None
        out.append(lane)
    return tuple(out)


class PartitionedStateView(Checkpointable):
    """One LOGICAL state table physically partitioned across N actor
    instances by vnode of the dispatch columns (the reference's 'same
    table_id, disjoint vnodes per actor' model). Presents the
    Checkpointable surface: deltas concatenate (key spaces are
    disjoint), restores route rows to the owning instance with the
    exact hash the upstream HashDispatcher used."""

    def __init__(self, instances: Sequence[object], positions: Dict[str, Tuple[int, ...]]):
        self._instances = list(instances)
        self._positions = dict(positions)  # table_id -> key-lane positions

    # -- Checkpointable ---------------------------------------------------
    @property
    def table_id(self) -> str:
        return self._instances[0].table_id

    def checkpoint_table_ids(self) -> List[str]:
        return self._instances[0].checkpoint_table_ids()

    def state_digest(self) -> int:
        """Wrapping sum over instance digests (disjoint key spaces;
        sum — not xor — so equal-state instances don't cancel)."""
        from risingwave_tpu.integrity import U64_MASK

        d = 0
        for inst in self._instances:
            d = (d + inst.state_digest()) & U64_MASK
        return d

    def checkpoint_delta(self) -> List[StateDelta]:
        by_tid: Dict[str, List[StateDelta]] = {}
        order: List[str] = []
        for inst in self._instances:
            for d in inst._pull_delta():
                if d.table_id not in by_tid:
                    order.append(d.table_id)
                by_tid.setdefault(d.table_id, []).append(d)
        out = []
        for tid in order:
            ds = by_tid[tid]
            if len(ds) == 1:
                out.append(ds[0])
                continue
            keys = {
                k: np.concatenate([d.key_cols[k] for d in ds])
                for k in ds[0].key_cols
            }
            vals = {
                k: np.concatenate([d.value_cols[k] for d in ds])
                for k in ds[0].value_cols
            }
            tomb = np.concatenate([d.tombstone for d in ds])
            out.append(StateDelta(tid, keys, vals, tomb, ds[0].key_order))
        return out

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        n = len(self._instances)
        if not key_cols or n == 1:
            for inst in self._instances:
                inst.restore_state(table_id, key_cols, value_cols)
            return
        pos = self._positions[table_id]
        lanes = [jnp.asarray(key_cols[f"k{p}"]) for p in pos]
        # EXACTLY the dispatcher's routing (graph.py _vnode_slice_mask):
        # a row restored to the wrong instance would be unreachable
        vnode = np.asarray(
            hash_columns(lanes, seed=0xC0FFEE) % VNODE_COUNT
        ).astype(np.int64)
        dest = vnode % n
        for i, inst in enumerate(self._instances):
            m = dest == i
            inst.restore_state(
                table_id,
                {k: v[m] for k, v in key_cols.items()},
                {k: v[m] for k, v in value_cols.items()},
            )

    # -- runtime hook fan-out ---------------------------------------------
    def state_nbytes(self) -> int:
        return sum(
            getattr(i, "state_nbytes", lambda: 0)() for i in self._instances
        )

    def evict_cold(self) -> int:
        total = 0
        for i in self._instances:
            fn = getattr(i, "evict_cold", None)
            if fn is not None and getattr(i, "cold_reader", None) is not None:
                total += fn()
        return total

    def on_epoch_durable(self, epoch: int) -> None:
        for i in self._instances:
            fn = getattr(i, "on_epoch_durable", None)
            if fn is not None:
                fn(epoch)

    def discard_pending(self) -> None:
        for i in self._instances:
            fn = getattr(i, "discard_pending", None)
            if fn is not None:
                fn()

    def on_recover(self, epoch: int) -> None:
        for i in self._instances:
            fn = getattr(i, "on_recover", None)
            if fn is not None:
                fn(epoch)

    @property
    def minput(self):
        for i in self._instances:
            m = getattr(i, "minput", None)
            if m:
                return m
        return {}

    @property
    def checkpoint_enabled(self):
        return getattr(self._instances[0], "checkpoint_enabled", False)

    @checkpoint_enabled.setter
    def checkpoint_enabled(self, v):
        for i in self._instances:
            if hasattr(i, "checkpoint_enabled"):
                i.checkpoint_enabled = v

    @property
    def cold_reader(self):
        return getattr(self._instances[0], "cold_reader", None)

    @cold_reader.setter
    def cold_reader(self, fn):
        for i in self._instances:
            if hasattr(i, "cold_reader"):
                i.cold_reader = fn


class GraphPipeline(FreshnessSurface):
    """Pipeline-compatible facade over a ``GraphRuntime`` actor graph:
    the object a StreamingRuntime registers, barriers, checkpoints, and
    recovers — while pushes flow through dispatchers, permit channels,
    and (possibly parallel) FragmentActor threads.

    Contract differences vs the serial Pipeline are epoch-granular:
    ``push``/``watermark`` return [] (processing is async inside the
    actors) and ``barrier`` returns everything the terminal fragment
    emitted during the epoch — the StreamingRuntime routes barrier
    output to subscribers before their own barrier runs, so MV-on-MV
    edges see identical per-epoch content in both modes."""

    def __init__(
        self,
        specs: Sequence[FragmentSpec],
        # side ("single" / "left" / "right" / "both") -> source fragment
        source_map: Dict[str, str],
        out_fragment: str,
        ckpt_executors: Sequence[object],
        epoch_batch: bool = True,
        ckpt_fragments: Optional[Sequence[str]] = None,
    ):
        self._specs = list(specs)
        self._epoch_batch = epoch_batch
        self._label: Optional[str] = None
        # a new view: every aggregate's flush lattice is compiled here,
        # inside its creation (a rebuild finds the programs in the
        # process and its executors mid-stream: it does not warm)
        self.graph = (
            GraphRuntime(self._specs, epoch_batch=epoch_batch)
            .warm_flush_lattices()
            .start()
        )
        self._sources = dict(source_map)
        self._out = out_fragment
        self._executors = list(ckpt_executors)
        # graph-fragment provenance of each ckpt executor (parallel to
        # ckpt_executors): lets partial recovery decide which fragments'
        # state a scoped rebuild must restore. None = unknown — scoped
        # intra-graph rebuild is then ineligible (full-graph rebuild,
        # still scoped at the runtime/MV level).
        if ckpt_fragments is not None and len(ckpt_fragments) != len(
            self._executors
        ):
            raise ValueError(
                "ckpt_fragments must parallel ckpt_executors "
                f"({len(ckpt_fragments)} vs {len(self._executors)})"
            )
        self._ckpt_fragments = (
            list(ckpt_fragments) if ckpt_fragments is not None else None
        )
        self.__dict__["_epoch_val"] = 0
        self._init_freshness()

    def rebuild(self, fragments: Optional[Sequence[str]] = None) -> None:
        """Replace dead actors: fresh threads + channels around the
        SAME executor instances (their state is restored separately by
        the runtime's recovery). The watchdog calls this before
        recover() when a graph-backed fragment fails.

        With ``fragments`` (a downstream-closed, source-free set from
        ``scoped_recovery_plan``), only that subtree is rebuilt: actors
        outside the blast radius keep their threads, channels, and live
        state — the fragment-scoped failover path."""
        if fragments:
            self.graph.rebuild_scoped(set(fragments))
            return
        try:
            self.graph.stop(timeout=1.0)
        except BaseException:
            pass  # a wedged/failed graph cannot block the rebuild
        self.graph = GraphRuntime(
            self._specs, epoch_batch=self._epoch_batch, label=self._label
        ).start()
        self.graph._epoch = self._epoch

    def set_label(self, name: str) -> None:
        """The name the runtime registered this pipeline under: its
        actors' stage keys and spans carry it (survives ``rebuild``)."""
        self._label = name
        self.graph.label = name
        for a in self.graph.actors:
            a.label = f"{name}/{a.actor_name}"

    # -- partial-recovery surface (the runtime's supervisor reads these)
    def failure_scope(self) -> Optional[Dict[str, object]]:
        """Structured view of the graph supervisor's failure state, or
        None while healthy: which fragments failed, the computed blast
        radius, and the per-actor errors."""
        g = self.graph
        if not getattr(g, "actor_errors", None):
            return None
        return {
            "failed_fragments": sorted(g.failed_fragments),
            "blast_radius": sorted(g.fenced_fragments),
            "errors": {a: repr(e) for a, e in g.actor_errors.items()},
        }

    def scoped_recovery_plan(self):
        """Decide how much of THIS pipeline a partial recovery must
        touch. Returns ``(graph_fragments, executors)``:

        - ``(blast, exs)`` — a scoped intra-graph rebuild is sound: only
          the blast radius's actors are rebuilt and only ``exs`` (its
          state tables) restore; actors outside keep running. Sound iff
          the blast excludes every source fragment, every STATEFUL
          fragment is inside it (replaying source data back through a
          live stateful fragment would double-apply), and every
          terminal fragment is inside it (otherwise the replay's output
          would be re-drained into subscribers).
        - ``(None, all_executors)`` — fall back to a full-graph rebuild
          (the MV as a whole still recovers scoped at the runtime
          level)."""
        full = (None, list(self._executors))
        g = self.graph
        blast = set(getattr(g, "fenced_fragments", ()) or ())
        if not blast or self._ckpt_fragments is None:
            return full
        sources = {s.name for s in self._specs if not s.inputs}
        consumed = {u for s in self._specs for (u, _p) in s.inputs}
        terminals = {s.name for s in self._specs if s.name not in consumed}
        stateful = {
            f
            for ex, f in zip(self._executors, self._ckpt_fragments)
            if isinstance(ex, Checkpointable)
        }
        if (
            (blast & sources)
            or not stateful <= blast
            or not terminals <= blast
        ):
            return full
        exs = [
            ex
            for ex, f in zip(self._executors, self._ckpt_fragments)
            if f in blast
        ]
        return set(blast), exs

    # the runtime assigns p._epoch on registration/recovery; keep the
    # actor graph's barrier clock in lockstep so injected epochs stay
    # monotonic relative to whatever the runtime restored
    @property
    def _epoch(self) -> int:
        return self.__dict__["_epoch_val"]

    @_epoch.setter
    def _epoch(self, v: int) -> None:
        self.__dict__["_epoch_val"] = v
        self.graph._epoch = v

    @property
    def epoch(self) -> int:
        return self._epoch

    @property
    def executors(self) -> List[object]:
        return self._executors

    # -- message surface --------------------------------------------------
    def push(self, chunk: StreamChunk, start: int = 0) -> List[StreamChunk]:
        self._note_ingest()
        self.graph.inject_chunk(self._sources["single"], chunk)
        return []

    def push_left(self, chunk: StreamChunk) -> List[StreamChunk]:
        self._note_ingest()
        self.graph.inject_chunk(self._sources["left"], chunk)
        return []

    def push_right(self, chunk: StreamChunk) -> List[StreamChunk]:
        self._note_ingest()
        self.graph.inject_chunk(self._sources["right"], chunk)
        return []

    def push_both(self, chunk: StreamChunk) -> List[StreamChunk]:
        """A chunk of one stream that feeds both join inputs: into each
        side's source, or once into the source of the sub-plan the two
        sides share."""
        if "both" not in self._sources:
            return self.push_left(chunk) + self.push_right(chunk)
        self._note_ingest()
        self.graph.inject_chunk(self._sources["both"], chunk)
        return []

    # -- the push lattice (StreamingRuntime.push, PR 32) -------------------
    def push_widths(self, capacity: int):
        """The widths at which this view takes a pushed chunk: the push
        lattice where every executor of every actor is a per-chunk
        step, the full width alone where one is not (an epoch-batched
        head keys its programs on a uniform chunk width)."""
        return chain_push_widths(self.graph.executors, capacity)

    def warm_push(self, chunk: StreamChunk, side: str = "single"):
        """A chunk with no valid row into the source ``push`` feeds for
        ``side``: each actor it reaches runs it through
        ``Executor.warm`` on its own thread, in the channel's order."""
        if side == "both" and "both" not in self._sources:
            sources = (self._sources["left"], self._sources["right"])
        else:
            sources = (self._sources[side],)
        for source in sources:
            self.graph.inject_warm(source, chunk)
        return []

    def watermark(self, column: str, value: int) -> List[StreamChunk]:
        self._note_watermark(value)
        self.graph.inject_watermark(column, value)
        return []  # flushed output surfaces at the next barrier drain

    def barrier(
        self, checkpoint: bool = True, epoch: Optional[int] = None
    ) -> List[StreamChunk]:
        t0 = time.perf_counter()
        target = self.barrier_nowait(checkpoint=checkpoint, epoch=epoch)
        outs = self.wait_barrier(target)
        self._sample_freshness((time.perf_counter() - t0) * 1e3)
        return outs

    # -- the barrier's two halves: inject, then wait ---------------------
    def barrier_nowait(
        self, checkpoint: bool = True, epoch: Optional[int] = None
    ) -> int:
        """Inject the barrier and return its epoch WITHOUT draining:
        pushes made after this belong to the next epoch while the
        actors are still flushing this one."""
        prev = self._epoch
        target = (
            epoch
            if epoch is not None
            else max(int(time.time() * 1000) << 16, prev + 1)
        )
        self._epoch = prev  # keep graph clock aligned before inject
        self.graph.inject_barrier_nowait(checkpoint=checkpoint, epoch=target)
        self.__dict__["_epoch_val"] = target
        return target

    def wait_barrier(self, epoch: int) -> List[StreamChunk]:
        """Block until every actor collected ``epoch``; drain what the
        terminal fragment emitted."""
        self.graph.wait_barrier(epoch)
        outs = self.graph.drain(self._out)
        # mesh observability: close this pipeline's per-shard window
        # (one matrix read + phase split; no-op unless armed AND this
        # graph carries sharded executors that were watched)
        if MESHPROF.enabled:
            MESHPROF.pipeline_barrier(self)
        return outs

    def close(self) -> None:
        self.graph.stop()


# ---------------------------------------------------------------------------
# sharded (multi-chip) fragment mode: one actor per fragment, the
# parallelism INSIDE it — stacked state over a jax Mesh, vnode exchange
# via all_to_all under shard_map (parallel/sharded_*.py). Unlike the
# actor-parallel mode, no dispatch-column tracing is needed: every
# sharded op re-exchanges its input by its OWN keys on device.
# ---------------------------------------------------------------------------


class StackSplitExecutor(Executor):
    """Flat (cap,) chunk -> stacked (n, cap) chunk, shard i seeing rows
    i, i+n, i+2n... (round-robin source split). The downstream sharded
    op's on-device exchange re-routes rows by key vnode, so the split
    here only balances load."""

    def __init__(self, n_shards: int):
        self.n = n_shards

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        n = self.n
        idx = jnp.arange(chunk.valid.shape[-1], dtype=jnp.int32)
        valid = jnp.stack([chunk.valid & (idx % n == i) for i in range(n)])
        bcast = lambda a: jnp.broadcast_to(a[None], (n,) + a.shape)
        return [
            StreamChunk(
                columns={k: bcast(v) for k, v in chunk.columns.items()},
                valid=valid,
                nulls={k: bcast(v) for k, v in chunk.nulls.items()},
                ops=bcast(chunk.ops),
            )
        ]

    def lint_info(self):
        # layout-only boundary: same lanes in and out (schema threading
        # through sharded chains survives the stacking edge)
        return {}


class FlattenExecutor(Executor):
    """Stacked (n, cap) chunk -> flat (n*cap,) chunk (host boundary)."""

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        from risingwave_tpu.parallel.sharded_join import flatten_stacked

        if chunk.valid.ndim == 1:
            return [chunk]  # already flat (e.g. a sharded agg flush)
        return [flatten_stacked(chunk)]

    def lint_info(self):
        # layout-only boundary: same lanes in and out
        return {}


def _sharded_equiv(ex, mesh, stacked_out: bool = False):
    """Sharded replacement for a keyed single-chip executor, carrying
    the SAME table_id (the checkpoint is one logical table either
    way). None when the executor's features aren't sharded yet."""
    from risingwave_tpu.parallel.sharded_agg import ShardedHashAgg
    from risingwave_tpu.parallel.sharded_join import ShardedDedup

    if isinstance(ex, HashAggExecutor):
        if ex.window_key is not None or any(
            c.materialized for c in ex.calls
        ):
            return None
        return ShardedHashAgg(
            mesh,
            ex.group_keys,
            ex.calls,
            ex._dtypes,
            capacity=ex.table.capacity,
            out_cap=ex.out_cap,
            nullable_keys=tuple(
                k for k, nb in zip(ex.group_keys, ex.nullable) if nb
            ),
            table_id=ex.table_id,
            stacked_out=stacked_out,
        )
    if isinstance(ex, AppendOnlyDedupExecutor):
        if ex.window_key is not None:
            return None
        return ShardedDedup(
            mesh,
            ex.keys,
            {k: lane.dtype for k, lane in zip(ex.keys, ex.table.keys)},
            capacity=ex.table.capacity,
            table_id=ex.table_id,
        )
    from risingwave_tpu.executors.top_n_plain import (
        RetractableGroupTopNExecutor,
    )
    from risingwave_tpu.parallel.sharded_top_n import ShardedGroupTopN

    if isinstance(ex, RetractableGroupTopNExecutor):
        # (the sharded twin ranks by one order key and hands on no rank)
        if (
            ex.window_key is not None
            or len(ex.order) != 1
            or ex.rank_col is not None
        ):
            return None
        return ShardedGroupTopN(
            mesh,
            ex.group_by,
            ex.order_col,
            ex.limit,
            ex.pk,
            {n: ex._dtypes[n] for n in ex.names},
            desc=ex.desc,
            capacity=ex.table.capacity,
            table_id=ex.table_id,
        )
    return None


def _shard_single_chain(chain, mesh):
    """chain -> sharded chain, or None when the shape can't shard:
    stateless* + ONE keyed (replaced by its sharded twin between
    StackSplit/Flatten) + anything (fed flat chunks as before)."""
    from risingwave_tpu.parallel.sharded_agg import ShardedHashAgg

    from risingwave_tpu.executors.row_id_gen import RowIdGenExecutor
    from risingwave_tpu.executors.top_n_plain import (
        RetractableGroupTopNExecutor,
    )
    from risingwave_tpu.parallel.sharded_top_n import ShardedGroupTopN

    keyed_idx = None
    for j, ex in enumerate(chain):
        if isinstance(ex, _KEYED + (RetractableGroupTopNExecutor,)):
            keyed_idx = j
            break
        # RowIdGen is safe here: the prefix runs FLAT, single-threaded,
        # BEFORE the StackSplit (ids stay globally unique) — unlike the
        # actor-parallel mode where per-instance generators would
        # collide
        if not isinstance(
            ex, _PARALLEL_STATELESS + (RowIdGenExecutor,)
        ):
            return None
    if keyed_idx is None:
        return None
    sharded = _sharded_equiv(chain[keyed_idx], mesh)
    if sharded is None:
        return None
    n = mesh.devices.size
    mid = [StackSplitExecutor(n), sharded]
    if not isinstance(sharded, (ShardedHashAgg, ShardedGroupTopN)):
        # dedup emits STACKED chunks from apply; GroupTopN/agg emit
        # host chunks at the barrier — only the former needs a flatten
        mid.append(FlattenExecutor())
    return list(chain[:keyed_idx]) + mid + list(chain[keyed_idx + 1 :])


def _shard_tail(tail, mesh, value_dtypes, value_nulls, capacity=None):
    """Replace a fixed-width materializer tail with a vnode-partitioned
    ``ShardedMaterialize`` (VERDICT r4 #6): Col-only projects stay
    stacked, the MV partitions by pk over the mesh, and a final Flatten
    keeps drained output flat for subscribers. ``value_dtypes`` /
    ``value_nulls`` describe the lanes arriving at the tail (from the
    upstream join or agg). Returns (tail_chain, sharded_mview) or None
    when the shape can't swap (nullable/unknown pk lane, non-Col
    projects, non-materializer tail)."""
    from risingwave_tpu.executors.materialize import (
        DeviceMaterializeExecutor,
        MaterializeExecutor,
    )
    from risingwave_tpu.parallel.sharded_mv import ShardedMaterialize

    if not tail:
        return None
    *pre, mat = tail
    for ex in pre:
        if not isinstance(ex, ProjectExecutor) or not all(
            isinstance(e, E.Col) for _n, e in ex.outputs
        ):
            return None
    renames: Dict[str, str] = {}  # output name -> source lane name
    for ex in pre:
        new = {n: renames.get(e.name, e.name) for n, e in ex.outputs}
        renames = new
    src_of = lambda n_: renames.get(n_, n_) if renames else n_
    if isinstance(mat, DeviceMaterializeExecutor):
        pk, columns = mat.pk, mat.columns
        dtypes = dict(mat.dtypes)
        nullable = tuple(mat.state.vnulls)
        capacity = mat.table.capacity
    elif isinstance(mat, MaterializeExecutor):
        pk, columns = mat.pk, mat.columns
        dtypes, nullable = {}, []
        for n_ in pk + columns:
            d = value_dtypes.get(src_of(n_))
            if d is None:
                return None
            dtypes[n_] = jnp.dtype(d)
            if src_of(n_) in value_nulls:
                if n_ in pk:
                    return None  # nullable pk: host-map executor only
                nullable.append(n_)
        nullable = tuple(nullable)
        # per-shard capacity follows the plan's sizing (the upstream
        # join/agg capacity), like every other sharded op
        capacity = capacity or (1 << 14)
    else:
        return None
    smv = ShardedMaterialize(
        mesh,
        pk,
        columns,
        dtypes,
        table_id=mat.table_id,
        capacity=capacity,
        nullable=nullable,
    )
    return list(pre) + [smv, FlattenExecutor()], smv


def sharded_planned_mv(planner_factory, sql: str, n_shards: int):
    """Plan ``sql`` and run it as SHARDED fragments over an n-device
    jax Mesh: keyed state stacked across devices, exchanges on ICI via
    all_to_all under shard_map — the multi-chip execution mode. Falls
    back to a single-actor graph when the shape can't shard."""
    from risingwave_tpu.parallel.sharded_agg import make_mesh
    from risingwave_tpu.parallel.sharded_join import ShardedHashJoin

    mesh = make_mesh(n_shards)
    proto = planner_factory().plan(sql)
    from risingwave_tpu.sql.planner import PlannedMV

    mview = proto.mview
    if isinstance(proto.pipeline, TwoInputPipeline):
        tp = proto.pipeline
        left = _shard_side_chain(tp.left, mesh, tp.head)
        right = _shard_side_chain(tp.right, mesh, tp.head)
        join = tp.join
        if (
            left is None
            or right is None
            # a residual evaluated inside the join (the chained layout)
            # has no sharded twin: one actor
            or getattr(join, "condition", None) is not None
        ):
            gp = _two_input_graph([proto], None)
        else:
            # the sharded join is the bucket layout across devices,
            # whichever layout the planner chose for one device (the
            # chained one declares no fan-out: the bucket layout's 16)
            side_cap = join.left.table.capacity
            fanout = getattr(join.left, "fanout", 16)
            sj = ShardedHashJoin(
                mesh,
                join.left_keys,
                join.right_keys,
                {n_: a.dtype for n_, a in join.left.rows.items()},
                {n_: a.dtype for n_, a in join.right.rows.items()},
                capacity=side_cap,
                fanout=fanout,
                out_cap=join.out_cap,
                left_nullable=tuple(join.left.row_nulls),
                right_nullable=tuple(join.right.row_nulls),
                join_type=join.join_type,
                table_id=join.table_id,
            )
            tail = None
            if join.join_type == "inner":
                # outer joins append computed null lanes per emission
                # side — only inner emissions carry exactly the declared
                # nullable sets, so only those swap to the sharded MV
                out_dtypes = {
                    n_: a.dtype for n_, a in join.left.rows.items()
                }
                out_dtypes.update(
                    {n_: a.dtype for n_, a in join.right.rows.items()}
                )
                out_nulls = set(join.left.row_nulls) | set(
                    join.right.row_nulls
                )
                tail = _shard_tail(
                    tp.tail,
                    mesh,
                    out_dtypes,
                    out_nulls,
                    capacity=side_cap,
                )
            if tail is None:
                tail_chain = [FlattenExecutor()] + list(tp.tail)
            else:
                tail_chain, mview = tail
            build = {
                "left": left,
                "right": right,
                "join": sj,
                "tail": tail_chain,
            }
            specs = [
                FragmentSpec("left_src", lambda i: []),
                FragmentSpec("right_src", lambda i: []),
                FragmentSpec(
                    "join",
                    lambda i, b=build: dict(b),
                    inputs=[("left_src", 0), ("right_src", 1)],
                ),
            ]
            ckpt = left + right + [sj] + build["tail"]
            gp = GraphPipeline(
                specs,
                {"left": "left_src", "right": "right_src"},
                "join",
                ckpt,
                ckpt_fragments=["join"] * len(ckpt),
            )
    else:
        chain = _shard_single_chain(list(proto.pipeline.executors), mesh)
        if chain is None:
            gp = _singleton_graph(list(proto.pipeline.executors))
        else:
            swapped = _shard_single_tail(chain, mesh)
            if swapped is not None:
                chain, mview = swapped
            specs = [FragmentSpec("mv", lambda i, c=tuple(chain): list(c))]
            gp = GraphPipeline(
                specs, {"single": "mv"}, "mv", chain,
                ckpt_fragments=["mv"] * len(chain),
            )
    return PlannedMV(
        proto.name, gp, mview, proto.inputs, schema=proto.schema
    )


def _shard_single_tail(chain, mesh):
    """After ``_shard_single_chain``, try to keep the MV sharded too:
    [..., ShardedHashAgg, (Flatten?), projects..., DeviceMaterialize]
    becomes [..., agg(stacked flush), projects..., ShardedMaterialize,
    Flatten]. Only the device materializer swaps here (its dtypes and
    null lanes are declared; the host-map executor's are inferred only
    on the join path). Returns (chain, mview) or None."""
    from risingwave_tpu.parallel.sharded_agg import ShardedHashAgg

    agg_idx = next(
        (
            j
            for j, ex in enumerate(chain)
            if isinstance(ex, ShardedHashAgg)
        ),
        None,
    )
    if agg_idx is None:
        return None
    rest = chain[agg_idx + 1 :]
    swapped = _shard_tail(rest, mesh, {}, set())
    if swapped is None:
        return None
    tail_chain, smv = swapped
    agg = chain[agg_idx]
    agg.stacked_out = True
    return list(chain[: agg_idx + 1]) + tail_chain, smv


def _shard_side_chain(chain, mesh, head=()):
    """A join side shards when it starts at its source (``head``, a
    sub-plan shared with the other side, has no sharded form: None) and
    is stateless* + optional ONE keyed op
    (append-only dedup -> ShardedDedup; windowless non-materialized
    HashAgg -> ShardedHashAgg whose barrier flush stays STACKED and
    feeds the join directly — the q7 per-window-MAX side) + rename-only
    projects (element-wise on stacked chunks). Returns the sharded
    chain or None."""
    from risingwave_tpu.executors.row_id_gen import RowIdGenExecutor

    if head:
        return None
    out = []
    seen_keyed = False
    for ex in chain:
        if isinstance(ex, _KEYED):
            if seen_keyed:
                return None
            # feature-check BEFORE building: _sharded_equiv allocates
            # mesh-stacked device state
            if isinstance(ex, HashAggExecutor):
                sharded = _sharded_equiv(ex, mesh, stacked_out=True)
            else:
                sharded = _sharded_equiv(ex, mesh)
            if sharded is None:
                return None
            seen_keyed = True
            out.append(StackSplitExecutor(mesh.devices.size))
            out.append(sharded)
        elif isinstance(ex, ProjectExecutor):
            if seen_keyed and not all(
                isinstance(e, E.Col) for _n, e in ex.outputs
            ):
                return None  # only renames are stacked-safe
            out.append(ex)
        elif isinstance(ex, (FilterExecutor, HopWindowExecutor)):
            if seen_keyed:
                return None  # pre-exchange ops only before the keyed op
            out.append(ex)
        elif isinstance(ex, RowIdGenExecutor):
            if seen_keyed:
                return None  # runs on flat host-side chunks only
            out.append(ex)
        else:
            return None
    if not seen_keyed:
        # stateless side: split right before the join's own exchange
        out.append(StackSplitExecutor(mesh.devices.size))
    return out


# ---------------------------------------------------------------------------
# fragment -> chain extraction (static analysis surface)
# ---------------------------------------------------------------------------


def fragment_chains(pipeline) -> Dict[str, Dict[str, List[object]]]:
    """Normalize ANY pipeline shape into ``{fragment: {section:
    executor chain}}`` for static analysis (plan verifier / fusion
    analyzer). Sections name the input side feeding the chain:
    ``single``/``left``/``right``/``both`` (source-fed — the analyzer
    can seed an abstract schema; ``both`` is the sub-plan a join's two
    sides share, and the sides it feeds are then ``head_left`` /
    ``head_right``), ``join_tail`` (the join executor + tail of a
    two-input shape), or ``chain`` (a graph fragment fed by other
    fragments — schema threads through lint_info, not sources).

    GraphPipeline fragments are SHADOW-built (``spec.build(0)``) on the
    host device only to read static metadata — the live actors hold
    their own executors; nothing here touches HBM or actor state."""
    if hasattr(pipeline, "_specs") and hasattr(pipeline, "graph"):
        from risingwave_tpu.analysis.plan_verifier import _host_device

        out: Dict[str, Dict[str, List[object]]] = {}
        frag_side = {
            frag: side for side, frag in pipeline._sources.items()
        }
        for s in pipeline._specs:
            try:
                with _host_device():
                    built = s.build(0)
            except Exception:  # noqa: BLE001 — builder needs live inputs
                built = None
            if isinstance(built, dict):
                out[s.name] = _two_input_sections(
                    built.get("head", ()),
                    built.get("left", ()),
                    built.get("right", ()),
                    built.get("join"),
                    built.get("tail", ()),
                )
            elif isinstance(built, (list, tuple)):
                side = frag_side.get(s.name)
                key = side or ("single" if not s.inputs else "chain")
                out[s.name] = {key: list(built)}
            else:
                out[s.name] = {}
        return out
    if hasattr(pipeline, "join") and hasattr(pipeline, "left"):
        sections = _two_input_sections(
            getattr(pipeline, "head", ()),
            pipeline.left,
            pipeline.right,
            pipeline.join,
            pipeline.tail,
        )
        frag = {
            "both": "head",
            "head_left": "left",
            "head_right": "right",
            "join_tail": "out",
        }
        return {
            frag.get(sec, sec): {sec: chain}
            for sec, chain in sections.items()
        }
    if hasattr(pipeline, "executors"):
        return {"mv": {"single": list(pipeline.executors)}}
    return {}


def _two_input_sections(head, left, right, join, tail):
    """A two-input shape's chains by section (``fragment_chains``): the
    sides are source-fed unless a shared head feeds them."""
    lname, rname = ("head_left", "head_right") if head else ("left", "right")
    sections = {"both": list(head)} if head else {}
    sections[lname] = list(left)
    sections[rname] = list(right)
    sections["join_tail"] = ([join] if join is not None else []) + list(tail)
    return sections


def is_mesh_executor(ex) -> bool:
    """True for mesh-resident executors (those declaring a
    ``mesh_contract()``) — the sharded ops the mesh analyzer proves."""
    return callable(getattr(ex, "mesh_contract", None))


def is_mesh_boundary(ex) -> bool:
    """True for the host-routing stack/flatten boundary executors — the
    edges where rows cross between flat host chunks and the stacked
    mesh layout (the RW-E901 exchange edges a fully SPMD fragment would
    absorb into its program)."""
    return isinstance(ex, (StackSplitExecutor, FlattenExecutor))


def sharded_chains(pipeline) -> Dict[str, Dict[str, List[object]]]:
    """``fragment_chains`` restricted to the SHARDED fragments: those
    whose chains contain at least one mesh-resident executor (or one of
    the stack/flatten boundary adapters feeding it). This is the mesh
    analyzer's extraction surface — per fragment, per section, the
    executor chain with the mesh ops and their host boundaries in
    source order."""
    out: Dict[str, Dict[str, List[object]]] = {}
    for frag, sections in fragment_chains(pipeline).items():
        kept = {
            sec: list(chain)
            for sec, chain in sections.items()
            if any(
                is_mesh_executor(e) or is_mesh_boundary(e) for e in chain
            )
        }
        if kept:
            out[frag] = kept
    return out


# ---------------------------------------------------------------------------
# planner output -> fragment graph
# ---------------------------------------------------------------------------


def graph_planned_mv(
    planner_factory, sql: str, parallelism: int = 1, epoch_batch: bool = True
):
    """Plan ``sql`` once per instance with FRESH planners (identical,
    deterministic table_ids across instances — they are partitions of
    the same logical tables) and return a PlannedMV whose pipeline is a
    GraphPipeline. Shapes that cannot partition fall back to a
    single-actor graph — same SQL, same results, still actors."""
    n = max(1, parallelism)
    proto = planner_factory().plan(sql)
    if getattr(proto, "aux", ()):
        # lowered multi-MV plans (nested joins / decorrelated scalar
        # subqueries) are wired through runtime subscription edges; the
        # actor-graph wrapper would drop the aux list — run them serial
        return proto
    # decide partitionability on the prototype BEFORE paying for N-1
    # more planner passes — a non-partitionable shape falls back to a
    # single-actor graph using only the prototype
    if isinstance(proto.pipeline, TwoInputPipeline):
        sides = _split_join(proto.pipeline) if n > 1 else None
        plans = (
            [proto] + [planner_factory().plan(sql) for _ in range(n - 1)]
            if sides is not None
            else [proto]
        )
        gp = _two_input_graph(plans, sides, epoch_batch=epoch_batch)
    else:
        split = (
            _split_single(list(proto.pipeline.executors)) if n > 1 else None
        )
        plans = (
            [proto] + [planner_factory().plan(sql) for _ in range(n - 1)]
            if split is not None
            else [proto]
        )
        gp = _single_graph(plans, split, epoch_batch=epoch_batch)
    from risingwave_tpu.sql.planner import PlannedMV

    return PlannedMV(
        proto.name, gp, proto.mview, proto.inputs, schema=proto.schema
    )


def _singleton_graph(chain, source_map_side="single", epoch_batch=True):
    name = "mv"
    specs = [FragmentSpec(name, lambda i, ch=tuple(chain): list(ch))]
    return GraphPipeline(
        specs, {source_map_side: name}, name, list(chain),
        epoch_batch=epoch_batch,
        ckpt_fragments=[name] * len(chain),
    )


def _single_graph(plans, split, epoch_batch=True) -> GraphPipeline:
    chains = [list(p.pipeline.executors) for p in plans]
    chain0 = chains[0]
    n = len(plans)

    if split is None or n == 1:
        return _singleton_graph(chain0, epoch_batch=epoch_batch)
    prefix_len, dispatch_cols, positions_by_idx = split

    specs = [
        FragmentSpec(
            "src", lambda i: [], dispatch=("hash", list(dispatch_cols))
        ),
        FragmentSpec(
            "par",
            lambda i: list(chains[i][:prefix_len]),
            inputs=[("src", 0)],
            parallelism=n,
        ),
        FragmentSpec(
            "mat",
            lambda i: list(chain0[prefix_len:]),
            inputs=[("par", 0)],
        ),
    ]
    ckpt: List[object] = []
    frags: List[str] = []
    for j in range(prefix_len):
        ex0 = chain0[j]
        if isinstance(ex0, Checkpointable):
            ckpt.append(
                PartitionedStateView(
                    [chains[i][j] for i in range(n)], positions_by_idx[j]
                )
            )
            frags.append("par")
    ckpt.extend(chain0[prefix_len:])
    frags.extend(["mat"] * len(chain0[prefix_len:]))
    return GraphPipeline(
        specs, {"single": "src"}, "mat", ckpt, epoch_batch=epoch_batch,
        ckpt_fragments=frags,
    )


def _split_single(chain):
    """Find the parallel prefix of a single-input chain: stateless ops
    up to and including the FIRST keyed stateful executor. Returns
    (prefix_len, dispatch source cols, {chain idx -> {table_id ->
    positions}}) or None when the shape cannot partition."""
    keyed_idx = None
    for j, ex in enumerate(chain):
        if isinstance(ex, _KEYED):
            keyed_idx = j
            break
        if not isinstance(ex, _PARALLEL_STATELESS):
            return None
    if keyed_idx is None:
        return None
    keyed = chain[keyed_idx]
    keys = _keys_of(keyed)
    before = chain[:keyed_idx]
    dispatch, lanes = [], []
    for pos, k in enumerate(keys):
        src = _trace_source_col(before, k)
        lane = _key_lane_index(keyed, pos)
        if src is not None and lane is not None:
            dispatch.append(src)
            lanes.append(lane)
    if not dispatch:
        return None
    positions = {
        keyed_idx: {
            tid: tuple(lanes) for tid in keyed.checkpoint_table_ids()
        }
    }
    return keyed_idx + 1, dispatch, positions


def _two_input_graph(plans, sides, epoch_batch=True) -> GraphPipeline:
    tp0 = plans[0].pipeline
    n = len(plans)
    if sides is None or n == 1:
        build = {
            "head": tp0.head,
            "left": tp0.left,
            "right": tp0.right,
            "join": tp0.join,
            "tail": tp0.tail,
        }
        # a shared head is the join actor's one input: one source
        sources = (
            {"both": "src"}
            if tp0.head
            else {"left": "left_src", "right": "right_src"}
        )
        specs = [FragmentSpec(src, lambda i: []) for src in sources.values()]
        specs.append(
            FragmentSpec(
                "join",
                lambda i, b=build: dict(b),
                inputs=[
                    (src, port) for port, src in enumerate(sources.values())
                ],
            )
        )
        return GraphPipeline(
            specs,
            sources,
            "join",
            tp0.executors,
            epoch_batch=epoch_batch,
            ckpt_fragments=["join"] * len(tp0.executors),
        )
    ldisp, rdisp, join_positions, side_positions = sides

    def build_join(i):
        tp = plans[i].pipeline
        return {
            "left": tp.left,
            "right": tp.right,
            "join": tp.join,
            "tail": [],
        }

    specs = [
        FragmentSpec(
            "left_src", lambda i: [], dispatch=("hash", list(ldisp))
        ),
        FragmentSpec(
            "right_src", lambda i: [], dispatch=("hash", list(rdisp))
        ),
        FragmentSpec(
            "join",
            build_join,
            inputs=[("left_src", 0), ("right_src", 1)],
            parallelism=n,
        ),
        FragmentSpec("mat", lambda i: list(tp0.tail), inputs=[("join", 0)]),
    ]
    ckpt: List[object] = []
    frags: List[str] = []
    for side_name in ("left", "right"):
        chain0 = getattr(tp0, side_name)
        for j, ex0 in enumerate(chain0):
            if isinstance(ex0, Checkpointable):
                ckpt.append(
                    PartitionedStateView(
                        [getattr(plans[i].pipeline, side_name)[j] for i in range(n)],
                        side_positions[(side_name, j)],
                    )
                )
                frags.append("join")
    ckpt.append(
        PartitionedStateView(
            [plans[i].pipeline.join for i in range(n)], join_positions
        )
    )
    frags.append("join")
    ckpt.extend(tp0.tail)
    frags.extend(["mat"] * len(tp0.tail))
    return GraphPipeline(
        specs,
        {"left": "left_src", "right": "right_src"},
        "mat",
        ckpt,
        epoch_batch=epoch_batch,
        ckpt_fragments=frags,
    )


def _split_join(tp):
    """Partitionability of a two-input join fragment. Returns
    (left dispatch cols, right dispatch cols, join table positions,
    {(side, idx) -> table positions}) or None."""
    if tp.head:
        return None  # one chain feeding both sides: no per-side dispatch
    join = tp.join
    lkeys = tuple(join.left_keys)
    rkeys = tuple(join.right_keys)
    ldisp, rdisp, jpos = [], [], []
    for p in range(len(lkeys)):
        ls = _trace_source_col(tp.left, lkeys[p])
        rs = _trace_source_col(tp.right, rkeys[p])
        if ls is not None and rs is not None:
            ldisp.append(ls)
            rdisp.append(rs)
            jpos.append(p)
    if not jpos:
        return None
    # every side executor must be either parallel-safe stateless or a
    # keyed stateful whose key tuple covers the side's dispatch columns
    side_positions: Dict[Tuple[str, int], Dict[str, Tuple[int, ...]]] = {}
    for side_name, disp in (("left", ldisp), ("right", rdisp)):
        chain = getattr(tp, side_name)
        for j, ex in enumerate(chain):
            if isinstance(ex, _PARALLEL_STATELESS):
                continue
            if isinstance(ex, _KEYED):
                pos = _view_positions(chain[:j], ex, disp)
                if pos is None:
                    return None
                side_positions[(side_name, j)] = {
                    tid: pos for tid in ex.checkpoint_table_ids()
                }
                continue
            return None
    tid = join.table_id
    join_positions = {
        f"{tid}.left": tuple(jpos),
        f"{tid}.right": tuple(jpos),
    }
    return ldisp, rdisp, join_positions, side_positions
