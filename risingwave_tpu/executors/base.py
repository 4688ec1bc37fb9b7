"""Executor protocol + control messages.

Reference: src/stream/src/executor/mod.rs —
- ``Execute`` trait (:180): an executor transforms a stream of
  ``Message::{Chunk, Barrier, Watermark}`` (:871);
- ``Barrier { epoch: EpochPair, kind }`` (:276) with checkpoint kinds;
- ``Watermark`` messages carry per-column monotonic lower bounds that
  drive state cleaning (executor/watermark_filter.rs).

TPU re-design: no async streams — the host epoch loop calls, in
dataflow order, ``apply(chunk)`` for data and ``on_barrier`` /
``on_watermark`` for control, collecting output chunks to feed the next
executor. Device state lives inside each executor as jax pytrees; all
math happens in pure jitted kernels so a whole chain runs as a few fused
XLA programs per chunk.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.array.lattice import push_lattice


@dataclass(frozen=True)
class Epoch:
    """EpochPair analogue (reference: src/common/src/util/epoch.rs:31).

    ``curr`` is the epoch being sealed by this barrier; ``prev`` is the
    previous sealed epoch. Values are physical-ms << 16 | seq in the
    runtime; tests may use small ints.
    """

    prev: int
    curr: int


@dataclass(frozen=True)
class Barrier:
    """A barrier message (reference: executor/mod.rs:276)."""

    epoch: Epoch
    checkpoint: bool = True


@dataclass(frozen=True)
class Watermark:
    """Monotonic per-column lower bound (reference: executor/mod.rs:871,
    watermark_filter.rs): no future row will carry ``column < value``."""

    column: str
    value: int


class Executor:
    """Base executor. Subclasses override what they react to.

    ``apply`` must be cheap on the host: stage device work, return
    fixed-capacity chunks. ``on_barrier`` flushes per-epoch deltas
    (reference: flush_data on barrier, e.g. hash_agg.rs:406).
    """

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        return [chunk]

    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        return []

    def on_watermark(self, watermark: Watermark):
        """Returns ``(downstream_watermark | None, output_chunks)``.

        Executors TRANSFORM watermarks as they pass (reference: derived
        watermarks through projections, watermark_filter.rs + plan-node
        watermark derivation): e.g. HopWindow maps an event-time
        watermark to a window_start watermark. None stops propagation.
        """
        return watermark, []

    def emit_watermark(self):
        """GENERATED watermark, polled by the pipeline after each
        barrier (WatermarkFilterExecutor overrides; reference:
        watermark_filter.rs emits into its output stream)."""
        return None

    def lint_info(self):
        """Static metadata for the plan verifier (analysis/), or None.

        None (the default) marks the executor OPAQUE: the verifier
        stops schema/watermark tracking at it and skips value-level
        checks downstream — it never guesses. Executors that know
        their column flow return a dict with any of:

        - ``requires``: columns read from the input channel
        - ``expects``: {col: dtype} declared input dtypes (implies
          requires)
        - ``adds``: {col: dtype|None} columns appended to the schema
        - ``emits``: {col: dtype|None} output schema REPLACING the
          input (aggs, joins, projects)
        - ``renames``: {out: in|None} for emits-executors — which
          output is an unmodified copy of which input (None =
          computed); drives dispatch-key tracing and watermark
          capability
        - ``keys``: state partition keys (exchange alignment, RW-E202)
        - ``state_pk``: state-table primary key (coverage, RW-E701)
        - ``table_ids``: state table ids (uniqueness, RW-E702)
        - ``window_key``: state-cleaning column that must be
          watermark-reachable (RW-E501)
        - ``watermark_map``: {in_col: out_col} watermark translation
          (hop window)
        - ``watermark_src``: column this executor GENERATES watermarks
          for (watermark filter)
        """
        return None

    def trace_contract(self):
        """Static COMPILABILITY metadata for the fusion analyzer
        (analysis/fusion_analyzer.py), or None = opaque (no trace
        contract: the analyzer cannot prove anything about this
        executor and it hard-stops a fragment's fusible prefix).

        The default derives a contract from ``pure_step()``: a
        stateless executor exposing a pure chunk->chunk step is
        trivially device-fusible. Stateful executors override and
        declare honestly what their apply/barrier path does TODAY —
        the analyzer verifies the claim (abstract tracing + an AST
        scan of the hot methods for host-sync markers), it does not
        trust it. Keys:

        - ``kind``: "device" (math staged in pure jitted kernels over
          (state, chunk) — abstractly traceable) or "host" (the data
          path leaves the device: NumPy fallback, dict probes).
        - ``trace_step``: chunk -> pytree callable CLOSED OVER the
          executor's current state, pure for tracing purposes (calls
          the underlying jitted kernel without mutating self); the
          analyzer make_jaxpr/eval_shape's it over the chunk-size
          bucket lattice. None when nothing is traceable.
        - ``state``: the donated state pytree, or None (stateless).
        - ``donate``: True when the step kernel donates its state
          buffers (donate_argnums) — False + state => RW-E804.
        - ``emission``: flush-chunk capacity behavior — "none" (never
          emits), "passthrough" (output capacity is a pure function
          of input capacity), "fixed"/"bucketed" (a declared, closed
          capacity set: ``emission_caps``), or "data_dependent"
          (capacity derives from live-row counts => RW-E802).
        - ``emission_caps``: tuple of declared emission capacities
          (fixed/bucketed kinds).
        - ``flush_walks``: for an aggregate, the declared lengths of
          the list of touched slots its flush programs range over
          (``lattice.touched_lattice``).
        - ``window_buckets``: for window-keyed executors, the declared
          bucket lattice of the per-window shape domain, or None =
          unbucketed (window churn re-traces without bound =>
          RW-E803, the q7 wedge class).
        - ``host_reason``: one-line reason for kind="host" (the AST
          scan adds exact file:line provenance).
        - ``hot_methods``: extra method names the host-sync scan must
          cover beyond apply/apply_left/apply_right/on_barrier/
          on_watermark.
        - ``fallback_syncs``: method names whose host syncs exist ONLY
          on the interpreted fallback path because the fused
          per-barrier step (runtime/fused_step) compiles a
          device-resident replacement for them (equivalence enforced
          by the fused-vs-interpreted twin tests). The analyzer
          reports them as ``fallback_sync_points`` instead of
          fusibility blockers.
        """
        step = self.pure_step()
        if step is None:
            return None
        return {
            "kind": "device",
            "trace_step": step,
            "state": None,
            "donate": True,
            "emission": "passthrough",
        }

    def pure_step(self):
        """A pure device function chunk -> chunk equivalent to this
        executor's ``apply`` (exactly one output chunk, no state), or
        None. Stateless executors expose it so an epoch-batching
        wrapper can trace them INTO a downstream stateful op's fused
        per-epoch program (one device dispatch per epoch instead of one
        per chunk — the XLA answer to the reference's per-chunk actor
        loop, hash_agg.rs:326).

        Contract: return a ``functools.partial`` of a MODULE-LEVEL
        function whose bound arguments are hashable — the composition
        is a static jit argument and must compare equal across executor
        instances of the same plan shape, or every graph rebuild
        recompiles the fused program."""
        return None

    # -- the push lattice (array/lattice.push_lattice; PR 32) --------
    # True on a stateful executor whose data path is one step a chunk
    # at the chunk's own width, and which knows ``warm``
    per_chunk_step = False

    def push_widths(self, capacity: int) -> Tuple[int, ...]:
        """The widths at which this executor takes a host-built chunk
        that was built at ``capacity`` lanes. An executor whose whole
        data path is one step a chunk — the stateless-pure ones, and
        the stateful ones that say ``per_chunk_step`` — takes the push
        lattice: ``StreamingRuntime.push`` may hand it the chunk cut to
        the smallest declared size that holds its rows. One that keys
        compiled programs on a uniform chunk width (an epoch-batched
        head, a fused barrier program) takes the full width only; one
        that keeps its chunks for a step of its own width says so
        itself (the general over-window). A fragment takes what every
        executor of it takes (``pipeline.chain_push_widths``)."""
        if self.per_chunk_step or self.pure_step() is not None:
            return push_lattice(capacity)
        return (int(capacity),)

    # -- the warm-up pass (runtime/graph.FragmentActor.warm_flush_lattice)
    def warm_emissions(self) -> Sequence[StreamChunk]:
        """One chunk with no valid row of every size this executor's
        barrier flush may hand on — its declared ``emission_caps`` —
        or nothing where what it hands on follows its input. When a
        view is created the actor sends them down what follows the
        executor, so that every program of every size exists before a
        stream first meets the size (HashAggExecutor)."""
        return ()

    def warm(self, chunk: StreamChunk) -> Optional[List[StreamChunk]]:
        """``apply`` for a chunk of the warm-up pass: run (so compile,
        or load from the persistent cache) what ``apply`` runs for a
        chunk of this shape, and LEAVE NO MARK: nothing stored or
        dirtied, no host bound advanced, no table grown, nothing for a
        checkpoint to stage. The chunk has no valid row, which the
        device steps treat as inert; the host side of a stateful
        ``apply`` (bounds, growth) is what an override leaves out.
        None = not known to be safe, the pass stops here: the default
        for everything but the stateless-pure executors, whose
        ``apply`` is their whole step."""
        return self.apply(chunk) if self.pure_step() is not None else None

    # -- overlapped barrier scalar reads ---------------------------------
    # Executors that must read device scalars at the barrier (overflow
    # latches, occupancy counters) ENQUEUE the packed read inside
    # ``on_barrier`` (sampling at their own position in the walk, i.e.
    # after absorbing upstream flushes) via ``stage_scalars`` and defer
    # the blocking host materialization to ``finish_barrier``, which
    # the pipeline calls for every executor AFTER the walk. The N
    # transfers are all in flight concurrently, so a chain pays ~one
    # device round-trip per barrier instead of N — with the
    # values and raise points semantically identical to synchronous
    # reads (checks still run before the runtime commits the epoch).

    _staged_scalars = None

    def finish_barrier(self) -> None:
        """Materialize scalars staged by on_barrier and run the
        executor's checks (one implementation; executors override
        ``_on_barrier_scalars`` only). Executors driven DIRECTLY with
        ``on_barrier(None)`` (tests/tools, no pipeline) finish inline
        so their latch checks still fire per epoch."""
        if self._staged_scalars is None:
            return
        from risingwave_tpu.ops.hash_table import finish_scalars
        from risingwave_tpu.trace import span

        # the materialization below is the barrier's device fence: the
        # span attributes per-executor device wait to the epoch trace
        # (and leaves a frame on the live stack for stall dumps)
        with span("executor.device_step", executor=type(self).__name__):
            vals = finish_scalars(self._staged_scalars)
        self._staged_scalars = None
        self._on_barrier_scalars(vals)

    def _on_barrier_scalars(self, vals) -> None:
        """Unpack + check the scalars this executor staged."""
        return None
