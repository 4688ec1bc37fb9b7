"""Per-epoch chunk batching — fuse a stateless prefix into HashAgg's
one-device-program-per-epoch path.

The reference's benched executor IS its production executor (the
criterion harness drives the real HashAggExecutor,
src/stream/src/executor/hash_agg.rs:62 + src/stream/benches/). This
wrapper gives the planner-built actor graph the same property on TPU:
instead of one device dispatch per chunk (per-chunk Python dispatch
dominates on the TPU), the fragment accumulates the epoch's
chunks and applies them in ONE fused XLA program — the stateless prefix
(filter/project/hop) traced into the same program through
``HashAggExecutor.apply_stacked``'s ``pre`` hook.

Emission semantics are unchanged: HashAgg emits only at barriers /
watermarks, and the wrapper flushes its buffer before delegating either,
so downstream executors observe byte-identical streams.

Since the fused per-barrier step landed (runtime/fused_step.py), this
wrapper is the designated FALLBACK for agg runs the fused program
cannot absorb whole: an agg whose flush EXITS to an interpreted
consumer (a join) keeps its exact-sliced interpreted flush but still
gets the one-device-program-per-epoch apply path through this
wrapper. ``ComposedSteps`` and ``_compose_lint_infos`` below are
shared with the fused step (same value-hashing compile discipline,
same composed-metadata rules).

Compile discipline (see docs in array/chunk.py): the stacked leading
axis is padded to a power of two, so at most log2(max chunks/epoch)
distinct programs exist per chunk signature.
"""

from __future__ import annotations

from typing import List, Sequence

from risingwave_tpu.array.chunk import StreamChunk, stack_chunks
from risingwave_tpu.executors.base import Barrier, Executor, Watermark
from risingwave_tpu.executors.hash_agg import HashAggExecutor


class ComposedSteps:
    """A chunk->chunk composition of ``functools.partial`` steps with
    VALUE hashing: two compositions of the same (function, static args)
    sequence are equal, so the fused epoch program — which takes the
    composition as a STATIC jit argument — compiles once per plan
    shape, not once per wrapper instance (graph rebuilds and fresh
    planner passes hit the cache; a recompile is minutes cold on the
    TPU)."""

    __slots__ = ("steps", "_key", "_hash", "__weakref__")

    def __init__(self, steps):
        self.steps = tuple(steps)
        self._key = tuple(
            (s.func, s.args, tuple(sorted(s.keywords.items())))
            for s in self.steps
        )
        # the composition is a STATIC jit argument hashed on every
        # fused dispatch: pay the partial-tuple hash once, not per
        # barrier (tuples do not cache their hash)
        self._hash = hash(self._key)

    def __call__(self, chunk):
        # Under an ACTIVE lifted-literal param scope, inline the
        # UNJITTED step bodies: a nested pjit call caches its jaxpr
        # keyed by (statics, avals) ONLY, so an ambient value read
        # during tracing (expr.LiftedLit -> param_scope) would be
        # baked into that cached jaxpr as a leaked tracer const and
        # poison the next trace. Inlining makes the ambient read an
        # ordinary intermediate of the outer trace. Without params the
        # nested-jit jaxpr cache is safe AND cheaper (baked plans
        # re-trace the cached jaxpr instead of the step bodies).
        from risingwave_tpu.expr.expr import params_active

        if params_active():
            for f in self.steps:
                inner = getattr(f.func, "__wrapped__", None)
                chunk = (
                    inner(chunk, *f.args, **f.keywords)
                    if inner is not None
                    else f(chunk)
                )
            return chunk
        for f in self.steps:
            chunk = f(chunk)
        return chunk

    def __hash__(self):
        return self._hash

    def __eq__(self, other):
        return (
            isinstance(other, ComposedSteps) and self._key == other._key
        )


class EpochBatchedAggExecutor(Executor):
    """[stateless-pure*, HashAgg] fused into a per-epoch batched op.

    The wrapped ``agg`` object is SHARED with the pipeline's checkpoint
    registry (GraphPipeline holds the original executor objects), so
    checkpoint/restore, cold-tier eviction and state introspection all
    keep working through the original reference — only the actor's data
    path goes through this wrapper.
    """

    def __init__(
        self,
        prefix: Sequence[Executor],
        agg: HashAggExecutor,
        mode: str = "reduce",
    ):
        self.prefix = list(prefix)
        self.agg = agg
        self.mode = mode
        pures = tuple(p.pure_step() for p in self.prefix)
        if any(f is None for f in pures):
            raise ValueError("prefix executors must expose pure_step()")
        self._pre = ComposedSteps(pures) if pures else None
        self._buf: List[StreamChunk] = []
        self._sig = None

    # -- static metadata --------------------------------------------------
    def lint_info(self):
        """The composition of the members' metadata: the wrapper IS
        ``prefix... ; agg`` to the verifier. Opacity propagates — if
        any member exposes nothing, the wrapper exposes nothing (the
        verifier never guesses)."""
        infos = []
        for m in list(self.prefix) + [self.agg]:
            fn = getattr(m, "lint_info", None)
            info = fn() if fn is not None else None
            if info is None:
                return None
            infos.append(info)
        return _compose_lint_infos(infos)

    def state_nbytes(self) -> int:
        """Memory-ledger contract: all state lives in the wrapped agg
        (the prefix is stateless-pure by construction)."""
        fn = getattr(self.agg, "state_nbytes", None)
        return int(fn()) if fn is not None else 0

    def trace_contract(self):
        inner = self.agg.trace_contract()
        if inner is None:
            return None
        contract = dict(inner)
        # the fused epoch program IS apply_stacked: prefix pure steps
        # trace into the agg's program; the per-chunk trace_step stays
        # the agg's (same kernels, same state)
        contract["hot_methods"] = tuple(
            contract.get("hot_methods", ())
        ) + ("flush",)
        return contract

    # -- data path --------------------------------------------------------
    @staticmethod
    def _signature(c: StreamChunk):
        """Chunks must agree on capacity/columns/null lanes/dtypes to
        stack; a signature change flushes the current buffer."""
        return (
            c.capacity,
            tuple(sorted((k, str(v.dtype)) for k, v in c.columns.items())),
            tuple(sorted(c.nulls)),
        )

    def apply(self, chunk: StreamChunk) -> List[StreamChunk]:
        sig = self._signature(chunk)
        if self._sig is not None and sig != self._sig:
            self.flush()
        self._sig = sig
        self._buf.append(chunk)
        return []

    def flush(self) -> None:
        """Apply everything buffered in one device dispatch."""
        buf, self._buf = self._buf, []
        self._sig = None
        if not buf:
            return
        n = len(buf)
        target = 1 << (n - 1).bit_length() if n > 1 else 1
        if target > n:
            buf = buf + [buf[0].emptied()] * (target - n)
        self.agg.apply_stacked(
            stack_chunks(buf), pre=self._pre, mode=self.mode
        )

    # -- the warm-up pass (Executor.warm) ----------------------------------
    def warm_emissions(self):
        return self.agg.warm_emissions()

    def warm(self, chunk: StreamChunk) -> List[StreamChunk]:
        """The one-chunk epoch program for a chunk of this shape (an
        upstream aggregate's flush chunk comes one a round), not
        buffered: nothing is left for a barrier to apply."""
        self.agg.warm_stacked(stack_chunks([chunk]), self._pre, self.mode)
        return []

    # -- control path -----------------------------------------------------
    def on_barrier(self, barrier: Barrier) -> List[StreamChunk]:
        self.flush()
        return self.agg.on_barrier(barrier)

    def on_watermark(self, watermark: Watermark):
        # buffered rows precede the watermark in stream order: apply
        # them before any state cleaning the watermark triggers
        self.flush()
        outs: List[StreamChunk] = []
        wm = watermark
        for p in self.prefix:
            wm, o = p.on_watermark(wm)
            outs.extend(o)
            if wm is None:
                return None, outs
        wm, o = self.agg.on_watermark(wm)
        outs.extend(o)
        return wm, outs

    def emit_watermark(self):
        # fused prefix members never generate watermarks (enforced by
        # fuse_epoch_batch); only the agg can (EOWC)
        return self.agg.emit_watermark()

    def finish_barrier(self) -> None:
        for p in self.prefix:
            p.finish_barrier()
        self.agg.finish_barrier()


def _compose_lint_infos(infos):
    """Fold a member sequence's lint_info dicts into ONE equivalent
    dict (the wrapper's view). Conservative by construction: anything
    that cannot be traced back to the wrapper's input column space is
    dropped rather than guessed, so a composed plan can only LOSE
    checks relative to walking the members individually, never gain
    false positives."""
    rmap = {}  # current-schema col -> wrapper-input col (None=computed)

    def back(col):
        return rmap.get(col, col)

    requires, expects = set(), {}
    table_ids: List[str] = []
    wmap = {}
    window_key = None
    emits_final, renames_final, keys_final = None, None, None
    for pos, info in enumerate(infos):
        reqs = set(info.get("requires") or ()) | set(
            info.get("expects") or {}
        )
        for r in sorted(reqs):
            src = back(r)
            if src is not None:
                requires.add(src)
                dt = (info.get("expects") or {}).get(r)
                if dt is not None and src not in expects:
                    expects[src] = dt
        table_ids.extend(info.get("table_ids") or ())
        wk = info.get("window_key")
        if wk is not None and window_key is None and pos == 0:
            # only a first-member window key is expressible at the
            # wrapper boundary (later members see internally-derived
            # watermark columns the boundary cannot name)
            window_key = wk
        for in_col, out_col in (info.get("watermark_map") or {}).items():
            src = back(in_col)
            if src is not None:
                wmap[src] = out_col
        emits = info.get("emits")
        if emits is not None:
            renames = info.get("renames") or {}
            new_rmap = {}
            for out in emits:
                src = renames.get(out)
                new_rmap[out] = back(src) if src is not None else None
            rmap = new_rmap
            emits_final = dict(emits)
            renames_final = dict(rmap)
            ks = info.get("keys")
            if ks:
                mapped = tuple(back(k) for k in ks)
                keys_final = (
                    mapped if all(m is not None for m in mapped) else None
                )
        else:
            for col in info.get("adds") or {}:
                rmap = dict(rmap)
                rmap[col] = None  # computed mid-composition
    out = {
        "requires": tuple(sorted(requires)),
        "expects": expects,
        "table_ids": tuple(table_ids),
    }
    if emits_final is not None:
        out["emits"] = emits_final
        out["renames"] = renames_final or {}
    if keys_final:
        out["keys"] = keys_final
    if window_key is not None:
        out["window_key"] = window_key
    if wmap:
        out["watermark_map"] = wmap
    return out


def fuse_epoch_batch(chain: Sequence[Executor]) -> List[Executor]:
    """Rewrite every ``[stateless-pure*, HashAgg]`` run in an actor
    chain into an EpochBatchedAggExecutor. Anything that breaks the
    run (stateful op, watermark generator, no pure_step) passes through
    untouched, as does a HashAgg with no preceding run (still batched:
    the wrapper works with an empty prefix)."""
    out: List[Executor] = []
    run: List[Executor] = []
    for ex in chain:
        if type(ex) is HashAggExecutor:
            out.append(EpochBatchedAggExecutor(run, ex))
            run = []
        elif (
            ex.pure_step() is not None
            and type(ex).emit_watermark is Executor.emit_watermark
        ):
            run.append(ex)
        else:
            out.extend(run)
            run = []
            out.append(ex)
    out.extend(run)
    return out
