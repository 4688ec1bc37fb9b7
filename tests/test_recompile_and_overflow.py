"""jit-cache stability + hash-table overflow behavior (VERDICT r1
next-step 8): the fixed-capacity chunk design exists so a pipeline
compiles once and replays every epoch with ZERO recompiles; overflow
past MAX_PROBE must signal -1 (host rehash), never corrupt."""

import numpy as np
import pytest

import jax.numpy as jnp

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.connectors.nexmark import NexmarkConfig, NexmarkGenerator
from risingwave_tpu.executors import hash_agg as hash_agg_mod
from risingwave_tpu.executors import hop_window as hop_mod
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.ops.hash_table import (
    MAX_PROBE,
    HashTable,
    lookup,
    lookup_or_insert,
)
from risingwave_tpu.queries.nexmark_q import build_q5_lite


def test_zero_recompiles_across_epochs():
    """After a warmup epoch, further epochs must not grow any jit
    cache (chunk.py's 'compile once, run every epoch' premise).
    Steady-state misses are asserted through the shared RecompileWatch
    (analysis/) — the same counter bench.py surfaces per query — and
    the executors' abstract input signatures must stay stable
    (SignatureWatch: the recompile-HAZARD detector)."""
    from risingwave_tpu.analysis.jax_sanitizer import (
        RecompileWatch,
        SignatureWatch,
    )
    from risingwave_tpu.metrics import REGISTRY

    q5 = build_q5_lite(capacity=1 << 12, state_cleaning=False)
    gen = NexmarkGenerator(NexmarkConfig())
    watch = SignatureWatch().start()
    import risingwave_tpu.runtime.pipeline as pipeline_mod

    orig = pipeline_mod.SIGNATURES
    pipeline_mod.SIGNATURES = watch  # route walk_chain observations

    # STEADY state: the same key set every epoch (counts grow, state
    # capacity does not). Fresh keys per epoch would legitimately grow
    # the MV table past its load factor — a rebuild+recompile by
    # design, not the regression this guards against.
    bid = gen.next_chunks(1000, 1024)["bid"].select(
        ["auction", "date_time"]
    )

    def push_epoch():
        q5.pipeline.push(bid)
        q5.pipeline.barrier()

    try:
        push_epoch()  # warmup: compiles everything
        push_epoch()  # flush path warm too (first flush may add an entry)
        recompiles = RecompileWatch()
        recompiles.snapshot()
        watch.mark_stable()
        before = REGISTRY.counter("recompiles_total")._values.copy()
        for _ in range(4):
            push_epoch()
        # steady-state epochs trigger ZERO recompiles across every
        # registered step kernel...
        assert recompiles.deltas() == {}
        assert REGISTRY.counter("recompiles_total")._values == before
        # ...and zero shape instability (no recompile hazards)
        assert watch.report() == []
        # the original per-kernel checks stay as a cross-check
        assert hash_agg_mod._agg_step._cache_size() > 0
        assert hop_mod._hop_step._cache_size() > 0
    finally:
        watch.stop()
        pipeline_mod.SIGNATURES = orig


def test_overflow_past_max_probe_signals_minus_one():
    """Drive a table far past 50% load: rows must either resolve to a
    verified slot or return -1 — never a wrong slot."""
    cap = 256
    table = HashTable.create(cap, (jnp.dtype(jnp.int64),))
    rng = np.random.default_rng(3)
    all_keys = []
    got_minus_one = False
    for _ in range(4):
        keys = rng.integers(0, 1 << 40, 120).astype(np.int64)
        all_keys.append(keys)
        table, slots, found, inserted = lookup_or_insert(
            table, (jnp.asarray(keys),), jnp.ones(120, jnp.bool_)
        )
        slots = np.asarray(slots)
        got_minus_one |= bool((slots < 0).any())
        # every resolved slot stores EXACTLY the row's key
        stored = np.asarray(table.keys[0])
        ok = slots >= 0
        assert (stored[slots[ok]] == keys[ok]).all()
    # 480 inserts into 256 slots: overflow must have fired
    assert got_minus_one
    # and the table never "finds" a key it doesn't hold
    probe = rng.integers(1 << 41, 1 << 42, 64).astype(np.int64)
    _, found = lookup(table, (jnp.asarray(probe),), jnp.ones(64, jnp.bool_))
    assert not bool(np.asarray(found).any())


def test_agg_executor_grows_past_initial_capacity():
    """Executor-level: sustained distinct keys trigger host rehash; the
    final state matches a fresh big-table run exactly."""
    from risingwave_tpu.executors import Barrier, HashAggExecutor
    from risingwave_tpu.executors.base import Epoch

    calls = (AggCall("count_star", None, "cnt"),)
    small = HashAggExecutor(
        ("k",), calls, {"k": jnp.int64}, capacity=1 << 6, out_cap=1 << 10
    )
    big = HashAggExecutor(
        ("k",), calls, {"k": jnp.int64}, capacity=1 << 12, out_cap=1 << 10
    )
    rng = np.random.default_rng(5)
    for _ in range(6):
        keys = rng.integers(0, 500, 100).astype(np.int64)
        chunk = StreamChunk.from_numpy({"k": keys}, 128)
        small.apply(chunk)
        big.apply(chunk)

    def snap(ex):
        outs = ex.on_barrier(Barrier(Epoch(0, 1)))
        d = {}
        for out in outs:
            o = out.to_numpy(with_ops=True)
            for i in range(len(o["__op__"])):
                d[int(o["k"][i])] = int(o["cnt"][i])
        return d

    assert small.table.capacity > (1 << 6)
    assert snap(small) == snap(big)


def test_float64_sum_precision():
    """FLOAT64 must really be f64 on device (r1 ADVICE): summing 10^6
    doubles stays within f64 tolerance of the numpy oracle."""
    from risingwave_tpu.executors import Barrier, HashAggExecutor
    from risingwave_tpu.executors.base import Epoch

    rng = np.random.default_rng(7)
    calls = (AggCall("sum", "x", "total"),)
    ex = HashAggExecutor(
        ("g",), calls, {"g": jnp.int64, "x": jnp.float64}, capacity=1 << 4
    )
    total = 0.0
    vals_all = []
    for _ in range(100):
        x = rng.uniform(0.1, 1e9, 10_000)
        vals_all.append(x)
        chunk = StreamChunk.from_numpy(
            {"g": np.zeros(10_000, np.int64), "x": x}, 1 << 14
        )
        ex.apply(chunk)
    outs = ex.on_barrier(Barrier(Epoch(0, 1)))
    got = None
    for out in outs:
        d = out.to_numpy(with_ops=True)
        if len(d["__op__"]):
            got = float(d["total"][-1])
    want = float(np.sum(np.concatenate(vals_all)))
    assert got == pytest.approx(want, rel=1e-12)


def test_int64_fingerprints_distinguish_high_bits():
    """int64 keys differing only above bit 31 must hash apart (r1
    weak #6: folded 32-bit lanes weakened fingerprints)."""
    from risingwave_tpu.ops.hashing import hash128

    base = np.int64(5)
    variants = np.array(
        [base + (np.int64(1) << s) for s in range(32, 63)], np.int64
    )
    keys = np.concatenate([[base], variants])
    h1, h2 = hash128((jnp.asarray(keys),))
    pairs = set(zip(np.asarray(h1).tolist(), np.asarray(h2).tolist()))
    assert len(pairs) == len(keys)  # no collisions among 32 variants


def test_bench_shape_stacked_scan_at_bench_capacity():
    """The BENCH's exact device shapes in the suite (VERDICT r2/r3: the
    r02 kernel-fault class only ever fired at bench scale): capacity
    2^16 agg state fed by stacked per-epoch scans in both agg modes."""
    import functools

    from risingwave_tpu.executors.hop_window import hop_step_fn
    from risingwave_tpu.array.chunk import stack_chunks

    q5 = build_q5_lite(capacity=1 << 16, state_cleaning=False)
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=10_000))
    pre = functools.partial(
        hop_step_fn,
        ts_col="date_time",
        size_ms=10_000,
        slide_ms=2_000,
        out_start="window_start",
    )
    total = 0
    for mode in ("reduce", "scan"):  # both bench agg modes
        for _ in range(2):
            chunks = []
            done = 0
            while done < 6_000:
                ev = gen.next_events(2048)
                done += 2048
                bid = ev["bid"]
                if bid and len(bid["auction"]):
                    chunks.append(
                        StreamChunk.from_numpy(
                            {
                                "auction": bid["auction"],
                                "date_time": bid["date_time"],
                            },
                            2048,
                        )
                    )
                    total += len(bid["auction"])
            q5.agg.apply_stacked(stack_chunks(chunks), pre=pre, mode=mode)
            q5.pipeline.barrier()
    assert total > 5_000
    assert q5.mview.snapshot()
