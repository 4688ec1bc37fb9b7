"""Dispatch counters: device-dispatch / transfer accounting per
executor, Perfetto export (named threads, epoch flows), stall-dump
fallback and device forensics."""

import json
import os
import threading
import time

import pytest

from risingwave_tpu.connectors.nexmark import NexmarkConfig, NexmarkGenerator
from risingwave_tpu.event_log import EVENT_LOG
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.profiler import PROFILER, device_forensics
from risingwave_tpu.queries.nexmark_q import build_q5_lite
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.storage.object_store import MemObjectStore


@pytest.fixture(autouse=True)
def _clean():
    yield
    PROFILER.disable()
    PROFILER.reset()
    EVENT_LOG.clear()


def _rt_with_q5():
    rt = StreamingRuntime(MemObjectStore(), async_checkpoint=False)
    q5 = build_q5_lite(capacity=1 << 12, state_cleaning=False)
    rt.register("q5", q5.pipeline)
    return rt, q5


def _steady_chunk(events=2_000):
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=50_000))
    return gen.next_chunks(events, 1 << 11)["bid"].select(
        ["auction", "date_time"]
    )


# ---------------------------------------------------------------------------
# attribution
# ---------------------------------------------------------------------------


def test_dispatch_and_transfer_counters():
    """Kernel interposer: jitted-kernel calls land in
    device_dispatches_total{executor} with per-kernel detail; the
    barrier's staged-scalar materialization counts as a d2h transfer."""
    rt, q5 = _rt_with_q5()
    bid = _steady_chunk()
    rt.push("q5", bid)
    rt.barrier()
    PROFILER.reset()
    PROFILER.enable()
    rt.push("q5", bid)
    rt.barrier()
    PROFILER.disable()
    counts = PROFILER.dispatch_counts()
    assert counts.get("HashAggExecutor", 0) >= 1
    kernels = PROFILER.kernel_counts()
    assert any(k.startswith("_agg") for k in kernels), kernels
    # finish_scalars runs jax.device_get at the barrier fence
    assert PROFILER.transfer_counts()["d2h"] >= 1
    # disable restores the patched kernels (no proxies left behind)
    import risingwave_tpu.executors.hash_agg as hash_agg_mod
    from risingwave_tpu.profiler import _KernelProxy

    assert not isinstance(hash_agg_mod._agg_step, _KernelProxy)


def test_dispatch_counts_deterministic_and_flat_in_steady_state():
    """Same seeded workload, fresh pipeline: identical per-epoch
    dispatch counts across runs, and flat across steady epochs (ties
    into the zero-recompile steady-state contract)."""
    bid = _steady_chunk()

    def run_once():
        q5 = build_q5_lite(capacity=1 << 12, state_cleaning=False)
        q5.pipeline.push(bid)
        q5.pipeline.barrier()  # warm: compiles + first flush
        PROFILER.reset()
        PROFILER.enable()
        per_epoch = []
        for _ in range(3):
            base = PROFILER.total_dispatches()
            q5.pipeline.push(bid)
            q5.pipeline.barrier()
            per_epoch.append(PROFILER.total_dispatches() - base)
        PROFILER.disable()
        return per_epoch

    a, b = run_once(), run_once()
    assert a == b, (a, b)
    assert len(set(a)) == 1, f"steady-state dispatch count drifted: {a}"

def test_enabled_profiler_counts_and_holds_no_clock():
    """An armed PROFILER leaves REGISTRY with its three counters and
    no histogram of its own (time is ``trace.span``'s), and ``disable``
    leaves no counting proxy on any module or on ``jax``."""
    import sys

    import jax

    from risingwave_tpu.profiler import _KernelProxy

    rt, q5 = _rt_with_q5()
    bid = _steady_chunk()
    rt.push("q5", bid)
    rt.barrier()
    get, put = jax.device_get, jax.device_put
    hists = set(REGISTRY.histograms)
    PROFILER.reset()
    PROFILER.enable()
    assert jax.device_get is not get and jax.device_put is not put
    rt.push("q5", bid)
    rt.barrier()
    PROFILER.disable()
    for c in (
        "device_dispatches_total",
        "device_dispatch_kernels_total",
        "host_device_transfers_total",
    ):
        assert c in REGISTRY.counters, c
    assert set(REGISTRY.histograms) == hists
    assert jax.device_get is get and jax.device_put is put
    proxies = [
        (name, attr)
        for name, mod in sys.modules.items()
        if name.startswith("risingwave_tpu") and mod is not None
        for attr, v in vars(mod).items()
        if isinstance(v, _KernelProxy)
    ]
    assert proxies == []
    assert set(PROFILER.snapshot()) == {
        "enabled", "dispatches", "kernels", "transfers",
    }


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------


def test_chrome_trace_thread_names_fragment_lanes_and_epoch_flows():
    """Satellite: stable tids + thread_name metadata (actor names show
    in Perfetto), fragments on distinct pid lanes, and flow events
    linking one barrier's spans across actor threads."""
    from risingwave_tpu.runtime.graph import FragmentSpec, GraphRuntime
    from risingwave_tpu.trace import TRACER

    TRACER.clear()
    q5 = build_q5_lite(capacity=1 << 12, state_cleaning=False)
    g = GraphRuntime(
        [
            FragmentSpec("src", lambda i: []),
            FragmentSpec(
                "agg",
                lambda i: list(q5.pipeline.executors),
                inputs=[("src", 0)],
            ),
        ]
    ).start()
    try:
        c = _steady_chunk(1_000)
        g.inject_chunk("src", c)
        g.inject_barrier()
        g.inject_barrier()
    finally:
        g.stop(timeout=5.0)
    doc = json.loads(TRACER.chrome_trace())
    evs = doc["traceEvents"]
    # named actor threads via ph:"M" metadata
    tnames = {
        e["args"]["name"]
        for e in evs
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert any(n.startswith("actor-") for n in tnames), tnames
    # fragments get their own pid lanes, named via process_name
    pnames = {
        e["args"]["name"]: e["pid"]
        for e in evs
        if e["ph"] == "M" and e["name"] == "process_name"
    }
    assert "host" in pnames
    frag_lanes = {k: v for k, v in pnames.items() if k.startswith("fragment:")}
    assert len(frag_lanes) >= 2  # src#0 + agg#0 lanes
    assert len(set(frag_lanes.values())) == len(frag_lanes)
    # epoch flow events: one barrier = one flow id across >1 thread
    flows = [e for e in evs if e["ph"] in ("s", "t") and e.get("cat") == "epoch"]
    assert flows, "no epoch flow events"
    by_id = {}
    for e in flows:
        by_id.setdefault(e["id"], []).append(e)
    linked = [fl for fl in by_id.values() if len(fl) >= 2]
    assert linked, by_id
    assert any(
        len({(e["pid"], e["tid"]) for e in fl}) >= 2 for fl in linked
    ), "flow never crosses a thread"
    # exactly one flow-start per epoch
    for fl in by_id.values():
        assert sum(1 for e in fl if e["ph"] == "s") == 1


def test_stable_tids_no_collisions_across_threads():
    from risingwave_tpu.trace import TRACER, span

    TRACER.clear()

    def work(name):
        with span(f"unit.{name}"):
            time.sleep(0.01)

    ts = [
        threading.Thread(target=work, args=(i,), name=f"unit-worker-{i}")
        for i in range(3)
    ]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    doc = json.loads(TRACER.chrome_trace())
    spans = [
        e for e in doc["traceEvents"]
        if e["ph"] == "X" and e["name"].startswith("unit.")
    ]
    tids = {e["tid"] for e in spans}
    assert len(tids) == 3  # one stable tid per thread, no collisions
    named = {
        e["tid"]: e["args"]["name"]
        for e in doc["traceEvents"]
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    for tid in tids:
        assert named.get(tid, "").startswith("unit-worker-")


# ---------------------------------------------------------------------------
# forensics
# ---------------------------------------------------------------------------


def test_stall_dump_falls_back_to_tempdir(tmp_path, monkeypatch):
    """Satellite: RW_STALL_DIR unwritable no longer returns "" silently
    — the dump lands in the system temp dir and the failure is event-
    logged; a writable dir still takes precedence."""
    from risingwave_tpu.epoch_trace import dump_stalls

    # a FILE as the stall dir: os.path.join(file, name) cannot open
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("x")
    monkeypatch.setenv("RW_STALL_DIR", str(blocker))
    EVENT_LOG.clear()
    path = dump_stalls("unit: unwritable dir")
    try:
        assert path, "fallback did not produce an artifact"
        import tempfile

        assert os.path.dirname(path) == tempfile.gettempdir()
        assert json.loads(open(path).read())["reason"].startswith("unit")
        fb = EVENT_LOG.events(kind="stall_dump_fallback")
        assert fb and fb[-1]["path"] == path
        assert EVENT_LOG.events(kind="stall_dump")[-1]["path"] == path
    finally:
        if path and os.path.exists(path):
            os.remove(path)
    # the writable path still lands where asked, no fallback event
    monkeypatch.setenv("RW_STALL_DIR", str(tmp_path))
    EVENT_LOG.clear()
    path2 = dump_stalls("unit: writable dir")
    assert os.path.dirname(path2) == str(tmp_path)
    assert not EVENT_LOG.events(kind="stall_dump_fallback")


def test_device_forensics_shape():
    d = device_forensics()
    assert d["platform"] == "cpu"
    assert "memory_stats" in d and "live_arrays" in d
    assert "state_tables" in d and "profiler" in d
