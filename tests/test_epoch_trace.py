"""Barrier-lifecycle observability: EpochTrace stage attribution,
stall dumps (await-tree analogue) on wedged barriers, and the meta
event log (reference: src/utils/runtime tracing + await-tree dumps,
meta event_log.rs)."""

import glob
import json
import time

import pytest

from risingwave_tpu import utils_sync_point as sync_point
from risingwave_tpu.connectors.nexmark import NexmarkConfig, NexmarkGenerator
from risingwave_tpu.event_log import EVENT_LOG
from risingwave_tpu.queries.nexmark_q import build_q5_lite
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.storage.object_store import MemObjectStore


@pytest.fixture(autouse=True)
def _clean():
    EVENT_LOG.clear()
    yield
    sync_point.reset()


def _rt_with_q5(**kw):
    rt = StreamingRuntime(MemObjectStore(), async_checkpoint=False, **kw)
    q5 = build_q5_lite(capacity=1 << 12, state_cleaning=False)
    rt.register("q5", q5.pipeline)
    return rt, q5


def _push_epoch(rt, gen, events=2_000):
    c = gen.next_chunks(events, 1 << 11)["bid"]
    if c is not None:
        rt.push("q5", c.select(["auction", "date_time"]))


def test_epoch_trace_stage_sums_approx_wall_time():
    """Every barrier gets an EpochTrace whose per-stage attribution
    accounts for (most of) the barrier wall time — no large unexplained
    gap, no stage exceeding the wall it is part of."""
    rt, q5 = _rt_with_q5()
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=50_000))
    for _ in range(3):
        _push_epoch(rt, gen)
        rt.barrier()
    tr = rt.last_epoch_trace
    assert tr is not None and tr.checkpoint
    # the full lifecycle is attributed
    for stage in ("ingest", "dispatch", "checkpoint_stage", "upload",
                  "manifest_commit"):
        assert stage in tr.stages_ms, tr.stages_ms
    # ingest is charged to the epoch but happens BEFORE the barrier;
    # the in-barrier stages must sum to ≈ the barrier wall. A child
    # key ("parent.child") lies inside its parent's time and an actor's
    # sums ("actor_*.<actor>") run beside the barrier's thread: only
    # the top-level stages add up
    in_barrier = sum(
        v
        for k, v in tr.stages_ms.items()
        if k != "ingest" and "." not in k
    )
    assert in_barrier <= tr.wall_ms * 1.2 + 5.0
    assert in_barrier >= tr.wall_ms * 0.2  # attribution, not decoration
    assert tr.wall_ms > 0 and len(rt.epoch_traces) == 3
    # device telemetry: bytes moved are accounted and the roofline
    # fraction is a sane measured number
    assert tr.chunk_bytes > 0
    assert tr.hbm_bytes_touched >= tr.chunk_bytes
    assert 0.0 <= tr.achieved_bw_frac
    d = tr.to_dict()
    assert d["stages_ms"] and d["achieved_bw_frac"] == tr.achieved_bw_frac
    # the prometheus surface carries the same attribution
    from risingwave_tpu.epoch_trace import stage_breakdown

    bd = stage_breakdown()
    assert any("stage=dispatch" in k for k in bd)


def test_stall_dump_fires_on_injected_slow_barrier(tmp_path, monkeypatch):
    """The q7-wedge case: an actor held inside barrier processing makes
    the graph blow its collection deadline — the dump artifact must
    land BEFORE the epoch is abandoned and must name the stuck actor."""
    monkeypatch.setenv("RW_STALL_DIR", str(tmp_path))
    from risingwave_tpu.runtime.graph import FragmentSpec, GraphRuntime

    q5 = build_q5_lite(capacity=1 << 12, state_cleaning=False)
    g = GraphRuntime(
        [
            FragmentSpec("src", lambda i: []),
            FragmentSpec(
                "agg", lambda i: list(q5.pipeline.executors),
                inputs=[("src", 0)],
            ),
        ]
    ).start()
    try:
        gen = NexmarkGenerator(NexmarkConfig(first_event_rate=50_000))
        c = gen.next_chunks(1_000, 1 << 10)["bid"]
        g.inject_chunk("src", c.select(["auction", "date_time"]))
        g.inject_barrier()  # healthy epoch first
        sync_point.activate("actor_barrier:agg#0", lambda: time.sleep(1.5))
        with pytest.raises(TimeoutError, match="agg#0"):
            g.inject_barrier(timeout=0.4)
        dumps = sorted(glob.glob(str(tmp_path / "STALL_DUMP_*.json")))
        assert dumps, "no stall-dump artifact written"
        doc = json.loads(open(dumps[-1]).read())
        assert "agg#0" in doc["reason"]
        pend = list(doc["graph"]["epochs_pending"].values())
        assert pend and "agg#0" in pend[0]["stuck"]
        # the healthy actor collected; per-actor lag is attributable
        actors = {a["actor"]: a for a in doc["graph"]["actors"]}
        assert actors["src#0"]["last_collected_epoch"] > \
            actors["agg#0"]["last_collected_epoch"]
        # the dump is cluster history too
        assert EVENT_LOG.events(kind="stall_dump")
    finally:
        sync_point.reset()
        time.sleep(1.2)  # let the held actor wake before teardown
        g.stop(timeout=5.0)


def test_runtime_watchdog_dumps_on_deadline(tmp_path, monkeypatch):
    """The StreamingRuntime-side watchdog: a barrier exceeding its
    deadline produces an artifact while the barrier is still stuck."""
    monkeypatch.setenv("RW_STALL_DIR", str(tmp_path))
    rt, q5 = _rt_with_q5()
    rt.stall_dump_after_s = 0.15
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=50_000))
    _push_epoch(rt, gen)
    sync_point.activate("before_manifest_commit", lambda: time.sleep(0.5))
    rt.barrier()  # slow but completes; the watchdog fired mid-commit
    for _ in range(50):
        dumps = glob.glob(str(tmp_path / "STALL_DUMP_*.json"))
        if dumps:
            break
        time.sleep(0.05)
    assert dumps, "watchdog never dumped"
    doc = json.loads(open(dumps[-1]).read())
    assert "deadline" in doc["reason"]
    assert "q5" in doc["runtime"]["fragments"]
    # a healthy (fast) barrier must NOT dump: the timer is canceled
    sync_point.reset()
    for p in dumps:
        import os

        os.remove(p)
    rt.stall_dump_after_s = 5.0
    _push_epoch(rt, gen)
    rt.barrier()
    time.sleep(0.3)
    assert not glob.glob(str(tmp_path / "STALL_DUMP_*.json"))


def test_event_log_records_ddl_and_recovery():
    from risingwave_tpu.frontend.session import SqlSession
    from risingwave_tpu.sql import Catalog

    rt = StreamingRuntime(MemObjectStore(), async_checkpoint=False)
    sess = SqlSession(Catalog({}), rt)
    sess.execute("CREATE TABLE t (k BIGINT, v BIGINT)")
    sess.execute(
        "CREATE MATERIALIZED VIEW m AS "
        "SELECT k, count(*) AS c FROM t GROUP BY k"
    )
    ddl = EVENT_LOG.events(kind="ddl")
    assert [e["tag"] for e in ddl] == ["CREATE_TABLE",
                                      "CREATE_MATERIALIZED_VIEW"]
    assert "CREATE TABLE t" in ddl[0]["sql"]
    sess.execute("INSERT INTO t VALUES (1, 10), (2, 20)")
    rt.barrier()
    commits = EVENT_LOG.events(kind="barrier_commit")
    assert commits and commits[-1]["epoch"] == rt.epoch
    rt.recover()
    rec = EVENT_LOG.events(kind="recovery")
    assert rec and rec[-1]["mode"] == "restore"
    # ring bound: the log never grows past its capacity
    for i in range(EVENT_LOG._events.maxlen + 10):
        EVENT_LOG.record("noise", i=i)
    assert len(EVENT_LOG.events()) == EVENT_LOG._events.maxlen


def test_event_log_jsonl_spill(tmp_path):
    path = str(tmp_path / "events.jsonl")
    EVENT_LOG.set_spill(path)
    try:
        EVENT_LOG.record("ddl", tag="X")
        EVENT_LOG.record("recovery", mode="auto")
    finally:
        EVENT_LOG.set_spill(None)
    lines = [json.loads(l) for l in open(path)]
    assert [l["kind"] for l in lines] == ["ddl", "recovery"]


def test_sharded_query_guard_rejects_non_distribution_key_mv():
    """cluster/multi_node: an MV grouping by something other than the
    distribution column holds PARTIAL groups per node — query() must
    refuse instead of returning duplicated groups (VERDICT weak #5).
    Exercised against the classifier directly (no real nodes)."""
    from risingwave_tpu.cluster.multi_node import ShardedClusterClient

    cc = ShardedClusterClient.__new__(ShardedClusterClient)
    cc.nodes = [object()]  # never touched by the classifier
    cc.dist = {"bid": "auction"}
    cc._unsafe_mv = {}
    cc._classify_mv(
        "CREATE MATERIALIZED VIEW ok AS SELECT auction, count(*) AS c "
        "FROM bid GROUP BY auction"
    )
    assert cc.dist["ok"] == "auction" and "ok" not in cc._unsafe_mv
    cc._classify_mv(
        "CREATE MATERIALIZED VIEW bad AS SELECT bidder, count(*) AS c "
        "FROM bid GROUP BY bidder"
    )
    assert "bad" in cc._unsafe_mv
    with pytest.raises(ValueError, match="duplicated|distribution"):
        cc.query("SELECT bidder, c FROM bad")
    # an MV stacked on the unsafe one inherits the rejection
    cc._classify_mv(
        "CREATE MATERIALIZED VIEW worse AS SELECT bidder FROM bad"
    )
    assert "worse" in cc._unsafe_mv
    # row-preserving MV keeps the contract
    cc._classify_mv("CREATE MATERIALIZED VIEW rows AS SELECT * FROM bid")
    assert cc.dist["rows"] == "auction"
    # DROP + re-CREATE with a safe key must clear the stale refusal
    cc._classify_mv(
        "CREATE MATERIALIZED VIEW bad AS SELECT auction, count(*) AS c "
        "FROM bid GROUP BY auction"
    )
    assert "bad" not in cc._unsafe_mv and cc.dist["bad"] == "auction"
