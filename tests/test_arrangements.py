"""Shared arrangements + serving tier (runtime/arrangements.py, PR 12).

Covers the registry lifecycle end to end: attach/refcount/free at the
DDL boundary, the device-state census returning to baseline after
DROP (the leak regression this PR fixed — which is also the
refcount-zero free proof), snapshot-consistent versioned reads under
a concurrent writer (never torn: every labeled read is bit-identical
to the quiesced state at that barrier), owner-fragment recovery with
live subscribers, kill-9 + restore staging shared state once, the
seeded concurrent CREATE/DROP/query stress, multi-tenant compile
sharing via lifted constants, and the rwlint sharing report.
"""

import gc
import threading
import time

import jax
import numpy as np
import pytest

from risingwave_tpu.frontend.session import SqlSession
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.sql import Catalog


def _mk(exec_mode="serial", runtime=None, capacity=1 << 10):
    return SqlSession(
        Catalog({}),
        runtime,
        capacity=capacity,
        exec_mode=exec_mode,
        parallelism=1,
    )


MV_SQL = (
    "CREATE MATERIALIZED VIEW {name} AS "
    "SELECT k, count(*) AS c FROM t WHERE v > {thr} GROUP BY k"
)


def _base(s, rows=((1, 100), (2, 20), (1, 300), (3, 50))):
    s.execute("CREATE TABLE t (k BIGINT, v BIGINT)")
    vals = ", ".join(f"({k}, {v})" for k, v in rows)
    s.execute(f"INSERT INTO t VALUES {vals}")


def _cols(out):
    return {k: list(map(int, v)) for k, v in out.items()}


# ---------------------------------------------------------------------------
# attach / refcount / versioned reads
# ---------------------------------------------------------------------------


def test_identical_mvs_share_one_arrangement():
    s = _mk()
    _base(s)
    s.execute(MV_SQL.format(name="a", thr=10))
    frags_after_owner = set(s.runtime.fragments)
    for name in ("b", "c", "d"):
        s.execute(MV_SQL.format(name=name, thr=10))
    # subscribers register NO fragments, NO executors, NO device state
    assert set(s.runtime.fragments) == frags_after_owner
    st = s.runtime.arrangements.stats()
    assert st["arrangements"] == 1 and st["refs"] == 4
    # all four names answer identically, and track new data together
    s.execute("INSERT INTO t VALUES (2, 500)")
    outs = [
        _cols(s.execute(f"SELECT k, c FROM {n} ORDER BY k")[0])
        for n in ("a", "b", "c", "d")
    ]
    assert all(o == outs[0] for o in outs)
    assert outs[0] == {"k": [1, 2, 3], "c": [2, 2, 1]}


def test_different_literals_do_not_share_state():
    s = _mk()
    _base(s)
    s.execute(MV_SQL.format(name="a", thr=10))
    s.execute(MV_SQL.format(name="b", thr=250))
    st = s.runtime.arrangements.stats()
    assert st["arrangements"] == 2 and st["refs"] == 2
    a = _cols(s.execute("SELECT k, c FROM a ORDER BY k")[0])
    b = _cols(s.execute("SELECT k, c FROM b ORDER BY k")[0])
    assert a == {"k": [1, 2, 3], "c": [2, 1, 1]}
    assert b == {"k": [1], "c": [1]}


def test_share_fingerprint_components():
    from risingwave_tpu.runtime.arrangements import plan_share_fingerprint
    from risingwave_tpu.sql import parser as P

    s = _mk()
    _base(s)
    kw = dict(capacity=1 << 10, exec_mode="serial", parallelism=1)
    fp = lambda sql: plan_share_fingerprint(P.parse(sql), s.catalog, **kw)
    same = "CREATE MATERIALIZED VIEW x AS SELECT k, count(*) AS c FROM t WHERE v > 5 GROUP BY k"
    twin = "CREATE MATERIALIZED VIEW y AS SELECT k, count(*) AS c FROM t WHERE v > 5 GROUP BY k"
    other = "CREATE MATERIALIZED VIEW z AS SELECT k, count(*) AS c FROM t WHERE v > 6 GROUP BY k"
    assert fp(same) == fp(twin)  # the NAME is not part of the key
    assert fp(same) != fp(other)  # literal values ARE
    # unknown relation / UNION: conservatively unshareable
    assert fp("CREATE MATERIALIZED VIEW u AS SELECT q FROM nosuch") is None
    # capacity/exec knobs split the key (different lattice/plan shape)
    alt = plan_share_fingerprint(
        P.parse(same), s.catalog,
        capacity=1 << 12, exec_mode="serial", parallelism=1,
    )
    assert alt != fp(same)


def test_owner_drop_hands_off_then_refcount_zero_frees():
    s = _mk()
    _base(s)
    s.execute(MV_SQL.format(name="a", thr=10))
    s.execute(MV_SQL.format(name="b", thr=10))
    s.execute("DROP MATERIALIZED VIEW a")
    # the writer keeps streaming under an internal alias
    assert "a" not in s.runtime.fragments
    assert any(f.startswith("__arr") for f in s.runtime.fragments)
    s.execute("INSERT INTO t VALUES (7, 700)")
    b = _cols(s.execute("SELECT k, c FROM b ORDER BY k")[0])
    assert b["k"] == [1, 2, 3, 7]
    assert s.runtime.arrangements.refcount("b") == 1
    # last reference: everything frees, the names become reusable
    s.execute("DROP MATERIALIZED VIEW b")
    assert s.runtime.arrangements.stats()["arrangements"] == 0
    assert set(s.runtime.fragments) == {"t"}
    s.execute(MV_SQL.format(name="a", thr=10))
    a = _cols(s.execute("SELECT k, c FROM a ORDER BY k")[0])
    assert a["k"] == [1, 2, 3, 7]


def test_mv_on_attached_mv_routes_to_writer_fragment():
    s = _mk()
    _base(s)
    s.execute(MV_SQL.format(name="a", thr=10))
    s.execute(MV_SQL.format(name="b", thr=10))  # attached
    # an MV OVER the attached name subscribes to the writer fragment
    s.execute(
        "CREATE MATERIALIZED VIEW over_b AS "
        "SELECT k, c FROM b WHERE c > 1"
    )
    s.execute("INSERT INTO t VALUES (3, 500), (3, 600)")
    out = _cols(s.execute("SELECT k, c FROM over_b ORDER BY k")[0])
    assert out == {"k": [1, 3], "c": [2, 3]}
    # dropping the attached name over_b reads from must be refused
    # even while the arrangement has OTHER references (_subs never
    # carries the attached name — the alias-dependency map does)
    with pytest.raises(ValueError, match="depend"):
        s.execute("DROP MATERIALIZED VIEW b")
    # freeing the last arrangement reference would tear down the
    # writer fragment over_b rides: the drop must be refused — even
    # through a handoff rename — until the dependent MV is gone
    s.execute("DROP MATERIALIZED VIEW a")  # handoff (b still attached)
    with pytest.raises(ValueError, match="depend"):
        s.execute("DROP MATERIALIZED VIEW b")
    s.execute("DROP MATERIALIZED VIEW over_b")
    s.execute("DROP MATERIALIZED VIEW b")
    assert s.runtime.arrangements.stats()["arrangements"] == 0


# ---------------------------------------------------------------------------
# DROP leak audit (the refcount-zero free check)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("exec_mode", ["serial", "graph"])
def test_drop_mv_returns_live_array_census_to_baseline(exec_mode):
    """After DROP MATERIALIZED VIEW the device-state census must return
    to baseline: no executor (or actor thread, in graph mode) may keep
    HBM slabs reachable. The first create/drop cycle warms jit caches
    (compiled programs legitimately retain constants); later cycles
    must be leak-free."""
    s = _mk(exec_mode=exec_mode)
    _base(s)
    mk = lambda n: s.execute(MV_SQL.format(name=n, thr=10))
    drop = lambda n: s.execute(f"DROP MATERIALIZED VIEW {n}")
    mk("warm")
    s.execute("INSERT INTO t VALUES (5, 50)")
    drop("warm")
    gc.collect()
    baseline_arrays = len(jax.live_arrays())
    baseline_threads = threading.active_count()
    for cycle in range(2):
        mk("leakcheck")
        s.execute("INSERT INTO t VALUES (6, 60)")
        drop("leakcheck")
        gc.collect()
        assert len(jax.live_arrays()) <= baseline_arrays, (
            f"cycle {cycle}: live arrays grew past baseline "
            f"({len(jax.live_arrays())} > {baseline_arrays})"
        )
        # graph mode: actor threads must be reaped, not leaked
        assert threading.active_count() <= baseline_threads


def test_shared_drop_frees_exactly_at_zero_refs():
    """The census proof for arrangements: N attached MVs add ZERO
    device state, and dropping all of them frees the writer's state."""
    s = _mk()
    _base(s)
    s.execute(MV_SQL.format(name="warm", thr=10))
    s.execute("DROP MATERIALIZED VIEW warm")
    gc.collect()
    baseline = len(jax.live_arrays())
    base_bytes = s.runtime.state_nbytes()
    s.execute(MV_SQL.format(name="a", thr=10))
    gc.collect()
    owner_arrays = len(jax.live_arrays())
    owner_bytes = s.runtime.state_nbytes()
    for n in ("b", "c", "d", "e"):
        s.execute(MV_SQL.format(name=n, thr=10))
    gc.collect()
    # N structurally-identical MVs over one shared index hold ~1x the
    # device state of a single private MV (<=: the idle barriers run
    # by each CREATE let the bucket allocator's lazy shrink kick in)
    assert s.runtime.state_nbytes() <= owner_bytes
    # small slack: the attach-time idle barriers may shrink-rebuild
    # tables, and each fresh compiled program retains a few cached
    # constants — the accounted STATE equality above is the real claim
    assert len(jax.live_arrays()) <= owner_arrays + 6
    for n in ("a", "b", "c", "d", "e"):
        s.execute(f"DROP MATERIALIZED VIEW {n}")
    gc.collect()
    assert s.runtime.state_nbytes() <= base_bytes
    assert len(jax.live_arrays()) <= baseline


# ---------------------------------------------------------------------------
# snapshot consistency (never torn) + concurrency stress
# ---------------------------------------------------------------------------


def test_chaos_readers_never_observe_torn_snapshot():
    """Reader threads hammer versioned reads while a writer streams
    INSERT+barrier cycles: every read labeled with epoch E must be
    BIT-IDENTICAL to the owner MV quiesced at barrier E (the ground
    truth recorded under the runtime lock right after each barrier)."""
    s = _mk()
    _base(s)
    s.execute(MV_SQL.format(name="owner", thr=0))
    s.execute(MV_SQL.format(name="sub", thr=0))
    reader = s.runtime.arrangements.reader("sub")
    truth = {}  # epoch -> canonical rows
    truth_lock = threading.Lock()

    def canon(cols):
        ks = np.asarray(cols["k"])
        cs = np.asarray(cols["c"])
        return tuple(sorted(zip(ks.tolist(), cs.tolist())))

    owner_mv = s.runtime.arrangements._by_name["owner"].mview
    with s.runtime.lock:
        with truth_lock:
            truth[s.runtime.epoch] = canon(owner_mv.to_numpy())

    stop = threading.Event()
    failures = []
    checked = [0]

    def read_loop(seed):
        rng = np.random.default_rng(seed)
        while not stop.is_set():
            epoch, cols = reader.read_versioned()
            if epoch is None:
                continue  # interim (pre-barrier-aligned) snapshot
            got = canon(cols)
            with truth_lock:
                want = truth.get(epoch)
            if want is None:
                continue  # a barrier the writer has not recorded yet
            checked[0] += 1
            if got != want:
                failures.append((epoch, got, want))
                return
            if rng.random() < 0.05:
                time.sleep(0.001)

    threads = [
        threading.Thread(target=read_loop, args=(i,), daemon=True)
        for i in range(8)
    ]
    for t in threads:
        t.start()
    rng = np.random.default_rng(42)
    for i in range(30):
        k, v = int(rng.integers(0, 9)), int(rng.integers(1, 1000))
        with s.runtime.lock:
            s._execute_locked(f"INSERT INTO t VALUES ({k}, {v})")
            # ground truth AT this barrier, before the lock releases
            with truth_lock:
                truth[s.runtime.epoch] = canon(owner_mv.to_numpy())
    stop.set()
    for t in threads:
        t.join(timeout=30)
    assert not failures, f"torn/stale read: {failures[0]}"
    assert checked[0] > 0, "readers never validated a labeled snapshot"


def test_concurrent_create_drop_query_stress():
    """Seeded catalog/registry mutation under concurrent readers: DDL
    churn (CREATE/DROP of shared + private MVs) races pgwire-style
    readers and never corrupts the catalog, wedges a reader, or loses
    a refcount."""
    s = _mk()
    _base(s)
    s.execute(MV_SQL.format(name="stable0", thr=10))
    s.execute(MV_SQL.format(name="stable1", thr=10))  # shared reader
    stop = threading.Event()
    errors = []

    def read_loop(seed):
        rng = np.random.default_rng(seed)
        names = ["stable0", "stable1"]
        while not stop.is_set():
            name = names[int(rng.integers(0, len(names)))]
            try:
                out, tag = s.execute(f"SELECT k, c FROM {name} ORDER BY k")
                assert tag.startswith("SELECT")
                assert list(out) == ["k", "c"]
            except Exception as e:  # noqa: BLE001
                errors.append(repr(e))
                return

    readers = [
        threading.Thread(target=read_loop, args=(i,), daemon=True)
        for i in range(4)
    ]
    for t in readers:
        t.start()
    rng = np.random.default_rng(7)
    for i in range(12):
        thr = int(rng.integers(0, 3)) * 100
        s.execute(MV_SQL.format(name=f"churn{i}", thr=thr))
        s.execute(f"INSERT INTO t VALUES ({i % 5}, {thr + 1})")
        if i % 2:
            s.execute(f"DROP MATERIALIZED VIEW churn{i}")
            s.execute(f"DROP MATERIALIZED VIEW churn{i - 1}")
    stop.set()
    for t in readers:
        t.join(timeout=30)
    assert not errors, errors[0]
    assert s.runtime.arrangements.refcount("stable1") == 2
    # every churn MV dropped -> only the stable arrangement remains
    st = s.runtime.arrangements.stats()
    assert st["refs"] == 2


# ---------------------------------------------------------------------------
# recovery lifecycle
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_owner_crash_partial_recovery_keeps_subscribers(tmp_path):
    """Owner-fragment crash with live subscribers: the blast radius IS
    the shared write path, partial recovery restores + replays it, the
    subscribers re-serve off the recovered state, refcounts exact."""
    from risingwave_tpu.storage.object_store import LocalFsObjectStore

    rt = StreamingRuntime(
        LocalFsObjectStore(str(tmp_path)), auto_recover=True
    )
    s = _mk(exec_mode="graph", runtime=rt)
    _base(s)
    s.execute(MV_SQL.format(name="a", thr=10))
    s.execute(MV_SQL.format(name="b", thr=10))
    before = _cols(s.execute("SELECT k, c FROM b ORDER BY k")[0])

    # poison the owner's actor chain: next chunk kills the actor
    pipeline = rt.fragments["a"]
    victim = pipeline.graph.executors[0]
    real_apply = victim.apply
    fired = []

    def poison(chunk):
        if not fired:
            fired.append(1)
            raise RuntimeError("injected owner-fragment crash")
        return real_apply(chunk)

    victim.apply = poison
    s.execute("INSERT INTO t VALUES (8, 800)")  # dies mid-epoch
    # the barrier inside INSERT auto-recovered FRAGMENT-SCOPED: only
    # the owner's blast radius restored + replayed, and the replayed
    # epoch closes at the NEXT barrier (partial recovery's rejoin
    # boundary) — run one so the replayed row becomes visible
    assert rt.auto_recoveries >= 1
    assert rt.partial_recoveries >= 1, "recovery was not fragment-scoped"
    with rt.lock:
        rt.barrier()
    after = _cols(s.execute("SELECT k, c FROM b ORDER BY k")[0])
    assert after["k"] == before["k"] + [8]
    assert s.runtime.arrangements.refcount("b") == 2
    a = _cols(s.execute("SELECT k, c FROM a ORDER BY k")[0])
    assert a == after


def test_restore_after_kill9_stages_shared_state_once(tmp_path):
    """kill-9 + restore: the DDL log replays CREATE a; CREATE b (the
    attach), recovery restores the ONE copy of shared state, both
    names serve, refcounts exact. Staging never wrote a twin: every
    staged table_id is unique (the owner-tagged single copy)."""
    from risingwave_tpu.storage.object_store import LocalFsObjectStore

    store = LocalFsObjectStore(str(tmp_path))
    rt = StreamingRuntime(store)
    s = _mk(runtime=rt)
    _base(s)
    s.execute(MV_SQL.format(name="a", thr=10))
    s.execute(MV_SQL.format(name="b", thr=10))
    s.execute("INSERT INTO t VALUES (9, 900)")
    rt.wait_checkpoints()
    want = _cols(s.execute("SELECT k, c FROM b ORDER BY k")[0])
    # staging covered the shared arrangement exactly once
    staged = rt.mgr.stage(rt.executors())
    tids = [d.table_id for d in staged]
    assert len(tids) == len(set(tids))
    del s  # no clean shutdown — the kill-9 analogue

    rt2 = StreamingRuntime(LocalFsObjectStore(str(tmp_path)))
    s2 = SqlSession.restore(rt2, capacity=1 << 10)
    st = rt2.arrangements.stats()
    assert st["arrangements"] == 1 and st["refs"] == 2
    for n in ("a", "b"):
        out = _cols(s2.execute(f"SELECT k, c FROM {n} ORDER BY k")[0])
        assert out == want


# ---------------------------------------------------------------------------
# multi-tenant compile sharing (lifted constants)
# ---------------------------------------------------------------------------


def test_parameter_variants_share_fused_programs():
    """Structurally-identical fused plans with different literals share
    one compiled program: after the shape-combo set compiles, further
    parameter variants add ZERO jit cache entries."""
    from risingwave_tpu.runtime.fused_step import fused_cache_stats

    s = _mk(exec_mode="graph")
    _base(s)
    sizes = []
    for i, thr in enumerate((11, 23, 37, 41, 53)):
        s.execute(MV_SQL.format(name=f"p{i}", thr=thr))
        s.execute(f"INSERT INTO t VALUES (1, {thr + 1}), (2, 3)")
        stats = fused_cache_stats()
        sizes.append(stats["compiled_programs"])
    assert stats["plans_lifted"] >= 5
    # the last two parameter variants hit the shared executables
    assert sizes[4] == sizes[3] == sizes[2], sizes
    # and the results stay exact per variant: v > 53 keeps the base
    # rows (1,100) and (1,300) plus the final insert (1,54)
    out = _cols(s.execute("SELECT k, c FROM p4 ORDER BY k")[0])
    assert out == {"k": [1], "c": [3]}


def test_registration_storm_counts():
    """A CI-scale registration storm in graph mode: 48 CREATE MVs over
    3 plan-shape families build 3 arrangements holding 48 refs (every
    further CREATE attaches), compile a number of fused programs bounded
    by the families (at most 10), not by the MVs, and every subscriber
    reads what its family's owner reads — zero reader errors."""
    from risingwave_tpu.runtime.fused_step import fused_cache_stats

    mvs, families = 48, (10, 250, 500)
    s = _mk(exec_mode="graph")
    _base(s)
    cache0 = fused_cache_stats()["compiled_programs"]
    assert cache0 >= 0, "jit cache size unreadable"
    for i in range(mvs):
        s.execute(
            MV_SQL.format(name=f"storm{i}", thr=families[i % len(families)])
        )
    s.execute("INSERT INTO t VALUES (1, 999), (2, 1), (3, 260)")
    st = s.runtime.arrangements.stats()
    assert st["arrangements"] == len(families) and st["refs"] == mvs
    compiled = fused_cache_stats()["compiled_programs"] - cache0
    assert 0 <= compiled <= 10, compiled
    reads = [
        _cols(s.execute(f"SELECT k, c FROM storm{i} ORDER BY k")[0])
        for i in range(mvs)
    ]
    for i, got in enumerate(reads):
        assert got == reads[i % len(families)], f"storm{i}"
    assert reads[0] == {"k": [1, 2, 3], "c": [3, 1, 2]}
    assert reads[2] == {"k": [1], "c": [1]}


def test_lift_rejected_plans_fall_back_to_baked_literals():
    """RW_FUSED_LIFT=0 keeps the baked-literal behavior (the kill
    switch contract) — results identical, no lifted plans."""
    import os

    from risingwave_tpu.runtime.fused_step import fused_cache_stats

    prev = os.environ.get("RW_FUSED_LIFT")
    os.environ["RW_FUSED_LIFT"] = "0"
    try:
        s = _mk(exec_mode="graph")
        _base(s)
        lifted0 = fused_cache_stats()["plans_lifted"]
        s.execute(MV_SQL.format(name="nolift", thr=10))
        s.execute("INSERT INTO t VALUES (1, 999)")
        assert fused_cache_stats()["plans_lifted"] == lifted0
        out = _cols(s.execute("SELECT k, c FROM nolift ORDER BY k")[0])
        assert out == {"k": [1, 2, 3], "c": [3, 1, 1]}
    finally:
        if prev is None:
            os.environ.pop("RW_FUSED_LIFT", None)
        else:
            os.environ["RW_FUSED_LIFT"] = prev


# ---------------------------------------------------------------------------
# rwlint sharing report
# ---------------------------------------------------------------------------


def test_sharing_report_finds_q5_q5u_window_agg_index():
    from risingwave_tpu.analysis.sharing import run_sharing_report

    rep = run_sharing_report()
    assert rep["summary"]["plans"] >= 4
    agg_opps = [
        o
        for o in rep["opportunities"]
        if o["keys"] == ["auction", "window_start"]
        and any("agg" in t for t in o["tables"])
    ]
    assert agg_opps, "q5/q5u shared window-agg index not reported"
    assert {"q5", "q5u"} <= set(agg_opps[0]["plans"])
    # the would-share-but-for-lattice diagnostic class
    assert any(
        d["code"] == "RW-E703" for d in rep["diagnostics"]
    ), "lattice-mismatch diagnostic missing"
    assert all(
        d["severity"] == "warning"
        for d in rep["diagnostics"]
        if d["code"] == "RW-E703"
    )


def test_sharing_disabled_kill_switch():
    import os

    prev = os.environ.get("RW_SHARED_ARRANGEMENTS")
    os.environ["RW_SHARED_ARRANGEMENTS"] = "0"
    try:
        s = _mk()
        _base(s)
        s.execute(MV_SQL.format(name="a", thr=10))
        s.execute(MV_SQL.format(name="b", thr=10))
        # both built private pipelines: two fragments, no arrangements
        assert "a" in s.runtime.fragments and "b" in s.runtime.fragments
        assert s.runtime.arrangements.stats()["arrangements"] == 0
    finally:
        if prev is None:
            os.environ.pop("RW_SHARED_ARRANGEMENTS", None)
        else:
            os.environ["RW_SHARED_ARRANGEMENTS"] = prev
