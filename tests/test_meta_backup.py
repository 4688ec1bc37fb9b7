"""Meta persistence (DDL log + dictionary) and backup/restore.

Reference: meta store (src/meta/src/storage/), cluster bootstrap
(barrier/recovery.rs:353), backup (src/storage/backup/).
"""

import json

import pytest

from risingwave_tpu.array.dictionary import StringDictionary
from risingwave_tpu.frontend.session import SqlSession
from risingwave_tpu.integrity import StateCorruption
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.runtime import StreamingRuntime
from risingwave_tpu.sql import Catalog
from risingwave_tpu.storage.meta_backup import (
    LEGACY_STRINGS_PATH,
    STRINGS_PREFIX,
    DictionaryPersistor,
    MetaStore,
    create_backup,
    list_backups,
    restore_backup,
)
from risingwave_tpu.storage.object_store import MemObjectStore
from risingwave_tpu.trace import TRACER


def _seed_session(store):
    rt = StreamingRuntime(store)
    s = SqlSession(Catalog({}), rt)
    s.execute("CREATE TABLE pay (uid BIGINT, name VARCHAR, amt BIGINT)")
    s.execute(
        "CREATE MATERIALIZED VIEW spend AS "
        "SELECT uid, sum(amt) AS total FROM pay GROUP BY uid"
    )
    s.execute(
        "INSERT INTO pay VALUES (1, 'alice', 10), (2, 'bob', 20), "
        "(1, 'alice', 5)"
    )
    rt.wait_checkpoints()
    return s, rt


def test_session_restore_replays_ddl_and_recovers_state():
    store = MemObjectStore()
    s1, rt1 = _seed_session(store)
    out, _ = s1.execute("SELECT uid, total FROM spend ORDER BY uid")
    want = (list(out["uid"]), list(out["total"]))

    # cold restart: fresh runtime + session from the same store
    rt2 = StreamingRuntime(store)
    s2 = SqlSession.restore(rt2)
    out, _ = s2.execute("SELECT uid, total FROM spend ORDER BY uid")
    assert (list(out["uid"]), list(out["total"])) == want

    # varchar codes survived: string columns decode identically and
    # NEW inserts of old strings reuse old codes
    out, _ = s2.execute("SELECT uid, name FROM pay ORDER BY uid")
    assert set(out["name"]) == {"alice", "bob"}
    s2.execute("INSERT INTO pay VALUES (3, 'alice', 7)")
    out, _ = s2.execute(
        "SELECT uid, amt FROM pay WHERE name = 'alice' ORDER BY uid"
    )
    assert list(out["uid"]) == [1, 1, 3]

    # and the stream keeps flowing into the recovered MV
    out, _ = s2.execute("SELECT uid, total FROM spend ORDER BY uid")
    assert list(out["total"]) == [15, 20, 7]


def test_restore_does_not_double_count_via_backfill():
    """Replayed CREATE MV must not snapshot-backfill (recovery restores
    its state): rows would double otherwise."""
    store = MemObjectStore()
    s1, rt1 = _seed_session(store)
    rt2 = StreamingRuntime(store)
    s2 = SqlSession.restore(rt2)
    out, _ = s2.execute("SELECT total FROM spend ORDER BY total")
    assert list(out["total"]) == [15, 20]  # not [30, 40]


def test_backup_restore_into_empty_store():
    src = MemObjectStore()
    s1, rt1 = _seed_session(src)
    summary = create_backup(src, "b1")
    assert summary["ssts"] > 0
    assert list_backups(src) == ["b1"]

    dst = MemObjectStore()
    restore_backup(src, "b1", dst)
    rt = StreamingRuntime(dst)
    s = SqlSession.restore(rt)
    out, _ = s.execute("SELECT uid, total FROM spend ORDER BY uid")
    assert list(out["total"]) == [15, 20]

    with pytest.raises(KeyError):
        restore_backup(src, "nope", dst)


def test_backup_survives_post_backup_writes():
    """The backup is a SNAPSHOT: later writes to the live store do not
    leak in (self-contained prefix)."""
    src = MemObjectStore()
    s1, rt1 = _seed_session(src)
    create_backup(src, "b1")
    s1.execute("INSERT INTO pay VALUES (9, 'eve', 99)")
    rt1.wait_checkpoints()

    dst = MemObjectStore()
    restore_backup(src, "b1", dst)
    s = SqlSession.restore(StreamingRuntime(dst))
    out, _ = s.execute("SELECT uid FROM pay ORDER BY uid")
    assert 9 not in list(out["uid"])


# ---------------------------------------------------------------------------
# the dictionary as append-only segments (meta/strings/<first code>.json)
# ---------------------------------------------------------------------------


def _insert(session, batch, rows):
    """One INSERT = one checkpointing barrier adding ``rows`` names."""
    session.execute(
        "INSERT INTO pay VALUES "
        + ", ".join(f"({batch * 100 + i}, 'n{batch}_{i}', 1)" for i in range(rows))
    )


def _segmented_session(store, batches=(2, 3, 4, 5)):
    """A session whose every barrier adds strings — and, between
    barriers, a literal only a SELECT's typecheck encodes."""
    rt = StreamingRuntime(store)
    s = SqlSession(Catalog({}), rt)
    s.execute("CREATE TABLE pay (uid BIGINT, name VARCHAR, amt BIGINT)")
    for k, rows in enumerate(batches):
        _insert(s, k, rows)
        s.execute(f"SELECT uid FROM pay WHERE name = 'lit{k}'")
    rt.wait_checkpoints()
    return s, rt


def _names(session):
    out, _ = session.execute("SELECT uid, name FROM pay ORDER BY uid")
    return list(zip((int(u) for u in out["uid"]), out["name"]))


def _segments(store):
    return {
        p: json.loads(store.read(p)) for p in store.list(STRINGS_PREFIX)
    }


def _dictionary_spans(since):
    return [
        sp.args
        for sp in TRACER.spans()
        if sp.name == "checkpoint.dictionary" and sp.sid > since
    ]


def test_a_checkpoint_writes_its_new_strings_not_the_dictionary():
    """The regression test for the O(dictionary) write: what barrier k
    puts tracks barrier k's new strings while the total keeps growing."""
    since = max((sp.sid for sp in TRACER.spans()), default=0)
    strings_total = REGISTRY.counter("checkpoint_dictionary_strings_total")
    bytes_total = REGISTRY.counter("checkpoint_dictionary_bytes_total")
    before = strings_total.total(), bytes_total.total()
    batches = (2, 3, 4, 5, 2)
    store = MemObjectStore()
    s, _ = _segmented_session(store, batches)
    spans = _dictionary_spans(since)
    # barrier k: its rows' names + the literal the SELECT before it added
    want = [rows + (k > 0) for k, rows in enumerate(batches)]
    assert [a["new_strings"] for a in spans] == want
    assert [a["strings"] for a in spans] == want
    totals = [sum(want[: k + 1]) for k in range(len(want))]
    assert [a["total_strings"] for a in spans] == totals
    segs = _segments(store)
    assert [len(store.read(p)) for p in sorted(segs)] == [
        a["bytes"] for a in spans
    ]
    # fewer new strings cost fewer bytes, however many came before them
    assert want[-1] < want[-2] and spans[-1]["bytes"] < spans[-2]["bytes"]
    assert strings_total.total() - before[0] == totals[-1]
    assert bytes_total.total() - before[1] == sum(a["bytes"] for a in spans)
    # over the run the counter is the dictionary's growth (the last
    # literal waits for the next barrier)
    assert len(s.strings) == totals[-1] + 1


def test_segment_is_durable_when_stage_returns():
    """Durability ordering: the put of an epoch's segment has returned
    before ``CheckpointManager.stage`` does, hence before the manifest
    that references its codes can be written."""
    store = MemObjectStore()
    s, rt = _segmented_session(store, (2,))
    assert rt.checkpoint_frequency == 1
    seen = []
    stage = rt.mgr.stage

    def staged(executors):
        out = stage(executors)
        seen.append((len(s.strings), _segments(store)))
        return out

    rt.mgr.stage = staged
    _insert(s, 7, 3)
    rt.wait_checkpoints()
    ((total, segs),) = seen
    durable = [t for p in sorted(segs) for t in segs[p]["strings"]]
    assert len(durable) == total and durable == s.strings.dump()


def test_b_restore_over_segments_keeps_every_code():
    store = MemObjectStore()
    s1, _ = _segmented_session(store)
    assert len(_segments(store)) == 4
    assert any("lit1" in seg["strings"] for seg in _segments(store).values())
    want, codes = _names(s1), {t: c for c, t in enumerate(s1.strings.dump())}

    s2 = SqlSession.restore(StreamingRuntime(store))
    assert _names(s2) == want
    persisted = s2.strings.dump()
    assert persisted == s1.strings.dump()[: len(persisted)]
    for t in ("n0_0", "lit0", "n2_3", "lit2", "n3_4"):
        assert s2.strings.encode_one(t) == codes[t]
    # (g) the open merged what it read: one segment, the same strings
    (merged,) = _segments(store).values()
    assert merged == {"first": 0, "strings": persisted}
    # and the next checkpoint appends where the merged one ends
    _insert(s2, 8, 2)
    s2.runtime.wait_checkpoints()
    assert sorted(seg["first"] for seg in _segments(store).values()) == [
        0, len(persisted),
    ]
    s3 = SqlSession.restore(StreamingRuntime(store))
    assert _names(s3) == want + [(800, "n8_0"), (801, "n8_1")]


def test_c_segment_durable_manifest_not_committed():
    """Crash between the segment's put and the manifest's: restore
    decodes committed state, the extra codes decode nothing, new strings
    come after them and the next segment is contiguous."""
    live = MemObjectStore()
    s1, rt1 = _segmented_session(live, (2, 3, 4))
    want = _names(s1)
    crashed = MemObjectStore()
    for p in live.list(""):
        crashed.put(p, live.read(p))
    _insert(s1, 5, 3)  # a further epoch: segment, SSTs, manifest
    rt1.wait_checkpoints()
    (extra,) = set(_segments(live)) - set(_segments(crashed))
    crashed.put(extra, live.read(extra))  # ... of which the segment survived

    s2 = SqlSession.restore(StreamingRuntime(crashed))
    assert _names(s2) == want
    extras = len(s2.strings)
    assert s2.strings.dump() == s1.strings.dump()[:extras]
    assert s2.strings.encode_one("after the crash") == extras
    _insert(s2, 6, 2)
    s2.runtime.wait_checkpoints()
    assert sorted(seg["first"] for seg in _segments(crashed).values()) == [
        0, extras,
    ]
    s3 = SqlSession.restore(StreamingRuntime(crashed))
    assert _names(s3) == want + [(600, "n6_0"), (601, "n6_1")]
    assert s3.strings.dump() == s2.strings.dump()


class _PutFailsOnce(MemObjectStore):
    def __init__(self):
        super().__init__()
        self.fail = False

    def put(self, path, data):
        if self.fail and path.startswith(STRINGS_PREFIX):
            self.fail = False
            raise OSError(f"disk full: {path}")
        super().put(path, data)


def test_d_failed_put_is_rewritten_under_the_same_name():
    store = _PutFailsOnce()
    strings = StringDictionary()
    persistor = DictionaryPersistor(strings, MetaStore(store))
    strings.encode(["a", "b"])
    persistor.checkpoint_delta()
    strings.encode(["c", "d"])
    store.fail = True
    with pytest.raises(OSError):
        persistor.checkpoint_delta()
    assert sorted(seg["first"] for seg in _segments(store).values()) == [0]
    strings.encode(["e"])
    persistor.checkpoint_delta()
    persistor.checkpoint_delta()  # nothing new: nothing written
    segs = _segments(store)
    assert [segs[p] for p in sorted(segs)] == [
        {"first": 0, "strings": ["a", "b"]},
        {"first": 2, "strings": ["c", "d", "e"]},
    ]
    assert MetaStore(store).load_strings() == ["a", "b", "c", "d", "e"]


ABCDEF = ["a", "b", "c", "d", "e", "f"]


@pytest.mark.parametrize(
    "legacy, segments, want_objects",
    [
        pytest.param(None, [], [], id="empty-store"),
        pytest.param(
            None, [(0, ABCDEF[:2]), (2, ABCDEF[2:3]), (3, ABCDEF[3:])],
            [STRINGS_PREFIX + "0000000000.json"], id="base-and-tail",
        ),
        pytest.param(
            ABCDEF, [], [LEGACY_STRINGS_PATH], id="f-legacy-alone-stays",
        ),
        pytest.param(
            ABCDEF[:3], [(3, ABCDEF[3:5]), (5, ABCDEF[5:])],
            [STRINGS_PREFIX + "0000000000.json"], id="f-legacy-base-and-tail",
        ),
        pytest.param(
            None, [(0, ABCDEF), (2, ABCDEF[2:3]), (3, ABCDEF[3:])],
            [STRINGS_PREFIX + "0000000000.json"], id="g-left-mid-merge",
        ),
        pytest.param(
            ABCDEF[:3], [(0, ABCDEF), (3, ABCDEF[3:5]), (5, ABCDEF[5:])],
            [STRINGS_PREFIX + "0000000000.json"],
            id="g-left-mid-merge-legacy-not-yet-deleted",
        ),
    ],
)
def test_load_reads_in_code_order_and_merges(legacy, segments, want_objects):
    store = MemObjectStore()
    if legacy is not None:
        store.put(LEGACY_STRINGS_PATH, json.dumps(legacy).encode())
    for first, strings in segments:
        MetaStore(store).append_strings(first, strings)
    want = ABCDEF if (legacy is not None or segments) else []
    assert MetaStore(store).load_strings() == want
    assert store.list("meta/") == want_objects
    # what the merge left loads to the same dictionary, and a session
    # over it appends where it ends
    assert MetaStore(store).load_strings() == want
    strings = StringDictionary()
    persistor = DictionaryPersistor(strings, MetaStore(store))
    assert strings.dump() == want
    strings.encode_one("g")
    persistor.checkpoint_delta()
    assert _segments(store)[STRINGS_PREFIX + f"{len(want):010d}.json"] == {
        "first": len(want), "strings": ["g"],
    }
    assert MetaStore(store).load_strings() == want + ["g"]


def test_e_missing_segment_fails_loudly():
    store = MemObjectStore()
    s1, _ = _segmented_session(store)
    paths = sorted(_segments(store))
    lost = json.loads(store.read(paths[1]))
    store.delete(paths[1])
    with pytest.raises(StateCorruption) as exc:
        SqlSession.restore(StreamingRuntime(store))
    lo, hi = lost["first"], lost["first"] + len(lost["strings"])
    assert f"[{lo}, {hi})" in str(exc.value)
    assert exc.value.artifact == paths[2]
    # nothing was merged or deleted on the way to the error
    assert sorted(_segments(store)) == [paths[0], *paths[2:]]


def test_f_store_written_by_an_earlier_tree_restores():
    """``meta/strings.json`` alone, as the parent commit wrote it."""
    store = MemObjectStore()
    s1, _ = _segmented_session(store)
    want = _names(s1)
    whole = MetaStore(store).load_strings()
    for p in store.list(STRINGS_PREFIX):
        store.delete(p)
    store.put(LEGACY_STRINGS_PATH, json.dumps(whole).encode())

    s2 = SqlSession.restore(StreamingRuntime(store))
    assert _names(s2) == want and s2.strings.dump() == whole
    _insert(s2, 9, 1)
    s2.runtime.wait_checkpoints()
    # the legacy blob is the base and is not written again
    assert json.loads(store.read(LEGACY_STRINGS_PATH)) == whole
    assert list(_segments(store).values()) == [
        {"first": len(whole), "strings": ["n9_0"]}
    ]
    s3 = SqlSession.restore(StreamingRuntime(store))
    assert _names(s3) == want + [(900, "n9_0")]
    assert not store.exists(LEGACY_STRINGS_PATH)
    assert list(_segments(store).values()) == [
        {"first": 0, "strings": whole + ["n9_0"]}
    ]


def test_h_backup_round_trips_a_segmented_dictionary():
    src = MemObjectStore()
    s1, _ = _segmented_session(src)
    want = _names(s1)
    summary = create_backup(src, "b1")
    assert sorted(p for p in summary["meta"] if p.startswith(STRINGS_PREFIX)) == (
        sorted(_segments(src))
    )
    assert len(_segments(src)) == 4  # a backup reads, it does not merge

    dst = MemObjectStore()
    restore_backup(src, "b1", dst)
    s2 = SqlSession.restore(StreamingRuntime(dst))
    assert _names(s2) == want
    assert s2.strings.dump() == MetaStore(src).load_strings()
