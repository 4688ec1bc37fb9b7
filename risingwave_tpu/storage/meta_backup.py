"""Meta store (catalog/DDL persistence) + cluster backup/restore.

Reference roles:
- meta store / catalog persistence (src/meta/src/storage/, sea-orm
  model_v2/): DDL survives restarts. Here the meta store is a DDL log
  + the session string dictionary, persisted as JSON blobs in the same
  object store as Hummock state (the reference uses etcd/SQL; ours
  rides the durability boundary that already exists);
- backup/restore (src/storage/backup/, backup_reader.rs): a backup is
  a SELF-CONTAINED prefix holding the meta snapshot, the version
  manifest, and every SST the manifest references — restorable into an
  empty store.

The dictionary is append-only (a code is a position and never moves),
so it is persisted as append-only SEGMENTS, one object per checkpoint
that has new strings::

    meta/strings/0000000000.json   {"first": 0,   "strings": [...]}
    meta/strings/0000000150.json   {"first": 150, "strings": [...]}
    meta/strings.json              legacy whole-list blob of an earlier
                                   tree: read as codes [0, n), never
                                   written

A segment is named by its first code, zero-padded: listing order is
code order, and the retry of a failed put writes the same name again
(puts are atomic). A checkpoint's cost is that of its new strings,
not of everything ingested so far. The write stays at checkpoint STAGE
time, on the barrier's thread (see ``DictionaryPersistor``); only
opening a store does O(dictionary) work: ``load_strings`` reads the
segments in order, fails on a gap, and merges them into one, so their
number is bounded by the barriers since the last start.

Restart flow (the reference's cluster bootstrap): replay the DDL log
with backfill/barriers suppressed (structure only), then
``runtime.recover()`` restores every executor's state from the last
committed epoch — tables, MVs, source offsets, dictionary.
"""

from __future__ import annotations

import json
from typing import List

from risingwave_tpu.integrity import StateCorruption
from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.storage.object_store import ObjectStore
from risingwave_tpu.trace import span

DDL_PATH = "meta/ddl.json"
STRINGS_PREFIX = "meta/strings/"
LEGACY_STRINGS_PATH = "meta/strings.json"
BACKUP_PREFIX = "backup"


def _segment_path(first: int) -> str:
    # ten digits hold every int32 code
    return f"{STRINGS_PREFIX}{first:010d}.json"


class MetaStore:
    """Durable DDL log + dictionary segments."""

    def __init__(self, store: ObjectStore):
        self.store = store
        self._ddl: List[str] = []
        if store.exists(DDL_PATH):
            self._ddl = json.loads(store.read(DDL_PATH))

    def append_ddl(self, sql: str) -> None:
        self._ddl.append(sql)
        self.store.put(DDL_PATH, json.dumps(self._ddl).encode())

    def ddl(self) -> List[str]:
        return list(self._ddl)

    def append_strings(self, first: int, strings: List[str]) -> int:
        """Persist codes ``[first, first + len(strings))`` as the
        segment named by ``first``; bytes written."""
        with span("dictionary.json", strings=len(strings)):
            blob = json.dumps({"first": first, "strings": strings}).encode()
        with span("dictionary.put", wait="io", bytes=len(blob)):
            self.store.put(_segment_path(first), blob)
        return len(blob)

    def load_strings(self) -> List[str]:
        """The persisted dictionary in code order: the legacy blob as
        the base, then every segment, each starting where the strings
        read so far end. A gap raises ``StateCorruption`` (shifted codes
        would silently change every VARCHAR in committed state); what a
        segment repeats of the strings already read is skipped (a merge
        that did not finish leaves such segments).

        More than one object read is merged into one (opening a store
        is the only place that does O(dictionary) work): the merged
        segment is put first, so a crash before the deletes leaves only
        objects it covers."""
        out: List[str] = []
        paths = []
        if self.store.exists(LEGACY_STRINGS_PATH):
            out = json.loads(self.store.read(LEGACY_STRINGS_PATH))
            paths.append(LEGACY_STRINGS_PATH)
        for path in self.store.list(STRINGS_PREFIX):
            seg = json.loads(self.store.read(path))
            first = seg["first"]
            if first > len(out):
                raise StateCorruption(
                    path,
                    "dictionary_gap",
                    f"no segment holds the codes [{len(out)}, {first})",
                    expected=len(out),
                    actual=first,
                )
            out.extend(seg["strings"][len(out) - first :])
            paths.append(path)
        if len(paths) > 1:
            self.append_strings(0, out)
            for path in paths:
                if path != _segment_path(0):
                    self.store.delete(path)
        return out


from risingwave_tpu.storage.state_table import Checkpointable


class DictionaryPersistor(Checkpointable):
    """Aux state object: persists the session dictionary's new strings
    at checkpoint STAGE time, on the barrier's thread — strictly BEFORE
    the manifest that references their codes becomes durable
    (persisting after the commit left a crash window where committed
    state held codes the persisted dictionary lacked). A segment
    persisted ahead of a failed commit is harmless: extra codes decode
    nothing, and after a restore the next new string takes the code
    after them, where the next segment starts.

    Opening restores the persisted strings into ``strings`` (empty
    until then: a code is a position)."""

    def __init__(self, strings, meta: MetaStore):
        self.strings = strings
        self.meta = meta
        for s in meta.load_strings():
            strings.encode_one(s)
        self._persisted_len = len(strings)

    def checkpoint_table_ids(self):
        return ()

    def checkpoint_delta(self):
        first = self._persisted_len
        new = len(self.strings) - first
        if new:
            # strings / total_strings is the share of the dictionary
            # this checkpoint wrote
            with span(
                "checkpoint.dictionary",
                stage="checkpoint_stage.dictionary",
                new_strings=new,
            ) as sp:
                tail = self.strings.tail(first)
                nbytes = self.meta.append_strings(first, tail)
                sp.args.update(
                    strings=len(tail),
                    bytes=nbytes,
                    total_strings=first + len(tail),
                )
            REGISTRY.counter("checkpoint_dictionary_strings_total").inc(
                len(tail)
            )
            REGISTRY.counter("checkpoint_dictionary_bytes_total").inc(nbytes)
            # only after the put returned: a put that raised leaves the
            # next barrier to write the same segment name again
            self._persisted_len = first + len(tail)
        return []

    def state_digest(self) -> int:
        from risingwave_tpu.integrity import host_obj_digest

        return host_obj_digest(self.strings.dump())

    def restore_state(self, table_id, key_cols, value_cols):
        return None


# ---------------------------------------------------------------------------
# backup / restore
# ---------------------------------------------------------------------------


def create_backup(store: ObjectStore, backup_id: str) -> dict:
    """Copy the meta snapshot + current manifest + every referenced SST
    into ``backup/<id>/`` (self-contained; reference: meta snapshot
    backup, src/storage/backup/).

    Every SST is checksum-VERIFIED on the copy read: a faithfully
    copied corrupt SST makes the backup worthless, so a wrong byte
    fails the backup loudly (StateCorruption naming the artifact,
    which is also quarantined) instead of laundering the corruption
    into the backup prefix."""
    from risingwave_tpu.integrity import decode_manifest
    from risingwave_tpu.storage.state_table import (
        MANIFEST,
        verify_sst_entry,
    )

    manifest_paths = [
        p
        for p in store.list("")
        if p.endswith(MANIFEST)
        and not p.startswith(BACKUP_PREFIX + "/")
        # a backup must not recursively swallow older backups (their
        # manifests reference SSTs the live GC may have deleted)
    ]
    copied = []
    ssts = 0
    for mp in manifest_paths:
        raw = store.read(mp)
        # decode_manifest verifies the envelope crc (and unwraps the
        # format-2 payload); a torn/corrupt manifest fails the backup
        manifest = decode_manifest(raw, artifact=mp)
        dst = f"{BACKUP_PREFIX}/{backup_id}/{mp}"
        store.put(dst, raw)
        copied.append(mp)
        # version["tables"]: table_id -> [{"path", "epoch", "crc"}, ...]
        for entries in manifest.get("tables", {}).values():
            for e in entries:
                store.put(
                    f"{BACKUP_PREFIX}/{backup_id}/{e['path']}",
                    verify_sst_entry(store, e),
                )
                ssts += 1
    # the dictionary AFTER the manifests: its segments then hold every
    # code the copied manifests reference
    for p in (DDL_PATH, LEGACY_STRINGS_PATH, *store.list(STRINGS_PREFIX)):
        if store.exists(p):
            store.put(f"{BACKUP_PREFIX}/{backup_id}/{p}", store.read(p))
            copied.append(p)
    summary = {
        "backup_id": backup_id,
        "manifests": len(manifest_paths),
        "ssts": ssts,
        "meta": [p for p in copied if p.startswith("meta/")],
    }
    store.put(
        f"{BACKUP_PREFIX}/{backup_id}/BACKUP_META",
        json.dumps(summary).encode(),
    )
    return summary


def list_backups(store: ObjectStore) -> List[str]:
    out = []
    for p in store.list(BACKUP_PREFIX + "/"):
        if p.endswith("/BACKUP_META"):
            out.append(p.split("/")[1])
    return sorted(set(out))


def restore_backup(
    src: ObjectStore, backup_id: str, dst: ObjectStore
) -> int:
    """Materialize a backup into ``dst`` (typically an empty store for
    a fresh cluster). Returns blobs restored."""
    prefix = f"{BACKUP_PREFIX}/{backup_id}/"
    blobs = [p for p in src.list(prefix) if not p.endswith("BACKUP_META")]
    if not blobs:
        raise KeyError(f"unknown backup {backup_id!r}")
    for p in blobs:
        dst.put(p[len(prefix):], src.read(p))
    return len(blobs)
