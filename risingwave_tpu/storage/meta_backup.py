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

Restart flow (the reference's cluster bootstrap): replay the DDL log
with backfill/barriers suppressed (structure only), then
``runtime.recover()`` restores every executor's state from the last
committed epoch — tables, MVs, source offsets, dictionary.
"""

from __future__ import annotations

import json
from typing import List, Optional


from risingwave_tpu.metrics import REGISTRY
from risingwave_tpu.storage.object_store import ObjectStore
from risingwave_tpu.trace import span

DDL_PATH = "meta/ddl.json"
STRINGS_PATH = "meta/strings.json"
BACKUP_PREFIX = "backup"


class MetaStore:
    """Durable DDL log + dictionary snapshot."""

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

    def save_strings(self, dump: List[str]):
        """Persist the dictionary; (strings, bytes) written."""
        with span("dictionary.json", strings=len(dump)):
            blob = json.dumps(dump).encode()
        with span("dictionary.put", bytes=len(blob)):
            self.store.put(STRINGS_PATH, blob)
        return len(dump), len(blob)

    def load_strings(self) -> Optional[List[str]]:
        if not self.store.exists(STRINGS_PATH):
            return None
        return json.loads(self.store.read(STRINGS_PATH))


from risingwave_tpu.storage.state_table import Checkpointable


class DictionaryPersistor(Checkpointable):
    """Aux state object: persists the session dictionary at checkpoint
    STAGE time — strictly BEFORE the manifest that references its codes
    becomes durable (persisting after the commit left a crash window
    where committed state held codes the persisted dictionary lacked).
    A dictionary persisted ahead of a failed commit is harmless: extra
    codes decode nothing."""

    def __init__(self, strings, meta: MetaStore):
        self.strings = strings
        self.meta = meta
        self._persisted_len = 0

    def checkpoint_table_ids(self):
        return ()

    def checkpoint_delta(self):
        new = len(self.strings) - self._persisted_len
        if new:
            # the dictionary is written whole whenever a string is new:
            # the span says what that costs, by strings and bytes
            with span(
                "checkpoint.dictionary",
                stage="checkpoint_stage.dictionary",
                new_strings=new,
            ) as sp:
                with span("dictionary.dump"):
                    dump = self.strings.dump()
                n, nbytes = self.meta.save_strings(dump)
                sp.args.update(strings=n, bytes=nbytes)
            REGISTRY.counter("checkpoint_dictionary_strings_total").inc(n)
            REGISTRY.counter("checkpoint_dictionary_bytes_total").inc(nbytes)
            self._persisted_len = len(self.strings)
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
    for p in (DDL_PATH, STRINGS_PATH):
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
