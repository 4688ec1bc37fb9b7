"""CDC backfill — consistent snapshot + change-stream switchover.

Reference: src/stream/src/executor/backfill/cdc/ — ingesting an
external database table needs BOTH its existing rows (a pk-ordered
snapshot scan) and its ongoing change stream, without losing or
double-applying rows that change DURING the scan. The reference's
algorithm, kept intact here:

- scan the external table in pk order, chunk by chunk, tracking the
  backfill position (highest pk emitted);
- concurrently drain the change log: an event whose pk is <= the
  position applies (that region is already downstream); an event
  BEYOND the position drops — the later snapshot read returns the
  post-change row, so applying both would double-count;
- when the scan is exhausted, backfill is done and every change event
  flows.

Progress (pk position + change-log offset + done flag) is
checkpointable, so recovery resumes the scan exactly (reference keeps
per-table cdc progress state the same way).

TPU re-design note: the scan emits columnar chunks sized for the
device path; the pk-position comparison is host-side (the change log
is a host stream anyway — device work starts downstream).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np

from risingwave_tpu.array.chunk import StreamChunk
from risingwave_tpu.array.composite import encode_column
from risingwave_tpu.storage.state_table import Checkpointable, StateDelta
from risingwave_tpu.types import Schema


class ExternalTable:
    """The upstream database table surface the backfill scans
    (reference: the external table reader over JDBC/debezium). A
    snapshot read returns CURRENT rows with pk > from_pk, pk-ordered.
    """

    def __init__(self, schema: Schema, pk_col: str):
        self.schema = schema
        self.pk_col = pk_col
        self.rows: Dict[int, tuple] = {}  # pk -> full row tuple

    def upsert(self, row: Sequence) -> None:
        self.rows[int(row[self.schema.names.index(self.pk_col)])] = tuple(
            row
        )

    def delete(self, pk: int) -> None:
        self.rows.pop(int(pk), None)

    def snapshot_read(self, from_pk: Optional[int], limit: int):
        """Rows with pk > from_pk in pk order (live state — the
        reference reads each snapshot chunk at current time too)."""
        pks = sorted(k for k in self.rows if from_pk is None or k > from_pk)
        take = pks[:limit]
        return [self.rows[k] for k in take], (take[-1] if take else None)


class CdcBackfillExecutor(Checkpointable):
    """(external table, change-log connector+parser) -> one combined
    chunk stream with the reference's backfill/stream merge rule."""

    def __init__(
        self,
        table: ExternalTable,
        log_connector,  # SplitEnumerator & SplitReader (change events)
        change_parser,  # ChangeParser (e.g. DebeziumJsonParser)
        table_id: str = "cdc.backfill",
        strings=None,
    ):
        self.table = table
        self.connector = log_connector
        self.parser = change_parser
        self.table_id = table_id
        self.strings = strings
        self.schema = table.schema
        self._pk_idx = self.schema.names.index(table.pk_col)
        self.pk_pos: Optional[int] = None  # highest backfilled pk
        self.done = False
        self.offsets: Dict[str, int] = {}
        self._committed = (None, False, {})

    # -- polling -----------------------------------------------------------
    def _encode(self, rows, ops=None, capacity=1 << 12) -> List[StreamChunk]:
        out = []
        for at in range(0, len(rows), capacity):
            part = rows[at : at + capacity]
            lanes: Dict[str, np.ndarray] = {}
            nulls: Dict[str, np.ndarray] = {}
            for j, f in enumerate(self.schema.fields):
                cl, cn = encode_column(
                    f, [r[j] for r in part], self.strings
                )
                lanes.update(cl)
                if cn:
                    nulls.update(cn)
            ops_arr = (
                np.asarray(ops[at : at + capacity], np.int32)
                if ops is not None
                else None
            )
            out.append(
                StreamChunk.from_numpy(
                    lanes, capacity, ops=ops_arr, nulls=nulls or None
                )
            )
        return out

    def poll(
        self, snapshot_rows: int = 1024, capacity: int = 1 << 12
    ) -> List[StreamChunk]:
        """One round: a snapshot batch (while backfilling) + the change
        log drained under the merge rule."""
        out: List[StreamChunk] = []
        if not self.done:
            rows, last = self.table.snapshot_read(
                self.pk_pos, snapshot_rows
            )
            if rows:
                out.extend(self._encode(rows, capacity=capacity))
                self.pk_pos = last
            else:
                self.done = True  # scan exhausted: pure streaming now
        # change log: apply events in the backfilled region only
        for split in self.connector.list_splits():
            sid = split.split_id
            raw, new_off = self.connector.read(
                split, self.offsets.get(sid, 0), 1 << 16
            )
            pairs = [
                p for r in raw for p in self.parser.parse_changes(r)
            ]
            keep_rows, keep_ops = [], []
            for op, row in pairs:
                pk = row[self._pk_idx]
                if not self.done and (
                    self.pk_pos is None
                    or pk is None
                    or int(pk) > self.pk_pos
                ):
                    # beyond the backfill frontier: the snapshot will
                    # (or did not yet) cover this pk — drop the event
                    continue
                keep_rows.append(row)
                keep_ops.append(op)
            if keep_rows:
                out.extend(
                    self._encode(keep_rows, keep_ops, capacity=capacity)
                )
            self.offsets[sid] = new_off
        return out

    # -- integrity ---------------------------------------------------------
    def state_digest(self) -> int:
        """Durable logical state: backfill cursor + upstream offsets."""
        from risingwave_tpu.integrity import host_obj_digest

        return host_obj_digest(
            {
                "pk_pos": self.pk_pos,
                "done": self.done,
                "offsets": dict(self.offsets),
            }
        )

    # -- checkpoint --------------------------------------------------------
    def checkpoint_delta(self) -> List[StateDelta]:
        cur = (self.pk_pos, self.done, dict(self.offsets))
        if cur == self._committed:
            return []
        self._committed = cur
        sids = sorted(self.offsets)
        n = 1 + len(sids)
        return [
            StateDelta(
                self.table_id,
                {"k": np.arange(n, dtype=np.int64)},
                {
                    "pos": np.asarray(
                        [-1 if self.pk_pos is None else self.pk_pos]
                        + [self.offsets[s] for s in sids],
                        np.int64,
                    ),
                    "done": np.asarray(
                        [int(self.done)] + [0] * len(sids), np.int64
                    ),
                    "sid": np.asarray(
                        [-1] + [int(s) for s in sids], np.int64
                    ),
                },
                np.zeros(n, bool),
                ("k",),
            )
        ]

    def _pull_delta(self) -> List[StateDelta]:
        return self.checkpoint_delta()

    def restore_state(self, table_id, key_cols, value_cols) -> None:
        if not key_cols:
            return
        order = np.argsort(np.asarray(key_cols["k"]))
        pos = np.asarray(value_cols["pos"])[order]
        done = np.asarray(value_cols["done"])[order]
        sid = np.asarray(value_cols["sid"])[order]
        self.pk_pos = None if int(pos[0]) < 0 else int(pos[0])
        self.done = bool(done[0])
        self.offsets = {
            str(int(s)): int(p) for s, p in zip(sid[1:], pos[1:])
        }
        self._committed = (self.pk_pos, self.done, dict(self.offsets))
