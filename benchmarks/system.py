"""The system under test, as the harness sees it: the only file of the
benchmark that imports the program.

It builds what ``python -m risingwave_tpu serve`` builds — a
checkpointing StreamingRuntime over a local object store, a SqlSession
in graph mode, a PgServer — runs the configuration's DDL, and routes
host columns into the streams' fragments the way an INSERT is routed
(``session.dml._targets``), one chunk per call, under the runtime lock.
"""

from __future__ import annotations

import numpy as np


class System:
    def __init__(self, config: dict, capacity: int, chunk_rows: int,
                 state_dir: str, vocab: dict, text: set):
        from risingwave_tpu.frontend import PgServer, SqlSession
        from risingwave_tpu.runtime import StreamingRuntime
        from risingwave_tpu.runtime.fused_step import fusion_refusals
        from risingwave_tpu.sql import Catalog
        from risingwave_tpu.storage.object_store import LocalFsObjectStore

        g = config["guarantees"]
        fusion_refusals(clear=True)
        self.config, self.chunk_rows = config, chunk_rows
        # a deployment whose reads may not overlap its writes (see the
        # configuration's probe.during_feed) has its readers held off,
        # by the runtime's own lock, from an epoch's first push to its
        # barrier's return
        self._hold_readers = not config["probe"].get("during_feed", True)
        self.runtime = StreamingRuntime(
            LocalFsObjectStore(state_dir),
            barrier_interval_ms=g["barrier_interval_ms"],
            checkpoint_frequency=g["checkpoint_frequency"],
        )
        self.session = SqlSession(
            Catalog({}),
            self.runtime,
            capacity=capacity,
            exec_mode=config["session"]["exec_mode"],
        )
        self.pg = None
        try:
            for sql in config["ddl"] + config["mv_sql"]:
                self.session.execute(sql)
            self.pg = PgServer(self.session, port=0).start()
        except BaseException:
            self.close()
            raise
        self.port = self.pg.port
        # VARCHAR columns arrive as indices into the generator's
        # vocabularies, whose codes the session's dictionary gives once,
        # or as free text, which goes through the dictionary row by row
        # as the DML route's encode_rows sends an INSERT's strings
        self._text = {k for k in text if k[0] in config["streams"]}
        self._codes = {
            key: np.asarray(self.session.strings.encode(words), np.int32)
            for key, words in vocab.items()
            if key[0] in config["streams"]
        }
        self._schemas = {
            s: self.session.catalog.tables[s] for s in config["streams"]
        }

    def describe(self) -> dict:
        """What the planner built, for the configuration's ``expects``."""
        from risingwave_tpu.runtime.fused_step import (
            fused_fragments,
            fusion_refusals,
        )

        return {
            "fragments": list(self.runtime.fragments),
            "fused": {
                name: fused_fragments(self.runtime.fragments[name])["count"]
                for name in self.config["mv_names"]
            },
            "fusion_refusals": fusion_refusals(),
        }

    def push(self, stream: str, cols: dict, rows: int) -> None:
        from risingwave_tpu.array.chunk import StreamChunk

        cols = dict(cols)
        for (s, col), codes in self._codes.items():
            if s == stream:
                cols[col] = codes[cols[col]]
        for s, col in self._text:
            if s == stream:
                cols[col] = self.session.strings.encode(cols[col])
        chunk = StreamChunk.from_numpy(
            cols, self.chunk_rows, schema=self._schemas[stream]
        )
        with self.runtime.lock:
            for frag, side in self.session.dml._targets.get(stream, ()):
                self.runtime.push(frag, chunk, side)

    def begin_epoch(self) -> None:
        if self._hold_readers:
            self.runtime.lock.acquire()

    def end_epoch(self) -> None:
        if self._hold_readers:
            self.runtime.lock.release()

    def barrier(self):
        """Blocking barrier; hands back the runtime's EpochTrace, whose
        upload and commit stages the checkpoint worker fills in later."""
        self.runtime.barrier()
        return self.runtime.last_epoch_trace

    def wait_durable(self):
        """(newest committed epoch, newest barrier's epoch) once the
        checkpoint lane is empty."""
        self.runtime.wait_checkpoints()
        return self.runtime.mgr.max_committed_epoch, self.runtime.epoch

    def state_nbytes(self) -> int:
        return int(self.runtime.state_nbytes())

    def dictionary_strings(self) -> int:
        """Distinct strings the session's host-side dictionary holds:
        where the records' free text lives (the device has its codes)."""
        return len(self.session.strings)

    def close(self) -> None:
        if self.pg is not None:
            self.pg.shutdown()
            self.pg = None
        self.session.close()
        # graph pipelines own actor threads: a process that exits with
        # one still open aborts inside the XLA client teardown
        for p in self.runtime.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()
