"""Meta/frontend-side client for a compute-node process.

Plays the reference's meta + frontend roles against one CN
(src/meta/src/barrier/rpc.rs:247 inject over the control stream;
src/rpc_client/ typed clients): drives DDL, streams chunks with permit
flow control, ticks the barrier clock, and — on compute death — drives
recovery: respawn, let the node restore from the shared store, then
replay every chunk not covered by the last committed epoch
(barrier/recovery.rs:353 + exact source-offset resume).
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
from typing import Dict, List, Optional, Tuple

from risingwave_tpu.cluster import wire
from risingwave_tpu.resilience import RetryPolicy


class ComputeError(RuntimeError):
    """The node rejected a request (application error, NOT a death)."""


#: connect retries: every OSError is transient here (the node is
#: booting; refusal/reset/timeout all mean "not up YET") — bounded by
#: the policy's deadline, the former fixed 50x100ms spin generalized
_CONNECT_POLICY = RetryPolicy(
    max_attempts=60,
    base_backoff_s=0.05,
    max_backoff_s=0.5,
    deadline_s=15.0,
    classify=lambda e: isinstance(e, OSError),
)


class ComputeClient:
    def __init__(self, port: int, proc: Optional[subprocess.Popen] = None,
                 state_dir: Optional[str] = None,
                 env: Optional[dict] = None):
        self.port = port
        self.proc = proc
        self.state_dir = state_dir
        self.env = dict(env or {})  # reproduced on recovery respawns
        self.sock: Optional[socket.socket] = None
        # client-side varchar lanes encode through ONE dictionary (the
        # session-side mirror); the wire itself carries strings
        from risingwave_tpu.array.dictionary import StringDictionary

        self._strings = StringDictionary()
        # replay buffer: [(sealing_epoch | None, table, cols, cap)] —
        # entries get their sealing epoch at the next barrier; entries
        # whose epoch is <= the node's committed frontier are durable
        # and fall out (the exact-offset-resume contract, client side)
        self._pending: List[Tuple[Optional[int], str, dict, int]] = []
        # crash-during-barrier disambiguation: if the node dies between
        # committing and replying, the restored frontier tells us
        # whether the in-flight barrier sealed the epoch-None entries
        self._last_committed = 0
        self._barrier_inflight = False

    # -- lifecycle -------------------------------------------------------
    @classmethod
    def spawn(
        cls, state_dir: str, port: int = 0, env: Optional[dict] = None
    ) -> "ComputeClient":
        proc = subprocess.Popen(
            [
                sys.executable,
                "-m",
                "risingwave_tpu.cluster.compute_node",
                "--port",
                str(port),
                "--state-dir",
                state_dir,
                "--device",
                "cpu",
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL,
            text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})},
        )
        line = proc.stdout.readline().strip()
        if not line.startswith("LISTENING"):
            raise RuntimeError(f"compute node failed to start: {line!r}")
        client = cls(int(line.split()[1]), proc, state_dir, env=env)
        client.connect()
        return client

    def connect(self, policy: Optional[RetryPolicy] = None) -> None:
        from risingwave_tpu.resilience import RetryBudgetExceeded

        def attempt():
            s = socket.create_connection(("127.0.0.1", self.port), 5)
            # RPC replies can lag behind jit compiles on the node
            # (~tens of seconds cold): generous per-op timeout, not
            # the connect timeout
            s.settimeout(300)
            self.sock = s

        try:
            (policy or _CONNECT_POLICY).run(attempt, op="node.connect")
        except RetryBudgetExceeded as e:
            raise ConnectionError(
                f"cannot reach compute node :{self.port}"
            ) from e

    def kill9(self) -> None:
        """SIGKILL the node (chaos path)."""
        if self.proc is not None:
            self.proc.kill()
            self.proc.wait()
        if self.sock is not None:
            self.sock.close()
            self.sock = None

    def close(self) -> None:
        try:
            if self.sock is not None:
                wire.send_frame(self.sock, {"type": "shutdown"})
                wire.recv_frame(self.sock)
        except (ConnectionError, OSError):
            pass
        if self.proc is not None:
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.terminate()
        if self.sock is not None:
            self.sock.close()

    # -- RPC surface -----------------------------------------------------
    def _rpc(self, header: dict, payload: bytes = b""):
        wire.send_frame(self.sock, header, payload)
        reply, data = wire.recv_frame(self.sock)
        if reply.get("type") == "error":
            raise ComputeError(reply["message"])
        return reply, data

    def ddl(self, sql: str) -> str:
        reply, _ = self._rpc({"type": "ddl", "sql": sql})
        return reply["tag"]

    def push_chunk(self, table: str, cols: dict, capacity: int) -> None:
        """Send one chunk (numpy column dict; str/object lanes are
        VARCHAR and ship as Arrow strings). Flow control is the
        synchronous absorb-ack — a window of one chunk in flight (the
        reference's permit channels generalize this to a row budget)."""
        import numpy as np

        from risingwave_tpu.array.chunk import StreamChunk

        rows = len(next(iter(cols.values())))
        enc, dicts, nulls = {}, {}, {}
        for k, v in cols.items():
            a = np.asarray(v)
            if a.dtype.kind in ("U", "O"):
                vals = a.tolist()
                isnull = np.array([x is None for x in vals], bool)
                if isnull.any():
                    nulls[k] = isnull  # SQL NULL, not the string "None"
                enc[k] = self._strings.encode(
                    ["" if x is None else str(x) for x in vals]
                )
                dicts[k] = self._strings
            else:
                enc[k] = a
        chunk = StreamChunk.from_numpy(enc, capacity, nulls=nulls or None)
        reply, _ = self._rpc(
            {"type": "chunk", "table": table, "capacity": capacity,
             "rows": rows},
            wire.chunk_payload(chunk, dictionaries=dicts or None),
        )
        assert reply["type"] == "ack"
        self._pending.append((None, table, cols, capacity))

    def _replay(self, entries) -> None:
        """Re-push entries one at a time; each leaves the pending
        buffer only when its replacement is acked (``push_chunk``
        re-appends on ack) — a death mid-replay keeps the tail for the
        next ``recover()`` instead of silently discarding it."""
        for i, (_e, table, cols, capacity) in enumerate(entries):
            try:
                self.push_chunk(table, cols, capacity)
            except BaseException:
                self._pending.extend(entries[i:])
                raise

    def barrier(self, _retried: bool = False) -> int:
        self._barrier_inflight = True
        try:
            reply, _ = self._rpc({"type": "barrier"})
        except ComputeError:
            # the node REPLIED (it is alive) but the barrier errored —
            # the commit may or may not have landed. Reconcile against
            # the live frontier (the same disambiguation recover()
            # uses) so epoch-None entries a landed commit covered are
            # never replayed; if even status() fails, keep the
            # in-flight ambiguity for recover().
            try:
                committed = self.status()
            except (ComputeError, ConnectionError, OSError):
                committed = None
            if committed is not None:
                if committed > self._last_committed:
                    self._pending = [
                        p for p in self._pending if p[0] is not None
                    ]
                self._last_committed = committed
                self._barrier_inflight = False
            raise
        self._barrier_inflight = False
        committed = int(reply["committed"])
        if reply["type"] == "barrier_failed":
            # the node rolled a poisoned epoch back in place; ITS
            # chunks came from this wire, so WE replay everything the
            # frontier does not cover, then retry once
            self._last_committed = committed
            replay = [
                p
                for p in self._pending
                if p[0] is None or p[0] > committed
            ]
            self._pending = []
            self._replay(replay)
            if _retried:
                raise ComputeError("barrier rolled back twice")
            return self.barrier(_retried=True)
        sealed = int(reply["epoch"])
        self._last_committed = committed
        self._pending = [
            (e if e is not None else sealed, t, c, cap)
            for (e, t, c, cap) in self._pending
        ]
        self._pending = [
            p for p in self._pending if p[0] > committed
        ]
        return committed

    def query(self, sql: str) -> Dict[str, list]:
        reply, _ = self._rpc({"type": "query", "sql": sql})
        return reply.get("data", {})

    def status(self) -> int:
        reply, _ = self._rpc({"type": "status"})
        return int(reply["committed"])

    # -- recovery --------------------------------------------------------
    def recover(self) -> None:
        """Respawn a dead node; it restores DDL + state from the shared
        store on boot. Then replay exactly the chunks the restored
        commit frontier does not cover (kill -9 between a commit and
        its reply must not double-apply rows)."""
        if self.state_dir is None:
            raise RuntimeError("no state_dir to recover from")
        fresh = ComputeClient.spawn(self.state_dir, env=self.env)
        self.port, self.proc, self.sock = fresh.port, fresh.proc, fresh.sock
        frontier = self.status()
        if self._barrier_inflight and frontier > self._last_committed:
            # the node died AFTER committing the in-flight barrier but
            # BEFORE replying: the epoch-None entries are durable —
            # replaying them would double-apply their rows
            self._pending = [p for p in self._pending if p[0] is not None]
        self._barrier_inflight = False
        self._last_committed = frontier
        replay = [
            p for p in self._pending if p[0] is None or p[0] > frontier
        ]
        self._pending = []
        self._replay(replay)
