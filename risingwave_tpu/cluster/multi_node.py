"""Multi-compute-node cluster: vnode-sharded fragments across N
node processes.

Reference: the multi-CN deployment — HashDataDispatcher crossing node
boundaries over the exchange service (src/stream/src/executor/
dispatch.rs:683 + src/compute/src/rpc/service/exchange_service.rs) with
the meta barrier manager driving every node's control stream
(proto/stream_service.proto InjectBarrier broadcast).

Engine mapping: each compute node runs the SAME DDL and owns the rows
whose DISTRIBUTION-column hash lands on it (``node = hash(dist) %
n``) — the cross-host half of the hash exchange happens at the
meta/frontend role, which splits every pushed chunk by the same
stable hash the storage layer uses, pushes each slice down its node's
wire, and injects barriers on ALL nodes per epoch. With the
distribution column equal to the MV's group/pk key (the reference's
distribution-key contract), per-node MVs hold DISJOINT keys and a
batch query is the concatenation of the nodes' results.

Each node keeps its own state dir (object store); kill -9 of any node
recovers independently through the single-node replay protocol
(cluster/client.py) while the other nodes keep their state.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence

import numpy as np

from risingwave_tpu.cluster.client import ComputeClient
from risingwave_tpu.trace import span
from risingwave_tpu.event_log import EVENT_LOG
from risingwave_tpu.resilience import (
    CircuitBreaker,
    CircuitOpenError,
    RetryPolicy,
)
from risingwave_tpu.storage.sstable import key_hashes

#: a node death during push/barrier is transient at the CLUSTER level:
#: recovery respawns it. ConnectionError/OSError = the wire died.
_NODE_TRANSIENT = (ConnectionError, OSError)


class ShardedClusterClient:
    """The meta/frontend role over N compute nodes."""

    def __init__(
        self,
        clients: Sequence[ComputeClient],
        recover_retry: Optional[RetryPolicy] = None,
    ):
        if not clients:
            raise ValueError("need at least one compute node")
        self.nodes: List[ComputeClient] = list(clients)
        # recover-and-retry budget per node death: a node that cannot
        # come back inside the deadline surfaces instead of wedging the
        # barrier forever (respawn itself can transiently fail)
        self.recover_retry = recover_retry or RetryPolicy.from_env(
            max_attempts=3,
            base_backoff_s=0.2,
            max_backoff_s=2.0,
            deadline_s=60.0,
            classify=lambda e: isinstance(e, _NODE_TRANSIENT),
        )
        # per-node breaker: a node that dies-and-fails-recovery
        # repeatedly opens its breaker, and the cluster fails fast on
        # the next barrier instead of burning a full recover budget
        # per epoch against a husk
        self.node_breakers: List[CircuitBreaker] = [
            CircuitBreaker.from_env(f"node{i}")
            for i in range(len(self.nodes))
        ]
        self.dist: Dict[str, str] = {}  # table/MV -> distribution column
        # MVs whose key does NOT contain their base's distribution
        # column: each node holds a PARTIAL group, so concatenating
        # per-node results duplicates groups — query() must refuse
        # instead of silently returning wrong rows
        self._unsafe_mv: Dict[str, str] = {}  # mv -> reason

    @classmethod
    def spawn(cls, n_nodes: int, state_dirs: Sequence[str]):
        if len(state_dirs) != n_nodes:
            raise ValueError("one state dir per node")
        return cls([ComputeClient.spawn(state_dir=d) for d in state_dirs])

    # -- DDL (broadcast) -------------------------------------------------
    def ddl(self, sql: str, distributed_by: Optional[str] = None) -> str:
        """Run DDL on EVERY node. ``distributed_by`` names the routing
        column for a CREATE TABLE (the reference's distribution key);
        MVs grouping/keying by that column then shard exactly."""
        tags = {self.nodes[i].ddl(sql) for i in range(len(self.nodes))}
        if len(tags) != 1:
            raise RuntimeError(f"nodes disagree on DDL: {tags}")
        tag = next(iter(tags))
        if distributed_by is not None:
            m = re.match(r"(?is)^\s*create\s+table\s+(\w+)", sql)
            if not m:
                raise ValueError("distributed_by applies to CREATE TABLE")
            self.dist[m.group(1)] = distributed_by
        self._classify_mv(sql)
        EVENT_LOG.record("ddl", tag=tag, sql=sql.strip()[:200], scope="cluster")
        return tag

    def _classify_mv(self, sql: str) -> None:
        """Track whether a CREATE MATERIALIZED VIEW's key preserves its
        base's distribution column. Groups sharded by a column in their
        GROUP BY stay node-local (the reference's distribution-key
        contract); an MV grouping by anything else holds PARTIAL groups
        per node, and scatter-gather reads would duplicate them."""
        m = re.match(
            r"(?is)^\s*create\s+materialized\s+view\s+(\w+)\s+as\s+(.*)$",
            sql,
        )
        if not m:
            return
        mv, select = m.group(1), m.group(2)
        # re-creating an MV re-classifies it from scratch — a stale
        # unsafe/dist entry from a dropped namesake must not stick
        self._unsafe_mv.pop(mv, None)
        self.dist.pop(mv, None)
        fm = re.search(r"(?is)\bfrom\s+(?:hop\s*\(\s*(\w+)|(\w+))", select)
        if not fm:
            return
        base = fm.group(1) or fm.group(2)
        base_dist = self.dist.get(base)
        if base_dist is None:
            if base in self._unsafe_mv:
                # MV over an already-unsafe MV inherits the problem
                self._unsafe_mv[mv] = f"builds on unsafe MV {base!r}"
            return
        gm = re.search(
            r"(?is)\bgroup\s+by\s+(.+?)(?:\bhaving\b|\border\s+by\b|;|$)",
            select,
        )
        if gm is None:
            # row-preserving MV: rows stay on the node their base row
            # hashed to — concatenation is exact, contract carries over
            self.dist[mv] = base_dist
            return
        group_cols = {c.strip().lower() for c in gm.group(1).split(",")}
        if base_dist.lower() in group_cols:
            self.dist[mv] = base_dist
        else:
            self._unsafe_mv[mv] = (
                f"key ({', '.join(sorted(group_cols))}) does not contain "
                f"{base!r}'s distribution column {base_dist!r}"
            )

    # -- data (hash-routed) ----------------------------------------------
    def push_chunk(
        self, table: str, cols: Dict[str, np.ndarray], capacity: int
    ) -> None:
        dcol = self.dist.get(table)
        if dcol is None:
            raise KeyError(
                f"table {table!r} has no distribution column (pass "
                "distributed_by= at CREATE TABLE)"
            )
        n = len(next(iter(cols.values())))
        if n == 0:
            return
        dest = (
            key_hashes([np.asarray(cols[dcol])])
            % np.uint64(len(self.nodes))
        ).astype(np.int64)
        for i, node in enumerate(self.nodes):
            m = dest == i
            if not m.any():
                continue
            part = {k: np.asarray(v)[m] for k, v in cols.items()}
            try:
                if node.sock is None:  # killed: socket torn down
                    raise ConnectionError("node down")
                node.push_chunk(table, part, capacity)
            except _NODE_TRANSIENT as e:
                # the chunk was never acked, so it is NOT in the
                # replay buffer: recover the node (which replays its
                # pending chunks), then re-push this one
                self._recover_node(
                    i, node, e,
                    lambda: node.push_chunk(table, part, capacity),
                )

    def _recover_node(self, i: int, node: ComputeClient, cause, fn):
        """Shared death handling for push/barrier: ONE ``recovery``
        event per death, then recover+retry bounded by the policy's
        deadline, gated by the node's breaker."""
        br = self.node_breakers[i]
        if not br.allow():
            raise CircuitOpenError(
                f"node{i} breaker is open (repeated failed recoveries); "
                f"last cause: {cause!r}"
            )
        EVENT_LOG.record("recovery", mode="node", node=i, cause=repr(cause))

        def attempt():
            node.recover()
            return fn()

        def on_retry(exc, n):
            # counts every TRANSIENT failure (incl. the giveup's last
            # attempt) — semantic errors (ComputeError) bypass on_retry
            # and must never poison the breaker: the node is alive
            br.record_failure()

        out = self.recover_retry.run(
            attempt, op="node.recover", on_retry=on_retry
        )
        br.record_success()
        return out

    def barrier(self) -> List[int]:
        """One epoch across the cluster: every node collects + commits
        its barrier (the meta barrier manager's broadcast). A DEAD node
        recovers in place — respawn from its durable state, replay its
        un-durable chunks (client.recover) — while the other nodes'
        state is untouched; the barrier then retries on that node,
        bounded by the recover policy's deadline and the node breaker."""
        epochs = []
        for i, node in enumerate(self.nodes):
            # per-node barrier RTT: the cross-node half of the epoch's
            # stage attribution (wire + that node's full commit)
            with span("node.commit", stage="node_commit", fragment=f"node{i}"):
                try:
                    if node.sock is None:  # killed: socket torn down
                        raise ConnectionError("node down")
                    epochs.append(node.barrier())
                except _NODE_TRANSIENT as e:
                    epochs.append(
                        self._recover_node(i, node, e, node.barrier)
                    )
        return epochs

    # -- reads (scatter-gather) -------------------------------------------
    def query(
        self, sql: str, order_by: Optional[str] = None, desc: bool = False
    ) -> Dict[str, list]:
        """Run the SELECT on every node and concatenate — exact when
        the MV's key is the distribution column (disjoint shards).
        ``order_by`` re-establishes a global order at the merge (the
        per-node ORDER BY only orders within a shard)."""
        fm = re.search(r"(?is)\bfrom\s+(\w+)", sql)
        if fm and fm.group(1) in self._unsafe_mv:
            # concatenating partial groups would silently return
            # duplicated-group results — refuse loudly instead
            raise ValueError(
                f"cannot scatter-gather query MV {fm.group(1)!r}: "
                f"{self._unsafe_mv[fm.group(1)]}. Re-create the MV "
                "grouping by the distribution column, or query the "
                "nodes individually and merge groups yourself."
            )
        merged: Dict[str, list] = {}
        for node in self.nodes:
            out = node.query(sql)
            for k, v in out.items():
                merged.setdefault(k, []).extend(v)
        if order_by is not None and merged:
            order = np.argsort(
                np.asarray(merged[order_by]), kind="stable"
            )
            if desc:
                order = order[::-1]
            merged = {k: [v[i] for i in order] for k, v in merged.items()}
        return merged

    # -- failure injection / lifecycle ------------------------------------
    def kill9(self, i: int) -> None:
        self.nodes[i].kill9()

    def close(self) -> None:
        for node in self.nodes:
            try:
                node.close()
            except Exception:
                pass
