"""Compute-node role: streaming fragments behind a TCP wire.

Reference: ``compute_node_serve`` (src/compute/src/server.rs:85) hosts
gRPC Task/Exchange/Stream services; barriers arrive over the meta
control stream (proto/stream_service.proto:116-122
StreamingControlStream) and data over ExchangeService.GetStream with
permit flow control (exchange_service.rs:78-146, permit.rs:35-90).

TPU build v0: ONE duplex TCP connection carries both streams as framed
messages (cluster/wire.py). DDL ships as SQL text (the reference ships
fragment-graph protos; SQL + deterministic planning reaches the same
actors — documented simplification). State persists to the SHARED
object store (``--state-dir``): a kill -9'd node restarts, replays the
DDL log, recovers from the last committed epoch, and the driver-side
client replays uncommitted chunks — the reference's recovery contract
(barrier/recovery.rs:353) across a real process boundary.

Run: ``python -m risingwave_tpu compute-node --port 0 --state-dir DIR``
(prints ``LISTENING <port>`` on stdout so a parent can connect).
"""

from __future__ import annotations

import os
import socket
import sys


def _build_session(state_dir: str):
    from risingwave_tpu.frontend.session import SqlSession
    from risingwave_tpu.runtime.runtime import StreamingRuntime
    from risingwave_tpu.sql import Catalog
    from risingwave_tpu.storage.meta_backup import DDL_PATH
    from risingwave_tpu.storage.object_store import LocalFsObjectStore

    store = LocalFsObjectStore(state_dir)
    runtime = StreamingRuntime(store)
    runtime.auto_recover = True
    if store.exists(DDL_PATH):
        session = SqlSession.restore(runtime)
    else:
        session = SqlSession(Catalog({}), runtime)
    return session


def serve(port: int, state_dir: str) -> None:
    from risingwave_tpu.cluster import wire

    session = _build_session(state_dir)
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", port))
    srv.listen(1)
    print(f"LISTENING {srv.getsockname()[1]}", flush=True)
    while True:
        conn, _addr = srv.accept()
        try:
            _serve_conn(conn, session)
        except ConnectionError:
            pass  # driver went away; await a reconnect
        finally:
            conn.close()


def _serve_conn(conn: socket.socket, session) -> None:
    from risingwave_tpu import utils_sync_point as sync_point
    from risingwave_tpu.cluster import wire

    shared = getattr(session, "strings", None)
    dicts = wire.SharedDictionaries(shared) if shared is not None else None
    while True:
        header, payload = wire.recv_frame(conn)
        kind = header.get("type")
        try:
            if kind == "ddl":
                _out, tag = session.execute(header["sql"])
                wire.send_frame(conn, {"type": "ok", "tag": tag})
            elif kind == "chunk":
                chunk = wire.payload_chunk(
                    payload,
                    capacity=header.get("capacity"),
                    dictionaries=dicts,
                )
                table = header["table"]
                targets = session.dml._targets.get(table, ())
                if not targets:
                    raise KeyError(f"no consumers for stream {table!r}")
                try:
                    for frag, side in targets:
                        sync_point.hit("compute_push")
                        session.runtime.push(frag, chunk, side)
                except Exception as push_err:
                    # a failure after the first target absorbed rows
                    # would leave the epoch half-applied; roll the WHOLE
                    # epoch back in place (the watchdog's recovery:
                    # rebuild dead actors + restore from last commit) so
                    # state is as-if this chunk never arrived, then
                    # surface the error — the client has not buffered it
                    # yet, and the next barrier reports barrier_failed
                    # so the client replays the epoch's EARLIER chunks.
                    # The flag is session-level (NOT connection-local,
                    # a reconnect must still see barrier_failed) and set
                    # BEFORE the rollback so no window commits the
                    # half-applied state.
                    session._push_rolled_back = True
                    try:
                        session.runtime._auto_recover(push_err)
                    except BaseException:
                        # the rollback itself failed (or escalated after
                        # repeated deterministic faults): in-place state
                        # is unrecoverable — die, so the driver's
                        # respawn + restore + replay path takes over
                        # from the last DURABLE epoch instead of ever
                        # committing the half-applied one
                        os._exit(11)
                    raise push_err
                # permit grant: rows are returned to the sender's
                # budget only after the node ABSORBED them (permit.rs)
                wire.send_frame(
                    conn,
                    {"type": "ack", "permits": int(header.get("rows", 0))},
                )
            elif kind == "barrier":
                # the watchdog may roll a poisoned epoch back in place
                # (auto_recover); the node's chunks come from the WIRE,
                # so it cannot replay them itself — report the rollback
                # honestly and let the driver replay (silently replying
                # barrier_complete would drop the epoch's rows)
                before = session.runtime.auto_recoveries
                session.runtime.barrier()
                session.runtime.wait_checkpoints()
                committed = (
                    session.runtime.mgr.max_committed_epoch
                    if session.runtime.mgr
                    else 0
                )
                if session.runtime.auto_recoveries > before or getattr(
                    session, "_push_rolled_back", False
                ):
                    session._push_rolled_back = False
                    wire.send_frame(
                        conn,
                        {"type": "barrier_failed", "committed": committed},
                    )
                else:
                    wire.send_frame(
                        conn,
                        {
                            "type": "barrier_complete",
                            "epoch": session.runtime.epoch,
                            "committed": committed,
                        },
                    )
            elif kind == "query":
                from decimal import Decimal

                out, tag = session.execute(header["sql"])
                # results are already decoded (strings, NULL as None)
                # by the session's result edge — small enough for JSON;
                # the DATA plane stays Arrow. DECIMALs cross as their
                # exact string form (JSON has no decimal type).
                rows = {
                    k: [
                        None
                        if x is None
                        else str(x)
                        if isinstance(x, Decimal)
                        else (x.item() if hasattr(x, "item") else x)
                        for x in v
                    ]
                    for k, v in out.items()
                }
                wire.send_frame(
                    conn, {"type": "rows", "tag": tag, "data": rows}
                )
            elif kind == "status":
                wire.send_frame(
                    conn,
                    {
                        "type": "status",
                        "committed": (
                            session.runtime.mgr.max_committed_epoch
                            if session.runtime.mgr
                            else 0
                        ),
                    },
                )
            elif kind == "shutdown":
                wire.send_frame(conn, {"type": "ok", "tag": "BYE"})
                sys.exit(0)
            else:
                raise ValueError(f"unknown frame type {kind!r}")
        except ConnectionError:
            raise
        except Exception as e:  # surfaced to the driver, keep serving
            wire.send_frame(conn, {"type": "error", "message": repr(e)})


def run(port: int, state_dir: str, device: str = "cpu") -> None:
    """Shared entry for ``python -m risingwave_tpu compute-node`` and
    direct module execution — ONE place defines the role's setup."""
    import os

    from risingwave_tpu.config import enable_compile_cache, select_device

    select_device(device)
    enable_compile_cache()
    # cross-process failpoint (the reference's fail::fail_point! over
    # its sync-point sites): RW_TPU_FAULT="<sync_point>:<nth>" arms the
    # named sync point to raise on its nth hit — tests drive exact
    # crash windows in the spawned node without reaching into it
    fault = os.environ.get("RW_TPU_FAULT")
    if fault:
        from risingwave_tpu import utils_sync_point as sync_point

        name, sep, nth_s = fault.rpartition(":")
        if not sep:
            name, nth_s = fault, "1"
        nth = int(nth_s)
        counter = {"n": 0}

        def _trip() -> None:
            counter["n"] += 1
            if counter["n"] == nth:
                raise RuntimeError(f"injected fault at {name} #{nth}")

        sync_point.activate(name, _trip)
    serve(port, state_dir)


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--state-dir", required=True)
    ap.add_argument("--device", choices=["cpu", "tpu"], default="cpu")
    args = ap.parse_args(argv)
    run(args.port, args.state_dir, args.device)


if __name__ == "__main__":
    main()
