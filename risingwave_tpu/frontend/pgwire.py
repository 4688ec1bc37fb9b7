"""pgwire — the Postgres wire protocol (v3) server.

Reference: src/utils/pgwire/src/pg_server.rs:250 (+ pg_protocol.rs
message codec): startup handshake, cleartext-free auth OK, the simple
query cycle Q -> RowDescription/DataRow*/CommandComplete ->
ReadyForQuery, plus the EXTENDED protocol (Parse/Bind/Describe/
Execute/Close/Sync with text-format parameters — prepared statements
bind $n placeholders as SQL literals; Describe infers the row shape
from the typing layer without executing). ErrorResponse on failure,
SSLRequest politely refused. Enough protocol for psql / psycopg
simple AND extended queries to work against the SqlSession.

This is a host control-plane surface — no device work happens here, so
a plain threaded TCP server (one thread per connection, like the
reference's per-session task) is the right shape.
"""

from __future__ import annotations

import socket
import socketserver
import struct
import threading
from typing import Optional

import numpy as np

from risingwave_tpu.frontend.session import SqlSession

_SSL_REQUEST = 80877103
_CANCEL_REQUEST = 80877102

# type OIDs (pg catalog)
_OID_BOOL, _OID_INT8, _OID_FLOAT8, _OID_TEXT = 16, 20, 701, 25


def _oid_of(dtype: np.dtype) -> int:
    if dtype == np.bool_:
        return _OID_BOOL
    if np.issubdtype(dtype, np.integer):
        return _OID_INT8
    if np.issubdtype(dtype, np.floating):
        return _OID_FLOAT8
    return _OID_TEXT


def _msg(tag: bytes, payload: bytes = b"") -> bytes:
    return tag + struct.pack("!I", len(payload) + 4) + payload


class _Conn(socketserver.BaseRequestHandler):
    def _recv_exact(self, n: int) -> Optional[bytes]:
        buf = b""
        while len(buf) < n:
            got = self.request.recv(n - len(buf))
            if not got:
                return None
            buf += got
        return buf

    def _startup(self) -> bool:
        while True:
            head = self._recv_exact(8)
            if head is None:
                return False
            length, code = struct.unpack("!II", head)
            body = self._recv_exact(length - 8)
            if body is None:
                return False
            if code == _SSL_REQUEST:
                self.request.sendall(b"N")  # no TLS; client retries plain
                continue
            if code == _CANCEL_REQUEST:
                return False
            # normal StartupMessage (protocol 3.0) — params ignored
            return True

    @staticmethod
    def _row_description(cols) -> bytes:
        names = list(cols)
        fields = b""
        for name in names:
            fields += (
                name.encode() + b"\0"
                + struct.pack(
                    "!IhIhih",
                    0, 0, _oid_of(np.asarray(cols[name]).dtype), -1, -1, 0,
                )
            )
        return _msg(b"T", struct.pack("!h", len(names)) + fields)

    @staticmethod
    def _data_rows(cols) -> bytes:
        names = list(cols)
        # joined once: appending to one bytes object copies the whole
        # reply per row, quadratic in the size of a full-MV read
        out = []
        n = len(cols[names[0]]) if names else 0
        for i in range(n):
            row = b""
            for name in names:
                v = cols[name][i]
                if v is None or (isinstance(v, float) and np.isnan(v)):
                    row += struct.pack("!i", -1)
                else:
                    s = str(
                        v.item() if hasattr(v, "item") else v
                    ).encode()
                    row += struct.pack("!i", len(s)) + s
            out.append(_msg(b"D", struct.pack("!h", len(names)) + row))
        return b"".join(out)

    @staticmethod
    def _bind_params(sql: str, params) -> str:
        """Substitute $n placeholders as SQL literals (text-format
        extended protocol; the in-process prepared-statement form).
        SINGLE-PASS regex substitution: replacements are never
        rescanned, so a parameter whose VALUE contains '$k' text can
        never have another parameter spliced into it."""
        import re as _re

        def lit(m):
            i = int(m.group(1))
            if not 1 <= i <= len(params):
                raise KeyError(f"no parameter ${i}")
            p = params[i - 1]
            if p is None:
                return "NULL"
            s = p.decode()
            if _re.fullmatch(
                r"[+-]?(\d+(\.\d*)?|\.\d+)([eE][+-]?\d+)?", s
            ):
                return s
            return "'" + s.replace("'", "''") + "'"

        return _re.sub(r"\$(\d+)", lit, sql)

    def handle(self):
        # protocol turns are many small writes (RowDescription, rows,
        # CommandComplete, ReadyForQuery): with Nagle armed they batch
        # behind the peer's delayed ACK — a flat ~40ms floor on every
        # query. Serving-tier readers need the real latency.
        self.request.setsockopt(
            socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
        )
        if not self._startup():
            return
        out = self.request.sendall
        out(_msg(b"R", struct.pack("!I", 0)))  # AuthenticationOk
        for k, v in (
            ("server_version", "13.0 (risingwave-tpu)"),
            ("client_encoding", "UTF8"),
        ):
            out(_msg(b"S", k.encode() + b"\0" + v.encode() + b"\0"))
        out(_msg(b"K", struct.pack("!II", 0, 0)))  # BackendKeyData
        out(_msg(b"Z", b"I"))

        session: SqlSession = self.server.session  # type: ignore[attr-defined]
        stmts: dict = {}  # prepared name -> sql
        portals: dict = {}  # portal name -> (bound sql, T already sent)
        skip_to_sync = False  # error in a pipeline: discard until Sync
        while True:
            head = self._recv_exact(5)
            if head is None:
                return
            tag, length = head[:1], struct.unpack("!I", head[1:])[0]
            body = self._recv_exact(length - 4)
            if body is None:
                return
            if tag == b"X":  # Terminate
                return
            if skip_to_sync:
                # protocol: after an extended-protocol error, queued
                # messages are DISCARDED until the client's Sync
                if tag == b"S":
                    skip_to_sync = False
                    out(_msg(b"Z", b"I"))
                continue
            try:
                if tag == b"Q":
                    sql = body.rstrip(b"\0").decode()
                    # concurrency is the SESSION's contract now: DDL/
                    # DML/stateful reads serialize on the runtime lock
                    # inside execute(), and shared-arrangement SELECTs
                    # serve lock-free off published versions — a global
                    # server lock here would put every reader back in
                    # one file line (the pre-serving-tier behavior)
                    cols, tag_str = session.execute(sql)
                    if cols:
                        out(self._row_description(cols))
                        out(self._data_rows(cols))
                    out(_msg(b"C", tag_str.encode() + b"\0"))
                    out(_msg(b"Z", b"I"))
                elif tag == b"P":  # Parse
                    name, rest = body.split(b"\0", 1)
                    sql, _rest = rest.split(b"\0", 1)
                    stmts[name] = sql.decode()
                    out(_msg(b"1"))  # ParseComplete
                elif tag == b"B":  # Bind
                    portal, rest = body.split(b"\0", 1)
                    stmt, rest = rest.split(b"\0", 1)
                    off = 0
                    (nfmt,) = struct.unpack_from("!h", rest, off)
                    off += 2
                    fmts = struct.unpack_from(f"!{nfmt}h", rest, off)
                    off += 2 * nfmt
                    if any(f == 1 for f in fmts):
                        raise ValueError(
                            "binary parameter format unsupported "
                            "(bind text-format parameters)"
                        )
                    (nparams,) = struct.unpack_from("!h", rest, off)
                    off += 2
                    params = []
                    for _ in range(nparams):
                        (plen,) = struct.unpack_from("!i", rest, off)
                        off += 4
                        if plen < 0:
                            params.append(None)
                        else:
                            params.append(rest[off : off + plen])
                            off += plen
                    if stmt not in stmts:
                        raise KeyError(
                            f"unknown prepared statement {stmt!r}"
                        )
                    portals[portal] = [
                        self._bind_params(stmts[stmt], params),
                        False,
                    ]
                    out(_msg(b"2"))  # BindComplete
                elif tag == b"D":  # Describe
                    kind, name = body[:1], body[1:].split(b"\0", 1)[0]
                    sql = (
                        portals.get(name, [None])[0]
                        if kind == b"P"
                        else stmts.get(name)
                    )
                    if kind == b"S":
                        # ParameterDescription is MANDATORY before the
                        # row shape when describing a statement
                        import re as _re

                        nps = (
                            max(
                                (
                                    int(m)
                                    for m in _re.findall(
                                        r"\$(\d+)", sql or ""
                                    )
                                ),
                                default=0,
                            )
                        )
                        out(
                            _msg(
                                b"t",
                                struct.pack("!h", nps)
                                + struct.pack("!I", 0) * nps,  # unknown
                            )
                        )
                    desc = None
                    if sql is not None and sql.lstrip()[:6].lower() == "select":
                        # infer the row shape WITHOUT executing
                        desc = self._describe_select(session, sql)
                    if desc is None:
                        out(_msg(b"n"))  # NoData
                    else:
                        out(desc)
                        if kind == b"P" and name in portals:
                            portals[name][1] = True
                elif tag == b"E":  # Execute
                    name = body.split(b"\0", 1)[0]
                    if name not in portals:
                        raise KeyError(f"unknown portal {name!r}")
                    sql, t_sent = portals[name]
                    cols, tag_str = session.execute(sql)
                    if cols:
                        if not t_sent:
                            out(self._row_description(cols))
                        out(self._data_rows(cols))
                    out(_msg(b"C", tag_str.encode() + b"\0"))
                elif tag == b"C":  # Close
                    kind, name = body[:1], body[1:].split(b"\0", 1)[0]
                    (portals if kind == b"P" else stmts).pop(name, None)
                    out(_msg(b"3"))  # CloseComplete
                elif tag == b"S":  # Sync
                    out(_msg(b"Z", b"I"))
                elif tag == b"H":  # Flush
                    pass
                else:
                    out(_err(f"unsupported message {tag!r}"))
                    out(_msg(b"Z", b"I"))
            except Exception as e:  # noqa: BLE001 — surface as pg error
                out(_err(str(e)))
                if tag == b"Q":
                    out(_msg(b"Z", b"I"))
                else:
                    # extended protocol: discard the rest of the
                    # pipeline; the client's Sync elicits ReadyForQuery
                    skip_to_sync = True

    @staticmethod
    def _describe_select(session: SqlSession, sql: str):
        """RowDescription for a SELECT from the typing layer (names +
        logical types; no execution, no side effects)."""
        try:
            import re as _re

            from risingwave_tpu.sql import parser as P
            from risingwave_tpu.sql.typing import (
                expand_star,
                infer_output_fields,
                output_name,
            )
            from risingwave_tpu.types import DataType

            # unbound parameters parse as NULL for shape inference
            stmt = P.parse(_re.sub(r"\$\d+", "NULL", sql))
            if not isinstance(stmt, P.Select):
                return None
            stmt = expand_star(stmt, session.catalog, strict=False)
            inferred = infer_output_fields(stmt, session.catalog)
            fields = b""
            names = [
                output_name(it, i) for i, it in enumerate(stmt.items)
            ]
            oid_map = {
                DataType.BOOLEAN: _OID_BOOL,
                DataType.FLOAT32: _OID_FLOAT8,
                DataType.FLOAT64: _OID_FLOAT8,
                DataType.VARCHAR: _OID_TEXT,
                DataType.JSONB: _OID_TEXT,
                DataType.DECIMAL: _OID_TEXT,
            }
            for nm in names:
                f = inferred.get(nm)
                oid = oid_map.get(f.dtype, _OID_INT8) if f else _OID_INT8
                fields += nm.encode() + b"\0" + struct.pack(
                    "!IhIhih", 0, 0, oid, -1, -1, 0
                )
            return _msg(b"T", struct.pack("!h", len(names)) + fields)
        except Exception:  # noqa: BLE001 — Describe is best-effort
            return None


def _err(message: str) -> bytes:
    payload = (
        b"SERROR\0"
        + b"CXX000\0"
        + b"M" + message.encode() + b"\0"
        + b"\0"
    )
    return _msg(b"E", payload)


class PgServer:
    """Serve a SqlSession over pgwire on 127.0.0.1."""

    def __init__(self, session: SqlSession, port: int = 0):
        class _Srv(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._srv = _Srv(("127.0.0.1", port), _Conn)
        self._srv.session = session  # type: ignore[attr-defined]
        self.port = self._srv.server_address[1]
        self._thread = threading.Thread(
            target=self._srv.serve_forever, daemon=True
        )

    def start(self) -> "PgServer":
        self._thread.start()
        return self

    def shutdown(self) -> None:
        self._srv.shutdown()
        self._srv.server_close()
