"""Minimal pgwire v3 client: start-up, simple query, DataRow cells as
text tuples. Plain sockets; imports nothing of the program."""

from __future__ import annotations

import socket
import struct


class PgClient:
    def __init__(self, port: int, timeout_s: float = 600.0):
        self.sock = socket.create_connection(
            ("127.0.0.1", port), timeout=timeout_s
        )
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._error = None
        body = struct.pack("!I", 196608) + b"user\0bench\0database\0dev\0\0"
        self.sock.sendall(struct.pack("!I", len(body) + 4) + body)
        self._drain()

    def _recv(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            got = self.sock.recv(min(1 << 20, n - len(buf)))
            if not got:
                raise ConnectionError("pgwire server closed the connection")
            buf += got
        return bytes(buf)

    def _drain(self):
        rows = []
        while True:
            head = self._recv(5)
            (length,) = struct.unpack("!I", head[1:])
            body = self._recv(length - 4)
            tag = head[:1]
            if tag == b"D":
                (ncols,) = struct.unpack("!h", body[:2])
                off, row = 2, []
                for _ in range(ncols):
                    (ln,) = struct.unpack("!i", body[off : off + 4])
                    off += 4
                    if ln < 0:
                        row.append(None)
                    else:
                        row.append(body[off : off + ln].decode())
                        off += ln
                rows.append(tuple(row))
            elif tag == b"E":
                # the server still sends ReadyForQuery after an error
                self._error = body
            elif tag == b"Z":
                err, self._error = self._error, None
                if err is not None:
                    raise RuntimeError(f"pgwire error: {err!r}")
                return rows

    def query(self, sql: str):
        body = sql.encode() + b"\0"
        self.sock.sendall(b"Q" + struct.pack("!I", len(body) + 4) + body)
        return self._drain()

    def close(self) -> None:
        try:
            self.sock.sendall(b"X" + struct.pack("!I", 4))
        except OSError:
            pass
        finally:
            self.sock.close()
