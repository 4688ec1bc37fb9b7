#!/usr/bin/env python
"""bench_serving — the multi-tenant serving-tier bench (ROADMAP item 4).

Measures the two halves of the shared-arrangement story end to end and
writes ``BENCH_SERVING.json``:

1. **Registration storm** — N ``CREATE MATERIALIZED VIEW`` statements
   across F structurally-distinct families (identical within a
   family). With sharing ON, each family costs ONE writer fragment +
   one set of device state; every further CREATE attaches in O(1).
   Reported: create-latency p50/p99, fused compile count (must be
   O(shape families), not O(MVs) — constant lifting shares the
   programs across families too), arrangements/refs, barrier p99
   before vs after the storm (flat = the win), total device state and
   bytes-per-MV vs a sharing-disabled private-twin control.

2. **Concurrent serving** — R threaded pgwire readers issue SELECTs
   against subscriber MVs (served lock-free off published per-barrier
   versions) while a writer keeps streaming INSERT + barrier cycles.
   Reported: reader p50/p99, reads/s, barrier p99 under read load vs
   idle, registry publish overhead per barrier.

Runs on the CPU by default (the artifact is a serving-tier scaling
proof, not a device number); ``--device tpu`` through the chip tool for
HBM-scale numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import struct
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _pctl(xs, q):
    if not xs:
        return 0.0
    xs = sorted(xs)
    i = min(len(xs) - 1, int(round(q * (len(xs) - 1))))
    return float(xs[i])


class _PgReader:
    """Minimal pgwire v3 client for the reader threads (startup +
    simple query), matching the server's subset."""

    def __init__(self, port: int):
        self.sock = socket.create_connection(
            ("127.0.0.1", port), timeout=30
        )
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        body = struct.pack("!I", 196608) + b"user\0bench\0database\0dev\0\0"
        self.sock.sendall(struct.pack("!I", len(body) + 4) + body)
        self._drain()

    def _recv(self, n):
        buf = b""
        while len(buf) < n:
            got = self.sock.recv(n - len(buf))
            if not got:
                raise ConnectionError("server closed")
            buf += got
        return buf

    def _drain(self):
        rows, err = 0, None
        while True:
            head = self._recv(5)
            (length,) = struct.unpack("!I", head[1:])
            body = self._recv(length - 4)
            if head[:1] == b"D":
                rows += 1
            elif head[:1] == b"E":
                err = body
            elif head[:1] == b"Z":
                return rows, err

    def query(self, sql: str):
        body = sql.encode() + b"\0"
        self.sock.sendall(b"Q" + struct.pack("!I", len(body) + 4) + body)
        return self._drain()

    def close(self):
        try:
            self.sock.sendall(b"X" + struct.pack("!I", 4))
            self.sock.close()
        except OSError:
            pass


def _mk_session(exec_mode: str, capacity: int):
    from risingwave_tpu.frontend.session import SqlSession
    from risingwave_tpu.sql import Catalog

    return SqlSession(
        Catalog({}),
        capacity=capacity,
        exec_mode=exec_mode,
        parallelism=1,
    )


_FAMILY_THRESHOLDS = (10, 250, 500, 750, 900, 120, 380, 640)


def _family_sql(name: str, family: int) -> str:
    thr = _FAMILY_THRESHOLDS[family % len(_FAMILY_THRESHOLDS)]
    return (
        f"CREATE MATERIALIZED VIEW {name} AS SELECT k, count(*) AS c "
        f"FROM base WHERE v > {thr} GROUP BY k"
    )


def _seed(session, rows: int, seed: int = 7) -> None:
    import numpy as np

    rng = np.random.default_rng(seed)
    ks = rng.integers(0, 64, size=rows)
    vs = rng.integers(0, 1000, size=rows)
    vals = ", ".join(f"({k}, {v})" for k, v in zip(ks, vs))
    session.execute(f"INSERT INTO base VALUES {vals}")


def _barrier_p99(session, n: int = 12):
    lat = []
    for _ in range(n):
        t0 = time.perf_counter()
        with session.runtime.lock:
            session.runtime.barrier()
        lat.append((time.perf_counter() - t0) * 1e3)
    return _pctl(lat, 0.5), _pctl(lat, 0.99)


def run_serving(
    mvs: int = 1000,
    families: int = 4,
    readers: int = 8,
    read_seconds: float = 4.0,
    exec_mode: str = "graph",
    capacity: int = 1 << 10,
    seed_rows: int = 512,
    private_twins: int = 8,
    use_pgwire: bool = True,
    verbose: bool = True,
) -> dict:
    from risingwave_tpu.runtime.fused_step import fused_cache_stats

    say = print if verbose else (lambda *a, **k: None)
    out: dict = {
        "mvs": mvs,
        "families": families,
        "readers": readers,
        "exec_mode": exec_mode,
    }

    # -- private-twin control (sharing OFF) ------------------------------
    prev = os.environ.get("RW_SHARED_ARRANGEMENTS")
    os.environ["RW_SHARED_ARRANGEMENTS"] = "0"
    try:
        ctl = _mk_session(exec_mode, capacity)
        ctl.execute("CREATE TABLE base (k BIGINT, v BIGINT)")
        _seed(ctl, seed_rows)
        base_bytes = ctl.runtime.state_nbytes()
        for i in range(private_twins):
            ctl.execute(_family_sql(f"priv{i}", 0))
        private_per_mv = (
            ctl.runtime.state_nbytes() - base_bytes
        ) / max(1, private_twins)
        for p in ctl.runtime.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()
    finally:
        if prev is None:
            os.environ.pop("RW_SHARED_ARRANGEMENTS", None)
        else:
            os.environ["RW_SHARED_ARRANGEMENTS"] = prev
    out["bytes_per_mv_private"] = round(private_per_mv, 1)
    say(f"[serving] private twin: {private_per_mv / 1e3:.1f} KB/MV")

    # -- shared storm ----------------------------------------------------
    session = _mk_session(exec_mode, capacity)
    session.execute("CREATE TABLE base (k BIGINT, v BIGINT)")
    _seed(session, seed_rows)
    base_bytes = session.runtime.state_nbytes()
    cache0 = fused_cache_stats()["compiled_programs"]

    # warm phase: one MV per family (the writers + their compiles)
    create_ms = []
    t_storm = time.perf_counter()
    for i in range(families):
        t0 = time.perf_counter()
        session.execute(_family_sql(f"mv{i}", i))
        create_ms.append((time.perf_counter() - t0) * 1e3)
    session.execute("INSERT INTO base VALUES (1, 999), (2, 1)")
    pre_p50, pre_p99 = _barrier_p99(session)

    for i in range(families, mvs):
        t0 = time.perf_counter()
        session.execute(_family_sql(f"mv{i}", i % families))
        create_ms.append((time.perf_counter() - t0) * 1e3)
    storm_wall = time.perf_counter() - t_storm
    post_p50, post_p99 = _barrier_p99(session)

    stats = session.runtime.arrangements.stats()
    cache = fused_cache_stats()
    shared_bytes = session.runtime.state_nbytes() - base_bytes
    out.update(
        {
            "storm_wall_s": round(storm_wall, 3),
            "creates_per_s": round(mvs / storm_wall, 1),
            "create_p50_ms": round(_pctl(create_ms, 0.5), 3),
            "create_p99_ms": round(_pctl(create_ms, 0.99), 3),
            "arrangements": stats["arrangements"],
            "arrangement_refs": stats["refs"],
            # -1 = the jit cache size is unreadable (a jax-internal
            # surface): propagate the sentinel rather than a bogus
            # delta, so the gate can refuse instead of passing vacuously
            "compile_programs": (
                cache["compiled_programs"] - cache0
                if cache["compiled_programs"] >= 0 and cache0 >= 0
                else -1
            ),
            "plans_lifted": cache["plans_lifted"],
            "plans_lift_rejected": cache["plans_lift_rejected"],
            "barrier_p50_ms_pre_storm": round(pre_p50, 3),
            "barrier_p99_ms_pre_storm": round(pre_p99, 3),
            "barrier_p50_ms_post_storm": round(post_p50, 3),
            "barrier_p99_ms_post_storm": round(post_p99, 3),
            "state_bytes_shared_total": int(shared_bytes),
            "bytes_per_mv_shared": round(shared_bytes / mvs, 1),
            "bytes_per_mv_ratio": round(
                (shared_bytes / mvs) / max(private_per_mv, 1.0), 4
            ),
        }
    )
    say(
        f"[serving] storm: {mvs} MVs in {storm_wall:.2f}s, "
        f"{stats['arrangements']} arrangement(s), "
        f"{out['compile_programs']} compiled program(s), barrier p99 "
        f"{pre_p99:.1f} -> {post_p99:.1f} ms"
    )

    # -- registry publish overhead (the no-reader barrier cost) ----------
    reg = session.runtime.arrangements
    epoch = session.runtime.epoch
    t0 = time.perf_counter()
    rounds = 500
    with session.runtime.lock:
        for _ in range(rounds):
            reg.publish(epoch)
    publish_us = (time.perf_counter() - t0) / rounds * 1e6
    out["publish_us_per_barrier"] = round(publish_us, 2)
    out["registry_overhead_frac"] = round(
        publish_us / 1e3 / max(post_p99, 1e-9), 6
    )

    # -- concurrent serving ----------------------------------------------
    sub_names = [f"mv{i}" for i in range(families, min(mvs, families + 64))]
    lat_ms: list = []
    lat_lock = threading.Lock()
    stop = threading.Event()
    errors: list = []
    reads = [0]

    pg = None
    port = None
    if use_pgwire:
        from risingwave_tpu.frontend.pgwire import PgServer

        pg = PgServer(session, port=0).start()
        port = pg.port

    def reader(idx: int):
        cli = _PgReader(port) if use_pgwire else None
        my = []
        n = 0
        try:
            while not stop.is_set():
                name = sub_names[(idx + n) % len(sub_names)]
                sql = f"SELECT k, c FROM {name} ORDER BY k"
                t0 = time.perf_counter()
                if cli is not None:
                    _rows, err = cli.query(sql)
                    if err:
                        errors.append(err.decode(errors="replace"))
                else:
                    session.execute(sql)
                my.append((time.perf_counter() - t0) * 1e3)
                n += 1
        except Exception as e:  # noqa: BLE001 — surfaced in the artifact
            errors.append(repr(e))
        finally:
            if cli is not None:
                cli.close()
        with lat_lock:
            lat_ms.extend(my)
            reads[0] += n

    # warmup: compile the serve-loop shapes OUTSIDE the timed window
    # (the 1-row insert chunk program, the facade read path, and the
    # eager publish's snapshot gather) — first-use compiles are a
    # compile-cache property, not a serving-tier latency
    session.execute("INSERT INTO base VALUES (0, 0)")
    for name in sub_names[:2]:
        session.execute(f"SELECT k, c FROM {name} ORDER BY k")
    session.execute("INSERT INTO base VALUES (0, 1)")
    session.execute(f"SELECT k, c FROM {sub_names[0]} ORDER BY k")
    session.execute("INSERT INTO base VALUES (0, 2)")

    threads = [
        threading.Thread(target=reader, args=(i,), daemon=True)
        for i in range(readers)
    ]
    n_barriers_before = len(session.runtime.barrier_latencies_ms)
    for t in threads:
        t.start()
    t_serve = time.perf_counter()
    deadline = t_serve + read_seconds
    wrote = 0
    while time.perf_counter() < deadline:
        session.execute(
            f"INSERT INTO base VALUES ({wrote % 64}, {wrote % 1000})"
        )
        wrote += 1
    stop.set()
    for t in threads:
        t.join(timeout=30)
    serve_wall = time.perf_counter() - t_serve
    if pg is not None:
        pg.shutdown()
    under_load = session.runtime.barrier_latencies_ms[n_barriers_before:]
    out.update(
        {
            "serve_wall_s": round(serve_wall, 3),
            "reads_total": reads[0],
            "reads_per_s": round(reads[0] / max(serve_wall, 1e-9), 1),
            "reader_p50_ms": round(_pctl(lat_ms, 0.5), 3),
            "reader_p99_ms": round(_pctl(lat_ms, 0.99), 3),
            "writes_during_serve": wrote,
            "barrier_p99_ms_under_read_load": round(
                _pctl(under_load, 0.99), 3
            ),
            "reader_errors": errors[:5],
            "reader_error_count": len(errors),
        }
    )
    say(
        f"[serving] {readers} readers: {out['reads_per_s']}/s, p50 "
        f"{out['reader_p50_ms']}ms p99 {out['reader_p99_ms']}ms; "
        f"barrier p99 under load {out['barrier_p99_ms_under_read_load']}"
        f"ms; {len(errors)} error(s)"
    )
    for p in session.runtime.fragments.values():
        close = getattr(p, "close", None)
        if close is not None:
            close()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--mvs", type=int, default=1000)
    ap.add_argument("--families", type=int, default=4)
    ap.add_argument("--readers", type=int, default=8)
    ap.add_argument("--read-seconds", type=float, default=4.0)
    ap.add_argument("--exec-mode", default="graph")
    ap.add_argument("--out", default=os.path.join(ROOT, "BENCH_SERVING.json"))
    ap.add_argument("--device", choices=["tpu", "cpu"], default="cpu")
    args = ap.parse_args(argv)
    from risingwave_tpu.config import select_device
    from risingwave_tpu.provenance import stamp

    select_device(args.device)

    out = run_serving(
        mvs=args.mvs,
        families=args.families,
        readers=args.readers,
        read_seconds=args.read_seconds,
        exec_mode=args.exec_mode,
    )
    out.update(stamp())
    out["device"] = args.device
    with open(args.out, "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    print(f"[serving] artifact -> {args.out}")
    return 1 if out.get("reader_error_count") else 0


if __name__ == "__main__":
    sys.exit(main())
