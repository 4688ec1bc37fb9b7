"""CLI — `python -m risingwave_tpu serve` starts a single-node cluster.

Reference roles: the `risingwave` all-in-one launcher + `risectl`
basics (src/cmd_all/, src/ctl/). One process hosts the frontend
(pgwire), the streaming runtime (barrier clock on a thread), and the
metrics endpoint; `CREATE TABLE` / `CREATE MATERIALIZED VIEW` /
`INSERT` / `SELECT` all work from any pg client.
"""

from __future__ import annotations

import argparse
import contextlib
import os
import sys
import threading
import time
import traceback


def serve(args) -> None:
    from risingwave_tpu.config import (
        enable_compile_cache,
        load_config,
        select_device,
    )

    select_device(args.device)
    enable_compile_cache()
    from risingwave_tpu.frontend import PgServer, SqlSession
    from risingwave_tpu.metrics import REGISTRY
    from risingwave_tpu.runtime import StreamingRuntime
    from risingwave_tpu.sql import Catalog
    from risingwave_tpu.storage.object_store import LocalFsObjectStore

    cfg = load_config(args.config) if args.config else None
    store = (
        LocalFsObjectStore(args.state_dir) if args.state_dir else None
    )
    runtime = (
        StreamingRuntime.from_config(cfg, store)
        if cfg is not None
        else StreamingRuntime(store)
    )
    # a served cluster self-heals (barrier/mod.rs:676 failure recovery):
    # a poisoned epoch or dead actor recovers in place and the source
    # pump replays the lost epoch from committed offsets. Gate on the
    # runtime's ACTUAL persistence (from_config builds its own store)
    if runtime.mgr is not None:
        runtime.auto_recover = True
    from risingwave_tpu.storage.meta_backup import DDL_PATH

    # config sets the baseline; a SET RW_STRICT_LINT wins (the same
    # no-restart escape-hatch precedence as the [resilience] knobs) —
    # passing None lets SqlSession resolve the env default itself
    strict = (
        None
        if "RW_STRICT_LINT" in os.environ
        else (cfg.streaming.strict_lint if cfg is not None else None)
    )
    if store is not None and store.exists(DDL_PATH):
        # warm restart: replay the DDL log, recover state (meta_backup)
        session = SqlSession.restore(runtime, strict_lint=strict)
        print(f"restored {len(session.meta.ddl())} DDL statements")
    else:
        session = SqlSession(Catalog({}), runtime, strict_lint=strict)
    pg = PgServer(session, port=args.port).start()
    mport = REGISTRY.serve(args.metrics_port)
    print(
        f"risingwave-tpu serving: pgwire on 127.0.0.1:{pg.port}, "
        f"metrics on http://127.0.0.1:{mport}/metrics"
        + (f", state in {args.state_dir}" if args.state_dir else " (no store)")
    )

    stop = threading.Event()

    def clock():
        while not stop.is_set():
            try:
                session.pump_sources()
                runtime.tick()
            except Exception:  # noqa: BLE001 — reads keep being served
                print("barrier error:", file=sys.stderr)
                traceback.print_exc()
            time.sleep(runtime.barrier_interval_ms / 1000 / 4)

    t = threading.Thread(target=clock, daemon=True)
    t.start()
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        stop.set()
        pg.shutdown()


def ctl(args) -> None:
    """risectl analogue: backup management over a state dir."""
    from risingwave_tpu.storage.meta_backup import (
        create_backup,
        list_backups,
        restore_backup,
    )
    from risingwave_tpu.storage.object_store import LocalFsObjectStore

    store = LocalFsObjectStore(args.state_dir)
    if args.ctl_cmd == "backup-create":
        print(create_backup(store, args.backup_id))
    elif args.ctl_cmd == "backup-list":
        for b in list_backups(store):
            print(b)
    elif args.ctl_cmd == "backup-restore":
        dst = LocalFsObjectStore(args.dest)
        n = restore_backup(store, args.backup_id, dst)
        print(f"restored {n} blobs into {args.dest}")
    elif args.ctl_cmd == "scrub":
        from risingwave_tpu.storage.state_table import CheckpointManager

        rows = CheckpointManager(store).scrub(deep=args.deep)
        bad = 0
        for r in rows:
            line = (
                f"{r['status']:<12} {r['artifact']}  "
                f"table={r['table_id'] or '-'} "
                f"level={r['level']} epoch={r['epoch']}"
            )
            if r["detail"]:
                line += f"  {r['detail']}"
            print(line)
            bad += r["status"] == "corrupt"
        print(f"{len(rows)} artifacts, {bad} corrupt")
        if bad:
            raise SystemExit(1)


def main() -> None:
    ap = argparse.ArgumentParser(prog="risingwave_tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)
    c = sub.add_parser("ctl", help="ops commands (risectl analogue)")
    csub = c.add_subparsers(dest="ctl_cmd", required=True)
    for name in ("backup-create", "backup-list", "backup-restore"):
        cc = csub.add_parser(name)
        cc.add_argument("--state-dir", required=True)
        if name != "backup-list":
            cc.add_argument("--backup-id", required=True)
        if name == "backup-restore":
            cc.add_argument("--dest", required=True)
    sc = csub.add_parser(
        "scrub", help="verify every checkpoint artifact (crc + digest)"
    )
    sc.add_argument("--state-dir", required=True)
    sc.add_argument(
        "--deep",
        action="store_true",
        help="also verify every per-block crc inside block SSTs",
    )
    c.set_defaults(fn=ctl)
    s = sub.add_parser("serve", help="start a single-node cluster")
    s.add_argument("--port", type=int, default=4566)
    s.add_argument("--metrics-port", type=int, default=0)
    s.add_argument("--state-dir", default=None, help="object store root")
    s.add_argument("--config", default=None, help="TOML config path")
    s.add_argument(
        "--device",
        choices=["tpu", "cpu"],
        default="tpu",
        help="the backend to serve from; the server refuses to start "
        "when jax finds another one",
    )
    s.set_defaults(fn=serve)
    ln = sub.add_parser(
        "lint",
        help="rwlint: static plan verifier + JAX compilation sanitizer "
        "over SQL files and/or the built-in Nexmark queries "
        "(analysis/; exit 0 = no errors)",
    )
    ln.add_argument(
        "paths", nargs="*", help="SQL files (DDL is executed in-memory)"
    )
    ln.add_argument(
        "--all-nexmark",
        action="store_true",
        help="lint every built-in Nexmark query pipeline (q5/q7/q8)",
    )
    ln.add_argument(
        "--deep",
        action="store_true",
        help="also trace jaxprs: dtype promotions, 64-bit hash "
        "arithmetic (no XLA compiles)",
    )
    ln.add_argument(
        "--fusion-report",
        action="store_true",
        dest="fusion_report",
        help="fusion-feasibility analysis per fragment: longest "
        "fusible executor prefix, RW-E8xx blockers with file:line "
        "provenance, estimated dispatch savings (implies "
        "--all-nexmark when no SQL files are given)",
    )
    ln.add_argument(
        "--sharing-report",
        action="store_true",
        dest="sharing_report",
        help="share-key fingerprints per keyed state table + the "
        "corpus' sharing opportunities (Shared Arrangements candidates; "
        "RW-E703 flags would-share tables split only by an incompatible "
        "bucket lattice). Analyzes the built-in corpus incl. the "
        "SQL-planned q5u twin",
    )
    ln.add_argument(
        "--mesh-report",
        action="store_true",
        dest="mesh_report",
        help="mesh-readiness analysis of the sharded corpus (q5/q7/q8 "
        "over the 8-virtual-device sim mesh): SPMD-fusibility proofs "
        "per sharded fragment, RW-E9xx blockers with file:line "
        "provenance, ranked by the committed multichip phase splits. "
        "Standalone: sets up its own mesh; exits 2 if jax was already "
        "initialized with fewer devices",
    )
    ln.add_argument("--json", action="store_true")
    ln.set_defaults(fn=_lint)
    bb = sub.add_parser(
        "blackbox",
        help="read a crash-surviving flight-recorder segment "
        "(BLACKBOX_*.jsonl, or a directory holding one): reconstruct "
        "the last-N-barrier timeline, optionally emit a Perfetto "
        "trace (exit 0 = parsed, 1 = timeline broken, 2 = unreadable)",
    )
    bb.add_argument(
        "path", help="segment file or the directory that holds it"
    )
    bb.add_argument(
        "--last", type=int, default=None, help="only the last N barriers"
    )
    bb.add_argument(
        "--trace",
        default=None,
        metavar="OUT.json",
        help="write a chrome://tracing / Perfetto trace of the timeline",
    )
    bb.add_argument(
        "--roofline",
        action="store_true",
        help="add a roofline summary column per barrier (modeled HBM "
        "bytes from the compiled executable, padding-bytes fraction, "
        "fused telemetry) and a timeline summary footer",
    )
    bb.add_argument("--json", action="store_true")
    bb.set_defaults(fn=_blackbox_read)
    pg = sub.add_parser(
        "programs",
        help="what the device's operations are: the instruction -> "
        "named scope map (trace.program_ops) of the programs a session "
        "compiles for a configuration's DDL at a capacity, after one "
        "empty chunk a stream and one barrier; with --rows, the rows of "
        "a run's breakdown.device_ops (the last line of a benchmark "
        "run, on stdin) each with its scope, source line and whether it "
        "is nested (trace.name_ops). Instruction names are the "
        "compiler's: name a chip run's rows with --device tpu",
    )
    pg.add_argument(
        "config", help="a configuration with ddl, mv_sql, streams, "
        "chunk_rows and session.exec_mode (benchmarks/configs/*.json)"
    )
    pg.add_argument("--capacity", type=int, required=True)
    pg.add_argument("--module", default=None, help="one XLA module alone")
    pg.add_argument("--rows", action="store_true")
    pg.add_argument("--device", choices=["cpu", "tpu"], default="cpu")
    pg.set_defaults(fn=_programs)
    cn = sub.add_parser(
        "compute-node",
        help="start a compute-node role behind a TCP wire "
        "(cluster/compute_node.py; compute_node_serve analogue)",
    )
    cn.add_argument("--port", type=int, default=0)
    cn.add_argument("--state-dir", required=True)
    cn.add_argument("--device", choices=["cpu", "tpu"], default="cpu")
    cn.set_defaults(fn=_compute_node)
    args = ap.parse_args()
    args.fn(args)


def _compute_node(args) -> None:
    from risingwave_tpu.cluster.compute_node import run

    run(args.port, args.state_dir, args.device)


@contextlib.contextmanager
def driven_session(config: dict, capacity: int):
    """The session ``serve`` would build for a configuration's DDL
    (``ddl``, ``mv_sql``, ``streams``, ``chunk_rows``,
    ``session.exec_mode``) at ``capacity``, driven just far enough that
    the programs of its route exist: one chunk with no row a stream
    (which settles the push widths and warms each) and one barrier."""
    import numpy as np

    from risingwave_tpu.array.chunk import StreamChunk
    from risingwave_tpu.frontend import SqlSession
    from risingwave_tpu.runtime import StreamingRuntime
    from risingwave_tpu.sql import Catalog
    from risingwave_tpu.storage.object_store import MemObjectStore

    runtime = StreamingRuntime(MemObjectStore())
    session = SqlSession(
        Catalog({}), runtime, capacity=capacity,
        exec_mode=config["session"]["exec_mode"],
    )
    try:
        for sql in config["ddl"] + config["mv_sql"]:
            session.execute(sql)
        for stream in config["streams"]:
            schema = session.catalog.tables[stream]
            chunk = StreamChunk.from_numpy(
                {n: np.zeros(0, np.int64) for n in schema.names},
                config["chunk_rows"], schema=schema,
            )
            with runtime.lock:
                for frag, side in session.dml._targets.get(stream, ()):
                    runtime.push(frag, chunk, side)
        runtime.barrier()
        runtime.wait_checkpoints()
        yield session
    finally:
        session.close()
        for p in runtime.fragments.values():
            close = getattr(p, "close", None)
            if close is not None:
                close()


def _programs(args) -> None:
    """``trace.program_ops`` / ``name_ops`` of a ``driven_session``, as
    JSON."""
    import json

    from risingwave_tpu.config import enable_compile_cache, select_device

    select_device(args.device)
    enable_compile_cache()
    from risingwave_tpu import trace

    with open(args.config) as f:
        config = json.load(f)
    with driven_session(config, args.capacity):
        if args.rows:
            last = [ln for ln in sys.stdin.read().splitlines() if ln.strip()]
            rows = json.loads(last[-1])["breakdown"]["device_ops"]
            out = trace.name_ops(rows)
        else:
            out = trace.program_ops(args.module)
        print(json.dumps(out, indent=1))


def _blackbox_read(args) -> None:
    """Black-box reader: a post-mortem tool that must work when the
    process that wrote the segment is gone (SIGKILL, OOM, wedged
    device). Parses torn tails, merges a rotated .old sibling, prints
    the barrier timeline, and flags non-monotonic epochs."""
    import json as _json
    import os
    import sys

    # a post-mortem read must never touch the (possibly still-wedged)
    # device — same CPU pin as the lint CLI
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")

    from risingwave_tpu.blackbox import read_segment, records_to_trace_events

    try:
        doc = read_segment(args.path, last=args.last)
    except (OSError, FileNotFoundError) as e:
        print(f"blackbox: cannot read {args.path!r}: {e}", file=sys.stderr)
        sys.exit(2)
    if args.trace:
        from risingwave_tpu.trace import render_chrome_trace

        with open(args.trace, "w") as f:
            f.write(
                render_chrome_trace(
                    records_to_trace_events(doc["records"]),
                    {1: "barrier", 2: "stages"},
                )
            )
    if args.json:
        print(_json.dumps(doc, default=str))
    else:
        recs = doc["records"]
        hdr = doc["header"] or {}
        print(
            f"blackbox: {len(recs)} barrier(s) from {doc['source']}"
            + (f" (pid {hdr.get('pid')})" if hdr else "")
            + (
                f", {doc['torn_lines']} torn line(s) tolerated"
                if doc["torn_lines"]
                else ""
            )
        )
        for r in recs:
            stages = " ".join(
                f"{k}={v:.1f}" for k, v in (r["stages_ms"] or {}).items()
            )
            extra = ""
            if "dispatches_delta" in r:
                extra += f" disp+{r['dispatches_delta']}"
            if r.get("sentinel"):
                extra += f" sen={r['sentinel']}"
            if "channel_depths" in r:
                extra += f" depths={r['channel_depths']}"
            if "mesh" in r:
                m = r["mesh"]
                extra += (
                    f" mesh[n={m.get('n_shards')}"
                    f" cov={m.get('coverage_frac', 0.0):.0%}"
                )
                sk = m.get("skew")
                if sk:
                    extra += (
                        f" SKEW shard{sk.get('shard')}"
                        f" x{sk.get('ratio', 0.0):.1f}"
                    )
                extra += "]"
            if args.roofline and "modeled_bytes" in r:
                extra += (
                    f" model={r['modeled_bytes'] / 1e6:.1f}MB"
                    f" pad={r.get('padding_bytes_frac', 0.0):.2%}"
                )
                tel = r.get("telemetry") or {}
                for frag, t in tel.items():
                    extra += f" {frag}[dirty={t.get('dirty', 0)}]"
            print(
                f"  epoch {r['epoch']} seq {r['seq']} "
                f"{'ckpt' if r['checkpoint'] else '    '} "
                f"wall {r['wall_ms']:.1f}ms  {stages}{extra}"
            )
        if args.roofline:
            # timeline summary: modeled traffic vs wall time — the
            # post-mortem roofline (what the fused programs moved, and
            # how much of it was masked-lane waste)
            modeled = [r for r in recs if r.get("modeled_bytes")]
            if modeled:
                total_b = sum(r["modeled_bytes"] for r in modeled)
                total_s = sum(r["wall_ms"] or 0.0 for r in modeled) / 1e3
                pad = sum(
                    r["modeled_bytes"] * r.get("padding_bytes_frac", 0.0)
                    for r in modeled
                )
                bw = total_b / total_s / 1e9 if total_s > 0 else 0.0
                print(
                    f"blackbox roofline: {len(modeled)} modeled "
                    f"barrier(s), {total_b / 1e6:.1f}MB modeled traffic "
                    f"({pad / max(total_b, 1):.1%} padding), "
                    f"~{bw:.2f} GB/s over barrier wall time"
                )
            else:
                print(
                    "blackbox roofline: no modeled-bytes records "
                    "(deviceprof was not armed in the writing process)"
                )
        meshed = [r for r in recs if r.get("mesh")]
        if meshed:
            # mesh footer: the last sharded barrier's per-shard locals
            # + (src,dst) exchange-row matrix — the post-mortem answer
            # to "which shard was hot when the segment ended"
            m = meshed[-1]["mesh"]
            loc = " ".join(
                f"s{i}={v:.1f}"
                for i, v in enumerate(m.get("shard_local_ms") or [])
            )
            print(
                f"blackbox mesh: {len(meshed)} sharded barrier(s), "
                f"last n={m.get('n_shards')} "
                f"cov={m.get('coverage_frac', 0.0):.0%}  {loc}"
            )
            xm = m.get("exchange_rows")
            if xm:
                for src, row in enumerate(xm):
                    cells = " ".join(f"{int(v):>7d}" for v in row)
                    print(f"  exchange src{src}: {cells}")
        if not doc["monotonic"]:
            print("blackbox: WARNING — epoch timeline is NOT monotonic")
        if args.trace:
            print(f"blackbox: Perfetto trace -> {args.trace}")
    sys.exit(0 if doc["monotonic"] else 1)


def _lint(args) -> None:
    # lint never touches the TPU: a chip belongs to one process at a
    # time, and a CI lint run must not be the one holding it
    import os
    import sys

    os.environ["JAX_PLATFORMS"] = "cpu"
    if getattr(args, "mesh_report", False):
        # the virtual-device flag only takes effect if it lands before
        # the first backend init — claim it here, before importing jax
        from risingwave_tpu.analysis.mesh_domain import (
            DEFAULT_MESH_SHARDS,
            MeshUnavailable,
            ensure_virtual_devices,
        )

        try:
            ensure_virtual_devices(DEFAULT_MESH_SHARDS)
        except MeshUnavailable as e:
            print(f"rwlint: {e}", file=sys.stderr)
            sys.exit(2)
    import jax

    jax.config.update("jax_platforms", "cpu")
    from risingwave_tpu.analysis.lint import run_cli

    sys.exit(run_cli(args))


if __name__ == "__main__":
    main()
