#!/usr/bin/env python3
"""One run of one benchmark cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one chip. Loads the cell's configuration and traffic mix
by name, sets the system up (DDL, events from the seed, a pgwire
server, a preload that fills the first windows), measures for
``--seconds``, then checks what was served against the configuration's
plain reference, and prints one JSON object as the last line of
stdout. Refuses to start without a TPU; ``--dry-run-cpu`` is the
rehearsal at a tiny size and says ``platform=cpu``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)  # the program; HERE is already first

import numpy as np  # noqa: E402

import checks  # noqa: E402
import feed  # noqa: E402
import nexmark_gen  # noqa: E402
from pgclient import PgClient  # noqa: E402

DEADLINE_S = 1150  # the contract's limit for a run that compiles is 1200
BARRIER_TIMEOUT_S = "900"  # must outlast a cold first barrier (PERF.md)
TRACE_START_S, TRACE_SECONDS = 8.0, 8.0
FAULTS = ("dup_chunk", "drop_chunk", "rare_checkpoint")


def say(*a) -> None:
    print(*a, flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    path = os.path.join(HERE, *parts)
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3], path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; have {sorted(cells)}")
    cell = cells[name]
    config = load_json("configs", cell["config"] + ".json")
    mix = load_json("traffic", cell["traffic"] + ".json")
    return bench, cell, config, mix


def capacity_for(rule: dict, events: int, floor=None) -> int:
    cap = int(floor if floor is not None else rule["floor"])
    while events * rule["headroom"] > cap:
        cap *= 2
    return cap


class Faulty:
    """Breaks the timed path under the harness: one chunk of the window
    delivered twice (at-least-once) or not at all (at-most-once)."""

    def __init__(self, system, kind: str, at_chunk: int = 3):
        self.system, self.kind, self.at, self.seen = system, kind, at_chunk, 0
        self.armed = False

    def push(self, stream, cols, rows):
        if self.armed:
            self.seen += 1
            if self.seen == self.at:
                say(f"FAULT {self.kind}: chunk {self.seen} of the window")
                if self.kind == "drop_chunk":
                    return
                self.system.push(stream, cols, rows)
        self.system.push(stream, cols, rows)

    def barrier(self):
        return self.system.barrier()

    def begin_epoch(self):
        self.system.begin_epoch()

    def end_epoch(self):
        self.system.end_epoch()


def generate(config, mix, seed, seconds, dry, chunk_rows):
    """All the run's subscribed events, their due times, the sizes of
    the preload's epochs, the offered rate, and how many events the run
    is expected to push (what the session's capacity is sized from: a
    backlog is generated at backlog_factor x what the window takes)."""
    gen_cfg = dict(config["generator"])
    catchup = config["catchup_events_per_s"]
    if dry:
        gen_cfg["first_event_rate"] = config["dry_run"]["first_event_rate"]
        catchup = config["dry_run"]["catchup_events_per_s"]
    rate = gen_cfg["first_event_rate"]
    share = sum(nexmark_gen.STREAM_SHARE[s] for s in config["streams"])
    interval_s = mix["interval_ms"] / 1e3
    if mix["arrivals"] == "schedule":
        mean = sum(
            p["seconds"] * p["rate_factor"] * rate for p in mix["phases"]
        ) / sum(p["seconds"] for p in mix["phases"])
        win_ordinals = int((seconds + mix["margin_s"]) * mean)
        expected = win_ordinals * share
        offered = mean * share
        nominal = offered * interval_s
    elif mix["arrivals"] == "backlog":
        win_ordinals = int(mix["backlog_factor"] * catchup * seconds / share)
        expected = catchup * seconds
        offered = mix["backlog_factor"] * catchup
        nominal = mix["max_epoch_chunks"] * chunk_rows
    else:
        raise SystemExit(f"unknown arrivals {mix['arrivals']!r}")
    # the preload: at least preload_event_seconds of event time, cut
    # into epochs of the sizes the window's can have — one of every
    # power-of-two count of chunks from the admission limit down to the
    # nominal epoch's (the fused barrier program is compiled per such
    # count, and a slow barrier makes the next epoch larger), then
    # nominal epochs, two at the least. On a schedule an epoch can also
    # be thin (a query's window turns over) or hold two intervals (the
    # loop came late), and the program pads what an epoch changed to a
    # power of two and compiles per size: so one epoch of twice and one
    # of half the nominal size go first
    target = config["preload_event_seconds"] * rate * share
    nominal = int(round(nominal))
    sizes, c = [], mix["max_epoch_chunks"]
    while c * chunk_rows >= 2 * nominal and c > 1:
        sizes.append(c * chunk_rows)
        c //= 2
    if mix["arrivals"] == "schedule":
        if 2 * nominal <= mix["max_epoch_chunks"] * chunk_rows:
            sizes.append(2 * nominal)
        sizes.append(nominal // 2)
    while sum(sizes) < target or sizes[-2:] != [nominal, nominal]:
        sizes.append(nominal)
    n_pre = sum(sizes)
    pre_ordinals = int(n_pre / share) + nexmark_gen.CYCLE
    gen = nexmark_gen.Generator(seed, gen_cfg)
    events = gen.events(0, pre_ordinals + win_ordinals, config["streams"])
    order = np.sort(np.concatenate([c["eid"] for c in events.values()]))
    due = np.full(len(order), -np.inf)
    if mix["arrivals"] == "schedule":
        due[n_pre:] = feed.due_times(
            order[n_pre:] - order[n_pre], rate, mix["phases"]
        )
    else:
        due[n_pre:] = 0.0
    return events, due, sizes, offered, int(n_pre + expected)


def check_expects(config, built) -> list:
    exp, bad = config.get("expects", {}), []
    if "fragments" in exp and built["fragments"] != exp["fragments"]:
        bad.append(f"fragments {built['fragments']} != {exp['fragments']}")
    return bad


def stage_dict(trace) -> dict:
    return dict(getattr(trace, "stages_ms", None) or {})


def reports(metric: dict, cell_name: str) -> bool:
    """Whether BENCHMARK.json has this cell report this metric."""
    return "workloads" not in metric or cell_name in metric["workloads"]


def layer_metrics(bench, cell_name, run) -> dict:
    """Every per-layer metric BENCHMARK.json lists for this cell, read
    by its own reader; a reader that finds nothing is left out."""
    out = {}
    for m in bench["per_layer"]:
        if not reports(m, cell_name):
            continue
        spec = load_json("layer_metrics", m["name"] + ".json")
        reader = load_module("readers", spec["reader"] + ".py")
        value = reader.read(run, spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-run-cpu", action="store_true",
                    help="rehearsal at a tiny size on the CPU backend")
    ap.add_argument("--rate", type=int, default=None,
                    help="sweeps only: overrides generator.first_event_rate "
                    "(schedule mixes) or catchup_events_per_s (backlogs)")
    ap.add_argument("--size-seconds", type=float, default=None,
                    help="controls only: generate events and size the "
                    "session as for a window of this length, so that a "
                    "short window runs at the cell's own size")
    ap.add_argument("--fault", choices=FAULTS, default=None,
                    help="controls only: break a guarantee under the harness")
    args = ap.parse_args(argv)

    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    bench, cell, config, mix = load_cell(args.workload)
    if args.rate is not None:
        if mix["arrivals"] == "backlog":
            config["catchup_events_per_s"] = args.rate
            config["dry_run"]["catchup_events_per_s"] = args.rate
        else:
            config["generator"]["first_event_rate"] = args.rate
            config["dry_run"]["first_event_rate"] = args.rate
    if args.fault == "rare_checkpoint":
        config["guarantees"]["checkpoint_frequency"] = 1000
    dry = args.dry_run_cpu
    os.environ.setdefault("RW_BARRIER_TIMEOUT_S", BARRIER_TIMEOUT_S)

    import jax

    from risingwave_tpu.config import enable_compile_cache, select_device

    dev = select_device("cpu" if dry else "tpu")
    if not dry and len(jax.devices()) < cell["chips"]:
        raise SystemExit(
            f"{args.workload} needs {cell['chips']} chips, jax found "
            f"{len(jax.devices())}"
        )
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": cell["chips"] if not dry else 1,
    }
    peaks = load_json("peaks.json")
    if not dry and dev.device_kind not in peaks:
        raise SystemExit(f"no peaks known for device kind {dev.device_kind!r}")
    cache_dir = enable_compile_cache()
    say(
        f"platform={device['platform']} device_kind={device['kind']!r} "
        f"device_count={len(jax.devices())} jax={jax.__version__} "
        f"compile_cache={cache_dir} "
        f"RW_BARRIER_TIMEOUT_S={os.environ['RW_BARRIER_TIMEOUT_S']}"
    )
    if dry:
        say("DRY RUN on cpu at a tiny size: no device number comes from it")

    from compile_meter import CompileMeter
    from system import System

    meter = CompileMeter()
    clock = time.monotonic

    t = clock()
    chunk_rows = config["dry_run"]["chunk_rows"] if dry else config["chunk_rows"]
    events, due, pre_sizes, offered, expected = generate(
        config, mix, args.seed,
        max(args.seconds, args.size_seconds or 0.0), dry, chunk_rows,
    )
    n_pre = sum(pre_sizes)
    plan = feed.FeedPlan(events, chunk_rows, due)
    generate_s = clock() - t
    capacity = capacity_for(
        config["session"]["capacity_rule"],
        expected,
        config["dry_run"]["capacity_floor"] if dry else None,
    )
    say(
        f"cell={args.workload} seed={args.seed} seconds={args.seconds} "
        f"events={plan.n} preload={n_pre} expected_pushed={expected} "
        f"offered_events_per_s={offered:.1f} "
        f"chunk_rows={chunk_rows} capacity={capacity} "
        f"generate_s={generate_s:.3f}"
    )

    why_not = []
    with tempfile.TemporaryDirectory(prefix="bench_state_") as state_dir:
        t = clock()
        system = System(config, capacity, chunk_rows, state_dir,
                        nexmark_gen.VOCAB, nexmark_gen.TEXT)
        reader = probe_client = read_client = None
        try:
            ddl_s = clock() - t
            built = system.describe()
            say(f"ddl_s={ddl_s:.3f} built={json.dumps(built)}")
            why_not += check_expects(config, built)
            target = Faulty(system, args.fault) if args.fault in (
                "dup_chunk", "drop_chunk") else system
            interval_s = mix["interval_ms"] / 1e3
            annotate = jax.profiler.TraceAnnotation

            # preload: the first windows' worth of event time, untimed,
            # in epochs of the sizes the window's will have, so that the
            # window opens on full windows and on shapes already compiled
            t = clock()
            pre = feed.run_preload(
                target, plan, epoch_sizes=pre_sizes, clock=clock,
                sleep=time.sleep,
            )
            if not all(e.ok for e in pre.epochs):
                raise RuntimeError(f"preload: {pre.epochs[-1].error}")
            system.wait_durable()
            preload_s = clock() - t
            setup_compile = meter.take()
            say(
                f"preload_s={preload_s:.3f} preload_epochs={len(pre.epochs)} "
                f"setup_compile_s={setup_compile[0]:.3f} "
                f"setup_programs={setup_compile[1]}"
            )

            probe_client = PgClient(system.port)
            reader = feed.Reader(
                probe_client, config["probe"]["sql"],
                mix["probe_interval_ms"] / 1e3, clock, annotate,
            )
            if isinstance(target, Faulty):
                target.armed = True
            tracer = None
            trace_dir = os.path.join(state_dir, "trace")
            trace_span = {}
            if args.trace:
                def _trace():
                    # device operations and the loop's annotations only:
                    # the python tracer would slow the host it watches
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    time.sleep(min(TRACE_START_S, args.seconds / 3))
                    jax.profiler.start_trace(trace_dir, profiler_options=opts)
                    trace_span["t0"] = clock()
                    time.sleep(min(TRACE_SECONDS, args.seconds / 3))
                    trace_span["t1"] = clock()
                    jax.profiler.stop_trace()
                tracer = threading.Thread(target=_trace, daemon=True)

            # ---- the measured window ----
            t0 = clock()
            setup_s = t0 - T_START
            reader.start()
            if tracer:
                tracer.start()
            win = feed.run_feed(
                target, plan, start=n_pre, t0=t0, seconds=args.seconds,
                interval_s=interval_s,
                max_epoch_chunks=mix["max_epoch_chunks"],
                clock=clock, sleep=time.sleep, annotate=annotate,
            )
            committed, newest = system.wait_durable()
            t_end = clock()
            # ---- window closed ----
            window_compile = meter.take()
            if tracer:
                tracer.join(timeout=120)
            if committed != newest:
                why_not.append(
                    f"window: committed epoch {committed} != barrier "
                    f"epoch {newest}"
                )
            stats = dev.memory_stats() or {}
            memory_peak = int(stats.get("peak_bytes_in_use", 0))

            # one probe that was issued after the last barrier returned
            if win.epochs[-1].ok:
                t_last = win.epochs[-1].t_return
                limit = clock() + 60
                while clock() < limit and not any(
                    r[0] > t_last for r in reader.replies
                ):
                    time.sleep(0.02)
            reader.stop()

            t = clock()
            read_client = PgClient(system.port)
            got_rows = read_client.query(config["mv_read"]["sql"])
            mv_read_s = clock() - t
            state_bytes = system.state_nbytes()
            dictionary_strings = system.dictionary_strings()

            # the streams' own tables: every pushed row is there once,
            # and a sample of rows reads back whole, every column
            t = clock()
            tables = checks.read_tables(
                read_client.query, config.get("table_reads", ()), events,
                plan.cut(win.epochs[-1].position), args.seed,
                nexmark_gen.VOCAB, nexmark_gen.TEXT,
            )
            table_read_s = clock() - t

            trace_summary = None
            if args.trace:
                import trace_reduce

                pb = trace_reduce.find_xplane(trace_dir)
                if pb is None or "t1" not in trace_span:
                    raise RuntimeError("the profiler left no trace")
                trace_summary = trace_reduce.reduce(trace_reduce.load(pb))
                if trace_summary is not None:
                    trace_summary["window_s"] = (
                        trace_span["t1"] - trace_span["t0"]
                    )
        finally:
            if reader is not None and reader.is_alive():
                reader.stop()
            for c in (probe_client, read_client):
                if c is not None:
                    c.close()
            system.close()

    # ---- the plain reference, once the system is closed ----
    t = clock()
    ref = load_module("configs", config["reference"] + ".py")
    all_epochs = [pre.epochs[-1]] + win.epochs
    values = ref.probe(
        events, [plan.cut(e.position) for e in all_epochs], nexmark_gen.VOCAB
    )
    boundaries = [
        (e.position, e.t_inject, e.t_return, tuple(v))
        for e, v in zip(all_epochs, values)
    ]
    shown, problems = checks.match_probes(reader.replies, boundaries)
    final_pos = all_epochs[-1].position
    want_rows = ref.mv(events, plan.cut(final_pos), nexmark_gen.VOCAB)
    cast = [int if ty == "int" else str for ty in config["mv_read"]["types"]]
    got_set = {tuple(c(x) for c, x in zip(cast, r)) for r in got_rows}
    same, only = checks.compare_rows(got_set, want_rows)
    reference_s = clock() - t

    barriers = len(win.epochs)
    barriers_failed = sum(not e.ok for e in win.epochs)
    in_window = [
        i for i, r in enumerate(reader.replies) if r[0] <= t_end
    ]
    probes_failed = sum(shown[i] < 0 for i in in_window)
    probes_failed_after = sum(s < 0 for s in shown) - probes_failed
    if barriers_failed:
        why_not.append(f"barrier failed: {win.epochs[-1].error}")
    if len(got_rows) != len(got_set):
        why_not.append("the MV read returned a row twice")
    if not same:
        why_not.append(f"MV != reference: {only}")
    if not want_rows:
        why_not.append("the reference's MV is empty")
    table_diff = sum(tb["differing"] for tb in tables)
    if table_diff:
        why_not.append(f"tables != events pushed: {tables}")
    if probes_failed or probes_failed_after:
        why_not.append(f"{probes_failed + probes_failed_after} probe replies "
                       f"unsound: {problems[:3]}")
    if not reader.replies:
        why_not.append("no probe was answered")
    ok = not why_not

    # each number compared, beside its limit (all comparisons are exact)
    say(
        f"CHECK mv_rows_differing={len(got_set ^ want_rows)} limit=0 "
        f"(system {len(got_set)} rows, reference {len(want_rows)} rows, "
        f"events {final_pos})"
    )
    say(
        f"CHECK table_rows_differing={table_diff} limit=0 ("
        + "; ".join(
            f"{tb['stream']} holds {tb['count']} of {tb['pushed']} pushed, "
            f"{tb['sampled']} rows of {tb['bytes']} bytes read back"
            for tb in tables
        )
        + ")"
    )
    say(
        f"CHECK probes_unsound={probes_failed + probes_failed_after} limit=0 "
        f"(of {len(reader.replies)} replies over {len(boundaries)} "
        f"boundaries)"
    )
    say(f"CHECK uncommitted_epochs={int(committed != newest)} limit=0 "
        f"(committed {committed}, newest {newest})")
    say(f"CHECK barriers_failed={barriers_failed} limit=0 (of {barriers})")
    for w in why_not:
        say(f"NOT CORRECT: {w}")

    # ---- end-to-end metrics ----
    window_s = t_end - t0
    good = [e for e in win.epochs if e.ok]
    reflected = (good[-1].position - n_pre) if good else 0
    metrics = {
        "events_per_s": {"value": reflected / window_s, "unit": "events/s"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    every = mix["fresh_sample_every"]
    if mix["arrivals"] == "schedule" and good:
        # every event due by the newest boundary when the last epoch was
        # cut; one the admission limit kept out counts as never seen
        sample_stop = good[-1].due_position
    else:
        sample_stop = good[-1].position if good else n_pre
    sample = np.arange(n_pre + every - 1, sample_stop, every)
    due_abs = t0 + plan.due[sample]
    fresh = checks.freshness_ms(reader.replies, shown, boundaries, due_abs,
                                sample)
    p50, _, n_fresh = checks.percentile(fresh, 50)
    p95, q95, _ = checks.percentile(fresh, 95)
    say(
        f"freshness samples={n_fresh} p50_ms={p50:.1f} "
        f"p{q95:g}_ms={p95:.1f} unseen={int(np.isinf(fresh).sum())}"
    )
    fresh_values = {"fresh_p50_ms": p50}
    for m in bench["end_to_end"]:
        if m["name"] in fresh_values and reports(m, args.workload):
            metrics[m["name"]] = {"value": fresh_values[m["name"]],
                                  "unit": m["unit"]}

    probe_ms = [
        (reader.replies[i][1] - reader.replies[i][0]) * 1e3 for i in in_window
    ]
    unpushed_mid = unpushed_end = None
    if mix["arrivals"] == "schedule":
        mid = [e for e in win.epochs if e.t_decide - t0 <= args.seconds / 2]
        if mid:
            unpushed_mid = mid[-1].due_position - mid[-1].position
        unpushed_end = win.epochs[-1].due_position - win.epochs[-1].position
    # epochs that took in more than one interval of the schedule: the
    # system did not hold the configuration's barrier interval there
    gaps = np.diff([e.t_decide for e in win.epochs])
    say(
        f"window_s={window_s:.3f} barriers={barriers} "
        f"epochs_over_interval={int((gaps > 1.5 * interval_s).sum())} "
        f"chunks={win.chunks} "
        f"events_reflected={reflected} probes={len(in_window)} "
        f"probes_per_s={len(in_window) / window_s:.2f} "
        f"unpushed_mid={unpushed_mid} unpushed_end={unpushed_end} "
        f"window_compile_s={window_compile[0]:.3f} "
        f"window_programs={window_compile[1]} mv_read_s={mv_read_s:.3f} "
        f"table_read_s={table_read_s:.3f} "
        f"reference_s={reference_s:.3f} state_bytes={state_bytes} "
        f"events_pushed={win.pushed} capacity={capacity} "
        f"pushed_per_lane={win.pushed / capacity:.3f} "
        f"mv_rows={len(got_set)} dictionary_strings={dictionary_strings} "
        f"memory_peak_bytes={memory_peak} generate_s={generate_s:.3f} "
        f"ddl_s={ddl_s:.3f} preload_s={preload_s:.3f}"
    )
    say("barrier_s=" + json.dumps(
        [round(e.t_return - e.t_inject, 4) for e in win.epochs]))

    device["memory_peak_bytes"] = memory_peak
    result = {
        "correct": bool(ok),
        "attempted": barriers + len(in_window),
        "failed": barriers_failed + probes_failed,
        "metrics": metrics,
        "device": device,
    }
    if args.trace:
        run = {
            "cell": args.workload,
            "config": config,
            "peaks": peaks.get(dev.device_kind),
            "window_s": window_s,
            "events": reflected,
            "chunks": win.chunks,
            "epochs": [
                {
                    "events": e.position - p.position,
                    "t_inject": e.t_inject,
                    "t_return": e.t_return,
                    "stages_ms": stage_dict(e.trace),
                }
                for p, e in zip([pre.epochs[-1]] + win.epochs, win.epochs)
                if e.ok
            ],
            "lags_ms": [x * 1e3 for x in win.lags_s],
            "probe_ms": probe_ms,
            "fresh_ms": fresh.tolist(),
            "compile_s": window_compile[0],
            "programs": window_compile[1],
            "device_trace": trace_summary,
        }
        result["metrics"] = layer_metrics(bench, args.workload, run)
        if trace_summary:
            say(f"trace modules_s={json.dumps(trace_summary['modules'])} "
                f"cycles={trace_summary['cycles']} modules_in_cycles_s="
                f"{json.dumps(trace_summary['modules_in_cycles_s'])}")
            device["busy_s"] = trace_summary["busy_s"]
            device["window_s"] = trace_summary["window_s"]
            result["breakdown"] = trace_summary["breakdown"]
    faulthandler.cancel_dump_traceback_later()
    say(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
