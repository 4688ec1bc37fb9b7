"""Where the chip waits, by the program's own spans.

Runs one traced benchmark cell in this process (``benchmarks/run.py`` as
it stands, with its own arguments), keeps the profiler's ``.xplane.pb``
the harness would delete, and reduces it: the device's idle gaps (the
complement of the union of ``XLA Ops``) split by the innermost ``rw/``
span open on the feed loop's thread, beside the harness's own ``bench/``
phases, and for every other thread that carries ``rw/`` spans what it
was in while the device idled, and whether it worked or waited there
(the ``wait`` argument of the innermost ``rw/`` event: a gap under a
span with none is the host working, under ``device`` the host waiting
on the device). From the program's span ring it adds, per epoch, the
summed milliseconds of every span name, so that a step in the barriers'
times can be put on a stage, and the barrier's critical path
(``trace.barrier_path``) of every epoch of the window beside the stages
it has to account for, with the ``by_span`` table of the median and of
the slowest barrier printed.

    python scripts/span_gaps.py --out chiprun_out/gaps_steady.json -- \\
        --workload nexmark_q8.steady --seed 4300000001 --seconds 40 --trace 1

Everything after ``--`` goes to the harness unchanged. ``reduce <pb>``
reduces an xplane that was kept earlier. Not part of the benchmark: the
ledger's ``idle_gaps`` come from ``benchmarks/trace_reduce.py``, which
keeps host events named ``bench/`` only.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import runpy
import shutil
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OPS_LINE = "XLA Ops"


def load(path: str):
    """(device op intervals per device plane, host lines): a host line
    is the list of (start_ns, end_ns, name, stats) of its ``rw/`` and
    ``bench/`` events; lines without any are left out."""
    from jax.profiler import ProfileData

    devices, lines = {}, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            mine = list(plane.lines)
            ops = [ln for ln in mine if ln.name == OPS_LINE] or mine
            devices[plane.name] = [
                (float(ev.start_ns), float(ev.start_ns + ev.duration_ns))
                for ln in ops
                for ev in ln.events
            ]
            continue
        for ln in plane.lines:
            evs = [
                (
                    float(ev.start_ns),
                    float(ev.start_ns + ev.duration_ns),
                    ev.name,
                    {k: v for k, v in ev.stats},
                )
                for ev in ln.events
                if ev.name.startswith(("rw/", "bench/"))
            ]
            if evs:
                lines.append(evs)
    return devices, lines


def union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def innermost(events):
    """Flatten nested (start, end, name) spans of one thread into
    non-overlapping segments named by the innermost span open."""
    out, stack, t = [], [], 0.0

    def emit(upto):
        nonlocal t
        if stack and upto > t:
            out.append((t, upto, stack[-1][2]))
        t = max(t, upto)

    for ev in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][1] <= ev[0]:
            emit(stack[-1][1])
            stack.pop()
        if stack:
            emit(ev[0])
        t = max(t, ev[0]) if stack else ev[0]
        stack.append(ev)
    while stack:
        emit(stack[-1][1])
        stack.pop()
    return out


def overlap_by_name(segments, gaps):
    """Seconds of ``gaps`` under each segment name (both sorted)."""
    out, j = {}, 0
    for s, e, name in segments:
        while j < len(gaps) and gaps[j][1] <= s:
            j += 1
        k = j
        while k < len(gaps) and gaps[k][0] < e:
            lo, hi = max(s, gaps[k][0]), min(e, gaps[k][1])
            if hi > lo:
                out[name] = out.get(name, 0.0) + (hi - lo) / 1e9
            k += 1
    return out


def intersect(a, b):
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def by_wait(evs, gaps) -> dict:
    """Seconds of ``gaps`` by what the thread's innermost ``rw/`` span
    waited for: ``working`` where it says nothing."""
    flat = innermost([
        (s, e, stats.get("wait", "working"))
        for s, e, name, stats in evs if name.startswith("rw/")
    ])
    return overlap_by_name(flat, gaps)


def evs_of(evs, prefix):
    return [e[:3] for e in evs if e[2].startswith(prefix)]


def thread_label(evs) -> str:
    names = {e[2] for e in evs}
    if "bench/feed" in names or "bench/barrier" in names:
        return "feed+barrier"
    if "bench/probe" in names:
        return "probe"
    for _s, _e, name, stats in evs:
        if name in ("rw/actor.barrier", "rw/actor.chunk") and "actor" in stats:
            return f"actor:{stats['actor']}"
    if "rw/checkpoint.commit" in names:
        return "checkpoint-worker"
    return "other:" + sorted(names)[0]


def reduce_xplane(path: str) -> dict:
    devices, lines = load(path)
    if not devices:
        return {"error": "no device plane in the trace"}
    ops = [iv for plane in devices.values() for iv in plane]
    merged = union(ops)
    busy = sum(e - s for s, e in merged) / 1e9
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] > a[1]]
    idle = sum(e - s for s, e in gaps) / 1e9
    report = {
        "xplane": path,
        "device_busy_s": busy,
        "device_idle_in_gaps_s": idle,
        "threads": {},
    }
    for evs in lines:
        label = thread_label(evs)
        flat = innermost([e[:3] for e in evs])
        by_span = overlap_by_name(flat, gaps)
        entry = {
            "events": len(evs),
            "epochs_seen": len(
                {e[3]["epoch"] for e in evs if "epoch" in e[3]}
            ),
            "idle_gap_s_by_innermost_span": dict(
                sorted(by_span.items(), key=lambda kv: -kv[1])
            ),
            "idle_gap_s_by_wait": by_wait(evs, gaps),
        }
        if label == "feed+barrier":
            bench = innermost(evs_of(evs, "bench/"))
            by_phase = overlap_by_name(bench, gaps)
            entry["idle_gap_s_by_bench_phase"] = by_phase
            # of what the harness books under its own two phases, the
            # part that lies under a span of the program
            booked = by_phase.get("bench/barrier", 0.0) + by_phase.get(
                "bench/feed", 0.0
            )
            rw = union((s_, e_) for s_, e_, n in evs_of(evs, "rw/"))
            two = union(
                (s_, e_) for s_, e_, n in bench
                if n in ("bench/barrier", "bench/feed")
            )
            inside = overlap_by_name(
                [(s_, e_, "rw") for s_, e_ in intersect(rw, two)], gaps
            ).get("rw", 0.0)
            # the harness's own rule, for the ledger's numbers: a whole
            # gap goes to the phase open at its midpoint
            by_mid = {}
            for s_, e_ in gaps:
                mid = (s_ + e_) / 2
                name = next(
                    (n for a, b, n in bench if a <= mid < b), "unannotated"
                )
                by_mid[name] = by_mid.get(name, 0.0) + (e_ - s_) / 1e9
            entry["idle_gap_s_by_bench_phase_at_midpoint"] = by_mid
            entry["booked_under_bench_barrier_and_feed_s"] = booked
            entry["of_it_under_an_rw_span_s"] = inside
            entry["share_under_an_rw_span"] = (
                inside / booked if booked else None
            )
        key, n = label, 1
        while key in report["threads"]:
            n += 1
            key = f"{label}#{n}"
        report["threads"][key] = entry
    return report


def ring_epochs() -> list:
    """Per epoch of the program's own span ring (oldest first): every
    span name's summed ms, and the rows the counts carry."""
    from risingwave_tpu.trace import TRACER

    by_epoch: dict = {}
    for sp in TRACER.spans():
        if sp.epoch is None or sp.dur is None:
            continue
        ep = by_epoch.setdefault(
            sp.epoch, {"t0": sp.t0, "ms": {}, "n": {}, "count": {}}
        )
        ep["t0"] = min(ep["t0"], sp.t0)
        name = sp.name
        if name in ("checkpoint.marks", "checkpoint.pull",
                    "checkpoint.upload", "mv.apply"):
            name = f"{name}[{sp.args.get('table_id')}]"
        elif name in ("push", "barrier.fragment", "dispatch.drain",
                      "dispatch.flush"):
            name = f"{name}[{sp.args.get('fragment')}]"
        elif name in ("actor.chunk", "actor.barrier"):
            name = f"{name}[{sp.args.get('actor')}]"
        ep["ms"][name] = ep["ms"].get(name, 0.0) + sp.dur * 1e3
        ep["n"][name] = ep["n"].get(name, 0) + 1
        for k in ("rows", "padded_rows", "strings", "total_strings", "bytes",
                  "permits"):
            v = sp.args.get(k)
            if isinstance(v, (int, float)):
                c = ep["count"].setdefault(name, {})
                c[k] = c.get(k, 0) + v
    out = []
    for epoch, ep in sorted(by_epoch.items(), key=lambda kv: kv[1]["t0"]):
        out.append({
            "epoch": epoch,
            "ms": {k: round(v, 3) for k, v in sorted(ep["ms"].items())},
            "n": ep["n"],
            "counts": ep["count"],
        })
    return out


def summarize_ring(epochs: list) -> dict:
    """Medians over the ring's complete epochs (those with a barrier)."""
    whole = [e for e in epochs if "barrier" in e["ms"]]
    names = sorted({k for e in whole for k in e["ms"]})
    return {
        "epochs": len(whole),
        "median_ms_per_epoch": {
            k: round(statistics.median(e["ms"].get(k, 0.0) for e in whole), 3)
            for k in names
        },
    }


# the stages of the barrier's thread that a barrier's path accounts for
PATH_STAGES = ("dispatch", "checkpoint_stage", "publish", "bookkeeping")


def window_paths(window) -> dict:
    """``trace.barrier_path`` of every epoch of the window (as the
    benchmark's own ``readers/epoch_spans.py`` finds them), each beside
    the summed ``PATH_STAGES`` spans of the same epoch, and the
    ``by_span`` table of the median and of the slowest barrier."""
    from readers.epoch_spans import window_epochs  # benchmarks/ is on the path
    from risingwave_tpu.trace import TRACER, barrier_path

    spans = TRACER.spans()
    epochs = window_epochs(
        {"epochs": [
            {"t_inject": e.t_inject, "t_return": e.t_return, "events": 0}
            for e in window if e.ok
        ]},
        spans,
    )
    rows = []
    for sp in spans:
        if sp.name != "barrier" or sp.epoch not in epochs:
            continue
        path = barrier_path(sp.epoch, spans)
        if path is None:
            continue
        stages = dict.fromkeys(PATH_STAGES, 0.0)
        for x in spans:
            if x.epoch == sp.epoch and x.tid == sp.tid and x.stage in stages:
                stages[x.stage] += x.dur * 1e3
        rows.append({"epoch": sp.epoch, "stages_ms": stages, **path})
    if not rows:
        return {"epochs": 0}
    by_wall = sorted(rows, key=lambda r: r["wall_ms"])
    kinds = sorted(rows[0]["by_kind"])
    accounted = [
        sum(v for k, v in r["by_kind"].items() if k != "unattributed")
        for r in rows
    ]
    staged = [sum(r["stages_ms"].values()) for r in rows]
    return {
        "epochs": len(rows),
        "median_by_kind_ms": {
            k: statistics.median(r["by_kind"][k] for r in rows) for k in kinds
        },
        "median_wall_ms": statistics.median(r["wall_ms"] for r in rows),
        "median_accounted_ms": statistics.median(accounted),
        "median_stages_ms": statistics.median(staged),
        "worst_sum_error": max(
            abs(sum(r["by_kind"].values()) - r["wall_ms"]) / r["wall_ms"]
            for r in rows
        ),
        "worst_accounted_over_stages": max(
            abs(a / s - 1.0) for a, s in zip(accounted, staged) if s
        ),
        "median_barrier": by_wall[len(by_wall) // 2],
        "slowest_barrier": by_wall[-1],
        "every_epoch": [
            {k: r[k] for k in ("epoch", "wall_ms", "by_kind", "stages_ms")}
            for r in rows
        ],
    }


def print_path(title: str, row: dict) -> None:
    print(f"BARRIER_PATH {title}: epoch {row['epoch']} "
          f"wall {row['wall_ms']:.1f} ms, actors {row['actors']}")
    print("  " + "  ".join(f"{k} {v:.1f}" for k, v in row["by_kind"].items()))
    for name, kind, ms in row["by_span"][:12]:
        print(f"  {ms:9.2f} ms  {kind:12s} {name}")


def run_cell(harness_args, out_path: str) -> int:
    import jax

    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))  # as a script has it
    import feed  # the harness's own module: its window is kept below

    kept = {}
    run_feed = feed.run_feed

    def keep_window(*a, **kw):
        kept["window"] = run_feed(*a, **kw)
        return kept["window"]

    feed.run_feed = keep_window
    start, stop = jax.profiler.start_trace, jax.profiler.stop_trace

    def start_trace(log_dir, *a, **kw):
        kept["dir"] = log_dir
        return start(log_dir, *a, **kw)

    def stop_trace():
        stop()
        found = glob.glob(os.path.join(
            kept.get("dir", ""), "plugins", "profile", "*", "*.xplane.pb"
        ))
        if found:
            dst = os.path.splitext(out_path)[0] + ".xplane.pb"
            shutil.copy(max(found, key=os.path.getmtime), dst)
            kept["pb"] = dst

    jax.profiler.start_trace, jax.profiler.stop_trace = start_trace, stop_trace
    sys.argv = [os.path.join(ROOT, "benchmarks", "run.py"), *harness_args]
    rc = 0
    try:
        runpy.run_path(sys.argv[0], run_name="__main__")
    except SystemExit as e:
        rc = int(e.code or 0)
    finally:
        jax.profiler.start_trace, jax.profiler.stop_trace = start, stop
        feed.run_feed = run_feed
    report = {"harness_args": harness_args, "harness_rc": rc}
    if "pb" in kept:
        report.update(reduce_xplane(kept["pb"]))
        if os.path.getsize(kept["pb"]) > 24 << 20:
            os.remove(kept["pb"])  # too big to bring back: the report stays
    epochs = ring_epochs()
    report["ring"] = summarize_ring(epochs)
    report["ring_epochs"] = epochs
    if "window" in kept:
        report["paths"] = window_paths(kept["window"].epochs)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1, default=str)
    brief = {k: v for k, v in report.items() if k != "ring_epochs"}
    paths = brief.pop("paths", {"epochs": 0})
    print("SPAN_GAPS " + json.dumps(brief, default=str))
    if paths["epochs"]:
        print("BARRIER_PATHS " + json.dumps(
            {k: v for k, v in paths.items()
             if k not in ("median_barrier", "slowest_barrier", "every_epoch")}
        ))
        print_path("median", paths["median_barrier"])
        print_path("slowest", paths["slowest_barrier"])
    return rc


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "reduce":
        print(json.dumps(reduce_xplane(argv[1]), indent=1))
        return 0
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("harness", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    rest = [a for a in args.harness if a != "--"]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    return run_cell(rest, args.out)


if __name__ == "__main__":
    sys.exit(main())
