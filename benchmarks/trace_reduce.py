"""From a profiler trace to numbers: device busy time, the device time
of each XLA module (one jitted program), the operations that took most
time, and the idle gaps by what the host was doing.

On the v5e the trace's device plane (``/device:TPU:0``) has a line
``XLA Modules`` (one event per program run, named ``jit_<fn>(<hash>)``)
and a line ``XLA Ops`` (one event per HLO instruction, named by its HLO
text); neither carries the ``jax.named_scope`` path in what
``ProfileData`` exposes, so time is attributed by module, not by scope.

``records`` everywhere is a list of
(plane, line, name, start_ns, duration_ns, text) — ``text`` is the
event's name and its string stats joined. ``load`` makes them from an
``.xplane.pb`` with nothing but jax; the tests feed a small recorded
list (tests/data/trace_small.json).
"""

from __future__ import annotations

import bisect
import glob
import os
import re

HOST_PREFIX = "bench/"  # the loop's own TraceAnnotations
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str):
    found = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    return max(found, key=os.path.getmtime) if found else None


def load(path: str):
    """Device-plane events and the loop's annotations of one trace."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIX):
                    continue
                text = ev.name
                if device:
                    text += " " + " ".join(
                        v for _, v in ev.stats if isinstance(v, str)
                    )
                out.append(
                    (plane.name, line.name, ev.name, float(ev.start_ns),
                     float(ev.duration_ns), text)
                )
    return out


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def device_planes(records):
    return sorted({r[0] for r in records if r[0].startswith("/device:")})


def op_records(records, plane):
    """The events that are operations running on one device: the
    'XLA Ops' line where the plane has one, else every line of it."""
    mine = [r for r in records if r[0] == plane]
    ops = [r for r in mine if r[1] == OPS_LINE]
    return ops or mine


MODULES_LINE = "XLA Modules"


def _short(name: str) -> str:
    """'%while.54 = (...) while(...)' -> 'while.54'; module names lose
    their '(fingerprint)'."""
    m = re.match(r"%?([\w.\-]+)", name)
    return m.group(1) if m else name[:40]


def reduce(records, top=10):
    """Summary of one traced span. Times in seconds. ``busy_s`` is the
    union of the intervals in which an operation ran, averaged over the
    device planes; ``modules`` the device time of every XLA module (one
    jitted program) by name, the ``top`` largest; ``cycles`` the
    complete epochs (barrier end to barrier end, by the loop's own
    annotations) the trace holds and ``modules_in_cycles_s`` the device
    time of every module inside them; ``breakdown`` as the benchmark's
    contract asks, each operation named module/op."""
    planes = device_planes(records)
    if not planes:
        return None
    busy_total, by_op, by_module = 0.0, {}, {}
    module_events, gaps = [], []
    for plane in planes:
        ops = op_records(records, plane)
        merged = _union((r[3], r[3] + r[4]) for r in ops)
        busy_total += sum(e - s for s, e in merged)
        gaps += [
            (a[1], b[0]) for a, b in zip(merged, merged[1:]) if b[0] > a[1]
        ]
        modules = sorted(
            (r[3], r[3] + r[4], _short(r[2]))
            for r in records
            if r[0] == plane and r[1] == MODULES_LINE
        )
        starts = [m[0] for m in modules]
        for m_start, m_end, name in modules:
            by_module[name] = by_module.get(name, 0.0) + (m_end - m_start)
            module_events.append((m_start, m_end - m_start, name))
        for _, _, name, start, dur, _ in ops:
            i = bisect.bisect_right(starts, start) - 1
            inside = i >= 0 and start < modules[i][1]
            key = (modules[i][2] if inside else "-") + "/" + _short(name)
            by_op[key] = by_op.get(key, 0.0) + dur
    host = [r for r in records if r[2].startswith(HOST_PREFIX)]
    phases = sorted((r[3], r[3] + r[4], r[2]) for r in host)

    def phase_at(t):
        best = "unannotated"
        for s, e, name in phases:
            if s <= t < e and name != HOST_PREFIX + "probe":
                best = name
        return best

    by_phase = {}
    for s, e in gaps:
        name = phase_at((s + e) / 2)
        by_phase[name] = by_phase.get(name, 0.0) + (e - s)

    barrier_ends = sorted(
        r[3] + r[4] for r in host if r[2] == HOST_PREFIX + "barrier"
    )
    cycles, in_cycles = 0, {}
    if len(barrier_ends) >= 2:
        lo, hi = barrier_ends[0], barrier_ends[-1]
        cycles = len(barrier_ends) - 1
        for s, d, name in module_events:
            if lo <= s < hi:
                in_cycles[name] = in_cycles.get(name, 0.0) + d

    def ranked(d):
        return [
            [k, v / 1e9]
            for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]
        ]

    return {
        "devices": len(planes),
        "busy_s": busy_total / len(planes) / 1e9,
        "modules": dict(ranked(by_module)),
        "cycles": cycles,
        "modules_in_cycles_s": {
            k: v / len(planes) / 1e9 for k, v in in_cycles.items()
        },
        "breakdown": {
            "device_ops": ranked(by_op),
            "idle_gaps": ranked(by_phase),
        },
    }
