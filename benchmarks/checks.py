"""What decides ``correct``, and the end-to-end arithmetic.

Every comparison here is exact (limit 0): counts, 64-bit keys and
decoded strings either equal the reference's or they do not.
"""

from __future__ import annotations

import numpy as np


def match_probes(replies, boundaries):
    """Map every probe reply to the epoch boundary it shows.

    ``boundaries`` is [(position, t_inject, t_return, reference probe
    value)] in epoch order (the preload's last barrier first).
    ``replies`` is [(t_issue, t_reply, value, error)]. A reply is sound
    when its value is the reference's at a boundary injected before the
    reply (where several boundaries share the value, the newest of
    them) and that boundary is no older than the last one that had
    returned when the probe went out.

    Returns (shown, problems): ``shown[i]`` is the boundary's index or
    -1, ``problems`` lists what was wrong with each unsound reply."""
    by_value = {}
    for k, (_, _, _, value) in enumerate(boundaries):
        by_value.setdefault(value, []).append(k)
    t_inject = np.array([b[1] for b in boundaries])
    t_return = np.array([b[2] for b in boundaries])
    shown, problems = [], []
    for i, (t_issue, t_reply, value, error) in enumerate(replies):
        if value is None:
            shown.append(-1)
            problems.append(f"probe {i}: {error}")
            continue
        known = [k for k in by_value.get(value, ()) if t_inject[k] <= t_reply]
        if not known:
            shown.append(-1)
            problems.append(
                f"probe {i}: reply {value} is no epoch boundary's value"
            )
            continue
        k = known[-1]
        # boundaries return in order, so this counts those returned
        readable = int(np.searchsorted(t_return, t_issue, side="right")) - 1
        if k < readable:
            shown.append(-1)
            problems.append(
                f"probe {i}: shows epoch {k}, but epoch {readable} had "
                "returned before it was issued"
            )
            continue
        shown.append(k)
    return shown, problems


def freshness_ms(replies, shown, boundaries, due_abs, sample_positions):
    """Event -> readable, in ms, for each sampled event: the reply time
    of the first sound probe that shows a boundary which includes the
    event, minus the time the event was due. Events no probe shows come
    back as inf."""
    t_reply = np.array([r[1] for r in replies])
    position = np.array(
        [boundaries[k][0] if k >= 0 else -1 for k in shown], dtype=np.int64
    )
    order = np.argsort(t_reply, kind="stable")
    out = np.full(len(sample_positions), np.inf)
    if len(order) == 0:
        return out
    # the most any probe replied by then has shown: non-decreasing
    best = np.maximum.accumulate(position[order])
    first = np.searchsorted(best, sample_positions, side="right")
    seen = first < len(best)
    out[seen] = (
        t_reply[order][first[seen]] - np.asarray(due_abs)[seen]
    ) * 1e3
    return out


def percentile(values, q: float):
    """The q-th percentile, lowered to the highest with ten samples
    beyond it. Returns (value, percentile used, sample count)."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    n = len(v)
    if n == 0:
        return float("nan"), q, 0
    q = min(q, 100.0 * max(n - 11, 0) / n) if n > 11 else min(q, 50.0)
    return float(np.percentile(v, q)), q, n


def compare_rows(got: set, want: set, limit: int = 5):
    """(equal, a few rows only one side has)."""
    if got == want:
        return True, []
    only = [("system only", r) for r in sorted(got - want)[:limit]]
    only += [("reference only", r) for r in sorted(want - got)[:limit]]
    return False, only


def table_sample(cols, key: str, pushed: int, seed: int, rows: int):
    """Which rows of a stream's table a run reads back: ``rows``
    consecutive pushed events drawn from the seed, as the half-open
    range of their keys. (lo, hi, positions of the pushed events whose
    key lies in it)."""
    if pushed <= 0:
        return 0, 0, np.zeros(0, np.int64)
    j = (int(seed) * 2654435761 + 12345) % max(pushed - rows, 1)
    k = cols[key][:pushed]
    lo, hi = int(k[j]), int(k[min(j + rows, pushed) - 1]) + 1
    return lo, hi, np.flatnonzero((k >= lo) & (k < hi))


def table_rows(cols, stream: str, columns, at, vocab: dict, text: set):
    """The rows at positions ``at`` as a text-format read returns them:
    tuples of str, vocabulary columns decoded."""
    out = []
    for name in columns:
        v = cols[name][at]
        if (stream, name) in vocab:
            words = vocab[(stream, name)]
            out.append([words[i] for i in v.tolist()])
        elif (stream, name) in text:
            out.append(v.tolist())
        else:
            out.append([str(x) for x in v.tolist()])
    return sorted(zip(*out))


def read_tables(query, specs, events, cut, seed, vocab, text) -> list:
    """Each stream's own table against the events pushed (ordinals
    below ``cut``): its row count, and a sample of rows in every
    column. ``specs`` is the configuration's ``table_reads``; ``query``
    runs one SQL statement and returns text rows."""
    out = []
    for spec in specs:
        cols = events[spec["stream"]]
        pushed = int(np.searchsorted(cols["eid"], cut, "left"))
        lo, hi, at = table_sample(
            cols, spec["key"], pushed, seed, spec["sample_rows"]
        )
        count = int(query(spec["count_sql"])[0][0])
        got = sorted(
            tuple("" if x is None else str(x) for x in r)
            for r in query(spec["rows_sql"].format(lo=lo, hi=hi))
        )
        want = table_rows(cols, spec["stream"], spec["columns"], at,
                          vocab, text)
        out.append({
            "stream": spec["stream"], "count": count, "pushed": pushed,
            "sampled": len(want),
            "bytes": sum(len(x) for r in want for x in r),
            "differing": abs(count - pushed) + rows_differing(got, want),
        })
    return out


def rows_differing(got, want) -> int:
    """Rows only one of two sorted lists has, a row twice counted twice."""
    from collections import Counter

    a, b = Counter(got), Counter(want)
    return sum(((a - b) + (b - a)).values())
