"""Plain reference for nexmark_q8: persons who opened an auction in the
10 s tumbling window in which they registered, recomputed from the
events alone. Imports nothing of the program.

``events`` is {stream: {"eid": ordinals, column: values}}; a prefix is
"every event whose ordinal is < cut". ``vocab[("person", "name")]``
turns the name column's indices into strings.
"""

import numpy as np

WINDOW_MS = 10_000


def _first_seen(ids, window, w0, span):
    """Sorted packed (id, window) keys and the position of the first
    event that carries each."""
    if int(ids.max()) * span >= 2**62 or int(ids.min()) < 0:
        raise OverflowError("id x window does not pack into 63 bits")
    return np.unique(ids * span + (window - w0), return_index=True)


def _joined(events):
    """Every (id, window) that has a person and an auction: the ordinal
    at which the later of the two arrived, and the person's row."""
    p, a = events["person"], events["auction"]
    if len(p["eid"]) == 0 or len(a["eid"]) == 0:
        z = np.zeros(0, np.int64)
        return z, z, z, z
    pw, aw = p["date_time"] // WINDOW_MS, a["date_time"] // WINDOW_MS
    w0 = int(min(pw.min(), aw.min()))
    span = int(max(pw.max(), aw.max())) - w0 + 1
    pk, ppos = _first_seen(p["id"], pw, w0, span)
    ak, apos = _first_seen(a["seller"], aw, w0, span)
    _, ip, ia = np.intersect1d(pk, ak, return_indices=True)
    prow = ppos[ip]
    entered = np.maximum(p["eid"][prow], a["eid"][apos[ia]])
    start = (pk[ip] % span + w0) * WINDOW_MS
    return entered, p["id"][prow], p["name"][prow], start


def mv(events, cut, vocab):
    """The whole MV over the prefix: {(id, name, starttime)}."""
    entered, pid, name, start = _joined(events)
    keep = entered < cut
    names = vocab[("person", "name")]
    return set(
        zip(
            pid[keep].tolist(),
            (names[i] for i in name[keep].tolist()),
            start[keep].tolist(),
        )
    )


def probe(events, cuts, vocab=None):
    """``SELECT count(*) FROM q8`` at each prefix."""
    entered = np.sort(_joined(events)[0])
    return [
        (int(np.searchsorted(entered, cut, side="left")),) for cut in cuts
    ]
