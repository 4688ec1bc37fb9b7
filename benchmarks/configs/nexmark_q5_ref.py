"""Plain reference for nexmark_q5 ("hot items"): for every 10 s window
sliding by 2 s, the auctions with the most bids in it, recomputed from
the bids alone. Imports nothing of the program.

``events`` is {"bid": {"eid": ordinals, "auction": ..., "date_time":
...}}; a prefix is "every bid whose ordinal is < cut". The view's rows
are (auction, num, starttime): the count of an (auction, window) group
that is not below the largest count of its window.
"""

import numpy as np

SIZE_MS, SLIDE_MS = 10_000, 2_000
PER_BID = SIZE_MS // SLIDE_MS  # windows a bid falls into


class _Groups:
    """Every (auction, window start) group the bids ever fall into, and
    per bid and window (row 5 i + k is bid i's k-th window) its group:
    a prefix of n bids is the first 5 n rows."""

    def __init__(self, auction, date_time):
        newest = (date_time // SLIDE_MS) * SLIDE_MS
        start = (
            newest[:, None] - SLIDE_MS * np.arange(PER_BID)[None, :]
        ).ravel()
        who = np.repeat(auction, PER_BID)
        self.starts, w = np.unique(start, return_inverse=True)
        ids, a = np.unique(who, return_inverse=True)
        group, self.row_group = np.unique(
            w * len(ids) + a, return_inverse=True
        )
        self.window = group // len(ids)  # index into starts, per group
        self.auction = ids[group % len(ids)]

    def hot(self, bids: int):
        """(auction, num, starttime) of the groups, over the first
        ``bids`` bids, whose count is not below their window's largest."""
        num = np.bincount(
            self.row_group[: PER_BID * bids], minlength=len(self.window)
        )
        most = np.zeros(len(self.starts), np.int64)
        np.maximum.at(most, self.window, num)
        keep = (num > 0) & (num >= most[self.window])
        return (
            self.auction[keep], num[keep], self.starts[self.window[keep]]
        )


def _prefixes(events, cuts):
    b = events["bid"]
    groups = _Groups(b["auction"], b["date_time"]) if len(b["eid"]) else None
    for cut in cuts:
        n = int(np.searchsorted(b["eid"], cut, side="left"))
        if groups is None or n == 0:
            z = np.zeros(0, np.int64)
            yield z, z, z
        else:
            yield groups.hot(n)


def mv(events, cut, vocab=None):
    """The whole MV over the prefix: {(auction, num, starttime)}."""
    ((who, num, start),) = _prefixes(events, [cut])
    return set(zip(who.tolist(), num.tolist(), start.tolist()))


def probe(events, cuts, vocab=None):
    """``SELECT count(*), sum(num), max(starttime) FROM q5`` at each
    prefix; an empty view reads (0, 0, 0), as the harness's reader
    turns the NULLs of an empty aggregate into 0."""
    return [
        (len(num), int(num.sum()), int(start.max())) if len(num) else (0, 0, 0)
        for _, num, start in _prefixes(events, cuts)
    ]
