"""Plain reference for nexmark_q7 ("highest bid"): the bids bucketed
into ten-second tumbling windows by ``date_time // 10 s``, each
window's highest ``price``, and for every window the bids of that price
whose ``date_time`` lies in ``[window_end - 10 s, window_end]``, both
ends included — so a bid stamped exactly on a window's end, which
belongs to the NEXT window, also stands under the window that ends
there when it carries that window's maximum, and every bid that ties a
maximum stands beside the one that set it. Recomputed from the bids
alone; imports nothing of the program.

``events`` is {"bid": {"eid": ordinals, "auction", "bidder", "price",
"date_time", ...}}; bids arrive in the order of their ordinals, and a
prefix is "every bid whose ordinal is < cut". The view's rows are
(auction, price, bidder, date_time, window_end).
"""

import numpy as np

SIZE = 10_000  # TUMBLE(bid, date_time, INTERVAL '10' SECOND), in ms


class _ByTime:
    """The bids in the order of their ``date_time``, so that a window
    and its band are each one run of them."""

    def __init__(self, bids):
        self.order = np.argsort(bids["date_time"], kind="stable")
        self.when = bids["date_time"][self.order]
        self.price = bids["price"][self.order]

    def pairs(self, n_prefix):
        """(positions in arrival order, window ends) of the view's rows
        over the first ``n_prefix`` bids."""
        inside = self.order < n_prefix
        when, price = self.when[inside], self.price[inside]
        at = self.order[inside]
        rows, ends = [], []
        for w in np.unique(when // SIZE).tolist():
            end = (w + 1) * SIZE
            lo, mid = np.searchsorted(when, [end - SIZE, end], side="left")
            hi = np.searchsorted(when, end, side="right")
            best = price[lo:mid].max()  # the window holds [start, end)
            hit = lo + np.flatnonzero(price[lo:hi] == best)  # the band
            rows.append(at[hit])
            ends.append(np.full(len(hit), end, np.int64))
        if not rows:
            return np.zeros(0, np.int64), np.zeros(0, np.int64)
        return np.concatenate(rows), np.concatenate(ends)


def _prefix(events, cut):
    b = events["bid"]
    return b, int(np.searchsorted(b["eid"], cut, side="left"))


def mv(events, cut, vocab=None):
    """The whole MV over the prefix."""
    b, n = _prefix(events, cut)
    at, ends = _ByTime(b).pairs(n)
    return set(
        zip(
            b["auction"][at].tolist(),
            b["price"][at].tolist(),
            b["bidder"][at].tolist(),
            b["date_time"][at].tolist(),
            ends.tolist(),
        )
    )


def probe(events, cuts, vocab=None):
    """``SELECT count(*), sum(price), max(date_time) FROM q7`` at each
    prefix; an empty view reads (0, 0, 0), as the harness's reader
    turns the NULLs of an empty aggregate into 0."""
    b = events["bid"]
    by_time = _ByTime(b)
    out = []
    for cut in cuts:
        at, _ = by_time.pairs(int(np.searchsorted(b["eid"], cut, side="left")))
        if len(at) == 0:
            out.append((0, 0, 0))
            continue
        out.append((
            len(at), int(b["price"][at].sum()), int(b["date_time"][at].max())
        ))
    return out
