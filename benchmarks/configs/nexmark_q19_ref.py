"""Plain reference for nexmark_q19 ("auction TOP-10 price"): for every
auction among the bids, its ten bids of the highest ``price``, numbered
1 to 10 from the highest down, and among bids of one ``price`` the one
that arrived first ranked first, recomputed from the bids alone.
Imports nothing of the program.

``events`` is {"bid": {"eid": ordinals, "auction", "bidder", "price",
"channel" (indices into ``vocab[("bid", "channel")]``), "date_time",
"extra" (text)}}; bids arrive in the order of their ordinals, and a
prefix is "every bid whose ordinal is < cut". The view's rows are
(auction, bidder, price, channel, date_time, extra, rank_number).
"""

import numpy as np

K = 10  # WHERE rank_number <= 10


class _Ranked:
    """All the bids in the order (auction, price DESC, arrival): the
    rank of a bid within a prefix is its place among the bids of its
    auction that arrived inside the prefix, which one running count
    over this order gives for any prefix."""

    def __init__(self, bids):
        n = len(bids["eid"])
        arrival = np.arange(n)
        self.order = np.lexsort((arrival, -bids["price"], bids["auction"]))
        a = bids["auction"][self.order]
        first = np.ones(n, bool)
        first[1:] = a[1:] != a[:-1]
        self.start = np.maximum.accumulate(np.where(first, np.arange(n), 0))

    def top(self, n_prefix):
        """(positions in arrival order, ranks) of the view's rows over
        the first ``n_prefix`` bids."""
        inside = self.order < n_prefix
        seen = np.cumsum(inside)
        # bids of the auction inside the prefix up to and with this one
        before = np.concatenate([[0], seen])[self.start]
        rank = seen - before
        keep = inside & (rank <= K)
        return self.order[keep], rank[keep]


def _prefix(events, cut):
    b = events["bid"]
    return b, int(np.searchsorted(b["eid"], cut, side="left"))


def mv(events, cut, vocab):
    """The whole MV over the prefix."""
    b, n = _prefix(events, cut)
    if n == 0:
        return set()
    at, rank = _Ranked(b).top(n)
    channels = vocab[("bid", "channel")]
    return set(
        zip(
            b["auction"][at].tolist(),
            b["bidder"][at].tolist(),
            b["price"][at].tolist(),
            (channels[i] for i in b["channel"][at].tolist()),
            b["date_time"][at].tolist(),
            (str(x) for x in b["extra"][at]),
            rank.tolist(),
        )
    )


def probe(events, cuts, vocab=None):
    """``SELECT count(*), max(date_time), sum(price), sum(rank_number)
    FROM q19`` at each prefix; an empty view reads (0, 0, 0, 0), as the
    harness's reader turns the NULLs of an empty aggregate into 0. The
    last term moves whenever a rank does, so a view whose rows are
    right and whose ranks are stale shows in every probe."""
    b = events["bid"]
    if len(b["eid"]) == 0:
        return [(0, 0, 0, 0) for _ in cuts]
    ranked = _Ranked(b)
    out = []
    for cut in cuts:
        n = int(np.searchsorted(b["eid"], cut, side="left"))
        at, rank = ranked.top(n)
        if len(at) == 0:
            out.append((0, 0, 0, 0))
            continue
        out.append((
            len(at),
            int(b["date_time"][at].max()),
            int(b["price"][at].sum()),
            int(rank.sum()),
        ))
    return out
