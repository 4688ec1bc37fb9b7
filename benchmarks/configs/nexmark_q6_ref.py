"""Plain reference for nexmark_q6 ("average selling price by seller"):
every bid joined to the auctions of its id while ``A.date_time <=
B.date_time <= A.expires``; of each (auction id, seller) the ONE pair
that comes first by (price ASCENDING, arrival) — the source orders by
``B.price`` as written, so an auction's row is its LOWEST in-lifetime
bid; each seller's kept rows in the order of (the kept bid's date_time,
arrival), and for each row the SUM and the COUNT of the kept prices of
that row and of up to ten rows before it. Recomputed from the events
alone, in integers; imports nothing of the program.

``events`` is {"auction": {"eid", "id", "date_time", "expires",
"seller", ...}, "bid": {"eid", "auction", "price", "date_time", ...}},
each sorted by ``eid``; rows arrive in that order, and a prefix is
"every event whose ordinal is < cut". A pair exists in a prefix once
both its rows do. Arrival, every order's last tie-break, is the pair's
stream key: the auction row's place in its stream, then the bid's in
its own.

The view holds one row a kept pair, (seller, avg, total, n) under the
pair's key, and two auctions of one seller may close at the same low
price; so it is read grouped, ``SELECT seller, total, n, count(*) ...
GROUP BY seller, total, n``, and ``mv`` gives those counted rows.
"""

import numpy as np

FRAME_ROWS = 11  # ROWS BETWEEN 10 PRECEDING AND CURRENT ROW


class _Pairs:
    """Every (auction, bid) pair the join ever holds, in the order it
    comes to exist, with its group and its place in the one order every
    group ranks by."""

    def __init__(self, events):
        a, b = events["auction"], events["bid"]
        by_id = np.argsort(a["id"], kind="stable")
        ids = a["id"][by_id]
        lo = np.searchsorted(ids, b["auction"], side="left")
        hi = np.searchsorted(ids, b["auction"], side="right")
        per_bid = hi - lo  # auction rows of the bid's id (1, but for a twin)
        bid = np.repeat(np.arange(len(per_bid)), per_bid)
        nth = np.arange(len(bid)) - np.repeat(
            np.cumsum(per_bid) - per_bid, per_bid
        )
        auc = by_id[np.repeat(lo, per_bid) + nth]
        when = b["date_time"][bid]
        keep = (a["date_time"][auc] <= when) & (when <= a["expires"][auc])
        bid, auc = bid[keep], auc[keep]
        since = np.maximum(b["eid"][bid], a["eid"][auc])
        by_time = np.argsort(since, kind="stable")
        self.since = since[by_time]
        self.bid, self.auc = bid[by_time], auc[by_time]
        # PARTITION BY A.id, A.seller
        if len(self.auc):
            _, group = np.unique(
                np.stack([a["id"][self.auc], a["seller"][self.auc]], axis=1),
                axis=0, return_inverse=True,
            )
            self.group = group.ravel()
        else:
            self.group = np.zeros(0, np.int64)
        self.n_groups = int(self.group.max()) + 1 if len(self.group) else 0
        # ORDER BY B.price (ascending), then the stream key
        self.by_rank = np.lexsort((self.bid, self.auc, b["price"][self.bid]))
        self.rank = np.empty(len(self.by_rank), np.int64)
        self.rank[self.by_rank] = np.arange(len(self.by_rank))

    def kept(self, cuts):
        """At each of ``cuts``, (bid, auction) positions of every
        group's first pair: a group's best rank only falls as pairs
        come, so one pass over the cuts in ascending order."""
        none = len(self.rank)
        best = np.full(self.n_groups, none, np.int64)
        out, done = {}, 0
        for cut in sorted(set(cuts)):
            upto = int(np.searchsorted(self.since, cut, side="left"))
            np.minimum.at(best, self.group[done:upto], self.rank[done:upto])
            done = upto
            at = self.by_rank[best[best < none]]
            out[cut] = (self.bid[at], self.auc[at])
        return [out[c] for c in cuts]


def _framed(events, bid, auc):
    """(seller, total, n) of every kept row: its seller's rows in the
    order of (the kept bid's date_time, the auction's arrival, the
    bid's), each with the sum and count of its own price and of up to
    ten rows before it."""
    a, b = events["auction"], events["bid"]
    seller = a["seller"][auc]
    order = np.lexsort((bid, auc, b["date_time"][bid], seller))
    seller, price = seller[order], b["price"][bid][order].astype(np.int64)
    at = np.arange(len(order))
    starts = np.r_[True, seller[1:] != seller[:-1]] if len(order) else (
        np.zeros(0, bool)
    )
    first = np.maximum.accumulate(np.where(starts, at, 0))  # the partition's
    lo = np.maximum(first, at - (FRAME_ROWS - 1))  # the frame's first row
    upto = np.r_[0, np.cumsum(price)]  # upto[i] = the prices before row i
    return seller, upto[at + 1] - upto[lo], at - lo + 1


def mv(events, cut, vocab=None):
    """``SELECT seller, total, n, count(*) FROM q6 GROUP BY seller,
    total, n`` over the prefix, as a set of rows."""
    ((bid, auc),) = _Pairs(events).kept([cut])
    seller, total, n = _framed(events, bid, auc)
    rows, counts = np.unique(
        np.stack([seller, total, n], axis=1).reshape(-1, 3),
        axis=0, return_counts=True,
    )
    return {
        (int(s), int(t), int(k), int(c))
        for (s, t, k), c in zip(rows.tolist(), counts.tolist())
    }


def probe(events, cuts, vocab=None):
    """``SELECT count(*), sum(total), sum(n) FROM q6`` at each prefix;
    an empty view reads (0, 0, 0), as the harness's reader turns the
    NULLs of an empty aggregate into 0."""
    out = []
    for bid, auc in _Pairs(events).kept(list(cuts)):
        _, total, n = _framed(events, bid, auc)
        out.append((len(bid), int(total.sum()), int(n.sum())))
    return out
