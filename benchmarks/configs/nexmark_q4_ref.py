"""Plain reference for nexmark_q4 ("average price for a category"):
every bid joined to the auctions of its id while ``A.date_time <=
B.date_time <= A.expires``, the highest such bid of each (auction id,
category), and per category the sum and the count of those — AVG's two
halves, which the view keeps as integers beside the average.
Recomputed from the events alone; imports nothing of the program.

``events`` is {"auction": {"eid", "id", "date_time", "expires",
"category"}, "bid": {"eid", "auction", "price", "date_time"}}, each
sorted by ``eid``; a prefix is "every event whose ordinal is < cut". A
pair exists in a prefix once both its rows do.
"""

import numpy as np


class _Pairs:
    """Every (bid, auction) pair the join ever holds, in the order it
    comes to exist, with its group — (auction id, category) — and
    price; per group its category."""

    def __init__(self, events):
        a, b = events["auction"], events["bid"]
        order = np.argsort(a["id"], kind="stable")
        ids = a["id"][order]
        lo = np.searchsorted(ids, b["auction"], side="left")
        hi = np.searchsorted(ids, b["auction"], side="right")
        per_bid = hi - lo  # auctions of the bid's id (1, but for a twin)
        bid = np.repeat(np.arange(len(per_bid)), per_bid)
        first = np.repeat(lo, per_bid)
        nth = np.arange(len(bid)) - np.repeat(np.cumsum(per_bid) - per_bid, per_bid)
        auc = order[first + nth]
        when = b["date_time"][bid]
        keep = (a["date_time"][auc] <= when) & (when <= a["expires"][auc])
        bid, auc = bid[keep], auc[keep]
        since = np.maximum(b["eid"][bid], a["eid"][auc])
        by_time = np.argsort(since, kind="stable")
        self.since = since[by_time]
        self.price = b["price"][bid[by_time]]
        key = np.stack([a["id"][auc[by_time]], a["category"][auc[by_time]]])
        groups, self.group = np.unique(key, axis=1, return_inverse=True)
        self.group = self.group.ravel()
        self.category = groups[1] if groups.size else np.zeros(0, np.int64)

    def prefixes(self, cuts):
        """(categories, totals, counts) at each of ``cuts``, ascending
        or not: a group's highest price only rises, so one pass."""
        none = np.iinfo(np.int64).min
        best = np.full(len(self.category), none, np.int64)
        out, done = {}, 0
        for cut in sorted(set(cuts)):
            upto = int(np.searchsorted(self.since, cut, side="left"))
            np.maximum.at(best, self.group[done:upto], self.price[done:upto])
            done = upto
            has = best > none
            cats, inv = np.unique(self.category[has], return_inverse=True)
            total = np.zeros(len(cats), np.int64)
            np.add.at(total, inv, best[has])
            out[cut] = (cats, total, np.bincount(inv, minlength=len(cats)))
        return [out[c] for c in cuts]


def mv(events, cut, vocab=None):
    """The whole MV over the prefix: {(category, total, n)}."""
    ((cats, total, n),) = _Pairs(events).prefixes([cut])
    return set(zip(cats.tolist(), total.tolist(), n.tolist()))


def probe(events, cuts, vocab=None):
    """``SELECT count(*), sum(total), sum(n) FROM q4`` at each prefix;
    an empty view reads (0, 0, 0), as the harness's reader turns the
    NULLs of an empty aggregate into 0."""
    return [
        (len(cats), int(total.sum()), int(n.sum()))
        for cats, total, n in _Pairs(events).prefixes(list(cuts))
    ]
