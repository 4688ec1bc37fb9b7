"""Plain reference for nexmark_q9 ("winning bids"): every bid joined to
the auctions of its id while ``A.date_time <= B.date_time <=
A.expires``, and of each auction id the ONE pair that comes first by
(price descending, the bid's date_time ascending, arrival), with every
column of the auction the view selects beside the bid's. Recomputed
from the events alone; imports nothing of the program.

``events`` is {"auction": {"eid", "id", "item_name" (indices into
``vocab[("auction", "item_name")]``), "description" (text),
"initial_bid", "reserve", "date_time", "expires", "seller",
"category"}, "bid": {"eid", "auction", "bidder", "price",
"date_time"}}, each sorted by ``eid``; rows arrive in that order, and a
prefix is "every event whose ordinal is < cut". A pair exists in a
prefix once both its rows do. Arrival, the last tie-break, is the
auction row's place in its stream and then the bid's in its own (the
pair's stream key; an auction id arrives once in the generator's
streams, so among one auction's pairs it is the bids' order). The view's
rows are (id, item_name, description, initial_bid, reserve, date_time,
expires, seller, category, auction, bidder, price, bid_date_time).
"""

import numpy as np


class _Pairs:
    """Every (auction, bid) pair the join ever holds, in the order it
    comes to exist, with its group (the auction id) and its place in
    the one order every group ranks by."""

    def __init__(self, events):
        a, b = events["auction"], events["bid"]
        order = np.argsort(a["id"], kind="stable")
        ids = a["id"][order]
        lo = np.searchsorted(ids, b["auction"], side="left")
        hi = np.searchsorted(ids, b["auction"], side="right")
        per_bid = hi - lo  # auctions of the bid's id (1, but for a twin)
        bid = np.repeat(np.arange(len(per_bid)), per_bid)
        first = np.repeat(lo, per_bid)
        nth = np.arange(len(bid)) - np.repeat(
            np.cumsum(per_bid) - per_bid, per_bid
        )
        auc = order[first + nth]
        when = b["date_time"][bid]
        keep = (a["date_time"][auc] <= when) & (when <= a["expires"][auc])
        bid, auc = bid[keep], auc[keep]
        since = np.maximum(b["eid"][bid], a["eid"][auc])
        by_time = np.argsort(since, kind="stable")
        self.since = since[by_time]
        self.bid, self.auc = bid[by_time], auc[by_time]
        _, self.group = np.unique(a["id"][self.auc], return_inverse=True)
        self.group = self.group.ravel()
        self.n_groups = int(self.group.max()) + 1 if len(self.group) else 0
        # a pair's place among ALL pairs by (price DESC, date_time ASC,
        # the auction's arrival, the bid's): within a group, its rank
        self.by_rank = np.lexsort(
            (self.bid, self.auc, b["date_time"][self.bid],
             -b["price"][self.bid])
        )
        self.rank = np.empty(len(self.by_rank), np.int64)
        self.rank[self.by_rank] = np.arange(len(self.by_rank))

    def winners(self, cuts):
        """At each of ``cuts`` (ascending or not), (bid, auction)
        positions of every group's first pair: a group's best rank only
        falls as pairs come, so one pass."""
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


def mv(events, cut, vocab):
    """The whole MV over the prefix, as a set of rows."""
    a, b = events["auction"], events["bid"]
    ((bid, auc),) = _Pairs(events).winners([cut])
    items = vocab[("auction", "item_name")]
    return set(
        zip(
            a["id"][auc].tolist(),
            (items[i] for i in a["item_name"][auc].tolist()),
            (str(x) for x in a["description"][auc]),
            a["initial_bid"][auc].tolist(),
            a["reserve"][auc].tolist(),
            a["date_time"][auc].tolist(),
            a["expires"][auc].tolist(),
            a["seller"][auc].tolist(),
            a["category"][auc].tolist(),
            b["auction"][bid].tolist(),
            b["bidder"][bid].tolist(),
            b["price"][bid].tolist(),
            b["date_time"][bid].tolist(),
        )
    )


def probe(events, cuts, vocab=None):
    """``SELECT count(*), max(bid_date_time), sum(price) FROM q9`` at
    each prefix; an empty view reads (0, 0, 0), as the harness's reader
    turns the NULLs of an empty aggregate into 0."""
    b = events["bid"]
    return [
        (
            len(bid),
            int(b["date_time"][bid].max()) if len(bid) else 0,
            int(b["price"][bid].sum()),
        )
        for bid, _ in _Pairs(events).winners(list(cuts))
    ]
