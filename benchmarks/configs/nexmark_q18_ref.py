"""Plain reference for nexmark_q18 ("find last bid"): for every
(bidder, auction) pair among the bids, the one bid with the greatest
``date_time``, and among bids of one ``date_time`` the one that arrived
first, recomputed from the bids alone. Imports nothing of the program.

``events`` is {"bid": {"eid": ordinals, "auction", "bidder", "price",
"channel" (indices into ``vocab[("bid", "channel")]``), "date_time",
"extra" (text)}}; bids arrive in the order of their ordinals, and a
prefix is "every bid whose ordinal is < cut". The view's rows are
(auction, bidder, price, channel, date_time, extra).
"""

import numpy as np


def _last_rows(bidder, auction, date_time):
    """Positions (= arrival order) of each pair's row: greatest
    ``date_time`` first, earliest arrival among equals."""
    arrival = np.arange(len(bidder))
    order = np.lexsort((arrival, -date_time, auction, bidder))
    b, a = bidder[order], auction[order]
    first = np.ones(len(order), bool)
    first[1:] = (b[1:] != b[:-1]) | (a[1:] != a[:-1])
    return order[first]


def _prefix(events, cut):
    b = events["bid"]
    return b, int(np.searchsorted(b["eid"], cut, side="left"))


def mv(events, cut, vocab):
    """The whole MV over the prefix."""
    b, n = _prefix(events, cut)
    at = _last_rows(b["bidder"][:n], b["auction"][:n], b["date_time"][:n])
    channels = vocab[("bid", "channel")]
    return set(
        zip(
            b["auction"][at].tolist(),
            b["bidder"][at].tolist(),
            b["price"][at].tolist(),
            (channels[i] for i in b["channel"][at].tolist()),
            b["date_time"][at].tolist(),
            (str(x) for x in b["extra"][at]),
        )
    )


def _steps(bidder, auction, date_time, price):
    """What each bid's arrival does to ``count(*)`` and ``sum(price)``
    of the view: a bid enters it when its ``date_time`` is above every
    earlier bid's of its pair (a tie keeps the earlier arrival), and
    the pair's row it displaces, if any, leaves."""
    n = len(bidder)
    order = np.lexsort((np.arange(n), auction, bidder))  # pair, arrival
    b, a, t = bidder[order], auction[order], date_time[order]
    first = np.ones(n, bool)
    first[1:] = (b[1:] != b[:-1]) | (a[1:] != a[:-1])
    group = np.cumsum(first) - 1
    # greatest date_time of the pair over the earlier arrivals: a
    # running maximum that starts anew in every pair (the pair's number
    # lifts its times above every earlier pair's)
    rel = t - t.min() + 1
    span = int(rel.max()) + 1
    if (int(group[-1]) + 1) * span >= 2**62:
        raise OverflowError("pair x date_time does not pack into 63 bits")
    run = np.maximum.accumulate(group * span + rel) - group * span
    before = np.zeros(n, np.int64)
    before[1:] = np.where(first[1:], 0, run[:-1])
    enters = rel > before
    # the row a bid displaces: the last earlier bid of its pair that
    # entered the view
    pos = np.where(enters, np.arange(n), -1)
    last = np.maximum.accumulate(pos)
    prev = np.full(n, -1)
    prev[1:] = last[:-1]
    displaced = enters & ~first & (prev >= 0)
    p = price[order]
    d_sum = np.where(enters, p, 0) - np.where(displaced, p[prev], 0)
    d_count, d_total = np.zeros(n, np.int64), np.zeros(n, np.int64)
    d_count[order] = first
    d_total[order] = d_sum
    return d_count, d_total


def probe(events, cuts, vocab=None):
    """``SELECT count(*), max(date_time), sum(price) FROM q18`` at each
    prefix; an empty view reads (0, 0, 0), as the harness's reader turns
    the NULLs of an empty aggregate into 0."""
    b = events["bid"]
    if len(b["eid"]) == 0:
        return [(0, 0, 0) for _ in cuts]
    d_count, d_total = _steps(
        b["bidder"], b["auction"], b["date_time"], b["price"]
    )
    count = np.concatenate([[0], np.cumsum(d_count)])
    total = np.concatenate([[0], np.cumsum(d_total)])
    newest = np.concatenate([[0], np.maximum.accumulate(b["date_time"])])
    out = []
    for cut in cuts:
        n = int(np.searchsorted(b["eid"], cut, side="left"))
        out.append((int(count[n]), int(newest[n]), int(total[n])))
    return out
