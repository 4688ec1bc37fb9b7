"""The plain reference of nexmark_q4 against auctions and bids written
out by hand: a late bid, a bid before its auction opens, two auctions of
one category, an auction with no bid, a bid that arrives before its
auction; and the probe against a plain loop over generated events."""

import numpy as np

import nexmark_gen
import nexmark_q4_ref as q4

T = 1_436_918_400_000


def _events(auctions, bids):
    """auctions: (ordinal, id, opens ms after T, expires, category);
    bids: (ordinal, auction, price, ms after T)."""
    a = [np.array(c, dtype=np.int64) for c in zip(*auctions)]
    b = [np.array(c, dtype=np.int64) for c in zip(*bids)]
    return {
        "auction": {"eid": a[0], "id": a[1], "date_time": T + a[2],
                    "expires": T + a[3], "category": a[4]},
        "bid": {"eid": b[0], "auction": b[1], "price": b[2],
                "date_time": T + b[3]},
    }


def test_the_highest_bid_of_each_auction_and_the_categories_sums():
    events = _events(
        [(0, 1000, 0, 10_000, 10), (1, 1001, 0, 10_000, 10),
         (2, 1002, 0, 10_000, 11), (3, 1003, 0, 10_000, 12)],  # 1003: no bid
        [(4, 1000, 100, 50), (5, 1000, 300, 60), (6, 1001, 50, 70),
         (7, 1002, 70, 80)],
    )
    assert q4.mv(events, 4) == set()
    assert q4.mv(events, 5) == {(10, 100, 1)}
    assert q4.mv(events, 6) == {(10, 300, 1)}  # the same auction, higher
    assert q4.mv(events, 7) == {(10, 350, 2)}  # a second auction of 10
    assert q4.mv(events, 8) == {(10, 350, 2), (11, 70, 1)}
    assert q4.probe(events, [4, 6, 8]) == [(0, 0, 0), (1, 300, 1), (2, 420, 3)]


def test_a_late_bid_and_a_bid_before_the_auction_opens_pair_with_none():
    events = _events(
        [(0, 1000, 1_000, 11_000, 10)],
        [(1, 1000, 900, 999), (2, 1000, 800, 11_001),  # too early, too late
         (3, 1000, 10, 1_000), (4, 1000, 20, 11_000)],  # the bounds are in
    )
    assert q4.mv(events, 3) == set()
    assert q4.mv(events, 4) == {(10, 10, 1)}
    assert q4.mv(events, 5) == {(10, 20, 1)}


def test_a_pair_exists_once_both_its_rows_do():
    events = _events(
        [(2, 1000, 0, 10_000, 10)],
        [(0, 1000, 500, 100), (1, 1000, 400, 200), (3, 1000, 450, 300)],
    )
    assert q4.mv(events, 2) == set()  # the bids wait for their auction
    assert q4.mv(events, 3) == {(10, 500, 1)}
    assert q4.probe(events, [2, 3, 4]) == [(0, 0, 0), (1, 500, 1), (1, 500, 1)]


def test_the_reference_on_generated_events_equals_a_plain_loop():
    gen = nexmark_gen.Generator(2147483999, {"first_event_rate": 20000})
    events = gen.events(0, 4_000, ["auction", "bid"])
    a, b = events["auction"], events["bid"]
    cuts = [1_000, 2_500, 4_000]
    got = q4.probe(events, cuts)
    for cut, (rows, total, n) in zip(cuts, got):
        best = {}
        for i in range(len(a["eid"])):
            if a["eid"][i] >= cut:
                continue
            for j in np.flatnonzero(b["auction"] == a["id"][i]):
                if b["eid"][j] < cut and (
                    a["date_time"][i] <= b["date_time"][j] <= a["expires"][i]
                ):
                    key = (int(a["id"][i]), int(a["category"][i]))
                    best[key] = max(best.get(key, 0), int(b["price"][j]))
        by_cat = {}
        for (_, cat), price in best.items():
            s, c = by_cat.get(cat, (0, 0))
            by_cat[cat] = (s + price, c + 1)
        assert (rows, total, n) == (
            len(by_cat), sum(s for s, _ in by_cat.values()),
            sum(c for _, c in by_cat.values()),
        )
        assert q4.mv(events, cut) == {
            (cat, s, c) for cat, (s, c) in by_cat.items()
        }
    assert got[-1][2] > 100  # auctions with a bid
