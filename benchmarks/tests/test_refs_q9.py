"""The plain reference of nexmark_q9 against auctions and bids written
out by hand: bids that tie on price, on price and date_time, a bid out
of its auction's range, an auction with no bid, bids that arrive before
their auction; and the probe against a plain loop over generated
events."""

import numpy as np

import nexmark_gen
import nexmark_q9_ref as q9

T = 1_436_918_400_000
VOCAB = {("auction", "item_name"): ["lamp", "desk", "vase"]}


def _events(auctions, bids):
    """auctions: (ordinal, id, opens ms after T, expires, category);
    bids: (ordinal, auction, bidder, price, ms after T)."""
    a = [np.array(c, dtype=np.int64) for c in zip(*auctions)]
    b = [np.array(c, dtype=np.int64) for c in zip(*bids)]
    return {
        "auction": {
            "eid": a[0], "id": a[1], "item_name": a[1] % 3,
            "description": np.array([f"d{i}" for i in a[1]], object),
            "initial_bid": a[1] + 1, "reserve": a[1] + 2,
            "date_time": T + a[2], "expires": T + a[3], "seller": a[1] + 3,
            "category": a[4],
        },
        "bid": {"eid": b[0], "auction": b[1], "bidder": b[2], "price": b[3],
                "date_time": T + b[4]},
    }


def _row(ident, opens, expires, category, bidder, price, when):
    item = VOCAB[("auction", "item_name")][ident % 3]
    return (ident, item, f"d{ident}", ident + 1, ident + 2, T + opens,
            T + expires, ident + 3, category, ident, bidder, price, T + when)


def test_the_highest_bid_wins_and_an_auction_with_no_bid_is_absent():
    events = _events(
        [(0, 1000, 0, 10_000, 10), (1, 1001, 0, 10_000, 11),
         (2, 1002, 0, 10_000, 12)],  # 1002: no bid
        [(3, 1000, 7, 100, 50), (4, 1000, 8, 300, 60), (5, 1001, 9, 50, 70),
         (6, 1000, 7, 200, 80)],
    )
    assert q9.mv(events, 3, VOCAB) == set()
    assert q9.mv(events, 4, VOCAB) == {_row(1000, 0, 10_000, 10, 7, 100, 50)}
    assert q9.mv(events, 5, VOCAB) == {_row(1000, 0, 10_000, 10, 8, 300, 60)}
    full = {_row(1000, 0, 10_000, 10, 8, 300, 60),
            _row(1001, 0, 10_000, 11, 9, 50, 70)}
    assert q9.mv(events, 6, VOCAB) == q9.mv(events, 7, VOCAB) == full
    assert q9.probe(events, [3, 5, 7]) == [
        (0, 0, 0), (1, T + 60, 300), (2, T + 70, 350),
    ]


def test_ties_on_price_fall_to_date_time_and_then_to_arrival():
    events = _events(
        [(0, 1000, 0, 10_000, 10)],
        [(1, 1000, 1, 500, 300),
         (2, 1000, 2, 500, 200),  # same price, earlier: wins
         (3, 1000, 3, 500, 200),  # ties on both: the earlier arrival stays
         (4, 1000, 4, 499, 100)],  # earliest of all, but a lower price
    )
    assert q9.mv(events, 2, VOCAB) == {_row(1000, 0, 10_000, 10, 1, 500, 300)}
    for cut in (3, 4, 5):
        assert q9.mv(events, cut, VOCAB) == {
            _row(1000, 0, 10_000, 10, 2, 500, 200)
        }


def test_a_bid_out_of_its_auctions_range_pairs_with_none():
    events = _events(
        [(0, 1000, 1_000, 11_000, 10)],
        [(1, 1000, 1, 900, 999), (2, 1000, 2, 800, 11_001),  # early, late
         (3, 1000, 3, 10, 1_000), (4, 1000, 4, 20, 11_000)],  # the bounds
    )
    assert q9.mv(events, 3, VOCAB) == set()
    assert q9.mv(events, 4, VOCAB) == {_row(1000, 1_000, 11_000, 10, 3, 10, 1_000)}
    assert q9.mv(events, 5, VOCAB) == {_row(1000, 1_000, 11_000, 10, 4, 20, 11_000)}


def test_a_pair_exists_once_both_its_rows_do():
    events = _events(
        [(2, 1000, 0, 10_000, 10)],
        [(0, 1000, 1, 500, 100), (1, 1000, 2, 400, 200), (3, 1000, 3, 450, 300)],
    )
    assert q9.mv(events, 2, VOCAB) == set()  # the bids wait for their auction
    assert q9.mv(events, 3, VOCAB) == {_row(1000, 0, 10_000, 10, 1, 500, 100)}
    assert q9.probe(events, [2, 3, 4]) == [
        (0, 0, 0), (1, T + 100, 500), (1, T + 100, 500),
    ]


def test_the_reference_on_generated_events_equals_a_plain_loop():
    gen = nexmark_gen.Generator(2147483999, {"first_event_rate": 20000})
    events = gen.events(0, 4_000, ["auction", "bid"])
    a, b = events["auction"], events["bid"]
    cuts = [1_000, 2_500, 4_000]
    got = q9.probe(events, cuts)
    for cut, (rows, newest, total) in zip(cuts, got):
        best = {}
        for i in range(len(a["eid"])):
            if a["eid"][i] >= cut:
                continue
            for j in np.flatnonzero(b["auction"] == a["id"][i]):
                if b["eid"][j] < cut and (
                    a["date_time"][i] <= b["date_time"][j] <= a["expires"][i]
                ):
                    rank = (-int(b["price"][j]), int(b["date_time"][j]), i, j)
                    key = int(a["id"][i])
                    best[key] = min(best.get(key, rank), rank)
        assert (rows, newest, total) == (
            len(best), max(r[1] for r in best.values()),
            -sum(r[0] for r in best.values()),
        )
        view = q9.mv(events, cut, nexmark_gen.VOCAB)
        assert {(r[0], r[10], r[11], r[12]) for r in view} == {
            (k, int(b["bidder"][j]), -p, t) for k, (p, t, _, j) in best.items()
        }
        for r in view:  # every column of the auction, decoded
            (i,) = np.flatnonzero(a["id"] == r[0])
            assert r[1] == nexmark_gen.VOCAB[("auction", "item_name")][
                a["item_name"][i]
            ]
            assert r[2] == a["description"][i] and r[8] == a["category"][i]
    assert got[-1][0] > 100  # auctions with a bid
