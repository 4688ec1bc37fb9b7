"""The plain reference of nexmark_q6 against auctions and bids written
out by hand — a lower bid that comes later, a re-kept row that moves
past its seller's neighbours, a seller with more than eleven auctions,
bids before their auction opens and after it expires, equal date_times
in one partition, ties on the price, bids that arrive before their
auction — and against a brute-force recompute over generated events."""

import numpy as np

import nexmark_gen
import nexmark_q6_ref as q6

T = 1_436_918_400_000


def _events(auctions, bids):
    """auctions: (ordinal, id, seller, opens ms after T, expires);
    bids: (ordinal, auction, price, ms after T)."""
    a = [np.array(c, dtype=np.int64) for c in zip(*auctions)]
    b = [np.array(c, dtype=np.int64) for c in zip(*bids)]
    return {
        "auction": {"eid": a[0], "id": a[1], "seller": a[2],
                    "date_time": T + a[3], "expires": T + a[4]},
        "bid": {"eid": b[0], "auction": b[1], "price": b[2],
                "date_time": T + b[3]},
    }


def brute(events, cut):
    """q6 by plain loops: (seller, total, n) of every kept row."""
    a, b = events["auction"], events["bid"]
    best = {}
    for i in range(len(a["eid"])):
        if a["eid"][i] >= cut:
            continue
        for j in range(len(b["eid"])):
            if (
                b["eid"][j] < cut
                and b["auction"][j] == a["id"][i]
                and a["date_time"][i] <= b["date_time"][j] <= a["expires"][i]
            ):
                group = (int(a["id"][i]), int(a["seller"][i]))
                rank = (int(b["price"][j]), i, j)
                best[group] = min(best.get(group, rank), rank)
    by_seller = {}
    for (_, seller), (price, i, j) in best.items():
        by_seller.setdefault(seller, []).append(
            (int(b["date_time"][j]), i, j, price)
        )
    rows = []
    for seller, kept in by_seller.items():
        kept.sort()
        for at in range(len(kept)):
            frame = kept[max(0, at - 10): at + 1]
            rows.append((seller, sum(k[3] for k in frame), len(frame)))
    return rows


def counted(rows):
    return {(s, t, n, rows.count((s, t, n))) for s, t, n in rows}


def test_a_lower_bid_that_comes_later_replaces_the_row_and_moves_it():
    events = _events(
        [(0, 1000, 7, 0, 10_000), (1, 1001, 7, 0, 10_000),
         (2, 1002, 7, 0, 10_000)],
        [(3, 1000, 500, 100), (4, 1001, 300, 200), (5, 1002, 900, 300),
         # 1000's lower bid, later than both neighbours' kept bids
         (6, 1000, 100, 400)],
    )
    assert q6.mv(events, 6) == {(7, 500, 1, 1), (7, 800, 2, 1), (7, 1700, 3, 1)}
    # now the seller's order is 1001 (200), 1002 (300), 1000 (400)
    assert q6.mv(events, 7) == {(7, 300, 1, 1), (7, 1200, 2, 1), (7, 1300, 3, 1)}
    assert q6.probe(events, [3, 6, 7]) == [
        (0, 0, 0), (3, 3000, 6), (3, 2800, 6),
    ]
    for cut in range(8):
        assert q6.mv(events, cut) == counted(brute(events, cut))


def test_the_frame_slides_past_eleven_rows_of_one_seller():
    auctions = [(i, 1000 + i, 9, 0, 100_000) for i in range(14)]
    bids = [(14 + i, 1000 + i, 10 * (i + 1), 1_000 + i) for i in range(14)]
    events = _events(auctions, bids)
    got = q6.mv(events, 28)
    prices = [10 * (i + 1) for i in range(14)]
    want = [
        (9, sum(prices[max(0, i - 10): i + 1]), min(i + 1, 11))
        for i in range(14)
    ]
    assert got == counted(want) == counted(brute(events, 28))
    assert max(n for _, _, n, _ in got) == 11
    assert q6.probe(events, [28]) == [
        (14, sum(t for _, t, _ in want), sum(n for _, _, n in want))
    ]


def test_bids_before_the_auction_opens_and_after_it_expires_pair_with_none():
    events = _events(
        [(0, 1000, 7, 1_000, 11_000)],
        [(1, 1000, 5, 999), (2, 1000, 6, 11_001),  # early, late: the lowest
         (3, 1000, 50, 1_000), (4, 1000, 40, 11_000)],  # the bounds
    )
    assert q6.mv(events, 3) == set()
    assert q6.mv(events, 4) == {(7, 50, 1, 1)}
    assert q6.mv(events, 5) == {(7, 40, 1, 1)}


def test_ties_on_the_price_keep_the_earlier_arrival_and_equal_times_order_by_arrival():
    events = _events(
        [(0, 1000, 7, 0, 10_000), (1, 1001, 7, 0, 10_000)],
        [(2, 1001, 70, 500),
         (3, 1000, 30, 500),  # equal date_time in one partition: 1000 first
         (4, 1000, 30, 100)],  # ties on the price: the earlier bid stays
    )
    # the partition's order is (500, auction 1000), (500, auction 1001)
    assert q6.mv(events, 5) == {(7, 30, 1, 1), (7, 100, 2, 1)}
    assert q6.mv(events, 5) == counted(brute(events, 5))


def test_two_rows_of_one_seller_with_the_same_sum_are_counted():
    events = _events(
        [(0, 1000, 7, 0, 10_000), (1, 1001, 8, 0, 10_000),
         (2, 1002, 8, 0, 10_000)],
        [(3, 1000, 10, 1), (4, 1001, 10, 2), (5, 1002, 0, 3)],
    )
    assert q6.mv(events, 6) == {(7, 10, 1, 1), (8, 10, 1, 1), (8, 10, 2, 1)}
    events["bid"]["price"][2] = 10  # and a third seller-8 row equal to none
    events["auction"]["seller"][:] = 8
    assert q6.mv(events, 5) == {(8, 10, 1, 1), (8, 20, 2, 1)}


def test_a_pair_exists_once_both_its_rows_do():
    events = _events(
        [(2, 1000, 7, 0, 10_000)],
        [(0, 1000, 500, 100), (1, 1000, 400, 200), (3, 1000, 450, 300)],
    )
    assert q6.mv(events, 2) == set()  # the bids wait for their auction
    assert q6.mv(events, 3) == q6.mv(events, 4) == {(7, 400, 1, 1)}
    assert q6.probe(events, [2, 3, 4]) == [(0, 0, 0), (1, 400, 1), (1, 400, 1)]


def test_the_reference_on_generated_events_equals_a_plain_loop():
    gen = nexmark_gen.Generator(2147483999, {"first_event_rate": 20000})
    events = gen.events(0, 6_000, ["auction", "bid"])
    cuts = [1_000, 2_500, 6_000]
    got = q6.probe(events, cuts)
    for cut, (rows, total, n) in zip(cuts, got):
        want = brute(events, cut)
        assert (rows, total, n) == (
            len(want), sum(r[1] for r in want), sum(r[2] for r in want)
        )
        assert q6.mv(events, cut, nexmark_gen.VOCAB) == counted(want)
    # the hot seller of the moment holds more rows than a frame
    assert max(r[2] for r in brute(events, 6_000)) == 11
