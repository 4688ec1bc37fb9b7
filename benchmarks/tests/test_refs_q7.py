"""The plain reference of nexmark_q7 against bids written out by hand:
a maximum that rises, a tie on a maximum, a bid stamped exactly on a
window's end (in the band of the window that ends there and in the
next window), a bid on a window's start, a late bid; and the view and
the probe against a plain loop over generated bids."""

import numpy as np

import nexmark_gen
import nexmark_q7_ref as q7

T = 1_436_918_400_000  # a multiple of 10 s


def _events(bids):
    """bids: (ordinal, auction, price, ms after T); the bidder is the
    auction + 100."""
    b = [np.array(c, dtype=np.int64) for c in zip(*bids)]
    return {"bid": {"eid": b[0], "auction": b[1], "bidder": b[1] + 100,
                    "price": b[2], "date_time": T + b[3]}}


def _short(rows):
    """{(auction, window end in s after T)}"""
    return {(r[0], (r[4] - T) // 1000) for r in rows}


def test_each_windows_highest_bid_and_a_maximum_that_rises():
    events = _events([
        (0, 1, 300, 1_000), (1, 2, 500, 2_000), (2, 3, 400, 11_000),
        (3, 4, 600, 3_000), (4, 5, 450, 19_999),
    ])
    assert q7.mv(events, 0) == set()
    assert q7.mv(events, 1) == {(1, 300, 101, T + 1_000, T + 10_000)}
    assert _short(q7.mv(events, 2)) == {(2, 10)}  # 500 takes 300's place
    assert _short(q7.mv(events, 3)) == {(2, 10), (3, 20)}
    assert _short(q7.mv(events, 4)) == {(4, 10), (3, 20)}  # a late bid wins
    assert _short(q7.mv(events, 5)) == {(4, 10), (5, 20)}
    assert q7.probe(events, [0, 2, 5]) == [
        (0, 0, 0), (1, 500, T + 2_000), (2, 1050, T + 19_999),
    ]


def test_every_bid_at_the_maximum_stands_in_the_view():
    events = _events([
        (0, 1, 500, 1_000), (1, 2, 500, 2_000), (2, 3, 499, 3_000),
        (3, 4, 500, 9_999),
    ])
    assert _short(q7.mv(events, 2)) == {(1, 10), (2, 10)}
    assert _short(q7.mv(events, 4)) == {(1, 10), (2, 10), (4, 10)}
    assert q7.probe(events, [4]) == [(3, 1500, T + 9_999)]


def test_the_band_holds_both_its_ends():
    # a bid stamped exactly on 10 s belongs to [10 s, 20 s) and lies in
    # the band [0 s, 10 s] of the window that ends there
    events = _events([
        (0, 1, 700, 5_000), (1, 2, 700, 10_000), (2, 3, 650, 15_000),
        (3, 4, 700, 0), (4, 5, 800, 20_000),
    ])
    # before the edge bid: one winner
    assert _short(q7.mv(events, 1)) == {(1, 10)}
    # the edge bid ties the first window's maximum and sets the second's
    assert _short(q7.mv(events, 3)) == {(1, 10), (2, 10), (2, 20)}
    # a bid on the window's start is the window's own (and, the clock
    # starting there, no earlier window's)
    assert _short(q7.mv(events, 4)) == {(1, 10), (2, 10), (2, 20), (4, 10)}
    # a bid on 20 s above the second window's maximum is in its band but
    # not in the window: it changes no maximum there, and wins the third
    assert _short(q7.mv(events, 5)) == {
        (1, 10), (2, 10), (2, 20), (4, 10), (5, 30),
    }
    # an edge bid above the ended window's maximum does not join it
    events = _events([(0, 1, 700, 5_000), (1, 2, 900, 10_000)])
    assert _short(q7.mv(events, 2)) == {(1, 10), (2, 20)}


def test_generated_bids_against_a_plain_loop():
    gen = nexmark_gen.Generator(11, {"first_event_rate": 40})
    bids = gen.events(0, 3000, ["bid"])["bid"]
    events = {"bid": bids}
    n = len(bids["eid"])
    cuts = [int(bids["eid"][i]) + 1 for i in (0, n // 3, n // 2, n - 1)]
    for cut, got in zip(cuts, q7.probe(events, cuts)):
        rows = [
            (int(a), int(p), int(b), int(t))
            for e, a, p, b, t in zip(
                bids["eid"], bids["auction"], bids["price"], bids["bidder"],
                bids["date_time"],
            ) if e < cut
        ]
        best = {}
        for _, p, _, t in rows:
            w = t // 10_000
            best[w] = max(best.get(w, p), p)
        want = set()
        for w, top in best.items():
            end = (w + 1) * 10_000
            for a, p, b, t in rows:
                if p == top and end - 10_000 <= t <= end:
                    want.add((a, p, b, t, end))
        assert q7.mv(events, cut) == want
        assert got == (
            len(want), sum(r[1] for r in want), max(r[3] for r in want)
        )
    assert len({r[4] for r in want}) >= 5  # five windows and more
