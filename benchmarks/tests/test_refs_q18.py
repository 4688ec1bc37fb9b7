"""The plain reference of nexmark_q18 against bids written out by hand:
a pair's later bid takes its row, bids of one millisecond keep the
earlier arrival, a pair is (bidder, auction) and not either alone; and
the probe's running sums against a recompute of the view at every cut
of generated bids."""

import numpy as np

import nexmark_gen
import nexmark_q18_ref as q18

T = 1_436_918_400_000
VOCAB = {("bid", "channel"): ["web", "app"]}


def _bids(rows):
    """rows: (ordinal, auction, bidder, price, ms after T)."""
    eid, auction, bidder, price, ts = (
        np.array(c, dtype=np.int64) for c in zip(*rows)
    )
    extra = np.empty(len(eid), dtype=object)
    extra[:] = [f"x{p}" for p in price]
    return {"bid": {
        "eid": eid, "auction": auction, "bidder": bidder, "price": price,
        "channel": eid % 2, "date_time": T + ts, "extra": extra,
    }}


def _row(auction, bidder, price, ms, eid):
    return (auction, bidder, price, VOCAB[("bid", "channel")][eid % 2],
            T + ms, f"x{price}")


def test_a_pairs_later_bid_takes_its_row():
    events = _bids([(3, 1000, 7, 100, 10), (4, 1000, 7, 90, 20)])
    assert q18.mv(events, 3, VOCAB) == set()
    assert q18.mv(events, 4, VOCAB) == {_row(1000, 7, 100, 10, 3)}
    assert q18.mv(events, 5, VOCAB) == {_row(1000, 7, 90, 20, 4)}
    assert q18.probe(events, [3, 4, 5]) == [
        (0, 0, 0), (1, T + 10, 100), (1, T + 20, 90)]


def test_bids_of_one_millisecond_keep_the_earlier_arrival():
    events = _bids([
        (3, 1000, 7, 100, 10), (4, 1000, 7, 200, 10), (6, 1000, 7, 300, 10),
        (8, 1000, 7, 400, 11), (9, 1000, 7, 500, 11),
    ])
    assert q18.mv(events, 7, VOCAB) == {_row(1000, 7, 100, 10, 3)}
    assert q18.mv(events, 10, VOCAB) == {_row(1000, 7, 400, 11, 8)}
    assert q18.probe(events, [4, 5, 7, 9, 10]) == [
        (1, T + 10, 100), (1, T + 10, 100), (1, T + 10, 100),
        (1, T + 11, 400), (1, T + 11, 400)]


def test_a_pair_is_bidder_and_auction():
    events = _bids([
        (3, 1000, 7, 100, 10), (4, 1001, 7, 200, 11), (5, 1000, 8, 300, 12),
        (6, 1000, 7, 400, 13),
    ])
    assert q18.mv(events, 7, VOCAB) == {
        _row(1001, 7, 200, 11, 4), _row(1000, 8, 300, 12, 5),
        _row(1000, 7, 400, 13, 6),
    }
    assert q18.probe(events, [6, 7]) == [
        (3, T + 12, 600), (3, T + 13, 900)]


def test_an_earlier_event_time_that_arrives_later_changes_nothing():
    # the generator's event time never falls, the rule does not rest on it
    events = _bids([(3, 1000, 7, 100, 20), (4, 1000, 7, 200, 10)])
    assert q18.mv(events, 5, VOCAB) == {_row(1000, 7, 100, 20, 3)}
    assert q18.probe(events, [5]) == [(1, T + 20, 100)]


def test_the_probes_running_sums_equal_a_recompute_at_every_cut():
    gen = nexmark_gen.Generator(2147483999, {"first_event_rate": 20000})
    events = gen.events(0, 30_000, ["bid"])
    eid = events["bid"]["eid"]
    cuts = [0, int(eid[0]), int(eid[0]) + 1, 777, 5_000, 17_001, 30_000]
    want = []
    for cut in cuts:
        rows = q18.mv(events, cut, nexmark_gen.VOCAB)
        want.append((
            len(rows), max((r[4] for r in rows), default=0),
            sum(r[2] for r in rows),
        ))
    assert q18.probe(events, cuts) == want
    # and the data holds what the tie rule is about
    b = events["bid"]
    key = np.stack([b["bidder"], b["auction"], b["date_time"]])
    assert len(eid) - np.unique(key, axis=1).shape[1] > 1000
