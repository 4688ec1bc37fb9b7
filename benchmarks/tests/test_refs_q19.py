"""The plain reference of nexmark_q19 against bids written out by hand:
an auction's bids numbered from the highest price down, the eleventh
left out, bids of one price ranked by arrival, an auction's ranks its
own; and the probe against a recompute of the view at every cut of
generated bids."""

import numpy as np

import nexmark_gen
import nexmark_q19_ref as q19

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


def _row(auction, bidder, price, ms, eid, rank):
    return (auction, bidder, price, VOCAB[("bid", "channel")][eid % 2],
            T + ms, f"x{price}", rank)


def test_an_auctions_bids_are_numbered_from_the_highest_down():
    events = _bids([
        (3, 1000, 7, 100, 10), (4, 1000, 8, 300, 11), (6, 1000, 9, 200, 12),
    ])
    assert q19.mv(events, 3, VOCAB) == set()
    assert q19.mv(events, 4, VOCAB) == {_row(1000, 7, 100, 10, 3, 1)}
    # a higher bid moves the one under it: the same row, another rank
    assert q19.mv(events, 5, VOCAB) == {
        _row(1000, 8, 300, 11, 4, 1), _row(1000, 7, 100, 10, 3, 2)}
    assert q19.mv(events, 7, VOCAB) == {
        _row(1000, 8, 300, 11, 4, 1), _row(1000, 9, 200, 12, 6, 2),
        _row(1000, 7, 100, 10, 3, 3)}
    # count, max(date_time), sum(price), sum(rank_number)
    assert q19.probe(events, [3, 4, 5, 7]) == [
        (0, 0, 0, 0), (1, T + 10, 100, 1), (2, T + 11, 400, 3),
        (3, T + 12, 600, 6)]


def test_the_eleventh_is_left_out_and_the_probe_says_when_ranks_move():
    rows = [(i, 1000, 7, 100 + 10 * i, i) for i in range(10)]
    events = _bids(rows + [(10, 1000, 7, 95, 10), (11, 1000, 7, 500, 11)])
    full = {_row(1000, 7, 100 + 10 * i, i, i, 10 - i) for i in range(10)}
    assert q19.mv(events, 10, VOCAB) == full
    assert q19.mv(events, 11, VOCAB) == full  # under the tenth: nobody moves
    # the new maximum moves nine rows down one and pushes out the tenth
    assert q19.mv(events, 12, VOCAB) == {
        _row(1000, 7, 500, 11, 11, 1)
    } | {_row(1000, 7, 100 + 10 * i, i, i, 11 - i) for i in range(1, 10)}
    before, same, after = q19.probe(events, [10, 11, 12])
    assert before == same == (10, T + 9, 1450, 55)
    # the view's newest row is the new bid; ranks still sum to 55
    assert after == (10, T + 11, 1450 - 100 + 500, 55)


def test_bids_of_one_price_rank_by_arrival():
    events = _bids([
        (3, 1000, 7, 100, 10), (4, 1000, 8, 100, 10), (6, 1000, 9, 200, 10),
        (8, 1000, 5, 100, 11),
    ])
    assert q19.mv(events, 9, VOCAB) == {
        _row(1000, 9, 200, 10, 6, 1), _row(1000, 7, 100, 10, 3, 2),
        _row(1000, 8, 100, 10, 4, 3), _row(1000, 5, 100, 11, 8, 4)}


def test_an_auctions_ranks_are_its_own():
    events = _bids([
        (3, 1000, 7, 100, 10), (4, 1001, 7, 50, 11), (5, 1000, 8, 300, 12),
    ])
    assert q19.mv(events, 6, VOCAB) == {
        _row(1000, 8, 300, 12, 5, 1), _row(1000, 7, 100, 10, 3, 2),
        _row(1001, 7, 50, 11, 4, 1)}
    assert q19.probe(events, [6]) == [(3, T + 12, 450, 4)]


def test_the_probe_equals_a_recompute_at_every_cut():
    gen = nexmark_gen.Generator(2147483999, {"first_event_rate": 20000})
    events = gen.events(0, 30_000, ["bid"])
    eid = events["bid"]["eid"]
    cuts = [0, int(eid[0]), int(eid[0]) + 1, 777, 5_000, 17_001, 30_000]
    want = []
    for cut in cuts:
        rows = q19.mv(events, cut, nexmark_gen.VOCAB)
        want.append((
            len(rows), max((r[4] for r in rows), default=0),
            sum(r[2] for r in rows), sum(r[6] for r in rows),
        ))
    assert q19.probe(events, cuts) == want
    # and the data holds what the query is about: full auctions, whose
    # eleventh bid and later are left out, and bids of one price
    rows = q19.mv(events, 30_000, nexmark_gen.VOCAB)
    b = events["bid"]
    assert 0.3 * len(eid) < len(rows) < 0.9 * len(eid)
    assert sum(r[6] == 10 for r in rows) > 500
    key = np.stack([b["auction"], b["price"]])
    assert len(eid) - np.unique(key, axis=1).shape[1] > 0


def test_the_reference_imports_nothing_of_the_program():
    with open(q19.__file__) as f:
        text = f.read()
    assert "risingwave_tpu" not in text.replace(
        "Imports nothing of the program", ""
    )
    assert "import jax" not in text
