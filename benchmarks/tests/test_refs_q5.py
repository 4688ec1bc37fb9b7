"""The plain reference of nexmark_q5 against bids written out by hand:
a tie, a tie that breaks, and a window that turns over."""

import numpy as np

import nexmark_q5_ref as q5

T = 1_436_918_400_000  # on a 10 s and a 2 s boundary


def _bids(rows):
    """rows: (ordinal, auction, ms after T)."""
    eid, auction, ts = (np.array(c, dtype=np.int64) for c in zip(*rows))
    return {"bid": {"eid": eid, "auction": auction, "date_time": T + ts}}


def _windows_of(ms):
    newest = (T + ms) // 2000 * 2000
    return [newest - 2000 * k for k in range(5)]


def test_a_bid_counts_in_the_five_windows_it_falls_into():
    events = _bids([(4, 1000, 100)])
    assert q5.mv(events, 5) == {(1000, 1, w) for w in _windows_of(100)}
    assert q5.mv(events, 4) == set()
    assert q5.probe(events, [4, 5]) == [(0, 0, 0), (5, 5, T)]


def test_a_tie_keeps_both_and_a_tie_that_breaks_removes_a_row():
    events = _bids([(4, 1000, 100), (5, 1001, 200), (6, 1001, 300)])
    ws = _windows_of(100)
    # after two bids both auctions have one bid in each window: a tie
    assert q5.mv(events, 6) == (
        {(1000, 1, w) for w in ws} | {(1001, 1, w) for w in ws}
    )
    # the third breaks it: 1001 alone, with 2; the view loses rows, so
    # its count alone would not say which boundary a probe shows
    assert q5.mv(events, 7) == {(1001, 2, w) for w in ws}
    assert q5.probe(events, [5, 6, 7]) == [(5, 5, T), (10, 10, T), (5, 10, T)]


def test_a_window_turns_over():
    # 1000 leads the windows that hold second 0..2; 1001 bids at 2.5 s,
    # which opens the window starting at T + 2 s and is outside the one
    # starting at T - 8 s
    events = _bids([(4, 1000, 100), (5, 1000, 1900), (6, 1001, 2500)])
    got = q5.mv(events, 7)
    old, new = _windows_of(100), _windows_of(2500)
    assert new[0] == T + 2000 and new[-1] == old[-2]
    assert (1000, 2, T - 8000) in got and (1001, 1, T - 8000) not in got
    assert (1001, 1, T + 2000) in got  # alone in the new window
    for w in old[:-1]:  # the four windows both fall into: 1000 leads
        assert (1000, 2, w) in got and (1001, 1, w) not in got
    assert len(got) == 6
    assert q5.probe(events, [7]) == [(6, 11, T + 2000)]


def test_the_reference_on_generated_bids_equals_a_plain_loop():
    import nexmark_gen

    bids = nexmark_gen.Generator(11, {"first_event_rate": 3000}).events(
        0, 20_000, ["bid"])
    counts = {}
    b = bids["bid"]
    for a, t in zip(b["auction"].tolist(), b["date_time"].tolist()):
        for k in range(5):
            key = (t // 2000 * 2000 - 2000 * k, a)
            counts[key] = counts.get(key, 0) + 1
    most = {}
    for (w, _), n in counts.items():
        most[w] = max(most.get(w, 0), n)
    want = {(a, n, w) for (w, a), n in counts.items() if n >= most[w]}
    assert q5.mv(bids, np.inf) == want and len(want) >= 8
