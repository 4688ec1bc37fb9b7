"""The plain reference against events written out by hand."""

import numpy as np

import nexmark_q8_ref as q8

T = 1_436_918_400_000  # on a 10 s and a 2 s boundary


def _cols(**kw):
    return {k: np.asarray(v, dtype=np.int64) for k, v in kw.items()}


def test_q8_joins_person_and_seller_in_the_same_window():
    vocab = {("person", "name"): ["Ann A", "Bob B", "Cy C"]}
    events = {
        "person": _cols(
            eid=[0, 50, 100],
            id=[1000, 1001, 1002],
            name=[0, 1, 2],
            date_time=[T + 10, T + 5000, T + 10_500],
        ),
        "auction": _cols(
            eid=[1, 2, 51, 101, 102],
            seller=[1000, 1000, 1002, 1001, 1002],
            date_time=[T + 20, T + 30, T + 6000, T + 10_600, T + 10_700],
        ),
    }
    # 1000 registers and sells in window T; 1001 registers in T but sells
    # in T+10 s; 1002 sells in T before registering in T+10 s, and again
    # in T+10 s after registering
    assert q8.mv(events, np.inf, vocab) == {
        (1000, "Ann A", T), (1002, "Cy C", T + 10_000),
    }
    assert q8.mv(events, 102, vocab) == {(1000, "Ann A", T)}
    assert q8.mv(events, 1, vocab) == set()
    assert q8.probe(events, [0, 1, 2, 102, 103]) == [
        (0,), (0,), (1,), (1,), (2,),
    ]


def test_q8_auction_before_person_joins_when_the_person_arrives():
    vocab = {("person", "name"): ["Ann A"]}
    events = {
        "person": _cols(eid=[50], id=[1000], name=[0], date_time=[T + 900]),
        "auction": _cols(eid=[1], seller=[1000], date_time=[T + 20]),
    }
    assert q8.probe(events, [2, 50, 51]) == [(0,), (0,), (1,)]
    assert q8.mv(events, 51, vocab) == {(1000, "Ann A", T)}
