"""Probe -> boundary mapping, freshness, the percentile rule and the
read-back of the streams' own tables."""

import numpy as np

import checks

# (position, t_inject, t_return, reference probe value)
BOUNDS = [
    (100, 0.0, 0.5, (10, 500)),
    (200, 1.0, 1.6, (17, 1000)),
    (300, 2.0, 2.7, (17, 1000 + 0)),  # same value as the one before
    (400, 3.0, 3.5, (30, 2000)),
]


def test_sound_replies_map_to_their_boundary():
    replies = [
        (0.6, 0.61, (10, 500), ""),   # after epoch 0 returned
        (1.2, 1.21, (10, 500), ""),   # epoch 1 in flight: still epoch 0
        (1.2, 1.7, (17, 1000), ""),   # issued in flight, answered after
        (2.8, 2.81, (17, 1000), ""),  # two boundaries share it: the newest
        (3.6, 3.61, (30, 2000), ""),
    ]
    shown, problems = checks.match_probes(replies, BOUNDS)
    assert shown == [0, 0, 1, 2, 3] and problems == []


def test_reply_that_matches_no_boundary_is_unsound():
    shown, problems = checks.match_probes(
        [(1.7, 1.71, (16, 995), "")], BOUNDS
    )
    assert shown == [-1] and "no epoch boundary" in problems[0]


def test_stale_and_future_and_failed_replies_are_unsound():
    replies = [
        (1.7, 1.71, (10, 500), ""),    # epoch 1 had returned: stale
        (0.6, 0.61, (30, 2000), ""),   # shows an epoch not yet injected
        (0.6, 0.61, None, "RuntimeError: pgwire error"),
    ]
    shown, problems = checks.match_probes(replies, BOUNDS)
    assert shown == [-1, -1, -1]
    assert "had returned before" in problems[0]
    assert "no epoch boundary" in problems[1]
    assert "pgwire error" in problems[2]


def test_freshness_is_first_showing_reply_minus_due():
    replies = [
        (0.6, 0.61, (10, 500), ""),
        (1.2, 1.7, (17, 1000), ""),
        (1.75, 1.76, (17, 1000), ""),
        (3.6, 3.61, (30, 2000), ""),
    ]
    shown, _ = checks.match_probes(replies, BOUNDS)
    sample = np.array([50, 150, 199, 200, 399, 400])
    due = np.array([0.0, 0.5, 0.9, 1.0, 2.9, 3.0])
    got = checks.freshness_ms(replies, shown, BOUNDS, due, sample)
    assert np.allclose(got[:5], [610, 1200, 800, 2610, 710])
    assert np.isinf(got[5])  # no boundary holds event 400 yet


def test_unsound_replies_show_nothing():
    replies = [(0.6, 0.61, (99, 99), ""), (0.7, 0.71, (10, 500), "")]
    shown, _ = checks.match_probes(replies, BOUNDS)
    got = checks.freshness_ms(replies, shown, BOUNDS, np.array([0.0]),
                              np.array([5]))
    assert np.allclose(got, [710])


def test_percentile_is_lowered_until_ten_samples_lie_beyond():
    v, q, n = checks.percentile(np.arange(1000), 95)
    assert (q, n) == (95, 1000)
    v, q, n = checks.percentile(np.arange(100), 95)
    assert n == 100 and q == 89.0  # ten of a hundred beyond the 89th
    v, q, n = checks.percentile(np.arange(8), 95)
    assert q == 50.0


def test_compare_rows_names_what_differs():
    same, only = checks.compare_rows({(1, 2)}, {(1, 2)})
    assert same and only == []
    same, only = checks.compare_rows({(1, 2), (3, 4)}, {(1, 2), (3, 5)})
    assert not same
    assert ("system only", (3, 4)) in only
    assert ("reference only", (3, 5)) in only


def _people():
    cols = {
        "eid": np.array([0, 50, 100, 150]),
        "id": np.array([1000, 1001, 1002, 1003]),
        "name": np.array([1, 0, 1, 0]),
        "extra": np.array(["aa", "bbb", "c", "dddd"], dtype=object),
    }
    return cols, {("person", "name"): ["Ann A", "Bob B"]}, {("person", "extra")}


def test_table_sample_is_a_run_of_pushed_rows_drawn_from_the_seed():
    cols, _, _ = _people()
    for seed in (1, 2147483999, 2**31 + 5):
        lo, hi, at = checks.table_sample(cols, "id", 3, seed, 2)
        assert len(at) == 2 and at.max() <= 2  # never the row not pushed
        assert (lo, hi) == (cols["id"][at[0]], cols["id"][at[1]] + 1)
    assert checks.table_sample(cols, "id", 0, 1, 2)[2].size == 0


def test_a_row_read_back_narrower_or_twice_differs():
    cols, vocab, text = _people()
    want = checks.table_rows(cols, "person", ["id", "name", "extra"],
                             np.array([1, 2]), vocab, text)
    assert want == [("1001", "Ann A", "bbb"), ("1002", "Bob B", "c")]
    assert checks.rows_differing(list(want), want) == 0
    # payload cut short: the row the system has and the one it should have
    narrow = [("1001", "Ann A", "bb"), want[1]]
    assert checks.rows_differing(narrow, want) == 2
    # delivered twice
    assert checks.rows_differing(want + [want[0]], want) == 1
    assert checks.rows_differing(want[:1], want) == 1


def test_read_tables_counts_and_compares_through_the_query():
    cols, vocab, text = _people()
    spec = {"stream": "person", "key": "id", "sample_rows": 2,
            "columns": ["id", "name", "extra"], "count_sql": "count",
            "rows_sql": "rows {lo} {hi}"}
    table = {1000: ("1000", "Bob B", "aa"), 1001: ("1001", "Ann A", "bbb"),
             1002: ("1002", "Bob B", "c")}

    def query(sql, lost=()):
        if sql == "count":
            return [(str(len(table) - len(lost)),)]
        _, lo, hi = sql.split()
        return [table[k] for k in range(int(lo), int(hi)) if k not in lost]

    # events below ordinal 101 were pushed: three of the four
    ok = checks.read_tables(query, [spec], {"person": cols}, 101, 7, vocab, text)
    assert ok[0]["pushed"] == 3 and ok[0]["sampled"] == 2
    assert ok[0]["differing"] == 0
    bad = checks.read_tables(lambda q: query(q, lost=(1001,)), [spec],
                             {"person": cols}, 101, 7, vocab, text)
    assert bad[0]["differing"] == 2  # the count is one short, and the row
