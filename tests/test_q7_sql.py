"""Nexmark q7 from SQL, end to end (VERDICT r4 missing #3).

The q7 shape — bids joined against their own per-window MAX — plans
from SQL as a SELF-join of two derived tables over one base stream.
The planner collapses the duplicate source to input side "both" and
the runtime feeds every source chunk to both join inputs.

Reference: e2e_test/nexmark/ q7 (join formulation), retracting agg
side through the join's delete/insert path.
"""

import numpy as np
import pytest

from risingwave_tpu.connectors.nexmark import (
    BID_SCHEMA,
    NexmarkConfig,
    NexmarkGenerator,
)
from risingwave_tpu.queries.nexmark_q import build_q7
from risingwave_tpu.sql import Catalog, StreamPlanner

pytestmark = pytest.mark.smoke

Q7_SQL = (
    "CREATE MATERIALIZED VIEW q7 AS "
    "SELECT b.auction, b.bidder, b.price, b.wstart FROM "
    "(SELECT auction, bidder, price, window_start AS wstart "
    " FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND)) AS b "
    "JOIN "
    "(SELECT max(price) AS maxprice, window_start AS mwstart "
    " FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
    " GROUP BY window_start) AS m "
    "ON b.wstart = m.mwstart AND b.price = m.maxprice"
)


def _bid_chunks(n, events=1500, cap=2048, rate=1000):
    gen = NexmarkGenerator(NexmarkConfig(first_event_rate=rate))
    out = []
    while len(out) < n:
        c = gen.next_chunks(events, cap)["bid"]
        if c is not None:
            out.append(c)
    return out


def _rows(mview):
    cols = mview.to_numpy()
    names = ("wstart", "auction", "bidder")
    price = cols.get("price", cols.get("maxprice"))
    return sorted(
        zip(*(np.asarray(cols[n]).tolist() for n in names), price.tolist())
    )


def test_q7_sql_matches_hand_built():
    """Several windows' worth of bids; each new window max retracts the
    previous max's join matches — SQL plan must land on exactly the
    hand-built pipeline's MV."""
    planner = StreamPlanner(Catalog({"bid": BID_SCHEMA}), capacity=1 << 14)
    mv = planner.plan(Q7_SQL)
    assert mv.inputs == {"bid": "both"}
    hand = build_q7(capacity=1 << 14, state_cleaning=False)
    for c in _bid_chunks(8):
        mv.pipeline.push_left(c)
        mv.pipeline.push_right(c)
        hand.pipeline.push_left(c)
        hand.pipeline.push_right(c)
        mv.pipeline.barrier()
        hand.pipeline.barrier()
    got, want = _rows(mv.mview), _rows(hand.mview)
    assert want  # multiple windows, non-trivial
    assert got == want


def test_q7_via_session_insert_routing():
    """Session-level: one INSERT into the base table reaches BOTH join
    sides (side='both' routing through the DML targets)."""
    from risingwave_tpu.frontend.session import SqlSession

    s = SqlSession(Catalog({}), capacity=1 << 10)
    s.execute("CREATE TABLE bid (auction BIGINT, bidder BIGINT, "
              "price BIGINT, date_time BIGINT)")
    s.execute(
        "CREATE MATERIALIZED VIEW q7 AS "
        "SELECT b.auction, b.bidder, b.price, b.wstart FROM "
        "(SELECT auction, bidder, price, window_start AS wstart "
        " FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND)) AS b "
        "JOIN "
        "(SELECT max(price) AS maxprice, window_start AS mwstart "
        " FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) "
        " GROUP BY window_start) AS m "
        "ON b.wstart = m.mwstart AND b.price = m.maxprice"
    )
    s.execute(
        "INSERT INTO bid VALUES (1, 10, 100, 1000), (2, 11, 250, 2000), "
        "(3, 12, 250, 11000)"
    )
    out, _ = s.execute(
        "SELECT auction, price FROM q7 ORDER BY auction"
    )
    # window [0,10s): max 250 -> auction 2; window [10s,20s): auction 3
    assert list(out["auction"]) == [2, 3]
    assert list(out["price"]) == [250, 250]
    # a new max in window 0 RETRACTS auction 2's row
    s.execute("INSERT INTO bid VALUES (4, 13, 300, 3000)")
    out, _ = s.execute("SELECT auction, price FROM q7 ORDER BY auction")
    assert list(out["auction"]) == [3, 4]
    assert list(out["price"]) == [250, 300]


# the source's own text (upstream RisingWave's nexmark q7; tests/
# test_nexmark_q7.py holds it to the benchmark's reference): a bare
# table beside a derived one, window_end, timestamp - INTERVAL, the
# band in WHERE, the equi key the price alone
Q7_SOURCE = (
    "CREATE MATERIALIZED VIEW q7s AS "
    "SELECT B.auction, B.price, B.bidder, B.date_time FROM bid B JOIN ("
    "SELECT MAX(price) AS maxprice, window_end AS date_time "
    "FROM TUMBLE(bid, date_time, INTERVAL '10' SECOND) GROUP BY window_end"
    ") B1 ON B.price = B1.maxprice "
    "WHERE B.date_time BETWEEN B1.date_time - INTERVAL '10' SECOND "
    "AND B1.date_time"
)


def test_q7_as_the_source_writes_it_lands_on_the_rewritten_shapes_view():
    """The source's text and the rewritten one (window_start in the
    equi key, both sides derived) give the same winners over generated
    bids: none of them is stamped on a window's edge, so the source's
    inclusive band and the rewrite's window key select alike."""
    planner = StreamPlanner(Catalog({"bid": BID_SCHEMA}), capacity=1 << 14)
    source = planner.plan(Q7_SOURCE)
    rewritten = planner.plan(Q7_SQL)
    assert source.inputs == rewritten.inputs == {"bid": "both"}
    assert source.pipeline.join.left_keys == ("price",)
    for c in _bid_chunks(8):
        assert not np.any(
            c.to_numpy()["date_time"] % 10_000 == 0
        )  # no bid on an edge
        for mv in (source, rewritten):
            mv.pipeline.push_left(c)
            mv.pipeline.push_right(c)
            mv.pipeline.barrier()

    def winners(mview):
        cols = mview.to_numpy()
        return sorted(zip(*(
            np.asarray(cols[n]).tolist()
            for n in ("auction", "bidder", "price")
        )))

    assert winners(source.mview) == winners(rewritten.mview) != []


def test_q7_source_text_via_session_retracts_and_keeps_the_edge():
    from risingwave_tpu.frontend.session import SqlSession

    s = SqlSession(Catalog({}), capacity=1 << 10)
    s.execute("CREATE TABLE bid (auction BIGINT, bidder BIGINT, "
              "price BIGINT, date_time BIGINT)")
    s.execute(Q7_SOURCE)
    s.execute(
        "INSERT INTO bid VALUES (1, 10, 100, 1000), (2, 11, 250, 2000), "
        "(3, 12, 250, 11000)"
    )
    out, _ = s.execute("SELECT auction, price FROM q7s ORDER BY auction")
    assert list(out["auction"]) == [2, 3]
    # a new max in window 0 RETRACTS auction 2's row
    s.execute("INSERT INTO bid VALUES (4, 13, 300, 3000)")
    out, _ = s.execute("SELECT auction, price FROM q7s ORDER BY auction")
    assert list(out["auction"]) == [3, 4]
    # a bid stamped exactly on window 0's end at its maximum: window 1's
    # (where 300 now wins) and, the band being inclusive, window 0's
    # too — the view is keyed by (row id, window), so both rows stand
    s.execute("INSERT INTO bid VALUES (5, 14, 300, 10000)")
    out, _ = s.execute("SELECT auction, price FROM q7s ORDER BY auction")
    assert list(out["auction"]) == [4, 5, 5]
