"""Nexmark query pipelines.

Reference queries (e2e_test/nexmark/):
- q5 (hot items): bids per auction per hop window (size 10s, slide 2s),
  then the max-count auction(s) per window. What this module builds is
  "q5 counts (q5-lite)", the stateful core alone: the hop-window bid
  count per auction — the HashAgg stage that dominates runtime (VERDICT
  r1 next-step 1). The whole query (the counts' per-window maximum and
  the join back on ``num >= maxn``) is planned from SQL
  (tests/test_nexmark_q5_sql.py, benchmarks/configs/nexmark_q5.json).
- q8 (monitor new users): persons who opened auctions in the same 10s
  tumble window — per-side tumble + DISTINCT, then a stream-stream
  INNER join on (person.id, window) = (auction.seller, window)
  (e2e_test/nexmark/ q8 .slt).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax.numpy as jnp

from risingwave_tpu.executors import AppendOnlyDedupExecutor, DynamicMaxFilterExecutor, HashAggExecutor, HashJoinExecutor, HopWindowExecutor
from risingwave_tpu.executors.materialize import DeviceMaterializeExecutor
from risingwave_tpu.ops.agg import AggCall
from risingwave_tpu.runtime import Pipeline, TwoInputPipeline

Q5_WINDOW_MS = 10_000
Q5_SLIDE_MS = 2_000
Q8_WINDOW_MS = 10_000


@dataclass
class Q5Lite:
    pipeline: Pipeline
    agg: HashAggExecutor
    mview: object  # Materialize or DeviceMaterialize


def build_q5_lite(
    capacity: int = 1 << 16,
    window_ms: int = Q5_WINDOW_MS,
    slide_ms: int = Q5_SLIDE_MS,
    state_cleaning: bool = True,
) -> Q5Lite:
    """bids -> hop window -> COUNT(*) per (auction, window_start) -> MV.

    With ``state_cleaning``, an event-time watermark issued as
    ``pipeline.watermark("date_time", wm)`` is translated by the hop
    executor into a ``window_start`` watermark, which closes windows
    that can receive no further rows: pending updates are flushed
    downstream, then state is freed silently (EOWC-final — the MV keeps
    closed windows' final counts). Mirrors the reference's
    watermark-driven state cleaning on q5's agg state
    (state_table.rs:1133).
    """
    hop = HopWindowExecutor("date_time", window_ms, slide_ms)
    agg = HashAggExecutor(
        group_keys=("auction", "window_start"),
        calls=(AggCall("count_star", None, "num"),),
        schema_dtypes={
            "auction": jnp.int64,
            "window_start": jnp.int64,
        },
        capacity=capacity,
        table_id="q5.agg",
        # HopWindowExecutor already translates the event-time watermark
        # into a window_start watermark (start >= first_start(wm) for any
        # future row), so windows below it are closed as-is: retention 0
        window_key=("window_start", 0, False) if state_cleaning else None,
    )
    # device-resident MV: the host-map executor pulls every flush chunk
    # to the host; this one stays in HBM end to end
    mview = DeviceMaterializeExecutor(
        pk=("auction", "window_start"),
        columns=("num",),
        schema_dtypes={
            "auction": jnp.int64,
            "window_start": jnp.int64,
            "num": jnp.int64,
        },
        table_id="q5.mview",
        capacity=max(1 << 12, capacity),
    )
    return Q5Lite(Pipeline([hop, agg, mview]), agg, mview)


@dataclass
class Q8:
    pipeline: TwoInputPipeline
    join: HashJoinExecutor
    mview: object  # Materialize or DeviceMaterialize


def build_q8(
    capacity: int = 1 << 14,
    fanout: int = 8,
    out_cap: int = 1 << 14,
    window_ms: int = Q8_WINDOW_MS,
    state_cleaning: bool = True,
) -> Q8:
    """person ⋈ auction per 10s tumble window (the q8 north star).

    Plan (mirrors the reference's stream plan for q8: two tumbles, two
    distinct aggs, one HashJoin):

      person  -> tumble(date_time)  -> DISTINCT(id, name, starttime)   ┐
                                                                        ⋈ inner on
      auction -> tumble(date_time)  -> DISTINCT(seller, astarttime)   ┘ (id,starttime)=(seller,astarttime)
              -> MV pk=(id, starttime)

    Both input streams are append-only, so each DISTINCT is an
    AppendOnlyDedup (the reference's planner makes the same
    specialization). Watermarks on date_time close old windows through
    the hop -> dedup -> join chain.
    """
    person_chain = [
        HopWindowExecutor("date_time", window_ms, window_ms, out_start="starttime"),
        AppendOnlyDedupExecutor(
            keys=("id", "name", "starttime"),
            schema_dtypes={
                "id": jnp.int64,
                "name": jnp.int32,
                "starttime": jnp.int64,
            },
            capacity=capacity,
            window_key=("starttime", 0) if state_cleaning else None,
            table_id="q8.dedup_person",
        ),
    ]
    auction_chain = [
        HopWindowExecutor("date_time", window_ms, window_ms, out_start="astarttime"),
        AppendOnlyDedupExecutor(
            keys=("seller", "astarttime"),
            schema_dtypes={"seller": jnp.int64, "astarttime": jnp.int64},
            capacity=capacity,
            window_key=("astarttime", 0) if state_cleaning else None,
            table_id="q8.dedup_auction",
        ),
    ]
    join = HashJoinExecutor(
        left_keys=("id", "starttime"),
        right_keys=("seller", "astarttime"),
        left_dtypes={
            "id": jnp.int64,
            "name": jnp.int32,
            "starttime": jnp.int64,
        },
        right_dtypes={"seller": jnp.int64, "astarttime": jnp.int64},
        capacity=capacity,
        fanout=fanout,
        out_cap=out_cap,
        window_cols=("starttime", "astarttime") if state_cleaning else None,
        table_id="q8.join",
    )
    mview = DeviceMaterializeExecutor(
        pk=("id", "starttime"),
        columns=("name",),
        schema_dtypes={
            "id": jnp.int64,
            "starttime": jnp.int64,
            "name": jnp.int32,
        },
        table_id="q8.mview",
        capacity=max(1 << 12, capacity),
    )
    pipeline = TwoInputPipeline(person_chain, auction_chain, join, [mview])
    return Q8(pipeline, join, mview)


@dataclass
class Q7:
    pipeline: TwoInputPipeline
    join: HashJoinExecutor
    agg: HashAggExecutor
    mview: object  # Materialize or DeviceMaterialize


def build_q7(
    capacity: int = 1 << 16,
    fanout: int = 4,
    out_cap: int = 1 << 14,
    window_ms: int = 10_000,
    state_cleaning: bool = True,
    agg_capacity: Optional[int] = None,
    filter_capacity: Optional[int] = None,
    bucketed: bool = True,
) -> Q7:
    """Highest bid per 10s tumble window (Nexmark q7, e2e_test/nexmark/).

    Reference plan shape: bids self-join against the per-window MAX
    (dynamic-filter-free formulation):

      bid -> tumble -> (left)  bids keyed (wstart, price)          ┐
                                                                     ⋈ inner on
      bid -> tumble -> MAX(price) per window -> (right) (mwstart,  ┘ (wstart,price)=(mwstart,maxprice)
              maxprice) change stream [U-/U+ on every new max]
          -> MV pk=(wstart, auction, bidder)

    The right side is the RETRACTING input: each new window max emits
    U-(old)/U+(new), which the join turns into delete/insert of the
    matching bid pairs — exercising the join's retraction path end to
    end. Both sides need the SAME bid chunks: drive with
    ``pipeline.push_left(c); pipeline.push_right(c)``.

    With ``state_cleaning``, advance ``pipeline.watermark("date_time",
    max_event_ts)`` every barrier: bid-side state is every bid of every
    OPEN window — watermarks are what keep it bounded (the same
    contract as the reference's watermark state cleaning on q7).
    """
    left_chain = [
        HopWindowExecutor("date_time", window_ms, window_ms, out_start="wstart"),
        # dynamic pre-filter (dynamic_filter.rs analogue): only bids at
        # or above their window's running max can ever match a future
        # max — keeps the join's bid-side state O(maxima chain), not
        # O(bids); see executors/dynamic_filter.py
        DynamicMaxFilterExecutor(
            group_col="wstart",
            value_col="price",
            schema_dtypes={"wstart": jnp.int64, "price": jnp.int64},
            # growth REBUILDS the table at a new capacity, which
            # recompiles every fused program touching it (~30s each on
            # TPU) — callers that know their volume size this up front
            capacity=filter_capacity or max(1 << 10, capacity >> 6),
            window_key=("wstart", 0) if state_cleaning else None,
            table_id="q7.maxfilter",
            # bucketed=False is the legacy unbounded-rehash twin (the
            # RW-E803 wedge class): soak baselines and the analyzer's
            # detection tests build it deliberately
            bucketed=bucketed,
        ),
    ]
    right_chain = [
        HopWindowExecutor("date_time", window_ms, window_ms, out_start="mwstart"),
        HashAggExecutor(
            group_keys=("mwstart",),
            calls=(AggCall("max", "price", "maxprice"),),
            schema_dtypes={"mwstart": jnp.int64, "price": jnp.int64},
            capacity=agg_capacity or max(1 << 12, capacity >> 4),
            window_key=("mwstart", 0, False) if state_cleaning else None,
            table_id="q7.maxagg",
        ),
    ]
    join = HashJoinExecutor(
        left_keys=("wstart", "price"),
        right_keys=("mwstart", "maxprice"),
        left_dtypes={
            "wstart": jnp.int64,
            "price": jnp.int64,
            "auction": jnp.int64,
            "bidder": jnp.int64,
        },
        right_dtypes={"mwstart": jnp.int64, "maxprice": jnp.int64},
        capacity=capacity,
        fanout=fanout,
        out_cap=out_cap,
        # the agg's delta chunks carry a maxprice null lane (all-False
        # here since price is non-null); declare it so the bucket state
        # would round-trip NULLs faithfully if that ever changes
        right_nullable=("maxprice",),
        window_cols=("wstart", "mwstart") if state_cleaning else None,
        table_id="q7.join",
        bucketed=bucketed,
    )
    mview = DeviceMaterializeExecutor(
        pk=("wstart", "auction", "bidder"),
        columns=("price",),
        schema_dtypes={
            "wstart": jnp.int64,
            "auction": jnp.int64,
            "bidder": jnp.int64,
            "price": jnp.int64,
        },
        table_id="q7.mview",
        capacity=max(1 << 12, capacity),
    )
    pipeline = TwoInputPipeline(left_chain, right_chain, join, [mview])
    agg = right_chain[1]
    return Q7(pipeline, join, agg, mview)
