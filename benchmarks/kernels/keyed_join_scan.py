"""Bytes a call of the keyed join's scan (the XLA module
``jit_keyed_join_scan``, risingwave_tpu/executors/keyed_join.py) has to
move between HBM and the chip, whatever its passes.

A call takes the many side's lanes from HBM and leaves two int32 result
lanes there: per lane it reads the live flag (1 B), the join-key
columns and the columns of its side that the residual names, once, and
writes 2 x 4 B (which changed row retracts the lane, which inserts it).
That is all a call HAS to move: the passes (one a changed row of the
unique side) need not touch HBM again, and on the v5e they do not. The
module compiled for that chip at the cells' 2^21 and 2^22 lanes keeps
the key and residual halves and the two result lanes in the chip's fast
memory (``S(1)`` in the optimized HLO) for the whole loop and copies the
results out once after it; benchmarks/tests/test_scan_bytes.py compiles it for a
described v5e and holds this count to the module's own arguments and
outputs. A count of lanes x passes x 17 or 33 B, as if every pass
streamed the lanes from HBM, read 57-61% and 118-120% (my chip runs,
PR 27): not what the module moves.

So the share says how far the call is from the time its HBM traffic
alone would take; it falls as the passes a call grow, because their
time is on-chip work and not HBM's. It cannot pass 100% unless the
module's time leaves out part of the work."""

RESULT_BYTES = 2 * 4


def bytes_per_lane_call(key_bytes: int, residual_bytes: int) -> int:
    return 1 + key_bytes + residual_bytes + RESULT_BYTES


def bytes_moved(call_lanes: float, key_bytes: int, residual_bytes: int) -> float:
    """``call_lanes`` = the many side's lanes, summed over the calls."""
    return call_lanes * bytes_per_lane_call(key_bytes, residual_bytes)
