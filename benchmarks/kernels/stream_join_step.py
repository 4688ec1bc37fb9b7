"""Bytes the chunks of an epoch have to move between HBM and the chip
on their way through a stream-to-stream join (the XLA module
``jit_stream_join_step``, risingwave_tpu/executors/stream_join.py),
whatever the layout of the sides.

A row that arrives is read once from its chunk and written once into
its side's store (2 x its bytes), and its join key is looked up twice:
in its own side's index, to be stored under it, and in the other's, to
find its matches (2 x the key's bytes: the least an index can be made
to show). A pair the equi key matches costs one read of the stored row
it pairs with (counted at the narrower side's width: which side a
pair's stored row lies on is not recorded) and one write of the pair
(both rows) to what the join hands on.

That is a lower bound for ANY layout: it names rows, keys and pairs,
not capacities, fingerprints, chain links, prefix sums or the lanes a
padded chunk carries, all of which the chained layout moves on top. A
step that gets cheaper leaves the count true, and the share of the
roofline it reads cannot pass 100% unless the module's time leaves out
part of the work. benchmarks/tests/test_join_step_bytes.py holds the
row, key and pair widths the metric files give to the shapes of the
module compiled for the q4 plan."""


def bytes_moved(
    left_rows: float,
    right_rows: float,
    pairs: float,
    left_row_bytes: int,
    right_row_bytes: int,
    key_bytes: int,
) -> float:
    stored = left_rows * (2 * left_row_bytes + 2 * key_bytes) + right_rows * (
        2 * right_row_bytes + 2 * key_bytes
    )
    paired = pairs * (
        min(left_row_bytes, right_row_bytes) + left_row_bytes + right_row_bytes
    )
    return stored + paired
