"""Bytes a barrier of the GroupTopN (the XLA modules ``jit__rank`` and
``jit__diff_gather``, risingwave_tpu/executors/top_n_plain.py: one
call of each) has to move between HBM and the chip: a lower bound.

The pair took the place of ``_group_topk_mask`` (one sort of eight
operands over every lane, three segment reductions and two scatters of
``capacity``) and of the host's walk over the touched groups. It ranks
every lane of the row store by (group, dead last, order key, stream
key): the key's 32-bit digits are packed into one integer a lane (each
digit gives only the bits its values' range needs), cut into 32-bit
words, and the lanes are sorted by one stable sort a word that is keyed
on it and carries every other word and the slot along; a word every
lane shares is skipped on the device, and the program says how many
sorts it made (``passes``, in the args of ``topn.pull``). It then diffs
the ranking against the lane of rows already handed on, and gathers the
two deltas. Per call it has to, at the least:

- per sort made: read and write every operand once (the words, as many
  as the key has digits, and the slot: 4 B each). A sort that fits no
  fast memory passes over its operands many times (a bitonic network
  makes log2(n) (log2(n) + 1) / 2 compare-exchange passes: 253 at 2^22
  lanes); the model counts ONE read and ONE write, which is why the
  share cannot pass 100% and why it is small;
- read the key lanes and the order lane once, write their digits, read
  them again and write the words (``key_bytes`` in, 3 x 4 B a digit);
- read what the flags are made of, once: liveness, ``emitted``,
  ``epoch_dirty`` (1 B each), and every column of the rows and of their
  shadow for the compare that finds rewritten rows (``2 x row_bytes``);
- write and read again the four scans it keeps in the sorted order
  (where a group starts, the last dirty row, the two running counts of
  the deltas; int32 each);
- gather both deltas, ``out_lanes`` lanes of every column read and
  written (the scatter back into ``emitted`` and the shadow touches as
  much again).

benchmarks/tests/test_rank_bytes.py holds the widths to the module
lowered for a described v5e: one sort, of a 32-bit operand a digit and
the slot."""

FLAG_BYTES = 3  # live, emitted, epoch_dirty
SCAN_BYTES = 4 * 4  # seg_start, last_dirty, two running counts
WORD_BYTES = 4  # a digit, a word, the slot


def n_digits(key_bytes) -> int:
    """One digit per 4 B of the stream-key lanes and of the order lane,
    one more for liveness."""
    return sum(-(-b // 4) for b in key_bytes) + 1


def pass_bytes_per_lane(key_bytes) -> int:
    return 2 * WORD_BYTES * (n_digits(key_bytes) + 1)


def fixed_bytes_per_lane(key_bytes, row_bytes: int) -> int:
    """What a call moves a lane whatever its passes. ``key_bytes``: the
    widths of the stream-key lanes and of the order lane."""
    return (
        sum(key_bytes) + 3 * WORD_BYTES * n_digits(key_bytes)
        + FLAG_BYTES + 2 * row_bytes
        + 2 * SCAN_BYTES
    )


def bytes_moved(
    capacity: float, out_lanes: float, passes: float, key_bytes,
    row_bytes: int,
) -> float:
    """One call over a store of ``capacity`` lanes that made ``passes``
    sorts and hands on two chunks of ``out_lanes`` lanes."""
    return (
        capacity * (
            fixed_bytes_per_lane(key_bytes, row_bytes)
            + passes * pass_bytes_per_lane(key_bytes)
        )
        + 2 * out_lanes * 4 * row_bytes
    )
