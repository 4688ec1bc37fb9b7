"""Bytes the general over-window's steps of one barrier (the XLA modules
``jit__general_over_step``, ``_emit`` and ``_commit``, risingwave_tpu/
executors/over_window.py) have to move between HBM and the chip: a
lower bound, and a count of the WORK, whatever implements it.

The executor keeps one row an input row in a pk-keyed arena, and a
shadow of what it handed on. A chunk's rows are written into the arena;
every partition a row touched is dirty; the rows of the dirty
partitions are ordered by (partition, order column, stream key) and
every window call is recomputed over them; what differs from the shadow
is retracted and inserted again. Per barrier that work has to, at the
least:

- read every input row from its chunk and write it into the arena
  (``in_rows`` x ``row_bytes``, twice);
- read every row of a dirty partition once (``dirty_rows`` x
  ``row_bytes``), read and write once the words its order is made of
  (the partition and order lanes, ``key_bytes``), and write each call's
  result (``out_bytes``: the values and their NULL flags);
- read every retracted row from the shadow and every inserted row from
  the arena and write both into the chunks handed on
  (``retract_rows + insert_rows`` x (``row_bytes + out_bytes``), twice).

Nothing here is the arena's capacity: the program of PR 49 sorts and
gathers EVERY lane of the arena a step, so its share of the roofline is
small, and a program that touches the dirty partitions alone would read
near this count (PERF.md 6, PR 46 (y): a model that charges ``capacity``
goes stale the day the store-wide pass is gone). All four counts are in
the args of the span ``over.barrier``; ``row_bytes`` in ``over.step``'s.
benchmarks/tests/test_over_window_bytes.py holds the widths to
nexmark_q6's planned executor."""


def bytes_moved(
    in_rows: float, dirty_rows: float, retract_rows: float,
    insert_rows: float, row_bytes: int, key_bytes, out_bytes: int,
) -> float:
    """One barrier's steps. ``key_bytes``: the widths of the partition
    lanes and of the order lane."""
    return (
        2 * in_rows * row_bytes
        + dirty_rows * (row_bytes + 2 * sum(key_bytes) + out_bytes)
        + 2 * (retract_rows + insert_rows) * (row_bytes + out_bytes)
    )
