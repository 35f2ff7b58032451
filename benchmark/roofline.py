"""Bytes a program has to move, from its shapes, and the share of the
chip's memory roofline that its measured time stands for.

`agg.update` is a scatter-reduce: for each of `rows` input rows it reads a
slot index and one value per accumulator column, and reads and writes the
touched slot of each accumulator. It does almost no arithmetic, so the
memory side of the roofline bounds it ("bytes-bound").
"""

from __future__ import annotations

INDEX_BYTES = 8      # int64 slot index
VALUE_BYTES = 8      # int64 accumulators (tpu.use_32bit_accumulators off)


def agg_update_bytes(rows: int, columns: int = 1) -> int:
    """Least bytes one `agg.update` over `rows` rows must move: the index
    and `columns` values read per row, and each touched state row read and
    written once per column (an upper bound on touched rows is `rows`)."""
    return rows * (INDEX_BYTES + columns * VALUE_BYTES) + (
        2 * rows * columns * VALUE_BYTES)


def roofline_pct(min_bytes: float, seconds: float, peaks: dict) -> float:
    """The least time the chip could take over the time it took, in %."""
    return 100.0 * (min_bytes / peaks["hbm_bytes_per_s"]) / seconds
