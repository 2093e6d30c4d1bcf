"""Bytes that one subword CBOW step with position weights must move:
``bytes_cbow_subword``'s count (every touched row gathered once, read and
written once by the scatter) plus what the position table's gradient reads,
once: the step's gradient, a row a position, and the span's composed words,
a row a word of the step's positions and ``window`` either side, both as they
rest, in whole 128-column lanes of float32; plus the table itself read and
written. Computed bytes, never speeds; nothing here imports the program.
"""

from benchmark.bytes_cbow_subword import cbow_subword_step_bytes

LANES = 128  # columns a row rests in are a multiple of this


def posgrad_bytes(batch_positions: int, window: int, dim: int) -> int:
    cols = -(-int(dim) // LANES) * LANES
    span = int(batch_positions) + 2 * int(window)
    return 4 * cols * (int(batch_positions) + span + 2 * 2 * int(window))


def cbow_pw_subword_step_bytes(batch_positions: int, negatives: int,
                               window: int, dim: int, group_rows: float,
                               itemsize: int = 4) -> dict:
    rows = cbow_subword_step_bytes(
        batch_positions, negatives, dim, group_rows, itemsize)
    extra = posgrad_bytes(batch_positions, window, dim)
    return dict(rows, posgrad=extra, total=rows["total"] + extra)
