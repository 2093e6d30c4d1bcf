"""Bytes that one packed SUBWORD step must move, by ``bytes.py``'s rule:
every touched row gathered once, read and written once by the scatter, so
3 x rows x d x itemsize. Computed bytes, never speeds; nothing here imports
the program.

A step covers ``batch_positions`` centre positions in expectation
(``bytes.packed_pair_slots``). Each centre touches the ``rows_per_center``
live ``syn0`` rows of its group (its word's own and its n-grams' buckets:
a property of the text, counted by the program on the device); each of the
step's pair slots touches 1 + ``negatives`` rows of ``syn1``.
"""

from benchmark.bytes import packed_pair_slots


def subword_step_rows(batch_positions: int, window: int, negatives: int,
                      rows_per_center: float) -> float:
    return (int(batch_positions) * float(rows_per_center)
            + packed_pair_slots(batch_positions, window)
            * (1 + int(negatives)))


def subword_step_bytes(batch_positions: int, window: int, negatives: int,
                       dim: int, rows_per_center: float,
                       itemsize: int = 4) -> dict:
    rows = subword_step_rows(batch_positions, window, negatives,
                             rows_per_center)
    gather = rows * int(dim) * int(itemsize)
    return {"rows": rows, "gather": gather, "scatter": 2 * gather,
            "total": 3 * gather}
