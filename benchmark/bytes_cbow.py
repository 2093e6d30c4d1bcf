"""Bytes that one CBOW step must move, by ``bytes.py``'s rule: every touched
row gathered once, read and written once by the scatter, so 3 x rows x d x
itemsize. Computed bytes, never speeds; nothing here imports the program.

A step trains ``batch_positions`` positions. Each touches the
``rows_per_bag`` live ``syn0`` rows of its bag (a property of the window,
the shrink draws and the sentences, counted by the program on the device)
and 1 + ``negatives`` rows of ``syn1``: its own word and its noise words.
"""


def cbow_step_rows(batch_positions: int, negatives: int,
                   rows_per_bag: float) -> float:
    return int(batch_positions) * (float(rows_per_bag) + 1 + int(negatives))


def cbow_step_bytes(batch_positions: int, negatives: int, dim: int,
                    rows_per_bag: float, itemsize: int = 4) -> dict:
    rows = cbow_step_rows(batch_positions, negatives, rows_per_bag)
    gather = rows * int(dim) * int(itemsize)
    return {"rows": rows, "gather": gather, "scatter": 2 * gather,
            "total": 3 * gather}
