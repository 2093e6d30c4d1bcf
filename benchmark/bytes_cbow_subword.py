"""Bytes that one subword CBOW step must move, by ``bytes.py``'s rule: every
touched row gathered once, read and written once by the scatter, so 3 x rows
x d x itemsize. Computed bytes, never speeds; nothing here imports the
program.

A step trains ``batch_positions`` positions. It touches the live ``syn0``
rows of the groups of its span's words, each word's group ONCE however many
bags hold the word (``group_rows``: the span words composed times the rows a
word has, counted by the program on the device), and 1 + ``negatives`` rows
of ``syn1`` a position: its own word and its noise words.
"""


def cbow_subword_step_rows(batch_positions: int, negatives: int,
                           group_rows: float) -> float:
    return float(group_rows) + int(batch_positions) * (1 + int(negatives))


def cbow_subword_step_bytes(batch_positions: int, negatives: int, dim: int,
                            group_rows: float, itemsize: int = 4) -> dict:
    rows = cbow_subword_step_rows(batch_positions, negatives, group_rows)
    gather = rows * int(dim) * int(itemsize)
    return {"rows": rows, "gather": gather, "scatter": 2 * gather,
            "total": 3 * gather}
