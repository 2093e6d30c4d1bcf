"""Bytes the model-axis exchange of one packed SGNS step must move, from
shapes alone: the numerator of ``exchange.ici_share``. Computed bytes,
never speeds; nothing here imports the program.

Every chip pulls every row its batch names (a centre, a context and
``negatives`` negatives a pair slot) as float32 and the chips all-reduce
them. An all-reduce of S bytes among n chips makes each chip send at least
2 (n - 1) / n x S: a reduce-scatter and an all-gather of (n - 1) / n x S
each, which no algorithm goes under.
"""

from benchmark.bytes import packed_pair_slots


def exchange_bytes(batch_positions: int, window: int, negatives: int,
                   dim: int, chips: int) -> int:
    """S: the float32 bytes one chip hands the step's all-reduces; nothing
    crosses where there is one chip."""
    if int(chips) <= 1:
        return 0
    rows = packed_pair_slots(batch_positions, window) * (2 + int(negatives))
    return rows * int(dim) * 4


def all_reduce_wire_bytes(payload: int, chips: int) -> float:
    """The least one chip sends to all-reduce ``payload`` bytes."""
    n = int(chips)
    return 2.0 * (n - 1) / n * payload if n > 1 else 0.0
