"""Bytes that one packed SGNS step and one top-k dispatch MUST move, from
shapes alone: the denominators of the ``*_hbm_share`` metrics. Computed
bytes, never speeds; nothing here imports the program.

The least a step can do is read every row it touches once (the gather) and
read and write every row it updates once (the scatter-add): 3 x rows x d x
itemsize. ROADMAP's yardstick for the grid step (V=1M, d=300, B=8192, C=7,
n=5, f32) is 352,256 rows, 423 MB gathered, 845 MB scattered, 1.27 GB.
"""

import math


def packed_pair_slots(batch_positions: int, window: int) -> int:
    """Dense pair slots of one packed step that covers ``batch_positions``
    center positions in expectation: E[pairs/position] = (W-1)^2 / W for a
    shrink draw b ~ U[0, W) (the program's ``packed_pair_batch`` rule,
    restated; a test holds the two together)."""
    w = int(window)
    per_position = max((w - 1) ** 2 / w, 1.0)
    return max(math.ceil(int(batch_positions) * per_position), 2 * w - 3)


def step_rows(pairs: int, contexts_per_pair: int, negatives: int) -> int:
    """Table rows one step touches: per center 1 syn0 row, and per
    (center, context) 1 + ``negatives`` syn1 rows."""
    return int(pairs) * (1 + int(contexts_per_pair) * (1 + int(negatives)))


def step_bytes(pairs: int, contexts_per_pair: int, negatives: int, dim: int,
               itemsize: int = 4) -> dict:
    rows = step_rows(pairs, contexts_per_pair, negatives)
    gather = rows * int(dim) * int(itemsize)
    return {"rows": rows, "gather": gather, "scatter": 2 * gather,
            "total": 3 * gather}


def packed_step_bytes(batch_positions: int, window: int, negatives: int,
                      dim: int, itemsize: int = 4) -> dict:
    """The packed step: batch rows ARE pairs (one context each)."""
    return step_bytes(packed_pair_slots(batch_positions, window), 1,
                      negatives, dim, itemsize)


def topk_dispatch_bytes(padded_rows: int, dim: int, itemsize: int = 4) -> int:
    """One top-k dispatch reads the whole table once, whatever Q is."""
    return int(padded_rows) * int(dim) * int(itemsize)
