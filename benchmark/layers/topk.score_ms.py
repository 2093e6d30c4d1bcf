"""Device ms one top-k dispatch spends under ``glint.score``: a
shard's pass over its rows (the product with the query batch) and the mask
terms.
Self time of the ops inside the traced runs of the top-k programs, over
their number, on the first device. A program without the scope gives
nothing to read."""

from benchmark.topk_trace import scope_ms


def read(run):
    return scope_ms(run, "glint.score")
