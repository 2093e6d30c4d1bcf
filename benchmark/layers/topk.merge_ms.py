"""Device ms one top-k dispatch spends under ``glint.merge``: the
all-gathers of the shards' candidates over the model axis, the second top-k
and the take of the ids: what exists only across chips.
Self time of the ops inside the traced runs of the top-k programs, over
their number, on the first device. A program without the scope gives
nothing to read."""

from benchmark.topk_trace import scope_ms


def read(run):
    return scope_ms(run, "glint.merge")
