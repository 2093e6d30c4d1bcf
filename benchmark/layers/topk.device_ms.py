"""Device time of one top-k dispatch: the traced runs of the top-k
programs, over their number."""

from benchmark.layer_util import topk_seconds


def read(run):
    s = topk_seconds(run)
    return None if s is None else s * 1e3
