"""Device time of one packed SGNS step: the traced runs of the packed-scan
program, over the steps they ran."""

from benchmark.layer_util import step_seconds


def read(run):
    s = step_seconds(run)
    return None if s is None else s * 1e3
