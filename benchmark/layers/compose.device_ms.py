"""Device time of one compose dispatch, in ms: the traced runs of the
pull-average program (``jit_local_pull_average``), over their number."""

import re

from benchmark.layer_util import module_time


def read(run):
    if not run.trace:
        return None
    seconds, runs = module_time(run, re.compile(r"pull_average"))
    return 1e3 * seconds / runs if runs else None
