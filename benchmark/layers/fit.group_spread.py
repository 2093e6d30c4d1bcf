"""(longest - median) / median distance between ``device_steps`` starts of
one epoch, over the whole fit, in percent: one stretched group shows here
and in no median. Left out: an epoch's first two distances (the pipeline
filling) and the two groups in which the benchmark's own profiler started
and stopped."""

from benchmark import fit_trace


def read(run):
    return fit_trace.read(run, "fit.group_spread")
