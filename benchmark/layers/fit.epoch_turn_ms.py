"""Median over the fit's epochs of the ms from the end of the previous
epoch's last ``readback_harvest`` (the first epoch: from the fit's first
``subsample_compact`` or ``device_steps``) to the start of the epoch's
first ``device_steps``: the compaction pass, its ``n_kept`` sync, the
offsets, the first dispatch's set-up."""

from benchmark import fit_trace


def read(run):
    return fit_trace.read(run, "fit.epoch_turn_ms")
