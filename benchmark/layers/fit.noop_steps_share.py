"""Share of the fit's dispatched steps that the device ran and that trained
nothing: over the ``readback_harvest`` spans of the whole fit, the steps
the device ran (arg ``ran``, counted from what it wrote) less the live ones
(arg ``n``), over the steps dispatched. A program whose spans carry no
``ran`` (before ISSUE 44) reads nothing; one whose groups stop at the
corpus end on the device reads 0."""

from benchmark import fit_trace


def read(run):
    harvests = [a for _, _, a in fit_trace.spans(run, "readback_harvest")]
    if not harvests or any("ran" not in a for a in harvests):
        return None
    dispatched = run.cfg["run"]["steps_per_call"] * len(harvests)
    return 100.0 * sum(a["ran"] - a["n"] for a in harvests) / dispatched
