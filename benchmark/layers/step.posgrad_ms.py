"""Device ms a packed step spends under ``glint.compose/posgrad``: what
training the position table costs: a reduction a lane over the batch of (the
composed word the lane reads x the position's gradient), the mean over the
lane's live positions, and the table's update. Self time, cut to the traced
scan's runs as ``step.bag_ms`` is, by the same second reduction with the
inner scope lifted into the phase's name. A program without the scope (the
parent, a fit without position weights) gives nothing to read.

A fused kernel is filed under the scope of its ROOT. On the v5e the kernel
that forms the positions' gradient (the sum of coefficient x ``syn1`` row,
``glint.grads`` ops, which the program without weights roots under
``glint.compose/bag``) ends in the first lane's reduction, so its time is read
HERE and ``step.bag_ms`` falls by it: the cell does not list ``step.bag_ms``,
and ``step.compose_ms``, the whole of ``glint.compose``, is the reader to set
beside the sibling's (``tests/test_tpu_compile.py`` holds every kernel with an
op of this scope to a root under ``glint.compose``)."""

from benchmark import program_trace
from benchmark.trace_reduce import find_xplane_files

INNER, LIFTED = "glint.compose/posgrad", "glint.compose_posgrad"


def read(run):
    data = program_trace.read(run)
    if not data or not data["scan_runs"]:
        return None
    from jax.profiler import ProfileData

    path = find_xplane_files(run.trace_dir)[-1]
    meta = {
        op: dict(stats, tf_op=stats["tf_op"].replace(INNER, LIFTED))
        for op, stats in program_trace.op_stats(path).items()
        if "tf_op" in stats
    }
    mine = program_trace.load(ProfileData.from_file(path), meta)
    scope_s, runs = program_trace.scope_seconds(mine["ops"], mine["modules"])
    if LIFTED not in scope_s or not runs:
        return None
    return 1e3 * scope_s[LIFTED] / (runs * run.cfg["run"]["steps_per_call"])
