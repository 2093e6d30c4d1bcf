"""Device ms a packed step spends under ``glint.compose/bag``: what sharing a
composed word across bags costs. There each position's hidden vector is
formed as the masked sum of its lanes' composed words over the sum of their
counts, and on the way back each span word's gradient as the sum over the
bags that hold it. Self time, cut to the traced scan's runs as the
``step.*`` readers are.

``program_trace`` files an op under ``glint.<phase>`` and its table alone,
so the trace is reduced once more here with the inner scope lifted into the
phase's name. A program without the scope (word level, skip-gram, an older
one) gives nothing to read."""

from benchmark import program_trace
from benchmark.trace_reduce import find_xplane_files

INNER, LIFTED = "glint.compose/bag", "glint.compose_bag"


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
