"""The top-k programs of a served cell, read from the run's device trace:
what the sharded cell's per-layer readers share.

``program_trace`` keeps the first device's ops, each under its outermost
``glint.`` scope, and its programs' runs. This takes, of those, the ops
that ran inside a run of a top-k program (``local_topk``,
``local_topk_batch``), and sums their self time by scope: ``glint.score``
(a shard's pass over its rows and the mask terms), ``glint.topk`` (the
local top-k), ``glint.merge`` (the all-gathers over the model axis, the
second top-k, the take of the ids). It also reads every device's own time
in those programs, for the slowest, and the round's programs a
``req.dispatch`` span covers, for what the host pays around them.

A program without the scopes (the parent of PR 47), a trace without program
lines (the CPU backend's) or a run without a trace gives None everywhere:
every reader built on this returns None and raises nothing.
"""

import re
import statistics

from benchmark import program_trace
from benchmark.trace_reduce import find_xplane_files, merged, self_times

TOPK = re.compile(r"top_?k", re.I)
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def _inside(ops, runs):
    """The ops that start inside one of the merged ``runs``."""
    out, i = [], 0
    for op in sorted(ops):
        while i < len(runs) and runs[i][1] <= op[0]:
            i += 1
        if i < len(runs) and runs[i][0] <= op[0]:
            out.append(op)
    return out


def read(run):
    """{"runs", "scope_s", "program_s", "slowest_s", "by_device"} of the
    traced top-k programs, or None where the trace has none."""
    if getattr(run, "_topk_trace", None) is None:
        data = program_trace.read(run)
        if not data:
            return None
        runs = [m for m in data["modules"] if TOPK.search(m[2])]
        if not runs:
            return None
        scope_s = {k: ns / 1e9 for k, ns in self_times(
            _inside(data["ops"], merged(runs))).items()}
        by_device = per_device_seconds(run)
        out = {
            "runs": len(runs),
            "scope_s": scope_s,
            "program_s": sum(d for _, d, _ in runs) / 1e9 / len(runs),
            "by_device": by_device,
            "slowest_s": max(by_device.values(), default=None),
        }
        run._topk_trace = out
        run.say(
            f"top-k trace: {out['runs']} runs on the first device, "
            f"{1e3 * out['program_s']:.4f} ms a run; by scope "
            + ", ".join(f"{k or 'no glint.* scope'} "
                        f"{1e3 * s / out['runs']:.4f} ms"
                        for k, s in sorted(scope_s.items(),
                                           key=lambda kv: -kv[1]))
            + "; a run by device "
            + ", ".join(f"{n} {1e3 * s:.4f} ms"
                        for n, s in sorted(by_device.items())))
    return run._topk_trace


def per_device_seconds(run) -> dict:
    """{device plane: mean seconds of a top-k program's run there}."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(
            find_xplane_files(run.trace_dir)[-1]).planes:
        if not _DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            runs = [float(e.duration_ns) for e in line.events
                    if TOPK.search(e.name)]
            if runs:
                out[plane.name] = sum(runs) / len(runs) / 1e9
    return out


def scope_ms(run, scope: str):
    """Device ms of one top-k dispatch under ``scope``: self time of its
    ops inside the traced runs of the top-k programs, over their number,
    on the first device. None where no op carries a ``glint.`` scope."""
    data = read(run)
    if not data or set(data["scope_s"]) <= {program_trace.UNSCOPED}:
        return None
    return 1e3 * data["scope_s"].get(scope, 0.0) / data["runs"]


def launch_ms(run):
    """Median, over the traced rounds, of a ``req.dispatch`` span less the
    first device's time in the programs that began inside it: what the
    host pays to launch the round's programs on the model's devices and to
    read their results back. None where the trace has no program line."""
    data = program_trace.read(run)
    rounds = program_trace.ring_spans(run, "req.dispatch")
    if not data or not data["modules"] or not rounds:
        return None
    modules = sorted((s / 1e9, d / 1e9) for s, d, _ in data["modules"])
    host = []
    for start, dur in rounds:
        device = sum(d for s, d in modules if start <= s <= start + dur)
        if device:  # a round whose programs the trace caught
            host.append(dur - device)
    return 1e3 * statistics.median(host) if host else None
