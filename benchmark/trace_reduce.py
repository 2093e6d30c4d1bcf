"""From a ``jax.profiler`` trace directory to the numbers the per-layer
metrics read. Read with ``jax.profiler.ProfileData`` alone.

The reduction of ``scripts/trace_summarize.py`` (PR 22 and before), changed
where a chip trace needs it: device busy time is the UNION of the op
intervals (a ``while`` op spans its body's ops, so a sum counts the scan
twice), an op's time is its SELF time (its interval less what its children
cover), and idle gaps are labelled by the benchmark's host annotation
(``bench.*``) that covers them.

Planes: ``/device:TPU:<n>`` with the lines ``XLA Ops`` (one event per
executed HLO op) and ``XLA Modules`` (one per executed program). On the CPU
backend (rehearsals and tests only, never a metric) the ops are the events of
``/host:CPU`` that carry an ``hlo_op`` stat.
"""

import collections
import glob
import os
import re

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective|"
    r"psum|ppermute", re.I)
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def find_xplane_files(trace_dir: str) -> list:
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def _events(line):
    return [(float(e.start_ns), float(e.duration_ns), e.name)
            for e in line.events]


def load(profile) -> dict:
    """{"devices": [{"name", "ops", "modules"}], "host": [...]}: every list
    holds (start_ns, duration_ns, name)."""
    devices, host = [], []
    cpu_ops = []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] += _events(line)
                elif line.name == "XLA Modules":
                    dev["modules"] += _events(line)
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((float(e.start_ns), float(e.duration_ns),
                                     e.name))
                    elif any(k == "hlo_op" for k, _ in e.stats):
                        cpu_ops.append((float(e.start_ns),
                                        float(e.duration_ns), e.name))
    if not devices and cpu_ops:
        devices = [{"name": "/host:CPU", "ops": cpu_ops, "modules": []}]
    return {"devices": devices, "host": host}


def merged(intervals) -> list:
    """Sorted disjoint [start, end] covering the same time."""
    out = []
    for s, d, _ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return out


def self_times(intervals) -> collections.Counter:
    """Exclusive ns per name: nested time is charged to the innermost."""
    out = collections.Counter()
    stack = []  # [name, start, end, child_ns]

    def pop():
        name, start, end, child = stack.pop()
        out[name] += max(end - start - child, 0.0)
        if stack:
            stack[-1][3] += end - start

    for s, d, name in sorted(intervals, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][2] <= s:
            pop()
        stack.append([name, s, s + d, 0.0])
    while stack:
        pop()
    return out


def reduce(trace_dir: str, window_s: float, top: int = 10) -> dict:
    from jax.profiler import ProfileData

    paths = find_xplane_files(trace_dir)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir!r}")
    return reduce_profile(ProfileData.from_file(paths[-1]), window_s, top)


def reduce_profile(profile, window_s: float, top: int = 10) -> dict:
    data = load(profile)
    if not data["devices"]:
        raise ValueError("the trace holds no device plane")
    busy, ops, modules = [], collections.Counter(), {}
    collective = 0.0
    for dev in data["devices"]:
        busy.append(sum(e - s for s, e in merged(dev["ops"])) / 1e9)
        st = self_times(dev["ops"])
        ops.update(st)
        collective += sum(ns for n, ns in st.items() if COLLECTIVE.search(n))
        for s, d, name in dev["modules"]:
            m = modules.setdefault(name, {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += d / 1e9
    n_dev = len(data["devices"])
    # Idle gaps of the first device, labelled by the host annotation that
    # covers each gap's middle, summed by label.
    first = merged(data["devices"][0]["ops"])
    host = sorted(data["host"])
    gaps = collections.Counter()
    for (_, e0), (s1, _) in zip(first, first[1:]):
        mid = (e0 + s1) / 2
        label = next((n for s, d, n in host if s <= mid <= s + d),
                     "host: outside the benchmark's spans")
        gaps[label] += (s1 - e0) / 1e9
    for m in modules.values():  # per device, like busy_s
        m["count"] /= n_dev
        m["seconds"] /= n_dev
    return {
        "busy_s": sum(busy) / n_dev,
        "window_s": float(window_s),
        "devices": n_dev,
        "op_events": sum(len(d["ops"]) for d in data["devices"]),
        "collective_s": collective / 1e9 / n_dev,
        "ops_self_s": {n: ns / 1e9 / n_dev for n, ns in ops.items()},
        "modules": modules,
        "host_spans": collections.Counter(n for _, _, n in host),
        "breakdown": {
            # an op's name is its whole HLO text: its head tells it apart
            "device_ops": [[n[:160], ns / 1e9 / n_dev]
                           for n, ns in ops.most_common(top)],
            "idle_gaps": [[n, s] for n, s in gaps.most_common(top)],
        },
    }


def dump(trace_dir: str, out=print) -> None:
    """What a trace holds, for reading one by hand."""
    from jax.profiler import ProfileData

    for path in find_xplane_files(trace_dir):
        out(f"FILE {path} {os.path.getsize(path)} bytes")
        for plane in ProfileData.from_file(path).planes:
            lines = list(plane.lines)
            out(f" PLANE {plane.name!r} lines={len(lines)}")
            for line in lines:
                evs = list(line.events)
                names = collections.Counter(e.name for e in evs)
                out(f"  LINE {line.name!r} events={len(evs)} "
                    f"names={len(names)}")
                if _DEVICE_PLANE.match(plane.name) or "python" not in \
                        line.name:
                    for n, c in names.most_common(12):
                        tot = sum(e.duration_ns for e in evs if e.name == n)
                        out(f"     {c:7d} x {n[:90]!r} {tot / 1e6:.3f} ms")


if __name__ == "__main__":
    import sys

    dump(sys.argv[1])
