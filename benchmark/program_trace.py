"""The program's own spans and scopes, read from the run's device trace.

``trace_reduce`` times the layers from outside (its ``bench.*`` annotations,
HLO text). This reads what the program itself writes into the trace:

* every ring-direct span of ``obs.events`` is, while a profiler runs, a host
  annotation ``glint.<span>`` whose stat ``t0_us`` is the ``ts`` of the
  span's ring event. Each is one reading of (trace clock - ring clock); the
  median is the offset that puts the whole ring, the request phases stamped
  after the fact included, on the trace's clock;
* the packed step's ops carry ``glint.<phase>`` (``jax.named_scope``) in
  ``tf_op``, the op's name in the program, a stat of the op's event
  METADATA on the device plane (:func:`op_stats`). A fusion has its root's.

PERF.md section 3 says where each was found in a chip trace read by hand.
Everything is read once a run (``read``) and kept on ``run``; a trace from a
program without the bridge or the scopes gives empty lists and no offset,
and every reader built on this returns None.
"""

import bisect
import collections
import re
import statistics

from benchmark.layer_util import program_spans
from benchmark.trace_reduce import find_xplane_files, merged, self_times

PREFIX = "glint."
UNSCOPED = ""
NO_ANNOTATION = "no glint.* annotation"
_SCOPE = re.compile(r"glint\.\w+(?:/syn[01])?")
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
_PACKED_SCAN = re.compile(r"packed_scan")


def scope_of(tf_op) -> str:
    """``glint.scatter/syn1`` out of ``jit(f)/while/body/glint.scatter/
    syn1/scatter-add``: the outermost ``glint.`` scope with its table."""
    m = _SCOPE.search(tf_op or "")
    return m.group(0) if m else UNSCOPED


def _varint(buf, i):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a slice of ``buf`` for anything with a length."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        else:
            if wire == 2:
                size, i = _varint(buf, i)
            elif wire in (1, 5):
                size = 8 if wire == 1 else 4
            else:
                raise ValueError(f"wire type {wire} in an xplane")
            value, i = buf[i:i + size], i + size
        yield key >> 3, value


def op_stats(path: str) -> dict:
    """{op's event name: {stat: text}} from the event metadata of the
    device planes. ``ProfileData`` gives an event's own stats, not its
    metadata's, and that is where the profiler keeps what it knows of an
    HLO op (its ``tf_op``: the op's name in the program, scopes and all),
    so the file's protobuf is walked here: XSpace.planes = 1; XPlane.name
    = 2, .event_metadata = 4, .stat_metadata = 5 (maps: key = 1, value =
    2); XEventMetadata.name = 2, .stats = 5; XStatMetadata.id = 1, .name =
    2; XStat.metadata_id = 1, .str_value = 5, .ref_value = 7 (a
    stat_metadata id whose name is the text). The lines, nearly all of
    the file, are skipped by their length."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for field, value in _fields(plane):
            if field == 2:
                name = bytes(value).decode()
            elif field == 4:
                metas.append(dict(_fields(value))[2])
            elif field == 5:
                meta = dict(_fields(dict(_fields(value))[2]))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not _DEVICE_PLANE.match(name):
            continue
        for meta in metas:
            op, stats = "", {}
            for field, value in _fields(meta):
                if field == 2:
                    op = bytes(value).decode()
                elif field == 5:
                    stat = dict(_fields(value))
                    text = (bytes(stat[5]).decode() if 5 in stat
                            else stat_names.get(stat[7]) if 7 in stat
                            else None)
                    if text is not None:
                        stats[stat_names.get(stat.get(1, 0), "")] = text
            out[op] = stats
    return out


def load(profile, ops_meta=None) -> dict:
    """``annotations``: (start_ns, duration_ns, name, t0_us) of the host
    planes' ``glint.*`` events; ``ops``: (start_ns, duration_ns, scope) and
    ``modules``: (start_ns, duration_ns, name) of the first device.
    ``ops_meta`` is :func:`op_stats` of the same file."""
    ops_meta = ops_meta or {}
    annotations, devices = [], []
    for plane in profile.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                if line.name == "XLA Ops":
                    dev["ops"] += [
                        (float(e.start_ns), float(e.duration_ns),
                         scope_of(ops_meta.get(e.name, {}).get("tf_op")))
                        for e in line.events]
                elif line.name == "XLA Modules":
                    dev["modules"] += [
                        (float(e.start_ns), float(e.duration_ns), e.name)
                        for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        t0 = dict(e.stats).get("t0_us")
                        annotations.append((
                            float(e.start_ns), float(e.duration_ns), e.name,
                            None if t0 is None else float(t0)))
    first = min(devices, key=lambda d: d["name"], default={})
    return {"annotations": sorted(annotations),
            "ops": first.get("ops", []), "modules": first.get("modules", [])}


def clock_offset(annotations):
    """(offset_us, spread_us, readings): the median of (trace clock - ring
    clock) over the bridged annotations and the distance between its
    quartiles; (None, None, 0) where nothing is bridged."""
    readings = [s / 1e3 - t0 for s, _, _, t0 in annotations if t0 is not None]
    if not readings:
        return None, None, 0
    spread = 0.0
    if len(readings) > 1:
        q = statistics.quantiles(readings, n=4)
        spread = q[2] - q[0]
    return statistics.median(readings), spread, len(readings)


def scope_seconds(ops, modules) -> tuple:
    """({scope: self seconds}, runs) over the ops that ran inside the
    packed-scan program's runs."""
    scans = [m for m in modules if _PACKED_SCAN.search(m[2])]
    runs, inside, i = merged(scans), [], 0
    for op in sorted(ops):
        while i < len(runs) and runs[i][1] <= op[0]:
            i += 1
        if i < len(runs) and runs[i][0] <= op[0]:
            inside.append(op)
    return {k: ns / 1e9 for k, ns in self_times(inside).items()}, len(scans)


def idle_gaps(ops, annotations) -> list:
    """(seconds, [names of the annotations open over it, outermost
    first]) for every piece of every idle gap between two ops of the
    device. A gap is cut wherever an annotation starts or ends: the serving
    device's gaps are as long as the host's whole cycle, and one label for
    a gap (``trace_reduce`` files each under what covers its middle) would
    put a round's idle under the pause that follows it. What lies before
    the first op seen and after the last is the profiler starting and
    stopping, not known idle."""
    busy = merged(ops)
    cuts = sorted({t for s, d, _, _ in annotations for t in (s, s + d)})
    out, i, active = [], 0, []
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        edges = [e0, *cuts[bisect.bisect_right(cuts, e0):
                           bisect.bisect_left(cuts, s1)], s1]
        for a, b in zip(edges, edges[1:]):
            mid = (a + b) / 2
            while i < len(annotations) and annotations[i][0] <= mid:
                active.append(annotations[i])
                i += 1
            active = [x for x in active if x[0] + x[1] >= mid]
            out.append(((b - a) / 1e9, [x[2] for x in active]))
    return out


def read(run):
    """The run's xplane reduced once; None where there is no trace."""
    if getattr(run, "_program_trace", None) is None:
        paths = find_xplane_files(run.trace_dir) if run.trace else []
        if not paths:
            return None
        from jax.profiler import ProfileData

        data = load(ProfileData.from_file(paths[-1]), op_stats(paths[-1]))
        (data["offset_us"], data["offset_spread_us"],
         data["offset_readings"]) = clock_offset(data["annotations"])
        data["gaps"] = idle_gaps(data["ops"], data["annotations"])
        data["scope_s"], data["scan_runs"] = scope_seconds(
            data["ops"], data["modules"])
        run._program_trace = data
        report(run, data)
    return run._program_trace


def report(run, data) -> None:
    if data["offset_us"] is None:
        run.say("program trace: no bridged glint.* annotation in the trace")
    else:
        run.say(f"program trace: clock offset (trace - ring) "
                f"{data['offset_us']:.1f} us, quartile spread "
                f"{data['offset_spread_us']:.1f} us over "
                f"{data['offset_readings']} bridged annotations")
    if data["ops"]:
        run.say(f"program trace: device ops from "
                f"{min(o[0] for o in data['ops']) / 1e9:.4f}s to "
                f"{max(o[0] + o[1] for o in data['ops']) / 1e9:.4f}s of a "
                f"{run.trace['window_s']:.4f}s window")
    by_label = collections.Counter()
    for seconds, names in data["gaps"]:
        by_label[names[-1] if names else NO_ANNOTATION] += seconds
    for label, seconds in by_label.most_common():
        run.say(f"program trace: idle {seconds:.4f}s under {label}")
    for scope, seconds in sorted(data["scope_s"].items(),
                                 key=lambda kv: -kv[1]):
        run.say(f"program trace: packed scan {seconds:.4f}s in "
                f"{scope or 'no glint.* scope'}")


# -- what the per-layer readers take ------------------------------------


def scope_ms(run, *scopes):
    """Device ms a packed step spends in ops of these scopes (self time
    over the steps traced, as ``layer_util.step_seconds`` counts them)."""
    data = read(run)
    if not data or not data["scan_runs"]:
        return None
    hit = [s for k, s in data["scope_s"].items()
           if k.split("/")[0] in scopes]
    steps = data["scan_runs"] * run.cfg["run"]["steps_per_call"]
    return 1e3 * sum(hit) / steps if hit else None


def unscoped_share(run):
    """Percent of the packed-scan runs' busy time under no ``glint.``
    scope; None where no op carries one (a CPU trace, an older program)."""
    data = read(run)
    if not data or set(data["scope_s"]) <= {UNSCOPED}:
        return None
    return 100.0 * data["scope_s"].get(UNSCOPED, 0.0) / sum(
        data["scope_s"].values())


def ring_spans(run, name) -> list:
    """(start_s, duration_s), on the trace's clock, of the ring's spans
    called ``name`` that began inside the traced window."""
    data = read(run)
    if not data or data["offset_us"] is None:
        return []
    moved = [(s + data["offset_us"] / 1e6, d)
             for s, d in program_spans(run, name)]
    return [(s, d) for s, d in moved if 0 <= s <= run.trace["window_s"]]


def median_span_ms(run, name):
    """Median duration, in ms, of those spans; None where there is none."""
    spans = [d for _, d in ring_spans(run, name)]
    return statistics.median(spans) * 1e3 if spans else None


def idle_share(run, under=(), outside=()):
    """Percent of the traced window the device idled while an annotation
    of ``under`` was open (any, if empty) and none of ``outside``."""
    data = read(run)
    if not data or not data["annotations"] or not data["gaps"]:
        return None
    seconds = sum(
        s for s, names in data["gaps"]
        if (not under or any(n in under for n in names))
        and not any(n in outside for n in names))
    return 100.0 * seconds / run.trace["window_s"]


def dump(trace_dir: str, out=print, per_line: int = 6) -> None:
    """Events with their stats, for reading a trace by hand: the longest
    few names of every line, and every ``glint.*`` / ``bench.*`` name."""
    from jax.profiler import ProfileData

    for path in find_xplane_files(trace_dir):
        meta = op_stats(path)
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                total, sample = collections.Counter(), {}
                for e in line.events:
                    total[e.name] += e.duration_ns
                    sample.setdefault(e.name, e)
                if not total:
                    continue
                out(f"PLANE {plane.name!r} LINE {line.name!r}: "
                    f"{len(total)} names")
                ours = [n for n in total if n.startswith((PREFIX, "bench."))]
                top = [n for n, _ in total.most_common(per_line)]
                for n in dict.fromkeys(top + ours):
                    e = sample[n]
                    out(f"  {total[n] / 1e6:10.3f} ms {n[:100]!r} first at "
                        f"{e.start_ns / 1e9:.6f}s")
                    for k, v in list(e.stats) + sorted(
                            meta.get(n, {}).items()):
                        out(f"      {k} = {str(v)[:240]!r}")


if __name__ == "__main__":
    import sys

    dump(sys.argv[1])
