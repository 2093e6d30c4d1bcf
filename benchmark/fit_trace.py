"""The training window's seconds that are not the step's, read from what
the fit driver records (PR 36): the dispatch group's host side as spans on
the profiler's clock, the gap between two runs of the packed scan on the
device, the epoch's turn and the fit's tail.

Built on ``program_trace.read(run)`` (the first device's ``XLA Modules``
and ops, the ``glint.*`` annotations, the idle gaps cut at their edges,
the offset between the ring's clock and the trace's) and on the ring the
fit wrote (``ObsConfig(chrome_trace=...)``). The ring holds every
dispatch group of the fit, the device trace the few traced ones. A reader
returns None where what it reads is absent: a CPU trace has no module
line, a program before PR 36 no ``harvest_wait`` and no ``epoch`` on its
``device_steps``.

Spans read (``models/word2vec.py`` ``_fit_corpus_resident``):
``device_steps`` (arg ``epoch``), ``readback_harvest`` and its children
``harvest_wait`` / ``harvest_convert`` / ``harvest_account``,
``subsample_compact``; for the report alone ``harvest_convert``,
``harvest_account``, ``subsample_prefetch`` and the instant ``run_end``.
"""

import bisect
import collections
import json
import os
import re
import statistics

from benchmark import program_trace
from benchmark.trace_reduce import merged

_PACKED_SCAN = re.compile(r"packed_scan")
DISPATCH, HARVEST, WAIT = (
    "glint.device_steps", "glint.readback_harvest", "glint.harvest_wait")


# -- the ring ------------------------------------------------------------


def ring(run) -> dict:
    """The chrome-trace document the fit wrote: ``traceEvents`` and, from
    the file, ``otherData`` (``mono_t0``: perf_counter at ``ts`` 0)."""
    doc = getattr(run, "_fit_ring", None)
    if doc is None:
        path = run.program_spans_path
        if path and os.path.exists(path):
            with open(path) as f:
                doc = json.load(f)
            if run.program_spans is None:  # layer_util's readers share it
                run.program_spans = doc["traceEvents"]
        else:
            doc = {"traceEvents": run.program_spans or []}
        run._fit_ring = doc
    return doc


def spans(run, name: str) -> list:
    """(start_s, end_s, args) of the ring's spans called ``name``, on the
    recorder's clock, oldest first."""
    return sorted(
        ((e["ts"] / 1e6, (e["ts"] + e.get("dur", 0.0)) / 1e6,
          e.get("args", {}))
         for e in ring(run)["traceEvents"]
         if e["name"] == name and e.get("ph") == "X"),
        key=lambda s: s[:2])


def instant(run, name: str):
    """Seconds, on the recorder's clock, of the last instant ``name``."""
    at = [e["ts"] / 1e6 for e in ring(run)["traceEvents"]
          if e["name"] == name and e.get("ph") == "i"]
    return max(at) if at else None


def _median_ms(seconds):
    return statistics.median(seconds) * 1e3 if seconds else None


def dispatch_ms(run):
    """Median ``device_steps`` span of the whole fit: what the host pays
    to enqueue one dispatch group."""
    return _median_ms([e - s for s, e, _ in spans(run, "device_steps")])


def harvest_host(run) -> list:
    """Seconds of each ``readback_harvest`` less the ``harvest_wait``
    inside it: the host's own work a group. [] without the child."""
    waits = spans(run, "harvest_wait")
    starts = [s for s, _, _ in waits]
    out = []
    for s, e, _ in spans(run, "readback_harvest"):
        i = bisect.bisect_left(starts, s)
        if i < len(waits) and waits[i][1] <= e:
            out.append((e - s) - (waits[i][1] - waits[i][0]))
    return out


def harvest_host_ms(run):
    return _median_ms(harvest_host(run))


def _by_epoch(run) -> dict:
    """{epoch: [start_s of its ``device_steps``]}; {} where the spans do
    not say their epoch."""
    out = collections.defaultdict(list)
    for s, _, args in spans(run, "device_steps"):
        if "epoch" not in args:
            return {}
        out[args["epoch"]].append(s)
    return dict(out)


def epoch_turns(run) -> list:
    """(epoch, seconds) from the end of the previous epoch's last
    ``readback_harvest`` (the first epoch: from the fit's first
    ``subsample_compact`` or ``device_steps``) to the start of the
    epoch's first ``device_steps``: the compaction pass or its adoption,
    the ``n_kept`` sync, the offsets, the first dispatch's set-up."""
    epochs = _by_epoch(run)
    harvest_ends = sorted(e for _, e, _ in spans(run, "readback_harvest"))
    compacts = [s for s, _, _ in spans(run, "subsample_compact")]
    out = []
    for n, epoch in enumerate(sorted(epochs)):
        first = min(epochs[epoch])
        if n == 0:
            since = min(compacts[:1] + [first])
        else:
            i = bisect.bisect_right(harvest_ends, first)
            if not i:
                continue
            since = harvest_ends[i - 1]
        out.append((epoch, first - since))
    return out


def epoch_turn_ms(run):
    return _median_ms([t for _, t in epoch_turns(run)])


def _mono_t0(run):
    """perf_counter at the ring's ``ts`` 0, or None."""
    return ring(run).get("otherData", {}).get("mono_t0")


def tail(run):
    """(seconds from the end of the last ``readback_harvest`` to the
    window's close, how many of them lie after ``run_end``): what the
    window pays after its last group. The second part holds the ring's
    own export, which a fit without ``obs`` does not pay."""
    mono, window = _mono_t0(run), getattr(run, "window", None)
    harvests = spans(run, "readback_harvest")
    if mono is None or not window or not harvests:
        return None
    close, end = window[1] - mono, instant(run, "run_end")
    return (close - max(e for _, e, _ in harvests),
            None if end is None else close - end)


def tail_ms(run):
    parts = tail(run)
    return parts and parts[0] * 1e3


def _profiler_edges(run) -> list:
    """The instants, on the recorder's clock, at which the benchmark
    started and stopped its profiler (``run.trace_t``, perf_counter):
    both happen inside a dispatch and stretch that group, which is the
    benchmark's doing."""
    mono = _mono_t0(run)
    at = getattr(run, "trace_t", None)
    if mono is None or not at:
        return []
    return [t - mono for t in at if t is not None]


def group_distances(run) -> list:
    """(epoch, index, seconds, traced) between successive ``device_steps``
    starts of one epoch, over the whole fit. Left out: an epoch's first
    two (the pipeline filling: the first dispatch has no harvest behind
    it, and the second's harvest spans two groups) and the two groups in
    which the benchmark's profiler started and stopped. ``traced``: the
    distance lies between those two."""
    edges = _profiler_edges(run)
    out = []
    for epoch, starts in sorted(_by_epoch(run).items()):
        starts = sorted(starts)
        for i, (a, b) in enumerate(zip(starts, starts[1:])):
            if i >= 2 and not any(a <= t <= b for t in edges):
                out.append((epoch, i, b - a,
                            len(edges) == 2 and edges[0] < a < edges[1]))
    return out


def group_spread(run):
    """(longest - median) / median of those distances, in percent."""
    d = [s for _, _, s, _ in group_distances(run)]
    if len(d) < 2:
        return None
    mid = statistics.median(d)
    return 100.0 * (max(d) - mid) / mid


# -- the device trace ----------------------------------------------------


def scans(run) -> list:
    """(start_s, end_s) of the first device's runs of the packed scan, on
    the trace's clock, in order; [] without a module line."""
    data = program_trace.read(run) if run.trace else None
    if not data:
        return []
    return sorted((s / 1e9, (s + d) / 1e9) for s, d, name in data["modules"]
                  if _PACKED_SCAN.search(name))


def scan_gaps(run) -> list:
    """One dict for each pair of successive scan runs: ``seconds`` from
    the end of one to the start of the next, the ``programs`` [(name,
    seconds)] whose runs start in between, and the ``busy_s`` of the
    device's ops that start in between (``idle_s`` the rest)."""
    if getattr(run, "_fit_scan_gaps", None) is not None:
        return run._fit_scan_gaps
    runs = scans(run)
    gaps = [{"start": e0, "end": s1, "seconds": s1 - e0, "programs": [],
             "ops": []} for (_, e0), (s1, _) in zip(runs, runs[1:])]
    if gaps:
        data = program_trace.read(run)
        starts = [g["start"] for g in gaps]

        def gap_of(ns):
            i = bisect.bisect_right(starts, ns / 1e9) - 1
            return gaps[i] if i >= 0 and ns / 1e9 < gaps[i]["end"] else None

        for s, d, name in data["modules"]:
            g = gap_of(s)
            if g is not None and not _PACKED_SCAN.search(name):
                g["programs"].append((name.split("(")[0], d / 1e9))
        for s, d, _ in data["ops"]:
            g = gap_of(s)
            if g is not None:
                g["ops"].append((s, min(d, g["end"] * 1e9 - s), ""))
        for g in gaps:
            g["busy_s"] = sum(e - s for s, e in merged(g.pop("ops"))) / 1e9
            g["idle_s"] = g["seconds"] - g["busy_s"]
    run._fit_scan_gaps = gaps
    return gaps


def scan_gap_ms(run):
    return _median_ms([g["seconds"] for g in scan_gaps(run)])


def gap_programs(run):
    gaps = scan_gaps(run)
    return statistics.median(len(g["programs"]) for g in gaps) \
        if gaps else None


def launch_leads(run) -> tuple:
    """([seconds], spans seen): for each ``device_steps`` span that began
    inside the traced window, matched in order to the first scan run
    that starts after the span does (the run it dispatched), the end of
    the scan run BEFORE that one less the end of the span. Positive: the
    dispatch had returned before the device came free. Spans and runs
    without a partner, and a run with no run before it, are dropped."""
    runs = scans(run)
    dispatched = [(s, s + d) for s, d in
                  program_trace.ring_spans(run, "device_steps")] \
        if runs else []
    leads, j = [], 0
    for start, end in dispatched:
        while j < len(runs) and runs[j][0] <= start:
            j += 1
        if j == len(runs):
            break
        if j:
            leads.append(runs[j - 1][1] - end)
        j += 1
    return leads, len(dispatched)


def launch_lead_ms(run):
    return _median_ms(launch_leads(run)[0])


def idle_parts(run):
    """Percent of the traced window the first device idled: under
    ``glint.device_steps``; under ``glint.harvest_wait``; under the rest
    of ``glint.readback_harvest``; between ops under neither span; and
    before its first op and after its last (the profiler starting and
    stopping: ``device.idle_share.train`` counts those too). None
    without idle gaps or annotations."""
    share = program_trace.idle_share
    parts = {
        "in_dispatch": share(run, under=(DISPATCH,)),
        "in_wait": share(run, under=(WAIT,)),
        "in_harvest_host": share(run, under=(HARVEST,), outside=(WAIT,)),
        "under_neither": share(run, outside=(DISPATCH, HARVEST)),
    }
    if None in parts.values():
        return None
    ops = program_trace.read(run)["ops"]
    seen = max(s + d for s, d, _ in ops) - min(s for s, _, _ in ops)
    parts["at_the_edges"] = 100.0 * (
        1.0 - seen / 1e9 / run.trace["window_s"])
    return parts


def idle_in(run, part: str):
    """One part of :func:`idle_parts`. ``in_wait`` and
    ``in_harvest_host`` are the program's to tell apart: None where it
    has no ``harvest_wait``."""
    parts = idle_parts(run) if run.trace else None
    if not parts:
        return None
    waited = any(n == WAIT for _, _, n, _ in
                 program_trace.read(run)["annotations"])
    if part != "in_dispatch" and not waited:
        return None
    return parts[part]


READERS = {
    "fit.scan_gap_ms": scan_gap_ms,
    "fit.gap_programs": gap_programs,
    "fit.launch_lead_ms": launch_lead_ms,
    "fit.idle_in_dispatch": lambda run: idle_in(run, "in_dispatch"),
    "fit.idle_in_wait": lambda run: idle_in(run, "in_wait"),
    "fit.idle_in_harvest_host": lambda run: idle_in(run, "in_harvest_host"),
    "fit.dispatch_ms": dispatch_ms,
    "fit.harvest_host_ms": harvest_host_ms,
    "fit.epoch_turn_ms": epoch_turn_ms,
    "fit.tail_ms": tail_ms,
    "fit.group_spread": group_spread,
}


def read(run, name: str):
    """What ``benchmark/layers/<name>.py`` returns; the first call of a
    run also prints the report lines."""
    if not getattr(run, "_fit_trace_said", False):
        run._fit_trace_said = True
        try:
            report(run)
        except Exception as e:  # the lines explain the numbers, no more
            run.say(f"fit trace: the report stopped at {e!r}")
    return READERS[name](run)


# -- the report ----------------------------------------------------------

#: a group this much longer than the median one counts as stretched
STRETCHED = 1.1


def report(run) -> None:
    """What the eleven numbers are made of; :func:`read` says it once."""
    say = run.say
    gaps = scan_gaps(run)
    if gaps:
        say(f"fit trace: {len(gaps) + 1} scan runs, {len(gaps)} gaps of "
            + " ".join(f"{g['seconds'] * 1e3:.3f}" for g in gaps)
            + " ms; device busy in a gap "
            f"{_median_ms([g['busy_s'] for g in gaps]):.3f} ms, idle "
            f"{_median_ms([g['idle_s'] for g in gaps]):.3f} ms (medians)")
        by_name = collections.defaultdict(list)  # name: [(runs, s) a gap]
        for g in gaps:
            for name in {n for n, _ in g["programs"]}:
                took = [s for n, s in g["programs"] if n == name]
                by_name[name].append((len(took), sum(took)))
        for name, seen in sorted(by_name.items(),
                                 key=lambda kv: -sum(s for _, s in kv[1])):
            say(f"fit trace: in the gap, {name}: "
                f"{statistics.median(c for c, _ in seen):g} runs, "
                f"{_median_ms([s for _, s in seen]):.4f} ms a gap "
                f"(in {len(seen)} of {len(gaps)} gaps)")
        # one gap laid out: the one of median length, its programs and the
        # host's spans over it, in ms from the end of the scan before it
        g = sorted(gaps, key=lambda g: g["seconds"])[len(gaps) // 2]
        data = program_trace.read(run)
        at = lambda ns: (ns / 1e9 - g["start"]) * 1e3  # noqa: E731
        say(f"fit trace: a gap of {g['seconds'] * 1e3:.3f} ms, its programs "
            "(+start ms name us): " + " ".join(
                f"+{at(s):.3f} {n.split('(')[0]} {d / 1e3:.1f}"
                for s, d, n in sorted(data["modules"])
                if g["start"] <= s / 1e9 < g["end"]
                and not _PACKED_SCAN.search(n)))
        say("fit trace: the host over that gap (span from..to ms): "
            + " ".join(
                f"{n[len(program_trace.PREFIX):]} {at(s):.3f}..{at(s + d):.3f}"
                for s, d, n, _ in data["annotations"]
                if s / 1e9 < g["end"] and (s + d) / 1e9 > g["start"]))
    leads, seen = launch_leads(run)
    if seen:
        say(f"fit trace: {len(leads)} of {seen} device_steps spans in the "
            f"window matched to a scan run with a run before it; the "
            f"dispatch returned before the device came free by "
            + " ".join(f"{x * 1e3:.3f}" for x in leads) + " ms")
    parts = idle_parts(run) if run.trace else None
    if parts:
        say("fit trace: idle " + ", ".join(
            f"{k} {v:.4f}%" for k, v in parts.items())
            + f"; together {sum(parts.values()):.4f}% of the window")
    passes = {name: {a.get("epoch"): e - s for s, e, a in spans(run, name)}
              for name in ("subsample_compact", "subsample_prefetch")}
    starts, harvests = _by_epoch(run), spans(run, "readback_harvest")
    turns = epoch_turns(run)
    for n, (epoch, seconds) in enumerate(turns):
        # the epoch's body: its first dispatch to the end of its last
        # harvest (the last one that starts before the next epoch does)
        first = min(starts[epoch])
        until = min(starts[turns[n + 1][0]]) if n + 1 < len(turns) \
            else float("inf")
        mine = [h for h in harvests if first <= h[0] < until]
        say(f"fit trace: epoch {epoch} turned in {seconds * 1e3:.3f} ms"
            + "".join(f", its {name} span {d[epoch] * 1e3:.3f} ms"
                      for name, d in passes.items() if epoch in d)
            + (f"; {len(starts[epoch])} groups from its first dispatch to "
               f"the end of its last harvest in "
               f"{(mine[-1][1] - first) * 1e3:.3f} ms, its last two "
               "harvests (live steps: ms) "
               + " ".join(f"{h[2].get('n')}: {(h[1] - h[0]) * 1e3:.3f}"
                          for h in mine[-2:]) if mine else ""))
    d = sorted(group_distances(run), key=lambda x: x[2])
    if len(d) >= 2:
        (epoch, i, longest, _), mid = d[-1], statistics.median(
            s for _, _, s, _ in d)
        traced = [s for _, _, s, t in d if t]
        say(f"fit trace: {len(d)} group distances, median {mid * 1e3:.3f} "
            f"ms, longest {longest * 1e3:.3f} ms (epoch {epoch}, group {i}),"
            f" second longest {d[-2][2] * 1e3:.3f} ms, "
            f"{sum(s > STRETCHED * mid for _, _, s, _ in d)} beyond "
            f"{STRETCHED:g} x the median"
            + (f"; the {len(traced)} under the profiler "
               f"{_median_ms(traced):.3f} ms" if traced else ""))
    live = [a["n"] for _, _, a in harvests if "n" in a]
    if live:
        spc = run.cfg["run"]["steps_per_call"]
        say(f"fit trace: {len(live)} groups harvested, {sum(live)} live "
            f"steps of the {spc * len(live)} dispatched; "
            f"{sum(n == 0 for n in live)} group(s) without a live step")
    host = harvest_host(run)
    if host:
        say(f"fit trace: harvest, medians of {len(host)} groups in ms: "
            f"the host's own {_median_ms(host):.3f}" + "".join(
                f", {name} {_median_ms([e - s for s, e, _ in kids]):.3f}"
                for name, kids in ((name, spans(run, "harvest_" + name))
                                   for name in ("convert", "account"))
                if kids))
    ends = tail(run)
    if ends:
        say(f"fit trace: the window closes {ends[0] * 1e3:.3f} ms after the "
            "last harvest" + ("" if ends[1] is None else
                              f", {ends[1] * 1e3:.3f} ms after run_end"))
