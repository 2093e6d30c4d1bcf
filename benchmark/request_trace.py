"""A served request measured from inside: what the ``serve.hit_*`` and the
round's readers share.

The program writes two kinds of thing into the recorder's ring (``obs/
events.py``). A kept request's PHASES carry its trace id (``args.trace``):
``req.head`` (``http.server``'s parse of the request line and headers),
``req.accept`` (the handler; args ``cache``, ``cpu_ms``), ``req.parse``,
``req.lookup``, ``req.serialize`` and, on a miss, ``req.queue``,
``req.query``, ``req.readback``, ``req.wake``. A ROUND is a ring-direct
``req.dispatch`` span of the leader's thread with its children on the same
thread: ``req.compose``, ``req.pull``, then for every query program it
launches a ``req.enqueue`` (the jitted call, returned before the device is
done) and a ``req.result`` (that call's outputs read back), then one
``req.decode``. Ring-direct spans are ``glint.<name>`` annotations too, so
``program_trace`` puts the whole ring on the device trace's clock.

Two windows. The recorder runs the whole traced run, the profiler a few
seconds of it with its Python tracer on, which stretches every host phase.
The requests are read OUTSIDE the profiler's window (inside the load
window); the rounds INSIDE it, as ``serve.round_ms`` reads them, beside the
device's programs. The load window is ``run.window``, moved onto the
trace's clock by ``run.trace_t[0]``: the profiler's session begins a little
before that stamp (``start_trace``'s own time), so the window lies that
much early. Cache hits need no window: the cache's warm-up asks every word
once and can hit nothing.

A ring without the spans (the parent of PR 52), a trace without program
lines (the CPU backend's) or a run without a trace gives None wherever the
missing thing is needed, and raises nothing.
"""

import statistics

from benchmark import program_trace
from benchmark.layer_util import counter_delta

HIT_PHASES = ("req.head", "req.parse", "req.lookup", "req.serialize")
ROUND_CHILDREN = ("req.enqueue", "req.result", "req.decode", "req.pull",
                  "req.compose")
IDLE_UNDER = ("glint.req.enqueue", "glint.req.result", "glint.req.decode",
              "glint.req.grace")


def _median(values):
    values = [v for v in values if v is not None]
    return statistics.median(values) if values else None


def _mean(values):
    values = [v for v in values if v is not None]
    return statistics.fmean(values) if values else None


def _ms(value) -> str:
    return "none" if value is None else f"{value:.4f}"


def requests(events, offset_us) -> list:
    """One record for every kept request that has a head and a root span:
    ``start`` (s, trace clock), ``ms`` (head + accept), ``cache``,
    ``cpu_ms`` and ``phases`` {name: ms}."""
    by_trace = {}
    for e in events:
        trace = (e.get("args") or {}).get("trace")
        if e.get("ph") == "X" and isinstance(trace, str):
            by_trace.setdefault(trace, {})[e["name"]] = e
    out = []
    for phases in by_trace.values():
        head, accept = phases.get("req.head"), phases.get("req.accept")
        if head is None or accept is None:
            continue
        out.append({
            "start": (head["ts"] + offset_us) / 1e6,
            "ms": (head["dur"] + accept["dur"]) / 1e3,
            "cache": accept["args"].get("cache"),
            "cpu_ms": accept["args"].get("cpu_ms"),
            "phases": {n: e["dur"] / 1e3 for n, e in phases.items()},
        })
    return sorted(out, key=lambda r: r["start"])


def rounds(events, offset_us) -> list:
    """One record for every ``req.dispatch`` of the ring: ``start``,
    ``end`` (s, trace clock), ``ms`` and ``children``, the spans of
    :data:`ROUND_CHILDREN` its thread recorded inside it, oldest first, as
    (name, start_s, end_s, args)."""
    spans = sorted((e for e in events if e.get("ph") == "X"
                    and e["name"] in ROUND_CHILDREN + ("req.dispatch",)),
                   key=lambda e: (e["tid"], e["ts"], -e["dur"]))
    out = []
    for e in spans:
        start = (e["ts"] + offset_us) / 1e6
        end = start + e["dur"] / 1e6
        if e["name"] == "req.dispatch":
            out.append({"start": start, "end": end, "tid": e["tid"],
                        "ms": e["dur"] / 1e3, "children": []})
        elif out and out[-1]["tid"] == e["tid"] and start <= out[-1]["end"]:
            out[-1]["children"].append(
                (e["name"], start, end, e.get("args") or {}))
    return sorted(out, key=lambda r: r["start"])


def child_ms(rnd, name):
    """Ms a round spent in its children called ``name``; None with none."""
    hit = [e - s for n, s, e, _ in rnd["children"] if n == name]
    return 1e3 * sum(hit) if hit else None


def round_parts(rnd) -> dict:
    """A round's ms by part, summing to the round: its children by name,
    ``req.compose`` less the launch and the read-back it holds (they are
    counted under their own names), and what lies under no child."""
    ms = {n: child_ms(rnd, n) or 0.0 for n in ROUND_CHILDREN}
    composes = [(s, e) for n, s, e, _ in rnd["children"]
                if n == "req.compose"]
    ms["req.compose"] -= 1e3 * sum(
        e - s for n, s, e, _ in rnd["children"]
        if n in ("req.enqueue", "req.result")
        and any(cs <= s <= ce for cs, ce in composes))
    ms["no child span"] = rnd["ms"] - sum(ms.values())
    return ms


def read_backs(rnd, modules) -> list:
    """(program, lag_ms) of each of a round's read-backs, in order: the end
    of the ``req.result`` less the end of the last run, among ``modules``
    (sorted (start_s, end_s) of the first device), of a program that began
    between the start of the ``req.enqueue`` before it and its own end. The
    device had finished; for that long the leader did not hold the result.
    A read-back none of whose programs the trace caught is left out."""
    out, enqueue = [], None
    for name, start, end, args in rnd["children"]:
        if name == "req.enqueue":
            enqueue = start
        elif name == "req.result" and enqueue is not None:
            done = [e for s, e in modules if enqueue <= s <= end]
            if done:
                out.append((args.get("program"), 1e3 * (end - max(done))))
            enqueue = None
    return out


def read(run):
    """The run's requests and rounds, reduced once and kept on ``run``;
    None where there is no trace or nothing bridges the ring to it."""
    if getattr(run, "_request_trace", None) is None:
        data = program_trace.read(run)
        events = run.program_spans or []
        if not data or data["offset_us"] is None or not events:
            return None
        offset, window_s = data["offset_us"], run.trace["window_s"]
        t0 = run.trace_t[0]
        load = (run.window[0] - t0, run.window[1] - t0)
        reqs = [r for r in requests(events, offset)
                if load[0] <= r["start"] <= load[1]]
        every_round = rounds(events, offset)
        modules = sorted((s / 1e9, (s + d) / 1e9)
                         for s, d, _ in data["modules"])
        out = {
            "inside": [r for r in reqs if 0 <= r["start"] <= window_s],
            "outside": [r for r in reqs
                        if not 0 <= r["start"] <= window_s],
            "rounds": [r for r in every_round
                       if 0 <= r["start"] <= window_s],
            # the host as it runs untraced; no device line to hold them to
            "rounds_outside": [
                r for r in every_round if load[0] <= r["start"] <= load[1]
                and not 0 <= r["start"] <= window_s],
            "modules": modules,
            "load": load,
            "compiles": [
                e for e in events if e["name"] == "query_compile"
                and load[0] <= (e["ts"] + offset) / 1e6 <= load[1]],
        }
        run._request_trace = out
        report(run, data, out)
    return run._request_trace


def _where(reqs, cache):
    return [r for r in reqs if r["cache"] == cache]


def _outside(run, cache) -> list:
    """The sampled requests of the load window that began outside the
    profiler's and whose root span says ``cache``."""
    data = read(run)
    return _where(data["outside"], cache) if data else []


def hit_ms(run, phase=None):
    """Median, over the sampled cache hits outside the profiler's window,
    of ``phase`` in ms (the whole request, head + accept, if None)."""
    return _median(r["ms"] if phase is None else r["phases"].get(phase)
                   for r in _outside(run, "hit"))


def hit_cpu_ms(run):
    """MEAN ``cpu_ms`` of those hits' ``req.accept``. Not the median: the
    chip's host charges a thread's CPU time by the scheduler's tick
    (``time.thread_time()`` read 0 for most hits and a whole tick for the
    rest: my chip run, PR 52), so one reading says nothing and the mean
    over thousands is the estimate a tick-sampled clock gives."""
    return _mean(r["cpu_ms"] for r in _outside(run, "hit"))


def wake_ms(run):
    """Median ``req.wake`` of the sampled misses outside the profiler's
    window: a finished answer waiting for its handler thread to run."""
    return _median(r["phases"].get("req.wake")
                   for r in _outside(run, "miss"))


def round_child_ms(run, name):
    """Median, over the traced rounds that have one, of the ms a round
    spent in its children called ``name``."""
    data = read(run)
    return _median(child_ms(r, name) for r in data["rounds"]
                   ) if data else None


def readback_lag_ms(run):
    """Median, over the traced rounds, of the FIRST read-back's lag
    (:func:`read_backs`); None where the trace has no program line."""
    data = read(run)
    if not data or not data["modules"]:
        return None
    lags = [read_backs(r, data["modules"]) for r in data["rounds"]]
    return _median(lag[0][1] for lag in lags if lag)


def idle_in_result(run):
    """Percent of the traced window the device idled under a
    ``glint.req.result`` annotation: the device done (or not yet begun),
    the host not yet holding the answer. None where the program has no
    such annotation."""
    data = program_trace.read(run)
    if not data or not any(a[2] == "glint.req.result"
                           for a in data["annotations"]):
        return None
    return program_trace.idle_share(run, under=("glint.req.result",))


# -- report lines: what no metric carries -------------------------------


def report(run, trace, data) -> None:
    say = run.say
    everyone = data["inside"] + data["outside"]
    hits_in, hits_out = (_where(data[k], "hit") for k in ("inside", "outside"))
    say(f"request trace: {len(data['outside'])} sampled requests outside "
        f"the profiler's window ({len(hits_out)} hits), "
        f"{len(data['inside'])} inside it ({len(hits_in)} hits); "
        f"{len(data['rounds'])} rounds inside it; the ring holds "
        f"{len(run.program_spans)} events")
    seen = getattr(run, "end_to_end", None) or {}
    if seen:  # a traced run's line carries no end-to-end metric
        say("request trace: this traced run's callers saw "
            + ", ".join(f"{k} {v:.4f}" for k, v in sorted(seen.items())))
    # What both sides of a traced pair record: the root span of every
    # sampled request, a hit or not (the median one is a hit).
    accepts = [((e["ts"] + trace["offset_us"]) / 1e6, e["dur"] / 1e3)
               for e in run.program_spans if e["name"] == "req.accept"]
    spans_out, spans_in = (
        [d for s, d in accepts if data["load"][0] <= s <= data["load"][1]
         and (0 <= s <= run.trace["window_s"]) == inside]
        for inside in (False, True))
    say(f"request trace: every sampled request's req.accept: median "
        f"{_ms(_median(spans_out))} ms over {len(spans_out)} outside the "
        f"profiler's window, {_ms(_median(spans_in))} ms over "
        f"{len(spans_in)} inside it")
    if everyone:
        say(f"request trace: load window {data['load'][0]:.3f}s to "
            f"{data['load'][1]:.3f}s on the trace's clock (the profiler's: "
            f"0 to {run.trace['window_s']:.3f}s); its first sampled "
            f"request began at {min(r['start'] for r in everyone):.3f}s, "
            f"its last at {max(r['start'] for r in everyone):.3f}s")
        _report_phases(say, data)
    else:
        say("request trace: no request of the ring has a req.head (a "
            "program from before PR 52): no phase of a hit or a miss read")
    _report_rounds(say, trace, data)
    # The callers' p50 is a hit, and not the median hit: with a hit share
    # h the median request is the (50 / h)-th percentile of the hits.
    if getattr(run, "serving_metrics", None) and hits_out:
        hits = counter_delta(run, "synonym_cache", "hits")
        lookups = hits + counter_delta(run, "synonym_cache", "misses")
        if 2 * hits > lookups:
            pct = 50.0 * lookups / hits
            ranked = sorted(r["ms"] for r in hits_out)
            at = ranked[min(len(ranked) - 1, int(len(ranked) * pct / 100))]
            say(f"request trace: the callers' p50 is the hits' p{pct:.1f}: "
                f"{at:.4f} ms on the server (head + accept); what the "
                f"callers saw beyond it is the loopback and their own "
                f"interpreter")
    for e in data["compiles"]:
        args = e.get("args") or {}
        say(f"request trace: query_compile in the load window: op "
            f"{args.get('op')} shape {args.get('shape')} "
            f"shared {args.get('shared')}")
    if not data["compiles"]:
        say("request trace: no query_compile instant in the load window")


def _report_phases(say, data) -> None:
    """Every phase's median outside the profiler's window beside the one
    inside it: the Python tracer's stretch, measured (ROADMAP D4's
    ``python_tracer_level`` item)."""
    named = HIT_PHASES + ("req.admission",)
    lines = [("hit", "whole (head + accept)", lambda r: r["ms"]),
             *(("hit", p, lambda r, p=p: r["phases"].get(p)) for p in named),
             ("hit", "time under no phase", lambda r: r["ms"] - sum(
                 r["phases"].get(p, 0.0) for p in named)),
             *(("miss", p, lambda r, p=p: r["phases"].get(p))
               for p in ("req.queue", "req.query", "req.readback",
                         "req.wake"))]
    for cache, name, get in lines:
        out, inside = (_median(map(get, _where(data[k], cache)))
                       for k in ("outside", "inside"))
        say(f"request trace: a {cache}'s {name}: median {_ms(out)} ms "
            f"outside the profiler's window, {_ms(inside)} ms inside it")
    # The thread's CPU clock may tick: the mean is the reading, and the
    # share of zeros and the least reading above zero say how coarse.
    for cache in ("hit", "miss"):
        cpu = [r["cpu_ms"] for r in _where(data["outside"], cache)
               if r["cpu_ms"] is not None]
        if cpu:
            wall = _mean(r["ms"] - r["phases"].get("req.head", 0.0)
                         for r in _where(data["outside"], cache))
            say(f"request trace: a {cache}'s cpu_ms outside the profiler's "
                f"window: mean {_ms(_mean(cpu))} of a mean req.accept of "
                f"{_ms(wall)} ms over {len(cpu)}; "
                f"{100.0 * sum(c == 0 for c in cpu) / len(cpu):.1f}% read "
                f"0, the least above 0 "
                f"{_ms(min((c for c in cpu if c > 0), default=None))} ms")


def _report_rounds(say, trace, data) -> None:
    # A round's parts sum to it round by round, so their means add and
    # their medians need not; req.compose is less the launch and the
    # read-back it holds. Outside the profiler's window the host runs as
    # it does untraced.
    for where, found in (("inside", data["rounds"]),
                         ("outside", data["rounds_outside"])):
        parts = [round_parts(r) for r in found]
        for what, mid in (("median", _median), ("mean", _mean),
                          ("longest", max)) if parts else ():
            say(f"request trace: {len(found)} rounds {where} the "
                f"profiler's window, {what} {_ms(mid(r['ms'] for r in found))}"
                f" ms; the {what} of each part: "
                + ", ".join(f"{n} {_ms(mid(p[n] for p in parts))}"
                            for n in parts[0]))
        if parts:  # is it the profiler's own stop, just past its window?
            worst = max(found, key=lambda r: r["ms"])
            say(f"request trace: the longest round {where} the profiler's "
                f"window began at {worst['start']:.3f}s on the trace's "
                f"clock")
    # Whichever program the round's first read-back reads.
    lags = {}
    for r in data["rounds"]:
        for i, (program, lag) in enumerate(read_backs(r, data["modules"])):
            lags.setdefault((program, "first" if i == 0 else "later"),
                            []).append(lag)
    for (program, which), values in sorted(lags.items()):
        say(f"request trace: read-back lag of {program}, the round's "
            f"{which}: median {_ms(_median(values))} ms over "
            f"{len(values)} (result held less the device's last run ended)")
    for label in IDLE_UNDER:
        seconds = sum(s for s, names in trace["gaps"] if label in names)
        say(f"request trace: device idle {seconds:.4f}s under {label}")
