"""A streamed fit's rounds, read from the ring the trainer wrote
(``ObsConfig(chrome_trace=...)``): what the seven ``stream.*`` readers
share. Built on ``fit_trace.spans`` (the ring) and ``program_trace.read``
(the device's ops and the offset between the ring's clock and the trace's).

Spans read (``streaming/trainer.py``): ``stream_round`` (args ``round``,
``fill``, ``raw_words``, ``live``, ``groups``) around the DEVICE half of one
round: ``stream_install`` (the round's noise table put on the device),
``upload_corpus``, and a ``device_steps`` / ``readback_harvest`` pair a
dispatch group. The HOST half of the rounds to come runs in slices between a
group's dispatch and its harvest: ``stream_fill`` (a chunk of sentences a
span), ``stream_promote``, ``stream_adapt``, each with the ``round`` it
prepares. A round's parts are the spans that START inside its
``stream_round``: for the host spans that is the host work done behind that
round's drain, a round's worth of it in a steady window, whatever round it
prepares. A LIVE round is one whose every sentence came from past the
bootstrap window (``live``). A program without ``stream_round`` (PR 50's
parent) gives no rounds, and every reader None.
"""

import bisect
import statistics

from benchmark import fit_trace, program_trace
from benchmark.trace_reduce import merged

PARTS = ("stream_fill", "stream_promote", "stream_adapt", "stream_install",
         "upload_corpus", "device_steps", "readback_harvest")


def rounds(run) -> list:
    """One dict a LIVE round, oldest first: ``start`` / ``end`` (seconds on
    the ring's clock), ``args``, and for each name of PARTS the (start,
    end) of the spans of that name that start inside the round."""
    if getattr(run, "_stream_rounds", None) is not None:
        return run._stream_rounds
    parts = {name: fit_trace.spans(run, name) for name in PARTS}
    starts = {name: [s for s, _, _ in v] for name, v in parts.items()}
    out = []
    for s, e, args in fit_trace.spans(run, "stream_round"):
        if not args.get("live") or not args.get("fill"):
            continue
        rnd = {"start": s, "end": e, "args": args}
        for name, v in parts.items():
            lo = bisect.bisect_left(starts[name], s)
            hi = bisect.bisect_left(starts[name], e)
            rnd[name] = [x[:2] for x in v[lo:hi]]
        out.append(rnd)
    run._stream_rounds = out
    if out:
        report(run, out)
    return out


def _total(rnd, *names) -> float:
    return sum(e - s for name in names for s, e in rnd[name])


def drain(rnd):
    """(start, end) from the round's first ``device_steps`` open to its
    last ``readback_harvest`` close; None for a round without either."""
    if not rnd["device_steps"] or not rnd["readback_harvest"]:
        return None
    return rnd["device_steps"][0][0], rnd["readback_harvest"][-1][1]


def part_seconds(rnd) -> dict:
    d = drain(rnd)
    return {
        "round": rnd["end"] - rnd["start"],
        "fill": _total(rnd, "stream_fill"),
        "adapt": _total(rnd, "stream_promote", "stream_adapt"),
        "upload": _total(rnd, "upload_corpus"),
        "drain": d[1] - d[0] if d else 0.0,
    }


def median_ms(run, part: str):
    """Median over the live rounds of one part of a round, in ms."""
    values = [part_seconds(r)[part] for r in rounds(run)]
    return statistics.median(values) * 1e3 if values else None


def buffer_fill(run):
    """Median over the live rounds of kept words over the buffer's words,
    in percent."""
    fills = [r["args"]["fill"] for r in rounds(run)]
    if not fills:
        return None
    return 100.0 * statistics.median(fills) / run.cfg["run"]["buffer_words"]


def drain_device_share(run):
    """Device busy time inside the traced rounds' drains over those
    drains' length, in percent; None without a trace, an offset or a
    round whose drain lies whole inside the traced window."""
    data = program_trace.read(run) if run.trace else None
    if not data or data["offset_us"] is None or not data["ops"]:
        return None
    shift = data["offset_us"] / 1e6
    busy = [(s / 1e9, e / 1e9) for s, e in merged(data["ops"])]
    inside = covered = 0.0
    for rnd in rounds(run):
        d = drain(rnd)
        if d is None:
            continue
        lo, hi = d[0] + shift, d[1] + shift
        if lo < 0 or hi > run.trace["window_s"]:
            continue
        inside += hi - lo
        covered += sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in busy)
    return 100.0 * covered / inside if inside else None


def report(run, rnds) -> None:
    """What the numbers are made of, once a run."""
    ms = lambda x: f"{x * 1e3:.1f}"  # noqa: E731
    for rnd in rnds:
        p, a = part_seconds(rnd), rnd["args"]
        run.say(f"stream trace: round {a.get('round')} fill {a.get('fill')} "
                f"raw {a.get('raw_words')} groups {a.get('groups')}: "
                f"{ms(p['round'])} ms = install "
                f"{ms(_total(rnd, 'stream_install'))} + upload "
                f"{ms(p['upload'])} + drain {ms(p['drain'])} (dispatch "
                f"{ms(_total(rnd, 'device_steps'))}, harvest "
                f"{ms(_total(rnd, 'readback_harvest'))}) + "
                f"{ms(p['round'] - p['upload'] - p['drain'] - _total(rnd, 'stream_install'))}"
                f" other; behind the drain, for later rounds: fill "
                f"{ms(p['fill'])} in {len(rnd['stream_fill'])} slices + "
                f"promote {ms(_total(rnd, 'stream_promote'))} + adapt "
                f"{ms(_total(rnd, 'stream_adapt'))}")
