"""The exchange's share of its roofline: the least one chip must send to
all-reduce the rows a packed step pulls (benchmark/bytes_sharded.py, from
shapes alone) at the chip's published interconnect peak
(benchmark/peaks_ici.json), over the step's time under ``glint.exchange``.
Bound by the interconnect's bytes."""

import json
import os

from benchmark import bytes_sharded
from benchmark.program_trace import scope_ms


def ici_bytes_per_s(run):
    """None on a CPU rehearsal; an unknown chip is an error."""
    if run.device["platform"] == "cpu":
        return None
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "peaks_ici.json")) as f:
        table = json.load(f)["devices"]
    return table[run.device["kind"]]["ici_bytes_per_s"]


def read(run):
    ms = scope_ms(run, "glint.exchange")
    peak = ici_bytes_per_s(run) if ms else None
    if not ms or not peak:
        return None
    m, r = run.cfg["model"], run.cfg["run"]
    chips = r["num_shards"]
    wire = bytes_sharded.all_reduce_wire_bytes(
        bytes_sharded.exchange_bytes(r["batch_size"], m["window"],
                                     m["negatives"], m["vector_size"], chips),
        chips)
    tm = run.training_metrics or {}
    run.say(f"exchange: {wire / 1e6:.1f} MB a chip a step on the wire at "
            f"least; the program counts "
            f"{tm.get('exchange_bytes_per_step')} bytes handed")
    return 100.0 * wire / peak / (ms / 1e3)
