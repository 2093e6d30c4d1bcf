"""Target-shaped bench sweep (round-3 directive #8).

Runs bench.py's worker across the declared-geometry grid — vocab {1M, 4M},
table dtype bfloat16, batch {8192, 16384}, all three mode variants — each
in its own subprocess (one backend init per cell), and writes
BENCH_SWEEP.json with every cell's full bench line. Like bench.py it needs
an accelerator: a cell without one fails.

Run on the chip:  python scripts/bench_sweep.py
"""

import itertools
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    smoke = os.environ.get("SWEEP_SMOKE") == "1"
    if smoke:
        vocabs = [20_000]
        batches = [512]
        spc = "4"
        extra = {"BENCH_SHARED_NEG": "256", "BENCH_MIN_SECONDS": "0.5",
                 "BENCH_MAX_CALLS": "3"}
    else:
        vocabs = [1_000_000, 4_000_000]
        batches = [8192, 16384]
        spc = "32"
        extra = {}

    cells = []
    for V, B in itertools.product(vocabs, batches):
        env = dict(
            os.environ,
            BENCH_WORKER="1",
            BENCH_VOCAB=str(V),
            BENCH_BATCH=str(B),
            BENCH_SPC=spc,
            BENCH_DTYPE="bfloat16",
            BENCH_MODES="per_pair,per_pair_bf16c,shared_bf16c",
            **extra,
        )
        t0 = time.time()
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            env=env, capture_output=True, text=True,
            timeout=float(os.environ.get("SWEEP_CELL_TIMEOUT", 900)),
        )
        line = None
        for ln in reversed(proc.stdout.splitlines()):
            ln = ln.strip()
            if ln.startswith("{") and '"metric"' in ln:
                line = json.loads(ln)
                break
        cell = {"vocab": V, "batch": B, "wall_s": round(time.time() - t0, 1)}
        if line is None:
            cell["error"] = (proc.stderr or "no output").strip()[-300:]
        else:
            cell["result"] = line
        cells.append(cell)
        print(json.dumps(cell), flush=True)

    out = os.path.join(REPO, "BENCH_SWEEP.json")
    # Temp + replace: a sweep interrupted mid-write keeps the previous
    # complete artifact instead of leaving a torn one.
    tmp = f"{out}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump({"cells": cells}, f, indent=2)
    os.replace(tmp, out)
    print(f"wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
