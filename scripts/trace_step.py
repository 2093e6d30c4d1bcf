"""Capture a jax.profiler device trace of the scanned train step.

Round-2/3 directives asked for a trace-backed step breakdown; the numeric
budget is already reconciled (PARITY.md perf table: arithmetic micros sum
to ~the measured device-resident step), so this is the corroborating
artifact. Writes a TensorBoard-format trace directory and prints one JSON
line with where it landed, or the failure mode if the backend rejects
profiling (also worth recording).

Usage: python scripts/trace_step.py [--out DIR] [--steps N]
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from glint_word2vec_tpu.utils.platform import force_platform  # noqa: E402

force_platform(os.environ.get("GLINT_PROFILE_PLATFORM"))

import jax  # noqa: E402
import numpy as np  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="/tmp/glint_trace")
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--spc", type=int, default=4)
    args = ap.parse_args()

    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    V, d, B, C = 1_000_000, 300, 8192, 7
    mesh = make_mesh(1, 1, devices=[jax.devices()[0]])
    counts = np.maximum(
        1e9 / np.arange(1, V + 1, dtype=np.float64), 1.0
    ).astype(np.int64)
    eng = EmbeddingEngine(mesh, V, d, counts, num_negatives=5, seed=0)

    rng = np.random.default_rng(0)
    p = counts / counts.sum()
    ck = jax.device_put(
        rng.choice(V, size=(args.spc, B), p=p).astype(np.int32)
    )
    xk = jax.device_put(
        rng.choice(V, size=(args.spc, B, C), p=p).astype(np.int32)
    )
    mk = jax.device_put(
        (rng.random((args.spc, B, C)) < 0.85).astype(np.float32)
    )
    al = jax.device_put(np.full(args.spc, 0.025, np.float32))
    key = jax.random.PRNGKey(0)
    # Warm: compile outside the trace so the trace holds steady-state steps.
    jax.block_until_ready(eng.train_steps(ck, xk, mk, key, al, 0))

    result = {"device": str(jax.devices()[0]), "out": args.out,
              "steps": args.steps * args.spc}
    try:
        with jax.profiler.trace(args.out):
            last = None
            for i in range(args.steps):
                last = eng.train_steps(ck, xk, mk, key, al, (i + 1) * args.spc)
            jax.block_until_ready(last)
        files = []
        for root, _, names in os.walk(args.out):
            files += [os.path.join(root, n) for n in names]
        result["ok"] = bool(files)
        result["trace_files"] = len(files)
        result["trace_bytes"] = sum(os.path.getsize(f) for f in files)
    except Exception as e:  # profiling unsupported on this backend path
        result["ok"] = False
        result["error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(result))


if __name__ == "__main__":
    main()
