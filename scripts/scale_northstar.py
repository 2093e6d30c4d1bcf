"""North-star geometry demonstration: 10M vocab x d=300 on ONE chip.

The reference's operational claim is vocabulary capacity beyond one
machine (/root/reference/README.md:69,71-73 — "huge models", the 8 GB
broadcast ceiling it exists to kill). This script substantiates the
equivalent claim for one TPU chip at the driver north-star geometry:
both tables at 10M x 300 in bfloat16 (~12 GB of a v5e's 16 GB HBM),
trained with the production device-resident corpus scan and then probed
through the full query surface (pull / top-k / batched top-k / norms /
save / load), in BOTH model-axis layouts.

Per round-4 verdict weak #1, every phase's results are flushed to
SCALE_r05.json incrementally, so a run that dies midway preserves the
phases that did complete; a non-TPU run is marked "fallback": "cpu" at
the top level and shrinks to a mechanism-check geometry.

Env: GLINT_NS_PLATFORM (force backend), GLINT_NS_VOCAB, GLINT_NS_DIM,
GLINT_NS_BATCH, GLINT_NS_MIN_SECONDS, GLINT_NS_CKPT (checkpoint dir,
default /tmp/ns_ckpt; ~24 GB f32 on disk at full geometry, removed
after the load check).
"""

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from glint_word2vec_tpu.utils.platform import force_platform  # noqa: E402

force_platform(os.environ.get("GLINT_NS_PLATFORM"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "SCALE_r05.json",
)


def _mem(dev):
    try:
        stats = dev.memory_stats() or {}
        return {
            k: int(stats[k])
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats
        }
    except Exception:
        return {}


class Flusher:
    def __init__(self, base):
        self.doc = base

    def flush(self, **updates):
        self.doc.update(updates)
        tmp = OUT + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.doc, f, indent=2)
        os.replace(tmp, OUT)


def _timed(fn, min_seconds=0.5, warm=True):
    """Best-effort steady-state timing: warm once (compile), then run
    until the floor; returns (seconds_per_call, calls)."""
    if warm:
        jax.block_until_ready(fn())
    t0 = time.time()
    calls = 0
    last = None
    while True:
        last = fn()
        calls += 1
        if calls >= 2 and time.time() - t0 >= min_seconds:
            break
        if calls >= 200:
            break
    jax.block_until_ready(last)
    return (time.time() - t0) / calls, calls


def run_layout(dev, layout, V, d, B, W, spc, min_seconds, counts, p, flags,
               res, flush):
    """Phases write into ``res`` and call ``flush()`` as each completes,
    so a failure mid-layout preserves every finished phase; the
    engine is destroyed on ANY exit so a failed phase can't leave 12 GB
    of tables pinned in HBM for the next layout's init to trip over."""
    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(1, 1, devices=[dev])
    t0 = time.time()
    eng = EmbeddingEngine(
        mesh, V, d, counts, num_negatives=5, seed=0,
        dtype="bfloat16", compute_dtype="bfloat16", layout=layout,
    )
    try:
        _run_layout_phases(
            dev, eng, layout, V, d, B, W, spc, min_seconds, p, flags,
            res, flush, mesh, t0,
        )
    finally:
        eng.destroy()


def _run_layout_phases(dev, eng, layout, V, d, B, W, spc, min_seconds, p,
                       flags, res, flush, mesh, t0):
    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine

    jax.block_until_ready(eng.syn0)
    res["layout"] = layout
    res["init_seconds"] = round(time.time() - t0, 1)
    res["memory_after_init"] = _mem(dev)
    flush()

    # --- Training at the north-star geometry: the production
    # device-resident corpus scan (fit/fit_file single-process path).
    rng = np.random.default_rng(0)
    sent_len = 40
    N = int(os.environ.get("GLINT_NS_CORPUS_WORDS", 2_000_000))
    N -= N % sent_len
    ids = rng.choice(V, size=N, p=p).astype(np.int32)
    offsets = np.arange(0, N + sent_len, sent_len, dtype=np.int64)
    eng.upload_corpus(ids, offsets)
    alphas = np.full(spc, 0.025, np.float32)
    key = jax.random.PRNGKey(0)

    t0 = time.time()
    jax.block_until_ready(eng.train_steps_corpus(0, B, W, key, alphas, 0))
    compile_s = time.time() - t0
    span = max(N - spc * B, 1)
    t0 = time.time()
    calls, last = 0, None
    while True:
        last = eng.train_steps_corpus(
            (calls * spc * B) % span, B, W, key, alphas, calls * spc
        )
        calls += 1
        if calls >= 2 and time.time() - t0 >= min_seconds:
            break
    jax.block_until_ready(last)
    dt = time.time() - t0
    steps = calls * spc
    res["train"] = {
        "words_per_sec": round(B * steps / dt, 1),
        "step_time_us": round(dt / steps * 1e6, 1),
        "compile_s": round(compile_s, 1),
        "timed_steps": steps,
        "corpus_words_device": N,
        "batch": B,
        "window": W,
    }
    res["memory_after_train"] = _mem(dev)
    flush()

    # --- Full query surface at 10M rows.
    q_idx = rng.integers(0, V, size=4096).astype(np.int32)
    s, c = _timed(lambda: eng.pull(q_idx), min_seconds)
    res["pull_4096_ms"] = round(s * 1e3, 2)
    vec = np.asarray(eng.pull(q_idx[:1])[0], dtype=np.float32)
    s, c = _timed(lambda: eng.top_k_cosine(vec, 10), min_seconds)
    res["topk10_ms"] = round(s * 1e3, 2)
    Q = np.asarray(eng.pull(q_idx[:64]), dtype=np.float32)
    s, c = _timed(lambda: eng.top_k_cosine_batch(Q, 10), min_seconds)
    res["topk10_batch64_ms"] = round(s * 1e3, 2)
    s, c = _timed(lambda: eng.norms(), min_seconds)
    res["norms_ms"] = round(s * 1e3, 2)
    res["memory_after_queries"] = _mem(dev)
    flush()

    # --- Persistence at size (once; both layouts write the same bytes).
    if flags.get("save_load"):
        ckpt = os.environ.get("GLINT_NS_CKPT", "/tmp/ns_ckpt")
        shutil.rmtree(ckpt, ignore_errors=True)
        probe = np.asarray(eng.pull(q_idx[:8]), dtype=np.float32)
        t0 = time.time()
        eng.save(ckpt)
        save_s = time.time() - t0
        ckpt_bytes = sum(
            os.path.getsize(os.path.join(r, f))
            for r, _, fs in os.walk(ckpt) for f in fs
        )
        # Free the live tables BEFORE loading: two engines at this
        # geometry (2 x 12 GB) exceed one chip's HBM. (The caller's
        # finally-destroy is idempotent.)
        eng.destroy()
        t0 = time.time()
        eng2 = EmbeddingEngine.load(ckpt, mesh)
        try:
            jax.block_until_ready(eng2.syn0)
            load_s = time.time() - t0
            probe2 = np.asarray(eng2.pull(q_idx[:8]), dtype=np.float32)
            res["save_load"] = {
                "save_seconds": round(save_s, 1),
                "load_seconds": round(load_s, 1),
                "checkpoint_bytes": ckpt_bytes,
                "roundtrip_exact": bool(np.array_equal(probe, probe2)),
            }
        finally:
            eng2.destroy()
            shutil.rmtree(ckpt, ignore_errors=True)
        flush()


def main():
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    V = int(os.environ.get("GLINT_NS_VOCAB", 10_000_000 if on_tpu else 200_000))
    d = int(os.environ.get("GLINT_NS_DIM", 300 if on_tpu else 64))
    B = int(os.environ.get("GLINT_NS_BATCH", 8192))
    min_seconds = float(
        os.environ.get("GLINT_NS_MIN_SECONDS", 3.0 if on_tpu else 0.5)
    )
    W, spc = 5, 16  # context lanes 2W-3 = 7, the bench geometry

    fl = Flusher({
        "metric": "northstar_scale",
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "vocab": V,
        "dim": d,
        "table_dtype": "bfloat16",
        "tables_bytes_declared": 2 * V * d * 2,
        "layouts": {},
    })
    if not on_tpu:
        fl.flush(fallback=dev.platform)

    ranks = np.arange(1, V + 1, dtype=np.float64)
    counts = np.maximum(1e9 / ranks, 1.0).astype(np.int64)
    p = (counts / counts.sum()).astype(np.float64)

    layouts = ("dims", "rows")
    for i, layout in enumerate(layouts):
        res = {}
        fl.doc["layouts"][layout] = res
        try:
            run_layout(
                dev, layout, V, d, B, W, spc, min_seconds, counts, p,
                {"save_load": i == len(layouts) - 1}, res, fl.flush,
            )
        except Exception as e:
            # Finished phases are already in res/flushed; record what
            # broke alongside them.
            res["error"] = f"{type(e).__name__}: {e}"
        fl.flush()
    print(json.dumps(fl.doc))


if __name__ == "__main__":
    main()
