"""Measure the host data path end-to-end (round-3 directive #6).

The host pipeline — subsample + shrunk-window context/mask generation +
batch assembly — must outrun the chip's step rate (not measured on the
current code) to keep a host-batched ``fit_file()`` device-bound
(SURVEY.md §7 hard part 5). This measures, on this machine:

  * native epoch pass (C++ window_batch_epoch, native/host_ops.cpp)
  * Python/NumPy fallback pass (the semantic reference)
  * the prefetch pipeline wrapping the native pass (overlap check)

on a synthetic Zipf corpus of ~20M words at the bench vocab (1M), i.e. the
shape of a real large-corpus run, and writes HOSTPATH.json. CPU-only; run
anywhere:  python scripts/host_path_bench.py
"""

import json
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import numpy as np

    from glint_word2vec_tpu.corpus.batching import SkipGramBatcher
    from glint_word2vec_tpu.corpus.vocab import Vocabulary

    V = int(os.environ.get("HOSTPATH_VOCAB", 1_000_000))
    total_words = int(os.environ.get("HOSTPATH_WORDS", 20_000_000))
    B = int(os.environ.get("HOSTPATH_BATCH", 8192))
    rng = np.random.default_rng(0)

    # Zipf-ish corpus: realistic skew, sentences of ~40 words (the corpus
    # regime after maxSentenceLength chunking).
    ranks = np.arange(1, V + 1, dtype=np.float64)
    counts = np.maximum(1e9 / ranks, 1.0).astype(np.int64)
    words = [f"w{i}" for i in range(V)]
    vocab = Vocabulary(
        words=words, counts=counts,
        word_index={w: i for i, w in enumerate(words)},
        train_words_count=int(counts.sum()),
    )

    ids = np.minimum(
        (rng.random(total_words) ** 4 * V), V - 1
    ).astype(np.int32)
    sent_len = 40
    n_sent = total_words // sent_len
    offsets = np.arange(0, (n_sent + 1) * sent_len, sent_len, dtype=np.int64)
    ids = ids[: offsets[-1]]

    res = {
        "vocab": V,
        "corpus_words": int(offsets[-1]),
        "batch": B,
        "sentence_len": sent_len,
        "machine_cpus": os.cpu_count(),
    }

    def run_epoch(subsample, native, max_seconds=120.0):
        b = SkipGramBatcher.from_flat(
            ids, offsets, vocab, batch_size=B, window=5,
            subsample_ratio=subsample, seed=1,
        )
        it = b.epoch(0) if native else b._epoch_python(0)
        t0 = time.perf_counter()
        batches = 0
        for _ in it:
            batches += 1
            if time.perf_counter() - t0 > max_seconds:
                break
        dt = time.perf_counter() - t0
        centers = batches * B
        return {
            "seconds": round(dt, 2),
            "batches": batches,
            "center_positions": centers,
            "centers_per_sec": round(centers / dt, 1),
            "complete_epoch": bool(b.words_done >= offsets[-1] * 0.99),
        }

    from glint_word2vec_tpu.native import get_lib

    res["native_available"] = get_lib() is not None

    print("[hostpath] native pass (no subsample)...", file=sys.stderr, flush=True)
    res["native_pass"] = run_epoch(0.0, native=True)
    print("[hostpath] native pass (subsample 1e-4)...", file=sys.stderr, flush=True)
    res["native_pass_subsampled"] = run_epoch(1e-4, native=True)
    print("[hostpath] python pass (bounded)...", file=sys.stderr, flush=True)
    res["python_pass"] = run_epoch(0.0, native=False, max_seconds=30.0)

    # Prefetch overlap: the producer thread should hide host batch prep
    # behind (simulated) device steps.
    from glint_word2vec_tpu.utils.prefetch import prefetch as prefetch_batches

    def timed_consume(it, consume_s, n=50):
        t0 = time.perf_counter()
        k = 0
        for _ in it:
            time.sleep(consume_s)  # stand-in for a device dispatch
            k += 1
            if k >= n:
                break
        return time.perf_counter() - t0

    b = SkipGramBatcher.from_flat(
        ids, offsets, vocab, batch_size=B, window=5, subsample_ratio=0.0,
        seed=1,
    )
    consume_s = 0.002
    direct = timed_consume(b.epoch(0), consume_s)
    b2 = SkipGramBatcher.from_flat(
        ids, offsets, vocab, batch_size=B, window=5, subsample_ratio=0.0,
        seed=1,
    )
    pre = timed_consume(prefetch_batches(b2.epoch(0), depth=4), consume_s)
    res["prefetch_overlap"] = {
        "consume_s_per_batch": consume_s,
        "direct_seconds_50": round(direct, 3),
        "prefetched_seconds_50": round(pre, 3),
        "overlap_gain": round(direct / pre, 3) if pre > 0 else None,
    }

    # File-ingestion passes (fit_file's two corpus scans): native C++
    # scanner vs the pure-Python passes. This was the end-to-end wall
    # dominator before the native scanner existed (~1M words/s in Python).
    ingest_words = int(os.environ.get("HOSTPATH_INGEST_WORDS", 5_000_000))
    import tempfile

    from glint_word2vec_tpu.corpus.vocab import (
        build_vocab, encode_file, iter_text_file,
    )
    from glint_word2vec_tpu.native import corpus_scan_native

    print("[hostpath] writing ingest corpus...", file=sys.stderr, flush=True)
    iid = ids[:ingest_words]
    with tempfile.NamedTemporaryFile(
        "w", suffix=".txt", delete=False
    ) as tf:
        corpus_path = tf.name
        for s in range(0, iid.size, sent_len):
            tf.write(" ".join(f"w{i}" for i in iid[s : s + sent_len]))
            tf.write("\n")
    try:
        n_words = int(iid.size)
        t0 = time.perf_counter()
        nat = corpus_scan_native(corpus_path, 1, 1000)
        dt_native = time.perf_counter() - t0
        t0 = time.perf_counter()
        pv = build_vocab(iter_text_file(corpus_path), min_count=1)
        _ = encode_file(corpus_path, pv, max_sentence_length=1000)
        dt_python = time.perf_counter() - t0
        res["file_ingest"] = {
            "corpus_words": n_words,
            "native_available": nat is not None,
            "native_seconds": (
                round(dt_native, 2) if nat is not None else None
            ),
            "native_words_per_sec": (
                round(n_words / dt_native, 1) if nat is not None else None
            ),
            "python_seconds": round(dt_python, 2),
            "python_words_per_sec": round(n_words / dt_python, 1),
        }
    finally:
        os.unlink(corpus_path)

    out = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "HOSTPATH.json",
    )
    from glint_word2vec_tpu.utils import atomic_write_json

    atomic_write_json(out, res, indent=2)
    print(json.dumps(res, indent=2))


if __name__ == "__main__":
    main()
