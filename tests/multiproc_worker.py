"""Worker process for the 2-process distributed training test.

Launched by tests/test_multiprocess.py as ``python multiproc_worker.py
<process_id> <num_processes> <coordinator_port> <workdir>``. Each process
owns 2 virtual CPU devices; together they form the 4-device ("data", "model")
= (2, 2) global mesh — the process-spanning analogue of the reference's
2-partition + 2-parameter-server integration topology
(ServerSideGlintWord2VecSpec.scala:90-94).

Asserts, inside the multi-host run itself:
  * fit() trains in lockstep across processes (steps > 0, finite loss);
  * sharded save/load round-trips (process-0 shard writes + manifest);
  * fit_file() — the native-scanner ingestion + flat-corpus process
    sharding path — reproduces fit(sentences) exactly;
  * checkpoint/resume across processes reproduces the uninterrupted fit
    exactly (same schedule, same keys);
  * query surface works identically on every process.
Exit code 0 = all assertions passed on this process.
"""

import json
import os
import sys


def main() -> int:
    pid, n_proc, port, workdir = (
        int(sys.argv[1]), int(sys.argv[2]), sys.argv[3], sys.argv[4]
    )
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    import jax

    jax.config.update("jax_platforms", "cpu")

    from glint_word2vec_tpu.parallel import distributed as dist

    dist.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=n_proc,
        process_id=pid,
    )
    assert jax.process_count() == n_proc
    assert jax.device_count() == 2 * n_proc

    import numpy as np

    from glint_word2vec_tpu import Word2Vec

    # Deterministic corpus, built identically on every process (the
    # shared-corpus contract of multi-host fit()). Sentence lengths are
    # deliberately skewed by position so the round-robin shards have very
    # different word counts: the word-light host MUST exercise the lockstep
    # zero-mask padding path (including whole pad-only groups), the
    # riskiest part of the multi-host loop. Odd sentence count also covers
    # the drop-the-remainder split.
    rng = np.random.default_rng(7)
    words = [f"w{i}" for i in range(40)]
    sentences = [
        [str(w) for w in rng.choice(words, size=(20 if i % 2 == 0 else 4))]
        for i in range(301)
    ]

    common = dict(
        vector_size=16,
        min_count=1,
        batch_size=64,  # 32 rows per process
        num_iterations=2,
        seed=3,
        num_partitions=2,
        num_shards=2,
        steps_per_call=4,
    )

    # --- full multi-host fit + save -----------------------------------
    model = Word2Vec(**common).fit(sentences)
    tm = model.training_metrics
    assert tm["steps"] > 0, tm
    # final_loss is recorded lazily (every log_every steps) and may be None
    # on short runs; when present it must be finite.
    assert tm["final_loss"] is None or np.isfinite(tm["final_loss"]), tm
    ref_vec = model.transform("w0")
    assert np.all(np.isfinite(ref_vec))
    syn = model.find_synonyms("w0", 5)
    assert len(syn) == 5 and all(np.isfinite(s) for _, s in syn)

    model_dir = os.path.join(workdir, "model")
    model.save(model_dir)

    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices("model_saved")

    # Sharded files must cover both tables (written across processes).
    meta = json.load(open(os.path.join(model_dir, "matrix", "engine.json")))
    assert meta["format"] == "sharded"
    for name in ("syn0", "syn1"):
        for b in meta["shards"][name]:
            assert os.path.exists(
                os.path.join(model_dir, "matrix", b["file"])
            ), b

    # --- load on the same global mesh, query parity -------------------
    from glint_word2vec_tpu import load_model

    loaded = load_model(model_dir)
    np.testing.assert_allclose(
        loaded.transform("w0"), ref_vec, rtol=1e-5, atol=1e-6
    )

    # --- fit_file under multi-host: the native scanner + flat-corpus
    # process sharding path. Process 0 writes the corpus; both read it
    # (the shared-filesystem contract). Must reproduce fit(sentences)
    # exactly: same vocab, same schedule, same draws.
    corpus_path = os.path.join(workdir, "corpus.txt")
    if pid == 0:
        with open(corpus_path, "w", encoding="utf-8") as f:
            for s in sentences:
                f.write(" ".join(s))
                f.write("\n")
    multihost_utils.sync_global_devices("corpus_written")
    model_ff = Word2Vec(**common).fit_file(corpus_path)
    assert model_ff.vocab.words == model.vocab.words
    np.testing.assert_allclose(
        model_ff.transform("w0"), ref_vec, rtol=1e-5, atol=1e-6
    )
    multihost_utils.sync_global_devices("fit_file_done")

    # --- checkpoint/resume across processes ---------------------------
    ck = os.path.join(workdir, "ck")
    Word2Vec(**common).fit(sentences, checkpoint_dir=ck, stop_after_epochs=1)
    multihost_utils.sync_global_devices("ckpt_phase1")
    state = json.load(open(os.path.join(ck, "train_state.json")))
    assert state["epochs_completed"] == 1, state
    resumed = Word2Vec(**common).fit(sentences, checkpoint_dir=ck)
    np.testing.assert_allclose(
        resumed.transform("w0"), ref_vec, rtol=1e-4, atol=1e-5
    )

    multihost_utils.sync_global_devices("done")
    print(f"proc {pid}: OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
