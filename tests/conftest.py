"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip hardware is not available in CI; sharding correctness is validated
on a virtual 8-device CPU platform exactly as the reference validates its
distributed stack on a 2-core pseudo-cluster in one container (SURVEY.md §4).
Must run before the first ``import jax`` anywhere in the test process.
"""

import os

# Force CPU even when the environment pre-sets a TPU platform: unit tests
# must never grab (or wait on) the real chip.
os.environ["JAX_PLATFORMS"] = "cpu"
# Skip checkpoint durability fsyncs suite-wide: on the 9p filesystems
# these tests run on, per-file fsync dominates every checkpoint/resume
# test's wall time (~25% of the whole tier-1 budget) while testing the
# KERNEL, not this code. Crash-safety semantics (temp dir + atomic
# rename + manifest) are unchanged and still exercised everywhere; the
# fsync codepath itself has a dedicated test that re-enables it
# (tests/test_ckpt_integrity.py::test_fsync_path_still_works).
os.environ.setdefault("GLINT_CKPT_NO_FSYNC", "1")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# NOTE: the jax persistent compilation cache is deliberately NOT
# enabled here. It was tried as a tier-1 wall reclaim (fresh engines
# can't share in-memory jit caches, so config-identical train scans
# recompile once per test) and the CPU backend of this jax version
# served cache-hit executables that broke checkpoint-resume BITWISE
# parity and corrupted the heap at interpreter exit ("double free or
# corruption"). Wall is reclaimed by session-scoped model fixtures and
# slow-marking instead.

import numpy as np
import pytest


def _make_tiny_corpus():
    """Deterministic synthetic corpus with learnable structure.

    Mirrors the role of the reference's German-Wikipedia country/capital
    fixture (ServerSideGlintWord2VecSpec.scala:22-37): small, real structure,
    fixed seed — big enough for analogy-style quality gates to be meaningful.
    Countries co-occur with their capitals and a shared 'capital' relation
    word, plus filler vocabulary for negative-sampling realism.
    """
    rng = np.random.default_rng(12345)
    pairs = [
        ("germany", "berlin"),
        ("france", "paris"),
        ("austria", "vienna"),
        ("spain", "madrid"),
        ("italy", "rome"),
        ("poland", "warsaw"),
    ]
    # Pair-specific theme words give each (country, capital) pair shared
    # contexts — the second-order co-occurrence that makes a capital
    # distributionally similar to its country in real text.
    theme = {c: [f"{c}_t{j}" for j in range(4)] for c, _ in pairs}
    filler = [f"w{i}" for i in range(40)]
    sentences = []
    for _ in range(4000):
        country, capital = pairs[rng.integers(len(pairs))]
        th = list(rng.choice(theme[country], size=2))
        noise = list(rng.choice(filler, size=2))
        style = rng.integers(4)
        if style == 0:
            s = [capital, "is", "the", "capital", "of", country] + th
        elif style == 1:
            s = [th[0], country, "capital", "city", capital, th[1]] + noise
        elif style == 2:
            s = [country, "has", "capital", capital] + th + noise
        else:
            x = country if rng.random() < 0.5 else capital
            s = [x, "famous", "for"] + th + noise
        sentences.append(s)
    # Pure-filler sentences so filler words reach min_count reliably.
    for _ in range(600):
        sentences.append(list(rng.choice(filler, size=8)))
    rng.shuffle(sentences)
    return [[str(w) for w in s] for s in sentences]


@pytest.fixture(scope="session")
def tiny_corpus():
    return _make_tiny_corpus()


@pytest.fixture(scope="session")
def e2e_model(tiny_corpus):
    """One 6-epoch reference training shared by every module that only
    reads it (test_model_e2e, test_eval trained config-identical models
    per module before — ~30s each on this container)."""
    from glint_word2vec_tpu import Word2Vec
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    m = (
        Word2Vec(mesh=make_mesh(2, 4))
        .set_vector_size(48)
        .set_window_size(5)
        .set_step_size(0.025)
        .set_batch_size(256)
        .set_num_negatives(5)
        .set_min_count(5)
        .set_num_iterations(6)
        .set_seed(1)
    ).fit(tiny_corpus)
    yield m
    m.stop()
