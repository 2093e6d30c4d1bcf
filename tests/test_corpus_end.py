"""A dispatch group stops at the corpus end, on the device (ISSUE 44).

The packed scans (``EmbeddingEngine._make_packed_corpus_scan``: the pair
scan and the bag scan, each with and without a group table) run a group's
steps in a loop whose condition is ``i < K and pos < n_valid``. A step
that would start past the end of the view is not run, and its five
outputs read as the host's accounting expects of a step that consumed
nothing.

Contracts pinned here, for all four programs:
  * a group started 5 steps before the corpus end leaves the tables the
    same 5 steps run alone leave, bit for bit, and the outputs of the
    steps it did not run are ``alphas`` 0 (the host counts the steps the
    device ran from it), ``losses`` / ``pair_counts`` / ``written`` 0 and
    ``pos_ends`` the last step's, at or past ``n_valid``;
  * a group started at or past the end returns both tables as they went
    in and ``pos_ends`` = the start;
  * it is still one program a ``(P, W, B, S, K, G)``, wherever the group
    starts.
"""

import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from test_cbow import zipf_corpus  # noqa: E402
from test_cbow_subword import BUCKET, G, random_groups  # noqa: E402

from glint_word2vec_tpu.corpus.batching import packed_pair_batch  # noqa: E402
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine  # noqa: E402
from glint_word2vec_tpu.parallel.mesh import make_mesh  # noqa: E402

V, D, NEG, WINDOW, BATCH, K = 512, 32, 5, 5, 64, 8  # test_cbow_subword's V
LIVE = 5  # steps the group under test starts before the corpus end
PROGRAMS = {  # name: (architecture, group table width)
    "pairs": ("skipgram", 0),
    "pairs-groups": ("skipgram", G),
    "bags": ("cbow", 0),
    "bags-groups": ("cbow", G),
}


def _engine(architecture, width):
    """An engine over the seed's tables with the compacted view of one
    seeded corpus on its device; ``(engine, n_valid)``."""
    counts = np.arange(V, 0, -1).astype(np.int64) * 3
    eng = EmbeddingEngine(make_mesh(1, 1), V, D, counts, num_negatives=NEG,
                          seed=3, extra_rows=BUCKET if width else 0,
                          architecture=architecture)
    if width:
        eng.upload_center_groups(random_groups())
    eng.upload_corpus(*zipf_corpus(sentences=120))
    eng.set_keep_probs(np.full(V, 0.8, np.float32))
    return eng, eng.compact_corpus(jax.random.PRNGKey(9))


def _dispatch(eng, start, step0, steps=K):
    pairs = (BATCH if eng.architecture == "cbow"
             else packed_pair_batch(BATCH, WINDOW, 1))
    return [np.asarray(x) for x in eng.train_steps_corpus_packed(
        start, pairs, WINDOW, BATCH, jax.random.PRNGKey(3), steps,
        step0=step0, step_size=0.05, total_words=5000)]


def _tables(eng):
    return np.asarray(eng.syn0), np.asarray(eng.syn1)


@pytest.fixture(scope="module", params=sorted(PROGRAMS))
def tail(request):
    """Where the steps of one epoch start, found by running it group by
    group: ``(program, n_valid, start of the LIVE-th step from the end,
    its step number)``."""
    eng, n_valid = _engine(*PROGRAMS[request.param])
    starts, pos = [], 0
    while pos < n_valid:
        _, _, pos_ends, alphas, _ = _dispatch(eng, pos, len(starts))
        ran = int((alphas > 0).sum())
        starts += [pos, *pos_ends[:ran - 1]]
        pos = int(pos_ends[-1])
    assert len(starts) > K + LIVE and starts == sorted(starts)
    return request.param, n_valid, int(starts[-LIVE]), len(starts) - LIVE


def test_a_group_stops_at_the_corpus_end(tail):
    program, n_valid, start, step0 = tail
    group, _ = _engine(*PROGRAMS[program])
    losses, pairs, pos_ends, alphas, written = _dispatch(group, start, step0)
    alone, _ = _engine(*PROGRAMS[program])
    ref = _dispatch(alone, start, step0, steps=LIVE)
    for got, want in zip(_tables(group), _tables(alone)):
        assert got.tobytes() == want.tobytes()
    for got, want in zip((losses, pairs, pos_ends, alphas, written), ref):
        np.testing.assert_array_equal(got[:LIVE], want)
    assert (alphas[:LIVE] > 0).all() and (losses[:LIVE] > 0).all()
    assert pos_ends[LIVE - 2] < n_valid <= pos_ends[LIVE - 1]
    assert (pos_ends[LIVE:] == pos_ends[LIVE - 1]).all()
    for rest in (alphas, losses, pairs, written):
        assert not rest[LIVE:].any()


@pytest.mark.parametrize("past", [0, 1, 10 * BATCH])
def test_a_group_past_the_corpus_end_runs_no_step(tail, past):
    program, n_valid, _, step0 = tail
    eng, _ = _engine(*PROGRAMS[program])
    before = _tables(eng)
    losses, pairs, pos_ends, alphas, written = _dispatch(
        eng, n_valid + past, step0)
    for got, want in zip(_tables(eng), before):
        assert got.tobytes() == want.tobytes()
    assert (pos_ends == n_valid + past).all()
    for out in (alphas, losses, pairs, written):
        assert out.shape[0] == K and not out.any()


def test_it_is_one_program_wherever_the_group_starts(tail):
    program, n_valid, start, step0 = tail
    eng, _ = _engine(*PROGRAMS[program])
    _dispatch(eng, 0, step0)
    (key,), (fn,) = zip(*eng._packed_scan_cache.items())
    assert key[-2:] == (K, PROGRAMS[program][1])
    compiled = fn._cache_size()  # the memo hands engines of one geometry one
    for at in (start, n_valid, n_valid + BATCH):
        _dispatch(eng, at, step0)
    assert len(eng._packed_scan_cache) == 1 and fn._cache_size() == compiled
