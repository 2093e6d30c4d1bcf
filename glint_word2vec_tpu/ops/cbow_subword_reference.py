"""The plain reference of the subword CBOW step, in the SOURCE's form.

One synchronous-batch step of continuous bag of words over subword groups
as the public ``fasttext cbow`` command trains it (facebookresearch/
fastText: ``FastText::cbow`` in ``src/fasttext.cc``, ``Model::computeHidden``
and ``Model::update`` in ``src/model.cc``, ``NegativeSamplingLoss`` in
``src/loss.cc``). Straightforward ``jax.numpy`` float32, contractions at
``highest`` precision, every update computed from the pre-step rows and a
row's shares summed before they are added; nothing here is shared with the
engine's step (``parallel/engine.py``), which the tests hold to it
(``tests/test_cbow_subword.py``, where a numpy transcription of the tool's
loop, run position by position with the tables frozen for the batch, holds
this file in turn).

A word ``w`` owns the group ``G(w)``: its own row, then the rows its
character n-grams hash to. For one position with word ``w``, bag ``C`` (the
positions within the drawn reach of it in its sentence; ``|C| = 0`` is
skipped) and noise words ``n_k``::

    I      = the CONCATENATION of G(w_c) over c in C   (bow.insert: a row
             that two words of the bag share is in it twice)
    h      = (1/|I|) * sum_{i in I} syn0[i]            (computeHidden)
    g_pos  = alpha * (1 - sigmoid(h . syn1[w]))
    g_k    = -alpha * sigmoid(h . syn1[n_k])           (0 where n_k == w)
    syn1[w]   += g_pos * h ;  syn1[n_k] += g_k * h     (word rows alone)
    e      = g_pos * syn1[w] + sum_k g_k * syn1[n_k]
    syn0[i]   += e        for every i in I, once each time it is in I

**One mean over the union, not a mean of the words' means**, and **the
gradient is not divided by |I|** (``Model::update`` divides for the
supervised model only). This IS ``word2vec.c``'s CBOW step
(``ops/cbow_reference.py``) with the bag ``I``: the function below forms
``I`` as the tool does and hands it to those equations. The engine never
forms ``I``: it sums each span word's group once and lets the bags read the
sums (``EmbeddingEngine`` ``make_packed_corpus_scan``), which is why the
two being equal says something.

Departures from the tool:

* **One synchronous batch, not Hogwild.** Every position of a step reads
  the tables as they stood before the step, and a row's updates are
  summed; the tool's threads update in place, racing.
* **The exact sigmoid and log**, not the tool's 512-entry tables clipped
  at +-8.
* **Alias sampling** from the unigram^0.5 distribution, not the tool's
  10,000,000-entry negatives table; a noise word equal to the position's
  word is masked, where the tool draws again.
* **Groups are cut at ``max_subwords``** (``corpus/subword.subword_group``);
  the tool keeps every n-gram.
* **No position weights.** The published ``cc.<lang>.300`` tables used a
  variant with position-dependent weights that the released tool does not
  have; this is the released tool's model.
"""

from __future__ import annotations

import jax.numpy as jnp

from glint_word2vec_tpu.ops.cbow_reference import cbow_step


def cbow_subword_step(syn0, syn1, groups, bags, centres, live, negs, alpha):
    """One step over P positions. ``groups (V, G)`` int32, word w's rows,
    padded with -1; ``bags (P, L)`` int32, each position's context WORDS,
    padded with -1; ``centres``, ``live (P,)``, ``negs (P, n)`` as
    ``cbow_step``. Returns ``(syn0, syn1, loss)``, the loss the mean over
    live positions."""
    inputs = jnp.where((bags >= 0)[..., None], groups[bags], -1)  # (P, L, G)
    return cbow_step(
        syn0, syn1, inputs.reshape(bags.shape[0], -1), centres, live, negs,
        alpha,
    )
