"""The plain reference of the grouped (subword) SGNS step.

One synchronous-batch step of skip-gram with negative sampling in which a
pair's centre is not one ``syn0`` row but the mean of a GROUP of them: the
word's own row and its hashed character n-gram rows (fastText; Bojanowski
et al., TACL 2017). Straightforward ``jax.numpy`` float32, contractions at
``highest`` precision, every update computed from the pre-step rows and
duplicates summed; nothing here is shared with the engine's step
(``parallel/engine.py``), which the tests hold to it
(``tests/test_subword_packed.py``, where a numpy transcription in the
manner of ``tests/test_sgns.py::_numpy_oracle`` holds this file in turn).

For a pair with centre group ``g`` (``m`` live rows), context ``c`` and
negatives ``n_k``::

    h      = (1/m) * sum_{r in g} syn0[r]
    c_pos  = alpha * (1 - sigmoid(h . syn1[c]))
    c_neg  = -alpha * sigmoid(h . syn1[n_k])        (0 where n_k == c)
    syn1[c]   += c_pos * h ;  syn1[n_k] += c_neg_k * h
    d      = c_pos * syn1[c] + sum_k c_neg_k * syn1[n_k]
    syn0[r]   += d / m                              for every r in g

Departures from fastText's ``Model::update`` / ``FastText::skipgram``:

* **The true gradient of the mean.** fastText averages the input rows but
  adds the UNDIVIDED gradient ``d`` to every one of them; here each row
  gets ``d / m``, the derivative of the mean it entered (the engine's
  rule since the family was added, ``step_body_rows``).
* **One synchronous batch, not Hogwild.** Every pair of a step reads the
  tables as they stood before the step, and a row's updates are summed;
  fastText's threads update in place, racing.
* **Groups are cut at ``max_subwords`` (32).** A word of ten letters and
  more has more n-grams of 3 to 6 characters than that; fastText keeps
  them all. The cut keeps the word's own row and the first n-grams in
  order of length, then start (``corpus/subword.subword_group``).
* Negatives are drawn a pair from the unigram table (as fastText does),
  and one equal to its positive context is skipped.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def grouped_sgns_step(syn0, syn1, groups, contexts, mask, negs, alpha):
    """One step over P pairs. ``groups (P, G)`` int32, each pair's centre
    group, padded with -1; ``contexts``, ``mask (P,)``; ``negs (P, n)``.
    Returns ``(syn0, syn1, loss)``, the loss the masked mean over pairs."""
    live = groups >= 0  # (P, G)
    rows = jnp.where(live, groups, 0)
    w = live.astype(jnp.float32)
    m = jnp.maximum(w.sum(axis=1, keepdims=True), 1.0)  # (P, 1)
    h = (syn0[rows] * w[..., None]).sum(axis=1) / m  # (P, d)
    u_pos, u_neg = syn1[contexts], syn1[negs]
    f_pos = jnp.einsum("pd,pd->p", h, u_pos, precision=_HI)
    f_neg = jnp.einsum("pd,pnd->pn", h, u_neg, precision=_HI)
    nmask = (negs != contexts[:, None]).astype(jnp.float32) * mask[:, None]
    c_pos = alpha * (1.0 - jax.nn.sigmoid(f_pos)) * mask
    c_neg = -alpha * jax.nn.sigmoid(f_neg) * nmask
    pair_loss = -jax.nn.log_sigmoid(f_pos) * mask - (
        jax.nn.log_sigmoid(-f_neg) * nmask).sum(axis=1) * mask
    loss = pair_loss.sum() / jnp.maximum(mask.sum(), 1.0)
    d_center = c_pos[:, None] * u_pos + jnp.einsum(
        "pn,pnd->pd", c_neg, u_neg, precision=_HI)
    syn1 = syn1.at[contexts].add(c_pos[:, None] * h)
    syn1 = syn1.at[negs.reshape(-1)].add(
        (c_neg[:, :, None] * h[:, None, :]).reshape(-1, h.shape[1]))
    share = (d_center / m)[:, None, :] * w[..., None]  # (P, G, d)
    # A row's shares are summed among themselves, then added to the row
    # once: each is small beside the row's own entries, and added one by
    # one would be rounded at the row's size.
    syn0 = syn0 + jnp.zeros_like(syn0).at[rows.reshape(-1)].add(
        share.reshape(-1, h.shape[1]))
    return syn0, syn1, loss
