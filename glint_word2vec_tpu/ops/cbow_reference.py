"""The plain reference of the CBOW step.

One synchronous-batch step of continuous bag of words with negative
sampling as ``word2vec.c`` trains it (``-cbow 1 -negative n``; Mikolov et
al., arXiv:1301.3781 and arXiv:1310.4546). Straightforward ``jax.numpy``
float32, contractions at ``highest`` precision, every update computed from
the pre-step rows and a row's shares summed before they are added; nothing
here is shared with the engine's step (``parallel/engine.py``), which the
tests hold to it (``tests/test_cbow.py``, where a numpy transcription of
``word2vec.c``'s loop body, run position by position with the tables
frozen for the batch, holds this file in turn).

For one position with word ``w``, bag ``C`` (the positions within
``window - b`` of it in its sentence, ``b`` drawn in ``[0, window)``;
``|C| = 0`` is skipped) and noise words ``n_k``::

    h      = (1/|C|) * sum_{c in C} syn0[w_c]
    g_pos  = alpha * (1 - sigmoid(h . syn1[w]))
    g_k    = -alpha * sigmoid(h . syn1[n_k])        (0 where n_k == w)
    syn1[w]   += g_pos * h ;  syn1[n_k] += g_k * h
    e      = g_pos * syn1[w] + sum_k g_k * syn1[n_k]
    syn0[w_c] += e                                  for every c in C

**The gradient is not divided by |C|.** ``word2vec.c`` (and gensim's
``cbow_mean=1``) add the whole ``neu1e`` to every context word, and the
tool's ``alpha = 0.05`` for this architecture is tuned to that. (The
grouped step of the subword family divides by the group's count,
``ops/grouped_reference.py``; here the source's rule is the model.)

Departures from ``word2vec.c``:

* **One synchronous batch, not Hogwild.** Every position of a step reads
  the tables as they stood before the step, and a row's updates are
  summed; the tool's threads update in place, position after position,
  racing.
* **The exact sigmoid**, not the tool's 1,000-entry table clipped at
  +-6 (beyond which the tool makes no update at all).
* **Alias sampling** from the unigram^0.75 distribution, not the tool's
  1e8-entry unigram table; a noise word equal to the position's word is
  skipped, as in the tool.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def cbow_step(syn0, syn1, bags, centres, live, negs, alpha):
    """One step over P positions. ``bags (P, L)`` int32, each position's
    context words, padded with -1; ``centres (P,)`` the positions' own
    words; ``live (P,)`` 1.0 where the position trains; ``negs (P, n)``.
    Returns ``(syn0, syn1, loss)``, the loss the mean over live positions."""
    w = (bags >= 0).astype(jnp.float32)  # (P, L)
    rows = jnp.where(bags >= 0, bags, 0)
    count = jnp.maximum(w.sum(axis=1, keepdims=True), 1.0)  # (P, 1)
    h = (syn0[rows] * w[..., None]).sum(axis=1) / count  # (P, d)
    u_pos, u_neg = syn1[centres], syn1[negs]
    f_pos = jnp.einsum("pd,pd->p", h, u_pos, precision=_HI)
    f_neg = jnp.einsum("pd,pnd->pn", h, u_neg, precision=_HI)
    nmask = (negs != centres[:, None]).astype(jnp.float32) * live[:, None]
    g_pos = alpha * (1.0 - jax.nn.sigmoid(f_pos)) * live
    g_neg = -alpha * jax.nn.sigmoid(f_neg) * nmask
    position_loss = -jax.nn.log_sigmoid(f_pos) * live - (
        jax.nn.log_sigmoid(-f_neg) * nmask).sum(axis=1)
    loss = position_loss.sum() / jnp.maximum(live.sum(), 1.0)
    e = g_pos[:, None] * u_pos + jnp.einsum(
        "pn,pnd->pd", g_neg, u_neg, precision=_HI)
    # A row's shares are summed among themselves, then added to the row
    # once: each is small beside the row's own entries, and added one by
    # one would be rounded at the row's size.
    d = h.shape[1]
    syn1 = syn1 + jnp.zeros_like(syn1).at[centres].add(
        g_pos[:, None] * h).at[negs.reshape(-1)].add(
            (g_neg[:, :, None] * h[:, None, :]).reshape(-1, d))
    share = e[:, None, :] * w[..., None]  # (P, L, d): the whole of e
    syn0 = syn0 + jnp.zeros_like(syn0).at[rows.reshape(-1)].add(
        share.reshape(-1, d))
    return syn0, syn1, loss
