"""The plain reference of the CBOW configuration (``w2v-cbow-300-3m``).
Nothing here imports the program.

Continuous bag of words with negative sampling as ``word2vec.c`` trains it
(``-cbow 1``; Mikolov et al., arXiv:1301.3781, arXiv:1310.4546). For one
position with word ``w``, bag ``C`` and noise words ``n_k``::

    h      = (1/|C|) * sum_{c in C} syn0[w_c]
    g_pos  = alpha * (1 - sigmoid(h . syn1[w]))
    g_k    = -alpha * sigmoid(h . syn1[n_k])        (0 where n_k == w)
    syn1[w]   += g_pos * h ;  syn1[n_k] += g_k * h
    e      = g_pos * syn1[w] + sum_k g_k * syn1[n_k]
    syn0[w_c] += e          for every c in C: the WHOLE of e, not e / |C|

``cbow_step`` is that in numpy, a position at a time over frozen tables
(the transcription of the tool's loop body that the tests hold everything
else to); ``replay`` follows many steps in plain ``jax.numpy`` float32 at
``highest`` precision over the rows the steps touch: ``syn0``'s (the bags'
words) and ``syn1``'s (the positions' words and the negatives) are two
lists. One synchronous batch: every position of a step reads the tables as
they stood before it, and a row's shares are summed before they are added.
"""

import numpy as np

from benchmark.reference import seed_rows
from benchmark.reference_subword import _pad, table_gaps


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def cbow_step(syn0, syn1, bags, centres, live, negs, alpha):
    """One step, ``word2vec.c``'s loop body position by position with the
    tables frozen for the batch. ``bags (P, L)`` -1 padded, ``centres``,
    ``live (P,)``, ``negs (P, n)``. Returns (syn0, syn1, loss): new arrays,
    the loss the mean over the positions that trained."""
    d0 = np.zeros(syn0.shape, np.float64)
    d1 = np.zeros(syn1.shape, np.float64)
    loss, trained = 0.0, 0
    for p in range(centres.shape[0]):
        ctx = [c for c in bags[p] if c >= 0]
        if not live[p] or not ctx:  # cw == 0: the tool skips the position
            continue
        trained += 1
        word = centres[p]
        neu1 = sum(syn0[c].astype(np.float64) for c in ctx) / len(ctx)
        neu1e = np.zeros_like(neu1)
        for k, target in enumerate([word] + list(negs[p])):
            label = 1.0 if k == 0 else 0.0
            if k and target == word:
                continue
            f = float(neu1 @ syn1[target])
            g = (label - _sigmoid(f)) * alpha
            loss -= np.log(_sigmoid(f if k == 0 else -f))
            neu1e += g * syn1[target]
            d1[target] += g * neu1
        for c in ctx:
            d0[c] += neu1e  # undivided
    return ((syn0 + d0).astype(np.float32), (syn1 + d1).astype(np.float32),
            loss / max(trained, 1))


def touched_rows(batches):
    """(``syn0`` rows, ``syn1`` rows) the batches touch, each sorted and
    padded (``reference.touched_rows``'s rule)."""
    bags = np.unique(np.concatenate([b["bags"].reshape(-1) for b in batches]))
    rows1 = np.unique(np.concatenate([
        np.concatenate([b["centres"], b["negs"].reshape(-1)])
        for b in batches]))
    return _pad(bags[bags >= 0]), _pad(rows1)


def replay(syn0_rows, rows0: np.ndarray, rows1: np.ndarray, batches):
    """Follow ``batches`` from the seed's ``syn0`` restricted to ``rows0``
    and a zero ``syn1`` restricted to ``rows1``. Returns (syn0_rows,
    syn1_rows, [loss per step])."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def step(tables, b):
        syn0, syn1 = tables
        bags, centres, live, negs, alpha = b  # bags (P, L): index or -1
        w = (bags >= 0).astype(jnp.float32)
        rows = jnp.maximum(bags, 0)
        count = jnp.maximum(w.sum(axis=1, keepdims=True), 1.0)
        h = (syn0[rows] * w[..., None]).sum(axis=1) / count
        u_pos, u_neg = syn1[centres], syn1[negs]
        f_pos = jnp.einsum("pd,pd->p", h, u_pos, precision=hi)
        f_neg = jnp.einsum("pd,pnd->pn", h, u_neg, precision=hi)
        nmask = (negs != centres[:, None]).astype(jnp.float32) * live[:, None]
        g_pos = alpha * (1.0 - jax.nn.sigmoid(f_pos)) * live
        g_neg = -alpha * jax.nn.sigmoid(f_neg) * nmask
        loss = (-jax.nn.log_sigmoid(f_pos) * live - (
            jax.nn.log_sigmoid(-f_neg) * nmask).sum(axis=1)
        ).sum() / jnp.maximum(live.sum(), 1.0)
        e = g_pos[:, None] * u_pos + jnp.einsum(
            "pn,pnd->pd", g_neg, u_neg, precision=hi)
        d = h.shape[1]
        # A row's shares are summed among themselves and added to the row
        # once: added one by one each would be rounded at the row's size.
        syn1 = syn1 + jnp.zeros_like(syn1).at[centres].add(
            g_pos[:, None] * h).at[negs.reshape(-1)].add(
                (g_neg[:, :, None] * h[:, None, :]).reshape(-1, d))
        syn0 = syn0 + jnp.zeros_like(syn0).at[rows.reshape(-1)].add(
            (e[:, None, :] * w[..., None]).reshape(-1, d))
        return (syn0, syn1), loss

    def local(rows, ids):
        return np.where(ids >= 0, np.searchsorted(rows, ids), -1).astype(
            np.int32)

    stacked = (
        jnp.asarray(np.stack([local(rows0, b["bags"]) for b in batches])),
        jnp.asarray(np.stack([local(rows1, b["centres"]) for b in batches])),
        jnp.asarray(np.stack([np.asarray(b["live"], np.float32)
                              for b in batches])),
        jnp.asarray(np.stack([local(rows1, b["negs"]) for b in batches])),
        jnp.asarray(np.stack([np.float32(b["alpha"]) for b in batches])),
    )
    syn0 = jnp.asarray(syn0_rows, jnp.float32)
    syn1 = jnp.zeros((rows1.size, syn0.shape[1]), jnp.float32)
    (syn0, syn1), losses = jax.jit(
        lambda s0, s1, bs: jax.lax.scan(step, (s0, s1), bs))(
            syn0, syn1, stacked)
    return syn0, syn1, losses


def replay_gaps(seed, vocab, dim, rows0, rows1, batches, prog0, prog1,
                prog_losses, devices) -> dict:
    """The numbers of ``reference.replay_gaps``, under the same names:
    follow ``batches`` from the seed's rows and read how far the program's
    rows and losses lie from the reference's (change norms from per-row
    float32 sums put together in float64: ``reference_subword.table_gaps``)."""
    init0 = seed_rows(seed, vocab, dim, rows0, devices)
    ref0, ref1, ref_losses = replay(init0, rows0, rows1, batches)
    out = {}
    for name, prog, ref, init, rows in (("syn0", prog0, ref0, init0, rows0),
                                        ("syn1", prog1, ref1, None, rows1)):
        gap, dnorm = table_gaps(prog, ref, init, rows)
        out[f"replay.{name}_gap"] = gap
        out[f"replay.{name}_dnorm_gap"] = dnorm
    ref_losses = np.asarray(ref_losses, np.float32)
    out["replay.loss_gap"] = float(np.max(
        np.abs(np.asarray(prog_losses, np.float32) - ref_losses)
        / ref_losses))
    return out
