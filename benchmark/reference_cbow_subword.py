"""The plain reference of the subword CBOW configuration
(``ft-cbow-300-1m-2mb``), in the SOURCE's form. Nothing here imports the
program, and nothing of ``corpus/subword.py``.

The public ``fasttext cbow`` command (facebookresearch/fastText:
``FastText::cbow``, ``Model::computeHidden`` / ``Model::update``,
``NegativeSamplingLoss``). A word ``w`` owns the group ``G(w)``: its own row
and the rows its character n-grams hash to (``reference_subword.group_table``:
this benchmark's own cutter and FNV-1a). For one position with word ``w``,
bag ``C`` and noise words ``n_k``::

    I      = the concatenation of G(w_c) over c in C  (a row two words of
             the bag share is in it twice)
    h      = (1/|I|) * sum_{i in I} syn0[i]           (ONE mean over I)
    g_pos  = alpha * (1 - sigmoid(h . syn1[w]))
    g_k    = -alpha * sigmoid(h . syn1[n_k])          (0 where n_k == w)
    syn1[w]   += g_pos * h ;  syn1[n_k] += g_k * h
    e      = g_pos * syn1[w] + sum_k g_k * syn1[n_k]
    syn0[i]   += e     for every i in I, once each time it is in I: the
                       WHOLE of e, not e / |I|

``cbow_subword_step`` is that in numpy, a position at a time over frozen
tables (the transcription of the tool's loop that the tests hold everything
else to); ``replay`` follows many steps in plain ``jax.numpy`` float32 at
``highest`` precision over the rows the steps touch. It forms ``I`` for
every position, as the tool does, and walks it a slot at a time (``2 *
window * max_subwords`` gathers and scatter-adds of P rows), so that no (P x
|I| x d) block is ever formed. The program never forms ``I``: it sums each
word of a step's span once and lets the bags read the sums. One synchronous
batch: every position of a step reads the tables as they stood before it,
and a row's shares are summed before they are added.
"""

import numpy as np

from benchmark.reference import seed_rows
from benchmark.reference_subword import _pad, table_gaps


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def inputs_of(bags: np.ndarray, groups: np.ndarray) -> np.ndarray:
    """``(P, L * G)``: each position's concatenated input I, the groups of
    its bag's words one after the other, -1 where a lane or a group slot
    is empty."""
    rows = np.where((bags >= 0)[..., None], groups[np.maximum(bags, 0)], -1)
    return rows.reshape(bags.shape[0], -1)


def cbow_subword_step(syn0, syn1, groups, bags, centres, live, negs, alpha):
    """One step, ``FastText::cbow`` + ``Model::update`` position by
    position with the tables frozen for the batch. ``bags (P, L)`` words,
    -1 padded; ``groups (V, G)`` rows, -1 padded. Returns (syn0, syn1,
    loss): new arrays, the loss the mean over the positions that
    trained."""
    d0 = np.zeros(syn0.shape, np.float64)
    d1 = np.zeros(syn1.shape, np.float64)
    loss, trained = 0.0, 0
    for p in range(centres.shape[0]):
        bow = [r for c in bags[p] if c >= 0 for r in groups[c] if r >= 0]
        if not live[p] or not bow:  # input.size() == 0: no update
            continue
        trained += 1
        word = centres[p]
        hidden = sum(syn0[r].astype(np.float64) for r in bow) / len(bow)
        grad = np.zeros_like(hidden)
        for k, target in enumerate([word] + list(negs[p])):
            if k and target == word:
                continue
            score = _sigmoid(float(hidden @ syn1[target]))
            a = alpha * ((1.0 if k == 0 else 0.0) - score)
            loss -= np.log(score if k == 0 else 1.0 - score)
            grad += a * syn1[target]
            d1[target] += a * hidden
        for r in bow:
            d0[r] += grad  # undivided, once each time r is in the input
    return ((syn0 + d0).astype(np.float32), (syn1 + d1).astype(np.float32),
            loss / max(trained, 1))


def touched_rows(batches, groups: np.ndarray):
    """(``syn0`` rows, ``syn1`` rows) the batches touch, each sorted and
    padded (``reference.touched_rows``'s rule)."""
    words = np.unique(np.concatenate([b["bags"].reshape(-1)
                                      for b in batches]))
    ids = np.unique(groups[words[words >= 0]])
    rows1 = np.unique(np.concatenate([
        np.concatenate([b["centres"], b["negs"].reshape(-1)])
        for b in batches]))
    return _pad(ids[ids >= 0]), _pad(rows1)


def replay(syn0_rows, rows0: np.ndarray, rows1: np.ndarray,
           groups: np.ndarray, batches):
    """Follow ``batches`` from the seed's ``syn0`` restricted to ``rows0``
    and a zero ``syn1`` restricted to ``rows1``. Returns (syn0_rows,
    syn1_rows, [loss per step])."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST

    def step(tables, b):
        syn0, syn1 = tables
        inputs, centres, live, negs, alpha = b  # inputs (P, |I|): or -1
        w = (inputs >= 0).astype(jnp.float32)
        size = jnp.maximum(w.sum(axis=1, keepdims=True), 1.0)  # |I|
        width = inputs.shape[1]

        def gather(s, acc):
            return acc + syn0[jnp.maximum(inputs[:, s], 0)] * w[:, s, None]

        h = jax.lax.fori_loop(
            0, width, gather,
            jnp.zeros((inputs.shape[0], syn0.shape[1]), jnp.float32)) / size
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

        def scatter(s, delta):
            return delta.at[jnp.maximum(inputs[:, s], 0)].add(
                e * w[:, s, None])

        syn0 = syn0 + jax.lax.fori_loop(
            0, width, scatter, jnp.zeros_like(syn0))
        return (syn0, syn1), loss

    def local(rows, ids):
        return np.where(ids >= 0, np.searchsorted(rows, ids), -1).astype(
            np.int32)

    stacked = (
        jnp.asarray(np.stack([local(rows0, inputs_of(b["bags"], groups))
                              for b in batches])),
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


def replay_gaps(seed, table_rows, dim, rows0, rows1, groups, batches, prog0,
                prog1, prog_losses, devices) -> dict:
    """The numbers of ``reference.replay_gaps``, under the same names:
    follow ``batches`` from the seed's rows (``table_rows`` = vocabulary +
    buckets: the engine draws one table over both) and read how far the
    program's rows and losses lie from the reference's (change norms from
    per-row float32 sums put together in float64:
    ``reference_subword.table_gaps``)."""
    init0 = seed_rows(seed, table_rows, dim, rows0, devices)
    ref0, ref1, ref_losses = replay(init0, rows0, rows1, groups, batches)
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
