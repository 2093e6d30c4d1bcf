"""The plain reference of CBOW with position weights
(``ft-cbow-pw-300-1m-2mb``, and the word-level form with a group table of one
row a word), in the SOURCE's form. Nothing here imports the program, and
nothing of ``corpus/subword.py``.

The ``cc.<lang>.300`` recipe (Grave et al., LREC 2018, arXiv:1802.06893:
"CBOW with position-weights"), its weights as Mikolov et al., LREC 2018
(arXiv:1712.09405, section 2.2) give them, a vector ``d_p`` for each relative
position p of the window multiplied element-wise into the context word's
vector before the bag is summed, everything else the released ``fasttext
cbow`` (``benchmark/reference_cbow_subword.py``). A word ``w`` owns the group
``G(w)``: its own row and the rows its character n-grams hash to
(``reference_subword.group_table``). For one position with word ``w``, a bag
whose lane p (p in -ws..-1, 1..ws, the order of the position table's rows)
holds the word ``c_p`` or nothing, and noise words ``n_k``::

    I      = the concatenation over the live lanes p of (i, p) for i in
             G(c_p): a list of (row, lane) inputs
    h      = (1/|I|) * sum_{(i, p) in I} d_p * syn0[i]     (ONE mean over I)
    g_pos  = alpha * (1 - sigmoid(h . syn1[w]))
    g_k    = -alpha * sigmoid(h . syn1[n_k])               (0 where n_k == w)
    syn1[w]   += g_pos * h ;  syn1[n_k] += g_k * h
    e      = g_pos * syn1[w] + sum_k g_k * syn1[n_k]
    syn0[i]   += d_p * e          for every (i, p) in I: the WHOLE of e
    D_p       += syn0[i] * e      for every (i, p) in I, undivided too

``*`` is element-wise over the columns, ``d_p`` starts at ones, and one
synchronous batch stands where the tool's threads race: every position of a
step reads the three tables as they stood before it, and a row's shares are
summed before they are added. ``D_p`` is lane p's share of ONE position;
``d_p`` takes the MEAN of them over the step's positions whose lane p holds a
word, ``d_p += (sum of D_p) / (positions with lane p live)``, where every row
of ``syn0`` and ``syn1`` takes the sum of its shares. That is the
configuration's one departure (its file says why: the sum, some 4,400
coherent shares a lane a step, is not finite after 96 steps on the chip), and
the program's rule too.

``cbow_pw_step`` is that in numpy float64, a position at a time over frozen
tables (the transcription the tests hold everything else to); ``replay``
follows many steps in plain ``jax.numpy`` float32 at ``highest`` precision
over the rows the steps touch, forming ``I`` for every position and walking
it a slot at a time, as ``reference_cbow_subword.replay`` does, from the
seed's tables and a position table of ones (``replay_gaps``) and once more
from the tables those steps left and a position table well away from ones
(``seeded_posw``, ``seeded_gaps``: from ones the table moves too little in
a dispatch group for a bag without its weights to show). The program
never forms ``I``: it sums each word of a step's span once, lets the bags
read the sums through ten shifted weighted adds, and reduces the position
table's gradient a lane at a time.
"""

import numpy as np

from benchmark.reference import seed_rows
from benchmark.reference_cbow_subword import inputs_of, touched_rows  # noqa: F401
from benchmark.reference_subword import table_gaps


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def cbow_pw_step(syn0, syn1, posw, groups, bags, centres, live, negs, alpha):
    """One step, a position at a time with the tables frozen for the batch.
    ``bags (P, L)`` words, -1 padded, lane k the position table's row k;
    ``groups (V, G)`` rows, -1 padded. Returns (syn0, syn1, posw, loss): new
    float32 arrays, the loss the mean over the positions that trained."""
    d0 = np.zeros(syn0.shape, np.float64)
    d1 = np.zeros(syn1.shape, np.float64)
    dp = np.zeros(posw.shape, np.float64)
    positions = np.zeros((posw.shape[0], 1))  # with the lane live
    loss, trained = 0.0, 0
    for p in range(centres.shape[0]):
        inputs = [(r, k) for k, c in enumerate(bags[p]) if c >= 0
                  for r in groups[c] if r >= 0]
        if not live[p] or not inputs:
            continue
        trained += 1
        word = centres[p]
        hidden = sum(posw[k].astype(np.float64) * syn0[r]
                     for r, k in inputs) / len(inputs)
        grad = np.zeros_like(hidden)
        for k, target in enumerate([word] + list(negs[p])):
            if k and target == word:
                continue
            score = _sigmoid(float(hidden @ syn1[target]))
            a = alpha * ((1.0 if k == 0 else 0.0) - score)
            loss -= np.log(score if k == 0 else 1.0 - score)
            grad += a * syn1[target]
            d1[target] += a * hidden
        for r, k in inputs:
            d0[r] += posw[k] * grad
            dp[k] += syn0[r] * grad
        positions[sorted({k for _, k in inputs})] += 1
    return ((syn0 + d0).astype(np.float32), (syn1 + d1).astype(np.float32),
            (posw + dp / np.maximum(positions, 1)).astype(np.float32),
            loss / max(trained, 1))


def replay(syn0_rows, rows0: np.ndarray, rows1: np.ndarray,
           groups: np.ndarray, batches, syn1_rows=None, posw=None):
    """Follow ``batches`` from ``syn0_rows`` (``syn0`` restricted to
    ``rows0``), ``syn1_rows`` (restricted to ``rows1``; zeros where not
    given) and ``posw`` (ones where not given: its rows are the lanes of
    the batches' bags). Returns (syn0_rows, syn1_rows, posw, [loss per
    step])."""
    import jax
    import jax.numpy as jnp

    G = groups.shape[1]

    def step(tables, b):
        syn0, syn1, posw = tables
        inputs, centres, live, negs, alpha = b  # inputs (P, L * G): or -1
        w = (inputs >= 0).astype(jnp.float32)
        size = jnp.maximum(w.sum(axis=1, keepdims=True), 1.0)  # |I|
        width = inputs.shape[1]

        def row(s):
            return syn0[jnp.maximum(inputs[:, s], 0)] * w[:, s, None]

        def gather(s, acc):  # slot s came in by lane s // G
            return acc + posw[s // G] * row(s)

        h = jax.lax.fori_loop(
            0, width, gather,
            jnp.zeros((inputs.shape[0], syn0.shape[1]), jnp.float32)) / size
        u_pos, u_neg = syn1[centres], syn1[negs]
        f_pos = jnp.einsum("pd,pd->p", h, u_pos)
        f_neg = jnp.einsum("pd,pnd->pn", h, u_neg)
        nmask = (negs != centres[:, None]).astype(jnp.float32) * live[:, None]
        g_pos = alpha * (1.0 - jax.nn.sigmoid(f_pos)) * live
        g_neg = -alpha * jax.nn.sigmoid(f_neg) * nmask
        loss = (-jax.nn.log_sigmoid(f_pos) * live - (
            jax.nn.log_sigmoid(-f_neg) * nmask).sum(axis=1)
        ).sum() / jnp.maximum(live.sum(), 1.0)
        e = g_pos[:, None] * u_pos + jnp.einsum("pn,pnd->pd", g_neg, u_neg)
        d = h.shape[1]
        # A row's shares are summed among themselves and added to the row
        # once: added one by one each would be rounded at the row's size.
        new1 = syn1 + jnp.zeros_like(syn1).at[centres].add(
            g_pos[:, None] * h).at[negs.reshape(-1)].add(
                (g_neg[:, :, None] * h[:, None, :]).reshape(-1, d))

        def scatter(s, deltas):  # both from the tables before the step
            delta0, deltap = deltas
            return (
                delta0.at[jnp.maximum(inputs[:, s], 0)].add(
                    posw[s // G] * e * w[:, s, None]),
                deltap.at[s // G].add((row(s) * e).sum(axis=0)))

        delta0, deltap = jax.lax.fori_loop(
            0, width, scatter, (jnp.zeros_like(syn0), jnp.zeros_like(posw)))
        # the positions whose lane holds a word: the mean's denominator
        positions = w.reshape(-1, posw.shape[0], G).max(axis=2).sum(axis=0)
        return (syn0 + delta0, new1,
                posw + deltap / jnp.maximum(positions, 1.0)[:, None]), loss

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
    syn1 = (jnp.zeros((rows1.size, syn0.shape[1]), jnp.float32)
            if syn1_rows is None else jnp.asarray(syn1_rows, jnp.float32))
    lanes = batches[0]["bags"].shape[1]
    posw = (jnp.ones((lanes, syn0.shape[1]), jnp.float32)
            if posw is None else jnp.asarray(posw, jnp.float32))
    with jax.default_matmul_precision("highest"):
        (syn0, syn1, posw), losses = jax.jit(
            lambda t, bs: jax.lax.scan(step, t, bs))(
                (syn0, syn1, posw), stacked)
    return syn0, syn1, posw, losses


def seeded_posw(seed, lanes: int, dim: int) -> np.ndarray:
    """A position table well away from ones, ``(lanes, dim)`` float32 drawn
    U[0.5, 1.5) from the seed: what the SEEDED replay starts from. From
    ones the table moves by some 1e-4 in a dispatch group, so a bag that
    never meets its weights is the weighted one to 1e-4 of the rows' change;
    from here every bag's sum and every row's update carry their lane's
    factor, a half to one and a half, column by column."""
    rng = np.random.default_rng([int(seed), lanes, dim])
    return rng.uniform(0.5, 1.5, (lanes, dim)).astype(np.float32)


def _gaps(prefix, tables, prog_losses, ref_losses) -> dict:
    """``{prefix.<table>_gap, prefix.<table>_dnorm_gap, prefix.loss_gap}``
    of ``tables``: (name, program's rows, reference's, what both started
    from (None: zeros), the rows' ids)."""
    out = {}
    for name, prog, ref, init, rows in tables:
        gap, dnorm = table_gaps(np.asarray(prog, np.float32), ref, init, rows)
        out[f"{prefix}.{name}_gap"] = gap
        out[f"{prefix}.{name}_dnorm_gap"] = dnorm
    ref_losses = np.asarray(ref_losses, np.float32)
    out[f"{prefix}.loss_gap"] = float(np.max(
        np.abs(np.asarray(prog_losses, np.float32) - ref_losses)
        / ref_losses))
    return out


def replay_gaps(seed, table_rows, dim, rows0, rows1, groups, batches, prog0,
                prog1, prog_posw, prog_losses, devices) -> dict:
    """The numbers of ``reference_cbow_subword.replay_gaps`` and the position
    table's two: follow ``batches`` from the seed's rows (``table_rows`` =
    vocabulary + buckets) and a position table of ones, and read how far the
    program's rows, its position table ``prog_posw (L, dim)`` and its losses
    lie from the reference's. ``replay.posw_gap`` is the largest entry gap
    over the reference's largest change from ones, and 1.0 for a table the
    program never trained."""
    init0 = seed_rows(seed, table_rows, dim, rows0, devices)
    ref0, ref1, refp, ref_losses = replay(init0, rows0, rows1, groups, batches)
    return _gaps("replay", (
        ("syn0", prog0, ref0, init0, rows0),
        ("syn1", prog1, ref1, None, rows1),
        ("posw", prog_posw, refp, np.ones(refp.shape, np.float32),
         np.arange(refp.shape[0]))), prog_losses, ref_losses)


def seeded_gaps(rows0, rows1, groups, batches, start, prog, prog_losses
                ) -> dict:
    """The same numbers under ``seeded.``: follow ``batches`` from ``start``
    = (``syn0``'s rows, ``syn1``'s rows, the position table) as the program
    held them when it ran the batches once more, the table then
    ``seeded_posw``'s, and read how far ``prog``, the program's three after
    them, lies from the reference's. Every gap is over the reference's
    largest change from ``start``."""
    ref0, ref1, refp, ref_losses = replay(
        start[0], rows0, rows1, groups, batches, syn1_rows=start[1],
        posw=start[2])
    return _gaps("seeded", (
        ("syn0", prog[0], ref0, start[0], rows0),
        ("syn1", prog[1], ref1, start[1], rows1),
        ("posw", prog[2], refp, np.asarray(start[2], np.float32),
         np.arange(refp.shape[0]))), prog_losses, ref_losses)
