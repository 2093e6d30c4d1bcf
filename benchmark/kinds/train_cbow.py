"""Cell kind ``train_cbow``: the ``train`` kind for word2vec's CBOW
architecture: one ``Word2Vec(architecture="cbow", ...).fit_file`` job (what
``cli train --architecture cbow`` calls) over the seeded corpus of the
cell's traffic file (``benchmark/corpus.py``).

The same job, set-up, window and comparisons as ``kinds/train.py``, whose
``Seam`` and ``table_rows`` it imports (that file's docstring says what each
takes from the program). What differs:

* The estimator is built FIRST, before the corpus is written, with the
  architecture named: a program that has no such parameter fails there, at
  once, and is never timed as a skip-gram.
* A step trains POSITIONS, each with its bag of context words, so the
  replayed batches are redrawn here (``capture_bags``) with the program's bag
  function and sampler (``bag_window_batch``, ``device_words_done``,
  ``sample_negatives_per_row``; no independent code can repeat the device's
  draws), from the replay engine's corpus buffers and the view's per-position
  record.
* The replay is followed by ``benchmark/reference_cbow.py`` (the CBOW
  equations: the bag's mean predicts the position's word, every row of the
  bag takes the whole gradient). ``syn0``'s touched rows (the bags' words)
  and ``syn1``'s (the positions' words and the negatives) are read apart.
* A wrong bag would be fed to both sides of that comparison, so the bags
  are held to ``enumerate_bags`` first: the window rule in numpy, from the
  view's words, its sentence OFFSETS and the shrink draws read back, with
  nothing of ``bag_window_batch``, its slices or the view's per-position
  record. The redrawn bags must equal it lane for lane
  (``bags.lanes_differing``), and so must what the TIMED program counted on
  its device in the same steps, live bag slots and positions trained
  (``bags.counts_differing``).

Taken from the program besides what ``kinds/train.py`` takes:
``Word2Vec(architecture=...)``; ``bag_window_batch``;
``grid_window_shrink``; ``engine._compacted_sent``; columns 4 and 5 of
``train_steps_corpus_packed``'s fifth output (live bag slots, positions
trained, a step); ``training_metrics.cbow_rows_per_bag`` / ``.pipeline``.
"""

import math
import os
import time

import numpy as np


def _estimator(cfg, seed, epochs, obs=None, dtype=None):
    from glint_word2vec_tpu import Word2Vec

    m, r = cfg["model"], cfg["run"]
    return Word2Vec(
        architecture=m["architecture"],
        obs=obs, vector_size=m["vector_size"], window=m["window"],
        num_negatives=m["negatives"], step_size=m["step_size"],
        subsample_ratio=m["subsample_ratio"], min_count=m["min_count"],
        batch_size=r["batch_size"], steps_per_call=r["steps_per_call"],
        num_shards=r["num_shards"], num_iterations=int(epochs),
        seed=int(seed), dtype=dtype or m["table_dtype"],
    )


def _view(engine):
    """The view the scan trains over: (words, sentence offsets, the
    per-position record, live words)."""
    if engine._corpus_compacted is not None:
        return (*engine._corpus_compacted, engine._compacted_sent,
                engine._n_kept)
    # a fit without subsampling
    return (*engine._corpus, engine._corpus_sent, engine._corpus_n_valid)


def capture_bags(engine, cfg, seed, n_steps, total_words):
    """The batches of the first ``n_steps`` steps of epoch 0, as the CBOW
    packed scan's body draws them: step i trains the B positions from
    i * B of the active view; shrink draws pinned to the grid mapping,
    ``fold_in(base_key, step)`` keys, negatives keyed by global position
    row, alpha from the consumed position. Returns a list of dicts of numpy
    arrays: centres (B,), bags (B, 2 * window) with -1 where a lane is not
    in the bag, live (B,), negs (B, n), alpha, and shrink (B,), the draw of
    each position by itself (``grid_window_shrink``)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from glint_word2vec_tpu.ops.device_batching import (
        bag_window_batch,
        device_words_done,
        grid_window_shrink,
    )
    from glint_word2vec_tpu.ops.sampling import sample_negatives_per_row

    m, r = cfg["model"], cfg["run"]
    W, B, n = m["window"], r["batch_size"], m["negatives"]
    ids, soffs, sent_of, n_valid = _view(engine)
    orig_offs = engine._corpus[1]
    base_key = jax.random.PRNGKey(int(seed))
    step_size = jnp.float32(m["step_size"])
    inv_total = jnp.float32(1.0 / float(total_words))
    rows = jnp.arange(B, dtype=jnp.int32)

    @jax.jit  # the key is an argument: a constant would compile per seed
    def draw(base_key, ids, sent_of, soffs, orig_offs, n_valid, prob, alias):
        def body(pos, i):
            key = jax.random.fold_in(base_key, jnp.uint32(0) + i)
            centres, bags, _, live = bag_window_batch(
                ids, sent_of, pos, base_key, jnp.uint32(0), window=W,
                batch=B, grid_batch=B, n_valid=n_valid,
            )
            pos_end = pos + B
            done = device_words_done(orig_offs, soffs, pos_end, n_valid)
            alpha = jnp.maximum(
                step_size * (1.0 - done.astype(jnp.float32) * inv_total),
                step_size * 1e-4)
            negs = sample_negatives_per_row(key, prob, alias, rows, (1, n))
            shrink = grid_window_shrink(
                base_key, pos + rows, B, jnp.uint32(0), W)
            return pos_end, (centres, bags, live, negs[:, 0, :], alpha,
                             shrink)

        return lax.scan(body, jnp.int32(0),
                        jnp.arange(n_steps, dtype=jnp.uint32))[1]

    centres, bags, live, negs, alphas, shrink = (
        np.asarray(a) for a in draw(
            base_key, ids, sent_of, soffs, orig_offs, jnp.int32(n_valid),
            engine._prob, engine._alias))
    return [
        {"centres": centres[i], "bags": bags[i], "live": live[i],
         "negs": negs[i], "alpha": alphas[i], "shrink": shrink[i]}
        for i in range(n_steps)
    ]


def enumerate_bags(words, soffs, n_valid, shrink, window):
    """``word2vec.c``'s window in numpy over the first ``shrink.size``
    positions of a view: position t with draw b takes every position
    within ``window - b`` of it, itself left out, that lies inside the
    view's ``n_valid`` words and in t's sentence, a sentence being what
    lies between two of the view's offsets ``soffs``. ``words`` is the
    view's head, ``shrink.size + window`` long. Returns (centres (n,),
    bags (n, 2 * window)) in ``bag_window_batch``'s form: word 0 for a
    position outside the view, -1 for a lane not in the bag."""
    n, W = shrink.size, int(window)
    t = np.arange(n)[:, None]
    offs = np.array([o for o in range(-W, W + 1) if o])[None, :]
    q = t + offs
    sent = np.searchsorted(soffs, np.arange(n + W), side="right")
    at = np.clip(q, 0, n + W - 1)
    valid = ((np.abs(offs) <= W - shrink[:, None])
             & (q >= 0) & (q < n_valid) & (t < n_valid)
             & (sent[at] == sent[t]))
    return (np.where(t[:, 0] < n_valid, words[:n], 0),
            np.where(valid, words[at], -1))


def bag_faults(engine, batches, window, counted):
    """(lanes differing, steps differing): how far the redrawn ``batches``
    lie from ``enumerate_bags``, entry by entry, and in how many of the
    steps the program ``counted`` (rows of live bag slots, positions
    trained: its own device's count of the last steps of ``batches``) it
    counted another bag than the enumeration holds."""
    ids, soffs, _, n_valid = _view(engine)
    shrink = np.concatenate([b["shrink"] for b in batches])
    B = batches[0]["shrink"].size
    words = np.zeros(shrink.size + window, np.int32)
    head = np.asarray(ids[:words.size])
    words[:head.size] = head
    centres, bags = enumerate_bags(
        words, np.asarray(soffs), int(n_valid), shrink, window)
    lanes = sum(
        int((b["centres"] != centres[i * B:(i + 1) * B]).sum())
        + int((b["bags"] != bags[i * B:(i + 1) * B]).sum())
        for i, b in enumerate(batches))
    per_step = (bags >= 0).reshape(len(batches), B, -1)
    mine = np.stack([per_step.sum(axis=(1, 2)),
                     per_step.any(axis=2).sum(axis=1)], axis=1)
    counted = np.asarray(counted).reshape(-1, 2)
    steps = int((counted != mine[-counted.shape[0]:]).any(axis=1).sum())
    return lanes, steps


def run(ctx):
    import jax

    from benchmark import corpus as corpus_mod
    from benchmark import reference_cbow as reference
    from benchmark.kinds.train import Seam, table_rows

    cfg, traffic, args = ctx.cfg, ctx.traffic, ctx.args
    m, r = cfg["model"], cfg["run"]
    prog_seed = int(args.seed) % (2**31 - 1)
    _estimator(cfg, prog_seed, 1)  # a program without the architecture: out
    corpus = os.path.join(ctx.work, "corpus.txt")
    t0 = time.perf_counter()
    n_tokens = corpus_mod.make_corpus(corpus, m["vocab"], traffic, args.seed)
    ctx.say(f"corpus: {n_tokens} tokens, vocabulary {m['vocab']}, "
            f"{os.path.getsize(corpus) >> 20} MiB in "
            f"{time.perf_counter() - t0:.2f}s")

    seam = Seam()
    seam.install()
    K = int(traffic["replay_groups"]) * r["steps_per_call"]
    epochs = max(1, round(
        float(args.seconds) * traffic["nominal_words_per_s"] / n_tokens))
    dtype = ctx.table_dtype  # None, or "bfloat16" for the control
    try:
        # -- set-up: the replay fit (also the warm-up) ------------------
        seam.phase = "replay"
        os.environ["GLINT_PACKED_STOP_AFTER_GROUPS"] = str(
            traffic["replay_groups"])
        try:
            replay = _estimator(
                cfg, prog_seed, epochs, dtype=dtype).fit_file(corpus)
        finally:
            os.environ.pop("GLINT_PACKED_STOP_AFTER_GROUPS", None)
        eng = replay.engine
        ctx.device = ctx.device_of(eng)
        ctx.say(f"replay fit: {len(seam.first_losses)} group(s), step body "
                f"{replay.training_metrics.get('step_body')}, pipeline "
                f"{replay.training_metrics.get('pipeline')}, "
                f"{time.perf_counter() - ctx.t_start:.2f}s since start")
        # -- the check's own reads (not set-up: taken off setup_s) ------
        t_check = time.perf_counter()
        prog_losses = np.concatenate(
            [np.asarray(x, np.float32) for x in seam.first_losses])[:K]
        total_words = epochs * replay.vocab.train_words_count + 1
        batches = capture_bags(eng, cfg, prog_seed, K, total_words)
        lanes_off, counts_off = bag_faults(
            eng, batches, m["window"],
            np.asarray(seam.last_call[2][4])[:, 4:6])
        rows0, rows1 = reference.touched_rows(batches)
        d = m["vector_size"]
        prog0 = table_rows(eng.syn0, rows0)[:, :d]
        prog1 = table_rows(eng.syn1, rows1)[:, :d]
        devices = sorted(eng.syn0.sharding.device_set, key=lambda x: x.id)
        ctx.check_seconds += time.perf_counter() - t_check
        # The window's dispatches pass their start as a device scalar, the
        # stopped fit a host integer: another program to jit. Load it now.
        seam.phase = "warm"
        a, k, out = seam.last_call
        jax.block_until_ready(
            eng.train_steps_corpus_packed(out[2][-1], *a[1:], **k))
        seam.last_call = None
        replay.stop()
        del replay, eng
        ctx.say(f"check reads: {rows0.size} syn0 and {rows1.size} syn1 "
                f"touched rows of {m['vocab']}, "
                f"{ctx.check_seconds:.2f}s (not counted in setup_s)")

        # -- the window -------------------------------------------------
        obs = None
        if args.trace:
            from glint_word2vec_tpu.obs import ObsConfig

            ctx.program_spans_path = os.path.join(ctx.work, "spans.json")
            obs = ObsConfig(chrome_trace=ctx.program_spans_path)
            seam.trace = tuple(traffic["trace_groups"])
            seam.trace_dir = ctx.trace_dir
        seam.phase = "window"
        with ctx.count_compiles() as compiles:
            model = _estimator(
                cfg, prog_seed, epochs, obs=obs, dtype=dtype).fit_file(corpus)
            t_end = time.perf_counter()
        seam.stop_trace()
        ctx.trace_t = seam.trace_t
        seam.phase = "after"
        ctx.window = (seam.window_t0, t_end)
        ctx.memory_peak_bytes = ctx.read_memory_peak()
        tm = ctx.training_metrics = model.training_metrics
        model.stop()
        del model
    finally:
        seam.uninstall()

    window_s = ctx.window[1] - ctx.window[0]
    words = n_tokens * epochs
    ctx.say(f"window: {epochs} epoch(s), {words} words in {window_s:.3f}s, "
            f"{seam.window_dispatches} dispatch groups; program says "
            f"words_done={tm['words_done']} steps={tm['steps']} "
            f"loss {tm['first_loss']} -> {tm['final_loss']}, "
            f"{tm.get('cbow_rows_per_bag')} rows a bag")

    # -- the reference, once the window has closed ----------------------
    t_ref = time.perf_counter()
    gaps = reference.replay_gaps(
        prog_seed, m["vocab"], d, rows0, rows1, batches, prog0, prog1,
        prog_losses, devices)
    ctx.say(f"cbow reference: {K} steps over {rows0.size} + {rows1.size} "
            f"rows, compared in {time.perf_counter() - t_ref:.2f}s")
    lim = traffic["limits"]
    numbers = [(name, float(gaps[name]), float(lim[name]))
               for name in sorted(gaps)]
    first, final = tm["first_loss"], tm["final_loss"]
    ok_loss = (first is not None and final is not None
               and math.isfinite(first) and math.isfinite(final))
    in_window = [(t, e) for t, e in compiles if t >= ctx.window[0]]
    for when, event in in_window:
        ctx.say(f"compiled in the window at +{when - ctx.window[0]:.3f}s:"
                f" {event}")
    numbers += [
        ("bags.lanes_differing", float(lanes_off), 0.0),
        ("bags.counts_differing", float(counts_off), 0.0),
        ("window.final_over_first_loss",
         final / first if ok_loss else float("inf"),
         float(lim["window.final_over_first_loss"])),
        ("window.words_not_trained", float(abs(tm["words_done"] - words)), 0.0),
        ("window.compiles", float(len(in_window)), 0.0),
        ("tables.devices_missing",
         float(max(0, ctx.cell["chips"] - len(devices))), 0.0),
    ]

    ctx.numbers = numbers
    ctx.attempted = seam.window_dispatches
    ctx.failed = 0
    ctx.end_to_end = {"train_words_per_s": words / window_s}
    ctx.notes = {"epochs": epochs, "words": words, "window_s": window_s,
                 "steps": tm["steps"]}
