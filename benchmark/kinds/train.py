"""Cell kind ``train``: one ``Word2Vec.fit_file`` job (what ``cli train``
calls) over the seeded corpus of the cell's traffic file.

Set-up writes the corpus and runs the REPLAY fit: the same ``fit_file`` on
the same file, stopped by the program's own hook after the first dispatch
group(s). It loads every program the window drives (same shapes) and leaves
the tables the timed programs produce from the seed's tables in their first
K steps, which the numpy reference follows once the window has closed.
The window is a second ``fit_file`` over the same file, ``epochs`` whole
epochs, run to its natural end; it starts at the fit's first device work (the
first epoch's subsample-compact pass, or the first dispatch where a fit has
no such pass) and ends when ``fit_file`` returns, which is after the last
harvested read-back.

What is taken from the program besides its entry point (the seam; PERF.md
lists it): ``EmbeddingEngine.compact_corpus`` and
``.train_steps_corpus_packed`` are wrapped with the benchmark's own spans;
the batches the replayed steps trained on are drawn once more with the
program's batcher and sampler (``pack_window_pairs``, ``device_words_done``,
``sample_negatives_per_row``) from the replay engine's corpus buffers, since
no independent code can repeat the device's random draws.
"""

import math
import os
import time

import numpy as np


class Seam:
    """The benchmark's own spans around the calls into the engine."""

    def __init__(self):
        self.phase = "setup"
        self.window_t0 = None  # the window's first call into the engine
        self.first_losses = []  # device arrays of the replay fit's groups
        self.trace = None  # (first, last) dispatch index to profile between
        self.trace_dir = None
        self.trace_t = None  # (t_start, t_stop) perf_counter
        self.window_dispatches = 0
        self.last_call = None  # (args, kwargs, result) of the replay's last

    def install(self):
        import jax
        from glint_word2vec_tpu.parallel.engine import EmbeddingEngine

        seam = self
        orig_packed = EmbeddingEngine.train_steps_corpus_packed
        orig_compact = EmbeddingEngine.compact_corpus

        def packed(engine, *a, **k):
            if seam.phase == "window":
                if seam.window_t0 is None:  # a fit with no compaction pass
                    seam.window_t0 = time.perf_counter()
                n = seam.window_dispatches
                seam.window_dispatches += 1
                if seam.trace and n == seam.trace[0]:
                    jax.profiler.start_trace(seam.trace_dir)
                    seam.trace_t = [time.perf_counter(), None]
                if seam.trace and n == seam.trace[1] and seam.trace_t:
                    seam.stop_trace()
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = orig_packed(engine, *a, **k)
            if seam.phase == "replay":
                seam.first_losses.append(out[0])
                seam.last_call = (a, k, out)
            return out

        def compact(engine, *a, **k):
            if seam.phase == "window" and seam.window_t0 is None:
                seam.window_t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.compact"):
                return orig_compact(engine, *a, **k)

        EmbeddingEngine.train_steps_corpus_packed = packed
        EmbeddingEngine.compact_corpus = compact
        self._restore = (EmbeddingEngine, orig_packed, orig_compact)

    def stop_trace(self):
        import jax

        if self.trace_t and self.trace_t[1] is None:
            self.trace_t[1] = time.perf_counter()
            jax.profiler.stop_trace()

    def uninstall(self):
        cls, packed, compact = self._restore
        cls.train_steps_corpus_packed = packed
        cls.compact_corpus = compact

def _fit(cfg, corpus, seed, epochs, obs=None, dtype=None):
    from glint_word2vec_tpu import Word2Vec

    m, r = cfg["model"], cfg["run"]
    est = Word2Vec(
        obs=obs, vector_size=m["vector_size"], window=m["window"],
        num_negatives=m["negatives"], step_size=m["step_size"],
        subsample_ratio=m["subsample_ratio"], min_count=m["min_count"],
        batch_size=r["batch_size"], steps_per_call=r["steps_per_call"],
        num_shards=r["num_shards"], num_iterations=int(epochs),
        seed=int(seed), dtype=dtype or m["table_dtype"],
    )
    return est.fit_file(corpus)


def capture_batches(engine, cfg, seed, n_steps, total_words):
    """The batches of the first ``n_steps`` steps of epoch 0, as the packed
    scan's body draws them: ``fold_in(base_key, step)`` keys, window-shrink
    draws pinned to the grid mapping, negatives keyed by global pair row,
    alpha from the consumed position. Returns a list of dicts of numpy
    arrays (centers, contexts, mask (P,), negs (P, n), alpha)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from glint_word2vec_tpu.corpus.batching import (
        context_width,
        packed_pair_batch,
    )
    from glint_word2vec_tpu.ops.device_batching import (
        device_words_done,
        pack_window_pairs,
    )
    from glint_word2vec_tpu.ops.sampling import sample_negatives_per_row

    m, r = cfg["model"], cfg["run"]
    W, B, n = m["window"], r["batch_size"], m["negatives"]
    P = packed_pair_batch(B, W, 1)
    S = -(-3 * P // context_width(W))
    ids, soffs = engine._corpus_compacted
    orig_offs = engine._corpus[1]
    n_valid = jnp.int32(engine._n_kept)
    prob, alias = engine._prob, engine._alias
    base_key = jax.random.PRNGKey(int(seed))
    step_size = jnp.float32(m["step_size"])
    inv_total = jnp.float32(1.0 / float(total_words))
    words_base = jnp.float32(0)
    rows = jnp.arange(P, dtype=jnp.int32)

    @jax.jit  # the key is an argument: a constant would compile per seed
    def draw(base_key, ids, soffs, orig_offs, n_valid, prob, alias):
        def body(pos, i):
            key = jax.random.fold_in(base_key, jnp.uint32(0) + i)
            pc, px, pm, n_cons, _ = pack_window_pairs(
                ids, soffs, pos, base_key, jnp.uint32(0), window=W, span=S,
                pair_batch=P, grid_batch=B, n_valid=n_valid,
            )
            pos_end = pos + n_cons
            done = device_words_done(orig_offs, soffs, pos_end, n_valid)
            wd = words_base + done.astype(jnp.float32)
            alpha = jnp.maximum(
                step_size * (1.0 - wd * inv_total), step_size * 1e-4)
            negs = sample_negatives_per_row(key, prob, alias, rows, (1, n))
            return pos_end, (pc, px, pm, negs[:, 0, :], alpha)

        return lax.scan(body, jnp.int32(0),
                        jnp.arange(n_steps, dtype=jnp.uint32))[1]

    pc, px, pm, negs, alphas = (
        np.asarray(a) for a in draw(base_key, ids, soffs, orig_offs, n_valid,
                                    prob, alias))
    return [
        {"centers": pc[i], "contexts": px[i], "mask": pm[i], "negs": negs[i],
         "alpha": alphas[i]}
        for i in range(n_steps)
    ]


def table_rows(table, rows, chunk=1 << 17):
    """Host copy of ``table[rows]`` as float32, gathered on the device in
    chunks small enough that, beside the replay engine, they stay under the
    window's own peak (memory_peak_bytes is the program's); over a mesh the
    gather's exchange buffers are three times its result."""
    import jax
    import jax.numpy as jnp

    take = jax.jit(lambda t, i: t[i].astype(jnp.float32))
    parts = []
    for s in range(0, rows.size, chunk):
        idx = rows[s:s + chunk]
        got = take(table, jnp.asarray(np.pad(idx, (0, chunk - idx.size))))
        parts.append(np.asarray(got)[:idx.size])
    return np.concatenate(parts)


def run(ctx):
    import jax

    from benchmark import corpus as corpus_mod
    from benchmark import reference

    cfg, traffic, args = ctx.cfg, ctx.traffic, ctx.args
    m, r = cfg["model"], cfg["run"]
    prog_seed = int(args.seed) % (2**31 - 1)
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
            replay = _fit(cfg, corpus, prog_seed, epochs, dtype=dtype)
        finally:
            os.environ.pop("GLINT_PACKED_STOP_AFTER_GROUPS", None)
        eng = replay.engine
        ctx.device = ctx.device_of(eng)
        ctx.say(f"replay fit: {len(seam.first_losses)} group(s), step body "
                f"{replay.training_metrics.get('step_body')}, "
                f"{time.perf_counter() - ctx.t_start:.2f}s since start")
        # -- the check's own reads (not set-up: taken off setup_s) ------
        t_check = time.perf_counter()
        prog_losses = np.concatenate(
            [np.asarray(x, np.float32) for x in seam.first_losses])[:K]
        total_words = epochs * replay.vocab.train_words_count + 1
        batches = capture_batches(eng, cfg, prog_seed, K, total_words)
        rows = reference.touched_rows(batches)
        d = m["vector_size"]
        prog0 = table_rows(eng.syn0, rows)[:, :d]
        prog1 = table_rows(eng.syn1, rows)[:, :d]
        devices = sorted(eng.syn0.sharding.device_set, key=lambda x: x.id)
        ctx.check_seconds += time.perf_counter() - t_check
        # The window chains each dispatch on the last one's end position,
        # a device scalar, where the stopped fit passed a host integer: to
        # jit that is another program. Load it now, as the window calls it.
        seam.phase = "warm"
        a, k, out = seam.last_call
        jax.block_until_ready(
            eng.train_steps_corpus_packed(out[2][-1], *a[1:], **k))
        seam.last_call = None
        replay.stop()
        del replay, eng
        ctx.say(f"check reads: {rows.size} touched rows of {m['vocab']}, "
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
            model = _fit(cfg, corpus, prog_seed, epochs, obs=obs,
                         dtype=dtype)
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
            f"loss {tm['first_loss']} -> {tm['final_loss']}")

    # -- the reference, once the window has closed ----------------------
    t_ref = time.perf_counter()
    gaps = reference.replay_gaps(prog_seed, m["vocab"], d, rows, batches,
                                 prog0, prog1, prog_losses, devices)
    ctx.say(f"reference: {K} steps over {rows.size} rows, compared in "
            f"{time.perf_counter() - t_ref:.2f}s")
    numbers = []

    def compare(name, value, limit):
        numbers.append((name, float(value), float(limit)))

    lim = traffic["limits"]
    for name in sorted(gaps):
        compare(name, gaps[name], lim[name])
    first, final = tm["first_loss"], tm["final_loss"]
    ok_loss = (first is not None and final is not None
               and math.isfinite(first) and math.isfinite(final))
    compare("window.final_over_first_loss",
            final / first if ok_loss else float("inf"),
            lim["window.final_over_first_loss"])
    compare("window.words_not_trained", abs(tm["words_done"] - words), 0)
    in_window = [(t, e) for t, e in compiles if t >= ctx.window[0]]
    for when, event in in_window:
        ctx.say(f"compiled in the window at +{when - ctx.window[0]:.3f}s:"
                f" {event}")
    compare("window.compiles", len(in_window), 0)
    compare("tables.devices_missing",
            max(0, ctx.cell["chips"] - len(devices)), 0)

    ctx.numbers = numbers
    ctx.attempted = seam.window_dispatches
    ctx.failed = 0
    ctx.end_to_end = {"train_words_per_s": words / window_s}
    ctx.notes = {"epochs": epochs, "words": words, "window_s": window_s,
                 "steps": tm["steps"]}
