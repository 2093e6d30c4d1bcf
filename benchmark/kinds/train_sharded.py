"""Cell kind ``train_sharded``: the ``train`` kind for a configuration whose
tables are split by rows over the cell's chips (``num_shards`` > 1).

The same job, set-up, window and comparisons as ``kinds/train.py``, whose
``Seam``, ``_fit``, ``capture_batches`` and ``table_rows`` it imports (that
file's docstring says what each takes from the program; this kind takes
nothing more). Two things differ. The replay is followed by
``benchmark/reference_sharded.py``, which keeps the touched rows split over
the chips (the one-chip reference puts them on the first, where at 3.67M rows
they do not fit). And a run whose tables stand whole on one chip is not this
configuration: the fullest chip may hold its share of each table's rows,
``ceil(vocab / chips)``, and no more.
"""

import math
import os
import time

import numpy as np


def rows_over_share(model, vocab: int, chips: int) -> int:
    """Rows the fullest device holds of either table beyond its share."""
    fullest = max(s.data.shape[0] for t in (model.engine.syn0,
                                            model.engine.syn1)
                  for s in t.addressable_shards)
    return max(0, fullest - -(-vocab // chips))


def run(ctx):
    import jax

    from benchmark import corpus as corpus_mod
    from benchmark import reference_sharded as reference
    from benchmark.kinds.train import Seam, _fit, capture_batches, table_rows

    cfg, traffic, args = ctx.cfg, ctx.traffic, ctx.args
    m, r = cfg["model"], cfg["run"]
    chips = ctx.cell["chips"]
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
        # The window's dispatches pass their start as a device scalar, the
        # stopped fit a host integer: another program to jit. Load it now.
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
        over_share = rows_over_share(model, m["vocab"], chips)
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
    ctx.say(f"sharded reference: {K} steps over {rows.size} rows on "
            f"{len(devices)} device(s), compared in "
            f"{time.perf_counter() - t_ref:.2f}s")
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
        ("window.final_over_first_loss",
         final / first if ok_loss else float("inf"),
         float(lim["window.final_over_first_loss"])),
        ("window.words_not_trained", float(abs(tm["words_done"] - words)), 0.0),
        ("window.compiles", float(len(in_window)), 0.0),
        ("tables.devices_missing", float(max(0, chips - len(devices))), 0.0),
        ("tables.rows_on_fullest_device_over_share", float(over_share), 0.0),
    ]

    ctx.numbers = numbers
    ctx.attempted = seam.window_dispatches
    ctx.failed = 0
    ctx.end_to_end = {"train_words_per_s": words / window_s}
    ctx.notes = {"epochs": epochs, "words": words, "window_s": window_s,
                 "steps": tm["steps"]}
