"""Cell kind ``train_cbow_pw_subword``: the ``train_cbow_subword`` kind for
CBOW with position weights: one ``FastTextWord2Vec(architecture="cbow",
position_weights=True, ...).fit_file`` job (what ``cli train --fasttext
--architecture cbow --position-weights`` calls) over the seeded corpus of the
cell's traffic file (``benchmark/corpus_words.py``: the very text of
``ft-cbow-300-1m-2mb.train``).

The same job, set-up, window and comparisons as ``kinds/train_cbow_subword.py``
(its docstring says what each takes from the program), whose
``count_faults`` it imports with ``kinds/train.py``'s ``Seam`` and
``table_rows`` and ``kinds/train_cbow.py``'s ``bag_faults`` and
``capture_bags``. What differs:

* The estimator is built FIRST with ``position_weights=True`` named: a
  program without the parameter fails there (``TypeError``), at once, before
  the corpus is written, and is never timed as the unweighted model. After
  the replay fit the kind refuses a program whose fit took another path than
  the corpus-resident one or holds no position table.
* The replay is followed by ``benchmark/reference_cbow_pw_subword.py`` (the
  SOURCE's form: each position's list of (row, lane) inputs, one mean, the
  whole gradient to each, the position table's row the mean of its lane's
  shares) on all THREE tables: ``replay.posw_gap`` is the largest entry gap
  of the position table over the reference's largest change from ones (1.0
  for a table never trained), ``replay.posw_dnorm_gap`` the gap of the
  change norms. The lanes of the replayed bags are the table's rows, in
  ``enumerate_bags``' order, -window..-1, 1..window.
* The SEEDED replay: from ones the position table moves by 1e-4 in a
  dispatch group, so ``_bag_sums`` and ``_bag_spread`` without their weights
  are the weighted ones to 1e-4 of the rows' change, under every limit. So
  the replay's last dispatch group is run once more, the very call (the same
  batches), from the rows as the replay left them and a position table drawn
  U[0.5, 1.5) from the seed (``engine.set_tables(posw=...)``), and the
  reference follows it from the same three: ``seeded.syn0_gap``,
  ``.syn1_gap``, ``.posw_gap``, their change norms and ``seeded.loss_gap``,
  each over the reference's largest change in those steps. There every bag
  and every row's update carries its lane's factor.
* ``window.posw_not_finite``: 1 where the timed fit's
  ``training_metrics.position_table`` is missing or says the table is not
  finite when the window closes (limit 0).

Taken from the program besides what those kinds take:
``FastTextWord2Vec(position_weights=...)``; ``engine.posw``
(``(2 * window, padded columns)`` float32); ``engine.set_tables(None, None,
posw=(2 * window, columns))``; ``training_metrics
.position_table`` (``rows``, ``max_abs_dev``, ``finite``).
"""

import math
import os
import time

import numpy as np


def _estimator(cfg, seed, epochs, obs=None, dtype=None):
    from glint_word2vec_tpu.models.fasttext import FastTextWord2Vec

    m, r = cfg["model"], cfg["run"]
    return FastTextWord2Vec(
        architecture=m["architecture"],
        position_weights=m["position_weights"], obs=obs, vector_size=m["vector_size"], window=m["window"],
        num_negatives=m["negatives"], step_size=m["step_size"],
        subsample_ratio=m["subsample_ratio"], min_count=m["min_count"],
        unigram_power=m["unigram_power"],
        min_n=m["min_n"], max_n=m["max_n"], bucket=m["bucket"],
        max_subwords=m["max_subwords"],
        batch_size=r["batch_size"], steps_per_call=r["steps_per_call"],
        num_shards=r["num_shards"], num_iterations=int(epochs),
        seed=int(seed), dtype=dtype or m["table_dtype"],
    )


def run(ctx):
    import jax

    from benchmark import corpus_words
    from benchmark import reference_cbow_pw_subword as reference
    from benchmark.kinds.train import Seam, table_rows
    from benchmark.kinds.train_cbow import bag_faults, capture_bags
    from benchmark.kinds.train_cbow_subword import count_faults
    from benchmark.reference_subword import group_table

    cfg, traffic, args = ctx.cfg, ctx.traffic, ctx.args
    m, r = cfg["model"], cfg["run"]
    prog_seed = int(args.seed) % (2**31 - 1)
    n_expected = (m["vocab"] + int(traffic["zipf_tokens"])
                  + 8 * int(traffic["planted_sentences"]))
    # a program without the parameter, or whose subword family refuses the
    # architecture: out, here
    if not _estimator(cfg, prog_seed, 1)._device_corpus_eligible(n_expected):
        raise RuntimeError(
            "this program's subword fit does not take the corpus-resident "
            "path (FastTextWord2Vec._device_corpus_eligible is false for "
            f"{n_expected} words): not the job this cell measures")
    corpus = os.path.join(ctx.work, "corpus.txt")
    t0 = time.perf_counter()
    n_tokens = corpus_words.make_corpus(
        corpus, m["vocab"], traffic, args.seed)
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
        if (replay.training_metrics.get("pipeline") != "device_corpus"
                or getattr(eng, "posw", None) is None):
            raise RuntimeError(
                "this program's fit took the path "
                f"{replay.training_metrics.get('pipeline')!r} or holds no "
                "position table: not the job this cell measures")
        ctx.say(f"replay fit: {len(seam.first_losses)} group(s), step body "
                f"{replay.training_metrics.get('step_body')}, pipeline "
                f"{replay.training_metrics.get('pipeline')}, "
                f"{time.perf_counter() - ctx.t_start:.2f}s since start")
        # -- the check's own reads (not set-up: taken off setup_s) ------
        t_check = time.perf_counter()
        prog_losses = np.concatenate(
            [np.asarray(x, np.float32) for x in seam.first_losses])[:K]
        total_words = epochs * replay.vocab.train_words_count + 1
        t_groups = time.perf_counter()
        groups = group_table(
            replay.vocab.words, m["vocab"], m["bucket"], m["min_n"],
            m["max_n"], m["max_subwords"])
        held = getattr(eng, "_center_groups", None)
        rows_differing = (
            groups.shape[0] if held is None
            or tuple(held.shape) != groups.shape
            else int((np.asarray(held) != groups).any(axis=1).sum()))
        ctx.say(f"group table: {groups.shape[0]} words x {groups.shape[1]}, "
                f"{(groups >= 0).sum(axis=1).mean():.3f} rows a word, at "
                f"most {(groups >= 0).sum(axis=1).max()}, built by the "
                f"benchmark in {time.perf_counter() - t_groups:.2f}s; "
                f"{rows_differing} rows differ from the device's")
        batches = capture_bags(eng, cfg, prog_seed, K, total_words)
        counted = np.asarray(seam.last_call[2][4])
        lanes_off, _ = bag_faults(eng, batches, m["window"], counted[:, 4:6])
        # a program that counts fewer columns counted none of the steps
        counts_off = K if counted.shape[1] != 9 else count_faults(
            eng, batches, groups, m["window"], counted[:, 4:9])
        rows0, rows1 = reference.touched_rows(batches, groups)
        d = m["vector_size"]
        prog0 = table_rows(eng.syn0, rows0)[:, :d]
        prog1 = table_rows(eng.syn1, rows1)[:, :d]
        prog_posw = np.asarray(eng.posw, np.float32)[:, :d]
        devices = sorted(eng.syn0.sharding.device_set, key=lambda x: x.id)
        # The SEEDED replay: the replay's last dispatch group once more, the
        # very call (same start, keys and rate, so the same batches), from
        # the rows as they now stand and a position table well away from
        # ones. From ones the table moves by 1e-4 in a group, and a bag that
        # never met its weights would pass every limit above.
        seam.phase = "seeded"
        a, k, out = seam.last_call
        start_posw = reference.seeded_posw(prog_seed, prog_posw.shape[0], d)
        eng.set_tables(None, None, posw=start_posw)
        again = eng.train_steps_corpus_packed(*a, **k)
        seeded_losses = np.asarray(again[0], np.float32)
        seeded = (table_rows(eng.syn0, rows0)[:, :d],
                  table_rows(eng.syn1, rows1)[:, :d],
                  np.asarray(eng.posw, np.float32)[:, :d])
        ctx.check_seconds += time.perf_counter() - t_check
        # The window's dispatches pass their start as a device scalar, the
        # stopped fit a host integer: another program to jit. Load it now.
        seam.phase = "warm"
        jax.block_until_ready(
            eng.train_steps_corpus_packed(out[2][-1], *a[1:], **k))
        seam.last_call = None
        replay.stop()
        del replay, eng, held, again
        ctx.say(f"check reads: {rows0.size} syn0 and {rows1.size} syn1 "
                f"touched rows of {m['vocab']} + {m['bucket']}, twice (the "
                f"seeded replay's too), {ctx.check_seconds:.2f}s (not "
                "counted in setup_s)")

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
    table = tm.get("position_table") or {}
    ctx.say(f"position table: {table.get('rows')} rows, max |d - 1| "
            f"{table.get('max_abs_dev')}, finite {table.get('finite')}; "
            f"after the replay's {K} steps max |d - 1| "
            f"{np.abs(prog_posw - 1).max():.6g}")
    ctx.say(f"window: {epochs} epoch(s), {words} words in {window_s:.3f}s, "
            f"{seam.window_dispatches} dispatch groups; program says "
            f"words_done={tm['words_done']} steps={tm['steps']} "
            f"loss {tm['first_loss']} -> {tm['final_loss']}, "
            f"{tm.get('cbow_rows_per_bag')} words a bag, "
            f"{tm.get('subword_rows_per_center')} rows a word, "
            f"{tm.get('cbow_input_rows_per_bag')} rows a bag, "
            f"{tm.get('subword_rows_per_step')} group rows a step")

    # -- the reference, once the window has closed ----------------------
    t_ref = time.perf_counter()
    gaps = reference.replay_gaps(
        prog_seed, m["vocab"] + m["bucket"], d, rows0, rows1, groups,
        batches, prog0, prog1, prog_posw, prog_losses, devices)
    gaps.update(reference.seeded_gaps(
        rows0, rows1, groups, batches[K - seeded_losses.size:],
        (prog0, prog1, start_posw), seeded, seeded_losses))
    ctx.say(f"position-weighted subword cbow reference: {K} steps from the "
            f"seed's tables and {seeded_losses.size} from a seeded position "
            f"table over {rows0.size} + {rows1.size} rows, compared in "
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
        ("groups.rows_differing", float(rows_differing), 0.0),
        ("bags.lanes_differing", float(lanes_off), 0.0),
        ("bags.counts_differing", float(counts_off), 0.0),
        ("window.final_over_first_loss",
         final / first if ok_loss else float("inf"),
         float(lim["window.final_over_first_loss"])),
        ("window.posw_not_finite",
         0.0 if table.get("finite") is True else 1.0, 0.0),
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
