"""Cell kind ``train_cbow_subword``: the ``train`` kind for fastText's CBOW
over subword groups: one ``FastTextWord2Vec(architecture="cbow", ...)
.fit_file`` job (what ``cli train --fasttext --architecture cbow`` calls)
over the seeded corpus of the cell's traffic file, whose fillers look like
words (``benchmark/corpus_words.py``: the very text of the skip-gram subword
cell).

The same job, set-up, window and comparisons as ``kinds/train.py``, whose
``Seam`` and ``table_rows`` it imports (that file's docstring says what each
takes from the program), decided as ``kinds/train_cbow.py`` and
``kinds/train_subword.py`` decide theirs, together:

* The estimator is built FIRST, before the corpus is written, with both
  parameters named: a program in which the subword family refuses CBOW fails
  there, at once, and is never timed as a skip-gram. It is then asked
  whether a subword fit of this corpus takes the corpus-resident path.
* The benchmark builds its OWN group table (``reference_subword
  .group_table``: its own n-gram cutter and FNV-1a) and compares it row for
  row with the table the program holds on its device
  (``groups.rows_differing``, limit 0).
* The replayed bags are redrawn with the program's bag function and sampler
  (``kinds/train_cbow.capture_bags``) and held to the numpy enumeration of
  the window rule lane for lane (``bags.lanes_differing``); what the TIMED
  program counted on its device in the same steps (live bag lanes, positions
  trained, live group ids gathered, span words composed, input rows) is held
  to the same enumeration and the benchmark's group table
  (``bags.counts_differing``).
* The replay is followed by ``benchmark/reference_cbow_subword.py``, written
  in the SOURCE's form (each position's concatenated input, one mean, the
  whole gradient to every member), where the program sums each span word's
  group once and lets the bags read the sums. ``syn0``'s touched rows (the
  span words' groups) and ``syn1``'s (the positions' words and the
  negatives) are read apart.

Taken from the program besides what those three kinds take:
``FastTextWord2Vec(architecture=..., unigram_power=...)``; columns 4 to 8 of
``train_steps_corpus_packed``'s fifth output; ``training_metrics
.cbow_rows_per_bag`` / ``.subword_rows_per_center`` /
``.cbow_input_rows_per_bag`` / ``.subword_rows_per_step`` / ``.pipeline``.
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
        obs=obs, vector_size=m["vector_size"], window=m["window"],
        num_negatives=m["negatives"], step_size=m["step_size"],
        subsample_ratio=m["subsample_ratio"], min_count=m["min_count"],
        unigram_power=m["unigram_power"],
        min_n=m["min_n"], max_n=m["max_n"], bucket=m["bucket"],
        max_subwords=m["max_subwords"],
        batch_size=r["batch_size"], steps_per_call=r["steps_per_call"],
        num_shards=r["num_shards"], num_iterations=int(epochs),
        seed=int(seed), dtype=dtype or m["table_dtype"],
    )


def count_faults(engine, batches, groups, window, counted) -> int:
    """In how many of the steps the program ``counted`` (rows of
    ``train_steps_corpus_packed``'s columns 4 to 8, its own device's count
    of the last steps of ``batches``) it counted another step than
    ``enumerate_bags`` and the benchmark's ``groups`` hold: live bag lanes,
    positions trained, live group ids gathered, span words composed (the
    step's positions and ``window`` either side, inside the view), input
    rows (the group sizes of a bag's words, summed over the bags)."""
    from benchmark.kinds.train_cbow import _view, enumerate_bags

    ids, soffs, _, n_valid = _view(engine)
    shrink = np.concatenate([b["shrink"] for b in batches])
    B, W, n_valid = batches[0]["shrink"].size, int(window), int(n_valid)
    words = np.zeros(shrink.size + W, np.int32)
    head = np.asarray(ids[:words.size])
    words[:head.size] = head
    _, bags = enumerate_bags(words, np.asarray(soffs), n_valid, shrink, W)
    sizes = (groups >= 0).sum(axis=1)
    mine = []
    for i in range(len(batches)):
        bag = bags[i * B:(i + 1) * B]
        span = words[max(i * B - W, 0):min((i + 1) * B + W, n_valid)]
        mine.append([
            (bag >= 0).sum(), (bag >= 0).any(axis=1).sum(),
            sizes[span].sum(), span.size, sizes[bag[bag >= 0]].sum()])
    counted = np.asarray(counted).reshape(-1, 5)
    return int((counted != np.asarray(mine)[-counted.shape[0]:]).any(
        axis=1).sum())


def run(ctx):
    import jax

    from benchmark import corpus_words
    from benchmark import reference_cbow_subword as reference
    from benchmark.kinds.train import Seam, table_rows
    from benchmark.kinds.train_cbow import bag_faults, capture_bags
    from benchmark.reference_subword import group_table

    cfg, traffic, args = ctx.cfg, ctx.traffic, ctx.args
    m, r = cfg["model"], cfg["run"]
    prog_seed = int(args.seed) % (2**31 - 1)
    n_expected = (m["vocab"] + int(traffic["zipf_tokens"])
                  + 8 * int(traffic["planted_sentences"]))
    # a program whose subword family refuses the architecture: out, here
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
        del replay, eng, held
        ctx.say(f"check reads: {rows0.size} syn0 and {rows1.size} syn1 "
                f"touched rows of {m['vocab']} + {m['bucket']}, "
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
            f"{tm.get('cbow_rows_per_bag')} words a bag, "
            f"{tm.get('subword_rows_per_center')} rows a word, "
            f"{tm.get('cbow_input_rows_per_bag')} rows a bag, "
            f"{tm.get('subword_rows_per_step')} group rows a step")

    # -- the reference, once the window has closed ----------------------
    t_ref = time.perf_counter()
    gaps = reference.replay_gaps(
        prog_seed, m["vocab"] + m["bucket"], d, rows0, rows1, groups,
        batches, prog0, prog1, prog_losses, devices)
    ctx.say(f"subword cbow reference: {K} steps over {rows0.size} + "
            f"{rows1.size} rows, compared in "
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
