"""Cell kind ``synonyms_subword``: a served fastText model under a closed loop
of callers, some of whose words are in no dictionary.

``kinds/synonyms.py`` for the subword family: what ``cli serve`` does after
its load, on a model whose tables are made from ``--seed``. ``syn0`` (the
dictionary's word rows, then the hashed n-gram rows) is drawn on the device in
row blocks (normal(0, std); speed and the reference need no trained table) and
written into a fresh engine with the program's ``write_rows``; the model is
built as ``FastTextModel._from_loaded`` builds it (its group table from the
words); ``ModelServer`` with ``cli serve``'s defaults, written out in the
configuration's ``serve``, warms and binds. The callers are
``benchmark/loadgen.py``, a child that never imports JAX. A request's word is
a dictionary word drawn Zipf 1/rank or, with probability ``oov_share``, an
out-of-dictionary variant of a Zipf-drawn word of five letters or more: one
edit of ``oov_edits`` (a letter dropped, a letter doubled, two neighbours
swapped), redrawn while the result is a dictionary word (and from another
word after eight edits of one).

Once the window has closed, a seeded sample of the answers the callers
received, ``checked_oov`` of them to out-of-dictionary words, is compared with
``benchmark/reference_nn.py`` (numpy float32 ``fasttext nn`` over ALL
dictionary words, from a host copy of ``syn0`` and the reference's own group
tables); the model's group table and the sampled words' groups with the
reference's, row for row; and ``composed_rows`` seeded rows of the composed
table on the device with the reference's.

Taken from the program: ``EmbeddingEngine(extra_rows=)``, ``write_rows``,
``pull``; ``FastTextModel._from_loaded``, ``_sub_ids`` / ``_sub_mask``,
``_oov_group``, ``_query_engine``; ``ModelServer``; ``GET /metrics``;
``kinds/synonyms.py``'s ``http`` and ``Seam`` (the benchmark's annotations
around the top-k dispatches). ``server.post_warmup_compiles`` is the growth of
the server's own count over the window, so that a program that compiles its
shapes in the cache warm-up's first requests (the parent of PR 43, which warms
nothing for this family) is held to its answers; the count since the port
bound is the per-layer metric ``serve.post_warmup_compiles``.
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
TABLE_BLOCK = 250_000  # rows of syn0 drawn, read back and written at a time


def vocabulary(vocab: int, seed: int):
    """The fastText training cells' words (``corpus_words.py``'s fillers, the
    shorter at the more frequent ranks, then the special words) and
    Zipf-shaped counts."""
    from benchmark.corpus import special_words
    from benchmark.corpus_words import filler_names

    _, special = special_words()
    words = list(filler_names(vocab - len(special), seed, taken=special))
    counts = np.maximum(1, (vocab / np.arange(1, vocab + 1))).astype(np.int64)
    return words + special, counts


def edit(word: str, how: str, at: float) -> str:
    """One edit of ``word``: ``how`` at the place ``at`` in [0, 1) picks."""
    if how == "swap":
        i = int(at * (len(word) - 1))
        return word[:i] + word[i + 1] + word[i] + word[i + 2:]
    i = int(at * len(word))
    if how == "drop":
        return word[:i] + word[i + 1:]
    if how == "double":
        return word[:i] + word[i] + word[i:]
    raise ValueError(f"unknown edit {how!r}")


def request_words(words, index, traffic, count: int, seed: int):
    """``count`` request words and which of them are out of the dictionary.
    Dictionary words by Zipf over all ranks; with probability ``oov_share``
    an edited Zipf draw among the words of five letters or more."""
    from benchmark.corpus import zipf_words

    rng = np.random.default_rng([int(seed), 43])
    out = [words[i] for i in zipf_words(
        len(words), count, traffic["zipf_exponent"], seed)]
    is_oov = rng.random(count) < float(traffic["oov_share"])
    long_ranks = np.flatnonzero(np.char.str_len(np.asarray(words)) >= 5)
    cdf = np.cumsum(1.0 / (long_ranks + 1.0) ** float(
        traffic["zipf_exponent"]))
    where = np.flatnonzero(is_oov)

    def draw(n):
        return long_ranks[np.searchsorted(cdf, rng.random(n) * cdf[-1])]

    edits = traffic["oov_edits"]
    for k, rank in zip(where, draw(where.size)):
        tries = 0
        while True:
            w = edit(words[rank], edits[rng.integers(len(edits))],
                     rng.random())
            if w not in index:
                break
            # "eeeee" beside "eeee" and "eeeeee": a word whose every edit
            # is a dictionary word would be redrawn for ever. After a few
            # edits of one word, another word is drawn.
            tries += 1
            if tries % 8 == 0:
                rank = draw(1)[0]
        out[k] = w
    return out, is_oov


def run(ctx):
    import jax
    import jax.numpy as jnp

    from benchmark import reference_nn
    from benchmark.kinds.synonyms import Seam, http
    from glint_word2vec_tpu.corpus.vocab import Vocabulary
    from glint_word2vec_tpu.models.fasttext import FastTextModel, FastTextParams
    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
    from glint_word2vec_tpu.parallel.mesh import make_mesh
    from glint_word2vec_tpu.serving import ModelServer

    cfg, traffic, args = ctx.cfg, ctx.traffic, ctx.args
    m, r, serve = cfg["model"], cfg["run"], cfg["serve"]
    V, B, d = m["vocab"], m["bucket"], m["vector_size"]
    geometry = (B, m["min_n"], m["max_n"], m["max_subwords"])
    prog_seed = int(args.seed) % (2**31 - 1)
    dtype = ctx.table_dtype or m["table_dtype"]

    t0 = time.perf_counter()
    words, counts = vocabulary(V, args.seed)
    vocab = Vocabulary.from_sorted(words, counts)
    ctx.say(f"dictionary: {V} words in {time.perf_counter() - t0:.2f}s")
    engine = EmbeddingEngine(
        make_mesh(1, r["num_shards"]), V, d, counts,
        num_negatives=m["negatives"], seed=prog_seed, dtype=dtype,
        extra_rows=B)
    # syn0 a block of rows at a time: drawn on the device, read back for the
    # reference (the check's own read, not set-up), written into the engine.
    host_syn0 = np.empty((V + B, d), np.float32)
    draw = jax.jit(
        lambda key, n: jax.random.normal(key, (n, d), dtype=jnp.float32)
        * jnp.float32(traffic["table_std"]), static_argnums=1)
    key = jax.random.PRNGKey(prog_seed)
    for i, s in enumerate(range(0, V + B, TABLE_BLOCK)):
        block = draw(jax.random.fold_in(key, i), min(TABLE_BLOCK, V + B - s))
        t_check = time.perf_counter()
        host_syn0[s:s + block.shape[0]] = np.asarray(block)
        ctx.check_seconds += time.perf_counter() - t_check
        engine.write_rows(s, block)
        block.delete()
    ctx.device = ctx.device_of(engine)
    spans_devices = len(engine.syn0.sharding.device_set)
    t0 = time.perf_counter()
    model = FastTextModel._from_loaded(vocab, engine, FastTextParams(
        vector_size=d, window=m["window"], num_negatives=m["negatives"],
        num_shards=r["num_shards"], seed=prog_seed, dtype=dtype,
        min_n=m["min_n"], max_n=m["max_n"], bucket=B,
        max_subwords=m["max_subwords"]))
    ctx.say(f"syn0 {V} + {B} x {d} {dtype} on the device, the model's group "
            f"table in {time.perf_counter() - t0:.2f}s, "
            f"{time.perf_counter() - ctx.t_start:.2f}s since start")

    seam = Seam()
    seam.install()
    recorder = None
    if args.trace:
        from glint_word2vec_tpu.obs import events as obs_events

        recorder = obs_events.EventRecorder(capacity=1 << 20)
        prev_recorder = obs_events.get_recorder()
        obs_events.set_recorder(recorder)
    server = None
    try:
        t0 = time.perf_counter()
        server = ModelServer(
            model, host="127.0.0.1", port=0, max_batch=serve["max_batch"],
            cache_size=serve["cache_size"])
        server.start_background()
        port = server.port
        ctx.say(f"server: port {port}, warm-up {time.perf_counter() - t0:.2f}s")

        def load(name, sent, **spec):
            """The callers, as a child process over a spec file."""
            n = traffic["callers"]
            spec = dict(spec, port=port, path="/synonyms",
                        num=traffic["num"],
                        keep_every=traffic["keep_every"],
                        callers=[sent[k::n] for k in range(n)],
                        out=os.path.join(ctx.work, name + "_out.json"))
            path = os.path.join(ctx.work, name + "_spec.json")
            with open(path, "w") as f:
                json.dump(spec, f)
            child = subprocess.Popen(
                [sys.executable,
                 os.path.join(os.path.dirname(HERE), "loadgen.py"), path],
                stdout=sys.stderr, stderr=sys.stderr)
            return child, spec["out"]

        # A server that has run for hours holds its hottest words' answers.
        t0 = time.perf_counter()
        warm_s = float(traffic["cache_warm_seconds"])
        child, _ = load("warm", words[:traffic["cache_warm_words"]],
                        seconds=warm_s, once=True)
        if child.wait(timeout=warm_s + 120) != 0:
            raise RuntimeError("cache warm-up failed")
        ctx.say(f"result cache: the {traffic['cache_warm_words']} most "
                f"frequent words asked for once, or as many of them as "
                f"{warm_s:.0f}s allow, in {time.perf_counter() - t0:.2f}s")
        m0 = http(port, "/metrics")
        per_caller = int(float(args.seconds) * 1500) + 1000
        sent, is_oov = request_words(
            words, vocab.word_index, traffic,
            traffic["callers"] * per_caller, args.seed)
        ctx.say(f"traffic: {len(sent)} words drawn, {int(is_oov.sum())} out "
                f"of the dictionary ({100.0 * is_oov.mean():.2f}%)")

        tracer = None
        if args.trace:
            lo, hi = traffic["trace_window_s"]
            trace_t = ctx.trace_t = [None, None]

            def trace():
                time.sleep(lo)
                jax.profiler.start_trace(ctx.trace_dir)
                trace_t[0] = time.perf_counter()
                time.sleep(hi - lo)
                trace_t[1] = time.perf_counter()
                jax.profiler.stop_trace()

            tracer = threading.Thread(target=trace, name="bench-trace")
        with ctx.count_compiles() as compiles:
            child, out_path = load("window", sent,
                                   seconds=float(args.seconds))
            if tracer:
                tracer.start()
            rc = child.wait(timeout=float(args.seconds) + 240)
            t_closed = time.perf_counter()
        if tracer:
            tracer.join()
        if rc != 0:
            raise RuntimeError(f"load generator exited {rc}")
        m1 = ctx.serving_metrics = http(port, "/metrics")
        ctx.serving_metrics_before = m0
        ctx.memory_peak_bytes = ctx.read_memory_peak()
        if recorder is not None:
            ctx.program_spans = recorder.events()
        # -- the check's reads of the program's state -------------------
        server.stop()
        server = None
        qeng = model._query_engine()
        padded_rows = int(qeng.padded_vocab)
        rng = np.random.default_rng([int(args.seed), 44])
        rows = np.sort(rng.choice(
            V, size=min(V, int(traffic["composed_rows"])), replace=False))
        served_rows = np.concatenate([
            np.asarray(qeng.pull(rows[s:s + 1024]), np.float32)
            for s in range(0, rows.size, 1024)])
        held = np.where(model._sub_mask > 0, model._sub_ids, -1)
    finally:
        if server is not None:
            server.stop()
        if args.trace:
            obs_events.set_recorder(prev_recorder)
        seam.uninstall()
        model.stop()

    with open(out_path) as f:
        out = json.load(f)
    # The child's window: from its first caller's start to its last reply.
    window_s = float(out["window_s"])
    ctx.window = (t_closed - window_s, t_closed)
    req = np.asarray(out["requests"], np.float64).reshape(-1, 3)
    ok = req[:, 2] == 200
    attempted, failed = int(req.shape[0]), int((~ok).sum())
    lat_ms = np.where(ok, req[:, 1] * 1e3, np.inf)
    ctx.say(f"window: {attempted} requests from {traffic['callers']} callers "
            f"in {window_s:.3f}s, {failed} failed; p95 over {attempted} "
            f"samples, {int(attempted * 0.05)} beyond it")
    ctx.say(f"server: resident_bytes {m1.get('resident_bytes')} by the "
            f"catalog's accounting; compose {m1.get('compose')}")

    # -- the reference, once the window has closed ----------------------
    t_ref = time.perf_counter()
    rng = np.random.default_rng(args.seed)
    kept = out["kept"]
    want_oov = int(traffic["checked_oov"])
    want = {True: want_oov, False: int(traffic["checked_answers"]) - want_oov}
    seen, sample = set(), []
    for i in rng.permutation(len(kept)):  # distinct words: a hot one is one
        w = kept[i]["word"]
        outside = w not in vocab.word_index
        if w not in seen and want[outside]:
            seen.add(w)
            want[outside] -= 1
            sample.append(kept[i])
    sample_oov = [s["word"] for s in sample
                  if s["word"] not in vocab.word_index]
    grp = reference_nn.groups(words, *geometry)
    grp_oov = reference_nn.oov_groups(sample_oov, V, *geometry)
    rows_differing = int((held != grp).any(axis=1).sum()) if (
        held.shape == grp.shape) else V
    for w, g in zip(sample_oov, grp_oov):
        ids, mask = model._oov_group(w)
        rows_differing += int(
            (np.where(mask[0] > 0, ids[0], -1) != g).any())
    composed = reference_nn.compose(host_syn0, grp)
    row_gap = float(np.abs(served_rows - composed[rows]).max()
                    / np.abs(composed[rows]).max())
    queries, banned = [], []
    at_oov = dict(zip(sample_oov, reference_nn.compose(host_syn0, grp_oov)))
    for s in sample:
        i = vocab.word_index.get(s["word"])
        banned.append(i)
        queries.append(composed[i] if i is not None else at_oov[s["word"]])
    nn = reference_nn.NN(composed)
    cos = nn.cosines(np.stack(queries)) if sample else None
    worst = worst_oov = 0.0
    bad_status = 0
    for j, s in enumerate(sample):
        if s["status"] != 200:
            bad_status += 1
            continue
        got = [(vocab.word_index.get(w), sc)
               for w, sc in json.loads(s["body"])]
        gap = nn.gap(banned[j], cos[:, j], got, traffic["num"])
        worst = max(worst, gap)
        if banned[j] is None:
            worst_oov = max(worst_oov, gap)
    ctx.say(f"reference: {len(sample)} answers of {len(kept)} kept, "
            f"{len(sample_oov)} of them to words outside the dictionary "
            f"(their largest gap {worst_oov:.6g}), {rows.size} composed "
            f"rows, in {time.perf_counter() - t_ref:.2f}s")
    lim = traffic["limits"]
    in_window = [(t, e) for t, e in compiles if t >= ctx.window[0]]
    ctx.numbers = [
        ("answers.score_gap", worst, lim["answers.score_gap"]),
        ("answers.sampled_not_ok", bad_status, 0),
        ("answers.too_few_sampled",
         max(0, min(traffic["checked_answers"], 4) - len(sample)), 0),
        ("answers.oov_too_few_sampled",
         max(0, min(want_oov, 2) - len(sample_oov)), 0),
        ("groups.rows_differing", rows_differing, 0),
        ("composed.row_gap", row_gap, lim["composed.row_gap"]),
        ("server.post_warmup_compiles",
         m1["compiles"]["post_warmup"] - m0["compiles"]["post_warmup"], 0),
        ("window.compiles", len(in_window), 0),
        ("tables.devices_missing",
         max(0, ctx.cell["chips"] - spans_devices), 0),
    ]
    ctx.attempted, ctx.failed = attempted, failed
    n_ok = int(ok.sum())
    # ``synonyms_qps`` reaches the result line only where BENCHMARK.json lists
    # the cell under it (``run.py``); the log carries it either way.
    ctx.say(f"window: {n_ok / window_s:.2f} queries/s completed")
    ctx.end_to_end = {
        "synonyms_qps": n_ok / window_s,
        "synonyms_p50_ms": float(np.percentile(lat_ms, 50)),
        "synonyms_p95_ms": float(np.percentile(lat_ms, 95)),
    }
    ctx.notes = {"window_s": window_s, "requests": attempted,
                 "padded_rows": padded_rows}
