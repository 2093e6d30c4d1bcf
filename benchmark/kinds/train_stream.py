"""Cell kind ``train_stream``: one ``Word2Vec.fit_stream`` job (what ``cli
fit-stream --corpus <file>`` calls, over the lines ``cli._stream_sentences``
yields) over the seeded stream of the cell's traffic file
(``benchmark/corpus_stream.py``): a bounded backfill, the stream ends.

Set-up writes the stream and a PREFIX of it (the bootstrap window and the
first ``prefix_live_sentences`` live sentences) and runs the REPLAY fit over
the prefix: the same ``fit_stream`` with the same keywords. It loads every
program the window drives (full buffers, the partial last one, a promotion,
a refresh), and two of its dispatch groups are what the numpy reference
follows once the window has closed: the first group of round 0, from the
seed's tables, and the first group after the first refresh that follows a
promotion, from the rows the program held before it, its negatives drawn
from the refreshed table. The alias table in force there is read back and
turned into the distribution it draws from.

The window is a second ``fit_stream`` over the whole stream. It opens at the
first ``upload_corpus`` of the first LIVE round (the first whose buffer holds
nothing of the bootstrap window; the rounds before it are set-up) and closes
when ``fit_stream`` returns. ``train_words_per_s`` is the raw tokens of the
sentences pulled for that round and those after it, over those seconds. The
program fills a round's buffer rounds ahead of the round that trains, behind
the device's work, so what it has pulled by an upload says nothing of that
round: WHICH round is the first live one is read from the replay fit's own
``rounds`` and ``live_rounds`` (the same stream and seed: the same rounds),
and held to the window's and to the reference's afterwards; how many raw
tokens the rounds before it pulled is the reference's count. Once the window
has closed, the plain reference (``benchmark/reference_stream.py``) reads the
same file: the first rounds' buffers, every promotion and its row, the final
counts and the words trained are compared exactly.

What is taken from the program besides its entry point (the seam; PERF.md
lists it): ``EmbeddingEngine.upload_corpus``, ``.train_steps_corpus_packed``,
``.noise_table``, ``.set_noise_counts`` and ``.assign_extra_rows`` are wrapped
(the first two with the benchmark's own spans; a table is built rounds before
it is installed, so the k-th build is matched to the k-th install); the batches of the two replayed groups are
drawn once more with the program's batcher and sampler from the engine's
corpus buffer and alias table and the arguments the trainer passed, since no
independent code can repeat the device's random draws; the alias table is
``engine._prob`` / ``._alias``.
"""

import functools
import math
import os
import time

import numpy as np

#: Over this many words an alias table built without native/host_ops.cpp
#: is a Python loop of minutes (corpus/alias.build_alias), once a refresh.
NATIVE_ALIAS_OVER = 100_000


def require_native_alias(vocab: int) -> bool:
    """Whether the program's native alias builder loaded; at a vocabulary
    over NATIVE_ALIAS_OVER its absence fails set-up at once: a run that
    refreshed 2M counts in Python would measure that loop."""
    from glint_word2vec_tpu.native import get_lib

    native = get_lib() is not None
    if not native and vocab > NATIVE_ALIAS_OVER:
        raise RuntimeError(
            f"the native alias builder (glint_word2vec_tpu/native/"
            f"host_ops.cpp, built with g++ on first use) did not load, and "
            f"a refresh over {vocab} counts without it is a Python loop of "
            f"minutes: build native/ on this machine first")
    return native


def require_one_promotion_program(ctx) -> None:
    """The configuration's guarantee, asked of the engine before anything
    is built at size: a promotion burst of a size not met before compiles
    nothing. A program that compiles a block a burst size (PR 50's parent:
    two programs a power of two) compiles inside any window whose bursts
    differ round by round, so it cannot run this cell: set-up says so, at
    once, on an engine of eight rows."""
    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    eng = EmbeddingEngine(make_mesh(1, 1), 8, 8, np.ones(8, np.int64),
                          num_negatives=2, seed=1, extra_rows=64)
    eng.assign_extra_rows([None] * 21)
    with ctx.count_compiles() as seen:
        eng.assign_extra_rows([None] * 42)
    if seen:
        raise RuntimeError(
            f"a promotion burst of a new size compiled {len(seen)} "
            "program(s) (EmbeddingEngine.assign_extra_rows): this program "
            "cannot hold the configuration's guarantee that nothing is "
            "compiled after the warm-up, and cannot run this cell")


class Pulled:
    """The sentences ``fit_stream`` is handed, counted as it pulls them."""

    def __init__(self, sentences):
        self.it = iter(sentences)
        self.sentences = self.tokens = 0

    def __iter__(self):
        return self

    def __next__(self):
        s = next(self.it)
        self.sentences += 1
        self.tokens += len(s)
        return s


class Seam:
    """The benchmark's own spans and captures around the calls into the
    engine."""

    def __init__(self, cfg, seed: int, bootstrap_sentences: int,
                 compare_rounds: int):
        self.cfg, self.seed = cfg, seed
        self.bootstrap_sentences = bootstrap_sentences
        self.compare_rounds = compare_rounds  # buffers kept for the check
        self.check_seconds = 0.0  # the check's own reads inside set-up
        self.phase = "setup"
        self.pulled = None  # the Pulled of the running fit
        # -- the replay fit --
        self.groups = 0  # packed dispatches of the running fit
        self.promoted = self.refreshed_after = False
        # noise tables built / installed by the running fit, and which
        # build is the first that follows a promotion
        self.builds = self.installs = 0
        self.promotion_build = None
        self.captured = {}  # "first" / "live": what the reference follows
        self.alias = None  # (prob, alias) in force at the "live" group
        self.misplaced = float("inf")  # rows_misplaced of the first burst
        # -- the window fit --
        self.uploads = 0  # rounds uploaded
        self.buffers = []  # (ids, offsets, n_valid) of the first rounds
        self.first_live = None  # index of the first live round
        self.window_t0 = None
        self.window_dispatches = 0
        self.trace = None  # (first, last) live round to profile between
        self.trace_dir = None
        self.trace_t = None  # [t_start, t_stop] perf_counter

    # -- what the wrappers do ------------------------------------------

    def _round(self, ids, offsets, n_valid):
        import jax

        if self.phase != "window":
            return
        k = self.uploads
        self.uploads += 1
        if k < self.compare_rounds:
            self.buffers.append((np.array(ids), np.array(offsets),
                                 int(n_valid)))
        if k == self.first_live:
            self.window_t0 = time.perf_counter()
        if k >= self.first_live and self.trace:
            live = k - self.first_live
            if live == self.trace[0]:
                opts = jax.profiler.ProfileOptions()
                # the fill is a million Python calls a round: the
                # profiler's Python tracer would record each
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
                self.trace_t = [time.perf_counter(), None]
            elif live == self.trace[1]:
                self.stop_trace()

    def install(self):
        import jax
        from glint_word2vec_tpu.parallel.engine import EmbeddingEngine

        seam = self
        cls = EmbeddingEngine
        orig = {n: getattr(cls, n) for n in (
            "upload_corpus", "train_steps_corpus_packed", "noise_table",
            "set_noise_counts", "assign_extra_rows")}

        def upload(engine, ids, offsets, n_valid=None):
            seam._round(ids, offsets, n_valid)
            with jax.profiler.TraceAnnotation("bench.upload"):
                return orig["upload_corpus"](engine, ids, offsets,
                                             n_valid=n_valid)

        def packed(engine, *a, **k):
            name = None
            if seam.phase == "replay":
                if seam.groups == 0:
                    name = "first"
                elif seam.refreshed_after and "live" not in seam.captured:
                    name = "live"
                    seam.alias = (np.asarray(engine._prob),
                                  np.asarray(engine._alias))
            elif seam.phase == "window" and seam.window_t0 is not None:
                seam.window_dispatches += 1
            seam.groups += 1
            d = seam.cfg["model"]["vector_size"]

            def rows_now(cap):
                return tuple(table_rows(t, cap["rows"], d)
                             for t in (engine.syn0, engine.syn1))

            if name:
                t_check = time.perf_counter()
                cap = seam.captured[name] = capture(engine, seam.cfg, a, k)
                if name == "live":
                    cap["before"] = rows_now(cap)
                seam.check_seconds += time.perf_counter() - t_check
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                out = orig["train_steps_corpus_packed"](engine, *a, **k)
            if name:
                t_check = time.perf_counter()
                cap["losses"] = np.asarray(out[0], np.float32)
                cap["after"] = rows_now(cap)
                seam.check_seconds += time.perf_counter() - t_check
            return out

        def noise_table(engine, counts):
            seam.builds += 1
            return orig["noise_table"](engine, counts)

        def set_noise_counts(engine, counts, table=None):
            if seam.installs == seam.promotion_build:
                seam.refreshed_after = True
            seam.installs += 1
            return orig["set_noise_counts"](engine, counts, table)

        def assign_extra_rows(engine, words):
            first = seam.phase == "replay" and not seam.promoted
            seam.promoted = True
            if seam.promotion_build is None:
                # the round's refresh, which follows its promotion, is
                # the next table built
                seam.promotion_build = seam.builds
            if not first:
                return orig["assign_extra_rows"](engine, words)
            # the first burst: the rows it claims and a few past them,
            # before and after
            t_check = time.perf_counter()
            start = engine.vocab_size + engine.extra_rows_assigned
            rows = np.arange(start, min(start + len(words) + 8,
                                        engine.num_rows))
            before = [table_rows(t, rows) for t in (engine.syn0, engine.syn1)]
            seam.check_seconds += time.perf_counter() - t_check
            out = orig["assign_extra_rows"](engine, words)
            t_check = time.perf_counter()
            after = [table_rows(t, rows) for t in (engine.syn0, engine.syn1)]
            seam.misplaced = rows_misplaced(
                seam.seed, seam.cfg["model"]["vector_size"], rows,
                len(words), before, after)
            seam.check_seconds += time.perf_counter() - t_check
            return out

        cls.upload_corpus = upload
        cls.train_steps_corpus_packed = packed
        cls.noise_table = noise_table
        cls.set_noise_counts = set_noise_counts
        cls.assign_extra_rows = assign_extra_rows
        self._restore = (cls, orig)

    def stop_trace(self):
        import jax

        if self.trace_t and self.trace_t[1] is None:
            self.trace_t[1] = time.perf_counter()
            jax.profiler.stop_trace()

    def uninstall(self):
        cls, orig = self._restore
        for name, fn in orig.items():
            setattr(cls, name, fn)


def table_rows(table, rows, dim=None, chunk=1 << 17):
    """Host copy of ``table[rows]`` as float32, its first ``dim`` columns
    (all of them by default): gathered on the device a chunk at a time, so
    that beside the replay engine the reads stay under the window's own
    peak (memory_peak_bytes is the program's)."""
    import jax.numpy as jnp

    take = _take_program(dim)
    parts = []
    for s in range(0, rows.size, chunk):
        idx = rows[s:s + chunk]
        got = take(table, jnp.asarray(np.pad(idx, (0, chunk - idx.size))))
        parts.append(np.asarray(got)[:idx.size])
    return np.concatenate(parts)


@functools.lru_cache(maxsize=None)
def _take_program(dim):
    """table_rows' program, by the columns it keeps."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t, i: t[i][:, :dim].astype(jnp.float32))


@functools.lru_cache(maxsize=None)
def _draw_program(P, W, B, n, n_steps):
    """capture's program for one geometry: ``n_steps`` steps of ``P`` pair
    slots, window ``W``, grid batch ``B``, ``n`` negatives a pair. Every
    number of a call is an argument: a constant would compile anew."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from glint_word2vec_tpu.corpus.batching import context_width
    from glint_word2vec_tpu.ops.device_batching import (
        device_words_done,
        pack_window_pairs,
    )
    from glint_word2vec_tpu.ops.sampling import sample_negatives_per_row

    S = -(-3 * P // context_width(W))
    rows = jnp.arange(P, dtype=jnp.int32)

    @jax.jit
    def draw(base_key, ids, soffs, n_valid, prob, alias, pos0, step0,
             grid_step0, step_size, inv_total, words_base):
        def body(pos, i):
            key = jax.random.fold_in(base_key, step0 + i)
            pc, px, pm, n_cons, _ = pack_window_pairs(
                ids, soffs, pos, base_key, grid_step0, window=W, span=S,
                pair_batch=P, grid_batch=B, n_valid=n_valid,
            )
            pos_end = pos + n_cons
            done = device_words_done(soffs, soffs, pos_end, n_valid)
            wd = words_base + done.astype(jnp.float32)
            alpha = jnp.maximum(
                step_size * (1.0 - wd * inv_total), step_size * 1e-4)
            negs = sample_negatives_per_row(key, prob, alias, rows, (1, n))
            return pos_end, (pc, px, pm, negs[:, 0, :], alpha)

        return lax.scan(body, pos0, jnp.arange(n_steps, dtype=jnp.uint32))[1]

    return draw


def capture(engine, cfg, a, k) -> dict:
    """The batches of one packed dispatch, drawn as the scan's body draws
    them from what the engine holds and the arguments the trainer passed
    (``a``, ``k`` of ``train_steps_corpus_packed``): ``fold_in(base_key,
    step0 + i)`` keys, window-shrink draws pinned to ``grid_step0``,
    negatives keyed by global pair row from the alias table in force,
    alpha from the consumed position. Returns ``batches`` (a list of dicts
    of numpy arrays: centers, contexts, mask (P,), negs (P, n), alpha) and
    the padded sorted ``rows`` they touch."""
    import jax.numpy as jnp

    from benchmark import reference_stream

    pos0, P, W, B, base_key, n_steps = a[:6]
    ids, soffs = engine._corpus
    draw = _draw_program(P, W, B, cfg["model"]["negatives"], n_steps)
    pc, px, pm, negs, alphas = (np.asarray(x) for x in draw(
        base_key, ids, soffs, jnp.int32(engine._corpus_n_valid),
        engine._prob, engine._alias, jnp.int32(pos0),
        jnp.uint32(k["step0"]), jnp.uint32(k["grid_step0"]),
        jnp.float32(k["step_size"]),
        jnp.float32(1.0 / float(k["total_words"])),
        jnp.float32(k["words_base"])))
    batches = [
        {"centers": pc[i], "contexts": px[i], "mask": pm[i], "negs": negs[i],
         "alpha": alphas[i]}
        for i in range(n_steps)
    ]
    return {"batches": batches,
            "rows": reference_stream.touched_rows(batches)}


def _fit(cfg, path, seed, anneal_words, seam, obs=None, dtype=None,
         publish_dir=None):
    from glint_word2vec_tpu import Word2Vec
    from glint_word2vec_tpu.cli import _stream_sentences

    m, r = cfg["model"], cfg["run"]
    est = Word2Vec(
        obs=obs, vector_size=m["vector_size"], window=m["window"],
        num_negatives=m["negatives"], step_size=m["step_size"],
        subsample_ratio=m["subsample_ratio"], min_count=m["min_count"],
        batch_size=r["batch_size"], steps_per_call=r["steps_per_call"],
        num_shards=r["num_shards"], seed=int(seed),
        dtype=dtype or m["table_dtype"],
    )
    seam.pulled = Pulled(_stream_sentences(path, False, False))
    seam.groups = seam.builds = seam.installs = 0
    return est.fit_stream(
        seam.pulled, publish_dir=publish_dir,
        bootstrap_words=r["bootstrap_words"],
        buffer_words=r["buffer_words"],
        buffer_sentences=r["buffer_sentences"],
        extra_rows=m["extra_rows"], refresh_words=r["refresh_words"],
        promote_min_count=m["promote_min_count"],
        sketch_capacity=m["sketch_capacity"], anneal_words=anneal_words,
    )


def rows_misplaced(seed, dim, rows, n, before, after) -> int:
    """Rows of a promotion burst that are not what it should leave. The
    burst claimed the first ``n`` of ``rows``: each holds, in ``syn0``, the
    word2vec init keyed by the seed and its GLOBAL row (drawn here as the
    engine states it: ``uniform(fold_in(PRNGKey(seed), 2**30 + row))`` over
    the row's whole lanes, the columns past ``dim`` zero) and zeros in
    ``syn1``; the rows past them hold what they held. ``before`` / ``after``
    are host copies of (syn0, syn1)[rows]."""
    import jax
    import jax.numpy as jnp

    lanes = -(-dim // 128) * 128
    base = jax.random.PRNGKey(int(seed))
    keys = jax.vmap(lambda r: jax.random.fold_in(base, (1 << 30) + r))(
        jnp.asarray(rows[:n], jnp.int32))
    fresh = np.array(jax.vmap(lambda k: jax.random.uniform(
        k, (lanes,), jnp.float32, minval=-0.5 / dim, maxval=0.5 / dim))(keys))
    fresh[:, dim:] = 0.0
    want0 = np.concatenate([fresh, before[0][n:]])
    want1 = np.concatenate([np.zeros_like(fresh), before[1][n:]])
    return int(np.sum(np.any(after[0] != want0, axis=1)
                      | np.any(after[1] != want1, axis=1)))


def run(ctx):
    from benchmark import corpus_stream, reference, reference_stream

    cfg, traffic, args = ctx.cfg, ctx.traffic, ctx.args
    m, r = cfg["model"], cfg["run"]
    native = require_native_alias(m["vocab"])
    ctx.say(f"native alias builder loaded: {native}")
    require_one_promotion_program(ctx)
    prog_seed = int(args.seed) % (2**31 - 1)
    path = os.path.join(ctx.work, "stream.txt")
    prefix = os.path.join(ctx.work, "prefix.txt")
    t0 = time.perf_counter()
    sizes = corpus_stream.make_stream(
        path, m["vocab"], dict(traffic, bootstrap_tokens=r["bootstrap_words"]),
        args.seed, args.seconds,
        prefix_path=prefix,
        prefix_live_sentences=int(traffic["prefix_live_sentences"]))
    n_tokens = sizes["bootstrap_tokens"] + sizes["live_tokens"]
    ctx.say(f"stream: {sizes}, {os.path.getsize(path) >> 20} MiB in "
            f"{time.perf_counter() - t0:.2f}s")

    seam = Seam(cfg, prog_seed, sizes["bootstrap_sentences"],
                int(traffic["compare_rounds"]))
    seam.install()
    dtype = ctx.table_dtype  # None, or "bfloat16" for the control
    try:
        # -- set-up: the replay fit over the prefix (also the warm-up) --
        seam.phase = "replay"
        replay = _fit(cfg, prefix, prog_seed, n_tokens, seam, dtype=dtype)
        eng = replay.engine
        ctx.device = ctx.device_of(eng)
        devices = sorted(eng.syn0.sharding.device_set, key=lambda x: x.id)
        rtm = replay.training_metrics
        ctx.say(f"replay fit: {rtm['rounds']} rounds, {seam.groups} groups, "
                f"{rtm['promoted_words']} promoted, step body "
                f"{rtm.get('step_body')}, "
                f"{time.perf_counter() - ctx.t_start:.2f}s since start")
        if set(seam.captured) != {"first", "live"}:
            raise RuntimeError(
                "the replay fit's prefix brought no promotion and refresh: "
                f"captured {sorted(seam.captured)}")
        # the rounds of the prefix are the window's first rounds
        seam.first_live = rtm["rounds"] - rtm["live_rounds"]
        if not 0 < seam.first_live < rtm["rounds"]:
            raise RuntimeError(
                "the replay fit's prefix has no round of the bootstrap "
                f"window followed by a live one: {rtm['rounds']} rounds, "
                f"{rtm['live_rounds']} live")
        ctx.check_seconds += seam.check_seconds
        ctx.say("check reads inside the replay fit: " + ", ".join(
            f"{name} group {cap['rows'].size} touched rows"
            for name, cap in sorted(seam.captured.items()))
            + f", {seam.check_seconds:.2f}s (not counted in setup_s)")
        replay.stop()
        del replay, eng

        # -- the window -------------------------------------------------
        obs = None
        if args.trace:
            from glint_word2vec_tpu.obs import ObsConfig

            ctx.program_spans_path = os.path.join(ctx.work, "spans.json")
            obs = ObsConfig(chrome_trace=ctx.program_spans_path)
            seam.trace = tuple(traffic["trace_rounds"])
            seam.trace_dir = ctx.trace_dir
        seam.phase = "window"
        with ctx.count_compiles() as compiles:
            model = _fit(cfg, path, prog_seed, n_tokens, seam, obs=obs,
                         dtype=dtype)
            t_end = time.perf_counter()
        seam.stop_trace()
        ctx.trace_t = seam.trace_t
        seam.phase = "after"
        if seam.window_t0 is None:
            raise RuntimeError("the stream ended before a live round")
        ctx.window = (seam.window_t0, t_end)
        ctx.memory_peak_bytes = ctx.read_memory_peak()
        tm = ctx.training_metrics = model.training_metrics
        words = list(model.vocab.words)
        counts = np.asarray(model.vocab.counts, np.int64)
        model.stop()
        del model
    finally:
        seam.uninstall()

    window_s = ctx.window[1] - ctx.window[0]
    pulled = seam.pulled
    live_rounds = seam.uploads - seam.first_live

    # -- the reference, once the window has closed ----------------------
    numbers = []

    def compare(name, value, limit):
        numbers.append((name, float(value), float(limit)))

    t_ref = time.perf_counter()
    from glint_word2vec_tpu.cli import _stream_sentences

    ref = reference_stream.StreamReference(
        _stream_sentences(path, False, False),
        bootstrap_words=r["bootstrap_words"], min_count=m["min_count"],
        promote_min_count=m["promote_min_count"],
        extra_rows=m["extra_rows"], sketch_capacity=m["sketch_capacity"],
        buffer_words=r["buffer_words"],
        buffer_sentences=r["buffer_sentences"],
        refresh_words=r["refresh_words"] or r["buffer_words"],
        subsample_ratio=m["subsample_ratio"], seed=prog_seed)
    wrong = rounds = tokens_before = 0
    first_live = noise_after = None
    while True:
        rnd = ref.next_round()
        if rnd is None:
            break
        if first_live is None and rnd["live"]:
            first_live = rounds
        if first_live is None:
            tokens_before += rnd["raw_words"]
        if rounds < len(seam.buffers):
            ids, offsets, n_valid = seam.buffers[rounds]
            wrong += int(n_valid != rnd["fill"]) + int(
                np.sum(ids != rnd["ids"])) + int(
                    np.sum(offsets != rnd["offsets"]))
        if noise_after is None and rnd["promoted"]:
            noise_after = rnd["noise"]
        rounds += 1
    ctx.say(f"reference (host half): {rounds} rounds, {len(ref.promoted)} "
            f"promoted, {ref.words_trained} words trained, in "
            f"{time.perf_counter() - t_ref:.2f}s")
    tokens = pulled.tokens - tokens_before
    ctx.say(f"window: {live_rounds} live rounds (round {seam.first_live} "
            f"on), {tokens} raw words in {window_s:.3f}s, "
            f"{seam.window_dispatches} dispatch groups; program says "
            f"rounds={tm['rounds']} words_trained={tm['words_trained']} "
            f"promoted={tm['promoted_words']} steps={tm['steps']} loss "
            f"{tm['first_loss']} -> {tm['final_loss']}; refreshes "
            f"{tm.get('refreshes')}, alias_native {tm.get('alias_native')}")
    # (a) the host half, exact
    compare("host.buffer_words_wrong",
            wrong + abs(len(seam.buffers) - traffic["compare_rounds"]), 0)
    compare("host.rounds_gap", abs(rounds - tm["rounds"]), 0)
    # the window opened at the round the reference and the program's own
    # count call the first live one
    compare("host.first_live_gap",
            abs((first_live if first_live is not None else -1)
                - seam.first_live)
            + abs(tm["rounds"] - tm["live_rounds"] - seam.first_live), 0)
    base = ref.base_size
    compare("host.base_vocab_gap", abs(base - m["vocab"]), 0)
    compare("host.promotions_wrong",
            abs(len(words) - len(ref.words)) + sum(
                a != b for a, b in zip(words[base:], ref.words[base:])), 0)
    compare("host.counts_wrong",
            abs(len(counts) - len(ref.counts)) + int(np.sum(
                counts[:len(ref.counts)] != np.asarray(
                    ref.counts, np.int64)[:len(counts)])), 0)
    compare("host.words_trained_gap",
            abs(tm["words_trained"] - ref.words_trained), 0)
    compare("spare.rows_misplaced", seam.misplaced, 0)
    # (b) the adaptive distribution the engine draws from
    lim = traffic["limits"]
    prob, alias = seam.alias
    pmf = reference_stream.alias_pmf(prob, alias)
    compare("noise.table_entries_gap", abs(pmf.shape[0] - base), 0)
    compare("noise.pmf_l1_gap",
            np.abs(pmf[:base] - noise_after[:pmf.shape[0]]).sum()
            if noise_after is not None else float("inf"),
            lim["noise.pmf_l1_gap"])
    # (c) the device half: two dispatch groups
    d = m["vector_size"]
    for name, label in (("first", "replay"), ("live", "replay_live")):
        cap = seam.captured[name]
        rows, after = cap["rows"], cap["after"]
        if name == "first":  # from the seed's tables, drawn independently
            before0 = np.asarray(reference.seed_rows(
                prog_seed, m["vocab"] + m["extra_rows"], d, rows, devices))
            before = (before0, np.zeros_like(before0))
        else:
            before = cap["before"]
        gaps = reference_stream.replay_gaps(
            rows, cap["batches"], before[0], before[1], after[0], after[1],
            cap["losses"])
        for key in sorted(gaps):
            # the first group's limits, where the live group has none of
            # its own (PERF.md section 4 has the readings behind each)
            compare(f"{label}.{key}", gaps[key],
                    lim.get(f"{label}.{key}", lim[f"replay.{key}"]))
        cap.clear()  # gigabytes a group
        del before, after
    ctx.say(f"reference: compared in {time.perf_counter() - t_ref:.2f}s")
    # (d) the window itself
    first, final = tm["first_loss"], tm["final_loss"]
    ok_loss = (first is not None and final is not None
               and math.isfinite(first) and math.isfinite(final))
    compare("window.final_over_first_loss",
            final / first if ok_loss else float("inf"),
            lim["window.final_over_first_loss"])
    compare("window.words_not_streamed",
            abs(pulled.tokens - n_tokens), 0)
    in_window = [(t, e) for t, e in compiles if t >= ctx.window[0]]
    for when, event in in_window:
        ctx.say(f"compiled in the window at +{when - ctx.window[0]:.3f}s:"
                f" {event}")
    compare("window.compiles", len(in_window), 0)

    ctx.numbers = numbers
    ctx.attempted = seam.window_dispatches
    ctx.failed = 0
    ctx.end_to_end = {"train_words_per_s": tokens / window_s}
    ctx.notes = {"words": tokens, "window_s": window_s,
                 "live_rounds": live_rounds, "steps": tm["steps"]}
