#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, in ONE process, through the entry points a user
calls, at the full width of ``BASELINE.json`` configs[1] (1,000,000-word
vocabulary, d=300, 5 negatives, f32 tables — bench.py's headline shape):

  seeded synthetic corpus -> ``cli train`` (the default fit: device-resident
  corpus, on-device subsampling, dense packing, the packed scan) ->
  ``model.save`` -> ``load_model``
  -> ``cli serve`` (``serve_model_dir``, warm-up included) answering
  ``/healthz``, ``/synonyms``, ``/transform`` and ``/metrics`` over loopback.

and checks what comes out: loss finite and below its first value, the step
body that ran, loaded tables equal to the saved files, ``/synonyms`` equal to
a plain numpy float32 cosine top-k over the loaded table, ``/transform``
equal to numpy means, zero compiles after warm-up. The first failed check
ends the run non-zero.

    python chip_smoke.py                # one TPU chip; what the driver runs
    python chip_smoke.py --chips 4      # ONLY the row-sharded path on a 1x4
                                        # mesh against the one-device result
    JAX_PLATFORMS=cpu python chip_smoke.py --platform cpu --tiny
                                        # rehearsal of the control flow

The LAST line on stdout is one JSON object, nothing after it:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``,
the device read from where the tables live. Everything else worth reading
(phase seconds, compile cache state, loss, step body, native library state)
is on earlier lines and in ``chiprun_out/chip_smoke.json``. Without a TPU it
fails at once; it never sets ``JAX_PLATFORMS`` (``--platform cpu`` only says
what the caller pinned, and is checked against what JAX reports).
"""

import argparse
import json
import os
import shutil
import sys
import threading
import time
import traceback
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".chip_smoke_work")  # corpus, models, port file
REPORT = os.path.join(HERE, "chiprun_out", "chip_smoke.json")

#: The real size, and the rehearsal's. Depth (corpus, steps) is what is cut;
#: the full run keeps every width of the configuration.
FULL = dict(vocab=1_000_000, dim=300, zipf_tokens=3_000_000,
            planted_sentences=80_000, batch=8192, steps_per_call=32)
TINY = dict(vocab=2_000, dim=32, zipf_tokens=60_000,
            planted_sentences=3_000, batch=256, steps_per_call=4)
WINDOW, NEGATIVES, STEP_SIZE, TOP_K = 5, 5, 0.025, 10
#: Frequency subsampling, as every real word2vec run on Zipf text uses: at
#: bench.py's batch of 8192 positions the unsubsampled fit sums ~400 same-row
#: updates of the most frequent word per synchronous step and reaches NaN
#: within 200 steps (any backend; found on the CPU rehearsal at full size).
#: It also puts the on-device subsample-compact pass on the path.
SUBSAMPLE = 1e-3

PAIRS = [("germany", "berlin"), ("france", "paris"), ("austria", "vienna"),
         ("spain", "madrid"), ("italy", "rome"), ("poland", "warsaw")]
RELATION = ["capital", "city", "of", "the", "is", "has", "famous", "for"]

OUT = sys.stdout  # the one stream the last line goes to
REPORT_DOC = {"phases": {}, "compile_seconds": {}, "checks": []}
#: Seconds XLA spent compiling (jax.monitoring), split by phase on exit.
COMPILE = {"seconds": 0.0, "programs": 0}


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", file=OUT, flush=True)


class SmokeFailure(Exception):
    pass


def check(name: str, ok: bool, detail: str = "") -> None:
    """One check. The first that fails ends the run; with ``--keep-going``
    the run goes on to show the later ones and still ends non-zero."""
    if name == ARGS.fail_check:  # test hook: see tests/test_chip_smoke.py
        ok, detail = False, f"forced by --fail-check ({detail})"
    REPORT_DOC["checks"].append({"name": name, "ok": bool(ok)})
    say(f"check {name}: {'ok' if ok else 'FAILED'}  {detail}")
    if not ok and not ARGS.keep_going:
        raise SmokeFailure(f"{name}: {detail}")


class phase:
    """Times one phase onto an earlier line and into the report."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.t0, self.c0 = time.time(), dict(COMPILE)
        say(f"phase {self.name} ...")
        return self

    def __exit__(self, exc_type, *_):
        dt = round(time.time() - self.t0, 2)
        comp = round(COMPILE["seconds"] - self.c0["seconds"], 2)
        REPORT_DOC["phases"][self.name] = dt
        REPORT_DOC["compile_seconds"][self.name] = comp
        say(f"phase {self.name}: {dt}s, of which compiling {comp}s "
            f"({COMPILE['programs'] - self.c0['programs']} programs)"
            f"{' (raised)' if exc_type else ''}")


# ----------------------------------------------------------------------
# Corpus: seeded, a vocabulary of exactly `vocab` words, planted pairs
# ----------------------------------------------------------------------


def make_corpus(path: str, size: dict, seed: int):
    """Write the corpus; return (filler names by frequency rank, n_tokens).

    Every filler word appears at least once (``--min-count 1`` keeps them
    all) and the rest are Zipf draws, so index skew is realistic. Planted
    (country, capital) sentences, as tests/conftest.py::_make_tiny_corpus
    builds them, give the loss something to learn and ``/synonyms`` an
    answer to check."""
    import numpy as np

    rng = np.random.default_rng(seed)
    theme = {c: [f"{c}_t{j}" for j in range(4)] for c, _ in PAIRS}
    special = ([w for p in PAIRS for w in p] + RELATION
               + [t for ts in theme.values() for t in ts])
    n_filler = size["vocab"] - len(special)
    names = np.array([f"w{i:07d}" for i in range(n_filler)])
    p = 1.0 / np.arange(1, n_filler + 1)
    tokens = np.concatenate([
        rng.permutation(n_filler),
        rng.choice(n_filler, size=size["zipf_tokens"], p=p / p.sum()),
    ])
    rng.shuffle(tokens)
    sent = 40
    lines = [" ".join(names[tokens[i:i + sent]])
             for i in range(0, tokens.size, sent)]
    n_tokens = int(tokens.size)
    some = names[:40]  # frequent filler as noise inside planted sentences
    for _ in range(size["planted_sentences"]):
        country, capital = PAIRS[rng.integers(len(PAIRS))]
        th = list(rng.choice(theme[country], size=2))
        noise = list(rng.choice(some, size=2))
        style = rng.integers(4)
        if style == 0:
            s = [capital, "is", "the", "capital", "of", country] + th
        elif style == 1:
            s = [th[0], country, "capital", "city", capital, th[1]] + noise
        elif style == 2:
            s = [country, "has", "capital", capital] + th + noise
        else:
            x = country if rng.random() < 0.5 else capital
            s = [x, "famous", "for"] + th + noise
        lines.append(" ".join(s))
        n_tokens += len(s)
    order = rng.permutation(len(lines))
    with open(path, "w") as f:
        f.write("\n".join(lines[i] for i in order))
        f.write("\n")
    return names, n_tokens


# ----------------------------------------------------------------------
# Train / load / reference
# ----------------------------------------------------------------------


def cli_train(corpus: str, out_dir: str, size: dict, seed: int,
              num_shards: int, tag: str = "train") -> dict:
    """``cli train`` in this process; returns its ``training_metrics``."""
    from glint_word2vec_tpu import cli

    metrics_path = out_dir + ".metrics.json"
    rc = cli.main([
        "train", "--corpus", corpus, "--output", out_dir,
        "--vector-size", str(size["dim"]), "--window", str(WINDOW),
        "--negatives", str(NEGATIVES), "--step-size", str(STEP_SIZE),
        "--batch-size", str(size["batch"]),
        "--steps-per-call", str(size["steps_per_call"]),
        "--subsample-ratio", str(SUBSAMPLE),
        "--min-count", "1", "--iterations", "1", "--seed", str(seed),
        "--num-shards", str(num_shards), "--metrics-out", metrics_path,
    ])
    check(f"{tag}.exit_code", rc == 0, f"rc={rc}")
    with open(metrics_path) as f:
        return json.load(f)


def check_training(tm: dict, tag: str = "train") -> None:
    import math

    first, last = tm.get("first_loss"), tm.get("final_loss")
    say(f"{tag}: steps={tm['steps']} words_done={tm['words_done']} "
        f"wall={tm['wall_seconds']}s words/s={tm['words_per_sec']} "
        f"(host clock, compile included: an observation, not a metric) "
        f"loss first={first} last={last} pipeline={tm['pipeline']} "
        f"packing={tm.get('batch_packing')} step_body={tm.get('step_body')}")
    check(f"{tag}.default_fit_path",
          tm["pipeline"] == "device_corpus"
          and tm.get("batch_packing") == "dense",
          f"pipeline={tm['pipeline']} packing={tm.get('batch_packing')}")
    # The body names the writer its scatters end in: the slab writer where
    # the program is lowered for a TPU (ops/slab_writer.py), XLA's elsewhere.
    writer = "slab" if ARGS.platform == "tpu" else "xla"
    check(f"{tag}.step_body",
          tm.get("step_body") == f"rows/per_pair/{writer}",
          str(tm.get("step_body")))
    check(f"{tag}.loss_finite",
          first is not None and last is not None
          and math.isfinite(first) and math.isfinite(last),
          f"first={first} last={last}")
    check(f"{tag}.loss_fell", last < first, f"{first} -> {last}")


def read_saved_table(matrix_dir: str, name: str):
    """One table as ``engine.save`` wrote it (either format), from disk."""
    import numpy as np

    with open(os.path.join(matrix_dir, "engine.json")) as f:
        meta = json.load(f)
    if meta.get("format", "single") != "sharded":
        return np.load(os.path.join(matrix_dir, f"{name}.npy"))
    blocks = sorted(meta["shards"][name], key=lambda b: b["start"])
    axis = 0 if blocks[0].get("axis", "rows") == "rows" else 1
    return np.concatenate(
        [np.load(os.path.join(matrix_dir, b["file"])) for b in blocks],
        axis=axis,
    )


def load_and_compare(model_dir: str, vocab: int, dim: int):
    """``load_model`` -> (model, {"syn0", "syn1"}: host copies of the
    vocabulary rows), after checking the loaded tables against the saved
    files bit for bit."""
    import numpy as np

    from glint_word2vec_tpu import load_model

    model = load_model(model_dir)
    eng = model.engine
    check("load.geometry",
          model.vocab.size == vocab and model.vector_size == dim,
          f"V={model.vocab.size} d={model.vector_size}")
    host = {}
    for name in ("syn0", "syn1"):
        saved = read_saved_table(os.path.join(model_dir, "matrix"), name)
        host[name] = np.asarray(getattr(eng, name))[:vocab, :dim]
        check(f"load.{name}_equals_saved",
              np.array_equal(host[name], saved[:vocab, :dim]),
              f"shape={host[name].shape} dtype={host[name].dtype}")
    check("load.trained", float(np.abs(host["syn1"]).max()) > 0.0,
          "syn1 starts at zero; training must have moved it")
    return model, host


def device_of(engine) -> dict:
    """The device as the tables' own arrays report it."""
    dev = next(iter(engine.syn0.devices()))
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": int(engine.mesh.devices.size)}


class Reference:
    """Plain numpy float32 cosine top-k over the loaded table."""

    def __init__(self, syn0, words):
        import numpy as np

        self.np = np
        self.w = np.ascontiguousarray(syn0, dtype=np.float32)
        self.norms = np.linalg.norm(self.w, axis=1)
        self.words = words
        self.index = {w: i for i, w in enumerate(words)}

    def cosines(self, word: str):
        np = self.np
        v = self.w[self.index[word]]
        v = v / np.linalg.norm(v)
        safe = np.where(self.norms > 0, self.norms, 1.0)
        return np.where(self.norms > 0, (self.w @ v) / safe, -np.inf)

    def agrees(self, word: str, got, k: int, tol: float):
        """Whether ``got`` ([[word, score], ...]) is the reference top-k of
        ``word``: the same words in the same order, where a swap is
        accepted only between reference scores closer than ``tol`` (two
        summation orders cannot rank a near-tie alike), and every score
        within ``tol`` of the reference's for that word."""
        np = self.np
        cos = self.cosines(word)
        cos[self.index[word]] = -np.inf  # the query word is not an answer
        order = np.argsort(-cos, kind="stable")[:k]
        if len(got) != k:
            return False, f"{len(got)} results for k={k}"
        worst = 0.0
        for j, (w, s) in enumerate(got):
            i = self.index.get(w)
            if i is None:
                return False, f"unknown word {w!r}"
            worst = max(worst, abs(float(s) - float(cos[i])),
                        abs(float(cos[i]) - float(cos[order[j]])))
        exact = [w for w, _ in got] == [self.words[i] for i in order]
        return worst <= tol, (
            f"{'identical order' if exact else 'near-tie swaps only'}, "
            f"max |score - reference| = {worst:.2e} (tol {tol:g})"
        )


# ----------------------------------------------------------------------
# Serve
# ----------------------------------------------------------------------


def http(port: int, path: str, body=None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        headers={"Content-Type": "application/json"} if data else {},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        raw = r.read()
        ctype = r.headers.get("Content-Type", "")
    return json.loads(raw) if "json" in ctype else raw.decode()


def serve_and_query(model_dir: str, ref: Reference, names, size: dict,
                    tol: float) -> dict:
    """The stack ``cli serve`` builds, on a background thread of this
    process; real HTTP over loopback; stopped before this returns."""
    import numpy as np

    from glint_word2vec_tpu import cli

    port_file = os.path.join(WORK, "serve.port.json")
    rc = {}
    t0 = time.time()
    th = threading.Thread(
        target=lambda: rc.update(rc=cli.main([
            "serve", "--model", model_dir, "--host", "127.0.0.1",
            "--port", "0", "--port-file", port_file,
        ])),
        name="chip-smoke-serve", daemon=True,
    )
    th.start()
    port = None
    try:
        # The port file appears once the whole shape family is warm.
        while not os.path.exists(port_file):
            if not th.is_alive():
                raise SmokeFailure(f"cli serve exited early: {rc}")
            if time.time() - t0 > 1000:
                raise SmokeFailure("serve warm-up exceeded 1000 s")
            time.sleep(0.2)
        with open(port_file) as f:
            port = json.load(f)["port"]
        warm_s = round(time.time() - t0, 2)
        REPORT_DOC["phases"]["serve.load_and_warmup"] = warm_s

        hz = http(port, "/healthz")
        say(f"serve: port {port}, load + warm-up {warm_s}s, "
            f"{hz['compiles']} query programs warmed")
        check("serve.healthz",
              hz["status"] == "ok" and hz["vocab_size"] == size["vocab"]
              and hz["dim"] == size["dim"], json.dumps(hz)[:200])

        # Sequential single queries (Q bucket 1) ...
        singles = ["austria", "berlin", str(names[0]), str(names[1234])]
        for w in singles:
            got = http(port, "/synonyms", {"word": w, "num": TOP_K})
            ok, why = ref.agrees(w, got, TOP_K, tol)
            check(f"serve.synonyms[{w}]", ok, why)
            if w == "austria":
                check("serve.planted_pair_learned",
                      "vienna" in [x for x, _ in got],
                      f"top-{TOP_K} of austria: {[x for x, _ in got]}")
        # ... then a concurrent burst, so the coalescer batches (Q bucket 8).
        burst = [c for c, _ in PAIRS] + [str(names[7]), str(names[99])]
        results = {}

        def ask(w):
            try:
                results[w] = http(port, "/synonyms",
                                  {"word": w, "num": TOP_K})
            except Exception as e:  # surfaces in the check below
                results[w] = e

        threads = [threading.Thread(target=ask, args=(w,)) for w in burst]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for w in burst:
            if isinstance(results[w], Exception):
                raise SmokeFailure(f"/synonyms {w!r}: {results[w]!r}")
            ok, why = ref.agrees(w, results[w], TOP_K, tol)
            check(f"serve.synonyms_burst[{w}]", ok, why)

        sents = [["austria", "vienna", str(names[1])], ["germany"],
                 ["not-a-word", "berlin"]]
        got = np.asarray(http(port, "/transform", {"sentences": sents}),
                         np.float32)
        want = np.stack([
            ref.w[[ref.index[w] for w in s if w in ref.index]].mean(axis=0)
            for s in sents
        ])
        err = float(np.abs(got - want).max())
        check("serve.transform", got.shape == want.shape and err <= 1e-5,
              f"max |mean - reference| = {err:.2e}")

        m = http(port, "/metrics")
        prom = http(port, "/metrics?format=prometheus")
        check("serve.metrics_prometheus",
              isinstance(prom, str) and "glint_" in prom,
              f"{len(prom)} bytes")
        say(f"serve: compiles {m['compiles']} batches "
            f"{m['coalesced_batch_sizes']} "
            f"/synonyms p50 {m['endpoints']['/synonyms']['p50_ms']} ms "
            "(host clock; an observation)")
        check("serve.zero_post_warmup_compiles",
              m["compiles"]["post_warmup"] == 0, json.dumps(m["compiles"]))
        return {"warmup_seconds": warm_s, "compiles": m["compiles"]}
    finally:
        if port is not None and th.is_alive():
            try:
                http(port, "/shutdown", {})
            except (urllib.error.URLError, OSError) as e:
                say(f"serve: /shutdown failed: {e!r}")
        th.join(timeout=60)
        if port is not None:
            check("serve.stopped", not th.is_alive() and rc.get("rc") == 0,
                  f"thread alive={th.is_alive()} rc={rc}")


# ----------------------------------------------------------------------
# The two runs
# ----------------------------------------------------------------------


def run_one_chip(size: dict, tol: float) -> dict:
    corpus = os.path.join(WORK, "corpus.txt")
    model_dir = os.path.join(WORK, "model")
    with phase("corpus"):
        names, n_tokens = make_corpus(corpus, size, ARGS.seed)
        say(f"corpus: {n_tokens} tokens, vocabulary {size['vocab']}, "
            f"{os.path.getsize(corpus) >> 20} MiB, seed {ARGS.seed}")
    with phase("train_and_save"):
        tm = cli_train(corpus, model_dir, size, ARGS.seed, num_shards=1)
    check_training(tm)
    with phase("load"):
        model, host = load_and_compare(model_dir, size["vocab"], size["dim"])
        device = device_of(model.engine)
        check("load.on_one_device", device["count"] == 1, json.dumps(device))
        ref = Reference(host["syn0"], list(model.vocab.words))
        del host
        model.stop()  # the server loads its own copy
    with phase("serve"):
        serve_and_query(model_dir, ref, names, size, tol)
    return device


def run_four_chips(size: dict, tol: float) -> dict:
    """Only the row-sharded path (1x4 mesh, ``--num-shards 4``) and what it
    is compared with: the one-device result from the same seed."""
    import jax
    import numpy as np

    from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    check("devices.four", len(jax.devices()) >= 4,
          f"{len(jax.devices())} device(s)")
    corpus = os.path.join(WORK, "corpus.txt")
    with phase("corpus"):
        names, n_tokens = make_corpus(corpus, size, ARGS.seed)
        say(f"corpus: {n_tokens} tokens, vocabulary {size['vocab']}")

    with phase("init_tables"):
        # Same seed -> the same initial tables on every mesh shape.
        counts = np.ones(size["vocab"], np.int64)
        inits = []
        for shape in ((1, 1), (1, 4)):
            eng = EmbeddingEngine(make_mesh(*shape), size["vocab"],
                                  size["dim"], counts, seed=ARGS.seed)
            inits.append(np.asarray(eng.syn0)[:size["vocab"]])
            eng.destroy()
        check("sharded.init_tables_identical",
              np.array_equal(inits[0], inits[1]),
              f"max |diff| = {float(np.abs(inits[0] - inits[1]).max()):.2e}")
        del inits

    def fit_on(shards: int, tag: str, groups=None, load: bool = False):
        """``cli train`` from the same seed on ``shards`` devices, stopped
        after ``groups`` dispatch groups (the fit's own
        GLINT_PACKED_STOP_AFTER_GROUPS hook; None = the whole epoch).
        Returns (metrics, the saved tables, the loaded model or None)."""
        model_dir = os.path.join(WORK, f"model_{tag}")
        if groups:
            os.environ["GLINT_PACKED_STOP_AFTER_GROUPS"] = str(groups)
        try:
            with phase(tag):
                tm = cli_train(corpus, model_dir, size, ARGS.seed, shards,
                               tag)
        finally:
            os.environ.pop("GLINT_PACKED_STOP_AFTER_GROUPS", None)
        if groups:
            check(f"{tag}.stopped_after_{groups}_groups",
                  tm["steps"] == groups * size["steps_per_call"],
                  f"steps={tm['steps']}")
        else:
            check_training(tm, tag)
        if load:
            with phase(f"load_{tag}"):
                model, host = load_and_compare(model_dir, size["vocab"],
                                               size["dim"])
            return tm, host, model
        host = {
            name: read_saved_table(os.path.join(model_dir, "matrix"),
                                   name)[:size["vocab"], :size["dim"]]
            for name in ("syn0", "syn1")
        }
        shutil.rmtree(model_dir)  # 2.4 GB the rest does not need
        return tm, host, None

    def worst_entry(a: dict, b: dict) -> dict:
        """max |a - b| over every entry, in units of the table's largest."""
        out = {}
        for name in ("syn0", "syn1"):
            scale = float(np.abs(a[name]).max())
            worst = float(np.abs(a[name] - b[name]).max())
            out[name] = {"max": worst, "scale": scale, "rel": worst / scale}
        return out

    # The sharded step IS the one-device step up to the scatter's summation
    # order, so while rounding is all that separates them the two runs agree
    # entry by entry: to 1e-5 of the largest entry after ONE dispatch group
    # and after TWO (measured on four chips: 1.2e-6 and 2.3e-6), the second
    # putting the carry from one dispatch to the next (position, step
    # counter, keys, alpha) under test as well. No further: between steps 64
    # and 128 this fit turns a last-bit difference into a different table
    # (PERF.md section 6), while a sharding fault moves entries by 1e-2 of
    # the scale and more from the first step.
    for groups in (1, 2):
        _, one, _ = fit_on(1, f"1shard_{groups}group", groups)
        _, four, _ = fit_on(4, f"4shard_{groups}group", groups)
        dist = worst_entry(one, four)
        say(f"sharded vs one device after {groups} dispatch group(s), "
            f"{groups * size['steps_per_call']} steps: {json.dumps(dist)}")
        check(f"sharded.tables_agree_after_{groups}_groups",
              all(v["rel"] <= 1e-5 for v in dist.values()),
              "max |diff| <= 1e-5 * max |table| for syn0 and syn1")
        del one, four

    # The whole epoch: entrywise equality is gone by then (the fit is
    # chaotic on its hottest rows; ROADMAP S8), so the runs are held to the
    # loss here and to the numpy top-k on the sharded tables below.
    tm1, full1, _ = fit_on(1, "1shard")
    tm4, full4, model = fit_on(4, "4shard", load=True)
    device = device_of(model.engine)
    check("sharded.mesh_is_1x4", device["count"] == 4
          and dict(model.engine.mesh.shape) == {"data": 1, "model": 4},
          f"{dict(model.engine.mesh.shape)}")
    per_dev = {str(s.device): int(s.data.nbytes)
               for s in model.engine.syn0.addressable_shards}
    say(f"sharded: syn0 bytes per device {per_dev}")
    # a table as it rests: rows of whole lanes (engine.TABLE_LANES)
    whole = size["vocab"] * model.engine.padded_dim * 4
    check("sharded.rows_spread",
          len(per_dev) == 4 and max(per_dev.values()) <= whole // 4 + 4096,
          f"largest shard {max(per_dev.values())} of {whole} bytes")
    l1, l4 = tm1["final_loss"], tm4["final_loss"]
    say(f"sharded vs one device after the epoch: loss {l1} vs {l4}; "
        f"{json.dumps(worst_entry(full1, full4))} (observed, not gated)")
    check("sharded.epoch_loss_agrees", abs(l4 - l1) <= 1e-2 * abs(l1),
          f"|{l4} - {l1}| <= 1e-2 * {l1}")
    sharded_syn0 = full4["syn0"]
    del full1, full4

    with phase("topk_sharded"):
        ref = Reference(sharded_syn0, list(model.vocab.words))
        for w in ["austria", "berlin", str(names[0]), str(names[1234])]:
            got = [[x, float(s)] for x, s in model.find_synonyms(w, TOP_K)]
            ok, why = ref.agrees(w, got, TOP_K, tol)
            check(f"sharded.synonyms[{w}]", ok, why)
        many = [c for c, _ in PAIRS] + [str(names[7]), str(names[99])]
        vecs = np.stack([ref.w[ref.index[w]] for w in many])
        hits = model.find_synonyms_batch(vecs, TOP_K + 1)
        for w, hs in zip(many, hits):
            got = [[x, float(s)] for x, s in hs if x != w][:TOP_K]
            ok, why = ref.agrees(w, got, TOP_K, tol)
            check(f"sharded.synonyms_batch[{w}]", ok, why)
    model.stop()
    return device


def main() -> int:
    import jax  # never sets JAX_PLATFORMS: JAX finds what there is

    from glint_word2vec_tpu import native
    from glint_word2vec_tpu.utils.platform import enable_compile_cache

    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    REPORT_DOC["device_found"] = found
    say(f"jax {jax.__version__} found {found}")
    if found["platform"] != ARGS.platform:
        say(f"FAILED: this run needs platform {ARGS.platform!r} and JAX "
            f"found {found['platform']!r}; no fallback")
        return finish(False, found)

    # Floor of 0 s: the ~50 small serving programs are kept as well.
    cache_dir = enable_compile_cache(0.0)
    entries = len(os.listdir(cache_dir)) if (
        cache_dir and os.path.isdir(cache_dir)) else 0
    cache = {"dir": cache_dir, "entries_at_start": entries,
             "state": "off" if not cache_dir
             else "warm" if entries else "cold", "hits": 0, "misses": 0}

    def on_event(event, **_):
        if event.endswith("/cache_hits"):
            cache["hits"] += 1
        elif event.endswith("/cache_misses"):
            cache["misses"] += 1

    def on_duration(event, seconds, **_):
        if event.endswith("/backend_compile_duration"):
            COMPILE["seconds"] += seconds
            COMPILE["programs"] += 1

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    REPORT_DOC["compile_cache"] = cache
    say(f"compile cache: {cache['state']} at {cache_dir} "
        f"({entries} entries at start)")
    lib = native.get_lib()
    REPORT_DOC["native_library_loaded"] = lib is not None
    say("native host library: "
        + ("loaded" if lib is not None else "NOT loaded (Python fallbacks)"))

    size = TINY if ARGS.tiny else FULL
    # /synonyms against the numpy float32 reference: the same on every
    # platform, single or coalesced (the scoring contractions run at
    # HIGHEST precision, engine._score).
    tol = 1e-5
    shutil.rmtree(WORK, ignore_errors=True)
    os.makedirs(WORK)
    device, ok = found, False
    try:
        run = run_four_chips if ARGS.chips == 4 else run_one_chip
        device = run(size, tol)
        ok = all(c["ok"] for c in REPORT_DOC["checks"])
    except SmokeFailure as e:
        say(f"FAILED: {e}")
    except Exception:
        say("FAILED: " + traceback.format_exc().strip().splitlines()[-1])
        traceback.print_exc(file=sys.stderr)
    finally:
        shutil.rmtree(WORK, ignore_errors=True)
    say(f"compile cache at end: hits {cache['hits']} misses "
        f"{cache['misses']} (was {cache['state']} at start)")
    say(f"phase seconds: {json.dumps(REPORT_DOC['phases'])}")
    say(f"compile seconds: {json.dumps(REPORT_DOC['compile_seconds'])}")
    return finish(ok, device)


def finish(ok: bool, device) -> int:
    """The report file, then the one line the contract reads — last."""
    REPORT_DOC.update(ok=ok, device=device, tiny=ARGS.tiny,
                      chips=ARGS.chips, seed=ARGS.seed)
    try:
        os.makedirs(os.path.dirname(REPORT), exist_ok=True)
        with open(REPORT, "w") as f:
            json.dump(REPORT_DOC, f, indent=1)
    except OSError as e:
        say(f"could not write {REPORT}: {e}")
    sys.stderr.flush()
    print(json.dumps({"ok": ok, "device": device}), file=OUT, flush=True)
    # Nothing may follow that line: whatever still writes to fd 1 during
    # teardown (a library's exit notice, a straggling thread) goes nowhere.
    os.dup2(os.open(os.devnull, os.O_WRONLY), OUT.fileno())
    return 0 if ok else 1


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the row-sharded 1x4 path and the "
                         "one-device run it is compared with")
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="the platform the caller gave JAX; checked, "
                         "never set. cpu is for rehearsals")
    ap.add_argument("--tiny", action="store_true",
                    help="rehearsal size (control flow only)")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--fail-check", default=None, metavar="NAME",
                    help="force the named check to fail (test hook)")
    ap.add_argument("--keep-going", action="store_true",
                    help="show every failed check, not only the first; "
                         "the run still ends non-zero")
    ARGS = ap.parse_args()
    # Everything the package prints (cli.py's result lines) goes to stderr;
    # stdout carries this script's lines only, the contract's line last.
    sys.stdout = sys.stderr
    try:
        code = main()
    except Exception:
        # JAX or the package would not even import, or no backend came up.
        traceback.print_exc(file=sys.stderr)
        say("FAILED before the run began: "
            + traceback.format_exc().strip().splitlines()[-1])
        code = finish(False, None)
    sys.exit(code)
