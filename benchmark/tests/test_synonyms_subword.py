"""The served subword cell ``ft-nn-300-1m-2mb.synonyms``: CPU, tiny sizes, a
synthetic xplane. Run by hand like its neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_synonyms_subword.py -q

``FAULTS`` are the two faults the cell's ``correct`` has to catch, planted
underneath the harness; on the chip they are planted by hand from here:

    python3 -c "import sys; sys.path[:0] = ['.', 'benchmark/tests']; \\
        import test_synonyms_subword as t; exec(t.FAULTS['word_row']); \\
        import benchmark.run as r; sys.exit(r.main([...]))"
"""

import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import BENCH, KEYS, ROOT, bench, harness  # noqa: E402
from test_subword import _reader  # noqa: E402

CELL = "ft-nn-300-1m-2mb.synonyms"
NEW = {"serve.compose_ms": ("serving host", "synonyms_p95_ms"),
       "compose.device_ms": ("query programs", "synonyms_p95_ms"),
       "serve.compose_fill": ("serving host", "synonyms_p95_ms")}
#: The accepted readers that move a metric the cell reports. The cell is off
#: ``synonyms_qps`` (PERF.md section 7: a rate 13 times the parent's cannot
#: be admitted under a bound reckoned from the parent's), and so off the
#: readers that move it.
SHARED = ["serve.queue_wait_ms", "serve.post_warmup_compiles",
          "serve.round_ms", "serve.pull_ms", "serve.grace_ms"]

#: Run in a child before the harness: the timed path broken underneath it.
FAULTS = {
    # an out-of-dictionary word answered with its nearest dictionary
    # word's vector (what a server that "corrects" the word would give)
    "nearest_word": """
from glint_word2vec_tpu.models.fasttext import FastTextModel
import numpy as np
real = FastTextModel.compose_oov
def nearest(self, words):
    vectors, errors, slots, rows = real(self, words)
    for i, v in enumerate(vectors):
        if v is not None:
            (w, _), = self.find_synonyms_vector(v, 1)
            row = np.array([self.vocab.word_index[w]], np.int32)
            vectors[i] = np.asarray(self._query_engine().pull(row))[0]
    return vectors, errors, slots, rows
FastTextModel.compose_oov = nearest
""",
    # an out-of-dictionary word's group led by a word row it does not have
    "word_row": """
from glint_word2vec_tpu.models.fasttext import FastTextModel
import numpy as np
real = FastTextModel._oov_groups
def with_a_word_row(self, words):
    g, m = real(self, words)
    g[:, 1:], m[:, 1:] = g[:, :-1].copy(), m[:, :-1].copy()
    g[:, 0] = [len(w) for w in words]  # some dictionary word's own row
    return g, m
FastTextModel._oov_groups = with_a_word_row
""",
}


def test_the_new_names_resolve_to_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    config = next(c for c in b["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and cell["traffic"] == "synonyms"
    assert config["reduced"] == ["vocab"]
    assert len(config["source"]) <= 200 and "fasttext nn" in config["source"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    m = cfg["model"]
    # the query commands over a model of the crawl vectors' sizes
    assert (m["vocab"], m["bucket"], m["vector_size"], m["min_n"],
            m["max_n"], m["max_subwords"], m["table_dtype"], m["window"],
            m["negatives"]) == (
                1_000_000, 2_000_000, 300, 5, 5, 16, "float32", 5, 10)
    assert cfg["run"] == {"num_shards": 1}
    assert cfg["serve"] == {"max_batch": 64, "cache_size": 65536}
    assert cfg["architecture"] is None and cfg["reduced"] == ["vocab"]
    assert cfg["source"] == config["source"]
    for key in ("deployment", "departure", "assumed", "guarantee",
                "reduced_why", "source_detail"):
        assert cfg[key], key
    assert cfg["tiny"]["model"] == {
        "vocab": 2000, "bucket": 4000, "vector_size": 32}
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "synonyms_subword"
    assert os.path.exists(os.path.join(BENCH, "kinds", "synonyms_subword.py"))
    assert (traffic["callers"], traffic["num"], traffic["zipf_exponent"],
            traffic["oov_share"], traffic["cache_warm_words"],
            traffic["table_std"], traffic["checked_answers"],
            traffic["checked_oov"]) == (16, 10, 1.0, 0.15, 16384, 0.1, 48, 16)
    assert traffic["oov_edits"] == ["drop", "double", "swap"]
    assert traffic["limits"]["answers.score_gap"] == 1e-5
    # the word-level serving cell's load: the same callers, num and window
    with open(os.path.join(BENCH, "traffic", "w2v-300-2m.synonyms.json")) as f:
        sibling = json.load(f)
    for key in ("callers", "num", "zipf_exponent", "table_std",
                "checked_answers", "trace_window_s"):
        assert traffic[key] == sibling[key], key
    specs = {s["name"]: s for s in b["per_layer"]}
    for name, (layer, moves) in NEW.items():
        assert CELL in specs[name]["workloads"]
        assert (specs[name]["layer"], specs[name]["moves"]) == (layer, moves)
        assert callable(_reader(name).read)
    for name in SHARED:
        assert CELL in specs[name]["workloads"], name
    e2e = {s["name"]: s for s in b["end_to_end"]}
    for name in ("synonyms_p50_ms", "synonyms_p95_ms"):
        assert CELL in e2e[name]["workloads"]
    # every reader that lists the cell moves a metric the cell reports
    reported = {n for n, s in e2e.items() if CELL in s.get("workloads", [CELL])}
    for name, spec in specs.items():
        if CELL in spec.get("workloads", [CELL]):
            assert spec["moves"] in reported, name


def _seeded_words(n, seed):
    rng = np.random.default_rng(seed)
    out = set()
    while len(out) < n:
        out.add("".join(rng.choice(list("abcdefghijklmnop"),
                                   size=rng.integers(2, 14))))
    return sorted(out)


@pytest.mark.parametrize("min_n,max_n,width", [(5, 5, 16), (3, 6, 8)])
def test_the_references_groups_are_subword_groups(min_n, max_n, width):
    """Dictionary words and words outside it, against the program's scalar
    cutter (``corpus/subword.subword_group``), short words, cut groups and
    words too short for any n-gram among them."""
    from benchmark import reference_nn
    from glint_word2vec_tpu.corpus.subword import subword_group

    words = _seeded_words(400, 1)
    bucket = 977
    grp = reference_nn.groups(words, bucket, min_n, max_n, width)
    for i, w in enumerate(words):
        ids = subword_group(w, i, len(words), bucket, min_n, max_n, width)
        assert list(grp[i][grp[i] >= 0]) == ids, w
        assert (grp[i][len(ids):] == -1).all()
    outside = [w for w in _seeded_words(300, 2) if w not in set(words)]
    got = reference_nn.oov_groups(outside, len(words), bucket, min_n, max_n,
                                  width)
    assert got.shape == (len(outside), width)
    some_empty = False
    for w, g in zip(outside, got):
        ids = subword_group(w, None, len(words), bucket, min_n, max_n, width)
        assert list(g[g >= 0]) == ids, w
        some_empty = some_empty or not ids
    assert some_empty or min_n < 4


def test_the_numpy_reference_is_the_repos_plain_reference():
    """``reference_nn`` (numpy, blocked) against ``ops/nn_reference.py``
    (the source's form, word by word), dictionary and outside words."""
    from benchmark import reference_nn
    from glint_word2vec_tpu.ops import nn_reference

    words = _seeded_words(150, 3)
    V, bucket, d = len(words), 211, 24
    geometry = dict(bucket=bucket, min_n=3, max_n=5, max_subwords=8)
    syn0 = np.random.default_rng(4).normal(
        0, 0.1, (V + bucket, d)).astype(np.float32)
    grp = reference_nn.groups(words, bucket, 3, 5, 8)
    composed = reference_nn.compose(syn0, grp)
    np.testing.assert_allclose(
        composed, np.asarray(nn_reference.word_vectors(
            syn0, words, **geometry)), rtol=0, atol=1e-7)
    nn = reference_nn.NN(composed)
    for query in (words[17], "notamongthem", words[3] + "x"):
        row = words.index(query) if query in words else None
        vec = (composed[row] if row is not None else reference_nn.compose(
            syn0, reference_nn.oov_groups([query], V, bucket, 3, 5, 8))[0])
        want = nn_reference.nn(syn0, words, query, 10, **geometry)
        cos = nn.cosines(vec[None])[:, 0]
        got = [(words.index(w), s) for w, s in want]
        assert nn.gap(row, cos, got, 10) < 1e-6
        assert nn.gap(row, cos, got[:9], 10) == float("inf")
        swapped = [got[1], got[0]] + got[2:]
        assert nn.gap(row, cos, swapped, 10) == pytest.approx(
            abs(want[0][1] - want[1][1]), abs=1e-6)
    # a dictionary word's answer may not hold the word itself
    cos = nn.cosines(composed[5][None])[:, 0]
    assert nn.gap(5, cos, [(5, 1.0)] + got[:9], 10) == float("inf")


def test_the_traffic_is_the_issues():
    """15% of the requests are one edit away from a dictionary word of five
    letters or more and are in no dictionary; the rest are Zipf draws."""
    from benchmark.kinds import synonyms_subword as kind

    words, _ = kind.vocabulary(3000, 5)
    index = {w: i for i, w in enumerate(words)}
    traffic = {"zipf_exponent": 1.0, "oov_share": 0.15,
               "oov_edits": ["drop", "double", "swap"]}
    sent, is_oov = kind.request_words(words, index, traffic, 20000, 5)
    again, _ = kind.request_words(words, index, traffic, 20000, 5)
    assert sent == again  # the seed's
    assert 0.13 < is_oov.mean() < 0.17
    for w, outside in zip(sent, is_oov):
        assert (w not in index) == bool(outside)
        if outside:
            assert 4 <= len(w) <= 13
    # a word whose every edit is a dictionary word does not hold the draw
    trap = ["eeee", "eeeee", "eeeeee", "abcdefgh"]
    sent, is_oov = kind.request_words(
        trap, {w: i for i, w in enumerate(trap)},
        dict(traffic, oov_share=1.0), 200, 6)
    assert is_oov.all() and not set(sent) & set(trap)
    assert kind.edit("abcde", "drop", 0.5) == "abde"
    assert kind.edit("abcde", "double", 0.5) == "abccde"
    assert kind.edit("abcde", "swap", 0.99) == "abced"


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace):
    doc, out = harness(CELL, "--trace", str(trace))
    assert set(doc) == KEYS | ({"breakdown"} if trace else set())
    assert doc["correct"] is True, out
    assert doc["device"]["platform"] == "cpu"  # a rehearsal, never a metric
    assert doc["attempted"] > 0 and doc["failed"] == 0
    b = bench()
    wanted = b["per_layer"] if trace else b["end_to_end"]
    listed = {m["name"] for m in wanted
              if CELL in m.get("workloads", [CELL])}
    assert set(doc["metrics"]) <= listed
    if trace:
        assert {"serve.compose_ms", "serve.compose_fill", "serve.round_ms",
                "serve.pull_ms"} <= set(doc["metrics"])
    else:
        assert set(doc["metrics"]) == listed
    for name in ("answers.score_gap", "answers.oov_too_few_sampled",
                 "groups.rows_differing", "composed.row_gap"):
        assert f"compare {name}:" in out


def test_the_control_in_lower_precision_is_not_correct():
    doc, out = harness(CELL, "--trace", "0", "--control", "bf16")
    assert doc["correct"] is False, out
    assert "compare answers.score_gap" in out


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(fault):
    doc, out = harness(CELL, "--trace", "0", prelude=FAULTS[fault])
    assert doc["correct"] is False, out
    line = next(x for x in out.splitlines()
                if "compare answers.score_gap" in x)
    assert line.endswith("NOT OK"), line


TRACE = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 50000000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 60000000 duration_ps: 30000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 50000000 duration_ps: 2000000 }
    events { metadata_id: 4 offset_ps: 60000000 duration_ps: 30000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1" stats { metadata_id: 1 str_value: "jit(local_pull_average)/shard_map/glint.gather/gather" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2" stats { metadata_id: 1 str_value: "jit(local_topk_batch)/shard_map/dot_general" } } }
  event_metadata { key: 3 value { id: 3 name: "jit_local_pull_average(77)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_local_topk_batch(78)" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
"""


def _run(tmp_path, spans, before, after):
    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    return types.SimpleNamespace(
        trace=trace_reduce.reduce_profile(
            ProfileData.from_text_proto(TRACE), 100e-6),
        trace_dir=str(tmp_path), say=lambda *_: None,
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        program_spans=spans, program_spans_path=None,
        serving_metrics=after, serving_metrics_before=before)


def test_the_three_readers(tmp_path, monkeypatch):
    from benchmark import program_trace

    spans = [{"name": "req.compose", "ph": "X", "ts": 10.0 * k, "dur": dur}
             for k, dur in enumerate((800.0, 1200.0, 1000.0))]
    monkeypatch.setattr(program_trace, "ring_spans", lambda run, name: [
        (e["ts"] / 1e6, e["dur"] / 1e6) for e in run.program_spans
        if e["name"] == name])
    counters = lambda s, r: {"compose": {  # noqa: E731
        "group_slots_total": s, "group_rows_total": r}}
    run = _run(tmp_path, spans, counters(1000, 300), counters(5000, 1500))
    assert _reader("serve.compose_ms").read(run) == pytest.approx(1.0)
    # two runs of the pull-average program, 4 us and 2 us
    assert _reader("compose.device_ms").read(run) == pytest.approx(3e-3)
    assert _reader("serve.compose_fill").read(run) == pytest.approx(30.0)
    # the top-k's reader does not take the compose for a top-k
    assert _reader("topk.device_ms").read(run) == pytest.approx(30e-3)


def test_a_program_without_the_span_or_the_counters_reads_as_nothing(
        tmp_path, monkeypatch):
    """The parent of PR 43: no ``req.compose``, no ``compose`` in
    ``/metrics``. Each reader returns None and does not raise."""
    from benchmark import program_trace

    monkeypatch.setattr(program_trace, "ring_spans", lambda run, name: [])
    run = _run(tmp_path, [], {"synonym_cache": {}}, {"synonym_cache": {}})
    assert _reader("serve.compose_ms").read(run) is None
    assert _reader("serve.compose_fill").read(run) is None
    run.trace = None
    assert _reader("compose.device_ms").read(run) is None
