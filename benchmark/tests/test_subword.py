"""The subword cell ``ft-300-1m-2mb.train``: CPU, tiny sizes, a synthetic
xplane. Run by hand like its neighbours:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_subword.py -q
"""

import json
import os
import sys
import types

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from test_benchmark import BENCH, BROKEN, KEYS, ROOT, bench, harness  # noqa: E402

CELL = "ft-300-1m-2mb.train"
NEW = ["step.compose_ms", "step.centre_share", "subword.rows_per_center",
       "subword_step_roofline"]
SHARED = ["fit.group_ms", "fit.harvest_share", "batcher.pack_fill",
          "step.device_ms", "step.index_ms", "step.gather_ms",
          "step.grads_ms", "step.scatter_ms", "step.unscoped_share",
          "scatter.distinct_share", "scatter.rows_per_slab",
          "device.idle_share.train"]


def _reader(name):
    from benchmark.run import load_module

    return load_module(os.path.join(BENCH, "layers", name + ".py"))


def test_the_new_names_resolve_to_files():
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == CELL)
    config = next(c for c in b["configs"] if c["name"] == cell["config"])
    assert cell["chips"] == 1 and config["reduced"] == ["vocab"]
    with open(os.path.join(ROOT, config["file"])) as f:
        cfg = json.load(f)
    m = cfg["model"]
    # the source's widths: fasttext skipgram's defaults at -dim 300
    assert (m["vector_size"], m["bucket"], m["min_n"], m["max_n"],
            m["negatives"], m["window"], m["subsample_ratio"]) == (
                300, 2_000_000, 3, 6, 5, 5, 1e-4)
    assert m["step_size"] in (0.05, 0.025) and m["table_dtype"] == "float32"
    assert cfg["architecture"] is None and cfg["reduced"] == ["vocab"]
    for key in ("source", "reduced_why", "assumed", "guarantee", "tiny"):
        assert cfg[key], key
    with open(os.path.join(BENCH, "traffic", CELL + ".json")) as f:
        traffic = json.load(f)
    assert traffic["kind"] == "train_subword"
    assert traffic["nominal_words_per_s"] % 10_000 == 0
    # every word once + the Zipf draws + 8 words a planted sentence
    assert (m["vocab"] - 44 + traffic["zipf_tokens"]
            + 8 * traffic["planted_sentences"]) == 5_639_956
    specs = {s["name"]: s for s in b["per_layer"]}
    for name in NEW:
        assert specs[name]["workloads"] == [CELL]
        assert specs[name]["moves"] == "train_words_per_s"
        assert callable(_reader(name).read)
    for name in SHARED:
        assert specs[name]["workloads"][-1] == CELL, name
    assert CELL not in specs["sgns_step_roofline"]["workloads"]


def test_fillers_look_like_words():
    from benchmark import corpus_words
    from benchmark.corpus import special_words

    special = special_words()[1]
    names = corpus_words.filler_names(20_000, 7, taken=special)
    assert names.size == 20_000 == np.unique(names).size
    assert not set(names) & set(special)
    lens = np.char.str_len(names)
    assert (np.diff(lens) >= 0).all()  # the short words at the head
    share = np.bincount(lens, minlength=13)[4:] / names.size
    np.testing.assert_allclose(
        share, np.asarray(corpus_words.LENGTH_SHARE) / 100, atol=1e-3)
    assert set("".join(names)) <= set("abcdefghijklmnopqrstuvwxyz")
    letters = np.bincount(
        np.frombuffer("".join(names).encode(), np.uint8) - ord("a"),
        minlength=26)
    assert letters.argmax() == ord("e") - ord("a")
    assert (corpus_words.filler_names(500, 7) ==
            corpus_words.filler_names(500, 7)).all()
    assert (corpus_words.filler_names(500, 7) !=
            corpus_words.filler_names(500, 8)).any()


def test_corpus_has_the_same_counts_for_every_seed(tmp_path):
    from benchmark import corpus_words

    traffic = {"zipf_tokens": 3000, "sentence_words": 40,
               "planted_sentences": 400}
    seen = set()
    for seed in (1, 2**31 + 11):
        path = tmp_path / f"c{seed}.txt"
        n = corpus_words.make_corpus(str(path), 600, traffic, seed)
        lines = path.read_text().splitlines()
        words = [w for line in lines for w in line.split()]
        assert n == len(words) == 600 - 44 + 3000 + 8 * 400
        assert len(set(words)) == 600
        seen.add((n, len(lines)))
    assert len(seen) == 1


def test_group_table_is_the_scalar_cutter_and_the_programs():
    """The benchmark's own table against a scalar transcription of the
    rule written here, and against the table the program builds."""
    from benchmark import reference_subword
    from glint_word2vec_tpu.corpus.subword import build_subword_table

    rng = np.random.default_rng(3)
    letters = list("abcdefghijklmnopqrstuvwxyz_0")
    words = ["".join(rng.choice(letters, size=rng.integers(1, 14)))
             for _ in range(300)] + ["a", "ab", "germany_t0"]

    def scalar(word, w_id, vocab, bucket, min_n, max_n, width):
        token, out = f"<{word}>", [w_id]
        for n in range(min_n, max_n + 1):
            for i in range(len(token) - n + 1):
                if n <= len(token) - 1:
                    h = 2166136261
                    for byte in token[i:i + n].encode():
                        h = ((h ^ byte) * 16777619) & 0xFFFFFFFF
                    out.append(vocab + h % bucket)
        return out[:width]

    for min_n, max_n, width, bucket in ((3, 6, 32, 2_000_000), (2, 4, 8, 97)):
        table = reference_subword.group_table(
            words, len(words), bucket, min_n, max_n, width)
        for w_id, w in enumerate(words):
            g = scalar(w, w_id, len(words), bucket, min_n, max_n, width)
            assert table[w_id].tolist() == g + [-1] * (width - len(g)), w
        ids, mask = build_subword_table(
            words, len(words), bucket, min_n, max_n, width)
        np.testing.assert_array_equal(table, np.where(mask > 0, ids, -1))
    with pytest.raises(UnicodeEncodeError):
        reference_subword.group_table(["wörter"], 1, 10, 3, 6, 8)


def test_replay_is_the_numpy_transcription_and_the_repos_reference():
    import jax.numpy as jnp

    from benchmark import reference, reference_subword
    from glint_word2vec_tpu.ops.grouped_reference import grouped_sgns_step

    rng = np.random.default_rng(0)
    words, buckets, dim, pairs, width = 60, 40, 8, 96, 5
    groups = words + rng.integers(0, buckets, (words, width)).astype(np.int32)
    groups[rng.random((words, width)) < 0.4] = -1
    groups[:, 0] = np.arange(words)
    batches = []
    for _ in range(4):
        contexts = rng.integers(0, words, pairs).astype(np.int32)
        negs = rng.integers(0, words, (pairs, 3)).astype(np.int32)
        negs[::7, 0] = contexts[::7]  # a negative equal to its context
        batches.append({
            "centers": rng.integers(0, words, pairs).astype(np.int32),
            "contexts": contexts, "negs": negs,
            "mask": (rng.random(pairs) > 0.15).astype(np.float32),
            "alpha": np.float32(0.05)})
    rows0 = np.unique(groups[groups >= 0])
    rows1 = np.arange(words)
    init = rng.normal(0, 0.1, (words + buckets, dim)).astype(np.float32)
    ref0, ref1, ref_losses = reference_subword.replay(
        init[rows0], rows0, rows1, groups, batches)
    # numpy: reference.sgns_step's equations, the centre a masked mean
    syn0, syn1 = init.copy(), np.zeros((words + buckets, dim), np.float32)
    j0, j1 = jnp.asarray(syn0), jnp.asarray(syn1)
    for b, ref_loss in zip(batches, np.asarray(ref_losses)):
        grp = groups[b["centers"]]
        live = (grp >= 0).astype(np.float32)
        count = live.sum(axis=1, keepdims=True)
        h = (syn0[np.maximum(grp, 0)] * live[..., None]).sum(axis=1) / count
        u_pos, u_neg = syn1[b["contexts"]], syn1[b["negs"]]
        f_pos = np.einsum("pd,pd->p", h, u_pos)
        f_neg = np.einsum("pd,pnd->pn", h, u_neg)
        nmask = (b["negs"] != b["contexts"][:, None]) * b["mask"][:, None]
        c_pos = b["alpha"] * (1 - reference._sigmoid(f_pos)) * b["mask"]
        c_neg = -b["alpha"] * reference._sigmoid(f_neg) * nmask
        loss = (-reference._log_sigmoid(f_pos) * b["mask"] - (
            reference._log_sigmoid(-f_neg) * nmask).sum(axis=1)
                ).sum() / b["mask"].sum()
        d = c_pos[:, None] * u_pos + np.einsum("pn,pnd->pd", c_neg, u_neg)
        reference.scatter_add(
            syn1, np.concatenate([b["contexts"], b["negs"].reshape(-1)]),
            np.concatenate([c_pos[:, None] * h, (
                c_neg[:, :, None] * h[:, None, :]).reshape(-1, dim)]))
        share = ((d / count)[:, None, :] * live[..., None]).reshape(-1, dim)
        reference.scatter_add(syn0, np.maximum(grp, 0).reshape(-1),
                              share.astype(np.float32))
        assert loss == pytest.approx(float(ref_loss), rel=1e-5)
        j0, j1, j_loss = grouped_sgns_step(
            j0, j1, jnp.asarray(grp), jnp.asarray(b["contexts"]),
            jnp.asarray(b["mask"]), jnp.asarray(b["negs"]), b["alpha"])
        assert float(j_loss) == pytest.approx(float(ref_loss), rel=1e-6)
    for got in (syn0, np.asarray(j0)):
        np.testing.assert_allclose(
            np.asarray(ref0), got[rows0], rtol=2e-5, atol=2e-7)
    for got in (syn1, np.asarray(j1)):
        np.testing.assert_allclose(
            np.asarray(ref1), got[rows1], rtol=2e-5, atol=2e-7)
    # the numbers the kind compares: zero against itself, far in bfloat16
    gaps = reference_subword.table_gaps(
        np.asarray(ref0), ref0, jnp.asarray(init[rows0]), rows0)
    assert gaps == (0.0, 0.0)
    import ml_dtypes

    low = np.asarray(ref0).astype(ml_dtypes.bfloat16).astype(np.float32)
    assert reference_subword.table_gaps(
        low, ref0, jnp.asarray(init[rows0]), rows0)[0] > 1e-3


def test_bytes_of_the_subword_step():
    from benchmark import bytes_subword

    got = bytes_subword.subword_step_bytes(8192, 5, 5, 300, 16.0)
    assert got["rows"] == 8192 * 16 + 26_215 * 6 == 288_362
    assert got["total"] == 3 * 288_362 * 300 * 4  # 1.04 GB a step


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(trace):
    doc, out = harness(CELL, "--trace", str(trace))
    assert set(doc) == KEYS | ({"breakdown"} if trace else set())
    assert doc["correct"] is True, out
    assert doc["device"]["platform"] == "cpu"  # a rehearsal, never a metric
    assert doc["attempted"] > 0 and doc["failed"] == 0
    b = bench()
    wanted = b["per_layer"] if trace else b["end_to_end"]
    listed = {m["name"] for m in wanted if CELL in m.get("workloads", [CELL])}
    assert set(doc["metrics"]) <= listed
    if not trace:
        assert set(doc["metrics"]) == listed
    else:  # the program's counter needs no chip
        assert 2 < doc["metrics"]["subword.rows_per_center"]["value"] <= 32
    assert "pipeline device_corpus" in out
    assert "0 rows differ from the device's" in out
    assert "compare groups.rows_differing: 0 " in out


def test_the_control_in_lower_precision_is_not_correct():
    doc, out = harness(CELL, "--trace", "0", "--control", "bf16")
    assert doc["correct"] is False, out


def test_a_broken_timed_path_is_not_correct():
    doc, out = harness(CELL, "--trace", "0", prelude=BROKEN["train"])
    assert doc["correct"] is False, out


HOST_PATH = """
from glint_word2vec_tpu.models.fasttext import FastTextWord2Vec
FastTextWord2Vec._device_corpus_eligible = lambda self, n=0: False
"""


def test_a_program_whose_subword_fit_takes_the_host_path_is_refused():
    import subprocess

    code = (f"import sys; sys.path.insert(0, {ROOT!r})\n" + HOST_PATH
            + "import benchmark.run as r\n"
            + f"sys.exit(r.main(['--workload', {CELL!r}, '--seed', '1', "
            "'--seconds', '1', '--trace', '0', '--tiny']))\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 1
    assert "does not take the corpus-resident path" in p.stdout
    assert "corpus:" not in p.stdout  # refused before anything is made


# One run of the packed subword scan, [0, 100] us: a while that spans the
# group rows' gather [5, 25], the masked mean under glint.compose [25, 35],
# syn1's gather [35, 45], and the two scatters [50, 70] and [70, 90].
TRACE = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 25000000 duration_ps: 10000000 }
    events { metadata_id: 4 offset_ps: 35000000 duration_ps: 10000000 }
    events { metadata_id: 6 offset_ps: 50000000 duration_ps: 20000000 }
    events { metadata_id: 7 offset_ps: 70000000 duration_ps: 20000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 5 offset_ps: 0 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%while.1 = (...)" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/shard_map/while" } } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.2" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/shard_map/while/body/closed_call/glint.gather/syn0/gather" } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.3" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/shard_map/while/body/closed_call/glint.compose/reduce_sum" } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.4" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/shard_map/while/body/closed_call/glint.gather/syn1/gather" } } }
  event_metadata { key: 5 value { id: 5 name: "jit_local_packed_scan(123)" } }
  event_metadata { key: 6 value { id: 6 name: "%fusion.6" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/shard_map/while/body/closed_call/glint.scatter/syn0/scatter-add" } } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.7" stats { metadata_id: 1 str_value: "jit(local_packed_scan)/shard_map/while/body/closed_call/glint.scatter/syn1/scatter-add" } } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
}
"""


def _run(tmp_path, text, platform="tpu"):
    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(text))
    said = []
    return types.SimpleNamespace(
        trace=trace_reduce.reduce_profile(
            ProfileData.from_text_proto(text), 100e-6),
        trace_dir=str(tmp_path), say=said.append, said=said,
        device={"platform": platform, "kind": "TPU v5 lite", "count": 1},
        cfg={"model": {"window": 5, "negatives": 5, "vector_size": 32,
                       "table_dtype": "float32"},
             "run": {"batch_size": 256, "steps_per_call": 2,
                     "num_shards": 1}},
        training_metrics={"subword_rows_per_center": 16.0},
        program_spans_path=None, program_spans=[])


def test_the_four_readers_on_a_synthetic_xplane(tmp_path):
    run = _run(tmp_path, TRACE)
    # 10 us over two steps
    assert _reader("step.compose_ms").read(run) == pytest.approx(5e-3)
    # compose 10 + syn0's gather 20 + syn0's scatter 20, of 100 us
    assert _reader("step.centre_share").read(run) == pytest.approx(50.0)
    assert _reader("subword.rows_per_center").read(run) == 16.0
    # (256 x 16 + 820 x 6) rows x 3 x 32 x 4 B at 819 GB/s over 50 us
    need = 3 * (256 * 16 + 820 * 6) * 32 * 4
    assert _reader("subword_step_roofline").read(run) == pytest.approx(
        100 * need / 819e9 / 50e-6)
    # the old readers take the gathers of both tables together
    assert _reader("step.gather_ms").read(run) == pytest.approx(15e-3)
    assert _reader("step.scatter_ms").read(run) == pytest.approx(20e-3)


def test_a_program_without_the_scope_or_the_counter_reads_as_nothing(tmp_path):
    # the parent: one gather scope, no compose, no count of group rows
    old = TRACE.replace("glint.gather/syn0", "glint.gather").replace(
        "glint.gather/syn1", "glint.gather").replace(
            "glint.compose", "glint.gather")
    run = _run(tmp_path, old)
    run.training_metrics = {}
    for name in NEW:
        assert _reader(name).read(run) is None, name
    assert _reader("step.gather_ms").read(run) == pytest.approx(20e-3)
    run = _run(tmp_path, TRACE, platform="cpu")
    assert _reader("subword_step_roofline").read(run) is None  # a chip's peak
    run = _run(tmp_path, TRACE)
    run.trace = None
    for name in ("step.compose_ms", "step.centre_share",
                 "subword_step_roofline"):
        assert _reader(name).read(run) is None, name
