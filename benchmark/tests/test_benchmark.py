"""The benchmark's own tests: CPU, tiny sizes. Outside ``pytest tests/``
(this directory is the benchmark's), so run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- BENCHMARK.json and the files its names resolve to ------------------


def test_benchmark_json_names_and_units():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = ([c["name"] for c in b["configs"]]
             + [w["name"] for w in b["workloads"]]
             + [w["traffic"] for w in b["workloads"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]]
             + [k for c in b["configs"] for k in c["reduced"]])
    for n in names:
        assert NAME.match(n), n
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    metric_names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert "setup_s" in metric_names
    assert sum(w["chips"] == 4 for w in b["workloads"]) <= max(
        1, len(b["workloads"]) // 4)
    for m in b["end_to_end"]:
        assert 0 < m["bound"] <= 0.1


def test_every_name_resolves_to_a_file():
    """A later PR adds a configuration, a traffic mix or a per-layer metric
    as new files plus entries: every name is found by name alone."""
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    cells = {w["name"]: w for w in b["workloads"]}
    for w in b["workloads"]:
        cfg_path = os.path.join(ROOT, configs[w["config"]]["file"])
        assert os.path.isfile(cfg_path), cfg_path
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        traffic = os.path.join(BENCH, "traffic", w["name"] + ".json")
        with open(traffic) as f:
            kind = json.load(f)["kind"]
        assert os.path.isfile(os.path.join(BENCH, "kinds", kind + ".py"))
        with open(cfg_path) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == configs[w["config"]]["reduced"]
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert os.path.isfile(
            os.path.join(BENCH, "layers", m["name"] + ".py")), m["name"]
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)
    for cell in cells:  # setup_s, another end-to-end, one per-layer
        assert sum(cell in m.get("workloads", cells)
                   for m in b["end_to_end"]) >= 2
        assert any(cell in m.get("workloads", cells) for m in b["per_layer"])


# -- the yardstick ------------------------------------------------------


def test_bytes_at_the_roadmap_figures():
    from benchmark import bytes as bytes_model

    grid = bytes_model.step_bytes(8192, 7, 5, 300, 4)
    assert grid["rows"] == 352_256
    assert grid["gather"] == 422_707_200  # "423 MB of rows"
    assert grid["scatter"] == 845_414_400  # "another 845 MB"
    assert abs(grid["total"] / 1e9 - 1.27) < 0.005  # "about 1.27 GB"
    packed = bytes_model.packed_step_bytes(8192, 5, 5, 300, 4)
    assert packed["rows"] == 26_215 * 7
    assert bytes_model.topk_dispatch_bytes(1_000_000, 300) == 1_200_000_000


def test_packed_pair_slots_is_the_programs_rule():
    from benchmark import bytes as bytes_model
    from glint_word2vec_tpu.corpus.batching import packed_pair_batch

    for b, w in ((8192, 5), (256, 5), (1024, 2), (64, 8), (8192, 1)):
        assert bytes_model.packed_pair_slots(b, w) == packed_pair_batch(b, w)


TRACE = """
planes { name: "/device:TPU:0"
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 4000000 }
    events { metadata_id: 3 offset_ps: 20000000 duration_ps: 5000000 }
  }
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 4 offset_ps: 0 duration_ps: 25000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "while.1" } }
  event_metadata { key: 2 value { id: 2 name: "scatter.3" } }
  event_metadata { key: 3 value { id: 3 name: "all-reduce.2" } }
  event_metadata { key: 4 value { id: 4 name: "jit_local_topk_batch(123)" } }
}
planes { name: "/host:CPU"
  lines { name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 12000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.dispatch" } }
}
"""


def test_trace_reduction_on_a_synthetic_xplane():
    from jax.profiler import ProfileData

    from benchmark import trace_reduce

    r = trace_reduce.reduce_profile(ProfileData.from_text_proto(TRACE), 40e-6)
    # while [0,10us] holds scatter [1,5us]; all-reduce [20,25us]: the union
    # is 15 us, not the 19 us a sum of durations gives.
    assert r["busy_s"] == pytest.approx(15e-6)
    assert r["ops_self_s"]["while.1"] == pytest.approx(6e-6)
    assert r["ops_self_s"]["scatter.3"] == pytest.approx(4e-6)
    assert r["collective_s"] == pytest.approx(5e-6)
    assert r["modules"]["jit_local_topk_batch(123)"] == {
        "count": 1, "seconds": pytest.approx(25e-6)}
    assert r["breakdown"]["device_ops"][0][0] == "while.1"
    # the one idle gap, [10,20us], lies under the host's bench.dispatch
    assert r["breakdown"]["idle_gaps"] == [
        ["bench.dispatch", pytest.approx(10e-6)]]


def _step_setup(dtype):
    import jax
    import jax.numpy as jnp

    from glint_word2vec_tpu.corpus.alias import build_unigram_alias
    from glint_word2vec_tpu.ops import sgns
    from glint_word2vec_tpu.ops.sampling import sample_negatives_per_row

    V, d, P, n = 50, 16, 24, 5
    rng = np.random.default_rng(3)
    syn0 = rng.normal(0, 0.3, (V, d)).astype(np.float32)
    syn1 = rng.normal(0, 0.3, (V, d)).astype(np.float32)
    centers = rng.integers(0, V, P).astype(np.int32)
    contexts = rng.integers(0, V, P).astype(np.int32)
    mask = (rng.random(P) < 0.9).astype(np.float32)
    t = build_unigram_alias(np.arange(1, V + 1))
    key, alpha = jax.random.PRNGKey(11), 0.05
    new0, new1, loss = jax.jit(sgns.train_step,
                               static_argnames="num_negatives")(
        jnp.asarray(syn0, dtype), jnp.asarray(syn1, dtype),
        jnp.asarray(t.prob), jnp.asarray(t.alias), jnp.asarray(centers),
        jnp.asarray(contexts)[:, None], jnp.asarray(mask)[:, None], key,
        jnp.float32(alpha), num_negatives=n)
    negs = np.asarray(sample_negatives_per_row(
        key, jnp.asarray(t.prob), jnp.asarray(t.alias),
        jnp.arange(P, dtype=jnp.int32), (1, n)))[:, 0, :]
    return (syn0, syn1, centers, contexts, mask, negs, alpha,
            np.asarray(new0, np.float32), np.asarray(new1, np.float32),
            float(loss))


@pytest.mark.parametrize("dtype,passes", [("float32", True),
                                          ("bfloat16", False)])
def test_numpy_step_reference_against_the_programs_step(dtype, passes):
    """The reference equals ``ops.sgns.train_step`` given the same
    negatives in float32, and tells bfloat16 tables apart."""
    from benchmark import reference

    (syn0, syn1, centers, contexts, mask, negs, alpha, new0, new1,
     loss) = _step_setup(dtype)
    ref0, ref1 = syn0.copy(), syn1.copy()
    ref_loss = reference.sgns_step(ref0, ref1, centers, contexts, mask, negs,
                                   alpha)
    gap = max(np.abs(new0 - ref0).max() / np.abs(ref0 - syn0).max(),
              np.abs(new1 - ref1).max() / np.abs(ref1 - syn1).max())
    assert (gap < 1e-4) == passes, gap
    if passes:
        assert abs(loss - ref_loss) < 1e-5 * ref_loss


def test_replay_in_jax_numpy_follows_the_numpy_step():
    """``sgns_replay`` (plain jax.numpy, what a run uses) against
    ``sgns_step`` (numpy) over several steps with repeated rows."""
    from benchmark import reference

    rng = np.random.default_rng(9)
    V, d, P, n, steps = 40, 8, 64, 5, 6
    rows = np.sort(rng.choice(1000, V, replace=False))
    init = rng.normal(0, 0.3, (V, d)).astype(np.float32)
    batches = [{
        "centers": rows[rng.integers(0, V, P)],
        "contexts": rows[rng.integers(0, V, P)],
        "mask": (rng.random(P) < 0.9).astype(np.float32),
        "negs": rows[rng.integers(0, V, (P, n))],
        "alpha": np.float32(0.05 - 0.001 * i),
    } for i in range(steps)]
    got0, got1, got_losses = reference.sgns_replay(init, rows, batches)
    want0, want1 = init.copy(), np.zeros_like(init)
    for b, got_loss in zip(batches, got_losses):
        loc = {k: np.searchsorted(rows, b[k])
               for k in ("centers", "contexts", "negs")}
        want = reference.sgns_step(want0, want1, loc["centers"],
                                   loc["contexts"], b["mask"], loc["negs"],
                                   b["alpha"])
        assert got_loss == pytest.approx(want, rel=1e-5)
    np.testing.assert_allclose(got0, want0, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(got1, want1, rtol=1e-4, atol=1e-6)


def test_topk_reference_gap():
    from benchmark import reference

    rng = np.random.default_rng(5)
    table = rng.normal(0, 0.1, (300, 16)).astype(np.float32)
    top = reference.TopK(table)
    cos = top.cosines(np.asarray([7]))[:, 0]
    order = [i for i in np.argsort(-cos) if i != 7][:10]
    good = [(int(i), float(cos[i])) for i in order]
    assert top.gap(7, cos, good, 10) < 1e-6
    wrong = [good[1], good[0]] + good[2:]
    assert top.gap(7, cos, wrong, 10) == pytest.approx(
        abs(cos[order[0]] - cos[order[1]]), rel=1e-3)
    assert top.gap(7, cos, good[:9], 10) == float("inf")


# -- whole runs of the harness at --tiny sizes ---------------------------

CELLS = {"train": "w2v-300-2m.train", "synonyms": "w2v-300-2m.synonyms"}

#: Run in a child: the timed path broken underneath the harness.
BROKEN = {
    # a step that returns its state unchanged
    "train": """
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
import jax.numpy as jnp
real = EmbeddingEngine.train_steps_corpus_packed
def unchanged(self, *a, **k):
    keep = jnp.copy(self.syn0), jnp.copy(self.syn1)
    out = real(self, *a, **k)
    self.syn0, self.syn1 = keep
    return out
EmbeddingEngine.train_steps_corpus_packed = unchanged
""",
    # an answer altered where it is produced
    "synonyms": """
from glint_word2vec_tpu.parallel.engine import EmbeddingEngine
import numpy as np
real = EmbeddingEngine.top_k_cosine_batch
def altered(self, *a, **k):
    vals, idx = real(self, *a, **k)
    return vals, np.roll(idx, 1, axis=1)
EmbeddingEngine.top_k_cosine_batch = altered
""",
}


def harness(cell, *extra, prelude=""):
    code = (f"import sys; sys.path.insert(0, {ROOT!r})\n" + prelude
            + "import benchmark.run as r\n"
            + f"sys.exit(r.main({['--workload', cell, '--seed', '2147483659', '--seconds', '1', '--tiny', *extra]!r}))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stdout


@pytest.mark.parametrize("kind", sorted(CELLS))
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_the_contracts_last_line(kind, trace):
    doc, out = harness(CELLS[kind], "--trace", str(trace))
    assert set(doc) == KEYS | ({"breakdown"} if trace else set())
    assert doc["correct"] is True, out
    assert doc["device"]["platform"] == "cpu"  # a rehearsal, never a metric
    assert doc["attempted"] > 0 and doc["failed"] == 0
    b = bench()
    wanted = b["per_layer"] if trace else b["end_to_end"]
    listed = {m["name"] for m in wanted
              if CELLS[kind] in m.get("workloads", [CELLS[kind]])}
    assert set(doc["metrics"]) <= listed
    if not trace:
        assert set(doc["metrics"]) == listed
    for v in doc["metrics"].values():
        assert set(v) == {"value", "unit"}
    assert {"busy_s", "window_s"} <= set(doc["device"]) or not trace
    assert "compare " in out  # each number compared, beside its limit


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_the_control_in_lower_precision_is_not_correct(kind):
    doc, out = harness(CELLS[kind], "--trace", "0", "--control", "bf16")
    assert doc["correct"] is False, out


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_a_broken_timed_path_is_not_correct(kind):
    doc, out = harness(CELLS[kind], "--trace", "0", prelude=BROKEN[kind])
    assert doc["correct"] is False, out


def test_no_run_without_the_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         CELLS["train"], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")
    assert '"correct"' not in p.stdout
