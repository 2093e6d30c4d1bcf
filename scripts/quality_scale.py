"""Quality at a 10M-word training budget: framework vs the independent
numpy control at matched trained-pair budget.

Round-4 verdict #6 asks for analogy accuracy beyond the 116k-word
fixture at a >=10M-word budget. This container has no larger real
corpus (zero egress; the reference fixture is the only natural text on
disk), so the corpus is the fixture's real German sentences
BOOTSTRAP-RESAMPLED with replacement to the target word count — same
vocabulary and distribution, 86x the training budget. That provenance
is recorded in the artifact: this measures quality at SCALE OF BUDGET,
not corpus diversity, and says so.

Budget matching (same convention as QUALITY.json's matched cell): the
control follows the C-tool window (width window-b per side, ~7
pairs/center); the framework implements the reference's narrower
windows (mllib:381-390, ~3.8 pairs/center; measured 461k vs 248k
pairs/epoch) — so 1 control epoch ~= 2 framework epochs at equal
trained pairs. Both subsample at 1e-3 with their own RNGs.

Writes QUALITY_SCALE.json. Env: GLINT_QS_WORDS (default 10_000_000),
GLINT_QS_SEEDS (default 3), GLINT_QS_CORPUS (reuse an existing built
corpus file).
"""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = os.environ.get("GLINT_EVAL_PLATFORM", "cpu")
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np  # noqa: E402

FIXTURE = "/root/reference/de_wikipedia_articles_country_capitals.txt"
OUT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "QUALITY_SCALE.json",
)


def build_corpus(target_words: int, path: str, seed: int = 0) -> int:
    """Bootstrap-resample fixture sentences (with replacement) to
    ``target_words``; returns the actual word count."""
    with open(FIXTURE, encoding="utf-8") as f:
        lines = [ln.strip() for ln in f if ln.split()]
    lens = np.array([len(ln.split()) for ln in lines], dtype=np.int64)
    rng = np.random.default_rng(seed)
    total = 0
    tmp = path + ".building"
    with open(tmp, "w", encoding="utf-8") as f:
        while total < target_words:
            for i in rng.integers(0, len(lines), 4096):
                f.write(lines[int(i)] + "\n")
                total += int(lens[int(i)])
                if total >= target_words:
                    break
    # Atomic: a run killed mid-build must never leave a partial corpus
    # that a later run's existence check would silently reuse.
    os.replace(tmp, path)
    return total


def main():
    from reference_quality import _mean_sd, analogy_questions, gates

    from glint_word2vec_tpu import Word2Vec
    from glint_word2vec_tpu.eval import evaluate_analogies
    from glint_word2vec_tpu.parallel.mesh import make_mesh

    target = int(os.environ.get("GLINT_QS_WORDS", 10_000_000))
    n_seeds = int(os.environ.get("GLINT_QS_SEEDS", 3))
    corpus = os.environ.get("GLINT_QS_CORPUS", "/tmp/quality_scale_corpus.txt")
    if not os.path.exists(corpus):
        actual = build_corpus(target, corpus)
    else:
        actual = sum(len(ln.split()) for ln in open(corpus, encoding="utf-8"))

    doc = {
        "metric": "quality_at_10m_word_budget",
        "corpus_words": actual,
        "corpus_provenance": (
            "reference fixture sentences bootstrap-resampled with "
            "replacement (no larger real corpus exists in this "
            "zero-egress container) — measures budget scale, not corpus "
            "diversity"
        ),
        "budget_note": (
            "1 control epoch ~= 2 framework epochs at equal trained "
            "pairs (window-convention ratio ~1.86, see QUALITY.json "
            "matched cell)"
        ),
        "n_seeds": n_seeds,
    }
    questions = analogy_questions()

    fw_rows = []
    for s in range(1, 1 + n_seeds):
        t0 = time.time()
        model = Word2Vec(
            mesh=make_mesh(1, 1), vector_size=100, step_size=0.025,
            batch_size=256, min_count=5, num_iterations=2, seed=s,
            steps_per_call=16, subsample_ratio=1e-3,
        ).fit_file(corpus, lowercase=True)
        row = {
            "seed": s,
            "train_seconds": round(time.time() - t0, 1),
            **gates(model),
            "top1": evaluate_analogies(model, questions, top_k=1)
            .to_dict()["accuracy"],
            "top5": evaluate_analogies(model, questions, top_k=5)
            .to_dict()["accuracy"],
        }
        vocab_size = model.vocab.size
        model.stop()
        fw_rows.append(row)
        print("framework", json.dumps(row), flush=True)

    import numpy_sgns_control

    ctl_rows = []
    for s in range(1, 1 + n_seeds):
        t0 = time.time()
        r = numpy_sgns_control.run(corpus, epochs=1, seed=s)
        ctl_rows.append({
            "seed": s,
            "train_seconds": round(time.time() - t0, 1),
            "top1": r["analogy_top1"]["accuracy"],
            "top5": r["analogy_top5"]["accuracy"],
        })
        print("control", json.dumps(ctl_rows[-1]), flush=True)

    f1, f1sd = _mean_sd([r["top1"] for r in fw_rows])
    f5, f5sd = _mean_sd([r["top5"] for r in fw_rows])
    c1, c1sd = _mean_sd([r["top1"] for r in ctl_rows])
    c5, c5sd = _mean_sd([r["top5"] for r in ctl_rows])
    import math

    def sem_gap(a, b):
        fa, fb = max(a, 0.09), max(b, 0.09)
        return math.sqrt((fa * fa + fb * fb) / n_seeds)

    doc.update({
        "vocab_size": vocab_size,
        "framework": {"per_seed": fw_rows, "top1_mean": f1, "top1_sd": f1sd,
                      "top5_mean": f5, "top5_sd": f5sd},
        "control": {"per_seed": ctl_rows, "top1_mean": c1, "top1_sd": c1sd,
                    "top5_mean": c5, "top5_sd": c5sd},
        "summary": {
            "gap_top1": round(f1 - c1, 4),
            "gap_top5": round(f5 - c5, 4),
            "meets_control": bool(
                f1 >= c1 - 2 * sem_gap(f1sd, c1sd)
                and f5 >= c5 - 2 * sem_gap(f5sd, c5sd)
            ),
        },
    })
    from glint_word2vec_tpu.utils import atomic_write_json

    atomic_write_json(OUT, doc, indent=2, ensure_ascii=False)
    print(json.dumps(doc["summary"]))


if __name__ == "__main__":
    main()
