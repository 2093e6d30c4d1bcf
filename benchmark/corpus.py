"""The one corpus generator: a seeded synthetic corpus whose vocabulary is
exactly ``vocab`` words, from the parameters of a traffic file.

Copied from ``chip_smoke.make_corpus`` (PR 22), which trains: every filler
word once (``min_count=1`` keeps them all), the rest Zipf 1/rank draws, so
the index skew is a real corpus's; planted (country, capital) sentences give
the loss something to learn. Nothing here imports the program or JAX.
"""

import numpy as np

PAIRS = [("germany", "berlin"), ("france", "paris"), ("austria", "vienna"),
         ("spain", "madrid"), ("italy", "rome"), ("poland", "warsaw")]
RELATION = ["capital", "city", "of", "the", "is", "has", "famous", "for"]


def special_words():
    theme = {c: [f"{c}_t{j}" for j in range(4)] for c, _ in PAIRS}
    special = ([w for p in PAIRS for w in p] + RELATION
               + [t for ts in theme.values() for t in ts])
    return theme, special


def filler_names(n: int) -> np.ndarray:
    return np.char.add("w", np.char.zfill(np.arange(n).astype(str), 7))


def make_corpus(path: str, vocab: int, traffic: dict, seed: int) -> int:
    """Write the corpus to ``path``; return its number of tokens. Every
    seed gives the same number of tokens and of sentences (each planted
    sentence has 8 words): both are static shapes of the fit's programs,
    so a seed that changed them would compile anew."""
    rng = np.random.default_rng(seed)
    theme, special = special_words()
    n_filler = vocab - len(special)
    names = filler_names(n_filler)
    p = 1.0 / np.arange(1, n_filler + 1)
    tokens = np.concatenate([
        rng.permutation(n_filler),
        rng.choice(n_filler, size=int(traffic["zipf_tokens"]), p=p / p.sum()),
    ])
    rng.shuffle(tokens)
    sent = int(traffic["sentence_words"])
    lines = [" ".join(names[tokens[i:i + sent]])
             for i in range(0, tokens.size, sent)]
    n_tokens = int(tokens.size)
    some = names[:40]  # frequent filler as noise inside planted sentences
    for _ in range(int(traffic["planted_sentences"])):
        country, capital = PAIRS[rng.integers(len(PAIRS))]
        th = list(rng.choice(theme[country], size=2))
        noise = list(rng.choice(some, size=3))
        style = rng.integers(4)
        if style == 0:
            s = [capital, "is", "the", "capital", "of", country] + th
        elif style == 1:
            s = [th[0], country, "capital", "city", capital, th[1]] + noise[:2]
        elif style == 2:
            s = [country, "has", "capital", capital] + th + noise[:2]
        else:
            x = country if rng.random() < 0.5 else capital
            s = [x, "famous", "for"] + th + noise
        lines.append(" ".join(s))
        n_tokens += len(s)
    order = rng.permutation(len(lines))
    with open(path, "w") as f:
        f.write("\n".join(lines[i] for i in order))
        f.write("\n")
    return n_tokens


def zipf_words(n_words: int, count: int, exponent: float, seed: int):
    """``count`` word ranks in [0, n_words) drawn by Zipf 1/rank**exponent:
    the query stream of the serving traffic."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, n_words + 1) ** float(exponent)
    cdf = np.cumsum(p)
    return np.searchsorted(cdf, rng.random(count) * cdf[-1]).astype(np.int64)
