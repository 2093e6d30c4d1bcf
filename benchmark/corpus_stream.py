"""The stream generator: a seeded sentence stream for ``fit-stream``, from
the parameters of a traffic file. ``corpus.py``'s names and planted
sentences, imported unchanged. Nothing here imports the program or JAX.

The file is two parts, in arrival order:

* the BOOTSTRAP WINDOW: ``bootstrap_tokens`` tokens, Zipf 1/rank draws over the
  base words and every base word once, shuffled, in ``sentence_words``-word
  sentences. The trainer scans it for exact counts, so the base vocabulary
  is exactly ``vocab`` words at ``min_count=1``;
* the LIVE STREAM: ``live_tokens`` tokens, in ``sentence_words``-word
  sentences of Zipf 1/rank draws, ``new_word_share`` of them over a pool of
  ``new_word_pool`` words the bootstrap window never shows (the rest over
  the base words), and 8-word planted country/capital sentences at
  ``w2v-300-2m.train``'s share of the sentences (``planted_per_sentence``),
  shuffled among them.

Every seed gives the same number of tokens and of sentences in each part.
"""

import numpy as np

from benchmark.corpus import PAIRS, filler_names, special_words

PLANTED_WORDS = 8  # every planted sentence has 8 words


def _zipf(rng, n_words: int, count: int) -> np.ndarray:
    p = 1.0 / np.arange(1, n_words + 1)
    cdf = np.cumsum(p)
    return np.searchsorted(cdf, rng.random(count) * cdf[-1]).astype(np.int64)


def _planted(rng, theme, some) -> list:
    country, capital = PAIRS[rng.integers(len(PAIRS))]
    th = list(rng.choice(theme[country], size=2))
    noise = list(rng.choice(some, size=3))
    style = rng.integers(4)
    if style == 0:
        return [capital, "is", "the", "capital", "of", country] + th
    if style == 1:
        return [th[0], country, "capital", "city", capital, th[1]] + noise[:2]
    if style == 2:
        return [country, "has", "capital", capital] + th + noise[:2]
    x = country if rng.random() < 0.5 else capital
    return [x, "famous", "for"] + th + noise


def plan(traffic: dict, seconds: float) -> dict:
    """The stream's sizes, from the traffic file and the window's seconds
    alone (no seed): sentences and tokens of each part."""
    sent = int(traffic["sentence_words"])
    boot_tokens = int(traffic["bootstrap_tokens"])
    if boot_tokens % sent:
        raise ValueError("bootstrap_tokens must be whole sentences")
    want = round(float(seconds) * traffic["nominal_words_per_s"])
    ratio = float(traffic["planted_per_sentence"])
    filler = int(want / (sent + PLANTED_WORDS * ratio))
    planted = round(filler * ratio)
    return {
        "bootstrap_sentences": boot_tokens // sent,
        "bootstrap_tokens": boot_tokens,
        "live_filler_sentences": filler, "live_planted_sentences": planted,
        "live_sentences": filler + planted,
        "live_tokens": filler * sent + planted * PLANTED_WORDS,
    }


def make_stream(path: str, vocab: int, traffic: dict, seed: int,
                seconds: float, prefix_path: str = None,
                prefix_live_sentences: int = 0) -> dict:
    """Write the stream to ``path`` (and, to ``prefix_path``, the bootstrap
    window with the first ``prefix_live_sentences`` live sentences: what
    the kind's warm-up fit reads); return :func:`plan`'s sizes."""
    sizes = plan(traffic, seconds)
    rng = np.random.default_rng(seed)
    theme, special = special_words()
    sent = int(traffic["sentence_words"])
    names = np.concatenate([filler_names(vocab - len(special)),
                            np.asarray(special)])
    if np.unique(names).size != vocab:
        raise ValueError("the base words are not distinct")
    new_names = np.char.add("n", np.char.zfill(
        np.arange(int(traffic["new_word_pool"])).astype(str), 7))
    # One table for both (a new word's index lies past the base words),
    # as bytes in cells of one width: a sentence is then one gather and
    # the blanks that pad a cell are more of the blank between two words.
    both = np.concatenate([names, new_names])
    width = both.dtype.itemsize // 4 + 1
    cells = np.char.ljust(both, width).astype(f"S{width}")

    def lines_of(tokens):
        rows = cells[tokens].reshape(-1, sent).view(f"S{sent * width}")
        return [row.rstrip() for row in rows[:, 0].tolist()]

    # -- the bootstrap window -------------------------------------------
    boot = np.concatenate([
        rng.permutation(vocab),
        _zipf(rng, vocab - len(special),
              sizes["bootstrap_tokens"] - vocab),
    ])
    rng.shuffle(boot)
    boot_lines = lines_of(boot)
    # -- the live stream ------------------------------------------------
    n_fill = sizes["live_filler_sentences"] * sent
    is_new = rng.random(n_fill) < float(traffic["new_word_share"])
    tokens = np.where(
        is_new, vocab + _zipf(rng, new_names.size, n_fill),
        _zipf(rng, vocab - len(special), n_fill))
    live_lines = lines_of(tokens)
    some = names[:40]  # frequent filler as noise inside planted sentences
    for _ in range(sizes["live_planted_sentences"]):
        live_lines.append(" ".join(_planted(rng, theme, some)).encode())
    order = rng.permutation(len(live_lines))
    with open(path, "wb") as f:
        f.write(b"\n".join(boot_lines))
        f.write(b"\n")
        f.write(b"\n".join(live_lines[i] for i in order))
        f.write(b"\n")
    if prefix_path:
        with open(prefix_path, "wb") as f:
            f.write(b"\n".join(boot_lines))
            f.write(b"\n")
            f.write(b"\n".join(live_lines[i]
                               for i in order[:prefix_live_sentences]))
            f.write(b"\n")
    return sizes
