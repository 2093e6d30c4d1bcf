"""The corpus of ``benchmark/corpus.py`` with fillers that look like words.

``corpus.filler_names`` calls its fillers ``w0000000``, ``w0000001``, ...: a
subword model cuts each into 26 n-grams over ten digits, and every frequent
word lands on the same few bucket rows, so the hashed rows would measure
nothing. Here a filler is a distinct seeded string over a-z: letters drawn by
English letter frequency, lengths 4 to 12 by the shares below, the shorter
words at the more frequent ranks (frequent words are short in every language;
lengths dealt at random over the ranks would put 25 table rows on the average
token where text has about 15). Everything else (each filler once, Zipf 1/rank
draws, sentence length, the planted country/capital sentences and their
special words) is ``corpus.make_corpus``'s, restated because that function
names its fillers itself. Nothing here imports the program or JAX.
"""

import numpy as np

from benchmark.corpus import PAIRS, special_words

# Relative frequency of a-z in English text, per cent (the table printed in
# Lewand, "Cryptological Mathematics", 2000, as commonly quoted).
LETTER_FREQ = [8.167, 1.492, 2.782, 4.253, 12.702, 2.228, 2.015, 6.094, 6.966,
               0.153, 0.772, 4.025, 2.406, 6.749, 7.507, 1.929, 0.095, 5.987,
               6.327, 9.056, 2.758, 0.978, 2.360, 0.150, 1.974, 0.074]
# Share of the fillers, per cent, that have 4, 5, ..., 12 letters.
LENGTH_SHARE = [5, 9, 13, 16, 16, 14, 11, 9, 7]
MIN_LETTERS = 4


def _distinct_words(rng, letters: int, need: int, taken: set) -> np.ndarray:
    """``need`` distinct strings of ``letters`` letters, in the order the
    seed first drew them, none of them in ``taken``."""
    p = np.asarray(LETTER_FREQ) / sum(LETTER_FREQ)
    seen, out = set(taken), []
    while len(out) < need:
        draw = rng.choice(26, size=(2 * need + 64, letters), p=p)
        words = (draw + ord("a")).astype(np.uint8).view(
            f"S{letters}").ravel().astype(str)
        _, first = np.unique(words, return_index=True)
        for w in words[np.sort(first)]:
            if w not in seen:
                seen.add(w)
                out.append(w)
    return np.asarray(out[:need])


def filler_names(n: int, seed: int, taken=()) -> np.ndarray:
    """``n`` distinct fillers, most frequent rank first: the shortest words
    at the head, each length's count by ``LENGTH_SHARE``."""
    rng = np.random.default_rng([int(seed), 31])
    edges = np.round(np.cumsum(LENGTH_SHARE) / sum(LENGTH_SHARE) * n)
    counts = np.diff(np.concatenate([[0], edges])).astype(int)
    return np.concatenate([
        _distinct_words(rng, MIN_LETTERS + i, int(c), set(taken))
        for i, c in enumerate(counts) if c])


def make_corpus(path: str, vocab: int, traffic: dict, seed: int) -> int:
    """Write the corpus to ``path``; return its number of tokens (the same
    for every seed, as the number of sentences: both are static shapes of
    the fit's programs)."""
    rng = np.random.default_rng(seed)
    theme, special = special_words()
    n_filler = vocab - len(special)
    names = filler_names(n_filler, seed, taken=special)
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
