"""Inputs from ``--seed``: one general generator per kind of data, driven by
the parameters in a traffic file (``benchmark/traffic/<mix>.json``).

Nothing here imports the program. The same seed gives the same inputs; every
seed gives the same amount of work (the same multiset of sizes and kinds, in
another order), so that a seed changes the draw and not the load.
"""

import numpy as np


def zipf_cdf(n: int, exponent: float) -> np.ndarray:
    """CDF of rank r ~ r**-exponent over ranks 1..n (float64)."""
    cdf = np.cumsum(1.0 / np.arange(1, n + 1, dtype=np.float64) ** exponent)
    return cdf / cdf[-1]


def zipf_ranks(rng, n: int, exponent: float, size) -> np.ndarray:
    """Ranks 0..n-1 with the weight of rank r falling as (r+1)**-exponent,
    drawn by the analytic inverse of the continuous power law on [1, n+1)
    and floored: no table over n ids is built. (Against the discrete zipf
    the first ranks get a little less; the tail is the same.) An exponent of
    0 is uniform."""
    u = rng.random(size)
    if abs(exponent - 1.0) < 1e-9:
        x = np.exp(u * np.log(n + 1.0))
    else:
        a = 1.0 - exponent
        x = (1.0 + u * ((n + 1.0) ** a - 1.0)) ** (1.0 / a)
    return np.minimum(x.astype(np.int64) - 1, n - 1)


# ----------------------------------------------------------------- corpus ---


def corpus_counts(vocab: int, zipf_tokens: int, exponent: float) -> np.ndarray:
    """How often each of ``vocab`` words stands in the generated corpus:
    once (so the vocabulary spans the whole table) plus its share of
    ``zipf_tokens`` more by zipf(``exponent``). The counts are the
    distribution's own (cumulative rounding) and not a draw: every seed
    trains the same words as often, in another order, and the program's
    unigram table - a constant of its compiled step - is the same for every
    seed, so a new seed finds the step in the compile cache."""
    cum = np.rint(zipf_tokens * zipf_cdf(vocab, exponent)).astype(np.int64)
    return 1 + np.diff(cum, prepend=0)


def corpus_ids(counts: np.ndarray, seed: int) -> np.ndarray:
    """Token ids of the generated corpus: word i ``counts[i]`` times, the
    whole shuffled by the seed. After ``chip_smoke.py``'s ``write_corpus``
    (PR 21)."""
    rng = np.random.default_rng(seed)
    ids = np.repeat(np.arange(len(counts)), counts)
    rng.shuffle(ids)
    return ids


def write_corpus(path: str, ids: np.ndarray) -> None:
    """The corpus as the text file ``snails train`` reads: words ``w<id>``."""
    with open(path, "w") as f:
        for lo in range(0, len(ids), 1 << 18):
            f.write(" ".join(f"w{i}" for i in ids[lo: lo + (1 << 18)]))
            f.write("\n")


# --------------------------------------------------------------- examples ---

ID_BASE = 100_000_000  # every id has nine digits, so a line has a fixed width


def ctr_examples(n: int, cardinalities, exponent: float, seed: int):
    """(labels [n] in {0,1}, ids [n, fields] int64): field f has
    ``cardinalities[f]`` ids of its own; an example draws one of them by
    rank (``zipf_ranks``) and gets the id ``ID_BASE + (ids of the fields
    before) + scatter(rank)``: ranks are scattered over the field's range by
    a fixed prime multiplier, so that hot ids are not neighbours. The label
    is 1 with probability 1/4, independent of the ids: speed does not depend
    on it."""
    rng = np.random.default_rng(seed)
    cards = np.asarray(cardinalities, np.int64)
    offsets = np.concatenate([[0], np.cumsum(cards)[:-1]])
    if ID_BASE + int(cards.sum()) >= min(2**31, 10 * ID_BASE):
        raise ValueError("ids leave int32 or nine digits: fewer or smaller fields")
    ids = np.empty((n, len(cards)), np.int64)
    for f, c in enumerate(cards):
        r = zipf_ranks(rng, int(c), exponent, n)
        ids[:, f] = ID_BASE + offsets[f] + (r * 2654435761 + f) % c
    labels = (rng.random(n) < 0.25).astype(np.int64)
    return labels, ids


_DIGITS5 = (np.arange(100000)[:, None] // 10 ** np.arange(4, -1, -1) % 10 + ord("0")).astype(np.uint8)


def write_ctr(path: str, labels: np.ndarray, ids: np.ndarray, chunk: int = 1 << 17) -> None:
    """``label f0 f1 ...`` lines, rendered by numpy: every id has nine digits
    (five and four, each looked up), so a line is a fixed number of bytes and
    no Python loop touches a row."""
    n, fields = ids.shape
    with open(path, "wb") as f:
        for lo in range(0, n, chunk):
            part = ids[lo:lo + chunk]
            line = np.full((len(part), 2 + fields * 10), ord(" "), np.uint8)
            line[:, 0] = labels[lo:lo + chunk] + ord("0")
            body = line[:, 1:-1].reshape(len(part), fields, 10)  # " ddddddddd" per field
            body[:, :, 1:6] = _DIGITS5[part // 10000]
            body[:, :, 6:] = _DIGITS5[part % 10000][:, :, 1:]
            line[:, -1] = ord("\n")
            f.write(line.tobytes())
