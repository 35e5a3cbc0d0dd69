"""Wikipedia-like words with text8's statistics, and unique URL tokens.

The words: lowercase a-z strings, one space after each, drawn from a
Zipf-Mandelbrot law, p(rank r) proportional to (r + q) ** -s over ``vocab``
ranks.  The configuration's ``s``, ``q`` and ``vocab`` are fitted to three
public counts of text8 (the first 10**8 bytes of cleaned English Wikipedia):
at its 17,005,207 tokens the law gives 253,854 distinct words, 71,290 of
them seen five times or more, and 1,061,396 tokens of the commonest word.
A word's length grows with the log of its rank, ``floor(a + slope * ln r +
u)`` with ``u`` uniform in [0, 1), the intercept ``a`` set so that the mean
length per token is ``mean_word_bytes`` (text8: 10**8 bytes over its tokens,
less the space).  Letters are random, each word distinct.

Then ``p_url`` of tokens are replaced by a URL, ``http://`` and a body of
random URL bytes whose length is log-uniform in ``url_body_bytes`` (as
``webby.py``): tokens of 40 to 306 bytes, past the map kernel's 32-byte
window, each one unique.
"""

from __future__ import annotations

import numpy as np

from corpus import Corpus, Vocabulary, assemble

_LETTERS = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz", np.uint8)
_URL_ALPHABET = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz0123456789./_-=&?",
                              np.uint8)


def rank_probs(params: dict) -> np.ndarray:
    """p of each rank, commonest first."""
    r = np.arange(1, int(params["vocab"]) + 1, dtype=np.float64)
    p = (r + params["q"]) ** -params["s"]
    return p / p.sum()


def word_lengths(params: dict, p: np.ndarray,
                 rng: np.random.Generator) -> np.ndarray:
    """Length of each rank's word, with the mean per token the
    configuration's ``mean_word_bytes``."""
    lo, hi = params["word_bytes"]
    base = params["len_slope"] * np.log(np.arange(1, len(p) + 1)) \
        + rng.random(len(p))

    def mean(a):
        return float(p @ np.clip(np.floor(a + base), lo, hi))

    a_lo, a_hi = -float(hi), float(hi)
    for _ in range(60):  # the mean grows with a
        a = (a_lo + a_hi) / 2
        a_lo, a_hi = (a, a_hi) if mean(a) < params["mean_word_bytes"] \
            else (a_lo, a)
    return np.clip(np.floor((a_lo + a_hi) / 2 + base), lo, hi).astype(np.int64)


def _distinct_strings(n: int, length: int,
                      rng: np.random.Generator) -> np.ndarray:
    """n distinct random lowercase strings of one length, as rows."""
    if 26 ** length < 4 * n:  # few strings: draw their numbers
        if n > 26 ** length:
            raise ValueError(f"{n} words of {length} letters do not exist")
        num = rng.choice(26 ** length, n, replace=False)
        digits = num[:, None] // 26 ** np.arange(length)[::-1] % 26
        return _LETTERS[digits]
    rows = np.empty((0, length), np.uint8)
    while len(rows) < n:
        more = _LETTERS[rng.integers(0, 26, (n - len(rows), length))]
        rows = np.unique(np.concatenate([rows, more]), axis=0)
    return rows[rng.permutation(len(rows))[:n]]


def _words(params: dict, lengths: np.ndarray,
           rng: np.random.Generator) -> list[bytes]:
    words = [b""] * len(lengths)
    for length in np.unique(lengths):
        where = np.flatnonzero(lengths == length)
        blob = _distinct_strings(len(where), int(length), rng).tobytes()
        for k, i in enumerate(where):
            words[i] = blob[k * length:(k + 1) * length]
    return words


def make(params: dict, rng: np.random.Generator,
         sizes: list[int]) -> list[Corpus]:
    p = rank_probs(params)
    lengths = word_lengths(params, p, rng)
    words = _words(params, lengths, rng)
    cdf = np.cumsum(p)
    lo, hi = params["url_body_bytes"]
    per_token = (1 - params["p_url"]) * (float(p @ lengths) + 1) \
        + params["p_url"] * ((hi - lo) / np.log(hi / lo) + 8)
    drawn = []
    for n_bytes in sizes:
        parts, have = [], 0
        while have < n_bytes:  # blocks bound the memory
            n = min(1 << 23, max(4096, int((n_bytes - have) / per_token)
                                 + 1024))
            ids = np.minimum(np.searchsorted(cdf, rng.random(n)),
                             len(p) - 1).astype(np.int32)
            url = rng.random(n) < params["p_url"]
            body = np.exp(rng.uniform(np.log(lo), np.log(hi),
                                      int(url.sum()))).astype(np.int64)
            ids[url] = -1
            parts.append((ids, body))
            have += int((lengths[ids[~url]] + 1).sum() + (body + 8).sum())
        drawn.append([np.concatenate(x) for x in zip(*parts)])
    # One URL per site, over all files: http:// and a random body.
    body = np.concatenate([d[1] for d in drawn])
    blob = _URL_ALPHABET[rng.integers(0, len(_URL_ALPHABET),
                                      int(body.sum()))].tobytes()
    ends = np.cumsum(body)
    first = len(words)
    words.extend(b"http://" + blob[e - n:e] for e, n in zip(ends, body))
    vocab = Vocabulary(words)
    out = []
    for (ids, b), n_bytes in zip(drawn, sizes):
        ids[ids == -1] = np.arange(first, first + len(b), dtype=np.int32)
        first += len(b)
        out.append(assemble(vocab, ids, np.zeros(len(ids), bool), n_bytes))
    return out
