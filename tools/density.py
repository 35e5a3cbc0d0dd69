#!/usr/bin/env python3
"""Token-density + overlong-token measurement over the bench corpora (CPU).

Two round-4 design questions need numbers, not guesses:

1. **Compaction slot budget** (round-3 review #2): the pallas kernel's output is
   one row per 2 input bytes because that is the worst-case emission rate;
   a slot-compacted output of B slots per W-byte window is lossless only
   when no window ever holds more than B token ends.  What budget do real
   corpora need, at the kernel's (block_rows x 128-lane) window geometry?

2. **>W-token envelope** (round-3 review #6): the pallas backend drops tokens
   longer than W=32 bytes into dropped_* accounting while the XLA backend
   counts them exactly.  How big is that divergence on natural-ish text?

Prints one JSON line per corpus.  Pure numpy — runs anywhere, no JAX.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mapreduce_tpu import constants  # noqa: E402


def token_ends(buf: np.ndarray) -> np.ndarray:
    """bool[n]: byte i ends a token (i non-sep, i+1 sep-or-EOF)."""
    sep = np.zeros(256, np.bool_)
    for b in constants.SEPARATOR_BYTES:
        sep[b] = True
    is_sep = sep[buf]
    nxt = np.concatenate([is_sep[1:], [True]])
    return (~is_sep) & nxt


def token_lengths(buf: np.ndarray) -> np.ndarray:
    """int array of token lengths, in order."""
    sep = np.zeros(256, np.bool_)
    for b in constants.SEPARATOR_BYTES:
        sep[b] = True
    is_sep = sep[buf]
    # Run-length over non-sep runs.
    d = np.diff(np.concatenate([[True], is_sep, [True]]).astype(np.int8))
    starts = np.flatnonzero(d == -1)
    ends = np.flatnonzero(d == 1)
    return ends - starts


def window_density(buf: np.ndarray, window: int) -> np.ndarray:
    """Token-end count per aligned `window`-byte window (the kernel's
    (block, lane) cell is exactly such a window of block_rows bytes)."""
    ends = token_ends(buf)
    n = (len(ends) // window) * window
    return ends[:n].reshape(-1, window).sum(axis=1)


def analyze(name: str, data: bytes, windows=(256, 512),
            budgets=(1 / 4, 5 / 16, 11 / 32, 3 / 8, 1 / 2)) -> dict:
    buf = np.frombuffer(data, dtype=np.uint8)
    lens = token_lengths(buf)
    n_tok = len(lens)
    out = {
        "corpus": name,
        "bytes": len(buf),
        "tokens": n_tok,
        "density": round(n_tok / len(buf), 4),
        "overlong_gt32_tokens": int((lens > 32).sum()),
        "overlong_gt32_rate": float((lens > 32).mean()),
        "overlong_gt63_tokens": int((lens > 63).sum()),
        "max_token_len": int(lens.max()),
    }
    for w in windows:
        dens = window_density(buf, w)
        row = {"max_ends": int(dens.max()),
               "p999_ends": int(np.quantile(dens, 0.999))}
        for b in budgets:
            slots = int(b * w)
            row[f"overflow_rate_b{slots}"] = float((dens > slots).mean())
        out[f"window{w}"] = row
    return out


def main() -> int:
    from bench import make_natural_corpus, make_zipf_corpus

    mb = int(os.environ.get("DENSITY_MB", "32"))
    corpora = {
        "synthetic-zipf": make_zipf_corpus(mb << 20),
        "synthetic-natural": make_natural_corpus(mb << 20),
    }
    fixture = os.path.join(REPO, "test.txt")
    if os.path.exists(fixture):
        corpora["test.txt"] = open(fixture, "rb").read()
    for name, data in corpora.items():
        print(json.dumps(analyze(name, data)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
