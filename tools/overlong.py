#!/usr/bin/env python3
"""Measure the >W-byte token envelope on the corpora we can generate.

round-3 review weak #4 / next #6: the pallas backend drops tokens longer than
its lookback window W (default 32) into ``dropped_*`` accounting while the
XLA backend counts them exactly, so the size of the semantic gap between
the backends on natural text was unknown.  This tool quantifies it host-side
(pure numpy, no device): token-length distribution, overlong rate at W=32
and W=63, and the per-32MB-chunk overlong-occurrence count that sizes the
rescue pass's slot budget (``Config.rescue_overlong``).

Corpora: the two bench generators (synthetic-zipf, synthetic-natural), the
bundled fixture ``test.txt``, and a "webby" proxy — natural text with ~0.3%
of words replaced by URL/path/base64-ish long tokens, the enwik/WET
statistic the other generators lack (real enwik8 is not mountable: zero
egress).  Rates go into PERF.md.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import (  # noqa: E402
    make_natural_corpus, make_webby_corpus, make_zipf_corpus)

SEPARATORS = b" \t\n\r\x00"


def token_length_stats(data: bytes) -> dict:
    buf = np.frombuffer(data, dtype=np.uint8)
    sep = np.isin(buf, np.frombuffer(SEPARATORS, np.uint8))
    # Run-length of non-separator runs.
    idx = np.flatnonzero(np.diff(np.concatenate([[True], sep, [True]]).astype(np.int8)))
    starts, ends = idx[::2], idx[1::2]
    lengths = ends - starts
    n = len(lengths)
    if n == 0:
        return {"tokens": 0}
    over32 = int((lengths > 32).sum())
    over63 = int((lengths > 63).sum())
    over256 = int((lengths > 256).sum())
    mb = len(data) / (1 << 20)
    return {
        "bytes": len(data),
        "tokens": n,
        "max_len": int(lengths.max()),
        "p999_len": int(np.quantile(lengths, 0.999)),
        "over_w32": over32,
        "over_w32_rate": over32 / n,
        "over_w63": over63,
        "over_w63_rate": over63 / n,
        "over_256": over256,
        "over_w32_per_32mb_chunk": over32 / max(mb / 32, 1e-9),
    }


def main() -> int:
    mb = int(os.environ.get("OVERLONG_MB", "32"))
    corpora = {
        "test.txt": open(os.path.join(REPO, "test.txt"), "rb").read(),
        "synthetic-zipf": make_zipf_corpus(mb << 20),
        "synthetic-natural": make_natural_corpus(mb << 20),
        "synthetic-webby": make_webby_corpus(mb << 20),
    }
    report = {name: token_length_stats(data) for name, data in corpora.items()}
    print(json.dumps(report, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
