#!/usr/bin/env python3
"""Microbenchmark: packed vs generic n-gram table build on the real chip.

Round 5 moved gram aggregation onto the stable2 packed path (pos<<7|len in
one uint32; ops/ngram.py `gram_table`): a 3-array 2-key stable sort instead
of the generic 7-array 4-key build (~2.3x the sorted bytes).  This script
times the whole per-chunk bigram map (fused kernel -> position sort ->
pairing -> table build) under both builds in one process, so the delta is
attributable to the build alone.

Run on the chip:  chiprun -- python tools/grambench.py
Run on CPU:       JAX_PLATFORMS=cpu GRAMBENCH_MB=1 python tools/grambench.py

Timing rules: sync by fetching a real
output element, poison each iteration's input with the previous output so
XLA cannot hoist or DCE, best-of-k.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
    from mapreduce_tpu.runtime.platform import force_cpu

    force_cpu()

import jax
import jax.numpy as jnp
import numpy as np

CHUNK_MB = int(os.environ.get("GRAMBENCH_MB", "32"))
REPEATS = int(os.environ.get("GRAMBENCH_REPEATS", "5"))
N = int(os.environ.get("GRAMBENCH_N", "2"))


def _sync(out):
    np.asarray(jax.tree.leaves(out)[0].ravel()[:1])


def bench(name, fn, chunk, k=REPEATS):
    fn = jax.jit(fn)
    out = fn(chunk)
    _sync(out)
    best = float("inf")
    for _ in range(k):
        poison = jnp.asarray(jax.tree.leaves(out)[0]).ravel()[0].astype(jnp.uint8)
        c0 = chunk.at[0].set(chunk[0] | (poison & jnp.uint8(0)))  # dep, no-op
        t0 = time.perf_counter()
        out = fn(c0)
        _sync(out)
        best = min(best, time.perf_counter() - t0)
    print(f"{name:40s} {best * 1e3:9.2f} ms", flush=True)
    return best


def main():
    from bench import make_natural_corpus
    from mapreduce_tpu.config import Config
    from mapreduce_tpu.models.wordcount import _pad_for_backend
    from mapreduce_tpu.ops import ngram as ngram_ops
    from mapreduce_tpu.ops import table as table_ops
    from mapreduce_tpu.ops.pallas import tokenize as pallas_tok

    cfg = Config(backend="pallas", chunk_bytes=CHUNK_MB << 20)
    capacity = cfg.batch_uniques
    data = make_natural_corpus(CHUNK_MB << 20)
    chunk = jnp.asarray(_pad_for_backend(data, cfg))
    print(f"backend: {jax.devices()[0].platform}, chunk: {CHUNK_MB} MB, "
          f"n={N}, capacity={capacity}", flush=True)

    def gram_stream(c):
        col, seam, _ = pallas_tok.tokenize_split(
            c, max_token_bytes=cfg.pallas_max_token)
        stream = pallas_tok.concat_streams(col, seam)
        key_hi, key_lo, packed = ngram_ops.position_sorted(stream)
        return ngram_ops.mark_long_spans(
            ngram_ops.grams_from_sorted(key_hi, key_lo, packed, N))

    def packed_map(c):
        gs = gram_stream(c)
        return ngram_ops.gram_table(gs, capacity, 0, max_pos=c.shape[0],
                                    sort_mode="stable2")

    def generic_map(c):
        gs = gram_stream(c)
        return table_ops.from_stream(gs, capacity, pos_hi=0)

    t_packed = bench("bigram map, packed stable2 build", packed_map, chunk)
    t_generic = bench("bigram map, generic 7-array build", generic_map, chunk)
    print(json.dumps({
        "tool": "grambench", "chunk_mb": CHUNK_MB, "n": N,
        "packed_ms": round(t_packed * 1e3, 2),
        "generic_ms": round(t_generic * 1e3, 2),
        "speedup": round(t_generic / t_packed, 3),
        "backend": jax.devices()[0].platform,
    }), flush=True)


if __name__ == "__main__":
    main()
