#!/usr/bin/env python3
"""Microbenchmark: aggregation-sort variants on the real chip.

Round-4 notes had the single-chip pipeline sort-bound (the 3-array 3-key
sort over 16.8M pair-compacted rows at 25-85 ms of a ~102 ms chunk, on a
remote chip since retired; not re-measured).  This script times the
candidate replacements in one process so op shares are comparable.

Run on the chip:  chiprun -- python tools/sortbench.py
Run on CPU:       JAX_PLATFORMS=cpu python tools/sortbench.py

Timing rules: sync by fetching a real output element, poison each
iteration's input with the previous output so XLA cannot hoist or DCE the
work, best-of-k.
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
    # Honor the documented CPU-sanity mode even when something pinned a
    # platform before this runs (the env var alone is then too late — same
    # escape hatch as the CLI's --platform cpu).
    from mapreduce_tpu.runtime.platform import force_cpu

    force_cpu()

import jax
import jax.numpy as jnp
import numpy as np

# 16.8M default: one 32 MB chunk's pair-compacted stream.  SORTBENCH_LOG2
# shrinks it (e.g. 20 for CPU sanity runs).
ROWS = 1 << int(os.environ.get("SORTBENCH_LOG2", "24"))


def _sync(out):
    """Fetch ONE element (sliced on device first: np.asarray on the full
    array would copy the whole 67 MB to the host inside the timing)."""
    np.asarray(jax.tree.leaves(out)[0].ravel()[:1])


def bench(name, fn, args, k=5):
    fn = jax.jit(fn)
    out = fn(*args)
    _sync(out)
    best = float("inf")
    for i in range(k):
        # Poison: fold one element of the previous output into arg 0 so
        # iteration i's input depends on i-1's output (no hoisting).
        poison = jnp.asarray(jax.tree.leaves(out)[0]).ravel()[0].astype(args[0].dtype)
        a0 = args[0].at[0].set(args[0][0] ^ poison) if args[0].dtype == jnp.uint32 \
            else args[0]
        t0 = time.perf_counter()
        out = fn(a0, *args[1:])
        _sync(out)
        best = min(best, time.perf_counter() - t0)
    print(f"{name:45s} {best * 1e3:9.2f} ms")
    return best


def main():
    print(f"backend: {jax.devices()[0].platform}, rows: {ROWS}")
    rng = np.random.default_rng(0)
    # Realistic content: ~half the rows live (Zipf-ish key skew), rest
    # sentinel, like a real pair-compacted stream.
    n_tok = ROWS // 2
    zipf = rng.zipf(1.3, size=n_tok).astype(np.uint64) % 50_000
    khi = np.full(ROWS, 0xFFFFFFFF, np.uint32)
    klo = np.full(ROWS, 0xFFFFFFFF, np.uint32)
    packed = np.full(ROWS, 0xFFFFFFFF, np.uint32)
    live_idx = np.sort(rng.choice(ROWS, size=n_tok, replace=False))
    khi[live_idx] = (zipf * 2654435761 % (1 << 32)).astype(np.uint32)
    klo[live_idx] = (zipf * 40503 % (1 << 32)).astype(np.uint32)
    packed[live_idx] = ((live_idx.astype(np.uint64) * 2 % (1 << 26)) << 6 | 5).astype(np.uint32)
    khi, klo, packed = map(jnp.asarray, (khi, klo, packed))

    bench("sort 3 arrays, 3 keys (baseline)",
          lambda a, b, c: jax.lax.sort((a, b, c), num_keys=3), (khi, klo, packed))
    bench("sort 3 arrays, 2 keys (packed as payload)",
          lambda a, b, c: jax.lax.sort((a, b, c), num_keys=2), (khi, klo, packed))
    bench("sort 3 arrays, 2 keys, stable",
          lambda a, b, c: jax.lax.sort((a, b, c), num_keys=2, is_stable=True),
          (khi, klo, packed))
    bench("sort 2 arrays, 2 keys",
          lambda a, b: jax.lax.sort((a, b), num_keys=2), (khi, klo))
    bench("sort 3 arrays, 1 key (position sort)",
          lambda c, a, b: jax.lax.sort((c, a, b), num_keys=1), (packed, khi, klo))
    bench("sort 1 array, 1 key",
          lambda a: jax.lax.sort((a,), num_keys=1), (khi,))

    # Blocked: sort rows of a [K, B] view independently (axis sort).
    for B in (1 << 10, 1 << 12, 1 << 14):
        K = ROWS // B
        bench(f"blocked sort [K={K}, B={B}] 3 arr 3 keys",
              lambda a, b, c: jax.lax.sort(
                  (a.reshape(K, B), b.reshape(K, B), c.reshape(K, B)),
                  dimension=1, num_keys=3),
              (khi, klo, packed))

    # Segmented-min alternative to carrying packed as a sort key: sorted
    # (khi, klo) + associative_scan min with boundary resets.
    def seg_min(a, b, c):
        sa, sb, sc = jax.lax.sort((a, b, c), num_keys=2)
        boundary = (sa != jnp.concatenate([sa[:1], sa[:-1]])) | \
                   (sb != jnp.concatenate([sb[:1], sb[:-1]]))

        def combine(x, y):
            xf, xv = x
            yf, yv = y
            return (xf | yf, jnp.where(yf, yv, jnp.minimum(xv, yv)))

        _, m = jax.lax.associative_scan(combine, (boundary, sc))
        return m

    # Full aggregation (sort + rank reduce + table build): the number that
    # decides config.sort_mode — and the denominator for "sort share of the
    # chunk budget" (round-2 review #1).
    from mapreduce_tpu.ops import table as table_ops

    cap = 1 << 18
    n_tok_u = jnp.uint32(n_tok)
    bench("from_packed_rows[sort3] full aggregation",
          lambda a, b, c: table_ops.from_packed_rows(
              a, b, c, n_tok_u, cap, 0, sort_mode="sort3"),
          (khi, klo, packed))
    # stable2 drops the third comparator key (first occurrence from tie
    # order); on this synthetic poisoned stream the positions are already
    # ascending, so the timing is the honest production shape.
    bench("from_packed_rows[stable2] full aggregation",
          lambda a, b, c: table_ops.from_packed_rows(
              a, b, c, n_tok_u, cap, 0, sort_mode="stable2"),
          (khi, klo, packed))

    # Radix partition/sort rows (round-6 chip notes pricing note): the
    # Pallas MSD digit partition behind Config.sort_impl, A/B'd against the
    # raw and full-aggregation XLA sorts above.  Off-TPU the kernel runs in
    # INTERPRET mode — orders of magnitude slower and meaningless to time —
    # so the rows are chip-only unless SORTBENCH_RADIX=1 opts in (tiny
    # SORTBENCH_LOG2 sanity runs).
    if jax.default_backend() == "tpu" \
            or os.environ.get("SORTBENCH_RADIX", "0") == "1":
        from mapreduce_tpu.ops.pallas import radix as radix_ops

        bench("radix_partition (1 level, B=8, + bucket sorts)",
              lambda a, b, c: radix_ops.radix_sort3(
                  a, b, c, impl="radix_partition"), (khi, klo, packed))
        bench("radix (2 levels, B=8 each)",
              lambda a, b, c: radix_ops.radix_sort3(a, b, c, impl="radix"),
              (khi, klo, packed))
        bench("from_packed_rows[stable2, radix_partition] full aggregation",
              lambda a, b, c: table_ops.from_packed_rows(
                  a, b, c, n_tok_u, cap, 0, sort_mode="stable2",
                  sort_impl="radix_partition"),
              (khi, klo, packed))
    else:
        print("radix rows skipped (interpret mode is not a measurement; "
              "SORTBENCH_RADIX=1 opts in for sanity runs)")

    # The per-step pairwise table merge (the other half of a streaming step).
    t_a = table_ops.from_packed_rows(khi, klo, packed, n_tok_u, cap, 0)
    t_b = table_ops.from_packed_rows(klo, khi, packed, n_tok_u, cap, 1)
    bench("pairwise table merge (cap 256K)",
          lambda a_hi, ta=t_a, tb=t_b: table_ops.merge(
              ta._replace(key_hi=a_hi), tb, capacity=cap),
          (t_a.key_hi,))

    # Scan-based variants LAST, gated: the 16.8M-row associative_scan hung
    # the chip for >30 min twice (2026-07-31, both suite runs stalled at
    # exactly this point after every plain sort completed) — the same
    # giant-scan pathology that rules out the XLA tokenizer on device.
    # SORTBENCH_SCAN=1 opts in (e.g. on CPU).
    if os.environ.get("SORTBENCH_SCAN", "0") == "1":
        bench("2-key sort + segmented scan-min of packed", seg_min,
              (khi, klo, packed))
        bench("from_packed_rows[segmin] full aggregation",
              lambda a, b, c: table_ops.from_packed_rows(
                  a, b, c, n_tok_u, cap, 0, sort_mode="segmin"),
              (khi, klo, packed))
    else:
        print("scan-based variants skipped (SORTBENCH_SCAN=1 to opt in): "
              "the 16.8M-row associative_scan hangs the chip")


if __name__ == "__main__":
    main()
