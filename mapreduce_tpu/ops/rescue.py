"""Overlong-token rescue: exact counts for >W-byte tokens on the pallas path.

The fused kernel (:mod:`mapreduce_tpu.ops.pallas.tokenize`) bounds its
on-chip lookback at W bytes; longer tokens leave it *accounted but unhashed*
(``dropped_*``), while the XLA backend counts any length exactly — the one
semantic gap between the backends.  Natural web-ish text has
real >W tokens (URLs, markup: ~0.3% of tokens on the webby proxy,
tools/overlong.py), so on such corpora the two backends disagreed.

This module closes the gap with the seam-pass idiom at chunk scale: the
kernel already emits a POISON row (``pos << 6`` with zero length bits) at
the last byte of every overlong run, and the aggregation sort delivers
those rows pre-compacted at the head of its sentinel segment for free
(``rescue_slots`` in :func:`mapreduce_tpu.ops.table.from_packed_rows`).
Hashing the token that ends at each poison position inside one bounded
window — with the XLA backend's own key definition
(:func:`...ops.tokenize.last_token`), so bit-identical by construction —
recovers each token's exact key/length/start, and a tiny table
built from those rows merges into the chunk's batch table.  The whole pass
sits under a ``lax.cond(overlong > 0)`` in the caller: corpora without
overlong tokens (both bench generators, test.txt) never pay for it.

Envelope, by construction rather than silence:
  * tokens longer than ``window - 1`` bytes cannot be verified complete in
    the window and stay dropped-but-accounted (p99.9 token length on the
    webby proxy is 151 bytes — a 192..320-byte window covers essentially
    everything real);
  * at most ``rescue_slots`` poison rows per chunk are rescued, smallest
    positions first (deterministic); the remainder stays accounted.
Both residuals land in ``dropped_*`` exactly as before, so results degrade
to the round-3 accounting, never to corruption.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from mapreduce_tpu import constants
from mapreduce_tpu.ops import table as table_ops
from mapreduce_tpu.ops import tokenize as tok_ops


def rescue_table(chunk: jax.Array, rescue_packed: jax.Array, w: int,
                 window: int, pos_hi: jax.Array | int
                 ) -> tuple[table_ops.CountTable, jax.Array]:
    """Build a count table of the rescued overlong tokens.

    Args:
      chunk: the uint8 chunk the poison positions index into (chunk-relative
        positions: the pallas map always tokenizes with base_offset 0 and
        carries global placement in ``pos_hi``).
      rescue_packed: uint32[R] from the aggregation sort — poison rows
        (``last_byte << 6``, zero length bits) first, all-ones filler after;
        any real-token rows a clamped slice pulled in carry nonzero length
        bits and are masked off here.
      w: the kernel's W — every true poison marks a run longer than w.
      window: static lookback bound for the rescue (tokens of length in
        (w, window-1] are rescued; longer ones stay accounted).
      pos_hi: the chunk id, so first-occurrence order stays global.

    Returns:
      ``(table, rescued)``: a capacity-R table of the rescued tokens (their
      exact 64-bit keys, counts, first-occurrence positions and true
      lengths) and the uint32 number of occurrences rescued.
    """
    n = int(chunk.shape[0])
    r = rescue_packed.shape[0]
    ones = jnp.uint32(0xFFFFFFFF)
    is_poison = (rescue_packed != ones) & \
        ((rescue_packed & jnp.uint32(63)) == 0)
    p = (rescue_packed >> 6).astype(jnp.int32)  # last byte of each run

    # Window i = chunk[p_i - window + 1 .. p_i], read from a front-padded
    # copy so early positions need no clamping (PAD is a separator, so the
    # synthetic prefix can never extend a run).  Dead slots index past the
    # end; clip-mode gather returns arbitrary in-range bytes that the
    # is_poison mask discards.
    padded = jnp.concatenate(
        [jnp.full((window,), constants.PAD_BYTE, jnp.uint8), chunk])
    idx = jnp.minimum(p[:, None] + 1 + jnp.arange(window, dtype=jnp.int32),
                      jnp.int32(n + window - 1))
    windows = jnp.take(padded, idx, axis=0)  # (R, window) uint8

    # The token ending at each window's last byte, hashed by the XLA
    # backend's own key definition (closed form: no scan to compile).
    key_hi, key_lo, length = tok_ops.last_token(windows)

    # length == window means the run reaches the window start: possibly
    # truncated, cannot be verified complete — stays accounted.  length <= w
    # on a poison row is impossible by kernel construction; masking it keeps
    # any future drift accounted instead of double-counted.
    valid = is_poison & (length < jnp.uint32(window)) \
        & (length > jnp.uint32(w))
    rescued = jnp.sum(valid.astype(jnp.uint32))

    sent = jnp.uint32(constants.SENTINEL_KEY)
    inf = jnp.uint32(constants.POS_INF)
    start = (p + 1).astype(jnp.uint32) - length  # first byte, chunk-relative
    stream = tok_ops.TokenStream(
        key_hi=jnp.where(valid, key_hi, sent),
        key_lo=jnp.where(valid, key_lo, sent),
        count=valid.astype(jnp.uint32),
        pos=jnp.where(valid, start, inf),
        length=jnp.where(valid, length, jnp.uint32(0)),
    )
    # Generic build (lengths exceed the 6-bit packed bound); R rows, so the
    # sort is noise.  Capacity R: at most R distinct keys, nothing can drop.
    return table_ops.from_stream(stream, r, pos_hi=pos_hi), rescued
