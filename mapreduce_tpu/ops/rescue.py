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
sits under a ``lax.cond(overlong > 0)`` in the caller: chunks without
overlong tokens (the Zipf bench corpus, test.txt) never pay for it, while
URL-dense text pays on every chunk (the ``webby-wordcount`` benchmark
text, ~16,000 URLs a 32 MiB chunk, takes the second tier on each).

The windows are fetched as rows, not bytes: the front-padded chunk is
viewed as 128-byte rows, each window is gathered as the few rows that
cover it (one index a row: 4 for a 320-byte window), and a log-shifter of
static slices brings its first byte to column 0.  A gather with one index
a byte cost ~85 ms a 32 MiB chunk on a v5e at 32,768 slots of 320 bytes;
the row fetch ~0.6 ms (PERF.md).

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

_ROW = 128  # bytes a row of the window fetch: the lane width


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

    Each slot's window, the ``window`` bytes that end at its poison
    position, is fetched as whole 128-byte rows and shifted into place
    (:func:`_windows`); the token ending at its last byte is then hashed.

    Returns:
      ``(table, rescued)``: a capacity-R table of the rescued tokens (their
      exact 64-bit keys, counts, first-occurrence positions and true
      lengths) and the uint32 number of occurrences rescued.
    """
    r = rescue_packed.shape[0]
    ones = jnp.uint32(0xFFFFFFFF)
    is_poison = (rescue_packed != ones) & \
        ((rescue_packed & jnp.uint32(63)) == 0)
    p = (rescue_packed >> 6).astype(jnp.int32)  # last byte of each run

    windows = _windows(chunk, p, window)  # (R, window) uint8

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


def _windows(chunk: jax.Array, p: jax.Array, window: int) -> jax.Array:
    """uint8[R, window]: row i holds ``chunk[p_i - window + 1 .. p_i]``,
    PAD where that reaches before the chunk's start.

    The chunk, padded in front to a whole number of rows (at least
    ``window`` PAD bytes: a separator, so the prefix never extends a run)
    and at the end to a whole row, is viewed as ``(rows, 128)``.  Each
    window starts ``start mod 128`` bytes into row ``start // 128`` and
    spans at most ``k`` rows; those rows are gathered whole (one index a
    row) and shifted left.  A slot whose rows run past the last is clamped
    to it: that is a dead slot (all-ones filler) or bytes past the
    window's end, which the shift drops.
    """
    n = int(chunk.shape[0])
    r = p.shape[0]
    front = -(-window // _ROW) * _ROW
    total = -(-(front + n) // _ROW) * _ROW
    rows = jnp.concatenate([
        jnp.full((front,), constants.PAD_BYTE, jnp.uint8), chunk,
        jnp.full((total - front - n,), constants.PAD_BYTE, jnp.uint8),
    ]).reshape(-1, _ROW)
    start = p + 1 + front - window  # each window's first byte in ``rows``
    k = -(-(_ROW - 1 + window) // _ROW)
    ridx = (start // _ROW)[:, None] + jnp.arange(k, dtype=jnp.int32)
    got = jnp.take(rows, ridx, axis=0, mode="clip").reshape(r, k * _ROW)
    # Shift each row left by start mod 128: 7 static slices, each taken
    # or not per row by one bit of the shift; ``keep`` is what the smaller
    # shifts still need.
    shift = start % _ROW
    d = _ROW // 2
    while d:
        keep = window + d - 1
        got = jnp.where(((shift & d) != 0)[:, None], got[:, d:d + keep],
                        got[:, :keep])
        d //= 2
    return got
