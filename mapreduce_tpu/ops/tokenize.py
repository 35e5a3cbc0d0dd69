"""Device-side tokenization + hashing.

Replaces both the reference's host tokenizer (char-scan loop, ``main.cu:187-202``)
and its device map UDF (per-thread byte-copy loops, ``mapper`` ``main.cu:37-54``)
with one data-parallel pass: a *segmented associative scan* over the raw byte
tensor.

Formulation
-----------
Scanning a token's bytes left-to-right with ``h' = h * B + c`` is composition
of affine maps ``f_c(h) = h*B + c``; affine composition is associative, so the
whole pass runs as ``jax.lax.associative_scan`` (log-depth, VPU-friendly,
static shapes) instead of a serial per-char loop.  Separator bytes insert a
*reset* element, giving the segmented variant: after the scan, every position
holds the rolling hash of the token prefix ending there, and positions where a
non-separator byte is followed by a separator (or end-of-buffer) hold the hash
of a complete token.

Two independent 32-bit lanes (different odd bases) form an effective 64-bit
key, finalized with murmur3's fmix32.  This fixes the reference's prefix-match
comparator defect (``compare``, ``main.cu:57-67``) by construction: equality is
on full-token 64-bit hashes (token length is mixed in as well).

No token strings are materialized on device.  For reporting, each table entry
carries the position/length of its first occurrence so the host can recover
the exact bytes from the source (SURVEY §7 "String recovery").
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from mapreduce_tpu import constants


class TokenStream(NamedTuple):
    """Per-byte-position token emissions (shape = input byte count).

    Positions that do not end a token carry the sentinel key and count 0; they
    are compacted away by :func:`mapreduce_tpu.ops.segment.unique_count`.
    """

    key_hi: jax.Array  # uint32
    key_lo: jax.Array  # uint32
    count: jax.Array  # uint32: 1 at token ends, else 0
    pos: jax.Array  # uint32: byte offset of the token's *first* byte
    length: jax.Array  # uint32: token length in bytes


def separator_mask(data: jax.Array) -> jax.Array:
    """True where the byte is a separator (whitespace / NUL pad)."""
    sep = jnp.zeros(data.shape, dtype=jnp.bool_)
    for b in constants.SEPARATOR_BYTES:
        sep = sep | (data == jnp.uint8(b))
    return sep


def _fmix32(x: jax.Array) -> jax.Array:
    """murmur3 finalizer: bijective avalanche on a uint32 lane."""
    x = x ^ (x >> 16)
    x = x * constants.FMIX_C1
    x = x ^ (x >> 13)
    x = x * constants.FMIX_C2
    x = x ^ (x >> 16)
    return x


def _segmented_combine(a, b):
    """Associative combine for the segmented affine-map scan.

    Element = (reset, v1, p1, v2, p2, length).  ``(v, p)`` represents the
    affine map ``h -> h*p + v`` accumulated since the last reset; ``reset``
    marks that the right operand contains a segment boundary, which discards
    the left operand's contribution.
    """
    a_f, a_v1, a_p1, a_v2, a_p2, a_len = a
    b_f, b_v1, b_p1, b_v2, b_p2, b_len = b
    f = a_f | b_f
    v1 = jnp.where(b_f, b_v1, a_v1 * b_p1 + b_v1)
    p1 = jnp.where(b_f, b_p1, a_p1 * b_p1)
    v2 = jnp.where(b_f, b_v2, a_v2 * b_p2 + b_v2)
    p2 = jnp.where(b_f, b_p2, a_p2 * b_p2)
    ln = jnp.where(b_f, b_len, a_len + b_len)
    return (f, v1, p1, v2, p2, ln)


def _token_keys(v1: jax.Array, v2: jax.Array, length: jax.Array):
    """The 64-bit key of a token from its two rolling hashes and its length
    — the one definition every backend shares."""
    key_hi = _fmix32(v1 ^ length)
    key_lo = _fmix32(v2 + jnp.uint32(0x9E3779B9) * length)

    # Clamp away from the two reserved keys (probability 2**-63 per token):
    # (sent, sent) marks dead rows and (sent, sent-1) marks overlong-poison
    # rows (:mod:`..ops.pallas.tokenize`); a real token hashing onto either
    # would be misread structurally, so both remap to (sent, sent-2).  Every
    # backend's clamp MUST share this rule or their keys drift.
    sentinel = jnp.uint32(constants.SENTINEL_KEY)
    at_sentinel = (key_hi == sentinel) & (key_lo >= sentinel - jnp.uint32(1))
    key_lo = jnp.where(at_sentinel, sentinel - jnp.uint32(2), key_lo)
    return key_hi, key_lo


def last_token(windows: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """``(key_hi, key_lo, length)`` of the token ending at the LAST byte of
    each row of ``windows`` (uint8[R, W]); ``length`` is 0 where that byte
    is a separator, and W where the run may continue left of the window.

    Bit-identical to what :func:`tokenize` emits at a row's last position,
    but closed-form: one max-reduction finds the run start, one sum per
    hash lane adds ``c_j * B**(W-1-j)`` over the run.  A vmapped
    :func:`tokenize` over tens of thousands of windows costs the TPU
    compiler many minutes; this costs seconds.
    """
    rows, wlen = windows.shape
    sep = separator_mask(windows)
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, wlen), 1)
    last_sep = jnp.max(jnp.where(sep, col, -1), axis=1, keepdims=True)
    intok = col > last_sep
    length = (wlen - 1 - last_sep[:, 0]).astype(jnp.uint32)
    c = windows.astype(jnp.uint32) + jnp.uint32(1)

    def lane(base) -> jax.Array:
        powers = np.array([pow(int(base), wlen - 1 - j, 1 << 32)
                           for j in range(wlen)], dtype=np.uint32)
        return jnp.sum(jnp.where(intok, c * powers[None, :], jnp.uint32(0)),
                       axis=1, dtype=jnp.uint32)

    key_hi, key_lo = _token_keys(lane(constants.HASH_BASE_1),
                                 lane(constants.HASH_BASE_2), length)
    return key_hi, key_lo, length


def tokenize(data: jax.Array, base_offset: jax.Array | int = 0) -> TokenStream:
    """Hash every whitespace-delimited token in a flat uint8 buffer.

    Args:
      data: uint8[N] byte buffer.  N is static.  The buffer is treated as if
        followed by a separator, so a token touching the end is complete —
        ingest must therefore only split shards at separator boundaries.
      base_offset: uint32 added to emitted positions (for global addressing of
        a shard within a larger stream).

    Returns:
      A :class:`TokenStream` of length N.
    """
    if data.dtype != jnp.uint8:
        raise TypeError(f"tokenize expects uint8 bytes, got {data.dtype}")
    if data.ndim != 1:
        raise ValueError(f"tokenize expects a flat buffer, got shape {data.shape}")

    n = data.shape[0]
    sep = separator_mask(data)
    c = data.astype(jnp.uint32)

    one = jnp.uint32(1)
    zero = jnp.uint32(0)
    elems = (
        sep,
        jnp.where(sep, zero, c + one),
        jnp.where(sep, one, jnp.uint32(constants.HASH_BASE_1)),
        jnp.where(sep, zero, c + one),
        jnp.where(sep, one, jnp.uint32(constants.HASH_BASE_2)),
        jnp.where(sep, zero, one),
    )
    _, v1, _, v2, _, length = jax.lax.associative_scan(_segmented_combine, elems)

    # A position ends a token iff it is a non-separator whose successor is a
    # separator or the end of the buffer.
    next_sep = jnp.concatenate([sep[1:], jnp.ones((1,), dtype=jnp.bool_)])
    is_end = (~sep) & next_sep

    key_hi, key_lo = _token_keys(v1, v2, length)
    sentinel = jnp.uint32(constants.SENTINEL_KEY)

    # Non-token positions carry the sentinel so they sort to the end.
    key_hi = jnp.where(is_end, key_hi, sentinel)
    key_lo = jnp.where(is_end, key_lo, sentinel)

    idx = jax.lax.broadcasted_iota(jnp.uint32, (n, 1), 0).squeeze(-1)
    base = jnp.asarray(base_offset, dtype=jnp.uint32)
    start = idx + one - length + base  # first byte of the token
    return TokenStream(
        key_hi=key_hi,
        key_lo=key_lo,
        count=is_end.astype(jnp.uint32),
        pos=jnp.where(is_end, start, jnp.uint32(constants.POS_INF)),
        length=jnp.where(is_end, length, zero),
    )


def _last_valid_combine(a, b):
    """Associative combine: rightmost valid element wins (carry-forward)."""
    a_v, a_hi, a_lo, a_pos = a
    b_v, b_hi, b_lo, b_pos = b
    return (
        a_v | b_v,
        jnp.where(b_v, b_hi, a_hi),
        jnp.where(b_v, b_lo, a_lo),
        jnp.where(b_v, b_pos, a_pos),
    )


def _extend_grams(gram: TokenStream, tokens: TokenStream) -> TokenStream:
    """One pairing step: (k)-gram stream = (k-1)-gram stream x token stream.

    For every token end, the (k-1)-gram ending at the *previous* token is
    found with a carry-forward associative scan over the gram stream (the
    bytes of the current token cannot hold a gram end, so "last gram end
    before this position" is exactly "gram ending at the previous token").
    The pairing is order-sensitive: the carried key is multiplied by an odd
    base (bijective) before mixing in the current token's key.
    """
    valid = gram.count > 0
    inc = jax.lax.associative_scan(
        _last_valid_combine, (valid, gram.key_hi, gram.key_lo, gram.pos))

    # Exclusive variant: shift the inclusive result right one position, so a
    # gram ending AT p never pairs with itself.
    def shift(x, fill):
        return jnp.concatenate([jnp.full((1,), fill, x.dtype), x[:-1]])

    c_valid = shift(inc[0], False)
    c_hi = shift(inc[1], jnp.uint32(0))
    c_lo = shift(inc[2], jnp.uint32(0))
    c_pos = shift(inc[3], jnp.uint32(constants.POS_INF))

    is_end = (tokens.count > 0) & c_valid
    key_hi = _fmix32(c_hi * jnp.uint32(constants.HASH_BASE_1) ^ tokens.key_hi)
    key_lo = _fmix32(c_lo * jnp.uint32(constants.HASH_BASE_2) ^ tokens.key_lo)

    sentinel = jnp.uint32(constants.SENTINEL_KEY)
    at_sentinel = (key_hi == sentinel) & (key_lo >= sentinel - jnp.uint32(1))
    key_lo = jnp.where(at_sentinel, sentinel - jnp.uint32(2), key_lo)

    # Span = first byte of the gram's first token .. last byte of the current
    # token (separator bytes in between included), so host string recovery
    # reads the exact source text of the gram.
    length = tokens.pos + tokens.length - c_pos
    return TokenStream(
        key_hi=jnp.where(is_end, key_hi, sentinel),
        key_lo=jnp.where(is_end, key_lo, sentinel),
        count=is_end.astype(jnp.uint32),
        pos=jnp.where(is_end, c_pos, jnp.uint32(constants.POS_INF)),
        length=jnp.where(is_end, length, jnp.uint32(0)),
    )


def ngrams(stream: TokenStream, n: int) -> TokenStream:
    """Derive the n-token-gram stream from a token stream (n >= 1).

    Each emission is keyed by an order-sensitive 64-bit hash of its n
    consecutive tokens and carries the byte span from the first token's first
    byte through the last token's last byte — so the host recovers the exact
    source text (inter-word separators included) the same way it recovers
    single words.  This per-buffer op forms only IN-BUFFER grams (the first
    n-1 tokens start no gram); streamed runs form the cross-chunk ones
    exactly via the seam-carry machinery of
    :class:`mapreduce_tpu.models.wordcount.NGramCountJob`.

    The reference has no n-gram capability (its map UDF emits single words
    only, ``mapper`` ``main.cu:37-54``); this is a beyond-parity model family
    riding the same tokenize -> table -> collective machinery.
    """
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    gram = stream
    for _ in range(n - 1):
        gram = _extend_grams(gram, stream)
    return gram


def token_count(data: jax.Array) -> jax.Array:
    """Total number of tokens in a flat uint8 buffer (uint32 scalar)."""
    sep = separator_mask(data)
    next_sep = jnp.concatenate([sep[1:], jnp.ones((1,), dtype=jnp.bool_)])
    return jnp.sum(((~sep) & next_sep).astype(jnp.uint32))


def pad_to(data: np.ndarray | bytes, size: int) -> np.ndarray:
    """Host-side: right-pad raw bytes with PAD_BYTE to a static size."""
    buf = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else data
    if buf.shape[0] > size:
        raise ValueError(f"buffer of {buf.shape[0]} bytes exceeds static size {size}")
    out = np.full((size,), constants.PAD_BYTE, dtype=np.uint8)
    out[: buf.shape[0]] = buf
    return out
