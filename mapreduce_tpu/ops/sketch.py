"""HyperLogLog distinct-count sketch (a second model family).

The reference can report at most 10 distinct words before silently
corrupting memory (``MAX_OUTPUT_COUNT``, ``main.cu:13,103-104``); the count
table here is exact up to its configured capacity and *accounts* what it
drops, but past capacity the distinct count degrades to an upper bound
(``CountTable.dropped_uniques``).  The sketch closes that gap: a fixed
2**p-register HyperLogLog tracks the number of distinct keys with ~1.04/√m
relative error at any corpus size, in O(KB) of state.

TPU-first shape of the design:

* Registers update from **deduplicated per-chunk table keys** (the ≤64K-row
  batch table the map phase already builds), never from the raw multi-million
  entry token stream — scatter cost scales with input size on TPU, and
  re-scattering duplicate tokens is pure waste.  HLL's register-max is
  idempotent, so cross-chunk duplicates are harmless.
* The register update is one ``scatter-max``; the cross-device/cross-chunk
  merge is elementwise ``maximum`` — an associative, commutative monoid that
  rides :func:`...collectives.tree_merge` (or ``lax.pmax``) like any other
  accumulator in this framework.
* The keys are the tokenizer's 64-bit hashes (khi, klo), already
  avalanche-finalized (murmur fmix, ``ops/tokenize.py``) — no rehashing.

Estimation (host-side, numpy float64) uses the standard bias-corrected HLL
estimator with the small-range (linear counting) correction.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from mapreduce_tpu import constants
from mapreduce_tpu.ops.tokenize import _fmix32  # single avalanche owner

DEFAULT_PRECISION = 14  # 2**14 registers = 64 KiB of uint32; ~0.8% error


def empty(precision: int = DEFAULT_PRECISION) -> jax.Array:
    """Zeroed registers, uint32[2**precision]."""
    if not 4 <= precision <= 18:
        raise ValueError(f"precision must be in [4, 18], got {precision}")
    return jnp.zeros((1 << precision,), dtype=jnp.uint32)


def _bit_length(x: jax.Array) -> jax.Array:
    """Per-lane bit length of a uint32 (0 for 0), elementwise (no clz on
    the VPU; 5-step binary search)."""
    n = jnp.zeros(x.shape, jnp.uint32)
    for shift in (16, 8, 4, 2, 1):
        big = x >= (jnp.uint32(1) << shift)
        n = jnp.where(big, n + shift, n)
        x = jnp.where(big, x >> shift, x)
    return n + (x > 0).astype(jnp.uint32)


def update_from_keys(registers: jax.Array, key_hi: jax.Array,
                     key_lo: jax.Array, valid: jax.Array) -> jax.Array:
    """Fold a batch of 64-bit keys into the registers.

    ``valid`` masks real rows (count-table slots may be empty/sentinel).
    Bucket = low p bits of key_hi; rho = leading-zero count of key_lo + 1
    (klo == 0 maps to the max rho, 33, as the all-zero suffix).
    """
    bucket = (key_hi & jnp.uint32(registers.shape[0] - 1)).astype(jnp.int32)
    rho = jnp.uint32(33) - _bit_length(key_lo)
    rho = jnp.where(valid, rho, jnp.uint32(0))  # max with 0 = no-op
    return registers.at[bucket].max(rho, mode="drop")


def merge(a: jax.Array, b: jax.Array) -> jax.Array:
    """Associative, commutative, idempotent register merge."""
    return jnp.maximum(a, b)


def estimate(registers: np.ndarray | jax.Array) -> float:
    """Bias-corrected HLL cardinality estimate (host-side)."""
    regs = np.asarray(registers, dtype=np.float64)
    m = regs.shape[0]
    alpha = {16: 0.673, 32: 0.697, 64: 0.709}.get(m, 0.7213 / (1 + 1.079 / m))
    raw = alpha * m * m / np.sum(np.exp2(-regs))
    zeros = int(np.sum(regs == 0))
    if raw <= 2.5 * m and zeros:
        return float(m * np.log(m / zeros))  # linear counting, small range
    return float(raw)


# --- Count-Min Sketch --------------------------------------------------------
# The exact CountTable answers "how often did word w occur" only for the words
# it retained; past capacity, spilled words vanish into dropped_* scalars.
# The CMS closes the *frequency* gap the way HLL closes the distinct-count
# gap: a (depth x width) uint32 matrix whose row-wise min upper-bounds any
# key's true count, with error <= total/width per row w.h.p.  Like the HLL,
# it updates from the deduplicated per-chunk batch table (depth
# capacity-sized scatter-adds, never stream-sized), and merges by elementwise
# addition — associative + commutative, riding the same collectives.

CMS_DEPTH = 4
CMS_WIDTH_LOG2 = 16  # 4 x 64K x uint32 = 1 MiB of state

# Odd row salts (xxhash/murmur-family primes) making the per-row bucket
# hashes effectively independent.
_CMS_SALTS = (0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F,
              0x165667B1, 0xFD7046C5, 0xB55A4F09, 0x2127599B)


def cms_empty(depth: int = CMS_DEPTH, width_log2: int = CMS_WIDTH_LOG2) -> jax.Array:
    """Zeroed sketch, uint32[depth, 2**width_log2]."""
    if not 1 <= depth <= len(_CMS_SALTS):
        raise ValueError(f"depth must be in [1, {len(_CMS_SALTS)}], got {depth}")
    if not 8 <= width_log2 <= 24:
        raise ValueError(f"width_log2 must be in [8, 24], got {width_log2}")
    return jnp.zeros((depth, 1 << width_log2), dtype=jnp.uint32)


def _cms_bucket_jnp(key_hi: jax.Array, key_lo: jax.Array, row: int,
                    width_mask: int) -> jax.Array:
    h = _fmix32((key_hi ^ jnp.uint32(_CMS_SALTS[row])) * constants.FMIX_C1
                + key_lo * constants.FMIX_C2 + jnp.uint32(row))
    return (h & jnp.uint32(width_mask)).astype(jnp.int32)


def cms_update(cms: jax.Array, key_hi: jax.Array, key_lo: jax.Array,
               counts: jax.Array) -> jax.Array:
    """Add a batch of (key, count) rows into the sketch.

    Empty table slots carry count 0, so no validity mask is needed: adding
    zero to an arbitrary bucket is a no-op.  All depth rows go through ONE
    flattened scatter-add: on TPU each scatter carries a large fixed cost
    (round-4/5 chip notes), so one scatter of depth*n updates beats depth scatters
    of n.
    """
    depth, width = cms.shape
    flat_idx = jnp.concatenate([
        _cms_bucket_jnp(key_hi, key_lo, r, width - 1) + jnp.int32(r * width)
        for r in range(depth)])
    updates = jnp.tile(counts.astype(jnp.uint32), depth)
    return cms.reshape(-1).at[flat_idx].add(updates, mode="drop").reshape(depth, width)


def cms_merge(a: jax.Array, b: jax.Array) -> jax.Array:
    """Associative, commutative sketch merge."""
    return a + b


# Host-side mirrors (python-int arithmetic, masked to 32 bits) so any word —
# retained or spilled — can be queried after the run without a device trip.

_M32 = 0xFFFFFFFF


def _fmix32_host(x: int) -> int:
    x ^= x >> 16
    x = (x * int(constants.FMIX_C1)) & _M32
    x ^= x >> 13
    x = (x * int(constants.FMIX_C2)) & _M32
    x ^= x >> 16
    return x


def _clamp_sentinel(key_hi: int, key_lo: int) -> tuple[int, int]:
    if key_hi == int(constants.SENTINEL_KEY) and key_lo == int(constants.SENTINEL_KEY):
        key_lo = (key_lo - 1) & _M32
    return key_hi, key_lo


def _hash_token(token: bytes) -> tuple[int, int]:
    v1 = v2 = 0
    for c in token:
        v1 = (v1 * int(constants.HASH_BASE_1) + c + 1) & _M32
        v2 = (v2 * int(constants.HASH_BASE_2) + c + 1) & _M32
    n = len(token)
    return _clamp_sentinel(_fmix32_host(v1 ^ (n & _M32)),
                           _fmix32_host((v2 + 0x9E3779B9 * n) & _M32))


def hash_word(word: bytes) -> tuple[int, int]:
    """The device 64-bit key for ``word`` — a single token OR an n-gram span
    (host mirror).

    A ``word`` containing separator bytes is keyed the way the device keys
    grams: per-token rolling-hash + fmix (mirroring
    :func:`mapreduce_tpu.ops.tokenize.tokenize`), folded left-to-right with
    the gram carry-mix (mirroring ``_extend_grams``).  The device never emits
    a *token* containing a separator, so the interpretations cannot collide.
    Pinned to the device hashes by
    ``tests/test_sketch.py::test_hash_word_matches_device`` (tokens) and
    ``test_hash_word_matches_device_grams`` (spans).
    """
    seps = bytes(constants.SEPARATOR_BYTES)
    tokens, cur = [], bytearray()
    for c in word:
        if c in seps:
            if cur:
                tokens.append(bytes(cur))
                cur = bytearray()
        else:
            cur.append(c)
    if cur:
        tokens.append(bytes(cur))
    if not tokens:
        return _hash_token(b"")
    key_hi, key_lo = _hash_token(tokens[0])
    for tok in tokens[1:]:
        t_hi, t_lo = _hash_token(tok)
        key_hi, key_lo = _clamp_sentinel(
            _fmix32_host(((key_hi * int(constants.HASH_BASE_1)) & _M32) ^ t_hi),
            _fmix32_host(((key_lo * int(constants.HASH_BASE_2)) & _M32) ^ t_lo))
    return key_hi, key_lo


def cms_query(cms: np.ndarray, word: bytes) -> int:
    """Estimated occurrence count of ``word``: min over rows (host-side).

    Never under-estimates a word the sketch saw; over-estimates by at most
    ~total/width per row with probability 1 - 2**-depth.
    """
    sk = np.asarray(cms)
    depth, width = sk.shape
    key_hi, key_lo = hash_word(word)
    est = None
    for r in range(depth):
        h = _fmix32_host(((key_hi ^ _CMS_SALTS[r]) * int(constants.FMIX_C1)
                          + key_lo * int(constants.FMIX_C2) + r) & _M32)
        v = int(sk[r, h & (width - 1)])
        est = v if est is None else min(est, v)
    return est
