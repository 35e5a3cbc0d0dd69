"""Pallas TPU kernel: fused tokenize + rolling-hash in one HBM pass.

TPU-native replacement for the map-phase device work of the reference (the
per-thread char-copy loops of ``mapper``, ``main.cu:37-54``, plus the host
tokenizer, ``main.cu:187-202``).  The pure-XLA formulation in
:mod:`mapreduce_tpu.ops.tokenize` runs a segmented ``associative_scan`` —
log-depth but several full-array materializations.  This kernel computes the
identical per-position (key_hi, key_lo, length) outputs in a *single* pass:
bytes stream HBM -> VMEM once, all W-byte lookback happens on-chip, and only
the three token-end output planes go back to HBM.

Layout
------
A flat uint8 chunk of N bytes is viewed column-major as ``(L, 128)``:
lane j holds the contiguous byte segment ``[j*L, (j+1)*L)``, rows are byte
positions within the segment.  A shift by one *byte* is then a shift by one
*row* — a cheap sublane move — and the W-step lookback loop is W static row
slices, fully vectorized over 128 lanes x block_rows sublanes.

The grid walks row-blocks top to bottom.  TPU grids execute sequentially, so
a ``(W+1, 128)`` VMEM scratch carries the previous block's tail rows: the
lookback window never re-reads HBM.

Token length is bounded by W (default 32).  Three cases leave the kernel for
the two tiny fix-up passes the wrapper runs in XLA:

* tokens touching a 128-lane *seam* (the boundary between consecutive byte
  segments, where "previous byte" lives in another lane) are suppressed
  in-kernel and re-tokenized from 129 seam windows of ``2W+2`` bytes each
  (<= 9 KB total) — the chunk-seam strategy of SURVEY §7 applied at lane
  granularity;
* tokens longer than W bytes are dropped and *counted* (exactly once, at
  their true end) into an overlong counter the caller folds into the count
  table's ``dropped_*`` accounting — never silent corruption (contrast the
  reference's unchecked buffer overflows past MAX_WORD_COUNT, ``main.cu:184``);
* the hash recurrence, fmix32 finalization, and sentinel clamping replicate
  :func:`mapreduce_tpu.ops.tokenize.tokenize` bit-for-bit, so tables built
  from either backend merge interchangeably.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mapreduce_tpu import constants
from mapreduce_tpu.ops import tokenize as tok_ops
from mapreduce_tpu.ops.pallas import meta
from mapreduce_tpu.ops.tokenize import TokenStream

LANES = 128
DEFAULT_MAX_TOKEN = 32  # W: max token bytes handled fully on the fast path
DEFAULT_BLOCK_ROWS = 256

# Fused-path seam-carry plane (one small second kernel input): rows
# [0, w+1) hold each lane's PREVIOUS lane's last w+1 bytes (PAD for lane
# 0), rows [AUX_HEAD_ROW, AUX_ROWS) hold the NEXT lane's first byte
# replicated (PAD for lane 127).  96 rows keep the uint8 block on the
# (32, 128) tile grid; AUX_HEAD_ROW = 64 leaves room for the W <= 63
# maximum tail.  With this plane resident the kernel resolves 128-lane
# seams entirely in VMEM — the XLA seam fix-up pass (and its per-chunk
# re-read of seam bytes from HBM) disappears from the fused map path.
AUX_ROWS = 96
AUX_HEAD_ROW = 64

# Analyzer contract (costcheck vmem/race passes): compact mode emits a
# spill counter (output #6) whose nonzero value means the planes are
# INCOMPLETE — the caller MUST wrap a full-resolution fallback in lax.cond
# (models/wordcount._map_stream does).  The pair path (5 outputs) is exact.
meta.register(meta.KernelMeta(
    name="_tokenize_kernel",
    spills=lambda num_outputs: num_outputs >= 6,
    description="fused tokenize+hash; compact mode spills past the "
                "per-window slot budget"))


def vmem_plan(block_rows: int = DEFAULT_BLOCK_ROWS,
              compact_slots: int = 0, w: int = DEFAULT_MAX_TOKEN,
              lane_major: bool = False, fused: bool = False,
              combiner_slots: int = 0,
              aux_rows: int = AUX_ROWS) -> meta.VmemPlan:
    """Static VMEM/SMEM footprint of one tokenize-kernel geometry — the
    analyzer's metadata hook (ops/pallas/meta.py).  Delegates to the
    jax-free :func:`...meta.tokenize_plan` constructor (ISSUE 12: the
    SAME arithmetic prices search candidates and derives the shipped
    ``production_plans`` list, so footprints cannot drift from what
    :func:`_column_pass` binds)."""
    return meta.tokenize_plan(block_rows=block_rows,
                              compact_slots=compact_slots, w=w,
                              lane_major=lane_major, fused=fused,
                              combiner_slots=combiner_slots,
                              aux_rows=aux_rows)


class CombinerCache(NamedTuple):
    """Flushed hot-key cache planes of one chunk (ISSUE 11): per lane, up
    to C resident entries — the first C distinct keys the lane saw, every
    occurrence of which was counted here instead of emitted.  All planes
    are ``(C, LANES)`` uint32; ``count == 0`` marks a never-filled slot
    (sentinel keys).  ``packed`` is the entry's FIRST in-lane occurrence
    (``start << 6 | len``), so a table built from these rows merges with
    the thinned stream's table bit-identically to the uncombined build
    (counts add exactly; the merge keeps each key's smallest position).

    Host-derivable telemetry (no extra kernel counters needed):
    ``hits = count.sum()`` occurrences absorbed, ``flushes = (count >
    0).sum()`` rows re-emitted at the flush, ``evicted = (count ==
    1).sum()`` cold entries whose slot bought nothing (the flush is where
    every entry is evicted; count-1 entries are the wasted ones).
    """

    key_hi: jax.Array
    key_lo: jax.Array
    count: jax.Array
    packed: jax.Array


class PackedTokenStream(NamedTuple):
    """A TokenStream (first five fields, same order — duck-compatible) plus
    the kernel's raw ``start << 6 | len`` plane and exact token count.

    Aggregation consumes ``packed`` directly as its sort payload and
    ``total`` for drop accounting, skipping two stream-sized HBM passes that
    reconstructing them from pos/length/count would cost.  ``packed`` is
    None when a nonzero base_offset made the raw plane unusable as-is.
    """

    key_hi: jax.Array
    key_lo: jax.Array
    count: jax.Array
    pos: jax.Array
    length: jax.Array
    packed: jax.Array | None
    total: jax.Array


def _pow_mod32(base: np.uint32, k: int) -> np.uint32:
    return np.uint32(pow(int(base), k, 1 << 32))


# Bit-for-bit parity with the XLA backend is the contract; share its hashing
# definition rather than copying it.
_fmix32 = tok_ops._fmix32


def _sep_mask_i32(x: jax.Array) -> jax.Array:
    """Separator test on int32-widened bytes.

    Mosaic (v5e) cannot lower 8-bit vector comparisons ("Target does not
    support this comparison"), so the kernel widens bytes to int32 at load
    and classifies there.  Derived from ``constants.SEPARATOR_BYTES`` — the
    same source of truth as :func:`...ops.tokenize.separator_mask` — so the
    backends can never drift apart.
    """
    sep = x == constants.SEPARATOR_BYTES[0]
    for b in constants.SEPARATOR_BYTES[1:]:
        sep = sep | (x == b)
    return sep


def _compact_planes(khi, klo, packed, has, slots: int):
    """In-VMEM slot compaction of pair-resolution planes (round-4 review #2).

    ``has`` marks live pair rows (emission or poison).  Per lane, live rows
    keep their order and pack into the first ``rank`` output slots; the
    rest fill with the all-ones sentinel.

    Algorithm: log-shift compaction.  Each live row must move UP (toward
    row 0) by ``d = #dead rows above it`` — d is non-decreasing down a
    lane, so applying its binary decomposition one bit at a time (shift by
    2^b where bit b of the remaining distance is set) can never collide:
    if the element at row j still has to travel >= 2^b, every row between
    its destination and j holds either a hole or an element also moving.
    Monotonicity survives each pass (clearing low bits preserves order),
    so log2(p) passes of three (p, L) selects replace the previous
    per-slot one-hot selection — O(p log p) VPU work instead of
    O(p * slots), measured ~20 ms/chunk of kernel time at S=88
    (round-4 chip notes), and a scoped-VMEM footprint back near the
    pair path's.

    Returns (khi[slots,L], klo[slots,L], packed[slots,L], n_spilled) where
    n_spilled counts live rows beyond the per-lane budget — the caller's
    exactness fallback trigger.
    """
    p, lanes = has.shape
    rank = has.astype(jnp.int32)
    k = 1
    while k < p:  # inclusive cumsum along sublanes: log-shift adds
        top = jnp.zeros((k, lanes), jnp.int32)
        rank = rank + jnp.concatenate([top, rank[:-k]], axis=0)
        k *= 2
    lane_live = rank[p - 1:p, :]  # (1, L) live rows per lane
    spilled = jnp.maximum(lane_live - slots, 0)
    n_spilled = jnp.sum(spilled).astype(jnp.uint32)

    row = jax.lax.broadcasted_iota(jnp.int32, (p, lanes), 0)
    dist = jnp.where(has, row - (rank - 1), 0)  # dead rows above each live row
    vals = [khi.astype(jnp.int32), klo.astype(jnp.int32),
            packed.astype(jnp.int32)]
    # Masks ride as int32 0/1 planes: Mosaic cannot shift/concatenate i1
    # vector registers ("Invalid vector register cast" on the chip), the
    # same class of constraint as the int32-widened separator test above.
    live = has.astype(jnp.int32)
    s = 1
    while s < p:
        def up(x):  # x[i] <- x[i+s] (shift toward row 0); int32 planes only
            pad = jnp.zeros((s, lanes), jnp.int32)
            return jnp.concatenate([x[s:], pad], axis=0)

        src_live = up(live)
        src_dist = up(dist)
        move_in = (src_live != 0) & ((src_dist & s) != 0)
        stay = (live != 0) & ((dist & s) == 0)
        vals = [jnp.where(move_in, up(v), jnp.where(stay, v, -1))
                for v in vals]
        dist = jnp.where(move_in, src_dist - s, dist)
        live = (move_in | stay).astype(jnp.int32)
        s *= 2
    sent = jnp.uint32(0xFFFFFFFF)
    out = [jnp.where(live[:slots] != 0, v[:slots].astype(jnp.uint32), sent)
           for v in vals]
    return out[0], out[1], out[2], n_spilled


def _tokenize_kernel(x_ref, *refs, w: int, block_rows: int, data_rows: int,
                     compact_slots: int = 0, lane_major: bool = False,
                     fused: bool = False, combiner_slots: int = 0):
    """One grid step: emit pair-compacted (key_hi, key_lo, packed) planes.

    Logical output row t of block i describes byte-row ``m = i*block_rows +
    t - 1`` of each lane (one-row offset so the next-byte separator test only
    ever looks at rows already resident).  A token end at byte row m requires
    byte m+1 to be a separator, so two consecutive rows can never both emit —
    the kernel folds each (2r, 2r+1) row pair to one output row *in VMEM*,
    writing half-resolution planes: at ~10 GB/s effective HBM bandwidth on
    the bench chip, the full-resolution planes plus the XLA-side re-read/
    re-write for pairing and (pos,len) packing were ~700 MB of traffic per
    32 MB chunk — most of the map phase's cost.

    ``packed`` = ``start_pos << 6 | length`` (the downstream sort payload;
    requires data length < 2**26 and w <= 63, validated by the wrapper);
    non-emitting pairs carry the sentinel key and all-ones packed.  ``ntok``
    accumulates the total emission count so callers get exact totals without
    another stream-sized pass.

    ``fused`` is the fully-fused map path (ISSUE 6): the byte input is the
    RAW ``(LANES, block_rows)`` lane view (transposed to the column layout
    in VMEM — the sublane-shift lookback structure is kept, the XLA-side
    transpose+pad materialization is not), and a second ``(AUX_ROWS,
    LANES)`` seam-carry input resolves 128-lane seams in-kernel: the i==0
    carry holds the PREVIOUS lane's tail instead of artificial separators,
    and the last data row's next-byte test reads the NEXT lane's first
    byte.  No token is deferred — the XLA seam fix-up pass (and its HBM
    round-trip over seam windows) does not exist on this path.
    """
    # Positional refs: the optional seam-carry aux input (fused mode),
    # the three planes + two scalars, then the optional spill scalar
    # (compact mode only), the optional combiner cache planes, and the
    # carry scratch.
    if fused:
        aux_ref, refs = refs[0], refs[1:]
    else:
        aux_ref = None
    khi_ref, klo_ref, packed_ref, over_ref, ntok_ref = refs[:5]
    refs = refs[5:]
    if compact_slots:
        spill_ref, refs = refs[0], refs[1:]
    else:
        spill_ref = None
    if combiner_slots:
        (ckhi_ref, cklo_ref, ccnt_ref, cpk_ref), refs = refs[:4], refs[4:]
    (carry_ref,) = refs
    i = pl.program_id(0)
    tb = block_rows
    aux = aux_ref[:].astype(jnp.int32) if fused else None

    @pl.when(i == 0)
    def _():
        if fused:
            # The carry above each lane's first block is the PREVIOUS
            # lane's last w+1 bytes (PAD for lane 0): the lookback crosses
            # lane seams over real bytes, in VMEM.
            carry_ref[:] = aux[: w + 1, :]
        else:
            # Rows "above" the first block are artificial separators: every
            # lane top is a segment start (real continuation is the previous
            # lane's tail, which the seam pass owns).
            carry_ref[:] = jnp.full_like(carry_ref, constants.PAD_BYTE)
        over_ref[0, 0] = jnp.uint32(0)
        ntok_ref[0, 0] = jnp.uint32(0)
        if spill_ref is not None:
            spill_ref[0, 0] = jnp.uint32(0)
        if combiner_slots:
            # Hot-key cache state rides REVISITED output blocks (index map
            # pinned to (0, 0)) under the guarded-init + read-modify-write
            # discipline the kernel-race pass certifies — the spill-scalar
            # idiom widened to planes.  After the last grid step the refs
            # hold the flushed cache verbatim: no separate flush pass.
            ckhi_ref[:] = jnp.full_like(ckhi_ref,
                                        jnp.uint32(constants.SENTINEL_KEY))
            cklo_ref[:] = jnp.full_like(cklo_ref,
                                        jnp.uint32(constants.SENTINEL_KEY))
            ccnt_ref[:] = jnp.zeros_like(ccnt_ref)
            cpk_ref[:] = jnp.full_like(cpk_ref, jnp.uint32(0xFFFFFFFF))

    # Widen bytes to int32 immediately: v5e Mosaic has no 8-bit vector
    # compares, and 32-bit lanes are the VPU-native layout anyway.  The
    # fused path's raw lane-view block transposes to the same column
    # layout here (a VMEM-local move) so the whole lookback below is
    # shared verbatim between the paths.
    x = x_ref[:].astype(jnp.int32).T if fused else x_ref[:].astype(jnp.int32)
    ext = jnp.concatenate([carry_ref[:], x], axis=0)  # (w+1+tb, LANES) int32
    carry_ref[:] = x[tb - (w + 1):, :]

    sep = _sep_mask_i32(ext)
    c = (ext + 1).astype(jnp.uint32)

    row_in_block = jax.lax.broadcasted_iota(jnp.int32, (tb, LANES), 0)
    m = i * tb + row_in_block - 1  # byte row within the lane's segment

    # Positions handled this step: ext rows [w, w+tb) = byte rows m below.
    cur_sep = sep[w:w + tb]
    nxt_sep = sep[w + 1:w + tb + 1]
    if fused:
        # The lane's LAST data byte's successor is the next lane's first
        # byte (aux head row), not the pad row the column view shows.
        # The select runs on int32 0/1 planes: a select between i1 planes
        # lowers through an i8 -> i1 truncation Mosaic refuses on v5e
        # ("Unsupported target bitwidth for truncation").
        nh_sep = _sep_mask_i32(aux[AUX_HEAD_ROW:AUX_HEAD_ROW + 1, :])
        nxt_sep = jnp.where(m == data_rows - 1,
                            jnp.broadcast_to(nh_sep.astype(jnp.int32),
                                             (tb, LANES)),
                            nxt_sep.astype(jnp.int32)) != 0
    is_end = (~cur_sep) & nxt_sep

    intok = ~cur_sep
    h1 = jnp.where(intok, c[w:w + tb], jnp.uint32(0))
    h2 = h1
    ln = intok.astype(jnp.uint32)
    for k in range(1, w):
        intok = intok & ~sep[w - k:w - k + tb]
        ck = c[w - k:w - k + tb]
        h1 = h1 + jnp.where(intok, ck * _pow_mod32(constants.HASH_BASE_1, k), jnp.uint32(0))
        h2 = h2 + jnp.where(intok, ck * _pow_mod32(constants.HASH_BASE_2, k), jnp.uint32(0))
        ln = ln + intok.astype(jnp.uint32)

    # True length may exceed w: the byte w back is still inside the run.
    run_exceeds_w = intok & ~sep[0:tb]

    if fused:
        # No deferral: the seam-carry aux made every lookback and every
        # next-byte test exact across lane seams.  Only the phantom m=-1
        # row (block 0's one-row output trail — the previous lane's last
        # byte, owned by THAT lane's last data row) is masked.
        alive = m >= 0
        emit = is_end & ~run_exceeds_w & alive
        overlong_here = is_end & run_exceeds_w & alive
    else:
        # Defer to the seam pass: tokens starting at lane row 0 (previous
        # byte is another lane's data) and tokens ending at the lane's last
        # data row (next byte is another lane's data, so is_end itself is
        # unreliable there).
        starts_at_lane_top = ln.astype(jnp.int32) == m + 1
        ends_at_lane_bottom = m == data_rows - 1
        emit = is_end & ~run_exceeds_w & ~starts_at_lane_top \
            & ~ends_at_lane_bottom

        # Overlong runs are counted exactly once, at their true end.  Runs
        # whose lookback crosses the lane top are counted by the seam pass
        # instead (their suppression here shows up as starts_at_lane_top=
        # False only when the lookback window is fully in-lane, which
        # run_exceeds_w guarantees).
        overlong_here = is_end & run_exceeds_w & ~ends_at_lane_bottom
    # Mosaic cannot lower reductions over unsigned ints; sum in int32.
    n_overlong = jnp.sum(overlong_here.astype(jnp.int32)).astype(jnp.uint32)
    over_ref[0, 0] = over_ref[0, 0] + n_overlong

    khi = _fmix32(h1 ^ ln)
    klo = _fmix32(h2 + jnp.uint32(0x9E3779B9) * ln)
    sent = jnp.uint32(constants.SENTINEL_KEY)
    # Clamp real keys off BOTH reserved values — (sent, sent) dead filler,
    # (sent, sent-1) poison — to (sent, sent-2); the same rule as the XLA
    # backend's tokenize (bit-identity contract).
    at_sent = (khi == sent) & (klo >= sent - jnp.uint32(1))
    klo = jnp.where(at_sent, sent - jnp.uint32(2), klo)

    if combiner_slots:
        # Map-side hot-key combiner (ISSUE 11): per lane, emissions whose
        # key is resident in the cache are COUNTED here and suppressed
        # from the stream; empty slots greedily adopt the first-seen
        # distinct keys (on Zipf streams the top-mass keys appear within
        # the first windows with overwhelming probability — PR 8's
        # top_mass proxy is exactly the collapsible mass).  Every update
        # is a static C-slot loop of lane-wise compares + sublane
        # reductions: no scatter, no data-dependent control flow.  Exact
        # by construction — a missed key flows to the sort unchanged, a
        # cached key's count and first in-lane occurrence flush at chunk
        # end — so results are bit-identical on every distribution.
        row = jax.lax.broadcasted_iota(jnp.int32, (tb, LANES), 0)
        lane_c = jax.lax.broadcasted_iota(jnp.int32, (tb, LANES), 1)
        start_raw = lane_c * data_rows + m + 1 - ln.astype(jnp.int32)
        packed_raw = (start_raw.astype(jnp.uint32) << 6) | ln
        ck = ckhi_ref[:]
        cl = cklo_ref[:]
        cc = ccnt_ref[:]
        cp = cpk_ref[:]
        ck_rows = [ck[c:c + 1, :] for c in range(combiner_slots)]
        cl_rows = [cl[c:c + 1, :] for c in range(combiner_slots)]
        cc_rows = [cc[c:c + 1, :] for c in range(combiner_slots)]
        cp_rows = [cp[c:c + 1, :] for c in range(combiner_slots)]
        # Hit pass: resident keys absorb their occurrences.  Sentinel
        # slots can never match — emissions carry clamped keys, so an
        # emitting row's (khi, klo) is never (sent, sent).
        for c in range(combiner_slots):
            m_hit = emit & (khi == ck_rows[c]) & (klo == cl_rows[c])
            n_hit = jnp.sum(m_hit.astype(jnp.int32), axis=0, keepdims=True)
            cc_rows[c] = cc_rows[c] + n_hit.astype(jnp.uint32)
            emit = emit & ~m_hit
        # Fill pass: each empty slot adopts the lane's first remaining
        # live emission (per-lane one-hot select via a masked int32 sum —
        # bit-exact, the sum has at most one nonzero term), records its
        # first occurrence, and absorbs its other occurrences in this
        # block.  Slots only ever fill, so an adopted entry's ``packed``
        # is provably the key's first in-lane occurrence: were the key
        # seen earlier with this slot empty, it would have been adopted
        # then.
        big = jnp.int32(tb + 1)
        for c in range(combiner_slots):
            empty = cc_rows[c] == 0
            cand = jnp.where(emit, row, big)
            idx = jnp.min(cand, axis=0, keepdims=True)
            take = empty & (idx < big)
            pick = emit & (row == idx)

            def sel(v):
                return jnp.sum(jnp.where(pick, v.astype(jnp.int32), 0),
                               axis=0, keepdims=True).astype(jnp.uint32)

            nk_hi, nk_lo, npk = sel(khi), sel(klo), sel(packed_raw)
            m_new = emit & take & (khi == nk_hi) & (klo == nk_lo)
            n_new = jnp.sum(m_new.astype(jnp.int32), axis=0, keepdims=True)
            ck_rows[c] = jnp.where(take, nk_hi, ck_rows[c])
            cl_rows[c] = jnp.where(take, nk_lo, cl_rows[c])
            cp_rows[c] = jnp.where(take, npk, cp_rows[c])
            cc_rows[c] = jnp.where(take, n_new.astype(jnp.uint32),
                                   cc_rows[c])
            emit = emit & ~m_new
        ckhi_ref[:] = jnp.concatenate(ck_rows, axis=0)
        cklo_ref[:] = jnp.concatenate(cl_rows, axis=0)
        ccnt_ref[:] = jnp.concatenate(cc_rows, axis=0)
        cpk_ref[:] = jnp.concatenate(cp_rows, axis=0)

    khi = jnp.where(emit, khi, sent)
    # Poison rows carry the reserved key (sent, sent-1): they sort into
    # their OWN segment immediately before the dead-filler segment, so the
    # rescue extraction can find them with a binary search even when the
    # aggregation sort carries no third key to order the filler behind them
    # (sort_mode='stable2').
    klo = jnp.where(emit, klo,
                    jnp.where(overlong_here, sent - jnp.uint32(1), sent))
    ln_e = jnp.where(emit, ln, jnp.uint32(0))
    ntok_ref[0, 0] = ntok_ref[0, 0] + jnp.sum(emit.astype(jnp.int32)).astype(jnp.uint32)

    # packed = start << 6 | length: the sort payload, built where the data
    # already is.  start = global byte offset of the token's first byte.
    # Overlong ends emit a POISON row instead: position of the run's last
    # byte with zero length bits (impossible for a real token).  Consumers
    # needing global token order (n-grams, sampling) keep poison rows in
    # their position sort, where they break row-adjacency across the
    # suppressed token — so grams spanning it self-invalidate instead of
    # pairing phantom neighbors (this replaces a whole-chunk lax.cond
    # fallback to the XLA scan, which embedded a pathologically-slow-to-
    # compile program in every n-gram step; round-2 review #4).
    lane = jax.lax.broadcasted_iota(jnp.int32, (tb, LANES), 1)
    start = lane * data_rows + m + 1 - ln_e.astype(jnp.int32)
    last_byte = (lane * data_rows + m).astype(jnp.uint32)
    packed = jnp.where(emit, (start.astype(jnp.uint32) << 6) | ln_e,
                       jnp.where(overlong_here, last_byte << 6,
                                 jnp.uint32(0xFFFFFFFF)))

    # Pairwise fold: adjacent rows are never both token ends (a real or
    # overlong end at m needs byte m+1 to be a separator), so each
    # (2r, 2r+1) pair holds at most one emission or poison — select it via
    # a sublane-group reshape.
    def fold(a, take_even):
        g = a.reshape(tb // 2, 2, LANES)
        return jnp.where(take_even, g[:, 0, :], g[:, 1, :])

    # The row-pair reshape runs on an int32 0/1 plane: reshaping an i1
    # plane makes Mosaic emit a compare on a non-vreg operand (LLO_CHECK
    # "lhs->ProducesVreg()" on v5e).
    live = (emit | overlong_here).astype(jnp.int32).reshape(tb // 2, 2, LANES)
    even_has = live[:, 0, :] != 0
    khi_h = fold(khi, even_has)
    klo_h = fold(klo, even_has)
    packed_h = fold(packed, even_has)
    if compact_slots:
        has_h = (live[:, 0, :] | live[:, 1, :]) != 0
        khi_c, klo_c, pck_c, n_spill = _compact_planes(
            khi_h, klo_h, packed_h, has_h, compact_slots)
        if lane_major:
            # Transposed (LANES, S) output blocks laid side by side give a
            # flattened stream in GLOBAL BYTE-POSITION order (lane j owns
            # the contiguous segment [j*L, (j+1)*L); within a lane, windows
            # and slots ascend with position) — the precondition for
            # sort_mode='stable2' recovering first occurrence from sort
            # stability alone.  At S=128 the transposed block is a fully
            # tile-aligned (128, 128) store.
            khi_ref[:] = khi_c.T
            klo_ref[:] = klo_c.T
            packed_ref[:] = pck_c.T
        else:
            khi_ref[:] = khi_c
            klo_ref[:] = klo_c
            packed_ref[:] = pck_c
        spill_ref[0, 0] = spill_ref[0, 0] + n_spill
    else:
        khi_ref[:] = khi_h
        klo_ref[:] = klo_h
        packed_ref[:] = packed_h


def _column_pass(cols_padded: jax.Array, w: int, block_rows: int,
                 data_rows: int, interpret: bool, compact_slots: int = 0,
                 lane_major: bool = False, fused_aux: jax.Array | None = None,
                 combiner_slots: int = 0):
    """Run the kernel over the (rows, 128) column view (one trailing pad block).

    Returns pair-compacted planes of rows//2 output rows — or, with
    ``compact_slots`` = S > 0, slot-compacted planes of rows/block_rows*S
    output rows plus a spill count (live rows beyond any lane's budget) —
    as (key_hi, key_lo, packed), plus the (overlong, token_count, spill)
    scalars (spill is 0 on the pair path).  With ``lane_major`` (compact
    mode only) the planes are (LANES, grid*S) transposed blocks whose
    row-major flattening is global byte-position order.

    With ``fused_aux`` (the :func:`_seam_aux` seam-carry plane) the input
    is instead the RAW ``(LANES, rows)`` lane view — no XLA-side transpose
    — and the kernel runs the fused map path (in-kernel seams, no token
    deferred; see ``_tokenize_kernel``).
    """
    fused = fused_aux is not None
    rows = cols_padded.shape[1] if fused else cols_padded.shape[0]
    grid = rows // block_rows
    kern = functools.partial(_tokenize_kernel, w=w, block_rows=block_rows,
                             data_rows=data_rows, compact_slots=compact_slots,
                             lane_major=lane_major, fused=fused,
                             combiner_slots=combiner_slots)
    out_rows = grid * compact_slots if compact_slots else rows // 2
    block_out = compact_slots if compact_slots else block_rows // 2
    if lane_major:
        out32 = jax.ShapeDtypeStruct((LANES, out_rows), jnp.uint32)
        plane_spec = pl.BlockSpec((LANES, block_out), lambda i: (0, i),
                                  memory_space=pltpu.VMEM)
    else:
        out32 = jax.ShapeDtypeStruct((out_rows, LANES), jnp.uint32)
        plane_spec = pl.BlockSpec((block_out, LANES), lambda i: (i, 0),
                                  memory_space=pltpu.VMEM)
    scalar = jax.ShapeDtypeStruct((1, 1), jnp.uint32)
    n_scalars = 3 if compact_slots else 2
    # Compact mode needs scoped VMEM above Mosaic's 16 MB default stack
    # budget (measured on-chip: the default limit rejects it with a
    # vmem-stack OOM at compile time; 64 MB compiles).  The limit predates
    # the log-shift rewrite — whether the smaller-footprint kernel now fits
    # the default is an open on-chip re-measurement (ADVICE r4); v5e has
    # ~128 MB physical VMEM, so the override is safe headroom either way.
    # The pair path stays well under the default; one shared limit keeps
    # the call site single-owner.
    params = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024) \
        if compact_slots else None
    if fused:
        # The aux plane's height is a geometry knob (ISSUE 12): the spec
        # reads it off the plane itself, so _seam_aux stays the single
        # owner of the plane layout.
        in_specs = [pl.BlockSpec((LANES, block_rows), lambda i: (0, i),
                                 memory_space=pltpu.VMEM),
                    pl.BlockSpec((fused_aux.shape[0], LANES),
                                 lambda i: (0, 0),
                                 memory_space=pltpu.VMEM)]
        args = (cols_padded, fused_aux)
    else:
        in_specs = [pl.BlockSpec((block_rows, LANES), lambda i: (i, 0),
                                 memory_space=pltpu.VMEM)]
        args = (cols_padded,)
    cache_shapes: list = []
    cache_specs: list = []
    if combiner_slots:
        # Cache state lives in revisited VMEM output blocks (index map
        # pinned to (0, 0)): the refs carry the cache across the
        # sequential grid, and their post-kernel value IS the flush.
        cache_shapes = [jax.ShapeDtypeStruct((combiner_slots, LANES),
                                             jnp.uint32)] * 4
        cache_specs = [pl.BlockSpec((combiner_slots, LANES),
                                    lambda i: (0, 0),
                                    memory_space=pltpu.VMEM)] * 4
    outs = pl.pallas_call(
        kern,
        grid=(grid,),
        in_specs=in_specs,
        out_shape=[out32, out32, out32] + [scalar] * n_scalars + cache_shapes,
        out_specs=[plane_spec] * 3
        + [pl.BlockSpec((1, 1), lambda i: (0, 0),
                        memory_space=pltpu.SMEM)] * n_scalars + cache_specs,
        scratch_shapes=[pltpu.VMEM((w + 1, LANES), jnp.int32)],
        compiler_params=params,
        interpret=interpret,
    )(*args)
    khi, klo, packed, over, ntok = outs[:5]
    spill = outs[5][0, 0] if compact_slots else jnp.uint32(0)
    cache = CombinerCache(*outs[3 + n_scalars:3 + n_scalars + 4]) \
        if combiner_slots else None
    return khi, klo, packed, over[0, 0], ntok[0, 0], spill, cache


def _seam_pass(data: jax.Array, seg_len: int, w: int,
               base_offset: jax.Array) -> tuple[TokenStream, jax.Array]:
    """Re-tokenize the 129 lane-seam windows with the XLA scan path.

    Window j covers bytes ``[j*seg_len - w - 1, j*seg_len + w + 1)`` (out of
    range = PAD).  It emits exactly the tokens the kernel deferred: those whose
    span touches a seam byte (``j*seg_len - 1`` or ``j*seg_len``), provided the
    whole token is visible in the window.  A run truncated by the window edge
    is an overlong token; it is counted, not emitted.
    """
    n = data.shape[0]
    wlen = 2 * w + 2
    # Window j covers [j*L - w - 1, j*L + w + 1): the last w+1 bytes of lane
    # segment j-1 plus the first w+1 bytes of segment j.  Build all 129
    # windows from static slices of the (LANES, L) view — a fancy-index
    # gather here costs ~13 us/element on TPU (measured: ~100 ms for these
    # ~8.5K bytes, 4x the entire rest of the pipeline).
    view = data.reshape(LANES, seg_len)
    pad_row = jnp.full((1, w + 1), constants.PAD_BYTE, dtype=jnp.uint8)
    tails = jnp.concatenate([pad_row, view[:, seg_len - (w + 1):]], axis=0)
    heads = jnp.concatenate([view[:, : w + 1], pad_row], axis=0)
    windows = jnp.concatenate([tails, heads], axis=1)  # (LANES+1, 2w+2)
    starts = jnp.arange(0, n + seg_len, seg_len)  # 129 window origins j*seg_len

    streams = jax.vmap(tok_ops.tokenize)(windows)  # fields: (129, wlen)
    wpos_end = jnp.arange(wlen)[None, :].astype(jnp.int32)
    length = streams.length.astype(jnp.int32)
    wstart = wpos_end - length + 1
    is_tok = streams.count > 0

    # Seam bytes sit at window positions w and w+1.
    touches = (wstart <= w) & (wpos_end >= w) | (wstart <= w + 1) & (wpos_end >= w + 1)
    complete = (wstart >= 1) & (wpos_end <= 2 * w)
    # Enforce the same <=W contract as the in-lane kernel so whether a token
    # is counted never depends on where the chunk layout happened to cut it.
    emit = is_tok & touches & complete & (length <= w)

    # Overlong tokens counted here, exactly once each: truncated-at-left
    # fragments whose true end is visible (their lookback crossed the seam, so
    # the kernel deferred them), and complete-but-longer-than-W seam tokens.
    is_overlong = is_tok & touches & ((wstart == 0) & (wpos_end <= 2 * w)
                                      | complete & (length > w))
    overlong = jnp.sum(is_overlong.astype(jnp.uint32))

    sent = jnp.uint32(constants.SENTINEL_KEY)
    global_start = (starts[:, None] - (w + 1) + wstart).astype(jnp.int32)
    # Poison rows mirror the kernel's: the overlong run's LAST byte position,
    # zero length, the reserved poison key (sent, sent-1), count 0.  They
    # ride the `pos` plane (count=0 rows are inert everywhere else) so
    # concat_streams can pack them for position-ordered consumers.
    global_end = (starts[:, None] - (w + 1) + wpos_end).astype(jnp.int32)
    pos = jnp.where(emit, global_start, jnp.where(is_overlong, global_end,
                                                  jnp.int32(-1)))
    stream = TokenStream(
        key_hi=jnp.where(emit, streams.key_hi, sent).reshape(-1),
        key_lo=jnp.where(emit, streams.key_lo,
                         jnp.where(is_overlong, sent - jnp.uint32(1),
                                   sent)).reshape(-1),
        count=jnp.where(emit, jnp.uint32(1), jnp.uint32(0)).reshape(-1),
        pos=jnp.where(pos >= 0, pos.astype(jnp.uint32)
                      + jnp.asarray(base_offset, jnp.uint32),
                      jnp.uint32(constants.POS_INF)).reshape(-1),
        length=jnp.where(emit, streams.length, jnp.uint32(0)).reshape(-1),
    )
    return stream, overlong


def tokenize_split(data: jax.Array, base_offset: jax.Array | int = 0,
                   max_token_bytes: int = DEFAULT_MAX_TOKEN,
                   block_rows: int | None = None,
                   interpret: bool | None = None
                   ) -> tuple[PackedTokenStream, TokenStream, jax.Array]:
    """Pallas-backed tokenize returning ``(col_stream, seam_stream, overlong)``
    — the bulk column-pass emissions and the tiny (~129*(2W+2) entries) seam
    fix-up emissions as *separate* streams.

    Aggregation-aware callers should consume the two streams separately
    (build a table from each and merge): concatenating them forces a full
    copy of every multi-hundred-MB column plane just to append a few KB.
    :func:`tokenize` below does exactly that concatenation for callers that
    want the single-stream view.

    Emits the same (key, count, pos, length) tuples per token as
    :func:`mapreduce_tpu.ops.tokenize.tokenize` for every token of at most
    ``max_token_bytes`` bytes; longer tokens are dropped and tallied in the
    returned ``overlong`` (uint32 scalar) for the caller to fold into
    ``CountTable.dropped_*``.  Stream entries are NOT in byte order (the
    column view interleaves lanes); downstream aggregation sorts by key, so
    order is irrelevant there.

    Requirements: ``len(data) % 128 == 0`` and at least one full block.
    """
    col, seam, overlong, _ = _tokenize_split_impl(
        data, base_offset, max_token_bytes, block_rows, interpret, 0)
    return col, seam, overlong


def tokenize_split_compact(data: jax.Array, compact_slots: int,
                           base_offset: jax.Array | int = 0,
                           max_token_bytes: int = DEFAULT_MAX_TOKEN,
                           block_rows: int | None = None,
                           interpret: bool | None = None,
                           lane_major: bool = False
                           ) -> tuple[PackedTokenStream, TokenStream,
                                      jax.Array, jax.Array]:
    """:func:`tokenize_split` with slot-compacted column planes: returns
    ``(col_stream, seam_stream, overlong, spill)``.

    The column planes hold ``compact_slots`` output rows per ``block_rows``
    byte rows (vs the pair path's ``block_rows/2``) — the downstream sort's
    input shrinks by the same ratio, which is where the chunk budget goes
    (round-4 op profile).  ``spill`` (uint32) counts live rows beyond
    any (block, lane) window's budget: when it is nonzero the compact
    planes are INCOMPLETE and the caller must discard them and re-run the
    full-resolution path (``models/wordcount._map_stream`` wraps exactly
    that in a ``lax.cond``).  Measured window densities (tools/density.py):
    the default 88 slots per 256-byte window never spills on either bench
    corpus (observed max 77, Zipf) — the fallback is for adversarial text
    (e.g. runs of single-letter tokens at density > 0.34), which stays
    exact at ~2x the chunk cost.

    ``lane_major`` writes the column planes transposed so the flattened
    col_stream is in GLOBAL BYTE-POSITION order — the input contract of
    ``sort_mode='stable2'`` aggregation (first occurrence recovered from
    sort stability instead of a third comparator key).  The row SET is
    identical either way; only the order changes.
    """
    if compact_slots <= 0:
        raise ValueError(f"compact_slots must be > 0, got {compact_slots}")
    return _tokenize_split_impl(data, base_offset, max_token_bytes,
                                block_rows, interpret, compact_slots,
                                lane_major)


def _resolve_args(data, max_token_bytes, block_rows, interpret,
                  compact_slots: int):
    """Shared argument validation/resolution for the split and fused entry
    points: returns ``(w, seg_len, block_rows, interpret)``."""
    if interpret is None:
        # The interpreter runs the kernel on the CPU backend (tests,
        # debugging); any other backend compiles it, so an accelerator that
        # Mosaic cannot target fails loudly instead of interpreting.
        interpret = jax.default_backend() == "cpu"
    if data.dtype != jnp.uint8:
        raise TypeError(f"pallas tokenize expects uint8, got {data.dtype}")
    n = data.shape[0]
    if n % LANES:
        raise ValueError(f"input length {n} must be a multiple of {LANES}")
    if n > (1 << 26):
        raise ValueError(
            f"input of {n} bytes exceeds the pallas backend's 2**26 (64 MB) "
            "chunk bound (positions are packed into 26 bits for the sort "
            "payload); lower chunk_bytes or use the xla backend")
    w = max_token_bytes
    if w < 1:
        raise ValueError(f"max_token_bytes must be >= 1, got {w}")
    if w > 63:
        raise ValueError(f"max_token_bytes must be <= 63 (length is packed "
                         f"into 6 bits), got {w}")
    seg_len = n // LANES
    if block_rows is None:
        # Blocks must cover the W-row lookback plus one row, and stay even
        # (pairwise compaction halves the output rows, which are a multiple
        # of block_rows).
        block_rows = max(DEFAULT_BLOCK_ROWS, w + 2 + (w % 2))
    if block_rows < w + 2:
        raise ValueError(f"block_rows {block_rows} must be >= max_token_bytes+2")
    if block_rows % 2:
        raise ValueError(f"block_rows must be even, got {block_rows}")
    if compact_slots and not 8 <= compact_slots <= block_rows // 2:
        raise ValueError(f"compact_slots {compact_slots} must be in "
                         f"[8, block_rows/2={block_rows // 2}]")
    if compact_slots % 8:
        raise ValueError(f"compact_slots must be a multiple of 8 (sublane "
                         f"alignment), got {compact_slots}")
    if seg_len < 2 * w + 2:
        raise ValueError(
            f"input of {n} bytes gives lane segments of {seg_len} < 2W+2="
            f"{2 * w + 2} bytes; seam windows would overlap (grow the chunk "
            f"or shrink max_token_bytes)")
    return w, seg_len, block_rows, interpret


def _packed_stream(khi, klo, packed, total, base_offset) -> PackedTokenStream:
    """Flatten kernel planes into the :class:`PackedTokenStream` view.

    The kernel already compacted and packed (start << 6 | len) in VMEM;
    pos/length/count are elementwise functions of ``packed``, which XLA
    fuses into whatever consumes them (aggregation feeds ``packed``
    straight into its sort, so the reconstructed planes never hit HBM
    there).
    """
    khi = khi.reshape(-1)
    klo = klo.reshape(-1)
    packed = packed.reshape(-1)
    # Zero length bits mark overlong-end POISON rows (position-ordering
    # markers, not tokens): excluded from the token view here, kept in the
    # packed plane for position-ordered consumers.
    has_tok = (packed != jnp.uint32(0xFFFFFFFF)) \
        & ((packed & jnp.uint32(63)) != 0)
    ln = jnp.where(has_tok, packed & jnp.uint32(63), jnp.uint32(0))
    start = jnp.where(has_tok,
                      (packed >> 6) + jnp.asarray(base_offset, jnp.uint32),
                      jnp.uint32(constants.POS_INF))
    base_is_zero = isinstance(base_offset, int) and base_offset == 0
    return PackedTokenStream(
        key_hi=khi, key_lo=klo,
        count=has_tok.astype(jnp.uint32),
        pos=start, length=ln,
        packed=packed if base_is_zero else None,
        total=total)


def _tokenize_split_impl(data, base_offset, max_token_bytes, block_rows,
                         interpret, compact_slots: int,
                         lane_major: bool = False):
    w, seg_len, block_rows, interpret = _resolve_args(
        data, max_token_bytes, block_rows, interpret, compact_slots)

    # Column-major view + pad rows to a whole number of blocks, plus one extra
    # pad block so every data row gets an output (outputs trail by one row).
    cols = data.reshape(LANES, seg_len).T
    pad_rows = (-seg_len) % block_rows + block_rows
    cols_padded = jnp.concatenate(
        [cols, jnp.full((pad_rows, LANES), constants.PAD_BYTE, dtype=jnp.uint8)])

    khi, klo, packed, over_cols, n_tokens, spill, _ = _column_pass(
        cols_padded, w, block_rows, data_rows=seg_len, interpret=interpret,
        compact_slots=compact_slots, lane_major=lane_major)

    col_stream = _packed_stream(khi, klo, packed, n_tokens, base_offset)
    seam_stream, over_seams = _seam_pass(data, seg_len, w, base_offset)
    return col_stream, seam_stream, over_cols + over_seams, spill


def _seam_aux(view: jax.Array, w: int, aux_rows: int = AUX_ROWS) -> jax.Array:
    """Build the fused kernel's ``(aux_rows, LANES)`` seam-carry plane from
    the raw ``(LANES, seg_len)`` lane view: rows ``[0, w+1)`` hold byte
    ``lane*L - (w+1) + c`` (the previous lane's tail; PAD for lane 0) and
    rows ``[AUX_HEAD_ROW, aux_rows)`` the next lane's first byte (PAD for
    lane 127).  ~12 KB of static slices — noise next to the chunk.
    ``aux_rows`` (a geometry knob, ISSUE 12) only sizes the tile-aligned
    plane; the head row stays pinned at ``AUX_HEAD_ROW`` = 64, the W <= 63
    bound, so rows past it are interchangeable replication."""
    seg_len = view.shape[1]
    pad = constants.PAD_BYTE
    tails = jnp.concatenate(
        [jnp.full((1, w + 1), pad, jnp.uint8),
         view[:-1, seg_len - (w + 1):]], axis=0)  # (LANES, w+1)
    heads = jnp.concatenate(
        [view[1:, :1], jnp.full((1, 1), pad, jnp.uint8)], axis=0)
    mid = jnp.full((LANES, AUX_HEAD_ROW - (w + 1)), pad, jnp.uint8)
    rep = jnp.broadcast_to(heads, (LANES, aux_rows - AUX_HEAD_ROW))
    return jnp.concatenate([tails, mid, rep], axis=1).T


def tokenize_fused(data: jax.Array, *, compact_slots: int = 0,
                   base_offset: jax.Array | int = 0,
                   max_token_bytes: int = DEFAULT_MAX_TOKEN,
                   block_rows: int | None = None,
                   interpret: bool | None = None,
                   lane_major: bool = False,
                   combiner_slots: int = 0,
                   aux_rows: int | None = None):
    """Fully fused map path (ISSUE 6): ``(stream, overlong, spill)`` from
    ONE kernel pass over the raw chunk bytes — no XLA transpose/pad of the
    input, no seam fix-up pass, no separate seam stream.

    Emission-set parity with :func:`tokenize_split` is exact: the same
    tokens (<= ``max_token_bytes`` bytes, counted once each), the same
    overlong accounting, and the same poison rows at overlong ends — but
    cross-lane-seam tokens are hashed in-kernel from the seam-carry aux
    plane (:func:`_seam_aux`) instead of being deferred to the XLA scan
    over 129 seam windows, so aggregation consumes a single stream.  With
    ``lane_major`` the flattened stream remains in global byte-position
    order (cross-seam tokens land in their end lane's first window, which
    is exactly their start-position slot), preserving the stable2
    aggregation precondition.

    ``spill`` semantics match :func:`tokenize_split_compact`: nonzero
    means the compact planes are incomplete and the caller MUST fall back
    to an exact path under ``lax.cond`` (the fused fallback is this same
    kernel in pair mode — ``compact_slots=0``).

    ``combiner_slots`` = C > 0 (ISSUE 11; requires ``compact_slots``)
    threads the per-lane hot-key cache through the grid and returns
    ``(stream, overlong, spill, cache)``: cached occurrences are counted
    in VMEM and ABSENT from the stream (``stream.total`` counts only
    emitted rows), and the caller folds the flushed :class:`CombinerCache`
    back in exactly (one table row per resident entry).  The occurrence
    union of stream + cache equals the C=0 stream's exactly, cache misses
    included byte-for-byte — the bit-identity contract of
    ``Config.combiner='hot-cache'``.
    """
    w, seg_len, block_rows, interpret = _resolve_args(
        data, max_token_bytes, block_rows, interpret, compact_slots)
    if aux_rows is None:
        aux_rows = AUX_ROWS
    if aux_rows % 32 or aux_rows <= AUX_HEAD_ROW:
        # The plane is uint8 (tile grid (32, 128)) and the head row is
        # pinned at AUX_HEAD_ROW (the W <= 63 bound): a geometry knob,
        # validated like every other kernel envelope (ISSUE 12).
        raise ValueError(f"aux_rows must be a multiple of 32 and > "
                         f"{AUX_HEAD_ROW}, got {aux_rows}")
    if combiner_slots:
        if not compact_slots:
            raise ValueError("combiner_slots requires the compact path "
                             "(the pair fallback is the combiner-free "
                             "exactness escape)")
        if combiner_slots % 8 or not 8 <= combiner_slots <= 32:
            raise ValueError(f"combiner_slots must be a multiple of 8 in "
                             f"[8, 32], got {combiner_slots}")
        if not (isinstance(base_offset, int) and base_offset == 0):
            # The cache's `packed` plane records raw in-chunk positions
            # (the same rule that nulls PackedTokenStream.packed under a
            # nonzero base): offsetting the stream but not the cache would
            # silently skew cached first occurrences by base_offset.
            raise ValueError("combiner_slots requires base_offset == 0 "
                             "(the cache flush records in-chunk positions; "
                             "callers apply chunk bases via pos_hi, the "
                             "wordcount idiom)")
    view = data.reshape(LANES, seg_len)
    # Pad lane columns to a whole number of blocks plus one extra pad block
    # (outputs trail by one row, exactly like the split column view).
    pad_cols = (-seg_len) % block_rows + block_rows
    view_padded = jnp.pad(view, ((0, 0), (0, pad_cols)),
                          constant_values=constants.PAD_BYTE)
    khi, klo, packed, overlong, n_tokens, spill, cache = _column_pass(
        view_padded, w, block_rows, data_rows=seg_len, interpret=interpret,
        compact_slots=compact_slots, lane_major=lane_major,
        fused_aux=_seam_aux(view, w, aux_rows),
        combiner_slots=combiner_slots)
    stream = _packed_stream(khi, klo, packed, n_tokens, base_offset)
    if combiner_slots:
        return stream, overlong, spill, cache
    return stream, overlong, spill


def concat_streams(col: PackedTokenStream, seam: TokenStream) -> PackedTokenStream:
    """Append the (tiny) seam stream to the column stream, preserving the
    packed plane and exact total, so aggregation runs ONCE over both.

    Building a separate seam table and merging it cost ~26 ms/chunk on the
    bench chip (a second searchsorted while-loop plus six fixed-cost device
    copies of the 8.5K-row seam arrays); one concatenated sort absorbs the
    8.5K extra rows for ~free.
    """
    sent = jnp.uint32(0xFFFFFFFF)
    seam_tok = seam.count > 0
    # count=0 rows with a real pos are the seam pass's POISON rows (overlong
    # ends): packed with zero length bits, like the kernel's own.
    seam_poison = ~seam_tok & (seam.pos != jnp.uint32(constants.POS_INF))
    seam_packed = jnp.where(seam_tok, (seam.pos << 6) | seam.length,
                            jnp.where(seam_poison, seam.pos << 6, sent))
    cat = lambda a, b: jnp.concatenate([a, b])
    return PackedTokenStream(
        key_hi=cat(col.key_hi, seam.key_hi),
        key_lo=cat(col.key_lo, seam.key_lo),
        count=cat(col.count, seam.count),
        pos=cat(col.pos, seam.pos),
        length=cat(col.length, seam.length),
        packed=cat(col.packed, seam_packed) if col.packed is not None else None,
        total=col.total + jnp.sum(seam.count),
    )


def tokenize(data: jax.Array, base_offset: jax.Array | int = 0,
             max_token_bytes: int = DEFAULT_MAX_TOKEN,
             block_rows: int | None = None,
             interpret: bool | None = None) -> tuple[TokenStream, jax.Array]:
    """Single-stream view of :func:`tokenize_split`: ``(stream, overlong)``."""
    col, seam, overlong = tokenize_split(data, base_offset, max_token_bytes,
                                         block_rows, interpret)
    return concat_streams(col, seam), overlong
