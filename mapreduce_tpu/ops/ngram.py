"""N-gram formation over the pallas token stream via position sort.

The XLA n-gram path (:func:`mapreduce_tpu.ops.tokenize.ngrams`) pairs tokens
with carry-forward associative scans over the flat per-byte stream — correct,
but several log-depth passes over chunk-sized planes, and incompatible with
the fused pallas kernel's lane-column layout (its emissions are not in byte
order).  This module makes the pallas backend's stream pairable with ONE
cheap sort:

  * the kernel's ``packed`` plane is ``start_pos << 6 | length`` — position
    in the high bits — so sorting the stream by ``packed`` ALONE (one sort
    key; non-tokens carry all-ones and sink to the tail) simultaneously
    compacts the stream and recovers global token order;
  * gram formation is then a pure elementwise shift-by-one over adjacent
    rows, iterated n-1 times — no scans, no carry machinery;
  * seam-pass tokens are concatenated in BEFORE the sort, so their positions
    interleave exactly where they belong and grams straddling the kernel's
    128-lane seams form correctly (the XLA fallback this replaces could not
    see the kernel's split streams at all);
  * tokens longer than the kernel window W are *suppressed* by the kernel,
    so two tokens adjacent in the sorted stream could straddle a suppressed
    overlong token and pair into a gram that does not exist in the text.
    The kernel (and seam pass) emit a POISON row per overlong end — last
    byte position, zero length bits — which the position sort places
    exactly between the suppressed token's neighbors: the pairing chain
    crosses a non-live row and the phantom gram self-invalidates.  Grams
    containing a >W token are *dropped and accounted* (``dropped_count``
    exact via the closed-form gram total, ``dropped_uniques`` an upper
    bound), mirroring how the wordcount family treats overlong tokens.
    The XLA backend still counts any token length exactly.  An earlier
    design instead fell back to the whole-chunk XLA scan via ``lax.cond``
    — but both cond branches are always compiled, so every n-gram program
    embedded the associative-scan formulation that compiles pathologically
    slowly at production chunk sizes (round-2 review #4); the poison rows
    delete that branch entirely.

Hashing replicates :func:`...ops.tokenize._extend_grams` exactly (same
composition, same fmix32 finalization, same sentinel clamp), so tables built
from either path merge interchangeably.

The reference has no n-gram capability (its map UDF emits single words only,
``mapper`` ``main.cu:37-54``); this family is beyond-parity.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from mapreduce_tpu import constants
from mapreduce_tpu.ops import table as table_ops
from mapreduce_tpu.ops import tokenize as tok_ops
from mapreduce_tpu.ops.tokenize import TokenStream

# NumPy, not jnp: a module-level jnp constant is a concrete device array —
# it initializes a backend at import time AND gets shared as a closure
# constant across otherwise-independent jitted programs, where mismatched
# sharding expectations break the second program's dispatch.
_SENT_PACKED = np.uint32(0xFFFFFFFF)


def position_sorted(stream) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Sort a packed-plane stream into global token order.

    One single-key sort: live rows (``packed != all-ones``) come first,
    ordered by start position (the high bits of ``packed``); sentinels sink
    to the tail.  Returns ``(key_hi, key_lo, packed)`` in that order.
    """
    if stream.packed is None:
        raise ValueError("stream has no packed plane (nonzero base_offset); "
                         "position sort needs the raw kernel packing")
    packed, key_hi, key_lo = jax.lax.sort(
        (stream.packed, stream.key_hi, stream.key_lo), num_keys=1)
    return key_hi, key_lo, packed


def grams_from_sorted(key_hi: jax.Array, key_lo: jax.Array,
                      packed: jax.Array, n: int) -> TokenStream:
    """Form the n-gram stream from position-sorted token rows.

    Adjacent rows are adjacent tokens, so each of the n-1 extension steps is
    an elementwise shift-by-one + hash mix — the same recurrence as
    :func:`...ops.tokenize._extend_grams`, minus its carry scans.
    """
    if n < 1:
        raise ValueError(f"n must be >= 1, got {n}")
    # Zero length bits = a poison row (overlong-token end marker): occupies
    # its position slot so real tokens across it are NOT row-adjacent, but
    # never itself starts or extends a gram.
    live = (packed != _SENT_PACKED) & ((packed & jnp.uint32(63)) != 0)
    start = jnp.where(live, packed >> 6, jnp.uint32(constants.POS_INF))
    end = (packed >> 6) + (packed & jnp.uint32(63))  # exclusive token end

    sentinel = jnp.uint32(constants.SENTINEL_KEY)
    one = jnp.uint32(1)

    def shift(x, fill):
        return jnp.concatenate([jnp.full((1,), fill, x.dtype), x[:-1]])

    g_hi, g_lo, g_pos, g_valid = key_hi, key_lo, start, live
    for _ in range(n - 1):
        p_hi = shift(g_hi, jnp.uint32(0))
        p_lo = shift(g_lo, jnp.uint32(0))
        p_pos = shift(g_pos, jnp.uint32(constants.POS_INF))
        p_valid = shift(g_valid, False)
        g_valid = live & p_valid
        # Order-sensitive pairing, bit-identical to the XLA path.
        g_hi = tok_ops._fmix32(p_hi * jnp.uint32(constants.HASH_BASE_1) ^ key_hi)
        g_lo = tok_ops._fmix32(p_lo * jnp.uint32(constants.HASH_BASE_2) ^ key_lo)
        at_sentinel = (g_hi == sentinel) & (g_lo >= sentinel - one)
        g_lo = jnp.where(at_sentinel, sentinel - jnp.uint32(2), g_lo)
        g_pos = p_pos

    length = jnp.where(g_valid, end - g_pos, jnp.uint32(0))
    return TokenStream(
        key_hi=jnp.where(g_valid, g_hi, sentinel),
        key_lo=jnp.where(g_valid, g_lo, sentinel),
        count=g_valid.astype(jnp.uint32),
        pos=jnp.where(g_valid, g_pos, jnp.uint32(constants.POS_INF)),
        length=length,
    )


def mark_long_spans(stream: TokenStream) -> TokenStream:
    """Length-plane policy for gram tables, identical in every backend:
    spans < 127 bytes are stored exactly; longer spans (and exactly-127
    ones) store ``SEAM_GRAM_LENGTH`` and the host recovers the span by
    scanning ``n`` entries forward from the start (the cross-chunk seam
    entry idiom, :func:`...data.reader.scan_gram_lengths`).  Inter-token
    separator runs are unbounded, so no static bound on a gram span exists
    — the 7-bit cap is what lets :func:`gram_table` ride the packed
    sort-lean aggregation (``pos << 7 | len`` in one uint32) instead of the
    generic 7-array build (ROADMAP r4 #4)."""
    long = (stream.count > 0) & (stream.length >= jnp.uint32(127))
    return stream._replace(length=jnp.where(
        long, jnp.uint32(constants.SEAM_GRAM_LENGTH), stream.length))


def gram_table(gs: TokenStream, capacity: int, pos_hi: jax.Array | int,
               max_pos: int, sort_mode: str = "stable2",
               salt_bits: int = 0) -> table_ops.CountTable:
    """Aggregate a position-ordered gram stream into a count table.

    Both backends' gram streams arrive in ascending start-position order
    (the pallas path pairs position-sorted rows; the XLA path's per-byte
    stream is indexed by byte), which is exactly the stable2 packed-path
    precondition — so when every position fits 25 bits (chunks <= 32 MB,
    the production default) the build is the same 3-array 2-key stable
    sort the wordcount family runs, instead of the generic 7-array 4-key
    build (~2.3x the sorted bytes).  Lengths ride packed as
    ``min(span, 127)``; 127 means "long span" and unpacks to the
    ``SEAM_GRAM_LENGTH`` scan-forward sentinel (:func:`mark_long_spans`
    must already have applied the same policy to ``gs`` so the generic
    fallback's length plane is bit-identical).

    ``max_pos`` is the static bound on gram start positions — the padded
    chunk length (NOT the stream row count: the pallas kernel's compacted
    stream has ~3x fewer rows than chunk bytes, but its positions still
    span the whole chunk).

    ``salt_bits`` (``Config.combiner='salt'``, ISSUE 11): a
    single-hot-gram stream is exactly as skewed as a single hot word, so
    the salt tier rides the shared packed build — spread over salted
    segments, de-salted exactly at the reduce
    (:func:`...ops.table.from_packed_rows`).  The gram family's hot-key
    CACHE tier does not exist: deleting duplicate tokens would break the
    position adjacency grams are formed from, so 'hot-cache' is a
    documented no-op here.
    """
    # pos << 7 needs pos < 2**25; the padded chunk length is a trace-time
    # constant, so the gate is static.
    if max_pos > (1 << 25):
        return table_ops.from_stream(gs, capacity, pos_hi=pos_hi)
    # Sentinel-collision proof (ADVICE r5): a live row packs to
    # _SENT_PACKED only with pos == 2**25-1 AND len7 == 127 simultaneously
    # — but len7 == 127 means the true span is >= 127 bytes
    # (mark_long_spans stores min(span, 127)), so pos + 127 <= span end <=
    # max_pos <= 2**25, i.e. pos <= 2**25 - 127 < 2**25 - 1.
    # Contradiction: the collision is unreachable at ANY admitted max_pos.
    # (Tightening the gate to `>=` instead would silently kick the
    # production 32 MB chunk — padded length exactly 2**25 — onto the
    # 2.3x-costlier generic build.)  The static assert pins the premise.
    assert max_pos <= (1 << 25), max_pos
    live = gs.count > 0
    len7 = jnp.minimum(gs.length, jnp.uint32(127))
    packed = jnp.where(live, (gs.pos << jnp.uint32(7)) | len7, _SENT_PACKED)
    # sort_mode passes through unchanged: stable2's position-order
    # precondition holds here (docstring), and sort3 has none.
    t = table_ops.from_packed_rows(
        gs.key_hi, gs.key_lo, packed, jnp.sum(gs.count), capacity, pos_hi,
        len_bits=7, sort_mode=sort_mode, salt_bits=salt_bits)
    occ = t.occupied()
    return t._replace(length=jnp.where(
        occ & (t.length == jnp.uint32(127)),
        jnp.uint32(constants.SEAM_GRAM_LENGTH), t.length))


def ngram_table(chunk: jax.Array, n: int, capacity: int,
                pos_hi: jax.Array | int, config) -> table_ops.CountTable:
    """Per-chunk n-gram count table on the pallas backend.

    One straight-line program: fused kernel -> position sort (poison rows
    included) -> elementwise pairing -> packed table build
    (:func:`gram_table`).  Grams containing a suppressed >W-byte token
    self-invalidate at the poison rows (module docstring) and are accounted
    exactly: the closed-form chunk gram total is ``max(all_tokens - (n-1),
    0)`` with ``all_tokens`` including overlong ones, so whatever the
    pairing did not form was dropped by suppression.
    """
    t, _ = ngram_map_with_summary(chunk, n, capacity, pos_hi, config)
    return t


def ngram_map_with_summary(chunk: jax.Array, n: int, capacity: int,
                           pos_hi: jax.Array | int, config):
    """(per-chunk table, :class:`ChunkSummary`) — the streamed exact-seam
    map's device side, sharing one kernel run + one position sort between
    in-chunk gram formation and the seam summary."""
    from mapreduce_tpu.ops.pallas import tokenize as pallas_tok

    if config.map_impl == "fused":
        # Fused map (Config.map_impl): one kernel pass emits the whole
        # stream — cross-lane-seam tokens hashed in-kernel — so the
        # position sort consumes it directly, no seam concat.  The gram
        # family keeps full resolution (pair mode): its consumer is the
        # position sort, which any row order feeds equally well, and the
        # pair path is spill-free by construction (exactness without a
        # fallback cond).  Poison rows ride the same stream.
        stream, overlong, _spill = pallas_tok.tokenize_fused(
            chunk, max_token_bytes=config.pallas_max_token,
            block_rows=config.resolved_pair_block_rows,
            aux_rows=config.resolved_aux_rows)
    else:
        col, seam, overlong = pallas_tok.tokenize_split(
            chunk, max_token_bytes=config.pallas_max_token,
            block_rows=config.resolved_pair_block_rows)
        stream = pallas_tok.concat_streams(col, seam)
    key_hi, key_lo, packed = position_sorted(stream)
    gs = mark_long_spans(grams_from_sorted(key_hi, key_lo, packed, n))
    t = gram_table(gs, capacity, pos_hi, max_pos=chunk.shape[0],
                   sort_mode=config.sort_mode,
                   salt_bits=config.resolved_salt_bits)
    # Live sorted rows = real tokens + one poison row per overlong end.
    all_tokens = stream.total + overlong
    nm1 = jnp.uint32(n - 1)
    full_total = jnp.where(all_tokens > nm1, all_tokens - nm1, jnp.uint32(0))
    missing = full_total - jnp.sum(gs.count)  # grams killed by suppression
    # ``missing`` occurrences are exact; distinct missing grams are unknowable
    # on device (overlong tokens leave the kernel unhashed), so uniques get
    # the same upper-bound treatment as the wordcount family's overlong.
    t = t._replace(dropped_uniques=t.dropped_uniques + missing,
                   dropped_count=t.dropped_count + missing)
    summ = summary_from_packed(key_hi, key_lo, packed, all_tokens, pos_hi, n)
    return t, summ


# --- Exact cross-chunk grams: carry summaries + seam windows -----------------
#
# A streamed run splits the corpus into chunks; grams whose tokens straddle a
# chunk seam have no single chunk to form in.  Mirroring grep's exact line
# carry (models/grep.py): each chunk's map emits a tiny summary — its first
# and last up-to-(n-1) position-ordered stream ENTRIES (tokens and poison
# markers alike) — the devices share summaries with one small all_gather per
# step, and the job's combine composes them in global chunk order, forming
# every window that crosses a join exactly once (at the join where its final
# token's chunk lands).  The carry composition is the classic sliding-window
# monoid: `compose_carry` keeps the last n-1 entries of a concatenation, so
# chunks with fewer than n-1 tokens (even zero) chain correctly and windows
# spanning 3+ chunks complete at the right join.

KIND_EMPTY = 0  # unoccupied slot
KIND_TOKEN = 1  # real token entry
KIND_POISON = 2  # suppressed >W token: occupies its slot, poisons windows


class GramCarry(NamedTuple):
    """Up-to-(n-1) consecutive stream entries.  All fields uint32[n-1].

    Used both LEFT-aligned (a chunk's first entries, slot 0 oldest) and
    RIGHT-aligned (the running carry / a chunk's last entries, slot n-2
    newest); empty slots carry kind 0 and zeroed fields.
    """

    key_hi: jax.Array
    key_lo: jax.Array
    chunk_id: jax.Array
    pos: jax.Array
    kind: jax.Array


class ChunkSummary(NamedTuple):
    """One chunk's seam-relevant view: first entries (left-aligned) + last
    entries (right-aligned).  A tiny fixed-shape pytree — the per-step
    all_gather moves ~5*(n-1) words per chunk."""

    first: GramCarry
    last: GramCarry


def empty_carry(n: int) -> GramCarry:
    z = jnp.zeros((n - 1,), jnp.uint32)
    return GramCarry(z, jnp.zeros_like(z), jnp.zeros_like(z),
                     jnp.zeros_like(z), jnp.zeros_like(z))


def chunk_summary(key_hi: jax.Array, key_lo: jax.Array, pos: jax.Array,
                  poison: jax.Array, n_entries: jax.Array, chunk_id: jax.Array,
                  n: int) -> ChunkSummary:
    """Summary of a position-sorted stream (live rows first).

    Inputs are position-ordered arrays (live entries occupying the first
    ``n_entries`` rows — real tokens + poison markers; data-dependent).
    Poison rows are kept: an overlong token at a chunk edge must poison
    cross-chunk windows exactly like in-chunk ones.  The pallas caller
    derives ``pos``/``poison`` from the kernel's packed plane; the XLA
    caller has no poison (any token length hashes exactly).
    """
    m = n - 1
    cap = key_hi.shape[0]
    cid = jnp.broadcast_to(jnp.asarray(chunk_id, jnp.uint32), (m,))
    ne = n_entries.astype(jnp.int32)

    def mk(idx, valid):
        idx_c = jnp.clip(idx, 0, cap - 1)
        kind = jnp.where(valid,
                         jnp.where(poison[idx_c], jnp.uint32(KIND_POISON),
                                   jnp.uint32(KIND_TOKEN)),
                         jnp.uint32(KIND_EMPTY))
        live = kind != KIND_EMPTY
        z = jnp.uint32(0)
        return GramCarry(
            key_hi=jnp.where(live, key_hi[idx_c], z),
            key_lo=jnp.where(live, key_lo[idx_c], z),
            chunk_id=jnp.where(live, cid, z),
            pos=jnp.where(live, pos[idx_c], z),
            kind=kind,
        )

    idx_f = jnp.arange(m, dtype=jnp.int32)
    first = mk(idx_f, idx_f < ne)
    idx_l = ne - m + jnp.arange(m, dtype=jnp.int32)
    last = mk(idx_l, idx_l >= 0)
    return ChunkSummary(first=first, last=last)


def summary_from_packed(key_hi: jax.Array, key_lo: jax.Array,
                        packed: jax.Array, n_entries: jax.Array,
                        chunk_id: jax.Array, n: int) -> ChunkSummary:
    """Pallas-path summary: position-sorted packed plane in, summary out."""
    return chunk_summary(key_hi, key_lo, packed >> 6,
                         (packed & jnp.uint32(63)) == 0,
                         n_entries, chunk_id, n)


def summary_from_stream(stream: TokenStream, chunk_id: jax.Array,
                        n: int) -> ChunkSummary:
    """XLA-path summary: one single-key position sort of the per-byte
    stream (non-tokens carry POS_INF and sink), no poison (the XLA
    tokenizer hashes any token length exactly)."""
    pos_key = jnp.where(stream.count > 0, stream.pos,
                        jnp.uint32(constants.POS_INF))
    pos_s, khi_s, klo_s = jax.lax.sort(
        (pos_key, stream.key_hi, stream.key_lo), num_keys=1)
    n_live = jnp.sum(stream.count)
    return chunk_summary(khi_s, klo_s, pos_s, jnp.zeros_like(pos_s, jnp.bool_),
                         n_live, chunk_id, n)


def compose_carry(carry: GramCarry, last: GramCarry) -> GramCarry:
    """Append a chunk's last-entries to the running carry, keeping the most
    recent n-1 entries (right-aligned).  The sliding-window monoid's fold:
    ``sv`` newer entries shift the old carry left by ``sv``."""
    m = carry.kind.shape[0]
    sv = jnp.sum((last.kind != KIND_EMPTY).astype(jnp.int32))
    k = jnp.arange(m, dtype=jnp.int32)
    take_new = k >= (m - sv)
    idx_old = jnp.clip(k + sv, 0, m - 1)
    pick = lambda old, new: jnp.where(take_new, new, old[idx_old])
    return GramCarry(*(pick(o, s) for o, s in zip(carry, last)))


def seam_gram_rows(prefix: GramCarry, first: GramCarry, n: int):
    """Windows crossing the join between ``prefix`` (right-aligned: all
    entries before this chunk) and this chunk's ``first`` entries.

    Returns ``(key_hi, key_lo, chunk_id, pos, count, dropped)`` — n-1 rows,
    row j-1 the window taking j entries from the left.  A window EXISTS when
    all n slots are occupied (otherwise it completes at a later join, or the
    corpus simply ends); an existing window is counted when every entry is a
    real token, and dropped (suppressed >W token inside) otherwise.
    ``dropped`` is the scalar count of such windows.  Hash composition is
    bit-identical to :func:`grams_from_sorted`.
    """
    m = n - 1
    sentinel = jnp.uint32(constants.SENTINEL_KEY)
    one = jnp.uint32(1)
    rows_hi, rows_lo, rows_cid, rows_pos, rows_cnt = [], [], [], [], []
    dropped = jnp.uint32(0)
    for j in range(1, n):
        ents = [(prefix, m - j + t) for t in range(j)] \
            + [(first, t) for t in range(n - j)]
        src0, i0 = ents[0]
        g_hi = src0.key_hi[i0]
        g_lo = src0.key_lo[i0]
        occupied = src0.kind[i0] != KIND_EMPTY
        all_tok = src0.kind[i0] == KIND_TOKEN
        for src, i in ents[1:]:
            occupied = occupied & (src.kind[i] != KIND_EMPTY)
            all_tok = all_tok & (src.kind[i] == KIND_TOKEN)
            g_hi = tok_ops._fmix32(
                g_hi * jnp.uint32(constants.HASH_BASE_1) ^ src.key_hi[i])
            g_lo = tok_ops._fmix32(
                g_lo * jnp.uint32(constants.HASH_BASE_2) ^ src.key_lo[i])
            at_sent = (g_hi == sentinel) & (g_lo >= sentinel - one)
            g_lo = jnp.where(at_sent, sentinel - jnp.uint32(2), g_lo)
        counted = occupied & all_tok
        dropped = dropped + (occupied & ~all_tok).astype(jnp.uint32)
        rows_hi.append(jnp.where(counted, g_hi, sentinel))
        rows_lo.append(jnp.where(counted, g_lo, sentinel))
        rows_cid.append(jnp.where(counted, prefix.chunk_id[m - j],
                                  jnp.uint32(constants.POS_INF)))
        rows_pos.append(jnp.where(counted, prefix.pos[m - j],
                                  jnp.uint32(constants.POS_INF)))
        rows_cnt.append(counted.astype(jnp.uint32))
    stack = lambda xs: jnp.stack(xs)
    return (stack(rows_hi), stack(rows_lo), stack(rows_cid), stack(rows_pos),
            stack(rows_cnt), dropped)


def seam_gram_table(prefix: GramCarry, first: GramCarry,
                    n: int) -> table_ops.CountTable:
    """The join's cross-window contribution as a tiny mergeable table.

    Entries carry ``SEAM_GRAM_LENGTH`` so host recovery knows to scan the
    span forward (its end lies in a later chunk whose row base the device
    cannot know).  Dropped (poisoned) windows land in ``dropped_*``.
    """
    k_hi, k_lo, cid, pos, cnt, dropped = seam_gram_rows(prefix, first, n)
    length = jnp.where(cnt > 0, jnp.uint32(constants.SEAM_GRAM_LENGTH),
                       jnp.uint32(0))
    z = jnp.uint32(0)
    return table_ops._build(k_hi, k_lo, cid, pos, cnt, jnp.zeros_like(cnt),
                            length, capacity=max(n - 1, 2),
                            carry_du=dropped, carry_du_hi=z,
                            carry_dc=dropped, carry_dc_hi=z)
