"""WordCount: the flagship model of the framework.

End-to-end equivalent of the reference program (tokenize ``main.cu:187-202``
-> map ``main.cu:37-54`` -> reduce ``main.cu:69-108`` -> report
``main.cu:212-218``), rebuilt TPU-first: bytes go to the device as a padded
uint8 tensor, tokenization/hashing/counting happen in one jitted XLA program,
and only the small count table returns to the host, where exact strings are
recovered from first-occurrence positions.

This module is the simple single-buffer path used by the CLI and tests; the
streaming / multi-chip path lives in :mod:`mapreduce_tpu.runtime.executor` and
:mod:`mapreduce_tpu.parallel.mapreduce`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from mapreduce_tpu import constants
from mapreduce_tpu.config import Config, DEFAULT_CONFIG
from mapreduce_tpu.ops import datastats
from mapreduce_tpu.ops import sketch as sketch_ops
from mapreduce_tpu.ops import table as table_ops
from mapreduce_tpu.ops import tokenize as tok_ops


@dataclasses.dataclass(frozen=True)
class WordCountResult:
    """Host-side result with recovered strings, insertion-ordered."""

    words: list[bytes]  # reported words, by first occurrence
    counts: list[int]  # parallel to words
    total: int  # total tokens (includes any spilled/dropped ones; exact)
    distinct: int  # distinct words: exact when dropped_uniques == 0; under
    #   table spill, a KMV estimate read off the full table's largest kept
    #   key (~1/sqrt(capacity) relative error — 0.2% at the default 256K;
    #   see ops.table.kmv_distinct), far tighter than the summed per-chunk
    #   bound it replaces.  Top-k finalized runs keep the upper bound (the
    #   terminal reorder destroys the KMV property).
    dropped_uniques: int  # upper bound on distinct words spilled past table
    #   capacity or dropped as overlong; loose because cross-chunk merges sum
    #   per-chunk bounds and the pallas backend cannot hash (hence cannot
    #   dedupe) tokens longer than its lookback window
    dropped_count: int  # tokens belonging to spilled/dropped words (exact)
    distinct_estimate: float | None = None  # HLL estimate (~0.8% err @ p=14);
    #   populated by sketched runs — unlike ``distinct`` it stays accurate
    #   past table capacity
    cms: np.ndarray | None = dataclasses.field(default=None, compare=False)
    #   Count-Min sketch from a count_sketch run: estimate_count() answers
    #   frequency queries for ANY word, including ones spilled past capacity

    def as_dict(self) -> dict[bytes, int]:
        return dict(zip(self.words, self.counts))

    def estimate_count(self, word: bytes) -> int | None:
        """CMS frequency estimate for ``word`` (None without a sketch).

        Never under-estimates a word the run saw (within the batch-capacity
        envelope); over-estimates by at most ~total/width per row w.h.p.
        """
        if self.cms is None:
            return None
        return sketch_ops.cms_query(self.cms, word)


def apply_top_k(result: WordCountResult, k: int) -> WordCountResult:
    """Restrict a result to its k most frequent words (host-side, stable).

    The single owner of top-k reordering for host results; ``total`` keeps
    counting every token, matching CountTable.total_count() semantics.
    """
    order = sorted(range(len(result.words)), key=lambda i: -result.counts[i])[:k]
    return dataclasses.replace(
        result,
        words=[result.words[i] for i in order],
        counts=[result.counts[i] for i in order],
    )


def _seam_table_cap(w: int) -> int:
    """Seam-table capacity for the stable2 split aggregation: seam
    emissions are bounded by (W+1) tokens per (2W+2)-byte window * 129
    windows (4257 at W=32, 8256 at the W=63 maximum) — sized from W so a
    spill is IMPOSSIBLE at any legal config (a spill here would silently
    diverge from the concat-path oracle, which absorbs all seam rows in
    the big sort)."""
    return 129 * (w + 1)


# Seam-deferred overlong runs per chunk are bounded by ~2 per seam window
# (one left-truncated + one complete >W run fit in 2W+2 bytes) * 129 windows.
_SEAM_RESCUE_SLOTS = 384


def _combiner_table(cache, pos_hi) -> table_ops.CountTable:
    """One chunk's flushed hot-key cache -> an exact tiny CountTable
    (ISSUE 11).  Cache rows carry per-entry counts and the entry's first
    in-lane occurrence; the same hot key resident in several lanes
    coalesces through the generic build's segment reduce (counts sum, the
    smallest position wins), so the merge with the thinned stream's table
    reproduces the uncombined build bit-for-bit.  Capacity = the plane
    size: distinct cached keys can never exceed the slot count, so this
    build is spill-free by construction."""
    khi = cache.key_hi.reshape(-1)
    klo = cache.key_lo.reshape(-1)
    cnt = cache.count.reshape(-1)
    packed = cache.packed.reshape(-1)
    live = cnt > 0
    sent = jnp.uint32(constants.SENTINEL_KEY)
    inf = jnp.uint32(constants.POS_INF)
    stream = tok_ops.TokenStream(
        key_hi=jnp.where(live, khi, sent),
        key_lo=jnp.where(live, klo, sent),
        count=jnp.where(live, cnt, jnp.uint32(0)),
        pos=jnp.where(live, packed >> 6, inf),
        length=jnp.where(live, packed & jnp.uint32(63), jnp.uint32(0)))
    return table_ops.from_stream(stream, khi.shape[0], pos_hi=pos_hi)


class SeamedUpdate(NamedTuple):
    """A per-chunk map result whose seam table has NOT been folded yet.

    The streamed stable2 path defers the seam fold to the per-step running
    merge (a three-way :func:`...ops.table.merge` — runs of <= 3 rows fold
    in the same two sorts), saving the two dedicated (capacity + seam)-row
    sorts a pairwise seam merge costs per chunk.  ``batch`` carries the
    chunk's dropped_* accounting; ``seam`` is spill-free by construction
    (:func:`_seam_table_cap` covers the 129*(W+1) emission bound)."""

    batch: table_ops.CountTable
    seam: table_ops.CountTable


def _map_stream(chunk: jax.Array, config: Config, capacity: int,
                pos_hi: jax.Array | int = 0, split_seam: bool = False,
                with_stats: bool = False):
    """Tokenize one buffer with the configured backend and build its table.

    With ``split_seam`` (streamed stable2 only) the result is a
    :class:`SeamedUpdate` whose seam table the caller folds at its next
    merge; otherwise a single fully-folded :class:`CountTable`.

    With ``with_stats`` (ISSUE 8: the telemetered streamed path) the
    result is ``(update, ops.datastats.DataStats)`` — the chunk's
    data-plane counters (overlong/rescued/dropped, spill-fallback and
    rescue-escalation cond branches taken, spill rows) surfaced as tiny
    uint32 scalars the executor fetches at group retirement.  The update
    itself is BIT-IDENTICAL to the plain path: the counters read
    predicates the map already computes (``overlong``, ``spill``, the
    rescue pass's own clamped count) and the built table's ``dropped_*``
    scalars; with ``with_stats=False`` (the default, and every
    non-telemetered caller) the traced program is unchanged.
    """
    if split_seam and (config.sort_mode != "stable2"
                       or config.resolved_backend() != "pallas"
                       or not config.resolved_compact_slots):
        raise ValueError("split_seam requires the pallas stable2 compact "
                         "path (the only producer of a separate seam table)")
    # ``ret`` pairs every aggregation return with its per-chunk rescued
    # count when stats are on (threaded through the same lax.cond branches
    # the tables take, so both modes keep one control structure); the
    # plain mode returns tables alone, bit-for-bit as before.
    if with_stats:
        ret = lambda t, rescued: (t, rescued)
    else:
        ret = lambda t, rescued: t
    zero_u32 = jnp.zeros((), jnp.uint32)

    def assemble(res, overlong, spill, cache=None, spill_gate=None):
        """Pair the final update with its chunk DataStats (stats mode).

        ``cache``/``spill_gate`` (ISSUE 11): the fused combiner's flushed
        hot-key planes and the spill scalar that decided whether they
        were USED — on a spilled chunk the pair fallback ran combiner-
        free, so the counters gate to zero with it (the cache planes
        exist outside the cond; reading them here adds no branch)."""
        if not with_stats:
            return res
        update, rescued = res
        tbl = update.batch if isinstance(update, SeamedUpdate) else update
        rescue_on = bool(config.rescue_slots)
        tiered = config.rescue_slots_max > config.rescue_slots > 0
        c_hits = c_flushes = c_evicted = 0
        if cache is not None:
            used = (spill_gate == 0).astype(jnp.uint32)
            c_hits = used * jnp.sum(cache.count)
            c_flushes = used * jnp.sum((cache.count > 0).astype(jnp.uint32))
            c_evicted = used * jnp.sum((cache.count == 1).astype(jnp.uint32))
        stats = datastats.map_stats(
            overlong=overlong, rescued=rescued,
            spill=spill if spill is not None else 0,
            fallback=(spill != 0) if spill is not None else 0,
            invoked=(overlong > 0) if rescue_on else 0,
            escalated=(overlong > jnp.uint32(config.rescue_slots))
            if tiered else 0,
            dropped_tokens=tbl.dropped_count,
            dropped_uniques=tbl.dropped_uniques,
            combiner_hits=c_hits, combiner_flushes=c_flushes,
            combiner_evicted=c_evicted)
        return update, stats

    if config.resolved_backend() == "pallas":
        from mapreduce_tpu.ops import rescue as rescue_ops
        from mapreduce_tpu.ops.pallas import tokenize as pallas_tok

        def accounted(t, n_over):
            # ``n_over`` counts occurrences.  For dropped_count
            # (occurrences) that is exact; for dropped_uniques it is the
            # only available upper bound — unrescued overlong tokens
            # leave the device unhashed, so their distinct words cannot
            # be deduplicated.
            return t._replace(dropped_uniques=t.dropped_uniques + n_over,
                              dropped_count=t.dropped_count + n_over)

        def rescued_table(t, rescue_packed, overlong):
            """cond(overlong > 0): exact re-hash of the poison positions
            (ops/rescue.py) — rescued tokens join the batch table with
            true keys/lengths/first occurrences; only the residual stays
            in dropped accounting.  TIERED (round-4 review weak #4): the
            common case re-hashes the first ``rescue_slots`` positions;
            when the chunk's overlong count exceeds that, a second cond
            escalates to the full ``rescue_slots_max`` extraction (URL-
            dense text: ~15K/chunk on the webby proxy) instead of
            silently leaving the residual dropped.  Overlong-free chunks
            (both bench corpora, all of test.txt) skip everything."""

            def pass_with(packed_r):
                rt, rescued = rescue_ops.rescue_table(
                    chunk, packed_r, config.pallas_max_token,
                    config.rescue_window, pos_hi)
                # rescued <= overlong holds by construction (one poison per
                # overlong run); the clamp bounds any future kernel drift
                # that double-emits a poison to an accounting error instead
                # of a silent uint32 wrap of dropped_count to ~2**32.
                ok = jnp.minimum(rescued, overlong)
                return ret(accounted(table_ops.merge(t, rt,
                                                     capacity=capacity),
                                     overlong - ok), ok)

            def with_rescue(_):
                r1 = config.rescue_slots
                if rescue_packed.shape[0] > r1:
                    return jax.lax.cond(
                        overlong > jnp.uint32(r1),
                        lambda _: pass_with(rescue_packed),
                        lambda _: pass_with(rescue_packed[:r1]), None)
                return pass_with(rescue_packed)

            return jax.lax.cond(overlong > 0, with_rescue,
                                lambda _: ret(accounted(t, overlong),
                                              zero_u32), None)

        # The spill-fallback / non-compact aggregation must not use stable2:
        # pair-layout streams are NOT position-ordered (rows interleave
        # lanes), so first-occurrence recovery needs the third sort key.
        concat_sort_mode = "sort3" if config.sort_mode == "stable2" \
            else config.sort_mode

        def aggregate_stream(stream, overlong, mode, cache=None):
            """ONE packed build over a single complete stream — the shared
            tail of the split concat path and the fused map path (whose
            kernel already holds every emission, cross-lane-seam tokens
            hashed in-kernel from the seam-carry plane): no seam table, no
            seam merge, and overlong poison rows ride the big sort's
            poison segment (contrast aggregate_stable2's seam-poison
            extraction dance).  With ``cache`` (the fused combiner's
            flushed hot-key planes, ISSUE 11) the occurrences the kernel
            absorbed fold back in as one tiny exact table merge — counts
            add, the merge keeps each key's smallest position, and the
            merged result equals the uncombined build's bit-for-bit
            (under batch-capacity spill both paths keep the same smallest
            ``capacity`` keys: the build and the merge share one
            largest-keys-drop rule; only the dropped_uniques upper bound
            can differ, as cross-table merges always could)."""
            built = table_ops.from_stream(
                stream, capacity, pos_hi=pos_hi,
                max_token_bytes=config.pallas_max_token,
                max_pos=int(chunk.shape[0]), sort_mode=mode,
                rescue_slots=config.rescue_slots_max,
                sort_impl=config.sort_impl,
                salt_bits=config.resolved_salt_bits,
                radix_geometry=config.resolved_radix_geometry)
            if not config.rescue_slots:
                res = ret(accounted(built, overlong), zero_u32)
            else:
                t, rescue_packed = built
                res = rescued_table(t, rescue_packed, overlong)
            if cache is None:
                return res
            t, resc = res if with_stats else (res, zero_u32)
            t = table_ops.merge(t, _combiner_table(cache, pos_hi),
                                capacity=capacity)
            return ret(t, resc)

        def aggregate(col, seam, overlong):
            # One aggregation over column + seam emissions together: the
            # seam rows are ~8.5K entries, absorbed by the big sort for
            # free, where a separate seam table + merge cost a second
            # (fixed-overhead-bound) reduce pass per chunk.
            return aggregate_stream(pallas_tok.concat_streams(col, seam),
                                    overlong, concat_sort_mode)

        def aggregate_stable2(col, seam, overlong):
            """Split aggregation for the lane-major layout: the column
            stream keeps its position order into a STABLE 2-key sort
            (first occurrence from tie order — the third comparator key,
            ~40% of the sort's compute, is gone), while the tiny seam
            stream builds its own table and folds in with one pairwise
            merge of (capacity + 8K) rows.  Kept keys/counts/positions
            and dropped_count are bit-identical to the concat path: the
            merge keeps each key's smallest (pos_hi, pos_lo), and the
            kept set of a capacity-merge of capacity-builds equals the
            kept set of one joint build (dropped keys are all larger than
            every kept one).  Only the dropped_uniques UPPER BOUND can
            differ under batch-capacity spill, as cross-table merges
            always could."""
            built = table_ops.from_stream(
                col, capacity, pos_hi=pos_hi,
                max_token_bytes=config.pallas_max_token,
                max_pos=int(chunk.shape[0]), sort_mode="stable2",
                rescue_slots=config.rescue_slots_max,
                sort_impl=config.sort_impl,
                salt_bits=config.resolved_salt_bits,
                radix_geometry=config.resolved_radix_geometry)
            seam_tbl = table_ops.from_stream(
                seam,
                min(capacity,
                    _seam_table_cap(config.pallas_max_token)),
                pos_hi=pos_hi)
            resc = zero_u32
            if not config.rescue_slots:
                t = accounted(built, overlong)
            else:
                t, col_rescue = built
                # Seam-deferred overlong runs are not in the column planes,
                # so their poisons cannot ride the big sort's poison
                # segment: extract them from the (tiny) seam stream
                # directly — count=0 rows with a real position are exactly
                # the seam poisons — and append their windows to the
                # rescue pass.  The combined array is re-sorted so the
                # tiered rescue's first-R1 slice keeps the globally
                # smallest positions (deterministic drop order), not a
                # per-source split.
                ones = jnp.uint32(0xFFFFFFFF)
                is_sp = (seam.count == 0) \
                    & (seam.pos != jnp.uint32(constants.POS_INF))
                sp = jnp.where(is_sp, seam.pos << 6, ones)
                sp = jax.lax.sort(sp)[:_SEAM_RESCUE_SLOTS]
                # Re-sort and slice back to the resolved budget: the tiered
                # rescue's slices then keep the globally smallest positions
                # (the same deterministic drop order as the concat path,
                # where seam poisons ride the big sort inside one budget).
                combined = jax.lax.sort(
                    jnp.concatenate([col_rescue, sp]))[:col_rescue.shape[0]]
                res = rescued_table(t, combined, overlong)
                t, resc = res if with_stats else (res, zero_u32)
            if split_seam:
                return ret(SeamedUpdate(batch=t, seam=seam_tbl), resc)
            return ret(table_ops.merge(t, seam_tbl, capacity=capacity), resc)

        def seamed(t):
            """Match the split-seam pytree for paths with no seam table to
            defer: an empty seam table rides along, inert in the caller's
            three-way merge."""
            if split_seam:
                return SeamedUpdate(
                    batch=t,
                    seam=table_ops.empty(min(
                        capacity,
                        _seam_table_cap(config.pallas_max_token))))
            return t

        def seamed_ret(res):
            """``seamed`` lifted over the (table, rescued) pairing."""
            if with_stats:
                t, resc = res
                return seamed(t), resc
            return seamed(res)

        def full_tok(_):
            """Full-resolution split path, also reporting its overlong
            scalar (the non-compact entry's stats need it; the cond
            branch below drops it)."""
            col, seam, overlong = pallas_tok.tokenize_split(
                chunk, max_token_bytes=config.pallas_max_token,
                block_rows=config.resolved_pair_block_rows)
            return seamed_ret(aggregate(col, seam, overlong)), overlong

        def full_path(_):
            return full_tok(_)[0]

        if config.map_impl == "fused":
            def fused_full_tok(_):
                # Spill fallback = the SAME fused kernel in pair mode
                # (full resolution, exact).  Pair-layout streams interleave
                # lanes, so first occurrence needs the third sort key.
                stream, overlong, _sp = pallas_tok.tokenize_fused(
                    chunk, max_token_bytes=config.pallas_max_token,
                    block_rows=config.resolved_pair_block_rows,
                    aux_rows=config.resolved_aux_rows)
                return seamed_ret(aggregate_stream(stream, overlong,
                                                   concat_sort_mode)), \
                    overlong

            def fused_full(_):
                return fused_full_tok(_)[0]

            if not config.resolved_compact_slots:
                res, overlong = fused_full_tok(None)
                return assemble(res, overlong, None)
            lane_major = config.sort_mode == "stable2"
            combiner_slots = config.resolved_combiner_slots
            if combiner_slots:
                # Hot-key combiner (ISSUE 11): the kernel counts cached
                # occurrences in VMEM and thins the stream; the flushed
                # cache folds back in inside the compact branch.  The
                # spill fallback stays the combiner-FREE pair path — on a
                # spilled chunk the aborted compact pass's cache is
                # discarded wholesale, so exactness never depends on it.
                stream, overlong, spill, cache = pallas_tok.tokenize_fused(
                    chunk, compact_slots=config.resolved_compact_slots,
                    max_token_bytes=config.pallas_max_token,
                    block_rows=config.resolved_block_rows,
                    lane_major=lane_major, combiner_slots=combiner_slots,
                    aux_rows=config.resolved_aux_rows)
            else:
                stream, overlong, spill = pallas_tok.tokenize_fused(
                    chunk, compact_slots=config.resolved_compact_slots,
                    max_token_bytes=config.pallas_max_token,
                    block_rows=config.resolved_block_rows,
                    lane_major=lane_major,
                    aux_rows=config.resolved_aux_rows)
                cache = None
            # Lane-major fused streams stay in global byte-position order
            # (cross-seam tokens land in their start-position slot), so the
            # stable2 tie-order contract holds over the single stream.
            mode = "stable2" if lane_major else concat_sort_mode
            return assemble(jax.lax.cond(
                spill == 0,
                lambda _: seamed_ret(aggregate_stream(stream, overlong,
                                                      mode, cache=cache)),
                fused_full, None), overlong, spill,
                cache=cache, spill_gate=spill)

        if not config.resolved_compact_slots:
            res, overlong = full_tok(None)
            return assemble(res, overlong, None)
        # Slot-compacted planes (config.compact_slots, default-on at 88:
        # +25% end-to-end on the chip, round-4 chip notes): the sort
        # input shrinks ~1.45x.  A nonzero spill means some (block, lane)
        # window exceeded its slot budget and the compact planes are
        # incomplete — the cond then re-runs the chunk at full resolution,
        # so ANY input stays exact (the compact branch is bit-identical
        # when it runs; tools/density.py: the default budget never spills
        # on the bench corpora).
        lane_major = config.sort_mode == "stable2"
        col, seam, overlong, spill = pallas_tok.tokenize_split_compact(
            chunk, config.resolved_compact_slots,
            max_token_bytes=config.pallas_max_token,
            block_rows=config.resolved_block_rows, lane_major=lane_major)
        return assemble(jax.lax.cond(
            spill == 0,
            (lambda _: aggregate_stable2(col, seam, overlong)) if lane_major
            else (lambda _: aggregate(col, seam, overlong)),
            full_path,
            None), overlong, spill)
    stream = tok_ops.tokenize(chunk)
    built = table_ops.from_stream(stream, capacity, pos_hi=pos_hi)
    if not with_stats:
        return built
    # XLA backend: no kernel window, no spill/rescue machinery — the only
    # data-plane signals are the table's own dropped accounting (capacity
    # spill) and the state gauges ``state_stats`` fills.
    return built, datastats.map_stats(dropped_tokens=built.dropped_count,
                                      dropped_uniques=built.dropped_uniques)


@functools.partial(jax.jit, static_argnames=("capacity", "config"))
def _count_step(data: jax.Array, capacity: int, config: Config) -> table_ops.CountTable:
    return _map_stream(data, config, capacity)


def _pad_for_backend(data: bytes | np.ndarray, config: Config) -> np.ndarray:
    """Pad a buffer to the backend's minimum static size (the pallas kernel
    needs whole lane segments of >= 2W+2 bytes; XLA just needs a multiple of
    128).  Single owner of the rule for every single-buffer entry point."""
    buf = np.frombuffer(data, dtype=np.uint8) if isinstance(data, (bytes, bytearray)) else data
    min_len = config.pallas_min_chunk if config.resolved_backend() == "pallas" else 128
    return tok_ops.pad_to(buf, max(min_len, -(-buf.shape[0] // 128) * 128))


def count_table(data: bytes | np.ndarray, config: Config = DEFAULT_CONFIG) -> table_ops.CountTable:
    """Run the device pipeline over one in-memory buffer, return the table."""
    padded = _pad_for_backend(data, config)
    return _count_step(jax.device_put(padded), config.table_capacity, config)


def _reported_distinct(tbl: table_ops.CountTable, n_words: int,
                       dropped_uniques: int, estimate: bool) -> int:
    """``distinct`` for a recovered result: exact when nothing spilled;
    the table's free KMV estimate when it did (see WordCountResult)."""
    if estimate and dropped_uniques > 0:
        est = table_ops.kmv_distinct(tbl)
        if est is not None:
            return max(n_words, int(round(est)))
    return n_words + dropped_uniques


def recover_result(tbl: table_ops.CountTable, source: bytes,
                   estimate_distinct: bool = True,
                   ngram: int = 1) -> WordCountResult:
    """Host-side string recovery from a single-buffer table (pos_hi == 0).

    ``ngram`` is the gram order of the table: entries whose length is the
    ``SEAM_GRAM_LENGTH`` sentinel are >= 127-byte spans (the packed gram
    build stores lengths in 7 bits) and are recovered by scanning ``ngram``
    entries forward from the start, the cross-chunk seam idiom.
    """
    count = np.asarray(tbl.count).astype(np.int64)
    count_hi = np.asarray(tbl.count_hi).astype(np.int64)
    valid = (count > 0) | (count_hi > 0)
    pos = np.asarray(tbl.pos_lo)[valid]
    length = np.asarray(tbl.length)[valid].astype(np.int64)
    cnt = (count + (count_hi << np.int64(32)))[valid]
    seam = np.flatnonzero(length == int(constants.SEAM_GRAM_LENGTH))
    if len(seam):
        from mapreduce_tpu.data import reader as reader_mod

        length[seam] = reader_mod.scan_gram_lengths_bytes(
            source, pos[seam].astype(np.int64), ngram)
    order = np.argsort(pos, kind="stable")
    words = [bytes(source[int(p): int(p) + int(l)]) for p, l in zip(pos[order], length[order])]
    dropped_uniques, dropped_count = tbl.dropped_totals()
    return WordCountResult(
        words=words,
        counts=[int(c) for c in cnt[order]],
        total=int(np.asarray(tbl.total_count())),
        distinct=_reported_distinct(tbl, len(words), dropped_uniques,
                                    estimate_distinct),
        dropped_uniques=dropped_uniques,
        dropped_count=dropped_count,
    )


def count_words(data: bytes, config: Config = DEFAULT_CONFIG) -> WordCountResult:
    """The one-call API: exact word counts for an in-memory buffer."""
    return recover_result(count_table(data, config), data)


@functools.partial(jax.jit, static_argnames=("capacity", "n", "config"))
def _ngram_step(data: jax.Array, capacity: int, n: int,
                config: Config) -> table_ops.CountTable:
    from mapreduce_tpu.ops import ngram as ngram_ops

    if config.resolved_backend() == "pallas":
        return ngram_ops.ngram_table(data, n, capacity, 0, config)
    gs = ngram_ops.mark_long_spans(tok_ops.ngrams(tok_ops.tokenize(data), n))
    return ngram_ops.gram_table(gs, capacity, 0, max_pos=data.shape[0],
                                sort_mode=config.sort_mode,
                                sort_impl=config.sort_impl,
                                salt_bits=config.resolved_salt_bits,
                                radix_geometry=config.resolved_radix_geometry)


def count_ngrams(data: bytes, n: int, config: Config = DEFAULT_CONFIG) -> WordCountResult:
    """Exact n-gram counts for an in-memory buffer (see :class:`NGramCountJob`).

    Reported "words" are the exact source spans of the grams (separators
    between tokens included); ``total`` is the number of grams,
    ``max(tokens - n + 1, 0)``.
    """
    padded = _pad_for_backend(data, config)
    tbl = _ngram_step(jax.device_put(padded), config.table_capacity, n, config)
    return recover_result(tbl, data, ngram=n)


class BufferedTableState(NamedTuple):
    """Running table + a pending buffer of up to K staged batch tables
    (``Config.merge_every = K > 1``).  ``cursor`` counts batches staged
    since the last flush; flushed pending slots carry sentinel keys / zero
    counts, inert to the K-way reduce."""

    table: table_ops.CountTable
    pend_key_hi: jax.Array  # uint32[K * batch_capacity]
    pend_key_lo: jax.Array
    pend_count: jax.Array
    pend_pos_hi: jax.Array
    pend_pos_lo: jax.Array
    pend_length: jax.Array
    cursor: jax.Array  # uint32 scalar


class WordCountJob:
    """WordCount as a :class:`mapreduce_tpu.parallel.mapreduce.MapReduceJob`.

    The flagship job: per-device accumulation into a CountTable, associative
    table merge as the global reduction.  ``chunk_id`` (step * n_devices +
    device) becomes ``pos_hi`` so first-occurrence order is global file order
    and the executor can recover exact strings from (chunk_id, pos_lo, len).

    ``config.merge_every = K > 1`` amortizes the per-step pairwise merge:
    batch tables stage into a pending buffer and ONE K-way sort+reduce
    (:func:`...ops.table.merge_batched`) replaces K merges.
    """

    # graphcheck metadata: ``pend_count`` (merge_every > 1 staging buffer)
    # holds per-chunk BATCH counts, bounded by chunk bytes / 2 << 2**32 —
    # the name-based overflow lint would misread it as a corpus-scale
    # running counter.  The running table's own counts are lane-paired.
    analysis_overflow_exempt = frozenset({"pend_count"})

    def __init__(self, config: Config = DEFAULT_CONFIG):
        self.config = config
        self.capacity = config.table_capacity
        self.batch_capacity = config.batch_uniques
        self.merge_every = config.merge_every

    @staticmethod
    def _with_empty_pending(table: table_ops.CountTable,
                            n: int) -> BufferedTableState:
        """Single owner of the empty pending-buffer layout (init + flush)."""
        sent = jnp.full((n,), jnp.uint32(constants.SENTINEL_KEY))
        inf = jnp.full((n,), jnp.uint32(constants.POS_INF))
        zero = jnp.zeros((n,), jnp.uint32)
        return BufferedTableState(table, sent, jnp.array(sent), zero,
                                  inf, jnp.array(inf), jnp.array(zero),
                                  jnp.zeros((), jnp.uint32))

    def init_state(self):
        if self.merge_every == 1:
            return table_ops.empty(self.capacity)
        return self._with_empty_pending(table_ops.empty(self.capacity),
                                        self.merge_every * self.batch_capacity)

    def _split_seam(self) -> bool:
        """Streamed stable2 defers the per-chunk seam fold to the per-step
        THREE-WAY running merge (merge_every == 1 only: the pending-buffer
        staging path folds whole batch tables and has no third slot)."""
        return (self.merge_every == 1
                and self.config.sort_mode == "stable2"
                and self.config.resolved_backend() == "pallas"
                and bool(self.config.resolved_compact_slots))

    def map_chunk(self, chunk: jax.Array, chunk_id: jax.Array):
        return _map_stream(chunk, self.config, self.batch_capacity,
                           pos_hi=chunk_id, split_seam=self._split_seam())

    # -- data-plane telemetry (ISSUE 8) ---------------------------------

    def map_chunk_stats_sharded(self, chunk, chunk_id, axis, device_index):
        """Stats-mode map: the same update plus the chunk's
        :class:`...ops.datastats.DataStats` counters.  The engine calls
        this instead of :meth:`map_chunk` only when data telemetry is on
        (``Engine(data_stats=True)``); results are bit-identical."""
        del axis, device_index  # the plain wordcount map is axis-free
        return _map_stream(chunk, self.config, self.batch_capacity,
                           pos_hi=chunk_id, split_seam=self._split_seam(),
                           with_stats=True)

    def _stats_table(self, state) -> table_ops.CountTable:
        """The running table the data-stats gauges read.  Deliberately
        NOT :meth:`_plain_table`: flushing a merge_every>1 pending buffer
        just to observe occupancy would add a K-way reduce per dispatch —
        the unflushed running table is at most K batches stale, which is
        telemetry-grade accurate at zero cost."""
        if isinstance(state, BufferedTableState):
            return state.table
        return state

    def state_stats(self, state, stats):
        """Fill the running-state gauges (occupancy, totals, top-bucket
        mass, cumulative dropped) after the group's last combine."""
        return datastats.with_table_gauges(stats, self._stats_table(state))

    def _flushed(self, st: BufferedTableState) -> BufferedTableState:
        """Fold all staged batches into the table (no-op when none staged)."""
        table = table_ops.merge_batched(
            st.table, st.pend_key_hi, st.pend_key_lo, st.pend_count,
            st.pend_pos_hi, st.pend_pos_lo, st.pend_length, self.capacity)
        return self._with_empty_pending(table, st.pend_key_hi.shape[0])

    def combine(self, state, update):
        if self.merge_every == 1:
            if isinstance(update, SeamedUpdate):
                # Three-way fold: batch + seam ride the running merge's two
                # sorts together (runs of <= 3 rows; see table_ops.merge).
                return table_ops.merge(state, update.batch,
                                       capacity=self.capacity,
                                       c=update.seam)
            return table_ops.merge(state, update, capacity=self.capacity)
        b = self.batch_capacity
        off = ((state.cursor % jnp.uint32(self.merge_every))
               * jnp.uint32(b)).astype(jnp.int32)
        put = lambda dst, src: jax.lax.dynamic_update_slice(dst, src, (off,))
        st = BufferedTableState(
            state.table,
            put(state.pend_key_hi, update.key_hi),
            put(state.pend_key_lo, update.key_lo),
            put(state.pend_count, update.count),
            put(state.pend_pos_hi, update.pos_hi),
            put(state.pend_pos_lo, update.pos_lo),
            put(state.pend_length, update.length),
            state.cursor + jnp.uint32(1))
        # Spilled batch accounting must not wait for the flush: the batch
        # table's own dropped_* scalars fold into the running table NOW
        # (merge_batched only carries the table's scalars).  Carry adds:
        # the running scalars are 64-bit lane pairs.
        du_lo, du_hi = table_ops.add64(
            st.table.dropped_uniques, st.table.dropped_uniques_hi,
            update.dropped_uniques, update.dropped_uniques_hi)
        dc_lo, dc_hi = table_ops.add64(
            st.table.dropped_count, st.table.dropped_count_hi,
            update.dropped_count, update.dropped_count_hi)
        st = st._replace(table=st.table._replace(
            dropped_uniques=du_lo, dropped_uniques_hi=du_hi,
            dropped_count=dc_lo, dropped_count_hi=dc_hi))
        return jax.lax.cond(st.cursor >= jnp.uint32(self.merge_every),
                            self._flushed, lambda s: s, st)

    def merge(self, a, b):
        if self.merge_every == 1:
            return table_ops.merge(a, b, capacity=self.capacity)
        fa, fb = self._flushed(a), self._flushed(b)
        return fa._replace(table=table_ops.merge(fa.table, fb.table,
                                                 capacity=self.capacity))

    def _plain_table(self, state) -> table_ops.CountTable:
        """The fully-folded CountTable behind any state shape."""
        if isinstance(state, BufferedTableState):
            return self._flushed(state).table
        return state

    def keyrange_merge(self, state, axis) -> table_ops.CountTable:
        """Collective global reduce via key-range all_to_all (the
        ``merge_strategy='keyrange'`` Engine hook): fold any pending
        batches locally, then one reduce-scatter + all_gather round
        (:func:`...parallel.collectives.key_range_merge`).  Returns the
        plain replicated CountTable; ``finalize`` accepts both shapes."""
        from mapreduce_tpu.parallel import collectives

        return collectives.key_range_merge(self._plain_table(state), axis)

    def keyrange_result_merge(self, a, b) -> table_ops.CountTable:
        """Merge two keyrange RESULTS (plain replicated CountTables) —
        the fold the hier-kr-tree outer tree legs and the overlap
        accumulator run on.  Batched-state cadence is irrelevant here:
        keyrange_merge already folded any pending rows."""
        return table_ops.merge(a, b, capacity=self.capacity)

    def finalize(self, state):
        return self._plain_table(state)

    def identity(self) -> str:
        # merge_every changes state SHAPE but not results; shapes are
        # validated against checkpoint leaves, so identity stays
        # cadence-independent.
        return "wordcount"


class TopKTable(NamedTuple):
    """A top-k finalized table plus the pre-reorder KMV snapshot.

    ``top_k`` is terminal: its count-descending reorder destroys the
    key-sorted KMV property, so the distinct estimate's inputs (occupancy
    and the largest kept key of the FULL table) are captured as scalars
    first — the Common-Crawl top-k config is exactly where table spill is
    likely, i.e. where the estimate matters (round-3 review weak #6).  The
    executor reads the scalars host-side via
    :func:`mapreduce_tpu.ops.table.kmv_from_snapshot`.
    """

    table: table_ops.CountTable
    kmv_n_valid: jax.Array  # uint32: occupancy at snapshot
    kmv_kth_hi: jax.Array  # uint32: largest kept key, hi lane
    kmv_kth_lo: jax.Array  # uint32: largest kept key, lo lane


def topk_with_snapshot(tbl: table_ops.CountTable, k: int) -> TopKTable:
    """Snapshot KMV scalars, then apply the terminal top-k reorder."""
    n_valid, kth_hi, kth_lo = table_ops.kmv_snapshot(tbl)
    return TopKTable(table_ops.top_k(tbl, k), n_valid, kth_hi, kth_lo)


class TopKWordCountJob(WordCountJob):
    """WordCount whose device-side finalize keeps only the k most frequent
    words (the Common-Crawl top-k benchmark config, BASELINE.md), plus the
    pre-reorder KMV snapshot (:class:`TopKTable`)."""

    def __init__(self, k: int, config: Config = DEFAULT_CONFIG):
        super().__init__(config)
        self.k = k

    def finalize(self, state):
        return topk_with_snapshot(self._plain_table(state), self.k)

    def identity(self) -> str:
        # k only affects finalize, but including it keeps resume semantics
        # obvious: one checkpoint, one job description.
        return f"wordcount-top{self.k}"


class NGramState(NamedTuple):
    """Streamed n-gram accumulator: running table + the last n-1 stream
    entries seen (the seam carry; ``ops.ngram.GramCarry``)."""

    table: table_ops.CountTable
    carry: Any


class NGramUpdate(NamedTuple):
    """One streamed step's per-device contribution: the chunk's in-window
    gram table, the step's gathered chunk summaries ([D]-leading leaves,
    identical on every device), and this device's linear index."""

    batch: table_ops.CountTable
    summaries: Any
    device_index: jax.Array


class NGramCountJob(WordCountJob):
    """Count n-token grams (bigrams, trigrams, ...) instead of single words.

    A beyond-parity model family (the reference's map UDF emits only single
    words, ``mapper`` ``main.cu:37-54``) that reuses the whole stack: the
    gram stream rides the same CountTable / collective-merge / string-recovery
    machinery, and each reported "word" is the exact source span of the gram
    (inter-token separators included, e.g. ``b"Hello World"``).

    Streamed runs are EXACT across chunk seams: each chunk's map also emits
    its first/last n-1 stream entries, one small all_gather shares them
    across the step, and ``combine`` composes the running carry in global
    chunk order — forming every window that crosses a join exactly once, the
    way grep threads its exact line carry (the round-2 "streamed runs
    undercount by up to (n-1)*(chunks-1)" envelope is gone).  Cross-chunk
    entries carry ``SEAM_GRAM_LENGTH`` and the host recovers their spans by
    scanning forward from the absolute start offset.

    Backends: the XLA path pairs tokens with carry-forward scans over the
    flat per-byte stream and counts any token length exactly; the pallas
    backend sorts the fused kernel's packed stream by position (one sort
    key recovers global token order, seam emissions included, so grams
    straddle the kernel's 128-lane seams exactly) and pairs rows
    elementwise.  Grams containing a token longer than the kernel window W
    self-invalidate at in-stream poison rows and land in ``dropped_*``
    accounting — the same >W contract as the pallas wordcount path
    (:mod:`mapreduce_tpu.ops.ngram`).  On overlong-free data the backends
    produce bit-identical tables.
    """

    def __init__(self, n: int, config: Config = DEFAULT_CONFIG,
                 top_k: int | None = None):
        if n < 1:
            raise ValueError(f"ngram order must be >= 1, got {n}")
        if n > 1 and config.merge_every > 1:
            # Honest failure beats a knob silently ignored: the n-gram
            # combine stages seam tables and merges pairwise.
            raise ValueError("merge_every > 1 applies to the wordcount "
                             "family only (n-gram combine is pairwise)")
        super().__init__(config)
        self.n = n
        self.k = top_k

    def map_chunk(self, chunk: jax.Array, chunk_id: jax.Array) -> table_ops.CountTable:
        """Per-chunk gram table (in-chunk windows only; the streamed seam
        machinery lives in :meth:`map_chunk_sharded` + :meth:`combine`)."""
        from mapreduce_tpu.ops import ngram as ngram_ops

        if self.config.resolved_backend() == "pallas":
            return ngram_ops.ngram_table(chunk, self.n, self.batch_capacity,
                                         chunk_id, self.config)
        gs = ngram_ops.mark_long_spans(
            tok_ops.ngrams(tok_ops.tokenize(chunk), self.n))
        return ngram_ops.gram_table(
            gs, self.batch_capacity, chunk_id, max_pos=chunk.shape[0],
            sort_mode=self.config.sort_mode,
            sort_impl=self.config.sort_impl,
            salt_bits=self.config.resolved_salt_bits,
            radix_geometry=self.config.resolved_radix_geometry)

    # -- exact cross-chunk grams (streamed runs) ----------------------------

    def init_state(self):
        from mapreduce_tpu.ops import ngram as ngram_ops

        if self.n == 1:
            return super().init_state()
        return NGramState(table=table_ops.empty(self.capacity),
                          carry=ngram_ops.empty_carry(self.n))

    def map_chunk_sharded(self, chunk, chunk_id, axis, device_index):
        """Streamed map: per-chunk table + this chunk's seam summary, with
        one small all_gather so every device sees the step's D summaries.
        The summaries are ~5*(n-1) words per chunk — noise next to the
        chunk itself."""
        if self.n == 1:
            return self.map_chunk(chunk, chunk_id)
        from mapreduce_tpu.ops import ngram as ngram_ops

        if self.config.resolved_backend() == "pallas":
            t, summ = ngram_ops.ngram_map_with_summary(
                chunk, self.n, self.batch_capacity, chunk_id, self.config)
        else:
            stream = tok_ops.tokenize(chunk)
            gs = ngram_ops.mark_long_spans(tok_ops.ngrams(stream, self.n))
            t = ngram_ops.gram_table(
                gs, self.batch_capacity, chunk_id, max_pos=chunk.shape[0],
                sort_mode=self.config.sort_mode,
                sort_impl=self.config.sort_impl,
                salt_bits=self.config.resolved_salt_bits,
                radix_geometry=self.config.resolved_radix_geometry)
            summ = ngram_ops.summary_from_stream(stream, chunk_id, self.n)
        gathered = jax.lax.all_gather(summ, axis_name=axis)  # leaves [D, n-1]
        return NGramUpdate(batch=t, summaries=gathered,
                           device_index=device_index)

    def map_chunk_stats_sharded(self, chunk, chunk_id, axis, device_index):
        """Stats-mode map for the gram family: the gram build computes no
        spill/rescue cond on its own (the fused pair-mode stream has no
        compact-window fallback), so the chunk counters carry only the
        batch table's dropped accounting — overlong-poisoned grams — and
        the gauges come off the running table as everywhere else."""
        upd = self.map_chunk_sharded(chunk, chunk_id, axis, device_index)
        tbl = upd.batch if isinstance(upd, NGramUpdate) else upd
        return upd, datastats.map_stats(dropped_tokens=tbl.dropped_count,
                                        dropped_uniques=tbl.dropped_uniques)

    def _stats_table(self, state) -> table_ops.CountTable:
        if isinstance(state, NGramState):
            return state.table
        return super()._stats_table(state)

    def combine(self, state, update):
        if self.n == 1:
            return super().combine(state, update)
        from mapreduce_tpu.ops import ngram as ngram_ops

        d_count = update.summaries.first.kind.shape[0]
        # Prefix carries in global chunk order: prefix[i] = everything before
        # this step's chunk i (state.carry composed with summaries 0..i-1).
        # A trace-time loop of D tiny elementwise folds; the final value is
        # the next step's carry, identical on every device.
        prefix = state.carry
        prefixes = [prefix]
        for i in range(d_count):
            s_i = jax.tree.map(lambda x, i=i: x[i], update.summaries)
            prefix = ngram_ops.compose_carry(prefix, s_i.last)
            prefixes.append(prefix)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *prefixes[:d_count])
        d = update.device_index.astype(jnp.int32)
        my_prefix = jax.tree.map(lambda x: jnp.take(x, d, axis=0), stacked)
        my_first = jax.tree.map(lambda x: jnp.take(x, d, axis=0),
                                update.summaries.first)
        seam_tbl = ngram_ops.seam_gram_table(my_prefix, my_first, self.n)
        batch = table_ops.merge(update.batch, seam_tbl,
                                capacity=update.batch.capacity)
        table = table_ops.merge(state.table, batch, capacity=self.capacity)
        return NGramState(table=table, carry=prefixes[-1])

    def merge(self, a, b):
        if self.n == 1:
            return super().merge(a, b)
        # Cross-device table reduction; carries are identical on every
        # device after combine (computed from the same gathered summaries),
        # so either operand's is fine.
        return NGramState(
            table=table_ops.merge(a.table, b.table, capacity=self.capacity),
            carry=a.carry)

    def analysis_observables(self, state):
        """graphcheck metadata: compare only the gram table in the merge
        property check.  The seam carry is coordination state — identical
        across devices within a run (every combine sees the same gathered
        summaries), so merge keeping one operand's is correct, but states
        built from different chunks legitimately disagree on it."""
        if self.n == 1 or not isinstance(state, NGramState):
            return state
        return state.table

    def keyrange_merge(self, state, axis) -> table_ops.CountTable:
        """Key-range reduce of the gram table (the carry is spent once
        every chunk's combine has run; only the table crosses devices)."""
        if self.n == 1:
            return super().keyrange_merge(state, axis)
        from mapreduce_tpu.parallel import collectives

        return collectives.key_range_merge(state.table, axis)

    def partial_reset(self, local):
        """Post-partial-merge reset (ISSUE 20 leg 2): the gram table was
        shipped into the resident accumulator, so it returns to empty —
        but the seam carry is CROSS-STEP context (the tail bytes of the
        previous chunk row), which the next step's combine still needs.
        Called per device inside shard_map on the LOCAL state."""
        init = self.init_state()
        if self.n == 1 or not isinstance(local, NGramState):
            return init
        return NGramState(table=init.table, carry=local.carry)

    def on_input_boundary(self, state):
        """Files are independent corpora: grams must not span a file seam.

        Called on the engine's STACKED state (carry leaves [D, n-1]), so the
        reset must preserve shapes (zeros_like, the GrepJob idiom) — a fresh
        empty_carry would collapse the leading device axis and break the
        next step's sharding.
        """
        if self.n == 1:
            return state
        return NGramState(table=state.table,
                          carry=jax.tree.map(jnp.zeros_like, state.carry))

    def finalize(self, state):
        tbl = state.table if isinstance(state, NGramState) \
            else self._plain_table(state)
        return topk_with_snapshot(tbl, self.k) if self.k else tbl

    def identity(self) -> str:
        # Resuming a bigram run's snapshot as a trigram run (same shapes!)
        # would mix gram orders: n is part of the job identity.
        return f"ngram{self.n}" + (f"-top{self.k}" if self.k else "")


class SketchedState(NamedTuple):
    """Count table + HyperLogLog registers (a pytree; engine/collective
    machinery treats it like any other mergeable accumulator)."""

    table: table_ops.CountTable
    registers: jax.Array  # uint32[2**p]


class FreqSketchedState(NamedTuple):
    """Count table + Count-Min Sketch (a pytree)."""

    table: table_ops.CountTable
    cms: jax.Array  # uint32[depth, width]


class BatchedSketchState(NamedTuple):
    """Sketch state with a pending-update buffer (sketch_flush_every > 1).

    Per-chunk key batches are staged into ``pend_*`` with a cheap
    ``dynamic_update_slice`` and scattered into the sketch once every K
    steps — TPU scatters carry a large fixed cost regardless of size
    (round-4/5 chip notes), so one scatter of K batches beats K scatters.
    ``pend_cnt`` doubles as the validity mask: flushed slots are zeroed, so
    re-flushing (e.g. at every collective merge level) is a masked no-op
    for both the idempotent HLL max and the additive CMS.
    """

    table: table_ops.CountTable
    sketch: jax.Array
    pend_hi: jax.Array  # uint32[K * batch_capacity]
    pend_lo: jax.Array
    pend_cnt: jax.Array
    cursor: jax.Array  # uint32 scalar: batches staged since last flush


class _SketchComposedJob:
    """Compose any WordCount-family job with a mergeable sketch.

    Shared TPU shape of all sketch families: the sketch updates from the
    *deduplicated* per-chunk batch table (capacity-sized device ops, never
    stream-sized), and merges with an associative+commutative monoid that
    rides the same collectives as the table.  Envelope: tokens spilled past
    per-chunk batch extraction miss the sketch too (accounted in
    ``dropped_count``).

    With ``config.sketch_flush_every = K > 1`` the per-step scatter is
    batched through :class:`BatchedSketchState` (flushed at merges and in
    finalize, so results are bit-identical to K=1); ``finalize`` always
    returns the plain ``state_cls`` so downstream result handling never
    sees the buffer.

    Subclasses set ``state_cls`` (a ``(table, sketch)`` NamedTuple) and the
    three sketch ops.
    """

    state_cls: type

    def __init__(self, base: WordCountJob):
        self.base = base
        self.config = base.config
        self.flush_every = base.config.sketch_flush_every

    def _empty(self) -> jax.Array:
        raise NotImplementedError

    def _update_arrays(self, sk: jax.Array, key_hi, key_lo, counts) -> jax.Array:
        raise NotImplementedError

    def _update(self, sk: jax.Array, update: table_ops.CountTable) -> jax.Array:
        return self._update_arrays(sk, update.key_hi, update.key_lo, update.count)

    def _merge(self, a: jax.Array, b: jax.Array) -> jax.Array:
        raise NotImplementedError

    def init_state(self):
        if self.flush_every == 1:
            return self.state_cls(self.base.init_state(), self._empty())
        n = self.flush_every * self.base.batch_capacity
        z = jnp.zeros((n,), jnp.uint32)
        return BatchedSketchState(self.base.init_state(), self._empty(),
                                  z, jnp.array(z), jnp.array(z),
                                  jnp.zeros((), jnp.uint32))

    @staticmethod
    def _folded(update):
        """Fold a SeamedUpdate before sketching: the sketch updates from
        the per-chunk batch table, so a deferred seam table would silently
        drop seam-first words from the HLL/CMS envelope.  Sketched runs
        pay the pairwise seam merge the plain path optimized away."""
        if isinstance(update, SeamedUpdate):
            return table_ops.merge(update.batch, update.seam,
                                   capacity=update.batch.capacity)
        return update

    def map_chunk(self, chunk, chunk_id) -> table_ops.CountTable:
        return self._folded(self.base.map_chunk(chunk, chunk_id))

    def map_chunk_sharded(self, chunk, chunk_id, axis, device_index):
        """Forward the base job's axis-aware map (n-grams' exact seam
        machinery) so sketch composition doesn't silently disable it."""
        fn = getattr(self.base, "map_chunk_sharded", None)
        if fn is not None:
            return self._folded(fn(chunk, chunk_id, axis, device_index))
        return self._folded(self.base.map_chunk(chunk, chunk_id))

    def on_input_boundary(self, state):
        """Forward the base job's file-boundary hook (n-gram carry reset)."""
        hook = getattr(self.base, "on_input_boundary", None)
        if hook is None:
            return state
        return state._replace(table=hook(state.table))

    # -- data-plane telemetry (ISSUE 8): forward the base job's stats ----

    @property
    def data_stats_supported(self) -> bool:
        return datastats.supports(self.base)

    def map_chunk_stats_sharded(self, chunk, chunk_id, axis, device_index):
        upd, stats = self.base.map_chunk_stats_sharded(
            chunk, chunk_id, axis, device_index)
        return self._folded(upd), stats

    def state_stats(self, state, stats):
        base_state = state.table if isinstance(state, BatchedSketchState) \
            else state[0]
        return self.base.state_stats(base_state, stats)

    @staticmethod
    def _batch_of(update) -> table_ops.CountTable:
        """The plain CountTable inside an update (n-gram updates bundle it
        with seam summaries).  Sketch envelope: cross-chunk seam grams
        (< n per step) miss the sketch, like spilled batch rows do."""
        return update if isinstance(update, table_ops.CountTable) else update.batch

    def combine(self, state, update):
        batch = self._batch_of(update)
        if self.flush_every == 1:
            return self.state_cls(self.base.combine(state[0], update),
                                  self._update(state[1], batch))
        table = self.base.combine(state.table, update)
        b = batch.key_hi.shape[0]
        off = (state.cursor % jnp.uint32(self.flush_every)) * jnp.uint32(b)
        off = off.astype(jnp.int32)
        pend_hi = jax.lax.dynamic_update_slice(state.pend_hi, batch.key_hi, (off,))
        pend_lo = jax.lax.dynamic_update_slice(state.pend_lo, batch.key_lo, (off,))
        pend_cnt = jax.lax.dynamic_update_slice(state.pend_cnt, batch.count, (off,))
        cursor = state.cursor + jnp.uint32(1)

        def flush(_):
            sk = self._update_arrays(state.sketch, pend_hi, pend_lo, pend_cnt)
            return sk, jnp.zeros_like(pend_cnt), jnp.zeros((), jnp.uint32)

        def keep(_):
            return state.sketch, pend_cnt, cursor

        sk, pend_cnt, cursor = jax.lax.cond(
            cursor >= jnp.uint32(self.flush_every), flush, keep, operand=None)
        return BatchedSketchState(table, sk, pend_hi, pend_lo, pend_cnt, cursor)

    def _flushed(self, st: BatchedSketchState) -> BatchedSketchState:
        """Fold any staged rows into the sketch (masked no-op when empty)."""
        sk = self._update_arrays(st.sketch, st.pend_hi, st.pend_lo, st.pend_cnt)
        return BatchedSketchState(st.table, sk, st.pend_hi, st.pend_lo,
                                  jnp.zeros_like(st.pend_cnt),
                                  jnp.zeros((), jnp.uint32))

    def merge(self, a, b):
        if self.flush_every == 1:
            return self.state_cls(self.base.merge(a[0], b[0]),
                                  self._merge(a[1], b[1]))
        fa, fb = self._flushed(a), self._flushed(b)
        return BatchedSketchState(
            self.base.merge(fa.table, fb.table),
            self._merge(fa.sketch, fb.sketch),
            fa.pend_hi, fa.pend_lo, fa.pend_cnt, fa.cursor)

    def keyrange_merge(self, state, axis):
        """Compose the base job's key-range table reduce with the sketch's
        own monoid over the axis (tree-merge of the small sketch array —
        its cost is noise next to the table exchange)."""
        from mapreduce_tpu.parallel import collectives

        if self.flush_every == 1:
            table_state, sketch = state[0], state[1]
        else:
            st = self._flushed(state)
            table_state, sketch = st.table, st.sketch
        return self.state_cls(
            self.base.keyrange_merge(table_state, axis),
            collectives.tree_merge(sketch, self._merge, axis))

    def keyrange_result_merge(self, a, b):
        """Merge two keyrange results (``state_cls(plain_table, sketch)``
        pairs): the base job's result merge on the table, the sketch's
        own monoid on the sketch — the hier-kr-tree outer-leg / overlap-
        accumulator fold."""
        return self.state_cls(self.base.keyrange_result_merge(a[0], b[0]),
                              self._merge(a[1], b[1]))

    def finalize(self, state):
        if self.flush_every == 1:
            return self.state_cls(self.base.finalize(state[0]), state[1])
        if isinstance(state, BatchedSketchState):
            st = self._flushed(state)
            # Downstream (executor result unwrapping, checkpoint-of-results)
            # sees the same plain state shape as unbatched runs.
            return self.state_cls(self.base.finalize(st.table), st.sketch)
        # Already a plain state_cls (the keyrange hook returns one).
        return self.state_cls(self.base.finalize(state[0]), state[1])

    def identity(self) -> str:
        # flush_every changes state SHAPE but not results; shapes are
        # validated against the checkpoint leaves, so identity stays
        # cadence-independent.
        return f"{type(self).__name__.lower()}({self.base.identity()})"


class FreqSketchedWordCountJob(_SketchComposedJob):
    """Wrap any WordCount-family job with a Count-Min frequency sketch.

    Where :class:`SketchedWordCountJob` keeps the *distinct count* honest past
    table capacity, this keeps *per-word frequencies* queryable: the sketch's
    row-min upper-bounds any key's true count (error <= total/width per row
    w.h.p.), including words the exact table spilled.  Query host-side with
    :func:`mapreduce_tpu.ops.sketch.cms_query` — any word (or n-gram span),
    no device trip.
    """

    state_cls = FreqSketchedState

    def __init__(self, base: WordCountJob, depth: int = sketch_ops.CMS_DEPTH,
                 width_log2: int = sketch_ops.CMS_WIDTH_LOG2):
        super().__init__(base)
        self.depth = depth
        self.width_log2 = width_log2

    def _empty(self):
        return sketch_ops.cms_empty(self.depth, self.width_log2)

    def _update_arrays(self, sk, key_hi, key_lo, counts):
        return sketch_ops.cms_update(sk, key_hi, key_lo, counts)

    def _merge(self, a, b):
        return sketch_ops.cms_merge(a, b)


class SketchedWordCountJob(_SketchComposedJob):
    """Wrap any WordCount-family job with a distinct-count sketch.

    The table's ``distinct`` degrades to an upper bound once keys spill past
    capacity (see WordCountResult); the HyperLogLog keeps an accurate
    distinct estimate at any scale.  Register updates are a capacity-sized
    scatter-max (the TPU cost model: scatter cost scales with input length);
    the merge is elementwise ``maximum``, idempotent, so cross-chunk
    duplicate keys are harmless.
    """

    state_cls = SketchedState

    def __init__(self, base: WordCountJob, precision: int = sketch_ops.DEFAULT_PRECISION):
        super().__init__(base)
        self.precision = precision

    def _empty(self):
        return sketch_ops.empty(self.precision)

    def _update_arrays(self, sk, key_hi, key_lo, counts):
        return sketch_ops.update_from_keys(sk, key_hi, key_lo, counts > 0)

    def _merge(self, a, b):
        return sketch_ops.merge(a, b)
