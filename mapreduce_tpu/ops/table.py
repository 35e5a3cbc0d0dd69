"""Sorted fixed-capacity count tables: the parallel reduce data plane.

The reference reduces with a single device thread doing an O(pairs x distinct)
linear-scan group-by (``reducer``, ``main.cu:69-108``, launched serially via
the ``i < 1`` loop at ``main.cu:120``).  Here the same group-by-key-and-sum is
a *sort + segment-reduce*: O(n log n) work, fully parallel, static shapes, and
— crucially — the resulting :class:`CountTable` has an **associative merge**,
which is what lets the global reduction become a collective (tree ``ppermute``
/ ``all_gather`` / key-range ``all_to_all``) instead of the reference's serial
device-wide pass.

Invariants of a well-formed table (established by every constructor here):
  * entries are sorted ascending by 64-bit key;
  * occupied slots (``(count | count_hi) > 0``) form a prefix; empty slots
    carry the sentinel key, count 0, pos = +inf, length 0;
  * ``(pos_hi, pos_lo)`` is the lexicographically smallest (i.e. first)
    occurrence of the key, enabling exact insertion-order reporting and
    host-side string recovery (SURVEY §7);
  * overflow past capacity is *accounted* (``dropped_count`` exact,
    ``dropped_uniques`` an upper bound), never silent corruption like the
    reference past MAX_OUTPUT_COUNT (``main.cu:103-104``).

Key-collision envelope: keys are 64-bit hashes (two independent fmix32
lanes, token length mixed in), never the token bytes — so two DISTINCT
words colliding on all 64 bits would silently merge into one entry (first
occurrence's identity, summed count).  Birthday arithmetic: P(any
collision among n distinct words) ~ n^2 / 2^65 — ~3e-8 at 1e6 distinct
(enwik8), ~3e-4 at 1e8 (the 100 GB Zipf target), ~3e-2 at 1e9
(Common-Crawl WET scale).  Undetectable from the table alone (the table
never sees the strings); the DETECTION path is a host-side exact recount
of reported words (:mod:`mapreduce_tpu.utils.verify`, CLI
``--verify-sample K``), where a collision shows as a reported count
exceeding the byte-exact recount.

Count envelope: per-key counts and the ``dropped_*`` scalars are exact
**64-bit** values carried as uint32 lo/hi lane pairs (JAX default-x64 is
off, so device uint64 is unavailable — the grep accumulator idiom,
``models/grep.py``).  Batch tables built from one chunk stream never exceed
2**26 rows, so their hi lanes are structurally zero; the hi lanes earn
their keep in the running-table ``merge``/``merge_batched`` adds, where a
single uint32 would silently wrap at ~4.29e9 occurrences per word (~30 GB
of one repeated word — inside the BASELINE 100 GB envelope).  Wrap is
silent corruption, the exact failure mode this framework exists to never
have; every add/sum in this module carries.  Host-side totals
(:meth:`CountTable.total_count` on fetched tables) are reconstructed
``hi << 32 | lo`` in int64.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from mapreduce_tpu import constants
from mapreduce_tpu.ops.tokenize import TokenStream


class CountTable(NamedTuple):
    """Keyed count state.  A pytree; all fields are device arrays.

    Counts and dropped scalars are exact 64-bit lo/hi uint32 pairs (module
    docstring).  Occupancy is ``(count | count_hi) > 0`` — a key holding
    exactly a multiple of 2**32 occurrences has ``count == 0`` with a
    nonzero hi lane, so ``count > 0`` alone is NOT an occupancy test.
    """

    key_hi: jax.Array  # uint32[V], sorted (with key_lo) ascending
    key_lo: jax.Array  # uint32[V]
    count: jax.Array  # uint32[V]  occurrence count, low word
    count_hi: jax.Array  # uint32[V]  occurrence count, high word
    pos_hi: jax.Array  # uint32[V]  (device,step) buffer id of first occurrence
    pos_lo: jax.Array  # uint32[V]  byte offset within that buffer
    length: jax.Array  # uint32[V]  token length in bytes
    dropped_uniques: jax.Array  # uint32 scalar, >= true number of spilled keys
    dropped_count: jax.Array  # uint32 scalar, exact token count spilled (lo)
    dropped_uniques_hi: jax.Array  # uint32 scalar, high word
    dropped_count_hi: jax.Array  # uint32 scalar, high word

    @property
    def capacity(self) -> int:
        return self.key_hi.shape[0]

    def occupied(self) -> jax.Array:
        """bool[V]: slots holding a live key (the single occupancy rule)."""
        return (self.count | self.count_hi) > 0

    def n_valid(self) -> jax.Array:
        return jnp.sum(self.occupied().astype(jnp.uint32))

    def dropped_totals(self) -> tuple[int, int]:
        """Host-side exact ``(dropped_uniques, dropped_count)`` ints from
        the 64-bit lane pairs (concrete tables only)."""
        return (int(self.dropped_uniques) + (int(self.dropped_uniques_hi) << 32),
                int(self.dropped_count) + (int(self.dropped_count_hi) << 32))

    def total_count64(self) -> tuple[jax.Array, jax.Array]:
        """Exact 64-bit total as ``(lo, hi)`` uint32 lanes — safe under jit.

        Per-key lanes are summed with wrap carry (:func:`sum64`) and the
        ``dropped_*`` lanes folded in (:func:`add64`), so the pair is exact
        at any corpus scale.  Host callers reconstructing an int:
        ``(hi << 32) | lo`` (what :meth:`total_count` does for them).
        """
        lo, hi = sum64(self.count, self.count_hi)
        return add64(lo, hi, self.dropped_count, self.dropped_count_hi)

    def total_count(self) -> int:
        """Total tokens represented, including spilled ones (exact int).

        Host-side only: concrete tables (numpy leaves, or fetched device
        arrays) reconstruct the 64-bit lanes in int64.  Under jit there is
        no device uint64 (x64 off), so a single traced scalar cannot carry
        the exact total — the old behavior summed the low words alone and
        silently wrapped at 2**32, the 32-bit count-path hazard the
        graphcheck overflow lint exists to catch.  Traced callers take the
        exact lane pair from :meth:`total_count64` instead.
        """
        if not isinstance(self.count, jax.core.Tracer):
            lo = np.asarray(self.count).astype(np.int64)
            hi = np.asarray(self.count_hi).astype(np.int64)
            return int((lo + (hi << np.int64(32))).sum()) \
                + int(self.dropped_count) + (int(self.dropped_count_hi) << 32)
        raise TypeError(
            "CountTable.total_count() is host-side (returns an exact int); "
            "under jit use total_count64() -> (lo, hi) uint32 lanes")


def empty(capacity: int) -> CountTable:
    sent = jnp.full((capacity,), constants.SENTINEL_KEY, dtype=jnp.uint32)
    zero = jnp.zeros((capacity,), dtype=jnp.uint32)
    inf = jnp.full((capacity,), constants.POS_INF, dtype=jnp.uint32)
    return CountTable(key_hi=sent, key_lo=jnp.array(sent), count=zero,
                      count_hi=jnp.array(zero), pos_hi=inf,
                      pos_lo=jnp.array(inf), length=jnp.array(zero),
                      dropped_uniques=jnp.uint32(0), dropped_count=jnp.uint32(0),
                      dropped_uniques_hi=jnp.uint32(0),
                      dropped_count_hi=jnp.uint32(0))


def add64(a_lo, a_hi, b_lo, b_hi):
    """(lo, hi) + (lo, hi) with carry: exact uint64 in two uint32 lanes.
    Elementwise — scalars and arrays alike (the grep accumulator idiom)."""
    lo = a_lo + b_lo
    return lo, a_hi + b_hi + (lo < a_lo).astype(jnp.uint32)


def _sub64(a_lo, a_hi, b_lo, b_hi):
    """(lo, hi) - (lo, hi) with borrow; caller guarantees a >= b."""
    return a_lo - b_lo, a_hi - b_hi - (a_lo < b_lo).astype(jnp.uint32)


def sum64(lo: jax.Array, hi: jax.Array | None = None):
    """Exact 64-bit (lo, hi) sum of uint32 lane arrays.

    The low-lane sum wraps; wraps are counted off the running cumsum (a
    partial sum decreases exactly when the add wrapped, since every addend
    is < 2**32) and folded into the high word.  The hi-lane sum itself is a
    plain uint32 sum: overflowing it needs > 2**64 total tokens, i.e. more
    bytes than the corpus can physically contain.
    """
    s = jnp.cumsum(lo)
    wraps = jnp.sum((s[1:] < s[:-1]).astype(jnp.uint32)) if lo.shape[0] > 1 \
        else jnp.uint32(0)
    hi_sum = jnp.sum(hi) if hi is not None else jnp.uint32(0)
    return s[-1], hi_sum + wraps


def _segment_heads(seg: jax.Array, capacity: int) -> jax.Array:
    """First sorted-row index of each of the first capacity+1 segments.

    Equivalent to ``jnp.searchsorted(seg, arange(capacity+1))`` but as an
    UNROLLED binary search: ``jnp.searchsorted``'s while-loop lowering pays
    a fixed per-iteration cost plus loop-carry device copies on TPU
    (~15 ms/chunk measured); the static log-n chain of gathers is both
    cheaper and fusion-friendly.
    """
    n = seg.shape[0]
    q = jnp.arange(capacity + 1, dtype=jnp.int32)
    lo = jnp.zeros((capacity + 1,), jnp.int32)
    hi = jnp.full((capacity + 1,), n, jnp.int32)
    # Range [0, n] holds n+1 candidate answers: n.bit_length() iterations
    # always suffice ((n-1).bit_length() is one short when n is a power of
    # two — exactly the table capacities).
    for _ in range(max(1, n.bit_length())):
        mid = (lo + hi) >> 1
        right = seg[jnp.minimum(mid, n - 1)] < q
        lo = jnp.where(right, mid + 1, lo)
        hi = jnp.where(right, hi, mid)
    return hi


def _first_key_geq(key_hi, key_lo, q_hi, q_lo) -> jax.Array:
    """Index of the first sorted row with 64-bit key >= (q_hi, q_lo)
    (``n`` if none) — an unrolled binary search over the two key lanes,
    the :func:`_segment_heads` idiom (searchsorted's while-loop lowering
    is the expensive path on TPU)."""
    n = key_hi.shape[0]
    q_hi = jnp.uint32(q_hi)
    q_lo = jnp.uint32(q_lo)
    lo = jnp.int32(0)
    hi = jnp.int32(n)
    for _ in range(max(1, n.bit_length())):
        mid = (lo + hi) >> 1
        m = jnp.minimum(mid, n - 1)
        below = (key_hi[m] < q_hi) | ((key_hi[m] == q_hi) & (key_lo[m] < q_lo))
        lo = jnp.where(below, mid + 1, lo)
        hi = jnp.where(below, hi, mid)
    return hi


def _segment_boundaries(key_hi, key_lo):
    """Boundary mask + segment ranks of key-sorted rows (shared by the
    generic and packed reduce paths so their grouping can never diverge)."""
    boundary = (key_hi != jnp.concatenate([key_hi[:1], key_hi[:-1]])) | \
               (key_lo != jnp.concatenate([key_lo[:1], key_lo[:-1]]))
    boundary = boundary.at[0].set(True)
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1  # int32[n], sorted
    return boundary, seg


def _overflow_accounting(sorted_key_hi, sorted_key_lo, seg, capacity: int):
    """dropped_uniques for real segments past capacity.  The two RESERVED
    pseudo-segments — overlong-poison markers (sent, sent-1), then dead
    filler (sent, sent) — sort last (real keys are clamped below both by
    every tokenizer backend) and are excluded via two log-n binary
    searches."""
    sent = jnp.uint32(constants.SENTINEL_KEY)
    n = sorted_key_hi.shape[0]
    s_poison = _first_key_geq(sorted_key_hi, sorted_key_lo,
                              sent, sent - jnp.uint32(1))
    s_filler = _first_key_geq(sorted_key_hi, sorted_key_lo, sent, sent)
    has_poison = (s_poison < s_filler).astype(jnp.uint32)
    has_filler = (s_filler < n).astype(jnp.uint32)
    n_real = (seg[-1] + 1).astype(jnp.uint32) - has_filler - has_poison
    cap = jnp.uint32(capacity)
    return jnp.where(n_real > cap, n_real - cap, jnp.uint32(0))


def _reduce_sorted_rows(key_hi, key_lo, pos_hi, pos_lo, count, count_hi,
                        length, capacity: int):
    """Group-by-key segment reduce of rows already sorted by (key, pos).

    Scatter-free (the TPU cost model: even capacity-sized scatters carry a
    large fixed cost — ~30 ms per merge step measured on v5e — while sorted
    binary search + capacity-sized gathers are ~free): segment heads come
    from one ``searchsorted`` of ``arange(capacity+1)`` against the segment
    ranks, per-segment count sums are prefix-sum differences at the heads,
    and the remaining per-key fields are head-row gathers (rows are sorted
    by (key, pos), so the head row of each segment carries the
    lexicographically-first occurrence).

    Counts are 64-bit lane pairs: the low-word cumsum wraps, so the prefix
    sums carry a running wrap count into the high word (``merge_batched``
    routes running-table rows with large counts through here).
    """
    _, seg = _segment_boundaries(key_hi, key_lo)
    n = key_hi.shape[0]
    sent = jnp.uint32(constants.SENTINEL_KEY)
    inf = jnp.uint32(constants.POS_INF)

    # Segment j occupies sorted rows [head[j], head[j+1]).
    head = _segment_heads(seg, capacity)
    fi = jnp.minimum(head[:capacity], n - 1)

    csum = jnp.cumsum(count)  # uint32 inclusive prefix sums, wrapping
    wrapped = jnp.concatenate([jnp.zeros((1,), jnp.uint32),
                               (csum[1:] < csum[:-1]).astype(jnp.uint32)])
    csum_hi = jnp.cumsum(count_hi) + jnp.cumsum(wrapped)

    def prefix(cs, h):  # lane sum of counts in rows [0, h)
        return jnp.where(h > 0, cs[jnp.maximum(h, 1) - 1], jnp.uint32(0))

    count_u, count_hi_u = _sub64(
        prefix(csum, head[1:]), prefix(csum_hi, head[1:]),
        prefix(csum, head[:capacity]), prefix(csum_hi, head[:capacity]))
    key_hi_u, key_lo_u = key_hi[fi], key_lo[fi]
    occupied = (head[:capacity] < n) & ((count_u | count_hi_u) > 0) \
        & ~((key_hi_u == sent) & (key_lo_u >= sent - jnp.uint32(1)))

    count_u = jnp.where(occupied, count_u, jnp.uint32(0))
    count_hi_u = jnp.where(occupied, count_hi_u, jnp.uint32(0))
    key_hi_u = jnp.where(occupied, key_hi_u, sent)
    key_lo_u = jnp.where(occupied, key_lo_u, sent)
    pos_hi_u = jnp.where(occupied, pos_hi[fi], inf)
    pos_lo_u = jnp.where(occupied, pos_lo[fi], inf)
    len_u = jnp.where(occupied, length[fi], jnp.uint32(0))

    dropped_uniques = _overflow_accounting(key_hi, key_lo, seg, capacity)
    dc_lo, dc_hi = _sub64(csum[-1], csum_hi[-1], *sum64(count_u, count_hi_u))
    return (key_hi_u, key_lo_u, count_u, count_hi_u, pos_hi_u, pos_lo_u,
            len_u, dropped_uniques, dc_lo, dc_hi)


def _build(key_hi, key_lo, pos_hi, pos_lo, count, count_hi, length,
           capacity: int, carry_du, carry_du_hi, carry_dc,
           carry_dc_hi) -> CountTable:
    """Sort rows by (key, first-occurrence) and segment-reduce into a table."""
    key_hi, key_lo, pos_hi, pos_lo, count, count_hi, length = jax.lax.sort(
        (key_hi, key_lo, pos_hi, pos_lo, count, count_hi, length), num_keys=4
    )
    (key_hi_u, key_lo_u, count_u, count_hi_u, pos_hi_u, pos_lo_u, len_u,
     du, dc, dc_hi) = _reduce_sorted_rows(
        key_hi, key_lo, pos_hi, pos_lo, count, count_hi, length, capacity
    )
    du_lo, du_hi = add64(carry_du, carry_du_hi, du, jnp.uint32(0))
    dc_lo2, dc_hi2 = add64(carry_dc, carry_dc_hi, dc, dc_hi)
    return CountTable(
        key_hi=key_hi_u, key_lo=key_lo_u, count=count_u, count_hi=count_hi_u,
        pos_hi=pos_hi_u, pos_lo=pos_lo_u, length=len_u,
        dropped_uniques=du_lo, dropped_count=dc_lo2,
        dropped_uniques_hi=du_hi, dropped_count_hi=dc_hi2,
    )


def from_packed_rows(key_hi: jax.Array, key_lo: jax.Array, packed: jax.Array,
                     total: jax.Array, capacity: int, pos_hi: jax.Array | int,
                     len_bits: int = 6, sort_mode: str = "sort3",
                     rescue_slots: int = 0, sort_impl: str = "xla",
                     salt_bits: int = 0,
                     radix_geometry: tuple | None = None):
    """Aggregate pre-packed single-occurrence rows (the sort-lean path).

    ``packed`` = ``pos << len_bits | length`` per live row (all-ones for
    dead rows, which sorts last); the caller guarantees length fits
    ``len_bits`` bits and pos fits the remaining 32-len_bits.  On a real
    chip, large scatters/gathers cost 300-900 ms while sorts cost
    ~3 ms/M/array and capacity-sized gathers are ~free, so this path:

      1. sorts just 3 arrays with 3 keys — (key_hi, key_lo, packed), so the
         smallest pos (first occurrence) leads each key's segment;
      2. segment-reduces with *no* full-length scatters: segment ranks from a
         cumsum, one ``searchsorted`` of arange(capacity+1) against the rank
         array (binary search = log-n capacity-sized gathers), counts as
         rank-range differences, and per-key fields as capacity-sized gathers
         at the segment heads.

    ``sort_mode='stable2'`` drops the third comparator key entirely: a
    STABLE two-key sort with ``packed`` as payload.  Its precondition is
    that the caller's rows arrive in ascending position order (the
    lane-major kernel layout, or the XLA backend's per-byte streams):
    stability then guarantees each segment's head row is the earliest
    input row = the smallest position — first occurrence for free.  The
    round-4 sortbench measured the comparator-width cut at ~40% of the
    sort's compute (173.8 -> 143.2 ms on 16.8M rows, stability +1.2%)
    where the stream sort is the single-chip floor; sort3 remains for
    slot-major streams, which are NOT position-ordered.

    ``sort_mode='segmin'`` also sorts two keys but recovers first
    occurrence as a segmented running-min of ``packed`` (no input-order
    precondition).  Bit-identical; REFUSED on TPU — its stream-sized
    associative_scan wedges the chip (round-4 chip notes).

    With ``rescue_slots = R > 0`` (sort3/stable2), also returns the first
    R ``packed`` values of the POISON segment — overlong-end markers
    (``pos << len_bits`` with zero length bits) carrying the reserved key
    (sent, sent-1), which sorts immediately before the dead-filler
    segment.  Under sort3 the third key orders them by position; under
    stable2 position-ordered input does.  The overlong-rescue pass
    (:mod:`mapreduce_tpu.ops.rescue`) re-tokenizes windows at exactly
    these positions; riding the aggregation sort makes the extraction
    ~free (one log-n binary search plus an R-row slice), where any
    standalone compaction would cost a second stream-sized sort or
    scatter.  Returns ``(table, rescue_packed)`` then; segmin cannot
    order the poison segment (packed rides as payload in arbitrary
    order), so that combination is rejected.

    ``sort_impl`` picks the sort IMPLEMENTATION behind ``sort_mode``
    (``Config.sort_impl``): ``'xla'`` is ``jax.lax.sort``; ``'radix'`` /
    ``'radix_partition'`` route the stream through the Pallas radix
    partition (:func:`mapreduce_tpu.ops.pallas.radix.radix_sort3`), whose
    tie-by-``packed`` contract is bit-identical to sort3 outright and to
    stable2 under its position-ordered-input precondition — so ONE radix
    implementation serves both modes, poison-segment rescue extraction
    included.  segmin is xla-only.

    With ``salt_bits`` = B > 0 (``Config.combiner='salt'``, ISSUE 11: the
    pathological-single-key-stream tier below the hot-key cache), the low
    B position bits are XORed into ``key_lo`` BEFORE the sort — one
    scorching key spreads over 2**B segments, defeating the measured ~4x
    radix hot-key slab amplification — and the built table is de-salted
    (the XOR is recoverable: every row of a salted segment shares
    ``pos & (2**B - 1)``, so the kept head row's position undoes it) and
    re-reduced through the generic build, coalescing the <= 2**B salted
    entries per original key with exact counts and the true minimum
    first occurrence.  Reserved-key rows (``key_hi == sent``: filler and
    poison) are never salted, so the poison-segment rescue extraction is
    untouched.  Exactness envelope, both legs documented: (1) two
    DISTINCT hash keys that differ only by a legal salt XOR would
    coalesce — a 2**B-fold widening of the documented ~n^2/2^65 64-bit
    key-collision envelope (detectable by --verify-sample, as ever); the
    single-key streams salting exists for cannot collide at all.  (2)
    Bit-identity to the unsalted build holds while distinct keys FIT
    ``capacity``: under unique overflow the capacity cutoff falls on the
    SALTED key order, so the kept set — and a straddling key's kept
    count — can differ from the unsalted build's (occurrence
    conservation still holds exactly through ``dropped_count``; this is
    the cross-table-merge dropped-accounting caveat the streamed paths
    already document, not silent loss).  segmin is refused (its payload
    scan keeps no per-salt-segment order to de-salt from).

    Matches :func:`_build` output bit-for-bit under its preconditions (every
    live row has count 1, one shared pos_hi).
    """
    if sort_mode not in ("sort3", "stable2", "segmin"):
        raise ValueError(f"unknown sort_mode {sort_mode!r}")
    if salt_bits and sort_mode == "segmin":
        raise ValueError("salt_bits requires sort_mode='sort3' or 'stable2'")
    if not 0 <= salt_bits <= 6:
        raise ValueError(f"salt_bits must be in [0, 6], got {salt_bits}")
    if sort_impl not in ("xla", "radix", "radix_partition"):
        raise ValueError(f"unknown sort_impl {sort_impl!r}")
    if sort_impl != "xla" and sort_mode == "segmin":
        raise ValueError("sort_impl='radix'/'radix_partition' requires "
                         "sort_mode='sort3' or 'stable2' (segmin keeps "
                         "packed as an unordered payload)")
    if rescue_slots and sort_mode == "segmin":
        raise ValueError("rescue_slots requires sort_mode='sort3' or "
                         "'stable2' (poison extraction needs the poison "
                         "segment position-ordered)")
    if sort_mode == "segmin":
        from mapreduce_tpu.config import (PlatformRefusedError,
                                          SEGMIN_TPU_ERROR, segmin_allowed)

        # Refuse the measured chip-wedge at trace time (the CPU A/B stays
        # alive); config.segmin_allowed owns the deliberate override.
        if jax.default_backend() == "tpu" and not segmin_allowed():
            raise PlatformRefusedError(SEGMIN_TPU_ERROR)
    sent = jnp.uint32(constants.SENTINEL_KEY)
    inf = jnp.uint32(constants.POS_INF)
    n = key_hi.shape[0]
    len_mask = jnp.uint32((1 << len_bits) - 1)

    if salt_bits:
        # Salt = the row's low position bits (every salted segment is then
        # position-homogeneous in those bits — the de-salt invariant).
        # key_hi == sent rows (dead filler, poison, and the rare clamped
        # real keys) pass through unsalted, keeping the reserved segments
        # and the poison binary search byte-identical.
        smask = jnp.uint32((1 << salt_bits) - 1)
        key_lo = jnp.where(key_hi != sent,
                           key_lo ^ ((packed >> len_bits) & smask), key_lo)

    if sort_mode == "segmin":
        key_hi, key_lo, packed = jax.lax.sort(
            (key_hi, key_lo, packed), num_keys=2)
        boundary, rank = _segment_boundaries(key_hi, key_lo)

        def _min_combine(x, y):
            # y is the later element; a boundary row restarts its segment.
            xb, xv = x
            yb, yv = y
            return xb | yb, jnp.where(yb, yv, jnp.minimum(xv, yv))

        _, run_min = jax.lax.associative_scan(_min_combine, (boundary, packed))
    elif sort_impl != "xla":
        # Radix path (Config.sort_impl): bit-identical to BOTH branches
        # below — ties resolve by `packed`, which is sort3's third
        # comparator key outright and, under stable2's position-ordered
        # input, exactly the tie order stability delivers.  Adversarial
        # bucket skew falls back to the XLA sort inside radix_sort3.
        from mapreduce_tpu.ops.pallas import radix as radix_ops

        # radix_geometry (ISSUE 12): an explicit (bits, block_rows,
        # slab_slack) candidate from Config.geometry; None keeps the
        # wrapper's call-time default resolution (the module-global
        # geometry override tests rely on).
        r_bits, r_rows, r_slack = radix_geometry or (None, None, None)
        key_hi, key_lo, packed = radix_ops.radix_sort3(
            key_hi, key_lo, packed, impl=sort_impl, bits=r_bits,
            block_rows=r_rows, slab_slack=r_slack)
        _, rank = _segment_boundaries(key_hi, key_lo)
        run_min = None
    elif sort_mode == "stable2":
        # Stable two-key sort, packed as PAYLOAD: ties keep input order, so
        # with position-ordered input each segment's head row carries the
        # smallest position — the same first-occurrence invariant sort3
        # buys with a third comparator key.
        key_hi, key_lo, packed = jax.lax.sort(
            (key_hi, key_lo, packed), num_keys=2, is_stable=True)
        _, rank = _segment_boundaries(key_hi, key_lo)
        run_min = None
    else:
        key_hi, key_lo, packed = jax.lax.sort(
            (key_hi, key_lo, packed), num_keys=3)
        _, rank = _segment_boundaries(key_hi, key_lo)
        run_min = None

    # Segment j occupies rows [head[j], head[j+1]) in sorted order.
    head = _segment_heads(rank, capacity)
    fi = jnp.minimum(head[:capacity], n - 1)
    count_u = (head[1:] - head[:capacity]).astype(jnp.uint32)

    key_hi_u, key_lo_u = key_hi[fi], key_lo[fi]
    if run_min is None:
        packed_u = packed[fi]  # sorted third key: head row IS min packed
    else:
        # The running min lands on each segment's LAST row (inclusive scan
        # restarting at boundaries).
        tail = jnp.minimum(jnp.maximum(head[1:], 1) - 1, n - 1)
        packed_u = run_min[tail]
    occupied = (head[:capacity] < n) & (count_u > 0) \
        & ((key_hi_u != sent) | (key_lo_u < sent - jnp.uint32(1)))

    count_u = jnp.where(occupied, count_u, jnp.uint32(0))
    key_hi_u = jnp.where(occupied, key_hi_u, sent)
    key_lo_u = jnp.where(occupied, key_lo_u, sent)
    pos_lo_u = jnp.where(occupied, packed_u >> len_bits, inf)
    len_u = jnp.where(occupied, packed_u & len_mask, jnp.uint32(0))
    pos_hi_u = jnp.where(occupied, jnp.asarray(pos_hi, jnp.uint32), inf)

    dropped_uniques = _overflow_accounting(key_hi, key_lo, rank, capacity)
    # Single-occurrence rows, <= 2**26 of them: every count fits the low
    # word, so the hi lanes of this path are structurally zero.
    dropped_count = total - jnp.sum(count_u)
    zero = jnp.uint32(0)
    table = CountTable(
        key_hi=key_hi_u, key_lo=key_lo_u, count=count_u,
        count_hi=jnp.zeros_like(count_u),
        pos_hi=pos_hi_u, pos_lo=pos_lo_u, length=len_u,
        dropped_uniques=dropped_uniques, dropped_count=dropped_count,
        dropped_uniques_hi=zero, dropped_count_hi=zero,
    )
    if salt_bits:
        # De-salt at the reduce seam: each kept row's position carries its
        # own salt (all rows of a salted segment share the low position
        # bits), so one XOR recovers the original key and a capacity-sized
        # generic re-build coalesces the <= 2**salt_bits entries per key —
        # exact counts, the true minimum first occurrence, dropped_*
        # carried through.  Noise next to the stream sort it de-skews.
        smask = jnp.uint32((1 << salt_bits) - 1)
        live = table.key_hi != sent
        desalted_lo = jnp.where(live, table.key_lo ^ (table.pos_lo & smask),
                                table.key_lo)
        table = _build(table.key_hi, desalted_lo, table.pos_hi, table.pos_lo,
                       table.count, table.count_hi, table.length, capacity,
                       table.dropped_uniques, table.dropped_uniques_hi,
                       table.dropped_count, table.dropped_count_hi)
    if not rescue_slots:
        return table
    # Poison-segment head (reserved key (sent, sent-1), immediately before
    # the dead-filler segment): poison rows are position-ordered there — by
    # the third sort key under sort3, by input order under stable2.  A
    # slice shorter than the segment (poisons beyond R) loses only the
    # LARGEST positions — rescue order is deterministic.  When fewer than R
    # poisons exist the slice runs into filler rows (all-ones packed) or,
    # when clamped at the array end, real-key rows; both carry nonzero
    # length bits the consumer masks off.
    r = min(rescue_slots, n)
    s0 = _first_key_geq(key_hi, key_lo, sent, sent - jnp.uint32(1))
    start = jnp.minimum(s0, jnp.int32(n - r))
    rescue_packed = jax.lax.dynamic_slice(packed, (start,), (r,))
    return table, rescue_packed


def _from_stream_packed(stream: TokenStream, capacity: int,
                        pos_hi: jax.Array | int,
                        sort_mode: str = "sort3", rescue_slots: int = 0,
                        sort_impl: str = "xla", salt_bits: int = 0,
                        radix_geometry: tuple | None = None):
    """Packed fast path for token streams: see :func:`from_packed_rows`."""
    # Packed-plane-carrying streams (the pallas kernel's PackedTokenStream)
    # feed their raw plane straight into the sort — repacking from
    # pos/length would re-stream ~67 MB/chunk through HBM for nothing.
    packed = getattr(stream, "packed", None)
    if packed is None:
        is_tok = stream.count > 0
        packed = jnp.where(is_tok, (stream.pos << 6) | stream.length,
                           jnp.uint32(0xFFFFFFFF))
    # Kernel-carried exact totals skip a stream-sized reduction pass.
    total = getattr(stream, "total", None)
    if total is None:
        total = jnp.sum(stream.count)
    return from_packed_rows(stream.key_hi, stream.key_lo, packed, total,
                            capacity, pos_hi, len_bits=6,
                            sort_mode=sort_mode, rescue_slots=rescue_slots,
                            sort_impl=sort_impl, salt_bits=salt_bits,
                            radix_geometry=radix_geometry)


def from_stream(stream: TokenStream, capacity: int, pos_hi: jax.Array | int = 0,
                max_token_bytes: int | None = None,
                max_pos: int | None = None,
                sort_mode: str = "sort3", rescue_slots: int = 0,
                sort_impl: str = "xla", salt_bits: int = 0,
                radix_geometry: tuple | None = None):
    """Aggregate a per-byte :class:`TokenStream` into a fresh table.

    ``pos_hi`` identifies the source buffer (e.g. ``step * n_devices +
    device_index``) so first-occurrence order is globally meaningful.

    ``max_token_bytes`` / ``max_pos`` are optional static bounds on the
    stream's length and pos fields.  When both fit a packed uint32
    (len <= 63, pos < 2**26 — true for the pallas backend's bounded-W
    streams over chunks <= 64 MB), a sort-lean fast path runs instead of
    the generic build; results are identical.  ``sort_mode`` picks that
    path's sort strategy (:func:`from_packed_rows`); ``rescue_slots`` (fast
    path only — the generic build has no poison rows to extract) makes the
    return ``(table, rescue_packed)``.  ``sort_impl`` picks the fast
    path's sort implementation (:func:`from_packed_rows`); the generic
    7-array build below keeps ``lax.sort`` — the radix seam covers the
    packed stream, which is the measured single-chip floor.  ``salt_bits``
    (fast path only, ``Config.combiner='salt'``) spreads hot keys over
    salted sort segments with an exact de-salting re-reduce
    (:func:`from_packed_rows`).  ``radix_geometry`` (ISSUE 12) is an
    explicit (bits, block_rows, slab_slack) candidate for the radix
    implementations; None keeps the module defaults.
    """
    if (max_token_bytes is not None and max_token_bytes <= 63
            and max_pos is not None and max_pos <= (1 << 26)):
        return _from_stream_packed(stream, capacity, pos_hi, sort_mode,
                                   rescue_slots, sort_impl, salt_bits,
                                   radix_geometry)
    if rescue_slots:
        raise ValueError("rescue_slots requires the packed fast path "
                         "(bounded max_token_bytes/max_pos)")
    if salt_bits:
        raise ValueError("salt_bits applies to the packed fast path only "
                         "(the generic 7-array build has no slab "
                         "amplification to de-skew)")
    n = stream.key_hi.shape[0]
    ph = jnp.full((n,), jnp.asarray(pos_hi, dtype=jnp.uint32))
    ph = jnp.where(stream.count > 0, ph, jnp.uint32(constants.POS_INF))
    z = jnp.uint32(0)
    return _build(stream.key_hi, stream.key_lo, ph, stream.pos, stream.count,
                  jnp.zeros_like(stream.count), stream.length, capacity,
                  z, z, z, z)


def merge(a: CountTable, b: CountTable, capacity: int | None = None,
          c: CountTable | None = None) -> CountTable:
    """Associative, commutative merge of two tables (the combiner).

    Exploits the table invariant (keys unique within each input) that a
    generic stream reduce cannot: after concat + sort, every key segment has
    at most TWO rows, so the group-by collapses to elementwise pair-folding
    — fold the follower's count into its head, sentinel the follower, and
    one more sort pushes the holes to the tail.  No segment ranks, no
    ``searchsorted`` (whose while-loop + fixed-cost device copies made the
    per-step combine the single most expensive stage on the bench chip:
    ~130 ms/chunk at 256K capacity, vs two ~5 ms sorts here).

    An optional THIRD table ``c`` folds in the same two sorts (runs grow to
    at most three rows; the fold checks one more neighbor — a few extra
    elementwise planes, no extra sort).  The streamed stable2 path uses
    this to fold the per-chunk seam table into the per-step running merge
    for ~free, where a dedicated pairwise seam merge cost two extra
    (capacity + 8K)-row sorts per chunk.
    """
    tables = [a, b] + ([c] if c is not None else [])
    cap = capacity if capacity is not None \
        else max(t.capacity for t in tables)
    sent = jnp.uint32(constants.SENTINEL_KEY)
    inf = jnp.uint32(constants.POS_INF)
    cat = lambda f: jnp.concatenate([getattr(t, f) for t in tables])
    key_hi, key_lo, pos_hi, pos_lo, count, count_hi, length = jax.lax.sort(
        (cat("key_hi"), cat("key_lo"), cat("pos_hi"), cat("pos_lo"),
         cat("count"), cat("count_hi"), cat("length")),
        num_keys=4,  # (key, pos): the head row of a run carries first occurrence
    )

    eq_next = (key_hi[1:] == key_hi[:-1]) & (key_lo[1:] == key_lo[:-1])
    false1 = jnp.zeros((1,), jnp.bool_)
    follower = jnp.concatenate([false1, eq_next])  # same key as previous row
    has_next = jnp.concatenate([eq_next, false1])  # next row is my follower
    zero1 = jnp.zeros((1,), jnp.uint32)
    next_count = jnp.concatenate([count[1:], zero1])
    next_count_hi = jnp.concatenate([count_hi[1:], zero1])

    is_empty = (key_hi == sent) & (key_lo == sent)
    head = ~follower & ~is_empty & ((count | count_hi) > 0)
    folded_lo, folded_hi = add64(count, count_hi,
                                 jnp.where(has_next, next_count, jnp.uint32(0)),
                                 jnp.where(has_next, next_count_hi, jnp.uint32(0)))
    if c is not None:
        # Three inputs: a key can run three rows; the head also absorbs its
        # follower's follower.  (head & has_next2) implies rows head+1 and
        # head+2 both carry the head's key.
        false2 = jnp.zeros((2,), jnp.bool_)
        zero2 = jnp.zeros((2,), jnp.uint32)
        eq_next2 = eq_next[1:] & eq_next[:-1]  # row i == i+1 == i+2
        has_next2 = jnp.concatenate([eq_next2, false2])
        folded_lo, folded_hi = add64(
            folded_lo, folded_hi,
            jnp.where(has_next2, jnp.concatenate([count[2:], zero2]),
                      jnp.uint32(0)),
            jnp.where(has_next2, jnp.concatenate([count_hi[2:], zero2]),
                      jnp.uint32(0)))
    count_m = jnp.where(head, folded_lo, jnp.uint32(0))
    count_hi_m = jnp.where(head, folded_hi, jnp.uint32(0))
    key_hi_m = jnp.where(head, key_hi, sent)
    key_lo_m = jnp.where(head, key_lo, sent)
    pos_hi_m = jnp.where(head, pos_hi, inf)
    pos_lo_m = jnp.where(head, pos_lo, inf)
    len_m = jnp.where(head, length, jnp.uint32(0))

    # Second sort: unique live keys ascending, sentinel holes to the tail;
    # the first `cap` rows are the result (spill = largest keys, matching the
    # rank-based reduce's drop order).
    key_hi_s, key_lo_s, count_s, count_hi_s, pos_hi_s, pos_lo_s, len_s = \
        jax.lax.sort((key_hi_m, key_lo_m, count_m, count_hi_m, pos_hi_m,
                      pos_lo_m, len_m), num_keys=2)
    n = key_hi_s.shape[0]
    if n < cap:  # explicit capacity above the inputs' sum: pad with holes
        pad = cap - n
        key_hi_s = jnp.concatenate([key_hi_s, jnp.full((pad,), sent)])
        key_lo_s = jnp.concatenate([key_lo_s, jnp.full((pad,), sent)])
        count_s = jnp.concatenate([count_s, jnp.zeros((pad,), jnp.uint32)])
        count_hi_s = jnp.concatenate([count_hi_s, jnp.zeros((pad,), jnp.uint32)])
        pos_hi_s = jnp.concatenate([pos_hi_s, jnp.full((pad,), inf)])
        pos_lo_s = jnp.concatenate([pos_lo_s, jnp.full((pad,), inf)])
        len_s = jnp.concatenate([len_s, jnp.zeros((pad,), jnp.uint32)])

    kept_lo, kept_hi = count_s[:cap], count_hi_s[:cap]
    n_live = jnp.sum(head.astype(jnp.uint32))
    spilled_uniques = jnp.where(n_live > jnp.uint32(cap),
                                n_live - jnp.uint32(cap), jnp.uint32(0))
    spill_lo, spill_hi = _sub64(*sum64(count, count_hi),
                                *sum64(kept_lo, kept_hi))
    # Every input's carried dropped_* folds in — including the optional
    # third table's (dropping c's carries would silently break occurrence
    # conservation whenever a seam table arrives with nonzero accounting).
    du_lo = du_hi = dc_lo = dc_hi = jnp.uint32(0)
    for t in tables:
        du_lo, du_hi = add64(du_lo, du_hi,
                             t.dropped_uniques, t.dropped_uniques_hi)
        dc_lo, dc_hi = add64(dc_lo, dc_hi,
                             t.dropped_count, t.dropped_count_hi)
    du_lo, du_hi = add64(du_lo, du_hi, spilled_uniques, jnp.uint32(0))
    dc_lo, dc_hi = add64(dc_lo, dc_hi, spill_lo, spill_hi)
    return CountTable(
        key_hi=key_hi_s[:cap], key_lo=key_lo_s[:cap],
        count=kept_lo, count_hi=kept_hi,
        pos_hi=pos_hi_s[:cap], pos_lo=pos_lo_s[:cap], length=len_s[:cap],
        dropped_uniques=du_lo, dropped_count=dc_lo,
        dropped_uniques_hi=du_hi, dropped_count_hi=dc_hi,
    )


def merge_batched(table: CountTable, pend_key_hi, pend_key_lo, pend_count,
                  pend_pos_hi, pend_pos_lo, pend_length,
                  capacity: int) -> CountTable:
    """Fold K staged batch tables + the running table in ONE sort + segment
    reduce (``Config.merge_every``): 2*K pairwise-merge sorts of
    (capacity + batch) rows become one 4-key sort of (capacity + K*batch).

    The pending arrays hold up to K batch tables' rows (flushed slots carry
    sentinel keys / zero counts, which the reduce ignores by construction).
    Kept keys, their counts, first-occurrence positions, ``dropped_count``
    and totals are identical to the pairwise fold — the kept set is the
    smallest-``capacity`` distinct keys of the union either way;
    ``dropped_uniques`` can only be TIGHTER (a respilled key counts once
    per flush, not once per step).
    """
    cat = lambda a, b: jnp.concatenate([a, b])
    # Pending rows are staged BATCH-table rows (single-chunk builds), whose
    # hi lanes are structurally zero; only the running table's hi lane
    # carries real bits into the fold.
    return _build(cat(table.key_hi, pend_key_hi),
                  cat(table.key_lo, pend_key_lo),
                  cat(table.pos_hi, pend_pos_hi),
                  cat(table.pos_lo, pend_pos_lo),
                  cat(table.count, pend_count),
                  cat(table.count_hi, jnp.zeros_like(pend_count)),
                  cat(table.length, pend_length),
                  capacity, table.dropped_uniques, table.dropped_uniques_hi,
                  table.dropped_count, table.dropped_count_hi)


def update(table: CountTable, stream: TokenStream, batch_capacity: int,
           pos_hi: jax.Array | int = 0) -> CountTable:
    """Fold one chunk's tokens into the running table (one streaming step)."""
    batch = from_stream(stream, batch_capacity, pos_hi=pos_hi)
    return merge(table, batch, capacity=table.capacity)


def kmv_distinct(table: CountTable) -> float | None:
    """Distinct-count estimate for a FULL table, free of device work.

    Spill order is deterministic (largest keys drop first, in batch builds
    and merges alike), so a full table's kept keys are exactly the
    ``capacity`` smallest distinct 64-bit key hashes ever seen — i.e. the
    table doubles as a k-minimum-values sketch with k = capacity.  The
    classic KMV estimator ``(k-1) / U_(k)`` (``U_(k)`` = the k-th smallest
    hash as a fraction of the hash space) then estimates total distinct
    hashed keys with relative error ~1/sqrt(k) — 0.2% at the default 256K
    capacity, versus the summed per-chunk upper bound ``dropped_uniques``
    degrades to.  Returns None when the table is not full (distinct is
    exact then, no estimate needed).  Host-side only: call on a fetched
    (numpy-leaf) table.

    Caveat: estimates distinct *hashed* words — on the pallas backend,
    >W-byte tokens never hash, so their distinct count (folded into
    ``dropped_uniques``'s bound) is not part of the estimate.
    """
    occ = (np.asarray(table.count) > 0) | (np.asarray(table.count_hi) > 0)
    n_valid = int(occ.sum())
    if n_valid < 1:
        return None
    return kmv_from_snapshot(n_valid,
                             int(np.asarray(table.key_hi)[n_valid - 1]),
                             int(np.asarray(table.key_lo)[n_valid - 1]),
                             table.capacity)


def kmv_snapshot(table: CountTable) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Device-side ``(n_valid, kth_key_hi, kth_key_lo)`` of a key-sorted
    table — everything :func:`kmv_distinct` needs, captured as three scalars.

    Taken BEFORE a terminal :func:`top_k` reorder (which destroys the KMV
    property: the kept keys stop being the smallest ever seen), so top-k
    finalized runs keep a ~1/sqrt(capacity)-error distinct estimate instead
    of degrading to the summed ``dropped_uniques`` upper bound
    (round-3 review weak #6).  Fetch the scalars and feed
    :func:`kmv_from_snapshot` host-side.
    """
    occ = table.occupied()
    n_valid = jnp.sum(occ.astype(jnp.uint32))
    last = jnp.maximum(n_valid.astype(jnp.int32) - 1, 0)
    return n_valid, table.key_hi[last], table.key_lo[last]


def kmv_from_snapshot(n_valid: int, kth_hi: int, kth_lo: int,
                      capacity: int) -> float | None:
    """Host-side KMV estimate from :func:`kmv_snapshot` scalars (None when
    the table was not full — distinct is exact then, no estimate needed)."""
    if n_valid < capacity or n_valid < 2:
        return None
    kth = (int(kth_hi) << 32) | int(kth_lo)
    if kth <= 0:
        return None
    return (n_valid - 1) * float(1 << 64) / float(kth)


def top_k(table: CountTable, k: int) -> CountTable:
    """The k most frequent keys, as a count-descending table of capacity k.

    A *terminal* op: the result is sorted by count, not by key, so it must not
    be merged further.  Evicted entries are folded into ``dropped_*`` so
    ``total_count()`` remains exact (total tokens, not just the top-k's).
    Ties break by first occurrence (ascending ``pos``), matching the host-side
    :func:`mapreduce_tpu.models.wordcount.apply_top_k` so streamed and
    single-buffer runs report identical word sets.
    """
    # Count-descending = ascending bitwise complement, hi lane primary
    # (lexsort's LAST key is the most significant).
    neg_lo = jnp.uint32(0xFFFFFFFF) - table.count
    neg_hi = jnp.uint32(0xFFFFFFFF) - table.count_hi
    order = jnp.lexsort((table.pos_lo, table.pos_hi, neg_lo, neg_hi))[:k]
    take = lambda f: f[order]
    kept_lo, kept_hi = take(table.count), take(table.count_hi)
    ev_lo, ev_hi = _sub64(*sum64(table.count, table.count_hi),
                          *sum64(kept_lo, kept_hi))
    evicted_uniques = table.n_valid() \
        - jnp.sum(((kept_lo | kept_hi) > 0).astype(jnp.uint32))
    du_lo, du_hi = add64(table.dropped_uniques, table.dropped_uniques_hi,
                         evicted_uniques, jnp.uint32(0))
    dc_lo, dc_hi = add64(table.dropped_count, table.dropped_count_hi,
                         ev_lo, ev_hi)
    return CountTable(
        key_hi=take(table.key_hi), key_lo=take(table.key_lo),
        count=kept_lo, count_hi=kept_hi,
        pos_hi=take(table.pos_hi), pos_lo=take(table.pos_lo), length=take(table.length),
        dropped_uniques=du_lo, dropped_count=dc_lo,
        dropped_uniques_hi=du_hi, dropped_count_hi=dc_hi,
    )
