"""Runtime configuration.

The reference has no flag system: ``argv`` is ignored (``main.cu:164``) and
every capacity is a compile-time ``#define`` (``main.cu:9-15``).  Here all
sizing is a runtime dataclass; shapes are static *per compiled step* (an XLA
requirement) but chosen freely per run.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


#: Salt width for combiner='salt': one hot key spreads over 2**3 = 8 sort
#: segments while the de-salt coalesce's collision envelope stays a
#: single-digit multiple of the documented 64-bit key envelope.
COMBINER_SALT_BITS = 3

#: The collective merge strategies the runtime builds, mirrored here so
#: Config stays jax-free (parallel/collectives.py STRATEGIES is the
#: source of truth; the bijection is test-pinned in test_collective.py).
MERGE_STRATEGIES = ("tree", "gather", "keyrange", "hier-kr-tree",
                    "hier-tree-tree")


@dataclasses.dataclass(frozen=True)
class Geometry:
    """One complete set of Pallas kernel geometries (ISSUE 12).

    Every field was a hand-picked constant scattered across the kernel
    wrappers until PR 12; collecting them in one validated, hashable
    dataclass is what makes the geometries *searchable*: the jax-free
    enumerator (``mapreduce_tpu/analysis/geometry.py``) walks candidate
    values over the tile-aligned lattice, the vmem/cost certifiers price
    and gate each candidate, and ``Config.geometry`` threads a certified
    winner to every kernel call site and ``vmem_plan`` metadata hook.

    The defaults ARE the shipped constants — a default ``Geometry()``
    reproduces today's kernels bit-for-bit (tested against the checked-in
    ``production_plans`` footprints).  Validation mirrors the kernel
    wrappers' envelopes so an off-lattice candidate fails at construction,
    not mid-trace; the *budget* gate (can the footprint fit VMEM?) is
    deliberately NOT here — that is the certifier's job, and the bounds
    below only encode tile alignment and packing-format limits.
    """

    #: stable2 lane-major compact window height in byte rows.  Multiple of
    #: 128: the fused path's raw lane-view input block is (LANES,
    #: block_rows) and Mosaic needs the minor block dim 128-divisible.
    block_rows: int = 384
    #: Slots per stable2 window.  Pinned to 128 — the only chip-validated
    #: lane-major value (the transposed output block puts SLOTS in the
    #: 128-divisible minor dim; S=120 failed lowering, round-4 chip notes).
    compact_slots: int = 128
    #: sort3 compact window height / slot budget (the round-4 shipped
    #: 256/88: 88 covers every measured density at 256 rows).
    sort3_block_rows: int = 256
    sort3_slots: int = 88
    #: Pair-resolution (spill-fallback / full-resolution) window height.
    pair_block_rows: int = 256
    #: Window height when the hot-key combiner runs (the cache absorbs
    #: the dominant duplicates, paying for taller windows — PR 11).
    combiner_block_rows: int = 512
    #: Per-lane hot-key cache entries (whole (8, 128) tiles).
    combiner_slots: int = 8
    #: Fused seam-carry aux plane rows (uint8 tile grid: multiple of 32;
    #: the head row is pinned at 64 = the W <= 63 bound, so 96 is the
    #: smallest tile-aligned plane that holds it).
    aux_rows: int = 96

    def __post_init__(self) -> None:
        for name in ("block_rows", "combiner_block_rows", "pair_block_rows"):
            v = getattr(self, name)
            if v % 128 or not 128 <= v <= (1 << 20):
                raise ValueError(
                    f"{name} must be a multiple of 128 in [128, 2**20] "
                    f"(the fused lane-view block puts rows in the "
                    f"128-divisible minor dim), got {v}")
        if self.compact_slots != 128:
            raise ValueError(
                "compact_slots must be 128 (the only chip-validated "
                "lane-major slot count: the transposed output block puts "
                f"slots in the 128-divisible minor dim), got "
                f"{self.compact_slots}")
        if self.block_rows < 2 * self.compact_slots:
            raise ValueError(
                f"block_rows {self.block_rows} must be >= 2 * "
                f"compact_slots ({2 * self.compact_slots}): the kernel's "
                "pairwise fold emits at most block_rows/2 live rows")
        if self.combiner_block_rows < 2 * self.compact_slots:
            raise ValueError(
                f"combiner_block_rows {self.combiner_block_rows} must be "
                f">= 2 * compact_slots ({2 * self.compact_slots})")
        if self.sort3_block_rows % 32 \
                or not 64 <= self.sort3_block_rows <= (1 << 20):
            raise ValueError(
                f"sort3_block_rows must be a multiple of 32 in "
                f"[64, 2**20] (uint8 sublane tile), got "
                f"{self.sort3_block_rows}")
        if self.sort3_slots % 8 \
                or not 8 <= self.sort3_slots <= self.sort3_block_rows // 2:
            raise ValueError(
                f"sort3_slots must be a multiple of 8 in [8, "
                f"sort3_block_rows/2={self.sort3_block_rows // 2}], got "
                f"{self.sort3_slots}")
        if self.combiner_slots % 8 or not 8 <= self.combiner_slots <= 32:
            raise ValueError(
                f"combiner_slots must be a multiple of 8 in [8, 32], got "
                f"{self.combiner_slots}")
        if self.aux_rows % 32 or not 96 <= self.aux_rows <= 512:
            raise ValueError(
                f"aux_rows must be a multiple of 32 in [96, 512] (the "
                "pinned head row at 64 needs the plane past it), got "
                f"{self.aux_rows}")

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


DEFAULT_GEOMETRY = Geometry()

#: Named geometry presets: the certified profiles a string
#: ``Config.geometry`` (and the tuner's ``geometry`` knob) can name.
#: 'tall512' is the PR-11 measured pair's other arm — 512-row windows at
#: the same 128 slots WITHOUT the combiner: −25% stable2 sort rows per
#: 32 MB chunk, at a spill risk on dense corpora the exact fallback
#: absorbs (the round-11 dead-end branch, now probe-able instead of
#: hand-written).  'combiner16' doubles the hot-key cache depth.
GEOMETRY_PRESETS = {
    "default": DEFAULT_GEOMETRY,
    "tall512": Geometry(block_rows=512),
    "combiner16": Geometry(combiner_slots=16),
}


@dataclasses.dataclass(frozen=True)
class Config:
    """Sizing and execution knobs for a MapReduce run.

    Attributes:
      chunk_bytes: bytes per device step per device.  The unit of streaming;
        each jitted step consumes this many bytes on every device.  Must be a
        multiple of 128 for TPU lane alignment.  Default 32 MB: the measured
        sweet spot on v5e (round-4 chip notes: 64 MB chunks LOSE ~15-40%
        end-to-end — sort cost is superlinear in rows and HBM pressure grows —
        and 1 MB chunks leave dispatch overhead unamortized).  Single-buffer
        entry points never pad small inputs up to this (padding is to the
        input's own length), so the default only shapes streamed runs.
      table_capacity: distinct keys the running count table can hold (per
        final table).  Beyond this, rarest-by-arrival keys spill and are
        tallied in ``dropped_*`` diagnostics rather than silently corrupting
        memory like the reference does past MAX_OUTPUT_COUNT (main.cu:103-104).
      batch_unique_capacity: distinct keys extracted from one step's chunk
        before merging into the table.  Bounded by tokens-per-chunk; a chunk of
        N bytes has at most ceil(N/2) tokens.
      mesh_axis: name of the data-parallel mesh axis.
      backend: map-phase implementation — 'xla' (segmented associative scan,
        any token length), 'pallas' (fused single-pass TPU kernel; tokens
        longer than ``pallas_max_token`` bytes are dropped into ``dropped_*``
        accounting rather than counted), or 'auto' (the default: pallas on
        TPU when the chunk is large enough for its seam windows, xla
        elsewhere).  'auto' exists because the associative-scan formulation,
        while fine on CPU and for small shapes, compiles pathologically
        slowly on real TPU at multi-MB chunk sizes — the fused kernel is the
        TPU path.
      pallas_max_token: W for the pallas backend's on-chip lookback window.
      superstep: chunks folded into ONE dispatch per device via ``lax.scan``
        (Engine.step_many).  >1 amortizes per-dispatch overhead — decisive on
        high-latency device links — at the cost of staging superstep *
        chunk_bytes input per device per dispatch.  Default 1 (lowest memory,
        per-step checkpoint granularity); where dispatch latency dominates
        raise it toward resident-corpus size — bench.py's timed window uses
        exactly that.
    """

    chunk_bytes: int = 1 << 25
    table_capacity: int = 1 << 18
    batch_unique_capacity: Optional[int] = None
    mesh_axis: str = "data"
    backend: str = "auto"
    pallas_max_token: int = 32
    superstep: int = 1
    # Sketched runs (HLL/CMS): fold per-chunk sketch updates into a pending
    # buffer and scatter once every K steps.  TPU scatters carry a large
    # fixed cost regardless of size (round-4/5 chip notes), so K amortizes it K-fold
    # at the price of K * batch_uniques rows of extra device state.  1 =
    # scatter every step (the round-1 behavior).
    sketch_flush_every: int = 1
    # Fold per-chunk batch tables into the running table once every K steps
    # instead of every step: batches stage into a pending buffer (cheap
    # dynamic_update_slice) and ONE K-way sort+segment-reduce replaces K
    # pairwise merges — 2*K sorts of (capacity + batch) rows become one
    # sort of (capacity + K*batch), a ~2x cut of the merge share of the
    # chunk budget at K >= 4 (sorts cost ~3 ms/M rows/array, round-4/5 chip notes).
    # Kept keys and their counts, dropped_count, and totals are identical
    # to K=1; only the dropped_uniques upper bound can differ under spill
    # (a key respilled in several steps is counted once per flush, not once
    # per step — a TIGHTER bound).  Costs K * batch_uniques * 6 words of
    # device state.  1 = merge every step.
    merge_every: int = 1
    # Aggregation sort strategy for the packed fast path (the pallas
    # wordcount family and the packed gram build on both backends; the xla
    # wordcount path runs the generic 7-array build, where it does not
    # apply).  Both modes sort with jax.lax.sort and give identical
    # results; they differ in the comparator and in the kernel layout they
    # need.  'stable2' (default): the compact kernel writes its planes
    # LANE-MAJOR, so the flattened stream is in global byte-position order,
    # and a STABLE sort on the two key lanes alone recovers each key's
    # first occurrence from tie order (the comparator-width cut measured
    # ~40% of the sort's compute, round-4 chip notes).  Windows are
    # block_rows=384 with 128 slots (measured spill-free: max 114 ends per
    # 384-byte window on Zipf, tools/density.py), whose transposed (128,
    # 128) output blocks are tile-aligned stores.  It is the default
    # because it won the round-5 on-chip A/B against sort3 on every bench
    # corpus (Zipf 0.4263 vs 0.4024 GB/s).  'sort3': `packed` (the
    # token's position and length) is a third sort key, so a segment's
    # head row is its first occurrence whatever the stream's order; the
    # compact kernel then writes slot-major 256-row windows with 88 slots.
    # Pair-layout and concatenated (column + seam) streams are not
    # position-ordered, so the map's full-resolution path and the spill
    # fallback sort with sort3 in either mode.
    sort_mode: str = "stable2"
    # Map-phase IMPLEMENTATION for the pallas backend (ISSUE 6) — the seam
    # between "tokenize with an XLA fix-up chain" and "one fused kernel".
    # 'split' (default): the round-4/5 shipped path — the compact kernel
    # emits column planes, 128-lane-seam tokens are re-tokenized by an XLA
    # scan over 129 seam windows (a second read of seam bytes from HBM +
    # a per-chunk seam-table merge), and the input is transposed/padded to
    # the column view in XLA before the kernel (two more materializing
    # passes over the chunk).  'fused' consumes the RAW lane view and
    # resolves lane seams IN-KERNEL from a small seam-carry plane
    # (ops/pallas/tokenize.tokenize_fused): tokenize -> hash -> window
    # compaction in one pallas_call, one stream straight into the
    # aggregation sort — no token-plane fix-up round-trip.  Results are
    # bit-identical (tests/test_fused.py), overlong-rescue and the
    # spill->exact fallback included (the fused fallback is the same
    # kernel in pair mode).  The costcheck hbm-cost pass prices the gap
    # and ERROR-gates `wordcount_fused` strictly below the split baseline;
    # 'split' stays default until an on-chip window confirms the predicted
    # win (round-9 chip notes).  Applies
    # to the pallas map paths (wordcount family + n-grams); the xla
    # backend has no kernel to fuse and ignores it.
    map_impl: str = "split"
    # Slot-compact the pallas kernel's column planes to S output rows per
    # block_rows-byte (block, lane) window instead of the pair path's
    # block_rows/2 (round-4 review #2: the sort floor is row-count-bound).  At
    # the default block_rows=256, S=88 cuts the sorted stream 1.45x and
    # covers every window density measured on the bench corpora
    # (tools/density.py: observed max 77 ends / 256 bytes on Zipf, 52 on
    # natural text).  Denser windows (adversarial single-letter runs) spill;
    # the map then falls back to the full-resolution path for that chunk
    # under a lax.cond — always exact, ~2x cost on such chunks.  None
    # (default) resolves to 88: measured on the chip 2026-07-31, compaction
    # wins the identical workload 0.3235 vs 0.2584 GB/s (+25%) end-to-end.
    # 0 = off (the round-3 pair path).  Ignored by the xla backend and the
    # n-gram family (position-ordered consumers keep full resolution).
    compact_slots: Optional[int] = None
    # Overlong-token rescue (pallas backend only; round-3 review #6): re-hash up
    # to this many >W-byte tokens per chunk exactly, via bounded XLA windows
    # at the kernel's poison positions (ops/rescue.py), so TPU runs agree
    # with the XLA backend on natural web-ish text (URLs/markup: ~0.3% of
    # tokens, ~15K per 32 MB chunk on the webby proxy — tools/overlong.py).
    # Guarded by lax.cond(overlong > 0): overlong-free corpora (both bench
    # generators) never pay.  Residuals (counts past the budget, tokens
    # longer than rescue_window - 1) stay in dropped_* accounting.  0 = off
    # (the round-3 behavior).  None (default) resolves to 1024.
    rescue_overlong: Optional[int] = None
    # Rescue lookback bound in bytes: tokens up to rescue_window - 1 bytes
    # are rescued exactly.  192 covers p99.9 of webby-proxy token lengths
    # (151 bytes); raise toward 320+ for URL-heavy corpora.
    rescue_window: int = 192
    # Streaming dispatch window (ISSUE 5): how many superstep groups may be
    # dispatched-but-unretired at once.  >1 pipelines the stream — reader,
    # host staging, async H2D, and device compute of DIFFERENT groups
    # overlap, and the executor blocks only when the window is full (or at
    # checkpoint/file boundaries, where it drains) instead of eagerly per
    # dispatch.  1 = strict serial (the safe fallback and the A/B control:
    # dispatch -> retire -> next group).  With retry > 0 that reproduces
    # the pre-window loop exactly (it synced every dispatch); the retry=0
    # pre-window loop instead rode the device queue's own backpressure
    # (async, no per-group sync), so 1 there is a strictly-more-serial
    # control, not a bug-for-bug baseline.  With
    # retry > 0 the window also sets the replay granularity: known-good
    # snapshots move from per-group to window-drain points, so a mid-window
    # failure replays at most the window (checkpoint boundaries still force
    # a drain, keeping resume replay bounded by checkpoint_every).  Memory
    # cost: up to inflight_groups * superstep * chunk_bytes of staged input
    # per device kept live.
    inflight_groups: int = 4
    # Reader prefetch depth (batches the background reader may run ahead),
    # co-tuned with the window: None (default) resolves to
    # superstep * inflight_groups clamped to [2, 16] — enough host-side
    # batches to keep a full window fed without unbounded buffering.
    prefetch_depth: Optional[int] = None
    # Closed-loop autotuner mode (ISSUE 10).  'off' (default): the knobs
    # above are what you set.  'hint': the executor feeds the run's OWN
    # ledger telemetry (the PR-7 `bottleneck` verdict, the PR-8
    # `data_health` verdict, the window statistics) through the jax-free
    # rule engine in mapreduce_tpu/tuning/ and folds the recommended next
    # config for inflight_groups / prefetch_depth / superstep /
    # chunk_bytes into a `tune` ledger record (ledger v4) and the run
    # summary — the LIVE run is never changed (apply a hint by re-running
    # with the proposed flags, or let tools/autotune.py walk the loop
    # offline).  Hints are a host-local-driver feature like retry and
    # data stats: run_job_global ignores the knob.
    autotune: str = "off"
    # Skew-adaptive map-side combiner (ISSUE 11, ROADMAP item 5): what to
    # do about Zipf-hot keys BEFORE the aggregation sort sees them.
    # 'off' (default): the shipped behavior.  'hot-cache': the fused
    # compact kernel threads a small VMEM-resident hot-key cache through
    # the tile grid (the seam-carry idiom) — per lane, the first
    # ``combiner_slots`` distinct keys are cached, every further
    # occurrence of a cached key is counted IN VMEM and emits nothing,
    # and at chunk end the cache flushes one exact (key, count,
    # first-occurrence) row per resident entry into a tiny table merged
    # with the chunk's batch table.  On Zipf streams the dominant
    # duplicate runs collapse before the stable2 sort materializes them,
    # which pays for a taller kernel window (block_rows 384 -> 512 at the
    # same 128 slots: ~25% fewer sort rows per chunk at the production
    # geometry, priced and ERROR-gated by the costcheck combiner gate);
    # denser-than-budget windows keep the exact spill fallback, so
    # results stay bit-identical to 'off' on EVERY distribution.  Applies
    # to the fused pallas compact path (map_impl='fused'); elsewhere it
    # is a documented no-op, like compact_slots on the xla backend.
    # 'salt': key-salting for pathological single-key streams — the
    # packed table build XORs low position bits into key_lo so one
    # scorching key spreads over 2**COMBINER_SALT_BITS sort segments,
    # then de-salts and re-reduces the capacity-sized table
    # exactly at the reduce seam.  Envelope (ops/table.from_packed_rows
    # documents both legs): exact de-salting widens the documented
    # ~n^2/2^65 64-bit key-collision envelope by the salt factor (8x at
    # the default 3 bits; --verify-sample detects as ever — the
    # single-key streams salting exists for cannot collide at all), and
    # bit-identity to 'off' holds while distinct keys fit the batch
    # capacity (under unique overflow the cutoff falls on salted key
    # order; occurrence totals stay conserved via dropped accounting).
    # Applies to the packed fast path (pallas wordcount family + gram
    # builds on both backends), the sort_mode scope.
    # 'auto': resolve from the PREVIOUS run's data-health verdict — the
    # first config knob chosen by the data, not the operator: skew-hot ->
    # 'hot-cache', anything else (or no ledger history) -> 'off'.  The
    # CLI resolves it against --ledger's existing records before any
    # trace (obs/datahealth.resolve_combiner); an unresolved 'auto'
    # (library callers that never resolve) behaves as 'off'.  The
    # autotuner's `skew-hot -> enable-combiner` rule proposes the same
    # flip from measured ledgers (mapreduce_tpu/tuning/).
    combiner: str = "off"
    # Per-lane hot-key cache entries for combiner='hot-cache' (multiple
    # of 8 for sublane tiling, in [8, 32]).  None resolves to 8: the
    # cache planes stay one (8, 128) tile each, and on Zipf the top
    # handful of keys carries the collapsible mass (PR 8's top_mass
    # proxy measures exactly this).
    combiner_slots: Optional[int] = None
    # Kernel-geometry override (ISSUE 12): which certified set of Pallas
    # kernel geometries this run compiles.  None (default) = the shipped
    # constants (``DEFAULT_GEOMETRY`` — today's kernels bit-for-bit).  A
    # ``Geometry`` instance or a plain dict of its fields (validated and
    # frozen at construction) = an explicit candidate, e.g. one the
    # geometry search shortlisted (tools/geomsearch.py); a preset name
    # from ``GEOMETRY_PRESETS`` ('tall512', 'combiner16', ...) = the same
    # by name, which is how the autotuner's geometry knob round-trips
    # through ledgers and tuned.json.  'auto' = resolve from a searched
    # profile BEFORE compiling — the driver's job, like combiner='auto'
    # (the CLI resolves against tuned.json via
    # analysis/geometry.resolve_auto; an unresolved 'auto' behaves as
    # the default).  Results are BIT-IDENTICAL across certified
    # geometries (the emission set, fallback exactness and accounting
    # are geometry-independent — tested); only the cost moves, which is
    # the point.  Scope: the pallas kernel paths (wordcount family +
    # grams); the xla backend has no kernel
    # geometry and ignores it.
    geometry: object = None
    # Second-tier rescue budget (round-4 review weak #4): URL-heavy text carries
    # ~15K overlong occurrences per 32 MB chunk (tools/overlong.py) — far
    # past the 1024-slot primary budget, which silently left >90% of them
    # in dropped_* unless hand-sized.  When a chunk's overlong count
    # exceeds ``rescue_slots``, a lax.cond escalates to this many slots
    # instead (the compact path's spill-fallback idiom): clean corpora pay
    # nothing, lightly-overlong chunks pay the small pass, only genuinely
    # URL-dense chunks pay the big one.  None (default) auto-sizes to
    # chunk_bytes/1024 clamped to [rescue_slots, 65536] — 32768 at the
    # default 32 MB chunk, covering the measured webby density with 2x
    # margin.  Adversarial all-overlong text can still exceed it; the
    # residual stays exactly accounted in dropped_*, as ever.
    rescue_overlong_max: Optional[int] = None
    # Deterministic fault injection (ISSUE 15): a seeded FaultPlan spec
    # string (runtime/faults.py grammar — e.g. 'seed=42,rate=0.02' or
    # 'at=dispatch:3:resource') fired at the executor's named seams
    # (reader read, staging, H2D, dispatch, token wait, checkpoint save,
    # ledger append, collective finish, process kill).  Every fired fault
    # lands as a `fault` ledger record (ledger v9), so a chaotic run can
    # be replayed exactly from its own ledger
    # (faults.FaultPlan.from_ledger).  None (default) is the provably
    # zero-cost disabled path: the executor guards every seam check with
    # one `is not None`, nothing is traced either way, and the compiled
    # programs are bit-identical to fault-plan-free builds.  Host-side
    # only — injection never reaches a jitted program.
    fault_plan: Optional[str] = None
    # Unified failure policy (ISSUE 15): None (default) maps the driver's
    # legacy `retry=N` counter onto transient+resource budgets (the exact
    # pre-ISSUE-15 semantics); a faults.FailurePolicy (or a dict of its
    # fields) sets per-class retry budgets, the exponential-backoff +
    # deterministic-jitter schedule, the completion-token wall-clock
    # timeout (a hung device reads as a typed fault instead of a silent
    # stall), and whether resource-classed exhaustion steps down the
    # degradation ladder (revert-geometry -> combiner-off -> map-split)
    # before giving up.
    failure_policy: object = None
    # Collective merge strategy for the global reduction (ISSUE 20): a
    # name from ``MERGE_STRATEGIES`` ('tree', 'gather', 'keyrange',
    # 'hier-kr-tree', 'hier-tree-tree' — parallel/collectives.py builds
    # them, analysis/meshcost.py prices them), or 'auto' = resolve from
    # the redplan tuned.json profile BEFORE building the engine — the
    # driver's job, exactly the combiner/geometry 'auto' contract (the
    # CLI resolves via obs/history.resolve_prior; an unresolved 'auto'
    # behaves as 'tree', the incumbent).  The hierarchical placements
    # need a multi-axis mesh; the keyrange family needs a job with a
    # keyrange_merge hook — both checked by the Engine at build.
    merge_strategy: str = "tree"
    # Window-boundary collective overlap (ISSUE 20 leg 2): at every
    # window-drain/checkpoint boundary the executor drains each host's
    # local table into a resident merged accumulator with an async
    # partial collective and resets the local table, so the DCN transfer
    # of window N overlaps the ingest+compute of window N+1 and table
    # pressure stays bounded by the window.  Byte-exact to the
    # monolithic merge (commutative fold + min-position rule; chaos- and
    # gloo-pair-certified).  Requires retry=0 (the replay anchor
    # machinery snapshots the local state, which a partial merge has
    # partially shipped); each partial lands as an op='partial'
    # `collective` ledger record (ledger v10).  Off (default): the old
    # single-finish ledger shape, bit-identical programs.
    merge_overlap: bool = False

    def __post_init__(self) -> None:
        if self.chunk_bytes % 128 != 0:
            raise ValueError(f"chunk_bytes must be a multiple of 128, got {self.chunk_bytes}")
        if self.table_capacity < 2:
            raise ValueError("table_capacity must be >= 2")
        if self.sketch_flush_every < 1:
            raise ValueError(
                f"sketch_flush_every must be >= 1, got {self.sketch_flush_every}")
        if self.backend not in ("auto", "xla", "pallas"):
            raise ValueError(f"unknown backend {self.backend!r}")
        if self.sort_mode not in ("sort3", "stable2"):
            raise ValueError(f"unknown sort_mode {self.sort_mode!r}")
        if self.map_impl not in ("split", "fused"):
            raise ValueError(f"unknown map_impl {self.map_impl!r}")
        if self.sort_mode == "stable2" and self.compact_slots is not None \
                and self.compact_slots != 128:
            # Mosaic requires the last block dim divisible by 128, and the
            # lane-major layout puts SLOTS last — measured: S=120 fails at
            # lowering ("block shape ... divisible by 8 and 128").  0 (off)
            # is equally invalid: the position-ordered input stable2 needs
            # only exists on the compact lane-major path.
            raise ValueError(
                "sort_mode='stable2' requires compact_slots=128 (the "
                "lane-major kernel layout puts slots in the 128-divisible "
                "block dimension); leave compact_slots unset")
        if self.compact_slots:
            # Mirrors the kernel wrapper's envelope (fail at construction,
            # not mid-trace): sublane-aligned, within the pair-path bound.
            if self.compact_slots % 8 or not 8 <= self.compact_slots <= 128:
                raise ValueError(
                    f"compact_slots must be a multiple of 8 in [8, 128], "
                    f"got {self.compact_slots}")
        if self.merge_every < 1:
            raise ValueError(
                f"merge_every must be >= 1, got {self.merge_every}")
        if self.rescue_overlong is not None and self.rescue_overlong < 0:
            raise ValueError(
                f"rescue_overlong must be >= 0, got {self.rescue_overlong}")
        if self.rescue_overlong_max is not None \
                and self.rescue_overlong_max < 0:
            raise ValueError(f"rescue_overlong_max must be >= 0, "
                             f"got {self.rescue_overlong_max}")
        if self.rescue_slots:
            if self.backend != "xla" \
                    and self.rescue_window <= self.pallas_max_token + 1:
                raise ValueError(
                    f"rescue_window ({self.rescue_window}) must exceed "
                    f"pallas_max_token + 1 ({self.pallas_max_token + 1}) "
                    "to rescue anything")
            if self.rescue_window > 4096:
                raise ValueError(
                    f"rescue_window must be <= 4096, got {self.rescue_window}")
        if self.combiner not in ("off", "hot-cache", "salt", "auto"):
            raise ValueError(f"unknown combiner {self.combiner!r} (expected "
                             "'off', 'hot-cache', 'salt' or 'auto')")
        if self.combiner_slots is not None:
            # Mirrors the kernel wrapper's envelope (fail at construction,
            # not mid-trace): one or more whole (8, 128) cache tiles.
            if self.combiner_slots % 8 or not 8 <= self.combiner_slots <= 32:
                raise ValueError(
                    f"combiner_slots must be a multiple of 8 in [8, 32], "
                    f"got {self.combiner_slots}")
            if self.combiner not in ("hot-cache", "auto"):
                raise ValueError(
                    "combiner_slots sizes the hot-key cache; set "
                    "combiner='hot-cache' (or 'auto') to use it")
        if isinstance(self.geometry, dict):
            # Accept plain dicts (JSON-shaped candidates from tuned.json /
            # the search tools) but STORE the validated frozen dataclass:
            # Config is hashable (a static jit argument), so the field
            # must be too.
            object.__setattr__(self, "geometry", Geometry(**self.geometry))
        if isinstance(self.geometry, str):
            if self.geometry != "auto" \
                    and self.geometry not in GEOMETRY_PRESETS:
                raise ValueError(
                    f"unknown geometry {self.geometry!r} (expected 'auto', "
                    f"a preset name {sorted(GEOMETRY_PRESETS)}, a Geometry, "
                    "or a dict of its fields)")
        elif self.geometry is not None \
                and not isinstance(self.geometry, Geometry):
            raise ValueError(
                f"geometry must be None, 'auto', a preset name, a Geometry "
                f"or a dict, got {type(self.geometry).__name__}")
        if self.autotune not in ("off", "hint"):
            raise ValueError(f"unknown autotune mode {self.autotune!r} "
                             "(expected 'off' or 'hint')")
        if self.merge_strategy != "auto" \
                and self.merge_strategy not in MERGE_STRATEGIES:
            raise ValueError(
                f"unknown merge_strategy {self.merge_strategy!r} (expected "
                f"'auto' or one of {list(MERGE_STRATEGIES)})")
        if not isinstance(self.merge_overlap, bool):
            raise ValueError(
                f"merge_overlap must be a bool, got "
                f"{type(self.merge_overlap).__name__}")
        if self.superstep < 1:
            raise ValueError(f"superstep must be >= 1, got {self.superstep}")
        if self.inflight_groups < 1:
            raise ValueError(
                f"inflight_groups must be >= 1, got {self.inflight_groups}")
        if self.prefetch_depth is not None and self.prefetch_depth < 1:
            raise ValueError(
                f"prefetch_depth must be >= 1, got {self.prefetch_depth}")
        if self.backend != "xla" and not 1 <= self.pallas_max_token <= 63:
            # 'auto' may resolve to pallas at runtime; fail at construction,
            # not mid-trace inside the kernel.  The kernel packs token length
            # into 6 bits of its sort payload, so W <= 63; tokens longer than
            # W are accounted, and the xla backend handles any length exactly.
            raise ValueError(
                f"pallas_max_token must be in [1, 63], got {self.pallas_max_token}")
        if self.backend == "pallas" and self.chunk_bytes < self.pallas_min_chunk:
            # Seam windows must not overlap: lane segment >= 2W+2 bytes.
            # ('auto' instead falls back to xla for chunks this small.)
            raise ValueError(
                f"pallas backend needs chunk_bytes >= {self.pallas_min_chunk} "
                f"for pallas_max_token={self.pallas_max_token}")
        if self.backend == "pallas" and self.chunk_bytes > (1 << 26):
            # Positions pack into 26 bits of the kernel's sort payload.
            # ('auto' instead falls back to xla above this size.)
            raise ValueError(
                f"pallas backend needs chunk_bytes <= {1 << 26} (64 MB), "
                f"got {self.chunk_bytes}")
        if self.fault_plan is not None or self.failure_policy is not None:
            # Validate at construction, not mid-stream (the geometry-dict
            # discipline); runtime/faults.py is jax-free and cheap.
            from mapreduce_tpu.runtime import faults as faults_mod

            if self.fault_plan is not None:
                if not isinstance(self.fault_plan, str):
                    raise ValueError(
                        f"fault_plan must be a spec string (or None), got "
                        f"{type(self.fault_plan).__name__}")
                faults_mod.FaultPlan.from_spec(self.fault_plan)
            if isinstance(self.failure_policy, dict):
                # Accept plain dicts (JSON-shaped) but STORE the frozen
                # dataclass: Config is hashable (a static jit argument),
                # so the field must be too (the geometry precedent).
                object.__setattr__(self, "failure_policy",
                                   faults_mod.FailurePolicy(
                                       **self.failure_policy))
            elif self.failure_policy is not None and not isinstance(
                    self.failure_policy, faults_mod.FailurePolicy):
                raise ValueError(
                    f"failure_policy must be None, a FailurePolicy or a "
                    f"dict of its fields, got "
                    f"{type(self.failure_policy).__name__}")

    @property
    def rescue_slots(self) -> int:
        """The resolved overlong-rescue budget (see ``rescue_overlong``)."""
        return 1024 if self.rescue_overlong is None else self.rescue_overlong

    @property
    def rescue_slots_max(self) -> int:
        """The resolved second-tier rescue budget (>= rescue_slots; 0 when
        rescue is off).  See ``rescue_overlong_max``."""
        if not self.rescue_slots:
            return 0
        if self.rescue_overlong_max is not None:
            return max(self.rescue_overlong_max, self.rescue_slots)
        # The 64K cap bounds only the AUTO sizing; an explicit primary
        # budget above it is always honored in full (clamping below
        # rescue_slots would silently shrink what the user asked for).
        return max(min(self.chunk_bytes >> 10, 1 << 16), self.rescue_slots)

    @property
    def resolved_geometry(self) -> Geometry:
        """The :class:`Geometry` this config compiles (see ``geometry``).
        An unresolved 'auto' behaves as the default — resolution against a
        searched profile is the driver's job (CLI / tools), never the
        trace's (the combiner='auto' contract)."""
        g = self.geometry
        if g is None or g == "auto":
            return DEFAULT_GEOMETRY
        if isinstance(g, str):
            return GEOMETRY_PRESETS[g]
        return g

    @property
    def geometry_label(self) -> str:
        """Compact name for ledgers / tuned profiles: 'default', a preset
        name, or 'custom' for an explicit non-preset Geometry."""
        g = self.geometry
        if g is None or g == "auto":
            return "default"
        if isinstance(g, str):
            return g
        if g == DEFAULT_GEOMETRY:
            return "default"
        return "custom"

    @property
    def resolved_compact_slots(self) -> int:
        """The resolved slot-compaction budget (see ``compact_slots``):
        88 per 256-byte window, or 128 per 384-byte window under stable2's
        lane-major geometry (both measured spill-free, tools/density.py).
        An explicit ``compact_slots`` wins over the geometry's value (the
        legacy knob precedence)."""
        if self.compact_slots is not None:
            return self.compact_slots
        g = self.resolved_geometry
        return g.compact_slots if self.sort_mode == "stable2" \
            else g.sort3_slots

    @property
    def resolved_merge_strategy(self) -> str:
        """The merge strategy the engine actually builds (see
        ``merge_strategy``): an unresolved 'auto' behaves as 'tree' (the
        incumbent) — resolution against the redplan tuned.json profile is
        the driver's job (CLI / bench), never the engine's."""
        return "tree" if self.merge_strategy == "auto" \
            else self.merge_strategy

    @property
    def resolved_combiner(self) -> str:
        """The combiner mode the trace actually runs (see ``combiner``):
        an unresolved 'auto' behaves as 'off' — resolution against a
        prior ledger is the driver's job (CLI / tools), never the
        trace's."""
        return "off" if self.combiner == "auto" else self.combiner

    @property
    def resolved_combiner_slots(self) -> int:
        """Per-lane hot-key cache entries (0 = no cache).  Nonzero only
        where the cache exists: the fused pallas compact path under
        combiner='hot-cache'.  An explicit ``combiner_slots`` wins over
        the geometry's value (the legacy knob precedence)."""
        if self.resolved_combiner != "hot-cache" or self.map_impl != "fused" \
                or not self.resolved_compact_slots:
            return 0
        return self.combiner_slots if self.combiner_slots is not None \
            else self.resolved_geometry.combiner_slots

    @property
    def resolved_salt_bits(self) -> int:
        """Low position bits XORed into key_lo by the packed table build
        under combiner='salt' (0 = no salting)."""
        return COMBINER_SALT_BITS if self.resolved_combiner == "salt" else 0

    @property
    def resolved_block_rows(self) -> int | None:
        """Compact-kernel window height in byte rows, from the resolved
        geometry: ``block_rows`` (default 384) under stable2 — the
        transposed output block stays a tile-aligned (128, 128) store —
        or ``combiner_block_rows`` (default 512) when the hot-key
        combiner runs (the cache absorbs the dominant duplicates, so
        taller windows — ~25% fewer sort rows per chunk — stay within
        the same 128-slot budget; denser windows keep the exact spill
        fallback).  Under sort3 the geometry's ``sort3_block_rows``
        applies; None (the default 256 there) defers to the kernel's own
        default so geometry-free callers stay byte-identical."""
        g = self.resolved_geometry
        if self.sort_mode != "stable2":
            return g.sort3_block_rows \
                if g.sort3_block_rows != DEFAULT_GEOMETRY.sort3_block_rows \
                else None
        return g.combiner_block_rows if self.resolved_combiner_slots \
            else g.block_rows

    @property
    def resolved_pair_block_rows(self) -> int | None:
        """Pair-resolution (full-resolution / spill-fallback) window
        height; None defers to the kernel's default (256) so the default
        geometry traces the exact pre-ISSUE-12 programs."""
        g = self.resolved_geometry
        return g.pair_block_rows \
            if g.pair_block_rows != DEFAULT_GEOMETRY.pair_block_rows \
            else None

    @property
    def resolved_aux_rows(self) -> int | None:
        """Fused seam-carry plane rows; None defers to the kernel's
        AUX_ROWS default (96)."""
        g = self.resolved_geometry
        return g.aux_rows \
            if g.aux_rows != DEFAULT_GEOMETRY.aux_rows else None

    @property
    def resolved_prefetch_depth(self) -> int:
        """The resolved reader prefetch depth (see ``prefetch_depth``):
        deep enough to feed a full dispatch window, bounded so host memory
        stays O(window)."""
        if self.prefetch_depth is not None:
            return self.prefetch_depth
        return min(16, max(2, self.superstep * self.inflight_groups))

    @property
    def pallas_min_chunk(self) -> int:
        """Smallest chunk the pallas kernel accepts (non-overlapping seam
        windows need lane segments of >= 2W+2 bytes)."""
        return 128 * (2 * self.pallas_max_token + 2)

    def resolved_backend(self) -> str:
        """Resolve 'auto' against the runtime platform.

        Deterministic for a given process (jax.default_backend() is fixed
        once initialized), so jit caches keyed on the Config stay coherent.
        """
        if self.backend != "auto":
            return self.backend
        import jax

        if (jax.default_backend() == "tpu"
                and self.pallas_min_chunk <= self.chunk_bytes <= (1 << 26)):
            return "pallas"
        return "xla"

    @property
    def batch_uniques(self) -> int:
        if self.batch_unique_capacity is not None:
            return self.batch_unique_capacity
        # At most one token per two bytes, +1 slack for the sentinel segment.
        return min(self.chunk_bytes // 2 + 1, self.table_capacity)


DEFAULT_CONFIG = Config()

# A small config for tests / the bundled-fixture CLI path.
SMALL_CONFIG = Config(chunk_bytes=1 << 10, table_capacity=1 << 10)
