"""Pallas TPU radix partition/sort over the packed 3-plane aggregation stream.

The single-chip budget is sort-bound: the round-5 opshare puts the XLA
aggregation sort at 37-47 ms of a 72.8 ms chunk program (up to 65% of
device time) on the 11.2M-row (key_hi, key_lo, packed) stream.  This module
is the priced falsifying prototype for the one lever that analysis left
open — replacing that sort with a digit-wise radix partition — built AFTER
the pricing note (round-6 chip notes) concluded it loses ~2-3x from
measured rates.  It ships behind ``Config.sort_impl`` so an on-chip A/B can
falsify the arithmetic instead of trusting it.

Why the structure below, and not a textbook LSD radix sort
---------------------------------------------------------
A classic LSD pass needs a STABLE scatter of every row to an exact global
offset.  TPU has no hardware scatter (measured: ~30 ms fixed scatter cost,
~13 us/element gathers — the round-1 findings the whole table layer is
built around), so the reorder here is scatter-free:

1. **Partition kernel** (one grid pass, sequential on TPU): each
   ``(block_rows, 128)`` block classifies rows by a ``bits``-wide MSD digit
   of ``key_hi``, drops dead filler rows (``(sent, sent)`` keys — they are
   interchangeable by the packed-stream contract, so only their count
   matters), and per bucket log-shift-compacts the three planes (the
   chip-proven :func:`...tokenize._compact_planes`) into a STATIC
   per-(block, bucket) slab of ``cap`` rows per lane.  Per-group digit
   histograms accumulate in SMEM; a spill counter records live rows beyond
   any lane's slab budget.
2. **Per-group finishing sort**: bucket slabs are restacked bucket-major
   and each bucket (digit range) is finished with one blocked 3-key
   ``lax.sort`` — pads carry the dead triple and sink to each bucket's
   tail.
3. **Pad compaction**: ascending ``dynamic_update_slice`` writes at the
   exact cumulative real offsets; each slab exactly overwrites the previous
   slab's pad tail, so one ~slack-sized pass re-joins the stream with no
   gather.

``impl='radix_partition'`` runs one partition level (the cheapest
falsifying prototype); ``impl='radix'`` runs two digit levels before the
finishing sorts (the multi-pass path; deeper levels only compound the
slack-write amplification the pricing note quantifies, and a TRUE LSD
chain is unbuildable without stable scatter — documented there).

Exactness: static slabs can overflow under adversarial key skew (every
live row in one digit bucket).  The kernel counts spilled rows exactly and
a ``lax.cond`` falls back to the plain XLA sort — the compact-path spill
idiom — so ANY input stays bit-exact.

Contract: the result is bit-identical to
``jax.lax.sort((key_hi, key_lo, packed), num_keys=3)``.  For aggregation
this single implementation serves both ``sort_mode='sort3'`` (that IS its
definition) and ``sort_mode='stable2'`` (ties resolve by ``packed``, which
under stable2's position-ordered-input precondition is exactly the tie
order stability would deliver).  It relies on the packed-stream dead-row
contract (:func:`...ops.table.from_packed_rows`): a ``(sent, sent)``-keyed
row always carries all-ones ``packed``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mapreduce_tpu import constants
from mapreduce_tpu.ops.pallas import meta
from mapreduce_tpu.ops.pallas.tokenize import LANES, _compact_planes

DEFAULT_BITS = 3  # B = 8 buckets per level
DEFAULT_BLOCK_ROWS = 256
# Slab budget per (block, lane, bucket) as a multiple of the uniform share
# block_rows/B.  4x covers the bench Zipf head (top key ~25% of live rows
# + ~12% uniform background lands ~0.3*block_rows in ONE bucket per lane);
# heavier skew spills into the exact XLA-sort fallback.
DEFAULT_SLAB_SLACK = 4

_IMPLS = ("radix_partition", "radix")

# Analyzer contract (costcheck vmem/race passes): the partition kernel
# ALWAYS emits a spill counter — live rows beyond a lane's slab budget
# mean the slabs are incomplete and radix_sort3's lax.cond MUST fall back
# to the exact XLA sort.
meta.register(meta.KernelMeta(
    name="_partition_kernel",
    spills=lambda num_outputs: True,
    description="MSD digit partition into static slabs; adversarial "
                "bucket skew spills past the slab budget"))


def vmem_plan(bits: int = DEFAULT_BITS,
              block_rows: int = DEFAULT_BLOCK_ROWS,
              slab_slack: int = DEFAULT_SLAB_SLACK) -> meta.VmemPlan:
    """Static VMEM/SMEM footprint of one partition-kernel geometry — the
    analyzer's metadata hook (ops/pallas/meta.py).  Delegates to the
    jax-free :func:`...meta.radix_plan` constructor (ISSUE 12: one
    arithmetic for search candidates, shipped plans, and what
    :func:`_partition_level` binds)."""
    return meta.radix_plan(bits=bits, block_rows=block_rows,
                           slab_slack=slab_slack)


def _partition_kernel(khi_ref, klo_ref, pck_ref, *out_refs, shift: int,
                      bits: int, cap: int, blocks_per_group: int):
    """One grid step: bucket this block's rows by digit into static slabs.

    Outputs (positional, after the three input planes): B per-bucket
    (khi, klo, packed) slab triples, then the per-group digit histogram
    (SMEM ``(1, B)`` row, zeroed at each group's first block) and the
    running spill scalar.  Dead rows — ``(sent, sent)`` keys — are dropped
    here (their count is implied: group rows minus the histogram row), so
    the finishing sorts never pay for the stream's dead fraction twice.
    """
    B = 1 << bits
    hist_ref = out_refs[3 * B]
    spill_ref = out_refs[3 * B + 1]
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        spill_ref[0, 0] = jnp.uint32(0)

    @pl.when(i % blocks_per_group == 0)
    def _():
        for b in range(B):
            hist_ref[0, b] = jnp.uint32(0)

    khi = khi_ref[:]
    klo = klo_ref[:]
    pck = pck_ref[:]
    sent = jnp.uint32(constants.SENTINEL_KEY)
    live = ~((khi == sent) & (klo == sent))
    digit = (khi >> jnp.uint32(shift)) & jnp.uint32(B - 1)
    spill = jnp.uint32(0)
    for b in range(B):
        mask = live & (digit == jnp.uint32(b))
        # _compact_planes pads with all-ones on every plane — exactly the
        # dead triple, so slab pads are indistinguishable from stream
        # filler and sink to each bucket's tail in the finishing sort.
        khi_c, klo_c, pck_c, n_sp = _compact_planes(khi, klo, pck, mask, cap)
        out_refs[3 * b][:] = khi_c
        out_refs[3 * b + 1][:] = klo_c
        out_refs[3 * b + 2][:] = pck_c
        hist_ref[0, b] = hist_ref[0, b] + \
            jnp.sum(mask.astype(jnp.int32)).astype(jnp.uint32)
        spill = spill + n_sp
    spill_ref[0, 0] = spill_ref[0, 0] + spill


def _partition_level(khi2d, klo2d, pck2d, *, shift: int, bits: int,
                     block_rows: int, cap: int, n_groups: int,
                     interpret: bool):
    """One scatter-free MSD partition pass over ``(R, 128)`` planes.

    The input stream is ``n_groups`` contiguous groups (digit ranges from
    prior levels; 1 on the first).  Returns the restacked
    (group-major, bucket-major) planes — now ``n_groups * B`` groups, each
    a narrower digit range — plus the per-(group, bucket) real-row
    histogram and the spill scalar.
    """
    B = 1 << bits
    R = khi2d.shape[0]
    if R % block_rows:
        raise ValueError(f"stream rows {R} not a multiple of block_rows "
                         f"{block_rows}")
    G = R // block_rows
    if G % n_groups:
        raise ValueError(f"grid {G} not a multiple of n_groups {n_groups}")
    bpg = G // n_groups
    kern = functools.partial(_partition_kernel, shift=shift, bits=bits,
                             cap=cap, blocks_per_group=bpg)
    slab = jax.ShapeDtypeStruct((G * cap, LANES), jnp.uint32)
    plane_spec = pl.BlockSpec((cap, LANES), lambda i: (i, 0),
                              memory_space=pltpu.VMEM)
    outs = pl.pallas_call(
        kern,
        grid=(G,),
        in_specs=[pl.BlockSpec((block_rows, LANES), lambda i: (i, 0),
                               memory_space=pltpu.VMEM)] * 3,
        out_shape=[slab] * (3 * B)
        + [jax.ShapeDtypeStruct((n_groups, B), jnp.uint32),
           jax.ShapeDtypeStruct((1, 1), jnp.uint32)],
        out_specs=[plane_spec] * (3 * B)
        + [pl.BlockSpec((1, B), lambda i: (i // bpg, 0),
                        memory_space=pltpu.SMEM),
           pl.BlockSpec((1, 1), lambda i: (0, 0),
                        memory_space=pltpu.SMEM)],
        interpret=interpret,
    )(khi2d, klo2d, pck2d)
    hist = outs[3 * B]
    spill = outs[3 * B + 1][0, 0]

    def restack(refs):
        # ref_b rows are grid-major = (group, inner-block)-major; stacking
        # buckets per group yields global (group, bucket, inner) order —
        # exactly ascending digit ranges.
        parts = [r.reshape(n_groups, bpg * cap, LANES) for r in refs]
        return jnp.stack(parts, axis=1).reshape(-1, LANES)

    return (restack(outs[0:3 * B:3]), restack(outs[1:3 * B:3]),
            restack(outs[2:3 * B:3]), hist, spill)


def radix_sort3(key_hi: jax.Array, key_lo: jax.Array, packed: jax.Array, *,
                impl: str = "radix_partition", bits: int | None = None,
                block_rows: int | None = None,
                slab_slack: int | None = None,
                interpret: bool | None = None):
    """Radix-partitioned equivalent of
    ``jax.lax.sort((key_hi, key_lo, packed), num_keys=3)`` — bit-identical,
    including tie order (module docstring).

    ``impl='radix_partition'``: one MSD digit level (``bits`` wide) +
    per-bucket blocked XLA sorts.  ``impl='radix'``: two digit levels
    before the (smaller) finishing sorts.  Adversarial bucket skew beyond
    the slab budget falls back to the plain XLA sort under a ``lax.cond``
    (exact always; the partition work is wasted on such inputs, which the
    pricing note accounts for).
    """
    if impl not in _IMPLS:
        raise ValueError(f"unknown radix impl {impl!r}; known: {_IMPLS}")
    if not (key_hi.dtype == key_lo.dtype == packed.dtype == jnp.uint32):
        raise TypeError("radix_sort3 expects three uint32 planes")
    if key_hi.ndim != 1 or not (key_hi.shape == key_lo.shape == packed.shape):
        raise ValueError("radix_sort3 expects equal-length 1-D planes")
    levels = 1 if impl == "radix_partition" else 2
    # None-sentinel resolution against the module defaults AT CALL TIME so
    # geometry is overridable globally (tests shrink it: kernel jaxpr size
    # — and so CPU compile cost — scales with B x log2(block_rows), while
    # semantics are geometry-free).
    bits = DEFAULT_BITS if bits is None else bits
    block_rows = DEFAULT_BLOCK_ROWS if block_rows is None else block_rows
    slab_slack = DEFAULT_SLAB_SLACK if slab_slack is None else slab_slack
    B = 1 << bits
    if bits < 1 or bits > 5:
        # B output-ref triples are unrolled in the kernel; past 32 buckets
        # the jaxpr (and Mosaic's register pressure) outgrows the design.
        raise ValueError(f"bits must be in [1, 5], got {bits}")
    from mapreduce_tpu.config import radix_slab_cap

    cap = radix_slab_cap(bits, block_rows, slab_slack)
    if cap < 8 or cap % 8:
        raise ValueError(
            f"slab cap {cap} (= slab_slack*block_rows/B, clamped to "
            f"block_rows) must be a multiple of 8 and >= 8; adjust "
            f"block_rows/bits/slab_slack")
    if interpret is None:
        # Interpret on the CPU backend only (tests, debugging); elsewhere
        # compile, so an accelerator Mosaic cannot target fails loudly.
        interpret = jax.default_backend() == "cpu"

    n = key_hi.shape[0]
    if n == 0:
        return key_hi, key_lo, packed
    sent = jnp.uint32(constants.SENTINEL_KEY)
    ones = jnp.uint32(0xFFFFFFFF)
    # Pad to whole blocks; multi-level needs level-1 group lengths (G*cap
    # rows per bucket) divisible by block_rows, which G % B == 0 guarantees
    # for any cap (cap*G/B = slack*block_rows*(G/B)/B ... held by the
    # stricter, simpler G % B == 0).
    unit = (B if levels > 1 else 1) * block_rows * LANES
    m = -(-n // unit) * unit

    def pad(x, fill):
        if m == n:
            return x
        return jnp.concatenate([x, jnp.full((m - n,), fill, jnp.uint32)])

    khi2d = pad(key_hi, sent).reshape(-1, LANES)
    klo2d = pad(key_lo, sent).reshape(-1, LANES)
    pck2d = pad(packed, ones).reshape(-1, LANES)

    n_groups = 1
    shift = 32
    spill_total = jnp.uint32(0)
    hist = None
    for _ in range(levels):
        shift -= bits
        khi2d, klo2d, pck2d, hist, sp = _partition_level(
            khi2d, klo2d, pck2d, shift=shift, bits=bits,
            block_rows=block_rows, cap=cap, n_groups=n_groups,
            interpret=interpret)
        spill_total = spill_total + sp
        n_groups *= B

    R_f = khi2d.shape[0]
    group_rows = R_f // n_groups
    slab_len = group_rows * LANES
    # Exact per-group real-row counts -> exclusive global offsets: the
    # compaction below writes slabs ASCENDING, each exactly overwriting the
    # previous slab's pad tail (off[g+1] = off[g] + real[g] <= off[g] +
    # slab_len always), so pads vanish without any gather.
    real = hist.reshape(-1).astype(jnp.int32)
    offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                            jnp.cumsum(real)])[:n_groups]
    final_groups = n_groups

    def finished(_):
        fh = khi2d.reshape(final_groups, slab_len)
        fl = klo2d.reshape(final_groups, slab_len)
        fp = pck2d.reshape(final_groups, slab_len)
        # Finishing sort per digit range; pads (dead triples) sink to each
        # group's tail.  One blocked sort: the sortbench-measured cheaper
        # shape (rows beat comparator width, round-4 chip notes).
        sh, sl, sp_ = jax.lax.sort((fh, fl, fp), dimension=1, num_keys=3)
        oh = jnp.full((n + slab_len,), ones, jnp.uint32)
        ol = jnp.full((n + slab_len,), ones, jnp.uint32)
        op = jnp.full((n + slab_len,), ones, jnp.uint32)
        for g in range(final_groups):
            start = (offs[g],)
            oh = jax.lax.dynamic_update_slice(oh, sh[g], start)
            ol = jax.lax.dynamic_update_slice(ol, sl[g], start)
            op = jax.lax.dynamic_update_slice(op, sp_[g], start)
        # Rows past the last group's real tail were either overwritten by
        # that group's own pads or never written: both are the dead triple,
        # matching the XLA sort's trailing filler segment bit-for-bit.
        return oh[:n], ol[:n], op[:n]

    def fallback(_):
        return jax.lax.sort((key_hi, key_lo, packed), num_keys=3)

    return jax.lax.cond(spill_total == 0, finished, fallback, None)
