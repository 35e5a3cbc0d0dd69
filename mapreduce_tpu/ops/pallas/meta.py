"""Kernel metadata for the static analyzer (costcheck's vmem/race passes).

Each Pallas kernel module declares, next to the kernel it describes:

* a :class:`KernelMeta` entry — spill semantics (does the kernel emit a
  spill counter whose nonzero value REQUIRES an exactness fallback in the
  caller?) keyed by the kernel function's name, which is how a traced
  ``pallas_call`` equation identifies itself (``name_and_src_info``);
* a ``vmem_plan`` hook returning :class:`VmemPlan` — the kernel's
  VMEM/SMEM footprint at a given geometry, computed from the same
  BlockSpec/scratch arithmetic the wrapper uses, so the analyzer can
  certify PRODUCTION geometries without tracing a production-sized
  program (analysis-config traces certify the same kernels at toy grids).

The per-core budgets live here too, single-owner: Mosaic's default VMEM
stack budget is 16 MB (measured: the compact tokenize kernel exceeds it
and ships a 64 MB override — ops/pallas/tokenize.py); v5e carries ~128 MB
physical VMEM, the hard ceiling no override may cross.  SMEM holds only
scalars/control (pallas guide); the shipped kernels use tens of bytes —
the 64 KiB budget is generous headroom, not a measured limit.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

VMEM_DEFAULT_LIMIT = 16 * 1024 * 1024  # Mosaic default stack budget
VMEM_PHYSICAL = 128 * 1024 * 1024  # v5e per-core physical VMEM
SMEM_BUDGET = 64 * 1024


@dataclasses.dataclass(frozen=True)
class KernelMeta:
    """Analyzer-facing contract of one Pallas kernel function."""

    name: str  # kernel function __name__ (pallas_call's own id)
    # Does this binding emit a spill counter requiring a caller-side
    # exactness fallback?  Receives (num_outputs,) — the tokenize kernel
    # only spills in compact mode (6 outputs vs the pair path's 5).
    spills: Callable[[int], bool]
    description: str = ""


@dataclasses.dataclass(frozen=True)
class Buffer:
    """One VMEM/SMEM allocation of a kernel binding."""

    label: str
    space: str  # "vmem" | "smem"
    bytes: int
    double_buffered: bool  # pipelined in/out blocks get 2x


@dataclasses.dataclass(frozen=True)
class VmemPlan:
    """Static footprint of one kernel geometry.

    ``vmem_bytes`` counts pipelined blocks twice (Pallas double-buffers
    grid in/out blocks so the next block's DMA overlaps compute) plus
    scratch once.  It is a LOWER bound: Mosaic may spill intermediate
    vectors to VMEM beyond declared blocks — which is exactly why the
    compact kernels ship an explicit ``vmem_limit_bytes`` override and the
    analyzer checks the plan against that declared limit, not against the
    physical ceiling alone.
    """

    kernel: str
    geometry: str  # human description of the knob setting
    buffers: tuple  # Buffer
    vmem_limit_bytes: Optional[int] = None  # kernel's own compiler override

    @property
    def vmem_bytes(self) -> int:
        return sum(b.bytes * (2 if b.double_buffered else 1)
                   for b in self.buffers if b.space == "vmem")

    @property
    def smem_bytes(self) -> int:
        return sum(b.bytes * (2 if b.double_buffered else 1)
                   for b in self.buffers if b.space == "smem")

    @property
    def budget(self) -> int:
        return self.vmem_limit_bytes or VMEM_DEFAULT_LIMIT

    def as_dict(self) -> dict:
        return {"kernel": self.kernel, "geometry": self.geometry,
                "vmem_bytes": self.vmem_bytes,
                "smem_bytes": self.smem_bytes,
                "vmem_limit_bytes": self.vmem_limit_bytes,
                "budget_bytes": self.budget,
                "buffers": [dataclasses.asdict(b) for b in self.buffers]}


_KERNEL_META: dict[str, KernelMeta] = {}


def register(meta: KernelMeta) -> KernelMeta:
    """Add (or replace — test idiom) a kernel's analyzer metadata."""
    _KERNEL_META[meta.name] = meta
    return meta


def lookup(kernel_name: str) -> Optional[KernelMeta]:
    return _KERNEL_META.get(kernel_name)


# -- geometry -> footprint constructors (ISSUE 12) ---------------------------
#
# The SAME BlockSpec/scratch arithmetic the kernel wrappers bind, as pure
# jax-free functions of the geometry knobs: the wrappers' ``vmem_plan``
# hooks delegate here, ``production_plans`` below derives the shipped list
# from ``config.DEFAULT_GEOMETRY`` through the same constructors, and the
# kernel-geometry search (``analysis/geometry.py``) prices CANDIDATE
# geometries with them — one source of truth, so the certified list can
# never silently drift from what the call sites bind.

_LANES = 128  # TPU vector lanes; mirrors ops/pallas/tokenize.LANES


def tokenize_plan(block_rows: int = 256, compact_slots: int = 0,
                  w: int = 32, lane_major: bool = False, fused: bool = False,
                  combiner_slots: int = 0, aux_rows: int = 96) -> VmemPlan:
    """Static VMEM/SMEM footprint of one tokenize-kernel geometry — the
    arithmetic behind ``ops/pallas/tokenize.vmem_plan`` (which delegates
    here).  ``fused`` adds the seam-carry aux plane (``aux_rows`` tall)
    and the in-VMEM transposed byte block of the fused map path;
    ``combiner_slots`` the hot-key cache's four ``(C, LANES)`` planes
    (cache state lives in revisited output blocks, the spill-scalar
    idiom, so it is pipelined like any other output)."""
    out_rows = compact_slots if compact_slots else block_rows // 2
    n_scalars = 3 if compact_slots else 2
    bufs = [Buffer("bytes-in", "vmem", block_rows * _LANES, True)]
    if fused:
        bufs.append(Buffer("seam-aux", "vmem", aux_rows * _LANES, True))
        # The raw lane-view block is transposed (widened) in VMEM before
        # the lookback loop; charge the int32 copy as resident scratch.
        bufs.append(Buffer("transpose-scratch", "vmem",
                           block_rows * _LANES * 4, False))
    bufs += [Buffer(f"plane-out[{i}]", "vmem", out_rows * _LANES * 4, True)
             for i in range(3)]
    bufs += [Buffer(f"scalar[{i}]", "smem", 4, False)
             for i in range(n_scalars)]
    if combiner_slots:
        bufs += [Buffer(f"combiner-cache[{name}]", "vmem",
                        combiner_slots * _LANES * 4, True)
                 for name in ("key_hi", "key_lo", "count", "packed")]
    bufs.append(Buffer("carry-scratch", "vmem", (w + 1) * _LANES * 4, False))
    geom = (f"block_rows={block_rows} w={w} slots={compact_slots or 'pair'}"
            + (" lane-major" if lane_major else "")
            + (" fused" if fused else "")
            + (f" combiner={combiner_slots}" if combiner_slots else ""))
    return VmemPlan(
        kernel="_tokenize_kernel", geometry=geom, buffers=tuple(bufs),
        vmem_limit_bytes=64 * 1024 * 1024 if compact_slots else None)


def geometry_plans(geom) -> list[VmemPlan]:
    """Every kernel footprint one :class:`~mapreduce_tpu.config.Geometry`
    implies — the stable2 compact window, the sort3 compact and pair
    variants, the fused map path, the hot-key combiner window and the
    fused spill fallback.  The geometry search certifies candidates
    through exactly this list."""
    return [
        tokenize_plan(block_rows=geom.block_rows,
                      compact_slots=geom.compact_slots, lane_major=True),
        tokenize_plan(block_rows=geom.sort3_block_rows,
                      compact_slots=geom.sort3_slots),
        tokenize_plan(block_rows=geom.pair_block_rows),
        tokenize_plan(block_rows=geom.block_rows,
                      compact_slots=geom.compact_slots, lane_major=True,
                      fused=True, aux_rows=geom.aux_rows),
        tokenize_plan(block_rows=geom.combiner_block_rows,
                      compact_slots=geom.compact_slots, lane_major=True,
                      fused=True, aux_rows=geom.aux_rows,
                      combiner_slots=geom.combiner_slots),
        tokenize_plan(block_rows=geom.pair_block_rows, fused=True,
                      aux_rows=geom.aux_rows),
    ]


def production_plans() -> list[VmemPlan]:
    """Every SHIPPED kernel geometry's static footprint — derived from
    ``config.DEFAULT_GEOMETRY`` through the same constructor the geometry
    search uses (ISSUE 12: one source of truth; the hand-maintained list
    this replaces could silently drift from the kernel call sites).  The
    set the vmem pass certifies regardless of which analysis-config
    models happened to trace them."""
    from mapreduce_tpu.config import DEFAULT_GEOMETRY

    return geometry_plans(DEFAULT_GEOMETRY)
