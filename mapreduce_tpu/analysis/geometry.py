"""Certifier-gated kernel-geometry search (ISSUE 12 tentpole).

PR 10 closed the loop over *runtime* knobs and PR 11 proved geometry is
where the wins live (block_rows 384 -> 512 under the combiner bought −25%
sort rows).  This module makes the kernel geometries themselves
searchable, in the spirit of CUDA-LLM (PAPERS.md: search over kernel
variants with a correctness gate as fitness):

1. :func:`enumerate_candidates` walks the candidate lattice — window
   heights, slot budgets, combiner cache depths, seam-aux heights, radix
   digit widths / slab slacks, each axis stepped on the (8, 128)/(32, 128)
   tile grids the :class:`~mapreduce_tpu.config.Geometry` validation
   encodes;
2. every candidate is **certified statically** (:func:`certify`): its
   full kernel-plan set (``ops/pallas/meta.geometry_plans`` — the SAME
   constructor that derives the shipped ``production_plans``) must fit
   the VMEM/SMEM budgets the vmem-budget pass enforces.  Candidates that
   fail are never emitted;
3. every certified candidate is **priced** (:func:`price`) with the
   hbm-cost model's own arithmetic: :func:`stable2_sort_rows` (the
   canonical formula — ``analysis/costmodel.py`` imports it from here)
   re-derived from the CANDIDATE geometry instead of the shipped
   constant, the sort's one-pass bytes, the radix slab write
   amplification, and the measured-density spill headroom;
4. :func:`shortlist` ranks the certified set by modeled sort traffic —
   the measured dominant cost of the chunk budget — and hands the top-K
   to the probe-pass machinery (``tools/geomsearch.py`` reusing the
   PR-10 loop in ``tools/autotune.py``) for measured on-device ranking.

The kernel-race and spill-reachability certifications are *structural*
program properties: every candidate compiles the SAME kernel bodies at
different static shapes, so the guarded-init/read-modify-write discipline
and the spill-fallback cond are geometry-independent — ``tools/
geomsearch.py --gate`` (and tests/test_geometry.py) still runs the full
graphcheck pipeline over shortlisted candidates to prove it, no device
needed.

Deliberately jax-free (imports only ``config`` and ``ops/pallas/meta``):
``tools/geomsearch.py --selftest`` drives the whole enumerate → certify →
price → rank path without jax in the process, the ``autotune --selftest``
contract.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Optional

from mapreduce_tpu.config import (DEFAULT_GEOMETRY, GEOMETRY_PRESETS,
                                  Geometry)
from mapreduce_tpu.ops.pallas import meta

#: Bumped when the candidate/shortlist artifact schema changes shape.
GEOMETRY_SEARCH_VERSION = 1

#: The pricing chunk: the production default (32 MB), where the round-6
#: sort pricing and the PR-11 row arithmetic live.
PRICING_CHUNK_BYTES = 1 << 25

#: Measured worst-case window density (tools/density.py, rounds 4 and
#: 11): 114 token ends in one 384-byte window on the Zipf bench
#: corpus (75 natural).  ceil(density * block_rows) > slots flags a
#: candidate spill-RISKY — never rejected (the fallback is exact; the
#: probe pass measures what the risk costs), but ranked with its eyes
#: open and smoked first by tools/kernel_smoke.py --geometry.
MEASURED_MAX_ENDS = 114
MEASURED_MAX_ENDS_WINDOW = 384

LANES = 128


def stable2_sort_rows(chunk_bytes: int, block_rows: int, slots: int,
                      lanes: int = LANES) -> int:
    """Rows of the stable2 aggregation sort for a pallas chunk, from the
    kernel geometry alone: the lane-major column pass emits ``slots``
    output rows per ``block_rows``-byte window per lane, over the padded
    column view (one extra pad block; the seam stream aggregates
    separately on this path).  The canonical formula — the hbm-cost
    pass's static leg (``analysis/costmodel.py`` re-exports it) and the
    search's pricing both read exactly this."""
    seg_len = chunk_bytes // lanes
    pad_rows = (-seg_len) % block_rows + block_rows
    grid = (seg_len + pad_rows) // block_rows
    return grid * slots * lanes


def radix_slab_write_amplification(geom: Geometry) -> float:
    """Slab bytes written per one-pass bytes for one partition level —
    the round-6 pricing note's slack-factor write amplification, derived
    from the CANDIDATE geometry instead of quoted: every block writes
    ``3 * B * cap`` slab rows per ``3 * block_rows`` input rows."""
    B = 1 << geom.radix_bits
    return (B * geom.radix_cap) / geom.radix_block_rows


def window_spill_risk(block_rows: int, slots: int) -> bool:
    """Does the measured worst-case density overflow this window's slot
    budget?  The PR-11 512-row dead-end branch, as arithmetic: 114 ends
    per 384 bytes -> ceil(0.297 * block_rows) vs slots."""
    worst = -(-MEASURED_MAX_ENDS * block_rows // MEASURED_MAX_ENDS_WINDOW)
    return worst > slots


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One certified, priced geometry candidate."""

    geometry: Geometry
    label: str  # preset name when one matches, else a compact spec
    axis: str  # which lattice axis produced it ('default' for the base)
    #: stable2 aggregation sort rows at the pricing chunk — the primary
    #: ranking key (the sort is the measured chunk-budget floor).
    sort_rows: int
    #: One full reorder pass over the 3 uint32 sort planes (read+write).
    sort_pass_bytes: int
    #: Peak single-kernel VMEM footprint over the candidate's plan set.
    vmem_peak_bytes: int
    #: Radix slab write amplification (one partition level).
    radix_amplification: float
    #: Measured-density spill risk of the candidate's compact window.
    spill_risk: bool

    def as_dict(self) -> dict:
        return {"label": self.label, "axis": self.axis,
                "sort_rows": self.sort_rows,
                "sort_pass_bytes": self.sort_pass_bytes,
                "vmem_peak_bytes": self.vmem_peak_bytes,
                "radix_amplification": round(self.radix_amplification, 3),
                "spill_risk": self.spill_risk,
                "geometry": self.geometry.as_dict()}


def certify(geom: Geometry) -> list[str]:
    """Static certifier: every kernel plan the geometry implies must fit
    the budgets the vmem-budget pass enforces (the same ``meta`` limits,
    through the same :func:`...meta.geometry_plans` constructor that
    derives the shipped list).  Returns the rejection reasons — empty
    means certified.  Construction-invalid geometries report their
    ValueError the same way, so callers probe the lattice uniformly."""
    errors: list[str] = []
    for plan in meta.geometry_plans(geom):
        label = f"{plan.kernel} [{plan.geometry}]"
        budget = plan.budget
        if budget > meta.VMEM_PHYSICAL:
            errors.append(
                f"{label}: declared vmem_limit_bytes {budget >> 20} MiB "
                f"exceeds the {meta.VMEM_PHYSICAL >> 20} MiB physical VMEM")
        if plan.vmem_bytes > budget:
            errors.append(
                f"{label}: static VMEM footprint {plan.vmem_bytes >> 10} "
                f"KiB exceeds the {budget >> 20} MiB budget "
                "(double-buffered blocks + scratch)")
        if plan.smem_bytes > meta.SMEM_BUDGET:
            errors.append(
                f"{label}: SMEM footprint {plan.smem_bytes} B exceeds the "
                f"{meta.SMEM_BUDGET >> 10} KiB budget")
    return errors


def label_for(geom: Geometry) -> str:
    """A preset name when one matches, else a compact spec string (for
    humans and row labels; the machine-readable form is the dict)."""
    for name, preset in GEOMETRY_PRESETS.items():
        if geom == preset:
            return name
    parts = []
    for f in dataclasses.fields(Geometry):
        v = getattr(geom, f.name)
        if v != getattr(DEFAULT_GEOMETRY, f.name):
            parts.append(f"{f.name}={v}")
    return ",".join(parts) or "default"


def price(geom: Geometry,
          chunk_bytes: int = PRICING_CHUNK_BYTES) -> dict:
    """The hbm-cost-model pricing of one candidate at ``chunk_bytes``:
    sort rows/pass bytes from the CANDIDATE's stable2 window, VMEM peak
    over its plan set, radix amplification, spill headroom."""
    rows = stable2_sort_rows(chunk_bytes, geom.block_rows,
                             geom.compact_slots)
    plans = meta.geometry_plans(geom)
    return {
        "chunk_bytes": chunk_bytes,
        "sort_rows": rows,
        "sort_pass_bytes": 2 * rows * 3 * 4,
        "vmem_peak_bytes": max(p.vmem_bytes for p in plans),
        "radix_amplification": radix_slab_write_amplification(geom),
        "spill_risk": window_spill_risk(geom.block_rows,
                                        geom.compact_slots),
    }


def _candidate(geom: Geometry, axis: str, chunk_bytes: int) -> Candidate:
    p = price(geom, chunk_bytes)
    return Candidate(geometry=geom, label=label_for(geom), axis=axis,
                     sort_rows=p["sort_rows"],
                     sort_pass_bytes=p["sort_pass_bytes"],
                     vmem_peak_bytes=p["vmem_peak_bytes"],
                     radix_amplification=p["radix_amplification"],
                     spill_risk=p["spill_risk"])


#: The candidate lattice: per-axis values stepped on the tile grids.  One
#: axis family varies at a time off the default (a full cross product
#: explodes combinatorially AND makes probe attribution useless — a
#: one-axis delta is a readable A/B, the PR-11 discipline).
LATTICE_AXES: dict = {
    "block_rows": (256, 384, 512, 640, 768),
    "aux_rows": (96, 128),
    "combiner_slots": (8, 16, 24, 32),
    "combiner_block_rows": (384, 512, 640),
    "pair_block_rows": (128, 256, 384),
    "sort3": tuple((br, s) for br in (256, 384, 512)
                   for s in (72, 80, 88, 96, 104, 112, 120, 128)
                   if s <= br // 2),
    "radix": tuple((b, sl) for b in (2, 3, 4, 5) for sl in (2, 4)),
}


def enumerate_candidates(chunk_bytes: int = PRICING_CHUNK_BYTES
                         ) -> list[Candidate]:
    """Walk the lattice, certify, price.  Every RETURNED candidate passed
    the static certifier by construction (off-lattice or over-budget
    points are dropped); the default geometry is always candidate zero."""
    out: list[Candidate] = []
    seen: set = set()

    def add(axis: str, **fields) -> None:
        try:
            geom = Geometry(**fields)
        except ValueError:
            return  # off the tile lattice: not a candidate
        if geom in seen:
            return
        seen.add(geom)
        if certify(geom):
            return  # over budget: the certifier is the gate
        out.append(_candidate(geom, axis, chunk_bytes))

    add("default")
    for br in LATTICE_AXES["block_rows"]:
        add("block_rows", block_rows=br)
    for ar in LATTICE_AXES["aux_rows"]:
        add("aux_rows", aux_rows=ar)
    for cs in LATTICE_AXES["combiner_slots"]:
        add("combiner_slots", combiner_slots=cs)
    for cbr in LATTICE_AXES["combiner_block_rows"]:
        add("combiner_block_rows", combiner_block_rows=cbr)
    for pbr in LATTICE_AXES["pair_block_rows"]:
        add("pair_block_rows", pair_block_rows=pbr)
    for sbr, ss in LATTICE_AXES["sort3"]:
        add("sort3", sort3_block_rows=sbr, sort3_slots=ss)
    for bits, slack in LATTICE_AXES["radix"]:
        add("radix", radix_bits=bits, radix_slab_slack=slack)
    return out


def shortlist(candidates: Iterable[Candidate], k: int = 5,
              axis: Optional[str] = None) -> list[Candidate]:
    """Top-K by modeled sort traffic (rows ascending, VMEM peak as the
    tie-break).  ``axis`` narrows to one lattice family plus the default
    (the readable A/B a probe run wants).  Spill-risky candidates rank by
    the same cost — the model says what they'd save, the flag says what
    the probe must watch — mirroring how the cost pass prices worst-case
    cond branches rather than hiding them."""
    pool = [c for c in candidates
            if axis is None or c.axis in (axis, "default")]
    ranked = sorted(pool, key=lambda c: (c.sort_rows, c.vmem_peak_bytes,
                                         c.label))
    return ranked[:k]


def search_artifact(candidates: list[Candidate], k: int = 5) -> dict:
    """The machine-readable search artifact (docs/analysis.md schema):
    what tools/geomsearch.py emits and the probe driver consumes."""
    return {
        "geometry_search_version": GEOMETRY_SEARCH_VERSION,
        "pricing_chunk_bytes": PRICING_CHUNK_BYTES,
        "candidates": len(candidates),
        "default": next((c.as_dict() for c in candidates
                         if c.axis == "default"), None),
        "shortlist": [c.as_dict() for c in shortlist(candidates, k)],
    }


def resolve_auto(profile_path: str, family: str = "wordcount"):
    """Resolve ``Config.geometry='auto'`` against a searched profile
    (ISSUE 12): the freshest ``tuned.json`` profile for ``family`` whose
    config carries a non-default geometry decides — its label (preset
    round-trip) or spec dict (Config accepts both).  No profile, no
    geometry entry, or an unreadable file resolves to 'default' — the
    combiner='auto' degrade-to-off contract.

    The read itself lives in the run-history warehouse now (ISSUE 14:
    ``obs/history.resolve_prior`` is the one place prior-run questions
    are answered); this wrapper supplies the Config-side validation the
    jax-free warehouse cannot import."""
    from mapreduce_tpu.obs import history

    def _valid_spec(spec: dict) -> bool:
        try:
            Geometry(**spec)
        except (TypeError, ValueError):
            return False  # future-shaped profile: skip, never crash
        return True

    return history.resolve_prior(
        profile_path=profile_path, family=family,
        presets=set(GEOMETRY_PRESETS), geometry_ok=_valid_spec)["geometry"]
