"""Static per-eqn HBM/FLOP accounting over jaxprs (the costcheck model).

The jaxpr-cost-model tradition (XLA's HLO cost analysis; Roofline-style
byte/FLOP accounting) applied to the traced step/finish programs: every
equation is classified into a family and charged HBM bytes and FLOPs from
its operand/result shapes and dtypes alone — no device, no profiler.

The byte model is deliberately simple and DOCUMENTED, because its job is
to be a stable, auditable bound, not a simulator:

* **materializing** primitives (sort, gather/scatter, concatenate, slices,
  transposes, pallas_call, ...) charge input + output bytes — they move
  their operands through HBM;
* **fusible** primitives (elementwise, compares, converts, broadcasts,
  reductions) charge ZERO HBM bytes but do charge FLOPs — XLA fuses
  elementwise chains into their consumers, and charging them as traffic
  made the round-1 hand pricing overshoot 3-5x (the same lesson as
  opshare's wrapper-span double-counting);
* **control** primitives recurse: ``cond`` charges the costlier branch
  (the certified bound is worst-case over the spill-fallback conds),
  ``scan`` charges body x length, ``while`` charges one trip and flags
  itself a lower bound;
* **collectives** are tallied in their own family and excluded from the
  HBM total — they price interconnect, not local HBM.

``effective passes`` = HBM bytes / bytes-of-one-input-pass: how many times
the program streams its own input, the unit the round-5 dead-end notes
prices in (the XLA sort measured at 2.6-3.4 such passes, round 6).
"""

from __future__ import annotations

import dataclasses
import math

from mapreduce_tpu.analysis import trace

_COLLECTIVES = {"psum", "pmax", "pmin", "all_gather", "all_to_all",
                "reduce_scatter", "ppermute", "pbroadcast", "axis_index"}
_MATERIALIZING = {"sort", "gather", "scatter", "scatter-add", "scatter_add",
                  "concatenate", "dynamic_slice", "dynamic_update_slice",
                  "slice", "pad", "transpose", "rev", "copy",
                  "pallas_call", "cumsum", "cumlogsumexp", "cummax",
                  "cummin", "cumprod", "associative_scan"}
_CONTROL = {"jit", "cond", "while", "scan", "shard_map", "custom_jvp_call",
            "custom_vjp_call", "custom_vjp_call_jaxpr", "closed_call",
            "core_call", "xla_call", "remat", "checkpoint", "custom_partitioning"}
# Sort comparators run log2(n) network stages over the comparator keys; the
# FLOP charge is n*log2(n) per operand plane (coarse, but shape-derived).


def _aval_bytes(aval) -> int:
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    return int(math.prod(shape)) * dtype.itemsize


def _aval_elems(aval) -> int:
    shape = getattr(aval, "shape", None)
    if shape is None:
        return 0
    return int(math.prod(shape))


@dataclasses.dataclass
class Cost:
    """Additive cost of a program region."""

    hbm_read: int = 0
    hbm_written: int = 0
    flops: int = 0
    collective_bytes: int = 0
    eqns: int = 0
    lower_bound: bool = False  # a while-loop body was charged once
    families: dict = dataclasses.field(default_factory=dict)

    @property
    def hbm_bytes(self) -> int:
        return self.hbm_read + self.hbm_written

    def add(self, other: "Cost", times: int = 1) -> None:
        self.hbm_read += other.hbm_read * times
        self.hbm_written += other.hbm_written * times
        self.flops += other.flops * times
        self.collective_bytes += other.collective_bytes * times
        self.eqns += other.eqns * times
        self.lower_bound |= other.lower_bound
        for k, v in other.families.items():
            self.families[k] = self.families.get(k, 0) + v * times

    def charge(self, family: str, read: int, written: int, flops: int) -> None:
        self.hbm_read += read
        self.hbm_written += written
        self.flops += flops
        self.families[family] = self.families.get(family, 0) + read + written

    def as_dict(self) -> dict:
        return {"hbm_read_bytes": self.hbm_read,
                "hbm_written_bytes": self.hbm_written,
                "hbm_bytes": self.hbm_bytes,
                "flops": self.flops,
                "collective_bytes": self.collective_bytes,
                "eqns": self.eqns,
                "lower_bound": self.lower_bound,
                "family_bytes": dict(sorted(self.families.items()))}


def _classify(name: str) -> str:
    if name in _COLLECTIVES:
        return "collective"
    if name == "sort":
        return "sort"
    if name == "pallas_call":
        return "pallas"
    if "gather" in name:
        return "gather"
    if "scatter" in name:
        return "scatter"
    if name in _MATERIALIZING:
        return "layout/copy"
    return "fusible"


def program_cost(jaxpr) -> Cost:
    """Walk one (Closed)Jaxpr, charging each equation per the module
    model.  Shapes inside ``shard_map`` bodies are per-shard, so the
    returned cost is per-device — divide by the per-device input bytes for
    effective passes."""
    j = getattr(jaxpr, "jaxpr", jaxpr)
    cost = Cost()
    for eqn in j.eqns:
        name = eqn.primitive.name
        cost.eqns += 1
        if name in _CONTROL or (trace.eqn_subjaxprs(eqn) and
                                name not in ("pallas_call",)):
            subs = [program_cost(s) for s in trace.eqn_subjaxprs(eqn)]
            if not subs:
                continue
            if name == "cond":
                cost.add(max(subs, key=lambda c: c.hbm_bytes + c.flops))
            elif name == "scan":
                times = int(eqn.params.get("length", 1) or 1)
                for s in subs:
                    cost.add(s, times)
            elif name == "while":
                for s in subs:
                    cost.add(s)
                cost.lower_bound = True
            else:
                for s in subs:
                    cost.add(s)
            continue
        in_bytes = sum(_aval_bytes(v.aval) for v in eqn.invars)
        out_bytes = sum(_aval_bytes(v.aval) for v in eqn.outvars)
        out_elems = sum(_aval_elems(v.aval) for v in eqn.outvars)
        family = _classify(name)
        if family == "collective":
            cost.collective_bytes += in_bytes
            cost.families["collective"] = \
                cost.families.get("collective", 0) + in_bytes
        elif family == "sort":
            rows = max((_aval_elems(v.aval) for v in eqn.invars), default=0)
            stages = max(1, int(math.log2(rows)) if rows > 1 else 1)
            cost.charge("sort", in_bytes, out_bytes,
                        rows * stages * max(1, len(eqn.invars)))
        elif name == "dot_general":
            # 2*max(M*K, K*N): coarse contraction FLOPs; operands stream
            # HBM (must precede the fusible branch, which would absorb it).
            m = _aval_elems(eqn.invars[0].aval)
            n = _aval_elems(eqn.invars[1].aval)
            cost.charge("dot", in_bytes, out_bytes, 2 * max(m, n))
        elif family == "fusible":
            cost.charge("fusible", 0, 0, out_elems)
        else:
            cost.charge(family, in_bytes, out_bytes, out_elems)
    return cost


# -- the aggregation-sort artifact ------------------------------------------


@dataclasses.dataclass(frozen=True)
class SortEqnInfo:
    rows: int  # elements per plane
    planes: int  # operands carried through the sort
    num_keys: int
    is_stable: bool
    location: str

    @property
    def pass_bytes(self) -> int:
        """Bytes of one full-stream reorder pass over this sort's operands:
        read + write every plane (the round-6 pricing unit)."""
        return 2 * self.rows * self.planes * 4  # uint32 planes


def find_aggregation_sort(jaxpr, num_keys: int | None = None
                          ) -> SortEqnInfo | None:
    """The packed fast path's aggregation sort: the LARGEST sort equation
    carrying exactly the three uint32 planes (key_hi, key_lo, packed).
    ``num_keys`` narrows to one comparator strategy — stable2 is the
    3-plane ``num_keys=2`` stable sort, sort3 the ``num_keys=3`` one (a
    stable2 step still CONTAINS a sort3 eqn in its spill-fallback branch,
    so the filter matters); the 7-array generic table builds never match."""
    best: SortEqnInfo | None = None
    for eqn, _ in trace.iter_eqns(jaxpr):
        if eqn.primitive.name != "sort":
            continue
        avals = [v.aval for v in eqn.invars]
        if len(avals) != 3:
            continue
        if any(str(getattr(a, "dtype", "")) != "uint32" for a in avals):
            continue
        rows = _aval_elems(avals[0])
        if any(_aval_elems(a) != rows for a in avals):
            continue
        if num_keys is not None and \
                int(eqn.params.get("num_keys", 1)) != num_keys:
            continue
        info = SortEqnInfo(
            rows=rows, planes=3,
            num_keys=int(eqn.params.get("num_keys", 1)),
            is_stable=bool(eqn.params.get("is_stable", False)),
            location=trace.eqn_location(eqn))
        if best is None or info.rows > best.rows:
            best = info
    return best


# The canonical sort-row formula moved to analysis/geometry.py (ISSUE 12:
# the jax-free geometry search prices CANDIDATE geometries with the same
# arithmetic the cost pass asserts against the traced sort equation —
# one formula, re-exported here for the pass's historical import path).
from mapreduce_tpu.analysis.geometry import stable2_sort_rows  # noqa: E402,F401
