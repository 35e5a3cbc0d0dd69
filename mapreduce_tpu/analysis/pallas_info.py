"""Pallas kernel bindings extracted from traced jaxprs.

The vmem and kernel-race passes both need the same view of every
``pallas_call`` equation reachable from a traced program: which kernel it
is, its grid, every ref's block shape / dtype / memory space, which grid
iterations revisit the same block (the index map evaluated over the whole
grid), the scratch shapes, and the kernel body jaxpr itself.  This module
builds that view once (:func:`collect_pallas_calls`) so the passes stay
pure policy.

Everything here reads jax's ``GridMapping`` / ``BlockMapping``
(``jax._src.pallas.core``) as the installed jax (pinned in pyproject.toml)
lays them out.  A field that is missing raises: a jax bump that moves one
must fail the analysis loudly, never certify a kernel it could not read.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import numpy as np

from mapreduce_tpu.analysis import trace

# Revisit detection enumerates the grid; anything larger is reported as
# unverified rather than stalling analysis (production grids reach ~10^3,
# analysis-config grids are single digits).
MAX_GRID_ENUM = 4096


@dataclasses.dataclass(frozen=True)
class RefInfo:
    """One kernel operand ref: an input/output block or a scratch buffer."""

    role: str  # "in" | "out" | "scratch"
    index: int  # position within its role
    block_shape: tuple  # block shape (scratch: full shape)
    dtype: Any  # numpy dtype of the buffer
    memory_space: str  # "vmem" | "smem" | "any" | "?"
    array_shape: Optional[tuple]  # full HBM-side array shape (None: scratch)
    revisited: Optional[bool]  # same block touched by >1 grid iteration
    # (None = could not be determined: dynamic grid, enum bound exceeded)

    @property
    def block_bytes(self) -> int:
        return int(math.prod(self.block_shape) * self.dtype.itemsize)

    @property
    def array_bytes(self) -> int:
        if self.array_shape is None:
            return 0
        return int(math.prod(self.array_shape) * self.dtype.itemsize)


@dataclasses.dataclass(frozen=True)
class PallasCallInfo:
    """One pallas_call equation, digested for the analysis passes."""

    kernel_name: str  # e.g. "_tokenize_kernel"
    src: str  # "name at file:line" (from name_and_src_info)
    program: str  # which traced program it was found in (step/finish/...)
    grid: tuple
    refs: tuple  # RefInfo, kernel-argument order: ins, outs, scratch
    kernel_jaxpr: Any  # the kernel body Jaxpr (refs are its invars)
    vmem_limit_bytes: Optional[int]  # mosaic compiler-params override
    dimension_semantics: Any  # mosaic grid-parallelism declaration
    enclosing_has_cond: bool  # a cond primitive exists in the same program

    @property
    def ins(self) -> tuple:
        return tuple(r for r in self.refs if r.role == "in")

    @property
    def outs(self) -> tuple:
        return tuple(r for r in self.refs if r.role == "out")

    @property
    def scratch(self) -> tuple:
        return tuple(r for r in self.refs if r.role == "scratch")

    def signature(self) -> tuple:
        """Dedup key: the same kernel binding traced into several branches
        (spill-fallback conds) should be certified once."""
        return (self.kernel_name, self.grid,
                tuple((r.role, r.block_shape, str(r.dtype), r.memory_space)
                      for r in self.refs))


def _memory_space_of(aval) -> str:
    """'vmem' | 'smem' | 'sem' | 'any' of a kernel ref's abstract value."""
    s = str(aval.memory_space).lower()
    for known in ("vmem", "smem", "sem", "any"):
        if known in s:
            return known
    return s


def _eval_index_map(bm, idx: tuple) -> tuple:
    imj = bm.index_map_jaxpr
    out = jax.core.eval_jaxpr(imj.jaxpr, imj.consts, *idx)
    return tuple(int(x) for x in out)


def _revisited(bm, grid: tuple) -> Optional[bool]:
    """Does any block index recur across grid iterations?  None: unknown."""
    try:
        points = int(math.prod(grid)) if grid else 1
    except TypeError:  # dynamic grid bound
        return None
    if points > MAX_GRID_ENUM:
        return None
    seen = set()
    # Row-major enumeration of the grid index space.
    dims = [int(g) for g in grid] or [1]
    idx = [0] * len(dims)
    for _ in range(points):
        block = _eval_index_map(bm, tuple(idx))
        if block in seen:
            return True
        seen.add(block)
        for d in reversed(range(len(dims))):
            idx[d] += 1
            if idx[d] < dims[d]:
                break
            idx[d] = 0
    return False


def digest_eqn(eqn, program: str, enclosing_has_cond: bool
               ) -> PallasCallInfo:
    """Build a PallasCallInfo from one pallas_call equation."""
    params = eqn.params
    gm = params["grid_mapping"]
    kj = params["jaxpr"]
    src = kj.debug_info.func_src_info
    kernel_name = kj.debug_info.func_name
    grid = tuple(gm.grid)

    n_in = gm.num_inputs
    refs: list[RefInfo] = []
    for i, bm in enumerate(gm.block_mappings):
        role = "in" if i < n_in else "out"
        refs.append(RefInfo(
            role=role, index=i if role == "in" else i - n_in,
            block_shape=tuple(bm.block_aval.inner_aval.shape),
            dtype=np.dtype(bm.block_aval.inner_aval.dtype),
            memory_space=_memory_space_of(bm.block_aval),
            array_shape=tuple(bm.array_aval.shape),
            revisited=_revisited(bm, grid)))
    for s, aval in enumerate(gm.scratch_avals):
        refs.append(RefInfo(
            role="scratch", index=s,
            block_shape=tuple(aval.inner_aval.shape),
            dtype=np.dtype(aval.inner_aval.dtype),
            memory_space=_memory_space_of(aval),
            array_shape=None,
            revisited=True))  # scratch persists across grid iterations

    mosaic = params["compiler_params"].get("mosaic_tpu")
    return PallasCallInfo(
        kernel_name=kernel_name, src=src, program=program,
        grid=grid, refs=tuple(refs), kernel_jaxpr=kj,
        vmem_limit_bytes=mosaic.vmem_limit_bytes if mosaic else None,
        dimension_semantics=mosaic.dimension_semantics if mosaic else None,
        enclosing_has_cond=enclosing_has_cond)


def _has_cond_outside_kernels(jaxpr) -> bool:
    """A ``cond`` primitive reachable WITHOUT descending into pallas_call
    kernel bodies: the spill-fallback reachability signal (a ``pl.when``
    inside the kernel itself guards nothing about the spill result)."""
    j = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in j.eqns:
        if eqn.primitive.name == "cond":
            return True
        if eqn.primitive.name == "pallas_call":
            continue
        for sub in trace.eqn_subjaxprs(eqn):
            if _has_cond_outside_kernels(sub):
                return True
    return False


def collect_pallas_calls(traces: dict) -> list[PallasCallInfo]:
    """Digest every pallas_call reachable from ``{program: ClosedJaxpr |
    TraceFailure}``, each distinct binding once."""
    infos: list[PallasCallInfo] = []
    seen: set = set()
    for program, traced in traces.items():
        if isinstance(traced, trace.TraceFailure):
            continue
        has_cond = _has_cond_outside_kernels(traced)
        for eqn, _ in trace.iter_eqns(traced):
            if eqn.primitive.name != "pallas_call":
                continue
            info = digest_eqn(eqn, program, has_cond)
            sig = info.signature()
            if sig in seen:
                continue
            seen.add(sig)
            infos.append(info)
    return infos


# -- kernel-body ref event analysis (for the race lint) ----------------------

# Ref-access primitives in pallas kernel jaxprs: `ref[...]` reads lower to
# `get`, `ref[...] = x` to `swap` (result unused), accumulation to
# `addupdate` (an atomic read-modify-write).
_READS = {"get", "masked_load"}
_WRITES = {"swap", "masked_swap"}
_RMW = {"addupdate"}


@dataclasses.dataclass(frozen=True)
class RefEvent:
    kind: str  # "read" | "write"
    guarded: bool  # inside a cond branch (pl.when / lax.cond)
    order: int  # program-order index within the kernel body


def ref_events(kernel_jaxpr) -> dict[int, list[RefEvent]]:
    """Per-ref read/write events of a kernel body, in program order.

    Returns ``{kernel_invar_position: [RefEvent, ...]}``.  Conditional
    scopes (``pl.when`` lowers to ``cond``) mark their events guarded;
    refs closed over into branch/body jaxprs are followed through the
    equation's invars (branch invars map 1:1 onto ``eqn.invars[1:]`` for
    cond, onto ``eqn.invars`` for jit-style calls).
    """
    j = getattr(kernel_jaxpr, "jaxpr", kernel_jaxpr)
    root_refs = {v: i for i, v in enumerate(j.invars)}
    events: dict[int, list[RefEvent]] = {}
    counter = [0]

    def record(pos: int, kind: str, guarded: bool) -> None:
        events.setdefault(pos, []).append(
            RefEvent(kind=kind, guarded=guarded, order=counter[0]))

    def lookup(refmap: dict, v) -> Optional[int]:
        # Equation operands may be unhashable Literals, never refs.
        try:
            return refmap.get(v)
        except TypeError:
            return None

    def walk(jaxpr, refmap: dict, guarded: bool) -> None:
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            counter[0] += 1
            if name in _READS or name in _WRITES or name in _RMW:
                pos = lookup(refmap, eqn.invars[0])
                if pos is not None:
                    if name in _READS or name in _RMW:
                        record(pos, "read", guarded)
                    if name in _WRITES or name in _RMW:
                        record(pos, "write", guarded)
                continue
            subs = trace.eqn_subjaxprs(eqn)
            if not subs:
                continue
            sub_guarded = guarded or name == "cond"
            # Map refs that flow into the sub-jaxpr: cond passes operands
            # [pred, *args] with branch invars = args; call-like primitives
            # (jit, scan, while) pass operands 1:1 (scan/while carry
            # prefixes don't matter here — only ref-typed vars can match).
            operands = eqn.invars[1:] if name == "cond" else eqn.invars
            for sub in subs:
                sj = getattr(sub, "jaxpr", sub)
                submap: dict = {}
                for outer, inner in zip(operands, sj.invars):
                    pos = lookup(refmap, outer)
                    if pos is not None:
                        submap[inner] = pos
                if len(sj.invars) != len(operands) and not submap:
                    # Arity mismatch (consts prefix, carry layout): retry
                    # aligning from the tail, where pallas puts refs.
                    for outer, inner in zip(reversed(operands),
                                            reversed(sj.invars)):
                        pos = lookup(refmap, outer)
                        if pos is not None:
                            submap[inner] = pos
                if submap:
                    walk(sj, submap, sub_guarded)

    walk(j, dict(root_refs), False)
    return events
