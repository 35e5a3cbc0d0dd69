"""Pass: Pallas ref-hazard lint across grid iterations.

TPU grids execute sequentially, and the shipped kernels lean on that hard:
the tokenize carry scratch hands the lookback window from block to block,
and its running counters (overlong, tokens, spill) live in revisited SMEM
outputs.  Those patterns are correct exactly when they keep a narrow
discipline, and this lint checks the discipline statically on every
traced kernel body:

* a ref REVISITED across grid iterations (scratch, or an output whose
  index map sends two iterations to the same block) must only be written
  via **read-modify-write** (a read of the same ref earlier in the body)
  or under a **guard** (``pl.when``/``cond``) — an unguarded blind write
  is a cross-iteration write/write hazard: iteration *i+1* clobbers what
  iteration *i* produced (ERROR);
* a revisited ref whose first access is an unguarded READ with no guarded
  write anywhere reads uninitialized memory on iteration 0 (WARNING —
  Mosaic zero-fills some scratch, but relying on it is exactly the class
  of latent bug the SMEM-histogram pattern hides);
* a write to an INPUT block ref is always an ERROR;
* ``dimension_semantics`` declaring a ``parallel`` grid dimension while
  the kernel carries cross-iteration state (scratch or revisited refs)
  breaks the sequential-grid assumption outright (ERROR).

The event extraction (``get``/``swap``/``addupdate`` walking, cond-guard
tracking) lives in :mod:`..pallas_info` so the vmem pass shares the
digested view.
"""

from __future__ import annotations

from mapreduce_tpu.analysis import core, pallas_info


def _ref_label(info, pos: int) -> str:
    """Human label of kernel invar position ``pos``."""
    n_in = len(info.ins)
    n_out = len(info.outs)
    if pos < n_in:
        r = info.ins[pos]
    elif pos < n_in + n_out:
        r = info.outs[pos - n_in]
    else:
        r = info.scratch[pos - n_in - n_out]
    return (f"{r.role}[{r.index}] {r.memory_space} "
            f"{tuple(r.block_shape)}")


def _ref_at(info, pos: int):
    n_in, n_out = len(info.ins), len(info.outs)
    if pos < n_in:
        return info.ins[pos]
    if pos < n_in + n_out:
        return info.outs[pos - n_in]
    if pos < n_in + n_out + len(info.scratch):
        return info.scratch[pos - n_in - n_out]
    return None


@core.register_pass
class KernelRacePass:
    pass_id = "kernel-race"
    description = ("cross-grid-iteration write/write and uninitialized-"
                   "read hazards on Pallas refs (SMEM accumulators, "
                   "carry scratch, revisited output blocks)")

    def run(self, ctx: core.AnalysisContext) -> list[core.Finding]:
        out: list[core.Finding] = []
        for info in ctx.pallas_calls:
            out.extend(self._kernel_findings(ctx, info))
        return out

    def _kernel_findings(self, ctx, info) -> list[core.Finding]:
        out: list[core.Finding] = []
        events = pallas_info.ref_events(info.kernel_jaxpr)
        carries_state = bool(info.scratch) or any(
            r.revisited for r in info.outs)

        sem = info.dimension_semantics
        if sem and any("parallel" in str(s).lower() for s in sem) \
                and carries_state:
            out.append(core.Finding(
                severity=core.ERROR, pass_id=self.pass_id,
                model=ctx.model, hook=info.program,
                message=(f"{info.kernel_name}: 'parallel' grid dimension "
                         "declared but the kernel carries cross-iteration "
                         "state (scratch / revisited blocks)"),
                location=info.src,
                hint="drop the parallel dimension_semantics or make every "
                     "iteration's blocks disjoint"))

        for pos, evts in sorted(events.items()):
            ref = _ref_at(info, pos)
            if ref is None:
                continue
            label = _ref_label(info, pos)
            if ref.role == "in" and any(e.kind == "write" for e in evts):
                out.append(core.Finding(
                    severity=core.ERROR, pass_id=self.pass_id,
                    model=ctx.model, hook=info.program,
                    message=f"{info.kernel_name}: write to input ref "
                            f"{label}",
                    location=info.src,
                    hint="inputs are read-only views of the HBM operand; "
                         "stage through scratch or an output"))
                continue
            if ref.role == "in":
                # Revisited INPUT blocks (an index map pinning every
                # iteration to the same operand block — the fused seam-aux
                # plane) are re-fetched from HBM, never uninitialized, and
                # unwritable per the check above: no cross-iteration hazard.
                continue
            if not ref.revisited:
                # Disjoint blocks per iteration: blind writes are the
                # normal output pattern; nothing cross-iteration to race.
                continue
            ordered = sorted(evts, key=lambda e: e.order)
            # Rule A: every unguarded write must be RMW — preceded by a
            # read of the same ref in body order.
            for e in ordered:
                if e.kind != "write" or e.guarded:
                    continue
                has_prior_read = any(r.kind == "read" and r.order <= e.order
                                     for r in ordered)
                if not has_prior_read:
                    out.append(core.Finding(
                        severity=core.ERROR, pass_id=self.pass_id,
                        model=ctx.model, hook=info.program,
                        message=(f"{info.kernel_name}: unguarded blind "
                                 f"write to revisited ref {label} — grid "
                                 "iterations overwrite each other "
                                 "(write/write hazard)"),
                        location=info.src,
                        hint="accumulate (read-modify-write), or guard the "
                             "write with pl.when on the revisit phase, or "
                             "make the index map injective over the grid"))
                    break
            # Rule B: first access an unguarded read + no guarded init.
            first = ordered[0] if ordered else None
            has_guarded_write = any(e.kind == "write" and e.guarded
                                    for e in ordered)
            if first is not None and first.kind == "read" \
                    and not first.guarded and not has_guarded_write:
                out.append(core.Finding(
                    severity=core.WARNING, pass_id=self.pass_id,
                    model=ctx.model, hook=info.program,
                    message=(f"{info.kernel_name}: revisited ref {label} "
                             "is read before any guarded initialization — "
                             "iteration 0 sees uninitialized memory"),
                    location=info.src,
                    hint="zero it under pl.when(first-iteration) like the "
                         "tokenize carry idiom"))
        if not out and (carries_state or info.scratch):
            out.append(core.Finding(
                severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
                hook=info.program,
                message=(f"{info.kernel_name}: cross-iteration refs follow "
                         "the guarded-init + read-modify-write discipline"),
                location=info.src))
        return out
