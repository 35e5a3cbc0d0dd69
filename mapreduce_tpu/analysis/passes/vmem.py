"""Pass: static VMEM/SMEM budget certifier for Pallas kernels.

Two legs, both from BlockSpec/grid/scratch shapes alone:

* **traced bindings** — every ``pallas_call`` reachable from the model's
  step/finish programs is footprinted (pipelined in/out blocks twice —
  Pallas double-buffers grid blocks so the next DMA overlaps compute —
  plus scratch once) and checked against the kernel's own declared
  ``vmem_limit_bytes`` (else Mosaic's 16 MB default), the physical
  ceiling, and the SMEM budget;
* **shipped geometries** — the production kernel plans the modules declare
  via their metadata hooks (``ops/pallas/meta.production_plans()``) are
  certified the same way, so the stable2/sort3 production shapes
  stay covered even though analysis configs trace toy grids
  (:func:`certify_production_kernels`, run once per pipeline by the CLI).

The pass also checks **spill-fallback reachability**: a kernel whose
metadata declares spill semantics (compact tokenize)
emits a counter that callers MUST gate an exactness fallback on — a
traced program containing such a kernel but no ``cond`` primitive at all
has statically unreachable fallback, which is how "always exact" silently
becomes "usually exact".
"""

from __future__ import annotations

from mapreduce_tpu.analysis import core
from mapreduce_tpu.ops.pallas import meta


def _footprint(info) -> tuple[int, int]:
    """(vmem_bytes, smem_bytes) of one traced binding: in/out blocks are
    double-buffered, scratch is resident once."""
    vmem = smem = 0
    for r in info.refs:
        mult = 1 if r.role == "scratch" else 2
        if r.memory_space == "smem":
            smem += r.block_bytes * mult
        elif r.memory_space in ("vmem", "any", "?"):
            # Unknown spaces are charged as VMEM: over-counting toward the
            # budget is the safe direction for a certifier.
            vmem += r.block_bytes * mult
    return vmem, smem


def _budget_findings(pass_id, model, hook, label, vmem, smem, limit,
                     location="") -> list[core.Finding]:
    out = []
    budget = limit or meta.VMEM_DEFAULT_LIMIT
    if budget > meta.VMEM_PHYSICAL:
        out.append(core.Finding(
            severity=core.ERROR, pass_id=pass_id, model=model, hook=hook,
            message=(f"{label}: declared vmem_limit_bytes "
                     f"{budget >> 20} MiB exceeds the {meta.VMEM_PHYSICAL >> 20}"
                     f" MiB physical VMEM"),
            location=location,
            hint="lower the compiler-params override; the physical core "
                 "cannot back it"))
    if vmem > budget:
        out.append(core.Finding(
            severity=core.ERROR, pass_id=pass_id, model=model, hook=hook,
            message=(f"{label}: static VMEM footprint {vmem >> 10} KiB "
                     f"exceeds the {budget >> 20} MiB budget "
                     "(double-buffered blocks + scratch)"),
            location=location,
            hint="shrink block shapes or raise vmem_limit_bytes (<= "
                 f"{meta.VMEM_PHYSICAL >> 20} MiB physical) deliberately"))
    if smem > meta.SMEM_BUDGET:
        out.append(core.Finding(
            severity=core.ERROR, pass_id=pass_id, model=model, hook=hook,
            message=(f"{label}: SMEM footprint {smem} B exceeds the "
                     f"{meta.SMEM_BUDGET >> 10} KiB budget"),
            location=location,
            hint="SMEM holds scalars/control only; move bulk state to VMEM"))
    return out


@core.register_pass
class VmemPass:
    pass_id = "vmem-budget"
    description = ("static VMEM/SMEM footprint of every traced Pallas "
                   "kernel vs per-core budgets; spill-fallback "
                   "reachability")

    def run(self, ctx: core.AnalysisContext) -> list[core.Finding]:
        out: list[core.Finding] = []
        kernels = []
        for info in ctx.pallas_calls:
            vmem, smem = _footprint(info)
            out.extend(_budget_findings(
                self.pass_id, ctx.model, info.program, info.kernel_name,
                vmem, smem, info.vmem_limit_bytes, location=info.src))
            kernels.append({"kernel": info.kernel_name,
                            "program": info.program,
                            "grid": list(info.grid),
                            "vmem_bytes": vmem, "smem_bytes": smem,
                            "vmem_limit_bytes": info.vmem_limit_bytes})
            out.extend(self._spill_findings(ctx, info))
        if kernels:
            ctx.artifacts["vmem"] = kernels
            out.append(core.Finding(
                severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=f"{len(kernels)} pallas kernel binding(s) "
                        "certified under the VMEM/SMEM budgets"))
        return out

    def _spill_findings(self, ctx, info) -> list[core.Finding]:
        km = meta.lookup(info.kernel_name)
        if km is None or not km.spills(len(info.outs)):
            return []
        if info.enclosing_has_cond:
            return []
        return [core.Finding(
            severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
            hook=info.program,
            message=(f"{info.kernel_name} emits a spill counter but the "
                     f"traced {info.program} program contains no cond: "
                     "the exactness fallback is statically unreachable"),
            location=info.src,
            hint="gate a fallback on the spill scalar with lax.cond (the "
                 "compact-path idiom, models/wordcount._map_stream)")]


def certify_production_kernels() -> list[core.Finding]:
    """Certify every SHIPPED kernel geometry's declared plan (the
    metadata hooks in ops/pallas/*) against the budgets — run once per
    pipeline invocation (CLI/tests), not per model."""
    out: list[core.Finding] = []
    for plan in meta.production_plans():
        found = _budget_findings(
            VmemPass.pass_id, "<kernels>", "production",
            f"{plan.kernel} [{plan.geometry}]",
            plan.vmem_bytes, plan.smem_bytes, plan.vmem_limit_bytes)
        out.extend(found)
        if not found:
            out.append(core.Finding(
                severity=core.INFO, pass_id=VmemPass.pass_id,
                model="<kernels>", hook="production",
                message=(f"{plan.kernel} [{plan.geometry}]: "
                         f"{plan.vmem_bytes >> 10} KiB VMEM + "
                         f"{plan.smem_bytes} B SMEM within the "
                         f"{plan.budget >> 20} MiB budget")))
    return out
