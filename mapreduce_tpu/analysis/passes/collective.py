"""Pass: mesh-aware collective pricing + SPMD divergence lint (ISSUE 16).

Two jobs, both over the traced engine ``step``/``finish`` programs:

1. **Collective cost** (artifact ``collective_cost``): every collective
   equation is attributed to its mesh axis, the axis to a link level
   (ICI within a host/slice, DCN across — the process-major contract of
   ``parallel/mesh.two_level_mesh``; ``AnalysisContext.fleet`` carries
   the simulated topology of the ``*_fleet`` registry twins), and the
   payload priced through the alpha-beta schedules of
   :mod:`..meshcost` — bytes x link level x schedule -> modeled
   seconds.  The ``collective`` byte family the hbm-cost pass tallies
   but cannot price finally gets seconds, and the hbm-cost artifact's
   ``collective.priced`` marker is flipped true with the modeled total
   attached.  Modeled seconds are baseline-gated like effective input
   passes (``analysis/baselines/<model>.collective.json``, same 20%
   tolerance, same ``--write-baselines`` regeneration).

2. **SPMD divergence lint**: a collective reachable under
   *device-varying* control flow is the static form of the distributed
   hang the chaos harness can only catch dynamically — some
   participants enter the collective, others take the branch without
   it, and the fleet deadlocks.  Inside ``shard_map`` scopes the pass
   runs a varying-taint dataflow (shard-body inputs vary per device;
   ``psum``-family outputs are uniform once they cover every bound
   axis; ``axis_index`` is varying by construction) and ERRORs any
   ``cond``/``switch`` whose predicate is varying while its branches
   disagree on the collectives they execute — a collective in one
   branch only, the same collective over mismatched axis names, or any
   other signature divergence.  Branches that agree (or conds under
   uniform predicates — every participant takes the same branch) stay
   quiet, so the spill-fallback conds of the shipped models pass.
"""

from __future__ import annotations

import json
import os

import jax.extend.core as jex_core

from mapreduce_tpu.analysis import core, costmodel, meshcost, trace
from mapreduce_tpu.analysis.passes.cost import (REGRESSION_TOLERANCE,
                                                _BASELINES_DIR)

# Communicating collectives (axis_index is per-device arithmetic: it
# varies, but it moves no bytes and cannot hang a peer).
_COMM = frozenset(costmodel._COLLECTIVES) - {"axis_index"} | {"psum_scatter"}

# Collectives whose outputs are identical on every participant after the
# reduction — taint stops here IF the eqn covers every bound mesh axis
# (a psum over only the inner axis of a 2-D mesh still varies across the
# outer one).
_UNIFORMING = frozenset({"psum", "pmax", "pmin", "all_gather", "pbroadcast"})

_LINT_CAP = 8  # findings per program before the pass summarizes
_ENTRY_CAP = 32  # per-program priced-eqn entries kept in the artifact


def collective_baseline_path(model: str,
                             baselines_dir: str | None = None) -> str:
    return os.path.join(baselines_dir or _BASELINES_DIR,
                        f"{model}.collective.json")


def load_collective_baseline(model: str, baselines_dir: str | None = None):
    path = collective_baseline_path(model, baselines_dir)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def _is_literal(v) -> bool:
    return isinstance(v, jex_core.Literal)


def _unwrap(jaxpr):
    return getattr(jaxpr, "jaxpr", jaxpr)


@core.register_pass
class CollectivePass:
    pass_id = "collective-cost"
    description = ("price collective bytes per mesh axis / link level "
                   "(ICI vs DCN, meshcost schedules) with a baseline "
                   "gate; ERROR on collectives under device-varying "
                   "control flow (SPMD divergence)")

    def run(self, ctx: core.AnalysisContext) -> list[core.Finding]:
        out: list[core.Finding] = []
        names = tuple(ctx.mesh.axis_names)
        sizes = tuple(int(ctx.mesh.shape[n]) for n in names)
        fleet = dict(getattr(ctx, "fleet", None) or {})
        processes = int(fleet.get("processes", 1))
        mesh_spec = meshcost.MeshSpec.from_mesh(names, sizes, processes)
        rates = meshcost.load_link_rates()
        levels = rates["levels"]

        art: dict = {
            "mesh": {"axes": [{"name": a.name, "size": a.size,
                               "level": a.level} for a in mesh_spec.axes],
                     "devices": mesh_spec.n_devices,
                     "processes": processes,
                     "label": mesh_spec.label()},
            "link_rates": {lv.name: {"alpha_s": lv.alpha_s,
                                     "beta_gbps": lv.beta_bps / 1e9}
                           for lv in levels.values()},
            "programs": {},
        }
        total_s = 0.0
        total_bytes = 0
        for hook, traced in ctx.engine_traces.items():
            if isinstance(traced, trace.TraceFailure):
                continue  # the sharding pass owns trace-failure reporting
            entries: list = []
            unpriced: list = []
            s, b = self._price_walk(traced, 1, mesh_spec, levels,
                                    entries, unpriced)
            art["programs"][hook] = {
                "modeled_s": round(s, 9), "bytes": b,
                "collectives": entries[:_ENTRY_CAP],
                "truncated": max(0, len(entries) - _ENTRY_CAP),
                "unpriced": unpriced[:_ENTRY_CAP]}
            total_s += s
            total_bytes += b
            if unpriced:
                out.append(core.Finding(
                    severity=core.WARNING, pass_id=self.pass_id,
                    model=ctx.model, hook=hook,
                    message=(f"{len(unpriced)} collective eqn(s) over axes "
                             "the mesh spec cannot attribute to a link "
                             f"level (e.g. {unpriced[0]['prim']} over "
                             f"{unpriced[0]['axes']}); their bytes are "
                             "tallied but not priced"),
                    hint="axis names must match the analysis mesh "
                         "(sharding-lint owns unknown-axis errors)"))
            out.extend(self._lint_program(ctx, hook, traced))
        art["modeled_total_s"] = round(total_s, 9)
        art["total_bytes"] = total_bytes

        if total_bytes or any(p["collectives"]
                              for p in art["programs"].values()):
            ctx.artifacts["collective_cost"] = art
            self._mark_priced(ctx, total_s)
            per_level: dict = {}
            for prog in art["programs"].values():
                for e in prog["collectives"]:
                    for pa in e["per_axis"]:
                        per_level[pa["level"]] = \
                            per_level.get(pa["level"], 0.0) + pa["seconds"]
            levels_txt = ", ".join(f"{k}={v * 1e6:.1f}us"
                                   for k, v in sorted(per_level.items()))
            out.append(core.Finding(
                severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"collectives modeled at {total_s * 1e6:.1f}us "
                         f"over mesh {art['mesh']['label']} "
                         f"({total_bytes} bytes; {levels_txt})"),
                hint="alpha-beta bound from "
                     "analysis/baselines/measured_link_rates.json; "
                     "congestion-free, per-device"))
            out.extend(self._baseline_findings(ctx, art))
        return out

    # -- pricing walk (mirrors costmodel.program_cost's control rules) ----

    def _price_walk(self, jaxpr, times, mesh_spec, levels, entries,
                    unpriced):
        """Accumulate (modeled seconds, collective bytes) for one program
        region, multiplied by ``times`` (scan bodies run length times;
        cond charges its costlier branch; while bodies are a one-trip
        lower bound) — the exact control rules of
        :func:`..costmodel.program_cost`, so the bytes priced here equal
        the ``collective_bytes`` the hbm-cost artifact reports."""
        j = _unwrap(jaxpr)
        total_s = 0.0
        total_b = 0
        for eqn in j.eqns:
            prim = eqn.primitive.name
            if prim in _COMM:
                payload = sum(costmodel._aval_bytes(v.aval)
                              for v in eqn.invars)
                axes = trace.eqn_axis_names(eqn)
                priced = meshcost.price_eqn(prim, payload, axes, mesh_spec,
                                            levels)
                loc = trace.eqn_location(eqn)
                if priced is None:
                    unpriced.append({"prim": prim, "bytes": payload,
                                     "axes": axes, "location": loc})
                    total_b += payload * times
                    continue
                entries.append({
                    "prim": prim, "bytes": payload, "times": times,
                    "axes": axes, "schedule": priced["schedule"],
                    "seconds": round(priced["seconds"] * times, 9),
                    "per_axis": [dict(pa, seconds=round(
                        pa["seconds"] * times, 9))
                        for pa in priced["per_axis"]],
                    "location": loc})
                total_s += priced["seconds"] * times
                total_b += payload * times
                continue
            subs = trace.eqn_subjaxprs(eqn)
            if not subs or prim == "pallas_call":
                continue
            if prim == "cond":
                costs = [costmodel.program_cost(s) for s in subs]
                pick = max(range(len(subs)),
                           key=lambda i: costs[i].hbm_bytes + costs[i].flops)
                s, b = self._price_walk(subs[pick], times, mesh_spec,
                                        levels, entries, unpriced)
            elif prim == "scan":
                length = int(eqn.params.get("length", 1) or 1)
                s = b = 0
                for sub in subs:
                    ss, sb = self._price_walk(sub, times * length, mesh_spec,
                                              levels, entries, unpriced)
                    s, b = s + ss, b + sb
            else:  # jit / while / shard_map / custom calls: once through
                s = b = 0
                for sub in subs:
                    ss, sb = self._price_walk(sub, times, mesh_spec, levels,
                                              entries, unpriced)
                    s, b = s + ss, b + sb
            total_s += s
            total_b += b
        return total_s, total_b

    def _mark_priced(self, ctx, total_s) -> None:
        cost_art = ctx.artifacts.get("cost")
        coll = cost_art.get("collective") if isinstance(cost_art, dict) \
            else None
        if isinstance(coll, dict):
            coll["priced"] = True
            coll["modeled_s"] = round(total_s, 9)
            coll["priced_by"] = self.pass_id

    # -- baseline regression gate (hbm-cost discipline) -------------------

    def _baseline_findings(self, ctx, art) -> list[core.Finding]:
        modeled = art["modeled_total_s"]
        if ctx.write_baselines:
            path = collective_baseline_path(ctx.model, ctx.baselines_dir)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                json.dump({
                    "model": ctx.model,
                    "modeled_total_s": modeled,
                    "total_bytes": art["total_bytes"],
                    "mesh": art["mesh"]["label"],
                    "_regenerate":
                        "python -m mapreduce_tpu.analysis --write-baselines",
                }, f, indent=2)
                f.write("\n")
            return [core.Finding(
                severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
                hook="step", message=f"collective baseline written: {path}")]
        base = load_collective_baseline(ctx.model, ctx.baselines_dir)
        if base is None:
            return [core.Finding(
                severity=core.WARNING, pass_id=self.pass_id,
                model=ctx.model, hook="step",
                message="no collective-cost baseline checked in for this "
                        "model",
                hint="regenerate with `python -m mapreduce_tpu.analysis "
                     f"{ctx.model} --write-baselines` and commit the JSON")]
        if base.get("mesh") != art["mesh"]["label"]:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"collective baseline priced mesh "
                         f"{base.get('mesh')!r} but this run traced "
                         f"{art['mesh']['label']!r}: modeled seconds are "
                         "not comparable"),
                hint="re-baseline deliberately (--write-baselines) after "
                     "a topology change")]
        ref = float(base.get("modeled_total_s", 0.0))
        art["baseline_modeled_total_s"] = ref
        if ref <= 0:
            return []
        growth = (modeled - ref) / ref
        if growth > REGRESSION_TOLERANCE:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"modeled collective seconds regressed "
                         f"{growth:+.0%}: {modeled * 1e6:.1f}us vs baseline "
                         f"{ref * 1e6:.1f}us (gate: "
                         f"{REGRESSION_TOLERANCE:.0%})"),
                hint="either fix the regression or regenerate baselines "
                     "deliberately (--write-baselines)")]
        if growth < -REGRESSION_TOLERANCE:
            return [core.Finding(
                severity=core.WARNING, pass_id=self.pass_id,
                model=ctx.model, hook="step",
                message=(f"modeled collective seconds improved {growth:+.0%}"
                         f" vs baseline {ref * 1e6:.1f}us"),
                hint="nice — re-baseline (--write-baselines) so the gate "
                     "protects the win")]
        return []

    # -- SPMD divergence lint ---------------------------------------------

    def _lint_program(self, ctx, hook, traced) -> list[core.Finding]:
        findings: list[core.Finding] = []
        self._lint_walk(ctx, hook, traced, varying=set(),
                        bound_axes=frozenset(), in_shard=False,
                        findings=findings, seen=set())
        if len(findings) > _LINT_CAP:
            kept, dropped = findings[:_LINT_CAP], len(findings) - _LINT_CAP
            kept.append(core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook=hook,
                message=f"... and {dropped} further divergent-collective "
                        "finding(s) suppressed"))
            return kept
        return findings

    def _collective_signature(self, jaxpr) -> tuple:
        """Canonical multiset of (collective, sorted axis names) a region
        executes — branches of a device-varying cond must agree on it."""
        sig = []
        for eqn, _ in trace.iter_eqns(jaxpr):
            if eqn.primitive.name in _COMM:
                sig.append((eqn.primitive.name,
                            tuple(sorted(trace.eqn_axis_names(eqn)))))
        return tuple(sorted(sig))

    def _divergence_finding(self, ctx, hook, eqn, sigs) -> core.Finding:
        loc = trace.eqn_location(eqn)
        prims = [tuple(p for p, _ in s) for s in sigs]
        n_empty = sum(1 for s in sigs if not s)
        if 0 < n_empty < len(sigs):
            msg = ("collective(s) "
                   f"{sorted({p for s in sigs for p, _ in s})} run in "
                   f"{len(sigs) - n_empty} of {len(sigs)} branches of a "
                   "cond whose predicate varies per device: participants "
                   "taking the empty branch never enter the collective — "
                   "a distributed hang")
            hint = ("hoist the collective out of the cond, or make the "
                    "predicate uniform (reduce it with psum/pmax first)")
        elif len(set(prims)) == 1:
            axes = sorted({a for s in sigs for _, ax in s for a in ax})
            msg = (f"branches of a device-varying cond run the same "
                   f"collective(s) over MISMATCHED axis names {axes}: "
                   "device groups disagree on who participates — a "
                   "distributed hang (or a silent wrong-group reduction)")
            hint = ("use one axis name on every path (the axis the engine "
                    "passes to map_chunk_sharded)")
        else:
            msg = (f"branches of a device-varying cond execute different "
                   f"collective programs {sorted(set(prims))}: "
                   "participants diverge at the first mismatched "
                   "collective — a distributed hang")
            hint = ("make every branch execute the same collective "
                    "sequence, or branch on a uniform predicate")
        return core.Finding(severity=core.ERROR, pass_id=self.pass_id,
                            model=ctx.model, hook=hook, message=msg,
                            location=loc, hint=hint)

    def _lint_walk(self, ctx, hook, jaxpr, varying, bound_axes, in_shard,
                   findings, seen) -> None:
        """Varying-taint dataflow over one jaxpr scope.  ``varying`` is
        the set of this scope's Vars known to differ across devices of
        the bound axes; sub-jaxpr scopes are seeded conservatively (any
        tainted operand taints every body input)."""
        j = _unwrap(jaxpr)
        for eqn in j.eqns:
            prim = eqn.primitive.name
            operands = [v for v in eqn.invars if not _is_literal(v)]
            tainted_in = any(v in varying for v in operands)
            subs = trace.eqn_subjaxprs(eqn)

            if prim == "shard_map":
                mesh = eqn.params.get("mesh")
                axes = frozenset(a for a in
                                 (getattr(mesh, "axis_names", ()) or ())
                                 if isinstance(a, str))
                for sub in subs:
                    sj = _unwrap(sub)
                    self._lint_walk(ctx, hook, sub,
                                    varying=set(sj.invars),
                                    bound_axes=bound_axes | axes,
                                    in_shard=True, findings=findings,
                                    seen=seen)
                # Outputs at this scope are the stacked global arrays —
                # not per-device values of an enclosing shard scope.
                continue

            if prim == "cond" and subs:
                pred = eqn.invars[0]
                pred_varying = in_shard and not _is_literal(pred) \
                    and pred in varying
                if pred_varying:
                    sigs = [self._collective_signature(s) for s in subs]
                    if len(set(sigs)) > 1:
                        key = ("cond", trace.eqn_location(eqn),
                               tuple(sigs))
                        if key not in seen:
                            seen.add(key)
                            findings.append(self._divergence_finding(
                                ctx, hook, eqn, sigs))
                for sub in subs:
                    sj = _unwrap(sub)
                    self._lint_walk(
                        ctx, hook, sub,
                        varying=set(sj.invars) if (in_shard and tainted_in)
                        else set(),
                        bound_axes=bound_axes, in_shard=in_shard,
                        findings=findings, seen=seen)
                if tainted_in:
                    varying.update(eqn.outvars)
                continue

            if subs and prim != "pallas_call":
                for sub in subs:
                    sj = _unwrap(sub)
                    self._lint_walk(
                        ctx, hook, sub,
                        varying=set(sj.invars) if (in_shard and tainted_in)
                        else set(),
                        bound_axes=bound_axes, in_shard=in_shard,
                        findings=findings, seen=seen)

            if prim == "axis_index" and in_shard:
                varying.update(eqn.outvars)
                continue
            if prim in _UNIFORMING and in_shard:
                # Uniform across every axis the eqn reduces/gathers over;
                # still varying if some bound axis is uncovered.
                if set(trace.eqn_axis_names(eqn)) >= bound_axes:
                    continue
                varying.update(eqn.outvars)
                continue
            if tainted_in:
                varying.update(eqn.outvars)
