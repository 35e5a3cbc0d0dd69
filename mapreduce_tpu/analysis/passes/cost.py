"""Pass: static HBM/FLOP cost certifier with a measured-rate cross-check.

Three jobs, all CI-gateable:

1. **Cost report** (artifact ``cost``): per-program HBM bytes read/written,
   FLOPs, and family shares from the traced step/finish jaxprs
   (:mod:`..costmodel`), plus ``effective input passes`` — how many times
   the step program streams its own chunk through HBM.  The unit the
   round-5 dead-end notes prices in, now computed by machine.

2. **Sort-pricing cross-check**: the round-6 ledger's central measured
   claim — the XLA aggregation sort runs at **2.6-3.4 effective HBM
   passes** — becomes an asserted artifact.  The pass re-derives the
   stable2 sort's row count from kernel geometry
   (:func:`..costmodel.stable2_sort_rows`), requires the traced sort
   equation to match it EXACTLY at the model's own config (the static
   leg), extrapolates to the production chunk, and recomputes the pass
   range from the measured fixture (sort ms / one-pass ms at the measured
   HBM rate).  Outside the declared tolerance of the claimed range →
   ERROR: either the kernel geometry drifted (row count changed) or the
   fixture is stale — both must be resolved deliberately, not in prose.

3. **Baseline regression gate**: each shipped model's predicted effective
   passes is checked into ``analysis/baselines/<model>.json``.  Growth
   beyond ``REGRESSION_TOLERANCE`` (20%) fails the pipeline unless the
   baselines are intentionally regenerated (``--write-baselines``); a
   SHRINK past the same margin is only a warning nudging a re-baseline.

4. **Fused-vs-split gate** (ISSUE 6): a model whose config runs the fused
   map path (``Config.map_impl='fused'``) must price STRICTLY below its
   split-path counterpart's checked-in baseline — the machine-checked
   before/after that certifies the fusion actually deleted HBM traffic
   instead of moving it.  Counterpart pairs are declared in
   ``_SPLIT_COUNTERPART``; a fused model without one is an ERROR too (an
   ungated fusion is exactly the unmeasured claim this pass exists to
   forbid).
"""

from __future__ import annotations

import json
import os

from mapreduce_tpu.analysis import core, costmodel, trace

REGRESSION_TOLERANCE = 0.20

# Fused-map registry models gated against their split-path twin's baseline
# (same chunk geometry, Config.map_impl the only delta — see
# models.FUSED_ANALYSIS_CONFIG).
_SPLIT_COUNTERPART = {"wordcount_fused": "wordcount_pallas",
                      "wordcount_fused_telemetry": "wordcount_telemetry"}

# Combiner registry models gated against their combiner-OFF twin's baseline
# (same chunk geometry, Config.combiner the only delta — ISSUE 11): the
# hot-key cache must price STRICTLY below the uncombined fused path, the
# machine-checked proof that the taller windows it pays for actually
# delete sort traffic.  Models in this dict (and their counterparts) are
# exempt from the fused-vs-split gate: their fused-ness is already
# certified by wordcount_fused at ITS geometry, and this pair exists at a
# different chunk so the combiner's window arithmetic is exact.
_UNCOMBINED_COUNTERPART = {"wordcount_combiner": "wordcount_nocombiner"}
_FUSED_GATE_EXEMPT = set(_UNCOMBINED_COUNTERPART) \
    | set(_UNCOMBINED_COUNTERPART.values())

# Data-stats-instrumented registry models gated against their
# UNINSTRUMENTED twin's baseline (same config, Engine data_stats the only
# delta — ISSUE 8): observability must never silently regress the cost
# certificates, so the instrumented step's effective_input_passes may move
# at most TELEMETRY_TOLERANCE from the plain program's.
_PLAIN_COUNTERPART = {"wordcount_telemetry": "wordcount_pallas",
                      "wordcount_fused_telemetry": "wordcount_fused"}
TELEMETRY_TOLERANCE = 0.01

_BASELINES_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "baselines")
_RATES_PATH = os.path.join(_BASELINES_DIR, "measured_rates.json")


def measured_rates() -> dict:
    with open(_RATES_PATH) as f:
        return json.load(f)


def baseline_path(model: str, baselines_dir: str | None = None) -> str:
    return os.path.join(baselines_dir or _BASELINES_DIR, f"{model}.json")


def load_baseline(model: str, baselines_dir: str | None = None):
    path = baseline_path(model, baselines_dir)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


@core.register_pass
class CostPass:
    pass_id = "hbm-cost"
    description = ("static per-eqn HBM/FLOP cost report; sort pricing "
                   "cross-checked against measured rates; baseline "
                   "regression gate")

    def run(self, ctx: core.AnalysisContext) -> list[core.Finding]:
        out: list[core.Finding] = []
        chunk_bytes = trace._chunk_bytes_for(ctx.job)
        report: dict = {"traced_chunk_bytes": chunk_bytes, "programs": {}}
        config = getattr(ctx.job, "config", None)
        if config is not None and hasattr(config, "geometry_label"):
            # Which kernel-geometry set priced this report (ISSUE 12):
            # candidate geometries are first-class here — every derived
            # figure below re-reads the CANDIDATE's resolved values, not
            # the shipped constants.
            report["geometry"] = config.geometry_label

        step_cost = None
        collective_bytes: dict = {}
        for hook, traced in ctx.engine_traces.items():
            if isinstance(traced, trace.TraceFailure):
                continue  # the sharding pass owns trace-failure reporting
            cost = costmodel.program_cost(traced)
            report["programs"][hook] = cost.as_dict()
            collective_bytes[hook] = cost.collective_bytes
            if hook == "step":
                step_cost = cost
        if step_cost is None:
            return out  # nothing traced; nothing to certify

        # The collective family, surfaced instead of silently excluded
        # (ISSUE 16): these bytes price interconnect, not local HBM, so
        # they stay out of effective_input_passes — but a report that
        # omits them under-states the program's traffic.  ``priced`` stays
        # False here; the collective-cost pass flips it (and attaches the
        # modeled seconds) when it has mesh/link context.
        total_coll = sum(collective_bytes.values())
        report["collective"] = {
            "per_program_bytes": collective_bytes,
            "total_bytes": total_coll,
            "priced": False,
            "note": "interconnect bytes, excluded from the HBM total; "
                    "priced by the collective-cost pass (meshcost link "
                    "model) when mesh context is available"}
        if total_coll:
            out.append(core.Finding(
                severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
                hook="finish" if collective_bytes.get("finish") else "step",
                message=(f"collective family: {total_coll >> 10} KiB "
                         "interconnect traffic "
                         f"({', '.join(f'{h}={b}' for h, b in sorted(collective_bytes.items()))} bytes), "
                         "excluded from the HBM total"),
                hint="the collective-cost pass prices these bytes per "
                     "link level (ICI/DCN) via analysis/meshcost.py"))

        passes = step_cost.hbm_bytes / max(chunk_bytes, 1)
        report["effective_input_passes"] = round(passes, 3)
        out.append(core.Finding(
            severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
            hook="step",
            message=(f"step streams {step_cost.hbm_bytes >> 10} KiB HBM for "
                     f"a {chunk_bytes >> 10} KiB chunk = "
                     f"{passes:.2f} effective input passes "
                     f"({step_cost.flops / 1e6:.1f} MFLOP est.)"),
            hint="worst-case bound: cond charges its costlier branch "
                 "(spill fallbacks); fusible eqns charge zero HBM"))

        out.extend(self._sort_findings(ctx, report))
        out.extend(self._baseline_findings(ctx, report))
        out.extend(self._fused_gate_findings(ctx, report))
        out.extend(self._combiner_gate_findings(ctx, report))
        out.extend(self._telemetry_gate_findings(ctx, report))
        ctx.artifacts["cost"] = report
        return out

    # -- telemetry-overhead gate (ISSUE 8) -------------------------------

    def _telemetry_gate_findings(self, ctx, report) -> list[core.Finding]:
        """An instrumented (data-stats) model must price within
        ``TELEMETRY_TOLERANCE`` of its uninstrumented twin's checked-in
        baseline — observability that silently grows the HBM bill would
        invalidate every cost certificate downstream of it."""
        plain_model = _PLAIN_COUNTERPART.get(ctx.model)
        passes = report.get("effective_input_passes")
        if plain_model is None or passes is None:
            return []
        plain = load_baseline(plain_model, ctx.baselines_dir)
        if plain is None:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"uninstrumented counterpart {plain_model!r} has "
                         "no cost baseline: the telemetry overhead cannot "
                         "be gated"),
                hint=f"regenerate with `python -m mapreduce_tpu.analysis "
                     f"{plain_model} --write-baselines` and commit the JSON")]
        plain_raw = plain.get("effective_input_passes")
        if not isinstance(plain_raw, (int, float)) or plain_raw <= 0 \
                or plain.get("traced_chunk_bytes") \
                != report["traced_chunk_bytes"]:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"counterpart {plain_model!r} baseline is not "
                         f"comparable (passes={plain_raw!r}, chunk="
                         f"{plain.get('traced_chunk_bytes')!r} vs "
                         f"{report['traced_chunk_bytes']}): the telemetry "
                         "overhead cannot be gated"),
                hint="keep the twin configs on the same chunk geometry and "
                     "regenerate the baseline")]
        plain_ref = float(plain_raw)
        overhead = (passes - plain_ref) / plain_ref
        report["telemetry_overhead"] = {
            "plain_model": plain_model,
            "plain_effective_input_passes": plain_ref,
            "instrumented_effective_input_passes": passes,
            "overhead_frac": round(overhead, 5),
            "tolerance": TELEMETRY_TOLERANCE}
        if abs(overhead) > TELEMETRY_TOLERANCE:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"data-stats instrumentation moves "
                         f"effective_input_passes {overhead:+.2%} "
                         f"({passes:.2f} vs {plain_ref:.2f} "
                         f"{plain_model}), past the "
                         f"{TELEMETRY_TOLERANCE:.0%} gate: observability "
                         "is regressing the cost certificates"),
                hint="the stats path grew real HBM traffic — keep the "
                     "counters to predicates the map already computes and "
                     "capacity-sized gauge reductions")]
        return [core.Finding(
            severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
            hook="step",
            message=(f"telemetry overhead certified: {passes:.2f} vs "
                     f"{plain_ref:.2f} uninstrumented "
                     f"({overhead:+.3%}, gate {TELEMETRY_TOLERANCE:.0%})"))]

    # -- the 2.6-3.4-passes artifact ------------------------------------

    def _sort_findings(self, ctx, report) -> list[core.Finding]:
        config = getattr(ctx.job, "config", None)
        step = ctx.engine_traces.get("step")
        if config is None or step is None or \
                isinstance(step, trace.TraceFailure):
            return []
        # The measured claim is about the shipped packed fast path: pallas
        # backend, stable2 comparator, XLA sort implementation, at the
        # DEFAULT 384-row window.  The combiner's 512-row geometry sorts a
        # deliberately different row count — extrapolating the measured
        # 384-geometry sort milliseconds over it would manufacture a
        # phantom pricing drift; its own strictly-below gate
        # (_combiner_gate_findings) owns that geometry instead.
        if config.resolved_backend() != "pallas" or \
                config.sort_mode != "stable2" \
                or config.resolved_combiner_slots:
            return []
        sort = costmodel.find_aggregation_sort(step, num_keys=2)
        if sort is None:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message="pallas/stable2/xla config but no 3-plane "
                        "aggregation sort in the traced step program",
                hint="the packed fast path changed shape; update "
                     "costmodel.find_aggregation_sort with it")]
        expected = costmodel.stable2_sort_rows(
            config.chunk_bytes, config.resolved_block_rows or 256,
            config.resolved_compact_slots)
        rates = measured_rates()
        art = {"traced_rows": sort.rows, "expected_rows": expected,
               "num_keys": sort.num_keys, "location": sort.location}
        report["aggregation_sort"] = art
        if sort.rows != expected:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"aggregation sort carries {sort.rows} rows but "
                         f"kernel geometry predicts {expected} "
                         f"(chunk={config.chunk_bytes}, "
                         f"block_rows={config.resolved_block_rows or 256}, "
                         f"slots={config.resolved_compact_slots})"),
                location=sort.location,
                hint="the sort pricing formula no longer matches the "
                     "program; fix costmodel.stable2_sort_rows or the "
                     "kernel, then re-measure")]
        # A non-default Config.geometry (ISSUE 12): the STATIC leg above
        # already certified the candidate's row arithmetic against the
        # traced program (expected was derived from the candidate's own
        # resolved_block_rows/slots), but the measured sort-milliseconds
        # fixture describes the SHIPPED 384-row geometry — extrapolating
        # it over a different window would manufacture a phantom pricing
        # drift, the combiner-512 lesson.  The candidate's modeled delta
        # lives in the geometry search artifact; the probe pass measures.
        from mapreduce_tpu.config import DEFAULT_GEOMETRY

        if config.resolved_geometry != DEFAULT_GEOMETRY:
            art["measured_leg"] = "skipped: non-default geometry " \
                f"({config.geometry_label}); rates fixture describes the " \
                "shipped default"
            return [core.Finding(
                severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"candidate geometry {config.geometry_label!r}: "
                         f"sort rows {sort.rows} certified against the "
                         "candidate's own window arithmetic; measured-rate "
                         "cross-check pinned to the shipped default "
                         "geometry (probe passes own the measurement)"),
                location=sort.location)]
        # Static extrapolation to the measured production geometry, then
        # the measured-rate leg: passes = sort_ms / one-pass ms.
        prod_rows = costmodel.stable2_sort_rows(
            rates["production_chunk_bytes"],
            config.resolved_block_rows or 256,
            config.resolved_compact_slots)
        pass_ms = (2 * prod_rows * 3 * 4) / (rates["hbm_gbps"] * 1e6)
        lo = rates["sort_ms_range"][0] / pass_ms
        hi = rates["sort_ms_range"][1] / pass_ms
        claimed_lo, claimed_hi = rates["claimed_sort_passes"]
        tol = rates["tolerance"]
        art.update({"production_rows": prod_rows,
                    "one_pass_ms": round(pass_ms, 3),
                    "derived_passes": [round(lo, 3), round(hi, 3)],
                    "claimed_passes": [claimed_lo, claimed_hi],
                    "tolerance": tol})
        ok = (abs(lo - claimed_lo) <= tol * claimed_lo
              and abs(hi - claimed_hi) <= tol * claimed_hi)
        if not ok:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"derived sort cost {lo:.2f}-{hi:.2f} effective "
                         f"HBM passes vs claimed {claimed_lo}-{claimed_hi} "
                         f"(tolerance {tol:.0%}): the round-6 pricing no "
                         "longer holds"),
                location=sort.location,
                hint="re-measure on chip (opshare + round-6 chip notes "
                     "discipline) and update "
                     "analysis/baselines/measured_rates.json deliberately")]
        return [core.Finding(
            severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
            hook="step",
            message=(f"sort pricing certified: {prod_rows} rows at "
                     f"{rates['production_chunk_bytes'] >> 20} MB chunk -> "
                     f"{lo:.2f}-{hi:.2f} effective HBM passes "
                     f"(claimed {claimed_lo}-{claimed_hi})"),
            location=sort.location)]

    # -- fused-vs-split gate (ISSUE 6) ----------------------------------

    def _fused_gate_findings(self, ctx, report) -> list[core.Finding]:
        config = getattr(ctx.job, "config", None)
        passes = report.get("effective_input_passes")
        if config is None or passes is None or config.map_impl != "fused" \
                or config.resolved_backend() != "pallas" \
                or ctx.model in _FUSED_GATE_EXEMPT:
            return []
        split_model = _SPLIT_COUNTERPART.get(ctx.model)
        if split_model is None:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message="fused map path with no declared split counterpart: "
                        "the fusion's win is unmeasured",
                hint="add the pair to cost._SPLIT_COUNTERPART so the gate "
                     "prices the fusion against its split baseline")]
        split = load_baseline(split_model, ctx.baselines_dir)
        if split is None:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"split counterpart {split_model!r} has no cost "
                         "baseline: the fused-vs-split gap cannot be gated"),
                hint=f"regenerate with `python -m mapreduce_tpu.analysis "
                     f"{split_model} --write-baselines` and commit the JSON")]
        split_raw = split.get("effective_input_passes")
        if not isinstance(split_raw, (int, float)) or split_raw <= 0:
            # A broken baseline must name itself: falling through would
            # publish a nonsense gap and misdiagnose as "the fusion
            # stopped deleting traffic".
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"split counterpart {split_model!r} baseline has "
                         f"no usable effective_input_passes "
                         f"({split_raw!r}): the fused-vs-split gap cannot "
                         "be gated"),
                hint=f"regenerate with `python -m mapreduce_tpu.analysis "
                     f"{split_model} --write-baselines` and commit the JSON")]
        split_ref = float(split_raw)
        if split.get("traced_chunk_bytes") != report["traced_chunk_bytes"]:
            # Do NOT publish a gap: bench._cost_record copies the artifact
            # verbatim, and a passes_saved the gate just declared
            # incomparable must not reach BENCH JSON.
            # A baseline MISSING the field is incomparable too — a wildcard
            # match would wave through a different-geometry pricing.
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"fused model traces a "
                         f"{report['traced_chunk_bytes']}-byte chunk but the "
                         f"split counterpart's baseline priced "
                         f"{split.get('traced_chunk_bytes')!r}: the passes "
                         "are not comparable"),
                hint="keep FUSED_ANALYSIS_CONFIG and the split model's "
                     "config on the same chunk geometry (regenerate the "
                     "baseline if it predates geometry recording)")]
        # Geometry certified comparable: publish the gap (bench copies it
        # into BENCH JSON; a LOSING gap still publishes — it is comparable
        # evidence, and the ERROR below gates it).
        report["fused_vs_split"] = {
            "split_model": split_model,
            "split_effective_input_passes": split_ref,
            "fused_effective_input_passes": passes,
            "passes_saved": round(split_ref - passes, 3)}
        if passes >= split_ref:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"fused map path prices {passes:.2f} effective HBM "
                         f"passes, NOT strictly below the split baseline "
                         f"{split_ref:.2f} ({split_model}): the fusion "
                         "stopped deleting traffic"),
                hint="the token-plane round-trip crept back in (or the "
                     "split baseline is stale); fix the kernel path or "
                     "re-measure deliberately and record it in PERF.md")]
        return [core.Finding(
            severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
            hook="step",
            message=(f"fusion certified: {passes:.2f} effective HBM passes "
                     f"vs split baseline {split_ref:.2f} ({split_model}) — "
                     f"{split_ref - passes:.2f} passes of token-plane "
                     "round-trip deleted"))]

    # -- combiner-vs-off gate (ISSUE 11) --------------------------------

    def _combiner_gate_findings(self, ctx, report) -> list[core.Finding]:
        """A hot-key-combiner model must price STRICTLY below its
        combiner-off twin's checked-in baseline at the same chunk
        geometry — the fused-vs-split discipline applied to the taller
        combiner windows: the cache only exists to delete sort rows, so
        the moment it stops doing that statically, CI says so."""
        config = getattr(ctx.job, "config", None)
        passes = report.get("effective_input_passes")
        off_model = _UNCOMBINED_COUNTERPART.get(ctx.model)
        if config is None or passes is None or off_model is None:
            return []
        if not config.resolved_combiner_slots:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message="combiner-gated model resolves to NO hot-key cache "
                        "(combiner/map_impl/compact config drifted): the "
                        "gate would compare two identical programs",
                hint="keep COMBINER_ANALYSIS_CONFIG on the fused compact "
                     "path with combiner='hot-cache'")]
        off = load_baseline(off_model, ctx.baselines_dir)
        if off is None:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"combiner-off counterpart {off_model!r} has no "
                         "cost baseline: the combiner's win is unmeasured"),
                hint=f"regenerate with `python -m mapreduce_tpu.analysis "
                     f"{off_model} --write-baselines` and commit the JSON")]
        off_raw = off.get("effective_input_passes")
        if not isinstance(off_raw, (int, float)) or off_raw <= 0:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"counterpart {off_model!r} baseline has no usable "
                         f"effective_input_passes ({off_raw!r}): the "
                         "combiner gap cannot be gated"),
                hint=f"regenerate with `python -m mapreduce_tpu.analysis "
                     f"{off_model} --write-baselines` and commit the JSON")]
        if off.get("traced_chunk_bytes") != report["traced_chunk_bytes"]:
            # Same no-publish rule as the fused gate: an incomparable gap
            # must never reach BENCH JSON via the copied artifact.
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"combiner model traces a "
                         f"{report['traced_chunk_bytes']}-byte chunk but "
                         f"{off_model!r} priced "
                         f"{off.get('traced_chunk_bytes')!r}: the passes "
                         "are not comparable"),
                hint="keep COMBINER_ANALYSIS_CONFIG and its twin on the "
                     "same chunk geometry")]
        off_ref = float(off_raw)
        report["combiner_vs_off"] = {
            "off_model": off_model,
            "off_effective_input_passes": off_ref,
            "combiner_effective_input_passes": passes,
            "passes_saved": round(off_ref - passes, 3)}
        if passes >= off_ref:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"hot-key combiner prices {passes:.2f} effective "
                         f"HBM passes, NOT strictly below the combiner-off "
                         f"baseline {off_ref:.2f} ({off_model}): the cache "
                         "stopped deleting sort traffic"),
                hint="the taller-window arithmetic broke (geometry drift?) "
                     "or the off baseline is stale; fix or re-measure "
                     "deliberately and record it in PERF.md")]
        return [core.Finding(
            severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
            hook="step",
            message=(f"combiner certified: {passes:.2f} effective HBM "
                     f"passes vs combiner-off baseline {off_ref:.2f} "
                     f"({off_model}) — {off_ref - passes:.2f} passes of "
                     "sort traffic deleted"))]

    # -- baseline regression gate ---------------------------------------

    def _baseline_findings(self, ctx, report) -> list[core.Finding]:
        passes = report.get("effective_input_passes")
        if passes is None:
            return []
        if ctx.write_baselines:
            path = baseline_path(ctx.model, ctx.baselines_dir)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(path, "w") as f:
                json.dump({
                    "model": ctx.model,
                    "effective_input_passes": passes,
                    "step_hbm_bytes":
                        report["programs"]["step"]["hbm_bytes"],
                    "step_flops": report["programs"]["step"]["flops"],
                    "traced_chunk_bytes": report["traced_chunk_bytes"],
                    "_regenerate":
                        "python -m mapreduce_tpu.analysis --write-baselines",
                }, f, indent=2)
                f.write("\n")
            return [core.Finding(
                severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
                hook="step", message=f"baseline written: {path}")]
        base = load_baseline(ctx.model, ctx.baselines_dir)
        if base is None:
            return [core.Finding(
                severity=core.WARNING, pass_id=self.pass_id,
                model=ctx.model, hook="step",
                message="no cost baseline checked in for this model",
                hint="regenerate with `python -m mapreduce_tpu.analysis "
                     f"{ctx.model} --write-baselines` and commit the JSON")]
        ref = float(base.get("effective_input_passes", 0.0))
        report["baseline_effective_input_passes"] = ref
        if ref <= 0:
            return []
        growth = (passes - ref) / ref
        if growth > REGRESSION_TOLERANCE:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"predicted HBM passes regressed {growth:+.0%}: "
                         f"{passes:.2f} vs baseline {ref:.2f} "
                         f"(gate: {REGRESSION_TOLERANCE:.0%})"),
                hint="either fix the regression or regenerate baselines "
                     "deliberately (--write-baselines) with the pricing "
                     "note in PERF.md")]
        if growth < -REGRESSION_TOLERANCE:
            return [core.Finding(
                severity=core.WARNING, pass_id=self.pass_id,
                model=ctx.model, hook="step",
                message=(f"predicted HBM passes improved {growth:+.0%} vs "
                         f"baseline {ref:.2f}"),
                hint="nice — re-baseline (--write-baselines) so the gate "
                     "protects the win")]
        return []
