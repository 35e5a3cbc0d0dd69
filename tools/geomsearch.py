#!/usr/bin/env python3
"""Certifier-gated kernel-geometry search driver (ISSUE 12 tentpole).

Three stages, each cheaper than the next is allowed to be:

1. **Enumerate + certify + rank** (default; jax-free): walk the candidate
   lattice in ``mapreduce_tpu/analysis/geometry.py``, drop anything the
   static vmem certifier rejects, price the survivors with the hbm-cost
   model's own arithmetic (stable2 sort rows re-derived from each
   CANDIDATE), and print the ranked shortlist as one
   JSON artifact — no jax, no device.
2. ``--gate``: run the full graphcheck pipeline (reducer-algebra,
   overflow, host-sync, sharding, **vmem-budget, kernel-race,
   spill-reachability**) over a WordCountJob built with each shortlisted
   candidate — the same baseline-free certification ``tools/autotune.py``
   applies to probe configs.  Traces on the host; still no device.
3. ``--probe``: measured on-device ranking — one telemetered streamed
   probe pass per shortlisted candidate through the PR-10 probe
   machinery (``tools/autotune.py``), winner written to ``tuned.json``
   (profile key ``<family>-geometry/<backend>/<corpus>``) with the
   ranked trail.
   ``Config.geometry='auto'`` / CLI ``--geometry auto`` resolve from
   exactly these profiles.

Usage::

    python tools/geomsearch.py                       # jax-free shortlist
    python tools/geomsearch.py --top 8 --axis block_rows
    python tools/geomsearch.py --gate                # + graphcheck gate
    python tools/geomsearch.py --probe --mb 64       # measured ranking
    python tools/geomsearch.py --selftest            # fixture-driven, jax-free

``--selftest`` (wired into ``tools/tier1.sh`` and ``tools/smoke.sh``
alongside the obs_report/trace_export/autotune selftests) asserts the
jax-free half end to end: the default geometry reproduces the shipped
``production_plans`` footprints bit-for-bit, every emitted candidate
passes the static certifier, a known-overflow candidate is rejected, the
384-vs-512 ranking matches the PR-11 hand arithmetic, and the tuner's
geometry knob proposes/reverts/oscillation-guards over the checked-in
fixtures — all without importing jax.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def _load_geometry():
    """Import ``mapreduce_tpu.analysis.geometry`` WITHOUT executing the
    ``mapreduce_tpu.analysis`` package __init__ (which registers the pass
    pipeline and pulls jax): the module itself imports only the jax-free
    corners (config, ops/pallas/meta), so loading it by file path keeps
    the selftest/shortlist stages genuinely jax-free.  When the package
    is already imported (pytest, --gate/--probe), reuse it."""
    mod = sys.modules.get("mapreduce_tpu.analysis.geometry")
    if mod is not None:
        return mod
    path = os.path.join(REPO, "mapreduce_tpu", "analysis", "geometry.py")
    spec = importlib.util.spec_from_file_location("_geomsearch_geometry",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    # dataclass creation resolves the defining module through sys.modules:
    # register under the private name BEFORE executing.
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


# -- stage 2: the graphcheck gate (jax; host-only) ---------------------------

def gate_candidates(cands, log) -> list:
    """Baseline-free graphcheck certification of each candidate — the
    autotune._certify discipline: vmem-budget, kernel-race (the
    revisited-ref discipline at the candidate's static shapes),
    spill-reachability, host-sync, sharding, algebra, overflow.  Returns
    the candidates whose reports carry zero errors."""
    from mapreduce_tpu import analysis
    from mapreduce_tpu.config import Config
    from mapreduce_tpu.models.wordcount import WordCountJob

    passes = [p for p in analysis.default_pipeline()
              if p.pass_id not in ("hbm-cost", "fusion-opportunity")]
    kept = []
    for c in cands:
        cfg = Config(chunk_bytes=128 * max(c.geometry.block_rows,
                                           c.geometry.combiner_block_rows),
                     table_capacity=512, backend="pallas",
                     map_impl="fused", geometry=c.geometry)
        report = analysis.analyze_job(WordCountJob(cfg),
                                      f"<geometry:{c.label}>",
                                      passes=passes)
        if report.errors:
            log(f"gate REJECTED {c.label}:\n"
                + report.format_text("error"))
            continue
        log(f"gate ok: {c.label}")
        kept.append(c)
    return kept


# -- stage 3: measured probe ranking (jax + device) --------------------------

def run_probe(args, geom_mod) -> int:
    import tempfile

    import bench  # repo-root module: the corpus generators

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import autotune  # the PR-10 probe machinery
    finally:
        sys.path.pop(0)

    wall0 = time.perf_counter()

    def log(msg: str) -> None:
        print(f"[geomsearch +{time.perf_counter() - wall0:6.1f}s] {msg}",
              file=sys.stderr, flush=True)

    import jax

    from mapreduce_tpu import obs
    from mapreduce_tpu.config import Config
    from mapreduce_tpu.models.wordcount import WordCountJob
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime import executor, profiling

    cands = geom_mod.shortlist(geom_mod.enumerate_candidates(),
                               args.top, axis=args.axis)
    # The default geometry is ALWAYS probed (the A/B baseline every
    # candidate is judged against), whether or not it made the shortlist.
    if not any(c.axis == "default" for c in cands):
        cands = [c for c in geom_mod.enumerate_candidates()
                 if c.axis == "default"] + cands

    # Drop candidates whose varied axis is INERT in the probe config
    # (fused/stable2/combiner-off): a sort3-axis candidate resolves to the
    # exact same program there, so probing it measures run-to-run noise and can crown a no-op knob the winner.
    # Resolved-value comparison, not axis names, so the filter can never
    # drift from what Config actually reads.  Logged, never silent
    # (the no-silent-caps rule).
    def _probe_resolved(c):
        cfg = Config(backend="pallas", map_impl="fused",
                     geometry=None if c.axis == "default" else c.geometry)
        return (cfg.resolved_block_rows, cfg.resolved_compact_slots,
                cfg.resolved_pair_block_rows, cfg.resolved_aux_rows,
                cfg.resolved_combiner_slots)

    default_resolved = _probe_resolved(
        next(c for c in cands if c.axis == "default"))
    kept = []
    for c in cands:
        if c.axis != "default" and _probe_resolved(c) == default_resolved:
            log(f"probe skipped {c.label}: its axis is inert in the probe "
                "config (identical resolved program) — rank it via a "
                "probe driver that exercises that axis instead")
            continue
        kept.append(c)
    cands = gate_candidates(kept, log)
    if not cands:
        print("geomsearch: no candidate survived the gate", file=sys.stderr)
        return 1

    profiling.enable_compile_cache()
    gen = {"zipf": bench.make_zipf_corpus,
           "natural": bench.make_natural_corpus,
           "webby": bench.make_webby_corpus,
           "markup": bench.make_markup_corpus}[args.corpus]
    corpus = gen(args.mb << 20)
    mesh = data_mesh()
    backend = jax.devices()[0].platform
    ledger_dir = args.keep_ledgers or tempfile.mkdtemp(prefix="geomsearch_")
    os.makedirs(ledger_dir, exist_ok=True)
    with tempfile.NamedTemporaryFile(suffix=".txt", delete=False) as f:
        f.write(corpus)
        path = f.name
    measured = []
    try:
        for i, c in enumerate(cands):
            cfg = Config(chunk_bytes=args.chunk_mb << 20,
                         table_capacity=1 << 18,
                         batch_unique_capacity=1 << 16,
                         backend="pallas", map_impl="fused",
                         geometry=None if c.axis == "default"
                         else c.geometry)
            ledger = os.path.join(ledger_dir, f"geom{i:02d}.jsonl")
            tel = obs.Telemetry.create(ledger_path=ledger)
            t0 = time.perf_counter()
            try:
                rr = executor.run_job(WordCountJob(cfg), path, config=cfg,
                                      mesh=mesh, telemetry=tel)
            finally:
                tel.close()
            dt = time.perf_counter() - t0
            gbps = round(rr.metrics.bytes_processed / 1e9 / dt, 4)
            log(f"probe {c.label}: {gbps} GB/s ({dt:.2f}s, "
                f"modeled sort_rows={c.sort_rows}, ledger {ledger})")
            measured.append((gbps, c))
    finally:
        os.unlink(path)
    measured.sort(key=lambda gc: -gc[0])
    best_gbps, best = measured[0]
    key = (f"wordcount-geometry/{backend}/"
           f"{args.corpus}-{args.mb}mb-chunk{args.chunk_mb}mb")
    entry = {"config": {"geometry": best.label
                        if best.label in ("default",)
                        or best.label in _preset_names()
                        else best.geometry.as_dict()},
             "measured_gbps": best_gbps,
             "stopped": "probed",
             "passes": len(measured),
             "backend": backend,
             "devices": int(mesh.size),
             "corpus": f"synthetic-{args.corpus}",
             "corpus_mb": args.mb,
             "trail": [{"geometry": c.label, "gbps": g,
                        "modeled_sort_rows": c.sort_rows,
                        "spill_risk": c.spill_risk}
                       for g, c in measured],
             "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime())}
    autotune.write_profile(args.out, key, entry)
    log(f"winner {best.label} @ {best_gbps} GB/s -> {args.out} [{key}]")
    print(json.dumps({"metric": "geomsearch_winner", "profile": key,
                      **entry}))
    return 0


def _preset_names():
    from mapreduce_tpu.config import GEOMETRY_PRESETS

    return set(GEOMETRY_PRESETS)


# -- selftest (jax-free) -----------------------------------------------------

def selftest() -> int:
    """The jax-free half end to end, against hand arithmetic and the
    checked-in fixtures — the tier-1/smoke gate."""
    had_jax = "jax" in sys.modules
    g = _load_geometry()
    from mapreduce_tpu.ops.pallas import meta  # jax-free

    # The shipped default geometries are reproduced EXACTLY by the
    # constructor: bit-identical vmem_plan footprints (the acceptance
    # criterion; the values are the pre-refactor hand-maintained list's).
    expected = [(508416, 12, 67108864), (352768, 12, 67108864),
                (475648, 8, None), (729600, 12, 67108864),
                (860672, 12, 67108864), (631296, 8, None)]
    plans = meta.production_plans()
    got = [(p.vmem_bytes, p.smem_bytes, p.vmem_limit_bytes) for p in plans]
    assert got == expected, f"production plans drifted: {got}"
    assert [p.as_dict() for p in plans] == \
        [p.as_dict() for p in meta.geometry_plans(g.DEFAULT_GEOMETRY)]

    # Every emitted candidate passes the static certifier by construction.
    cands = g.enumerate_candidates()
    assert len(cands) >= 30, f"lattice shrank to {len(cands)}"
    assert all(not g.certify(c.geometry) for c in cands)
    assert sum(c.axis == "default" for c in cands) == 1

    # A known-overflow candidate is rejected: 16384-row pair windows are
    # ~28 MiB of double-buffered blocks per grid step — past Mosaic's
    # 16 MB default stack budget, which the pair path does not override.
    bad = g.Geometry(pair_block_rows=16384)
    errs = g.certify(bad)
    assert errs and any("16 MiB budget" in e for e in errs), errs
    assert not any(c.geometry == bad for c in cands)

    # Cost ranking matches the PR-11 hand arithmetic (the free oracle):
    # 384x128 -> 11,206,656 sort rows per 32 MB chunk, 512x128 ->
    # 8,404,992 (-25%), so tall512 prices BELOW the default; spill risk
    # is flagged on the 512 window without the combiner (114 ends / 384
    # bytes measured density -> 152 > 128 slots) and NOT on the default.
    assert g.stable2_sort_rows(1 << 25, 384, 128) == 11206656
    assert g.stable2_sort_rows(1 << 25, 512, 128) == 8404992
    default = next(c for c in cands if c.axis == "default")
    tall = next(c for c in cands if c.label == "tall512")
    assert tall.sort_rows < default.sort_rows
    assert tall.spill_risk and not default.spill_risk
    sl = g.shortlist(cands, 5)
    assert sl.index(tall) < len(sl), "tall512 must make the top-5"
    assert all(sl[i].sort_rows <= sl[i + 1].sort_rows
               for i in range(len(sl) - 1)), "shortlist must rank by rows"
    art = g.search_artifact(cands, 5)
    assert art["default"]["sort_rows"] == 11206656
    assert len(art["shortlist"]) == 5
    json.dumps(art)  # the artifact is JSON-clean

    # The tuner's geometry knob (the second non-numeric knob): propose on
    # the device-bound-with-headroom fixture, revert on the spilling
    # tall-window fixture, and the oscillation guard stops the pair.
    from mapreduce_tpu.tuning import engine

    def fx(name):
        with open(os.path.join(FIXTURES, name + ".jsonl"),
                  encoding="utf-8") as f:
            return [json.loads(line) for line in f if line.strip()]

    geom_recs, spill_recs = fx("tuner_geometry"), fx("tuner_geomspill")
    p = engine.propose(geom_recs)
    assert p["rule"] == "try-geometry", p["rule"]
    assert p["changed"] == {"geometry": ["default", "tall512"]}, p["changed"]
    assert p["signals"]["window_occupancy"] == 0.55, p["signals"]
    engine.validate_knobs(p["proposal"])
    p2 = engine.propose(spill_recs)
    assert p2["rule"] == "revert-geometry", p2["rule"]
    assert p2["changed"] == {"geometry": ["tall512", "default"]}, p2
    engine.validate_knobs(p2["proposal"])
    r = engine.search(
        lambda k: geom_recs if k["geometry"] == "default" else spill_recs,
        {"chunk_bytes": 1 << 21, "superstep": 1, "inflight_groups": 4,
         "prefetch_depth": 4}, budget=8)
    assert r["stopped"] == "oscillation" and r["passes"] == 2, r
    assert [t["rule"] for t in r["trail"]] == \
        ["try-geometry", "revert-geometry"]
    for t in r["trail"]:
        engine.validate_knobs(t["proposal"])

    # 'auto' resolution round-trip: preset label and spec dict both
    # resolve; garbage/missing profiles degrade to 'default'.
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        prof = os.path.join(d, "tuned.json")
        with open(prof, "w", encoding="utf-8") as f:
            json.dump({"profiles": {
                "wordcount-geometry/tpu/zipf-64mb-chunk32mb": {
                    "recorded_at": "2026-08-04T00:00:00Z",
                    "config": {"geometry": "tall512"}}}}, f)
        assert g.resolve_auto(prof) == "tall512"
        spec = g.Geometry(block_rows=640).as_dict()
        with open(prof, "w", encoding="utf-8") as f:
            json.dump({"profiles": {
                "wordcount-geometry/tpu/zipf-64mb-chunk32mb": {
                    "recorded_at": "2026-08-04T00:00:00Z",
                    "config": {"geometry": spec}}}}, f)
        assert g.resolve_auto(prof) == spec
        with open(prof, "w", encoding="utf-8") as f:
            f.write("not json")
        assert g.resolve_auto(prof) == "default"
        assert g.resolve_auto(os.path.join(d, "missing.json")) == "default"

    assert had_jax or "jax" not in sys.modules, \
        "selftest must stay jax-free"
    print(f"geomsearch selftest ok ({len(cands)} candidates certified, "
          f"default {default.sort_rows} rows vs tall512 {tall.sort_rows} "
          f"(-25%), overflow rejected, tuner try/revert + oscillation "
          "guard ok, auto-resolution ok)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="certifier-gated kernel-geometry search: jax-free "
                    "shortlist, graphcheck gate, measured probe ranking")
    ap.add_argument("--top", type=int, default=5,
                    help="shortlist size (default 5)")
    ap.add_argument("--axis", default=None,
                    help="narrow the lattice to one axis family "
                         "(block_rows, sort3, ...)")
    ap.add_argument("--gate", action="store_true",
                    help="run the graphcheck pipeline over the shortlist "
                         "(host tracing; no device)")
    ap.add_argument("--probe", action="store_true",
                    help="measured on-device ranking of the gated "
                         "shortlist (one streamed probe pass each)")
    ap.add_argument("--corpus", choices=("zipf", "natural", "webby",
                                         "markup"), default="zipf")
    ap.add_argument("--mb", type=int, default=32,
                    help="probe corpus size (default 32)")
    ap.add_argument("--chunk-mb", type=int, default=32,
                    help="probe chunk size in MB (default 32 — the "
                         "pricing chunk the modeled ranking uses)")
    ap.add_argument("--out", default=os.path.join(REPO, "tuned.json"),
                    help="tuned-profile JSON path (default ./tuned.json)")
    ap.add_argument("--keep-ledgers", default=None, metavar="DIR",
                    help="keep per-probe ledgers in DIR (default: tmpdir)")
    ap.add_argument("--selftest", action="store_true",
                    help="run the jax-free selftest and exit")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    g = _load_geometry()
    if args.probe:
        return run_probe(args, g)
    cands = g.enumerate_candidates()
    if args.gate:
        short = g.shortlist(cands, args.top, axis=args.axis)
        kept = gate_candidates(
            short, lambda m: print(f"[geomsearch] {m}", file=sys.stderr))
        print(json.dumps({**g.search_artifact(cands, args.top),
                          "gated": [c.label for c in kept]}))
        return 0 if len(kept) == len(short) else 1
    print(json.dumps(g.search_artifact(cands, args.top)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
