#!/usr/bin/env python3
"""Offline window autotuner (ISSUE 10 mode (a)): walk the rule table of
``mapreduce_tpu/tuning/`` over N short streamed probe passes on a
bench-style corpus until the config converges, the oscillation guard
trips, or the pass budget runs out — then emit a ``tuned.json`` profile
keyed by (family, backend, corpus shape) with the full decision trail.

Each probe pass streams the corpus through ``executor.run_job`` with
telemetry into its own ledger; the tuner then reads exactly what the run
recorded (the PR-7 ``bottleneck`` verdict, the PR-8 ``data_health``
verdict, the window statistics) — the same pure function the online
``--autotune`` hint uses.  Every ACCEPTED config is validated through
``Config.__post_init__`` (the engine does this) and certified by the
graphcheck/costcheck gate — the baseline-free passes (reducer-algebra,
overflow, host-sync, sharding, vmem-budget, kernel-race), which are the
geometry-dependent device-safety certification — before it is allowed to
touch a device (the per-model hbm-cost baseline regression stays
tier-1's job: probe configs are not registry models).

Usage::

    python tools/autotune.py                          # zipf, 32 MB, CPU ok
    python tools/autotune.py --corpus natural --mb 64 --chunk-mb 4
    python tools/autotune.py --out /tmp/tuned.json --budget 5
    python tools/autotune.py --selftest               # fixture-driven, jax-free

``--selftest`` drives the search loop against the checked-in synthetic
ledgers (``tools/fixtures/tuner_*.jsonl``) through simulated systems —
the reader-bound system converges to the hand-computed higher-prefetch
config, the device-bound system raises superstep and provably never
touches ``inflight_groups``, and an adversarial occupancy/table-pressure
pair terminates via the oscillation guard — all without importing jax.
Wired into ``tools/tier1.sh`` and ``tools/smoke.sh`` alongside the
obs_report/trace_export selftests.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from mapreduce_tpu.tuning import engine  # noqa: E402 (jax-free)

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")


def _read_fixture(name: str) -> list:
    with open(os.path.join(FIXTURES, name + ".jsonl"), encoding="utf-8") as f:
        return [json.loads(line) for line in f if line.strip()]


# -- the probe-pass measure function (jax; offline mode only) ----------------

def _probe_config(knobs: dict):
    """The ONE knobs->Config mapping every probe-pass consumer (warm-up,
    measure, certify) builds from — bench-style table geometry included,
    so the warm-up provably compiles the same program shapes the timed
    passes run."""
    from mapreduce_tpu.config import Config

    combiner = str(knobs.get("combiner", "off"))
    geometry = knobs.get("geometry", "default")
    return Config(chunk_bytes=int(knobs["chunk_bytes"]),
                  superstep=int(knobs["superstep"]),
                  inflight_groups=int(knobs["inflight_groups"]),
                  prefetch_depth=int(knobs["prefetch_depth"]),
                  combiner=combiner,
                  # The geometry knob (ISSUE 12) round-trips as 'default'
                  # or a GEOMETRY_PRESETS name; dict-shaped candidates
                  # come from the geomsearch driver, which passes them
                  # through the same Config surface.
                  geometry=None if geometry in (None, "default")
                  else geometry,
                  # The hot-key cache only exists on the fused map path
                  # (resolved_combiner_slots is 0 elsewhere): a probe that
                  # left map_impl at 'split' would re-measure the IDENTICAL
                  # program while reporting the combiner engaged — the same
                  # pairing the tuned rows apply.
                  map_impl="fused" if combiner == "hot-cache"
                  else Config.map_impl,
                  # Placed-reduction knobs (ISSUE 20): round-trip as a
                  # MERGE_STRATEGIES name and an 'off'/'on' string.
                  merge_strategy=str(knobs.get("merge_strategy", "tree")),
                  merge_overlap=str(knobs.get("merge_overlap",
                                              "off")) == "on",
                  table_capacity=1 << 18,
                  batch_unique_capacity=1 << 16)


def _certify(knobs: dict) -> None:
    """Graphcheck gate for one ACCEPTED probe config: the baseline-free
    passes over a WordCountJob built with exactly these knobs.  An
    error-severity finding aborts the walk — a config the certifier
    rejects must never touch the device."""
    from mapreduce_tpu import analysis
    from mapreduce_tpu.models.wordcount import WordCountJob

    passes = [p for p in analysis.default_pipeline()
              if p.pass_id not in ("hbm-cost", "fusion-opportunity")]
    report = analysis.analyze_job(WordCountJob(_probe_config(knobs)),
                                  "<autotune-probe>", passes=passes)
    if report.errors:
        raise SystemExit("autotune: costcheck gate REJECTED config "
                         f"{knobs}:\n" + report.format_text("error"))


def _make_measure(corpus_path: str, mesh, ledger_dir: str,
                  log) -> "callable":
    """The real measure function: one telemetered streamed pass per call,
    returning (records, gbps) via a closure side-channel."""
    from mapreduce_tpu import obs
    from mapreduce_tpu.models.wordcount import WordCountJob
    from mapreduce_tpu.runtime import executor

    state = {"pass": 0, "gbps": None, "ledger": None}

    def measure(knobs: dict) -> list:
        _certify(knobs)
        state["pass"] += 1
        cfg = _probe_config(knobs)
        ledger = os.path.join(ledger_dir, f"probe{state['pass']:02d}.jsonl")
        tel = obs.Telemetry.create(ledger_path=ledger)
        t0 = time.perf_counter()
        try:
            rr = executor.run_job(WordCountJob(cfg), corpus_path,
                                  config=cfg, mesh=mesh, telemetry=tel)
        finally:
            tel.close()
        dt = time.perf_counter() - t0
        state["gbps"] = round(rr.metrics.bytes_processed / 1e9 / dt, 4)
        state["ledger"] = ledger
        log(f"pass {state['pass']}: {knobs} -> {state['gbps']} GB/s "
            f"({dt:.2f}s, ledger {ledger})")
        return [r for r in obs.read_ledger(ledger)
                if r.get("run_id") == tel.run_id]

    return measure, state


# -- tuned.json -------------------------------------------------------------

def _trail_summary(result: dict) -> list:
    """The per-pass decision trail, compacted for the profile/record."""
    return [{"rule": p["rule"], "changed": p["changed"],
             "converged": p["converged"],
             "resource": p["signals"].get("resource"),
             "saving_frac": p["signals"].get("saving_frac"),
             "data_verdict": p["signals"].get("data_verdict")}
            for p in result["trail"]]


def write_profile(out_path: str, key: str, entry: dict) -> None:
    """Merge one (family, backend, corpus-shape)-keyed profile into the
    tuned.json file (other keys preserved)."""
    profiles = {}
    try:
        with open(out_path, encoding="utf-8") as f:
            profiles = json.load(f).get("profiles", {})
    except (OSError, ValueError):
        pass
    profiles[key] = entry
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump({"tuner_version": engine.TUNER_VERSION,
                   "profiles": profiles}, f, indent=1)
        f.write("\n")


# -- offline search ----------------------------------------------------------

def run_search(args) -> int:
    import tempfile

    import bench  # repo-root module: the corpus generators

    wall0 = time.perf_counter()

    def log(msg: str) -> None:
        print(f"[autotune +{time.perf_counter() - wall0:6.1f}s] {msg}",
              file=sys.stderr, flush=True)

    import jax

    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime import profiling

    profiling.enable_compile_cache()
    gen = {"zipf": bench.make_zipf_corpus,
           "natural": bench.make_natural_corpus,
           "webby": bench.make_webby_corpus,
           "markup": bench.make_markup_corpus}[args.corpus]
    corpus = gen(args.mb << 20)
    log(f"corpus ready: {len(corpus) >> 20} MB (synthetic-{args.corpus})")
    mesh = data_mesh()
    backend = jax.devices()[0].platform
    ledger_dir = args.keep_ledgers or tempfile.mkdtemp(prefix="autotune_")
    os.makedirs(ledger_dir, exist_ok=True)
    with tempfile.NamedTemporaryFile(suffix=".txt", delete=False) as f:
        f.write(corpus)
        path = f.name
    start = {"chunk_bytes": args.chunk_mb << 20,
             "superstep": args.superstep,
             "inflight_groups": args.inflight,
             "prefetch_depth": args.prefetch}
    try:
        measure, state = _make_measure(path, mesh, ledger_dir, log)
        # Warm-up: pay the XLA compiles for the starting shapes so pass 1
        # measures ingest, not compilation (chunk moves recompile anyway —
        # an accepted cost: the walk compares configs, and the persistent
        # cache converts repeat shapes into hits).
        from mapreduce_tpu.models.wordcount import WordCountJob
        from mapreduce_tpu.runtime import executor

        warm_cfg = _probe_config(start)
        warm_hi = min(len(corpus), mesh.size * warm_cfg.chunk_bytes
                      * (warm_cfg.superstep + 1))
        executor.run_job(WordCountJob(warm_cfg), path, config=warm_cfg,
                         mesh=mesh, byte_range=(0, warm_hi))
        log("warm-up done (compile paid)")
        result = engine.search(measure, start, budget=args.budget,
                               backend="auto")
    finally:
        os.unlink(path)
    key = (f"wordcount/{backend}/"
           f"{args.corpus}-{args.mb}mb-chunk{args.chunk_mb}mb")
    # The winner's OWN pass's throughput (engine.search pairs them): on an
    # oscillation stop state["gbps"] holds the losing final pass's number.
    # The ledger-derived figure is preferred; the harness wall-clock one
    # is the fallback for ledgers that carried no run_end throughput.
    winner_gbps = result.get("winner_gbps")
    entry = {"config": result["winner"],
             "measured_gbps": winner_gbps if winner_gbps is not None
             else state["gbps"],
             "stopped": result["stopped"],
             "passes": result["passes"],
             "backend": backend,
             "devices": int(mesh.size),
             "corpus": f"synthetic-{args.corpus}",
             "corpus_mb": args.mb,
             "trail": _trail_summary(result),
             "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                          time.gmtime())}
    write_profile(args.out, key, entry)
    log(f"{result['stopped']} after {result['passes']} pass(es); "
        f"winner {result['winner']} @ {entry['measured_gbps']} GB/s -> "
        f"{args.out} [{key}]")
    print(json.dumps({"metric": "autotune_winner", "profile": key, **entry}))
    return 0


# -- selftest ----------------------------------------------------------------

def selftest() -> int:
    """Drive the search loop through simulated systems built from the
    checked-in fixtures and assert the hand-computed outcomes — the whole
    ledger -> signals -> rule-table -> search path, jax-free."""
    # jax-free claim: the selftest must never ADD jax to the process (it
    # may already be loaded when invoked from inside pytest).
    had_jax = "jax" in sys.modules
    reader = _read_fixture("tuner_reader_bound")
    device = _read_fixture("tuner_device_bound")
    conv = _read_fixture("tuner_converged")
    occ = _read_fixture("tuner_occupancy")
    table = _read_fixture("tuner_tablepressure")
    skew = _read_fixture("tuner_skewhot")

    # Single-proposal rule checks against each fixture (the unit facts the
    # convergence walks below compose).
    for recs, rule, changed in [
            (reader, "raise-prefetch", {"prefetch_depth": [4, 8]}),
            (device, "try-superstep", {"superstep": [1, 2]}),
            (conv, "converged", {}),
            (occ, "grow-chunk", {"chunk_bytes": [2097152, 4194304]}),
            (table, "shrink-chunk", {"chunk_bytes": [4194304, 2097152]}),
            (skew, "enable-combiner", {"combiner": ["off", "hot-cache"]})]:
        p = engine.propose(recs)
        assert p["rule"] == rule, (rule, p["rule"])
        assert p["changed"] == changed, (rule, p["changed"])
        assert p["trail"] and all(
            set(t) == {"rule", "fired", "why"} for t in p["trail"]), \
            "decision trail must be machine-readable"
        engine.validate_knobs(p["proposal"])

    # Reader-bound system: reader-starved until prefetch reaches 16, then
    # the well-overlapped ledger.  Hand-computed walk: 4 -> 8 -> 16, then
    # converged; nothing else moves.
    sim_calls = []

    def sim_reader(knobs):
        sim_calls.append(dict(knobs))
        return reader if knobs["prefetch_depth"] < 16 else conv

    r = engine.search(sim_reader, {"chunk_bytes": 1 << 25, "superstep": 1,
                                   "inflight_groups": 4,
                                   "prefetch_depth": 4}, budget=6)
    assert r["stopped"] == "converged", r["stopped"]
    assert r["winner"] == {"chunk_bytes": 1 << 25, "superstep": 1,
                           "inflight_groups": 4, "prefetch_depth": 16,
                           "combiner": "off", "geometry": "default",
                           "merge_strategy": "tree",
                           "merge_overlap": "off"}, \
        r["winner"]
    assert [p["rule"] for p in r["trail"]] == \
        ["raise-prefetch", "raise-prefetch", "converged"], \
        [p["rule"] for p in r["trail"]]
    assert [c["prefetch_depth"] for c in sim_calls] == [4, 8, 16]

    # Skew-hot system (ISSUE 11): a Zipf-hot ledger flips the combiner on
    # in ONE pass; the combiner-on ledger then measures device-bound with
    # the window unsaturated -> converged.  The decision trail must show
    # enable-combiner firing exactly once, and no pipeline knob may move
    # while the data-shape rule is answering the skew.
    def sim_skew(knobs):
        return skew if knobs["combiner"] == "off" else conv

    rs = engine.search(sim_skew, {"chunk_bytes": 1 << 21, "superstep": 1,
                                  "inflight_groups": 4,
                                  "prefetch_depth": 4}, budget=6)
    assert rs["stopped"] == "converged", rs["stopped"]
    assert rs["winner"]["combiner"] == "hot-cache", rs["winner"]
    assert rs["winner"]["prefetch_depth"] == 4 \
        and rs["winner"]["superstep"] == 1 \
        and rs["winner"]["inflight_groups"] == 4, rs["winner"]
    assert [p["rule"] for p in rs["trail"]] == \
        ["enable-combiner", "converged"], [p["rule"] for p in rs["trail"]]
    assert rs["trail"][0]["changed"] == {"combiner": ["off", "hot-cache"]}

    # Device-bound system (window always full): superstep 1 -> 2 -> 4,
    # inflight provably NEVER raised — the "stop raising inflight" rule.
    def sim_device(knobs):
        return device if knobs["superstep"] < 4 else conv

    r2 = engine.search(sim_device, {"chunk_bytes": 1 << 25, "superstep": 1,
                                    "inflight_groups": 4,
                                    "prefetch_depth": 4}, budget=6)
    assert r2["stopped"] == "converged", r2["stopped"]
    assert r2["winner"]["superstep"] == 4 and \
        r2["winner"]["inflight_groups"] == 4, r2["winner"]
    assert not any(p["rule"] == "raise-inflight" for p in r2["trail"])
    assert [p["rule"] for p in r2["trail"]] == \
        ["try-superstep", "try-superstep", "converged"]

    # Oscillation guard: a system whose data verdict flips between
    # occupancy-starved (grow) and table-pressure (shrink) at the 2 MB
    # boundary would ping-pong forever — the guard terminates it the
    # moment a proposed config was already visited.
    def sim_osc(knobs):
        return occ if knobs["chunk_bytes"] <= (2 << 20) else table

    r3 = engine.search(sim_osc, {"chunk_bytes": 2 << 20, "superstep": 1,
                                 "inflight_groups": 4,
                                 "prefetch_depth": 4}, budget=10)
    assert r3["stopped"] == "oscillation", r3["stopped"]
    assert r3["passes"] == 2 and r3["trail"][-1].get("oscillation"), r3
    # Every proposal the walks produced passes real Config validation.
    for res in (r, r2, r3, rs):
        for p in res["trail"]:
            engine.validate_knobs(p["proposal"])

    # Profile writing, exercised against a temp file.
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        prof = os.path.join(d, "tuned.json")
        entry = {"config": r["winner"], "measured_gbps": 0.5,
                 "stopped": "converged", "trail": _trail_summary(r),
                 "recorded_at": "2026-08-04T00:00:00Z"}
        write_profile(prof, "wordcount/tpu/zipf-32mb-chunk2mb", entry)
        write_profile(prof, "wordcount/tpu/natural-64mb-chunk4mb", entry)
        with open(prof, encoding="utf-8") as f:
            blob = json.load(f)
        assert set(blob["profiles"]) == {
            "wordcount/tpu/zipf-32mb-chunk2mb",
            "wordcount/tpu/natural-64mb-chunk4mb"}, blob
    assert had_jax or "jax" not in sys.modules, \
        "selftest must stay jax-free"
    print("autotune selftest ok (reader walk -> prefetch 16 in "
          f"{r['passes']} passes, device walk -> superstep "
          f"{r2['winner']['superstep']} with inflight untouched, "
          f"skew walk -> combiner {rs['winner']['combiner']} in "
          f"{rs['passes']} passes, "
          f"oscillation stopped in {r3['passes']}, profiles ok)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="offline window autotuner: probe-pass search over "
                    "inflight/prefetch/superstep/chunk via the run "
                    "ledger's own verdicts")
    ap.add_argument("--corpus", choices=("zipf", "natural", "webby",
                                         "markup"), default="zipf")
    ap.add_argument("--mb", type=int, default=32,
                    help="corpus size per probe pass (default 32)")
    ap.add_argument("--chunk-mb", type=int, default=2,
                    help="starting chunk size in MB (default 2)")
    ap.add_argument("--superstep", type=int, default=1)
    ap.add_argument("--inflight", type=int, default=4)
    ap.add_argument("--prefetch", type=int, default=4)
    ap.add_argument("--budget", type=int, default=6,
                    help="max probe passes (default 6)")
    ap.add_argument("--out", default=os.path.join(REPO, "tuned.json"),
                    help="tuned-profile JSON path (default ./tuned.json)")
    ap.add_argument("--keep-ledgers", default=None, metavar="DIR",
                    help="keep per-pass ledgers in DIR (default: tmpdir)")
    ap.add_argument("--selftest", action="store_true",
                    help="run against the checked-in fixtures and exit")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    return run_search(args)


if __name__ == "__main__":
    sys.exit(main())
