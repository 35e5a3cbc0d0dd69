#!/usr/bin/env python3
"""Render a run-ledger (and flight-recorder dump) into a run report.

Reads the JSONL run ledger the executor writes under ``--ledger``
(schema: docs/observability.md) and prints, per run:

* the run header (driver, job, devices, chunk geometry, input);
* throughput: steps, bytes, wall seconds, GB/s;
* the phase breakdown (read_wait / stage / dispatch / drain / reduce) with
  a bound classification — **dispatch-bound** (device queue full: compute
  or link is the ceiling), **read-bound** (the reader cannot keep ahead),
  or **stage-bound** (host assembly + H2D placement dominates) — the
  question the round-4 3x streamed-vs-H2D gap needed answered;
* anomalies: step-time spikes (elapsed > 3x the median step — recompiles
  and device stalls look exactly like this), device memory growth across
  the run (leaked live arrays), retries, failures (with the flight-dump
  path), checkpoint cadence, compile cost;
* the **timeline** section (ISSUE 7), when the ledger carries ``group``
  lifecycle records: measured per-resource busy seconds, the device-idle
  total with per-lane blame, the pairwise overlap matrix, and the
  critical-path ``bottleneck`` verdict (bounding resource + projected
  saving were it infinitely fast) — reconstructed by
  ``mapreduce_tpu/obs/timeline.py``; ``tools/trace_export.py`` renders
  the same records as a Perfetto-viewable trace;
* the **data health** section (ISSUE 8), when the ledger carries the
  per-run ``data`` record: on-device spill-fallback / rescue-escalation /
  dropped-token counters, table occupancy, top-bucket mass (key skew) and
  stable2 window occupancy, classified by ``mapreduce_tpu/obs/
  datahealth.py`` into spill-bound / rescue-heavy / skew-hot /
  occupancy-starved / table-pressure verdicts — the data-shape fitness
  signal next to the timeline's resource verdict.

``--compare A.jsonl B.jsonl`` diffs two ledgers' phase shares, bound
classifications, bottleneck verdicts and data-health dicts in one table —
the render surface for A/B rows (pipeline/nopipeline, fused/split).
``--run-id`` selects one run from an append-mode ledger (render and
compare alike) instead of always the last completed one (ISSUE 13
satellite).  When per-host shard files (``<ledger>.h*.jsonl``, ledger
v7) sit next to the analyzed ledger, the report appends the **fleet
section** — per-host busy/collective seconds, straggler skew with
slowest-host attribution, the ``fleet_bottleneck`` verdict and
host-imbalance flags (``mapreduce_tpu/obs/fleet.py``) — and
``--compare`` gains the fleet rows.

Deliberately jax-free and stdlib-only: a wedged TPU box, a laptop, or CI
can all read the forensics of a run that happened somewhere else (the
timeline module is loaded by file path, not via the package).  Unknown
record kinds and unknown fields pass through untouched (ledger forward
compat): a future-versioned ledger still renders.

Usage::

    python tools/obs_report.py /path/run.jsonl           # human report
    python tools/obs_report.py /path/run.jsonl --json    # machine-readable
    python tools/obs_report.py --flight /path/run.jsonl.flight.json
    python tools/obs_report.py --selftest                # fixture-driven

``--selftest`` analyzes the checked-in miniature ledger + flight fixtures
(``tools/fixtures/``) and asserts the report's load-bearing facts, so the
whole reporting path is exercised in tier-1 without a TPU (ISSUE 2
satellite).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

SPIKE_FACTOR = 3.0  # a step slower than 3x the median step is an anomaly
SPIKE_FLOOR_S = 0.05  # ...unless everything is sub-noise fast
MEM_GROWTH_FACTOR = 1.5  # first->last live-bytes ratio that flags growth
MEM_GROWTH_FLOOR = 32 << 20  # ...and the absolute delta that makes it real

_OBS_MODS: dict = {}


def _obs_mod(name: str):
    """A jax-free obs module (``timeline``/``datahealth``), loaded by file
    path from the source tree (importing the package would pull
    config/jax); falls back to the installed package, and to None when
    neither exists — the report then simply drops that section."""
    if name not in _OBS_MODS:
        src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           os.pardir, "mapreduce_tpu", "obs", name + ".py")
        try:
            if os.path.exists(src):
                import importlib.util

                spec = importlib.util.spec_from_file_location(
                    f"_mapreduce_tpu_obs_{name}", src)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                _OBS_MODS[name] = mod
            else:
                import importlib

                _OBS_MODS[name] = importlib.import_module(
                    f"mapreduce_tpu.obs.{name}")
        except Exception:
            _OBS_MODS[name] = False  # degraded: report without that section
    return _OBS_MODS[name] or None


def _timeline_mod():
    return _obs_mod("timeline")


def _datahealth_mod():
    return _obs_mod("datahealth")


def _fleet_mod():
    return _obs_mod("fleet")


def fleet_view_for(ledger_path: str, run_id=None):
    """The fleet artifact for a ledger with ``<ledger>.h*.jsonl`` shard
    files next to it (ISSUE 13), or None on single-host ledgers / when
    the fleet module is unavailable — the report degrades to no fleet
    section, never an error."""
    fl = _fleet_mod()
    if fl is None:
        return None
    try:
        return fl.from_ledger(ledger_path, run_id)
    except Exception:
        return None


def render_fleet(view: dict, out) -> None:
    _fleet_mod().render(view, out)


def read_ledger(path: str):
    """Parse JSONL, skipping unparseable lines (crash-truncated records are
    expected forensics).  Mirrors mapreduce_tpu.obs.ledger.read_ledger but
    stays import-free so this tool runs without the package or jax."""
    out = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue
    return out


def _median(xs):
    s = sorted(xs)
    n = len(s)
    if not n:
        return 0.0
    return s[n // 2] if n % 2 else (s[n // 2 - 1] + s[n // 2]) / 2


def _mem_bytes(mem: dict):
    """The comparable memory figure of a step record: the backend's
    bytes_in_use when it reports one (TPU/GPU), else the live-array
    aggregate (the CPU backend's only signal)."""
    if not mem:
        return None
    return mem.get("bytes_in_use", mem.get("live_bytes"))


def classify(phases: dict) -> str:
    """Bound classification over the streaming phases (not the end-of-
    stream tails or reduce: they time the stream END, not the steady
    state).  ``retire_wait`` — blocked on a full dispatch window — means
    the device is the ceiling and the window is doing its job.

    The classification is map-path-agnostic by design: on a FUSED run
    (run_start ``map_impl='fused'``) the whole map chain — tokenize
    included — executes inside ``dispatch``, so a bigger dispatch share
    than the same corpus' split run is the fusion working, not a
    regression.  :func:`map_flags` owns that attribution (the split-path
    tokenize/stage boundary this classifier was first written against no
    longer holds on fused runs)."""
    streaming = {k: phases.get(k, 0.0)
                 for k in ("read_wait", "stage", "dispatch", "retire_wait")}
    total = sum(streaming.values())
    if total <= 0:
        return "unknown"
    name, val = max(streaming.items(), key=lambda kv: kv[1])
    if val / total < 0.5:
        return "mixed"
    return {"read_wait": "read-bound", "stage": "stage-bound",
            "dispatch": "dispatch-bound",
            "retire_wait": "device-bound"}[name]


# Pipelining health thresholds (ISSUE 5): a run whose end-of-stream tails
# eat this share of the stream is drain-heavy (the window stopped feeding
# the device long before the stream ended); an overlap fraction below the
# floor means the loop spent most of the stream blocked — serialized.
DRAIN_HEAVY_FRAC = 0.25
OVERLAP_FLOOR = 0.5


def pipeline_flags(phases: dict, pipeline: dict | None) -> list:
    """Window-health findings from the run-end pipeline stats + phases:
    drain-heavy / overlap-starved runs and inflight misconfiguration
    (window never filled vs always full)."""
    flags = []
    stream = phases.get("stream") or sum(
        phases.get(k, 0.0) for k in ("read_wait", "stage", "dispatch",
                                     "retire_wait", "h2d_tail",
                                     "compute_tail", "drain"))
    tails = phases.get("h2d_tail", 0.0) + phases.get("compute_tail", 0.0) \
        + phases.get("drain", 0.0)
    if stream > 0 and tails / stream > DRAIN_HEAVY_FRAC:
        flags.append({
            "flag": "drain-heavy",
            "detail": (f"end-of-stream tails are {tails:.3f}s of "
                       f"{stream:.3f}s stream "
                       f"(h2d_tail={phases.get('h2d_tail', 0.0):.3f}s, "
                       f"compute_tail={phases.get('compute_tail', 0.0):.3f}s)"
                       " — the device finished the stream long after the "
                       "reader; deepen --inflight/--prefetch-depth or "
                       "shrink the superstep")})
    overlap = (pipeline or {}).get("overlap_fraction")
    if overlap is None and stream > 0:
        blocked = sum(phases.get(k, 0.0)
                      for k in ("read_wait", "retire_wait", "snapshot",
                                "h2d_tail", "compute_tail"))
        overlap = max(0.0, 1.0 - blocked / stream)
    if overlap is not None and overlap < OVERLAP_FLOOR:
        flags.append({
            "flag": "overlap-starved",
            "detail": (f"overlap fraction {overlap:.2f} < {OVERLAP_FLOOR}: "
                       "the driver loop spent most of the stream blocked "
                       "(serialized dispatch?); check inflight_groups > 1 "
                       "and the read_wait share")})
    if pipeline:
        cap = pipeline.get("inflight_groups") or 0
        depth_max = pipeline.get("depth_max")
        if cap > 1 and depth_max is not None and depth_max < cap:
            flags.append({
                "flag": "inflight-window-never-filled",
                "detail": (f"configured inflight_groups={cap} but observed "
                           f"depth peaked at {depth_max}: the reader/"
                           "staging side never fed a full window — the "
                           "extra depth buys nothing (raise prefetch_depth "
                           "or lower inflight_groups)")})
        full_frac = pipeline.get("full_frac")
        if cap > 1 and full_frac is not None and full_frac >= 0.9:
            flags.append({
                "flag": "inflight-window-always-full",
                "detail": (f"window hit capacity on {full_frac:.0%} of "
                           "dispatches: the device is the ceiling — a "
                           "deeper window may overlap more (or this is "
                           "simply compute-bound)")})
    return flags


def map_flags(header: dict | None, classification: str) -> list:
    """Map-path attribution (ISSUE 6): a FUSED run moved the whole map
    chain into the device dispatch, so host-side ceilings mean something
    different than they did on the split path — call that out instead of
    letting the split-era reading stand."""
    impl = (header or {}).get("map_impl")
    if impl != "fused":
        return []
    flags = []
    if classification in ("stage-bound", "read-bound"):
        flags.append({
            "flag": "fused-map-host-bound",
            "detail": (f"fused map run is {classification}: the fused "
                       "kernel deleted the device-side seam fix-up and "
                       "transpose/pad work, so the HOST side (reader/"
                       "staging) is now the ceiling — raise "
                       "--prefetch-depth / chunk size before blaming the "
                       "kernel")})
    return flags


def analyze_run(records: list) -> dict:
    """Summarize one run's records (already filtered to one run_id)."""
    start = next((r for r in records if r["kind"] == "run_start"), None)
    end = next((r for r in records if r["kind"] == "run_end"), None)
    steps = [r for r in records if r["kind"] == "step"]
    retries = [r for r in records if r["kind"] == "retry"]
    failures = [r for r in records if r["kind"] == "failure"]
    checkpoints = [r for r in records if r["kind"] == "checkpoint"]
    faults = [r for r in records if r["kind"] == "fault"]
    degrades = [r for r in records if r["kind"] == "degrade"]

    n_steps = sum(r.get("steps", 1) for r in steps)
    bytes_done = sum(r.get("group_bytes", 0) for r in steps)
    phases: dict = {}
    source = end.get("phases", {}) if end else {}
    if source:
        phases = dict(source)
    else:  # crashed run: reconstruct from the step deltas that DID land
        for r in steps:
            for k, v in r.get("phases", {}).items():
                phases[k] = phases.get(k, 0.0) + v
    wall = end.get("elapsed_s") if end else None

    # Step-time spikes: elapsed_s is wall since the previous record, so a
    # recompile or a stalled device shows as one fat step.
    elapsed = [(r.get("step_first"), r["elapsed_s"])
               for r in steps if r.get("elapsed_s") is not None]
    med = _median([e for _, e in elapsed])
    spikes = [{"step": s, "elapsed_s": e, "median_s": round(med, 6)}
              for s, e in elapsed
              if med > 0 and e > SPIKE_FACTOR * med and e > SPIKE_FLOOR_S]

    # Memory growth: compare the first and last step records' figure.
    mem_first = next((_mem_bytes(r.get("mem")) for r in steps
                      if _mem_bytes(r.get("mem")) is not None), None)
    mem_last = next((_mem_bytes(r.get("mem")) for r in reversed(steps)
                     if _mem_bytes(r.get("mem")) is not None), None)
    mem_growth = None
    if mem_first and mem_last and mem_last > mem_first * MEM_GROWTH_FACTOR \
            and mem_last - mem_first > MEM_GROWTH_FLOOR:
        mem_growth = {"first_bytes": mem_first, "last_bytes": mem_last,
                      "ratio": round(mem_last / mem_first, 2)}

    compile_s = 0.0
    for r in steps:
        evs = r.get("compile_events", {})
        if isinstance(evs, dict):
            compile_s += sum(e.get("seconds", 0.0) for e in evs.values())
        else:  # pre-aggregation record shape: a list of single events
            compile_s += sum(e.get("seconds", 0.0) for e in evs)

    gbps = None
    if wall and bytes_done:
        gbps = bytes_done / 1e9 / wall
    pipeline = end.get("pipeline") if end else None
    header = {k: start.get(k) for k in
              ("driver", "job", "devices", "chunk_bytes", "superstep",
               "backend", "map_impl", "combiner", "geometry",
               "geometry_spec", "merge_strategy", "input",
               "retry", "ledger_version", "host", "processes",
               "fault_plan")} \
        if start else None
    classification = classify(phases)
    # Measured timeline (ISSUE 7): present only when the run carries
    # `group` lifecycle records AND the reconstructor is loadable.
    timeline = None
    if any(r.get("kind") == "group" for r in records):
        tl = _timeline_mod()
        if tl is not None:
            timeline = tl.reconstruct(records,
                                      run_id=records[0].get("run_id"))
    # Data health (ISSUE 8): present only when the run carries a `data`
    # record AND the classifier is loadable.
    data = next((r for r in records if r.get("kind") == "data"), None)
    data_health = None
    if data is not None:
        data = {k: v for k, v in data.items()
                if k not in ("ts", "run_id", "kind")}
        dh = _datahealth_mod()
        if dh is not None:
            data_health = dh.classify(data)
    # Autotune recommendation (ISSUE 10): the `tune` record of hint-mode
    # runs, passed through as-is (future shapes render defensively).
    tune = next((r for r in records if r.get("kind") == "tune"), None)
    if tune is not None:
        tune = {k: v for k, v in tune.items()
                if k not in ("ts", "run_id", "kind")}
    # Live-run heartbeat (ISSUE 14, ledger v8): the LAST `progress`
    # record — an in-flight/crashed run's cursor, completion fraction
    # and ETA; tools/obswatch.py renders the same records live.
    progress = next((r for r in reversed(records)
                     if r.get("kind") == "progress"), None)
    if progress is not None:
        progress = {k: v for k, v in progress.items()
                    if k not in ("ts", "run_id", "kind")}
    # Reliability verdict (ISSUE 15, ledger v9): present only when the
    # run carries fault/degrade/retry/failure records AND the classifier
    # is loadable — a fault-free run has no reliability section, exactly
    # like a data-record-free run has no data-health section.
    reliability = None
    if faults or degrades or retries or failures:
        dh = _datahealth_mod()
        if dh is not None and hasattr(dh, "classify_reliability"):
            reliability = dh.classify_reliability(
                records, run_id=records[0].get("run_id"))
    return {
        "started_ts": start.get("ts") if start else None,
        "progress": progress,
        "failure_count": len(failures),
        "timeline": timeline,
        "data": data,
        "data_health": data_health,
        "tune": tune,
        "pipeline": pipeline,
        "overlap_fraction": (pipeline or {}).get("overlap_fraction"),
        "pipeline_flags": pipeline_flags(phases, pipeline),
        "map_flags": map_flags(header, classification),
        "run_id": records[0].get("run_id"),
        "header": header,
        "completed": end is not None,
        "step_records": len(steps),
        "steps": n_steps,
        "bytes": bytes_done,
        "wall_s": wall,
        "gb_per_s": round(gbps, 4) if gbps is not None else None,
        "phases": {k: round(v, 4) for k, v in sorted(phases.items())},
        "classification": classification,
        "spikes": spikes,
        "mem_growth": mem_growth,
        "retries": len(retries),
        "failures": [{"step": f.get("step"), "error": f.get("error"),
                      "flight_dump": f.get("flight_dump")} for f in failures],
        "checkpoints": len(checkpoints),
        "compile_s": round(compile_s, 4),
        "faults": len(faults),
        "degrades": [d.get("ladder_step") for d in degrades],
        "reliability": reliability,
    }


def analyze(path: str) -> list:
    """All run INSTANCES in a ledger file, in first-appearance order.

    Instances, not just ids (ISSUE 13): the multi-host contract passes
    one shared run_id to every process, and a crash+relaunch recovery
    appends a second run under that id — every run_start opens a new
    instance (``obs/fleet.py``'s canonical ``split_instances`` rule), so
    a crashed attempt and its recovery analyze separately instead of
    fusing into a chimera (first header + last run_end + combined
    steps)."""
    records = read_ledger(path)
    fl = _fleet_mod()
    if fl is not None:
        return [analyze_run(recs)
                for _, _, recs in fl.split_instances(records)]
    # Standalone-copy fallback (this file shipped without the obs
    # modules): the same rule, inlined — fleet.split_instances is the
    # canonical implementation.
    by_run: list = []   # (run_id, records) per instance
    current: dict = {}  # run_id -> index into by_run
    for r in records:
        rid = r.get("run_id", "?")
        if r.get("kind") == "run_start" or rid not in current:
            current[rid] = len(by_run)
            by_run.append((rid, []))
        by_run[current[rid]][1].append(r)
    return [analyze_run(rs) for _, rs in by_run]


def render_run(a: dict, out) -> None:
    h = a["header"] or {}
    out.write(f"run {a['run_id']}  [{h.get('driver', '?')}/"
              f"{h.get('job', '?')}  devices={h.get('devices', '?')}  "
              f"chunk={h.get('chunk_bytes', '?')}  "
              f"superstep={h.get('superstep', '?')}  "
              f"backend={h.get('backend', '?')}]\n")
    if h.get("input"):
        out.write(f"  input: {', '.join(map(str, h['input']))}\n")
    status = "completed" if a["completed"] else "DID NOT COMPLETE"
    out.write(f"  {status}: {a['steps']} steps "
              f"({a['step_records']} records), {a['bytes']} bytes")
    if a["wall_s"] is not None:
        out.write(f", {a['wall_s']:.3f}s")
    if a["gb_per_s"] is not None:
        out.write(f", {a['gb_per_s']:.4f} GB/s")
    out.write("\n")
    # Live-run heartbeat (ISSUE 14, ledger v8): an incomplete run's last
    # `progress` record says where the stream cursor got to — the
    # difference between "crashed at 10%" and "crashed at 99%", and what
    # tools/obswatch.py tails while the run is still going.
    p = a.get("progress")
    if p and not a["completed"]:
        out.write(f"  in flight: {p.get('cursor_bytes', '?')} bytes")
        if p.get("frac") is not None:
            out.write(f" ({100 * p['frac']:.1f}%)")
        if p.get("gb_per_s") is not None:
            out.write(f", {p['gb_per_s']:.4f} GB/s")
        if p.get("eta_s") is not None:
            out.write(f", ETA {p['eta_s']:.1f}s")
        if p.get("inflight_depth") is not None:
            out.write(f", inflight {p['inflight_depth']}")
        out.write("\n")
    if a["phases"]:
        streaming = ("read_wait", "stage", "dispatch", "retire_wait")
        total = sum(v for k, v in a["phases"].items()
                    if k in streaming) or 1.0
        parts = []
        for k, v in a["phases"].items():
            share = f" ({100 * v / total:.0f}%)" if k in streaming else ""
            parts.append(f"{k}={v:.3f}s{share}")
        out.write(f"  phases: {'  '.join(parts)}\n")
    out.write(f"  bound: {a['classification']}")
    if a["compile_s"]:
        out.write(f"  (compiles: {a['compile_s']:.2f}s)")
    out.write("\n")
    if (a["header"] or {}).get("map_impl") == "fused":
        out.write("  map: fused (whole map chain — tokenize included — "
                  "runs inside dispatch; read dispatch shares of a "
                  "fused/split A/B with that in view)\n")
    # Kernel-geometry line (ISSUE 12): which certified geometry set the
    # run compiled — rendered only when it is NOT the shipped default
    # (the map_impl/combiner precedent).  Future/custom shapes (a spec
    # dict, an unknown label) print as-is, never crash.
    geom = (a["header"] or {}).get("geometry")
    if geom not in (None, "default"):
        spec = (a["header"] or {}).get("geometry_spec")
        out.write(f"  geometry: {geom}"
                  + (f" {spec}" if spec else "") + "\n")
    # Multi-host stamp (ISSUE 13, ledger v7): which host's view this run
    # record stream is, and how many processes the fleet had — the shard
    # files next to the ledger hold the other hosts' views.
    procs = (a["header"] or {}).get("processes")
    if procs not in (None, 1):
        out.write(f"  fleet: host {(a['header'] or {}).get('host', '?')} "
                  f"of {procs} processes (per-host shards: "
                  "<ledger>.h<p>.jsonl)\n")
    p = a.get("pipeline")
    if p:
        out.write(f"  pipeline: inflight={p.get('inflight_groups')}  "
                  f"prefetch={p.get('prefetch_depth')}  "
                  f"depth mean/max={p.get('depth_mean')}/"
                  f"{p.get('depth_max')}")
        if a.get("overlap_fraction") is not None:
            out.write(f"  overlap={a['overlap_fraction']:.2f}")
        out.write("\n")
    tl = a.get("timeline")
    if tl:
        bn = tl["bottleneck"]
        idle = tl["device_idle"]
        out.write(f"  timeline: {tl['groups']} groups over "
                  f"{tl['span_s']:.3f}s  device busy "
                  f"{bn['device_busy_s']:.3f}s  idle {idle['total_s']:.3f}s")
        if idle.get("blocked_on"):
            blame = ", ".join(
                f"{k} {v:.3f}s" for k, v in
                sorted(idle["blocked_on"].items(), key=lambda kv: -kv[1]))
            out.write(f" (blocked on: {blame})")
        out.write("\n")
        out.write(f"  bottleneck: {bn['resource']} — {bn['detail']}\n")
        overlaps = {k: v for k, v in tl.get("overlap_s", {}).items() if v}
        if overlaps:
            out.write("  overlap: " + "  ".join(
                f"{k}={v:.3f}s" for k, v in
                sorted(overlaps.items(), key=lambda kv: -kv[1])[:6]) + "\n")
    d = a.get("data")
    if d:
        out.write(f"  data: {d.get('chunks', '?')} chunks, "
                  f"{d.get('tokens', '?')} tokens")
        if d.get("dropped_tokens") is not None:
            out.write(f", dropped {d['dropped_tokens']}")
        if d.get("overlong"):
            out.write(f", overlong {d['overlong']} "
                      f"(rescued {d.get('rescued', 0)})")
        if d.get("fallback_chunks"):
            out.write(f", spill fallbacks {d['fallback_chunks']}")
        if d.get("table_occupancy") is not None:
            out.write(f", table {100 * d['table_occupancy']:.1f}% full")
        if d.get("top_mass") is not None:
            out.write(f", top-mass {100 * d['top_mass']:.2f}%")
        if d.get("window_occupancy") is not None:
            out.write(f", windows {100 * d['window_occupancy']:.0f}% full")
        out.write("\n")
        # Map-side combiner line (ISSUE 11): resolved mode + what the
        # hot-key cache actually bought this run.
        mode = d.get("combiner") or (a["header"] or {}).get("combiner")
        if (mode and mode != "off") or d.get("combiner_hits"):
            out.write(f"  combiner: {mode or '?'}")
            hits = d.get("combiner_hits")
            if hits:
                hr = d.get("combiner_hit_rate")
                out.write(f" — {hits} hits"
                          + (f" ({100 * hr:.2f}% of tokens)"
                             if hr is not None else ""))
                if d.get("combiner_rows_deleted") is not None:
                    out.write(f", {d['combiner_rows_deleted']} sort rows "
                              "deleted")
                out.write(f", {d.get('combiner_flushes', 0)} flushes "
                          f"({d.get('combiner_evicted', 0)} cold)")
            elif mode == "hot-cache":
                out.write(" — no hits (cache cold or fallback-dominated)")
            out.write("\n")
    health = a.get("data_health")
    if health:
        out.write(f"  data health: {health['verdict']}\n")
        for f in health.get("flags", []):
            out.write(f"  DATA {f['flag']}: {f['detail']}\n")
    t = a.get("tune")
    if t:
        changed = t.get("changed") or {}
        moves = ", ".join(
            f"{k} {v[0]} -> {v[1]}"
            if isinstance(v, (list, tuple)) and len(v) == 2
            else f"{k}: {v}" for k, v in changed.items())
        verdict = "converged" if t.get("converged") else (moves or "no move")
        out.write(f"  tune: {t.get('rule', '?')} — {verdict}\n")
        if t.get("reason"):
            out.write(f"    {t['reason']}\n")
    for f in a.get("pipeline_flags", []):
        out.write(f"  PIPELINE {f['flag']}: {f['detail']}\n")
    for f in a.get("map_flags", []):
        out.write(f"  MAP {f['flag']}: {f['detail']}\n")
    if a["checkpoints"] or a["retries"]:
        out.write(f"  checkpoints: {a['checkpoints']}  "
                  f"retries: {a['retries']}\n")
    # Reliability section (ISSUE 15, ledger v9): a degraded-but-alive or
    # chaos-tested run is VISIBLE, not mysterious.  The header's
    # fault_plan stamp names the chaos a chaotic run ran under.
    if (a["header"] or {}).get("fault_plan"):
        out.write(f"  chaos: fault_plan={a['header']['fault_plan']}\n")
    r = a.get("reliability")
    if r and (r.get("verdict") != "clean" or a.get("faults")):
        out.write(f"  reliability: {r.get('verdict', '?')}")
        sig = r.get("signals") or {}
        if sig.get("faults_total"):
            out.write(f"  ({sig.get('faults_injected', 0)} injected / "
                      f"{sig.get('faults_real', 0)} real faults)")
        out.write("\n")
        for f in r.get("flags", []):
            out.write(f"  RELIABILITY {f.get('flag', '?')}: "
                      f"{f.get('detail', '')}\n")
    for s in a["spikes"]:
        out.write(f"  ANOMALY step-time spike: step {s['step']} took "
                  f"{s['elapsed_s']:.3f}s vs median {s['median_s']:.3f}s "
                  "(recompile? device stall?)\n")
    if a["mem_growth"]:
        g = a["mem_growth"]
        out.write(f"  ANOMALY memory growth: {g['first_bytes']} -> "
                  f"{g['last_bytes']} bytes ({g['ratio']}x) across the run "
                  "(leaked live arrays?)\n")
    for f in a["failures"]:
        out.write(f"  FAILURE at step {f['step']}: {f['error']}\n")
        if f.get("flight_dump"):
            out.write(f"    flight dump: {f['flight_dump']}\n")


# -- run enumeration (ISSUE 14 satellite) ------------------------------------

def run_status(a: dict) -> str:
    """completed / crashed / in-flight of one analyzed run — the one
    rule lives in ``obs/fleet.py`` (``run_status``), shared with
    ``obswatch`` and the ``history`` digests; the inline expression is
    the standalone-copy fallback."""
    fl = _fleet_mod()
    if fl is not None:
        return fl.run_status(bool(a.get("completed")),
                             int(a.get("failure_count") or 0))
    if a.get("completed"):
        return "completed"
    return "crashed" if a.get("failure_count") else "in-flight"


def list_runs(path: str) -> list:
    """Enumerate the run INSTANCES of an append-mode ledger (ISSUE 14
    satellite): ``--run-id`` requires already knowing the id — this is
    where the ids come from.  One row per instance, in file order, with
    the start wall time, family/backend and the geometry/combiner/
    map-impl stamps the A/B selectors key on."""
    rows = []
    for a in analyze(path):
        h = a.get("header") or {}
        rows.append({
            "run_id": a.get("run_id"),
            "started_ts": a.get("started_ts"),
            "status": run_status(a),
            "driver": h.get("driver"),
            "family": h.get("job"),
            "backend": h.get("backend"),
            "geometry": h.get("geometry") or "default",
            "combiner": h.get("combiner") or "off",
            "map_impl": h.get("map_impl") or "split",
            "steps": a.get("steps"),
            "bytes": a.get("bytes"),
            "gb_per_s": a.get("gb_per_s"),
            "cursor_frac": (a.get("progress") or {}).get("frac"),
        })
    return rows


def render_list(rows: list, out) -> None:
    import datetime

    for r in rows:
        ts = r.get("started_ts")
        when = datetime.datetime.fromtimestamp(ts).strftime(
            "%Y-%m-%d %H:%M:%S") if isinstance(ts, (int, float)) else "?"
        geom = "" if r["geometry"] == "default" else f" geom={r['geometry']}"
        comb = "" if r["combiner"] == "off" else f" combiner={r['combiner']}"
        tail = f"  {r['gb_per_s']:.4f} GB/s" if r.get("gb_per_s") else ""
        frac = f" @{100 * r['cursor_frac']:.0f}%" \
            if r["status"] != "completed" and r.get("cursor_frac") else ""
        out.write(f"{r['run_id']}  {when}  {r['status']}{frac}  "
                  f"[{r.get('family', '?')}/{r.get('backend', '?')}"
                  f"/{r['map_impl']}{geom}{comb}]  "
                  f"{r.get('steps', '?')} steps{tail}\n")


# -- A/B ledger diffing (ISSUE 8 satellite) ----------------------------------

_STREAMING_PHASES = ("read_wait", "stage", "dispatch", "retire_wait")


def _phase_shares(phases: dict) -> dict:
    total = sum(phases.get(k, 0.0) for k in _STREAMING_PHASES)
    if total <= 0:
        return {}
    return {k: phases.get(k, 0.0) / total for k in _STREAMING_PHASES
            if phases.get(k)}


def _pick_run(runs: list, run_id: str | None = None) -> dict | None:
    """The run a compare reads from one ledger: ``run_id`` when the
    caller selects one (ISSUE 13 satellite: an append-mode ledger holds
    many runs — bench keys on run_id, humans get the same selector),
    else the LAST completed run (the most recent measurement), else the
    last run at all.  An explicit id picks its LAST instance — the same
    rule ``obs/fleet.py`` applies, so a compare's phase rows and fleet
    rows describe the same execution."""
    if run_id is not None:
        matches = [a for a in runs if a.get("run_id") == run_id]
        return matches[-1] if matches else None
    done = [a for a in runs if a.get("completed")]
    pool = done or runs
    return pool[-1] if pool else None


def compare_runs(a: dict, b: dict) -> list:
    """Two analyzed runs -> comparison rows ``[label, A, B, delta]``
    (delta empty for non-numeric rows).  One table answers the A/B
    question the queued bench rows ask: where did the seconds move, did
    the bounding resource change, and did the DATA see the same world."""
    rows: list = []

    def num(label, va, vb, fmt="{:.4f}"):
        da = fmt.format(va) if isinstance(va, (int, float)) else "-"
        db = fmt.format(vb) if isinstance(vb, (int, float)) else "-"
        dd = fmt.format(vb - va) \
            if isinstance(va, (int, float)) and isinstance(vb, (int, float)) \
            else ""
        rows.append([label, da, db, dd])

    def text(label, va, vb):
        rows.append([label, str(va if va is not None else "-"),
                     str(vb if vb is not None else "-"), ""])

    num("gb_per_s", a.get("gb_per_s"), b.get("gb_per_s"))
    num("wall_s", a.get("wall_s"), b.get("wall_s"), "{:.3f}")
    sa, sb = _phase_shares(a.get("phases", {})), \
        _phase_shares(b.get("phases", {}))
    for k in _STREAMING_PHASES:
        if k in sa or k in sb:
            num(f"{k} share", sa.get(k, 0.0), sb.get(k, 0.0), "{:.0%}")
    text("bound", a.get("classification"), b.get("classification"))
    num("overlap_fraction", a.get("overlap_fraction"),
        b.get("overlap_fraction"), "{:.2f}")
    bna = (a.get("timeline") or {}).get("bottleneck") or {}
    bnb = (b.get("timeline") or {}).get("bottleneck") or {}
    if bna or bnb:
        text("bottleneck", bna.get("resource"), bnb.get("resource"))
        num("projected_saving_s", bna.get("projected_saving_s"),
            bnb.get("projected_saving_s"), "{:.3f}")
    ha, hb = a.get("data_health") or {}, b.get("data_health") or {}
    if ha or hb:
        text("data verdict", ha.get("verdict"), hb.get("verdict"))
        siga, sigb = ha.get("signals", {}), hb.get("signals", {})
        for k in ("top_mass", "fallback_frac", "overlong_frac",
                  "dropped_frac", "table_occupancy", "window_occupancy",
                  "distinct_ratio", "combiner_hit_rate"):
            va, vb = siga.get(k), sigb.get(k)
            if va is not None or vb is not None:
                num(k, va, vb, "{:.4f}")
    ga = (a.get("header") or {}).get("geometry")
    gb = (b.get("header") or {}).get("geometry")
    if (ga not in (None, "default")) or (gb not in (None, "default")):
        # The geometry A/B row (ISSUE 12): which arm compiled which
        # certified kernel-geometry set.
        text("geometry", ga or "default", gb or "default")
    da, db = a.get("data") or {}, b.get("data") or {}
    ca, cb = da.get("combiner"), db.get("combiner")
    if (ca and ca != "off") or (cb and cb != "off"):
        # The combiner A/B row (ISSUE 11): which arm ran which mode, and
        # the net sort rows each deleted.
        text("combiner", ca, cb)
        num("combiner_rows_deleted", da.get("combiner_rows_deleted"),
            db.get("combiner_rows_deleted"), "{:.0f}")
    ra, rb = a.get("reliability") or {}, b.get("reliability") or {}
    if ra or rb:
        # The reliability A/B row (ISSUE 15): did either arm degrade,
        # absorb faults, or run under a fault plan.
        text("reliability", ra.get("verdict"), rb.get("verdict"))
        num("faults", a.get("faults"), b.get("faults"), "{:.0f}")
    return rows


def compare(path_a: str, path_b: str, out, as_json: bool = False,
            run_id: str | None = None) -> int:
    """Diff two ledgers (phase shares, verdicts, data health) in one
    table; see ``compare_runs``.  ``run_id`` selects that run on both
    sides instead of each side's last completed one."""
    a = _pick_run(analyze(path_a), run_id)
    b = _pick_run(analyze(path_b), run_id)
    if a is None or b is None:
        print("compare: no runs found in "
              f"{path_a if a is None else path_b}"
              + (f" (run_id {run_id})" if run_id else ""), file=sys.stderr)
        return 1
    rows = compare_runs(a, b)
    # Fleet rows (ISSUE 13): when either side is a sharded multi-host
    # ledger, the A/B table also answers which arm's FLEET was bound by
    # what, and by how much.  Keyed on the PICKED run's id, so the fleet
    # rows always describe the same run as the phase/verdict rows above
    # (an append-mode ledger's last completed run need not be the
    # shards' last run).
    fa = fleet_view_for(path_a, run_id or a.get("run_id"))
    fb = fleet_view_for(path_b, run_id or b.get("run_id"))
    if fa or fb:
        bna = (fa or {}).get("fleet_bottleneck") or {}
        bnb = (fb or {}).get("fleet_bottleneck") or {}
        rows.append(["fleet verdict", str(bna.get("verdict", "-")),
                     str(bnb.get("verdict", "-")), ""])
        va, vb = bna.get("projected_saving_s"), bnb.get("projected_saving_s")
        rows.append(["fleet saving_s",
                     f"{va:.3f}" if isinstance(va, (int, float)) else "-",
                     f"{vb:.3f}" if isinstance(vb, (int, float)) else "-",
                     f"{vb - va:.3f}" if isinstance(va, (int, float))
                     and isinstance(vb, (int, float)) else ""])
        rows.append(["fleet imbalance",
                     str(((fa or {}).get("imbalance") or {})
                         .get("verdict", "-")),
                     str(((fb or {}).get("imbalance") or {})
                         .get("verdict", "-")), ""])
    if as_json:
        out.write(json.dumps({
            "a": {"ledger": path_a, "run_id": a.get("run_id")},
            "b": {"ledger": path_b, "run_id": b.get("run_id")},
            "rows": rows,
            "a_run": a, "b_run": b}) + "\n")
        return 0
    name_a = f"A={a.get('run_id')}"
    name_b = f"B={b.get('run_id')}"
    out.write(f"compare  A: {path_a} ({a.get('run_id')})  "
              f"B: {path_b} ({b.get('run_id')})\n")
    widths = [max(len(r[i]) if i else len(r[0]) for r in rows)
              for i in range(4)]
    widths = [max(w, len(h)) for w, h in
              zip(widths, ("metric", name_a, name_b, "delta"))]
    header = ["metric", name_a, name_b, "delta"]
    out.write("  " + "  ".join(h.ljust(w) for h, w in
                               zip(header, widths)).rstrip() + "\n")
    for r in rows:
        out.write("  " + "  ".join(c.ljust(w) for c, w in
                                   zip(r, widths)).rstrip() + "\n")
    return 0


def render_flight(path: str, out) -> None:
    with open(path, encoding="utf-8") as f:
        dump = json.load(f)
    ctx = dump.get("context", {})
    out.write(f"flight dump {path}\n")
    out.write(f"  context: {json.dumps(ctx)}\n")
    out.write(f"  events: {dump.get('events_kept', 0)} kept of "
              f"{dump.get('events_recorded', 0)} recorded\n")
    for e in dump.get("events", [])[-10:]:
        extra = {k: v for k, v in e.items() if k not in ("ts", "kind")}
        out.write(f"    {e.get('kind')} {json.dumps(extra)}\n")
    state = dump.get("state")
    if state:
        out.write(f"  state: {state.get('n_leaves')} leaves, "
                  f"{state.get('total_nbytes')} bytes\n")


def selftest() -> int:
    """Exercise the full analysis path on the checked-in fixtures and
    assert the report's load-bearing facts."""
    fdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
    ledger = os.path.join(fdir, "mini_ledger.jsonl")
    ledger_b = os.path.join(fdir, "mini_ledger_b.jsonl")
    flight = os.path.join(fdir, "mini_flight.json")
    runs = analyze(ledger)
    assert len(runs) == 10, f"fixture holds ten runs, got {len(runs)}"
    a = runs[0]
    assert a["completed"], "fixture run has a run_end record"
    assert a["steps"] == 6 and a["step_records"] == 6, \
        f"6 step records expected, got {a['steps']}/{a['step_records']}"
    assert a["bytes"] == 6 * 4 * (1 << 20), f"bytes wrong: {a['bytes']}"
    assert a["classification"] == "dispatch-bound", a["classification"]
    assert [s["step"] for s in a["spikes"]] == [4], a["spikes"]
    assert a["mem_growth"] and a["mem_growth"]["ratio"] > 4, a["mem_growth"]
    assert a["retries"] == 1 and a["checkpoints"] == 1
    assert a["compile_s"] > 0.5, a["compile_s"]
    # Run 1: the window was configured but never filled, and the loop was
    # mostly blocked — both ISSUE 5 misconfiguration flags must fire.
    assert a["pipeline"]["inflight_groups"] == 4
    assert a["overlap_fraction"] == 0.31
    flags = {f["flag"] for f in a["pipeline_flags"]}
    assert flags == {"overlap-starved", "inflight-window-never-filled"}, flags
    # Run 2: window always full + fat end-of-stream tails -> drain-heavy
    # and always-full, but NOT never-filled.
    b = runs[1]
    assert b["classification"] == "device-bound", b["classification"]
    bflags = {f["flag"] for f in b["pipeline_flags"]}
    assert bflags == {"drain-heavy", "overlap-starved",
                      "inflight-window-always-full"}, bflags
    # Runs 1-2 predate map_impl in the ledger (split-era records): the
    # header degrades to None and no map flag may fire.
    assert a["header"]["map_impl"] is None and not a["map_flags"]
    # Run 3: a FUSED run (ISSUE 6) that is stage-bound — the split-era
    # reading ("host assembly dominates, kernel fine") is now the
    # headline fact: the fused kernel deleted device-side map work, so
    # the host IS the ceiling, and the fused-specific flag must say so.
    c = runs[2]
    assert c["header"]["map_impl"] == "fused", c["header"]
    assert c["classification"] == "stage-bound", c["classification"]
    assert not c["pipeline_flags"], c["pipeline_flags"]
    cflags = {f["flag"] for f in c["map_flags"]}
    assert cflags == {"fused-map-host-bound"}, cflags
    # Runs 1-3 predate group records: no timeline section, by design —
    # and predate data records: no data-health section either (ISSUE 8).
    assert a["timeline"] is None and c["timeline"] is None
    assert a["data"] is None and a["data_health"] is None
    # Run 4 (ISSUE 7): a pipelined run carrying `group` lifecycle records.
    # Constructed reader-bound: two 0.2 s device-idle gaps both covered by
    # the reader lane, and 0.28 s of the 2.02 s span is reader-exclusive —
    # the timeline must name the reader as the critical path with exactly
    # those measured seconds.
    d = runs[3]
    assert d["header"]["ledger_version"] == 2, d["header"]
    tl = d["timeline"]
    assert tl is not None and tl["groups"] == 4, tl
    bn = tl["bottleneck"]
    assert bn["resource"] == "reader", bn
    assert round(bn["projected_saving_s"], 4) == 0.28, bn
    assert round(tl["device_idle"]["total_s"], 4) == 0.4, tl["device_idle"]
    assert [g["blocking"] for g in tl["device_idle"]["gaps"]] \
        == ["reader", "reader"], tl["device_idle"]
    assert round(tl["overlap_s"]["staging+device"], 4) == 0.1
    assert round(tl["overlap_s"]["reader+device"], 4) == 1.1
    assert round(tl["lane_busy_s"]["device"], 4) == 1.4
    # The phase classifier agrees with the measured timeline here (both
    # say the reader) — the timeline adds the HOW MUCH the deltas cannot.
    assert d["classification"] == "read-bound", d["classification"]
    # Run 5 in file order (ISSUE 10): a ledger-v4 autotune-hint run.  The
    # `tune` record (recommendation + decision trail) must surface next to
    # the verdicts it was derived from — here a reader-bound run whose
    # hint doubles prefetch_depth — and the other runs (no tune record)
    # must carry None.  (It sits BEFORE fixture05 in the file so the
    # spill-heavy run stays the --compare pick below.)
    g7 = runs[4]
    assert g7["header"]["ledger_version"] == 4, g7["header"]
    tn = g7["tune"]
    assert tn is not None and tn["rule"] == "raise-prefetch", tn
    assert tn["changed"] == {"prefetch_depth": [4, 8]}, tn["changed"]
    assert tn["converged"] is False and tn["mode"] == "hint", tn
    assert tn["signals"]["resource"] == "reader", tn["signals"]
    assert tn["trail"], "decision trail must ride the record"
    assert g7["timeline"]["bottleneck"]["resource"] == "reader", \
        "the tune hint and the timeline verdict describe the same run"
    # Run 6 in file order (ISSUE 11): a ledger-v5 combiner-on fused run.
    # Hand arithmetic: 42000 of 60000 tokens absorbed by the hot-key
    # cache (hit rate 0.7), 2000 flush rows re-emitted -> 40000 sort rows
    # deleted net, 150 cold entries; the top key at 12000/60000 = 20% is
    # skew-hot, and the flag's detail must say the combiner is already
    # absorbing the stream instead of recommending the knob.
    h8 = runs[5]
    assert h8["header"]["ledger_version"] == 5, h8["header"]
    assert h8["header"]["combiner"] == "hot-cache", h8["header"]
    assert h8["data"]["combiner"] == "hot-cache", h8["data"]
    h8sig = h8["data_health"]["signals"]
    assert h8sig["combiner_hit_rate"] == round(42000 / 60000, 6), h8sig
    assert h8sig["combiner_rows_deleted"] == 42000 - 2000, h8sig
    assert h8["data_health"]["verdict"] == "skew-hot", h8["data_health"]
    h8flag = next(f for f in h8["data_health"]["flags"]
                  if f["flag"] == "skew-hot")
    assert "absorbing 70.0%" in h8flag["detail"], h8flag
    # Run 7 in file order (ISSUE 13): a ledger-v7 two-host run's
    # coordinator view — host-stamped records, the processes/clock
    # topology in run_start, a `collective` record.  The header must
    # surface the stamp, and the collective record must pass through
    # every consumer (it sits BEFORE fixture05 so the spill run stays
    # the --compare pick below).
    p9 = runs[6]
    assert p9["header"]["ledger_version"] == 7, p9["header"]
    assert p9["header"]["host"] == 0 and p9["header"]["processes"] == 2, \
        p9["header"]
    assert p9["completed"] and p9["timeline"]["groups"] == 2, p9["timeline"]
    # Run 8 in file order (ISSUE 15): a ledger-v9 CHAOTIC run — a fault
    # plan fired two injected faults (dispatch crossing 2, token-wait
    # crossing 1), the transient one was absorbed by a retry, and the
    # resource one stepped the degradation ladder twice (tall512 ->
    # default geometry, then combiner off).  Hand arithmetic: 2 injected
    # / 0 real faults, retries {transient: 1, resource: 1}, verdict
    # `degraded` (degraded outranks chaos-tested in RELIABILITY_ORDER),
    # and the run_start fault_plan stamp must round-trip.
    ch = runs[7]
    assert ch["header"]["ledger_version"] == 9, ch["header"]
    assert ch["header"]["fault_plan"] \
        == "at=dispatch:2:transient,at=token-wait:1:resource", ch["header"]
    assert ch["completed"], "the chaotic run finished — degraded, alive"
    assert ch["faults"] == 2, ch["faults"]
    assert ch["degrades"] == ["revert-geometry", "combiner-off"], \
        ch["degrades"]
    rel = ch["reliability"]
    assert rel is not None and rel["verdict"] == "degraded", rel
    rsig = rel["signals"]
    assert rsig["faults_injected"] == 2 and rsig["faults_real"] == 0, rsig
    assert rsig["retries"] == 2 and rsig["retries_by_class"] \
        == {"transient": 1, "resource": 1}, rsig
    assert rsig["degrade_steps"] == ["revert-geometry", "combiner-off"]
    relflags = {f["flag"] for f in rel["flags"]}
    assert relflags == {"degraded", "chaos-tested"}, relflags
    # Fault-free runs carry NO reliability section at all (the section
    # only exists when there is something to report) — except fixture01,
    # whose single pre-taxonomy retry record classifies clean.
    assert c["reliability"] is None and d["reliability"] is None
    assert a["reliability"] is not None \
        and a["reliability"]["verdict"] == "clean", a["reliability"]
    # Run 9 in file order (ISSUE 8): a spill-heavy pallas run carrying
    # per-group `data` dicts and the per-run `data` record.  Checked
    # against the arithmetic done by hand on the fixture: 3 of 6 chunks
    # took the full-resolution fallback (fallback_frac 0.5 > the 5%
    # gate), overlong is 120/60000 = 0.2% of the stream with one tier-2
    # escalation, the top key carries 1500/60000 = 2.5% (NOT skew-hot at
    # the 5% gate), and 20 distinct keys spilled — so the verdict is
    # spill-bound with rescue-heavy and table-pressure riding along, and
    # nothing else.
    e = runs[8]
    assert e["header"]["ledger_version"] == 3, e["header"]
    assert e["data"] is not None and e["data"]["fallback_chunks"] == 3
    eh = e["data_health"]
    assert eh is not None, "data record must classify"
    sig = eh["signals"]
    assert sig["fallback_frac"] == round(3 / 6, 6), sig
    assert sig["overlong_frac"] == round(120 / 60000, 6), sig
    assert sig["rescued_frac"] == round(100 / 120, 6), sig
    assert sig["top_mass"] == round(1500 / 60000, 6), sig
    assert sig["window_occupancy"] == 0.6104, sig
    eflags = {f["flag"] for f in eh["flags"]}
    assert eflags == {"spill-bound", "rescue-heavy", "table-pressure"}, eflags
    assert eh["verdict"] == "spill-bound", eh["verdict"]
    # Per-group data dicts ride the group records into the timeline args.
    egroups = [r for r in read_ledger(ledger)
               if r.get("kind") == "group" and r.get("run_id") == "fixture05"]
    assert all("data" in g for g in egroups), egroups
    assert all(runs[i]["tune"] is None
               for i in (0, 1, 2, 3, 5, 6, 7, 8, 9)), \
        "runs without a tune record must carry None"
    # Run 9 in file order (ISSUE 14): a ledger-v8 run still IN FLIGHT —
    # no run_end, but two `progress` heartbeat records.  Hand arithmetic:
    # 16 MiB of the 32 MiB corpus at 8 MiB/s -> 50.0%, ETA 2.0 s.  The
    # report must surface the last heartbeat instead of a bare DID NOT
    # COMPLETE, and the status classifier must read in-flight (no
    # failure record), not crashed.
    w = runs[9]
    assert w["header"]["ledger_version"] == 8, w["header"]
    assert not w["completed"] and w["failure_count"] == 0
    assert w["progress"]["frac"] == 0.5, w["progress"]
    assert w["progress"]["eta_s"] == 2.0, w["progress"]
    assert run_status(w) == "in-flight"
    # --list-runs (ISSUE 14 satellite): one row per instance with the
    # stamps and status — where --run-id ids come from.
    lrows = list_runs(ledger)
    assert len(lrows) == 10, lrows
    byid = {r["run_id"]: r for r in lrows}
    assert byid["fixture10"]["status"] == "in-flight"
    assert byid["fixture10"]["cursor_frac"] == 0.5
    assert byid["fixture01"]["status"] == "completed"
    assert byid["fixture08"]["combiner"] == "hot-cache", byid["fixture08"]
    assert byid["fixture03"]["map_impl"] == "fused", byid["fixture03"]
    import io

    lbuf = io.StringIO()
    render_list(lrows, lbuf)
    ltext = lbuf.getvalue()
    assert "fixture10" in ltext and "in-flight @50%" in ltext, ltext
    assert ltext.count("\n") == 10, ltext
    # --run-id (ISSUE 13 satellite): an append-mode ledger's compare pick
    # honors an explicit selector instead of always the last completed
    # run, and an absent id is an honest miss, not a silent fallback.
    assert _pick_run(runs, "fixture01")["run_id"] == "fixture01"
    assert _pick_run(runs, "no-such-run") is None
    assert _pick_run(runs)["run_id"] == "fixture05"
    # The clean A/B counterpart (mini_ledger_b): uniform corpus, no
    # fallbacks, top key at 24/60000 = 0.04% — verdict clean; the pair is
    # the checked-in proof that a hot-key corpus and a uniform one are
    # DISTINGUISHABLE from the ledger alone.
    runs_b = analyze(ledger_b)
    assert len(runs_b) == 1, runs_b
    f6 = runs_b[0]
    assert f6["data_health"]["verdict"] == "clean", f6["data_health"]
    assert not f6["data_health"]["flags"]
    assert f6["data_health"]["signals"]["top_mass"] == round(24 / 60000, 6)
    # fixture06 is also the ledger-v6 geometry-stamped run (ISSUE 12):
    # the searched 'tall512' label must surface in the header and render
    # as a geometry line, while runs with no stamp (every mini_ledger
    # run) degrade to None and render nothing.
    assert f6["header"]["ledger_version"] == 6, f6["header"]
    assert f6["header"]["geometry"] == "tall512", f6["header"]
    assert h8["header"]["geometry"] is None, h8["header"]
    # The human renderer must run over all artifacts without raising.
    import io

    buf = io.StringIO()
    render_run(a, buf)
    render_run(b, buf)
    render_run(c, buf)
    render_run(d, buf)
    render_run(e, buf)
    render_run(g7, buf)
    render_run(h8, buf)
    render_run(f6, buf)
    render_run(p9, buf)
    render_run(ch, buf)
    render_run(w, buf)
    render_flight(flight, buf)
    body = buf.getvalue()
    assert "in flight: 16777216 bytes (50.0%)" in body, body
    assert "ETA 2.0s" in body, body
    assert "fleet: host 0 of 2 processes" in body, body
    assert ("combiner: hot-cache — 42000 hits (70.00% of tokens), "
            "40000 sort rows deleted, 2000 flushes (150 cold)") in body, body
    assert "ANOMALY step-time spike" in body
    assert "ANOMALY memory growth" in body
    assert "injected device fault" in body
    assert "PIPELINE inflight-window-never-filled" in body
    assert "PIPELINE drain-heavy" in body
    assert "pipeline: inflight=4" in body
    assert "map: fused" in body
    assert "MAP fused-map-host-bound" in body
    assert "timeline: 4 groups" in body
    assert "bottleneck: reader" in body
    assert "blocked on: reader 0.400s" in body
    assert "geometry: tall512" in body
    assert "data health: spill-bound" in body
    assert "DATA spill-bound" in body and "DATA rescue-heavy" in body
    assert "spill fallbacks 3" in body
    assert "tune: raise-prefetch — prefetch_depth 4 -> 8" in body
    # The reliability section (ISSUE 15): a degraded-but-alive chaos run
    # is rendered visibly — plan stamp, verdict, the ladder walked.
    assert ("chaos: fault_plan=at=dispatch:2:transient,"
            "at=token-wait:1:resource") in body, body
    assert "reliability: degraded  (2 injected / 0 real faults)" in body, \
        body
    assert "RELIABILITY degraded" in body \
        and "revert-geometry -> combiner-off" in body, body
    assert "RELIABILITY chaos-tested" in body, body
    # A/B ledger diffing (ISSUE 8 satellite): the spill-heavy run vs the
    # clean uniform counterpart must render one table naming both data
    # verdicts, and the machine-readable form must carry the rows.
    cbuf = io.StringIO()
    assert compare(ledger, ledger_b, cbuf) == 0
    ctext = cbuf.getvalue()
    assert "A=fixture05" in ctext and "B=fixture06" in ctext, ctext
    assert "data verdict" in ctext and "spill-bound" in ctext \
        and "clean" in ctext, ctext
    assert "fallback_frac" in ctext and "top_mass" in ctext, ctext
    # The geometry A/B row (ISSUE 12): the unstamped spill run reads as
    # 'default' against fixture06's searched 'tall512'.
    grow = next(line for line in ctext.splitlines()
                if line.strip().startswith("geometry"))
    assert "default" in grow and "tall512" in grow, grow
    cjson = io.StringIO()
    assert compare(ledger, ledger_b, cjson, as_json=True) == 0
    cobj = json.loads(cjson.getvalue())
    assert cobj["a"]["run_id"] == "fixture05" \
        and cobj["b"]["run_id"] == "fixture06", cobj
    assert any(r[0] == "data verdict" for r in cobj["rows"]), cobj["rows"]
    # Fleet section (ISSUE 13): the two-host shard fixtures next to
    # fleet_ledger.jsonl merge into the cross-host view — straggler
    # verdict + host-imbalance flag rendered under the run report — and
    # the --compare table gains the fleet rows when either side shards.
    fview = fleet_view_for(os.path.join(fdir, "fleet_ledger.jsonl"))
    assert fview is not None and fview["hosts"] == [0, 1], fview
    assert fview["fleet_bottleneck"]["verdict"] == "straggler-bound", fview
    assert fview["straggler"]["total_skew_s"] == 2.0, fview["straggler"]
    assert fview["imbalance"]["verdict"] == "host-imbalance", fview
    fbuf = io.StringIO()
    render_fleet(fview, fbuf)
    fbody = fbuf.getvalue()
    assert "fleet bottleneck: straggler-bound" in fbody, fbody
    assert "FLEET host-imbalance" in fbody, fbody
    assert fleet_view_for(ledger) is None, \
        "a shardless ledger must degrade to no fleet section"
    fcmp = io.StringIO()
    assert compare(os.path.join(fdir, "fleet_ledger.jsonl"), ledger_b,
                   fcmp) == 0
    ftext = fcmp.getvalue()
    assert "fleet verdict" in ftext and "straggler-bound" in ftext, ftext
    # Ledger forward compat (ISSUE 7 satellite): a future-versioned ledger
    # with unknown kinds and unknown fields must analyze and render
    # without error, and still surface the facts it does understand —
    # including a future-shaped `data` record with extra fields (ISSUE 8).
    fruns = analyze(os.path.join(fdir, "future_ledger.jsonl"))
    assert len(fruns) == 1, fruns
    f = fruns[0]
    assert f["header"]["ledger_version"] == 99, f["header"]
    assert f["completed"] and f["steps"] == 1 and f["bytes"] == 1024
    assert f["timeline"] is not None and f["timeline"]["groups"] == 1, \
        "the malformed future group record must be skipped, not fatal"
    assert f["data"] is not None and f["data_health"] is not None, \
        "the future data record must classify (extra fields ignored)"
    assert f["data_health"]["verdict"] == "skew-hot", f["data_health"]
    # The future-shaped `tune` record (unknown rule, non-knob changes, an
    # opaque trail) must pass through and render without error (ISSUE 10
    # forward compat).
    assert f["tune"] is not None and f["tune"]["rule"] == "warp-rebalance"
    # The future-shaped fault/degrade records (unknown fault class,
    # unknown ladder step) must classify without error (ISSUE 15 forward
    # compat): an injected fault + a degrade step read `degraded`.
    assert f["reliability"] is not None \
        and f["reliability"]["verdict"] == "degraded", f["reliability"]
    # The future-shaped geometry stamp (a spec dict where the label
    # string lives today) must surface and render without error.
    assert f["header"]["geometry"] == {"block_rows": 1024,
                                       "warp_slots": 7}, f["header"]
    render_run(f, io.StringIO())
    print("obs_report selftest ok "
          f"({a['step_records']} records, {len(a['spikes'])} spike, "
          "1 memory-growth flag, "
          f"{len(a['pipeline_flags']) + len(b['pipeline_flags'])} "
          f"pipeline flags, {len(c['map_flags'])} map flag, "
          f"timeline bottleneck={bn['resource']}, "
          f"data health={eh['verdict']}, tune rule={tn['rule']}, "
          f"geometry={f6['header']['geometry']}, "
          f"fleet={fview['fleet_bottleneck']['verdict']}, "
          f"reliability={rel['verdict']}, "
          "run-id selector ok, compare ok, future-ledger ok)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="summarize a mapreduce_tpu run ledger / flight dump")
    ap.add_argument("ledger", nargs="?", help="JSONL run-ledger path")
    ap.add_argument("--flight", default=None,
                    help="flight-recorder dump to render (default: any "
                         "<ledger>.flight.json that exists)")
    ap.add_argument("--json", action="store_true",
                    help="emit the machine-readable analysis instead")
    ap.add_argument("--run-id", default=None,
                    help="select one run from an append-mode ledger "
                         "(default: render every run; --compare defaults "
                         "to each side's last completed run)")
    ap.add_argument("--list-runs", action="store_true",
                    help="enumerate the ledger's run instances (run_id, "
                         "start time, family/backend/stamps, completed/"
                         "crashed/in-flight) — where --run-id ids come "
                         "from")
    ap.add_argument("--compare", nargs=2, metavar=("A", "B"), default=None,
                    help="diff two ledgers' phase shares, bound/bottleneck "
                         "verdicts and data-health dicts in one table "
                         "(each side uses its last completed run unless "
                         "--run-id selects one)")
    ap.add_argument("--selftest", action="store_true",
                    help="run against the checked-in fixtures and exit")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if args.list_runs:
        if not args.ledger:
            ap.error("--list-runs requires a ledger path")
        rows = list_runs(args.ledger)
        if args.json:
            print(json.dumps(rows))
        else:
            render_list(rows, sys.stdout)
        if not rows:
            print("no runs found", file=sys.stderr)
            return 1
        return 0
    if args.compare:
        return compare(args.compare[0], args.compare[1], sys.stdout,
                       as_json=args.json, run_id=args.run_id)
    if not args.ledger and not args.flight:
        ap.error("a ledger path (or --flight, --compare, or --selftest) "
                 "is required")
    runs = analyze(args.ledger) if args.ledger else []
    if args.run_id is not None and args.ledger:
        # Flight-only invocations (--flight without a ledger) skip the
        # filter: there are no runs to select from.
        runs = [a for a in runs if a.get("run_id") == args.run_id]
        if not runs:
            print(f"no run {args.run_id!r} in {args.ledger}",
                  file=sys.stderr)
            return 1
    # Fleet section (ISSUE 13): a multi-host ledger's shard files merge
    # into the cross-host view right under the per-run reports.
    fleet = fleet_view_for(args.ledger, args.run_id) if args.ledger else None
    flight = args.flight
    if flight is None and args.ledger \
            and os.path.exists(args.ledger + ".flight.json"):
        flight = args.ledger + ".flight.json"
    if args.json:
        print(json.dumps({"runs": runs, "flight": flight, "fleet": fleet}))
        return 0
    for a in runs:
        render_run(a, sys.stdout)
    if fleet:
        render_fleet(fleet, sys.stdout)
    if flight:
        render_flight(flight, sys.stdout)
    if not runs and not flight:
        print("no records found", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
