#!/usr/bin/env python3
"""Per-op profile of one word-count chunk step: where the chunk budget goes.

The round-2 verdict's sort-floor criterion is stated in op shares ("sort
share < 50% of the op profile"), and the round-1 numbers that shaped the
design (round-5 "where the remaining time goes") were captured by
hand.  This tool automates that capture: it runs one map+combine step over
a device-resident chunk under ``jax.profiler``, parses the XSpace with
``jax.profiler.ProfileData``, and prints the top device ops with their
share of total device time — one line per op family (sort, fusion,
gather/scatter, pallas kernel, ...).

Run on the chip:  python tools/opshare.py          (ambient backend)
CPU sanity:       JAX_PLATFORMS=cpu python tools/opshare.py

Env knobs: OPSHARE_CHUNK_MB (default 32), OPSHARE_SORT_MODE (sort3|segmin),
OPSHARE_SORT_IMPL (xla|radix|radix_partition — the round-6 radix A/B),
OPSHARE_MERGE_EVERY (default 1), OPSHARE_STEPS (steps profiled, default 4).
Prints a final JSON line {"sort_share": ..., "top": [...]} for machines.
"""

from __future__ import annotations

import json
import os
import re
import sys
import tempfile
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
    from mapreduce_tpu.runtime.platform import force_cpu

    force_cpu()

import jax
import numpy as np


def classify(name: str) -> str:
    """Map an XLA op/event name to a coarse family."""
    n = name.lower()
    if "sort" in n:
        return "sort"
    if "custom-call" in n and ("mosaic" in n or "tpu" in n) or "pallas" in n:
        return "pallas-kernel"
    if "all-gather" in n or "all-reduce" in n or "collective" in n \
            or "permute" in n:
        return "collective"
    if "scatter" in n:
        return "scatter"
    if "gather" in n:
        return "gather"
    if "fusion" in n or "loop_" in n.replace("-", "_"):
        return "fusion/elementwise"
    if "copy" in n or "transpose" in n or "reshape" in n or "bitcast" in n:
        return "copy/layout"
    if "convert" in n or "broadcast" in n or "iota" in n:
        return "fusion/elementwise"
    return "other"


def main() -> int:
    chunk_mb = int(os.environ.get("OPSHARE_CHUNK_MB", "32"))
    steps = int(os.environ.get("OPSHARE_STEPS", "4"))

    from mapreduce_tpu.config import Config
    from mapreduce_tpu.models.wordcount import WordCountJob
    from mapreduce_tpu.parallel.mapreduce import Engine
    from mapreduce_tpu.parallel.mesh import data_mesh

    sort_mode = os.environ.get("OPSHARE_SORT_MODE", Config.sort_mode)
    if sort_mode == "segmin" and jax.default_backend() == "tpu" \
            and os.environ.get("OPSHARE_FORCE", "0") != "1":
        # Measured 2026-07-31: the 16.8M-row segmented associative_scan
        # hung the chip for >30 min (twice in sortbench, once in a full
        # bench) — refusing beats burning the chip budget.
        # OPSHARE_FORCE=1 overrides.
        print(json.dumps({"skipped": "segmin on tpu: giant associative_scan "
                                     "hangs the chip (round 4)"}))
        return 0
    cfg = Config(chunk_bytes=chunk_mb << 20, table_capacity=1 << 18,
                 batch_unique_capacity=1 << 16,
                 sort_mode=sort_mode,
                 sort_impl=os.environ.get("OPSHARE_SORT_IMPL",
                                          Config.sort_impl),
                 merge_every=int(os.environ.get("OPSHARE_MERGE_EVERY", "1")),
                 compact_slots=(int(os.environ["OPSHARE_COMPACT_SLOTS"])
                                if "OPSHARE_COMPACT_SLOTS" in os.environ
                                else None))
    print(f"backend={jax.default_backend()} chunk={chunk_mb}MB "
          f"sort_mode={cfg.sort_mode} sort_impl={cfg.sort_impl} "
          f"merge_every={cfg.merge_every} steps={steps}", file=sys.stderr)

    rng = np.random.default_rng(3)
    data = rng.integers(97, 123, size=(1, cfg.chunk_bytes), dtype=np.uint8)
    data[rng.random(data.shape) < 0.16] = 0x20
    engine = Engine(WordCountJob(cfg), data_mesh(1))
    state = engine.init_states()
    staged = jax.device_put(data, engine.sharding)

    # Warm up (pay compiles) outside the trace.
    state = engine.step(state, staged, 0)
    np.asarray(jax.tree.leaves(state)[0].ravel()[:1])

    tmp = tempfile.mkdtemp(prefix="opshare_")
    with jax.profiler.trace(tmp):
        for s in range(1, steps + 1):
            state = engine.step(state, staged, s)
        np.asarray(jax.tree.leaves(state)[0].ravel()[:1])

    # Find the captured XSpace and aggregate device-plane event durations.
    xspaces = []
    for root, _dirs, files in os.walk(tmp):
        xspaces += [os.path.join(root, f) for f in files
                    if f.endswith(".xplane.pb")]
    if not xspaces:
        print(json.dumps({"error": f"no xplane.pb under {tmp}"}))
        return 1
    # Wrapper spans NEST leaf ops (a cond's duration includes the sort
    # inside its taken branch; jit_<step> spans the whole program), and
    # async copy-start durations OVERLAP compute until their copy-done —
    # summing any of them double-counts.  Round-4 calibration: with them
    # included this tool reported 333 ms/chunk where the end-to-end bench
    # measured 92 ms/chunk on the same config.  Leaf, non-async events
    # only; the program span is kept separately as the honest wall anchor.
    _wrapper = re.compile(r"^%?(jit_|cond|while|call|conditional|copy-start)")
    fam_us: dict[str, float] = defaultdict(float)
    op_us: dict[str, float] = defaultdict(float)
    program_us = 0.0
    program_lines = 0  # device lines that carried jit_ spans (one per device)
    for xs in xspaces:
        pd = jax.profiler.ProfileData.from_serialized_xspace(
            open(xs, "rb").read())
        for plane in pd.planes:
            pname = plane.name.lower()
            device_plane = ("tpu" in pname or "gpu" in pname
                            or re.search(r"/device:", pname))
            for line in plane.lines:
                # TPU/GPU: every line of the device plane is op events.
                # CPU: ops live in the host plane's tf_XLA* executor lines
                # (the python line would double-count wall time).
                if not (device_plane or line.name.startswith("tf_XLA")):
                    continue
                line_program_us = 0.0
                for ev in line.events:
                    if "::" in ev.name:  # runtime infra spans nest over ops
                        continue
                    dur = ev.duration_ns / 1e3
                    if ev.name.startswith("jit_"):
                        line_program_us += dur
                    if _wrapper.match(ev.name):
                        continue
                    fam_us[classify(ev.name)] += dur
                    op_us[ev.name] += dur
                # Each device line replays the same program on a mesh run;
                # summing across lines would report D devices' spans as one
                # chunk's cost (ADVICE r4).  Average over the lines that
                # carried program spans instead (on the single-device bench
                # chip this is a no-op: one line, same number).
                if line_program_us:
                    program_us += line_program_us
                    program_lines += 1
    total = sum(fam_us.values())
    if total <= 0:
        print(json.dumps({"error": "no device events captured",
                          "planes": [p.name for xs in xspaces
                                     for p in jax.profiler.ProfileData
                                     .from_serialized_xspace(
                                         open(xs, "rb").read()).planes]}))
        return 1
    print(f"{'family':24s} {'us':>12s}  share", file=sys.stderr)
    for fam, us in sorted(fam_us.items(), key=lambda kv: -kv[1]):
        print(f"{fam:24s} {us:12.0f}  {us / total:6.1%}", file=sys.stderr)
    top = sorted(op_us.items(), key=lambda kv: -kv[1])[:12]
    for name, us in top:
        print(f"  {name[:70]:70s} {us:10.0f} us", file=sys.stderr)
    print(json.dumps({
        "backend": jax.default_backend(),
        "chunk_mb": chunk_mb, "steps": steps,
        "sort_mode": cfg.sort_mode, "sort_impl": cfg.sort_impl,
        "merge_every": cfg.merge_every,
        "compact_slots": cfg.compact_slots,
        "total_device_us": round(total, 0),
        # Per-chunk numbers are averaged over the device lines that carried
        # program spans: on a D-device mesh every line replays the same
        # program, so raw sums would report D devices' work as one chunk's
        # cost (ADVICE r4) — and the leaf total must be scaled the same way
        # as the program span or their calibration gap becomes a Dx phantom.
        # Single-device runs (the bench chip): one line, numbers unchanged.
        "us_per_chunk": round(total / steps / max(program_lines, 1), 0),
        "program_us_per_chunk": round(program_us / program_lines / steps, 0)
        if program_us else None,
        "program_device_lines": program_lines or None,
        "sort_share": round(fam_us.get("sort", 0.0) / total, 4),
        "shares": {k: round(v / total, 4) for k, v in fam_us.items()},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
