#!/usr/bin/env python3
"""On-chip Mosaic kernel parity smoke: one tiny chunk, real kernel vs oracle.

Interpret mode validates kernel *semantics* only — the VMEM-stack limit, i1
vector-register shifts, 8-bit compares, and unsigned reductions all passed
interpret and failed only on the chip (round 4: "interpret validates semantics, not
the target").  This smoke costs ~seconds of a live
window and catches the next Mosaic lowering surprise BEFORE a bench run
spends the window: it compiles and runs the production kernel configs on the
real device over a 1 MB corpus slice and bit-compares the resulting tables
against the XLA-scan oracle.

Prints ONE JSON line: {"kernel_parity_ok": bool, "modes": {...}, ...}.
Exit 0 when every mode agrees, 1 otherwise, 3 when JAX finds no TPU.
"""

from __future__ import annotations

import json
import os
import sys
import time


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="on-chip Mosaic kernel parity smoke (production "
                    "configs; --geometry sweeps shortlist candidates)")
    ap.add_argument("--geometry", type=int, default=0, metavar="K",
                    help="ISSUE 12 sweep mode: smoke the top-K certified "
                         "geometry-search candidates' Mosaic surfaces "
                         "(stable2 + fused paths at each candidate's "
                         "windows) BEFORE any probe pass spends device "
                         "time — the PR-11 kernel-smoke discipline, "
                         "generalized (0 = the production configs only)")
    args = ap.parse_args(argv)
    import jax

    if jax.default_backend() != "tpu":
        print(json.dumps({"kernel_parity_ok": None,
                          "error": f"no TPU (backend {jax.default_backend()})"}))
        return 3

    from bench import make_zipf_corpus
    from mapreduce_tpu.config import Config
    from mapreduce_tpu.models import wordcount

    t0 = time.perf_counter()
    data = make_zipf_corpus(1 << 20)
    # A few overlong runs so the poison/rescue path is exercised on-chip.
    data = data[: 1 << 19] + b" " + b"u" * 40 + b" " + data[1 << 19:]

    # The XLA-scan oracle runs on CPU (it compiles pathologically slowly on
    # TPU at MB sizes — the reason the pallas path exists).
    cpus = jax.devices("cpu")
    with jax.default_device(cpus[0]):
        oracle_r = wordcount.count_words(
            data, Config(backend="xla", chunk_bytes=1 << 20,
                         table_capacity=1 << 16))

    modes = {}
    ok = True
    configs = {
        "sort3_compact88": Config(backend="pallas", chunk_bytes=1 << 20,
                                  table_capacity=1 << 16, sort_mode="sort3"),
        "stable2_lane_major": Config(backend="pallas", chunk_bytes=1 << 20,
                                     table_capacity=1 << 16,
                                     sort_mode="stable2"),
        # ISSUE 11 map-side combiner: the hot-key cache's Mosaic surface
        # — four revisited (8, 128) output refs, axis-0 sublane
        # reductions, masked one-hot selects — has never lowered on a
        # real chip; smoke it before the bench-zipf-combiner rows spend
        # a window on it.  'salt' exercises the de-salting re-reduce.
        "fused_combiner": Config(backend="pallas", chunk_bytes=1 << 20,
                                 table_capacity=1 << 16, map_impl="fused",
                                 combiner="hot-cache"),
        "fused_salt": Config(backend="pallas", chunk_bytes=1 << 20,
                             table_capacity=1 << 16, map_impl="fused",
                             combiner="salt"),
    }
    if args.geometry:
        # ISSUE 12 sweep: every shortlisted candidate's Mosaic surface —
        # new window heights move BlockSpec shapes and grid sizes, the
        # exact class of lowering surprise interpret mode cannot see —
        # smoked on the stable2 AND fused paths before tools/geomsearch.py
        # --probe spends a measurement window on any of them.
        from mapreduce_tpu.analysis import geometry as geom_mod

        short = geom_mod.shortlist(geom_mod.enumerate_candidates(),
                                   args.geometry)
        for c in short:
            if c.axis == "default":
                continue  # the production configs above already cover it
            configs[f"geom_{c.label}"] = Config(
                backend="pallas", chunk_bytes=1 << 20,
                table_capacity=1 << 16, geometry=c.geometry)
            configs[f"geom_{c.label}_fused"] = Config(
                backend="pallas", chunk_bytes=1 << 20,
                table_capacity=1 << 16, map_impl="fused",
                geometry=c.geometry)
    for name, cfg in configs.items():
        try:
            r = wordcount.count_words(data, cfg)
            same = (r.words == oracle_r.words and r.counts == oracle_r.counts
                    and r.total == oracle_r.total)
            modes[name] = "ok" if same else "MISMATCH"
            ok = ok and same
        except Exception as e:  # compile/runtime lowering failure
            modes[name] = f"ERROR: {type(e).__name__}: {e}"[:300]
            ok = False
    print(json.dumps({
        "kernel_parity_ok": ok,
        "modes": modes,
        "backend": jax.default_backend(),
        "elapsed_s": round(time.perf_counter() - t0, 1),
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
