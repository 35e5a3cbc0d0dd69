#!/usr/bin/env python3
"""Two-process CPU fleet dryrun + merged fleet report (ISSUE 13).

The multichip dryrun proves the collective geometry compiles and runs;
this tool proves the POD-SCALE OBSERVABILITY stack end to end on the same
box, without a TPU: it spawns 2 worker processes that join one JAX
runtime over gloo CPU collectives, drives ``executor.run_job_global``
with telemetry at a shared ledger path (so every process writes its
``<ledger>.h<p>.jsonl`` shard and the coordinator the main file), then —
jax-free, in the parent — merges the shards via ``mapreduce_tpu/obs/
fleet.py``, writes the pid-per-host Perfetto trace next to the ledger,
and prints ONE JSON line with the ``fleet_bottleneck`` verdict, the
per-superstep skew total, and the artifact paths.

On a chip host the same run leaves a merged fleet trace + verdict next to
the multichip numbers.

Usage::

    python tools/fleet_report.py [--out /tmp/fleet] [--mb 1] [--chunk 4096]

(the ``--worker`` form is internal: the parent spawns itself twice).
"""

from __future__ import annotations

import argparse
import json
import os
import socket
import subprocess
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.dirname(os.path.abspath(__file__))

N_PROC = 2
DEV_PER_PROC = 2


def _worker(pid: int, n_proc: int, port: str, corpus: str, chunk: int,
            ledger: str) -> int:
    """One fleet process: gloo init, run_job_global with telemetry at the
    shared ledger path + a shared run_id (explicit shard pairing)."""
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={DEV_PER_PROC}")
    sys.path.insert(0, REPO)
    from mapreduce_tpu.runtime.platform import force_cpu

    jax = force_cpu(verify=False)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    from mapreduce_tpu.parallel import distributed as dist

    dist.initialize(coordinator_address=f"127.0.0.1:{port}",
                    num_processes=n_proc, process_id=pid, timeout_s=60)

    from mapreduce_tpu.config import Config
    from mapreduce_tpu.models.wordcount import WordCountJob
    from mapreduce_tpu.obs import Telemetry
    from mapreduce_tpu.runtime import executor

    # Placed-reduction knobs (ISSUE 20), env-carried so the internal
    # --worker argv stays stable: the merge strategy the global finish
    # builds, and window-boundary overlap (partial merges ride inside
    # the map stream; the ledger then carries op="partial" collective
    # records and the fleet verdict charges only the visible share).
    merge = os.environ.get("FLEET_MERGE_STRATEGY", "tree")
    overlap = os.environ.get("FLEET_MERGE_OVERLAP") == "1"
    cfg = Config(chunk_bytes=chunk, table_capacity=1 << 12,
                 merge_strategy=merge, merge_overlap=overlap)
    mesh = None
    if merge.startswith("hier-"):
        # The hier-* 2-D programs need the process-major two-level mesh
        # (outer axis rides the gloo "DCN", inner the per-process pair).
        from mapreduce_tpu.parallel.mesh import two_level_mesh

        mesh = two_level_mesh(n_proc, DEV_PER_PROC, devices=jax.devices())
    tel = Telemetry.create(ledger_path=ledger, run_id="fleetreport")
    try:
        rr = executor.run_job_global(WordCountJob(cfg), corpus, config=cfg,
                                     mesh=mesh, telemetry=tel)
    finally:
        tel.close()
    if dist.is_coordinator():
        print(json.dumps({"worker_total": int(rr.metrics.words_counted)}))
    return 0


def _make_corpus(path: str, mb: float) -> None:
    import random

    rng = random.Random(7)
    words = [f"w{i:04d}" for i in range(400)]
    target = int(mb * (1 << 20))
    with open(path, "w", encoding="utf-8") as f:
        n = 0
        while n < target:
            line = " ".join(rng.choice(words) for _ in range(12)) + "\n"
            f.write(line)
            n += len(line)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", nargs=6, default=None,
                    help=argparse.SUPPRESS)  # internal spawn form
    ap.add_argument("--out", default=None,
                    help="artifact prefix (default: a temp dir); the "
                         "ledger lands at <out>.ledger.jsonl")
    ap.add_argument("--corpus", default=None,
                    help="existing corpus file (default: generated)")
    ap.add_argument("--mb", type=float, default=1.0)
    ap.add_argument("--chunk", type=int, default=4096)
    ap.add_argument("--timeout-s", type=float, default=300.0)
    ap.add_argument("--merge-strategy", default="tree",
                    help="collective merge strategy for the global finish "
                         "(hier-* builds the 2-process x 2-device "
                         "two-level mesh)")
    ap.add_argument("--overlap", action="store_true",
                    help="window-boundary partial merges (ISSUE 20): "
                         "op='partial' collective records land in the "
                         "shards and the fleet verdict splits "
                         "visible/hidden collective time")
    args = ap.parse_args()
    if args.worker:
        w = args.worker
        return _worker(int(w[0]), int(w[1]), w[2], w[3], int(w[4]), w[5])

    out = args.out or os.path.join(tempfile.mkdtemp(prefix="fleetrep-"),
                                   "fleet")
    corpus = args.corpus
    if corpus is None:
        corpus = out + ".corpus.txt"
        _make_corpus(corpus, args.mb)
    ledger = out + ".ledger.jsonl"
    stale = [ledger, ledger + ".flight.json",
             *(f"{ledger}.h{i}.jsonl" for i in range(N_PROC)),
             *(f"{ledger}.h{i}.flight.json" for i in range(N_PROC))]
    for p in stale:
        # Append-mode ledgers: a stale run must not merge in — and a
        # prior crash's flight dumps must not read as THIS run's
        # forensics (obs_report auto-picks the adjacent .flight.json).
        try:
            os.remove(p)
        except OSError:
            pass

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = REPO
    env["FLEET_MERGE_STRATEGY"] = args.merge_strategy
    if args.overlap:
        env["FLEET_MERGE_OVERLAP"] = "1"
    else:
        env.pop("FLEET_MERGE_OVERLAP", None)
    procs = [subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--worker",
         str(p), str(N_PROC), str(port), corpus, str(args.chunk), ledger],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for p in range(N_PROC)]
    fail = None
    for p in procs:
        try:
            _, err = p.communicate(timeout=args.timeout_s)
        except subprocess.TimeoutExpired:
            p.kill()
            _, err = p.communicate()
            fail = fail or f"worker timed out after {args.timeout_s:.0f}s"
        if p.returncode != 0:
            fail = fail or f"worker rc={p.returncode}: {err[-2000:]}"
    if fail:
        print(json.dumps({"ok": False, "error": fail}))
        return 1

    # Merge + report, jax-free (the parent never imports jax): the same
    # by-path module loading the report tools use.
    sys.path.insert(0, HERE)
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    fl = obs_report._fleet_mod()
    by_host = {h: fl.read_jsonl(p)
               for h, p in fl.shard_paths(ledger).items()}
    selected = fl._select_aligned(by_host)
    view = fl.fleet_view(by_host, selected=selected)
    if view is None or len(view["hosts"]) != N_PROC:
        print(json.dumps({"ok": False,
                          "error": f"expected {N_PROC} shards, got "
                                   f"{sorted(by_host)} -> {view}"}))
        return 1
    trace_path = ledger + ".fleet.trace.json"
    with open(trace_path, "w", encoding="utf-8") as f:
        json.dump(fl.to_chrome_trace(by_host, selected=selected,
                                     view=view), f)
    merged_path = ledger + ".fleet.jsonl"
    with open(merged_path, "w", encoding="utf-8") as f:
        for r in fl.merged_records(by_host, selected=selected, view=view):
            f.write(json.dumps(r, sort_keys=True) + "\n")
    print(json.dumps({
        "ok": True,
        "hosts": view["hosts"],
        "aligned": view["aligned"],
        "span_s": view["span_s"],
        "merge_strategy": args.merge_strategy,
        "merge_overlap": bool(args.overlap),
        "fleet_bottleneck": view["fleet_bottleneck"],
        "collective": view["collective"],
        "straggler_skew_s": view["straggler"]["total_skew_s"],
        "imbalance": view["imbalance"]["verdict"],
        "ledger": ledger,
        "merged": merged_path,
        "trace": trace_path,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
