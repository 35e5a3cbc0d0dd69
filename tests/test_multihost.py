"""Host-side multi-host partitioning logic (pure functions; the collective
side of multi-host is covered by the emulated-mesh tests in
test_distributed.py and the driver's dryrun_multichip)."""

from __future__ import annotations

import numpy as np
import pytest

from mapreduce_tpu.parallel import distributed as dist
from mapreduce_tpu.utils import oracle


def test_host_byte_ranges_partition_exactly():
    size = 1_000_003
    ranges = [dist.host_byte_range(size, p, 8) for p in range(8)]
    assert ranges[0][0] == 0 and ranges[-1][1] == size
    for (a_lo, a_hi), (b_lo, b_hi) in zip(ranges, ranges[1:]):
        assert a_hi == b_lo and a_lo < a_hi


def test_host_byte_range_validates_index():
    with pytest.raises(ValueError):
        dist.host_byte_range(100, 4, 4)


def test_aligned_ranges_count_every_token_once(tmp_path, rng):
    """Crucial seam property: snapping both ends with the same rule keeps
    ranges exactly adjacent, and summing per-range counts == global count."""
    from tests.conftest import make_corpus

    corpus = make_corpus(rng, n_words=3000, vocab=100)
    path = tmp_path / "c.txt"
    path.write_bytes(corpus)
    n_hosts = 4
    totals: dict[bytes, int] = {}
    prev_hi = 0
    for p in range(n_hosts):
        lo, hi = dist.host_byte_range(len(corpus), p, n_hosts)
        lo, hi = dist.align_range_to_separator(str(path), lo, hi)
        assert lo == prev_hi  # ranges stay a partition after snapping
        prev_hi = hi
        for w, c in oracle.word_counts(corpus[lo:hi]).items():
            totals[w] = totals.get(w, 0) + c
    assert prev_hi == len(corpus)
    assert totals == oracle.word_counts(corpus)


def test_align_handles_separator_free_file(tmp_path):
    blob = b"x" * 4096  # one giant token, no separators at all
    path = tmp_path / "b.txt"
    path.write_bytes(blob)
    lo, hi = dist.align_range_to_separator(str(path), 1024, 3072,
                                           max_token_bytes=256)
    assert (lo, hi) == (1024, 3072)  # falls back to force-split offsets


def test_host_shards_are_process_major():
    assert list(dist.host_shards(16, 1, 4)) == [4, 5, 6, 7]
    with pytest.raises(ValueError):
        dist.host_shards(10, 0, 4)


def test_initialize_is_noop_on_single_host(monkeypatch):
    for var in ("COORDINATOR_ADDRESS", "JAX_COORDINATOR_ADDRESS",
                "MEGASCALE_COORDINATOR_ADDRESS", "TPU_WORKER_HOSTNAMES"):
        monkeypatch.delenv(var, raising=False)
    dist.initialize()  # must not raise or hang
    assert dist.is_coordinator()


@pytest.mark.slow
def test_per_host_byte_range_runs_merge_to_global_counts(tmp_path, rng):
    """The full documented multi-host flow, emulated in-process: each 'host'
    streams only its aligned [lo, hi) range (run_job byte_range), and the
    merged per-host tables equal a single global run."""
    from tests.conftest import make_corpus
    from mapreduce_tpu.config import Config
    from mapreduce_tpu.models.wordcount import WordCountJob
    from mapreduce_tpu.ops import table as table_ops
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime import executor

    corpus = make_corpus(rng, n_words=3000, vocab=120)
    path = tmp_path / "c.txt"
    path.write_bytes(corpus)
    cfg = Config(chunk_bytes=512, table_capacity=1024)
    mesh = data_mesh(2)
    job = WordCountJob(cfg)

    n_hosts = 3
    partials = []
    for p in range(n_hosts):
        lo, hi = dist.host_byte_range(len(corpus), p, n_hosts)
        lo, hi = dist.align_range_to_separator(str(path), lo, hi)
        rr = executor.run_job(job, str(path), config=cfg, mesh=mesh,
                              byte_range=(lo, hi))
        partials.append(rr.value)

    merged = partials[0]
    for t in partials[1:]:
        merged = table_ops.merge(merged, t, capacity=cfg.table_capacity)

    got = {(int(h), int(l)): int(c) for h, l, c in
           zip(np.asarray(merged.key_hi), np.asarray(merged.key_lo),
               np.asarray(merged.count)) if c > 0}
    expected = oracle.word_counts(corpus)
    assert sorted(got.values()) == sorted(expected.values())
    assert int(np.asarray(merged.total_count())) == oracle.total_count(corpus)


@pytest.mark.slow
def test_true_multiprocess_spmd_run(tmp_path):
    """round-1 review #7: REAL multi-process multi-host — 2 worker processes
    join one JAX runtime via jax.distributed.initialize (gloo CPU
    collectives), build a 4-device global mesh, stage only their own shard
    rows via device_put_local, and drive the Engine's sharded step +
    collective finish.  The coordinator's replicated result must equal a
    single-process oracle count."""
    import json
    import os
    import socket
    import subprocess
    import sys
    from pathlib import Path

    corpus = (b"Hello World EveryOne\nWorld Good News\n"
              b"Good Morning Hello\n" * 40)
    path = tmp_path / "mh.txt"
    path.write_bytes(corpus)

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(repo)
    worker = str(repo / "tests" / "multihost_worker.py")
    procs = [subprocess.Popen(
        [sys.executable, worker, str(p), "2", str(port), str(path), "256", "2"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for p in range(2)]
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=300))
    finally:
        for p in procs:
            p.kill()
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{err[-2000:]}"

    # Coordinator prints the one JSON line (gloo chatter precedes it).
    json_lines = [ln for out, _ in outs for ln in out.splitlines()
                  if ln.startswith("{")]
    assert len(json_lines) == 1, json_lines
    got = json.loads(json_lines[0])
    expected = oracle.word_counts(corpus)
    assert got["total"] == oracle.total_count(corpus)
    assert got["distinct"] == len(expected)
    assert got["counts"] == sorted(expected.values())
    assert got["processes"] == 2 and got["devices"] == 4


@pytest.mark.slow
def test_run_job_global_multiprocess_with_crash_resume(tmp_path):
    """round-3 review #5 'done' case: the executor-level global-SPMD driver
    (run_job_global) runs REAL 2-process SPMD over gloo — global mesh,
    host_shards staging, coordinator-only checkpoints — survives a
    synchronized injected crash, and a relaunch RESUMES from the
    checkpoint to the exact oracle counts."""
    import json
    import os

    corpus = (b"Hello World EveryOne\nWorld Good News\n"
              b"Good Morning Hello\n" * 40)
    path = tmp_path / "gmh.txt"
    path.write_bytes(corpus)
    ckpt = str(tmp_path / "g.ck.npz")

    # Round 1: both processes crash (synchronously) before step 2; the
    # coordinator has checkpointed steps 1 and 2 by then.
    procs, outs = _launch_global_workers(path, ckpt, crash_at=2)
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 17, f"injection missing:\nrc={p.returncode}\n{err[-2000:]}"
    assert os.path.exists(ckpt), "no checkpoint written before the crash"

    # Round 2: fresh processes resume from the checkpoint and finish.
    procs, outs = _launch_global_workers(path, ckpt, crash_at=-1)
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"resume failed:\n{err[-2000:]}"
    json_lines = [ln for out, _ in outs for ln in out.splitlines()
                  if ln.startswith("{")]
    assert len(json_lines) == 1, json_lines
    got = json.loads(json_lines[0])
    expected = oracle.word_counts(corpus)
    assert got["total"] == oracle.total_count(corpus)
    assert got["distinct"] == len(expected)
    assert got["counts"] == sorted(expected.values())
    assert got["processes"] == 2 and got["devices"] == 4


@pytest.mark.slow
def test_run_job_global_host_kill_fault_resumes(tmp_path):
    """ISSUE 15 chaos matrix: the process-kill seam on the REAL
    2-process gloo harness.  A fault plan hard-kills every process
    (``os._exit(113)``) at the same deterministic crossing — a
    synchronized platform reclaim, fired through the executor's own
    injection seam rather than a monkeypatched step — after the
    coordinator has checkpointed; each process's ledger shard records
    the `fault` before dying; a plan-free relaunch resumes from the
    checkpoint to the exact oracle counts."""
    import json
    import os

    corpus = (b"Hello World EveryOne\nWorld Good News\n"
              b"Good Morning Hello\n" * 40)
    path = tmp_path / "gk.txt"
    path.write_bytes(corpus)
    ckpt = str(tmp_path / "gk.ck.npz")
    ledger = str(tmp_path / "gk.jsonl")

    # Round 1: the plan kills both processes at process-kill crossing 2
    # (the third dispatched group) — checkpoint_every=1 guarantees a
    # snapshot exists by then.
    procs, outs = _launch_global_workers(
        path, ckpt, crash_at=-1, ledger=ledger,
        fault_plan="at=process-kill:2:permanent")
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 113, \
            f"hard-kill missing:\nrc={p.returncode}\n{err[-2000:]}"
    assert os.path.exists(ckpt), "no checkpoint written before the kill"
    # Every process's shard recorded the typed fault before os._exit —
    # the flushed-ledger contract is what makes a kill diagnosable.
    from mapreduce_tpu import obs

    for proc_index in (0, 1):
        shard = f"{ledger}.h{proc_index}.jsonl"
        assert os.path.exists(shard), shard
        faults_recs = [r for r in obs.read_ledger(shard)
                       if r.get("kind") == "fault"]
        assert any(f.get("seam") == "process-kill" and f.get("injected")
                   for f in faults_recs), (proc_index, faults_recs)

    # Round 2: plan-free relaunch resumes and finishes exactly.
    procs, outs = _launch_global_workers(path, ckpt, crash_at=-1)
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"resume failed:\n{err[-2000:]}"
    json_lines = [ln for out, _ in outs for ln in out.splitlines()
                  if ln.startswith("{")]
    assert len(json_lines) == 1, json_lines
    got = json.loads(json_lines[0])
    expected = oracle.word_counts(corpus)
    assert got["total"] == oracle.total_count(corpus)
    assert got["distinct"] == len(expected)
    assert got["counts"] == sorted(expected.values())


@pytest.mark.slow
def test_run_job_global_host_kill_after_partial_merge_resumes(tmp_path):
    """ISSUE 20 chaos: hard-kill every process AFTER window-boundary
    partial merges have drained local tables into the replicated
    accumulator.  The shards must show op='partial' collective records
    preceding the process-kill fault; the plan-free relaunch (overlap
    still on) resumes from the coordinator's {state, accumulator}
    snapshot to the exact oracle counts — the partial-merge/checkpoint
    interaction the fast tier cannot cover with real collectives."""
    import json
    import os

    corpus = (b"Hello World EveryOne\nWorld Good News\n"
              b"Good Morning Hello\n" * 40)
    path = tmp_path / "gp.txt"
    path.write_bytes(corpus)
    ckpt = str(tmp_path / "gp.ck.npz")
    ledger = str(tmp_path / "gp.jsonl")

    # inflight_groups=1 (the worker's overlap mode) + checkpoint_every=1
    # fire a partial at every checkpoint boundary, so by process-kill
    # crossing 2 (the last dispatched group on this corpus) two partials
    # have merged and the latest snapshot holds the accumulator.
    procs, outs = _launch_global_workers(
        path, ckpt, crash_at=-1, ledger=ledger,
        fault_plan="at=process-kill:2:permanent", merge_overlap=True)
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 113, \
            f"hard-kill missing:\nrc={p.returncode}\n{err[-2000:]}"
    assert os.path.exists(ckpt), "no checkpoint written before the kill"
    from mapreduce_tpu import obs

    for proc_index in (0, 1):
        shard = f"{ledger}.h{proc_index}.jsonl"
        recs = list(obs.read_ledger(shard))
        partial_ts = [r["ts"] for r in recs if r.get("kind") == "collective"
                      and r.get("op") == "partial"]
        kill_ts = [r["ts"] for r in recs if r.get("kind") == "fault"
                   and r.get("seam") == "process-kill"]
        assert partial_ts and kill_ts, (proc_index, recs)
        assert min(partial_ts) < min(kill_ts), \
            "the kill must land AFTER a partial merge retired"

    # Plan-free relaunch, overlap still on: resume merges the snapshot's
    # accumulator + residual to the exact oracle counts.
    procs, outs = _launch_global_workers(path, ckpt, crash_at=-1,
                                         merge_overlap=True)
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"resume failed:\n{err[-2000:]}"
    json_lines = [ln for out, _ in outs for ln in out.splitlines()
                  if ln.startswith("{")]
    assert len(json_lines) == 1, json_lines
    got = json.loads(json_lines[0])
    expected = oracle.word_counts(corpus)
    assert got["total"] == oracle.total_count(corpus)
    assert got["distinct"] == len(expected)
    assert got["counts"] == sorted(expected.values())


def _launch_global_workers(path, ckpt, crash_at, ledger=None,
                           chunk_bytes=256, fault_plan=None,
                           merge_overlap=False):
    """Spawn the 2-process run_job_global gloo harness (global_worker.py);
    ``ledger`` attaches telemetry at a shared path (ISSUE 13);
    ``fault_plan`` arms the executor's injection seams (ISSUE 15 — the
    process-kill seam is the host-kill chaos scenario);
    ``merge_overlap`` turns on window-boundary partial merges at
    inflight_groups=1 (ISSUE 20)."""
    import os
    import socket
    import subprocess
    import sys
    from pathlib import Path

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    repo = Path(__file__).resolve().parent.parent
    env = dict(os.environ)
    env["PYTHONPATH"] = str(repo)
    if merge_overlap:
        env["GW_MERGE_OVERLAP"] = "1"
    else:
        env.pop("GW_MERGE_OVERLAP", None)
    worker = str(repo / "tests" / "global_worker.py")
    argv = [sys.executable, worker, "PID", "2", str(port), str(path),
            str(chunk_bytes), "2", str(ckpt), str(crash_at)]
    if ledger is not None or fault_plan is not None:
        argv.append(ledger or "")
    if fault_plan is not None:
        argv.append(fault_plan)
    procs = [subprocess.Popen(argv[:2] + [str(p)] + argv[3:],
                              env=env, stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for p in range(2)]
    outs = []
    try:
        for p in procs:
            outs.append(p.communicate(timeout=300))
    finally:
        for p in procs:
            p.kill()
    return procs, outs


@pytest.mark.slow
def test_run_job_global_multiprocess_writes_host_shards(tmp_path):
    """ISSUE 13 tentpole, falsified on the real 2-process gloo harness: a
    telemetered run_job_global leaves one host-stamped shard ledger per
    process (one group record per retired group, the run-epoch clock on
    run_start, a collective record, per-host run_end phases) next to the
    coordinator's main file; obs/fleet.py merges the shards into a 2-host
    view with a fleet_bottleneck verdict, byte-stable across merges."""
    import json
    import os

    from mapreduce_tpu import obs
    from mapreduce_tpu.obs import fleet

    corpus = (b"Hello World EveryOne\nWorld Good News\n"
              b"Good Morning Hello\n" * 40)
    path = tmp_path / "fl.txt"
    path.write_bytes(corpus)
    ledger = str(tmp_path / "fl.jsonl")

    procs, outs = _launch_global_workers(path, tmp_path / "fl.ck.npz",
                                         crash_at=-1, ledger=ledger)
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{err[-2000:]}"

    # The coordinator's main file: gated records, all host-0 stamped.
    main = list(obs.read_ledger(ledger))
    kinds = [r["kind"] for r in main]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    assert "collective" in kinds
    assert all(r.get("host") == 0 for r in main), \
        "only the coordinator writes the main file"
    n_groups_main = kinds.count("group")
    assert n_groups_main > 0

    # One shard per process, every record host-stamped, exactly one group
    # record per retired group (== the coordinator's count: SPMD lockstep),
    # topology + clock on run_start, per-host run_end.
    for h in (0, 1):
        sp = obs.shard_path(ledger, h)
        assert os.path.exists(sp), f"missing shard {sp}"
        recs = list(obs.read_ledger(sp))
        assert all(r.get("host") == h for r in recs)
        start = next(r for r in recs if r["kind"] == "run_start")
        assert start["ledger_version"] == obs.LEDGER_VERSION == 10
        assert start["processes"] == 2 and start["local_devices"] == 2
        assert set(start["clock"]) == {"wall", "mono"}
        groups = [r for r in recs if r["kind"] == "group"]
        assert len(groups) == n_groups_main
        assert all(g.get("host_bytes") is not None for g in groups), \
            "global-driver groups carry this host's staged bytes"
        assert all(g["host_bytes"] <= g["group_bytes"] for g in groups)
        assert [r["kind"] for r in recs].count("run_end") == 1
        assert any(r["kind"] == "collective" for r in recs)

    # Fleet merge: 2 hosts, aligned clocks, a verdict, stable bytes.
    by_host = {h: fleet.read_jsonl(p)
               for h, p in fleet.shard_paths(ledger).items()}
    view = fleet.fleet_view(by_host)
    assert view["hosts"] == [0, 1] and view["aligned"] is True
    assert view["processes"] == 2
    assert view["fleet_bottleneck"]["verdict"] in (
        "straggler-bound", "collective-bound", "balanced")
    assert view["per_host"]["0"]["groups"] == n_groups_main
    # Both hosts staged half the shard rows of the same global batches.
    assert view["per_host"]["0"]["group_bytes"] \
        == view["per_host"]["1"]["group_bytes"]
    twice = [json.dumps(fleet.fleet_view(by_host), sort_keys=True)
             for _ in range(2)]
    assert twice[0] == twice[1]


@pytest.mark.slow
def test_noncoordinator_failure_leaves_host_flight_dump(tmp_path):
    """ISSUE 13 satellite bugfix: pre-v7 the coordinator-only write_gate
    swallowed every non-coordinator flight dump.  An injected failure now
    leaves a dump from EACH host at its own path — the non-coordinator's
    at the host-suffixed one — plus a failure record in its shard."""
    import json
    import os

    from mapreduce_tpu import obs

    corpus = (b"Hello World EveryOne\nWorld Good News\n"
              b"Good Morning Hello\n" * 40)
    path = tmp_path / "flc.txt"
    path.write_bytes(corpus)
    ledger = str(tmp_path / "flc.jsonl")

    procs, outs = _launch_global_workers(path, tmp_path / "flc.ck.npz",
                                         crash_at=2, ledger=ledger)
    for p, (out, err) in zip(procs, outs):
        assert p.returncode == 17, \
            f"injection missing:\nrc={p.returncode}\n{err[-2000:]}"

    # Coordinator keeps the classic path; host 1 dumps to its own file.
    assert os.path.exists(ledger + ".flight.json")
    h1_dump = obs.shard_flight_path(ledger, 1)
    assert os.path.exists(h1_dump), \
        "non-coordinator failure must dump on that host"
    with open(h1_dump) as f:
        dump = json.load(f)
    assert "injected crash" in dump["context"]["error"]
    assert dump["events"], "the ring must carry the host's events"
    # The failure record lands in host 1's shard (the main file's copy
    # stays coordinator-gated).
    h1 = list(obs.read_ledger(obs.shard_path(ledger, 1)))
    fails = [r for r in h1 if r["kind"] == "failure"]
    assert len(fails) == 1 and fails[0]["host"] == 1
    assert fails[0]["flight_dump"] == h1_dump
    main_fails = [r for r in obs.read_ledger(ledger)
                  if r["kind"] == "failure"]
    assert all(r.get("host") == 0 for r in main_fails)


@pytest.mark.slow
def test_run_job_global_window_matches_serial(tmp_path, rng):
    """ISSUE 5 on the global-SPMD driver: run_job_global (single process —
    initialize() is a no-op, the global mesh is all local devices) with
    the dispatch window active produces the identical result as the
    serialized control, with pooled shard-row staging and the window
    statistics in the run result."""
    from tests.conftest import make_corpus
    from mapreduce_tpu.config import Config
    from mapreduce_tpu.models.wordcount import WordCountJob
    from mapreduce_tpu.runtime import executor

    corpus = make_corpus(rng, n_words=2500, vocab=100)
    path = tmp_path / "g.txt"
    path.write_bytes(corpus)
    dist.initialize()
    totals = {}
    for inflight in (1, 3):
        cfg = Config(chunk_bytes=256, table_capacity=1024,
                     inflight_groups=inflight)
        rr = executor.run_job_global(WordCountJob(cfg), str(path), config=cfg)
        assert rr.metrics.bytes_processed == len(corpus)
        assert rr.pipeline["inflight_groups"] == inflight
        counts = sorted(int(c) for c in np.asarray(rr.value.count) if c > 0)
        totals[inflight] = (rr.metrics.words_counted, counts)
    assert totals[1] == totals[3]
    assert totals[1][0] == oracle.total_count(corpus)
    assert totals[1][1] == sorted(oracle.word_counts(corpus).values())
