"""Worker process for the run_job_global multi-process test.

Drives the EXECUTOR's global-SPMD entry point (``executor.run_job_global``,
round-3 review #5) end-to-end: ``jax.distributed.initialize`` over gloo, a
global mesh spanning both processes, per-process ``host_shards`` staging,
coordinator-only checkpointing — and, when ``crash_at_step >= 0``, a
deterministic injected failure on EVERY process at that step (both raise
together, so no peer is left blocked in a collective), exercising the
checkpoint/resume recovery path a second launch completes.

Usage: python global_worker.py <process_id> <n_processes> <port> \
    <corpus_path> <chunk_bytes> <devices_per_process> <ckpt_path> \
    <crash_at_step> [ledger_path] [fault_plan]

``ledger_path`` (optional, ISSUE 13): attach full telemetry at that
shared path — every process then writes its own ``<ledger>.h<p>.jsonl``
shard (with a shared run_id, so fleet merges pair runs explicitly), the
coordinator the main file, and a crash dumps each host's flight recorder
to its host-suffixed path.

``fault_plan`` (optional, ISSUE 15): a ``Config.fault_plan`` spec fired
through the executor's real injection seams — ``at=process-kill:N:...``
is the multi-host hard-kill chaos scenario (``os._exit(113)`` between
dispatched groups on every process at the same deterministic crossing,
exactly like a synchronized platform reclaim; the relaunch resumes from
the coordinator's checkpoint).

``GW_MERGE_OVERLAP=1`` (env, ISSUE 20): window-boundary partial merges
with ``inflight_groups=1`` (a partial every retired group, so even the
tiny test corpus crosses several boundaries); ``GW_MERGE_STRATEGY``
overrides the collective strategy.  Env-carried so the positional argv
contract above stays stable.
"""

from __future__ import annotations

import json
import os
import sys


def main() -> int:
    pid, n_proc = int(sys.argv[1]), int(sys.argv[2])
    port, path = sys.argv[3], sys.argv[4]
    chunk_bytes, dev_per_proc = int(sys.argv[5]), int(sys.argv[6])
    ckpt_path, crash_at = sys.argv[7], int(sys.argv[8])
    ledger_path = sys.argv[9] if len(sys.argv) > 9 else None
    fault_plan = sys.argv[10] if len(sys.argv) > 10 else None

    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={dev_per_proc}")
    from mapreduce_tpu.runtime.platform import force_cpu

    jax = force_cpu(verify=False)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

    from mapreduce_tpu.parallel import distributed as dist

    dist.initialize(coordinator_address=f"127.0.0.1:{port}",
                    num_processes=n_proc, process_id=pid, timeout_s=60)

    from mapreduce_tpu.config import Config
    from mapreduce_tpu.models.wordcount import WordCountJob
    from mapreduce_tpu.runtime import executor

    if crash_at >= 0:
        # Deterministic synchronized failure: every process raises before
        # dispatching step `crash_at`, after identical checkpoints exist.
        from mapreduce_tpu.parallel import mapreduce as mr

        original = mr.Engine.step

        def crashing_step(self, state, chunks, step_index):
            if int(step_index) >= crash_at:
                raise RuntimeError(f"injected crash at step {step_index}")
            return original(self, state, chunks, step_index)

        mr.Engine.step = crashing_step

    overlap = os.environ.get("GW_MERGE_OVERLAP") == "1"
    cfg = Config(chunk_bytes=chunk_bytes, table_capacity=1 << 10,
                 fault_plan=fault_plan or None,
                 merge_strategy=os.environ.get("GW_MERGE_STRATEGY",
                                               "tree"),
                 merge_overlap=overlap,
                 **({"inflight_groups": 1} if overlap else {}))
    telemetry = None
    if ledger_path:
        from mapreduce_tpu.obs import Telemetry

        # A shared run_id makes the shard pairing explicit (the fleet
        # merge's documented multi-host contract).
        telemetry = Telemetry.create(ledger_path=ledger_path,
                                     run_id="gw-fleet")
    try:
        rr = executor.run_job_global(WordCountJob(cfg), path, config=cfg,
                                     checkpoint_path=ckpt_path,
                                     checkpoint_every=1,
                                     telemetry=telemetry)
    except RuntimeError as e:
        if "injected crash" in str(e):
            print(json.dumps({"crashed": True, "process": pid}))
            return 17  # distinct code: the parent asserts the injection fired
        raise
    finally:
        if telemetry is not None:
            telemetry.close()

    table = rr.value
    if dist.is_coordinator():
        live = (table.count > 0) | (table.count_hi > 0)
        counts = sorted(int(c) for c in table.count[live])
        print(json.dumps({
            "total": int(table.total_count()),
            "counts": counts,
            "distinct": int(live.sum()),
            "resumed_bases_rows": int(rr.bases.shape[0]),
            "processes": n_proc,
            "devices": len(jax.devices()),
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
