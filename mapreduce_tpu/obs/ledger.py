"""Per-run JSONL run ledger (ISSUE 2 tentpole (2)).

One machine-readable record per streaming step/superstep, plus run start/end
and checkpoint/retry/failure markers — the durable trace that makes a run's
seconds attributable after the fact (the round-4 3x streamed-vs-H2D gap
was unattributable until phase timers were threaded in by hand; the
ledger records what those timers see, every run).

Format: one JSON object per line, append-only, flushed per record so a
crashed or wedged run keeps every record up to the wedge.  In multi-host
runs only the checkpoint-writing process (the coordinator) writes —
callers gate on the executor's ``write_gate`` hook.

Record kinds (full schema: docs/observability.md):

=============  ===========================================================
kind           carries
=============  ===========================================================
run_start      run_id, ledger_version, config summary (devices,
               chunk_bytes, superstep, backend, map_impl, input paths),
               resume cursor
step           step_first/step_last/steps, group_bytes, cursor_bytes,
               per-phase second deltas (read_wait/stage/dispatch/...),
               elapsed_s since the previous record, device memory stats,
               compile events landed since the previous record, retries
group          one per RETIRED superstep group (ISSUE 7): monotonic-clock
               lifecycle timestamps (read_at/staged_at/dispatched_at/
               token_ready_at/retired_at, h2d_done_at on the last group),
               group bytes/steps, retire_wait_s, retry attempts — the raw
               material ``obs/timeline.py`` reconstructs per-resource
               timelines, overlap matrices and critical-path verdicts
               from — plus the group's ``data`` dict (ISSUE 8: per-group
               overlong/rescued/dropped/spill-fallback counters and
               running occupancy/top-mass) on stats-mode runs
data           one per run (ISSUE 8, before run_end): the data-plane
               summary — overlong/rescued/dropped totals, spill-fallback
               and rescue-escalation counts, table occupancy, top-bucket
               mass (key-skew proxy), stable2 window occupancy —
               classified by ``obs/datahealth.py`` and consumed by the
               window autotuner next to the timeline verdict
tune           one per run on ``Config(autotune='hint')`` runs (ISSUE 10,
               before run_end): the autotuner's recommendation — current
               vs proposed inflight_groups/prefetch_depth/superstep/
               chunk_bytes, the fired rule + reason, the signals read
               (bottleneck resource, projected-saving fraction, data
               verdict, window stats), and the full rule-by-rule decision
               trail.  Advisory: the live run is never changed
collective     the collective reduction's monotonic interval
               (started_at/ended_at) + merge strategy + ``op`` (ledger
               v10): ``op="finish"`` is the end-of-stream global reduce
               (ISSUE 13, one per run, inside the reduce phase);
               ``op="partial"`` is a window-boundary overlap merge
               (ISSUE 20, ``Config.merge_overlap`` runs only: one per
               retired partial, stamped with the boundary ``step``) —
               together the raw material of the fleet timeline's
               ``collective`` lane (strategy *builds* stay registry
               metrics: they happen at trace time)
progress       the live-run heartbeat (ISSUE 14, ledger v8): emitted on
               a wall-clock cadence from the dispatch/retire points —
               stream cursor + total bytes + completion fraction,
               groups dispatched/retired, current in-flight depth,
               throughput-so-far, ETA from the byte cursor.  Host-side
               only (no device work, no memory-stat sampling) and
               flushed per record, so ``tools/obswatch.py`` can tail a
               run in flight
checkpoint     step, cursor_bytes, save_s, path
retry          step, attempt, error
failure        step, cursor_bytes, error, flight-dump path (if written)
run_end        RunMetrics summary (bytes, words, elapsed, phases, GB/s)
=============  ===========================================================

Multi-host (ISSUE 13, ledger v7): every process of a multi-host run
writes its OWN shard file ``<ledger>.h<process_index>.jsonl`` (see
:func:`shard_path`) carrying every record kind above stamped with the
process's ``host`` index; ``run_start`` additionally carries the
process/device topology (``processes``, ``local_devices``) and the
``clock`` pair ``{wall, mono}`` sampled at ``jax.distributed`` init, so
``obs/fleet.py`` can rebase each host's monotonic lifecycle stamps onto
the shared wall clock and merge the shards into one fleet timeline.  The
coordinator keeps writing the merged-authoritative main file exactly as
before; flight dumps land per host (:func:`shard_flight_path` on
non-coordinators), so a remote failure leaves forensics from the host
that actually failed instead of being swallowed by the write gate.

Forward compatibility (ISSUE 7 satellite): ``run_start`` records carry
``ledger_version``; every consumer (:func:`read_ledger`, ``obs_report``,
``timeline``, ``trace_export``) skips unknown record kinds and unknown
fields instead of erroring, so a ledger written by a NEWER version of this
code still renders on an older reader — and vice versa.

Readers: :func:`read_ledger` here (used by tests) and ``tools/obs_report.py``
(the human/anomaly report; deliberately jax-free so it runs anywhere).
"""

from __future__ import annotations

import json
import os
import time
from typing import Iterator, Optional

#: Bumped when the record stream gains kinds/fields a consumer may care to
#: version-gate on.  1 = ISSUE 2-6 shape (implicit; pre-ISSUE-7 ledgers
#: carry no version field at all); 2 = adds ``group`` lifecycle records;
#: 3 = adds the per-run ``data`` record + per-group ``data`` dicts
#: (ISSUE 8); 4 = adds the per-run ``tune`` record (ISSUE 10: the window
#: autotuner's recommendation + decision trail, ``autotune='hint'`` runs
#: only); 5 = the ``data`` record and run_start gain the map-side
#: combiner fields (ISSUE 11: ``combiner`` resolved mode,
#: ``combiner_hits``/``combiner_flushes``/``combiner_evicted`` counters,
#: ``combiner_hit_rate``/``combiner_rows_deleted`` derived ratios);
#: 6 = run_start gains the kernel-geometry stamp (ISSUE 12: ``geometry``
#: label — 'default', a preset name, or 'custom' — plus
#: ``geometry_spec`` with the full field dict on custom runs), the knob
#: the geometry search tunes and ``obs_report --compare`` diffs;
#: 7 = pod-scale observability (ISSUE 13): multi-host records carry the
#: ``host`` process-index stamp, run_start the ``processes``/
#: ``local_devices`` topology + the ``clock`` {wall, mono} alignment
#: pair, every process writes its own ``<ledger>.h<p>.jsonl`` shard, and
#: the new per-run ``collective`` record times the collective finish;
#: 8 = live run watching (ISSUE 14): the executor's telemetry emits a
#: periodic ``progress`` heartbeat record (wall-clock cadence, host-side
#: only: stream cursor + total bytes, groups dispatched/retired, current
#: in-flight depth, throughput-so-far and the ETA derived from the byte
#: cursor), flushed like every record so ``tools/obswatch.py`` can tail
#: a run that has not ended — and ``obs/history.py`` can digest crashed
#: runs up to their last heartbeat;
#: 9 = robustness (ISSUE 15): typed ``fault`` records (seam,
#: fault_class, injected, crossing index — a chaotic run's own replayable
#: schedule via ``runtime/faults.FaultPlan.from_ledger``), ``degrade``
#: records (one per degradation-ladder step: ladder_step, field,
#: from/to), ``retry``/``failure`` records gain ``fault_class`` (+
#: ``seam`` on non-dispatch retries), and run_start stamps the
#: ``fault_plan`` spec on chaos runs.  Fault-free runs emit no new
#: records and no new fields beyond the version stamp;
#: 10 = placed reductions at runtime (ISSUE 20): ``collective`` records
#: gain ``op`` ("finish" = the end-of-stream reduce, exactly the v7
#: record; "partial" = a window-boundary overlap merge, one per retired
#: partial with its boundary ``step``), run_start stamps
#: ``merge_overlap: true`` on overlapped runs (absent otherwise), and
#: ``merge_strategy`` may now name a hierarchical 2-D program
#: (``hier-kr-tree`` / ``hier-tree-tree``).  Overlap-off runs emit no
#: new records and no new fields beyond the version stamp and the
#: finish record's ``op`` tag.
LEDGER_VERSION = 10


def shard_path(path: str, process_index: int) -> str:
    """The per-host shard ledger next to the main file (ledger v7):
    ``run.jsonl`` -> ``run.jsonl.h3.jsonl`` for process 3."""
    return f"{path}.h{int(process_index)}.jsonl"


def shard_flight_path(path: str, process_index: int) -> str:
    """The host-suffixed flight-dump path (ISSUE 13 bugfix: a
    non-coordinator failure dumps HERE instead of being swallowed by the
    coordinator-only write gate)."""
    return f"{path}.h{int(process_index)}.flight.json"


class RunLedger:
    """Append-only JSONL writer.  Not thread-safe by design: the executor
    writes from the driving thread only (the prefetch thread records into
    the metrics registry instead)."""

    def __init__(self, path: str, run_id: str):
        self.path = path
        self.run_id = run_id
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        self._f = open(path, "a", encoding="utf-8")
        self.records_written = 0

    def write(self, kind: str, **fields) -> None:
        if kind == "run_start":
            # Every writer stamps the stream's schema version exactly once,
            # without each call site having to remember to.
            fields.setdefault("ledger_version", LEDGER_VERSION)
        rec = {"ts": round(time.time(), 6), "run_id": self.run_id,
               "kind": kind, **fields}
        self._f.write(json.dumps(rec, default=_json_default) + "\n")
        self._f.flush()  # a wedged run must keep everything up to the wedge
        self.records_written += 1

    def close(self) -> None:
        if not self._f.closed:
            self._f.close()

    def __enter__(self) -> "RunLedger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def _json_default(obj):
    """Last-resort coercion: numpy scalars AND arrays ride through cleanly
    (``tolist`` handles both — ``item()`` would raise on size > 1);
    anything else becomes its repr (a ledger write must never take down
    the run it is observing)."""
    if hasattr(obj, "tolist"):
        try:
            return obj.tolist()
        except Exception:
            pass
    return repr(obj)


def read_ledger(path: str, kind: Optional[str] = None) -> Iterator[dict]:
    """Yield ledger records, skipping lines that fail to parse (a record
    truncated by a crash mid-write is expected forensics, not an error)."""
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if kind is None or rec.get("kind") == kind:
                yield rec
