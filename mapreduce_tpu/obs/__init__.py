"""Unified telemetry for the streaming runtime (ISSUE 2).

One subsystem joining disconnected shims (``runtime/metrics.py``'s timers
and ``runtime/logging.py``'s event lines, still used, now fed through one
layer):

* :mod:`.registry` — process-wide counters/gauges/histograms with labels;
* :mod:`.ledger` — per-run JSONL step records (phase timings, bytes,
  device memory, compile events, retries);
* :mod:`.flight` — bounded ring of recent events, dumped with a state
  summary on the failure path;
* :mod:`.spans` — profiler-region + phase-timer spans so XProf timelines
  line up with ledger records;
* :mod:`.timeline` — jax-free reconstruction of per-group ``group``
  lifecycle records into per-resource timelines, overlap matrices,
  device-idle gap attribution and a critical-path ``bottleneck`` verdict
  (ISSUE 7); ``tools/trace_export.py`` renders the same records as
  Perfetto-viewable Chrome trace-event JSON;
* :mod:`.datahealth` — jax-free classification of the per-run ``data``
  record (on-device spill/rescue/skew/occupancy counters, ISSUE 8) into
  spill-bound / rescue-heavy / skew-hot / occupancy-starved /
  table-pressure verdicts — the data-shape fitness signal next to the
  timeline's resource verdict;
* :mod:`.fleet` — jax-free merge of multi-host per-process ledger shards
  (``<ledger>.h<p>.jsonl``, ISSUE 13) into one clock-aligned fleet
  timeline: per-host lanes, per-superstep straggler skew, collective
  accounting, and the ``fleet_bottleneck`` verdict (straggler-bound /
  collective-bound / balanced);
* :mod:`.history` — the run-history warehouse (ISSUE 14): ingest many
  (possibly sharded, append-mode) ledgers into an on-disk index of
  per-run digests keyed by config, longitudinal series/streak queries,
  the ``regressing``/``improving``/``steady``/``config-drift`` drift
  classifier, and :func:`.history.resolve_prior` — the one prior-run
  read ``combiner='auto'``, ``geometry='auto'`` and the autotuner's
  ``derive_signals`` resolve through;
* :mod:`.telemetry` — the facade the executor takes as ONE optional arg.

Reporting: ``tools/obs_report.py`` renders a ledger/flight pair into a run
summary with anomaly flags.  Schemas: ``docs/observability.md``.
"""

from mapreduce_tpu.obs import datahealth, fleet, history, timeline
from mapreduce_tpu.obs.flight import FlightRecorder, summarize_state
from mapreduce_tpu.obs.ledger import (LEDGER_VERSION, RunLedger, read_ledger,
                                      shard_flight_path, shard_path)
from mapreduce_tpu.obs.registry import MetricsRegistry, get_registry
from mapreduce_tpu.obs.spans import span
from mapreduce_tpu.obs.telemetry import (Telemetry, device_memory_stats,
                                         maybe)

__all__ = [
    "FlightRecorder", "LEDGER_VERSION", "MetricsRegistry", "RunLedger",
    "Telemetry", "datahealth", "device_memory_stats", "fleet",
    "get_registry", "history", "maybe", "read_ledger",
    "shard_flight_path", "shard_path", "span", "summarize_state",
    "timeline",
]
