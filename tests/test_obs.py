"""Unit tests for the obs/ telemetry subsystem (ISSUE 2): metrics
registry, run ledger, flight recorder, spans, the Telemetry facade, and
the obs_report tool's selftest path."""

import json
import os
import subprocess
import sys
import time

import pytest

from mapreduce_tpu import obs
from mapreduce_tpu.obs.registry import MetricsRegistry
from mapreduce_tpu.runtime import metrics as metrics_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- registry ---------------------------------------------------------------

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(2.5)
    reg.gauge("g").set(7)
    reg.observe("h", 0.004)
    reg.observe("h", 30.0)
    reg.observe("h", 500.0)  # past the last bound -> +Inf bucket
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 3.5
    assert snap["gauges"]["g"] == 7.0
    h = snap["histograms"]["h"]
    assert h["count"] == 3 and h["min"] == 0.004 and h["max"] == 500.0
    assert h["buckets"]["+Inf"] == 1


def test_registry_labels_key_separately():
    reg = MetricsRegistry()
    reg.counter("builds", strategy="tree").inc()
    reg.counter("builds", strategy="keyrange").inc(2)
    snap = reg.snapshot()["counters"]
    assert snap["builds{strategy=tree}"] == 1
    assert snap["builds{strategy=keyrange}"] == 2


def test_registry_kind_conflict_raises():
    reg = MetricsRegistry()
    reg.counter("x").inc()
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("x")


def test_registry_negative_counter_rejected():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match=">= 0"):
        reg.counter("c").inc(-1)


def test_registry_int_counters_snapshot_as_int():
    reg = MetricsRegistry()
    reg.counter("n").inc(3)
    assert reg.snapshot()["counters"]["n"] == 3
    assert isinstance(reg.snapshot()["counters"]["n"], int)


# -- ledger -----------------------------------------------------------------

def test_ledger_roundtrip_and_corrupt_line_skipped(tmp_path):
    p = str(tmp_path / "run.jsonl")
    with obs.RunLedger(p, run_id="r1") as led:
        led.write("run_start", devices=4)
        led.write("step", step_first=0, group_bytes=100)
    with open(p, "a") as f:
        f.write('{"truncated": \n')  # crash mid-write forensics
    with obs.RunLedger(p, run_id="r1") as led:  # append mode: resumes
        led.write("run_end", bytes=100)
    recs = list(obs.read_ledger(p))
    assert [r["kind"] for r in recs] == ["run_start", "step", "run_end"]
    assert all(r["run_id"] == "r1" for r in recs)
    steps = list(obs.read_ledger(p, kind="step"))
    assert len(steps) == 1 and steps[0]["group_bytes"] == 100


def test_ledger_run_start_carries_version(tmp_path):
    """Forward compat (ISSUE 7 satellite): every writer stamps the stream
    schema version on run_start — and only there — without call sites
    having to remember it."""
    p = str(tmp_path / "run.jsonl")
    with obs.RunLedger(p, run_id="r1") as led:
        led.write("run_start", devices=1)
        led.write("step", step_first=0)
    recs = list(obs.read_ledger(p))
    assert recs[0]["ledger_version"] == obs.LEDGER_VERSION == 10
    assert "ledger_version" not in recs[1]


def test_ledger_coerces_numpy_fields(tmp_path):
    """A ledger write must never take down the run: numpy scalars AND
    arrays coerce to JSON instead of raising out of json.dumps."""
    import numpy as np

    p = str(tmp_path / "run.jsonl")
    with obs.RunLedger(p, run_id="r1") as led:
        led.write("step", count=np.int64(7),
                  per_device=np.array([1, 2, 3], np.int64),
                  weird=object())
    rec = next(obs.read_ledger(p))
    assert rec["count"] == 7 and rec["per_device"] == [1, 2, 3]
    assert isinstance(rec["weird"], str)  # repr fallback


# -- flight recorder --------------------------------------------------------

def test_flight_ring_bounded_and_dump(tmp_path):
    fr = obs.FlightRecorder(capacity=4)
    for i in range(10):
        fr.record("step", step_first=i)
    assert fr.events_recorded == 10
    evs = fr.events()
    assert len(evs) == 4 and evs[0]["step_first"] == 6  # oldest evicted
    p = str(tmp_path / "crash.json")
    out = fr.dump(p, context={"error": "boom"})
    assert out == p and os.path.exists(p)
    with open(p) as f:
        dump = json.load(f)
    assert dump["context"]["error"] == "boom"
    assert dump["events_recorded"] == 10 and dump["events_kept"] == 4
    # Idempotent: a second failure in the same run must not overwrite the
    # first (most specific) dump.
    fr.record("unwind", step_first=99)
    assert fr.dump(str(tmp_path / "other.json")) == p
    assert not os.path.exists(str(tmp_path / "other.json"))


def test_flight_dump_write_failure_returns_none(tmp_path):
    """A failed dump must not claim a path that does not exist (the ledger
    failure record embeds the return value), and must not consume the
    one-dump-per-run slot."""
    fr = obs.FlightRecorder()
    fr.record("step", step_first=0)
    bad = str(tmp_path / "nodir")
    open(bad, "w").close()  # a FILE where a directory is needed
    assert fr.dump(os.path.join(bad, "crash.json")) is None
    assert fr.dumped_to is None
    good = str(tmp_path / "crash.json")
    assert fr.dump(good) == good  # a later good path still gets the dump


def test_flight_summarize_state_bounds_leaves():
    import numpy as np

    state = {"a": np.zeros((4, 8), np.uint32), "b": np.zeros(3, np.int64)}
    s = obs.summarize_state(state)
    assert s["n_leaves"] == 2
    assert s["total_nbytes"] == 4 * 8 * 4 + 3 * 8
    assert {"shape": [4, 8], "dtype": "uint32", "nbytes": 128} in s["leaves"]


# -- spans ------------------------------------------------------------------

def test_span_accumulates_timer_and_registry():
    """Spans accumulate into the timer only: repeated spans of one name
    add up, and nothing lands in the process registry."""
    timer = metrics_mod.PhaseTimer()
    with obs.span("work", timer):
        time.sleep(0.002)
    first = timer["work"]
    with obs.span("work", timer):
        pass
    with obs.span("untimed"):
        pass
    assert 0.002 <= first < timer["work"]
    assert set(timer.phases) == {"work"}
    assert not any(name.startswith("span.") for name in
                   obs.get_registry().snapshot()["histograms"])


def test_span_records_on_exception():
    timer = metrics_mod.PhaseTimer()
    with pytest.raises(RuntimeError):
        with obs.span("fails", timer):
            raise RuntimeError("boom")
    assert timer["fails"] > 0


# -- telemetry facade -------------------------------------------------------

def test_telemetry_disabled_is_noop(tmp_path):
    tel = obs.Telemetry.disabled()
    assert not tel.enabled
    timer = metrics_mod.PhaseTimer()
    timer.start("dispatch")
    timer.stop("dispatch")
    # None of these may write or raise.
    tel.step_record(step_first=0, step_last=0, group_bytes=1, cursor_bytes=1,
                    timer=timer)
    tel.event("step", step_first=0)
    tel.ledger_write("run_start")
    assert tel.flight_dump(context={"x": 1}) is None


def test_telemetry_step_record_phase_deltas(tmp_path):
    p = str(tmp_path / "run.jsonl")
    timer = metrics_mod.PhaseTimer()
    reg = MetricsRegistry()
    with obs.Telemetry.create(ledger_path=p, registry=reg) as tel:
        timer.phases = {"dispatch": 1.0, "read_wait": 0.5}
        tel.step_record(step_first=0, step_last=0, group_bytes=10,
                        cursor_bytes=10, timer=timer)
        timer.phases = {"dispatch": 1.25, "read_wait": 0.5}
        tel.step_record(step_first=1, step_last=1, group_bytes=10,
                        cursor_bytes=20, timer=timer)
    recs = list(obs.read_ledger(p, kind="step"))
    assert recs[0]["phases"] == {"dispatch": 1.0, "read_wait": 0.5}
    # Second record carries DELTAS, and the unchanged phase is dropped.
    assert recs[1]["phases"] == {"dispatch": 0.25}
    assert recs[1]["elapsed_s"] > 0
    assert reg.snapshot()["counters"]["executor.steps"] == 2
    # Flight path defaults next to the ledger.
    assert tel.flight_path == p + ".flight.json"


def test_telemetry_nonwriter_advances_baseline(tmp_path):
    """A non-coordinator process (write=False) must still advance the phase
    baseline, or a later writing record would report a cumulative blob."""
    p = str(tmp_path / "run.jsonl")
    timer = metrics_mod.PhaseTimer()
    with obs.Telemetry.create(ledger_path=p,
                              registry=MetricsRegistry()) as tel:
        timer.phases = {"dispatch": 1.0}
        tel.step_record(step_first=0, step_last=0, group_bytes=1,
                        cursor_bytes=1, timer=timer, write=False)
        timer.phases = {"dispatch": 1.2}
        tel.step_record(step_first=1, step_last=1, group_bytes=1,
                        cursor_bytes=2, timer=timer, write=True)
    recs = list(obs.read_ledger(p, kind="step"))
    assert len(recs) == 1 and recs[0]["phases"] == {"dispatch": 0.2}


def test_device_memory_stats_host_side():
    stats = obs.device_memory_stats()
    # CPU backend: memory_stats() is unavailable, live-array aggregate is
    # the fallback signal — present and non-negative.
    assert stats.get("live_arrays", 0) >= 0
    assert stats.get("live_bytes", 0) >= 0


# -- obs_report -------------------------------------------------------------

def test_obs_report_selftest_fixture():
    """The committed reporting path runs (jax-free) against the checked-in
    miniature ledger + flight fixtures — ISSUE 2 satellite."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "obs_report.py"),
         "--selftest"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "selftest ok" in proc.stdout


def test_obs_report_analyzes_generated_ledger(tmp_path):
    """analyze() agrees with a ledger produced by the real writer."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import obs_report
    finally:
        sys.path.pop(0)
    p = str(tmp_path / "run.jsonl")
    with obs.RunLedger(p, run_id="t1") as led:
        led.write("run_start", driver="run_job", job="wordcount", devices=2,
                  chunk_bytes=512, superstep=1, backend="xla",
                  merge_strategy="tree", input=["x"], retry=0)
        led.write("step", step_first=0, step_last=0, steps=1,
                  group_bytes=512, cursor_bytes=512,
                  phases={"read_wait": 0.3, "stage": 0.01, "dispatch": 0.1},
                  mem={"live_bytes": 1000, "live_arrays": 3})
        led.write("run_end", bytes=512, words=80, elapsed_s=0.5,
                  phases={"read_wait": 0.3, "stage": 0.01, "dispatch": 0.1})
    runs = obs_report.analyze(p)
    assert len(runs) == 1
    a = runs[0]
    assert a["completed"] and a["steps"] == 1 and a["bytes"] == 512
    assert a["classification"] == "read-bound"
    assert a["spikes"] == [] and a["mem_growth"] is None
