"""The readers of the program-build and string-recovery metrics on fixed
records: ``build_share`` from the jobs' phases, ``recover_share`` from host
spans; and each reads nothing from a program that opens no such span."""

from __future__ import annotations

import importlib.util
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
S = 1_000_000_000  # ns


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


tr = _load(os.path.join(BENCH, "trace.py"), "bench_trace_build_metrics")


def metric(name: str):
    return _load(os.path.join(BENCH, "metrics", name + ".py"),
                 "metric_b_" + name).read


def reduction(spans, window=(0, 10 * S)):
    return tr.Reduction(window=window, n_devices=1, ops=[], spans=spans)


def run_of(trace=None, jobs=()):
    return tr.Run(jobs=list(jobs), window_s=10.0, setup_s=1.0,
                  peaks={"hbm_bytes_per_s": 819e9}, trace=trace)


JOBS = [{"phases": {"total": 4.0, "build": 3.0, "build.trace": 2.5}},
        {"phases": {"total": 6.0, "build": 2.0, "build.trace": 1.5}}]
#: The same two jobs from a program that times no build phase.
UNTIMED = [{"phases": {"total": 4.0, "stream": 3.0}},
           {"phases": {"total": 6.0, "stream": 5.0}}]
SPANS = [("job", 0, 4 * S), ("dispatch", 1, 2 * S), ("build", 2, S),
         ("build.trace", 3, S // 2), ("reduce", 3 * S, 4 * S),
         ("build", 3 * S, 3 * S + 100), ("recover", 3 * S + 200, 4 * S),
         ("recover", 3 * S + 300, 4 * S - 10),  # the harness's twin
         ("job", 4 * S, 10 * S), ("build", 5 * S, 6 * S),
         ("build", 6 * S, 7 * S), ("recover", 9 * S, 10 * S)]


def test_build_share_is_build_over_total():
    assert metric("build_share")(run_of(jobs=JOBS)) == pytest.approx(50.0)


def test_build_share_reads_zero_builds_as_zero():
    jobs = [{"phases": {"total": 2.0, "build": 0.0}}]
    assert metric("build_share")(run_of(jobs=jobs)) == 0.0


def test_build_share_leaves_out_jobs_that_time_no_build():
    # A job of a program without the build phase adds nothing to either sum.
    jobs = JOBS + [{"phases": {"total": 10.0, "stream": 9.0}}]
    assert metric("build_share")(run_of(jobs=jobs)) == pytest.approx(50.0)


def test_recover_share_counts_nested_twins_once():
    # 1 s - 200 ns in job 1 (its twin inside it adds nothing), 1 s in job 2.
    t = reduction(SPANS)
    expected = 100.0 * (2 * S - 200) / 1e9 / 10.0
    assert metric("recover_share")(run_of(t)) == pytest.approx(expected)


def test_recover_share_merges_overlapping_spans():
    # 1-3 s and 2-5 s overlap: 4 s covered, not 5.
    t = reduction([("recover", S, 3 * S), ("recover", 2 * S, 5 * S)])
    assert metric("recover_share")(run_of(t)) == pytest.approx(40.0)


def test_recover_share_clips_to_the_window():
    t = reduction([("job", 0, 2 * S), ("recover", S, 3 * S)],
                  window=(0, 2 * S))
    assert metric("recover_share")(run_of(t)) == pytest.approx(50.0)


@pytest.mark.parametrize("name", ["build_share", "recover_share"])
def test_nothing_to_read_reads_nothing(name):
    no_build = [(n, s, e) for n, s, e in SPANS if not n.startswith("build")]
    untimed = run_of(reduction(no_build), UNTIMED)
    assert metric(name)(run_of()) is None
    if name != "recover_share":  # the harness opens recover spans itself
        assert metric(name)(untimed) is None
    else:
        assert metric(name)(untimed) > 0
        no_recover = [(n, s, e) for n, s, e in no_build if n != "recover"]
        assert metric(name)(run_of(reduction(no_recover))) is None

