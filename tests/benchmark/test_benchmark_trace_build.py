"""The trace reduction and the build and recovery readers on a second small
trace recorded on a TPU v5e: one ``count_file`` job of 1,000,000 bytes of
the ``zipf-scale-wordcount`` configuration, under the harness's ``job`` and
``recover`` spans, from a program that spans its own program builds and
string recovery.  Stored gzipped, without the profiler's
``/host:metadata`` plane."""

from __future__ import annotations

import gzip
import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark")
TRACE = os.path.join(HERE, "data", "build.xplane.pb.gz")
BUILD = ("build", "build.trace", "build.lower", "build.compile")


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


tr = _load(os.path.join(BENCH, "trace.py"), "bench_trace_build_recorded")


@pytest.fixture(scope="module")
def path(tmp_path_factory):
    out = tmp_path_factory.mktemp("trace") / "build.xplane.pb"
    with gzip.open(TRACE) as f:
        out.write_bytes(f.read())
    return str(out)


@pytest.fixture(scope="module")
def red(path):
    """The reduction as the benchmark has it: build spans are not among
    the host spans that name idle gaps."""
    return tr.reduce(path, 1)


@pytest.fixture(scope="module")
def red_build(path):
    """The reduction with the build spans among the host spans (a copy of
    the reduction's module, so ``red`` keeps the benchmark's own)."""
    mod = _load(os.path.join(BENCH, "trace.py"), "bench_trace_build_spans")
    mod.HOST_SPANS = mod.HOST_SPANS + BUILD
    return mod.reduce(path, 1)


def run_of(red, jobs=({"phases": {"total": 1.0, "build": 0.9}},)):
    return tr.Run(jobs=list(jobs), window_s=red.window_s, setup_s=0.0,
                  peaks={"hbm_bytes_per_s": 819e9}, trace=red)


def metric(name: str):
    return _load(os.path.join(BENCH, "metrics", name + ".py"),
                 "metric_rec_build_" + name).read


def test_the_largest_idle_gap_is_build_trace(red, red_build):
    idle = red_build.idle_by_span()
    assert max(idle, key=idle.get) == "build.trace"
    assert idle.get("dispatch", 0.0) < 1.0
    # The gaps are the same gaps, named anew: their total does not move.
    assert sum(idle.values()) == pytest.approx(sum(
        red.idle_by_span().values()))
    assert sum(idle.values()) == pytest.approx(
        red_build.window_s - red_build.busy_s)
    # Without the build spans the same time is named after dispatch.
    old = red.idle_by_span()
    assert max(old, key=old.get) == "dispatch"


def test_each_build_holds_its_three_stages(red_build):
    spans = red_build.spans
    builds = [(s, e) for n, s, e in spans if n == "build"]
    assert len(builds) == 2
    for lo, hi in builds:
        inner = sorted((s, n) for n, s, e in spans
                       if n in BUILD[1:] and lo <= s and e <= hi)
        assert [n for _, n in inner] == list(BUILD[1:])


def test_two_builds_start_inside_the_job(red_build):
    # The step program and the finish program, at superstep 1.
    (job,) = [(s, e) for n, s, e in red_build.spans if n == "job"]
    starts = [s for n, s, _ in red_build.spans if n == "build"]
    assert len(starts) == 2 and all(job[0] <= s < job[1] for s in starts)


def test_recover_share_counts_the_twin_spans_once(red):
    recovers = [(s, e) for n, s, e in red.spans if n == "recover"]
    # The program's own span and the harness's, one inside the other.
    assert len(recovers) == 2
    outer = max(recovers, key=lambda r: r[1] - r[0])
    share = metric("recover_share")(run_of(red))
    assert share == pytest.approx(
        100.0 * (outer[1] - outer[0]) / 1e9 / red.window_s)
    assert 0 < share < 100


def test_programs_are_named_on_the_modules_line(path):
    from jax.profiler import ProfileData

    names = {ev.name.split("(")[0] for plane in ProfileData.from_file(
        path).planes for line in plane.lines if line.name == "XLA Modules"
        for ev in line.events}
    assert {"jit_mr_step", "jit_mr_finish"} <= names
    assert not names & {"jit_local_step", "jit_final"}


def test_the_map_kernel_is_found_under_its_new_name(red):
    kernels = {op.label for op in red.ops if op.pallas}
    assert kernels == {"mr_step.1 custom-call tpu_custom_call"}
    share = metric("map_kernel_roofline")(run_of(red))
    assert 0 < share < 100
