"""``rescue_device_share`` on the small trace recorded on the chip (one 1 MB
webby job, ``tiny.xplane.pb.gz``) and on made-up reductions."""

from __future__ import annotations

import gzip
import importlib.util
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.join(os.path.dirname(os.path.dirname(HERE)), "benchmark")
TRACE = os.path.join(HERE, "data", "tiny.xplane.pb.gz")


def _load(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


tr = _load(os.path.join(BENCH, "trace.py"), "bench_trace_rescue")
share = _load(os.path.join(BENCH, "metrics", "rescue_device_share.py"),
              "metric_t_rescue_device_share")


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(TRACE) as f:
        path.write_bytes(f.read())
    return tr.reduce(str(path), 1)


def run_of(trace):
    return tr.Run(jobs=[], window_s=1.0, setup_s=0.0, peaks=None,
                  trace=trace)


def _op(red, name):
    return next(op for op in red.ops if op.name == name)


def test_picks_the_rescue_cond_and_what_it_holds(red):
    conds = {op.name for op in red.ops if op.opcode == "conditional"}
    assert conds == {"cond.139", "cond.116", "cond.76"}
    assert [op.name for op in red.ops if share.is_rescue(op)] == ["cond.116"]
    rescue, tier = _op(red, "cond.116"), _op(red, "cond.76")
    # The tier cond (u32[32768] poison rows) is nested in the rescue's.
    assert "u32[32768]" in tier.hlo
    assert rescue.start <= tier.start and tier.end <= rescue.end
    assert rescue.seconds == pytest.approx(4.1327e-3, rel=1e-4)
    assert red.busy_s == pytest.approx(0.1209258, rel=1e-5)
    value = share.read(run_of(red))
    assert value == pytest.approx(100 * rescue.seconds / red.busy_s)
    assert value == pytest.approx(3.4176, rel=1e-4)


def test_the_spill_fallback_cond_is_not_the_rescue(red):
    # cond.139 holds the 32 MiB chunk too, and runs 26x longer.
    fallback = _op(red, "cond.139")
    assert "u8[33554432]" in fallback.hlo
    assert fallback.seconds > 25 * _op(red, "cond.116").seconds
    assert not share.is_rescue(fallback)
    assert not share.is_rescue(_op(red, "cond.76"))


def test_no_rescue_cond_reads_nothing(red):
    assert share.read(run_of(None)) is None
    others = [op for op in red.ops if not share.is_rescue(op)]
    rest = tr.Reduction(window=red.window, n_devices=1, ops=others,
                        spans=red.spans)
    assert rest.busy_s > 0
    assert share.read(run_of(rest)) is None


def test_rescue_time_over_busy_on_each_device(red):
    """Made-up ops on two devices: the rescue cond and what it holds count,
    ops outside it do not, and the share is averaged over devices."""
    hlo = _op(red, "cond.116").hlo
    ms = 1_000_000
    ops = []
    for dev in (0, 1):
        ops += [tr.Op("fusion.1", dev, 0, 60 * ms, "%fusion.1 = u32[8]{0} "
                      "fusion(u32[8]{0} %a), kind=kLoop"),
                tr.Op("cond.116", dev, 60 * ms, 80 * ms, hlo),
                tr.Op("gather.2", dev, 62 * ms, 70 * ms, ""),
                tr.Op("sort.3", dev, 70 * ms, 79 * ms, ""),
                tr.Op("sort.4", dev, 85 * ms, 100 * ms, "")]
    tr._self_times([op for op in ops if op.device == 0])
    tr._self_times([op for op in ops if op.device == 1])
    red2 = tr.Reduction(window=(0, 100 * ms), n_devices=2, ops=ops,
                        spans=[])
    assert red2.busy_s == pytest.approx(0.095)
    assert share.read(run_of(red2)) == pytest.approx(100 * 20 / 95)


def test_cell_1_takes_the_empty_branch_and_is_not_asked(tmp_path):
    """The recorded cell-1 job (``build.xplane.pb.gz``, Zipf words of at
    most 6 bytes) runs the same conditional on its no-overlong branch: the
    reader finds it, 2.5 us of 117 ms busy.  The harness does not ask
    cell 1 for the metric: its ``workloads`` list holds the webby cell
    alone."""
    path = tmp_path / "build.xplane.pb"
    with gzip.open(os.path.join(HERE, "data", "build.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    red1 = tr.reduce(str(path), 1)
    [rescue] = [op for op in red1.ops if share.is_rescue(op)]
    assert rescue.name == "cond.116"
    assert rescue.seconds == pytest.approx(2.537e-6, rel=1e-3)
    assert share.read(run_of(red1)) == pytest.approx(0.0021715, rel=1e-3)
    harness = _load(os.path.join(BENCH, "run.py"), "bench_run_rescue_cells")
    root = os.path.dirname(BENCH)
    asked = {w: {m["name"] for m in harness.load_cell(root, w)["per_layer"]}
             for w in ("zipf-scale-wordcount.stream",
                       "webby-wordcount.stream")}
    assert "rescue_device_share" not in asked["zipf-scale-wordcount.stream"]
    assert "rescue_device_share" in asked["webby-wordcount.stream"]
