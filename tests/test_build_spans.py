"""Program build and string recovery as spans (``obs.span``) inside the
program: one profiled ``count_file`` on the CPU, reduced by the benchmark's
own trace reduction (``benchmark/trace.py``) with the build spans added to
the host spans it keeps; and the Engine's build-once contract.  Each test
starts from an empty program table (``tests/conftest.py``), so a fresh
Engine builds."""

from __future__ import annotations

import glob
import importlib.util
import logging
import os
import sys

import jax
import numpy as np
import pytest

from mapreduce_tpu import obs
from mapreduce_tpu.config import Config
from mapreduce_tpu.models.wordcount import WordCountJob
from mapreduce_tpu.parallel.mapreduce import Engine, clear_programs
from mapreduce_tpu.parallel.mesh import data_mesh
from mapreduce_tpu.runtime import executor
from mapreduce_tpu.runtime import metrics as metrics_mod
from mapreduce_tpu.utils import oracle
from tests.conftest import make_corpus

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = Config(chunk_bytes=512, table_capacity=2048)
BUILD = ("build.trace", "build.lower", "build.compile")


def _load_trace():
    spec = importlib.util.spec_from_file_location(
        "bench_trace_build_spans",
        os.path.join(REPO, "benchmark", "trace.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    mod.HOST_SPANS = mod.HOST_SPANS + ("build",) + BUILD
    return mod


def _builds() -> dict:
    counters = obs.get_registry().snapshot()["counters"]
    return {k: v for k, v in counters.items()
            if k.startswith("engine.builds")}


class _Phases(logging.Handler):
    def __init__(self):
        super().__init__(logging.INFO)
        self.phases: list[dict] = []

    def emit(self, record):
        fields = getattr(record, "fields", None) or {}
        if record.getMessage() == "run complete":
            self.phases.append(dict(fields["phases"]))


@pytest.fixture(scope="module")
def profiled(tmp_path_factory, rng):
    """One ``count_file`` job of three steps under the profiler, inside a
    ``job`` span as the benchmark harness opens it, from an empty program
    table (a module fixture runs before the per-test clear)."""
    clear_programs()
    tmp = tmp_path_factory.mktemp("build_spans")
    corpus = make_corpus(rng, 250, 60)
    path = tmp / "corpus.txt"
    path.write_bytes(corpus)
    handler = _Phases()
    logger = logging.getLogger("test_build_spans")
    logger.handlers[:] = [handler]
    logger.setLevel(logging.INFO)
    logger.propagate = False
    before = _builds()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp / "trace"), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("job"):
            result = executor.count_file(str(path), CFG, mesh=data_mesh(1),
                                         logger=logger)
    finally:
        jax.profiler.stop_trace()
    after = _builds()
    files = glob.glob(str(tmp / "trace" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    red = _load_trace().reduce(files[0], 1)
    return {"result": result, "corpus": corpus, "spans": red.spans,
            "phases": handler.phases,
            "builds": {k: after[k] - before.get(k, 0) for k in after
                       if after[k] != before.get(k, 0)}}


def _named(spans, name):
    return [(s, e) for n, s, e in spans if n == name]


def _inside(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_profiled_job_counts_exactly(profiled):
    assert profiled["result"].as_dict() == oracle.word_counts(
        profiled["corpus"])


def test_one_build_span_per_engine_program(profiled):
    builds = _named(profiled["spans"], "build")
    # superstep 1: the step program and the finish program.
    assert len(builds) == 2
    assert profiled["builds"] == {"engine.builds{program=mr_step}": 1,
                                  "engine.builds{program=mr_finish}": 1}


def test_each_build_holds_trace_lower_compile_in_order(profiled):
    spans = profiled["spans"]
    for b in _named(spans, "build"):
        children = sorted((s, n) for n, s, e in spans
                          if n in BUILD and _inside((s, e), b))
        assert [n for _, n in children] == list(BUILD)
    assert sum(len(_named(spans, n)) for n in BUILD) == 3 * 2


def test_builds_sit_in_dispatch_or_reduce(profiled):
    spans = profiled["spans"]
    parents = _named(spans, "dispatch") + _named(spans, "reduce")
    builds = _named(spans, "build")
    assert all(any(_inside(b, p) for p in parents) for b in builds)
    # The step program is built in the first dispatch, the finish program
    # in the reduce phase.
    assert any(_inside(b, p) for b in builds for p in _named(spans, "reduce"))


def test_recover_sits_inside_the_job(profiled):
    (job,) = _named(profiled["spans"], "job")
    recovers = _named(profiled["spans"], "recover")
    assert len(recovers) == 1 and _inside(recovers[0], job)


def test_phases_carry_build_within_total(profiled):
    (phases,) = profiled["phases"]
    assert 0 < phases["build"] <= phases["total"]
    for child in BUILD:
        assert 0 <= phases[child] <= phases["build"]


def _engine(timer=None) -> Engine:
    return Engine(WordCountJob(CFG), data_mesh(2), timer=timer)


def _chunks(rng, n_dev: int, k: int = 0) -> np.ndarray:
    text = np.frombuffer(make_corpus(rng, 400, 50)[:CFG.chunk_bytes],
                         np.uint8)
    one = np.zeros((CFG.chunk_bytes,), np.uint8)
    one[:len(text)] = text
    shape = (n_dev, k, CFG.chunk_bytes) if k else (n_dev, CFG.chunk_bytes)
    return np.broadcast_to(one, shape).copy()


def test_second_dispatch_builds_nothing(rng):
    timer = metrics_mod.PhaseTimer()
    eng = _engine(timer)
    assert timer["build"] == 0.0  # timed from the start
    chunks = _chunks(rng, 2)
    state = eng.step(eng.init_states(), chunks, 0)
    first, built = timer["build"], _builds()
    assert first > 0
    state = eng.step(state, chunks, 1)
    jax.block_until_ready(state)
    assert timer["build"] == first and _builds() == built
    assert list(eng._programs) == [("mr_step", (2, CFG.chunk_bytes))]


def test_every_program_is_built_once_under_its_own_name(rng):
    eng = _engine()
    before = _builds()
    state = eng.step_many(eng.init_states(), _chunks(rng, 2, k=2), 0)
    accum = eng.partial_merge(None, state)
    state = eng.partial_reset(state)
    state = eng.step(state, _chunks(rng, 2), 2)
    accum = eng.partial_merge(accum, state)
    eng.replicate_to_host(state)
    jax.block_until_ready(eng.finish_residual(accum, state))
    jax.block_until_ready(eng.finish(state))
    for _ in range(2):  # a second round builds nothing more
        state = eng.step(state, _chunks(rng, 2), 3)
        jax.block_until_ready(eng.partial_merge(accum, state))
    after = _builds()
    names = ("mr_step_many", "mr_partial", "mr_reset", "mr_step",
             "mr_replicate", "mr_finish_residual", "mr_finish")
    delta = {k: after[k] - before.get(k, 0) for k in after}
    assert delta == {f"engine.builds{{program={n}}}": 2 if n == "mr_partial"
                     else 1 for n in names}
    # The XLA module of each program is named after it.
    for key, prog in eng._programs.items():
        assert f"HloModule jit_{key[0]}" in prog.as_text()


def test_a_new_chunk_shape_is_a_new_program(rng):
    eng = _engine()
    state = eng.step(eng.init_states(), _chunks(rng, 2), 0)
    wide = np.zeros((2, 2 * CFG.chunk_bytes), np.uint8)
    jax.block_until_ready(eng.step(state, wide, 1))
    assert [k[1] for k in eng._programs] == [(2, CFG.chunk_bytes),
                                             (2, 2 * CFG.chunk_bytes)]


@pytest.mark.parametrize("strategy", ["tree", "gather", "keyrange"])
def test_partial_shape_is_the_first_partials_without_a_build(rng, strategy):
    eng = Engine(WordCountJob(CFG), data_mesh(2), merge_strategy=strategy)
    before = _builds()
    shape = eng.partial_shape()
    assert eng._programs == {} and _builds() == before
    accum = eng.partial_merge(None, eng.step(eng.init_states(),
                                             _chunks(rng, 2), 0))
    assert jax.tree.map(lambda a: (a.shape, a.dtype), shape) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), accum)
