"""The process-wide program table (``parallel/mapreduce.py``): Engines of
equal keys share compiled programs across jobs, Engines whose keys differ in
anything the trace reads never do, and the table keeps no Engine alive."""

from __future__ import annotations

import contextlib
import gc
import logging
import random
import sys
import threading
import weakref

import jax
import numpy as np
import pytest

from mapreduce_tpu import obs
from mapreduce_tpu.config import Config
from mapreduce_tpu.models import wordcount
from mapreduce_tpu.models.grep import GrepJob
from mapreduce_tpu.models.wordcount import WordCountJob
from mapreduce_tpu.parallel import mapreduce as mr
from mapreduce_tpu.parallel.mesh import data_mesh
from mapreduce_tpu.runtime import executor
from mapreduce_tpu.utils import oracle
from tests.conftest import make_corpus

CFG = Config(chunk_bytes=512, table_capacity=2048)


def _engine_counters() -> dict:
    counters = obs.get_registry().snapshot()["counters"]
    return {k: v for k, v in counters.items() if k.startswith("engine.")}


class counted:
    """``engine.builds`` and ``engine.reuses`` counted inside a ``with``
    block, by program name."""

    def __enter__(self):
        self.before = _engine_counters()
        self.builds: dict[str, int] = {}
        self.reuses: dict[str, int] = {}
        return self

    def __exit__(self, *exc):
        for k, v in _engine_counters().items():
            n = v - self.before.get(k, 0)
            if n:
                kind, program = k[:-1].split("{program=")
                out = self.builds if kind == "engine.builds" else self.reuses
                out[program] = n


@pytest.fixture
def spans(monkeypatch):
    """Names of every ``obs.span`` opened while the test runs."""
    names: list[str] = []
    real = obs.span

    @contextlib.contextmanager
    def recording(name, timer=None):
        names.append(name)
        with real(name, timer):
            yield

    monkeypatch.setattr(obs, "span", recording)
    return names


@pytest.fixture
def engines(monkeypatch):
    """A weak reference to every Engine ``run_job`` makes, and the programs
    each acquired (the dict, which holds no reference to its Engine)."""
    made: list[tuple[weakref.ref, dict]] = []

    class Recorded(mr.Engine):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            made.append((weakref.ref(self), self._programs))

    monkeypatch.setattr(executor, "Engine", Recorded)
    return made


@pytest.fixture(scope="module")
def corpus_path(tmp_path_factory, rng):
    corpus = make_corpus(rng, 800, 60)  # four steps on two devices
    path = tmp_path_factory.mktemp("program_table") / "corpus.txt"
    path.write_bytes(corpus)
    return str(path), corpus


class _Phases(logging.Handler):
    """The phases of each run's ``run complete`` event."""

    def __init__(self):
        super().__init__(logging.INFO)
        self.phases: list[dict] = []
        self.logger = logging.getLogger("test_program_table")
        self.logger.handlers[:] = [self]
        self.logger.setLevel(logging.INFO)
        self.logger.propagate = False

    def emit(self, record):
        if record.getMessage() == "run complete":
            self.phases.append(dict(record.fields["phases"]))


@pytest.mark.parametrize("kind", [{}, {"top_k": 5}, {"ngram": 2},
                                  {"distinct_sketch": True},
                                  {"count_sketch": True}],
                         ids=["words", "top_k", "ngram", "hll", "cms"])
def test_a_second_job_builds_nothing_and_counts_exactly(corpus_path, spans,
                                                        kind):
    path, corpus = corpus_path
    log = _Phases()
    with counted() as first:
        a = executor.count_file(path, CFG, mesh=data_mesh(2),
                                logger=log.logger, **kind)
    assert first.builds == {"mr_step": 1, "mr_finish": 1} and not first.reuses
    assert "build" in spans
    spans.clear()
    with counted() as second:
        b = executor.count_file(path, CFG, mesh=data_mesh(2),
                                logger=log.logger, **kind)
    assert not second.builds
    assert second.reuses == {"mr_step": 1, "mr_finish": 1}
    assert not [n for n in spans if n.startswith("build")]
    assert log.phases[0]["build"] > 0 and log.phases[1]["build"] == 0.0
    assert (b.as_dict(), b.total) == (a.as_dict(), a.total)
    if "ngram" in kind:
        ref = wordcount.count_ngrams(corpus, 2, CFG)
        assert (b.as_dict(), b.total) == (ref.as_dict(), ref.total)
    else:
        truth = oracle.word_counts(corpus)
        assert b.as_dict() == {w: truth[w] for w in b.words}
        assert b.total == sum(truth.values())
        assert len(b.words) == (5 if "top_k" in kind else len(truth))


def _chunks(rng, n_dev: int) -> np.ndarray:
    text = np.frombuffer(make_corpus(rng, 400, 50)[:CFG.chunk_bytes],
                         np.uint8)
    one = np.zeros((CFG.chunk_bytes,), np.uint8)
    one[:len(text)] = text
    return np.broadcast_to(one, (n_dev, CFG.chunk_bytes)).copy()


def _step(config: Config = CFG, n_dev: int = 2, data_stats: bool = False,
          rng=None):
    eng = mr.Engine(WordCountJob(config), data_mesh(n_dev),
                    data_stats=data_stats)
    jax.block_until_ready(eng.step(eng.init_states(), _chunks(rng, n_dev), 0))
    return eng._programs[("mr_step", (n_dev, CFG.chunk_bytes))]


@pytest.mark.parametrize("variant", [
    {"config": Config(chunk_bytes=512, table_capacity=4096)},
    {"config": Config(chunk_bytes=512, table_capacity=2048, backend="xla")},
    {"config": Config(chunk_bytes=512, table_capacity=2048, combiner="salt")},
    {"config": Config(chunk_bytes=512, table_capacity=2048,
                      geometry="tall512")},
    {"config": Config(chunk_bytes=512, table_capacity=2048, merge_every=2)},
    {"n_dev": 4},
    {"data_stats": True},
], ids=["table_capacity", "backend", "combiner", "geometry", "merge_every",
        "mesh", "data_stats"])
def test_engines_that_differ_in_what_the_trace_reads_never_share(rng,
                                                                 variant):
    base = _step(rng=rng)
    with counted() as d:
        other = _step(rng=rng, **variant)
    assert d.builds == {"mr_step": 1} and not d.reuses
    assert other is not base
    # The control: the base key again is served from the table.
    with counted() as d:
        assert _step(rng=rng) is base
    assert d.reuses == {"mr_step": 1} and not d.builds


def test_the_table_keeps_no_engine_alive(corpus_path, engines):
    path, _ = corpus_path
    for _ in range(2):
        result = executor.run_job(WordCountJob(CFG), path, CFG,
                                  mesh=data_mesh(2))
        del result
    gc.collect()
    assert [ref() for ref, _ in engines] == [None, None]
    first, second = (programs for _, programs in engines)
    assert first and second == first
    with mr._PROGRAMS_LOCK:
        held = list(mr._PROGRAMS.values())
    assert all(any(p is q for q in held) for p in first.values())


def test_the_table_is_bounded_and_evicts_the_least_recently_used():
    progs = [object() for _ in range(mr.PROGRAM_TABLE_SIZE + 1)]
    for i, p in enumerate(progs[:-1]):
        mr._table_put(("k", i), p)
    assert mr._table_get(("k", 0)) is progs[0]  # now the most recent
    mr._table_put(("k", len(progs) - 1), progs[-1])
    assert len(mr._PROGRAMS) == mr.PROGRAM_TABLE_SIZE
    assert mr._table_get(("k", 1)) is None
    assert mr._table_get(("k", 0)) is progs[0]
    assert mr._table_get(("k", len(progs) - 1)) is progs[-1]
    mr.clear_programs()
    assert not mr._PROGRAMS


def test_the_table_stays_whole_under_threads():
    """Threads looking up and adding programs at a tiny switch interval:
    no lookup fails on a key another thread evicted, and the table stays
    at its size."""
    errors: list[BaseException] = []

    def worker(seed: int):
        r = random.Random(seed)
        try:
            for _ in range(2000):
                key = ("k", r.randrange(3 * mr.PROGRAM_TABLE_SIZE))
                if mr._table_get(key) is None:
                    mr._table_put(key, object())
        except Exception as e:  # noqa: BLE001 - reported by the assert
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(16)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert not errors
    assert len(mr._PROGRAMS) == mr.PROGRAM_TABLE_SIZE


class _PrivateWordCount(WordCountJob):
    def program_key(self):
        return None


@pytest.mark.parametrize("make", [lambda: _PrivateWordCount(CFG),
                                  lambda: GrepJob(b"w1")],
                         ids=["wordcount_without_key", "grep"])
def test_a_job_without_a_program_key_builds_in_every_run(corpus_path, make):
    path, _ = corpus_path
    for _ in range(2):
        with counted() as d:
            executor.run_job(make(), path, CFG, mesh=data_mesh(2))
        assert d.builds == {"mr_step": 1, "mr_finish": 1} and not d.reuses
    assert not mr._PROGRAMS


def test_the_degradation_ladder_builds_and_never_gets_the_undegraded_program(
        corpus_path, engines, monkeypatch):
    """A resource storm that clears only once the ladder reverts the
    geometry: the rebuilt Engine builds its step program anew, and a second
    run gets both programs from the table, each under its own config."""
    path, corpus = corpus_path
    real_step = mr.Engine.step

    def storming(self, state, chunks, step_index):
        if self.job.config.geometry is not None and int(step_index) >= 2:
            raise RuntimeError("RESOURCE_EXHAUSTED: injected VMEM storm")
        return real_step(self, state, chunks, step_index)

    monkeypatch.setattr(mr.Engine, "step", storming)
    cfg = Config(chunk_bytes=512, table_capacity=2048, geometry="tall512",
                 failure_policy={"resource_retries": 1,
                                 "transient_retries": 1,
                                 "backoff_base_s": 0.0, "jitter_frac": 0.0,
                                 "degrade": True})
    shape = ("mr_step", (2, CFG.chunk_bytes))
    for run in range(2):
        with counted() as d:
            rr = executor.run_job(WordCountJob(cfg), path, cfg,
                                  mesh=data_mesh(2))
        assert rr.pipeline.get("degrade_steps") == ["revert-geometry"]
        assert rr.metrics.words_counted == oracle.total_count(corpus)
        if run == 0:
            assert d.builds == {"mr_step": 2, "mr_finish": 1}
            assert not d.reuses
        else:
            assert d.reuses == {"mr_step": 2, "mr_finish": 1}
            assert not d.builds
    undegraded, degraded = (programs[shape] for _, programs in engines[:2])
    assert degraded is not undegraded
    assert [programs[shape] for _, programs in engines[2:]] == \
        [undegraded, degraded]
