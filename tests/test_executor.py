"""End-to-end streaming executor tests: count_file, checkpoint/resume, metrics."""

import os

import numpy as np
import pytest

from mapreduce_tpu.config import Config
from mapreduce_tpu.models.wordcount import WordCountJob
from mapreduce_tpu.parallel.mesh import data_mesh
from mapreduce_tpu.runtime import checkpoint as ckpt
from mapreduce_tpu.runtime import executor
from mapreduce_tpu.utils import oracle
from tests.conftest import make_corpus

CFG = Config(chunk_bytes=512, table_capacity=2048)


def _write(tmp_path, data: bytes):
    p = tmp_path / "corpus.txt"
    p.write_bytes(data)
    return str(p)


def test_count_file_matches_oracle(tmp_path, rng):
    corpus = make_corpus(rng, 4000, 250)
    path = _write(tmp_path, corpus)
    result = executor.count_file(path, CFG, mesh=data_mesh(8))
    assert result.as_dict() == oracle.word_counts(corpus)
    assert result.total == oracle.total_count(corpus)


def test_count_file_insertion_order(tmp_path):
    data = b"pear apple pear cherry apple pear\n"
    path = _write(tmp_path, data)
    result = executor.count_file(path, CFG, mesh=data_mesh(2))
    assert result.words == [b"pear", b"apple", b"cherry"]
    assert result.counts == [3, 2, 1]


@pytest.mark.slow
def test_count_file_top_k(tmp_path, rng):
    corpus = make_corpus(rng, 3000, 150)
    path = _write(tmp_path, corpus)
    result = executor.count_file(path, CFG, mesh=data_mesh(4), top_k=5)
    expected = sorted(oracle.word_counts(corpus).values(), reverse=True)[:5]
    assert result.counts == expected


def test_run_metrics(tmp_path, rng):
    corpus = make_corpus(rng, 2000, 100)
    path = _write(tmp_path, corpus)
    rr = executor.run_job(WordCountJob(CFG), path, CFG, mesh=data_mesh(4))
    assert rr.metrics.bytes_processed == len(corpus)
    assert rr.metrics.words_counted == oracle.total_count(corpus)
    assert rr.metrics.elapsed_s > 0 and rr.metrics.gb_per_s > 0
    assert "stream" in rr.metrics.phases and "reduce" in rr.metrics.phases


@pytest.mark.slow
def test_run_metrics_unwrap_topk_and_sketch(tmp_path, rng):
    """words_counted must survive every finalize result shape: the TopKTable
    wrapper (and its nesting inside sketch states) carries the table one
    level down — metrics reporting 0 there is a silent regression."""
    from mapreduce_tpu.models.wordcount import (SketchedWordCountJob,
                                                TopKWordCountJob)

    corpus = make_corpus(rng, 2000, 100)
    path = _write(tmp_path, corpus)
    total = oracle.total_count(corpus)
    rr = executor.run_job(TopKWordCountJob(5, CFG), path, CFG, mesh=data_mesh(4))
    assert rr.metrics.words_counted == total
    rr = executor.run_job(SketchedWordCountJob(TopKWordCountJob(5, CFG)),
                          path, CFG, mesh=data_mesh(4))
    assert rr.metrics.words_counted == total


@pytest.mark.slow
def test_checkpoint_resume_same_result(tmp_path, rng):
    """Kill-and-resume produces the identical count multiset (SURVEY §5)."""
    corpus = make_corpus(rng, 5000, 200)
    path = _write(tmp_path, corpus)
    mesh = data_mesh(4)
    ck = str(tmp_path / "state.npz")

    # Full run, no checkpointing: the golden answer.
    full = executor.count_file(path, CFG, mesh=mesh)

    # Run with checkpointing every step, then simulate a crash by reloading
    # from the last checkpoint and re-running.
    executor.count_file(path, CFG, mesh=mesh, checkpoint_path=ck, checkpoint_every=1)
    assert ckpt.exists(ck)
    state, step, offset, bases, _ = ckpt.load(ck)
    assert step > 1 and 0 < offset <= len(corpus)

    resumed = executor.count_file(path, CFG, mesh=mesh, checkpoint_path=ck,
                                  checkpoint_every=1)
    assert resumed.as_dict() == full.as_dict()
    assert resumed.total == full.total


def test_checkpoint_mismatch_rejected(tmp_path, rng):
    """Resuming against a replaced input file must fail loudly, not corrupt."""
    corpus = make_corpus(rng, 3000, 100)
    path = _write(tmp_path, corpus)
    ck = str(tmp_path / "state.npz")
    small = Config(chunk_bytes=256, table_capacity=1024)
    executor.count_file(path, small, mesh=data_mesh(2), checkpoint_path=ck,
                        checkpoint_every=1)
    # Replace the input: same path, different content.
    (tmp_path / "corpus.txt").write_bytes(make_corpus(rng, 3000, 100))
    with pytest.raises(ckpt.CheckpointMismatch):
        executor.count_file(path, small, mesh=data_mesh(2), checkpoint_path=ck,
                            checkpoint_every=1)
    # Different device count is also rejected.
    with pytest.raises(ckpt.CheckpointMismatch):
        executor.count_file(path, small, mesh=data_mesh(4), checkpoint_path=ck,
                            checkpoint_every=1)


def test_checkpoint_capacity_mismatch_rejected(tmp_path, rng):
    """Resuming with a different table_capacity would silently spill entries."""
    corpus = make_corpus(rng, 3000, 100)
    path = _write(tmp_path, corpus)
    ck = str(tmp_path / "state.npz")
    executor.count_file(path, Config(chunk_bytes=256, table_capacity=2048),
                        mesh=data_mesh(2), checkpoint_path=ck, checkpoint_every=1)
    with pytest.raises(ckpt.CheckpointMismatch):
        executor.count_file(path, Config(chunk_bytes=256, table_capacity=1024),
                            mesh=data_mesh(2), checkpoint_path=ck, checkpoint_every=1)


@pytest.mark.slow
def test_stream_and_single_buffer_top_k_agree(tmp_path):
    """Device-side and host-side top-k must break count ties identically
    (by first occurrence), so --stream --top-k and --top-k match."""
    # Five words, counts 3,2,2,2,1: the k=2 boundary lands inside the tie.
    data = b"aa bb aa cc dd aa bb cc dd bb cc dd ee\n" * 3
    path = _write(tmp_path, data)
    streamed = executor.count_file(path, CFG, mesh=data_mesh(2), top_k=2)

    from mapreduce_tpu.models.wordcount import apply_top_k, count_words

    single = apply_top_k(count_words(data), 2)
    assert streamed.words == single.words
    assert streamed.counts == single.counts


@pytest.mark.slow
def test_stream_top_k_total_is_exact(tmp_path, rng):
    """--stream --top-k must report the full token total, not the top-k sum."""
    corpus = make_corpus(rng, 2000, 120)
    path = _write(tmp_path, corpus)
    result = executor.count_file(path, CFG, mesh=data_mesh(2), top_k=3)
    assert result.total == oracle.total_count(corpus)
    assert result.distinct == len(oracle.word_counts(corpus))
    assert len(result.words) == 3


def test_checkpoint_roundtrip(tmp_path):
    from mapreduce_tpu.ops import table as tbl

    t = tbl.empty(16)
    import jax

    stacked = jax.tree.map(lambda x: np.broadcast_to(np.asarray(x)[None], (4,) + x.shape), t)
    p = str(tmp_path / "ck.npz")
    ckpt.save(p, stacked, step=3, offset=12345, bases=np.zeros((3, 4), np.int64))
    s2, step, offset, bases, _ = ckpt.load(p, template=stacked)
    assert step == 3 and offset == 12345 and bases.shape == (3, 4)
    for f in t._fields:
        np.testing.assert_array_equal(np.asarray(getattr(stacked, f)),
                                      np.asarray(getattr(s2, f)))


@pytest.mark.slow
def test_stream_superstep_matches_single_step(tmp_path, rng):
    """config.superstep>1 (scan-fused dispatches + remainder single steps)
    must produce the identical result and checkpoint-compatible bases."""
    from tests.conftest import make_corpus

    corpus = make_corpus(rng, n_words=4000, vocab=200)
    path = tmp_path / "c.txt"
    path.write_bytes(corpus)
    base = dict(table_capacity=1 << 10, chunk_bytes=512)
    r1 = executor.count_file(str(path), config=Config(**base))
    r3 = executor.count_file(str(path), config=Config(**base, superstep=3))
    assert r1.as_dict() == r3.as_dict()
    assert r1.words == r3.words and r1.total == r3.total


@pytest.mark.slow
def test_sketched_checkpoint_resume(tmp_path, rng):
    """Sketched runs checkpoint (table + HLL registers as extras) and resume
    to the same result; resuming across sketched/unsketched is rejected."""
    corpus = make_corpus(rng, n_words=4000, vocab=600)
    path = tmp_path / "c.txt"
    path.write_bytes(corpus)
    cfg = Config(chunk_bytes=512, table_capacity=256)
    ck = str(tmp_path / "ck.npz")

    full = executor.count_file(str(path), config=cfg, distinct_sketch=True)
    # Run with frequent checkpoints; then resume from the on-disk snapshot.
    r1 = executor.count_file(str(path), config=cfg, distinct_sketch=True,
                             checkpoint_path=ck, checkpoint_every=2)
    assert ckpt.exists(ck)  # sketched state DID snapshot
    r2 = executor.count_file(str(path), config=cfg, distinct_sketch=True,
                             checkpoint_path=ck, checkpoint_every=2)
    assert r1.as_dict() == full.as_dict() == r2.as_dict()
    assert r2.distinct_estimate == pytest.approx(r1.distinct_estimate)

    with pytest.raises(ckpt.CheckpointMismatch, match="sketch"):
        executor.count_file(str(path), config=cfg, distinct_sketch=False,
                            checkpoint_path=ck, checkpoint_every=2)


@pytest.mark.slow
def test_multi_file_corpus_counts_and_recovery(tmp_path, rng):
    """Three files streamed as one corpus: counts equal the concatenation's
    oracle, words recover exactly, checkpoints resume across file seams."""
    blobs = [make_corpus(rng, n_words=1500, vocab=120) for _ in range(3)]
    paths = []
    for i, blob in enumerate(blobs):
        p = tmp_path / f"shard{i}.txt"
        p.write_bytes(blob)
        paths.append(str(p))
    expected = {}
    for blob in blobs:  # files are independent streams
        for w, c in oracle.word_counts(blob).items():
            expected[w] = expected.get(w, 0) + c

    cfg = Config(chunk_bytes=512, table_capacity=1024)
    r = executor.count_file(paths, config=cfg)
    assert {w: c for w, c in zip(r.words, r.counts)} == expected
    assert r.total == sum(expected.values())

    ck = str(tmp_path / "ck.npz")
    r2 = executor.count_file(paths, config=cfg, checkpoint_path=ck,
                             checkpoint_every=2)
    assert ckpt.exists(ck)
    r3 = executor.count_file(paths, config=cfg, checkpoint_path=ck,
                             checkpoint_every=2)  # resumes mid-corpus
    assert r2.as_dict() == r.as_dict() == r3.as_dict()


def test_step_failure_is_surfaced_with_resume_cursor(tmp_path, rng):
    """Failure detection (SURVEY §5): a failing step logs the resume cursor
    loudly and re-raises — never a silent partial result."""
    import logging

    corpus = make_corpus(rng, 2000, 100)
    path = _write(tmp_path, corpus)
    mesh = data_mesh(2)
    job = WordCountJob(CFG)

    class FailingEngine(executor.Engine):
        def step(self, state, chunks, step_index):
            if step_index >= 2:
                raise RuntimeError("injected device fault")
            return super().step(state, chunks, step_index)

    records: list[logging.LogRecord] = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    logger = logging.getLogger("mapreduce_tpu")  # propagate=False: attach
    handler = Capture()
    logger.addHandler(handler)
    real_engine = executor.Engine
    executor.Engine = FailingEngine
    try:
        with pytest.raises(RuntimeError, match="injected device fault"):
            executor.run_job(job, path, config=CFG, mesh=mesh)
    finally:
        executor.Engine = real_engine
        logger.removeHandler(handler)
    failed = [r for r in records if "step failed" in r.getMessage()]
    assert failed, "the failure must be logged before re-raising"
    fields = getattr(failed[0], "fields", {})
    assert fields.get("step") == 2  # the resume cursor names the failed step
    assert "resume_hint" in fields


def test_checkpoint_legacy_format_named_cause(tmp_path):
    """A pre-versioning (field-named leaves) snapshot must be rejected with
    the real cause named, not a misleading '0 leaves' structure error."""
    p = str(tmp_path / "legacy.npz")
    np.savez(p, keys=np.zeros(4, np.uint32), counts=np.zeros(4, np.uint32),
             __step=np.int64(1), __offset=np.int64(0),
             __bases=np.zeros((1, 1), np.int64))
    with pytest.raises(ckpt.CheckpointMismatch, match="older version"):
        ckpt.load(p, template={"k": np.zeros(4, np.uint32)})


def test_checkpoint_future_format_rejected(tmp_path):
    import json as _json

    p = str(tmp_path / "future.npz")
    meta = np.frombuffer(_json.dumps({"format": 99}).encode(), dtype=np.uint8)
    np.savez(p, __leaf_0=np.zeros(4, np.uint32), __step=np.int64(0),
             __offset=np.int64(0), __bases=np.zeros((0, 1), np.int64),
             __meta=meta)
    with pytest.raises(ckpt.CheckpointMismatch, match="newer version"):
        ckpt.load(p, template={"k": np.zeros(4, np.uint32)})


@pytest.mark.slow
def test_step_retry_recovers_transient_failure(tmp_path, rng, monkeypatch):
    """round-1 review #5 'done' case: an injected one-shot step failure recovers
    via the in-memory known-good snapshot, without a checkpoint file, and
    produces exact counts."""
    from mapreduce_tpu.parallel.mapreduce import Engine

    corpus = make_corpus(rng, n_words=3000, vocab=120)
    path = tmp_path / "c.txt"
    path.write_bytes(corpus)

    fired = set()  # one-shot per step: the retry of step 2 must succeed
    original = Engine.step

    def flaky(self, state, chunks, step_index):
        if step_index in (2, 5) and step_index not in fired:
            fired.add(step_index)
            raise RuntimeError("injected transient device failure")
        return original(self, state, chunks, step_index)

    from mapreduce_tpu.parallel import mapreduce as mr
    monkeypatch.setattr(mr.Engine, "step", flaky)

    cfg = Config(chunk_bytes=512, table_capacity=1 << 10)
    result = executor.count_file(str(path), cfg, mesh=data_mesh(2), retry=1)
    assert fired == {2, 5}, "injection never fired; test is vacuous"
    want = oracle.word_counts(corpus)
    assert result.total == oracle.total_count(corpus)
    assert dict(zip(result.words, result.counts)) == want


def test_step_retry_exhausted_surfaces(tmp_path, rng, monkeypatch):
    """A persistent failure still surfaces after the retries run out."""
    corpus = make_corpus(rng, n_words=500, vocab=50)
    path = tmp_path / "c.txt"
    path.write_bytes(corpus)

    def always_fail(self, state, chunks, step_index):
        raise RuntimeError("persistent device failure")

    from mapreduce_tpu.parallel import mapreduce as mr
    monkeypatch.setattr(mr.Engine, "step", always_fail)

    cfg = Config(chunk_bytes=512, table_capacity=1 << 10)
    with pytest.raises(RuntimeError, match="persistent"):
        executor.count_file(str(path), cfg, mesh=data_mesh(2), retry=2)


@pytest.mark.slow
def test_mid_superstep_checkpoint_granularity(tmp_path, rng, monkeypatch):
    """round-1 review #10 'done' case: with checkpoint_every finer than the
    superstep, a kill mid-run resumes from the last per-step checkpoint —
    replaying at most checkpoint_every (=1 here) chunks per device, not a
    whole superstep."""
    from mapreduce_tpu.parallel.mapreduce import Engine

    corpus = make_corpus(rng, n_words=6000, vocab=150)
    path = tmp_path / "c.txt"
    path.write_bytes(corpus)
    ck = str(tmp_path / "ck.npz")
    cfg = Config(chunk_bytes=512, table_capacity=1 << 10, superstep=4)

    dispatched: list[int] = []
    orig_step, orig_many = Engine.step, Engine.step_many
    crash_at = {"step": 6, "armed": True}

    def rec_step(self, state, chunks, step_index):
        if crash_at["armed"] and step_index >= crash_at["step"]:
            raise RuntimeError("injected kill")
        dispatched.append(int(step_index))
        return orig_step(self, state, chunks, step_index)

    def rec_many(self, state, chunks, step_index, repeats=1):
        k = chunks.shape[1]
        if crash_at["armed"] and step_index + k > crash_at["step"]:
            raise RuntimeError("injected kill")
        dispatched.extend(range(int(step_index), int(step_index) + k))
        return orig_many(self, state, chunks, step_index, repeats)

    from mapreduce_tpu.parallel import mapreduce as mr
    monkeypatch.setattr(mr.Engine, "step", rec_step)
    monkeypatch.setattr(mr.Engine, "step_many", rec_many)

    # First run: checkpoint every step (finer than the 4-step superstep),
    # killed at step 6 — i.e. mid-way through the second superstep group.
    with pytest.raises(RuntimeError, match="injected kill"):
        executor.count_file(str(path), cfg, mesh=data_mesh(2),
                            checkpoint_path=ck, checkpoint_every=1)
    assert ckpt.exists(ck)
    completed = max(dispatched) + 1
    assert completed == crash_at["step"]  # steps 0..5 done and checkpointed

    # Resume: must start exactly at the crash step (replay < 1 chunk/device).
    crash_at["armed"] = False
    dispatched.clear()
    result = executor.count_file(str(path), cfg, mesh=data_mesh(2),
                                 checkpoint_path=ck, checkpoint_every=1)
    assert min(dispatched) == crash_at["step"], \
        f"resume replayed from step {min(dispatched)}, not {crash_at['step']}"
    assert result.total == oracle.total_count(corpus)
    assert dict(zip(result.words, result.counts)) == oracle.word_counts(corpus)


def test_ledger_one_record_per_step(tmp_path, rng):
    """ISSUE 2 acceptance: a telemetered run writes >= 1 JSONL step record
    per step, each with the phase decomposition (read_wait/stage/dispatch),
    byte counts, and device memory stats; run_start/run_end bracket them."""
    from mapreduce_tpu import obs

    corpus = make_corpus(rng, 2000, 100)
    path = _write(tmp_path, corpus)
    led = str(tmp_path / "run.jsonl")
    with obs.Telemetry.create(ledger_path=led) as tel:
        rr = executor.run_job(WordCountJob(CFG), path, CFG, mesh=data_mesh(4),
                              telemetry=tel)
    recs = list(obs.read_ledger(led))
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "run_start" and kinds[-1] == "run_end"
    steps = [r for r in recs if r["kind"] == "step"]
    assert steps, "at least one step record"
    # superstep=1: exactly one record per step, contiguous from 0.
    assert [r["step_first"] for r in steps] == list(range(len(steps)))
    assert all(r["steps"] == 1 for r in steps)
    assert sum(r["group_bytes"] for r in steps) == len(corpus)
    assert steps[-1]["cursor_bytes"] == len(corpus)
    for r in steps:
        assert r["phases"].get("dispatch", 0) > 0
        assert r["mem"].get("live_arrays", 0) > 0
        assert r["mem"].get("live_bytes", 0) > 0
    phase_keys = set().union(*(r["phases"] for r in steps))
    assert {"read_wait", "stage", "dispatch"} <= phase_keys
    # The step records decompose (within rounding) the run's stream phases.
    end = recs[-1]
    assert end["bytes"] == rr.metrics.bytes_processed == len(corpus)
    total_dispatch = sum(r["phases"].get("dispatch", 0) for r in steps)
    assert total_dispatch == pytest.approx(rr.metrics.phases["dispatch"],
                                           rel=0.05)


def test_flight_dump_on_injected_step_failure(tmp_path, rng, monkeypatch):
    """ISSUE 2 acceptance: an injected step failure leaves a flight-recorder
    dump (recent events + context + metrics) and a ledger failure record —
    forensics instead of nothing."""
    import json as _json

    from mapreduce_tpu import obs
    from mapreduce_tpu.parallel import mapreduce as mr

    corpus = make_corpus(rng, 2000, 100)
    path = _write(tmp_path, corpus)
    original = mr.Engine.step

    def failing(self, state, chunks, step_index):
        if step_index >= 2:
            raise RuntimeError("injected device fault")
        return original(self, state, chunks, step_index)

    monkeypatch.setattr(mr.Engine, "step", failing)
    led = str(tmp_path / "run.jsonl")
    with obs.Telemetry.create(ledger_path=led) as tel:
        with pytest.raises(RuntimeError, match="injected device fault"):
            executor.run_job(WordCountJob(CFG), path, CFG, mesh=data_mesh(2),
                             telemetry=tel)
    dump_path = led + ".flight.json"
    assert os.path.exists(dump_path), "failure must leave a flight dump"
    with open(dump_path) as f:
        dump = _json.load(f)
    assert dump["context"]["step"] == 2
    assert "injected device fault" in dump["context"]["error"]
    kinds = [e["kind"] for e in dump["events"]]
    assert "step" in kinds and "step_failed" in kinds
    assert dump["metrics"]["counters"]["executor.steps"] >= 2
    # The ledger names the failure and points at the dump.
    failures = list(obs.read_ledger(led, kind="failure"))
    assert len(failures) == 1 and failures[0]["step"] == 2
    assert failures[0]["flight_dump"] == dump_path
    # No run_end: the crash is visible to obs_report as DID NOT COMPLETE.
    assert not list(obs.read_ledger(led, kind="run_end"))


@pytest.mark.slow
def test_merge_every_batched_equals_pairwise(tmp_path, rng):
    """merge_every=K folds K staged batch tables in one reduce: results must
    equal the K=1 pairwise fold — words, counts, totals, order — including
    an end-of-stream flush of a partial buffer (chunk count not divisible
    by K) and a device-side top-k finalize."""
    corpus = make_corpus(rng, n_words=4000, vocab=200)
    path = _write(tmp_path, corpus)
    base = dict(chunk_bytes=512, table_capacity=1 << 12)
    r1 = executor.count_file(path, Config(**base), mesh=data_mesh(2))
    rk = executor.count_file(path, Config(**base, merge_every=3),
                             mesh=data_mesh(2))
    assert rk.words == r1.words and rk.counts == r1.counts
    assert rk.total == r1.total and rk.distinct == r1.distinct
    assert rk.dropped_count == r1.dropped_count

    t1 = executor.count_file(path, Config(**base), mesh=data_mesh(2), top_k=7)
    tk = executor.count_file(path, Config(**base, merge_every=4),
                             mesh=data_mesh(2), top_k=7)
    assert tk.as_dict() == t1.as_dict()


@pytest.mark.slow
def test_merge_every_under_capacity_pressure(tmp_path):
    """Under table spill the kept keys/counts and dropped_count stay
    identical; the dropped_uniques bound can only TIGHTEN (a respilled key
    counts once per flush, not once per step)."""
    words = [f"z{i:04d}" for i in range(3000)]
    corpus = (" ".join(words) + " " + " ".join(words)).encode()
    path = tmp_path / "c.txt"
    path.write_bytes(corpus)
    base = dict(chunk_bytes=512, table_capacity=256)
    r1 = executor.count_file(str(path), Config(**base), mesh=data_mesh(2))
    rk = executor.count_file(str(path), Config(**base, merge_every=4),
                             mesh=data_mesh(2))
    assert rk.words == r1.words and rk.counts == r1.counts
    assert rk.total == r1.total
    assert rk.dropped_count == r1.dropped_count
    assert rk.dropped_uniques <= r1.dropped_uniques


@pytest.mark.slow
def test_merge_every_checkpoint_resume(tmp_path, rng):
    """The buffered state (pending arrays + cursor) snapshots and resumes
    exactly like any other state pytree."""
    corpus = make_corpus(rng, n_words=3000, vocab=100)
    path = _write(tmp_path, corpus)
    cfg = Config(chunk_bytes=512, table_capacity=1 << 12, merge_every=3)
    mesh = data_mesh(2)
    full = executor.count_file(path, cfg, mesh=mesh)
    ck = str(tmp_path / "ck.npz")
    from mapreduce_tpu.parallel import mapreduce as mr

    original = mr.Engine.step
    fired = []

    def crash_mid(self, state, chunks, step_index):
        if step_index == 4 and not fired:
            fired.append(1)
            raise RuntimeError("injected crash")
        return original(self, state, chunks, step_index)

    import pytest as _pytest

    try:
        mr.Engine.step = crash_mid
        with _pytest.raises(RuntimeError, match="injected"):
            executor.count_file(path, cfg, mesh=mesh, checkpoint_path=ck,
                                checkpoint_every=2)
    finally:
        mr.Engine.step = original
    assert fired, "injection never fired; test is vacuous"
    resumed = executor.count_file(path, cfg, mesh=mesh, checkpoint_path=ck,
                                  checkpoint_every=2)
    assert resumed.as_dict() == full.as_dict()
    assert resumed.total == full.total


# -- ISSUE 5: the bounded in-flight dispatch window ---------------------------


@pytest.mark.smoke
def test_pipelined_window_matches_serial(tmp_path, rng):
    """inflight_groups > 1 must be byte-identical to the serialized window
    (inflight_groups=1, the A/B control): words, counts, order, totals."""
    corpus = make_corpus(rng, 3000, 150)
    path = _write(tmp_path, corpus)
    base = dict(chunk_bytes=512, table_capacity=2048)
    serial = executor.count_file(path, Config(**base, inflight_groups=1),
                                 mesh=data_mesh(4))
    piped = executor.count_file(path, Config(**base, inflight_groups=4),
                                mesh=data_mesh(4))
    assert piped.as_dict() == serial.as_dict() == oracle.word_counts(corpus)
    assert piped.words == serial.words and piped.counts == serial.counts
    assert piped.total == serial.total == oracle.total_count(corpus)


@pytest.mark.smoke
def test_ledger_one_record_per_group_under_pipelining(tmp_path, rng):
    """ISSUE 5 acceptance: with the window active, telemetry still emits
    exactly ONE ledger step record per dispatched group, in step order,
    each carrying the observed in-flight depth; run_end carries the window
    statistics and the overlap fraction."""
    from mapreduce_tpu import obs

    corpus = make_corpus(rng, 2500, 120)
    path = _write(tmp_path, corpus)
    cfg = Config(chunk_bytes=512, table_capacity=2048, inflight_groups=3)
    led = str(tmp_path / "run.jsonl")
    with obs.Telemetry.create(ledger_path=led) as tel:
        rr = executor.run_job(WordCountJob(cfg), path, cfg, mesh=data_mesh(4),
                              telemetry=tel)
    recs = list(obs.read_ledger(led))
    steps = [r for r in recs if r["kind"] == "step"]
    # one record per group, order-preserving and contiguous from step 0
    assert [r["step_first"] for r in steps] == list(range(len(steps)))
    assert sum(r["group_bytes"] for r in steps) == len(corpus)
    for r in steps:
        assert 1 <= r["inflight_depth"] <= 3
    assert max(r["inflight_depth"] for r in steps) > 1, \
        "window never pipelined; the test corpus is too small"
    end = recs[-1]
    assert end["kind"] == "run_end"
    pipe = end["pipeline"]
    assert pipe["inflight_groups"] == 3
    assert pipe["dispatch_groups"] == len(steps)
    assert 1 <= pipe["depth_max"] <= 3
    assert 0.0 <= pipe["overlap_fraction"] <= 1.0
    assert rr.pipeline == pipe


def test_mid_window_async_failure_attributed_and_retried(tmp_path, rng,
                                                         monkeypatch):
    """ISSUE 5 acceptance: a failure that surfaces ASYNCHRONOUSLY at a
    completion token (emulated through the _wait_token seam — the CPU
    backend has no late-surfacing errors) is attributed to the group that
    caused it, not to a neighbor, and the run recovers from the window
    anchor to exact counts."""
    import jax as _jax

    from mapreduce_tpu import obs

    corpus = make_corpus(rng, 4000, 150)
    path = _write(tmp_path, corpus)
    cfg = Config(chunk_bytes=512, table_capacity=2048, inflight_groups=3)

    orig_token = executor._state_token
    made = []

    def tok(state):
        t = orig_token(state)
        made.append(t)
        if len(made) - 1 == 2:  # the step-2 group's token, poisoned once
            return ("poison", t)
        return t

    def wait(t):
        if isinstance(t, tuple) and t[0] == "poison":
            raise RuntimeError("injected async device fault")
        _jax.block_until_ready(t)

    monkeypatch.setattr(executor, "_state_token", tok)
    monkeypatch.setattr(executor, "_wait_token", wait)

    led = str(tmp_path / "run.jsonl")
    with obs.Telemetry.create(ledger_path=led) as tel:
        result = executor.count_file(path, cfg, mesh=data_mesh(2), retry=1,
                                     telemetry=tel)
    assert len(made) > 3, "window never pipelined past the poisoned group"
    # exact results despite the mid-window failure + replay
    assert result.as_dict() == oracle.word_counts(corpus)
    assert result.total == oracle.total_count(corpus)
    # attribution: the retry record names step 2 — the poisoned group —
    # even though the failure surfaced while draining a 3-deep window.
    retries = list(obs.read_ledger(led, kind="retry"))
    assert [r["step"] for r in retries] == [2]
    assert not list(obs.read_ledger(led, kind="failure"))
    # still exactly one step record per dispatched group
    steps = list(obs.read_ledger(led, kind="step"))
    assert [r["step_first"] for r in steps] == list(range(len(steps)))
    assert all(r["inflight_depth"] >= 1 for r in steps)


def test_mid_window_sync_failure_replays_from_anchor(tmp_path, rng,
                                                     monkeypatch):
    """The OTHER recover() entry: a failure raised by the dispatch call
    itself (not a completion token) mid-window.  The failed group was
    never enrolled, so recovery must replay from the anchor with exactly
    that group charged one attempt, account it exactly once (one step
    record per group, in order, inflight_depth >= 1 — the serialized
    replay is depth 1), and stay exact."""
    from mapreduce_tpu import obs
    from mapreduce_tpu.parallel import mapreduce as mr

    corpus = make_corpus(rng, 3000, 120)
    path = _write(tmp_path, corpus)
    cfg = Config(chunk_bytes=512, table_capacity=2048, inflight_groups=3)

    fired = []
    orig_step = mr.Engine.step

    def flaky(self, state, chunks, step_index):
        if step_index == 4 and not fired:
            fired.append(int(step_index))
            raise RuntimeError("injected sync device fault")
        return orig_step(self, state, chunks, step_index)

    monkeypatch.setattr(mr.Engine, "step", flaky)

    led = str(tmp_path / "run.jsonl")
    with obs.Telemetry.create(ledger_path=led) as tel:
        result = executor.count_file(path, cfg, mesh=data_mesh(2), retry=1,
                                     telemetry=tel)
    assert fired == [4], "injection never fired; test is vacuous"
    assert result.as_dict() == oracle.word_counts(corpus)
    assert result.total == oracle.total_count(corpus)
    retries = list(obs.read_ledger(led, kind="retry"))
    assert [r["step"] for r in retries] == [4]
    assert not list(obs.read_ledger(led, kind="failure"))
    steps = list(obs.read_ledger(led, kind="step"))
    assert [r["step_first"] for r in steps] == list(range(len(steps)))
    assert all(r["inflight_depth"] >= 1 for r in steps)
    # Only the recovered group's GROUP record carries the charged attempt
    # (ISSUE 15 satellite: the async path's step record is written at
    # dispatch, before any retry can exist, so the group record is the
    # one carrier both recovery paths charge consistently).
    groups = list(obs.read_ledger(led, kind="group"))
    assert [g["step_first"] for g in groups if g.get("retries")] == [4]
    assert not any(r.get("retries") for r in steps), \
        "step records must not charge replay retries on either path"


def test_window_checkpoint_replay_bounded(tmp_path, rng, monkeypatch):
    """ISSUE 5 acceptance: checkpoint boundaries force window drains, so a
    crash with the window active resumes with at most checkpoint_every
    chunks replayed per device — the window widens throughput, not the
    replay radius."""
    from mapreduce_tpu.parallel import mapreduce as mr

    corpus = make_corpus(rng, 6000, 150)
    path = _write(tmp_path, corpus)
    ck = str(tmp_path / "ck.npz")
    cfg = Config(chunk_bytes=512, table_capacity=1024, inflight_groups=4)
    every = 2

    dispatched: list[int] = []
    orig_step = mr.Engine.step
    crash = {"at": 5, "armed": True}

    def rec_step(self, state, chunks, step_index):
        if crash["armed"] and step_index >= crash["at"]:
            raise RuntimeError("injected kill")
        dispatched.append(int(step_index))
        return orig_step(self, state, chunks, step_index)

    monkeypatch.setattr(mr.Engine, "step", rec_step)

    with pytest.raises(RuntimeError, match="injected kill"):
        executor.count_file(path, cfg, mesh=data_mesh(2),
                            checkpoint_path=ck, checkpoint_every=every)
    assert ckpt.exists(ck)
    _, saved_step, _, _, _ = ckpt.load(ck)
    # the window drained at every boundary: the snapshot is the last
    # boundary at or before the crash step, never further back
    assert saved_step == (crash["at"] // every) * every

    crash["armed"] = False
    dispatched.clear()
    result = executor.count_file(path, cfg, mesh=data_mesh(2),
                                 checkpoint_path=ck, checkpoint_every=every)
    assert min(dispatched) == saved_step
    assert crash["at"] - min(dispatched) <= every, \
        f"resume replayed {crash['at'] - min(dispatched)} steps > {every}"
    assert result.total == oracle.total_count(corpus)
    assert dict(zip(result.words, result.counts)) == oracle.word_counts(corpus)


@pytest.mark.slow
def test_window_ab_identical_across_families(tmp_path, rng):
    """The CPU-proxy A/B of the acceptance criteria: grep, sample, and
    n-gram streamed runs are byte-identical with the window on vs off
    (wordcount is covered in the fast tier)."""
    from mapreduce_tpu.models import grep as grep_mod
    from mapreduce_tpu.models import sample as sample_mod

    corpus = make_corpus(rng, 4000, 150)
    path = _write(tmp_path, corpus)
    base = dict(chunk_bytes=512, table_capacity=2048)
    serial = Config(**base, inflight_groups=1)
    piped = Config(**base, inflight_groups=4)

    g1 = grep_mod.grep_file(path, b"w1", config=serial)
    g4 = grep_mod.grep_file(path, b"w1", config=piped)
    assert (g1.matches, g1.lines) == (g4.matches, g4.lines)

    s1 = sample_mod.sample_file(path, 7, config=serial)
    s4 = sample_mod.sample_file(path, 7, config=piped)
    assert s1.tokens == s4.tokens and s1.total == s4.total

    n1 = executor.count_file(path, serial, mesh=data_mesh(2), ngram=2)
    n4 = executor.count_file(path, piped, mesh=data_mesh(2), ngram=2)
    assert n1.as_dict() == n4.as_dict()
    assert n1.words == n4.words and n1.total == n4.total
