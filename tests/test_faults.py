"""Fault-injection harness + unified failure policy (ISSUE 15).

The chaos matrix: one fast test per named seam (injected fault -> typed
classification -> policy outcome), the taxonomy/backoff/ladder units next
to tools/chaos.py's jax-free selftest, checkpoint-integrity fallback, the
degradation ladder end-to-end, and the chaos-certification byte-identity
contract — a run under a seeded fault plan whose retry budget absorbs the
chaos produces results bit-identical to the fault-free run, and the run's
own ledger replays the identical fault sequence.
"""

import json
import os

import numpy as np
import pytest

from mapreduce_tpu import obs
from mapreduce_tpu.config import Config
from mapreduce_tpu.models.wordcount import WordCountJob
from mapreduce_tpu.parallel.mesh import data_mesh
from mapreduce_tpu.runtime import checkpoint as ckpt
from mapreduce_tpu.runtime import executor, faults
from mapreduce_tpu.utils import oracle
from tests.conftest import make_corpus

CFG = Config(chunk_bytes=512, table_capacity=2048)


def _write(tmp_path, data: bytes):
    p = tmp_path / "corpus.txt"
    p.write_bytes(data)
    return str(p)


def _chaos_cfg(plan: str, **kw) -> Config:
    return Config(chunk_bytes=512, table_capacity=2048, fault_plan=plan,
                  **kw)


# ---------------------------------------------------------------------------
# units: taxonomy / policy / plan / ladder (the chaos-selftest surface,
# re-checked through the real package import)
# ---------------------------------------------------------------------------


@pytest.mark.smoke
def test_classify_taxonomy():
    assert faults.classify(faults.TransientFault("x")) == "transient"
    assert faults.classify(faults.ResourceFault("x")) == "resource"
    assert faults.classify(faults.PermanentFault("x")) == "permanent"
    assert faults.classify(faults.PreemptionFault("x")) == "preemption"
    assert faults.classify(faults.TokenTimeout("hung")) == "transient"
    # Real exceptions: type beats message markers, markers beat the default.
    assert faults.classify(
        RuntimeError("RESOURCE_EXHAUSTED: failed to allocate")) == "resource"
    assert faults.classify(RuntimeError("VMEM limit exceeded")) == "resource"
    assert faults.classify(
        RuntimeError("host preempted: maintenance event")) == "preemption"
    assert faults.classify(KeyboardInterrupt()) == "preemption"
    assert faults.classify(ValueError("bad shape")) == "permanent"
    assert faults.classify(TypeError("no")) == "permanent"
    # A permanent-typed error whose message happens to contain a marker
    # substring ('oom' in 'bloom', 'preempt...') is still a programming
    # error: retrying or walking the ladder re-runs the same bug.
    assert faults.classify(ValueError("bad bloom_bits")) == "permanent"
    assert faults.classify(KeyError("room_id")) == "permanent"
    assert faults.classify(ValueError("preempt_queue empty")) == "permanent"
    # 'oom' counts as a whole word only — 'bloom'/'room'/'zoom' inside a
    # non-permanent-typed message must not charge the resource budget
    # (and walk the ladder); a real 'OOM when allocating' still does.
    assert faults.classify(RuntimeError("bloom filter sync failed")) \
        == "transient"
    assert faults.classify(OSError("no room in zoom buffer")) == "transient"
    assert faults.classify(RuntimeError("OOM when allocating")) == "resource"
    # Unknown -> transient: the legacy retry=N semantics retried ANY
    # exception, and the default policy must keep doing exactly that.
    assert faults.classify(RuntimeError("flaky link")) == "transient"
    assert faults.classify(OSError("read failed")) == "transient"


@pytest.mark.smoke
def test_policy_legacy_mapping_and_validation():
    p = faults.FailurePolicy.resolve(None, retry=3)
    assert p.transient_retries == 3 and p.resource_retries == 3
    assert p.permanent_retries == 0
    assert p.budget("preemption") == 0, "preemption never retries"
    assert p.dispatch_budget == 3
    p0 = faults.FailurePolicy.resolve(None, retry=0)
    assert p0.dispatch_budget == 0
    d = faults.FailurePolicy.resolve({"transient_retries": 2,
                                      "token_timeout_s": 1.5})
    assert d.transient_retries == 2 and d.token_timeout_s == 1.5
    for bad in (dict(transient_retries=-1), dict(backoff_factor=0.5),
                dict(jitter_frac=1.5), dict(token_timeout_s=0)):
        with pytest.raises(ValueError):
            faults.FailurePolicy(**bad)
    with pytest.raises(ValueError, match="failure_policy"):
        faults.FailurePolicy.resolve("not-a-policy")


@pytest.mark.smoke
def test_backoff_hand_values_and_deterministic_jitter():
    p = faults.FailurePolicy(backoff_base_s=0.05, backoff_factor=2.0,
                             backoff_max_s=5.0, jitter_frac=0.0)
    assert [p.backoff_s("transient", a) for a in range(1, 10)] == \
        [0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 3.2, 5.0, 5.0]
    pj = faults.FailurePolicy(backoff_base_s=1.0, backoff_factor=1.0,
                              backoff_max_s=1.0, jitter_frac=0.2, seed=7)
    v = pj.backoff_s("transient", 1, seam="dispatch")
    assert v == pj.backoff_s("transient", 1, seam="dispatch")
    assert 0.8 <= v <= 1.2
    assert v != pj.backoff_s("transient", 1, seam="reader-read")


@pytest.mark.smoke
def test_plan_spec_roundtrip_and_determinism():
    plan = faults.FaultPlan.from_spec(
        "seed=9,rate=0.1,seams=dispatch+token-wait,classes=transient,"
        "max=3,at=checkpoint-save:0:resource")
    rt = faults.FaultPlan.from_spec(plan.spec)
    assert rt.spec == plan.spec and rt.events == plan.events
    # Same seed -> same firing decisions, a different seed differs.
    d1 = [plan.decide("dispatch", i) for i in range(100)]
    d2 = [faults.FaultPlan.from_spec(plan.spec).decide("dispatch", i)
          for i in range(100)]
    assert d1 == d2
    assert plan.decide("checkpoint-save", 0) == "resource", \
        "explicit events fire regardless of rate/seams"
    assert plan.decide("reader-read", 0) is None, \
        "rate only targets the plan's seams"
    for bad in ("", "rate=1.5", "at=dispatch:x:transient", "seams=warp",
                "classes=entropic", "bogus"):
        with pytest.raises(ValueError):
            faults.FaultPlan.from_spec(bad)
    assert faults.FaultPlan.resolve(None) is None, \
        "the zero-cost disabled path must stay None"


@pytest.mark.smoke
def test_degradation_ladder_walks():
    full = {"geometry": "tall512", "combiner": "hot-cache",
            "map_impl": "fused", "sort_impl": "radix"}
    assert faults.ladder_walk(full) == [
        "revert-geometry", "combiner-off", "map-split", "sort-xla"]
    assert faults.next_degrade(
        {"geometry": "default", "combiner": "off", "map_impl": "split",
         "sort_impl": "xla"}) is None


def test_config_fault_surface():
    # fault_plan validates at construction, not mid-stream.
    with pytest.raises(ValueError):
        Config(fault_plan="rate=2.0")
    with pytest.raises(ValueError, match="fault_plan"):
        Config(fault_plan=123)
    # failure_policy: dicts coerce to the frozen dataclass (Config stays
    # hashable — a static jit argument), bad types refuse.
    c = Config(failure_policy={"transient_retries": 2, "degrade": False})
    assert isinstance(c.failure_policy, faults.FailurePolicy)
    assert c.failure_policy.transient_retries == 2
    hash(c)  # must stay hashable with the policy attached
    with pytest.raises(ValueError, match="failure_policy"):
        Config(failure_policy="retry-lots")
    # The valid chaotic config round-trips its spec.
    c2 = Config(fault_plan="seed=3,rate=0.05")
    assert faults.FaultPlan.resolve(c2.fault_plan).seed == 3


# ---------------------------------------------------------------------------
# the chaos matrix: one injected fault per named seam -> typed
# classification -> policy outcome (fast tier; ISSUE 15 satellite)
# ---------------------------------------------------------------------------

#: (seam, crossing index, whether the policy outcome is a retry record).
#: ledger-append is absorbed (observing must never kill the observed
#: run); every other seam retries on the transient budget.
_SEAM_CASES = [
    ("reader-read", 1, True),
    ("stage-acquire", 1, True),
    ("h2d", 1, True),
    ("dispatch", 1, True),
    ("token-wait", 1, True),
    ("checkpoint-save", 0, True),
    ("ledger-append", 1, False),
    ("collective-finish", 0, True),
]


@pytest.mark.parametrize("seam,index,retries", _SEAM_CASES,
                         ids=[c[0] for c in _SEAM_CASES])
def test_seam_injection_classifies_and_recovers(tmp_path, rng, seam,
                                                index, retries):
    """Injected transient fault at one seam: the run records a typed
    `fault` ledger record at that seam, the policy absorbs it (retry, or
    absorption for the telemetry plane), and results stay exact."""
    corpus = make_corpus(rng, 1500, 100)
    path = _write(tmp_path, corpus)
    cfg = _chaos_cfg(f"at={seam}:{index}:transient")
    kw = {}
    if seam == "checkpoint-save":
        kw = dict(checkpoint_path=str(tmp_path / "ck.npz"),
                  checkpoint_every=2)
    led = str(tmp_path / "run.jsonl")
    with obs.Telemetry.create(ledger_path=led) as tel:
        result = executor.count_file(path, cfg, mesh=data_mesh(2),
                                     retry=2, telemetry=tel, **kw)
    assert result.as_dict() == oracle.word_counts(corpus)
    assert result.total == oracle.total_count(corpus)
    fault_recs = list(obs.read_ledger(led, kind="fault"))
    assert len(fault_recs) == 1, fault_recs
    f = fault_recs[0]
    assert f["seam"] == seam and f["fault_class"] == "transient"
    assert f["injected"] is True and f["index"] == index
    assert not list(obs.read_ledger(led, kind="failure"))
    retry_recs = list(obs.read_ledger(led, kind="retry"))
    if retries:
        assert retry_recs, f"seam {seam} must charge a retry"
        assert all(r["fault_class"] == "transient" for r in retry_recs)
    else:
        assert not retry_recs, "an absorbed ledger-append fault is not " \
            "a retry — the step record is simply skipped"
    # run_start names the chaos (ledger v9) with the CANONICAL spec.
    start = next(iter(obs.read_ledger(led, kind="run_start")))
    assert start["fault_plan"] \
        == faults.FaultPlan.from_spec(cfg.fault_plan).spec
    assert start["ledger_version"] == obs.LEDGER_VERSION == 10


def test_permanent_fault_fails_immediately(tmp_path, rng):
    """Permanent class: retrying re-runs the same bug, so the budget is
    never consulted — one attempt, loud failure, classified record."""
    corpus = make_corpus(rng, 1000, 80)
    path = _write(tmp_path, corpus)
    cfg = _chaos_cfg("at=dispatch:1:permanent")
    led = str(tmp_path / "run.jsonl")
    with obs.Telemetry.create(ledger_path=led) as tel:
        with pytest.raises(faults.PermanentFault):
            executor.count_file(path, cfg, mesh=data_mesh(2), retry=3,
                                telemetry=tel)
    fails = list(obs.read_ledger(led, kind="failure"))
    assert len(fails) == 1 and fails[0]["fault_class"] == "permanent"
    assert not list(obs.read_ledger(led, kind="retry")), \
        "a permanent fault must not burn the retry budget"
    assert fails[0].get("flight_dump"), "forensics must still dump"


def test_preemption_drains_checkpoints_and_resumes(tmp_path, rng):
    """Preemption: drain the in-flight window -> checkpoint -> clean exit
    with a resumable cursor (no flight dump, no failure record); a
    relaunch resumes from the snapshot and finishes exactly."""
    corpus = make_corpus(rng, 2500, 120)
    path = _write(tmp_path, corpus)
    ck = str(tmp_path / "ck.npz")
    led = str(tmp_path / "run.jsonl")
    cfg = _chaos_cfg("at=dispatch:3:preemption")
    with obs.Telemetry.create(ledger_path=led) as tel:
        with pytest.raises(faults.Preempted) as ei:
            executor.count_file(path, cfg, mesh=data_mesh(2), retry=1,
                                checkpoint_path=ck, checkpoint_every=50,
                                telemetry=tel)
    pe = ei.value
    assert pe.checkpointed and pe.checkpoint_path == ck
    assert 0 < pe.cursor_bytes < len(corpus)
    assert ckpt.exists(ck), "the preemption drain must leave a snapshot"
    assert not list(obs.read_ledger(led, kind="failure")), \
        "an orderly shutdown is not a failure"
    assert not os.path.exists(led + ".flight.json"), \
        "no flight dump on the preemption path"
    cks = list(obs.read_ledger(led, kind="checkpoint"))
    assert cks and cks[-1].get("preempt") is True
    # Relaunch (no plan) resumes from the cursor and stays exact.
    result = executor.count_file(path, CFG, mesh=data_mesh(2),
                                 checkpoint_path=ck, checkpoint_every=50)
    assert result.as_dict() == oracle.word_counts(corpus)
    assert result.total == oracle.total_count(corpus)


def test_real_preemption_exception_takes_drain_path(tmp_path, rng):
    """A REAL platform preemption arrives as an ordinary RuntimeError
    whose MESSAGE marks it ('maintenance event'), never as an injected
    PreemptionFault — the stream handler must catch it by CLASS and run
    the same drain -> checkpoint -> Preempted orderly exit (regression:
    the handler once caught the PreemptionFault type only, so real
    preemptions fell through to the failure path)."""
    corpus = make_corpus(rng, 2500, 120)
    path = _write(tmp_path, corpus)
    ck = str(tmp_path / "ck.npz")
    led = str(tmp_path / "run.jsonl")
    from mapreduce_tpu.parallel import mapreduce as mr

    orig_step = mr.Engine.step
    fired = []

    def preempting(self, state, chunks, step_index):
        if int(step_index) >= 3 and not fired:
            fired.append(int(step_index))
            raise RuntimeError("host preempted: maintenance event")
        return orig_step(self, state, chunks, step_index)

    mr.Engine.step = preempting
    try:
        with obs.Telemetry.create(ledger_path=led) as tel:
            with pytest.raises(faults.Preempted) as ei:
                executor.count_file(path, CFG, mesh=data_mesh(2), retry=1,
                                    checkpoint_path=ck, checkpoint_every=50,
                                    telemetry=tel)
    finally:
        mr.Engine.step = orig_step
    assert fired, "the preemption never fired; test is vacuous"
    pe = ei.value
    assert pe.checkpointed and ckpt.exists(ck)
    assert not list(obs.read_ledger(led, kind="failure")), \
        "an orderly shutdown is not a failure"
    assert not os.path.exists(led + ".flight.json"), \
        "no flight dump on the preemption path"
    # Relaunch resumes from the snapshot and finishes exactly.
    result = executor.count_file(path, CFG, mesh=data_mesh(2),
                                 checkpoint_path=ck, checkpoint_every=50)
    assert result.as_dict() == oracle.word_counts(corpus)
    assert result.total == oracle.total_count(corpus)


def test_token_timeout_reads_as_typed_fault(tmp_path, rng, monkeypatch):
    """A hung completion-token wait past token_timeout_s raises a typed
    TokenTimeout (transient) instead of stalling forever; the replay path
    recovers and the run stays exact."""
    corpus = make_corpus(rng, 1500, 100)
    path = _write(tmp_path, corpus)

    import time as _time

    orig_wait = executor._wait_token
    hung = []

    def slow_wait(token):
        if not hung:  # first wait hangs well past the deadline
            hung.append(True)
            _time.sleep(2.0)
        return orig_wait(token)

    monkeypatch.setattr(executor, "_wait_token", slow_wait)
    cfg = Config(chunk_bytes=512, table_capacity=2048,
                 failure_policy={"transient_retries": 2,
                                 "token_timeout_s": 0.2,
                                 "backoff_base_s": 0.0,
                                 "jitter_frac": 0.0})
    led = str(tmp_path / "run.jsonl")
    with obs.Telemetry.create(ledger_path=led) as tel:
        result = executor.count_file(path, cfg, mesh=data_mesh(2),
                                     telemetry=tel)
    assert hung, "the hang never fired; test is vacuous"
    assert result.as_dict() == oracle.word_counts(corpus)
    faults_recs = list(obs.read_ledger(led, kind="fault"))
    assert any(f["seam"] == "token-wait" and not f["injected"]
               and f["fault_class"] == "transient" for f in faults_recs), \
        faults_recs
    assert not list(obs.read_ledger(led, kind="failure"))


def test_retries_by_class_lands_in_registry(tmp_path, rng):
    """ISSUE 15 satellite: per-class retry accounting is a first-class
    registry metric."""
    corpus = make_corpus(rng, 1200, 80)
    path = _write(tmp_path, corpus)
    reg = obs.get_registry()
    before = reg.snapshot()["counters"].get(
        "executor.retries_by_class{fault_class=transient}", 0)
    cfg = _chaos_cfg("at=dispatch:1:transient")
    with obs.Telemetry.create() as tel:
        executor.count_file(path, cfg, mesh=data_mesh(2), retry=2,
                            telemetry=tel)
    after = reg.snapshot()["counters"].get(
        "executor.retries_by_class{fault_class=transient}", 0)
    assert after == before + 1, (before, after)


# ---------------------------------------------------------------------------
# degradation ladder (tentpole (3))
# ---------------------------------------------------------------------------


def test_degradation_ladder_steps_down_and_stays_exact(tmp_path, rng,
                                                       monkeypatch):
    """A persistent resource-classed failure exhausts its budget and
    steps the ladder: revert-geometry rebuilds the engine on the default
    geometry (the xla path carries the label without compiling it — the
    cheapest real ladder step to drive on CPU), a `degrade` ledger
    record lands, and the replay finishes EXACTLY."""
    from mapreduce_tpu.parallel import mapreduce as mr

    corpus = make_corpus(rng, 2000, 100)
    path = _write(tmp_path, corpus)
    orig_step = mr.Engine.step
    fired = []

    def storming(self, state, chunks, step_index):
        # A VMEM storm that only clears once the ladder reverts the
        # geometry: the job's config is the ladder's moving target.
        if self.job.config.geometry is not None and int(step_index) >= 2:
            fired.append(int(step_index))
            raise RuntimeError("RESOURCE_EXHAUSTED: injected VMEM storm")
        return orig_step(self, state, chunks, step_index)

    monkeypatch.setattr(mr.Engine, "step", storming)
    cfg = Config(chunk_bytes=512, table_capacity=2048, geometry="tall512",
                 failure_policy={"resource_retries": 1,
                                 "transient_retries": 1,
                                 "backoff_base_s": 0.0, "jitter_frac": 0.0,
                                 "degrade": True})
    led = str(tmp_path / "run.jsonl")
    with obs.Telemetry.create(ledger_path=led) as tel:
        rr = executor.run_job(WordCountJob(cfg), path, cfg,
                              mesh=data_mesh(2), telemetry=tel)
    assert fired, "the storm never fired; test is vacuous"
    assert rr.metrics.words_counted == oracle.total_count(corpus)
    assert rr.pipeline.get("degrade_steps") == ["revert-geometry"]
    degs = list(obs.read_ledger(led, kind="degrade"))
    assert len(degs) == 1, degs
    assert degs[0]["ladder_step"] == "revert-geometry"
    assert degs[0]["field"] == "geometry"
    assert degs[0]["from"] == "tall512" and degs[0]["to"] == "default"
    assert degs[0]["fault_class"] == "resource"
    assert not list(obs.read_ledger(led, kind="failure")), \
        "a degraded run is alive, not failed"
    snap = obs.get_registry().snapshot()["counters"]
    assert snap.get(
        "executor.degrade_steps{ladder_step=revert-geometry}", 0) >= 1


def test_ladder_exhausted_fails_with_resource_class(tmp_path, rng,
                                                    monkeypatch):
    """With every ladder knob already at its floor, a persistent
    resource failure surfaces as a failure record classified
    `resource` — the honest outcome when there is nothing left to give
    up."""
    from mapreduce_tpu.parallel import mapreduce as mr

    corpus = make_corpus(rng, 1000, 80)
    path = _write(tmp_path, corpus)
    orig_step = mr.Engine.step

    def storming(self, state, chunks, step_index):
        if int(step_index) >= 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: persistent OOM")
        return orig_step(self, state, chunks, step_index)

    monkeypatch.setattr(mr.Engine, "step", storming)
    cfg = Config(chunk_bytes=512, table_capacity=2048,
                 failure_policy={"resource_retries": 1,
                                 "backoff_base_s": 0.0, "jitter_frac": 0.0,
                                 "degrade": True})
    led = str(tmp_path / "run.jsonl")
    with obs.Telemetry.create(ledger_path=led) as tel:
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            executor.count_file(path, cfg, mesh=data_mesh(2),
                                telemetry=tel)
    fails = list(obs.read_ledger(led, kind="failure"))
    assert len(fails) == 1 and fails[0]["fault_class"] == "resource"
    assert not list(obs.read_ledger(led, kind="degrade")), \
        "the default config has no ladder step to take"


# ---------------------------------------------------------------------------
# checkpoint integrity (ISSUE 15 satellite)
# ---------------------------------------------------------------------------


def _mini_state():
    return {"a": np.arange(8, dtype=np.int64).reshape(2, 4),
            "b": np.ones((2, 3), np.float32)}


def test_checkpoint_checksum_roundtrip(tmp_path):
    path = str(tmp_path / "ck.npz")
    ckpt.save(path, _mini_state(), 3, 4096, np.zeros((3, 2), np.int64))
    assert ckpt.verify(path) is True
    assert os.path.exists(ckpt.integrity_path(path))
    (state, step, offset, bases, fi) = ckpt.load_verified(path)
    assert step == 3 and offset == 4096
    # A flipped byte fails the checksum and load_verified names it.
    with open(path, "r+b") as f:
        f.seek(100)
        b = f.read(1)
        f.seek(100)
        f.write(bytes([b[0] ^ 0xFF]))
    assert ckpt.verify(path) is False
    with pytest.raises(ckpt.CheckpointCorrupt):
        ckpt.load_verified(path)
    # No sidecar (a pre-integrity snapshot): verify is unknown (None) and
    # a parseable file still loads.
    os.unlink(ckpt.integrity_path(path))
    assert ckpt.verify(path) is None


def test_corrupt_checkpoint_falls_back_to_previous(tmp_path):
    path = str(tmp_path / "ck.npz")
    ckpt.save(path, _mini_state(), 1, 1024, np.zeros((1, 2), np.int64))
    ckpt.save(path, _mini_state(), 2, 2048, np.zeros((2, 2), np.int64))
    assert os.path.exists(ckpt.previous_path(path)), \
        "the second save must rotate the first aside as .prev"
    assert ckpt.verify(ckpt.previous_path(path)) is True
    # Tear the live snapshot; the resilient load returns the previous
    # good one and NAMES the fallback.
    with open(path, "wb") as f:
        f.write(b"torn mid-save")
    (_, step, offset, _, _), fb = ckpt.load_resilient(path)
    assert step == 1 and offset == 1024
    assert fb is not None and fb["corrupt"] == path
    assert fb["loaded"] == ckpt.previous_path(path)
    # Both torn -> CheckpointCorrupt (the caller chooses restart).
    with open(ckpt.previous_path(path), "wb") as f:
        f.write(b"also torn")
    with pytest.raises(ckpt.CheckpointCorrupt):
        ckpt.load_resilient(path)


def test_missing_live_snapshot_resumes_from_previous(tmp_path):
    """A kill inside save()'s rename-fallback rotation can leave `path`
    absent with a good `.prev`: the resume gate must still say yes and
    the resilient load must come back from `.prev` — not restart the
    stream from byte 0."""
    path = str(tmp_path / "ck.npz")
    ckpt.save(path, _mini_state(), 1, 1024, np.zeros((1, 2), np.int64))
    ckpt.save(path, _mini_state(), 2, 2048, np.zeros((2, 2), np.int64))
    os.unlink(path)
    os.unlink(ckpt.integrity_path(path))
    assert ckpt.exists(path), \
        "a good .prev alone must still gate resume on"
    (_, step, offset, _, _), fb = ckpt.load_resilient(path)
    assert step == 1 and offset == 1024
    assert fb is not None and fb["loaded"] == ckpt.previous_path(path)


def test_resume_from_corrupt_checkpoint_e2e(tmp_path, rng):
    """A torn live snapshot at resume falls back to the previous good one
    (ledger `fault` note at seam checkpoint-load) and the resumed run
    stays exact — the relaunch just replays a little more stream."""
    corpus = make_corpus(rng, 3000, 120)
    path = _write(tmp_path, corpus)
    ck = str(tmp_path / "ck.npz")
    from mapreduce_tpu.parallel import mapreduce as mr

    # First run crashes partway (the test_executor crash idiom) after at
    # least two checkpoints exist, so .prev is populated.
    orig_step = mr.Engine.step
    crashed = []

    def crashing(self, state, chunks, step_index):
        if int(step_index) >= 8 and not crashed:
            crashed.append(int(step_index))
            raise RuntimeError("injected crash")
        return orig_step(self, state, chunks, step_index)

    mr.Engine.step = crashing
    try:
        with pytest.raises(RuntimeError, match="injected crash"):
            executor.count_file(path, CFG, mesh=data_mesh(2),
                                checkpoint_path=ck, checkpoint_every=2)
    finally:
        mr.Engine.step = orig_step
    assert crashed and os.path.exists(ckpt.previous_path(ck))
    # Tear the live snapshot.
    with open(ck, "wb") as f:
        f.write(b"torn by a crash mid-save")
    led = str(tmp_path / "resume.jsonl")
    with obs.Telemetry.create(ledger_path=led) as tel:
        result = executor.count_file(path, CFG, mesh=data_mesh(2),
                                     checkpoint_path=ck,
                                     checkpoint_every=2, telemetry=tel)
    assert result.as_dict() == oracle.word_counts(corpus)
    assert result.total == oracle.total_count(corpus)
    notes = [f for f in obs.read_ledger(led, kind="fault")
             if f.get("seam") == "checkpoint-load"]
    assert len(notes) == 1 and notes[0]["injected"] is False, notes
    assert notes[0]["fallback"] == ckpt.previous_path(ck)


# ---------------------------------------------------------------------------
# chaos certification (tentpole (4)): byte-identity + ledger replay
# ---------------------------------------------------------------------------

#: The fast certification trio: a mid-window async fault, a
#: checkpoint-save failure (budget exhausted -> degrade to unsaved), and
#: a seeded random plan.  The @slow sweep extends to >= 8 plans covering
#: every seam.
_FAST_PLANS = [
    "at=token-wait:1:transient,at=token-wait:2:transient",
    "at=checkpoint-save:0:transient,at=checkpoint-save:1:transient,"
    "at=checkpoint-save:2:transient",
    "seed=3,rate=0.08,classes=transient",
]

_SLOW_PLANS = _FAST_PLANS + [
    "seed=1,rate=0.05",
    "seed=2,rate=0.15,classes=transient",
    "at=reader-read:1:transient,at=reader-read:3:transient",
    "at=dispatch:0:transient,at=h2d:2:transient,"
    "at=stage-acquire:1:transient",
    "at=ledger-append:0:transient,at=collective-finish:0:transient",
    "seed=9,rate=0.3,seams=dispatch+token-wait,max=5",
]


def _certify(tmp_path, corpus, plans, inflight=3):
    """Each plan's run must be bit-identical to the fault-free run."""
    path = _write(tmp_path, corpus)
    base_cfg = Config(chunk_bytes=512, table_capacity=2048,
                      inflight_groups=inflight)
    ck = str(tmp_path / "base_ck.npz")
    base = executor.count_file(path, base_cfg, mesh=data_mesh(2), retry=3,
                               checkpoint_path=ck, checkpoint_every=3)
    os.unlink(ck)
    for i, plan in enumerate(plans):
        cfg = Config(chunk_bytes=512, table_capacity=2048,
                     inflight_groups=inflight, fault_plan=plan)
        ckp = str(tmp_path / f"ck_{i}.npz")
        chaos = executor.count_file(path, cfg, mesh=data_mesh(2), retry=3,
                                    checkpoint_path=ckp,
                                    checkpoint_every=3)
        assert chaos.as_dict() == base.as_dict(), f"plan {plan!r} diverged"
        assert chaos.total == base.total
        assert chaos.words == base.words and chaos.counts == base.counts
        assert chaos.distinct == base.distinct
    return base


@pytest.mark.slow
def test_chaos_byte_identity_trio(tmp_path, rng):
    """The three headline plans (mid-window async, checkpoint-save
    failure, seeded random) against one shared baseline.  @slow per the
    >=10s line — the fast tier keeps per-seam EXACTNESS through the
    seam-matrix tests above, which assert oracle equality under every
    injected fault."""
    corpus = make_corpus(rng, 1500, 100)
    _certify(tmp_path, corpus, _FAST_PLANS)


@pytest.mark.slow
def test_chaos_certification_eight_plans(tmp_path, rng):
    """ISSUE 15 acceptance: >= 8 distinct seeded fault plans — covering
    every injectable seam, incl. a mid-window async fault and a
    checkpoint-save failure — each bit-identical to the fault-free run."""
    assert len(_SLOW_PLANS) >= 8
    covered = set()
    for plan in _SLOW_PLANS:
        p = faults.FaultPlan.from_spec(plan)
        covered.update(p.seams if p.rate else ())
        covered.update(s for (s, _) in p.events)
    assert covered >= {s for s in faults.SEAMS
                       if s not in ("process-kill", "checkpoint-load")}, \
        covered
    corpus = make_corpus(rng, 2500, 150)
    _certify(tmp_path, corpus, _SLOW_PLANS)


@pytest.mark.slow
def test_chaos_midstream_partial_merge_identity(tmp_path, rng):
    """ISSUE 20: the collective-finish seam fires on window-boundary
    PARTIAL merges too (plan grammar unchanged).  Plans whose collective
    faults land mid-stream — on partial-merge crossings, not just the
    end-of-stream finish — must replay to counts bit-identical to the
    overlap-OFF fault-free baseline."""
    corpus = make_corpus(rng, 2000, 120)
    path = _write(tmp_path, corpus)
    base = executor.count_file(path, Config(chunk_bytes=512,
                                            table_capacity=2048,
                                            inflight_groups=2),
                               mesh=data_mesh(2))
    plans = [
        # Crossing 0 is the FIRST partial (the finish is the last
        # crossing), so both faults land mid-stream by construction.
        "at=collective-finish:0:transient,at=collective-finish:2:transient",
        "seed=11,rate=0.5,seams=collective-finish,max=4",
    ]
    for i, plan in enumerate(plans):
        # Overlap disarms window replay, so the collective retries need
        # an EXPLICIT policy (the legacy retry counter would raise);
        # budget 4 covers the seeded plan's max=4 consecutive fires.
        cfg = Config(chunk_bytes=512, table_capacity=2048,
                     inflight_groups=2, merge_overlap=True,
                     fault_plan=plan,
                     failure_policy={"transient_retries": 4})
        led = str(tmp_path / f"ov_{i}.jsonl")
        with obs.Telemetry.create(ledger_path=led) as tel:
            chaos = executor.count_file(path, cfg, mesh=data_mesh(2),
                                        retry=0, telemetry=tel)
        assert chaos.as_dict() == base.as_dict(), f"plan {plan!r} diverged"
        assert chaos.total == base.total
        colls = list(obs.read_ledger(led, kind="collective"))
        n_partial = sum(1 for c in colls if c["op"] == "partial")
        assert n_partial >= 2 and colls[-1]["op"] == "finish", colls
        hits = [f for f in obs.read_ledger(led, kind="fault")
                if f["seam"] == "collective-finish"]
        assert hits and all(f["injected"] for f in hits), (plan, hits)
        # At least one fault struck a PARTIAL crossing: crossing indices
        # below the partial count belong to partials, not the finish.
        assert min(f["index"] for f in hits) < n_partial, (plan, hits)
        end = next(iter(obs.read_ledger(led, kind="run_end")))
        assert end["pipeline"]["partial_merges"] == n_partial


@pytest.mark.slow
def test_chaos_grep_ngram_identity(tmp_path, rng):
    """The certification holds across families: streamed grep and ngram
    under a seeded plan match their fault-free runs bit-for-bit."""
    from mapreduce_tpu.models import grep

    corpus = make_corpus(rng, 2000, 120) + b"\nneedle hay needle stack\n"
    path = _write(tmp_path, corpus)
    plan = "seed=5,rate=0.1,classes=transient"

    base_n = executor.count_file(path, CFG, mesh=data_mesh(2), retry=3,
                                 ngram=2)
    cfg = _chaos_cfg(plan)
    chaos_n = executor.count_file(path, cfg, mesh=data_mesh(2), retry=3,
                                  ngram=2)
    assert chaos_n.as_dict() == base_n.as_dict()
    assert chaos_n.total == base_n.total

    base_g = grep.grep_file(path, b"needle", config=CFG,
                            mesh=data_mesh(2), retry=3)
    chaos_g = grep.grep_file(path, b"needle", config=cfg,
                             mesh=data_mesh(2), retry=3)
    assert base_g.matches >= 2
    assert (chaos_g.matches, chaos_g.lines) \
        == (base_g.matches, base_g.lines)


def test_replay_from_ledger_reproduces_fault_sequence(tmp_path, rng):
    """ISSUE 15 acceptance: a fault plan replayed from its own ledger
    records reproduces the identical fault sequence (and the identical
    results)."""
    corpus = make_corpus(rng, 1500, 100)
    path = _write(tmp_path, corpus)
    led1 = str(tmp_path / "chaotic.jsonl")
    cfg1 = _chaos_cfg("seed=11,rate=0.12,classes=transient")
    with obs.Telemetry.create(ledger_path=led1) as tel:
        r1 = executor.count_file(path, cfg1, mesh=data_mesh(2), retry=4,
                                 telemetry=tel)
    seq1 = faults.fired_sequence(obs.read_ledger(led1))
    assert seq1, "the chaotic run fired nothing; test is vacuous"
    # Rebuild the plan from the run's own ledger and replay.
    replay_plan = faults.FaultPlan.from_ledger(obs.read_ledger(led1))
    led2 = str(tmp_path / "replay.jsonl")
    cfg2 = _chaos_cfg(replay_plan.spec)
    with obs.Telemetry.create(ledger_path=led2) as tel:
        r2 = executor.count_file(path, cfg2, mesh=data_mesh(2), retry=4,
                                 telemetry=tel)
    seq2 = faults.fired_sequence(obs.read_ledger(led2))
    assert seq2 == seq1, (seq1, seq2)
    assert r2.as_dict() == r1.as_dict() and r2.total == r1.total


@pytest.mark.smoke
def test_from_ledger_filters_to_first_run_in_appended_ledger():
    """An append-mode ledger holding TWO chaotic runs: with run_id=None,
    from_ledger and fired_sequence must agree on the FIRST run's events
    only — merging both runs' schedules would replay faults the original
    run never saw."""
    records = [
        {"kind": "fault", "injected": True, "run_id": "runA",
         "seam": "reader-read", "index": 3, "fault_class": "transient"},
        {"kind": "fault", "injected": True, "run_id": "runA",
         "seam": "dispatch", "index": 7, "fault_class": "resource"},
        {"kind": "fault", "injected": True, "run_id": "runB",
         "seam": "h2d", "index": 1, "fault_class": "transient"},
    ]
    plan = faults.FaultPlan.from_ledger(records)
    want = [("reader-read", 3, "transient"), ("dispatch", 7, "resource")]
    assert sorted(plan.events.items()) \
        == sorted([((s, i), c) for s, i, c in want])
    assert faults.fired_sequence(records) == want
    # An explicit run_id selects that run, first or not.
    plan_b = faults.FaultPlan.from_ledger(records, run_id="runB")
    assert sorted(plan_b.events.items()) == [(("h2d", 1), "transient")]


def test_fault_free_run_emits_no_chaos_records(tmp_path, rng):
    """The disabled path: no fault plan -> no fault/degrade records, no
    fault_plan stamp — fault-free ledgers keep their v8 record shapes
    (plus the version bump)."""
    corpus = make_corpus(rng, 1000, 80)
    path = _write(tmp_path, corpus)
    led = str(tmp_path / "run.jsonl")
    with obs.Telemetry.create(ledger_path=led) as tel:
        executor.count_file(path, CFG, mesh=data_mesh(2), retry=1,
                            telemetry=tel)
    assert not list(obs.read_ledger(led, kind="fault"))
    assert not list(obs.read_ledger(led, kind="degrade"))
    start = next(iter(obs.read_ledger(led, kind="run_start")))
    assert "fault_plan" not in start, start
