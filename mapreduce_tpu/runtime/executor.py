"""Streaming executor: files -> sharded device stream -> merged result.

The orchestration layer of the framework (reference analogue: the body of
``main()`` plus ``runMapReduce``, ``main.cu:133-222``), with the capabilities
the reference lacks (SURVEY §5): step retry on transient failure, periodic
checkpoint/resume, structured progress logging, and throughput metrics.

Flow per run:
  1. build (or accept) a data mesh and an Engine for the job;
  2. stream boundary-aligned [D, chunk_bytes] batches from the reader,
     folding each into device-resident per-device states (one jitted SPMD
     step; accumulators never round-trip to host);
  3. collectively merge + finalize;
  4. recover exact strings host-side from (chunk_id, pos, len) first-
     occurrence records against the memory-mapped source file.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
import weakref
from typing import Any, Optional

import jax
import numpy as np

from mapreduce_tpu import constants
from mapreduce_tpu import obs
from mapreduce_tpu.config import Config, DEFAULT_CONFIG
from mapreduce_tpu.data import reader as reader_mod
from mapreduce_tpu.runtime import faults as faults_mod
from mapreduce_tpu.models.wordcount import (WordCountJob, TopKWordCountJob,
                                            NGramCountJob, TopKTable,
                                            SketchedState, SketchedWordCountJob,
                                            FreqSketchedState, FreqSketchedWordCountJob,
                                            WordCountResult, apply_top_k,
                                            _reported_distinct)
from mapreduce_tpu.ops import table as table_ops
from mapreduce_tpu.parallel.mapreduce import Engine, MapReduceJob
from mapreduce_tpu.parallel.mesh import data_mesh
from mapreduce_tpu.runtime import checkpoint as ckpt_mod
from mapreduce_tpu.runtime import metrics as metrics_mod
from mapreduce_tpu.runtime.logging import get_logger, log_event


@dataclasses.dataclass
class RunResult:
    """Generic job result + run metrics."""

    value: Any
    metrics: metrics_mod.RunMetrics
    bases: np.ndarray  # int64[steps, D] row base offsets (string recovery)
    # Streamed runs: the dispatch-window statistics the run-end ledger
    # record carries (configured/observed in-flight depth, drain counts,
    # overlap_fraction).  None for drivers that never streamed.
    pipeline: Optional[dict] = None
    # Config(autotune='hint') runs: the window autotuner's recommendation
    # for this run (the `tune` ledger record's payload — proposal,
    # fired rule, decision trail).  None when autotuning is off or the
    # hint path failed (it is advisory and must never fail the run).
    tune: Optional[dict] = None


def _overlap_fraction(timer) -> Optional[float]:
    """``1 - blocked_time / stream_time``: the share of streamed wall-clock
    the driver loop was NOT sitting in an explicit wait (reader empty,
    full-window retires, retry-anchor snapshot fetches, end-of-stream
    tails).  A fully serialized loop trends toward 0; a pipeline hiding
    H2D behind compute trends toward 1.  ``stage``/``dispatch`` are host
    WORK, not waits — they count as overlapped.  None before the stream
    phase has been timed."""
    stream = timer["stream"]
    if not stream:
        return None
    blocked = sum(timer[p] for p in ("read_wait", "retire_wait",
                                     "snapshot", "h2d_tail",
                                     "compute_tail"))
    return round(max(0.0, 1.0 - blocked / stream), 4)


def _finalize_pipeline(pipe: dict, timer, tel) -> None:
    """Attach the run's overlap fraction to the window stats and export it
    through the registry — shared by both drivers, so the two entry points
    can never drift apart on overlap semantics."""
    pipe["overlap_fraction"] = _overlap_fraction(timer)
    if pipe["overlap_fraction"] is not None:
        tel.registry.gauge("executor.overlap_fraction").set(
            pipe["overlap_fraction"])


def _autotune_hint(config: Config, tel, pipe: dict, timer,
                   data_rec: Optional[dict], logger) -> Optional[dict]:
    """Online autotune hint (ISSUE 10): run the jax-free tuning engine
    over THIS run's own ledger records and fold the recommendation into a
    ``tune`` ledger record + the run summary — the live run is never
    changed.  The records are read back from the run's ledger file (it is
    flushed per record, and the tuner is a pure function of ledger
    records — exactly the offline path); with no ledger attached, the
    in-memory run_end view (phases + window stats + data summary) still
    yields a phase-classified hint.  Advisory by contract: any failure is
    logged and swallowed, never surfaced as a run failure."""
    try:
        from mapreduce_tpu import tuning

        if tel.enabled and tel.ledger is not None:
            records = [r for r in obs.read_ledger(tel.ledger.path)
                       if r.get("run_id") == tel.run_id]
        else:
            records = []
            if data_rec is not None:
                records.append({"run_id": tel.run_id, "kind": "data",
                                **data_rec})
        # run_end is written AFTER the tune record (the "no run_end = did
        # not complete" invariant): synthesize its view so the proposal
        # reads this run's phases and window statistics either way.
        records.append({"run_id": tel.run_id, "kind": "run_end",
                        "phases": dict(timer.phases), "pipeline": pipe})
        prop = tuning.propose(records, run_id=tel.run_id, current={
            "chunk_bytes": config.chunk_bytes,
            "superstep": config.superstep,
            "inflight_groups": config.inflight_groups,
            "prefetch_depth": config.resolved_prefetch_depth})
        # Belt over the engine's own clamps: a proposal that cannot pass
        # Config validation must never reach the ledger.
        tuning.validate_knobs(prop["proposal"], config.backend)
        prop["mode"] = "hint"
        tel.ledger_write("tune", **prop)
        tel.note_tune(prop)
        log_event(logger, "autotune hint", rule=prop["rule"],
                  changed=prop["changed"], converged=prop["converged"])
        return prop
    except Exception as e:  # noqa: BLE001 — advisory, never fatal
        log_event(logger, "autotune hint failed", error=repr(e))
        return None


@dataclasses.dataclass
class _StreamHooks:
    """The strategy seams between the host-local (:func:`run_job`) and
    global-SPMD (:func:`run_job_global`) drivers.  Everything else about
    streaming — superstep grouping, checkpoint-boundary splitting, file-
    boundary hooks, the retry loop, progress/checkpoint cadence — is ONE
    shared loop (:func:`_drive_stream`), so a fix to that machinery lands
    in both entry points by construction."""

    stage_single: Any  # Batch -> engine.step chunks argument
    stage_group: Any  # list[Batch] -> engine.step_many stacked argument
    snapshot: Any  # device state -> host pytree (checkpoint fetch / retry)
    restage: Any  # host pytree -> sharded device state (retry; None = n/a)
    write_gate: Any  # () -> bool: this process writes checkpoint files
    retry: int = 0
    # Optional staged-input recycler: called with a group's staged value
    # when the group RETIRES (its program provably consumed the input), so
    # host staging buffers return to a pool instead of being reallocated
    # per group (ISSUE 5 satellite).  Retirement is the safe recycle point
    # even where device_put may alias host memory (CPU backend): a retired
    # group's program has finished every read of its input.
    stage_release: Any = None
    # Optional Batch -> Batch applied the moment a batch leaves the reader:
    # run_job uses it to device_put each [D, C] chunk array immediately
    # (async H2D starts right away and overlaps the PREVIOUS group's
    # compute), so superstep groups stack already-resident device arrays
    # instead of shipping one K-times-larger host array at dispatch time
    # (round 5 measured a single 128 MB staged array ~7x slower per byte
    # than 32 MB chunk arrays, on a remote chip since retired).
    stage_arrival: Any = None
    # Multi-host (ISSUE 13): the shard-row indices THIS process stages
    # (run_job_global's `host_shards`).  When set, each group's lifecycle
    # record also carries `host_bytes` — the bytes of real data this host
    # staged, the per-host balance signal obs/fleet.py reads (group_bytes
    # is the GLOBAL batch size, identical on every process).
    host_rows: Any = None
    # Degradation ladder (ISSUE 15): Config -> fresh Engine for a
    # degraded config.  The ladder only moves knobs that keep state
    # shapes (and results — each is bit-identical-tested) intact, so the
    # anchor snapshot restages into the rebuilt engine unchanged.  None
    # (run_job_global) disables the ladder: resource exhaustion there
    # fails over to checkpoint/resume like every other global failure.
    rebuild: Any = None
    # Window-boundary collective overlap (ISSUE 20 leg 2): the driver's
    # :class:`_OverlapMerger`, or None (default — the old single-finish
    # shape, bit-identical programs and ledger).  Only valid with
    # retry=0: the replay anchor snapshots local state a partial merge
    # has partially shipped.
    overlap: Any = None


class _StagePool:
    """Reusable host staging buffers, keyed by (shape, dtype).

    A streamed run re-allocates an identical staging buffer for every
    superstep group (``np.stack`` in ``stage_group``, shard-row gathers in
    the global driver) — pure allocator churn on the ingest hot path.  The
    pool recycles each buffer when its group retires (see
    ``_StreamHooks.stage_release``), so a run holds O(window) staging
    buffers total instead of one fresh allocation per group.
    """

    def __init__(self) -> None:
        self._free: dict = {}
        # id -> weakref of every outstanding issued buffer.  Weak, with a
        # purge callback: a buffer dropped on an exception path (never
        # given back) must not leave a dangling id behind — CPython reuses
        # addresses, and a stale id would make give() adopt a foreign
        # (e.g. reader-owned) array into the free list.
        self._issued: dict = {}

    def take(self, shape, dtype) -> np.ndarray:
        free = self._free.get((tuple(shape), np.dtype(dtype)))
        buf = free.pop() if free else np.empty(shape, dtype)
        self._issued[id(buf)] = weakref.ref(
            buf, lambda _r, _i=id(buf): self._issued.pop(_i, None))
        return buf

    def give(self, arr) -> None:
        # Only re-pool buffers THIS pool issued (verified by identity, not
        # just id): retirement also hands back reader-owned single-batch
        # arrays, and adopting any of those would retain the whole corpus
        # in the free list.
        if not isinstance(arr, np.ndarray):
            return
        ref = self._issued.get(id(arr))
        if ref is None or ref() is not arr:
            return
        del self._issued[id(arr)]
        self._free.setdefault((arr.shape, arr.dtype), []).append(arr)


def _probe_body(leaf):
    """The completion-probe program: a jitted copy of ONE small state leaf.

    All outputs of a dispatch become ready together and are poisoned by the
    same error, so this token is ready exactly when its group's step program
    finished — while SURVIVING the donation of the state into the next
    group's dispatch (a non-donated jit output never aliases its input; the
    state arrays themselves are deleted the moment the next step consumes
    them).  The graphcheck host-sync pass traces this body and certifies it
    stays free of host coupling: the window adds one tiny async program per
    group, never a hidden sync.
    """
    return leaf


_probe_jit = jax.jit(_probe_body)

#: Barrier-copy for any state about to enter the DONATING step programs.
#: A state built by ``jax.device_put`` (replay restage, checkpoint resume)
#: must not be donated as-is: donating a transfer-created buffer corrupts
#: the process heap on the CPU backend (glibc double-free aborts — the
#: chaos harness's token-wait plans reproduce it deterministically; an
#: XLA-produced buffer is donation-safe).  ``optimization_barrier`` is a
#: real equation, so jit cannot prune it to a pass-through and the output
#: is a fresh XLA-owned allocation with the input's sharding.
_owned_state = jax.jit(jax.lax.optimization_barrier)


def _state_token(state):
    """Per-group completion token: the smallest state leaf, copied through
    :func:`_probe_body`.  Blocking on it observes (and attributes) exactly
    one group's completion; it is never donated, so it outlives the state.
    """
    leaves = jax.tree.leaves(state)
    leaf = min(leaves, key=lambda x: getattr(x, "nbytes", 1 << 62))
    return _probe_jit(leaf)


def _wait_token(token) -> None:
    """The window's completion wait, as a seam: tests poison this to
    emulate a device error that surfaces ASYNCHRONOUSLY at the blocking
    fetch (the CPU backend executes callbacks at dispatch, so the real
    late-surfacing failure mode cannot be produced natively here)."""
    jax.block_until_ready(token)


def _wait_token_timed(token, timeout_s: float) -> None:
    """:func:`_wait_token` under a wall-clock deadline
    (``FailurePolicy.token_timeout_s``, ISSUE 15): a wait past the
    deadline raises a typed :class:`...runtime.faults.TokenTimeout`
    (transient — the replay path re-dispatches from the window anchor)
    instead of stalling the driver forever on a hung device.
    ``jax.block_until_ready`` has no timeout of its own, so
    the wait runs on a daemon worker thread; an abandoned wait costs one
    parked thread, which the recovery replay's fresh dispatch obsoletes."""
    if not timeout_s:
        return _wait_token(token)
    box: list = []

    def run() -> None:
        try:
            _wait_token(token)
            box.append(None)
        except BaseException as e:  # surfaced at the fetch: deliver as-is
            box.append(e)

    t = threading.Thread(target=run, daemon=True,
                         name="mapreduce-token-wait")
    t.start()
    t.join(timeout_s)
    if not box:
        raise faults_mod.TokenTimeout(
            f"completion token not ready within {timeout_s}s "
            "(hung device)", seam="token-wait")
    if box[0] is not None:
        raise box[0]


def _record_fault(tel, write: bool, exc: BaseException, *, seam: str,
                  injected: bool, index: Optional[int] = None,
                  step: Optional[int] = None) -> str:
    """One typed-fault observation (ISSUE 15, ledger v9): the taxonomy
    class lands in the ``executor.faults`` registry counter, the flight
    ring, and a ``fault`` ledger record.  The ledger write is
    best-effort — the ledger may be the very seam that is failing — and
    a fault record must never mask the fault itself.  Returns the class."""
    cls = faults_mod.classify(exc)
    tel.registry.counter("executor.faults", seam=seam, fault_class=cls).inc()
    tel.event("fault", seam=seam, fault_class=cls, injected=injected,
              error=repr(exc))
    try:
        rec: dict = {"seam": seam, "fault_class": cls, "injected": injected,
                     "error": repr(exc)}
        if index is not None:
            rec["index"] = int(index)
        if step is not None:
            rec["step"] = int(step)
        tel.ledger_write("fault", write=write, **rec)
    except Exception:
        pass
    return cls


class _DegradeSignal(Exception):
    """Internal: a resource-classed failure exhausted its budget inside
    the recovery replay and the degradation ladder may still have a step
    — ``recover()``'s ladder loop owns the decision."""

    def __init__(self, error: BaseException):
        self.error = error


def _config_summary(config: Config) -> dict:
    """The degradation ladder's view of a config (label values only;
    ``faults.next_degrade`` consumes exactly this shape)."""
    return {"geometry": config.geometry_label,
            "combiner": config.resolved_combiner,
            "map_impl": config.map_impl,
            "sort_impl": config.sort_impl}


def _apply_degrade(config: Config, field: str, value: str) -> Config:
    """One ladder step applied to the real Config.  revert-geometry maps
    to the None sentinel (the shipped constants); combiner-off also drops
    the cache sizing knob, which only validates with the cache on."""
    if field == "geometry":
        return dataclasses.replace(config, geometry=None)
    kw: dict = {field: value}
    if field == "combiner":
        kw["combiner_slots"] = None
    return dataclasses.replace(config, **kw)


def _job_with_config(job, config: Config):
    """Shallow-rebind a job's Config for a degradation-ladder step.  The
    ladder moves only knobs that leave state SHAPES untouched (geometry/
    combiner/map_impl/sort_impl swap kernels, not pytrees — each shipped
    with a bit-identity suite), so a copied job with the degraded config
    drives the same state through cheaper programs.  Composed jobs
    (sketch wrappers) rebind their base job too."""
    import copy

    j = copy.copy(job)
    base = getattr(j, "base", None)
    if base is not None:
        j.base = _job_with_config(base, config)
    if hasattr(j, "config"):
        j.config = config
    return j


def _collective_call(thunk, plan, policy, tel, write: bool, logger):
    """A collective dispatch behind the collective-finish seam (ISSUE 15
    refactored for ISSUE 20: the stream-end finish AND the window-boundary
    partial merges cross the SAME seam, so chaos plans written against the
    old grammar exercise both).

    Injected faults fire BEFORE the collective runs, so retrying them on
    the transient/resource budget is always safe; a real collective
    failure is classified + recorded and propagates — in a fleet, peer
    processes are blocked mid-program, and checkpoint/resume is the
    recovery path (the run_job_global no-retry contract)."""
    attempt = 0
    while True:
        try:
            if plan is not None:
                exc = plan.check("collective-finish")
                if exc is not None:
                    _record_fault(tel, write, exc, seam="collective-finish",
                                  injected=True, index=exc.index)
                    raise exc
            return thunk()
        except faults_mod.FaultError as fe:
            if not fe.injected or fe.fault_class == "preemption":
                raise
            if attempt >= policy.budget(fe.fault_class):
                raise
            attempt += 1
            tel.registry.counter("executor.retry_attempts").inc()
            tel.registry.counter("executor.retries_by_class",
                                 fault_class=fe.fault_class).inc()
            tel.ledger_write("retry", attempt=attempt, error=repr(fe),
                             fault_class=fe.fault_class,
                             seam="collective-finish", write=write)
            log_event(logger, "collective finish fault; retrying",
                      attempt=attempt, fault_class=fe.fault_class)
            s = policy.backoff_s(fe.fault_class, attempt,
                                 seam="collective-finish")
            if s > 0:
                time.sleep(s)
        except Exception as e:
            _record_fault(tel, write, e, seam="collective-finish",
                          injected=False)
            raise


def _collective_finish(engine, state, plan, policy, tel, write: bool,
                       logger):
    """``engine.finish`` through the collective-finish seam (the
    monolithic stream-end merge)."""
    return _collective_call(lambda: engine.finish(state), plan, policy,
                            tel, write, logger)


class _OverlapMerger:
    """Window-boundary collective overlap (ISSUE 20 leg 2).

    One resident replicated accumulator plus at most one in-flight
    partial collective.  At a window-drain/checkpoint boundary the driver
    calls :meth:`boundary`: the previous partial is retired lazily (it
    owns a completion token exactly like a window group, and it had a
    whole window of ingest to hide behind), the current local tables are
    drained into the accumulator by an async-dispatched partial merge
    through the collective-finish seam, and the local tables are reset —
    so the DCN transfer of window N overlaps the ingest+compute of
    window N+1 and table pressure stays bounded by the window.  Each
    retired partial lands as an op='partial' ``collective`` ledger record
    (ledger v10) carrying its real dispatch->token-ready interval: the
    in-stream interconnect time obs/timeline's collective lane,
    ``fleet_bottleneck`` and obswatch read.  Byte-exact to the monolithic
    merge: the fold is the job's commutative merge (min-position rule),
    certified by the chaos harness and the 2-process gloo pair."""

    def __init__(self, engine, tel, write_gate, plan, policy, logger,
                 strategy: str, window_cap: int):
        self.engine = engine
        self.tel = tel
        self.write_gate = write_gate
        self.plan = plan
        self.policy = policy
        self.logger = logger
        self.strategy = strategy
        self.window_cap = max(1, int(window_cap))
        self.accum = None
        self.partials = 0
        self._retired_at_last = 0
        self._inflight = None  # (token, started_at, step)

    def disarm(self) -> None:
        """Preemption shutdown: no further injected faults (the stream
        loop disarms its own plan reference the same way)."""
        self.plan = None

    def due(self, retired_groups: int) -> bool:
        """A partial fires when a full window's worth of groups retired
        since the last boundary — a pure function of the group sequence,
        so every process of a fleet dispatches the same partial at the
        same point (the partial is one SPMD program)."""
        return retired_groups - self._retired_at_last >= self.window_cap

    def retire(self) -> None:
        """Observe the previous partial's completion (usually long since
        ready) and write its ledger record with the real interval."""
        if self._inflight is None:
            return
        token, t0, step = self._inflight
        self._inflight = None
        _wait_token(token)
        self.tel.ledger_write(
            "collective", op="partial", strategy=self.strategy,
            step=step, started_at=t0,
            ended_at=round(time.perf_counter(), 6),
            write=self.write_gate())

    def boundary(self, state, step: int, retired_groups: int):
        """Async-dispatch a partial merge of ``state`` into the
        accumulator and return the reset local state."""
        self.retire()
        t0 = round(time.perf_counter(), 6)
        self.accum = _collective_call(
            lambda: self.engine.partial_merge(self.accum, state),
            self.plan, self.policy, self.tel, self.write_gate(),
            self.logger)
        self._inflight = (_state_token(self.accum), t0, step)
        self.partials += 1
        self._retired_at_last = retired_groups
        self.tel.event("partial_merge", step=step)
        return self.engine.partial_reset(state)

    def host_accum(self):
        """The accumulator as host numpy (checkpoint packing).  The
        partial's output is fully replicated, so the fetch is addressable
        on every process; retire() first so the fetch never waits."""
        self.retire()
        if self.accum is None:
            return None
        return jax.tree.map(lambda x: np.array(x, copy=True), self.accum)

    def accum_template(self):
        """Abstract accumulator shapes (checkpoint resume template): the
        first partial's output for this engine's strategy and job."""
        eng = self.engine
        return jax.eval_shape(lambda: eng.partial_merge(
            None, eng.init_states()))


@dataclasses.dataclass
class _Inflight:
    """One dispatched-but-unretired superstep group (the window unit)."""

    token: Any  # completion probe output: ready <=> the group's program ran
    staged: Any  # staged chunks handle (h2d tail timing; pool recycling)
    step_first: int
    cursor_before: int  # bytes_done before this group (honest failure cursor)
    life: dict  # lifecycle timestamps + sizes (the `group` ledger record)
    # Data-plane stats output of the group's step program (ISSUE 8):
    # a tiny non-donated DataStats pytree, ready together with the
    # completion token; fetched at retirement.  None when telemetry is
    # off or the job has no stats hooks.
    stats: Any = None


def _group_life(group, read_at: Optional[float], group_bytes: int) -> dict:
    """Start a group's lifecycle record (ISSUE 7): identity, sizes, and the
    monotonic-clock timestamps stamped so far.  ``staged_at`` is stamped
    here — the caller invokes this immediately before staging begins.
    ``group_bytes`` is computed once by the caller and shared with the
    step-record accounting (no second pass over the batch lengths)."""
    return {"step_first": group[0].step, "step_last": group[-1].step,
            "steps": group[-1].step - group[0].step + 1,
            "group_bytes": group_bytes,
            "read_at": round(read_at, 6) if read_at is not None else None,
            "staged_at": round(time.perf_counter(), 6)}


#: group-record ``data`` counters mirrored into registry counters at
#: retirement (per-group deltas; names match the ledger fields).
_DATA_COUNTER_METRICS = (
    ("overlong", "data.overlong_tokens"),
    ("rescued", "data.rescued_tokens"),
    ("dropped_tokens", "data.dropped_tokens"),
    ("fallback_chunks", "data.spill_fallback_chunks"),
    ("rescue_escalations", "data.rescue_escalations"),
    ("spill_rows", "data.spill_rows"),
)


def _group_record(tel, write: bool, life: dict, token_ready_at: float,
                  retired_at: float, wait_s: float, retries: int = 0,
                  data: Optional[dict] = None) -> None:
    """Emit one ``group`` ledger record for a RETIRED group — the lifecycle
    raw material ``obs/timeline.py`` reconstructs lanes from.  Pure
    host-side bookkeeping: a handful of ``perf_counter`` stamps and one
    JSONL append (same cost class as the step record written at dispatch);
    a unit test holds the non-I/O part under 1 ms per group.  ``data``
    (ISSUE 8): the group's data-plane counter dict, already reduced
    host-side by :class:`...ops.datastats.DataAggregator` — attached to
    the record and mirrored into the registry's ``data.*`` instruments."""
    tel.registry.counter("executor.groups_retired").inc()
    d = life.get("dispatched_at")
    if d is not None:
        tel.registry.observe("executor.group_device_seconds",
                             max(0.0, token_ready_at - d))
    tel.registry.observe("executor.retire_wait_seconds", max(0.0, wait_s))
    rec = {k: v for k, v in life.items() if v is not None}
    rec["token_ready_at"] = round(token_ready_at, 6)
    rec["retired_at"] = round(retired_at, 6)
    rec["retire_wait_s"] = round(max(0.0, wait_s), 6)
    if retries:
        rec["retries"] = retries
    if data is not None:
        rec["data"] = data
        for field, metric in _DATA_COUNTER_METRICS:
            v = data.get(field)
            if v:
                tel.registry.counter(metric).inc(v)
        if data.get("occupancy") is not None:
            tel.registry.gauge("data.table_occupancy").set(data["occupancy"])
        if data.get("top_mass") is not None:
            tel.registry.gauge("data.top_mass").set(data["top_mass"])
    # write gates the main (coordinator) file only: the per-host shard
    # keeps every retired group's lifecycle (ISSUE 13).
    tel.ledger_write("group", write=write, **rec)


def _stream_total_bytes(path, start_offset, end_offset) -> Optional[int]:
    """Best-effort total bytes this stream will ingest — the denominator
    of the heartbeat's completion fraction / ETA (ISSUE 14).  A byte
    range answers exactly; otherwise the file size(s).  None (no
    fraction, no ETA — the heartbeat degrades to cursor + rate) when the
    input is not stat-able (pipes, exotic path objects)."""
    try:
        if end_offset is not None:
            return max(0, int(end_offset) - int(start_offset))
        import os

        paths = path if isinstance(path, (list, tuple)) else [path]
        total = sum(os.path.getsize(p) for p in paths)
        return max(0, int(total) - int(start_offset))
    except (OSError, TypeError, ValueError):
        return None


def _drive_stream(engine, job, config: Config, path, state,
                  hooks: _StreamHooks, *, start_step: int, start_offset: int,
                  end_offset, bases_list: list, checkpoint_path,
                  checkpoint_every: int, fingerprint, resumed_file,
                  logger, progress_every: int, timer=None, telemetry=None,
                  data_agg=None, plan=None, policy=None):
    """The shared streaming loop: reader -> prefetch -> superstep groups ->
    a bounded in-flight dispatch window (ISSUE 5), with checkpoint cadence
    and file-boundary hooks.  Returns ``(state, bytes_done, step_index,
    pipe)``; ``bytes_done`` is the absolute stream cursor (starts at
    ``start_offset``) and ``pipe`` the window statistics the run-end ledger
    record carries (configured/observed depth, drain counts).

    The window (``Config.inflight_groups``): up to W superstep groups stay
    dispatched-but-unretired, so the reader/prefetch thread, host staging,
    async H2D, and device compute of DIFFERENT groups overlap instead of
    the old dispatch -> (retry-mode sync) -> next-group lockstep.  Each
    dispatch also launches a tiny non-donated completion probe
    (:func:`_state_token`); groups retire lazily — when the window is full,
    at checkpoint/file boundaries, and at stream end — by blocking on those
    tokens in dispatch order, which attributes an asynchronously surfaced
    device failure to exactly the group that caused it (the old loop could
    only attribute by syncing EVERY dispatch).  ``inflight_groups=1`` is
    strict serial — one group in flight, retired before the next dispatch:
    with retry this is exactly the pre-window loop's per-dispatch sync;
    with retry=0 the pre-window loop was async behind the device queue, so
    1 is the A/B control's serial floor, not a bug-for-bug baseline.

    Retry (``hooks.retry > 0``): the known-good snapshot moves from
    per-group to window-drain cadence — the window fills, drains as one
    batch, and a fresh host snapshot anchors the next window.  A failure
    mid-window replays the window's still-alive host batches serially from
    the anchor (the failed group charged one attempt, predecessors replayed
    free), so retry-from-snapshot semantics survive the async window while
    replay stays bounded by the window — and by ``checkpoint_every``, since
    checkpoint boundaries force a drain.

    ``timer`` (a :class:`...runtime.metrics.PhaseTimer`) decomposes the
    stream wall-clock into the phases the ingest number is made of:
    ``read_wait`` (blocking on the prefetching reader), ``stage`` (host
    assembly + host->device placement of a group), ``dispatch`` (program
    enqueue; blocks only when the device queue is full), ``retire_wait``
    (blocking on a full window's oldest completion token), ``snapshot``
    (retry-anchor fetches), and the end-of-stream tail split ``h2d_tail``
    (last group's input still in transfer) vs ``compute_tail`` (queued
    compute after the last enqueue) — the decomposition of what the old
    single ``drain`` phase lumped together.  The phases are timed through
    :func:`...obs.spans.span`, which also drops a profiler TraceAnnotation
    per phase so XProf timelines line up with the ledger.

    ``telemetry`` (:class:`...obs.telemetry.Telemetry`): exactly one ledger
    step record per dispatched group, written at dispatch in step order
    (completion is observed later under pipelining), carrying phase deltas,
    bytes, the in-flight depth after the dispatch, and device memory stats;
    plus exactly one ``group`` record per RETIRED group (ISSUE 7), written
    at retirement, carrying the group's monotonic-clock lifecycle
    (``read_at``/``staged_at``/``dispatched_at``/``token_ready_at``/
    ``retired_at``) — the per-resource timeline ``obs/timeline.py``
    reconstructs lanes, overlap matrices and the critical-path verdict
    from; flight-recorder events per dispatch / retry / checkpoint, dumped
    with a state summary when the failure path runs; plus the live-run
    ``progress`` heartbeat (ISSUE 14, ledger v8) — a wall-clock-cadenced
    record from the dispatch/retire points carrying the byte cursor,
    groups dispatched/retired, in-flight depth and the cursor-derived
    ETA, so ``tools/obswatch.py`` can tail the run before it ends (the
    not-due path is one monotonic read; nothing here is traced).
    Disabled telemetry
    (the ``None`` default) does no per-step work and — the invariant the
    graphcheck host-sync pass certifies — never adds a host sync to the
    dispatch pipeline either way: everything here is host-side bookkeeping
    around async enqueues (the lifecycle adds ~5 ``perf_counter`` stamps
    per group, never a device wait that was not already there).
    """
    bytes_done = int(start_offset)
    step_index = start_step
    last_ckpt = start_step // checkpoint_every if checkpoint_every else 0
    k = config.superstep
    pending: list = []
    timer = timer if timer is not None else metrics_mod.PhaseTimer()
    tel = obs.maybe(telemetry)
    # Unified failure policy + fault plan (ISSUE 15).  `plan is None` is
    # the provably zero-cost disabled path: every seam check below is
    # guarded by that one identity test, and nothing here is traced.
    # `cur_config` is the degradation ladder's moving target — the ladder
    # only moves kernel-choice knobs, so the loop's own reads of `config`
    # (superstep, window, prefetch) stay pinned to what the caller set.
    policy = policy if policy is not None \
        else faults_mod.FailurePolicy.resolve(None, retry=hooks.retry)
    cur_config = config
    window_cap = max(1, config.inflight_groups)
    overlap = hooks.overlap
    window: collections.deque = collections.deque()
    # retry > 0: host snapshot of the state at the current anchor point —
    # the replay source.  (Re)taken lazily before the first dispatch of a
    # window and at every drain; invalidated by file-boundary state hooks.
    # ``since_anchor`` keeps every ``(group, pre-group cursor)`` dispatched
    # SINCE that snapshot (including groups already retired mid-drain): a
    # failure replays all of them — a group retired inside the current
    # drain has no snapshot of its own, so the anchor is the only rebuild
    # point — and the paired cursor keeps a replay failure's ledger record
    # honest about where the failed group started.
    anchor = None
    since_anchor: list = []
    last_file_dispatched = resumed_file or 0
    # step -> monotonic arrival time of the batch out of the prefetching
    # reader: a group's `read_at` is its FIRST batch's arrival (the reader
    # lane of the timeline spans read + superstep accumulation).
    read_t: dict = {}
    pipe = {"inflight_groups": window_cap,
            "prefetch_depth": config.resolved_prefetch_depth,
            "dispatch_groups": 0, "depth_sum": 0, "depth_max": 0,
            "full_retires": 0, "boundary_drains": 0}
    # Live-run heartbeat raw material (ISSUE 14, ledger v8): the stream's
    # total-byte denominator (None degrades the heartbeat to cursor+rate)
    # and the retired-group counter the `progress` records carry.
    stream_total = _stream_total_bytes(path, start_offset, end_offset) \
        if tel.enabled else None
    retired_groups = 0

    def heartbeat() -> None:
        """One call per dispatch/retire point; Telemetry.progress gates
        on its wall-clock cadence, so the not-due cost is one monotonic
        read — never a device wait, never a traced-program change."""
        tel.progress(step=step_index, cursor_bytes=bytes_done,
                     streamed_bytes=bytes_done - int(start_offset),
                     total_bytes=stream_total,
                     groups_dispatched=pipe["dispatch_groups"],
                     groups_retired=retired_groups,
                     inflight_depth=len(window),
                     write=hooks.write_gate())

    def cross(seam):
        """One named seam crossing of the fault plan (ISSUE 15; call
        sites guard on ``plan is not None``): count it, and when the plan
        says this crossing fails, record the typed ``fault`` ledger
        record and raise.  ``process-kill`` is not an exception — it is
        the machine going away (``os._exit``, no cleanup, no flush beyond
        the already-flushed ledger): the multi-host chaos scenario."""
        exc = plan.check(seam)
        if exc is None:
            return
        _record_fault(tel, hooks.write_gate(), exc, seam=seam,
                      injected=True, index=exc.index, step=step_index)
        if seam == "process-kill":
            import os

            os._exit(113)
        raise exc

    def backoff(fault_class, attempt, seam):
        """Policy backoff before retry ``attempt`` (exponential +
        deterministic jitter — runtime/faults.py owns the formula)."""
        s = policy.backoff_s(fault_class, attempt, seam=seam)
        if s > 0:
            time.sleep(s)

    def dispatch(state, group):
        with obs.span("stage", timer):
            if plan is not None:
                cross("stage-acquire")
            staged = hooks.stage_single(group[0]) if len(group) == 1 \
                else hooks.stage_group(group)
            if plan is not None:
                cross("h2d")
        with obs.span("dispatch", timer):
            if plan is not None:
                cross("dispatch")
            if len(group) == 1:
                out = engine.step(state, staged, group[0].step)
            else:
                out = engine.step_many(state, staged, group[0].step)
        stats = None
        if engine.data_stats:
            out, stats = out
        return out, stats, staged

    def group_stats_data(stats):
        """Fetch one retired group's DataStats leaves and fold them into
        the run aggregate (ISSUE 8).  Called only after the group's
        completion token was observed ready — the stats arrays are
        outputs of the same program, so the fetch copies a few dozen
        ready bytes, it never waits on the device."""
        if stats is None or data_agg is None:
            return None
        data = data_agg.group_data(jax.tree.map(np.asarray, stats))
        tel.note_data(data_agg.snapshot())
        return data

    def split_at_checkpoints(group):
        """Cut a superstep group at checkpoint boundaries, so resume
        granularity is governed by ``checkpoint_every`` even when it is
        finer than the superstep: a crash then replays at most
        ``checkpoint_every`` chunks per device, not a whole superstep
        (set ``checkpoint_every >= superstep`` to keep the full dispatch
        amortization)."""
        if not (checkpoint_every and checkpoint_path):
            return [group]
        subs, cur = [], []
        for b in group:
            cur.append(b)
            if (b.step + 1) % checkpoint_every == 0:
                subs.append(cur)
                cur = []
        if cur:
            subs.append(cur)
        return subs

    def final_failure(e, step, attempts, snapshot=None, cursor=None,
                      fault_class=None):
        """Failure detection (SURVEY §5): out of retries (or none
        requested).  Surface loudly with the resume cursor;
        checkpoint/resume is the recovery path.  The flight recorder dumps
        its ring + state summary FIRST, so a run that dies here leaves
        forensics on disk (a hung or crashed device) before the raise
        unwinds.  The dump fires on EVERY host (ISSUE 13 bugfix: each
        process owns a host-suffixed flight path, so a non-coordinator
        failure leaves forensics from the host that actually failed
        instead of being swallowed by the write gate — N processes no
        longer race one file); the failure record rides the gate into
        the main ledger and lands in the per-host shard regardless.
        ``fault_class`` (ISSUE 15): the taxonomy class the policy decided
        on, stamped into the failure record."""
        cursor = bytes_done if cursor is None else cursor
        fault_class = fault_class or faults_mod.classify(e)
        tel.event("step_failed", step=step, attempt=attempts - 1,
                  error=repr(e))
        dump = tel.flight_dump(
            context={"step": step, "offset": cursor,
                     "attempts": attempts, "error": repr(e),
                     "fault_class": fault_class,
                     "checkpoint_path": checkpoint_path},
            state=snapshot)
        tel.ledger_write("failure", step=step, cursor_bytes=cursor,
                         error=repr(e), fault_class=fault_class,
                         flight_dump=dump, write=hooks.write_gate())
        log_event(logger, "step failed", step=step, offset=cursor,
                  fault_class=fault_class,
                  resume_hint=checkpoint_path
                  or "enable checkpointing to resume")
        raise e

    def retry_record(step, attempt, e, fault_class="transient", seam=None):
        tel.registry.counter("executor.retry_attempts").inc()
        # Satellite (ISSUE 15): per-class retry accounting in the
        # registry — the service-level "how flaky is this fleet" signal.
        tel.registry.counter("executor.retries_by_class",
                             fault_class=fault_class).inc()
        tel.event("retry", step=step, attempt=attempt, error=repr(e))
        rec = {"step": step, "attempt": attempt, "error": repr(e),
               "fault_class": fault_class}
        if seam:
            rec["seam"] = seam
        tel.ledger_write("retry", write=hooks.write_gate(), **rec)
        log_event(logger, "step failed; retrying", step=step,
                  attempt=attempt, fault_class=fault_class)

    def serial_dispatch(state, group, attempts_used=0, used_out=None,
                        cursor=None, charged_class="transient"):
        """The serialized dispatch: snapshot -> dispatch -> block, retrying
        from the snapshot on failure under the PER-CLASS policy budgets
        (ISSUE 15) — the window's recovery path (and the exact pre-window
        semantics when the policy is the legacy ``retry=N`` mapping).
        ``attempts_used`` pre-charges the attempt the failed group already
        burned inside the window, against ``charged_class``; ``used_out``
        (a 1-slot list) reports the final total attempt count; ``cursor``
        is the stream offset BEFORE this group, so a replay that exhausts
        its retries reports an honest failure cursor (``bytes_done``
        already includes later groups accounted at their original
        dispatch).  A resource-classed exhaustion raises
        :class:`_DegradeSignal` when the ladder can still step down —
        ``recover()``'s ladder loop owns that choice."""
        snapshot = hooks.snapshot(state)
        used = {c: 0 for c in faults_mod.FAULT_CLASSES}
        used[charged_class] = attempts_used
        total = attempts_used
        while True:
            staged = None
            try:
                out, stats, staged = dispatch(state, group)
                with obs.span("retire_wait", timer):
                    jax.block_until_ready(out)
                if hooks.stage_release is not None:
                    hooks.stage_release(staged)
                if used_out is not None:
                    used_out[0] = total
                return out, stats
            except Exception as e:
                # Return the failed attempt's staging buffer so its id
                # never dangles in the pool (the doomed H2D may still read
                # it — harmless, its output is discarded).
                if staged is not None and hooks.stage_release is not None:
                    hooks.stage_release(staged)
                cls = faults_mod.classify(e)
                if cls == "preemption":
                    raise
                if not (isinstance(e, faults_mod.FaultError)
                        and e.injected):
                    _record_fault(tel, hooks.write_gate(), e,
                                  seam=getattr(e, "seam", None)
                                  or "dispatch",
                                  injected=False, step=group[0].step)
                if used[cls] >= policy.budget(cls):
                    if cls == "resource" and policy.degrade \
                            and hooks.rebuild is not None:
                        raise _DegradeSignal(e)
                    final_failure(e, group[0].step, attempts=total + 1,
                                  snapshot=snapshot, cursor=cursor,
                                  fault_class=cls)
                used[cls] += 1
                total += 1
                retry_record(group[0].step, total, e, fault_class=cls)
                backoff(cls, used[cls], "dispatch")
                # Transient-failure recovery: rebuild a fresh sharded state
                # from the snapshot and re-dispatch the same host batches.
                state = hooks.restage(snapshot)

    def reanchor(state):
        """Fresh known-good snapshot: everything before it is durable,
        everything after it is replayable from it."""
        nonlocal anchor
        with obs.span("snapshot", timer):
            anchor = hooks.snapshot(state)
        del since_anchor[:]

    def recover(state, e, entry=None, sync_group=None, sync_life=None):
        """A group's program failed — either surfaced at its completion
        token (``entry``: the oldest in-flight group; tokens are blocked in
        dispatch order, so it is provably the EARLIEST failure) or raised
        by the dispatch call itself (``sync_group``: dispatched but never
        accounted).  Attribution is to that group's first step, never to
        whichever later group happened to block first.

        ISSUE 15: the exception is CLASSIFIED first (transient / resource
        / permanent / preemption) and the class decides the outcome —
        preemption re-raises to the stream-level drain-checkpoint-exit
        handler (the window is healthy, the signal is not a device
        error); permanent fails immediately; transient/resource replay
        every group since the anchor snapshot serially on their per-class
        budgets (groups before the failure re-dispatch free — the anchor
        is their only rebuild point — and the failed group is charged one
        attempt); a resource-classed budget exhaustion steps down the
        degradation ladder (rebuild the engine on a cheaper config,
        replay again) until the ladder runs out."""
        nonlocal retired_groups, engine, cur_config
        cls = faults_mod.classify(e)
        fail_step = (entry.step_first if entry is not None
                     else sync_group[0].step)
        if not (isinstance(e, faults_mod.FaultError) and e.injected):
            _record_fault(tel, hooks.write_gate(), e,
                          seam=getattr(e, "seam", None)
                          or ("token-wait" if entry is not None
                              else "dispatch"),
                          injected=False, step=fail_step)
        if cls == "preemption":
            # Not a device error: the window's other groups are healthy
            # and an unenrolled sync group simply replays after resume.
            raise e
        cursor = entry.cursor_before if entry is not None else bytes_done
        budget = policy.budget(cls)
        can_ladder = (cls == "resource" and policy.degrade
                      and hooks.rebuild is not None)
        if hooks.retry <= 0 or hooks.restage is None \
                or (budget <= 0 and not can_ladder):
            final_failure(e, fail_step, attempts=1, cursor=cursor,
                          fault_class=cls)
        replay = list(since_anchor)
        if sync_group is not None:
            replay.append((sync_group, cursor))
        fail_idx = next(i for i, (g, _) in enumerate(replay)
                        if g[0].step == fail_step)
        # Lifecycle records still owed: the doomed window's groups never
        # retired (their records are emitted after the replay below, with
        # coarse serialized timestamps — the replay IS when they actually
        # completed); groups in `since_anchor` but NOT in the window
        # retired earlier and already own a record, so the replay must not
        # emit a second one for them (exactly-one-per-retired-group).
        lost = {en.step_first: en.life for en in window}
        if sync_group is not None and sync_life is not None:
            lost[sync_group[0].step] = sync_life
        # Quiesce the doomed window before replaying: the OTHER in-flight
        # groups' programs may still be RUNNING — an injected token-wait
        # fault abandons a healthy window — and a serial replay racing
        # them contends for staging buffers and the backend's execution
        # machinery (the interpret-mode pallas runtime is not safe under
        # that concurrency; observed corrupting replay outputs).  Their
        # tokens resolve promptly — the programs complete or fail, and a
        # real hang is bounded by token_timeout_s — and any error they
        # surface is subsumed by the replay below.
        # A REAL TokenTimeout already spent the full timeout on the
        # failed entry's token (and on a genuinely hung device would
        # spend it again): its quiesce outcome is known, skip it.  An
        # INJECTED token-wait fault raised before the wait ever ran —
        # that entry's program may still be executing, so it must be
        # quiesced like the rest.
        already_waited = (entry is not None
                          and isinstance(e, faults_mod.TokenTimeout)
                          and not e.injected)
        for doomed in window:
            if already_waited and doomed is entry:
                continue
            try:
                if policy.token_timeout_s:
                    _wait_token_timed(doomed.token, policy.token_timeout_s)
                else:
                    _wait_token(doomed.token)
            except Exception:
                pass
        # Drop the doomed window, returning pool-issued staging buffers so
        # their ids never dangle in the pool's issued set (a freed buffer's
        # id can be reused by a reader-owned array, which give() would then
        # wrongly adopt).  A doomed dispatch's H2D may still read a buffer
        # we later refill — harmless: its output is discarded and the
        # replay restages fresh device state from the anchor.
        while window:
            dropped = window.popleft()
            if hooks.stage_release is not None:
                hooks.stage_release(dropped.staged)
        # The windowed failure charges one attempt against its class —
        # unless the class has no budget at all (the pure-ladder path,
        # where the first resource fault goes straight to a degrade).
        charged = 1 if budget > 0 else 0
        if charged:
            retry_record(fail_step, 1, e, fault_class=cls)
            backoff(cls, 1, "dispatch")
        used = [charged]
        while True:  # degradation-ladder loop (one pass when no degrade)
            try:
                state = hooks.restage(anchor)
                done: list = []
                for i, (group, group_cursor) in enumerate(replay):
                    replay_t0 = time.perf_counter()
                    state, replay_stats = serial_dispatch(
                        state, group,
                        attempts_used=charged if i == fail_idx else 0,
                        used_out=used if i == fail_idx else None,
                        cursor=group_cursor, charged_class=cls)
                    done.append((i, group, replay_t0,
                                 time.perf_counter(), replay_stats))
                break
            except _DegradeSignal as ds:
                nd = faults_mod.next_degrade(_config_summary(cur_config))
                if nd is None:
                    final_failure(ds.error, fail_step,
                                  attempts=used[0] + 1, cursor=cursor,
                                  fault_class="resource")
                step_name, field, degraded = nd
                was = _config_summary(cur_config)[field]
                cur_config = _apply_degrade(cur_config, field, degraded)
                pipe.setdefault("degrade_steps", []).append(step_name)
                tel.registry.counter("executor.degrade_steps",
                                     ladder_step=step_name).inc()
                tel.event("degrade", ladder_step=step_name, field=field)
                tel.ledger_write(
                    "degrade", step=fail_step, ladder_step=step_name,
                    field=field, **{"from": was, "to": degraded},
                    fault_class="resource", error=repr(ds.error),
                    write=hooks.write_gate())
                log_event(logger, "degradation ladder step",
                          ladder_step=step_name, field=field,
                          to=degraded)
                engine = hooks.rebuild(cur_config)
        # Emit the owed lifecycle records only for the FINAL successful
        # round: an aborted ladder round's groups were invalidated with
        # their state, so emitting them would duplicate records (and
        # double-fold data stats).  Coarse serialized stamps: the
        # original enqueue was doomed with the window, so the replay's
        # blocking re-dispatch is the group's real completion interval.
        # Data stats fold only for groups that never retired: a group
        # replayed from the anchor but retired earlier already
        # contributed its counters once.
        for i, group, t0, t1, replay_stats in done:
            life = lost.pop(group[0].step, None)
            if life is not None:
                life = dict(life, staged_at=round(t0, 6),
                            dispatched_at=round(t0, 6))
                _group_record(tel, hooks.write_gate(), life,
                              token_ready_at=t1, retired_at=t1,
                              wait_s=t1 - t0,
                              retries=used[0] if i == fail_idx else 0,
                              data=group_stats_data(replay_stats))
                retired_groups += 1
                heartbeat()
        tel.registry.counter("executor.retry_recoveries").inc()
        if sync_group is not None:
            # The sync-failed group raised inside `dispatch` itself, so it
            # was never enrolled: account it now that it landed.  It ran
            # serially, alone — depth 1, the serialized-window contract
            # (ledger consumers rely on inflight_depth >= 1, and the depth
            # mean divides by dispatch_groups).  Its charged attempts live
            # on its GROUP record — the one place replay retries are
            # charged on BOTH recovery paths (ISSUE 15 satellite: the
            # async path's step record is written at dispatch, before any
            # retry can exist, so the group record is the only consistent
            # carrier).
            record_depth(1)
            account(sync_group, depth=1,
                    group_bytes=sync_life["group_bytes"] if sync_life
                    else int(sum(int(b.lengths.sum()) for b in sync_group)))
        reanchor(state)
        return state

    def token_wait(entry):
        """The window's completion wait behind the token-wait seam
        (ISSUE 15): the plan may inject here (the mid-window ASYNC fault
        — it surfaces at the oldest group's retire, exactly like a real
        late device error), and ``policy.token_timeout_s`` bounds the
        wall-clock so a hung device reads as a typed TokenTimeout."""
        if plan is not None:
            cross("token-wait")
        if policy.token_timeout_s:
            _wait_token_timed(entry.token, policy.token_timeout_s)
        else:
            _wait_token(entry.token)

    def retire_oldest(state, phase="retire_wait"):
        """Block until the oldest in-flight group's program completed (its
        completion token is ready); recycle its staging buffer.  An error
        surfacing here belongs to exactly this group."""
        entry = window[0]
        wait_t0 = time.perf_counter()
        try:
            if phase is not None:
                with obs.span(phase, timer):
                    token_wait(entry)
            else:
                token_wait(entry)
        except Exception as e:
            return recover(state, e, entry=entry)
        token_ready_at = time.perf_counter()
        window.popleft()
        if hooks.stage_release is not None:
            hooks.stage_release(entry.staged)
        _group_record(tel, hooks.write_gate(), entry.life,
                      token_ready_at=token_ready_at,
                      retired_at=time.perf_counter(),
                      wait_s=token_ready_at - wait_t0,
                      data=group_stats_data(entry.stats))
        nonlocal retired_groups
        retired_groups += 1
        heartbeat()
        return state

    def drain_window(state, phase="retire_wait", do_reanchor=True):
        """Retire every in-flight group (checkpoint/file boundaries, full
        retry-mode windows, stream end); with retry, re-anchor the next
        window on a fresh known-good snapshot.  ``since_anchor`` empty
        means the anchor is already current (recover() just replayed and
        re-anchored, or nothing was dispatched since) — skip the redundant
        device->host fetch."""
        while window:
            state = retire_oldest(state, phase)
        if hooks.retry > 0 and do_reanchor and since_anchor:
            reanchor(state)
        return state

    def record_depth(depth):
        """The window-depth statistics behind the run-end `pipeline` dict
        and the `executor.inflight_depth` histogram — one sample per
        dispatched group (enroll and the sync-recover path alike, so the
        depth mean's numerator and denominator can never drift)."""
        pipe["dispatch_groups"] += 1
        pipe["depth_sum"] += depth
        pipe["depth_max"] = max(pipe["depth_max"], depth)
        tel.registry.observe("executor.inflight_depth", depth)

    def account(group, depth, group_bytes):
        """Advance the cursor, bases, and telemetry for one dispatched
        group: the ledger step record is written at dispatch, in step
        order — one per dispatched group, completion observed later.
        ``group_bytes`` comes from the caller's lifecycle record: the
        batch lengths are summed exactly once per group.  Replay retries
        are NOT stamped here (ISSUE 15 satellite): the async recovery
        path's step record is written at dispatch, before any retry can
        exist, so charging them here on the sync path only made the two
        paths disagree — the group record is the one consistent carrier.

        The ledger-append seam crosses here (ISSUE 15): an injected
        append fault is recorded and ABSORBED — observing must never take
        down the observed run, so the policy outcome for the telemetry
        plane is always degrade-to-unobserved, not death."""
        nonlocal bytes_done, step_index, last_file_dispatched
        last_file_dispatched = group[-1].file_index
        for b in group:
            bases_list.append(b.base_offsets)
        bytes_done += group_bytes
        step_index = group[-1].step + 1
        skip_record = False
        if plan is not None:
            try:
                cross("ledger-append")
            except faults_mod.FaultError as fe:
                if fe.fault_class == "preemption":
                    raise
                skip_record = True
                log_event(logger, "ledger append fault absorbed",
                          error=repr(fe))
        if not skip_record:
            tel.step_record(step_first=group[0].step,
                            step_last=group[-1].step,
                            group_bytes=group_bytes,
                            cursor_bytes=bytes_done, timer=timer,
                            inflight_depth=depth,
                            write=hooks.write_gate())
        heartbeat()
        if progress_every and step_index % progress_every < len(group):
            log_event(logger, "progress", step=step_index, bytes=bytes_done)

    def enroll(out, stats, staged, group, cursor_before, life):
        """Window bookkeeping + accounting for a DISPATCHED group.  Runs
        outside the recover() routing on purpose: a failure here (say the
        ledger's disk filling up mid step-record) is host bookkeeping, not
        a device fault — routing it through recover would replay a group
        that is already in the window and partially accounted, dispatching
        and counting it twice.  It propagates loudly instead, exactly as
        the pre-window loop's accounting (outside its retry try) did."""
        window.append(_Inflight(
            token=_state_token(out), staged=staged,
            step_first=group[0].step, cursor_before=cursor_before,
            life=life, stats=stats))
        if hooks.retry > 0:
            # Paired with the pre-group cursor, so a replay that later
            # exhausts its retries can report where THIS group started.
            since_anchor.append((group, cursor_before))
        depth = len(window)
        record_depth(depth)
        account(group, depth, life["group_bytes"])

    def stack_bases():
        return np.stack(bases_list) if bases_list \
            else np.zeros((0, engine.n_devices), np.int64)

    def save_snapshot(state_host):
        """The checkpoint write behind the checkpoint-save seam +
        policy (ISSUE 15): injected AND real save failures retry on the
        per-class budget (the save is idempotent — atomic tmp+rename),
        and an exhausted budget DEGRADES — fault recorded, loud log, the
        run continues without this snapshot — instead of killing a
        healthy stream.  Durability is reduced; results are not.
        Returns True when the snapshot landed."""
        attempt = 0
        while True:
            try:
                if plan is not None:
                    cross("checkpoint-save")
                if hooks.write_gate():
                    ckpt_mod.save(checkpoint_path, state_host, step_index,
                                  bytes_done, stack_bases(),
                                  fingerprint=fingerprint,
                                  file_index=last_file_dispatched)
                return True
            except faults_mod.PreemptionFault:
                raise
            except Exception as ce:
                ccls = faults_mod.classify(ce)
                if not (isinstance(ce, faults_mod.FaultError)
                        and ce.injected):
                    _record_fault(tel, hooks.write_gate(), ce,
                                  seam="checkpoint-save", injected=False,
                                  step=step_index)
                if attempt >= policy.budget(ccls):
                    log_event(logger,
                              "checkpoint save failed; continuing "
                              "without this snapshot",
                              error=repr(ce), fault_class=ccls)
                    return False
                attempt += 1
                retry_record(step_index, attempt, ce, fault_class=ccls,
                             seam="checkpoint-save")
                backoff(ccls, attempt, "checkpoint-save")

    def flush(state, group):
        """Dispatch a group of consecutive batches (one superstep, split at
        any interior checkpoint boundaries)."""
        for sub in split_at_checkpoints(group):
            state = flush_one(state, sub)
        return state

    def flush_one(state, group):
        """Dispatch one group of consecutive batches as a single program,
        keeping at most ``window_cap`` groups in flight."""
        nonlocal last_ckpt, anchor
        # Make room FIRST, so the device never holds more than the window.
        # retry=0 slides (retire just the oldest: continuous pipeline);
        # retry>0 drains the full window and re-anchors (the snapshot that
        # makes a replay possible is only fetchable when nothing is in
        # flight — the state array is donated into every next dispatch).
        if hooks.retry > 0:
            if len(window) >= window_cap:
                # One count PER RETIRED GROUP (the drain retires the whole
                # window), so full_frac = full_retires/dispatch_groups means
                # the same thing in both modes: the share of groups retired
                # because the window was at capacity (~1 = device-bound).
                pipe["full_retires"] += len(window)
                state = drain_window(state)
            if anchor is None:
                reanchor(state)
        else:
            while len(window) >= window_cap:
                pipe["full_retires"] += 1
                state = retire_oldest(state)
            # Window-boundary partial merge (ISSUE 20 leg 2): a full
            # window's worth of groups has retired since the last
            # boundary — drain the local tables into the resident
            # accumulator (async; the DCN transfer hides behind the
            # next window) and reset them.  The dispatch is host work
            # ("dispatch" phase); the previous partial's lazy retire is
            # a wait ("retire_wait"), normally instant.
            if overlap is not None and overlap.due(retired_groups):
                with obs.span("retire_wait", timer):
                    overlap.retire()
                with obs.span("dispatch", timer):
                    state = overlap.boundary(state, step_index,
                                             retired_groups)
        cursor_before = bytes_done
        # Lifecycle (ISSUE 7): read_at = the group's first batch leaving
        # the reader; staged_at is stamped by _group_life right here, just
        # before staging begins; later steps' arrival stamps are dropped
        # (the reader lane spans read + superstep accumulation).
        read_at = read_t.pop(group[0].step, None)
        for b in group[1:]:
            read_t.pop(b.step, None)
        life = _group_life(group, read_at,
                           int(sum(int(b.lengths.sum()) for b in group)))
        if hooks.host_rows is not None:
            life["host_bytes"] = int(sum(
                int(b.lengths[hooks.host_rows].sum()) for b in group))
        try:
            out, stats, staged = dispatch(state, group)
        except Exception as e:
            # Only the dispatch itself routes here: a device/staging fault
            # for a group that was never enrolled (see enroll()).
            state = recover(state, e, sync_group=group, sync_life=life)
        else:
            life["dispatched_at"] = round(time.perf_counter(), 6)
            enroll(out, stats, staged, group, cursor_before, life)
            state = out
        if plan is not None:
            # Whole-process kill (ISSUE 15, multi-host chaos): crossed
            # once per dispatched group, AFTER the group is enrolled and
            # accounted — the hard-kill lands between groups, exactly
            # where a platform reclaim would.
            cross("process-kill")
        if (checkpoint_every and checkpoint_path
                and step_index // checkpoint_every > last_ckpt):
            # Checkpoint boundary: retire everything (a failure discovered
            # here is attributed per group by the token order, instead of
            # surfacing inside the snapshot fetch blamed on the boundary),
            # then snapshot the state and ingest cursor.  The snapshot
            # format holds ANY job state pytree (tables, sketched states,
            # grep scalars alike).  Multi-host: every process pays the
            # fetch (it is a collective there), only the gate-holder
            # touches the filesystem.
            state = drain_window(state)
            pipe["boundary_drains"] += 1
            last_ckpt = step_index // checkpoint_every
            # Checkpoint boundaries are window boundaries too (ISSUE 20
            # leg 2): drain the local tables into the accumulator so the
            # snapshot packs {"s": reset local state, "a": accumulator}
            # — resume restores both and the stream stays byte-exact.
            if overlap is not None:
                with obs.span("retire_wait", timer):
                    overlap.retire()
                with obs.span("dispatch", timer):
                    state = overlap.boundary(state, step_index,
                                             retired_groups)
            ck_before = timer["checkpoint"]
            with obs.span("checkpoint", timer):
                # retry mode just re-anchored on this very state: reuse the
                # fetch instead of paying a second device->host round.
                # file_index makes the snapshot boundary-aware: resuming
                # a checkpoint that ends a corpus member must still fire
                # the job's on_input_boundary hook on the next member's
                # first batch (the carry reset happens AFTER this save
                # in the stream loop).
                state_host = anchor if hooks.retry > 0 \
                    else hooks.snapshot(state)
                if overlap is not None:
                    state_host = {"s": state_host,
                                  "a": overlap.host_accum()}
                saved = save_snapshot(state_host)
            tel.event("checkpoint", step=step_index, cursor_bytes=bytes_done)
            if saved:
                tel.ledger_write(
                    "checkpoint", step=step_index, cursor_bytes=bytes_done,
                    save_s=round(timer["checkpoint"] - ck_before, 6),
                    path=checkpoint_path, write=hooks.write_gate())
                log_event(logger, "checkpoint", step=step_index,
                          path=checkpoint_path, writer=hooks.write_gate())
        return state

    # Jobs with cross-row sequential state (grep's line carry) reset it at
    # file boundaries — files are independent corpora.  Optional, duck-typed
    # like the other hooks; transitions are rare (once per corpus member),
    # so the early superstep flush they force costs nothing measurable.
    boundary_hook = getattr(job, "on_input_boundary", None)
    # Resume restores which corpus member the snapshot's last batch came
    # from, so a snapshot saved at a file seam still triggers the boundary
    # hook on the next file's first batch (advisor round 2: last_file=None
    # after resume silently skipped the reset and leaked grep's line carry).
    last_file: Optional[int] = resumed_file
    # Prefetch: host-side chunking runs ahead of device compute, co-tuned
    # with the window (Config.prefetch_depth: deep enough to feed a full
    # window).  The manual iterator lets read_wait be timed: time spent
    # HERE is the reader failing to keep ahead of the device.
    def read_guarded():
        """One reader read behind the reader-read seam (ISSUE 15): the
        injected fault fires BEFORE the underlying ``next``, so retrying
        it on the policy budget is always safe.  A REAL reader error is
        recorded as a typed fault and propagates — the prefetch iterator
        is dead after raising, and re-nexting a dead generator would read
        as a silent end-of-stream (a truncation, the one unforgivable
        outcome)."""
        attempt = 0
        while True:
            try:
                cross("reader-read")
                return next(it, None)
            except faults_mod.FaultError as fe:
                if not fe.injected or fe.fault_class == "preemption":
                    raise
                if attempt >= policy.budget(fe.fault_class):
                    raise
                attempt += 1
                retry_record(step_index, attempt, fe,
                             fault_class=fe.fault_class, seam="reader-read")
                backoff(fe.fault_class, attempt, "reader-read")
            except Exception as re_:
                _record_fault(tel, hooks.write_gate(), re_,
                              seam="reader-read", injected=False,
                              step=step_index)
                raise

    it = iter(reader_mod.prefetch(
        reader_mod.iter_batches_multi(path, engine.n_devices,
                                      config.chunk_bytes,
                                      start_offset=start_offset,
                                      start_step=start_step,
                                      end_offset=end_offset),
        depth=config.resolved_prefetch_depth))
    try:
        while True:
            with obs.span("read_wait", timer):
                batch = next(it, None) if plan is None else read_guarded()
            if batch is None:
                break
            read_t[batch.step] = time.perf_counter()
            if hooks.stage_arrival is not None:
                with obs.span("stage", timer):
                    batch = hooks.stage_arrival(batch)
            if (boundary_hook is not None and last_file is not None
                    and batch.file_index != last_file):
                if pending:
                    state = flush(state, pending)
                    pending = []
                # Retire at the file boundary: a failure in the old file's
                # groups is attributed there, and the boundary hook's state
                # edit invalidates the replay anchor (re-taken lazily).
                state = drain_window(state, do_reanchor=False)
                pipe["boundary_drains"] += 1
                # File boundaries are window boundaries too: ship the old
                # corpus member's counts before the hook edits the carry
                # (partial_reset preserves seam context; the hook then
                # zeroes it exactly as it would on the monolithic state).
                if overlap is not None:
                    with obs.span("retire_wait", timer):
                        overlap.retire()
                    with obs.span("dispatch", timer):
                        state = overlap.boundary(state, step_index,
                                                 retired_groups)
                state = boundary_hook(state)
                anchor = None
                del since_anchor[:]
            last_file = batch.file_index
            pending.append(batch)
            if len(pending) == k:
                state = flush(state, pending)
                pending = []
        for batch in pending:  # remainder: single steps (no extra jit keys)
            state = flush(state, [batch])
        # End-of-stream tail decomposition (the old opaque `drain`):
        # h2d_tail = the last group's staged input still in transfer when
        # the reader ran dry; compute_tail = device work still queued
        # behind it.  Spanned even when empty so the phase keys always
        # exist for reports.
        with obs.span("h2d_tail", timer):
            if window:
                jax.block_until_ready(window[-1].staged)
                # The one per-group H2D completion the loop DOES observe
                # (the reader ran dry, so this wait serializes nothing):
                # the last group's record carries it, giving the timeline
                # a measured h2d lane interval instead of pure inference.
                window[-1].life["h2d_done_at"] = \
                    round(time.perf_counter(), 6)
        with obs.span("compute_tail", timer):
            state = drain_window(state, phase=None, do_reanchor=False)
    except BaseException as pe:
        # Preemption (ISSUE 15): drain the in-flight window (the groups
        # are healthy — the signal is not a device error; their bytes are
        # already accounted), snapshot if a checkpoint is configured, and
        # exit CLEANLY with the resumable cursor.  Caught by CLASS, not
        # type: recover() re-raises REAL preemption-shaped exceptions
        # (SIGTERM/maintenance-event markers) unwrapped, and
        # KeyboardInterrupt — classified preemption — is a BaseException
        # that never even routes through recover().  Anything not
        # preemption-classed re-raises untouched.  The plan is disarmed
        # first so no second injected fault can interrupt the orderly
        # shutdown (a real platform sends one SIGTERM, not a stream).
        if faults_mod.classify(pe) != "preemption":
            raise
        plan = None
        if overlap is not None:
            overlap.disarm()
        state = drain_window(state, do_reanchor=False)
        checkpointed = False
        if checkpoint_path:
            ck_before = timer["checkpoint"]
            with obs.span("checkpoint", timer):
                # The state fetch rides the same absorb-and-continue
                # discipline as the save: under a real preemption the
                # device may already be going away, and an unfetchable
                # state degrades to an uncheckpointed (still orderly)
                # exit, never a crash inside the drain handler.
                try:
                    if overlap is not None:
                        # Preemption is a boundary too: ship the local
                        # tables so the packed snapshot resumes exactly.
                        state = overlap.boundary(state, step_index,
                                                 retired_groups)
                    state_host = hooks.snapshot(state)
                    if overlap is not None:
                        state_host = {"s": state_host,
                                      "a": overlap.host_accum()}
                except Exception as se:
                    _record_fault(tel, hooks.write_gate(), se,
                                  seam="checkpoint-save", injected=False,
                                  step=step_index)
                    log_event(logger,
                              "preemption snapshot fetch failed; "
                              "exiting without checkpoint",
                              step=step_index, error=str(se))
                    state_host = None
                if state_host is not None:
                    checkpointed = save_snapshot(state_host)
            if checkpointed:
                tel.ledger_write(
                    "checkpoint", step=step_index, cursor_bytes=bytes_done,
                    save_s=round(timer["checkpoint"] - ck_before, 6),
                    path=checkpoint_path, preempt=True,
                    write=hooks.write_gate())
        log_event(logger, "preempted; drained and exiting cleanly",
                  step=step_index, cursor=bytes_done,
                  checkpointed=checkpointed)
        raise faults_mod.Preempted(
            step=step_index, cursor_bytes=bytes_done,
            checkpoint_path=checkpoint_path,
            checkpointed=checkpointed) from pe
    n_groups = pipe["dispatch_groups"]
    pipe["depth_mean"] = round(pipe.pop("depth_sum") / n_groups, 2) \
        if n_groups else 0.0
    pipe["window_filled"] = pipe["depth_max"] >= window_cap
    pipe["full_frac"] = round(pipe["full_retires"] / n_groups, 3) \
        if n_groups else 0.0
    # Only stamped when overlap ran: overlap-off runs keep the exact old
    # pipeline dict shape (the ledger A/B control).
    if overlap is not None:
        pipe["partial_merges"] = overlap.partials
    return state, bytes_done, step_index, pipe


def _path_names(path) -> list[str]:
    """Input path(s) as a list of strings for the run-ledger header."""
    import os

    if isinstance(path, (str, bytes, os.PathLike)):
        return [os.fspath(path) if not isinstance(path, bytes)
                else path.decode(errors="backslashreplace")]
    return [_path_names(p)[0] for p in path]


def _geometry_stamp(config) -> dict:
    """run_start kernel-geometry fields (ISSUE 12, ledger v6): the compact
    label always — 'default', a preset name, or 'custom' — plus the full
    field dict on custom runs (a preset/default label already names its
    spec; the A/B compare and the tuner knob read the label)."""
    label = config.geometry_label
    stamp = {"geometry": label}
    if label == "custom":
        stamp["geometry_spec"] = config.resolved_geometry.as_dict()
    return stamp


def _metrics_word_count(value) -> int:
    """Total words inside any finalize result shape, for RunMetrics.

    Finalize results nest: sketch wrappers hold a ``.table`` that may itself
    be a :class:`TopKTable` (top-k + sketch compositions).  Unwrap until the
    CountTable appears; non-table jobs (grep, sample) report 0 here — their
    metrics live in their own result fields.
    """
    for _ in range(3):
        if isinstance(value, (SketchedState, FreqSketchedState, TopKTable)):
            value = value.table
        else:
            break
    return int(value.total_count()) \
        if isinstance(value, table_ops.CountTable) else 0


def run_job(job: MapReduceJob, path, config: Config = DEFAULT_CONFIG,
            mesh=None, merge_strategy: Optional[str] = None,
            checkpoint_path: Optional[str] = None, checkpoint_every: int = 0,
            logger=None, progress_every: int = 50,
            byte_range: Optional[tuple[int, int]] = None,
            retry: int = 0, telemetry=None) -> RunResult:
    """Stream ``path`` through ``job`` over the mesh; see module docstring.

    ``telemetry`` (:class:`...obs.telemetry.Telemetry`, optional): per-step
    run-ledger records, flight-recorder forensics on failure, and metrics-
    registry counters for the run.  For jobs with data-stats hooks
    (the wordcount family) a telemetered run also runs the engine in
    stats mode (ISSUE 8): per-group data-plane counters ride the
    ``group`` records and one per-run ``data`` summary record lands —
    results stay byte-identical.  ``None`` disables all of it at zero
    per-step cost and keeps the exact uninstrumented step programs.  The
    caller owns the handle's lifetime (``tel.close()`` flushes the
    ledger).

    ``retry``: retries per step group on a transient dispatch failure.  The
    device state is donated into each step, so with ``retry > 0`` the
    executor keeps a host-side leaf-copy of the known-good state — anchored
    per dispatch window (``Config.inflight_groups``; one device->host fetch
    per window drain, the amortized cost of replayability) — plus the
    still-alive host batches, rebuilds a fresh sharded state from the
    anchor, and replays the window with the failed group charged one
    attempt (``inflight_groups=1``: exactly the old per-group snapshot +
    retry).  ``retry=0`` (default) keeps the full async pipeline and
    surfaces the failure with the resume cursor, attributed to the right
    step by its completion token; checkpoint/resume is then the recovery
    path.

    ``byte_range``: restrict ingestion to ``[lo, hi)`` — this host's slice of
    a multi-host corpus (:func:`...parallel.distributed.host_byte_range`,
    pre-aligned with ``align_range_to_separator``).  The returned value is
    then this host's *partial* state, to be merged host-side
    (``table_ops.merge``) across hosts.  Note this per-host-driven mode uses
    a host-LOCAL mesh: run_job stages plain numpy batches, so a mesh spanning
    non-addressable devices is not supported here — for one global SPMD
    program over all hosts use :func:`run_job_global`.
    """
    if retry < 0:
        raise ValueError(f"retry must be >= 0, got {retry}")
    logger = logger or get_logger()
    tel = obs.maybe(telemetry)
    # The strategy the engine builds: an explicit argument wins (the
    # pre-ISSUE-20 call convention); None defers to the config, whose
    # unresolved 'auto' behaves as 'tree' — 'auto' resolution against
    # the redplan profile is the CLI/bench driver's job.
    merge_strategy = merge_strategy if merge_strategy is not None \
        else config.resolved_merge_strategy
    # Unified failure policy + fault plan (ISSUE 15): the legacy `retry`
    # counter resolves into per-class budgets (None policy = exactly the
    # old semantics), and the policy's dispatch budget is what arms the
    # snapshot/replay machinery below — an explicit policy with budgets
    # enables replay without the caller touching `retry`.
    plan = faults_mod.FaultPlan.resolve(config.fault_plan)
    policy = faults_mod.FailurePolicy.resolve(config.failure_policy,
                                              retry=retry)
    retry = policy.dispatch_budget
    if config.merge_overlap and retry > 0:
        if config.failure_policy is None:
            raise ValueError(
                "merge_overlap requires retry=0: the replay anchor "
                "snapshots local state that a window-boundary partial "
                "merge has already shipped into the accumulator — "
                "checkpoint/resume is the recovery path for overlapped "
                "runs")
        # An explicit policy keeps its per-class budgets on the seams
        # that never replay shipped state (reader, checkpoint-save, and
        # collective-finish — injected collective faults fire BEFORE the
        # program runs, so retrying re-dispatches nothing the
        # accumulator already holds), matching run_job_global's
        # contract.  Window replay alone stays disarmed: its anchor
        # would snapshot local tables a partial merge already drained.
        retry = 0
    mesh = mesh if mesh is not None else data_mesh()
    # Shard over EVERY mesh axis: a 2-D ('replica','data') mesh contributes
    # all its devices to the data-parallel stream (the Engine linearizes the
    # axes row-major; hierarchical merge reduces innermost-first).
    axes = tuple(mesh.axis_names)
    n_dev = mesh.size  # == product over all axes, which we shard in full
    # Data-plane telemetry (ISSUE 8): telemetered runs of jobs with stats
    # hooks run the engine in stats mode — each step also returns a tiny
    # DataStats pytree fetched at group retirement and folded into the
    # `group` records + the per-run `data` summary record.  Results stay
    # byte-identical; telemetry=None keeps the exact pre-ISSUE-8 programs.
    from mapreduce_tpu.ops import datastats as datastats_ops

    data_stats = tel.enabled and datastats_ops.supports(job)
    engine = Engine(job, mesh, axis=axes if len(axes) > 1 else axes[0],
                    merge_strategy=merge_strategy, data_stats=data_stats)
    overlap = _OverlapMerger(engine, tel, lambda: True, plan, policy,
                             logger, merge_strategy,
                             config.inflight_groups) \
        if config.merge_overlap else None
    data_agg = datastats_ops.DataAggregator.for_run(config, n_dev) \
        if data_stats else None
    range_lo, range_hi = byte_range if byte_range is not None else (0, None)

    timer = metrics_mod.PhaseTimer()
    timer.start("total")

    start_step, start_offset = 0, range_lo
    bases_list: list[np.ndarray] = []
    fingerprint = ckpt_mod.run_fingerprint(
        path, n_dev, config.chunk_bytes, backend=config.resolved_backend(),
        pallas_max_token=config.pallas_max_token, byte_range=byte_range,
        job_identity=job.identity()) \
        if checkpoint_path else None
    ck_fallback = None
    if checkpoint_path and ckpt_mod.exists(checkpoint_path):
        # An abstract state (shapes/dtypes only, no device allocation) is
        # the structural template: any drift in job kind, capacities,
        # sketch precision, or device count surfaces as CheckpointMismatch
        # (shapes are ground truth).  A torn/corrupt snapshot falls back
        # to the previous good one (ISSUE 15 satellite; the fallback is
        # noted in the ledger after run_start) instead of crashing.
        # Overlapped runs pack {"s": local state, "a": accumulator}
        # (checkpoint boundaries always ship a partial first, so the
        # accumulator exists in every overlap snapshot); the packed
        # structure itself guards against resuming across an overlap
        # on/off flip.
        template = jax.eval_shape(engine.init_states)
        if overlap is not None:
            template = {"s": template, "a": overlap.accum_template()}
        (state_np, start_step, start_offset, bases_arr, resumed_file), \
            ck_fallback = ckpt_mod.load_resilient(
                checkpoint_path, template=template,
                expect_fingerprint=fingerprint)
        if overlap is not None:
            overlap.accum = jax.device_put(state_np["a"],
                                           engine._replicated)
            state_np = state_np["s"]
        state = _owned_state(jax.device_put(state_np, engine._sharded))
        bases_list = list(bases_arr)
        log_event(logger, "resumed from checkpoint", step=start_step,
                  offset=start_offset)
        if ck_fallback is not None:
            log_event(logger, "corrupt checkpoint; resumed from previous "
                      "good snapshot", **ck_fallback)
    else:
        state = engine.init_states()
        resumed_file = None

    # Each batch is staged to the device the moment the reader hands it
    # over (stage_arrival): the async H2D overlaps the previous group's
    # compute, the phase decomposition attributes placement to "stage",
    # and superstep groups stack ALREADY-RESIDENT [D, C] arrays on device
    # — shipping one K-times-larger stacked host array at dispatch time
    # measured ~7x slower per byte (round 5, on a remote chip since
    # retired).
    import jax.numpy as jnp

    # With retry > 0 the batches must stay HOST numpy: the replay contract
    # re-dispatches the still-alive host buffers with a FRESH H2D per
    # attempt — an arrival-staged device array could itself be the failed
    # (error-poisoned) object, making every retry re-raise.  The stacked
    # staging buffer comes from a pool recycled at group retirement, so the
    # window costs O(inflight_groups) buffers, not one alloc per group.
    pool = _StagePool() if retry > 0 else None

    def stage_group_np(g):
        buf = pool.take((g[0].data.shape[0], len(g), g[0].data.shape[1]),
                        g[0].data.dtype)
        np.stack([b.data for b in g], axis=1, out=buf)
        return buf

    def rebuild(new_config: Config):
        """Degradation-ladder engine rebuild (ISSUE 15): same mesh, same
        state SHAPES (the ladder only moves kernel-choice knobs — each
        bit-identity-tested), cheaper programs.  The job is rebound so
        every map call site reads the degraded knobs; the anchor snapshot
        restages into the new engine unchanged."""
        nonlocal job, engine
        job = _job_with_config(job, new_config)
        engine = Engine(job, mesh, axis=axes if len(axes) > 1 else axes[0],
                        merge_strategy=merge_strategy, data_stats=data_stats)
        return engine

    hooks = _StreamHooks(
        stage_single=lambda b: b.data,
        stage_group=stage_group_np if retry > 0 else
        (lambda g: jnp.stack([b.data for b in g], axis=1)),
        # An honest COPY, not np.asarray: on the CPU backend np.asarray
        # of a jax array is a zero-copy VIEW of the live buffer, and the
        # state it views is donated into the next dispatch — a snapshot
        # that can be overwritten is not a known-good anchor.
        snapshot=lambda s: jax.tree.map(lambda x: np.array(x, copy=True),
                                        s),
        # _owned_state: the restaged tree is donated into the next step —
        # a raw device_put result is not donation-safe (see _owned_state).
        restage=lambda s_np: _owned_state(
            jax.device_put(s_np, engine._sharded)),
        write_gate=lambda: True,
        retry=retry,
        stage_release=pool.give if retry > 0 else None,
        stage_arrival=None if retry > 0 else (lambda b: dataclasses.replace(
            b, data=jax.device_put(b.data, engine.sharding))),
        rebuild=rebuild,
        overlap=overlap)
    if jax.process_count() > 1:
        # Per-host-driven multi-host (mode a): each host owns its whole
        # ledger file already, so no second shard file — but the records
        # get the v7 host stamp + clock so obs/fleet.py can merge the
        # per-host ledgers into one fleet timeline (ISSUE 13).
        from mapreduce_tpu.parallel import distributed as dist

        tel.attach_host(jax.process_index(), jax.process_count(),
                        local_devices=len(jax.local_devices()),
                        clock=dist.run_epoch(), shard=False)
    tel.registry.counter("executor.runs", driver="run_job").inc()
    # run_start stamps the fault plan's canonical spec (ISSUE 15, ledger
    # v9) so a chaotic ledger names its own chaos; absent when injection
    # is off, keeping fault-free records byte-identical to v8 shapes.
    chaos_stamp = {"fault_plan": plan.spec} if plan is not None else {}
    tel.ledger_write("run_start", driver="run_job", job=job.identity(),
                     devices=n_dev, chunk_bytes=config.chunk_bytes,
                     superstep=config.superstep,
                     backend=config.resolved_backend(),
                     map_impl=config.map_impl,
                     combiner=config.resolved_combiner,
                     **_geometry_stamp(config), **chaos_stamp,
                     merge_strategy=merge_strategy,
                     **({"merge_overlap": True} if config.merge_overlap
                        else {}),
                     input=_path_names(path),
                     resume_step=start_step, resume_offset=start_offset,
                     retry=retry)
    if ck_fallback is not None:
        # The corrupt-checkpoint fallback's ledger note (ISSUE 15
        # satellite): a real checkpoint-load fault, observed and healed.
        tel.ledger_write("fault", seam="checkpoint-load",
                         fault_class="transient", injected=False,
                         error=ck_fallback["error"],
                         fallback=ck_fallback["loaded"],
                         corrupt=ck_fallback["corrupt"])
    timer.start("stream")
    try:
        state, bytes_done, _, pipe = _drive_stream(
            engine, job, config, path, state, hooks,
            start_step=start_step, start_offset=start_offset,
            end_offset=range_hi, bases_list=bases_list,
            checkpoint_path=checkpoint_path, checkpoint_every=checkpoint_every,
            fingerprint=fingerprint, resumed_file=resumed_file,
            logger=logger, progress_every=progress_every, timer=timer,
            telemetry=tel, data_agg=data_agg, plan=plan, policy=policy)
        # Residual drain: the stream loop already retired every in-flight
        # group (h2d_tail/compute_tail decompose what this phase used to
        # lump together); this keeps the stream/reduce boundary honest.
        with obs.span("drain", timer):
            jax.block_until_ready(state)
        timer.stop("stream")

        with obs.span("reduce", timer):
            # Overlap: retire the last in-flight partial (its ledger
            # record lands with its real interval), then the residual
            # finish merges only what arrived after the last boundary.
            if overlap is not None:
                overlap.retire()
            fin_t0 = time.perf_counter()
            if overlap is not None:
                value = _collective_call(
                    lambda: engine.finish_residual(overlap.accum, state),
                    plan, policy, tel, True, logger)
            else:
                value = _collective_finish(engine, state, plan, policy,
                                           tel, True, logger)
            value = jax.tree.map(np.asarray, value)  # block + fetch the result
            # One op='finish' `collective` record per run (ISSUE 13;
            # op='partial' records joined it in ledger v10): the observed
            # finish interval + merge strategy — the fleet timeline's
            # `collective` lane (strategy builds stay registry metrics).
            tel.ledger_write("collective", op="finish",
                             strategy=merge_strategy,
                             started_at=round(fin_t0, 6),
                             ended_at=round(time.perf_counter(), 6))
    except faults_mod.Preempted:
        # Orderly preemption shutdown (ISSUE 15), not a failure: the
        # stream drained, the snapshot (if configured) landed, and the
        # exception carries the resumable cursor — no flight dump.
        raise
    except Exception as e:
        # Dispatch failures already dumped inside _drive_stream (with step
        # context); this catches everything else on the streaming path —
        # reader errors, drain/finish failures — so ANY crashed telemetered
        # run leaves forensics.  flight_dump is idempotent per run: the
        # first (most specific) dump wins.
        tel.flight_dump(context={"where": "run_job", "error": repr(e)})
        raise
    total_s = timer.stop("total")

    _finalize_pipeline(pipe, timer, tel)
    data_rec = None
    if data_agg is not None and data_agg.groups:
        # One per-run data-plane summary record (ISSUE 8) — written before
        # run_end so "no run_end = did not complete" stays the last-record
        # invariant.  obs/datahealth.py classifies this dict; the window
        # autotuner (ISSUE 10) reads it next to the PR-7 bottleneck.
        data_rec = data_agg.run_record()
        tel.ledger_write("data", **data_rec)
        tel.note_data(data_rec)
    # Online autotune hint (ISSUE 10): written after the data record and
    # before run_end, so the tune record can read everything this run
    # measured while "no run_end = did not complete" stays true.
    tune = _autotune_hint(config, tel, pipe, timer, data_rec, logger) \
        if config.autotune == "hint" else None
    words = _metrics_word_count(value)
    # bytes_done is the absolute resume CURSOR (checkpoints store it); the
    # throughput metric counts only bytes this run actually streamed.
    m = metrics_mod.RunMetrics(bytes_processed=bytes_done - range_lo, words_counted=words,
                               elapsed_s=total_s, phases=dict(timer.phases))
    tel.ledger_write("run_end", **m.as_dict(), pipeline=pipe)
    log_event(logger, "run complete", **m.as_dict())
    bases = np.stack(bases_list) if bases_list else np.zeros((0, n_dev), np.int64)
    return RunResult(value=value, metrics=m, bases=bases, pipeline=pipe,
                     tune=tune)


def run_job_global(job: MapReduceJob, path, config: Config = DEFAULT_CONFIG,
                   mesh=None, merge_strategy: Optional[str] = None,
                   checkpoint_path: Optional[str] = None,
                   checkpoint_every: int = 0,
                   logger=None, progress_every: int = 50,
                   telemetry=None) -> RunResult:
    """Multi-host mode (b) as one entry point: ONE global SPMD program over
    every chip of every process (round-3 review #5; the 100 GB / v5e-256
    BASELINE config runs through this).

    Every process calls this with the same arguments after
    :func:`...parallel.distributed.initialize`.  Per process:

      * the mesh spans ALL processes' devices
        (:func:`...parallel.distributed.global_data_mesh` by default);
      * the reader runs identically everywhere (same deterministic chunk
        geometry — cut offsets must agree across processes), but each
        process STAGES only its own contiguous block of shard rows
        (``host_shards``) via ``device_put_local``, so no process ships
        another's data over DCN;
      * the engine step is the same jitted SPMD program on every process
        (multi-controller SPMD: identical programs, local data);
      * the collective ``finish`` replicates the merged result to every
        process — the returned ``RunResult`` is identical everywhere;
        report/print on :func:`...parallel.distributed.is_coordinator`.

    Checkpointing: the sharded state is fetched with one all-gather round
    (:meth:`Engine.replicate_to_host`) and ONLY the coordinator writes the
    snapshot (``checkpoint_path`` should be on storage the coordinator owns;
    resume requires every process to read it — shared filesystem, or
    distribute the file before relaunch).  Resume re-stages each process's
    own shard rows from the snapshot.  Step retry is not offered here: a
    failed collective leaves peer processes blocked mid-program, so the
    recovery path for global runs IS checkpoint/resume (SURVEY §5 failure
    detection: the jax.distributed heartbeat surfaces dead peers).

    Differences from :func:`run_job`: no ``byte_range`` (the global program
    consumes the whole corpus; per-host byte ranges are mode (a)), no
    ``retry``, and single-buffer convenience staging is replaced by
    ``device_put_local``.
    """
    from mapreduce_tpu.parallel import distributed as dist

    logger = logger or get_logger()
    tel = obs.maybe(telemetry)
    # Fault plan + failure policy (ISSUE 15): the global driver gets the
    # full seam set (incl. process-kill — the multi-host chaos scenario)
    # but NO window replay (restage=None below: a failed collective
    # leaves peers blocked mid-program, checkpoint/resume is the recovery
    # path) and no degradation ladder (rebuild=None: every process would
    # have to step in lockstep).  The policy still drives reader/
    # checkpoint-save/collective-finish retries and the token timeout.
    merge_strategy = merge_strategy if merge_strategy is not None \
        else config.resolved_merge_strategy
    plan = faults_mod.FaultPlan.resolve(config.fault_plan)
    policy = faults_mod.FailurePolicy.resolve(config.failure_policy,
                                              retry=0)
    mesh = mesh if mesh is not None else dist.global_data_mesh()
    axes = tuple(mesh.axis_names)
    n_dev = mesh.size
    # No data-stats mode here (like no retry): the stats leaves are [D]
    # per-shard scalars, and fetching them on a mesh spanning other
    # processes' devices would need a collective round per retirement.
    # Data-plane telemetry is the per-host-driven / single-host story.
    engine = Engine(job, mesh, axis=axes if len(axes) > 1 else axes[0],
                    merge_strategy=merge_strategy)
    # Window-boundary overlap (ISSUE 20 leg 2) — THE fleet scenario: the
    # partial merge is one SPMD program every process dispatches at the
    # same deterministic boundary, so the DCN transfer of window N rides
    # under window N+1's ingest.  The global driver has no retry, so no
    # gating is needed here.
    overlap = _OverlapMerger(engine, tel, dist.is_coordinator, plan,
                             policy, logger, merge_strategy,
                             config.inflight_groups) \
        if config.merge_overlap else None
    mine = np.asarray(dist.host_shards(n_dev), dtype=np.int64)

    timer = metrics_mod.PhaseTimer()
    timer.start("total")

    start_step, start_offset = 0, 0
    bases_list: list[np.ndarray] = []
    fingerprint = ckpt_mod.run_fingerprint(
        path, n_dev, config.chunk_bytes, backend=config.resolved_backend(),
        pallas_max_token=config.pallas_max_token, byte_range=None,
        job_identity=job.identity()) if checkpoint_path else None

    # Shard-row staging buffers come from a pool recycled when their group
    # retires (the program consumed the input), instead of a fresh gather
    # allocation per group; ``_staged_bufs`` pairs each staged device array
    # with the host buffer it was transferred from.
    pool = _StagePool()
    _staged_bufs: dict[int, np.ndarray] = {}

    def stage(host_rows: np.ndarray):
        """This process's rows -> one globally-sharded array."""
        arr = dist.device_put_local(host_rows, engine.sharding)
        _staged_bufs[id(arr)] = host_rows
        return arr

    def stage_release(staged) -> None:
        pool.give(_staged_bufs.pop(id(staged), None))

    def stage_single(b):
        buf = pool.take((len(mine), b.data.shape[1]), b.data.dtype)
        np.take(b.data, mine, axis=0, out=buf)
        return stage(buf)

    def stage_group(g):
        buf = pool.take((len(mine), len(g), g[0].data.shape[1]),
                        g[0].data.dtype)
        for j, b in enumerate(g):
            buf[:, j] = b.data[mine]
        return stage(buf)

    ck_fallback = None
    if checkpoint_path and ckpt_mod.exists(checkpoint_path):
        template = jax.eval_shape(engine.init_states_global)
        if overlap is not None:
            # Overlap snapshots pack {"s": local state, "a": accumulator}
            # (every checkpoint boundary ships a partial first).
            template = {"s": template, "a": overlap.accum_template()}
        (state_np, start_step, start_offset, bases_arr, resumed_file), \
            ck_fallback = ckpt_mod.load_resilient(
                checkpoint_path, template=template,
                expect_fingerprint=fingerprint)
        if overlap is not None:
            # The accumulator is fully replicated: every process holds
            # the identical host value, so local data = global value.
            overlap.accum = jax.tree.map(
                lambda x: jax.make_array_from_process_local_data(
                    engine._replicated, np.asarray(x)), state_np["a"])
            state_np = state_np["s"]
        # _owned_state: the resumed tree is donated into the first global
        # step — a raw transfer-created buffer is not donation-safe.
        state = _owned_state(
            jax.tree.map(lambda x: stage(np.asarray(x)[mine]), state_np))
        bases_list = list(bases_arr)
        log_event(logger, "resumed from checkpoint (global)",
                  step=start_step, offset=start_offset)
        if ck_fallback is not None:
            log_event(logger, "corrupt checkpoint; resumed from previous "
                      "good snapshot", **ck_fallback)
    else:
        state = engine.init_states_global()
        resumed_file = None

    hooks = _StreamHooks(
        stage_single=stage_single,
        stage_group=stage_group,
        # The checkpoint fetch is a collective (one all-gather round makes
        # the sharded state addressable everywhere); only the coordinator
        # touches the filesystem.  No retry (see docstring).
        snapshot=engine.replicate_to_host,
        restage=None,
        write_gate=dist.is_coordinator,
        retry=0,
        stage_release=stage_release,
        host_rows=mine,
        overlap=overlap)
    # Pod-scale observability (ISSUE 13, ledger v7): every process writes
    # its own `<ledger>.h<p>.jsonl` shard (host-stamped records, the
    # run-epoch clock pair in run_start, per-host flight dumps); the
    # coordinator keeps the merged-authoritative main file it always
    # wrote.  Pass the SAME ledger path (and ideally the same run_id) on
    # every process; obs/fleet.py merges the shards.
    if jax.process_count() > 1:
        tel.attach_host(jax.process_index(), jax.process_count(),
                        local_devices=len(jax.local_devices()),
                        clock=dist.run_epoch())
    tel.registry.counter("executor.runs", driver="run_job_global").inc()
    # The main ledger rides the same gate as checkpoints: one file,
    # written by the coordinator; the per-host shard gets every record.
    chaos_stamp = {"fault_plan": plan.spec} if plan is not None else {}
    tel.ledger_write("run_start", driver="run_job_global",
                     job=job.identity(), devices=n_dev,
                     chunk_bytes=config.chunk_bytes,
                     superstep=config.superstep,
                     backend=config.resolved_backend(),
                     map_impl=config.map_impl,
                     combiner=config.resolved_combiner,
                     **_geometry_stamp(config), **chaos_stamp,
                     merge_strategy=merge_strategy,
                     **({"merge_overlap": True} if config.merge_overlap
                        else {}),
                     input=_path_names(path),
                     resume_step=start_step, resume_offset=start_offset,
                     write=dist.is_coordinator())
    if ck_fallback is not None:
        tel.ledger_write("fault", seam="checkpoint-load",
                         fault_class="transient", injected=False,
                         error=ck_fallback["error"],
                         fallback=ck_fallback["loaded"],
                         corrupt=ck_fallback["corrupt"],
                         write=dist.is_coordinator())
    timer.start("stream")
    try:
        state, bytes_done, _, pipe = _drive_stream(
            engine, job, config, path, state, hooks,
            start_step=start_step, start_offset=start_offset,
            end_offset=None, bases_list=bases_list,
            checkpoint_path=checkpoint_path, checkpoint_every=checkpoint_every,
            fingerprint=fingerprint, resumed_file=resumed_file,
            logger=logger, progress_every=progress_every, timer=timer,
            telemetry=tel, plan=plan, policy=policy)
        with obs.span("drain", timer):
            jax.block_until_ready(state)
        timer.stop("stream")

        with obs.span("reduce", timer):
            if overlap is not None:
                overlap.retire()
            fin_t0 = time.perf_counter()
            # Replicated finish: addressable everywhere.  The collective-
            # finish seam + injected-fault retry budget wrap it (ISSUE
            # 15); real collective failures classify, record, propagate.
            if overlap is not None:
                value = _collective_call(
                    lambda: engine.finish_residual(overlap.accum, state),
                    plan, policy, tel, dist.is_coordinator(), logger)
            else:
                value = _collective_finish(engine, state, plan, policy,
                                           tel, dist.is_coordinator(),
                                           logger)
            value = jax.tree.map(np.asarray, value)
            # Every host times the SAME collective finish from its own
            # side (ISSUE 13): the fleet `collective` lane + the
            # collective-bound half of the fleet_bottleneck verdict.
            tel.ledger_write("collective", op="finish",
                             strategy=merge_strategy,
                             started_at=round(fin_t0, 6),
                             ended_at=round(time.perf_counter(), 6),
                             write=dist.is_coordinator())
    except faults_mod.Preempted:
        # Orderly preemption shutdown (ISSUE 15): resumable, not a
        # failure — no flight dump.
        raise
    except Exception as e:
        # Each process dumps to its OWN (host-suffixed) flight path —
        # no shared-file race, and the failing host's forensics survive
        # (ISSUE 13 bugfix: this used to ride the coordinator gate).
        tel.flight_dump(context={"where": "run_job_global",
                                 "error": repr(e)})
        raise
    total_s = timer.stop("total")

    _finalize_pipeline(pipe, timer, tel)
    words = _metrics_word_count(value)
    m = metrics_mod.RunMetrics(bytes_processed=bytes_done, words_counted=words,
                               elapsed_s=total_s, phases=dict(timer.phases))
    # The shard's run_end carries THIS host's phase totals — the per-host
    # straggler raw material; the coordinator's main record is unchanged.
    tel.ledger_write("run_end", **m.as_dict(), pipeline=pipe,
                     write=dist.is_coordinator())
    log_event(logger, "global run complete", **m.as_dict())
    bases = np.stack(bases_list) if bases_list else np.zeros((0, n_dev), np.int64)
    return RunResult(value=value, metrics=m, bases=bases, pipeline=pipe)


def absolute_offsets(chunk_id: np.ndarray, pos: np.ndarray,
                     bases: np.ndarray, n_devices: int) -> np.ndarray:
    """Decode (chunk_id = step * n_devices + device, per-chunk pos) into
    absolute corpus offsets via the recorded row bases — the single host-
    side owner of the Engine's chunk-id linearization (every job recovering
    source spans goes through this)."""
    step, dev = chunk_id // n_devices, chunk_id % n_devices
    return bases[step, dev] + pos


def recover_from_file(tbl: table_ops.CountTable, path, bases: np.ndarray,
                      n_devices: int, ngram: int = 1,
                      estimate_distinct: bool = True) -> WordCountResult:
    """Host-side string recovery for a streamed run.

    ``pos_hi`` encodes chunk_id = step * n_devices + device; its absolute file
    base is ``bases[step, device]``.  Entries are reported in file order
    (first occurrence), the reference's insertion order (main.cu:212-215).

    Entries whose length is ``SEAM_GRAM_LENGTH`` are cross-chunk grams: the
    device knew the start but not the end (it lies in a later chunk), so the
    span length is recovered here by scanning ``ngram`` tokens forward.
    """
    count = np.asarray(tbl.count).astype(np.int64)
    count_hi = np.asarray(tbl.count_hi).astype(np.int64)
    valid = (count > 0) | (count_hi > 0)
    chunk_id = np.asarray(tbl.pos_hi)[valid].astype(np.int64)
    pos = np.asarray(tbl.pos_lo)[valid].astype(np.int64)
    length = np.asarray(tbl.length)[valid].astype(np.int64)
    cnt = (count + (count_hi << np.int64(32)))[valid]
    absolute = absolute_offsets(chunk_id, pos, bases, n_devices)
    seam = np.flatnonzero(length == int(constants.SEAM_GRAM_LENGTH))
    if len(seam):
        # Row bases mark force-split entry ends (the reader cuts separator-
        # free runs there); one batch call maps each touched file once.
        length[seam] = reader_mod.scan_gram_lengths(
            path, absolute[seam], ngram, cut_offsets=bases.ravel())
    order = np.argsort(absolute, kind="stable")
    spans = [(int(absolute[i]), int(length[i])) for i in order]
    words = reader_mod.read_words_at_multi(path, spans)
    dropped_uniques, dropped_count = tbl.dropped_totals()
    return WordCountResult(
        words=words,
        counts=[int(c) for c in cnt[order]],
        total=int(np.asarray(tbl.total_count())),
        distinct=_reported_distinct(tbl, len(words), dropped_uniques,
                                    estimate_distinct),
        dropped_uniques=dropped_uniques,
        dropped_count=dropped_count,
    )


def count_file(path, config: Config = DEFAULT_CONFIG, mesh=None,
               top_k: Optional[int] = None, distinct_sketch: bool = False,
               count_sketch: bool = False, ngram: int = 1, **kw) -> WordCountResult:
    """WordCount over a file via the streaming sharded pipeline.

    ``distinct_sketch`` composes a HyperLogLog over the run, populating
    ``result.distinct_estimate`` — accurate (~0.8%) even when distinct words
    spill past table capacity.  Sketched runs checkpoint like plain ones
    (snapshots hold the whole state pytree); resuming a checkpoint across
    sketched/unsketched configurations raises CheckpointMismatch.

    ``count_sketch`` composes a Count-Min sketch instead, populating
    ``result.cms`` so ``result.estimate_count(word)`` answers frequency
    queries for any word — including ones the exact table spilled.  The two
    sketches are mutually exclusive per run (their states checkpoint
    differently); pick the one matching the question being asked.

    ``ngram > 1`` counts n-token grams instead of single words — exactly,
    including grams spanning chunk seams (the seam-carry machinery of
    :class:`...models.wordcount.NGramCountJob`); streamed results match
    single-buffer runs bit-for-bit.
    """
    if distinct_sketch and count_sketch:
        raise ValueError("distinct_sketch and count_sketch are mutually "
                         "exclusive per run; run twice to get both")
    mesh = mesh if mesh is not None else data_mesh()
    if ngram > 1:
        job = NGramCountJob(ngram, config, top_k=top_k or None)
    else:
        job = TopKWordCountJob(top_k, config) if top_k else WordCountJob(config)
    if distinct_sketch:
        job = SketchedWordCountJob(job)
    elif count_sketch:
        job = FreqSketchedWordCountJob(job)
    rr = run_job(job, path, config=config, mesh=mesh, **kw)
    n_dev = mesh.size
    value, registers, cms = rr.value, None, None
    if isinstance(value, SketchedState):
        value, registers = value.table, value.registers
    elif isinstance(value, FreqSketchedState):
        value, cms = value.table, np.asarray(value.cms)
    # Top-k finalize reorders the table on device, destroying the KMV
    # property — but it snapshots the estimator's scalars first
    # (TopKTable), so spilled top-k runs still get the tight distinct
    # estimate instead of the summed upper bound.
    kmv_est = None
    if isinstance(value, TopKTable):
        kmv_est = table_ops.kmv_from_snapshot(
            int(value.kmv_n_valid), int(value.kmv_kth_hi),
            int(value.kmv_kth_lo), config.table_capacity)
        value = value.table
    result = recover_from_file(value, path, rr.bases, n_dev, ngram=ngram,
                               estimate_distinct=not top_k)
    if kmv_est is not None:
        result = dataclasses.replace(
            result, distinct=max(len(result.words), int(round(kmv_est))))
    if registers is not None:
        from mapreduce_tpu.ops import sketch as sketch_ops

        result = dataclasses.replace(
            result, distinct_estimate=sketch_ops.estimate(registers))
    if cms is not None:
        result = dataclasses.replace(result, cms=cms)
    if top_k:
        result = apply_top_k(result, top_k)
    return result
