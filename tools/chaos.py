#!/usr/bin/env python3
"""Chaos tooling for the fault-injection harness (ISSUE 15).

Jax-free and stdlib-only: ``runtime/faults.py`` is loaded by file path
(the obs_report pattern), so this runs on boxes with neither jax nor the
package installed — CI's tier-1/smoke gates run ``--selftest`` before
pytest ever imports jax.

Usage::

    python tools/chaos.py --selftest            # hand-computed fixtures
    python tools/chaos.py --plan 'seed=7,rate=0.1' --walk 20
                                                # which crossings fire?
    python tools/chaos.py --replay run.jsonl    # ledger -> replay spec

``--replay`` is the fault-plan replay workflow (docs/robustness.md):
read a chaotic run's own ``fault`` records, rebuild the exact injection
schedule (``FaultPlan.from_ledger``), and print the canonical spec to
hand to ``--fault-plan`` / ``Config.fault_plan`` for a fault-for-fault
identical rerun.

``--selftest`` checks the module's arithmetic against values computed by
hand:

* backoff: base 0.05 s, factor 2, cap 5 s, no jitter -> 0.05, 0.1, 0.2,
  0.4, 0.8, 1.6, 3.2, 5.0 (capped), 5.0;
* jitter: deterministic per (seed, seam, class, attempt), bounded by
  ``base * (1 +/- jitter_frac)``, different across seams/seeds;
* ladder: a full-featured config walks revert-geometry -> combiner-off
  -> map-split -> sort-xla and an already-degraded config walks only its
  remaining steps;
* plan determinism: same seed -> same firing set, rate=0 never fires,
  ``max`` bounds the count, explicit ``at=`` events fire regardless;
* spec round-trip and ledger replay over the checked-in chaotic fixture
  run (tools/fixtures/mini_ledger.jsonl, fixture11).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

_FAULTS = None


def faults_mod():
    """``mapreduce_tpu.runtime.faults`` loaded WITHOUT the package
    (importing it would pull config -> jax)."""
    global _FAULTS
    if _FAULTS is None:
        src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           os.pardir, "mapreduce_tpu", "runtime",
                           "faults.py")
        if os.path.exists(src):
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "_mapreduce_tpu_runtime_faults", src)
            mod = importlib.util.module_from_spec(spec)
            # dataclass processing resolves cls.__module__ through
            # sys.modules — a file-loaded module must register first.
            sys.modules[spec.name] = mod
            spec.loader.exec_module(mod)
            _FAULTS = mod
        else:
            import importlib

            _FAULTS = importlib.import_module(
                "mapreduce_tpu.runtime.faults")
    return _FAULTS


def read_jsonl(path: str) -> list:
    out = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue  # torn tail / crash-truncated record
    return out


def replay(path: str, run_id=None, out=sys.stdout) -> int:
    """Ledger -> the replay plan: the canonical spec plus the fired
    sequence it encodes.  Exit 1 when the ledger holds no injected
    ``fault`` records (nothing to replay — an honest miss)."""
    fm = faults_mod()
    records = read_jsonl(path)
    seq = fm.fired_sequence(records, run_id=run_id)
    if not seq:
        print(f"chaos replay: no injected fault records in {path}"
              + (f" (run_id {run_id})" if run_id else ""), file=sys.stderr)
        return 1
    plan = fm.FaultPlan.from_ledger(records, run_id=run_id)
    out.write(f"replay plan for {path}:\n")
    out.write(f"  --fault-plan '{plan.spec}'\n")
    for seam, index, fcls in seq:
        out.write(f"  {seam} crossing {index}: {fcls}\n")
    return 0


def walk_plan(spec: str, crossings: int, out=sys.stdout) -> int:
    """Print the deterministic firing decisions of a plan's first N
    crossings per seam — what WOULD a run under this plan see."""
    fm = faults_mod()
    plan = fm.FaultPlan.from_spec(spec)
    out.write(f"plan {plan.spec}\n")
    fired = 0
    for seam in fm.SEAMS:
        for i in range(crossings):
            if plan.max_faults and fired >= plan.max_faults:
                break
            cls = plan.decide(seam, i)
            if cls is not None:
                out.write(f"  {seam} crossing {i}: {cls}\n")
                fired += 1
    out.write(f"  {fired} fault(s) over the first {crossings} crossings "
              "per seam\n")
    return 0


def selftest() -> int:
    fm = faults_mod()

    # --- backoff arithmetic, by hand (no jitter).
    p = fm.FailurePolicy(transient_retries=8, backoff_base_s=0.05,
                         backoff_factor=2.0, backoff_max_s=5.0,
                         jitter_frac=0.0)
    want = [0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 3.2, 5.0, 5.0]
    got = [p.backoff_s("transient", a) for a in range(1, 10)]
    assert got == want, got
    assert p.backoff_s("transient", 0) == 0.0, "attempt 0 never sleeps"

    # --- jitter: deterministic, bounded, seam/seed-sensitive.
    pj = fm.FailurePolicy(backoff_base_s=1.0, backoff_factor=1.0,
                          backoff_max_s=1.0, jitter_frac=0.25, seed=42)
    v1 = pj.backoff_s("transient", 1, seam="dispatch")
    v2 = pj.backoff_s("transient", 1, seam="dispatch")
    assert v1 == v2, "same identity must back off identically"
    assert 0.75 <= v1 <= 1.25, v1
    v3 = pj.backoff_s("transient", 1, seam="reader-read")
    pj2 = fm.FailurePolicy(backoff_base_s=1.0, backoff_factor=1.0,
                           backoff_max_s=1.0, jitter_frac=0.25, seed=43)
    v4 = pj2.backoff_s("transient", 1, seam="dispatch")
    assert v3 != v1 and v4 != v1, \
        "jitter must decorrelate across seams and seeds"

    # --- the legacy retry=N mapping + budgets.
    legacy = fm.FailurePolicy.resolve(None, retry=3)
    assert legacy.transient_retries == 3 and legacy.resource_retries == 3
    assert legacy.permanent_retries == 0 and legacy.budget("preemption") == 0
    assert legacy.dispatch_budget == 3
    assert fm.FailurePolicy.resolve({"transient_retries": 2}) \
        .transient_retries == 2

    # --- taxonomy: typed faults carry their class; real exceptions
    # classify by message then type; unknown -> transient (the legacy
    # retry-anything semantics).
    assert fm.classify(fm.ResourceFault("x")) == "resource"
    assert fm.classify(RuntimeError("RESOURCE_EXHAUSTED: out of memory "
                                    "allocating")) == "resource"
    assert fm.classify(RuntimeError("preempted: maintenance event")) \
        == "preemption"
    assert fm.classify(KeyboardInterrupt()) == "preemption"
    assert fm.classify(ValueError("bad config")) == "permanent"
    assert fm.classify(RuntimeError("flaky link")) == "transient"
    assert fm.classify(fm.TokenTimeout("hung")) == "transient"

    # --- ladder walks from fixture dicts, by hand.
    full = {"geometry": "tall512", "combiner": "hot-cache",
            "map_impl": "fused"}
    assert fm.ladder_walk(full) == ["revert-geometry", "combiner-off",
                                    "map-split"]
    assert fm.next_degrade(full) == ("revert-geometry", "geometry",
                                     "default")
    part = {"geometry": "default", "combiner": "off",
            "map_impl": "fused"}
    assert fm.ladder_walk(part) == ["map-split"]
    done = {"geometry": "default", "combiner": "off",
            "map_impl": "split"}
    assert fm.next_degrade(done) is None and fm.ladder_walk(done) == []

    # --- plan determinism: same seed -> same firing set; rate=0 silent;
    # max bounds; explicit events always fire; process-kill never fires
    # from the random rate.
    def fired_set(seed, rate, n=200):
        plan = fm.FaultPlan(seed=seed, rate=rate)
        out = set()
        for seam in plan.seams:
            for i in range(n):
                if plan.decide(seam, i) is not None:
                    out.add((seam, i))
        return out

    a, b = fired_set(7, 0.05), fired_set(7, 0.05)
    assert a == b and a, "seeded plans must fire identically (and fire)"
    assert fired_set(8, 0.05) != a, "a different seed is a different run"
    assert not fired_set(7, 0.0), "rate=0 never fires"
    frac = len(a) / (200 * len(fm.FaultPlan(seed=7, rate=0.05).seams))
    assert 0.02 < frac < 0.10, f"5% rate fired {frac:.1%}"
    capped = fm.FaultPlan(seed=7, rate=1.0, max_faults=3)
    hits = 0
    for seam in capped.seams:
        for i in range(10):
            if capped.check(seam) is not None:
                hits += 1
    assert hits == 3 and len(capped.fired) == 3, hits
    assert "process-kill" not in fm.FaultPlan(seed=1, rate=1.0).seams, \
        "random chaos must never hard-kill unless asked by name"

    # --- spec grammar round-trip + explicit events.
    plan = fm.FaultPlan.from_spec(
        "seed=9,at=dispatch:3:resource,at=token-wait:1:preemption")
    assert plan.decide("dispatch", 3) == "resource"
    assert plan.decide("dispatch", 2) is None
    assert plan.decide("token-wait", 1) == "preemption"
    rt = fm.FaultPlan.from_spec(plan.spec)
    assert rt.spec == plan.spec and rt.events == plan.events
    for bad in ("", "rate=2.0", "seams=warp", "classes=entropic",
                "at=dispatch:x:resource", "nonsense"):
        try:
            fm.FaultPlan.from_spec(bad)
        except ValueError:
            pass
        else:
            raise AssertionError(f"spec {bad!r} must be rejected")

    # --- ledger replay over the checked-in chaotic fixture run: the
    # rebuilt plan fires exactly the recorded (seam, index, class)
    # sequence, and a plan replayed from its OWN fired log reproduces
    # itself (the chaos-certification replay contract).
    fdir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "fixtures")
    records = read_jsonl(os.path.join(fdir, "mini_ledger.jsonl"))
    seq = fm.fired_sequence(records, run_id="fixture11")
    assert seq == [("dispatch", 2, "transient"),
                   ("token-wait", 1, "resource")], seq
    rebuilt = fm.FaultPlan.from_ledger(records, run_id="fixture11")
    assert rebuilt.events == {("dispatch", 2): "transient",
                              ("token-wait", 1): "resource"}
    # drive the rebuilt plan through the crossings a rerun would make:
    # the SAME faults fire at the SAME crossings, nothing else.
    refired = []
    for seam in fm.SEAMS:
        for i in range(5):
            f = rebuilt.check(seam)
            if f is not None:
                refired.append((f.seam, f.index, f.fault_class))
    assert sorted(refired) == sorted(seq), refired
    # a random plan's own fired log rebuilds a plan that re-fires it.
    wild = fm.FaultPlan(seed=5, rate=0.1, classes=("transient",
                                                   "resource"))
    for seam in wild.seams:
        for i in range(40):
            wild.check(seam)
    own_records = [dict(kind="fault", injected=True, run_id="w", **f)
                   for f in wild.fired]
    rewild = fm.FaultPlan.from_ledger(own_records)
    for seam, index, fcls in fm.fired_sequence(own_records):
        assert rewild.decide(seam, index) == fcls

    print(f"chaos selftest ok (backoff 0.05->5.0 capped x{len(want)}, "
          f"jitter bounded deterministic, 3-step ladder walk, "
          f"plan determinism {len(a)} firings @5%, spec round-trip, "
          f"fixture11 replay {len(seq)} faults, "
          f"own-ledger replay {len(wild.fired)} faults)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="fault-plan chaos tooling (jax-free)")
    ap.add_argument("--selftest", action="store_true",
                    help="hand-computed backoff/ladder/plan fixtures")
    ap.add_argument("--replay", metavar="LEDGER",
                    help="rebuild a chaotic run's fault plan from its "
                         "own ledger records")
    ap.add_argument("--run-id", default=None,
                    help="with --replay: select one run of an "
                         "append-mode ledger")
    ap.add_argument("--plan", metavar="SPEC",
                    help="show the deterministic firing decisions of a "
                         "plan spec")
    ap.add_argument("--walk", type=int, default=20, metavar="N",
                    help="with --plan: crossings to evaluate per seam "
                         "(default %(default)s)")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if args.replay:
        return replay(args.replay, run_id=args.run_id)
    if args.plan:
        return walk_plan(args.plan, args.walk)
    ap.error("one of --selftest / --replay / --plan is required")
    return 2


if __name__ == "__main__":
    sys.exit(main())
