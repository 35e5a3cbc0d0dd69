"""Command-line entry point with exact reference-output parity.

The reference CLI ignores ``argv`` and hardcodes ``test.txt``
(``main.cu:164,167``); its stdout contract is::

    Input Data:
    <echo of the input lines>
    --------------------------
    <word>\t<count>        (one line per distinct word, insertion order)
    --------------------------
    Total Count:<N>

SURVEY §7 fixes the contract as: positional file argument, defaulting to
``test.txt`` when absent.  This module preserves that stdout shape byte-for-
byte on the golden fixture while adding real flags (top-k, sizing, JSON
output, device/mesh selection) the reference lacks.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from mapreduce_tpu.config import MERGE_STRATEGIES, Config


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="mapreduce-tpu",
        description="TPU-native MapReduce word count (reference-parity CLI).",
    )
    from mapreduce_tpu.version import __version__

    p.add_argument("--version", action="version",
                   version=f"%(prog)s {__version__}")
    p.add_argument("input", nargs="*", default=["test.txt"],
                   help="input text file(s) (default: test.txt, matching the "
                        "reference; multiple files stream as one corpus)")
    p.add_argument("--top-k", type=int, default=0,
                   help="report only the k most frequent words (0 = all)")
    p.add_argument("--ngram", type=int, default=1, metavar="N",
                   help="count n-token grams instead of single words "
                        "(reported entries are the exact source spans, e.g. "
                        "'Hello World'; --stream counts grams exactly, "
                        "including ones spanning chunk seams)")
    p.add_argument("--chunk-bytes", type=int, default=1 << 25,
                   help="bytes per device step (default 32 MB, the measured "
                        "v5e sweet spot; small inputs are never padded up "
                        "to this)")
    p.add_argument("--table-capacity", type=int, default=1 << 18)
    p.add_argument("--format", choices=("reference", "json", "tsv"), default="reference",
                   help="'reference' replicates the CUDA program's stdout shape")
    p.add_argument("--no-echo", action="store_true",
                   help="suppress the 'Input Data:' echo (for large corpora)")
    p.add_argument("--stream", action="store_true",
                   help="use the sharded streaming executor (for large files)")
    p.add_argument("--checkpoint", default=None, metavar="PATH",
                   help="with --stream: checkpoint state to PATH and resume from it")
    p.add_argument("--checkpoint-every", type=int, default=25, metavar="STEPS")
    p.add_argument("--superstep", type=int, default=1, metavar="K",
                   help="with --stream: fold K chunks into one dispatch "
                        "(lax.scan) to amortize per-dispatch overhead")
    p.add_argument("--inflight", type=int, default=Config.inflight_groups,
                   metavar="W",
                   help="with --stream: keep up to W superstep groups "
                        "dispatched-but-unretired, so reader/staging/H2D "
                        "and device compute of different groups overlap "
                        "(1 = serialized dispatch, the safe fallback and "
                        "A/B control; default %(default)s)")
    p.add_argument("--prefetch-depth", type=int, default=None, metavar="N",
                   help="with --stream: batches the background reader may "
                        "run ahead (default auto: superstep * inflight, "
                        "clamped to [2, 16] — co-tuned with the window)")
    p.add_argument("--autotune", action="store_true",
                   help="with --stream: feed the run's own telemetry "
                        "(timeline bottleneck, data health, window stats) "
                        "through the config autotuner and fold the "
                        "recommended next inflight/prefetch/superstep/"
                        "chunk-bytes into a `tune` ledger record and the "
                        "run summary — the live run is unchanged; "
                        "tools/autotune.py walks the loop offline")
    p.add_argument("--stats", action="store_true", help="print timing/throughput to stderr")
    p.add_argument("--retry", type=int, default=0, metavar="N",
                   help="with --stream: retry a failed device step N times "
                        "from an in-memory known-good snapshot before "
                        "surfacing the failure")
    p.add_argument("--fault-plan", default=None, metavar="SPEC",
                   help="with --stream: deterministic fault injection at "
                        "the executor's named seams (runtime/faults.py "
                        "grammar, e.g. 'seed=42,rate=0.02' or "
                        "'at=dispatch:3:resource'); every fired fault "
                        "lands as a `fault` ledger record so the run can "
                        "be replayed from its own ledger (tools/chaos.py "
                        "replay). Default from MAPREDUCE_FAULT_PLAN; "
                        "results stay bit-identical to the fault-free "
                        "run when the retry budget absorbs the chaos")
    p.add_argument("--distinct-sketch", action="store_true",
                   help="with --stream: carry a HyperLogLog so the distinct "
                        "count stays accurate past table capacity "
                        "(distinct_estimate in json output)")
    p.add_argument("--count-sketch", action="store_true",
                   help="with --stream: carry a Count-Min sketch so any "
                        "word's frequency stays queryable past table "
                        "capacity (see --estimate)")
    p.add_argument("--estimate", action="append", default=[], metavar="WORD",
                   help="report the sketch-estimated count of WORD "
                        "(repeatable; implies --count-sketch)")
    p.add_argument("--sketch-flush-every", type=int, default=1, metavar="K",
                   help="sketched runs: stage per-chunk sketch updates and "
                        "scatter once every K steps (amortizes the fixed "
                        "TPU scatter cost; results are identical)")
    p.add_argument("--grep", action="append", default=None, metavar="PATTERN",
                   help="count occurrences of PATTERN instead of words "
                        "(overlapping matches + exact matching lines; "
                        "composes with --stream for sharded corpora; "
                        "repeatable — P patterns share ONE pass over the "
                        "corpus)")
    p.add_argument("--grep-syntax", choices=("literal", "class"),
                   default="literal",
                   help="pattern syntax for --grep: 'class' enables "
                        "regex-lite byte classes — '.' (any byte but "
                        "newline), '[a-z0-9]', '[^...]', '\\\\x' escapes; "
                        "fixed length, no repetition/alternation")
    p.add_argument("--sample", type=int, default=None, metavar="K",
                   help="report a uniform random sample of K token "
                        "occurrences instead of counts (mergeable bottom-k "
                        "sketch; composes with --stream; deterministic for "
                        "a given corpus + chunking)")
    p.add_argument("--backend", choices=("auto", "xla", "pallas"), default="auto",
                   help="map-phase implementation (auto = pallas fused kernel "
                        "on TPU, xla scan elsewhere)")
    p.add_argument("--merge-every", type=int, default=1, metavar="K",
                   help="fold per-chunk batch tables into the running table "
                        "once every K steps (one K-way reduce replaces K "
                        "pairwise merges; word-count family only; kept "
                        "counts identical)")
    p.add_argument("--merge-strategy",
                   choices=MERGE_STRATEGIES + ("auto",),
                   default="tree",
                   help="collective global-reduce strategy for streamed "
                        "word-count runs: butterfly tree (log2(D) rounds), "
                        "all_gather + fold, key-range all_to_all "
                        "reduce-scatter (one round; the pod-scale choice), "
                        "or a hierarchical 2-D program (hier-kr-tree / "
                        "hier-tree-tree — fleet meshes only; the CLI's 1-D "
                        "mesh rejects them). 'auto' warm-starts from the "
                        "static reduction planner's freshest tuned profile "
                        "(tools/redplan.py --out, read from the "
                        "--geometry-profile file; no matching profile "
                        "falls back loudly to tree)")
    p.add_argument("--merge-overlap", action="store_true",
                   help="with --stream: drain the local tables into a "
                        "device-resident merged accumulator at window "
                        "boundaries (one async partial collective per "
                        "--inflight retired groups), overlapping "
                        "interconnect time with map compute; results stay "
                        "bit-identical and each partial lands as an "
                        "op='partial' collective ledger record (v10); "
                        "requires --retry 0")
    p.add_argument("--compact-slots", type=int, default=None, metavar="S",
                   help="slot-compact the pallas kernel's output to S rows "
                        "per 256-byte window (multiple of 8; 0 = off; "
                        "default auto = 88, +25%% measured on-chip). Cuts "
                        "the aggregation sort's input ~1.45x at S=88; "
                        "windows denser than S fall back to the full path "
                        "for that chunk (always exact)")
    p.add_argument("--sort-mode", choices=("sort3", "stable2"),
                   default="stable2",
                   help="aggregation sort strategy on the pallas fast path "
                        "(bit-identical results): 'stable2' drops the third "
                        "sort key via a lane-major kernel layout + stable "
                        "2-key sort; 'sort3' sorts on three keys over the "
                        "slot-major layout")
    p.add_argument("--map-impl", choices=("split", "fused"), default="split",
                   help="pallas map-phase implementation (bit-identical "
                        "results): 'split' = compact kernel + XLA seam "
                        "fix-up over 129 seam windows (the shipped path); "
                        "'fused' = tokenize -> hash -> window compaction in "
                        "ONE kernel pass over raw chunk bytes, lane seams "
                        "resolved in-VMEM from a seam-carry plane — no "
                        "token-plane round-trip to HBM before the "
                        "aggregation sort (costcheck prices the gap; "
                        "'split' stays default until the on-chip window "
                        "confirms the predicted win, round-9 chip notes)")
    p.add_argument("--combiner", choices=("off", "hot-cache", "salt", "auto"),
                   default="off",
                   help="skew-adaptive map-side combiner (bit-identical "
                        "results): 'hot-cache' = a per-lane VMEM hot-key "
                        "cache in the fused pallas kernel pre-aggregates "
                        "the top-mass keys per chunk, deleting the "
                        "dominant duplicate rows before the aggregation "
                        "sort (pairs with --map-impl fused; taller kernel "
                        "windows cut sort rows ~25%%, priced by costcheck); "
                        "'salt' = spread a pathological single hot key "
                        "over salted sort segments with an exact de-salt "
                        "at the reduce; 'auto' = resolve from the previous "
                        "run's data-health verdict in --ledger (skew-hot "
                        "-> hot-cache, else off)")
    p.add_argument("--combiner-slots", type=int, default=None, metavar="C",
                   help="per-lane hot-key cache entries for --combiner "
                        "hot-cache (multiple of 8 in [8, 32]; default 8)")
    p.add_argument("--geometry", default=None, metavar="G",
                   help="kernel-geometry set (ISSUE 12): a preset name "
                        "('tall512', 'combiner16'), 'auto' to resolve "
                        "from the geometry search's tuned profile "
                        "(--geometry-profile), or omit for the shipped "
                        "default constants.  Results are bit-identical "
                        "across certified geometries; only the cost "
                        "moves")
    p.add_argument("--geometry-profile", default="tuned.json",
                   metavar="PATH",
                   help="tuned.json searched profiles for --geometry "
                        "auto (default ./tuned.json; missing file "
                        "resolves to the default geometry)")
    p.add_argument("--max-token-bytes", type=int, default=32, metavar="W",
                   help="pallas backend: tokens longer than W bytes are "
                        "dropped into dropped_* accounting (xla counts any "
                        "length)")
    p.add_argument("--rescue-overlong", type=int, default=None, metavar="R",
                   help="pallas backend: re-hash up to R >W-byte tokens per "
                        "chunk exactly via bounded XLA windows (URLs/markup "
                        "on natural text; default 1024; 0 disables)")
    p.add_argument("--rescue-overlong-max", type=int, default=None,
                   metavar="R2",
                   help="second-tier rescue budget: chunks whose overlong "
                        "count exceeds --rescue-overlong escalate to R2 "
                        "slots under a cond (default auto: chunk_bytes/1024 "
                        "clamped to [R, 65536] — covers URL-dense text with "
                        "no hand-sizing)")
    p.add_argument("--rescue-window", type=int, default=192, metavar="B",
                   help="rescue lookback bound: tokens up to B-1 bytes are "
                        "recovered exactly; longer ones stay accounted")
    p.add_argument("--verify-sample", type=int, default=0, metavar="K",
                   help="after a word-count run, exactly recount K reported "
                        "words host-side (byte-string keyed, no hashing) "
                        "and fail loudly on any mismatch — the detection "
                        "path for the ~n^2/2^65 64-bit key-collision "
                        "envelope (see utils/verify.py); costs one host "
                        "pass over the corpus")
    p.add_argument("--profile", default=None, metavar="DIR",
                   help="capture a jax.profiler trace (XProf/Perfetto) to DIR")
    p.add_argument("--ledger", default=None, metavar="PATH",
                   help="append a JSONL run ledger to PATH. Streamed runs "
                        "record one step + one group record per dispatch "
                        "group (phase timings, bytes, device memory, "
                        "compile events, lifecycle stamps, data-plane "
                        "counters) plus a per-run data summary; a failed "
                        "run also dumps flight-recorder forensics to "
                        "PATH.flight.json. Batch (non---stream) runs emit "
                        "run_start / data / run_end. Summarize with "
                        "tools/obs_report.py")
    p.add_argument("--metrics-out", default=None, metavar="PATH",
                   help="write the end-of-run metrics-registry snapshot "
                        "(executor/reader/checkpoint/collective/data "
                        "counters, gauges, histograms) as JSON to PATH")
    p.add_argument("--platform", choices=("auto", "cpu"), default="auto",
                   help="'cpu' forces the run onto the host CPU even when the "
                        "environment pins JAX to an accelerator (equivalent "
                        "to JAX_PLATFORMS=cpu; the escape hatch when the "
                        "device is unreachable)")
    return p


def _apply_platform(requested: str = "auto") -> str:
    """Force the JAX platform when the user asked for one; return the
    EFFECTIVE platform string (lowercase; '' = JAX resolves the local
    backend) the run will use.

    A cpu request (``--platform cpu`` or ``JAX_PLATFORMS=cpu``) lands via
    ``jax.config.update`` before any device use
    (:func:`...runtime.platform.force_cpu`, which also verifies the force
    landed).  The return value is read from the CONFIG, not the env var:
    the config is what JAX will actually dial.
    """
    import os

    from mapreduce_tpu.runtime import platform as platform_mod

    want = "" if requested in (None, "auto") else requested.lower()
    if not want and os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
        want = "cpu"
    if want == "cpu":
        platform_mod.force_cpu()
    return platform_mod.effective_platforms()


_CTRL_ESCAPES = str.maketrans({"\t": "\\t", "\n": "\\n", "\r": "\\r",
                               "\x00": "\\x00", "\x0b": "\\x0b", "\x0c": "\\x0c"})


def _decode(words: list[bytes]) -> list[str]:
    """Lossless-enough display decoding: distinct byte words stay distinct.

    Control separators are escaped so n-gram spans (which carry their real
    inter-token separator bytes) keep report lines one-per-entry; single
    words never contain separators, so reference byte-parity is unaffected.
    """
    return [w.decode("utf-8", errors="backslashreplace").translate(_CTRL_ESCAPES)
            for w in words]


def _echo_file(paths: list[str]) -> None:
    """Stream the input bytes to stdout (the reference's line echo,
    main.cu:180) without materializing the files in memory."""
    sys.stdout.write("Input Data:\n")
    sys.stdout.flush()
    for path in paths:
        last = b"\n"
        with open(path, "rb") as f:
            while True:
                block = f.read(1 << 20)
                if not block:
                    break
                sys.stdout.buffer.write(block)
                last = block[-1:]
        if last != b"\n":
            sys.stdout.buffer.write(b"\n")
    sys.stdout.buffer.flush()


def _print_stats(input_bytes: int, count: int, unit: str, elapsed: float) -> None:
    print(f"[stats] {input_bytes} bytes, {count} {unit}, "
          f"{elapsed:.3f}s, {input_bytes / 1e9 / elapsed:.3f} GB/s",
          file=sys.stderr)


def _grep_main(args, paths, data, config, input_bytes: int,
               telemetry=None) -> int:
    """--grep mode: pattern counts instead of word counts.  Multiple --grep
    flags run as ONE fused pass (one ingest, P match masks)."""
    from mapreduce_tpu.models import grep

    from mapreduce_tpu.runtime import profiling

    patterns = [g.encode() for g in args.grep]
    syntax = args.grep_syntax
    kw = dict(config=config, syntax=syntax, checkpoint_path=args.checkpoint,
              checkpoint_every=args.checkpoint_every if args.checkpoint else 0,
              retry=args.retry, telemetry=telemetry)
    batch_tel = telemetry if not args.stream else None
    if batch_tel is not None:
        _batch_run_start(batch_tel, "grep", paths, config, input_bytes)
    t0 = time.perf_counter()
    try:
        with profiling.trace(args.profile):
            if args.stream and len(patterns) == 1:
                results = [grep.grep_file(paths, patterns[0], **kw)]
            elif args.stream:
                results = grep.grep_file_multi(paths, patterns, **kw)
            else:
                # Each file is grepped separately and summed: a newline-
                # bearing pattern (only NUL is rejected) must not fabricate a
                # match across the artificial seam a joined buffer would add.
                per_file = [grep.grep_bytes_multi(c, patterns, syntax)
                            for c in data]
                results = [grep.GrepResult(
                    p, sum(f[i].matches for f in per_file),
                    sum(f[i].lines for f in per_file))
                    for i, p in enumerate(patterns)]
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    elapsed = time.perf_counter() - t0
    if batch_tel is not None:
        batch_tel.ledger_write("run_end", bytes=input_bytes,
                               words=sum(r.matches for r in results),
                               elapsed_s=round(elapsed, 6))

    out = sys.stdout
    multi = len(results) > 1
    if args.format == "json":
        if multi:
            out.write(json.dumps({"patterns": [
                {"pattern": g, "matches": r.matches, "lines": r.lines}
                for g, r in zip(args.grep, results)]}) + "\n")
        else:
            out.write(json.dumps({"pattern": args.grep[0],
                                  "matches": results[0].matches,
                                  "lines": results[0].lines}) + "\n")
    elif args.format == "tsv":
        if multi:
            for g, r in zip(args.grep, results):
                out.write(f"{g}\t{r.matches}\t{r.lines}\n")
        else:
            out.write(f"matches\t{results[0].matches}\n"
                      f"lines\t{results[0].lines}\n")
    else:
        for g, r in zip(args.grep, results):
            if multi:
                out.write(f"Pattern:{g}\n")
            out.write(f"Matches:{r.matches}\n")
            out.write(f"Matching Lines:{r.lines}\n")
    if args.stats:
        _print_stats(input_bytes, sum(r.matches for r in results),
                     "matches", elapsed)
    return 0


def _sample_main(args, paths, data, config, input_bytes: int,
                 telemetry=None) -> int:
    """--sample mode: uniform token sample instead of counts."""
    from mapreduce_tpu.models import sample as sample_mod
    from mapreduce_tpu.runtime import profiling

    batch_tel = telemetry if not args.stream else None
    if batch_tel is not None:
        _batch_run_start(batch_tel, "sample", paths, config, input_bytes)
    t0 = time.perf_counter()
    try:
        with profiling.trace(args.profile):
            if args.stream:
                result = sample_mod.sample_file(
                    paths, args.sample, config=config,
                    checkpoint_path=args.checkpoint,
                    checkpoint_every=args.checkpoint_every if args.checkpoint else 0,
                    retry=args.retry, telemetry=telemetry)
            else:
                result = sample_mod.sample_bytes(data, args.sample, config)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    elapsed = time.perf_counter() - t0
    if batch_tel is not None:
        batch_tel.ledger_write("run_end", bytes=input_bytes,
                               words=result.total,
                               elapsed_s=round(elapsed, 6))

    out = sys.stdout
    display = _decode(result.tokens)
    if args.format == "json":
        out.write(json.dumps({"sample": display, "k": args.sample,
                              "total": result.total}) + "\n")
    elif args.format == "tsv":
        for w in display:
            out.write(w + "\n")
    else:
        out.write("--------------------------\n")
        for w in display:
            out.write(w + "\n")
        out.write("--------------------------\n")
        out.write(f"Sampled:{len(display)} of {result.total}\n")
    if args.stats:
        _print_stats(input_bytes, result.total, "tokens", elapsed)
    return 0


def main(argv: list[str] | None = None) -> int:
    import os

    parser = build_parser()
    args = parser.parse_args(argv)
    if args.ngram < 1:
        parser.error(f"--ngram must be >= 1, got {args.ngram}")
    if (args.count_sketch or args.estimate) and not args.stream:
        parser.error("--count-sketch/--estimate require --stream")
    if args.distinct_sketch and not args.stream:
        # Honest failure beats a flag silently ignored: the non-stream path
        # never consults the sketch.
        parser.error("--distinct-sketch requires --stream")
    if args.sketch_flush_every != 1 and not (args.distinct_sketch
                                             or args.count_sketch
                                             or args.estimate):
        parser.error("--sketch-flush-every requires a sketch flag "
                     "(--distinct-sketch / --count-sketch / --estimate)")
    if args.checkpoint and not args.stream:
        parser.error("--checkpoint requires --stream")
    if args.autotune and not args.stream:
        parser.error("--autotune requires --stream (the single-buffer path "
                     "has no pipeline knobs to tune)")
    if args.autotune and (args.grep is not None or args.sample is not None):
        # The hint path rides run_job's word-count-family summary; grep/
        # sample streams have no tuner integration yet — honest refusal.
        parser.error("--autotune applies to word-count runs only")
    if args.retry and not args.stream:
        parser.error("--retry requires --stream (the non-stream path has no "
                     "step dispatch to retry)")
    if args.retry < 0:
        parser.error(f"--retry must be >= 0, got {args.retry}")
    if args.fault_plan is None:
        # The env default binds only to streamed runs: exporting
        # MAPREDUCE_FAULT_PLAN to chaos-test a service must not turn
        # every unrelated batch-mode invocation into a hard error.
        import os as _os

        env_plan = _os.environ.get("MAPREDUCE_FAULT_PLAN") or None
        if env_plan:
            if args.stream:
                args.fault_plan = env_plan
            else:
                print("warning: MAPREDUCE_FAULT_PLAN is set but this is "
                      "not a --stream run; fault injection skipped",
                      file=sys.stderr)
    elif not args.stream:
        parser.error("--fault-plan requires --stream (the injection seams "
                     "exist only on the streamed path)")
    if args.grep_syntax != "literal" and args.grep is None:
        parser.error("--grep-syntax requires --grep")
    if (args.count_sketch or args.estimate) and args.distinct_sketch:
        parser.error("--count-sketch/--estimate and --distinct-sketch are "
                     "mutually exclusive per run")
    if args.sample is not None and args.sample < 1:
        # A distinct None default so an explicit --sample 0 errors instead
        # of silently falling through to word-count mode.
        parser.error(f"--sample must be >= 1, got {args.sample}")
    if args.grep is not None or args.sample is not None:
        # Honest failure beats a flag silently ignored: grep/sample modes
        # do not count words, so word-count-only flags are errors.
        mode = "--grep" if args.grep is not None else "--sample"
        for flag, present in (("--ngram", args.ngram != 1),
                              ("--top-k", bool(args.top_k)),
                              ("--distinct-sketch", args.distinct_sketch),
                              ("--count-sketch", args.count_sketch),
                              ("--estimate", bool(args.estimate)),
                              ("--merge-every", args.merge_every != 1)):
            if present:
                parser.error(f"{flag} is not supported with {mode}")
    if args.grep is not None and args.sample is not None:
        parser.error("--grep and --sample are mutually exclusive")
    if args.verify_sample:
        if args.verify_sample < 0:
            parser.error(f"--verify-sample must be >= 0, got {args.verify_sample}")
        if args.ngram > 1 or args.grep is not None or args.sample is not None:
            # Recounting is word-keyed; gram spans contain separators and
            # grep/sample report no counts to check.
            parser.error("--verify-sample applies to word-count runs only")
    if args.ngram > 1 and args.merge_every > 1:
        # Mirror NGramCountJob's refusal as a clean usage error instead of a
        # mid-run traceback (the n-gram combine is pairwise by design).
        parser.error("--merge-every applies to word-count runs only "
                     "(not --ngram)")
    if args.merge_every != 1 and not args.stream:
        # Honest failure beats a knob silently ignored: the single-buffer
        # path has no per-step merges to batch.
        parser.error("--merge-every requires --stream")
    if args.merge_strategy != "tree":
        # Same honesty rule: the collective strategy only exists on the
        # streamed word-count path (grep/sample states ride psum-like
        # merges; the single-buffer path has no collective at all).
        if not args.stream:
            parser.error("--merge-strategy requires --stream")
        if args.grep is not None or args.sample is not None:
            parser.error("--merge-strategy applies to word-count runs only")
        if args.merge_strategy.startswith("hier-"):
            # The hierarchical 2-D programs place legs on named mesh axes;
            # the CLI drives a 1-D data mesh, so refuse here instead of
            # surfacing the Engine's multi-axis ValueError mid-run.
            parser.error(f"--merge-strategy {args.merge_strategy} needs a "
                         "multi-axis device mesh; the CLI drives a 1-D "
                         "mesh (2-D programs run via the fleet registry "
                         "twins / run_job_global)")
    if args.merge_overlap:
        if not args.stream:
            parser.error("--merge-overlap requires --stream")
        if args.retry:
            parser.error("--merge-overlap requires --retry 0 (the replay "
                         "anchor snapshots local state only; an overlapped "
                         "window has shipped counts the anchor cannot "
                         "restore)")
    paths = args.input
    try:
        # Probe readability up front (the reference silently succeeds on
        # fopen failure, main.cu:174); stream mode never loads the files.
        chunks = []
        input_bytes = 0
        for path in paths:  # one pass so a failure blames the right file
            input_bytes += os.path.getsize(path)
            with open(path, "rb") as f:
                if not args.stream:
                    chunks.append(f.read())
        # Non-stream, multi-file: files are independent token streams; join
        # with a separator so no token merges across a file boundary.  Grep
        # keeps the per-file list instead — its patterns may contain the
        # separator, so any join byte could fabricate cross-file matches.
        if args.stream:
            data = None
        elif args.grep is not None:
            data = chunks
        else:
            data = b"\n".join(chunks)
        del chunks  # don't hold a second copy of the corpus for the run
    except OSError as e:
        print(f"error: cannot read {path}: {e}", file=sys.stderr)
        return 2

    try:
        config = Config(chunk_bytes=args.chunk_bytes, table_capacity=args.table_capacity,
                        backend=args.backend, superstep=args.superstep,
                        inflight_groups=args.inflight,
                        prefetch_depth=args.prefetch_depth,
                        pallas_max_token=args.max_token_bytes,
                        sketch_flush_every=args.sketch_flush_every,
                        sort_mode=args.sort_mode,
                        map_impl=args.map_impl,
                        merge_every=args.merge_every,
                        compact_slots=args.compact_slots,
                        combiner=args.combiner,
                        combiner_slots=args.combiner_slots,
                        geometry=args.geometry,
                        rescue_overlong=args.rescue_overlong,
                        rescue_overlong_max=args.rescue_overlong_max,
                        rescue_window=args.rescue_window,
                        fault_plan=args.fault_plan,
                        merge_overlap=args.merge_overlap,
                        autotune="hint" if args.autotune else "off")
    except ValueError as e:
        parser.error(str(e))

    if args.geometry == "auto":
        # Resolve 'auto' BEFORE any trace, against the geometry search's
        # tuned profiles (the combiner='auto' discipline: resolution is
        # the driver's job; the resolved set is stamped into this run's
        # records via the run_start geometry label).
        import dataclasses as _dc

        from mapreduce_tpu.analysis.geometry import resolve_auto

        resolved_geom = resolve_auto(args.geometry_profile)
        config = _dc.replace(
            config, geometry=None if resolved_geom == "default"
            else resolved_geom)
        print(f"geometry: auto -> {config.geometry_label}",
              file=sys.stderr)

    if args.combiner == "auto":
        # Resolve 'auto' BEFORE any trace, against the prior run's records
        # in the --ledger file (append-mode ledgers hold run history — the
        # most recent data-health verdict decides; no ledger history
        # resolves to 'off').  The resolved mode is stamped into this
        # run's own run_start/data records, so a chain of 'auto' runs is
        # a self-documenting feedback loop.  The read goes through the
        # run-history warehouse's resolve_prior (ISSUE 14: the one place
        # "what did runs like this one do before" is answered) — same
        # outcome as the old datahealth.resolve_combiner read.
        import dataclasses as _dc

        records = []
        if args.ledger and os.path.exists(args.ledger):
            from mapreduce_tpu.obs import read_ledger

            records = read_ledger(args.ledger)
        from mapreduce_tpu.obs import history

        resolved = history.resolve_prior(records=records)["combiner"]
        # An 'off' resolution also drops any explicit cache sizing: the
        # slots knob only exists with the cache (Config validates that).
        config = _dc.replace(
            config, combiner=resolved,
            combiner_slots=config.combiner_slots
            if resolved == "hot-cache" else None)
        print(f"combiner: auto -> {resolved}"
              + ("" if records else " (no ledger history)"), file=sys.stderr)

    if args.merge_strategy == "auto":
        # Resolve 'auto' BEFORE any trace, against the static reduction
        # planner's tuned profiles (tools/redplan.py --out writes the
        # modeled winner next to the geometry/autotune profiles) — the
        # geometry/combiner 'auto' discipline: resolution is the driver's
        # job, and the RESOLVED strategy is stamped into this run's
        # run_start, never the literal 'auto'.  The CLI drives a 1-D
        # mesh, so only single-axis strategies are eligible — a hier-*
        # winner planned over a 2-D fleet mesh is skipped, and no
        # matching profile falls back loudly to 'tree'.
        from mapreduce_tpu.obs import history

        single_axis = tuple(s for s in MERGE_STRATEGIES
                            if not s.startswith("hier-"))
        prior = history.resolve_prior(profile_path=args.geometry_profile,
                                      merge_allowed=single_axis)
        args.merge_strategy = prior["merge_strategy"]
        print(f"merge-strategy: auto -> {args.merge_strategy}"
              + ("" if prior["merge_strategy_profile"]
                 else " (no redplan profile; tree)"), file=sys.stderr)

    from mapreduce_tpu.runtime import profiling

    # Persistent XLA compile cache (multi-minute first compiles otherwise):
    # JAX_COMPILATION_CACHE_DIR, else the checkout's .jax_cache.
    profiling.enable_compile_cache()

    # Honor a cpu request (--platform cpu / JAX_PLATFORMS=cpu) BEFORE any
    # device use.
    try:
        _apply_platform(args.platform)
    except RuntimeError as e:  # cpu force could not land (backend already up)
        print(f"error: {e}", file=sys.stderr)
        return 2

    # One telemetry handle across every mode: the run ledger + flight
    # recorder (--ledger) and the registry snapshot (--metrics-out).  The
    # finally guarantees the snapshot and ledger flush land even when the
    # run itself failed — a crashed telemetered run must leave evidence.
    # --autotune also forces a handle (ledgerless when --ledger is
    # absent): the hint is derived from telemetry, and the CLI reports it
    # from the handle (count_file never returns the RunResult that
    # carries it).
    tel = None
    if args.ledger or args.metrics_out or args.autotune:
        from mapreduce_tpu import obs

        try:
            tel = obs.Telemetry.create(ledger_path=args.ledger)
        except OSError as e:
            print(f"error: cannot open ledger {args.ledger}: {e}",
                  file=sys.stderr)
            return 2
    try:
        if args.grep is not None:
            return _grep_main(args, paths, data, config, input_bytes,
                              telemetry=tel)
        if args.sample is not None:
            return _sample_main(args, paths, data, config, input_bytes,
                                telemetry=tel)
        return _wordcount_main(args, paths, data, config, input_bytes,
                               telemetry=tel)
    except Exception as e:
        # Orderly preemption shutdown (ISSUE 15): the stream drained and
        # (when configured) checkpointed before raising — a clean
        # one-line exit with the resume cursor, not a crash traceback.
        # Exit 75 (EX_TEMPFAIL): relaunch the same command to resume.
        from mapreduce_tpu.runtime import faults as faults_mod

        if not isinstance(e, faults_mod.Preempted):
            raise
        print(f"preempted: {e}", file=sys.stderr)
        return 75
    finally:
        if tel is not None:
            if args.metrics_out:
                try:
                    with open(args.metrics_out, "w") as f:
                        json.dump(tel.registry.snapshot(), f, indent=1)
                        f.write("\n")
                except OSError as e:
                    print(f"error: cannot write {args.metrics_out}: {e}",
                          file=sys.stderr)
            tel.close()


def _print_tune(telemetry) -> None:
    """Report the run's autotune recommendation (ISSUE 10) to stderr —
    the CLI's "run summary" surface for --autotune.  The full record
    (signals + decision trail) lands in the ledger; stdout stays the
    reference-parity result."""
    t = getattr(telemetry, "last_tune", None)
    if not t:
        print("autotune: no recommendation (hint path unavailable "
              "for this run)", file=sys.stderr)
        return
    changed = t.get("changed") or {}
    moves = ", ".join(f"{k} {v[0]} -> {v[1]}" for k, v in changed.items())
    verdict = "converged" if t.get("converged") else (moves or "no move")
    print(f"autotune: {t.get('rule')} — {verdict}", file=sys.stderr)
    if t.get("reason"):
        print(f"autotune: {t['reason']}", file=sys.stderr)


def _resolved_backend_name(config) -> str:
    """The backend a run will actually use, for ledger records: 'auto'
    must never reach the ledger (consumers key data records on the real
    map path), but backend resolution needs jax — degrade to the raw
    string rather than fail a telemetry write."""
    try:
        return config.resolved_backend()
    except Exception:
        return config.backend


def _batch_run_start(tel, job: str, paths, config, input_bytes: int) -> None:
    """Telemetered BATCH (non---stream) runs emit a run_start up front
    (ISSUE 8 satellite: --ledger no longer requires --stream): the
    single-buffer path has no step dispatches, so the ledger carries
    run_start, a result-derived `data` record, and run_end — enough for
    obs_report/--compare, and a crash leaves the honest run_start-only
    trail."""
    from mapreduce_tpu.runtime.executor import _geometry_stamp

    tel.ledger_write("run_start", driver="single_buffer", job=job,
                     devices=1, chunk_bytes=input_bytes,
                     superstep=1, backend=_resolved_backend_name(config),
                     map_impl=config.map_impl,
                     combiner=config.resolved_combiner,
                     **_geometry_stamp(config),
                     merge_strategy="none", input=list(paths),
                     resume_step=0, resume_offset=0, retry=0)


def _wordcount_main(args, paths, data, config, input_bytes: int,
                    telemetry=None) -> int:
    """Default mode: word counts (the reference's contract)."""
    from mapreduce_tpu.runtime import profiling

    batch_tel = telemetry if not args.stream else None
    if batch_tel is not None:
        job = f"ngram{args.ngram}" if args.ngram > 1 else "wordcount"
        _batch_run_start(batch_tel, job, paths, config, input_bytes)
    t0 = time.perf_counter()
    with profiling.trace(args.profile):
        if args.stream:
            from mapreduce_tpu.runtime.executor import count_file

            result = count_file(paths, config=config, top_k=args.top_k or None,
                                distinct_sketch=args.distinct_sketch,
                                count_sketch=args.count_sketch or bool(args.estimate),
                                ngram=args.ngram,
                                merge_strategy=args.merge_strategy,
                                checkpoint_path=args.checkpoint,
                                checkpoint_every=args.checkpoint_every if args.checkpoint else 0,
                                retry=args.retry, telemetry=telemetry)
        else:
            from mapreduce_tpu.models import wordcount

            result = wordcount.count_ngrams(data, args.ngram, config) \
                if args.ngram > 1 else wordcount.count_words(data, config)
    elapsed = time.perf_counter() - t0
    if batch_tel is not None:
        # Result-derived data record: the batch path runs one jitted
        # program over the whole buffer, so the data-plane story IS the
        # result's accounting (tokens, dropped, distinct, top count).
        batch_tel.ledger_write(
            "data", groups=1, chunks=1,
            backend=_resolved_backend_name(config),
            map_impl=config.map_impl,
            combiner=config.resolved_combiner,
            capacity=config.table_capacity, tokens=result.total,
            dropped_tokens=result.dropped_count,
            dropped_uniques=result.dropped_uniques,
            table_valid=len(result.words),
            top_count=max(result.counts, default=0),
            table_occupancy=round(
                len(result.words) / max(config.table_capacity, 1), 4))
        batch_tel.ledger_write("run_end", bytes=input_bytes,
                               words=result.total,
                               elapsed_s=round(elapsed, 6))

    if args.top_k and not args.stream:  # stream mode already applied top-k
        from mapreduce_tpu.models.wordcount import apply_top_k

        result = apply_top_k(result, args.top_k)
    words, counts = result.words, result.counts

    estimates = {w: result.estimate_count(w.encode()) for w in args.estimate} \
        if result.cms is not None else {}

    out = sys.stdout
    display = _decode(words)
    if args.format == "reference":
        if not args.no_echo:
            _echo_file(paths)
        out.write("--------------------------\n")
        for w, c in zip(display, counts):
            out.write(f"{w}\t{c}\n")
        out.write("--------------------------\n")
        out.write(f"Total Count:{result.total}\n")
        for w, e in estimates.items():
            out.write(f"estimate:{w}\t{e}\n")
    elif args.format == "tsv":
        for w, c in zip(display, counts):
            out.write(f"{w}\t{c}\n")
        for w, e in estimates.items():
            out.write(f"estimate:{w}\t{e}\n")
    else:
        # "counts" is a list of pairs, not an object: distinct byte words must
        # stay distinct entries even if their display decodings collide.
        payload = {
            "counts": [[w, c] for w, c in zip(display, counts)],
            "total": result.total,
            "distinct": result.distinct,
            "dropped_uniques": result.dropped_uniques,
            "dropped_count": result.dropped_count,
        }
        if result.distinct_estimate is not None:
            payload["distinct_estimate"] = round(result.distinct_estimate, 1)
        if estimates:
            payload["estimates"] = estimates
        out.write(json.dumps(payload) + "\n")

    if args.stats:
        _print_stats(input_bytes, result.total, "words", elapsed)

    if args.autotune:
        _print_tune(telemetry)

    if args.verify_sample:
        from mapreduce_tpu.utils.verify import verify_result

        mismatches = verify_result(words, counts, paths,
                                   sample=args.verify_sample)
        if mismatches:
            for w, rep, true in mismatches:
                print(f"verify: MISMATCH {w!r}: reported {rep}, exact "
                      f"recount {true} (possible 64-bit key collision — "
                      "see mapreduce_tpu/utils/verify.py)", file=sys.stderr)
            return 4
        print(f"verify: ok ({min(args.verify_sample, len(words))} words "
              "recounted exactly)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
