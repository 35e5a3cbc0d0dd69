#!/usr/bin/env python3
"""Smoke run of the streamed word count on the TPU: does the main path start
on the chip and count exactly?  A smoke run, not a benchmark: the times it
prints include compilation and are not measurements of anything.

One chip (the default):

(a) ``mapreduce_tpu.cli.main(["test.txt"])`` in-process; its stdout must
    equal the reference block in README.md byte for byte.
(b) A 1 GiB seeded Zipf corpus (``bench.make_zipf_corpus``: 50K words,
    seed 7) streamed through ``runtime.executor.count_file`` with
    ``DEFAULT_CONFIG`` (32 MiB chunks, 2**18-slot table, backend auto).  The
    backend must resolve to pallas, every Pallas kernel must be compiled
    (none interpreted), the native chunker must load, nothing may be
    dropped, and words, counts and total must equal
    ``utils.oracle.word_counts`` exactly.

``--chips 4`` runs only (b) over ``data_mesh(4)``, once with the tree merge
and once with the key-range merge, each compared with the oracle.

The last line of stdout is ``{"ok": true, "device": {...}}``.  Without a TPU
the script exits non-zero before any work and prints no such line; any
failed phase raises, so the script exits non-zero without it.  Everything
runs in this one process: it holds the chip.

``--rehearse`` runs the same phases on the CPU at a small size, with the
kernels interpreted, for checking the script itself off the chip.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import io
import json
import os
import shutil
import sys
import tempfile
import threading
import time
from importlib import metadata

REPO = os.path.dirname(os.path.abspath(__file__))
T0 = time.perf_counter()
CORPUS_BYTES = 1 << 30
VOCAB = 50_000
SEED = 7


def log(msg: str) -> None:
    # The process's own stdout: phase (a) redirects sys.stdout to capture
    # the CLI while the corpus thread logs.
    print(f"[smoke +{time.perf_counter() - T0:.1f}s] {msg}",
          file=sys.__stdout__, flush=True)


def readme_reference() -> bytes:
    """The ``./main test.txt`` output block of README.md."""
    with open(os.path.join(REPO, "README.md"), "rb") as f:
        lines = f.read().split(b"\n")
    start = lines.index(b"$ ./main test.txt") + 1
    end = lines.index(b"```", start)
    return b"\n".join(lines[start:end]) + b"\n"


class KernelSpy:
    """Records the ``interpret`` flag of every ``pallas_call`` traced."""

    def __init__(self):
        from jax.experimental import pallas as pl

        self.interpret: list[bool] = []
        real = pl.pallas_call

        def spy(*args, **kwargs):
            self.interpret.append(bool(kwargs.get("interpret", False)))
            return real(*args, **kwargs)

        pl.pallas_call = spy


class CompileClock:
    """Seconds the XLA backend spent compiling programs (tracing and
    lowering excluded: their events nest and would count twice)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0

        def listen(event, duration, **_):
            if event == self.EVENT:
                self.seconds += duration

        monitoring.register_event_duration_secs_listener(listen)


def phase_cli() -> None:
    from mapreduce_tpu import cli

    raw = io.BytesIO()
    out = io.TextIOWrapper(raw, encoding="utf-8", newline="\n")
    with contextlib.redirect_stdout(out):
        rc = cli.main([os.path.join(REPO, "test.txt")])
        out.flush()
    if rc != 0:
        raise SystemExit(f"phase cli: main returned {rc}")
    if raw.getvalue() != readme_reference():
        raise SystemExit("phase cli: stdout differs from README.md's "
                         f"reference block:\n{raw.getvalue()!r}")
    log("phase cli: ./main test.txt stdout == README.md reference "
        "(Total Count:9)")


class Corpus(threading.Thread):
    """Writes the seeded corpus, then counts it with the host oracle — off
    the main thread, so both overlap the main thread's compiles."""

    def __init__(self, path: str, n_bytes: int):
        super().__init__(daemon=True)
        self.path, self.n_bytes = path, n_bytes
        self.written = threading.Event()
        self.expected: dict | None = None
        self.error: BaseException | None = None

    def run(self) -> None:
        import bench
        from mapreduce_tpu.utils import oracle

        try:
            t0 = time.perf_counter()
            corpus = bench.make_zipf_corpus(self.n_bytes, vocab=VOCAB,
                                            seed=SEED)
            with open(self.path, "wb") as f:
                f.write(corpus)
            log(f"corpus written: {len(corpus)} bytes "
                f"({time.perf_counter() - t0:.1f}s)")
            self.written.set()
            self.expected = oracle.word_counts(corpus)
            log(f"host oracle: {len(self.expected)} distinct words "
                f"({time.perf_counter() - t0:.1f}s)")
        except BaseException as e:  # re-raised on the main thread
            self.error = e
            self.written.set()

    def wait_written(self) -> None:
        self.written.wait()
        if self.error is not None:
            raise self.error

    def oracle(self) -> dict:
        self.join()
        if self.error is not None:
            raise self.error
        return self.expected


def phase_count(path: str, oracle, config, mesh, label: str, rehearse: bool,
                clock: CompileClock, spy: KernelSpy) -> None:
    """Stream ``path`` through ``count_file`` and hold the result to
    ``oracle()`` (called after the count, so the host oracle of a big
    corpus may still be running while the chip counts)."""
    from mapreduce_tpu.runtime import executor

    log(f"phase {label}: streaming {path}")
    compile0, calls0 = clock.seconds, len(spy.interpret)
    t0 = time.perf_counter()
    res = executor.count_file(path, config, mesh=mesh, retry=0)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - compile0
    interpreted = spy.interpret[calls0:]
    if not rehearse:
        if not interpreted:
            raise SystemExit(f"phase {label}: no Pallas kernel was traced")
        if any(interpreted):
            raise SystemExit(f"phase {label}: a Pallas kernel ran "
                             "interpreted")
    if res.dropped_count or res.dropped_uniques:
        raise SystemExit(f"phase {label}: dropped_count={res.dropped_count}"
                         f" dropped_uniques={res.dropped_uniques}")
    expected = oracle()
    got = dict(zip(res.words, res.counts))
    if list(got.items()) != list(expected.items()):
        missing = len(expected.keys() - got.keys())
        extra = len(got.keys() - expected.keys())
        raise SystemExit(f"phase {label}: counts differ from the oracle "
                         f"({missing} words missing, {extra} extra, "
                         f"{len(got)} vs {len(expected)} distinct)")
    if res.total != sum(expected.values()):
        raise SystemExit(f"phase {label}: total {res.total} != oracle "
                         f"{sum(expected.values())}")
    log(f"phase {label}: exact match with the host oracle; "
        f"bytes={os.path.getsize(path)} words={res.total} "
        f"distinct={len(got)} kernels_traced={len(interpreted)} "
        f"interpreted={sum(interpreted)} wall_s={wall} "
        f"backend_compile_s={compile_s} (smoke run, compile included: "
        "not a benchmark number)")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the streamed count over a 4-chip mesh, "
                         "tree merge and key-range merge")
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at a small size (not a chip run)")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not args.rehearse:
        print(f"error: JAX found no TPU (platform {platform!r}); this is a "
              "chip smoke run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"error: --chips {args.chips} needs {args.chips} devices, "
              f"have {len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, REPO)
    from mapreduce_tpu import native
    from mapreduce_tpu.config import DEFAULT_CONFIG, Config
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime import profiling
    from mapreduce_tpu.utils import oracle

    profiling.enable_compile_cache()

    kind = devices[0].device_kind
    log(f"device={platform} kind={kind} count={len(devices)} "
        f"jax={jax.__version__} libtpu={metadata.version('libtpu')}")
    spy, clock = KernelSpy(), CompileClock()

    if native.load() is None:
        raise SystemExit("the native chunker did not load (see the warning "
                         "above)")
    log(f"native chunker loaded: {native.lib_path()}")

    config = DEFAULT_CONFIG
    corpus_bytes = CORPUS_BYTES
    if args.rehearse:
        config = Config(chunk_bytes=1 << 16, table_capacity=1 << 16,
                        backend="pallas")
        corpus_bytes = 1 << 21
    if config.failure_policy is not None:
        raise SystemExit("the smoke config must leave failure_policy unset")
    backend = config.resolved_backend()
    if backend != "pallas":
        raise SystemExit(f"backend resolved to {backend!r}, not 'pallas'")
    log(f"backend=pallas chunk_bytes={config.chunk_bytes} "
        f"table_capacity={config.table_capacity}")

    work = tempfile.mkdtemp(prefix=".chip_smoke-", dir=REPO)
    try:
        corpus = Corpus(os.path.join(work, "zipf.txt"), corpus_bytes)
        corpus.start()
        if args.chips == 1:
            phase_cli()
        mesh = data_mesh(args.chips)
        strategies = ("tree",) if args.chips == 1 else ("tree", "keyrange")
        # While the corpus is generated, stream test.txt through the same
        # entry point: it compiles the streamed step (the persistent
        # compile cache serves the corpus count) and is checked too.
        fixture = os.path.join(REPO, "test.txt")
        with open(fixture, "rb") as f:
            fixture_words = oracle.word_counts(f.read())
        for strategy in strategies:
            cfg = dataclasses.replace(config, merge_strategy=strategy)
            phase_count(fixture, lambda: fixture_words, cfg, mesh,
                        f"test.txt-{args.chips}chip-{strategy}",
                        args.rehearse, clock, spy)
        corpus.wait_written()
        for strategy in strategies:
            cfg = dataclasses.replace(config, merge_strategy=strategy)
            phase_count(corpus.path, corpus.oracle, cfg, mesh,
                        f"zipf-{corpus_bytes >> 20}MiB-{args.chips}chip-"
                        f"{strategy}",
                        args.rehearse, clock, spy)
    finally:
        shutil.rmtree(work)

    stats = devices[0].memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}"
        " (device 0)")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
