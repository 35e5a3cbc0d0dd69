"""Built-in model registry.

One place that names every shipped model family and builds a
representative job for it — the surface the graphcheck CLI (and any future
model-zoo tooling) enumerates.  Factories take a
:class:`~mapreduce_tpu.config.Config` and return a fully-constructed job;
models whose jobs are config-free by construction (grep: the pattern IS
the job, there is no sizing to configure) accept and ignore it, so the
registry surface stays uniform.  The default analysis config keeps shapes
small (tracing and the randomized property checks run on the host in
seconds, not minutes).
"""

from __future__ import annotations

from typing import Callable, Dict

from mapreduce_tpu.config import Config

# Small shapes for static analysis / smoke tracing: the jaxprs are the
# same graphs as production, just with smaller static dimensions.
ANALYSIS_CONFIG = Config(chunk_bytes=1 << 10, table_capacity=512,
                         backend="xla")

# Production-shaped pallas wordcount (the shipped default path: stable2
# lane-major compact kernel + XLA aggregation sort), at one full 384-row
# kernel window per lane.  Registered for the costcheck passes: the cost
# pass re-derives the round-6 sort pricing (2.6-3.4 effective HBM passes)
# from THIS program's traced sort equation, and the vmem/kernelrace passes
# certify the stable2 kernel geometry from its pallas_call bindings.  The
# jaxprs are the production graphs with a smaller grid.
PALLAS_ANALYSIS_CONFIG = Config(chunk_bytes=128 * 384, table_capacity=512,
                                backend="pallas")

# Fused map path (ISSUE 6): the same production-shaped stable2 pallas
# program with Config.map_impl='fused' — tokenize -> hash -> window
# compaction in ONE pallas_call, no token-plane round-trip to HBM.  Same
# chunk geometry as PALLAS_ANALYSIS_CONFIG so the hbm-cost pass's
# `effective_input_passes` is directly comparable: the cost pass ERROR-
# gates this model strictly below the split-path wordcount_pallas
# baseline (the machine-checked before/after of the fusion).
FUSED_ANALYSIS_CONFIG = Config(chunk_bytes=128 * 384, table_capacity=512,
                               backend="pallas", map_impl="fused")

# Skew-adaptive map-side combiner pair (ISSUE 11): the Zipf-shaped model
# with the hot-key cache ON vs its combiner-off twin, both fused/stable2
# at one shared chunk geometry so the hbm-cost combiner gate compares
# like with like.  The chunk is 128 * 512 — the analyzer's 64 KiB
# tracing cap, and one lane segment spanning a whole combiner window —
# so the sort-row delta is exact window arithmetic: nocombiner grids 3
# 384-row windows of 128 slots (49152 sort rows), the combiner 2
# 512-row windows (32768 rows, −33%; −25% at the 32 MB production chunk
# where the padding window amortizes away).
COMBINER_ANALYSIS_CONFIG = Config(chunk_bytes=128 * 512, table_capacity=512,
                                  backend="pallas", map_impl="fused",
                                  combiner="hot-cache")
NOCOMBINER_ANALYSIS_CONFIG = Config(chunk_bytes=128 * 512,
                                    table_capacity=512,
                                    backend="pallas", map_impl="fused")


def _wordcount(config: Config):
    from mapreduce_tpu.models.wordcount import WordCountJob

    return WordCountJob(config)


def _grep(config: Config):
    from mapreduce_tpu.models.grep import GrepJob

    del config  # GrepJob is config-free: the pattern is the whole job
    return GrepJob(b"the")


def _sample(config: Config):
    from mapreduce_tpu.models.sample import ReservoirSampleJob

    return ReservoirSampleJob(16, config)


def _ngram(config: Config):
    from mapreduce_tpu.models.wordcount import NGramCountJob

    return NGramCountJob(2, config)


def _sketch(config: Config):
    from mapreduce_tpu.models.wordcount import (SketchedWordCountJob,
                                                WordCountJob)

    return SketchedWordCountJob(WordCountJob(config))


def _wordcount_pallas(config: Config):
    from mapreduce_tpu.models.wordcount import WordCountJob

    # Pinned config like grep's pinned pattern: the model EXISTS to put the
    # shipped stable2 pallas program in front of the costcheck passes, so
    # the caller's sizing config is deliberately ignored.
    del config
    return WordCountJob(PALLAS_ANALYSIS_CONFIG)


def _wordcount_fused(config: Config):
    from mapreduce_tpu.models.wordcount import WordCountJob

    # Pinned config (see _wordcount_pallas): the model exists to put the
    # fused map program in front of the full graphcheck/costcheck gate,
    # with its cost baseline error-gated below the split path's.
    del config
    return WordCountJob(FUSED_ANALYSIS_CONFIG)


def _wordcount_combiner(config: Config):
    from mapreduce_tpu.models.wordcount import WordCountJob

    # Pinned config (see _wordcount_pallas): the Zipf-shaped combiner-ON
    # program — the hbm-cost combiner gate prices it strictly below its
    # combiner-off twin, and the vmem/kernelrace passes certify the
    # hot-key cache's revisited-output discipline.
    del config
    return WordCountJob(COMBINER_ANALYSIS_CONFIG)


def _wordcount_nocombiner(config: Config):
    from mapreduce_tpu.models.wordcount import WordCountJob

    # Pinned config: the combiner-off twin at the SAME chunk geometry —
    # the baseline the combiner gate compares against.
    del config
    return WordCountJob(NOCOMBINER_ANALYSIS_CONFIG)


def _instrumented(job):
    """Mark a job so ``analysis.trace.trace_engine`` builds the Engine in
    data-stats mode (ISSUE 8): the traced step program is the INSTRUMENTED
    one telemetered runs dispatch — map counters + state gauges returned
    next to the state — so the hbm-cost pass prices exactly what
    observability costs (ERROR-gated within 1% of the uninstrumented
    twin's baseline) and the host-sync pass certifies the stats path adds
    no host coupling."""
    job.analysis_data_stats = True
    return job


def _wordcount_telemetry(config: Config):
    from mapreduce_tpu.models.wordcount import WordCountJob

    # Pinned config (see _wordcount_pallas): the data-stats twin of the
    # shipped stable2 pallas program, priced against it at 1%.
    del config
    return _instrumented(WordCountJob(PALLAS_ANALYSIS_CONFIG))


def _wordcount_fused_telemetry(config: Config):
    from mapreduce_tpu.models.wordcount import WordCountJob

    # Pinned config: the data-stats twin of the fused map program.
    del config
    return _instrumented(WordCountJob(FUSED_ANALYSIS_CONFIG))


def _fleet(job, processes: int, local_devices: int, merge: str = "tree"):
    """Mark a job so analysis certifies it over a SIMULATED fleet
    topology (ISSUE 16): ``analysis_fleet`` makes ``AnalysisContext``
    build the process-major mesh (outer axis rides DCN) and lets the
    collective-cost pass attribute link levels; ``analysis_merge_strategy``
    selects the Engine merge the traced finish program builds."""
    job.analysis_fleet = {"processes": processes,
                          "local_devices": local_devices}
    job.analysis_merge_strategy = merge
    return job


def _wordcount_fleet2(config: Config):
    from mapreduce_tpu.models.wordcount import WordCountJob

    # Pinned config (see _wordcount_pallas): the 2-host x 4-device fleet
    # twin — the hierarchical tree merge's butterfly runs per level
    # (inner ICI axis first, one merged payload across DCN), so the
    # collective-cost pass prices a real 2-D ICI/DCN program in CI.
    del config
    return _fleet(WordCountJob(ANALYSIS_CONFIG), processes=2,
                  local_devices=4)


def _wordcount_fleet2x4(config: Config):
    from mapreduce_tpu.models.wordcount import WordCountJob

    # Pinned config: the 2-host x 4-device twin on the PLACED hierarchical
    # merge (ISSUE 20) — key-range all_to_all + owner-reduce + all_gather
    # confined to the inner ICI axis, then one butterfly tree leg across
    # DCN — so the collective-cost pass prices the planner's 2-D
    # skew-sensitive program (hier-kr-tree) in CI next to the per-level
    # tree twin (_wordcount_fleet2) over the identical topology.
    del config
    return _fleet(WordCountJob(ANALYSIS_CONFIG), processes=2,
                  local_devices=4, merge="hier-kr-tree")


def _wordcount_fleet8(config: Config):
    from mapreduce_tpu.models.wordcount import WordCountJob

    # Pinned config: the 8-host x 1-device twin on the keyrange merge —
    # the budgeted all_to_all + owner-reduce + all_gather program over a
    # flattened all-DCN axis, the other end of the planner's tradeoff.
    del config
    return _fleet(WordCountJob(ANALYSIS_CONFIG), processes=8,
                  local_devices=1, merge="keyrange")


_REGISTRY: Dict[str, Callable[[Config], object]] = {
    "wordcount": _wordcount,
    "grep": _grep,
    "sample": _sample,
    "ngram": _ngram,
    "sketch": _sketch,
    "wordcount_pallas": _wordcount_pallas,
    "wordcount_fused": _wordcount_fused,
    "wordcount_combiner": _wordcount_combiner,
    "wordcount_nocombiner": _wordcount_nocombiner,
    "wordcount_telemetry": _wordcount_telemetry,
    "wordcount_fused_telemetry": _wordcount_fused_telemetry,
    "wordcount_fleet2": _wordcount_fleet2,
    "wordcount_fleet2x4": _wordcount_fleet2x4,
    "wordcount_fleet8": _wordcount_fleet8,
}


def model_names() -> list[str]:
    return list(_REGISTRY)


def build_model(name: str, config: Config = ANALYSIS_CONFIG):
    """Construct the named built-in model's job."""
    try:
        factory = _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown model {name!r}; "
                         f"known: {', '.join(_REGISTRY)}") from None
    return factory(config)


__all__ = ["ANALYSIS_CONFIG", "COMBINER_ANALYSIS_CONFIG",
           "FUSED_ANALYSIS_CONFIG", "NOCOMBINER_ANALYSIS_CONFIG",
           "PALLAS_ANALYSIS_CONFIG", "build_model", "model_names"]
