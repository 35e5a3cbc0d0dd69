#!/usr/bin/env python3
"""Benchmark: word-count throughput on a synthetic Zipf corpus (BASELINE.md).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...extras}.

The reference publishes no numbers and physically caps at ~5.8 KB of input
(SURVEY §6), so the baseline here is the natural host-CPU implementation a
user would reach for (``collections.Counter(data.split())``), measured on a
slice of the same corpus; ``vs_baseline`` is our GB/s over its GB/s.

Headline metric = the device MapReduce pipeline (tokenize + hash + count +
merge) on device-resident chunks, i.e. the part of the stack this framework
owns.  Host->device staging is measured and reported separately
(``h2d_gbps``), and the streamed pass (reader -> H2D -> compute -> merge)
reports the end-to-end ingest rate.

Measurement shape: the whole corpus is staged on device once, then the timed
window is ONE ``Engine.step_many(..., repeats=R)`` dispatch that cycles the
resident chunks R times (epoch semantics) — processing corpus*R bytes of
map+combine work in a single program, so dispatch latency stays out of the
device-compute number.

Env knobs: BENCH_MB (corpus size, default 256; the timed window is
corpus*BENCH_REPEATS regardless), BENCH_CHUNK_MB (per-device
step size, default 32 — the measured sweet spot on v5e), BENCH_REPEATS
(device passes over the resident corpus in the timed dispatch, default 8),
BENCH_SUPERSTEP (override chunks per dispatch; default: all resident),
BENCH_BASELINE_MB (CPU baseline slice, default 16), BENCH_SORT_MODE /
BENCH_MAP_IMPL / BENCH_COMBINER / BENCH_GEOMETRY /
BENCH_MERGE_EVERY /
BENCH_MERGE_STRATEGY (tree / gather / keyrange / hier-kr-tree /
hier-tree-tree / auto — the reduction seam the static planner
`tools/redplan.py` ranks; keyrange is the planner's skew-sensitive
alternative, the hier-* 2-D programs need a fleet mesh, and 'auto'
warm-starts from the planner's freshest tuned.json profile via
resolve_prior — the resolved strategy is stamped, never 'auto';
BENCH_MERGE_PROFILE overrides the profile path) /
BENCH_MERGE_OVERLAP (1 = drain local tables into a resident accumulator
at window boundaries on the STREAMED pass — async partial collectives
overlapped with the map stream, bit-identical results, op='partial'
ledger records; ISSUE 20 leg 2) /
BENCH_COMPACT_SLOTS /
BENCH_INFLIGHT / BENCH_PREFETCH_DEPTH (A/B knobs; BENCH_INFLIGHT=1 is the
serialized dispatch-window control, see Config.inflight_groups; BENCH_MAP_IMPL=fused
runs the ISSUE 6 fused map kernel, see Config.map_impl;
BENCH_COMBINER=hot-cache runs the ISSUE 11 map-side combiner on top of
it, see Config.combiner).

BENCH JSON carries a `cost` record: the static hbm-cost pricing
(`effective_input_passes`) of the benched map path's registry twin
(wordcount_fused vs wordcount_pallas), so every bench row states the
predicted HBM-pass count next to the measured GB/s, so a fused-vs-split
A/B reads the predicted delta and the measured delta from the same JSON.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

import numpy as np

def make_zipf_corpus(n_bytes: int, vocab: int = 50_000, a: float = 1.3,
                     seed: int = 7) -> bytes:
    rng = np.random.default_rng(seed)
    words = np.array([b"w%d" % i for i in range(vocab)], dtype=object)
    # Zipf draws skew short (w1, w2, ...), so bytes-per-word is corpus-
    # dependent: generate in slabs until the requested size is reached.
    parts, have = [], 0
    while have < n_bytes:
        idx = rng.zipf(a, size=1 << 20).astype(np.int64) % vocab
        slab = b" ".join(words[idx]) + b" "
        parts.append(slab)
        have += len(slab)
    blob = b"".join(parts)
    return blob[:n_bytes].rsplit(b" ", 1)[0] + b"\n"


# ~200 high-frequency English words: the head of a realistic unigram
# distribution (the tail is synthesized below with rarer, longer forms).
_COMMON = ("the of and to in a is that it was for on are as with his they at"
           " be this have from or one had by word but not what all were we"
           " when your can said there use an each which she do how their if"
           " will up other about out many then them these so some her would"
           " make like him into time has look two more write go see number"
           " no way could people my than first water been call who oil its"
           " now find long down day did get come made may part over new sound"
           " take only little work know place year live me back give most"
           " very after thing our just name good sentence man think say great"
           " where help through much before line right too mean old any same"
           " tell boy follow came want show also around form three small set"
           " put end does another well large must big even such because turn"
           " here why ask went men read need land different home us move try"
           " kind hand picture again change off play spell air away animal"
           " house point page letter mother answer found study still learn"
           " should america world high every near add food between own below"
           " country plant last school father keep tree never start city"
           " earth eye light thought head under story saw left dont few while"
           " along might close something seem next hard open example begin"
           " life always those both paper together got group often run").split()


def make_natural_corpus(n_bytes: int, seed: int = 11) -> bytes:
    """English-like text proxy (enwik8 stand-in: nothing real is mountable).

    Unlike the pure-Zipf corpus, this has the statistics that stress the
    pipeline the way natural text does: punctuation attached to words (so
    ``word`` / ``word,`` / ``word.`` are distinct tokens), sentence-initial
    capitalization (more distinct casings), a heavy head of short common
    words plus a long tail of rarer coined forms, variable sentence and
    paragraph lengths, and occasional markup-ish tokens.  Fully vectorized
    per slab (numpy choice + np.char ops): generation must not dominate the
    run at BENCH_MB=256+.
    """
    rng = np.random.default_rng(seed)
    head = np.array(_COMMON)
    tail = np.array([f"{head[i % len(head)]}{head[(i * 7 + 3) % len(head)]}"
                     + ("ing" if i % 3 else "s") for i in range(20_000)])
    parts: list[bytes] = []
    have = 0
    slab_n = 200_000  # words per vectorized slab (~1.1 MB)
    while have < n_bytes:
        words = np.where(rng.random(slab_n) < 0.18,
                         tail[rng.integers(0, len(tail), size=slab_n)],
                         head[rng.integers(0, len(head), size=slab_n)])
        # Sentence ends (~every 12 words); the following word starts a
        # sentence and is capitalized.
        ends = rng.random(slab_n) < (1 / 12)
        starts = np.concatenate([[True], ends[:-1]])
        words[starts] = np.char.capitalize(words[starts])
        # Markup-ish tokens replace ~0.5% of words.
        mk = rng.random(slab_n) < 0.005
        words[mk] = np.where(rng.random(int(mk.sum())) < 0.5,
                             "[[link]]", "&quot;")
        # Punctuation: terminal . / ? at ends, commas mid-sentence.
        r = rng.random(slab_n)
        suffix = np.where(ends, np.where(r < 0.9, ".", "?"),
                          np.where(r < 0.06, ",", ""))
        # Paragraph breaks after ~12% of sentence ends.
        sep = np.where(ends & (rng.random(slab_n) < 0.12), "\n", " ")
        slab = "".join(np.char.add(np.char.add(words, suffix), sep).tolist()) \
            .encode()
        parts.append(slab)
        have += len(slab)
    return b"".join(parts)[:n_bytes].rsplit(b" ", 1)[0] + b"\n"


def make_webby_corpus(n_bytes: int, seed: int = 23) -> bytes:
    """Natural-text proxy with an enwik-like long-token tail.

    enwik8 (wikipedia XML) carries URLs, wiki-link paths and attribute blobs
    far beyond the pallas kernel's W=32 window; WET Common-Crawl text adds
    base64-ish junk.  ~0.3% of words here become such tokens (enwik8
    ballpark: 0.1-0.5% of whitespace-delimited tokens exceed 32 bytes),
    lengths log-uniform in [33, 300] — the corpus that exercises the
    overlong-rescue path (ops/rescue.py) under benchmark load, where the
    other generators never fire its cond.
    """
    rng = np.random.default_rng(seed)
    words = make_natural_corpus(n_bytes, seed=seed).split(b" ")
    alpha = np.frombuffer(b"abcdefghijklmnopqrstuvwxyz0123456789./_-=&?",
                          np.uint8)
    # Splice URLs at ~0.3% of sites: touch only the chosen sites (one draw
    # of all URL bytes up front), not every word — corpus generation runs
    # inside the timed chip call.
    sites = np.flatnonzero(rng.random(len(words)) < 0.003)
    lengths = np.exp(rng.uniform(np.log(33), np.log(300),
                                 size=len(sites))).astype(np.int64)
    blob = alpha[rng.integers(0, len(alpha), int(lengths.sum()))].tobytes()
    ends = np.cumsum(lengths)
    for i, site in enumerate(sites):
        words[site] = b"http://" + blob[ends[i] - lengths[i]:ends[i]]
    return b" ".join(words)[:n_bytes]


def make_markup_corpus(n_bytes: int, seed: int = 31) -> bytes:
    """enwik-like markup proxy: the hostile-input stand-in (round-4 review
    missing #3 — the other generators are clean ASCII).

    Structured like wikipedia XML dumps: nested tags with attribute blobs,
    ``[[wiki links|display text]]``, ``&entities;``, UTF-8 MULTIBYTE words
    (Latin-1 accents, Greek, CJK — continuation bytes >= 0x80 must never
    split tokens), URLs past the W=32 window, and occasional very long
    separator-free attribute runs that exercise the reader's force-split.
    Tokens here are what the framework's whitespace semantics see — e.g.
    ``<title>Αθήνα</title>`` is ONE token — matching how the reference
    would tokenize the same bytes.
    """
    rng = np.random.default_rng(seed)
    latin = ["café", "naïve", "über", "résumé",
             "Zürich", "élève"]
    greek = ["Αθήνα", "λόγος"]
    cjk = ["東京", "中文", "日本語"]
    plain = _COMMON
    ents = ["&amp;", "&lt;", "&gt;", "&quot;", "&#945;"]
    parts, have = [], 0
    while have < n_bytes:
        page = ["<page>\n  <title>",
                str(rng.choice(plain)).capitalize(),
                "</title>\n  <revision id=\"",
                str(int(rng.integers(1e6, 1e8))), "\">\n    <text>"]
        for _ in range(int(rng.integers(40, 120))):
            r = rng.random()
            if r < 0.72:
                page.append(str(rng.choice(plain)))
            elif r < 0.82:
                page.append(str(rng.choice(latin + greek + cjk)))
            elif r < 0.88:
                page.append("[[" + str(rng.choice(plain)) + "|"
                            + str(rng.choice(plain)) + "]]")
            elif r < 0.93:
                page.append(str(rng.choice(ents)))
            elif r < 0.97:
                page.append("http://example.org/wiki/"
                            + "/".join(str(rng.choice(plain))
                                       for _ in range(int(rng.integers(2, 7)))))
            else:  # long separator-free attribute blob (force-split fodder)
                n = int(rng.integers(40, 400))
                page.append("style=\"" + "a" * n + "\"")
            page.append("\n" if rng.random() < 0.1 else " ")
        page.append("</text>\n  </revision>\n</page>\n")
        slab = "".join(page).encode("utf-8")
        parts.append(slab)
        have += len(slab)
    return b"".join(parts)[:n_bytes].rsplit(b" ", 1)[0] + b"\n"


def cpu_baseline_gbps(data: bytes, repeats: int = 1) -> float:
    from collections import Counter

    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        counts = Counter(data.split())
        dt = time.perf_counter() - t0
        best = min(best, dt)
        assert counts  # keep it honest
    return len(data) / 1e9 / best


def _log(msg: str, t0: float) -> None:
    """Phase progress to stderr (stdout stays the single JSON line)."""
    print(f"[bench +{time.perf_counter() - t0:6.1f}s] {msg}", file=sys.stderr)


def main() -> int:
    wall0 = time.perf_counter()

    mb = int(os.environ.get("BENCH_MB", "256"))
    chunk_mb = int(os.environ.get("BENCH_CHUNK_MB", "32"))
    superstep = int(os.environ.get("BENCH_SUPERSTEP", "0"))  # 0 = all chunks
    base_mb = int(os.environ.get("BENCH_BASELINE_MB", "16"))

    # BENCH_INPUT: bench a real corpus file (e.g. enwik8/enwik9 per
    # BASELINE.md) instead of synthetic text.  BENCH_CORPUS=natural selects
    # the English-text proxy (punctuated, cased, headed+tailed vocabulary)
    # over the default Zipf word soup.
    input_path = os.environ.get("BENCH_INPUT")
    corpus_kind = os.environ.get("BENCH_CORPUS", "zipf")
    if input_path:
        with open(input_path, "rb") as f:
            corpus = f.read(mb << 20)
        corpus_name = os.path.basename(input_path)
    elif corpus_kind == "natural":
        corpus = make_natural_corpus(mb << 20)
        corpus_name = "synthetic-natural"
    elif corpus_kind == "webby":
        corpus = make_webby_corpus(mb << 20)
        corpus_name = "synthetic-webby"
    elif corpus_kind == "markup":
        corpus = make_markup_corpus(mb << 20)
        corpus_name = "synthetic-markup"
    else:
        corpus = make_zipf_corpus(mb << 20)
        corpus_name = "synthetic-zipf"
    _log(f"corpus ready: {len(corpus) >> 20} MB ({corpus_name})", wall0)

    # CPU baseline BEFORE any device work: it is pure host numpy and it
    # makes vs_baseline available the moment the timed window lands (the
    # headline record must never wait on an optional post-phase).
    base = cpu_baseline_gbps(corpus[: base_mb << 20], repeats=3)
    _log(f"cpu baseline: {base:.4f} GB/s over {base_mb} MB", wall0)

    import jax

    from mapreduce_tpu.runtime import profiling

    # Persistent compile cache: repeated bench runs skip the multi-minute
    # first compile when shapes are unchanged.
    profiling.enable_compile_cache()

    from mapreduce_tpu.config import Config
    from mapreduce_tpu.data import reader
    from mapreduce_tpu.models.wordcount import WordCountJob
    from mapreduce_tpu.parallel.mapreduce import Engine
    from mapreduce_tpu.parallel.mesh import data_mesh

    # Capacities sized to the corpus: 50K-word Zipf vocab fits comfortably in
    # a 256K-slot table and 64K distinct-per-chunk batch extraction.
    # BENCH_SORT_MODE switches the aggregation sort strategy (sort3/stable2,
    # bit-identical results) and BENCH_MERGE_EVERY the table-merge cadence,
    # so live windows can A/B the sort floor and the merge amortization.
    # BENCH_COMBINER A/Bs the ISSUE 11 map-side combiner (hot-cache /
    # salt; pairs with BENCH_MAP_IMPL=fused).  BENCH_GEOMETRY A/Bs a
    # searched kernel-geometry set (ISSUE 12): a preset name, or a JSON
    # field dict for non-preset shortlist winners.
    geom_env = os.environ.get("BENCH_GEOMETRY") or None
    if geom_env and geom_env.lstrip().startswith("{"):
        geom_env = json.loads(geom_env)
    # BENCH_MERGE_STRATEGY=auto warm-starts from the static reduction
    # planner's freshest tuned.json profile (tools/redplan.py --out),
    # through the run-history warehouse's resolve_prior — the RESOLVED
    # strategy reaches the Engine, the streamed config and the run_start
    # stamp (never the literal 'auto'); no matching profile falls back
    # LOUDLY to tree.  The bench mesh is 1-D, so only single-axis
    # strategies are eligible — a hier-* winner planned over a 2-D fleet
    # mesh is skipped, not mis-run.
    merge_strategy = os.environ.get("BENCH_MERGE_STRATEGY", "tree")
    if merge_strategy == "auto":
        from mapreduce_tpu.config import MERGE_STRATEGIES
        from mapreduce_tpu.obs import history

        prior = history.resolve_prior(
            profile_path=os.environ.get("BENCH_MERGE_PROFILE", "tuned.json"),
            merge_allowed=tuple(s for s in MERGE_STRATEGIES
                                if not s.startswith("hier-")))
        merge_strategy = prior["merge_strategy"]
        _log("merge-strategy: auto -> " + merge_strategy
             + ("" if prior["merge_strategy_profile"]
                else " (no redplan profile; tree)"), wall0)
    merge_overlap = os.environ.get("BENCH_MERGE_OVERLAP", "0") == "1"
    cfg = Config(chunk_bytes=chunk_mb << 20, table_capacity=1 << 18,
                 batch_unique_capacity=1 << 16,
                 sort_mode=os.environ.get("BENCH_SORT_MODE",
                                          Config.sort_mode),
                 map_impl=os.environ.get("BENCH_MAP_IMPL",
                                         Config.map_impl),
                 combiner=os.environ.get("BENCH_COMBINER",
                                         Config.combiner),
                 geometry=geom_env,
                 merge_every=int(os.environ.get("BENCH_MERGE_EVERY", "1")),
                 merge_strategy=merge_strategy,
                 compact_slots=(int(os.environ["BENCH_COMPACT_SLOTS"])
                                if "BENCH_COMPACT_SLOTS" in os.environ
                                else None))
    mesh = data_mesh()
    n_dev = mesh.devices.size
    engine = Engine(WordCountJob(cfg), mesh, merge_strategy=merge_strategy)

    with tempfile.NamedTemporaryFile(suffix=".txt", delete=False) as f:
        f.write(corpus)
        path = f.name
    repeats = int(os.environ.get("BENCH_REPEATS", "8"))
    try:
        batches = list(reader.iter_batches(path, n_dev, cfg.chunk_bytes))
        # All full-size chunks stay device-resident; the timed dispatch
        # cycles them `repeats` times (see module docstring).
        if not batches:
            raise SystemExit("no full chunks: corpus smaller than one "
                             f"{chunk_mb} MB chunk; raise BENCH_MB or check "
                             "BENCH_INPUT, or lower BENCH_CHUNK_MB")
        k = max(1, min(superstep or len(batches), len(batches)))
        group = batches[:k]
        state = engine.init_states()

        # Stage the group once, timing the H2D transfer by itself (host-side
        # stacking stays outside the window), ended by a host fetch.
        stacked = np.stack([b.data for b in group], axis=1)
        t0 = time.perf_counter()
        staged = jax.device_put(stacked, engine.sharding)
        jax.block_until_ready(staged)
        np.asarray(staged[..., -1:])
        h2d_gbps = staged.nbytes / 1e9 / (time.perf_counter() - t0)
        _log(f"staged {staged.nbytes >> 20} MB on device "
             f"({h2d_gbps:.3f} GB/s H2D); k={k}, repeats={repeats}", wall0)

        # Warm-up: pays the XLA compiles (one for the (k, repeats) program,
        # one for finish -- finish does not donate, so the state stays valid).
        t_c0 = time.perf_counter()
        state = engine.step_many(state, staged, 0, repeats=repeats)
        # Generic ONE-ELEMENT host fetch: the state may be a bare CountTable
        # or (with BENCH_MERGE_EVERY > 1) a buffered pytree around one.
        np.asarray(jax.tree.leaves(state)[0].ravel()[:1])
        compile_s = time.perf_counter() - t_c0
        _log("warm-up dispatch done (compile paid)", wall0)
        np.asarray(engine.finish(state).dropped_count)
        _log("warm finish done", wall0)

        group_bytes = int(sum(b.lengths.sum() for b in group))
        t0 = time.perf_counter()
        state = engine.step_many(state, staged, k * repeats, repeats=repeats)
        table = engine.finish(state)
        np.asarray(table.dropped_count)  # barrier: fetch an existing leaf
        dt = time.perf_counter() - t0
        steady_bytes = group_bytes * repeats
        _log(f"timed window done: {dt:.3f}s over {steady_bytes >> 20} MB "
             f"({repeats} passes)", wall0)
        total_words = int(np.asarray(table.total_count()))
        processed_bytes = group_bytes * 2 * repeats  # warm-up + timed
        gbps = steady_bytes / 1e9 / dt
        words_per_s = total_words * (steady_bytes / processed_bytes) / dt

        result = {
            "metric": "zipf_wordcount_device_throughput",
            "input": corpus_name,
            "h2d_gbps": round(h2d_gbps, 4),
            "value": round(gbps, 4),
            "unit": "GB/s",
            "vs_baseline": round(gbps / base, 3) if base else 0.0,
            "corpus_mb": round(group_bytes / (1 << 20), 1),
            "devices": n_dev,
            "backend": jax.devices()[0].platform,
            "total_words": total_words,
            "cpu_baseline_gbps": round(base, 4),
            "words_per_s": round(words_per_s, 0),
        }
        # End-to-end STREAMED ingest (round-3 review #7): reader + prefetch +
        # H2D + compute + collective finish through the executor's run_job
        # path — the BASELINE.md "GB/s ingest" metric proper, where the
        # device-resident window above isolates device compute.  One full
        # pass over the corpus file; superstep amortizes dispatch latency
        # the same way production runs do.  BENCH_STREAMED=0 skips.
        streamed_gbps = None
        streamed_ledger = None
        streamed_metrics = None
        streamed_pipeline = None
        if os.environ.get("BENCH_STREAMED", "1") != "0":
            try:
                import dataclasses

                from mapreduce_tpu.runtime import executor

                # BENCH_INFLIGHT / BENCH_PREFETCH_DEPTH: the ISSUE 5
                # dispatch-window A/B knobs (1 = the serialized control).
                from mapreduce_tpu.config import Config as _Config

                s_cfg = dataclasses.replace(
                    cfg, superstep=int(os.environ.get(
                        "BENCH_STREAM_SUPERSTEP", "4")),
                    inflight_groups=int(os.environ.get(
                        "BENCH_INFLIGHT", str(_Config.inflight_groups))),
                    prefetch_depth=(
                        int(os.environ["BENCH_PREFETCH_DEPTH"])
                        if os.environ.get("BENCH_PREFETCH_DEPTH") else None),
                    # BENCH_MERGE_OVERLAP=1: window-boundary partial
                    # collectives on the streamed pass (ISSUE 20 leg 2;
                    # bit-identical results).
                    merge_overlap=merge_overlap)
                # Warm-up: a short-range run pays the XLA compiles for the
                # streamed shapes (the persistent compile cache makes the
                # timed run's identical programs cache hits), so the timed
                # window measures ingest, not compilation.
                warm_hi = min(len(corpus),
                              n_dev * s_cfg.chunk_bytes
                              * (s_cfg.superstep + 1))
                executor.run_job(WordCountJob(s_cfg), path, config=s_cfg,
                                 mesh=mesh, byte_range=(0, warm_hi))
                _log("streamed warm-up done (compile paid)", wall0)
                # Telemetry on the TIMED pass only: the run ledger (one
                # JSONL record per step: phase deltas, bytes, device mem,
                # compile events) makes a bench row attributable after the
                # fact — summarize with tools/obs_report.py.  BENCH_LEDGER
                # overrides the path.
                from mapreduce_tpu import obs

                ledger_path = os.environ.get("BENCH_LEDGER") or os.path.join(
                    tempfile.gettempdir(), f"bench_ledger.{os.getpid()}.jsonl")
                tel = obs.Telemetry.create(ledger_path=ledger_path)
                # The registry is process-global and already holds the
                # headline + warm-up activity; snapshot here so the
                # reported metrics are the DELTA over the timed pass only.
                snap_before = obs.get_registry().snapshot()
                t0 = time.perf_counter()
                try:
                    rr = executor.run_job(WordCountJob(s_cfg), path,
                                          config=s_cfg, mesh=mesh,
                                          telemetry=tel)
                finally:
                    tel.close()
                np.asarray(jax.tree.leaves(rr.value)[0].ravel()[:1])
                s_dt = time.perf_counter() - t0
                streamed_gbps = rr.metrics.bytes_processed / 1e9 / s_dt
                # Decomposition (round-4 review next #2): where the streamed
                # seconds actually went — read_wait (reader behind),
                # stage (host assembly + H2D placement), dispatch
                # (program enqueue; large = device queue full =
                # compute-bound), drain (queued compute at stream end).
                streamed_phases = {k: round(v, 3)
                                   for k, v in rr.metrics.phases.items()}
                streamed_ledger = ledger_path
                streamed_metrics = _metrics_delta(
                    snap_before, obs.get_registry().snapshot())
                streamed_pipeline = rr.pipeline
                _log(f"streamed ingest pass done: {s_dt:.3f}s over "
                     f"{rr.metrics.bytes_processed >> 20} MB "
                     f"({streamed_gbps:.4f} GB/s end-to-end); "
                     f"phases={streamed_phases}; pipeline={rr.pipeline}; "
                     f"ledger={ledger_path}", wall0)
            except Exception as e:  # noqa: BLE001 — headline must survive
                _log(f"streamed phase failed ({e!r}); keeping headline", wall0)
    finally:
        os.unlink(path)

    # Static cost pricing of the benched map path (ISSUE 6): predicted HBM
    # passes next to the measured GB/s, so the fused-vs-split A/B rows
    # carry the prediction and the measurement in one JSON.
    result["map_impl"] = cfg.map_impl
    result["combiner"] = cfg.resolved_combiner
    # The reduction placement next to the measurement (ISSUE 20): the
    # RESOLVED strategy (never 'auto') + whether the streamed pass
    # overlapped its partial collectives with the map stream.
    result["merge_strategy"] = merge_strategy
    if merge_overlap:
        result["merge_overlap"] = True
    cost = _cost_record(cfg.map_impl, cfg.resolved_combiner)
    if cost is not None:
        result["cost"] = cost
    if streamed_gbps is not None:
        result["streamed_ingest_gbps"] = round(streamed_gbps, 4)
        result["streamed_phases"] = streamed_phases
        ratio = _streamed_ratio(result)
        if ratio is not None:
            result["streamed_vs_h2d_ratio"] = ratio
            time_ratio = _time_ratio(ratio)
            if time_ratio is not None:
                result["streamed_vs_h2d_time_ratio"] = time_ratio
        if streamed_pipeline is not None:
            # Window forensics for the A/B rows: configured/observed
            # in-flight depth and the overlap fraction (1 - blocked/stream).
            result["streamed_overlap_fraction"] = \
                streamed_pipeline.get("overlap_fraction")
            result["streamed_pipeline"] = {
                k: streamed_pipeline.get(k)
                for k in ("inflight_groups", "prefetch_depth", "depth_mean",
                          "depth_max", "window_filled", "full_frac")}
        if streamed_ledger:
            result["ledger"] = streamed_ledger
            # Timeline forensics (ISSUE 7): reconstruct the timed pass's
            # per-group lifecycle into the critical-path `bottleneck`
            # verdict and export a Perfetto-viewable trace NEXT TO the
            # ledger — the queued pipeline A/B rows land with measured
            # timelines attached, not just two scalar ratios.  Advisory:
            # post-processing of records already on disk, so any failure
            # is logged and skipped (the measured row must survive).
            # BENCH_TRACE=0 skips (harness knob, measurement-neutral).
            if os.environ.get("BENCH_TRACE", "1") != "0":
                try:
                    from mapreduce_tpu.obs import timeline as tl_mod

                    recs = list(obs.read_ledger(streamed_ledger))
                    art = tl_mod.reconstruct(recs)
                    if art is not None:
                        result["bottleneck"] = art["bottleneck"]
                        trace_path = streamed_ledger + ".trace.json"
                        with open(trace_path, "w") as tf:
                            json.dump(tl_mod.to_chrome_trace(recs), tf)
                        result["trace"] = trace_path
                        _log("trace exported: "
                             f"{trace_path} (bottleneck="
                             f"{art['bottleneck']['resource']}, device idle "
                             f"{art['device_idle']['total_s']:.3f}s)", wall0)
                except Exception as e:  # noqa: BLE001 — advisory only
                    print(f"[bench] trace export skipped ({e!r})",
                          file=sys.stderr)
            # Data-plane summary (ISSUE 8): the timed pass's `data` record
            # + its health classification ride BENCH JSON, so the A/B rows
            # carry the corpus-and-backend shape signals (skew, spill
            # fallbacks, window occupancy) the autotuner needs next to
            # the bottleneck verdict.  Advisory.
            try:
                from mapreduce_tpu.obs import datahealth as dh_mod

                recs = list(obs.read_ledger(streamed_ledger))
                # Keyed to THIS pass's run_id: BENCH_LEDGER may point at an
                # appended multi-run file, and the summary must describe the
                # timed pass, not whichever run landed first.
                data_rec = dh_mod.data_record(recs, run_id=tel.run_id)
                if data_rec is not None:
                    result["data"] = {
                        k: v for k, v in data_rec.items()
                        if k not in ("ts", "run_id", "kind")}
                    health = dh_mod.classify(result["data"])
                    result["data_health"] = health
                    _log("data health: "
                         f"{health['verdict']} (top_mass="
                         f"{health['signals'].get('top_mass')}, "
                         "fallback_frac="
                         f"{health['signals'].get('fallback_frac')})",
                         wall0)
            except Exception as e:  # noqa: BLE001 — advisory only
                print(f"[bench] data summary skipped ({e!r})",
                      file=sys.stderr)
            # Run-history registration (ISSUE 14 satellite BUGFIX): an
            # append-mode BENCH_LEDGER accumulates many timed passes but
            # nothing ever ingested them — every pass now lands in the
            # run-history warehouse (BENCH_HISTORY overrides the index
            # dir, 0 disables; default next to the ledger), and the row
            # carries this pass's config key + the key group's drift
            # verdict (regressing/improving/steady/config-drift).
            # Harness-neutral and advisory: post-processing of records
            # already on disk, never measurement-altering.
            hist_env = os.environ.get("BENCH_HISTORY", "")
            if hist_env != "0":
                try:
                    from mapreduce_tpu.obs import history as history_mod

                    hdir = hist_env or (streamed_ledger + ".history")
                    index = history_mod.ingest([streamed_ledger], hdir)
                    mine = [r for r in index["runs"].values()
                            if r.get("run_id") == tel.run_id]
                    row = max(mine, key=lambda r: r.get("instance") or 0) \
                        if mine else None
                    drift = history_mod.classify_drift(
                        history_mod.group_rows(index, row["group"])) \
                        if row else None
                    result["history"] = {
                        "index": history_mod.index_path(hdir),
                        "runs": len(index.get("runs", {})),
                        "key": row.get("key") if row else None,
                        "drift": (drift or {}).get("verdict"),
                    }
                    _log("history: registered run under "
                         f"{result['history']['key']} "
                         f"({result['history']['runs']} runs indexed, "
                         f"drift={result['history']['drift']})", wall0)
                except Exception as e:  # noqa: BLE001 — advisory only
                    print(f"[bench] history registration skipped ({e!r})",
                          file=sys.stderr)
        # Registry DELTA over the timed streamed pass (the registry is
        # process-global, so an absolute snapshot would fold in the
        # headline + warm-up activity): steps/dispatches/prefetches and
        # where the seconds pooled, machine-readable per round.
        if streamed_metrics is not None:
            result["metrics"] = streamed_metrics
    print(json.dumps(result))
    return 0


def _streamed_ratio(result: dict) -> float | None:
    """Streamed GB/s over the SAME-RUN H2D floor: how close end-to-end
    ingest runs to the host->device link.  None when either leg is
    missing/zero."""
    streamed = result.get("streamed_ingest_gbps")
    h2d = result.get("h2d_gbps")
    if not streamed or not h2d:
        return None
    return round(streamed / h2d, 4)


def _time_ratio(ratio: float | None) -> float | None:
    """The same evidence in time form (ISSUE 5's falsifiable target):
    streamed wall-clock over the same-run H2D floor, lower is better,
    1.0 = ingest at the link floor.  A near-hung streamed pass can round
    the GB/s ratio all the way to 0.0 — the time form is then
    unrepresentable, not infinite: return None rather than crash the
    headline result this late (this is the run most worth keeping)."""
    if not ratio:
        return None
    return round(1.0 / ratio, 4)


def _cost_record(map_impl: str, combiner: str = "off") -> dict | None:
    """Static hbm-cost pricing of the benched map path (ISSUE 6/11): run
    the analysis cost pass over the registry twin of the benched config
    (wordcount_combiner when the hot-key combiner is on, wordcount_fused
    when BENCH_MAP_IMPL=fused, else wordcount_pallas) and surface
    `effective_input_passes` — plus the fused-vs-split / combiner-vs-off
    gap the pass certifies — in BENCH JSON.  Pure tracing, no device
    work; any failure is logged and skipped (the measured row must
    survive)."""
    try:
        from mapreduce_tpu import analysis, models
        from mapreduce_tpu.analysis.passes.cost import CostPass

        if combiner == "hot-cache" and map_impl == "fused":
            name = "wordcount_combiner"
        elif map_impl == "fused":
            name = "wordcount_fused"
        else:
            name = "wordcount_pallas"
        rep = analysis.analyze_job(models.build_model(name), name,
                                   passes=[CostPass()])
        art = rep.artifacts.get(name, {}).get("cost")
        if not art:
            return None
        rec = {"model": name,
               "effective_input_passes": art.get("effective_input_passes")}
        if "fused_vs_split" in art:
            rec["fused_vs_split"] = art["fused_vs_split"]
        if "combiner_vs_off" in art:
            rec["combiner_vs_off"] = art["combiner_vs_off"]
        return rec
    except Exception as e:  # noqa: BLE001 — advisory, never fatal
        print(f"[bench] cost artifact skipped ({e!r})", file=sys.stderr)
        return None


def _metrics_delta(before: dict, after: dict) -> dict:
    """Counter and histogram count/sum deltas between two registry
    snapshots (gauges are last-write-wins and pass through).  Histogram
    min/max are window-less and deliberately dropped."""
    b_c = before.get("counters", {})
    counters = {k: round(v - b_c.get(k, 0), 6)
                for k, v in after.get("counters", {}).items()
                if v != b_c.get(k, 0)}
    b_h = before.get("histograms", {})
    hists = {}
    for k, h in after.get("histograms", {}).items():
        prev = b_h.get(k, {"count": 0, "sum": 0.0})
        if h["count"] != prev["count"]:
            hists[k] = {"count": h["count"] - prev["count"],
                        "sum": round(h["sum"] - prev["sum"], 6)}
    return {"counters": counters, "gauges": after.get("gauges", {}),
            "histograms": hists}


if __name__ == "__main__":
    sys.exit(main())
