"""Skew-adaptive map-side combiner (ISSUE 11).

Fast tier: kernel-level exactness of the hot-key cache against the XLA
oracle (occurrence multiset + first occurrences + eviction accounting),
the salt round-trip at the table level, the cache-flush table fold, the
'auto' resolver, config validation, and the autotuner's enable-combiner
rule.  @slow (the >=10 s line): end-to-end wordcount/ngram bit-identity
across Zipf / uniform / single-key corpora in pallas interpret mode, the
dense-corpus spill fallback, and the streamed telemetered run whose
`data` record carries the combiner counters.
"""

from __future__ import annotations

import functools
import json
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mapreduce_tpu import constants
from mapreduce_tpu.config import COMBINER_SALT_BITS, Config
from mapreduce_tpu.obs import datahealth
from mapreduce_tpu.ops import table as table_ops
from mapreduce_tpu.ops import tokenize as tok_ops
from mapreduce_tpu.tuning import engine as tuning_engine

SENT = int(constants.SENTINEL_KEY)
N = 128 * 132  # smallest-ish fused chunk: seg_len 132 >= 2W+2


def _corpus(kind: str, n: int = N) -> bytes:
    rng = np.random.default_rng(7)
    words = [b"aa", b"bb", b"c", b"ddd", b"ee", b"f", b"gg", b"hh",
             b"iii", b"jj", b"kk", b"lll", b"mm", b"n", b"oo", b"pp"]
    if kind == "zipf":
        p = np.array([1 / (i + 1) ** 1.3 for i in range(len(words))])
        toks = rng.choice(len(words), 3000, p=p / p.sum())
    elif kind == "uniform":
        toks = rng.integers(0, len(words), 3000)
    elif kind == "single":
        toks = np.zeros(3000, np.int64)
    else:
        raise ValueError(kind)
    data = b" ".join(words[t] for t in toks)
    return (data + b" " * n)[:n]


@functools.lru_cache(maxsize=None)
def _combined(data: bytes):
    """One jitted combiner-kernel pass (cached so every test shares the
    single ~7 s interpret-mode compile)."""
    from mapreduce_tpu.ops.pallas import tokenize as pallas_tok

    @jax.jit
    def run(arr):
        return pallas_tok.tokenize_fused(
            arr, compact_slots=128, lane_major=True, block_rows=512,
            combiner_slots=8)

    stream, overlong, spill, cache = run(
        jnp.asarray(np.frombuffer(data, np.uint8)))
    return (jax.tree.map(np.asarray, stream), int(overlong), int(spill),
            jax.tree.map(np.asarray, cache))


def _occurrences(stream) -> Counter:
    m = np.asarray(stream.count) > 0
    return Counter(zip(np.asarray(stream.key_hi)[m].tolist(),
                       np.asarray(stream.key_lo)[m].tolist()))


def _first_pos(stream) -> dict:
    m = np.asarray(stream.count) > 0
    out: dict = {}
    for k, l, p in zip(np.asarray(stream.key_hi)[m].tolist(),
                       np.asarray(stream.key_lo)[m].tolist(),
                       np.asarray(stream.pos)[m].tolist()):
        out[(k, l)] = min(out.get((k, l), 1 << 40), p)
    return out


@pytest.mark.smoke
def test_hot_cache_kernel_matches_xla_oracle():
    """The exactness core: stream + flushed cache together hold exactly
    the XLA oracle's occurrence multiset, and per-key first occurrences
    are preserved (the cache records each entry's first in-lane
    occurrence; the global min survives the fold)."""
    data = _corpus("zipf")
    stream, overlong, spill, cache = _combined(data)
    assert spill == 0 and overlong == 0
    oracle = tok_ops.tokenize(jnp.asarray(np.frombuffer(data, np.uint8)))
    want = _occurrences(oracle)
    got = _occurrences(stream)
    ck = cache.key_hi.ravel().tolist()
    cl = cache.key_lo.ravel().tolist()
    cc = cache.count.ravel().tolist()
    cp = cache.packed.ravel().tolist()
    for k, l, c in zip(ck, cl, cc):
        if c:
            got[(k, l)] += c
    assert got == want
    # The cache absorbed the dominant mass on a Zipf stream: most
    # occurrences never reach the sort.
    hits = sum(c for c in cc if c)
    assert hits > 0.8 * sum(want.values()), hits
    # First occurrences: min over (stream, cache) positions == oracle's.
    first = _first_pos(stream)
    for k, l, c, p in zip(ck, cl, cc, cp):
        if c:
            key = (k, l)
            first[key] = min(first.get(key, 1 << 40), p >> 6)
    assert first == _first_pos(oracle)


def test_hot_cache_eviction_accounting():
    """Every resident entry is evicted at the flush; count-1 entries are
    the cold ones (slots that bought nothing).  The fixture's long tail
    guarantees some, and exactness never depends on which keys went
    cold (the oracle-parity test above shares this cache)."""
    _, _, _, cache = _combined(_corpus("zipf"))
    cc = cache.count.ravel()
    flushes = int((cc > 0).sum())
    evicted = int((cc == 1).sum())
    assert flushes > 0 and 0 < evicted < flushes
    # Rows deleted from the sort input = hits - flush rows re-emitted.
    assert int(cc.sum()) - flushes > 0


def test_combiner_table_fold_is_exact():
    """merge(build(thinned stream), cache table) == build(oracle stream):
    the fold the fused map path runs, checked key-for-key at the table
    level (counts, first occurrence, dropped accounting)."""
    from mapreduce_tpu.models.wordcount import _combiner_table

    data = _corpus("zipf")
    stream, _, _, cache = _combined(data)
    cap = 512
    thin = table_ops.from_stream(
        jax.tree.map(jnp.asarray, stream), cap, pos_hi=0,
        max_token_bytes=32, max_pos=N, sort_mode="stable2")
    cache_tbl = _combiner_table(jax.tree.map(jnp.asarray, cache), 0)
    merged = table_ops.merge(thin, cache_tbl, capacity=cap)
    oracle = tok_ops.tokenize(jnp.asarray(np.frombuffer(data, np.uint8)))
    want = table_ops.from_stream(oracle, cap, pos_hi=0)
    for f in ("key_hi", "key_lo", "count", "count_hi", "pos_hi", "pos_lo",
              "length"):
        np.testing.assert_array_equal(np.asarray(getattr(merged, f)),
                                      np.asarray(getattr(want, f)), f)
    assert int(merged.dropped_count) == int(want.dropped_count)


def test_salt_round_trip_bit_identical():
    """from_packed_rows(salt_bits) == from_packed_rows() on packed rows
    with duplicate hot keys, poison rows, and dead filler — the de-salt
    re-reduce recovers exact counts and minimum first occurrences, and
    the poison-segment rescue extraction is untouched."""
    rng = np.random.default_rng(3)
    n = 4096
    # Keys separated by more than the 2**COMBINER_SALT_BITS XOR envelope
    # (adjacent key_lo values under one key_hi would legitimately
    # coalesce — that is the documented salt collision envelope, not a
    # round-trip bug).
    keys = [(0x1234, 0x9900), (0x1234, 0xA200), (SENT, SENT - 0x40),
            (7, 0x800), (9, 0x1000)]
    khi = np.full(n, SENT, np.uint32)
    klo = np.full(n, SENT, np.uint32)
    packed = np.full(n, 0xFFFFFFFF, np.uint32)
    live = 3000
    pick = rng.integers(0, len(keys), live)
    pick[:2000] = 0  # one scorching key — the salt scenario
    pos = np.sort(rng.choice(1 << 20, live, replace=False))
    for i in range(live):
        khi[i], klo[i] = keys[pick[i]]
        packed[i] = (pos[i] << 6) | 3
    # Two poison rows (reserved key, zero length bits).
    khi[live:live + 2] = SENT
    klo[live:live + 2] = SENT - 1
    packed[live] = (123 << 6)
    packed[live + 1] = (456 << 6)
    args = (jnp.asarray(khi), jnp.asarray(klo), jnp.asarray(packed),
            jnp.uint32(live), 256, 0)
    for mode in ("stable2", "sort3"):
        plain, resc_p = table_ops.from_packed_rows(
            *args, sort_mode=mode, rescue_slots=4)
        salted, resc_s = table_ops.from_packed_rows(
            *args, sort_mode=mode, rescue_slots=4,
            salt_bits=COMBINER_SALT_BITS)
        for f in plain._fields:
            np.testing.assert_array_equal(np.asarray(getattr(plain, f)),
                                          np.asarray(getattr(salted, f)),
                                          f"{mode}:{f}")
        np.testing.assert_array_equal(np.asarray(resc_p), np.asarray(resc_s))


def test_salt_refusals():
    with pytest.raises(ValueError, match="salt_bits"):
        table_ops.from_packed_rows(
            jnp.zeros(8, jnp.uint32), jnp.zeros(8, jnp.uint32),
            jnp.zeros(8, jnp.uint32), jnp.uint32(0), 4, 0,
            salt_bits=7)  # past the 6 low position bits a row can spare
    with pytest.raises(ValueError, match="salt_bits"):
        table_ops.from_stream(
            tok_ops.TokenStream(*[jnp.zeros(8, jnp.uint32)] * 5), 4,
            salt_bits=2)  # generic build has no salt


@pytest.mark.smoke
def test_auto_resolution_from_ledger():
    """Config(combiner='auto') acceptance: a skew-hot ledger flips the
    combiner on, a clean one (and no history) stays off, and an
    append-mode ledger resolves from the LATEST data record."""
    skew = {"kind": "data", "run_id": "a", "tokens": 60000,
            "top_count": 12000, "chunks": 4, "capacity": 1 << 16,
            "table_valid": 900}
    clean = {"kind": "data", "run_id": "b", "tokens": 60000,
             "top_count": 24, "chunks": 4, "capacity": 1 << 16,
             "table_valid": 900}
    assert datahealth.resolve_combiner([skew]) == "hot-cache"
    assert datahealth.resolve_combiner([clean]) == "off"
    assert datahealth.resolve_combiner([]) == "off"
    assert datahealth.resolve_combiner([clean, skew]) == "hot-cache"
    assert datahealth.resolve_combiner([skew, clean]) == "off"
    # An unresolved 'auto' traces as 'off' (library callers that never
    # resolve get the shipped behavior, not a surprise cache).
    cfg = Config(combiner="auto")
    assert cfg.resolved_combiner == "off"
    assert cfg.resolved_combiner_slots == 0


def test_config_surface():
    with pytest.raises(ValueError, match="combiner"):
        Config(combiner="always")
    # Salting rides both sort modes: each keeps a segment's rows in
    # position order, which the de-salt reads.
    for mode in ("sort3", "stable2"):
        assert Config(combiner="salt", sort_mode=mode).resolved_salt_bits \
            == 3
    with pytest.raises(ValueError, match="combiner_slots"):
        Config(combiner="hot-cache", combiner_slots=12)
    with pytest.raises(ValueError, match="combiner_slots"):
        Config(combiner_slots=8)  # sizing a cache that is off
    base = dict(backend="pallas", map_impl="fused", chunk_bytes=1 << 15)
    on = Config(**base, combiner="hot-cache")
    assert on.resolved_combiner_slots == 8
    assert on.resolved_block_rows == 512
    off = Config(**base)
    assert off.resolved_combiner_slots == 0
    assert off.resolved_block_rows == 384
    # The cache only exists on the fused compact path: split mode (and
    # the xla backend) resolve to no cache — and keep the 384 geometry.
    split = Config(backend="pallas", combiner="hot-cache",
                   chunk_bytes=1 << 15)
    assert split.resolved_combiner_slots == 0
    assert split.resolved_block_rows == 384
    assert Config(combiner="salt").resolved_salt_bits == COMBINER_SALT_BITS
    assert Config().resolved_salt_bits == 0


def test_kernel_combiner_validation():
    from mapreduce_tpu.ops.pallas import tokenize as pallas_tok

    arr = jnp.zeros(N, jnp.uint8)
    with pytest.raises(ValueError, match="combiner_slots"):
        pallas_tok.tokenize_fused(arr, combiner_slots=8)  # pair mode
    with pytest.raises(ValueError, match="combiner_slots"):
        pallas_tok.tokenize_fused(arr, compact_slots=128, lane_major=True,
                                  combiner_slots=12)
    with pytest.raises(ValueError, match="base_offset"):
        # The cache flush records in-chunk positions; offsetting the
        # stream but not the cache would skew cached first occurrences.
        pallas_tok.tokenize_fused(arr, compact_slots=128, lane_major=True,
                                  combiner_slots=8, base_offset=128)


@pytest.mark.smoke
def test_tuner_enable_combiner_rule():
    """The skew-hot -> enable-combiner row: fires exactly when the data
    verdict is skew-hot and the combiner is off; an already-on run notes
    the fact in the trail and falls through."""
    skew_data = {"kind": "data", "run_id": "r", "tokens": 60000,
                 "top_count": 12000, "chunks": 4, "capacity": 1 << 16,
                 "table_valid": 900}
    start = {"kind": "run_start", "run_id": "r", "chunk_bytes": 1 << 21,
             "superstep": 1, "combiner": "off"}
    end = {"kind": "run_end", "run_id": "r", "bytes": 1 << 23,
           "elapsed_s": 1.0,
           "phases": {"read_wait": 0.1, "dispatch": 0.8}}
    p = tuning_engine.propose([start, skew_data, end])
    assert p["rule"] == "enable-combiner"
    assert p["changed"] == {"combiner": ["off", "hot-cache"]}
    tuning_engine.validate_knobs(p["proposal"])
    # Already on: the rule is considered, does not fire, and the trail
    # records why; no pipeline knob chases the (already answered) skew.
    start_on = dict(start, combiner="hot-cache")
    p2 = tuning_engine.propose([start_on, skew_data, end])
    assert p2["rule"] != "enable-combiner"
    noted = [t for t in p2["trail"] if t["rule"] == "enable-combiner"]
    assert noted and not noted[-1]["fired"]
    assert "already" in noted[-1]["why"]


def test_cli_combiner_auto_resolves_from_prior_ledger(tmp_path, capsys):
    """CLI acceptance: --combiner auto + a --ledger whose history says
    skew-hot resolves to hot-cache and stamps the RESOLVED mode into the
    new run's own records (xla backend: the cache is a no-op there, but
    the resolution contract is backend-independent)."""
    from mapreduce_tpu import cli

    corpus = tmp_path / "c.txt"
    corpus.write_bytes(b"hot hot hot cold\n" * 40)
    led = tmp_path / "run.jsonl"
    led.write_text(json.dumps(
        {"ts": 1.0, "run_id": "prev", "kind": "data", "tokens": 60000,
         "top_count": 12000, "chunks": 4, "capacity": 1 << 16,
         "table_valid": 900}) + "\n")
    rc = cli.main([str(corpus), "--combiner", "auto", "--ledger", str(led),
                   "--format", "json", "--no-echo", "--backend", "xla"])
    assert rc == 0
    assert "combiner: auto -> hot-cache" in capsys.readouterr().err
    recs = [json.loads(ln) for ln in led.read_text().splitlines()]
    start = [r for r in recs if r.get("kind") == "run_start"
             and r.get("run_id") != "prev"]
    assert start and start[0]["combiner"] == "hot-cache"
    data = [r for r in recs if r.get("kind") == "data"
            and r.get("run_id") == start[0]["run_id"]]
    assert data and data[0]["combiner"] == "hot-cache"
    # No history: resolves off, and says so.
    led2 = tmp_path / "fresh.jsonl"
    rc = cli.main([str(corpus), "--combiner", "auto", "--ledger",
                   str(led2), "--format", "json", "--no-echo",
                   "--backend", "xla"])
    assert rc == 0
    assert "combiner: auto -> off" in capsys.readouterr().err


# -- end-to-end parity (pallas interpret: >=10 s each -> @slow) --------------


def _parity_configs():
    base = dict(chunk_bytes=1 << 15, table_capacity=1 << 10,
                backend="pallas", map_impl="fused")
    return (Config(**base), Config(**base, combiner="hot-cache"),
            Config(**base, combiner="salt"))


@pytest.mark.slow
@pytest.mark.parametrize("kind", ["zipf", "uniform", "single"])
def test_wordcount_bit_identity(kind):
    """Acceptance: combiner-on (hot-cache AND salt) output is
    bit-identical to combiner-off on every distribution."""
    from mapreduce_tpu.models import wordcount

    data = _corpus(kind, 1 << 15)
    off, on, salt = (wordcount.count_words(data, c)
                     for c in _parity_configs())
    assert off == on == salt


@pytest.mark.slow
def test_ngram_bit_identity():
    """Gram family: 'salt' rides the packed gram build, 'hot-cache' is a
    documented no-op (position-ordered consumers cannot delete rows) —
    either way, bit-identical."""
    from mapreduce_tpu.models import wordcount

    data = _corpus("zipf", 1 << 15)
    off, on, salt = (wordcount.count_ngrams(data, 2, c)
                     for c in _parity_configs())
    assert off == on == salt


@pytest.mark.slow
def test_dense_corpus_spill_fallback_stays_exact():
    """Adversarial density (single-letter tokens) overflows the taller
    combiner windows: the chunk must fall back to the combiner-free pair
    path and stay exact — and the stats counters must report the
    fallback with zeroed combiner counters."""
    from mapreduce_tpu.models import wordcount
    from mapreduce_tpu.models.wordcount import _map_stream

    # 64 distinct single-byte tokens at density 0.5 over a 64 KiB chunk
    # (512-byte lane segments = one FULL 512-row combiner window): the
    # cache holds only 8 of the 64 per lane, so ~7/8 of ~256 ends per
    # window stay live — far past the 128-slot budget.  (Fewer distinct
    # words than cache slots would NOT spill: the cache absorbs the whole
    # stream, which is the point of the combiner, not a fallback
    # scenario; and a chunk smaller than 128*512 bytes leaves the tall
    # window mostly padding.)
    alphabet = bytes(range(0x21, 0x61))
    data = (b" ".join(bytes([b]) for b in alphabet) + b" ") * 600
    data = data[: 1 << 16]
    base = dict(chunk_bytes=1 << 16, table_capacity=1 << 10,
                backend="pallas", map_impl="fused")
    off = Config(**base)
    on = Config(**base, combiner="hot-cache")
    assert wordcount.count_words(data, off) == \
        wordcount.count_words(data, on)
    chunk = jnp.asarray(np.frombuffer(data, np.uint8))
    (_, stats) = jax.jit(
        lambda c: _map_stream(c, on, 1 << 10, with_stats=True))(chunk)
    assert int(stats.fallback_chunks) == 1
    assert int(stats.spill_rows) > 0
    assert int(stats.combiner_hits) == 0
    assert int(stats.combiner_flushes) == 0


@pytest.mark.slow
def test_stats_counters_land_in_data_record(tmp_path):
    """Streamed telemetered combiner run: the kernel counters ride the
    completion token into the per-run `data` record (combiner mode, hits
    / flushes / evicted, hit rate), and the result is byte-identical to
    the combiner-off streamed run."""
    from mapreduce_tpu import obs
    from mapreduce_tpu.models.wordcount import WordCountJob
    from mapreduce_tpu.runtime import executor

    data = _corpus("zipf", 1 << 15) * 4
    path = tmp_path / "corpus.txt"
    path.write_bytes(data)
    off, on, _ = _parity_configs()
    results = {}
    for name, cfg in (("off", off), ("on", on)):
        led = tmp_path / f"{name}.jsonl"
        tel = obs.Telemetry.create(ledger_path=str(led))
        rr = executor.run_job(WordCountJob(cfg), str(path), config=cfg,
                              telemetry=tel)
        tel.close()
        results[name] = jax.tree.map(np.asarray, rr.value)
        recs = list(obs.read_ledger(str(led)))
        data_rec = next(r for r in recs if r["kind"] == "data")
        assert data_rec["combiner"] == \
            ("hot-cache" if name == "on" else "off")
        if name == "on":
            assert data_rec["combiner_hits"] > 0
            assert data_rec["combiner_flushes"] > 0
            assert data_rec["combiner_hit_rate"] == pytest.approx(
                data_rec["combiner_hits"] / data_rec["tokens"], abs=1e-6)
            assert data_rec["combiner_rows_deleted"] == \
                data_rec["combiner_hits"] - data_rec["combiner_flushes"]
            start = next(r for r in recs if r["kind"] == "run_start")
            assert start["combiner"] == "hot-cache"
        else:
            assert data_rec["combiner_hits"] == 0
    for a, b in zip(jax.tree.leaves(results["off"]),
                    jax.tree.leaves(results["on"])):
        np.testing.assert_array_equal(a, b)
