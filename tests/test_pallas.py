"""Pallas tokenize kernel vs. the XLA scan oracle (interpret mode on CPU).

SURVEY §4: kernel-level tests compare Pallas output to the pure-JAX oracle
under ``interpret=True``.  Tables built from either backend must be
field-for-field identical (same hashes, counts, first-occurrence positions)
for every token within the W-byte envelope; overlong tokens must be dropped
into exact ``dropped_*`` accounting.
"""

import numpy as np
import pytest

from mapreduce_tpu.config import Config
from mapreduce_tpu.models import wordcount
from mapreduce_tpu.ops import table as tbl
from mapreduce_tpu.ops import tokenize as tok
from mapreduce_tpu.ops.pallas import tokenize as ptok
from mapreduce_tpu.utils import oracle
from tests.conftest import make_corpus

W = 8  # small lookback so tests exercise the overlong path cheaply
CAP = 4096


def _pad(data: bytes, w: int = W) -> np.ndarray:
    n = max(128 * (2 * w + 2), -(-len(data) // 128) * 128)
    return tok.pad_to(data, n)


def _tables(data: bytes, w: int = W, block_rows: int = 64):
    buf = _pad(data, w)
    stream_x = tok.tokenize(buf)
    want = tbl.from_stream(stream_x, CAP)
    stream_p, overlong = ptok.tokenize(buf, max_token_bytes=w,
                                       block_rows=block_rows, interpret=True)
    got = tbl.from_stream(stream_p, CAP)
    return want, got, int(overlong)


def _assert_tables_equal(want, got):
    for f in want._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(want, f)), np.asarray(getattr(got, f)), err_msg=f)


@pytest.mark.smoke
def test_fixture_exact(fixture_text):
    want, got, overlong = _tables(fixture_text)
    assert overlong == 0
    _assert_tables_equal(want, got)


def test_random_corpus_exact(rng):
    corpus = make_corpus(rng, n_words=3000, vocab=200)  # words well under W
    want, got, overlong = _tables(corpus)
    assert overlong == 0
    _assert_tables_equal(want, got)


def test_tokens_at_exact_w_boundary():
    # length W is on the fast path; W+1 is overlong.
    data = (b"x" * W + b" " + b"y" * W + b"\n") * 40
    want, got, overlong = _tables(data)
    assert overlong == 0
    _assert_tables_equal(want, got)


def test_overlong_tokens_dropped_and_counted():
    data = b"short " * 50 + b"z" * (W + 1) + b" tail " + b"q" * (3 * W) + b"\n"
    buf = _pad(data)
    stream_p, overlong = ptok.tokenize(buf, max_token_bytes=W,
                                       block_rows=64, interpret=True)
    got = tbl.from_stream(stream_p, CAP)
    assert int(overlong) == 2  # the two overlong runs, once each
    # Every short token still counted exactly.
    counts = oracle.word_counts(data)
    short_total = sum(c for word, c in counts.items() if len(word) <= W)
    assert int(got.total_count()) == short_total
    assert int(got.n_valid()) == len([w for w in counts if len(w) <= W])


def test_lane_seam_tokens(rng):
    """Tokens placed to straddle the 128-lane segment seams exactly."""
    w = 8
    n = 128 * (2 * w + 2)  # minimum size: every seam is close to its neighbors
    seg = n // 128
    buf = np.full(n, 0x20, dtype=np.uint8)
    # A word crossing every seam j*seg for j=1..127, plus chunk start/end.
    for j in range(1, 128):
        s = j * seg - 3
        buf[s:s + 6] = np.frombuffer(b"abcdef", dtype=np.uint8)
    buf[:4] = np.frombuffer(b"head", dtype=np.uint8)
    buf[-4:] = np.frombuffer(b"tail", dtype=np.uint8)
    want = tbl.from_stream(tok.tokenize(buf), CAP)
    stream_p, overlong = ptok.tokenize(buf, max_token_bytes=w,
                                       block_rows=32, interpret=True)
    got = tbl.from_stream(stream_p, CAP)
    assert int(overlong) == 0
    _assert_tables_equal(want, got)


@pytest.mark.slow
def test_count_words_pallas_backend(rng):
    corpus = make_corpus(rng, n_words=1500, vocab=120)
    cfg = Config(chunk_bytes=128 * (2 * 32 + 2), table_capacity=CAP,
                 backend="pallas")
    with _interpret_mode():
        result = wordcount.count_words(corpus, cfg)
    assert result.as_dict() == oracle.word_counts(corpus)
    assert result.total == oracle.total_count(corpus)


@pytest.mark.slow
def test_streaming_executor_pallas_backend(tmp_path, rng):
    """The full sharded streaming path (shard_map-traced pallas_call, padded
    rows, overlong accounting through merge) with backend='pallas'."""
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime import executor

    corpus = make_corpus(rng, n_words=4000, vocab=200)
    path = tmp_path / "corpus.txt"
    path.write_bytes(corpus)
    cfg = Config(chunk_bytes=128 * (2 * 32 + 2), table_capacity=CAP,
                 backend="pallas")
    result = executor.count_file(str(path), cfg, mesh=data_mesh(4))
    assert result.as_dict() == oracle.word_counts(corpus)
    assert result.total == oracle.total_count(corpus)


def _interpret_mode():
    from tests.conftest import pallas_interpret_mode

    return pallas_interpret_mode()


def test_packed_bounds_validation():
    """The packed sort payload pins the kernel's envelope: 26-bit positions
    (64 MB chunks) and 6-bit lengths (W <= 63); out-of-envelope requests
    fail loudly instead of wrapping."""
    import jax.numpy as jnp
    import pytest
    from mapreduce_tpu.ops.pallas import tokenize as pt

    data = jnp.zeros((1 << 12,), jnp.uint8)
    with pytest.raises(ValueError, match="64 MB"):
        pt.tokenize_split(jnp.zeros(((1 << 26) + 128,), jnp.uint8))
    with pytest.raises(ValueError, match="<= 63"):
        pt.tokenize_split(data, max_token_bytes=64)


def test_packed_stream_consistency(small_corpus):
    """PackedTokenStream's packed plane and total agree with its own
    reconstructed pos/length/count fields."""
    import numpy as np
    from mapreduce_tpu.ops import tokenize as tok_ops
    from mapreduce_tpu.ops.pallas import tokenize as pt

    # Lane segments must cover the 2W+2 seam window: >= 66*128 bytes.
    padded_len = max(-(-len(small_corpus) // 128) * 128, 128 * 128)
    buf = tok_ops.pad_to(np.frombuffer(small_corpus, np.uint8), padded_len)
    col, seam, over = pt.tokenize_split(buf)
    packed = np.asarray(col.packed)
    count = np.asarray(col.count)
    has = packed != 0xFFFFFFFF
    assert np.array_equal(has.astype(np.uint32), count)
    assert int(col.total) == int(count.sum())
    np.testing.assert_array_equal(np.asarray(col.pos)[has], (packed >> 6)[has])
    np.testing.assert_array_equal(np.asarray(col.length)[has],
                                  (packed & 63)[has])


# --- slot compaction (round-4 review #2) -----------------------------------------


def _compact_table(data: bytes, slots: int, w: int = W, block_rows: int = 64):
    buf = _pad(data, w)
    col, seam, overlong, spill = ptok.tokenize_split_compact(
        buf, slots, max_token_bytes=w, block_rows=block_rows, interpret=True)
    stream = ptok.concat_streams(col, seam)
    t = tbl.from_stream(stream, CAP, max_token_bytes=w,
                        max_pos=int(buf.shape[0]))
    return t, int(overlong), int(spill)


def test_compact_bit_identical_when_no_spill(rng):
    corpus = make_corpus(rng, n_words=3000, vocab=200)
    want, got_full, _ = _tables(corpus)
    got, overlong, spill = _compact_table(corpus, slots=24)
    assert spill == 0
    _assert_tables_equal(want, got)


def test_compact_fixture_exact(fixture_text):
    want, _, _ = _tables(fixture_text)
    got, _, spill = _compact_table(fixture_text, slots=24)
    assert spill == 0
    _assert_tables_equal(want, got)


def test_compact_spill_detected_on_dense_text():
    """Alternating single-letter tokens: density 1/2 overflows any budget
    below block_rows/2, and the kernel must say so."""
    data = b"a " * 2048
    got, _, spill = _compact_table(data, slots=8, block_rows=64)
    assert spill > 0


@pytest.mark.slow
def test_compact_map_stream_falls_back_exactly(rng):
    """_map_stream's lax.cond: a spilling chunk silently reruns the full
    path — results must equal the XLA oracle for ANY density."""
    import jax
    import jax.numpy as jnp

    from mapreduce_tpu.models.wordcount import _map_stream

    for data in (b"a b " * 1024,          # density 1/2: always spills
                 make_corpus(np.random.default_rng(5), 2000, 150)):
        cfg = Config(backend="pallas", chunk_bytes=1 << 14, sort_mode="sort3",
                     compact_slots=8, pallas_max_token=32)
        buf = tok.pad_to(np.frombuffer(data, np.uint8),
                         max(cfg.pallas_min_chunk,
                             -(-len(data) // 128) * 128))
        t = jax.jit(lambda b: _map_stream(b, cfg, CAP))(jnp.asarray(buf))
        want = tbl.from_stream(tok.tokenize(jnp.asarray(buf)), CAP)
        _assert_tables_equal(want, t)


@pytest.mark.slow
def test_compact_density_sweep_bit_identical(rng):
    """Log-shift compaction across the density spectrum: separator-heavy
    (long movement distances), long runs (overlong poison rows riding the
    shift), and mixed densities — every no-spill case must equal the
    FULL-RESOLUTION pallas table bit for bit (the compaction invariant;
    the full path owns the W contract, so overlong mixes are in scope).
    Guards the shift algorithm's distance bookkeeping (movement = per-lane
    dead-row count, applied one binary bit per pass), whose failure modes
    are density-dependent in ways the two bench corpora never exercise."""
    cases = [
        b" " * 4000 + b"word " * 20,               # almost-empty lanes
        (b"a" * 30 + b" ") * 300,                  # overlong runs: poisons move
        b"ab " * 1500,                             # density 1/3
        b"abcd " * 1000,                           # density 1/5
        bytes(rng.integers(97, 100, 6000).tobytes())
        .replace(b"c", b" "),                      # random ~1/3 separators
        (b"w " * 10 + b"token " + b"\n") * 250,    # dense-but-fitting lanes
    ]
    for data in cases:
        _, got_full, overlong_full = _tables(data)
        got, overlong_c, spill = _compact_table(data, slots=24)
        assert spill == 0, data[:20]
        assert overlong_c == overlong_full
        _assert_tables_equal(got_full, got)


def test_compact_overlong_accounting(rng):
    """Overlong poison rows survive compaction: dropped_* match the full
    path's accounting bit for bit."""
    words = [b"x" * 3, b"y" * (W + 5), b"zz", b"q" * (2 * W)]
    corpus = b" ".join(words[int(i)] for i in rng.integers(0, 4, 600)) + b" "
    # Reference is the FULL-resolution pallas table (the XLA oracle keeps
    # >W words both pallas paths drop by contract).
    _, got_full, overlong_full = _tables(corpus)
    got, overlong_c, spill = _compact_table(corpus, slots=24)
    assert spill == 0
    assert overlong_c == overlong_full > 0
    _assert_tables_equal(got_full, got)


def test_compact_slots_validation():
    with pytest.raises(ValueError, match="compact_slots"):
        Config(compact_slots=12, sort_mode="sort3")  # not a multiple of 8
    with pytest.raises(ValueError, match="compact_slots"):
        Config(compact_slots=136, sort_mode="sort3")  # > 128
    with pytest.raises(ValueError, match="compact_slots"):
        ptok.tokenize_split_compact(
            tok.pad_to(b"hello world", 128 * 18), 48,
            max_token_bytes=8, block_rows=64, interpret=True)  # > block/2


@pytest.mark.slow
def test_natural_corpus_backends_agree():
    """round-3 review #6: on the natural-proxy corpus the pallas and xla
    backends must produce the SAME table — tools/density.py measured zero
    >W tokens there (max 18 bytes), so the >W envelope costs nothing on
    the bench corpora (round-4 chip notes section quantifies this)."""
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import make_natural_corpus

    corpus = make_natural_corpus(1 << 18)
    buf = tok.pad_to(corpus, -(-len(corpus) // 128) * 128)
    want = tbl.from_stream(tok.tokenize(buf), CAP)
    stream_p, overlong = ptok.tokenize(buf, max_token_bytes=32,
                                       interpret=True)
    assert int(overlong) == 0
    got = tbl.from_stream(stream_p, CAP)
    _assert_tables_equal(want, got)
    # And through the compact path, same story.
    col, seam, over_c, spill = ptok.tokenize_split_compact(
        buf, 88, max_token_bytes=32, interpret=True)
    assert int(spill) == 0 and int(over_c) == 0
    got_c = tbl.from_stream(ptok.concat_streams(col, seam), CAP,
                            max_token_bytes=32, max_pos=int(buf.shape[0]))
    _assert_tables_equal(want, got_c)
