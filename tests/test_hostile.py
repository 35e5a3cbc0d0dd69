"""Hostile-input fixtures beyond clean ASCII (round-4 review next #9).

The bench generators are clean ASCII; real corpora (enwik dumps, WET crawl
text) carry UTF-8 multibyte words, NUL bytes, markup, and very long
separator-free runs.  Each fixture here pins either exact backend agreement
(pallas vs the XLA oracle vs the host oracle) or the documented accounting
envelope where the semantics intentionally bound work (force-split, rescue
window).
"""


import pytest

from bench import make_markup_corpus
from mapreduce_tpu.config import Config
from mapreduce_tpu.models import wordcount
from mapreduce_tpu.utils import oracle

XLA = Config(chunk_bytes=1 << 13, table_capacity=1 << 12, backend="xla")
PALLAS = Config(chunk_bytes=1 << 14, table_capacity=1 << 12,
                backend="pallas")  # stable2 default: the production shape


def _agree(data: bytes, pallas_cfg: Config = PALLAS):
    rp = wordcount.count_words(data, pallas_cfg)
    rx = wordcount.count_words(data, XLA)
    want = oracle.word_counts(data)
    assert rx.as_dict() == want
    assert rp.as_dict() == want
    assert rp.words == rx.words  # first-occurrence order identical
    assert rp.total == rx.total
    return rp


@pytest.mark.slow
def test_utf8_multibyte_words():
    """Continuation bytes (>= 0x80) are never separators: multibyte words
    stay whole, stay distinct from their prefixes, and report byte-exact."""
    text = ("café naïve über résumé Αθήνα λόγος 東京 中文 "
            "caf café 日本語テスト emoji\U0001F600mix "
            "café").encode("utf-8")
    r = _agree(text)
    d = r.as_dict()
    assert d["café".encode()] == 2
    assert d["caf".encode()] == 1  # prefix is its own word
    # NFC vs NFD stay distinct (byte semantics, not unicode-normalized).
    assert "café".encode() in d


def test_utf8_words_crossing_chunk_seams(tmp_path):
    """Streamed runs must never split a multibyte word at a chunk seam
    (the reader cuts at separators only)."""
    from mapreduce_tpu.runtime.executor import count_file

    words = ["Αθήνα", "東京都庁", "naïveté", "plain"] * 400
    text = " ".join(words).encode("utf-8")
    p = tmp_path / "u.txt"
    p.write_bytes(text)
    cfg = Config(chunk_bytes=1 << 10, table_capacity=1 << 12, backend="xla")
    r = count_file([str(p)], config=cfg)
    assert r.as_dict() == oracle.word_counts(text)


@pytest.mark.slow
def test_nul_bearing_input():
    """NUL is a separator (the reference's memset-padding made it one
    implicitly, main.cu:178): embedded NULs split tokens exactly and
    tokens around them report byte-exact.

    @slow (the ">= ~10 s carries @slow" rebalance, ISSUE 8 round: 32 s —
    two fresh unique-shape compiles for a 5-token input): NUL-as-
    separator stays fast-tier via test_fuzz's separator-pathology sweep
    (NUL is in its separator set) and the pallas fixture tests; this
    byte-exact micro case runs in the full suite."""
    data = b"alpha\x00beta \x00\x00 gamma\x00\x00delta alpha"
    r = _agree(data)
    assert r.as_dict() == {b"alpha": 2, b"beta": 1, b"gamma": 1, b"delta": 1}


@pytest.mark.slow
def test_long_separator_free_run_force_split(tmp_path):
    """A separator-free run far beyond chunk_bytes: the reader force-splits
    (it must make progress), producing deterministic artificial token
    boundaries at the cut points — streamed totals stay exact and
    deterministic, and every reported word is a true substring count."""
    from mapreduce_tpu.runtime.executor import count_file

    run = b"Z" * 50_000  # no separator anywhere
    text = b"head " + run + b" tail"
    p = tmp_path / "r.txt"
    p.write_bytes(text)
    cfg = Config(chunk_bytes=1 << 12, table_capacity=1 << 12, backend="xla")
    r1 = count_file([str(p)], config=cfg)
    r2 = count_file([str(p)], config=cfg)
    assert r1.as_dict() == r2.as_dict()  # deterministic
    assert r1.as_dict()[b"head"] == 1 and r1.as_dict()[b"tail"] == 1
    # The run's bytes are all accounted: fragments sum to the run length.
    frag_bytes = sum(len(w) * c for w, c in r1.as_dict().items()
                     if w.startswith(b"Z"))
    assert frag_bytes == len(run)


@pytest.mark.slow
def test_markup_corpus_backends_agree():
    """The enwik-like markup generator (UTF-8, tags, entities, wiki links,
    URLs, long attribute blobs): pallas with DEFAULT flags (stable2 +
    tiered rescue) must match the XLA oracle exactly — every >W token
    rescued (the generator's longest run is 400+8 bytes < the 512-byte
    window used here)."""
    data = make_markup_corpus(120_000)
    cfg = Config(chunk_bytes=1 << 15, table_capacity=1 << 13,
                 backend="pallas", rescue_window=512)
    rp = wordcount.count_words(data, cfg)
    rx = wordcount.count_words(data, Config(chunk_bytes=1 << 15,
                                            table_capacity=1 << 13,
                                            backend="xla"))
    assert rp.as_dict() == rx.as_dict()
    assert rp.words == rx.words
    assert rp.dropped_count == 0
    assert rx.as_dict() == oracle.word_counts(data)
    # The fixture really is hostile: multibyte + overlong tokens present.
    assert any(max(w) >= 0x80 for w in rp.words)
    assert any(len(w) > 32 for w in rp.words)


@pytest.mark.slow
def test_markup_corpus_streamed_matches_buffered(tmp_path):
    from mapreduce_tpu.runtime.executor import count_file

    data = make_markup_corpus(80_000)
    p = tmp_path / "m.txt"
    p.write_bytes(data)
    cfg = Config(chunk_bytes=1 << 14, table_capacity=1 << 13, backend="xla")
    rs = count_file([str(p)], config=cfg)
    rb = wordcount.count_words(data, cfg)
    assert rs.as_dict() == rb.as_dict()
    assert rs.words == rb.words
