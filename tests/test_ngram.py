"""N-gram counting: device results vs. a pure-Python oracle.

The reference has no n-gram capability (its map UDF emits single words only,
``mapper`` ``main.cu:37-54``); this family is beyond-parity, so the oracle is
the standard definition: sliding windows of n consecutive tokens of the
whitespace-split stream, keyed by the exact source span (separators between
tokens included).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from mapreduce_tpu.config import Config
from mapreduce_tpu.models import wordcount
from mapreduce_tpu.utils import oracle


def ngram_oracle(data: bytes, n: int) -> dict[bytes, int]:
    """{first-occurrence source span: count} per distinct n-token window.

    Grams are keyed by their *token sequence* (the device semantics: the gram
    hash mixes the token hashes, not the separator bytes between them), and
    each is displayed as the source span of its first occurrence — so
    ``b"w1 w1"`` and ``b"w1\\tw1"`` are the same bigram, reported under
    whichever span came first.
    """
    # Token spans (start, end) in order, replicating oracle.split_words.
    spans = []
    start = None
    seps = bytes(__import__("mapreduce_tpu").constants.SEPARATOR_BYTES)
    for i, b in enumerate(data):
        if b in seps:
            if start is not None:
                spans.append((start, i))
                start = None
        elif start is None:
            start = i
    if start is not None:
        spans.append((start, len(data)))
    counts: dict[tuple, int] = {}
    first_span: dict[tuple, bytes] = {}
    for i in range(len(spans) - n + 1):
        window = spans[i: i + n]
        key = tuple(data[s:e] for s, e in window)
        counts[key] = counts.get(key, 0) + 1
        first_span.setdefault(key, data[window[0][0]: window[-1][1]])
    return {first_span[k]: c for k, c in counts.items()}


def ngram_counts_by_tokens(data: bytes, n: int) -> dict[tuple, int]:
    """Oracle counts keyed by the token tuple itself (separator-independent).

    Streamed comparisons must use THIS keying: if a gram's true first
    occurrence straddles a chunk seam (dropped per the documented envelope),
    the streamed run reports a later occurrence's span, whose separator
    bytes may differ — span-keyed dict lookups would miss spuriously.
    """
    toks = oracle.split_words(data)
    counts: dict[tuple, int] = {}
    for i in range(len(toks) - n + 1):
        key = tuple(toks[i: i + n])
        counts[key] = counts.get(key, 0) + 1
    return counts


@pytest.mark.parametrize("n", [2, 3])
@pytest.mark.slow
def test_ngrams_match_oracle(small_corpus, n):
    cfg = Config(table_capacity=1 << 14)
    result = wordcount.count_ngrams(small_corpus, n, cfg)
    expected = ngram_oracle(small_corpus, n)
    assert result.as_dict() == expected
    assert result.total == sum(expected.values())
    assert result.dropped_count == 0


def test_bigram_fixture(fixture_text):
    result = wordcount.count_ngrams(fixture_text, 2)
    expected = ngram_oracle(fixture_text, 2)
    # 9 tokens -> 8 bigrams, all distinct except none repeat in the fixture.
    assert result.total == 8
    assert result.as_dict() == expected
    # Spans carry the real separator bytes (here the fixture's spaces and
    # newlines), e.g. the first bigram is the literal source text.
    assert result.words[0] == b"Hello World"


def test_unigram_order_matches_wordcount(fixture_text):
    uni = wordcount.count_ngrams(fixture_text, 1)
    base = wordcount.count_words(fixture_text)
    assert uni.as_dict() == base.as_dict()


@pytest.mark.slow  # 27 s measured round 6 (3 configs compiled): past the
# tier-1 >=10 s line; gram totals stay covered by test_ngrams_match_oracle.
def test_total_grams_is_tokens_minus_n_plus_1(small_corpus):
    tokens = oracle.total_count(small_corpus)
    for n in (1, 2, 3):
        result = wordcount.count_ngrams(small_corpus, n, Config(table_capacity=1 << 14))
        assert result.total == max(tokens - n + 1, 0)


def test_order_sensitive_keys():
    r = wordcount.count_ngrams(b"a b b a", 2)
    # 'a b', 'b b', 'b a' — order matters, all three distinct.
    assert r.as_dict() == {b"a b": 1, b"b b": 1, b"b a": 1}


def test_fewer_tokens_than_n():
    r = wordcount.count_ngrams(b"only two", 3)
    assert r.total == 0
    assert r.words == []


def test_gram_table_sentinel_boundary_at_max_pos_gate():
    """The packed gram build's sentinel-collision envelope (ADVICE r5):
    at the gate boundary max_pos == 2**25 a live row packs to
    _SENT_PACKED only with pos == 2**25-1 AND len7 == 127 together —
    unreachable, since a >=127-byte span cannot start within 127 bytes of
    max_pos (gram_table documents the proof; this pins its premises).

    Mechanically: rows AT the two extremes — the largest admissible pos
    with a short span, and the latest-starting >=127-byte span — must
    both survive the packed build with their identities intact."""
    from mapreduce_tpu import constants
    from mapreduce_tpu.ops import ngram as ngram_ops
    from mapreduce_tpu.ops.tokenize import TokenStream

    max_pos = 1 << 25
    sent = np.uint32(0xFFFFFFFF)
    n = 8
    khi = np.full(n, sent, np.uint32)
    klo = np.full(n, sent, np.uint32)
    cnt = np.zeros(n, np.uint32)
    pos = np.full(n, constants.POS_INF, np.uint32)
    length = np.zeros(n, np.uint32)
    # Row 0: a >=127-byte span at the latest start the invariant admits.
    khi[0], klo[0], cnt[0] = 7, 11, 1
    pos[0], length[0] = max_pos - 127, np.uint32(constants.SEAM_GRAM_LENGTH)
    # Row 1: the largest admissible pos, 1-byte span (packed = 0xFFFFFF81).
    khi[1], klo[1], cnt[1] = 13, 17, 1
    pos[1], length[1] = max_pos - 1, 1
    gs = TokenStream(key_hi=jnp.asarray(khi), key_lo=jnp.asarray(klo),
                     count=jnp.asarray(cnt), pos=jnp.asarray(pos),
                     length=jnp.asarray(length))
    t = ngram_ops.gram_table(gs, 8, 0, max_pos=max_pos)
    occ = np.asarray(t.occupied())
    assert int(occ.sum()) == 2  # neither row collided with the sentinel
    got = {(int(h), int(l)): (int(p), int(ln)) for h, l, p, ln in zip(
        np.asarray(t.key_hi)[occ], np.asarray(t.key_lo)[occ],
        np.asarray(t.pos_lo)[occ], np.asarray(t.length)[occ])}
    assert got[(7, 11)] == (max_pos - 127,
                            int(constants.SEAM_GRAM_LENGTH))
    assert got[(13, 17)] == (max_pos - 1, 1)
    assert int(np.asarray(t.dropped_count)) == 0


@pytest.mark.slow
def test_streamed_ngrams_single_device_exact(tmp_path):
    """Streamed == single-buffer, bit-exact, on a one-device mesh whose
    2 KB chunks force grams to straddle every row seam (round-2 review #5:
    the old (n-1)*(chunks-1) undercount envelope is gone — the seam carry
    forms every crossing window exactly once)."""
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime.executor import count_file

    from tests.conftest import make_corpus

    # Hermetic corpus (private rng): the shared session rng makes fixture
    # content depend on test-collection order, turning envelope assertions
    # into order-dependent flakes.
    corpus = make_corpus(np.random.default_rng(77), n_words=2000, vocab=150)

    path = tmp_path / "corpus.txt"
    path.write_bytes(corpus)
    cfg = Config(chunk_bytes=2048, table_capacity=1 << 14, backend="xla")
    mesh = data_mesh(1)
    result = count_file(str(path), config=cfg, mesh=mesh, ngram=2)
    single = wordcount.count_ngrams(corpus, 2, Config(table_capacity=1 << 14,
                                                      backend="xla"))
    assert result.total == single.total
    assert result.as_dict() == single.as_dict()
    assert result.words == single.words  # identical insertion order + spans


@pytest.mark.parametrize("n", [2, 3])
@pytest.mark.slow
def test_streamed_ngrams_multi_device_exact(tmp_path, n):
    """Streamed == single-buffer across an 8-device mesh: seams between
    devices within a step AND between steps, all exact."""
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime.executor import count_file
    from tests.conftest import make_corpus

    corpus = make_corpus(np.random.default_rng(78), n_words=2000, vocab=150)
    path = tmp_path / "corpus.txt"
    path.write_bytes(corpus)
    cfg = Config(chunk_bytes=1024, table_capacity=1 << 14, backend="xla")
    result = count_file(str(path), config=cfg, mesh=data_mesh(8), ngram=n)
    single = wordcount.count_ngrams(corpus, n, Config(table_capacity=1 << 14,
                                                      backend="xla"))
    assert result.total == single.total
    assert result.as_dict() == single.as_dict()
    assert result.words == single.words


@pytest.mark.slow
def test_streamed_ngrams_window_spans_three_chunks(tmp_path):
    """A separator run longer than a whole chunk leaves empty chunks between
    two tokens: the carry composes across them and the window completes at
    the right join (trigrams spanning 3+ chunks)."""
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime.executor import count_file

    corpus = b"aa bb" + b" " * 700 + b"cc" + b" " * 700 + b"dd ee\n"
    path = tmp_path / "gap.txt"
    path.write_bytes(corpus)
    cfg = Config(chunk_bytes=128, table_capacity=1 << 10, backend="xla")
    result = count_file(str(path), config=cfg, mesh=data_mesh(2), ngram=3)
    single = wordcount.count_ngrams(corpus, 3, Config(table_capacity=1 << 10,
                                                      backend="xla"))
    assert result.total == single.total == 3
    assert result.as_dict() == single.as_dict()
    assert result.words == single.words  # spans include the 700-byte gaps


@pytest.mark.slow
def test_streamed_pallas_ngrams_exact_across_seams(tmp_path):
    """The pallas backend's streamed grams are exact across chunk seams too
    (summary extracted from the position-sorted packed stream)."""
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime.executor import count_file
    from tests.conftest import make_corpus

    corpus = make_corpus(np.random.default_rng(81), n_words=8000, vocab=120)
    path = tmp_path / "corpus.txt"
    path.write_bytes(corpus)
    cfg = Config(chunk_bytes=128 * 66, table_capacity=1 << 14, backend="pallas")
    result = count_file(str(path), config=cfg, mesh=data_mesh(2), ngram=2)
    single = wordcount.count_ngrams(corpus, 2, Config(table_capacity=1 << 14,
                                                      backend="xla"))
    assert result.total == single.total
    assert result.as_dict() == single.as_dict()
    assert result.words == single.words


@pytest.mark.slow
def test_ngram_checkpoint_order_mismatch(tmp_path, small_corpus):
    """Bigram and trigram states share shapes; job identity refuses the
    cross-resume."""
    import pytest
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime import checkpoint as ckpt
    from mapreduce_tpu.runtime.executor import count_file

    path = tmp_path / "c.txt"
    path.write_bytes(small_corpus)
    cfg = Config(chunk_bytes=1024, table_capacity=1 << 12, backend="xla")
    ck = str(tmp_path / "ng.npz")
    count_file(str(path), config=cfg, mesh=data_mesh(2), ngram=2,
               checkpoint_path=ck, checkpoint_every=1)
    with pytest.raises(ckpt.CheckpointMismatch, match="job"):
        count_file(str(path), config=cfg, mesh=data_mesh(2), ngram=3,
                   checkpoint_path=ck, checkpoint_every=1)


# --- pallas backend (position-sort path, mapreduce_tpu/ops/ngram.py) -------

PALLAS_CFG = Config(chunk_bytes=128 * 66, table_capacity=1 << 14,
                    backend="pallas")


@pytest.mark.parametrize("n", [2, 3])
@pytest.mark.slow
def test_pallas_ngrams_match_oracle_and_xla(small_corpus, n):
    """The position-sort path produces bit-identical results to the XLA
    scan path (same hashes, same spans, same order)."""
    pal = wordcount.count_ngrams(small_corpus, n, PALLAS_CFG)
    xla = wordcount.count_ngrams(small_corpus, n,
                                 Config(table_capacity=1 << 14, backend="xla"))
    assert pal.as_dict() == ngram_oracle(small_corpus, n)
    assert pal.as_dict() == xla.as_dict()
    assert pal.words == xla.words  # identical insertion order
    assert pal.total == xla.total


def test_pallas_gram_straddles_lane_seam():
    """round-1 review #4 'done' case: the kernel's 128-lane seams cut the buffer
    every seg_len bytes; seam emissions are concatenated before the position
    sort, so grams whose tokens straddle a seam must form exactly.  The
    buffer is sized to one pallas chunk (seg_len = 66), so a corpus covering
    it crosses ~128 seams; exact dict equality proves no seam gram is lost."""
    words = [b"w%d" % (i % 37) for i in range(1800)]
    data = b" ".join(words)[: 128 * 66 - 2]  # fill the whole chunk
    data = data.rsplit(b" ", 1)[0]  # end on a whole token
    pal = wordcount.count_ngrams(data, 2, PALLAS_CFG)
    assert pal.as_dict() == ngram_oracle(data, 2)
    assert pal.total == oracle.total_count(data) - 1
    assert pal.dropped_count == 0


def test_pallas_ngram_overlong_poison(small_corpus):
    """A chunk containing a token longer than the kernel window W: poison
    rows break the pairing chain at the suppressed token, so its neighbors
    never pair into phantom grams; the grams it would have joined are
    dropped and accounted (round-2 review #4 — this replaced the whole-chunk
    lax.cond XLA fallback that embedded a pathologically-slow-to-compile
    branch in every n-gram program)."""
    data = small_corpus[:4000] + b" " + b"x" * 40 + b" " + small_corpus[4000:]
    pal = wordcount.count_ngrams(data, 2, PALLAS_CFG)
    xla = wordcount.count_ngrams(data, 2,
                                 Config(table_capacity=1 << 14, backend="xla"))
    # total_count includes dropped grams: the closed-form total is shared.
    assert pal.total == xla.total
    # The long token joins exactly 2 bigrams; both dropped, never phantom.
    long_grams = {w for w in xla.words if b"x" * 40 in w}
    assert len(long_grams) == 2
    assert pal.dropped_count == sum(
        xla.counts[xla.words.index(w)] for w in long_grams)
    assert not any(b"x" * 40 in w for w in pal.words)
    # Every other gram is identical, with identical counts — and no phantom
    # gram (a span bridging the suppressed token) appears.
    pal_counts = dict(zip(pal.words, pal.counts))
    xla_counts = {w: c for w, c in zip(xla.words, xla.counts)
                  if w not in long_grams}
    assert pal_counts == xla_counts


def test_pallas_ngram_overlong_adjacent_grams_trigram():
    """Overlong tokens adjacent to real grams, n=3: the whole pairing window
    crossing the poison row invalidates (not just the immediate neighbor
    pair), and dense gram structure around the suppression stays exact."""
    data = b"aa bb " + b"y" * 50 + b" cc dd ee " + b"z" * 40 + b" ff gg"
    pal = wordcount.count_ngrams(data, 3, PALLAS_CFG)
    xla = wordcount.count_ngrams(data, 3,
                                 Config(table_capacity=1 << 14, backend="xla"))
    assert pal.total == xla.total  # closed-form total incl. dropped
    # Only trigrams fully inside a run of <=W tokens survive: "cc dd ee".
    assert pal.words == [b"cc dd ee"]
    # 9 tokens -> 7 trigrams total; 1 formed, 6 dropped (every window that
    # touches y*50 or z*40).
    assert pal.counts == [1]
    assert pal.dropped_count == 6
    # And the XLA backend counts all 7 exactly (any token length).
    assert xla.total == 7 and xla.dropped_count == 0


def test_pallas_ngram_program_has_no_cond_fallback():
    """The n-gram program must be straight-line: no lax.cond (both branches
    of a cond are always compiled, so an embedded XLA-scan fallback would
    poison every program's compile time at production chunk sizes)."""
    import jax

    from mapreduce_tpu.ops import ngram as ngram_ops

    def step(chunk):
        return ngram_ops.ngram_table(chunk, 2, 1 << 10, 0, PALLAS_CFG)

    jaxpr = str(jax.make_jaxpr(step)(
        jnp.zeros((PALLAS_CFG.chunk_bytes,), jnp.uint8)))
    # Exactly one cond exists: the kernel's own `pl.when(i == 0)` scratch
    # init INSIDE the pallas_call.  The deleted fallback was a top-level
    # two-branch cond whose branches each returned a whole CountTable; any
    # second cond appearing here means a fallback crept back in.
    assert jaxpr.count("cond[") == 1 and "pallas_call" in jaxpr


@pytest.mark.slow
def test_streamed_pallas_ngrams_match_xla_backend(tmp_path):
    """Streamed n-grams: pallas and xla backends over identical chunking
    must agree exactly (the per-chunk envelope is backend-independent)."""
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime.executor import count_file
    from tests.conftest import make_corpus

    corpus = make_corpus(np.random.default_rng(79), n_words=6000, vocab=150)
    path = tmp_path / "corpus.txt"
    path.write_bytes(corpus)
    base = dict(chunk_bytes=128 * 66, table_capacity=1 << 14)
    rp = count_file(str(path), config=Config(**base, backend="pallas"),
                    mesh=data_mesh(2), ngram=2)
    rx = count_file(str(path), config=Config(**base, backend="xla"),
                    mesh=data_mesh(2), ngram=2)
    assert rp.as_dict() == rx.as_dict()
    assert rp.words == rx.words
    assert rp.total == rx.total


def test_seam_carry_monoid_and_poison():
    """Unit tests of the sliding-window monoid: compose_carry keeps the most
    recent n-1 entries across short chunks, and a poison entry (suppressed
    >W token) in the carry kills exactly the windows containing it."""
    from mapreduce_tpu.ops import ngram as ng

    def carry(entries, m):
        """Right-aligned carry from [(khi, kind), ...] (newest last)."""
        pad = [(0, ng.KIND_EMPTY)] * (m - len(entries)) + entries
        return ng.GramCarry(
            key_hi=jnp.array([e[0] for e in pad], jnp.uint32),
            key_lo=jnp.array([e[0] ^ 7 for e in pad], jnp.uint32),
            chunk_id=jnp.array([9] * m, jnp.uint32),
            pos=jnp.array(range(m), jnp.uint32),
            kind=jnp.array([e[1] for e in pad], jnp.uint32))

    m = 3  # n = 4
    t = ng.KIND_TOKEN
    a = carry([(1, t), (2, t), (3, t)], m)
    b_short = carry([(4, t)], m)  # a 1-token chunk
    c = ng.compose_carry(a, b_short)
    assert list(np.asarray(c.key_hi)) == [2, 3, 4]
    assert list(np.asarray(c.kind)) == [t, t, t]
    # A full replacement: 3+ new entries wipe the old carry.
    b_full = carry([(5, t), (6, t), (7, t)], m)
    c2 = ng.compose_carry(a, b_full)
    assert list(np.asarray(c2.key_hi)) == [5, 6, 7]
    # Empty chunk: identity.
    c3 = ng.compose_carry(a, carry([], m))
    assert list(np.asarray(c3.key_hi)) == [1, 2, 3]

    # Poison in the prefix: windows containing it exist but are dropped.
    n = 4
    prefix = carry([(1, t), (2, ng.KIND_POISON), (3, t)], m)
    first = carry([], m)._replace(  # left-aligned: 3 tokens
        key_hi=jnp.array([10, 11, 12], jnp.uint32),
        key_lo=jnp.array([20, 21, 22], jnp.uint32),
        kind=jnp.array([t, t, t], jnp.uint32))
    k_hi, k_lo, cid, pos, cnt, dropped = ng.seam_gram_rows(prefix, first, n)
    # j=1: [3,10,11,12] all tokens -> counted; j=2: contains poison ->
    # dropped; j=3: contains poison -> dropped.
    assert list(np.asarray(cnt)) == [1, 0, 0]
    assert int(dropped) == 2


@pytest.mark.slow
def test_streamed_sketched_ngrams_exact(tmp_path):
    """Sketch composition forwards the seam machinery: a distinct-sketch
    streamed bigram run still matches single-buffer totals exactly."""
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime.executor import count_file
    from tests.conftest import make_corpus

    corpus = make_corpus(np.random.default_rng(82), n_words=1500, vocab=100)
    path = tmp_path / "corpus.txt"
    path.write_bytes(corpus)
    cfg = Config(chunk_bytes=1024, table_capacity=1 << 14, backend="xla")
    result = count_file(str(path), config=cfg, mesh=data_mesh(4), ngram=2,
                        distinct_sketch=True)
    single = wordcount.count_ngrams(corpus, 2, Config(table_capacity=1 << 14,
                                                      backend="xla"))
    assert result.total == single.total
    assert result.as_dict() == single.as_dict()
    assert result.distinct_estimate == pytest.approx(single.distinct, rel=0.1)


@pytest.mark.slow
def test_streamed_ngrams_top_k_with_seam_entries(tmp_path):
    """Device-side top_k over the streamed NGramState: seam entries
    (SEAM_GRAM_LENGTH) survive the terminal reorder and recover real spans;
    counts match the single-buffer top-k multiset."""
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime.executor import count_file
    from tests.conftest import make_corpus

    corpus = make_corpus(np.random.default_rng(83), n_words=2000, vocab=40)
    path = tmp_path / "corpus.txt"
    path.write_bytes(corpus)
    cfg = Config(chunk_bytes=1024, table_capacity=1 << 14, backend="xla")
    result = count_file(str(path), config=cfg, mesh=data_mesh(4), ngram=2,
                        top_k=10)
    single = wordcount.count_ngrams(corpus, 2, Config(table_capacity=1 << 14,
                                                      backend="xla"))
    single_top = wordcount.apply_top_k(single, 10)
    assert len(result.words) == 10
    assert sorted(result.counts, reverse=True) == sorted(
        single_top.counts, reverse=True)
    assert result.total == single.total
    # Reported spans are real corpus grams with true counts.
    exact = single.as_dict()
    for w, c in zip(result.words, result.counts):
        assert exact.get(w) == c, w


@pytest.mark.slow
def test_streamed_ngrams_multi_file_no_cross_file_grams(tmp_path):
    """Files are independent corpora: the seam carry resets at file
    boundaries (stacked-state-shaped reset), so no gram spans two files and
    the result equals per-file single-buffer runs summed."""
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime.executor import count_file

    a, b = tmp_path / "a.txt", tmp_path / "b.txt"
    a.write_bytes(b"p q r s")  # no trailing newline: seam right at file end
    b.write_bytes(b"t u v w\n")
    cfg = Config(chunk_bytes=128, table_capacity=1 << 10, backend="xla")
    result = count_file([str(a), str(b)], config=cfg, mesh=data_mesh(2),
                        ngram=2)
    xcfg = Config(table_capacity=1 << 10, backend="xla")
    ra = wordcount.count_ngrams(b"p q r s", 2, xcfg)
    rb = wordcount.count_ngrams(b"t u v w\n", 2, xcfg)
    assert result.total == ra.total + rb.total == 6
    assert result.as_dict() == {**ra.as_dict(), **rb.as_dict()}
    assert b"s t" not in result.as_dict()  # no cross-file gram


def test_seam_span_over_force_split_run(tmp_path):
    """A separator-free run longer than the reader's alignment window gets
    force-split at a row cut into two stream entries; a seam gram over the
    halves must recover a span ending at the cut-induced entry end, not
    swallow the run plus the next word (scan_gram_lengths cut_offsets)."""
    from mapreduce_tpu.data import reader

    run = b"x" * 5000
    corpus = run + b" next word\n"
    path = tmp_path / "r.txt"
    path.write_bytes(corpus)
    # Simulated device view: a cut at 4096 splits the run into two entries.
    lengths = reader.scan_gram_lengths(str(path), [0], 2, cut_offsets=[4096])
    # Entry 1 = run[:4096] (ends at the cut), entry 2 = run[4096:5000]
    # (ends at the separator): the 2-gram span is exactly the whole run.
    assert lengths == [5000]
    # Without the cut the two entries are run + "next": span reaches "next".
    assert reader.scan_gram_lengths(str(path), [0], 2) == [5000 + 5]


@pytest.mark.slow
def test_streamed_ngrams_superstep_exact(tmp_path):
    """Superstep (lax.scan) dispatch: each scan iteration is one step —
    its own summary gather + carry composition — so K-chunk supersteps
    keep streamed n-grams bit-exact."""
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime.executor import count_file
    from tests.conftest import make_corpus

    corpus = make_corpus(np.random.default_rng(84), n_words=2500, vocab=120)
    path = tmp_path / "corpus.txt"
    path.write_bytes(corpus)
    cfg = Config(chunk_bytes=1024, table_capacity=1 << 14, backend="xla",
                 superstep=3)
    result = count_file(str(path), config=cfg, mesh=data_mesh(2), ngram=2)
    single = wordcount.count_ngrams(corpus, 2, Config(table_capacity=1 << 14,
                                                      backend="xla"))
    assert result.total == single.total
    assert result.as_dict() == single.as_dict()
    assert result.words == single.words


@pytest.mark.slow
def test_streamed_ngrams_2d_mesh_exact(tmp_path):
    """Streamed n-grams on a 2-D ('replica','data') mesh: the summary
    all_gather over the axis TUPLE must order rows exactly like the
    engine's row-major device-index linearization, or seam windows pair
    the wrong chunks.  Exactness against single-buffer proves the order."""
    from mapreduce_tpu.parallel.mesh import two_level_mesh
    from mapreduce_tpu.runtime.executor import count_file
    from tests.conftest import make_corpus

    corpus = make_corpus(np.random.default_rng(85), n_words=2000, vocab=100)
    path = tmp_path / "corpus.txt"
    path.write_bytes(corpus)
    cfg = Config(chunk_bytes=1024, table_capacity=1 << 14, backend="xla")
    result = count_file(str(path), config=cfg, mesh=two_level_mesh(2, 4),
                        ngram=2)
    single = wordcount.count_ngrams(corpus, 2, Config(table_capacity=1 << 14,
                                                      backend="xla"))
    assert result.total == single.total
    assert result.as_dict() == single.as_dict()
    assert result.words == single.words


@pytest.mark.slow
def test_long_span_grams_recovered_exactly(tmp_path):
    """Gram spans >= 127 bytes (unbounded separator runs between tokens)
    exceed the packed build's 7-bit length field: the table stores the
    SEAM_GRAM_LENGTH scan-forward sentinel and recovery rescans the span —
    single-buffer (scan_gram_lengths_bytes) and streamed
    (scan_gram_lengths) alike, on both backends, bit-identically."""
    from mapreduce_tpu.runtime.executor import count_file

    corpus = (b"alpha" + b" " * 200 + b"beta gamma ") * 3 + b"alpha beta"
    expect = ngram_oracle(corpus, 2)
    xla_cfg = Config(table_capacity=1 << 14, backend="xla")
    xla = wordcount.count_ngrams(corpus, 2, xla_cfg)
    pal = wordcount.count_ngrams(corpus, 2, PALLAS_CFG)
    assert xla.as_dict() == expect
    assert pal.as_dict() == expect
    assert pal.words == xla.words
    # The long-gap bigram's reported span really is the 200-separator one.
    assert any(len(w) > 200 for w in xla.words)
    path = tmp_path / "corpus.txt"
    path.write_bytes(corpus)
    streamed = count_file(str(path), config=Config(
        chunk_bytes=1024, table_capacity=1 << 14, backend="xla"), ngram=2)
    assert streamed.total == xla.total
    # Token-keyed comparison (the streamed-comparison caveat,
    # ngram_counts_by_tokens): a wrong host-rescanned span would split
    # into the wrong token tuple and miss here.
    by_tokens = {tuple(oracle.split_words(w)): c
                 for w, c in zip(streamed.words, streamed.counts)}
    assert by_tokens == ngram_counts_by_tokens(corpus, 2)
