"""Host-side exact-recount verification (the key-collision detection path).

round-4 review missing #4: the 64-bit key-collision envelope needed (1) stated
arithmetic (ops/table.py module docstring), (2) a detection tool, and (3) a
test that INJECTS a collision and shows the failure mode is visible.  The
injection here collapses the hash finalizer to 4 bits, guaranteeing many
distinct words share a key — exactly the (astronomically rare) real failure,
made reproducible.
"""


import pytest

from mapreduce_tpu.config import Config
from mapreduce_tpu.models import wordcount
from mapreduce_tpu.ops import tokenize as tok_ops
from mapreduce_tpu.utils import oracle
from mapreduce_tpu.utils.verify import recount_exact, verify_result
from tests.conftest import make_corpus


def test_recount_exact_matches_oracle(tmp_path, rng):
    corpus = make_corpus(rng, n_words=5000, vocab=200)
    p = tmp_path / "c.txt"
    p.write_bytes(corpus)
    want = oracle.word_counts(corpus)
    some = list(want)[:50]
    got = recount_exact(str(p), some, chunk_bytes=512)  # many carry seams
    assert got == {w: want[w] for w in some}


def test_recount_exact_multi_file_and_unterminated_tail(tmp_path):
    a, b = tmp_path / "a.txt", tmp_path / "b.txt"
    a.write_bytes(b"x y x")  # no trailing separator: tail token counts
    b.write_bytes(b"x z")
    got = recount_exact([str(a), str(b)], [b"x", b"y", b"z"])
    assert got == {b"x": 3, b"y": 1, b"z": 1}


@pytest.mark.slow
def test_verify_result_passes_on_honest_run(tmp_path, rng):
    corpus = make_corpus(rng, n_words=4000, vocab=100)
    p = tmp_path / "c.txt"
    p.write_bytes(corpus)
    r = wordcount.count_words(corpus, Config(chunk_bytes=1 << 15,
                                             table_capacity=4096))
    assert verify_result(r.words, r.counts, str(p), sample=32) == []


def test_injected_collision_is_detected(tmp_path, rng, monkeypatch):
    """Collapse the hash finalizer to 4 bits: distinct words now share
    64-bit keys, the table silently merges them (summed counts under one
    identity) — and the exact recount flags it."""
    corpus = make_corpus(rng, n_words=3000, vocab=300)
    p = tmp_path / "c.txt"
    p.write_bytes(corpus)

    real_fmix = tok_ops._fmix32
    monkeypatch.setattr(tok_ops, "_fmix32", lambda x: real_fmix(x) & 0xF)
    # A chunk size no other test uses: the jit cache must not serve a
    # trace made with the honest hash.
    r = wordcount.count_words(corpus, Config(chunk_bytes=(1 << 15) + 128,
                                             table_capacity=4096,
                                             backend="xla"))
    monkeypatch.undo()

    # The collision itself: fewer reported identities than true distinct,
    # but totals conserved (merging never loses occurrences).
    true_counts = oracle.word_counts(corpus)
    assert len(r.words) < len(true_counts)
    assert r.total == sum(true_counts.values())

    mismatches = verify_result(r.words, r.counts, str(p), sample=64)
    assert mismatches, "collision went undetected"
    for w, reported, true in mismatches:
        # The absorber's reported count exceeds its exact recount.
        assert reported > true
