"""Unit tests for the segmented-scan tokenizer (mapreduce_tpu/ops/tokenize.py)."""

import jax.numpy as jnp
import numpy as np
import pytest

from mapreduce_tpu import constants
from mapreduce_tpu.ops import tokenize as tok
from mapreduce_tpu.utils import oracle


def _as_buf(data: bytes):
    return jnp.asarray(np.frombuffer(data, dtype=np.uint8))


def test_separator_mask():
    data = _as_buf(b"a b\tc\nd\re\x00f")
    mask = np.asarray(tok.separator_mask(data))
    expected = [False, True, False, True, False, True, False, True, False, True, False]
    assert mask.tolist() == expected


def test_token_count_matches_oracle(small_corpus):
    n = int(tok.token_count(_as_buf(small_corpus)))
    assert n == oracle.total_count(small_corpus)


def test_token_ends_positions_lengths():
    data = b"ab cde\nf"
    s = tok.tokenize(_as_buf(data))
    ends = np.flatnonzero(np.asarray(s.count))
    assert ends.tolist() == [1, 5, 7]
    pos = np.asarray(s.pos)[ends]
    length = np.asarray(s.length)[ends]
    assert pos.tolist() == [0, 3, 7]
    assert length.tolist() == [2, 3, 1]


def test_equal_tokens_equal_hashes():
    data = b"foo bar foo baz foo bar"
    s = tok.tokenize(_as_buf(data))
    ends = np.flatnonzero(np.asarray(s.count))
    hi = np.asarray(s.key_hi)[ends]
    lo = np.asarray(s.key_lo)[ends]
    words = oracle.split_words(data)
    seen = {}
    for w, h, l in zip(words, hi, lo):
        if w in seen:
            assert seen[w] == (h, l)
        else:
            seen[w] = (h, l)
    # distinct words -> distinct hashes
    assert len({v for v in seen.values()}) == len(seen)


def test_prefix_words_hash_differently():
    """The reference's prefix-compare defect (main.cu:57-67) must not recur."""
    data = b"Good Goodness Go Goo Good"
    s = tok.tokenize(_as_buf(data))
    ends = np.flatnonzero(np.asarray(s.count))
    keys = {(int(h), int(l)) for h, l in zip(np.asarray(s.key_hi)[ends], np.asarray(s.key_lo)[ends])}
    assert len(keys) == 4


@pytest.mark.slow
def test_hash_collision_rate(rng):
    """64-bit keys over a 50k-word vocabulary: no collisions expected."""
    vocab = [f"word{i}" for i in range(50_000)]
    data = (" ".join(vocab)).encode()
    s = tok.tokenize(_as_buf(data))
    ends = np.flatnonzero(np.asarray(s.count))
    pairs = set(zip(np.asarray(s.key_hi)[ends].tolist(), np.asarray(s.key_lo)[ends].tolist()))
    assert len(pairs) == len(vocab)


def test_non_token_positions_are_sentinel():
    s = tok.tokenize(_as_buf(b"a  b"))
    non_ends = np.asarray(s.count) == 0
    assert np.all(np.asarray(s.key_hi)[non_ends] == constants.SENTINEL_KEY)
    assert np.all(np.asarray(s.key_lo)[non_ends] == constants.SENTINEL_KEY)


def test_pad_bytes_do_not_create_tokens():
    raw = b"alpha beta"
    padded = tok.pad_to(raw, 128)
    n = int(tok.token_count(jnp.asarray(padded)))
    assert n == 2


def test_rejects_wrong_dtype():
    with pytest.raises(TypeError):
        tok.tokenize(jnp.zeros((8,), jnp.int32))


def test_base_offset_shifts_positions():
    s = tok.tokenize(_as_buf(b"ab cd"), base_offset=100)
    ends = np.flatnonzero(np.asarray(s.count))
    assert np.asarray(s.pos)[ends].tolist() == [100, 103]


@pytest.mark.parametrize("width", [1, 7, 64, 192])
@pytest.mark.parametrize("alphabet", [b" a", b"\x00\t\n ab\xff", b"ab\xc8"])
def test_last_token_matches_tokenize(width, alphabet):
    """The closed-form hash of the token ending at each row's last byte
    (the overlong rescue's) equals tokenize()'s emission there, bit for
    bit — including runs that fill the whole window."""
    import jax

    rng = np.random.default_rng(width)
    rows = rng.choice(np.frombuffer(alphabet, np.uint8), size=(200, width))
    s = jax.vmap(tok.tokenize)(jnp.asarray(rows))
    hi, lo, ln = tok.last_token(jnp.asarray(rows))
    assert np.array_equal(np.asarray(ln), np.asarray(s.length[:, -1]))
    ends = np.asarray(s.count[:, -1]) > 0
    assert np.array_equal(np.asarray(hi)[ends], np.asarray(s.key_hi[:, -1])[ends])
    assert np.array_equal(np.asarray(lo)[ends], np.asarray(s.key_lo[:, -1])[ends])
