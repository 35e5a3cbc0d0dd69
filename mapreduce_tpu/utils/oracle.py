"""Pure-Python reference implementations ("oracles") for tests.

The reference repo has no tests (SURVEY §4); its implied methodology is one
manual golden run over ``test.txt``.  We instead check every device path
against these host oracles, which implement the *intended* semantics of the
reference (whitespace-split word count, insertion-ordered report,
``main.cu:187-218``) without its defects (prefix compare, capacity overflows).
"""

from __future__ import annotations

from collections import Counter

from mapreduce_tpu import constants

# Every separator becomes a space, then ``bytes.split()`` splits on runs of
# ASCII whitespace — which must all be separators, or split() would cut
# tokens the framework keeps whole.
_TO_SPACE = bytes.maketrans(bytes(constants.SEPARATOR_BYTES),
                            b" " * len(constants.SEPARATOR_BYTES))
assert all(b in constants.SEPARATOR_BYTES
           for b in range(256) if not bytes([b]).split())

#: Bytes split per slab in :func:`word_counts`: bounds the token list's
#: host memory on GiB corpora.
_SLAB = 16 << 20


def split_words(data: bytes) -> list[bytes]:
    """All tokens in order, splitting on the framework's separator set."""
    return bytes(data).translate(_TO_SPACE).split()


def word_counts(data: bytes) -> dict[bytes, int]:
    """Insertion-ordered {word: count} — the golden semantics (SURVEY §2)."""
    text = bytes(data).translate(_TO_SPACE)
    counts: Counter = Counter()
    start = 0
    while start < len(text):
        end = len(text)
        if end - start > _SLAB:
            # Cut after a separator so no token straddles two slabs.
            cut = text.rfind(b" ", start, start + _SLAB)
            if cut < 0:
                cut = text.find(b" ", start + _SLAB)
            end = len(text) if cut < 0 else cut + 1
        counts.update(text[start:end].split())
        start = end
    return dict(counts)


def total_count(data: bytes) -> int:
    return len(split_words(data))
