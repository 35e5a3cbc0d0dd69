"""Ingest tests: boundary alignment, streaming equivalence, recovery spans."""

import numpy as np
import pytest

from mapreduce_tpu import constants
from mapreduce_tpu.data import reader
from mapreduce_tpu.utils import oracle
from tests.conftest import make_corpus

SEPS = set(constants.SEPARATOR_BYTES)


def _write(tmp_path, data: bytes):
    p = tmp_path / "corpus.txt"
    p.write_bytes(data)
    return str(p)


def test_rows_end_at_separators(tmp_path, rng):
    corpus = make_corpus(rng, 3000, 100)
    path = _write(tmp_path, corpus)
    for batch in reader.iter_batches(path, 4, 256):
        for i in range(4):
            ln = int(batch.lengths[i])
            if ln == 0 or int(batch.base_offsets[i]) + ln >= len(corpus):
                continue  # empty row or end of file
            assert int(batch.data[i, ln - 1]) in SEPS, "row must end at a separator"


def test_batches_cover_file_exactly(tmp_path, rng):
    corpus = make_corpus(rng, 2000, 90)
    path = _write(tmp_path, corpus)
    reconstructed = bytearray()
    for batch in reader.iter_batches(path, 3, 128):
        for i in range(3):
            ln = int(batch.lengths[i])
            assert int(batch.base_offsets[i]) == len(reconstructed)
            reconstructed += bytes(batch.data[i, :ln])
    assert bytes(reconstructed) == corpus


def test_no_token_split_across_rows(tmp_path, rng):
    corpus = make_corpus(rng, 4000, 150)
    path = _write(tmp_path, corpus)
    words_streamed = []
    for batch in reader.iter_batches(path, 5, 192):
        for i in range(5):
            ln = int(batch.lengths[i])
            words_streamed.extend(oracle.split_words(bytes(batch.data[i, :ln])))
    assert words_streamed == oracle.split_words(corpus)


def test_force_split_monster_token(tmp_path):
    """A token longer than max_token_bytes is split, not a stall/overflow
    (the reference would smash its 20-byte stack buffer, main.cu:184)."""
    data = b"a" * 10_000 + b" end"
    path = _write(tmp_path, data)
    batches = list(reader.iter_batches(path, 2, 512, max_token_bytes=256))
    total = sum(int(b.lengths.sum()) for b in batches)
    assert total == len(data)


def test_empty_file(tmp_path):
    path = _write(tmp_path, b"")
    assert list(reader.iter_batches(path, 4, 128)) == []


def test_resume_cursor(tmp_path, rng):
    corpus = make_corpus(rng, 1000, 50)
    path = _write(tmp_path, corpus)
    full = list(reader.iter_batches(path, 2, 128))
    # Stop after 2 steps, resume from the reported cursor.
    consumed = sum(int(b.lengths.sum()) for b in full[:2])
    resumed = list(reader.iter_batches(path, 2, 128, start_offset=consumed, start_step=2))
    assert [b.step for b in resumed] == [b.step for b in full[2:]]
    for a, b in zip(resumed, full[2:]):
        np.testing.assert_array_equal(a.data, b.data)
        np.testing.assert_array_equal(a.base_offsets, b.base_offsets)


def test_read_words_at(tmp_path):
    path = _write(tmp_path, b"alpha beta gamma")
    assert reader.read_words_at(path, [(0, 5), (6, 4), (11, 5)]) == \
        [b"alpha", b"beta", b"gamma"]


def test_prefetch_preserves_stream(tmp_path, rng):
    """prefetch() must yield exactly the same batches, in order."""

    corpus = make_corpus(rng, n_words=2000, vocab=100)
    path = tmp_path / "c.txt"
    path.write_bytes(corpus)
    direct = list(reader.iter_batches(str(path), 2, 512))
    fetched = list(reader.prefetch(reader.iter_batches(str(path), 2, 512)))
    assert len(direct) == len(fetched) and len(direct) > 2
    for a, b in zip(direct, fetched):
        np.testing.assert_array_equal(a.data, b.data)
        np.testing.assert_array_equal(a.base_offsets, b.base_offsets)
        assert a.step == b.step


def test_prefetch_propagates_producer_errors():
    def gen():
        raise RuntimeError("disk on fire")
        yield  # pragma: no cover

    with pytest.raises(RuntimeError, match="disk on fire"):
        list(reader.prefetch(gen()))


def test_prefetch_abandoned_consumer_stops_producer(tmp_path, rng):
    """Dropping the generator early must release the producer thread."""
    import threading
    import time

    corpus = make_corpus(rng, n_words=5000, vocab=100)
    path = tmp_path / "c.txt"
    path.write_bytes(corpus)
    gen = reader.prefetch(reader.iter_batches(str(path), 2, 256), depth=1)
    next(gen)
    gen.close()  # consumer abandons mid-stream
    deadline = time.time() + 5
    while time.time() < deadline:
        if not any(t.name == "ingest-prefetch" and t.is_alive()
                   for t in threading.enumerate()):
            break
        time.sleep(0.05)
    assert not any(t.name == "ingest-prefetch" and t.is_alive()
                   for t in threading.enumerate())


def _write_files(tmp_path, blobs):
    paths = []
    for i, blob in enumerate(blobs):
        p = tmp_path / f"part{i}.txt"
        p.write_bytes(blob)
        paths.append(str(p))
    return paths


def test_multi_file_batches_cover_all_files(tmp_path, rng):
    from tests.conftest import make_corpus

    blobs = [make_corpus(rng, n_words=300, vocab=50) for _ in range(3)]
    blobs[1] = blobs[1].rstrip() + b"tail-no-newline"  # no trailing separator
    paths = _write_files(tmp_path, blobs)
    total = 0
    seen_bytes = bytearray()
    for b in reader.iter_batches_multi(paths, 2, 256):
        for row, base, ln in zip(b.data, b.base_offsets, b.lengths):
            total += int(ln)
            seen_bytes.extend(row[: int(ln)])
    assert total == sum(len(b) for b in blobs)
    assert bytes(seen_bytes) == b"".join(blobs)


def test_multi_file_virtual_offsets_recover_words(tmp_path):
    paths = _write_files(tmp_path, [b"alpha beta\n", b"gamma delta\n"])
    # virtual offsets: gamma starts at 11 (after file 0's 11 bytes)
    assert reader.read_words_at_multi(paths, [(0, 5), (11, 5), (17, 5)]) == \
        [b"alpha", b"gamma", b"delta"]


def test_multi_file_no_token_merge_at_file_boundary(tmp_path):
    """'abc' at end of file 0 and 'def' at start of file 1 stay two tokens."""
    paths = _write_files(tmp_path, [b"x abc", b"def y\n"])
    got = {}
    for b in reader.iter_batches_multi(paths, 1, 128):
        for row, ln in zip(b.data, b.lengths):
            for w in bytes(row[: int(ln)]).split():
                got[w] = got.get(w, 0) + 1
    assert got == {b"x": 1, b"abc": 1, b"def": 1, b"y": 1}


def test_multi_file_start_and_end_offsets(tmp_path):
    paths = _write_files(tmp_path, [b"aa bb \n", b"cc dd \n"])
    words = []
    for b in reader.iter_batches_multi(paths, 1, 128, start_offset=3,
                                       end_offset=10):
        for row, ln in zip(b.data, b.lengths):
            words += bytes(row[: int(ln)]).split()
    assert words == [b"bb", b"cc"]


def _memmap_slices(paths, spans):
    """The per-span reading that :func:`reader.read_spans` replaced: one
    ``np.memmap`` slice copied to ``bytes`` per span (reference)."""
    starts = np.cumsum([0] + [len(open(p, "rb").read()) for p in paths])
    out = []
    for off, ln in spans:
        k = int(np.searchsorted(starts, off, side="right")) - 1
        mm = np.memmap(paths[k], dtype=np.uint8, mode="r")
        local = off - int(starts[k])
        out.append(bytes(mm[local: local + ln]))
    return out


_SPAN_CASES = {
    "one_file": ([b"alpha beta gamma\n"], [(0, 5), (6, 4), (11, 5)]),
    "three_files_out_of_order": (
        [b"alpha beta\n", b"gamma delta\n", b"eps zeta\n"],
        [(28, 4), (0, 5), (17, 5), (23, 3), (6, 4), (11, 5)]),
    "zero_byte_file_between": (
        [b"alpha beta\n", b"", b"gamma\n"], [(11, 5), (0, 5), (6, 4)]),
    "clipped_at_eof": ([b"alpha beta\n", b"gamma"], [(6, 40), (11, 9)]),
    "empty_span_list": ([b"alpha\n"], []),
    "zero_length_span": ([b"alpha beta\n", b"gamma\n"], [(6, 0), (11, 0)]),
}


@pytest.mark.parametrize("case", sorted(_SPAN_CASES))
def test_read_spans_matches_memmap_slicing(tmp_path, case):
    blobs, spans = _SPAN_CASES[case]
    paths = _write_files(tmp_path, blobs)
    want = _memmap_slices(paths, spans)
    offs = np.asarray([s[0] for s in spans], dtype=np.int64)
    lens = np.asarray([s[1] for s in spans], dtype=np.int64)
    got = reader.read_spans(paths, offs, lens)
    assert got == want
    assert all(type(w) is bytes for w in got)
    assert reader.read_words_at_multi(paths, spans) == want
    if len(paths) == 1:
        assert reader.read_words_at(paths[0], spans) == want


def test_read_spans_zero_byte_file_alone(tmp_path):
    """A zero-byte file is never mapped (``mmap`` refuses one): its spans
    read empty."""
    path = _write(tmp_path, b"")
    assert reader.read_spans(path, np.array([0, 0]), np.array([3, 0])) == \
        [b"", b""]
    assert reader.read_words_at(path, [(0, 4)]) == [b""]


def test_slice_spans_returns_bytes_of_a_buffer():
    got = reader.slice_spans(b"alpha beta", np.array([6, 0, 8]),
                             np.array([4, 5, 9]))
    assert got == [b"beta", b"alpha", b"ta"]
    assert all(type(w) is bytes for w in got)
