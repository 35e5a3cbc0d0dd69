"""Boundary-aligned sharded ingest.

Replaces the reference's host input pipeline — ``fopen``/``fgets`` with a
100-byte line buffer and per-char splitting (``main.cu:166-207``) — with a
memory-mapped, token-boundary-aligned chunker: each streaming step yields a
``uint8[n_shards, chunk_bytes]`` batch (one row per device) plus the absolute
file offset of every row, so device-side token positions can be mapped back to
exact byte ranges for string recovery.

Alignment rule: a row may only end at a separator byte, so no token ever spans
two rows and no cross-chunk fix-up exchange is needed (SURVEY §7 "hard parts":
the seam problem is solved at ingest, where the bytes already are, instead of
with a device-side halo exchange).  Tokens longer than ``max_token_bytes`` are
force-split (and counted as two tokens) rather than stalling the pipeline; the
reference would overflow a stack buffer in that case (``main.cu:184,199``).
"""

from __future__ import annotations

import dataclasses
import mmap
import os
from typing import Iterator

import numpy as np

from mapreduce_tpu import constants

_SEP_LUT = np.zeros(256, dtype=bool)
for _b in constants.SEPARATOR_BYTES:
    _SEP_LUT[_b] = True


@dataclasses.dataclass(frozen=True)
class Batch:
    """One streaming step's input across all shards."""

    data: np.ndarray  # uint8[n_shards, chunk_bytes], zero-padded rows
    base_offsets: np.ndarray  # int64[n_shards], absolute file offset of row starts
    lengths: np.ndarray  # int64[n_shards], valid bytes per row
    step: int
    file_index: int = 0  # which corpus member this batch came from: a batch
    # never spans files, so jobs with cross-row state (grep's line carry) can
    # reset at the hard file boundary


def _aligned_cuts(buf: np.ndarray, n_shards: int, chunk_bytes: int,
                  max_token_bytes: int, at_eof: bool) -> list[int]:
    """Cut points (ascending, len n_shards) so every row ends at a separator
    (or at a force-split after max_token_bytes of unbroken non-separators).
    Only the file's true end (``at_eof``) may cut mid-buffer unaligned — a
    buffer end mid-file is a carry point, not a token boundary."""
    is_sep = _SEP_LUT[buf]
    cuts = []
    prev = 0
    n = buf.shape[0]
    for i in range(n_shards):
        ideal = min(prev + chunk_bytes, n)
        if ideal >= n and at_eof:
            cuts.append(n)
            prev = n
            continue
        lo = max(prev, ideal - max_token_bytes)
        window = is_sep[lo:ideal]
        hits = np.flatnonzero(window)
        # Cut just after the last separator in the window; if the window is
        # all token bytes, force-split at the ideal point.
        cut = lo + int(hits[-1]) + 1 if hits.size else ideal
        cuts.append(cut)
        prev = cut
    return cuts


def iter_batches(path: str, n_shards: int, chunk_bytes: int,
                 max_token_bytes: int = 4096, start_offset: int = 0,
                 start_step: int = 0, use_native: bool = True,
                 end_offset: int | None = None) -> Iterator[Batch]:
    """Stream a file as boundary-aligned [n_shards, chunk_bytes] batches.

    ``start_offset``/``start_step`` support checkpoint resume: iteration
    continues from a previously reported cursor.  ``end_offset`` bounds the
    stream to the half-open byte range ``[start_offset, end_offset)`` — the
    multi-host case, where each host reads only its own
    :func:`...parallel.distributed.host_byte_range` (pre-aligned via
    ``align_range_to_separator``, so the range end IS a token boundary and
    the usual EOF alignment rule applies at it).  The batch fill runs in the
    native chunker (:mod:`mapreduce_tpu.native`) when available, falling back
    to the pure-numpy path; both produce byte-identical batches
    (tests/test_native.py asserts parity).
    """
    from mapreduce_tpu import native

    mm = np.memmap(path, dtype=np.uint8, mode="r") if _file_size(path) else None
    total = 0 if mm is None else mm.shape[0]
    if end_offset is not None:
        total = min(total, end_offset)
    offset = start_offset
    step = start_step
    stride = n_shards * chunk_bytes
    while offset < total:
        raw = np.asarray(mm[offset: min(offset + stride, total)])
        at_eof = offset + raw.shape[0] >= total
        data = np.empty((n_shards, chunk_bytes), dtype=np.uint8)
        bases = np.empty((n_shards,), dtype=np.int64)
        lengths = np.empty((n_shards,), dtype=np.int64)
        consumed = None
        if use_native:
            consumed = native.fill_batch(raw, at_eof, n_shards, chunk_bytes,
                                         max_token_bytes, data.reshape(-1),
                                         bases, lengths)
        if consumed is None:
            data[:] = 0
            cuts = _aligned_cuts(raw, n_shards, chunk_bytes, max_token_bytes,
                                 at_eof=at_eof)
            prev = 0
            for i, cut in enumerate(cuts):
                row = raw[prev:cut]
                data[i, : row.shape[0]] = row
                bases[i] = prev
                lengths[i] = row.shape[0]
                prev = cut
            consumed = cuts[-1]
        bases += offset
        yield Batch(data=data, base_offsets=bases, lengths=lengths, step=step)
        if consumed == 0:  # defensive: cannot happen (first cut >= 1 byte)
            raise RuntimeError("ingest made no progress")
        offset += consumed
        step += 1


def iter_batches_multi(paths, n_shards: int, chunk_bytes: int,
                       max_token_bytes: int = 4096, start_offset: int = 0,
                       start_step: int = 0, use_native: bool = True,
                       end_offset: int | None = None) -> Iterator[Batch]:
    """Stream a MULTI-FILE corpus (real corpora — e.g. Common Crawl WET
    shards, BASELINE.md — are many files) as one logical byte stream.

    Offsets (``start_offset``/``end_offset``/``Batch.base_offsets``) are
    *virtual*: positions in the concatenation of the files in order.  Files
    are chunked independently — a file's end is a hard token boundary, so no
    token ever spans two files and no join bytes are inserted.  Step
    numbering continues across files (chunk ids stay globally unique).
    """
    if isinstance(paths, (str, bytes, os.PathLike)):
        paths = [paths]
    sizes = [_file_size(p) for p in paths]
    step = start_step
    file_start = 0
    for fi, (path, size) in enumerate(zip(paths, sizes)):
        file_end = file_start + size
        local_lo = max(0, start_offset - file_start)
        local_hi = size if end_offset is None \
            else min(size, max(0, end_offset - file_start))
        if local_lo < local_hi:
            for b in iter_batches(path, n_shards, chunk_bytes,
                                  max_token_bytes=max_token_bytes,
                                  start_offset=local_lo, start_step=step,
                                  end_offset=local_hi, use_native=use_native):
                yield Batch(data=b.data,
                            base_offsets=b.base_offsets + file_start,
                            lengths=b.lengths, step=b.step, file_index=fi)
                step = b.step + 1
        file_start = file_end


def read_words_at_multi(paths, spans: list[tuple[int, int]]) -> list[bytes]:
    """Multi-file :func:`read_words_at`: spans use virtual corpus offsets."""
    if not spans:
        return []
    offs, lens = np.asarray(spans, dtype=np.int64).T
    return read_spans(paths, offs, lens)


def prefetch(batches: Iterator[Batch], depth: int = 2) -> Iterator[Batch]:
    """Run an iterator in a background thread, ``depth`` items ahead.

    Double-buffers ingest against device compute (SURVEY §7 step 4): while
    the devices chew on step N, the host memmap-reads and boundary-aligns
    step N+1.  The producer thread is daemonic and bounded by a queue, so an
    abandoned consumer cannot leak unbounded memory; producer exceptions are
    re-raised at the consumer's next pull.

    Wait accounting (ISSUE 2): the producer records per-batch production
    time and time spent blocked on a FULL queue into the process metrics
    registry.  Together with the executor's ``read_wait`` phase (consumer
    blocked on an EMPTY queue) this classifies the pipeline — large
    ``read_wait`` = reader-bound, large ``stall_full_queue`` = producer
    comfortably ahead (device-bound).  Host-side dict updates only.
    """
    import queue
    import threading
    import time as _time

    from mapreduce_tpu.obs import registry as _obs_registry

    reg = _obs_registry.get_registry()
    # The configured depth is part of the pipeline telemetry (ISSUE 5):
    # read_wait with a deep queue means the producer itself is the floor,
    # with a shallow one it may just be the queue size.
    reg.gauge("reader.prefetch_depth").set(depth)
    q: queue.Queue = queue.Queue(maxsize=depth)
    stop = threading.Event()
    _END, _ERR = object(), object()

    def put(item) -> bool:
        """Bounded put that gives up when the consumer is gone."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def produce() -> None:
        try:
            t_prev = _time.perf_counter()
            for b in batches:
                t_ready = _time.perf_counter()
                reg.observe("reader.produce_seconds", t_ready - t_prev)
                reg.counter("reader.batches_prefetched").inc()
                if not put(b):
                    return  # consumer abandoned the stream
                t_prev = _time.perf_counter()
                # put() returned: anything beyond the enqueue itself was
                # blocking on a full queue — the producer running ahead.
                reg.counter("reader.stall_full_queue_seconds").inc(
                    t_prev - t_ready)
            put(_END)
        except BaseException as e:  # surfaced on the consumer side
            put((_ERR, e))

    t = threading.Thread(target=produce, daemon=True, name="ingest-prefetch")
    t.start()
    try:
        while True:
            item = q.get()
            if item is _END:
                return
            if isinstance(item, tuple) and len(item) == 2 and item[0] is _ERR:
                raise item[1]
            yield item
    finally:
        # Early exit (consumer error/close): release the producer so it does
        # not sit blocked on a full queue holding batches and the memmap.
        stop.set()


def _file_size(path: str) -> int:
    return os.path.getsize(path)


def read_words_at(path: str, spans: list[tuple[int, int]]) -> list[bytes]:
    """Host-side string recovery: exact bytes for (absolute_offset, length)."""
    return read_words_at_multi(path, spans)


def slice_spans(buf, offsets: np.ndarray, lengths: np.ndarray) -> list[bytes]:
    """``bytes(buf[o:o + n])`` for each span, in order.  ``buf`` is anything
    whose slice is ``bytes`` (``bytes``, ``mmap.mmap``); the bounds go to
    Python ints in two ``tolist`` calls, so no numpy object is made per span.
    Python slice semantics: a span past the end is clipped there."""
    starts = np.asarray(offsets, dtype=np.int64)
    ends = starts + np.asarray(lengths, dtype=np.int64)
    return [buf[a:b] for a, b in zip(starts.tolist(), ends.tolist())]


def read_spans(paths, offsets: np.ndarray,
               lengths: np.ndarray) -> list[bytes]:
    """Exact bytes of each ``(offsets[i], lengths[i])`` span, in input order.

    ``paths`` is one file or a list read as one virtual corpus (offsets run
    on across files).  A span is read from the file its offset falls in and
    is clipped at that file's end.  Spans are grouped by file with one
    argsort, and each touched file is mapped once; a zero-byte file is never
    mapped (``mmap`` refuses one): its spans are empty.
    """
    offs = np.asarray(offsets, dtype=np.int64)
    lens = np.asarray(lengths, dtype=np.int64)
    if offs.shape[0] == 0:
        return []
    plist = [paths] if isinstance(paths, (str, bytes, os.PathLike)) \
        else list(paths)
    sizes = [_file_size(p) for p in plist]
    starts = np.cumsum([0] + sizes)
    file_idx = np.clip(np.searchsorted(starts, offs, side="right") - 1,
                       0, len(plist) - 1)
    order = np.argsort(file_idx, kind="stable")
    cuts = np.flatnonzero(np.diff(file_idx[order])) + 1
    words: list[bytes] = []
    for group in np.split(order, cuts):
        k = int(file_idx[group[0]])
        local = offs[group] - starts[k]
        if sizes[k] == 0:
            words.extend([b""] * len(group))
            continue
        with open(plist[k], "rb") as f, \
                mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ) as mm:
            words.extend(slice_spans(mm, local, lens[group]))
    if np.any(np.diff(order) < 0):
        # Spans were given out of file order: put them back in input order.
        inverse = np.empty_like(order)
        inverse[order] = np.arange(len(order))
        words = [words[i] for i in inverse.tolist()]
    return words


def scan_gram_lengths_bytes(source: bytes | np.ndarray, offsets,
                            n: int) -> list[int]:
    """In-memory :func:`scan_gram_lengths`: spans of the n-entry grams
    starting at ``offsets`` of one whole buffer (no chunk cuts — the
    single-buffer paths never force-split).  Used by
    ``models.wordcount.recover_result`` for long-span gram entries
    (length = ``SEAM_GRAM_LENGTH``, the >= 127-byte spans the packed gram
    build cannot store).  One vectorized pass over the buffer however many
    offsets."""
    arr = np.frombuffer(source, dtype=np.uint8) if isinstance(source, bytes) \
        else np.asarray(source, dtype=np.uint8)
    if arr.shape[0] == 0:
        return [0 for _ in offsets]
    sep = _SEP_LUT[arr]
    nxt = np.concatenate([sep[1:], np.array([True])])
    epos = np.flatnonzero(~sep & nxt)  # entry end positions (inclusive)
    offs = np.asarray(list(offsets), dtype=np.int64)
    if len(epos) == 0:  # all-separator buffer: spans run to the end
        return [int(arr.shape[0] - o) for o in offs]
    # A gram that exists has n entry ends at/after its start; if the
    # buffer ends mid-stream the remaining bytes are the span.
    j = np.searchsorted(epos, offs) + n - 1
    in_range = j < len(epos)
    ends = np.where(in_range, epos[np.minimum(j, len(epos) - 1)] + 1,
                    arr.shape[0])
    return [int(e - o) for e, o in zip(ends, offs)]


def scan_gram_lengths(paths, offsets, n: int,
                      cut_offsets=None) -> list[int]:
    """Byte lengths of the n-entry grams starting at virtual corpus offsets.

    Host-side recovery for cross-chunk gram entries (length =
    ``SEAM_GRAM_LENGTH``): the device knows each gram's absolute start but
    not its end (it lies in a later chunk whose row base only the host
    tracks), so the host scans forward from the start — which must be an
    entry start — to the end of the n-th stream entry.  Separator runs
    between tokens are unbounded, so the read window doubles until the gram
    completes (or the file ends: the remaining bytes are the span).  Grams
    never cross file boundaries (the executor resets the seam carry there),
    so each scan stays within the file containing its offset.

    ``cut_offsets``: absolute chunk-row base offsets of the run.  The
    reader force-splits a separator-free run longer than its alignment
    window at a row cut, making BOTH halves stream entries — so a cut
    inside a run is an entry end too, and the scan counts it to match the
    device's entry stream (without this, a seam span over a force-split
    run would swallow the whole run plus the following real token).

    Batch API: one file-size pass + one memmap per touched file, however
    many offsets (a full table of seam entries is recovered in one call).
    """
    single = isinstance(paths, (str, bytes, os.PathLike))
    plist = [paths] if single else list(paths)
    starts = np.cumsum([0] + [_file_size(p) for p in plist])
    cuts = np.sort(np.asarray(cut_offsets, dtype=np.int64)) \
        if cut_offsets is not None else np.empty(0, np.int64)
    offs = np.asarray(list(offsets), dtype=np.int64)
    file_idx = np.searchsorted(starts, offs, side="right") - 1
    mms: dict[int, np.memmap] = {}
    out: list[int] = []
    for j, off in enumerate(offs):
        k = int(file_idx[j])
        if k not in mms:
            mms[k] = np.memmap(plist[k], dtype=np.uint8, mode="r")
        mm = mms[k]
        base, local, size = int(starts[k]), int(off - starts[k]), mm.shape[0]
        win = 4096
        while True:
            end = min(local + win, size)
            buf = np.asarray(mm[local:end])
            sep = _SEP_LUT[buf]
            at_eof = end >= size
            # Entry ends: non-separator followed by separator (or EOF)...
            nxt = np.concatenate([sep[1:], np.array([True])]) if at_eof \
                else sep[1:]
            ends = ~sep[: len(nxt)] & nxt
            # ...plus force-split ends: a chunk cut at absolute c ends the
            # entry at byte c-1 when that byte is a non-separator (if the
            # following byte is a separator this is already an end).
            lo_v = base + local
            ci = cuts[(cuts > lo_v) & (cuts <= lo_v + len(nxt))] - lo_v - 1
            if len(ci):
                ends[ci[~sep[ci]]] = True
            epos = np.flatnonzero(ends)
            if len(epos) >= n:
                out.append(int(epos[n - 1]) + 1)
                break
            if at_eof:  # corpus ends mid-gram: remaining bytes are the span
                out.append(int(len(buf)))
                break
            win *= 2
    return out
