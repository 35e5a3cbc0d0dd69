"""Distributed grep: device counts vs. a pure-Python oracle.

Oracle semantics (module docstring of :mod:`mapreduce_tpu.models.grep`):
overlapping occurrences; matching lines = lines containing >= 1 occurrence.
"""

import numpy as np
import pytest

from mapreduce_tpu.config import Config
from mapreduce_tpu.models import grep


def occurrences(data: bytes, pat: bytes) -> int:
    return sum(1 for i in range(len(data) - len(pat) + 1)
               if data[i: i + len(pat)] == pat)


def matching_lines(data: bytes, pat: bytes) -> int:
    return sum(1 for line in data.split(b"\n") if pat in line)


def test_overlapping_occurrences():
    r = grep.grep_bytes(b"aaaa\n", b"aa")
    assert r.matches == 3  # overlapping, unlike bytes.count's 2
    assert r.lines == 1


@pytest.mark.parametrize("pat", [b"w1", b"w23", b"w1 w", b"zqx"])
def test_matches_oracle(small_corpus, pat):
    r = grep.grep_bytes(small_corpus, pat)
    assert r.matches == occurrences(small_corpus, pat)
    # Patterns without newline: matching-lines oracle applies exactly.
    assert r.lines == matching_lines(small_corpus, pat)


def test_multiple_matches_one_line_count_once():
    r = grep.grep_bytes(b"x y x y x\nplain\nx\n", b"x")
    assert r.matches == 4
    assert r.lines == 2


def test_empty_and_oversized_pattern_rejected():
    with pytest.raises(ValueError):
        grep.GrepJob(b"")
    with pytest.raises(ValueError):
        grep.GrepJob(b"a" * 257)


def test_pattern_longer_than_data():
    r = grep.grep_bytes(b"hi\n", b"this-pattern-is-longer-than-the-data")
    assert r.matches == 0 and r.lines == 0


def test_streamed_grep_matches_oracle(tmp_path, small_corpus):
    path = tmp_path / "c.txt"
    path.write_bytes(small_corpus)
    cfg = Config(chunk_bytes=1024)
    r = grep.grep_file(str(path), b"w1", config=cfg)
    # Separator-free patterns cannot span the separator-aligned chunk seams:
    # occurrence counts are exact under sharding.
    assert r.matches == occurrences(small_corpus, b"w1")
    # Lines are exact even when logical lines split across rows: the per-step
    # summary all_gather + carry chain dedups continuation segments.
    assert r.lines == matching_lines(small_corpus, b"w1")


def test_streamed_grep_line_split_across_rows_exact(tmp_path):
    """round-1 review #9 'done' case: a matching line whose segments land in
    different chunk rows (and different steps) must count once."""
    # Lines far longer than chunk_bytes, separated by spaces so the reader
    # cuts mid-line at separator boundaries; matches in several segments.
    line1 = b"MATCH " + b"x " * 150 + b"MATCH " + b"y " * 150 + b"MATCH"
    line2 = b"z " * 200  # no match
    line3 = b"a " * 100 + b"MATCH " + b"b " * 250  # match mid-line
    corpus = line1 + b"\n" + line2 + b"\n" + line3 + b"\n"
    path = tmp_path / "long.txt"
    path.write_bytes(corpus)
    for chunk_bytes in (128, 256, 512):
        cfg = Config(chunk_bytes=chunk_bytes)
        r = grep.grep_file(str(path), b"MATCH", config=cfg)
        assert r.matches == occurrences(corpus, b"MATCH"), chunk_bytes
        assert r.lines == matching_lines(corpus, b"MATCH") == 2, chunk_bytes


def test_streamed_grep_transparent_middle_rows_exact(tmp_path):
    """A line spanning 3+ rows with an unmatched middle row: the transparent
    (newline-free, matchless) row must pass the carry through unchanged."""
    corpus = (b"MATCH " + b"q " * 800 + b"MATCH\n" +  # one line, many rows
              b"plain line\n")
    path = tmp_path / "t.txt"
    path.write_bytes(corpus)
    cfg = Config(chunk_bytes=128)
    r = grep.grep_file(str(path), b"MATCH", config=cfg)
    assert r.matches == 2
    assert r.lines == 1


@pytest.mark.slow
def test_streamed_grep_lines_exact_fuzz(tmp_path, rng):
    """Randomized cross-check of the exact-lines carry chain against the
    pure-Python oracle under many row geometries."""
    words = [b"MATCH", b"aa", b"b", b"ccc dd", b"ee\nff", b"\n", b"gg hh ii"]
    for trial in range(6):
        parts = [words[int(i)] for i in rng.integers(0, len(words), size=600)]
        corpus = b" ".join(parts) + b"\n"
        path = tmp_path / f"f{trial}.txt"
        path.write_bytes(corpus)
        cfg = Config(chunk_bytes=128 * int(rng.integers(1, 4)))
        r = grep.grep_file(str(path), b"MATCH", config=cfg)
        assert r.matches == occurrences(corpus, b"MATCH")
        assert r.lines == matching_lines(corpus, b"MATCH"), \
            (trial, cfg.chunk_bytes)


def test_64bit_carry_accumulation():
    """The lo/hi carry math is exact where a uint32 would wrap."""
    import jax.numpy as jnp
    import numpy as np

    job = grep.GrepJob(b"x")
    near = jnp.uint32(0xFFFFFFF0)
    state = grep.GrepState(near, jnp.uint32(0), near, jnp.uint32(0))
    other = grep.GrepState(jnp.uint32(0x20), jnp.uint32(0),
                           jnp.uint32(0x20), jnp.uint32(0))
    merged = job.merge(state, other)
    result = grep._state_result(b"x", merged)
    assert result.matches == 0xFFFFFFF0 + 0x20  # > 2**32
    assert result.lines == 0xFFFFFFF0 + 0x20


@pytest.mark.slow
def test_grep_cli(tmp_path, capsys):
    from mapreduce_tpu import cli

    path = tmp_path / "c.txt"
    path.write_bytes(b"the cat\nthe dog\nno match\n")
    assert cli.main([str(path), "--grep", "the"]) == 0
    out = capsys.readouterr().out
    assert "Matches:2\nMatching Lines:2\n" in out
    assert cli.main([str(path), "--grep", "the", "--format", "json"]) == 0
    assert '"matches": 2' in capsys.readouterr().out
    assert cli.main([str(path), "--grep", "the", "--stream",
                     "--format", "tsv"]) == 0
    assert "matches\t2" in capsys.readouterr().out


def test_grep_checkpoint_resume(tmp_path, small_corpus):
    """Grep's scalar state rides the generic pytree snapshot format."""
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime import checkpoint as ckpt

    path = tmp_path / "c.txt"
    path.write_bytes(small_corpus)
    cfg = Config(chunk_bytes=1024)
    ck = str(tmp_path / "grep.npz")
    full = grep.grep_file(str(path), b"w1", config=cfg, mesh=data_mesh(2))
    r1 = grep.grep_file(str(path), b"w1", config=cfg, mesh=data_mesh(2),
                        checkpoint_path=ck, checkpoint_every=1)
    assert ckpt.exists(ck)
    r2 = grep.grep_file(str(path), b"w1", config=cfg, mesh=data_mesh(2),
                        checkpoint_path=ck, checkpoint_every=1)
    assert r1.matches == r2.matches == full.matches
    assert r1.lines == r2.lines == full.lines

    # A word-count run must refuse grep's snapshot (different structure).
    import pytest
    from mapreduce_tpu.runtime import executor

    with pytest.raises(ckpt.CheckpointMismatch):
        executor.count_file(str(path), config=cfg, mesh=data_mesh(2),
                            checkpoint_path=ck, checkpoint_every=1)


def test_grep_checkpoint_pattern_mismatch(tmp_path, small_corpus):
    """Same state SHAPE, different pattern: the job identity in the
    fingerprint refuses the resume (the review's silent-corruption case)."""
    import pytest
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime import checkpoint as ckpt

    path = tmp_path / "c.txt"
    path.write_bytes(small_corpus)
    cfg = Config(chunk_bytes=1024)
    ck = str(tmp_path / "grep.npz")
    grep.grep_file(str(path), b"w1", config=cfg, mesh=data_mesh(2),
                   checkpoint_path=ck, checkpoint_every=1)
    with pytest.raises(ckpt.CheckpointMismatch, match="job"):
        grep.grep_file(str(path), b"w2", config=cfg, mesh=data_mesh(2),
                       checkpoint_path=ck, checkpoint_every=1)


def test_grep_exact_lines_2d_mesh(tmp_path):
    """The seam-correction all_gather must order rows identically on a 2-D
    ('replica','data') mesh (row-major over the axes, matching
    Engine._device_index) — exactness would break if gather order and row
    order diverged."""
    import jax

    from mapreduce_tpu.data import reader
    from mapreduce_tpu.parallel.mapreduce import Engine
    from mapreduce_tpu.parallel.mesh import two_level_mesh

    line = b"MATCH " + b"w " * 600 + b"MATCH"  # spans many 128-byte rows
    corpus = line + b"\nplain\nMATCH line\n"
    path = tmp_path / "m.txt"
    path.write_bytes(corpus)

    eng = Engine(grep.GrepJob(b"MATCH"), two_level_mesh(2, 4),
                 axis=("replica", "data"))
    state = eng.init_states()
    for b in reader.iter_batches(str(path), 8, 128):
        state = eng.step(state, b.data, b.step)
    r = grep._state_result(b"MATCH", eng.finish(state))
    assert r.matches == occurrences(corpus, b"MATCH")
    assert r.lines == matching_lines(corpus, b"MATCH") == 2


def test_streamed_multi_file_grep_no_carry_leak(tmp_path):
    """Files are independent corpora: the open-line carry from a file with
    no trailing newline must not suppress (or join) the next file's first
    line.  Streamed and non-stream per-file semantics must agree."""
    a, b = tmp_path / "a.txt", tmp_path / "b.txt"
    a.write_bytes(b"x MATCH")  # no trailing newline: line stays open at EOF
    b.write_bytes(b"MATCH y\n")
    r = grep.grep_file([str(a), str(b)], b"MATCH",
                       config=Config(chunk_bytes=128))
    assert r.matches == 2
    assert r.lines == 2  # one matching line in each file
    # And with a multi-row continuation before the boundary.
    c = tmp_path / "c.txt"
    c.write_bytes(b"MATCH " + b"q " * 200)  # open line spanning rows, no \n
    r2 = grep.grep_file([str(c), str(b)], b"MATCH",
                        config=Config(chunk_bytes=128))
    assert r2.matches == 2
    assert r2.lines == 2


@pytest.mark.slow
def test_multi_pattern_grep_matches_singles(tmp_path, small_corpus):
    """MultiGrepJob: P patterns in one pass must equal P single runs."""
    pats = [b"w1", b"w23", b"zqx", b"w1 w"]
    multi = grep.grep_bytes_multi(small_corpus, pats)
    for p, r in zip(pats, multi):
        single = grep.grep_bytes(small_corpus, p)
        assert (r.matches, r.lines) == (single.matches, single.lines), p

    path = tmp_path / "c.txt"
    path.write_bytes(small_corpus)
    cfg = Config(chunk_bytes=1024)
    streamed = grep.grep_file_multi(str(path), pats, config=cfg)
    for p, r in zip(pats, streamed):
        single = grep.grep_file(str(path), p, config=cfg)
        assert (r.matches, r.lines) == (single.matches, single.lines), p


def test_multi_pattern_grep_exact_lines_across_rows(tmp_path):
    """The [P]-shaped carry chain stays exact per pattern when lines span
    rows (each pattern has its own open-line bit)."""
    corpus = (b"AAA " + b"x " * 100 + b"BBB\n" +  # one long line: AAA & BBB
              b"BBB solo\n" + b"q " * 200 + b"\n")
    path = tmp_path / "m.txt"
    path.write_bytes(corpus)
    rs = grep.grep_file_multi(str(path), [b"AAA", b"BBB", b"q"],
                              config=Config(chunk_bytes=128))
    assert (rs[0].matches, rs[0].lines) == (1, 1)
    assert (rs[1].matches, rs[1].lines) == (2, 2)
    assert rs[2].lines == 1  # all q's on one (newline-terminated) line


def test_multi_grep_checkpoint_identity(tmp_path, small_corpus):
    """Different pattern SETS share state shapes only if P matches; the job
    identity must still refuse cross-resume."""
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime import checkpoint as ckpt

    path = tmp_path / "c.txt"
    path.write_bytes(small_corpus)
    cfg = Config(chunk_bytes=1024)
    ck = str(tmp_path / "g.npz")
    grep.grep_file_multi(str(path), [b"w1", b"w2"], config=cfg,
                         mesh=data_mesh(2), checkpoint_path=ck,
                         checkpoint_every=1)
    with pytest.raises(ckpt.CheckpointMismatch, match="job"):
        grep.grep_file_multi(str(path), [b"w1", b"w3"], config=cfg,
                             mesh=data_mesh(2), checkpoint_path=ck,
                             checkpoint_every=1)


def test_multi_grep_cli(tmp_path, capsys):
    from mapreduce_tpu import cli

    path = tmp_path / "c.txt"
    path.write_bytes(b"the cat sat\nthe dog\nno match here\n")
    assert cli.main([str(path), "--grep", "the", "--grep", "cat"]) == 0
    out = capsys.readouterr().out
    assert "Pattern:the\nMatches:2\nMatching Lines:2\n" in out
    assert "Pattern:cat\nMatches:1\nMatching Lines:1\n" in out
    assert cli.main([str(path), "--grep", "the", "--grep", "dog",
                     "--format", "json"]) == 0
    import json as _json

    obj = _json.loads(capsys.readouterr().out)
    assert obj["patterns"][1] == {"pattern": "dog", "matches": 1, "lines": 1}
    # Single-pattern output shape is unchanged.
    assert cli.main([str(path), "--grep", "the"]) == 0
    assert capsys.readouterr().out == "Matches:2\nMatching Lines:2\n"


# --- regex-lite byte classes (--grep-syntax class) -------------------------

def re_overlapping(data: bytes, regex: bytes) -> int:
    import re

    return sum(1 for _ in re.finditer(b"(?=" + regex + b")", data, re.DOTALL))


def re_matching_lines(data: bytes, regex: bytes) -> int:
    import re

    return sum(1 for line in data.split(b"\n")
               if re.search(regex, line, re.DOTALL))


@pytest.mark.parametrize("spec,regex", [
    (b"[0-9][0-9]", rb"[0-9][0-9]"),
    (b"w.x", rb"w[^\n\x00]x"),
    (b"[a-cx]1", rb"[a-cx]1"),
    (b"[^ 0-9]z", rb"[^ 0-9\x00]z"),
    (rb"a\.b", rb"a\.b"),
])
def test_class_patterns_match_re_oracle(spec, regex):
    data = (b"w1x w9x 42 73 a1 b1 c1 x1 d1 qz 9z\n"
            b"a.b a,b axb\nw\nx 10 99 [z] .z\n")
    r = grep.grep_bytes(data, spec, syntax="class")
    assert r.matches == re_overlapping(data, regex), spec
    assert r.lines == re_matching_lines(data, regex), spec


def test_class_pattern_overlapping_and_dotall():
    # '.' matches any byte except newline (and the NUL pad).
    r = grep.grep_bytes(b"aaa\naaa\n", b"a.a", syntax="class")
    assert r.matches == 2  # one per line; '.' never crosses the newline
    r2 = grep.grep_bytes(b"aaaa\n", b"a.a", syntax="class")
    assert r2.matches == 2  # overlapping starts at 0 and 1


def test_class_pattern_parse_errors():
    for bad in (b"[abc", b"[]x", b"a\\", b"[z-a]"):
        with pytest.raises(ValueError):
            grep.ClassPattern(bad)
    with pytest.raises(ValueError, match="NUL"):
        grep.ClassPattern(b"[\x00-\x05]")
    # Negated classes are fine: NUL stays excluded automatically.
    grep.ClassPattern(b"[^abc]")


def test_class_pattern_streamed_matches_single_buffer(tmp_path):
    corpus = (b"id42 and id73 overlap 1234 here\n" * 30
              + b"no digits on this line\n" * 10)
    path = tmp_path / "c.txt"
    path.write_bytes(corpus)
    single = grep.grep_bytes(corpus, b"[0-9][0-9]", syntax="class")
    streamed = grep.grep_file(str(path), b"[0-9][0-9]",
                              config=Config(chunk_bytes=128), syntax="class")
    assert (streamed.matches, streamed.lines) == (single.matches, single.lines)
    assert single.matches == re_overlapping(corpus, rb"[0-9][0-9]")
    assert single.lines == re_matching_lines(corpus, rb"[0-9][0-9]")


def test_class_pattern_multi_and_identity(tmp_path, small_corpus):
    """Class + multi compose; literal and class jobs for byte-identical
    specs have distinct checkpoint identities."""
    rs = grep.grep_bytes_multi(small_corpus, [b"w[0-9]", b"[a-z]1"],
                               syntax="class")
    assert rs[0].matches == re_overlapping(small_corpus, rb"w[0-9]")
    assert rs[1].matches == re_overlapping(small_corpus, rb"[a-z]1")
    lit = grep.GrepJob(b"w.x")  # literal dot: 3 exact bytes
    cls = grep.GrepJob(b"w.x", syntax="class")
    assert lit.identity() != cls.identity()


def test_class_pattern_cli(tmp_path, capsys):
    from mapreduce_tpu import cli

    path = tmp_path / "c.txt"
    path.write_bytes(b"ab1 cd2 xyz\nno digits\n")
    assert cli.main([str(path), "--grep", "[a-d][a-d][0-9]",
                     "--grep-syntax", "class", "--format", "json"]) == 0
    import json as _json

    obj = _json.loads(capsys.readouterr().out)
    assert obj["matches"] == 2 and obj["lines"] == 1
    # --grep-syntax without --grep is an honest error.
    with pytest.raises(SystemExit):
        cli.main([str(path), "--grep-syntax", "class"])


def test_grep_resume_across_file_seam_keeps_boundary_reset(tmp_path, monkeypatch):
    """Advisor round 2 (medium): the flush that ends a file checkpoints
    BEFORE the boundary hook resets the line carry, so the snapshot holds a
    set carry and sits exactly at the seam.  A resumed run must still fire
    on_input_boundary on the next file's first batch — without the persisted
    file index it silently never did, and the resumed count diverged from
    the uninterrupted one (lines=1 vs lines=2)."""
    from mapreduce_tpu.parallel import mapreduce as mr
    from mapreduce_tpu.parallel.mesh import data_mesh

    a, b = tmp_path / "a.txt", tmp_path / "b.txt"
    a.write_bytes(b"x MATCH")   # unterminated matching line: carry=1 at EOF
    b.write_bytes(b"MATCH y\n")
    cfg = Config(chunk_bytes=128)
    paths = [str(a), str(b)]
    mesh = data_mesh(2)

    full = grep.grep_file(paths, b"MATCH", config=cfg, mesh=mesh)
    assert (full.matches, full.lines) == (2, 2)

    # checkpoint_every=1 puts a snapshot right after file A's only step;
    # the injected crash hits file B's first step, so the resumed run
    # starts exactly at the seam with the pre-reset carry.
    ck = str(tmp_path / "ck.npz")
    original = mr.Engine.step
    fired = []

    def crash_at_seam(self, state, chunks, step_index):
        if step_index == 1 and not fired:
            fired.append(step_index)
            raise RuntimeError("injected crash at file seam")
        return original(self, state, chunks, step_index)

    monkeypatch.setattr(mr.Engine, "step", crash_at_seam)
    with pytest.raises(RuntimeError, match="injected crash"):
        grep.grep_file(paths, b"MATCH", config=cfg, mesh=mesh,
                       checkpoint_path=ck, checkpoint_every=1)
    assert fired, "injection never fired; test is vacuous"

    resumed = grep.grep_file(paths, b"MATCH", config=cfg, mesh=mesh,
                             checkpoint_path=ck, checkpoint_every=1)
    assert (resumed.matches, resumed.lines) == (full.matches, full.lines)


def test_bare_map_chunk_sequential_exact_lines():
    """round-3 review #8: the no-axis map_chunk fallback must be exact when rows
    are driven sequentially (map_chunk + combine, no mesh) — the single-row
    transfer terms make lines match the oracle even for lines spanning rows."""
    import jax.numpy as jnp

    from mapreduce_tpu.ops.tokenize import pad_to

    corpus = (b"MATCH " + b"x " * 100 + b"MATCH\n" +  # one line, many rows
              b"plain\n" + b"a " * 60 + b"MATCH " + b"b " * 90 + b"\n")
    job = grep.GrepJob(b"MATCH")
    for row_bytes in (128, 256):
        state = job.init_state()
        # Rows cut at separator boundaries like the reader does (a pattern
        # split mid-row is out of envelope; separators only here).
        off = 0
        while off < len(corpus):
            hi = min(off + row_bytes, len(corpus))
            if hi < len(corpus):
                while hi > off and corpus[hi - 1] not in b" \n\t\r":
                    hi -= 1
            row = np.frombuffer(corpus[off:hi], dtype=np.uint8)
            off = hi
            padded = pad_to(row, max(128, -(-row.shape[0] // 128) * 128))
            state = job.combine(state, job.map_chunk(jnp.asarray(padded),
                                                     jnp.uint32(0)))
        result = grep._state_result(b"MATCH", state)
        assert result.matches == occurrences(corpus, b"MATCH"), row_bytes
        assert result.lines == matching_lines(corpus, b"MATCH") == 2, row_bytes


def test_bare_map_chunk_multi_sequential_exact_lines():
    """Same exactness through MultiGrepJob's [P]-shaped fallback."""
    import jax.numpy as jnp

    from mapreduce_tpu.ops.tokenize import pad_to

    corpus = b"AB " + b"q " * 200 + b"CD\nAB CD\nplain\n"
    pats = [b"AB", b"CD", b"zz"]
    job = grep.MultiGrepJob(pats)
    state = job.init_state()
    off = 0
    while off < len(corpus):
        hi = min(off + 128, len(corpus))
        if hi < len(corpus):
            while hi > off and corpus[hi - 1] not in b" \n\t\r":
                hi -= 1
        row = np.frombuffer(corpus[off:hi], dtype=np.uint8)
        off = hi
        padded = pad_to(row, 128)
        state = job.combine(state, job.map_chunk(jnp.asarray(padded),
                                                 jnp.uint32(0)))
    for res, pat in zip(grep._multi_results(pats, state), pats):
        assert res.matches == occurrences(corpus, pat), pat
        assert res.lines == matching_lines(corpus, pat), pat
