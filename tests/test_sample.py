"""Uniform reservoir sampling (bottom-k sketch): device results vs oracle
properties.  The sample is deterministic for a fixed corpus + chunking (the
priorities hash the occurrence's global identity), so distribution checks
assert concrete spread properties of that fixed draw."""

import numpy as np
import pytest

from mapreduce_tpu.config import Config
from mapreduce_tpu.models import sample as sample_mod
from mapreduce_tpu.utils import oracle


def test_sample_bytes_basic(small_corpus):
    k = 50
    r = sample_mod.sample_bytes(small_corpus, k)
    assert r.total == oracle.total_count(small_corpus)
    assert len(r.tokens) == k
    words = set(oracle.split_words(small_corpus))
    for t in r.tokens:
        assert t in words, t


def test_sample_smaller_population_returns_all():
    data = b"alpha beta gamma\n"
    r = sample_mod.sample_bytes(data, 10)
    assert r.total == 3
    assert sorted(r.tokens) == [b"alpha", b"beta", b"gamma"]


def test_sample_deterministic(small_corpus):
    a = sample_mod.sample_bytes(small_corpus, 20)
    b = sample_mod.sample_bytes(small_corpus, 20)
    assert a == b


def test_sample_k_validation():
    with pytest.raises(ValueError):
        sample_mod.ReservoirSampleJob(0)


def test_sample_spread_over_corpus():
    """1000 distinct single-occurrence tokens; the fixed 100-draw must be
    duplicate-free and touch every quarter of the corpus (a badly biased
    priority hash would fail this)."""
    tokens = [b"tok%04d" % i for i in range(1000)]
    data = b" ".join(tokens) + b"\n"
    r = sample_mod.sample_bytes(data, 100)
    assert len(set(r.tokens)) == 100  # without replacement
    idx = sorted(int(t[3:]) for t in r.tokens)
    for q in range(4):
        in_q = sum(1 for i in idx if q * 250 <= i < (q + 1) * 250)
        assert in_q >= 10, f"quarter {q} got only {in_q} of 100 draws"


@pytest.mark.slow
def test_merge_associative_commutative(small_corpus):
    """Bottom-k merge order must not change the result (collective safety)."""
    import jax

    cfg = Config(chunk_bytes=1024)
    job = sample_mod.ReservoirSampleJob(16, cfg)
    from mapreduce_tpu.ops.tokenize import pad_to

    thirds = [small_corpus[i::3] for i in range(3)]  # arbitrary split
    states = [job.map_chunk(jax.device_put(pad_to(t, 4096)), i)
              for i, t in enumerate(thirds)]
    a, b, c = states
    left = job.merge(job.merge(a, b), c)
    right = job.merge(a, job.merge(b, c))
    swapped = job.merge(c, job.merge(b, a))
    for l, r_, s in zip(jax.tree.leaves(left), jax.tree.leaves(right),
                        jax.tree.leaves(swapped)):
        np.testing.assert_array_equal(np.asarray(l), np.asarray(r_))
        np.testing.assert_array_equal(np.asarray(l), np.asarray(s))


def test_sample_file_streamed(tmp_path, small_corpus):
    from mapreduce_tpu.parallel.mesh import data_mesh

    path = tmp_path / "c.txt"
    path.write_bytes(small_corpus)
    cfg = Config(chunk_bytes=1024)
    r = sample_mod.sample_file(str(path), 25, config=cfg, mesh=data_mesh(4))
    assert r.total == oracle.total_count(small_corpus)
    assert len(r.tokens) == 25
    words = set(oracle.split_words(small_corpus))
    for t in r.tokens:
        assert t in words, t
    # Deterministic for fixed corpus + chunking.
    r2 = sample_mod.sample_file(str(path), 25, config=cfg, mesh=data_mesh(4))
    assert r.tokens == r2.tokens


def test_sample_cli(tmp_path, capsys):
    from mapreduce_tpu import cli

    path = tmp_path / "c.txt"
    path.write_bytes(b"aa bb cc dd ee ff gg hh\n")
    assert cli.main([str(path), "--sample", "3", "--format", "json"]) == 0
    import json

    obj = json.loads(capsys.readouterr().out)
    assert obj["total"] == 8 and len(obj["sample"]) == 3
    assert cli.main([str(path), "--sample", "3"]) == 0
    out = capsys.readouterr().out
    assert "Sampled:3 of 8\n" in out
    # Conflicting flags are honest errors.
    with pytest.raises(SystemExit):
        cli.main([str(path), "--sample", "3", "--top-k", "2"])
    with pytest.raises(SystemExit):
        cli.main([str(path), "--sample", "3", "--grep", "aa"])


def test_pallas_sample_matches_xla(small_corpus):
    """round-2 review #6: the sample job honors config.resolved_backend(), and
    the pallas fused-kernel path draws the IDENTICAL sample (priorities
    depend only on (chunk_id, pos), shared by both backends)."""
    base = dict(chunk_bytes=1 << 14, table_capacity=1 << 10)
    sx = sample_mod.sample_bytes(small_corpus, 16, Config(**base, backend="xla"))
    sp = sample_mod.sample_bytes(small_corpus, 16, Config(**base, backend="pallas"))
    assert sx.tokens == sp.tokens
    assert sx.total == sp.total


def test_pallas_sample_streamed_deterministic(tmp_path, small_corpus):
    """Streamed pallas sampling: same corpus + chunking -> same sample, and
    it equals the streamed XLA sample (chunk ids and offsets agree)."""
    path = tmp_path / "c.txt"
    path.write_bytes(small_corpus)
    from mapreduce_tpu.parallel.mesh import data_mesh

    base = dict(chunk_bytes=128 * 66, table_capacity=1 << 10)
    sp1 = sample_mod.sample_file(str(path), 12,
                                 Config(**base, backend="pallas"),
                                 mesh=data_mesh(2))
    sp2 = sample_mod.sample_file(str(path), 12,
                                 Config(**base, backend="pallas"),
                                 mesh=data_mesh(2))
    sx = sample_mod.sample_file(str(path), 12, Config(**base, backend="xla"),
                                mesh=data_mesh(2))
    assert sp1.tokens == sp2.tokens  # deterministic
    assert sp1.tokens == sx.tokens  # backend-independent
    assert sp1.total == sx.total


def test_pallas_sample_excludes_overlong(tmp_path):
    """>W tokens are excluded from sample AND population (the family-wide
    pallas contract); the XLA backend samples them."""
    data = b"aa bb " + b"x" * 50 + b" cc dd ee ff gg hh\n"
    cfg = Config(chunk_bytes=1 << 14, table_capacity=1 << 10, backend="pallas")
    r = sample_mod.sample_bytes(data, 50, cfg)
    assert r.total == 8  # 9 tokens minus the overlong one
    assert all(b"x" * 50 != t for t in r.tokens)
