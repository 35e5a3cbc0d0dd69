"""Multi-chip path tests on the emulated 8-device CPU mesh (SURVEY §4).

These exercise the real shard_map/collective code paths — the ones the driver
also dry-runs via __graft_entry__.dryrun_multichip — against the host oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mapreduce_tpu.config import Config
from mapreduce_tpu.models.wordcount import WordCountJob, TopKWordCountJob
from mapreduce_tpu.ops import table as table_ops
from mapreduce_tpu.parallel import collectives
from mapreduce_tpu.parallel.mapreduce import Engine
from mapreduce_tpu.parallel.mesh import data_mesh
from mapreduce_tpu.utils import oracle
from tests.conftest import make_corpus

CFG = Config(chunk_bytes=512, table_capacity=1024)


def _batches(data: bytes, n_dev: int, chunk: int):
    """Boundary-aligned [n_dev, chunk] batches via the reader, from memory."""
    from mapreduce_tpu.data import reader as r
    import tempfile, os

    with tempfile.NamedTemporaryFile(delete=False) as f:
        f.write(data)
        path = f.name
    try:
        yield from r.iter_batches(path, n_dev, chunk)
    finally:
        os.unlink(path)


def _table_dict(t):
    c = np.asarray(t.count)
    return {(int(h), int(l)): int(n) for h, l, n in
            zip(np.asarray(t.key_hi), np.asarray(t.key_lo), c) if n > 0}


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) >= 8, "conftest must force 8 CPU devices"
    return data_mesh(8)


@pytest.mark.parametrize("strategy", ["tree", "gather"])
@pytest.mark.slow
def test_engine_wordcount_matches_oracle(mesh8, rng, strategy):
    corpus = make_corpus(rng, n_words=5000, vocab=300)
    eng = Engine(WordCountJob(CFG), mesh8, merge_strategy=strategy)
    batches = [b.data for b in _batches(corpus, 8, CFG.chunk_bytes)]
    assert len(batches) > 1  # actually exercises streaming
    result = eng.run(batches)
    expected = oracle.word_counts(corpus)
    assert int(result.n_valid()) == len(expected)
    assert sorted(_table_dict(result).values()) == sorted(expected.values())
    assert int(result.total_count()) == oracle.total_count(corpus)


@pytest.mark.slow
def test_mesh_sizes_agree(rng):
    """Same corpus, meshes of 1/2/4/8 devices: identical count multisets."""
    corpus = make_corpus(rng, n_words=2000, vocab=120)
    results = {}
    for d in (1, 2, 4, 8):
        eng = Engine(WordCountJob(CFG), data_mesh(d))
        batches = [b.data for b in _batches(corpus, d, CFG.chunk_bytes)]
        results[d] = _table_dict(eng.run(batches))
    assert results[1] == results[2] == results[4] == results[8]


@pytest.mark.slow
def test_gather_merge_non_power_of_two(rng):
    corpus = make_corpus(rng, n_words=1000, vocab=80)
    eng = Engine(WordCountJob(CFG), data_mesh(3), merge_strategy="tree")  # falls back
    batches = [b.data for b in _batches(corpus, 3, CFG.chunk_bytes)]
    result = eng.run(batches)
    assert sorted(_table_dict(result).values()) == \
        sorted(oracle.word_counts(corpus).values())


@pytest.mark.slow
def test_top_k_job(mesh8, rng):
    corpus = make_corpus(rng, n_words=3000, vocab=200)
    eng = Engine(TopKWordCountJob(10, CFG), mesh8)
    batches = [b.data for b in _batches(corpus, 8, CFG.chunk_bytes)]
    result = eng.run(batches)
    # Top-k finalize bundles the table with its pre-reorder KMV snapshot.
    tbl = result.table
    got = sorted(np.asarray(tbl.count)[np.asarray(tbl.count) > 0].tolist(), reverse=True)
    expected = sorted(oracle.word_counts(corpus).values(), reverse=True)[:10]
    assert got == expected
    # Nothing spilled here: occupancy below capacity, so no estimate — the
    # snapshot still reports the true occupancy.
    assert int(result.kmv_n_valid) == len(oracle.word_counts(corpus))


def test_psum_collective(mesh8):
    """Scalar totals ride the native psum path (the north-star collective)."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def f(x):
        return collectives.psum(x.sum(), "data")

    fn = shard_map(f, mesh=mesh8, in_specs=(P("data"),), out_specs=P())
    out = jax.jit(fn)(np.arange(64, dtype=np.int32))
    assert int(out) == 64 * 63 // 2


@pytest.mark.slow
def test_step_many_equals_repeated_steps(mesh8, rng):
    """One superstep dispatch (lax.scan over K chunks) must produce exactly
    the same state as K individual steps, chunk_ids included."""
    corpus = make_corpus(rng, n_words=6000, vocab=250)
    batches = [b.data for b in _batches(corpus, 8, CFG.chunk_bytes)]
    k = len(batches)
    assert k >= 2

    eng_a = Engine(WordCountJob(CFG), mesh8)
    state_a = eng_a.init_states()
    for i, b in enumerate(batches):
        state_a = eng_a.step(state_a, b, i)
    final_a = eng_a.finish(state_a)

    eng_b = Engine(WordCountJob(CFG), mesh8)
    state_b = eng_b.init_states()
    stacked = np.stack(batches, axis=1)  # [D, K, C]
    state_b = eng_b.step_many(state_b, stacked, 0)
    final_b = eng_b.finish(state_b)

    for fa, fb in zip(final_a, final_b):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


@pytest.mark.slow
def test_step_many_mixed_with_single_steps(mesh8, rng):
    """step_many must compose with step() (remainder batches) seamlessly."""
    corpus = make_corpus(rng, n_words=6000, vocab=250)
    batches = [b.data for b in _batches(corpus, 8, CFG.chunk_bytes)]
    assert len(batches) >= 3
    head, tail = batches[:2], batches[2:]

    eng = Engine(WordCountJob(CFG), mesh8)
    state = eng.init_states()
    state = eng.step_many(state, np.stack(head, axis=1), 0)
    for j, b in enumerate(tail):
        state = eng.step(state, b, len(head) + j)
    result = eng.finish(state)

    expected = oracle.word_counts(corpus)
    assert sorted(_table_dict(result).values()) == sorted(expected.values())
    assert int(result.total_count()) == oracle.total_count(corpus)


@pytest.mark.slow
def test_two_level_mesh_engine_matches_oracle(rng):
    """2-D ('replica','data') mesh with hierarchical (ICI-then-DCN) merge:
    the multi-slice topology of SURVEY §7 step 4, emulated as 2x4 CPU."""
    from mapreduce_tpu.parallel.mesh import two_level_mesh

    corpus = make_corpus(rng, n_words=5000, vocab=300)
    mesh = two_level_mesh(2, 4)
    eng = Engine(WordCountJob(CFG), mesh, axis=("replica", "data"))
    assert eng.n_devices == 8
    batches = [b.data for b in _batches(corpus, 8, CFG.chunk_bytes)]
    result = eng.run(batches)
    expected = oracle.word_counts(corpus)
    assert int(result.n_valid()) == len(expected)
    assert sorted(_table_dict(result).values()) == sorted(expected.values())
    assert int(result.total_count()) == oracle.total_count(corpus)


@pytest.mark.slow
def test_two_level_matches_flat_mesh(rng):
    """Same devices, 1-D vs 2-D mesh: identical tables (chunk ids and all)."""
    from mapreduce_tpu.parallel.mesh import two_level_mesh

    corpus = make_corpus(rng, n_words=4000, vocab=150)
    batches = [b.data for b in _batches(corpus, 8, CFG.chunk_bytes)]

    flat = Engine(WordCountJob(CFG), data_mesh(8)).run(batches)
    two = Engine(WordCountJob(CFG), two_level_mesh(2, 4),
                 axis=("replica", "data")).run(batches)
    for fa, fb in zip(flat, two):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


@pytest.mark.slow
def test_count_file_over_two_level_mesh(tmp_path, rng):
    """The streaming executor must shard over ALL axes of a 2-D mesh (8
    shards from 2x4), not just the leading one."""
    from mapreduce_tpu.parallel.mesh import two_level_mesh
    from mapreduce_tpu.runtime import executor

    corpus = make_corpus(rng, n_words=4000, vocab=150)
    path = tmp_path / "c.txt"
    path.write_bytes(corpus)
    r = executor.count_file(str(path), config=CFG, mesh=two_level_mesh(2, 4))
    assert {w: c for w, c in zip(r.words, r.counts)} == oracle.word_counts(corpus)


@pytest.mark.slow
def test_step_many_repeats_equals_repeated_dispatch():
    """step_many(repeats=R) == R sequential step_many calls over the same
    chunks with advancing step indices (epoch semantics)."""
    import numpy as np
    from mapreduce_tpu.config import Config
    from mapreduce_tpu.models.wordcount import WordCountJob
    from mapreduce_tpu.parallel.mapreduce import Engine
    from mapreduce_tpu.parallel.mesh import data_mesh

    cfg = Config(chunk_bytes=256, table_capacity=512, backend="xla")
    rng = np.random.default_rng(5)
    chunks = rng.integers(97, 110, size=(4, 2, 256), dtype=np.uint8)
    chunks[rng.random(chunks.shape) < 0.2] = 0x20

    eng1 = Engine(WordCountJob(cfg), data_mesh(4))
    s1 = eng1.init_states()
    s1 = eng1.step_many(s1, chunks, 0, repeats=3)
    t1 = eng1.finish(s1)

    eng2 = Engine(WordCountJob(cfg), data_mesh(4))
    s2 = eng2.init_states()
    for r in range(3):
        s2 = eng2.step_many(s2, chunks, r * 2)
    t2 = eng2.finish(s2)

    for a, b in zip(t1, t2):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --- key-range all_to_all merge (round-3 review #3) ------------------------------


@pytest.mark.slow
def test_keyrange_engine_matches_oracle(mesh8, rng):
    corpus = make_corpus(rng, n_words=5000, vocab=300)
    eng = Engine(WordCountJob(CFG), mesh8, merge_strategy="keyrange")
    batches = [b.data for b in _batches(corpus, 8, CFG.chunk_bytes)]
    result = eng.run(batches)
    expected = oracle.word_counts(corpus)
    assert sorted(_table_dict(result).values()) == sorted(expected.values())
    assert int(result.total_count()) == oracle.total_count(corpus)


@pytest.mark.slow
def test_keyrange_bit_identical_to_tree(mesh8, rng):
    """No-spill runs: keyrange and tree produce the same table, field for
    field (kept keys, counts, first occurrences, dropped scalars)."""
    corpus = make_corpus(rng, n_words=4000, vocab=200)
    batches = [b.data for b in _batches(corpus, 8, CFG.chunk_bytes)]
    tree = Engine(WordCountJob(CFG), mesh8, merge_strategy="tree").run(batches)
    keyr = Engine(WordCountJob(CFG), mesh8, merge_strategy="keyrange").run(batches)
    for fa, fb in zip(tree, keyr):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


@pytest.mark.slow
def test_keyrange_non_power_of_two(rng):
    """all_to_all has no power-of-two constraint (unlike the butterfly)."""
    corpus = make_corpus(rng, n_words=1500, vocab=90)
    eng = Engine(WordCountJob(CFG), data_mesh(3), merge_strategy="keyrange")
    batches = [b.data for b in _batches(corpus, 3, CFG.chunk_bytes)]
    result = eng.run(batches)
    assert sorted(_table_dict(result).values()) == \
        sorted(oracle.word_counts(corpus).values())


@pytest.mark.slow
def test_keyrange_two_level_mesh(rng):
    """Tuple axes: the keyrange round flattens the 2-D mesh."""
    from mapreduce_tpu.parallel.mesh import two_level_mesh

    corpus = make_corpus(rng, n_words=3000, vocab=150)
    batches = [b.data for b in _batches(corpus, 8, CFG.chunk_bytes)]
    flat = Engine(WordCountJob(CFG), data_mesh(8),
                  merge_strategy="keyrange").run(batches)
    two = Engine(WordCountJob(CFG), two_level_mesh(2, 4),
                 axis=("replica", "data"), merge_strategy="keyrange").run(batches)
    for fa, fb in zip(flat, two):
        np.testing.assert_array_equal(np.asarray(fa), np.asarray(fb))


def test_keyrange_unsupported_job_raises(mesh8):
    from mapreduce_tpu.models.grep import GrepJob

    with pytest.raises(ValueError, match="keyrange"):
        Engine(GrepJob(b"x"), mesh8, merge_strategy="keyrange")


def _crafted_tables(n_dev: int, cap: int, keys_per_dev, rng):
    """Stacked per-device tables with CHOSEN (key_hi, key_lo) rows (count 1
    each, distinct pos), built through the real _build path so invariants
    hold.  keys_per_dev: list of lists of (hi, lo) pairs."""
    stacked = []
    for d, keys in enumerate(keys_per_dev):
        n = max(len(keys), 1)
        pad = -(-n // 8) * 8
        khi = np.full((pad,), 0xFFFFFFFF, np.uint32)
        klo = np.full((pad,), 0xFFFFFFFF, np.uint32)
        cnt = np.zeros((pad,), np.uint32)
        for i, (hi, lo) in enumerate(keys):
            khi[i], klo[i], cnt[i] = hi, lo, 1
        phi = np.where(cnt > 0, np.uint32(d), np.uint32(0xFFFFFFFF)).astype(np.uint32)
        plo = np.arange(pad, dtype=np.uint32)
        plo = np.where(cnt > 0, plo, np.uint32(0xFFFFFFFF)).astype(np.uint32)
        ln = np.where(cnt > 0, np.uint32(3), np.uint32(0)).astype(np.uint32)
        z = jnp.uint32(0)
        t = table_ops._build(jnp.asarray(khi), jnp.asarray(klo),
                             jnp.asarray(phi), jnp.asarray(plo),
                             jnp.asarray(cnt), jnp.zeros((pad,), jnp.uint32),
                             jnp.asarray(ln), cap, z, z, z, z)
        stacked.append(t)
    return jax.tree.map(lambda *xs: jnp.stack(xs), *stacked)


def _run_collective(mesh, fn, stacked):
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def body(state):
        local = jax.tree.map(lambda x: x[0], state)
        return fn(local)

    wrapped = shard_map(body, mesh=mesh, in_specs=(P("data"),), out_specs=P(),
                        check_vma=False)
    return jax.tree.map(np.asarray, jax.jit(wrapped)(stacked))


@pytest.mark.slow
def test_keyrange_budget_spill_never_partial(mesh8, rng):
    """Force one partition past the B = slack*C/D budget on one device: the
    spilled keys must be fully evicted everywhere (never reported with a
    partial count) and the mass exactly accounted in dropped_count."""
    cap, n_dev = 64, 8
    b = -(-2 * cap // n_dev)  # 16: the budget key_range_merge derives
    # Device 0: 3*b keys all landing in partition 3 (key_lo % 8 == 3).
    hot = [(0x1000 + i, 8 * i + 3) for i in range(3 * b)]
    # Device 1 holds copies of the 8 LARGEST hot keys (they will be budget-
    # spilled on device 0) plus its own distinct keys in other partitions.
    copies = hot[-8:]
    own = [(0x9000 + i, 8 * i + 5) for i in range(10)]
    tables = _crafted_tables(
        n_dev, cap, [hot, copies + own] + [[] for _ in range(n_dev - 2)], rng)

    merged = _run_collective(
        data_mesh(n_dev), lambda t: collectives.key_range_merge(t, "data"),
        tables)

    kept = {(int(h), int(l)): int(c) for h, l, c in
            zip(merged.key_hi, merged.key_lo, merged.count) if c}
    # True multiset: hot keys count 1 (dev0) except the 8 copied ones count 2.
    truth = {k: 1 for k in hot}
    for k in copies:
        truth[k] = 2
    for k in own:
        truth[k] = 1
    # Invariant: every kept key carries its FULL true count.
    for k, c in kept.items():
        assert truth[k] == c, (k, c)
    # The budget forced spill: some hot keys are gone, but all mass is
    # accounted — kept + dropped == total emitted.
    assert len(kept) < len(truth)
    _, dc = merged.dropped_totals()
    assert sum(kept.values()) + dc == sum(truth.values())
    # Spill is deterministic largest-first: every SURVIVING hot key is
    # smaller than every spilled one.
    spilled = sorted(set(truth) - set(kept))
    if spilled:
        surviving_hot = [k for k in kept if k[1] % 8 == 3]
        assert max(surviving_hot, default=(0, 0)) < min(spilled)


@pytest.mark.slow
def test_keyrange_count_file_end_to_end(tmp_path, rng):
    """merge_strategy plumbs through run_job/count_file."""
    from mapreduce_tpu.runtime import executor

    corpus = make_corpus(rng, n_words=3000, vocab=150)
    path = tmp_path / "c.txt"
    path.write_bytes(corpus)
    r = executor.count_file(str(path), config=CFG, mesh=data_mesh(8),
                            merge_strategy="keyrange")
    assert {w: c for w, c in zip(r.words, r.counts)} == oracle.word_counts(corpus)


@pytest.mark.slow
def test_keyrange_tiny_capacity_skewed_partitions(mesh8, rng):
    """The small-C/D budget regime (round-5 D=256 scale-dryrun bug): with
    capacity/D of order 1, balls-in-bins max partition load exceeds any
    purely multiplicative slack, so the old ``b = ceil(2C/D)`` budget
    spilled REAL keys and keyrange (correctly, per the spill contract)
    diverged from tree on the kept set.  The additive ``+ 8 + 4 log2 D``
    term must keep tiny tables bit-identical to tree — across many seeds
    so skewed ``key_lo % D`` partitions actually occur."""
    cfg = Config(chunk_bytes=512, table_capacity=16)
    # Engines hoisted out of the seed loop: each instance caches its own
    # jitted programs, and batch shapes are identical across seeds.
    eng_tree = Engine(WordCountJob(cfg), mesh8, merge_strategy="tree")
    eng_keyr = Engine(WordCountJob(cfg), mesh8, merge_strategy="keyrange")
    for seed in range(5):
        r2 = np.random.default_rng(1000 + seed)
        corpus = make_corpus(r2, n_words=600, vocab=40)
        batches = [b.data for b in _batches(corpus, 8, cfg.chunk_bytes)]
        tree = eng_tree.run(batches)
        keyr = eng_keyr.run(batches)
        for f in tree._fields:
            if f.startswith("dropped_uniques"):
                continue  # documented bound-looseness difference
            np.testing.assert_array_equal(
                np.asarray(getattr(tree, f)), np.asarray(getattr(keyr, f)),
                err_msg=f"{f} diverged at seed {seed}")
        assert keyr.dropped_totals()[0] <= tree.dropped_totals()[0]
