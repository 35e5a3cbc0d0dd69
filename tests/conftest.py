"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

This is the standard JAX fake-backend idiom (SURVEY §4): multi-chip sharding
paths are exercised on CPU without TPUs.  Must run before any jax import.
"""

import os
import sys

# The force-CPU idiom (config.update after import — env vars alone are too
# late once something has imported jax) lives in
# one place: __graft_entry__._force_cpu_mesh.  It also bumps a too-small
# ambient xla_force_host_platform_device_count, which the old inline copy
# here could not.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from __graft_entry__ import _force_cpu_mesh

jax = _force_cpu_mesh(8)

# The persistent compile cache stays off for the whole suite, through JAX's
# own switch (the program's enable_compile_cache only places the cache and
# never turns it back on): the XLA:CPU executable serialization on the cache
# WRITE has segfaulted pytest (rounds 4 and 5, stack through
# compilation_cache.put_executable_and_time).
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_between_modules():
    """XLA:CPU segfaults nondeterministically deep into a single-process
    run of the whole suite (~280 cumulative compiles in; observed twice in
    round 5, both times while COMPILING a fresh program inside
    test_sketch — the same test passes in isolation and in any small
    batch).  No forked/xdist plugin is available in this image, so the
    mitigation is to drop compiled-executable and tracing caches at module
    boundaries, keeping the compiler's in-process footprint bounded.  The
    cost is cross-module cache misses for shared shapes (~minutes over the
    suite), which beats a segfaulted run with no report."""
    yield
    jax.clear_caches()


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(20260729)


@pytest.fixture(scope="session")
def fixture_text() -> bytes:
    """The reference's bundled fixture (test.txt:1-3)."""
    return b"Hello World EveryOne\nWorld Good News\nGood Morning Hello\n"


def make_corpus(rng, n_words: int, vocab: int, zipf_a: float = 1.3, seed_words=None) -> bytes:
    """Random Zipf-distributed corpus, whitespace-joined."""
    words = seed_words or [f"w{i:x}" for i in range(vocab)]
    idx = rng.zipf(zipf_a, size=n_words) % len(words)
    seps = np.array([" ", "\n", "\t", "  ", " \r\n"])
    parts = []
    for i in idx:
        parts.append(words[int(i)])
        parts.append(str(seps[int(rng.integers(0, len(seps)))]))
    return "".join(parts).encode()


@pytest.fixture(scope="session")
def small_corpus(rng) -> bytes:
    return make_corpus(rng, n_words=2000, vocab=150)


def pallas_interpret_mode():
    """Force pallas interpret mode for the kernels traced inside."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.force_tpu_interpret_mode()
