"""Uniform token sampling: a bottom-k sketch as a MapReduce job.

A fifth model family (after word count, n-grams, HLL/CMS sketches, and
grep) with yet another accumulator shape: a fixed-k *reservoir* of token
occurrences.  The reference has nothing comparable (its map UDF emits only
word counts, ``mapper`` ``main.cu:37-54``); uniform sampling is the classic
MapReduce companion for "show me representative records" at corpus scale.

TPU formulation — the mergeable form of reservoir sampling is the
**bottom-k sketch**: every token occurrence gets an i.i.d. pseudo-uniform
64-bit priority (a hash of its global identity: chunk_id and byte offset),
and the sample is the k smallest priorities.  Bottom-k of a union is the
bottom-k of the parts' bottom-k's, so:

  * map     = tokenize + hash priorities + one sort, slice ``[:k]``;
  * combine = concat [2k] + sort + slice ``[:k]`` — tiny, fixed-size;
  * merge   = same op: associative AND commutative, so it rides the same
    collective tree-merge as every other family.

The result is an exact uniform k-sample *without replacement* over token
occurrences (frequent words appear proportionally more often — sampling
occurrences, not distinct words).  Strings are recovered host-side from
(chunk_id, pos, len) exactly like word count's first-occurrence recovery.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from mapreduce_tpu import constants
from mapreduce_tpu.config import Config, DEFAULT_CONFIG
from mapreduce_tpu.ops import tokenize as tok_ops


class ReservoirState(NamedTuple):
    """Bottom-k sample (a pytree; all fields [k] device arrays)."""

    prio_hi: jax.Array  # uint32[k]: priority high word (max = empty slot)
    prio_lo: jax.Array  # uint32[k]: priority low word
    pos_hi: jax.Array  # uint32[k]: chunk id of the sampled occurrence
    pos_lo: jax.Array  # uint32[k]: byte offset within the chunk
    length: jax.Array  # uint32[k]: token length in bytes
    total_lo: jax.Array  # uint32: population size seen (64-bit lo/hi)
    total_hi: jax.Array


_MAXU = np.uint32(0xFFFFFFFF)


def _empty(k: int) -> ReservoirState:
    full = jnp.full((k,), _MAXU)
    zero = jnp.zeros((), jnp.uint32)
    return ReservoirState(full, jnp.array(full), jnp.array(full),
                          jnp.array(full), jnp.zeros((k,), jnp.uint32),
                          zero, jnp.array(zero))


def _bottom_k(state_parts, k: int) -> tuple[jax.Array, ...]:
    """Sort by 64-bit priority (then position, for determinism under the
    astronomically-unlikely tie) and keep the k smallest."""
    prio_hi, prio_lo, pos_hi, pos_lo, length = jax.lax.sort(
        state_parts, num_keys=4)
    return (prio_hi[:k], prio_lo[:k], pos_hi[:k], pos_lo[:k], length[:k])


class ReservoirSampleJob:
    """Uniform bottom-k token sampling as a MapReduceJob (duck-typed)."""

    def __init__(self, k: int, config: Config = DEFAULT_CONFIG):
        if k < 1:
            raise ValueError(f"sample size must be >= 1, got {k}")
        self.k = k
        self.config = config

    def init_state(self) -> ReservoirState:
        return _empty(self.k)

    def _priorities(self, pos: jax.Array, is_tok: jax.Array,
                    chunk_id: jax.Array) -> tuple[jax.Array, jax.Array]:
        """Two pseudo-uniform priority lanes from the occurrence's global
        identity (chunk_id, byte offset); fmix32 avalanches, the odd
        multipliers decorrelate.  Backend-independent by construction: both
        backends see the same (chunk_id, pos) pairs for any <=W token, so
        the bottom-k selection — and therefore the sample — is identical."""
        cid = jnp.asarray(chunk_id, jnp.uint32)
        seed1 = pos * jnp.uint32(constants.HASH_BASE_1) ^ \
            tok_ops._fmix32(cid + jnp.uint32(0x9E3779B9))
        seed2 = pos * jnp.uint32(constants.HASH_BASE_2) ^ \
            tok_ops._fmix32(cid ^ jnp.uint32(0x85EBCA6B))
        prio_hi = tok_ops._fmix32(seed1)
        # Clamp away from the all-ones empty-slot sentinel (2**-32 per
        # token), mirroring the tokenizer's sentinel clamp convention.
        prio_hi = jnp.where(prio_hi == _MAXU, prio_hi - jnp.uint32(1), prio_hi)
        prio_hi = jnp.where(is_tok, prio_hi, _MAXU)
        prio_lo = jnp.where(is_tok, tok_ops._fmix32(seed2), _MAXU)
        return prio_hi, prio_lo

    def map_chunk(self, chunk: jax.Array, chunk_id: jax.Array) -> ReservoirState:
        if self.config.resolved_backend() == "pallas":
            return self._map_chunk_pallas(chunk, chunk_id)
        stream = tok_ops.tokenize(chunk)
        is_tok = stream.count > 0
        cid = jnp.asarray(chunk_id, jnp.uint32)
        prio_hi, prio_lo = self._priorities(stream.pos, is_tok, chunk_id)
        pos_hi = jnp.where(is_tok, cid, _MAXU)
        parts = _bottom_k((prio_hi, prio_lo, pos_hi, stream.pos,
                           stream.length), self.k)
        n = jnp.sum(is_tok.astype(jnp.uint32))
        return ReservoirState(*parts, n, jnp.zeros((), jnp.uint32))

    def _map_chunk_pallas(self, chunk: jax.Array,
                          chunk_id: jax.Array) -> ReservoirState:
        """Fused-kernel map: priorities derive from the packed plane (pos in
        the payload's high bits), so sampling rides the single-pass pallas
        kernel instead of the XLA associative scan — which compiles
        pathologically slowly at production chunk sizes (round-2 review #6) —
        and the bottom-k sorts HALF the rows (pair-compacted planes), with
        (pos, len) carried through the sort as ONE packed payload lane.

        Same sample as the XLA path for any corpus of <=W-byte tokens
        (priorities depend only on (chunk_id, pos)).  Tokens longer than W
        are excluded from both the sample and the reported population —
        the family-wide pallas >W contract.
        """
        from mapreduce_tpu.ops.pallas import tokenize as pallas_tok

        col, seam, _overlong = pallas_tok.tokenize_split(
            chunk, max_token_bytes=self.config.pallas_max_token)
        stream = pallas_tok.concat_streams(col, seam)
        # Poison rows (overlong ends, zero length bits) are not samples.
        is_tok = stream.count > 0
        prio_hi, prio_lo = self._priorities(stream.pos, is_tok, chunk_id)
        packed = jnp.where(is_tok, stream.packed, _MAXU)
        # One sort, 3 arrays: ties (64-bit priority collisions) break by
        # packed = pos<<6|len — the same within-chunk position order the
        # XLA path's (pos_hi, pos_lo) tiebreak yields.
        prio_hi, prio_lo, packed = jax.lax.sort(
            (prio_hi, prio_lo, packed), num_keys=3)
        prio_hi, prio_lo, packed = prio_hi[:self.k], prio_lo[:self.k], packed[:self.k]
        live = prio_hi != _MAXU
        cid = jnp.asarray(chunk_id, jnp.uint32)
        return ReservoirState(
            prio_hi=prio_hi, prio_lo=prio_lo,
            pos_hi=jnp.where(live, cid, _MAXU),
            pos_lo=jnp.where(live, packed >> 6, _MAXU),
            length=jnp.where(live, packed & jnp.uint32(63), jnp.uint32(0)),
            total_lo=stream.total, total_hi=jnp.zeros((), jnp.uint32))

    # -- data-plane telemetry (ISSUE 11 satellite: sample previously ran
    # -- telemetered streams in plain mode — the classifier and the
    # -- combiner 'auto' switch now cover every shipped family) -----------

    def map_chunk_stats_sharded(self, chunk, chunk_id, axis, device_index):
        """Stats-mode map: the reservoir has no spill/rescue machinery —
        counters are structurally zero; the gauges carry the population
        and reservoir fill."""
        from mapreduce_tpu.ops import datastats

        del axis, device_index  # the bottom-k map is axis-free
        return self.map_chunk(chunk, chunk_id), datastats.map_stats()

    def state_stats(self, state: ReservoirState, stats):
        """Gauges: population size as the ``tokens`` lane, live reservoir
        slots as ``table_valid`` (a full reservoir at k slots reads as
        occupancy k/table_capacity — honest, if dimensionless: the
        reservoir IS this family's table)."""
        live = jnp.sum((state.prio_hi != _MAXU).astype(jnp.uint32))
        return stats._replace(table_valid=live,
                              total_lo=state.total_lo,
                              total_hi=state.total_hi)

    def combine(self, state: ReservoirState, update: ReservoirState) -> ReservoirState:
        cat = lambda f: jnp.concatenate(f)
        parts = _bottom_k(
            (cat((state.prio_hi, update.prio_hi)),
             cat((state.prio_lo, update.prio_lo)),
             cat((state.pos_hi, update.pos_hi)),
             cat((state.pos_lo, update.pos_lo)),
             cat((state.length, update.length))), self.k)
        lo = state.total_lo + update.total_lo
        carry = (lo < state.total_lo).astype(jnp.uint32)
        return ReservoirState(*parts, lo,
                              state.total_hi + update.total_hi + carry)

    def merge(self, a: ReservoirState, b: ReservoirState) -> ReservoirState:
        return self.combine(a, b)

    def finalize(self, state: ReservoirState) -> ReservoirState:
        return state

    def identity(self) -> str:
        # k shapes the state, but identity documents intent anyway.
        return f"sample{self.k}"


class SampleResult(NamedTuple):
    """Host-side result: sampled token occurrences + population size."""

    tokens: list[bytes]
    total: int  # population size the sample was drawn from


import functools


@functools.partial(jax.jit, static_argnames=("k", "config"))
def _sample_step(buf: jax.Array, k: int, config: Config) -> ReservoirState:
    return ReservoirSampleJob(k, config).map_chunk(buf, jnp.uint32(0))


def sample_bytes(data: bytes, k: int,
                 config: Config = DEFAULT_CONFIG) -> SampleResult:
    """One-call API: uniform k-sample of token occurrences in a buffer."""
    from mapreduce_tpu.models.wordcount import _pad_for_backend

    ReservoirSampleJob(k, config)  # validate before any device work
    padded = _pad_for_backend(data, config)
    st = jax.tree.map(np.asarray, _sample_step(jax.device_put(padded), k, config))
    live = st.prio_hi != 0xFFFFFFFF
    # Ascending priority = unbiased order; position recovery is direct.
    spans = [(int(p), int(ln)) for p, ln in
             zip(st.pos_lo[live], st.length[live])]
    return SampleResult([bytes(data[o: o + ln]) for o, ln in spans],
                        int((int(st.total_hi) << 32) | int(st.total_lo)))


def sample_file(path, k: int, config: Config = DEFAULT_CONFIG,
                mesh=None, **kw) -> SampleResult:
    """Uniform k-sample over a file via the streaming sharded pipeline."""
    from mapreduce_tpu.data import reader
    from mapreduce_tpu.parallel.mesh import data_mesh
    from mapreduce_tpu.runtime import executor

    mesh = mesh if mesh is not None else data_mesh()
    rr = executor.run_job(ReservoirSampleJob(k, config), path, config=config,
                          mesh=mesh, **kw)
    st = jax.tree.map(np.asarray, rr.value)
    live = st.prio_hi != 0xFFFFFFFF
    chunk_id = st.pos_hi[live].astype(np.int64)
    pos = st.pos_lo[live].astype(np.int64)
    length = st.length[live].astype(np.int64)
    absolute = executor.absolute_offsets(chunk_id, pos, rr.bases, mesh.size)
    spans = [(int(a), int(ln)) for a, ln in zip(absolute, length)]
    return SampleResult(reader.read_words_at_multi(path, spans),
                        int((int(st.total_hi) << 32) | int(st.total_lo)))
