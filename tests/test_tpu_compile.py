"""The main path's Pallas kernels compile for a described TPU v5e.

Interpret mode checks what a kernel computes, not whether Mosaic lowers it:
every kernel here passed its interpret-mode tests while the chip's compiler
refused it (PR 21: an i1 reshape and an i1 select).  These tests compile for
a v5e that is described, not attached — no chip time, about two seconds
each — at the default 32 MiB chunk's geometry on a 1 MiB chunk.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load libtpu, and the pytest-xdist
workers all import every test file.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from mapreduce_tpu.ops.pallas import tokenize as pt

CHUNK = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def chunk(topo):
    return jax.ShapeDtypeStruct((CHUNK,), jnp.uint8,
                                sharding=SingleDeviceSharding(topo.devices[0]))


def _split(d):
    col, seam, over = pt.tokenize_split(d, interpret=False)
    return col.key_hi, col.packed, seam.key_hi, over


def _sort3_compact(d):
    col, seam, over, spill = pt.tokenize_split_compact(
        d, 88, block_rows=256, interpret=False)
    return col.key_hi, col.packed, seam.key_hi, over, spill


def _stable2_lane_major(d):
    col, seam, over, spill = pt.tokenize_split_compact(
        d, 128, block_rows=384, interpret=False, lane_major=True)
    return col.key_hi, col.packed, seam.key_hi, over, spill


def _fused_pair(d):
    stream, over, spill = pt.tokenize_fused(d, interpret=False)
    return stream.key_hi, stream.packed, over, spill


def _fused_lane_major(d):
    stream, over, spill = pt.tokenize_fused(
        d, compact_slots=128, block_rows=384, interpret=False,
        lane_major=True)
    return stream.key_hi, stream.packed, over, spill


def _fused_combiner(d):
    stream, over, spill, cache = pt.tokenize_fused(
        d, compact_slots=128, block_rows=512, interpret=False,
        lane_major=True, combiner_slots=8)
    return stream.key_hi, over, spill, cache.count


# The split kernel (pair, sort3 compact, stable2 lane-major — the default
# path and its spill fallback) carries the seam pass in the same program.
@pytest.mark.parametrize("fn", [_split, _sort3_compact, _stable2_lane_major,
                                _fused_pair, _fused_lane_major,
                                _fused_combiner],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_map_kernel_compiles_for_v5e(chunk, fn):
    compiled = jax.jit(fn).lower(chunk).compile()
    assert "tpu_custom_call" in compiled.as_text()
