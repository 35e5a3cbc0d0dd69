"""Config validation and 'auto' backend resolution.

The CPU-pinned suite (conftest forces JAX_PLATFORMS=cpu) never sees a real
TPU, so the TPU branches of ``resolved_backend`` are exercised here by
monkeypatching ``jax.default_backend`` — the resolution logic is pure given
(platform, chunk_bytes).
"""

from __future__ import annotations

import jax
import pytest

from mapreduce_tpu.config import Config

# Pure-host validation logic: the cheapest module in the fast tier.
pytestmark = pytest.mark.smoke


def test_default_backend_is_auto():
    assert Config().backend == "auto"


def test_auto_resolves_to_xla_off_tpu():
    assert jax.default_backend() != "tpu"  # conftest pins CPU
    assert Config().resolved_backend() == "xla"


def test_auto_resolves_to_pallas_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = Config(chunk_bytes=1 << 20)
    assert cfg.chunk_bytes >= cfg.pallas_min_chunk
    assert cfg.resolved_backend() == "pallas"


def test_auto_falls_back_to_xla_for_small_chunks_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = Config(chunk_bytes=1 << 10)  # below pallas_min_chunk (8448 @ W=32)
    assert cfg.chunk_bytes < cfg.pallas_min_chunk
    assert cfg.resolved_backend() == "xla"


def test_explicit_backends_resolve_to_themselves(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert Config(backend="xla").resolved_backend() == "xla"
    assert Config(backend="pallas").resolved_backend() == "pallas"


def test_pallas_max_token_validated_for_auto_and_pallas():
    with pytest.raises(ValueError, match="pallas_max_token"):
        Config(backend="auto", pallas_max_token=0)
    with pytest.raises(ValueError, match="pallas_max_token"):
        Config(backend="pallas", pallas_max_token=0)
    Config(backend="xla", pallas_max_token=0)  # xla never consults it


def test_pallas_chunk_floor_enforced_only_for_explicit_pallas():
    with pytest.raises(ValueError, match="chunk_bytes"):
        Config(backend="pallas", chunk_bytes=1 << 10)
    Config(backend="auto", chunk_bytes=1 << 10)  # auto falls back instead


def test_chunk_bytes_alignment():
    with pytest.raises(ValueError, match="multiple of 128"):
        Config(chunk_bytes=1000)


def test_defaults_match_measured_decisions():
    """Pin the production defaults to the round-4 on-chip measurements
    (round-4 chip notes, "the full suite"): 32 MB chunks beat both 1 MB
    (dispatch-bound) and 64 MB (sort superlinear + HBM pressure); slot
    compaction default-on at 88 (+25%); merge_every=1 (batching measured a
    loss on top of compaction); stable2 (round-5 on-chip A/B).  A default
    drifting from the measured winner should fail loudly here (round-4 review
    weak #2: "production defaults ignore the round's own measurements")."""
    cfg = Config()
    assert cfg.chunk_bytes == 1 << 25  # 32 MB
    assert cfg.sort_mode == "stable2"  # round-5 on-chip A/B: +5.9% zipf
    assert cfg.resolved_compact_slots == 128  # lane-major 384-byte windows
    assert cfg.resolved_block_rows == 384
    assert cfg.merge_every == 1
    assert cfg.rescue_slots == 1024

    # The CLI must hand users the same measured-optimal shape with no flags.
    from mapreduce_tpu.cli import build_parser

    args = build_parser().parse_args([])
    assert args.chunk_bytes == cfg.chunk_bytes
    assert args.merge_every == cfg.merge_every
    assert args.sort_mode == cfg.sort_mode
    assert args.compact_slots is None  # auto -> resolved_compact_slots


def test_inflight_groups_validation():
    with pytest.raises(ValueError, match="inflight_groups"):
        Config(inflight_groups=0)
    with pytest.raises(ValueError, match="inflight_groups"):
        Config(inflight_groups=-2)
    assert Config(inflight_groups=1).inflight_groups == 1  # serial fallback
    assert Config().inflight_groups >= 1


def test_prefetch_depth_validation_and_resolution():
    with pytest.raises(ValueError, match="prefetch_depth"):
        Config(prefetch_depth=0)
    # explicit depth wins verbatim
    assert Config(prefetch_depth=7).resolved_prefetch_depth == 7
    # auto: co-tuned with the window (superstep * inflight), clamped [2, 16]
    assert Config(superstep=1, inflight_groups=1).resolved_prefetch_depth == 2
    assert Config(superstep=2, inflight_groups=3).resolved_prefetch_depth == 6
    assert Config(superstep=8, inflight_groups=8).resolved_prefetch_depth == 16
