"""Overlong-token rescue: the pallas backend must agree with the XLA oracle
on corpora with >W-byte tokens (round-3 review #6; ops/rescue.py).

The XLA backend counts any token length exactly, so it IS the oracle: with
rescue on, pallas runs must match it bit-for-bit whenever every overlong
token fits the rescue window and budget — and degrade to the accounted
(dropped_*) envelope, never corruption, when they don't.
"""

import numpy as np
import pytest

from mapreduce_tpu.config import Config
from mapreduce_tpu.models import wordcount as wc


def _cfg(backend, **kw):
    base = dict(chunk_bytes=1 << 14, table_capacity=1 << 12, backend=backend)
    base.update(kw)
    return Config(**base)


def _mixed_text(rng, n_words=400, long_words=None):
    """Normal words interleaved with given overlong tokens, shuffled."""
    vocab = [b"the", b"quick", b"fox", b"jumps", b"count"]
    words = [vocab[i % len(vocab)] for i in range(n_words)]
    words += list(long_words or [])
    order = rng.permutation(len(words))
    return b" ".join(words[i] for i in order)


@pytest.fixture
def oracle():
    def run(text, **pallas_kw):
        rp = wc.count_words(text, _cfg("pallas", **pallas_kw))
        rx = wc.count_words(text, _cfg("xla"))
        return rp, rx

    return run


class TestRescueExact:
    @pytest.mark.slow
    def test_matches_xla_oracle_counts_and_order(self, rng, oracle):
        longs = [b"x" * 40, b"y" * 100, b"z" * 150] * 3 + [b"u" * 63]
        text = _mixed_text(rng, long_words=longs)
        rp, rx = oracle(text, rescue_overlong=64, rescue_window=192)
        assert rp.as_dict() == rx.as_dict()
        assert rp.words == rx.words  # insertion order identical
        assert rp.total == rx.total
        assert rp.dropped_count == 0 and rp.dropped_uniques == 0
        assert rp.distinct == rx.distinct

    @pytest.mark.slow  # 31 s measured round 6: past the tier-1 >=10 s line
    def test_repeated_overlong_word_accumulates(self, rng, oracle):
        url = b"http://example.com/a/very/long/path/segment/beyond-w"
        assert len(url) > 32
        text = _mixed_text(rng, long_words=[url] * 17)
        rp, rx = oracle(text, rescue_overlong=64, rescue_window=192)
        assert rp.as_dict()[url] == 17
        assert rp.as_dict() == rx.as_dict()

    @pytest.mark.slow  # ~30 s on the one-core box; tier-1 budget rule
    def test_exact_at_w_boundaries(self, rng, oracle):
        # 32 is in-kernel, 33 is the smallest rescued length, window-1 the
        # largest; window stays dropped (covered in TestRescueEnvelope).
        longs = [b"a" * 32, b"b" * 33, b"c" * 191]
        text = _mixed_text(rng, long_words=longs * 2)
        rp, rx = oracle(text, rescue_overlong=64, rescue_window=192)
        assert rp.as_dict() == rx.as_dict()
        assert rp.dropped_count == 0

    @pytest.mark.slow
    def test_overlong_crossing_lane_seams(self, oracle):
        # A chunk-sized text where overlong tokens land on many different
        # lane-segment offsets, including straddling 128-lane seam bytes:
        # seam-pass poisons must be rescued exactly like in-lane ones.
        rng = np.random.default_rng(5)
        words = []
        for i in range(2000):
            words.append(b"w%d" % (i % 37))
            if i % 29 == 0:
                words.append(bytes([97 + i % 26]) * (33 + i % 120))
        text = b" ".join(words)
        rp, rx = oracle(text, rescue_overlong=256, rescue_window=192)
        assert rp.as_dict() == rx.as_dict()
        assert rp.total == rx.total
        assert rp.dropped_count == 0

    @pytest.mark.slow
    def test_with_compact_slots(self, oracle):
        rng = np.random.default_rng(9)
        longs = [b"q" * 50] * 5 + [b"r" * 120] * 2
        text = _mixed_text(rng, long_words=longs)
        rp, rx = oracle(text, rescue_overlong=64, rescue_window=192,
                        sort_mode="sort3", compact_slots=88)
        assert rp.as_dict() == rx.as_dict()
        assert rp.dropped_count == 0


class TestRescueEnvelope:
    def test_token_longer_than_window_stays_accounted(self, rng, oracle):
        giant = b"g" * 500  # > rescue_window - 1
        text = _mixed_text(rng, long_words=[giant] * 3 + [b"m" * 40])
        rp, rx = oracle(text, rescue_overlong=64, rescue_window=192)
        d = rp.as_dict()
        assert giant not in d
        assert d[b"m" * 40] == 1  # within-window token still rescued
        assert rp.dropped_count == 3
        assert rp.dropped_uniques == 3  # upper bound: unhashed, undedupable
        assert rp.total == rx.total  # accounting keeps totals exact

    @pytest.mark.slow
    def test_budget_overflow_rescues_prefix_keeps_totals(self, rng):
        # More overlong tokens than BOTH tiers: the smallest positions win,
        # the rest stays accounted, totals stay exact.  Words are DISTINCT:
        # a duplicated word with only some occurrences inside the budget
        # would legitimately report a partial count (residual in dropped_*).
        # rescue_overlong_max pins the second tier to the primary budget so
        # this exercises the genuine-overflow envelope.
        longs = [b"%02d" % i + b"x" * 40 for i in range(30)]
        text = _mixed_text(rng, long_words=longs)
        cfg = _cfg("pallas", rescue_overlong=8, rescue_overlong_max=8,
                   rescue_window=192)
        rp = wc.count_words(text, cfg)
        rx = wc.count_words(text, _cfg("xla"))
        assert rp.total == rx.total
        assert rp.dropped_count == len(longs) - 8
        # Every rescued word is correct (subset of the oracle's counts).
        ox = rx.as_dict()
        for w, c in rp.as_dict().items():
            assert ox[w] == c

    @pytest.mark.slow
    def test_tier_escalates_past_primary_budget(self, rng):
        """round-4 review weak #4: overlong counts past the primary budget
        escalate to the second tier under a lax.cond instead of silently
        leaving the residual in dropped_* — URL-dense chunks stay exact
        with no hand-sizing."""
        longs = [b"%02d" % i + b"u" * 40 for i in range(30)]
        text = _mixed_text(rng, long_words=longs)
        rp = wc.count_words(text, _cfg("pallas", rescue_overlong=8,
                                       rescue_overlong_max=64,
                                       rescue_window=192))
        rx = wc.count_words(text, _cfg("xla"))
        assert rp.as_dict() == rx.as_dict()
        assert rp.words == rx.words
        assert rp.dropped_count == 0

    @pytest.mark.slow  # 43 s measured round 6: past the tier-1 >=10 s line
    def test_tier_escalates_under_stable2_with_seam_poisons(self, rng):
        """The tiered path composes with stable2's split rescue sources
        (column poison segment + seam-stream poisons, re-sorted so the
        first-R1 slice keeps the globally smallest positions)."""
        longs = [b"%02d" % i + b"v" * 40 for i in range(25)]
        text = _mixed_text(rng, long_words=longs)
        rp = wc.count_words(text, _cfg("pallas", sort_mode="stable2",
                                       rescue_overlong=8,
                                       rescue_overlong_max=64,
                                       rescue_window=192))
        rx = wc.count_words(text, _cfg("xla"))
        assert rp.as_dict() == rx.as_dict()
        assert rp.dropped_count == 0

    def test_tier_auto_sizing_arithmetic(self):
        # Auto: chunk_bytes/1024 clamped to [rescue_slots, 65536].
        assert Config().rescue_slots_max == (1 << 25) >> 10  # 32768 @ 32 MB
        assert Config(chunk_bytes=1 << 14).rescue_slots_max == 1024  # floor
        assert Config(chunk_bytes=1 << 26).rescue_slots_max == 65536  # cap
        assert Config(rescue_overlong=0).rescue_slots_max == 0  # off = off
        assert Config(rescue_overlong_max=99,
                      rescue_overlong=8).rescue_slots_max == 99
        # An explicit primary budget above the auto cap is honored in full.
        assert Config(rescue_overlong=100000).rescue_slots_max == 100000

    @pytest.mark.slow
    def test_rescue_off_keeps_round3_accounting(self, rng, oracle):
        text = _mixed_text(rng, long_words=[b"n" * 40] * 4)
        rp, rx = oracle(text, rescue_overlong=0)
        assert b"n" * 40 not in rp.as_dict()
        assert rp.dropped_count == 4
        assert rp.total == rx.total

    @pytest.mark.slow  # ~19 s on the one-core box; tier-1 budget rule
    def test_no_overlong_bit_identical_to_rescue_off(self, rng):
        # The cond guard: overlong-free chunks must produce the same table
        # with rescue on or off (the branch never runs).
        text = _mixed_text(rng)
        t_on = wc.count_table(text, _cfg("pallas", rescue_overlong=64))
        t_off = wc.count_table(text, _cfg("pallas", rescue_overlong=0))
        for a, b in zip(t_on, t_off):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestRescueConfig:
    def test_default_auto_resolves_by_sort_mode(self):
        # None (the default) = 1024 slots under either sort mode.
        assert Config().rescue_slots == 1024
        assert Config(sort_mode="sort3").rescue_slots == 1024
        assert Config(rescue_overlong=64).rescue_slots == 64
        assert Config(rescue_overlong=0).rescue_slots == 0

    def test_window_must_exceed_w(self):
        with pytest.raises(ValueError, match="rescue_window"):
            Config(rescue_overlong=64, rescue_window=32)

    @pytest.mark.slow
    def test_streamed_executor_rescues(self, tmp_path, rng):
        # The engine/executor path flows through the same _map_stream:
        # a multi-chunk streamed run must agree with the XLA oracle.
        from mapreduce_tpu.runtime import executor

        longs = [b"s" * 45] * 6 + [b"t" * 90] * 3
        text = _mixed_text(rng, n_words=3000, long_words=longs)
        p = tmp_path / "corpus.txt"
        p.write_bytes(text)
        cfg = _cfg("pallas", chunk_bytes=128 * 66, rescue_overlong=64,
                   rescue_window=128)
        got = executor.count_file(str(p), cfg)
        rx = wc.count_words(text, _cfg("xla"))
        assert got.as_dict() == rx.as_dict()
        assert got.total == rx.total
