"""Kernel-geometry search tests (ISSUE 12): the jax-free enumerator/
certifier/pricing lattice against hand arithmetic, the Config.geometry
surface, bit-identity of results across certified geometries, the
tuner's geometry knob (try/revert + oscillation guard), and the
graphcheck certification of shortlisted candidates."""

import json
import os
import sys

import pytest

from mapreduce_tpu.analysis import geometry as geom_mod
from mapreduce_tpu.config import (DEFAULT_GEOMETRY, GEOMETRY_PRESETS,
                                  Config, Geometry)
from mapreduce_tpu.ops.pallas import meta
from mapreduce_tpu.tuning import engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(REPO, "tools", "fixtures")


def _fixture(name: str) -> list:
    with open(os.path.join(FIXTURES, name + ".jsonl")) as f:
        return [json.loads(line) for line in f if line.strip()]


# -- the constructor is the single source of truth ---------------------------

@pytest.mark.smoke
def test_default_geometry_reproduces_shipped_plans():
    """Acceptance: the shipped default geometries are reproduced EXACTLY
    by the constructor — bit-identical vmem_plan footprints (the values
    are the pre-refactor hand-maintained production_plans list's)."""
    expected = [(508416, 12, 67108864), (352768, 12, 67108864),
                (475648, 8, None), (729600, 12, 67108864),
                (860672, 12, 67108864), (631296, 8, None)]
    plans = meta.production_plans()
    got = [(p.vmem_bytes, p.smem_bytes, p.vmem_limit_bytes) for p in plans]
    assert got == expected, got
    # production_plans IS geometry_plans(DEFAULT_GEOMETRY): one constructor.
    assert [p.as_dict() for p in plans] == \
        [p.as_dict() for p in meta.geometry_plans(DEFAULT_GEOMETRY)]
    # The kernel wrappers delegate to the same constructor.
    from mapreduce_tpu.ops.pallas import tokenize

    assert tokenize.vmem_plan(block_rows=384, compact_slots=128,
                              lane_major=True).as_dict() == \
        plans[0].as_dict()
    assert tokenize.vmem_plan(block_rows=256).as_dict() == \
        plans[2].as_dict()


def test_enumerator_candidates_all_pass_vmem_budget():
    """Acceptance: every EMITTED candidate passes the static certifier by
    construction (over-budget lattice points are dropped, not flagged)."""
    cands = geom_mod.enumerate_candidates()
    assert len(cands) >= 30
    assert all(not geom_mod.certify(c.geometry) for c in cands)
    assert sum(c.axis == "default" for c in cands) == 1
    # Every candidate's plans stay within the budgets the vmem pass
    # enforces — re-checked against the raw plan arithmetic.
    for c in cands:
        for plan in meta.geometry_plans(c.geometry):
            assert plan.vmem_bytes <= plan.budget, (c.label, plan.geometry)
            assert plan.smem_bytes <= meta.SMEM_BUDGET


def test_known_overflow_candidate_rejected():
    """A tile-legal but over-budget geometry is rejected by the
    certifier, not the dataclass: 16384-row pair windows blow Mosaic's
    16 MB default stack budget, which the pair path does not override."""
    bad = Geometry(pair_block_rows=16384)
    errs = geom_mod.certify(bad)
    assert errs and any("16 MiB budget" in e for e in errs), errs
    assert all(c.geometry != bad for c in geom_mod.enumerate_candidates())


def test_cost_ranking_matches_pr11_hand_arithmetic():
    """The PR-11 measured pair is the free oracle: 384x128 windows give
    11,206,656 stable2 sort rows per 32 MB chunk, 512x128 give 8,404,992
    (−25%), so tall512 must price BELOW the default — with the spill
    risk flagged (114 ends / 384 bytes measured density → 152 > 128
    slots at the taller window)."""
    assert geom_mod.stable2_sort_rows(1 << 25, 384, 128) == 11206656
    assert geom_mod.stable2_sort_rows(1 << 25, 512, 128) == 8404992
    cands = geom_mod.enumerate_candidates()
    default = next(c for c in cands if c.axis == "default")
    tall = next(c for c in cands if c.label == "tall512")
    assert default.sort_rows == 11206656
    assert tall.sort_rows == 8404992
    assert tall.spill_risk and not default.spill_risk
    sl = geom_mod.shortlist(cands, 5)
    assert sl.index(tall) < len(sl)
    assert [c.sort_rows for c in sl] == sorted(c.sort_rows for c in sl)
    # The cost pass reads the same formula (the re-export contract).
    from mapreduce_tpu.analysis import costmodel

    assert costmodel.stable2_sort_rows is geom_mod.stable2_sort_rows


# -- Config surface ----------------------------------------------------------

@pytest.mark.smoke
def test_config_geometry_validation_and_resolution():
    # Presets resolve; labels round-trip; dicts convert to the frozen
    # dataclass (Config stays hashable — a static jit argument).
    assert Config().geometry_label == "default"
    assert Config(geometry="auto").geometry_label == "default"
    c = Config(geometry="tall512")
    assert c.resolved_block_rows == 512 and c.geometry_label == "tall512"
    d = Config(geometry={"block_rows": 512})
    assert d.geometry == Geometry(block_rows=512)
    assert d.geometry_label == "custom" and hash(d)
    # Explicit default-valued dict reads as the default label.
    assert Config(geometry=Geometry()).geometry_label == "default"
    # combiner16 deepens the cache without touching windows.
    c16 = Config(geometry="combiner16", map_impl="fused",
                 combiner="hot-cache")
    assert c16.resolved_combiner_slots == 16
    assert c16.resolved_block_rows == 512  # combiner window unchanged
    # The None-sentinel contract: default geometry defers to kernel
    # defaults everywhere (the pre-ISSUE-12 traced programs exactly).
    base = Config()
    assert base.resolved_pair_block_rows is None
    assert base.resolved_aux_rows is None
    assert Config(sort_mode="sort3").resolved_block_rows is None
    # Non-default fields thread through the resolvers.
    g = Geometry(pair_block_rows=384, aux_rows=128,
                 sort3_block_rows=384, sort3_slots=128)
    cg = Config(geometry=g)
    assert cg.resolved_pair_block_rows == 384
    assert cg.resolved_aux_rows == 128
    assert Config(geometry=g, sort_mode="sort3").resolved_block_rows == 384
    assert Config(geometry=g,
                  sort_mode="sort3").resolved_compact_slots == 128
    with pytest.raises(ValueError, match="geometry"):
        Config(geometry="bogus")
    with pytest.raises(ValueError, match="geometry"):
        Config(geometry=42)
    with pytest.raises(ValueError, match="compact_slots"):
        Config(geometry={"compact_slots": 120})
    for bad in (dict(block_rows=200), dict(aux_rows=64),
                dict(sort3_slots=100), dict(pair_block_rows=200),
                dict(combiner_slots=12), dict(block_rows=128),
                dict(sort3_block_rows=100)):
        with pytest.raises(ValueError):
            Geometry(**bad)
    # Presets are themselves valid and include the documented pair arm.
    assert GEOMETRY_PRESETS["default"] == DEFAULT_GEOMETRY
    assert GEOMETRY_PRESETS["tall512"].block_rows == 512


def test_run_start_geometry_stamp_shapes():
    """The ledger stamp: label always; the full spec dict only on custom
    runs (a preset name already names its spec)."""
    from mapreduce_tpu.runtime.executor import _geometry_stamp

    assert _geometry_stamp(Config()) == {"geometry": "default"}
    assert _geometry_stamp(Config(geometry="tall512")) == \
        {"geometry": "tall512"}
    st = _geometry_stamp(Config(geometry={"block_rows": 640}))
    assert st["geometry"] == "custom"
    assert st["geometry_spec"]["block_rows"] == 640


# -- bit-identity across certified geometries --------------------------------

@pytest.mark.smoke
def test_kernel_stream_identity_across_geometries():
    """The fused kernel's live emission SEQUENCE (lane-major = global
    byte-position order) is identical across window heights and aux
    sizes — geometry only repartitions the windows and pads.  Kernel
    level with a small lookback (w=8) so two interpret compiles stay
    fast-tier; the full wordcount/ngram path identity is the @slow test
    below."""
    import numpy as np
    import jax.numpy as jnp

    from mapreduce_tpu.ops.pallas import tokenize as pt

    raw = (b"the quick brown fox honorificabilitudinitatibus jumps "
           b"over a lazy dog " * 150)[:8192]
    data = jnp.asarray(np.frombuffer(raw, np.uint8))

    def live(block_rows, aux_rows=None):
        s, overlong, spill = pt.tokenize_fused(
            data, compact_slots=128, lane_major=True,
            block_rows=block_rows, aux_rows=aux_rows, max_token_bytes=8)
        khi, klo, pk = map(np.asarray, (s.key_hi, s.key_lo, s.packed))
        keep = pk != 0xFFFFFFFF
        return (list(zip(khi[keep], klo[keep], pk[keep])),
                int(s.total), int(overlong), int(spill))

    base = live(384)
    tall = live(512, aux_rows=128)
    assert base == tall
    assert base[0] and base[2] > 0, "corpus must exercise poison rows"


@pytest.mark.slow
def test_wordcount_bit_identity_across_geometries():
    """Acceptance: a non-default certified candidate produces
    bit-identical wordcount results to the default geometry — the
    emission set, fallback exactness and accounting are geometry-
    independent; only the cost moves.  @slow per the >=10 s line (four
    interpret compiles of the full aggregation program); the fast tier
    keeps the kernel-level stream identity above."""
    from mapreduce_tpu.models import wordcount

    def counts(data: bytes, **cfg_kw):
        r = wordcount.count_words(
            data, Config(backend="pallas", chunk_bytes=1 << 14,
                         table_capacity=1 << 11, **cfg_kw))
        return r.words, r.counts, r.total, r.dropped_count

    data = (b"the quick brown fox jumps over the lazy dog " * 150
            + b"u" * 40 + b" tail words here ")
    base = counts(data)
    assert base == counts(data, geometry="tall512")
    assert base == counts(data, geometry={"block_rows": 256,
                                          "aux_rows": 128})


@pytest.mark.slow
def test_fused_and_ngram_bit_identity_across_geometries():
    """The fused map path and the gram family under a custom geometry
    (taller windows + taller aux plane + wider pair fallback) match the
    default bit-for-bit (the acceptance's ngram leg)."""
    from mapreduce_tpu.models import wordcount

    data = (b"alpha beta gamma alpha delta " * 200).rstrip()
    geom = {"block_rows": 512, "aux_rows": 128, "pair_block_rows": 384}

    def fused(geometry=None):
        r = wordcount.count_words(
            data, Config(backend="pallas", chunk_bytes=1 << 14,
                         table_capacity=1 << 11, map_impl="fused",
                         geometry=geometry))
        return r.words, r.counts, r.total

    assert fused() == fused(geom)

    def grams(geometry=None):
        r = wordcount.count_ngrams(
            data, 2, Config(backend="pallas", chunk_bytes=1 << 14,
                            table_capacity=1 << 11, map_impl="fused",
                            geometry=geometry))
        return r.words, r.counts, r.total

    assert grams() == grams(geom)


# -- the tuner's geometry knob (the second non-numeric knob) -----------------

@pytest.mark.smoke
def test_tuner_proposes_and_reverts_geometry():
    p = engine.propose(_fixture("tuner_geometry"))
    assert p["rule"] == "try-geometry"
    assert p["changed"] == {"geometry": ["default", "tall512"]}
    assert p["signals"]["window_occupancy"] == 0.55
    engine.validate_knobs(p["proposal"])
    p2 = engine.propose(_fixture("tuner_geomspill"))
    assert p2["rule"] == "revert-geometry"
    assert p2["changed"] == {"geometry": ["tall512", "default"]}
    engine.validate_knobs(p2["proposal"])
    # A default-geometry spill-bound run keeps the foreign-knob note
    # (its knob is --compact-slots, not a geometry this tuner set).
    spill_default = [dict(r, geometry="default")
                     for r in _fixture("tuner_geomspill")]
    pd = engine.propose(spill_default)
    assert pd["rule"] != "revert-geometry", pd["rule"]
    assert any(t["rule"] == "data-spill-bound" for t in pd["trail"])


def test_tuner_geometry_oscillation_guard():
    """Acceptance: the tuner can propose a geometry change that survives
    validate_knobs, and the oscillation guard stops the try/revert pair
    on the new non-numeric knob."""
    geom_recs, spill_recs = _fixture("tuner_geometry"), \
        _fixture("tuner_geomspill")
    r = engine.search(
        lambda k: geom_recs if k["geometry"] == "default" else spill_recs,
        {"chunk_bytes": 1 << 21, "superstep": 1, "inflight_groups": 4,
         "prefetch_depth": 4}, budget=8)
    assert r["stopped"] == "oscillation" and r["passes"] == 2
    assert [t["rule"] for t in r["trail"]] == \
        ["try-geometry", "revert-geometry"]
    for t in r["trail"]:
        engine.validate_knobs(t["proposal"])
    assert "geometry" in engine.KNOBS
    assert engine.default_knobs()["geometry"] == "default"


def test_tuner_geometry_gated_off_when_combiner_on():
    """With the hot-key cache on, windows are already tall (the
    combiner_block_rows geometry): try-geometry must not fire."""
    recs = [dict(r, combiner="hot-cache") if r.get("kind") == "run_start"
            else r for r in _fixture("tuner_geometry")]
    p = engine.propose(recs)
    assert p["rule"] != "try-geometry", p["rule"]


# -- CLI surface -------------------------------------------------------------

@pytest.mark.smoke
def test_cli_geometry_surface(tmp_path, capsys):
    from mapreduce_tpu import cli

    f = tmp_path / "in.txt"
    f.write_text("a b a c\n")
    with pytest.raises(SystemExit) as exc:
        cli.main([str(f), "--geometry", "bogus"])
    assert exc.value.code == 2
    capsys.readouterr()
    assert cli.main([str(f), "--no-echo", "--format", "json",
                     "--geometry", "tall512"]) == 0
    capsys.readouterr()
    # 'auto' with no profile resolves to the default, loudly.
    assert cli.main([str(f), "--no-echo", "--format", "json",
                     "--geometry", "auto", "--geometry-profile",
                     str(tmp_path / "missing.json")]) == 0
    assert "geometry: auto -> default" in capsys.readouterr().err
    # 'auto' against a searched profile resolves and stamps the ledger.
    prof = tmp_path / "tuned.json"
    prof.write_text(json.dumps({"profiles": {
        "wordcount-geometry/cpu/zipf": {
            "recorded_at": "2026-08-04T00:00:00Z",
            "config": {"geometry": "tall512"}}}}))
    led = tmp_path / "led.jsonl"
    assert cli.main([str(f), "--no-echo", "--format", "json",
                     "--geometry", "auto", "--geometry-profile",
                     str(prof), "--ledger", str(led)]) == 0
    assert "geometry: auto -> tall512" in capsys.readouterr().err
    from mapreduce_tpu import obs

    start = next(r for r in obs.read_ledger(str(led))
                 if r["kind"] == "run_start")
    assert start["geometry"] == "tall512"
    assert start["ledger_version"] == obs.LEDGER_VERSION == 10


# -- the search artifact / selftest entry ------------------------------------

def test_geomsearch_selftest_entry():
    """The tools/geomsearch.py selftest (the tier-1/smoke gate) passes
    from pytest too — one entry point, wherever it is invoked from."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import geomsearch
    finally:
        sys.path.pop(0)
    assert geomsearch.selftest() == 0


def test_search_artifact_schema():
    cands = geom_mod.enumerate_candidates()
    art = geom_mod.search_artifact(cands, 3)
    assert art["geometry_search_version"] == \
        geom_mod.GEOMETRY_SEARCH_VERSION
    assert art["candidates"] == len(cands)
    assert len(art["shortlist"]) == 3
    for entry in art["shortlist"]:
        assert set(entry) == {"label", "axis", "sort_rows",
                              "sort_pass_bytes", "vmem_peak_bytes",
                              "spill_risk",
                              "geometry"}
        Geometry(**entry["geometry"])  # the spec round-trips
    json.dumps(art)


# -- graphcheck certification of a candidate ---------------------------------

@pytest.mark.slow
def test_graphcheck_certifies_shortlist_candidate():
    """Acceptance: a shortlisted candidate passes the full baseline-free
    graphcheck pipeline (vmem-budget, kernel-race, spill-reachability,
    host-sync, sharding, algebra, overflow) with zero errors — the
    geometry changes static shapes, never the certified disciplines."""
    from mapreduce_tpu import analysis
    from mapreduce_tpu.models.wordcount import WordCountJob

    passes = [p for p in analysis.default_pipeline()
              if p.pass_id not in ("hbm-cost", "fusion-opportunity")]
    cfg = Config(chunk_bytes=128 * 512, table_capacity=512,
                 backend="pallas", map_impl="fused", geometry="tall512")
    report = analysis.analyze_job(WordCountJob(cfg), "<geometry:tall512>",
                                  passes=passes)
    assert not report.errors, report.format_text("error")


@pytest.mark.slow
def test_cost_pass_prices_candidate_geometry():
    """The hbm-cost pass re-derives stable2_sort_rows from the CANDIDATE
    geometry: the traced sort equation must match the candidate's own
    window arithmetic exactly, the artifact must carry the geometry
    label, and the measured-rates leg must be pinned to the default."""
    from mapreduce_tpu import analysis
    from mapreduce_tpu.models.wordcount import WordCountJob

    cfg = Config(chunk_bytes=128 * 512, table_capacity=512,
                 backend="pallas", geometry="tall512")
    report = analysis.analyze_job(WordCountJob(cfg), "<geom-cost>")
    errors = [f for f in report.findings if f.severity == "error"
              and f.pass_id == "hbm-cost"
              and "baseline" not in f.message]
    assert not errors, [f.message for f in errors]
    art = report.artifacts.get("<geom-cost>", {}).get("cost", {})
    assert art.get("geometry") == "tall512"
    sort_art = art.get("aggregation_sort", {})
    assert sort_art.get("traced_rows") == sort_art.get("expected_rows") \
        == geom_mod.stable2_sort_rows(128 * 512, 512, 128)
    assert "skipped" in sort_art.get("measured_leg", "")
