"""costcheck (cost / vmem-budget / kernel-race passes): the contract.

Each new pass is demonstrated by a known-bad fixture that must produce an
error-severity finding — an injected VMEM-overflow kernel, a broken cost
baseline, a seeded cross-iteration ref race, an unreachable spill
fallback — and the shipped models must come back clean (the all-models
gate lives in test_graphcheck and now runs these passes too).  The
round-6 sort pricing (2.6-3.4 effective HBM passes) is asserted as a
machine-checked artifact of the production-shaped wordcount_pallas model.
"""

import json

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mapreduce_tpu import analysis
from mapreduce_tpu import models as models_mod
from mapreduce_tpu.analysis import core as acore
from mapreduce_tpu.analysis.passes.cost import CostPass
from mapreduce_tpu.analysis.passes.kernelrace import KernelRacePass
from mapreduce_tpu.analysis.passes.vmem import (VmemPass,
                                                certify_production_kernels)
from mapreduce_tpu.ops.pallas import meta
from mapreduce_tpu.parallel.mesh import data_mesh


@pytest.fixture(scope="module")
def mesh8():
    return data_mesh(8)


@pytest.fixture(scope="module")
def pallas_ctx(mesh8):
    """One shared context for the production-shaped stable2 model: the
    engine trace is the expensive part, so every pass test reuses it."""
    job = models_mod.build_model("wordcount_pallas")
    return acore.AnalysisContext(job, "wordcount_pallas", mesh=mesh8)


# -- known-bad fixture jobs --------------------------------------------------


class _ScalarJob:
    """Minimal correct job (see test_graphcheck): one uint32 scalar."""

    def init_state(self):
        return jnp.zeros((), jnp.uint32)

    def map_chunk(self, chunk, chunk_id):
        return jnp.sum((chunk != 0).astype(jnp.uint32))

    def combine(self, state, update):
        return state + update

    def merge(self, a, b):
        return a + b

    def finalize(self, state):
        return state


def _copy_kernel(x_ref, o_ref):
    o_ref[:] = x_ref[:]


class VmemHogJob(_ScalarJob):
    """A kernel whose double-buffered blocks blow Mosaic's 16 MB default
    VMEM budget: two (2048, 2048) f32 blocks x 2 (in+out) x 2 (pipeline
    double-buffering) = 64 MiB.  The vmem pass must refuse it."""

    def map_chunk(self, chunk, chunk_id):
        big = jnp.zeros((2048, 2048), jnp.float32) + chunk[0]
        out = pl.pallas_call(
            _copy_kernel,
            grid=(1,),
            in_specs=[pl.BlockSpec((2048, 2048), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((2048, 2048), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((2048, 2048), jnp.float32),
            interpret=True,
        )(big)
        return out[0, 0].astype(jnp.uint32)


def _racy_kernel(x_ref, o_ref):
    # Blind unconditional write to a block every grid iteration revisits:
    # iteration i+1 clobbers iteration i (no read, no pl.when guard).
    o_ref[:] = x_ref[:] * jnp.uint32(2)


class RefRaceJob(_ScalarJob):
    """Seeded cross-iteration write/write hazard: 4 grid iterations all
    write the SAME output block unconditionally."""

    def map_chunk(self, chunk, chunk_id):
        x = (chunk[: 8 * 128].reshape(8, 128)).astype(jnp.uint32)
        out = pl.pallas_call(
            _racy_kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((2, 128), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((2, 128), lambda i: (0, 0),
                                   memory_space=pltpu.VMEM),
            out_shape=jax.ShapeDtypeStruct((2, 128), jnp.uint32),
            interpret=True,
        )(x)
        return out[0, 0]


def _spilly_kernel(x_ref, o_ref, spill_ref):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _():
        spill_ref[0, 0] = jnp.uint32(0)

    o_ref[:] = x_ref[:]
    spill_ref[0, 0] = spill_ref[0, 0] + jnp.uint32(1)


class NoFallbackJob(_ScalarJob):
    """A spill-emitting kernel whose caller never branches on the spill
    counter: the exactness fallback is statically unreachable."""

    def map_chunk(self, chunk, chunk_id):
        x = (chunk[: 8 * 128].reshape(8, 128)).astype(jnp.uint32)
        out, _spill = pl.pallas_call(
            _spilly_kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((2, 128), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=[pl.BlockSpec((2, 128), lambda i: (i, 0),
                                    memory_space=pltpu.VMEM),
                       pl.BlockSpec((1, 1), lambda i: (0, 0),
                                    memory_space=pltpu.SMEM)],
            out_shape=[jax.ShapeDtypeStruct((8, 128), jnp.uint32),
                       jax.ShapeDtypeStruct((1, 1), jnp.uint32)],
            interpret=True,
        )(x)
        return out[0, 0]


def _errors(report, pass_id):
    return [f for f in report.errors if f.pass_id == pass_id]


# -- vmem pass ---------------------------------------------------------------


@pytest.mark.smoke
def test_vmem_pass_flags_overflowing_kernel(mesh8):
    report = analysis.analyze_job(VmemHogJob(), "vmem-hog", mesh=mesh8,
                                  passes=[VmemPass()])
    errs = _errors(report, "vmem-budget")
    assert errs, report.format_text()
    assert any("exceeds" in f.message and "VMEM" in f.message
               for f in errs)
    assert report.exit_code != 0


def test_vmem_pass_flags_unreachable_spill_fallback(mesh8):
    meta.register(meta.KernelMeta(name="_spilly_kernel",
                                  spills=lambda n_out: True,
                                  description="test fixture"))
    report = analysis.analyze_job(NoFallbackJob(), "no-fallback",
                                  mesh=mesh8, passes=[VmemPass()])
    errs = _errors(report, "vmem-budget")
    assert any("fallback" in f.message and "unreachable"
               in f.message for f in errs), report.format_text()


def test_vmem_pass_certifies_pallas_model(pallas_ctx):
    report = acore.run_pipeline(pallas_ctx, [VmemPass()])
    assert not report.errors, report.format_text()
    kernels = report.artifacts["wordcount_pallas"]["vmem"]
    assert any(k["kernel"] == "_tokenize_kernel" for k in kernels)
    for k in kernels:
        assert k["vmem_bytes"] <= (k["vmem_limit_bytes"]
                                   or meta.VMEM_DEFAULT_LIMIT)


def test_production_kernel_plans_certified():
    findings = certify_production_kernels()
    assert findings  # every shipped geometry reports
    assert not [f for f in findings if f.severity == acore.ERROR], \
        "\n".join(f.format() for f in findings)
    # Every tokenize variant covered: compact, pair and fused.
    msgs = " ".join(f.message for f in findings)
    assert "_tokenize_kernel" in msgs and "slots=pair" in msgs
    assert "fused" in msgs
    assert "lane-major" in msgs  # the stable2 geometry


# -- kernel-race pass --------------------------------------------------------


@pytest.mark.smoke
def test_kernelrace_pass_flags_seeded_race(mesh8):
    report = analysis.analyze_job(RefRaceJob(), "ref-race", mesh=mesh8,
                                  passes=[KernelRacePass()])
    errs = _errors(report, "kernel-race")
    assert errs, report.format_text()
    assert any("write/write" in f.message for f in errs)
    assert report.exit_code != 0


def test_kernelrace_pass_accepts_shipped_kernels(pallas_ctx):
    report = acore.run_pipeline(pallas_ctx, [KernelRacePass()])
    assert not report.errors, report.format_text()
    # The SMEM accumulator + carry-scratch discipline is recognized, not
    # merely unseen.
    assert any("read-modify-write" in f.message for f in report.findings)


# -- cost pass ---------------------------------------------------------------


def test_cost_pass_certifies_sort_pricing(pallas_ctx):
    report = acore.run_pipeline(pallas_ctx, [CostPass()])
    assert not report.errors, report.format_text()
    art = report.artifacts["wordcount_pallas"]["cost"]
    sort = art["aggregation_sort"]
    # The static leg: traced rows == geometry formula, and the production
    # extrapolation reproduces the measured 11.2M-row stream.
    assert sort["traced_rows"] == sort["expected_rows"]
    assert sort["production_rows"] == 11206656
    lo, hi = sort["derived_passes"]
    claimed_lo, claimed_hi = sort["claimed_passes"]
    tol = sort["tolerance"]
    assert abs(lo - claimed_lo) <= tol * claimed_lo
    assert abs(hi - claimed_hi) <= tol * claimed_hi


def test_cost_pass_flags_broken_baseline(mesh8, tmp_path, pallas_ctx):
    # A baseline claiming far fewer HBM passes than the program predicts
    # is a regression the gate must catch.  Populate the cost artifact
    # here rather than relying on an earlier test having run the pass on
    # the shared fixture (order-independence; the trace is memoized so
    # the re-run is cheap).
    if "cost" not in pallas_ctx.artifacts:
        acore.run_pipeline(pallas_ctx, [CostPass()])
    real = pallas_ctx.artifacts["cost"]["effective_input_passes"]
    (tmp_path / "wordcount_pallas.json").write_text(json.dumps(
        {"model": "wordcount_pallas",
         "effective_input_passes": real / 10}))
    ctx = acore.AnalysisContext(pallas_ctx.job, "wordcount_pallas",
                                mesh=mesh8, baselines_dir=str(tmp_path))
    ctx._engine_traces = pallas_ctx.engine_traces  # reuse the trace
    report = acore.run_pipeline(ctx, [CostPass()])
    errs = _errors(report, "hbm-cost")
    assert any("regressed" in f.message for f in errs), report.format_text()
    assert report.exit_code != 0


def test_cost_pass_write_then_gate_roundtrip(mesh8, tmp_path, pallas_ctx):
    wctx = acore.AnalysisContext(pallas_ctx.job, "wordcount_pallas",
                                 mesh=mesh8, baselines_dir=str(tmp_path),
                                 write_baselines=True)
    wctx._engine_traces = pallas_ctx.engine_traces
    report = acore.run_pipeline(wctx, [CostPass()])
    assert not report.errors, report.format_text()
    assert (tmp_path / "wordcount_pallas.json").exists()
    # Gate against what was just written: clean.
    gctx = acore.AnalysisContext(pallas_ctx.job, "wordcount_pallas",
                                 mesh=mesh8, baselines_dir=str(tmp_path))
    gctx._engine_traces = pallas_ctx.engine_traces
    report2 = acore.run_pipeline(gctx, [CostPass()])
    assert not report2.errors, report2.format_text()
    assert not [f for f in report2.findings
                if "no cost baseline" in f.message]


def test_checked_in_baselines_cover_all_models():
    from mapreduce_tpu.analysis.passes.cost import load_baseline

    for name in models_mod.model_names():
        base = load_baseline(name)
        assert base is not None, f"missing analysis/baselines/{name}.json"
        assert base["effective_input_passes"] > 0


# -- fusion-opportunity pass (ISSUE 6) ---------------------------------------


class SmallAdjacentJob(_ScalarJob):
    """Two adjacent materializing eqns with a VMEM-sized intermediate —
    cumsum feeds (through a fusible +1) a sort: the canonical candidate
    the fusion-opportunity pass exists to surface."""

    def map_chunk(self, chunk, chunk_id):
        x = chunk[:128].astype(jnp.uint32)
        y = jnp.cumsum(x)
        z = jnp.sort(y + 1)
        return z[0]


class HugeAdjacentJob(_ScalarJob):
    """The same adjacency shape, but the pair's combined working set
    (two 64 MiB f32 planes in flight) dwarfs Mosaic's 16 MB VMEM
    envelope: NOT a candidate — flagging it would send someone chasing a
    fusion that cannot be a kernel (the known-bad fixture of the pass)."""

    def map_chunk(self, chunk, chunk_id):
        big = jnp.zeros((4096, 4096), jnp.float32) + chunk[0]
        y = jnp.cumsum(big, axis=0)
        z = jnp.sort(y, axis=0)
        return z[0, 0].astype(jnp.uint32)


def _fusion_candidates(report, model):
    art = report.artifacts[model]["fusion"]
    return [c for prog in art["programs"].values() for c in prog]


@pytest.mark.smoke
def test_fusion_pass_flags_adjacent_pair(mesh8):
    from mapreduce_tpu.analysis.passes.fusion import FusionPass

    report = analysis.analyze_job(SmallAdjacentJob(), "small-adjacent",
                                  mesh=mesh8, passes=[FusionPass()])
    assert not report.errors, report.format_text()  # candidates are leads
    cands = _fusion_candidates(report, "small-adjacent")
    pair = [c for c in cands
            if c["producer"] == "cumsum" and c["consumer"] == "sort"]
    assert pair, cands
    assert pair[0]["hbm_bytes_saved"] == 2 * pair[0]["intermediate_bytes"]
    assert pair[0]["combined_vmem_bytes"] <= meta.VMEM_DEFAULT_LIMIT
    assert any("candidate fusion" in f.message for f in report.findings)


def test_fusion_pass_respects_vmem_envelope(mesh8):
    """Adjacent materializing eqns whose combined footprint exceeds the
    VMEM envelope must NOT be flagged."""
    from mapreduce_tpu.analysis.passes.fusion import FusionPass

    report = analysis.analyze_job(HugeAdjacentJob(), "huge-adjacent",
                                  mesh=mesh8, passes=[FusionPass()])
    assert not report.errors, report.format_text()
    cands = _fusion_candidates(report, "huge-adjacent")
    assert not [c for c in cands
                if c["producer"] == "cumsum" and c["consumer"] == "sort"], \
        cands
    # The envelope invariant holds for every candidate the pass emits.
    assert all(c["combined_vmem_bytes"] <= meta.VMEM_DEFAULT_LIMIT
               for c in cands), cands


# -- combiner-vs-off cost gate (ISSUE 11) ------------------------------------


@pytest.fixture(scope="module")
def combiner_ctx(mesh8):
    job = models_mod.build_model("wordcount_combiner")
    return acore.AnalysisContext(job, "wordcount_combiner", mesh=mesh8)


def test_cost_gate_certifies_combiner_below_off(combiner_ctx):
    """ISSUE 11 acceptance: the hot-key-combiner model prices strictly
    below its combiner-off twin's checked-in baseline, the artifact
    carries the gap, and the fused-vs-split gate stays out of the way
    (the pair is exempt — its fused-ness is wordcount_fused's
    certificate)."""
    report = acore.run_pipeline(combiner_ctx, [CostPass()])
    assert not report.errors, report.format_text()
    art = report.artifacts["wordcount_combiner"]["cost"]
    gap = art["combiner_vs_off"]
    assert gap["off_model"] == "wordcount_nocombiner"
    assert gap["combiner_effective_input_passes"] \
        < gap["off_effective_input_passes"]
    assert gap["passes_saved"] > 0
    assert "fused_vs_split" not in art
    assert any("combiner certified" in f.message for f in report.findings)


def test_cost_gate_flags_combiner_that_stopped_winning(mesh8, tmp_path,
                                                       combiner_ctx):
    """An off baseline priced BELOW the combiner program = the cache
    stopped deleting sort traffic: ERROR, and no gap is published."""
    if "cost" not in combiner_ctx.artifacts:
        acore.run_pipeline(combiner_ctx, [CostPass()])
    passes = combiner_ctx.artifacts["cost"]["effective_input_passes"]
    chunk = combiner_ctx.artifacts["cost"]["traced_chunk_bytes"]
    (tmp_path / "wordcount_nocombiner.json").write_text(json.dumps(
        {"model": "wordcount_nocombiner",
         "effective_input_passes": passes / 2,
         "traced_chunk_bytes": chunk}))
    (tmp_path / "wordcount_combiner.json").write_text(json.dumps(
        {"model": "wordcount_combiner",
         "effective_input_passes": passes,
         "traced_chunk_bytes": chunk}))
    ctx = acore.AnalysisContext(combiner_ctx.job, "wordcount_combiner",
                                mesh=mesh8, baselines_dir=str(tmp_path))
    ctx._engine_traces = combiner_ctx.engine_traces  # reuse the trace
    report = acore.run_pipeline(ctx, [CostPass()])
    errs = _errors(report, "hbm-cost")
    assert any("NOT strictly below" in f.message for f in errs), \
        report.format_text()
    assert report.exit_code != 0


def test_cost_gate_refuses_combiner_incomparable_geometry(mesh8, tmp_path,
                                                          combiner_ctx):
    """An off baseline priced at a different chunk cannot gate the
    combiner model, and the incomparable gap must not be published."""
    if "cost" not in combiner_ctx.artifacts:
        acore.run_pipeline(combiner_ctx, [CostPass()])
    passes = combiner_ctx.artifacts["cost"]["effective_input_passes"]
    chunk = combiner_ctx.artifacts["cost"]["traced_chunk_bytes"]
    (tmp_path / "wordcount_nocombiner.json").write_text(json.dumps(
        {"model": "wordcount_nocombiner",
         "effective_input_passes": passes * 2,
         "traced_chunk_bytes": chunk * 2}))
    (tmp_path / "wordcount_combiner.json").write_text(json.dumps(
        {"model": "wordcount_combiner",
         "effective_input_passes": passes,
         "traced_chunk_bytes": chunk}))
    ctx = acore.AnalysisContext(combiner_ctx.job, "wordcount_combiner",
                                mesh=mesh8, baselines_dir=str(tmp_path))
    ctx._engine_traces = combiner_ctx.engine_traces
    report = acore.run_pipeline(ctx, [CostPass()])
    errs = _errors(report, "hbm-cost")
    assert any("not comparable" in f.message for f in errs), \
        report.format_text()
    assert "combiner_vs_off" not in \
        report.artifacts["wordcount_combiner"]["cost"]


# -- fused-vs-split cost gate (ISSUE 6) --------------------------------------


@pytest.fixture(scope="module")
def fused_ctx(mesh8):
    job = models_mod.build_model("wordcount_fused")
    return acore.AnalysisContext(job, "wordcount_fused", mesh=mesh8)


@pytest.mark.smoke
def test_cost_gate_certifies_fused_below_split(fused_ctx):
    """The machine-checked before/after: the fused model prices strictly
    below the split-path baseline, and the artifact carries the gap."""
    report = acore.run_pipeline(fused_ctx, [CostPass()])
    assert not report.errors, report.format_text()
    art = report.artifacts["wordcount_fused"]["cost"]
    gap = art["fused_vs_split"]
    assert gap["split_model"] == "wordcount_pallas"
    assert gap["fused_effective_input_passes"] \
        < gap["split_effective_input_passes"]
    assert gap["passes_saved"] > 0
    assert any("fusion certified" in f.message for f in report.findings)


def test_cost_gate_flags_fusion_that_stopped_winning(mesh8, tmp_path,
                                                     fused_ctx):
    """A split baseline priced BELOW the fused program = the fusion
    stopped deleting traffic: ERROR."""
    if "cost" not in fused_ctx.artifacts:
        acore.run_pipeline(fused_ctx, [CostPass()])
    fused_passes = fused_ctx.artifacts["cost"]["effective_input_passes"]
    chunk = fused_ctx.artifacts["cost"]["traced_chunk_bytes"]
    (tmp_path / "wordcount_pallas.json").write_text(json.dumps(
        {"model": "wordcount_pallas",
         "effective_input_passes": fused_passes / 2,
         "traced_chunk_bytes": chunk}))
    # The fused model's own baseline must still gate clean from tmp_path.
    (tmp_path / "wordcount_fused.json").write_text(json.dumps(
        {"model": "wordcount_fused",
         "effective_input_passes": fused_passes,
         "traced_chunk_bytes": chunk}))
    ctx = acore.AnalysisContext(fused_ctx.job, "wordcount_fused",
                                mesh=mesh8, baselines_dir=str(tmp_path))
    ctx._engine_traces = fused_ctx.engine_traces  # reuse the trace
    report = acore.run_pipeline(ctx, [CostPass()])
    errs = _errors(report, "hbm-cost")
    assert any("NOT strictly below" in f.message for f in errs), \
        report.format_text()
    assert report.exit_code != 0


def test_cost_gate_refuses_incomparable_chunk_geometry(mesh8, tmp_path,
                                                       fused_ctx):
    """A split baseline priced at a DIFFERENT chunk geometry cannot gate
    the fused model: passes are per-chunk, comparing them is nonsense."""
    if "cost" not in fused_ctx.artifacts:
        acore.run_pipeline(fused_ctx, [CostPass()])
    fused_passes = fused_ctx.artifacts["cost"]["effective_input_passes"]
    chunk = fused_ctx.artifacts["cost"]["traced_chunk_bytes"]
    (tmp_path / "wordcount_pallas.json").write_text(json.dumps(
        {"model": "wordcount_pallas",
         "effective_input_passes": fused_passes * 2,
         "traced_chunk_bytes": chunk * 2}))
    (tmp_path / "wordcount_fused.json").write_text(json.dumps(
        {"model": "wordcount_fused",
         "effective_input_passes": fused_passes,
         "traced_chunk_bytes": chunk}))
    ctx = acore.AnalysisContext(fused_ctx.job, "wordcount_fused",
                                mesh=mesh8, baselines_dir=str(tmp_path))
    ctx._engine_traces = fused_ctx.engine_traces
    report = acore.run_pipeline(ctx, [CostPass()])
    errs = _errors(report, "hbm-cost")
    assert any("not" in f.message and "comparable" in f.message
               for f in errs), report.format_text()
    # The rejected gap must NOT be published: bench._cost_record copies
    # the artifact verbatim into BENCH JSON.
    assert "fused_vs_split" not in report.artifacts["wordcount_fused"]["cost"]


def test_cost_gate_flags_malformed_split_baseline(mesh8, tmp_path,
                                                  fused_ctx):
    """A split baseline with a zero/missing effective_input_passes AND a
    different chunk geometry must name the broken BASELINE — not publish
    a nonsense gap, and not misdiagnose as 'the fusion stopped deleting
    traffic' (the old `split_ref > 0` guard skipped the geometry check
    on exactly this input)."""
    if "cost" not in fused_ctx.artifacts:
        acore.run_pipeline(fused_ctx, [CostPass()])
    fused_passes = fused_ctx.artifacts["cost"]["effective_input_passes"]
    chunk = fused_ctx.artifacts["cost"]["traced_chunk_bytes"]
    (tmp_path / "wordcount_pallas.json").write_text(json.dumps(
        {"model": "wordcount_pallas",
         "effective_input_passes": 0.0,
         "traced_chunk_bytes": chunk * 2}))
    (tmp_path / "wordcount_fused.json").write_text(json.dumps(
        {"model": "wordcount_fused",
         "effective_input_passes": fused_passes,
         "traced_chunk_bytes": chunk}))
    ctx = acore.AnalysisContext(fused_ctx.job, "wordcount_fused",
                                mesh=mesh8, baselines_dir=str(tmp_path))
    ctx._engine_traces = fused_ctx.engine_traces
    report = acore.run_pipeline(ctx, [CostPass()])
    errs = _errors(report, "hbm-cost")
    assert any("no usable effective_input_passes" in f.message
               for f in errs), report.format_text()
    assert not any("NOT strictly below" in f.message for f in errs)
    assert "fused_vs_split" not in report.artifacts["wordcount_fused"]["cost"]


def test_cost_gate_refuses_baseline_missing_geometry(mesh8, tmp_path,
                                                     fused_ctx):
    """A split baseline that never recorded traced_chunk_bytes cannot be
    certified geometry-comparable: missing must gate like mismatched,
    not wildcard-match and publish the gap."""
    if "cost" not in fused_ctx.artifacts:
        acore.run_pipeline(fused_ctx, [CostPass()])
    fused_passes = fused_ctx.artifacts["cost"]["effective_input_passes"]
    chunk = fused_ctx.artifacts["cost"]["traced_chunk_bytes"]
    (tmp_path / "wordcount_pallas.json").write_text(json.dumps(
        {"model": "wordcount_pallas",
         "effective_input_passes": fused_passes * 2}))
    (tmp_path / "wordcount_fused.json").write_text(json.dumps(
        {"model": "wordcount_fused",
         "effective_input_passes": fused_passes,
         "traced_chunk_bytes": chunk}))
    ctx = acore.AnalysisContext(fused_ctx.job, "wordcount_fused",
                                mesh=mesh8, baselines_dir=str(tmp_path))
    ctx._engine_traces = fused_ctx.engine_traces
    report = acore.run_pipeline(ctx, [CostPass()])
    errs = _errors(report, "hbm-cost")
    assert any("not comparable" in f.message for f in errs), \
        report.format_text()
    assert "fused_vs_split" not in report.artifacts["wordcount_fused"]["cost"]


class SharedJaxprJob(_ScalarJob):
    """Two same-shaped jnp.sort calls: JAX's jit cache hands both the
    SAME inner jaxpr (and Var objects).  A later cumsum consumes the
    FIRST sort's result — NOT adjacent (the second sort sits between) —
    so no sort->cumsum candidate may appear.  Guards the value-id
    canonicalization in _scan_scope: keying on shared Vars would alias
    the two calls' results and fabricate exactly that candidate."""

    def map_chunk(self, chunk, chunk_id):
        x = chunk[:128].astype(jnp.uint32)
        a = jnp.sort(x)
        b = jnp.sort(x + 2)
        z = jnp.cumsum(a)
        return z[0] + b[0]


def test_fusion_pass_does_not_alias_cached_jaxpr_calls(mesh8):
    from mapreduce_tpu.analysis.passes.fusion import FusionPass

    report = analysis.analyze_job(SharedJaxprJob(), "shared-jaxpr",
                                  mesh=mesh8, passes=[FusionPass()])
    cands = _fusion_candidates(report, "shared-jaxpr")
    assert not [c for c in cands
                if c["producer"] == "sort" and c["consumer"] == "cumsum"], \
        cands


class DowncastChainJob(_ScalarJob):
    """cumsum(uint32) -> astype(uint8) -> sort: the value round-tripping
    HBM is cumsum's 4-byte-per-element OUTPUT, not the 1-byte derived
    operand the sort consumes — pricing the consumer-side aval would
    report the saved traffic 4x too small."""

    def map_chunk(self, chunk, chunk_id):
        x = chunk[:128].astype(jnp.uint32)
        y = jnp.cumsum(x)
        z = jnp.sort((y & 0xFF).astype(jnp.uint8))
        return z[0].astype(jnp.uint32)


def test_fusion_pass_prices_materialized_producer_output(mesh8):
    from mapreduce_tpu.analysis.passes.fusion import FusionPass

    report = analysis.analyze_job(DowncastChainJob(), "downcast-chain",
                                  mesh=mesh8, passes=[FusionPass()])
    pair = [c for c in _fusion_candidates(report, "downcast-chain")
            if c["producer"] == "cumsum" and c["consumer"] == "sort"]
    assert pair, _fusion_candidates(report, "downcast-chain")
    # 128 x uint32 = 512 bytes materialized (NOT 128 x uint8 = 128).
    assert pair[0]["intermediate_bytes"] == 512, pair


class FanoutIntermediateJob(_ScalarJob):
    """cumsum feeds the adjacent sort AND a later equation: the fused
    kernel deletes the sort's READ of the intermediate, but its WRITE
    must stay for the other consumer — crediting 2x here would inflate
    the candidate over genuinely single-consumer fusions."""

    def map_chunk(self, chunk, chunk_id):
        x = chunk[:128].astype(jnp.uint32)
        y = jnp.cumsum(x)
        z = jnp.sort(y)
        return z[0] + y[0]  # y escapes the chain


def test_fusion_pass_keeps_write_for_fanout_intermediate(mesh8):
    from mapreduce_tpu.analysis.passes.fusion import FusionPass

    report = analysis.analyze_job(FanoutIntermediateJob(), "fanout-inter",
                                  mesh=mesh8, passes=[FusionPass()])
    pair = [c for c in _fusion_candidates(report, "fanout-inter")
            if c["producer"] == "cumsum" and c["consumer"] == "sort"]
    assert pair, _fusion_candidates(report, "fanout-inter")
    # Read saved, write preserved: 1x the intermediate, not 2x.
    assert pair[0]["hbm_bytes_saved"] == pair[0]["intermediate_bytes"], pair


# -- telemetry-overhead cost gate (ISSUE 8) ----------------------------------


@pytest.fixture(scope="module")
def telemetry_ctx(mesh8):
    """Context for the data-stats-instrumented stable2 model: the traced
    step is the INSTRUMENTED program telemetered runs dispatch."""
    job = models_mod.build_model("wordcount_telemetry")
    return acore.AnalysisContext(job, "wordcount_telemetry", mesh=mesh8)


@pytest.mark.smoke
def test_cost_gate_certifies_telemetry_overhead(telemetry_ctx):
    """ISSUE 8 acceptance: the instrumented model prices within 1% of the
    uninstrumented twin's checked-in baseline, and the artifact carries
    the measured overhead."""
    report = acore.run_pipeline(telemetry_ctx, [CostPass()])
    assert not report.errors, report.format_text()
    art = report.artifacts["wordcount_telemetry"]["cost"]
    ov = art["telemetry_overhead"]
    assert ov["plain_model"] == "wordcount_pallas"
    assert abs(ov["overhead_frac"]) <= ov["tolerance"] == 0.01, ov
    assert ov["instrumented_effective_input_passes"] \
        >= ov["plain_effective_input_passes"], \
        "instrumentation can only add traffic"
    assert any("telemetry overhead certified" in f.message
               for f in report.findings)


def test_cost_gate_flags_telemetry_overhead_regression(mesh8, tmp_path,
                                                       telemetry_ctx):
    """A plain baseline priced well below the instrumented program =
    observability grew the HBM bill past the 1% gate: ERROR."""
    if "cost" not in telemetry_ctx.artifacts:
        acore.run_pipeline(telemetry_ctx, [CostPass()])
    instr = telemetry_ctx.artifacts["cost"]["effective_input_passes"]
    chunk = telemetry_ctx.artifacts["cost"]["traced_chunk_bytes"]
    (tmp_path / "wordcount_pallas.json").write_text(json.dumps(
        {"model": "wordcount_pallas",
         "effective_input_passes": instr / 1.5,
         "traced_chunk_bytes": chunk}))
    # Own regression baseline stays clean so only the overhead gate fires.
    (tmp_path / "wordcount_telemetry.json").write_text(json.dumps(
        {"model": "wordcount_telemetry",
         "effective_input_passes": instr,
         "traced_chunk_bytes": chunk}))
    ctx = acore.AnalysisContext(telemetry_ctx.job, "wordcount_telemetry",
                                mesh=mesh8, baselines_dir=str(tmp_path))
    ctx._engine_traces = telemetry_ctx.engine_traces  # reuse the trace
    report = acore.run_pipeline(ctx, [CostPass()])
    errs = _errors(report, "hbm-cost")
    assert any("observability is regressing" in f.message for f in errs), \
        report.format_text()
    assert report.exit_code != 0


def test_cost_gate_flags_missing_plain_counterpart(mesh8, tmp_path,
                                                   telemetry_ctx):
    """No uninstrumented baseline = the overhead cannot be gated: ERROR
    (mirrors the fused gate's missing-counterpart contract)."""
    if "cost" not in telemetry_ctx.artifacts:
        acore.run_pipeline(telemetry_ctx, [CostPass()])
    instr = telemetry_ctx.artifacts["cost"]["effective_input_passes"]
    chunk = telemetry_ctx.artifacts["cost"]["traced_chunk_bytes"]
    (tmp_path / "wordcount_telemetry.json").write_text(json.dumps(
        {"model": "wordcount_telemetry",
         "effective_input_passes": instr,
         "traced_chunk_bytes": chunk}))
    ctx = acore.AnalysisContext(telemetry_ctx.job, "wordcount_telemetry",
                                mesh=mesh8, baselines_dir=str(tmp_path))
    ctx._engine_traces = telemetry_ctx.engine_traces
    report = acore.run_pipeline(ctx, [CostPass()])
    errs = _errors(report, "hbm-cost")
    assert any("telemetry overhead cannot be gated" in f.message
               for f in errs), report.format_text()
