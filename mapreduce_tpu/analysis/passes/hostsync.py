"""Pass: host-sync & recompile-hazard detector.

The streamed-ingest bench spent 66 s of a 97 s run inside ``dispatch``
(round 5) — the classic smell of a hot loop that re-enters Python, blocks
on the host, or recompiles.  The worst offenders are *visible statically*
in the step program's jaxpr:

* **callbacks** (``pure_callback``/``io_callback``/``debug_callback``/
  ``debug_print``) inside a jitted body force a device->host->device round
  trip per step — ERROR on the step/finish hot paths;
* **infeed/outfeed** likewise couple every step to the host — ERROR;
* **large baked-in constants**: a big array captured as a jaxpr constant
  (instead of passed as an argument) is re-uploaded per executable and —
  when the Python value varies per call — forces a fresh compile each
  step, the direct recompile hazard of unhashable/varying "static" args.
  WARNING above 1 MiB;
* **program size**: per-dispatch overhead scales with program size; the
  pass reports eqn counts (INFO) so a dispatch-bound phase report can be
  attributed without profiling.

The executor hot path keeps ``step_index`` a *traced* uint32 argument
(``Engine.step`` converts before dispatch) — the pass asserts the traced
step program indeed has the step scalar as an input rather than a constant.
"""

from __future__ import annotations

import numpy as np

from mapreduce_tpu.analysis import core, trace

_CALLBACKS = {"pure_callback", "io_callback", "debug_callback",
              "debug_print", "python_callback"}
_HOST_COUPLING = {"infeed", "outfeed"}
_CONST_WARN_BYTES = 1 << 20


def _const_bytes(jaxpr) -> list[tuple[int, str]]:
    """(nbytes, dtype/shape repr) of every jaxpr constant, recursive."""
    out = []

    def one(closed):
        consts = getattr(closed, "consts", None) or ()
        for c in consts:
            arr = np.asarray(c) if hasattr(c, "shape") else None
            if arr is not None:
                out.append((int(arr.size) * arr.dtype.itemsize,
                            f"{arr.dtype}[{','.join(map(str, arr.shape))}]"))

    one(jaxpr)
    for eqn, _ in trace.iter_eqns(jaxpr):
        for sub in trace.eqn_subjaxprs(eqn):
            one(sub)
    return out


@core.register_pass
class HostSyncPass:
    pass_id = "host-sync"
    description = ("callbacks / host coupling / baked constants / program "
                   "size in the jitted step+finish hot paths")

    def run(self, ctx: core.AnalysisContext) -> list[core.Finding]:
        out: list[core.Finding] = []
        for hook, traced in ctx.engine_traces.items():
            if isinstance(traced, trace.TraceFailure):
                # The sharding pass owns trace-failure reporting (axis
                # errors are its findings); stay quiet here.
                continue
            out.extend(self._program_findings(ctx, hook, traced))
        step = ctx.engine_traces.get("step")
        if step is not None and not isinstance(step, trace.TraceFailure):
            out.extend(self._step_arg_findings(ctx, step))
        out.extend(self._probe_findings(ctx))
        return out

    def _probe_findings(self, ctx) -> list[core.Finding]:
        """ISSUE 5: the executor's in-flight window launches one extra
        program per dispatched group — the completion probe
        (:func:`...runtime.executor._probe_body` over the smallest state
        leaf).  Certify it stays a pure device-side copy: a callback or
        infeed here would put a host round trip back into the no-retry hot
        loop the window exists to pipeline."""
        import jax
        import numpy as _np

        from mapreduce_tpu.runtime import executor as executor_mod

        st = ctx.state_shape
        if isinstance(st, trace.TraceFailure):
            return []  # init_state failures are reported elsewhere
        leaves = jax.tree.leaves(st)
        if not leaves:
            return []
        leaf = min(leaves, key=lambda x: int(
            _np.prod(x.shape, dtype=_np.int64)) * x.dtype.itemsize)
        try:
            traced = jax.make_jaxpr(executor_mod._probe_body)(
                jax.ShapeDtypeStruct(leaf.shape, leaf.dtype))
        except Exception as e:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id,
                model=ctx.model, hook="probe",
                message=f"window completion probe does not trace: {e!r}",
                hint="executor._probe_body must stay a trivial jittable "
                     "copy of one state leaf")]
        bad = []
        n_eqns = 0
        for eqn, _ in trace.iter_eqns(traced):
            n_eqns += 1
            name = eqn.primitive.name
            if name in _CALLBACKS or name in _HOST_COUPLING:
                bad.append(core.Finding(
                    severity=core.ERROR, pass_id=self.pass_id,
                    model=ctx.model, hook="probe",
                    message=(f"'{name}' inside the window completion "
                             "probe: every dispatched group would pay a "
                             "host round trip, serializing the pipeline "
                             "the in-flight window exists to build"),
                    location=trace.eqn_location(eqn),
                    hint="keep executor._probe_body a pure device-side "
                         "copy; do telemetry host-side at retirement"))
        if bad:
            return bad
        return [core.Finding(
            severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
            hook="probe",
            message=(f"window completion probe traces to {n_eqns} "
                     f"equation(s) over {leaf.dtype}"
                     f"[{','.join(map(str, leaf.shape))}]: no host "
                     "coupling — the async window adds no hidden sync"))]

    def _program_findings(self, ctx, hook, traced) -> list[core.Finding]:
        out = []
        n_eqns = 0
        seen: set[str] = set()
        for eqn, _ in trace.iter_eqns(traced):
            n_eqns += 1
            name = eqn.primitive.name
            if name in _CALLBACKS and name not in seen:
                seen.add(name)
                out.append(core.Finding(
                    severity=core.ERROR, pass_id=self.pass_id,
                    model=ctx.model, hook=hook,
                    message=(f"host callback '{name}' inside the jitted "
                             f"{hook} program: every dispatch round-trips "
                             "to the host (the 66 s dispatch-phase smell)"),
                    location=trace.eqn_location(eqn),
                    hint="move host work outside the step (log from the "
                         "executor loop; fetch metrics at finish)"))
            elif name in _HOST_COUPLING and name not in seen:
                seen.add(name)
                out.append(core.Finding(
                    severity=core.ERROR, pass_id=self.pass_id,
                    model=ctx.model, hook=hook,
                    message=f"'{name}' couples the {hook} program to the "
                            "host per dispatch",
                    location=trace.eqn_location(eqn),
                    hint="stream data via the executor's staged batches "
                         "instead"))
        for nbytes, desc in _const_bytes(traced):
            if nbytes >= _CONST_WARN_BYTES:
                out.append(core.Finding(
                    severity=core.WARNING, pass_id=self.pass_id,
                    model=ctx.model, hook=hook,
                    message=(f"large constant {desc} ({nbytes >> 20} MiB) "
                             f"baked into the {hook} program: re-shipped "
                             "per executable, and a per-call-varying value "
                             "here means a fresh compile per step"),
                    hint="pass varying arrays as traced arguments (or hash-"
                         "stable statics); keep big tables out of closures"))
        out.append(core.Finding(
            severity=core.INFO, pass_id=self.pass_id,
            model=ctx.model, hook=hook,
            message=f"{hook} program traces to {n_eqns} equations",
            hint="per-dispatch overhead scales with program size; fold "
                 "steps with superstep (lax.scan) when dispatch-bound"))
        return out

    def _step_arg_findings(self, ctx, step) -> list[core.Finding]:
        # The step program's flat inputs are (state leaves..., chunk, step
        # scalar).  A rank-0 invar must exist; if the builder had closed
        # over a Python int instead, each step index would be a distinct
        # baked constant -> one compile per step.
        jaxpr = step.jaxpr
        has_scalar_invar = any(
            getattr(v.aval, "shape", None) == () for v in jaxpr.invars)
        if not has_scalar_invar:
            return [core.Finding(
                severity=core.ERROR, pass_id=self.pass_id,
                model=ctx.model, hook="step",
                message="step program has no scalar (step-index) input: "
                        "the index is baked per trace, forcing one compile "
                        "per step",
                hint="pass step_index as a traced uint32 argument "
                     "(Engine.step does this; custom drivers must too)")]
        return []
