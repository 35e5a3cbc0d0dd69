"""Pass: fusion-opportunity finder over the traced step/finish jaxprs.

The costcheck byte model (:mod:`..costmodel`) charges **materializing**
primitives their full operand+result HBM traffic; every value flowing
between two adjacent materializing equations is a round-trip the program
pays that a fused kernel would not — exactly the token-plane round-trip
ISSUE 6's fused map path deleted (tokenize -> hash -> window compaction in
one ``pallas_call``).  This pass finds the NEXT such seams mechanically:

* walk each traced program scope by scope (control bodies are their own
  scopes — a cond branch cannot fuse with its sibling), INLINING
  transparent call boundaries: ``jit``/``closed_call``/``remat``/
  ``shard_map`` wrappers are function-call plumbing XLA inlines (every
  ``jnp.sort``/``jnp.cumsum`` arrives wrapped in its own one-eqn ``jit``),
  so their bodies continue the enclosing scope with invar/outvar identity
  threaded through — without this, no cross-library-call adjacency is
  visible at all;
* within a scope, track the most recent materializing equation and the
  set of values derived from its outputs through *fusible* (elementwise)
  equations — XLA fuses those chains into their consumers, so they do not
  break adjacency;
* when a later materializing equation consumes one of those values, the
  pair is a **candidate fusion**: the producer's MATERIALIZED output bytes
  (not the consumer-side operand a dtype-changing chain derives from it)
  are HBM traffic a fused implementation saves — the consumer's read
  always, the producer's write only when nothing in the chain escapes to
  another consumer or the program output (an escaping intermediate must
  stay in HBM, so only the read is recovered) — provided the pair's
  combined operand+result footprint fits the
  vmem-budget pass's envelope (:data:`..ops.pallas.meta.VMEM_DEFAULT_LIMIT`,
  Mosaic's default per-core stack budget): a fusion whose working set
  cannot be resident on-chip is not a kernel, it is a different algorithm,
  and flagging it would send someone chasing an impossible win.

Findings are INFO (candidates are leads, not defects — the error-severity
tier-1 gate stays clean by construction); the machine-readable candidate
list lands in the ``fusion`` artifact so tooling can rank programs by
recoverable HBM bytes.  Methodology per CUDA-LLM (PAPERS.md): this pass
proposes variants, the hbm-cost baselines are the fitness gate that
certifies each one actually landed.
"""

from __future__ import annotations

import jax.extend.core as jex_core

from mapreduce_tpu.analysis import core, costmodel, trace
from mapreduce_tpu.ops.pallas import meta

# At most this many per-program candidates become findings (ranked by
# saved bytes); the artifact always carries the full list.
MAX_FINDINGS_PER_PROGRAM = 4


def _family(eqn) -> str:
    return costmodel._classify(eqn.primitive.name)


def _invar_vars(eqn) -> list:
    """The eqn's Var operands (Literals are unhashable constants — they
    carry no producer, so they can never witness adjacency)."""
    return [v for v in eqn.invars if isinstance(v, jex_core.Var)]


def _is_control(eqn) -> bool:
    name = eqn.primitive.name
    return name in costmodel._CONTROL or (
        bool(trace.eqn_subjaxprs(eqn)) and name != "pallas_call")


# Call-shaped wrappers whose body is semantically inline in the enclosing
# scope (XLA inlines them; crucially every jax.numpy library call — sort,
# cumsum, ... — arrives as its own one-eqn jit).  cond/while/scan stay
# fresh scopes: their bodies run zero/N times or per-branch.
_TRANSPARENT = {"jit", "closed_call", "core_call", "xla_call", "remat",
                "checkpoint", "custom_jvp_call", "custom_vjp_call",
                "custom_vjp_call_jaxpr", "custom_partitioning", "shard_map"}


class _Values:
    """Value-identity tracking across inlined call boundaries.

    Canonical ids are fresh INTEGERS assigned each time a defining
    equation is *visited* — never the jaxpr ``Var`` objects themselves:
    JAX caches library-call jaxprs, so two same-shaped ``jnp.sort`` calls
    share one inner jaxpr (and its Vars), and keying on the shared Var
    would alias the two calls' results into one value (a phantom
    adjacency between unrelated equations).  Re-visiting the shared body
    re-assigns new ids, so each invocation's values stay distinct.
    """

    def __init__(self):
        self._env: dict = {}   # Var -> int id (resolved at insert)
        self._next = 0

    def _fresh(self) -> int:
        self._next += 1
        return self._next

    def of(self, v) -> int:
        """The var's current value id (fresh on first sight — top-level
        invars/constvars define themselves)."""
        if v not in self._env:
            self._env[v] = self._fresh()
        return self._env[v]

    def define(self, outvars) -> None:
        """A visited equation defines its outputs as NEW values."""
        for v in outvars:
            self._env[v] = self._fresh()

    def alias(self, dst, src) -> None:
        """Call-boundary plumbing: ``dst`` names the same value as
        ``src`` (inner invar = caller operand; caller outvar = body
        result)."""
        self._env[dst] = self.of(src)


class _Scan:
    """Per-program accumulators shared across every scope of one walk.

    ``raw`` collects candidate tuples; ``root_bytes`` prices each
    materializing producer OUTPUT (the value actually written to HBM —
    pricing the consumer-side derived aval would mis-size dtype-changing
    fusible chains); ``uses``/``chain_uses`` count, per value id, total
    consuming equations vs consumptions by the candidate's own fusible
    chain + consumer, so the finalizer can tell whether the
    intermediate's WRITE is deletable (no other consumer needs it) or
    only the consumer's read is saved."""

    def __init__(self):
        self.raw: list = []
        self.root_bytes: dict = {}   # root id -> producer outvar bytes
        self.uses: dict = {}         # id -> consuming-eqn count
        self.chain_uses: dict = {}   # id -> consumptions inside its chain

    def use(self, ids) -> None:
        for i in ids:
            self.uses[i] = self.uses.get(i, 0) + 1

    def chain_use(self, ids) -> None:
        for i in ids:
            self.chain_uses[i] = self.chain_uses.get(i, 0) + 1


def _scan_scope(eqns, acc: _Scan, values: _Values, state: list) -> None:
    """One linear scope: emit (producer, consumer, roots, chain,
    combined_bytes) candidate tuples into ``acc.raw`` (``roots`` = the
    producer-output ids reaching the consumer, ``chain`` = the chain's
    frozen carried dict for the post-walk fanout check); inline
    transparent call bodies into the CURRENT scope (``values`` threads
    value identity across the call boundary, ``state = [prev, carried]``
    is shared so adjacency survives the return); recurse into control
    bodies as fresh scopes."""
    for eqn in eqns:
        subs = trace.eqn_subjaxprs(eqn)
        if subs and eqn.primitive.name in _TRANSPARENT and len(subs) == 1:
            j = getattr(subs[0], "jaxpr", subs[0])
            if len(j.invars) == len(eqn.invars) \
                    and len(j.outvars) == len(eqn.outvars):
                for inner, outer in zip(j.invars, eqn.invars):
                    if isinstance(outer, jex_core.Var):
                        values.alias(inner, outer)
                _scan_scope(j.eqns, acc, values, state)
                for outer, inner in zip(eqn.outvars, j.outvars):
                    if isinstance(inner, jex_core.Var):
                        values.alias(outer, inner)
                continue
        ids = {values.of(v) for v in _invar_vars(eqn)}
        acc.use(ids)
        if _is_control(eqn):
            for sub in subs:
                j = getattr(sub, "jaxpr", sub)
                _scan_scope(j.eqns, acc, _Values(), [None, {}])
            state[0], state[1] = None, {}
            continue
        fam = _family(eqn)
        prev, carried = state
        if fam == "fusible":
            # Elementwise chains fuse into their consumers: they extend
            # the producer's reach instead of breaking adjacency.
            hit = [i for i in ids if i in carried]
            values.define(eqn.outvars)
            if prev is not None and hit:
                acc.chain_use(hit)
                roots = frozenset().union(*(carried[i] for i in hit))
                for v in eqn.outvars:
                    carried[values.of(v)] = roots
            continue
        if fam == "collective":
            values.define(eqn.outvars)
            state[0], state[1] = None, {}
            continue
        # A materializing equation.  Does it consume the previous one?
        if prev is not None:
            hit = [i for i in ids if i in carried]
            if hit:
                acc.chain_use(hit)
                roots = sorted(frozenset().union(*(carried[i]
                                                   for i in hit)))
                combined = sum(
                    costmodel._aval_bytes(v.aval)
                    for e in (prev, eqn)
                    for v in list(e.invars) + list(e.outvars)
                    if hasattr(v, "aval"))
                # carried is frozen from here: the consumer becomes the
                # new prev and state[1] is rebound below, so the dict
                # reference is a safe post-walk snapshot of the chain.
                acc.raw.append((prev, eqn, roots, carried, combined))
        values.define(eqn.outvars)
        ids_out = [values.of(v) for v in eqn.outvars]
        for i, v in zip(ids_out, eqn.outvars):
            acc.root_bytes[i] = costmodel._aval_bytes(v.aval)
        state[0] = eqn
        state[1] = {i: frozenset((i,)) for i in ids_out}


@core.register_pass
class FusionPass:
    pass_id = "fusion-opportunity"
    description = ("adjacent materializing eqns whose combined footprint "
                   "fits the VMEM envelope: candidate kernel fusions and "
                   "the HBM bytes each would save")

    def run(self, ctx: core.AnalysisContext) -> list[core.Finding]:
        out: list[core.Finding] = []
        artifact: dict = {"programs": {}, "envelope_bytes":
                          meta.VMEM_DEFAULT_LIMIT}
        total_saved = n_candidates = 0
        for hook, traced in ctx.engine_traces.items():
            if isinstance(traced, trace.TraceFailure):
                continue  # the sharding pass owns trace-failure reporting
            acc = _Scan()
            values = _Values()
            _scan_scope(traced.jaxpr.eqns, acc, values, [None, {}])
            # A value the program RETURNS must stay materialized no
            # matter what fuses: count the top-level outputs as uses so
            # the write-deletable check below sees them.
            acc.use({values.of(v) for v in traced.jaxpr.outvars
                     if hasattr(v, "count")})
            cands = []
            for prev, eqn, roots, chain, combined in acc.raw:
                # The envelope gate: pairs whose working set cannot sit in
                # VMEM are NOT candidates (see module docstring).
                if combined > meta.VMEM_DEFAULT_LIMIT:
                    continue
                inter = sum(acc.root_bytes[r] for r in roots)
                if inter <= 0:
                    continue
                saved = 0
                for r in roots:
                    # The consumer's READ of the root always fuses away;
                    # the root's WRITE is deletable only if every use of
                    # the root — and of every chain value derived from it
                    # (an escaping derived value re-reads the root in its
                    # own fusion cluster) — sits inside this chain.
                    chain_ids = [i for i, rs in chain.items() if r in rs]
                    escapes = any(
                        acc.uses.get(i, 0) != acc.chain_uses.get(i, 0)
                        for i in chain_ids)
                    saved += acc.root_bytes[r] * (1 if escapes else 2)
                cands.append({
                    "producer": prev.primitive.name,
                    "consumer": eqn.primitive.name,
                    "location": trace.eqn_location(eqn),
                    "intermediate_bytes": inter,
                    "hbm_bytes_saved": saved,
                    "combined_vmem_bytes": combined,
                })
            cands.sort(key=lambda c: -c["hbm_bytes_saved"])
            artifact["programs"][hook] = cands
            n_candidates += len(cands)
            total_saved += sum(c["hbm_bytes_saved"] for c in cands)
            for c in cands[:MAX_FINDINGS_PER_PROGRAM]:
                out.append(core.Finding(
                    severity=core.INFO, pass_id=self.pass_id,
                    model=ctx.model, hook=hook,
                    message=(f"candidate fusion {c['producer']} -> "
                             f"{c['consumer']}: the "
                             f"{c['intermediate_bytes'] >> 10} KiB "
                             f"intermediate round-trips HBM "
                             f"({c['hbm_bytes_saved'] >> 10} KiB saved "
                             f"fused; combined working set "
                             f"{c['combined_vmem_bytes'] >> 10} KiB fits "
                             "the VMEM envelope)"),
                    location=c["location"],
                    hint="a lead, not a defect: prototype the fused "
                         "kernel, then certify the win with the hbm-cost "
                         "baseline (the ISSUE 6 map-fusion workflow)"))
        artifact["candidates"] = n_candidates
        artifact["total_hbm_bytes_saved"] = total_saved
        ctx.artifacts["fusion"] = artifact
        if n_candidates:
            out.append(core.Finding(
                severity=core.INFO, pass_id=self.pass_id, model=ctx.model,
                hook="step",
                message=(f"{n_candidates} candidate fusion(s), "
                         f"{total_saved >> 10} KiB of recoverable HBM "
                         "traffic (see the 'fusion' artifact)")))
        return out
