"""Tracing utilities: MapReduceJob hooks -> jaxprs, plus jaxpr walkers.

Everything graphcheck knows it learns from two sources built here:

* **per-hook jaxprs** under abstract inputs (``jax.make_jaxpr`` with
  ``ShapeDtypeStruct`` arguments) — no device work, no data;
* **engine programs**: the real jitted SPMD ``step``/``finish`` the
  :class:`~mapreduce_tpu.parallel.mapreduce.Engine` would dispatch, traced
  over the actual mesh — this is where ``shard_map`` bindings, collectives,
  and callbacks appear with their axis names attached.

A hook that cannot be traced (raises at trace time) is recorded as a
:class:`TraceFailure` value instead of propagating: passes decide whether
that is itself a finding (the sharding lint treats an unbound-axis-name
trace error as the mismatched-PartitionSpec finding it usually is).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Iterator

import jax
import jax.extend.core
import numpy as np

_ClosedJaxpr = jax.extend.core.ClosedJaxpr
_Jaxpr = jax.extend.core.Jaxpr


@dataclasses.dataclass(frozen=True)
class TraceFailure:
    """A hook that raised during tracing: the exception, preserved as data."""

    hook: str
    error_type: str
    error: str

    @classmethod
    def of(cls, hook: str, e: Exception) -> "TraceFailure":
        return cls(hook=hook, error_type=type(e).__name__, error=str(e))


def _chunk_bytes_for(job: Any, default: int = 1 << 10) -> int:
    """A chunk size the job's backend accepts (pallas needs seam windows)."""
    config = getattr(job, "config", None)
    if config is None:
        return default
    n = min(int(config.chunk_bytes), 1 << 16)
    if getattr(config, "backend", None) == "pallas":
        n = max(n, config.pallas_min_chunk)
    return max(128, (n // 128) * 128)


def state_shape(job: Any):
    """Abstract ``init_state`` pytree (ShapeDtypeStruct leaves)."""
    try:
        return jax.eval_shape(job.init_state)
    except Exception as e:
        return TraceFailure.of("init_state", e)


def abstract_chunk(job: Any):
    n = _chunk_bytes_for(job)
    return jax.ShapeDtypeStruct((n,), np.uint8)


def trace_hooks(job: Any, chunk_bytes: int | None = None) -> dict:
    """Trace each protocol hook to a ClosedJaxpr under abstract inputs.

    Returns ``{hook: ClosedJaxpr | TraceFailure}`` for ``init_state``,
    ``map_chunk``, ``combine``, ``merge``, ``finalize``.  ``combine`` is
    traced against the abstract update ``map_chunk`` produces; axis-aware
    maps (``map_chunk_sharded``) need a bound mesh axis and are traced as
    part of the engine step instead (:func:`trace_engine`).
    """
    n = chunk_bytes if chunk_bytes is not None else _chunk_bytes_for(job)
    n = max(128, (int(n) // 128) * 128)
    chunk = jax.ShapeDtypeStruct((n,), np.uint8)
    cid = jax.ShapeDtypeStruct((), np.uint32)
    out: dict[str, Any] = {}

    def attempt(hook, fn, *args):
        try:
            out[hook] = jax.make_jaxpr(fn)(*args)
        except Exception as e:
            out[hook] = TraceFailure.of(hook, e)

    attempt("init_state", lambda: job.init_state())
    st = state_shape(job)
    if isinstance(st, TraceFailure):
        for hook in ("map_chunk", "combine", "merge", "finalize"):
            out[hook] = TraceFailure.of(hook, RuntimeError(
                f"init_state untraceable: {st.error}"))
        return out
    attempt("map_chunk", lambda c, i: job.map_chunk(c, i), chunk, cid)
    try:
        upd = jax.eval_shape(lambda c, i: job.map_chunk(c, i), chunk, cid)
    except Exception as e:
        upd = TraceFailure.of("map_chunk", e)
    if isinstance(upd, TraceFailure):
        out["combine"] = TraceFailure.of("combine", RuntimeError(
            f"map_chunk untraceable: {upd.error}"))
    else:
        attempt("combine", lambda s, u: job.combine(s, u), st, upd)
    attempt("merge", lambda a, b: job.merge(a, b), st, st)
    attempt("finalize", lambda s: job.finalize(s), st)
    return out


def trace_engine(job: Any, mesh) -> dict:
    """Trace the Engine's jitted ``step`` and ``finish`` SPMD programs.

    These are the programs that actually hit the device: ``shard_map``
    bindings, collectives with axis names, and anything a hook smuggles in
    (callbacks, transfers) are all visible here.  Returns
    ``{'step'|'finish': ClosedJaxpr | TraceFailure}``.
    """
    from mapreduce_tpu.parallel.mapreduce import Engine

    out: dict[str, Any] = {}
    axes = tuple(mesh.axis_names)
    try:
        # ``analysis_data_stats`` (registry: the *_telemetry models): trace
        # the INSTRUMENTED step — data-plane counters returned next to the
        # state (ISSUE 8) — so the cost/host-sync passes certify exactly
        # the program telemetered runs dispatch.  ``analysis_merge_strategy``
        # (the *_fleet twins) likewise selects the Engine merge the traced
        # finish program builds — keyrange twins certify the all_to_all
        # program, not the default butterfly.
        eng = Engine(job, mesh, axis=axes if len(axes) > 1 else axes[0],
                     data_stats=getattr(job, "analysis_data_stats", False),
                     merge_strategy=getattr(job, "analysis_merge_strategy",
                                            "tree"))
    except Exception as e:
        f = TraceFailure.of("engine", e)
        return {"step": f, "finish": f}
    st = state_shape(job)
    if isinstance(st, TraceFailure):
        f = TraceFailure.of("engine", RuntimeError(
            f"init_state untraceable: {st.error}"))
        return {"step": f, "finish": f}
    n_dev = eng.n_devices
    stacked = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct((n_dev,) + x.shape, x.dtype), st)
    chunks = jax.ShapeDtypeStruct((n_dev, _chunk_bytes_for(job)), np.uint8)
    step = jax.ShapeDtypeStruct((), np.uint32)
    try:
        out["step"] = jax.make_jaxpr(eng._build_step())(stacked, chunks, step)
    except Exception as e:
        out["step"] = TraceFailure.of("step", e)
    try:
        out["finish"] = jax.make_jaxpr(eng._build_finish())(stacked)
    except Exception as e:
        out["finish"] = TraceFailure.of("finish", e)
    return out


def sample_states(job: Any, n: int = 3, chunk_bytes: int = 1 << 10,
                  seed: int = 20260803) -> tuple[list, TraceFailure | None]:
    """Concrete, *reachable* states for randomized property checks.

    Each state is ``init_state`` folded with one random text chunk through
    a 1-device engine step (so axis-aware maps and their collectives run
    too, over an axis of size one).  Reachability matters: merge is only
    required to be associative+commutative on states the map/combine
    machinery can actually produce — random bit patterns would violate
    table invariants and prove nothing.  Returns ``(states, failure)``:
    host (numpy-leaf) pytrees and ``None``, or ``([], TraceFailure)`` when
    the job cannot execute on this host — the failure is preserved as data
    so the property-check-skipped finding can say WHY.
    """
    from mapreduce_tpu.parallel.mapreduce import Engine
    from mapreduce_tpu.parallel.mesh import data_mesh

    rng = np.random.default_rng(seed)
    cb = max(128, (int(chunk_bytes) // 128) * 128)
    try:
        mesh = data_mesh(1)
        eng = Engine(job, mesh, axis=mesh.axis_names[0])
        states = []
        for i in range(n):
            chunk = random_text(rng, cb)
            st = eng.step(eng.init_states(), chunk[None, :], i)
            states.append(jax.tree.map(lambda x: np.asarray(x)[0], st))
        return states, None
    except Exception as e:
        return [], TraceFailure.of("sample_states", e)


def random_text(rng: np.random.Generator, n_bytes: int) -> np.ndarray:
    """Random word-ish bytes (lowercase tokens, space/newline separated),
    with a random NUL-padded tail — chunks of a real stream end padded,
    and unequal payload sizes keep sampled states distinguishable (a
    property check on three identical states proves nothing)."""
    out = np.full((n_bytes,), 0x20, dtype=np.uint8)
    i = 0
    while i < n_bytes:
        length = int(rng.integers(1, 9))
        word = rng.integers(97, 123, size=length, dtype=np.uint8)
        end = min(i + length, n_bytes)
        out[i:end] = word[: end - i]
        i = end + 1
        if i - 1 < n_bytes and rng.random() < 0.2:
            out[i - 1] = 0x0A
    tail = int(rng.integers(0, max(n_bytes // 4, 2)))
    if tail:
        out[n_bytes - tail:] = 0
    return out


# -- jaxpr walking ----------------------------------------------------------


def eqn_subjaxprs(eqn) -> list:
    """Every ClosedJaxpr/Jaxpr nested in an equation's params (jit bodies,
    cond branches, scan/while bodies, shard_map bodies, custom calls)."""
    out = []
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for x in items:
            if isinstance(x, (_ClosedJaxpr, _Jaxpr)):
                out.append(x)
    return out


def iter_eqns(jaxpr, bound_axes: frozenset = frozenset()) -> Iterator:
    """Yield ``(eqn, bound_axes)`` over a jaxpr and every nested sub-jaxpr.

    ``bound_axes`` is the set of mesh axis names bound by enclosing
    ``shard_map`` scopes — what collectives inside may legally reduce over.
    """
    j = jaxpr.jaxpr if isinstance(jaxpr, _ClosedJaxpr) else jaxpr
    for eqn in j.eqns:
        yield eqn, bound_axes
        sub_axes = bound_axes
        if eqn.primitive.name == "shard_map":
            mesh = eqn.params.get("mesh")
            names = tuple(getattr(mesh, "axis_names", ()) or ())
            sub_axes = bound_axes | frozenset(names)
        for sub in eqn_subjaxprs(eqn):
            yield from iter_eqns(sub, sub_axes)


def collect_primitives(jaxpr) -> set[str]:
    """All primitive names appearing anywhere in a jaxpr (recursive)."""
    return {eqn.primitive.name for eqn, _ in iter_eqns(jaxpr)}


def eqn_axis_names(eqn) -> list[str]:
    """Mesh axis names a collective equation operates over (if any)."""
    names: list[str] = []
    for key in ("axis_name", "axes"):
        if key in eqn.params:
            v = eqn.params[key]
            items = v if isinstance(v, (tuple, list)) else (v,)
            names.extend(x for x in items if isinstance(x, str))
    return names


def eqn_location(eqn) -> str:
    """Human-oriented source location of an equation: the innermost frame
    OUTSIDE jax itself (jax internals would otherwise win every time)."""
    src = getattr(eqn, "source_info", None)
    try:
        frames = list(src.traceback.frames) if src and src.traceback else []
        import os

        jax_dir = os.sep + "jax" + os.sep
        user = [f for f in frames
                if jax_dir not in getattr(f, "file_name", "")]
        frame = (user or frames or [None])[0]
        if frame is not None:
            name = os.path.basename(getattr(frame, "file_name", "?"))
            line = getattr(frame, "start_line",
                           getattr(frame, "line_num", "?"))
            return f"{eqn.primitive.name} @ {name}:{line}"
    except Exception:
        pass
    return eqn.primitive.name


# -- state-leaf walking -----------------------------------------------------


def named_leaves(tree: Any, prefix: str = "state") -> list[tuple[str, Any]]:
    """Flatten a pytree to ``(dotted.path, leaf)`` pairs, preserving
    NamedTuple field names (jax's keypath API reduces namedtuples to
    positional indices, which the overflow lint's lane-pair matching
    needs names for)."""
    out: list[tuple[str, Any]] = []

    def rec(x, path):
        if isinstance(x, tuple) and hasattr(x, "_fields"):
            for name in x._fields:
                rec(getattr(x, name), f"{path}.{name}")
        elif isinstance(x, dict):
            for k in sorted(x):
                rec(x[k], f"{path}[{k!r}]")
        elif isinstance(x, (tuple, list)):
            for i, v in enumerate(x):
                rec(v, f"{path}[{i}]")
        else:
            out.append((path, x))

    rec(tree, prefix)
    return out
