"""The MapReduce engine: the framework core.

TPU-first replacement for ``runMapReduce`` (``main.cu:133-162``).  Where the
reference hand-sequences device malloc / H2D copy / map launch / reduce launch
/ D2H copy on the default CUDA stream, here the whole map+combine step is one
jitted SPMD program over a device mesh, and the global reduction is a
collective.  The user-visible contract is a small functional protocol:

  * ``init_state()``  — per-device accumulator (a pytree);
  * ``map_chunk(chunk, chunk_id)`` — the map UDF: one device's chunk of bytes
    to an update pytree (reference analogue: ``mapper``, ``main.cu:37-54``);
  * ``combine(state, update)`` — fold an update into the local accumulator
    (the "combiner" classic MapReduce runs map-side);
  * ``merge(a, b)`` — associative+commutative merge of two accumulators,
    used by the collective global reduce (reference analogue: the serial
    ``reducer``, ``main.cu:69-108``);
  * ``finalize(state)`` — device-side post-processing of the fully merged
    state (e.g. top-k selection).

Execution model: every step feeds each device one ``chunk_bytes`` slice of the
corpus (data parallelism over the 'data' mesh axis — the same axis the
reference parallelizes, lines->chunks, ``main.cu:113``), accumulators stay
device-resident across steps (no per-step host round-trips, unlike the
reference's per-call cudaMemcpy pattern), and ``finish`` runs the collective
tree-merge + finalize once at the end.

Program build: each program is acquired once per Engine, on first use.
It comes from the process-wide program table when an Engine with the same
key built it before (:data:`PROGRAM_TABLE_SIZE` programs, least recently
used evicted; counted as ``engine.reuses{program=...}``).  Otherwise it is
built in JAX's ahead-of-time stages (trace, lower, compile-or-fetch from
the persistent cache), each under its own span (``build.trace``,
``build.lower``, ``build.compile`` inside ``build``), and counted as
``engine.builds{program=...}``.  The key is what the trace reads: the job's
``program_key()``, the mesh, axes, merge strategy and stats mode, the
program's own key, and the arguments' structure, shapes, dtypes and
shardings.  A job whose ``program_key()`` is None keeps its programs to
its Engine.  Every dispatch then calls the kept ``jax.stages.Compiled``
directly.  The programs carry stable names (``mr_step``, ``mr_step_many``,
``mr_finish``, ``mr_finish_residual``, ``mr_partial``, ``mr_reset``,
``mr_replicate``): a profiler trace shows them as ``jit_<name>``.
"""

from __future__ import annotations

import collections
import functools
import threading
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from mapreduce_tpu import obs
from mapreduce_tpu.parallel import collectives
from mapreduce_tpu.parallel import mesh as mesh_mod

#: Programs the process-wide table holds before it evicts the least
#: recently used.
PROGRAM_TABLE_SIZE = 64

# (engine key, program key, argument signature) -> jax.stages.Compiled,
# least recently used first.  It holds nothing that refers back to an
# Engine or a job: a Compiled keeps the executable, not the traced function.
_PROGRAMS: collections.OrderedDict = collections.OrderedDict()
_PROGRAMS_LOCK = threading.Lock()


def clear_programs() -> None:
    """Empty the process-wide program table: every Engine builds anew."""
    with _PROGRAMS_LOCK:
        _PROGRAMS.clear()


def _table_get(key: tuple) -> Any:
    with _PROGRAMS_LOCK:
        prog = _PROGRAMS.get(key)
        if prog is not None:
            _PROGRAMS.move_to_end(key)
        return prog


def _table_put(key: tuple, prog: Any) -> None:
    with _PROGRAMS_LOCK:
        _PROGRAMS[key] = prog
        _PROGRAMS.move_to_end(key)
        while len(_PROGRAMS) > PROGRAM_TABLE_SIZE:
            _PROGRAMS.popitem(last=False)


def _signature(args) -> tuple:
    """What a trace reads of its arguments: the tree structure and each
    leaf's shape, dtype, weak type and sharding."""
    leaves, tree = jax.tree.flatten(args)
    return tree, tuple((x.shape, x.dtype, getattr(x, "weak_type", False),
                        getattr(x, "sharding", None)) for x in leaves)


def _map_with_axis(job, chunk, chunk_id, axis, device_index):
    """Dispatch to the job's axis-aware map hook when it defines one.

    Jobs are duck-typed (WordCountJob and friends don't inherit the base
    class), so the optional hook is resolved by name at trace time.
    ``device_index`` is the Engine's row-major linear shard index — passed
    through so jobs never re-derive (and risk diverging from) the axis
    linearization their gathered data is ordered by.
    """
    fn = getattr(job, "map_chunk_sharded", None)
    if fn is not None:
        return fn(chunk, chunk_id, axis, device_index)
    return job.map_chunk(chunk, chunk_id)


class MapReduceJob:
    """Base class for jobs.  Subclasses override the five hooks.

    All hooks are traced under jit: they must be pure, static-shaped JAX.
    """

    def init_state(self) -> Any:
        raise NotImplementedError

    def map_chunk(self, chunk: jax.Array, chunk_id: jax.Array) -> Any:
        raise NotImplementedError

    def map_chunk_sharded(self, chunk: jax.Array, chunk_id: jax.Array,
                          axis, device_index: jax.Array) -> Any:
        """Optional axis-aware map: runs inside ``shard_map``, so it may use
        collectives over ``axis`` (a mesh axis name or tuple of them).  Jobs
        whose per-chunk updates need neighbor/seam context (e.g. grep's
        exact matching-line count across row boundaries) override this; the
        default is the plain per-device :meth:`map_chunk`.  ``device_index``
        is the row-major linear shard index over the sharded axes (uint32
        scalar) — it matches the row order of ``jax.lax.all_gather(...,
        axis_name=axis)`` output."""
        return self.map_chunk(chunk, chunk_id)

    def combine(self, state: Any, update: Any) -> Any:
        raise NotImplementedError

    def merge(self, a: Any, b: Any) -> Any:
        raise NotImplementedError

    def finalize(self, state: Any) -> Any:
        return state

    def identity(self) -> str:
        """Stable description of what this job computes, for checkpoint
        fingerprints: resuming a snapshot under a job with a different
        identity is refused (e.g. a grep for a different pattern, whose
        state SHAPE is identical but whose accumulated numbers mean
        something else).  Subclasses with parameters that change the
        meaning of accumulated state must include them."""
        return type(self).__name__.lower()

    def program_key(self) -> Any:
        """A hashable value covering everything the hooks read when traced
        (the job's type, its parameters, its whole Config), so that Engines
        of equal keys share compiled programs; None (the default) keeps a
        job's programs to its own Engine."""
        return None


class Engine:
    """Compiles and runs a :class:`MapReduceJob` over a mesh.

    Usage::

        eng = Engine(job, mesh)
        state = eng.init_states()
        for step, batch in enumerate(reader):   # batch: uint8[D, chunk_bytes]
            state = eng.step(state, batch, step)
        result = eng.finish(state)              # merged + finalized, replicated
    """

    def __init__(self, job: MapReduceJob, mesh: Mesh,
                 axis: str | tuple[str, ...] = "data",
                 merge_strategy: str = "tree", data_stats: bool = False,
                 timer=None):
        axes = (axis,) if isinstance(axis, str) else tuple(axis)
        for a in axes:
            if a not in mesh.axis_names:
                raise ValueError(f"axis {a!r} not in mesh axes {mesh.axis_names}")
        self.job = job
        self.mesh = mesh
        self.axis = axes[0] if len(axes) == 1 else axes
        self.axes = axes
        self.n_devices = 1
        for a in axes:
            self.n_devices *= mesh.shape[a]
        if merge_strategy == "auto":
            raise ValueError(
                "merge_strategy='auto' reaches the Engine unresolved: "
                "resolution (via the redplan tuned.json profile) is the "
                "driver's job — pass the resolved strategy name")
        if merge_strategy not in collectives.STRATEGIES:
            raise ValueError(f"unknown merge_strategy {merge_strategy!r}")
        if merge_strategy in ("keyrange", "hier-kr-tree") \
                and getattr(job, "keyrange_merge", None) is None:
            raise ValueError(
                f"merge_strategy={merge_strategy!r} needs a job with a "
                "keyrange_merge hook (the CountTable wordcount family); "
                f"use 'tree'/'gather' for {type(job).__name__}")
        if merge_strategy.startswith("hier-") and len(axes) < 2:
            raise ValueError(
                f"merge_strategy={merge_strategy!r} composes two mesh "
                f"levels; the mesh has one axis ({axes[0]!r}) — use "
                "'tree'/'gather'/'keyrange' on single-axis meshes")
        # Data-plane telemetry (ISSUE 8): when on, step/step_many return
        # ``(state, DataStats)`` — the stats leaves are tiny uint32 scalars
        # per shard, a NON-donated second output the executor fetches at
        # group retirement (the completion token already proved the program
        # finished, so the fetch observes, never syncs).  Off (default):
        # the built programs are bit-identical to pre-ISSUE-8.  Support is
        # duck-typed by ``ops.datastats.supports`` (the hooks, or a
        # wrapper's forwarded ``data_stats_supported``).
        if data_stats:
            from mapreduce_tpu.ops import datastats

            if not datastats.supports(job):
                raise ValueError(
                    f"data_stats=True but {type(job).__name__} has no "
                    "map_chunk_stats_sharded/state_stats hooks")
        self.data_stats = bool(data_stats)
        self.merge_strategy = merge_strategy
        self._keyrange = merge_strategy == "keyrange"
        # The keyrange-family strategies return the job's keyrange RESULT
        # shape (wordcount family: a plain replicated CountTable), so any
        # further fold of their output — the hier outer tree legs, the
        # overlap accumulator — must use the job's result-shape merge.
        self._kr_family = merge_strategy in ("keyrange", "hier-kr-tree")
        self._result_merge = getattr(job, "keyrange_result_merge", None) \
            if self._kr_family else None
        if self._kr_family and self._result_merge is None:
            self._result_merge = job.merge
        # Multi-axis meshes reduce level by level (innermost = fastest link
        # first); single-axis meshes use the chosen strategy directly.
        # Keyrange flattens the axes inside its single all_to_all round
        # (the job hook receives the full axis tuple); the hier-*
        # placements compose a strategy per level (_merge_local).
        self._collective = None if self._kr_family else (
            functools.partial(
                collectives.hierarchical_merge, strategy=merge_strategy)
            if len(axes) > 1 else
            (collectives.tree_merge if merge_strategy
             in ("tree", "hier-tree-tree") else collectives.gather_merge))
        self._sharded = mesh_mod.sharded(mesh, axes if len(axes) > 1 else axes[0])
        self._replicated = mesh_mod.replicated(mesh)
        # The run's PhaseTimer (optional): program builds accumulate into
        # its ``build`` / ``build.*`` phases.  ``build`` is present from the
        # start, so a run that built nothing reads 0 s, not "not timed".
        self.timer = timer
        if timer is not None:
            timer.phases.setdefault("build", 0.0)
        # What the trace reads besides a program's own key and arguments;
        # None keeps this Engine's programs out of the process-wide table.
        # Jobs are duck-typed, so the hook is optional.
        job_key = getattr(job, "program_key", lambda: None)()
        self._share_key = None if job_key is None else (
            job_key, mesh, axes, merge_strategy, self.data_stats)
        # (program name, what varies) -> jax.stages.Compiled, as acquired
        self._programs: dict[tuple, Any] = {}

    def _device_index(self):
        """Linear index of this shard across all sharded axes (row-major)."""
        idx = jax.lax.axis_index(self.axes[0])
        for a in self.axes[1:]:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        return idx.astype(jnp.uint32)

    @property
    def sharding(self):
        """The NamedSharding for per-device inputs/states (public: callers
        staging inputs ahead of step/step_many should place them with this)."""
        return self._sharded

    # -- state ---------------------------------------------------------------

    def init_states(self) -> Any:
        """Stacked per-device states, leading axis = mesh axis, sharded."""
        one = self.job.init_state()
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (self.n_devices,) + x.shape), one)
        return jax.device_put(stacked, self._sharded)

    def init_states_global(self) -> Any:
        """Sharded initial state for multi-controller SPMD (a mesh spanning
        several processes): no process can ``device_put`` to another
        process's devices, so the global program itself materializes the
        state and ``out_shardings`` places it.  Identical result to
        :meth:`init_states` on a single-process mesh."""
        job, n = self.job, self.n_devices

        def init():
            one = job.init_state()
            return jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (n,) + x.shape), one)

        return jax.jit(init, out_shardings=self._sharded)()

    def replicate_to_host(self, state: Any) -> Any:
        """Fetch a (possibly non-addressable) sharded state as host numpy:
        one jitted identity with replicated out_shardings (an all_gather
        over the mesh) makes every shard addressable on every process —
        the multi-host checkpoint fetch.  Costs one collective round."""
        def mr_replicate(s):
            return s

        rep = self._program(
            ("mr_replicate",),
            lambda: jax.jit(mr_replicate, out_shardings=self._replicated),
            state)
        return jax.tree.map(np.asarray, rep(state))

    # -- compiled programs ---------------------------------------------------

    def _program(self, key: tuple, build: Callable[[], Any], *args) -> Any:
        """The compiled program ``key`` (its name first), acquired on first
        use for arguments shaped like ``args``: from the process-wide table
        when another Engine of the same key built it, else from
        ``build()``, a ``jax.jit`` object, traced, lowered and compiled (or
        fetched from the persistent compile cache) under the ``build`` span
        and its ``build.trace`` / ``build.lower`` / ``build.compile``
        children."""
        prog = self._programs.get(key)
        if prog is not None:
            return prog
        shared = None if self._share_key is None \
            else (self._share_key, key, _signature(args))
        prog = None if shared is None else _table_get(shared)
        if prog is not None:
            obs.get_registry().counter("engine.reuses", program=key[0]).inc()
        else:
            timer = self.timer
            with obs.span("build", timer):
                with obs.span("build.trace", timer):
                    traced = build().trace(*args)
                with obs.span("build.lower", timer):
                    lowered = traced.lower()
                with obs.span("build.compile", timer):
                    prog = lowered.compile()
            obs.get_registry().counter("engine.builds", program=key[0]).inc()
            if shared is not None:
                _table_put(shared, prog)
        self._programs[key] = prog
        return prog

    def _build_step(self):
        axis, job, n = self.axis, self.job, self.n_devices

        def mr_step(state, chunks, step):
            local = jax.tree.map(lambda x: x[0], state)
            chunk = chunks[0]
            dev = self._device_index()
            chunk_id = step * jnp.uint32(n) + dev
            if self.data_stats:
                update, stats = job.map_chunk_stats_sharded(
                    chunk, chunk_id, axis, dev)
                new = job.combine(local, update)
                stats = job.state_stats(new, stats)
                return (jax.tree.map(lambda x: x[None], new),
                        jax.tree.map(lambda x: x[None], stats))
            update = _map_with_axis(job, chunk, chunk_id, axis, dev)
            new = job.combine(local, update)
            return jax.tree.map(lambda x: x[None], new)

        fn = shard_map(
            mr_step, mesh=self.mesh,
            in_specs=(P(axis), P(axis), P()),
            out_specs=(P(axis), P(axis)) if self.data_stats else P(axis),
            check_vma=False,
        )
        # Explicit in_shardings: without them XLA may propagate a sharding
        # onto the 0-d step scalar (observed with data-dependent lax.cond in
        # a job's map), and a partitioned spec on a rank-0 input breaks the
        # second dispatch's argument resharding.
        return jax.jit(fn, donate_argnums=(0,),
                       in_shardings=(self._sharded, self._sharded,
                                     self._replicated))

    def _build_step_many(self, k: int, repeats: int = 1):
        axis, job, n = self.axis, self.job, self.n_devices

        def mr_step_many(state, chunks, step0):
            local = jax.tree.map(lambda x: x[0], state)
            my = chunks[0]  # (k, chunk_bytes) after shard_map
            dev = self._device_index()

            def chunk_at(j):
                # Cycle over the k resident chunks: pass r of `repeats`
                # re-reads them with fresh step indices (epoch semantics).
                return jax.lax.dynamic_index_in_dim(
                    my, (j % jnp.uint32(k)).astype(jnp.int32), keepdims=False)

            if self.data_stats:
                from mapreduce_tpu.ops import datastats

                def body_stats(carry, j):
                    st, acc = carry
                    chunk_id = (step0 + j) * jnp.uint32(n) + dev
                    update, stats = job.map_chunk_stats_sharded(
                        chunk_at(j), chunk_id, axis, dev)
                    return (job.combine(st, update),
                            datastats.add(acc, stats)), None

                (new, acc), _ = jax.lax.scan(
                    body_stats, (local, datastats.zeros()),
                    jnp.arange(k * repeats, dtype=jnp.uint32))
                acc = job.state_stats(new, acc)
                return (jax.tree.map(lambda x: x[None], new),
                        jax.tree.map(lambda x: x[None], acc))

            def body(st, j):
                chunk_id = (step0 + j) * jnp.uint32(n) + dev
                return job.combine(
                    st, _map_with_axis(job, chunk_at(j), chunk_id, axis,
                                       dev)), None

            new, _ = jax.lax.scan(
                body, local, jnp.arange(k * repeats, dtype=jnp.uint32))
            return jax.tree.map(lambda x: x[None], new)

        fn = shard_map(
            mr_step_many, mesh=self.mesh,
            in_specs=(P(axis), P(axis), P()),
            out_specs=(P(axis), P(axis)) if self.data_stats else P(axis),
            check_vma=False,
        )
        return jax.jit(fn, donate_argnums=(0,),
                       in_shardings=(self._sharded, self._sharded,
                                     self._replicated))

    def _merge_local(self, local):
        """The configured cross-device reduction of one local state —
        traced inside shard_map.  Returns the REPLICATED merged value:
        the job state shape for tree/gather, the job's keyrange RESULT
        shape for the keyrange family (finalize accepts both)."""
        job, axis = self.job, self.axis
        if self._keyrange:
            return job.keyrange_merge(local, axis)
        if self.merge_strategy == "hier-kr-tree":
            return collectives.hier_kr_tree_merge(
                local, job.keyrange_merge, self._result_merge, self.axes)
        return self._collective(local, job.merge, axis)

    def _fold_merged(self, latest, accum):
        """Fold the latest merged window into the accumulator — the
        overlap accumulator's monoid: the result-shape merge for the
        keyrange family, the job merge otherwise.  The LATEST value is
        operand ``a`` deliberately: counters are commutative, but jobs
        that keep one operand's coordination leaves (grep's line_carry,
        NGram's seam carry) keep ``a``'s — and the monolithic finish
        would report the stream-end value of those leaves."""
        return self._result_merge(latest, accum) if self._kr_family \
            else self.job.merge(latest, accum)

    def _build_finish(self):
        axis, job = self.axis, self.job

        def mr_finish(state):
            local = jax.tree.map(lambda x: x[0], state)
            return job.finalize(self._merge_local(local))

        fn = shard_map(
            mr_finish, mesh=self.mesh,
            in_specs=(P(axis),), out_specs=P(),
            check_vma=False,
        )
        return jax.jit(fn)

    def _build_partial(self, with_accum: bool):
        """The window-boundary partial collective (ISSUE 20 leg 2): merge
        the current local states across the mesh with the SAME configured
        strategy the finish uses, folding into the resident accumulator
        when one exists.  Replicated output; nothing finalized."""
        axis = self.axis

        def mr_partial(*args):  # (state,), or (accum, state) with_accum
            local = jax.tree.map(lambda x: x[0], args[-1])
            merged = self._merge_local(local)
            return self._fold_merged(merged, args[0]) if with_accum \
                else merged

        fn = shard_map(
            mr_partial, mesh=self.mesh,
            in_specs=(P(), P(axis)) if with_accum else (P(axis),),
            out_specs=P(),
            check_vma=False,
        )
        # No donation: the executor dispatches this async at a window
        # boundary and then resets the local table from the same buffers;
        # tables are small next to the staged input stream.
        return jax.jit(fn)

    def _build_residual(self):
        """Stream-end finish under overlap: merge the residual local
        states, fold the accumulator in, finalize — one program, so the
        final collective record stays one span like the monolithic path."""
        axis, job = self.axis, self.job

        def mr_finish_residual(accum, state):
            local = jax.tree.map(lambda x: x[0], state)
            return job.finalize(
                self._fold_merged(self._merge_local(local), accum))

        fn = shard_map(
            mr_finish_residual, mesh=self.mesh,
            in_specs=(P(), P(axis)), out_specs=P(),
            check_vma=False,
        )
        return jax.jit(fn)

    def _build_reset(self):
        """Post-partial local reset: every device returns to its init
        state — except jobs carrying cross-step seam context, whose
        ``partial_reset`` hook preserves it (NGram keeps the carry; the
        gram table itself was shipped by the partial merge)."""
        job = self.job
        axis = self.axis
        fn_hook = getattr(job, "partial_reset", None)

        def mr_reset(state):
            local = jax.tree.map(lambda x: x[0], state)
            new = fn_hook(local) if fn_hook is not None else job.init_state()
            return jax.tree.map(lambda x: x[None], new)

        fn = shard_map(
            mr_reset, mesh=self.mesh,
            in_specs=(P(axis),), out_specs=P(axis),
            check_vma=False,
        )
        return jax.jit(fn)

    # -- public API ----------------------------------------------------------

    def step(self, state: Any, chunks: jax.Array, step_index: int) -> Any:
        """One map+combine step.  ``chunks``: uint8[n_devices, chunk_bytes].

        With ``data_stats=True`` (construction-time) the return value is
        ``(new_state, DataStats)`` — the stats pytree's leaves are [D]
        uint32 scalars, non-donated, ready together with the state."""
        chunks = jax.device_put(chunks, self._sharded)
        args = (state, chunks, jnp.uint32(step_index))
        return self._program(("mr_step", chunks.shape), self._build_step,
                             *args)(*args)

    def step_many(self, state: Any, chunks: jax.Array, step_index: int,
                  repeats: int = 1) -> Any:
        """K map+combine steps in ONE dispatch via ``lax.scan``.

        ``chunks``: uint8[n_devices, K, chunk_bytes].  Equivalent to K calls
        of :meth:`step` with step indices ``step_index .. step_index+K-1``
        (chunk_ids match exactly), but amortizes per-dispatch overhead —
        which dominates under high-latency device links — over K steps.
        Compiles once per distinct (K, repeats).

        ``repeats > 1`` folds the K device-resident chunks ``repeats`` times
        (epoch semantics: pass r re-reads every chunk with fresh step
        indices ``step_index + r*K ..``), processing K*repeats chunks in one
        dispatch without re-staging — the multi-pass analogue of a training
        loop's epochs, and the lever that keeps per-dispatch overhead out of
        throughput measurements on high-latency links.
        """
        k = chunks.shape[1]
        chunks = jax.device_put(chunks, self._sharded)
        args = (state, chunks, jnp.uint32(step_index))
        return self._program(
            ("mr_step_many", chunks.shape, repeats),
            lambda: self._build_step_many(k, repeats), *args)(*args)

    def finish(self, state: Any) -> Any:
        """Collective global merge + finalize.  Result is replicated."""
        return self._program(("mr_finish",), self._build_finish,
                             state)(state)

    def partial_merge(self, accum: Any, state: Any) -> Any:
        """Window-boundary partial collective (ISSUE 20 leg 2): reduce
        the current per-device states across the mesh with the configured
        strategy and fold into ``accum`` (pass ``None`` for the first
        window).  Returns the new replicated accumulator — dispatched
        async by the executor so the DCN transfer overlaps the next
        window's ingest."""
        with_accum = accum is not None
        args = (accum, state) if with_accum else (state,)
        return self._program(("mr_partial", with_accum),
                             lambda: self._build_partial(with_accum),
                             *args)(*args)

    def partial_shape(self) -> Any:
        """Abstract shapes of the first partial merge's accumulator (the
        checkpoint resume template), traced without building a program."""
        return jax.eval_shape(
            lambda: self._build_partial(False)(self.init_states()))

    def finish_residual(self, accum: Any, state: Any) -> Any:
        """Stream-end finish under overlap: merge the residual states,
        fold ``accum`` in, finalize.  With ``accum=None`` (no partial was
        ever dispatched) this is exactly :meth:`finish`."""
        if accum is None:
            return self.finish(state)
        return self._program(("mr_finish_residual",), self._build_residual,
                             accum, state)(accum, state)

    def partial_reset(self, state: Any) -> Any:
        """Fresh per-device states after a partial merge shipped the old
        ones (jobs with cross-step seam context override ``partial_reset``
        to keep it — NGram's carry)."""
        return self._program(("mr_reset",), self._build_reset,
                             state)(state)

    def run(self, batches, progress: Callable[[int], None] | None = None) -> Any:
        """Convenience: fold an iterable of [D, C] uint8 batches and finish."""
        state = self.init_states()
        for i, batch in enumerate(batches):
            state = self.step(state, batch, i)
            if progress is not None:
                progress(i)
        return self.finish(state)
