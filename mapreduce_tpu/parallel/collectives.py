"""Collective reductions over arbitrary mergeable states.

This is the distributed communication backend of the framework — the role the
reference fills with one serial device thread scanning every emitted pair
(``reduceKernel``/``reducer``, ``main.cu:119-123,69-108``) plus PCIe
``cudaMemcpy`` (``main.cu:147,157-158``).  Here cross-device aggregation is
expressed as XLA collectives over the mesh (ICI within a slice, DCN across
slices), in three interchangeable strategies:

* :func:`tree_merge` — butterfly all-reduce built from ``ppermute`` rounds
  with a user merge function.  log2(D) rounds; requires a power-of-two axis.
  The generalization of ``psum`` to non-additive monoids (count tables).
* :func:`gather_merge` — ``all_gather`` + fold.  Works for any axis size;
  O(D) memory; the fallback and the simplest correct form.
* :func:`key_range_merge` — the pod-scale strategy for :class:`CountTable`
  states specifically: reduce-scatter by hash range (one ``all_to_all``,
  capacity/D-sized owner merges) + ``all_gather`` of the already-reduced
  blocks.  One communication round where the butterfly does log2(D)
  sequential full-table rounds; see the function docstring for the traffic
  arithmetic and the exactness argument.
* ``psum`` — used directly wherever the state really is additive (scalar
  totals, sketch matrices, histogram vectors); XLA lowers it to the native
  ICI all-reduce (the BASELINE.json north-star transformation).

All functions take *pytrees* (``key_range_merge``: a CountTable) and must be
called inside ``shard_map``.
"""

from __future__ import annotations

from typing import Callable, TypeVar

import jax
import jax.numpy as jnp

from mapreduce_tpu.obs import registry as obs_registry
from mapreduce_tpu.ops import table as table_ops

T = TypeVar("T")
MergeFn = Callable[[T, T], T]


def _count_build(strategy: str, axis) -> None:
    """Trace-time collective accounting into the metrics registry.

    These functions run INSIDE shard_map/jit, so per-execution timing from
    here would require a host callback — exactly the per-step sync the
    graphcheck host-sync pass forbids.  What IS observable host-side is
    each strategy *build* (once per trace, i.e. per compiled program), with
    its axis width: enough to see which reduce strategies a run compiled
    and at what scale, and to correlate a compile-event spike in the run
    ledger with the collective that caused it.  Execution cost belongs to
    the profiler timeline (``obs.span`` regions around the dispatch).
    """
    try:
        d = jax.lax.axis_size(axis)
    except Exception:
        d = 0
    obs_registry.get_registry().counter(
        "collectives.builds", strategy=strategy, axis_size=d).inc()


def tree_merge(state: T, merge: MergeFn, axis: str) -> T:
    """Butterfly all-reduce: after log2(D) ppermute+merge rounds every device
    holds the merge of all D states.  Deterministic and replicated.
    """
    n = jax.lax.axis_size(axis)
    if n & (n - 1):
        return gather_merge(state, merge, axis)
    _count_build("tree", axis)
    rounds = n.bit_length() - 1
    for r in range(rounds):
        bit = 1 << r
        perm = [(i, i ^ bit) for i in range(n)]
        partner = jax.tree.map(lambda x: jax.lax.ppermute(x, axis, perm), state)
        state = merge(state, partner)
    return state


def gather_merge(state: T, merge: MergeFn, axis: str) -> T:
    """all_gather every state then fold left.  Any axis size; replicated."""
    n = jax.lax.axis_size(axis)
    _count_build("gather", axis)
    gathered = jax.tree.map(lambda x: jax.lax.all_gather(x, axis), state)
    take = lambda i: jax.tree.map(lambda x: x[i], gathered)
    acc = take(0)
    for i in range(1, n):
        acc = merge(acc, take(i))
    return acc


def psum(state: T, axis: str) -> T:
    """Additive all-reduce of a pytree (native XLA collective)."""
    return jax.lax.psum(state, axis)


def psum64(lo: jax.Array, hi: jax.Array, axis) -> tuple[jax.Array, jax.Array]:
    """Exact 64-bit all-reduce sum of uint32 (lo, hi) lane-pair scalars.

    A plain ``psum`` of the low lanes would drop inter-device carries
    silently; instead the D scalars are gathered (a few bytes) and folded
    with the wrap-counting :func:`...ops.table.sum64`."""
    return table_ops.sum64(jax.lax.all_gather(lo, axis),
                           jax.lax.all_gather(hi, axis))


def key_range_merge(table: table_ops.CountTable, axis,
                    slack: float = 2.0) -> table_ops.CountTable:
    """Key-range sharded global reduce of per-device CountTables: the
    reduce-scatter formulation of the serial reduce the reference runs on
    one thread (``main.cu:119-123``), for pod scale.

    The tree/butterfly strategy moves each device's FULL table log2(D)
    sequential times and runs log2(D) full-capacity merge sorts.  Here the
    key space is partitioned over the axis, every device routes each row to
    its owner in ONE ``all_to_all``, owners reduce their (capacity/D-scale)
    partition locally, and one ``all_gather`` of the already-reduced blocks
    replicates the result.  Per device, with table bytes M and slack s:

    =========  ====================  =====================================
    strategy   bytes moved           sequential sort rows
    =========  ====================  =====================================
    tree       M * log2(D)           2C * log2(D)
    keyrange   s*M (a2a) + s*M (ag)  C (pack) + s*C (owner) + s*C (final)
    =========  ====================  =====================================

    At D=256, C=256K, M~7 MB, s=2: ~56 MB & 4.2M sequential sort rows
    (tree) vs ~28 MB & ~1.3M rows (keyrange) — and the all_to_all round is
    a single collective XLA schedules across ICI links at once, not log2(D)
    dependent steps.

    Partitioning is by ``key_lo % D``: tables keep the capacity SMALLEST
    (key_hi, key_lo) keys, so key_hi ranges are mass-skewed toward small
    values, while the second hash word stays uniform under that selection.

    Exactness: each destination block has a fixed budget
    ``B = ceil(s*C/D) + 8 + 4*ceil(log2 D)`` rows; a device whose
    partition overflows B spills its LARGEST keys past the budget (rank
    order = key order).  Spilling key k implies >= B smaller distinct
    keys in that partition, all of which reach the owner, whose
    capacity-B reduce then evicts k everywhere it survived — so a spilled
    key is never reported with a partial count: it is fully evicted and
    accounted in ``dropped_*``, the same contract as capacity spill
    (ops/table.py module docstring).  The budget needs BOTH terms: for
    hash-uniform keys the max partition load is ~mean + O(sqrt(mean log
    D) + log D) (balls in bins), so a purely multiplicative slack fails
    exactly when C/D is small — at C=512, D=256 the mean is 2 rows but
    the max is ~9, so ``b = 2*C/D = 4`` spilled real keys and the merge
    (correctly, per the spill contract) diverged from tree on kept keys
    (found by the D=256 scale dryrun, round 5).  The additive term is
    noise at pod scale (+~40 rows on a 2048-row block at C=256K, D=256)
    and makes the no-spill regime — where the result is bit-identical to
    tree/gather — cover every realistic shape including tiny dryruns.

    Works for any axis size (not just powers of two) and for tuple axes
    (the mesh is flattened; the single a2a round trades the ICI/DCN
    hierarchy for one scheduled collective).
    """
    d = jax.lax.axis_size(axis)
    cap = table.capacity
    if d == 1:
        return table
    _count_build("keyrange", axis)
    b = min(cap, -(-int(slack * cap) // d) + 8 + 4 * (d - 1).bit_length())
    sent = jnp.uint32(table_ops.constants.SENTINEL_KEY)
    inf = jnp.uint32(table_ops.constants.POS_INF)
    zero = jnp.uint32(0)

    # 1. Pack: sort rows by (owner, key); dead rows get owner D (sorts last,
    #    never sent).  Keys are unique within a table, so (owner, key_hi,
    #    key_lo) is already a total order; pos/count lanes ride as payload.
    owner = jnp.where(table.occupied(),
                      table.key_lo % jnp.uint32(d), jnp.uint32(d))
    own_s, khi, klo, cnt, cnth, phi, plo, ln = jax.lax.sort(
        (owner, table.key_hi, table.key_lo, table.count, table.count_hi,
         table.pos_hi, table.pos_lo, table.length), num_keys=3)
    own_i = own_s.astype(jnp.int32)
    # heads[q] = first sorted row with owner >= q (q = 0..D; owner values
    # are sorted, which is all the shared binary search needs).
    heads = table_ops._segment_heads(own_i, d)

    # Destination slot t of block j holds partition j's rank-t row.
    slot = jnp.arange(d * b, dtype=jnp.int32)
    j, r = slot // b, slot % b
    src = heads[j] + r
    valid = src < heads[j + 1]
    srcc = jnp.minimum(src, cap - 1)
    take = lambda a, fill: jnp.where(valid, a[srcc], fill)
    s_khi, s_klo = take(khi, sent), take(klo, sent)
    s_cnt, s_cnth = take(cnt, zero), take(cnth, zero)
    s_phi, s_plo = take(phi, inf), take(plo, inf)
    s_ln = take(ln, zero)

    # Budget spill: within-partition rank >= B — deterministically the
    # partition's largest keys (see docstring for why this stays exact).
    rank = jnp.arange(cap, dtype=jnp.int32) - heads[jnp.minimum(own_i, d)]
    spilled = (own_i < d) & (rank >= b)
    sp_u = jnp.sum(spilled.astype(jnp.uint32))
    sp_lo, sp_hi = table_ops.sum64(jnp.where(spilled, cnt, zero),
                                   jnp.where(spilled, cnth, zero))

    # 2. Exchange: block j goes to device j; block s received from source s.
    def a2a(a):
        return jax.lax.all_to_all(a.reshape(d, b), axis,
                                  split_axis=0, concat_axis=0).reshape(d * b)

    # 3. Owner reduce: all sources' rows of MY partition -> capacity B.
    mine = table_ops._build(a2a(s_khi), a2a(s_klo), a2a(s_phi), a2a(s_plo),
                            a2a(s_cnt), a2a(s_cnth), a2a(s_ln), b,
                            zero, zero, zero, zero)

    # 4. Replicate: gather every owner's reduced block, final reduce to C.
    ag = lambda a: jax.lax.all_gather(a, axis).reshape(d * b)
    du_lo, du_hi = table_ops.add64(table.dropped_uniques,
                                   table.dropped_uniques_hi, sp_u, zero)
    dc_lo, dc_hi = table_ops.add64(table.dropped_count,
                                   table.dropped_count_hi, sp_lo, sp_hi)
    du_lo, du_hi = table_ops.add64(du_lo, du_hi, mine.dropped_uniques,
                                   mine.dropped_uniques_hi)
    dc_lo, dc_hi = table_ops.add64(dc_lo, dc_hi, mine.dropped_count,
                                   mine.dropped_count_hi)
    gdu_lo, gdu_hi = psum64(du_lo, du_hi, axis)
    gdc_lo, gdc_hi = psum64(dc_lo, dc_hi, axis)
    return table_ops._build(ag(mine.key_hi), ag(mine.key_lo), ag(mine.pos_hi),
                            ag(mine.pos_lo), ag(mine.count), ag(mine.count_hi),
                            ag(mine.length), cap,
                            gdu_lo, gdu_hi, gdc_lo, gdc_hi)


def hierarchical_merge(state: T, merge: MergeFn, axes: tuple[str, ...],
                       strategy: str = "tree") -> T:
    """Level-by-level merge over a multi-axis mesh, innermost axis first.

    The multi-slice/multi-host pattern (SURVEY §5 "distributed communication
    backend"): on a mesh like ``('replica', 'data')`` where the inner axis
    rides ICI within a slice and the outer axis rides DCN across slices,
    reducing the fast axis first shrinks what crosses the slow link to one
    already-merged state per slice — the two-level reduction of the build
    plan (SURVEY §7 step 4).  Axes are given outermost-first, matching mesh
    construction order.
    """
    if strategy == "hier-tree-tree":
        strategy = "tree"  # the named 2-D descriptor for the same schedule
    if strategy not in ("tree", "gather"):
        raise ValueError(f"unknown strategy {strategy!r}")
    fn = tree_merge if strategy == "tree" else gather_merge
    for axis in reversed(axes):
        state = fn(state, merge, axis)
    return state


def hier_tree_tree_merge(state: T, merge: MergeFn,
                         axes: tuple[str, ...]) -> T:
    """The named 2-D tree composition (planner descriptor
    ``hier-tree-tree``): butterfly per level, innermost (ICI) axis first,
    so the outer (DCN) level moves one already-merged payload per slice.
    Exactly :func:`hierarchical_merge` with ``strategy='tree'`` — named so
    the planner's descriptor table maps one-to-one onto a runtime builder.
    """
    return hierarchical_merge(state, merge, axes, strategy="tree")


def hier_kr_tree_merge(state: T, keyrange_fn, result_merge: MergeFn,
                       axes: tuple[str, ...]) -> T:
    """Placed 2-D reduction (planner descriptor ``hier-kr-tree``):
    key-range reduce-scatter on the INNERMOST axis (the ICI level, where
    the budgeted all_to_all's 2sM bytes are cheap and the owner merges are
    capacity/D-sized), then butterfly tree over the OUTER axes (the DCN
    level crosses once per round with the already-reduced payload).

    ``keyrange_fn(state, axis)`` is the job's ``keyrange_merge`` hook: it
    folds any batched shape and returns the replicated REDUCED result
    (wordcount family: a plain CountTable).  ``result_merge`` must be a
    merge valid on that result shape (the job's ``keyrange_result_merge``
    hook) — the outer tree legs run on keyrange's output, not on the raw
    accumulator shape.
    """
    if len(axes) < 2:
        raise ValueError(
            f"hier-kr-tree composes two mesh levels; got axes {axes!r}")
    merged = keyrange_fn(state, axes[-1])
    for axis in reversed(axes[:-1]):
        merged = tree_merge(merged, result_merge, axis)
    return merged


# Reduction-strategy descriptors (ISSUE 16): the machine-readable surface
# the static planner enumerates.  Names are the Engine ``merge_strategy``
# values; ``builder`` is the function this module actually dispatches.
# ``analysis/meshcost.py`` (jax-free, so it cannot import this module)
# carries a mirrored table with the same names/builders/constraints — a
# test asserts the two stay in bijection, so the planner can never rank a
# strategy the runtime does not build (or miss one it does).
STRATEGIES: dict[str, dict] = {
    "tree": {
        "builder": f"{__name__}.tree_merge",
        "power_of_two_only": True,  # non-pow2 axes fall back to gather
        "needs_keyrange_hook": False,
        "per_axis": True,  # hierarchical_merge runs it innermost-first
    },
    "gather": {
        "builder": f"{__name__}.gather_merge",
        "power_of_two_only": False,
        "needs_keyrange_hook": False,
        "per_axis": True,
    },
    "keyrange": {
        "builder": f"{__name__}.key_range_merge",
        "power_of_two_only": False,
        "needs_keyrange_hook": True,  # Engine requires job.keyrange_merge
        "per_axis": False,  # flattens the whole mesh into one collective
    },
    # The 2-D placed compositions (ISSUE 20): whole-mesh builders that
    # assign a strategy per link level the way the planner prices them.
    "hier-kr-tree": {
        "builder": f"{__name__}.hier_kr_tree_merge",
        "power_of_two_only": True,  # the outer tree legs (gather fallback)
        "needs_keyrange_hook": True,  # inner leg is the job keyrange hook
        "per_axis": False,  # fixed placement: keyrange inner, tree outer
    },
    "hier-tree-tree": {
        "builder": f"{__name__}.hier_tree_tree_merge",
        "power_of_two_only": True,
        "needs_keyrange_hook": False,
        "per_axis": False,  # the named whole-mesh composition
    },
}
