"""Multi-host runtime: process bootstrap + per-host work partitioning.

The distributed communication backend of the framework (SURVEY §5: the
reference has none — its only transport is single-GPU PCIe ``cudaMemcpy``,
``main.cu:147,157-158``).  Cross-chip data movement itself is expressed as
XLA collectives (:mod:`mapreduce_tpu.parallel.collectives`) compiled over the
ICI/DCN mesh; what remains host-side is (a) bringing every process into one
JAX runtime and (b) deciding which byte-range of the corpus each host reads.
This module owns both.

Two multi-host modes::

    from mapreduce_tpu.parallel import distributed as dist

    dist.initialize()                      # no-op on a single host

    # (a) per-host-driven (tested end-to-end in tests/test_multihost.py):
    #     each host runs the executor over its OWN devices and its own
    #     byte-range, then partial tables are merged (host-side
    #     table_ops.merge, or any reduction transport).
    lo, hi = dist.host_byte_range(os.path.getsize(path))
    lo, hi = dist.align_range_to_separator(path, lo, hi)
    rr = executor.run_job(job, path, byte_range=(lo, hi))   # local mesh

    # (b) one global SPMD program: a global mesh plus per-host staging —
    #     run from ONE entry point, executor.run_job_global (every process
    #     calls it with the same arguments; each stages only its own
    #     host_shards rows via device_put_local, the collective finish
    #     replicates the result, checkpoints are coordinator-written and
    #     resumable — tested end-to-end with a real 2-process gloo run in
    #     tests/test_multihost.py, crash + resume included).
    rr = executor.run_job_global(job, path, config=cfg, checkpoint_path=ck)

``initialize`` wraps :func:`jax.distributed.initialize`, which reads the
cluster-environment variables (coordinator address, process count/index) that
TPU pod launchers export; on a laptop or a single TPU VM it does nothing, so
the same program runs unmodified at every scale.
"""

from __future__ import annotations

import os
import time
from typing import Optional, Sequence

import jax
import numpy as np

from mapreduce_tpu.obs import registry as obs_registry
from mapreduce_tpu.runtime.logging import get_logger, log_event


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None,
               timeout_s: int = 300) -> None:
    """Join this process to the cluster-wide JAX runtime.

    Arguments default to auto-detection from the launcher environment (the
    behavior of :func:`jax.distributed.initialize`); pass them explicitly for
    bare-metal/SSH launches.  Safe to call on a single host: when no cluster
    environment exists and no arguments are given, it's a no-op.

    Failure detection (SURVEY §5): a host that cannot reach the coordinator
    raises within ``timeout_s`` instead of hanging the pod; the error is
    logged with the process identity so the failing host is identifiable
    from any log stream.
    """
    # NOTE: must not touch jax.process_count()/jax.devices() here — any such
    # call initializes the XLA backend, after which
    # jax.distributed.initialize() refuses to run.
    if jax.distributed.is_initialized():
        return
    explicit = coordinator_address or num_processes or process_id
    env = (os.environ.get("COORDINATOR_ADDRESS")
           or os.environ.get("JAX_COORDINATOR_ADDRESS")
           or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"))
    if not explicit and not env and not _on_pod():
        return  # single-host run: nothing to join
    # Init wall-clock into the registry: a pod bring-up that creeps from
    # seconds to minutes (DNS, a slow peer, a flaky coordinator) shows up
    # in every run's metrics snapshot instead of being lost to stderr.
    reg = obs_registry.get_registry()
    t0 = time.perf_counter()
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            initialization_timeout=timeout_s)
    except Exception as e:
        reg.counter("distributed.init_failures").inc()
        log_event(get_logger(), "distributed initialization failed",
                  process_id=process_id, coordinator=coordinator_address or env,
                  error=repr(e))
        raise
    init_s = time.perf_counter() - t0
    # Shared run epoch (ISSUE 13): every process stamps its {wall, mono}
    # clock pair the moment the cluster-wide runtime is up — the pair
    # obs/fleet.py uses to rebase per-host monotonic lifecycle stamps
    # onto the (NTP-shared) wall clock when merging ledger shards.
    _stamp_epoch()
    reg.counter("distributed.inits").inc()
    reg.gauge("distributed.init_seconds").set(init_s)
    log_event(get_logger(), "distributed runtime up",
              process=jax.process_index(), processes=jax.process_count(),
              local_devices=len(jax.local_devices()),
              global_devices=len(jax.devices()),
              init_s=round(init_s, 3))


def _on_pod() -> bool:
    """True when a TPU pod launcher exported a multi-worker topology: a
    comma-separated list of two or more host names.  A single host may
    carry ``TPU_WORKER_HOSTNAMES`` too — naming only itself, or holding
    libtpu's warning text when a topology is merely described — and has
    nothing to join."""
    hosts = [h.strip() for h in
             os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")]
    return len(hosts) > 1 and all(h and not any(c.isspace() for c in h)
                                  for h in hosts)


#: {wall, mono} sampled together at jax.distributed init (lazily on
#: single-host runs).  wall - mono is this process's monotonic->wall
#: offset; wall clocks are the cross-host reference (same box in the CPU
#: harness, NTP on pods), so fleet merges align shard timelines with it.
_RUN_EPOCH: Optional[dict] = None


def _stamp_epoch() -> dict:
    global _RUN_EPOCH
    if _RUN_EPOCH is None:
        _RUN_EPOCH = {"wall": round(time.time(), 6),
                      "mono": round(time.perf_counter(), 6)}
    return _RUN_EPOCH


def run_epoch() -> dict:
    """This process's clock-alignment pair (ISSUE 13): wall-clock and
    monotonic seconds sampled together — stamped once at
    :func:`initialize` success, lazily on first use otherwise.  Written
    into every shard ledger's ``run_start`` as ``clock`` so
    ``obs/fleet.py`` can rebase each host's monotonic lifecycle stamps
    to the shared wall clock (``aligned = mono + (wall - mono_epoch)``).
    """
    return dict(_stamp_epoch())


def is_coordinator() -> bool:
    """True on the process that should own singleton side effects
    (checkpoint writes, final report printing)."""
    return jax.process_index() == 0


def global_data_mesh(axis: str = "data"):
    """1-D mesh over every chip of every host (devices are process-major,
    so contiguous index ranges align with hosts)."""
    from mapreduce_tpu.parallel.mesh import data_mesh

    return data_mesh(devices=jax.devices(), axis=axis)


def host_byte_range(file_size: int, process_index: Optional[int] = None,
                    process_count: Optional[int] = None) -> tuple[int, int]:
    """The half-open byte range of the corpus this host ingests.

    Even split by bytes, not lines — the reader aligns chunk boundaries to
    token separators within the range, and range seams are token-exact
    because the split offsets are identical on every host (each host extends
    its range's head to the first separator after the cut, mirroring the
    reader's boundary rule; see :func:`align_range_to_separator`).
    """
    p = jax.process_index() if process_index is None else process_index
    n = jax.process_count() if process_count is None else process_count
    if not 0 <= p < n:
        raise ValueError(f"process_index {p} outside [0, {n})")
    per = file_size // n
    lo = p * per
    hi = file_size if p == n - 1 else (p + 1) * per
    return lo, hi


def align_range_to_separator(path: str, lo: int, hi: int,
                             max_token_bytes: int = 1 << 16,
                             separators: bytes | None = None) -> tuple[int, int]:
    """Snap a byte range so both ends sit just after a separator byte.

    Every host applies the same deterministic rule to its own ``lo`` and
    ``hi``, so adjacent ranges stay exactly adjacent: a token spanning a raw
    cut is counted by the host whose range contains its first byte, and only
    by it.  ``max_token_bytes`` bounds the scan past the cut (a pathological
    separator-free file falls back to the raw offset, force-splitting the
    token exactly like the in-range reader does).

    ``separators`` overrides the boundary byte class (default: the token
    separator set).  Cross-host grep wants ``separators=b"\\n"`` so no
    logical LINE straddles a range seam — per-host line counts then merge
    exactly (:meth:`...models.grep.GrepJob.merge`).
    """
    from mapreduce_tpu import constants

    sep = bytes(constants.SEPARATOR_BYTES) if separators is None else separators
    size = os.path.getsize(path)

    def snap(off: int) -> int:
        if off <= 0 or off >= size:
            return max(0, min(off, size))
        with open(path, "rb") as f:
            f.seek(off - 1)
            window = f.read(max_token_bytes + 1)
        if window[0] in sep:  # byte off-1 is a separator: already aligned
            return off
        for i, b in enumerate(window[1:]):  # window[1+i] is byte off+i
            if b in sep:
                return off + i + 1  # just past that separator
        return off  # separator-free window: force-split like the reader
    return snap(lo), snap(hi)


def host_shards(n_global_shards: int,
                process_index: Optional[int] = None,
                process_count: Optional[int] = None) -> Sequence[int]:
    """Global shard indices owned by this host (contiguous, process-major —
    matching the device order of :func:`global_data_mesh`)."""
    p = jax.process_index() if process_index is None else process_index
    n = jax.process_count() if process_count is None else process_count
    if n_global_shards % n:
        raise ValueError(
            f"{n_global_shards} shards do not divide over {n} processes")
    per = n_global_shards // n
    return range(p * per, (p + 1) * per)


def device_put_local(batch: np.ndarray, sharding):
    """Place this host's rows of a [global_shards, ...] batch onto its local
    devices, assembling the global sharded array without materializing other
    hosts' data (``jax.make_array_from_process_local_data``)."""
    return jax.make_array_from_process_local_data(sharding, batch)
