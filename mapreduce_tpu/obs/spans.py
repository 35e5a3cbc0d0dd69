"""Span-based tracing: one context manager = profiler region + phase time.

ISSUE 2 tentpole (4): the executor's phase decomposition (``read_wait`` /
``stage`` / ``dispatch``) and the XProf timeline were previously separate
worlds — the ledger said "dispatch took 8 s" and the profiler trace had no
marker saying which 8 s that was.  A :func:`span` nests a
``jax.profiler.TraceAnnotation`` around the timed section AND accumulates
the wall-clock into a :class:`...runtime.metrics.PhaseTimer`, so ledger
records and profiler timelines line up by construction.

Host-only: a TraceAnnotation is a nanosecond-scale TraceMe when no trace is
active, and nothing here runs inside a jitted program.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator


@contextlib.contextmanager
def span(name: str, timer=None) -> Iterator[None]:
    """Time a section as ``name``: the profiler-timeline label, and the
    phase key in ``timer`` (a ``PhaseTimer``, optional)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if timer is not None:
                timer.phases[name] = timer.phases.get(name, 0.0) \
                    + time.perf_counter() - t0
