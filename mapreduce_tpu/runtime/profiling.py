"""Profiler hooks: XProf/Perfetto traces and named trace regions.

The reference has no tracing of any kind (``time.h`` is a dead include,
``main.cu:6``; SURVEY §5).  Here any run can capture a ``jax.profiler`` trace
— device timelines, XLA op breakdown, HBM usage — viewable in XProf /
Perfetto, plus cheap named host regions that show up on the same timeline.

Usage::

    with profiling.trace("/tmp/trace"):     # no-op when path is falsy
        with profiling.region("step"):
            state = engine.step(state, batch.data, batch.step)
"""

from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional


@contextlib.contextmanager
def trace(path: Optional[str]) -> Iterator[None]:
    """Capture a jax.profiler trace under ``path`` (a directory).  Falsy path
    = no-op, so call sites can pass the flag through unconditionally."""
    if not path:
        yield
        return
    import jax

    with jax.profiler.trace(path):
        yield


@contextlib.contextmanager
def region(name: str) -> Iterator[None]:
    """A named region on the profiler timeline (cheap when not tracing)."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


#: The compile cache's one place when the environment names none: inside
#: the checkout (git-ignored), so every run of this tree hits the same cache.
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))), ".jax_cache")


def enable_compile_cache() -> None:
    """Place JAX's persistent compilation cache.

    First compiles of the streaming step take minutes on the chip; the
    cache makes every later same-shape run skip them.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing is
    set here; otherwise the cache lives at :data:`CACHE_DIR`.  Whether the
    cache is on at all stays JAX's own ``jax_enable_compilation_cache``
    switch (the test suite turns it off).
    """
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
