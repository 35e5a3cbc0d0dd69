"""Platform forcing and reporting — the force-CPU idiom, in ONE place.

Something may import jax and pin ``jax_platforms`` before user code runs,
which makes the ``JAX_PLATFORMS`` env var alone too late to redirect a run.
The backend itself still initializes lazily, so ``jax.config.update`` lands
as long as no device has been touched yet.  That two-step — set the env var
for child processes, update the config for this process — previously lived
as three divergent copies (``__graft_entry__``, ``tests/conftest.py`` via
the former, ``tests/multihost_worker.py``); they now all call
:func:`force_cpu`.

Reference analogue: none — the reference runs wherever nvcc pointed it
(``main.cu`` has no device selection at all); SURVEY §5 config/flag system.
"""

from __future__ import annotations

import os
import re


def force_cpu(min_devices: int = 0, verify: bool = True):
    """Force the CPU platform hermetically; verify the force landed.

    ``min_devices > 0`` additionally guarantees that many virtual CPU devices
    (``--xla_force_host_platform_device_count``, raised but never lowered —
    an ambient larger value keeps working).  Returns the imported ``jax``
    module.  Raises ``RuntimeError`` if a non-CPU backend was already
    initialized (the config update then cannot redirect device resolution —
    proceeding would silently dial the platform the caller asked to escape).

    ``verify=False`` skips the check for callers that must not initialize
    the backend yet (``jax.distributed.initialize()`` requires a pristine
    runtime); they own verifying the platform after their own init.
    """
    os.environ["JAX_PLATFORMS"] = "cpu"  # children inherit the request
    if min_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        m = re.search(r"--xla_force_host_platform_device_count=(\d+)", flags)
        if m is None:
            flags = (flags +
                     f" --xla_force_host_platform_device_count={min_devices}").strip()
        elif int(m.group(1)) < min_devices:
            flags = flags[: m.start(1)] + str(min_devices) + flags[m.end(1):]
        os.environ["XLA_FLAGS"] = flags

    import jax

    if (jax.config.jax_platforms or "") != "cpu":
        jax.config.update("jax_platforms", "cpu")
    if not verify:
        return jax
    backend = jax.default_backend()  # initializes the (cpu) backend: verify
    if backend != "cpu":
        raise RuntimeError(
            f"cpu was requested but the {backend!r} JAX backend was already "
            "initialized before the platform could be forced; set "
            "JAX_PLATFORMS=cpu in the environment before starting python")
    if min_devices and len(jax.devices()) < min_devices:
        raise RuntimeError(
            f"need {min_devices} virtual CPU devices, have "
            f"{len(jax.devices())}: xla_force_host_platform_device_count "
            "landed after backend init")
    return jax


def effective_platforms() -> str:
    """The platform string JAX will actually dial, lowercase ('' = resolve a
    local backend).  Reads the CONFIG first — the env var neither redirects a
    pinned process nor predicts what an unpinned one resolves."""
    import jax

    return (jax.config.jax_platforms or os.environ.get("JAX_PLATFORMS", "")).lower()
