"""Flight recorder: a bounded ring of recent events, dumped on failure.

A run that dies or hangs must leave something on disk saying what it was
doing.  The flight recorder does that (ISSUE 2 tentpole (3)):
the executor records a tiny host-side event per dispatch / retry /
checkpoint into a fixed-size ring buffer, and the failure path dumps the
ring plus a state snapshot summary and the metrics-registry snapshot to a
JSON file, so a crashed or wedged run leaves forensics instead of nothing.

Recording cost is one deque.append of a small dict — host-only, no device
sync, O(1) memory (the ring evicts) — so it is safe to leave on for every
telemetered run.
"""

from __future__ import annotations

import collections
import json
import os
import time
from typing import Optional


DEFAULT_CAPACITY = 256


class FlightRecorder:
    """Bounded event ring + one-shot crash dump."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self.events_recorded = 0  # total, including evicted
        self.dumped_to: Optional[str] = None

    def record(self, kind: str, **fields) -> None:
        self._ring.append({"ts": round(time.time(), 6), "kind": kind,
                           **fields})
        self.events_recorded += 1

    def events(self) -> list:
        return list(self._ring)

    def dump(self, path: str, context: Optional[dict] = None,
             state_summary: Optional[dict] = None,
             registry_snapshot: Optional[dict] = None,
             data: Optional[dict] = None,
             data_health: Optional[dict] = None) -> Optional[str]:
        """Write the forensics file; returns the path actually written, or
        ``None`` when the write failed (read-only/full filesystem) — a
        ledger failure record must not point at a dump that does not
        exist.  Idempotent per recorder: the first SUCCESSFUL dump owns
        the file (later failures in the same run would only overwrite the
        interesting one with unwind noise).  Best-effort by contract — a
        dump failure must never mask the run failure being reported."""
        if self.dumped_to is not None:
            return self.dumped_to
        payload = {
            "dumped_at": round(time.time(), 6),
            "context": context or {},
            "events_recorded": self.events_recorded,
            "events_kept": len(self._ring),
            "events": list(self._ring),
        }
        if state_summary is not None:
            payload["state"] = state_summary
        if registry_snapshot is not None:
            payload["metrics"] = registry_snapshot
        if data is not None:  # data-plane snapshot as of the crash (ISSUE 8)
            payload["data"] = data
        if data_health is not None:
            payload["data_health"] = data_health
        try:
            d = os.path.dirname(os.path.abspath(path))
            os.makedirs(d, exist_ok=True)
            with open(path, "w", encoding="utf-8") as f:
                json.dump(payload, f, indent=1, default=repr)
                f.write("\n")
        except OSError:
            return None  # the run failure itself still surfaces
        self.dumped_to = path
        return path


def summarize_state(state) -> dict:
    """Leaf-level summary of a host state pytree for the dump: shapes,
    dtypes, and byte sizes — enough to see WHAT was in flight without
    serializing a multi-GB accumulator into a crash file."""
    import jax
    import numpy as np

    leaves = jax.tree.leaves(state)
    out = {"n_leaves": len(leaves), "leaves": []}
    total = 0
    # total_nbytes covers EVERY leaf (it is what an OOM triage reads);
    # only the per-leaf detail list is capped to bound the dump size.
    for i, leaf in enumerate(leaves):
        arr = np.asarray(leaf)
        total += arr.nbytes
        if i < 64:
            out["leaves"].append({"shape": list(arr.shape),
                                  "dtype": str(arr.dtype),
                                  "nbytes": int(arr.nbytes)})
    out["total_nbytes"] = int(total)
    return out
