#!/usr/bin/env python3
"""Per-family cost rows: what each shipped job family pays vs plain
word count on the SAME corpus.

Every family the CLI ships now gets a measured end-to-end number from one
tool, one family per invocation so each gets its own capture and
deadline:

    python tools/familybench.py plain     # the denominator row
    python tools/familybench.py grep      # --grep the (literal pattern)
    python tools/familybench.py sample    # --sample 16 (reservoir)
    python tools/familybench.py sketch    # --distinct-sketch (HLL ride-along)
    python tools/familybench.py verify    # --verify-sample 64: K=64 byte-
                                          # exact recount against the corpus
                                          # oracle; MUST log zero mismatches

Each run streams the same cached synthetic corpus file through the real
CLI in a fresh subprocess (fresh jax, ambient platform — TPU on the chip,
CPU elsewhere) and prints one JSON line: family, wall seconds, corpus
bytes, GB/s, and the verify line when applicable.  Overhead-vs-plain is
computed by the reader from the plain row of the same session.

Env knobs: FAMILY_MB (default 64), FAMILY_CORPUS (zipf|natural|webby|
markup, default zipf), FAMILY_CHUNK_MB (default 32), FAMILY_TIMEOUT_S
(default 1500).  CPU sanity: JAX_PLATFORMS=cpu FAMILY_MB=4
FAMILY_CHUNK_MB=1 python tools/familybench.py grep.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

FAMILIES = ("plain", "grep", "sample", "sketch", "verify")


def corpus_path(kind: str, mb: int) -> str:
    """Generate (once) and cache the bench corpus as a real file — the
    streamed CLI path reads files, and all family rows must share bytes."""
    path = f"/tmp/familybench_{kind}_{mb}mb.txt"
    if os.path.exists(path) and os.path.getsize(path) > 0:
        return path
    import bench

    maker = {"zipf": bench.make_zipf_corpus,
             "natural": bench.make_natural_corpus,
             "webby": bench.make_webby_corpus,
             "markup": bench.make_markup_corpus}[kind]
    blob = maker(mb << 20)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, path)
    return path


def family_args(family: str) -> list[str]:
    return {
        "plain": [],
        "grep": ["--grep", "the"],
        "sample": ["--sample", "16"],
        "sketch": ["--distinct-sketch"],
        "verify": ["--verify-sample", "64"],
    }[family]


def main() -> int:
    if len(sys.argv) != 2 or sys.argv[1] not in FAMILIES:
        print(f"usage: familybench.py {{{'|'.join(FAMILIES)}}}",
              file=sys.stderr)
        return 2
    family = sys.argv[1]
    mb = int(os.environ.get("FAMILY_MB", "64"))
    kind = os.environ.get("FAMILY_CORPUS", "zipf")
    chunk_mb = int(os.environ.get("FAMILY_CHUNK_MB", "32"))
    timeout_s = float(os.environ.get("FAMILY_TIMEOUT_S", "1500"))

    path = corpus_path(kind, mb)
    n_bytes = os.path.getsize(path)
    cmd = [sys.executable, "-m", "mapreduce_tpu.cli", path, "--stream",
           "--no-echo", "--format", "json",
           "--chunk-bytes", str(chunk_mb << 20)] + family_args(family)
    t0 = time.monotonic()
    proc = subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                          timeout=timeout_s)
    wall = time.monotonic() - t0
    # "verify: ok" goes to stderr (the CLI keeps stdout machine-parseable);
    # mismatches also land there before the rc=4 exit.
    verify_line = next((ln for ln in (proc.stdout + proc.stderr).splitlines()
                        if ln.startswith("verify:")), None)
    record = {
        "tool": "familybench", "family": family, "corpus": kind,
        "corpus_mb": mb, "chunk_mb": chunk_mb, "bytes": n_bytes,
        "seconds": round(wall, 3),
        "gbps": round(n_bytes / wall / 1e9, 4),
        "rc": proc.returncode,
    }
    if family == "verify":
        # The satellite's contract: a zero-mismatch K=64 byte-exact
        # recount line, machine-checkable (rc != 0 on any mismatch).
        record["verify"] = verify_line
        record["verify_ok"] = proc.returncode == 0 and \
            verify_line is not None and "ok" in verify_line
    if proc.returncode != 0:
        record["stderr_tail"] = proc.stderr[-2000:]
    print(json.dumps(record))
    return 0 if proc.returncode == 0 else 1


if __name__ == "__main__":
    sys.exit(main())
