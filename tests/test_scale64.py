"""Distributed paths at D=64 virtual devices (round-4 review missing #1).

The 8-device conftest mesh exercises correctness of the SPMD programs, but
SPMD *program bugs* — reshape/layout limits in ``all_to_all``, the keyrange
budget arithmetic ``b = 2C/D``, collective scheduling — characteristically
appear at larger D.  (Proven immediately: this test's first D=64 run caught
the keyrange-vs-tree ``dropped_uniques`` bound divergence under spill that
D=8 could never see.)  The driver's dryrun runs the FULL battery at D=8;
here the GEOMETRY-sensitive subset (tree/hierarchical/keyrange merges with
bit-identity checks, superstep scan, run_job_global staging) runs at D=64
in a SUBPROCESS (the session's device count is pinned at import time).

Manual wider runs: ``MAPREDUCE_SCALE_FULL=1`` adds every model family;
``MAPREDUCE_SCALE_DEVICES=256`` runs the pod-scale row.  Budget: the
geometry subset compiles in a few minutes on this one-core box; the full
battery at D=64 costs ~an hour of XLA compile and is not suite material.
"""

import os
import pytest
import signal
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_dryrun_multichip_at_64_devices(tmp_path):
    n = int(os.environ.get("MAPREDUCE_SCALE_DEVICES", "64"))
    # The geometry subset fits well inside 30 min; the documented manual
    # escape hatches (full battery / D=256) budget ~an hour of one-core
    # XLA compile and get a matching deadline.
    wide = os.environ.get("MAPREDUCE_SCALE_FULL", "0") == "1" or n > 64
    deadline_s = 7200 if wide else 1800
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out_path = tmp_path / "scale.out"
    # File-backed output + its own session: no capture pipes to deadlock
    # on, and cleanup kills the whole process GROUP (a timed-out child's
    # own descendants included) — subprocess.run(capture_output=True) can
    # block forever in communicate() after an external kill.
    with open(out_path, "w") as out_f:
        proc = subprocess.Popen(
            [sys.executable, "-c",
             f"import sys; sys.path.insert(0, {REPO!r})\n"
             f"from __graft_entry__ import _force_cpu_mesh, dryrun_multichip\n"
             f"jax = _force_cpu_mesh({n})\n"
             f"assert len(jax.devices()) >= {n}, len(jax.devices())\n"
             f"dryrun_multichip({n})\n"
             f"print('scale-ok', {n})\n"],
            cwd=REPO, env=env, stdout=out_f, stderr=subprocess.STDOUT,
            start_new_session=True)
        try:
            rc = proc.wait(timeout=deadline_s)
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()
            rc = -9
    body = out_path.read_text()
    assert rc == 0, (f"(rc={rc}; -9 means the {deadline_s}s deadline "
                     f"expired)\n" + body[-4000:])
    assert f"scale-ok {n}" in body
