#!/usr/bin/env bash
# Fast iteration gate (round-5 review #7): the <5-minute smoke subset — golden
# semantics, CLI surface, table units, one pallas-interpret case, config
# validation, the costcheck known-bad fixtures, and the ISSUE 6 fused-map
# seam (stream-level fused-vs-split row-set identity, ngram bit-identity,
# the oracle-exact fused rescue+spill case, and the fused-below-split
# cost gate) — so a mid-PR edit gets a signal in minutes instead of the
# ~12-minute tier-1 run.
#
# Green here is NOT the gate: tier-1 (tools/tier1.sh) stays the merge bar
# and the full suite (no marker filter) the release bar.  Prints
# DOTS_PASSED like tier1.sh and exits with pytest's status.
cd "$(dirname "$0")/.." || exit 1
# The jax-free obs_report/trace-export selftests (ISSUE 7/8) cost well
# under a second each and catch fixture/reconstruction/data-health drift
# before any jax import.
timeout -k 5 60 python tools/obs_report.py --selftest || { echo "SMOKE: obs_report selftest FAILED"; exit 1; }
timeout -k 5 60 python tools/trace_export.py --selftest || { echo "SMOKE: trace_export selftest FAILED"; exit 1; }
timeout -k 5 60 python mapreduce_tpu/obs/fleet.py --selftest || { echo "SMOKE: fleet selftest FAILED"; exit 1; }
timeout -k 5 60 python mapreduce_tpu/obs/history.py --selftest || { echo "SMOKE: history selftest FAILED"; exit 1; }
timeout -k 5 60 python tools/obswatch.py --selftest || { echo "SMOKE: obswatch selftest FAILED"; exit 1; }
timeout -k 5 60 python tools/autotune.py --selftest || { echo "SMOKE: autotune selftest FAILED"; exit 1; }
timeout -k 5 60 python tools/geomsearch.py --selftest || { echo "SMOKE: geomsearch selftest FAILED"; exit 1; }
timeout -k 5 60 python tools/chaos.py --selftest || { echo "SMOKE: chaos selftest FAILED"; exit 1; }
timeout -k 5 60 python tools/redplan.py --selftest || { echo "SMOKE: redplan selftest FAILED"; exit 1; }
set -o pipefail; rm -f /tmp/_smoke.log; timeout -k 10 300 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'smoke and not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_smoke.log; rc=${PIPESTATUS[0]}; echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_smoke.log | tr -cd . | wc -c); exit $rc
