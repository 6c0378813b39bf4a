#!/bin/bash
# Chaos smoke — the tier-1 gate shape of tools/chaos_fuzz.py (ISSUE 10):
# ONE fixed seed, small waves, runtime-bounded, asserting the global
# recovery invariants (page conservation, token exactness vs the
# fault-free oracle, zero leaks, liveness) and that the chaos schedule
# actually fired.  The full multi-seed fuzz with the all-points
# coverage requirement is the `slow`-marked test in
# tests/test_serving_chaos.py.
#
# CPU-only by construction (the fuzz driver forces jax_platforms=cpu
# itself).
set -o pipefail
cd "$(dirname "$0")/.."
timeout -k 10 300 python tools/chaos_fuzz.py --smoke
