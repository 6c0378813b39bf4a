#!/bin/bash
# Hierarchical KV tier smoke — the tier-1 gate shape of the round-20
# host/disk page tier (ISSUE 16): the bench_serving --kvtier smoke
# replay (revisit thrash over a device pool too small for the working
# set, ≥3 host-pool sizes including the pool=0 recompute baseline plus
# a RAM+disk point) asserting that at least one pool size actually
# restored spilled pages, PLUS the pytest fault-point/conservation
# classes (spill→restore bit-exactness per cache_dtype, best-effort
# degradation under every tier fault point, cross-tier conservation).
#
# CPU-only by construction (bench smoke mode selects the CPU mesh; the
# tests run on the suite's virtual CPU mesh).  The conftest BENCH
# snapshot guard
# is a pytest fixture and does not cover this entry point, so the
# script snapshots BENCH_serving_kvtier.json itself and restores it on
# exit — re-banking stays a deliberate quiet-VM act (round-12
# addenda).
set -o pipefail
cd "$(dirname "$0")/.."
snap=""
if [ -f BENCH_serving_kvtier.json ]; then
  snap=$(mktemp)
  cp BENCH_serving_kvtier.json "$snap"
fi
restore() {
  if [ -n "$snap" ]; then
    mv -f "$snap" BENCH_serving_kvtier.json
  else
    rm -f BENCH_serving_kvtier.json
  fi
}
trap restore EXIT
timeout -k 10 300 python bench_serving.py --smoke --kvtier || exit 1
timeout -k 10 420 env JAX_PLATFORMS=cpu python -m pytest \
  tests/test_serving_kvtier.py::TestSpillRestore \
  tests/test_serving_kvtier.py::TestTierFaultPoints \
  tests/test_serving_kvtier.py::TestCrossTierConservation \
  -q -p no:cacheprovider || exit 1
