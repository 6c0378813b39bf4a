#!/bin/bash
# Tier-1 verify wrapper: the gates below, then the whole fast suite as
# the driver runs it (/root/TESTS_LAST_RUN.json: six xdist workers,
# `--dist loadfile`, `timeout 1470`, passes counted from the junit file).
# PR 25 measured that command at 0.57 of its former wall time (674 s on
# an idle 8-core machine), every test collected and none deselected
# (CHANGES.md).
#
# Usage: bash tools/tier1.sh
# Exit code is pytest's; DOTS_PASSED echoes the count of passes.
set -o pipefail
cd "$(dirname "$0")/.."
# graftlint gate (ISSUE 6): invariant lint + env-knob registry sync
# run ahead of the suite — a new finding fails tier-1 before pytest.
bash tools/lint.sh || exit 1
# chaos smoke (ISSUE 10): one fixed-seed fault schedule through a
# mixed fleet, global recovery invariants asserted — runtime-bounded
# so the pytest window stays intact.
bash tools/chaos_smoke.sh || exit 1
# fleet smoke (ISSUE 12): process-backed fleet + router takeover under
# kills, SLO-gated (zero lost streams / zero leaked processes) —
# runtime-bounded, CPU-only.
bash tools/fleet_smoke.sh || exit 1
# kvtier smoke (ISSUE 16): host/disk page-tier spill→restore replay +
# fault-point/conservation classes — runtime-bounded, CPU-only; banks
# nothing (the script snapshots BENCH_serving_kvtier.json itself).
bash tools/kvtier_smoke.sh || exit 1
# deploy smoke (ISSUE 17): rolling weight swap under traffic + replica
# kill, version-pinned exactness + distill acceptance gates —
# runtime-bounded, CPU-only; never banks BENCH_serving_deploy.json.
bash tools/deploy_smoke.sh || exit 1
# tp smoke (ISSUE 19): TP=1 vs TP=2 SPMD step replay on the 8-device
# CPU mesh, token-exact across degrees — runtime-bounded, CPU-only;
# never banks BENCH_serving_tp.json.
bash tools/tp_smoke.sh || exit 1
rm -f /tmp/_t1.log /tmp/_t1.xml
timeout -k 10 1470 env JAX_PLATFORMS=cpu ALLOW_MULTIPLE_LIBTPU_LOAD=1 \
  python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p xdist -n 6 \
  --dist loadfile --junitxml=/tmp/_t1.xml -p no:randomly 2>&1 \
  | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
  | tr -cd . | wc -c)
exit $rc
