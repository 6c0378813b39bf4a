#!/bin/bash
# Versioned-deployment smoke — the tier-1 gate shape of
# tools/deploy_harness.py (ISSUE 17): an in-process fleet serves paced
# traffic while a RollingDeployer rolls the target weights one replica
# at a time (drain → quiesce-swap → readmit) with a replica-kill drill
# mid-rollout, gated on VERSION-PINNED exactness — every client stream
# matches ONE version's oracle in its entirety, zero lost streams,
# zero cross-version splices, every replica on the new version — plus
# the distillation leg: a draft trained on logged verify pairs is
# pushed through the same deployer and the measured acceptance rate
# must improve while emitted tokens stay bit-identical.
#
# CPU-only by construction (the harness forces jax_platforms=cpu).
# Never banks:
# BENCH_serving_deploy.json is written only by full (non-smoke) runs
# on a quiet VM.
set -o pipefail
cd "$(dirname "$0")/.."
timeout -k 10 300 python tools/deploy_harness.py --smoke
