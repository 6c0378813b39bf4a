#!/bin/bash
# graftlint wrapper: invariant lint + env-knob registry sync.
# Non-zero on any NEW finding (baseline-grandfathered ones pass) or
# when docs/ENV_KNOBS.md is out of sync with the tree.
# Wired into tools/tier1.sh ahead of pytest (ISSUE 6); safe anywhere —
# tools/lint.py never imports jax (stub-parent import), so it never
# claims the chip.
set -o pipefail
cd "$(dirname "$0")/.."
rc=0
python tools/lint.py paddle_tpu tools tests || rc=1
python tools/lint.py --check-knobs || rc=1
exit $rc
