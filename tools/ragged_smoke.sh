#!/bin/bash
# Unified ragged-step smoke (ISSUE 18) — the tier-1 gate shape of
# `bench_serving.py --smoke --ragged`: the same greedy Poisson trace
# through a bucketed and a ragged engine (one warm engine each,
# two-point marginal), token-exactness asserted across the two, and
# the ragged engine's compiled step-program-class count asserted <= 2.
#
# CPU-only by construction (`--smoke` selects the CPU mesh; the
# unified ragged Pallas kernel stays behind PADDLE_TPU_PAGED_KERNEL and
# is interpret-mode, CPU only).  Never banks:
# BENCH_serving_ragged.json is written only by full (non-smoke) runs
# on a quiet VM.
set -o pipefail
cd "$(dirname "$0")/.."
timeout -k 10 300 python bench_serving.py --smoke --ragged
