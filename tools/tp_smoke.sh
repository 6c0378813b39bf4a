#!/bin/bash
# Tensor-parallel serving smoke (ISSUE 19) — the tier-1 gate shape of
# `bench_serving.py --smoke --tp`: the same greedy Poisson trace
# through a TP=1 and a TP=2 engine on the 8-device CPU mesh (one warm
# engine each, two-point marginal), token-exactness asserted across
# the degrees — the by-construction contract (only non-contracting
# dims shard; collectives are pure data movement) checked end to end.
#
# CPU-only by construction (`--tp` selects the CPU mesh via
# --xla_force_host_platform_device_count=8; pallas_call has no GSPMD
# rule so the SPMD step pins the jnp gather path).
# Never banks: BENCH_serving_tp.json is written only by full
# (non-smoke) runs on a quiet VM.
set -o pipefail
cd "$(dirname "$0")/.."
timeout -k 10 300 python bench_serving.py --smoke --tp
