#!/bin/bash
# Serving-trace observability smoke (round 16): replays the tracing
# overhead guard in --smoke mode and banks the JSON artifact.  CPU-mesh
# BY CONSTRUCTION — bench_serving's --smoke path selects the CPU
# platform before it touches a device.
#
# The smoke replay measures the on/off marginal ratio but does NOT
# assert the 3% contract (marginal ratios under suite/CPU load are
# noise — CLAUDE.md round-4); the banked quiet-VM BENCH_serving_trace
# artifact is the real gate.  The chrome-export roundtrip through
# paddle_tpu.profiler.load_profiler_result IS asserted here.
#
# Run detached like every capture step:
#   setsid bash tools/serving_trace_smoke.sh > .bench_r4/serving_trace_smoke.log 2>&1 &
set -u -o pipefail
cd "$(dirname "$0")/.."
mkdir -p .bench_r4
python bench_serving.py --smoke --trace | tee .bench_r4/serving_trace_smoke.json
