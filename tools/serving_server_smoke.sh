#!/bin/bash
# HTTP front-end smoke for the chip-capture list (append AFTER the safe
# tier, next to serving_smoke.sh): replays a tiny Poisson trace over
# REAL sockets — ServingServer on an ephemeral localhost port, SSE
# streaming, thread-per-request load generator — and banks the JSON
# artifact.
#
# --smoke selects the CPU mesh, the paged-attention Pallas stub stays
# interpret-gated (PADDLE_TPU_PAGED_KERNEL unset), and every socket has
# a timeout, so this script is bounded and never touches the chip.
#
# Run detached like every capture step:
#   setsid bash tools/serving_server_smoke.sh \
#     > .bench_r4/serving_server_smoke.log 2>&1 &
set -u -o pipefail
cd "$(dirname "$0")/.."
mkdir -p .bench_r4
python bench_serving.py --server --smoke \
  | tee .bench_r4/serving_server_smoke.json
