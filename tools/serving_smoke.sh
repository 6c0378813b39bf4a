#!/bin/bash
# Serving-engine smoke: replays a tiny Poisson trace through the
# continuous-batching engine on the CPU mesh (--smoke selects it) and
# banks the JSON artifact.
set -u -o pipefail
cd "$(dirname "$0")/.."
mkdir -p .bench_r4
python bench_serving.py --smoke | tee .bench_r4/serving_smoke.json
