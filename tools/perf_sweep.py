"""Perf sweep over flash-attention block sizes + bench shapes.

Each configuration = one `bench.py` subprocess with env overrides, run
one at a time; this parent never touches jax, so each child gets the
chip. A child that finds no TPU exits non-zero and the sweep stops.
Rehearse a new block config with an AOT compile first
(tests/test_aot_tpu_compile.py shows how): what the chip's compiler
refuses there costs no chip time.

Usage: python tools/perf_sweep.py [--quick]   # appends to .bench_r3/sweep.jsonl
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(HERE, ".bench_r3", "sweep.jsonl")

CONFIGS = [
    {"name": "baseline_b16"},
    {"name": "fa_bk256", "env": {"PADDLE_TPU_FA_BLOCK_K": "256"}},
    {"name": "b8_s2048", "env": {"PADDLE_TPU_BENCH_BATCH": "8",
                                 "PADDLE_TPU_BENCH_SEQ": "2048"}},
    {"name": "b20", "env": {"PADDLE_TPU_BENCH_BATCH": "20"}},
]


def run_one(name, env, timeout_s=1200):
    p = subprocess.run([sys.executable, "bench.py"],
                       env=dict(os.environ, **(env or {})), cwd=HERE,
                       capture_output=True, text=True, timeout=timeout_s)
    lines = [l for l in p.stdout.splitlines() if l.startswith("{")]
    if p.returncode != 0 or not lines:
        return {"name": name,
                "error": f"rc={p.returncode}: {p.stderr[-300:]}"}
    rec = json.loads(lines[-1])
    rec["name"] = name
    rec["env"] = env or {}
    return rec


def main():
    quick = "--quick" in sys.argv
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    for cfg in (CONFIGS[:2] if quick else CONFIGS):
        rec = run_one(cfg["name"], cfg.get("env"))
        rec["ts"] = time.strftime("%H:%M:%S")
        with open(OUT, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps(rec), flush=True)
        if rec.get("error"):
            sys.exit(f"sweep stopped at {cfg['name']}: {rec['error']}")


if __name__ == "__main__":
    main()
