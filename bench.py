"""Benchmark: LLaMA causal-LM training step on the TPU.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Metric: model-FLOPs utilization (MFU) of a compiled train step
(fwd+bwd+fused AdamW in one XLA program) — the single-chip proxy for the
north-star (BASELINE.json: ≥50% MFU target ⇒ vs_baseline = MFU / 0.50).
Needs a TPU whose device_kind is in paddle_tpu.utils.chip_specs; without
one it exits non-zero and prints no metric.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np


def require_tpu():
    """The one gate of the bench scripts, before their first compile:
    exits non-zero unless jax.devices()[0] is a TPU — there is no CPU
    fallback, a CPU run gives no device metric — then turns the
    persistent compile cache on."""
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        sys.exit(f"{os.path.basename(sys.argv[0])}: needs a TPU, jax "
                 f"reports platform {d.platform!r} ({d.device_kind}); "
                 "no metric printed")
    from paddle_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()


def jax_device_record():
    """{"platform", "kind", "count"} as jax reports them — every bench
    result names the device it ran on."""
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def detect_peak():
    """(bf16 peak FLOP/s, device_kind) of device 0; an unknown
    device_kind raises."""
    import jax

    from paddle_tpu.utils.chip_specs import chip_spec
    kind = jax.devices()[0].device_kind
    return chip_spec(kind).bf16_flops, kind


def main():
    require_tpu()

    import paddle_tpu as P
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion,
                                   flops_per_token)

    peak, kind = detect_peak()

    # ~0.5B-param proxy chosen to PUSH the chip: h=2048 makes every
    # matmul MXU-saturating, bf16 weights, Pallas flash attention
    # engaged, fused AdamW; batch 16 fits a 16G v5e (24 OOMs) and the
    # OOM-halving loop below recovers on smaller chips. Labeled a proxy
    # for the 7B north-star (BASELINE.md).
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5504, num_hidden_layers=8,
                      num_attention_heads=16,
                      max_position_embeddings=2048, recompute=False,
                      fuse_linear_cross_entropy=True,
                      dtype="bfloat16")
    # fused linear+CE: the [B·S, 32000] f32 logits are never
    # materialized (chunked head matmul + CE under checkpoint).
    batch, seq, iters = 16, 1024, 20
    # sweep overrides (tools/perf_sweep.py)
    batch = int(os.environ.get("PADDLE_TPU_BENCH_BATCH", batch))
    seq = int(os.environ.get("PADDLE_TPU_BENCH_SEQ", seq))
    if seq != 1024:
        cfg.max_position_embeddings = max(seq, 2048)

    from paddle_tpu.ops.pallas import flash_attention as _fa
    while True:
        # Build everything inside the retry loop: the train step donates
        # params/buffers/opt-states, so a failed execution can leave them
        # deleted — a fresh model/optimizer is required for the retry.
        # Reset dispatch counters per attempt so the banked stats
        # describe THIS measurement, not failed/earlier traces.
        _fa.reset_dispatch_stats()
        P.seed(0)
        model = LlamaForCausalLM(cfg)
        model.to(dtype="bfloat16")
        crit = LlamaPretrainingCriterion(cfg)
        crit.bind(model)  # chunked head+CE reads the lm_head weight
        opt = P.optimizer.AdamW(1e-4, parameters=model.parameters(),
                                multi_precision=True)
        m = P.Model(model)
        m.prepare(opt, crit)
        ids = np.random.default_rng(0).integers(
            0, cfg.vocab_size, (batch, seq)).astype(np.int32)
        try:
            # warmup: compile + run the device-side loop program once,
            # and wait for the EXECUTION, not just the dispatch
            xloop = P.to_tensor(
                np.broadcast_to(ids, (iters,) + ids.shape).copy())
            m.train_batch_loop([xloop], [xloop])._data.block_until_ready()
            break
        except Exception as e:
            # HBM headroom varies with the chip; halve the batch (it is
            # recorded in the output) rather than fail outright.
            if "RESOURCE_EXHAUSTED" not in str(e) or batch <= 1:
                raise
            batch //= 2

    # Timed region: the device-side training loop — N steps compiled
    # into ONE XLA program (hapi Model.train_batch_loop), ended by a
    # dependent host fetch of the last loss. min of 2 samples.
    def _timed(x):
        t0 = time.perf_counter()
        ls = m.train_batch_loop([x], [x])
        lv = float(np.asarray(ls._data[-1]))
        return time.perf_counter() - t0, lv

    t1, loss = _timed(xloop)
    t2, _ = _timed(xloop)
    step_s = min(t1, t2) / iters

    tok_per_s = batch * seq / step_s
    mfu = tok_per_s * flops_per_token(cfg, seq) / peak

    dev = jax_device_record()
    print(json.dumps({
        "metric": "llama_bench_mfu",
        "value": round(mfu, 4),
        "unit": "MFU (model FLOPs utilization, fwd+bwd+opt)",
        "vs_baseline": round(mfu / 0.50, 4),
        "tokens_per_sec": round(tok_per_s, 1),
        "batch": batch,
        "loss": float(loss),
        "device": dev,
        # kernel-engagement accounting IN the artifact: any fallback > 0
        # means the number is not a kernel number (flash_attention.py
        # dispatch discipline)
        "pallas_dispatch": _fa.dispatch_stats(),
    }))


if __name__ == "__main__":
    main()
