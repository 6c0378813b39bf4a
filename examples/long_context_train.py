"""Context-parallel (sep axis) long-context training walkthrough.

Runs a LLaMA proxy with `context_parallel="ring"` sequence-sharded over
a sep mesh axis — ring flash attention + globally-shifted token CE (the
capability the sep axis exists for; see fleet/long_context.py and
SPMDTrainer._build_sep_loss).

python examples/long_context_train.py [--cpu] [--mode ring|ulysses]
On a CPU box, run with: XLA_FLAGS=--xla_force_host_platform_device_count=8
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if "--cpu" in sys.argv:  # 8-device virtual CPU mesh
    sys.argv.remove("--cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

import numpy as np

import paddle_tpu as P
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                               LlamaPretrainingCriterion)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="ring",
                    choices=["ring", "ulysses"])
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()

    import jax
    n = jax.device_count()
    sep = 4 if n % 4 == 0 else 2
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": n // sep, "sep_degree": sep}
    fleet.init(is_collective=True, strategy=strategy)

    P.seed(0)
    cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                      intermediate_size=256, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=args.seq,
                      context_parallel=args.mode)
    model = LlamaForCausalLM(cfg)
    opt = P.optimizer.AdamW(3e-4, parameters=model.parameters())
    opt = fleet.distributed_optimizer(opt)
    dmodel = fleet.distributed_model(model)
    crit = LlamaPretrainingCriterion(cfg)

    rng = np.random.default_rng(0)
    bsz = max(n // sep, 1) * 2
    for step in range(args.steps):
        ids = P.to_tensor(rng.integers(
            0, cfg.vocab_size, (bsz, args.seq)).astype(np.int32))
        loss = dmodel.train_batch([ids], [ids], opt, crit)
        print(f"step {step}  seq {args.seq} over sep={sep}  "
              f"loss {float(loss.numpy()):.4f}")


if __name__ == "__main__":
    main()
