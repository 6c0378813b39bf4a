"""Fleet hybrid-parallel training walkthrough — the user-level story of
the distributed stack (reference workflow: paddle.distributed.fleet
hybrid_configs + distributed_model/distributed_optimizer).

Composes THREE parallelism axes on one mesh and trains a LLaMA proxy a
few steps, printing the loss from every configuration and checking they
match the single-device oracle:

  1. dp2 x mp2 x ZeRO-3(2)  — data parallel x tensor parallel x
     parameter-sharded optimizer (the 4D-hybrid minus pipeline; the
     pipeline axis is examples/long_context_train.py's sibling,
     fleet.PipelineParallel — see tests/test_pipeline.py)
  2. dp4 x sharding2        — ZeRO-1 over a wider data axis
  3. single device          — the oracle

Run on any box (8 virtual CPU devices):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/fleet_hybrid_train.py --cpu
On a TPU pod slice, drop --cpu and launch one process per host via
`python -m paddle_tpu.distributed.launch ...` (the PADDLE_* env
protocol); the SAME code runs multi-controller.
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

parser = argparse.ArgumentParser()
parser.add_argument("--cpu", action="store_true",
                    help="force an 8-device virtual CPU mesh")
parser.add_argument("--steps", type=int, default=5)
parser.add_argument("--quick", action="store_true",
                    help="one hybrid config only (CI smoke)")
args = parser.parse_args()

if args.cpu:
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)

import numpy as np

import jax

import paddle_tpu as P
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet import DistributedStrategy
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                               LlamaPretrainingCriterion)

N_DEV = len(jax.devices())
if N_DEV < 8:
    raise SystemExit(
        f"need 8 devices (got {N_DEV}); run with --cpu and "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8")


def make_batch(cfg, batch, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, 32)).astype(np.int32)
    return P.to_tensor(ids)


def train(strategy, tensor_parallel, steps, tag):
    """fleet.init -> distributed_model/optimizer -> train_batch loop."""
    P.seed(0)
    if strategy is not None:
        fleet.init(is_collective=True, strategy=strategy)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=64,
                      tensor_parallel=tensor_parallel)
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion(cfg)
    opt = P.optimizer.AdamW(1e-3, parameters=model.parameters())
    losses = []
    if strategy is None:
        for s in range(steps):
            ids = make_batch(cfg, 8, seed=s)
            logits = model(ids)
            loss = crit(logits, ids)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(np.asarray(loss.numpy())))
    else:
        opt = fleet.distributed_optimizer(opt)
        dmodel = fleet.distributed_model(model)
        for s in range(steps):
            ids = make_batch(cfg, 8, seed=s)
            loss = dmodel.train_batch([ids], [ids], opt, crit)
            losses.append(float(np.asarray(loss.numpy())))
    print(f"{tag:>18}: " + " ".join(f"{v:.4f}" for v in losses))
    return losses


def main():
    # oracle
    ref = train(None, False, args.steps, "single-device")

    # dp2 x mp2 x ZeRO-3(2)
    s1 = DistributedStrategy()
    s1.sharding = True
    s1.sharding_configs = {"stage": 3, "sharding_degree": 2}
    s1.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                         "sharding_degree": 2}
    l1 = train(s1, True, args.steps, "dp2 x mp2 x zero3")

    legs = [("dp2xmp2xzero3", l1)]
    if not args.quick:
        # dp4 x ZeRO-1(2)
        s2 = DistributedStrategy()
        s2.hybrid_configs = {"dp_degree": 4, "sharding_degree": 2}
        legs.append(("dp4xzero1",
                     train(s2, False, args.steps, "dp4 x zero1(2)")))

    for tag, got in legs:
        err = max(abs(a - b) for a, b in zip(ref, got))
        status = "MATCH" if err < 2e-2 else f"DIVERGED (max {err:.3f})"
        print(f"{tag}: loss parity vs single device -> {status}")
        if err >= 2e-2:
            raise SystemExit(1)
    print("hybrid-parallel training parity OK on", N_DEV, "devices")


if __name__ == "__main__":
    main()
