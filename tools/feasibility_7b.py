"""LLaMA-2-7B feasibility artifact (round 3, VERDICT r2 item 5).

AOT-lowers (NO execution) the real fleet SPMD train step for the actual
7B config under ZeRO-3 (+TP) on a virtual CPU mesh, proving the program
compiles, and derives the per-device memory table from the lowered
shardings. Prints one JSON record; FEASIBILITY.md is authored from the
records of the two standard layouts below.

Usage:
    python tools/feasibility_7b.py [--devices 8] [--mp 1] [--seq 4096]

Run once with --devices 8 (v5e-8 layout: ZeRO-3 over 8 chips) and once
with --devices 32 --mp 4 (v5p-32 layout: TP4 x ZeRO-3(8)ordinates).
"""
import argparse
import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--mp", type=int, default=1)
    ap.add_argument("--sep", type=int, default=1,
                    help="context-parallel degree (Ulysses on the flash "
                         "core) — the 7B LONG-CONTEXT layout")
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--hidden", type=int, default=4096)
    ap.add_argument("--layers", type=int, default=32)
    ap.add_argument("--dtype", default="bfloat16",
                    choices=("bfloat16", "float32"))
    ap.add_argument("--no-recompute", action="store_true")
    ap.add_argument("--json-out", default=None)
    args = ap.parse_args()
    if args.mp * args.sep > args.devices or \
            args.devices % (args.mp * args.sep):
        ap.error(f"--devices {args.devices} must be a multiple of "
                 f"mp*sep = {args.mp * args.sep}")
    if args.seq % max(args.sep, 1):
        ap.error(f"--seq {args.seq} must be divisible by --sep "
                 f"{args.sep}")

    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.devices} "
        + os.environ.get("XLA_FLAGS", ""))
    import sys as _sys
    _sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu as P
    from paddle_tpu.distributed import fleet
    from paddle_tpu.distributed.fleet import DistributedStrategy
    from paddle_tpu.distributed.fleet.spmd import SPMDTrainer, state_spec
    from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                   LlamaPretrainingCriterion,
                                   flops_per_token)

    sharding_degree = args.devices // (args.mp * args.sep)
    # global batch must divide the data axes (dp × sharding)
    if args.batch % sharding_degree != 0:
        args.batch = sharding_degree
    strategy = DistributedStrategy()
    hc = {"sharding_degree": sharding_degree}
    if args.mp > 1:
        hc["mp_degree"] = args.mp
    if args.sep > 1:
        hc["sep_degree"] = args.sep
    strategy.hybrid_configs = hc
    strategy.sharding = True
    strategy.sharding_configs = {"stage": 3}
    fleet.init(is_collective=True, strategy=strategy)
    from paddle_tpu.distributed.fleet.fleet import _state
    mesh = _state.hcg.mesh

    # default: the REAL LLaMA-2-7B architecture (--hidden/--layers
    # shrink it for compile-bisect probes); bf16 params, remat, fused CE
    hid = args.hidden
    cfg = LlamaConfig(vocab_size=32000, hidden_size=hid,
                      intermediate_size=(11008 if hid == 4096 else
                                         hid * 11 // 4 // 16 * 16),
                      num_hidden_layers=args.layers,
                      num_attention_heads=max(1, hid // 128),
                      max_position_embeddings=args.seq,
                      recompute=not args.no_recompute,
                      # the sep trainer computes its own sharded token
                      # CE (globally shifted labels) — fused CE is the
                      # single-controller head-side variant
                      fuse_linear_cross_entropy=args.sep == 1,
                      tensor_parallel=args.mp > 1,
                      context_parallel="ulysses" if args.sep > 1
                      else None, dtype=args.dtype)
    P.seed(0)
    print(f"building 7B model on host ({args.devices} virtual devices, "
          f"mp={args.mp}, sharding={sharding_degree})...", flush=True)
    model = LlamaForCausalLM(cfg)
    if args.dtype == "bfloat16":
        model.to(dtype="bfloat16")
    crit = LlamaPretrainingCriterion(cfg)
    if cfg.fuse_linear_cross_entropy:
        crit.bind(model)
    opt = P.optimizer.AdamW(1e-4, parameters=model.parameters(),
                            multi_precision=True)
    trainer = SPMDTrainer(model, opt, crit, mesh, strategy)

    n_params = sum(int(np.prod(p.shape))
                   for _, p in trainer._train_named)

    def shard_factor(spec, shape):
        axd = dict(zip(mesh.axis_names, mesh.devices.shape))
        f = 1
        for d, ax in enumerate(spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            for a in axes:
                f *= axd.get(a, 1)
        return f

    # analytic per-device memory from the REAL sharding specs
    bytes_param = bytes_master = bytes_m = bytes_v = 0
    for (_, p), spec in zip(trainer._train_named, trainer._pspecs):
        shp = tuple(p.shape)
        n = int(np.prod(shp))
        pf = shard_factor(spec, shp)
        bytes_param += 2 * n // pf           # bf16 at rest
        sspec = state_spec(spec, shp, 3, sharding_degree)
        sf = shard_factor(sspec, shp)
        bytes_master += 4 * n // sf
        bytes_m += 4 * n // sf
        bytes_v += 4 * n // sf

    # AOT-lower the REAL train step with abstract (ShapeDtypeStruct) args
    print("AOT-lowering the ZeRO-3 train step...", flush=True)
    states_abs = []
    for (_, p) in trainer._train_named:
        shp = tuple(p.shape)
        states_abs.append({
            "moment1": jax.ShapeDtypeStruct(shp, jnp.float32),
            "moment2": jax.ShapeDtypeStruct(shp, jnp.float32),
            "master": jax.ShapeDtypeStruct(shp, jnp.float32),
        })
    batch_sds = jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)
    fn = trainer._build(1, 1, (states_abs, [2, 2]), do_update=True)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    pdt = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    lowered = fn.lower(
        key,
        [jax.ShapeDtypeStruct(tuple(p.shape), pdt)
         for _, p in trainer._train_named],
        [jax.ShapeDtypeStruct(tuple(p.shape), pdt)
         for _, p in trainer._frozen_named],
        [jax.ShapeDtypeStruct(tuple(b.shape), b._data.dtype)
         for _, b in trainer._buf_named],
        states_abs,
        [],
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32),
        batch_sds, batch_sds)
    print("lowering OK; compiling (SPMD-partitioned, no execution)...",
          flush=True)
    compiled = lowered.compile()
    try:
        ma = compiled.memory_analysis()
        mem = {"argument_bytes": int(ma.argument_size_in_bytes),
               "output_bytes": int(ma.output_size_in_bytes),
               "temp_bytes": int(ma.temp_size_in_bytes),
               "generated_code_bytes": int(
                   ma.generated_code_size_in_bytes)}
    except Exception as e:
        mem = {"unavailable": str(e)[:200]}

    gib = 1024 ** 3
    rec = {
        "devices": args.devices,
        "mp": args.mp,
        "sep": args.sep,
        "sharding_degree": sharding_degree,
        "seq": args.seq,
        "batch_per_step": args.batch,
        "n_params": n_params,
        "per_device_gib": {
            "params_bf16": round(bytes_param / gib, 2),
            "master_f32": round(bytes_master / gib, 2),
            "adam_m_f32": round(bytes_m / gib, 2),
            "adam_v_f32": round(bytes_v / gib, 2),
            "total_states": round((bytes_param + bytes_master + bytes_m +
                                   bytes_v) / gib, 2),
        },
        "flops_per_token": flops_per_token(cfg, args.seq),
        "compiled": True,
        "xla_memory_analysis": mem,
    }
    print(json.dumps(rec))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
