"""The cells' step programs compiled at their real shapes for a described
(not attached) TPU v5e, to read the compiler's ``memory_analysis()`` before
any chip minute is spent. Nothing runs: no time comes from here.

    python3 -m benchmark.harness.aot      # every configuration of its family

``main`` describes the configurations whose ``model_type`` is one of
``FAMILY`` (the Llama shape that ``_lazy_model`` builds) and names the
others, which ``aot_latent_moe.py`` or a later family's file describes.
The model is described under ``LazyGuard`` and every operand is a
``ShapeDtypeStruct`` on the described device. ``tests/benchmark`` calls
the same functions from a module fixture (marked slow: a whole step
program compiles in tens of seconds).
"""
from __future__ import annotations

import functools
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

FAMILY = ("llama", "mistral")       # the ``model_type``s of the Llama shape


def describe_v5e():
    from jax.experimental import topologies
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def _lazy_model(cfg):
    import paddle_tpu as P
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    from ..drivers.serve import llama_kwargs
    with P.LazyGuard():
        model = LlamaForCausalLM(LlamaConfig(**llama_kwargs(cfg)))
    for p in model.parameters():      # stay shapes: no initializer runs
        del p._lazy_init
    for lyr in model.sublayers(include_self=True):
        lyr.__dict__["_has_lazy_params"] = False
    return model


def _on_one_chip(topo):
    """shape, dtype -> a ``ShapeDtypeStruct`` on the first described chip."""
    import jax
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(
        tuple(shape), dtype, sharding=one)


def _memory(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}
    out["live_bytes"] = (out["argument_size_in_bytes"]
                         + out["output_size_in_bytes"]
                         - out["alias_size_in_bytes"]
                         + out["temp_size_in_bytes"])
    out["kernels"] = compiled.as_text().count("tpu_custom_call")
    return out


def serve_step(cfg: dict, topo, mixed: bool = True) -> dict:
    """The ragged step (the class that carries a prefill chunk, or the
    decode-only one) at the configuration's engine geometry."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving import engine as eng_mod

    sds = _on_one_chip(topo)
    dt = jnp.dtype(cfg["torch_dtype"])
    model = _lazy_model(cfg)
    model.eval()
    kw = {k: v for k, v in cfg["engine"].items() if k != "max_queued"}
    engine = ServingEngine(model, ragged=True, eos_token_id=None, **kw)
    t = engine._ragged_tok_mixed if mixed else engine._ragged_tok_small
    lanes = engine._ragged_lanes
    pages = -(-engine.max_seq_len // engine.cache.page_size)
    warrs = [sds(p.shape, dt) for p in model._gen_state_tensors()]
    k_ops, v_ops = engine.cache.program_operands()
    pools = lambda ops: [sds(a.shape, a.dtype) for a in ops]  # noqa: E731
    i32 = lambda *s: sds(s, jnp.int32)                        # noqa: E731
    samp = (sds((t,), jnp.bool_), sds((t,), jnp.float32), i32(t),
            sds((t,), jnp.float32), i32(t), i32(t))
    fn = jax.jit(functools.partial(eng_mod._ragged_step_pure, model,
                                   engine._core, engine.window, None))
    compiled = fn.lower(warrs, i32(1, t), i32(1, t), i32(lanes, pages),
                        i32(lanes), i32(lanes), i32(lanes), i32(1, t), samp,
                        pools(k_ops), pools(v_ops)).compile()
    out = _memory(compiled)
    out["tokens"] = t
    return out


def train_loop(cfg: dict, mix: dict, topo) -> dict:
    """``Model.train_batch_loop``'s program: ``steps_per_call`` optimizer
    steps in one ``lax.scan``, state donated."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as P
    from paddle_tpu.hapi.model import _JitStepper
    from paddle_tpu.models import LlamaPretrainingCriterion
    from paddle_tpu.ops.pallas import flash_attention as fa

    sds = _on_one_chip(topo)
    dt = jnp.dtype(cfg["torch_dtype"])
    model = _lazy_model(cfg)
    model.train()
    crit = LlamaPretrainingCriterion(model.cfg).bind(model)
    o = cfg["optimizer"]
    opt = P.optimizer.AdamW(o["learning_rate"], beta1=o["beta1"],
                            beta2=o["beta2"], epsilon=o["epsilon"],
                            weight_decay=o["weight_decay"],
                            parameters=model.parameters(),
                            multi_precision=True)
    stepper = _JitStepper(model, crit, opt)
    n, b, s = (int(mix["steps_per_call"]), int(mix["batch"]),
               int(mix["sequence"]))
    was = fa._on_tpu
    fa._on_tpu = lambda: True        # steer the dispatch: kernels, not XLA
    try:
        fn, (train_p, frozen_p, bufs) = stepper._build_loop(1, 1)
        params = [sds(p.shape, dt) for _, p in train_p]
        f32 = lambda p: sds(p.shape, jnp.float32)             # noqa: E731
        states = [{"moment1": f32(p), "moment2": f32(p), "master": f32(p)}
                  for _, p in train_p]
        keys = sds((n, 2), jnp.uint32)
        xs = sds((n, b, s), jnp.int32)
        compiled = fn.lower(keys, params, [], [], states,
                            sds((), jnp.float32), sds((), jnp.int32),
                            xs, xs).compile()
    finally:
        fa._on_tpu = was
    out = _memory(compiled)
    out["steps_per_call"] = n
    return out


def main():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from . import spec
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = describe_v5e()
    root = spec.ROOT / "benchmark"
    for path in sorted((root / "configs").glob("*.json")):
        cfg = json.loads(path.read_text())
        if cfg.get("model_type") not in FAMILY:
            print(path.stem, f"not described: model_type "
                  f"{cfg.get('model_type')!r} is not of {FAMILY}; left to "
                  f"aot_latent_moe.py or its own family's file", flush=True)
        elif "engine" in cfg:
            for mixed in (False, True):
                print(path.stem, "ragged step", json.dumps(
                    serve_step(cfg, topo, mixed)), flush=True)
        elif "optimizer" in cfg and "strategy" not in cfg:
            mix = json.loads((root / "traffic" /
                              f"{cfg['aot_traffic']}.json").read_text())
            print(path.stem, "train loop", json.dumps(
                train_loop(cfg, mix, topo)), flush=True)


if __name__ == "__main__":
    sys.exit(main())
