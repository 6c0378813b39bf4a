"""``aot.serve_step`` for the SambaY family: the ragged step of
``SambaYForCausalLM`` compiled at the configuration's engine geometry for
a described (not attached) TPU v5e, to read the compiler's
``memory_analysis()`` before any chip minute is spent.

    python3 -m benchmark.harness.aot_sambay [configuration file] [hlo out]
"""
from __future__ import annotations

import functools
import json
import sys

from . import aot


def _lazy_model(cfg):
    import paddle_tpu as P
    from paddle_tpu.models import SambaYConfig, SambaYForCausalLM
    with P.LazyGuard():
        model = SambaYForCausalLM(SambaYConfig.from_published(
            cfg, dtype=cfg["torch_dtype"], **cfg.get("program", {})))
    for p in model.parameters():      # stay shapes: no initializer runs
        del p._lazy_init
    for lyr in model.sublayers(include_self=True):
        lyr.__dict__["_has_lazy_params"] = False
    return model


def serve_step(cfg: dict, topo, mixed: bool = True, hlo_out=None) -> dict:
    """The ragged step (the class that carries a prefill chunk, or the
    decode-only one) over the full pool, the window pools and the lane
    states."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving import engine as eng_mod

    sds = aot._on_one_chip(topo)
    dt = jnp.dtype(cfg["torch_dtype"])
    model = _lazy_model(cfg)
    model.eval()
    kw = {k: v for k, v in cfg["engine"].items() if k != "max_queued"}
    engine = ServingEngine(model, ragged=True, eos_token_id=None, **kw)
    t = engine._ragged_tok_mixed if mixed else engine._ragged_tok_small
    lanes = engine._ragged_lanes
    pages = engine.max_pages_per_seq
    cache = engine.cache
    shapes = lambda tree: jax.tree.map(                       # noqa: E731
        lambda a: sds(a.shape, a.dtype), tree)
    warrs = [sds(p.shape, dt) for p in model._gen_state_tensors()]
    k_ops, v_ops = shapes(cache.program_operands())
    i32 = lambda *s: sds(s, jnp.int32)                        # noqa: E731
    extra = dict(shapes(cache.extra_operands()), lane_slot=i32(lanes),
                 wpt=i32(lanes, cache.window_pages_per_lane),
                 wbase=i32(lanes), wslots=i32(t))
    samp = (sds((t,), jnp.bool_), sds((t,), jnp.float32), i32(t),
            sds((t,), jnp.float32), i32(t), i32(t))
    fn = jax.jit(functools.partial(eng_mod._ragged_step_pure, model,
                                   engine._core, engine.window, None))
    compiled = fn.lower(warrs, i32(1, t), i32(1, t), i32(lanes, pages),
                        i32(lanes), i32(lanes), i32(lanes), i32(1, t), samp,
                        k_ops, v_ops, extra).compile()
    out = aot._memory(compiled)
    out["tokens"] = t
    if hlo_out:
        with open(hlo_out, "w") as f:
            f.write(compiled.as_text())
    return out


def main(argv):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from . import spec
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    topo = aot.describe_v5e()
    path = argv[0] if argv else str(
        spec.ROOT / "benchmark/configs/phi4flash/"
        "phi-4-mini-flash-reasoning.serve.json")
    with open(path) as f:
        cfg = json.load(f)
    for mixed in (False, True):
        hlo = f"{argv[1]}.{'mixed' if mixed else 'decode'}.txt" \
            if len(argv) > 1 else None
        print("ragged step", json.dumps(serve_step(cfg, topo, mixed, hlo)),
              flush=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
