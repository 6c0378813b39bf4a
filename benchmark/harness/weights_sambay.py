"""Weights from ``--seed`` for the SambaY family (Mamba-1 mixers, window
and full differential attention, a cross-decoder over one layer's keys
and values, Gated Memory Units; arXiv:2507.06607), in the type they are
served in. As ``weights.py``: the benchmark makes them and hands the same
arrays to the program and to the plain reference.

Leaf ``i`` is a function of (seed, i) alone, one jitted call a leaf (one
program a shape). By the leaf's name: a projection matrix is N(0,
``initializer_range``); a LayerNorm / RMSNorm weight 1 and a LayerNorm
bias 0; ``A_log`` = log(1..d_state) in every channel; ``D`` = 1; ``dt_b``
the inverse softplus of a step drawn log-uniformly in [1e-3, 1e-1]; the
four lambda vectors N(0, 0.1); the depthwise convolution's taps N(0,
1 / sqrt(d_conv)) (Mamba's own scale: at 0.02 the scan's input would be
nought); every other bias N(0, ``initializer_range``), so that leaving
one out is seen. The configuration's file lists each under ``assumed``.
"""
from __future__ import annotations

import functools
import math

from .weights import get, root_key  # noqa: F401  (one way to name a leaf)

MAMBA = {"d_state": 16, "d_conv": 4, "expand": 2}


def dims(cfg) -> dict:
    """The sizes the published keys leave to the modeling file's
    defaults (the configuration's ``assumed``), with the published ones
    they follow from."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    n_layers = cfg["num_hidden_layers"]
    return {"h": h, "ffn": cfg["intermediate_size"], "nh": nh,
            "nkv": cfg["num_key_value_heads"], "d": h // nh,
            "di": MAMBA["expand"] * h, "n": MAMBA["d_state"],
            "k": MAMBA["d_conv"], "r": math.ceil(h / 16),
            "memory_layer": int(cfg.get("program", {}).get(
                "memory_layer", n_layers // 2))}


def kind(cfg, i: int) -> str:
    """``mamba`` | ``window`` | ``full`` | ``gmu`` | ``cross``."""
    m = dims(cfg)["memory_layer"]
    if i <= m:
        return "window" if i % 2 else "mamba"
    if i == m + 1:
        return "full"
    return "cross" if i % 2 else "gmu"


def lam0(i: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def leaf_shapes(cfg) -> dict:
    """The pytree of shapes; projection weights are [in, out]."""
    z = dims(cfg)
    h, f, di, n, r, d = z["h"], z["ffn"], z["di"], z["n"], z["r"], z["d"]
    kw = z["nkv"] * d
    block = {"ln1_w": (h,), "ln1_b": (h,), "ln2_w": (h,), "ln2_b": (h,),
             "fc1": (h, 2 * f), "fc2": (f, h)}
    lam = {"lq1": (d,), "lk1": (d,), "lq2": (d,), "lk2": (d,),
           "subln": (2 * d,)}
    mixer = {
        "mamba": {"in": (h, 2 * di), "conv_w": (z["k"], di),
                  "conv_b": (di,), "x": (di, r + 2 * n), "dt_w": (r, di),
                  "dt_b": (di,), "A_log": (n, di), "D": (di,),
                  "out": (di, h)},
        "self": {"qkv_w": (h, h + 2 * kw), "qkv_b": (h + 2 * kw,),
                 "o_w": (h, h), "o_b": (h,), **lam},
        "cross": {"q_w": (h, h), "q_b": (h,), "o_w": (h, h), "o_b": (h,),
                  **lam},
        "gmu": {"in": (h, di), "out": (di, h)}}
    of = {"mamba": "mamba", "window": "self", "full": "self",
          "cross": "cross", "gmu": "gmu"}
    layers = [{**block, **mixer[of[kind(cfg, i)]]}
              for i in range(cfg["num_hidden_layers"])]
    return {"embed": (cfg["vocab_size"], h), "layers": layers,
            "norm_w": (h,), "norm_b": (h,)}


ONES = {"ln1_w", "ln2_w", "norm_w", "subln", "D"}
ZEROS = {"ln1_b", "ln2_b", "norm_b"}
LAMBDAS = {"lq1", "lk1", "lq2", "lk2"}


@functools.lru_cache(maxsize=None)
def _normal(shape, dtype_name):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(key, std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(
            jnp.dtype(dtype_name))
    return f


def _leaf(name, shape, key, std, dtype):
    import jax
    import jax.numpy as jnp
    if name in ONES:
        return jnp.ones(shape, dtype)
    if name in ZEROS:
        return jnp.zeros(shape, dtype)
    if name == "A_log":
        return jnp.broadcast_to(jnp.log(jnp.arange(
            1, shape[0] + 1, dtype=jnp.float32))[:, None],
            shape).astype(dtype)
    if name == "dt_b":
        step = jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        return jnp.log(jnp.expm1(step)).astype(dtype)
    if name in LAMBDAS:
        std = 0.1
    elif name == "conv_w":
        std = 1.0 / math.sqrt(shape[0])
    return _normal(shape, jnp.dtype(dtype).name)(key, jnp.float32(std))


def make(seed: int, cfg):
    """The whole pytree, a leaf a call."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["torch_dtype"])
    std = float(cfg.get("initializer_range", 0.02))
    paths, tree = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    key = root_key(seed)
    out = [_leaf(path[-1].key, shape, jax.random.fold_in(key, i), std,
                 dtype) for i, (path, shape) in enumerate(paths)]
    return jax.tree.unflatten(tree, out)


def program_names(cfg) -> dict:
    """Benchmark leaf path -> the parameter name ``SambaYForCausalLM``
    gives it (the head is the embedding: no leaf of its own)."""
    names = {("embed",): "embed_tokens.weight",
             ("norm_w",): "norm.weight", ("norm_b",): "norm.bias"}
    block = {"ln1_w": "input_layernorm.weight",
             "ln1_b": "input_layernorm.bias",
             "ln2_w": "post_attention_layernorm.weight",
             "ln2_b": "post_attention_layernorm.bias",
             "fc1": "mlp.fc1.weight", "fc2": "mlp.fc2.weight"}
    lam = {"lq1": "mixer.lambda_q1", "lk1": "mixer.lambda_k1",
           "lq2": "mixer.lambda_q2", "lk2": "mixer.lambda_k2",
           "subln": "mixer.subln_weight",
           "o_w": "mixer.out_proj.weight", "o_b": "mixer.out_proj.bias"}
    mixer = {
        "mamba": {"in": "mixer.in_proj.weight",
                  "conv_w": "mixer.conv_weight",
                  "conv_b": "mixer.conv_bias", "x": "mixer.x_proj.weight",
                  "dt_w": "mixer.dt_proj.weight",
                  "dt_b": "mixer.dt_proj.bias", "A_log": "mixer.A_log",
                  "D": "mixer.D", "out": "mixer.out_proj.weight"},
        "self": {"qkv_w": "mixer.Wqkv.weight", "qkv_b": "mixer.Wqkv.bias",
                 **lam},
        "cross": {"q_w": "mixer.Wq.weight", "q_b": "mixer.Wq.bias", **lam},
        "gmu": {"in": "mixer.in_proj.weight",
                "out": "mixer.out_proj.weight"}}
    of = {"mamba": "mamba", "window": "self", "full": "self",
          "cross": "cross", "gmu": "gmu"}
    for i in range(cfg["num_hidden_layers"]):
        for leaf, name in {**block, **mixer[of[kind(cfg, i)]]}.items():
            names[("layers", i, leaf)] = f"layers.{i}.{name}"
    return names
