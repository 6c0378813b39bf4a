"""Weights from ``--seed`` for MiniCPM-SALA's language model (sparse and
lightning mixers, dense SwiGLU FFN), in the type they are served in. As
``weights.py``: the benchmark makes them and hands the same arrays to the
program and to the plain reference.

Leaf ``i`` is a function of (seed, i) alone, one jitted call a leaf. By
the leaf's name: the embedding is N(0, ``initializer_range``); every
other matrix N(0, ``initializer_range / sqrt(hidden_size /
dim_model_base)``), muP's width-scaled draw (MiniCPM's own
initialisation); a norm weight is 1, but for the sparse layers' q and k
norms, which are ``QK_GAIN``. The configuration's file lists each under
``assumed``.
"""
from __future__ import annotations

import functools
import math

from .weights import get, root_key  # noqa: F401  (one way to name a leaf)

SPARSE = "minicpm4"
# The sparse layers' q and k norm weights. At 1, normed random q and k
# score every key alike (softmax over 30k keys is flat), so no block
# choice moves a logit more than bfloat16 rounding does; at 2 the scores
# spread by 4, as a trained model's peaked attention, and attending the
# wrong blocks shows in the logits (PERF.md section 6)
QK_GAIN = 2.0


def kind(cfg, i: int) -> str:
    """``minicpm4`` | ``lightning-attn``: held layer ``i``'s mixer (the
    published layer ``first_layer + i``)."""
    return cfg["mixer_types"][cfg.get("first_layer", 0) + i]


def depth_published(cfg) -> int:
    """The published depth muP divides by: ``reduced``'s record of it
    where the depth is cut."""
    rec = cfg.get("reduced", {}).get("num_hidden_layers")
    return int(rec["published"] if rec else cfg["num_hidden_layers"])


def program_kw(cfg) -> dict:
    """``MiniCPMSalaConfig`` settings beside the published keys."""
    return {"dtype": cfg["torch_dtype"],
            "first_layer": cfg.get("first_layer", 0),
            "depth_published": depth_published(cfg),
            "sparse_config": cfg["sparse_config"],
            **cfg.get("program", {})}


def leaf_shapes(cfg) -> dict:
    """The pytree of shapes; linear weights are [in, out]."""
    h = cfg["hidden_size"]
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    lh, ld = cfg["lightning_nh"], cfg["lightning_head_dim"]
    f = cfg["intermediate_size"]
    sparse = {"q": (h, nh * d), "k": (h, nkv * d), "v": (h, nkv * d),
              "q_norm": (d,), "k_norm": (d,), "g": (h, nh * d),
              "o": (nh * d, h)}
    light = {"q": (h, lh * ld), "k": (h, lh * ld), "v": (h, lh * ld),
             "q_norm": (ld,), "k_norm": (ld,), "o_norm": (lh * ld,),
             "g": (h, lh * ld), "o": (lh * ld, h)}
    layers = [{"ln1": (h,), "ln2": (h,),
               **(sparse if kind(cfg, i) == SPARSE else light),
               "gate": (h, f), "up": (h, f), "down": (f, h)}
              for i in range(cfg["num_hidden_layers"])]
    v = cfg["vocab_size"]
    return {"embed": (v, h), "layers": layers, "norm": (h,), "head": (h, v)}


@functools.lru_cache(maxsize=None)
def _draw(shape, dtype_name):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(key, std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(
            jnp.dtype(dtype_name))
    return f


def make(seed: int, cfg):
    """The whole pytree, a leaf a call."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["torch_dtype"])
    std = float(cfg.get("initializer_range", 0.1))
    hidden_std = std / math.sqrt(cfg["hidden_size"] / cfg["dim_model_base"])
    paths, tree = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    key = root_key(seed)
    out = []
    for i, (path, shape) in enumerate(paths):
        name = getattr(path[-1], "key", None)
        if len(shape) == 1:
            sparse_qk = name in ("q_norm", "k_norm") and kind(
                cfg, path[1].idx) == SPARSE
            out.append(jnp.full(shape, QK_GAIN if sparse_qk else 1.0,
                                dtype))
            continue
        out.append(_draw(shape, dtype.name)(
            jax.random.fold_in(key, i),
            jnp.float32(std if name == "embed" else hidden_std)))
    return jax.tree.unflatten(tree, out)


def program_names(cfg) -> dict:
    """Benchmark leaf path -> the parameter name ``MiniCPMSalaForCausalLM``
    gives it."""
    names = {("embed",): "embed_tokens.weight", ("norm",): "norm.weight",
             ("head",): "lm_head.weight"}
    mixer = {"q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
             "v": "self_attn.v_proj.weight", "q_norm": "self_attn.q_norm",
             "k_norm": "self_attn.k_norm", "o_norm": "self_attn.o_norm",
             "g": "self_attn.g_proj.weight", "o": "self_attn.o_proj.weight"}
    ffn = {"ln1": "input_layernorm.weight",
           "ln2": "post_attention_layernorm.weight",
           "gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
           "down": "mlp.down_proj.weight"}
    for i, leaves in enumerate(leaf_shapes(cfg)["layers"]):
        for leaf in leaves:
            names[("layers", i, leaf)] = \
                f"layers.{i}.{mixer.get(leaf) or ffn[leaf]}"
    return names
