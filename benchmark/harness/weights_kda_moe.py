"""Weights from ``--seed`` for the Kimi-delta-attention / latent-attention
hybrid with group-routed experts (Ling-3.0-flash's language model), in the
type they are served in. As ``weights.py``: the benchmark makes them and
hands the same arrays to the program and to the plain reference.

Leaf ``i`` is a function of (seed, i) alone, one jitted call a leaf (one
program a shape: a stack of 128 experts is 1 GB as float32 before it is
cast). By the leaf's name: a projection matrix and the router's selection
bias are N(0, ``initializer_range``); a norm weight is 1; a KDA layer's
``A_log`` is log of a draw uniform in [1, 16] a head and its ``dt_bias``
the inverse softplus of a step drawn log-uniformly in [1e-3, 1e-1] a
channel (the usual initialisation of a decay gate: most channels then
keep most of their state from a token to the next); the short
convolution's taps N(0, 1 / sqrt(kernel)) (at 0.02 the delta rule's
inputs would be nought to rounding). The configuration's file lists each
under ``assumed``.
"""
from __future__ import annotations

import functools
import math

from .weights import get, root_key  # noqa: F401  (one way to name a leaf)


def routed(cfg) -> tuple[int, int, int]:
    """(experts the router scores, the first held here, how many are
    held). A file that holds a share of a layer's experts gives the
    count it holds as ``num_experts`` and the published one under
    ``reduced`` (the share is ``[0, count)``); ``experts_held`` names
    another share (the CPU tests')."""
    share = cfg.get("reduced", {}).get("num_experts", {})
    total = int(share.get("published", cfg["num_experts"]))
    first, count = cfg.get("experts_held") or (0, cfg["num_experts"])
    return total, int(first), int(count)


def kind(cfg, i: int) -> str:
    """``mla`` | ``kda``: layer ``i``'s mixer."""
    return "mla" if (i + 1) % cfg["layer_group_size"] == 0 else "kda"


def leaf_shapes(cfg) -> dict:
    """The pytree of shapes; linear weights are [in, out], expert stacks
    [experts held, in, out]."""
    h, nh, d = cfg["hidden_size"], cfg["num_attention_heads"], \
        cfg["head_dim"]
    hd, k = nh * d, cfg["short_conv_kernel_size"]
    rkv = cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    kda = {"q": (h, hd), "k": (h, hd), "v": (h, hd), "f": (h, hd),
           "b": (h, nh), "g": (h, nh), "conv": (k, 3 * hd), "A_log": (nh,),
           "dt_bias": (hd,), "o_norm": (hd,), "o": (hd, h)}
    mla = {"q": (h, nh * (dn + dr)), "kv_a": (h, rkv + dr),
           "kv_a_ln": (rkv,), "kv_b": (rkv, nh * (dn + dv)),
           "o": (nh * dv, h)}
    ffn, f = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    e, _, held = routed(cfg)
    sh = cfg["moe_shared_expert_intermediate_size"]
    dense = {"gate": (h, ffn), "up": (h, ffn), "down": (ffn, h)}
    moe = {"router": (h, e), "router_bias": (e,),
           "w_gate": (held, h, f), "w_up": (held, h, f),
           "w_down": (held, f, h),
           "shared_gate": (h, sh), "shared_up": (h, sh),
           "shared_down": (sh, h)}
    layers = [{"ln1": (h,), "ln2": (h,),
               **(kda if kind(cfg, i) == "kda" else mla),
               **(dense if i < cfg["first_k_dense_replace"] else moe)}
              for i in range(cfg["num_hidden_layers"])]
    v = cfg["vocab_size"]
    return {"embed": (v, h), "layers": layers, "norm": (h,), "head": (h, v)}


@functools.lru_cache(maxsize=None)
def _draw(shape, dtype_name, how):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(key, std):
        if how == "a_log":
            x = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                           16.0))
        elif how == "dt_bias":
            step = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            x = step + jnp.log(-jnp.expm1(-step))    # inverse softplus
        else:
            x = std * jax.random.normal(key, shape, jnp.float32)
        return x.astype(jnp.dtype(dtype_name))
    return f


def make(seed: int, cfg):
    """The whole pytree, a leaf a call."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["torch_dtype"])
    std = float(cfg.get("initializer_range", 0.02))
    conv_std = 1.0 / math.sqrt(cfg["short_conv_kernel_size"])
    paths, tree = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    key = root_key(seed)
    out = []
    for i, (path, shape) in enumerate(paths):
        name = getattr(path[-1], "key", None)
        how = {"A_log": "a_log", "dt_bias": "dt_bias"}.get(name, "normal")
        if len(shape) == 1 and how == "normal" and name != "router_bias":
            out.append(jnp.ones(shape, dtype))
            continue
        out.append(_draw(shape, dtype.name, how)(
            jax.random.fold_in(key, i),
            jnp.float32(conv_std if name == "conv" else std)))
    return jax.tree.unflatten(tree, out)


def program_names(cfg) -> dict:
    """Benchmark leaf path -> the parameter name ``KdaMoEForCausalLM``
    gives it."""
    names = {("embed",): "embed_tokens.weight", ("norm",): "norm.weight",
             ("head",): "lm_head.weight"}
    kda = {"q": "self_attn.q_proj.weight", "k": "self_attn.k_proj.weight",
           "v": "self_attn.v_proj.weight", "f": "self_attn.f_proj.weight",
           "b": "self_attn.b_proj.weight", "g": "self_attn.g_proj.weight",
           "conv": "self_attn.conv_weight", "A_log": "self_attn.A_log",
           "dt_bias": "self_attn.dt_bias", "o_norm": "self_attn.o_norm",
           "o": "self_attn.o_proj.weight"}
    mla = {"q": "self_attn.q_proj.weight",
           "kv_a": "self_attn.kv_a_proj_with_mqa.weight",
           "kv_a_ln": "self_attn.kv_a_layernorm.weight",
           "kv_b": "self_attn.kv_b_proj.weight",
           "o": "self_attn.o_proj.weight"}
    dense = {"gate": "mlp.gate_proj.weight", "up": "mlp.up_proj.weight",
             "down": "mlp.down_proj.weight"}
    moe = {"router": "mlp.router.weight",
           "router_bias": "mlp.e_score_correction_bias",
           "w_gate": "mlp.w_gate", "w_up": "mlp.w_up",
           "w_down": "mlp.w_down",
           "shared_gate": "mlp.shared_experts.gate_proj.weight",
           "shared_up": "mlp.shared_experts.up_proj.weight",
           "shared_down": "mlp.shared_experts.down_proj.weight"}
    for i in range(cfg["num_hidden_layers"]):
        leaves = {"ln1": "input_layernorm.weight",
                  "ln2": "post_attention_layernorm.weight",
                  **(kda if kind(cfg, i) == "kda" else mla),
                  **(dense if i < cfg["first_k_dense_replace"] else moe)}
        for leaf, name in leaves.items():
            names[("layers", i, leaf)] = f"layers.{i}.{name}"
    return names
