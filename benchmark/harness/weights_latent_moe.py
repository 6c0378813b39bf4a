"""Weights from ``--seed`` for the latent-attention, sparse-expert
configurations (the DeepSeek-V3 key set), in the type they are served in.
As ``weights.py``: the benchmark makes them and hands the same arrays to
the program and to the plain reference.

Every matrix is N(0, ``initializer_range``), every norm weight is 1, and
the router's correction bias is N(0, ``initializer_range``) too (the
configuration lists that under ``assumed``): with a bias of nought,
selecting by ``s + b`` and weighting by ``s`` could not be told apart.
Leaf ``i`` is a function of (seed, i) alone. The leaves are made one
jitted call each (one program a shape): a stack of 256 experts is 1.6 GB
as float32 before it is cast, and one call for the whole tree would hold
every leaf's float32 form beside the 11 GB of output.
"""
from __future__ import annotations

import functools

from .weights import get, root_key  # noqa: F401  (one way to name a leaf)

ATTN = ("ln1", "q_a", "q_a_ln", "q_b", "kv_a", "kv_a_ln", "kv_b", "o", "ln2")


def experts_held(cfg) -> tuple[int, int]:
    first, count = cfg.get("experts_held") or (0, cfg["n_routed_experts"])
    return int(first), int(count)


def leaf_shapes(cfg) -> dict:
    """The pytree of shapes; linear weights are [in, out], expert stacks
    [experts held, in, out]."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    f, e = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    held = experts_held(cfg)[1]
    attn = {"ln1": (h,), "q_a": (h, rq), "q_a_ln": (rq,),
            "q_b": (rq, nh * (dn + dr)), "kv_a": (h, rkv + dr),
            "kv_a_ln": (rkv,), "kv_b": (rkv, nh * (dn + dv)),
            "o": (nh * dv, h), "ln2": (h,)}
    ffn = cfg["intermediate_size"]
    dense = {"gate": (h, ffn), "up": (h, ffn), "down": (ffn, h)}
    sh = cfg["n_shared_experts"] * f
    moe = {"router": (h, e), "router_bias": (e,),
           "w_gate": (held, h, f), "w_up": (held, h, f),
           "w_down": (held, f, h),
           "shared_gate": (h, sh), "shared_up": (h, sh),
           "shared_down": (sh, h)}
    layers = [{**attn, **(dense if i < cfg["first_k_dense_replace"]
                          else moe)}
              for i in range(cfg["num_hidden_layers"])]
    v = cfg["vocab_size"]
    return {"embed": (v, h), "layers": layers, "norm": (h,), "head": (h, v)}


@functools.lru_cache(maxsize=None)
def _normal(shape, dtype_name):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(key, std):
        return (std * jax.random.normal(key, shape, jnp.float32)).astype(
            jnp.dtype(dtype_name))
    return f


def make(seed: int, cfg):
    """The whole pytree, a leaf a call. A vector is a norm weight (1),
    but for the router's correction bias (drawn like a matrix)."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["torch_dtype"])
    std = float(cfg.get("initializer_range", 0.02))
    paths, tree = jax.tree_util.tree_flatten_with_path(
        leaf_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    key = root_key(seed)
    out = []
    for i, (path, shape) in enumerate(paths):
        name = getattr(path[-1], "key", None)
        if len(shape) == 1 and name != "router_bias":
            out.append(jnp.ones(shape, dtype))
        else:
            out.append(_normal(shape, dtype.name)(
                jax.random.fold_in(key, i), jnp.float32(std)))
    return jax.tree.unflatten(tree, out)


def program_names(cfg) -> dict:
    """Benchmark leaf path -> the parameter name ``LatentMoEForCausalLM``
    gives it."""
    names = {("embed",): "embed_tokens.weight", ("norm",): "norm.weight",
             ("head",): "lm_head.weight"}
    attn = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm",
            "q_a": "self_attn.q_a_proj", "q_a_ln": "self_attn.q_a_layernorm",
            "q_b": "self_attn.q_b_proj",
            "kv_a": "self_attn.kv_a_proj_with_mqa",
            "kv_a_ln": "self_attn.kv_a_layernorm",
            "kv_b": "self_attn.kv_b_proj", "o": "self_attn.o_proj"}
    dense = {"gate": "mlp.gate_proj", "up": "mlp.up_proj",
             "down": "mlp.down_proj"}
    moe = {"router": "mlp.router.weight",
           "router_bias": "mlp.e_score_correction_bias",
           "w_gate": "mlp.w_gate", "w_up": "mlp.w_up",
           "w_down": "mlp.w_down",
           "shared_gate": "mlp.shared_experts.gate_proj.weight",
           "shared_up": "mlp.shared_experts.up_proj.weight",
           "shared_down": "mlp.shared_experts.down_proj.weight"}
    for i in range(cfg["num_hidden_layers"]):
        for leaf, mod in attn.items():
            names[("layers", i, leaf)] = f"layers.{i}.{mod}.weight"
        if i < cfg["first_k_dense_replace"]:
            for leaf, mod in dense.items():
                names[("layers", i, leaf)] = f"layers.{i}.{mod}.weight"
        else:
            for leaf, name in moe.items():
                names[("layers", i, leaf)] = f"layers.{i}.{name}"
    return names
