"""Weights from ``--seed``, made on the device in one jitted call, in the
type they are served or trained in. The benchmark makes them and hands
the same arrays to the program and to the plain reference; neither takes
anything the other has made.

Every matrix is N(0, ``initializer_range``) (0.02 in both published
configurations), every norm weight is 1. Leaf ``i`` is a function of
(seed, i) alone, so one leaf can be made again without the others.
"""
from __future__ import annotations

LAYER_LEAVES = ("ln1", "q", "k", "v", "o", "ln2", "gate", "up", "down")


def leaf_shapes(cfg) -> dict:
    """The pytree of shapes; linear weights are [in, out]."""
    h = cfg["hidden_size"]
    nh = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads") or nh
    hd, ffn, v = h // nh, cfg["intermediate_size"], cfg["vocab_size"]
    layer = {"ln1": (h,), "q": (h, nh * hd), "k": (h, nkv * hd),
             "v": (h, nkv * hd), "o": (nh * hd, h), "ln2": (h,),
             "gate": (h, ffn), "up": (h, ffn), "down": (ffn, h)}
    return {"embed": (v, h),
            "layers": [dict(layer) for _ in range(cfg["num_hidden_layers"])],
            "norm": (h,), "head": (h, v)}


def root_key(seed: int):
    """--seed may pass 2**31: fold its high bits in."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _leaf(key, i, shape, std, dtype):
    import jax
    import jax.numpy as jnp
    if len(shape) == 1:
        return jnp.ones(shape, dtype)
    k = jax.random.fold_in(key, i)
    return (std * jax.random.normal(k, shape, jnp.float32)).astype(dtype)


def make(seed: int, cfg):
    """The whole pytree in one jitted call."""
    import jax
    import jax.numpy as jnp
    dtype = jnp.dtype(cfg["torch_dtype"])
    std = float(cfg.get("initializer_range", 0.02))
    shapes = leaf_shapes(cfg)
    flat, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))

    @jax.jit
    def build(key):
        return [_leaf(key, i, s, std, dtype) for i, s in enumerate(flat)]

    return jax.tree.unflatten(tree, build(root_key(seed)))


def program_names(cfg) -> dict:
    """Benchmark leaf path -> the parameter name ``LlamaForCausalLM``
    gives it (the one place the two namings meet)."""
    names = {("embed",): "llama.embed_tokens.weight",
             ("norm",): "llama.norm.weight", ("head",): "lm_head.weight"}
    sub = {"ln1": "input_layernorm", "ln2": "post_attention_layernorm",
           "q": "self_attn.q_proj", "k": "self_attn.k_proj",
           "v": "self_attn.v_proj", "o": "self_attn.o_proj",
           "gate": "mlp.gate_proj", "up": "mlp.up_proj",
           "down": "mlp.down_proj"}
    for i in range(cfg["num_hidden_layers"]):
        for leaf, mod in sub.items():
            names[("layers", i, leaf)] = f"llama.layers.{i}.{mod}.weight"
    return names


def get(tree, path):
    for p in path:
        tree = tree[p]
    return tree
