"""The plain reference of the latent-attention, sparse-expert family (the
DeepSeek-V3 key set): the published forward pass in straightforward
float32 ``jax.numpy`` at ``highest`` matmul precision, attention in its
expanded form (keys and values expanded per head from the latent), no
cache, no kernel, no batching. It imports nothing of the program.

Per token, pre-norm residual, RMSNorm, no biases:

    y = RMSNorm(x)
    c_q = RMSNorm(y W_qa);  q = c_q W_qb  -> heads x (nope | rope)
    [c | k_r] = y W_kva;  c_kv = RMSNorm(c);  k_rope = RoPE(k_r)
    [k_nope_h | v_h] = c_kv W_kvb
    scores = (q_nope_h . k_nope_h + RoPE(q_rope_h) . k_rope) / sqrt(nope + rope)
    x += concat_h(softmax(scores) v_h) W_o
    leading layers:  x += SwiGLU(RMSNorm(x))
    the others:      s = sigmoid(y W_r);  chosen = top-k of s + b
                     g_i = scale * s_i / (sum_chosen s_j + 1e-20)
                     x += sum_i g_i E_i(y) + E_shared(y)

RoPE rotates interleaved pairs (2i, 2i+1); there is no capacity and no
token is dropped. ``experts_held`` in the configuration gives the share
of the routed experts that the weights hold: routing is over all of them,
the sum over those held.

It has to fit beside the served weights (11 GB in bfloat16 at the
published widths), so a layer's weights are upcast piece by piece: the
routed experts ``group`` at a time (16 experts are 0.3 GB in float32, a
whole expert layer 5 GB), the head in blocks of vocabulary columns,
attention in blocks of query rows.

``prec="int8"`` or ``"fp8"`` is the control, as in ``reference.py``: every
matmul operand (weights by output channel, activations by token) and the
cached latent entry and the queries (by token and head) rounded to 8 bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .reference import HI, LOW, _mm, rms_norm
from .weights_latent_moe import experts_held

F32 = jnp.float32


def rope_interleaved(x, theta):
    """x [B, S, ..., D] at positions 0..S-1; pairs (2i, 2i + 1) rotate."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    f = jnp.arange(s, dtype=F32)[:, None] * inv               # [S, D/2]
    f = f.reshape((1, s) + (1,) * (x.ndim - 3) + (d // 2,))
    sin, cos = jnp.sin(f), jnp.cos(f)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def causal_attention(q, k, v, block):
    """q, k [B,S,H,Dk], v [B,S,H,Dv] -> [B,S,H,Dv]; query rows go
    ``block`` at a time."""
    b, s, nh, dk = q.shape
    block = min(block, s)
    assert s % block == 0, (s, block)
    qb = q.reshape(b, s // block, block, nh, dk).transpose(1, 0, 2, 3, 4)
    kpos = jnp.arange(s)

    def one(args):
        i, qi = args
        qpos = i * block + jnp.arange(block)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision=HI) \
            / jnp.sqrt(F32(dk))
        ok = kpos[None, :] <= qpos[:, None]
        p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)

    out = jax.lax.map(one, (jnp.arange(s // block), qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, nh, v.shape[-1])


def attention_block(x, w, *, dims, prec, block):
    """x + attention(RMSNorm(x)), expanded form."""
    nh, r, dn, dr, dv, eps, theta = dims
    w = {n: w[n].astype(F32) for n in
         ("ln1", "q_a", "q_a_ln", "q_b", "kv_a", "kv_a_ln", "kv_b", "o")}
    b, s, _ = x.shape
    y = rms_norm(x, w["ln1"], eps)
    c_q = rms_norm(_mm(y, w["q_a"], prec), w["q_a_ln"], eps)
    q = _mm(c_q, w["q_b"], prec).reshape(b, s, nh, dn + dr)
    kv = _mm(y, w["kv_a"], prec)
    c_kv = rms_norm(kv[..., :r], w["kv_a_ln"], eps)
    k_rope = rope_interleaved(kv[..., r:], theta)              # [B, S, dr]
    q = jnp.concatenate(
        [q[..., :dn], rope_interleaved(q[..., dn:], theta)], -1)
    if prec in LOW:
        entry = LOW[prec](jnp.concatenate([c_kv, k_rope], -1), -1)
        c_kv, k_rope = entry[..., :r], entry[..., r:]
        q = LOW[prec](q, -1)
    kvb = _mm(c_kv, w["kv_b"], prec).reshape(b, s, nh, dn + dv)
    k = jnp.concatenate(
        [kvb[..., :dn],
         jnp.broadcast_to(k_rope[:, :, None, :], (b, s, nh, dr))], -1)
    a = causal_attention(q, k, kvb[..., dn:], block).reshape(b, s, nh * dv)
    return x + _mm(a, w["o"], prec)


def swiglu(y, gate, up, down, prec):
    return _mm(jax.nn.silu(_mm(y, gate.astype(F32), prec))
               * _mm(y, up.astype(F32), prec), down.astype(F32), prec)


def route(y, router, bias, *, top_k, scale, norm, prec, margin=False):
    """(chosen expert ids [.., k], their weights [.., k]); with ``margin``
    also how far the last chosen expert's biased score lies above the
    first one left out (a token at a margin near nought is routed by
    rounding)."""
    s = jax.nn.sigmoid(_mm(y, router.astype(F32), prec))
    top, idx = jax.lax.top_k(s + bias.astype(F32), top_k + int(margin))
    idx = idx[..., :top_k]
    g = jnp.take_along_axis(s, idx, -1)
    if norm:
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    if margin:
        return idx, g * scale, top[..., top_k - 1] - top[..., top_k]
    return idx, g * scale


def expert_group(y, gates, w_gate, w_up, w_down, prec):
    """sum over the experts of one group of gate * E(y): y [B,S,H],
    gates [B,S,G] (nought where a token was not routed to the expert),
    w_* [G, in, out]. Every expert of the group runs over every token."""
    wg, wu, wd = (a.astype(F32) for a in (w_gate, w_up, w_down))
    if prec in LOW:
        y = LOW[prec](y, -1)
        wg, wu, wd = (LOW[prec](a, 1) for a in (wg, wu, wd))
    a = jax.nn.silu(jnp.einsum("bsh,ghf->gbsf", y, wg, precision=HI)) \
        * jnp.einsum("bsh,ghf->gbsf", y, wu, precision=HI)
    if prec in LOW:
        a = LOW[prec](a, -1)
    out = jnp.einsum("gbsf,gfh->gbsh", a, wd, precision=HI)
    return jnp.einsum("gbsh,bsg->bsh", out, gates, precision=HI)


def _dims(cfg):
    return (cfg["num_attention_heads"], cfg["kv_lora_rank"],
            cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]))


@functools.lru_cache(maxsize=None)
def _jitted(dims, route_kw, prec, block):
    eps = dims[5]
    attn = jax.jit(functools.partial(attention_block, dims=dims, prec=prec,
                                     block=block))

    @jax.jit
    def embed(table, ids):
        return table[ids].astype(F32)

    @jax.jit
    def dense(h, lw):
        y = rms_norm(h, lw["ln2"].astype(F32), eps)
        return h + swiglu(y, lw["gate"], lw["up"], lw["down"], prec)

    @jax.jit
    def moe_open(h, lw):
        """(RMSNorm(h), h + E_shared, chosen ids, their weights, the
        routing margin)."""
        y = rms_norm(h, lw["ln2"].astype(F32), eps)
        idx, g, margin = route(y, lw["router"], lw["router_bias"],
                               prec=prec, margin=True, **dict(route_kw))
        return y, h + swiglu(y, lw["shared_gate"], lw["shared_up"],
                             lw["shared_down"], prec), idx, g, margin

    @functools.partial(jax.jit, static_argnames=("n",))
    def group(acc, y, idx, g, w_gate, w_up, w_down, first, n):
        gates = jnp.sum(jax.nn.one_hot(idx - first, n, dtype=F32)
                        * g[..., None], axis=-2)
        return acc + expert_group(y, gates, w_gate, w_up, w_down, prec)

    @jax.jit
    def last_norm(x, positions, norm_w):
        return rms_norm(x[0, positions], norm_w.astype(F32), eps)

    @jax.jit
    def head(y, head_w):
        return _mm(y, head_w.astype(F32), prec)

    return embed, attn, dense, moe_open, group, last_norm, head


def moe_layer(h, lw, cfg, fns, group_size, routes=None):
    """h + E_shared(y) + the routed experts held, ``group_size`` at a
    time. ``routes``, a list, receives (chosen ids [B,S,k], margin
    [B,S])."""
    _, _, _, moe_open, group, _, _ = fns
    y, acc, idx, g, margin = moe_open(h, lw)
    if routes is not None:
        routes.append((idx, margin))
    first, count = experts_held(cfg)
    for a in range(0, count, group_size):
        n = min(group_size, count - a)
        acc = group(acc, y, idx, g, lw["w_gate"][a:a + n],
                    lw["w_up"][a:a + n], lw["w_down"][a:a + n],
                    first + a, n=n)
    return acc


def logits_at(w, cfg, ids, positions, prec=None, block=512, group_size=16,
              vocab_block=32768, routes=None):
    """[len(positions), V] float32 logits of one sequence ``ids`` [1, S]
    at the given positions, layer by layer. ``routes``, a list, receives
    every expert layer's (chosen ids [1,S,k], routing margin [1,S])."""
    prec = prec or "f32"
    route_kw = (("top_k", int(cfg["num_experts_per_tok"])),
                ("scale", float(cfg["routed_scaling_factor"])),
                ("norm", bool(cfg["norm_topk_prob"])))
    fns = _jitted(_dims(cfg), route_kw, prec, block)
    embed, attn, dense, _, _, last_norm, head = fns
    x = embed(w["embed"], jnp.asarray(ids))
    for i, lw in enumerate(w["layers"]):
        x = attn(x, lw)
        x = dense(x, lw) if i < cfg["first_k_dense_replace"] \
            else moe_layer(x, lw, cfg, fns, group_size, routes)
    y = last_norm(x, jnp.asarray(positions), w["norm"])
    v = w["head"].shape[1]
    return jnp.concatenate(
        [head(y, w["head"][:, a:a + vocab_block])
         for a in range(0, v, vocab_block)], -1)
