"""The plain reference of the Kimi-delta-attention / latent-attention
hybrid with group-routed experts (Ling-3.0-flash's language model): the
forward pass as the configuration's ``assumed`` list writes it, in
straightforward float32 ``jax.numpy`` at ``highest`` matmul precision, no
cache, no kernel, no batching. It imports nothing of the program.

Per token, pre-norm residual, RMSNorm, no biases but those named:

    y = RMSNorm(x)
    KDA layer ((i + 1) % layer_group_size != 0), H heads of d:
        q, k, v = silu(conv4(y W_q)), silu(conv4(y W_k)), silu(conv4(y W_v))
        q, k divided by their L2 norm a head;  beta = sigmoid(y W_b)
        g = lower_bound * sigmoid(exp(A_log_h) * (y W_f + b_dt))
        S' = Diag(exp(g_t)) S_{t-1};  S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
        o_t = S_t^T q_t / sqrt(d)        (S [d, d] a head, nought at 0)
        x += (RMSNorm_groups(o) * sigmoid(y W_g)_h) W_o
    MLA layer: q = y W_q (full rank) -> heads x (nope | rope);
        [c | k_r] = y W_kva;  c_kv = RMSNorm(c);  k_rope = RoPE(k_r)
        [k_nope_h | v_h] = c_kv W_kvb;  scores / sqrt(nope + rope), causal
        x += concat_h(softmax(scores) v_h) W_o
    leading layers: x += SwiGLU(RMSNorm(x))
    the others: s = sigmoid(y W_r);  c = s + b;  a group of experts scores
        the sum of its two best c;  the topk_group best groups are kept;
        chosen = top-k of c among their experts
        x += sum_{i held} scale * s_i / sum_chosen s_j * E_i(y) + E_shared(y)

The delta rule is the token-by-token recurrence (a ``lax.scan`` over the
positions), not the program's chunked form. RoPE rotates interleaved pairs
(2i, 2i+1). ``weights_kda_moe.routed`` gives the share of the routed
experts the weights hold: routing is over all of them, the sum over those
held.

It has to fit beside the served weights (8.9 GB in bfloat16 at the cut's
widths), so a layer's weights are upcast piece by piece: the routed
experts ``group`` at a time, the head in blocks of vocabulary columns,
attention in blocks of query rows.

``prec="int8"`` or ``"fp8"`` is the control, as in ``reference.py``: every
matmul operand (weights by output channel, activations by token) and the
cached latent entry and the queries rounded to 8 bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .reference import HI, LOW, _mm, rms_norm
from .reference_latent_moe import causal_attention, expert_group, \
    rope_interleaved, swiglu
from .weights_kda_moe import kind, routed

F32 = jnp.float32


def _silu(a):
    return a * jax.nn.sigmoid(a)


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def causal_conv(x, w):
    """x [B, S, C], taps w [K, C] (w[K-1] meets the row itself), nought
    before position 0."""
    k, s = w.shape[0], x.shape[1]
    full = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(full[:, j:j + s] * w[j] for j in range(k))


def recurrence(q, k, v, g, beta):
    """The gated delta rule, a position at a time: q, k, g [B, S, H, d],
    v [B, S, H, dv], beta [B, S, H] -> o [B, S, H, dv] (unscaled)."""
    b, _, nh, d = q.shape

    def step(st, row):
        q_t, k_t, v_t, g_t, b_t = row
        st = jnp.exp(g_t)[..., None] * st
        kv = jnp.einsum("bhk,bhkv->bhv", k_t, st, precision=HI)
        st = st + jnp.einsum("bhk,bhv->bhkv", k_t,
                             b_t[..., None] * (v_t - kv), precision=HI)
        return st, jnp.einsum("bhkv,bhk->bhv", st, q_t, precision=HI)

    rows = tuple(jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta))
    _, o = jax.lax.scan(step, jnp.zeros((b, nh, d, v.shape[-1]), F32), rows)
    return jnp.swapaxes(o, 0, 1)


def kda_block(x, w, *, dims, prec):
    """x + KDA(RMSNorm(x))."""
    nh, d, eps, lower, groups = dims
    w = {n: w[n].astype(F32) for n in ("ln1", "q", "k", "v", "f", "b", "g",
                                       "conv", "A_log", "dt_bias",
                                       "o_norm", "o")}
    b, s, _ = x.shape
    hd = nh * d
    y = rms_norm(x, w["ln1"], eps)
    qkv = jnp.concatenate([_mm(y, w[n], prec) for n in "qkv"], -1)
    a = _silu(causal_conv(qkv, w["conv"]))
    heads = (b, s, nh, d)
    q = _l2(a[..., :hd].reshape(heads))
    k = _l2(a[..., hd:2 * hd].reshape(heads))
    v = a[..., 2 * hd:].reshape(heads)
    beta = jax.nn.sigmoid(_mm(y, w["b"], prec))
    g = lower * jax.nn.sigmoid(jnp.exp(w["A_log"])[:, None] * (
        _mm(y, w["f"], prec) + w["dt_bias"]).reshape(heads))
    o = recurrence(q, k, v, g, beta) / jnp.sqrt(F32(d))
    o = o.reshape(b, s, groups, hd // groups)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    o = o.reshape(b, s, hd) * w["o_norm"]
    o = (o.reshape(heads) * jax.nn.sigmoid(_mm(y, w["g"], prec))[..., None])
    return x + _mm(o.reshape(b, s, hd), w["o"], prec)


def mla_block(x, w, *, dims, prec, block):
    """x + latent attention(RMSNorm(x)), expanded form, full-rank query."""
    nh, r, dn, dr, dv, eps, theta = dims
    w = {n: w[n].astype(F32) for n in
         ("ln1", "q", "kv_a", "kv_a_ln", "kv_b", "o")}
    b, s, _ = x.shape
    y = rms_norm(x, w["ln1"], eps)
    q = _mm(y, w["q"], prec).reshape(b, s, nh, dn + dr)
    kv = _mm(y, w["kv_a"], prec)
    c_kv = rms_norm(kv[..., :r], w["kv_a_ln"], eps)
    k_rope = rope_interleaved(kv[..., r:], theta)
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


def route(y, router, bias, *, top_k, scale, n_group, topk_group, prec):
    """(chosen expert ids [.., k], their weights [.., k], the routing
    margin [..]): the group-limited choice written out. The margin is the
    smaller of how far the last kept group's score lies above the first
    group left out, and how far the last chosen expert's ``s + b`` lies
    above the best left out among the kept groups (a token at a margin
    near nought is routed by rounding)."""
    s = jax.nn.sigmoid(_mm(y, router.astype(F32), prec))
    c = s + bias.astype(F32)
    e = c.shape[-1]
    grouped = c.reshape(c.shape[:-1] + (n_group, e // n_group))
    two = jnp.sort(grouped, -1)[..., -2:]
    gscore = two[..., 0] + two[..., 1]                      # [.., G]
    order = jnp.sort(gscore, -1)
    g_margin = order[..., -topk_group] - order[..., -topk_group - 1]
    kept = gscore >= order[..., -topk_group, None]          # [.., G]
    c = jnp.where(jnp.repeat(kept, e // n_group, -1), c, -jnp.inf)
    top, idx = jax.lax.top_k(c, top_k + 1)
    idx = idx[..., :top_k]
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    margin = jnp.minimum(top[..., top_k - 1] - top[..., top_k], g_margin)
    return idx, w * scale, margin


@functools.lru_cache(maxsize=None)
def _jitted(kda_dims, mla_dims, route_kw, prec, block):
    eps = kda_dims[2]
    kda = jax.jit(functools.partial(kda_block, dims=kda_dims, prec=prec))
    mla = jax.jit(functools.partial(mla_block, dims=mla_dims, prec=prec,
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
                               prec=prec, **dict(route_kw))
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

    return kda, mla, embed, dense, moe_open, group, last_norm, head


def logits_at(w, cfg, ids, positions, prec=None, block=512, group_size=16,
              vocab_block=32768, routes=None):
    """[len(positions), V] float32 logits of one sequence ``ids`` [1, S]
    at the given positions, layer by layer. ``routes``, a list, receives
    every expert layer's (chosen ids [1,S,k], routing margin [1,S])."""
    prec = prec or "f32"
    eps = float(cfg["rms_norm_eps"])
    kda_dims = (cfg["num_attention_heads"], cfg["head_dim"], eps,
                float(cfg["kda_lower_bound"]), int(cfg["group_norm_size"]))
    mla_dims = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["v_head_dim"], eps, float(cfg["rope_theta"]))
    route_kw = (("top_k", int(cfg["num_experts_per_tok"])),
                ("scale", float(cfg["routed_scaling_factor"])),
                ("n_group", int(cfg["n_group"])),
                ("topk_group", int(cfg["topk_group"])))
    kda, mla, embed, dense, moe_open, group, last_norm, head = _jitted(
        kda_dims, mla_dims, route_kw, prec, block)
    _, first, count = routed(cfg)
    x = embed(w["embed"], jnp.asarray(ids))
    for i, lw in enumerate(w["layers"]):
        x = kda(x, lw) if kind(cfg, i) == "kda" else mla(x, lw)
        if i < cfg["first_k_dense_replace"]:
            x = dense(x, lw)
            continue
        y, x, idx, g, margin = moe_open(x, lw)
        if routes is not None:
            routes.append((idx, margin))
        for a in range(0, count, group_size):
            n = min(group_size, count - a)
            x = group(x, y, idx, g, lw["w_gate"][a:a + n],
                      lw["w_up"][a:a + n], lw["w_down"][a:a + n],
                      first + a, n=n)
    y = last_norm(x, jnp.asarray(positions), w["norm"])
    v = w["head"].shape[1]
    return jnp.concatenate(
        [head(y, w["head"][:, a:a + vocab_block])
         for a in range(0, v, vocab_block)], -1)
