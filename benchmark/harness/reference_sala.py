"""The plain reference of MiniCPM-SALA's language model: the forward pass
as the configuration's ``assumed`` list writes it, in straightforward
float32 ``jax.numpy`` at ``highest`` matmul precision, no cache, no
kernel, no batching. It imports nothing of the program.

Per token, pre-norm residual, RMSNorm, no biases, ``a = scale_depth /
sqrt(N)`` with N the published depth (``weights_sala.depth_published``):

    x_0 = scale_emb * E[id]
    y = RMSNorm(x)
    minicpm4 layer (sparse), H query heads and KV key heads of d, no RoPE:
        q, k = RMSNorm_head(y W_q), RMSNorm_head(y W_k);  v = y W_v
        ck_c = mean(k[c s .. c s + K - 1])     (kernel K, stride s)
        at row p: ck_c exists where c s + K - 1 <= p;
          w_c = sum over the group's heads of softmax_c(q_h . ck_c / sqrt(d))
          score(block b) = max of w_c over the c with floor(c s / B) = b
          dense where p + 1 <= dense_len, else the init blocks, the blocks
          of positions p - window + 1 .. p, and the top-k others (ties to
          the lower block, unscored blocks never)
        o_p = softmax over the selected keys at or before p of
              q . k / sqrt(d), times v
        x += a * (o * sigmoid(y W_g)) W_o
    lightning-attn layer, H heads of d:
        q, k = RoPE(RMSNorm_head(y W_q)), RoPE(RMSNorm_head(y W_k));
        v = y W_v;  lambda_h = exp(-2^(-8 (h+1) / H) (1 - l / (N - 1) + 1e-5))
        S_t = lambda_h S_{t-1} + k_t v_t^T;  o_t = S_t^T q_t / sqrt(d)
        x += a * ((RMSNorm(o) * sigmoid(y W_g)) W_o)
    x += a * SwiGLU(RMSNorm(x))
    logits = (RMSNorm(x) / (hidden_size / dim_model_base)) W_head

The recurrence runs a token at a time (a ``lax.scan``), not the
program's chunked form; the selection is computed from the reference's
own float32 scores. So that 64k positions fit beside the served weights,
the lightning layers and the FFN go in blocks of rows, and the sparse
layers' selection and attention in blocks of query rows, each over every
key with the selection as a mask.

``prec="int8"`` or ``"fp8"`` is the control, as in ``reference.py``:
every matmul operand and the cached keys, values and compressed keys
rounded to 8 bits. ``dense=True`` attends every key at or before a row
in the sparse layers (the selection left out); ``decay=False`` gives
every lightning head ``lambda = 1``: the planted faults of
``check_sala``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .reference import HI, LOW, _mm, rms_norm
from .weights_sala import SPARSE, depth_published, kind

F32 = jnp.float32


def head_rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_at(x, pos, theta):
    """x [R, H, d] at positions pos [R]; pairs (i, i + d/2) rotate."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    f = pos.astype(F32)[:, None] * inv
    sin, cos = jnp.sin(f)[:, None], jnp.cos(f)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def log_decay(cfg, i):
    """log lambda_h [H] of held layer ``i``."""
    h, n = cfg["lightning_nh"], depth_published(cfg)
    layer = cfg.get("first_layer", 0) + i
    slopes = 2.0 ** (-8.0 * jnp.arange(1, h + 1, dtype=F32) / h)
    return -slopes * (1 - layer / (n - 1) + 1e-5)


def selection(q, ck, pos, sc, p):
    """bool [R, KV, P]: the blocks rows at ``pos`` [R] attend, from q [R,
    KV, G, d] (scaled) and the compressed keys ck [C, KV, d]."""
    blk, stride, kern = sc["block_size"], sc["kernel_stride"], \
        sc["kernel_size"]
    c = ck.shape[0]
    s = jnp.einsum("rkgd,ckd->rkgc", q, ck, precision=HI)
    exists = (jnp.arange(c) * stride + kern - 1)[None] <= pos[:, None]
    ex = exists[:, None, None]
    s = jnp.where(ex, s, -jnp.inf)
    m = jnp.max(s, -1, keepdims=True)
    e = jnp.where(ex, jnp.exp(s - jnp.where(jnp.isfinite(m), m, 0.0)), 0.0)
    w = jnp.sum(e / jnp.maximum(jnp.sum(e, -1, keepdims=True), 1e-30), 2)
    w = jnp.where(exists[:, None], w, -jnp.inf)               # [R, KV, C]
    # compressed key c starts in block c s // B: B / s of them a block
    e = blk // stride
    w = jnp.pad(w, ((0, 0), (0, 0), (0, p * e - c)),
                constant_values=-jnp.inf)
    score = jnp.max(w.reshape(w.shape[:2] + (p, e)), -1)      # [R, KV, P]
    b = jnp.arange(p)
    live = b[None] <= (pos // blk)[:, None]
    init = b[None] < sc["init_blocks"]
    win = b[None] >= (jnp.maximum(pos - sc["window_size"] + 1, 0)
                      // blk)[:, None]
    cand = (live & ~init & ~win)[:, None]
    val, idx = jax.lax.top_k(jnp.where(cand, score, -jnp.inf),
                             min(sc["topk"], p))
    chosen = jnp.any((idx[..., None] == b) & jnp.isfinite(val)[..., None],
                     -2)
    dense = (pos + 1 <= sc["dense_len"])[:, None, None]
    return live[:, None] & (dense | init[:, None] | win[:, None] | chosen)


def sparse_block(x, w, *, cfg_t, prec, rows, dense):
    """x + a * sparse attention(RMSNorm(x)) over one sequence x [S, h]."""
    (nh, nkv, d, eps, a, sc) = cfg_t
    sc = dict(sc)
    w = {n: w[n].astype(F32) for n in ("ln1", "q", "k", "v", "q_norm",
                                       "k_norm", "g", "o")}
    s = x.shape[0]
    blk, stride, kern = sc["block_size"], sc["kernel_stride"], \
        sc["kernel_size"]
    p = -(-s // blk)
    g = nh // nkv
    y = rms_norm(x, w["ln1"], eps)
    k = head_rms(_mm(y, w["k"], prec).reshape(s, nkv, d), w["k_norm"], eps)
    v = _mm(y, w["v"], prec).reshape(s, nkv, d)
    if prec in LOW:
        k, v = LOW[prec](k, -1), LOW[prec](v, -1)
    c = max((s - kern) // stride + 1, 1)
    ck = jax.vmap(lambda j: jnp.mean(jax.lax.dynamic_slice_in_dim(
        k, j * stride, kern), 0))(jnp.arange(c))
    if prec in LOW:
        ck = LOW[prec](ck, -1)
    kpos = jnp.arange(s)

    def one(args):
        yr, pos = args
        q = head_rms(_mm(yr, w["q"], prec).reshape(rows, nh, d),
                     w["q_norm"], eps)
        if prec in LOW:
            q = LOW[prec](q, -1)
        q = q.reshape(rows, nkv, g, d) / math.sqrt(d)
        sel = jnp.ones((rows, nkv, p), bool) if dense else selection(
            q, ck, pos, sc, p)
        see = (jnp.repeat(sel, blk, -1)[..., :s]
               & (kpos[None, None] <= pos[:, None, None]))  # [R, KV, S]
        sco = jnp.einsum("rkgd,skd->rkgs", q, k, precision=HI)
        sco = jnp.where(see[:, :, None], sco, -jnp.inf)
        o = jnp.einsum("rkgs,skd->rkgd", jax.nn.softmax(sco, -1), v,
                       precision=HI).reshape(rows, nh * d)
        o = o * jax.nn.sigmoid(_mm(yr, w["g"], prec))
        return _mm(o, w["o"], prec)

    out = jax.lax.map(one, (y.reshape(s // rows, rows, -1),
                            kpos.reshape(s // rows, rows)))
    return x + a * out.reshape(s, -1)


def lightning_block(x, w, logd, *, cfg_t, prec, rows):
    """x + a * lightning attention(RMSNorm(x)), rows at a time."""
    (nh, d, eps, a, theta) = cfg_t
    w = {n: w[n].astype(F32) for n in ("ln1", "q", "k", "v", "q_norm",
                                       "k_norm", "o_norm", "g", "o")}
    s = x.shape[0]
    lam = jnp.exp(logd)[:, None, None]

    def block(st, args):
        xr, pos = args
        y = rms_norm(xr, w["ln1"], eps)

        def heads(n):
            return _mm(y, w[n], prec).reshape(rows, nh, d)
        q = rope_at(head_rms(heads("q"), w["q_norm"], eps), pos, theta)
        k = rope_at(head_rms(heads("k"), w["k_norm"], eps), pos, theta)
        v = heads("v")

        def step(st, row):
            q_t, k_t, v_t = row
            st = lam * st + jnp.einsum("hk,hv->hkv", k_t, v_t, precision=HI)
            return st, jnp.einsum("hkv,hk->hv", st, q_t, precision=HI)

        st, o = jax.lax.scan(step, st, (q, k, v))
        o = o.reshape(rows, nh * d) / math.sqrt(d)
        o = rms_norm(o, w["o_norm"], eps) * jax.nn.sigmoid(
            _mm(y, w["g"], prec))
        return st, _mm(o, w["o"], prec)

    _, out = jax.lax.scan(block, jnp.zeros((nh, d, d), F32),
                          (x.reshape(s // rows, rows, -1),
                           jnp.arange(s).reshape(s // rows, rows)))
    return x + a * out.reshape(s, -1)


def ffn_block(x, w, *, eps, a, prec, rows):
    w = {n: w[n].astype(F32) for n in ("ln2", "gate", "up", "down")}

    def one(xr):
        y = rms_norm(xr, w["ln2"], eps)
        gt = _mm(y, w["gate"], prec)
        return xr + a * _mm(gt * jax.nn.sigmoid(gt) * _mm(y, w["up"], prec),
                            w["down"], prec)
    s = x.shape[0]
    return jax.lax.map(one, x.reshape(s // rows, rows, -1)).reshape(s, -1)


@functools.lru_cache(maxsize=None)
def _jitted(sparse_t, light_t, eps, a, scale_emb, divisor, prec, dense,
            decay, rows, ffn_rows):
    sparse = jax.jit(functools.partial(sparse_block, cfg_t=sparse_t,
                                       prec=prec, rows=rows, dense=dense))
    light = jax.jit(functools.partial(lightning_block, cfg_t=light_t,
                                      prec=prec, rows=ffn_rows))
    ffn = jax.jit(functools.partial(ffn_block, eps=eps, a=a, prec=prec,
                                    rows=ffn_rows))

    @jax.jit
    def embed(table, ids):
        return table[ids].astype(F32) * scale_emb

    @jax.jit
    def last_norm(x, positions, norm_w):
        return rms_norm(x[positions], norm_w.astype(F32), eps) / divisor

    @jax.jit
    def head(y, head_w):
        return _mm(y, head_w.astype(F32), prec)

    return sparse, light, ffn, embed, last_norm, head


def logits_at(w, cfg, ids, positions, prec=None, dense=False, decay=True,
              rows=64, ffn_rows=256, vocab_block=16384):
    """[len(positions), V] float32 logits of one sequence ``ids`` [1, S]
    (S a multiple of ``ffn_rows``) at the given positions, layer by
    layer."""
    prec = prec or "f32"
    eps = float(cfg["rms_norm_eps"])
    a = float(cfg["scale_depth"]) / math.sqrt(depth_published(cfg))
    sc = tuple(sorted(cfg["sparse_config"].items()))
    sparse_t = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], eps, a, sc)
    light_t = (cfg["lightning_nh"], cfg["lightning_head_dim"], eps, a,
               float(cfg["rope_theta"]))
    sparse, light, ffn, embed, last_norm, head = _jitted(
        sparse_t, light_t, eps, a, float(cfg["scale_emb"]),
        cfg["hidden_size"] / cfg["dim_model_base"], prec, bool(dense),
        bool(decay), rows, ffn_rows)
    x = embed(w["embed"], jnp.asarray(ids)[0])
    for i, lw in enumerate(w["layers"]):
        if kind(cfg, i) == SPARSE:
            x = sparse(x, lw)
        else:
            logd = log_decay(cfg, i) if decay else jnp.zeros(
                cfg["lightning_nh"], F32)
            x = light(x, lw, logd)
        x = ffn(x, lw)
    y = last_norm(x, jnp.asarray(positions), w["norm"])
    v = w["head"].shape[1]
    return jnp.concatenate(
        [head(y, w["head"][:, j:j + vocab_block])
         for j in range(0, v, vocab_block)], -1)
