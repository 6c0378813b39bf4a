"""The plain reference of the SambaY family (arXiv:2507.06607; the
configuration's ``assumed`` lists what no published key gives): the
forward pass in straightforward float32 ``jax.numpy`` at ``highest``
matmul precision, a sequential ``lax.scan`` over time for the
recurrence, attention in blocks of query rows, the head in blocks of
vocabulary columns; no cache, no kernel, no batching. It imports nothing
of the program.

``LN`` is LayerNorm with weight and bias. Layer ``i`` over a sequence:

    h  = x + Mixer_i(LN1_i(x))
    x' = h + W2 (silu(g) * y),   [g | y] = W1 LN2_i(h)

then a final LN and logits = hidden x E^T (``E`` the embedding). With
``m`` the memory layer (``weights_sambay.kind``):

    Mamba (i < m even, and m):
        [xs | z] = W_in u;  xs = silu(conv_t(xs))   (causal depthwise, bias)
        [r | B | C] = W_x xs;  dt = softplus(W_dt r + b_dt)
        S_t = exp(dt_t A) S_{t-1} + (dt_t xs_t) (x) B_t,  A = -exp(A_log)
        y_t = S_t C_t + D xs_t;  out = W_out (y silu(z))
        (layer m hands on y: the memory)
    differential attention (i < m odd: window; m + 1: full):
        [q | k | v] = Wqkv u + b; pairs of adjacent heads; values joined
        a_w = softmax(q_w k_w^T / sqrt(d)) v,  w = 1, 2
        lam = exp(lq1.lk1) - exp(lq2.lk2) + lam0(i)
        o = RMSNorm(a_1 - lam a_2) (1 - lam0(i));  out = W_o o + b_o
    GMU (i > m + 1 even):    W_out (silu(W_in u) * memory)
    cross (i > m + 1 odd):   queries W_q u + b, K and V layer m + 1's

The state is [d_inner, d_state] as in the paper (the program holds its
transpose; ``A_log`` lies [d_state, d_inner] in the leaves). The leaves
stay as drawn (bfloat16 at the published widths: 7.7 GB, where float32
would be 15.4) and are widened one layer at a time.

``prec="int8"`` or ``"fp8"`` is the control, as in ``reference.py``: every
matmul operand (weights by output channel, activations by token) and the
queries, keys and values (by token and head) rounded to 8 bits; the
recurrence itself stays float32 over its rounded inputs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .reference import HI, LOW, _mm
from .weights_sambay import dims, kind, lam0

F32 = jnp.float32


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def silu(a):
    return a * jax.nn.sigmoid(a)


def recurrence(xs, dt, a, b, c, d):
    """xs, dt [S, D], a [D, n], b, c [S, n], d [D] -> y [S, D]: the
    selective scan step by step from a zero state."""
    def step(s, row):
        x_t, dt_t, b_t, c_t = row
        s = jnp.exp(dt_t[:, None] * a) * s \
            + (dt_t * x_t)[:, None] * b_t[None, :]
        return s, jnp.sum(s * c_t[None, :], -1) + d * x_t
    _, y = jax.lax.scan(step, jnp.zeros(a.shape, F32), (xs, dt, b, c))
    return y


def causal_conv(xs, w, bias):
    """xs [S, D], w [K, D] (``w[K-1]`` meets the row itself), bias [D]."""
    k, s = w.shape[0], xs.shape[0]
    pad = jnp.concatenate([jnp.zeros((k - 1, xs.shape[1]), F32), xs])
    return bias + sum(pad[j:j + s] * w[j] for j in range(k))


def mamba(u, w, z, prec):
    """u [S, H] (normed) -> (out [S, H], y [S, D])."""
    di, n, r = z["di"], z["n"], z["r"]
    xz = _mm(u, w["in"], prec)
    xs = silu(causal_conv(xz[:, :di], w["conv_w"], w["conv_b"]))
    rbc = _mm(xs, w["x"], prec)
    dt = jax.nn.softplus(_mm(rbc[:, :r], w["dt_w"], prec) + w["dt_b"])
    y = recurrence(xs, dt, -jnp.exp(w["A_log"]).T, rbc[:, r:r + n],
                   rbc[:, r + n:], w["D"])
    return _mm(y * silu(xz[:, di:]), w["out"], prec), y


def diff_attend(q, k, v, w, lam_0, window, block, eps=1e-5):
    """q [S, H, d], k / v [S, KV, d] -> [S, H * d]: per pair of adjacent
    query heads (q1, q2), against key pair j // (H / KV) and its joined
    values; two softmaxes; query rows go ``block`` at a time."""
    s, nh, d = q.shape
    nkv = k.shape[1]
    g, r = nkv // 2, nh // nkv
    block = min(block, s)
    assert s % block == 0, (s, block)
    qb = q.reshape(s // block, block, g, r, 2, d)
    kg, vg = k.reshape(s, g, 2, d), v.reshape(s, g, 2 * d)
    kpos = jnp.arange(s)
    lam = (jnp.exp(jnp.sum(w["lq1"] * w["lk1"]))
           - jnp.exp(jnp.sum(w["lq2"] * w["lk2"])) + lam_0)

    def one(args):
        i, qi = args                                  # [blk, g, r, 2, d]
        qpos = i * block + jnp.arange(block)
        sc = jnp.einsum("qgrwd,kgwd->grwqk", qi, kg, precision=HI) \
            / jnp.sqrt(F32(d))
        ok = kpos[None, :] <= qpos[:, None]
        ok = jnp.where(window > 0,
                       ok & (kpos[None, :] > qpos[:, None] - window), ok)
        p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), -1)
        o = jnp.einsum("grwqk,kge->qgrwe", p, vg, precision=HI)
        a = o[..., 0, :] - lam * o[..., 1, :]
        a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps)
        return a * w["subln"] * (1.0 - lam_0)

    out = jax.lax.map(one, (jnp.arange(s // block), qb))
    return out.reshape(s, nh * d)


def _heads(y, n, d):
    return y.reshape(y.shape[0], n, d)


def self_attention(u, w, z, lam_0, window, prec, block):
    """-> (out [S, H], k, v [S, KV, d])."""
    h, nh, nkv, d = z["h"], z["nh"], z["nkv"], z["d"]
    qkv = _mm(u, w["qkv_w"], prec) + w["qkv_b"]
    q = _heads(qkv[:, :h], nh, d)
    k = _heads(qkv[:, h:h + nkv * d], nkv, d)
    v = _heads(qkv[:, h + nkv * d:], nkv, d)
    if prec in LOW:
        q, k, v = (LOW[prec](t, -1) for t in (q, k, v))
    a = diff_attend(q, k, v, w, lam_0, window, block)
    return _mm(a, w["o_w"], prec) + w["o_b"], k, v


def cross_attention(u, w, k, v, z, lam_0, prec, block):
    q = _heads(_mm(u, w["q_w"], prec) + w["q_b"], z["nh"], z["d"])
    if prec in LOW:
        q = LOW[prec](q, -1)
    a = diff_attend(q, k, v, w, lam_0, jnp.int32(0), block)
    return _mm(a, w["o_w"], prec) + w["o_b"]


def gmu(u, w, memory, prec):
    return _mm(silu(_mm(u, w["in"], prec)) * memory, w["out"], prec)


def _close(h, w, eps, prec, ffn):
    """h + W2 (silu(g) * y), [g | y] = W1 LN2(h)."""
    gy = _mm(layer_norm(h, w["ln2_w"], w["ln2_b"], eps), w["fc1"], prec)
    return h + _mm(silu(gy[:, :ffn]) * gy[:, ffn:], w["fc2"], prec)


@functools.lru_cache(maxsize=None)
def _jitted(zkey, eps, prec, block):
    z = dict(zkey)

    def opened(x, lw):
        w = {n: a.astype(F32) for n, a in lw.items()}
        return w, layer_norm(x, w["ln1_w"], w["ln1_b"], eps)

    @jax.jit
    def embed(table, ids):
        return table[ids].astype(F32)

    @jax.jit
    def mamba_layer(x, lw):
        w, u = opened(x, lw)
        out, y = mamba(u, w, z, prec)
        return _close(x + out, w, eps, prec, z["ffn"]), y

    @jax.jit
    def self_layer(x, lw, lam_0, window):
        w, u = opened(x, lw)
        out, k, v = self_attention(u, w, z, lam_0, window, prec, block)
        return _close(x + out, w, eps, prec, z["ffn"]), k, v

    @jax.jit
    def cross_layer(x, lw, k, v, lam_0):
        w, u = opened(x, lw)
        out = cross_attention(u, w, k, v, z, lam_0, prec, block)
        return _close(x + out, w, eps, prec, z["ffn"])

    @jax.jit
    def gmu_layer(x, lw, memory):
        w, u = opened(x, lw)
        return _close(x + gmu(u, w, memory, prec), w, eps, prec, z["ffn"])

    @jax.jit
    def last_norm(x, positions, nw, nb):
        return layer_norm(x[positions], nw.astype(F32), nb.astype(F32),
                          eps)

    @jax.jit
    def head(y, rows):
        return _mm(y, rows.astype(F32).T, prec)

    return (embed, mamba_layer, self_layer, cross_layer, gmu_layer,
            last_norm, head)


def hidden(w, cfg, ids, prec=None, block=512):
    """[S, H] float32: the last layer's output of one sequence ``ids``
    [1, S], before the final norm, layer by layer."""
    prec = prec or "f32"
    z = dims(cfg)
    fns = _jitted(tuple(sorted(z.items())), float(cfg["layer_norm_eps"]),
                  prec, block)
    embed, mamba_layer, self_layer, cross_layer, gmu_layer = fns[:5]
    x = embed(w["embed"], jnp.asarray(ids)[0])
    memory = kv = None
    for i, lw in enumerate(w["layers"]):
        what = kind(cfg, i)
        if what == "mamba":
            x, y = mamba_layer(x, lw)
            if i == z["memory_layer"]:
                memory = y
        elif what in ("window", "full"):
            win = int(cfg["sliding_window"]) if what == "window" else 0
            x, k, v = self_layer(x, lw, F32(lam0(i)), jnp.int32(win))
            if what == "full":
                kv = (k, v)
        elif what == "cross":
            x = cross_layer(x, lw, *kv, F32(lam0(i)))
        else:
            x = gmu_layer(x, lw, memory)
    return x, fns


def logits_at(w, cfg, ids, positions, prec=None, block=512,
              vocab_block=32768):
    """[len(positions), V] float32 logits of one sequence ``ids`` [1, S]
    at the given positions; the head in blocks of vocabulary columns."""
    x, fns = hidden(w, cfg, ids, prec, block)
    last_norm, head = fns[5:]
    y = last_norm(x, jnp.asarray(positions), w["norm_w"], w["norm_b"])
    v = w["embed"].shape[0]
    return jnp.concatenate(
        [head(y, w["embed"][a:a + vocab_block])
         for a in range(0, v, vocab_block)], -1)
