"""The plain reference: the published forward pass (RMSNorm, rotary
embedding in the half-split convention, grouped-query causal attention
with an optional sliding window, SwiGLU, untied head), its next-token
loss, gradients and AdamW -- in straightforward float32 ``jax.numpy`` at
``highest`` matmul precision, with no kernel, cache or batching.

It imports nothing of the program. Weights are the benchmark's own pytree
(``weights.make``); a layer's weights are upcast one layer at a time, and
attention is computed in blocks of query rows, so that it fits beside
them at the timed sizes.

``prec="int8"`` or ``"fp8"`` is the control: the same arithmetic with
every matmul operand (weights by output channel, activations and cached
keys and values by token) rounded to 8-bit integers or to e4m3 -- the
nearest precisions below the bfloat16 the configurations state. Its
gradient passes straight through the rounding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def fake_int8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    q = jnp.clip(jnp.round(x / s), -127, 127) * s
    return x + jax.lax.stop_gradient(q - x)


def fake_fp8(x, axis):
    """e4m3: scaled so that the largest magnitude is 448, four significant
    bits, a fixed step below 2**-6."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s == 0, 1.0, s)
    xs = x / s
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(xs), 2.0 ** -6)))
    step = jnp.exp2(e - 3)
    q = jnp.round(xs / step) * step * s
    return x + jax.lax.stop_gradient(q - x)


LOW = {"int8": fake_int8, "fp8": fake_fp8}


def _mm(x, w, prec):
    if prec in LOW:
        x, w = LOW[prec](x, -1), LOW[prec](w, 0)
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1; pairs (i, i + D/2) rotate."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    f = jnp.arange(s, dtype=jnp.float32)[:, None] * inv
    sin, cos = jnp.sin(f)[None, :, None], jnp.cos(f)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window, block):
    """q [B,S,H,D], k/v [B,S,KV,D] -> [B,S,H,D]; causal, each query sees
    itself and at most ``window - 1`` positions before it; query heads
    share key heads in groups; query rows go ``block`` at a time."""
    b, s, nh, d = q.shape
    nkv = k.shape[2]
    g = nh // nkv
    block = min(block, s)
    assert s % block == 0, (s, block)
    qb = q.reshape(b, s // block, block, nkv, g, d).transpose(1, 0, 2, 3, 4, 5)
    kpos = jnp.arange(s)

    @jax.checkpoint
    def one(args):
        i, qi = args                              # qi [B, blk, KV, G, D]
        qpos = i * block + jnp.arange(block)
        sc = jnp.einsum("bqkgd,bskd->bkgqs", qi, k, precision=HI) \
            / jnp.sqrt(jnp.float32(d))
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok &= kpos[None, :] > qpos[:, None] - int(window)
        p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), -1)
        return jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HI)

    out = jax.lax.map(one, (jnp.arange(s // block), qb))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, nh, d)


def layer_fn(x, w, *, nh, nkv, eps, theta, window, prec, block):
    w = {n: a.astype(jnp.float32) for n, a in w.items()}
    b, s, h = x.shape
    d = h // nh
    y = rms_norm(x, w["ln1"], eps)
    q = rope(_mm(y, w["q"], prec).reshape(b, s, nh, d), theta)
    k = rope(_mm(y, w["k"], prec).reshape(b, s, nkv, d), theta)
    v = _mm(y, w["v"], prec).reshape(b, s, nkv, d)
    if prec in LOW:
        q, k, v = (LOW[prec](t, -1) for t in (q, k, v))
    a = attention(q, k, v, window, block).reshape(b, s, nh * d)
    x = x + _mm(a, w["o"], prec)
    y = rms_norm(x, w["ln2"], eps)
    ff = jax.nn.silu(_mm(y, w["gate"], prec)) * _mm(y, w["up"], prec)
    return x + _mm(ff, w["down"], prec)


def _layer_kw(cfg, prec, block):
    return dict(nh=cfg["num_attention_heads"],
                nkv=cfg.get("num_key_value_heads")
                or cfg["num_attention_heads"],
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]),
                window=cfg.get("sliding_window"), prec=prec, block=block)


@functools.lru_cache(maxsize=None)
def _jitted(kw_items):
    kw = dict(kw_items)
    eps, prec = kw["eps"], kw["prec"]
    layer = jax.jit(functools.partial(layer_fn, **kw))

    @jax.jit
    def embed(table, ids):
        return table[ids].astype(jnp.float32)

    @jax.jit
    def head(x, positions, norm_w, head_w):
        y = rms_norm(x[0, positions], norm_w.astype(jnp.float32), eps)
        return _mm(y, head_w.astype(jnp.float32), prec)

    return embed, layer, head


def logits_at(w, cfg, ids, positions, prec="f32", block=512):
    """[len(positions), V] float32 logits of one sequence ``ids`` [1, S]
    at the given positions, layer by layer."""
    embed, layer, head = _jitted(tuple(sorted(
        _layer_kw(cfg, prec, block).items(), key=lambda kv: kv[0])))
    x = embed(w["embed"], jnp.asarray(ids))
    for lw in w["layers"]:
        x = layer(x, lw)
    return head(x, jnp.asarray(positions), w["norm"], w["head"])


# -- training ----------------------------------------------------------------


def loss_fn(w, ids, *, kw, chunk=1024, half_batch=False):
    """Mean next-token cross entropy over ids [B, S] (positions 0..S-2
    predict 1..S-1), the head applied ``chunk`` positions at a time.
    ``half_batch`` plants the fault of step 3: the second half of the
    rows (of the positions, where there is one row) left out, the mean
    taken over the rest."""
    eps, prec = kw["eps"], kw["prec"]
    x = w["embed"].astype(jnp.float32)[ids]
    lay = jax.checkpoint(functools.partial(layer_fn, **kw))
    for lw in w["layers"]:
        x = lay(x, lw)
    y = rms_norm(x, w["norm"].astype(jnp.float32), eps)[:, :-1]
    tgt = ids[:, 1:]
    b, n, h = y.shape
    if half_batch:
        if b > 1:
            y, tgt = y[:b // 2], tgt[:b // 2]
        else:
            y, tgt = y[:, :n // 2], tgt[:, :n // 2]
        b, n, h = y.shape
    hw = w["head"].astype(jnp.float32)

    @jax.checkpoint
    def piece(yc, tc):
        lp = jax.nn.log_softmax(_mm(yc, hw, prec), -1)
        return -jnp.sum(jnp.take_along_axis(lp, tc[..., None], -1))

    total = jnp.float32(0)
    for a in range(0, n, chunk):
        total = total + piece(y[:, a:a + chunk], tgt[:, a:a + chunk])
    return total / (b * n)


def adamw(p, g, m, v, step, *, lr, b1, b2, eps, wd):
    """Decoupled weight decay, bias-corrected moments, all float32."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    mh = m / (1 - b1 ** step)
    vh = v / (1 - b2 ** step)
    return p - lr * (mh / (jnp.sqrt(vh) + eps) + wd * p), m, v


def _norms(tree):
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(
        jnp.square(a.astype(jnp.float32)))), tree)


def train_follow(w, cfg, batches, opt, prec="f32", block=512,
                 half_batch=False, frozen=False):
    """Follow ``len(batches)`` optimizer steps from weights ``w``.
    Returns (losses, per-leaf norm of the first gradient, per-leaf norm of
    the parameters' change after the last step) as pytrees of floats.
    ``frozen`` plants the fault of a step that returns its state
    unchanged."""
    kw = _layer_kw(cfg, prec, block)
    hyper = dict(lr=float(opt["learning_rate"]), b1=float(opt["beta1"]),
                 b2=float(opt["beta2"]), eps=float(opt["epsilon"]),
                 wd=float(opt["weight_decay"]))
    grad = jax.jit(jax.value_and_grad(functools.partial(
        loss_fn, kw=kw, half_batch=half_batch)))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def update(p, g, m, v, step):
        out = jax.tree.map(lambda *a: adamw(*a, step, **hyper), p, g, m, v)
        pick = lambda i: jax.tree.map(            # noqa: E731
            lambda t: t[i], out, is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2)

    delta = jax.jit(lambda p, w0: _norms(jax.tree.map(
        lambda a, b: a - b.astype(jnp.float32), p, w0)))
    p = jax.tree.map(lambda a: jnp.array(a, jnp.float32, copy=True), w)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, g1 = [], None
    for i, ids in enumerate(batches):
        loss, g = grad(p, jnp.asarray(ids))
        losses.append(float(loss))
        if i == 0:
            g1 = jax.tree.map(float, jax.jit(_norms)(g))
        if frozen:
            del g
            continue
        p, m, v = update(p, g, m, v, jnp.float32(i + 1))
    return losses, g1, jax.tree.map(float, delta(p, w))
