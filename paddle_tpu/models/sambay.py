"""A decoder-hybrid-decoder causal LM: the SambaY architecture of
arXiv:2507.06607 (Phi-4-mini-flash-reasoning's), built from Mamba-1
mixers (arXiv:2312.00752), differential attention (arXiv:2410.05258), a
YOCO cross-decoder (arXiv:2405.05254) and Gated Memory Units.

``LN`` is LayerNorm with weight and bias. Layer ``i`` over rows ``x``:

    h  = x + Mixer_i(LN1_i(x))
    x' = h + W2 (silu(g) * y),   [g | y] = W1 LN2_i(h)

and after the last layer a final ``LN``; logits = hidden x E^T with ``E``
the embedding (tied, no bias). With ``m`` = ``memory_layer`` (the depth's
half, an even index), ``Mixer_i`` is

- ``i < m`` even, and ``m`` itself: **Mamba**: ``[xs | z] = W_in u``,
  ``xs = silu(conv(xs))`` (causal depthwise, kernel 4, bias),
  ``[r | B | C] = W_x xs``, ``dt = softplus(W_dt r + b_dt)``, the
  selective scan (``ops/selective_scan.py``), ``out = W_out (y *
  silu(z))``. Layer ``m`` also hands on its scan output ``y`` (before
  the gate): the memory.
- ``i < m`` odd: **differential self-attention**, causal, window
  ``sliding_window``; ``m + 1``: the same with no window: its K and V
  are what the layers behind it attend.
- ``i > m + 1`` even: **Gated Memory Unit** ``W_out (silu(W_in u) *
  memory)``; odd: **differential cross-attention**: queries from ``u``,
  K and V layer ``m + 1``'s, causal.

Differential attention: ``[q | k | v] = Wqkv u + b``; ``q`` in pairs of
adjacent heads ``(q1, q2)``, ``k`` likewise, ``v`` the pair's two heads
joined; query pair ``j`` uses key/value pair ``j // (heads / kv heads)``;
``a_w = softmax(q_w k_w^T / sqrt(d)) v``; ``lam = exp(lq1.lk1) -
exp(lq2.lk2) + lam0(i)``, ``lam0(i) = 0.8 - 0.6 exp(-0.3 i)``; the head's
output ``RMSNorm(a_1 - lam a_2) (1 - lam0(i))``. There is no positional
encoding anywhere.

Serving: ``ServingEngine(model, ragged=True)``. Every layer brings
``paged_forward(x, step)`` (``serving/attention.py::PagedStep``) and says
through ``paged_cache`` what it keeps for a sequence: a Mamba layer a
lane state (the scan state in float32, the convolution's tail), a window
layer a window pool, layer ``m + 1`` the one full pool (a pool's entry is
a token's keys and values joined, ``k | v``), a cross layer nothing (it
reads layer ``m + 1``'s), a GMU nothing. The packed step
computes every layer for every packed token.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autograd import apply
from ..core.tensor import Tensor
from ..nn import Embedding, Layer, LayerList, LayerNorm, Linear
from ..nn import initializer as I
from ..nn.layer import ParamAttr
from ..ops.selective_scan import causal_conv_tail, selective_scan
from .llama import _TiedLMHead

__all__ = ["SambaYConfig", "SambaYForCausalLM"]

F32 = jnp.float32


@dataclass
class SambaYConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    max_position_embeddings: int = 262144
    layer_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    embd_pdrop: float = 0.0
    resid_pdrop: float = 0.0
    # the modeling file's defaults (no published key gives them)
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int | None = None      # ceil(hidden / 16)
    memory_layer: int | None = None       # num_hidden_layers // 2
    subln_eps: float = 1e-5
    dtype: str = "float32"

    def __post_init__(self):
        unbuilt = []
        if self.hidden_act != "silu":
            unbuilt.append(f"hidden_act={self.hidden_act!r}")
        if not self.tie_word_embeddings:
            unbuilt.append("tie_word_embeddings=False (an untied head)")
        if self.mlp_bias or self.lm_head_bias:
            unbuilt.append("mlp_bias / lm_head_bias")
        if self.embd_pdrop or self.resid_pdrop:
            unbuilt.append("embd_pdrop / resid_pdrop > 0")
        if self.mb_per_layer != 2:
            unbuilt.append(f"mb_per_layer={self.mb_per_layer}")
        if unbuilt:
            raise NotImplementedError(
                "SambaYConfig: the equations for " + ", ".join(unbuilt)
                + " are not written here")
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = math.ceil(self.hidden_size / 16)
        if self.memory_layer is None:
            self.memory_layer = self.num_hidden_layers // 2
        m, n = self.memory_layer, self.num_hidden_layers
        if m % 2 or (n - m) % 2 or not 0 <= m < n - 1:
            raise ValueError(
                f"memory_layer={m} of {n} layers: the Mamba layer that "
                "hands on the memory sits at an even index, with the "
                "full-attention layer behind it and whole GMU/cross "
                "pairs after that")
        if self.num_attention_heads % self.num_key_value_heads \
                or self.num_key_value_heads % 2:
            raise ValueError("differential attention pairs adjacent "
                             "heads: heads and kv heads must be even "
                             "and divide")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    def kind(self, i: int) -> str:
        """``mamba`` | ``window`` | ``full`` | ``gmu`` | ``cross``."""
        m = self.memory_layer
        if i <= m:
            return "window" if i % 2 else "mamba"
        if i == m + 1:
            return "full"
        return "cross" if i % 2 else "gmu"

    def lam0(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * i)

    @classmethod
    def from_published(cls, config: dict, **program):
        """The keys of a public ``config.json`` that this class takes,
        with program settings (``dtype``, ``memory_layer``) laid over."""
        fields = cls.__dataclass_fields__
        kw = {k: v for k, v in config.items() if k in fields}
        kw.update(program)
        return cls(**kw)

    @staticmethod
    def tiny(**kw):
        """One pair of each half with the memory and the full layer
        between: Mamba, window, Mamba+memory, full, GMU, cross."""
        return SambaYConfig(**{**dict(
            vocab_size=320, hidden_size=128, intermediate_size=256,
            num_hidden_layers=6, memory_layer=2, num_attention_heads=8,
            num_key_value_heads=4, sliding_window=8,
            max_position_embeddings=256), **kw})


# -- the arithmetic, on arrays ----------------------------------------------

def _silu(a):
    return a * jax.nn.sigmoid(a)


def diff_attention(q, k, v, mask, lam, subln_w, lam0, eps):
    """q [N, S, H, d], k / v [N, T, KV, d] (adjacent heads pair up),
    mask [N, S, T] (True: the key is seen), lam scalar float32,
    subln_w [2d]. Returns [N, S, H * d] float32: per query pair the
    difference of two softmax attentions over the joined values, normed.
    Operands are read as stored; both products accumulate in float32. A
    row that sees no key comes out NaN: the caller discards it."""
    n, s, nh, d = q.shape
    t, nkv = k.shape[1], k.shape[2]
    g, r = nkv // 2, nh // nkv
    qg = q.reshape(n, s, g, r, 2, d)
    kg = k.reshape(n, t, g, 2, d)
    vg = v.reshape(n, t, g, 2 * d)
    sc = jnp.einsum("nsgrwd,ntgwd->ngrwst", qg, kg,
                    preferred_element_type=F32) / math.sqrt(d)
    sc = jnp.where(mask[:, None, None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    o = jnp.einsum("ngrwst,ntge->nsgrwe", p.astype(v.dtype), vg,
                   preferred_element_type=F32)
    a = o[..., 0, :] - lam * o[..., 1, :]                # [N,S,g,r,2d]
    a = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps)
    a = a * subln_w.astype(F32) * (1.0 - lam0)
    return a.reshape(n, s, nh * d)


def _lam(lq1, lk1, lq2, lk2, lam0):
    def e(a, b):
        return jnp.exp(jnp.sum(a.astype(F32) * b.astype(F32)))
    return e(lq1, lk1) - e(lq2, lk2) + lam0


def _causal_mask(s, window):
    pos = jnp.arange(s, dtype=jnp.int32)
    ok = pos[None, :] <= pos[:, None]
    if window:
        ok &= pos[None, :] > pos[:, None] - int(window)
    return ok


def _paged_mask(qoff, cl, s, t, base, window):
    """[N, S, T]: table slot ``u`` of lane ``n`` holds position ``base[n]
    + u``; a row sees it if it is not ahead of the row, inside the lane's
    context and, with a window, at most ``window - 1`` behind."""
    qpos = qoff[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    kpos = jnp.arange(t, dtype=jnp.int32)[None]
    if base is not None:
        kpos = kpos + base[:, None]
    ok = (kpos[:, None, :] <= qpos[:, :, None]) \
        & (kpos[:, None, :] < cl[:, None, None])
    if window:
        ok &= kpos[:, None, :] > qpos[:, :, None] - int(window)
    return ok


def paged_diff_attention(q, pool, table, cl, qoff, base, window, lam,
                         subln_w, lam0, eps, n_kv_heads):
    """One region of the packed step: q [N, S, H, d]; pool [NP, PS, 2 *
    KV * d], a token's keys and values joined in one entry (``k | v``:
    a width the device tiles without padding, where ``[.., KV, d]`` with
    d = 64 is laid out anew on the way in and out of every step); table
    [N, P] the lanes' pages (gathered once a lane, keys and values in
    one gather); cl / qoff [N]; base [N] or None (a window pool's table
    starts at ``base``). Returns [N, S, H * d] float32."""
    n, s = q.shape[:2]
    ps, width = pool.shape[1:]
    t = table.shape[1] * ps
    ctx = pool[table].reshape(n, t, width)
    kw = width // 2
    k = ctx[..., :kw].reshape(n, t, n_kv_heads, kw // n_kv_heads)
    v = ctx[..., kw:].reshape(n, t, n_kv_heads, kw // n_kv_heads)
    return diff_attention(q, k, v, _paged_mask(qoff, cl, s, t, base,
                                               window),
                          lam, subln_w, lam0, eps)


def _scatter(pages, slots, k, v):
    """Write the rows' entries ``k | v`` (k, v [T, KV, d]) at the flat
    ``slots [T]`` of a pool [NP, PS, 2 * KV * d]."""
    npg, ps, width = pages.shape
    t = k.shape[0]
    rows = jnp.concatenate([k.reshape(t, -1), v.reshape(t, -1)], -1)
    return pages.reshape(npg * ps, width).at[slots].set(
        rows.astype(pages.dtype)).reshape(pages.shape)


# -- mixers -----------------------------------------------------------------

class MambaMixer(Layer):
    def __init__(self, cfg: SambaYConfig):
        super().__init__()
        self.cfg = cfg
        h, di, n = cfg.hidden_size, cfg.d_inner, cfg.mamba_d_state
        k, r = cfg.mamba_d_conv, cfg.mamba_dt_rank
        self.in_proj = Linear(h, 2 * di, bias_attr=False)
        self.conv_weight = self.create_parameter(
            (k, di), default_initializer=I.Normal(0.0, 0.02))
        self.conv_bias = self.create_parameter((di,), is_bias=True)
        self.x_proj = Linear(di, r + 2 * n, bias_attr=False)
        step = np.exp(np.linspace(math.log(1e-3), math.log(1e-1), di))
        self.dt_proj = Linear(r, di, bias_attr=ParamAttr(
            initializer=I.Assign(
                np.log(np.expm1(step)).astype(np.float32))))
        # A = -exp(A_log), held [n, d_inner]: the paper's [d_inner, n]
        # with the wide axis minor
        self.A_log = self.create_parameter(
            (n, di), default_initializer=I.Assign(np.broadcast_to(
                np.log(np.arange(1, n + 1, dtype=np.float32))[:, None],
                (n, di)).copy()))
        self.D = self.create_parameter(
            (di,), default_initializer=I.Constant(1.0))
        self.out_proj = Linear(di, h, bias_attr=False)

    def _scan_inputs(self, xs_conv):
        """silu(conv) [.., d_inner] -> (xs, dt, B, C) of the scan."""
        cfg = self.cfg
        r, n = cfg.mamba_dt_rank, cfg.mamba_d_state
        xs = _silu(xs_conv)
        rbc = jnp.matmul(xs.astype(self.x_proj.weight._data.dtype),
                         self.x_proj.weight._data)
        dt = jnp.matmul(rbc[..., :r], self.dt_proj.weight._data)
        dt = jax.nn.softplus(dt.astype(F32)
                             + self.dt_proj.bias._data.astype(F32))
        return xs, dt, rbc[..., r:r + n], rbc[..., r + n:]

    def _a(self):
        return -jnp.exp(self.A_log._data.astype(F32))

    def run(self, u, tail, state, live=None, n_live=None):
        """u [N, S, H] (normed, an array) from the lanes' ``tail`` [N,
        K-1, d_inner] and ``state`` [N, n, d_inner] -> (out [N, S, H],
        scan output y [N, S, d_inner], new tail, new state)."""
        di = self.cfg.d_inner
        xz = jnp.matmul(u, self.in_proj.weight._data)
        conv, tail = causal_conv_tail(
            xz[..., :di], tail, self.conv_weight._data,
            self.conv_bias._data, n_live)
        xs, dt, b, c = self._scan_inputs(conv)
        y, state = selective_scan(xs.astype(u.dtype), dt, self._a(), b, c,
                                  self.D._data, state, live)
        gated = y * _silu(xz[..., di:].astype(F32)).astype(y.dtype)
        return (jnp.matmul(gated, self.out_proj.weight._data), y, tail,
                state)

    def forward(self, u):
        """u [B, S, H] Tensor -> (out, y): a whole sequence from a zero
        state. (The mixers' ``forward`` read their weights where they
        lie: inference only; the scan's backward and a training path
        are not built.)"""
        cfg = self.cfg

        def f(u):
            b = u.shape[0]
            out, y, _, _ = self.run(
                u, jnp.zeros((b, cfg.mamba_d_conv - 1, cfg.d_inner),
                             u.dtype),
                jnp.zeros((b, cfg.mamba_d_state, cfg.d_inner), F32))
            return out, y
        return apply(f, u, name="mamba_mixer")


class DiffAttention(Layer):
    """Differential attention; ``cross`` has a query projection alone
    and attends the keys and values another layer wrote."""

    def __init__(self, cfg: SambaYConfig, layer_idx: int, cross=False):
        super().__init__()
        self.cfg, self.cross = cfg, cross
        self.lam0 = cfg.lam0(layer_idx)
        h, d = cfg.hidden_size, cfg.head_dim
        self.kv_width = cfg.num_key_value_heads * d
        if cross:
            self.Wq = Linear(h, h)
        else:
            self.Wqkv = Linear(h, h + 2 * self.kv_width)
        self.out_proj = Linear(h, h)
        lam = I.Normal(0.0, 0.1)
        for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, name, self.create_parameter(
                (d,), default_initializer=lam))
        self.subln_weight = self.create_parameter(
            (2 * d,), default_initializer=I.Constant(1.0))

    def lam(self):
        return _lam(self.lambda_q1._data, self.lambda_k1._data,
                    self.lambda_q2._data, self.lambda_k2._data, self.lam0)

    def qkv(self, u):
        """u [..., H] array -> (q [..., heads, d], k, v [..., kv, d] or
        None for a cross layer)."""
        cfg = self.cfg
        d, nh, nkv = cfg.head_dim, cfg.num_attention_heads, \
            cfg.num_key_value_heads
        lin = self.Wq if self.cross else self.Wqkv
        y = jnp.matmul(u, lin.weight._data) + lin.bias._data
        h = cfg.hidden_size
        q = y[..., :h].reshape(y.shape[:-1] + (nh, d))
        if self.cross:
            return q, None, None
        kw = self.kv_width
        return (q, y[..., h:h + kw].reshape(y.shape[:-1] + (nkv, d)),
                y[..., h + kw:].reshape(y.shape[:-1] + (nkv, d)))

    def out(self, a, dtype):
        return jnp.matmul(a.astype(dtype), self.out_proj.weight._data) \
            + self.out_proj.bias._data

    def attend(self, q, k, v, mask):
        return diff_attention(q, k, v, mask, self.lam(),
                              self.subln_weight._data, self.lam0,
                              self.cfg.subln_eps)

    def forward(self, u, kv=None, window=None):
        """u [B, S, H] Tensor; ``kv``: the (k, v) Tensors another layer
        made (a cross layer). Returns (out, k, v)."""
        def f(u, *rest):
            q, k, v = self.qkv(u)
            if self.cross:
                k, v = rest[0], rest[1]
            mask = jnp.broadcast_to(_causal_mask(u.shape[1], window),
                                    (u.shape[0],) + (u.shape[1],) * 2)
            return self.out(self.attend(q, k, v, mask), u.dtype), k, v
        return apply(f, u, *(kv or ()), name="diff_attention")


class GatedMemoryUnit(Layer):
    def __init__(self, cfg: SambaYConfig):
        super().__init__()
        self.in_proj = Linear(cfg.hidden_size, cfg.d_inner,
                              bias_attr=False)
        self.out_proj = Linear(cfg.d_inner, cfg.hidden_size,
                               bias_attr=False)

    def run(self, u, memory):
        g = _silu(jnp.matmul(u, self.in_proj.weight._data).astype(F32))
        return jnp.matmul((g * memory.astype(F32)).astype(u.dtype),
                          self.out_proj.weight._data)

    def forward(self, u, memory):
        return apply(self.run, u, memory, name="gmu")


class GatedMLP(Layer):
    """``W2 (silu(g) * y)``, ``[g | y] = W1 x``: one fused gate/up."""

    def __init__(self, cfg: SambaYConfig):
        super().__init__()
        self.width = cfg.intermediate_size
        self.fc1 = Linear(cfg.hidden_size, 2 * self.width,
                          bias_attr=False)
        self.fc2 = Linear(self.width, cfg.hidden_size, bias_attr=False)

    def forward(self, x):
        def f(x, w1, w2):
            gy = jnp.matmul(x, w1)
            a = _silu(gy[..., :self.width].astype(F32)) \
                * gy[..., self.width:].astype(F32)
            return jnp.matmul(a.astype(x.dtype), w2)
        return apply(f, x, self.fc1.weight, self.fc2.weight,
                     name="gated_mlp")


# -- the block ------------------------------------------------------------------

class SambaYDecoderLayer(Layer):
    def __init__(self, cfg: SambaYConfig, layer_idx: int):
        super().__init__()
        self.cfg, self.layer_idx = cfg, layer_idx
        self.kind = cfg.kind(layer_idx)
        self.input_layernorm = LayerNorm(cfg.hidden_size,
                                         cfg.layer_norm_eps)
        self.post_attention_layernorm = LayerNorm(cfg.hidden_size,
                                                  cfg.layer_norm_eps)
        if self.kind == "mamba":
            self.mixer = MambaMixer(cfg)
        elif self.kind == "gmu":
            self.mixer = GatedMemoryUnit(cfg)
        else:
            self.mixer = DiffAttention(cfg, layer_idx,
                                       cross=self.kind == "cross")
        self.mlp = GatedMLP(cfg)

    @property
    def hands_on_memory(self):
        return self.layer_idx == self.cfg.memory_layer

    # what a serving cache keeps for this layer, a sequence
    @property
    def paged_cache(self):
        from ..serving.kv_cache import LayerCache
        cfg = self.cfg
        # a token's keys and values joined in one entry of a pool
        kv = dict(n_kv_heads=1, latent=True,
                  head_dim=2 * cfg.num_key_value_heads * cfg.head_dim)
        if self.kind == "mamba":
            return LayerCache(state=(
                ("scan", (cfg.mamba_d_state, cfg.d_inner), "float32"),
                ("conv_tail", (cfg.mamba_d_conv - 1, cfg.d_inner), None)))
        if self.kind == "window":
            return LayerCache(pool="window", window=cfg.sliding_window,
                              **kv)
        if self.kind == "full":
            return LayerCache(pool="full", **kv)
        if self.kind == "cross":
            return LayerCache(reads=cfg.memory_layer + 1)
        return LayerCache()

    def forward(self, x, shared):
        """x [B, S, H]; ``shared``, a dict, carries the memory and layer
        ``m + 1``'s keys and values to the layers behind."""
        u = self.input_layernorm(x)
        if self.kind == "mamba":
            a, y = self.mixer(u)
            if self.hands_on_memory:
                shared["memory"] = y
        elif self.kind == "gmu":
            a = self.mixer(u, shared["memory"])
        elif self.kind == "cross":
            a, _, _ = self.mixer(u, kv=shared["kv"])
        else:
            a, k, v = self.mixer(
                u, window=self.cfg.sliding_window
                if self.kind == "window" else None)
            if self.kind == "full":
                shared["kv"] = (k, v)
        h = x + a
        return h + self.mlp(self.post_attention_layernorm(h))

    # -- the packed step -----------------------------------------------------
    def paged_forward(self, x, step):
        """The block over the packed step (x [1, T, H] Tensor; ``step``:
        ``serving/attention.py::PagedStep``). Padding rows change no
        state, write to scratch, and their mixer output is nought."""
        u = self.input_layernorm(x)._data[0]               # [T, H]
        mix = getattr(self, f"_paged_{self.kind}")(u, step)
        mix = jnp.where(step.valid[:, None], mix, 0).astype(u.dtype)
        h = x + Tensor(mix[None])
        return h + self.mlp(self.post_attention_layernorm(h))

    def _paged_mamba(self, u, step):
        i = self.layer_idx
        scan, tail = step.states[i]
        outs, mems = [], []
        with jax.named_scope("ssm_layer"):
            for rows, lanes, n, s in step.regions():
                slot = step.lane_slot[lanes]
                ql, fresh = step.ql[lanes], step.qoff[lanes] == 0
                # a lane whose first row starts a sequence: nought
                s0 = jnp.where(fresh[:, None, None], 0, scan[slot])
                t0 = jnp.where(fresh[:, None, None], 0, tail[slot])
                live = jnp.arange(s, dtype=jnp.int32)[None] < ql[:, None]
                out, y, t1, s1 = self.mixer.run(
                    u[rows].reshape(n, s, -1), t0, s0, live, ql)
                scan = scan.at[slot].set(s1)
                tail = tail.at[slot].set(t1)
                outs.append(out.reshape(n * s, -1))
                mems.append(y.reshape(n * s, -1))
        step.states[i] = (scan, tail)
        if self.hands_on_memory:
            step.carried["memory"] = jnp.concatenate(mems)
        return jnp.concatenate(outs)

    def _paged_gmu(self, u, step):
        with jax.named_scope("gmu"):
            return self.mixer.run(u, step.carried["memory"])

    def _attend_regions(self, q, step, pool, table, base, window):
        at = self.mixer
        parts = []
        for rows, lanes, n, s in step.regions():
            parts.append(paged_diff_attention(
                q[rows].reshape((n, s) + q.shape[1:]), pool, table[lanes],
                step.cl[lanes], step.qoff[lanes],
                None if base is None else base[lanes], window, at.lam(),
                at.subln_weight._data, at.lam0, self.cfg.subln_eps,
                self.cfg.num_key_value_heads).reshape(n * s, -1))
        return at.out(jnp.concatenate(parts), q.dtype)

    def _paged_window(self, u, step):
        i = self.layer_idx
        with jax.named_scope("window_attention"):
            q, k, v = self.mixer.qkv(u)
            pool = _scatter(step.window_pools[i], step.wslots, k, v)
            step.window_pools[i] = pool
            return self._attend_regions(q, step, pool, step.wpt,
                                        step.wbase,
                                        self.cfg.sliding_window)

    def _paged_full(self, u, step):
        i = self.layer_idx
        with jax.named_scope("full_attention"):
            q, k, v = self.mixer.qkv(u)
            pool = _scatter(step.pools[i], step.slots, k, v)
            step.pools[i] = pool
            return self._attend_regions(q, step, pool, step.pt, None,
                                        None)

    def _paged_cross(self, u, step):
        with jax.named_scope("cross_attention"):
            q, _, _ = self.mixer.qkv(u)
            return self._attend_regions(
                q, step, step.pools[self.paged_cache.reads], step.pt,
                None, None)


class SambaYForCausalLM(Layer):
    """``embed_tokens / layers / norm / lm_head / cfg``: the shape of
    core the serving engine takes. Generation is the serving engine's;
    ``GenerationMixin``'s static-cache ``generate()`` has no lane
    state."""

    def __init__(self, cfg: SambaYConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=ParamAttr(initializer=I.Normal(0.0, 0.02)))
        self.layers = LayerList([SambaYDecoderLayer(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = LayerNorm(cfg.hidden_size, cfg.layer_norm_eps)
        self.lm_head = _TiedLMHead(self.embed_tokens.weight)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        shared = {}
        for layer in self.layers:
            x = layer(x, shared)
        return self.lm_head(self.norm(x))

    def _gen_state_tensors(self):
        """Parameters and buffers in a fixed order: the weight arguments
        of the engine's compiled step."""
        return list(self.parameters()) + [b for _, b in
                                          self.named_buffers()]
