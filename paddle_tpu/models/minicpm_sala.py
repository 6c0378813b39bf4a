"""MiniCPM-SALA's language model: InfLLM-v2 block-selected sparse attention
(MiniCPM4, arXiv:2506.07900) beside Lightning Attention (MiniMax-01,
arXiv:2501.08313), with MiniCPM's muP constants (MiniCPM-SALA's
``config.json``: ``mixer_types``, ``lightning_*``, ``qk_norm``,
``scale_emb``, ``scale_depth``, ``dim_model_base``, ...).

Pre-norm residual blocks, RMSNorm (eps ``rms_norm_eps``), no biases, a
SwiGLU FFN of ``intermediate_size``. ``x`` is a block's input and ``y =
RMSNorm(x)``; each branch is scaled by ``scale_depth / sqrt(N)`` with
``N`` the published depth (``depth_published``), the embedding by
``scale_emb``, and the head's input divided by ``hidden_size /
dim_model_base``. Layer ``i`` holds published layer ``first_layer + i``,
whose mixer ``mixer_types`` names:

- **minicpm4** (sparse): q ``H`` heads, k and v ``KV`` heads of ``d``;
  q and k through a per-head RMSNorm (``qk_norm``), no position encoding
  (``attn_use_rope`` false). A row at position ``p`` attends with causal
  softmax over the blocks of ``block_size`` tokens it selects (all it
  holds while ``p + 1 <= dense_len``): compressed keys are the means of
  k over ``kernel_size`` positions at stride ``kernel_stride``, and exist
  once their last position is at or before ``p``; each (row, KV group)
  scores them with its query heads (softmax over the existing keys a
  head, ``/ sqrt(d)``, summed over the group); a block's score is the
  largest of the compressed keys that start in it; the row takes the
  first ``init_blocks`` blocks, the blocks that cover its last
  ``window_size`` positions, and the ``topk`` best of the others (ties to
  the lower block; blocks with no score are never taken). Then
  ``o * sigmoid(y W_g)`` (``attn_use_output_gate``) and ``W_o``.
- **lightning-attn**: q, k, v ``lightning_nh`` heads of
  ``lightning_head_dim``, q and k through the per-head RMSNorm and RoPE
  (``lightning_use_rope``, ``rope_theta``, halves rotated); Lightning
  Attention (``ops/lightning_attn.py``) with ``lambda_h = exp(-slope_h *
  (1 - l / (N - 1) + 1e-5))`` for published layer ``l`` and ALiBi slopes
  ``slope_h = 2^(-8 (h + 1) / H)`` (MiniMax-01's rule), scale
  ``1/sqrt(d)``; RMSNorm over the ``H d`` outputs (``use_output_norm``),
  ``* sigmoid(y W_g)`` (``use_output_gate``), ``W_o``.

Serving: ``ServingEngine(model, ragged=True)``. A sparse layer owns a
full pool of one entry a token (its keys and values joined, ``[2, KV,
d]``), with pages of ``block_size`` tokens so that a block is a page, and
an indexer pool beside it (``LayerCache(indexer=...)``: the compressed
keys, ``block_size / kernel_stride`` a page and KV head, written as a row
completes a kernel window). Decode rows gather only the pages their
heads selected (``attention.py::selected_paged_attention``); a prefill
chunk streams its lane's pages under a per-row block mask
(``block_masked_paged_attention``). The layer counts what it selected
on the device (``step.stats``: ``SPARSE_COUNTS`` of
``serving/metrics.py``). A lightning layer keeps a lane state
(``[H, d, d]`` float32, nought at position 0).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..incubate.moe import SwiGLU
from ..nn import Embedding, Layer, LayerList, Linear, RMSNorm
from ..nn import initializer as I
from ..nn.layer import ParamAttr
from ..ops.lightning_attn import lightning_attention

__all__ = ["MiniCPMSalaConfig", "MiniCPMSalaForCausalLM"]

F32 = jnp.float32
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"
# MiniCPM4-8B's published sparse_config (the SALA config gives none)
SPARSE_CONFIG = dict(kernel_size=32, kernel_stride=16, block_size=64,
                     topk=64, init_blocks=1, window_size=2048,
                     dense_len=8192)


@dataclass
class MiniCPMSalaConfig:
    vocab_size: int = 73448
    hidden_size: int = 4096
    intermediate_size: int = 16384
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    lightning_nh: int = 32
    lightning_nkv: int = 32
    lightning_head_dim: int = 128
    lightning_scale: str = "1/sqrt(d)"
    lightning_use_rope: bool = True
    attn_use_rope: bool = False
    qk_norm: bool = True
    use_output_gate: bool = True
    use_output_norm: bool = True
    attn_use_output_gate: bool = True
    attention_bias: bool = False
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    mup_denominator: int = 32
    dim_model_base: int = 256
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 524288
    mixer_types: list = field(default_factory=lambda: [SPARSE] * 32)
    dtype: str = "float32"
    # program settings: the published layer the first held one is, the
    # published depth muP divides by, the block selection's settings
    first_layer: int = 0
    depth_published: int | None = None
    sparse_config: dict = field(default_factory=lambda: dict(SPARSE_CONFIG))

    def __post_init__(self):
        unbuilt = [what for bad, what in (
            (self.attention_bias, "attention_bias"),
            (self.hidden_act != "silu", f"hidden_act={self.hidden_act!r}"),
            (self.attn_use_rope, "attn_use_rope (RoPE in sparse layers)"),
            (not self.lightning_use_rope, "lightning_use_rope=False"),
            (not self.qk_norm, "qk_norm=False"),
            (not (self.use_output_gate and self.use_output_norm
                  and self.attn_use_output_gate),
             "a lightning or sparse layer without its output gate/norm"),
            (self.lightning_nkv != self.lightning_nh,
             "lightning heads sharing keys"),
            (self.lightning_scale != "1/sqrt(d)",
             f"lightning_scale={self.lightning_scale!r}"),
            (self.tie_word_embeddings, "tie_word_embeddings"))
            if bad]
        if unbuilt:
            raise NotImplementedError(
                "MiniCPMSalaConfig: the equations for " + ", ".join(unbuilt)
                + " are not written here")
        self.sparse_config = {**SPARSE_CONFIG, **dict(self.sparse_config)}
        sc = self.sparse_config
        if sc["block_size"] % sc["kernel_stride"]:
            raise ValueError("kernel_stride must divide block_size")
        kinds = self.kinds()
        if len(kinds) != self.num_hidden_layers or not set(kinds) <= {
                SPARSE, LIGHTNING}:
            raise ValueError(f"mixer_types from {self.first_layer} hold "
                             f"{kinds}, not {self.num_hidden_layers} of "
                             f"{SPARSE!r} / {LIGHTNING!r}")
        if self.depth_published is None:
            self.depth_published = len(self.mixer_types)

    def kinds(self) -> list:
        return list(self.mixer_types[self.first_layer:self.first_layer
                                     + self.num_hidden_layers])

    @property
    def residual_scale(self) -> float:
        """``scale_depth / sqrt(N)``, N the published depth."""
        return self.scale_depth / math.sqrt(self.depth_published)

    @property
    def head_divisor(self) -> float:
        return self.hidden_size / self.dim_model_base

    def log_decay(self, i: int) -> np.ndarray:
        """``log lambda_h`` [H] of held layer ``i`` (published layer
        ``first_layer + i``)."""
        h = self.lightning_nh
        slopes = 2.0 ** (-8.0 * np.arange(1, h + 1) / h)
        layer = self.first_layer + i
        return (-slopes * (1 - layer / (self.depth_published - 1) + 1e-5)
                ).astype(np.float32)

    @property
    def blocks_a_row(self) -> int:
        """Pages a decode row's table holds a KV head: the most a row
        selects (the init blocks, the window's blocks, the top-k) or
        attends densely."""
        sc = self.sparse_config
        b = sc["block_size"]
        return max(sc["init_blocks"] + sc["window_size"] // b + 1
                   + sc["topk"], -(-sc["dense_len"] // b))

    @classmethod
    def from_published(cls, config: dict, **program):
        fields = cls.__dataclass_fields__
        kw = {k: v for k, v in config.items() if k in fields}
        kw.update(program)
        return cls(**kw)


# -- what a row reads by construction, on the host (the engine counts it) -

def kernels_scored(cfg: MiniCPMSalaConfig, pos):
    """Compressed keys a row at each of ``pos`` [n] scores to select: the
    ones that exist (their kernel ends at or before it), none where it
    attends densely."""
    sc = cfg.sparse_config
    pos = np.asarray(pos, np.int64)
    return np.where((pos + 1 <= sc["dense_len"])
                    | (pos < sc["kernel_size"] - 1), 0,
                    (pos - sc["kernel_size"] + 1) // sc["kernel_stride"]
                    + 1)


# -- the arithmetic, on arrays ---------------------------------------------

def head_rms(x, w, eps):
    """RMSNorm of every head's last axis, in float32, times ``w``."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(F32)


def rope(x, positions, theta):
    """x [T, H, d] rotated by halves at ``positions`` [T], float32."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None]          # [T, d/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x = x.astype(F32)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def select_blocks(q, ck, pos, cfg: MiniCPMSalaConfig, scale):
    """The blocks each (row, KV head) attends: q [n, s, H, d], ck [n, C,
    KV, d] the lanes' compressed keys in page order (``C = P * E``), pos
    [n, s]. Returns bool [n, s, KV, P]."""
    sc = cfg.sparse_config
    n, s, nh, d = q.shape
    _, c, nkv, _ = ck.shape
    blk, stride = sc["block_size"], sc["kernel_stride"]
    e = blk // stride
    p = c // e
    qg = q.reshape(n, s, nkv, nh // nkv, d).astype(F32)
    score = jnp.einsum("nskgd,nckd->nskgc", qg, ck.astype(F32)) * scale
    start = jnp.arange(c, dtype=jnp.int32) * stride
    exists = (start[None, None] + sc["kernel_size"] - 1
              <= pos[..., None])                               # [n, s, C]
    ex = exists[:, :, None, None]
    score = jnp.where(ex, score, -jnp.inf)
    top = jnp.max(score, -1, keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    w = jnp.where(ex, jnp.exp(score - top), 0.0)
    w = w / jnp.maximum(jnp.sum(w, -1, keepdims=True), 1e-30)
    w = jnp.where(exists[:, :, None], jnp.sum(w, 3), -jnp.inf)  # [n,s,KV,C]
    bscore = jnp.max(w.reshape(n, s, nkv, p, e), -1)
    b = jnp.arange(p, dtype=jnp.int32)
    last = (pos // blk)[..., None, None]
    live = b <= last
    dense = (pos + 1 <= sc["dense_len"])[..., None, None]
    init = b < sc["init_blocks"]
    win = b >= (jnp.maximum(pos - sc["window_size"] + 1, 0) // blk
                )[..., None, None]
    cand = live & ~init & ~win
    val, idx = jax.lax.top_k(jnp.where(cand, bscore, -jnp.inf),
                             min(sc["topk"], p))
    flat = idx.reshape(-1, idx.shape[-1])
    chosen = jnp.zeros((flat.shape[0], p), bool).at[
        jnp.arange(flat.shape[0])[:, None], flat].set(
        jnp.isfinite(val).reshape(flat.shape)).reshape(bscore.shape)
    return live & (dense | init | win | chosen)


# -- mixers ---------------------------------------------------------------

class SparseAttention(Layer):
    """The ``minicpm4`` mixer (InfLLM-v2 block selection)."""

    def __init__(self, cfg: MiniCPMSalaConfig):
        super().__init__()
        self.cfg = cfg
        h, nh, nkv, d = (cfg.hidden_size, cfg.num_attention_heads,
                         cfg.num_key_value_heads, cfg.head_dim)
        self.q_proj = Linear(h, nh * d, bias_attr=False)
        self.k_proj = Linear(h, nkv * d, bias_attr=False)
        self.v_proj = Linear(h, nkv * d, bias_attr=False)
        self.q_norm = self.create_parameter(
            (d,), default_initializer=I.Constant(1.0))
        self.k_norm = self.create_parameter(
            (d,), default_initializer=I.Constant(1.0))
        self.g_proj = Linear(h, nh * d, bias_attr=False)
        self.o_proj = Linear(nh * d, h, bias_attr=False)

    def paged(self, u, step, i):
        """u [T, H] (normed) -> [T, H]; writes layer ``i``'s pool and
        indexer in ``step``."""
        from ..serving.attention import block_masked_paged_attention
        cfg, sc = self.cfg, self.cfg.sparse_config
        nh, nkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                      cfg.head_dim)
        t, eps, scale = u.shape[0], cfg.rms_norm_eps, d ** -0.5
        q = head_rms(jnp.matmul(u, self.q_proj.weight._data).reshape(
            t, nh, d), self.q_norm._data, eps).astype(u.dtype)
        k = head_rms(jnp.matmul(u, self.k_proj.weight._data).reshape(
            t, nkv, d), self.k_norm._data, eps)
        v = jnp.matmul(u, self.v_proj.weight._data).reshape(t, nkv, d)
        pool, index = step.pools[i], step.indexer_pools[i]
        np_, ps, width = pool.shape
        entry = jnp.stack([k.astype(pool.dtype), v.astype(pool.dtype)], 1)
        pool = pool.reshape(np_ * ps, width).at[step.slots].set(
            entry.reshape(t, width)).reshape(np_, ps, width)
        pos = step.positions[0].astype(jnp.int32)
        pt_rows, _, valid = step.per_token
        # the compressed key a row completes: the mean of k over the
        # kernel's positions, read back from the pool
        kern, stride = sc["kernel_size"], sc["kernel_stride"]
        e = index.shape[1]
        c = (pos - (kern - 1)) // stride
        done = valid & (pos >= kern - 1) & ((pos - (kern - 1)) % stride == 0)
        kpos = jnp.maximum(pos[:, None] - jnp.arange(kern), 0)
        phys = (jnp.take_along_axis(pt_rows, kpos // ps, 1) * ps
                + kpos % ps)
        ck = jnp.mean(pool.reshape(np_ * ps, width)[phys][..., :nkv * d]
                      .astype(F32), 1).reshape(t, nkv, d)
        page = jnp.take_along_axis(pt_rows, jnp.maximum(c // e, 0)[:, None],
                                   1)[:, 0]
        at = jnp.where(done, page * e + jnp.maximum(c, 0) % e, 0)
        index = index.reshape(np_ * e, nkv, d).at[at].set(
            ck.astype(index.dtype)).reshape(np_, e, nkv, d)
        step.pools[i], step.indexer_pools[i] = pool, index
        outs, counts = [], jnp.zeros(3, jnp.int32)
        with jax.named_scope("sparse_attention"):
            for rows, lanes, n, s in step.regions():
                tab = step.pt[lanes]                             # [n, P]
                p = tab.shape[1]
                rp = pos[rows].reshape(n, s)
                sel = select_blocks(q[rows].reshape(n, s, nh, d),
                                    index[tab].reshape(n, p * e, nkv, d),
                                    rp, cfg, scale)         # [n,s,KV,P]
                if s == step.k1:
                    outs.append(self._decode(q[rows], pool, tab, sel[:, 0],
                                             rp[:, 0], scale))
                else:
                    outs.append(block_masked_paged_attention(
                        q[rows], pool, tab[0], sel[0], rp[0],
                        step.cl[lanes][0], scale=scale))
                # SPARSE_COUNTS: blocks the live rows attended, the
                # blocks they held, the distinct pages a lane selected
                vr = valid[rows].reshape(n, s)
                took = sel & vr[..., None, None]
                counts = counts + jnp.stack([
                    jnp.sum(took),
                    nkv * jnp.sum(jnp.where(vr, rp // ps + 1, 0)),
                    jnp.sum(jnp.any(took, 1))]).astype(jnp.int32)
        if step.stats is not None:
            step.stats.append(("sparse", counts))
        o = jnp.concatenate(outs).reshape(t, nh * d)
        o = o.astype(F32) * jax.nn.sigmoid(
            jnp.matmul(u, self.g_proj.weight._data).astype(F32))
        return jnp.matmul(o.astype(u.dtype), self.o_proj.weight._data)

    def _decode(self, q, pool, tab, sel, rp, scale):
        """Decode rows (q [n, H, d], one a lane; sel [n, KV, P]; rp [n])
        that gather only the pages their heads selected, in sequence
        order."""
        from ..serving.attention import selected_paged_attention
        p = tab.shape[1]
        b = jnp.arange(p, dtype=jnp.int32)
        prio, blocks = jax.lax.top_k(jnp.where(sel, p - b, 0),
                                     min(self.cfg.blocks_a_row, p))
        pages = jnp.take_along_axis(tab[:, None], blocks, -1)  # [n, KV, j]
        return selected_paged_attention(q, pool, pages, prio > 0, rp,
                                        scale=scale)


class LightningAttention(Layer):
    """The ``lightning-attn`` mixer."""

    def __init__(self, cfg: MiniCPMSalaConfig, layer_idx: int):
        super().__init__()
        self.cfg = cfg
        h, nh, d = cfg.hidden_size, cfg.lightning_nh, cfg.lightning_head_dim
        self.q_proj = Linear(h, nh * d, bias_attr=False)
        self.k_proj = Linear(h, nh * d, bias_attr=False)
        self.v_proj = Linear(h, nh * d, bias_attr=False)
        self.q_norm = self.create_parameter(
            (d,), default_initializer=I.Constant(1.0))
        self.k_norm = self.create_parameter(
            (d,), default_initializer=I.Constant(1.0))
        self.o_norm = self.create_parameter(
            (nh * d,), default_initializer=I.Constant(1.0))
        self.g_proj = Linear(h, nh * d, bias_attr=False)
        self.o_proj = Linear(nh * d, h, bias_attr=False)
        self.log_decay = cfg.log_decay(layer_idx)

    def paged(self, u, step, i):
        cfg = self.cfg
        nh, d, eps = cfg.lightning_nh, cfg.lightning_head_dim, \
            cfg.rms_norm_eps
        t = u.shape[0]
        pos = step.positions[0]

        def heads(lin, norm=None):
            a = jnp.matmul(u, lin.weight._data).reshape(t, nh, d)
            if norm is None:
                return a
            return rope(head_rms(a, norm._data, eps), pos, cfg.rope_theta)

        q, k = heads(self.q_proj, self.q_norm), heads(self.k_proj,
                                                      self.k_norm)
        v = heads(self.v_proj)
        (state,) = step.states[i]
        outs = []
        with jax.named_scope("lightning_layer"):
            for rows, lanes, n, s in step.regions():
                live = jnp.arange(s, dtype=jnp.int32)[None] \
                    < step.ql[lanes][:, None]
                g = jnp.broadcast_to(jnp.asarray(self.log_decay),
                                     (n, s, nh))
                o, state = lightning_attention(
                    q[rows].reshape(n, s, nh, d), k[rows].reshape(n, s, nh, d),
                    v[rows].reshape(n, s, nh, d), g, state, d ** -0.5, live,
                    step.lane_slot[lanes], step.qoff[lanes] == 0)
                outs.append(o.reshape(n * s, nh * d))
        step.states[i] = (state,)
        o = jnp.concatenate(outs)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
            * self.o_norm._data.astype(F32)
        o = o * jax.nn.sigmoid(jnp.matmul(u, self.g_proj.weight._data)
                               .astype(F32))
        return jnp.matmul(o.astype(u.dtype), self.o_proj.weight._data)


# -- the block ------------------------------------------------------------

class MiniCPMSalaDecoderLayer(Layer):
    def __init__(self, cfg: MiniCPMSalaConfig, layer_idx: int):
        super().__init__()
        self.cfg, self.layer_idx = cfg, layer_idx
        self.kind = cfg.kinds()[layer_idx]
        h = cfg.hidden_size
        self.input_layernorm = RMSNorm(h, cfg.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(h, cfg.rms_norm_eps)
        self.self_attn = (SparseAttention(cfg) if self.kind == SPARSE
                          else LightningAttention(cfg, layer_idx))
        self.mlp = SwiGLU(h, cfg.intermediate_size)

    @property
    def paged_cache(self):
        from ..serving.kv_cache import LayerCache
        cfg = self.cfg
        if self.kind == SPARSE:
            nkv, d = cfg.num_key_value_heads, cfg.head_dim
            sc = cfg.sparse_config
            return LayerCache(
                pool="full", n_kv_heads=1, head_dim=2 * nkv * d,
                latent=True,
                indexer=(sc["block_size"] // sc["kernel_stride"], nkv, d))
        nh, d = cfg.lightning_nh, cfg.lightning_head_dim
        return LayerCache(state=(("lightning", (nh, d, d), "float32"),))

    def kernels_scored(self, pos):
        return kernels_scored(self.cfg, pos)

    def paged_forward(self, x, step):
        """The block over the packed step (x [1, T, H] Tensor; ``step``:
        ``serving/attention.py::PagedStep``). Padding rows change no
        state, write to scratch, and their mixer output is nought."""
        if self.kind == SPARSE and step.k1 != 1:
            raise NotImplementedError(
                "a sparse layer's decode rows are one a lane (k1 = 1)")
        a = self.cfg.residual_scale
        u = self.input_layernorm(x)._data[0]
        mix = self.self_attn.paged(u, step, self.layer_idx)
        mix = jnp.where(step.valid[:, None], mix, 0).astype(u.dtype)
        h = x + Tensor(mix[None] * a)
        return h + self.mlp(self.post_attention_layernorm(h)) * a


class _ScaledEmbedding(Embedding):
    def __init__(self, *a, scale, **kw):
        super().__init__(*a, **kw)
        self.scale = scale

    def forward(self, ids):
        return super().forward(ids) * self.scale


class _DividedHead(Linear):
    def __init__(self, *a, divisor, **kw):
        super().__init__(*a, **kw)
        self.divisor = divisor

    def forward(self, x):
        return super().forward(x / self.divisor)


class MiniCPMSalaForCausalLM(Layer):
    """``embed_tokens / layers / norm / lm_head / cfg``: the shape of core
    the serving engine takes; generation is the serving engine's."""

    def __init__(self, cfg: MiniCPMSalaConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = _ScaledEmbedding(
            cfg.vocab_size, cfg.hidden_size, scale=cfg.scale_emb,
            weight_attr=ParamAttr(initializer=I.Normal(0.0, 0.02)))
        self.layers = LayerList([MiniCPMSalaDecoderLayer(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.lm_head = _DividedHead(cfg.hidden_size, cfg.vocab_size,
                                    bias_attr=False,
                                    divisor=cfg.head_divisor)

    def forward(self, input_ids):
        raise NotImplementedError(
            "MiniCPM-SALA runs on the serving path (ServingEngine)")

    def _gen_state_tensors(self):
        return list(self.parameters()) + [b for _, b in
                                          self.named_buffers()]
