"""A causal LM with latent attention (MLA) and dropless sparse experts:
the DeepSeek-V3 key set, which several public 2025-26 models carry in
their ``config.json`` (``kv_lora_rank``, ``q_lora_rank``,
``qk_nope_head_dim`` / ``qk_rope_head_dim`` / ``v_head_dim``,
``n_routed_experts``, ``num_experts_per_tok``, ``n_shared_experts``,
``first_k_dense_replace``, ``scoring_func: sigmoid``,
``topk_method: noaux_tc``).

Per token, pre-norm residual, RMSNorm, no biases:

- **Latent attention.** ``c_q = RMSNorm(y W_qa)``, ``q = c_q W_qb`` (or
  ``q = y W_q`` where ``q_lora_rank`` is null: a full-rank query) split
  per head into ``q_nope | q_rope``; ``[c | k_r] = y W_kva``,
  ``c_kv = RMSNorm(c)``, ``k_rope = RoPE(k_r)`` (one for all heads, RoPE
  over interleaved pairs ``(2i, 2i+1)``); ``[k_nope_h | v_h] = c_kv W_kvb``.
  Scores ``(q_nope_h . k_nope_h + q_rope_h . k_rope) / sqrt(nope + rope)``,
  causal, softmax in float32. A cache holds ``[c_kv | k_rope]`` a token a
  layer, once. :meth:`LatentAttention.forward` computes the *expanded*
  (published) form; :meth:`LatentAttention.paged_forward` attends a page
  pool of latent entries in the *absorbed* form (``q_nope_h W_UK_h^T``
  against ``c_kv``, ``sum p c_kv`` through ``W_UV_h``), a re-association
  of the same sums.
- **Leading dense layers** (``first_k_dense_replace``): SwiGLU.
- **Expert layers**: :class:`~paddle_tpu.incubate.moe.DroplessMoE`
  (group-limited routing where ``n_group`` > 1).
- The multi-token-prediction module (``num_nextn_predict_layers``) is not
  instantiated: it is a training loss or a self-draft (ROADMAP R5).

Serving: ``ServingEngine(model, ragged=True)``; the engine asks each
layer for its ``paged_forward(x, step)`` (``serving/attention.py::
PagedStep``: the one protocol of layers that bring their own) over the
latent page pool it owns (``paged_cache``).
"""
from __future__ import annotations

import functools
import types
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.autograd import apply, mark_stable
from ..core.tensor import Tensor
from ..incubate.moe import DroplessMoE, SwiGLU
from ..nn import Embedding, Layer, LayerList, Linear, RMSNorm

__all__ = ["LatentMoEConfig", "LatentAttention", "LatentMoEDecoderLayer",
           "LatentMoEForCausalLM"]


@dataclass
class LatentMoEConfig:
    vocab_size: int = 129280
    hidden_size: int = 2048
    intermediate_size: int = 7168
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int | None = 1536       # None: a full-rank query
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    rope_theta: float = 32000000.0
    rope_interleave: bool = True
    rope_scaling: dict | None = None
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 131072
    tie_word_embeddings: bool = False
    num_nextn_predict_layers: int = 0
    dtype: str = "float32"
    # (first, count): the routed experts this program holds; the router
    # keeps its published width. None = all of them.
    experts_held: tuple | None = None

    def __post_init__(self):
        unbuilt = []
        if self.scoring_func != "sigmoid":
            unbuilt.append(f"scoring_func={self.scoring_func!r}")
        if self.topk_method != "noaux_tc":
            unbuilt.append(f"topk_method={self.topk_method!r}")
        if self.rope_scaling:
            unbuilt.append("rope_scaling")
        if not self.rope_interleave:
            unbuilt.append("rope_interleave=False")
        if self.tie_word_embeddings:
            unbuilt.append("tie_word_embeddings")
        if self.moe_layer_freq != 1:
            unbuilt.append(f"moe_layer_freq={self.moe_layer_freq}")
        if unbuilt:
            raise NotImplementedError(
                "LatentMoEConfig: the equations for "
                + ", ".join(unbuilt) + " are not written here")
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)

    @property
    def latent_dim(self) -> int:
        """Values a token a layer holds in a cache: ``c_kv | k_rope``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @classmethod
    def from_published(cls, config: dict, **program):
        """The keys of a public ``config.json`` that this class takes
        (the rest say nothing about a shape), with program settings
        (``dtype``, ``experts_held``) laid over them."""
        fields = cls.__dataclass_fields__
        kw = {k: v for k, v in config.items() if k in fields}
        kw.update(program)
        return cls(**kw)

    @staticmethod
    def tiny(**kw):
        return LatentMoEConfig(**{**dict(
            vocab_size=320, hidden_size=128, intermediate_size=256,
            moe_intermediate_size=64, num_hidden_layers=3,
            num_attention_heads=4, q_lora_rank=48, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=16, num_experts_per_tok=4,
            max_position_embeddings=256), **kw})


# -- the arithmetic, on arrays ----------------------------------------------

def rope_interleaved(x, positions, theta):
    """x [..., D] at integer ``positions`` (broadcast against x's leading
    dims, one trailing head axis allowed): pairs (2i, 2i+1) rotate by
    ``pos * theta ** (-2i / D)``, computed in float32."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    f = positions.astype(jnp.float32)[..., None] * inv
    sin, cos = jnp.sin(f), jnp.cos(f)
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.reshape(xf.shape).astype(x.dtype)


def _rms(a, w, eps):
    a32 = a.astype(jnp.float32)
    ms = jnp.mean(a32 * a32, -1, keepdims=True)
    return (a32 * jax.lax.rsqrt(ms + eps)).astype(a.dtype) * w


def _split_q(q, cfg):
    nh, dn, dr = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                  cfg.qk_rope_head_dim)
    q = q.reshape(q.shape[:-1] + (nh, dn + dr))
    return q[..., :dn], q[..., dn:]


def _latent_entry(kv, ln_w, positions, cfg):
    """``y W_kva`` [..., rank + rope] -> the cache entry ``c_kv | k_rope``."""
    r = cfg.kv_lora_rank
    c = _rms(kv[..., :r], ln_w, cfg.rms_norm_eps)
    k_r = rope_interleaved(kv[..., r:], positions, cfg.rope_theta)
    return jnp.concatenate([c, k_r], -1)


def _w_kvb(w, cfg):
    """W_kvb [rank, nh * (nope + v)] -> (W_UK [rank, nh, nope],
    W_UV [rank, nh, v])."""
    nh, dn = cfg.num_attention_heads, cfg.qk_nope_head_dim
    w = w.reshape(cfg.kv_lora_rank, nh, dn + cfg.v_head_dim)
    return w[..., :dn], w[..., dn:]


def expanded_attention(q, kv, ln_w, w_kvb, positions, cfg):
    """The published form over one contiguous causal sequence:
    q [B,S,nh*(nope+rope)], kv [B,S,rank+rope], positions [B,S] ->
    [B,S,nh*v]. Keys and values are expanded per head from ``c_kv``."""
    r = cfg.kv_lora_rank
    q_nope, q_rope = _split_q(q, cfg)
    q_rope = rope_interleaved(q_rope, positions[..., None], cfg.rope_theta)
    entry = _latent_entry(kv, ln_w, positions, cfg)
    w_uk, w_uv = _w_kvb(w_kvb, cfg)
    k_nope = jnp.einsum("bsc,chd->bshd", entry[..., :r], w_uk)
    v = jnp.einsum("bsc,chd->bshd", entry[..., :r], w_uv)
    sc = (jnp.einsum("bqhd,bkhd->bhqk", q_nope, k_nope,
                     preferred_element_type=jnp.float32)
          + jnp.einsum("bqhd,bkd->bhqk", q_rope, entry[..., r:],
                       preferred_element_type=jnp.float32))
    sc = sc / jnp.sqrt(jnp.float32(cfg.qk_nope_head_dim
                                   + cfg.qk_rope_head_dim))
    ok = positions[:, None, :, None] >= positions[:, None, None, :]
    p = jax.nn.softmax(jnp.where(ok, sc, -jnp.inf), -1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(out.shape[:2] + (-1,)).astype(q.dtype)


def absorbed_paged_attention(q, kv, ln_w, w_kvb, positions, pool, slots,
                             regions, cfg):
    """The absorbed form over a latent page pool: q [T, nh*(nope+rope)],
    kv [T, rank+rope], positions [T] (absolute), pool [NP, PS,
    rank+rope], slots [T] (flat slot each row's entry is written to).
    ``regions``: ``(rows, pt, qoff, cl, s)`` a slice of the packed rows
    that is ``N`` lanes of ``s`` rows each, with each lane's page-table
    row ``pt [N, P]``, its first row's position ``qoff [N]`` and the keys
    it may see ``cl [N]``, itself included. A lane's padded table is
    gathered once for its ``s`` rows; one row a lane (a table row a
    token) gathers it once a token. Returns ([T, nh*v], pool). The pool's
    entries are read as stored; the two contractions over the context
    accumulate in float32."""
    r = cfg.kv_lora_rank
    npg, ps, width = pool.shape
    entry = _latent_entry(kv, ln_w, positions, cfg)
    pool = pool.reshape(npg * ps, width).at[slots].set(
        entry.astype(pool.dtype)).reshape(npg, ps, width)
    q_nope, q_rope = _split_q(q, cfg)
    q_rope = rope_interleaved(q_rope, positions[:, None], cfg.rope_theta)
    w_uk, w_uv = _w_kvb(w_kvb, cfg)
    q_abs = jnp.einsum("thd,chd->thc", q_nope, w_uk,
                       preferred_element_type=jnp.float32)
    qq = jnp.concatenate([q_abs, q_rope.astype(jnp.float32)],
                         -1).astype(pool.dtype)            # [T, nh, width]
    nh = qq.shape[1]
    scale = jnp.sqrt(jnp.float32(cfg.qk_nope_head_dim
                                 + cfg.qk_rope_head_dim))
    parts = []
    for rows, pt, qoff, cl, s in regions:
        # a lane's s rows x nh heads as one axis: with one row a token
        # this is the per-token form's own program, operation for
        # operation
        n, t = pt.shape[0], pt.shape[1] * ps
        ctx = pool[pt].reshape(n, t, width)                # [N, S, width]
        sc = jnp.einsum("nmc,ntc->nmt",
                        qq[rows].reshape(n, s * nh, width), ctx,
                        preferred_element_type=jnp.float32) / scale
        qpos = qoff[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        kpos = jnp.arange(t, dtype=jnp.int32)[None, None]
        ok = (kpos <= qpos[:, :, None]) & (kpos < cl[:, None, None])
        sc = jnp.where(ok[:, :, None], sc.reshape(n, s, nh, t), -jnp.inf)
        p = jax.nn.softmax(sc.reshape(n, s * nh, t), -1)
        o = jnp.einsum("nmt,ntc->nmc", p.astype(pool.dtype), ctx[..., :r],
                       preferred_element_type=jnp.float32)
        parts.append(o.reshape(n * s, nh, r))
    o_lat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    out = jnp.einsum("thc,chd->thd", o_lat.astype(w_uv.dtype), w_uv,
                     preferred_element_type=jnp.float32)
    return out.reshape(out.shape[0], -1).astype(q.dtype), pool


@functools.lru_cache(maxsize=16)
def _expanded_fn(cfg_key):
    cfg = types.SimpleNamespace(**dict(cfg_key))
    return mark_stable(
        lambda q, kv, ln_w, w, pos: expanded_attention(q, kv, ln_w, w,
                                                       pos, cfg))


def _cfg_key(cfg):
    """The fields the attention arithmetic reads, hashable."""
    names = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
             "qk_rope_head_dim", "v_head_dim", "rope_theta", "rms_norm_eps")
    return tuple((n, getattr(cfg, n)) for n in names)


# -- layers ---------------------------------------------------------------------

class LatentAttention(Layer):
    def __init__(self, cfg: LatentMoEConfig):
        super().__init__()
        self.cfg = cfg
        h, nh = cfg.hidden_size, cfg.num_attention_heads
        qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        if cfg.q_lora_rank:
            self.q_a_proj = Linear(h, cfg.q_lora_rank, bias_attr=False)
            self.q_a_layernorm = RMSNorm(cfg.q_lora_rank, cfg.rms_norm_eps)
            self.q_b_proj = Linear(cfg.q_lora_rank, nh * qk,
                                   bias_attr=False)
        else:
            self.q_proj = Linear(h, nh * qk, bias_attr=False)
        self.kv_a_proj_with_mqa = Linear(h, cfg.latent_dim, bias_attr=False)
        self.kv_a_layernorm = RMSNorm(cfg.kv_lora_rank, cfg.rms_norm_eps)
        self.kv_b_proj = Linear(
            cfg.kv_lora_rank, nh * (cfg.qk_nope_head_dim + cfg.v_head_dim),
            bias_attr=False)
        self.o_proj = Linear(nh * cfg.v_head_dim, h, bias_attr=False)

    def _q_kv(self, y):
        q = (self.q_b_proj(self.q_a_layernorm(self.q_a_proj(y)))
             if self.cfg.q_lora_rank else self.q_proj(y))
        return q, self.kv_a_proj_with_mqa(y)

    def forward(self, y, position_ids=None):
        """y [B, S, H] (normed) -> [B, S, H]: the expanded form."""
        b, s = y.shape[0], y.shape[1]
        if position_ids is None:
            position_ids = Tensor(jnp.broadcast_to(
                jnp.arange(s, dtype=jnp.int32), (b, s)))
        q, kv = self._q_kv(y)
        out = apply(_expanded_fn(_cfg_key(self.cfg)), q, kv,
                    self.kv_a_layernorm.weight, self.kv_b_proj.weight,
                    position_ids.detach(), name="latent_attention")
        return self.o_proj(out)

    def paged_forward(self, y, positions, pool, slots, regions):
        """y [B, S, H] (normed) with B*S packed tokens in row-major
        order; the other operands as :func:`absorbed_paged_attention`
        takes them. Returns (Tensor [B,S,H], pool)."""
        b, s = y.shape[0], y.shape[1]
        q, kv = self._q_kv(y)
        out, pool = absorbed_paged_attention(
            q._data.reshape(b * s, -1), kv._data.reshape(b * s, -1),
            self.kv_a_layernorm.weight._data, self.kv_b_proj.weight._data,
            positions.reshape(-1), pool, slots, regions, self.cfg)
        return self.o_proj(Tensor(out.reshape(b, s, -1))), pool


class LatentMoEDecoderLayer(Layer):
    def __init__(self, cfg: LatentMoEConfig, layer_idx: int):
        super().__init__()
        self.cfg = cfg
        self.layer_idx = layer_idx
        self.input_layernorm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = LatentAttention(cfg)
        self.post_attention_layernorm = RMSNorm(cfg.hidden_size,
                                                cfg.rms_norm_eps)
        self.is_moe = layer_idx >= cfg.first_k_dense_replace
        if self.is_moe:
            self.mlp = DroplessMoE(
                cfg.hidden_size, cfg.moe_intermediate_size,
                cfg.n_routed_experts, cfg.num_experts_per_tok,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob,
                shared_width=cfg.n_shared_experts
                * cfg.moe_intermediate_size,
                experts_held=cfg.experts_held, n_group=cfg.n_group,
                topk_group=cfg.topk_group)
        else:
            self.mlp = SwiGLU(cfg.hidden_size, cfg.intermediate_size)

    # what a serving cache holds for this layer: one latent entry a
    # token, in a full pool of its own
    @property
    def paged_cache(self):
        from ..serving.kv_cache import LayerCache
        return LayerCache(pool="full", n_kv_heads=1,
                          head_dim=self.cfg.latent_dim, latent=True)

    def forward(self, x, position_ids=None):
        h = x + self.self_attn(self.input_layernorm(x), position_ids)
        return h + self.mlp(self.post_attention_layernorm(h))

    def paged_forward(self, x, step):
        """The block over its latent page pool (the serving engine's
        call; ``step``: ``serving/attention.py::PagedStep``). The
        routing counts of an expert layer go to ``step.stats``
        (:meth:`DroplessMoE.forward_counted`, over the rows that are
        not padding)."""
        pt_tok, cl_tok, valid = step.per_token
        # a region of one row a token: each gathers its lane's table
        regions = ((slice(None), pt_tok, step.positions.reshape(-1),
                    cl_tok, 1),)
        a, step.pools[self.layer_idx] = self.self_attn.paged_forward(
            self.input_layernorm(x), step.positions,
            step.pools[self.layer_idx], step.slots, regions)
        h = x + a
        y = self.post_attention_layernorm(h)
        if self.is_moe and step.stats is not None:
            out, counts = self.mlp.forward_counted(y, valid)
            step.stats.append(("moe", counts))
        else:
            out = self.mlp(y)
        return h + out


class LatentMoEForCausalLM(Layer):
    """``embed_tokens / layers / norm / lm_head / cfg``: the shape of core
    the serving engine takes. Generation is the serving engine's;
    ``GenerationMixin``'s static-cache ``generate()`` has no latent
    cache."""

    def __init__(self, cfg: LatentMoEConfig):
        super().__init__()
        self.cfg = cfg
        from ..nn.initializer import Normal
        from ..nn.layer import ParamAttr
        self.embed_tokens = Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=ParamAttr(initializer=Normal(0.0, 0.02)))
        self.layers = LayerList([LatentMoEDecoderLayer(cfg, i)
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size,
                              bias_attr=False)

    def forward(self, input_ids, position_ids=None):
        x = self.embed_tokens(input_ids)
        for layer in self.layers:
            x = layer(x, position_ids)
        return self.lm_head(self.norm(x))

    def _gen_state_tensors(self):
        """Parameters and buffers in a fixed order: the weight arguments
        of the engine's compiled step."""
        return list(self.parameters()) + [b for _, b in
                                          self.named_buffers()]
