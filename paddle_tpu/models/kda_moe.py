"""A hybrid causal LM of Kimi Delta Attention (KDA, arXiv:2510.26692)
mixers beside latent attention (MLA), with group-routed sparse experts:
the language model of the Ling-3.0-flash family (Ling-3.0-flash-VL's
``config.json``: ``layer_group_size``, ``kda_*``, ``short_conv_kernel_size``,
``n_group`` / ``topk_group``, ``num_experts``, ...).

Pre-norm residual blocks, RMSNorm (eps ``rms_norm_eps``), no biases but
those named. ``x`` is a block's input and ``y = RMSNorm(x)``. Layer ``i``'s
mixer is MLA where ``(i + 1) % layer_group_size == 0`` and KDA otherwise:

- **KDA** (``H = num_attention_heads`` heads of ``d = head_dim``):
  ``q~, k~, v~ = y W_q, y W_k, y W_v``, each through a depthwise causal
  convolution over the last ``short_conv_kernel_size`` positions (no bias)
  and SiLU; q and k divided by their L2 norm a head; ``beta =
  sigmoid(y W_b)`` a head; the gate a channel, full rank (``no_kda_lora``),
  the safe gate ``g = kda_lower_bound * sigmoid(exp(A_log_h) * (y W_f +
  b_dt))`` in ``(kda_lower_bound, 0)``; the gated delta rule
  (``ops/delta_rule.py``) over a ``[d, d]`` float32 state a head, output
  ``S_t^T q_t / sqrt(d)``; RMSNorm in ``group_norm_size`` groups of the
  ``H d`` values; a head scaled by ``sigmoid(y W_g)_h``
  (``gated_attention_proj_granularity_type: head_wise``); ``W_o``.
- **MLA**: ``models/latent_moe.py``'s latent attention, with a full-rank
  query where ``q_lora_rank`` is null.
- **FFN**: SwiGLU of ``intermediate_size`` in the first
  ``first_k_dense_replace`` layers; elsewhere a shared SwiGLU of
  ``moe_shared_expert_intermediate_size`` plus the routed experts
  (:class:`~paddle_tpu.incubate.moe.DroplessMoE`, sigmoid scores with a
  selection bias, ``topk_group`` of ``n_group`` groups, top
  ``num_experts_per_tok``, weights normalised and scaled by
  ``routed_scaling_factor``), of which this program may hold a share
  (``experts_held``).
- A final RMSNorm and an untied head.

Serving: ``ServingEngine(model, ragged=True)``. A KDA layer keeps a lane
state (``LayerCache(state=...)``: the delta rule's ``[H, d, d]`` in
float32 and the convolution's tail of ``3 H d`` channels); the MLA layer
owns the one latent full pool, whose padded table it gathers once a lane
and region of the packed step (not once a token). A lane's first row at
position 0 starts from a nought state; a preempted lane is prefilled
again from token 0. What is not built beside a lane state refuses by name
(``kv_cache.py``, ``engine.py``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autograd import apply
from ..core.tensor import Tensor
from ..incubate.moe import DroplessMoE, SwiGLU
from ..nn import Embedding, Layer, LayerList, Linear, RMSNorm
from ..nn import initializer as I
from ..nn.layer import ParamAttr
from ..ops.delta_rule import GATE_FLOOR, delta_rule
from ..ops.selective_scan import causal_conv_tail
from . import latent_moe as lm

__all__ = ["KdaMoEConfig", "KdaMoEForCausalLM"]

F32 = jnp.float32


@dataclass
class KdaMoEConfig:
    vocab_size: int = 157184
    hidden_size: int = 2560
    intermediate_size: int = 6144
    num_hidden_layers: int = 42
    first_k_dense_replace: int = 2
    max_position_embeddings: int = 131072
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_experts: int = 512
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    score_function: str = "sigmoid"
    moe_router_enable_expert_bias: bool = True
    scale_router_input: bool = False
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    head_dim: int = 128
    q_lora_rank: int | None = None
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rotary_dim: int = 64
    partial_rotary_factor: float = 0.5
    rope_theta: float = 6000000.0
    rms_norm_eps: float = 1e-6
    layer_group_size: int = 6
    num_kv_heads_for_linear_attn: int = 0
    group_norm_size: int = 1
    linear_silu: bool = True
    use_qk_norm: bool = True
    short_conv_kernel_size: int = 4
    gated_attention_proj_granularity_type: str = "head_wise"
    no_kda_lora: bool = True
    use_kda_lora: bool = False
    kda_safe_gate: bool = True
    kda_lower_bound: float = -5.0
    use_mla_nope: bool = False
    use_nGPT: bool = False
    value_norm: bool = False
    up_proj_norm: bool = False
    mtp_use_kda: bool = False
    expert_swiglu_limit_list: list | None = None
    share_expert_swiglu_limit_list: list | None = None
    # not in the VL config.json: Ling-3.0-flash's own gives
    # rope_interleave true; the VL config names no tied head
    rope_interleave: bool = True
    tie_word_embeddings: bool = False
    dtype: str = "float32"
    # (first, count): the routed experts this program holds; the router
    # keeps its published width. None = all of them.
    experts_held: tuple | None = None

    def __post_init__(self):
        h = self.num_attention_heads
        unbuilt = [what for bad, what in (
            (self.score_function != "sigmoid",
             f"score_function={self.score_function!r}"),
            (not self.moe_router_enable_expert_bias,
             "moe_router_enable_expert_bias=False"),
            (self.scale_router_input, "scale_router_input"),
            (self.use_nGPT, "use_nGPT"),
            (self.value_norm, "value_norm"),
            (self.up_proj_norm, "up_proj_norm"),
            (self.use_mla_nope, "use_mla_nope"),
            (self.use_kda_lora or not self.no_kda_lora,
             "a low-rank KDA gate (use_kda_lora / no_kda_lora=False)"),
            (not self.kda_safe_gate, "kda_safe_gate=False"),
            (self.kda_lower_bound < GATE_FLOOR,
             f"kda_lower_bound={self.kda_lower_bound} below the "
             f"{GATE_FLOOR} the delta rule's sub-chunks of 16 hold in "
             "float32"),
            (not self.linear_silu, "linear_silu=False"),
            (not self.use_qk_norm, "use_qk_norm=False"),
            (self.gated_attention_proj_granularity_type != "head_wise",
             "gated_attention_proj_granularity_type="
             f"{self.gated_attention_proj_granularity_type!r}"),
            (self.num_kv_heads_for_linear_attn not in (0, h),
             "num_kv_heads_for_linear_attn="
             f"{self.num_kv_heads_for_linear_attn} (KDA heads sharing "
             "keys)"),
            (self.rotary_dim != self.qk_rope_head_dim,
             f"rotary_dim={self.rotary_dim} beside qk_rope_head_dim="
             f"{self.qk_rope_head_dim}"),
            (not self.rope_interleave, "rope_interleave=False"),
            (self.tie_word_embeddings, "tie_word_embeddings"))
            if bad]
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            held = list(getattr(self, key) or ())[:self.num_hidden_layers]
            if any(held):
                unbuilt.append(f"a swiglu limit ({key} is {held} over the "
                               "layers held)")
        if unbuilt:
            raise NotImplementedError(
                "KdaMoEConfig: the equations for " + ", ".join(unbuilt)
                + " are not written here")
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)
        if (self.num_attention_heads * self.head_dim) \
                % self.group_norm_size:
            raise ValueError("group_norm_size must divide the KDA output")

    @property
    def latent_dim(self) -> int:
        """Values a token of the MLA layer holds in a cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def kind(self, i: int) -> str:
        """``mla`` | ``kda``."""
        return "mla" if (i + 1) % self.layer_group_size == 0 else "kda"

    @classmethod
    def from_published(cls, config: dict, **program):
        """The keys of a public ``config.json`` that this class takes,
        with program settings (``dtype``, ``experts_held``) laid over."""
        fields = cls.__dataclass_fields__
        kw = {k: v for k, v in config.items() if k in fields}
        kw.update(program)
        return cls(**kw)

    @staticmethod
    def tiny(**kw):
        return KdaMoEConfig(**{**dict(
            vocab_size=512, hidden_size=64, intermediate_size=128,
            num_hidden_layers=4, first_k_dense_replace=1,
            layer_group_size=3, moe_intermediate_size=32,
            moe_shared_expert_intermediate_size=32, num_experts=16,
            num_experts_per_tok=4, n_group=4, topk_group=2,
            num_attention_heads=4, num_key_value_heads=4, head_dim=16,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            rotary_dim=8, v_head_dim=16, max_position_embeddings=512),
            **kw})


# -- the arithmetic, on arrays ----------------------------------------------

def _silu(a):
    return a * jax.nn.sigmoid(a)


def l2_normed(x, eps=1e-6):
    """x / sqrt(sum x^2 + eps) over the last axis, in float32."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def group_rms(x, w, groups, eps):
    """RMSNorm of x [..., D] float32 in ``groups`` equal groups of its
    last axis, then times ``w`` [D]."""
    shape = x.shape
    x = x.reshape(shape[:-1] + (groups, shape[-1] // groups))
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x.reshape(shape) * w.astype(F32)


# -- mixers -----------------------------------------------------------------

class KimiDeltaAttention(Layer):
    def __init__(self, cfg: KdaMoEConfig):
        super().__init__()
        self.cfg = cfg
        h, nh, d = cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim
        hd = nh * d
        self.q_proj = Linear(h, hd, bias_attr=False)
        self.k_proj = Linear(h, hd, bias_attr=False)
        self.v_proj = Linear(h, hd, bias_attr=False)
        self.f_proj = Linear(h, hd, bias_attr=False)
        self.b_proj = Linear(h, nh, bias_attr=False)
        self.g_proj = Linear(h, nh, bias_attr=False)
        self.conv_weight = self.create_parameter(
            (cfg.short_conv_kernel_size, 3 * hd),
            default_initializer=I.Normal(
                0.0, 1.0 / math.sqrt(cfg.short_conv_kernel_size)))
        self.A_log = self.create_parameter(
            (nh,), default_initializer=I.Assign(
                np.log(np.linspace(1.0, 16.0, nh)).astype(np.float32)))
        step = np.exp(np.linspace(math.log(1e-3), math.log(1e-1), hd))
        self.dt_bias = self.create_parameter(
            (hd,), default_initializer=I.Assign(
                np.log(np.expm1(step)).astype(np.float32)))
        self.o_norm = self.create_parameter(
            (hd,), default_initializer=I.Constant(1.0))
        self.o_proj = Linear(hd, h, bias_attr=False)

    def _w(self, lin):
        return lin.weight._data

    def run(self, u, tail, state, live=None, n_live=None):
        """u [N, S, H] (normed, an array) from the lanes' ``tail`` [N,
        K-1, 3 H d] and ``state`` [N, heads, d, d] float32 -> (out [N, S,
        H], new tail, new state)."""
        cfg = self.cfg
        nh, d = cfg.num_attention_heads, cfg.head_dim
        hd = nh * d
        qkv = jnp.concatenate([jnp.matmul(u, self._w(p)) for p in
                               (self.q_proj, self.k_proj, self.v_proj)], -1)
        conv, tail = causal_conv_tail(qkv, tail, self.conv_weight._data,
                                      jnp.float32(0.0), n_live)
        a = _silu(conv)
        heads = a.shape[:-1] + (nh, d)
        q = l2_normed(a[..., :hd].reshape(heads))
        k = l2_normed(a[..., hd:2 * hd].reshape(heads))
        v = a[..., 2 * hd:].reshape(heads)
        beta = jax.nn.sigmoid(jnp.matmul(u, self._w(self.b_proj))
                              .astype(F32))
        f = (jnp.matmul(u, self._w(self.f_proj)).astype(F32)
             + self.dt_bias._data.astype(F32)).reshape(heads)
        g = cfg.kda_lower_bound * jax.nn.sigmoid(
            jnp.exp(self.A_log._data.astype(F32))[:, None] * f)
        o, state = delta_rule(q, k, v, g, beta, state, d ** -0.5, live)
        o = group_rms(o.reshape(heads[:-2] + (hd,)), self.o_norm._data,
                      cfg.group_norm_size, cfg.rms_norm_eps)
        gate = jax.nn.sigmoid(jnp.matmul(u, self._w(self.g_proj))
                              .astype(F32))
        o = (o.reshape(heads) * gate[..., None]).reshape(o.shape)
        return (jnp.matmul(o.astype(u.dtype), self._w(self.o_proj)), tail,
                state)

    def forward(self, u):
        """u [B, S, H] Tensor -> out: a whole sequence from a nought state
        (inference only: the delta rule's backward is not built)."""
        cfg = self.cfg
        hd = cfg.num_attention_heads * cfg.head_dim

        def f(u):
            b = u.shape[0]
            out, _, _ = self.run(
                u, jnp.zeros((b, cfg.short_conv_kernel_size - 1, 3 * hd),
                             u.dtype),
                jnp.zeros((b, cfg.num_attention_heads, cfg.head_dim,
                           cfg.head_dim), F32))
            return out
        return apply(f, u, name="kimi_delta_attention")


# -- the block ------------------------------------------------------------------

class KdaMoEDecoderLayer(Layer):
    def __init__(self, cfg: KdaMoEConfig, layer_idx: int):
        super().__init__()
        self.cfg, self.layer_idx = cfg, layer_idx
        self.kind = cfg.kind(layer_idx)
        h = cfg.hidden_size
        self.input_layernorm = RMSNorm(h, cfg.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(h, cfg.rms_norm_eps)
        self.self_attn = (KimiDeltaAttention(cfg) if self.kind == "kda"
                          else lm.LatentAttention(cfg))
        self.is_moe = layer_idx >= cfg.first_k_dense_replace
        if self.is_moe:
            self.mlp = DroplessMoE(
                h, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok,
                routed_scaling_factor=cfg.routed_scaling_factor,
                norm_topk_prob=cfg.norm_topk_prob,
                shared_width=cfg.moe_shared_expert_intermediate_size,
                experts_held=cfg.experts_held, n_group=cfg.n_group,
                topk_group=cfg.topk_group)
        else:
            self.mlp = SwiGLU(h, cfg.intermediate_size)

    # what a serving cache keeps for this layer, a sequence
    @property
    def paged_cache(self):
        from ..serving.kv_cache import LayerCache
        cfg = self.cfg
        if self.kind == "mla":
            return LayerCache(pool="full", n_kv_heads=1,
                              head_dim=cfg.latent_dim, latent=True)
        nh, d = cfg.num_attention_heads, cfg.head_dim
        return LayerCache(state=(
            ("S", (nh, d, d), "float32"),
            ("conv", (cfg.short_conv_kernel_size - 1, 3 * nh * d), None)))

    def forward(self, x, position_ids=None):
        y = self.input_layernorm(x)
        a = (self.self_attn(y) if self.kind == "kda"
             else self.self_attn(y, position_ids))
        h = x + a
        return h + self.mlp(self.post_attention_layernorm(h))

    # -- the packed step -----------------------------------------------------
    def paged_forward(self, x, step):
        """The block over the packed step (x [1, T, H] Tensor; ``step``:
        ``serving/attention.py::PagedStep``). Padding rows change no
        state, write to scratch, and their mixer output is nought. The
        routing counts of an expert layer go to ``step.stats``."""
        u = self.input_layernorm(x)._data[0]               # [T, H]
        mix = (self._paged_kda if self.kind == "kda"
               else self._paged_mla)(u, step)
        mix = jnp.where(step.valid[:, None], mix, 0).astype(u.dtype)
        h = x + Tensor(mix[None])
        y = self.post_attention_layernorm(h)
        if self.is_moe and step.stats is not None:
            out, counts = self.mlp.forward_counted(y, step.valid)
            step.stats.append(counts)
        else:
            out = self.mlp(y)
        return h + out

    def _paged_kda(self, u, step):
        i = self.layer_idx
        state, tail = step.states[i]
        outs = []
        with jax.named_scope("kda_layer"):
            for rows, lanes, n, s in step.regions():
                slot = step.lane_slot[lanes]
                ql, fresh = step.ql[lanes], step.qoff[lanes] == 0
                # a lane whose first row starts a sequence: nought
                s0 = jnp.where(fresh[:, None, None, None], 0, state[slot])
                t0 = jnp.where(fresh[:, None, None], 0, tail[slot])
                live = jnp.arange(s, dtype=jnp.int32)[None] < ql[:, None]
                out, t1, s1 = self.self_attn.run(
                    u[rows].reshape(n, s, -1), t0, s0, live, ql)
                state = state.at[slot].set(s1)
                tail = tail.at[slot].set(t1)
                outs.append(out.reshape(n * s, -1))
        step.states[i] = (state, tail)
        return jnp.concatenate(outs)

    def _paged_mla(self, u, step):
        """``latent_moe.absorbed_paged_attention`` over the latent pool,
        each lane's padded table gathered once a region, not once a
        token."""
        i = self.layer_idx
        regions = [(rows, step.pt[lanes], step.qoff[lanes], step.cl[lanes],
                    s) for rows, lanes, _, s in step.regions()]
        with jax.named_scope("mla_attention"):
            out, step.pools[i] = self.self_attn.paged_forward(
                Tensor(u[None]), step.positions, step.pools[i], step.slots,
                regions)
        return out._data[0]


class KdaMoEForCausalLM(Layer):
    """``embed_tokens / layers / norm / lm_head / cfg``: the shape of
    core the serving engine takes. Generation is the serving engine's;
    ``GenerationMixin``'s static-cache ``generate()`` has no lane
    state."""

    def __init__(self, cfg: KdaMoEConfig):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = Embedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=ParamAttr(initializer=I.Normal(0.0, 0.02)))
        self.layers = LayerList([KdaMoEDecoderLayer(cfg, i)
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
