"""Mixture-of-Experts with expert parallelism.

Reference parity: the incubate MoE stack — gates (GShard/Switch top-k),
`global_scatter`/`global_gather` alltoall dispatch, expert-parallel groups
(upstream python/paddle/incubate/distributed/models/moe/ — unverified, see
SURVEY.md §2.3 "Expert parallel").

TPU-native design: experts live as ONE stacked weight tensor [E, ...] whose
expert dim carries a partition hint over the expert-parallel mesh axis;
token dispatch is the GShard einsum formulation (dispatch/combine one-hot
tensors with capacity), which the GSPMD partitioner lowers to the same
all_to_all the reference issues by hand. The explicit shard_map path
(`global_scatter`/`global_gather`) is provided for the collective-level
API.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from ..core.autograd import apply, mark_stable
from ..core.tensor import Tensor
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer import Layer
from ..distributed._axis import current_axis_env


def _excl_cumsum(c):
    return jnp.concatenate(
        [jnp.zeros((1,), c.dtype), jnp.cumsum(c)[:-1]])


def _use_ragged_op() -> bool:
    """`jax.lax.ragged_all_to_all` is the native XLA ragged collective
    on TPU; XLA:CPU has no lowering for it (UNIMPLEMENTED), so the
    8-device CPU test mesh takes the padded-bucket exchange. Override
    with PADDLE_TPU_RAGGED_A2A=ragged|padded."""
    mode = os.environ.get("PADDLE_TPU_RAGGED_A2A", "auto")
    if mode in ("ragged", "padded"):
        return mode == "ragged"
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


def _padded_exchange(xa, send_sizes, recv_sizes, axis, out_rows, w):
    """Dense emulation of the ragged exchange: per-destination buckets
    padded to a static capacity (the per-shard row count), one tiled
    all_to_all, then a count-driven repack on the receiver. W× transient
    memory but runs on every backend."""
    n = xa.shape[0]
    cap = n
    in_off = _excl_cumsum(send_sizes)
    i = jnp.arange(n)
    csum = jnp.cumsum(send_sizes)
    b = jnp.searchsorted(csum, i, side="right")        # dest bucket
    valid_in = i < csum[-1]
    bc = jnp.clip(b, 0, w - 1)
    pos = jnp.clip(i - in_off[bc], 0, cap - 1)
    vmask = valid_in.reshape((-1,) + (1,) * (xa.ndim - 1))
    buf = jnp.zeros((w, cap) + xa.shape[1:], xa.dtype)
    # .add, not .set: invalid rows contribute exact zeros at clipped
    # slots without overwriting a valid row's data
    buf = buf.at[bc, pos].add(jnp.where(vmask, xa, 0))
    recv = jax.lax.all_to_all(buf, axis, 0, 0)         # [w, cap, ...]
    ro = _excl_cumsum(recv_sizes)
    rsum = jnp.cumsum(recv_sizes)
    j = jnp.arange(out_rows)
    bj = jnp.clip(jnp.searchsorted(rsum, j, side="right"), 0, w - 1)
    pj = jnp.clip(j - ro[bj], 0, cap - 1)
    out = recv[bj, pj]
    omask = (j < rsum[-1]).reshape((-1,) + (1,) * (xa.ndim - 1))
    return jnp.where(omask, out, 0)


def _ragged_exchange(xa, send_sizes, recv_sizes, axis, out_rows, w):
    """Variable-split all_to_all over `axis`: `send_sizes[r]` rows of
    `xa` (taken contiguously, rank-major) go to rank r; received chunks
    pack source-rank-major into a zero-initialized [out_rows, ...]
    buffer (valid rows are the sum(recv_sizes) prefix — XLA needs the
    static bound). On TPU this is `jax.lax.ragged_all_to_all` (rides ICI
    with no densification); offsets into every REMOTE output need the
    full send matrix — one [W] int all_gather."""
    send_sizes = send_sizes.astype(jnp.int32)
    recv_sizes = recv_sizes.astype(jnp.int32)
    if not _use_ragged_op():
        return _padded_exchange(xa, send_sizes, recv_sizes, axis,
                                out_rows, w)
    me = jax.lax.axis_index(axis)
    in_off = _excl_cumsum(send_sizes)
    mat = jax.lax.all_gather(send_sizes, axis)     # [W, W]: mat[i, r] i→r
    out_off = (jnp.cumsum(mat, axis=0) - mat)[me]  # my chunk's offset @ r
    out = jnp.zeros((out_rows,) + xa.shape[1:], xa.dtype)
    return jax.lax.ragged_all_to_all(xa, out, in_off, send_sizes,
                                     out_off, recv_sizes, axis_name=axis)


def global_scatter(x, local_count, global_count, group=None,
                   out_rows=None):
    """Reference API: alltoall dispatch of tokens to expert owners —
    COUNT-AWARE (VERDICT r4 missing #5; the counts used to be ignored in
    favor of a uniform tiled split).

    x: [N, D] token rows sorted by destination GLOBAL expert id
    (= rank-major when experts are contiguously owned). local_count:
    [E_total] int — tokens this rank sends to each global expert.
    global_count: [E_total] int — tokens this rank receives; segment r
    (length E_local) is what rank r sends to my local experts. Returns
    [out_rows, D] with the sum(global_count) valid rows packed first,
    ordered source-rank-major (the reference's receive layout); the tail
    is zero padding — XLA static shapes need the bound, default
    out_rows = N * world_size."""
    if group is None or group.axis_name not in current_axis_env():
        return x
    axis, w = group.axis_name, group.nranks
    rows = int(out_rows) if out_rows is not None else x.shape[0] * w
    lc = local_count._data if hasattr(local_count, "_data") \
        else jnp.asarray(local_count)
    gc = global_count._data if hasattr(global_count, "_data") \
        else jnp.asarray(global_count)

    def f(a):
        send = lc.reshape(w, -1).sum(-1)
        recv = gc.reshape(w, -1).sum(-1)
        return _ragged_exchange(a, send, recv, axis, rows, w)
    return apply(f, x, name="global_scatter")


def global_gather(x, local_count, global_count, group=None,
                  out_rows=None):
    """Inverse of `global_scatter`: expert outputs return to their token
    owners. x: [M, D] rows in the scatter RECEIVE layout (source-rank-
    major); returns [out_rows, D] whose sum(local_count) valid prefix is
    back in the original sorted-by-destination-expert order. Counts are
    load-bearing: send sizes come from global_count, receive sizes from
    local_count (the exact mirror of the scatter). Default out_rows =
    M: the gather receives exactly the tokens this rank originally
    dispatched (sum(local_count) <= original N <= M for the standard
    scatter->gather round trip) — pass out_rows for a tighter buffer."""
    if group is None or group.axis_name not in current_axis_env():
        return x
    axis, w = group.axis_name, group.nranks
    rows = int(out_rows) if out_rows is not None else x.shape[0]
    lc = local_count._data if hasattr(local_count, "_data") \
        else jnp.asarray(local_count)
    gc = global_count._data if hasattr(global_count, "_data") \
        else jnp.asarray(global_count)

    def f(a):
        send = gc.reshape(w, -1).sum(-1)
        recv = lc.reshape(w, -1).sum(-1)
        return _ragged_exchange(a, send, recv, axis, rows, w)
    return apply(f, x, name="global_gather")


class TopKGate(Layer):
    """GShard-style noisy top-k gate with load-balancing aux loss."""

    def __init__(self, d_model, num_experts, top_k=2,
                 capacity_factor=1.25, eval_capacity_factor=2.0,
                 noisy_gate=True):
        super().__init__()
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.eval_capacity_factor = eval_capacity_factor
        self.noisy_gate = noisy_gate
        self.weight = self.create_parameter(
            (d_model, num_experts),
            default_initializer=I.XavierUniform())

    def forward(self, x):
        return F.linear(x, self.weight)


class MoELayer(Layer):
    """paddle.incubate MoELayer parity: gate + expert FFNs + dispatch.

    experts: stacked SwiGLU-free FFN (w_in [E, D, M], w_out [E, M, D]).
    The aux load-balance loss is exposed as `self.l_aux` after forward.
    """

    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 capacity_factor=1.25, gate=None, ep_axis="sharding",
                 activation="gelu", recompute_interval=0,
                 dispatch_mode="sort"):
        super().__init__()
        if dispatch_mode not in ("sort", "dense"):
            raise ValueError(f"dispatch_mode {dispatch_mode!r} not in "
                             "('sort', 'dense')")
        self.d_model = d_model
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.activation = activation
        self.dispatch_mode = dispatch_mode
        self.gate = gate or TopKGate(d_model, num_experts, top_k,
                                     capacity_factor)
        self.w_in = self.create_parameter(
            (num_experts, d_model, d_hidden),
            default_initializer=I.XavierUniform())
        self.w_out = self.create_parameter(
            (num_experts, d_hidden, d_model),
            default_initializer=I.XavierUniform())
        # expert dim partition hint for the SPMD engine
        self.w_in.dist_spec = (ep_axis, None, None)
        self.w_out.dist_spec = (ep_axis, None, None)
        self.l_aux = None

    def forward(self, x):
        """x: [B, S, D] (or [N, D])."""
        squeeze = x.ndim == 2
        if squeeze:
            x = x.unsqueeze(0)
        b, s, d = x.shape
        n_tokens = b * s
        e = self.num_experts
        capacity = max(1, int(self.capacity_factor * n_tokens / e))
        logits = self.gate(x)  # [B, S, E]
        act_name = self.activation

        top_k = self.top_k
        mode = self.dispatch_mode

        def gate_topk(logits_a):
            lg = logits_a.reshape(n_tokens, e).astype(jnp.float32)
            probs = jax.nn.softmax(lg, axis=-1)
            topv, topi = jax.lax.top_k(probs, top_k)
            topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
            # aux load-balancing loss (GShard): E * sum(me * ce)
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(
                jax.nn.one_hot(topi[:, 0], e).astype(jnp.float32), axis=0)
            return topv, topi, jnp.sum(me * ce) * e

        def experts_fwd(expert_in, w_in, w_out):
            """[E, C, D] → [E, C, D] through the stacked FFNs."""
            h = jnp.einsum("ecd,edm->ecm", expert_in,
                           w_in.astype(jnp.float32))
            h = getattr(jax.nn, act_name)(h)
            return jnp.einsum("ecm,emd->ecd", h,
                              w_out.astype(jnp.float32))

        def moe_fn_sort(xa, logits_a, w_in, w_out):
            """Sort/segment dispatch — peak memory O(N·K + E·C·D), never
            O(N·E·C) (VERDICT r3 item 7). Exactly equivalent to the
            GShard per-slot capacity bookkeeping: entries take positions
            in their expert's queue in (slot, token) priority order, and
            an expert that overflows at slot s drops every later-priority
            entry in BOTH formulations (dense `used` saturates at
            capacity; here pos >= count >= capacity)."""
            xt = xa.reshape(n_tokens, d)
            topv, topi, l_aux = gate_topk(logits_a)
            nk = n_tokens * top_k
            # slot-major flattening: all slot-0 entries (token order),
            # then slot-1 … — the GShard priority order
            fe = topi.T.reshape(nk)                       # expert ids
            fw = topv.T.reshape(nk)                       # combine weights
            ftok = jnp.tile(jnp.arange(n_tokens), (top_k,))
            order = jnp.argsort(fe)                       # stable in jax
            se = fe[order]
            sw = fw[order]
            stok = ftok[order]
            # position of each entry in its expert's queue
            counts = jax.ops.segment_sum(jnp.ones((nk,), jnp.int32), se,
                                         num_segments=e)
            starts = jnp.concatenate(
                [jnp.zeros((1,), jnp.int32),
                 jnp.cumsum(counts)[:-1].astype(jnp.int32)])
            pos = jnp.arange(nk, dtype=jnp.int32) - starts[se]
            keep = pos < capacity
            dest = se * capacity + jnp.clip(pos, 0, capacity - 1)
            # scatter tokens into the expert buffers (dropped entries
            # contribute exact zeros at a clipped slot)
            contrib = xt[stok].astype(jnp.float32) * \
                keep[:, None].astype(jnp.float32)
            expert_in = jnp.zeros((e * capacity, d), jnp.float32) \
                .at[dest].add(contrib).reshape(e, capacity, d)
            expert_out = experts_fwd(expert_in, w_in, w_out) \
                .reshape(e * capacity, d)
            gathered = expert_out[dest] * \
                (sw * keep.astype(jnp.float32))[:, None]
            out = jnp.zeros((n_tokens, d), jnp.float32) \
                .at[stok].add(gathered)
            return out.reshape(b, s, d).astype(xa.dtype), l_aux

        def moe_fn_dense(xa, logits_a, w_in, w_out):
            """GShard one-hot einsum dispatch (O(N·E·C) dispatch/combine
            tensors). Kept as the opt-in mode whose einsums the GSPMD
            partitioner lowers straight to all_to_all; the sort mode is
            the default at real token counts."""
            xt = xa.reshape(n_tokens, d)
            topv, topi, l_aux = gate_topk(logits_a)
            dispatch = jnp.zeros((n_tokens, e, capacity), jnp.float32)
            combine = jnp.zeros((n_tokens, e, capacity), jnp.float32)
            used = jnp.zeros((e,), jnp.int32)
            for slot in range(top_k):
                idx = topi[:, slot]                       # [N]
                onehot = jax.nn.one_hot(idx, e)           # [N, E]
                pos_in_e = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot
                pos = jnp.sum(pos_in_e * onehot, axis=-1).astype(
                    jnp.int32) + jnp.take(used, idx)
                keep = pos < capacity
                pos_c = jnp.clip(pos, 0, capacity - 1)
                oh_cap = jax.nn.one_hot(pos_c, capacity) * \
                    keep[:, None].astype(jnp.float32)
                disp_slot = onehot[:, :, None] * oh_cap[:, None, :]
                dispatch = dispatch + disp_slot
                combine = combine + disp_slot * topv[:, slot][:, None,
                                                              None]
                used = used + jnp.sum(
                    onehot * keep[:, None], axis=0).astype(jnp.int32)
            expert_in = jnp.einsum("nec,nd->ecd", dispatch,
                                   xt.astype(jnp.float32))
            expert_out = experts_fwd(expert_in, w_in, w_out)
            out = jnp.einsum("nec,ecd->nd", combine, expert_out)
            return out.reshape(b, s, d).astype(xa.dtype), l_aux

        moe_fn = moe_fn_sort if mode == "sort" else moe_fn_dense
        out, l_aux = apply(moe_fn, x, logits, self.w_in, self.w_out,
                           name="moe")
        self.l_aux = l_aux
        if squeeze:
            out = out.squeeze(0)
        return out


# -- dropless routing over the experts held here ---------------------------

def dropless_route(y, w_router, bias, top_k, scale, norm_topk_prob=True):
    """The ``noaux_tc`` sigmoid router without group limits, in float32:
    ``s = sigmoid(y W_r)``; the ``top_k`` of ``s + bias`` are chosen (the
    correction bias moves the choice, never the weight); the weights are
    the chosen ``s``, normalised to sum 1 where asked, times ``scale``.
    y [T, H] -> (expert ids [T, k] int32, weights [T, k] float32)."""
    s = jax.nn.sigmoid(jnp.matmul(y.astype(jnp.float32),
                                  w_router.astype(jnp.float32)))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    g = jnp.take_along_axis(s, idx, -1)
    if norm_topk_prob:
        g = g / (jnp.sum(g, -1, keepdims=True) + 1e-20)
    return idx.astype(jnp.int32), g * scale


def dropless_experts(y, idx, gates, w_gate, w_up, w_down, first=0):
    """``sum_i g_i E_i(y)`` over the experts held here (global ids
    ``first .. first + count - 1``; ``w_*`` are their stacks), each a
    SwiGLU. No capacity, no drop: every held expert runs over every
    token, weighted by a gate that is zero where the token was not
    routed to it -- at serving's token counts the layer is bound by
    reading the expert weights once, which this does. Both products
    accumulate in float32; the down product contracts experts and width
    together. y [T, H] -> [T, H] in y's dtype."""
    count = w_gate.shape[0]
    dense = jnp.sum(jax.nn.one_hot(idx - first, count, dtype=jnp.float32)
                    * gates[..., None], axis=1)               # [T, count]
    h1 = jnp.einsum("th,ehf->etf", y, w_gate,
                    preferred_element_type=jnp.float32)
    h2 = jnp.einsum("th,ehf->etf", y, w_up,
                    preferred_element_type=jnp.float32)
    a = jax.nn.silu(h1) * h2 * dense.T[:, :, None]
    out = jnp.einsum("etf,efh->th", a.astype(w_down.dtype), w_down,
                     preferred_element_type=jnp.float32)
    return out.astype(y.dtype)


def routing_counts(idx, first, count, valid=None):
    """int32 [4] for one layer-step: assignments to the experts held
    here, distinct held experts with a token, the largest count on one
    of them, and 1 (the layer-step itself). ``valid`` [T] leaves padding
    tokens out."""
    hot = jax.nn.one_hot(idx - first, count, dtype=jnp.int32)  # [T,k,count]
    if valid is not None:
        hot = hot * valid.astype(jnp.int32)[:, None, None]
    load = jnp.sum(hot, axis=(0, 1))
    return jnp.stack([jnp.sum(load), jnp.sum(load > 0), jnp.max(load),
                      jnp.int32(1)]).astype(jnp.int32)


@functools.lru_cache(maxsize=32)
def _dropless_fn(top_k, scale, norm, first):
    def f(y, w_router, bias, w_gate, w_up, w_down):
        shape = y.shape
        y = y.reshape(-1, shape[-1])
        idx, gates = dropless_route(y, w_router, bias, top_k, scale, norm)
        return dropless_experts(y, idx, gates, w_gate, w_up, w_down,
                                first).reshape(shape)
    return mark_stable(f)


class SwiGLU(Layer):
    """``down(silu(gate(x)) * up(x))``, no biases."""

    def __init__(self, hidden, width):
        super().__init__()
        from ..nn import Linear
        self.gate_proj = Linear(hidden, width, bias_attr=False)
        self.up_proj = Linear(hidden, width, bias_attr=False)
        self.down_proj = Linear(width, hidden, bias_attr=False)

    def forward(self, x):
        from .nn.functional import swiglu
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


class DroplessMoE(Layer):
    """A sparse-expert layer as one chip's share of it: it is told which
    routed experts it holds (``experts_held=(first, count)``, default
    all), routes every token over all ``num_experts`` with the published
    router (:func:`dropless_route`), and returns the part of
    ``sum_i g_i E_i(y)`` that its own experts give plus, where
    ``shared_width`` is set, the shared expert ``E_shared(y)``. The
    shares of a layer, the shared expert counted once, add up to the
    whole layer. On one chip it runs without an exchange; the exchange
    over chips is not built (ROADMAP R1).

    Parameters: ``router.weight`` [H, E], ``e_score_correction_bias`` [E],
    ``w_gate`` / ``w_up`` [count, H, F], ``w_down`` [count, F, H],
    ``shared_experts.{gate,up,down}_proj``."""

    def __init__(self, d_model, d_expert, num_experts, top_k, *,
                 routed_scaling_factor=1.0, norm_topk_prob=True,
                 shared_width=0, experts_held=None):
        super().__init__()
        from ..nn import Linear
        first, count = experts_held or (0, num_experts)
        if not (0 <= first and count >= 1
                and first + count <= num_experts):
            raise ValueError(
                f"experts_held={experts_held!r} is not a range of the "
                f"{num_experts} routed experts")
        if top_k > num_experts:
            raise ValueError(f"top_k={top_k} > num_experts={num_experts}")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.first, self.count = int(first), int(count)
        self.scale = float(routed_scaling_factor)
        self.norm_topk_prob = bool(norm_topk_prob)
        self.router = Linear(d_model, num_experts, bias_attr=False)
        self.e_score_correction_bias = self.create_parameter(
            (num_experts,), default_initializer=I.Constant(0.0))
        init = I.Normal(0.0, 0.02)
        self.w_gate = self.create_parameter((count, d_model, d_expert),
                                            default_initializer=init)
        self.w_up = self.create_parameter((count, d_model, d_expert),
                                          default_initializer=init)
        self.w_down = self.create_parameter((count, d_expert, d_model),
                                            default_initializer=init)
        self.shared_experts = (SwiGLU(d_model, shared_width)
                               if shared_width else None)

    def _operands(self):
        return (self.router.weight, self.e_score_correction_bias,
                self.w_gate, self.w_up, self.w_down)

    def forward(self, y):
        out = apply(_dropless_fn(self.top_k, self.scale,
                                 self.norm_topk_prob, self.first),
                    y, *self._operands(), name="dropless_moe")
        if self.shared_experts is not None:
            out = out + self.shared_experts(y)
        return out

    def forward_counted(self, y, valid=None):
        """As :meth:`forward`, also returning :func:`routing_counts` of
        the step (the serving engine's call, inside its compiled step:
        the counts stay on the device, and the compiler folds this
        second routing into the forward's)."""
        ya = y._data
        idx, _ = dropless_route(
            ya.reshape(-1, ya.shape[-1]), self.router.weight._data,
            self.e_score_correction_bias._data, self.top_k, self.scale,
            self.norm_topk_prob)
        return self.forward(y), routing_counts(idx, self.first,
                                               self.count, valid)
