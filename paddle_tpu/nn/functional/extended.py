"""Long-tail paddle.nn.functional surface (reference:
python/paddle/nn/functional/{pooling,loss,vision,activation}.py —
unverified, SURVEY.md §2.2 paddle.nn). Each op is one jax expression or
a lax.scan DP (ctc_loss); 3-D pools ride reduce_window, grid_sample and
max_unpool are vectorized gathers/scatters.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.autograd import apply, mark_stable
from ...core.random import next_key
from ...core.tensor import Tensor
from ...ops._base import ensure_tensor

__all__ = [
    "avg_pool3d", "max_pool3d", "adaptive_avg_pool3d",
    "adaptive_max_pool1d", "bilinear", "conv1d_transpose",
    "conv3d_transpose", "ctc_loss", "dice_loss", "grid_sample",
    "hsigmoid_loss", "log_loss", "log_sigmoid", "max_unpool2d",
    "pairwise_distance", "pixel_unshuffle", "rrelu",
    "sigmoid_focal_loss", "square_error_cost", "temporal_shift",
    "triplet_margin_loss", "zeropad2d",
]


def _t3(v):
    return (v,) * 3 if isinstance(v, int) else tuple(v)


def _pool3d(x, ks, stride, padding, op, init, avg, name):
    x = ensure_tensor(x)
    ks = _t3(ks)
    st = _t3(stride if stride is not None else ks)
    pd = _t3(padding)

    def f(a):
        out = jax.lax.reduce_window(
            a, jnp.asarray(init, a.dtype), op,
            window_dimensions=(1, 1) + ks,
            window_strides=(1, 1) + st,
            padding=((0, 0), (0, 0)) + tuple((p, p) for p in pd))
        if avg:
            ones = jnp.ones_like(a)
            cnt = jax.lax.reduce_window(
                ones, jnp.asarray(0.0, a.dtype), jax.lax.add,
                window_dimensions=(1, 1) + ks,
                window_strides=(1, 1) + st,
                padding=((0, 0), (0, 0)) + tuple((p, p) for p in pd))
            out = out / cnt
        return out
    return apply(f, x, name=name)


def _require_cf(data_format, allowed):
    if data_format != allowed:
        raise NotImplementedError(
            f"data_format={data_format!r} is not supported here (only "
            f"{allowed!r}); transpose the input instead")


def max_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               return_mask=False, data_format="NCDHW", name=None):
    _require_cf(data_format, "NCDHW")
    if return_mask:
        raise NotImplementedError(
            "max_pool3d(return_mask=True) is not supported (no 3-D "
            "unpool consumer exists here); use return_mask=False")
    if ceil_mode:
        raise NotImplementedError("max_pool3d(ceil_mode=True) is not "
                                  "supported; pad the input instead")
    return _pool3d(x, kernel_size, stride, padding, jax.lax.max,
                   -jnp.inf, False, "max_pool3d")


def avg_pool3d(x, kernel_size, stride=None, padding=0, ceil_mode=False,
               exclusive=True, divisor_override=None,
               data_format="NCDHW", name=None):
    _require_cf(data_format, "NCDHW")
    if ceil_mode:
        raise NotImplementedError("avg_pool3d(ceil_mode=True) is not "
                                  "supported; pad the input instead")
    if divisor_override is not None or not exclusive:
        # fixed divisor: the override, or (exclusive=False) the full
        # kernel volume including padded elements
        ks = _t3(kernel_size)
        div = float(divisor_override) if divisor_override is not None \
            else float(ks[0] * ks[1] * ks[2])
        summed = _pool3d(x, kernel_size, stride, padding, jax.lax.add,
                         0.0, False, "avg_pool3d")
        return apply(lambda a: a / div, summed, name="avg_pool3d_div")
    return _pool3d(x, kernel_size, stride, padding, jax.lax.add, 0.0,
                   True, "avg_pool3d")


def _adaptive_bins(L, os, dtype):
    """Membership matrix [L, os] of the reference's overlapping adaptive
    bins (bin i covers [floor(iL/os), ceil((i+1)L/os)))."""
    i = jnp.arange(os)
    starts = (i * L) // os
    ends = -((-(i + 1) * L) // os)
    pos = jnp.arange(L)
    return ((pos[:, None] >= starts[None, :]) &
            (pos[:, None] < ends[None, :])).astype(dtype)


def adaptive_avg_pool3d(x, output_size, data_format="NCDHW", name=None):
    _require_cf(data_format, "NCDHW")
    x = ensure_tensor(x)
    os = _t3(output_size)

    def f(a):
        d, h, w = a.shape[-3:]
        od, oh, ow = os
        if d % od == 0 and h % oh == 0 and w % ow == 0:
            a2 = a.reshape(a.shape[:-3] + (od, d // od, oh, h // oh,
                                           ow, w // ow))
            return jnp.mean(a2, axis=(-5, -3, -1))
        # exact overlapping-bin averaging: box-sum is separable (one
        # membership contraction per axis), then divide by the box size
        f32 = a.astype(jnp.float32)
        md = _adaptive_bins(d, od, jnp.float32)
        mh = _adaptive_bins(h, oh, jnp.float32)
        mw = _adaptive_bins(w, ow, jnp.float32)
        s = jnp.einsum("...dhw,dx,hy,wz->...xyz", f32, md, mh, mw)
        cnt = jnp.einsum("d,dx->x", jnp.ones(d, jnp.float32), md)[
            :, None, None] * \
            jnp.einsum("h,hy->y", jnp.ones(h, jnp.float32), mh)[
                None, :, None] * \
            jnp.einsum("w,wz->z", jnp.ones(w, jnp.float32), mw)[
                None, None, :]
        return (s / cnt).astype(a.dtype)
    return apply(f, x, name="adaptive_avg_pool3d")


def adaptive_max_pool1d(x, output_size, return_mask=False, name=None):
    if return_mask:
        raise NotImplementedError(
            "adaptive_max_pool1d(return_mask=True) is not supported")
    x = ensure_tensor(x)
    os = int(output_size)

    def f(a):
        L = a.shape[-1]
        if L % os == 0:
            return jnp.max(a.reshape(a.shape[:-1] + (os, L // os)), -1)
        member = _adaptive_bins(L, os, bool)          # [L, os]
        neg = jnp.asarray(-jnp.inf, a.dtype)
        masked = jnp.where(member[None, None], a[..., :, None], neg)
        return jnp.max(masked, axis=-2)
    return apply(f, x, name="adaptive_max_pool1d")


def bilinear(x1, x2, weight, bias=None, name=None):
    """out[b, o] = x1[b, i] W[o, i, j] x2[b, j] + bias (reference
    paddle.nn.functional.bilinear)."""
    x1 = ensure_tensor(x1)
    x2 = ensure_tensor(x2)
    weight = ensure_tensor(weight)
    args = [x1, x2, weight]
    if bias is not None:
        args.append(ensure_tensor(bias))

    def f(a, b, w, *bb):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        if bb:
            out = out + bb[0]
        return out
    return apply(f, *args, name="bilinear")


def _convnd_transpose(x, weight, bias, stride, padding, output_padding,
                      groups, dilation, nd, spec, output_size=None):
    if groups != 1:
        raise NotImplementedError(
            "conv1d/3d_transpose with groups>1 is not supported yet "
            "(lax.conv_transpose has no grouping); split channels and "
            "concatenate, or use conv2d_transpose")
    if output_size is not None:
        raise NotImplementedError(
            "conv1d/3d_transpose output_size is not supported; pass "
            "output_padding instead")
    x, weight = ensure_tensor(x), ensure_tensor(weight)
    stride = (stride,) * nd if isinstance(stride, int) else tuple(stride)
    dilation = (dilation,) * nd if isinstance(dilation, int) \
        else tuple(dilation)
    pads = [(padding, padding)] * nd if isinstance(padding, int) \
        else [(int(p), int(p)) for p in padding]
    opad = (output_padding,) * nd if isinstance(output_padding, int) \
        else tuple(output_padding)

    def f(a, w, *b):
        pad_cfg = [
            (dilation[i] * (w.shape[2 + i] - 1) - pads[i][0],
             dilation[i] * (w.shape[2 + i] - 1) - pads[i][1] + opad[i])
            for i in range(nd)]
        out = jax.lax.conv_transpose(
            a, w, strides=stride, padding=pad_cfg,
            rhs_dilation=dilation,
            dimension_numbers=spec,
            transpose_kernel=True)
        if b:
            out = out + b[0].reshape((1, -1) + (1,) * nd)
        return out
    args = [x, weight] + ([ensure_tensor(bias)] if bias is not None else [])
    return apply(f, *args, name="conv_transpose")


def conv1d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCL", name=None):
    return _convnd_transpose(x, weight, bias, stride, padding,
                             output_padding, groups, dilation, 1,
                             ("NCH", "OIH", "NCH"), output_size)


def conv3d_transpose(x, weight, bias=None, stride=1, padding=0,
                     output_padding=0, groups=1, dilation=1,
                     output_size=None, data_format="NCDHW", name=None):
    return _convnd_transpose(x, weight, bias, stride, padding,
                             output_padding, groups, dilation, 3,
                             ("NCDHW", "OIDHW", "NCDHW"), output_size)


@functools.lru_cache(maxsize=None)
def _ctc_loss_fn(blank, reduction, norm_by_times):
    """The pure CTC loss of one configuration, built once: apply()'s
    micro-jit keys on the function's identity, and a closure made in
    every ctc_loss() call had its alpha scan compiled by XLA again on
    every call, forward and backward."""
    def f(logits, lab, il, ll):
        T, B, C = logits.shape
        logp = jax.nn.log_softmax(logits, axis=-1)
        L = lab.shape[1]
        S = 2 * L + 1
        # extended label sequence: blank, l1, blank, l2, ... blank
        ext = jnp.full((B, S), blank, jnp.int32)
        ext = ext.at[:, 1::2].set(lab)
        neg_inf = jnp.asarray(-1e30, logp.dtype)
        # can skip from s-2 to s when ext[s] != blank and != ext[s-2]
        skip_ok = jnp.concatenate(
            [jnp.zeros((B, 2), bool),
             (ext[:, 2:] != blank) & (ext[:, 2:] != ext[:, :-2])], axis=1)

        a0 = jnp.full((B, S), neg_inf)
        a0 = a0.at[:, 0].set(logp[0, jnp.arange(B), ext[:, 0]])
        a0 = a0.at[:, 1].set(jnp.where(
            ll > 0, logp[0, jnp.arange(B), ext[:, 1]], neg_inf))

        def step(alpha, logp_t):
            stay = alpha
            from_prev = jnp.concatenate(
                [jnp.full((B, 1), neg_inf), alpha[:, :-1]], axis=1)
            from_skip = jnp.where(
                skip_ok,
                jnp.concatenate([jnp.full((B, 2), neg_inf),
                                 alpha[:, :-2]], axis=1), neg_inf)
            tot = jnp.logaddexp(jnp.logaddexp(stay, from_prev), from_skip)
            emit = jnp.take_along_axis(logp_t[:, :], ext, axis=1)
            return tot + emit, tot + emit

        _, alphas = jax.lax.scan(step, a0, logp[1:])
        alphas = jnp.concatenate([a0[None], alphas], axis=0)  # [T, B, S]
        # gather alpha at t = input_length-1, s = 2*label_length{-1, 0}
        bidx = jnp.arange(B)
        t_last = jnp.clip(il - 1, 0, T - 1)
        aT = alphas[t_last, bidx]                  # [B, S]
        s_last = jnp.clip(2 * ll, 0, S - 1)
        s_prev = jnp.clip(2 * ll - 1, 0, S - 1)
        ml = jnp.logaddexp(aT[bidx, s_last],
                           jnp.where(ll > 0, aT[bidx, s_prev],
                                     neg_inf))
        loss = -ml
        if norm_by_times:
            loss = loss / jnp.maximum(il.astype(loss.dtype), 1)
        if reduction == "mean":
            # reference: per-sample loss / label_length, then batch mean
            return jnp.mean(loss / jnp.maximum(
                ll.astype(loss.dtype), 1))
        if reduction == "sum":
            return jnp.sum(loss)
        return loss
    return mark_stable(f)


def ctc_loss(log_probs, labels, input_lengths, label_lengths, blank=0,
             reduction="mean", norm_by_times=False, name=None):
    """CTC loss via the log-space alpha (forward) recursion as a
    lax.scan over time (reference: warpctc-backed paddle ctc_loss;
    log_probs [T, B, C] logits — softmax applied internally like the
    reference, labels [B, L])."""
    lp = ensure_tensor(log_probs)
    lab, il, ll = (Tensor(ensure_tensor(t)._data.astype(jnp.int32))
                   for t in (labels, input_lengths, label_lengths))
    return apply(_ctc_loss_fn(blank, reduction, bool(norm_by_times)),
                 lp, lab, il, ll, name="ctc_loss")


def dice_loss(input, label, epsilon=1e-5, name=None):
    input = ensure_tensor(input)
    label = ensure_tensor(label)

    def f(p, y):
        y1 = jax.nn.one_hot(y[..., 0].astype(jnp.int32), p.shape[-1],
                            dtype=p.dtype)
        red = tuple(range(1, p.ndim))
        inter = jnp.sum(p * y1, axis=red)
        union = jnp.sum(p, axis=red) + jnp.sum(y1, axis=red)
        return jnp.mean(1 - (2 * inter + epsilon) / (union + epsilon))
    return apply(f, input, label.detach(), name="dice_loss")


def grid_sample(x, grid, mode="bilinear", padding_mode="zeros",
                align_corners=True, name=None):
    """NCHW input, grid [N, Ho, Wo, 2] in [-1, 1] (x, y order)."""
    if mode not in ("bilinear", "nearest"):
        raise NotImplementedError(f"grid_sample mode={mode!r} (only "
                                  "bilinear/nearest)")
    if padding_mode not in ("zeros", "border"):
        raise NotImplementedError(
            f"grid_sample padding_mode={padding_mode!r} (only "
            "zeros/border)")
    x = ensure_tensor(x)
    grid = ensure_tensor(grid)

    def f(a, g):
        N, C, H, W = a.shape
        gx = g[..., 0]
        gy = g[..., 1]
        if align_corners:
            fx = (gx + 1) * 0.5 * (W - 1)
            fy = (gy + 1) * 0.5 * (H - 1)
        else:
            fx = ((gx + 1) * W - 1) * 0.5
            fy = ((gy + 1) * H - 1) * 0.5

        def tap(yi, xi, w):
            valid = (yi >= 0) & (yi < H) & (xi >= 0) & (xi < W)
            yc = jnp.clip(yi, 0, H - 1)
            xc = jnp.clip(xi, 0, W - 1)
            # per-batch gather: a [N,C,H,W], yc/xc [N,Ho,Wo]
            v = jax.vmap(lambda ai, yy, xx: ai[:, yy, xx])(a, yc, xc)
            if padding_mode == "zeros":
                return v * (w * valid)[:, None]
            return v * w[:, None]

        if mode == "nearest":
            yi = jnp.round(fy).astype(jnp.int32)
            xi = jnp.round(fx).astype(jnp.int32)
            return tap(yi, xi, jnp.ones_like(fx))
        x0 = jnp.floor(fx).astype(jnp.int32)
        y0 = jnp.floor(fy).astype(jnp.int32)
        wx1 = fx - x0
        wy1 = fy - y0
        return (tap(y0, x0, (1 - wy1) * (1 - wx1)) +
                tap(y0, x0 + 1, (1 - wy1) * wx1) +
                tap(y0 + 1, x0, wy1 * (1 - wx1)) +
                tap(y0 + 1, x0 + 1, wy1 * wx1))
    return apply(f, x, grid, name="grid_sample")


import functools as _functools


@_functools.lru_cache(maxsize=16)
def _hsigmoid_tables(n):
    """Complete-binary-tree path tables for n classes (built once per n:
    hsigmoid exists for LARGE n — an O(n·depth) Python loop plus device
    upload per forward would dominate step time)."""
    import numpy as _np
    depth = max(1, (n - 1).bit_length())
    # leaf l sits at node n-1+l in the heap; internal nodes 0..n-2;
    # walk to the root recording (node, bit)
    tbl = _np.zeros((n, depth), _np.int64)
    code = _np.zeros((n, depth), _np.float32)
    valid = _np.zeros((n, depth), _np.float32)
    for l in range(n):
        node = n - 1 + l
        d = 0
        while node > 0 and d < depth:
            parent = (node - 1) // 2
            tbl[l, d] = parent
            code[l, d] = float(node == 2 * parent + 2)  # right child
            valid[l, d] = 1.0
            node = parent
            d += 1
    return jnp.asarray(tbl), jnp.asarray(code), jnp.asarray(valid)


def hsigmoid_loss(input, label, num_classes, weight, bias=None,
                  path_table=None, path_code=None, is_sparse=False,
                  name=None):
    """Hierarchical sigmoid over the default COMPLETE binary tree
    (path_table/path_code custom trees also supported)."""
    input = ensure_tensor(input)
    w = ensure_tensor(weight)
    lab = ensure_tensor(label)._data.astype(jnp.int32).reshape(-1)
    n = int(num_classes)

    if path_table is None:
        tbl_j, code_j, valid_j = _hsigmoid_tables(n)
    else:
        tbl_j = ensure_tensor(path_table)._data.astype(jnp.int32)
        code_j = ensure_tensor(path_code)._data.astype(jnp.float32)
        valid_j = (tbl_j >= 0).astype(jnp.float32)
        tbl_j = jnp.maximum(tbl_j, 0)

    args = [input, w] + ([ensure_tensor(bias)] if bias is not None else [])

    def f(xa, wa, *ba):
        nodes = tbl_j[lab]                     # [B, depth]
        codes = code_j[lab]
        val = valid_j[lab]
        wn = wa[nodes]                         # [B, depth, D]
        z = jnp.einsum("bd,bkd->bk", xa, wn)
        if ba:
            z = z + ba[0][nodes]
        # bernoulli log-likelihood of each branch decision
        ll = codes * jax.nn.log_sigmoid(z) + \
            (1 - codes) * jax.nn.log_sigmoid(-z)
        return -jnp.sum(ll * val, axis=1).mean()
    return apply(f, *args, name="hsigmoid_loss")


def log_loss(input, label, epsilon=1e-4, name=None):
    input = ensure_tensor(input)
    label = ensure_tensor(label)
    return apply(lambda p, y: -y * jnp.log(p + epsilon) -
                 (1 - y) * jnp.log(1 - p + epsilon),
                 input, label.detach(), name="log_loss")


def log_sigmoid(x, name=None):
    return apply(jax.nn.log_sigmoid, ensure_tensor(x), name="log_sigmoid")


def max_unpool2d(x, indices, kernel_size, stride=None, padding=0,
                 data_format="NCHW", output_size=None, name=None):
    """Scatter pooled values back to their argmax positions (indices are
    flat per-channel positions, the reference's max_pool2d(return_mask)
    convention)."""
    x = ensure_tensor(x)
    idx = ensure_tensor(indices)
    ks = (kernel_size, kernel_size) if isinstance(kernel_size, int) \
        else tuple(kernel_size)
    st = ks if stride is None else (
        (stride, stride) if isinstance(stride, int) else tuple(stride))

    def f(a, i):
        N, C, H, W = a.shape
        if output_size is not None:
            oh, ow = output_size[-2:]
        else:
            oh = (H - 1) * st[0] + ks[0] - 2 * padding
            ow = (W - 1) * st[1] + ks[1] - 2 * padding
        flat = jnp.zeros((N, C, oh * ow), a.dtype)
        ii = i.reshape(N, C, -1).astype(jnp.int32)
        vv = a.reshape(N, C, -1)
        flat = jax.vmap(jax.vmap(
            lambda fz, jj, vz: fz.at[jj].set(vz)))(flat, ii, vv)
        return flat.reshape(N, C, oh, ow)
    return apply(f, x, idx.detach(), name="max_unpool2d")


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False,
                      name=None):
    x, y = ensure_tensor(x), ensure_tensor(y)
    return apply(
        lambda a, b: jnp.sum(jnp.abs(a - b + epsilon) ** p,
                             axis=-1, keepdims=keepdim) ** (1.0 / p),
        x, y, name="pairwise_distance")


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    x = ensure_tensor(x)
    r = int(downscale_factor)

    def f(a):
        N, C, H, W = a.shape
        a = a.reshape(N, C, H // r, r, W // r, r)
        return a.transpose(0, 1, 3, 5, 2, 4).reshape(
            N, C * r * r, H // r, W // r)
    return apply(f, x, name="pixel_unshuffle")


def rrelu(x, lower=1.0 / 8.0, upper=1.0 / 3.0, training=False,
          name=None):
    x = ensure_tensor(x)
    if training:
        k = next_key()

        def f(a):
            slope = jax.random.uniform(k, a.shape, jnp.float32, lower,
                                       upper).astype(a.dtype)
            return jnp.where(a >= 0, a, a * slope)
        return apply(f, x, name="rrelu")
    mid = (lower + upper) / 2.0
    return apply(lambda a: jnp.where(a >= 0, a, a * mid), x, name="rrelu")


def sigmoid_focal_loss(logit, label, normalizer=None, alpha=0.25,
                       gamma=2.0, reduction="sum", name=None):
    logit = ensure_tensor(logit)
    label = ensure_tensor(label)
    args = [logit, label.detach()]
    if normalizer is not None:
        args.append(ensure_tensor(normalizer))

    def f(z, y, *nm):
        p = jax.nn.sigmoid(z)
        ce = jnp.maximum(z, 0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
        p_t = p * y + (1 - p) * (1 - y)
        a_t = alpha * y + (1 - alpha) * (1 - y)
        loss = a_t * ((1 - p_t) ** gamma) * ce
        if nm:
            loss = loss / nm[0]
        if reduction == "mean":
            return jnp.mean(loss)
        if reduction == "sum":
            return jnp.sum(loss)
        return loss
    return apply(f, *args, name="sigmoid_focal_loss")


def square_error_cost(input, label, name=None):
    return apply(lambda a, b: (a - b) ** 2, ensure_tensor(input),
                 ensure_tensor(label), name="square_error_cost")


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW",
                   name=None):
    """TSM channel shift across the time dimension (x: [N*T, C, H, W])."""
    x = ensure_tensor(x)

    def f(a):
        NT, C, H, W = a.shape
        T = seg_num
        N = NT // T
        v = a.reshape(N, T, C, H, W)
        k = int(C * shift_ratio)
        fwd = jnp.concatenate(
            [v[:, 1:, :k], jnp.zeros_like(v[:, :1, :k])], axis=1)
        bwd = jnp.concatenate(
            [jnp.zeros_like(v[:, :1, k:2 * k]), v[:, :-1, k:2 * k]],
            axis=1)
        rest = v[:, :, 2 * k:]
        return jnp.concatenate([fwd, bwd, rest], axis=2).reshape(
            NT, C, H, W)
    return apply(f, x, name="temporal_shift")


def triplet_margin_loss(input, positive, negative, margin=1.0, p=2.0,
                        epsilon=1e-6, swap=False, reduction="mean",
                        name=None):
    input = ensure_tensor(input)
    positive = ensure_tensor(positive)
    negative = ensure_tensor(negative)

    def f(a, pos, neg):
        def d(u, v):
            return jnp.sum(jnp.abs(u - v + epsilon) ** p,
                           axis=-1) ** (1.0 / p)
        dp = d(a, pos)
        dn = d(a, neg)
        if swap:
            dn = jnp.minimum(dn, d(pos, neg))
        loss = jnp.maximum(dp - dn + margin, 0)
        if reduction == "mean":
            return jnp.mean(loss)
        if reduction == "sum":
            return jnp.sum(loss)
        return loss
    return apply(f, input, positive, negative, name="triplet_margin_loss")


def zeropad2d(x, padding, data_format="NCHW", name=None):
    x = ensure_tensor(x)
    if isinstance(padding, int):
        pl = pr = pt = pb = padding
    else:
        pl, pr, pt, pb = padding
    return apply(lambda a: jnp.pad(
        a, ((0, 0), (0, 0), (pt, pb), (pl, pr))), x, name="zeropad2d")
