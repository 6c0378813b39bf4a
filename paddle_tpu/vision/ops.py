"""paddle_tpu.vision.ops — detection ops (reference: paddle.vision.ops
nms/roi_align/roi_pool/deform_conv2d/box_coder/yolo_box — upstream
python/paddle/vision/ops.py + CUDA kernels in paddle/phi/kernels/gpu/,
unverified; see SURVEY.md §2.2 "Vision").

TPU-native design: every op is expressed with static shapes and
vectorized gathers so it compiles under jit —
- `nms` is the O(n²) mask formulation (pairwise IoU matrix + a lax scan
  over score rank) instead of the reference's dynamic worklist: no
  data-dependent shapes, MXU/VPU-friendly, exact same result;
- `roi_align`/`roi_pool` sample with batched bilinear gathers (one
  gather per pooling bin sample, vmapped over ROIs);
- `deform_conv2d` is im2col-with-deformed-offsets: bilinear-sample the
  input at offset positions → one big matmul (the MXU path);
- `box_coder`/`yolo_box` are pure elementwise decodes.
Outputs are fixed-size with validity masks where the reference returns
ragged results (the XLA static-shape contract; callers slice by the
returned count).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.autograd import apply as _apply, mark_stable
from ..core.tensor import Tensor
from ..nn.layer import Layer
from ..ops._base import ensure_tensor

__all__ = ["nms", "roi_align", "roi_pool", "box_coder", "yolo_box",
           "deform_conv2d", "RoIAlign", "RoIPool", "DeformConv2D"]


def _box_iou(boxes):
    """Pairwise IoU of [N, 4] (x1, y1, x2, y2) boxes."""
    area = jnp.maximum(boxes[:, 2] - boxes[:, 0], 0) * \
        jnp.maximum(boxes[:, 3] - boxes[:, 1], 0)
    lt = jnp.maximum(boxes[:, None, :2], boxes[None, :, :2])
    rb = jnp.minimum(boxes[:, None, 2:], boxes[None, :, 2:])
    wh = jnp.maximum(rb - lt, 0)
    inter = wh[..., 0] * wh[..., 1]
    union = area[:, None] + area[None, :] - inter
    return jnp.where(union > 0, inter / union, 0.0)


def nms(boxes, iou_threshold=0.3, scores=None, category_idxs=None,
        categories=None, top_k=None):
    """Greedy NMS. Returns kept indices sorted by descending score
    (fixed length N with -1 padding when compiled; eager returns the
    trimmed result like the reference).

    Multi-class (category_idxs given) offsets boxes per class so
    suppression never crosses classes (the reference's batched_nms
    trick).
    """
    b = ensure_tensor(boxes)._data.astype(jnp.float32)
    n = b.shape[0]
    sc = (ensure_tensor(scores)._data.astype(jnp.float32)
          if scores is not None else jnp.arange(n, 0, -1, jnp.float32))
    if category_idxs is not None:
        cat = ensure_tensor(category_idxs)._data
        span = jnp.max(b) - jnp.min(b) + 1.0
        b = b + (cat.astype(jnp.float32) * span)[:, None]

    order = jnp.argsort(-sc)
    iou = _box_iou(b)[order][:, order]

    def step(keep, i):
        # keep[i] stays True only if no higher-ranked kept box overlaps
        sup = jnp.any(keep & (jnp.arange(n) < i) & (iou[i] > iou_threshold))
        keep = keep.at[i].set(~sup)
        return keep, None

    keep0 = jnp.ones((n,), bool)
    keep, _ = jax.lax.scan(step, keep0, jnp.arange(n))
    kept_sorted = jnp.where(keep, order, -1)  # rank order, -1 = suppressed
    # compact: kept indices first (stable), -1 padding after
    key = jnp.where(keep, jnp.arange(n), n)
    perm = jnp.argsort(key)
    out = kept_sorted[perm]
    if isinstance(out, jax.core.Tracer):
        # top_k is a Python int, so the slice is shape-static and legal
        # under trace; -1 padding semantics are preserved.
        if top_k is not None:
            out = out[:top_k]
        return Tensor(out)
    out = out[out >= 0]
    if top_k is not None:
        out = out[:top_k]
    return Tensor(out)


def _bilinear(feat, y, x):
    """Sample feat [C, H, W] at fractional (y, x) — zero outside."""
    H, W = feat.shape[1], feat.shape[2]
    y0 = jnp.floor(y).astype(jnp.int32)
    x0 = jnp.floor(x).astype(jnp.int32)
    y1, x1 = y0 + 1, x0 + 1
    wy1 = y - y0
    wx1 = x - x0
    wy0, wx0 = 1 - wy1, 1 - wx1

    def tap(yi, xi, w):
        valid = (yi >= 0) & (yi < H) & (xi >= 0) & (xi < W)
        v = feat[:, jnp.clip(yi, 0, H - 1), jnp.clip(xi, 0, W - 1)]
        return v * (w * valid)

    return (tap(y0, x0, wy0 * wx0) + tap(y0, x1, wy0 * wx1) +
            tap(y1, x0, wy1 * wx0) + tap(y1, x1, wy1 * wx1))


def roi_align(x, boxes, boxes_num, output_size, spatial_scale=1.0,
              sampling_ratio=-1, aligned=True):
    """RoIAlign (reference semantics incl. `aligned` half-pixel shift).

    x: [N, C, H, W]; boxes: [R, 4] in input coords; boxes_num: [N] ROIs
    per image (prefix-assigns ROIs to images). Returns [R, C, ph, pw].

    Numerics note: with sampling_ratio<=0 the reference adapts the
    sub-sample count per ROI (ceil(roi_size/pooled_size)); that is a
    data-dependent shape, illegal under XLA's static-shape contract, so
    this implementation uses a fixed ratio of 2 (the common detector
    setting). Outputs deviate slightly from reference numerics for ROIs
    much larger than the output grid; pass an explicit sampling_ratio to
    pin the reference behavior you need.
    """
    xd = ensure_tensor(x)._data.astype(jnp.float32)
    bx = ensure_tensor(boxes)._data.astype(jnp.float32)
    bn = ensure_tensor(boxes_num)._data
    ph, pw = (output_size if isinstance(output_size, (tuple, list))
              else (output_size, output_size))
    ratio = 2 if sampling_ratio <= 0 else int(sampling_ratio)
    off = 0.5 if aligned else 0.0
    img_of_roi = jnp.repeat(jnp.arange(bn.shape[0]), bn,
                            total_repeat_length=bx.shape[0])

    # sample positions inside the ROI: `ratio` uniform sub-samples per
    # output cell (uniform over the whole ROI == per-bin sampling)
    cell = jnp.arange(ph * ratio, dtype=jnp.float32)
    frac_y = (cell + 0.5) / (ph * ratio)  # uniform — equals per-bin sampling
    cellx = jnp.arange(pw * ratio, dtype=jnp.float32)
    frac_x = (cellx + 0.5) / (pw * ratio)

    def one_roi(box, img):
        x1, y1, x2, y2 = box * spatial_scale
        x1, y1 = x1 - off, y1 - off
        x2, y2 = x2 - off, y2 - off
        h = y2 - y1 if aligned else jnp.maximum(y2 - y1, 1.0)
        w = x2 - x1 if aligned else jnp.maximum(x2 - x1, 1.0)
        ys = y1 + frac_y * h                      # [ph*ratio]
        xs = x1 + frac_x * w                      # [pw*ratio]
        yy = jnp.repeat(ys, pw * ratio)
        xx = jnp.tile(xs, ph * ratio)
        vals = _bilinear(xd[img], yy, xx)         # [C, ph*r*pw*r]
        C = vals.shape[0]
        vals = vals.reshape(C, ph, ratio, pw, ratio)
        return vals.mean(axis=(2, 4))             # [C, ph, pw]

    out = jax.vmap(one_roi)(bx, img_of_roi)
    return Tensor(out)


def roi_pool(x, boxes, boxes_num, output_size, spatial_scale=1.0):
    """RoIPool via dense max over adaptive bins (gather formulation)."""
    xd = ensure_tensor(x)._data.astype(jnp.float32)
    bx = ensure_tensor(boxes)._data.astype(jnp.float32)
    bn = ensure_tensor(boxes_num)._data
    ph, pw = (output_size if isinstance(output_size, (tuple, list))
              else (output_size, output_size))
    H, W = xd.shape[2], xd.shape[3]
    img_of_roi = jnp.repeat(jnp.arange(bn.shape[0]), bn,
                            total_repeat_length=bx.shape[0])
    iy = jnp.arange(H)
    ix = jnp.arange(W)

    def one_roi(box, img):
        x1 = jnp.floor(box[0] * spatial_scale).astype(jnp.int32)
        y1 = jnp.floor(box[1] * spatial_scale).astype(jnp.int32)
        x2 = jnp.ceil(box[2] * spatial_scale).astype(jnp.int32)
        y2 = jnp.ceil(box[3] * spatial_scale).astype(jnp.int32)
        hh = jnp.maximum(y2 - y1, 1).astype(jnp.float32)
        ww = jnp.maximum(x2 - x1, 1).astype(jnp.float32)
        # bin index of every pixel (pixels outside the ROI get -1)
        by = jnp.floor((iy - y1).astype(jnp.float32) * ph / hh).astype(
            jnp.int32)
        bxx = jnp.floor((ix - x1).astype(jnp.float32) * pw / ww).astype(
            jnp.int32)
        by = jnp.where((iy >= y1) & (iy < jnp.maximum(y2, y1 + 1)),
                       jnp.clip(by, 0, ph - 1), -1)
        bxx = jnp.where((ix >= x1) & (ix < jnp.maximum(x2, x1 + 1)),
                        jnp.clip(bxx, 0, pw - 1), -1)
        onehot_y = (by[:, None] == jnp.arange(ph)[None, :])   # [H, ph]
        onehot_x = (bxx[:, None] == jnp.arange(pw)[None, :])  # [W, pw]
        feat = xd[img]                                        # [C, H, W]
        neg = jnp.finfo(jnp.float32).min
        masked = jnp.where(onehot_y[None, :, None, :, None] &
                           onehot_x[None, None, :, None, :],
                           feat[:, :, :, None, None], neg)
        pooled = masked.max(axis=(1, 2))                      # [C, ph, pw]
        return jnp.where(pooled == neg, 0.0, pooled)

    return Tensor(jax.vmap(one_roi)(bx, img_of_roi))


def box_coder(prior_box, prior_box_var, target_box,
              code_type="encode_center_size", box_normalized=True,
              axis=0):
    """Encode/decode boxes against priors (reference box_coder)."""
    pb = ensure_tensor(prior_box)._data.astype(jnp.float32)
    pbv = (ensure_tensor(prior_box_var)._data.astype(jnp.float32)
           if prior_box_var is not None else None)
    tb = ensure_tensor(target_box)._data.astype(jnp.float32)
    norm = 0.0 if box_normalized else 1.0
    pw = pb[:, 2] - pb[:, 0] + norm
    phh = pb[:, 3] - pb[:, 1] + norm
    pcx = pb[:, 0] + pw * 0.5
    pcy = pb[:, 1] + phh * 0.5
    if code_type == "encode_center_size":
        tw = tb[:, 2] - tb[:, 0] + norm
        th = tb[:, 3] - tb[:, 1] + norm
        tcx = tb[:, 0] + tw * 0.5
        tcy = tb[:, 1] + th * 0.5
        dx = (tcx[:, None] - pcx[None, :]) / pw[None, :]
        dy = (tcy[:, None] - pcy[None, :]) / phh[None, :]
        dw = jnp.log(tw[:, None] / pw[None, :])
        dh = jnp.log(th[:, None] / phh[None, :])
        out = jnp.stack([dx, dy, dw, dh], axis=-1)
        if pbv is not None:
            out = out / pbv[None, :, :]
        return Tensor(out)
    # decode: target [N, M, 4] deltas against priors on `axis`
    if tb.ndim == 2:
        tb = tb[:, None, :]
    d = tb * (pbv[None, :, :] if pbv is not None else 1.0)
    shp = (1, -1) if axis == 0 else (-1, 1)
    pw_, ph_ = pw.reshape(shp), phh.reshape(shp)
    pcx_, pcy_ = pcx.reshape(shp), pcy.reshape(shp)
    cx = d[..., 0] * pw_ + pcx_
    cy = d[..., 1] * ph_ + pcy_
    w = jnp.exp(d[..., 2]) * pw_
    h = jnp.exp(d[..., 3]) * ph_
    return Tensor(jnp.stack([cx - w / 2, cy - h / 2,
                             cx + w / 2 - norm, cy + h / 2 - norm],
                            axis=-1))


def yolo_box(x, img_size, anchors, class_num, conf_thresh=0.01,
             downsample_ratio=32, clip_bbox=True, scale_x_y=1.0,
             iou_aware=False, iou_aware_factor=0.5):
    """Decode YOLOv3 head output [N, A*(5+C), H, W] → boxes + scores."""
    xd = ensure_tensor(x)._data.astype(jnp.float32)
    imgs = ensure_tensor(img_size)._data.astype(jnp.float32)
    N, _, H, W = xd.shape
    A = len(anchors) // 2
    an = jnp.asarray(anchors, jnp.float32).reshape(A, 2)
    feat = xd.reshape(N, A, 5 + class_num, H, W)
    gx = jnp.arange(W, dtype=jnp.float32)
    gy = jnp.arange(H, dtype=jnp.float32)
    bx = (jax.nn.sigmoid(feat[:, :, 0]) * scale_x_y -
          (scale_x_y - 1) / 2 + gx[None, None, None, :]) / W
    by = (jax.nn.sigmoid(feat[:, :, 1]) * scale_x_y -
          (scale_x_y - 1) / 2 + gy[None, None, :, None]) / H
    bw = jnp.exp(feat[:, :, 2]) * an[None, :, 0, None, None] / \
        (W * downsample_ratio)
    bh = jnp.exp(feat[:, :, 3]) * an[None, :, 1, None, None] / \
        (H * downsample_ratio)
    obj = jax.nn.sigmoid(feat[:, :, 4])
    cls = jax.nn.sigmoid(feat[:, :, 5:])
    score = obj[:, :, None] * cls                      # [N, A, C, H, W]
    imw = imgs[:, 1].reshape(N, 1, 1, 1)
    imh = imgs[:, 0].reshape(N, 1, 1, 1)
    x1 = (bx - bw / 2) * imw
    y1 = (by - bh / 2) * imh
    x2 = (bx + bw / 2) * imw
    y2 = (by + bh / 2) * imh
    if clip_bbox:
        x1 = jnp.clip(x1, 0, imw - 1)
        y1 = jnp.clip(y1, 0, imh - 1)
        x2 = jnp.clip(x2, 0, imw - 1)
        y2 = jnp.clip(y2, 0, imh - 1)
    boxes = jnp.stack([x1, y1, x2, y2], axis=-1).reshape(N, -1, 4)
    mask = (obj > conf_thresh)[:, :, None]
    scores = jnp.where(mask, score, 0.0)
    scores = scores.transpose(0, 1, 3, 4, 2).reshape(N, -1, class_num)
    return Tensor(boxes), Tensor(scores)


def deform_conv2d(x, offset, weight, bias=None, stride=1, padding=0,
                  dilation=1, deformable_groups=1, groups=1, mask=None):
    """Deformable conv v1/v2 as bilinear im2col + MXU matmul.

    x: [N, Cin, H, W]; offset: [N, 2*dg*kh*kw, Ho, Wo];
    weight: [Cout, Cin/g, kh, kw]; mask (v2): [N, dg*kh*kw, Ho, Wo].
    """
    xd = ensure_tensor(x)._data.astype(jnp.float32)
    od = ensure_tensor(offset)._data.astype(jnp.float32)
    wd = ensure_tensor(weight)._data.astype(jnp.float32)
    md = ensure_tensor(mask)._data.astype(jnp.float32) \
        if mask is not None else None
    sh, sw = (stride if isinstance(stride, (tuple, list))
              else (stride, stride))
    ph, pw = (padding if isinstance(padding, (tuple, list))
              else (padding, padding))
    dh, dw = (dilation if isinstance(dilation, (tuple, list))
              else (dilation, dilation))
    N, Cin, H, W = xd.shape
    Cout, _, kh, kw = wd.shape
    Ho = (H + 2 * ph - dh * (kh - 1) - 1) // sh + 1
    Wo = (W + 2 * pw - dw * (kw - 1) - 1) // sw + 1
    dg = deformable_groups
    off = od.reshape(N, dg, kh * kw, 2, Ho, Wo)

    oy = jnp.arange(Ho) * sh - ph
    ox = jnp.arange(Wo) * sw - pw
    ky = jnp.arange(kh) * dh
    kx = jnp.arange(kw) * dw
    # per kernel tap (kh*kw), per out position
    tap_y = (oy[None, :, None] +
             jnp.repeat(ky, kw)[:, None, None]).astype(jnp.float32)
    tap_x = (ox[None, None, :] +
             jnp.tile(kx, kh)[:, None, None]).astype(jnp.float32)

    cg = Cin // dg  # channels per deformable group
    # v2 mask defaults to all-ones (v1 semantics)
    msk_r = (md.reshape(N, dg, kh * kw, Ho * Wo) if md is not None
             else jnp.ones((N, dg, kh * kw, Ho * Wo), jnp.float32))

    def one_image(img, offs, msk):
        def one_group(g):
            feat = jax.lax.dynamic_slice_in_dim(img, g * cg, cg, axis=0)
            yy = tap_y + offs[g, :, 0]            # [kk, Ho, Wo]
            xx = tap_x + offs[g, :, 1]
            vals = jax.vmap(
                lambda y_, x_: _bilinear(feat, y_.reshape(-1),
                                         x_.reshape(-1)))(yy, xx)
            # vals: [kk, cg, Ho*Wo]
            return vals * msk[g][:, None, :]
        return jnp.concatenate([one_group(g) for g in range(dg)], axis=1)

    cols = jax.vmap(one_image)(
        xd, off.reshape(N, dg, kh * kw, 2, Ho, Wo), msk_r)
    # cols: [N, kk, Cin, Ho*Wo] → output = weight · cols
    wcol = wd.reshape(Cout, Cin // groups * kh * kw)
    out_groups = []
    cpg_in = Cin // groups
    cpg_out = Cout // groups
    cols_t = cols.transpose(0, 2, 1, 3)  # [N, Cin, kk, Ho*Wo]
    for g in range(groups):
        seg = cols_t[:, g * cpg_in:(g + 1) * cpg_in]  # [N,cpg,kk,HoWo]
        seg = seg.reshape(N, cpg_in * kh * kw, Ho * Wo)
        wseg = wcol[g * cpg_out:(g + 1) * cpg_out]
        out_groups.append(jnp.einsum("ok,nkp->nop", wseg, seg))
    out = jnp.concatenate(out_groups, axis=1).reshape(N, Cout, Ho, Wo)
    if bias is not None:
        out = out + ensure_tensor(bias)._data.reshape(1, -1, 1, 1)
    return Tensor(out)


class RoIAlign(Layer):
    def __init__(self, output_size, spatial_scale=1.0):
        super().__init__()
        self._size = output_size
        self._scale = spatial_scale

    def forward(self, x, boxes, boxes_num):
        return roi_align(x, boxes, boxes_num, self._size, self._scale)


class RoIPool(Layer):
    def __init__(self, output_size, spatial_scale=1.0):
        super().__init__()
        self._size = output_size
        self._scale = spatial_scale

    def forward(self, x, boxes, boxes_num):
        return roi_pool(x, boxes, boxes_num, self._size, self._scale)


class DeformConv2D(Layer):
    def __init__(self, in_channels, out_channels, kernel_size, stride=1,
                 padding=0, dilation=1, deformable_groups=1, groups=1,
                 weight_attr=None, bias_attr=None):
        super().__init__()
        from ..core.tensor import Parameter
        from ..nn import initializer as init
        kh, kw = (kernel_size if isinstance(kernel_size, (tuple, list))
                  else (kernel_size, kernel_size))
        self._args = (stride, padding, dilation, deformable_groups, groups)
        fan_in = in_channels * kh * kw
        w = init.XavierUniform(fan_in=fan_in,
                               fan_out=out_channels * kh * kw)(
            (out_channels, in_channels // groups, kh, kw), jnp.float32)
        self.weight = Parameter(w)
        if bias_attr is not False:
            self.bias = Parameter(jnp.zeros((out_channels,), jnp.float32))
        else:
            self.bias = None

    def forward(self, x, offset, mask=None):
        s, p, d, dg, g = self._args
        return deform_conv2d(x, offset, self.weight, self.bias, s, p, d,
                             dg, g, mask)


def prior_box(input, image, min_sizes, max_sizes=None, aspect_ratios=(1.0,),
              variance=(0.1, 0.1, 0.2, 0.2), flip=False, clip=False,
              steps=(0.0, 0.0), offset=0.5, min_max_aspect_ratios_order
              =False, name=None):
    """SSD prior (anchor) box generation (reference paddle.vision.ops.
    prior_box — upstream python/paddle/vision/ops.py, unverified).
    input: [N, C, H, W] feature map; image: [N, C, Him, Wim]. Returns
    (boxes [H, W, num_priors, 4] normalized xmin/ymin/xmax/ymax,
    variances broadcast to the same shape). Pure elementwise decode —
    one fused XLA kernel."""
    input, image = ensure_tensor(input), ensure_tensor(image)
    H, W = input.shape[2], input.shape[3]
    Him, Wim = image.shape[2], image.shape[3]
    ars = [1.0]
    for ar in aspect_ratios:
        if not any(abs(ar - a) < 1e-6 for a in ars):
            ars.append(float(ar))
            if flip:
                ars.append(1.0 / float(ar))
    min_sizes = [float(m) for m in min_sizes]
    max_sizes = [float(m) for m in (max_sizes or [])]
    if max_sizes and len(max_sizes) != len(min_sizes):
        raise ValueError("max_sizes must pair with min_sizes")
    step_w = float(steps[0]) or Wim / W
    step_h = float(steps[1]) or Him / H
    # per-cell prior (w, h) list in the reference's order
    whs = []
    for i, ms in enumerate(min_sizes):
        if min_max_aspect_ratios_order:
            whs.append((ms, ms))
            if max_sizes:
                s = (ms * max_sizes[i]) ** 0.5
                whs.append((s, s))
            for ar in ars:
                if abs(ar - 1.0) < 1e-6:
                    continue
                whs.append((ms * ar ** 0.5, ms / ar ** 0.5))
        else:
            for ar in ars:
                whs.append((ms * ar ** 0.5, ms / ar ** 0.5))
            if max_sizes:
                s = (ms * max_sizes[i]) ** 0.5
                whs.append((s, s))

    def f(_in, _img):
        cx = (jnp.arange(W, dtype=jnp.float32) + offset) * step_w
        cy = (jnp.arange(H, dtype=jnp.float32) + offset) * step_h
        cx = cx[None, :, None] / Wim                        # [1, W, 1]
        cy = cy[:, None, None] / Him                        # [H, 1, 1]
        bw = jnp.asarray([w for w, _ in whs], jnp.float32)[None, None, :] \
            / (2.0 * Wim)
        bh = jnp.asarray([h for _, h in whs], jnp.float32)[None, None, :] \
            / (2.0 * Him)
        boxes = jnp.stack(jnp.broadcast_arrays(
            cx - bw, cy - bh, cx + bw, cy + bh), axis=-1)
        if clip:
            boxes = jnp.clip(boxes, 0.0, 1.0)
        var = jnp.broadcast_to(jnp.asarray(variance, jnp.float32),
                               boxes.shape)
        return boxes, var

    return _apply(f, input, image, name="prior_box")


def matrix_nms(bboxes, scores, score_threshold, post_threshold=0.0,
               nms_top_k=400, keep_top_k=200, use_gaussian=False,
               gaussian_sigma=2.0, background_label=0, normalized=True,
               return_index=False, return_rois_num=True, name=None):
    """Matrix NMS (SOLOv2; reference paddle.vision.ops.matrix_nms —
    unverified). Decay-based soft suppression: for each candidate the
    min over higher-scored same-class boxes of decay(iou)/decay(max iou
    of the suppressor) — all-pairs, no sequential worklist, so it is
    one masked matrix program on the VPU (the design the paper picked
    for parallel hardware; exact, not an approximation).

    bboxes [N, M, 4], scores [N, C, M]. Static-shape contract: returns
    (out [N*keep_top_k, 6] rows (label, score, x1, y1, x2, y2) with
    score 0 padding, rois_num [N], index [N*keep_top_k, 1])."""
    bboxes, scores = ensure_tensor(bboxes), ensure_tensor(scores)
    N, M = bboxes.shape[0], bboxes.shape[1]
    C = scores.shape[1]
    # pixel-coordinate boxes measure +1 wide/tall (same convention as
    # box_coder's `norm` above)
    off = 0.0 if normalized else 1.0

    def one_image(boxes, scr):
        # flatten candidates over classes (skip background)
        cls_ids = jnp.arange(C)
        keep_cls = cls_ids != background_label
        flat_scores = jnp.where(keep_cls[:, None], scr, -1.0).reshape(-1)
        flat_cls = jnp.repeat(cls_ids, M)
        flat_box = jnp.tile(jnp.arange(M), C)
        ok = flat_scores > score_threshold
        flat_scores = jnp.where(ok, flat_scores, -1.0)
        k = min(nms_top_k, C * M)
        top_scores, top_idx = jax.lax.top_k(flat_scores, k)
        tcls = flat_cls[top_idx]
        tbox = boxes[flat_box[top_idx]]                       # [k, 4]
        valid = top_scores > score_threshold
        # pairwise IoU over the top-k
        area = jnp.maximum(tbox[:, 2] - tbox[:, 0] + off, 0.0) * \
            jnp.maximum(tbox[:, 3] - tbox[:, 1] + off, 0.0)
        lt = jnp.maximum(tbox[:, None, :2], tbox[None, :, :2])
        rb = jnp.minimum(tbox[:, None, 2:], tbox[None, :, 2:])
        wh = jnp.maximum(rb - lt + off, 0.0)
        inter = wh[..., 0] * wh[..., 1]
        iou = inter / jnp.maximum(area[:, None] + area[None, :] - inter,
                                  1e-10)
        # suppressor mask: higher-scored (earlier in top-k), same class
        ii = jnp.arange(k)
        sup = (ii[None, :] < ii[:, None]) & \
            (tcls[:, None] == tcls[None, :]) & \
            valid[None, :] & valid[:, None]
        iou_s = jnp.where(sup, iou, 0.0)                      # [i, j]
        # comp[j]: suppressor j's own max IoU with ITS higher-scored
        # peers (the paper's normalizer)
        comp = jnp.max(iou_s, axis=1)                         # [k]
        if use_gaussian:
            decay = jnp.exp(-(iou_s ** 2 - comp[None, :] ** 2)
                            / gaussian_sigma)
        else:
            decay = (1.0 - iou_s) / jnp.maximum(1.0 - comp[None, :],
                                                1e-10)
        decay = jnp.where(sup, decay, 1.0)
        factor = jnp.min(decay, axis=1)
        new_scores = jnp.where(valid, top_scores * factor, 0.0)
        keep = new_scores > post_threshold
        new_scores = jnp.where(keep, new_scores, 0.0)
        kk = min(keep_top_k, k)
        fin_scores, fin_idx = jax.lax.top_k(new_scores, kk)
        rows = jnp.concatenate([
            tcls[fin_idx, None].astype(boxes.dtype),
            fin_scores[:, None].astype(boxes.dtype),
            tbox[fin_idx]], axis=1)
        cnt = jnp.sum((fin_scores > 0).astype(jnp.int32))
        src = flat_box[top_idx][fin_idx]
        return rows, cnt, src[:, None].astype(jnp.int32)

    def f(ba, sa):
        rows, cnt, idx = jax.vmap(one_image)(ba, sa)
        return (rows.reshape(-1, 6), cnt.astype(jnp.int32),
                idx.reshape(-1, 1))

    out, rois_num, index = _apply(f, bboxes, scores,
                                  name="matrix_nms")
    res = [out]
    if return_rois_num:
        res.append(rois_num)
    if return_index:
        res.append(index)
    return tuple(res) if len(res) > 1 else res[0]


def psroi_pool(x, boxes, boxes_num, output_size, spatial_scale=1.0,
               name=None):
    """Position-sensitive RoI pooling (R-FCN; reference paddle.vision.
    ops.psroi_pool — unverified). x: [N, C, H, W] with C = out_c*ps*ps;
    each (ph, pw) output bin average-pools its OWN channel group —
    static-shape bin averaging via masked means, vmapped over rois."""
    x, boxes = ensure_tensor(x), ensure_tensor(boxes)
    if isinstance(output_size, int):
        oh = ow = output_size
    else:
        oh, ow = output_size
    if oh != ow:
        raise NotImplementedError("psroi_pool needs square output_size "
                                  "(position-sensitive channel split)")
    N, C, H, W = x.shape
    if C % (oh * ow) != 0:
        raise ValueError(f"channels {C} not divisible by "
                         f"output_size^2 {oh * ow}")
    out_c = C // (oh * ow)
    bn = [int(v) for v in np.asarray(boxes_num.numpy()
                                     if hasattr(boxes_num, "numpy")
                                     else boxes_num)]
    img_of_roi = np.repeat(np.arange(len(bn)), bn)

    def one_roi(box, img):
        x1, y1, x2, y2 = (box[i] * spatial_scale for i in range(4))
        rw = jnp.maximum(x2 - x1, 0.1)
        rh = jnp.maximum(y2 - y1, 0.1)
        bw, bh = rw / ow, rh / oh
        ph = jnp.arange(oh, dtype=jnp.float32)
        pw = jnp.arange(ow, dtype=jnp.float32)
        hs = jnp.floor(y1 + ph * bh)[:, None]        # [oh, 1]
        he = jnp.ceil(y1 + (ph + 1) * bh)[:, None]
        ws = jnp.floor(x1 + pw * bw)[None, :]        # [1, ow]
        we = jnp.ceil(x1 + (pw + 1) * bw)[None, :]
        ih = jnp.arange(H, dtype=jnp.float32)
        iw = jnp.arange(W, dtype=jnp.float32)
        # bin membership masks [oh, H] / [ow, W]
        mh = (ih[None, :] >= hs) & (ih[None, :] < he)  # [oh, H]
        mw = (iw[None, :] >= ws.T) & (iw[None, :] < we.T)  # [ow, W]
        feat = img.reshape(out_c, oh * ow, H, W)
        # per (ph, pw): mean over the bin of channel group ph*ow+pw
        m2 = (mh[:, None, :, None] & mw[None, :, None, :]).astype(
            jnp.float32)                              # [oh, ow, H, W]
        cnt = jnp.maximum(m2.sum((-1, -2)), 1.0)       # [oh, ow]
        grp = feat.reshape(out_c, oh, ow, H, W)
        s = jnp.einsum("cijhw,ijhw->cij", grp, m2)
        return s / cnt

    def f(xa, ba):
        imgs = xa[jnp.asarray(img_of_roi)]            # [R, C, H, W]
        return jax.vmap(one_roi)(ba, imgs)

    return _apply(f, x, boxes, name="psroi_pool")


def read_file(filename, name=None):
    """paddle.vision.ops.read_file: raw bytes as a uint8 1-D tensor
    (host IO — eager only, like the reference CPU kernel)."""
    with open(filename, "rb") as fh:
        data = fh.read()
    return Tensor(jnp.asarray(np.frombuffer(data, dtype=np.uint8)))


def decode_jpeg(x, mode="unchanged", name=None):
    """paddle.vision.ops.decode_jpeg: JPEG bytes tensor → [C, H, W]
    uint8 (PIL-backed host decode; the reference uses nvjpeg on GPU —
    same contract, eager only)."""
    import io as _io

    from PIL import Image
    x = ensure_tensor(x)
    raw = bytes(np.asarray(x._data, dtype=np.uint8))
    img = Image.open(_io.BytesIO(raw))
    if mode != "unchanged":
        img = img.convert(mode.upper() if mode != "gray" else "L")
    arr = np.asarray(img, dtype=np.uint8)
    if arr.ndim == 2:
        arr = arr[None]
    else:
        arr = arr.transpose(2, 0, 1)
    return Tensor(jnp.asarray(arr))


__all__ += ["prior_box", "matrix_nms", "psroi_pool", "read_file",
            "decode_jpeg"]


@functools.lru_cache(maxsize=None)
def _yolo_loss_fn(anchors, amask, class_num, ignore_thresh,
                  downsample_ratio, delta, sx):
    """The pure loss of one head configuration, built once: apply()'s
    micro-jit keys on the function's identity, and a closure made in
    every yolo_loss() call would have its fori_loop compiled by XLA
    again on every call (forward and backward)."""
    A = len(amask)
    n_anchors = len(anchors) // 2

    def bce(logit, label):
        # sigmoid cross entropy with logits, stable form
        return jnp.maximum(logit, 0) - logit * label + \
            jnp.log1p(jnp.exp(-jnp.abs(logit)))

    def f(xa, gb, gl, *rest):
        N, _, H, W = xa.shape
        B = gb.shape[1]
        in_w, in_h = W * downsample_ratio, H * downsample_ratio
        aw_all = jnp.asarray(anchors[0::2], jnp.float32) / in_w  # normalized
        ah_all = jnp.asarray(anchors[1::2], jnp.float32) / in_h
        aw = aw_all[jnp.asarray(amask)]
        ah = ah_all[jnp.asarray(amask)]
        gs = rest[0] if rest else jnp.ones((N, B), jnp.float32)
        xa = xa.reshape(N, A, 5 + class_num, H, W).astype(jnp.float32)
        tx, ty, tw, th = xa[:, :, 0], xa[:, :, 1], xa[:, :, 2], xa[:, :, 3]
        tobj = xa[:, :, 4]
        tcls = xa[:, :, 5:]                       # [N, A, cls, H, W]
        gb = gb.astype(jnp.float32)
        gs = gs.astype(jnp.float32)
        valid = (gb[..., 2] > 0) & (gb[..., 3] > 0)          # [N, B]

        # decoded pred boxes (normalized) for the ignore mask
        ix = jnp.arange(W, dtype=jnp.float32)[None, None, None, :]
        iy = jnp.arange(H, dtype=jnp.float32)[None, None, :, None]
        px = (ix + sx * jax.nn.sigmoid(tx) - 0.5 * (sx - 1.0)) / W
        py = (iy + sx * jax.nn.sigmoid(ty) - 0.5 * (sx - 1.0)) / H
        pw = aw[None, :, None, None] * jnp.exp(tw)
        phh = ah[None, :, None, None] * jnp.exp(th)
        # IoU pred [N,A,H,W] x gt [N,B] -> max over B
        px1, py1 = px - pw / 2, py - phh / 2
        px2, py2 = px + pw / 2, py + phh / 2
        gx1 = (gb[..., 0] - gb[..., 2] / 2)[:, None, None, None, :]
        gy1 = (gb[..., 1] - gb[..., 3] / 2)[:, None, None, None, :]
        gx2 = (gb[..., 0] + gb[..., 2] / 2)[:, None, None, None, :]
        gy2 = (gb[..., 1] + gb[..., 3] / 2)[:, None, None, None, :]
        iw = jnp.maximum(jnp.minimum(px2[..., None], gx2)
                         - jnp.maximum(px1[..., None], gx1), 0.0)
        ih = jnp.maximum(jnp.minimum(py2[..., None], gy2)
                         - jnp.maximum(py1[..., None], gy1), 0.0)
        inter = iw * ih
        union = (pw * phh)[..., None] + \
            (gb[..., 2] * gb[..., 3])[:, None, None, None, :] - inter
        iou = jnp.where(valid[:, None, None, None, :],
                        inter / jnp.maximum(union, 1e-10), 0.0)
        ignore = jnp.max(iou, axis=-1) > ignore_thresh       # [N,A,H,W]

        # per-gt responsible anchor over ALL anchors (wh IoU)
        ginter = jnp.minimum(gb[..., 2:3], aw_all[None, None, :]) * \
            jnp.minimum(gb[..., 3:4], ah_all[None, None, :])
        gunion = gb[..., 2:3] * gb[..., 3:4] + \
            (aw_all * ah_all)[None, None, :] - ginter
        best = jnp.argmax(ginter / jnp.maximum(gunion, 1e-10), -1)
        slot_of = jnp.full((n_anchors,), -1, jnp.int32)
        for s, a in enumerate(amask):
            slot_of = slot_of.at[a].set(s)
        slot = slot_of[best]                                  # [N, B]
        gi = jnp.clip((gb[..., 0] * W).astype(jnp.int32), 0, W - 1)
        gj = jnp.clip((gb[..., 1] * H).astype(jnp.int32), 0, H - 1)
        assigned = valid & (slot >= 0)

        # dense target maps via deterministic per-gt scatter
        zero = jnp.zeros((N, A, H, W), jnp.float32)
        maps0 = {"pos": zero, "tx": zero, "ty": zero, "tw": zero,
                 "th": zero, "wt": zero, "score": zero,
                 "label": jnp.zeros((N, A, H, W), jnp.int32)}
        nidx = jnp.arange(N)

        def body(b, maps):
            ok = assigned[:, b]                                # [N]
            s = jnp.where(ok, slot[:, b], 0)
            jj = jnp.where(ok, gj[:, b], 0)
            ii = jnp.where(ok, gi[:, b], 0)

            def put(m, v):
                cur = m[nidx, s, jj, ii]
                new = jnp.where(ok, v, cur)
                return m.at[nidx, s, jj, ii].set(
                    new.astype(m.dtype))

            txv = gb[:, b, 0] * W - ii.astype(jnp.float32)
            tyv = gb[:, b, 1] * H - jj.astype(jnp.float32)
            twv = jnp.log(jnp.maximum(
                gb[:, b, 2] / jnp.maximum(aw[s], 1e-10), 1e-10))
            thv = jnp.log(jnp.maximum(
                gb[:, b, 3] / jnp.maximum(ah[s], 1e-10), 1e-10))
            wtv = (2.0 - gb[:, b, 2] * gb[:, b, 3]) * gs[:, b]
            maps = dict(maps)
            maps["pos"] = put(maps["pos"], jnp.ones((N,)))
            maps["tx"] = put(maps["tx"], txv)
            maps["ty"] = put(maps["ty"], tyv)
            maps["tw"] = put(maps["tw"], twv)
            maps["th"] = put(maps["th"], thv)
            maps["wt"] = put(maps["wt"], wtv)
            maps["score"] = put(maps["score"], gs[:, b])
            maps["label"] = put(maps["label"], gl[:, b].astype(jnp.int32))
            return maps

        maps = jax.lax.fori_loop(0, B, body, maps0)
        pos = maps["pos"]

        loss_xy = maps["wt"] * (bce(tx, maps["tx"]) + bce(ty, maps["ty"]))
        loss_wh = maps["wt"] * (jnp.abs(tw - maps["tw"])
                                + jnp.abs(th - maps["th"]))
        obj_pos = maps["score"] * bce(tobj, jnp.ones_like(tobj))
        obj_neg = bce(tobj, jnp.zeros_like(tobj))
        loss_obj = jnp.where(pos > 0, obj_pos,
                             jnp.where(ignore, 0.0, obj_neg))
        onehot = jax.nn.one_hot(maps["label"], class_num,
                                axis=2)                     # [N,A,cls,H,W]
        cls_target = onehot * (1.0 - delta) + (1 - onehot) * delta
        loss_cls = maps["score"][:, :, None] * \
            bce(tcls, cls_target) * pos[:, :, None]
        per_sample = (jnp.sum((loss_xy + loss_wh) * pos, axis=(1, 2, 3))
                      + jnp.sum(loss_obj, axis=(1, 2, 3))
                      + jnp.sum(loss_cls, axis=(1, 2, 3, 4)))
        return per_sample

    return mark_stable(f)


def yolo_loss(x, gt_box, gt_label, anchors, anchor_mask, class_num,
              ignore_thresh, downsample_ratio, gt_score=None,
              use_label_smooth=True, name=None, scale_x_y=1.0):
    """YOLOv3 training loss (reference paddle.vision.ops.yolo_loss /
    phi yolov3_loss kernel — upstream unverified; formulas follow the
    YOLOv3 paper + the reference kernel structure):

    - x: [N, A*(5+class_num), H, W] raw head output (A = len(anchor_mask));
    - gt_box [N, B, 4] normalized (cx, cy, w, h), gt_label [N, B],
      gt_score [N, B] (mixup weight, default 1);
    - per-gt responsibility: best wh-IoU over ALL anchors; the gt is
      assigned only if that anchor belongs to this head's anchor_mask,
      at cell (floor(cx*W), floor(cy*H));
    - sigmoid-CE for x/y/objectness/class, L1 for w/h, box weight
      (2 − w·h)·score; negatives whose best IoU with any gt exceeds
      `ignore_thresh` are ignored; label smoothing moves targets to
      (1−δ, δ), δ = min(1/class_num, 1/40).

    TPU-native: everything is dense [N, A, H, W] target maps built by a
    lax.fori_loop of per-gt scatters (deterministic last-writer, B is
    small) + one fused elementwise loss — no dynamic shapes. Returns
    the per-sample loss [N]."""
    x = ensure_tensor(x)
    gt_box, gt_label = ensure_tensor(gt_box), ensure_tensor(gt_label)
    args = [x, gt_box, gt_label]
    if gt_score is not None:
        args.append(ensure_tensor(gt_score))
    A = len(anchor_mask)
    C = x.shape[1]
    if C != A * (5 + class_num):
        raise ValueError(f"x channels {C} != len(anchor_mask)*(5+cls) "
                         f"= {A * (5 + class_num)}")
    delta = min(1.0 / class_num, 1.0 / 40.0) if use_label_smooth else 0.0
    f = _yolo_loss_fn(tuple(float(a) for a in anchors),
                      tuple(int(a) for a in anchor_mask), int(class_num),
                      float(ignore_thresh), int(downsample_ratio), delta,
                      float(scale_x_y))
    return _apply(f, *args, name="yolo_loss")


__all__ += ["yolo_loss"]


def distribute_fpn_proposals(fpn_rois, min_level, max_level, refer_level,
                             refer_scale, pixel_offset=False,
                             rois_num=None, name=None):
    """Assign RoIs to FPN levels by scale (reference paddle.vision.ops.
    distribute_fpn_proposals — unverified): level = floor(log2(
    sqrt(area)/refer_scale + eps)) + refer_level, clamped to
    [min_level, max_level]. Returns (multi_rois list low→high level,
    restore_ind [R, 1], rois_num_per_level list or None).

    EAGER-ONLY: per-level counts are data-dependent (ragged output), so
    this is a host op like the reference's CPU kernel; under tracing it
    raises (use level masks for a compiled pipeline)."""
    fpn_rois = ensure_tensor(fpn_rois)
    if isinstance(fpn_rois._data, jax.core.Tracer):
        raise RuntimeError(
            "distribute_fpn_proposals is eager-only (ragged outputs); "
            "compute level masks instead inside jit")
    rois = np.asarray(fpn_rois._data, np.float32)
    off = 1.0 if pixel_offset else 0.0
    w = np.maximum(rois[:, 2] - rois[:, 0] + off, 0.0)
    h = np.maximum(rois[:, 3] - rois[:, 1] + off, 0.0)
    scale = np.sqrt(w * h)
    lvl = np.floor(np.log2(scale / float(refer_scale) + 1e-8)) \
        + refer_level
    lvl = np.clip(lvl, min_level, max_level).astype(np.int64)
    multi_rois, order = [], []
    for L in range(min_level, max_level + 1):
        idx = np.nonzero(lvl == L)[0]
        order.append(idx)
        multi_rois.append(Tensor(jnp.asarray(rois[idx])))
    order = np.concatenate(order) if order else np.zeros(0, np.int64)
    restore = np.empty_like(order)
    restore[order] = np.arange(order.shape[0])
    restore_ind = Tensor(jnp.asarray(restore[:, None].astype(np.int32)))
    if rois_num is not None:
        rn = np.asarray(ensure_tensor(rois_num)._data)
        img_of = np.repeat(np.arange(rn.shape[0]), rn)
        per_level = [
            Tensor(jnp.asarray(np.bincount(
                img_of[lvl == L], minlength=rn.shape[0]).astype(np.int32)))
            for L in range(min_level, max_level + 1)]
        return multi_rois, restore_ind, per_level
    return multi_rois, restore_ind, None


def generate_proposals(scores, bbox_deltas, img_size, anchors, variances,
                       pre_nms_top_n=6000, post_nms_top_n=1000,
                       nms_thresh=0.5, min_size=0.1, eta=1.0,
                       pixel_offset=False, return_rois_num=False,
                       name=None):
    """RPN proposal generation (reference paddle.vision.ops.
    generate_proposals — unverified): decode anchor deltas → clip to the
    image → drop boxes smaller than min_size → top pre_nms_top_n by
    score → greedy NMS → top post_nms_top_n. EAGER-ONLY host op (ragged
    output), composed from box_coder-style decode + this module's nms.

    scores [N, A, H, W]; bbox_deltas [N, 4A, H, W]; img_size [N, 2]
    (h, w); anchors [H, W, A, 4] or [H*W*A, 4]; variances same shape.
    Returns (rpn_rois [R, 4], rpn_roi_probs [R, 1][, rois_num])."""
    scores, bbox_deltas = ensure_tensor(scores), ensure_tensor(bbox_deltas)
    if isinstance(scores._data, jax.core.Tracer):
        raise RuntimeError("generate_proposals is eager-only (ragged "
                           "outputs)")
    sc = np.asarray(scores._data, np.float32)
    bd = np.asarray(bbox_deltas._data, np.float32)
    isz = np.asarray(ensure_tensor(img_size)._data, np.float32)
    anc = np.asarray(ensure_tensor(anchors)._data, np.float32).reshape(-1, 4)
    var = np.asarray(ensure_tensor(variances)._data,
                     np.float32).reshape(-1, 4)
    N, A, H, W = sc.shape
    off = 1.0 if pixel_offset else 0.0
    all_rois, all_probs, nums = [], [], []
    for n in range(N):
        s = sc[n].transpose(1, 2, 0).reshape(-1)          # [H*W*A]
        d = bd[n].reshape(A, 4, H, W).transpose(2, 3, 0, 1).reshape(-1, 4)
        k = min(pre_nms_top_n, s.shape[0])
        top = np.argsort(-s)[:k]
        s_k, d_k, a_k, v_k = s[top], d[top], anc[top], var[top]
        # decode (box_coder decode_center_size semantics)
        aw = a_k[:, 2] - a_k[:, 0] + off
        ah = a_k[:, 3] - a_k[:, 1] + off
        acx = a_k[:, 0] + aw / 2
        acy = a_k[:, 1] + ah / 2
        cx = v_k[:, 0] * d_k[:, 0] * aw + acx
        cy = v_k[:, 1] * d_k[:, 1] * ah + acy
        bw = np.exp(np.minimum(v_k[:, 2] * d_k[:, 2], 10.0)) * aw
        bh = np.exp(np.minimum(v_k[:, 3] * d_k[:, 3], 10.0)) * ah
        boxes = np.stack([cx - bw / 2, cy - bh / 2,
                          cx + bw / 2 - off, cy + bh / 2 - off], 1)
        ih, iw = isz[n, 0], isz[n, 1]
        boxes[:, 0::2] = np.clip(boxes[:, 0::2], 0, iw - off)
        boxes[:, 1::2] = np.clip(boxes[:, 1::2], 0, ih - off)
        keep = ((boxes[:, 2] - boxes[:, 0] + off >= min_size) &
                (boxes[:, 3] - boxes[:, 1] + off >= min_size))
        boxes, s_k = boxes[keep], s_k[keep]
        if boxes.shape[0]:
            kept = np.asarray(nms(Tensor(jnp.asarray(boxes)),
                                  iou_threshold=nms_thresh,
                                  scores=Tensor(jnp.asarray(s_k)),
                                  top_k=post_nms_top_n).numpy())
            boxes, s_k = boxes[kept], s_k[kept]
        all_rois.append(boxes)
        all_probs.append(s_k[:, None])
        nums.append(boxes.shape[0])
    rois = Tensor(jnp.asarray(np.concatenate(all_rois, 0)
                              if all_rois else np.zeros((0, 4))))
    probs = Tensor(jnp.asarray(np.concatenate(all_probs, 0)
                               if all_probs else np.zeros((0, 1))))
    if return_rois_num:
        return rois, probs, Tensor(jnp.asarray(np.asarray(nums, np.int32)))
    return rois, probs


__all__ += ["distribute_fpn_proposals", "generate_proposals"]
