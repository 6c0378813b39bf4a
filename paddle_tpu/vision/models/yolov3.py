"""YOLOv3 detection family: DarkNet-53 backbone + FPN neck + 3 heads.

Reference surface: the Paddle-ecosystem YOLOv3 (upstream
PaddleDetection ppdet/modeling/architectures/yolo.py +
backbones/darknet.py + necks/yolo_fpn.py, unverified — see SURVEY.md
§2.2 "Vision"). This assembles the already-oracle-tested op layer —
`vision.ops.yolo_loss` (analytic-oracle-exact), `yolo_box`, `nms` —
into the full trainable/deployable architecture: conv-BN-LeakyReLU
DarkNet residual stages → per-level 5-conv blocks with upsample routes
→ A·(5+C)-channel raw heads; training sums the three per-level YOLO
losses, inference decodes all levels with `yolo_box` and fuses them
through class-aware NMS.

TPU-first notes:
- The whole forward is static-shape convs (MXU via XLA) — one program
  per image size; nearest-neighbor upsampling is a reshape-broadcast.
- Training targets are built inside `yolo_loss`'s dense scatter maps —
  no ragged per-image host work in the step.
- Inference: the forward + yolo_box decode + mask-scan NMS ops are all
  jit-able device programs; `predict`'s per-image box assembly
  (thresholding, row packing) is host-side by design, after ONE
  batched device→host fetch.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

import paddle_tpu as P
from ... import vision
from ...nn import (BatchNorm2D, Layer, LayerList, LeakyReLU,
                   Sequential)
from ...nn import functional as F
from ...nn.conv import Conv2D

__all__ = ["YOLOv3", "YOLOv3Config", "DarkNet53", "yolov3_darknet53"]

_ANCHORS = [10, 13, 16, 30, 33, 23, 30, 61, 62, 45, 59, 119, 116, 90,
            156, 198, 373, 326]
_MASKS = ([6, 7, 8], [3, 4, 5], [0, 1, 2])


@dataclass
class YOLOv3Config:
    num_classes: int = 80
    anchors: tuple = tuple(_ANCHORS)
    anchor_masks: tuple = _MASKS
    ignore_thresh: float = 0.7
    stem_channels: int = 32
    depths: tuple = (1, 2, 8, 8, 4)  # DarkNet-53 residual counts
    nms_top_k: int = 100
    score_thresh: float = 0.01
    nms_iou: float = 0.45

    @staticmethod
    def tiny(**kw):
        return YOLOv3Config(**{**dict(
            num_classes=2, stem_channels=8, depths=(1, 1, 1, 1, 1),
            ignore_thresh=0.5), **kw})


def _conv_bn(cin, cout, k, stride=1):
    return Sequential(
        Conv2D(cin, cout, k, stride=stride, padding=k // 2,
               bias_attr=False),
        BatchNorm2D(cout), LeakyReLU(0.1))


class _Residual(Layer):
    def __init__(self, c):
        super().__init__()
        self.conv1 = _conv_bn(c, c // 2, 1)
        self.conv2 = _conv_bn(c // 2, c, 3)

    def forward(self, x):
        return x + self.conv2(self.conv1(x))


class DarkNet53(Layer):
    """Returns (C3, C4, C5) features at strides 8/16/32."""

    def __init__(self, cfg: YOLOv3Config):
        super().__init__()
        c = cfg.stem_channels
        self.stem = _conv_bn(3, c, 3)
        downs, stages = [], []
        for i, depth in enumerate(cfg.depths):
            downs.append(_conv_bn(c * 2 ** i, c * 2 ** (i + 1), 3,
                                  stride=2))
            stages.append(Sequential(*[
                _Residual(c * 2 ** (i + 1)) for _ in range(depth)]))
        self.downs = LayerList(downs)
        self.stages = LayerList(stages)

    def forward(self, x):
        x = self.stem(x)
        feats = []
        for down, stage in zip(self.downs, self.stages):
            x = stage(down(x))
            feats.append(x)
        return feats[-3], feats[-2], feats[-1]


class _NeckBlock(Layer):
    """The 5-conv YOLOv3 block; exposes the route (for upsampling) and
    the head input."""

    def __init__(self, cin, cmid):
        super().__init__()
        self.body = Sequential(
            _conv_bn(cin, cmid, 1), _conv_bn(cmid, cmid * 2, 3),
            _conv_bn(cmid * 2, cmid, 1), _conv_bn(cmid, cmid * 2, 3),
            _conv_bn(cmid * 2, cmid, 1))
        self.tip = _conv_bn(cmid, cmid * 2, 3)

    def forward(self, x):
        route = self.body(x)
        return route, self.tip(route)


class YOLOv3(Layer):
    def __init__(self, cfg: YOLOv3Config):
        super().__init__()
        if len(cfg.depths) != 5:
            # neck widths and head strides (32/16/8) assume the 5-stage
            # DarkNet pyramid; other depths would silently corrupt
            # target assignment via wrong downsample ratios
            raise ValueError(
                f"YOLOv3 requires exactly 5 backbone stages, got "
                f"depths={cfg.depths}")
        self.cfg = cfg
        self.backbone = DarkNet53(cfg)
        c = cfg.stem_channels
        c5, c4, c3 = c * 32, c * 16, c * 8
        a = len(cfg.anchor_masks[0])
        out_ch = a * (5 + cfg.num_classes)
        self.block5 = _NeckBlock(c5, c5 // 2)
        self.route5 = _conv_bn(c5 // 2, c4 // 2, 1)
        self.block4 = _NeckBlock(c4 + c4 // 2, c4 // 2)
        self.route4 = _conv_bn(c4 // 2, c3 // 2, 1)
        self.block3 = _NeckBlock(c3 + c3 // 2, c3 // 2)
        self.head5 = Conv2D(c5, out_ch, 1)
        self.head4 = Conv2D(c4, out_ch, 1)
        self.head3 = Conv2D(c3, out_ch, 1)

    def forward(self, img):
        """img [N, 3, H, W] -> three raw head maps (strides 32/16/8)."""
        c3, c4, c5 = self.backbone(img)
        r5, t5 = self.block5(c5)
        up5 = F.interpolate(self.route5(r5), scale_factor=2,
                            mode="nearest")
        r4, t4 = self.block4(P.concat([up5, c4], axis=1))
        up4 = F.interpolate(self.route4(r4), scale_factor=2,
                            mode="nearest")
        _, t3 = self.block3(P.concat([up4, c3], axis=1))
        return self.head5(t5), self.head4(t4), self.head3(t3)

    def get_loss(self, outputs, gt_box, gt_label, gt_score=None):
        """Sum of the three per-level YOLO losses (mean over batch)."""
        cfg = self.cfg
        total = None
        for out, mask, down in zip(outputs, cfg.anchor_masks,
                                   (32, 16, 8)):
            loss = vision.ops.yolo_loss(
                out, gt_box, gt_label, list(cfg.anchors), list(mask),
                cfg.num_classes, cfg.ignore_thresh, down,
                gt_score=gt_score).mean()
            total = loss if total is None else total + loss
        return total

    def predict(self, img, img_size):
        """Decode + class-aware NMS. Returns per-image lists of
        (label, score, x1, y1, x2, y2) arrays (host-side assembly over
        device-computed decode/NMS)."""
        cfg = self.cfg
        outputs = self.forward(img)
        boxes_all, scores_all = [], []
        for out, mask, down in zip(outputs, cfg.anchor_masks,
                                   (32, 16, 8)):
            sub_anchors = []
            for m in mask:
                sub_anchors += [cfg.anchors[2 * m],
                                cfg.anchors[2 * m + 1]]
            b, s = vision.ops.yolo_box(
                out, img_size, sub_anchors, cfg.num_classes,
                conf_thresh=cfg.score_thresh, downsample_ratio=down)
            boxes_all.append(b)       # [N, M, 4]
            scores_all.append(s)      # [N, M, C]
        boxes = P.concat(boxes_all, axis=1)
        scores = P.concat(scores_all, axis=1)
        # ONE device->host fetch for the whole batch (each fetch is a
        # blocking device sync)
        sc_all = np.asarray(scores._data)         # [N, M, C]
        bx_all = np.asarray(boxes._data)          # [N, M, 4]
        results = []
        n, c = sc_all.shape[0], sc_all.shape[2]
        for i in range(n):
            sc = sc_all[i]                        # [M, C]
            bx = bx_all[i]                        # [M, 4]
            cls = sc.argmax(axis=1)
            best = sc.max(axis=1)
            keep_mask = best > cfg.score_thresh
            idx = np.nonzero(keep_mask)[0]
            if idx.size == 0:
                results.append(np.zeros((0, 6), np.float32))
                continue
            keep = vision.ops.nms(
                P.to_tensor(bx[idx]), iou_threshold=cfg.nms_iou,
                scores=P.to_tensor(best[idx]),
                category_idxs=P.to_tensor(cls[idx].astype(np.int64)),
                categories=list(range(c)), top_k=cfg.nms_top_k)
            kept = np.asarray(keep._data)
            rows = np.concatenate(
                [cls[idx][kept][:, None].astype(np.float32),
                 best[idx][kept][:, None].astype(np.float32),
                 bx[idx][kept]], axis=1)
            results.append(rows.astype(np.float32))
        return results


def yolov3_darknet53(num_classes=80, **kw):
    return YOLOv3(YOLOv3Config(num_classes=num_classes, **kw))
