"""YOLOv3 detection family: architecture contracts + a single-image
overfit that must LOCALIZE (the end-to-end evidence that backbone,
neck, heads, yolo_loss target assignment, yolo_box decode, and NMS
fusion all agree on coordinate conventions)."""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.vision.models.yolov3 import (DarkNet53, YOLOv3,
                                             YOLOv3Config)


def _iou(b, g):
    ix = max(0.0, min(b[2], g[2]) - max(b[0], g[0]))
    iy = max(0.0, min(b[3], g[3]) - max(b[1], g[1]))
    inter = ix * iy
    union = ((b[2] - b[0]) * (b[3] - b[1])
             + (g[2] - g[0]) * (g[3] - g[1]) - inter)
    return inter / union


class TestYOLOv3:
    # Shape contracts and the loss/predict plumbing below are the family's,
    # not the eager path's: the forward runs as one traced program
    # (`to_static`), not ~170 XLA compiles an input shape.
    def test_head_shapes_and_strides(self):
        m = P.jit.to_static(YOLOv3(YOLOv3Config.tiny()))
        m.eval()
        x = P.to_tensor(np.zeros((2, 3, 64, 64), np.float32))
        o5, o4, o3 = m(x)
        a, c = 3, 2
        assert o5.shape == [2, a * (5 + c), 2, 2]    # stride 32
        assert o4.shape == [2, a * (5 + c), 4, 4]    # stride 16
        assert o3.shape == [2, a * (5 + c), 8, 8]    # stride 8

    def test_backbone_feature_pyramid(self):
        cfg = YOLOv3Config.tiny()
        bb = P.jit.to_static(DarkNet53(cfg))
        bb.eval()
        c3, c4, c5 = bb(P.to_tensor(np.zeros((1, 3, 64, 64),
                                             np.float32)))
        assert c3.shape == [1, cfg.stem_channels * 8, 8, 8]
        assert c4.shape == [1, cfg.stem_channels * 16, 4, 4]
        assert c5.shape == [1, cfg.stem_channels * 32, 2, 2]

    def test_overfit_localizes_synthetic_box(self):
        """30 Adam steps on one image with one bright box: the top
        prediction must be the right class with IoU > 0.3 — this fails
        if ANY of target assignment, decode, or NMS disagree on the
        (cx, cy, w, h)/pixel conventions.

        The subject is the family, not the eager path: the 30 steps run
        through hapi's jitted step (`Model.train_batch`) and predict's
        forward through `to_static` — two compiles, not one an op. The
        eager loop's steady state is guarded by
        test_autograd.py::TestEagerLoopsCompileOnce."""
        from paddle_tpu.optimizer import Adam
        P.seed(0)
        rng = np.random.default_rng(0)
        img = rng.standard_normal((1, 3, 64, 64)).astype(np.float32)
        img *= 0.1
        img[0, :, 16:48, 8:40] += 1.0  # pixels x1=8 y1=16 x2=40 y2=48
        m = YOLOv3(YOLOv3Config.tiny())
        m.train()
        opt = Adam(3e-3, parameters=m.parameters())
        x = P.to_tensor(img)
        gb = P.to_tensor(np.array([[[0.375, 0.5, 0.5, 0.5]]],
                                  np.float32))
        gl = P.to_tensor(np.array([[1]], np.int32))
        model = P.Model(m)
        model.prepare(opt, loss=lambda o5, o4, o3, b, l: m.get_loss(
            (o5, o4, o3), b, l))
        losses = [model.train_batch([x], [gb, gl]) for _ in range(30)]
        assert losses[-1] < losses[0] * 0.2, (losses[0], losses[-1])
        m.eval()
        P.jit.to_static(m)
        res = m.predict(x, P.to_tensor(np.array([[64, 64]],
                                                np.int32)))[0]
        assert len(res) > 0
        top = res[0]
        assert int(top[0]) == 1, res[:3]          # class
        assert top[1] > 0.5, res[:3]              # confidence
        assert _iou(top[2:], (8, 16, 40, 48)) > 0.3, res[:3]

    def test_multiimage_batch_loss_and_predict(self):
        m = P.jit.to_static(YOLOv3(YOLOv3Config.tiny()))
        m.eval()
        rng = np.random.default_rng(1)
        x = P.to_tensor(rng.standard_normal((2, 3, 64, 64))
                        .astype(np.float32))
        gb = P.to_tensor(rng.uniform(0.2, 0.6, (2, 3, 4))
                         .astype(np.float32))
        gl = P.to_tensor(rng.integers(0, 2, (2, 3)).astype(np.int32))
        loss = m.get_loss(m(x), gb, gl)
        assert np.isfinite(float(loss))
        res = m.predict(x, P.to_tensor(np.array([[64, 64], [64, 64]],
                                                np.int32)))
        assert len(res) == 2
        for rows in res:
            assert rows.shape[1] == 6
