"""U-Net segmentation family: shape contracts, dice-term oracle, and a
synthetic-mask overfit that must reach high mIoU (end-to-end evidence
for encoder/skip/transposed-conv-decoder agreement — also the first
model-level exercise of the fixed conv2d_transpose)."""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.vision.models.unet import UNet, UNetConfig


class TestUNet:
    def test_shapes_full_resolution(self):
        m = UNet(UNetConfig.tiny())
        m.eval()
        x = P.to_tensor(np.zeros((2, 1, 32, 32), np.float32))
        y = m(x)
        assert y.shape == [2, 3, 32, 32]

    def test_dice_term_matches_manual_formula(self):
        m = UNet(UNetConfig.tiny())
        rng = np.random.default_rng(0)
        logits = rng.standard_normal((1, 3, 8, 8)).astype(np.float32)
        labels = rng.integers(0, 3, (1, 8, 8)).astype(np.int64)
        lt, yt = P.to_tensor(logits), P.to_tensor(labels)
        ce_only = float(m.loss(lt, yt, dice_weight=0.0))
        both = float(m.loss(lt, yt, dice_weight=1.0))
        # manual dice on softmax probs vs one-hot
        e = np.exp(logits - logits.max(1, keepdims=True))
        probs = e / e.sum(1, keepdims=True)
        oneh = np.eye(3)[labels].transpose(0, 3, 1, 2)
        inter = (probs * oneh).sum((2, 3))
        denom = probs.sum((2, 3)) + oneh.sum((2, 3))
        dice = 1.0 - (2 * inter / (denom + 1e-5)).mean()
        np.testing.assert_allclose(both - ce_only, dice, atol=1e-5)

    def test_overfit_segments_synthetic_shapes(self):
        from paddle_tpu.optimizer import Adam
        P.seed(0)
        m = UNet(UNetConfig.tiny())
        m.train()
        opt = Adam(5e-3, parameters=m.parameters())
        rng = np.random.default_rng(0)
        img = rng.standard_normal((2, 1, 32, 32)).astype(np.float32)
        img *= 0.1
        yy, xx = np.mgrid[0:32, 0:32]
        mask = np.zeros((2, 32, 32), np.int64)
        disc = (yy - 16) ** 2 + (xx - 16) ** 2 < 64
        mask[:, disc] = 1
        mask[:, :, 26:30] = 2
        img[:, 0][np.broadcast_to(disc, (2, 32, 32))] += 1.0
        img[:, 0, :, 26:30] -= 1.0
        x, y = P.to_tensor(img), P.to_tensor(mask)
        # the subject is the family, not the eager path: the loss is one
        # traced program (`to_static`) and backward() differentiates that
        # one program — not one XLA compile an op
        loss_of = P.jit.to_static(
            lambda x, y: m.loss(m(x), y, dice_weight=0.5))
        for _ in range(40):
            loss = loss_of(x, y)
            loss.backward()
            opt.step()
            opt.clear_grad()
        m.eval()
        pred = np.asarray(m(x)._data).argmax(1)
        ious = []
        for c in range(3):
            inter = ((pred == c) & (mask == c)).sum()
            union = ((pred == c) | (mask == c)).sum()
            ious.append(inter / max(union, 1))
        assert np.mean(ious) > 0.8, ious
