"""Vision model zoo — forward shape + grad-flow checks for every family.

Mirrors the reference's per-model vision tests (SURVEY.md §4) at tiny
input sizes where the architecture allows it (fixed-topology nets like
AlexNet/Inception need their native input size).
"""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.vision import models as M

NUM_CLASSES = 10


def _check(model, hw, num_classes=NUM_CLASSES):
    """Output shape + grad flow of a family — not of the eager path: the
    forward runs as one traced program (`to_static`), and `backward()`
    differentiates that one program, instead of one XLA compile an op
    (densenet121: ~1,500 of them)."""
    model.eval()
    P.jit.to_static(model)
    x = P.to_tensor(np.random.default_rng(0)
                    .standard_normal((2, 3, hw, hw)).astype(np.float32))
    x.stop_gradient = False
    out = model(x)
    assert tuple(out.shape) == (2, num_classes)
    out.sum().backward()
    grads = [p.grad for p in model.parameters() if not p.stop_gradient]
    assert any(g is not None for g in grads)


# 32px for the fully-convolutional (adaptive-pool) families — the test
# checks output shape + grad flow, which is input-size-invariant; 64px
# cost ~4x the conv time for no extra coverage (round-4 durations trim)
@pytest.mark.parametrize("name,factory,hw", [
    ("alexnet", lambda: M.alexnet(num_classes=NUM_CLASSES), 224),
    ("squeezenet1_1",
     lambda: M.squeezenet1_1(num_classes=NUM_CLASSES), 32),
    ("densenet121", lambda: M.densenet121(num_classes=NUM_CLASSES), 32),
    ("shufflenet_v2_x0_5",
     lambda: M.shufflenet_v2_x0_5(num_classes=NUM_CLASSES), 32),
    ("mobilenet_v1",
     lambda: M.mobilenet_v1(scale=0.25, num_classes=NUM_CLASSES), 32),
    ("mobilenet_v3_small",
     lambda: M.mobilenet_v3_small(num_classes=NUM_CLASSES), 32),
    ("resnext50_32x4d",
     lambda: M.resnext50_32x4d(num_classes=NUM_CLASSES), 32),
])
def test_zoo_forward_backward(name, factory, hw):
    P.seed(0)
    _check(factory(), hw)


def test_inception_v3():
    P.seed(0)
    model = P.jit.to_static(M.inception_v3(num_classes=NUM_CLASSES))
    model.eval()
    x = P.to_tensor(np.random.default_rng(0)
                    .standard_normal((1, 3, 299, 299)).astype(np.float32))
    out = model(x)
    assert tuple(out.shape) == (1, NUM_CLASSES)


def test_googlenet_aux_heads():
    P.seed(0)
    model = P.jit.to_static(M.googlenet(num_classes=NUM_CLASSES))
    x = P.to_tensor(np.random.default_rng(0)
                    .standard_normal((1, 3, 224, 224)).astype(np.float32))
    model.train()
    out, a1, a2 = model(x)
    assert tuple(out.shape) == tuple(a1.shape) == tuple(a2.shape) \
        == (1, NUM_CLASSES)
    model.eval()
    out = model(x)
    assert tuple(out.shape) == (1, NUM_CLASSES)
