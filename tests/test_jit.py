"""to_static tests: compiled-vs-eager parity, guards, fallback, autograd
through the jit boundary (reference dy2static test pattern — SURVEY.md §4
dygraph_to_static: run both modes, compare)."""
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.nn as nn
from paddle_tpu.jit import to_static


def t(a, sg=True):
    return P.to_tensor(np.asarray(a, np.float32), stop_gradient=sg)


class TestToStatic:
    def test_function_parity(self):
        def fn(x, y):
            return P.tanh(x) * y + x.sum()

        sfn = to_static(fn)
        x, y = t(np.random.randn(3, 3)), t(np.random.randn(3, 3))
        assert np.allclose(sfn(x, y).numpy(), fn(x, y).numpy(), atol=1e-6)

    def test_layer_method_parity(self):
        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        x = t(np.random.randn(5, 4))
        eager = net(x).numpy()
        net.forward = to_static(net.forward)
        compiled = net(x).numpy()
        assert np.allclose(eager, compiled, atol=1e-5)

    def test_params_not_baked(self):
        """Weight updates must be visible without retracing."""
        lin = nn.Linear(2, 2, bias_attr=False)
        sfn = to_static(lin.forward)
        x = t(np.ones((1, 2)))
        out1 = sfn(x).numpy()
        with P.no_grad():
            lin.weight.set_value(P.to_tensor(lin.weight.numpy() * 2))
        out2 = sfn(x).numpy()
        assert np.allclose(out2, out1 * 2, atol=1e-5)
        # only one trace should exist
        assert len(sfn._jit_cache) == 1

    def test_backward_through_jit(self):
        lin = nn.Linear(3, 1, bias_attr=False)
        sfn = to_static(lin.forward)
        x = t(np.random.randn(4, 3))
        loss = sfn(x).sum()
        loss.backward()
        assert lin.weight.grad is not None
        ref = np.broadcast_to(x.numpy().sum(0)[:, None], (3, 1))
        assert np.allclose(lin.weight.grad.numpy(), ref, atol=1e-5)

    def test_dropout_randomness_inside_jit(self):
        drop = nn.Dropout(0.5)
        sfn = to_static(lambda x: drop(x))
        x = t(np.ones((64, 64)))
        a = sfn(x).numpy()
        b = sfn(x).numpy()
        assert not np.array_equal(a, b)  # fresh mask per call, same trace
        assert 0.3 < (a == 0).mean() < 0.7

    def test_buffer_update_through_jit(self):
        bn = nn.BatchNorm1D(4)
        bn.train()
        sfn = to_static(bn.forward)
        x = t(np.random.randn(16, 4) * 2 + 3)
        sfn(x)
        assert not np.allclose(bn._mean.numpy(), 0.0)

    def test_eager_fallback_on_dynamic_control_flow(self):
        def fn(x):
            if float(x.sum().numpy()) > 0:  # data-dependent → graph break
                return x * 2
            return x * 3

        sfn = to_static(fn)
        x = t(np.ones(3))
        assert np.allclose(sfn(x).numpy(), 2.0)
        xneg = t(-np.ones(3))
        assert np.allclose(sfn(xneg).numpy(), -3.0)

    def test_shape_guard_retrace(self):
        calls = []

        def fn(x):
            calls.append(1)  # python body runs once per trace
            return x * 2

        sfn = to_static(fn)
        sfn(t(np.ones((2, 2))))
        sfn(t(np.ones((2, 2))))
        assert len(calls) == 1
        sfn(t(np.ones((3, 3))))  # new shape → retrace
        assert len(calls) == 2

    def test_decorator_on_layer(self):
        @to_static
        def fn(x):
            return P.exp(x)

        assert np.allclose(fn(t([0.0, 1.0])).numpy(), [1.0, np.e],
                           atol=1e-5)


class TestJitSaveLoad:
    def test_save_load_inference(self, tmp_path):
        from paddle_tpu.jit.save_load import InputSpec
        net = nn.Sequential(nn.Linear(4, 8), nn.ReLU(), nn.Linear(8, 2))
        net.eval()
        x = t(np.random.randn(3, 4))
        ref = net(x).numpy()
        path = str(tmp_path / "infer_model")
        P.jit.save(net, path, input_spec=[InputSpec([3, 4])])
        loaded = P.jit.load(path)
        out = loaded(x)
        assert np.allclose(out.numpy(), ref, atol=1e-5)


class TestNativeArtifact:
    """jit.save emits the C++-loadable triple (.mlir/.pdpjrt.txt/.pdparams.bin)
    consumed by native/pjrt_loader.cpp (executing it needs a PJRT plugin
    and a device; not covered here)."""

    def test_native_artifact_files(self, tmp_path):
        import json
        import os
        import numpy as np
        import paddle_tpu as P
        from paddle_tpu.jit import save as jit_save
        from paddle_tpu.jit.save_load import InputSpec

        net = P.nn.Sequential(P.nn.Linear(8, 16), P.nn.ReLU(),
                              P.nn.Linear(16, 4))
        prefix = str(tmp_path / "m")
        jit_save(net, prefix, input_spec=[InputSpec([2, 8], "float32")])
        meta = json.load(open(prefix + ".pdmodel.json"))
        assert meta.get("native_artifact"), meta
        assert os.path.getsize(prefix + ".mlir") > 0
        lines = open(prefix + ".pdpjrt.txt").read().strip().splitlines()
        # 4 params (2 weights + 2 biases) + 1 input
        assert len(lines) == 5
        assert lines[-1].split()[-2] == "input"
        nbytes = sum(np.prod([int(x) for x in l.split()[3:3 + int(l.split()[2])]],
                             dtype=np.int64) * 4
                     for l in lines if l.split()[-2] == "param")
        assert os.path.getsize(prefix + ".pdparams.bin") == nbytes

    def test_pjrt_loader_builds(self):
        from paddle_tpu.native import _build_pjrt, pd_infer_binary
        import os
        assert os.path.exists(_build_pjrt())
        assert os.path.exists(pd_infer_binary())
