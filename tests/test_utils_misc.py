"""Top-level namespace parity: utils / version / regularizer / batch /
hub / sysconfig / incubate.DistributedFusedLamb."""
import os

import numpy as np
import pytest

import paddle_tpu as P


class TestUtils:
    def test_run_check(self, capsys):
        assert P.utils.run_check()
        out = capsys.readouterr().out
        assert "installed successfully" in out

    def test_unique_name_guard(self):
        un = P.utils.unique_name
        with un.guard():
            a = un.generate("x")
            b = un.generate("x")
        assert a != b
        with un.guard():
            assert un.generate("x") == a  # counter reset inside guard

    def test_deprecated_warns(self):
        @P.utils.deprecated(update_to="new_fn", since="2.0")
        def old_fn():
            return 42
        with pytest.warns(DeprecationWarning):
            assert old_fn() == 42

    def test_version(self):
        assert P.version.full_version
        P.version.show()


class TestRegularizer:
    def test_l2_decay_changes_update(self):
        P.seed(0)

        def run(wd):
            P.seed(0)
            lin = P.nn.Linear(4, 4)
            opt = P.optimizer.SGD(0.1, parameters=lin.parameters(),
                                  weight_decay=wd)
            lin(P.to_tensor(np.ones((2, 4), np.float32))).sum().backward()
            opt.step()
            return np.asarray(lin.weight._data)

        w_plain = run(None)
        w_l2 = run(P.L2Decay(0.5))
        assert not np.allclose(w_plain, w_l2)

    def test_l1_decay_sign_subgradient(self):
        P.seed(0)
        lin = P.nn.Linear(3, 3)
        w0 = np.asarray(lin.weight._data).copy()
        opt = P.optimizer.SGD(0.1, parameters=lin.parameters(),
                              weight_decay=P.L1Decay(0.2))
        # zero loss: grads are 0, so the whole step is -lr*c*sign(w)
        (lin(P.to_tensor(np.zeros((1, 3), np.float32))).sum() * 0
         ).backward()
        opt.step()
        w1 = np.asarray(lin.weight._data)
        np.testing.assert_allclose(w1, w0 - 0.1 * 0.2 * np.sign(w0),
                                   atol=1e-6)


class TestBatchHubSysconfig:
    def test_batch_reader(self):
        r = P.batch(lambda: iter(range(10)), 4)
        sizes = [len(b) for b in r()]
        assert sizes == [4, 4, 2]
        r2 = P.batch(lambda: iter(range(10)), 4, drop_last=True)
        assert [len(b) for b in r2()] == [4, 4]

    def test_hub_local(self, tmp_path):
        (tmp_path / "hubconf.py").write_text(
            "def tiny(n=2):\n"
            "    'a tiny model'\n"
            "    import paddle_tpu as P\n"
            "    return P.nn.Linear(n, n)\n")
        assert "tiny" in P.hub.list(str(tmp_path))
        assert "tiny model" in P.hub.help(str(tmp_path), "tiny")
        m = P.hub.load(str(tmp_path), "tiny", n=3)
        assert m.weight.shape == [3, 3]
        with pytest.raises(RuntimeError):
            P.hub.load("user/repo", "tiny", source="github")

    def test_sysconfig_paths(self):
        assert os.path.isdir(P.sysconfig.get_include())
        assert os.path.isdir(P.sysconfig.get_lib())

    def test_callbacks_namespace(self):
        assert hasattr(P.callbacks, "ModelCheckpoint")

    def test_distributed_fused_lamb_maps_to_lamb(self):
        from paddle_tpu.incubate import DistributedFusedLamb
        o = DistributedFusedLamb(
            0.001, parameters=P.nn.Linear(2, 2).parameters(),
            clip_after_allreduce=True)
        assert type(o).__name__ == "Lamb"


class TestReviewRegressions:
    def test_cpp_extension_real_surface(self):
        """cpp_extension is REAL since round 6 (the old stub raised with
        ctypes guidance); the load/setup/CppExtension surface exists and
        load without `functions` fails loudly (no PD_BUILD_OP registry
        to introspect). The full compile path is tests/
        test_cpp_extension.py."""
        assert callable(P.utils.cpp_extension.load)
        assert callable(P.utils.cpp_extension.setup)
        assert P.utils.cpp_extension.CppExtension is not None
        with pytest.raises(ValueError, match="functions"):
            P.utils.cpp_extension.load(name="x", sources=["nope.cc"])

    def test_l1_subclass_detected(self):
        class MyL1(P.L1Decay):
            pass
        from paddle_tpu.optimizer.optimizer import _decay_coeff, _l1_coeff
        wd = MyL1(0.3)
        assert _decay_coeff(wd) == 0.0
        assert _l1_coeff(wd) == 0.3


class TestNamespaceProbes:
    def test_io_subset_random_sampler(self):
        s = P.io.SubsetRandomSampler([5, 2, 9])
        assert sorted(s) == [2, 5, 9] and len(s) == 3

    def test_amp_capability_probes(self):
        assert P.amp.is_bfloat16_supported() is True
        assert isinstance(P.amp.is_float16_supported(), bool)
        P.amp.debugging.check_numerics(P.to_tensor([1.0, 2.0]))
        with pytest.raises(RuntimeError):
            P.amp.debugging.check_numerics(
                P.to_tensor(np.asarray([np.inf], np.float32)))

    def test_device_probes(self):
        assert P.device.is_compiled_with_cuda() is False
        assert "cpu" in P.device.get_all_device_type()
        assert ":" in P.device.get_available_device()


class TestIncubateOps:
    def test_segment_ops(self):
        x = P.to_tensor(np.arange(10, dtype=np.float32).reshape(5, 2))
        ids = P.to_tensor(np.asarray([0, 0, 1, 2, 2]))
        from paddle_tpu import incubate as inc
        s = np.asarray(inc.segment_sum(x, ids)._data)
        np.testing.assert_allclose(s[0], [2, 4])
        m = np.asarray(inc.segment_mean(x, ids)._data)
        np.testing.assert_allclose(m[2], [7, 8])
        mx = np.asarray(inc.segment_max(x, ids)._data)
        np.testing.assert_allclose(mx[2], [8, 9])

    def test_graph_send_recv(self):
        from paddle_tpu import incubate as inc
        x = P.to_tensor(np.eye(3, dtype=np.float32))
        src = P.to_tensor(np.asarray([0, 1, 2]))
        dst = P.to_tensor(np.asarray([1, 2, 0]))
        out = np.asarray(inc.graph_send_recv(x, src, dst, "sum")._data)
        np.testing.assert_allclose(out, np.roll(np.eye(3), 1, axis=0))

    def test_fused_layers(self):
        from paddle_tpu.incubate.nn import (FusedLinear,
                                            FusedTransformerEncoderLayer)
        P.seed(0)
        l = FusedTransformerEncoderLayer(16, 4, 32)
        out = l(P.to_tensor(np.random.default_rng(1).standard_normal(
            (2, 6, 16)).astype(np.float32)))
        assert out.shape == [2, 6, 16]
        fl = FusedLinear(8, 4)
        assert fl(P.to_tensor(np.ones((2, 8), np.float32))).shape == [2, 4]

    def test_jit_enable_to_static_toggle(self):
        @P.jit.to_static
        def f(x):
            return x + 1
        x = P.to_tensor(np.zeros(2, np.float32))
        P.jit.enable_to_static(False)
        try:
            out = f(x)
        finally:
            P.jit.enable_to_static(True)
        np.testing.assert_allclose(np.asarray(out._data), 1.0)


class TestGeometric:
    def test_send_u_recv_and_ue(self):
        x = P.to_tensor(np.eye(3, dtype=np.float32))
        e = P.to_tensor(np.ones((3, 3), np.float32))
        src = P.to_tensor(np.asarray([0, 1, 2]))
        dst = P.to_tensor(np.asarray([1, 2, 0]))
        out = np.asarray(P.geometric.send_u_recv(x, src, dst)._data)
        np.testing.assert_allclose(out, np.roll(np.eye(3), 1, 0))
        out2 = np.asarray(P.geometric.send_ue_recv(
            x, e, src, dst, "add", "mean")._data)
        np.testing.assert_allclose(out2, np.roll(np.eye(3), 1, 0) + 1)
        uv = np.asarray(P.geometric.send_uv(x, x, src, dst, "add")._data)
        assert uv.shape == (3, 3)


class TestPerTestTimeLimit:
    def test_overrun_fails_with_the_limits_message_and_the_next_runs(
            self, tmp_path):
        """tests/conftest.py's limit, end to end: a fresh pytest run over
        a copy of the conftest, the limit patched to 1 s."""
        import shutil
        import subprocess
        import sys
        here = os.path.dirname(os.path.abspath(__file__))
        shutil.copy(os.path.join(here, "conftest.py"), tmp_path)
        (tmp_path / "test_limit.py").write_text(
            "import time\n"
            "import conftest\n"
            "conftest._TEST_LIMIT_S = 1\n"
            "def test_sleeps():\n"
            "    time.sleep(2)\n"
            "def test_next():\n"
            "    pass\n")
        p = subprocess.run(
            [sys.executable, "-m", "pytest", str(tmp_path), "-q",
             "-p", "no:cacheprovider", "--rootdir", str(tmp_path)],
            capture_output=True, text=True, timeout=120, cwd=str(tmp_path))
        assert "test_limit.py::test_sleeps ran over the per-test limit " \
            "of 1 s" in p.stdout, p.stdout[-2000:] + p.stderr[-2000:]
        assert "1 failed, 1 passed" in p.stdout, p.stdout[-2000:]
