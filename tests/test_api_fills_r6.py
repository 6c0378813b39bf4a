"""Round-6 API fills: the paddle.linalg namespace-shadow regression,
linalg.matrix_transpose, fractional max pooling (torch-oracle in kernel
mode, paper-formula self-oracle in disjoint mode), and the decode-phase
masked_multihead_attention (numpy oracle). Reference paths unverified —
mount empty; see SURVEY.md §2.2."""
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.nn.functional as F


class TestLinalgNamespace:
    def test_package_not_shadowed_fresh_process(self):
        """`import paddle_tpu` alone must expose the full linalg package
        (cond/ormqr/vecdot) — the ops star-import used to shadow it with
        the ops.linalg submodule (round-6 fix in __init__)."""
        code = (
            "import jax; jax.config.update('jax_platforms', 'cpu')\n"
            "import paddle_tpu as P\n"
            "assert P.linalg.__file__.endswith('linalg/__init__.py'), "
            "P.linalg.__file__\n"
            "for n in ('cond', 'ormqr', 'vecdot', 'matrix_transpose',"
            " 'cholesky', 'svd_lowrank'):\n"
            "    assert hasattr(P.linalg, n), n\n"
            "print('ok')\n")
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=280)
        assert p.returncode == 0, p.stderr[-1500:]
        assert "ok" in p.stdout

    def test_matrix_transpose(self):
        x = P.to_tensor(np.arange(24, dtype=np.float32).reshape(2, 3, 4))
        y = P.linalg.matrix_transpose(x)
        assert y.shape == [2, 4, 3]
        assert np.allclose(y.numpy(), np.swapaxes(x.numpy(), -1, -2))
        with pytest.raises(ValueError):
            P.linalg.matrix_transpose(P.to_tensor(np.float32([1, 2])))


class TestFractionalMaxPool:
    U = 0.37

    def test_2d_kernel_mode_torch_oracle(self):
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(0)
        x = rng.standard_normal((2, 3, 16, 20)).astype(np.float32)
        ref = torch.nn.functional.fractional_max_pool2d(
            torch.tensor(x), kernel_size=3, output_size=(5, 7),
            _random_samples=torch.full((2, 3, 2), self.U,
                                       dtype=torch.float32))
        got = F.fractional_max_pool2d(P.to_tensor(x), output_size=(5, 7),
                                      kernel_size=3, random_u=self.U)
        assert np.array_equal(got.numpy(), ref.numpy())

    def test_3d_kernel_mode_torch_oracle(self):
        torch = pytest.importorskip("torch")
        rng = np.random.default_rng(1)
        x = rng.standard_normal((1, 2, 8, 10, 12)).astype(np.float32)
        ref = torch.nn.functional.fractional_max_pool3d(
            torch.tensor(x), kernel_size=2, output_size=(3, 4, 5),
            _random_samples=torch.full((1, 2, 3), self.U,
                                       dtype=torch.float32))
        got = F.fractional_max_pool3d(P.to_tensor(x), output_size=(3, 4, 5),
                                      kernel_size=2, random_u=self.U)
        assert np.array_equal(got.numpy(), ref.numpy())

    def test_2d_disjoint_regions_oracle(self):
        rng = np.random.default_rng(2)
        x = rng.standard_normal((2, 3, 16, 20)).astype(np.float32)
        outs = (5, 7)

        def edges(in_sz, out_sz):
            al = in_sz / out_sz
            e = (np.ceil(al * (np.arange(out_sz + 1) + self.U))
                 - np.ceil(al * self.U)).astype(int)
            e[0], e[-1] = 0, in_sz
            return e

        eh, ew = edges(16, outs[0]), edges(20, outs[1])
        ref = np.zeros((2, 3) + outs, np.float32)
        for i in range(outs[0]):
            for j in range(outs[1]):
                ref[:, :, i, j] = x[:, :, eh[i]:eh[i + 1],
                                    ew[j]:ew[j + 1]].max((2, 3))
        got = F.fractional_max_pool2d(P.to_tensor(x), output_size=outs,
                                      random_u=self.U)
        assert np.array_equal(got.numpy(), ref)

    def test_mask_addresses_maxima_and_grads_flow(self):
        rng = np.random.default_rng(3)
        x = rng.standard_normal((2, 3, 16, 20)).astype(np.float32)
        out, mask = F.fractional_max_pool2d(
            P.to_tensor(x), output_size=(5, 7), kernel_size=3,
            random_u=self.U, return_mask=True)
        flat = x.reshape(2, 3, -1)
        gathered = np.take_along_axis(
            flat, mask.numpy().reshape(2, 3, -1), axis=2)
        assert np.array_equal(gathered.reshape(tuple(out.shape)),
                              out.numpy())
        xt = P.to_tensor(x)
        xt.stop_gradient = False
        y = F.fractional_max_pool2d(xt, output_size=(5, 7), kernel_size=3,
                                    random_u=self.U)
        y.sum().backward()
        nz = int((xt.grad.numpy() != 0).sum())
        assert 0 < nz <= 2 * 3 * 5 * 7

    def test_layers_and_random_u_draw(self):
        from paddle_tpu.nn import FractionalMaxPool2D, FractionalMaxPool3D
        x = P.to_tensor(np.random.default_rng(4).standard_normal(
            (1, 2, 9, 9)).astype(np.float32))
        P.seed(7)
        a = FractionalMaxPool2D(output_size=4)(x)  # framework-drawn u
        assert a.shape == [1, 2, 4, 4]
        x3 = P.to_tensor(np.random.default_rng(5).standard_normal(
            (1, 1, 6, 6, 6)).astype(np.float32))
        b = FractionalMaxPool3D(output_size=2, kernel_size=2,
                                random_u=0.5)(x3)
        assert b.shape == [1, 1, 2, 2, 2]

    def test_errors(self):
        x = P.to_tensor(np.zeros((1, 1, 4, 4), np.float32))
        with pytest.raises(ValueError):
            F.fractional_max_pool2d(x, output_size=2, random_u=1.5)
        with pytest.raises(ValueError):
            F.fractional_max_pool2d(x, output_size=8, random_u=0.5)
        with pytest.raises(ValueError):
            F.fractional_max_pool2d(
                P.to_tensor(np.zeros((4, 4), np.float32)),
                output_size=2, random_u=0.5)


class TestMaskedMultiheadAttention:
    def _oracle(self, x, cache, bias, mask, lens):
        b = x.shape[0]
        _, _, nh, L, hd = cache.shape
        qkv = x + (bias if bias is not None else 0.0)
        q, k, v = (t.reshape(b, nh, hd) for t in np.split(qkv, 3, -1))
        kc, vc = cache[0].copy(), cache[1].copy()
        out = np.zeros((b, nh, hd), np.float32)
        for i in range(b):
            t = int(lens[i])
            kc[i, :, t] = k[i]
            vc[i, :, t] = v[i]
            s = np.einsum("hd,hld->hl", q[i], kc[i, :, :t + 1]) / \
                np.sqrt(hd)
            if mask is not None:
                s = s + mask[i, 0, 0, :t + 1][None, :]
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            out[i] = np.einsum("hl,hld->hd", p, vc[i, :, :t + 1])
        return out.reshape(b, nh * hd), np.stack([kc, vc])

    def test_oracle_parity_per_row_lengths(self):
        from paddle_tpu.incubate.nn.functional import \
            masked_multihead_attention
        rng = np.random.default_rng(0)
        b, nh, L, hd = 3, 4, 10, 8
        x = rng.standard_normal((b, 3 * nh * hd)).astype(np.float32)
        cache = rng.standard_normal((2, b, nh, L, hd)).astype(np.float32)
        bias = rng.standard_normal((3 * nh * hd,)).astype(np.float32)
        lens = np.asarray([2, 5, 0], np.int32)
        mask = np.where(rng.random((b, 1, 1, L)) < 0.2, -1e9,
                        0.0).astype(np.float32)
        # the current position must stay attendable
        for i in range(b):
            mask[i, 0, 0, lens[i]] = 0.0
        out, ck = masked_multihead_attention(
            P.to_tensor(x), cache_kv=P.to_tensor(cache),
            bias=P.to_tensor(bias), src_mask=P.to_tensor(mask),
            sequence_lengths=P.to_tensor(lens.reshape(b, 1)))
        ref_out, ref_ck = self._oracle(x, cache, bias, mask, lens)
        assert np.allclose(out.numpy(), ref_out, atol=1e-5)
        assert np.allclose(ck.numpy(), ref_ck, atol=1e-6)

    def test_position_from_mask_and_guards(self):
        from paddle_tpu.incubate.nn.functional import \
            masked_multihead_attention
        rng = np.random.default_rng(1)
        b, nh, L, hd = 2, 2, 6, 4
        x = rng.standard_normal((b, 3 * nh * hd)).astype(np.float32)
        cache = rng.standard_normal((2, b, nh, L, hd)).astype(np.float32)
        t = 3
        mask = np.zeros((b, 1, 1, t + 1), np.float32)
        out, ck = masked_multihead_attention(
            P.to_tensor(x), cache_kv=P.to_tensor(cache),
            src_mask=P.to_tensor(mask))
        lens = np.full((b,), t, np.int32)
        ref_out, ref_ck = self._oracle(x, cache, None, None, lens)
        assert np.allclose(out.numpy(), ref_out, atol=1e-5)
        assert np.allclose(ck.numpy(), ref_ck, atol=1e-6)
        with pytest.raises(ValueError):
            masked_multihead_attention(P.to_tensor(x))
        with pytest.raises(NotImplementedError):
            masked_multihead_attention(
                P.to_tensor(x), cache_kv=P.to_tensor(cache),
                src_mask=P.to_tensor(mask), out_scale=1.0)
        with pytest.raises(NotImplementedError):
            masked_multihead_attention(
                P.to_tensor(x), cache_kv=P.to_tensor(cache),
                src_mask=P.to_tensor(mask),
                rotary_tensor=P.to_tensor(mask))
