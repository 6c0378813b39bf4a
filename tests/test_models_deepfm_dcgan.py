"""DeepFM (recommendation) and DCGAN (adversarial generation) families,
plus the torch-oracle coverage for conv2d_transpose that the DCGAN work
exposed as missing (the op was silently broken under jax 0.9 —
`transpose_kernel` kwarg removed — with zero tests)."""
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.nn.functional as F

torch = pytest.importorskip("torch")

# cert marker (ADVICE.md #3): under PADDLE_TPU_CERT_RUN=1 the conftest
# makes these oracle deps mandatory (missing -> run FAILS, not skips)
pytestmark = pytest.mark.certification


class TestConvTransposeOracle:
    @pytest.mark.parametrize("cin,cout,k,s,p,d,g", [
        (3, 5, 4, 2, 1, 1, 1),   # DCGAN upsample shape class
        (4, 4, 3, 1, 0, 1, 2),   # grouped
        (6, 4, 4, 2, 1, 2, 2),   # grouped + dilated
        (2, 3, 5, 3, 2, 1, 1),   # big kernel, stride 3
    ])
    def test_matches_torch(self, cin, cout, k, s, p, d, g):
        rng = np.random.default_rng(0)
        x = rng.standard_normal((2, cin, 7, 7)).astype(np.float32)
        w = rng.standard_normal((cin, cout // g, k, k)).astype(
            np.float32)
        b = rng.standard_normal((cout,)).astype(np.float32)
        ref = torch.nn.functional.conv_transpose2d(
            torch.tensor(x), torch.tensor(w), torch.tensor(b),
            stride=s, padding=p, dilation=d, groups=g).numpy()
        got = np.asarray(F.conv2d_transpose(
            P.to_tensor(x), P.to_tensor(w), P.to_tensor(b), stride=s,
            padding=p, dilation=d, groups=g)._data)
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, atol=2e-4, rtol=1e-4)

    def test_output_padding_and_output_size(self):
        rng = np.random.default_rng(4)
        x = rng.standard_normal((1, 3, 5, 5)).astype(np.float32)
        w = rng.standard_normal((3, 4, 3, 3)).astype(np.float32)
        ref = torch.nn.functional.conv_transpose2d(
            torch.tensor(x), torch.tensor(w), stride=2, padding=1,
            output_padding=1).numpy()
        got = np.asarray(F.conv2d_transpose(
            P.to_tensor(x), P.to_tensor(w), stride=2, padding=1,
            output_padding=1)._data)
        assert got.shape == ref.shape == (1, 4, 10, 10)
        np.testing.assert_allclose(got, ref, atol=2e-5)
        # output_size picks the implied output_padding
        got2 = np.asarray(F.conv2d_transpose(
            P.to_tensor(x), P.to_tensor(w), stride=2, padding=1,
            output_size=10)._data)
        np.testing.assert_allclose(got2, ref, atol=2e-5)
        with pytest.raises(ValueError, match="unreachable"):
            F.conv2d_transpose(P.to_tensor(x), P.to_tensor(w),
                               stride=2, padding=1, output_size=23)

    def test_gradients_flow(self):
        x = P.to_tensor(np.random.default_rng(1).standard_normal(
            (1, 2, 4, 4)).astype(np.float32))
        x.stop_gradient = False
        w = P.to_tensor(np.random.default_rng(2).standard_normal(
            (2, 3, 4, 4)).astype(np.float32))
        w.stop_gradient = False
        out = F.conv2d_transpose(x, w, stride=2, padding=1)
        out.sum().backward()
        assert x.grad is not None and w.grad is not None
        assert float(abs(P.to_tensor(w.grad)).sum()) > 0


class TestDeepFM:
    def test_fm_term_matches_pairwise_oracle(self):
        """The sum-square identity == explicit O(F²) Σ_{i<j}⟨v_i,v_j⟩."""
        from paddle_tpu.models.deepfm import DeepFM, DeepFMConfig
        m = DeepFM(DeepFMConfig.tiny())
        rng = np.random.default_rng(0)
        emb = rng.standard_normal((3, 6, 4)).astype(np.float32)
        got = np.asarray(m.fm_second_order(P.to_tensor(emb))._data)
        ref = np.zeros(3, np.float32)
        for i in range(6):
            for j in range(i + 1, 6):
                ref += (emb[:, i] * emb[:, j]).sum(-1)
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-5)

    def test_ctr_training_learns_interaction(self):
        """Labels are a PURE second-order interaction (click iff fields
        0 and 1 agree) — linear-only models can't separate it; DeepFM's
        FM/deep parts must."""
        from paddle_tpu.models.deepfm import DeepFM, DeepFMConfig
        from paddle_tpu.optimizer import Adam
        P.seed(0)
        rng = np.random.default_rng(0)
        n = 256
        f01 = rng.integers(0, 2, (n, 2))
        rest = rng.integers(4, 64, (n, 4))
        ids = np.concatenate([f01 + 2 * np.arange(2)[None], rest],
                             axis=1).astype(np.int32)
        y = (f01[:, 0] == f01[:, 1]).astype(np.float32)
        m = DeepFM(DeepFMConfig.tiny())
        m.train()
        opt = Adam(5e-2, parameters=m.parameters())
        xt, yt = P.to_tensor(ids), P.to_tensor(y)
        losses = []
        for _ in range(60):
            logits = m(xt)
            loss = F.binary_cross_entropy_with_logits(logits, yt)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < 0.25, losses[-1]
        m.eval()
        acc = np.mean((np.asarray(m.predict_ctr(xt)._data) > 0.5) == y)
        assert acc > 0.9, acc


class TestDCGAN:
    def test_adversarial_training_moves_generator(self):
        """Alternating G/D steps on a one-mode dataset: D separates at
        start, G's samples move toward the data statistics, and the
        detach contract holds (D's step leaves G's params untouched)."""
        from paddle_tpu.models.dcgan import (DCGANConfig, Discriminator,
                                             Generator,
                                             discriminator_loss,
                                             generator_loss)
        from paddle_tpu.optimizer import Adam
        P.seed(0)
        cfg = DCGANConfig.tiny()
        # G and D each run as one traced program (`to_static`): the tape
        # still sees their parameters as the inputs of one node each, so
        # the detach contract and the version check are exercised as before
        g = P.jit.to_static(Generator(cfg))
        d = P.jit.to_static(Discriminator(cfg))
        g.train()
        d.train()
        opt_g = Adam(2e-3, parameters=g.parameters(), beta1=0.5)
        opt_d = Adam(2e-3, parameters=d.parameters(), beta1=0.5)
        rng = np.random.default_rng(0)
        real_mean = 0.6
        g_w0 = np.asarray(g.project.weight._data).copy()

        import jax
        key = jax.random.PRNGKey(0)
        d_losses, g_losses = [], []
        for step in range(30):
            real = P.to_tensor(
                (real_mean + 0.05 * rng.standard_normal(
                    (8, 1, 16, 16))).astype(np.float32))
            key, sub = jax.random.split(key)
            z = P.Tensor(jax.random.normal(sub, (8, cfg.latent_dim)))
            fake = g(z)
            # D step (fake detached: G must not receive grads)
            d_loss = discriminator_loss(d, real, fake)
            d_loss.backward()
            for p in g.parameters():
                assert p.grad is None or float(
                    abs(P.to_tensor(p.grad)).sum()) == 0.0
            opt_d.step()
            opt_d.clear_grad()
            # G step with a FRESH d(fake) forward (post-D-update —
            # computing it earlier would reference D's pre-step
            # weights and the tape's version check faults)
            g_loss = generator_loss(d, fake)
            g_loss.backward()
            opt_g.step()
            opt_g.clear_grad()
            opt_d.clear_grad()  # drop D grads from the G pass
            d_losses.append(float(d_loss))
            g_losses.append(float(g_loss))
        # G moved, and its samples drifted toward the data mean
        assert np.abs(np.asarray(g.project.weight._data)
                      - g_w0).max() > 1e-4
        g.eval()
        key, sub = jax.random.split(key)
        z = P.Tensor(jax.random.normal(sub, (16, cfg.latent_dim)))
        sample_mean = float(np.asarray(g(z)._data).mean())
        assert sample_mean > 0.1, sample_mean  # started near 0
        assert np.isfinite(d_losses[-1]) and np.isfinite(g_losses[-1])
