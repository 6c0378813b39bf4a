"""DDPM/DDIM diffusion family: scheduler math vs an INDEPENDENT numpy
implementation of the papers' closed forms, q-marginal statistics,
training convergence, and compiled-loop/host-loop sampling equality."""
import numpy as np
import pytest

import jax
import paddle_tpu as P
from paddle_tpu.models.ddpm import (DDIMScheduler, DDPMScheduler,
                                    UNet2DConfig, UNet2DModel,
                                    ddpm_train_loss)


def _np_schedule(T, b0=1e-4, b1=0.02):
    betas = np.linspace(b0, b1, T)
    alphas = 1.0 - betas
    return betas, alphas, np.cumprod(alphas)


class TestSchedulerMath:
    def test_cumprods_match_reference_formula(self):
        sch = DDPMScheduler(num_train_timesteps=100)
        betas, alphas, ac = _np_schedule(100)
        np.testing.assert_allclose(sch.betas, betas, rtol=1e-12)
        np.testing.assert_allclose(sch.alphas_cumprod, ac, rtol=1e-12)

    def test_add_noise_closed_form(self):
        sch = DDPMScheduler(num_train_timesteps=100)
        _, _, ac = _np_schedule(100)
        rng = np.random.default_rng(0)
        x0 = rng.standard_normal((3, 1, 4, 4)).astype(np.float32)
        eps = rng.standard_normal((3, 1, 4, 4)).astype(np.float32)
        t = np.array([0, 50, 99])
        got = np.asarray(sch.add_noise(
            P.to_tensor(x0), P.to_tensor(eps),
            P.to_tensor(t.astype(np.int32)))._data)
        ref = (np.sqrt(ac[t])[:, None, None, None] * x0
               + np.sqrt(1 - ac[t])[:, None, None, None] * eps)
        np.testing.assert_allclose(got, ref, atol=1e-5)  # f32 vs f64

    def test_ancestral_step_mean_closed_form(self):
        """At t=0 the step adds no noise, so it equals the posterior
        mean — checked against the paper's formula."""
        sch = DDPMScheduler(num_train_timesteps=10)
        betas, alphas, ac = _np_schedule(10)
        rng = np.random.default_rng(1)
        x = rng.standard_normal((2, 1, 4, 4)).astype(np.float32)
        e = rng.standard_normal((2, 1, 4, 4)).astype(np.float32)
        got = np.asarray(sch.step(
            P.to_tensor(e), 0, P.to_tensor(x),
            jax.random.PRNGKey(0))._data)
        ref = (x - betas[0] / np.sqrt(1 - ac[0]) * e) / \
            np.sqrt(alphas[0])
        np.testing.assert_allclose(got, ref, atol=1e-4)  # f32 vs f64,
        # amplified by the 1/sqrt(1-ac[0]) ≈ 1/sqrt(beta0) = 100 factor

    def test_ddim_step_closed_form_and_final_x0(self):
        sch = DDIMScheduler(num_train_timesteps=20)
        _, _, ac = _np_schedule(20)
        rng = np.random.default_rng(2)
        x = rng.standard_normal((1, 1, 4, 4)).astype(np.float32)
        e = rng.standard_normal((1, 1, 4, 4)).astype(np.float32)
        x0_hat = (x - np.sqrt(1 - ac[10]) * e) / np.sqrt(ac[10])
        got = np.asarray(sch.step_ddim(P.to_tensor(e), 10, 5,
                                       P.to_tensor(x))._data)
        ref = np.sqrt(ac[5]) * x0_hat + np.sqrt(1 - ac[5]) * e
        np.testing.assert_allclose(got, ref, atol=1e-5)
        # t_prev = -1 (the final step) returns the x0 estimate exactly
        got0 = np.asarray(sch.step_ddim(P.to_tensor(e), 10, -1,
                                        P.to_tensor(x))._data)
        np.testing.assert_allclose(got0, x0_hat, atol=1e-5)

    def test_forward_marginal_is_standard_normal_at_large_t(self):
        """ᾱ_T ≈ 0 ⇒ x_T ~ N(0, 1) regardless of x0."""
        sch = DDPMScheduler(num_train_timesteps=1000)
        rng = np.random.default_rng(3)
        x0 = np.full((64, 1, 8, 8), 5.0, np.float32)  # far from 0
        eps = rng.standard_normal((64, 1, 8, 8)).astype(np.float32)
        t = np.full((64,), 999, np.int32)
        xt = np.asarray(sch.add_noise(P.to_tensor(x0), P.to_tensor(eps),
                                      P.to_tensor(t))._data)
        assert abs(xt.mean()) < 0.1
        assert abs(xt.std() - 1.0) < 0.1


class TestUNetAndSampling:
    def test_train_loss_decreases(self):
        """The U-Net learns to predict the noise. The subject is the
        family, not the eager path: the loss is one traced program
        (`to_static`), differentiated as one — not ~250 compiles an op."""
        from paddle_tpu.optimizer import Adam
        P.seed(0)
        train_loss = P.jit.to_static(  # a raw-array argument arrives a Tensor
            lambda m, sch, x0, key: ddpm_train_loss(m, sch, x0, key._data))
        m = UNet2DModel(UNet2DConfig.tiny())
        m.train()
        sch = DDPMScheduler(num_train_timesteps=50)
        opt = Adam(2e-3, parameters=m.parameters())
        rng = np.random.default_rng(0)
        key = jax.random.PRNGKey(0)
        losses = []
        for _ in range(40):
            sign = rng.choice([-0.8, 0.8], (8, 1, 1, 1))
            x0 = P.to_tensor(np.broadcast_to(
                sign, (8, 1, 8, 8)).astype(np.float32).copy())
            key, sub = jax.random.split(key)
            loss = train_loss(m, sch, x0, sub)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert np.mean(losses[-5:]) < np.mean(losses[:5]) * 0.5, losses

    def test_compiled_sampling_equals_host_loop(self):
        """The lax.fori_loop program reproduces the eager per-step loop
        (same keys, same math) — and its program cache survives weight
        updates because weights are arguments."""
        P.seed(1)
        m = UNet2DModel(UNet2DConfig.tiny())
        m.eval()
        sch = DDPMScheduler(num_train_timesteps=10)
        a = np.asarray(m.sample_compiled(sch, (2, 1, 8, 8),
                                         seed=5)._data)
        b = np.asarray(m.sample(sch, (2, 1, 8, 8), seed=5)._data)
        np.testing.assert_allclose(a, b, atol=1e-5)
        # mutate weights; the cached program must track them
        w = m.conv_out.weight
        w.set_value(w * 0.5)
        a2 = np.asarray(m.sample_compiled(sch, (2, 1, 8, 8),
                                          seed=5)._data)
        assert np.abs(a2 - a).max() > 1e-4

    def test_ddim_subsequence_deterministic(self):
        P.seed(2)
        m = UNet2DModel(UNet2DConfig.tiny())
        m.eval()
        sch = DDIMScheduler(num_train_timesteps=40)
        # batch 2, the shape the test above sampled at: determinism does
        # not depend on it, and the eager U-Net's ~150 per-op programs are
        # then already compiled in this process
        s1 = np.asarray(m.sample(sch, (2, 1, 8, 8), seed=9,
                                 num_inference_steps=8)._data)
        s2 = np.asarray(m.sample(sch, (2, 1, 8, 8), seed=9,
                                 num_inference_steps=8)._data)
        np.testing.assert_array_equal(s1, s2)
        assert np.isfinite(s1).all()
