"""Ulysses + ring attention + MoE tests: parity vs the dense oracle on the
virtual mesh (SURVEY.md §5.7 mechanisms)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as Pspec

import paddle_tpu as P
import paddle_tpu.distributed as dist
from paddle_tpu.distributed._axis import axis_env
from paddle_tpu.distributed.fleet.long_context import (ring_flash_attention,
                                                       ulysses_attention)
from paddle_tpu.ops.pallas.flash_attention import _attention_ref


def make_qkv(b=2, s=32, h=4, d=8, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.standard_normal((b, s, h, d)).astype(np.float32)
            for _ in range(3)]


class TestUlysses:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_dense(self, causal):
        n = 4
        q, k, v = make_qkv()
        ref = np.asarray(_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                        jnp.asarray(v), causal=causal))
        mesh = Mesh(np.array(jax.devices()[:n]), ("sep",))
        g = dist.new_group(list(range(n)), axis_name="sep")

        def body(qa, ka, va):
            out = ulysses_attention(P.Tensor(qa), P.Tensor(ka),
                                    P.Tensor(va), group=g, causal=causal)
            return out._data

        f = jax.jit(jax.shard_map(body, mesh=mesh,
                          in_specs=Pspec(None, "sep"),
                          out_specs=Pspec(None, "sep")))
        with axis_env("sep"):
            out = np.asarray(f(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v)))
        assert np.allclose(out, ref, atol=2e-4), np.abs(out - ref).max()


class TestSepGQA:
    """Round-4: GQA rides the sep composition with NATIVE KV heads —
    ring rotates K/V whole; Ulysses splits each tensor's own head count
    (sep | nkv). No repeat_kv, parity vs the dense GQA reference."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_ulysses_gqa_native_kv(self, causal):
        n = 4
        q, _, _ = make_qkv(h=8)
        _, k, v = make_qkv(h=4, seed=5)          # nkv=4, sep=4 divides
        ref = np.asarray(_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                        jnp.asarray(v), causal=causal))
        mesh = Mesh(np.array(jax.devices()[:n]), ("sep",))
        g = dist.new_group(list(range(n)), axis_name="sep")

        def body(qa, ka, va):
            out = ulysses_attention(P.Tensor(qa), P.Tensor(ka),
                                    P.Tensor(va), group=g, causal=causal)
            return out._data

        f = jax.jit(jax.shard_map(body, mesh=mesh,
                          in_specs=Pspec(None, "sep"),
                          out_specs=Pspec(None, "sep")))
        with axis_env("sep"):
            out = np.asarray(f(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v)))
        assert np.allclose(out, ref, atol=2e-4), np.abs(out - ref).max()

    def test_ulysses_gqa_native_kv_grad_parity(self):
        """Backward through the no-repeat Ulysses GQA composition (the
        seq2head alltoall transpose with nkv < nh) matches dense grads."""
        import jax as _jax
        n = 4
        q, _, _ = make_qkv(h=8, seed=11)
        _, k, v = make_qkv(h=4, seed=12)
        g = dist.new_group(list(range(n)), axis_name="sep")
        mesh = Mesh(np.array(jax.devices()[:n]), ("sep",))

        def loss_sep(qa, ka, va):
            def body(q_, k_, v_):
                out = ulysses_attention(P.Tensor(q_), P.Tensor(k_),
                                        P.Tensor(v_), group=g,
                                        causal=True)
                return out._data

            f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=Pspec(None, "sep"),
                              out_specs=Pspec(None, "sep")))
            with axis_env("sep"):
                return (f(qa, ka, va) ** 2).sum()

        def loss_dense(qa, ka, va):
            return (_attention_ref(qa, ka, va, causal=True)
                    .astype(jnp.float32) ** 2).sum()

        args = tuple(jnp.asarray(x) for x in (q, k, v))
        g_sep = _jax.grad(loss_sep, argnums=(0, 1, 2))(*args)
        g_dense = _jax.grad(loss_dense, argnums=(0, 1, 2))(*args)
        for a, b, name in zip(g_sep, g_dense, ("dq", "dk", "dv")):
            assert np.allclose(np.asarray(a), np.asarray(b),
                               atol=2e-3), \
                (name, np.abs(np.asarray(a) - np.asarray(b)).max())

    @pytest.mark.parametrize("causal", [False, True])
    def test_ring_gqa_native_kv(self, causal):
        n = 4
        q, _, _ = make_qkv(h=8, seed=7)
        _, k, v = make_qkv(h=2, seed=8)          # nkv=2 < sep=4: fine
        ref = np.asarray(_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                        jnp.asarray(v), causal=causal))
        mesh = Mesh(np.array(jax.devices()[:n]), ("sep",))
        g = dist.new_group(list(range(n)), axis_name="sep")

        def body(qa, ka, va):
            out = ring_flash_attention(P.Tensor(qa), P.Tensor(ka),
                                       P.Tensor(va), group=g,
                                       causal=causal)
            return out._data

        f = jax.jit(jax.shard_map(body, mesh=mesh,
                          in_specs=Pspec(None, "sep"),
                          out_specs=Pspec(None, "sep")))
        with axis_env("sep"):
            out = np.asarray(f(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v)))
        assert np.allclose(out, ref, atol=2e-4), np.abs(out - ref).max()


class TestRingAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_dense(self, causal):
        n = 4
        q, k, v = make_qkv(seed=3)
        ref = np.asarray(_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                        jnp.asarray(v), causal=causal))
        mesh = Mesh(np.array(jax.devices()[:n]), ("sep",))
        g = dist.new_group(list(range(n)), axis_name="sep")

        def body(qa, ka, va):
            out = ring_flash_attention(P.Tensor(qa), P.Tensor(ka),
                                       P.Tensor(va), group=g,
                                       causal=causal)
            return out._data

        f = jax.jit(jax.shard_map(body, mesh=mesh,
                          in_specs=Pspec(None, "sep"),
                          out_specs=Pspec(None, "sep")))
        with axis_env("sep"):
            out = np.asarray(f(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v)))
        assert np.allclose(out, ref, atol=2e-4), np.abs(out - ref).max()

    def test_gradients_flow(self):
        n = 4
        q, k, v = make_qkv(seed=4)
        mesh = Mesh(np.array(jax.devices()[:n]), ("sep",))
        g = dist.new_group(list(range(n)), axis_name="sep")
        from paddle_tpu.distributed.fleet.long_context import \
            _ring_attention_core

        def loss(qa, ka, va):
            def body(q_, k_, v_):
                return _ring_attention_core(q_, k_, v_, "sep", n, True,
                                            None)
            f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=Pspec(None, "sep"),
                              out_specs=Pspec(None, "sep")))
            return jnp.sum(f(qa, ka, va) ** 2)

        def dense_loss(qa, ka, va):
            return jnp.sum(_attention_ref(qa, ka, va, causal=True) ** 2)

        g_ring = jax.grad(loss)(jnp.asarray(q), jnp.asarray(k))  \
            if False else jax.grad(loss, argnums=(0, 1, 2))(
                jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(g_ring, g_dense):
            assert np.allclose(np.asarray(a), np.asarray(b), atol=3e-3), \
                np.abs(np.asarray(a) - np.asarray(b)).max()


class TestMoE:
    def test_forward_and_capacity(self):
        from paddle_tpu.incubate.moe import MoELayer
        P.seed(0)
        moe = MoELayer(d_model=16, d_hidden=32, num_experts=4, top_k=2,
                       capacity_factor=2.0)
        x = P.randn([2, 8, 16])
        out = moe(x)
        assert out.shape == [2, 8, 16]
        assert moe.l_aux is not None
        assert float(moe.l_aux.numpy()) > 0

    def test_training_decreases_loss(self):
        from paddle_tpu.incubate.moe import MoELayer
        P.seed(1)
        moe = MoELayer(d_model=8, d_hidden=16, num_experts=4, top_k=2,
                       capacity_factor=4.0)
        tgt = P.randn([4, 6, 8])
        x = P.randn([4, 6, 8])
        opt = P.optimizer.Adam(0.01, parameters=moe.parameters())
        # the subject is the family, not the eager path: the loss is one
        # traced program (`to_static`) and backward() differentiates that
        # one program — not one XLA compile an op
        loss_of = P.jit.to_static(
            lambda x: ((moe(x) - tgt) ** 2).mean() + 0.01 * moe.l_aux)
        losses = []
        for _ in range(30):
            loss = loss_of(x)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0] * 0.8

    def test_sort_dispatch_matches_dense(self):
        """Round-4 (VERDICT r3 item 7): the sort/segment dispatch is
        bit-equivalent to the GShard one-hot einsum formulation,
        including capacity overflow drops."""
        from paddle_tpu.incubate.moe import MoELayer
        for cf, seed in ((4.0, 0), (1.0, 1), (0.5, 2)):  # incl. overflow
            P.seed(0)
            a = MoELayer(d_model=16, d_hidden=32, num_experts=4, top_k=2,
                         capacity_factor=cf, dispatch_mode="sort")
            P.seed(0)
            b = MoELayer(d_model=16, d_hidden=32, num_experts=4, top_k=2,
                         capacity_factor=cf, dispatch_mode="dense")
            P.seed(seed + 10)
            x = P.randn([2, 16, 16])
            oa, ob = a(x), b(x)
            np.testing.assert_allclose(oa.numpy(), ob.numpy(),
                                       atol=1e-5, err_msg=f"cf={cf}")
            np.testing.assert_allclose(float(a.l_aux.numpy()),
                                       float(b.l_aux.numpy()), atol=1e-6)

    def test_sort_dispatch_grad_matches_dense(self):
        from paddle_tpu.incubate.moe import MoELayer
        P.seed(3)
        x_np = np.random.default_rng(5).standard_normal(
            (2, 8, 16)).astype(np.float32)
        grads = {}
        for mode in ("sort", "dense"):
            P.seed(3)
            moe = MoELayer(d_model=16, d_hidden=32, num_experts=4,
                           top_k=2, capacity_factor=1.0,
                           dispatch_mode=mode)
            x = P.to_tensor(x_np, stop_gradient=False)
            out = moe(x)
            (out.sum() + 0.1 * moe.l_aux).backward()
            grads[mode] = (x.grad.numpy(), moe.w_in.grad.numpy(),
                           moe.w_out.grad.numpy())
        for ga, gb in zip(grads["sort"], grads["dense"]):
            np.testing.assert_allclose(ga, gb, atol=1e-4)

    def test_sort_dispatch_scales_to_real_token_counts(self):
        """N=8192, E=64 — the dense dispatch/combine tensors would be
        2 × [8192, 64, 160] f32 ≈ 670 MB; the sort path's biggest
        intermediates are O(N·K) indices and the [E, C, D] buffers."""
        from paddle_tpu.incubate.moe import MoELayer
        P.seed(4)
        moe = MoELayer(d_model=8, d_hidden=16, num_experts=64, top_k=2,
                       capacity_factor=1.25, dispatch_mode="sort")
        x = P.randn([8, 1024, 8])        # 8192 tokens
        out = moe(x)
        assert out.shape == [8, 1024, 8]
        assert np.isfinite(out.numpy()).all()
        assert np.abs(out.numpy()).sum() > 0

    def test_expert_weights_sharded_in_spmd(self):
        """Expert dim partition hint is honored by the SPMD engine."""
        from paddle_tpu.incubate.moe import MoELayer
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.fleet import _state
        from paddle_tpu.distributed.fleet.topology import \
            set_hybrid_communicate_group
        _state.initialized = False
        set_hybrid_communicate_group(None)
        P.seed(0)
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {"sharding_degree": 4, "dp_degree": 2}
        fleet.init(is_collective=True, strategy=strategy)

        import paddle_tpu.nn as nn

        class Net(nn.Layer):
            def __init__(self):
                super().__init__()
                self.moe = MoELayer(8, 16, num_experts=4, top_k=1,
                                    capacity_factor=4.0)
                self.head = nn.Linear(8, 4)

            def forward(self, x):
                return self.head(self.moe(x)).mean(axis=1)

        net = Net()
        opt = P.optimizer.Adam(0.01, parameters=net.parameters())
        model = fleet.distributed_model(net)
        x = P.randn([8, 4, 8])
        y = P.to_tensor(np.zeros((8,), np.int32))
        loss = model.train_batch([x], [y], opt,
                                 nn.CrossEntropyLoss())
        assert np.isfinite(float(loss.numpy()))
        spec = net.moe.w_in._data.sharding.spec
        assert "sharding" in [s for s in spec if s is not None]


class TestRingWithPallasKernel:
    """Ring attention with the actual Pallas FA kernels engaged
    (interpret mode off-TPU) — the blueprint's flagship composition."""

    @pytest.mark.parametrize("causal", [True, False])
    def test_parity_kernel_engaged(self, causal, monkeypatch):
        from paddle_tpu.ops.pallas import flash_attention as fa_mod
        monkeypatch.setattr(fa_mod, "_FORCE_INTERPRET", True)
        from paddle_tpu.distributed.fleet.long_context import \
            _ring_attention_core
        n = 4
        q, k, v = make_qkv(b=1, s=4 * 128, h=2, d=64, seed=5)
        ref = np.asarray(_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                        jnp.asarray(v), causal=causal))
        mesh = Mesh(np.array(jax.devices()[:n]), ("sep",))
        fa_mod.reset_dispatch_stats()
        f = jax.jit(jax.shard_map(
            lambda a, b_, c: _ring_attention_core(a, b_, c, "sep", n,
                                                  causal, None),
            mesh=mesh, in_specs=Pspec(None, "sep"),
            out_specs=Pspec(None, "sep"), check_vma=False))
        out = np.asarray(f(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)))
        # the kernel must actually engage (a silent fallback here hid
        # behind parity-only asserts until round 3's dispatch counters)
        assert fa_mod.dispatch_stats()["pallas"] >= 1
        assert np.allclose(out, ref, atol=2e-3), np.abs(out - ref).max()

    def test_grad_parity_kernel_engaged(self, monkeypatch):
        from paddle_tpu.ops.pallas import flash_attention as fa_mod
        monkeypatch.setattr(fa_mod, "_FORCE_INTERPRET", True)
        from paddle_tpu.distributed.fleet.long_context import \
            _ring_attention_core
        n = 2
        q, k, v = make_qkv(b=1, s=2 * 128, h=2, d=64, seed=6)
        mesh = Mesh(np.array(jax.devices()[:n]), ("sep",))

        def loss(qa, ka, va):
            f = jax.jit(jax.shard_map(
                lambda a, b_, c: _ring_attention_core(a, b_, c, "sep", n,
                                                      True, None),
                mesh=mesh, in_specs=Pspec(None, "sep"),
                out_specs=Pspec(None, "sep"), check_vma=False))
            return jnp.sum(f(qa, ka, va) ** 2)

        def dense_loss(qa, ka, va):
            return jnp.sum(_attention_ref(qa, ka, va, causal=True) ** 2)

        g_ring = jax.grad(loss, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        g_dense = jax.grad(dense_loss, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(g_ring, g_dense):
            assert np.allclose(np.asarray(a), np.asarray(b), atol=5e-3), \
                np.abs(np.asarray(a) - np.asarray(b)).max()


class TestFlashCoreLse:
    def test_lse_cotangent_fold(self, monkeypatch):
        """grad through (out, lse) with nonzero lse cotangent matches the
        XLA oracle — validates the delta-fold backward (dlse path)."""
        from paddle_tpu.ops.pallas import flash_attention as fa_mod
        monkeypatch.setattr(fa_mod, "_FORCE_INTERPRET", True)
        q, k, v = (jnp.asarray(x) for x in make_qkv(b=1, s=128, h=2, d=64,
                                                    seed=7))

        def f_kernel(qa, ka, va):
            out, lse = fa_mod.flash_core_lse(qa, ka, va, True, None)
            return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

        def f_ref(qa, ka, va):
            out, lse = fa_mod._attention_ref_lse(qa, ka, va, causal=True)
            return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

        gk = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(gk, gr):
            assert np.allclose(np.asarray(a), np.asarray(b), atol=2e-3), \
                np.abs(np.asarray(a) - np.asarray(b)).max()


class TestUlyssesOnFlashCore:
    """Round-3 (VERDICT r2 item 4): the Ulysses per-head attention runs
    the Pallas flash core, not the O(s²) reference."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_kernel_engaged_and_parity(self, causal, monkeypatch):
        import paddle_tpu.ops.pallas.flash_attention as fa_mod
        monkeypatch.setattr(fa_mod, "_FORCE_INTERPRET", True)
        fa_mod.reset_dispatch_stats()
        n = 4
        # kernel-shaped: S=512 (/128), d=64, h divisible by n
        q, k, v = make_qkv(s=512, h=4, d=64)
        ref = np.asarray(_attention_ref(jnp.asarray(q), jnp.asarray(k),
                                        jnp.asarray(v), causal=causal))
        mesh = Mesh(np.array(jax.devices()[:n]), ("sep",))
        g = dist.new_group(list(range(n)), axis_name="sep")

        def body(qa, ka, va):
            out = ulysses_attention(P.Tensor(qa), P.Tensor(ka),
                                    P.Tensor(va), group=g, causal=causal)
            return out._data

        f = jax.jit(jax.shard_map(body, mesh=mesh,
                          in_specs=Pspec(None, "sep"),
                          out_specs=Pspec(None, "sep"), check_vma=False))
        with axis_env("sep"):
            out = np.asarray(f(jnp.asarray(q), jnp.asarray(k),
                               jnp.asarray(v)))
        assert fa_mod.dispatch_stats()["pallas"] >= 1  # kernel engaged
        assert np.allclose(out, ref, atol=3e-4), np.abs(out - ref).max()

    def test_grad_parity_through_kernel(self, monkeypatch):
        import paddle_tpu.ops.pallas.flash_attention as fa_mod
        from paddle_tpu.distributed.fleet.long_context import \
            ulysses_attention as ua
        monkeypatch.setattr(fa_mod, "_FORCE_INTERPRET", True)
        n = 4
        q, k, v = make_qkv(s=512, h=4, d=64, seed=9)
        mesh = Mesh(np.array(jax.devices()[:n]), ("sep",))
        g = dist.new_group(list(range(n)), axis_name="sep")

        def loss(qa, ka, va):
            def body(q_, k_, v_):
                out = ua(P.Tensor(q_), P.Tensor(k_), P.Tensor(v_),
                         group=g, causal=True)
                return out._data
            f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=Pspec(None, "sep"),
                              out_specs=Pspec(None, "sep"),
                              check_vma=False))
            with axis_env("sep"):
                return jnp.sum(f(qa, ka, va) ** 2)

        def dense_loss(qa, ka, va):
            return jnp.sum(_attention_ref(qa, ka, va, causal=True) ** 2)

        g_u = jax.grad(loss, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        g_d = jax.grad(dense_loss, argnums=(0, 1, 2))(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
        for a, b in zip(g_u, g_d):
            assert np.allclose(np.asarray(a), np.asarray(b), atol=3e-3), \
                np.abs(np.asarray(a) - np.asarray(b)).max()


class TestSepTrainer:
    """Config-level context-parallel TRAINING: SPMDTrainer's sep branch
    (shard_map manual over 'sep', globally-shifted token CE) with the
    model routing attention through ring/ulysses on the flash core."""

    def _dense_losses(self, cfg_kw, ids, steps=3, lr=0.1):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        P.seed(17)
        cfg = LlamaConfig(**cfg_kw)  # no context_parallel: dense oracle
        dense = LlamaForCausalLM(cfg)
        opt = P.optimizer.SGD(lr, parameters=dense.parameters())
        xs = P.to_tensor(ids)
        import jax.numpy as jnp
        lab = np.concatenate(
            [ids[:, 1:], np.full((ids.shape[0], 1), -100, ids.dtype)],
            axis=1)
        out = []
        for _ in range(steps):
            logits = dense(xs)
            lp = P.nn.functional.log_softmax(
                logits.astype("float32"), axis=-1)
            labt = P.to_tensor(np.where(lab < 0, 0, lab))
            tok = P.take_along_axis(lp, labt.unsqueeze(-1),
                                    axis=-1).squeeze(-1)
            mask = P.to_tensor((lab >= 0).astype(np.float32))
            loss = -(tok * mask).sum() / mask.sum()
            loss.backward()
            opt.step()
            opt.clear_grad()
            out.append(float(loss.numpy()))
        return out, {n: p.numpy().copy()
                     for n, p in dense.named_parameters()}

    def _sep_losses(self, mode, cfg_kw, ids, hybrid, steps=3, lr=0.1):
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       LlamaPretrainingCriterion)
        from paddle_tpu.distributed.fleet.fleet import _state
        from paddle_tpu.distributed.fleet.topology import \
            set_hybrid_communicate_group
        _state.initialized = False
        _state.strategy = None
        _state.hcg = None
        set_hybrid_communicate_group(None)
        P.seed(17)
        strategy = DistributedStrategy()
        strategy.hybrid_configs = hybrid
        fleet.init(is_collective=True, strategy=strategy)
        cfg = LlamaConfig(context_parallel=mode, **cfg_kw)
        model = LlamaForCausalLM(cfg)
        opt = P.optimizer.SGD(lr, parameters=model.parameters())
        opt = fleet.distributed_optimizer(opt)
        dmodel = fleet.distributed_model(model)
        crit = LlamaPretrainingCriterion(cfg)
        losses = []
        for _ in range(steps):
            loss = dmodel.train_batch([P.to_tensor(ids)],
                                      [P.to_tensor(ids)], opt, crit)
            losses.append(float(loss.numpy()))
        return losses

    CFG = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
               num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2,  # GQA: ring runs native KV heads;
               # ulysses at sep=4 (4 ∤ 2) takes the repeat path
               max_position_embeddings=64)

    @pytest.mark.parametrize("mode", ["ring", "ulysses"])
    def test_sep_training_matches_dense(self, mode):
        ids = np.random.default_rng(3).integers(
            0, 64, (2, 32)).astype(np.int32)
        ref, _ = self._dense_losses(self.CFG, ids)
        got = self._sep_losses(mode, self.CFG, ids,
                               {"sep_degree": 4})
        assert np.allclose(got, ref, rtol=2e-3, atol=2e-4), (got, ref)

    def test_sep_composes_with_dp(self):
        ids = np.random.default_rng(4).integers(
            0, 64, (4, 32)).astype(np.int32)
        ref, _ = self._dense_losses(self.CFG, ids)
        got = self._sep_losses("ring", self.CFG, ids,
                               {"dp_degree": 2, "sep_degree": 4})
        assert np.allclose(got, ref, rtol=2e-3, atol=2e-4), (got, ref)
