"""Distributed stack tests on the 8-device virtual CPU mesh.

Methodology (SURVEY.md §4): LOSS PARITY — hybrid-parallel runs must match
the single-device baseline's loss sequence; collective semantics tested
via explicit shard_map; sharding verified on physical placements.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
import paddle_tpu.nn as nn
import paddle_tpu.distributed as dist
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet import DistributedStrategy


def _reset_fleet():
    from paddle_tpu.distributed.fleet.fleet import _state
    from paddle_tpu.distributed.fleet.topology import \
        set_hybrid_communicate_group
    _state.initialized = False
    _state.strategy = None
    _state.hcg = None
    set_hybrid_communicate_group(None)


class MLP(nn.Layer):
    def __init__(self, din=8, dh=16, dout=4):
        super().__init__()
        self.fc1 = nn.Linear(din, dh)
        self.fc2 = nn.Linear(dh, dout)

    def forward(self, x):
        return self.fc2(P.nn.functional.relu(self.fc1(x)))


def make_batch(n=16, din=8, dout=4, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, din)).astype(np.float32)
    y = rng.integers(0, dout, (n,)).astype(np.int32)
    return x, y


def baseline_losses(steps=4, seed=5, lr=0.05):
    """Single-device eager reference run."""
    _reset_fleet()
    P.seed(seed)
    net = MLP()
    opt = P.optimizer.Adam(lr, parameters=net.parameters())
    loss_fn = nn.CrossEntropyLoss()
    x, y = make_batch()
    losses = []
    for _ in range(steps):
        loss = loss_fn(net(P.to_tensor(x)), P.to_tensor(y))
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(loss.numpy()))
    return losses


class TestCollectiveAPI:
    def test_process_group_and_topology(self):
        from paddle_tpu.distributed.fleet.topology import (
            CommunicateTopology)
        topo = CommunicateTopology(
            ["data", "pipe", "sharding", "sep", "model"], [2, 1, 2, 1, 2])
        assert topo.world_size() == 8
        assert topo.get_rank(data=1, pipe=0, sharding=0, sep=0, model=1) \
            == 5
        coord = topo.get_coord(5)
        assert coord["data"] == 1 and coord["model"] == 1
        groups = topo.get_comm_list("model")
        assert len(groups) == 4 and all(len(g) == 2 for g in groups)

    def test_traced_allreduce_psum(self):
        """all_reduce lowers to psum inside shard_map."""
        from paddle_tpu.distributed._axis import axis_env
        from jax.sharding import Mesh, PartitionSpec as Pspec
        mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
        g = dist.new_group([0, 1, 2, 3], axis_name="mp")

        def body(x):
            t = P.Tensor(x)
            dist.all_reduce(t, group=g)
            return t._data

        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=Pspec("mp"),
                          out_specs=Pspec("mp")))
        with axis_env("mp"):
            out = f(jnp.arange(4.0))
        assert np.allclose(np.asarray(out), [6, 6, 6, 6])

    def test_hcg_groups(self):
        _reset_fleet()
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2,
                                   "pp_degree": 2}
        fleet.init(is_collective=True, strategy=strategy)
        hcg = fleet.get_hybrid_group()
        assert hcg.get_data_parallel_world_size() == 2
        assert hcg.get_model_parallel_world_size() == 2
        assert hcg.get_pipe_parallel_world_size() == 2
        assert hcg.mesh.shape["dp"] == 2
        assert tuple(hcg.mesh.axis_names) == ("dp", "pp", "sharding",
                                              "sep", "mp")


class TestDataParallelParity:
    def test_dp_loss_parity(self):
        ref = baseline_losses()
        _reset_fleet()
        P.seed(5)
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 8}
        fleet.init(is_collective=True, strategy=strategy)
        net = MLP()
        opt = P.optimizer.Adam(0.05, parameters=net.parameters())
        opt = fleet.distributed_optimizer(opt)
        model = fleet.distributed_model(net)
        loss_fn = nn.CrossEntropyLoss()
        x, y = make_batch()
        losses = []
        for _ in range(4):
            loss = model.train_batch([P.to_tensor(x)], [P.to_tensor(y)],
                                     opt, loss_fn)
            losses.append(float(loss.numpy()))
        assert np.allclose(losses, ref, rtol=2e-3, atol=2e-4), \
            (losses, ref)


class TestShardingStages:
    @pytest.mark.parametrize("stage", [1, 2, 3])
    def test_zero_stage_loss_parity(self, stage):
        ref = baseline_losses()
        _reset_fleet()
        P.seed(5)
        strategy = DistributedStrategy()
        strategy.sharding = True
        strategy.sharding_configs = {"stage": stage, "sharding_degree": 8}
        strategy.hybrid_configs = {"sharding_degree": 8}
        fleet.init(is_collective=True, strategy=strategy)
        net = MLP()
        opt = P.optimizer.Adam(0.05, parameters=net.parameters())
        opt = fleet.distributed_optimizer(opt)
        model = fleet.distributed_model(net)
        loss_fn = nn.CrossEntropyLoss()
        x, y = make_batch()
        losses = []
        for _ in range(4):
            loss = model.train_batch([P.to_tensor(x)], [P.to_tensor(y)],
                                     opt, loss_fn)
            losses.append(float(loss.numpy()))
        assert np.allclose(losses, ref, rtol=2e-3, atol=2e-4), \
            (stage, losses, ref)

    def test_stage3_params_physically_sharded(self):
        _reset_fleet()
        P.seed(5)
        strategy = DistributedStrategy()
        strategy.sharding = True
        strategy.sharding_configs = {"stage": 3, "sharding_degree": 8}
        strategy.hybrid_configs = {"sharding_degree": 8}
        fleet.init(is_collective=True, strategy=strategy)
        net = MLP()
        opt = P.optimizer.Adam(0.05, parameters=net.parameters())
        model = fleet.distributed_model(net)
        loss_fn = nn.CrossEntropyLoss()
        x, y = make_batch()
        model.train_batch([P.to_tensor(x)], [P.to_tensor(y)], opt, loss_fn)
        w = net.fc1.weight  # [8,16]: dim1=16 divisible by 8
        sh = w._data.sharding
        spec = sh.spec
        assert any(s == "sharding" for s in spec if s is not None), spec
        # optimizer state sharded too
        st = opt._accum[id(w)]
        m_sh = st["moment1"].sharding.spec
        assert any(s == "sharding" for s in m_sh if s is not None)

    def test_group_sharded_parallel_api(self):
        _reset_fleet()
        P.seed(5)
        net = MLP()
        opt = P.optimizer.AdamW(0.05, parameters=net.parameters())
        model, opt2 = dist.group_sharded_parallel(net, opt, "p_g_os")
        loss_fn = nn.CrossEntropyLoss()
        x, y = make_batch()
        l1 = model.train_batch([P.to_tensor(x)], [P.to_tensor(y)], opt2,
                               loss_fn)
        l2 = model.train_batch([P.to_tensor(x)], [P.to_tensor(y)], opt2,
                               loss_fn)
        assert float(l2.numpy()) < float(l1.numpy())


class TPMLP(nn.Layer):
    """2-layer MLP with Megatron TP (column then row)."""

    def __init__(self, din=8, dh=16, dout=4):
        super().__init__()
        from paddle_tpu.distributed.fleet import (ColumnParallelLinear,
                                                  RowParallelLinear)
        self.fc1 = ColumnParallelLinear(din, dh, gather_output=False)
        self.fc2 = RowParallelLinear(dh, dout, input_is_parallel=True)

    def forward(self, x):
        return self.fc2(P.nn.functional.relu(self.fc1(x)))


class TestTensorParallel:
    def test_tp_loss_parity_gspmd(self):
        """TP via GSPMD weight sharding matches the dense baseline."""
        _reset_fleet()
        P.seed(5)
        # baseline with same init: plain MLP sharing weights
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {"mp_degree": 8}
        fleet.init(is_collective=True, strategy=strategy)
        net = TPMLP()
        # snapshot init
        w1 = net.fc1.weight.numpy().copy()
        b1 = net.fc1.bias.numpy().copy()
        w2 = net.fc2.weight.numpy().copy()
        b2 = net.fc2.bias.numpy().copy()

        opt = P.optimizer.Adam(0.05, parameters=net.parameters())
        model = fleet.distributed_model(net)
        loss_fn = nn.CrossEntropyLoss()
        x, y = make_batch()
        tp_losses = []
        for _ in range(4):
            loss = model.train_batch([P.to_tensor(x)], [P.to_tensor(y)],
                                     opt, loss_fn)
            tp_losses.append(float(loss.numpy()))

        # dense baseline with identical weights
        _reset_fleet()
        dense = MLP()
        with P.no_grad():
            dense.fc1.weight.set_value(P.to_tensor(w1))
            dense.fc1.bias.set_value(P.to_tensor(b1))
            dense.fc2.weight.set_value(P.to_tensor(w2))
            dense.fc2.bias.set_value(P.to_tensor(b2))
        opt2 = P.optimizer.Adam(0.05, parameters=dense.parameters())
        ref = []
        for _ in range(4):
            loss = loss_fn(dense(P.to_tensor(x)), P.to_tensor(y))
            loss.backward()
            opt2.step()
            opt2.clear_grad()
            ref.append(float(loss.numpy()))
        assert np.allclose(tp_losses, ref, rtol=2e-3, atol=2e-4), \
            (tp_losses, ref)

    def test_tp_weights_physically_sharded(self):
        _reset_fleet()
        P.seed(0)
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {"mp_degree": 8}
        fleet.init(is_collective=True, strategy=strategy)
        net = TPMLP()
        opt = P.optimizer.SGD(0.1, parameters=net.parameters())
        model = fleet.distributed_model(net)
        x, y = make_batch()
        model.train_batch([P.to_tensor(x)], [P.to_tensor(y)], opt,
                          nn.CrossEntropyLoss())
        assert net.fc1.weight.dist_spec == (None, "mp")
        spec = net.fc1.weight._data.sharding.spec
        assert "mp" in [s for s in spec if s is not None]

    def test_mp_ops_explicit_shard_map(self):
        """Column→row parallel matmul with explicit collectives equals
        dense matmul."""
        from paddle_tpu.distributed._axis import axis_env
        from paddle_tpu.distributed.fleet import mp_ops
        from jax.sharding import Mesh, PartitionSpec as Pspec
        n = 4
        mesh = Mesh(np.array(jax.devices()[:n]), ("mp",))
        g = dist.new_group(list(range(n)), axis_name="mp")
        x = np.random.randn(2, 8).astype(np.float32)
        w1 = np.random.randn(8, 12).astype(np.float32)
        w2 = np.random.randn(12, 6).astype(np.float32)

        def body(xa, w1a, w2a):
            xt = P.Tensor(xa)
            xt = mp_ops._identity(xt, g)
            h = P.Tensor(jnp.maximum(xt._data @ w1a, 0.0))
            out = P.Tensor(h._data @ w2a)
            out = mp_ops._mp_allreduce(out, g)
            return out._data

        f = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=(Pspec(), Pspec(None, "mp"), Pspec("mp", None)),
            out_specs=Pspec()))
        with axis_env("mp"):
            out = np.asarray(f(x, w1, w2))
        ref = np.maximum(x @ w1, 0) @ w2
        assert np.allclose(out, ref, atol=1e-4)


class TestAutoParallel:
    def test_shard_tensor_and_reshard(self):
        mesh = dist.ProcessMesh(np.arange(8).reshape(4, 2), ["x", "y"])
        data = np.random.randn(8, 4).astype(np.float32)
        d = dist.shard_tensor(data, mesh, [dist.Shard(0), dist.Shard(1)])
        assert np.allclose(d.numpy(), data)
        spec = d._data.sharding.spec
        assert spec[0] == "x" and spec[1] == "y"
        r = dist.reshard(d, mesh, [dist.Replicate(), dist.Replicate()])
        assert np.allclose(r.numpy(), data)
        assert all(s is None for s in r._data.sharding.spec)


class TestRecompute:
    def test_recompute_matches_plain(self):
        from paddle_tpu.distributed.fleet.utils import recompute
        P.seed(3)
        net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 4))
        x = P.to_tensor(np.random.randn(5, 4).astype(np.float32))
        plain = net(x)
        plain.sum().backward()
        g_plain = [p.grad.numpy().copy() for p in net.parameters()]
        for p in net.parameters():
            p.clear_grad()
        out = recompute(net, x)
        assert np.allclose(out.numpy(), plain.numpy(), atol=1e-5)
        out.sum().backward()
        g_rc = [p.grad.numpy() for p in net.parameters()]
        for a, b in zip(g_plain, g_rc):
            assert np.allclose(a, b, atol=1e-5)

    def test_recompute_granularities_match_plain(self):
        """Round-4 remat-policy knob (VERDICT r3 item 2): full /
        full_attn / core_attn all produce the no-remat loss and grads;
        full_attn keeps the Pallas custom_vjp intact (kernel engaged in
        interpret mode with zero fallbacks)."""
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        from paddle_tpu.models.llama import LlamaPretrainingCriterion
        import paddle_tpu.ops.pallas.flash_attention as fa_mod
        ids = np.random.default_rng(0).integers(
            0, 128, (2, 128)).astype(np.int32)
        results = {}
        for gran in (None, "full", "full_attn", "core_attn"):
            cfg = LlamaConfig(
                vocab_size=128, hidden_size=256, intermediate_size=256,
                num_hidden_layers=2, num_attention_heads=4,
                num_key_value_heads=2, max_position_embeddings=128,
                recompute=gran is not None,
                recompute_granularity=gran or "full", dtype="float32")
            P.seed(7)
            model = LlamaForCausalLM(cfg)
            crit = LlamaPretrainingCriterion(cfg)
            fa_mod._FORCE_INTERPRET = True
            fa_mod.reset_dispatch_stats()
            try:
                loss = crit(model(P.to_tensor(ids)), P.to_tensor(ids))
                loss.backward()
                stats = fa_mod.dispatch_stats()
            finally:
                fa_mod._FORCE_INTERPRET = False
            assert stats["fallback"] == 0, (gran, stats)
            assert stats["pallas"] > 0, (gran, stats)
            g = model.llama.layers[0].self_attn.q_proj.weight.grad
            results[gran] = (float(loss.numpy()), g.numpy().copy())
        ref_l, ref_g = results[None]
        for gran in ("full", "full_attn", "core_attn"):
            l, g = results[gran]
            assert np.isclose(l, ref_l, atol=1e-5), (gran, l, ref_l)
            assert np.allclose(g, ref_g, atol=1e-4), gran

    def test_recompute_dropout_determinism(self):
        from paddle_tpu.distributed.fleet.utils import recompute
        net = nn.Sequential(nn.Linear(8, 8), nn.Dropout(0.5))
        x = P.to_tensor(np.ones((4, 8), np.float32))
        out = recompute(net, x)
        # backward must see the same mask (no error, grads finite)
        out.sum().backward()
        for p in net.parameters():
            assert np.all(np.isfinite(p.grad.numpy()))


class TestRNGTracker:
    def test_tracker_states(self):
        from paddle_tpu.distributed.fleet import get_rng_state_tracker
        tr = get_rng_state_tracker()
        tr.reset()
        tr.add("mp_rng", 123)
        with tr.rng_state("mp_rng"):
            a = P.randn([4]).numpy()
        with tr.rng_state("mp_rng"):
            b = P.randn([4]).numpy()
        assert not np.array_equal(a, b)  # stream advances
        tr.reset()
        tr.add("mp_rng", 123)
        with tr.rng_state("mp_rng"):
            c = P.randn([4]).numpy()
        assert np.array_equal(a, c)  # deterministic from seed


class TestGradientMerge:
    def test_gradient_merge_parity(self):
        """gradient_merge k_steps=2 over the SPMD engine == dense run on
        the concatenated batch (avg semantics)."""
        _reset_fleet()
        P.seed(5)
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 8}
        strategy.gradient_merge = True
        strategy.gradient_merge_configs = {"k_steps": 2, "avg": True}
        fleet.init(is_collective=True, strategy=strategy)
        net = MLP()
        snap = {n: p.numpy().copy() for n, p in net.named_parameters()}
        opt = P.optimizer.SGD(0.1, parameters=net.parameters())
        opt = fleet.distributed_optimizer(opt)
        model = fleet.distributed_model(net)
        loss_fn = nn.CrossEntropyLoss()
        x, y = make_batch()
        xa, ya = x[:8], y[:8]
        xb, yb = x[8:], y[8:]
        merged = []
        for _ in range(2):  # 2 optimizer steps = 4 micro-steps
            la = model.train_batch([P.to_tensor(xa)], [P.to_tensor(ya)],
                                   opt, loss_fn)
            lb = model.train_batch([P.to_tensor(xb)], [P.to_tensor(yb)],
                                   opt, loss_fn)
            merged.append((float(la.numpy()) + float(lb.numpy())) / 2)
        for p in net.parameters():
            p._data.block_until_ready()

        # oracle: eager accumulation of the two half-batch grads, then
        # one SGD step on the averaged grad
        _reset_fleet()
        P.seed(5)
        dense = MLP()
        dense.set_state_dict({n: P.to_tensor(a) for n, a in snap.items()})
        opt2 = P.optimizer.SGD(0.1, parameters=dense.parameters())
        ref = []
        for _ in range(2):
            tot = 0.0
            for xm, ym in ((xa, ya), (xb, yb)):
                loss = loss_fn(dense(P.to_tensor(xm)), P.to_tensor(ym)) / 2
                loss.backward()
                tot += float(loss.numpy())
            opt2.step()
            opt2.clear_grad()
            ref.append(tot)
        assert np.allclose(merged, ref, rtol=2e-3, atol=2e-4), (merged,
                                                                ref)
        _reset_fleet()


class TestTPZeroComposition:
    """ZeRO-3 must COMPOSE with TP: a TP-sharded weight is further
    sharded across the sharding group, and its optimizer states carry
    both axes (the 7B TP4 feasibility run exposed params at total/mp —
    ZeRO silently skipped for dist_spec'd params)."""

    def test_tp_param_and_state_carry_both_axes(self):
        _reset_fleet()
        P.seed(5)
        strategy = DistributedStrategy()
        strategy.sharding = True
        strategy.sharding_configs = {"stage": 3, "sharding_degree": 4}
        strategy.hybrid_configs = {"mp_degree": 2, "sharding_degree": 4}
        fleet.init(is_collective=True, strategy=strategy)
        net = TPMLP(din=8, dh=16, dout=4)
        opt = P.optimizer.Adam(0.05, parameters=net.parameters())
        model = fleet.distributed_model(net)
        loss_fn = nn.CrossEntropyLoss()
        x, y = make_batch()
        model.train_batch([P.to_tensor(x)], [P.to_tensor(y)], opt,
                          loss_fn)
        w = net.fc1.weight           # ColumnParallel: dim1 carries 'mp'
        spec = tuple(w._data.sharding.spec)
        flat = [a for s in spec if s is not None
                for a in (s if isinstance(s, tuple) else (s,))]
        assert "mp" in flat, spec
        assert "sharding" in flat, spec
        st = opt._accum[id(w)]
        m_flat = [a for s in st["moment1"].sharding.spec if s is not None
                  for a in (s if isinstance(s, tuple) else (s,))]
        assert "mp" in m_flat and "sharding" in m_flat, m_flat

    def test_tp_zero3_loss_parity(self):
        """composed TP×ZeRO-3 still trains to the dense baseline."""
        ref = baseline_losses()
        _reset_fleet()
        P.seed(5)
        strategy = DistributedStrategy()
        strategy.sharding = True
        strategy.sharding_configs = {"stage": 3, "sharding_degree": 4}
        strategy.hybrid_configs = {"mp_degree": 2, "sharding_degree": 4}
        fleet.init(is_collective=True, strategy=strategy)
        net = MLP()
        opt = P.optimizer.Adam(0.05, parameters=net.parameters())
        opt = fleet.distributed_optimizer(opt)
        model = fleet.distributed_model(net)
        loss_fn = nn.CrossEntropyLoss()
        x, y = make_batch()
        losses = []
        for _ in range(4):
            loss = model.train_batch([P.to_tensor(x)], [P.to_tensor(y)],
                                     opt, loss_fn)
            losses.append(float(loss.numpy()))
        assert np.allclose(losses, ref, rtol=2e-3, atol=2e-4), \
            (losses, ref)


class TestFusedAllreduceGradients:
    def test_identity_in_single_controller_regime(self):
        """fleet.utils.fused_allreduce_gradients: in the eager-SPMD view
        grads are already global — the helper must not rescale them."""
        from paddle_tpu.distributed.fleet.utils import \
            fused_allreduce_gradients
        _reset_fleet()
        P.seed(5)
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 8}
        fleet.init(is_collective=True, strategy=strategy)
        lin = P.nn.Linear(4, 2)
        x = P.to_tensor(np.ones((2, 4), np.float32))
        loss = (lin(x) * lin(x)).mean()
        loss.backward()
        g0 = lin.weight.grad.numpy().copy()
        fused_allreduce_gradients(list(lin.parameters()))
        np.testing.assert_allclose(g0, lin.weight.grad.numpy())

    def test_skips_params_without_grad(self):
        from paddle_tpu.distributed.fleet.utils import \
            fused_allreduce_gradients
        _reset_fleet()
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 8}
        fleet.init(is_collective=True, strategy=strategy)
        lin = P.nn.Linear(4, 2)
        fused_allreduce_gradients(list(lin.parameters()))  # no grads: noop
