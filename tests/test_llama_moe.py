"""MoE LLaMA model family (round-6): LlamaConfig(moe_num_experts=N)
swaps the dense SwiGLU MLP for incubate.MoELayer on every
moe_layer_interval-th decoder layer, with the gate aux loss folded in
by LlamaPretrainingCriterion(model=...). Reference: incubate MoELayer +
the PaddleNLP MoE-LLaMA family (upstream unverified — mount empty)."""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.incubate.moe import MoELayer
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                               LlamaPretrainingCriterion)
from paddle_tpu.models.llama import LlamaMLP


def _cfg(**kw):
    return LlamaConfig.tiny(moe_num_experts=4, moe_top_k=2, **kw)


def _batch(cfg, b=2, s=16, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (b, s)).astype(np.int32)
    return P.to_tensor(ids)


class TestMoELlamaConstruction:
    def test_layers_and_interval(self):
        m = LlamaForCausalLM(_cfg())
        assert all(isinstance(layer.mlp, MoELayer)
                   for layer in m.llama.layers)
        m2 = LlamaForCausalLM(LlamaConfig.tiny(
            moe_num_experts=4, moe_layer_interval=2,
            num_hidden_layers=4))
        kinds = [type(layer.mlp) for layer in m2.llama.layers]
        assert kinds == [MoELayer, LlamaMLP, MoELayer, LlamaMLP]

    def test_expert_dim_carries_ep_dist_spec(self):
        m = LlamaForCausalLM(_cfg())
        moe = m.llama.layers[0].mlp
        assert moe.w_in.dist_spec == ("sharding", None, None)
        assert moe.w_out.dist_spec == ("sharding", None, None)

    def test_recompute_guard(self):
        with pytest.raises(NotImplementedError):
            LlamaForCausalLM(_cfg(recompute=True))
        # attention-only remat is the supported composition
        m = LlamaForCausalLM(_cfg(recompute=True,
                                  recompute_granularity="core_attn"))
        assert isinstance(m.llama.layers[0].mlp, MoELayer)


class TestMoELlamaTraining:
    def test_forward_sets_aux_and_criterion_adds_it(self):
        cfg = _cfg()
        P.seed(0)
        m = LlamaForCausalLM(cfg)
        ids = _batch(cfg)
        logits = m(ids)
        aux = m.moe_aux_loss()
        assert aux is not None and float(np.asarray(aux.numpy())) > 0
        # the aux rides ON the logits: every criterion construction
        # (plain, model=, bind) folds it in identically
        lp = float(np.asarray(
            LlamaPretrainingCriterion(cfg)(logits, ids).numpy()))
        lm = float(np.asarray(
            LlamaPretrainingCriterion(cfg, model=m)(logits, ids).numpy()))
        assert abs(lp - lm) < 1e-7
        # weight 0 turns it off; the difference is exactly w * aux
        cfg0 = _cfg(moe_aux_loss_weight=0.0)
        l0 = float(np.asarray(
            LlamaPretrainingCriterion(cfg0)(logits, ids).numpy()))
        expected = l0 + cfg.moe_aux_loss_weight * float(
            np.asarray(aux.numpy()))
        assert abs(lm - expected) < 1e-6

    def test_aux_bound_to_producing_forward(self):
        """An interleaved eval/decode forward must not corrupt the aux
        folded into a training loss (the aux rides the logits)."""
        cfg = _cfg()
        P.seed(0)
        m = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg)
        train_ids = _batch(cfg, seed=0)
        logits = m(train_ids)
        aux_train = float(np.asarray(logits._moe_aux.numpy()))
        m(_batch(cfg, seed=99))  # interleaved forward overwrites l_aux
        cfg0 = _cfg(moe_aux_loss_weight=0.0)
        base = float(np.asarray(
            LlamaPretrainingCriterion(cfg0)(logits, train_ids).numpy()))
        got = float(np.asarray(crit(logits, train_ids).numpy()))
        assert abs(got - (base + cfg.moe_aux_loss_weight * aux_train)) \
            < 1e-6

    def test_trains_and_gate_gets_gradients(self):
        cfg = _cfg()
        P.seed(0)
        m = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg, model=m)
        opt = P.optimizer.AdamW(5e-3, parameters=m.parameters())
        ids = _batch(cfg)
        # the subject is the family, not the eager path: the loss is one
        # traced program (`to_static`) and backward() differentiates that
        # one program — not one XLA compile an op
        loss_of = P.jit.to_static(lambda ids: crit(m(ids), ids))
        losses = []
        for _ in range(8):
            loss = loss_of(ids)
            loss.backward()
            gate_w = m.llama.layers[0].mlp.gate.weight
            assert gate_w.grad is not None
            assert float(np.abs(np.asarray(gate_w.grad.numpy())).max()) \
                > 0
            opt.step()
            opt.clear_grad()
            losses.append(float(np.asarray(loss.numpy())))
        assert losses[-1] < losses[0]

    def test_compiled_step_matches_eager(self):
        from paddle_tpu.jit import to_static
        cfg = _cfg()
        P.seed(0)
        m = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg, model=m)
        ids = _batch(cfg)

        def loss_of(batch):
            return crit(m(batch), batch)

        eager = float(np.asarray(loss_of(ids).numpy()))
        st = to_static(loss_of)
        compiled = float(np.asarray(st(ids).numpy()))
        assert abs(eager - compiled) < 1e-4

    def test_generation_runs(self):
        cfg = _cfg()
        P.seed(0)
        m = LlamaForCausalLM(cfg)
        out = m.generate(_batch(cfg, b=1, s=4), max_new_tokens=4,
                         do_sample=False)
        ids = out[0] if isinstance(out, (tuple, list)) else out
        # reference generate() returns the NEW tokens
        assert ids.shape[-1] == 4


class TestMoELlamaPipeGuard:
    def test_pipe_rejects_moe(self):
        from paddle_tpu.models.llama import LlamaForCausalLMPipe
        with pytest.raises(NotImplementedError):
            LlamaForCausalLMPipe(_cfg(), num_stages=2)


class TestMoELlamaSPMD:
    def test_ep_sharded_train_step(self):
        """The fleet SPMD engine shards the expert dim over the
        'sharding' axis — one real train step on a dp2 x sharding4
        mesh (the EP regime of the driver dryrun, through the MODEL
        family instead of a bare layer)."""
        import jax
        if len(jax.devices()) < 8:
            pytest.skip("needs the 8-device conftest mesh")
        from jax.sharding import Mesh

        from paddle_tpu.distributed.fleet import (DistributedStrategy,
                                                  SPMDTrainer)
        cfg = _cfg()
        P.seed(0)
        m = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg, model=m)
        opt = P.optimizer.AdamW(1e-3, parameters=m.parameters())
        devs = np.asarray(jax.devices()[:8]).reshape(2, 4)
        mesh = Mesh(devs, ("dp", "sharding"))
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {"dp_degree": 2, "sharding_degree": 4}
        tr = SPMDTrainer(m, opt, lambda out, lb: crit(out, lb),
                         mesh, strategy=strategy)
        ids = _batch(cfg, b=8)  # batch shards over dp x sharding = 8
        loss = tr.train_batch([ids], [ids])
        v = float(np.asarray(loss.numpy() if hasattr(loss, "numpy")
                             else loss))
        assert np.isfinite(v) and v > 0

