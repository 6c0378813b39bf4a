"""Model-family tests: forward shapes, causal-LM loss decreases, TP parity
for LLaMA (the north-star model)."""
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.nn as nn
from paddle_tpu.models import (BertConfig, BertForSequenceClassification,
                               GPTConfig, GPTForCausalLM, LlamaConfig,
                               LlamaForCausalLM, LlamaPretrainingCriterion,
                               count_params)


def _reset_fleet():
    from paddle_tpu.distributed.fleet.fleet import _state
    from paddle_tpu.distributed.fleet.topology import \
        set_hybrid_communicate_group
    _state.initialized = False
    _state.strategy = None
    _state.hcg = None
    set_hybrid_communicate_group(None)


def batch(cfg_vocab, b=2, s=16, seed=0):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, cfg_vocab, (b, s)).astype(np.int32)
    return P.to_tensor(ids)


class TestLlama:
    def test_forward_shape(self):
        _reset_fleet()
        P.seed(0)
        cfg = LlamaConfig.tiny()
        m = LlamaForCausalLM(cfg)
        ids = batch(cfg.vocab_size)
        out = m(ids)
        assert out.shape == [2, 16, cfg.vocab_size]

    def test_param_count_7b(self):
        cfg = LlamaConfig.llama2_7b()
        n = count_params(cfg)
        assert 6.5e9 < n < 7.0e9  # ≈6.74B

    def test_loss_decreases(self):
        _reset_fleet()
        P.seed(0)
        cfg = LlamaConfig.tiny()
        m = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg)
        opt = P.optimizer.AdamW(1e-3, parameters=m.parameters())
        ids = batch(cfg.vocab_size, b=4, s=32)
        # the subject is the family, not the eager path: the loss is one
        # traced program (`to_static`) and backward() differentiates that
        # one program — not one XLA compile an op
        loss_of = P.jit.to_static(lambda ids: crit(m(ids), ids))
        losses = []
        for _ in range(8):
            loss = loss_of(ids)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0] * 0.8, losses

    def test_gqa(self):
        _reset_fleet()
        P.seed(0)
        cfg = LlamaConfig.tiny(num_key_value_heads=2)
        m = LlamaForCausalLM(cfg)
        out = m(batch(cfg.vocab_size))
        assert out.shape == [2, 16, cfg.vocab_size]

    def test_tp_training_via_fleet(self):
        _reset_fleet()
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.fleet import DistributedStrategy
        P.seed(0)
        strategy = DistributedStrategy()
        strategy.hybrid_configs = {"mp_degree": 4, "dp_degree": 2}
        fleet.init(is_collective=True, strategy=strategy)
        cfg = LlamaConfig.tiny(tensor_parallel=True)
        m = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg)
        opt = P.optimizer.AdamW(1e-3, parameters=m.parameters())
        model = fleet.distributed_model(m)
        ids = batch(cfg.vocab_size, b=4, s=32)
        l0 = model.train_batch([ids], [ids], opt, crit)
        l1 = model.train_batch([ids], [ids], opt, crit)
        assert float(l1.numpy()) < float(l0.numpy())
        # q weight sharded over mp
        spec = m.llama.layers[0].self_attn.q_proj.weight._data.sharding.spec
        assert "mp" in [s for s in spec if s is not None]

    def test_zero3_training_via_fleet(self):
        _reset_fleet()
        from paddle_tpu.distributed import fleet
        from paddle_tpu.distributed.fleet import DistributedStrategy
        P.seed(0)
        strategy = DistributedStrategy()
        strategy.sharding = True
        strategy.sharding_configs = {"stage": 3, "sharding_degree": 8}
        strategy.hybrid_configs = {"sharding_degree": 8}
        fleet.init(is_collective=True, strategy=strategy)
        cfg = LlamaConfig.tiny()
        m = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg)
        opt = P.optimizer.AdamW(1e-3, parameters=m.parameters())
        model = fleet.distributed_model(m)
        ids = batch(cfg.vocab_size, b=8, s=32)
        l0 = model.train_batch([ids], [ids], opt, crit)
        l1 = model.train_batch([ids], [ids], opt, crit)
        assert float(l1.numpy()) < float(l0.numpy())


class TestGPT:
    def test_forward_and_train(self):
        _reset_fleet()
        P.seed(0)
        cfg = GPTConfig.tiny()
        m = GPTForCausalLM(cfg)
        ids = batch(cfg.vocab_size, b=4, s=32)
        out = m(ids)
        assert out.shape == [4, 32, cfg.vocab_size]
        opt = P.optimizer.AdamW(1e-3, parameters=m.parameters())
        # the eager forward is asserted above; the five training steps are
        # about the family: the loss is one traced program (`to_static`)
        # and backward() differentiates that one program
        loss_of = P.jit.to_static(lambda ids: nn.functional.cross_entropy(
            m(ids)[:, :-1].reshape([-1, cfg.vocab_size]),
            ids[:, 1:].reshape([-1])))
        losses = []
        for _ in range(5):
            loss = loss_of(ids)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0]


class TestBert:
    def test_classification(self):
        _reset_fleet()
        P.seed(0)
        cfg = BertConfig.tiny()
        m = BertForSequenceClassification(cfg)
        ids = batch(cfg.vocab_size, b=4, s=24)
        mask = P.ones([4, 24], dtype="int32")
        logits = m(ids, attention_mask=mask)
        assert logits.shape == [4, 2]

    def test_amp_o2_fine_tune_step(self):
        """Config-2 pattern: BERT AMP-O2 training step."""
        _reset_fleet()
        P.seed(0)
        cfg = BertConfig.tiny()
        m = BertForSequenceClassification(cfg)
        opt = P.optimizer.AdamW(1e-4, parameters=m.parameters())
        model, opt = P.amp.decorate(m, opt, level="O2", dtype="bfloat16")
        scaler = P.amp.GradScaler()
        ids = batch(cfg.vocab_size, b=4, s=24)
        labels = P.to_tensor(np.array([0, 1, 0, 1], np.int32))
        losses = []
        for _ in range(5):
            with P.amp.auto_cast(level="O2", dtype="bfloat16"):
                logits = model(ids)
                loss = nn.functional.cross_entropy(
                    logits.astype("float32"), labels)
            scaled = scaler.scale(loss)
            scaled.backward()
            scaler.step(opt)
            scaler.update()
            opt.clear_grad()
            losses.append(float(loss.numpy()))
        assert losses[-1] < losses[0]


class TestFusedLinearCrossEntropy:
    def test_fused_loss_and_grads_match_unfused(self):
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       LlamaPretrainingCriterion)
        P.seed(0)
        base = dict(vocab_size=128, hidden_size=32, intermediate_size=64,
                    num_hidden_layers=2, num_attention_heads=4,
                    max_position_embeddings=64)
        cfgF = LlamaConfig(**base, fuse_linear_cross_entropy=True,
                           loss_chunk_size=16)
        cfgU = LlamaConfig(**base)
        mF = LlamaForCausalLM(cfgF)
        snap = {n: p.numpy().copy() for n, p in mF.named_parameters()}
        P.seed(0)
        mU = LlamaForCausalLM(cfgU)
        mU.set_state_dict({n: P.to_tensor(a) for n, a in snap.items()})

        critF = LlamaPretrainingCriterion(cfgF).bind(mF)
        critU = LlamaPretrainingCriterion(cfgU)
        ids = P.to_tensor(np.random.default_rng(0).integers(
            0, 128, (2, 40)).astype(np.int32))  # 39 = 2*16 + 7 tail

        lF = critF(mF(ids), ids)
        lU = critU(mU(ids), ids)
        assert np.allclose(lF.numpy(), lU.numpy(), rtol=1e-5), \
            (lF.numpy(), lU.numpy())

        lF.backward()
        lU.backward()
        for (n, pF), (_, pU) in zip(mF.named_parameters(),
                                    mU.named_parameters()):
            gF = pF.grad.numpy() if pF.grad is not None else None
            gU = pU.grad.numpy() if pU.grad is not None else None
            assert (gF is None) == (gU is None), n
            if gF is not None:
                assert np.allclose(gF, gU, rtol=1e-4, atol=1e-5), n

    def test_fused_eval_still_returns_logits(self):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        P.seed(0)
        cfg = LlamaConfig(vocab_size=128, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=1,
                          num_attention_heads=4,
                          max_position_embeddings=32,
                          fuse_linear_cross_entropy=True)
        m = LlamaForCausalLM(cfg)
        m.eval()
        ids = P.to_tensor(np.zeros((1, 8), np.int32))
        out = m(ids)
        assert out.shape[-1] == 128

    def test_no_flash_matches_flash(self):
        from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
        base = dict(vocab_size=64, hidden_size=32, intermediate_size=64,
                    num_hidden_layers=1, num_attention_heads=4,
                    max_position_embeddings=32)
        P.seed(0)
        mF = LlamaForCausalLM(LlamaConfig(**base))
        snap = {n: p.numpy().copy() for n, p in mF.named_parameters()}
        P.seed(0)
        mN = LlamaForCausalLM(LlamaConfig(**base,
                                          use_flash_attention=False))
        mN.set_state_dict({n: P.to_tensor(a) for n, a in snap.items()})
        ids = P.to_tensor(np.random.default_rng(1).integers(
            0, 64, (2, 16)).astype(np.int32))
        np.testing.assert_allclose(mF(ids).numpy(), mN(ids).numpy(),
                                   rtol=1e-4, atol=1e-5)


class TestOverfitConvergence:
    """End-to-end integration: the full training stack (model + AdamW +
    criterion + compiled stepper) must overfit a repeated batch — the
    loss-curve sanity check behind BASELINE's parity target."""

    def test_llama_proxy_overfits_fixed_batch(self):
        from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                                       LlamaPretrainingCriterion)
        P.seed(0)
        cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4,
                          max_position_embeddings=32)
        model = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg)
        opt = P.optimizer.AdamW(5e-3, parameters=model.parameters())
        m = P.Model(model)
        m.prepare(opt, crit)
        ids = P.to_tensor(np.random.default_rng(0).integers(
            0, 128, (4, 32)).astype(np.int32))
        first = last = None
        for _ in range(60):
            loss = m.train_batch([ids], [ids])
            v = float(np.asarray(loss._data if hasattr(loss, "_data")
                                 else loss))
            if first is None:
                first = v
            last = v
        # random init CE ~ ln(128) ~ 4.85; memorizing one batch must cut
        # it by an order of magnitude
        assert first > 3.5, first
        assert last < 0.5, (first, last)


class TestLlamaFlashMask:
    """Round-4: attn_mask_startend_row_indices threads through the model
    (reference: PaddleNLP document-packing training via FlashMask)."""

    def _cfg(self, **kw):
        from paddle_tpu.models.llama import LlamaConfig
        return LlamaConfig(**{**dict(
            vocab_size=128, hidden_size=256, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, max_position_embeddings=256,
            dtype="float32"), **kw})

    def test_document_packing_isolation(self, monkeypatch):
        """Packed doc0's logits match running doc0 alone (columns of
        doc0 masked for rows >= 128), kernel engaged per layer."""
        import paddle_tpu.ops.pallas.flash_attention as fa
        from paddle_tpu.models.llama import LlamaForCausalLM
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        fa.reset_dispatch_stats()
        P.seed(0)
        model = LlamaForCausalLM(self._cfg())
        ids = np.random.default_rng(0).integers(
            0, 128, (1, 256)).astype(np.int32)
        starts = np.full((1, 1, 256, 1), 2 ** 31 - 1, np.int32)
        starts[:, :, :128, 0] = 128
        out = model(P.to_tensor(ids),
                    attn_mask_startend_row_indices=P.to_tensor(starts))
        stats = fa.dispatch_stats()
        assert stats["fallback"] == 0 and stats["pallas"] >= 2, stats
        out0 = model(P.to_tensor(ids[:, :128]))
        np.testing.assert_allclose(np.asarray(out._data)[:, :128],
                                   np.asarray(out0._data), atol=1e-4)

    def test_trains_with_remat(self):
        from paddle_tpu.models.llama import (LlamaForCausalLM,
                                             LlamaPretrainingCriterion)
        cfg = self._cfg(recompute=True)
        P.seed(0)
        model = LlamaForCausalLM(cfg)
        crit = LlamaPretrainingCriterion(cfg)
        ids = np.random.default_rng(1).integers(
            0, 128, (1, 256)).astype(np.int32)
        starts = np.full((1, 1, 256, 1), 2 ** 31 - 1, np.int32)
        starts[:, :, :128, 0] = 128
        loss = crit(model(
            P.to_tensor(ids),
            attn_mask_startend_row_indices=P.to_tensor(starts)),
            P.to_tensor(ids))
        loss.backward()
        g = model.llama.layers[0].self_attn.q_proj.weight.grad
        assert g is not None
        assert np.isfinite(np.asarray(g._data)).all()

    def test_mutually_exclusive_with_attn_mask(self):
        from paddle_tpu.models.llama import LlamaForCausalLM
        P.seed(0)
        model = LlamaForCausalLM(self._cfg())
        ids = P.to_tensor(np.zeros((1, 128), np.int32))
        m = P.to_tensor(np.ones((1, 1, 128, 128), bool))
        idx = P.to_tensor(np.zeros((1, 1, 128, 1), np.int32))
        with pytest.raises(ValueError, match="mutually exclusive"):
            model(ids, attn_mask=m, attn_mask_startend_row_indices=idx)


class TestGPTMasks:
    """Round-4: GPT accepts attn_mask AND attn_mask_startend_row_indices
    (it previously took neither — reference GPT forward carries an
    attention_mask)."""

    def _model(self):
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        P.seed(0)
        return GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=128, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=2,
            max_position_embeddings=256, hidden_dropout_prob=0.0,
            attention_dropout_prob=0.0))

    def test_flashmask_document_isolation(self, monkeypatch):
        import paddle_tpu.ops.pallas.flash_attention as fa
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        fa.reset_dispatch_stats()
        model = self._model()
        ids = np.random.default_rng(0).integers(
            0, 128, (1, 256)).astype(np.int32)
        starts = np.full((1, 1, 256, 1), 2 ** 31 - 1, np.int32)
        starts[:, :, :128, 0] = 128
        out = model(P.to_tensor(ids),
                    attn_mask_startend_row_indices=P.to_tensor(starts))
        stats = fa.dispatch_stats()
        assert stats["fallback"] == 0 and stats["pallas"] >= 2, stats
        out0 = model(P.to_tensor(ids[:, :128]))
        np.testing.assert_allclose(np.asarray(out._data)[:, :128],
                                   np.asarray(out0._data), atol=1e-4)

    def test_attn_mask_load_bearing(self):
        """The padding mask must actually change row 1's outputs: its
        first 48 positions equal running the 48-token prefix alone."""
        model = self._model()
        ids_np = np.random.default_rng(1).integers(
            0, 128, (2, 64)).astype(np.int32)
        keep = np.ones((2, 1, 1, 64), bool)
        keep[1, :, :, 48:] = False          # pad tail of row 1
        out = model(P.to_tensor(ids_np), attn_mask=P.to_tensor(keep))
        assert list(out.shape) == [2, 64, 128]
        alone = model(P.to_tensor(ids_np[1:2, :48]))
        np.testing.assert_allclose(
            np.asarray(out._data)[1, :48],
            np.asarray(alone._data)[0], atol=1e-4)
        # and the mask is not a no-op vs the unmasked run
        unmasked = model(P.to_tensor(ids_np))
        # causal: rows < 48 never see cols >= 48, so compare a late row
        d = np.abs(np.asarray(out._data)[1, 60] -
                   np.asarray(unmasked._data)[1, 60]).max()
        assert d > 1e-4

    def test_flashmask_trains_with_remat(self, monkeypatch):
        """The recompute branch threads the mask closures (backward
        replay must see the same bounds)."""
        import paddle_tpu.ops.pallas.flash_attention as fa
        from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        P.seed(0)
        model = GPTForCausalLM(GPTConfig(
            vocab_size=128, hidden_size=128, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=2,
            max_position_embeddings=256, hidden_dropout_prob=0.0,
            attention_dropout_prob=0.0, recompute=True))
        ids = np.random.default_rng(2).integers(
            0, 128, (1, 256)).astype(np.int32)
        starts = np.full((1, 1, 256, 1), 2 ** 31 - 1, np.int32)
        starts[:, :, :128, 0] = 128
        crit = P.nn.CrossEntropyLoss()
        logits = model(P.to_tensor(ids),
                       attn_mask_startend_row_indices=P.to_tensor(starts))
        loss = crit(logits.reshape([-1, 128]),
                    P.to_tensor(ids.reshape(-1).astype(np.int64)))
        loss.backward()
        g = model.gpt.h[0].attn.qkv_proj.weight.grad
        assert g is not None
        assert np.isfinite(np.asarray(g._data)).all()
        assert np.abs(np.asarray(g._data)).sum() > 0
