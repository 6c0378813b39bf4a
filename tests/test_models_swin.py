"""Swin family parity vs the `transformers` torch oracle (weight
transplant — same strategy as tests/test_models_vit_t5.py). The tiny
config has an 8x8 stage-1 grid with window 4, so block 1 of stage 1
exercises the SHIFTED-window path (cyclic roll + cross-region mask) —
the parity check covers it end to end."""
import numpy as np
import pytest

import paddle_tpu as P

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

# cert marker (ADVICE.md #3): under PADDLE_TPU_CERT_RUN=1 the conftest
# makes these oracle deps mandatory (missing -> run FAILS, not skips)
pytestmark = pytest.mark.certification


def _t(a):
    return P.to_tensor(np.asarray(a.detach().numpy()))


def _set(p, a):
    p.set_value(_t(a))


def _tiny_hf():
    from transformers import SwinConfig as HFConfig, SwinModel
    cfg = HFConfig(
        image_size=32, patch_size=4, num_channels=3, embed_dim=32,
        depths=[2, 2], num_heads=[2, 4], window_size=4, mlp_ratio=2.0,
        drop_path_rate=0.0, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    torch.manual_seed(4)
    return SwinModel(cfg).eval()


def _transplant(hf):
    from paddle_tpu.vision.models.swin import (SwinConfig,
                                               SwinTransformer)
    ours = SwinTransformer(SwinConfig.tiny(num_classes=0))
    ours.eval()
    _set(ours.patch_embed.weight,
         hf.embeddings.patch_embeddings.projection.weight)
    _set(ours.patch_embed.bias,
         hf.embeddings.patch_embeddings.projection.bias)
    _set(ours.embed_norm.weight, hf.embeddings.norm.weight)
    _set(ours.embed_norm.bias, hf.embeddings.norm.bias)
    for hs, os_ in zip(hf.encoder.layers, ours.stages):
        for hb, ob in zip(hs.blocks, os_.blocks):
            a = hb.attention
            _set(ob.attn.query.weight, a.self.query.weight.T)
            _set(ob.attn.query.bias, a.self.query.bias)
            _set(ob.attn.key.weight, a.self.key.weight.T)
            _set(ob.attn.key.bias, a.self.key.bias)
            _set(ob.attn.value.weight, a.self.value.weight.T)
            _set(ob.attn.value.bias, a.self.value.bias)
            _set(ob.attn.relative_position_bias_table,
                 a.self.relative_position_bias_table)
            _set(ob.attn.proj.weight, a.output.dense.weight.T)
            _set(ob.attn.proj.bias, a.output.dense.bias)
            _set(ob.norm_before.weight, hb.layernorm_before.weight)
            _set(ob.norm_before.bias, hb.layernorm_before.bias)
            _set(ob.norm_after.weight, hb.layernorm_after.weight)
            _set(ob.norm_after.bias, hb.layernorm_after.bias)
            _set(ob.mlp_in.weight, hb.intermediate.dense.weight.T)
            _set(ob.mlp_in.bias, hb.intermediate.dense.bias)
            _set(ob.mlp_out.weight, hb.output.dense.weight.T)
            _set(ob.mlp_out.bias, hb.output.dense.bias)
        if hs.downsample is not None:
            _set(os_.downsample.norm.weight, hs.downsample.norm.weight)
            _set(os_.downsample.norm.bias, hs.downsample.norm.bias)
            _set(os_.downsample.reduction.weight,
                 hs.downsample.reduction.weight.T)
    _set(ours.norm.weight, hf.layernorm.weight)
    _set(ours.norm.bias, hf.layernorm.bias)
    return ours


class TestSwinParity:
    @pytest.fixture(scope="class")
    def pair(self):
        hf = _tiny_hf()
        return hf, _transplant(hf)

    def test_features_match_oracle(self, pair):
        hf, ours = pair
        x = np.random.default_rng(0).standard_normal(
            (2, 3, 32, 32)).astype(np.float32)
        with torch.no_grad():
            out = hf(torch.tensor(x))
            ref_seq = out.last_hidden_state.numpy()
            ref_pool = out.pooler_output.numpy()
        tok, pooled = ours.forward_features(P.to_tensor(x))
        got_seq = np.asarray(tok._data)
        assert got_seq.shape == ref_seq.shape
        np.testing.assert_allclose(got_seq, ref_seq, atol=3e-4,
                                   rtol=1e-3)
        np.testing.assert_allclose(np.asarray(pooled._data), ref_pool,
                                   atol=3e-4, rtol=1e-3)

    def test_shifted_window_mask_is_loadbearing(self, pair):
        """Zeroing the shift on block 1 must CHANGE the output — proves
        the parity above actually exercises the shifted path."""
        hf, ours = pair
        x = P.to_tensor(np.random.default_rng(1).standard_normal(
            (1, 3, 32, 32)).astype(np.float32))
        ref, _ = ours.forward_features(x)
        blk = ours.stages[0].blocks[1]
        assert blk.shift == 2 and blk._mask is not None
        saved_shift, saved_mask = blk.shift, blk._mask
        try:
            blk.shift, blk._mask = 0, None
            unshifted, _ = ours.forward_features(x)
        finally:
            blk.shift, blk._mask = saved_shift, saved_mask
        assert float(abs(ref - unshifted).max()) > 1e-3

    def test_trains(self):
        from paddle_tpu.vision.models.swin import (SwinConfig,
                                                   SwinTransformer)
        from paddle_tpu.optimizer import AdamW
        import paddle_tpu.nn.functional as F
        m = SwinTransformer(SwinConfig.tiny())
        m.train()
        opt = AdamW(learning_rate=1e-3, parameters=m.parameters())
        rng = np.random.default_rng(2)
        x = P.to_tensor(rng.standard_normal((4, 3, 32, 32))
                        .astype(np.float32))
        y = P.to_tensor(rng.integers(0, 10, (4,)).astype(np.int64))
        # the subject is the family, not the eager path: the loss is one
        # traced program (`to_static`) and backward() differentiates that
        # one program — not one XLA compile an op
        loss_of = P.jit.to_static(lambda x, y: F.cross_entropy(m(x), y))
        losses = []
        for _ in range(6):
            loss = loss_of(x, y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.9, losses

    def test_relative_bias_table_learns(self):
        from paddle_tpu.vision.models.swin import (SwinConfig,
                                                   SwinTransformer)
        from paddle_tpu.optimizer import AdamW
        import paddle_tpu.nn.functional as F
        m = SwinTransformer(SwinConfig.tiny())
        m.train()
        tbl = m.stages[0].blocks[0].attn.relative_position_bias_table
        before = np.asarray(tbl._data).copy()
        opt = AdamW(learning_rate=1e-2, parameters=m.parameters())
        rng = np.random.default_rng(5)
        x = P.to_tensor(rng.standard_normal((2, 3, 32, 32))
                        .astype(np.float32))
        y = P.to_tensor(rng.integers(0, 10, (2,)).astype(np.int64))
        loss = F.cross_entropy(m(x), y)
        loss.backward()
        opt.step()
        # the tensor-index gather must record on the tape: the table
        # has to actually move under the optimizer
        after = np.asarray(tbl._data)
        assert np.abs(after - before).max() > 1e-6

    def test_indivisible_config_rejected(self):
        from paddle_tpu.vision.models.swin import (SwinConfig,
                                                   SwinTransformer)
        with pytest.raises(ValueError, match="divisible"):
            SwinTransformer(SwinConfig(image_size=192))  # 48x48 vs w=7

    def test_builders(self):
        from paddle_tpu.vision.models import swin_t
        # described, not initialised (LazyGuard): the asserts read shapes
        # and structure; the same classes' initialisers run in this file's
        # tiny-config tests, not again at 28-86 M parameters
        with P.LazyGuard():
            m = swin_t(num_classes=5)
        assert m.head.weight.shape[1] == 5
        assert len(m.stages) == 4
