"""ALBERT family parity vs the `transformers` torch oracle (weight
transplant). The load-bearing architectural checks: the factorized
embedding projection and CROSS-LAYER SHARING (one weight set applied L
times — depth changes outputs with zero new parameters)."""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models.albert import AlbertConfig, AlbertModel

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
    from transformers import AlbertConfig as HFConfig
    from transformers import AlbertModel as HFModel
    cfg = HFConfig(
        vocab_size=128, embedding_size=32, hidden_size=64,
        num_hidden_layers=3, num_hidden_groups=1, inner_group_num=1,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=64, type_vocab_size=2,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        classifier_dropout_prob=0.0)
    torch.manual_seed(9)
    return HFModel(cfg).eval()


def _transplant(hf):
    ours = AlbertModel(AlbertConfig.tiny())
    ours.eval()
    # the parity tests compare values, not the eager path: forward runs
    # as one traced program a shape, not one XLA compile an op
    P.jit.to_static(ours)
    e = hf.embeddings
    _set(ours.word_embeddings.weight, e.word_embeddings.weight)
    _set(ours.position_embeddings.weight, e.position_embeddings.weight)
    _set(ours.token_type_embeddings.weight,
         e.token_type_embeddings.weight)
    _set(ours.embed_norm.weight, e.LayerNorm.weight)
    _set(ours.embed_norm.bias, e.LayerNorm.bias)
    enc = hf.encoder
    _set(ours.embed_proj.weight,
         enc.embedding_hidden_mapping_in.weight.T)
    _set(ours.embed_proj.bias, enc.embedding_hidden_mapping_in.bias)
    hl = enc.albert_layer_groups[0].albert_layers[0]
    ol = ours.shared_layer
    at = hl.attention
    _set(ol.q.weight, at.query.weight.T)
    _set(ol.q.bias, at.query.bias)
    _set(ol.k.weight, at.key.weight.T)
    _set(ol.k.bias, at.key.bias)
    _set(ol.v.weight, at.value.weight.T)
    _set(ol.v.bias, at.value.bias)
    _set(ol.attn_out.weight, at.dense.weight.T)
    _set(ol.attn_out.bias, at.dense.bias)
    _set(ol.attn_norm.weight, at.LayerNorm.weight)
    _set(ol.attn_norm.bias, at.LayerNorm.bias)
    _set(ol.ffn.weight, hl.ffn.weight.T)
    _set(ol.ffn.bias, hl.ffn.bias)
    _set(ol.ffn_out.weight, hl.ffn_output.weight.T)
    _set(ol.ffn_out.bias, hl.ffn_output.bias)
    _set(ol.full_norm.weight, hl.full_layer_layer_norm.weight)
    _set(ol.full_norm.bias, hl.full_layer_layer_norm.bias)
    _set(ours.pooler.weight, hf.pooler.weight.T)
    _set(ours.pooler.bias, hf.pooler.bias)
    return ours


class TestAlbertParity:
    @pytest.fixture(scope="class")
    def pair(self):
        hf = _tiny_hf()
        return hf, _transplant(hf)

    def test_sequence_and_pooled_match_oracle(self, pair):
        hf, ours = pair
        rng = np.random.default_rng(0)
        ids = rng.integers(0, 128, (2, 12))
        tok = rng.integers(0, 2, (2, 12))
        with torch.no_grad():
            out = hf(torch.tensor(ids),
                     token_type_ids=torch.tensor(tok))
        seq, pooled = ours(P.to_tensor(ids.astype(np.int32)),
                           P.to_tensor(tok.astype(np.int32)))
        np.testing.assert_allclose(np.asarray(seq._data),
                                   out.last_hidden_state.numpy(),
                                   atol=3e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(pooled._data),
                                   out.pooler_output.numpy(),
                                   atol=3e-4, rtol=1e-3)

    def test_cross_layer_sharing_is_real(self):
        """Depth L vs L+2 with IDENTICAL parameters: outputs differ
        (depth is load-bearing) while the parameter count is
        unchanged — the ALBERT signature property."""
        P.seed(1)
        m3 = AlbertModel(AlbertConfig.tiny(num_hidden_layers=3))
        m5 = AlbertModel(AlbertConfig.tiny(num_hidden_layers=5))
        m5.set_state_dict(m3.state_dict())  # same params, deeper loop
        m3.eval()
        m5.eval()
        n3 = sum(np.prod(p.shape) for _, p in m3.named_parameters())
        n5 = sum(np.prod(p.shape) for _, p in m5.named_parameters())
        assert n3 == n5
        ids = P.to_tensor(np.random.default_rng(2).integers(
            0, 128, (1, 8)).astype(np.int32))
        a, _ = m3(ids)
        b, _ = m5(ids)
        assert np.abs(np.asarray(a._data)
                      - np.asarray(b._data)).max() > 1e-3

    def test_trains(self):
        from paddle_tpu.optimizer import AdamW
        import paddle_tpu.nn.functional as F
        P.seed(3)
        m = AlbertModel(AlbertConfig.tiny())
        head = P.nn.Linear(64, 2)
        m.train()
        params = m.parameters() + head.parameters()
        opt = AdamW(learning_rate=1e-3, parameters=params)
        rng = np.random.default_rng(3)
        ids = P.to_tensor(rng.integers(0, 128, (4, 10))
                          .astype(np.int32))
        y = P.to_tensor(rng.integers(0, 2, (4,)).astype(np.int64))
        # the subject is the family, not the eager path: the loss is one
        # traced program (`to_static`) and backward() differentiates that
        # one program — not one XLA compile an op
        loss_of = P.jit.to_static(
            lambda ids, y: F.cross_entropy(head(m(ids)[1]), y))
        losses = []
        for _ in range(8):
            loss = loss_of(ids, y)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.9, losses
