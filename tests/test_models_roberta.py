"""RoBERTa parity vs the `transformers` torch oracle: the position-id
offset convention is the load-bearing difference from BERT (the test
proves offset-less positions give DIFFERENT outputs)."""
import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models.roberta import RobertaConfig, RobertaModel

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")

# cert marker (ADVICE.md #3): under PADDLE_TPU_CERT_RUN=1 the conftest
# makes these oracle deps mandatory (missing -> run FAILS, not skips)
pytestmark = pytest.mark.certification


def _t(a):
    return P.to_tensor(np.asarray(a.detach().numpy()))


def _set(p, a):
    p.set_value(_t(a))


@pytest.fixture(scope="module")
def pair():
    from transformers import RobertaConfig as HFConfig, RobertaModel \
        as HFModel
    hf_cfg = HFConfig(
        vocab_size=256, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        max_position_embeddings=130, type_vocab_size=1,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
        layer_norm_eps=1e-5, pad_token_id=1)
    torch.manual_seed(11)
    hf = HFModel(hf_cfg, add_pooling_layer=True).eval()
    ours = RobertaModel(RobertaConfig.tiny())
    ours.eval()
    # the parity tests compare values, not the eager path: forward runs
    # as one traced program a shape, not one XLA compile an op
    P.jit.to_static(ours)
    e = hf.embeddings
    _set(ours.embeddings.word_embeddings.weight,
         e.word_embeddings.weight)
    _set(ours.embeddings.position_embeddings.weight,
         e.position_embeddings.weight)
    _set(ours.embeddings.token_type_embeddings.weight,
         e.token_type_embeddings.weight)
    _set(ours.embeddings.layer_norm.weight, e.LayerNorm.weight)
    _set(ours.embeddings.layer_norm.bias, e.LayerNorm.bias)
    for hl, ol in zip(hf.encoder.layer, ours.encoder):
        at = hl.attention
        _set(ol.q.weight, at.self.query.weight.T)
        _set(ol.q.bias, at.self.query.bias)
        _set(ol.k.weight, at.self.key.weight.T)
        _set(ol.k.bias, at.self.key.bias)
        _set(ol.v.weight, at.self.value.weight.T)
        _set(ol.v.bias, at.self.value.bias)
        _set(ol.attn_out.weight, at.output.dense.weight.T)
        _set(ol.attn_out.bias, at.output.dense.bias)
        _set(ol.attn_norm.weight, at.output.LayerNorm.weight)
        _set(ol.attn_norm.bias, at.output.LayerNorm.bias)
        _set(ol.ffn_in.weight, hl.intermediate.dense.weight.T)
        _set(ol.ffn_in.bias, hl.intermediate.dense.bias)
        _set(ol.ffn_out.weight, hl.output.dense.weight.T)
        _set(ol.ffn_out.bias, hl.output.dense.bias)
        _set(ol.ffn_norm.weight, hl.output.LayerNorm.weight)
        _set(ol.ffn_norm.bias, hl.output.LayerNorm.bias)
    _set(ours.pooler.weight, hf.pooler.dense.weight.T)
    _set(ours.pooler.bias, hf.pooler.dense.bias)
    return hf, ours


def test_outputs_match_oracle(pair):
    hf, ours = pair
    # ids must avoid pad (1): HF derives positions from non-pad mask
    ids = np.random.default_rng(0).integers(2, 256, (2, 12))
    with torch.no_grad():
        out = hf(torch.tensor(ids))
    seq, pooled = ours(P.to_tensor(ids.astype(np.int32)))
    np.testing.assert_allclose(np.asarray(seq._data),
                               out.last_hidden_state.numpy(),
                               atol=3e-4, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(pooled._data),
                               out.pooler_output.numpy(),
                               atol=3e-4, rtol=1e-3)


def test_position_offset_is_load_bearing(pair):
    _, ours = pair
    ids = P.to_tensor(np.random.default_rng(1).integers(
        2, 256, (1, 8)).astype(np.int32))
    a, _ = ours(ids)
    b, _ = ours(ids, position_ids=P.to_tensor(
        np.arange(8)[None].astype(np.int32)))  # BERT-style, no offset
    assert np.abs(np.asarray(a._data) - np.asarray(b._data)).max() \
        > 1e-3


def test_padded_batch_matches_oracle(pair):
    """HF derives positions from the non-pad cumsum — a padded batch's
    REAL tokens must match the oracle (the convention the plain
    arange+2 would break)."""
    hf, ours = pair
    rng = np.random.default_rng(2)
    ids = rng.integers(2, 256, (2, 10))
    ids[0, 7:] = 1  # right-pad with pad_token_id=1
    am = (ids != 1).astype(np.int64)
    with torch.no_grad():
        ref = hf(torch.tensor(ids),
                 attention_mask=torch.tensor(am)).last_hidden_state
    seq, _ = ours(P.to_tensor(ids.astype(np.int32)),
                  attention_mask=P.to_tensor(am.astype(np.float32)))
    got = np.asarray(seq._data)
    np.testing.assert_allclose(got[0, :7], ref.numpy()[0, :7],
                               atol=3e-4, rtol=1e-3)
    np.testing.assert_allclose(got[1], ref.numpy()[1], atol=3e-4,
                               rtol=1e-3)
