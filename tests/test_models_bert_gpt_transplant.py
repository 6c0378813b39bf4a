"""Transplant parity for the ORIGINAL bench families (BERT, GPT) vs
the `transformers` torch oracle — extending the round-7 evidence class
to the models the benchmarks run. HF GPT-2's Conv1D kernels are
[in, out], the same layout as this framework's Linear, so the GPT
transplant copies without transposes; BERT's torch Linears transpose as
usual."""
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


class TestGPT2Transplant:
    @pytest.fixture(scope="class")
    def pair(self):
        from transformers import GPT2Config as HFConfig, GPT2LMHeadModel
        from paddle_tpu.models import GPTConfig, GPTForCausalLM
        hf_cfg = HFConfig(
            vocab_size=256, n_positions=64, n_embd=64, n_layer=2,
            n_head=4, resid_pdrop=0.0, embd_pdrop=0.0, attn_pdrop=0.0,
            layer_norm_epsilon=1e-5)
        torch.manual_seed(7)
        hf = GPT2LMHeadModel(hf_cfg).eval()
        ours = GPTForCausalLM(GPTConfig.tiny(
            max_position_embeddings=64, tie_word_embeddings=True))
        ours.eval()
        g = ours.gpt
        t = hf.transformer
        _set(g.wte.weight, t.wte.weight)
        _set(g.wpe.weight, t.wpe.weight)
        for hb, ob in zip(t.h, g.h):
            _set(ob.ln_1.weight, hb.ln_1.weight)
            _set(ob.ln_1.bias, hb.ln_1.bias)
            # HF Conv1D: weight [in, out] == our Linear layout
            _set(ob.attn.qkv_proj.weight, hb.attn.c_attn.weight)
            _set(ob.attn.qkv_proj.bias, hb.attn.c_attn.bias)
            _set(ob.attn.out_proj.weight, hb.attn.c_proj.weight)
            _set(ob.attn.out_proj.bias, hb.attn.c_proj.bias)
            _set(ob.ln_2.weight, hb.ln_2.weight)
            _set(ob.ln_2.bias, hb.ln_2.bias)
            _set(ob.fc_in.weight, hb.mlp.c_fc.weight)
            _set(ob.fc_in.bias, hb.mlp.c_fc.bias)
            _set(ob.fc_out.weight, hb.mlp.c_proj.weight)
            _set(ob.fc_out.bias, hb.mlp.c_proj.bias)
        _set(g.ln_f.weight, t.ln_f.weight)
        _set(g.ln_f.bias, t.ln_f.bias)
        return hf, ours

    def test_logits_match_oracle(self, pair):
        hf, ours = pair
        ids = np.random.default_rng(0).integers(0, 256, (2, 16))
        with torch.no_grad():
            ref = hf(torch.tensor(ids)).logits.numpy()
        got = np.asarray(ours(P.to_tensor(
            ids.astype(np.int32)))._data)
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, atol=3e-4, rtol=1e-3)

    def test_greedy_generate_matches_oracle(self, pair):
        hf, ours = pair
        ids = np.random.default_rng(1).integers(0, 256, (1, 8))
        with torch.no_grad():
            ref = hf.generate(
                torch.tensor(ids), max_new_tokens=8, do_sample=False,
                pad_token_id=0).numpy()[:, 8:]
        got = np.asarray(ours.generate(
            P.to_tensor(ids.astype(np.int32)),
            max_new_tokens=8)._data)
        np.testing.assert_array_equal(got, ref)


class TestBertTransplant:
    @pytest.fixture(scope="class")
    def pair(self):
        from transformers import BertConfig as HFConfig, BertModel
        from paddle_tpu.models import BertConfig
        from paddle_tpu.models.bert import BertModel as OurBert
        hf_cfg = HFConfig(
            vocab_size=256, hidden_size=64, num_hidden_layers=2,
            num_attention_heads=4, intermediate_size=128,
            max_position_embeddings=128, type_vocab_size=2,
            hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0,
            layer_norm_eps=1e-12)
        torch.manual_seed(8)
        hf = BertModel(hf_cfg).eval()
        ours = OurBert(BertConfig.tiny())
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

    def test_sequence_and_pooled_match_oracle(self, pair):
        hf, ours = pair
        rng = np.random.default_rng(2)
        ids = rng.integers(0, 256, (2, 12))
        tok = rng.integers(0, 2, (2, 12))
        with torch.no_grad():
            out = hf(torch.tensor(ids),
                     token_type_ids=torch.tensor(tok))
            ref_seq = out.last_hidden_state.numpy()
            ref_pool = out.pooler_output.numpy()
        seq, pooled = ours(P.to_tensor(ids.astype(np.int32)),
                           P.to_tensor(tok.astype(np.int32)))
        np.testing.assert_allclose(np.asarray(seq._data), ref_seq,
                                   atol=3e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(pooled._data), ref_pool,
                                   atol=3e-4, rtol=1e-3)

    def test_padding_mask_matches_oracle(self, pair):
        hf, ours = pair
        rng = np.random.default_rng(3)
        ids = rng.integers(0, 256, (2, 10))
        am = np.ones((2, 10), np.int64)
        am[0, 7:] = 0
        am[1, 4:] = 0
        with torch.no_grad():
            ref = hf(torch.tensor(ids),
                     attention_mask=torch.tensor(am))
            ref_seq = ref.last_hidden_state.numpy()
        seq, _ = ours(P.to_tensor(ids.astype(np.int32)),
                      attention_mask=P.to_tensor(
                          am.astype(np.float32)))
        got = np.asarray(seq._data)
        # compare only VALID positions (masked keys don't affect them)
        np.testing.assert_allclose(got[0, :7], ref_seq[0, :7],
                                   atol=3e-4, rtol=1e-3)
        np.testing.assert_allclose(got[1, :4], ref_seq[1, :4],
                                   atol=3e-4, rtol=1e-3)
