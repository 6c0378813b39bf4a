"""wav2vec2 family parity vs the `transformers` torch oracle (weight
transplant — same strategy as tests/test_models_vit_t5.py). The pos-conv
weight-norm parametrization is materialized on the torch side before
transplant."""
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
    from transformers import Wav2Vec2Config, Wav2Vec2ForCTC
    cfg = Wav2Vec2Config(
        vocab_size=32, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=128,
        conv_dim=[16, 16, 16], conv_kernel=[10, 3, 3],
        conv_stride=[5, 2, 2], num_feat_extract_layers=3,
        num_conv_pos_embeddings=16, num_conv_pos_embedding_groups=4,
        do_stable_layer_norm=False, feat_extract_norm="group",
        hidden_dropout=0.0, attention_dropout=0.0,
        activation_dropout=0.0, feat_proj_dropout=0.0,
        layerdrop=0.0, pad_token_id=0)
    torch.manual_seed(6)
    return Wav2Vec2ForCTC(cfg).eval()


def _transplant(hf):
    from paddle_tpu.models.wav2vec2 import (Wav2Vec2Config,
                                            Wav2Vec2ForCTC)
    ours = Wav2Vec2ForCTC(Wav2Vec2Config.tiny())
    ours.eval()
    # the parity tests compare values, not the eager path: forward runs
    # as one traced program a shape, not one XLA compile an op
    P.jit.to_static(ours)
    w_o, w_h = ours.wav2vec2, hf.wav2vec2
    for i, (oc, hc) in enumerate(zip(w_o.feature_extractor.convs,
                                     w_h.feature_extractor.conv_layers)):
        _set(oc.weight, hc.conv.weight)
        if i == 0:
            _set(w_o.feature_extractor.group_norm.weight,
                 hc.layer_norm.weight)
            _set(w_o.feature_extractor.group_norm.bias,
                 hc.layer_norm.bias)
    _set(w_o.fp_norm.weight, w_h.feature_projection.layer_norm.weight)
    _set(w_o.fp_norm.bias, w_h.feature_projection.layer_norm.bias)
    _set(w_o.fp_proj.weight, w_h.feature_projection.projection.weight.T)
    _set(w_o.fp_proj.bias, w_h.feature_projection.projection.bias)
    # materialize the torch weight-norm parametrization
    _set(w_o.pos_conv_embed.conv.weight,
         w_h.encoder.pos_conv_embed.conv.weight)
    _set(w_o.pos_conv_embed.conv.bias,
         w_h.encoder.pos_conv_embed.conv.bias)
    _set(w_o.encoder_norm.weight, w_h.encoder.layer_norm.weight)
    _set(w_o.encoder_norm.bias, w_h.encoder.layer_norm.bias)
    for ho, oo in zip(w_h.encoder.layers, w_o.layers):
        at = ho.attention
        _set(oo.q.weight, at.q_proj.weight.T)
        _set(oo.q.bias, at.q_proj.bias)
        _set(oo.k.weight, at.k_proj.weight.T)
        _set(oo.k.bias, at.k_proj.bias)
        _set(oo.v.weight, at.v_proj.weight.T)
        _set(oo.v.bias, at.v_proj.bias)
        _set(oo.o.weight, at.out_proj.weight.T)
        _set(oo.o.bias, at.out_proj.bias)
        _set(oo.layer_norm.weight, ho.layer_norm.weight)
        _set(oo.layer_norm.bias, ho.layer_norm.bias)
        _set(oo.ff_in.weight,
             ho.feed_forward.intermediate_dense.weight.T)
        _set(oo.ff_in.bias, ho.feed_forward.intermediate_dense.bias)
        _set(oo.ff_out.weight, ho.feed_forward.output_dense.weight.T)
        _set(oo.ff_out.bias, ho.feed_forward.output_dense.bias)
        _set(oo.final_layer_norm.weight, ho.final_layer_norm.weight)
        _set(oo.final_layer_norm.bias, ho.final_layer_norm.bias)
    _set(ours.lm_head.weight, hf.lm_head.weight.T)
    _set(ours.lm_head.bias, hf.lm_head.bias)
    return ours


class TestWav2Vec2Parity:
    @pytest.fixture(scope="class")
    def pair(self):
        hf = _tiny_hf()
        return hf, _transplant(hf)

    def test_ctc_logits_match_oracle(self, pair):
        hf, ours = pair
        wave = np.random.default_rng(0).standard_normal(
            (2, 800)).astype(np.float32) * 0.1
        with torch.no_grad():
            ref = hf(torch.tensor(wave)).logits.numpy()
        got = np.asarray(ours(P.to_tensor(wave))._data)
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, atol=3e-4, rtol=1e-3)

    def test_frame_length_formula(self, pair):
        hf, ours = pair
        wave = np.zeros((1, 1000), np.float32)
        got = np.asarray(ours(P.to_tensor(wave))._data)
        expect = int(ours.cfg.feat_lengths([1000])[0])
        assert got.shape[1] == expect

    def test_ctc_finetune_decreases_loss(self):
        from paddle_tpu.models.wav2vec2 import (Wav2Vec2Config,
                                                Wav2Vec2ForCTC)
        from paddle_tpu.optimizer import AdamW
        m = Wav2Vec2ForCTC(Wav2Vec2Config.tiny())
        m.train()
        opt = AdamW(learning_rate=3e-4, parameters=m.parameters())
        rng = np.random.default_rng(1)
        wave = P.to_tensor(rng.standard_normal((2, 800))
                           .astype(np.float32) * 0.1)
        labels = P.to_tensor(rng.integers(1, 32, (2, 5))
                             .astype(np.int32))
        # the subject is the family, not the eager path: the loss is one
        # traced program (`to_static`) and backward() differentiates that
        # one program — not one XLA compile an op
        loss_of = P.jit.to_static(
            lambda wave, labels: m(wave, labels=labels)[0])
        losses = []
        for _ in range(8):
            loss = loss_of(wave, labels)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.95, losses

    @staticmethod
    def _collapse(path):
        out, prev = [], -1
        for t in path:
            if t != prev and t != 0:
                out.append(int(t))
            prev = t
        return out

    def test_greedy_ctc_decode_matches_oracle(self, pair):
        """Greedy collapse (merge repeats, drop blanks) of our logits
        equals the same decode of the HF oracle's logits."""
        hf, ours = pair
        wave = np.random.default_rng(2).standard_normal(
            (1, 800)).astype(np.float32) * 0.1
        logits = np.asarray(ours(P.to_tensor(wave))._data)[0]
        with torch.no_grad():
            ref_logits = hf(torch.tensor(wave)).logits.numpy()[0]
        assert self._collapse(logits.argmax(-1)) == \
            self._collapse(ref_logits.argmax(-1))

    def test_padded_batch_input_lengths(self, pair):
        """wave_lengths is load-bearing: the CTC loss over a padded row
        equals a manual ctc_loss on only the true frames' logits.

        (Feature equality with the unpadded forward is NOT expected —
        the reference's layer-0 group norm normalizes over the whole
        time axis, so padding shifts features; base wav2vec2 upstream
        has the same property and no attention mask.)"""
        _, ours = pair
        import paddle_tpu.nn.functional as F
        rng = np.random.default_rng(3)
        short = rng.standard_normal((1, 400)).astype(np.float32) * 0.1
        labels = rng.integers(1, 32, (1, 3)).astype(np.int32)
        padded = np.concatenate(
            [short, np.zeros((1, 400), np.float32)], axis=1)
        true_frames = int(ours.cfg.feat_lengths([400])[0])
        loss_len, logits = ours(
            P.to_tensor(padded), labels=P.to_tensor(labels),
            wave_lengths=np.asarray([400]))
        manual = F.ctc_loss(
            logits.transpose([1, 0, 2]), P.to_tensor(labels),
            P.to_tensor(np.asarray([true_frames], np.int32)),
            P.to_tensor(np.asarray([3], np.int32)), blank=0)
        assert abs(float(loss_len) - float(manual)) < 1e-5
        loss_full, _ = ours(P.to_tensor(padded),
                            labels=P.to_tensor(labels))
        assert abs(float(loss_full) - float(loss_len)) > 1e-3

    def test_padded_labels_derive_lengths(self, pair):
        """pad_token_id-padded transcripts score identically to their
        unpadded form (label_lengths derives from non-pad counts — a
        full-width default would score pad slots as real symbols)."""
        _, ours = pair
        rng = np.random.default_rng(4)
        wave = P.to_tensor(rng.standard_normal((1, 800))
                           .astype(np.float32) * 0.1)
        lab = rng.integers(1, 32, (1, 3)).astype(np.int32)
        l1, _ = ours(wave, labels=P.to_tensor(lab))
        padded = np.concatenate([lab, np.zeros((1, 2), np.int32)], 1)
        l2, _ = ours(wave, labels=P.to_tensor(padded))
        assert abs(float(l1) - float(l2)) < 1e-5
