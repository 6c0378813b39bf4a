"""Whisper family parity vs the `transformers` torch oracle (weight
transplant — same strategy as tests/test_models_vit_t5.py)."""
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
    from transformers import WhisperConfig as HFConfig, WhisperModel
    cfg = HFConfig(
        vocab_size=128, num_mel_bins=16, d_model=64, encoder_layers=2,
        decoder_layers=2, encoder_attention_heads=4,
        decoder_attention_heads=4, encoder_ffn_dim=128,
        decoder_ffn_dim=128, max_source_positions=15,
        max_target_positions=32, dropout=0.0, pad_token_id=0,
        eos_token_id=1, decoder_start_token_id=2, bos_token_id=3)
    torch.manual_seed(2)
    return WhisperModel(cfg).eval()


def _copy_attn(oat, hat):
    _set(oat.q.weight, hat.q_proj.weight.T)
    _set(oat.q.bias, hat.q_proj.bias)
    _set(oat.k.weight, hat.k_proj.weight.T)
    _set(oat.v.weight, hat.v_proj.weight.T)
    _set(oat.v.bias, hat.v_proj.bias)
    _set(oat.o.weight, hat.out_proj.weight.T)
    _set(oat.o.bias, hat.out_proj.bias)


def _transplant(hf):
    from paddle_tpu.models.whisper import (WhisperConfig,
                                           WhisperForConditionalGeneration)
    ours = WhisperForConditionalGeneration(
        WhisperConfig.tiny(max_source_positions=15))
    ours.eval()
    enc_o, enc_h = ours.model.encoder, hf.encoder
    _set(enc_o.conv1.weight, enc_h.conv1.weight)
    _set(enc_o.conv1.bias, enc_h.conv1.bias)
    _set(enc_o.conv2.weight, enc_h.conv2.weight)
    _set(enc_o.conv2.bias, enc_h.conv2.bias)
    enc_o.embed_positions.set_value(_t(enc_h.embed_positions.weight))
    for ho, oo in zip(enc_h.layers, enc_o.layers):
        _copy_attn(oo.self_attn, ho.self_attn)
        _set(oo.self_norm.weight, ho.self_attn_layer_norm.weight)
        _set(oo.self_norm.bias, ho.self_attn_layer_norm.bias)
        _set(oo.fc1.weight, ho.fc1.weight.T)
        _set(oo.fc1.bias, ho.fc1.bias)
        _set(oo.fc2.weight, ho.fc2.weight.T)
        _set(oo.fc2.bias, ho.fc2.bias)
        _set(oo.ff_norm.weight, ho.final_layer_norm.weight)
        _set(oo.ff_norm.bias, ho.final_layer_norm.bias)
    _set(enc_o.layer_norm.weight, enc_h.layer_norm.weight)
    _set(enc_o.layer_norm.bias, enc_h.layer_norm.bias)

    dec_o, dec_h = ours.model.decoder, hf.decoder
    _set(dec_o.embed_tokens.weight, dec_h.embed_tokens.weight)
    dec_o.embed_positions.set_value(_t(dec_h.embed_positions.weight))
    for ho, oo in zip(dec_h.layers, dec_o.layers):
        _copy_attn(oo.self_attn, ho.self_attn)
        _set(oo.self_norm.weight, ho.self_attn_layer_norm.weight)
        _set(oo.self_norm.bias, ho.self_attn_layer_norm.bias)
        _copy_attn(oo.cross_attn, ho.encoder_attn)
        _set(oo.cross_norm.weight, ho.encoder_attn_layer_norm.weight)
        _set(oo.cross_norm.bias, ho.encoder_attn_layer_norm.bias)
        _set(oo._fc1.weight, ho.fc1.weight.T)
        _set(oo._fc1.bias, ho.fc1.bias)
        _set(oo._fc2.weight, ho.fc2.weight.T)
        _set(oo._fc2.bias, ho.fc2.bias)
        _set(oo.ff_norm.weight, ho.final_layer_norm.weight)
        _set(oo.ff_norm.bias, ho.final_layer_norm.bias)
    _set(dec_o.layer_norm.weight, dec_h.layer_norm.weight)
    _set(dec_o.layer_norm.bias, dec_h.layer_norm.bias)
    return ours


class TestWhisperParity:
    @pytest.fixture(scope="class")
    def pair(self):
        hf = _tiny_hf()
        return hf, _transplant(hf)

    def test_encoder_matches_oracle(self, pair):
        hf, ours = pair
        mel = np.random.default_rng(0).standard_normal(
            (2, 16, 30)).astype(np.float32)
        with torch.no_grad():
            ref = hf.encoder(torch.tensor(mel)).last_hidden_state.numpy()
        got = np.asarray(ours.model.encoder(P.to_tensor(mel))._data)
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, atol=2e-4, rtol=1e-3)

    def test_teacher_forced_logits_match_oracle(self, pair):
        hf, ours = pair
        rng = np.random.default_rng(1)
        mel = rng.standard_normal((2, 16, 30)).astype(np.float32)
        dec = rng.integers(4, 128, (2, 7)).astype(np.int64)
        with torch.no_grad():
            h = hf(input_features=torch.tensor(mel),
                   decoder_input_ids=torch.tensor(dec)).last_hidden_state
            ref = (h @ hf.decoder.embed_tokens.weight.T).numpy()
        got = np.asarray(ours(P.to_tensor(mel),
                              P.to_tensor(dec.astype(np.int32)))._data)
        assert got.shape == ref.shape
        np.testing.assert_allclose(got, ref, atol=3e-4, rtol=1e-3)

    def test_greedy_generate_matches_manual_oracle(self, pair):
        hf, ours = pair
        rng = np.random.default_rng(2)
        mel = rng.standard_normal((2, 16, 30)).astype(np.float32)
        max_new = 8
        # manual torch greedy rollout (teacher-forced re-forward each
        # step) — avoids HF's transcription-specific generate() logic
        ids = torch.full((2, 1), 2, dtype=torch.long)  # decoder_start
        with torch.no_grad():
            for _ in range(max_new):
                h = hf(input_features=torch.tensor(mel),
                       decoder_input_ids=ids).last_hidden_state
                lg = h[:, -1] @ hf.decoder.embed_tokens.weight.T
                ids = torch.cat([ids, lg.argmax(-1, keepdim=True)], 1)
        ref = ids[:, 1:].numpy()
        got = np.asarray(ours.generate(P.to_tensor(mel),
                                       max_new_tokens=max_new)._data)
        eos = 1
        for b in range(2):
            for i in range(max_new):
                assert got[b, i] == ref[b, i], (b, i, ref[b], got[b])
                if ref[b, i] == eos:
                    break

    def test_trains_and_mel_frontend_integrates(self, pair):
        _, ours = pair
        from paddle_tpu.optimizer import AdamW
        ours.train()
        opt = AdamW(learning_rate=3e-3, parameters=ours.parameters())
        rng = np.random.default_rng(3)
        mel = P.to_tensor(rng.standard_normal((2, 16, 30))
                          .astype(np.float32))
        dec = P.to_tensor(rng.integers(4, 128, (2, 6)).astype(np.int32))
        # the subject is the family, not the eager path: the loss is one
        # traced program (`to_static`) and backward() differentiates that
        # one program — not one XLA compile an op
        loss_of = P.jit.to_static(
            lambda mel, dec: ours(mel, dec, labels=dec)[0])
        losses = []
        for _ in range(6):
            loss = loss_of(mel, dec)
            loss.backward()
            opt.step()
            opt.clear_grad()
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.9, losses
        # frozen sinusoidal positions stay frozen
        assert ours.model.encoder.embed_positions.stop_gradient
        ours.eval()

    def test_audio_features_to_model(self):
        """audio.features log-mel → Whisper encoder shape contract."""
        from paddle_tpu.audio.features import LogMelSpectrogram
        from paddle_tpu.models.whisper import (
            WhisperConfig, WhisperForConditionalGeneration)
        sr, n_mels = 16000, 16
        wav = P.to_tensor(np.sin(
            2 * np.pi * 440 * np.arange(sr // 10) / sr)
            .astype(np.float32)[None])
        mel = LogMelSpectrogram(sr=sr, n_fft=400, hop_length=160,
                                n_mels=n_mels)(wav)  # [B, n_mels, T]
        t = int(mel.shape[2])
        m = WhisperForConditionalGeneration(WhisperConfig.tiny(
            max_source_positions=(t + 1) // 2 + 1))
        m.eval()
        enc = m.model.encoder(mel)
        assert enc.shape[0] == 1 and enc.shape[2] == 64
        out = m.generate(mel, max_new_tokens=4)
        assert np.asarray(out._data).shape == (1, 4)
