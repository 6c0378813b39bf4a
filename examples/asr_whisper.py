"""Train a tiny Whisper to transcribe synthetic tones, end to end:
wave → log-mel (audio.features) → encoder-decoder → compiled greedy
decode.

Walkthrough of the reference speech workflow (PaddleSpeech-style ASR
fine-tune) on the TPU-native stack: four pure tones map to four
"words"; after a few hundred teacher-forced steps the model transcribes
held-out tones at ~100% accuracy through `generate()` (the shared
compiled encoder-decoder decode loop, models/encdec.py).

    python examples/asr_whisper.py --cpu [--steps 120]

(--cpu selects the CPU backend; JAX_PLATFORMS=cpu does the same.)
"""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if "--cpu" in sys.argv:
    import jax
    jax.config.update("jax_platforms", "cpu")

import paddle_tpu as P  # noqa: E402
from paddle_tpu.audio.features import LogMelSpectrogram  # noqa: E402
from paddle_tpu.models import (WhisperConfig,  # noqa: E402
                               WhisperForConditionalGeneration)
from paddle_tpu.optimizer import AdamW  # noqa: E402

SR = 8000
FREQS = [300, 600, 1200, 2400]          # four "words"
START, EOS = 2, 1


def make_batch(rng, b, mel_fn):
    waves, labels = [], []
    for _ in range(b):
        k = int(rng.integers(0, 4))
        t = np.arange(SR // 4) / SR
        w = np.sin(2 * np.pi * FREQS[k] * t) * (0.5 + 0.5 * rng.random())
        w += 0.05 * rng.standard_normal(len(t))
        waves.append(w.astype(np.float32))
        labels.append(k)
    mel = mel_fn(P.to_tensor(np.stack(waves)))
    return mel, np.asarray(labels)


def main():
    steps = 120
    if "--steps" in sys.argv:
        steps = int(sys.argv[sys.argv.index("--steps") + 1])
    P.seed(0)
    rng = np.random.default_rng(0)
    mel_fn = LogMelSpectrogram(sr=SR, n_fft=256, hop_length=128,
                               n_mels=16)
    mel, _ = make_batch(rng, 1, mel_fn)
    t_frames = int(mel.shape[2])
    cfg = WhisperConfig.tiny(
        vocab_size=16, max_source_positions=(t_frames + 1) // 2,
        max_target_positions=8, decoder_start_token_id=START,
        eos_token_id=EOS)
    model = WhisperForConditionalGeneration(cfg)
    model.train()
    opt = AdamW(learning_rate=2e-3, parameters=model.parameters())
    b = 8
    for step in range(steps):
        mel, lab = make_batch(rng, b, mel_fn)
        dec_in = np.stack([np.full(b, START), lab + 4], 1).astype(
            np.int32)
        target = np.stack([lab + 4, np.full(b, EOS)], 1).astype(
            np.int32)
        loss, _ = model(mel, P.to_tensor(dec_in),
                        labels=P.to_tensor(target))
        loss.backward()
        opt.step()
        opt.clear_grad()
        if step % 30 == 0 or step == steps - 1:
            print(f"step {step:3d}  loss {float(loss):.4f}")
    model.eval()
    mel, lab = make_batch(rng, 16, mel_fn)
    out = np.asarray(model.generate(mel, max_new_tokens=2)._data)
    acc = float((out[:, 0] == lab + 4).mean())
    eos = float((out[:, 1] == EOS).mean())
    print(f"held-out transcription accuracy {acc:.2f}  "
          f"eos rate {eos:.2f}")
    print(f"ASR training OK (acc {acc:.2f})")
    return acc


if __name__ == "__main__":
    main()
