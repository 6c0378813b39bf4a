"""Milestone-config benches beyond the headline bench.py (BASELINE.md
"Milestone configs"): config 1 — ResNet-50/CIFAR-10 via the Model fit
path — and config 2 — BERT-base dynamic-graph fine-tune with AMP-O2 on
a single TPU chip. Records throughput rows to BENCH_extra.json and
captures a jax.profiler trace artifact (--trace).

Usage: python bench_extra.py [--trace]
Needs a TPU (non-zero exit without one).
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from bench import detect_peak, jax_device_record, require_tpu


def _timed_device_loop(m, inputs, labels):
    """The timing harness, in ONE place: compile + warm via a first
    loop run, DRAIN it with a dependent fetch, then time exactly one
    device-loop dispatch whose timed region ends in a dependent fetch
    of the last step's loss. Returns (last_loss, seconds)."""
    warm = m.train_batch_loop(inputs, labels)
    float(np.asarray(warm._data)[-1])
    t0 = time.perf_counter()
    losses = m.train_batch_loop(inputs, labels)
    loss = float(np.asarray(losses._data)[-1])
    return loss, time.perf_counter() - t0


def bert_amp_o2(trace: bool = False):
    import jax

    import paddle_tpu as P
    from paddle_tpu.models import BertConfig, BertForSequenceClassification

    cfg = BertConfig()  # BERT-base defaults
    batch, seq, iters = 32, 128, 20

    P.seed(0)
    model = BertForSequenceClassification(cfg)
    opt = P.optimizer.AdamW(2e-5, parameters=model.parameters(),
                            multi_precision=True)
    crit = P.nn.CrossEntropyLoss()
    m = P.Model(model)
    m.prepare(opt, crit, amp_configs="O2")

    rng = np.random.default_rng(0)
    ids = P.to_tensor(rng.integers(0, cfg.vocab_size,
                                   (batch, seq)).astype(np.int32))
    labels = P.to_tensor(rng.integers(0, 2, (batch,)).astype(np.int64))

    if trace:
        # the per-step program is only used for the trace capture —
        # compile it only on that path
        m.train_batch([ids], [labels])
        m.train_batch([ids], [labels])
        jax.effects_barrier()
        import os
        os.makedirs("traces", exist_ok=True)
        with jax.profiler.trace("traces/bert_amp_o2"):
            for _ in range(3):
                m.train_batch([ids], [labels])
            jax.effects_barrier()

    # DEVICE LOOP: one lax.scan program over all iters = one dispatch
    # + one dependent fetch, same as bench.py (a per-step train_batch
    # loop pays a host round-trip per step, which at BERT's small step
    # time dominates the wall).
    ids_l = P.to_tensor(np.broadcast_to(
        np.asarray(ids._data)[None], (iters,) + tuple(ids.shape)).copy())
    lab_l = P.to_tensor(np.broadcast_to(
        np.asarray(labels._data)[None],
        (iters,) + tuple(labels.shape)).copy())
    loss, dt = _timed_device_loop(m, [ids_l], [lab_l])

    tok_s = batch * seq * iters / dt
    # 6N FLOPs/token proxy (fine-tune fwd+bwd)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    mfu = tok_s * 6 * n_params / detect_peak()[0]
    return {
        "metric": "bert_base_amp_o2_finetune",
        "device": jax_device_record(),
        "value": round(tok_s, 1),
        "unit": "tokens/sec (fwd+bwd+opt, AMP-O2)",
        "mfu_6N_proxy": round(mfu, 4),
        "batch": batch, "seq": seq,
        "loss": loss,
    }


def resnet50_cifar_fit():
    """BASELINE config 1: ResNet-50 on CIFAR-10 via Model.fit-style
    training (synthetic CIFAR-shaped data, device-loop timed region —
    one dispatch + one dependent fetch)."""
    import paddle_tpu as P
    from paddle_tpu.vision import models as M

    batch, steps = 64, 20
    P.seed(0)
    model = M.resnet50(num_classes=10)
    opt = P.optimizer.Momentum(0.01, momentum=0.9,
                               parameters=model.parameters())
    crit = P.nn.CrossEntropyLoss()
    m = P.Model(model)
    m.prepare(opt, crit)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((steps, batch, 3, 32, 32)).astype(np.float32)
    y = rng.integers(0, 10, (steps, batch)).astype(np.int64)
    xl, yl = P.to_tensor(x), P.to_tensor(y)
    loss, dt = _timed_device_loop(m, [xl], [yl])
    img_s = batch * steps / dt
    return {
        "metric": "resnet50_cifar10_fit",
        "device": jax_device_record(),
        "value": round(img_s, 1),
        "unit": "images/sec (fwd+bwd+momentum, Model device loop)",
        "batch": batch, "steps": steps, "loss": loss,
    }


def main():
    trace = "--trace" in sys.argv
    require_tpu()  # no TPU: non-zero exit, no metric
    rec = bert_amp_o2(trace=trace)
    print(json.dumps(rec))
    rec2 = resnet50_cifar_fit()
    print(json.dumps(rec2))
    with open("BENCH_extra.json", "w") as f:
        json.dump({"bert_amp_o2": rec, "resnet50_cifar10": rec2}, f,
                  indent=1)


if __name__ == "__main__":
    main()
