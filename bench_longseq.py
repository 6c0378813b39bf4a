"""Long-sequence single-chip bench: LLaMA proxy (h2048 L8) at s=8192
with recompute + fused linear-cross-entropy.

Usage: python bench_longseq.py [batch] [seq] [recompute] [fuse_ce]
Prints one JSON line; needs a TPU (non-zero exit without one).
"""
import sys, time, json
import numpy as np

batch = int(sys.argv[1]) if len(sys.argv) > 1 else 1
seq = int(sys.argv[2]) if len(sys.argv) > 2 else 8192
recompute = (sys.argv[3] != "0") if len(sys.argv) > 3 else True
fuse = (sys.argv[4] != "0") if len(sys.argv) > 4 else True

from bench import detect_peak, jax_device_record, require_tpu  # noqa: E402

require_tpu()  # no TPU: non-zero exit, no metric
import jax  # noqa: E402
import paddle_tpu as P  # noqa: E402
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,  # noqa: E402
                               LlamaPretrainingCriterion, flops_per_token)

# remat-policy knob (VERDICT r3 item 2): PADDLE_TPU_RECOMPUTE_GRAN =
# full (default) | full_attn (save flash outputs, skip their recompute)
import os  # noqa: E402
gran = os.environ.get("PADDLE_TPU_RECOMPUTE_GRAN", "full")
cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                  intermediate_size=5504, num_hidden_layers=8,
                  num_attention_heads=16,
                  max_position_embeddings=seq, recompute=recompute,
                  recompute_granularity=gran,
                  fuse_linear_cross_entropy=fuse, dtype="bfloat16")
P.seed(0)
model = LlamaForCausalLM(cfg)
model.to(dtype="bfloat16")
crit = LlamaPretrainingCriterion(cfg)
if fuse:
    crit.bind(model)
opt = P.optimizer.AdamW(1e-4, parameters=model.parameters(), multi_precision=True)
m = P.Model(model); m.prepare(opt, crit)
ids = np.random.default_rng(0).integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
x = P.to_tensor(ids)
m.train_batch([x], [x]); m.train_batch([x], [x]); jax.effects_barrier()
iters = 8
# timed region ends in a dependent host fetch of the LAST step's loss,
# which depends on the whole chain of steps
t0 = time.perf_counter()
for _ in range(iters):
    loss = m.train_batch([x], [x])
loss_val = float(np.asarray(loss._data if hasattr(loss, "_data") else loss))
dt = time.perf_counter() - t0
tok_s = batch * seq * iters / dt
mfu = tok_s * flops_per_token(cfg, seq) / detect_peak()[0]
print(json.dumps({"batch": batch, "seq": seq, "recompute": recompute,
                  "recompute_gran": gran, "device": jax_device_record(),
                  "fuse_ce": fuse, "tok_s": round(tok_s, 1),
                  "mfu": round(mfu, 4), "loss": loss_val}))
