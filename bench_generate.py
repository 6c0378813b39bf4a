"""Decode-throughput bench: LLaMA proxy autoregressive generation with
the static-KV-cache jitted decode loop (models/generation.py).

Usage: python bench_generate.py [batch] [prompt_len] [new_tokens] [--wq int8|int4] [--kv int8] [--spec K]
`--wq` swaps every linear (except lm_head) to weight-only quantized
storage before compiling the decode program — decode is HBM-bound, so
int8/int4 weights target ~2x/4x the streamed bytes.
Prints one JSON line {metric, value (decode tokens/sec), ...}.
Needs a TPU (non-zero exit without one).
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

wq = None
if "--wq" in sys.argv:
    i = sys.argv.index("--wq")
    wq = sys.argv[i + 1]
    del sys.argv[i:i + 2]
kv = None
if "--kv" in sys.argv:
    i = sys.argv.index("--kv")
    kv = sys.argv[i + 1]
    del sys.argv[i:i + 2]
spec_k = 0
if "--spec" in sys.argv:
    i = sys.argv.index("--spec")
    spec_k = int(sys.argv[i + 1])
    del sys.argv[i:i + 2]
batch = int(sys.argv[1]) if len(sys.argv) > 1 else 8
prompt = int(sys.argv[2]) if len(sys.argv) > 2 else 128
new = int(sys.argv[3]) if len(sys.argv) > 3 else 128


def main():
    from bench import jax_device_record, require_tpu
    require_tpu()  # no TPU: non-zero exit, no metric
    import paddle_tpu as P
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    # speculative mode needs k+1 extra cache/position slots — size the
    # config up front (a post-hoc mutation would defeat the maxpos
    # guard for families with build-time position tables)
    maxpos = prompt + new + (spec_k + 1 if spec_k else 0)
    cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                      intermediate_size=5504, num_hidden_layers=8,
                      num_attention_heads=16,
                      max_position_embeddings=maxpos,
                      dtype="bfloat16")
    P.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()
    if wq:
        from paddle_tpu.nn.quant import convert_to_weight_only
        convert_to_weight_only(model, algo=f"weight_only_{wq}",
                               exclude=("lm_head",))
    draft = None
    if spec_k:
        # layer-skip self-speculation: the draft is the target truncated
        # to its first quarter of layers (shared embedding/head weights
        # copied) — a realistic acceptance-rate proxy, unlike an
        # uncorrelated random draft
        dcfg_kw = dict(vocab_size=cfg.vocab_size,
                       hidden_size=cfg.hidden_size,
                       intermediate_size=cfg.intermediate_size,
                       num_hidden_layers=max(1, cfg.num_hidden_layers // 4),
                       num_attention_heads=cfg.num_attention_heads,
                       max_position_embeddings=maxpos,
                       dtype=cfg.dtype)
        draft = LlamaForCausalLM(LlamaConfig(**dcfg_kw))
        sd = model.state_dict()
        dsd = draft.state_dict()
        for name in dsd:
            if name in sd and tuple(sd[name].shape) == \
                    tuple(dsd[name].shape):
                dsd[name].set_value(sd[name])
        draft.to(dtype="bfloat16")
        draft.eval()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, prompt)).astype(np.int32)
    x = P.to_tensor(ids)

    # Two-point measurement: generate() is prefill + a decode scan in
    # ONE program, so timing the SAME cache layout at two trip counts
    # and taking the marginal rate (extra tokens / extra wall) isolates
    # the decode step from prefill and dispatch. Each timed region ends
    # in a host fetch of a value derived from the output.
    new_q = max(1, new // 4)
    gen_kw = dict(cache_dtype=kv)
    if draft is not None:
        gen_kw.update(draft_model=draft, speculative_k=spec_k)
    for warm_n in (new, new_q):   # compile both trip counts
        out = model.generate(x, max_new_tokens=warm_n, **gen_kw)
        out._data.block_until_ready()

    def timed(n):
        best = float("inf")   # min over 2 samples
        for _ in range(2):
            ids2 = rng.integers(0, cfg.vocab_size,
                                (batch, prompt)).astype(np.int32)
            x2 = P.to_tensor(ids2)
            t0 = time.perf_counter()
            out = model.generate(x2, max_new_tokens=n, **gen_kw)
            int(np.asarray(out._data).sum())   # dependent fetch
            best = min(best, time.perf_counter() - t0)
        return best

    dt_q = timed(new_q)
    dt = timed(new)
    marginal = None
    if dt > dt_q and new > new_q:
        marginal = batch * (new - new_q) / (dt - dt_q)
        # fixed overhead = quarter-run wall minus its device share,
        # scaled from the marginal per-step time
        step_s = (dt - dt_q) / (new - new_q)
        overhead = max(0.0, min(dt_q, dt_q - step_s * new_q))
    tok_s = batch * new / dt
    rate_kind = "marginal decode rate" if marginal else \
        "end-to-end (marginal unavailable: noise inverted the " \
        "two-point; includes prefill)"
    print(json.dumps({
        "metric": "llama_decode_tok_per_s",
        "device": jax_device_record(),
        "value": round(marginal, 1) if marginal else round(tok_s, 1),
        "unit": f"decode tokens/sec (batch total, {rate_kind}; "
                "static-cache jitted loop)",
        "batch": batch, "prompt": prompt, "new_tokens": new,
        "weight_quant": wq or "none",
        "kv_cache": kv or "bf16",
        "speculative_k": spec_k,
        "e2e_tok_per_s": round(tok_s, 1),
        "wall_s": round(dt, 3), "wall_quarter_s": round(dt_q, 3),
        "fixed_overhead_s_est":
            round(overhead, 3) if marginal else None,
        # verify-round accounting → measured acceptance (spec mode):
        # prefill yields token 1; R rounds yield the other new−1 at ≤k+1
        # each ⇒ mean accepted per round = (new−1)/R − 1 of k proposed
        # (generation.py: rounds == ceil((new−1)/(k+1)) at acceptance 1)
        "spec_rounds": getattr(model, "_last_spec_rounds", None)
            if spec_k else None,
        "spec_acceptance": (round(
            ((new - 1) / model._last_spec_rounds - 1) / spec_k, 3)
            if spec_k and getattr(model, "_last_spec_rounds", None)
            else None),
    }))


if __name__ == "__main__":
    main()
