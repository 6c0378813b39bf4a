"""Autoregressive generation with the static-KV-cache jitted decode loop.

python examples/generate_llama.py [--tiny]
(real checkpoints load via paddle.load / model.set_state_dict)
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # repo checkout; unnecessary if installed

if "--cpu" in sys.argv:  # run on the CPU backend (e.g. no chip attached)
    sys.argv.remove("--cpu")
    import jax
    jax.config.update("jax_platforms", "cpu")


import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--max-new-tokens", type=int, default=16)
    args = ap.parse_args()

    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=128) if args.tiny else \
        LlamaConfig()
    model = LlamaForCausalLM(cfg)
    model.eval()

    prompt = paddle.to_tensor(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (1, 8)).astype(np.int32))
    greedy = model.generate(prompt, max_new_tokens=args.max_new_tokens)
    sampled = model.generate(prompt, max_new_tokens=args.max_new_tokens,
                             do_sample=True, temperature=0.8, top_p=0.9,
                             seed=7)
    print("greedy :", np.asarray(greedy._data)[0].tolist())
    print("sampled:", np.asarray(sampled._data)[0].tolist())

    # speculative decoding: a shallow draft proposes, the target
    # verifies — greedy output is token-exact vs the vanilla loop
    # (rollback is free on the static absolute-position cache)
    import dataclasses
    paddle.seed(1)
    draft_cfg = dataclasses.replace(
        cfg, num_hidden_layers=max(1, cfg.num_hidden_layers // 2))
    draft = LlamaForCausalLM(draft_cfg)
    draft.eval()
    spec = model.generate(prompt, max_new_tokens=args.max_new_tokens,
                          draft_model=draft, speculative_k=4)
    print("spec   :", np.asarray(spec._data)[0].tolist(),
          f"(== greedy: {bool((spec._data == greedy._data).all())}, "
          f"{model._last_spec_rounds} verify rounds)")


if __name__ == "__main__":
    main()
