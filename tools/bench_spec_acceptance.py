"""Real-draft speculative acceptance curve (round 4, VERDICT r3 item 6;
round 5: KL-DISTILLED draft, VERDICT r4 missing #6 / next-round task 4).

Round 4 measured the honest curve with a CE-trained 1-layer draft:
acceptance 0.28/0.23/0.12/0.06 at k=1/2/4/8, best speedup 1.12x — the
draft was the bottleneck, not the mechanism. Round 5 distills the draft
the way a serving stack would:

- target: byte-level LLaMA (4 layers) trained on local text (the repo's
  docs, same recipe as tools/eval_kv8_quality.py), longer schedule;
- draft: 1-layer model DISTILLED on the target's logits (full-softmax
  KL at T=1, >=2k steps) — argmax agreement is what greedy speculative
  acceptance pays for, and KL on soft targets is the standard recipe;
- diagnostics: teacher-forced held-out argmax agreement (the acceptance
  upper bound), then for k in {1, 2, 4, 8}: greedy generate with/
  without the draft, verify rounds → measured acceptance, marginal
  decode rate (two-point measurement, prefill cancelled) → measured
  speedup; plus a batch>1 row at the best k.

Acceptance rates and round counts are the product here; the wall
ratios it prints on a CPU run are not device speedups.

Run: python tools/bench_spec_acceptance.py [--steps 1500]
     [--distill-steps 2500]
Writes BENCH_spec_acceptance.json at the repo root.
"""
import argparse
import json
import os
import sys
import time

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import paddle_tpu as P  # noqa: E402
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM  # noqa: E402
from tools.eval_kv8_quality import corpus, train  # noqa: E402

PROMPT = 64
NEW = 256


def build(layers, seed, maxpos, hidden=256, inter=688):
    cfg = LlamaConfig(vocab_size=256, hidden_size=hidden,
                      intermediate_size=inter, num_hidden_layers=layers,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=maxpos, dtype="float32")
    P.seed(seed)
    return LlamaForCausalLM(cfg)


def distill(draft, target, arr, steps, lr=3e-3):
    """KL(teacher || student) on the target's full softmax (T=1): the
    greedy-acceptance objective is argmax agreement, and matching the
    whole distribution where the teacher is confident is what buys it."""
    from tools.eval_kv8_quality import SEQ, batches
    import paddle_tpu.nn.functional as F
    target.eval()
    opt = P.optimizer.AdamW(lr, parameters=draft.parameters())
    rng = np.random.default_rng(3)
    kl = None
    t0 = time.time()
    for i, chunk in enumerate(batches(arr, rng, steps)):
        ids = P.to_tensor(chunk[:, :-1])
        with P.no_grad():
            t_logits = target(ids)
        t_logp = F.log_softmax(t_logits.detach(), axis=-1)
        s_logp = F.log_softmax(draft(ids), axis=-1)
        kl = (t_logp.exp() * (t_logp - s_logp)).sum(-1).mean()
        kl.backward()
        opt.step()
        opt.clear_grad()
        if i % 100 == 0:
            print(f"distill step {i}: KL {float(kl.numpy()):.4f} "
                  f"({time.time() - t0:.0f}s)", flush=True)
    return float(kl.numpy()) if kl is not None else float("nan")


def argmax_agreement(draft, target, held, n_seq=24, seq=192):
    """Teacher-forced held-out argmax agreement — the ceiling on greedy
    speculative acceptance."""
    rng = np.random.default_rng(7)
    agree = total = 0
    for _ in range(n_seq):
        s = int(rng.integers(0, len(held) - seq))
        ids = P.to_tensor(held[s:s + seq][None].astype(np.int32))
        ta = np.argmax(np.asarray(target(ids)._data), -1)
        da = np.argmax(np.asarray(draft(ids)._data), -1)
        agree += int((ta == da).sum())
        total += ta.size
    return agree / total


def marginal_rate(model, prompts, gen_kw, new=NEW):
    """Two-point marginal decode rate (PERF.md protocol): extra tokens /
    extra wall between a full and a quarter run, min of 2 samples."""
    new_q = max(1, new // 4)
    for warm_n in (new, new_q):
        out = model.generate(P.to_tensor(prompts[0]),
                             max_new_tokens=warm_n, **gen_kw)
        out._data.block_until_ready()

    def timed(n, ids):
        best = float("inf")
        for k in range(2):
            x = P.to_tensor(ids[k])
            t0 = time.perf_counter()
            out = model.generate(x, max_new_tokens=n, **gen_kw)
            int(np.asarray(out._data).sum())
            best = min(best, time.perf_counter() - t0)
        return best

    dt_q = timed(new_q, prompts[1:3])
    dt = timed(new, prompts[3:5])
    if dt <= dt_q:
        return None, dt
    return prompts[0].shape[0] * (new - new_q) / (dt - dt_q), dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=1500)
    ap.add_argument("--distill-steps", type=int, default=2500)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--batch2", type=int, default=4,
                    help="second batch size measured at the best k")
    ap.add_argument("--draft-hidden", type=int, default=128,
                    help="draft width: the round-5 1.01x lesson is that "
                    "a same-width 1-layer draft costs too much per "
                    "round on the CPU marginal — the draft must be "
                    "CHEAP, not just shallow")
    ap.add_argument("--draft-inter", type=int, default=344)
    ap.add_argument("--target-hidden", type=int, default=256,
                    help="target width (multiple of 4 heads): the CPU "
                    "marginal is overhead-bound at h256 (per-call "
                    "fixed cost ~0.8 of a step); a wider target makes "
                    "draft/target cost ratios meaningful, the regime "
                    "real serving runs")
    ap.add_argument("--target-inter", type=int, default=None,
                    help="default: hidden * 2.6875 (the 256/688 ratio)")
    ap.add_argument("--target-layers", type=int, default=4)
    args = ap.parse_args()
    if args.target_hidden % 4:
        ap.error("--target-hidden must be divisible by the 4 heads")
    if args.target_inter is None:
        args.target_inter = round(args.target_hidden * 2.6875)

    train_arr, held = corpus()
    maxpos = PROMPT + NEW + 16
    target = build(args.target_layers, 0, maxpos,
                   hidden=args.target_hidden, inter=args.target_inter)
    print(f"training target ({args.target_layers} layers, hidden "
          f"{args.target_hidden}, {args.steps} steps)...", flush=True)
    train(target, train_arr, args.steps)
    target.eval()
    draft = build(1, 1, maxpos, hidden=args.draft_hidden,
                  inter=args.draft_inter)
    print(f"distilling draft (1 layer, hidden {args.draft_hidden}, "
          f"{args.distill_steps} KL steps)...", flush=True)
    final_kl = distill(draft, target, train_arr, args.distill_steps)
    draft.eval()
    agree = argmax_agreement(draft, target, held)
    print(f"held-out argmax agreement {agree:.3f} (final KL "
          f"{final_kl:.4f})", flush=True)

    # prompts drawn from held-out text (the distribution that matters)
    rng = np.random.default_rng(2)
    prompts = []
    for _ in range(8):
        starts = rng.integers(0, len(held) - PROMPT, args.batch)
        prompts.append(np.stack([held[s:s + PROMPT] for s in starts])
                       .astype(np.int32))

    base_rate, base_wall = marginal_rate(target, prompts, {})
    print(f"vanilla greedy: marginal {base_rate and round(base_rate, 1)} "
          f"tok/s wall {base_wall:.2f}s", flush=True)

    rows = []
    for k in (1, 2, 4, 8):
        kw = dict(draft_model=draft, speculative_k=k)
        rate, wall = marginal_rate(target, prompts, kw)
        rounds = target._last_spec_rounds
        # prefill yields token 1; R rounds yield the other NEW−1 tokens
        acc = ((NEW - 1) / rounds - 1) / k if rounds else None
        speedup = rate / base_rate if rate and base_rate else None
        row = {"k": k, "rounds": rounds, "acceptance": acc,
               "marginal_tok_s": rate and round(rate, 1),
               "wall_s": round(wall, 2),
               "speedup_vs_greedy": speedup and round(speedup, 2)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    # batch>1 at the best k (serving batches amortize the verify pass)
    batch2_row = None
    best = max(rows, key=lambda r: r["speedup_vs_greedy"] or 0)
    if args.batch2 > args.batch and best["speedup_vs_greedy"]:
        prompts2 = []
        for _ in range(8):
            starts = rng.integers(0, len(held) - PROMPT, args.batch2)
            prompts2.append(
                np.stack([held[s:s + PROMPT] for s in starts])
                .astype(np.int32))
        b2_base, _ = marginal_rate(target, prompts2, {})
        b2_rate, _ = marginal_rate(
            target, prompts2,
            dict(draft_model=draft, speculative_k=best["k"]))
        if b2_base and b2_rate:
            batch2_row = {"batch": args.batch2, "k": best["k"],
                          "marginal_tok_s": round(b2_rate, 1),
                          "greedy_marginal_tok_s": round(b2_base, 1),
                          "speedup_vs_greedy":
                              round(b2_rate / b2_base, 2)}
            print(json.dumps(batch2_row), flush=True)

    out = {"metric": "speculative_acceptance_curve",
           "target_layers": args.target_layers,
           "target_hidden": args.target_hidden,
           "draft_layers": 1,
           "draft_hidden": args.draft_hidden,
           "train_steps": args.steps,
           "distill_steps": args.distill_steps,
           "distill": "KL on target logits (T=1)",
           "heldout_argmax_agreement": round(agree, 4),
           "batch": args.batch,
           "prompt": PROMPT, "new_tokens": NEW,
           "backend": jax.default_backend(),
           "greedy_marginal_tok_s": base_rate and round(base_rate, 1),
           "rows": rows, "batch2": batch2_row}
    with open(os.path.join(REPO, "BENCH_spec_acceptance.json"), "w") as f:
        json.dump(out, f, indent=1)
    print("written BENCH_spec_acceptance.json")


if __name__ == "__main__":
    main()
