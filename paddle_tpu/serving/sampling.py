"""On-device fused sampling for the serving decode hot path.

Reference capability: the fused sampler every vLLM-class TPU serving
stack runs inside the decode program (PAPERS.md Gemma-on-TPU serving
comparison: the per-step host round-trip of [B, V] logits is the decode
latency killer on TPU). Moving sampling on-device shrinks the per-step
host fetch from ``B * V * 4`` bytes of logits to ``B`` int32 token ids
plus ``B`` float32 logprobs (<= B*8 bytes) while keeping the step at
one dispatch + one fetch.

Design constraints (reproducibility rules):

- Everything here is pure jnp — it traces inside the engine's bucketed
  step program; per-request ``(seed, step)`` ride as int32 ARGUMENTS,
  so no RNG state is baked into the compiled program and the jit cache
  stays bounded (no per-seed recompiles).
- The RNG is counter-based: lane i draws from
  ``fold_in(PRNGKey(seed_i), step_i)`` where ``step`` is the REQUEST's
  token index (len(out_tokens) at sampling time), not the engine step.
  Token t of a request is therefore a pure function of
  ``(weights, history, seed, t)`` — preemption + recompute replays the
  identical stream, and forked children (distinct seeds) diverge
  deterministically.
- Categorical sampling is Gumbel-max over the filtered/temperature-
  scaled logits: one argmax, no normalization, no [B, V] division —
  and a greedy lane is literally the same argmax without noise, which
  is what makes greedy device-vs-host parity token-exact.
- ``sample_capable=False`` (a STATIC python flag at the engine's jit
  boundary) compiles the greedy-only variant with no sort in it, so
  an all-greedy decode batch — the common serving case — never pays
  the top-k/top-p sort. The trace cache at most doubles (still
  bounded by 2 * (log2(max_batch) + 2)).

Filter semantics match the host oracle (`engine._sample`, numpy):
``top_k <= 0`` or ``>= V`` disables top-k; ``top_p <= 0`` or ``>= 1``
disables top-p; both thresholds KEEP ties; top-p is applied after
top-k on the already-filtered distribution and always keeps at least
the most probable token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["fused_sample", "fused_sample_multi"]


def _lane_keys(seeds, steps):
    """Counter-based per-lane keys: fold the request's token index into
    a key derived from its seed. Both are traced int32 arguments."""
    def one(seed, step):
        return jax.random.fold_in(jax.random.PRNGKey(seed), step)
    return jax.vmap(one)(seeds, steps)


def _filter_top_k(scaled, top_k):
    """Per-lane top-k mask (k<=0 disables; ties kept)."""
    b, v = scaled.shape
    srt = jnp.sort(scaled, axis=-1)[:, ::-1]                 # descending
    k = jnp.clip(top_k, 1, v)
    kth = jnp.take_along_axis(srt, (k - 1)[:, None], axis=-1)  # [B,1]
    disabled = (top_k[:, None] <= 0) | (top_k[:, None] >= v)
    return disabled | (scaled >= kth)


def _filter_top_p(filtered, top_p):
    """Per-lane nucleus mask on the (already top-k-filtered) logits:
    keep the smallest set of tokens whose cumulative probability
    reaches top_p (the crossing token included; ties kept)."""
    srt = jnp.sort(filtered, axis=-1)[:, ::-1]               # descending
    probs = jax.nn.softmax(srt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < top_p[:, None]   # exclusive cumsum < p
    thr = jnp.min(jnp.where(keep_sorted, srt, jnp.inf), axis=-1)
    disabled = (top_p[:, None] <= 0.0) | (top_p[:, None] >= 1.0)
    return disabled | (filtered >= thr[:, None])


def fused_sample(logits, do_sample, temperature, top_k, top_p, seeds,
                 steps, *, sample_capable=True):
    """Sample one token per lane inside the compiled step program.

    logits [B, V] float; do_sample bool [B]; temperature float32 [B];
    top_k int32 [B]; top_p float32 [B]; seeds/steps int32 [B].
    ``sample_capable`` is a PYTHON bool resolved at trace time.

    Returns ``(tokens int32 [B], logprobs float32 [B])`` — the logprob
    is the chosen token's log-probability under the distribution it was
    actually drawn from (post-filter, post-temperature for sampled
    lanes; the raw softmax for greedy lanes).
    """
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    if not sample_capable:
        lp = jax.nn.log_softmax(lg, axis=-1)
        return greedy, jnp.take_along_axis(
            lp, greedy[:, None], axis=-1)[:, 0]
    scaled = lg / jnp.maximum(temperature, 1e-6)[:, None]
    keep = _filter_top_k(scaled, top_k)
    filtered = jnp.where(keep, scaled, -jnp.inf)
    keep = keep & _filter_top_p(filtered, top_p)
    final = jnp.where(keep, scaled, -jnp.inf)
    gumbel = jax.vmap(
        lambda key: jax.random.gumbel(key, (lg.shape[1],), jnp.float32)
    )(_lane_keys(seeds, steps))
    sampled = jnp.argmax(final + gumbel, axis=-1).astype(jnp.int32)
    tok = jnp.where(do_sample, sampled, greedy)
    dist = jnp.where(do_sample[:, None], final, lg)
    lp = jax.nn.log_softmax(dist, axis=-1)
    return tok, jnp.take_along_axis(lp, tok[:, None], axis=-1)[:, 0]


def fused_sample_multi(logits, do_sample, temperature, top_k, top_p,
                       seeds, steps0, *, sample_capable=True):
    """Per-POSITION fused sampling for the speculative verify step.

    ``logits`` is [B, S, V]; the per-lane sampling params are [B] and
    broadcast over the S positions; position j of lane i draws with the
    counter key ``fold_in(PRNGKey(seeds[i]), steps0[i] + j)`` — exactly
    the key the non-speculative engine would use when sampling that
    request's token ``steps0[i] + j``. That identity is what makes
    deterministic-sample verification token-exact vs the plain decode
    loop: the verify step recomputes the SAME samples the one-token-at-
    a-time engine would have emitted, and acceptance is a pure prefix
    match against the draft's proposals.

    Returns ``(tokens int32 [B, S], logprobs float32 [B, S])``.
    """
    b, s, _ = logits.shape
    flat = logits.reshape(b * s, logits.shape[-1])

    def rep(a):
        return jnp.repeat(a, s, axis=0)

    steps = (steps0[:, None]
             + jnp.arange(s, dtype=jnp.int32)[None, :]).reshape(-1)
    tok, lp = fused_sample(flat, rep(do_sample), rep(temperature),
                           rep(top_k), rep(top_p), rep(seeds), steps,
                           sample_capable=sample_capable)
    return tok.reshape(b, s), lp.reshape(b, s)
