"""On-device fused sampling for the serving decode hot path.

Reference capability: the fused sampler every vLLM-class TPU serving
stack runs inside the decode program (PAPERS.md Gemma-on-TPU serving
comparison: the per-step host round-trip of [B, V] logits is the decode
latency killer on TPU). Moving sampling on-device shrinks the per-step
host fetch from ``B * V * 4`` bytes of logits to ``B`` int32 token ids
plus ``B`` float32 logprobs (<= B*8 bytes) while keeping the step at
one dispatch + one fetch.

Design constraints (reproducibility rules):

- Everything here is pure jnp — it traces inside the engine's step
  program; per-request ``(seed, step)`` ride as int32 ARGUMENTS,
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
- A batch pays for what one of its lanes asks. The sample-capable
  program holds the top-k/top-p sort (ONE, shared by both filters)
  under ``lax.cond`` on "some sampling lane's filter binds", and the
  Gumbel draw under ``lax.cond`` on "some lane samples" -- scalars of
  the whole batch read from the per-lane arguments the program already
  receives, so an all-greedy batch, the common serving case, runs
  neither in the SAME compiled program a sampled batch uses. Nothing
  may ``vmap`` over :func:`fused_sample`: a ``cond`` under ``vmap``
  becomes a ``select`` and runs both branches.
- ``sample_capable=False`` (a STATIC python flag) compiles the
  greedy-only variant with no sort and no conditional in it. The
  engine's step never uses it (one class for greedy and sampled
  steps); the draft's proposal scan does, from ``any(r.do_sample)``
  of its batch, so that scan has at most two classes a bucket.

Filter semantics match the host oracle (`engine._sample`, numpy):
``top_k <= 0`` or ``>= V`` disables top-k; ``top_p <= 0`` or ``>= 1``
disables top-p; both thresholds KEEP ties; top-p is applied after
top-k on the already-filtered distribution and always keeps at least
the most probable token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["filter_binds", "fused_sample"]


def _lane_keys(seeds, steps):
    """Counter-based per-lane keys: fold the request's token index into
    a key derived from its seed. Both are traced int32 arguments."""
    def one(seed, step):
        return jax.random.fold_in(jax.random.PRNGKey(seed), step)
    return jax.vmap(one)(seeds, steps)


def _filter_thresholds(scaled, top_k, top_p):
    """Per-lane thresholds ``(kth [B, 1], thr [B])`` of the top-k and
    the nucleus filter, from ONE descending sort: the top-k-filtered
    row sorted descending is the sorted row with its tail masked,
    element for element (same multiset, ties kept on both sides)."""
    v = scaled.shape[-1]
    srt = jnp.sort(scaled, axis=-1)[:, ::-1]                 # descending
    k = jnp.clip(top_k, 1, v)
    kth = jnp.take_along_axis(srt, (k - 1)[:, None], axis=-1)  # [B,1]
    srt = jnp.where(_no_top_k(top_k, v) | (srt >= kth), srt, -jnp.inf)
    probs = jax.nn.softmax(srt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep_sorted = (cum - probs) < top_p[:, None]   # exclusive cumsum < p
    thr = jnp.min(jnp.where(keep_sorted, srt, jnp.inf), axis=-1)
    return kth, thr


def _no_top_k(top_k, v):
    return (top_k[:, None] <= 0) | (top_k[:, None] >= v)


def _no_top_p(top_p):
    return (top_p[:, None] <= 0.0) | (top_p[:, None] >= 1.0)


def filter_binds(top_k, top_p, v):
    """Per lane: can its top-k or its top-p cut anything of a ``v``-wide
    row. The condition the sort runs under, for device and host arrays
    alike (the engine counts its steps with it)."""
    return ~(_no_top_k(top_k, v) & _no_top_p(top_p))[:, 0]


def _chosen_logprob(dist, tok):
    lp = jax.nn.log_softmax(dist, axis=-1)
    return jnp.take_along_axis(lp, tok[:, None], axis=-1)[:, 0]


@functools.partial(jax.jit, static_argnames="sample_capable")
def fused_sample(logits, do_sample, temperature, top_k, top_p, seeds,
                 steps, *, sample_capable=True):
    """Sample one token per lane inside the compiled step program (a
    step program inlines this ``jit``; an eager caller, a fork child's
    one row, compiles it once a shape and not a ``cond`` a call).

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
        return greedy, _chosen_logprob(lg, greedy)
    v = lg.shape[-1]
    binds = filter_binds(top_k, top_p, v)

    def draw():
        scaled = lg / jnp.maximum(temperature, 1e-6)[:, None]
        # a threshold of -inf keeps every token: what a lane whose
        # filters are off reads from the sort too
        kth, thr = jax.lax.cond(
            jnp.any(do_sample & binds),
            lambda: _filter_thresholds(scaled, top_k, top_p),
            lambda: (jnp.full_like(scaled[:, :1], -jnp.inf),
                     jnp.full_like(scaled[:, 0], -jnp.inf)))
        keep = _no_top_k(top_k, v) | (scaled >= kth)
        filtered = jnp.where(keep, scaled, -jnp.inf)
        keep = keep & (_no_top_p(top_p) | (filtered >= thr[:, None]))
        final = jnp.where(keep, scaled, -jnp.inf)
        gumbel = jax.vmap(
            lambda key: jax.random.gumbel(key, (v,), jnp.float32)
        )(_lane_keys(seeds, steps))
        sampled = jnp.argmax(final + gumbel, axis=-1).astype(jnp.int32)
        tok = jnp.where(do_sample, sampled, greedy)
        dist = jnp.where(do_sample[:, None], final, lg)
        return tok, _chosen_logprob(dist, tok)

    return jax.lax.cond(jnp.any(do_sample), draw,
                        lambda: (greedy, _chosen_logprob(lg, greedy)))

