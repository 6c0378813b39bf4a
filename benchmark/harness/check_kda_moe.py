"""The comparison that decides ``correct`` for the Kimi-delta-attention /
latent-attention hybrid with group-routed experts: ``check_latent_moe``'s
numbers against ``reference_kda_moe``, of which the configuration judges
the median and the 99th percentile of the log-probability error.

A sparse layer answers rounding with routing flips (``check_latent_moe``),
and here two things widen them. Group-limited routing adds a second kind
of tie (a token whose fourth and fifth groups lie closer than rounding
keeps another 64 experts), so the reference's routing margin (``route``:
the smaller of the group margin and the expert margin) is under
``check.sure_margin`` in one of five layers for nearly every token. And a
delta-rule state carries an earlier token's flip forward into every later
token of the lane (the latent family's attention averages it over the
keys): a token routed surely in every layer still inherits its
predecessors' errors. Measured on the chip (PERF.md section 6, PR 35):
9-15 of 2,449-3,355 served tokens surely routed, their largest
log-probability error 0.20 on one seed beside the int8 control's 0.34;
the median over all tokens 0.032-0.036 beside the control's
0.129-0.135, the 99th percentile 0.19-0.21 beside 0.50-0.52. So
``logprob_err_p50`` and ``logprob_err_p99`` are judged (a fault in a
minority of the tokens moves the second), and the all-token and
sure-token maxima are read and printed but judged by no limit
(``not_compared``); ``short_answers`` and ``unfinished_requests``
exactly.
"""
from __future__ import annotations

import numpy as np

from .check_latent_moe import _numbers


def served_against_reference(w, cfg, sample, pad_to: int,
                             control: str | None = None) -> dict:
    """One reference pass over each sampled request's prompt with its
    served tokens, padded to ``pad_to`` (every layer is causal, so no
    position that counts sees the padding). With ``control`` ("int8")
    that reference stands in the program's place: at each position the
    token it puts first, and its log-probability."""
    import jax
    import jax.numpy as jnp

    from . import reference_kda_moe as reference

    sure_margin = float(cfg["check"]["sure_margin"])
    short = 0
    prog = {"gap": [], "lp": []}
    ctrl = {"gap": [], "lp": []}
    sure = []
    for r in sample:
        n_out = len(r.tokens)
        short += int(n_out != r.max_new)
        ids = np.zeros((1, pad_to), np.int32)
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        ids[0, :len(seq)] = seq
        pos = len(r.prompt) - 1 + np.arange(n_out)
        routes = []
        ref = reference.logits_at(w, cfg, ids, pos, routes=routes)
        lsm = jax.nn.log_softmax(ref, -1)
        best = jnp.max(ref, -1)
        margin = jnp.min(jnp.stack([m[0] for _, m in routes]), 0)
        sure.append(np.asarray(margin)[pos] >= sure_margin)

        def against(tok, logprob):
            tok = jnp.asarray(tok, jnp.int32)[:, None]
            own = jnp.take_along_axis(ref, tok, -1)[:, 0]
            return (np.asarray(best - own), np.abs(np.asarray(
                logprob - jnp.take_along_axis(lsm, tok, -1)[:, 0])))

        if len(r.logprobs) != n_out:     # a token served without its
            served = np.full(n_out, np.inf, np.float32)   # log-probability
        else:                                             # cannot pass
            served = np.asarray(r.logprobs, np.float32)
        g, e = against(r.tokens, served)
        prog["gap"].append(g)
        prog["lp"].append(e)
        if control:
            low = reference.logits_at(w, cfg, ids, pos, prec=control)
            ctok = jnp.argmax(low, -1)
            clp = jnp.take_along_axis(jax.nn.log_softmax(low, -1),
                                      ctok[:, None], -1)[:, 0]
            g, e = against(ctok, np.asarray(clp))
            ctrl["gap"].append(g)
            ctrl["lp"].append(e)
    out = dict(_numbers(prog, sure), **_tail(prog, sure))
    out["short_answers"] = float(short)
    out["_compared"] = {"requests": len(sample),
                        "tokens": int(sum(len(s) for s in sure)),
                        "sure_tokens": int(sum(s.sum() for s in sure))}
    if control:
        out["_control"] = dict(_numbers(ctrl, sure), **_tail(ctrl, sure))
    return out


def _tail(store: dict, sure: list) -> dict:
    """The 99th percentile of the log-probability error over every
    compared token: what a fault confined to a minority of tokens (one
    of the requests compared, one layer, rows at a chunk's edge) moves
    and the median does not."""
    if not sure:
        return {"logprob_err_p99": float("inf")}
    return {"logprob_err_p99": float(np.quantile(np.concatenate(
        store["lp"]), 0.99))}
