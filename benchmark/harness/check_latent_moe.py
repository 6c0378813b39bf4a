"""The comparison that decides ``correct`` for the latent-attention,
sparse-expert family: the served tokens and log-probabilities of the
timed path against ``reference_latent_moe``, as ``check.py`` reads them
for the dense family -- and two numbers more, because of how a sparse
layer answers rounding.

A token whose 8th and 9th expert lie closer than rounding is routed one
way by the bfloat16 program and the other way by the float32 reference,
and with seeded weights one such flip moves its logits by several tenths:
as far as the int8 control moves them. (Measured, PERF.md section 6: half
the served tokens have a routing margin under 0.0012 in one of four
layers; the largest error over *all* tokens reads 0.66-0.91 for the sound
program and 0.85-0.96 for the control.) So the largest error over all
tokens is read and printed but judged by no limit (``not_compared`` in
the configuration's file), and what is judged is:

- ``logprob_err_p50``: the median over every served token of the sample
  of |served log-probability - the reference's|. A flip moves a token,
  not the median; a lower precision moves every token.
- ``sure_logit_gap_max``, ``sure_logprob_err_max``: the largest over the
  served tokens that are *surely routed*: the reference's own routing
  margin (the last chosen expert's biased score less the first left
  out's) is at least the configuration's ``check.sure_margin`` in every
  expert layer. There the program has to agree as a dense one does.
- ``short_answers`` (and the driver's ``unfinished_requests``), exact.
"""
from __future__ import annotations

import numpy as np


def served_against_reference(w, cfg, sample, pad_to: int,
                             control: str | None = None) -> dict:
    """One reference pass over each sampled request's prompt with its
    served tokens, padded to ``pad_to``. With ``control`` ("int8") that
    reference stands in the program's place: at each position the token
    it puts first, and its log-probability, judged over the same
    positions."""
    import jax
    import jax.numpy as jnp

    from . import reference_latent_moe as reference

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
            """(gap of the reference's best over ``tok``, |logprob - the
            reference's|), a row a served position."""
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
    out = _numbers(prog, sure)
    out["short_answers"] = float(short)
    out["_compared"] = {"requests": len(sample),
                        "tokens": int(sum(len(s) for s in sure)),
                        "sure_tokens": int(sum(s.sum() for s in sure))}
    if control:
        out["_control"] = _numbers(ctrl, sure)
    return out


def _numbers(store: dict, sure: list) -> dict:
    """With no finished request to compare, nothing was shown to be
    right: every number infinite. With no surely-routed token among those
    served, the two ``sure_`` numbers have nothing to say: nought."""
    if not sure:
        return dict.fromkeys(("logit_gap_max", "logprob_err_max",
                              "logprob_err_p50", "sure_logit_gap_max",
                              "sure_logprob_err_max"), float("inf"))
    gap, lp, keep = (np.concatenate(a) for a in
                     (store["gap"], store["lp"], sure))
    return {"logit_gap_max": float(gap.max()),
            "logprob_err_max": float(lp.max()),
            "logprob_err_p50": float(np.median(lp)),
            "sure_logit_gap_max": float(gap[keep].max(initial=0.0)),
            "sure_logprob_err_max": float(lp[keep].max(initial=0.0))}
