"""The comparison that decides ``correct`` for the SambaY family: what
``check.py`` reads for the dense family (``logit_gap_max``,
``logprob_err_max``, ``short_answers``), against ``reference_sambay``.
A dense model answers rounding smoothly (no router flips), so the
all-token maxima are judged, as in the dense cells.
"""
from __future__ import annotations

import numpy as np


def served_against_reference(w, cfg, sample, pad_to: int,
                             control: str | None = None) -> dict:
    """One reference pass over each sampled request's prompt with its
    served tokens, padded to ``pad_to`` (padding follows the last token:
    every layer is causal, so no position that counts can see it). With
    ``control`` ("int8", "fp8") that reference stands in the program's
    place: at each position the token it puts first, and its
    log-probability."""
    import jax
    import jax.numpy as jnp

    from . import reference_sambay as reference

    gap = lp_err = 0.0 if sample else float("inf")
    n_tok = short = 0
    c_gap = c_lp = 0.0
    for r in sample:
        n_out = len(r.tokens)
        short += int(n_out != r.max_new)
        ids = np.zeros((1, pad_to), np.int32)
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        ids[0, :len(seq)] = seq
        pos = len(r.prompt) - 1 + np.arange(n_out)
        ref = reference.logits_at(w, cfg, ids, pos)
        lsm = jax.nn.log_softmax(ref, -1)
        best = jnp.max(ref, -1)

        def against(tok, logprob):
            tok = jnp.asarray(tok, jnp.int32)[:, None]
            return (float(jnp.max(
                best - jnp.take_along_axis(ref, tok, -1)[:, 0])),
                float(jnp.max(jnp.abs(jnp.asarray(logprob, jnp.float32)
                    - jnp.take_along_axis(lsm, tok, -1)[:, 0]))))

        if len(r.logprobs) != n_out:     # a token served without its
            served = np.full(n_out, np.inf, np.float32)   # log-probability
        else:                                             # cannot pass
            served = np.asarray(r.logprobs, np.float32)
        g, e = against(r.tokens, served)
        gap, lp_err = max(gap, g), max(lp_err, e)
        n_tok += n_out
        if control:
            low = reference.logits_at(w, cfg, ids, pos, prec=control)
            ctok = jnp.argmax(low, -1)
            g, e = against(ctok, jnp.take_along_axis(
                jax.nn.log_softmax(low, -1), ctok[:, None], -1)[:, 0])
            c_gap, c_lp = max(c_gap, g), max(c_lp, e)
    out = {"logit_gap_max": gap, "logprob_err_max": lp_err,
           "short_answers": float(short),
           "_compared": {"requests": len(sample), "tokens": n_tok}}
    if control:
        out["_control"] = {"logit_gap_max": c_gap, "logprob_err_max": c_lp}
    return out
