"""The comparison that decides ``correct`` for MiniCPM-SALA: for every
served token of every sampled request, how far its reference logit lies
below the reference's best (``logit_gap_max``) and how far the served
log-probability lies from the reference's (``logprob_err_*``: the
median, the 99th percentile and the largest), against
``reference_sala``; ``short_answers`` and ``unfinished_requests``
exactly. The configuration's ``limits`` say which are judged, and
``PERF.md`` the readings they were set from.

A block-selecting layer answers rounding with selection flips: where two
blocks' scores lie closer than bfloat16 keeps them apart, the program
attends one and the reference the other, and a lightning state carries
the difference on. So the limits are set from sound runs on the chip,
between them and the control; four stand-ins for the program are judged
by them:

- the control (``--control``): the reference with every matmul operand
  rounded to 8 bits, the nearest precision below the configuration's;
- ``_fault_half_batch``: the reference over each request with the first
  half of its prompt lost (a prefill batch of which half the rows never
  reached the cache);
- ``fault_dense``: dense attention in the sparse layers in place of the
  selection;
- ``fault_no_decay``: the lightning layers with every decay left out
  (``lambda = 1``).

Each stand-in serves, at every position, the token it puts first and its
log-probability; the last two are judged here and reported under
``_info``, which the run's line prints. The weights give the sparse
layers' q and k norms a gain (``weights_sala.QK_GAIN``), so that a
row's scores peak as a trained model's do and the blocks it attends
show in its logits; with unit norms, dense attention and the half
prompt read under the program's own rounding (PERF.md section 6).
"""
from __future__ import annotations

import numpy as np

from . import check

FAULTS = {"fault_dense": {"dense": True}, "fault_no_decay": {"decay": False}}


def numbers(store: dict) -> dict:
    """With no finished request to compare, nothing was shown to be
    right: every number infinite."""
    keys = ("logit_gap_max", "logprob_err_max", "logprob_err_p50",
            "logprob_err_p99")
    if not store["lp"]:
        return dict.fromkeys(keys, float("inf"))
    gap, lp = np.concatenate(store["gap"]), np.concatenate(store["lp"])
    return dict(zip(keys, (float(gap.max()), float(lp.max()),
                           float(np.median(lp)),
                           float(np.quantile(lp, 0.99)))))


def _pad(n: int, rows: int) -> int:
    return -(-n // rows) * rows


def served_against_reference(w, cfg, sample, control: str | None = None,
                             rows: int = 32, ffn_rows: int = 256) -> dict:
    """One reference pass over each sampled request's prompt with its
    served tokens, padded to a whole number of ``ffn_rows`` (every layer
    is causal, so no position that counts sees the padding). With
    ``control`` ("int8"), the control and the three planted faults too."""
    import jax
    import jax.numpy as jnp

    from . import reference_sala as reference

    short = 0
    kinds = ["prog"] + (["_control", "_fault_half_batch", *FAULTS]
                        if control else [])
    store = {k: {"gap": [], "lp": []} for k in kinds}
    for r in sample:
        n_out = len(r.tokens)
        short += int(n_out != r.max_new)
        seq = np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])
        ids = np.zeros((1, _pad(len(seq), ffn_rows)), np.int32)
        ids[0, :len(seq)] = seq
        pos = len(r.prompt) - 1 + np.arange(n_out)

        def at(ids_, pos_, **kw):
            return reference.logits_at(w, cfg, ids_, pos_, rows=rows,
                                       ffn_rows=ffn_rows, **kw)

        ref = at(ids, pos)
        lsm = jax.nn.log_softmax(ref, -1)
        best = jnp.max(ref, -1)

        def against(kind, tok, logprob):
            tok = jnp.asarray(tok, jnp.int32)[:, None]
            own = jnp.take_along_axis(ref, tok, -1)[:, 0]
            store[kind]["gap"].append(np.asarray(best - own))
            store[kind]["lp"].append(np.abs(np.asarray(
                logprob - jnp.take_along_axis(lsm, tok, -1)[:, 0])))

        def stand_in(kind, low):
            ctok = jnp.argmax(low, -1)
            against(kind, ctok, jnp.take_along_axis(
                jax.nn.log_softmax(low, -1), ctok[:, None], -1)[:, 0])

        if len(r.logprobs) != n_out:     # a token served without its
            served = np.full(n_out, np.inf, np.float32)   # log-probability
        else:                                             # cannot pass
            served = np.asarray(r.logprobs, np.float32)
        against("prog", r.tokens, served)
        if not control:
            continue
        stand_in("_control", at(ids, pos, prec=control))
        half = len(r.prompt) // 2
        cut = np.zeros_like(ids)
        cut[0, :len(seq) - half] = seq[half:]
        stand_in("_fault_half_batch", at(cut, pos - half))
        for kind, kw in FAULTS.items():
            stand_in(kind, at(ids, pos, **kw))
    out = numbers(store["prog"])
    out["short_answers"] = float(short)
    out["_compared"] = {"requests": len(sample),
                        "tokens": int(sum(len(r.tokens) for r in sample))}
    for kind in kinds[1:]:
        out[kind if kind.startswith("_") else f"_{kind}"] = numbers(
            store[kind])
    return out


def judge_faults(got: dict, limits: dict, unjudged=()) -> dict:
    """The planted faults that ``benchmark/run.py`` does not judge, judged
    as the program is, for the run's ``info``."""
    out = {}
    for kind in FAULTS:
        if f"_{kind}" in got:
            ok, tab = check.judge(got[f"_{kind}"], limits, unjudged)
            out[kind] = {"correct": ok, "compared": tab}
    return out
