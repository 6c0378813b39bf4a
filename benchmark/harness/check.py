"""The comparison that decides ``correct``: what the timed path produced,
at the timed sizes, against the plain reference. Each number compared has
a limit of its own, kept in the configuration's file (``limits``) with
the readings it was set from in ``PERF.md``.
"""
from __future__ import annotations

import sys

import numpy as np


def pick_sample(records, n: int, seed: int) -> list:
    """``n`` of the finished requests, drawn from the seed, the longest
    (prompt and served tokens together) always among them."""
    done = [r for r in records if r.ok]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens),
                                       -r.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 0xC0DE])
    take = rng.permutation(len(rest))[:max(0, n - 1)]
    return [longest] + [rest[i] for i in sorted(take)]


def served_against_reference(w, cfg, sample, pad_to: int,
                             control: str | None = None) -> dict:
    """For every served token of every sampled request: how far its
    reference logit lies below the reference's best at that position
    (``logit_gap_max``: 0 where the served token is the reference's own
    choice, the size of the near-tie where rounding chose its neighbour,
    several logit deviations where a token is wrong), and how far the
    served log-probability lies from the reference's for that token
    (``logprob_err_max``). One reference pass over each prompt with its
    served tokens, padded to ``pad_to`` (padding follows the last token,
    so no position that counts can see it).

    With ``control`` ("int8", "fp8") that reference stands in the program's place: at
    each position the token it puts first, and its log-probability."""
    import jax
    import jax.numpy as jnp

    from . import reference

    # with no finished request to compare, nothing was shown to be right
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
        tok = jnp.asarray(r.tokens, jnp.int32)[:, None]
        best = jnp.max(ref, -1)
        gap = max(gap, float(jnp.max(
            best - jnp.take_along_axis(ref, tok, -1)[:, 0])))
        if len(r.logprobs) != n_out:     # a token served without its
            lp_err = float("inf")        # log-probability cannot pass
        else:
            served = jnp.asarray(r.logprobs, jnp.float32)
            lp_err = max(lp_err, float(jnp.max(jnp.abs(
                served - jnp.take_along_axis(lsm, tok, -1)[:, 0]))))
        n_tok += n_out
        if control:
            low = reference.logits_at(w, cfg, ids, pos, prec=control)
            ctok = jnp.argmax(low, -1)[:, None]
            c_gap = max(c_gap, float(jnp.max(
                best - jnp.take_along_axis(ref, ctok, -1)[:, 0])))
            c_lp = max(c_lp, float(jnp.max(jnp.abs(
                jnp.take_along_axis(jax.nn.log_softmax(low, -1), ctok, -1)
                - jnp.take_along_axis(lsm, ctok, -1)))))
    out = {"logit_gap_max": gap, "logprob_err_max": lp_err,
           "short_answers": float(short),
           "_compared": {"requests": len(sample), "tokens": n_tok}}
    if control:
        out["_control"] = {"logit_gap_max": c_gap, "logprob_err_max": c_lp}
    return out


def worst_leaf_gap(prog: dict, ref: dict, keep=None) -> float:
    """Gap between the program's norm and the reference's, by the worst
    leaf, measured against the reference's norm of that leaf or of the
    median leaf, whichever is larger. Leaves are keyed alike."""
    med = float(np.median([ref[k] for k in ref]))
    worst = 0.0
    for k, r in ref.items():
        if keep is not None and k not in keep:
            continue
        worst = max(worst, abs(prog[k] - r) / max(r, med))
    return worst


def moving_leaves(ref_grad: dict) -> set:
    """Leaves whose reference gradient is not nought to rounding: at
    least a thousandth of the median leaf's. The others move under Adam
    by round-off alone and are left out of the parameters' change."""
    med = float(np.median(list(ref_grad.values())))
    return {k for k, g in ref_grad.items() if g >= 1e-3 * med}


def judge(numbers: dict, limits: dict, not_compared=()) -> tuple[bool, dict]:
    """Every number beside its limit; correct where none passes its own.
    A number with no limit in the configuration's file is an error of the
    benchmark, not a pass -- unless the file names it under
    ``not_compared`` (a number that no control or fault separates from
    sound runs: read and printed, never judged)."""
    table, ok = {}, True
    for name, value in numbers.items():
        if name.startswith("_"):
            continue
        if name in not_compared:
            print(f"benchmark: read, not compared: {name} = {value:.6g}",
                  file=sys.stderr)
            continue
        if name not in limits:
            raise SystemExit(f"benchmark: no limit for {name!r} in the "
                             "configuration's file")
        lim = float(limits[name])
        good = bool(np.isfinite(value)) and value <= lim
        ok &= good
        table[name] = {"value": float(value), "limit": lim}
    return ok, table


def report(table: dict, correct: bool, extra: dict | None = None):
    """Each number compared beside its limit, as the last lines on
    standard error."""
    for k, v in (extra or {}).items():
        print(f"benchmark: {k} = {v}", file=sys.stderr)
    for name, row in table.items():
        print(f"benchmark: compared {name} = {row['value']:.6g} "
              f"(limit {row['limit']:.6g})", file=sys.stderr)
    print(f"benchmark: correct = {str(correct).lower()}", file=sys.stderr,
          flush=True)
