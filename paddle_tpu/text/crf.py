"""Linear-chain CRF (sequence labeling — the reference's classic
lexical-analysis stack).

Reference surface: upstream linear_chain_crf op + PaddleNLP
LinearChainCrf/LinearChainCrfLoss (unverified — see SURVEY.md §2.2
"Misc domains"): learnable tag-transition matrix with START/STOP
boundary scores, forward-algorithm log-partition for the NLL loss, and
Viterbi decode (delegates to text.viterbi_decode — one copy of the DP).

TPU-first notes:
- The log-partition forward recursion is a `lax.scan` over time of one
  [B, N] logsumexp-matmul step; masking handles ragged lengths with
  static shapes. (log Z and the gold score are two ops today — under a
  jitted train step XLA fuses them into one program; eager micro-jit
  dispatches them separately.)
- The exactness oracle (tests/test_text_crf.py) enumerates ALL tag
  paths at small T, N and matches log Z and the decoded argmax path —
  the strongest possible check of the recursion.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.autograd import apply
from ..core.tensor import Tensor
from ..nn.layer import Layer

__all__ = ["LinearChainCrf", "LinearChainCrfLoss"]


# Module-level, so that each is ONE function object for the life of the
# process: apply() keys its micro-jit (and lax.scan its compiled loop) on
# the function's identity, and a def nested in the method would be a new
# object — a new trace and a new XLA compile — on every call.
def _gold_score(em, lab, ln, trans, start, stop):
    b, t, n = em.shape
    pos = jnp.arange(t)
    valid = pos[None, :] < ln[:, None]                 # [B,T]
    em_score = jnp.take_along_axis(
        em, lab[..., None], axis=2)[..., 0]            # [B,T]
    em_score = jnp.where(valid, em_score, 0.0).sum(-1)
    tr = trans[lab[:, :-1], lab[:, 1:]]                # [B,T-1]
    tr_valid = pos[None, 1:] < ln[:, None]
    tr_score = jnp.where(tr_valid, tr, 0.0).sum(-1)
    last = jnp.take_along_axis(
        lab, (ln - 1)[:, None], axis=1)[:, 0]
    return (em_score + tr_score + start[lab[:, 0]]
            + stop[last])


def _log_partition(em, ln, trans, start, stop):
    b, t, n = em.shape
    alpha0 = start[None, :] + em[:, 0]                 # [B,N]

    def step(alpha, inputs):
        em_t, pos = inputs
        nxt = jax.nn.logsumexp(
            alpha[:, :, None] + trans[None], axis=1) + em_t
        keep = (pos < ln)[:, None]
        return jnp.where(keep, nxt, alpha), None

    alpha, _ = jax.lax.scan(
        step, alpha0,
        (jnp.swapaxes(em[:, 1:], 0, 1),
         jnp.arange(1, t)))
    return jax.nn.logsumexp(alpha + stop[None, :], axis=-1)


class LinearChainCrf(Layer):
    """Holds the learnable transition scores.

    `transitions` [N, N] (from-tag -> to-tag), plus `start_scores` /
    `stop_scores` [N] boundary terms (the reference packs these as the
    two extra rows of an [N+2, N+2] table; the math is identical).
    """

    def __init__(self, num_tags):
        super().__init__()
        self.num_tags = num_tags
        self.transitions = self.create_parameter((num_tags, num_tags))
        self.start_scores = self.create_parameter((num_tags,))
        self.stop_scores = self.create_parameter((num_tags,))

    # -- scores ---------------------------------------------------------
    def gold_score(self, emissions, labels, lengths):
        """Score of the gold path: emissions [B,T,N], labels [B,T],
        lengths [B] -> [B]."""
        emissions = _ensure(emissions)
        labels = _ensure(labels).detach()
        lengths = _ensure(lengths).detach()
        return apply(_gold_score, emissions, labels, lengths,
                     self.transitions, self.start_scores, self.stop_scores,
                     name="crf_gold_score")

    def log_partition(self, emissions, lengths):
        """log Z via the forward algorithm: [B,T,N],[B] -> [B]."""
        emissions = _ensure(emissions)
        lengths = _ensure(lengths).detach()
        return apply(_log_partition, emissions, lengths, self.transitions,
                     self.start_scores, self.stop_scores,
                     name="crf_log_partition")

    def decode(self, emissions, lengths):
        """Viterbi argmax paths -> (scores [B], paths [B,T]). Delegates
        to text.viterbi_decode (one DP implementation) with the
        boundary scores folded into the first/last emissions."""
        from . import viterbi_decode
        emissions = _ensure(emissions)
        lengths = _ensure(lengths)
        em = emissions._data
        b, t, n = em.shape
        ln = lengths._data
        em = em.at[:, 0].add(self.start_scores._data[None])
        last = jnp.clip(ln - 1, 0, t - 1)
        em = em.at[jnp.arange(b), last].add(
            self.stop_scores._data[None])
        return viterbi_decode(Tensor(em), self.transitions, lengths,
                              include_bos_eos_tag=False)


class LinearChainCrfLoss(Layer):
    """NLL = log Z − score(gold): the reference's CRF training loss.

    reduction: "mean" (default) | "sum" | "none" ([B] per-sequence nll
    — the reference's shape, for per-example weighting)."""

    def __init__(self, crf: LinearChainCrf, reduction="mean"):
        super().__init__()
        self.crf = crf
        self.reduction = reduction

    def forward(self, emissions, lengths, labels):
        nll = (self.crf.log_partition(emissions, lengths)
               - self.crf.gold_score(emissions, labels, lengths))
        if self.reduction == "mean":
            return nll.mean()
        if self.reduction == "sum":
            return nll.sum()
        return nll


def _ensure(x):
    from ..core.tensor import to_tensor
    return x if isinstance(x, Tensor) else to_tensor(x)
