"""One general generator of traffic, driven by a mix file.

A mix fixes the sequence of request sizes and of arrival gaps (drawn once
from the mix's own ``pool_seed``); the run's ``--seed`` draws only the
token ids (and the weights). So every seed offers the same work on the
same schedule, and two seeds differ as two runs of one seed do. (Reordering
the sizes by seed moved the backlog cell's rate by 4 % from seed to seed
and by 0.5 % between two runs of one seed: PERF.md.)

Serving mixes (``driver: serve``)::

    {"loop": "closed", "clients": 16, ...}            # callers that wait
    {"loop": "open", "rate_per_s": 7.2, ...}          # independent users
    "prompt_len":  {"median": 512, "sigma": 0.4, "min": 256, "max": 768}
    "output_len":  {"median": 128, "sigma": 0.4, "min": 64,  "max": 224}
    "shared_prefix": {"tokens": 128, "prompts": 4, "zipf_s": 1.0}   # optional
    "arrivals": {"process": "poisson"}
    "pool": 64, "pool_seed": 1

Lengths are lognormal (``median`` * exp(``sigma`` * z)), clipped; the
pool takes z at the ``pool`` evenly spaced quantiles of N(0,1), so a small
pool still stands for the whole distribution, and pairs prompt and output
lengths in an order drawn from ``pool_seed``. Size the pool to about what
one window consumes: then every run does nearly the same work.

A planned request is a function of (mix, seed, index) alone
(``Plan.at(i)``). An open loop takes them in order, each at its due
time (``next()``). A closed loop deals them out: caller ``k`` of ``C``
sends the requests ``k, k + C, k + 2C, ...`` whatever the others do, and
the first round is admitted in index order (``drivers/serve.py::Load``).
So a caller's work is its own in every run. (What a closed-loop cell's
rate still follows is the host's speed: a step 5 % longer sends 5 %
fewer requests in the window and delivers 5 % fewer tokens, PERF.md
section 6, PR 32.)
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Planned:
    """One request as the generator will send it."""
    index: int
    due: float                 # seconds after the loop starts (open loop)
    prompt: np.ndarray         # int32 token ids
    max_new: int
    prefix_id: int = -1        # which shared prefix, -1 for none


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * rng.permutation(z))
    return np.clip(np.rint(raw), spec["min"], spec["max"]).astype(np.int64)


def size_pool(mix: dict) -> list[tuple[int, int, int]]:
    """The mix's fixed set of (prompt_len, output_len, prefix_id)."""
    rng = np.random.default_rng(int(mix.get("pool_seed", 1)))
    n = int(mix["pool"])
    p = _lengths(rng, mix["prompt_len"], n)
    o = _lengths(rng, mix["output_len"], n)
    sp = mix.get("shared_prefix")
    if sp:
        ranks = np.arange(1, int(sp["prompts"]) + 1, dtype=np.float64)
        w = ranks ** -float(sp.get("zipf_s", 1.0))
        pid = rng.choice(len(ranks), size=n, p=w / w.sum())
        p = np.maximum(p, int(sp["tokens"]) + 1)
    else:
        pid = np.full(n, -1)
    return [(int(a), int(b), int(c)) for a, b, c in zip(p, o, pid)]


def gap_pool(mix: dict) -> np.ndarray:
    """The mix's fixed set of inter-arrival gaps, mean exactly 1/rate."""
    rng = np.random.default_rng(int(mix.get("pool_seed", 1)) + 7919)
    n = int(mix["pool"])
    arr = mix.get("arrivals", {"process": "poisson"})
    u = rng.permutation((np.arange(n) + 0.5) / n)     # stratified draws
    if arr["process"] == "poisson":
        g = -np.log1p(-u)
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    return g / g.mean() / float(mix["rate_per_s"])


class Plan:
    """An endless sequence of planned requests: the size pool and the gap
    pool in their own order, cycled; token ids from ``seed`` and the
    request's index, so that ``at(i)`` is the same whoever asks, and
    whenever."""

    def __init__(self, mix: dict, seed: int, vocab: int, max_seq_len: int):
        self.mix, self.vocab = mix, int(vocab)
        self.seed = int(seed)
        self.sizes = size_pool(mix)
        for p, o, _ in self.sizes:
            if p + o > max_seq_len:
                raise ValueError(
                    f"mix asks for prompt {p} + output {o} tokens, past "
                    f"max_seq_len {max_seq_len}: no operation may fail")
        self.dues = (np.cumsum(gap_pool(mix)) if mix["loop"] == "open"
                     else None)
        sp = mix.get("shared_prefix")
        rng = np.random.default_rng([self.seed, 0xBE7C])
        self.prefixes = [] if not sp else [
            rng.integers(0, self.vocab, int(sp["tokens"]), dtype=np.int32)
            for _ in range(int(sp["prompts"]))]
        self._n = 0

    def at(self, index: int) -> Planned:
        """Request ``index`` of the plan."""
        index = int(index)
        cycle, i = divmod(index, len(self.sizes))
        plen, olen, pid = self.sizes[i]
        rng = np.random.default_rng([self.seed, 0xBE7C, index])
        prompt = rng.integers(0, self.vocab, plen, dtype=np.int32)
        if pid >= 0:
            pre = self.prefixes[pid]
            prompt[:len(pre)] = pre
        due = 0.0 if self.dues is None else float(
            cycle * self.dues[-1] + self.dues[i])
        return Planned(index, due, prompt, olen, pid)

    def next(self) -> Planned:
        out = self.at(self._n)
        self._n += 1
        return out
