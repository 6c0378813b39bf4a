"""The arithmetic from client-side records to end-to-end numbers.

Every number here is all the work over the whole window, or a tail over
every request of the window: never a median of chunks. A stall inside
the window has to move each of them (tested on a synthetic schedule).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np


@dataclass
class ReqRecord:
    """One request as the client saw it, on the client's clock."""
    index: int
    due: float                     # when it was due to be sent
    prompt: np.ndarray
    max_new: int
    sent: float | None = None      # when submit() was called
    stamps: list = field(default_factory=list)    # one per token event
    tokens: list = field(default_factory=list)
    logprobs: list = field(default_factory=list)
    finished: float | None = None
    error: str | None = None       # refused, failed or never finished

    @property
    def ok(self) -> bool:
        return (self.error is None and self.finished is not None
                and len(self.tokens) == self.max_new)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default), as a float."""
    return float(np.percentile(np.asarray(values, np.float64), q))


def in_window(records, t0: float, t1: float) -> list:
    """The requests of the window: those due inside it."""
    return [r for r in records if t0 <= r.due < t1]


def ttft_s(records, t0, t1, gave_up_at: float) -> list[float]:
    """Time to first token of every request due in the window, from the
    moment it was due. One that failed, was refused or never produced a
    token counts as the wait until the client gave up, which is later
    than any that succeeded."""
    out = []
    for r in in_window(records, t0, t1):
        if r.stamps and r.error is None:
            out.append(r.stamps[0] - r.due)
        else:
            out.append(max(gave_up_at, t1) - r.due)
    return out


def token_gaps_s(records, t0, t1) -> list[float]:
    """Every gap between consecutive tokens of every request of the
    window, pooled."""
    out = []
    for r in in_window(records, t0, t1):
        out.extend(np.diff(r.stamps).tolist())
    return out


def tokens_in_window(records, t0, t1) -> int:
    """Token events that reached a client inside the window, whatever
    request they belong to."""
    return int(sum(sum(1 for s in r.stamps if t0 <= s < t1)
                   for r in records))


def lateness_s(records, t0, t1) -> list[float]:
    """How late the generator sent each request of the window."""
    return [r.sent - r.due for r in in_window(records, t0, t1)
            if r.sent is not None]
