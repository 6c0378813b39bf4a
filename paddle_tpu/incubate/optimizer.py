"""paddle.incubate.optimizer — LookAhead and ModelAverage (reference:
python/paddle/incubate/optimizer/ — unverified, SURVEY.md §2.2 Incubate).

Both are weight-space wrappers around any base optimizer: LookAhead
interpolates slow weights toward the fast ones every k steps; ModelAverage
keeps a running average applied at evaluation time. All weight updates go
through no_grad set_value, so they compose with AMP master weights and
the compiled steppers (weights stay the same Tensor objects).
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp
import numpy as np

from ..core.autograd import no_grad
from ..core.tensor import Tensor

__all__ = ["LookAhead", "ModelAverage"]


class LookAhead:
    """k-step lookahead (Zhang et al. 2019): fast weights run the inner
    optimizer; every k steps slow <- slow + alpha*(fast - slow) and
    fast <- slow."""

    def __init__(self, inner_optimizer, alpha=0.5, k=5, name=None):
        if not (0.0 <= float(alpha) <= 1.0):
            raise ValueError(f"alpha must be in [0, 1], got {alpha}")
        if int(k) < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        self.inner_optimizer = inner_optimizer
        self.alpha = float(alpha)
        self.k = int(k)
        self._steps = 0
        # dedupe by identity: a param in several groups must appear once,
        # or the save (keyed on _slow) and load (enumerating _params)
        # index spaces misalign (ADVICE r3 #2)
        self._params = []
        _seen: set = set()
        for g in inner_optimizer._param_groups:
            for p in g["params"]:
                if id(p) not in _seen:
                    _seen.add(id(p))
                    self._params.append(p)
        with no_grad():
            self._slow = {id(p): np.asarray(p._data).copy()
                          for p in self._params}

    def step(self):
        self.inner_optimizer.step()
        self._steps += 1
        if self._steps % self.k == 0:
            with no_grad():
                for p in self._params:
                    slow = self._slow[id(p)]
                    slow = slow + self.alpha * (
                        np.asarray(p._data) - slow)
                    self._slow[id(p)] = slow
                    p.set_value(slow)
                    # multi_precision: the inner optimizer recomputes p
                    # from its fp32 master copy every step — sync it or
                    # the interpolation is silently discarded
                    st = self.inner_optimizer._accum.get(id(p))
                    if st is not None and "master" in st:
                        st["master"] = jnp.asarray(slow,
                                                   jnp.float32)

    def clear_grad(self, set_to_zero=False):
        return self.inner_optimizer.clear_grad(set_to_zero)

    def state_dict(self):
        return {"inner": self.inner_optimizer.state_dict(),
                # enumerate self._params (the same sequence set_state_dict
                # walks) — not _slow insertion order
                "slow": {str(i): self._slow[id(p)]
                         for i, p in enumerate(self._params)},
                "steps": self._steps,
                "alpha": self.alpha, "k": self.k}

    def set_state_dict(self, state):
        self.inner_optimizer.set_state_dict(state["inner"])
        self._steps = int(state["steps"])
        for i, p in enumerate(self._params):
            v = state["slow"].get(str(i))
            if v is not None:
                self._slow[id(p)] = np.asarray(v)


class ModelAverage:
    """Running average of parameters (reference semantics: call .step()
    after each optimizer step; wrap evaluation in `.apply()` to swap the
    averaged weights in, `.restore()`/context exit swaps back).

    average_window_rate bounds the window: the accumulator restarts when
    the window exceeds max(min_average_window,
    average_window_rate * num_updates) capped by max_average_window."""

    def __init__(self, average_window_rate, parameters=None,
                 min_average_window=10000, max_average_window=10000,
                 name=None):
        if parameters is None:
            raise ValueError("parameters is required")
        self.rate = float(average_window_rate)
        self.min_window = int(min_average_window)
        self.max_window = int(max_average_window)
        self._params = list(parameters)
        # accumulate ON DEVICE (f32): a per-step host fetch of every
        # parameter would serialize the training hot loop on the
        # device-to-host copy; apply() is the only host-visible point
        self._sum = {id(p): jnp.zeros_like(p._data, dtype=jnp.float32)
                     for p in self._params}
        self._count = 0
        self._updates = 0
        self._backup = None

    def step(self):
        self._updates += 1
        with no_grad():
            for p in self._params:
                self._sum[id(p)] = self._sum[id(p)] \
                    + p._data.astype(jnp.float32)
        self._count += 1
        window = max(self.min_window,
                     int(self.rate * self._updates))
        window = min(window, self.max_window)
        if self._count > window:
            # restart the window from the current weights
            with no_grad():
                for p in self._params:
                    self._sum[id(p)] = p._data.astype(jnp.float32)
            self._count = 1

    def minimize(self, loss=None):  # reference-API alias
        self.step()

    @contextlib.contextmanager
    def apply(self, executor=None, need_restore=True):
        if self._count == 0:
            yield
            return
        with no_grad():
            self._backup = {id(p): p._data for p in self._params}
            for p in self._params:
                avg = (self._sum[id(p)] / self._count).astype(
                    p._data.dtype)
                p.set_value(avg)
        try:
            yield
        finally:
            if need_restore:
                self.restore()

    def restore(self, executor=None):
        if self._backup is None:
            return
        with no_grad():
            for p in self._params:
                p.set_value(self._backup[id(p)])
        self._backup = None


from ..optimizer.optimizers import LBFGS  # noqa: E402,F401  (reference
# re-exports the LBFGS implementation under incubate.optimizer too)
