"""Online draft distillation (round 21): the training half of the
training↔serving loop.

The speculative verify step already computes the TARGET model's sample
for every draft position (engine ``_ragged_step``) — i.e. live traffic
continuously produces free (history, target-token) supervision for the
draft.  This module captures it and turns it into refreshed draft
weights:

- :class:`DistillBuffer` — a bounded ring of (history, target-token)
  pairs, fed by the engine's verify loop (one cheap append per emitted
  token, under the front-end lock; knob-gated via
  ``PADDLE_TPU_SERVING_DISTILL``).  Histories are clipped to the last
  ``PADDLE_TPU_SERVING_DISTILL_HIST`` tokens — the draft's effective
  conditioning window; training on a bounded window is what keeps one
  update cheap.
- :class:`DraftDistiller` — trains a TRAINING COPY of the draft
  (never the serving engine's tensors: the serving pytree only changes
  through the deployer's quiesce path, graftlint ``weight-swap-lock``)
  with the existing stack — ``F.cross_entropy`` on the buffered hard
  targets + ``P.optimizer.AdamW`` — and pushes the refreshed weights
  through a :class:`~paddle_tpu.serving.deploy.RollingDeployer` as a
  new "draft" registry version.  Draft K/V is DISPOSABLE engine state
  (freed anywhere, catchup-prefilled next round), so a draft swap
  needs no prefix flush and in-flight streams stay token-exact: the
  draft only PROPOSES, the target's verify step decides every emitted
  token.  Acceptance rate (``spec_acceptance_rate``) becomes the
  per-workload self-improving metric the fleet harness tracks.

The ``distill_push_torn`` chaos point tears the pushed payload (drops
the tail of the array list) before it reaches the deployer: the swap's
all-or-nothing validation must bounce it and keep the old draft
serving — a bad push degrades acceptance back to where it was, never
correctness.
"""
from __future__ import annotations

import logging
import os
import threading
from collections import deque

import numpy as np

from .chaos import ChaosConfig, ChaosInjector

_log = logging.getLogger("paddle_tpu.serving")

__all__ = ["DistillBuffer", "DraftDistiller", "distill_buffer_from_env"]

# "1" = engines create a DistillBuffer and log verify pairs
_ENV_DISTILL = "PADDLE_TPU_SERVING_DISTILL"
# ring capacity (pairs) and history clip (tokens)
_ENV_BUFFER = "PADDLE_TPU_SERVING_DISTILL_BUFFER"
_ENV_HIST = "PADDLE_TPU_SERVING_DISTILL_HIST"


def distill_buffer_from_env():
    """The engine's constructor hook: a DistillBuffer when the
    ``PADDLE_TPU_SERVING_DISTILL`` knob is on, else None (logging off —
    the verify loop then pays nothing)."""
    if os.environ.get(_ENV_DISTILL) != "1":
        return None
    cap = int(os.environ.get(_ENV_BUFFER) or 4096)
    hist = int(os.environ.get(_ENV_HIST) or 64)
    return DistillBuffer(capacity=cap, max_history=hist)


class DistillBuffer:
    """Bounded ring of (history, target-token) pairs.

    ``log`` runs on the engine loop thread under the front-end lock —
    it must stay O(max_history) per token (tuple slice + append).  The
    trainer reads via ``snapshot()`` from its own thread; the internal
    mutex makes the handoff safe without touching the engine lock."""

    def __init__(self, capacity=4096, max_history=64):
        if capacity < 1:
            raise ValueError(f"capacity={capacity}")
        if max_history < 1:
            raise ValueError(f"max_history={max_history}")
        self.capacity = int(capacity)
        self.max_history = int(max_history)
        self._lock = threading.Lock()
        self._ring = deque(maxlen=self.capacity)
        self.logged = 0         # lifetime pairs (ring may have evicted)

    def log(self, prompt, out_tokens, target_token):
        """One verify-step pair: the token history BEFORE the emitted
        token (prompt + accepted output so far, clipped to the last
        ``max_history`` tokens) and the target's chosen token."""
        k = self.max_history
        out = tuple(out_tokens[-k:]) if out_tokens else ()
        if len(out) < k:
            take = k - len(out)
            hist = tuple(int(t) for t in prompt[-take:]) + out
        else:
            hist = out
        with self._lock:
            self._ring.append((hist, int(target_token)))
            self.logged += 1

    def __len__(self):
        with self._lock:
            return len(self._ring)

    def snapshot(self, clear=False):
        """The buffered pairs, oldest first."""
        with self._lock:
            pairs = list(self._ring)
            if clear:
                self._ring.clear()
        return pairs

    def stats(self):
        with self._lock:
            return {"pairs": len(self._ring), "logged": self.logged,
                    "capacity": self.capacity,
                    "max_history": self.max_history}


class DraftDistiller:
    """Train a draft copy on buffered verify pairs; push via the
    deployer.

    ``train_model`` is the caller-built TRAINING instance of the draft
    architecture (never the serving engine's model object — build it
    up front, and build it SERIALLY with any engine builds:
    ``P.seed()`` is process-global, the round-19 RNG-interleave
    hazard).  ``run_background()`` drives train→push cycles on a
    daemon thread — the "background process" of the loop; it shares
    the interpreter but touches serving state only through the
    deployer's quiesced swap."""

    def __init__(self, train_model, buffer, *, lr=1e-3, batch_size=32,
                 min_pairs=64, chaos=None):
        self.model = train_model
        self.buffer = buffer
        self.lr = float(lr)
        self.batch_size = int(batch_size)
        self.min_pairs = int(min_pairs)
        if isinstance(chaos, ChaosInjector):
            self.chaos = chaos
        else:
            assert chaos is None or isinstance(chaos, ChaosConfig)
            self.chaos = ChaosInjector(chaos, name="distill")
        self._opt = None
        self._stop = threading.Event()
        self._thread = None
        self.steps_trained = 0
        self.pushes = 0

    # -- training ----------------------------------------------------------
    def _optimizer(self):
        if self._opt is None:
            import paddle_tpu as P
            self._opt = P.optimizer.AdamW(
                self.lr, parameters=self.model.parameters())
        return self._opt

    def train_once(self, max_steps=50, clear=False):
        """One training pass over the current buffer contents (hard
        targets, cross-entropy on the LAST position of each history —
        ``ignore_index`` masks the rest, no slicing on the logits).
        Same-length histories batch together.  Returns a report with
        first/last loss so the harness can assert learning happened."""
        import paddle_tpu as P
        import paddle_tpu.nn.functional as F
        pairs = self.buffer.snapshot(clear=clear)
        if len(pairs) < self.min_pairs:
            return {"steps": 0, "pairs": len(pairs),
                    "skipped": "not enough pairs"}
        by_len = {}
        for hist, tok in pairs:
            by_len.setdefault(len(hist), []).append((hist, tok))
        self.model.train()
        opt = self._optimizer()
        losses = []
        steps = 0
        for length in sorted(by_len, reverse=True):
            group = by_len[length]
            for i in range(0, len(group), self.batch_size):
                if steps >= max_steps:
                    break
                chunk = group[i:i + self.batch_size]
                ids = np.asarray([h for h, _ in chunk], np.int32)
                labels = np.full(ids.shape, -100, np.int64)
                labels[:, -1] = [t for _, t in chunk]
                logits = self.model(P.to_tensor(ids))
                loss = F.cross_entropy(logits, P.to_tensor(labels),
                                       ignore_index=-100)
                loss.backward()
                opt.step()
                opt.clear_grad()
                losses.append(float(np.asarray(loss._data)))
                steps += 1
            if steps >= max_steps:
                break
        self.steps_trained += steps
        return {"steps": steps, "pairs": len(pairs),
                "loss_first": losses[0] if losses else None,
                "loss_last": losses[-1] if losses else None}

    # -- the push ----------------------------------------------------------
    def push(self, registry, deployer=None):
        """Publish the trained weights as a new "draft" version and
        (with a deployer) roll the fleet to it.  The
        ``distill_push_torn`` point tears the payload here — the
        deployer-side all-or-nothing validation must bounce the swap
        and keep the OLD draft serving (the push is retried whole next
        cycle; a torn push never becomes a half-swapped draft)."""
        from .deploy import snapshot_weights
        arrays = snapshot_weights(self.model)
        if self.chaos.fire("distill_push_torn"):
            arrays = arrays[:max(1, len(arrays) // 2)]
        version = registry.publish("draft", arrays)
        report = {"version": version, "rolled": None}
        if deployer is not None:
            report["rolled"] = deployer.rollout("draft", version)
        self.pushes += 1
        return report

    # -- background loop ---------------------------------------------------
    def run_background(self, registry, deployer, *, interval_s=1.0,
                       max_steps=50):
        """Start the train→push cycle on a daemon thread.  Returns the
        thread; ``stop()`` ends it.  Push failures (torn payload, swap
        chaos) are logged and the cycle continues — the loop is
        strictly best-effort, serving never depends on it."""
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("distiller already running")
        self._stop.clear()

        def loop():
            while not self._stop.is_set():
                try:
                    rep = self.train_once(max_steps=max_steps)
                    if rep["steps"]:
                        self.push(registry, deployer)
                except Exception:
                    _log.warning("distill cycle failed; retrying next "
                                 "interval", exc_info=True)
                self._stop.wait(interval_s)

        self._thread = threading.Thread(
            target=loop, name="serving-distill", daemon=True)
        self._thread.start()
        return self._thread

    def stop(self, timeout=10.0):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            self._thread = None
