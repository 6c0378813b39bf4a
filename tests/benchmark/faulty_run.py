"""Drive a whole benchmark run with the timed path broken underneath.

    python3 tests/benchmark/faulty_run.py <fault> <benchmark.run arguments>

The fault is planted in the program, the harness runs as ever (CPU
rehearsal sizes), and ``correct`` has to come out false. One fault for
each that a cell can have: a token altered where it is produced, and
tokens served without their log-probabilities (serving);
a step that returns its state unchanged, and half of the batch left out
with the mean taken over the rest (training).
"""
import sys


def altered_token():
    from paddle_tpu.serving.engine import ServingEngine
    real = ServingEngine._emit_token
    count = [0]

    def emit(self, req, tok, events, logprob=None):
        count[0] += 1
        if count[0] % 7 == 0:
            tok = (int(tok) + 1) % self.model.cfg.vocab_size
        return real(self, req, tok, events, logprob=logprob)

    ServingEngine._emit_token = emit


def dropped_logprobs():
    from paddle_tpu.serving.engine import ServingEngine
    real = ServingEngine._emit_token

    def emit(self, req, tok, events, logprob=None):
        return real(self, req, tok, events, logprob=None)

    ServingEngine._emit_token = emit


def state_unchanged():
    from paddle_tpu.optimizer.optimizers import Adam

    def apply(self, params, grads, states, lr, step, use_pallas=None):
        return list(params), [dict(s) for s in states]

    Adam._fused_apply = apply


def half_batch():
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models.llama import LlamaPretrainingCriterion
    real = LlamaPretrainingCriterion.forward

    def forward(self, logits, labels):
        lab = labels._data
        b, s = lab.shape
        keep = (jnp.arange(b)[:, None] < b // 2) if b > 1 \
            else (jnp.arange(s)[None, :] <= s // 2)
        return real(self, logits, Tensor(jnp.where(
            keep, lab, self.ignore_index)))

    LlamaPretrainingCriterion.forward = forward


FAULTS = {"altered_token": altered_token,
          "dropped_logprobs": dropped_logprobs,
          "state_unchanged": state_unchanged,
          "half_batch": half_batch}

if __name__ == "__main__":
    FAULTS[sys.argv[1]]()
    from benchmark.run import main
    sys.exit(main(sys.argv[2:]))
