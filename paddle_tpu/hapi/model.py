"""paddle.Model — the high-level train/eval/predict API.

Reference parity: upstream python/paddle/hapi/model.py (unverified, see
SURVEY.md §2.2, call stack §3.3): prepare/fit/evaluate/predict/train_batch/
eval_batch/save/load/summary + callbacks.

TPU-native design: `train_batch` runs ONE compiled XLA computation —
forward, backward (jax.grad) and the fused optimizer update — the pattern
the reference reaches only via dy2static+CINN. Eager fallback engages
automatically when the step doesn't trace (dynamic shapes etc.). Buffers
(BN running stats) and the RNG key are functionalized through the jit
boundary exactly like paddle_tpu.jit.to_static.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np

from ..core import random as _random
from ..core.autograd import no_grad
from ..core.device import committed
from ..core.tensor import Tensor, to_tensor
from ..io import DataLoader
from ..metric import Metric
from .callbacks import Callback, CallbackList, ModelCheckpoint, ProgBarLogger

__all__ = ["Model"]


def _to_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


class _JitStepper:
    """Compiles loss-forward+backward+optimizer-update into one XLA call."""

    def __init__(self, network, loss_fn, optimizer, amp_level=None):
        self.network = network
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.amp_level = amp_level
        self._jit = None
        self._sig = None

    def _named_state(self):
        # Dedup tied/shared parameters (e.g. tie_word_embeddings): the same
        # Tensor may be reachable under several names, but each donated jit
        # argument must be a distinct buffer.
        train_p, frozen_p, seen = [], [], set()
        for n, p in self.network.named_parameters():
            if id(p) in seen:
                continue
            seen.add(id(p))
            (frozen_p if p.stop_gradient else train_p).append((n, p))
        bufs, seen_b = [], set()
        for n, b in self.network.named_buffers():
            if id(b) in seen_b:
                continue
            seen_b.add(id(b))
            bufs.append((n, b))
        return train_p, frozen_p, bufs

    def _build(self, n_inputs, n_labels):
        train_p, frozen_p, bufs = self._named_state()
        opt = self.optimizer
        loss_fn = self.loss_fn
        network = self.network

        def pure(key, params, frozen, buffers, states, lr, step_i, *batch):
            inputs = [Tensor(a) for a in batch[:n_inputs]]
            labels = [Tensor(a) for a in batch[n_inputs:]]
            all_t = ([t for _, t in train_p] + [t for _, t in frozen_p] +
                     [t for _, t in bufs])
            saved = [(t, t._data) for t in all_t]
            _random.push_trace_key(key)
            try:
                def loss_of(params_):
                    for (n, t), arr in zip(train_p, params_):
                        t._data = arr
                    for (n, t), arr in zip(frozen_p, frozen):
                        t._data = arr
                    for (n, t), arr in zip(bufs, buffers):
                        t._data = arr
                    if self.amp_level:  # graftlint: disable=jit-constant-capture (static scalar config selecting the traced branch, not arrays; weights are jit arguments)
                        # AMP inside the trace: the auto_cast op hooks
                        # emit traced casts, so the compiled program IS
                        # the mixed-precision program
                        from .. import amp as amp_mod
                        with amp_mod.auto_cast(level=self.amp_level):
                            return _forward_loss()
                    return _forward_loss()

                def _forward_loss():
                    outs = network(*inputs)
                    outs = outs if isinstance(outs, (list, tuple)) else \
                        [outs]
                    loss = loss_fn(*(list(outs) + labels))
                    losses = loss if isinstance(loss, (list, tuple)) else \
                        [loss]
                    total = losses[0]
                    for l_ in losses[1:]:
                        total = total + l_
                    new_buf = [t._data for _, t in bufs]
                    return total._data, ([o._data for o in outs], new_buf)

                (loss_v, (out_arrays, new_buf)), grads = \
                    jax.value_and_grad(loss_of, has_aux=True)(list(params))

                if opt._grad_clip is not None:
                    pg = [(t, Tensor(g)) for (n, t), g in zip(train_p,
                                                              grads)]
                    pg = opt._grad_clip(pg)
                    grads = [g._data for _, g in pg]
                new_params, new_states = opt._fused_apply(
                    list(params), grads, list(states), lr, step_i)
                return (loss_v, out_arrays, new_buf, new_params,
                        new_states)
            finally:
                _random.pop_trace_key()
                for t, arr in saved:
                    t._data = arr

        # Donate params/buffers/opt-states: they are consumed and replaced
        # by the returned updated arrays, so XLA can update in place instead
        # of double-buffering the whole model+optimizer footprint in HBM.
        return (jax.jit(pure, donate_argnums=(1, 3, 4)),
                (train_p, frozen_p, bufs))

    def _build_loop(self, n_inputs, n_labels):
        """Compiled MULTI-STEP trainer: lax.scan of the single-step body
        over batches stacked on a leading axis — the whole loop is one
        XLA program, eliminating the per-step host round-trip (~14% of
        wall time in the single-chip profile, PERF.md). LR is captured
        once per loop (schedulers tick between loops, not inside)."""
        step_jit, state_ref = self._build(n_inputs, n_labels)
        pure = step_jit.__wrapped__

        def pure_loop(keys, params, frozen, buffers, states, lr, step0,
                      *batches):
            def body(carry, xs):
                params_, buffers_, states_, step_i = carry
                key = xs[0]
                batch = xs[1:]
                loss_v, _outs, new_buf, new_params, new_states = pure(
                    key, params_, frozen, buffers_, states_, lr, step_i,
                    *batch)
                return ((new_params, new_buf, new_states, step_i + 1),
                        loss_v)

            (params, buffers, states, _), losses = jax.lax.scan(
                body, (list(params), list(buffers), list(states), step0),
                (keys,) + tuple(batches))
            return losses, params, buffers, states

        return (jax.jit(pure_loop, donate_argnums=(1, 3, 4)), state_ref)

    def step_loop(self, inputs, labels):
        """Run N compiled steps at once. inputs/labels arrays carry a
        leading step axis [N, batch, ...]; returns the [N] loss vector."""
        n_steps = int(inputs[0].shape[0])
        sig = ("loop", len(inputs), len(labels),
               tuple(tuple(t.shape) for t in inputs + labels))
        if self._jit is None or self._sig != sig:
            self._jit, self._state_ref = self._build_loop(len(inputs),
                                                          len(labels))
            self._sig = sig
        train_p, frozen_p, bufs = self._state_ref
        opt = self.optimizer
        step0 = jnp.asarray(opt._step_count + 1, jnp.int32)
        opt._step_count += n_steps
        states = [opt._get_state(t) for _, t in train_p]
        keys = jnp.stack([_random.next_key() for _ in range(n_steps)])
        losses, new_params, new_buf, new_states = self._jit(
            keys,
            [t._data for _, t in train_p],
            [t._data for _, t in frozen_p],
            [t._data for _, t in bufs],
            states,
            jnp.asarray(opt.get_lr(), jnp.float32),
            step0,
            *[t._data for t in inputs + labels])
        for (n, t), arr in zip(train_p, new_params):
            t._inplace_update(arr)
        for (n, t), ns in zip(train_p, new_states):
            opt._accum[id(t)] = ns
        for (n, t), arr in zip(bufs, new_buf):
            t._inplace_update(arr)
        return Tensor(losses)

    def step(self, inputs, labels):
        sig = (len(inputs), len(labels),
               tuple(tuple(t.shape) for t in inputs + labels))
        if self._jit is None or self._sig != sig:
            self._jit, self._state_ref = self._build(len(inputs),
                                                     len(labels))
            self._sig = sig
        train_p, frozen_p, bufs = self._state_ref
        opt = self.optimizer
        opt._step_count += 1
        states = [opt._get_state(t) for _, t in train_p]
        key = _random.next_key()
        try:
            loss_v, out_arrays, new_buf, new_params, new_states = \
                self._jit(
                    key,
                    [committed(t._data) for _, t in train_p],
                    [committed(t._data) for _, t in frozen_p],
                    [committed(t._data) for _, t in bufs],
                    jax.tree_util.tree_map(committed, states),
                    jnp.asarray(opt.get_lr(), jnp.float32),
                    jnp.asarray(opt._step_count, jnp.int32),
                    *[t._data for t in inputs + labels])
        except Exception as e:
            # Donated buffers may already be invalidated by a failed
            # execution — the model/optimizer cannot be trusted afterwards.
            raise RuntimeError(
                "jitted train step failed after its inputs were donated; "
                "the model and optimizer state are invalid. Rebuild the "
                "model (and reload a checkpoint) before retrying — e.g. "
                "with a smaller batch if this was RESOURCE_EXHAUSTED. "
                f"Original error: {e}") from e
        for (n, t), arr in zip(train_p, new_params):
            t._inplace_update(arr)
        for (n, t), ns in zip(train_p, new_states):
            opt._accum[id(t)] = ns
        for (n, t), arr in zip(bufs, new_buf):
            t._inplace_update(arr)
        return Tensor(loss_v), [Tensor(o) for o in out_arrays]


class Model:
    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = inputs
        self._labels = labels
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._scaler = None
        self.stop_training = False
        self._stepper = None
        self._jit_broken = False

    # -- preparation ---------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _to_list(metrics)
        for m in self._metrics:
            if not isinstance(m, Metric):
                raise TypeError(f"metrics must be Metric instances, got "
                                f"{type(m)}")
        self._amp_level = None
        if amp_configs:
            if isinstance(amp_configs, str):
                self._amp_level = amp_configs
            else:
                self._amp_level = amp_configs.get("level", "O1")
        # a cached stepper baked the previous optimizer/loss/amp_level
        # into its compiled program — re-preparing must invalidate it
        self._stepper = None
        self._jit_broken = False
        return self

    def _make_stepper(self):
        """When fleet is initialized, train through the mesh-aware SPMD
        engine (DP/ZeRO/TP composed); otherwise the single-device jit
        stepper. Reference flow §3.2→§3.3 unified behind Model.fit."""
        from ..distributed import fleet as fleet_mod
        if fleet_mod.is_initialized():
            from ..distributed.fleet.fleet import _state
            from ..distributed.fleet.spmd import SPMDTrainer
            trainer = SPMDTrainer(self.network, self._optimizer, self._loss,
                                  _state.hcg.mesh, _state.strategy,
                                  amp_level=self._amp_level)

            class _FleetStepper:
                def step(self_, inputs, labels):
                    loss = trainer.train_batch(inputs, labels)
                    return loss, []
            return _FleetStepper()
        return _JitStepper(self.network, self._loss, self._optimizer,
                           amp_level=self._amp_level)

    # -- single-batch ops -----------------------------------------------------
    def train_batch_loop(self, inputs, labels=None):
        """Device-side training loop: N steps compiled into ONE XLA
        program (lax.scan). inputs/labels carry a leading step axis
        [N, batch, ...]; returns the [N] per-step losses. The TPU-native
        counterpart of feeding N batches to train_batch — no host
        round-trip between steps."""
        self.network.train()
        inputs = [to_tensor(x) if not isinstance(x, Tensor) else x
                  for x in _to_list(inputs)]
        labels = [to_tensor(x) if not isinstance(x, Tensor) else x
                  for x in _to_list(labels)]
        if self._stepper is None:
            self._stepper = self._make_stepper()
        return self._stepper.step_loop(inputs, labels)

    def train_batch(self, inputs, labels=None, update=True):
        self.network.train()
        inputs = [to_tensor(x) if not isinstance(x, Tensor) else x
                  for x in _to_list(inputs)]
        labels = [to_tensor(x) if not isinstance(x, Tensor) else x
                  for x in _to_list(labels)]

        if not self._jit_broken and update:
            if self._stepper is None:
                self._stepper = self._make_stepper()
            try:
                loss, outs = self._stepper.step(inputs, labels)
                if outs:
                    self._update_metrics(outs, labels)
                return self._loss_value(loss)
            except (jax.errors.ConcretizationTypeError,
                    jax.errors.TracerBoolConversionError,
                    jax.errors.TracerArrayConversionError) as e:
                warnings.warn(f"jit train step fell back to eager: {e}")
                self._jit_broken = True

        return self._train_batch_eager(inputs, labels, update)

    def _train_batch_eager(self, inputs, labels, update=True):
        from .. import amp as amp_mod
        use_amp = self._amp_level is not None
        if use_amp:
            ctx = amp_mod.auto_cast(level=self._amp_level)
        else:
            import contextlib
            ctx = contextlib.nullcontext()
        with ctx:
            outs = self.network(*inputs)
            outs_l = outs if isinstance(outs, (list, tuple)) else [outs]
            loss = self._loss(*(list(outs_l) + labels))
            losses = _to_list(loss)
            total = losses[0]
            for l_ in losses[1:]:
                total = total + l_
        total.backward()
        if update:
            self._optimizer.step()
            self._optimizer.clear_grad()
        self._update_metrics(outs_l, labels)
        return self._loss_value(total)

    def _loss_value(self, loss):
        return float(np.asarray(loss.numpy()))

    def _update_metrics(self, outs, labels):
        res = []
        for m in self._metrics:
            state = m.compute(*(list(outs) + labels))
            state = state if isinstance(state, (list, tuple)) else [state]
            res.append(m.update(*state))
        return res

    @no_grad()
    def eval_batch(self, inputs, labels=None):
        self.network.eval()
        inputs = [to_tensor(x) if not isinstance(x, Tensor) else x
                  for x in _to_list(inputs)]
        labels = [to_tensor(x) if not isinstance(x, Tensor) else x
                  for x in _to_list(labels)]
        outs = self.network(*inputs)
        outs_l = outs if isinstance(outs, (list, tuple)) else [outs]
        loss = self._loss(*(list(outs_l) + labels)) if self._loss else None
        self._update_metrics(outs_l, labels)
        return (self._loss_value(_to_list(loss)[0])
                if loss is not None else None)

    @no_grad()
    def predict_batch(self, inputs):
        self.network.eval()
        inputs = [to_tensor(x) if not isinstance(x, Tensor) else x
                  for x in _to_list(inputs)]
        outs = self.network(*inputs)
        outs_l = outs if isinstance(outs, (list, tuple)) else [outs]
        return [o.numpy() for o in outs_l]

    # -- loops ----------------------------------------------------------------
    def _make_loader(self, data, batch_size, shuffle, num_workers):
        if isinstance(data, DataLoader):
            return data
        return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                          num_workers=num_workers)

    def _split_batch(self, batch):
        batch = list(batch) if isinstance(batch, (list, tuple)) else [batch]
        n_in = len(self._inputs) if self._inputs else 1
        if len(batch) == 1:
            return batch, []
        return batch[:n_in], batch[n_in:]

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1, verbose=2,
            drop_last=False, shuffle=True, num_workers=0, callbacks=None,
            accumulate_grad_batches=1, num_iters=None):
        loader = self._make_loader(train_data, batch_size, shuffle,
                                   num_workers)
        eval_loader = (self._make_loader(eval_data, batch_size, False,
                                         num_workers)
                       if eval_data is not None else None)
        cbks = _to_list(callbacks)
        if verbose:
            cbks.append(ProgBarLogger(log_freq, verbose=verbose))
        if save_dir:
            cbks.append(ModelCheckpoint(save_freq, save_dir))
        cb = CallbackList(cbks)
        cb.set_model(self)
        try:
            steps = len(loader)
        except TypeError:
            steps = None
        cb.set_params({"epochs": epochs, "steps": steps, "verbose": verbose,
                       "metrics": ["loss"] + [n for m in self._metrics
                                              for n in _to_list(m.name())]})
        self.stop_training = False
        cb.on_train_begin()
        it_count = 0
        logs = {}
        for epoch in range(epochs):
            if hasattr(loader, "batch_sampler") and hasattr(
                    loader.batch_sampler, "set_epoch"):
                loader.batch_sampler.set_epoch(epoch)
            cb.on_epoch_begin(epoch)
            for m in self._metrics:
                m.reset()
            logs = {}
            for step, batch in enumerate(loader):
                cb.on_train_batch_begin(step)
                inputs, labels = self._split_batch(batch)
                loss = self.train_batch(inputs, labels)
                logs = {"loss": loss}
                for m in self._metrics:
                    for n, v in zip(_to_list(m.name()),
                                    _to_list(m.accumulate())):
                        logs[n] = v
                cb.on_train_batch_end(step, logs)
                it_count += 1
                if num_iters is not None and it_count >= num_iters:
                    self.stop_training = True
                    break
            cb.on_epoch_end(epoch, logs)
            if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                eval_logs = self.evaluate(eval_loader,
                                          batch_size=batch_size, verbose=0)
                cb.on_eval_end(eval_logs)
            if self.stop_training:
                break
        cb.on_train_end(logs)

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, num_samples=None):
        loader = self._make_loader(eval_data, batch_size, False, num_workers)
        for m in self._metrics:
            m.reset()
        cb = CallbackList(_to_list(callbacks) +
                          ([ProgBarLogger(log_freq, verbose)] if verbose
                           else []))
        cb.set_model(self)
        cb.set_params({"verbose": verbose})
        cb.on_eval_begin()
        logs = {}
        total_loss, n = 0.0, 0
        for step, batch in enumerate(loader):
            inputs, labels = self._split_batch(batch)
            loss = self.eval_batch(inputs, labels)
            if loss is not None:
                total_loss += loss
                n += 1
            cb.on_eval_batch_end(step, {"loss": loss})
        if n:
            logs["loss"] = total_loss / n
        for m in self._metrics:
            for name, v in zip(_to_list(m.name()),
                               _to_list(m.accumulate())):
                logs[name] = v
        cb.on_eval_end(logs)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, verbose=1, callbacks=None):
        loader = self._make_loader(test_data, batch_size, False, num_workers)
        outputs = []
        for batch in loader:
            inputs, _ = self._split_batch(batch)
            outputs.append(self.predict_batch(inputs))
        if stack_outputs and outputs:
            n_out = len(outputs[0])
            return [np.concatenate([o[i] for o in outputs])
                    for i in range(n_out)]
        return outputs

    # -- persistence ----------------------------------------------------------
    def save(self, path, training=True):
        from ..framework.io_save import save as _save
        _save(self.network.state_dict(), path + ".pdparams")
        if training and self._optimizer is not None:
            _save(self._optimizer.state_dict(), path + ".pdopt")

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        import os

        from ..framework.io_save import load as _load
        state = _load(path + ".pdparams")
        self.network.set_state_dict(state)
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(path + ".pdopt"):
            self._optimizer.set_state_dict(_load(path + ".pdopt"))

    def parameters(self, *args, **kwargs):
        return self.network.parameters()

    def summary(self, input_size=None, dtype=None):
        if input_size is not None:
            from .summary import summary as _summary
            return _summary(self.network, input_size, dtypes=dtype)
        total = 0
        trainable = 0
        lines = ["-" * 60,
                 f"{'Param name':<40}{'Shape':<14}{'#':>6}", "-" * 60]
        for n, p in self.network.named_parameters():
            cnt = p.size
            total += cnt
            if not p.stop_gradient:
                trainable += cnt
            lines.append(f"{n:<40}{str(p.shape):<14}{cnt:>6}")
        lines += ["-" * 60, f"Total params: {total}",
                  f"Trainable params: {trainable}",
                  f"Non-trainable params: {total - trainable}", "-" * 60]
        print("\n".join(lines))
        return {"total_params": total, "trainable_params": trainable}
