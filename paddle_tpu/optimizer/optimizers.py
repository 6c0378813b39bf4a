"""Concrete optimizers: SGD, Momentum, Adagrad, RMSProp, Adam, AdamW, Lamb,
Adamax, Adadelta (reference: paddle.optimizer.* — upstream
python/paddle/optimizer/, unverified; see SURVEY.md §2.2).

Each `_update` is a pure jax function over (param, grad, state) executed
inside the base class's single fused jit (SURVEY.md §2.1 multi-tensor
adamw parity). Adam-family epsilon placement matches the reference:
eps is added to sqrt(v_hat) *after* bias correction.
"""
from __future__ import annotations

import os

import jax.numpy as jnp

from ..core.autograd import enable_grad as _enable_grad_ctx, no_grad
from .optimizer import Optimizer


def _pallas_adamw_auto() -> bool:
    """Opt-in (PADDLE_TPU_PALLAS_ADAMW=1), single-chip only.

    Measured on TPU v5e (PERF.md): the per-leaf Pallas launches LOSE to
    XLA's whole-pytree fused update (48.1% vs 50.3% MFU on the LLaMA
    proxy) — XLA already fuses the master-weight casts into one update
    loop and overlaps across leaves, so the default stays XLA. The kernel
    remains available for experimentation and as the building block for a
    future multi-leaf (truly multi-tensor) variant.

    Multi-device programs (fleet SPMD / pipeline) must keep the plain-XLA
    update either way — `pallas_call` has no GSPMD partitioning rule, so
    a sharded leaf would be gathered; those call sites pass
    use_pallas=False.
    """
    if os.environ.get("PADDLE_TPU_PALLAS_ADAMW", "0") != "1":
        return False
    try:
        import jax
        return (jax.default_backend() == "tpu"
                and jax.device_count() == 1)
    except Exception:
        return False


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    @staticmethod
    def _update(param, grad, state, lr, step, hp):
        wd = hp["weight_decay"]
        if wd:
            grad = grad + wd * param
        return param - lr * grad, {}


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None):
        self._momentum = float(momentum)
        self._nesterov = use_nesterov
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def _init_state(self, p):
        return {"velocity": jnp.zeros(p._data.shape, jnp.float32)}

    def _hyperparams(self):
        return {"weight_decay": self._weight_decay, "mu": self._momentum,
                "nesterov": self._nesterov}

    @staticmethod
    def _update(param, grad, state, lr, step, hp):
        wd, mu = hp["weight_decay"], hp["mu"]
        if wd:
            grad = grad + wd * param
        v = mu * state["velocity"] + grad
        if hp["nesterov"]:
            new_p = param - lr * (grad + mu * v)
        else:
            new_p = param - lr * v
        return new_p, {"velocity": v}


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value
                 =0.0, multi_precision=False, name=None):
        self._epsilon = epsilon
        self._init_acc = initial_accumulator_value
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def _init_state(self, p):
        return {"moment": jnp.full(p._data.shape, self._init_acc,
                                   jnp.float32)}

    def _hyperparams(self):
        return {"weight_decay": self._weight_decay, "eps": self._epsilon}

    @staticmethod
    def _update(param, grad, state, lr, step, hp):
        wd = hp["weight_decay"]
        if wd:
            grad = grad + wd * param
        m = state["moment"] + grad * grad
        return param - lr * grad / (jnp.sqrt(m) + hp["eps"]), {"moment": m}


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None):
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def _init_state(self, p):
        s = {"mean_square": jnp.zeros(p._data.shape, jnp.float32),
             "moment": jnp.zeros(p._data.shape, jnp.float32)}
        if self._centered:
            s["mean_grad"] = jnp.zeros(p._data.shape, jnp.float32)
        return s

    def _hyperparams(self):
        return {"weight_decay": self._weight_decay, "rho": self._rho,
                "eps": self._epsilon, "mu": self._momentum,
                "centered": self._centered}

    @staticmethod
    def _update(param, grad, state, lr, step, hp):
        wd, rho, eps, mu = (hp["weight_decay"], hp["rho"], hp["eps"],
                            hp["mu"])
        if wd:
            grad = grad + wd * param
        ms = rho * state["mean_square"] + (1 - rho) * grad * grad
        out_state = {"mean_square": ms}
        if hp["centered"]:
            mg = rho * state["mean_grad"] + (1 - rho) * grad
            denom = jnp.sqrt(ms - mg * mg + eps)
            out_state["mean_grad"] = mg
        else:
            denom = jnp.sqrt(ms + eps)
        mom = mu * state["moment"] + lr * grad / denom
        out_state["moment"] = mom
        return param - mom, out_state


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, lazy_mode=False, multi_precision=False,
                 use_multi_tensor=False, amsgrad=False, name=None):
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._amsgrad = amsgrad
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         multi_precision, name)

    def _init_state(self, p):
        s = {"moment1": jnp.zeros(p._data.shape, jnp.float32),
             "moment2": jnp.zeros(p._data.shape, jnp.float32)}
        if self._amsgrad:
            s["moment2_max"] = jnp.zeros(p._data.shape, jnp.float32)
        return s

    def _hyperparams(self):
        return {"weight_decay": self._weight_decay, "b1": self._beta1,
                "b2": self._beta2, "eps": self._epsilon,
                "amsgrad": self._amsgrad, "decoupled": False}

    def _fused_apply(self, params, grads, states, lr, step,
                     use_pallas=None):
        """Route lane-divisible leaves through the fused Pallas kernel
        (one HBM pass incl. the master-weight casts); everything else
        takes the base XLA path."""
        if use_pallas is None:
            use_pallas = _pallas_adamw_auto()
        if not use_pallas or self._amsgrad:
            return super()._fused_apply(params, grads, states, lr, step)
        from ..ops.pallas._adamw_kernel import adamw_eligible, adamw_update
        hp = self._hyperparams()
        new_params, new_states = [], []
        for p, g, s in zip(params, grads, states):
            if adamw_eligible(p.shape, p.dtype, s):
                np_, ns = adamw_update(
                    p, g, s, lr, step, b1=hp["b1"], b2=hp["b2"],
                    eps=hp["eps"], wd=hp["weight_decay"],
                    decoupled=hp["decoupled"])
            else:
                np_, ns = self._update_one(p, g, s, lr, step, hp)
            new_params.append(np_)
            new_states.append(ns)
        return new_params, new_states

    @staticmethod
    def _update(param, grad, state, lr, step, hp):
        b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
        wd = hp["weight_decay"]
        if wd and not hp["decoupled"]:
            grad = grad + wd * param
        m1 = b1 * state["moment1"] + (1 - b1) * grad
        m2 = b2 * state["moment2"] + (1 - b2) * grad * grad
        stepf = step.astype(jnp.float32)
        bc1 = 1 - b1 ** stepf
        bc2 = 1 - b2 ** stepf
        out = {"moment1": m1, "moment2": m2}
        v = m2
        if hp["amsgrad"]:
            v = jnp.maximum(state["moment2_max"], m2)
            out["moment2_max"] = v
        m_hat = m1 / bc1
        v_hat = v / bc2
        update = m_hat / (jnp.sqrt(v_hat) + eps)
        if wd and hp["decoupled"]:
            update = update + wd * param
        return param - lr * update, out


class AdamW(Adam):
    """Decoupled weight decay (reference default coeff 0.01)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=0.01,
                 lr_ratio=None, apply_decay_param_fun=None, grad_clip=None,
                 lazy_mode=False, multi_precision=False, amsgrad=False,
                 name=None):
        self._apply_decay_fun = apply_decay_param_fun
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         False, amsgrad, name)

    def _hyperparams(self):
        hp = super()._hyperparams()
        hp["decoupled"] = True
        return hp


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         False, name)

    def _init_state(self, p):
        return {"moment": jnp.zeros(p._data.shape, jnp.float32),
                "inf_norm": jnp.zeros(p._data.shape, jnp.float32)}

    def _hyperparams(self):
        return {"weight_decay": self._weight_decay, "b1": self._beta1,
                "b2": self._beta2, "eps": self._epsilon}

    @staticmethod
    def _update(param, grad, state, lr, step, hp):
        b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
        wd = hp["weight_decay"]
        if wd:
            grad = grad + wd * param
        m = b1 * state["moment"] + (1 - b1) * grad
        u = jnp.maximum(b2 * state["inf_norm"], jnp.abs(grad))
        stepf = step.astype(jnp.float32)
        lr_t = lr / (1 - b1 ** stepf)
        return (param - lr_t * m / (u + eps),
                {"moment": m, "inf_norm": u})


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 name=None):
        self._rho, self._epsilon = rho, epsilon
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         False, name)

    def _init_state(self, p):
        return {"avg_squared_grad": jnp.zeros(p._data.shape, jnp.float32),
                "avg_squared_update": jnp.zeros(p._data.shape, jnp.float32)}

    def _hyperparams(self):
        return {"weight_decay": self._weight_decay, "rho": self._rho,
                "eps": self._epsilon}

    @staticmethod
    def _update(param, grad, state, lr, step, hp):
        rho, eps = hp["rho"], hp["eps"]
        wd = hp["weight_decay"]
        if wd:
            grad = grad + wd * param
        asg = rho * state["avg_squared_grad"] + (1 - rho) * grad * grad
        upd = (jnp.sqrt(state["avg_squared_update"] + eps) /
               jnp.sqrt(asg + eps)) * grad
        asu = rho * state["avg_squared_update"] + (1 - rho) * upd * upd
        return param - lr * upd, {"avg_squared_grad": asg,
                                  "avg_squared_update": asu}


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01,
                 beta1=0.9, beta2=0.999, epsilon=1e-6, parameters=None,
                 grad_clip=None, exclude_from_weight_decay_fn=None,
                 multi_precision=False, name=None):
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._exclude_fn = exclude_from_weight_decay_fn
        super().__init__(learning_rate, parameters, lamb_weight_decay,
                         grad_clip, multi_precision, name)

    def _init_state(self, p):
        return {"moment1": jnp.zeros(p._data.shape, jnp.float32),
                "moment2": jnp.zeros(p._data.shape, jnp.float32)}

    def _hyperparams(self):
        return {"weight_decay": self._weight_decay, "b1": self._beta1,
                "b2": self._beta2, "eps": self._epsilon}

    @staticmethod
    def _update(param, grad, state, lr, step, hp):
        b1, b2, eps, wd = hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"]
        m1 = b1 * state["moment1"] + (1 - b1) * grad
        m2 = b2 * state["moment2"] + (1 - b2) * grad * grad
        stepf = step.astype(jnp.float32)
        m_hat = m1 / (1 - b1 ** stepf)
        v_hat = m2 / (1 - b2 ** stepf)
        r = m_hat / (jnp.sqrt(v_hat) + eps) + wd * param
        w_norm = jnp.sqrt(jnp.sum(param * param))
        r_norm = jnp.sqrt(jnp.sum(r * r))
        trust = jnp.where((w_norm > 0) & (r_norm > 0), w_norm / r_norm, 1.0)
        return param - lr * trust * r, {"moment1": m1, "moment2": m2}


class NAdam(Optimizer):
    """Nesterov Adam (reference: paddle.optimizer.NAdam / torch NAdam
    with momentum_decay)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, momentum_decay=0.004, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._psi = momentum_decay
        super().__init__(learning_rate, parameters, weight_decay,
                         grad_clip, False, name)

    def _init_state(self, p):
        return {"moment1": jnp.zeros(p._data.shape, jnp.float32),
                "moment2": jnp.zeros(p._data.shape, jnp.float32),
                "mu_product": jnp.ones((), jnp.float32)}

    def _hyperparams(self):
        return {"weight_decay": self._weight_decay, "b1": self._beta1,
                "b2": self._beta2, "eps": self._epsilon,
                "psi": self._psi}

    @staticmethod
    def _update(param, grad, state, lr, step, hp):
        b1, b2, eps, psi = hp["b1"], hp["b2"], hp["eps"], hp["psi"]
        wd = hp["weight_decay"]
        if wd:
            grad = grad + wd * param
        t = step.astype(jnp.float32)
        mu_t = b1 * (1 - 0.5 * 0.96 ** (t * psi))
        mu_t1 = b1 * (1 - 0.5 * 0.96 ** ((t + 1) * psi))
        mu_prod = state["mu_product"] * mu_t
        m1 = b1 * state["moment1"] + (1 - b1) * grad
        m2 = b2 * state["moment2"] + (1 - b2) * grad * grad
        m_hat = (mu_t1 * m1 / (1 - mu_prod * mu_t1) +
                 (1 - mu_t) * grad / (1 - mu_prod))
        v_hat = m2 / (1 - b2 ** t)
        new = param - lr * m_hat / (jnp.sqrt(v_hat) + eps)
        return new, {"moment1": m1, "moment2": m2, "mu_product": mu_prod}


class RAdam(Optimizer):
    """Rectified Adam (reference: paddle.optimizer.RAdam)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999,
                 epsilon=1e-8, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        super().__init__(learning_rate, parameters, weight_decay,
                         grad_clip, False, name)

    def _init_state(self, p):
        return {"moment1": jnp.zeros(p._data.shape, jnp.float32),
                "moment2": jnp.zeros(p._data.shape, jnp.float32)}

    def _hyperparams(self):
        return {"weight_decay": self._weight_decay, "b1": self._beta1,
                "b2": self._beta2, "eps": self._epsilon}

    @staticmethod
    def _update(param, grad, state, lr, step, hp):
        b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
        wd = hp["weight_decay"]
        if wd:
            grad = grad + wd * param
        t = step.astype(jnp.float32)
        m1 = b1 * state["moment1"] + (1 - b1) * grad
        m2 = b2 * state["moment2"] + (1 - b2) * grad * grad
        m_hat = m1 / (1 - b1 ** t)
        rho_inf = 2.0 / (1 - b2) - 1.0
        rho_t = rho_inf - 2.0 * t * (b2 ** t) / (1 - b2 ** t)
        # variance rectification (SMA length > 4), else unadapted step
        r_num = (rho_t - 4) * (rho_t - 2) * rho_inf
        r_den = (rho_inf - 4) * (rho_inf - 2) * rho_t
        rect = jnp.sqrt(jnp.maximum(r_num / jnp.maximum(r_den, 1e-30),
                                    0.0))
        # reference (and torch) convention: eps on sqrt(m2) BEFORE the
        # bias-correction scale; rho threshold 5
        adaptive = rect * jnp.sqrt(1 - b2 ** t) / (jnp.sqrt(m2) + eps)
        adapted = param - lr * m_hat * adaptive
        plain = param - lr * m_hat
        new = jnp.where(rho_t > 5.0, adapted, plain)
        return new, {"moment1": m1, "moment2": m2}


class Rprop(Optimizer):
    """Resilient backprop (reference: paddle.optimizer.Rprop) — per-
    element step sizes grown/shrunk by gradient-sign agreement; batch
    training only in spirit but the rule is faithful."""

    def __init__(self, learning_rate=0.01, learning_rate_range=(1e-5, 50),
                 parameters=None, etas=(0.5, 1.2), grad_clip=None,
                 name=None):
        self._eta_minus, self._eta_plus = etas
        self._lr_min, self._lr_max = learning_rate_range
        self._lr0 = learning_rate
        super().__init__(learning_rate, parameters, None, grad_clip,
                         False, name)

    def _init_state(self, p):
        return {"prev_grad": jnp.zeros(p._data.shape, jnp.float32),
                "step_size": jnp.full(p._data.shape, self._lr0,
                                      jnp.float32)}

    def _hyperparams(self):
        return {"weight_decay": 0.0, "em": self._eta_minus,
                "ep": self._eta_plus, "lo": self._lr_min,
                "hi": self._lr_max}

    @staticmethod
    def _update(param, grad, state, lr, step, hp):
        em, ep, lo, hi = hp["em"], hp["ep"], hp["lo"], hp["hi"]
        sign = jnp.sign(grad * state["prev_grad"])
        size = jnp.where(sign > 0, state["step_size"] * ep,
                         jnp.where(sign < 0, state["step_size"] * em,
                                   state["step_size"]))
        size = jnp.clip(size, lo, hi)
        # on sign change: no move, zero the stored grad (classic Rprop-)
        eff_grad = jnp.where(sign < 0, 0.0, grad)
        new = param - jnp.sign(eff_grad) * size
        return new, {"prev_grad": eff_grad, "step_size": size}


class ASGD(Optimizer):
    """Averaged SGD (reference: paddle.optimizer.ASGD): SGD steps plus a
    running polyak average of the iterates held in state['averaged']
    (fetch via state_dict or the `averaged_parameters` helper)."""

    def __init__(self, learning_rate=0.001, batch_num=1, parameters=None,
                 weight_decay=None, grad_clip=None, multi_precision=False,
                 name=None):
        super().__init__(learning_rate, parameters, weight_decay,
                         grad_clip, multi_precision, name)

    def _init_state(self, p):
        return {"averaged": p._data.astype(jnp.float32)}

    def _hyperparams(self):
        return {"weight_decay": self._weight_decay}

    @staticmethod
    def _update(param, grad, state, lr, step, hp):
        wd = hp["weight_decay"]
        if wd:
            grad = grad + wd * param
        new = param - lr * grad
        t = step.astype(jnp.float32)
        avg = state["averaged"] + (new - state["averaged"]) / t
        return new, {"averaged": avg}

    def averaged_parameters(self):
        return [self._accum[id(p)]["averaged"]
                for p in self._all_params() if id(p) in self._accum]


class LBFGS(Optimizer):
    """L-BFGS with closure API (reference: paddle.optimizer.LBFGS).

    TPU-native scope: two-loop recursion over a `history_size` window
    with a backtracking (Armijo) line search — the closure is
    re-evaluated on device per probe. Deterministic full-batch use, as
    upstream documents."""

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-7, tolerance_change=1e-9,
                 history_size=100, line_search_fn=None, parameters=None,
                 weight_decay=None, grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay,
                         grad_clip, False, name)
        self._max_iter = max_iter
        self._tol_g = tolerance_grad
        self._tol_x = tolerance_change
        self._hist = history_size
        self._s, self._y = [], []
        self._prev_flat = None
        self._prev_grad = None

    def _flat(self, arrs):
        return jnp.concatenate([a.reshape(-1).astype(jnp.float32)
                                for a in arrs])

    def _unflat(self, flat):
        out, off = [], 0
        for p in self._all_params():
            n = p._data.size
            out.append(flat[off:off + n].reshape(p._data.shape
                                                 ).astype(p._data.dtype))
            off += n
        return out

    def _set_params(self, flat):
        for p, arr in zip(self._all_params(), self._unflat(flat)):
            p._inplace_update(arr)

    @no_grad()
    def step(self, closure):
        import jax as _jax

        def eval_closure():
            for p in self._all_params():
                p.clear_grad()
            with _enable_grad_ctx():
                loss = closure()
            g = self._flat([(p.grad._data if p.grad is not None else
                             jnp.zeros_like(p._data))
                            for p in self._all_params()])
            return float(loss), g

        x = self._flat([p._data for p in self._all_params()])
        loss, g = eval_closure()
        for _ in range(self._max_iter):
            if float(jnp.max(jnp.abs(g))) < self._tol_g:
                break
            # two-loop recursion
            q = g
            alphas = []
            for s, y in reversed(list(zip(self._s, self._y))):
                rho = 1.0 / jnp.maximum(jnp.dot(y, s), 1e-10)
                a = rho * jnp.dot(s, q)
                alphas.append((a, rho, s, y))
                q = q - a * y
            if self._y:
                y_last, s_last = self._y[-1], self._s[-1]
                gamma = jnp.dot(s_last, y_last) / jnp.maximum(
                    jnp.dot(y_last, y_last), 1e-10)
                q = q * gamma
            for a, rho, s, y in reversed(alphas):
                b = rho * jnp.dot(y, q)
                q = q + s * (a - b)
            d = -q
            # line search: Armijo backtracking, then a Wolfe-style
            # curvature EXPANSION (double t while |g_newᵀd| > 0.9|gᵀd|
            # and Armijo still holds). Armijo alone accepts too-short
            # steps whose (s, y) pairs carry poor curvature information
            # and L-BFGS crawls (Rosenbrock stalls); with the expansion
            # it converges in ~35 iterations.
            t = float(self.get_lr())
            gtd = float(jnp.dot(g, d))
            ok = False
            best = None  # (t, loss, g) of the best simple-decrease probe
            for _bt in range(25):
                self._set_params(x + t * d)
                new_loss, new_g = eval_closure()
                if new_loss <= loss + 1e-4 * t * gtd:
                    ok = True
                    break
                if new_loss < loss and (best is None or
                                        new_loss < best[1]):
                    best = (t, new_loss, new_g)
                t *= 0.5
            if not ok:
                if best is None:
                    self._set_params(x)
                    if self._s:
                        # the quasi-Newton model produced a non-descent
                        # direction (ill-conditioned curvature pair) —
                        # drop the history and retry as steepest descent
                        self._s.clear()
                        self._y.clear()
                        continue
                    break
                t, new_loss, new_g = best
                self._set_params(x + t * d)
            else:
                for _ex in range(10):
                    if abs(float(jnp.dot(new_g, d))) <= 0.9 * abs(gtd):
                        break
                    t2 = t * 2.0
                    self._set_params(x + t2 * d)
                    l2, g2 = eval_closure()
                    if l2 <= loss + 1e-4 * t2 * gtd:
                        t, new_loss, new_g = t2, l2, g2
                    else:
                        self._set_params(x + t * d)
                        break
            x_new = x + t * d
            s_vec = x_new - x
            y_vec = new_g - g
            if float(jnp.dot(s_vec, y_vec)) > 1e-10:
                self._s.append(s_vec)
                self._y.append(y_vec)
                if len(self._s) > self._hist:
                    self._s.pop(0)
                    self._y.pop(0)
            if float(jnp.max(jnp.abs(s_vec))) < self._tol_x:
                x, loss, g = x_new, new_loss, new_g
                break
            x, loss, g = x_new, new_loss, new_g
        self._set_params(x)
        return loss

