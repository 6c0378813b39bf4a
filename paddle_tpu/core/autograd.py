"""Eager reverse-mode autograd engine.

Reference parity: the dygraph engine — AutogradMeta/GradNodeBase/
egr::Backward/GradTensorHolder (upstream paddle/fluid/eager/ — unverified,
see SURVEY.md §2.1, §3.1). TPU-native design: instead of hand-written
per-op GradNodes, every differentiable op is executed through `jax.vjp`,
which runs the forward *and* captures a pullback closure holding exactly
the residuals JAX's AD rules need. The graph is a DAG of `TapeNode`s hung
off output tensors; `backward()` does an iterative topological sweep,
calling each pullback and accumulating cotangents (the GradTensorHolder
role). Everything in here is pure Python over jax ops, so the same engine
works unchanged under `jax.jit` tracing — that is what makes `to_static`
a thin wrapper rather than a second execution engine.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading
import weakref

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# grad-enabled state — THREAD-LOCAL (round 11). The serving tier runs
# several engine loop threads concurrently, each wrapping its step in
# no_grad; with a process-global flag, interleaved __enter__/__exit__
# across threads could restore a False saved by ANOTHER thread and
# leave grad mode off for the whole process (the round-11 tier-1
# incident: every later backward() raised "does not require grad").
# Each thread now owns its mode, defaulting to enabled.

_grad_state = threading.local()


def is_grad_enabled() -> bool:
    return getattr(_grad_state, "enabled", True)


def set_grad_enabled(mode: bool):
    _grad_state.enabled = bool(mode)


class no_grad(contextlib.ContextDecorator):
    """paddle.no_grad — usable as context manager or decorator."""

    def __enter__(self):
        self._prev = is_grad_enabled()
        set_grad_enabled(False)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False


class enable_grad(contextlib.ContextDecorator):
    def __enter__(self):
        self._prev = is_grad_enabled()
        set_grad_enabled(True)
        return self

    def __exit__(self, *exc):
        set_grad_enabled(self._prev)
        return False


# ---------------------------------------------------------------------------
# saved_tensors_hooks (reference: paddle.autograd.saved_tensors_hooks,
# upstream python/paddle/autograd/saved_tensors_hooks.py — unverified,
# SURVEY.md blocker notice).
#
# TPU-native realization: the eager tape's backward is remat-based — what
# it saves per op is the op's INPUT tensors, so those are the "saved
# tensors" the hooks see. While a context is active, every recorded node
# stores pack(input) instead of relying on the live arrays, and backward
# re-derives the pullback from unpack(packed). A pack that offloads to
# host (np.asarray) or requantizes therefore genuinely changes what
# backward reads. Under jit/compiled steppers, XLA rematerialization
# (jax.checkpoint policies, fleet recompute) owns residual memory — the
# hooks are an eager-mode feature there, as in the reference. PyLayer's
# explicitly saved tensors are not intercepted (documented deviation).

_SAVED_HOOKS: list = []


def _unpack_value(x):
    """Normalize an unpack-hook result (Tensor | array-like) to an array."""
    from .tensor import Tensor
    return x._data if isinstance(x, Tensor) else x


class saved_tensors_hooks:
    """Context manager: pack_hook(tensor) runs when the tape saves a
    tensor for backward; unpack_hook(packed) runs when backward needs it.
    """

    def __init__(self, pack_hook, unpack_hook):
        self.pack_hook = pack_hook
        self.unpack_hook = unpack_hook

    def __enter__(self):
        _SAVED_HOOKS.append((self.pack_hook, self.unpack_hook))
        return self

    def __exit__(self, *exc):
        _SAVED_HOOKS.pop()
        return False


# ---------------------------------------------------------------------------
# Tape nodes

class TapeNode:
    """One recorded differentiable op: inputs + vjp pullback + output slots."""

    __slots__ = ("inputs", "in_versions", "vjp_fn", "multi_out", "out_refs",
                 "out_info", "name", "fn", "tensor_vjp", "packed", "unpack",
                 "__weakref__")

    def __init__(self, inputs, vjp_fn, multi_out, name="", fn=None):
        self.inputs = tuple(inputs)          # strong refs keep the graph alive
        self.in_versions = tuple(t._version for t in inputs)
        self.vjp_fn = vjp_fn
        self.multi_out = multi_out
        self.out_refs: list = []             # weakrefs to output Tensors
        self.out_info: list = []             # (shape, dtype) per output
        self.name = name
        self.fn = fn          # forward fn, kept for create_graph re-trace
        self.tensor_vjp = None  # PyLayer: Tensor-level backward (create_graph)
        self.packed = None    # saved_tensors_hooks: packed input values
        self.unpack = None    # ... and the matching unpack hook

    def add_output(self, tensor):
        self.out_refs.append(weakref.ref(tensor))
        self.out_info.append((tensor._data.shape, tensor._data.dtype))

    def release(self):
        self.vjp_fn = None
        self.inputs = ()
        self.fn = None
        self.tensor_vjp = None
        self.packed = None
        self.unpack = None


def _check_versions(node: TapeNode):
    for t, v in zip(node.inputs, node.in_versions):
        if t._version != v:
            raise RuntimeError(
                f"one of the tensors needed for gradient computation "
                f"(shape={list(t._data.shape)}) was modified in place "
                f"(version {t._version}, expected {v}). Clone it before the "
                f"in-place op, or avoid the in-place op.")


# ---------------------------------------------------------------------------
# Micro-jit dispatch (SURVEY.md §7 hard-part 1: eager per-op overhead).
#
# The naive eager path re-traces `jax.vjp(fn, ...)` through Python on
# EVERY op call (~hundreds of µs). When `fn` has a stable identity
# (module-level op, cached scalar closure), we instead dispatch through
# two jits cached by (fn, abstract args):
#   fwd:  jit(fn)                      — one cached XLA program
#   bwd:  jit(vjp(fn)∘pullback)        — re-derives the pullback INSIDE
#         the jit from the saved inputs (rematerialization: trades a
#         recompute for not holding residuals), cached the same way.
# Steady-state Python cost per op drops to two cached-jit dispatches.
# Unstable fns (per-call lambdas) keep the legacy vjp path — a jit cache
# keyed on a fresh lambda would never hit and leak entries.

_MICROJIT = os.environ.get("PADDLE_TPU_EAGER_MICROJIT", "1") != "0"


@functools.partial(jax.jit, static_argnums=0)
def _mj_fwd(fn, args):
    return fn(*args)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _mj_bwd(fn, args, multi, cots):
    _, vjp_fn = jax.vjp(fn, *args)
    return vjp_fn(tuple(cots) if multi else cots[0])


def _is_stable(fn) -> bool:
    if getattr(fn, "_pt_stable", False):
        return True
    if getattr(fn, "__closure__", None) is not None:
        return False
    # a def nested in a function is a new object on every call of the
    # outer one, whether or not it closes over anything
    qualname = getattr(fn, "__qualname__", "<lambda>")
    return "<lambda>" not in qualname and "<locals>" not in qualname


def mark_stable(fn):
    """Tag fn as identity-stable so apply() may micro-jit it."""
    try:
        fn._pt_stable = True
    except (AttributeError, TypeError):
        pass
    return fn


# ---------------------------------------------------------------------------
# The op applicator — every differentiable op goes through here.

# Static-graph recorder (paddle_tpu.static): when a Program is active,
# every apply() additionally appends (fn, inputs, outputs) to it so
# Executor.run can replay the op DAG as a pure jitted function of the
# feeds. None in the common case — a single attribute load per op.
_STATIC_RECORDER = None


def _set_static_recorder(rec):
    global _STATIC_RECORDER
    prev = _STATIC_RECORDER
    _STATIC_RECORDER = rec
    return prev


def apply(fn, *tensors, name: str = ""):
    """Run `fn(*arrays)` eagerly; record a TapeNode if grad is required.

    `fn` must be a pure function of the positional arrays (close over any
    static arguments). Returns Tensor or tuple of Tensors mirroring fn's
    output structure.
    """
    from .tensor import Tensor

    arrs = tuple(t._data for t in tensors)
    traced = any(isinstance(a, jax.core.Tracer) for a in arrs)
    microjit = _MICROJIT and _is_stable(fn) and not traced
    needs_grad = is_grad_enabled() and any(not t.stop_gradient for t in tensors)
    if needs_grad and traced:
        # An OUTER jax transform owns differentiation here — either an
        # enclosing AD transform (the compiled steppers' value_and_grad,
        # detected by JVP/linearize tracers) or ANY enclosing trace
        # (jit / to_static / jax.checkpoint body staging, detected by
        # plain tracers: if grads are wanted for traced values, a jax
        # transform outside the trace will derive them). Eagerly calling
        # jax.vjp at tracers would be a second-order linearization that
        # (a) cannot see custom_vjp rules from inside the replayed jaxpr,
        # silently knocking Pallas kernels down to their XLA fallback —
        # inside a jax.checkpoint body this plants a bare pallas_call in
        # the remat jaxpr, which crashes the outer AD's jvp replay —
        # and (b) bloats the traced program. Run fn plainly — the outer
        # AD differentiates it with every custom_vjp rule intact — but
        # keep a LAZY tape node (fn only), so an inner
        # paddle.grad/backward inside the traced loss (gradient
        # penalties) still works via the lazy-vjp path.
        out = fn(*arrs)
        node = TapeNode(tensors, None, isinstance(out, (tuple, list)),
                        name=name, fn=fn)
        if node.multi_out:
            res = tuple(Tensor(o, stop_gradient=False, _node=node)
                        for o in out)
            for t in res:
                node.add_output(t)
            if _STATIC_RECORDER is not None:
                _STATIC_RECORDER.record(fn, tensors, res, name)
            return res
        t = Tensor(out, stop_gradient=False, _node=node)
        node.add_output(t)
        if _STATIC_RECORDER is not None:
            _STATIC_RECORDER.record(fn, tensors, (t,), name)
        return t
    if needs_grad:
        if _SAVED_HOOKS:
            # saved_tensors_hooks active: the values the tape saves for
            # backward go through pack NOW; backward re-derives the
            # pullback (remat) from unpack's results, so a lossy pack
            # (offload, quantize) genuinely feeds the gradients. Eager
            # jax.vjp is skipped — its residuals live inside the closure
            # where hooks can't reach.
            pack, unpack = _SAVED_HOOKS[-1]
            out = fn(*arrs)
            node = TapeNode(tensors, None, isinstance(out, (tuple, list)),
                            name=name, fn=fn)
            node.packed = tuple(pack(t) for t in tensors)
            node.unpack = unpack
            # Device-memory relief — the point of an offload pack: once an
            # INTERMEDIATE input (produced by the tape, not a leaf/param)
            # is packed TO HOST, swap its live device array for a host
            # copy. Only when the pack result is itself a host ndarray —
            # identity/logging/requantize packs keep device arrays in
            # place (no forced sync per recorded op — ADVICE r3 #1).
            # numpy is a transparent stand-in (jnp ops re-upload on use);
            # no version bump — this is not a user-visible value change.
            import numpy as _np
            for t, p in zip(tensors, node.packed):
                if t._node is not None and isinstance(p, _np.ndarray) \
                        and not isinstance(t._data, _np.ndarray):
                    # copy the LIVE value off-device — never substitute
                    # the pack result itself: a lossy same-shape pack
                    # (fp16 roundtrip) must feed only the backward
                    # re-derivation, not the forward-visible value
                    t._data = _np.asarray(t._data)
        elif microjit:
            # lazy backward: the pullback is derived inside a cached jit
            # at backward time (see _mj_bwd) — vjp_fn stays None
            out = _mj_fwd(fn, arrs)
            node = TapeNode(tensors, None,
                            isinstance(out, (tuple, list)), name=name,
                            fn=fn)
        else:
            out, vjp_fn = jax.vjp(fn, *arrs)
            node = TapeNode(tensors, vjp_fn,
                            isinstance(out, (tuple, list)), name=name,
                            fn=fn)
        if node.multi_out:
            res = tuple(Tensor(o, stop_gradient=False, _node=node) for o in out)
            for t in res:
                node.add_output(t)
            if _STATIC_RECORDER is not None:
                _STATIC_RECORDER.record(fn, tensors, res, name)
            return res
        t = Tensor(out, stop_gradient=False, _node=node)
        node.add_output(t)
        if _STATIC_RECORDER is not None:
            _STATIC_RECORDER.record(fn, tensors, (t,), name)
        return t
    out = _mj_fwd(fn, arrs) if microjit else fn(*arrs)
    if isinstance(out, (tuple, list)):
        res = tuple(Tensor(o) for o in out)
        if _STATIC_RECORDER is not None:
            _STATIC_RECORDER.record(fn, tensors, res, name)
        return res
    t = Tensor(out)
    if _STATIC_RECORDER is not None:
        _STATIC_RECORDER.record(fn, tensors, (t,), name)
    return t


# ---------------------------------------------------------------------------
# Backward engine

def _topo_order(roots):
    """Iterative post-order over the node DAG; returns nodes forward-ordered."""
    order, state = [], {}
    stack = [(n, False) for n in roots if n is not None]
    seen_root = set()
    stack = []
    for n in roots:
        if n is not None and id(n) not in seen_root:
            seen_root.add(id(n))
            stack.append((n, False))
    while stack:
        node, expanded = stack.pop()
        if expanded:
            order.append(node)
            continue
        st = state.get(id(node))
        if st is not None:
            continue
        state[id(node)] = 1
        stack.append((node, True))
        for t in node.inputs:
            child = t._node
            if child is not None and id(child) not in state:
                stack.append((child, False))
    return order


def _accumulate(dst: dict, key, g):
    if key in dst:
        dst[key] = dst[key] + g
    else:
        dst[key] = g


def _make_pullback(node: TapeNode):
    """A pure array function computing node's vjp FROM SCRATCH: re-traces
    jax.vjp(fn, *inputs) so the input-dependence of the residuals is
    differentiable — the requirement for create_graph (double backward)."""
    n_in = len(node.inputs)
    fwd = node.fn
    multi = node.multi_out

    def pullback(*args):
        ins, cots = args[:n_in], args[n_in:]
        _, vjp_fn = jax.vjp(fwd, *ins)
        return vjp_fn(tuple(cots) if multi else cots[0])

    return pullback


def run_backward(tensors, grad_tensors=None, retain_graph=False,
                 sinks=None, accumulate_into_grad=True, create_graph=False):
    """Core engine. `sinks`: optional list of Tensors whose cotangents should
    be collected and returned (paddle.grad); when given with
    accumulate_into_grad=False, .grad fields are untouched.

    create_graph=True runs every pullback through `apply()` — the vjp is
    re-traced as a function of (inputs, cotangents), so the backward pass
    itself lands on the tape and is differentiable (double backward,
    reference: paddle.grad(create_graph=True), SURVEY.md §2.2 Autograd).
    Cotangents are then Tensors and accumulate via tape-recorded adds.
    """
    from .tensor import Tensor

    if create_graph:
        retain_graph = True  # residual re-trace needs the graph intact

    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)

    grads: dict[int, object] = {}     # id(Tensor) -> cotangent (array|Tensor)
    alive: dict[int, object] = {}     # id -> Tensor, pins ids
    sink_ids = {id(t) for t in (sinks or [])}
    sink_grads: dict[int, object] = {}

    def deposit(t, g):
        if t.stop_gradient:
            return
        garr = g._data if isinstance(g, Tensor) else g
        if getattr(garr, "dtype", None) == jax.dtypes.float0:
            return  # non-differentiable (integer/key) input
        for hook in t._hooks:
            out = hook(g if isinstance(g, Tensor) else Tensor(g))
            if out is not None:
                g = out if create_graph else \
                    (out._data if isinstance(out, Tensor) else out)
        if id(t) in sink_ids:
            _accumulate(sink_grads, id(t), g)
        if accumulate_into_grad and (t._node is None or t._retain_grads):
            if create_graph:
                t.grad = g if t.grad is None else t.grad + g
            else:
                t.grad = Tensor(g) if t.grad is None \
                    else Tensor(t.grad._data + g)
        if t._node is not None:
            _accumulate(grads, id(t), g)
            alive[id(t)] = t

    for t, g in zip(tensors, grad_tensors):
        if t.stop_gradient and t._node is None:
            raise RuntimeError("backward() called on a tensor that does not "
                               "require grad (stop_gradient=True, no graph).")
        if g is None:
            seed = jnp.ones(t._data.shape, t._data.dtype)
            seed = Tensor(seed) if create_graph else seed
        elif create_graph:
            seed = g if isinstance(g, Tensor) else Tensor(jnp.asarray(g))
        else:
            seed = g._data if isinstance(g, Tensor) else jnp.asarray(g)
        deposit(t, seed)

    order = _topo_order([t._node for t in tensors])

    for node in reversed(order):
        if node.vjp_fn is None and node.tensor_vjp is None and \
                node.fn is None:
            raise RuntimeError(
                "Trying to backward through the graph a second time, but the "
                "saved intermediate results have already been freed. Pass "
                "retain_graph=True to backward() the first time.")
        cotangents, any_grad = [], False
        for ref, (shape, dtype) in zip(node.out_refs, node.out_info):
            t = ref()
            g = grads.pop(id(t), None) if t is not None else None
            if g is None:
                g = jnp.zeros(shape, dtype)
                if create_graph:
                    g = Tensor(g)
            else:
                any_grad = True
            cotangents.append(g)
        if not any_grad:
            continue
        _check_versions(node)
        if create_graph:
            cot_ts = [c if isinstance(c, Tensor) else Tensor(c)
                      for c in cotangents]
            if node.fn is not None:
                ins = node.inputs
                if node.packed is not None:
                    # hooks + create_graph: re-trace from the unpacked
                    # values as fresh leaves (grad-of-grad w.r.t. the
                    # originals is cut by packing — documented)
                    ins = tuple(Tensor(_unpack_value(node.unpack(p)))
                                for p in node.packed)
                in_grads = apply(_make_pullback(node), *ins, *cot_ts,
                                 name=f"vjp[{node.name}]")
                if not isinstance(in_grads, tuple):
                    in_grads = (in_grads,)
            elif node.tensor_vjp is not None:
                in_grads = node.tensor_vjp(cot_ts)
            else:
                raise RuntimeError(
                    f"node '{node.name}' does not support create_graph "
                    "(no re-traceable forward)")
        elif node.vjp_fn is not None:
            in_grads = node.vjp_fn(tuple(cotangents) if node.multi_out
                                   else cotangents[0])
        else:
            # micro-jit lazy backward: cached jit re-derives the pullback
            # from the saved inputs (remat — no residuals were kept).
            # saved_tensors_hooks: the saved values are the UNPACKED
            # packs, so offloaded/requantized data is what backward sees.
            if node.packed is not None:
                arrs = tuple(_unpack_value(node.unpack(p))
                             for p in node.packed)
                if _is_stable(node.fn):
                    in_grads = _mj_bwd(node.fn, arrs,
                                       node.multi_out, tuple(cotangents))
                else:
                    # per-call lambdas would never hit the fn-keyed jit
                    # cache (one fresh XLA program per op per step — the
                    # micro-jit comment's exact hazard); eager vjp instead
                    _, vjp_fn = jax.vjp(node.fn, *arrs)
                    in_grads = vjp_fn(tuple(cotangents) if node.multi_out
                                      else cotangents[0])
            else:
                arrs = tuple(t._data for t in node.inputs)
                in_grads = _mj_bwd(node.fn, arrs,
                                   node.multi_out, tuple(cotangents))
        for t, g in zip(node.inputs, in_grads):
            if g is not None:
                deposit(t, g)
        if not retain_graph:
            node.release()

    return sink_grads


def backward(tensors, grad_tensors=None, retain_graph=False):
    """paddle.autograd.backward"""
    if not isinstance(tensors, (list, tuple)):
        tensors = [tensors]
    if grad_tensors is not None and not isinstance(grad_tensors, (list, tuple)):
        grad_tensors = [grad_tensors]
    run_backward(list(tensors), grad_tensors, retain_graph)


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False):
    """paddle.grad — functional gradients without touching .grad.

    create_graph=True records the backward pass on the tape so the result
    is itself differentiable (double backward / jacobian / hessian).
    """
    from .tensor import Tensor

    outputs = list(outputs) if isinstance(outputs, (list, tuple)) else [outputs]
    inputs = list(inputs) if isinstance(inputs, (list, tuple)) else [inputs]
    if retain_graph is None:
        retain_graph = False
    sink_grads = run_backward(outputs, grad_outputs, retain_graph=retain_graph,
                              sinks=inputs, accumulate_into_grad=False,
                              create_graph=create_graph)
    result = []
    for t in inputs:
        g = sink_grads.get(id(t))
        if g is None:
            if not allow_unused:
                raise RuntimeError(
                    "One of the differentiated tensors appears to not have "
                    "been used in the graph. Set allow_unused=True if this "
                    "is intended.")
            result.append(None)
        else:
            result.append(g if isinstance(g, Tensor) else Tensor(g))
    return result


# ---------------------------------------------------------------------------
# PyLayer — user-defined forward/backward (reference: paddle.autograd.PyLayer)

class PyLayerContext:
    def __init__(self):
        self._saved = ()
        self.__dict__["_attrs"] = {}

    def save_for_backward(self, *tensors):
        self._saved = tensors

    @property
    def saved_tensor(self):
        return self._saved

    def saved_tensors(self):
        return self._saved


class PyLayerMeta(type):
    pass


class PyLayer(metaclass=PyLayerMeta):
    """Subclass with @staticmethod forward(ctx, *args) / backward(ctx, *grads)."""

    @classmethod
    def apply(cls, *args, **kwargs):
        from .tensor import Tensor

        ctx = PyLayerContext()
        with no_grad():
            outs = cls.forward(ctx, *args, **kwargs)
        multi = isinstance(outs, (tuple, list))
        out_list = list(outs) if multi else [outs]

        tensor_inputs = [a for a in args if isinstance(a, Tensor)]
        needs_grad = is_grad_enabled() and any(
            not t.stop_gradient for t in tensor_inputs)
        if not needs_grad:
            return outs

        def vjp_fn(cots):
            cot_list = list(cots) if multi else [cots]
            with no_grad():
                gin = cls.backward(ctx, *[Tensor(c) for c in cot_list])
            gin = list(gin) if isinstance(gin, (tuple, list)) else [gin]
            out = []
            it = iter(gin)
            for a in args:
                if isinstance(a, Tensor):
                    g = next(it, None)
                    out.append(None if g is None else
                               (g._data if isinstance(g, Tensor) else g))
            return out

        def tensor_vjp(cot_tensors):
            """create_graph path: run the user backward with grad ENABLED on
            Tensor cotangents so a differentiable backward lands on the tape
            (reference: PyLayer double backward when backward() is composed
            of differentiable ops)."""
            gin = cls.backward(ctx, *(cot_tensors if multi
                                      else [cot_tensors[0]]))
            gin = list(gin) if isinstance(gin, (tuple, list)) else [gin]
            out, it = [], iter(gin)
            for a in args:
                if isinstance(a, Tensor):
                    out.append(next(it, None))
            return out

        node = TapeNode(tensor_inputs, vjp_fn, multi, name=cls.__name__)
        node.tensor_vjp = tensor_vjp
        results = []
        for o in out_list:
            t = o if isinstance(o, Tensor) else Tensor(o)
            res = Tensor(t._data, stop_gradient=False, _node=node)
            node.add_output(res)
            results.append(res)
        return tuple(results) if multi else results[0]
