"""to_static implementation (see paddle_tpu.jit docstring for the design)."""
from __future__ import annotations

import functools

import numpy as _np

import jax
import jax.numpy as jnp

from ..core import random as _random
from ..core.autograd import apply, is_grad_enabled, mark_stable
from ..core.device import committed
from ..core.tensor import GraphBreakError as _GraphBreakError
from ..core.tensor import Tensor
from ..nn.layer import Layer

_NOT_TO_STATIC = set()

# live StaticFunction instances for jit.graph_break_report(); weak so a
# dropped function's diagnostics die with it
import weakref as _weakref

_LIVE_STATIC_FNS: "_weakref.WeakSet" = _weakref.WeakSet()


def not_to_static(fn):
    """Mark a function to always run eagerly (reference parity shim)."""
    _NOT_TO_STATIC.add(fn)
    return fn


def ignore_module(modules):
    pass  # all Python is traceable or falls back; nothing to ignore


def _tree_flatten_tensors(obj):
    """Flatten nested (list/tuple/dict) of Tensors + statics.

    Returns (tensor_list, rebuild(tensors)->obj, static_signature).
    """
    tensors = []
    statics = []

    def walk(o):
        if isinstance(o, Tensor):
            idx = len(tensors)
            tensors.append(o)
            return ("T", idx)
        if isinstance(o, (jax.Array, jax.core.Tracer, _np.ndarray)):
            # raw arrays (promoted dy2static loop carries, numpy args)
            # must ride the traced path, never the static signature — a
            # tracer buried in a static would leak out of the jit, and a
            # large numpy array keyed by its summarized repr() would
            # alias distinct values onto one stale compiled constant
            idx = len(tensors)
            tensors.append(Tensor(jnp.asarray(o), stop_gradient=True))
            return ("T", idx)
        if isinstance(o, (list, tuple)):
            return (type(o).__name__, [walk(x) for x in o])
        if isinstance(o, dict):
            return ("dict", {k: walk(v) for k, v in sorted(o.items())})
        statics.append(o)
        return ("S", o)

    spec = walk(obj)

    def rebuild(arrs, sp=spec):
        def un(s):
            tag = s[0]
            if tag == "T":
                return arrs[s[1]]
            if tag == "S":
                return s[1]
            if tag == "dict":
                return {k: un(v) for k, v in s[1].items()}
            seq = [un(x) for x in s[1]]
            return tuple(seq) if tag == "tuple" else seq
        return un(sp)

    def sig(s):
        tag = s[0]
        if tag == "T":
            return ("T",)
        if tag == "S":
            v = s[1]
            return ("S", v if isinstance(v, (int, float, str, bool,
                                             type(None))) else repr(v))
        if tag == "dict":
            return ("dict", tuple((k, sig(v)) for k, v in s[1].items()))
        return (tag, tuple(sig(x) for x in s[1]))

    return tensors, rebuild, sig(spec)


def _discover_layers(fn, args, kwargs, extra):
    layers = []
    seen = set()

    def add(l):
        if id(l) not in seen:
            seen.add(id(l))
            layers.append(l)

    self_obj = getattr(fn, "__self__", None)
    if isinstance(self_obj, Layer):
        add(self_obj)
    for a in list(args) + list(kwargs.values()) + list(extra):
        if isinstance(a, Layer):
            add(a)
    # closure scan: layers referenced by the function body
    closure = getattr(fn, "__closure__", None)
    if closure:
        for cell in closure:
            try:
                v = cell.cell_contents
            except ValueError:
                continue
            if isinstance(v, Layer):
                add(v)
    g = getattr(fn, "__globals__", None)
    names = getattr(getattr(fn, "__code__", None), "co_names", ())
    if g:
        for n in names:
            v = g.get(n)
            if isinstance(v, Layer):
                add(v)
    return layers


_TO_STATIC_ENABLED = True  # paddle.jit.enable_to_static toggle


class StaticFunction:
    """The compiled callable returned by to_static."""

    def __init__(self, fn, build_strategy=None, backend=None,
                 full_graph=False, layers=None):
        self._fn = fn
        self._layers = list(layers) if layers else None
        self._jit_cache = {}
        self._fallback_warned = False
        self._traced_fn = None       # dy2static-transformed fn (lazy)
        self._transform_note = None
        self.graph_break_reasons = []
        functools.update_wrapper(self, fn,
                                 assigned=("__name__", "__doc__",
                                           "__qualname__"), updated=())
        _LIVE_STATIC_FNS.add(self)

    def _get_traced(self):
        """The fn actually traced under jit: tensor-dependent control flow
        lowered to lax.cond/while_loop by the AST pass (dy2static); falls
        back to the original fn when the source can't be transformed."""
        if self._traced_fn is None:
            from . import dy2static
            try:
                self._traced_fn = dy2static.transform(self._fn)
            except Exception as e:
                self._transform_note = f"dy2static transform skipped: {e!r}"
                self.graph_break_reasons.append(self._transform_note)
                self._traced_fn = self._fn
        return self._traced_fn

    # descriptor protocol: decorating a method binds per-instance; the
    # bound StaticFunction is cached in the INSTANCE dict so the jit
    # cache and dy2static transform survive across calls, and the cache
    # entry dies with the instance (no global registry to leak)
    def __get__(self, obj, objtype=None):
        if obj is None:
            return self
        # key includes THIS descriptor's identity: base and subclass may
        # both decorate the same method name, and super().forward() must
        # not resolve to the subclass's cached bound wrapper
        key = f"_jst_bound_{self._fn.__name__}_{id(self):x}"
        try:
            d = obj.__dict__
        except AttributeError:  # __slots__ instance — uncached
            return StaticFunction(self._fn.__get__(obj, objtype),
                                  layers=self._layers)
        bound = d.get(key)
        if not isinstance(bound, StaticFunction):
            bound = StaticFunction(self._fn.__get__(obj, objtype),
                                   layers=self._layers)
            d[key] = bound
        return bound

    @property
    def code(self):
        return "<jax.jit-compiled; inspect via jax.make_jaxpr>"

    def concrete_program_specs(self):
        return list(self._jit_cache.keys())

    def __call__(self, *args, **kwargs):
        if not _TO_STATIC_ENABLED:
            return self._fn(*args, **kwargs)  # global eager toggle
        fn = self._fn
        layers = self._layers or _discover_layers(fn, args, kwargs, ())
        named_params = []
        named_buffers = []
        for li, layer in enumerate(layers):
            for n, p in layer.named_parameters():
                named_params.append((li, n, p))
            for n, b in layer.named_buffers():
                named_buffers.append((li, n, b))

        in_tensors, rebuild_in, static_sig = _tree_flatten_tensors(
            (args, kwargs))
        # train()/eval() select another program (dropout, BatchNorm,
        # aux heads): the mode of every sublayer is part of the key
        cache_key = (static_sig, len(named_params), len(named_buffers),
                     tuple((li, n) for li, n, _ in named_params),
                     tuple(sub.training for layer in layers
                           for sub in layer.sublayers(include_self=True)))

        jit_entry = self._jit_cache.get(cache_key)
        if jit_entry is None:
            jit_entry = self._build(self._get_traced(), layers,
                                    named_params, named_buffers, rebuild_in)
            self._jit_cache[cache_key] = jit_entry
        jit_fn, n_out_holder = jit_entry

        key = _random.next_key()
        param_tensors = [p for _, _, p in named_params]
        buffer_tensors = [b for _, _, b in named_buffers]
        # else call 1 (fed the program's outputs, and what an optimizer
        # made of its gradients) compiles the forward and backward again
        for t in param_tensors + buffer_tensors:
            t._data = committed(t._data)

        try:
            # the tape node keeps the buffers AS THEY ENTER the program
            # (detached views): the live ones are rebound to the program's
            # outputs below, and backward() must neither see those values
            # nor trip its modified-in-place check on them
            outs = apply(jit_fn, Tensor(key),
                         *[b.detach() for b in buffer_tensors],
                         *param_tensors, *in_tensors, name="to_static")
        except (jax.errors.ConcretizationTypeError,
                jax.errors.TracerBoolConversionError,
                jax.errors.TracerArrayConversionError,
                jax.errors.TracerIntegerConversionError,
                _GraphBreakError) as e:
            # graph break → eager fallback (reference: SOT fallback),
            # with the reason recorded for diagnosis (bounded: a
            # permanently-falling-back fn must not grow the list forever)
            self.graph_break_reasons.append(
                f"{type(e).__name__}: {e}")
            del self.graph_break_reasons[:-50]
            self._jit_cache.pop(cache_key, None)
            return fn(*args, **kwargs)

        outs = list(outs) if isinstance(outs, tuple) else [outs]
        n_out, rebuild_out = n_out_holder[0]
        # rebind updated buffers
        new_buf = outs[n_out:]
        for b, nb in zip(buffer_tensors, new_buf):
            b._inplace_update(nb._data)
        return rebuild_out([t for t in outs[:n_out]])

    def _build(self, fn, layers, named_params, named_buffers, rebuild_in):
        n_buf = len(named_buffers)
        n_par = len(named_params)
        n_out_holder: list = []

        def pure(key, *flat):
            buf_arrays = flat[:n_buf]
            par_arrays = flat[n_buf:n_buf + n_par]  # graftlint: disable=jit-constant-capture (n_par is an int count; the param arrays themselves are the *flat jit arguments)
            in_arrays = flat[n_buf + n_par:]
            # snapshot live state, substitute tracers
            saved = []
            for (li, n, t), arr in zip(
                    list(named_buffers) + list(named_params),  # graftlint: disable=jit-constant-capture (trace-time substitute/restore idiom: the traced arrays are the *flat jit arguments)
                    list(buf_arrays) + list(par_arrays)):
                saved.append((t, t._data))
                t._data = arr
            _random.push_trace_key(key)
            try:
                args2, kwargs2 = rebuild_in(
                    [Tensor(a, stop_gradient=True) for a in in_arrays])
                result = fn(*args2, **kwargs2)
                out_tensors, rebuild_out, _ = _tree_flatten_tensors(result)
                new_buf = [t._data for _, _, t in named_buffers]
                if not n_out_holder:
                    n_out_holder.append(
                        (len(out_tensors),
                         lambda ts, rb=rebuild_out: rb(ts)))
                return tuple(t._data for t in out_tensors) + tuple(new_buf)
            finally:
                _random.pop_trace_key()
                for t, arr in saved:
                    t._data = arr

        # built once per cache entry; apply() micro-jits it by identity
        return mark_stable(jax.jit(pure)), n_out_holder

    def rollback(self):
        return self._fn


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=False, **kwargs):
    """@paddle.jit.to_static parity. Works on functions, methods & Layers."""

    def decorate(obj):
        if isinstance(obj, Layer):
            obj.forward = StaticFunction(obj.forward, layers=[obj])
            return obj
        return StaticFunction(obj)

    if function is not None:
        return decorate(function)
    return decorate
