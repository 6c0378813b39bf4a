"""jit-constant-capture: weights must be ARGUMENTS of compiled programs
(baked-in constants make the serialized program O(model size), and jit
caches keyed on such programs go stale when weights change)."""
from __future__ import annotations

import ast
import re

from ..core import Rule, dotted_name

_JIT_NAMES = {"jax.jit", "jit"}
# closure-variable names / assignment sources that read as model state
_ARRAYISH_NAME = re.compile(r"(?i)(param|weight|state_dict|_data\b)")


def _is_jit_decorator(dec):
    """@jax.jit, @jit, @functools.partial(jax.jit, ...), @jax.jit(...)"""
    name = dotted_name(dec)
    if name in _JIT_NAMES:
        return True
    if isinstance(dec, ast.Call):
        fname = dotted_name(dec.func)
        if fname in _JIT_NAMES:
            return True
        if fname in ("functools.partial", "partial") and dec.args:
            return dotted_name(dec.args[0]) in _JIT_NAMES
    return False


class JitConstantCapture(Rule):
    """jit-wrapped callables closing over module/instance arrays.

    A jit-captured weight is a CONSTANT of the compiled program: the
    remote-compile transport rejects the resulting big request bodies
    (HTTP 413 / broken pipe), and any cache of such programs silently
    serves stale weights after an update.  Weights must be arguments.

    Flags, inside a jit-wrapped function:
    - any ``self.<attr>`` use when ``self`` is captured from an
      enclosing method (a closure baking instance state in);
    - ``@jax.jit`` directly on a method (``self`` becomes a traced/
      static arg — instance arrays become constants either way);
    - closure variables from an enclosing function whose name or
      assignment source looks like model state (``params``, ``weights``,
      ``state_dict()``, ``._data``)."""

    id = "jit-constant-capture"
    description = ("jit-wrapped callable closes over module/instance "
                   "arrays — weights must be arguments (HTTP-413 / "
                   "stale-cache hazard)")

    def applies(self, ctx):
        return ctx.relpath.startswith("paddle_tpu/")

    # -- jit-function discovery --------------------------------------------
    def _jit_functions(self, ctx):
        fns = ctx.functions_by_name()
        out = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.FunctionDef) and any(
                    _is_jit_decorator(d) for d in node.decorator_list):
                out[node.name] = node
            elif isinstance(node, ast.Call) \
                    and dotted_name(node.func) in _JIT_NAMES \
                    and node.args \
                    and isinstance(node.args[0], ast.Name):
                target = fns.get(node.args[0].id)
                if target is not None:
                    out[target.name] = target
        return out.values()

    # -- scope analysis ----------------------------------------------------
    def _local_bindings(self, fn):
        """Names bound inside fn: params, assignments, imports, defs."""
        bound = {a.arg for a in fn.args.args + fn.args.posonlyargs
                 + fn.args.kwonlyargs}
        if fn.args.vararg:
            bound.add(fn.args.vararg.arg)
        if fn.args.kwarg:
            bound.add(fn.args.kwarg.arg)
        for node in ast.walk(fn):
            if isinstance(node, ast.Name) and isinstance(
                    node.ctx, (ast.Store, ast.Del)):
                bound.add(node.id)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.ClassDef)):
                bound.add(node.name)
            elif isinstance(node, (ast.Import, ast.ImportFrom)):
                for a in node.names:
                    bound.add((a.asname or a.name).split(".")[0])
        return bound

    def _enclosing_arrayish(self, ctx, fn):
        """Closure-candidate names bound in enclosing FUNCTION scopes
        whose name or assignment RHS looks like model state."""
        arrayish = {}
        for anc in ctx.ancestors(fn):
            if not isinstance(anc, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(anc):
                if node is fn or isinstance(node, ast.FunctionDef) \
                        and node is not anc:
                    continue
                if isinstance(node, ast.Assign):
                    rhs = ast.dump(node.value)
                    looks = bool(_ARRAYISH_NAME.search(rhs)) or \
                        ".parameters" in rhs or "state_dict" in rhs
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name) and (
                                looks or _ARRAYISH_NAME.search(tgt.id)):
                            arrayish.setdefault(tgt.id, node.lineno)
            for a in anc.args.args:
                if _ARRAYISH_NAME.search(a.arg):
                    arrayish.setdefault(a.arg, anc.lineno)
        return arrayish

    def check(self, ctx):
        for fn in self._jit_functions(ctx):
            local = self._local_bindings(fn)
            if "self" in local:
                # @jax.jit straight on a method
                yield ctx.finding(
                    self.id, fn,
                    f"`{fn.name}` is jit-wrapped with `self` as a "
                    "parameter — instance arrays become compile-time "
                    "constants; compile a pure function taking weights "
                    "as explicit arguments instead")
                continue
            arrayish = self._enclosing_arrayish(ctx, fn)
            reported = set()
            for node in ast.walk(fn):
                if isinstance(node, ast.Attribute) \
                        and isinstance(node.value, ast.Name) \
                        and node.value.id == "self":
                    key = f"self.{node.attr}"
                    if key not in reported:
                        reported.add(key)
                        yield ctx.finding(
                            self.id, node,
                            f"jit-wrapped `{fn.name}` reads `{key}` — "
                            "instance state is baked into the compiled "
                            "program as a constant (413/stale-cache "
                            "hazard); pass it as an argument")
                elif isinstance(node, ast.Name) \
                        and isinstance(node.ctx, ast.Load) \
                        and node.id not in local \
                        and node.id in arrayish \
                        and node.id not in reported:
                    reported.add(node.id)
                    yield ctx.finding(
                        self.id, node,
                        f"jit-wrapped `{fn.name}` closes over "
                        f"`{node.id}` (bound at line "
                        f"{arrayish[node.id]}, looks like model state) "
                        "— weights must be ARGUMENTS of compiled "
                        "programs, never jit-captured constants")
