"""Tracks which mesh axis names are live (i.e. we are executing inside a
shard_map-traced region). The fleet SPMD runtime pushes axis names around
the traced step function; collective.py consults this to decide traced vs
eager lowering. (The reference analogue is "are we inside a comm stream
capture" — here the question is "is the axis bound in the trace".)
"""
from __future__ import annotations

import contextlib

_axis_stack: list[tuple[str, ...]] = []


@contextlib.contextmanager
def axis_env(*names: str):
    _axis_stack.append(tuple(n for n in names if n))
    try:
        yield
    finally:
        _axis_stack.pop()


def current_axis_env() -> set:
    out = set()
    for names in _axis_stack:
        out.update(names)
    return out


# The mesh a GSPMD stepper is tracing its step for. A `pallas_call` has
# no partitioning rule, so code that dispatches a kernel (ops/pallas/
# flash_attention.py) consults this to run the kernel per shard inside
# `jax.shard_map` instead of handing Mosaic a sharded operand.
_mesh_stack: list = []


@contextlib.contextmanager
def mesh_env(mesh):
    _mesh_stack.append(mesh)
    try:
        yield
    finally:
        _mesh_stack.pop()


def current_mesh_env():
    """The innermost stepper mesh, or None outside a mesh-traced step."""
    return _mesh_stack[-1] if _mesh_stack else None
