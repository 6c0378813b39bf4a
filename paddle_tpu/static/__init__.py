"""paddle_tpu.static — static-graph mode.

Reference parity: paddle.static.* (upstream python/paddle/static/ —
unverified, see SURVEY.md §2.2). Two tiers:

- **Real Program/Executor** (static/program.py): `program_guard` records
  the op DAG through the autograd chokepoint while ops run eagerly on
  placeholder zeros; `Executor.run(prog, feed, fetch_list)` replays it as
  ONE jitted XLA computation per feed signature. Inference-style programs
  (data → layers/ops → fetch) work end-to-end; parameters created inside
  the guard stay live Tensors, so their trained values flow into later
  runs.
- Deployment save/load maps onto jit.save/load (StableHLO artifacts).
- **Static TRAINING**: `append_backward(loss)` + `optimizer.minimize`
  inside `program_guard` append gradient/update records whose outputs
  are written back to parameter and optimizer-state leaves after every
  `Executor.run` (see static/program.py). The dynamic path (`to_static`,
  fleet Engine) remains the recommended compiled-training story.
"""
from __future__ import annotations

import contextlib

from ..jit.save_load import InputSpec, TranslatedLayer  # noqa: F401
from . import amp  # noqa: F401
from ..jit.save_load import load as _jit_load
from ..jit.save_load import save as _jit_save
from . import nn  # noqa: F401
from .program import (Executor, Program, append_backward, data,  # noqa: F401
                      default_main_program, default_startup_program,
                      global_scope, program_guard, scope_guard)

__all__ = ["InputSpec", "save_inference_model", "load_inference_model",
           "Program", "program_guard", "data", "Executor",
           "append_backward", "default_main_program",
           "default_startup_program", "global_scope", "scope_guard",
           "name_scope", "device_guard"]


@contextlib.contextmanager
def name_scope(prefix=None):
    yield


from ..core.device import device_guard  # noqa: E402,F401


def save_inference_model(path_prefix, feed_vars, fetch_vars, executor=None,
                         program=None, **kwargs):
    """Export an inference artifact loadable by `load_inference_model` /
    `jit.load` / the C++ `pd_infer` runtime.

    Two paths (reference: paddle.static.save_inference_model):
    - `layer=<nn.Layer>`: delegates to jit.save (trace-based export);
    - a recorded PROGRAM (default main or `program=`): the op records
      reaching `fetch_vars` are pruned (training records excluded) and
      exported as StableHLO with the leaf constants/parameters saved by
      name — the reference's Program→inference-model path."""
    layer = kwargs.get("layer")
    if layer is not None:
        specs = feed_vars if feed_vars else None
        _jit_save(layer, path_prefix, input_spec=specs)
        return
    import json
    import os

    import numpy as np

    import jax
    import jax.numpy as jnp

    from .program import Program, default_main_program
    prog = program if isinstance(program, Program) \
        else default_main_program()
    if not prog._records:
        raise ValueError(
            "save_inference_model: the Program has no recorded ops; "
            "build it under program_guard (or pass layer=<nn.Layer>)")
    feed_vars = list(feed_vars or [])
    fetch_vars = list(fetch_vars or [])
    if not feed_vars or not fetch_vars:
        raise ValueError("save_inference_model needs feed_vars and "
                         "fetch_vars from the recorded Program")
    fetch_keys = [id(t) for t in fetch_vars]
    feed_keys = [id(t) for t in feed_vars]
    # prune to forward records reaching the fetches (no training records,
    # no writebacks — an inference snapshot)
    need = set(fetch_keys)
    active = []
    for rec in reversed([r for r in prog._records if r.kind == "op"]):
        if any(k in need for k in rec.out_keys):
            active.append(rec)
            need.update(rec.in_keys)
    active.reverse()
    leaf_keys = [k for k in prog._leaves if k in need]
    leaf_arrays = [prog._leaves[k]._data for k in leaf_keys]
    names = [f"leaf_{i}" for i in range(len(leaf_keys))]

    def pure(params, buffers, *feeds):
        env = dict(zip(leaf_keys, params))
        env.update(zip(feed_keys, feeds))
        for rec in active:
            args = [env[k] for k in rec.in_keys]
            out = rec.fn(*args)
            outs = out if isinstance(out, (tuple, list)) else (out,)
            env.update(zip(rec.out_keys, outs))
        return tuple(env[k] for k in fetch_keys)

    os.makedirs(os.path.dirname(path_prefix) or ".", exist_ok=True)
    np.savez(path_prefix + ".pdiparams.npz",
             **{n: np.asarray(a) for n, a in zip(names, leaf_arrays)})
    meta = {"type": "program", "params": names, "buffers": [],
            "fetches": len(fetch_keys)}
    specs = [jax.ShapeDtypeStruct(tuple(t._data.shape),
                                  jnp.dtype(t._data.dtype))
             for t in feed_vars]
    try:
        exported = jax.export.export(jax.jit(pure))(
            [jax.ShapeDtypeStruct(a.shape, a.dtype)
             for a in leaf_arrays], [], *specs)
        with open(path_prefix + ".stablehlo", "wb") as f:
            f.write(exported.serialize())
        meta["stablehlo"] = True
    except Exception as e:
        meta["stablehlo"] = False
        meta["export_error"] = str(e)[:500]
    with open(path_prefix + ".pdmodel.json", "w") as f:
        json.dump(meta, f)


def load_inference_model(path_prefix, executor=None, **kwargs):
    return _jit_load(path_prefix)


def cpu_places(device_count=None):
    """paddle.static.cpu_places (reference python/paddle/base/framework
    — unverified): CPU places; count defaults to 1 (the reference reads
    CPU_NUM)."""
    import os

    from ..core.device import Place
    n = device_count or int(os.environ.get("CPU_NUM", "1"))
    return [Place("cpu", i) for i in range(n)]


def cuda_places(device_ids=None):
    """paddle.static.cuda_places, TPU-natively: places of the visible
    ACCELERATOR devices (tpu under PJRT — the role 'cuda_places'
    plays in reference code is "give me the accelerators"). Falls back
    to CPU places when no accelerator is attached."""
    import jax

    from ..core.device import Place
    kinds = {"tpu": "tpu", "gpu": "gpu", "cuda": "gpu"}
    devs = [d for d in jax.local_devices()
            if d.platform in kinds]
    if not devs:
        return cpu_places(len(device_ids) if device_ids else None)
    if device_ids is None:
        device_ids = range(len(devs))
    return [Place(kinds[devs[i].platform], i) for i in device_ids]


def save(program, path_prefix, protocol=4):
    """paddle.static.save: persist the program's parameters
    (``.pdparams``) and the remaining float leaf state, e.g. optimizer
    moments pinned by minimize (``.pdopt``). Positional format — the
    reference keys by variable name; record-time ids are not stable
    across processes, so entries are (name, array) pairs restored by
    position into the SAME program structure."""
    import pickle

    import numpy as np

    from ..core.tensor import Parameter
    params, state = [], []
    for t in program._leaves.values():
        entry = (getattr(t, "name", None), np.asarray(t._data))
        (params if isinstance(t, Parameter) else state).append(entry)
    with open(path_prefix + ".pdparams", "wb") as f:
        pickle.dump(params, f, protocol=protocol)
    if state:
        with open(path_prefix + ".pdopt", "wb") as f:
            pickle.dump(state, f, protocol=protocol)


def load(program, path_prefix, executor=None, var_list=None):
    """paddle.static.load: restore what `save` wrote, by position."""
    import os
    import pickle

    import jax.numpy as jnp

    from ..core.tensor import Parameter
    with open(path_prefix + ".pdparams", "rb") as f:
        params = pickle.load(f)
    state = []
    if os.path.exists(path_prefix + ".pdopt"):
        with open(path_prefix + ".pdopt", "rb") as f:
            state = pickle.load(f)
    targets_p = [t for t in program._leaves.values()
                 if isinstance(t, Parameter)]
    targets_s = [t for t in program._leaves.values()
                 if not isinstance(t, Parameter)]
    if len(params) != len(targets_p):
        raise ValueError(
            f"checkpoint has {len(params)} parameters, program has "
            f"{len(targets_p)} — was it saved from this program?")
    if state and len(state) != len(targets_s):
        raise ValueError(
            f"checkpoint has {len(state)} aux-state entries, program "
            f"has {len(targets_s)} — rebuild the program to the same "
            "point (e.g. run minimize before load) or delete the "
            ".pdopt file for a params-only restore")
    for t, (_, arr) in zip(targets_p, params):
        t._inplace_update(jnp.asarray(arr).astype(t._data.dtype))
    for t, (_, arr) in zip(targets_s, state):
        t._inplace_update(jnp.asarray(arr).astype(t._data.dtype))


def normalize_program(program, feed_vars, fetch_vars, **kwargs):
    """paddle.static.normalize_program: prune a trained program down to
    the inference graph for the given feeds/fetches. The record-replay
    design makes this the test-mode clone (dead-record elimination at
    run time keeps exactly the ops reaching the fetches)."""
    return program.clone(for_test=True)


from .program import gradients, py_func  # noqa: E402,F401

__all__ += ["cpu_places", "cuda_places", "save", "load",
            "normalize_program", "gradients", "py_func"]
