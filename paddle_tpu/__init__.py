"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle-class
capabilities, built from scratch on JAX/XLA/Pallas.

Top-level namespace mirrors the reference `paddle.*` API surface (see
SURVEY.md for the structural map). Compute lowers to XLA via jax.numpy with
Pallas kernels for hot paths; distribution is SPMD over jax.sharding meshes.
"""
from __future__ import annotations

__version__ = "0.1.0"

# core
from .core import dtype as _dtype_mod
from .core.dtype import (finfo, iinfo,  # noqa: F401
                         bfloat16, bool_, complex64, complex128, float16,
                         float32, float64, get_default_dtype, int8, int16,
                         int32, int64, set_default_dtype, uint8)
from .core.device import (CPUPlace, Place, TPUPlace, device_count, get_device,
                          is_compiled_with_tpu, set_device)
from .core.tensor import Parameter, Tensor, to_tensor
from .core.autograd import enable_grad, is_grad_enabled, no_grad, set_grad_enabled
from .core.random import get_rng_state, seed, set_rng_state
from .core.flags import get_flags, set_flags

# ops (also installs Tensor methods)
from .ops import *  # noqa: F401,F403
from .ops import linalg as _ops_linalg

# subsystem namespaces (populated as the framework grows)
from . import amp  # noqa: F401
from . import audio  # noqa: F401
from . import autograd  # noqa: F401
from . import device  # noqa: F401
from . import distributed  # noqa: F401
from . import distribution  # noqa: F401
from . import fft  # noqa: F401
from . import incubate  # noqa: F401
from . import inference  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
# `from . import linalg` would short-circuit on the attribute the ops
# star-import already bound (the ops.linalg SUBMODULE — IMPORT_FROM
# checks the package attr before importing), silently shadowing the
# full paddle_tpu/linalg/ package (cond/ormqr/vecdot were unreachable
# via `paddle_tpu.linalg` until round 6). Force the real submodule.
import importlib as _importlib  # noqa: E402

linalg = _importlib.import_module(".linalg", __name__)
from . import metric  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import profiler  # noqa: F401
from . import quantization  # noqa: F401
from . import serving  # noqa: F401
from . import signal  # noqa: F401
from . import sparse  # noqa: F401
from . import static  # noqa: F401
from . import text  # noqa: F401
from . import utils  # noqa: F401
from . import version  # noqa: F401
from . import vision  # noqa: F401
from . import regularizer  # noqa: F401
from . import geometric  # noqa: F401
from . import hub  # noqa: F401
from . import sysconfig  # noqa: F401
from . import callbacks  # noqa: F401
from . import onnx  # noqa: F401
from .regularizer import L1Decay, L2Decay  # noqa: F401
from .nn.layer import LazyGuard  # noqa: E402,F401

from .distributed.parallel import DataParallel  # noqa: E402
from .framework.io_save import load, save  # noqa: E402
from .hapi.model import Model  # noqa: E402
from .hapi.summary import flops, summary  # noqa: E402,F401
from .nn.clip_grad import (ClipGradByGlobalNorm, ClipGradByNorm,  # noqa: E402
                           ClipGradByValue)

bool = bool_  # paddle.bool


def disable_static(place=None):
    """No-op: this framework is eager-first (reference parity shim)."""


def enable_static():
    raise NotImplementedError(
        "paddle_tpu's static mode is scoped, not global: build programs "
        "with `with paddle_tpu.static.program_guard(prog): ...` and run "
        "them via static.Executor (record-and-replay over XLA); "
        "compiled training uses paddle_tpu.jit.to_static / fleet "
        "Engine.")


def in_dynamic_mode():
    return True


def in_pir_mode():
    # static programs here are recorded eagerly (static/program.py), not
    # interpreted from a separate IR — the dygraph surface stays live
    return False


def in_dynamic_or_pir_mode():
    return in_dynamic_mode() or in_pir_mode()


from .device import (is_compiled_with_cuda, is_compiled_with_rocm,  # noqa: E402,F401
                     is_compiled_with_xpu)


def is_compiled_with_custom_device(device_name):
    return device_name == "tpu"


def get_cudnn_version():
    """paddle.get_cudnn_version: None when not built with CUDA (the
    reference contract) — always None on this TPU-native build."""
    return None


from .ops.logic import histogram_bin_edges  # noqa: E402,F401


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """paddle.set_printoptions: Tensor repr goes through numpy, so this
    maps onto numpy's global print options."""
    import numpy as _np
    kw = {}
    if precision is not None:
        kw["precision"] = int(precision)
    if threshold is not None:
        kw["threshold"] = int(threshold)
    if edgeitems is not None:
        kw["edgeitems"] = int(edgeitems)
    if linewidth is not None:
        kw["linewidth"] = int(linewidth)
    if sci_mode is not None:
        kw["suppress"] = not bool(sci_mode)
    _np.set_printoptions(**kw)


def disable_signal_handler():
    """Reference parity no-op: the C++ runtime's SIGSEGV/SIGBUS hooks
    don't exist here (Python-native + XLA runtime)."""
    return None


def grad(outputs, inputs, grad_outputs=None, retain_graph=None,
         create_graph=False, only_inputs=True, allow_unused=False,
         no_grad_vars=None):
    from .core.autograd import grad as _grad
    return _grad(outputs, inputs, grad_outputs, retain_graph, create_graph,
                 only_inputs, allow_unused)


def batch(reader, batch_size, drop_last=False):
    """paddle.batch: wrap a sample reader into a mini-batch reader
    (reference: python/paddle/batch.py)."""
    def batched():
        buf = []
        for sample in reader():
            buf.append(sample)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf
    return batched
