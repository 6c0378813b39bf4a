"""DLPack interchange (paddle.utils.dlpack parity).

Reference surface: paddle.utils.dlpack.to_dlpack / from_dlpack (upstream
python/paddle/utils/dlpack.py — unverified, SURVEY.md blocker notice).

TPU-native: `jax.Array` already speaks the DLPack protocol; we surface the
capsule form for legacy consumers (torch.utils.dlpack, cupy) and accept
either a capsule or any object exporting ``__dlpack__`` on import.
Zero-copy on CPU. DLPack has no TPU device type, and the TPU PJRT client
does not implement external buffer references — exporting a device-resident
tensor therefore falls back to a host copy (documented deviation: the
reference's GPU path is zero-copy; cross-device interchange on TPU goes
through host memory by construction).
"""
from __future__ import annotations

from ..core.tensor import Tensor
from ..ops._base import ensure_tensor


def to_dlpack(x):
    """Export a Tensor as a DLPack capsule (host copy if the device
    buffer cannot be externally referenced, e.g. on TPU)."""
    import numpy as np
    t = ensure_tensor(x)
    data = t._data
    if hasattr(data, "__dlpack__"):
        try:
            return data.__dlpack__()
        except Exception:  # TPU: no external-reference support
            # np.asarray gives a read-only view, which DLPack refuses to
            # export — take a writable host copy.
            return np.array(data, copy=True).__dlpack__()
    import jax.dlpack
    return jax.dlpack.to_dlpack(data)  # pragma: no cover - legacy jax


def from_dlpack(ext):
    """Import a DLPack capsule (or any ``__dlpack__`` exporter, e.g. a
    torch/numpy/cupy array) as a Tensor."""
    import jax.numpy as jnp
    if hasattr(ext, "__dlpack__"):
        return Tensor(jnp.from_dlpack(ext))
    import jax.dlpack
    return Tensor(jax.dlpack.from_dlpack(ext))
