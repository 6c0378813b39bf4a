"""Device / Place management.

Reference parity: paddle.set_device / paddle.get_device and the
phi::Place hierarchy (upstream paddle/phi/common/place.h — unverified, see
SURVEY.md). TPU-native realization: a Place is a thin descriptor over a
`jax.Device`; `set_device` installs a process-global default that tensor
creation honors via `jax.device_put`. There are no streams to manage —
XLA/PJRT owns scheduling — so the stream/event APIs are intentionally
minimal shims (`synchronize` blocks on ready arrays).
"""
from __future__ import annotations

import contextlib
import os

import jax


class Place:
    """Device descriptor: place type string + device index."""

    def __init__(self, kind: str, index: int = 0):
        self.kind = kind  # 'tpu' | 'cpu' | 'gpu'
        self.index = index

    def __repr__(self):
        return f"Place({self.kind}:{self.index})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.kind == other.kind
            and self.index == other.index
        )

    def __hash__(self):
        return hash((self.kind, self.index))

    def is_tpu_place(self):
        return self.kind == "tpu"

    def is_cpu_place(self):
        return self.kind == "cpu"

    def is_gpu_place(self):
        return self.kind == "gpu"

    @property
    def jax_device(self):
        return _jax_device_for(self.kind, self.index)


def TPUPlace(index: int = 0) -> Place:
    return Place("tpu", index)


def CPUPlace(index: int = 0) -> Place:
    return Place("cpu", index)


_PLATFORM_ALIASES = {
    "tpu": ("tpu",),
    "cpu": ("cpu",),
    "gpu": ("gpu", "cuda", "rocm"),
}


def _jax_device_for(kind: str, index: int):
    for platform in _PLATFORM_ALIASES.get(kind, (kind,)):
        try:
            # LOCAL devices only: in the multi-controller regime the
            # global list leads with process 0's devices, which other
            # processes cannot address — eager data must live locally
            devs = jax.local_devices(backend=platform)
        except RuntimeError:
            continue
        if devs:
            if not 0 <= index < len(devs):
                raise RuntimeError(
                    f"{kind}:{index} out of range: {len(devs)} local "
                    f"{kind!r} device(s)")
            return devs[index]
    raise RuntimeError(f"No {kind!r} device available (jax backends: "
                       f"{[d.platform for d in jax.devices()]})")


_current_place: Place | None = None


def _default_place() -> Place:
    """TPU if present, else CPU — mirrors the reference's GPU-first default."""
    for kind in ("tpu", "gpu", "cpu"):
        try:
            _jax_device_for(kind, 0)
            return Place(kind, 0)
        except RuntimeError:
            continue
    return Place("cpu", 0)


def set_device(device: str) -> Place:
    """paddle.set_device('tpu') / 'tpu:0' / 'cpu'."""
    global _current_place
    kind, _, idx = device.partition(":")
    place = Place(kind, int(idx) if idx else 0)
    _jax_device_for(place.kind, place.index)  # validate now
    _current_place = place
    return place


def get_device() -> str:
    p = get_place()
    return f"{p.kind}:{p.index}"


def get_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = _default_place()
    return _current_place


def get_jax_device():
    return get_place().jax_device


def committed(arr):
    """`arr`, committed to the device(s) it is on. What jax.random and
    jnp.zeros return is uncommitted; what a compiled step returns is
    committed (its batch, from to_tensor, is). State fed back from step 0
    then has other jit cache keys than it had at step 0, and every
    program compiles again at step 1 — so the compiled steppers let
    state enter step 0 as it will enter every later step."""
    if isinstance(arr, jax.Array) and not isinstance(arr, jax.core.Tracer) \
            and not arr.committed:
        return jax.device_put(arr, arr.sharding)
    return arr


def device_count(kind: str | None = None) -> int:
    kind = kind or get_place().kind
    total = 0
    for platform in _PLATFORM_ALIASES.get(kind, (kind,)):
        try:
            total = max(total, len(jax.devices(platform)))
        except RuntimeError:
            pass
    return total


def is_compiled_with_tpu() -> bool:
    try:
        _jax_device_for("tpu", 0)
        return True
    except RuntimeError:
        return False


# Reference parity: paddle.device.cuda.synchronize / streams. XLA owns
# scheduling; synchronize = drain all outstanding work on the default device.
def synchronize(device: str | None = None):
    # jax arrays are futures; calling block_until_ready on a fresh trivial
    # computation serializes behind everything already enqueued.
    import jax.numpy as jnp

    jnp.zeros(()).block_until_ready()


@contextlib.contextmanager
def device_guard(device: str):
    """Temporarily switch the default place (paddle.static.device_guard)."""
    global _current_place
    prev = get_place()
    set_device(device)
    try:
        yield
    finally:
        _current_place = prev
