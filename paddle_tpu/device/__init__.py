"""paddle_tpu.device (paddle.device parity)."""
from ..core.device import (CPUPlace, Place, TPUPlace, device_count,  # noqa: F401
                           device_guard, get_device, get_place,
                           is_compiled_with_tpu, set_device, synchronize)


class _DeviceNamespace:
    """paddle.device.cuda-style namespace for the TPU."""

    @staticmethod
    def device_count():
        return device_count("tpu")

    @staticmethod
    def synchronize(device=None):
        synchronize(device)

    @staticmethod
    def empty_cache():
        pass  # XLA/PJRT owns the device memory pool

    @staticmethod
    def max_memory_allocated(device=None):
        import jax
        try:
            stats = jax.local_devices()[0].memory_stats()
            return stats.get("peak_bytes_in_use", 0)
        except Exception:
            return 0

    @staticmethod
    def memory_allocated(device=None):
        import jax
        try:
            stats = jax.local_devices()[0].memory_stats()
            return stats.get("bytes_in_use", 0)
        except Exception:
            return 0


tpu = _DeviceNamespace()
cuda = _DeviceNamespace()  # API-compat alias so ported scripts run
xpu = _DeviceNamespace()   # same, for XPU-targeting scripts


def is_compiled_with_cuda():
    return False  # TPU-native build


def is_compiled_with_xpu():
    return False


def is_compiled_with_rocm():
    return False


def get_all_device_type():
    import jax
    seen = []
    for d in jax.devices():
        if d.platform not in seen:
            seen.append(d.platform)
    if "cpu" not in seen:
        seen.append("cpu")
    return seen


def get_available_device():
    import jax
    d = jax.devices()[0]
    return f"{d.platform}:{d.id}"
