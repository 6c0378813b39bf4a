"""The device: the table of peaks, the look for a chip, the compile cache
and the count of compilations.

The table is the benchmark's own (a later PR may edit the program's
``paddle_tpu/utils/chip_specs.py``). A device that is not in it is an
error, never a default.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

# Peaks of one chip, keyed by ``device_kind`` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e)",
    },
}


def require(chips: int, rehearse: bool,
            client_options: dict | None = None) -> tuple[dict, dict | None]:
    """(device record, peaks). Exits non-zero, printing no result, when
    JAX finds no accelerator of the table or fewer chips than the cell
    asks for. ``rehearse`` lets the CPU tests through: peaks are then
    ``None`` and no device metric is ever computed. ``client_options``
    (a mix's, e.g. how many computations the runtime lets a process have
    in flight) are laid over the PJRT client's own before it is made."""
    import jax
    if client_options and not rehearse:
        jax.config.update("jax_pjrt_client_create_options", laid_over(
            jax.config.jax_pjrt_client_create_options, client_options))
    if rehearse:
        jax.config.update("jax_platforms", "cpu")
        try:
            jax.config.update("jax_num_cpu_devices", chips)
        except RuntimeError:
            pass       # the backend is up already (a test's process)
    devs = jax.devices()
    d = devs[0]
    record = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    if rehearse:
        if d.platform != "cpu":
            sys.exit("benchmark: --rehearse runs on the CPU only")
        return record, None
    if d.platform != "tpu" or d.device_kind not in PEAKS:
        sys.exit(f"benchmark: needs a TPU of its peak table "
                 f"({sorted(PEAKS)}); JAX reports platform "
                 f"{d.platform!r}, kind {d.device_kind!r}")
    if len(devs) < chips:
        sys.exit(f"benchmark: the cell asks for {chips} chip(s), JAX "
                 f"reports {len(devs)}")
    record["count"] = chips
    return record, PEAKS[d.device_kind]


def laid_over(have: str | dict | None, more: dict) -> dict:
    """The client's options as JAX holds them (``"k1:v1;k2:v2"``, a dict
    or nothing) with a mix's laid over them. A mix's values keep their
    type: the client takes a whole number only as one."""
    if isinstance(have, str):
        have = dict(o.split(":", 1) for o in have.split(";") if o)
    return {**(have or {}), **more}


def memory_peak_bytes(chips: int) -> int:
    """Peak on the fullest chip used; 0 where the backend reports none."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache at a path that never moves: where
    ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself, otherwise
    ``<checkout>/.jax_cache`` (the program's own default too, so the two
    never disagree)."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts what JAX compiles (or fetches from the persistent cache):
    one ``backend_compile`` event per program. ``mark()`` before the
    window, ``since_mark()`` after it: it has to read 0."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self.seconds = 0.0
        self._mark = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            if name.endswith("backend_compile_duration"):
                self.count += 1

    def mark(self):
        self._mark = self.count

    def since_mark(self) -> int:
        return self.count - self._mark
