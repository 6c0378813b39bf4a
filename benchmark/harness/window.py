"""What one run hands to the metric readers, and the traced window.

The readers (``benchmark/end_metrics``, ``benchmark/layer_metrics``) see
a ``Run`` and nothing else: the client-side records or the train loop's
clock readings, the program's counters over the window, and -- in a
``--trace 1`` run -- the reduced profiler trace.
"""
from __future__ import annotations

import shutil
import time
from dataclasses import dataclass, field
from pathlib import Path


@dataclass
class Run:
    cfg: dict                         # configuration as run
    mix: dict                         # traffic as run
    peaks: dict | None                # None on the CPU: no device metric
    chips: int
    setup_s: float = 0.0
    t0: float = 0.0                   # the window, on the host's clock
    t1: float = 0.0
    gave_up_at: float = 0.0
    records: list = field(default_factory=list)      # serving
    train: dict = field(default_factory=dict)        # training
    counters: dict = field(default_factory=dict)     # program counters, delta
    trace: object = None              # trace.Reduced, --trace 1 only
    compiles_in_window: int = 0
    memory_peak_bytes: int = 0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


class Tracer:
    """The profiler around a window. ``start()`` and ``stop()`` bracket
    the traced work; an annotation named ``bench.window`` marks it in the
    trace's own clock. The trace directory lies inside the checkout and
    is removed once reduced (a run writes little to disk)."""

    def __init__(self, root: Path, on: bool):
        self.on = on
        self.dir = root / ".bench_trace"
        self._ann = None

    def start(self):
        if not self.on:
            return
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(str(self.dir))
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()

    def stop(self):
        if not self.on:
            return
        import jax
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def reduce(self, chips: int):
        if not self.on:
            return None
        from . import trace
        try:
            files = sorted(self.dir.rglob("*.xplane.pb"))
            if not files:
                raise SystemExit("benchmark: the profiler wrote no trace")
            return trace.reduce(trace.load(files[-1]), chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def annotate(name: str):
    """A host span in the profiler's own trace (``bench.<name>``), which
    labels the device's idle gaps."""
    import jax
    return jax.profiler.TraceAnnotation(f"bench.{name}")


def sleep_until(t: float, clock=time.perf_counter):
    while True:
        d = t - clock()
        if d <= 0:
            return
        time.sleep(min(d, 0.05))
