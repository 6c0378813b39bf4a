"""paddle_tpu.profiler — profiling/tracing.

Reference parity: paddle.profiler.Profiler with scheduler windows,
RecordEvent spans, export_chrome_tracing, summary tables, throughput timer
(upstream python/paddle/profiler/ + C++ host/CUPTI tracers — unverified,
see SURVEY.md §5.1).

TPU-native: device timeline comes from `jax.profiler` (XPlane → perfetto/
TensorBoard — the CUPTI-equivalent); host spans from
jax.profiler.TraceAnnotation + a fixed in-process ring that powers
`summary()`, chrome export and the window query `span_totals()`.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import defaultdict
from enum import Enum

import jax

__all__ = ["Profiler", "ProfilerTarget", "ProfilerState", "RecordEvent",
           "make_scheduler", "export_chrome_tracing",
           "load_profiler_result", "events_dropped", "span_totals"]


class ProfilerTarget(Enum):
    CPU = 0
    GPU = 1
    TPU = 2
    CUSTOM_DEVICE = 3


class ProfilerState(Enum):
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3


def make_scheduler(closed=0, ready=0, record=1, repeat=0, skip_first=0):
    """Returns fn(step)->ProfilerState over cyclic windows."""
    cycle = closed + ready + record

    def scheduler(step):
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * cycle:
            return ProfilerState.CLOSED
        pos = s % cycle
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == cycle - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD
    return scheduler


# The host span store: ONE fixed ring of ``_RING_SIZE`` (2^18) entries,
# each ``(name, thread ident, start_ns, dur_ns)`` on
# ``time.perf_counter_ns()`` -- the clock of ``time.perf_counter()``,
# which the serving engine and its callers stamp with.  A writer takes
# its slot from ``itertools.count`` and stores one tuple: both steps are
# single operations under the GIL, so the append path holds no lock and
# builds no per-span dict, and concurrent writers never share a slot.
# The ring only overwrites its oldest entries: a long-running server
# keeps its latest 2^18 spans, never goes blind, never grows.
#
# Size: a full ring is ~136 B an entry (the tuple and its two fresh
# ints; the name is the caller's string, the ident one object a thread)
# plus 8 B of list slot, about 36 MiB (measured with tracemalloc).  The
# serving loop writes about 10 spans a step; the longest drain after a
# benchmark window (150 s at about 27 steps/s, or 60 s at up to about
# 120 steps/s) writes under 80 k, so a 6 s window's spans outlive it
# with more than three times that to spare.  An idle front-end loop
# writes one ``serving.lock_wait`` a poll (about 1 k/s): the ring then
# holds over four minutes of it.
_RING_SIZE = 1 << 18
_MASK = _RING_SIZE - 1
_ring: list = [None] * _RING_SIZE
_seq = itertools.count()
_mark = 0                       # the ring's position at Profiler.start()
_tls = threading.local()
_clock_ns = time.perf_counter_ns
_TraceAnnotation = jax.profiler.TraceAnnotation


def _written():
    """Spans written so far (``itertools.count`` shows its next value
    only in its repr)."""
    return int(repr(_seq)[6:-1])


def _entries(lo):
    """The entries written at or after position ``lo`` that the ring
    still holds, oldest first."""
    n = _written()
    got = (_ring[i & _MASK] for i in range(max(lo, n - _RING_SIZE), n))
    return [e for e in got if e is not None]


def events_dropped():
    """Spans overwritten since the last Profiler.start() (the ring holds
    the newest ``2^18``)."""
    return max(0, _written() - _mark - _RING_SIZE)


def span_totals(t0, t1, thread=None):
    """``{name: (count, seconds)}`` of the spans that began in
    ``[t0, t1)`` -- seconds on ``time.perf_counter`` -- and that the ring
    still holds; with ``thread`` (an ident, ``threading.get_ident()``),
    only that thread's.  The window query a scrape or a benchmark reads
    the engine's step phases with."""
    lo, hi = int(t0 * 1e9), int(t1 * 1e9)
    out = {}
    for name, tid, start, dur in _entries(0):
        if lo <= start < hi and (thread is None or tid == thread):
            count, ns = out.get(name, (0, 0))
            out[name] = (count + 1, ns + dur)
    return {name: (count, ns / 1e9) for name, (count, ns) in out.items()}


class RecordEvent:
    """Host-side span: one ``jax.profiler.TraceAnnotation`` (the span on
    the profiler's clock, beside the device's ops) and one ring entry (on
    ``perf_counter``, for summary(), chrome export and span_totals()).
    Spans nest by their intervals; any thread may record."""

    __slots__ = ("name", "_t0", "_ann")

    def __init__(self, name, event_type=None):
        self.name = name
        self._t0 = None
        self._ann = None

    def begin(self):
        try:
            self._ann = _TraceAnnotation(self.name)
            self._ann.__enter__()
        except Exception:
            self._ann = None
        self._t0 = _clock_ns()
        return self

    def end(self, *exc):
        t1 = _clock_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        try:
            tid = _tls.tid
        except AttributeError:         # one ident object a thread
            tid = _tls.tid = threading.get_ident()
        _ring[next(_seq) & _MASK] = (self.name, tid, self._t0,
                                     t1 - self._t0)

    # the with-statement's two calls are the same two methods
    __enter__, __exit__ = begin, end


class Profiler:
    def __init__(self, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 emit_nvtx=False, custom_device_types=None):
        self._scheduler = scheduler if callable(scheduler) else (
            make_scheduler(record=scheduler[1] - scheduler[0],
                           closed=scheduler[0])
            if isinstance(scheduler, (tuple, list)) else
            (lambda step: ProfilerState.RECORD))
        self._on_trace_ready = on_trace_ready
        self._step = 0
        self._state = ProfilerState.CLOSED
        self._jax_tracing = False
        self._logdir = None
        self._timer_only = timer_only
        self._step_times: list[float] = []
        self._t_last = None

    def start(self):
        global _mark
        _mark = _written()
        self._state = self._scheduler(self._step)
        self._maybe_toggle()
        self._t_last = time.perf_counter()

    def stop(self):
        if self._jax_tracing:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._jax_tracing = False
        if self._on_trace_ready is not None:
            self._on_trace_ready(self)

    def step(self, num_samples=None):
        now = time.perf_counter()
        if self._t_last is not None:
            self._step_times.append(now - self._t_last)
        self._t_last = now
        self._step += 1
        new_state = self._scheduler(self._step)
        if new_state != self._state:
            self._state = new_state
            self._maybe_toggle()

    def _maybe_toggle(self):
        want = self._state in (ProfilerState.RECORD,
                               ProfilerState.RECORD_AND_RETURN)
        if want and not self._jax_tracing and not self._timer_only:
            self._logdir = os.environ.get("PADDLE_TPU_PROFILE_DIR",
                                          "/tmp/paddle_tpu_profile")
            try:
                jax.profiler.start_trace(self._logdir)
                self._jax_tracing = True
            except Exception:
                pass
        elif not want and self._jax_tracing:
            try:
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._jax_tracing = False

    def export_chrome_tracing(self, dir_name, worker_name=None):
        os.makedirs(dir_name, exist_ok=True)
        path = os.path.join(dir_name,
                            (worker_name or "worker") + ".json")
        pid = os.getpid()
        events = [{"name": name, "ts": start / 1e3, "dur": dur / 1e3,
                   "ph": "X", "pid": pid, "tid": tid}
                  for name, tid, start, dur in _entries(_mark)]
        with open(path, "w") as f:
            json.dump({"traceEvents": events}, f)
        return path

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        agg = defaultdict(lambda: [0.0, 0])
        for name, _, _, dur in _entries(_mark):
            agg[name][0] += dur / 1e6
            agg[name][1] += 1
        lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Avg(ms)':>12}",
                 "-" * 72]
        for name, (total, calls) in sorted(agg.items(),
                                           key=lambda kv: -kv[1][0]):
            lines.append(f"{name:<40}{calls:>8}{total:>12.3f}"
                         f"{total / calls:>12.3f}")
        if self._step_times:
            avg = sum(self._step_times) / len(self._step_times)
            lines.append(f"steps: {len(self._step_times)}  avg "
                         f"{avg * 1e3:.2f} ms  ips {1.0 / avg:.2f}")
        out = "\n".join(lines)
        print(out)
        return out

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()
        return False


def export_chrome_tracing(dir_name, worker_name=None):
    def handler(prof):
        prof.export_chrome_tracing(dir_name, worker_name)
    return handler


def load_profiler_result(filename):
    with open(filename) as f:
        return json.load(f)
