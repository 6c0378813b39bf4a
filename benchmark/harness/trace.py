"""From the profiler's trace to numbers: device busy and idle time, time
per operation and per program, and the idle gaps labelled by what the
host was doing.

``load`` reads an ``.xplane.pb`` with nothing but JAX into a plain dict
(the form of the small recorded trace in ``tests/benchmark/data``);
``reduce`` is pure arithmetic over that dict.

A device plane is ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one
event per executed HLO operation (a ``while`` holds its body's events
inside its own interval), ``XLA Modules`` one per executed program. Host
planes hold the ``bench.*`` annotations the drivers put around their
calls; ``bench.window`` marks the traced window in the trace's clock.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
# operations that only hold others: their own interval is not work
CONTAINER = re.compile(r"^%?(while|conditional|call)([.\d]|$)")
WINDOW = "bench.window"
SHORT_GAP_NS = 20_000
NAME_CHARS = 160        # an operation's name is its whole HLO line


def load(path) -> dict:
    """{"devices": {id: {"ops": [[name, start_ns, dur_ns]..],
    "modules": [..]}}, "host": [[name, start_ns, dur_ns]..]} -- host
    events are the ``bench.*`` annotations only."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = out["devices"].setdefault(
                int(m.group(1)), {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key].extend(
                        [ev.name[:NAME_CHARS], int(ev.start_ns),
                         int(ev.duration_ns)] for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"].extend(
                    [ev.name, int(ev.start_ns), int(ev.duration_ns)]
                    for ev in line.events if ev.name.startswith("bench."))
    return out


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _length(merged) -> int:
    return sum(b - a for a, b in merged)


def _subtract(merged_a, merged_b):
    """Parts of a not covered by b (both merged and sorted)."""
    out, j = [], 0
    for a, b in merged_a:
        cur = a
        while j < len(merged_b) and merged_b[j][1] <= cur:
            j += 1
        k = j
        while k < len(merged_b) and merged_b[k][0] < b:
            if merged_b[k][0] > cur:
                out.append([cur, merged_b[k][0]])
            cur = max(cur, merged_b[k][1])
            k += 1
        if cur < b:
            out.append([cur, b])
    return out


def _self_times(events):
    """{name: ns} with each event's time less what its children cover
    (events of one line nest, they never half-overlap)."""
    total, stack = {}, []          # stack of [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            total[name] = total.get(name, 0) + own

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return total


def _clip(events, w0, w1):
    out = []
    for name, start, dur in events:
        a, b = max(start, w0), min(start + dur, w1)
        if b > a:
            out.append([name, a, b - a])
    return out


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over the chips used
    busy_by_device: dict                # id -> seconds
    op_self_s: dict                     # name -> seconds, summed over chips
    module_s: dict                      # program name -> [seconds, ...]
    idle_gaps: list = field(default_factory=list)    # [[label, seconds]]

    def idle_share(self) -> float:
        """1 - busy/window on the fullest... busiest device."""
        return 1.0 - max(self.busy_by_device.values()) / self.window_s

    def top_ops(self, n=10):
        return [[k, v] for k, v in sorted(
            self.op_self_s.items(), key=lambda kv: -kv[1])[:n]]

    def op_seconds(self, pattern: str) -> float | None:
        """Summed self time of the operations whose name matches, or None
        where none does."""
        rx = re.compile(pattern)
        hit = [v for k, v in self.op_self_s.items() if rx.search(k)]
        return sum(hit) if hit else None

    def module_classes(self, pattern: str) -> list:
        """The seconds of every execution, one list per program whose name
        (with its fingerprint) matches."""
        rx = re.compile(pattern)
        return [ts for k, ts in self.module_s.items() if rx.search(k)]


def reduce(loaded: dict, chips: int) -> Reduced:
    win = [e for e in loaded["host"] if e[0] == WINDOW]
    if not win:
        raise SystemExit("benchmark: the trace holds no bench.window span")
    w0, w1 = win[0][1], win[0][1] + win[0][2]
    devices = dict(sorted((int(d), v) for d, v in
                          loaded["devices"].items())[:chips])
    if not devices:
        raise SystemExit("benchmark: the trace holds no device plane")
    busy, op_self, modules = {}, {}, {}
    gaps = []
    for dev, lines in devices.items():
        ops = _clip(lines["ops"], w0, w1)
        merged = _union([[s, s + d] for n, s, d in ops
                         if not CONTAINER.match(n)])
        busy[dev] = _length(merged) / 1e9
        for name, ns in _self_times(ops).items():
            op_self[name] = op_self.get(name, 0.0) + ns / 1e9
        for name, start, dur in lines["modules"]:
            if w0 <= start and start + dur <= w1:
                modules.setdefault(name, []).append(dur / 1e9)
        if dev == min(devices):
            gaps = _subtract([[w0, w1]], merged)
    spans = [e for e in loaded["host"] if e[0] != WINDOW]
    by_label = {}
    for a, b in gaps:
        if b - a < SHORT_GAP_NS:       # the breath between two operations
            by_label["between_ops"] = by_label.get(
                "between_ops", 0.0) + (b - a) / 1e9
            continue
        label, best = "unattributed", 0
        for name, s, d in spans:
            ov = min(b, s + d) - max(a, s)
            if ov > best:
                label, best = name, ov
        by_label[label] = by_label.get(label, 0.0) + (b - a) / 1e9
    return Reduced(
        window_s=(w1 - w0) / 1e9,
        busy_s=sum(busy.values()) / len(busy),
        busy_by_device=busy, op_self_s=op_self, module_s=modules,
        idle_gaps=[[k, v] for k, v in sorted(
            by_label.items(), key=lambda kv: -kv[1])[:10]])
