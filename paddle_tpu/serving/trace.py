"""Serving-wide request tracing + the per-engine flight recorder.

The serving stack spans admission, chunked prefill, fused-sampling
decode, speculative rounds, preemption recompute, prefix-cache hits,
page migration, failover splicing and autoscaling — this module is the
layer that can SEE the other eight.  Reference capability:
paddle.profiler's RecordEvent spans + chrome export (SURVEY.md §5.1 —
`paddle_tpu.profiler` mirrors the API; serving now emits into the same
chrome://tracing event shape), and the per-phase TTFT/TPOT latency
decompositions the TPU serving literature reasons in (PAPERS.md
Gemma-on-TPU, Ragged Paged Attention step accounting).

Three pieces:

- **Request spans** (:class:`RequestTrace`): every request accumulates
  typed spans — ``queued``, ``prefill_chunk``, ``recompute``,
  ``decode_round``, ``spec_round`` (attrs carry proposed/accepted),
  ``preempted``, ``prefix_hit``, ``migration`` (attrs carry pages),
  ``failover_splice``, ``held`` — with MONOTONIC-clock start/dur and a
  small attr dict.  Emission is an append to a per-request list under
  the existing engine/frontend lock (no new locking — the graftlint
  engine-lock discipline is unchanged), capped per request
  (``PADDLE_TPU_SERVING_TRACE_SPANS``, default 512; overflow is
  COUNTED, never stored).  Contiguous decode/spec rounds COALESCE into
  one run-span (``rounds``/``accepted`` attrs accumulate; any other
  span type breaks the run) — per-token span dicts measurably drag the
  CPU decode marginal, coalesced runs are free, and the timeline keeps
  its phase structure exactly.  Each trace records a
  ``(wall, monotonic)`` anchor pair at creation so serialized spans
  carry ``t0_unix`` — what lets a router stitch spans from SEPARATE
  processes (HTTP replicas have unrelated perf_counter origins) into
  one timeline.  Trace context rides the existing ``X-Request-Id``
  plumbing (``Request.request_id``) across HTTPReplica hops and the
  pagewire export meta, so a disaggregated request's prefill-replica
  spans and decode-replica spans stitch into ONE timeline at the
  router.

- **Flight recorder** (:class:`FlightRecorder`): a fixed-size ring of
  recent engine events (``PADDLE_TPU_SERVING_TRACE_FLIGHT``, default
  256) — step begin (batch composition) / step end (wall time),
  admission, shed, preemption, fault injection, drain, loop error;
  round 17 adds ``chaos`` (injected fault firings), ``held_expired``
  (deadline-released held pages) and, on the router ring,
  ``breaker_open``.  The ring is dumped to the structured log on loop
  failure, on fault ESCALATION, and on a circuit-breaker open, so the
  round-9/11 failure classes are post-mortem-able without a rerun.

- **Chrome export**: completed timelines convert to chrome://tracing
  JSON via the same event dict shape ``paddle_tpu.profiler`` emits
  (``{"name", "ph": "X", "ts", "dur", "pid", "tid"}`` — microseconds),
  one pid per replica, one tid per request lane, so
  ``bench_serving.py --trace-out`` drops a trace
  ``paddle_tpu.profiler.load_profiler_result`` can re-open.

Overhead contract: tracing is ALWAYS ON by default and must stay in
the noise of the decode marginal (<3%, the BENCH_serving_trace gate);
``PADDLE_TPU_SERVING_TRACE=0`` disables span/flight emission entirely
(the overhead bench's control arm).  Nothing in this module touches a
device or takes a lock: callers emit under the lock they already hold.
"""
from __future__ import annotations

import json
import os
import time
from collections import deque

__all__ = ["FlightRecorder", "RequestTrace", "ServingTrace",
           "chrome_trace_events", "export_chrome_trace",
           "load_trace_export"]

TRACE_ENV = "PADDLE_TPU_SERVING_TRACE"
TRACE_SPANS_ENV = "PADDLE_TPU_SERVING_TRACE_SPANS"
TRACE_FLIGHT_ENV = "PADDLE_TPU_SERVING_TRACE_FLIGHT"
# round 19 (fleet control plane): completed timelines append to a
# size-capped JSONL file the moment they finish, so a fleet-harness run
# leaves a post-mortem artifact even after the process that owned the
# trace store dies (the OTLP follow-on's minimal file-based form)
TRACE_EXPORT_ENV = "PADDLE_TPU_SERVING_TRACE_EXPORT"
TRACE_EXPORT_MB_ENV = "PADDLE_TPU_SERVING_TRACE_EXPORT_MB"

# completed request traces retained per engine (oldest evicted): bounds
# the store under sustained traffic without a knob per dimension
_KEEP_FINISHED = 1024

# phase attribution for the finish-log breakdown (queue/prefill/decode/
# stall); span types not listed (prefix_hit, preempted, migration, …)
# are markers, not time owners
_QUEUE_SPANS = ("queued",)
_PREFILL_SPANS = ("prefill_chunk",)
# one step program, one span a lane kind: a plain decode lane's is
# decode_round, a verify lane's spec_round, the prefill lane's
# prefill_chunk or recompute
_DECODE_SPANS = ("decode_round", "spec_round")
_STALL_SPANS = ("recompute",)


def trace_enabled():
    """The always-on default: only an explicit =0/off disables."""
    return os.environ.get(TRACE_ENV, "1") not in ("0", "off", "false")


def span_cap():
    try:
        return max(8, int(os.environ.get(TRACE_SPANS_ENV, "512")))
    except ValueError:
        return 512


def flight_cap():
    try:
        return max(16, int(os.environ.get(TRACE_FLIGHT_ENV, "256")))
    except ValueError:
        return 256


def export_cap_bytes():
    try:
        mb = float(os.environ.get(TRACE_EXPORT_MB_ENV, "64") or 64)
    except ValueError:
        mb = 64.0
    return int(mb * 1024 * 1024)


class RequestTrace:
    """One request's span timeline.  Append-only, capped; overflow is
    counted in ``dropped`` (the timeline keeps its HEAD — the phase
    structure — and sheds the repetitive decode tail)."""

    __slots__ = ("req_id", "request_id", "spans", "dropped", "cap",
                 "anchor_wall", "anchor_mono", "marks")

    def __init__(self, req_id, request_id=None, cap=None,
                 anchor=None):
        self.req_id = req_id
        self.request_id = request_id
        self.cap = span_cap() if cap is None else int(cap)
        self.spans: list[dict] = []
        self.dropped = 0
        # (wall, monotonic) pair: spans store monotonic t0; export maps
        # to wall so cross-process timelines share a clock
        self.anchor_wall, self.anchor_mono = anchor or (
            time.time(), time.perf_counter())
        self.marks: dict = {}  # open-span bookkeeping (queued/held t0)

    def add(self, name, t0, dur=0.0, **attrs):
        if len(self.spans) >= self.cap:
            self.dropped += 1
            return
        span = {"name": name, "t0": float(t0), "dur": float(dur)}
        if attrs:
            span["attrs"] = attrs
        self.spans.append(span)

    def add_run(self, name, t0, dur, batch=None, **counters):
        """Coalescing append for the per-round span types
        (``decode_round``/``spec_round``): a CONTIGUOUS run of rounds
        extends the previous span in place — ``rounds`` counts them,
        counter attrs (accepted/proposed/…) accumulate, ``batch``
        tracks the latest composition — instead of allocating one span
        per token.  This is the overhead contract's load-bearing move:
        per-token span dicts cost ~3% of the CPU decode marginal at
        toy scale (measured, BENCH_serving_trace), coalesced runs are
        noise.  Any differently-named span (preempted, migration,
        prefill_chunk, …) breaks the run, so the timeline keeps its
        phase structure exactly; per-step composition detail stays in
        the flight ring."""
        spans = self.spans
        if spans:
            last = spans[-1]
            if last["name"] == name:
                last["dur"] = float(t0) + float(dur) - last["t0"]
                a = last["attrs"]
                a["rounds"] += 1
                if batch is not None:
                    a["batch"] = batch
                for k, v in counters.items():
                    a[k] = a.get(k, 0) + v
                return
        attrs = {"rounds": 1}
        if batch is not None:
            attrs["batch"] = batch
        attrs.update(counters)
        self.add(name, t0, dur, **attrs)

    def to_wall(self, t0):
        return self.anchor_wall + (float(t0) - self.anchor_mono)

    def total(self, names):
        return sum(s["dur"] for s in self.spans if s["name"] in names)

    def phase_breakdown(self):
        """The finish-log latency decomposition: wall seconds per
        phase, derived purely from the accumulated spans."""
        return {
            "queue_s": round(self.total(_QUEUE_SPANS), 6),
            "prefill_s": round(self.total(_PREFILL_SPANS), 6),
            "decode_s": round(self.total(_DECODE_SPANS), 6),
            "stall_s": round(self.total(_STALL_SPANS), 6),
        }

    def to_json(self):
        spans = []
        for s in self.spans:
            out = dict(s, t0_unix=self.to_wall(s["t0"]))
            spans.append(out)
        return {"req_id": self.req_id, "request_id": self.request_id,
                "spans": spans, "dropped": self.dropped}


class FlightRecorder:
    """Fixed-size ring of recent engine events.  ``record`` stamps each
    event with wall time; ``dump`` returns the ring oldest-first."""

    def __init__(self, cap=None):
        self._ring: deque = deque(maxlen=(flight_cap() if cap is None
                                          else int(cap)))
        self.recorded = 0

    @property
    def cap(self):
        return self._ring.maxlen

    def record(self, kind, **fields):
        self.recorded += 1
        ev = {"t_unix": time.time(), "kind": kind}
        ev.update(fields)
        self._ring.append(ev)

    def dump(self):
        return list(self._ring)


class ServingTrace:
    """Per-engine trace store: request timelines + the flight ring.

    All mutation happens from whichever thread drives the engine —
    i.e. under the front-end lock (or a single-threaded direct driver),
    exactly like the metrics objects; this class adds NO locking of its
    own.  ``enabled`` is resolved once at construction (engines are
    built per config; the overhead bench builds its control engine
    under PADDLE_TPU_SERVING_TRACE=0)."""

    def __init__(self, span_cap_=None, flight_cap_=None, enabled=None,
                 export_path=None):
        self.enabled = trace_enabled() if enabled is None else enabled
        self._span_cap = span_cap_
        self.flight = FlightRecorder(flight_cap_)
        self._requests: dict = {}          # req_id -> RequestTrace
        self._by_request_id: dict = {}     # request_id -> [req_id, ...]
        self._done: deque = deque()        # finished req_ids, FIFO
        # one anchor per store: every request trace shares it, so spans
        # from the same engine are mutually ordered exactly
        self._anchor = (time.time(), time.perf_counter())
        # file-based trace export (round 19): each finished timeline
        # appends its chrome-trace records as JSONL lines, flushed per
        # line — the artifact survives the owner's death.  Size-capped;
        # strictly best-effort (an unwritable path never fails serving)
        if export_path is None:
            export_path = os.environ.get(TRACE_EXPORT_ENV) or None
        self.export_path = export_path
        self._export_file = None
        self._export_bytes = 0
        self.export_written = 0     # records written
        self.export_dropped = 0     # records dropped at the size cap
        if self.export_path:
            try:
                self._export_bytes = os.path.getsize(self.export_path)
            except OSError:
                self._export_bytes = 0

    # -- request lifecycle -------------------------------------------------
    def begin(self, req_id, request_id=None):
        if not self.enabled or req_id in self._requests:
            return self._requests.get(req_id)
        tr = RequestTrace(req_id, request_id, cap=self._span_cap,
                          anchor=self._anchor)
        self._requests[req_id] = tr
        if request_id is not None:
            self._by_request_id.setdefault(str(request_id),
                                           []).append(req_id)
        return tr

    def get(self, req_id):
        return self._requests.get(req_id)

    def span(self, req_id, name, t0, dur=0.0, **attrs):
        tr = self._requests.get(req_id)
        if tr is not None:
            tr.add(name, t0, dur, **attrs)

    def run_span(self, req_id, name, t0, dur, batch=None, **counters):
        tr = self._requests.get(req_id)
        if tr is not None:
            tr.add_run(name, t0, dur, batch=batch, **counters)

    def mark(self, req_id, key, value):
        tr = self._requests.get(req_id)
        if tr is not None:
            tr.marks[key] = value

    def pop_mark(self, req_id, key):
        tr = self._requests.get(req_id)
        if tr is None:
            return None
        return tr.marks.pop(key, None)

    def finish(self, req_id):
        """Mark a request's timeline complete; evict the oldest
        finished traces beyond the retention bound.  Returns the trace
        (for the finish-log phase breakdown)."""
        tr = self._requests.get(req_id)
        if tr is None:
            return None
        if self.export_path:
            self._export(tr)
        self._done.append(req_id)
        while len(self._done) > _KEEP_FINISHED:
            old = self._done.popleft()
            dead = self._requests.pop(old, None)
            if dead is not None and dead.request_id is not None:
                ids = self._by_request_id.get(str(dead.request_id))
                if ids is not None:
                    try:
                        ids.remove(old)
                    except ValueError:
                        pass
                    if not ids:
                        del self._by_request_id[str(dead.request_id)]
        return tr

    # -- file export (round 19) --------------------------------------------
    def _export(self, tr):
        """Append one finished timeline's chrome-trace records as JSONL
        lines (one ``ph:"X"`` event per line, the
        :func:`chrome_trace_events` shape, so ``{"traceEvents":
        load_trace_export(path)}`` opens in chrome://tracing).  Caller
        holds the engine/frontend lock (finish() runs under it).  Lines
        are flushed immediately — the file is the post-mortem artifact
        a dead router leaves behind.  Failures are swallowed: export is
        an observability tap, never a serving dependency."""
        try:
            events = chrome_trace_events([tr.to_json()])
            payload = "".join(
                json.dumps(ev, separators=(",", ":")) + "\n"
                for ev in events)
            data = payload.encode()
            if self._export_bytes + len(data) > export_cap_bytes():
                self.export_dropped += 1
                return
            if self._export_file is None:
                self._export_file = open(self.export_path, "ab")
            self._export_file.write(data)
            self._export_file.flush()
            self._export_bytes += len(data)
            self.export_written += 1
        except (OSError, ValueError, TypeError):
            self.export_dropped += 1

    # -- query -------------------------------------------------------------
    def timelines(self, request_id=None, req_id=None):
        """Serialized timelines.  ``request_id`` (the X-Request-Id
        string) may match several engine requests (forks, re-
        submissions); ``req_id`` addresses exactly one; neither returns
        every retained timeline."""
        if req_id is not None:
            tr = self._requests.get(req_id)
            return [tr.to_json()] if tr is not None else []
        if request_id is not None:
            ids = self._by_request_id.get(str(request_id), [])
            return [self._requests[r].to_json() for r in ids
                    if r in self._requests]
        return [tr.to_json() for tr in self._requests.values()]


# -- chrome://tracing export ------------------------------------------------

def chrome_trace_events(timelines, pid=0, pid_name=None):
    """Convert serialized timelines (``RequestTrace.to_json`` dicts,
    each span carrying ``t0_unix``) into chrome trace events — the SAME
    event shape ``paddle_tpu.profiler`` emits (``ph: "X"``, ts/dur in
    microseconds): one ``pid`` per replica, one ``tid`` per request
    lane, plus thread-name metadata so the lanes are labelled."""
    events = []
    for tl in timelines:
        tid = tl["req_id"] if isinstance(tl["req_id"], int) \
            else abs(hash(tl["req_id"])) % (1 << 31)
        label = (f"req {tl['req_id']}"
                 + (f" [{tl['request_id']}]" if tl.get("request_id")
                    else ""))
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": label}})
        for s in tl["spans"]:
            events.append({
                "name": s["name"], "ph": "X",
                "ts": s["t0_unix"] * 1e6,
                "dur": max(s["dur"], 0.0) * 1e6,
                "pid": pid, "tid": tid,
                "args": dict(s.get("attrs", {}))})
    if pid_name is not None:
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": pid_name}})
    return events


def load_trace_export(path):
    """Read a ``PADDLE_TPU_SERVING_TRACE_EXPORT`` JSONL artifact back
    into a chrome event list.  A torn final line (the writer died
    mid-append) is skipped, not an error — the file exists precisely
    for post-mortems of processes that did not exit cleanly.  Wrap the
    result as ``{"traceEvents": events}`` to open it in
    chrome://tracing."""
    events = []
    with open(path, "rb") as f:
        for raw in f:
            if not raw.endswith(b"\n"):
                break  # torn tail: the writer died mid-line
            try:
                events.append(json.loads(raw))
            except ValueError:
                continue  # interleaved/garbled line: skip, keep reading
    return events


def export_chrome_trace(path, timelines_by_pid):
    """Write ``{"traceEvents": [...]}`` chrome JSON.
    ``timelines_by_pid``: iterable of ``(pid, pid_name, timelines)``.
    The file round-trips through
    ``paddle_tpu.profiler.load_profiler_result``."""
    events = []
    for pid, pid_name, timelines in timelines_by_pid:
        events.extend(chrome_trace_events(timelines, pid=pid,
                                          pid_name=pid_name))
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return path
