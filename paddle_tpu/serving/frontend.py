"""Thread-safe bridge between concurrent clients and the single-threaded
:class:`~paddle_tpu.serving.engine.ServingEngine` loop.

The engine is strictly single-threaded (host bookkeeping + a jit step);
the front-end owns it behind ONE lock and a dedicated loop thread:

- ``submit()`` (any thread) admits a request under the lock and returns
  a :class:`RequestStream` — a queue the loop thread feeds via the
  engine's ``on_event`` callback, so tokens stream out as they are
  sampled (no drain-then-return).
- **Load shedding** (the no-preemption envelope): a submission is
  REJECTED (:class:`Rejected` → HTTP 429) when the waiting queue is at
  ``max_queued`` or when reserving the request's WORST-CASE page need
  (full prompt+max_new_tokens, ×n for forks) on top of every already
  accepted request's outstanding reservation would dip into the
  scheduler watermark. Reservation admission is deliberately more
  conservative than the engine's own history+1 watermark check: every
  accepted request can grow to completion without the allocator ever
  raising OutOfPages, so an over-capacity burst is shed with 429s and
  NEVER evicts a running decode. (Direct engine users keep the
  preemption elasticity; the shed gate is a front-end policy.)
- ``cancel()`` (any thread) frees the request's pages and purges the
  scheduler queues synchronously under the lock.
- ``drain()`` stops admissions (:class:`Unavailable` → HTTP 503),
  finishes all in-flight work, then parks the loop thread.
- The loop SURVIVES injected step faults (engine.FaultInjected — the
  hook fires before any state mutation, so the step is retried); any
  other loop exception is fatal: live pages are released
  (``engine.release_live``), every open stream gets an error event, and
  the front-end reports ``"failed"``.

Capacity math and engine state are only ever read/written under the
lock, so a submission races neither the step loop nor other submitters.
The lock is held across a whole engine step — including the first-call
jit trace — so a submit may block for one step duration; that IS the
backpressure.
"""
from __future__ import annotations

import json
import logging
import os
import queue
import threading
import time

import numpy as np

from .engine import FaultInjected

_log = logging.getLogger("paddle_tpu.serving")

__all__ = ["Rejected", "RequestStream", "ServingFrontend", "Unavailable"]


class Rejected(RuntimeError):
    """Load-shed admission (maps to HTTP 429: retry later)."""


class Unavailable(RuntimeError):
    """Front-end draining or failed (maps to HTTP 503)."""


class RequestStream:
    """Per-submission event stream. For ``n>1`` sampling the forked
    children's events arrive on the SAME stream, tagged with a stable
    ``index`` (0 = the submitted parent, 1.. = forks in creation order);
    the stream completes after ``n`` finish events."""

    def __init__(self, req_id, n=1):
        self.req_id = req_id
        self.n = int(n)
        self._q: queue.SimpleQueue = queue.SimpleQueue()
        self._ids = {req_id: 0}
        self._finished = 0
        self.error = None

    # -- loop-thread side --------------------------------------------------
    def _index_for(self, rid):
        if rid not in self._ids:
            self._ids[rid] = len(self._ids)
        return self._ids[rid]

    def _push(self, ev):
        if ev["type"] == "finish":
            self._finished += 1
        self._q.put(ev)

    def _fail(self, exc):
        self.error = exc
        self._q.put({"type": "error", "message": str(exc)})

    @property
    def done(self):
        return self._finished >= self.n

    def all_ids(self):
        """Every req_id feeding this stream (parent + known forks)."""
        return list(self._ids)

    # -- client side -------------------------------------------------------
    def events(self, timeout=120.0, idle_s=None):
        """Yield event dicts ({"type": "token"|"finish", "index", ...})
        until all n samples finished. Raises TimeoutError when no event
        lands within ``timeout`` seconds, RuntimeError when the engine
        loop died. With ``idle_s`` set, a ``{"type": "idle"}`` event is
        yielded whenever no real event arrived for that long (the SSE
        keepalive hook: the server turns idles into ``: ping`` comment
        frames, which is ALSO how client disconnects are detected in
        bounded time while decode or prefill stalls)."""
        finishes = 0
        last = time.monotonic()
        while finishes < self.n:
            wait = timeout if idle_s is None else min(idle_s, timeout)
            try:
                ev = self._q.get(timeout=wait)
            except queue.Empty:
                if idle_s is not None \
                        and time.monotonic() - last < timeout:
                    yield {"type": "idle"}
                    continue
                raise TimeoutError(
                    f"request {self.req_id}: no event within "
                    f"{timeout}s") from None
            if ev["type"] == "error":
                raise RuntimeError(
                    f"engine loop failed: {ev['message']}")
            last = time.monotonic()
            yield ev
            if ev["type"] == "finish":
                finishes += 1

    def result(self, timeout=120.0):
        """Block until complete; returns a list of n dicts
        ({"tokens", "finish_reason"}) ordered by sample index."""
        out = [{"tokens": [], "finish_reason": None}
               for _ in range(self.n)]
        for ev in self.events(timeout=timeout):
            slot = out[ev["index"]]
            if ev["type"] == "token":
                slot["tokens"].append(ev["token"])
            else:
                slot["finish_reason"] = ev["reason"]
        return out


ROLES = ("mixed", "prefill", "decode")


class ServingFrontend:
    def __init__(self, engine, *, max_queued=64, poll_interval_s=0.001,
                 role=None):
        if engine.on_event is not None:
            raise ValueError("engine already has an on_event consumer")
        engine.on_event = self._on_event
        self.engine = engine
        role = role or os.environ.get("PADDLE_TPU_SERVING_ROLE") \
            or "mixed"
        if role not in ROLES:
            raise ValueError(f"unknown role {role!r}; one of {ROLES}")
        # advertised in /healthz; a ROUTING intent, not a capability
        # limit — any engine can serve either phase, the disagg router
        # just routes prefill_only work to "prefill" replicas and page
        # adoptions to "decode" ones
        self.role = role
        self.max_queued = int(max_queued)
        self.poll_interval_s = float(poll_interval_s)
        # process identity (round 19, fleet control plane): /healthz
        # advertises pid + start time so a supervising backend (and a
        # recovering router's sweep) can tell a RESTARTED replica
        # process from the one that died — same host:port, new life
        self.started_unix = time.time()
        self.lock = threading.Lock()
        self.error = None
        self._streams: dict[int, RequestStream] = {}
        self._state = "ok"            # ok | draining | failed
        self._thread = None
        self._stop = threading.Event()
        self._drained = threading.Event()
        self._fault_streak = 0  # consecutive FaultInjected (escalation)
        # loop naps route through the engine's chaos sleeper so fault
        # schedules stay deterministic under a fake clock (graftlint
        # serving-raw-sleep); engines always carry one since round 17
        chaos = getattr(engine, "chaos", None)
        self._sleep = chaos.sleep if chaos is not None else time.sleep

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if self._thread is not None:
            raise RuntimeError("front-end already started")
        self._thread = threading.Thread(
            target=self._loop, name="serving-engine-loop", daemon=True)
        self._thread.start()
        return self

    @property
    def state(self):
        return self._state

    def drain(self, timeout=120.0):
        """Stop admissions, finish every in-flight request, stop the
        loop thread. Returns True when fully drained within timeout."""
        with self.lock:
            if self._state == "ok":
                self._state = "draining"
                self.engine.start_drain()
        ok = self._drained.wait(timeout)
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        return ok and self._state != "failed"

    def resume(self):
        """Rolling-drain re-admit: restart a DRAINED front-end (weight
        reloads happen in the drained window — weights are arguments of
        the compiled step, so the update flows through live). Raises
        unless the loop thread is parked and the state is recoverable."""
        if self._state == "failed":
            raise RuntimeError("cannot resume a failed front-end")
        if self._thread is not None and self._thread.is_alive():
            raise RuntimeError("front-end not drained: loop still live")
        self._thread = None
        self._stop.clear()
        self._drained.clear()
        self.engine.resume_admissions()
        self._state = "ok"
        return self.start()

    def fail(self, exc):
        """External failure injection (the router's replica-kill hook
        and the fault-escalation path): release live pages, error every
        open stream, flip to "failed", park the loop."""
        with self.lock:
            self._fail_locked(exc)
        self._stop.set()

    def close(self, timeout=120.0):
        return self.drain(timeout)

    # -- client API --------------------------------------------------------
    def submit(self, prompt, max_new_tokens=16, **kw):
        """Admit a request; returns a RequestStream. Raises Rejected
        (429) under load shed, Unavailable (503) when draining/failed,
        ValueError for malformed requests."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = int(kw.get("n", 1))
        with self.lock:
            if self._state != "ok":
                raise Unavailable(f"front-end is {self._state}")
            self._check_capacity(prompt, int(max_new_tokens), n,
                                 prefill_only=bool(
                                     kw.get("prefill_only")))
            rid = self.engine.add_request(
                prompt, max_new_tokens=int(max_new_tokens), **kw)
            stream = RequestStream(rid, n)
            self._streams[rid] = stream
        return stream

    def cancel(self, req_id):
        """Cancel a submission (parent + any forks on its stream);
        pages return to the free list before this call returns. True
        if anything was actually cancelled."""
        with self.lock:
            stream = self._streams.get(req_id)
            ids = stream.all_ids() if stream is not None else [req_id]
            hit = False
            for rid in ids:
                hit = self.engine.cancel(rid) or hit
        return hit

    def cancel_stream(self, stream):
        """Identity-checked cancel (round 19): engine req_ids are
        PER-ENGINE sequential ints, so a caller holding a stale stream
        handle — e.g. a router teardown racing a cross-replica
        failover — can alias a DIFFERENT live request's rid on this
        engine.  Cancel only if this exact stream object still owns
        its rid here; the identity check and the cancel share the lock
        so no new owner can slip in between (the fleet harness's
        exactness gate caught the unchecked version cancelling an
        innocent stream)."""
        with self.lock:
            if self._streams.get(stream.req_id) is not stream:
                return False
            hit = False
            for rid in stream.all_ids():
                hit = self.engine.cancel(rid) or hit
        return hit

    def health(self):
        with self.lock:
            eng = self.engine
            tier_stats = eng.tier_stats()
            return {"status": self._state,
                    "role": self.role,
                    "pid": os.getpid(),
                    "platform": getattr(eng, "platform", None),
                    "started_unix": self.started_unix,
                    "waiting": eng.scheduler.queue_depth(),
                    "live": len(eng.scheduler.live_requests()),
                    "held": len(eng._held),
                    "free_pages": eng.cache.free_pages,
                    "reserved_pages": self._reserved_pages(),
                    "speculative_k": getattr(eng, "spec_k", 0),
                    # quantized serving (round 15): the cache dtype is
                    # part of the migration geometry contract, so a
                    # disagg router can see dtype skew before a page
                    # transfer bounces on GeometryMismatch
                    "cache_dtype": getattr(eng, "cache_dtype",
                                           str(eng.cache.dtype)),
                    "weight_quant": getattr(eng, "weight_quant", None),
                    # tensor-parallel serving (round 23): the shard
                    # degree is part of the pagewire geometry contract
                    # (per-shard payload lists), so a router can bounce
                    # tp-skewed transfers up front — same shape as the
                    # dtype-skew guard
                    "tp_degree": getattr(eng, "tp_degree", 1),
                    "tp_mesh": getattr(eng, "tp_mesh_shape", None),
                    # fleet prefix cache (round 18): how much reusable
                    # prefix this replica holds — the router's transfer
                    # index consults these before scheduling a ship
                    "cached_pages": eng.cache.cached_pages,
                    "reclaimable_pages": eng.cache.reclaimable_pages,
                    "prefix_tree_depth": eng.cache.prefix_tree_depth,
                    # hierarchical KV tier (round 20): host-tier
                    # occupancy — a router can prefer a warm replica
                    # (kvtier is None without a tier; the flat page
                    # count rides top-level for cheap router reads)
                    "host_pool_pages": (tier_stats or
                                        {}).get("host_pool_pages", 0),
                    "kvtier": tier_stats,
                    # versioned live deployment (round 21): the weight
                    # version each set is serving.  MUTABLE mid-life —
                    # consumers must read it fresh every time (never
                    # the cache_dtype cached-once pattern); the
                    # router's version-pin guard depends on that
                    "weight_version": dict(
                        getattr(eng, "weight_version", None) or
                        {"target": 0, "draft": 0}),
                    "requests_finished":
                        eng.metrics.requests_finished.value}

    def load(self):
        """Routing load signal: outstanding worst-case page
        reservations (the same math the shed gate charges admissions
        against). 0 = idle; the router's least-loaded policy sorts on
        this, and /healthz exposes it as ``reserved_pages`` so HTTP
        replicas report the identical number."""
        with self.lock:
            return self._reserved_pages()

    def prometheus(self):
        """Refresh the point-in-time gauges and render the exposition."""
        with self.lock:
            eng = self.engine
            m = eng.metrics
            m.queue_depth_gauge.set(eng.scheduler.queue_depth())
            m.page_occupancy_gauge.set(eng.cache.occupancy())
            m.running_gauge.set(len(eng.scheduler.running))
            return m.to_prometheus()

    # -- observability (round 16): /debug/trace + /debug/flight ------------
    def debug_trace(self, request_id=None, req_id=None):
        """Serialized span timelines for one request (by X-Request-Id
        string or engine req_id) or, with neither, every retained
        timeline.  Reads under the engine lock — a scrape never races
        the step loop's appends."""
        with self.lock:
            return {"timelines": self.engine.trace.timelines(
                request_id=request_id, req_id=req_id)}

    def debug_flight(self):
        """The engine flight ring, oldest-first, plus counters."""
        with self.lock:
            flight = self.engine.trace.flight
            return {"events": flight.dump(),
                    "recorded": flight.recorded,
                    "cap": flight.cap}

    # -- KV page migration (disaggregated serving, round 14) ---------------
    # Export/import touch the cache's device buffers and host
    # bookkeeping, so every path below holds the SAME lock as the step
    # loop — a page import racing a step would scatter into buffers the
    # in-flight program is about to replace (enforced by graftlint
    # `page-migration-lock`).
    def probe_prefix(self, prompt, hist_len=None):
        """Radix-tree transfer index: how many leading prompt pages are
        already resident HERE (the exporter skips exactly these)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if hist_len is None:
            hist_len = prompt.size + 1
        with self.lock:
            return self.engine.cache.probe_prefix(prompt, hist_len)

    def export_request(self, req_id, skip_pages=0):
        """Export a held request's page chain (meta, k, v)."""
        with self.lock:
            return self.engine.export_request(req_id, skip_pages)

    def release_request(self, req_id):
        """Drop a held request's pages once the migration committed."""
        with self.lock:
            return self.engine.release_request(req_id)

    def adopt(self, meta, k_arrays, v_arrays, *, max_new_tokens, **kw):
        """Import a migrated page chain and continue decoding it here;
        returns a RequestStream that emits only NEW tokens (the prefill
        replica's tokens ride in ``meta["out_tokens"]``).  Sheds with
        Rejected when the imported chain plus its remaining decode
        growth cannot be reserved — the router then tries another
        decode replica."""
        with self.lock:
            if self._state != "ok":
                raise Unavailable(f"front-end is {self._state}")
            eng = self.engine
            cache = eng.cache
            prompt = np.asarray(meta["prompt"], np.int32).reshape(-1)
            need = cache.pages_for(prompt.size + int(max_new_tokens))
            need -= int(meta.get("skip_pages", 0))
            promised = self._reserved_pages()
            if need + promised + eng.scheduler.watermark_pages \
                    > cache.available_pages:
                eng.metrics.rejections.inc()
                raise Rejected(
                    f"over capacity: adoption needs {need} page(s), "
                    f"{cache.available_pages} available - {promised} "
                    f"reserved - {eng.scheduler.watermark_pages} "
                    "watermark")
            rid = eng.adopt_request(meta, k_arrays, v_arrays,
                                    max_new_tokens=int(max_new_tokens),
                                    **kw)
            stream = RequestStream(rid, 1)
            self._streams[rid] = stream
        return stream

    # -- fleet prefix transfer (round 18) ----------------------------------
    # Same locking contract as migration: prefix export/import touch
    # the cache's device buffers and radix tree, so they hold the
    # engine lock (graftlint `page-migration-lock` polices the cache/
    # engine-level calls; these wrappers are the blessed call shape).
    def export_prefix(self, prompt, skip_pages=0):
        """Export this replica's cached prefix of ``prompt`` (minus
        ``skip_pages`` leading pages the recipient already holds)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        with self.lock:
            return self.engine.export_prefix(prompt, skip_pages)

    def import_prefix(self, meta, k_arrays, v_arrays):
        """Land a shipped prefix payload here.  Sheds with Rejected
        when hosting the pages would dip into outstanding reservations
        + watermark — a prefix ship is an optimization and must never
        evict capacity live traffic has been promised."""
        with self.lock:
            if self._state != "ok":
                raise Unavailable(f"front-end is {self._state}")
            eng = self.engine
            need = int(meta.get("n_pages", 0))
            promised = self._reserved_pages()
            if need + promised + eng.scheduler.watermark_pages \
                    > eng.cache.available_pages:
                raise Rejected(
                    f"over capacity: prefix ship needs {need} page(s), "
                    f"{eng.cache.available_pages} available - "
                    f"{promised} reserved - "
                    f"{eng.scheduler.watermark_pages} watermark")
            return eng.import_prefix(meta, k_arrays, v_arrays)

    def drop_prefix(self, prompt):
        """Evict the unpinned cached chain for ``prompt`` (router
        dedup).  Returns the number of pages freed."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        with self.lock:
            return self.engine.drop_prefix(prompt)

    # -- hierarchical KV tier (round 20) -----------------------------------
    def restore_prefix(self, prompt):
        """Best-effort host-tier restore of ``prompt``'s missing prefix
        pages (probe order: local device → local host tier → remote
        donor → recompute).  Restored pages land CACHED at rc==0, so
        the shed gate's probe_prefix-based accounting covers them with
        no new case.  Returns pages restored (0 without a tier)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        with self.lock:
            return self.engine.restore_prefix(prompt)

    def prewarm_prefix(self, max_chains=None):
        """Restore the hottest spilled chains (autoscaler pre-warm of
        a freshly grown replica).  Returns pages restored."""
        with self.lock:
            return self.engine.prewarm_prefix(max_chains)

    # -- versioned live weight deployment (round 21) -----------------------
    def swap_weights(self, which, arrays, version):
        """The deployer's quiesce-swap — the ONE blessed multi-threaded
        path to ``engine.set_weights`` (graftlint ``weight-swap-lock``).
        The lock below is held across every engine step, so acquiring
        it IS the one-step quiesce: no compiled program can be
        mid-flight while the argument pytree changes, whether the loop
        is live (a mid-traffic draft refresh) or parked (a drained
        target rollout).  All-or-nothing and raising on a torn payload
        — the OLD version keeps serving on any failure.  Returns the
        number of stale-weight prefix pages flushed."""
        t0 = time.perf_counter()
        with self.lock:
            if self._state == "failed":
                raise Unavailable("front-end is failed")
            flushed = self.engine.set_weights(which, arrays, version)
            self.engine.metrics.weight_swap_s.record(
                time.perf_counter() - t0)
        return flushed

    def weight_version(self, which="target"):
        """Fresh read of the serving weight version (never cached —
        versions are mutable mid-life, unlike cache_dtype)."""
        return self.engine.weight_version.get(which)

    # -- internals ---------------------------------------------------------
    def _check_capacity(self, prompt, max_new, n, prefill_only=False):
        """Reservation admission (no-preemption envelope): reject when
        the waiting queue is full or the worst-case page need cannot be
        covered on top of all outstanding reservations + watermark.

        Prefix-cache accounting: the need counts only UNCACHED pages
        (``probe_prefix`` lookup — the matched pages are pinned by
        ``add_request`` under this same lock, so they cannot be evicted
        between this check and admission), and every queued request's
        reservation is likewise net of the pages it already holds
        pinned. Cached-but-unpinned pages count as capacity
        (``available_pages``) because eviction turns them into free
        pages on demand."""
        eng = self.engine
        sched, cache = eng.scheduler, eng.cache
        prompt_len = int(prompt.size)
        if sched.queue_depth() >= self.max_queued:
            eng.metrics.rejections.inc()
            if eng.trace.enabled:
                eng.trace.flight.record("shed", cause="queue_full",
                                        waiting=sched.queue_depth())
            raise Rejected(
                f"intake queue full ({self.max_queued} waiting)")
        # a prefill-only request stops after its first sampled token:
        # its worst case is prompt+1, never prompt+max_new — the
        # reservation asymmetry that makes a dedicated prefill replica
        # admit deep bursts a mixed replica would shed
        worst_new = 1 if prefill_only else max_new
        need = cache.pages_for(prompt_len + worst_new) * n
        need -= cache.probe_prefix(prompt)  # shared across the n forks
        promised = self._reserved_pages()
        if need + promised + sched.watermark_pages \
                > cache.available_pages:
            eng.metrics.rejections.inc()
            if eng.trace.enabled:
                eng.trace.flight.record("shed", cause="over_capacity",
                                        need=need, reserved=promised)
            raise Rejected(
                f"over capacity: need {need} page(s), "
                f"{cache.available_pages} available - {promised} "
                f"reserved - {sched.watermark_pages} watermark")

    def _reserved_pages(self):
        """Sum of every accepted request's outstanding worst-case page
        reservation (full prompt+max_new ×n, net of pages already
        held). Call under the lock."""
        eng = self.engine
        cache, sched = eng.cache, eng.scheduler
        promised = 0
        for r in list(sched.live_requests()) + list(sched.waiting):
            worst_new = 1 if r.prefill_only else r.max_new_tokens
            promised += max(
                0, cache.pages_for(r.prompt.size + worst_new)
                * r.n - cache.pages_held(r.seq_id))
        return promised

    def _on_event(self, ev):
        # runs in whichever thread holds the lock and drives the engine
        # (the loop thread via step(), a handler thread via cancel())
        rid = ev["req_id"]
        stream = self._streams.get(rid)
        if stream is None:
            req = self.engine.request(rid)
            pid = getattr(req, "parent_id", None)
            if pid is None or pid not in self._streams:
                return  # not a front-end submission
            stream = self._streams[pid]
            self._streams[rid] = stream
        stream._push(dict(ev, index=stream._index_for(rid)))
        if ev["type"] == "finish" and stream.done:
            for r in stream.all_ids():
                self._streams.pop(r, None)

    def _loop(self):
        eng = self.engine
        try:
            while not self._stop.is_set():
                with self.lock:
                    if self._state == "failed":
                        return  # externally killed (fail()); stop cold
                    idle = eng.scheduler.all_done()
                    if not idle:
                        try:
                            eng.step()
                            self._fault_streak = 0
                        except FaultInjected as exc:
                            # counted; boundary fault — retry next. But
                            # a fault STREAK means the replica is sick,
                            # not unlucky: escalate to a loop failure
                            # (streams error out, the router fails the
                            # requests over to a healthy replica). The
                            # threshold rides ChaosConfig (the legacy
                            # FAULT_ESCALATE_N env knob aliases in)
                            self._fault_streak += 1
                            esc = self._escalate_n()
                            if esc and self._fault_streak >= esc:
                                self._fail_locked(RuntimeError(
                                    f"fault escalation after "
                                    f"{self._fault_streak} consecutive "
                                    f"faults: {exc}"))
                                return
                        except Exception as exc:  # fatal: clean + report
                            self._fail_locked(exc)
                            return
                    elif self._state == "draining":
                        # quiesce: a live chaos alloc-pressure spike
                        # must not outlive the drained loop
                        eng._release_chaos_spike()
                        return
                    else:
                        # idle upkeep: held-deadline sweep + chaos
                        # alloc-spike countdown — a pure prefill
                        # replica idles between handoffs, and its held
                        # pages must still expire on deadline
                        eng.chaos_idle_tick()
                # idle: nap off-lock; busy: yield so submitters can
                # grab the lock between steps
                self._sleep(self.poll_interval_s if idle else 0)
        finally:
            self._drained.set()

    def _escalate_n(self):
        chaos = getattr(self.engine, "chaos", None)
        if chaos is None:
            return int(os.environ.get(
                "PADDLE_TPU_SERVING_FAULT_ESCALATE_N", "0") or 0)
        return int(chaos.cfg.escalate_n)

    def _fail_locked(self, exc):
        self._state = "failed"
        self.error = exc
        trace = self.engine.trace
        if trace.enabled:
            # the flight-recorder dump: the ring holds the failing
            # step's batch composition (step_begin precedes the device
            # work), so the round-9/11 loop-failure classes are
            # post-mortem-able from the structured log alone
            trace.flight.record("loop_error", error=repr(exc))
            _log.error(json.dumps({
                "event": "flight_recorder_dump",
                "error": repr(exc),
                "recorded": trace.flight.recorded,
                "events": trace.flight.dump()}))
        try:
            self.engine.release_live()
        except Exception:
            pass
        for stream in set(self._streams.values()):
            stream._fail(exc)
        self._streams.clear()
