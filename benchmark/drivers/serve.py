"""The serving cells: ``ServingFrontend.submit()`` -> ``RequestStream``
events, in the process that holds the chip.

Beside the front-end's own loop thread, every caller is a thread of the
benchmark that submits and then blocks on its stream, stamping each token
event on the client's clock. An open loop starts a caller when its request
is due on the mix's schedule, whatever the engine does (a ``submit`` that
waits for the front-end holds up no other request); a closed loop's
callers each send their next request when the last one has finished, and
every round is ordered: caller ``k`` of ``C`` sends the plan's requests
``k, k + C, k + 2C, ...``, and the first round is admitted in that order.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from ..harness import check, traffic, weights
from ..harness.stats import ReqRecord
from ..harness.window import Run, Tracer, annotate, sleep_until

clock = time.perf_counter

COUNTERS = ("step_dispatches", "decode_steps", "prefill_chunks",
            "tokens_generated", "requests_finished", "preemptions",
            "prefix_hit_pages", "prefix_miss_pages", "rejections")


def llama_kwargs(cfg: dict) -> dict:
    """The published ``config.json`` keys that ``LlamaConfig`` takes, plus
    the configuration file's ``program`` group."""
    keys = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "max_position_embeddings",
            "rms_norm_eps", "rope_theta", "sliding_window",
            "tie_word_embeddings")
    kw = {k: cfg[k] for k in keys if cfg.get(k) is not None}
    kw["dtype"] = cfg["torch_dtype"]
    kw.update(cfg.get("program", {}))
    return kw


def build_model(cfg: dict, seed: int):
    """(model, the benchmark's weights). The model is described under
    ``LazyGuard`` (no initializer runs, on the host or the device) and
    given the benchmark's arrays leaf by leaf."""
    import paddle_tpu as P
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    P.seed(int(seed) % (2 ** 31 - 1))
    with P.LazyGuard():
        model = LlamaForCausalLM(LlamaConfig(**llama_kwargs(cfg)))
    w = weights.make(seed, cfg)
    place_weights(model, w, cfg)
    return model, w


def place_weights(model, w, cfg):
    params = dict(model.named_parameters())
    names = weights.program_names(cfg)
    if set(names.values()) != set(params):
        raise SystemExit(
            "benchmark: the model's parameters are not the reference's "
            f"leaves: {sorted(set(names.values()) ^ set(params))}")
    for path, name in names.items():
        p = params[name]
        arr = weights.get(w, path)
        if tuple(p.shape) != tuple(arr.shape):
            raise SystemExit(f"benchmark: {name} is {tuple(p.shape)}, the "
                             f"reference's leaf {tuple(arr.shape)}")
        p._data = arr
        if hasattr(p, "_lazy_init"):
            del p._lazy_init
    for lyr in model.sublayers(include_self=True):
        lyr.__dict__["_has_lazy_params"] = False


class Load:
    """The callers around one front-end, each a thread of its own that
    blocks on its stream as a connection's handler does: it submits,
    then stamps every token event on the client's clock as it arrives.
    Nothing polls, so the load takes the interpreter from the engine's
    loop thread only while an event is handed over."""

    def __init__(self, frontend, plan, mix):
        self.fe, self.plan, self.mix = frontend, plan, mix
        self.records: list[ReqRecord] = []
        self.errors: list[str] = []
        self._stop = threading.Event()
        self._give_up_at = float("inf")
        self._callers: list[threading.Thread] = []
        self.closed = mix["loop"] == "closed"
        if self.closed:
            n = int(mix["clients"])
            self._threads = [self._thread(self._closed_caller, k, n)
                             for k in range(n)]
        else:
            self._threads = [self._thread(self._open_schedule)]

    def _thread(self, fn, *args):
        return threading.Thread(target=self._guard, args=(fn, *args),
                                name=f"bench-{fn.__name__}", daemon=True)

    def _guard(self, fn, *args):
        try:
            fn(*args)
        except BaseException as e:          # surfaced by finish()
            self.errors.append(f"{fn.__name__}: {e!r}")

    def start(self):
        """A closed loop's first round is admitted in the plan's order: a
        caller is started when the one before it has been admitted.
        Started together, the callers wait for the front-end's lock while
        the loop thread steps, and the lock hands them over in no order:
        which of the first sizes hold the lanes then differs from run to
        run, and with it the window's share of chunk steps (measured,
        PERF.md section 6, PR 27: ten runs of one mix read 578-583
        tokens/s eight times and 569, 573 twice, by that alone)."""
        sched = self.fe.engine.scheduler
        self.t_start = clock()
        for i, t in enumerate(self._threads):
            t.start()
            # no request of the first round finishes inside it (at the
            # rehearsal's sizes one may: the deadline lets the next start)
            give_up = clock() + 2.0
            while (self.closed and clock() < give_up and not self.errors
                   and len(sched.waiting) + len(sched.live_requests()) <= i):
                time.sleep(0.0005)

    # -- one request, from its caller's thread ------------------------------------
    def _call(self, planned, due):
        from paddle_tpu.serving.frontend import Rejected, Unavailable
        rec = ReqRecord(planned.index, due, planned.prompt, planned.max_new)
        self.records.append(rec)
        rec.sent = clock()
        try:
            with annotate("submit"):
                stream = self.fe.submit(planned.prompt,
                                        max_new_tokens=planned.max_new,
                                        logprobs=True)
        except (Rejected, Unavailable, ValueError) as e:
            rec.error = repr(e)
            return rec
        try:
            # blocks until an event comes; wakes twice a second without
            # one, to see whether the client has given up
            for ev in stream.events(timeout=900.0, idle_s=0.5):
                now = clock()
                if ev["type"] == "token":
                    rec.stamps.append(now)
                    rec.tokens.append(int(ev["token"]))
                    if "logprob" in ev:
                        rec.logprobs.append(float(ev["logprob"]))
                elif ev["type"] == "finish":
                    rec.finished = now
                elif now > self._give_up_at:
                    rec.error = "never finished"
                    break
        except (RuntimeError, TimeoutError) as e:
            rec.error = repr(e)
        return rec

    # -- open loop: independent users on the mix's schedule -----------------------
    def _open_schedule(self):
        while True:
            planned = self.plan.next()
            due = self.t_start + planned.due
            if self._stop.wait(max(0.0, due - clock())):
                return
            t = self._thread(self._call, planned, due)
            self._callers.append(t)
            t.start()

    # -- closed loop: callers that wait for their answer --------------------------
    def _closed_caller(self, k: int, n: int):
        """Caller ``k`` of ``n``: its requests are the plan's ``k, k + n,
        k + 2n, ...`` whatever order the others finish in."""
        index = k
        while not self._stop.is_set():
            if self._call(self.plan.at(index), clock()).error:
                self._stop.wait(0.01)       # refused: ask again, not spin
            index += n

    def finish(self, wait_s: float) -> float:
        """Stop sending, wait for every open request (at most ``wait_s``
        past now), return when the client gave up or all were in."""
        self._stop.set()
        self._give_up_at = clock() + wait_s
        for t in self._threads:
            t.join(timeout=wait_s + 30)
        for t in self._callers:
            t.join(timeout=max(0.0, self._give_up_at + 30 - clock()))
        if any(t.is_alive() for t in self._threads + self._callers):
            self.errors.append("a benchmark thread did not stop")
        return clock()


def _snapshot(engine) -> dict:
    engine._sync_prefix_metrics()
    m = engine.metrics
    snap = {k: float(getattr(m, k).value) for k in COUNTERS}
    snap["batch_size_sum"] = float(m.batch_size.total)
    snap["batch_size_count"] = float(m.batch_size.count)
    return snap


def warm_up(frontend, cfg, seed):
    """Both step classes (decode-only and decode beside a prefill chunk)
    through the front-end: a few requests of two chunks each, together."""
    rng = np.random.default_rng([int(seed), 0x3A23])
    eng = cfg["engine"]
    plen = min(2 * int(eng["prefill_chunk"]) + 3,
               int(eng["max_seq_len"]) - 8)
    streams = [frontend.submit(
        rng.integers(0, cfg["vocab_size"], plen, dtype=np.int32),
        max_new_tokens=6)
        for _ in range(3)]
    for s in streams:
        s.result(timeout=1100.0)


def sweep_view(run_) -> dict:
    """What a rate sweep reads to find the knee (chip runs only)."""
    from ..harness import readers, stats
    ttft = stats.ttft_s(run_.records, run_.t0, run_.t1, run_.gave_up_at)
    return {"tok_s": readers.serve_tok_s(run_),
            "ttft_p50_ms": 1e3 * float(np.median(ttft)),
            "ttft_p95_ms": readers.ttft_p95_ms(run_),
            "gap_p95_ms": readers.gap_p95_ms(run_),
            "late_p95_ms": readers.gen_late_p95_ms(run_),
            "open_at_close": sum(1 for r in run_.records
                                 if r.finished is None
                                 or r.finished > run_.t1),
            "counters": run_.counters}


def run(cell, args, ctx) -> dict:
    from paddle_tpu.serving import ServingEngine
    from paddle_tpu.serving.frontend import ServingFrontend

    cfg, mix = ctx["cfg"], ctx["mix"]
    eng_kw = dict(cfg["engine"])
    max_queued = int(eng_kw.pop("max_queued", 64))
    model, w = build_model(cfg, args.seed)
    model.eval()
    engine = ServingEngine(model, ragged=True, eos_token_id=None, **eng_kw)
    frontend = ServingFrontend(engine, max_queued=max_queued).start()
    warm_up(frontend, cfg, args.seed)
    plan = traffic.Plan(mix, args.seed, cfg["vocab_size"],
                        int(eng_kw["max_seq_len"]))
    tracer = Tracer(cell.root, bool(args.trace) and not ctx["rehearse"])
    seconds = float(args.seconds)
    if args.trace:
        seconds = min(seconds, float(mix.get("trace_seconds", 6.0)))
    load = Load(frontend, plan, mix)

    run_ = Run(cfg=cfg, mix=mix, peaks=ctx["peaks"], chips=cell.chips)
    load.start()
    sleep_until(load.t_start + float(mix.get("ramp_seconds", 0.0)))
    # -- the window -----------------------------------------------------------
    run_.setup_s = clock() - ctx["t_process"]
    ctx["compiles"].mark()
    tracer.start()
    run_.t0 = clock()
    before = _snapshot(engine)
    sleep_until(run_.t0 + seconds)
    after = _snapshot(engine)
    run_.t1 = clock()
    tracer.stop()
    run_.compiles_in_window = ctx["compiles"].since_mark()
    # -------------------------------------------------------------------------
    run_.gave_up_at = load.finish(wait_s=float(mix.get("drain_seconds", 60)))
    run_.memory_peak_bytes = ctx["memory_peak"]()
    run_.records = load.records
    run_.counters = {k: after[k] - before[k] for k in after}
    run_.counters["step_program_classes"] = float(
        engine.metrics.step_program_classes.value)
    frontend.close(timeout=30.0)
    if load.errors or frontend.error is not None:
        raise SystemExit(f"benchmark: the load or the engine loop failed: "
                         f"{load.errors} {frontend.error!r}")
    run_.trace = tracer.reduce(cell.chips)

    # -- free the program, then the reference ---------------------------------
    del frontend, engine, model, load
    gc.collect()
    window = [r for r in run_.records if run_.t0 <= r.due < run_.t1]
    sample = check.pick_sample(window or run_.records,
                               int(mix.get("check_requests", 4)), args.seed)
    numbers = check.served_against_reference(
        w, cfg, sample, pad_to=int(eng_kw["max_seq_len"]),
        control=cfg.get("control_precision", "int8")
        if ctx.get("control") else None)
    numbers["unfinished_requests"] = float(
        sum(1 for r in window if not r.ok))
    if ctx["peaks"] is not None and (args.set or args.control):
        numbers["_info"] = sweep_view(run_)   # a sweep's or a control's view
    return {"run": run_, "numbers": numbers,
            "attempted": len(window),
            "failed": sum(1 for r in window if not r.ok)}
