"""Continuous-batching serving bench: replay a synthetic Poisson arrival
trace through `paddle_tpu.serving.ServingEngine` on a small LLaMA-family
model and report throughput + latency.

Usage: python bench_serving.py [n_requests] [rate_per_s] [max_new]
                               [--smoke] [--server] [--shared-prefix]
                               [--router] [--spec] [--disagg] [--kv8]
                               [--trace] [--trace-out FILE]
                               [--prefix-fleet] [--kvtier] [--tp]

`--tp` measures tensor-parallel SPMD serving (round 23): the same
Poisson trace replays through one warm engine per shard degree
(TP ∈ {1, 2} smoke, {1, 2, 4} full) on the 8-device CPU mesh, a
two-point marginal each, with the token-exactness gate (every TP
degree's greedy streams identical to TP=1) riding the bench.  The CPU
mesh proves exactness and baselines collective overhead — virtual
host devices share cores, so TP>1 marginals are expected BELOW TP=1
here.  Banks BENCH_serving_tp.json (non-smoke only).

`--kvtier` measures the round-20 hierarchical KV tier: a round-robin
revisit schedule over MORE distinct long-prompt chains than the device
page pool holds (every revisit finds its prefix pages LRU-evicted), on
a prefill-heavy model (h256/L4 — the round-18 lesson: at h128 a
prefill chunk costs about a page copy and restore-vs-recompute
measures nothing). The same trace replays at ≥3 host-pool sizes
INCLUDING pool=0 (the tierless recompute baseline) plus a
RAM+disk point; per size the artifact records revisit-TTFT
percentiles, the tier hit rate, and spill/restore/demotion counters.
The acceptance gate (asserted on quiet-VM non-smoke runs): the
full-coverage pool's revisit TTFT p50 beats the pool=0 recompute
baseline. Banks BENCH_serving_kvtier.json.

`--prefix-fleet` measures the round-18 fleet-wide prefix cache: the
shared-prefix workload through a 2-replica fleet in three configs —
cache-aware local hits (ships off), least-loaded recompute (ships
off), least-loaded with prefix SHIPS on (the router moves the cached
prefix pages over the pagewire path to the replica it places each
request on, so only the unique tail is prefilled). Client-side TTFT
per config + two-point marginals; greedy AND seeded-sampled streams
are asserted token-exact vs a single-engine oracle through the ships.
Banks BENCH_serving_prefix_fleet.json.

`--trace` is the round-16 observability OVERHEAD GUARD: the same
Poisson trace replays through two warm engines — tracing on (the
always-on default) and tracing off (PADDLE_TPU_SERVING_TRACE=0 at
engine construction) — two-point marginal each, and the artifact
records the on/off marginal ratio. The acceptance contract is that
span emission stays within noise (<3% of the trace-off marginal),
asserted on quiet-VM (non-smoke) runs; a chrome trace of the traced
replay is exported and round-tripped through
paddle_tpu.profiler.load_profiler_result. Banks
BENCH_serving_trace.json.

`--trace-out FILE` (offline mode) drops a chrome://tracing JSON of the
whole replay — one pid for the engine, one tid per request lane — that
chrome://tracing / Perfetto opens directly.

`--kv8` measures quantized serving (round 15) two ways. (1) MEMORY
PRESSURE: the same Poisson trace replays through a front-end whose
engine sizes its paged KV cache from a FIXED small `hbm_budget_mb`,
once with a bf16 cache and once with the int8 codes+scales cache —
equal budget, so the int8 engine simply HAS ~2*D/(D+4) more pages
(1.88x at head_dim 64). Shedding is client-visible 429s (no retry);
the claim is higher admitted concurrency / completed tokens and a
lower shed rate at the same budget, plus the usual two-point marginal.
(2) QUALITY GATE: a byte-level LM quick-trained on the repo's own docs
replays held-out NLL TEACHER-FORCED THROUGH THE SERVING ENGINE (one
cut position per request, logits probed after each `engine.run` — the
paged-attention dequant path end to end, prefix cache accelerating the
sweep) under bf16, int8, and int8+weight-only-int8; the bench asserts
|delta-NLL| < 0.01 vs the bf16 cache (the BENCH_kv8_quality recipe,
now through `serving/` instead of the generation path). Banks
BENCH_serving_kv8.json.

`--disagg` replays a MIXED workload — TTFT-heavy requests (long
prompt, 4-token decode) interleaved with TPOT-heavy ones (short
prompt, full decode budget) on one Poisson arrival process — through
TWO fleet topologies of identical size: 1 prefill + 2 decode replicas
behind a DisaggRouter (prefill-only admission, KV page migration,
spliced streams) vs 3 mixed replicas behind the round-11 least-loaded
router. Two-point marginal per topology (quarter vs full decode
budget on the SAME trace); client-side TTFT percentiles are reported
PER CLASS — the disagg claim is that the TTFT-heavy burst stops
queueing behind running decodes. Streams are asserted complete and
migration/fallback counters are banked. BENCH_serving_disagg.json.

`--spec` measures batched speculative decoding in the engine: a target
and an h128-class 1-layer draft are quick-trained on a deterministic
successor task (the acceptance-FAVORABLE workload — the bench measures
the mechanism's ceiling, the honest distilled-draft acceptance curve
lives in BENCH_spec_acceptance.json), then the SAME greedy Poisson
trace is replayed through a non-speculative and a speculative engine
(one WARM engine per config, two-point marginal each — the PR-3
recipe). Banks BENCH_serving_spec.json with both marginal decode rates,
the speedup, and the measured acceptance rate; greedy streams are
token-exact across the two engines by construction (deterministic-
sample verification), which the replay asserts.

`--router` replays the shared-prefix workload through a ServingRouter
over TWO in-process replicas (each its own engine + prefix cache),
round-robin vs cache-aware, and banks BENCH_serving_router.json: the
cache-aware policy must show a strictly higher aggregate prefix hit
rate and lower TTFT p50 (requests stick to the replica that holds the
cached pages). A third AVAILABILITY replay (3 replicas, cache-aware)
kills one replica mid-replay and records that every stream completed
via token-exact mid-stream failover (failovers/spliced counters).

`--shared-prefix` replays a shared-system-prompt workload (every request
carries the same long prefix + a short unique tail) TWICE — radix-tree
prefix cache off, then on — and banks BENCH_serving_prefix.json with
both TTFT distributions and both two-point-marginal decode rates. This
is the workload the prefix cache exists for: with the cache on, every
request after the first skips the shared prefix's prefill chunks
entirely (admission maps the cached pages and chunk-prefills only the
tail), so TTFT drops and the decode loop sees fewer prefill bubbles.

`--server` replays the SAME trace over real sockets: a ServingServer is
bound on an ephemeral localhost port and a thread-per-request load
generator POSTs `/v1/completions` with `stream=true`, collecting SSE
chunks (so the full front-end — HTTP parse, SSE framing, per-request
stream queues, the engine-loop lock — sits on the measured path). The
two-point marginal discipline is unchanged: fresh server per replay,
quarter vs full decode budget, marginal tokens/s. Artifact:
BENCH_serving_http.json (offline mode keeps BENCH_serving.json).

Measurement (PERF.md round-3 method): the decode rate is a TWO-POINT
MARGINAL — the SAME trace is replayed at a quarter decode budget and at
the full budget, and tokens/s = extra tokens / extra wall. That cancels
the fixed per-replay overhead (compile-cache warmup, host scheduling)
that otherwise understates the decode rate. TTFT percentiles come from
the full-budget replay (TTFT is budget-independent). Every engine step
ends in a host fetch of the sampled tokens.

Prints ONE JSON line and banks it to BENCH_serving.json. Without
`--smoke`/`--tp` (which select the CPU mesh explicitly; their metric
names end in `_cpu`) it needs a TPU and exits non-zero without one.
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

smoke = "--smoke" in sys.argv
if smoke:
    sys.argv.remove("--smoke")
server_mode = "--server" in sys.argv
if server_mode:
    sys.argv.remove("--server")
prefix_mode = "--shared-prefix" in sys.argv
if prefix_mode:
    sys.argv.remove("--shared-prefix")
router_mode = "--router" in sys.argv
if router_mode:
    sys.argv.remove("--router")
spec_mode = "--spec" in sys.argv
if spec_mode:
    sys.argv.remove("--spec")
disagg_mode = "--disagg" in sys.argv
if disagg_mode:
    sys.argv.remove("--disagg")
kv8_mode = "--kv8" in sys.argv
if kv8_mode:
    sys.argv.remove("--kv8")
trace_mode = "--trace" in sys.argv
if trace_mode:
    sys.argv.remove("--trace")
prefix_fleet_mode = "--prefix-fleet" in sys.argv
if prefix_fleet_mode:
    sys.argv.remove("--prefix-fleet")
kvtier_mode = "--kvtier" in sys.argv
if kvtier_mode:
    sys.argv.remove("--kvtier")
tp_mode = "--tp" in sys.argv
if tp_mode:
    sys.argv.remove("--tp")
    # the TP bench runs on the 8-device CPU mesh (the exactness
    # contract's reference geometry); the host-device-count flag is
    # read at XLA backend init, so it must land before any jax import
    import os as _os
    if "--xla_force_host_platform_device_count" not in \
            _os.environ.get("XLA_FLAGS", ""):
        _os.environ["XLA_FLAGS"] = (
            _os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
trace_out = None
if "--trace-out" in sys.argv:
    i = sys.argv.index("--trace-out")
    trace_out = sys.argv[i + 1]
    del sys.argv[i:i + 2]
n_requests = int(sys.argv[1]) if len(sys.argv) > 1 else (8 if smoke else 32)
rate = float(sys.argv[2]) if len(sys.argv) > 2 else 16.0
max_new = int(sys.argv[3]) if len(sys.argv) > 3 else (8 if smoke else 64)


def make_trace(n, rate, vocab, seed=0):
    """Poisson arrivals (exponential gaps) with mixed prompt lengths."""
    rng = np.random.default_rng(seed)
    gaps = rng.exponential(1.0 / rate, n)
    arrivals = np.cumsum(gaps)
    prompts = [rng.integers(0, vocab, int(rng.integers(8, 65)))
               .astype(np.int32) for _ in range(n)]
    return arrivals, prompts


def make_shared_prefix_trace(n, rate, vocab, prefix_len, seed=0):
    """Poisson arrivals; every prompt = one shared system prefix + a
    short unique tail (the agent/chat serving shape the prefix cache
    targets)."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n))
    shared = rng.integers(0, vocab, prefix_len).astype(np.int32)
    prompts = [np.concatenate(
        [shared, rng.integers(0, vocab, int(rng.integers(8, 17)))
         .astype(np.int32)]) for _ in range(n)]
    return arrivals, prompts


def replay(model, arrivals, prompts, new_tokens, engine=None,
           **engine_kw):
    """Wall-clock replay: requests join the engine when their arrival
    time passes; steps run continuously (idle steps are cheap). Pass
    ``engine=`` to reuse one across replays (jit caches stay warm —
    the shared-prefix bench measures steady state, not compiles)."""
    from paddle_tpu.serving import ServingEngine
    eng = engine if engine is not None else ServingEngine(model,
                                                          **engine_kw)
    t0 = time.perf_counter()
    pending = list(zip(arrivals, prompts))
    n_total = len(pending)
    done = 0
    done_tokens = 0
    while True:
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            _, p = pending.pop(0)
            eng.add_request(p, max_new_tokens=new_tokens)
        if not pending and eng.scheduler.all_done():
            break
        if eng.scheduler.all_done():
            time.sleep(min(0.002, max(0.0, pending[0][0] - now)))
            continue
        for ev in eng.step():
            if ev["type"] == "finish":
                done += 1
                done_tokens += ev["n_tokens"]
    wall = time.perf_counter() - t0
    assert done == n_total, (done, n_total)
    return wall, done_tokens, eng.metrics


def replay_http(model, arrivals, prompts, new_tokens, **engine_kw):
    """Wall-clock replay over real sockets: a fresh ServingServer per
    replay; one loader thread per request fires at its Poisson arrival
    time and streams `/v1/completions` SSE to completion."""
    import http.client
    import threading

    from paddle_tpu.serving import ServingEngine, ServingServer

    eng = ServingEngine(model, **engine_kw)
    srv = ServingServer(eng, max_queued=len(prompts) + 1)
    host, port = srv.start()
    counts = [0] * len(prompts)
    errors = []

    def fire(i, due, prompt, t0):
        time.sleep(max(0.0, due - (time.perf_counter() - t0)))
        try:
            c = http.client.HTTPConnection(host, port, timeout=600)
            c.request("POST", "/v1/completions", json.dumps(
                {"prompt": [int(t) for t in prompt],
                 "max_tokens": new_tokens, "stream": True}),
                {"Content-Type": "application/json"})
            r = c.getresponse()
            assert r.status == 200, r.status
            n = 0
            for raw in r:
                if raw.startswith(b"data: ") and b"token_id" in raw:
                    n += 1
            counts[i] = n
            c.close()
        except Exception as e:  # surfaced after join; bench must not hang
            errors.append((i, repr(e)))

    t0 = time.perf_counter()
    threads = [threading.Thread(target=fire, args=(i, a, p, t0),
                                daemon=True)
               for i, (a, p) in enumerate(zip(arrivals, prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    srv.close()
    assert not errors, errors[:4]
    assert all(n == new_tokens for n in counts), counts
    return wall, sum(counts), eng.metrics


def main():
    import jax
    if smoke or tp_mode:
        # the explicit CPU-mesh modes (tier-1 replay tests and
        # tools/*_smoke.sh): correctness and counts, never device times
        jax.config.update("jax_platforms", "cpu")
    else:
        from bench import require_tpu
        require_tpu()  # no TPU: non-zero exit, no metric
    import paddle_tpu as P
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    on_tpu = jax.devices()[0].platform == "tpu"
    prefix_len = 96  # shared-prefix mode: 6 pages of 16
    if prefix_fleet_mode and not smoke:
        prefix_len = 224  # 14 pages: the probe ships vs re-prefills it
    maxlen = (prefix_len + 16 if prefix_mode or router_mode
              or disagg_mode or prefix_fleet_mode else 64) + max_new + 1
    if on_tpu:
        cfg = LlamaConfig(vocab_size=32000, hidden_size=2048,
                          intermediate_size=5504, num_hidden_layers=8,
                          num_attention_heads=16,
                          max_position_embeddings=maxlen,
                          dtype="bfloat16")
        num_pages = 4096
    else:
        cfg = LlamaConfig(vocab_size=512, hidden_size=128,
                          intermediate_size=256, num_hidden_layers=2,
                          num_attention_heads=4,
                          max_position_embeddings=maxlen)
        num_pages = 1024
    P.seed(0)
    model = LlamaForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    model.eval()
    engine_kw = dict(page_size=16, num_pages=num_pages, max_batch=8,
                     prefill_chunk=32, max_seq_len=maxlen)

    if prefix_mode:
        _bench_shared_prefix(model, cfg, engine_kw, on_tpu)
        return
    if router_mode:
        _bench_router(cfg, engine_kw, on_tpu)
        return
    if spec_mode:
        _bench_speculative(on_tpu)
        return
    if disagg_mode:
        _bench_disagg(cfg, engine_kw, on_tpu)
        return
    if kv8_mode:
        _bench_kv8(on_tpu)
        return
    if trace_mode:
        _bench_trace_overhead(model, cfg, engine_kw, on_tpu)
        return
    if prefix_fleet_mode:
        _bench_prefix_fleet(cfg, engine_kw, on_tpu)
        return
    if kvtier_mode:
        _bench_kvtier(on_tpu)
        return
    if tp_mode:
        _bench_tp(model, cfg, engine_kw, on_tpu)
        return

    arrivals, prompts = make_trace(n_requests, rate, cfg.vocab_size)
    new_q = max(1, max_new // 4)
    run = replay_http if server_mode else replay

    # warmup: compile every bucketed program class off the clock
    warm_n = min(4, n_requests)
    run(model, np.zeros(warm_n), prompts[:warm_n], new_q, **engine_kw)
    run(model, np.zeros(warm_n), prompts[:warm_n], max_new,
        **engine_kw)

    wall_q, toks_q, _ = run(model, arrivals, prompts, new_q,
                            **engine_kw)
    if trace_out and not server_mode:
        # --trace-out: drive the full-budget replay through an explicit
        # engine so its span store survives the replay, then drop a
        # chrome://tracing JSON (one pid, one tid per request lane)
        from paddle_tpu.serving import ServingEngine, export_chrome_trace
        eng = ServingEngine(model, **engine_kw)
        wall, toks, metrics = run(model, arrivals, prompts, max_new,
                                  engine=eng)
        export_chrome_trace(
            trace_out, [(0, "serving-engine", eng.trace.timelines())])
        print(json.dumps({"event": "trace_exported", "path": trace_out,
                          "timelines": len(eng.trace.timelines())}))
    else:
        wall, toks, metrics = run(model, arrivals, prompts, max_new,
                                  **engine_kw)

    marginal = None
    if wall > wall_q and toks > toks_q:
        marginal = (toks - toks_q) / (wall - wall_q)
    e2e = toks / wall
    m = metrics.export()
    out = {
        "metric": ("serving_http_tok_per_s" if server_mode
                   else "serving_tok_per_s") + ("" if on_tpu else "_cpu"),
        "value": round(marginal, 1) if marginal else round(e2e, 1),
        "unit": "decode tokens/sec ("
                + ("HTTP/SSE front-end, " if server_mode else "")
                + "continuous batching, "
                + ("two-point marginal" if marginal else
                   "end-to-end — marginal unavailable") + ")",
        "n_requests": n_requests, "rate_per_s": rate,
        "max_new_tokens": max_new,
        "e2e_tok_per_s": round(e2e, 1),
        "wall_s": round(wall, 3), "wall_quarter_s": round(wall_q, 3),
        "ttft_p50_s": m["ttft_s"]["p50"],
        "ttft_p99_s": m["ttft_s"]["p99"],
        "inter_token_p50_s": m["inter_token_s"]["p50"],
        "page_occupancy_max": m["page_occupancy"]["max"],
        "preemptions": m["preemptions"],
        "deadline_evictions": m["deadline_evictions"],
        "smoke": smoke,
    }
    if server_mode:
        out["rejections"] = m["rejections"]
        out["cancellations"] = m["cancellations"]
    line = json.dumps(out)
    print(line)
    artifact = ("BENCH_serving_http.json" if server_mode
                else "BENCH_serving.json")
    with open(artifact, "w") as f:
        f.write(line + "\n")


def _bench_shared_prefix(model, cfg, engine_kw, on_tpu):
    """Cache-off vs cache-on replays of the shared-prefix trace, each a
    two-point marginal (PERF.md hygiene: quarter vs full decode budget
    cancels fixed per-replay overhead); TTFT percentiles come from the
    full-budget replays. One JSON line -> BENCH_serving_prefix.json."""
    prefix_len = 96
    arrivals, prompts = make_shared_prefix_trace(
        n_requests, rate, cfg.vocab_size, prefix_len)
    new_q = max(1, max_new // 4)

    def measure(prefix_cache):
        from paddle_tpu.serving import ServingEngine, ServingMetrics
        # ONE engine per config: warmup compiles every bucketed program
        # (and, cache-on, seeds the radix tree) so the measured replays
        # see steady state; metrics reset between replays
        eng = ServingEngine(model,
                            **dict(engine_kw, prefix_cache=prefix_cache))
        warm_n = min(8, n_requests)
        replay(model, np.zeros(warm_n), prompts[:warm_n], new_q,
               engine=eng)
        replay(model, np.zeros(warm_n), prompts[:warm_n], max_new,
               engine=eng)
        eng.metrics = ServingMetrics()
        wall_q, toks_q, _ = replay(model, arrivals, prompts, new_q,
                                   engine=eng)
        eng.metrics = ServingMetrics()
        c = eng.cache  # prefix counters are cumulative: delta the
        base = (c.prefix_hit_pages, c.prefix_miss_pages,  # full replay
                c.prefix_evictions)
        wall, toks, metrics = replay(model, arrivals, prompts, max_new,
                                     engine=eng)
        hit = c.prefix_hit_pages - base[0]
        miss = c.prefix_miss_pages - base[1]
        m = metrics.export()
        marginal = ((toks - toks_q) / (wall - wall_q)
                    if wall > wall_q and toks > toks_q else None)
        return {
            "tok_per_s_marginal": (round(marginal, 1)
                                   if marginal else None),
            "e2e_tok_per_s": round(toks / wall, 1),
            "wall_s": round(wall, 3),
            "ttft_p50_s": m["ttft_s"]["p50"],
            "ttft_p99_s": m["ttft_s"]["p99"],
            "prefill_chunks": m["prefill_chunks"],
            "prefix_hit_pages": hit,
            "prefix_miss_pages": miss,
            "prefix_evictions": c.prefix_evictions - base[2],
            "prefix_hit_rate": (round(hit / (hit + miss), 3)
                                if hit + miss else 0.0),
            "fetch_bytes": m["fetch_bytes"],
            "preemptions": m["preemptions"],
        }

    off = measure(False)
    on = measure(True)
    out = {
        "metric": "serving_prefix_ttft_p50_s"
                  + ("" if on_tpu else "_cpu"),
        "value": on["ttft_p50_s"],
        "unit": "s (shared-prefix workload, radix prefix cache ON; "
                "compare cache_off.ttft_p50_s)",
        "n_requests": n_requests, "rate_per_s": rate,
        "max_new_tokens": max_new, "shared_prefix_tokens": prefix_len,
        "page_size": engine_kw["page_size"],
        "cache_on": on, "cache_off": off,
        "ttft_p50_speedup": (round(off["ttft_p50_s"]
                                   / on["ttft_p50_s"], 2)
                             if on["ttft_p50_s"] else None),
        "smoke": smoke,
    }
    line = json.dumps(out)
    print(line)
    with open("BENCH_serving_prefix.json", "w") as f:
        f.write(line + "\n")


def _bench_router(cfg, engine_kw, on_tpu):
    """Router tier bench: shared-prefix workload across 2 in-process
    replicas, round-robin vs cache-aware (two-point marginal each,
    client-side TTFT), plus a kill-one-replica availability replay on
    3 replicas. One JSON line -> BENCH_serving_router.json."""
    import threading

    import paddle_tpu as P
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.serving import (InProcessReplica, ServingEngine,
                                    ServingRouter)

    prefix_len = 96
    arrivals, prompts = make_shared_prefix_trace(
        n_requests, rate, cfg.vocab_size, prefix_len)
    new_q = max(1, max_new // 4)

    def make_router(n, policy):
        # one model instance PER replica (identical weights via the
        # same seed): concurrent engine loops must never share a
        # module tree — first-call traces swap weight tensors in place
        replicas = []
        for _ in range(n):
            P.seed(0)
            m = LlamaForCausalLM(cfg)
            if on_tpu:
                m.to(dtype="bfloat16")
            m.eval()
            eng = ServingEngine(m, **dict(engine_kw, prefix_cache=True))
            replicas.append(InProcessReplica(
                eng, max_queued=len(prompts) + 8))
        # NOT started yet: warmup drives the engines directly (single
        # thread); router.start() spins the loop threads up afterwards
        return ServingRouter(replicas, policy=policy,
                             page_size=engine_kw["page_size"])

    def warm(router):
        # warm every bucketed program class per replica with NON-shared
        # prompts (same length mix), then flush the prefix caches: the
        # measured replay must see a COLD radix tree, else warmup seeds
        # the shared prefix on every replica and both policies trivially
        # hit 1.0 (the policy comparison would measure nothing)
        warm_rng = np.random.default_rng(1234)
        warm_prompts = [warm_rng.integers(
            0, cfg.vocab_size, int(p.size)).astype(np.int32)
            for p in prompts[:8]]
        for rep in router.replicas:
            for budget in (new_q, max_new):
                for p in warm_prompts:
                    rep.engine.add_request(p, max_new_tokens=budget)
                rep.engine.run()
            rep.engine.cache.clear_prefix()
        return router.start()

    def flush_prefix(router):
        for rep in router.replicas:
            rep.engine.cache.clear_prefix()

    def replay_router(router, arrivals, prompts, new_tokens,
                      kill=None):
        """Thread-per-request Poisson replay through the router;
        returns (wall, tokens, client-side ttft list). ``kill``:
        (replica_idx, after_seconds) availability drill."""
        ttfts = [None] * len(prompts)
        counts = [0] * len(prompts)
        errors = []
        killed = []
        t0 = time.perf_counter()

        def fire(i, due, prompt):
            time.sleep(max(0.0, due - (time.perf_counter() - t0)))
            try:
                sub = time.perf_counter()
                stream = router.submit(prompt,
                                       max_new_tokens=new_tokens)
                for ev in stream.events(timeout=600):
                    if ev["type"] == "token":
                        if ttfts[i] is None:
                            ttfts[i] = time.perf_counter() - sub
                        counts[i] += 1
            except Exception as e:
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=fire, args=(i, a, p),
                                    daemon=True)
                   for i, (a, p) in enumerate(zip(arrivals, prompts))]
        for t in threads:
            t.start()
        if kill is not None:
            time.sleep(kill)
            # kill the BUSIEST replica — the one whose death actually
            # exercises mid-stream failover
            idx = max(range(len(router.replicas)),
                      key=lambda i: router.replicas[i].load())
            router.kill_replica(idx)
            killed.append(idx)
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        assert not errors, errors[:4]
        # zero-loss property: every stream completed despite the kill
        assert all(c == new_tokens for c in counts), counts
        return wall, sum(counts), ttfts, killed

    def measure(policy):
        router = warm(make_router(2, policy))
        wall_q, toks_q, _, _ = replay_router(router, arrivals, prompts,
                                             new_q)
        # each replay starts prefix-COLD (the policy difference is how
        # many replicas must re-prefill the shared prefix per replay)
        flush_prefix(router)
        base = [(rep.engine.cache.prefix_hit_pages,
                 rep.engine.cache.prefix_miss_pages)
                for rep in router.replicas]
        wall, toks, ttfts, _ = replay_router(router, arrivals, prompts,
                                             max_new)
        hit = sum(rep.engine.cache.prefix_hit_pages - b[0]
                  for rep, b in zip(router.replicas, base))
        miss = sum(rep.engine.cache.prefix_miss_pages - b[1]
                   for rep, b in zip(router.replicas, base))
        marginal = ((toks - toks_q) / (wall - wall_q)
                    if wall > wall_q and toks > toks_q else None)
        routed = router.metrics.routed_total.export()
        router.close()
        tt = sorted(t for t in ttfts if t is not None)
        return {
            "tok_per_s_marginal": (round(marginal, 1)
                                   if marginal else None),
            "e2e_tok_per_s": round(toks / wall, 1),
            "wall_s": round(wall, 3),
            "ttft_p50_s": round(tt[len(tt) // 2], 4) if tt else None,
            "ttft_p99_s": (round(tt[min(len(tt) - 1,
                                        int(len(tt) * 0.99))], 4)
                           if tt else None),
            "prefix_hit_pages": hit,
            "prefix_miss_pages": miss,
            "prefix_hit_rate": (round(hit / (hit + miss), 3)
                                if hit + miss else 0.0),
            "routed_total": routed,
        }

    rr = measure("round_robin")
    ca = measure("cache_aware")

    # availability drill: 3 replicas, kill the busiest ~30% into the
    # replay; a small injected step latency keeps streams long-lived
    # enough that the kill lands MID-stream (the drill measures
    # completion under failover, not throughput)
    import os
    router = warm(make_router(3, "cache_aware"))
    span = float(arrivals[-1]) if len(arrivals) else 0.0
    os.environ["PADDLE_TPU_SERVING_FAULT_LATENCY_S"] = "0.01"
    try:
        wall_k, toks_k, _, killed = replay_router(
            router, arrivals, prompts, max_new, kill=0.3 * span + 0.1)
    finally:
        del os.environ["PADDLE_TPU_SERVING_FAULT_LATENCY_S"]
    avail = {
        "replicas": 3, "killed_replica": killed[0] if killed else None,
        "completed_tokens": toks_k,
        "expected_tokens": len(prompts) * max_new,
        "wall_s": round(wall_k, 3),
        "failovers": router.metrics.failovers_total.export(),
        "spliced_tokens": router.metrics.spliced_tokens_total.value,
    }
    router.close()

    out = {
        "metric": "serving_router_ttft_p50_s"
                  + ("" if on_tpu else "_cpu"),
        "value": ca["ttft_p50_s"],
        "unit": "s (shared-prefix workload, 2 replicas, cache-aware "
                "routing; compare round_robin.ttft_p50_s)",
        "n_requests": n_requests, "rate_per_s": rate,
        "max_new_tokens": max_new,
        "shared_prefix_tokens": prefix_len,
        "round_robin": rr, "cache_aware": ca,
        "hit_rate_gain": round(ca["prefix_hit_rate"]
                               - rr["prefix_hit_rate"], 3),
        "availability": avail,
        "smoke": smoke,
    }
    line = json.dumps(out)
    print(line)
    with open("BENCH_serving_router.json", "w") as f:
        f.write(line + "\n")


def _bench_prefix_fleet(cfg, engine_kw, on_tpu):
    """Fleet-wide prefix cache bench (round 18), two parts.

    (1) TTFT PROBES — the acceptance comparison, measured serially on
    an idle 2-replica fleet so the three placement classes are pure
    step cost, not queueing noise: ``local`` (request lands on the
    replica already holding the shared prefix — radix hit, tail-only
    prefill), ``cross`` (request lands on a COLD replica with fleet
    ships ON: the pages move over the pagewire path, then tail-only
    prefill), ``recompute`` (same cold placement, ships OFF: the full
    shared prefix re-prefills).  The claim: cross beats recompute and
    sits within ~2x of local.

    (2) FLEET REPLAY — the shared-prefix Poisson workload through the
    same fleet under least_loaded routing, ships off vs on, each a
    TWO-POINT MARGINAL (quarter vs full decode budget, PERF.md
    hygiene); greedy AND seeded-sampled streams are asserted
    token-exact vs a single-engine oracle through the ships.

    One JSON line -> BENCH_serving_prefix_fleet.json."""
    import threading

    import paddle_tpu as P
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.serving import (InProcessReplica, ServingEngine,
                                    ServingRouter)

    # a LONG shared prefix (14 pages non-smoke): the probe compares
    # re-prefilling it against shipping it, so it must dominate the
    # tail
    prefix_len = 96 if smoke else 224
    ps = engine_kw["page_size"]
    arrivals, prompts = make_shared_prefix_trace(
        n_requests, rate, cfg.vocab_size, prefix_len)
    new_q = max(1, max_new // 4)
    seeds = [1000 + i for i in range(len(prompts))]
    rng = np.random.default_rng(99)
    shared = prompts[0][:prefix_len]

    def fresh_probe_prompt():
        return np.concatenate(
            [shared, rng.integers(0, cfg.vocab_size, 12)
             .astype(np.int32)])

    # The probes compare RE-PREFILLING the shared prefix against
    # SHIPPING its pages, so the probe model must have real prefill
    # cost per page — the replay's 2-layer CPU config is so small that
    # a chunk step costs about the same as a host page copy, which is
    # not the serving regime this cache targets.  On TPU the main
    # config is already prefill-heavy.
    if on_tpu:
        probe_cfg = cfg
    else:
        from paddle_tpu.models import LlamaConfig
        probe_cfg = LlamaConfig(
            vocab_size=cfg.vocab_size, hidden_size=256,
            intermediate_size=512, num_hidden_layers=4,
            num_attention_heads=8,
            max_position_embeddings=cfg.max_position_embeddings)

    def make_router(policy, fleet, num_pages=None, model_cfg=None):
        replicas = []
        kw = dict(engine_kw, prefix_cache=True)
        if num_pages is not None:
            # the import's functional scatter copies the whole pool,
            # so its cost scales with num_pages — the serial probes
            # use a pool sized for their actual residency instead of
            # the replay's burst pool (no admission pressure either
            # way; the replay keeps the big pool)
            kw["num_pages"] = num_pages
        for _ in range(2):
            P.seed(0)
            m = LlamaForCausalLM(model_cfg or cfg)
            if on_tpu:
                m.to(dtype="bfloat16")
            m.eval()
            eng = ServingEngine(m, **kw)
            replicas.append(InProcessReplica(
                eng, max_queued=len(prompts) + 8))
        return ServingRouter(replicas, policy=policy,
                             page_size=ps, prefix_fleet=fleet)

    def warm(router):
        # compile every program class per replica off the clock with
        # NON-shared prompts, then flush: the measurement starts
        # prefix-cold
        warm_rng = np.random.default_rng(1234)
        warm_prompts = [warm_rng.integers(
            0, cfg.vocab_size, int(p.size)).astype(np.int32)
            for p in prompts[:8]]
        for rep in router.replicas:
            for budget in (new_q, max_new):
                for p in warm_prompts:
                    rep.engine.add_request(p, max_new_tokens=budget)
                rep.engine.run()
            rep.engine.cache.clear_prefix()
        return router.start()

    def flush_prefix(router):
        for rep in router.replicas:
            rep.engine.cache.clear_prefix()

    # -- part 1: serial TTFT probes on an idle fleet -----------------------
    def probe_once(router, target, fleet):
        """One probed submission steered to ``target`` (round_robin
        pointer reset — bench-only steering); returns client TTFT."""
        router.prefix_fleet = fleet
        router._rr = target
        sub = time.perf_counter()
        stream = router.submit(fresh_probe_prompt(), max_new_tokens=4)
        ttft = None
        for ev in stream.events(timeout=600):
            if ev["type"] == "token" and ttft is None:
                ttft = time.perf_counter() - sub
        assert stream.replica_idx == target, (
            "probe steering broke", stream.replica_idx, target)
        return ttft

    router = warm(make_router("round_robin", False, num_pages=128,
                              model_cfg=probe_cfg))
    donor, cold = router.replicas
    # seed the donor (replica 0) with the shared prefix, off the
    # clock — fleet=True so the placement teaches the transfer index
    # (under a non-cache-aware policy only fleet placements record)
    probe_once(router, 0, True)
    reps_n = 4 if smoke else 12
    probes = {"local": [], "cross": [], "recompute": []}
    ships0 = router.metrics.prefix_ships_total.value
    for _ in range(reps_n):
        probes["local"].append(probe_once(router, 0, False))
        cold.engine.cache.drop_prefix(shared)
        probes["cross"].append(probe_once(router, 1, True))
        cold.engine.cache.drop_prefix(shared)
        probes["recompute"].append(probe_once(router, 1, False))
        cold.engine.cache.drop_prefix(shared)
    ships = router.metrics.prefix_ships_total.value - ships0
    shipped = router.metrics.prefix_shipped_pages_total.value
    assert ships == reps_n, (ships, reps_n)
    router.close()

    def med(xs):
        return round(sorted(xs)[len(xs) // 2], 4)

    probe_out = {
        "reps": reps_n,
        "local_ttft_p50_s": med(probes["local"]),
        "cross_ttft_p50_s": med(probes["cross"]),
        "recompute_ttft_p50_s": med(probes["recompute"]),
        "prefix_ships": ships,
        "prefix_shipped_pages": shipped,
        "pages_per_ship": round(shipped / max(ships, 1), 1),
    }

    # -- part 2: fleet replay, two-point marginal, exactness ---------------
    def oracle(do_sample):
        P.seed(0)
        m = LlamaForCausalLM(cfg)
        if on_tpu:
            m.to(dtype="bfloat16")
        m.eval()
        eng = ServingEngine(m, **dict(engine_kw, prefix_cache=True))
        rids = []
        for i, p in enumerate(prompts):
            kw = ({"do_sample": True, "temperature": 0.8,
                   "seed": seeds[i]} if do_sample else {})
            rids.append(eng.add_request(p, max_new_tokens=max_new,
                                        **kw))
        res = eng.run()
        return [res[r]["tokens"] for r in rids]

    want_greedy = oracle(False)
    want_sampled = oracle(True)

    def replay_fleet(router, new_tokens, do_sample=False):
        outs = [[] for _ in prompts]
        errors = []
        t0 = time.perf_counter()

        def fire(i, due, prompt):
            time.sleep(max(0.0, due - (time.perf_counter() - t0)))
            kw = ({"do_sample": True, "temperature": 0.8,
                   "seed": seeds[i]} if do_sample else {})
            try:
                stream = router.submit(prompt,
                                       max_new_tokens=new_tokens, **kw)
                for ev in stream.events(timeout=600):
                    if ev["type"] == "token":
                        outs[i].append(ev["token"])
            except Exception as e:
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=fire, args=(i, a, p),
                                    daemon=True)
                   for i, (a, p) in enumerate(zip(arrivals, prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        assert not errors, errors[:4]
        return wall, sum(len(o) for o in outs), outs

    def measure(fleet):
        router = warm(make_router("least_loaded", fleet))
        wall_q, toks_q, _ = replay_fleet(router, new_q)
        flush_prefix(router)
        wall, toks, outs = replay_fleet(router, max_new)
        assert outs == want_greedy, "greedy streams diverged from " \
            "the single-engine oracle"
        flush_prefix(router)
        _, _, souts = replay_fleet(router, max_new, do_sample=True)
        assert souts == want_sampled, "seeded-sampled streams " \
            "diverged from the single-engine oracle"
        m = router.metrics
        marginal = ((toks - toks_q) / (wall - wall_q)
                    if wall > wall_q and toks > toks_q else None)
        out = {
            "prefix_fleet": fleet,
            "tok_per_s_marginal": (round(marginal, 1)
                                   if marginal else None),
            "e2e_tok_per_s": round(toks / wall, 1),
            "wall_s": round(wall, 3),
            "prefix_ships": m.prefix_ships_total.value,
            "prefix_shipped_pages": m.prefix_shipped_pages_total.value,
            "prefix_ship_fallbacks":
                m.prefix_ship_fallbacks_total.value,
            "exact_greedy": True, "exact_sampled": True,
        }
        router.close()
        return out

    fleet_off = measure(False)
    fleet_on = measure(True)

    out = {
        "metric": "serving_prefix_fleet_cross_ttft_p50_s"
                  + ("" if on_tpu else "_cpu"),
        "value": probe_out["cross_ttft_p50_s"],
        "unit": "s (cross-replica prefix hit: cached pages shipped "
                "over pagewire, tail-only prefill; compare "
                "probes.recompute_ttft_p50_s and "
                "probes.local_ttft_p50_s)",
        "n_requests": n_requests, "rate_per_s": rate,
        "max_new_tokens": max_new,
        "shared_prefix_tokens": prefix_len,
        "page_size": ps,
        "probes": probe_out,
        "fleet_replay": {"ships_off": fleet_off, "ships_on": fleet_on},
        "cross_vs_recompute_ttft_speedup": round(
            probe_out["recompute_ttft_p50_s"]
            / probe_out["cross_ttft_p50_s"], 2),
        "cross_vs_local_ttft_ratio": round(
            probe_out["cross_ttft_p50_s"]
            / probe_out["local_ttft_p50_s"], 2),
        "smoke": smoke,
    }
    line = json.dumps(out)
    print(line)
    with open("BENCH_serving_prefix_fleet.json", "w") as f:
        f.write(line + "\n")


def _bench_disagg(cfg, engine_kw, on_tpu):
    """Disaggregated (1 prefill + 2 decode) vs symmetric (3 mixed)
    fleet on a mixed TTFT-heavy + TPOT-heavy Poisson workload.

    TTFT-heavy class: 96-token prompt, 4 decode tokens (the
    agent-burst shape that stalls a symmetric fleet's decode loop).
    TPOT-heavy class: 8-16 token prompt, the full decode budget (the
    steady streams whose TPOT the bursts degrade).  Same trace, same
    models, same total replica count; two-point marginal per topology
    (quarter vs full decode budget); TTFT percentiles client-side and
    per class.  One JSON line -> BENCH_serving_disagg.json."""
    import threading

    import paddle_tpu as P
    from paddle_tpu.models import LlamaForCausalLM
    from paddle_tpu.serving import (DisaggRouter, InProcessReplica,
                                    ServingEngine, ServingRouter)

    ttft_prompt_len = 96
    ttft_decode = 4
    rng = np.random.default_rng(7)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
    kinds = rng.random(n_requests) < 0.5      # half each class
    prompts = [
        (rng.integers(0, cfg.vocab_size, ttft_prompt_len)
         if heavy else
         rng.integers(0, cfg.vocab_size, int(rng.integers(8, 17))))
        .astype(np.int32)
        for heavy in kinds]
    new_q = max(1, max_new // 4)

    def budgets(decode_budget):
        return [ttft_decode if heavy else decode_budget
                for heavy in kinds]

    def make_fleet(disagg):
        replicas, roles = [], (("prefill", "decode", "decode")
                               if disagg else ("mixed",) * 3)
        for role in roles:
            P.seed(0)
            m = LlamaForCausalLM(cfg)
            if on_tpu:
                m.to(dtype="bfloat16")
            m.eval()
            eng = ServingEngine(m, **dict(engine_kw,
                                          prefix_cache=True))
            replicas.append(InProcessReplica(
                eng, max_queued=len(prompts) + 8, role=role))
        if disagg:
            return DisaggRouter(replicas,
                                page_size=engine_kw["page_size"])
        return ServingRouter(replicas, policy="least_loaded",
                             page_size=engine_kw["page_size"])

    def warm(router):
        # every replica compiles its bucketed program classes OFF the
        # clock (single-threaded, router unstarted), then the prefix
        # trees are flushed so the measured replay starts cold
        warm_rng = np.random.default_rng(1234)
        for rep in router.replicas:
            for budget in (ttft_decode, new_q, max_new):
                # 8 concurrent requests per budget: every decode batch
                # bucket (1..max_batch) compiles off the clock — the
                # quarter replay must never eat a first-call trace
                for _ in range(8):
                    p = warm_rng.integers(
                        0, cfg.vocab_size,
                        int(warm_rng.integers(8, 97))).astype(np.int32)
                    rep.engine.add_request(p, max_new_tokens=budget)
                rep.engine.run()
            rep.engine.cache.clear_prefix()
        return router.start()

    def replay_fleet(router, decode_budget):
        """Thread-per-request replay; returns (wall, tokens, per-class
        client TTFT lists)."""
        buds = budgets(decode_budget)
        ttfts = [None] * len(prompts)
        counts = [0] * len(prompts)
        errors = []
        t0 = time.perf_counter()

        def fire(i, due, prompt):
            time.sleep(max(0.0, due - (time.perf_counter() - t0)))
            try:
                sub = time.perf_counter()
                stream = router.submit(prompt,
                                       max_new_tokens=buds[i])
                for ev in stream.events(timeout=600):
                    if ev["type"] == "token":
                        if ttfts[i] is None:
                            ttfts[i] = time.perf_counter() - sub
                        counts[i] += 1
            except Exception as e:
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=fire, args=(i, a, p),
                                    daemon=True)
                   for i, (a, p) in enumerate(zip(arrivals, prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        assert not errors, errors[:4]
        assert all(c == b for c, b in zip(counts, buds)), \
            list(zip(counts, buds))[:8]
        return wall, sum(counts), ttfts

    def pct(values, p):
        vals = sorted(v for v in values if v is not None)
        if not vals:
            return None
        return round(vals[min(len(vals) - 1,
                              int(len(vals) * p / 100))], 4)

    def measure(disagg):
        router = warm(make_fleet(disagg))
        wall_q, toks_q, _ = replay_fleet(router, new_q)
        for rep in router.replicas:
            rep.engine.cache.clear_prefix()
        wall, toks, ttfts = replay_fleet(router, max_new)
        heavy_ttft = [t for t, h in zip(ttfts, kinds) if h]
        steady_ttft = [t for t, h in zip(ttfts, kinds) if not h]
        marginal = ((toks - toks_q) / (wall - wall_q)
                    if wall > wall_q and toks > toks_q else None)
        out = {
            "tok_per_s_marginal": (round(marginal, 1)
                                   if marginal else None),
            "e2e_tok_per_s": round(toks / wall, 1),
            "wall_s": round(wall, 3),
            "ttft_heavy_p50_s": pct(heavy_ttft, 50),
            "ttft_heavy_p99_s": pct(heavy_ttft, 99),
            "ttft_steady_p50_s": pct(steady_ttft, 50),
        }
        if disagg:
            out.update(
                migrations=router.metrics.migrations_total.value,
                migrated_pages=router.metrics
                .migrated_pages_total.value,
                migration_fallbacks=router.metrics
                .migration_fallbacks_total.value)
        router.close()
        return out

    mixed = measure(False)
    dis = measure(True)
    out = {
        "metric": "serving_disagg_ttft_heavy_p50_s"
                  + ("" if on_tpu else "_cpu"),
        "value": dis["ttft_heavy_p50_s"],
        "unit": "s (mixed TTFT/TPOT workload, 1 prefill + 2 decode "
                "replicas w/ KV page migration; compare "
                "mixed_fleet.ttft_heavy_p50_s on 3 mixed replicas)",
        "n_requests": n_requests, "rate_per_s": rate,
        "max_new_tokens": max_new,
        "ttft_prompt_tokens": ttft_prompt_len,
        "ttft_decode_tokens": ttft_decode,
        "disagg_fleet": dis, "mixed_fleet": mixed,
        "ttft_p50_speedup": (
            round(mixed["ttft_heavy_p50_s"]
                  / dis["ttft_heavy_p50_s"], 2)
            if dis["ttft_heavy_p50_s"] else None),
        "smoke": smoke,
    }
    line = json.dumps(out)
    print(line)
    with open("BENCH_serving_disagg.json", "w") as f:
        f.write(line + "\n")


def _bench_kv8(on_tpu):
    """Quantized serving: int8 paged KV vs bf16 at an EQUAL fixed HBM
    budget (memory-pressure replay through a shedding front-end) plus
    the serving-path held-out-NLL quality gate. One JSON line ->
    BENCH_serving_kv8.json; asserts the |delta-NLL| < 0.01 gate."""
    import glob
    import os
    import threading

    import paddle_tpu as P
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaPretrainingCriterion
    from paddle_tpu.serving import (Rejected, ServingEngine,
                                    ServingFrontend)

    # -- part A: memory pressure at a fixed budget -------------------------
    # bf16: 63 allocatable pages; int8: 119 (1.89x). The model is sized
    # so one decode step costs ~0.1 s on the CPU mesh (hidden 512 x 4
    # layers): requests then OUTLIVE the arrival window and the page
    # pool — not step speed — caps admitted concurrency, which is the
    # regime the int8 capacity claim is about (the earlier h128 toy
    # drained faster than the Poisson arrivals and nothing ever shed).
    budget_mb = 2
    maxlen = 64 + max_new + 1
    cfg = LlamaConfig(vocab_size=512, hidden_size=512,
                      intermediate_size=1024, num_hidden_layers=4,
                      num_attention_heads=8,  # head_dim 64 -> the
                      num_key_value_heads=2,  # honest 2D/(D+4)
                      # capacity ratio (1.88x vs bf16)
                      max_position_embeddings=maxlen)
    P.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    arrivals, prompts = make_trace(n_requests, rate, cfg.vocab_size)
    new_q = max(1, max_new // 4)
    engine_kw = dict(page_size=16, hbm_budget_mb=budget_mb,
                     max_batch=8, prefill_chunk=32, max_seq_len=maxlen)

    def replay_shed(fe, decode_budget):
        """Thread-per-request Poisson replay; a 429 (Rejected) is a
        SHED — no retry, the lost work is the cost of the smaller page
        pool. Returns (wall, completed tokens, client TTFTs, shed)."""
        ttfts = [None] * len(prompts)
        counts = [0] * len(prompts)
        shed = [0]
        errors = []
        lock = threading.Lock()
        t0 = time.perf_counter()

        def fire(i, due, prompt):
            time.sleep(max(0.0, due - (time.perf_counter() - t0)))
            sub = time.perf_counter()
            try:
                stream = fe.submit(prompt,
                                   max_new_tokens=decode_budget)
            except Rejected:
                with lock:
                    shed[0] += 1
                return
            try:
                for ev in stream.events(timeout=600):
                    if ev["type"] == "token":
                        if ttfts[i] is None:
                            ttfts[i] = time.perf_counter() - sub
                        counts[i] += 1
            except Exception as e:
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=fire, args=(i, a, p),
                                    daemon=True)
                   for i, (a, p) in enumerate(zip(arrivals, prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        assert not errors, errors[:4]
        return wall, sum(counts), ttfts, shed[0]

    def measure(dtype):
        eng = ServingEngine(model, cache_dtype=dtype, **engine_kw)
        # warmup compiles every bucketed program class off the clock
        # (engine-direct: preemption elasticity instead of shedding)
        warm_rng = np.random.default_rng(99)
        for budget in (new_q, max_new):
            for _ in range(8):
                p = warm_rng.integers(
                    0, cfg.vocab_size,
                    int(warm_rng.integers(8, 65))).astype(np.int32)
                eng.add_request(p, max_new_tokens=budget)
            eng.run()
        fe = ServingFrontend(eng,
                             max_queued=len(prompts) + 8).start()
        wall_q, toks_q, _, shed_q = replay_shed(fe, new_q)
        wall, toks, ttfts, shed = replay_shed(fe, max_new)
        fe.drain()
        m = eng.metrics.export()
        marginal = ((toks - toks_q) / (wall - wall_q)
                    if wall > wall_q and toks > toks_q else None)
        tt = sorted(t for t in ttfts if t is not None)
        return {
            "allocatable_pages": eng.cache.allocatable_pages,
            "page_bytes": eng.cache.bytes_total // eng.cache.num_pages,
            "admitted": len(prompts) - shed,
            "shed": shed,
            "shed_rate": round(shed / len(prompts), 3),
            "completed_tokens": toks,
            "tok_per_s_marginal": (round(marginal, 1)
                                   if marginal else None),
            "e2e_tok_per_s": round(toks / wall, 1) if wall else None,
            "wall_s": round(wall, 3),
            "ttft_p50_s": (round(tt[len(tt) // 2], 4) if tt else None),
            "decode_batch_max": m["batch_size"]["max"],
            "preemptions": m["preemptions"],
        }

    bf16 = measure("bfloat16")
    int8 = measure("int8")
    ratio = int8["allocatable_pages"] / bf16["allocatable_pages"]

    # -- part B: serving-path quality gate ---------------------------------
    root = os.path.dirname(os.path.abspath(__file__))
    txt = []
    for pat in ("*.md", "docs/*.md"):
        for path in sorted(glob.glob(os.path.join(root, pat))):
            with open(path, "rb") as f:
                txt.append(f.read())
    data = np.frombuffer(b"\n\n".join(txt), np.uint8).astype(np.int32)
    held = data[-4096:]
    train_arr = data[:-4096]
    seq_q, batch = 96, 8
    steps = 40 if smoke else 200
    n_eval = 2 if smoke else 4
    qcfg = LlamaConfig(vocab_size=256, hidden_size=256,
                       intermediate_size=688, num_hidden_layers=4,
                       num_attention_heads=4, num_key_value_heads=2,
                       max_position_embeddings=seq_q + 8)
    P.seed(0)
    qmodel = LlamaForCausalLM(qcfg)
    crit = LlamaPretrainingCriterion(qcfg)
    opt = P.optimizer.AdamW(3e-3, parameters=qmodel.parameters())
    rng = np.random.default_rng(0)
    t0 = time.perf_counter()
    loss = None
    for _ in range(steps):
        starts = rng.integers(0, len(train_arr) - seq_q - 1, batch)
        chunk = np.stack([train_arr[s:s + seq_q + 1] for s in starts])
        logits = qmodel(P.to_tensor(chunk[:, :-1]))
        loss = crit(logits, P.to_tensor(chunk[:, 1:]))
        loss.backward()
        opt.step()
        opt.clear_grad()
    qmodel.eval()
    train_s = time.perf_counter() - t0
    seqs = [held[i * seq_q:(i + 1) * seq_q] for i in range(n_eval)]

    def serving_nll(cache_dtype):
        """Teacher-forced held-out NLL through the serving engine: one
        cut position per request (prompt = seq[:t], max_new=1), logits
        of position t-1 probed after the drain — every position runs
        the paged-attention dequant path; the radix prefix cache keeps
        each sweep step to one tail chunk."""
        eng = ServingEngine(qmodel, page_size=16, num_pages=256,
                            max_batch=1, prefill_chunk=32,
                            max_seq_len=seq_q + 8,
                            cache_dtype=cache_dtype, prefix_cache=True)
        nll, n = 0.0, 0
        for s in seqs:
            for t in range(16, seq_q):
                eng.add_request(s[:t], max_new_tokens=1)
                eng.run()
                row = np.asarray(eng._last_logits_probe, np.float64)
                lse = np.log(np.exp(row - row.max()).sum()) + row.max()
                nll += -(row[int(s[t])] - lse)
                n += 1
        return nll / n

    nll_bf16 = serving_nll("bfloat16")
    nll_int8 = serving_nll("int8")
    from paddle_tpu.nn.quant import convert_to_weight_only
    convert_to_weight_only(qmodel, algo="weight_only_int8",
                           exclude=("lm_head",))
    nll_wq = serving_nll("int8")
    quality = {
        "train_steps": steps,
        "train_loss": (round(float(loss.numpy()), 4)
                       if loss is not None else None),
        "train_s": round(train_s, 1),
        "eval_positions": n_eval * (seq_q - 16),
        "nll_bf16_cache": round(nll_bf16, 6),
        "nll_int8_kv": round(nll_int8, 6),
        "nll_int8_kv_int8_weights": round(nll_wq, 6),
        "delta_nll_int8_kv": round(nll_int8 - nll_bf16, 6),
        "delta_nll_int8_kv_int8_weights": round(nll_wq - nll_bf16, 6),
    }
    # the acceptance gate: quantized serving must not move held-out
    # NLL by more than 0.01 vs the bf16 cache (BENCH_kv8_quality saw
    # ~1e-3 on the generation path; this replays it through serving/)
    assert abs(quality["delta_nll_int8_kv"]) < 0.01, quality
    assert abs(quality["delta_nll_int8_kv_int8_weights"]) < 0.01, \
        quality

    out = {
        "metric": "serving_kv8_page_capacity_ratio"
                  + ("" if on_tpu else "_cpu"),
        "value": round(ratio, 3),
        "unit": "x allocatable pages vs bf16 at an equal "
                f"hbm_budget_mb={budget_mb} (head_dim 64; compare "
                "int8/bf16 admitted+shed under memory pressure)",
        "n_requests": n_requests, "rate_per_s": rate,
        "max_new_tokens": max_new,
        "hbm_budget_mb": budget_mb,
        "page_capacity_ratio": round(ratio, 3),
        "bf16": bf16, "int8": int8,
        "quality": quality,
        "gate_pass": True,
        "smoke": smoke,
    }
    line = json.dumps(out)
    print(line)
    with open("BENCH_serving_kv8.json", "w") as f:
        f.write(line + "\n")


def _bench_kvtier(on_tpu):
    """Hierarchical KV tier (round 20): revisit-TTFT and hit rate vs
    host-pool size. A round-robin schedule over more long-prompt
    chains than the device pool holds guarantees every revisit finds
    its prefix LRU-evicted; the pool=0 engine recomputes the prefill,
    a tiered engine restores the spilled pages through the fused
    import path. One JSON line -> BENCH_serving_kvtier.json; on
    non-smoke runs asserts restore beats recompute on revisit TTFT
    p50."""
    import tempfile

    import paddle_tpu as P
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import (DiskPagePool, HostPagePool,
                                    ServingEngine)

    # prefill-heavy shape (round-18 lesson: h128 prefill chunks cost
    # about a page copy, so restore-vs-recompute measures nothing
    # there); page bytes at h256/L4/page16 fp32 ~= 128 KB
    page_size = 16
    if smoke:
        n_chains, rounds, prompt_pages, new_toks = 4, 2, 6, 4
        num_pages = 16   # 15 usable: ~2 chains resident, 4 thrash
        pool_sizes = [0, 1, 8]
        disk_point = (1, 16)  # (host MB, disk MB)
    else:
        n_chains, rounds, prompt_pages, new_toks = 6, 3, 14, 8
        num_pages = 40   # ~2.5 chains resident, 6 thrash
        pool_sizes = [0, 4, 24]
        disk_point = (2, 32)
    prompt_len = prompt_pages * page_size
    maxlen = prompt_len + new_toks + 1
    cfg = LlamaConfig(vocab_size=512, hidden_size=256,
                      intermediate_size=512, num_hidden_layers=4,
                      num_attention_heads=4,
                      max_position_embeddings=maxlen)
    P.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(7)
    chains = [rng.integers(0, cfg.vocab_size, prompt_len)
              .astype(np.int32) for _ in range(n_chains)]
    engine_kw = dict(page_size=page_size, num_pages=num_pages,
                     max_batch=2, prefill_chunk=32, max_seq_len=maxlen,
                     prefix_cache=True)

    def serve_one(eng, prompt):
        """One sequential request; returns client TTFT (the engine is
        otherwise idle, so the first token event is ours)."""
        sub = time.perf_counter()
        eng.add_request(prompt, max_new_tokens=new_toks)
        ttft = None
        while not eng.scheduler.all_done():
            for ev in eng.step():
                if ev["type"] == "token" and ttft is None:
                    ttft = time.perf_counter() - sub
        return ttft

    def measure(host_mb, disk_mb=0, disk_dir=None):
        pool = None
        if host_mb:
            disk = (DiskPagePool(disk_dir, budget_bytes=disk_mb << 20)
                    if disk_mb else None)
            pool = HostPagePool(budget_bytes=host_mb << 20, disk=disk)
        eng = ServingEngine(model, host_pool=pool, **engine_kw)
        # compile off the clock: prefill+decode, then (tiered only)
        # the fused spill-export / restore-import program classes —
        # force the warm chain through a full evict->restore cycle
        warm_p = rng.integers(0, cfg.vocab_size, prompt_len) \
            .astype(np.int32)
        serve_one(eng, warm_p)
        if pool is not None:
            while eng.cache._evict_lru_leaf():
                pass
            eng.kvtier.flush()
            eng.restore_prefix(warm_p)
            serve_one(eng, warm_p)
            pool.clear()
            eng.cache.clear_prefix()
            serve_one(eng, warm_p)  # re-populate so configs match
        m = eng.metrics
        base = {n: getattr(m, n).value for n in
                ("tier_restore_hits", "tier_restore_misses",
                 "tier_restore_pages", "tier_spill_pages",
                 "prefix_hit_pages")}
        t0 = time.perf_counter()
        ttfts = []  # revisit rounds only (round 0 populates, cold)
        for r in range(rounds):
            for c in chains:
                ttft = serve_one(eng, c)
                if r > 0:
                    ttfts.append(ttft)
        wall = time.perf_counter() - t0
        delta = {n: getattr(m, n).value - v for n, v in base.items()}
        hits = delta["tier_restore_hits"]
        misses = delta["tier_restore_misses"]
        tt = sorted(t for t in ttfts if t is not None)
        rec = {
            "host_pool_mb": host_mb,
            "disk_pool_mb": disk_mb,
            "revisits": len(ttfts),
            "ttft_revisit_p50_s": (round(tt[len(tt) // 2], 4)
                                   if tt else None),
            "ttft_revisit_p90_s": (round(tt[int(len(tt) * 0.9)], 4)
                                   if tt else None),
            "wall_s": round(wall, 3),
            "tier_restore_hits": hits,
            "tier_restore_misses": misses,
            "tier_hit_rate": (round(hits / (hits + misses), 3)
                              if hits + misses else None),
            "tier_restore_pages": delta["tier_restore_pages"],
            "tier_spill_pages": delta["tier_spill_pages"],
            "prefix_hit_pages": delta["prefix_hit_pages"],
        }
        if pool:
            rec["pool"] = pool.stats()
            pool.clear()
        return rec

    pools = [measure(mb) for mb in pool_sizes]
    with tempfile.TemporaryDirectory(prefix="pdtpu_kvtier_") as d:
        pools.append(measure(disk_point[0], disk_point[1], d))

    base = pools[0]
    warm = [p for p in pools[1:] if p["tier_restore_pages"] > 0]
    best = min(warm, key=lambda p: p["ttft_revisit_p50_s"] or 1e9) \
        if warm else None
    speedup = (round(base["ttft_revisit_p50_s"]
                     / best["ttft_revisit_p50_s"], 3)
               if best and best["ttft_revisit_p50_s"] else None)
    assert warm, "no pool size ever restored — thrash sizing broken"
    if not smoke:
        # the acceptance gate: a host-tier restore must beat the
        # recompute the engine would otherwise have done (quiet VM)
        assert speedup and speedup > 1.0, (base, best)

    out = {
        "metric": "serving_kvtier_ttft_restore_speedup"
                  + ("" if on_tpu else "_cpu"),
        "value": speedup,
        "unit": "x revisit-TTFT p50 vs the pool=0 recompute baseline "
                f"({n_chains} chains x {prompt_pages} pages thrashing "
                f"a {num_pages}-page device pool)",
        "n_chains": n_chains, "rounds": rounds,
        "prompt_len": prompt_len, "page_size": page_size,
        "num_pages": num_pages, "max_new_tokens": new_toks,
        "pools": pools,
        "smoke": smoke,
    }
    line = json.dumps(out)
    print(line)
    with open("BENCH_serving_kvtier.json", "w") as f:
        f.write(line + "\n")


def _bench_trace_overhead(model, cfg, engine_kw, on_tpu):
    """Tracing overhead guard (round 16): the SAME Poisson trace
    replays through one warm engine per config — span tracing ON (the
    always-on default) and OFF (PADDLE_TPU_SERVING_TRACE=0 at engine
    construction) — with a two-point marginal each (quarter vs full
    decode budget, the PERF.md discipline that cancels fixed per-replay
    overhead).  The acceptance contract: the trace-on marginal stays
    within 3% of trace-off.  Asserted on non-smoke runs only — under
    suite/CPU load marginal ratios are noise (CLAUDE.md round-4), and
    the in-suite smoke replay must not flake on them; the BANKED
    quiet-VM artifact is the gate.  Also exports the traced replay as
    chrome JSON and round-trips it through
    paddle_tpu.profiler.load_profiler_result.  One JSON line ->
    BENCH_serving_trace.json."""
    import os
    import statistics
    import tempfile

    from paddle_tpu.profiler import load_profiler_result
    from paddle_tpu.serving import (ServingEngine, ServingMetrics,
                                    export_chrome_trace)

    _, prompts = make_trace(n_requests, rate, cfg.vocab_size)
    new_q = max(1, max_new // 4)
    reps = 1 if smoke else 5

    # Measurement discipline, tuned on this VM (all two failure modes
    # below make the ratio measure the HARNESS, not tracing):
    # - SYNCHRONOUS submission, not the Poisson arrival replay: the
    #   3% contract is about per-step span-emission cost, and arrival-
    #   gap/step-boundary interaction swings the Poisson marginal
    #   ~30% run to run — far above the signal.  Batch-submit drains
    #   are reproducible to ~2% here.
    # - Engines are built fresh per repetition and DROPPED before the
    #   next one: keeping measured engines (device page pools, jit
    #   caches) alive inflates later configs' step time up to ~2x.
    # - Configs ALTERNATE (off/on per repetition, after a throwaway
    #   process-warmup engine — the first engine in a process runs
    #   ~25% slow) and the banked ratio is median(on)/median(off).
    def marginal_once(trace_on):
        env_before = os.environ.get("PADDLE_TPU_SERVING_TRACE")
        os.environ["PADDLE_TPU_SERVING_TRACE"] = \
            "1" if trace_on else "0"
        try:
            eng = ServingEngine(model, **engine_kw)
        finally:
            if env_before is None:
                os.environ.pop("PADDLE_TPU_SERVING_TRACE", None)
            else:
                os.environ["PADDLE_TPU_SERVING_TRACE"] = env_before
        assert eng.trace.enabled is trace_on

        def drain(budget):
            for p in prompts:
                eng.add_request(p, max_new_tokens=budget)
            t0 = time.perf_counter()
            eng.run()
            return time.perf_counter() - t0

        drain(new_q)   # warm every bucketed program class
        drain(max_new)
        eng.metrics = ServingMetrics()
        wall_q = drain(new_q)
        wall_f = drain(max_new)
        m = eng.metrics.export()
        marginal = (len(prompts) * (max_new - new_q)
                    / (wall_f - wall_q))
        timelines = eng.trace.timelines() if trace_on else None
        if not trace_on:
            assert not eng.trace.timelines(), \
                "trace-off engine recorded spans"
        return {"marginal": marginal, "wall_full_s": wall_f,
                "step_duration_p50_s": m["step_duration_s"]["p50"],
                "timelines": timelines}

    # throwaway process warmup (neither config measured)
    marginal_once(False)
    runs_off, runs_on = [], []
    timelines = None
    for _ in range(reps):
        runs_off.append(marginal_once(False))
        r_on = marginal_once(True)
        timelines = r_on.pop("timelines")
        runs_on.append(r_on)
    for r in runs_off:
        r.pop("timelines")
    assert timelines, "trace-on engine recorded nothing"

    # chrome export of the traced replay: valid trace JSON end to end
    with tempfile.NamedTemporaryFile(suffix=".json",
                                     delete=False) as f:
        trace_path = f.name
    export_chrome_trace(trace_path,
                        [(0, "serving-engine", timelines)])
    loaded = load_profiler_result(trace_path)
    spans = [e for e in loaded["traceEvents"] if e.get("ph") == "X"]
    assert spans, "chrome export is empty"
    os.unlink(trace_path)

    med_on = statistics.median(r["marginal"] for r in runs_on)
    med_off = statistics.median(r["marginal"] for r in runs_off)
    # per-PAIR ratios, then the median: adjacent off/on runs share the
    # VM weather, so pairing cancels slow drift the two config-level
    # medians would keep
    pair_ratios = [on_r["marginal"] / off_r["marginal"]
                   for off_r, on_r in zip(runs_off, runs_on)]
    ratio = round(statistics.median(pair_ratios), 4)
    overhead_ok = abs(1.0 - ratio) < 0.03
    if not smoke:
        # asserted only on quiet-VM (non-smoke) runs: under suite/CPU
        # load marginals are noise (CLAUDE.md round-4) and the in-suite
        # smoke replay must not flake on them
        assert overhead_ok, (
            f"tracing overhead outside the 3% contract: on/off "
            f"marginal ratio {ratio} (on={runs_on}, off={runs_off})")
    out = {
        "metric": "serving_trace_marginal_ratio"
                  + ("" if on_tpu else "_cpu"),
        "value": ratio,
        "unit": "trace-on / trace-off marginal decode tok/s (median of "
                f"{reps} alternating two-point marginals, synchronous "
                "drain; contract: within 3% of 1.0)",
        "n_requests": n_requests, "rate_per_s": rate,
        "max_new_tokens": max_new,
        "repetitions": reps,
        "trace_on": {
            "tok_per_s_marginal": round(med_on, 1),
            "step_duration_p50_s": statistics.median(
                r["step_duration_p50_s"] for r in runs_on),
            "runs": [round(r["marginal"], 1) for r in runs_on]},
        "trace_off": {
            "tok_per_s_marginal": round(med_off, 1),
            "step_duration_p50_s": statistics.median(
                r["step_duration_p50_s"] for r in runs_off),
            "runs": [round(r["marginal"], 1) for r in runs_off]},
        "overhead_within_3pct": overhead_ok,
        "traced_requests": len(timelines),
        "chrome_events": len(spans),
        "smoke": smoke,
    }
    line = json.dumps(out)
    print(line)
    with open("BENCH_serving_trace.json", "w") as f:
        f.write(line + "\n")


def _bench_speculative(on_tpu):
    """Speculative vs plain decode through the serving engine on an
    acceptance-favorable workload.

    The task is a deterministic SUCCESSOR pattern (a fixed random
    permutation cycle over 64 distinct byte tokens): both the target
    and the narrow 1-layer h128-class draft learn it to ~1.0 argmax
    agreement in a few hundred CE steps, so the measured speedup
    reflects the round arithmetic (k+1 fused draft steps + ONE [B, k+1]
    verify vs one target step per token), not draft quality — the
    honest distilled-draft acceptance curve is the offline
    BENCH_spec_acceptance.json artifact. Two-point marginal per config,
    one WARM engine per config, greedy streams asserted token-exact
    across the two engines."""
    import paddle_tpu as P
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.models.llama import LlamaPretrainingCriterion
    from paddle_tpu.serving import ServingEngine, ServingMetrics

    vocab, plen, seq, batch = 256, 64, 96, 8
    spec_k = 4
    steps = 60 if smoke else 300
    new_tokens = max_new
    maxlen = 32 + new_tokens + 8
    rng = np.random.default_rng(42)
    pattern = rng.permutation(vocab)[:plen].astype(np.int32)

    def make_seqs(n, length):
        offs = rng.integers(0, plen, n)
        tiled = np.concatenate([pattern] * (length // plen + 2))
        return np.stack([tiled[o:o + length] for o in offs])

    def build(hidden, inter, layers, seed):
        P.seed(seed)
        cfg = LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                          intermediate_size=inter,
                          num_hidden_layers=layers,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=maxlen)
        return LlamaForCausalLM(cfg)

    def fit(model, steps, lr=3e-3):
        crit = LlamaPretrainingCriterion(model.cfg)
        opt = P.optimizer.AdamW(lr, parameters=model.parameters())
        loss = None
        for i in range(steps):
            chunk = make_seqs(batch, seq + 1)
            logits = model(P.to_tensor(chunk[:, :-1]))
            loss = crit(logits, P.to_tensor(chunk[:, 1:]))
            loss.backward()
            opt.step()
            opt.clear_grad()
        model.eval()
        return float(loss.numpy()) if loss is not None else None

    t0 = time.perf_counter()
    target = build(256, 688, 4, seed=0)
    draft = build(128, 344, 1, seed=1)
    t_loss = fit(target, steps)
    d_loss = fit(draft, steps)
    train_s = time.perf_counter() - t0

    arrivals, _ = make_trace(n_requests, rate, vocab)
    prompts = [row[:int(g)] for row, g in zip(
        make_seqs(n_requests, 32),
        np.random.default_rng(7).integers(16, 33, n_requests))]
    new_q = max(1, new_tokens // 4)
    engine_kw = dict(page_size=16, num_pages=2048, max_batch=8,
                     prefill_chunk=32, max_seq_len=maxlen)

    def measure(spec):
        ekw = dict(engine_kw)
        if spec:
            ekw.update(draft_model=draft, speculative_k=spec_k)
        eng = ServingEngine(target, **ekw)
        warm_n = min(4, n_requests)
        replay(target, np.zeros(warm_n), prompts[:warm_n], new_q,
               engine=eng)
        replay(target, np.zeros(warm_n), prompts[:warm_n], new_tokens,
               engine=eng)
        eng.metrics = ServingMetrics()
        wall_q, toks_q, _ = replay(target, arrivals, prompts, new_q,
                                   engine=eng)
        eng.metrics = ServingMetrics()
        wall, toks, metrics = replay(target, arrivals, prompts,
                                     new_tokens, engine=eng)
        m = metrics.export()
        marginal = ((toks - toks_q) / (wall - wall_q)
                    if wall > wall_q and toks > toks_q else None)
        out = {
            "tok_per_s_marginal": (round(marginal, 1)
                                   if marginal else None),
            "e2e_tok_per_s": round(toks / wall, 1),
            "wall_s": round(wall, 3),
            "ttft_p50_s": m["ttft_s"]["p50"],
            "decode_steps": m["decode_steps"],
            "fetch_bytes": m["fetch_bytes"],
        }
        if spec:
            out.update(
                spec_rounds=m["spec_rounds"],
                spec_draft_tokens=m["spec_draft_tokens"],
                spec_accepted_tokens=m["spec_accepted_tokens"],
                spec_fallbacks=m["spec_fallbacks"],
                acceptance_rate=(
                    round(m["spec_accepted_tokens"]
                          / m["spec_draft_tokens"], 3)
                    if m["spec_draft_tokens"] else 0.0))
        results = {rid: r["tokens"]
                   for rid, r in eng.results().items()}
        return out, results

    plain, ref = measure(False)
    spec, got = measure(True)
    # determinism contract: greedy speculative streams are token-exact
    # vs the plain engine (same (weights, history, seed, t) function)
    ref_sorted = sorted(map(tuple, ref.values()))
    got_sorted = sorted(map(tuple, got.values()))
    assert ref_sorted == got_sorted, "speculative streams diverged"

    speedup = None
    if plain["tok_per_s_marginal"] and spec["tok_per_s_marginal"]:
        speedup = round(spec["tok_per_s_marginal"]
                        / plain["tok_per_s_marginal"], 2)
    out = {
        "metric": "serving_spec_speedup" + ("" if on_tpu else "_cpu"),
        "value": speedup,
        "unit": "x marginal decode tok/s vs the non-speculative "
                f"engine (greedy, k={spec_k}, h128-class 1-layer "
                "draft, deterministic successor workload)",
        "n_requests": n_requests, "rate_per_s": rate,
        "max_new_tokens": new_tokens, "speculative_k": spec_k,
        "train_steps": steps, "train_s": round(train_s, 1),
        "target_loss": (round(t_loss, 4)
                        if t_loss is not None else None),
        "draft_loss": (round(d_loss, 4)
                       if d_loss is not None else None),
        "acceptance_rate": spec.get("acceptance_rate"),
        "token_exact_vs_plain": True,
        "speculative": spec, "plain": plain,
        "smoke": smoke,
    }
    line = json.dumps(out)
    print(line)
    with open("BENCH_serving_spec.json", "w") as f:
        f.write(line + "\n")


def _bench_tp(model, cfg, engine_kw, on_tpu):
    """Tensor-parallel SPMD serving on the 8-device CPU mesh
    (round 23).

    The SAME Poisson trace replays through one warm engine per shard
    degree (TP ∈ {1, 2} smoke, {1, 2, 4} full) — warmup replays
    compile the SPMD program classes off the clock, then quarter +
    full replays give the two-point marginal per degree.  The
    exactness gate rides the bench: greedy streams at every TP degree
    must be token-identical to TP=1 (the by-construction contract —
    only non-contracting dims shard, so every matmul keeps its full
    contraction local and collectives are pure data movement).  NOTE
    the CPU mesh measures program correctness and collective overhead,
    not a speedup: 8 virtual host devices share the same cores, so
    marginal tok/s at TP>1 is expected to be BELOW TP=1 here — the
    artifact exists as the exactness proof + overhead baseline the
    real-mesh run can diff against.  Banks BENCH_serving_tp.json
    (non-smoke only)."""
    from paddle_tpu.serving import ServingEngine, ServingMetrics

    arrivals, prompts = make_trace(n_requests, rate, cfg.vocab_size)
    new_q = max(1, max_new // 4)

    def measure(tp):
        eng = ServingEngine(model, tp_degree=(tp if tp > 1 else None),
                            **engine_kw)
        warm_n = min(4, n_requests)
        replay(model, np.zeros(warm_n), prompts[:warm_n], new_q,
               engine=eng)
        replay(model, np.zeros(warm_n), prompts[:warm_n], max_new,
               engine=eng)
        eng.metrics = ServingMetrics()
        wall_q, toks_q, _ = replay(model, arrivals, prompts, new_q,
                                   engine=eng)
        eng.metrics = ServingMetrics()
        wall, toks, metrics = replay(model, arrivals, prompts, max_new,
                                     engine=eng)
        m = metrics.export()
        marginal = ((toks - toks_q) / (wall - wall_q)
                    if wall > wall_q and toks > toks_q else None)
        out = {
            "tp_degree": tp,
            "tok_per_s_marginal": (round(marginal, 1)
                                   if marginal else None),
            "e2e_tok_per_s": round(toks / wall, 1),
            "wall_s": round(wall, 3),
            "wall_quarter_s": round(wall_q, 3),
            "ttft_p50_s": m["ttft_s"]["p50"],
            "ttft_p99_s": m["ttft_s"]["p99"],
            "inter_token_p50_s": m["inter_token_s"]["p50"],
            "tp_kernel_fallbacks": m["tp_kernel_fallbacks"],
            "preemptions": m["preemptions"],
        }
        results = {rid: tuple(r["tokens"])
                   for rid, r in eng.results().items()}
        return out, results

    degrees = (1, 2) if smoke else (1, 2, 4)
    points, ref = [], None
    for tp in degrees:
        out, got = measure(tp)
        points.append(out)
        if ref is None:
            ref = got
        else:
            # the tentpole contract: TP=k streams token-exact vs TP=1
            assert sorted(ref.values()) == sorted(got.values()), \
                f"tp={tp} streams diverged from tp=1"
    out = {
        "metric": "serving_tp_exactness" + ("" if on_tpu else "_cpu"),
        "value": max(degrees),
        "unit": "max TP degree streaming token-exact vs TP=1 (greedy, "
                "same Poisson trace, 8-device CPU mesh two-point "
                "marginals)",
        "n_requests": n_requests, "rate_per_s": rate,
        "max_new_tokens": max_new,
        "token_exact_vs_tp1": True,
        "mesh_devices": 8,
        "points": points,
        "smoke": smoke,
    }
    line = json.dumps(out)
    print(line)
    if not smoke:
        with open("BENCH_serving_tp.json", "w") as f:
            f.write(line + "\n")


if __name__ == "__main__":
    main()
