#!/usr/bin/env python3
"""Fleet traffic harness — the ISSUE-12 proof at scale.

Replays a bursty/diurnal arrival trace of tens of thousands of
requests against a SUPERVISED fleet (RouterSupervisor + journal) while
a seeded chaos schedule runs CONCURRENTLY — engine step faults and
latency spikes the whole way, one replica hard-kill and one primary-
router kill mid-traffic — and gates the run on SLOs:

- **zero lost or duplicated streams**: every accepted request completes
  token-exact vs a fault-free single-engine oracle (client-side splice
  over bounded resubmits; an exact match is simultaneously the no-loss
  and the no-duplication check),
- **TTFT / TPOT percentiles** (client-measured, arrival-to-first-token
  — queue wait included, that is what a user sees),
- **shed rate** under the burst peaks,
- **page conservation + quiescence** on every surviving engine after
  drain (the chaos-layer invariants),
- **zero leaked processes** after the process-fleet phase (the backend
  reaps everything; the gate asserts it).

Two phases:

1. **scale replay** (in-process replicas): the volume phase — the
   arrival trace is a diurnal sinusoid with superimposed burst windows,
   paced in real time and consumed by a worker pool.  The replica kill
   and the router kill (standby takeover) land at fixed progress
   fractions, so every banked run exercises both.
2. **process fleet** (``ProcessReplicaBackend`` + real server
   processes): a smaller replay proving the same contract across
   process boundaries — one replica server is SIGKILLed mid-traffic
   (supervision restarts it, the prober readmits it), the primary
   router is killed (standby takeover over HTTP replicas), and the
   zero-orphan gate closes the phase.

Usage:
    python tools/fleet_harness.py [--requests N] [--rate R]
        [--replicas K] [--smoke] [--json] [--out BENCH.json]
        [--skip-process-fleet] [--slo-ttft-p99 S] [--slo-shed-max F]

``--smoke`` is the tools/fleet_smoke.sh shape: a small replay (still
both phases, both kills) bounded to tens of seconds; it never writes
the banked artifact unless ``--out`` is passed explicitly.
"""
from __future__ import annotations

import argparse
import json
import os
import queue
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# standalone driver: pick the CPU platform before any framework work
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import paddle_tpu as P  # noqa: E402
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.serving import (ChaosConfig, InProcessReplica,  # noqa: E402
                                ProcessReplicaBackend, Rejected,
                                ReplicaSpec, RouterSupervisor,
                                ServingEngine, SubprocessLauncher,
                                Unavailable)
from paddle_tpu.serving.chaos import (fleet_invariants,  # noqa: E402
                                      verify_engine_quiescent)

VOCAB = 97
PROMPT_POOL = 48          # distinct prompts (oracle computed once each)
LIVENESS_S = 90.0         # per-request completion deadline

ENGINE_RATES = {"step_fault": 0.01, "step_latency": 0.02}


def tiny_model(seed=0):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=4,
                      max_position_embeddings=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def make_engine(chaos=None, num_pages=400):
    return ServingEngine(tiny_model(0), page_size=4,
                         num_pages=num_pages, max_batch=8,
                         prefill_chunk=8, chaos=chaos)


def warm_engine(eng, max_new=4):
    """Compile the bucketed program classes off the traffic clock — 8
    concurrent requests so every decode bucket the replay will hit is
    traced before the SLO clock starts (the bench_serving warmup
    lesson: a first-call trace mid-replay nulls the percentiles)."""
    from paddle_tpu.serving import FaultInjected
    for k in range(8):
        eng.add_request(np.arange(6 + k, dtype=np.int32) % VOCAB,
                        max_new_tokens=max_new)
    for _ in range(2000):
        if eng.scheduler.all_done():
            break
        try:
            eng.step()
        except FaultInjected:
            continue
    eng.cache.clear_prefix()


def build_pool(rng, n=PROMPT_POOL, lo=8, hi=16, shared_frac=0.5):
    """Distinct prompts, half opening with a common 2-page prefix so
    the cache-aware tier has real affinity to rebuild after takeover."""
    shared = rng.integers(0, VOCAB, 8).astype(np.int32)
    pool = []
    for i in range(n):
        tail = rng.integers(0, VOCAB, int(rng.integers(lo, hi)))\
            .astype(np.int32)
        pool.append(np.concatenate([shared, tail])
                    if i < int(n * shared_frac) else tail)
    return pool


def oracle_tokens(pool, max_new):
    eng = make_engine()
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in pool]
    res = eng.run()
    return [res[r]["tokens"] for r in rids]


def arrival_times(rng, n, mean_rate, burst_factor=4.0,
                  burst_frac=0.08, diurnal_amp=0.7):
    """Bursty/diurnal arrivals: a sinusoidal base rate (two 'days'
    across the replay) with Poisson bursts at ``burst_factor``x during
    ``burst_frac`` of the windows.  Returns seconds-from-start, sorted."""
    duration = n / mean_rate
    t, out = 0.0, []
    while len(out) < n:
        phase = 2.0 * np.pi * 2.0 * (t / max(duration, 1e-9))
        rate = mean_rate * (1.0 + diurnal_amp * np.sin(phase))
        if rng.random() < burst_frac:
            rate *= burst_factor
        rate = max(rate, mean_rate * 0.05)
        t += float(rng.exponential(1.0 / rate))
        out.append(t)
    return out


class Stats:
    """Thread-safe accumulators for the client-side SLO numbers."""

    def __init__(self):
        self.lock = threading.Lock()
        self.ttft = []
        self.tpot = []
        self.sheds = 0
        self.attempts = 0
        self.resubmits = 0
        self.mismatches = []
        self.failures = []

    def percentiles(self, xs):
        if not xs:
            return {"p50": None, "p99": None}
        a = np.asarray(xs)
        return {"p50": round(float(np.percentile(a, 50)), 4),
                "p99": round(float(np.percentile(a, 99)), 4)}


def consume_one(sup, prompt, want, max_new, stats, arrived_at):
    """One request end-to-end with bounded splice-resubmits: the
    client-visible token stream must equal the oracle exactly (no loss,
    no duplication) no matter what dies underneath."""
    got = []
    reasons = []
    first_tok_at = None
    last_tok_at = None
    deadline = time.monotonic() + LIVENESS_S
    while True:
        if time.monotonic() >= deadline:
            raise TimeoutError(f"liveness: request not done in "
                               f"{LIVENESS_S}s ({len(got)} tokens)")
        skip = len(got)
        with stats.lock:
            stats.attempts += 1
            if skip:
                stats.resubmits += 1
        try:
            stream = sup.submit(prompt, max_new_tokens=max_new)
        except (Rejected, Unavailable):
            with stats.lock:
                stats.sheds += 1
            time.sleep(0.02)
            continue
        try:
            for ev in stream.events(timeout=LIVENESS_S):
                if ev["type"] == "finish":
                    reasons.append(ev.get("reason"))
                if ev["type"] != "token":
                    continue
                if skip > 0:
                    skip -= 1
                    continue
                now = time.monotonic()
                if first_tok_at is None:
                    first_tok_at = now
                last_tok_at = now
                got.append(ev["token"])
            break
        except RuntimeError:
            continue  # stream died terminally: resubmit + splice
    if got != want:
        with stats.lock:
            stats.mismatches.append({"got": got, "want": want,
                                     "finish_reasons": reasons})
        return
    with stats.lock:
        if first_tok_at is not None:
            stats.ttft.append(first_tok_at - arrived_at)
        if last_tok_at is not None and first_tok_at is not None \
                and len(got) > 1:
            stats.tpot.append((last_tok_at - first_tok_at)
                              / (len(got) - 1))


def run_replay(sup, pool, want, schedule, max_new, workers,
               drills=()):
    """Pace the arrival schedule in real time through a worker pool;
    fire each (progress_fraction, fn) drill once as the replay crosses
    it.  Returns (stats, wall_s)."""
    stats = Stats()
    work: "queue.Queue" = queue.Queue()

    def client():
        while True:
            item = work.get()
            if item is None:
                return
            i, arrived_at = item
            prompt = pool[i % len(pool)]
            try:
                consume_one(sup, prompt, want[i % len(pool)], max_new,
                            stats, arrived_at)
            except Exception as e:  # noqa: BLE001 - recorded, gated
                with stats.lock:
                    stats.failures.append(repr(e))

    threads = [threading.Thread(target=client, daemon=True)
               for _ in range(workers)]
    for t in threads:
        t.start()
    t0 = time.monotonic()
    fired = [False] * len(drills)
    n = len(schedule)
    for i, at in enumerate(schedule):
        for k, (frac, fn) in enumerate(drills):
            if not fired[k] and i >= frac * n:
                fired[k] = True
                threading.Thread(target=fn, daemon=True).start()
        now = time.monotonic() - t0
        if at > now:
            time.sleep(at - now)
        work.put((i, time.monotonic()))
    for _ in threads:
        work.put(None)
    for t in threads:
        t.join(timeout=LIVENESS_S * 2)
        if t.is_alive():
            stats.failures.append("client thread stuck (liveness)")
    return stats, time.monotonic() - t0


def phase_scale(args, rng):
    """Phase 1: in-process fleet at volume, replica kill + router kill
    mid-traffic."""
    pool = build_pool(rng)
    want = oracle_tokens(pool, args.max_new)
    engines = [make_engine(chaos=ChaosConfig(
        seed=args.seed * 31 + i, rates=ENGINE_RATES,
        step_latency_s=0.002, escalate_n=6))
        for i in range(args.replicas)]
    for eng in engines:
        warm_engine(eng)
    reps = [InProcessReplica(eng, max_queued=args.max_queued)
            for eng in engines]
    journal = os.path.join(args.workdir, "scale.journal")
    sup = RouterSupervisor(
        reps, journal_path=journal, policy=args.policy, page_size=4,
        chaos=ChaosConfig(seed=args.seed * 7,
                          rates={"journal_torn_write": 0.02}))
    sup.start()
    schedule = arrival_times(rng, args.requests, args.rate)

    def kill_replica():
        victim = int(rng.integers(0, args.replicas))
        sup.active.kill_replica(victim)

    def kill_router():
        sup.kill_active(cause="harness: router kill drill")

    try:
        stats, wall = run_replay(
            sup, pool, want, schedule, args.max_new, args.workers,
            drills=((0.3, kill_replica), (0.55, kill_router)))
        sup.drain(timeout=LIVENESS_S)
        checked = fleet_invariants(sup.active)
        report = {
            "requests": args.requests, "rate_req_s": args.rate,
            "replicas": args.replicas, "wall_s": round(wall, 1),
            "throughput_req_s": round(args.requests / wall, 1),
            "ttft_s": stats.percentiles(stats.ttft),
            "tpot_s": stats.percentiles(stats.tpot),
            "shed_rate": round(stats.sheds / max(stats.attempts, 1), 4),
            "resubmits": stats.resubmits,
            "lost_streams": len(stats.failures),
            "mismatched_streams": len(stats.mismatches),
            "takeovers": sup.takeovers,
            "takeover_s": (round(sup.takeover_s, 4)
                           if sup.takeover_s else None),
            "journal": sup.journal.stats(),
            "engines_conserved": checked,
            "chaos_fired": dict(sum(
                (eng.chaos.counts for eng in engines),
                sup.chaos.counts + sup.journal.chaos.counts)),
        }
        if stats.failures:
            report["failures"] = stats.failures[:5]
        if stats.mismatches:
            report["first_mismatch"] = stats.mismatches[0]
        return report
    finally:
        sup.close(timeout=LIVENESS_S)


def phase_process(args, rng):
    """Phase 2: real replica server processes — SIGKILL one
    mid-traffic, kill the router, reap everything."""
    pool = build_pool(rng, n=8)
    want = oracle_tokens(pool, args.max_new)
    spec = ReplicaSpec(model={"seed": 0},
                       engine={"page_size": 4, "num_pages": 200,
                               "max_batch": 8, "prefill_chunk": 8})
    backend = ProcessReplicaBackend(
        spec, launcher=SubprocessLauncher(log_dir=args.workdir),
        startup_s=args.startup_s, restart_budget=2,
        supervise_interval_s=0.2)
    sup = None
    try:
        reps = [backend.provision("mixed")
                for _ in range(args.proc_replicas)]
        journal = os.path.join(args.workdir, "proc.journal")
        sup = RouterSupervisor(reps, journal_path=journal,
                               policy="round_robin", page_size=4,
                               probe_interval_s=0.2)
        sup.start()
        # warm each server's compile caches off the traffic clock
        for i, p in enumerate(pool[:len(reps)]):
            consume_one(sup, p, want[i], args.max_new, Stats(),
                        time.monotonic())
        schedule = arrival_times(rng, args.proc_requests,
                                 args.proc_rate)

        def kill_proc():
            backend.kill_replica_process(reps[0])

        def kill_router():
            sup.kill_active(cause="harness: process-fleet router kill")

        stats, wall = run_replay(
            sup, pool, want, schedule, args.max_new,
            workers=max(4, args.workers // 4),
            drills=((0.25, kill_proc), (0.6, kill_router)))
        # the SIGKILL drill must be observed THROUGH recovery: wait for
        # supervision to restart the dead process and for the router's
        # prober to readmit it before the books close
        deadline = time.monotonic() + args.startup_s
        while time.monotonic() < deadline \
                and (backend.restarts < 1
                     or reps[0].health().get("status") != "ok"):
            time.sleep(0.1)
        sup.drain(timeout=LIVENESS_S)
        report = {
            "requests": args.proc_requests,
            "replicas": args.proc_replicas,
            "wall_s": round(wall, 1),
            "ttft_s": stats.percentiles(stats.ttft),
            "tpot_s": stats.percentiles(stats.tpot),
            "shed_rate": round(stats.sheds / max(stats.attempts, 1), 4),
            "lost_streams": len(stats.failures),
            "mismatched_streams": len(stats.mismatches),
            "takeovers": sup.takeovers,
            "takeover_s": (round(sup.takeover_s, 4)
                           if sup.takeover_s else None),
            "backend": backend.stats(),
        }
        if stats.failures:
            report["failures"] = stats.failures[:5]
        return report
    finally:
        if sup is not None:
            sup.close(timeout=LIVENESS_S)
        reaped = backend.close(grace=10.0)
        leftovers = backend.live_pids()
        # the zero-orphan gate data (asserted by the SLO gate below)
        if sup is not None:
            pass
        globals()["_LAST_REAP"] = {"reaped_clean": bool(reaped),
                                   "leaked_pids": leftovers}


def slo_gate(args, scale, proc):
    """The pass/fail verdict the smoke and the banked run share."""
    gates = {}
    gates["zero_lost_streams"] = (
        scale["lost_streams"] == 0
        and (proc is None or proc["lost_streams"] == 0))
    gates["zero_mismatched_streams"] = (
        scale["mismatched_streams"] == 0
        and (proc is None or proc["mismatched_streams"] == 0))
    gates["router_takeover_happened"] = scale["takeovers"] >= 1 and (
        proc is None or proc["takeovers"] >= 1)
    gates["page_conservation"] = scale["engines_conserved"] >= 1
    p99 = scale["ttft_s"]["p99"]
    gates["ttft_p99_slo"] = p99 is not None and p99 <= args.slo_ttft_p99
    gates["shed_rate_slo"] = scale["shed_rate"] <= args.slo_shed_max
    if proc is not None:
        reap = globals().get("_LAST_REAP", {})
        gates["zero_leaked_processes"] = (
            reap.get("reaped_clean") and not reap.get("leaked_pids"))
        gates["process_restart_happened"] = \
            proc["backend"]["restarts"] >= 1
    gates["pass"] = all(gates.values())
    return gates


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=20000)
    ap.add_argument("--rate", type=float, default=45.0,
                    help="mean arrival rate, requests/s — size so the "
                         "DIURNAL PEAK (1.7x mean) stays under the "
                         "fleet's service rate (~85 req/s for 3 tiny "
                         "replicas on the CPU mesh) and only the "
                         "burst windows (4x base) overshoot briefly; "
                         "a peak above capacity queues for the whole "
                         "peak half-cycle and the percentiles measure "
                         "the backlog, not the fleet")
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--max-queued", type=int, default=256)
    ap.add_argument("--workers", type=int, default=32)
    ap.add_argument("--policy", default="cache_aware")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--proc-replicas", type=int, default=2)
    ap.add_argument("--proc-requests", type=int, default=400)
    ap.add_argument("--proc-rate", type=float, default=30.0)
    ap.add_argument("--startup-s", type=float, default=60.0)
    ap.add_argument("--skip-process-fleet", action="store_true")
    ap.add_argument("--slo-ttft-p99", type=float, default=5.0)
    ap.add_argument("--slo-shed-max", type=float, default=0.2)
    ap.add_argument("--smoke", action="store_true",
                    help="bounded tens-of-seconds shape (both phases, "
                         "both kills); never banks unless --out")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--out", default=None,
                    help="bank the report JSON here (default "
                         "BENCH_serving_fleet.json on full runs)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests = min(args.requests, 300)
        args.rate = min(args.rate, 80.0)
        args.replicas = min(args.replicas, 2)
        args.workers = min(args.workers, 12)
        args.proc_requests = min(args.proc_requests, 60)
        args.proc_rate = min(args.proc_rate, 20.0)
    import tempfile
    args.workdir = tempfile.mkdtemp(prefix="pdtpu_fleet_harness_")

    rng = np.random.default_rng(args.seed)
    t0 = time.monotonic()
    scale = phase_scale(args, rng)
    proc = None
    if not args.skip_process_fleet:
        proc = phase_process(args, rng)
    gates = slo_gate(args, scale, proc)
    report = {
        "config": {"requests": args.requests, "rate": args.rate,
                   "replicas": args.replicas, "max_new": args.max_new,
                   "policy": args.policy, "seed": args.seed,
                   "smoke": bool(args.smoke)},
        "scale_replay": scale,
        "process_fleet": proc,
        "slo_gate": gates,
        "wall_s_total": round(time.monotonic() - t0, 1),
    }
    out = args.out
    if out is None and not args.smoke:
        out = "BENCH_serving_fleet.json"
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=1)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        print(json.dumps({"slo_gate": gates,
                          "ttft_s": scale["ttft_s"],
                          "shed_rate": scale["shed_rate"],
                          "takeover_s": scale["takeover_s"],
                          "wall_s": report["wall_s_total"]}, indent=1))
    return 0 if gates["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
