#!/usr/bin/env python3
"""Versioned-deployment harness — the ISSUE-17 acceptance artifact.

Phase 1 (rolling deploy under traffic): a 3-replica in-process fleet
serves a paced arrival schedule while a RollingDeployer rolls the
TARGET weights to a new version mid-replay (drain → quiesce-swap →
readmit per replica; chaos-free — the fault schedules live in
tools/chaos_fuzz.py's deploy wave) and a replica-kill drill fires
mid-rollout.  The gate is VERSION-PINNED exactness: every client
stream must match ONE version's fault-free oracle in its entirety — a
mixed stream is a cross-version splice, the structural failure the
router's per-stream pin exists to prevent.  Clients restart FRESH on
a terminal stream death (never splice a resubmission: it may land on
the other version).  The banked report records per-replica
``quiesce_s`` — the time each engine spent weight-swapping under the
frontend lock.

Phase 2 (online draft distillation): a speculative engine serves a
SKEWED synthetic workload (a handful of hot prompts — the shape a
per-workload draft can actually learn) with a deliberately mismatched
draft, logging (history, target-token) pairs from the verify step.
The DraftDistiller trains a copy of the draft on those pairs and
pushes it through the same deployer; the gate is that the measured
acceptance rate IMPROVES on the same workload while the emitted
tokens stay bit-identical (the draft only proposes — the target's
verify step decides every token).

Usage:
    python tools/deploy_harness.py [--requests N] [--rate R]
                                   [--smoke] [--json] [--out BENCH.json]

``--smoke`` is the tools/deploy_smoke.sh tier-1 shape: a bounded
replay with the same gates; it never banks unless --out is given
(the conftest artifact guard also restores BENCH_serving_deploy.json
around the in-suite replay test).
"""
from __future__ import annotations

import argparse
import json
import os
import queue
import sys
import threading
import time

_TOOLS = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(_TOOLS))
sys.path.insert(0, _TOOLS)

# standalone driver: pick the CPU platform before any framework work.
# fleet_harness does it at import time;
# importing it here is what makes the shared helpers safe too.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

import fleet_harness as fh  # noqa: E402  (arrival_times/Stats/pool)
import paddle_tpu as P  # noqa: E402
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM  # noqa: E402
from paddle_tpu.serving import (InProcessReplica, Rejected,  # noqa: E402
                                RollingDeployer, ServingEngine,
                                ServingRouter, DistillBuffer,
                                DraftDistiller, Unavailable,
                                WeightRegistry, snapshot_weights)

VOCAB = 97
LIVENESS_S = 90.0
NEW_SEED = 7          # the "retrained" target weights


def tiny_draft(seed, hidden=16):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=hidden,
                      intermediate_size=2 * hidden, num_hidden_layers=1,
                      num_attention_heads=2, max_position_embeddings=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def oracle_tokens(pool, max_new, model_seed=0):
    eng = ServingEngine(fh.tiny_model(model_seed), page_size=4,
                        num_pages=400, max_batch=8, prefill_chunk=8)
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in pool]
    res = eng.run()
    return [res[r]["tokens"] for r in rids]


def consume_pinned(router, prompt, oracles, max_new, stats, arrived_at):
    """One request end-to-end, version-pinned: a terminal stream death
    restarts FRESH (the resubmission may land on the other version —
    splicing it would manufacture the exact bug under test).  The one
    full stream that completes must equal SOME version's oracle."""
    deadline = time.monotonic() + LIVENESS_S
    while True:
        if time.monotonic() >= deadline:
            raise TimeoutError(
                f"liveness: request not done in {LIVENESS_S}s")
        with stats.lock:
            stats.attempts += 1
        try:
            stream = router.submit(prompt, max_new_tokens=max_new)
        except (Rejected, Unavailable):
            with stats.lock:
                stats.sheds += 1
            time.sleep(0.02)
            continue
        got = []
        first_tok_at = None
        try:
            for ev in stream.events(timeout=LIVENESS_S):
                if ev["type"] != "token":
                    continue
                if first_tok_at is None:
                    first_tok_at = time.monotonic()
                got.append(ev["token"])
        except RuntimeError:
            with stats.lock:
                stats.resubmits += 1
            continue  # died terminally: restart fresh on some version
        if got not in oracles:
            with stats.lock:
                stats.mismatches.append(
                    {"got": got, "oracles": list(oracles)})
        elif first_tok_at is not None:
            with stats.lock:
                stats.ttft.append(first_tok_at - arrived_at)
        return


def run_pinned_replay(router, pool, oracle_pairs, schedule, max_new,
                      workers, drills=()):
    """Pace the arrivals through a worker pool (fleet_harness.Stats
    for the client-side numbers); fire each (progress_fraction, fn)
    drill once as the replay crosses it."""
    stats = fh.Stats()
    work: "queue.Queue" = queue.Queue()

    def client():
        while True:
            item = work.get()
            if item is None:
                return
            i, arrived_at = item
            k = i % len(pool)
            try:
                consume_pinned(router, pool[k], oracle_pairs[k],
                               max_new, stats, arrived_at)
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


def phase_rolling(args, rng):
    """Phase 1: rolling target deploy + replica-kill drill under paced
    traffic, gated on version-pinned exactness."""
    pool = fh.build_pool(rng, n=24)
    want_old = oracle_tokens(pool, args.max_new)
    want_new = oracle_tokens(pool, args.max_new, model_seed=NEW_SEED)
    assert want_old != want_new, "oracle versions indistinguishable"
    oracle_pairs = [(o, n) for o, n in zip(want_old, want_new)]
    engines = [ServingEngine(fh.tiny_model(0), page_size=4,
                             num_pages=400, max_batch=8,
                             prefill_chunk=8)
               for _ in range(args.replicas)]
    for eng in engines:
        fh.warm_engine(eng, max_new=args.max_new)
    reps = [InProcessReplica(eng, max_queued=args.max_queued)
            for eng in engines]
    router = ServingRouter(reps, policy=args.policy, page_size=4,
                           probe_interval_s=0.2)
    reg = WeightRegistry()
    new_v = reg.publish("target", snapshot_weights(
        fh.tiny_model(NEW_SEED)))
    dep = RollingDeployer(router, reg, drain_timeout_s=LIVENESS_S)
    router.start()
    schedule = fh.arrival_times(rng, args.requests, args.rate)
    rollout_done = threading.Event()
    rollout_err = []

    def do_rollout():
        try:
            deadline = time.monotonic() + LIVENESS_S
            while True:
                report = dep.rollout("target", new_v)
                if report["complete"]:
                    return
                if time.monotonic() >= deadline:
                    raise TimeoutError("rollout never completed: "
                                       + json.dumps(report["replicas"]))
        except Exception as e:  # noqa: BLE001 - recorded, gated
            rollout_err.append(repr(e))
        finally:
            rollout_done.set()

    def kill_replica():
        router.kill_replica(int(rng.integers(0, args.replicas)))

    try:
        stats, wall = run_pinned_replay(
            router, pool, oracle_pairs, schedule, args.max_new,
            args.workers,
            drills=((0.25, do_rollout), (0.45, kill_replica)))
        assert rollout_done.wait(LIVENESS_S), "rollout thread stuck"
        # a kill racing the rollout can leave a replica un-swapped
        # (deploy failure degrades to the old version serving) — the
        # operator's converging move is re-running the same rollout
        final = dep.rollout("target", new_v)
        router.drain(timeout=LIVENESS_S)
        versions = [r.weight_version("target") for r in reps]
        # per-replica quiesce: the swap-time entries from the rollout
        # history (skipped entries carry no quiesce)
        quiesce = [e["quiesce_s"] for rep in dep.history
                   for e in rep["replicas"]
                   if e["quiesce_s"] is not None]
        return {
            "requests": args.requests, "rate_req_s": args.rate,
            "replicas": args.replicas, "wall_s": round(wall, 1),
            "version_rolled": new_v,
            "replica_versions": versions,
            "rollout_complete": final["complete"] and not rollout_err,
            "rollout_errors": rollout_err,
            "rollouts_run": len(dep.history),
            "quiesce_s": {
                "per_swap": [round(q, 4) for q in quiesce],
                "max": round(max(quiesce), 4) if quiesce else None,
            },
            "ttft_s": stats.percentiles(stats.ttft),
            "shed_rate": round(
                stats.sheds / max(stats.attempts, 1), 4),
            "fresh_restarts": stats.resubmits,
            "lost_streams": len(stats.failures),
            "spliced_or_mismatched_streams": len(stats.mismatches),
            "first_mismatch": (stats.mismatches[0]
                               if stats.mismatches else None),
            "failures": stats.failures[:5],
        }
    finally:
        router.close()


def phase_distill(args, rng):
    """Phase 2: draft distillation on a skewed workload — acceptance
    must improve after the push while the emitted tokens stay
    bit-identical."""
    # the skew: a handful of hot prompts replayed over and over (the
    # system-prompt-plus-template shape); tiny histories a 1-layer
    # draft can memorize
    pool = [rng.integers(0, VOCAB, int(rng.integers(6, 10)))
            .astype(np.int32) for _ in range(args.distill_prompts)]
    buf = DistillBuffer(capacity=4096, max_history=8)
    # build SERIALLY: P.seed is process-global (round-19 hazard)
    target = fh.tiny_model(0)
    draft = tiny_draft(91)      # deliberately mismatched vs the target
    train_copy = tiny_draft(91)  # same init: the trained successor
    eng = ServingEngine(target, draft_model=draft, speculative_k=3,
                        distill=buf, page_size=4, num_pages=400,
                        max_batch=8, prefill_chunk=8)
    rep = InProcessReplica(eng).start()
    reg = WeightRegistry()
    dep = RollingDeployer([rep], reg)

    def run_workload(passes):
        # drive through the replica's frontend — its loop thread owns
        # the engine lock; stepping the engine directly here would
        # race it (the engine-lock discipline)
        m = eng.metrics
        d0, a0 = m.spec_draft_tokens.value, m.spec_accepted_tokens.value
        toks = []
        for _ in range(passes):
            streams = [rep.submit(p, max_new_tokens=args.max_new)
                       for p in pool]
            toks.append([s.result(timeout=LIVENESS_S)[0]["tokens"]
                         for s in streams])
        drafted = m.spec_draft_tokens.value - d0
        accepted = m.spec_accepted_tokens.value - a0
        return toks, accepted / max(drafted, 1)

    try:
        toks_before, acc_before = run_workload(args.distill_passes)
        pairs_logged = len(buf)
        dist = DraftDistiller(train_copy, buf, lr=args.distill_lr,
                              batch_size=32, min_pairs=8)
        train_report, t0 = None, time.monotonic()
        for _ in range(args.distill_epochs):
            train_report = dist.train_once(max_steps=200)
        train_s = time.monotonic() - t0
        push = dist.push(reg, dep)
        assert push["rolled"]["complete"], push
        toks_after, acc_after = run_workload(args.distill_passes)
    finally:
        rep.close()
    return {
        "workload": {"prompts": len(pool), "passes": args.distill_passes,
                     "max_new": args.max_new},
        "pairs_logged": pairs_logged,
        "train": {"epochs": args.distill_epochs,
                  "steps": dist.steps_trained,
                  "loss_first": train_report.get("loss_first"),
                  "loss_last": train_report.get("loss_last"),
                  "wall_s": round(train_s, 1)},
        "draft_version_pushed": push["version"],
        "acceptance_before": round(acc_before, 4),
        "acceptance_after": round(acc_after, 4),
        "acceptance_delta": round(acc_after - acc_before, 4),
        "tokens_identical": toks_after == toks_before,
    }


def deploy_gate(args, rolling, distill):
    """The pass/fail verdict the smoke and the banked run share."""
    gates = {}
    gates["zero_lost_streams"] = rolling["lost_streams"] == 0
    gates["zero_version_splices"] = \
        rolling["spliced_or_mismatched_streams"] == 0
    gates["rollout_complete"] = bool(rolling["rollout_complete"])
    gates["all_replicas_on_new_version"] = all(
        v == rolling["version_rolled"]
        for v in rolling["replica_versions"])
    p99 = rolling["ttft_s"]["p99"]
    gates["ttft_p99_slo"] = p99 is not None and p99 <= args.slo_ttft_p99
    gates["shed_rate_slo"] = rolling["shed_rate"] <= args.slo_shed_max
    gates["acceptance_improved"] = distill["acceptance_delta"] > 0
    gates["distill_tokens_identical"] = distill["tokens_identical"]
    gates["pass"] = all(gates.values())
    return gates


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--requests", type=int, default=1500)
    ap.add_argument("--rate", type=float, default=40.0)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--max-new", type=int, default=4)
    ap.add_argument("--max-queued", type=int, default=256)
    ap.add_argument("--workers", type=int, default=24)
    ap.add_argument("--policy", default="round_robin")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--distill-prompts", type=int, default=6)
    ap.add_argument("--distill-passes", type=int, default=4)
    ap.add_argument("--distill-epochs", type=int, default=8)
    ap.add_argument("--distill-lr", type=float, default=3e-2)
    ap.add_argument("--slo-ttft-p99", type=float, default=5.0)
    ap.add_argument("--slo-shed-max", type=float, default=0.2)
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 shape: bounded replay, same gates; "
                         "never banks unless --out")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--out", default=None,
                    help="bank the report JSON here (default "
                         "BENCH_serving_deploy.json on full runs)")
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests = min(args.requests, 120)
        args.rate = min(args.rate, 60.0)
        args.replicas = min(args.replicas, 2)
        args.workers = min(args.workers, 8)
        args.distill_passes = min(args.distill_passes, 2)
        args.distill_epochs = min(args.distill_epochs, 6)

    rng = np.random.default_rng(args.seed)
    t0 = time.monotonic()
    rolling = phase_rolling(args, rng)
    distill = phase_distill(args, rng)
    gates = deploy_gate(args, rolling, distill)
    report = {
        "config": {"requests": args.requests, "rate": args.rate,
                   "replicas": args.replicas, "max_new": args.max_new,
                   "policy": args.policy, "seed": args.seed,
                   "smoke": bool(args.smoke)},
        "rolling_deploy": rolling,
        "distill": distill,
        "deploy_gate": gates,
        "wall_s_total": round(time.monotonic() - t0, 1),
    }
    out = args.out
    if out is None and not args.smoke:
        out = "BENCH_serving_deploy.json"
    if out:
        with open(out, "w") as f:
            json.dump(report, f, indent=1)
    if args.json:
        print(json.dumps(report, indent=1))
    else:
        print(json.dumps({
            "deploy_gate": gates,
            "quiesce_s": rolling["quiesce_s"],
            "acceptance_delta": distill["acceptance_delta"],
            "wall_s": report["wall_s_total"]}, indent=1))
    return 0 if gates["pass"] else 1


if __name__ == "__main__":
    sys.exit(main())
