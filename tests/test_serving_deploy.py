"""Versioned live weight deployment + online draft distillation
(round 21, ISSUE 17): the WeightRegistry, the engine's blessed
``set_weights`` hot-swap (all-or-nothing, prefix-flushing,
version-advertising), the RollingDeployer's drain→quiesce→readmit
cycle, router-side per-stream version pinning (no stream ever splices
tokens from two weight versions — the failover resubmission and
prefix-ship skew guards), the distillation buffer/trainer/push loop,
and the round-19 ``_sup_lock`` serialization regression.

Exactness discipline: greedy decode is deterministic per (weights,
history), so "which version produced this stream" is decidable by
comparing against per-version single-engine oracles — the same
determinism→transparent-retry link the failover tests lean on."""
import io
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (ChaosConfig, DeployError, DistillBuffer,
                                DraftDistiller, InProcessReplica,
                                ProcessReplicaBackend, ReplicaSpec,
                                RollingDeployer, ServingEngine,
                                ServingRouter, ServingServer,
                                ThreadLauncher, WeightRegistry,
                                snapshot_weights)
from paddle_tpu.serving.distill import distill_buffer_from_env
from serving_utils import wait_until

ENG_KW = dict(page_size=4, num_pages=200, max_batch=8, prefill_chunk=8)


def tiny_model(seed=0, layers=2, hidden=32, **kw):
    P.seed(seed)
    cfg = LlamaConfig(vocab_size=97, hidden_size=hidden,
                      intermediate_size=2 * hidden,
                      num_hidden_layers=layers, num_attention_heads=4,
                      max_position_embeddings=64, **kw)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def make_engine(seed=0, **kw):
    merged = dict(ENG_KW)
    merged.update(kw)
    return ServingEngine(tiny_model(seed), **merged)


def oracle_tokens(prompts, max_new, model_seed=0, engine_kw=None,
                  arrays=None):
    """Single-engine oracle at one FIXED weight version (optionally a
    swapped-in array list) — the reference every version-exactness
    assertion compares against."""
    eng = make_engine(model_seed, **(engine_kw or {}))
    if arrays is not None:
        eng.set_weights("target", arrays, 999)
    rids = [eng.add_request(p, max_new_tokens=max_new) for p in prompts]
    res = eng.run()
    return [res[r]["tokens"] for r in rids]


def rng_prompts(n, lo=3, hi=12, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 97, int(rng.integers(lo, hi)))
            .astype(np.int32) for _ in range(n)]


# ---------------------------------------------------------------------------
# WeightRegistry


class TestWeightRegistry:
    def test_versions_monotonic_across_names(self):
        r = WeightRegistry()
        v1 = r.publish("target", [np.ones(3)])
        v2 = r.publish("draft", [np.zeros(2)])
        v3 = r.publish("target", [np.ones(3) * 2])
        assert (v1, v2, v3) == (1, 2, 3)  # ONE timeline for all names
        assert r.latest("target") == 3
        assert r.latest("draft") == 2
        assert r.latest("never") is None
        assert r.versions("target") == [1, 3]

    def test_publish_copies_its_bytes(self):
        r = WeightRegistry()
        src = np.ones(4)
        v = r.publish("target", [src])
        src[:] = 7.0  # a later optimizer step on the source
        assert r.get("target", v)[0][0] == 1.0

    def test_publish_from_model_snapshot(self):
        m = tiny_model(0)
        r = WeightRegistry()
        v = r.publish("target", m)
        arrays = r.get("target", v)
        assert len(arrays) == len(m._gen_state_tensors())
        np.testing.assert_array_equal(
            arrays[0], np.asarray(m._gen_state_tensors()[0]._data))

    def test_spill_roundtrip(self, tmp_path):
        r = WeightRegistry(dirpath=str(tmp_path))
        want = [np.arange(6, dtype=np.float32).reshape(2, 3),
                np.ones(5, np.int32)]
        v = r.publish("target", want)
        path = r.spill("target", v)
        assert path.endswith(f"target-v{v}.npz")
        assert r.stats()["in_memory"] == 0  # bytes moved, not copied
        got = r.get("target", v)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
        assert r.spill("target", v) == path  # idempotent

    def test_spill_without_dir_raises(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_SERVING_DEPLOY_DIR",
                           raising=False)
        r = WeightRegistry()
        v = r.publish("target", [np.ones(2)])
        with pytest.raises(DeployError, match="registry dir"):
            r.spill("target", v)

    def test_drop_refuses_latest(self, tmp_path):
        r = WeightRegistry(dirpath=str(tmp_path))
        v1 = r.publish("target", [np.ones(2)])
        v2 = r.publish("target", [np.ones(2) * 2])
        with pytest.raises(DeployError, match="latest"):
            r.drop("target", v2)
        r.drop("target", v1)  # rollback target retention is the
        with pytest.raises(KeyError):  # caller's policy, not ours
            r.get("target", v1)

    def test_get_unknown_raises(self):
        r = WeightRegistry()
        with pytest.raises(KeyError):
            r.get("target")
        with pytest.raises(KeyError):
            r.get("target", 42)

    def test_empty_publish_rejected(self):
        with pytest.raises(ValueError):
            WeightRegistry().publish("target", [])


# ---------------------------------------------------------------------------
# engine.set_weights — the blessed mutation site


class TestEngineSetWeights:
    def test_swap_takes_effect_next_run_no_rebuild(self):
        prompts = rng_prompts(3, seed=1)
        base = oracle_tokens(prompts, 6, model_seed=0)
        other_arrays = snapshot_weights(tiny_model(1))
        other = oracle_tokens(prompts, 6, model_seed=1)
        assert base != other  # different weights, different streams
        eng = make_engine(0)
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        res = eng.run()
        assert [res[r]["tokens"] for r in rids] == base
        eng.set_weights("target", other_arrays, 7)
        assert eng.weight_version == {"target": 7, "draft": 0}
        rids = [eng.add_request(p, max_new_tokens=6) for p in prompts]
        res = eng.run()
        # the swapped pytree flows through as arguments — the SAME
        # engine now reproduces the other model's streams exactly
        assert [res[r]["tokens"] for r in rids] == other
        assert eng.metrics.weight_swaps.value == 1
        assert eng.metrics.weight_version_target.value == 7

    def test_torn_payload_is_all_or_nothing(self):
        prompts = rng_prompts(2, seed=2)
        base = oracle_tokens(prompts, 5, model_seed=0)
        eng = make_engine(0)
        arrays = snapshot_weights(tiny_model(1))
        with pytest.raises(ValueError, match="torn"):
            eng.set_weights("target", arrays[: len(arrays) // 2], 9)
        assert eng.weight_version["target"] == 0
        assert eng.metrics.weight_swap_rejects.value == 1
        rids = [eng.add_request(p, max_new_tokens=5) for p in prompts]
        res = eng.run()
        assert [res[r]["tokens"] for r in rids] == base  # old serves

    def test_shape_skew_rejected_before_any_write(self):
        eng = make_engine(0)
        arrays = snapshot_weights(eng.model)
        good0 = np.array(arrays[0], copy=True)
        arrays[-1] = np.zeros((3, 3), np.float32)  # wrong tail shape
        arrays[0] = good0 * 2  # head would have been "written first"
        with pytest.raises(ValueError, match="shape"):
            eng.set_weights("target", arrays, 9)
        np.testing.assert_array_equal(
            np.asarray(eng.model._gen_state_tensors()[0]._data), good0)

    def test_unknown_set_and_missing_draft_raise(self):
        eng = make_engine(0)
        with pytest.raises(ValueError, match="unknown weight set"):
            eng.set_weights("verifier", [], 1)
        with pytest.raises(ValueError, match="draft"):
            eng.set_weights("draft", [], 1)

    def test_target_swap_flushes_prefix_draft_swap_does_not(self):
        m = tiny_model(0)
        draft = tiny_model(5, layers=1, hidden=16)
        eng = ServingEngine(m, draft_model=draft, speculative_k=2,
                            prefix_cache=True, **ENG_KW)
        p = np.arange(12, dtype=np.int32) % 97
        eng.add_request(p, max_new_tokens=4)
        eng.run()
        assert eng.cache.cached_pages > 0
        # draft K/V is disposable and the draft only PROPOSES — no
        # flush on a draft refresh (in-flight streams stay exact)
        flushed = eng.set_weights(
            "draft", snapshot_weights(draft), 3)
        assert flushed == 0
        assert eng.cache.cached_pages > 0
        assert eng.weight_version == {"target": 0, "draft": 3}
        # target K/V was computed under the OLD weights: flush
        flushed = eng.set_weights(
            "target", snapshot_weights(tiny_model(1)), 4)
        assert flushed > 0
        assert eng.cache.cached_pages == 0


# ---------------------------------------------------------------------------
# frontend / replica / server surfaces


class TestFrontendAndReplicaSwap:
    def test_swap_quiesces_under_live_traffic(self):
        prompts = rng_prompts(4, seed=3)
        old = oracle_tokens(prompts, 8, model_seed=0)
        new_arrays = snapshot_weights(tiny_model(1))
        new = oracle_tokens(prompts, 8, model_seed=1, arrays=new_arrays)
        rep = InProcessReplica(make_engine(0)).start()
        try:
            # park live streams, swap mid-traffic, then finish: each
            # stream's tokens must match ONE version's oracle entirely
            streams = [rep.submit(p, max_new_tokens=8) for p in prompts]
            rep.swap_weights("target", new_arrays, 2)
            assert rep.weight_version("target") == 2
            for i, s in enumerate(streams):
                toks = [e["token"] for e in s.events(timeout=60)
                        if e["type"] == "token"]
                assert toks in (old[i], new[i]), (
                    f"stream {i} spliced versions: {toks}")
            # post-swap submissions are pure new-version streams
            got = [
                [e["token"]
                 for e in rep.submit(p, max_new_tokens=8)
                 .events(timeout=60) if e["type"] == "token"]
                for p in prompts]
            assert got == new
        finally:
            rep.close()

    def test_health_advertises_mutable_weight_version(self):
        rep = InProcessReplica(make_engine(0)).start()
        try:
            assert rep.health()["weight_version"] == {"target": 0,
                                                      "draft": 0}
            rep.swap_weights("target", snapshot_weights(tiny_model(1)),
                             5)
            # MUST be a fresh read (the deploy_stale_version hazard):
            # the version changed mid-life, unlike cache_dtype
            assert rep.health()["weight_version"]["target"] == 5
            assert rep.weight_version("target") == 5
        finally:
            rep.close()

    def test_http_swap_roundtrip(self):
        from paddle_tpu.serving import HTTPReplica
        server = ServingServer(make_engine(0), port=0)
        server.start()
        try:
            rep = HTTPReplica("127.0.0.1", server.port)
            assert rep.weight_version("target") == 0
            arrays = snapshot_weights(tiny_model(1))
            rep.swap_weights("target", arrays, 3)
            assert rep.weight_version("target") == 3  # fresh /healthz
            p = rng_prompts(1, seed=4)[0]
            want = oracle_tokens([p], 5, model_seed=1, arrays=arrays)[0]
            got = [e["token"] for e in
                   rep.submit(p, max_new_tokens=5).events(timeout=60)
                   if e["type"] == "token"]
            assert got == want
        finally:
            server.close()

    def test_http_torn_payload_bounces_with_400(self):
        import urllib.request
        import base64
        server = ServingServer(make_engine(0), port=0)
        server.start()
        try:
            arrays = snapshot_weights(tiny_model(1))[:2]  # torn
            buf = io.BytesIO()
            np.savez(buf, **{f"w{i}": a for i, a in enumerate(arrays)})
            body = json.dumps({
                "which": "target", "version": 3,
                "npz_b64": base64.b64encode(buf.getvalue()).decode(),
            }).encode()
            req = urllib.request.Request(
                f"http://127.0.0.1:{server.port}/v1/_deploy/swap",
                data=body, headers={"Content-Type": "application/json"})
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=30)
            assert ei.value.code == 400
            # all-or-nothing: the old version still serves
            assert server.frontend.weight_version("target") == 0
        finally:
            server.close()


# ---------------------------------------------------------------------------
# RollingDeployer


class TestRollingDeployer:
    def _fleet(self, n=2, **engine_kw):
        return [InProcessReplica(make_engine(0, **engine_kw)).start()
                for _ in range(n)]

    def test_bare_fleet_rollout_and_idempotence(self):
        reps = self._fleet(2)
        try:
            reg = WeightRegistry()
            v = reg.publish("target", tiny_model(1))
            dep = RollingDeployer(reps, reg)
            report = dep.rollout("target")
            assert (report["ok"], report["skipped"],
                    report["failed"]) == (2, 0, 0)
            assert report["complete"] and report["version"] == v
            assert all(r.weight_version("target") == v for r in reps)
            assert all(e["quiesce_s"] is not None
                       and e["advertised"] == v
                       for e in report["replicas"])
            again = dep.rollout("target")  # already there: all skipped
            assert (again["ok"], again["skipped"],
                    again["failed"]) == (0, 2, 0)
            assert again["complete"]
            assert dep.history == [report, again]
        finally:
            for r in reps:
                r.close()

    def test_router_rollout_serves_new_version(self):
        router = ServingRouter(self._fleet(2), page_size=4).start()
        try:
            reg = WeightRegistry()
            arrays = snapshot_weights(tiny_model(1))
            v = reg.publish("target", arrays)
            report = RollingDeployer(router, reg).rollout("target")
            assert report["complete"]
            prompts = rng_prompts(3, seed=5)
            want = oracle_tokens(prompts, 5, arrays=arrays)
            got = [router.submit(p, max_new_tokens=5)
                   .result(timeout=60)[0]["tokens"] for p in prompts]
            assert got == want
            # drain/readmit left every replica routable
            assert router.health()["status"] == "ok"
        finally:
            router.close()

    def test_swap_fail_chaos_degrades_to_old_version(self):
        router = ServingRouter(self._fleet(2), page_size=4).start()
        try:
            reg = WeightRegistry()
            reg.publish("target", tiny_model(1))
            dep = RollingDeployer(
                router, reg,
                chaos=ChaosConfig(rates={"deploy_swap_fail": 1.0}))
            report = dep.rollout("target")
            assert report["failed"] == 2 and not report["complete"]
            assert all("deploy_swap_fail" in e["error"]
                       for e in report["replicas"])
            # the failure contract: old version KEEPS SERVING — no
            # failed requests, old-oracle-exact streams
            prompts = rng_prompts(2, seed=6)
            want = oracle_tokens(prompts, 5, model_seed=0)
            got = [router.submit(p, max_new_tokens=5)
                   .result(timeout=60)[0]["tokens"] for p in prompts]
            assert got == want
            assert router.health()["status"] == "ok"  # all readmitted
        finally:
            router.close()

    def test_stale_version_chaos_converges_on_reread(self):
        reps = self._fleet(1)
        try:
            reg = WeightRegistry()
            v = reg.publish("target", tiny_model(1))
            dep = RollingDeployer(
                reps, reg,
                chaos=ChaosConfig(rates={"deploy_stale_version": 1.0}))
            report = dep.rollout("target")
            # a stale first scrape must trigger ONE fresh re-read —
            # never a re-roll of an already-applied swap
            assert report["ok"] == 1 and report["complete"]
            assert report["replicas"][0]["advertised"] == v
            assert reps[0].frontend.engine.metrics.weight_swaps.value \
                == 1
        finally:
            for r in reps:
                r.close()

    def test_rollback_is_a_rollout_of_an_older_id(self):
        reps = self._fleet(1)
        try:
            reg = WeightRegistry()
            v1 = reg.publish("target", tiny_model(1))
            v2 = reg.publish("target", tiny_model(2))
            dep = RollingDeployer(reps, reg)
            assert dep.rollout("target")["version"] == v2
            assert reps[0].weight_version("target") == v2
            back = dep.rollback("target")
            assert back["version"] == v1 and back["complete"]
            assert reps[0].weight_version("target") == v1
        finally:
            for r in reps:
                r.close()

    def test_rollback_needs_history(self):
        reg = WeightRegistry()
        reg.publish("target", tiny_model(1))
        with pytest.raises(DeployError, match="roll back"):
            RollingDeployer([], reg).rollback("target")

    def test_unpublished_rollout_raises(self):
        with pytest.raises(DeployError, match="no published"):
            RollingDeployer([], WeightRegistry()).rollout("target")
        with pytest.raises(ValueError, match="unknown weight set"):
            RollingDeployer([], WeightRegistry()).rollout("verifier")

    def test_sync_replica_catches_up_a_fresh_replica(self):
        reps = self._fleet(1)
        try:
            reg = WeightRegistry()
            v = reg.publish("target", tiny_model(1))
            dep = RollingDeployer(reps, reg)
            out = dep.sync_replica(reps[0])
            assert out["target"]["ok"]
            assert reps[0].weight_version("target") == v
            assert dep.sync_replica(reps[0]) == {}  # already current
        finally:
            for r in reps:
                r.close()


# ---------------------------------------------------------------------------
# router version pinning — zero cross-version splices


class TestRouterVersionPin:
    def _router(self, n=2):
        reps = [InProcessReplica(make_engine(0)).start()
                for _ in range(n)]
        return ServingRouter(reps, page_size=4).start()

    def test_stream_pins_placement_version(self):
        router = self._router(2)
        try:
            s = router.submit(rng_prompts(1)[0], max_new_tokens=3)
            s.result(timeout=60)
            assert s.pinned_version == 0
        finally:
            router.close()

    def test_failover_refuses_version_skewed_survivor(self, monkeypatch):
        # slow decode so the kill lands mid-stream deterministically
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.05")
        router = self._router(2)
        try:
            victim = router.submit(rng_prompts(1, seed=7)[0],
                                   max_new_tokens=30)
            wait_until(lambda: victim.replica_idx is not None)
            first = victim.replica_idx
            other = 1 - first
            # roll ONLY the survivor to a new version (bare swap: no
            # traffic on it), then kill the serving replica
            router.replicas[other].swap_weights(
                "target", snapshot_weights(tiny_model(1)), 5)
            collected = []
            with pytest.raises(RuntimeError, match="failover failed"):
                for ev in victim.events(timeout=60):
                    if ev["type"] == "token":
                        collected.append(ev["token"])
                        if len(collected) == 2:
                            router.kill_replica(first)
            # the pin SKIPPED the skewed survivor rather than splice
            # old-version head tokens with new-version tail tokens —
            # the client restarts fresh (a correct, unspliced stream)
            assert router.metrics.version_pin_skips_total.value >= 1
        finally:
            router.close()

    def test_failover_splices_exactly_on_matched_versions(
            self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_SERVING_FAULT_LATENCY_S", "0.02")
        router = self._router(2)
        try:
            arrays = snapshot_weights(tiny_model(1))
            for rep in router.replicas:  # fleet fully rolled: same v
                rep.swap_weights("target", arrays, 5)
            p = rng_prompts(1, seed=8)[0]
            want = oracle_tokens([p], 10, arrays=arrays)[0]
            victim = router.submit(p, max_new_tokens=10)
            got = []
            for ev in victim.events(timeout=120):
                if ev["type"] == "token":
                    got.append(ev["token"])
                    if len(got) == 3:
                        router.kill_replica(victim.replica_idx)
            assert got == want  # token-exact splice at the SAME version
            assert victim.failovers == 1
            assert victim.pinned_version == 5
        finally:
            router.close()

    def test_ship_guard_skips_version_skewed_donor(self):
        # construct the skew directly: the guard logic must skip a
        # donor whose advertised version differs from the target's
        router = self._router(2)
        try:
            router.replicas[0].swap_weights(
                "target", snapshot_weights(tiny_model(1)), 5)
            assert router._replica_weight_version(0) == 5
            assert router._replica_weight_version(1) == 0
            before = router.metrics.prefix_ship_skipped_total.value(
                reason="version_skew")
            router._ship_prefix_inner(
                _FakeStream(), target_idx=1,
                prompt=np.arange(16, dtype=np.int32),
                total_pages=4, owners={0: 4})
            after = router.metrics.prefix_ship_skipped_total.value(
                reason="version_skew")
            assert after == before + 1
        finally:
            router.close()


class _FakeStream:
    request_id = "fake"
    prompt = np.arange(16, dtype=np.int32)


# ---------------------------------------------------------------------------
# distillation


class TestDistillBuffer:
    def test_history_clipping_shapes(self):
        b = DistillBuffer(capacity=8, max_history=4)
        b.log(np.asarray([1, 2, 3, 4, 5], np.int32), [10, 11], 42)
        hist, tok = b.snapshot()[0]
        assert hist == (4, 5, 10, 11) and tok == 42  # prompt-tail fill
        b.log(np.asarray([1, 2], np.int32), [], 7)
        assert b.snapshot()[1] == ((1, 2), 7)  # short history stays
        b.log(np.asarray([1], np.int32), list(range(20, 30)), 8)
        assert b.snapshot()[2] == ((26, 27, 28, 29), 8)  # out tail wins

    def test_capacity_ring_and_stats(self):
        b = DistillBuffer(capacity=3, max_history=2)
        for i in range(5):
            b.log(np.asarray([i], np.int32), [i], i)
        assert len(b) == 3 and b.logged == 5
        assert [tok for _, tok in b.snapshot()] == [2, 3, 4]
        assert b.stats()["pairs"] == 3
        got = b.snapshot(clear=True)
        assert len(got) == 3 and len(b) == 0

    def test_env_gate(self, monkeypatch):
        monkeypatch.delenv("PADDLE_TPU_SERVING_DISTILL", raising=False)
        assert distill_buffer_from_env() is None
        monkeypatch.setenv("PADDLE_TPU_SERVING_DISTILL", "1")
        monkeypatch.setenv("PADDLE_TPU_SERVING_DISTILL_BUFFER", "17")
        monkeypatch.setenv("PADDLE_TPU_SERVING_DISTILL_HIST", "9")
        b = distill_buffer_from_env()
        assert (b.capacity, b.max_history) == (17, 9)

    def test_engine_logs_verify_pairs(self):
        m = tiny_model(0)
        buf = DistillBuffer(capacity=256, max_history=8)
        eng = ServingEngine(m, draft_model=m, speculative_k=2,
                            distill=buf, **ENG_KW)
        for p in rng_prompts(3, seed=9):
            eng.add_request(p, max_new_tokens=5)
        eng.run()
        # every spec-verify-emitted token logged ONE (history, target)
        # pair (first tokens come from prefill, not the verify loop)
        assert buf.logged == eng.metrics.distill_pairs.value
        assert buf.logged > 0
        hist, tok = buf.snapshot()[0]
        assert len(hist) <= 8 and 0 <= tok < 97


class TestDraftDistiller:
    def _pairs_model(self, seed=11):
        # a learnable synthetic rule: target = (last token + 1) % 97
        return tiny_model(seed, layers=1, hidden=16)

    def _fill(self, buf, n=256, seed=3):
        rng = np.random.default_rng(seed)
        for _ in range(n):
            hist = rng.integers(0, 97, 6).astype(np.int32)
            buf.log(hist, [], int((hist[-1] + 1) % 97))

    def test_train_once_reduces_loss(self):
        buf = DistillBuffer(capacity=512, max_history=6)
        self._fill(buf)
        d = DraftDistiller(self._pairs_model(), buf, lr=5e-2,
                           batch_size=64, min_pairs=64)
        first = d.train_once(max_steps=12)
        assert first["steps"] > 0
        second = d.train_once(max_steps=12)
        assert second["loss_last"] < first["loss_first"]
        assert d.steps_trained == first["steps"] + second["steps"]

    def test_min_pairs_gate(self):
        buf = DistillBuffer(capacity=64, max_history=4)
        d = DraftDistiller(self._pairs_model(), buf, min_pairs=64)
        rep = d.train_once()
        assert rep["steps"] == 0 and "skipped" in rep

    def test_push_publishes_and_rolls_draft(self):
        m = tiny_model(0)
        draft = tiny_model(5, layers=1, hidden=16)
        eng = ServingEngine(m, draft_model=draft, speculative_k=2,
                            **ENG_KW)
        rep = InProcessReplica(eng).start()
        try:
            reg = WeightRegistry()
            dep = RollingDeployer([rep], reg)
            P.seed(12)
            train = tiny_model(5, layers=1, hidden=16)
            d = DraftDistiller(train, DistillBuffer())
            out = d.push(reg, dep)
            assert out["rolled"]["complete"]
            assert rep.weight_version("draft") == out["version"]
            assert d.pushes == 1
        finally:
            rep.close()

    def test_torn_push_bounces_old_draft_serves(self):
        m = tiny_model(0)
        draft = tiny_model(5, layers=1, hidden=16)
        eng = ServingEngine(m, draft_model=draft, speculative_k=2,
                            **ENG_KW)
        rep = InProcessReplica(eng).start()
        try:
            reg = WeightRegistry()
            dep = RollingDeployer([rep], reg)
            d = DraftDistiller(
                tiny_model(5, layers=1, hidden=16), DistillBuffer(),
                chaos=ChaosConfig(rates={"distill_push_torn": 1.0}))
            out = d.push(reg, dep)
            # the torn payload reached the engine and was bounced by
            # the all-or-nothing validation: version stays 0, the old
            # draft serves, requests still complete (proposals only)
            assert not out["rolled"]["complete"]
            assert rep.weight_version("draft") == 0
            assert eng.metrics.weight_swap_rejects.value >= 1
            p = rng_prompts(1, seed=13)[0]
            want = oracle_tokens([p], 5, model_seed=0)[0]
            got = [e["token"] for e in
                   rep.submit(p, max_new_tokens=5).events(timeout=60)
                   if e["type"] == "token"]
            assert got == want
        finally:
            rep.close()

    def test_background_loop_trains_and_pushes(self):
        buf = DistillBuffer(capacity=512, max_history=6)
        self._fill(buf, n=128)
        reg = WeightRegistry()
        d = DraftDistiller(self._pairs_model(), buf, lr=1e-2,
                           batch_size=64, min_pairs=64)
        d.run_background(reg, None, interval_s=0.01, max_steps=2)
        try:
            wait_until(lambda: reg.latest("draft") is not None,
                       timeout=60)
            with pytest.raises(RuntimeError, match="already running"):
                d.run_background(reg, None)
        finally:
            d.stop()
        assert d.pushes >= 1


# ---------------------------------------------------------------------------
# round-19 regression: engine rebuilds stay serialized under _sup_lock


class TestSupervisionSerialization:
    def test_concurrent_supervise_passes_never_overlap_builds(self):
        """P.seed() is a process GLOBAL: two engine builds interleaving
        their RNG draws produce different weights (round-19 addenda —
        restarted replicas then token-diverge).  A rolling deploy adds
        a second driver of replica churn next to the supervision
        daemon, so pin the serialization: N threads hammering
        supervise_once() while replicas need restarting must never
        build two engines at once."""
        active = [0]
        peak = [0]
        gate = threading.Lock()

        def factory(spec):
            with gate:
                active[0] += 1
                peak[0] = max(peak[0], active[0])
            time.sleep(0.02)  # widen the window a racing build needs
            eng = make_engine(0, num_pages=32)
            with gate:
                active[0] -= 1
            return eng

        backend = ProcessReplicaBackend(
            {"mixed": ReplicaSpec(role="mixed")},
            launcher=ThreadLauncher(engine_factory=factory),
            supervise_interval_s=0.0)
        try:
            reps = [backend.provision("mixed") for _ in range(2)]
            for r in reps:
                backend.kill_replica_process(r)
            threads = [threading.Thread(target=backend.supervise_once)
                       for _ in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert backend.restarts >= 1
            assert peak[0] == 1, (
                f"{peak[0]} concurrent engine builds — P.seed() RNG "
                "draws interleaved (round-19 hazard)")
        finally:
            backend.close()


@pytest.mark.slow
class TestServingDeployReplay:
    """The deploy harness's tier-1 shape in a subprocess (the conftest
    artifact guard snapshots BENCH_serving*.json around this class —
    the smoke never banks, but belt and braces)."""

    def test_deploy_harness_smoke_gate_passes(self):
        import os
        import subprocess
        import sys
        repo = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))
        proc = subprocess.Popen(
            [sys.executable, "tools/deploy_harness.py", "--smoke",
             "--json"],
            cwd=repo, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL)
        out, _ = proc.communicate(timeout=280)
        assert proc.returncode == 0
        report = json.loads(out)
        gate = report["deploy_gate"]
        assert gate["pass"], gate
        assert gate["zero_version_splices"]
        assert gate["all_replicas_on_new_version"]
        assert gate["acceptance_improved"]
        assert gate["distill_tokens_identical"]
        assert report["rolling_deploy"]["quiesce_s"]["max"] is not None
