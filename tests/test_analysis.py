"""graftlint (paddle_tpu.analysis, ISSUE 6): every rule gets a
bad/good fixture pair — the bad snippet reproduces the ORIGINAL bug
shape the rule encodes (round-11 grad-mode interleaving, verbatim
dist_spec return, ...) — plus suppression/
baseline mechanics, the env-knob registry sync check, and a whole-tree
self-check asserting the repo is clean modulo the checked-in baseline
(the same invariant tools/lint.sh gates ahead of tier-1 pytest).

Fast and CPU-only: pure AST work, no device touch, no jax tracing."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from paddle_tpu.analysis import (ALL_RULES, BAD_BASELINE,
                                 BAD_SUPPRESSION, Project, RULES_BY_ID,
                                 apply_baseline, knobs, load_baseline,
                                 run_paths, run_source, save_baseline)

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_PROJECT = Project(ROOT)


def lint(src, relpath, rule_id=None):
    rules = [RULES_BY_ID[rule_id]] if rule_id else ALL_RULES
    return run_source(textwrap.dedent(src), relpath, rules,
                      project=_PROJECT)


def rule_ids(findings):
    return {f.rule for f in findings}


# ---------------------------------------------------------------------------
# rule registry sanity

class TestRegistry:
    def test_twelve_rules_with_ids_and_docs(self):
        assert len(ALL_RULES) == 12
        for r in ALL_RULES:
            assert r.id and r.description
        assert set(RULES_BY_ID) == {
            "autograd-bypass", "thread-grad-state", "pallas-hazards",
            "jit-constant-capture", "dist-spec-passthrough",
            "engine-lock-discipline",
            "page-migration-lock", "env-knob-registry",
            "serving-raw-sleep", "fleet-process-spawn",
            "kvtier-blessed-access", "weight-swap-lock"}


# ---------------------------------------------------------------------------
# 1. autograd-bypass

_AUTOGRAD_BAD = """
    import jax

    def my_op(x):
        out, vjp_fn = jax.vjp(lambda a: a * 2, x)
        return out

    def my_grad(f, x):
        return jax.grad(f)(x)
"""

_AUTOGRAD_GOOD = """
    from ..core.autograd import apply

    def my_op(x):
        return apply(lambda a: a * 2, x)
"""

_AUTOGRAD_DEFVJP_GOOD = """
    import functools
    import jax

    @functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
    def op(x, flag):
        return x * 2

    def _op_fwd(x, flag):
        out, vjp_fn = jax.vjp(lambda a: a * 2, x)
        return out, vjp_fn

    def _op_bwd(flag, res, g):
        return (res(g)[0],)

    op.defvjp(_op_fwd, _op_bwd)
"""


class TestAutogradBypass:
    def test_bad_flags_both_calls(self):
        fs = lint(_AUTOGRAD_BAD, "paddle_tpu/nn/badop.py",
                  "autograd-bypass")
        assert len(fs) == 2
        assert all(f.rule == "autograd-bypass" for f in fs)

    def test_good_routes_through_apply(self):
        assert lint(_AUTOGRAD_GOOD, "paddle_tpu/nn/goodop.py",
                    "autograd-bypass") == []

    def test_defvjp_registered_fwd_allowed(self):
        # the flash-attention pattern: custom_vjp decorator + jax.vjp
        # inside the registered fwd is the blessed kernel-rule shape
        assert lint(_AUTOGRAD_DEFVJP_GOOD, "paddle_tpu/ops/kern.py",
                    "autograd-bypass") == []

    def test_ad_engine_files_exempt(self):
        assert lint(_AUTOGRAD_BAD, "paddle_tpu/core/autograd.py",
                    "autograd-bypass") == []

    def test_inline_disable_suppresses(self):
        src = _AUTOGRAD_BAD.replace(
            "out, vjp_fn = jax.vjp(lambda a: a * 2, x)",
            "out, vjp_fn = jax.vjp(lambda a: a * 2, x)  "
            "# graftlint: disable=autograd-bypass (fixture: intended)")
        fs = lint(src, "paddle_tpu/nn/badop.py", "autograd-bypass")
        assert len(fs) == 1  # only the jax.grad one remains


# ---------------------------------------------------------------------------
# 2. thread-grad-state — the round-11 interleaving pattern must flag

_THREAD_BAD = """
    import threading
    from ..core.autograd import is_grad_enabled, set_grad_enabled

    def loop(engine):
        prev = is_grad_enabled()
        set_grad_enabled(False)   # manual save/restore across threads:
        engine.do_step()          # the round-11 interleaving bug shape
        set_grad_enabled(prev)

    t = threading.Thread(target=loop)
"""

_THREAD_BAD_HELPER = """
    import threading
    from ..core.autograd import no_grad

    def helper():
        ctx = no_grad()
        ctx.__enter__()

    def loop(engine):
        helper()

    t = threading.Thread(target=loop)
"""

_THREAD_GOOD = """
    import threading
    from ..core.autograd import no_grad

    def loop(engine):
        with no_grad():
            engine.do_step()

    t = threading.Thread(target=loop)
"""


class TestThreadGradState:
    def test_round11_interleaving_pattern_flags(self):
        fs = lint(_THREAD_BAD, "paddle_tpu/serving/custom.py",
                  "thread-grad-state")
        assert len(fs) == 2  # both set_grad_enabled calls
        assert "round-11" in fs[0].message

    def test_unscoped_no_grad_in_callee_flags(self):
        fs = lint(_THREAD_BAD_HELPER, "paddle_tpu/serving/custom.py",
                  "thread-grad-state")
        assert rule_ids(fs) == {"thread-grad-state"}

    def test_scoped_with_block_passes(self):
        assert lint(_THREAD_GOOD, "paddle_tpu/serving/custom.py",
                    "thread-grad-state") == []

    def test_non_thread_manual_toggle_passes(self):
        # outside a thread target, manual toggling is main-thread code
        src = """
            from ..core.autograd import set_grad_enabled
            def eval_mode():
                set_grad_enabled(False)
        """
        assert lint(src, "paddle_tpu/hapi/thing.py",
                    "thread-grad-state") == []


# ---------------------------------------------------------------------------
# 3. pallas-hazards

_PALLAS_LOOP_BAD = """
    import jax
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        def body(i, acc):
            j = pl.program_id(0)
            return acc + j
        o_ref[...] = jax.lax.fori_loop(0, 4, body, 0)
"""

_PALLAS_LOOP_GOOD = """
    import jax
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        j = pl.program_id(0)   # hoisted to kernel top level
        def body(i, acc):
            return acc + j
        o_ref[...] = jax.lax.fori_loop(0, 4, body, 0)
"""

_PALLAS_PRNG_BAD = """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(seed_ref, o_ref):
        pltpu.prng_seed(seed_ref[0])
        o_ref[...] = pltpu.prng_random_bits(o_ref.shape)
"""

_PALLAS_BLOCKSPEC_BAD = """
    from jax.experimental import pallas as pl

    def build(seq_len, d, block_q):
        return pl.BlockSpec((1, seq_len, d), lambda i, j: (i, 0, 0))
"""

_PALLAS_BLOCKSPEC_GOOD = """
    from jax.experimental import pallas as pl

    def build(seq_len, d, block_q):
        return pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0))
"""

# round 22: the ragged kernel's packed-token axis T is batch*seq-scaled
# — a T-sized block is the same O(seq) VMEM hazard by another name
_PALLAS_BLOCKSPEC_TOK_BAD = """
    from jax.experimental import pallas as pl

    def build(t, nh, d):
        return pl.BlockSpec((t, nh, d), lambda i: (0, 0, 0))
"""

_PALLAS_BLOCKSPEC_TOK_GOOD = """
    from jax.experimental import pallas as pl

    def build(t, nh, d):
        # one token cell per grid instance: block stays O(1) on T
        return pl.BlockSpec((1, nh, d), lambda i: (i, 0, 0))
"""


# round 23: pallas_call mixed with GSPMD sharding machinery in one
# module — pallas_call has no GSPMD partitioning rule (the serving TP
# step pins the jnp gather path; tp.py vs attention.py is the split)
_PALLAS_SPMD_MIX_BAD = """
    import jax
    from jax.experimental import pallas as pl
    from jax.sharding import NamedSharding, PartitionSpec

    def run(x, mesh, kernel):
        x = jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, PartitionSpec()))
        return pl.pallas_call(kernel, out_shape=x)(x)
"""

_PALLAS_SPMD_SPLIT_GOOD = """
    from jax.experimental import pallas as pl

    def run(x, kernel):
        # sharding machinery lives in its own module (serving/tp.py);
        # this module only owns the kernel entry
        return pl.pallas_call(kernel, out_shape=x)(x)
"""


class TestPallasHazards:
    def test_program_id_in_fori_loop_body_flags(self):
        fs = lint(_PALLAS_LOOP_BAD, "paddle_tpu/ops/pallas/k.py",
                  "pallas-hazards")
        assert len(fs) == 1 and "program_id" in fs[0].message

    def test_program_id_hoisted_passes(self):
        assert lint(_PALLAS_LOOP_GOOD, "paddle_tpu/ops/pallas/k.py",
                    "pallas-hazards") == []

    def test_pltpu_prng_flags(self):
        fs = lint(_PALLAS_PRNG_BAD, "paddle_tpu/ops/pallas/k.py",
                  "pallas-hazards")
        assert len(fs) == 2
        assert all("interpret" in f.message for f in fs)

    def test_seq_scaled_blockspec_flags(self):
        fs = lint(_PALLAS_BLOCKSPEC_BAD, "paddle_tpu/ops/pallas/k.py",
                  "pallas-hazards")
        assert len(fs) == 1 and "VMEM" in fs[0].message

    def test_block_sized_blockspec_passes(self):
        assert lint(_PALLAS_BLOCKSPEC_GOOD,
                    "paddle_tpu/ops/pallas/k.py",
                    "pallas-hazards") == []

    def test_token_scaled_blockspec_flags(self):
        fs = lint(_PALLAS_BLOCKSPEC_TOK_BAD,
                  "paddle_tpu/serving/attention.py", "pallas-hazards")
        assert len(fs) == 1 and "VMEM" in fs[0].message

    def test_token_cell_blockspec_passes(self):
        assert lint(_PALLAS_BLOCKSPEC_TOK_GOOD,
                    "paddle_tpu/serving/attention.py",
                    "pallas-hazards") == []

    def test_pallas_mixed_with_sharding_flags(self):
        fs = lint(_PALLAS_SPMD_MIX_BAD,
                  "paddle_tpu/serving/attention.py", "pallas-hazards")
        assert len(fs) == 1 and "GSPMD" in fs[0].message

    def test_pallas_without_sharding_passes(self):
        assert lint(_PALLAS_SPMD_SPLIT_GOOD,
                    "paddle_tpu/serving/attention.py",
                    "pallas-hazards") == []


# ---------------------------------------------------------------------------
# 4. jit-constant-capture

_JIT_METHOD_BAD = """
    import jax

    class Model:
        @jax.jit
        def step(self, x):
            return x * self.scale
"""

_JIT_CLOSURE_SELF_BAD = """
    import jax

    class Model:
        def compile(self):
            def fn(x):
                return x @ self.weight
            return jax.jit(fn)
"""

_JIT_CLOSURE_PARAMS_BAD = """
    import jax

    def build(layer):
        params = layer.parameters()
        def fn(x):
            return x + params[0]
        return jax.jit(fn)
"""

_JIT_GOOD = """
    import jax

    def build():
        def fn(params, x):   # weights are ARGUMENTS
            return x + params[0]
        return jax.jit(fn)
"""


class TestJitConstantCapture:
    def test_jit_on_method_flags(self):
        fs = lint(_JIT_METHOD_BAD, "paddle_tpu/models/m.py",
                  "jit-constant-capture")
        assert len(fs) == 1 and "self" in fs[0].message

    def test_closure_over_self_flags(self):
        fs = lint(_JIT_CLOSURE_SELF_BAD, "paddle_tpu/models/m.py",
                  "jit-constant-capture")
        assert len(fs) == 1 and "self.weight" in fs[0].message

    def test_closure_over_params_flags(self):
        fs = lint(_JIT_CLOSURE_PARAMS_BAD, "paddle_tpu/models/m.py",
                  "jit-constant-capture")
        assert len(fs) == 1 and "`params`" in fs[0].message

    def test_weights_as_arguments_pass(self):
        assert lint(_JIT_GOOD, "paddle_tpu/models/m.py",
                    "jit-constant-capture") == []

    def test_out_of_scope_paths_skipped(self):
        # the rule is scoped to paddle_tpu/ — test helpers jit freely
        assert lint(_JIT_METHOD_BAD, "tests/helper.py",
                    "jit-constant-capture") == []


# ---------------------------------------------------------------------------
# 5. dist-spec-passthrough — the round-3 verbatim return must flag

_DIST_BAD_ATTR = """
    from jax.sharding import PartitionSpec as P

    def param_spec(param, shape, degree):
        return P(*param.dist_spec)
"""

_DIST_BAD_PARAM = """
    def my_spec(dist_spec, shape):
        return dist_spec
"""

_DIST_GOOD = """
    from jax.sharding import PartitionSpec as P

    def param_spec(param, shape, degree):
        spec = P(*param.dist_spec)
        composed = _add_sharding(spec, shape, degree)
        if composed is not None:
            return composed
        return spec
"""


class TestDistSpecPassthrough:
    def test_verbatim_attr_return_flags(self):
        fs = lint(_DIST_BAD_ATTR, "paddle_tpu/distributed/foo.py",
                  "dist-spec-passthrough")
        assert len(fs) == 1 and "replicate" in fs[0].message

    def test_verbatim_param_return_flags(self):
        fs = lint(_DIST_BAD_PARAM, "paddle_tpu/distributed/foo.py",
                  "dist-spec-passthrough")
        assert len(fs) == 1

    def test_composed_spec_passes(self):
        assert lint(_DIST_GOOD, "paddle_tpu/distributed/foo.py",
                    "dist-spec-passthrough") == []


# ---------------------------------------------------------------------------
# 7. engine-lock-discipline

_LOCK_BAD = """
    class Policy:
        def act(self, rid):
            self.engine.cancel(rid)
            self.engine.step()
"""

_LOCK_GOOD = """
    class Policy:
        def act(self, rid):
            self.frontend.cancel(rid)
"""


class TestEngineLockDiscipline:
    def test_direct_engine_calls_flag(self):
        fs = lint(_LOCK_BAD, "paddle_tpu/serving/newpolicy.py",
                  "engine-lock-discipline")
        assert len(fs) == 2
        assert all("ServingFrontend" in f.message for f in fs)

    def test_frontend_calls_pass(self):
        assert lint(_LOCK_GOOD, "paddle_tpu/serving/newpolicy.py",
                    "engine-lock-discipline") == []

    def test_frontend_file_exempt(self):
        assert lint(_LOCK_BAD, "paddle_tpu/serving/frontend.py",
                    "engine-lock-discipline") == []


# ---------------------------------------------------------------------------
# 7b. page-migration-lock (round 14)

_MIGRATE_BAD = """
    class Mover:
        def steal(self, payload, prompt):
            # racing the step loop: scatter into buffers mid-step
            meta, k, v = self.engine.cache.export_pages("seq")
            self.engine.cache.import_pages("dst", meta, k, v)
            rid = self.engine.adopt_request(meta, k, v,
                                            max_new_tokens=8)
"""

_MIGRATE_GOOD = """
    class Mover:
        def move(self, src, dst, stream, prompt):
            # replica/frontend wrappers hold the engine lock
            have = dst.probe_pages(prompt)
            meta, k, v = src.export_pages(stream, have)
            inner = dst.adopt(meta, k, v, max_new_tokens=8)
            src.release_pages(stream)
"""

# round 18: the fleet prefix-transfer family rides the same rule —
# prefix export/import/drop touch the same device buffers + radix tree
_PREFIX_BAD = """
    class Shipper:
        def ship(self, prompt):
            meta, k, v = self.engine.cache.export_prefix_pages(prompt)
            self.engine.cache.import_prefix_pages(meta, k, v)
            self.engine.drop_prefix(prompt)
"""

_PREFIX_GOOD = """
    class Shipper:
        def ship(self, donor, target, prompt, skip):
            meta, k, v = donor.export_prefix(prompt, skip)
            target.import_prefix(meta, k, v)
            donor.drop_prefix(prompt)
"""


class TestPageMigrationLock:
    def test_direct_cache_engine_migration_flags(self):
        fs = lint(_MIGRATE_BAD, "paddle_tpu/serving/newmover.py",
                  "page-migration-lock")
        assert len(fs) == 3
        assert all("front-end lock" in f.message for f in fs)

    def test_replica_wrappers_pass(self):
        # the disagg router's own shape: replica-level calls only
        assert lint(_MIGRATE_GOOD, "paddle_tpu/serving/newmover.py",
                    "page-migration-lock") == []

    def test_direct_prefix_transfer_flags(self):
        fs = lint(_PREFIX_BAD, "paddle_tpu/serving/newship.py",
                  "page-migration-lock")
        assert len(fs) == 3
        assert all("front-end lock" in f.message for f in fs)

    def test_prefix_replica_wrappers_pass(self):
        # the round-18 router's own shape: replica-level calls only
        assert lint(_PREFIX_GOOD, "paddle_tpu/serving/newship.py",
                    "page-migration-lock") == []

    def test_allocator_engine_frontend_exempt(self):
        for path in ("paddle_tpu/serving/kv_cache.py",
                     "paddle_tpu/serving/engine.py",
                     "paddle_tpu/serving/frontend.py"):
            assert lint(_MIGRATE_BAD, path,
                        "page-migration-lock") == []


# ---------------------------------------------------------------------------
# 7c. serving-raw-sleep (round 17, chaos layer)

_SLEEP_BAD = """
    import time

    class Loop:
        def run(self, engine):
            while True:
                engine_step_somehow()
                time.sleep(0.001)   # nondeterministic under chaos
"""

_SLEEP_GOOD = """
    class Loop:
        def run(self, engine):
            while True:
                engine_step_somehow()
                engine.chaos.sleep(0.001)   # injected sleeper
"""

_SLEEP_SUPPRESSED = """
    import time

    class Loop:
        def run(self):
            time.sleep(1)  # graftlint: disable=serving-raw-sleep (operator CLI wait, not a loop path)
"""


class TestServingRawSleep:
    def test_raw_sleep_in_serving_flags(self):
        fs = lint(_SLEEP_BAD, "paddle_tpu/serving/newloop.py",
                  "serving-raw-sleep")
        assert len(fs) == 1
        assert "chaos sleeper" in fs[0].message

    def test_injected_sleeper_passes(self):
        assert lint(_SLEEP_GOOD, "paddle_tpu/serving/newloop.py",
                    "serving-raw-sleep") == []

    def test_chaos_module_and_outside_serving_exempt(self):
        assert lint(_SLEEP_BAD, "paddle_tpu/serving/chaos.py",
                    "serving-raw-sleep") == []
        assert lint(_SLEEP_BAD, "paddle_tpu/hapi/model.py",
                    "serving-raw-sleep") == []

    def test_reasoned_suppression_holds(self):
        assert lint(_SLEEP_SUPPRESSED, "paddle_tpu/serving/newloop.py",
                    "serving-raw-sleep") == []


# ---------------------------------------------------------------------------
# 7d. fleet-process-spawn (round 19)

_SPAWN_BAD_SERVING = """
    import subprocess

    def grow(cmd):
        # serving library code forking on its own: no readiness
        # deadline, no restart budget, nothing reaps it
        return subprocess.Popen(cmd)
"""

_SPAWN_BAD_TOOL = """
    import subprocess, sys

    def spawn_replica(spec):
        # the original bug shape: a hand-rolled replica server spawn
        return subprocess.Popen(
            [sys.executable, "-m", "paddle_tpu.serving.fleet_worker",
             "--spec", spec])
"""

_SPAWN_GOOD_TOOL = """
    from paddle_tpu.serving import ProcessReplicaBackend, ReplicaSpec

    def spawn_replica(role):
        backend = ProcessReplicaBackend(ReplicaSpec())
        return backend.provision(role)
"""

_SPAWN_UNRELATED_TOOL = """
    import subprocess, sys

    def run_bench():
        # subprocess use that is NOT a replica server spawn passes
        return subprocess.Popen([sys.executable, "bench_serving.py"])
"""


class TestFleetProcessSpawn:
    def test_subprocess_in_serving_flags(self):
        fs = lint(_SPAWN_BAD_SERVING, "paddle_tpu/serving/newgrow.py",
                  "fleet-process-spawn")
        assert len(fs) == 1
        assert "ProcessReplicaBackend" in fs[0].message

    def test_worker_spawn_in_tools_flags(self):
        fs = lint(_SPAWN_BAD_TOOL, "tools/new_harness.py",
                  "fleet-process-spawn")
        assert len(fs) == 1

    def test_backend_route_passes(self):
        assert lint(_SPAWN_GOOD_TOOL, "tools/new_harness.py",
                    "fleet-process-spawn") == []

    def test_unrelated_subprocess_in_tools_passes(self):
        assert lint(_SPAWN_UNRELATED_TOOL, "tools/new_harness.py",
                    "fleet-process-spawn") == []

    def test_backend_home_exempt(self):
        assert lint(_SPAWN_BAD_TOOL, "paddle_tpu/serving/fleet.py",
                    "fleet-process-spawn") == []


# ---------------------------------------------------------------------------
# 7e. kvtier-blessed-access (round 20)

_KVTIER_BAD_PUT = """
    def stash(pool, key, payload):
        # raw payload movement: no geometry meta, no CRC disposal path
        pool.put(key, payload)
        return pool.get(key)
"""

_KVTIER_BAD_INTERNALS = """
    def peek(engine):
        # reaching into the LRU dict skirts the byte accounting the
        # cross-tier conservation check audits
        return list(engine.kvtier.pool._entries)
"""

_KVTIER_GOOD_BLESSED = """
    def occupancy(pool, tier, cache, prompt):
        tier.flush()
        n = tier.restore(cache, prompt)
        return n, pool.stats(), pool.snapshot(), pool.contains(b"k")
"""

_KVTIER_GOOD_UNRELATED = """
    def lookup(cfg, registry):
        # dict-style get/pop on non-pool receivers passes
        registry.pop("stale")
        return cfg.get("key")
"""


class TestKvtierBlessedAccess:
    def test_raw_put_get_flags(self):
        fs = lint(_KVTIER_BAD_PUT, "paddle_tpu/serving/newrouter.py",
                  "kvtier-blessed-access")
        assert len(fs) == 2
        assert "KVTier.spill/restore" in fs[0].message

    def test_pool_internals_flags(self):
        fs = lint(_KVTIER_BAD_INTERNALS, "tools/new_probe.py",
                  "kvtier-blessed-access")
        assert len(fs) == 1
        assert "conservation" in fs[0].message

    def test_blessed_surface_passes(self):
        assert lint(_KVTIER_GOOD_BLESSED,
                    "paddle_tpu/serving/newrouter.py",
                    "kvtier-blessed-access") == []

    def test_non_pool_receivers_pass(self):
        assert lint(_KVTIER_GOOD_UNRELATED,
                    "paddle_tpu/serving/newrouter.py",
                    "kvtier-blessed-access") == []

    def test_tier_home_exempt(self):
        assert lint(_KVTIER_BAD_PUT, "paddle_tpu/serving/kvtier.py",
                    "kvtier-blessed-access") == []


# ---------------------------------------------------------------------------
# 7f. weight-swap-lock (round 21)

_SWAP_BAD_RAW_WRITE = """
    def hot_patch(engine, arrays):
        # the original bug shape: swapping the argument pytree off the
        # front-end lock races the step's argument gather, and skips
        # validation / prefix flush / the version bump
        for t, a in zip(engine.model._gen_state_tensors(), arrays):
            t._data = a
"""

_SWAP_BAD_DIRECT_SET = """
    def rollout_one(engine, arrays, version):
        engine.set_weights("target", arrays, version)
"""

_SWAP_GOOD_FRONTEND = """
    def rollout_one(frontend, replica, arrays, version):
        # the blessed chain: replica/front-end wrappers take the lock
        frontend.swap_weights("target", arrays, version)
        replica.swap_weights("draft", arrays, version)
"""

_SWAP_GOOD_READ = """
    import numpy as np

    def snapshot(model):
        # READS of the pytree are fine — only writes are the hazard
        return [np.asarray(t._data) for t in model._gen_state_tensors()]
"""


class TestWeightSwapLock:
    def test_raw_data_write_flags(self):
        fs = lint(_SWAP_BAD_RAW_WRITE, "paddle_tpu/serving/newdep.py",
                  "weight-swap-lock")
        assert len(fs) == 1
        assert "set_weights" in fs[0].message

    def test_direct_set_weights_flags(self):
        fs = lint(_SWAP_BAD_DIRECT_SET, "paddle_tpu/serving/newdep.py",
                  "weight-swap-lock")
        assert len(fs) == 1
        assert "front-end" in fs[0].message or "lock" in fs[0].message

    def test_wrapper_calls_pass(self):
        assert lint(_SWAP_GOOD_FRONTEND,
                    "paddle_tpu/serving/newdep.py",
                    "weight-swap-lock") == []

    def test_reads_pass(self):
        assert lint(_SWAP_GOOD_READ, "paddle_tpu/serving/newdep.py",
                    "weight-swap-lock") == []

    def test_engine_home_exempt(self):
        assert lint(_SWAP_BAD_RAW_WRITE, "paddle_tpu/serving/engine.py",
                    "weight-swap-lock") == []

    def test_frontend_may_call_set_weights(self):
        assert lint(_SWAP_BAD_DIRECT_SET,
                    "paddle_tpu/serving/frontend.py",
                    "weight-swap-lock") == []

    def test_outside_serving_out_of_scope(self):
        assert lint(_SWAP_BAD_RAW_WRITE, "paddle_tpu/optimizer.py",
                    "weight-swap-lock") == []


# ---------------------------------------------------------------------------
# 8. env-knob-registry

class TestEnvKnobRegistry:
    def test_unregistered_knob_flags(self):
        knob = "PADDLE_TPU_" + "NOT_A_REAL_KNOB_XYZ"
        src = f"""
            import os
            v = os.environ.get({knob!r})
        """
        fs = lint(src, "paddle_tpu/newmod.py", "env-knob-registry")
        assert len(fs) == 1 and "ENV_KNOBS.md" in fs[0].message

    def test_registered_knob_passes(self):
        src = """
            import os
            v = os.environ.get("PADDLE_TPU_PAGED_KERNEL")
        """
        assert lint(src, "paddle_tpu/newmod.py",
                    "env-knob-registry") == []

    def test_registry_parses_nonempty(self):
        reg = _PROJECT.knob_registry()
        assert "PADDLE_TPU_PAGED_KERNEL" in reg
        assert len(reg) > 25

    def test_registry_in_sync_with_tree(self):
        """Satellite: regenerating the registry (descriptions
        preserved) must reproduce docs/ENV_KNOBS.md byte-exactly."""
        ok, msg = knobs.check_sync(ROOT)
        assert ok, msg


# ---------------------------------------------------------------------------
# suppression mechanics

class TestSuppressions:
    def test_disable_with_reason_suppresses(self):
        src = _PALLAS_PRNG_BAD.replace(
            "pltpu.prng_seed(seed_ref[0])",
            "pltpu.prng_seed(seed_ref[0])  "
            "# graftlint: disable=pallas-hazards (fixture reason)")
        fs = lint(src, "paddle_tpu/ops/pallas/k.py", "pallas-hazards")
        assert len(fs) == 1  # prng_random_bits still flagged

    def test_standalone_comment_covers_next_line(self):
        src = _PALLAS_PRNG_BAD.replace(
            "pltpu.prng_seed(seed_ref[0])",
            "# graftlint: disable=pallas-hazards (fixture reason)\n"
            "        pltpu.prng_seed(seed_ref[0])")
        fs = lint(src, "paddle_tpu/ops/pallas/k.py", "pallas-hazards")
        assert len(fs) == 1

    def test_empty_reason_is_a_finding(self):
        src = _PALLAS_PRNG_BAD.replace(
            "pltpu.prng_seed(seed_ref[0])",
            "pltpu.prng_seed(seed_ref[0])  "
            "# graftlint: disable=pallas-hazards")
        fs = lint(src, "paddle_tpu/ops/pallas/k.py", "pallas-hazards")
        assert BAD_SUPPRESSION in rule_ids(fs)

    def test_unknown_rule_id_is_a_finding(self):
        src = """
            x = 1  # graftlint: disable=no-such-rule (typo fixture)
        """
        fs = lint(src, "paddle_tpu/newmod.py")
        assert rule_ids(fs) == {BAD_SUPPRESSION}
        assert "unknown rule" in fs[0].message

    def test_disable_file_suppresses_whole_file(self):
        src = ('"""Doc."""\n'
               "# graftlint: disable-file=pallas-hazards (fixture "
               "reason)\n" + textwrap.dedent(_PALLAS_PRNG_BAD))
        fs = run_source(src, "paddle_tpu/ops/pallas/k.py",
                        [RULES_BY_ID["pallas-hazards"]],
                        project=_PROJECT)
        assert fs == []


# ---------------------------------------------------------------------------
# baseline mechanics

class TestBaseline:
    def test_roundtrip_and_matching(self, tmp_path):
        fs = lint(_DIST_BAD_PARAM, "paddle_tpu/distributed/foo.py",
                  "dist-spec-passthrough")
        assert len(fs) == 1
        bpath = str(tmp_path / "baseline.json")
        save_baseline(bpath, fs, "pre-existing debt (fixture)")
        baseline, bad = load_baseline(bpath)
        assert bad == []
        new, old = apply_baseline(fs, baseline)
        assert new == [] and len(old) == 1

    def test_entry_without_reason_is_a_finding(self, tmp_path):
        bpath = tmp_path / "baseline.json"
        bpath.write_text(json.dumps({"entries": [
            {"rule": "pallas-hazards", "path": "x.py",
             "snippet": "y", "reason": ""}]}))
        baseline, bad = load_baseline(str(bpath))
        assert baseline == {}
        assert len(bad) == 1 and bad[0].rule == BAD_BASELINE

    def test_checked_in_baseline_entries_valid(self):
        """Acceptance: every baseline entry carries a rule id and a
        non-empty reason (empty baseline trivially satisfies)."""
        _, bad = load_baseline(
            os.path.join(ROOT, "tools", "graftlint_baseline.json"))
        assert bad == []


# ---------------------------------------------------------------------------
# whole-tree self-check + CLI

class TestWholeTree:
    def test_repo_clean_modulo_baseline(self):
        """The tools/lint.sh gate as a test: the repo at HEAD has no
        new findings over paddle_tpu + tools + tests."""
        findings, stats = run_paths(["paddle_tpu", "tools", "tests"],
                                    ROOT, ALL_RULES)
        baseline, bad = load_baseline(
            os.path.join(ROOT, "tools", "graftlint_baseline.json"))
        findings.extend(bad)
        new, _old = apply_baseline(findings, baseline)
        assert new == [], "new graftlint findings:\n" + "\n".join(
            str(f) for f in new)
        assert stats["files"] > 250

    def test_cli_json_smoke(self):
        """tools/lint.py end-to-end (stub-parent import path — must
        work in a fresh interpreter WITHOUT importing jax)."""
        p = subprocess.run(
            [sys.executable, os.path.join("tools", "lint.py"),
             "--json", "paddle_tpu/analysis"],
            cwd=ROOT, capture_output=True, text=True, timeout=120)
        assert p.returncode == 0, p.stderr[-2000:]
        out = json.loads(p.stdout)
        assert out["findings"] == []
        assert out["stats"]["files"] >= 10
