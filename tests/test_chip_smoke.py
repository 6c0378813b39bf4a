"""chip_smoke.py and the pieces it stands on: the rehearsal drives the
whole script on the CPU; without --rehearse (or a TPU) nothing runs and
nothing is printed that could be read as a result; the compile cache can
be placed from outside; peaks come from one table that refuses a device
it does not know; a bench script never falls back to the CPU."""
import json
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SMOKE = os.path.join(_REPO, "chip_smoke.py")


def _run(*args):
    return subprocess.run([sys.executable, *args], cwd=_REPO,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=280)


def _json_lines(text):
    return [json.loads(ln) for ln in text.splitlines()
            if ln.startswith("{")]


class TestChipSmoke:
    def test_rehearsal_runs_both_phases_on_cpu(self):
        p = _run(_SMOKE, "--rehearse")
        assert p.returncode == 0, p.stderr[-3000:]
        lines = _json_lines(p.stdout)
        assert p.stdout.strip().splitlines()[-1] == json.dumps(lines[-1])
        assert lines[-1] == {"ok": True, "device": {
            "platform": "cpu", "kind": "cpu", "count": 1}}
        phases = {d["phase"]: d for d in lines if "phase" in d}
        assert set(phases) == {"train", "serve"}
        train, serve = phases["train"], phases["serve"]
        assert train["flash_dispatch"]["fallback"] == 0
        assert train["flash_dispatch"]["pallas"] > 0
        assert train["losses"][-1] < train["losses"][0]
        assert serve["streams_exact_vs_generate"] == serve["requests"] >= 8
        assert serve["step_programs_compiled"] <= 2
        assert max(serve["prompt_lens"]) > serve["prefill_chunk"]

    def test_without_rehearse_a_cpu_host_is_refused(self):
        p = _run(_SMOKE)
        assert p.returncode != 0
        assert "needs a TPU" in p.stderr
        assert _json_lines(p.stdout) == []      # no result of any kind


class TestCompileCache:
    """paddle_tpu.utils.compile_cache: the directory is part of the
    cache key, so it is either the one JAX reads from the environment or
    one fixed path inside the checkout."""

    def test_env_set_means_no_directory_set_in_code(self, monkeypatch):
        import jax

        from paddle_tpu.utils import compile_cache
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        assert compile_cache.enable_compile_cache() == "/somewhere/else"
        assert calls == []

    def test_env_unset_means_checkout_dot_jax_cache(self, monkeypatch):
        import jax

        from paddle_tpu.utils import compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        calls = []
        monkeypatch.setattr(jax.config, "update",
                            lambda *a: calls.append(a))
        want = os.path.join(_REPO, ".jax_cache")
        assert compile_cache.enable_compile_cache() == want
        assert calls == [("jax_compilation_cache_dir", want)]

    def test_cache_dir_is_git_ignored(self):
        with open(os.path.join(_REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()


class TestChipSpecs:
    def test_knows_the_v5e_by_the_name_jax_reports(self):
        from paddle_tpu.utils.chip_specs import chip_spec
        v5e = chip_spec("TPU v5 lite")
        assert (v5e.bf16_flops, v5e.hbm_bytes_per_s) == (197e12, 819e9)

    @pytest.mark.parametrize("kind", ["cpu", "TPU v5e", "v5litepod",
                                      "TPU v9", ""])
    def test_unknown_device_kind_is_an_error(self, kind):
        from paddle_tpu.utils.chip_specs import chip_spec
        with pytest.raises(ValueError, match="unknown device_kind"):
            chip_spec(kind)


class TestNoCpuFallback:
    def test_require_tpu_exits_non_zero_on_cpu(self, capsys):
        sys.path.insert(0, _REPO)
        try:
            import bench
        finally:
            sys.path.remove(_REPO)
        with pytest.raises(SystemExit) as e:
            bench.require_tpu()
        assert e.value.code not in (0, None)
        assert capsys.readouterr().out == ""    # no metric printed

    @pytest.mark.parametrize("script", [
        "bench.py", "bench_serving.py", "bench_generate.py",
        "bench_extra.py", "bench_longseq.py", "tools/bench_dispatch.py"])
    def test_bench_script_gates_on_the_tpu(self, script):
        with open(os.path.join(_REPO, script)) as f:
            src = f.read()
        assert "require_tpu()" in src
        assert "sys.exit(0)" not in src

    def test_no_backend_cache_clearing_left_in_the_tree(self):
        hits = []
        for root, dirs, files in os.walk(_REPO):
            dirs[:] = [d for d in dirs if not d.startswith(".")
                       and d != "chiprun_out"]
            for name in files:
                if not name.endswith((".py", ".sh", ".md")):
                    continue
                path = os.path.join(root, name)
                if path == os.path.abspath(__file__) \
                        or name == "ISSUE.md":
                    continue
                with open(path, errors="replace") as f:
                    if "_clear_backends" in f.read():
                        hits.append(os.path.relpath(path, _REPO))
        assert hits == []

    def test_device_index_out_of_range_is_an_error(self):
        import jax

        from paddle_tpu.core.device import _jax_device_for
        n = len(jax.local_devices(backend="cpu"))
        assert _jax_device_for("cpu", n - 1) is not None
        with pytest.raises(RuntimeError, match="out of range"):
            _jax_device_for("cpu", n)


class TestKernelPerShardUnderAMesh:
    """What the fleet steppers rely on (flash_attention._kernel_plan):
    traced under mesh_env, the kernel runs per shard inside shard_map
    and computes what the unsharded reference computes; a shape the mesh
    does not divide takes the counted fallback, never a silent one."""

    @pytest.fixture
    def setup(self, monkeypatch):
        import jax
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        from paddle_tpu.ops.pallas import flash_attention as fa
        monkeypatch.setattr(fa, "_FORCE_INTERPRET", True)
        fa.reset_dispatch_stats()
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(1, 1, 2, 1, 2),
                    ("dp", "pp", "sharding", "sep", "mp"))
        sh = NamedSharding(mesh, P(("dp", "sharding"), None, "mp", None))

        def grads(fn, *qkv):
            return jax.grad(lambda *a: (fn(*a) ** 2).sum(),
                            argnums=(0, 1, 2))(*qkv)
        return fa, mesh, sh, grads

    def _qkv(self, b):
        import jax.numpy as jnp
        import numpy as np
        rng = np.random.default_rng(0)
        return [jnp.asarray(rng.standard_normal((b, 128, 4, 64)),
                            jnp.float32) for _ in range(3)]

    def test_sharded_kernel_matches_the_reference(self, setup):
        import jax
        import numpy as np

        from paddle_tpu.distributed._axis import mesh_env
        fa, mesh, sh, grads = setup
        qkv = self._qkv(4)
        want = grads(lambda q, k, v: fa._attention_ref(
            q, k, v, causal=True), *qkv)
        with mesh_env(mesh):
            got = jax.jit(lambda *a: grads(
                lambda q, k, v: fa._flash_core_ext(
                    q, k, v, None, None, None, True, None), *a))(
                *[jax.device_put(a, sh) for a in qkv])
        stats = fa.dispatch_stats()
        assert (stats["pallas"], stats["fallback"]) == (1, 0), stats
        for g, w in zip(got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=2e-4)

    def test_undivided_batch_is_a_counted_fallback(self, setup):
        import jax
        import numpy as np

        from paddle_tpu.distributed._axis import mesh_env
        fa, mesh, _, _ = setup
        q, k, v = self._qkv(3)           # 3 rows over sharding=2
        with mesh_env(mesh), pytest.warns(UserWarning,
                                          match="not divisible"):
            out = jax.jit(lambda q, k, v: fa._flash_core_ext(
                q, k, v, None, None, None, True, None))(q, k, v)
        stats = fa.dispatch_stats()
        assert (stats["pallas"], stats["fallback"]) == (0, 1), stats
        np.testing.assert_allclose(
            np.asarray(out),
            np.asarray(fa._attention_ref(q, k, v, causal=True)),
            atol=2e-5)
