"""Examples as load-bearing artifacts: run the light examples as real
subprocesses (fresh interpreters, the user's entry path). The heavy
walkthroughs (long_context_train, fleet_hybrid_train) are exercised by
their underlying test suites; here we keep the quick ones green so the
documentation-by-example cannot rot."""
import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_example(name, args=(), timeout=240, extra_env=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    p = subprocess.run(
        [sys.executable, os.path.join(_REPO, "examples", name), *args],
        capture_output=True, text=True, cwd=_REPO, env=env,
        timeout=timeout)
    assert p.returncode == 0, (p.stdout[-1500:], p.stderr[-1500:])
    return p.stdout


class TestExamples:
    def test_custom_cpp_op(self):
        import shutil
        if shutil.which("g++") is None:
            pytest.skip("no g++")
        out = _run_example("custom_cpp_op.py")
        assert "custom C++ op trains OK" in out

    def test_static_train(self):
        out = _run_example("static_train.py", args=("--cpu",))
        assert "loss" in out.lower() or out.strip()

    def test_fleet_hybrid_train(self):
        out = _run_example(
            "fleet_hybrid_train.py", args=("--cpu", "--steps", "3", "--quick"),
            timeout=280,
            extra_env={"XLA_FLAGS":
                       "--xla_force_host_platform_device_count=8"})
        assert "hybrid-parallel training parity OK" in out

    def test_train_clip_contrastive(self):
        out = _run_example("train_clip_contrastive.py", args=("--cpu",))
        assert "CLIP contrastive training OK" in out

    def test_train_clip_contrastive_mesh(self):
        out = _run_example("train_clip_contrastive.py",
                           args=("--cpu", "--mesh"), timeout=280)
        assert "global-batch(mesh dp=4)" in out
        assert "CLIP contrastive training OK" in out

    def test_asr_whisper(self):
        out = _run_example("asr_whisper.py", args=("--cpu", "--steps", "30"),
                           timeout=280)
        assert "ASR training OK" in out

    def test_ner_bigru_crf(self):
        out = _run_example("ner_bigru_crf.py", args=("--cpu", "--steps", "50"),
                           timeout=280)
        assert "NER training OK" in out
