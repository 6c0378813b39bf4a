"""The one-chip cells' step programs, compiled at their real shapes for a
described TPU v5e: they have to fit one chip's 16 GB, and the train loop
has to hold its flash kernels. Marked slow (a whole step program compiles
in tens of seconds): run by hand, its numbers are in PERF.md.

    JAX_PLATFORMS=cpu python -m pytest tests/benchmark/test_bench_aot.py -m slow -s
"""
import json
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow
ROOT = Path(__file__).resolve().parents[2]
HBM = 16e9


def _cfg(name):
    return json.loads((ROOT / "benchmark/configs" / name).read_text())


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from benchmark.harness import aot
    try:
        desc = aot.describe_v5e()
    except Exception as e:      # no libtpu, or it cannot describe a v5e
        pytest.skip(f"TPU topology cannot be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("name", ["deepseek-llm-7b.serve-L8.json",
                                  "mistral-7b-v0.1.serve-L8.json"])
def test_ragged_step_fits_one_chip(topo, name):
    from benchmark.harness import aot
    m = aot.serve_step(_cfg(name), topo, mixed=True)
    print(name, json.dumps(m))
    assert m["alias_size_in_bytes"] == 0       # the pool is not donated
    assert m["live_bytes"] < HBM
    assert m["kernels"] == 0                   # paged attention is a gather


def test_train_loop_fits_one_chip_and_holds_its_kernels(topo):
    from benchmark.harness import aot
    cfg = _cfg("mistral-7b-v0.1.train-L2.json")
    mix = json.loads((ROOT / "benchmark/traffic/s4096.json").read_text())
    m = aot.train_loop(cfg, mix, topo)
    print(json.dumps(m))
    assert m["alias_size_in_bytes"] > 0.99 * m["argument_size_in_bytes"]
    assert m["live_bytes"] < HBM
    assert m["kernels"] >= 1
