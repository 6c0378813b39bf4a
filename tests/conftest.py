"""Test configuration: force an 8-device virtual CPU mesh (SURVEY.md §4 —
the single-host multi-device trick for distributed tests).

The suite never runs on the chip: the platform is pinned to cpu here,
before any test touches a device (tier-1 also sets JAX_PLATFORMS=cpu;
the config update makes a bare `pytest` do the same). XLA_FLAGS is read
at the (lazy) backend init, so setting it here works. The chip is
reached only by `python chip_smoke.py` through the builder's chip tool.
"""
import glob
import importlib.util
import os
import shutil
import signal
import tempfile

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Many tests build the same small engines and models, and each build
# compiles its programs anew (a jit a model instance). A persistent
# compile cache a test process turns the repeats into reads. Where
# JAX_COMPILATION_CACHE_DIR is set JAX reads it itself; otherwise the
# cache is a fresh directory of this process alone (xdist's workers are
# separate processes, and an entry is written non-atomically, so a
# shared directory could hand a reader half a file), removed when the
# process's session ends, and keeping every compile: the repeats are
# small programs too.
_CACHE_DIR = None
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _CACHE_DIR = tempfile.mkdtemp(prefix="jax-test-cache-")
    jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
_CACHE_OWNER = os.getpid()


def pytest_unconfigure(config):
    if _CACHE_DIR is not None and os.getpid() == _CACHE_OWNER:
        shutil.rmtree(_CACHE_DIR, ignore_errors=True)


# Oracle deps the transplant-parity suites importorskip on. Under a
# certification run their absence must FAIL, not silently skip
# (ADVICE.md #3): docs claim oracle parity at HEAD, and a skip-degraded
# run would certify nothing.
_ORACLE_DEPS = ("torch", "transformers")


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long end-to-end replays excluded from the tier-1 run "
        "(ROADMAP.md tier-1 verify uses -m 'not slow')")
    config.addinivalue_line(
        "markers",
        "certification: evidence-bearing oracle-parity suites; under "
        "PADDLE_TPU_CERT_RUN=1 their dependencies are mandatory")
    if os.environ.get("PADDLE_TPU_CERT_RUN") == "1":
        missing = [m for m in _ORACLE_DEPS
                   if importlib.util.find_spec(m) is None]
        if missing:
            raise pytest.UsageError(
                "PADDLE_TPU_CERT_RUN=1 but oracle dependencies are "
                f"missing: {', '.join(missing)}. The transplant-parity "
                "suites would silently degrade to skips — aborting the "
                "certification run instead.")


_REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

# One time limit a test (set-up and tear-down included), so that a hang
# costs one test and not the run's tail. Every blocking call in tests/
# (subprocess, join, wait, get, result) has a shorter deadline of its own.
_TEST_LIMIT_S = 300


@pytest.fixture(autouse=True)
def _per_test_time_limit(request):
    """SIGALRM on the main thread, where pytest and xdist's workers run
    the tests. The `slow` replays (not in tier-1) are long by definition
    and keep only their subprocess deadlines."""
    if request.node.get_closest_marker("slow"):
        yield
        return
    limit = _TEST_LIMIT_S

    def on_alarm(signum, frame):
        pytest.fail(f"{request.node.nodeid} ran over the per-test limit "
                    f"of {limit} s", pytrace=False)

    old_handler = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, old_handler)


@pytest.fixture(autouse=True)
def _bench_artifact_guard(request):
    """Round-12 hazard fix (ISSUE 6 satellite): the slow
    TestServingReplay tests run bench_serving.py in a SUBPROCESS, which
    OVERWRITES the banked BENCH_serving*.json artifacts with numbers
    measured under suite load (http marginal collapsed 30.9→20.0 in one
    round-12 run).  Snapshot the artifacts around those tests and
    restore them afterwards, deleting any the subprocess created anew —
    re-banking a bench number must be a deliberate quiet-VM act, never a
    suite side effect.  The guard keys on every replay-class name that
    shells out to bench_serving.py: round 14 added the disagg replay
    (subprocess writes BENCH_serving_disagg.json — covered by the same
    glob) AND closed a hole — the HTTP replay class is named
    `TestServerReplay`, which the original "TestServingReplay"
    substring never matched, so BENCH_serving_http.json was still
    being overwritten by in-suite runs (caught by the round-14 tier-1
    run: 30.9 -> 20.1 under suite load, the exact round-12 symptom).
    Round 21: the deploy replay (BENCH_serving_deploy.json via
    tools/deploy_harness.py --smoke) rides the same glob — which also
    keeps covering BENCH_serving_kvtier.json and any future
    BENCH_serving_*.json with zero new per-artifact code."""
    _replay_classes = ("TestServingReplay", "TestServerReplay",
                       "TestServingDisaggReplay", "TestServingKv8Replay",
                       "TestServingTraceReplay",
                       "TestServingPrefixFleetReplay",
                       "TestServingFleetReplay",
                       "TestServingKvtierReplay",
                       "TestServingDeployReplay")
    if not any(c in request.node.nodeid for c in _replay_classes):
        yield
        return
    pattern = os.path.join(_REPO_ROOT, "BENCH_serving*.json")
    snap = {}
    for p in glob.glob(pattern):
        with open(p, "rb") as f:
            snap[p] = f.read()
    try:
        yield
    finally:
        for p, data in snap.items():
            with open(p, "wb") as f:
                f.write(data)
        for p in glob.glob(pattern):
            if p not in snap:
                os.unlink(p)


@pytest.fixture(autouse=True, scope="module")
def _fleet_state_stays_in_its_file():
    """A file that ends with the fleet initialised (test_sequence_parallel,
    test_pipeline, ...) left `Model.fit` of whatever file the worker got
    next on the SPMD trainer, and its eager `evaluate` then failed on
    mesh-placed weights: which files share a worker differs a run."""
    yield
    import sys
    fleet = sys.modules.get("paddle_tpu.distributed.fleet.fleet")
    if fleet is not None and fleet._state.initialized:
        from paddle_tpu.distributed.fleet.topology import \
            set_hybrid_communicate_group
        fleet._state.initialized = False
        fleet._state.strategy = None
        fleet._state.hcg = None
        set_hybrid_communicate_group(None)


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Record skip counts in the suite summary (ADVICE.md #3): how many
    tests skipped, and how many of those were oracle-dependency skips —
    the number a certification log must show as 0."""
    skipped = terminalreporter.stats.get("skipped", [])
    oracle = sum(1 for rep in skipped
                 if any(dep in str(getattr(rep, "longrepr", ""))
                        for dep in _ORACLE_DEPS))
    terminalreporter.write_line(
        f"skip accounting: {len(skipped)} skipped "
        f"({oracle} oracle-dependency skips; cert runs require 0 — "
        "set PADDLE_TPU_CERT_RUN=1 to make missing oracles fatal)")
