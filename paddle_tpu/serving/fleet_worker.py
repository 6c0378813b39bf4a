"""Replica server process entry — what :class:`ProcessReplicaBackend`
spawns (``python -m paddle_tpu.serving.fleet_worker``).

One worker = one :class:`~paddle_tpu.serving.engine.ServingEngine`
behind one :class:`~paddle_tpu.serving.server.ServingServer` on an
ephemeral port.  The bound port is announced through an atomically
written ready file (tmp + rename, so the supervising backend never
reads a half-written announcement), then the worker serves until:

- SIGTERM/SIGINT — graceful: drain in-flight requests (bounded by the
  spec's ``drain_s``), then exit 0;
- its PARENT dies — the self-reap watchdog: a worker whose supervising
  process vanished (harness SIGKILLed, pytest timeout, operator ^C -9)
  notices ``os.getppid()`` changed and drains itself out, so fleet
  workers can never become stale-pytest-style orphans (CLAUDE.md
  round-4 addenda) no matter how the parent went away.

The device platform is forced to ``cpu`` by default BEFORE any jax
work: a chip belongs to one process at a time, so N workers on one
host cannot all take the default accelerator.  A deployment that gives
a worker its own chip passes ``platform: null`` in the spec (one such
worker per chip; the router parent stays off jax — docs/FLEET.md);
``/healthz`` reports the platform the worker ended up on.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading


def build_engine_from_spec(spec):
    """``spec`` (the :class:`~paddle_tpu.serving.fleet.ReplicaSpec`
    dict) → a ready ``ServingEngine``.  ``builder:
    "module:function"`` overrides the default tiny-Llama builder —
    the function receives the spec dict and returns the engine (real
    deployments load real weights there)."""
    builder = spec.get("builder")
    if builder:
        import importlib
        mod, _, fn = str(builder).partition(":")
        make = getattr(importlib.import_module(mod), fn)
        return make(spec)
    import paddle_tpu as P
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from .engine import ServingEngine
    model_kw = dict(spec.get("model") or {})
    seed = int(model_kw.pop("seed", 0))
    model_kw.setdefault("vocab_size", 97)
    model_kw.setdefault("hidden_size", 32)
    model_kw.setdefault("intermediate_size", 64)
    model_kw.setdefault("num_hidden_layers", 2)
    model_kw.setdefault("num_attention_heads", 4)
    model_kw.setdefault("max_position_embeddings", 64)
    P.seed(seed)
    model = LlamaForCausalLM(LlamaConfig(**model_kw))
    model.eval()
    engine_kw = dict(spec.get("engine") or {})
    engine_kw.setdefault("page_size", 4)
    engine_kw.setdefault("num_pages", 160)
    engine_kw.setdefault("max_batch", 8)
    engine_kw.setdefault("prefill_chunk", 8)
    return ServingEngine(model, **engine_kw)


def _write_ready(path, info):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(info, f)
    os.replace(tmp, path)  # atomic: the backend never reads a torn file


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--spec", required=True,
                    help="path to the ReplicaSpec JSON")
    ap.add_argument("--ready-file", required=True,
                    help="where to announce {port, pid} once serving")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--parent-pid", type=int, default=0,
                    help="self-reap when this process disappears")
    args = ap.parse_args(argv)

    with open(args.spec) as f:
        spec = json.load(f)

    engine_spec = dict(spec.get("engine") or {})
    tp = int(engine_spec.get("tp_degree") or 0)
    if tp > 1 and "--xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        # a tp_degree spec needs a multi-device mesh; on the CPU
        # platform that means the host-device-count flag, which XLA
        # reads at backend init — set it BEFORE the first jax touch
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={max(tp, 8)}")

    platform = spec.get("platform", "cpu")
    if platform:
        # a process picks its platform once, BEFORE the first jax
        # device touch
        import jax
        jax.config.update("jax_platforms", platform)
    if platform != "cpu":
        # a worker on an accelerator keeps its compiles across restarts
        # (XLA:CPU executables read back from a cache warn per load, so
        # the cpu default stays off it)
        from ..utils.compile_cache import enable_compile_cache
        enable_compile_cache()

    engine = build_engine_from_spec(spec)
    from .server import ServingServer
    srv = ServingServer(engine, host=args.host, port=0,
                        role=spec.get("role"),
                        max_queued=int(spec.get("max_queued", 64)))
    _, port = srv.start()
    _write_ready(args.ready_file, {"port": port, "pid": os.getpid()})

    stop = threading.Event()
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.set())

    if args.parent_pid:
        def watchdog():
            while not stop.wait(2.0):
                if os.getppid() != args.parent_pid:
                    stop.set()  # parent died: self-reap, never orphan
                    return
        threading.Thread(target=watchdog, name="fleet-parent-watchdog",
                         daemon=True).start()

    stop.wait()
    srv.close(timeout=float(spec.get("drain_s", 10.0)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
