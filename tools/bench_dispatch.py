"""Eager-dispatch micro-benchmark on the TPU (SURVEY.md §7 hard-part 1:
per-op dispatch overhead).

Measures ms/step of an eager MLP fwd+bwd+SGD step (~20 op dispatches)
with the micro-jit dispatch cache ON vs OFF, plus the fully-jitted step
as the floor. The timed region ends fetching the final loss float.

Usage: python tools/bench_dispatch.py [iters]   # prints one JSON line
The script re-execs itself in subprocesses, one at a time (the flag is
read at import); the parent never touches jax, so each child gets the
chip. A child that finds no TPU exits non-zero and the run fails.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

if len(sys.argv) > 1 and sys.argv[1] == "--child":
    ITERS = int(sys.argv[2])
else:
    ITERS = int(sys.argv[1]) if len(sys.argv) > 1 else 30


def child(mode: str):
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import require_tpu
    require_tpu()
    import numpy as np
    import paddle_tpu as P

    P.seed(0)
    lin1 = P.nn.Linear(256, 256)
    lin2 = P.nn.Linear(256, 256)
    opt = P.optimizer.SGD(0.01, parameters=[*lin1.parameters(),
                                            *lin2.parameters()])
    x = P.to_tensor(np.random.default_rng(0).standard_normal(
        (32, 256)).astype(np.float32))

    def step():
        h = P.nn.functional.relu(lin1(x))
        loss = (lin2(h) * lin2(h)).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    if mode == "jit":
        import jax

        params = [p for p in lin1.parameters()] + \
            [p for p in lin2.parameters()]

        @jax.jit
        def jstep(arrs, xv):
            saved = [(p, p._data) for p in params]
            for p, a in zip(params, arrs):
                p._data = a
            try:
                h = P.nn.functional.relu(lin1(P.Tensor(xv)))
                loss = (lin2(h) * lin2(h)).mean()
                import jax.numpy as jnp
                return loss._data.astype(jnp.float32)
            finally:
                for p, a in saved:
                    p._data = a

        arrs = [p._data for p in params]
        float(np.asarray(jstep(arrs, x._data)))  # compile
        t0 = time.perf_counter()
        for i in range(ITERS):
            # vary the input so requests differ (no param update here);
            # i+1 so the first timed call also differs from the warmup
            v = jstep(arrs, x._data * (1.0 + 1e-6 * (i + 1)))
        out = float(np.asarray(v))
        dt = time.perf_counter() - t0
    else:
        for _ in range(3):
            loss = step()  # warmup: compile micro-jits / build caches
        float(loss.numpy())
        t0 = time.perf_counter()
        for _ in range(ITERS):
            loss = step()
        out = float(loss.numpy())
        dt = time.perf_counter() - t0
    print(json.dumps({"mode": mode, "ms_per_step": dt / ITERS * 1e3,
                      "loss": out}))


def main():
    here = os.path.abspath(__file__)
    results = {}
    for mode, env in (("microjit", {"PADDLE_TPU_EAGER_MICROJIT": "1"}),
                      ("plain", {"PADDLE_TPU_EAGER_MICROJIT": "0"}),
                      ("jit", {})):
        p = subprocess.run([sys.executable, here, "--child", str(ITERS),
                            mode], env=dict(os.environ, **env),
                           capture_output=True, text=True, timeout=900)
        line = [l for l in p.stdout.splitlines() if l.startswith("{")]
        if p.returncode != 0 or not line:
            sys.exit(f"{mode} failed (rc={p.returncode}): "
                     f"{p.stderr[-500:]}")
        results[mode] = json.loads(line[-1])
    print(json.dumps({
        "metric": "eager_dispatch_ms_per_step",
        "iters": ITERS,
        **{f"{k}_ms": round(v["ms_per_step"], 2)
           for k, v in results.items()},
    }))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(sys.argv[3])
    else:
        main()
