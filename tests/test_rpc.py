"""paddle.distributed.rpc tests — real multi-process RPC over sockets.

Mirrors the reference's single-host multi-process distributed test trick
(SURVEY.md §4): spawn worker subprocesses, rendezvous through the C++
TCPStore, and exercise rpc_sync / rpc_async / worker-info / shutdown.
"""
import os
import pickle
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
sys.path.insert(0, os.environ["REPO"])
# pick the CPU platform before any backend touch: workers stay off the TPU
import jax
jax.config.update("jax_platforms", "cpu")
from paddle_tpu.distributed import rpc

def add(a, b):
    return a + b

def whoami():
    return rpc.get_worker_info().name

rank = int(os.environ["RANK"])
rpc.init_rpc(f"worker{rank}", rank=rank, world_size=2,
             master_endpoint=os.environ["EP"])

if rank == 0:
    assert rpc.rpc_sync("worker1", add, args=(2, 3)) == 5
    fut = rpc.rpc_async("worker1", whoami)
    assert fut.result(timeout=60) == "worker1"
    infos = rpc.get_all_worker_infos()
    assert [w.name for w in infos] == ["worker0", "worker1"]
    # exceptions propagate
    try:
        rpc.rpc_sync("worker1", divmod, args=(1, 0))
        raise AssertionError("expected ZeroDivisionError")
    except ZeroDivisionError:
        pass
    print("RANK0_OK", flush=True)
else:
    # worker1 can also call back into worker0
    assert rpc.rpc_sync("worker0", add, args=(10, 20)) == 30
    print("RANK1_OK", flush=True)
rpc.shutdown()
"""


def test_rpc_two_process(tmp_path):
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    env_base = {**os.environ, "REPO": os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))),
        "EP": f"127.0.0.1:{port}", "JAX_PLATFORMS": "cpu"}
    procs = []
    for rank in range(2):
        procs.append(subprocess.Popen(
            [sys.executable, "-c", _WORKER],
            env={**env_base, "RANK": str(rank)},
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=180)
        outs.append(out.decode())
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank{rank} failed:\n{out}"
    assert "RANK0_OK" in outs[0]
    assert "RANK1_OK" in outs[1]
