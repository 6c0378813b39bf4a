"""True multi-process execution proof (VERDICT r1 item 6): the launch CLI
spawns 2 OS processes, jax.distributed connects them (Gloo over CPU), the
eager collectives move real data between controllers, DataParallel grad
sync gives loss parity with the single-process oracle, and the elastic
path survives a worker crash + restart (SURVEY.md §4 trick 1, §3.5)."""
import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKERS = os.path.join(REPO, "tests", "workers")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _clean_env():
    env = os.environ.copy()
    # the workers must see a plain single-device CPU world of their own
    env.pop("XLA_FLAGS", None)
    for k in list(env):
        if k.startswith("PADDLE_"):
            env.pop(k)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


class TestLaunchMultiProcess:
    def test_two_process_collectives_and_dp_parity(self, tmp_path):
        port = _free_port()
        cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
               "--nnodes", "2", "--master", f"127.0.0.1:{port}",
               "--log_dir", str(tmp_path / "logs"),
               os.path.join(WORKERS, "mp_worker.py"), str(tmp_path)]
        r = subprocess.run(cmd, env=_clean_env(), cwd=REPO, timeout=280,
                           capture_output=True, text=True)
        logs = ""
        logdir = tmp_path / "logs"
        if logdir.exists():
            for f in sorted(logdir.iterdir()):
                logs += f"\n--- {f.name} ---\n" + f.read_text()[-3000:]
        assert r.returncode == 0, (r.stdout, r.stderr, logs)

        res = [json.load(open(tmp_path / f"result.{rk}.json"))
               for rk in range(2)]
        # both ranks agree on the (global) loss sequence
        assert np.allclose(res[0]["losses"], res[1]["losses"]), res

        # single-process oracle: full batch, same init
        import paddle_tpu as P
        import paddle_tpu.nn as nn
        P.seed(0)
        net = nn.Linear(4, 2)
        opt = P.optimizer.SGD(0.1, parameters=net.parameters())
        rng = np.random.default_rng(7)
        X = rng.standard_normal((8, 4)).astype(np.float32)
        Y = rng.standard_normal((8, 2)).astype(np.float32)
        oracle = []
        for _ in range(2):
            loss = ((net(P.to_tensor(X)) - P.to_tensor(Y)) ** 2).mean()
            loss.backward()
            opt.step()
            opt.clear_grad()
            oracle.append(float(loss.numpy()))
        assert np.allclose(res[0]["losses"], oracle, rtol=2e-3,
                           atol=2e-4), (res[0]["losses"], oracle)

        # no_sync accumulation phase: first synced backward must reduce
        # the whole accumulated grad (DDP contract)
        assert np.isclose(res[0]["probe"], res[1]["probe"]), res
        P.seed(1)
        net2 = nn.Linear(4, 2)
        opt2 = P.optimizer.SGD(0.1, parameters=net2.parameters())
        per = 4
        for m in [slice(0, 2), slice(2, 3), slice(3, 4)]:
            rows = np.r_[np.arange(m.start, m.stop),
                         per + np.arange(m.start, m.stop)]
            loss = ((net2(P.to_tensor(X[rows])) -
                     P.to_tensor(Y[rows])) ** 2).mean()
            loss.backward()
        opt2.step()
        opt2.clear_grad()
        probe_oracle = float(((net2(P.to_tensor(X)) -
                               P.to_tensor(Y)) ** 2).mean().numpy())
        assert np.isclose(res[0]["probe"], probe_oracle, rtol=2e-3), \
            (res[0]["probe"], probe_oracle)

    def test_elastic_crash_restart_reregister(self, tmp_path):
        from paddle_tpu.native import TCPStore
        store_port = _free_port()
        master = TCPStore("127.0.0.1", store_port, is_master=True)
        try:
            cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
                   "--nnodes", "2", "--max_restarts", "2",
                   "--elastic_level", "1",
                   "--log_dir", str(tmp_path / "logs"),
                   os.path.join(WORKERS, "elastic_worker.py"),
                   str(store_port), str(tmp_path)]
            r = subprocess.run(cmd, env=_clean_env(), cwd=REPO,
                               timeout=280, capture_output=True, text=True)
            assert r.returncode == 0, (r.stdout, r.stderr)
            # the launcher really did restart rank 1
            assert "restart" in r.stdout, r.stdout
            # rank 1 crashed exactly once (marker) and then re-registered
            # (generation counter observed by rank 0 → job completed)
            assert (tmp_path / "crashed.1").exists()
        finally:
            master.close()


def _spawn_worker(out_dir):
    """Module-level so the spawn context can pickle it."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import paddle_tpu as P
    import paddle_tpu.distributed as dist
    dist.init_parallel_env()
    rank = dist.get_rank()
    t = P.to_tensor(np.array([float(rank + 1)], np.float32))
    dist.all_reduce(t)
    with open(os.path.join(out_dir, f"spawn.{rank}"), "w") as f:
        f.write(str(float(t.numpy()[0])))


class TestSpawn:
    def test_spawn_two_workers_allreduce(self, tmp_path):
        import paddle_tpu.distributed as dist
        # run in a clean subprocess: spawn children must not inherit this
        # test process's 8-device CPU config / initialized backend
        code = (
            "import tests.test_multiprocess as m\n"
            "import paddle_tpu.distributed as dist\n"
            f"dist.spawn(m._spawn_worker, args=({str(tmp_path)!r},), "
            "nprocs=2)\n")
        r = subprocess.run([sys.executable, "-c", code], env=_clean_env(),
                           cwd=REPO, timeout=240, capture_output=True,
                           text=True)
        assert r.returncode == 0, (r.stdout, r.stderr)
        vals = [float(open(tmp_path / f"spawn.{rk}").read())
                for rk in range(2)]
        assert vals == [3.0, 3.0], vals


class TestMultiProcessCheckpoint:
    def test_per_rank_ckpt_roundtrip(self, tmp_path):
        """Round-3 (VERDICT r2 item 8): per-rank shard files + async_save
        + coordinator metadata across 2 real processes."""
        port = _free_port()
        cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
               "--nnodes", "2", "--master", f"127.0.0.1:{port}",
               "--log_dir", str(tmp_path / "logs"),
               os.path.join(WORKERS, "ckpt_worker.py"), str(tmp_path)]
        r = subprocess.run(cmd, env=_clean_env(), cwd=REPO, timeout=280,
                           capture_output=True, text=True)
        logs = ""
        logdir = tmp_path / "logs"
        if logdir.exists():
            for f in sorted(logdir.iterdir()):
                logs += f"\n--- {f.name} ---\n" + f.read_text()[-3000:]
        assert r.returncode == 0, (r.stdout, r.stderr, logs)
        res = [json.load(open(tmp_path / f"ckpt_result.{rk}.json"))
               for rk in range(2)]
        # each rank restored ITS OWN private shard
        assert np.allclose(res[0]["private"], 1.0)
        assert np.allclose(res[1]["private"], 2.0)


class TestMultiControllerSPMD:
    def test_spmd_train_step_across_two_processes(self, tmp_path):
        """Round-4 (VERDICT r3 item 4): an SPMD train step over a GLOBAL
        8-device mesh spanning 2 OS processes (4 virtual CPU devices
        each, jax.distributed) — ZeRO-3 and DP×TP — matches the
        single-process 8-device oracle loss-for-loss. This is the
        multi-controller regime a v5p-32 pod actually runs."""
        port = _free_port()
        cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
               "--nnodes", "2", "--master", f"127.0.0.1:{port}",
               "--log_dir", str(tmp_path / "logs"),
               os.path.join(WORKERS, "spmd_mc_worker.py"), str(tmp_path)]
        env = _clean_env()
        r = subprocess.run(cmd, env=env, cwd=REPO, timeout=280,
                           capture_output=True, text=True)
        logs = ""
        logdir = tmp_path / "logs"
        if logdir.exists():
            for f in sorted(logdir.iterdir()):
                logs += f"\n--- {f.name} ---\n" + f.read_text()[-3000:]
        assert r.returncode == 0, (r.stdout, r.stderr, logs)
        res = [json.load(open(tmp_path / f"spmd_mc.{rk}.json"))
               for rk in range(2)]
        # both controllers observe the same global loss sequence
        for key in ("zero3", "dp_tp", "pipeline_4d", "sep", "ep"):
            assert np.allclose(res[0][key], res[1][key]), (key, res)

        # single-process oracle: same model/seed/data on this process's
        # own 8-device mesh (conftest), same fleet configs
        from tests.workers.spmd_mc_worker import (MLP, TPMLP, run_config,
                                                  run_ep, run_pipeline,
                                                  run_sep, _reset_fleet)
        oracle_z3 = run_config({"sharding_degree": 8}, MLP, stage=3)
        oracle_tp = run_config({"dp_degree": 2, "mp_degree": 4}, TPMLP)
        oracle_pp = run_pipeline()
        oracle_sep = run_sep()
        oracle_ep = run_ep()
        _reset_fleet()
        assert np.allclose(res[0]["zero3"], oracle_z3, rtol=2e-3,
                           atol=2e-4), (res[0]["zero3"], oracle_z3)
        assert np.allclose(res[0]["dp_tp"], oracle_tp, rtol=2e-3,
                           atol=2e-4), (res[0]["dp_tp"], oracle_tp)
        # the PIPELINE runtime (pp2 x mp2 x ZeRO-3(2)) across processes
        assert np.allclose(res[0]["pipeline_4d"], oracle_pp, rtol=2e-3,
                           atol=2e-4), (res[0]["pipeline_4d"], oracle_pp)
        # ring context-parallel training (sep) across processes
        assert np.allclose(res[0]["sep"], oracle_sep, rtol=2e-3,
                           atol=2e-4), (res[0]["sep"], oracle_sep)
        # MoE expert-parallel step (sort dispatch) across processes
        assert np.allclose(res[0]["ep"], oracle_ep, rtol=2e-3,
                           atol=2e-4), (res[0]["ep"], oracle_ep)


class TestElasticScaleOut:
    def test_reform_at_larger_world(self, tmp_path):
        """Round-4 (VERDICT r3 item 8): the job starts at world size 1
        (below --nnodes max 2); the scale_to signal makes the launcher
        re-form at world size 2 and workers resume from checkpoint."""
        logdir = tmp_path / "logs"
        logdir.mkdir(parents=True)
        cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
               "--nnodes", "1:2", "--start_nodes", "1",
               "--log_dir", str(logdir),
               os.path.join(WORKERS, "elastic_scaleout_worker.py"),
               str(tmp_path), str(logdir)]
        r = subprocess.run(cmd, env=_clean_env(), cwd=REPO, timeout=280,
                           capture_output=True, text=True)
        logs = ""
        if logdir.exists():
            for f in sorted(logdir.iterdir()):
                if f.is_file():
                    logs += f"\n--- {f.name} ---\n" + f.read_text()[-2000:]
        assert r.returncode == 0, (r.stdout, r.stderr, logs)
        assert "re-form" in r.stdout, r.stdout
        res = json.load(open(tmp_path / "scaleout_result.json"))
        assert res["world"] == 2, res           # scaled OUT
        assert res["incarnation"] == 1, res     # one re-form
        assert 0 < res["resumed_from"] < 20, res  # resumed mid-run
        assert res["final_step"] == 20, res


class TestElasticScaleIn:
    def test_reform_at_smaller_world(self, tmp_path):
        """Round-3 (VERDICT r2 item 9): permanent rank failure →
        launcher re-forms the job at world size 1 (recomputed ranks,
        bumped incarnation); the survivor resumes from checkpoint."""
        cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
               "--nnodes", "1:2", "--log_dir", str(tmp_path / "logs"),
               os.path.join(WORKERS, "elastic_scalein_worker.py"),
               str(tmp_path)]
        r = subprocess.run(cmd, env=_clean_env(), cwd=REPO, timeout=280,
                           capture_output=True, text=True)
        logs = ""
        logdir = tmp_path / "logs"
        if logdir.exists():
            for f in sorted(logdir.iterdir()):
                logs += f"\n--- {f.name} ---\n" + f.read_text()[-2000:]
        assert r.returncode == 0, (r.stdout, r.stderr, logs)
        assert "re-form" in r.stdout, r.stdout
        res = json.load(open(tmp_path / "scalein_result.json"))
        assert res["world"] == 1, res           # scaled in
        assert res["incarnation"] == 1, res     # one re-form
        assert 0 < res["resumed_from"] < 20, res  # resumed mid-run
        assert res["final_step"] == 20, res
