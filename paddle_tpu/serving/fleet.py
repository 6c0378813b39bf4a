"""Crash-survivable fleet control plane (round 19).

Everything below the router already survives faults — failover splices
streams, migrations bounce and retry, breakers shed flaky replicas —
but until this module the CONTROL PLANE was a lab stub: replicas were
factory callbacks in the router's own process, and the one router
object was a single point of failure whose affinity/breaker/ownership
state died with it.  This module is the production tier (reference
capability: Paddle Fleet elastic training's control plane, and the
replica-lifecycle/SLO operability the Gemma-on-TPU serving paper
frames as what separates a demo engine from a deployment):

- :class:`RouterJournal` — a small append-only JSONL journal with
  per-record CRC framing and bounded rotation.  The router appends its
  journaled state transitions (placements, ownership drops, breaker
  opens, stream begin/end, down/up) as it serves; replay skips torn
  records (the ``journal_torn_write`` chaos point tears them on
  purpose) instead of dying — the file is a recovery accelerant, never
  a dependency.
- :class:`ProcessReplicaBackend` — real provisioning for
  :class:`~paddle_tpu.serving.autoscale.FleetAutoscaler`: spawns
  actual replica *server processes* (``python -m
  paddle_tpu.serving.fleet_worker``) with ephemeral-port allocation, a
  readiness poll against ``/healthz`` under a bounded startup
  deadline, and liveness supervision that restarts a dead process with
  backoff under a per-replica restart budget.  Spawned processes are
  tracked and reaped on EVERY exit path (close, atexit, and the worker
  self-reaps when its parent dies) — no stale-pytest-style orphans.
  :class:`ThreadLauncher` swaps the subprocess for an in-process
  ``ServingServer`` so the chaos fuzz and unit tests exercise the
  identical supervision machinery without process spawn costs; the
  graftlint ``fleet-process-spawn`` rule keeps every OTHER replica
  spawn in the tree routed through this backend.
- :class:`RouterSupervisor` — primary + warm standby with takeover:
  the primary router journals as it serves; when it crashes
  (``kill_active`` or the ``router_crash`` chaos point), the dead
  router's client connections are torn down exactly as a dead
  process's would be (in-process streams erred, HTTP sockets closed —
  the remote's disconnect-cancel fires), and the FIRST client to
  notice promotes the standby: journal replay rebuilds
  affinity/ownership/breaker state, ONE ``/healthz`` sweep rebuilds
  liveness and load, orphaned requests are cancelled best-effort (held
  pages otherwise fall to the deadline-expiry sweep).  Promotion is
  idempotent under the supervisor lock — the ``standby_takeover_race``
  chaos point drives a concurrent promotion attempt through the guard.
  :class:`SupervisorStream` retries a crashed router's streams on the
  new active with a client-side splice, so accepted streams survive
  the death of the router itself token-exactly.

What is journaled vs swept (the recovery contract, docs/FLEET.md):
liveness, loads and reservations are LIVE state owned by the replicas
— one sweep rebuilds them; affinity/ownership order, breaker opens and
stream begin/end are ROUTER state — the journal rebuilds them.  A cold
router = constructor + ``adopt_journal`` + ``sweep_health`` +
``release_orphans`` (:meth:`ServingRouter.recover`), and converges to
a never-crashed router's routing decisions within that one sweep.

Env knobs (docs/ENV_KNOBS.md): ``PADDLE_TPU_SERVING_FLEET_STARTUP_S``,
``PADDLE_TPU_SERVING_FLEET_RESTARTS``,
``PADDLE_TPU_SERVING_FLEET_SUPERVISE_S``,
``PADDLE_TPU_SERVING_FLEET_JOURNAL_MB``.

Nothing here imports jax: the control plane is host bookkeeping (the
worker process imports jax in ITS interpreter, and a parent that
touched jax would hold the chip its workers need).  Subprocess workers
force ``jax_platforms=cpu`` by default; pass ``platform=None`` in the
spec for a worker that owns its chip — one per chip (docs/FLEET.md).
"""
from __future__ import annotations

import atexit
import http.client
import json
import logging
import os
import subprocess
import sys
import tempfile
import threading
import time
import weakref
import zlib

import numpy as np

from .chaos import ChaosConfig, ChaosInjector
from .frontend import Rejected, Unavailable
from .replica import HTTPReplica
from .router import ServingRouter

__all__ = ["ProcessReplica", "ProcessReplicaBackend", "ReplicaSpec",
           "RouterCrashed", "RouterJournal", "RouterSupervisor",
           "SubprocessLauncher", "SupervisorStream", "ThreadLauncher"]

_log = logging.getLogger("paddle_tpu.serving")

_ENV_STARTUP = "PADDLE_TPU_SERVING_FLEET_STARTUP_S"
_ENV_RESTARTS = "PADDLE_TPU_SERVING_FLEET_RESTARTS"
_ENV_SUPERVISE = "PADDLE_TPU_SERVING_FLEET_SUPERVISE_S"
_ENV_JOURNAL_MB = "PADDLE_TPU_SERVING_FLEET_JOURNAL_MB"


def _env_float(name, default):
    v = os.environ.get(name)
    try:
        return float(v) if v not in (None, "") else float(default)
    except ValueError:
        return float(default)


class RouterCrashed(RuntimeError):
    """The router serving this stream died — retry against the
    standby (the supervisor does this transparently)."""


# ---------------------------------------------------------------------------
# The routing journal


class RouterJournal:
    """Append-only JSONL journal with per-record CRC framing.

    Line format: ``<crc32 hex8> <compact json>\\n`` — the CRC covers the
    JSON bytes, so a record torn mid-write (process death, full disk,
    the ``journal_torn_write`` chaos point) fails the check and replay
    SKIPS it (counted in ``torn_skipped``) instead of dying.  Appends
    are flushed per record: the file is current at the instant of a
    crash, which is the whole point.

    Rotation keeps the journal small: past ``max_bytes`` (default
    ``PADDLE_TPU_SERVING_FLEET_JOURNAL_MB``, 16 MB) the live file
    rotates to ``<path>.1`` (replacing the previous rotation) and
    replay reads ``.1`` then the live file — affinity state is
    recency-weighted, so dropping the oldest half of history degrades
    recovered cache-hit rates, never correctness."""

    def __init__(self, path, *, max_bytes=None, chaos=None):
        self.path = str(path)
        if max_bytes is None:
            max_bytes = int(_env_float(_ENV_JOURNAL_MB, 16.0)
                            * 1024 * 1024)
        self.max_bytes = int(max_bytes)
        if isinstance(chaos, ChaosInjector):
            self.chaos = chaos
        else:
            assert chaos is None or isinstance(chaos, ChaosConfig)
            self.chaos = ChaosInjector(chaos, name="journal")
        self._lock = threading.Lock()
        self._file = None
        try:
            self._bytes = os.path.getsize(self.path)
        except OSError:
            self._bytes = 0
        self.appended = 0       # records written (incl. torn ones)
        self.torn_writes = 0    # records the chaos point tore
        self.torn_skipped = 0   # bad records skipped by the last replay

    def append(self, rec):
        line = json.dumps(rec, separators=(",", ":"))
        data = line.encode()
        framed = f"{zlib.crc32(data):08x} {line}\n".encode()
        if self.chaos.fire("journal_torn_write"):
            # a torn write: the frame stops mid-JSON.  The newline is
            # kept so the NEXT record stays parseable — replay handles
            # an un-terminated final line (real crash tail) separately.
            framed = framed[: max(10, len(framed) // 2)] + b"\n"
            self.torn_writes += 1
        with self._lock:
            if self._bytes + len(framed) > self.max_bytes:
                self._rotate_locked()
            if self._file is None:
                self._file = open(self.path, "ab")
            self._file.write(framed)
            self._file.flush()
            self._bytes += len(framed)
            self.appended += 1

    def _rotate_locked(self):
        if self._file is not None:
            self._file.close()
            self._file = None
        try:
            os.replace(self.path, self.path + ".1")
        except OSError:
            pass
        self._bytes = 0

    def replay(self):
        """Yield journaled records oldest-first (rotated file, then the
        live one), skipping torn/corrupt lines."""
        self.torn_skipped = 0
        for path in (self.path + ".1", self.path):
            try:
                f = open(path, "rb")
            except OSError:
                continue
            with f:
                for raw in f:
                    rec = self._parse(raw)
                    if rec is None:
                        self.torn_skipped += 1
                        continue
                    yield rec

    @staticmethod
    def _parse(raw):
        raw = raw.rstrip(b"\n")
        if not raw:
            return None
        crc, _, body = raw.partition(b" ")
        if len(crc) != 8 or not body:
            return None
        try:
            if int(crc, 16) != zlib.crc32(body):
                return None
            rec = json.loads(body)
        except ValueError:
            return None
        return rec if isinstance(rec, dict) else None

    def close(self):
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None

    def stats(self):
        return {"path": self.path, "appended": self.appended,
                "torn_writes": self.torn_writes,
                "torn_skipped": self.torn_skipped,
                "bytes": self._bytes}


# ---------------------------------------------------------------------------
# Replica server processes: spec, launchers, backend


class ReplicaSpec:
    """How one replica server process is built.  ``model`` /
    ``engine`` are kwargs for the worker's default tiny-Llama builder;
    ``builder`` (``"module:function"``, called with the spec dict,
    returning a ``ServingEngine``) overrides it for real models.
    ``platform`` defaults to ``"cpu"`` — a chip belongs to one process
    at a time, so workers sharing a host cannot all take the default
    accelerator; set ``platform=None`` only for a worker that owns its
    chip (one per chip, docs/FLEET.md)."""

    def __init__(self, *, model=None, engine=None, role="mixed",
                 builder=None, max_queued=64, platform="cpu",
                 drain_s=10.0):
        self.model = dict(model or {})
        self.engine = dict(engine or {})
        self.role = role
        self.builder = builder
        self.max_queued = int(max_queued)
        self.platform = platform
        self.drain_s = float(drain_s)

    def to_dict(self):
        return {"model": self.model, "engine": self.engine,
                "role": self.role, "builder": self.builder,
                "max_queued": self.max_queued,
                "platform": self.platform, "drain_s": self.drain_s}

    @classmethod
    def from_dict(cls, d):
        return cls(**{k: d.get(k) for k in
                      ("model", "engine", "role", "builder",
                       "max_queued", "platform", "drain_s")
                      if d.get(k) is not None})


class WorkerHandle:
    """One spawned replica server: either a real subprocess (``proc``)
    or an in-process ServingServer (``server``/``engine``)."""

    def __init__(self, *, proc=None, server=None, engine=None,
                 ready_file=None, log_path=None, pid=None, port=None):
        self.proc = proc
        self.server = server
        self.engine = engine
        self.ready_file = ready_file
        self.log_path = log_path
        self.pid = pid
        self.port = port
        self._killed = False

    def alive(self):
        if self.proc is not None:
            return self.proc.poll() is None
        return self.server is not None and not self._killed


class SubprocessLauncher:
    """Spawns real replica server processes.  The ONE blessed home of
    ``subprocess.Popen`` for serving processes (graftlint
    ``fleet-process-spawn``): every spawn here is tracked, deadline-
    polled for readiness, and reaped on every exit path."""

    def __init__(self, *, python=None, log_dir=None, extra_env=None):
        self.python = python or sys.executable
        self.log_dir = log_dir or tempfile.mkdtemp(
            prefix="pdtpu_fleet_")
        self.extra_env = dict(extra_env or {})
        self._seq = 0

    def spawn(self, spec, name):
        self._seq += 1
        base = os.path.join(self.log_dir, f"{name}_{self._seq}")
        spec_path = base + ".spec.json"
        ready_path = base + ".ready.json"
        log_path = base + ".log"
        with open(spec_path, "w") as f:
            json.dump(spec.to_dict(), f)
        cmd = [self.python, "-m", "paddle_tpu.serving.fleet_worker",
               "--spec", spec_path, "--ready-file", ready_path,
               "--parent-pid", str(os.getpid())]
        env = dict(os.environ, **self.extra_env)
        log = open(log_path, "ab")
        try:
            proc = subprocess.Popen(cmd, stdout=log,
                                    stderr=subprocess.STDOUT, env=env)
        finally:
            log.close()
        return WorkerHandle(proc=proc, ready_file=ready_path,
                            log_path=log_path, pid=proc.pid)

    def poll_ready(self, handle):
        """One non-blocking readiness check: the worker writes its
        bound port to the ready file atomically once serving."""
        if handle.port is not None:
            return handle.port
        try:
            with open(handle.ready_file) as f:
                info = json.load(f)
        except (OSError, ValueError):
            return None
        handle.port = int(info["port"])
        handle.pid = int(info.get("pid", handle.pid or 0)) or handle.pid
        return handle.port

    def kill(self, handle):
        """SIGKILL — the kill -9 drill."""
        handle._killed = True
        if handle.proc is not None and handle.proc.poll() is None:
            handle.proc.kill()
            handle.proc.wait(timeout=10)

    def terminate(self, handle, grace=10.0):
        """SIGTERM with grace (the worker drains), then SIGKILL."""
        handle._killed = True
        proc = handle.proc
        if proc is None or proc.poll() is not None:
            return
        proc.terminate()
        try:
            proc.communicate(timeout=grace)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)


class ThreadLauncher:
    """In-process stand-in for :class:`SubprocessLauncher` — the chaos
    fuzz and unit tests drive the IDENTICAL supervision machinery
    (spawn / readiness / kill / restart budget) without paying a
    process spawn per replica.  ``kill`` is the closest in-process
    analog of SIGKILL the invariants allow: the front-end fails hard
    (pages released — a real SIGKILL releases them by erasing the
    process) and the listener stops, so clients see reset connections
    and an unreachable ``/healthz``."""

    def __init__(self, engine_factory=None):
        # engine_factory(spec) -> ServingEngine; defaults to the
        # worker's own spec builder (single source of truth)
        self.engine_factory = engine_factory
        self._seq = 0

    def _build_engine(self, spec):
        if self.engine_factory is not None:
            return self.engine_factory(spec)
        from .fleet_worker import build_engine_from_spec
        return build_engine_from_spec(spec.to_dict())

    def spawn(self, spec, name):
        from .server import ServingServer
        self._seq += 1
        engine = self._build_engine(spec)
        srv = ServingServer(engine, port=0, role=spec.role,
                            max_queued=spec.max_queued)
        _, port = srv.start()
        return WorkerHandle(server=srv, engine=engine, port=port,
                            pid=-self._seq)  # synthetic, never a real pid

    def poll_ready(self, handle):
        return handle.port

    def kill(self, handle):
        handle._killed = True
        handle.server.abort(RouterCrashed("fleet: process killed"))

    def terminate(self, handle, grace=10.0):
        handle._killed = True
        handle.server.close(timeout=grace)


class _BackendEntry:
    __slots__ = ("replica", "spec", "handle", "name", "restarts",
                 "stopped", "failed")

    def __init__(self, replica, spec, handle, name):
        self.replica = replica
        self.spec = spec
        self.handle = handle
        self.name = name
        self.restarts = 0
        self.stopped = False
        self.failed = False


class ProcessReplica(HTTPReplica):
    """An :class:`HTTPReplica` bound to a supervised server process.
    ``close()`` routes through the backend (terminate + reap); a
    supervised restart re-points ``port`` at the new process — the
    router's health prober then readmits the slot on its own."""

    kind = "proc"

    def __init__(self, backend, host, port, **kw):
        super().__init__(host, port, **kw)
        self._backend = weakref.ref(backend)
        self.failed_permanently = False

    @property
    def pid(self):
        entry = self.backend_entry
        return entry.handle.pid if entry is not None else None

    @property
    def restarts(self):
        entry = self.backend_entry
        return entry.restarts if entry is not None else 0

    @property
    def backend_entry(self):
        backend = self._backend()
        if backend is None:
            return None
        return backend._entry_for(self)

    def close(self, timeout=10.0):
        backend = self._backend()
        if backend is not None:
            backend.stop_replica(self, grace=timeout)
        return True


# every live backend gets reaped at interpreter exit — belt-and-braces
# on top of close(); the worker's parent-pid watchdog is the third net
_LIVE_BACKENDS: "weakref.WeakSet" = weakref.WeakSet()


def _reap_all_backends():  # pragma: no cover - exit-path safety net
    for backend in list(_LIVE_BACKENDS):
        try:
            backend.close(grace=2.0)
        except Exception:
            pass


atexit.register(_reap_all_backends)


class ProcessReplicaBackend:
    """Real provisioning for the autoscaler: ``provision(role)``
    spawns a replica server process, waits for ``/healthz`` readiness
    under the startup deadline, and returns a routable
    :class:`ProcessReplica`.  A supervision thread restarts dead
    processes with backoff under the per-replica restart budget
    (``PADDLE_TPU_SERVING_FLEET_RESTARTS``); budget exhaustion marks
    the replica permanently failed — the router's breaker keeps
    traffic away, and drain-by-health rotation replaces it.

    ``spec_for_role`` is a :class:`ReplicaSpec`, a ``{role: spec}``
    dict, or a callable ``role -> spec``.  ``launcher`` defaults to
    :class:`SubprocessLauncher`; :class:`ThreadLauncher` runs the same
    machinery in-process for tests and the chaos fuzz (whose
    ``replica_proc_kill`` point fires in the supervision loop)."""

    def __init__(self, spec_for_role, *, launcher=None, startup_s=None,
                 restart_budget=None, supervise_interval_s=None,
                 chaos=None):
        self._spec_for_role = spec_for_role
        self.launcher = launcher or SubprocessLauncher()
        self.startup_s = (_env_float(_ENV_STARTUP, 45.0)
                          if startup_s is None else float(startup_s))
        self.restart_budget = (int(_env_float(_ENV_RESTARTS, 3))
                               if restart_budget is None
                               else int(restart_budget))
        self.supervise_interval_s = (
            _env_float(_ENV_SUPERVISE, 0.5)
            if supervise_interval_s is None
            else float(supervise_interval_s))
        if isinstance(chaos, ChaosInjector):
            self.chaos = chaos
        else:
            assert chaos is None or isinstance(chaos, ChaosConfig)
            self.chaos = ChaosInjector(chaos, name="fleet-backend")
        self._entries: list[_BackendEntry] = []
        self._lock = threading.Lock()
        # supervision passes are mutually exclusive: a manual
        # supervise_once() racing the daemon pass must not double-
        # restart one dead process (and leak the loser's spawn)
        self._sup_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread = None
        self._seq = 0
        self.spawns = 0
        self.restarts = 0
        self.kills = 0          # chaos replica_proc_kill firings
        self.perm_failures = 0  # restart budgets exhausted
        self._closed = False
        _LIVE_BACKENDS.add(self)

    # -- provisioning ------------------------------------------------------
    def _resolve_spec(self, role):
        s = self._spec_for_role
        if callable(s):
            s = s(role)
        elif isinstance(s, dict) and not isinstance(s, ReplicaSpec):
            s = s.get(role) or s.get("__default__")
        if not isinstance(s, ReplicaSpec):
            raise ValueError(f"no ReplicaSpec for role {role!r}")
        if s.role != role:
            s = ReplicaSpec(**dict(s.to_dict(), role=role))
        return s

    def provision(self, role="mixed"):
        """The autoscaler factory: spawn → ready → routable replica."""
        spec = self._resolve_spec(role)
        self._seq += 1
        name = f"replica_{role}_{self._seq}"
        handle = self._spawn_ready(spec, name)
        rep = ProcessReplica(self, "127.0.0.1", handle.port, role=role)
        with self._lock:
            self._entries.append(_BackendEntry(rep, spec, handle, name))
        self.start_supervision()
        _log.info(json.dumps({"event": "fleet_provisioned",
                              "name": name, "role": role,
                              "pid": handle.pid, "port": handle.port}))
        return rep

    def _spawn_ready(self, spec, name):
        """Spawn + bounded readiness: the ready file yields the port,
        then ``/healthz`` must answer ``ok`` — all under the startup
        deadline.  Failure reaps the half-started process."""
        handle = self.launcher.spawn(spec, name)
        self.spawns += 1
        deadline = time.monotonic() + self.startup_s
        port = None
        try:
            while time.monotonic() < deadline:
                if not handle.alive():
                    raise RuntimeError(
                        f"fleet replica {name} died during startup "
                        f"(log: {handle.log_path})")
                port = self.launcher.poll_ready(handle)
                if port is not None and self._healthz_ok(port):
                    return handle
                self.chaos.sleep(0.05)
            raise RuntimeError(
                f"fleet replica {name} not ready within "
                f"{self.startup_s}s (port={port}, "
                f"log: {handle.log_path})")
        except Exception:
            self.launcher.terminate(handle, grace=2.0)
            raise

    @staticmethod
    def _healthz_ok(port, timeout=2.0):
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=timeout)
            try:
                conn.request("GET", "/healthz")
                resp = conn.getresponse()
                data = resp.read()
            finally:
                conn.close()
            return (resp.status == 200
                    and json.loads(data).get("status") == "ok")
        except (OSError, ValueError):
            return False

    # -- supervision -------------------------------------------------------
    def start_supervision(self):
        if self._thread is None and not self._closed:
            self._thread = threading.Thread(
                target=self._supervise_loop, name="fleet-supervisor",
                daemon=True)
            self._thread.start()
        return self

    def _supervise_loop(self):
        # wait-first: a freshly provisioned replica is known healthy,
        # and tests driving manual supervise_once() passes must not
        # race an immediate daemon pass
        while not self._stop.wait(self.supervise_interval_s):
            try:
                self.supervise_once()
            except Exception:  # pragma: no cover - loop must not die
                _log.exception("fleet supervision pass failed")

    def supervise_once(self):
        """One supervision pass (tests call this synchronously): fire
        the ``replica_proc_kill`` chaos point, then restart any dead
        process with backoff under the restart budget."""
        with self._sup_lock:
            self._supervise_pass()

    def _supervise_pass(self):
        with self._lock:
            entries = list(self._entries)
        for entry in entries:
            if entry.stopped or entry.failed:
                continue
            if entry.handle.alive() \
                    and self.chaos.fire("replica_proc_kill",
                                        replica=entry.name):
                self.kills += 1
                self.launcher.kill(entry.handle)
                _log.warning(json.dumps({
                    "event": "fleet_chaos_proc_kill",
                    "name": entry.name, "pid": entry.handle.pid}))
            if entry.handle.alive():
                continue
            self._restart(entry)

    def _restart(self, entry):
        if entry.restarts >= self.restart_budget:
            entry.failed = True
            entry.replica.failed_permanently = True
            self.perm_failures += 1
            _log.error(json.dumps({
                "event": "fleet_replica_failed_permanently",
                "name": entry.name, "restarts": entry.restarts}))
            return
        delay = self.chaos.backoff().delay(entry.restarts)
        self.chaos.sleep(delay)
        entry.restarts += 1
        try:
            handle = self._spawn_ready(
                entry.spec, f"{entry.name}_r{entry.restarts}")
        except Exception as e:
            # counted against the budget; next pass retries or fails
            _log.warning(json.dumps({
                "event": "fleet_restart_failed", "name": entry.name,
                "attempt": entry.restarts, "cause": repr(e)}))
            return
        entry.handle = handle
        entry.replica.port = handle.port
        self.restarts += 1
        _log.info(json.dumps({
            "event": "fleet_replica_restarted", "name": entry.name,
            "attempt": entry.restarts, "pid": handle.pid,
            "port": handle.port}))

    # -- drills / teardown -------------------------------------------------
    def _entry_for(self, replica):
        with self._lock:
            for entry in self._entries:
                if entry.replica is replica:
                    return entry
        return None

    def kill_replica_process(self, replica):
        """The harness's kill -9 drill: SIGKILL the replica's server
        process NOW (supervision will restart it within budget)."""
        entry = self._entry_for(replica)
        if entry is None or not entry.handle.alive():
            return False
        self.launcher.kill(entry.handle)
        _log.warning(json.dumps({"event": "fleet_proc_kill_drill",
                                 "name": entry.name,
                                 "pid": entry.handle.pid}))
        return True

    def stop_replica(self, replica, grace=10.0):
        entry = self._entry_for(replica)
        if entry is None or entry.stopped:
            return False
        entry.stopped = True
        self.launcher.terminate(entry.handle, grace=grace)
        return True

    def live_pids(self):
        """Pids of processes still alive — the harness's zero-orphan
        gate asserts this is empty after close()."""
        with self._lock:
            return [e.handle.pid for e in self._entries
                    if e.handle.alive()]

    def close(self, grace=10.0):
        """Reap EVERYTHING: stop supervision, terminate every process
        (SIGTERM with grace, then SIGKILL), verify nothing survived."""
        self._closed = True
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=max(5.0, grace))
            self._thread = None
        with self._lock:
            entries = list(self._entries)
        for entry in entries:
            entry.stopped = True
            try:
                self.launcher.terminate(entry.handle, grace=grace)
            except Exception:  # pragma: no cover - reap best-effort
                pass
        leftovers = self.live_pids()
        if leftovers:  # pragma: no cover - the reap above is bounded
            _log.error(json.dumps({"event": "fleet_orphan_processes",
                                   "pids": leftovers}))
        return not leftovers

    def stats(self):
        with self._lock:
            return {"replicas": len(self._entries),
                    "spawns": self.spawns, "restarts": self.restarts,
                    "chaos_kills": self.kills,
                    "perm_failures": self.perm_failures,
                    "live": len([e for e in self._entries
                                 if e.handle.alive()])}


# ---------------------------------------------------------------------------
# Router supervisor: primary + warm standby with takeover


class SupervisorStream:
    """One client stream that survives ROUTER death: consumes the
    active router's :class:`RouterStream` and, when that router
    crashes mid-stream, resubmits on the promoted standby with a
    client-side splice (skip the tokens already delivered) — the
    determinism contract (token t pure in weights/history/seed/t)
    makes the retried stream byte-identical."""

    def __init__(self, sup, req_id, prompt, kwargs, n):
        self.sup = sup
        self.req_id = req_id
        self.request_id = kwargs.get("request_id")
        self.prompt = prompt
        self.kwargs = kwargs
        self.n = int(n)
        self._delivered = [0] * self.n
        self._finished = [False] * self.n
        self._router = None
        self._rs = None
        self.takeovers_seen = 0

    @property
    def done(self):
        return all(self._finished)

    def _attach(self, router):
        """(Re)submit on ``router``, arming the cross-router splice."""
        rs = router.submit(self.prompt, **self.kwargs)
        rs._skip = [d if not f else 0
                    for d, f in zip(self._delivered, self._finished)]
        self._router, self._rs = router, rs
        return rs

    def events(self, timeout=120.0, idle_s=None):
        sup = self.sup
        deadline = time.monotonic() + timeout
        while not self.done:
            router = sup._ensure_active()
            if self._router is not router:
                try:
                    self._attach(router)
                    self.takeovers_seen = sup.takeovers
                except Unavailable:
                    if sup.active is not router or router._crashed:
                        continue  # crashed between ensure and submit
                    raise
                except Rejected:
                    raise
            try:
                for ev in self._rs.events(timeout=timeout,
                                          idle_s=idle_s):
                    if self._router._crashed:
                        # the router died under us: events pulled past
                        # this point may be orphan-release artifacts
                        # (a `cancelled` finish for a request the NEW
                        # router's recovery reaped) — never treat them
                        # as completion; resubmit with the splice.
                        # `_crashed` is set before the takeover that
                        # runs orphan release, so the check is ordered
                        # ahead of any such artifact.
                        raise RouterCrashed("router crashed mid-stream")
                    if ev["type"] == "idle":
                        yield ev
                        continue
                    idx = ev.get("index", 0)
                    if self._finished[idx]:
                        continue  # replayed sample on a resubmission
                    if ev["type"] == "token":
                        self._delivered[idx] += 1
                        if sup.chaos.fire("router_crash"):
                            sup.kill_active(cause="chaos:router_crash")
                        yield ev
                    elif ev["type"] == "finish":
                        self._finished[idx] = True
                        yield ev
                if not self.done and time.monotonic() > deadline:
                    raise TimeoutError(
                        f"supervisor stream {self.req_id} incomplete "
                        f"after {timeout}s")
            except TimeoutError:
                raise
            except RuntimeError:
                # the router serving us died (RouterCrashed via the
                # inner stream, or its failover path found the router
                # halted) -> retry on the promoted standby.  A router
                # that is alive and still active re-raises: that is a
                # terminal stream failure, not a takeover.
                if self._router is not None and (
                        self._router._crashed
                        or sup.active is not self._router):
                    self._router = None
                    continue
                raise
        sup._stream_done(self)

    def result(self, timeout=120.0):
        out = [{"tokens": [], "finish_reason": None}
               for _ in range(self.n)]
        for ev in self.events(timeout=timeout):
            if ev["type"] == "token":
                out[ev["index"]]["tokens"].append(ev["token"])
            elif ev["type"] == "finish":
                out[ev["index"]]["finish_reason"] = ev["reason"]
        return out


class RouterSupervisor:
    """Primary + warm standby for the routing tier itself.

    The PRIMARY router serves and journals; the WARM STANDBY is a
    constructed (unstarted, state-cold) router over the same fleet.
    On primary death (:meth:`kill_active`, or the ``router_crash``
    chaos point firing inside a stream), the dead router's client
    connections are torn down the way a dead process's would be, and
    the first caller to need a router promotes the standby:
    ``adopt_journal`` (affinity/ownership/breakers/orphans) +
    ``sweep_health`` (liveness/loads) + ``release_orphans``.
    Promotion is idempotent under the supervisor lock; the
    ``standby_takeover_race`` point drives a concurrent attempt
    through the guard.  Presents the front-end surface
    (``submit``/``cancel``/``health``/``prometheus``/``drain``), so a
    ``ServingServer`` can front a supervised fleet unchanged."""

    def __init__(self, replicas, *, journal_path, router_cls=None,
                 chaos=None, seed=None, **router_kw):
        self.router_cls = router_cls or ServingRouter
        self.router_kw = dict(router_kw)
        if isinstance(chaos, ChaosInjector):
            self.chaos = chaos
        else:
            assert chaos is None or isinstance(chaos, ChaosConfig)
            self.chaos = ChaosInjector(chaos, name="supervisor")
        self.journal = RouterJournal(
            journal_path,
            chaos=ChaosInjector(self.chaos._config, name="journal"))
        self.active = self.router_cls(replicas, journal=self.journal,
                                      **self.router_kw)
        self._standby = self._make_standby()
        self._lock = threading.Lock()
        self._ids = iter(range(1 << 60))
        self._streams: dict[int, SupervisorStream] = {}
        self._seed_rng = np.random.default_rng(seed)
        self.epoch = 0
        self.takeovers = 0
        self.takeover_s = None      # duration of the last promotion
        self._started = False

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if not self._started:
            self.active.start()
            self._started = True
        return self

    def drain(self, timeout=120.0):
        return self.active.drain(timeout)

    def close(self, timeout=120.0):
        ok = self.active.close(timeout)
        self.journal.close()
        return ok

    # -- the crash drill ---------------------------------------------------
    def kill_active(self, cause="kill_active"):
        """Crash the active router: halt it (prober stopped, submits
        refused), bump the epoch, and tear down its client connections
        — in-process inner streams get an error event (their consumers
        wake with ``RouterCrashed``), HTTP inner sockets close (the
        remote's disconnect-cancel frees the pages), and in-process
        replica-side requests are cancelled (the disconnect-cancel
        analog).  Held pages a teardown cannot reach fall to the
        deadline-expiry sweep.  Promotion itself is LAZY — the next
        caller that needs a router performs it — which is exactly the
        cold-standby shape: the standby does nothing until traffic
        arrives."""
        with self._lock:
            dead = self.active
            if dead is None or dead._crashed:
                return False
            dead.halt()
            self.epoch += 1
        _log.warning(json.dumps({"event": "router_crashed",
                                 "epoch": self.epoch, "cause": cause}))
        for stream in list(dead._streams.values()):
            inner = stream._inner
            if inner is None:
                continue
            try:
                if hasattr(inner, "_fail"):
                    inner._fail(RouterCrashed(
                        f"router crashed ({cause})"))
                else:
                    inner.close()
            except Exception:
                pass
            idx = stream.replica_idx
            try:
                if idx is not None and hasattr(dead.replicas[idx],
                                               "frontend"):
                    dead.replicas[idx].cancel_stream(inner)
            except Exception:
                pass
        return True

    def _ensure_active(self):
        """The takeover: promote the warm standby if the active router
        crashed.  Idempotent — concurrent callers serialize on the
        lock and late ones see the promotion already done (the
        ``standby_takeover_race`` chaos point drives a second attempt
        through that guard for real)."""
        race = False
        with self._lock:
            act = self.active
            if not act._crashed:
                return act
            t0 = time.perf_counter()
            standby = self._standby
            if standby is None \
                    or len(standby.replicas) != len(act.replicas):
                # the fleet grew/shrank under the old primary: the
                # pre-built standby is stale — rebuild from the dead
                # router's (authoritative) replica list
                standby = self._make_standby(act)
            race = self.chaos.fire("standby_takeover_race")
            standby.adopt_journal(self.journal)
            standby.sweep_health()
            standby.start()
            orphans = standby.release_orphans()
            self.active = standby
            self._standby = None
            self.takeovers += 1
            self.takeover_s = time.perf_counter() - t0
            _log.warning(json.dumps({
                "event": "router_takeover", "epoch": self.epoch,
                "takeover_s": round(self.takeover_s, 4),
                "orphans": orphans,
                "journal": self.journal.stats()}))
        if race:
            # a concurrent promotion attempt MUST no-op: it serializes
            # on the lock and finds the new active healthy
            t = threading.Thread(target=self._ensure_active)
            t.start()
            t.join()
        with self._lock:
            if self._standby is None:
                self._standby = self._make_standby()
        return self.active

    def _make_standby(self, source=None):
        src = source or self.active
        kw = dict(self.router_kw)
        # the standby shares the fleet (replica objects) but none of
        # the routing state: that arrives via journal replay + sweep
        return self.router_cls(list(src.replicas), **kw)

    # -- front-end surface -------------------------------------------------
    def submit(self, prompt, max_new_tokens=16, **kw):
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if kw.get("do_sample") and kw.get("seed") is None:
            # the seed must OUTLIVE any one router: a takeover
            # resubmission is token-exact only if it rides along
            kw["seed"] = int(self._seed_rng.integers(1, 2 ** 31 - 1))
        kw["max_new_tokens"] = int(max_new_tokens)
        stream = SupervisorStream(self, next(self._ids), prompt, kw,
                                  n=int(kw.get("n", 1)))
        with self._lock:
            self._streams[stream.req_id] = stream
        return stream

    def cancel(self, req_id):
        with self._lock:
            stream = self._streams.pop(req_id, None)
        if stream is None or stream._rs is None or stream._router is None:
            return False
        return bool(stream._router.cancel(stream._rs.req_id))

    def _stream_done(self, stream):
        with self._lock:
            self._streams.pop(stream.req_id, None)

    def health(self):
        h = self.active.health()
        h.update(epoch=self.epoch, takeovers=self.takeovers,
                 takeover_s=self.takeover_s,
                 journal=self.journal.stats())
        return h

    def prometheus(self):
        text = self.active.prometheus()
        pre = "paddle_tpu_serving_supervisor"
        lines = [f"# TYPE {pre}_takeovers_total counter",
                 f"{pre}_takeovers_total {self.takeovers}",
                 f"# TYPE {pre}_epoch gauge",
                 f"{pre}_epoch {self.epoch}",
                 f"# TYPE {pre}_journal_torn_skipped_total counter",
                 f"{pre}_journal_torn_skipped_total "
                 f"{self.journal.torn_skipped}"]
        return text + "\n".join(lines) + "\n"

    def debug_trace(self, request_id=None, req_id=None):
        return self.active.debug_trace(request_id=request_id,
                                       req_id=req_id)

    def debug_flight(self):
        return self.active.debug_flight()

    @property
    def state(self):
        return self.active.state
