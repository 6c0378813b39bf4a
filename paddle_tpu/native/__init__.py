"""Native (C++) runtime components + ctypes bindings.

Reference parity: the native runtime around the compute path — TCPStore
rendezvous (paddle/fluid/distributed/store/) and DataLoader worker core
(SURVEY.md §2.1/§2.2) — re-designed in compact C++17, built on demand with
g++ (no pybind11 in this image; bindings are ctypes over a C ABI).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
import time as _time

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_LOCK = threading.Lock()


def _build(src: str, out: str, *, shared=True, extra_flags=()) -> str:
    """Compile `src` to `out` on demand, keyed by source CONTENT hash.

    Binaries are machine/ABI-specific and never checked in (.gitignore);
    an mtime check would trust a stale artifact after a fresh checkout
    (git resets mtimes), so the rebuild key is a sha256 of the source +
    flags, stored in a sidecar `.stamp` file next to the binary.
    """
    import fcntl
    src_path = os.path.join(_DIR, src)
    out_path = os.path.join(_DIR, out)
    stamp_path = out_path + ".stamp"
    with open(src_path, "rb") as f:
        digest = hashlib.sha256(
            f.read() + repr(sorted(extra_flags)).encode()).hexdigest()
    # _BUILD_LOCK serializes threads; the fcntl lock serializes PROCESSES
    # (multi-controller workers all import native on startup and would
    # otherwise race g++ writing the same .so in place).
    with _BUILD_LOCK, open(out_path + ".lock", "w") as lockf:
        fcntl.flock(lockf, fcntl.LOCK_EX)
        stale = not os.path.exists(out_path)
        if not stale:
            try:
                with open(stamp_path) as f:
                    stale = f.read().strip() != digest
            except OSError:
                stale = True
        if stale:
            tmp_path = f"{out_path}.tmp.{os.getpid()}"
            cmd = (["g++", "-O2", "-std=c++17"] +
                   (["-shared"] if shared else []) +
                   ["-fPIC", "-pthread"] + list(extra_flags) +
                   [src_path, "-o", tmp_path])
            r = subprocess.run(cmd, capture_output=True, text=True)
            if r.returncode != 0:
                raise RuntimeError(f"native build of {src} failed:\n"
                                   f"{r.stderr}")
            os.replace(tmp_path, out_path)  # atomic: no half-written dlopen
            with open(stamp_path, "w") as f:
                f.write(digest)
    return out_path


def _load(src, out):
    return ctypes.CDLL(_build(src, out))


# --------------------------------------------------------------------------
# TCPStore


class TCPStore:
    """Reference parity: paddle.distributed's TCPStore rendezvous KV.

    is_master=True starts the in-process master daemon; every instance is
    also a client. Values are bytes; `add` is an atomic int64 counter —
    the primitive barrier/rendezvous building block.
    """

    _lib = None

    @classmethod
    def lib(cls):
        if cls._lib is None:
            lib = _load("tcp_store.cpp", "libpd_store.so")
            lib.pd_store_server_start.restype = ctypes.c_void_p
            lib.pd_store_server_start.argtypes = [ctypes.c_int]
            lib.pd_store_server_stop.argtypes = [ctypes.c_void_p]
            lib.pd_store_client_new.restype = ctypes.c_void_p
            lib.pd_store_client_new.argtypes = [ctypes.c_char_p,
                                                ctypes.c_int]
            lib.pd_store_client_free.argtypes = [ctypes.c_void_p]
            lib.pd_store_set.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                         ctypes.c_char_p, ctypes.c_int]
            lib.pd_store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
            lib.pd_store_wait.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
            lib.pd_store_keys.argtypes = [ctypes.c_void_p]
            lib.pd_store_keys_prefix.argtypes = [ctypes.c_void_p,
                                                 ctypes.c_char_p]
            lib.pd_store_fetch.argtypes = [ctypes.c_void_p,
                                           ctypes.c_char_p, ctypes.c_int]
            lib.pd_store_delete.argtypes = [ctypes.c_void_p,
                                            ctypes.c_char_p]
            lib.pd_store_add.restype = ctypes.c_longlong
            lib.pd_store_add.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                         ctypes.c_longlong]
            cls._lib = lib
        return cls._lib

    def __init__(self, host="127.0.0.1", port=23457, is_master=False,
                 world_size=1, timeout=None):
        lib = self.lib()
        self._server = None
        if is_master:
            self._server = lib.pd_store_server_start(port)
            if not self._server:
                raise RuntimeError(f"TCPStore master failed to bind :{port}")
        # Non-master workers may race the master's bind: retry until the
        # timeout (reference TCPStore clients block on connect the same way).
        deadline = _time.monotonic() + (120.0 if timeout is None
                                        else timeout)
        self._client = lib.pd_store_client_new(host.encode(), port)
        while not self._client and _time.monotonic() < deadline:
            _time.sleep(0.1)
            self._client = lib.pd_store_client_new(host.encode(), port)
        if not self._client:
            if self._server:
                lib.pd_store_server_stop(self._server)
            raise RuntimeError(f"TCPStore cannot connect {host}:{port}")

    def set(self, key: str, value: bytes):
        if isinstance(value, str):
            value = value.encode()
        rc = self.lib().pd_store_set(self._client, key.encode(), value,
                                     len(value))
        if rc != 0:
            raise RuntimeError("TCPStore.set failed")

    def _fetch(self, n: int) -> bytes:
        buf = ctypes.create_string_buffer(n)
        self.lib().pd_store_fetch(self._client, buf, n)
        return buf.raw[:n]

    def get(self, key: str) -> bytes:
        n = self.lib().pd_store_get(self._client, key.encode())
        if n == -1:
            raise KeyError(key)
        if n < 0:
            raise RuntimeError("TCPStore.get failed")
        return self._fetch(n)

    def wait(self, key: str) -> bytes:
        n = self.lib().pd_store_wait(self._client, key.encode())
        if n < 0:
            raise RuntimeError("TCPStore.wait failed")
        return self._fetch(n)

    def add(self, key: str, delta: int = 1) -> int:
        return int(self.lib().pd_store_add(self._client, key.encode(),
                                           delta))

    def delete(self, key: str):
        self.lib().pd_store_delete(self._client, key.encode())

    def keys(self, prefix: str = ""):
        """List keys; `prefix` filters SERVER-side (the elastic
        heartbeat scan stays O(matching keys), not O(total store))."""
        if prefix:
            n = self.lib().pd_store_keys_prefix(self._client,
                                                prefix.encode())
        else:
            n = self.lib().pd_store_keys(self._client)
        if n < 0:
            raise RuntimeError("TCPStore.keys failed")
        raw = self._fetch(n).decode()
        return [k for k in raw.split("\n") if k]

    def close(self):
        lib = self.lib()
        if self._client:
            lib.pd_store_client_free(self._client)
            self._client = None
        if self._server:
            lib.pd_store_server_stop(self._server)
            self._server = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# --------------------------------------------------------------------------
# Token loader


class TokenLoader:
    """C++ mmap+prefetch reader of flat token binaries → [B, S+1] int32
    batches (LLM pretraining input pipeline; see data_loader.cpp)."""

    _lib = None

    @classmethod
    def lib(cls):
        if cls._lib is None:
            lib = _load("data_loader.cpp", "libpd_loader.so")
            lib.pd_loader_new.restype = ctypes.c_void_p
            lib.pd_loader_new.argtypes = [
                ctypes.c_char_p, ctypes.c_longlong, ctypes.c_longlong,
                ctypes.c_int, ctypes.c_int, ctypes.c_ulonglong,
                ctypes.c_int]
            lib.pd_loader_num_windows.restype = ctypes.c_longlong
            lib.pd_loader_num_windows.argtypes = [ctypes.c_void_p]
            lib.pd_loader_next.argtypes = [
                ctypes.c_void_p,
                np.ctypeslib.ndpointer(dtype=np.int32, flags="C")]
            lib.pd_loader_free.argtypes = [ctypes.c_void_p]
            cls._lib = lib
        return cls._lib

    def __init__(self, path, seq_len, batch_size, num_workers=2,
                 prefetch=4, seed=0, dtype="uint16"):
        dtype_size = np.dtype(dtype).itemsize
        self.seq_len = int(seq_len)
        self.batch_size = int(batch_size)
        self._h = self.lib().pd_loader_new(
            str(path).encode(), seq_len, batch_size, num_workers, prefetch,
            seed, dtype_size)
        if not self._h:
            raise RuntimeError(f"TokenLoader cannot open {path}")

    @property
    def num_windows(self):
        return int(self.lib().pd_loader_num_windows(self._h))

    def next(self):
        out = np.empty((self.batch_size, self.seq_len + 1), np.int32)
        rc = self.lib().pd_loader_next(self._h, out)
        if rc != 0:
            raise StopIteration
        return out

    def __iter__(self):
        while True:
            yield self.next()

    def close(self):
        if self._h:
            self.lib().pd_loader_free(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


# --------------------------------------------------------------------------
# PJRT C++ inference runtime (native/pjrt_loader.cpp)


def _pjrt_include_dir():
    """The PJRT C API header ships with the tensorflow wheel in this
    image; the loader only needs pjrt_c_api.h (self-contained C)."""
    import glob
    import sysconfig
    for pat in [
        os.path.join(sysconfig.get_paths()["purelib"],
                     "tensorflow", "include"),
        "/opt/venv/lib/python3.12/site-packages/tensorflow/include",
    ]:
        for d in glob.glob(pat):
            if os.path.exists(os.path.join(d, "xla", "pjrt", "c",
                                           "pjrt_c_api.h")):
                return d
    raise RuntimeError("pjrt_c_api.h not found (tensorflow include dir)")


def _build_pjrt(binary=False):
    flags = ["-I", _pjrt_include_dir(), "-ldl"]
    if binary:
        flags.append("-DPD_PJRT_MAIN")
    return _build("pjrt_loader.cpp",
                  "pd_infer" if binary else "libpd_pjrt.so",
                  shared=not binary, extra_flags=flags)


def pd_infer_binary():
    """Build (if needed) and return the path of the pd_infer CLI."""
    return _build_pjrt(binary=True)


# dtype → code shared by the manifest writer (jit/save_load.py), the
# ctypes runner below, and the C++ enum switch in pjrt_loader.cpp.
PJRT_DTYPE_CODES = {"float32": 0, "bfloat16": 1, "int32": 2, "float16": 3,
                    "float64": 4, "int64": 5, "bool": 6, "int8": 7,
                    "uint8": 8}


class PjrtRunner:
    """C++ PJRT inference session (reference parity: the C++ side of
    jit.save/load + AnalysisPredictor; SURVEY.md §2.1 "C++ JIT").

    Compiles StableHLO bytecode on a PJRT plugin and executes it without
    jax in the loop — the same native runtime the `pd_infer` CLI uses.
    """

    _lib = None

    @classmethod
    def lib(cls):
        if cls._lib is None:
            lib = ctypes.CDLL(_build_pjrt())
            lib.pd_pjrt_create.restype = ctypes.c_void_p
            lib.pd_pjrt_create.argtypes = [ctypes.c_char_p,
                                           ctypes.c_char_p]
            lib.pd_pjrt_destroy.argtypes = [ctypes.c_void_p]
            lib.pd_pjrt_last_error.restype = ctypes.c_char_p
            lib.pd_pjrt_last_error.argtypes = [ctypes.c_void_p]
            lib.pd_pjrt_compile.restype = ctypes.c_void_p
            lib.pd_pjrt_compile.argtypes = [ctypes.c_void_p,
                                            ctypes.c_char_p,
                                            ctypes.c_size_t]
            lib.pd_pjrt_num_outputs.restype = ctypes.c_size_t
            lib.pd_pjrt_num_outputs.argtypes = [ctypes.c_void_p]
            lib.pd_pjrt_execute.restype = ctypes.c_void_p
            lib.pd_pjrt_execute.argtypes = [
                ctypes.c_void_p, ctypes.c_size_t,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_void_p)]
            lib.pd_pjrt_output_size.restype = ctypes.c_int64
            lib.pd_pjrt_output_size.argtypes = [ctypes.c_void_p,
                                                ctypes.c_size_t]
            lib.pd_pjrt_output_copy.restype = ctypes.c_int
            lib.pd_pjrt_output_copy.argtypes = [ctypes.c_void_p,
                                                ctypes.c_size_t,
                                                ctypes.c_void_p,
                                                ctypes.c_size_t]
            lib.pd_pjrt_result_destroy.argtypes = [ctypes.c_void_p]
            lib.pd_pjrt_exec_destroy.argtypes = [ctypes.c_void_p]
            cls._lib = lib
        return cls._lib

    def __init__(self, plugin_path, options=None):
        """options: dict of plugin create options (ints or strings);
        libtpu needs none."""
        spec = None
        if options:
            spec = ";".join(f"{k}={v}" for k, v in options.items()).encode()
        self._ctx = self.lib().pd_pjrt_create(str(plugin_path).encode(),
                                              spec)
        if not self._ctx:
            raise RuntimeError(f"PJRT plugin init failed: {plugin_path}")
        self._exec = None

    def _err(self):
        return self.lib().pd_pjrt_last_error(self._ctx).decode()

    def compile(self, stablehlo_bytes: bytes):
        e = self.lib().pd_pjrt_compile(self._ctx, stablehlo_bytes,
                                       len(stablehlo_bytes))
        if not e:
            raise RuntimeError(f"PJRT compile failed: {self._err()}")
        self._exec = e
        return self

    def run(self, arrays):
        """Execute with host numpy arrays; returns list of raw byte
        buffers (one per output — caller reshapes/casts)."""
        assert self._exec, "compile() first"
        lib = self.lib()
        n = len(arrays)
        arrays = [np.ascontiguousarray(a) for a in arrays]
        dtypes = (ctypes.c_int * n)(*[
            PJRT_DTYPE_CODES[str(a.dtype)] for a in arrays])
        ranks = (ctypes.c_int * n)(*[a.ndim for a in arrays])
        dims_flat = []
        for a in arrays:
            dims_flat += list(a.shape)
        dims = (ctypes.c_int64 * len(dims_flat))(*dims_flat)
        ptrs = (ctypes.c_void_p * n)(*[
            a.ctypes.data_as(ctypes.c_void_p).value for a in arrays])
        res = lib.pd_pjrt_execute(self._exec, n, dtypes, ranks, dims, ptrs)
        if not res:
            raise RuntimeError(f"PJRT execute failed: {self._err()}")
        outs = []
        try:
            for i in range(lib.pd_pjrt_num_outputs(self._exec)):
                sz = lib.pd_pjrt_output_size(res, i)
                if sz < 0:
                    raise RuntimeError(self._err())
                buf = ctypes.create_string_buffer(int(sz))
                if lib.pd_pjrt_output_copy(res, i, buf, int(sz)) != 0:
                    raise RuntimeError(self._err())
                outs.append(bytes(buf.raw))
        finally:
            lib.pd_pjrt_result_destroy(res)
        return outs

    def close(self):
        if self._exec:
            self.lib().pd_pjrt_exec_destroy(self._exec)
            self._exec = None
        if self._ctx:
            self.lib().pd_pjrt_destroy(self._ctx)
            self._ctx = None
