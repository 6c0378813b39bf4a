"""paddle_tpu.io — datasets and DataLoader.

Reference parity: paddle.io (upstream python/paddle/io/ — unverified, see
SURVEY.md §2.2): Dataset/IterableDataset/TensorDataset, samplers,
DistributedBatchSampler, DataLoader with worker prefetch.

TPU-native design: workers are background threads feeding a bounded queue
(numpy batches stay on host; device transfer happens at dequeue). Thread
workers sidestep fork-vs-PJRT hazards that process workers would hit, and
host→HBM transfer overlaps compute because jax transfers are async.
num_workers>0 enables the prefetch pipeline; 0 = synchronous iteration.
"""
from __future__ import annotations

import itertools
import os
import queue as _queue
import threading

import numpy as np

from ..core.random import next_key
from ..core.tensor import Tensor, to_tensor

__all__ = ["Dataset", "IterableDataset", "TensorDataset", "ComposeDataset",
           "ChainDataset", "ConcatDataset", "Subset", "random_split",
           "Sampler",
           "SequenceSampler", "RandomSampler", "BatchSampler",
           "DistributedBatchSampler", "WeightedRandomSampler", "DataLoader",
           "get_worker_info", "default_collate_fn",
           "default_convert_fn"]


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset is not indexable")

    def __len__(self):
        raise RuntimeError("IterableDataset has no len()")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __len__(self):
        return min(len(d) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            item = d[idx]
            out.extend(item if isinstance(item, tuple) else (item,))
        return tuple(out)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)

    def __iter__(self):
        for d in self.datasets:
            yield from d


class ConcatDataset(Dataset):
    """Concatenation of map-style datasets: index i addresses the
    dataset whose cumulative-length bucket contains i (reference
    paddle.io.ConcatDataset; path unverified — mount empty)."""

    def __init__(self, datasets):
        self.datasets = list(datasets)
        if not self.datasets:
            raise ValueError("datasets should not be an empty iterable")
        for d in self.datasets:
            if isinstance(d, IterableDataset):
                raise TypeError(
                    "ConcatDataset does not support IterableDataset")
        self.cumulative_sizes = list(
            np.cumsum([len(d) for d in self.datasets]))

    def __len__(self):
        return int(self.cumulative_sizes[-1])

    def __getitem__(self, idx):
        n = len(self)
        if idx < 0:
            if idx < -n:
                raise IndexError("index out of range")
            idx += n
        elif idx >= n:
            raise IndexError("index out of range")
        di = int(np.searchsorted(self.cumulative_sizes, idx, side="right"))
        prev = self.cumulative_sizes[di - 1] if di > 0 else 0
        return self.datasets[di][idx - int(prev)]


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    import jax.random as jrandom
    total = len(dataset)
    if sum(lengths) != total:
        # fractional lengths
        if all(0 < l < 1 for l in lengths):
            lengths = [int(l * total) for l in lengths]
            lengths[-1] = total - sum(lengths[:-1])
        else:
            raise ValueError("sum of lengths != dataset size")
    perm = np.asarray(jrandom.permutation(next_key(), total))
    out, off = [], 0
    for l in lengths:
        out.append(Subset(dataset, perm[off:off + l].tolist()))
        off += l
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError

    def __len__(self):
        return len(self.data_source)


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self._num_samples = num_samples

    @property
    def num_samples(self):
        return self._num_samples or len(self.data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            import jax.random as jrandom
            idx = np.asarray(jrandom.randint(next_key(),
                                             (self.num_samples,), 0, n))
            return iter(idx.tolist())
        import jax.random as jrandom
        perm = np.asarray(jrandom.permutation(next_key(), n))
        return iter(perm[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, dtype=np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        rng = np.random.default_rng(
            int(np.asarray(next_key())[-1]) & 0x7FFFFFFF)
        idx = rng.choice(len(self.weights), size=self.num_samples,
                         replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """Shards the index space across data-parallel ranks.

    Reference parity: paddle.io.DistributedBatchSampler. Under SPMD the
    "rank" is the dp mesh coordinate (see paddle_tpu.distributed).
    """

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        from ..distributed import env as dist_env
        self.dataset = dataset
        self.batch_size = batch_size
        self.nranks = num_replicas if num_replicas is not None \
            else dist_env.get_world_size()
        self.local_rank = rank if rank is not None else dist_env.get_rank()
        self.shuffle = shuffle
        self.drop_last = drop_last
        self.epoch = 0
        self.num_samples = int(np.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.default_rng(self.epoch)
            indices = rng.permutation(n).tolist()
        else:
            indices = list(range(n))
        indices += indices[: self.total_size - n]
        local = indices[self.local_rank:self.total_size:self.nranks]
        batch = []
        for idx in local:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size


class _WorkerInfo:
    def __init__(self, id_, num_workers, dataset):
        self.id = id_
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = threading.local()


def get_worker_info():
    return getattr(_worker_info, "info", None)


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, Tensor):
        import paddle_tpu as P
        return P.stack(batch, axis=0)
    if isinstance(sample, np.ndarray):
        return to_tensor(np.stack(batch, axis=0))
    if isinstance(sample, (int, float, np.integer, np.floating)):
        return to_tensor(np.asarray(batch))
    if isinstance(sample, (list, tuple)):
        return tuple(default_collate_fn([b[i] for b in batch])
                     for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    return batch


# ---------------------------------------------------------------------------
# process workers (reference: DataLoader num_workers subprocesses +
# use_shared_memory — upstream python/paddle/io/dataloader/worker.py,
# unverified; see SURVEY.md §2.2 Data). Workers parallelize the
# Python-heavy dataset[i] transforms across real processes (no GIL);
# numpy payloads ride a shared-memory segment per batch, pickles only
# carry descriptors. Collation and the jax device put stay in the parent
# — forked children never touch the accelerator runtime.

def _shm_pack(samples, seg_name=None):
    """Replace ndarray leaves with shm descriptors; returns (spec, shm_name)
    or (samples, None) when nothing is packable. `seg_name` gives the
    segment a loader-scoped deterministic name so the parent can sweep
    leftovers even when a terminate() loses the queue descriptor."""
    from multiprocessing import shared_memory

    arrays = []

    def scan(o):
        if isinstance(o, Tensor):
            o = np.asarray(o._data)
        if isinstance(o, np.ndarray) and o.nbytes > 0:
            arrays.append(np.ascontiguousarray(o))
            return ("A", len(arrays) - 1, o.shape, str(o.dtype))
        if isinstance(o, (list, tuple)):
            return (type(o).__name__, [scan(x) for x in o])
        if isinstance(o, dict):
            return ("dict", [(k, scan(v)) for k, v in o.items()])
        return ("S", o)

    spec = [scan(s) for s in samples]
    if not arrays:
        return samples, None, None
    offsets = []
    total = 0
    for a in arrays:
        offsets.append(total)
        total += a.nbytes
    shm = shared_memory.SharedMemory(create=True, size=max(total, 1),
                                     name=seg_name)
    for a, off in zip(arrays, offsets):
        # write straight into the segment — tobytes() would materialize a
        # second full copy of every batch in the worker's hot path
        view = np.frombuffer(shm.buf, dtype=a.dtype, count=a.size,
                             offset=off).reshape(a.shape)
        np.copyto(view, a)
        del view
    name = shm.name
    # the PARENT owns the segment's lifetime (it unlinks after reading);
    # unregister from this process's resource_tracker so worker exit
    # doesn't whine about (or destroy) a segment it no longer owns
    try:
        from multiprocessing import resource_tracker
        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:
        pass
    shm.close()
    return spec, name, offsets


def _shm_unpack(spec, shm_name, offsets):
    from multiprocessing import shared_memory
    if shm_name is None:
        return spec
    shm = shared_memory.SharedMemory(name=shm_name)
    try:
        def un(s):
            tag = s[0]
            if tag == "A":
                _, idx, shape, dtype = s
                n = int(np.prod(shape)) * np.dtype(dtype).itemsize
                off = offsets[idx]
                return np.frombuffer(
                    bytes(shm.buf[off:off + n]), dtype=dtype).reshape(shape)
            if tag == "S":
                return s[1]
            if tag == "dict":
                return {k: un(v) for k, v in s[1]}
            seq = [un(x) for x in s[1]]
            return tuple(seq) if tag == "tuple" else seq

        return [un(s) for s in spec]
    finally:
        shm.close()
        try:
            shm.unlink()
        except Exception:
            pass


def _process_worker(wid, num_workers, dataset, index_q, result_q,
                    worker_init_fn, use_shm, shm_token=None):
    _worker_info.info = _WorkerInfo(wid, num_workers, dataset)
    if worker_init_fn:
        worker_init_fn(wid)
    seq = 0
    while True:
        item = index_q.get()
        if item is None:
            return
        i, indices = item
        try:
            samples = [dataset[j] for j in indices]
            if use_shm:
                seg = f"{shm_token}_{wid}_{seq}" if shm_token else None
                seq += 1
                spec, name, offsets = _shm_pack(samples, seg)
                result_q.put((i, "shm" if name else "raw",
                              (spec, name, offsets) if name else samples))
            else:
                result_q.put((i, "raw", samples))
        except Exception as e:  # surface dataset errors to the parent
            result_q.put((i, "err", f"{type(e).__name__}: {e}"))


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self._user_collate = collate_fn is not None
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = max(prefetch_factor, 2)
        self.worker_init_fn = worker_init_fn
        self.use_shared_memory = use_shared_memory
        self._iterable = isinstance(dataset, IterableDataset)
        if self._iterable:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            if batch_size is None:
                self.batch_sampler = None
                self.batch_size = None
            else:
                self.batch_sampler = BatchSampler(
                    dataset, shuffle=shuffle, batch_size=batch_size,
                    drop_last=drop_last)

    def __len__(self):
        if self._iterable:
            raise TypeError("IterableDataset DataLoader has no len()")
        if self.batch_sampler is None:
            return len(self.dataset)
        return len(self.batch_sampler)

    def _fetch(self, indices):
        return self.collate_fn([self.dataset[i] for i in indices])

    def _iter_sync(self):
        if self._iterable:
            if self.batch_size is None:
                # unbatched passthrough (same semantics as map-style)
                for item in self.dataset:
                    yield self.collate_fn(item) if self._user_collate \
                        else default_convert_fn(item)
                return
            batch = []
            for item in self.dataset:
                batch.append(item)
                if len(batch) == self.batch_size:
                    yield self.collate_fn(batch)
                    batch = []
            if batch and not self.drop_last:
                yield self.collate_fn(batch)
            return
        if self.batch_sampler is None:
            # batch_size=None (map-style): samples pass through UNBATCHED
            # — default_convert_fn adds no leading dim (reference
            # semantics); a user collate_fn receives the raw sample
            for i in range(len(self.dataset)):
                sample = self.dataset[i]
                yield self.collate_fn(sample) if self._user_collate \
                    else default_convert_fn(sample)
            return
        for indices in self.batch_sampler:
            yield self._fetch(indices)

    def _iter_prefetch(self):
        """Thread pool keeps `num_workers * prefetch_factor` batches ready."""
        q: _queue.Queue = _queue.Queue(
            maxsize=self.num_workers * self.prefetch_factor)
        sentinel = object()

        if self._iterable:
            def producer():
                _worker_info.info = _WorkerInfo(0, 1, self.dataset)
                if self.worker_init_fn:
                    self.worker_init_fn(0)
                try:
                    for b in self._iter_sync():
                        q.put(b)
                finally:
                    q.put(sentinel)
            t = threading.Thread(target=producer, daemon=True)
            t.start()
            while True:
                item = q.get()
                if item is sentinel:
                    break
                yield item
            return

        index_q: _queue.Queue = _queue.Queue()
        batches = list(self.batch_sampler)
        for i, b in enumerate(batches):
            index_q.put((i, b))
        results: dict[int, object] = {}
        lock = threading.Lock()
        n_done = [0]
        cond = threading.Condition(lock)

        def worker(wid):
            _worker_info.info = _WorkerInfo(wid, self.num_workers,
                                            self.dataset)
            if self.worker_init_fn:
                self.worker_init_fn(wid)
            while True:
                try:
                    i, indices = index_q.get_nowait()
                except _queue.Empty:
                    return
                data = self._fetch(indices)
                with cond:
                    results[i] = data
                    cond.notify_all()

        threads = [threading.Thread(target=worker, args=(w,), daemon=True)
                   for w in range(self.num_workers)]
        for t in threads:
            t.start()
        for i in range(len(batches)):
            with cond:
                while i not in results:
                    cond.wait(timeout=60.0)
            yield results.pop(i)

    def _iter_procs(self):
        """Real subprocess workers (fork): dataset[i] runs GIL-free in
        parallel; batches return via shared memory; parent collates."""
        import multiprocessing as mp
        import uuid

        ctx = mp.get_context("fork")
        batches = list(self.batch_sampler)
        shm_token = f"pdtpu{os.getpid()}_{uuid.uuid4().hex[:8]}" \
            if self.use_shared_memory else None
        index_q = ctx.Queue()
        result_q = ctx.Queue(
            maxsize=max(self.num_workers * self.prefetch_factor, 2))
        for item in enumerate(batches):
            index_q.put(item)
        for _ in range(self.num_workers):
            index_q.put(None)
        procs = [ctx.Process(
            target=_process_worker,
            args=(w, self.num_workers, self.dataset, index_q, result_q,
                  self.worker_init_fn, self.use_shared_memory, shm_token),
            daemon=True) for w in range(self.num_workers)]
        for p in procs:
            p.start()
        results: dict[int, object] = {}
        try:
            for want in range(len(batches)):
                while want not in results:
                    try:
                        i, kind, payload = result_q.get(timeout=120.0)
                    except _queue.Empty:
                        dead = [p.exitcode for p in procs
                                if p.exitcode not in (None, 0)]
                        if not dead:
                            continue  # slow dataset, workers healthy
                        raise RuntimeError(
                            f"DataLoader worker(s) died (exitcodes "
                            f"{dead})") from None
                    if kind == "err":
                        raise RuntimeError(
                            f"DataLoader worker failed on batch {i}: "
                            f"{payload}")
                    if kind == "shm":
                        spec, name, offsets = payload
                        results[i] = _shm_unpack(spec, name, offsets)
                    else:
                        results[i] = payload
                yield self.collate_fn(results.pop(want))
            # every batch is delivered and every worker has its None in
            # the queue: let each run to it, so that one the others
            # outran still runs worker_init_fn (the reference runs it in
            # every worker it starts) before it leaves
            for p in procs:
                p.join(timeout=5)
        finally:
            for p in procs:
                if p.is_alive():
                    p.terminate()
            for p in procs:
                p.join(timeout=5)
                if p.is_alive():
                    # under load a worker can outlive SIGTERM's 5 s: one
                    # that still lives would make a segment AFTER the sweep
                    # below, and nothing would ever unlink it
                    p.kill()
                    p.join(timeout=30)
            # release undelivered shm segments — the workers unregistered
            # them from their resource_tracker, so nothing else will ever
            # unlink a leaked one (early break / error / a terminate()
            # that loses a queue descriptor would fill /dev/shm across
            # epochs). Loader-scoped names make leftovers discoverable
            # even when the descriptor never reached the queue.
            from multiprocessing import shared_memory
            while True:
                try:
                    _, kind, payload = result_q.get_nowait()
                except (_queue.Empty, OSError, ValueError):
                    break
                if kind == "shm":
                    try:
                        seg = shared_memory.SharedMemory(name=payload[1])
                        seg.close()
                        seg.unlink()
                    except Exception:
                        pass
            if shm_token is not None:
                import glob as _glob
                for path in _glob.glob(f"/dev/shm/{shm_token}_*"):
                    try:
                        seg = shared_memory.SharedMemory(
                            name=os.path.basename(path))
                        seg.close()
                        seg.unlink()
                    except Exception:
                        pass
            index_q.close()
            result_q.close()

    def __iter__(self):
        if not self._iterable and self.batch_sampler is None:
            # batch_size=None: unbatched passthrough is host-trivial —
            # worker pools iterate self.batch_sampler and would crash
            if self.num_workers and self.num_workers > 0:
                import warnings as _warnings
                _warnings.warn(
                    "DataLoader(batch_size=None) iterates synchronously; "
                    f"num_workers={self.num_workers} is ignored on the "
                    "unbatched passthrough path")
            return self._iter_sync()
        if self.num_workers and self.num_workers > 0:
            import multiprocessing as mp
            if not self._iterable and self.batch_sampler is not None \
                    and "fork" in mp.get_all_start_methods():
                return self._iter_procs()
            # IterableDataset (single stream) or no fork (non-Linux):
            # threaded prefetch fallback
            return self._iter_prefetch()
        return self._iter_sync()


class SubsetRandomSampler(Sampler):
    """Sample the given indices in random order (reference parity)."""

    def __init__(self, indices):
        self.indices = list(indices)

    def __iter__(self):
        import random as _random_mod
        order = list(self.indices)
        _random_mod.shuffle(order)
        return iter(order)

    def __len__(self):
        return len(self.indices)


def default_convert_fn(batch):
    """Reference parity: paddle.io.dataloader.collate.default_convert_fn
    — convert a SINGLE sample's leaves to Tensors without adding a batch
    dim (the batch_size=None passthrough path)."""
    import numpy as _np
    import jax.numpy as _jnp
    from ..core.tensor import Tensor as _T
    if isinstance(batch, _T):
        return batch
    if isinstance(batch, (_np.ndarray, _np.generic)):
        return _T(_jnp.asarray(batch))
    if isinstance(batch, (int, float)):
        return _T(_jnp.asarray(batch))
    if isinstance(batch, tuple) and hasattr(batch, "_fields"):
        return type(batch)(*(default_convert_fn(b) for b in batch))
    if isinstance(batch, (list, tuple)):
        return type(batch)(default_convert_fn(b) for b in batch)
    if isinstance(batch, dict):
        return {k: default_convert_fn(v) for k, v in batch.items()}
    return batch
