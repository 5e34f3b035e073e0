"""paddle_tpu.io — Dataset / DataLoader / samplers.

Parity: `python/paddle/io/` over the reference's reader stack
(`python/paddle/fluid/reader.py:275 DataLoader`,
`fluid/dataloader/` workers, C++ shared-mem plumbing
`imperative/data_loader.cc`, `memory/allocation/mmap_allocator`).

TPU-native: the loader is a host-side prefetching iterator (threads, not
forked workers — jax arrays transfer via device_put on the producer side);
the out-of-core `InMemoryDataset`/DataFeed capability for PS training lives
in paddle_tpu/ps/ (native engine).
"""
from __future__ import annotations

import itertools
import math
import queue
import threading

import numpy as np

from ..core.tensor import Tensor


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise RuntimeError("IterableDataset has no __getitem__")

    def __len__(self):
        raise RuntimeError("IterableDataset has no __len__")


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = [t if isinstance(t, Tensor) else Tensor(t)
                        for t in tensors]
        n = self.tensors[0].shape[0]
        assert all(t.shape[0] == n for t in self.tensors)

    def __getitem__(self, idx):
        return tuple(t.numpy()[idx] for t in self.tensors)

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


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    total = len(dataset)
    if sum(lengths) != total:
        # paddle >= 2.5 allows fractions
        if all(0 < l < 1 for l in lengths):
            lengths = [int(math.floor(total * l)) for l in lengths]
            lengths[-1] = total - sum(lengths[:-1])
        else:
            raise ValueError("lengths must sum to dataset size")
    perm = np.random.permutation(total)
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


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))

    def __len__(self):
        return len(self.data_source)


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
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, dtype=np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(len(self.weights), self.num_samples,
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
    """Parity: `python/paddle/fluid/dataloader/batch_sampler.py`
    DistributedBatchSampler — shards the dataset across dp ranks."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.drop_last = drop_last
        self.shuffle = shuffle
        from ..parallel import env as dist_env
        self.nranks = num_replicas if num_replicas is not None else \
            dist_env.get_world_size()
        self.local_rank = rank if rank is not None else dist_env.get_rank()
        self.epoch = 0
        self.num_samples = int(math.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def __iter__(self):
        n = len(self.dataset)
        indices = np.arange(n).tolist()
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            indices = rng.permutation(n).tolist()
            self.epoch += 1
        indices += indices[:(self.total_size - len(indices))]
        indices = indices[self.local_rank:self.total_size:self.nranks]
        batch = []
        for idx in indices:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch):
        self.epoch = epoch


def _collate_numpy(batch):
    """Stack samples into batched numpy arrays. Pure host work: this is
    what a forked DataLoader worker runs, and a worker must never build
    a `Tensor` — that is a `jax.device_put`, and the chip belongs to
    the parent."""
    sample = batch[0]
    if isinstance(sample, (Tensor,)):
        return np.stack([s.numpy() for s in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float)):
        return np.asarray(batch)
    if isinstance(sample, (list, tuple)):
        return [_collate_numpy([s[i] for s in batch])
                for i in range(len(sample))]
    if isinstance(sample, dict):
        return {k: _collate_numpy([s[k] for s in batch])
                for k in sample}
    return batch


def _wrap_tensors(obj):
    """numpy -> Tensor; structure preserved (the parent-side half of
    the default collate)."""
    if isinstance(obj, np.ndarray):
        return Tensor(obj)
    if isinstance(obj, list):
        return [_wrap_tensors(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _wrap_tensors(v) for k, v in obj.items()}
    return obj


def default_collate_fn(batch):
    """Stack samples into batched numpy arrays (→ Tensors)."""
    return _wrap_tensors(_collate_numpy(batch))


# ---------------------------------------------------------------------
# multiprocess workers (reference reader.py:275 + mmap_allocator shared
# memory). Workers are forked processes pulling index batches from a
# queue; collated numpy arrays return via SharedMemory segments (large
# arrays bypass pickle — the mmap_allocator role) with an order-restoring
# reorder buffer in the parent.

_SHM_MIN_BYTES = 1 << 16


def _strip_tensors(obj):
    """Tensor -> numpy for IPC; structure (incl. tuple-ness) preserved."""
    if isinstance(obj, Tensor):
        return np.asarray(obj.numpy())
    if isinstance(obj, tuple):
        return tuple(_strip_tensors(o) for o in obj)
    if isinstance(obj, list):
        return [_strip_tensors(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _strip_tensors(v) for k, v in obj.items()}
    return obj


def _to_shm(obj, shms):
    """Replace big ndarrays with ('__shm__', name, shape, dtype)."""
    from multiprocessing import shared_memory
    if isinstance(obj, np.ndarray) and obj.nbytes >= _SHM_MIN_BYTES:
        shm = shared_memory.SharedMemory(create=True, size=obj.nbytes)
        # ownership transfers to the parent (which unlinks after copy-out)
        # — unregister from THIS process's resource tracker, or a worker
        # exiting before the parent attaches would unlink the segment
        # out from under it
        try:
            from multiprocessing import resource_tracker
            resource_tracker.unregister(shm._name, "shared_memory")
        except Exception:
            pass
        np.ndarray(obj.shape, obj.dtype, buffer=shm.buf)[...] = obj
        shms.append(shm)
        return ("__shm__", shm.name, obj.shape, str(obj.dtype))
    if isinstance(obj, tuple):
        # wrap user tuples so they can't collide with the shm marker
        return ("__tuple__", [_to_shm(o, shms) for o in obj])
    if isinstance(obj, list):
        return [_to_shm(o, shms) for o in obj]
    if isinstance(obj, dict):
        return {k: _to_shm(v, shms) for k, v in obj.items()}
    return obj


def _from_shm(obj):
    from multiprocessing import shared_memory
    if isinstance(obj, tuple) and len(obj) == 4 and obj[0] == "__shm__":
        _, name, shape, dtype = obj
        shm = shared_memory.SharedMemory(name=name)
        arr = np.array(np.ndarray(shape, dtype, buffer=shm.buf))
        shm.close()
        shm.unlink()
        return Tensor(arr)
    if isinstance(obj, tuple) and len(obj) == 2 and obj[0] == "__tuple__":
        return tuple(_from_shm(o) for o in obj[1])
    if isinstance(obj, np.ndarray):
        return Tensor(obj)
    if isinstance(obj, list):
        return [_from_shm(o) for o in obj]
    if isinstance(obj, dict):
        return {k: _from_shm(v) for k, v in obj.items()}
    return obj


def _release_shm(obj):
    """Unlink shm descriptors in an undelivered payload."""
    from multiprocessing import shared_memory
    if isinstance(obj, tuple) and len(obj) == 4 and obj[0] == "__shm__":
        try:
            shm = shared_memory.SharedMemory(name=obj[1])
            shm.close()
            shm.unlink()
        except (FileNotFoundError, OSError):
            pass
        return
    if isinstance(obj, (list, tuple)):
        for o in obj:
            _release_shm(o)
    elif isinstance(obj, dict):
        for o in obj.values():
            _release_shm(o)


def _mp_worker_loop(dataset, index_q, data_q, collate_fn,
                    use_shared_memory, worker_init_fn, worker_id):
    if worker_init_fn is not None:
        worker_init_fn(worker_id)
    while True:
        item = index_q.get()
        if item is None:
            return
        bid, idxs = item
        try:
            batch = collate_fn([dataset[i] for i in idxs])
            payload = _strip_tensors(batch)
            if use_shared_memory:
                shms = []
                payload = _to_shm(payload, shms)
                data_q.put((bid, payload, None))
                for shm in shms:
                    shm.close()  # parent owns unlink
            else:
                data_q.put((bid, payload, None))
        except Exception as e:  # propagate into the parent iterator
            data_q.put((bid, None, f"{type(e).__name__}: {e}"))


class DataLoader:
    """Parity: `python/paddle/fluid/reader.py:275`. num_workers=0 runs
    in-process (with thread prefetch when use_buffer_reader); num_workers
    > 0 forks worker processes that collate index batches and ship the
    arrays back through SharedMemory (the reference's multiprocess
    reader + mmap_allocator path). IterableDataset always runs
    in-process (worker sharding semantics are the map-style path's)."""

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.use_shared_memory = use_shared_memory
        self.worker_init_fn = worker_init_fn
        self.timeout = timeout
        self.prefetch = max(2, prefetch_factor * max(num_workers, 1))
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
            self.batch_size = batch_sampler.batch_size
        elif self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last)
            self.batch_size = batch_size

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset DataLoader has no len()")
        return len(self.batch_sampler)

    def _gen_batches(self):
        if self._iterable_mode:
            it = iter(self.dataset)
            while True:
                batch = list(itertools.islice(it, self.batch_size))
                if not batch:
                    return
                if len(batch) < self.batch_size and self.drop_last:
                    return
                yield self.collate_fn(batch)
        else:
            for idxs in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in idxs])

    def __iter__(self):
        if self.num_workers <= 0:
            yield from self._gen_batches()
            return
        if not self._iterable_mode:
            # fall back ONLY on setup failure — once batches have been
            # yielded, restarting on the thread path would silently
            # duplicate the epoch's data
            try:
                mp_iter = self._start_multiprocess()
            except (ImportError, OSError, ValueError) as e:
                import warnings
                warnings.warn(f"multiprocess DataLoader unavailable "
                              f"({e!r}); using thread prefetch")
            else:
                yield from mp_iter
                return
        q = queue.Queue(maxsize=self.prefetch)
        sentinel = object()

        def producer():
            try:
                for b in self._gen_batches():
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

    def _start_multiprocess(self):
        """Setup (may raise -> caller falls back), returning the draining
        generator."""
        import multiprocessing as mp

        ctx = mp.get_context("fork")
        # the default collate stays numpy in the worker; `_from_shm`
        # makes the Tensors here, in the process that owns the chip
        worker_collate = (_collate_numpy
                          if self.collate_fn is default_collate_fn
                          else self.collate_fn)
        index_q = ctx.Queue()
        data_q = ctx.Queue(maxsize=self.prefetch)
        workers = [
            ctx.Process(
                target=_mp_worker_loop,
                args=(self.dataset, index_q, data_q, worker_collate,
                      self.use_shared_memory, self.worker_init_fn, wid),
                daemon=True)
            for wid in range(self.num_workers)]
        for w in workers:
            w.start()
        n_batches = 0
        for bid, idxs in enumerate(self.batch_sampler):
            index_q.put((bid, list(idxs)))
            n_batches += 1
        for _ in workers:
            index_q.put(None)
        return self._drain_multiprocess(workers, data_q, n_batches)

    def _drain_multiprocess(self, workers, data_q, n_batches):
        reorder = {}
        try:
            next_bid = 0
            while next_bid < n_batches:
                while next_bid not in reorder:
                    bid, payload, err = data_q.get(
                        timeout=self.timeout or 120)
                    if err is not None:
                        raise RuntimeError(
                            f"DataLoader worker failed on batch {bid}: "
                            f"{err}")
                    reorder[bid] = payload
                yield _from_shm(reorder.pop(next_bid))
                next_bid += 1
        finally:
            for w in workers:
                if w.is_alive():
                    w.terminate()
            for w in workers:
                w.join(timeout=5)
            # unlink SharedMemory segments still queued or reordered —
            # on early break / worker error they would otherwise leak
            # in /dev/shm until interpreter exit
            import queue as _q
            while True:
                try:
                    _, payload, _err = data_q.get_nowait()
                except (_q.Empty, OSError):
                    break
                _release_shm(payload)
            for payload in reorder.values():
                _release_shm(payload)


def get_worker_info():
    return None


class DeviceCacheLoader:
    """Pin a (small) dataset's batches in device HBM after the first
    epoch — repeated epochs then feed with ZERO host->device transfers.

    The TPU-first input-pipeline pattern (tf.data `.cache()` on-device
    analogue): host->device bandwidth is often the fit-loop bottleneck
    for small models (not measured on the direct backend); datasets
    that fit in HBM (MNIST: ~13 MB) should live there. Wraps any
    iterable loader:

        loader = DeviceCacheLoader(DataLoader(ds, batch_size=64))
        model.fit(loader, ...)

    Caching is ALL-OR-NOTHING: if the first epoch exceeds `max_bytes`
    the cache is discarded (with a warning) and every epoch streams
    from the base loader — a partial cache over a shuffling base would
    silently bias sampling (cached prefix replayed + a differently-
    shuffled remainder). Cached epochs replay the first epoch's batches
    (re-shuffled at batch granularity when `reshuffle=True`); a
    per-sample re-shuffle would need fresh host batches and defeat the
    cache.
    """

    def __init__(self, loader, max_bytes=512 * 1024 * 1024,
                 reshuffle=True, seed=0):
        self._loader = loader
        self._max_bytes = max_bytes
        self._cache = None
        self._overflowed = False
        self._reshuffle = reshuffle
        self._epoch = 0
        self._seed = seed

    def __len__(self):
        return len(self._loader)

    def __iter__(self):
        import jax.numpy as jnp
        if self._cache is not None:
            order = list(range(len(self._cache)))
            if self._reshuffle:
                import random as _random
                self._epoch += 1
                _random.Random(self._seed + self._epoch).shuffle(order)
            for i in order:
                yield self._cache[i]
            return
        if self._overflowed:
            yield from self._loader
            return
        cache = []
        used = 0
        for batch in self._loader:
            if cache is not None:
                items = tuple(
                    t._data if hasattr(t, "_data") else jnp.asarray(t)
                    for t in (batch if isinstance(batch, (list, tuple))
                              else [batch]))
                nbytes = sum(getattr(a, "nbytes", 0) for a in items)
                if used + nbytes <= self._max_bytes:
                    cache.append(items)
                    used += nbytes
                    yield items
                    continue
                import warnings
                warnings.warn(
                    f"DeviceCacheLoader: dataset exceeds max_bytes="
                    f"{self._max_bytes}; caching disabled (all epochs "
                    "stream from host — a partial cache would bias "
                    "sampling)")
                cache = None
                self._overflowed = True
            yield batch
        if cache is not None:
            self._cache = cache
