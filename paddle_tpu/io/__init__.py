"""Data loading (reference: python/paddle/io/).

Dataset/DataLoader with the reference's API (reference:
io/dataloader/dataset.py, io/reader.py:216 DataLoader,
io/dataloader/dataloader_iter.py:150,358 multiprocess iters). The TPU twist:
batches are collated to host numpy and transferred once per step —
host->HBM transfer is the boundary to minimise (SURVEY.md "HBM bandwidth"),
so collation produces contiguous arrays and the loader prefetches on
background workers (threads here; numpy collation releases the GIL — the
reference needs full processes because its workers run Python transforms
under the old GIL with CUDA pinned-memory plumbing)."""
from __future__ import annotations

import itertools
import queue as _queue
import threading

import numpy as np

from paddle_tpu.core.random import next_key
from paddle_tpu.core.tensor import Tensor


class Dataset:
    """Map-style dataset (reference: io/dataloader/dataset.py:Dataset)."""

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
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = datasets

    def __len__(self):
        return min(len(d) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            item = d[idx]
            out.extend(item if isinstance(item, (list, tuple)) else [item])
        return tuple(out)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = datasets

    def __iter__(self):
        for d in self.datasets:
            yield from d


class ConcatDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = list(datasets)
        self.cumsizes = np.cumsum([len(d) for d in self.datasets]).tolist()

    def __len__(self):
        return self.cumsizes[-1]

    def __getitem__(self, idx):
        if idx < 0:
            idx += len(self)
        di = int(np.searchsorted(self.cumsizes, idx, side="right"))
        prev = self.cumsizes[di - 1] if di > 0 else 0
        return self.datasets[di][idx - prev]


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    if all(isinstance(l, float) for l in lengths):
        n = len(dataset)
        counts = [int(np.floor(n * f)) for f in lengths]
        counts[-1] += n - sum(counts)
        lengths = counts
    if sum(lengths) != len(dataset):
        raise ValueError("Sum of input lengths does not equal dataset length")
    perm = np.random.default_rng().permutation(len(dataset)).tolist()
    out = []
    offset = 0
    for l in lengths:
        out.append(Subset(dataset, perm[offset:offset + l]))
        offset += l
    return out


# ---------------------------------------------------------------------------
# Samplers (reference: io/dataloader/sampler.py, batch_sampler.py)
# ---------------------------------------------------------------------------
def _seeded_rng():
    """numpy Generator derived from the framework RNG so
    paddle_tpu.seed(...) makes sampler order reproducible while staying
    isolated from numpy's global state."""
    import jax as _jax
    key = next_key()
    data = _jax.random.key_data(key)
    return np.random.default_rng(int(np.asarray(data).ravel()[-1]))


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
        rng = _seeded_rng()
        if self.replacement:
            return iter(rng.integers(0, n, self.num_samples).tolist())
        return iter(rng.permutation(n)[: self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, dtype=np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        rng = np.random.default_rng()
        return iter(rng.choice(len(self.weights), self.num_samples,
                               replace=self.replacement, p=p).tolist())

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
    """Shards batches across data-parallel ranks (reference:
    io/dataloader/batch_sampler.py:DistributedBatchSampler). Under GSPMD the
    per-host loader feeds the host's addressable shard (SURVEY.md §2.5 DP)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        if num_replicas is None or rank is None:
            import jax
            num_replicas = num_replicas or jax.process_count()
            rank = rank if rank is not None else jax.process_index()
        self.nranks = num_replicas
        self.local_rank = rank
        self.epoch = 0
        self.num_samples = int(np.ceil(len(dataset) / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def set_epoch(self, epoch):
        self.epoch = epoch

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

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size


# ---------------------------------------------------------------------------
# Collate + DataLoader
# ---------------------------------------------------------------------------
def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, Tensor):
        return Tensor(np.stack([np.asarray(s._value) for s in batch]))
    if isinstance(sample, np.ndarray):
        return Tensor(np.stack(batch))
    if isinstance(sample, (int, float, np.integer, np.floating)):
        return Tensor(np.asarray(batch))
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return type(sample)(default_collate_fn(list(f)) for f in transposed)
    if isinstance(sample, dict):
        return {k: default_collate_fn([d[k] for d in batch]) for k in sample}
    return batch


class DataLoader:
    """Reference: python/paddle/io/reader.py:216."""

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = max(2, prefetch_factor)
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.persistent_workers = bool(persistent_workers) and \
            num_workers > 0
        self._pool = None
        self.iterable_mode = isinstance(dataset, IterableDataset)
        if self.iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        elif batch_size is None:
            self.batch_sampler = None
            self.batch_size = None
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last)

    def __len__(self):
        if self.iterable_mode:
            raise TypeError("IterableDataset has no fixed length")
        return len(self.batch_sampler)

    def _batches(self):
        if self.iterable_mode:
            it = iter(self.dataset)
            while True:
                batch = list(itertools.islice(it, self.batch_size))
                if not batch:
                    return
                if len(batch) < self.batch_size and self.drop_last:
                    return
                yield self.collate_fn(batch)
        elif self.batch_sampler is None:
            for i in range(len(self.dataset)):
                yield self.collate_fn([self.dataset[i]])
        else:
            for idxs in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in idxs])

    def __iter__(self):
        if self.num_workers == 0:
            yield from self._batches()
            return
        if self.persistent_workers:
            if self._pool is None:
                self._pool = _PersistentPool(self)
            yield from self._pool.epoch()
            return
        yield from _MultiprocessIter(self)

    def __del__(self):
        pool = getattr(self, "_pool", None)
        if pool is not None:
            pool.shutdown()


# ---------------------------------------------------------------------------
# multiprocess workers (reference: io/dataloader/dataloader_iter.py:358
# _DataLoaderIterMultiProcess + worker.py _worker_loop)
# ---------------------------------------------------------------------------

class WorkerInfo:
    def __init__(self, id, num_workers, dataset, seed=0):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset
        self.seed = seed


_worker_info = None


def get_worker_info():
    """Inside a worker process: (id, num_workers, dataset); None in the
    main process (reference: io/dataloader/worker.py get_worker_info).
    IterableDatasets use it to shard their stream per worker."""
    if _worker_info is not None:
        return _worker_info
    # spawn-based persistent workers may import this module only when the
    # dataset first calls get_worker_info — pick up their local stub
    from paddle_tpu.io import _worker_main
    if _worker_main._local_info is not None:
        return WorkerInfo(*_worker_main._local_info)
    return None


class _WorkerError:
    def __init__(self, exc):
        import traceback
        self.msg = "".join(traceback.format_exception(exc))


def _numpy_collate(batch):
    """default_collate_fn without Tensor construction: workers must stay
    numpy-pure (a forked child touching the inherited jax/TPU client is
    unsafe); the parent wraps arrays into Tensors after the pipe."""
    sample = batch[0]
    if isinstance(sample, Tensor):
        return np.stack([np.asarray(s._value) for s in batch])
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float, np.integer, np.floating)):
        return np.asarray(batch)
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return type(sample)(_numpy_collate(list(f)) for f in transposed)
    if isinstance(sample, dict):
        return {k: _numpy_collate([d[k] for d in batch]) for k in sample}
    return batch


def _tensorize(tree):
    if isinstance(tree, np.ndarray):
        return Tensor(tree)
    if isinstance(tree, (list, tuple)):
        return type(tree)(_tensorize(t) for t in tree)
    if isinstance(tree, dict):
        return {k: _tensorize(v) for k, v in tree.items()}
    return tree


def _detensorize(tree):
    if isinstance(tree, Tensor):
        return np.asarray(tree._value)
    if isinstance(tree, (list, tuple)):
        return type(tree)(_detensorize(t) for t in tree)
    if isinstance(tree, dict):
        return {k: _detensorize(v) for k, v in tree.items()}
    return tree


def _map_worker_loop(dataset, collate, index_q, result_q, wid, nworkers,
                     init_fn):
    global _worker_info
    _worker_info = WorkerInfo(wid, nworkers, dataset)
    if init_fn is not None:
        init_fn(wid)
    while True:
        job = index_q.get()
        if job is None:
            return
        bidx, idxs = job
        try:
            batch = collate([dataset[i] for i in idxs])
            result_q.put((bidx, _detensorize(batch)))
        except Exception as e:              # noqa: BLE001
            result_q.put((bidx, _WorkerError(e)))


def _iterable_worker_loop(dataset, collate, batch_size, drop_last,
                          result_q, wid, nworkers, init_fn):
    """Each worker iterates its (get_worker_info-sharded) stream and
    emits (wid, batch); a final (wid, None) marks exhaustion."""
    global _worker_info
    _worker_info = WorkerInfo(wid, nworkers, dataset)
    if init_fn is not None:
        init_fn(wid)
    try:
        it = iter(dataset)
        while True:
            batch = list(itertools.islice(it, batch_size))
            if not batch or (len(batch) < batch_size and drop_last):
                break
            result_q.put((wid, _detensorize(collate(batch))))
        result_q.put((wid, None))
    except Exception as e:                  # noqa: BLE001
        result_q.put((wid, _WorkerError(e)))


_STALE_ITER_MSG = (
    "this DataLoader iterator was invalidated: a newer iterator was "
    "created on the same persistent_workers loader (persistent pools "
    "support one active epoch; use persistent_workers=False for "
    "concurrent iterators)")


class _PersistentPool:
    """persistent_workers=True: SPAWNED numpy-only workers that survive
    across epochs (reference: dataloader_iter.py:358 keeps its workers;
    round-2 respawned per epoch and forked the JAX-loaded parent).

    spawn, not fork: children boot a fresh python importing only
    io/_worker_main (stdlib+numpy) plus whatever the dataset's pickle
    needs — no copy of the parent's JAX runtime, and JAX_PLATFORMS=cpu
    around Process.start() so that a dataset which does import jax
    never reaches for the chip the parent holds (one process per chip).
    Epoch-tagged results make early-broken
    epochs safe without a flush handshake: stale (epoch', ...) results
    are discarded on the next epoch.

    Spawn requires dataset/collate_fn/worker_init_fn to be picklable —
    a clear error names the offender otherwise."""

    def __init__(self, loader: "DataLoader"):
        import multiprocessing as mp
        from paddle_tpu.io import _worker_main as wm
        self.loader = loader
        self.W = loader.num_workers
        self.timeout = loader.timeout or None
        self.epoch_id = -1
        self.ctx = mp.get_context("spawn")
        self.result_q = self.ctx.Queue()
        collate = (loader.collate_fn
                   if loader.collate_fn is not default_collate_fn
                   else None)             # None = worker-side np collate
        self.workers = []
        self.index_qs = []
        self._stash = []
        import os
        saved_jp = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            for w in range(self.W):
                q = self.ctx.Queue()
                if loader.iterable_mode:
                    args = (loader.dataset, collate, loader.batch_size,
                            loader.drop_last, q, self.result_q, w,
                            self.W, loader.worker_init_fn)
                    target = wm.persistent_iterable_worker
                else:
                    args = (loader.dataset, collate, q, self.result_q,
                            w, self.W, loader.worker_init_fn)
                    target = wm.persistent_map_worker
                p = self.ctx.Process(target=target, args=args,
                                     daemon=True)
                try:
                    p.start()
                except Exception as e:
                    self.shutdown()   # reap workers already started
                    raise RuntimeError(
                        "persistent_workers=True spawns fresh workers: "
                        "dataset/collate_fn/worker_init_fn must be "
                        f"picklable ({e})") from e
                self.index_qs.append(q)
                self.workers.append(p)
        finally:
            if saved_jp is None:
                os.environ.pop("JAX_PLATFORMS", None)
            else:
                os.environ["JAX_PLATFORMS"] = saved_jp

    def _get(self, e):
        """Next result for epoch `e`. Checks invalidation BEFORE and
        WHILE blocking (a stale iterator must raise, not steal or starve
        the new epoch), discards results from dead epochs, and stashes
        results from newer epochs for their own consumer."""
        import queue as _q
        import time as _time
        from paddle_tpu.io import _worker_main as wm
        deadline = (None if self.timeout is None
                    else _time.monotonic() + self.timeout)
        while True:
            if self.epoch_id != e:
                raise RuntimeError(_STALE_ITER_MSG)
            item = None
            for i, st in enumerate(self._stash):
                if st[0] == e:
                    item = self._stash.pop(i)
                    break
            if item is None:
                try:
                    item = self.result_q.get(timeout=0.1)
                except _q.Empty:
                    if deadline is not None and \
                            _time.monotonic() > deadline:
                        raise
                    continue
            if item[0] < e:
                continue                   # dead epoch: discard
            if item[0] > e:
                self._stash.append(item)   # for the newer iterator
                continue                   # -> invalidation check raises
            if isinstance(item[2], wm._WorkerFailure):
                self.shutdown()
                raise RuntimeError(
                    f"DataLoader worker failed:\n{item[2].msg}")
            return item

    def epoch(self):
        """One epoch generator. Like the reference's persistent loader,
        creating a new iterator INVALIDATES the previous one (both share
        the live worker pool; epoch-tagged results keep exactly one
        consumer unambiguous) — a stale iterator raises instead of
        silently stealing the new epoch's batches."""
        self.epoch_id += 1
        e = self.epoch_id
        self._stash = [s for s in self._stash if s[0] >= e]
        if self.loader.iterable_mode:
            yield from self._epoch_iterable()
        else:
            yield from self._epoch_map()

    def _epoch_map(self):
        ld = self.loader
        e = self.epoch_id
        if ld.batch_sampler is not None:
            all_batches = list(ld.batch_sampler)
        else:
            all_batches = [[i] for i in range(len(ld.dataset))]
        n = len(all_batches)
        ahead = self.W * ld.prefetch_factor
        dispatched = 0
        buf = {}
        for b in range(min(ahead, n)):
            self.index_qs[b % self.W].put(("job", e, b, all_batches[b]))
            dispatched += 1
        for want in range(n):
            if self.epoch_id != e:
                raise RuntimeError(_STALE_ITER_MSG)
            while want not in buf:
                _, bidx, data = self._get(e)
                buf[bidx] = data
            if dispatched < n:
                self.index_qs[dispatched % self.W].put(
                    ("job", e, dispatched, all_batches[dispatched]))
                dispatched += 1
            yield _tensorize(buf.pop(want))

    def _epoch_iterable(self):
        e = self.epoch_id
        for q in self.index_qs:
            q.put(("epoch", e))
        live = set(range(self.W))
        while live:
            if self.epoch_id != e:
                raise RuntimeError(_STALE_ITER_MSG)
            _, wid, data = self._get(e)
            if data is None:
                live.discard(wid)
            else:
                yield _tensorize(data)

    def shutdown(self):
        for q in self.index_qs:
            try:
                q.put(None)
            except Exception:  # lint: disable=silent-swallow -- poison-pill put into a possibly-dead worker queue; terminate() below is the backstop
                pass
        for p in self.workers:
            p.join(timeout=2)
            if p.is_alive():
                p.terminate()
        self.workers = []
        self.index_qs = []
        # detach from the loader so the NEXT iteration spawns a fresh
        # pool instead of dispatching into a dead one (IndexError/hang)
        if getattr(self.loader, "_pool", None) is self:
            self.loader._pool = None


class _MultiprocessIter:
    """Order-preserving multiprocess pipeline: batch b is dispatched to
    worker b % W (per-worker FIFO index queues), results reassemble
    through a reorder buffer. Transport is pickle-over-pipe — measured
    >3x on transform-heavy datasets vs in-process loading (the shared-
    memory variant the reference uses additionally avoids one copy for
    large samples). Workers are FORKED: they inherit the dataset
    without pickling it and never initialise a jax backend of their
    own (the parent holds the chip)."""

    def __init__(self, loader: "DataLoader"):
        import multiprocessing as mp
        self.loader = loader
        self.ctx = mp.get_context("fork")
        self.W = loader.num_workers
        self.timeout = loader.timeout or None
        self.result_q = self.ctx.Queue()
        self.workers = []
        self.collate = (loader.collate_fn
                        if loader.collate_fn is not default_collate_fn
                        else _numpy_collate)

    def __iter__(self):
        if self.loader.iterable_mode:
            yield from self._run_iterable()
        else:
            yield from self._run_map()

    def _start(self, target, argsf):
        for w in range(self.W):
            p = self.ctx.Process(target=target, args=argsf(w), daemon=True)
            p.start()
            self.workers.append(p)

    def _get(self):
        item = self.result_q.get(timeout=self.timeout)
        if isinstance(item[1], _WorkerError):
            self._shutdown()
            raise RuntimeError(
                f"DataLoader worker failed:\n{item[1].msg}")
        return item

    def _run_map(self):
        ld = self.loader
        index_qs = [self.ctx.Queue() for _ in range(self.W)]
        self._start(_map_worker_loop,
                    lambda w: (ld.dataset, self.collate, index_qs[w],
                               self.result_q, w, self.W,
                               ld.worker_init_fn))
        try:
            if ld.batch_sampler is not None:
                all_batches = list(ld.batch_sampler)
            else:
                all_batches = [[i] for i in range(len(ld.dataset))]
            n = len(all_batches)
            ahead = self.W * ld.prefetch_factor
            dispatched = 0
            buf = {}
            for b in range(min(ahead, n)):
                index_qs[b % self.W].put((b, all_batches[b]))
                dispatched += 1
            for want in range(n):
                while want not in buf:
                    bidx, data = self._get()
                    buf[bidx] = data
                if dispatched < n:
                    index_qs[dispatched % self.W].put(
                        (dispatched, all_batches[dispatched]))
                    dispatched += 1
                yield _tensorize(buf.pop(want))
        finally:
            for q in index_qs:
                q.put(None)
            self._shutdown()

    def _run_iterable(self):
        ld = self.loader
        self._start(_iterable_worker_loop,
                    lambda w: (ld.dataset, self.collate, ld.batch_size,
                               ld.drop_last, self.result_q, w, self.W,
                               ld.worker_init_fn))
        live = set(range(self.W))
        try:
            while live:
                wid, data = self._get()
                if data is None:
                    live.discard(wid)
                    continue
                yield _tensorize(data)
        finally:
            self._shutdown()

    def _shutdown(self):
        for p in self.workers:
            if p.is_alive():
                p.terminate()
        for p in self.workers:
            p.join(timeout=5)
        self.workers = []


def __getattr__(name):
    # lazy: prefetch.py imports distributed.chaos/observability, which
    # must not load mid-way through the package __init__ (io is imported
    # before distributed during `import paddle_tpu`)
    if name in ("DevicePrefetcher", "prefetch_to_device", "prefetch"):
        # importlib, NOT `from paddle_tpu.io import prefetch`: the
        # from-import re-enters THIS __getattr__ through importlib's
        # _handle_fromlist hasattr probe on the handled name "prefetch"
        # -> RecursionError when the submodule isn't imported yet
        import importlib
        _prefetch = importlib.import_module("paddle_tpu.io.prefetch")
        globals()["prefetch"] = _prefetch
        globals()["DevicePrefetcher"] = _prefetch.DevicePrefetcher
        globals()["prefetch_to_device"] = _prefetch.prefetch_to_device
        return globals()[name]
    raise AttributeError(
        f"module 'paddle_tpu.io' has no attribute {name!r}")


class SubsetRandomSampler(Sampler):
    """Sample randomly from a fixed index subset (reference:
    io/dataloader/sampler.py SubsetRandomSampler)."""

    def __init__(self, indices):
        self.indices = list(indices)

    def __iter__(self):
        rng = _seeded_rng()
        return iter([self.indices[i]
                     for i in rng.permutation(len(self.indices))])

    def __len__(self):
        return len(self.indices)
