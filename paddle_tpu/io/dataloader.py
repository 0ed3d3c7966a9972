"""Dataset / DataLoader / samplers.

Reference: python/paddle/io (Dataset, DataLoader with multiprocess workers +
shared-mem queue, fluid/dataloader/dataloader_iter.py:162) and
DistributedBatchSampler. TPU-native: host-side numpy batching feeding
`jax.device_put` (one transfer per step); multiprocessing workers use the
stdlib pool since there is no CUDA-pinned-memory dance. For the mesh path,
`DistributedBatchSampler` shards by dp rank exactly like the reference.
"""
from __future__ import annotations

import math
import multiprocessing.pool

import numpy as np

from ..core.tensor import Tensor, wrap

__all__ = ["Dataset", "IterableDataset", "TensorDataset", "Subset",
           "random_split", "ComposeDataset", "ChainDataset", "DataLoader",
           "BatchSampler", "Sampler", "SequenceSampler", "RandomSampler",
           "WeightedRandomSampler",
           "DistributedBatchSampler", "default_collate_fn", "get_worker_info"]


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return len(self.tensors[0])


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    n = len(dataset)
    if sum(lengths) != n:
        # fractional lengths
        if all(0 < l < 1 for l in lengths):
            lengths = [int(l * n) for l in lengths]
            lengths[-1] = n - sum(lengths[:-1])
        else:
            raise ValueError("lengths must sum to dataset size")
    perm = np.random.permutation(n)
    out, ofs = [], 0
    for l in lengths:
        out.append(Subset(dataset, perm[ofs:ofs + l].tolist()))
        ofs += l
    return out


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = datasets

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
        self.datasets = datasets

    def __iter__(self):
        for d in self.datasets:
            yield from d


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
        self.num_samples = num_samples or len(data_source)

    def __iter__(self):
        n = len(self.data_source)
        if self.replacement:
            return iter(np.random.randint(0, n, self.num_samples).tolist())
        return iter(np.random.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    """Sample indices with given per-sample weights (reference
    python/paddle/io WeightedRandomSampler)."""

    def __init__(self, weights, num_samples, replacement=True):
        if num_samples <= 0:
            raise ValueError("num_samples must be positive")
        if not replacement and num_samples > len(weights):
            raise ValueError(
                "num_samples exceeds population for replacement=False")
        self.weights = np.asarray(weights, dtype=np.float64)
        self.num_samples = int(num_samples)
        self.replacement = bool(replacement)

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(len(p), size=self.num_samples,
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
    """Reference: python/paddle/io DistributedBatchSampler — shard indices by
    dp rank. num_replicas/rank default to the mesh dp axis."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        if num_replicas is None:
            from ..parallel.mesh import get_mesh
            m = get_mesh()
            num_replicas = m.degree("dp") if m else 1
        self.nranks = num_replicas
        self.local_rank = rank or 0
        self.epoch = 0
        self.num_samples = int(math.ceil(len(dataset) / num_replicas))
        self.total_size = self.num_samples * num_replicas

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            indices = rng.permutation(n).tolist()
        else:
            indices = list(range(n))
        indices += indices[:self.total_size - len(indices)]
        local = indices[self.local_rank::self.nranks]
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


class _WorkerInfo:
    def __init__(self, id_, num_workers, dataset):
        self.id = id_
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = None


def get_worker_info():
    return _worker_info


def default_collate_fn(batch):
    """Stack samples into batched numpy arrays / Tensors."""
    sample = batch[0]
    if isinstance(sample, (Tensor,)):
        return wrap(np.stack([s.numpy() for s in batch]))
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if isinstance(sample, (int, float)):
        return np.asarray(batch)
    if isinstance(sample, (list, tuple)):
        return type(sample)(default_collate_fn([b[i] for b in batch])
                            for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    return batch


# --------------------------------------------- multiprocess worker plumbing

class _ShmRef:
    """Pickle-light reference to a numpy array parked in POSIX shared
    memory (reference: dataloader_iter.py:162 shared-mem worker queue —
    large batches cross the process boundary as a name + memcpy, never
    through pickle serialization)."""

    __slots__ = ("name", "shape", "dtype")

    def __init__(self, name, shape, dtype):
        self.name = name
        self.shape = shape
        self.dtype = dtype


def _tree_to_shm(obj):
    from multiprocessing import shared_memory
    if isinstance(obj, np.ndarray) and obj.nbytes > 0:
        shm = shared_memory.SharedMemory(create=True, size=obj.nbytes)
        np.frombuffer(shm.buf, obj.dtype)[:obj.size] = obj.reshape(-1)
        ref = _ShmRef(shm.name, obj.shape, obj.dtype)
        shm.close()  # worker-side handle; parent unlinks after reading
        return ref
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_to_shm(v) for v in obj)
    if isinstance(obj, dict):
        return {k: _tree_to_shm(v) for k, v in obj.items()}
    return obj


def _tree_from_shm(obj):
    from multiprocessing import shared_memory
    if isinstance(obj, _ShmRef):
        shm = shared_memory.SharedMemory(name=obj.name)
        try:
            arr = np.frombuffer(shm.buf, obj.dtype)[
                :int(np.prod(obj.shape))].reshape(obj.shape).copy()
        finally:
            shm.close()
            shm.unlink()
        return arr
    if isinstance(obj, (list, tuple)):
        return type(obj)(_tree_from_shm(v) for v in obj)
    if isinstance(obj, dict):
        return {k: _tree_from_shm(v) for k, v in obj.items()}
    return obj


class _RingResultQueue:
    """Queue-interface adapter over per-worker native SPSC rings
    (runtime.ShmRing, csrc/shm_ring.cc — the reference's C++
    buffered_reader transport). The parent pops round-robin; each
    worker attaches its own ring by name and pushes pickled results
    (large batches go inline through the ring's slot — one memcpy into
    shared memory, no pipe, no feeder thread)."""

    def __init__(self, names, slot_size, n_slots=8):
        from ..runtime import ShmRing
        self._rings = [ShmRing(n, slot_size=slot_size, n_slots=n_slots,
                               create=True) for n in names]
        self._slot = slot_size

    def _sweep(self):
        import pickle
        for r in self._rings:
            data = r.pop(timeout_ms=0)
            if data is not None:
                return pickle.loads(data)
        return None

    def get(self, timeout=5.0):
        import queue as queue_mod
        import time as time_mod
        deadline = time_mod.monotonic() + timeout
        while True:
            msg = self._sweep()
            if msg is not None:
                return msg
            if time_mod.monotonic() > deadline:
                raise queue_mod.Empty
            time_mod.sleep(0.001)

    def get_nowait(self):
        import queue as queue_mod
        msg = self._sweep()
        if msg is None:
            raise queue_mod.Empty
        return msg

    def close(self):
        for r in self._rings:
            r.close()
        self._rings = []


def _refuse_device_arrays(batch):
    """Workers are FORKED from a parent that may hold an accelerator;
    a forked child cannot use the parent's chip (it fails or hangs),
    so workers must stay off JAX and hand back numpy. A batch carrying
    a framework Tensor or a jax.Array broke that rule."""
    import jax
    for leaf in jax.tree_util.tree_leaves(
            batch, is_leaf=lambda x: isinstance(x, Tensor)):
        if isinstance(leaf, (Tensor, jax.Array)):
            raise TypeError(
                "DataLoader workers must stay off JAX: a worker process "
                "is forked from a parent that may hold the accelerator "
                "and cannot share it. Return numpy from the dataset/"
                f"collate_fn (got {type(leaf).__name__}) or use "
                "num_workers=0.")


def _worker_loop(dataset, index_queue, result_queue, collate_fn, wid,
                 num_workers, worker_init_fn, use_shared_memory, seed,
                 ring_name=None, ring_slot=0):
    """Worker process body (reference _worker_loop, dataloader/worker.py)."""
    global _worker_info
    _worker_info = _WorkerInfo(wid, num_workers, dataset)
    np.random.seed((seed + wid) % (2 ** 31))
    if worker_init_fn is not None:
        worker_init_fn(wid)
    if ring_name is not None:
        import pickle
        from ..runtime import ShmRing
        ring = ShmRing(ring_name, create=False)

        def _send(msg):
            ep_, bi_, ok_, payload_ = msg
            data = pickle.dumps(msg)
            if len(data) + 8 > ring_slot and ok_:
                # batch bigger than a slot: park arrays in their own
                # shm segments and send the light refs through the ring
                data = pickle.dumps((ep_, bi_, ok_,
                                     _tree_to_shm(payload_)))
            if len(data) + 8 > ring_slot:
                # still oversized (object-heavy batch or a huge error
                # traceback): report the failure instead of dying on
                # the push — the worker must stay alive
                note = (f"batch {bi_} payload exceeds the native ring "
                        f"slot ({len(data)} > {ring_slot - 8} bytes); "
                        "raise ring_slot_mb or disable use_native_ring"
                        if ok_ else
                        "worker error traceback exceeded the ring "
                        "slot:\n" + str(payload_)[:4096])
                data = pickle.dumps((ep_, bi_, False, note))
            ring.push(data)
    else:
        def _send(msg):
            result_queue.put(msg)
    while True:
        item = index_queue.get()
        if item is None:
            break
        epoch, bidx, indices = item
        try:
            batch = collate_fn([dataset[i] for i in indices])
            _refuse_device_arrays(batch)
            if use_shared_memory and ring_name is None:
                batch = _tree_to_shm(batch)
            _send((epoch, bidx, True, batch))
        except Exception:
            import traceback
            _send((epoch, bidx, False, traceback.format_exc()))


class DataLoader:
    """paddle.io.DataLoader parity. num_workers>0 spawns REAL worker
    processes (fork) with per-worker index queues and a shared result
    queue; use_shared_memory routes numpy payloads through POSIX shared
    memory instead of pickle (reference
    python/paddle/fluid/dataloader/dataloader_iter.py:162,370).
    Workers are forked after JAX may have started, so they must stay
    off JAX (one process per chip): datasets and collate functions run
    in workers return numpy, and a batch holding device arrays is
    refused with a typed error."""

    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False, use_native_ring=False,
                 ring_slot_mb=8):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.use_shared_memory = use_shared_memory
        self.use_native_ring = use_native_ring
        self.ring_slot = int(ring_slot_mb) << 20
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        self.persistent_workers = persistent_workers
        if batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)
        self.prefetch_factor = prefetch_factor
        self._workers = []
        self._index_queues = []
        self._result_queue = None
        self._epoch = 0

    def __len__(self):
        return len(self.batch_sampler)

    def _fetch(self, indices):
        return self.collate_fn([self.dataset[i] for i in indices])

    def resume_iter(self, skip):
        """Batches starting at batch index ``skip`` — mid-epoch exact
        resume. Single-process map-style loaders skip by consuming only
        the sampler's index lists (no ``__getitem__``/collate for the
        already-trained prefix, so resume cost is independent of the
        position in the epoch); iterable datasets and multiprocess
        loaders fall back to fetch-and-discard."""
        if skip <= 0:
            yield from self
            return
        if isinstance(self.dataset, IterableDataset) or self.num_workers > 0:
            it = iter(self)
            for _ in range(skip):
                try:
                    next(it)
                except StopIteration:
                    return
            yield from it
            return
        for i, indices in enumerate(self.batch_sampler):
            if i >= skip:
                yield self._fetch(indices)

    # ---------------------------------------------------- worker control
    def _start_workers(self):
        import os as os_mod
        ctx = multiprocessing.get_context("fork")
        ring_names = None
        if self.use_native_ring:
            ring_names = [f"/pt_dl_{os_mod.getpid()}_{id(self)}_{w}"
                          for w in range(self.num_workers)]
            # slots must cover this worker's share of the dispatch
            # window or producers block at epoch boundaries
            n_slots = max(8, 2 * max(2, self.prefetch_factor) + 2)
            self._result_queue = _RingResultQueue(ring_names,
                                                  self.ring_slot,
                                                  n_slots=n_slots)
        else:
            self._result_queue = ctx.Queue()
        for wid in range(self.num_workers):
            iq = ctx.Queue()
            p = ctx.Process(
                target=_worker_loop,
                args=(self.dataset, iq,
                      None if ring_names else self._result_queue,
                      self.collate_fn, wid, self.num_workers,
                      self.worker_init_fn, self.use_shared_memory,
                      np.random.randint(0, 2 ** 31),
                      ring_names[wid] if ring_names else None,
                      self.ring_slot),
                daemon=True)
            p.start()
            self._workers.append(p)
            self._index_queues.append(iq)

    def _drain_result_queue(self):
        """Unlink any parked shared-memory payloads so abandoned epochs
        and error paths don't leak /dev/shm segments."""
        import queue as queue_mod
        if self._result_queue is None:
            return
        while True:
            try:
                item = self._result_queue.get_nowait()
            except (queue_mod.Empty, OSError, ValueError):
                return
            payload = item[-1]
            if item[-2]:  # ok flag: payload may hold shm refs
                try:
                    _tree_from_shm(payload)
                except Exception:
                    pass

    def _shutdown_workers(self):
        for iq in self._index_queues:
            try:
                iq.put(None)
            except (OSError, ValueError):
                pass
        self._drain_result_queue()
        for p in self._workers:
            p.join(timeout=5)
            if p.is_alive():
                p.terminate()
        self._drain_result_queue()
        if isinstance(self._result_queue, _RingResultQueue):
            self._result_queue.close()
        self._workers, self._index_queues = [], []
        self._result_queue = None

    def __del__(self):
        try:
            self._shutdown_workers()
        except Exception:
            pass

    # ------------------------------------------------------------- iter
    def __iter__(self):
        if isinstance(self.dataset, IterableDataset):
            yield from self._iter_iterable()
            return
        if self.num_workers <= 0:
            for indices in self.batch_sampler:
                yield self._fetch(indices)
            return
        yield from self._iter_multiprocess()

    def _iter_multiprocess(self):
        import time as time_mod
        import queue as queue_mod
        if not self._workers:
            self._start_workers()
        self._epoch += 1
        epoch = self._epoch
        batches = list(self.batch_sampler)
        # bounded dispatch (reference: prefetch_factor * num_workers
        # outstanding batches) — no unbounded /dev/shm buildup when the
        # consumer is slower than the workers
        window = max(2, self.prefetch_factor) * self.num_workers
        next_submit = 0

        def submit_upto(n):
            nonlocal next_submit
            while next_submit < min(n, len(batches)):
                self._index_queues[next_submit % self.num_workers].put(
                    (epoch, next_submit, batches[next_submit]))
                next_submit += 1

        submit_upto(window)
        pending = {}
        try:
            for want in range(len(batches)):
                deadline = (time_mod.monotonic() + self.timeout
                            if self.timeout else None)
                while want not in pending:
                    try:
                        # poll so dead workers / user timeout are noticed
                        # even though timeout=0 means wait-forever
                        ep, bidx, ok, payload = self._result_queue.get(
                            timeout=5.0)
                    except queue_mod.Empty:
                        dead = [i for i, p in enumerate(self._workers)
                                if not p.is_alive()]
                        if dead:
                            self._shutdown_workers()
                            raise RuntimeError(
                                f"DataLoader workers died: {dead}")
                        if deadline and time_mod.monotonic() > deadline:
                            self._shutdown_workers()
                            raise RuntimeError(
                                f"DataLoader timed out after "
                                f"{self.timeout}s waiting for batch "
                                f"{want}")
                        continue
                    if not ok:
                        self._shutdown_workers()
                        raise RuntimeError(
                            f"DataLoader worker failed:\n{payload}")
                    if self.use_shared_memory or self.use_native_ring:
                        # ring payloads are inline unless a batch
                        # overflowed its slot into shm refs; the
                        # converter passes plain arrays through
                        payload = _tree_from_shm(payload)
                    if ep != epoch:
                        continue  # stale result from an abandoned epoch
                    pending[bidx] = payload
                submit_upto(want + 1 + window)
                yield pending.pop(want)
        finally:
            if not self.persistent_workers:
                self._shutdown_workers()

    def _iter_iterable(self):
        batch = []
        bs = self.batch_sampler.batch_size
        for item in self.dataset:
            batch.append(item)
            if len(batch) == bs:
                yield self.collate_fn(batch)
                batch = []
        if batch and not self.batch_sampler.drop_last:
            yield self.collate_fn(batch)
