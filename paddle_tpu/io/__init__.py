"""`paddle.io` equivalent: Dataset / Sampler / DataLoader.

Role parity: reference python/paddle/fluid/reader.py (`DataLoader`:147)
+ fluid/dataloader/ (dataloader_iter.py:262 single-process / :467
multi-process workers, batch_sampler.py, dataset.py).  TPU-native notes:
the loader feeds a host-side pipeline; batches should be padded to
static shapes (XLA recompiles per new shape) — `DataLoader` keeps the
reference's drop_last/shuffle/collate semantics and adds background
prefetch so host IO overlaps device compute (the reference's
buffered_reader double-buffering role).
"""
from __future__ import annotations

import itertools
import queue
import threading
from typing import Iterable, List, Optional, Sequence

import numpy as np


class Dataset:
    """Map-style dataset (reference fluid/dataloader/dataset.py)."""

    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError

    def __getitem__(self, idx):
        raise TypeError("IterableDataset is not subscriptable")

    def __len__(self):
        raise TypeError("IterableDataset has no len()")


class TensorDataset(Dataset):
    def __init__(self, tensors: Sequence):
        arrays = [np.asarray(t) if not hasattr(t, "numpy") else t.numpy()
                  for t in tensors]
        n = len(arrays[0])
        assert all(len(a) == n for a in arrays), "tensors must share dim 0"
        self.tensors = arrays

    def __getitem__(self, idx):
        return tuple(a[idx] for a in self.tensors)

    def __len__(self):
        return len(self.tensors[0])


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset, self.indices = dataset, list(indices)

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    assert sum(lengths) == len(dataset)
    rng = np.random.RandomState(generator if isinstance(generator, int) else None)
    perm = rng.permutation(len(dataset))
    out, ofs = [], 0
    for ln in lengths:
        out.append(Subset(dataset, perm[ofs:ofs + ln].tolist()))
        ofs += ln
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
        self.num_samples = num_samples or len(data_source)
        self.generator = generator

    def __iter__(self):
        n = len(self.data_source)
        rng = np.random.RandomState(
            self.generator if isinstance(self.generator, int) else None)
        if self.replacement:
            return iter(rng.randint(0, n, self.num_samples).tolist())
        return iter(rng.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class DistributedBatchSampler(Sampler):
    """Shards batches across ranks (reference
    fluid/dataloader/batch_sampler.py DistributedBatchSampler)."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        from ..distributed import get_rank, get_world_size

        self.nranks = num_replicas if num_replicas is not None else get_world_size()
        self.local_rank = rank if rank is not None else get_rank()
        self.epoch = 0
        n = len(dataset)
        import math

        self.num_samples = int(math.ceil(n / self.nranks))
        self.total_size = self.num_samples * self.nranks

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __iter__(self):
        n = len(self.dataset)
        indices = list(range(n))
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            rng.shuffle(indices)
        indices += indices[: self.total_size - n]
        local = indices[self.local_rank::self.nranks]
        batch = []
        for i in local:
            batch.append(i)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        import math

        if self.drop_last:
            return self.num_samples // self.batch_size
        return int(math.ceil(self.num_samples / self.batch_size))


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        if sampler is None:
            sampler = (RandomSampler(dataset) if shuffle
                       else SequenceSampler(dataset))
        self.sampler = sampler
        self.batch_size = batch_size
        self.drop_last = drop_last

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


def default_collate_fn(batch: List):
    """Stack samples into batch arrays (reference
    fluid/dataloader/collate.py)."""
    sample = batch[0]
    if isinstance(sample, (list, tuple)):
        return tuple(default_collate_fn([b[i] for b in batch])
                     for i in range(len(sample)))
    if isinstance(sample, dict):
        return {k: default_collate_fn([b[k] for b in batch]) for k in sample}
    if hasattr(sample, "numpy"):
        return np.stack([np.asarray(b.numpy()) for b in batch])
    arr = np.asarray(batch)
    if arr.dtype == np.float64:
        arr = arr.astype(np.float32)
    return arr


class _StageIterator:
    """Consumer half of one background pipeline stage: bounded queue,
    ``_END`` marker, exception propagation, stop-event abandonment, and
    the ``input_wait_seconds`` accounting.  ``_PrefetchIterator`` (host
    batch assembly) and ``DevicePrefetcher`` (H2D transfer) are this
    plus a producer thread running ``_stage_fill``."""

    _END = object()

    def __init__(self, queue_size, record_wait=True):
        self._q = queue.Queue(maxsize=queue_size)
        self._exc_box: list = []
        self._stop_evt = threading.Event()
        self._done = False
        # input_wait_seconds is the TRAINING loop's stall metric: only
        # the OUTERMOST stage records it (an inner stage's queue waits
        # are background-thread idle time, not consumer stalls)
        self._record_wait = record_wait

    def _start(self, target, args):
        # the fill function must NOT hold a strong ref to self: a running
        # thread would keep the iterator alive forever and __del__ (the
        # worker-reaping trigger on abandonment) would never fire
        self._thread = threading.Thread(target=target, args=args,
                                        daemon=True)
        self._thread.start()

    def close(self):
        """Release the fill thread (and through it any worker processes)
        when the consumer abandons the iterator mid-epoch."""
        self._stop_evt.set()

    __del__ = close

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            # the single _END marker was already consumed and the fill
            # thread has exited: a re-entered exhausted iterator must
            # keep raising StopIteration, not block on an empty queue
            raise StopIteration
        if self._record_wait:
            import time as _time

            from ..observe.histogram import stat_time

            t0 = _time.perf_counter()
            item = self._q.get()
            stat_time("input_wait_seconds", _time.perf_counter() - t0)
        else:
            item = self._q.get()
        if item is self._END:
            self._done = True
            if self._exc_box:
                raise self._exc_box[0]
            raise StopIteration
        return item


class _PrefetchIterator(_StageIterator):
    """Background-thread prefetch (the reference buffered_reader /
    multiprocess worker role; threads suffice because workers mostly wait
    on IO and numpy releases the GIL)."""

    def __init__(self, make_batches, num_workers, prefetch_factor=2,
                 record_wait=True):
        super().__init__(max(2, num_workers * prefetch_factor),
                         record_wait=record_wait)
        self._start(_prefetch_fill,
                    (make_batches, self._q, self._exc_box, self._stop_evt))


def _stage_fill(gen, q, exc_box, stop_evt, end_marker, transform=None):
    """The one background pipeline-stage body (_PrefetchIterator and
    DevicePrefetcher both run this): pull items from ``gen``, optionally
    ``transform`` each, block-put into the bounded queue with stop-event
    polling, surface exceptions through ``exc_box``.

    The ``end_marker`` must ALWAYS reach the consumer, even when the
    queue is still full of undrained batches (e.g. an epoch with fewer
    batches than the queue capacity finishes before the consumer takes
    its first item) — a dropped marker blocks ``__next__`` forever.
    Block-put with the same stop-event polling as normal batches; only
    an explicit close() abandons delivery."""
    try:
        for b in gen:
            if transform is not None:
                b = transform(b)
            placed = False
            while not stop_evt.is_set():
                try:
                    q.put(b, timeout=0.25)
                    placed = True
                    break
                except queue.Full:
                    continue
            if not placed:
                break
    except BaseException as e:  # surfaced on the consumer side
        exc_box.append(e)
    finally:
        # abandonment path: closing the generator runs its finally,
        # which shuts down any worker processes it spawned
        if hasattr(gen, "close"):
            gen.close()
        while True:
            try:
                q.put(end_marker, timeout=0.25)
                break
            except queue.Full:
                if stop_evt.is_set():
                    break


def _prefetch_fill(make_batches, q, exc_box, stop_evt):
    _stage_fill(make_batches(), q, exc_box, stop_evt,
                _PrefetchIterator._END)


from ..framework.scope import is_device_array as _is_device_array  # noqa: E402


def _device_put_batch(batch, sharding):
    """Transfer every array leaf of ``batch`` (nested tuples/lists/
    dicts) to device, returning ``(device_batch, bytes_transferred)``.
    ``sharding`` may be a single jax Sharding/device applied to every
    leaf, or a dict/sequence matching the batch structure for per-feed
    placement.  Leaves that are already device arrays pass through
    untouched when no explicit sharding is requested (clean fallback
    for loaders that already yield device data)."""
    import jax

    n_bytes = 0

    def put(x, sh):
        nonlocal n_bytes
        if isinstance(x, dict):
            shs = sh if isinstance(sh, dict) else {k: sh for k in x}
            return {k: put(v, shs.get(k)) for k, v in x.items()}
        if isinstance(x, (list, tuple)):
            if isinstance(sh, (list, tuple)) and len(sh) == len(x):
                out = [put(v, s) for v, s in zip(x, sh)]
            else:
                out = [put(v, sh) for v in x]
            return tuple(out) if isinstance(x, tuple) else out
        if _is_device_array(x) and sh is None:
            return x  # already placed; nothing to transfer
        arr = x if hasattr(x, "nbytes") else np.asarray(x)
        n_bytes += int(getattr(arr, "nbytes", 0))
        return jax.device_put(arr, sh)

    return put(batch, sharding), n_bytes


def _device_prefetch_fill(it, q, exc_box, stop_evt, sharding):
    """Background transfer stage: pull host batches, ``jax.device_put``
    them (H2D overlaps device compute instead of serializing inside the
    jitted step call), queue device batches.  The queue/END/abandonment
    protocol is _stage_fill's — only the per-item transform differs."""
    from ..monitor import stat_add, stat_set
    from ..observe import tracer as otrace

    def to_device(b):
        with otrace.span("h2d_prefetch"):
            b, n = _device_put_batch(b, sharding)
            otrace.set_span_args(bytes=n)
        stat_set("h2d_bytes_per_step", n)
        stat_add("h2d_bytes_total", n)
        return b

    _stage_fill(it, q, exc_box, stop_evt, DevicePrefetcher._END,
                transform=to_device)


class DevicePrefetcher(_StageIterator):
    """Device-side input prefetch: wraps any batch iterable and moves
    the next ``prefetch_factor`` batches onto device from a background
    thread (double buffering), so the H2D transfer overlaps the device's
    compute instead of serializing inside the Executor's jitted call.

    ``sharding`` places leaves onto the step's feed sharding (a jax
    Sharding/device, or a dict/sequence matching the batch structure);
    ``None`` uses jax's default device.  Batches whose leaves are
    already device arrays pass through untouched.  Exceptions from the
    source iterable (or the transfer) surface on the consumer's
    ``next()``.  ``input_wait_seconds`` (histogram) records how long the
    consumer blocked per batch; ``h2d_bytes_per_step`` (gauge) /
    ``h2d_bytes_total`` (counter) and the ``h2d_prefetch`` tracer span
    account the transfers."""

    def __init__(self, iterable, prefetch_factor: int = 2, sharding=None):
        super().__init__(max(int(prefetch_factor), 1))
        it = iter(iterable)
        if isinstance(it, _StageIterator):
            # this stage is now the outermost: the inner stage's queue
            # waits happen on OUR background thread and must not be
            # recorded as training-loop input stalls.  Checked on the
            # ITERATOR — wrapping a DataLoader directly builds its
            # _PrefetchIterator only at iter()
            it._record_wait = False
        self._start(_device_prefetch_fill,
                    (it, self._q, self._exc_box, self._stop_evt, sharding))


_ENV_PIN_LOCK = threading.Lock()  # guards the JAX_PLATFORMS pin in start


def _worker_loop(wid, n_workers, dataset, collate, init_fn, task_q,
                 result_q, parent_pid):
    """Worker-process body.  Module-level so the spawn start method can
    pickle it by reference (a closure can't be).  Polls the task queue
    with a short timeout and watches the parent's liveness: if the
    parent is SIGKILL'd (daemon=True doesn't cover that), getppid() is
    reparented and the worker exits instead of surviving as an orphan
    holding queue/file state."""
    import os
    import queue as _q

    # Never touch the accelerator from a worker: the chip belongs to the
    # parent, and a stray jax.devices() in user dataset code must find
    # the CPU only.  The parent pins JAX_PLATFORMS around spawn (below).
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    global _worker_info
    _worker_info = WorkerInfo(wid, n_workers, dataset)
    if init_fn is not None:
        init_fn(wid)

    def put_watching_parent(item):
        """Bounded-queue put that also watches parent liveness — a
        worker blocked in put() when the parent is SIGKILL'd must exit,
        not survive as an orphan."""
        while True:
            try:
                result_q.put(item, timeout=2.0)
                return True
            except _q.Full:
                if os.getppid() != parent_pid:
                    return False

    while True:
        try:
            task = task_q.get(timeout=2.0)
        except _q.Empty:
            if os.getppid() != parent_pid:
                return  # parent died; don't orphan
            continue
        if task is None:
            return
        bid, idxs = task
        try:
            batch = collate([dataset[i] for i in idxs])
            ok = put_watching_parent((bid, batch, None))
        except BaseException:  # surfaced in the parent
            import traceback

            ok = put_watching_parent((bid, None, traceback.format_exc()))
        if not ok:
            return


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None, return_list=True,
                 batch_sampler=None, batch_size=1, shuffle=False,
                 drop_last=False, collate_fn=None, num_workers=0,
                 use_buffer_reader=True, prefetch_factor=2, use_shared_memory=True,
                 timeout=0, worker_init_fn=None, device_prefetch=False,
                 feed_sharding=None):
        self.dataset = dataset
        self.return_list = return_list
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = num_workers
        self.prefetch_factor = prefetch_factor
        self.use_buffer_reader = use_buffer_reader
        self.timeout = timeout
        self.worker_init_fn = worker_init_fn
        # device-side input prefetch (DevicePrefetcher): batches come
        # back with array leaves already jax.device_put onto
        # ``feed_sharding`` from a background transfer thread
        self.device_prefetch = device_prefetch
        self.feed_sharding = feed_sharding
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(dataset, shuffle=shuffle,
                                              batch_size=batch_size,
                                              drop_last=drop_last)

    def _batches(self):
        if self._iterable_mode:
            it = iter(self.dataset)
            while True:
                chunk = list(itertools.islice(it, self.batch_size))
                if not chunk:
                    return
                if len(chunk) < self.batch_size and self.drop_last:
                    return
                yield self.collate_fn(chunk)
        elif self.num_workers > 0:
            yield from self._worker_batches()
        else:
            for idxs in self.batch_sampler:
                yield self.collate_fn([self.dataset[i] for i in idxs])

    def _worker_batches(self):
        """Real worker PROCESSES (reference dataloader_iter.py:467
        _DataLoaderIterMultiProcess): workers pull (batch_id, indices)
        tasks, run dataset[i] + collate, and send pickled batches back
        over queues; the parent reassembles in order with a bounded
        in-flight window.

        Workers are SPAWNED, not forked: the parent is a jax-initialized
        multithreaded process (fork from it deadlocks, and forked
        children would inherit live TPU client state — an orphan can
        keep the chip unavailable to every later process).  Spawned
        children start interpreter-fresh with JAX_PLATFORMS=cpu pinned
        so they can never touch the device; they run only dataset +
        collate (numpy), matching the reference's CPU-only worker
        contract.  Fork remains an explicit opt-in
        (PADDLE_TPU_WORKER_START=fork) for jax-free embedders; threads
        are the fallback when the dataset doesn't pickle."""
        import multiprocessing as mp
        import os

        start = os.environ.get("PADDLE_TPU_WORKER_START", "spawn")
        try:
            ctx = mp.get_context(start)
        except ValueError:
            yield from self._thread_batches()
            return

        if getattr(mp.current_process(), "_inheriting", False):
            # POSITIVE spawn-bootstrap check: we are a spawned child
            # still importing an UNGUARDED __main__ (a script that
            # iterates a num_workers>0 loader at module top level).
            # Fork tolerated such scripts; serve this child's copy of
            # the top-level loop on threads instead of tripping
            # python's bootstrap error.
            import warnings

            warnings.warn(
                "DataLoader: this process is a spawned worker re-running "
                "an unguarded script top level; serving its loader on "
                "threads.  Wrap the script's entry point in `if __name__ "
                "== '__main__':` to avoid re-executing top-level code "
                "once per worker.", RuntimeWarning, stacklevel=3)
            yield from self._thread_batches()
            return

        n_workers = self.num_workers
        task_q = ctx.Queue()
        # one window constant governs BOTH the result-queue capacity and
        # the dispatch in-flight bound — they must stay equal or workers
        # block on a queue smaller than the dispatch window
        max_in_flight = max(2, n_workers * self.prefetch_factor)
        result_q = ctx.Queue(maxsize=max_in_flight)

        procs = [ctx.Process(
            target=_worker_loop,
            args=(w, n_workers, self.dataset, self.collate_fn,
                  self.worker_init_fn, task_q, result_q, os.getpid()),
            daemon=True) for w in range(n_workers)]
        # spawned children must never initialize a TPU backend even if
        # something in their import chain touches jax — pin them to cpu
        # for the duration of the exec (env is captured at start()).
        # Import jax in the parent FIRST so its platform config is
        # already snapshotted and the temporary env pin cannot leak
        # into a concurrent first jax import on another thread.
        import jax  # noqa: F401

        import pickle

        started = False
        # the save/set/restore of the process-global env var must not
        # interleave across loaders iterating concurrently (train+eval),
        # or one thread's restore can leak the cpu pin permanently
        with _ENV_PIN_LOCK:
            saved_jp = os.environ.get("JAX_PLATFORMS")
            os.environ["JAX_PLATFORMS"] = "cpu"
            try:
                for p in procs:
                    p.start()
                started = True
            except BaseException as e:
                for p in procs:  # reap whatever partially started
                    if p.is_alive():
                        p.terminate()
                import warnings

                if isinstance(e, (pickle.PicklingError, TypeError,
                                  AttributeError)):
                    # spawn pickles (dataset, collate_fn,
                    # worker_init_fn) by value; closures / local
                    # classes don't pickle — degrade to the thread pool
                    # rather than erroring the epoch.  Loudly: threads
                    # are GIL-bound and skip worker_init_fn /
                    # get_worker_info semantics.
                    warnings.warn(
                        f"DataLoader: dataset/collate_fn/worker_init_fn "
                        f"not picklable for spawned workers ({e!r}); "
                        f"falling back to a thread pool (GIL-bound, no "
                        f"worker_init_fn / get_worker_info). Move the "
                        f"dataset class to module scope for real "
                        f"worker processes.", RuntimeWarning,
                        stacklevel=3)
                else:
                    # real errors (resource limits, …): propagate
                    # rather than silently changing the execution model
                    raise
            finally:
                if saved_jp is None:
                    os.environ.pop("JAX_PLATFORMS", None)
                else:
                    os.environ["JAX_PLATFORMS"] = saved_jp
        if not started:
            yield from self._thread_batches()
            return

        # timeout=0 (the default) means NO user deadline — block as long
        # as workers are alive (reference semantics); dead workers are
        # still detected on a liveness poll
        user_timeout = float(self.timeout) if self.timeout else None
        pending = {}  # bid -> batch, out-of-order arrivals
        next_out = 0
        dispatched = 0
        sampler_it = iter(self.batch_sampler)

        def recv():
            nonlocal next_out
            import queue as _q
            import time as _time

            # poll in <=10s slices even under a long user timeout so a
            # dead worker is diagnosed within seconds, not at deadline
            deadline = (_time.monotonic() + user_timeout) \
                if user_timeout else None
            while next_out not in pending:
                slice_t = 10.0 if deadline is None else max(
                    0.1, min(10.0, deadline - _time.monotonic()))
                try:
                    bid, batch, err = result_q.get(timeout=slice_t)
                except _q.Empty:
                    dead = [w for w, p in enumerate(procs)
                            if not p.is_alive()]
                    if dead:
                        raise RuntimeError(
                            f"DataLoader worker(s) {dead} died without "
                            f"producing their batch") from None
                    if deadline is not None and \
                            _time.monotonic() >= deadline:
                        raise RuntimeError(
                            f"DataLoader produced no batch within the "
                            f"configured timeout={user_timeout}s") from None
                    continue  # workers alive, deadline not hit: wait on
                if err is not None:
                    raise RuntimeError(
                        f"DataLoader worker failed on batch {bid}:\n{err}")
                pending[bid] = batch
            out = pending.pop(next_out)
            next_out += 1
            return out

        try:
            exhausted = False
            while True:
                while not exhausted and dispatched - next_out \
                        - len(pending) < max_in_flight:
                    try:
                        idxs = next(sampler_it)
                    except StopIteration:
                        exhausted = True
                        break
                    task_q.put((dispatched, list(idxs)))
                    dispatched += 1
                if next_out >= dispatched and exhausted:
                    return
                yield recv()
        finally:
            for _ in procs:
                task_q.put(None)
            for p in procs:
                p.join(timeout=5)
                if p.is_alive():
                    p.terminate()

    def _thread_batches(self):
        from concurrent.futures import ThreadPoolExecutor

        def load(idxs):
            return self.collate_fn([self.dataset[i] for i in idxs])

        in_flight = []
        max_in_flight = self.num_workers * self.prefetch_factor
        with ThreadPoolExecutor(self.num_workers) as pool:
            for idxs in self.batch_sampler:
                in_flight.append(pool.submit(load, idxs))
                while len(in_flight) >= max_in_flight:
                    yield in_flight.pop(0).result()
            for f in in_flight:
                yield f.result()

    def __iter__(self):
        if self.use_buffer_reader:
            it = _PrefetchIterator(self._batches, max(self.num_workers, 1),
                                   self.prefetch_factor,
                                   record_wait=not self.device_prefetch)
        else:
            it = self._batches()
        if self.device_prefetch:
            it = DevicePrefetcher(it, prefetch_factor=self.prefetch_factor,
                                  sharding=self.feed_sharding)
        return it

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("length of an IterableDataset loader is unknown")
        return len(self.batch_sampler)


class WorkerInfo:
    """Reference fluid.dataloader worker_info: visible only inside a
    worker process."""

    def __init__(self, wid, num_workers, dataset):
        self.id = wid
        self.num_workers = num_workers
        self.dataset = dataset


_worker_info = None


def get_worker_info():
    return _worker_info  # None in the main process


from .data_feed import MultiSlotDataFeed  # noqa: E402,F401
