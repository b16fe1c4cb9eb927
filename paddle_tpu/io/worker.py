"""Multiprocess DataLoader workers over the native shared-memory rings.

Parity: python/paddle/io/dataloader/dataloader_iter.py:358
(_DataLoaderIterMultiProcess) + worker.py — N forked worker processes,
each assembling its round-robin share of batches and pushing them through
shared memory; the trainer consumes worker rings in round-robin order,
which restores the global batch order without an explicit reorder buffer
(worker i emits its batches in order).

TPU caveat handled here: workers are forked and must never touch the
accelerator — batches are converted to numpy inside the worker, and the
fork happens lazily at iterator start (the launcher-style import path
keeps jax uninitialized, but a trainer process will already own the TPU,
so workers touch only numpy + the native ring).
"""
from __future__ import annotations

import multiprocessing as mp
import os
import threading
import struct
from typing import List

import numpy as np

from .shm_queue import SENTINEL, ShmQueue, encode_batch


class WorkerInfo:
    def __init__(self, id: int, num_workers: int, dataset, seed: int):
        self.id = id
        self.num_workers = num_workers
        self.dataset = dataset
        self.seed = seed


_worker_info = None


def get_worker_info():
    """Inside a worker: its WorkerInfo; None in the main process
    (parity: paddle.io.get_worker_info)."""
    return _worker_info


def _to_numpy_batch(batch) -> List[np.ndarray]:
    out = []
    for item in batch if isinstance(batch, (list, tuple)) else [batch]:
        if hasattr(item, "numpy"):
            out.append(np.asarray(item.numpy()))
        else:
            out.append(np.asarray(item))
    return out


def _worker_loop(dataset, index_batches, collate_fn, qname, worker_id,
                 num_workers, init_fn, seed):
    # data-prep workers are host-side: pin the child to the CPU backend
    # BEFORE any jax array op, so a worker never initializes the
    # accelerator it inherited via env — a chip belongs to one process,
    # and that process is the trainer. jax is already imported (it read
    # JAX_PLATFORMS then), so the live config is set too.
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax as _jax
    _jax.config.update("jax_platforms", "cpu")
    global _worker_info
    _worker_info = WorkerInfo(worker_id, num_workers, dataset, seed)
    np.random.seed((seed + worker_id) % (2 ** 32))
    if init_fn is not None:
        init_fn(worker_id)
    q = ShmQueue(qname)
    try:
        if index_batches is None:  # IterableDataset: shard by item index
            batch = []
            bs = collate_fn.batch_size
            for i, item in enumerate(dataset):
                if i % num_workers != worker_id:
                    continue
                batch.append(item)
                if len(batch) == bs:
                    q.push(encode_batch(_to_numpy_batch(
                        collate_fn(batch))), timeout_s=300)
                    batch = []
            if batch and not collate_fn.drop_last:
                q.push(encode_batch(_to_numpy_batch(collate_fn(batch))),
                       timeout_s=300)
        else:
            for idx_batch in index_batches:
                samples = [dataset[i] for i in idx_batch]
                q.push(encode_batch(_to_numpy_batch(collate_fn(samples))),
                       timeout_s=300)
        q.push(SENTINEL, timeout_s=300)
    except (BrokenPipeError, TimeoutError):
        pass  # consumer gone: exit quietly
    finally:
        q.close()
    os._exit(0)  # skip atexit/jax teardown inherited from the parent


_ENV_SCRUB_LOCK = threading.Lock()


class WorkerStartupError(RuntimeError):
    """Worker processes could not start (most commonly: the dataset or
    collate_fn is not picklable under the spawn/forkserver start method)."""


class _CollateWrap:
    """Picklable-by-fork collate carrier for the iterable path."""

    def __init__(self, fn, batch_size, drop_last):
        self.fn = fn
        self.batch_size = batch_size
        self.drop_last = drop_last

    def __call__(self, batch):
        return self.fn(batch)


class MultiprocessLoaderIter:
    """Consumer side: fork workers, round-robin the rings in order."""

    def __init__(self, loader, shm_capacity: int = 64 << 20,
                 timeout: float = 300.0):
        self.loader = loader
        self.num_workers = loader.num_workers
        self.timeout = timeout if timeout > 0 else 300.0
        # fork after JAX has spun up its runtime threads deadlocks (the child
        # inherits locked mutexes); forkserver forks from a clean helper
        # process instead. Parity: the reference defaults to fork but its
        # dataloader documents the same hazard
        # (python/paddle/io/dataloader/dataloader_iter.py:358).
        from ..core import flags as _flags
        method = _flags.get_flag("dataloader_start_method") or "forkserver"
        ctx = mp.get_context(method)
        seed = int.from_bytes(os.urandom(4), "little")
        uid = f"{os.getpid()}_{id(self)}"
        self.queues = [
            ShmQueue(f"/ptpu_dl_{uid}_{w}",
                     capacity=shm_capacity // self.num_workers, create=True)
            for w in range(self.num_workers)]
        collate = _CollateWrap(loader.collate_fn, loader.batch_size,
                               loader.drop_last)
        if loader.batch_sampler is not None:
            all_batches = list(loader.batch_sampler)
            shares = [all_batches[w::self.num_workers]
                      for w in range(self.num_workers)]
        else:
            shares = [None] * self.num_workers
        self.procs = []
        # shutdown() can race itself: the consumer thread reaches it via
        # StopIteration while GC runs __del__ on another thread (the
        # usual shape: a DevicePrefetcher's producer thread is draining
        # this iter when the owning loader is collected). Both used to
        # pass the "already shut down?" check and double-close the
        # native shm handles (shmq_close on a freed handle). The lock
        # makes exactly one caller the closer; created before the
        # worker-start loop because a start failure calls shutdown()
        # from inside __init__.
        self._shutdown_lock = threading.Lock()
        # Serialize the env scrub across threads: the window mutates
        # process-global env, so concurrent iterator construction must
        # not interleave save/restore (and the window is kept as short
        # as possible — only the Process.start calls).
        # Children must inherit a CPU-pinned jax: dataset args can hold
        # jax arrays whose UNPICKLING (before _worker_loop's own guard
        # runs) initializes the default backend — on a TPU host that
        # would have every data worker reach for the chip the trainer
        # holds. The guard also covers the forkserver helper, which
        # captures env at its first boot.
        scrub = {"JAX_PLATFORMS": "cpu"}
        _ENV_SCRUB_LOCK.acquire()
        prev_env = {k: os.environ.get(k) for k in scrub}
        os.environ.update(scrub)
        try:
            for w in range(self.num_workers):
                p = ctx.Process(
                    target=_worker_loop,
                    args=(loader.dataset, shares[w], collate,
                          self.queues[w].name, w, self.num_workers,
                          loader.worker_init_fn, seed),
                    daemon=True)
                try:
                    p.start()
                except Exception as e:
                    self.shutdown()
                    raise WorkerStartupError(
                        f"could not start DataLoader worker {w} under the "
                        f"'{method}' start method: {e}") from e
                self.procs.append(p)
        finally:
            for k, v in prev_env.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
            _ENV_SCRUB_LOCK.release()
        self._done = [False] * self.num_workers
        self._started = [False] * self.num_workers
        self._t0 = __import__("time").monotonic()
        # workers re-import the framework (jax alone is ~5s) under
        # forkserver; the user-facing timeout must not tick during startup
        # (reference: its timeout is per-batch once workers are live)
        self._startup_grace = 120.0
        self._next = 0

    def __iter__(self):
        return self

    def __next__(self):
        import time

        from .shm_queue import decode_batch
        while not all(self._done):
            w = self._next
            self._next = (self._next + 1) % self.num_workers
            if self._done[w]:
                # graft-lint: disable=GL705 -- bounded skip, not a spin:
                # rotates to the next non-done worker (at most
                # num_workers hops) and that worker's ring.pop blocks
                continue
            # take the ring/process references under the shutdown lock:
            # a concurrent shutdown() (e.g. GC __del__ on another
            # thread) swaps the lists out, and this iteration must end
            # cleanly rather than index into the emptied lists
            with self._shutdown_lock:
                if not self.queues:
                    raise StopIteration
                ring, proc = self.queues[w], self.procs[w]
            # poll in short slices so a dead worker is detected promptly
            # instead of only after the full user-facing timeout
            deadline = time.monotonic() + self.timeout
            rec = None
            while True:
                remaining = deadline - time.monotonic()
                try:
                    rec = ring.pop(
                        timeout_s=max(0.05, min(1.0, remaining)))
                    self._started[w] = True
                    break
                except TimeoutError:
                    if not proc.is_alive():
                        # exit/drain race: the worker may have pushed its
                        # remaining batches + sentinel and exited between
                        # our pop slice expiring and this liveness check.
                        # Its exit happens-after its pushes, so one more
                        # drain pop observes anything it left behind; only
                        # an exited worker with an EMPTY ring (sentinel
                        # never delivered) has actually died.
                        try:
                            rec = ring.pop(timeout_s=0.05)
                            self._started[w] = True
                            break
                        except TimeoutError:
                            pass
                        self.shutdown()
                        raise RuntimeError(
                            f"DataLoader worker {w} died (exit code "
                            f"{proc.exitcode})") from None
                    if remaining <= 0:
                        if not self._started[w] and \
                                time.monotonic() - self._t0 < \
                                self._startup_grace:
                            # still importing/booting: extend, don't fail
                            deadline = time.monotonic() + self.timeout
                            continue
                        raise
            if rec is None:
                self._done[w] = True
                continue
            batch = decode_batch(memoryview(rec))
            if batch is None:  # sentinel
                self._done[w] = True
                continue
            from ..core.tensor import Tensor
            return tuple(Tensor(a) for a in batch) if len(batch) > 1 \
                else (Tensor(batch[0]),)
        self.shutdown()
        raise StopIteration

    def shutdown(self):
        with self._shutdown_lock:
            if not self.queues:
                return  # idempotent: StopIteration, __del__, and error
                # paths all call this; only the first caller closes
            queues, self.queues = self.queues, []
            procs, self.procs = self.procs, []
        for q in queues:
            try:
                q.mark_closed()
            except Exception:
                pass
        for p in procs:
            p.join(timeout=2)
            if p.is_alive():
                p.terminate()
        for q in queues:
            q.close()

    def __del__(self):
        try:
            self.shutdown()
        except Exception:
            pass
