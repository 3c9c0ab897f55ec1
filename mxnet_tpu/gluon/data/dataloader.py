"""DataLoader with background prefetch.

Reference: ``python/mxnet/gluon/data/dataloader.py`` (_MultiWorkerIter with
multiprocessing workers + POSIX-shm zero-copy batches — SURVEY.md §3.4).

TPU-native: the default host-side pipeline is a thread pool (NumPy decode
releases the GIL in the hot paths) feeding a device-prefetch queue — same
shape as the reference's parser→batcher→prefetcher pipeline (§4.5), and
threads never fight the TPU runtime for the process space.  For GIL-bound
user transforms (pure-Python ``transform_fn``s that never release the
GIL), pass ``thread_pool=False`` to get PROCESS workers — the reference's
multiprocessing design with pickle transport: workers run dataset[i] +
batchify to plain numpy (no device runtime in children) and the parent
converts to NDArray.  ``num_workers`` sizes either pool.
"""
from __future__ import annotations

import queue
import threading
import time as _time
from concurrent.futures import ThreadPoolExecutor

import numpy as _np

from ... import telemetry
from .dataset import Dataset
from .sampler import BatchSampler, RandomSampler, SequentialSampler, Sampler

__all__ = ["DataLoader", "default_batchify_fn", "default_mp_batchify_fn"]

_BATCH_WAIT = telemetry.histogram(
    "mxnet_dataloader_batch_wait_seconds",
    "time the consumer waited for the next batch")
_BATCHES_TOTAL = telemetry.counter(
    "mxnet_dataloader_batches_total", "batches yielded")
_WORKERS_GAUGE = telemetry.gauge(
    "mxnet_dataloader_workers",
    "live process-pool workers (of the most recently active loader)")
_WORKER_DEATHS = telemetry.counter(
    "mxnet_dataloader_worker_deaths_total",
    "abnormal process-worker deaths detected mid-epoch")


def default_batchify_fn(data):
    """Stack samples into a batch (reference: default_batchify_fn)."""
    from ...ndarray.ndarray import NDArray, array

    if isinstance(data[0], NDArray):
        import jax.numpy as jnp

        return array(_np.stack([d.asnumpy() for d in data]))
    if isinstance(data[0], tuple):
        return tuple(default_batchify_fn(list(d)) for d in zip(*data))
    arr = _np.asarray(data)
    return array(arr)


def default_mp_batchify_fn(data):
    """Stack samples into a NUMPY batch — the worker-process batchify
    (reference: default_mp_batchify_fn building shared-memory NDArrays).
    Children must not touch the device runtime; the parent converts."""
    if isinstance(data[0], tuple):
        return tuple(default_mp_batchify_fn(list(d)) for d in zip(*data))
    if hasattr(data[0], "asnumpy"):
        return _np.stack([d.asnumpy() for d in data])
    return _np.asarray(data)


_worker_dataset = None
# set in the CHILD when the jax CPU pin failed there: the chip belongs to
# the parent, and a mis-pinned worker that reaches for it fails or hangs
# in a way that otherwise never points back to this cause
_worker_pin_error = None


def _worker_initializer(dataset):
    global _worker_dataset, _worker_pin_error
    _worker_dataset = dataset
    # pin any jax use in this child to CPU BEFORE its first dispatch: the
    # chip belongs to the parent.  Effective for spawn children and for
    # fork children whose parent has not initialized a device backend yet
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
    except Exception as e:
        import logging
        import os as _os

        _worker_pin_error = f"{type(e).__name__}: {e}"
        logging.getLogger(__name__).warning(
            "DataLoader worker pid=%d: jax CPU pin failed (%s) — this "
            "child may initialize the device runtime", _os.getpid(),
            _worker_pin_error)


def _terminate_pool(pool, stops=()):
    # unblock any active epoch's gated() generator FIRST: the pool's
    # task-handler thread sits inside it, and terminate() joins that
    # thread — without the stop signal the join deadlocks
    for s in list(stops):
        s.set()
    pool.terminate()
    pool.join()


class _WorkerFn:
    """Picklable per-batch task: dataset[i] for the batch + batchify."""

    def __init__(self, batchify_fn):
        self._fn = batchify_fn

    def __call__(self, batch):
        from ... import fault

        # seam is armed via MXNET_FAULT_SPEC (the env reaches spawn
        # children) — in-process inject() plans do not cross the fork
        fault.check("dataloader.worker")
        try:
            return self._fn([_worker_dataset[i] for i in batch])
        except Exception as e:
            if _worker_pin_error is not None:
                # the pickled traceback loses child-side logs; carry the
                # pin diagnosis inside the exception that crosses back
                raise RuntimeError(
                    f"{type(e).__name__}: {e} [worker jax CPU pin had "
                    f"failed: {_worker_pin_error}]") from e
            raise


def _to_nd(out):
    from ...ndarray.ndarray import array

    if isinstance(out, tuple):
        return tuple(_to_nd(o) for o in out)
    if isinstance(out, _np.ndarray):
        return array(out)
    return out


class DataLoader:
    def __init__(self, dataset, batch_size=None, shuffle=False, sampler=None,
                 last_batch=None, batch_sampler=None, batchify_fn=None,
                 num_workers=0, pin_memory=False, prefetch=None,
                 thread_pool=True, prefetch_to_device=None):
        """``prefetch_to_device``: overlap host→device staging with the
        training step (gluon/data/prefetcher.py).  ``True`` prefetches to
        the default device; a ``jax.sharding.Sharding`` (e.g. a
        TrainStep's ``_batch_shard``) places the global batch.  Depth is
        ``MXNET_PREFETCH_BUFFER`` (default 2; 0 turns the pipeline off
        and batches stage inline on the consumer's thread)."""
        self._dataset = dataset
        if batch_sampler is None:
            if batch_size is None:
                raise ValueError("batch_size required when batch_sampler is None")
            if sampler is None:
                sampler = RandomSampler(len(dataset)) if shuffle else \
                    SequentialSampler(len(dataset))
            elif shuffle:
                raise ValueError("shuffle must be False with custom sampler")
            batch_sampler = BatchSampler(sampler, batch_size, last_batch or "keep")
        self._batch_sampler = batch_sampler
        self._batchify_fn = batchify_fn or default_batchify_fn
        self._num_workers = num_workers
        self._thread_pool = thread_pool
        self._prefetch = max(0, prefetch or 2 * max(num_workers, 1))
        self._prefetch_to_device = prefetch_to_device
        self._proc_pool = None          # persistent process pool (spawn is
        self._proc_pool_method = None   # expensive: pay startup once)
        self._pool_finalizer = None
        self._active_stops = set()      # stop events of live epoch iters
        # exact-resume position (lifecycle.capture_train_state): epoch of
        # the iterator currently live, batches the CONSUMER received from
        # it, the batch-sampler state as of that epoch's start, and a
        # pending resume point applied by the next __iter__
        self._epoch = -1
        self._batches_served = 0
        self._epoch_start_state = None
        self._skip_next = 0
        self._resume = None

    def __len__(self):
        return len(self._batch_sampler)

    def state_dict(self):
        """Resume point for :meth:`load_state_dict`: the live epoch, how
        many batches the consumer already received from it, and the
        batch-sampler state as of the epoch start (shuffle seed + epoch
        + rollover carry).  Capture at a step boundary; state tracking
        assumes ONE active iterator per loader (the training loop's)."""
        if self._resume is not None:
            # captured before the armed resume point was consumed by an
            # __iter__: the position is still the armed one
            return dict(self._resume)
        return {"epoch": max(self._epoch, 0),
                "batch": self._batches_served,
                "sampler": self._epoch_start_state}

    def load_state_dict(self, state):
        """Arm the next ``__iter__`` to resume at ``state``: the sampler
        regenerates the recorded epoch's index sequence and the first
        ``state["batch"]`` batches are skipped DECODE-FREE — only index
        lists are consumed, ``dataset[i]`` is never called for them —
        so fast-forwarding a multi-epoch position costs microseconds,
        not an epoch of decode."""
        self._resume = dict(state or {})

    def _begin_epoch(self):
        """Apply epoch numbering (and any armed resume point) before the
        underlying iterator is built; returns nothing, sets counters."""
        resume, self._resume = self._resume, None
        if resume is not None:
            self._epoch = int(resume.get("epoch") or 0)
            self._skip_next = int(resume.get("batch") or 0)
            sd = resume.get("sampler")
            if sd is not None and hasattr(self._batch_sampler,
                                          "load_state_dict"):
                self._batch_sampler.load_state_dict(sd)
            elif self._skip_next:
                # no captured sampler state, OR state that the rebuilt
                # sampler cannot load: we can fast-forward the COUNT but
                # not replay the order — if the sampler reshuffles,
                # skipped batches come from a DIFFERENT permutation and
                # data is silently repeated or lost.  Exact resume needs
                # state_dict AND load_state_dict (and ideally set_epoch)
                # on the batch sampler.
                import warnings

                warnings.warn(
                    "DataLoader resume: the batch sampler "
                    + ("recorded no state (no state_dict())"
                       if sd is None else
                       "cannot restore its recorded state "
                       "(no load_state_dict())")
                    + f"; skipping {self._skip_next} batches of a "
                    "potentially DIFFERENT order — the resumed sequence "
                    "is only bit-identical for deterministic samplers",
                    stacklevel=3)
        else:
            self._epoch += 1
            self._skip_next = 0
        se = getattr(self._batch_sampler, "set_epoch", None)
        if se is not None:
            se(self._epoch)
        self._epoch_start_state = self._batch_sampler.state_dict() \
            if hasattr(self._batch_sampler, "state_dict") else None
        # skipped batches were already consumed by the killed run
        self._batches_served = self._skip_next

    def _epoch_batches(self):
        """Index-batches of the current epoch, with the resume skip
        applied: the fast-forward drains index lists only — decode-free."""
        it = iter(self._batch_sampler)
        skip, self._skip_next = self._skip_next, 0
        for _ in range(skip):
            if next(it, None) is None:
                return
        yield from it

    def __iter__(self):
        # batch-wait attribution: time from the consumer asking for the
        # next batch to it being ready — with a prefetching pool this is
        # the stall the training loop actually feels, the "data wait"
        # answer to "why was this step slow?"  The device prefetcher sits
        # INSIDE this measurement so the histogram shows the shrink.
        self._begin_epoch()
        it = self._iter_impl()
        pf = None
        if self._prefetch_to_device:
            from .prefetcher import PrefetchIterator

            sharding = self._prefetch_to_device \
                if self._prefetch_to_device is not True else None
            pf = it = PrefetchIterator(it, sharding=sharding)
        try:
            while True:
                t0 = _time.perf_counter()
                try:
                    batch = next(it)
                except StopIteration:
                    return
                _BATCH_WAIT.observe(_time.perf_counter() - t0)
                _BATCHES_TOTAL.inc()
                self._batches_served += 1
                yield batch
        finally:
            # runs on exhaustion, break, and generator GC alike — a
            # SIGKILLed worker's error must not strand the prefetch thread
            if pf is not None:
                pf.close()

    def _iter_impl(self):
        if self._num_workers == 0:
            for batch in self._epoch_batches():
                yield self._batchify_fn([self._dataset[i] for i in batch])
            return
        if self._thread_pool:
            yield from self._threaded_iter()
        else:
            yield from self._process_iter()

    def _process_iter(self):
        """Process workers for GIL-bound transforms (reference:
        _MultiWorkerIter).  Workers produce numpy batches (pickle
        transport); the parent converts to NDArray.

        Start method defaults to ``spawn``: the parent is effectively
        always multi-threaded (prefetch ThreadPoolExecutor, jax runtime
        internals), and fork() from a multi-threaded process can deadlock
        children on inherited locks (Python 3.12 DeprecationWarning) — and
        a forked child would also inherit a live TPU client (the chip
        belongs to the parent).  ``fork`` remains an explicit opt-in via
        MXNET_MP_START_METHOD=fork (``forkserver`` also accepted).  Spawn
        imposes the standard multiprocessing contract fork did not: the
        dataset/batchify must be picklable (no lambdas) and scripts that
        iterate a DataLoader at module top level need an
        ``if __name__ == "__main__":`` guard.  Either way the worker
        initializer pins jax in the child to CPU before any dispatch.

        The pool PERSISTS across epochs (a spawn startup per __iter__
        would cost num_workers interpreter launches + imports every
        epoch): workers snapshot the dataset once at pool creation, so
        in-place dataset mutations between epochs are not visible to
        process workers — build a new DataLoader for a new dataset."""
        import multiprocessing as mp

        from ...base import MXNetError

        fn = self._batchify_fn
        if fn is default_batchify_fn:
            fn = default_mp_batchify_fn
        pool = self._get_proc_pool()
        # bound in-flight work: imap's feeder thread would otherwise
        # enqueue the whole epoch and buffer every finished batch.  The
        # stop event unblocks the feeder if the consumer abandons the
        # iterator early (queued tasks drain harmlessly in the background
        # of the persistent pool).
        sem = threading.BoundedSemaphore(self._num_workers + self._prefetch)
        stop = threading.Event()
        # registered so close()/pool teardown can unblock gated() even
        # when this generator was abandoned without being closed
        self._active_stops.add(stop)

        def gated():
            for b in self._epoch_batches():
                while not sem.acquire(timeout=0.1):
                    if stop.is_set():
                        return
                if stop.is_set():
                    return
                yield b

        # liveness snapshot: Pool's maintenance thread silently replaces a
        # dead worker in pool._pool, but the batch the casualty held never
        # completes — a blind `for out in imap(...)` then hangs forever.
        # Holding the ORIGINAL Process objects lets the poll below see the
        # death (exitcode flips non-None; workers never exit on their own
        # while the pool lives, so any exit mid-epoch is abnormal).
        workers = list(pool._pool)
        it = pool.imap(_WorkerFn(fn), gated())
        idx = 0
        try:
            while True:
                try:
                    out = it.next(timeout=0.2)
                except StopIteration:
                    break
                except mp.TimeoutError:
                    dead = [p for p in workers if p.exitcode is not None]
                    _WORKERS_GAUGE.set(len(workers) - len(dead))
                    if dead:
                        _WORKER_DEATHS.inc(len(dead))
                        # the pool's task bookkeeping is now unknowable
                        # (the dead child's in-flight batch is lost);
                        # discard it so the NEXT epoch gets clean workers.
                        # stop MUST be set before teardown: the pool's
                        # task-handler thread is inside gated() and the
                        # teardown joins it
                        stop.set()
                        self._abandon_proc_pool()
                        raise MXNetError(
                            "DataLoader process worker(s) died while "
                            f"computing batch {idx}: "
                            + ", ".join(f"pid={p.pid} exitcode={p.exitcode}"
                                        for p in dead)
                            + " (killed by the OOM killer or a signal?); "
                            "the worker pool was recycled — re-iterate to "
                            "respawn workers")
                    continue
                except MXNetError:
                    raise
                except Exception as e:
                    # worker-side failure pickled back through imap: name
                    # the batch so the bad sample/transform is findable
                    raise MXNetError(
                        f"DataLoader worker failed on batch {idx}: "
                        f"{type(e).__name__}: {e}") from e
                sem.release()
                yield _to_nd(out)
                idx += 1
        finally:
            stop.set()
            self._active_stops.discard(stop)

    def _get_proc_pool(self):
        import multiprocessing as mp
        import os
        import weakref

        method = os.environ.get("MXNET_MP_START_METHOD") or "spawn"
        if self._proc_pool is not None and self._proc_pool_method == method:
            return self._proc_pool
        self._shutdown_proc_pool()
        ctx = mp.get_context(method)
        prev = os.environ.get("JAX_PLATFORMS")
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            pool = ctx.Pool(self._num_workers,
                            initializer=_worker_initializer,
                            initargs=(self._dataset,))
        finally:
            if prev is None:
                os.environ.pop("JAX_PLATFORMS", None)
            else:
                os.environ["JAX_PLATFORMS"] = prev
        self._proc_pool = pool
        self._proc_pool_method = method
        _WORKERS_GAUGE.set(self._num_workers)
        # terminate workers when the loader is garbage collected (or at
        # interpreter exit) — __del__ alone is not reliable enough for
        # child processes.  The finalizer carries the stop-event set (no
        # strong ref back to self) so a teardown that fires while an
        # epoch iterator is still alive does not deadlock on the
        # task-handler join.
        self._pool_finalizer = weakref.finalize(
            self, _terminate_pool, pool, self._active_stops)
        return pool

    def _shutdown_proc_pool(self):
        for s in list(self._active_stops):
            s.set()   # see _terminate_pool: unblock gated() before join
        if self._pool_finalizer is not None:
            self._pool_finalizer()  # terminates + joins, idempotent
            self._pool_finalizer = None
        if self._proc_pool is not None:
            _WORKERS_GAUGE.set(0)   # a scrape after close() must not
        self._proc_pool = None      # report the dead pool as live
        self._proc_pool_method = None

    def _abandon_proc_pool(self):
        """Discard a pool poisoned by an abnormal worker death.  A
        SIGKILLed child may have died holding a shared queue lock, so the
        orderly terminate+join of ``_shutdown_proc_pool`` can deadlock
        the parent: instead detach the finalizer (it must not re-run the
        blocking teardown at GC/exit), hard-kill the remaining children,
        and run the blocking teardown on a daemon thread — the iterator
        raises immediately and interpreter exit is never held hostage."""
        pool = self._proc_pool
        if pool is None:
            return
        if self._pool_finalizer is not None:
            self._pool_finalizer.detach()
            self._pool_finalizer = None
        self._proc_pool = None
        self._proc_pool_method = None
        _WORKERS_GAUGE.set(0)
        for p in list(pool._pool):
            try:
                p.kill()
            except Exception:  # already reaped
                pass
        threading.Thread(target=_terminate_pool, args=(pool,),
                         daemon=True).start()

    def close(self):
        """Release the persistent worker processes now instead of at GC /
        interpreter exit.  The loader remains usable — the next process-
        worker epoch starts a fresh pool.  Also usable as a context
        manager: ``with DataLoader(...) as dl: ...``."""
        self._shutdown_proc_pool()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def _threaded_iter(self):
        pool = ThreadPoolExecutor(max_workers=self._num_workers)
        batches = list(self._epoch_batches())

        def load(batch):
            return self._batchify_fn([self._dataset[i] for i in batch])

        try:
            futures = queue.Queue()
            it = iter(batches)
            # prime the prefetch window
            primed = 0
            for batch in it:
                futures.put(pool.submit(load, batch))
                primed += 1
                if primed >= self._prefetch:
                    break
            while not futures.empty():
                f = futures.get()
                try:
                    nxt = next(it)
                    futures.put(pool.submit(load, nxt))
                except StopIteration:
                    pass
                yield f.result()
        finally:
            pool.shutdown(wait=False, cancel_futures=True)
