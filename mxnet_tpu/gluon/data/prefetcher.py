"""Device prefetch: keep the next N batches in flight on the accelerator.

Reference analog: the dependency-engine overlap of the source paper's input
pipeline (PAPER §1 — the accelerator never waits on the host because staging
overlaps compute) and tf.data's ``prefetch_to_device`` (PAPERS.md).  A
background thread pulls batches from the source iterator and issues
**non-blocking** ``jax.device_put`` — transfers ride the DMA engines while
the previous step computes — so the consumer's ``data`` phase collapses to a
queue pop.

Depth is ``MXNET_PREFETCH_BUFFER`` (default 2: one batch transferring, one
ready; ``0`` disables and the iterator degrades to a plain pass-through
staging wrapper on the caller's thread).

Failure domain (PR 2 contract): the prefetch thread is a *consumer* of the
DataLoader's worker-liveness machinery — a SIGKILLed process worker raises
``MXNetError`` inside the thread within the liveness deadline, and that
error is re-raised to the training loop on its next batch request, never
swallowed and never a hang.  ``close()`` (also wired through a GC
finalizer) unblocks and joins the thread even when the consumer abandons
the epoch mid-way.
"""
from __future__ import annotations

import queue
import threading
import time as _time
import weakref

import numpy as _np

from ... import env as _env
from ... import telemetry as _telemetry
from ...base import MXNetError

__all__ = ["PrefetchIterator", "device_put_batch", "stage_leaf"]

_HITS = _telemetry.counter(
    "mxnet_prefetch_hits_total",
    "batch requests served from a ready (already prefetched) batch")
_MISSES = _telemetry.counter(
    "mxnet_prefetch_misses_total",
    "batch requests that had to wait on the prefetch pipeline")
_DEPTH = _telemetry.gauge(
    "mxnet_prefetch_depth",
    "batches staged and ready (of the most recently active prefetcher)")
_WAIT = _telemetry.histogram(
    "mxnet_prefetch_wait_seconds",
    "time the consumer blocked waiting for a prefetched batch")
_STAGE = _telemetry.histogram(
    "mxnet_prefetch_stage_seconds",
    "time the producer thread spent staging one batch on the device")

_ITEM, _END, _ERR = 0, 1, 2


def stage_leaf(host, sharding):
    """Place ONE array under ``sharding`` — the single decision tree every
    staging path shares (prefetcher, ``TrainStep._stage_batch``), so the
    subtle multi-process placement logic cannot drift between copies:

    - ``sharding=None``: default device;
    - already a ``jax.Array`` with the target sharding: zero-copy pass;
    - single process: plain ``device_put`` (handles resharding too);
    - multi-process: the value is this process's LOCAL shard of the
      global batch — assemble per-addressable-shard (``device_put`` would
      raise on a sharding spanning non-addressable devices; same recipe
      as ``parallel.distributed._put``)."""
    import jax

    if sharding is None:
        return jax.device_put(host)
    if isinstance(host, jax.Array) and host.sharding == sharding:
        return host
    if jax.process_count() == 1:
        return jax.device_put(host, sharding)
    return jax.make_array_from_process_local_data(
        sharding, _np.asarray(host))


def device_put_batch(batch, sharding=None):
    """Stage one batch on device, non-blocking, preserving structure
    (tuple/list of NDArray/numpy leaves stay NDArray-wrapped so downstream
    Gluon code keeps working).

    ``sharding=None`` targets the default device; a ``NamedSharding``
    places the global batch (a training step's ``_batch_shard``).  In a
    multi-process job each process contributes its local batch and the
    global array is assembled per-process-addressable-shard (same recipe
    as ``parallel.distributed._put`` — no cross-host host round trip)."""
    import jax

    from ...ndarray.ndarray import NDArray

    def put(leaf):
        if isinstance(leaf, (tuple, list)):
            return type(leaf)(put(x) for x in leaf)
        host = leaf
        ctx = None
        if isinstance(leaf, NDArray):
            ctx = leaf.context
            host = leaf._get()
        elif not isinstance(host, (jax.Array, _np.ndarray)):
            return leaf  # labels/metadata that are not arrays pass through
        return NDArray._from_jax(stage_leaf(host, sharding), ctx)

    return put(batch)


def _drain(q):
    try:
        while True:
            q.get_nowait()
    except queue.Empty:
        pass


def _finalize(stop, q, thread):
    # module-level (no self ref) so the weakref finalizer cannot keep the
    # iterator alive; drain unblocks a producer stuck in put()
    stop.set()
    _drain(q)
    if thread is not None and thread.is_alive():
        thread.join(timeout=5)


class PrefetchIterator:
    """Wrap a batch iterator with an N-deep device-prefetch pipeline.

    Usage::

        it = PrefetchIterator(iter(loader), sharding=step._batch_shard)
        for x, y in it:
            loss = step(x, y)      # x/y already on device
        it.close()                 # or rely on the GC finalizer
    """

    def __init__(self, source, depth=None, sharding=None, stage_fn=None):
        if depth is None:
            # the tuning funnel (env pin > MXNET_TUNE=1 winner >
            # default); the env accessor is the fallback so a broken
            # tuning tier can never stall the input pipeline
            try:
                from ... import tuning as _tuning

                depth = int(_tuning.resolve("prefetch_buffer"))
            except Exception:
                depth = _env.prefetch_buffer()
        self._depth = max(0, int(depth))
        self._sharding = sharding
        self._stage = stage_fn or (
            lambda b: device_put_batch(b, sharding))
        self._source = iter(source)
        self._error = None
        self._done = False
        self._batches = 0   # staged so far: the next batch's number
        self._delivered = 0  # taken from the queue so far
        if self._depth == 0:
            # disabled: stage on the caller's thread, no pipeline
            self._q = None
            self._thread = None
            self._stop = None
            self._finalizer = None
            return
        self._q = queue.Queue(maxsize=self._depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._producer, name="mxnet-prefetch", daemon=True)
        self._finalizer = weakref.finalize(
            self, _finalize, self._stop, self._q, self._thread)
        self._thread.start()

    # -- producer ----------------------------------------------------------
    def _put(self, msg):
        while not self._stop.is_set():
            try:
                self._q.put(msg, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _staged(self, item):
        """Stage ``item`` under the next batch number; returns the staged
        batch and what ``telemetry.batch_taken`` takes of it: the number
        and the ``prefetch.stage`` span on the staging thread."""
        n = self._batches
        self._batches += 1
        # a plain annotation, not a telemetry.phase: the step timeline
        # belongs to the consumer's thread
        t0 = _time.perf_counter()
        with _telemetry.trace_annotation("prefetch.stage", batch=n):
            staged = self._stage(item)
        t1 = _time.perf_counter()
        _STAGE.observe(t1 - t0)
        return staged, {"batch": n, "prefetch.stage":
                        [t0, t1, threading.get_ident()]}

    def _producer(self):
        try:
            for item in self._source:
                if not self._put((_ITEM, self._staged(item))):
                    return
            self._put((_END, None))
        except BaseException as e:  # incl. worker-liveness MXNetError
            self._error = e  # visible even if the sentinel put is raced
            self._put((_ERR, e))

    # -- consumer ----------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        if self._q is None:  # depth 0: plain staging pass-through
            try:
                item = next(self._source)
            except StopIteration:
                self._done = True
                raise
            staged, taken = self._staged(item)
            _telemetry.batch_taken(taken)
            return staged
        t0 = _time.perf_counter()
        hit = not self._q.empty()
        # the queue is first in, first out: the batch taken is the
        # ``_delivered``-th staged
        with _telemetry.trace_annotation("prefetch.wait",
                                         batch=self._delivered):
            while True:
                try:
                    kind, val = self._q.get(timeout=0.2)
                    break
                except queue.Empty:
                    if self._thread is not None \
                            and not self._thread.is_alive():
                        # producer died without managing to enqueue a
                        # sentinel
                        self._done = True
                        if self._error is not None:
                            raise self._error
                        raise MXNetError(
                            "prefetch thread died without delivering a "
                            "batch or an error (crashed interpreter "
                            "thread?)")
        t1 = _time.perf_counter()
        _WAIT.observe(t1 - t0)
        _DEPTH.set(self._q.qsize())
        if kind == _ITEM:
            # count only delivered batches (the end-of-epoch sentinel
            # fetch is not a batch request)
            (_HITS if hit else _MISSES).inc()
            self._delivered += 1
            staged, taken = val
            taken["prefetch.wait"] = [t0, t1, threading.get_ident()]
            _telemetry.batch_taken(taken)
            return staged
        self._done = True
        if kind == _ERR:
            raise val
        raise StopIteration  # _END

    def close(self):
        """Stop the background thread and release the queue.  Idempotent;
        safe to call from ``finally`` while the producer is mid-put."""
        self._done = True
        if self._finalizer is not None:
            self._finalizer()  # runs _finalize exactly once
        self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
