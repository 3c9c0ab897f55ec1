"""Checkpoint/resume + failure recovery.

Reference scope (SURVEY.md §6.3): MXNet 1.x ships Module.save_checkpoint /
load_checkpoint and leaves elastic recovery to the operator; modern TPU
jobs need the full loop — atomic checkpoints, auto-resume from the latest
good step, and a supervised retry wrapper (the moral equivalent of the
ps-lite worker-restart story, redesigned for SPMD jobs where every process
restarts together).

Design:
- ``CheckpointManager``: step-indexed directory layout, ATOMIC publishes
  (write to tmp, fsync, rename — a partially-written checkpoint is never
  visible), per-file sha256 checksums recorded in ``meta.json`` and
  verified on restore (a bit-flipped or truncated file is detected, the
  step is skipped, and restore falls back to the newest VALID older
  step), bounded retention, ``latest_step()`` discovery for resume, and
  orphaned-staging GC (a crash mid-save leaves a ``.tmp_step_*`` dir; the
  next manager construction sweeps them).
  In a multi-process job only process 0 writes (weights are replicated);
  all processes barrier on publish so no one resumes past a checkpoint a
  peer has not finished.
- ``run_with_recovery``: restarts a training function from the latest
  checkpoint after transient failures (preemption, XLA OOM after
  defragmentation, flaky interconnect) with exponential backoff + jitter
  between restarts and a restart budget that RESETS whenever the job made
  checkpoint progress between failures — a job that keeps advancing is
  healthy no matter how often it is preempted, while a crash loop at the
  same step still exhausts the budget.

Failure domains are exercised through :mod:`mxnet_tpu.fault` (seams
``checkpoint.write`` / ``checkpoint.fsync`` / ``checkpoint.publish``);
see tests/test_fault.py for the chaos suite.
"""
from __future__ import annotations

import hashlib
import json
import logging
import os
import shutil
import tempfile
import threading
import time

from . import fault
from . import flight_recorder as _flight
from . import telemetry
from .base import MXNetError

__all__ = ["CheckpointManager", "run_with_recovery"]

_SAVE_HIST = telemetry.histogram(
    "mxnet_checkpoint_save_seconds", "checkpoint save duration (publish)")
_RESTORE_HIST = telemetry.histogram(
    "mxnet_checkpoint_restore_seconds", "checkpoint restore duration")
_SAVES_TOTAL = telemetry.counter(
    "mxnet_checkpoint_saves_total", "published checkpoints")
_RESTORES_TOTAL = telemetry.counter(
    "mxnet_checkpoint_restores_total", "completed checkpoint restores")
_RESTARTS_TOTAL = telemetry.counter(
    "mxnet_recovery_restarts_total", "run_with_recovery restarts")
_INFLIGHT = telemetry.gauge(
    "mxnet_checkpoint_inflight",
    "1 while an async checkpoint write is staging/publishing in background")
_SNAPSHOT_HIST = telemetry.histogram(
    "mxnet_checkpoint_snapshot_seconds",
    "blocking device->host snapshot portion of an async save")

_LOGGER = logging.getLogger(__name__)

_TMP_PREFIX = ".tmp_step_"
# files that never get a checksum: meta.json carries the sums, COMMITTED
# is the marker itself
_UNSUMMED = ("meta.json", "COMMITTED")


def _fsync_file(path):
    # a long synchronous save (the preemption stop path in particular)
    # is progress, not a stall: beat the watchdog per durability step
    telemetry.heartbeat()
    fault.check("checkpoint.fsync")
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _fsync_dir(path):
    try:
        fd = os.open(path, os.O_RDONLY | getattr(os, "O_DIRECTORY", 0))
    except OSError:  # pragma: no cover - platforms without O_DIRECTORY
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _sha256(path):
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def _stat_sig(path):
    """(size, mtime_ns) fingerprint, or None when missing — cheap change
    detector for the verify() verdict cache."""
    try:
        st = os.stat(path)
    except OSError:
        return None
    return (st.st_size, st.st_mtime_ns)


class CheckpointManager:
    """Atomic, step-indexed checkpoints for Gluon nets + Trainers.

    Usage::

        mgr = CheckpointManager(dir, max_to_keep=3)
        start = mgr.restore(net, trainer)  # 0 if none yet
        for epoch in range(start, n):
            ...train...
            mgr.save(epoch + 1, net, trainer)
    """

    def __init__(self, directory, max_to_keep=5, logger=None):
        self.directory = directory
        self.max_to_keep = max_to_keep
        self.logger = logger or _LOGGER
        # verify() verdict cache: step -> {file: (size, mtime_ns)} at the
        # time the step last hashed clean
        self._valid_steps = {}
        # steps that VERIFIED clean but failed to load (pre-checksum
        # checkpoint with a torn file): latest_valid_step must skip them
        # or the next restart's start step disagrees with the weights
        # restore() actually falls back to
        self._load_failed = set()
        # async-save state: at most ONE background write in flight; the
        # next save()/close()/restore() joins it first
        self._pending = None
        self._pending_step = None
        self._pending_error = None
        os.makedirs(directory, exist_ok=True)
        # only the writing process sweeps: a non-primary peer constructing
        # its manager while process 0 is mid-save must not delete the live
        # staging dir out from under it.  The rank comes from the LAUNCHER
        # env, not jax.process_index(): constructing a manager must not
        # initialize the jax backend (that would break a later
        # jax.distributed.initialize), and before initialization every
        # process would report index 0 anyway.
        if self._launcher_rank() == 0:
            self._gc_orphaned_tmp()

    @staticmethod
    def _launcher_rank():
        """Process rank WITHOUT initializing the jax backend; -1 = multi-
        process job whose rank cannot be proven (callers fail closed)."""
        for var in ("MXNET_WORKER_ID", "DMLC_WORKER_ID", "TPU_WORKER_ID",
                    "CLOUD_TPU_TASK_ID"):
            v = os.environ.get(var)
            if v:
                try:
                    return int(v)
                except ValueError:
                    return -1  # unparseable: cannot prove primary
        from .parallel import distributed as _dist

        if _dist.is_initialized():
            import jax   # already initialized: reading the index is safe

            return jax.process_index()
        if os.environ.get("MXNET_COORDINATOR_ADDRESS") or \
                os.environ.get("DMLC_PS_ROOT_URI"):
            # a coordinator is configured but no rank var and not yet
            # initialized: this IS a multi-process job — fail closed
            # rather than risk every peer sweeping the shared directory
            return -1
        return 0  # single-process / un-launched

    def _gc_orphaned_tmp(self):
        """Sweep ``.tmp_step_*`` staging dirs left by a crash mid-save
        (they were never published, so deleting them is always safe —
        an in-flight save in ANOTHER process is the operator's error:
        two writers on one checkpoint dir corrupt retention anyway)."""
        for name in os.listdir(self.directory):
            path = os.path.join(self.directory, name)
            if name.startswith(_TMP_PREFIX) and os.path.isdir(path):
                shutil.rmtree(path, ignore_errors=True)
                self.logger.warning(
                    "removed orphaned checkpoint staging dir %s "
                    "(crash mid-save)", path)

    # -- discovery ---------------------------------------------------------
    def _step_dir(self, step):
        return os.path.join(self.directory, f"step_{step:08d}")

    def all_steps(self):
        out = []
        for name in os.listdir(self.directory):
            if name.startswith("step_") and os.path.isdir(
                    os.path.join(self.directory, name)) and \
                    os.path.exists(os.path.join(self.directory, name,
                                                "COMMITTED")):
                out.append(int(name[len("step_"):]))
        return sorted(out)

    def latest_step(self):
        steps = self.all_steps()
        return steps[-1] if steps else None

    def latest_valid_step(self):
        """Newest step that passes checksum verification and has not been
        seen to fail a load — the step a restore() will actually serve.
        Resume logic must use THIS, not ``latest_step()``: after
        corruption the two differ, and trusting the unverified number
        silently skips the corrupted step's work."""
        # an in-flight async write may be about to publish (or to mutate
        # the verify cache): join first so the answer is race-free and
        # credits exactly the published steps
        self._join_pending(raise_=False)
        for s in reversed(self.all_steps()):
            if s not in self._load_failed and self.verify(s) is None:
                return s
        return None

    def verify(self, step):
        """Integrity-check checkpoint ``step`` against the checksums in
        its meta.json.  Returns None when valid, else a string naming the
        first problem.  Checkpoints written before checksums existed
        (no "files" key) verify as valid — there is nothing to check.

        A VALID verdict is cached against each file's (size, mtime_ns) —
        resume would otherwise sha256 a multi-GB checkpoint twice
        (latest_valid_step, then restore).  Any stat change voids the
        cache and re-hashes; failures are never cached, so an operator
        who repairs a file in place is believed."""
        d = self._step_dir(step)
        cached = self._valid_steps.get(step)
        if cached is not None:
            if all(_stat_sig(os.path.join(d, n)) == sig
                   for n, sig in cached.items()):
                return None
            del self._valid_steps[step]
        if not os.path.exists(os.path.join(d, "COMMITTED")):
            return f"{d}: no COMMITTED marker"
        try:
            with open(os.path.join(d, "meta.json")) as f:
                meta = json.load(f)
        except (OSError, ValueError) as e:
            return f"{d}/meta.json unreadable: {e}"
        for name, want in (meta.get("files") or {}).items():
            path = os.path.join(d, name)
            if not os.path.exists(path):
                return f"{path}: missing"
            if os.path.getsize(path) != want["size"]:
                return (f"{path}: size {os.path.getsize(path)} != recorded "
                        f"{want['size']} (truncated?)")
            if _sha256(path) != want["sha256"]:
                return f"{path}: sha256 mismatch (corrupt)"
        self._valid_steps[step] = {
            name: _stat_sig(os.path.join(d, name))
            for name in (meta.get("files") or {})}
        return None

    # -- save/restore ------------------------------------------------------
    def _write_step(self, step, write_payloads, extra, primary,
                    barrier=True):
        """Stage, checksum, fsync, and atomically publish checkpoint
        ``step``.  ``write_payloads(tmp_dir)`` writes the payload files;
        everything else (manifest, durability ordering, publish rename,
        retention GC) is identical for the sync and async paths — the
        fault seams and sha256 contract hold for both.  ``barrier=False``
        for the async background writer: a collective issued from a
        second thread would race the main thread's training collectives
        (SPMD peers must enqueue collectives in one program order), so
        the async path barriers on the CALLER's thread instead."""
        final = self._step_dir(step)
        telemetry.heartbeat()   # a save is progress, not a stall
        try:
            if primary:
                tmp = tempfile.mkdtemp(prefix=f"{_TMP_PREFIX}{step}_",
                                       dir=self.directory)
                try:
                    fault.check("checkpoint.write")
                    write_payloads(tmp)
                    telemetry.heartbeat()
                    meta = {"step": int(step), "time": time.time()}
                    if extra:
                        meta["extra"] = extra
                    # integrity: restore() re-hashes each payload file
                    # against these sums before trusting the step
                    meta["files"] = {
                        name: {"sha256": _sha256(os.path.join(tmp, name)),
                               "size": os.path.getsize(
                                   os.path.join(tmp, name))}
                        for name in os.listdir(tmp) if name not in _UNSUMMED}
                    with open(os.path.join(tmp, "meta.json"), "w") as f:
                        json.dump(meta, f)
                    # durability: every payload file reaches the platter
                    # BEFORE the commit marker exists, and the marker +
                    # directory entries before the publish rename
                    for name in os.listdir(tmp):
                        _fsync_file(os.path.join(tmp, name))
                    with open(os.path.join(tmp, "COMMITTED"), "w") as f:
                        f.write("1")
                        f.flush()
                        os.fsync(f.fileno())
                    _fsync_dir(tmp)
                    fault.check("checkpoint.publish")
                    if os.path.exists(final):
                        shutil.rmtree(final)
                    self._valid_steps.pop(step, None)  # content changes now
                    self._load_failed.discard(step)
                    os.rename(tmp, final)
                    _fsync_dir(self.directory)
                except Exception:
                    shutil.rmtree(tmp, ignore_errors=True)
                    raise
                self._gc()
        finally:
            # ALL processes must reach the barrier even when the primary's
            # write fails — otherwise the peers deadlock in the collective
            if barrier:
                self._barrier()
        return final

    def save(self, step, net=None, trainer=None, extra=None, async_=None,
             train_state=None):
        """Publish checkpoint `step` atomically; returns its directory.

        ``train_state`` (a JSON-able dict, normally from
        ``lifecycle.capture_train_state``) is written as
        ``train_state.json`` — sha256-summed like every payload file —
        and read back with :meth:`read_train_state`.  It carries what a
        bit-identical resume needs beyond weights/optimizer state:
        DataLoader/sampler position, the global RNG state, loss-scaler
        counters, and step counters.

        ``async_=True`` (default from ``MXNET_CHECKPOINT_ASYNC``) makes
        only the device→host snapshot block the caller: file writes,
        fsyncs, and the atomic publish run on a background thread with
        the same fault seams and sha256 manifest.  The next ``save`` (or
        ``close()``/``restore()``) joins the previous write first — a
        failed background write surfaces there, and its step was simply
        never published (costs one step, never the job).  Supervisors
        must credit progress from ``latest_valid_step()``, which sees
        only *published* steps."""
        import jax

        if async_ is None:
            from . import env as _env

            async_ = _env.checkpoint_async_default()
        # surface a failed previous background write before anything else:
        # losing its step already cost one checkpoint; losing the ERROR
        # would hide a persistently broken disk behind green saves.
        # Multi-process: LOG instead of raising — only the primary has
        # pending state, and a primary-only raise here would strand the
        # peers in the barrier below (the all-processes-reach-the-barrier
        # invariant).  The unpublished step still never counts as
        # progress; close() at end-of-job (no more collectives) raises.
        self._join_pending(raise_=jax.process_count() == 1)
        primary = jax.process_index() == 0
        final = self._step_dir(step)
        t0 = time.perf_counter()
        # serialize NOW in both paths: train_state is host data, and the
        # caller may mutate its dicts (sampler epoch, RNG) right after
        ts_blob = None if train_state is None else \
            json.dumps(train_state).encode()
        if not async_:
            def write_payloads(tmp):
                if net is not None:
                    net.save_parameters(os.path.join(tmp, "model.params"))
                if trainer is not None:
                    trainer.save_states(os.path.join(tmp, "trainer.states"))
                if ts_blob is not None:
                    with open(os.path.join(tmp, "train_state.json"),
                              "wb") as f:
                        f.write(ts_blob)

            # a save inside an open telemetry step is its own phase; the
            # phase must close even when the barrier fails, or the
            # dangling frame mis-attributes the rest of the step
            with telemetry.phase("checkpoint"):
                self._write_step(step, write_payloads, extra, primary)
            dt = time.perf_counter() - t0
            _SAVE_HIST.observe(dt)
            # goodput: a synchronous save blocks training for its full
            # duration (the step timeline excludes its in-step
            # checkpoint phase from "productive" for the same reason)
            telemetry.goodput_note("checkpoint", dt)
            _SAVES_TOTAL.inc()
            return final
        # async: snapshot device→host NOW (host copies — the step loop
        # mutating params right after cannot leak into the file), write
        # and publish in background.  The peer barrier runs HERE, on the
        # calling thread: every process calls save() at the same point of
        # its step loop, so the collective stays in program order; a
        # barrier from the background thread would race the main thread's
        # training collectives and desync SPMD peers.  The synchronized
        # event is therefore "snapshot taken everywhere", and the publish
        # is primary-local — supervisors credit only PUBLISHED steps.
        with telemetry.phase("checkpoint"):
            try:
                writers = self._snapshot_payloads(net, trainer) if primary \
                    else {}
                if primary and ts_blob is not None:
                    def write_ts(path, _blob=ts_blob):
                        with open(path, "wb") as f:
                            f.write(_blob)

                    writers["train_state.json"] = write_ts
            finally:
                # ALL processes must reach the barrier even when the
                # primary's snapshot raises (same invariant as the sync
                # path's finally in _write_step) — peers are already
                # blocked in it
                self._barrier()
        dt_snap = time.perf_counter() - t0
        _SNAPSHOT_HIST.observe(dt_snap)
        # goodput: an ASYNC save only blocks for the device->host
        # snapshot — the background write overlaps training and is
        # deliberately NOT charged (that overlap is the feature)
        telemetry.goodput_note("checkpoint", dt_snap)
        if not primary:
            return final  # nothing to write; the snapshot barrier is done

        def write_payloads(tmp):
            for name, write in writers.items():
                write(os.path.join(tmp, name))

        self._pending_step = step
        self._pending_error = None
        _INFLIGHT.set(1)

        def task():
            try:
                # NO telemetry.phase here: the step timeline is the MAIN
                # thread's; a background frame would corrupt attribution
                self._write_step(step, write_payloads, extra, primary,
                                 barrier=False)
                _SAVE_HIST.observe(time.perf_counter() - t0)
                _SAVES_TOTAL.inc()
            except BaseException as e:
                self._pending_error = e
            finally:
                _INFLIGHT.set(0)

        self._pending = threading.Thread(
            target=task, name=f"mxnet-ckpt-save-{step}", daemon=True)
        self._pending.start()
        return final

    def _snapshot_payloads(self, net, trainer):
        """Host-resident copies of everything save() would write, as
        path-writer callables — the blocking (D2H) half of an async save."""
        import numpy as _np

        writers = {}
        if net is not None:
            snap = {k: _np.array(_np.asarray(v.data()._get()))
                    for k, v in net._collect_params_with_prefix().items()}

            def write_params(path, _snap=snap):
                from .ndarray.serialization import save as _save

                _save(path, _snap)

            writers["model.params"] = write_params
        if trainer is not None:
            blob = trainer._states_blob()

            def write_states(path, _blob=blob):
                with open(path, "wb") as f:
                    f.write(_blob)

            writers["trainer.states"] = write_states
        return writers

    def _join_pending(self, raise_=True):
        """Wait for the in-flight background write (if any); re-raise its
        failure unless ``raise_=False`` (then it is logged and dropped —
        the unpublished step is the cost)."""
        t = self._pending
        if t is not None:
            t.join()
            self._pending = None
        err, self._pending_error = self._pending_error, None
        if err is None:
            return
        if raise_:
            raise MXNetError(
                f"async checkpoint write for step {self._pending_step} "
                f"failed: {err!r}") from err
        self.logger.warning(
            "async checkpoint write for step %s failed (%r); that step "
            "was never published", self._pending_step, err)

    def close(self):
        """Join the in-flight async write; raises if it failed.  Call at
        the end of training (or use the manager as a context manager)."""
        self._join_pending()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        # don't mask an in-flight exception with the join's verdict
        self._join_pending(raise_=exc[0] is None)
        return False

    def restore(self, net=None, trainer=None, step=None, ctx=None):
        """Load the newest VALID checkpoint (default), or exactly ``step``
        when one is requested explicitly; returns the loaded step number,
        or 0 when no valid checkpoint exists.

        With ``step=None`` a checkpoint whose files fail checksum
        verification — or whose load raises — is skipped with a warning
        and the next older step is tried: one corrupt file must cost one
        checkpoint of progress, not the job.  An EXPLICIT ``step`` keeps
        the strict contract: the caller pinned that checkpoint
        (reproduction run, eval of a named step), so serving different
        weights would be silent corruption — missing or invalid raises."""
        # loading while a background save is staging/publishing would race
        # the writer (and the verify cache); a FAILED background write is
        # logged and costs its (never-published) step only
        self._join_pending(raise_=False)
        t0 = time.perf_counter()
        if step is not None:
            if step not in self.all_steps():
                raise MXNetError(
                    f"checkpoint {self._step_dir(step)} is not committed")
            problem = self.verify(step)
            if problem is not None:
                raise MXNetError(
                    f"checkpoint step {step} requested explicitly but "
                    f"failed verification: {problem}")
            self._load(step, net, trainer, ctx)
            _RESTORE_HIST.observe(time.perf_counter() - t0)
            _RESTORES_TOTAL.inc()
            return step
        for s in reversed(self.all_steps()):
            if s in self._load_failed:
                # stays skipped for this manager's lifetime even if the
                # failure was transient: latest_valid_step() skips it, so
                # loading it here would hand back step-s weights while
                # the supervisor already told train_fn to start at s-1
                continue
            problem = self.verify(s)
            if problem is not None:
                self.logger.warning(
                    "checkpoint step %d failed verification (%s); "
                    "falling back to an older step", s, problem)
                continue
            try:
                self._load(s, net, trainer, ctx)
            except Exception as e:  # checksum passed but load failed:
                # treat like corruption (e.g. pre-checksum checkpoint
                # with a torn file) and keep walking back; remember the
                # step so latest_valid_step stops advertising it
                self._load_failed.add(s)
                self.logger.warning(
                    "checkpoint step %d failed to load (%r); "
                    "falling back to an older step", s, e)
                continue
            _RESTORE_HIST.observe(time.perf_counter() - t0)
            _RESTORES_TOTAL.inc()
            return s
        return 0

    def _load(self, step, net, trainer, ctx):
        d = self._step_dir(step)
        if net is not None:
            net.load_parameters(os.path.join(d, "model.params"), ctx=ctx)
        if trainer is not None:
            tpath = os.path.join(d, "trainer.states")
            if os.path.exists(tpath):
                trainer.load_states(tpath)

    def read_meta(self, step):
        with open(os.path.join(self._step_dir(step), "meta.json")) as f:
            return json.load(f)

    def read_train_state(self, step):
        """The ``train_state`` dict saved with ``step`` (None when the
        checkpoint predates exact-resume or none was passed).  Feed it to
        ``lifecycle.restore_train_state`` after ``restore()``."""
        path = os.path.join(self._step_dir(step), "train_state.json")
        if not os.path.exists(path):
            return None
        with open(path) as f:
            return json.load(f)

    def _gc(self):
        steps = self.all_steps()
        for s in steps[:-self.max_to_keep] if self.max_to_keep else []:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)
            self._valid_steps.pop(s, None)   # week-long jobs: no leak
            self._load_failed.discard(s)

    def _barrier(self):
        import jax

        if jax.process_count() > 1:
            from .parallel.collectives import barrier

            barrier()


def run_with_recovery(train_fn, manager, max_restarts=3,
                      should_retry=None, logger=None, backoff_ms=None,
                      resharder=None):
    """Supervised training loop: ``train_fn(start_step, manager)`` runs to
    completion or raises; on a retryable failure it is re-invoked from the
    latest checkpoint (elastic semantics for preemptible TPU jobs).

    - ``should_retry(exc) -> bool`` filters failures (default: retry
      everything except KeyboardInterrupt).
    - Restarts back off exponentially with full jitter (seed
      ``backoff_ms``, default MXNET_FAULT_BACKOFF_MS=100, capped at 30s)
      so a fleet of preempted workers does not re-stampede the
      coordinator.
    - The restart budget (``max_restarts``) counts CONSECUTIVE failures
      at the same checkpoint step: whenever ``manager.latest_step()``
      advanced since the previous failure the budget resets, so a
      long-running job survives unlimited preemptions as long as it keeps
      making progress.
    - Restart telemetry always reaches a logger — the module logger when
      ``logger`` is None — so silent restart loops show up in production
      logs.
    - A ``lifecycle.GracefulExit`` from train_fn is a PREEMPTED-CLEAN
      exit, not a failure: the final checkpoint is already published, so
      the supervisor joins any in-flight async write, does NOT count a
      restart, and re-raises — the caller translates it to
      ``sys.exit(lifecycle.EXIT_PREEMPTED)`` and the external scheduler
      relaunches the job, which resumes bit-identically.
    - ``resharder(exc) -> step | None`` is the zero-downtime elasticity
      hook (``lifecycle.elastic_resharder`` builds one): when the
      surviving in-process state is intact — and every SPMD peer AGREES
      it is — it live-reshards that state to the (possibly resized)
      mesh and returns the step the state corresponds to, so the next
      ``train_fn(start, manager)`` skips the checkpoint disk round trip
      entirely.  Returning None (state damaged, peers disagree, or the
      reshard itself failed) falls back to the checkpoint path — the
      choice is automatic, per failure.

    Returns train_fn's result."""
    from .lifecycle import GracefulExit

    log = logger or _LOGGER
    if backoff_ms is None:
        backoff_ms = fault.backoff_ms()
    # resume from the newest VERIFIED step: latest_step() would count a
    # corrupt checkpoint that restore() will skip, telling train_fn to
    # start past state it never loaded (silent step/state skew)
    progress = getattr(manager, "latest_valid_step", manager.latest_step)
    restarts = 0
    # per-path progress markers (see the reset logic below — live and
    # checkpoint steps are different clocks).  The checkpoint marker
    # seeds from the supervisor's starting state so the FIRST failure
    # already gets credit for any checkpoint published since launch.
    last_ckpt_step = progress() or 0
    last_live_step = None
    live_start = None
    fail_t = None          # goodput: failure -> next attempt downtime
    fail_bucket = "restart"  # or "rewind" for guard-verdict failures
    reshard_dt = 0.0       # resharder time inside that window (charged
    #                        to the reshard bucket by apply_transfer)
    while True:
        start = live_start if live_start is not None else progress() or 0
        live_start = None
        if fail_t is not None:
            # restart downtime: everything between the failure and this
            # re-attempt (join, progress probe, backoff sleep) except
            # the live-reshard transfer, which the resharding seam
            # already charged to its own bucket.  A numerical-integrity
            # failure (guard rewind/divergence) charges the ``rewind``
            # bucket instead: time lost to wrong VALUES, not to a lost
            # process — the distinction an SLO postmortem needs
            telemetry.goodput_note(
                fail_bucket,
                max(0.0, time.perf_counter() - fail_t - reshard_dt))
            fail_t, fail_bucket, reshard_dt = None, "restart", 0.0
        try:
            result = train_fn(start, manager)
            # a final async save may still be staging: join before the
            # supervisor returns (daemon writer threads die with the
            # interpreter).  Single-process, a FAILED final write raises
            # here, inside the try, so it re-enters the retry loop and
            # the lost step is re-trained instead of silently dropped.
            # Multi-process it is only logged: peers have already
            # returned, and a primary-only retry would desync their
            # collectives — the lost step escalates to the external
            # whole-job supervisor (PR 2's SPMD-restart philosophy).
            join = getattr(manager, "_join_pending", None)
            if join is not None:
                import jax

                join(raise_=jax.process_count() == 1)
            return result
        except KeyboardInterrupt:
            raise
        except GracefulExit as e:
            # preempted-clean: the loop honored a stop and published its
            # final checkpoint — never counted against the restart budget
            join = getattr(manager, "_join_pending", None)
            if join is not None:
                import jax

                join(raise_=jax.process_count() == 1)
            log.info("preempted-clean exit (%s); latest valid step %s",
                     e, progress())
            raise
        except Exception as e:
            if should_retry is not None and not should_retry(e):
                raise
            fail_t = time.perf_counter()
            from . import guard as _guard

            divergence = isinstance(e, _guard.NumericalDivergence)
            if divergence or isinstance(e, _guard.GuardRewind):
                fail_bucket = "rewind"
            # black-box first, while the ring still holds the failing
            # step's collectives: the dump is atomic and per-rank (the
            # mesh may be mid-desync — NEVER a collective here), and a
            # later successful recovery simply leaves the newest
            # abnormal event on record
            _flight.record_event("lifecycle", event="train_failure",
                                 error=repr(e)[:200])
            _flight.dump_blackbox("numerical_divergence" if divergence
                                  else "run_with_recovery_failure")
            # a background checkpoint write may still be in flight from
            # before the failure: let it finish (it may publish the step
            # that resets the budget) before judging progress — a FAILED
            # write is logged and its step simply never counts
            join = getattr(manager, "_join_pending", None)
            if join is not None:
                join(raise_=False)
            step_now = progress() or 0
            if resharder is not None:
                # live elasticity: reshard surviving state instead of
                # restoring from disk when the hook (with peer
                # agreement) says it is intact; any failure inside the
                # hook falls back to the checkpoint path.  Consulted
                # BEFORE the budget verdict: a live-resharded step is
                # progress exactly like a published checkpoint, so a
                # job advancing through preemptions between checkpoint
                # intervals must not exhaust the budget and die
                # "stuck" at a step it long passed.
                from .parallel import resharding as _resharding

                t_rs = time.perf_counter()
                rs_before = telemetry.goodput_summary()["buckets"].get(
                    "reshard", 0.0)
                try:
                    live_start = resharder(e)
                except Exception as re:
                    live_start = None
                    log.warning("live resharder failed (%r); falling "
                                "back to checkpoint restore", re)
                reshard_dt = time.perf_counter() - t_rs
                # the whole resharder call is reshard-bucket time, but
                # only its apply_transfer portion self-charges at the
                # seam — top the bucket up with the uncovered remainder
                # (plan building, agreement, a raise BEFORE the
                # transfer) so the time subtracted from the restart
                # bucket below never vanishes from the ledger
                covered = telemetry.goodput_summary()["buckets"].get(
                    "reshard", 0.0) - rs_before
                telemetry.goodput_note("reshard",
                                       max(0.0, reshard_dt - covered))
                if live_start is not None:
                    _resharding.record_live_reshard()
                    log.info("live reshard accepted: resuming from "
                             "in-process state at step %s (checkpoint "
                             "would have been step %s)", live_start,
                             step_now)
                else:
                    _resharding.record_reshard_fallback()
            # progress resets the budget — only repeated failures at
            # the SAME point are a crash loop.  Each recovery path is
            # compared against ITS OWN last marker: a live step and a
            # checkpoint step are different clocks (a lost live reshard
            # can outrun the checkpoints; later checkpoint advances
            # below it are still real progress and must still reset).
            # Both quantities are peer-agreed/deterministic, so the
            # verdict is uniform across SPMD peers.
            if live_start is not None:
                progressed = last_live_step is not None and \
                    live_start > last_live_step
                last_live_step = live_start
                effective = live_start
            else:
                progressed = step_now > last_ckpt_step
                last_ckpt_step = step_now
                effective = step_now
            if progressed:
                log.info("progress advanced to step %s between "
                         "failures (%s); restart budget reset",
                         effective,
                         "live reshard" if live_start is not None
                         else "checkpoint")
                restarts = 0
            restarts += 1
            _RESTARTS_TOTAL.inc()
            _flight.record_event("lifecycle", event="restart",
                                 attempt=restarts, step=effective)
            if restarts > max_restarts:
                _flight.dump_blackbox("restart_budget_exhausted")
                raise MXNetError(
                    f"training failed after {max_restarts} restarts "
                    f"without progress (stuck at step "
                    f"{effective}; last error: {e!r})") from e
            delay = fault.backoff_delay(restarts - 1, backoff_ms)
            log.warning("restart %d/%d from step %s in %.3fs after: %r",
                        restarts, max_restarts, effective, delay, e)
            if delay > 0:
                time.sleep(delay)
