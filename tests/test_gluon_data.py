"""gluon.data DataLoader / Dataset / samplers (reference:
tests/python/unittest/test_gluon_data.py)."""
import os
import time

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu.gluon.data import ArrayDataset, DataLoader
from mxnet_tpu.gluon.data.dataset import Dataset


def test_dataloader_eager_threaded_process_parity():
    """All three worker modes yield identical batches in order."""
    X = np.arange(40, dtype="f").reshape(20, 2)
    Y = np.arange(20, dtype="f")
    ds = ArrayDataset(X, Y)

    def collect(**kw):
        out = []
        for xb, yb in DataLoader(ds, batch_size=6, shuffle=False, **kw):
            out.append((xb.asnumpy(), yb.asnumpy()))
        return out

    eager = collect(num_workers=0)
    threaded = collect(num_workers=2)
    procs = collect(num_workers=2, thread_pool=False)
    assert len(eager) == len(threaded) == len(procs) == 4
    for (xe, ye), (xt, yt), (xp, yp) in zip(eager, threaded, procs):
        np.testing.assert_array_equal(xe, xt)
        np.testing.assert_array_equal(xe, xp)
        np.testing.assert_array_equal(ye, yt)
        np.testing.assert_array_equal(ye, yp)


class _MeetingDataset(Dataset):
    """A GIL-bound transform (a pure-Python loop that never releases the
    GIL: the workload process workers exist for) that leaves its process's
    mark in ``where`` and comes back only once ``parties`` processes have
    left theirs: that many are then inside the transform at once."""

    def __init__(self, n, where, parties, iters=20000):
        self._n, self._where, self._parties = n, where, parties
        self._iters = iters

    def __len__(self):
        return self._n

    def __getitem__(self, idx):
        acc = 0.0
        for i in range(self._iters):
            acc += (idx * 31 + i) % 7
        open(os.path.join(self._where, str(os.getpid())), "w").close()
        deadline = time.monotonic() + 120.0
        while len(os.listdir(self._where)) < self._parties:
            if time.monotonic() > deadline:
                raise RuntimeError(
                    f"{os.listdir(self._where)} of {self._parties} worker "
                    "processes reached the transform in 120 s")
            time.sleep(0.01)
        return np.array([idx, acc], "f")


def test_dataloader_process_workers_scale_gil_bound_transform(tmp_path):
    """With a GIL-bound transform, four process workers are four
    interpreters inside the transform at once (threads share one GIL and
    cannot be): the first batches come back only after four processes,
    none of them this one, have met in it, and the batches are a single
    in-process pass's, in order.

    Events, not seconds: the ratio of one worker's time to four's that this
    test used to gate on is the host's scheduling, and fell under any fixed
    margin with six xdist workers beside it.  And by the default start
    method: forking the pool from this thread-laden process for a short
    start-up left a child waiting on an inherited lock, which hung the whole
    of tier-1 until its time limit (ROADMAP.md, D8)."""
    for name in ("alone", "together"):
        (tmp_path / name).mkdir()
    want = [b.asnumpy() for b in DataLoader(
        _MeetingDataset(32, str(tmp_path / "alone"), 1), batch_size=4)]
    with DataLoader(_MeetingDataset(32, str(tmp_path / "together"), 4),
                    batch_size=4, num_workers=4, thread_pool=False) as loader:
        got = [b.asnumpy() for b in loader]
    assert len(got) == len(want) == 8
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert os.listdir(tmp_path / "alone") == [str(os.getpid())]
    workers = os.listdir(tmp_path / "together")
    assert len(workers) == 4 and str(os.getpid()) not in workers


def test_dataloader_shuffle_covers_dataset():
    ds = ArrayDataset(np.arange(30, dtype="f"))
    seen = []
    for b in DataLoader(ds, batch_size=7, shuffle=True, last_batch="keep"):
        seen.extend(b.asnumpy().astype(int).tolist())
    assert sorted(seen) == list(range(30))


def _double_batchify(samples):
    """Module-level (picklable) batchify: numpy in, numpy out."""
    return np.stack([s * 2 for s in samples])


def test_dataloader_custom_batchify_in_process_mode():
    ds = ArrayDataset(np.arange(12, dtype="f"))
    batchify = _double_batchify
    got = [b.asnumpy() for b in DataLoader(
        ds, batch_size=4, shuffle=False, num_workers=2, thread_pool=False,
        batchify_fn=batchify)]
    np.testing.assert_array_equal(
        np.concatenate(got), np.arange(12, dtype="f") * 2)


def test_dataloader_process_mode_abandoned_iteration_no_deadlock():
    """Breaking out of a process-worker epoch early must not hang the
    parent on pool teardown (review finding r5: the semaphore-gated
    feeder thread needs the stop signal)."""
    ds = ArrayDataset(np.arange(64, dtype="f"))
    t0 = time.perf_counter()
    for i, b in enumerate(DataLoader(ds, batch_size=2, shuffle=False,
                                     num_workers=2, thread_pool=False)):
        if i == 0:
            break
    assert time.perf_counter() - t0 < 30.0


def test_dataloader_start_method_defaults_to_spawn():
    """The process pool defaults to spawn (fork from this always-multi-
    threaded parent can deadlock children on inherited locks); fork is an
    explicit MXNET_MP_START_METHOD opt-in.  The method asked for is what is
    read; the pool here is spawned either way, since a fork from this
    process is the deadlock itself."""
    import multiprocessing as mp

    seen = []
    real_get_context = mp.get_context

    def spy(method=None):
        seen.append(method)
        return real_get_context("spawn")

    ds = ArrayDataset(np.arange(8, dtype="f"))
    mp.get_context = spy
    try:
        list(DataLoader(ds, batch_size=4, num_workers=1, thread_pool=False))
        assert seen[-1] == "spawn"
        os.environ["MXNET_MP_START_METHOD"] = "fork"
        list(DataLoader(ds, batch_size=4, num_workers=1, thread_pool=False))
        assert seen[-1] == "fork"
    finally:
        mp.get_context = real_get_context
        os.environ.pop("MXNET_MP_START_METHOD", None)


def test_dataloader_process_pool_persists_across_epochs():
    """Spawn startup is paid once: the worker pool is reused across
    __iter__ calls instead of being respawned per epoch."""
    ds = ArrayDataset(np.arange(16, dtype="f"))
    dl = DataLoader(ds, batch_size=4, num_workers=1, thread_pool=False)
    first = [b.asnumpy() for b in dl]
    pool = dl._proc_pool
    assert pool is not None
    second = [b.asnumpy() for b in dl]
    assert dl._proc_pool is pool  # same workers, no respawn
    for a, b in zip(first, second):
        np.testing.assert_array_equal(a, b)


def test_dataloader_close_releases_workers():
    """close() (or the context manager) tears the persistent pool down
    deterministically; the loader stays usable afterwards."""
    ds = ArrayDataset(np.arange(8, dtype="f"))
    with DataLoader(ds, batch_size=4, num_workers=1,
                    thread_pool=False) as dl:
        list(dl)
        assert dl._proc_pool is not None
    assert dl._proc_pool is None  # context exit closed the pool
    out = [b.asnumpy() for b in dl]  # fresh pool on demand
    np.testing.assert_array_equal(np.concatenate(out),
                                  np.arange(8, dtype="f"))
    dl.close()
