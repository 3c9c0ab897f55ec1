"""CSVIter / LibSVMIter / MNISTIter + parallel-decode ImageRecordIter
(reference: src/io/iter_csv.cc, iter_libsvm.cc, iter_mnist.cc,
iter_image_recordio_2.cc — SURVEY.md §3.4/§4.5)."""
import gzip
import struct

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import io as mio
from mxnet_tpu import recordio


def test_csv_iter_matches_numpy(tmp_path):
    R = np.random.RandomState(0)
    data = R.randn(10, 6).astype("f")
    labels = R.randint(0, 3, (10, 1)).astype("f")
    dpath, lpath = str(tmp_path / "d.csv"), str(tmp_path / "l.csv")
    np.savetxt(dpath, data, delimiter=",")
    np.savetxt(lpath, labels, delimiter=",")
    it = mio.CSVIter(data_csv=dpath, data_shape=(2, 3), label_csv=lpath,
                     batch_size=4)
    batches = list(it)
    assert len(batches) == 3
    got = np.concatenate([b.data[0].asnumpy() for b in batches])
    want = data.reshape(10, 2, 3)
    # tail batch wraps to the head (round_batch)
    want = np.concatenate([want, want[:2]])
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert batches[-1].pad == 2
    got_l = np.concatenate([b.label[0].asnumpy() for b in batches])
    np.testing.assert_allclose(got_l[:10], labels[:, 0], rtol=1e-5)
    # reset restarts
    it.reset()
    b0 = next(it)
    np.testing.assert_allclose(b0.data[0].asnumpy(),
                               data[:4].reshape(4, 2, 3), rtol=1e-5)


def test_libsvm_iter_csr(tmp_path):
    path = str(tmp_path / "d.libsvm")
    rows = ["1 0:1.5 3:2.0", "0 1:1.0", "1 2:3.0 4:0.5", "0 0:2.0 4:1.0",
            "1 3:1.0"]
    with open(path, "w") as f:
        f.write("\n".join(rows) + "\n")
    dense = np.zeros((5, 5), "f")
    dense[0, 0], dense[0, 3] = 1.5, 2.0
    dense[1, 1] = 1.0
    dense[2, 2], dense[2, 4] = 3.0, 0.5
    dense[3, 0], dense[3, 4] = 2.0, 1.0
    dense[4, 3] = 1.0
    labels = np.array([1, 0, 1, 0, 1], "f")

    it = mio.LibSVMIter(data_libsvm=path, data_shape=(5,), batch_size=2)
    batches = list(it)
    assert len(batches) == 3
    assert batches[0].data[0].stype == "csr"
    got = np.concatenate(
        [np.asarray(b.data[0]._get()) for b in batches])
    want = np.concatenate([dense, dense[:1]])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    got_l = np.concatenate([b.label[0].asnumpy() for b in batches])
    np.testing.assert_allclose(got_l[:5], labels)
    assert batches[-1].pad == 1


def _write_idx(tmp_path, images, labels):
    ipath, lpath = str(tmp_path / "img.idx.gz"), str(tmp_path / "lbl.idx")
    with gzip.open(ipath, "wb") as f:
        f.write(struct.pack(">BBBB", 0, 0, 8, 3))
        f.write(struct.pack(">III", *images.shape))
        f.write(images.tobytes())
    with open(lpath, "wb") as f:
        f.write(struct.pack(">BBBB", 0, 0, 8, 1))
        f.write(struct.pack(">I", labels.shape[0]))
        f.write(labels.tobytes())
    return ipath, lpath


def test_mnist_iter(tmp_path):
    R = np.random.RandomState(0)
    images = R.randint(0, 256, (10, 5, 5)).astype(np.uint8)
    labels = R.randint(0, 10, (10,)).astype(np.uint8)
    ipath, lpath = _write_idx(tmp_path, images, labels)
    it = mio.MNISTIter(image=ipath, label=lpath, batch_size=4, flat=False)
    b = next(it)
    assert b.data[0].shape == (4, 1, 5, 5)
    np.testing.assert_allclose(b.data[0].asnumpy(),
                               images[:4, None].astype("f") / 255.0,
                               rtol=1e-6)
    np.testing.assert_allclose(b.label[0].asnumpy(), labels[:4].astype("f"))
    # flat + shuffle determinism under seed
    it2 = mio.MNISTIter(image=ipath, label=lpath, batch_size=4, flat=True,
                        shuffle=True, seed=7)
    it3 = mio.MNISTIter(image=ipath, label=lpath, batch_size=4, flat=True,
                        shuffle=True, seed=7)
    b2, b3 = next(it2), next(it3)
    assert b2.data[0].shape == (4, 25)
    np.testing.assert_allclose(b2.data[0].asnumpy(), b3.data[0].asnumpy())


def _make_rec(tmp_path, n, hw=32):
    path = str(tmp_path / "synth.rec")
    rec = recordio.MXRecordIO(path, "w")
    R = np.random.RandomState(0)
    for i in range(n):
        img = R.randint(0, 255, (hw, hw, 3)).astype(np.uint8)
        header = recordio.IRHeader(0, float(i % 7), i, 0)
        rec.write(recordio.pack_img(header, img))
    rec.close()
    return path


def test_image_record_iter_parallel_decode_deterministic(tmp_path):
    """Augmentation must be deterministic under the decode pool (per-record
    RNG), and two epochs must differ when rand_mirror is on."""
    path = _make_rec(tmp_path, 24)
    def collect():
        it = mio.ImageRecordIter(
            path_imgrec=path, data_shape=(3, 28, 28), batch_size=8,
            rand_crop=True, rand_mirror=True, seed=3, preprocess_threads=4)
        return np.concatenate([b.data[0].asnumpy() for b in it])

    a, b = collect(), collect()
    np.testing.assert_allclose(a, b, rtol=1e-6)  # same seed => identical
    assert a.shape == (24, 3, 28, 28)


def test_csv_and_libsvm_pad_wraps_multiple_times(tmp_path):
    """batch_size larger than the dataset must wrap repeatedly (the
    reference round_batch semantics), not crash or emit short batches."""
    dpath = str(tmp_path / "d3.csv")
    np.savetxt(dpath, np.arange(6, dtype="f").reshape(3, 2), delimiter=",")
    it = mio.CSVIter(data_csv=dpath, data_shape=(2,), batch_size=8)
    b = next(it)
    assert b.data[0].shape == (8, 2)
    assert b.pad == 5
    want = np.arange(6, dtype="f").reshape(3, 2)
    np.testing.assert_allclose(b.data[0].asnumpy(),
                               want[np.arange(8) % 3], rtol=1e-6)

    spath = str(tmp_path / "d3.libsvm")
    with open(spath, "w") as f:
        f.write("1 0:1.0\n0 2:2.0\n1 1:3.0\n")
    sit = mio.LibSVMIter(data_libsvm=spath, data_shape=(4,), batch_size=8)
    sb = next(sit)
    assert sb.data[0].stype == "csr"
    assert sb.data[0].shape == (8, 4)
    assert sb.pad == 5
    dense = np.zeros((3, 4), "f")
    dense[0, 0], dense[1, 2], dense[2, 1] = 1.0, 2.0, 3.0
    np.testing.assert_allclose(np.asarray(sb.data[0]._get()),
                               dense[np.arange(8) % 3], rtol=1e-6)


def test_mnist_iter_rejects_non_idx(tmp_path):
    bad = str(tmp_path / "junk.idx")
    with open(bad, "wb") as f:
        f.write(b"\x01\x02\x03\x03" + b"\x00" * 16)
    with pytest.raises(mx.MXNetError):
        mio.MNISTIter(image=bad, label=bad, batch_size=2)


def test_image_record_iter_close_and_abandon(tmp_path):
    """close() stops the pool; an abandoned iterator's feeder thread exits
    on its own (weak binding) instead of leaking forever."""
    import gc
    import threading

    path = _make_rec(tmp_path, 64)
    it = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                             batch_size=4, preprocess_threads=2,
                             prefetch_buffer=1)
    next(it)
    it.close()
    with pytest.raises(mx.MXNetError):
        it.next()

    it2 = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                              batch_size=4, preprocess_threads=2,
                              prefetch_buffer=1)
    next(it2)
    feeder = it2._pipeline._thread
    del it2
    gc.collect()
    feeder.join(timeout=5)
    assert not feeder.is_alive(), "feeder thread leaked after abandonment"


def test_image_record_iter_epoch_reset(tmp_path):
    path = _make_rec(tmp_path, 10)
    it = mio.ImageRecordIter(path_imgrec=path, data_shape=(3, 32, 32),
                             batch_size=4, preprocess_threads=2)
    n1 = sum(b.data[0].shape[0] for b in it)
    with pytest.raises(StopIteration):
        it.next()
    it.reset()
    n2 = sum(b.data[0].shape[0] for b in it)
    assert n1 == n2 == 12  # 10 records padded to 3 batches of 4


def test_image_record_iter_sustained_throughput(tmp_path):
    """The decode pool keeps several decodes in flight at once, so a single
    Python thread never bounds the pipeline (SURVEY §4.5): the first decode
    of a pooled run comes back only after a second thread has entered
    another, and what the pool yields is the single-threaded run's, batch
    for batch (a record's augmentation is keyed by the record, not by the
    thread that decodes it).

    Events, not seconds: the ratio of pooled to serial images a second that
    this test used to gate on is the host's scheduling (0.7 to 0.85 on an
    oversubscribed machine whatever the pool's width), and failed under six
    xdist workers on healthy code."""
    import threading

    path = _make_rec(tmp_path, 128, hw=64)

    class Meeting(mio.ImageRecordIter):
        threads, lock, met = set(), threading.Lock(), threading.Event()

        def _decode(self, payload, index):
            with self.lock:
                self.threads.add(threading.get_ident())
                if len(self.threads) > 1:
                    self.met.set()
            if self._n_threads > 1 and not self.met.wait(60):
                raise RuntimeError("no second decode began within 60 s")
            return super()._decode(payload, index)

    def run(threads):
        return [(b.data[0].asnumpy(), b.label[0].asnumpy())
                for b in Meeting(
                    path_imgrec=path, data_shape=(3, 56, 56), batch_size=64,
                    rand_crop=True, preprocess_threads=threads, seed=1)]

    serial = run(1)
    assert len(Meeting.threads) == 1 and not Meeting.met.is_set()
    Meeting.threads.clear()
    pooled = run(4)
    assert Meeting.met.is_set() and 2 <= len(Meeting.threads) <= 4
    assert len(pooled) == len(serial) == 2
    for (a, la), (b, lb) in zip(pooled, serial):
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(la, lb)
