"""Data-plane pipeline tests: threaded decode, deep prefetch, raw records.

Covers the fused fast path of the reference's ImageRecordIter
(src/io/iter_image_recordio_2.cc:663-762): multi-threaded decode+augment
(`preprocess_threads`), N-deep background prefetch (`prefetch_buffer` /
iter_prefetcher.h), and the raw-tensor record path that feeds an
accelerator faster than a host JPEG decoder can.
"""
import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import io as mio
from incubator_mxnet_tpu import recordio


def _write_rec(tmp_path, n=12, h=8, w=8, raw=False, indexed=True):
    import cv2
    prefix = str(tmp_path / ("raw" if raw else "jpg"))
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rs = np.random.RandomState(7)
    imgs = []
    for i in range(n):
        img = (rs.rand(h, w, 3) * 255).astype(np.uint8)
        header = recordio.IRHeader(0, float(i % 3), i, 0)
        if raw:
            s = recordio.pack(header, img.tobytes())
        else:
            ok, buf = cv2.imencode(".png", cv2.cvtColor(img,
                                                        cv2.COLOR_RGB2BGR))
            assert ok
            s = recordio.pack(header, buf.tobytes())
        rec.write_idx(i, s)
        imgs.append(img)
    rec.close()
    return prefix, np.stack(imgs)


def test_image_record_iter_honors_knobs(tmp_path):
    """preprocess_threads must actually change the decode path (pool) and
    prefetch_buffer must wrap in PrefetchingIter — and the data must come
    out identical to the single-threaded, unbuffered path."""
    prefix, imgs = _write_rec(tmp_path, n=12)
    kw = dict(path_imgrec=prefix + ".rec", path_imgidx=prefix + ".idx",
              data_shape=(3, 8, 8), batch_size=4, label_width=1)
    it_plain = mio.ImageRecordIter(preprocess_threads=1, prefetch_buffer=0,
                                   **kw)
    it_fast = mio.ImageRecordIter(preprocess_threads=3, prefetch_buffer=3,
                                  **kw)
    assert isinstance(it_fast, mio.PrefetchingIter)
    assert not isinstance(it_plain, mio.PrefetchingIter)
    for _ in range(2):  # two epochs: reset() must survive the buffering
        got_plain = [b.data[0].asnumpy() for b in it_plain]
        got_fast = [b.data[0].asnumpy() for b in it_fast]
        assert len(got_plain) == len(got_fast) == 3
        for a, b in zip(got_plain, got_fast):
            np.testing.assert_array_equal(a, b)
        it_plain.reset()
        it_fast.reset()


def test_raw_record_decode(tmp_path):
    """decode='raw'/auto must reproduce the packed tensors exactly."""
    prefix, imgs = _write_rec(tmp_path, n=8, raw=True)
    it = mio.ImageRecordIter(path_imgrec=prefix + ".rec",
                             path_imgidx=prefix + ".idx",
                             data_shape=(3, 8, 8), batch_size=8,
                             preprocess_threads=2, prefetch_buffer=2)
    batch = it.next()
    got = batch.data[0].asnumpy()  # NCHW float32
    want = imgs.transpose(0, 3, 1, 2).astype(np.float32)
    np.testing.assert_array_equal(got, want)
    labels = batch.label[0].asnumpy()
    np.testing.assert_array_equal(labels, np.arange(8) % 3)


def test_prefetching_iter_depth_and_reset():
    """A prefetch_buffer-deep PrefetchingIter must deliver every batch of
    every epoch in order, same as the base iterator."""
    data = np.arange(40, dtype=np.float32).reshape(20, 2)
    label = np.arange(20, dtype=np.float32)
    base = mio.NDArrayIter(data.copy(), label.copy(), batch_size=5)
    pf = mio.PrefetchingIter(
        mio.NDArrayIter(data.copy(), label.copy(), batch_size=5),
        prefetch_buffer=3)
    for _ in range(3):
        want = [b.data[0].asnumpy() for b in base]
        got = [b.data[0].asnumpy() for b in pf]
        assert len(want) == len(got) == 4
        for a, b in zip(want, got):
            np.testing.assert_array_equal(a, b)
        base.reset()
        pf.reset()


def test_sequential_rec_native_or_python(tmp_path):
    """Sequential (non-indexed) .rec reading must work through whichever
    reader backend is active (native C++ prefetch reader when built)."""
    prefix, imgs = _write_rec(tmp_path, n=6, raw=True)
    from incubator_mxnet_tpu.image import image as img_mod
    it = img_mod.ImageIter(batch_size=3, data_shape=(3, 8, 8),
                           path_imgrec=prefix + ".rec")
    seen = [b.data[0].asnumpy() for b in it]
    assert len(seen) == 2
    np.testing.assert_array_equal(
        np.concatenate(seen),
        imgs.transpose(0, 3, 1, 2).astype(np.float32))
    it.reset()  # native reader must reopen cleanly
    again = [b.data[0].asnumpy() for b in it]
    np.testing.assert_array_equal(np.concatenate(again),
                                  np.concatenate(seen))


def test_prefetching_iter_repolls_after_exhaustion():
    """iter_next() past end-of-epoch must keep answering False, not hang
    (regression: the queue-based rewrite initially deadlocked here)."""
    data = np.arange(8, dtype=np.float32).reshape(4, 2)
    pf = mio.PrefetchingIter(mio.NDArrayIter(data, np.zeros(4), batch_size=2),
                             prefetch_buffer=2)
    assert pf.iter_next() and pf.iter_next()
    for _ in range(3):
        assert not pf.iter_next()
    pf.reset()
    assert pf.iter_next()


def test_uint8_pipeline_keeps_float_labels(tmp_path):
    """dtype='uint8' types only the image blob — labels >= 256 must
    survive (regression: labels were cast to uint8 and wrapped mod 256)."""
    prefix = str(tmp_path / "biglabel")
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    rs = np.random.RandomState(0)
    for i in range(4):
        img = (rs.rand(8, 8, 3) * 255).astype(np.uint8)
        rec.write_idx(i, recordio.pack(
            recordio.IRHeader(0, float(700 + i), i, 0), img.tobytes()))
    rec.close()
    it = mio.ImageRecordIter(path_imgrec=prefix + ".rec",
                             path_imgidx=prefix + ".idx",
                             data_shape=(3, 8, 8), batch_size=4,
                             dtype="uint8", aug_list=[],
                             preprocess_threads=1, prefetch_buffer=0)
    b = it.next()
    assert b.data[0].dtype == np.uint8
    np.testing.assert_array_equal(b.label[0].asnumpy(),
                                  [700.0, 701.0, 702.0, 703.0])


def test_prefetch_propagates_producer_errors():
    """A corrupt record must fail the consumer loudly, not hang it."""
    class Boom(mio.DataIter):
        def __init__(self):
            super().__init__(2)
            self.n = 0
        @property
        def provide_data(self):
            return [mio.DataDesc("data", (2, 2), "float32")]
        @property
        def provide_label(self):
            return [mio.DataDesc("l", (2,), "float32")]
        def reset(self):
            self.n = 0
        def next(self):
            self.n += 1
            if self.n == 2:
                raise ValueError("corrupt record")
            return mio.DataBatch([mx.nd.zeros((2, 2))], [mx.nd.zeros(2)], 0)
    pf = mio.PrefetchingIter(Boom(), prefetch_buffer=2)
    assert pf.iter_next()
    with pytest.raises(ValueError, match="corrupt record"):
        pf.iter_next()
    pf.close()


def test_uint8_with_augmenters_rejected(tmp_path):
    prefix, _ = _write_rec(tmp_path, n=4, raw=True)
    with pytest.raises(ValueError, match="uint8"):
        mio.ImageRecordIter(path_imgrec=prefix + ".rec",
                            path_imgidx=prefix + ".idx",
                            data_shape=(3, 8, 8), batch_size=4,
                            dtype="uint8", mean_r=123.0,
                            preprocess_threads=1, prefetch_buffer=0)


def test_multipart_record_roundtrip(tmp_path, monkeypatch):
    """Payloads over the 29-bit length limit split into begin/middle/end
    parts (dmlc convention) instead of silently corrupting the header —
    readable by BOTH the python and native readers (ADVICE r02)."""
    monkeypatch.setattr(recordio, "_MAX_REC_LEN", 100)  # force splitting
    monkeypatch.setenv("MXTPU_NATIVE_IO", "0")  # python framing path
    path = str(tmp_path / "multi.rec")
    w = recordio.MXRecordIO(path, "w")
    assert not w._native_handle
    payloads = [b"x" * 10, b"y" * 321, b"z" * 100, b"w" * 205]
    for pl in payloads:
        w.write(pl)
    w.close()

    r = recordio.MXRecordIO(path, "r")
    assert not r._native_handle
    got = []
    while True:
        s = r.read()
        if s is None:
            break
        got.append(bytes(s))
    assert got == payloads

    from incubator_mxnet_tpu import _native
    if _native.available():
        nr = _native.NativeRecordReader(path)
        ngot = []
        while True:
            s = nr.read()
            if s is None:
                break
            ngot.append(bytes(s))
        assert ngot == payloads


def test_prefetch_depth_knob(monkeypatch):
    """``GRAFT_PREFETCH_DEPTH`` sets the DataLoader's batches in flight
    (default 2, floor 1); a live ``set_prefetch_depth`` beats the env,
    and no depth changes what the loader yields."""
    from incubator_mxnet_tpu.gluon.data import DataLoader
    from incubator_mxnet_tpu.gluon.data.dataloader import (
        prefetch_depth_default)
    from incubator_mxnet_tpu.gluon.data.dataset import ArrayDataset
    assert prefetch_depth_default() == 2    # the double-buffer default
    monkeypatch.setenv("GRAFT_PREFETCH_DEPTH", "5")
    assert prefetch_depth_default() == 5
    monkeypatch.setenv("GRAFT_PREFETCH_DEPTH", "0")
    assert prefetch_depth_default() == 1    # floor: one in flight
    monkeypatch.setenv("GRAFT_PREFETCH_DEPTH", "junk")
    assert prefetch_depth_default() == 2
    ds = ArrayDataset(mx.nd.array(np.arange(32, dtype=np.float32)))
    loader = DataLoader(ds, batch_size=4, prefetch_device=False)
    try:
        assert loader.prefetch_depth() == 2
        loader.set_prefetch_depth(6)        # live override beats the env
        assert loader.prefetch_depth() == 6
        loader.set_prefetch_depth(0)
        assert loader.prefetch_depth() == 1
        out = [b for b in loader]
        assert len(out) == 8                # depth never changes content
    finally:
        loader.close()
