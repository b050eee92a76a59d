"""Loader for the native C++ components (src/ → build/*.so).

The data plane (RecordIO parsing, threaded prefetch) and the C predict
ABI are native code like the reference's (SURVEY §1 layers 7/8); Python
binds them through ctypes.  When the toolchain cannot build them the
pure-Python paths serve instead, and the loader says so once on stderr.
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import sys

_SRC_DIR = os.path.join(os.path.dirname(__file__), "..", "src")
_BUILD_DIR = os.path.join(_SRC_DIR, "build")

_io_lib = None
_io_tried = False


def _build():
    """Bring ``src/build`` up to date with the sources.  Runs before every
    first load, not only when the library is missing: ``src/build/`` is
    not tracked by git, so a file already on disk may have been compiled
    from older sources, and ``make`` rebuilds only what is stale."""
    try:
        subprocess.run(["make", "-C", _SRC_DIR], capture_output=True,
                       text=True, timeout=300, check=True)
        return True
    except (OSError, subprocess.SubprocessError) as exc:
        detail = (getattr(exc, "stderr", None) or str(exc)).strip()
        print("incubator_mxnet_tpu: `make -C %s` failed, the pure-Python "
              "RecordIO reader takes over: %s"
              % (os.path.normpath(_SRC_DIR),
                 detail.splitlines()[-1] if detail else type(exc).__name__),
              file=sys.stderr)
        return False


def _load(name):
    if not _build():
        return None
    try:
        return ctypes.CDLL(os.path.join(_BUILD_DIR, name))
    except OSError:
        return None


def io_lib():
    """The RecordIO native library, or None (pure-Python fallback)."""
    global _io_lib, _io_tried
    if _io_tried:
        return _io_lib
    _io_tried = True
    lib = _load("libmxtpu_io.so")
    if lib is not None:
        lib.MXTPURecordIOReaderCreate.restype = ctypes.c_void_p
        lib.MXTPURecordIOReaderCreate.argtypes = [ctypes.c_char_p]
        lib.MXTPURecordIOReaderNext.restype = ctypes.c_int
        lib.MXTPURecordIOReaderNext.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.MXTPURecordIOReaderSeek.argtypes = [ctypes.c_void_p,
                                                ctypes.c_uint64]
        lib.MXTPURecordIOReaderTell.restype = ctypes.c_uint64
        lib.MXTPURecordIOReaderTell.argtypes = [ctypes.c_void_p]
        lib.MXTPURecordIOReaderFree.argtypes = [ctypes.c_void_p]
        lib.MXTPURecordIOWriterCreate.restype = ctypes.c_void_p
        lib.MXTPURecordIOWriterCreate.argtypes = [ctypes.c_char_p]
        lib.MXTPURecordIOWriterWrite.restype = ctypes.c_int
        lib.MXTPURecordIOWriterWrite.argtypes = [
            ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
        lib.MXTPURecordIOWriterTell.restype = ctypes.c_uint64
        lib.MXTPURecordIOWriterTell.argtypes = [ctypes.c_void_p]
        lib.MXTPURecordIOWriterFree.argtypes = [ctypes.c_void_p]
        lib.MXTPUPrefetchReaderCreate.restype = ctypes.c_void_p
        lib.MXTPUPrefetchReaderCreate.argtypes = [ctypes.c_char_p,
                                                  ctypes.c_uint64]
        lib.MXTPUPrefetchReaderNext.restype = ctypes.c_int
        lib.MXTPUPrefetchReaderNext.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_char_p),
            ctypes.POINTER(ctypes.c_uint64)]
        lib.MXTPUPrefetchReaderFree.argtypes = [ctypes.c_void_p]
    _io_lib = lib
    return lib


class NativeRecordReader(object):
    """Sequential reader over libmxtpu_io (dmlc wire format)."""

    def __init__(self, path):
        lib = io_lib()
        if lib is None:
            raise OSError("native IO library unavailable")
        self._lib = lib
        self._h = lib.MXTPURecordIOReaderCreate(path.encode())
        if not self._h:
            raise IOError("cannot open %s" % path)

    def read(self):
        out = ctypes.c_char_p()
        size = ctypes.c_uint64()
        ok = self._lib.MXTPURecordIOReaderNext(self._h, ctypes.byref(out),
                                               ctypes.byref(size))
        if not ok:
            return None
        return ctypes.string_at(out, size.value)

    def seek(self, pos):
        self._lib.MXTPURecordIOReaderSeek(self._h, pos)

    def tell(self):
        return self._lib.MXTPURecordIOReaderTell(self._h)

    def close(self):
        if self._h:
            self._lib.MXTPURecordIOReaderFree(self._h)
            self._h = None

    def __del__(self):
        self.close()


class NativeRecordWriter(object):
    """Sequential writer over libmxtpu_io."""

    def __init__(self, path):
        lib = io_lib()
        if lib is None:
            raise OSError("native IO library unavailable")
        self._lib = lib
        self._h = lib.MXTPURecordIOWriterCreate(path.encode())
        if not self._h:
            raise IOError("cannot open %s" % path)

    def write(self, data):
        data = bytes(data)
        if self._lib.MXTPURecordIOWriterWrite(self._h, data, len(data)) != 0:
            raise IOError("native RecordIO write failed")

    def tell(self):
        return self._lib.MXTPURecordIOWriterTell(self._h)

    def close(self):
        if self._h:
            self._lib.MXTPURecordIOWriterFree(self._h)
            self._h = None

    def __del__(self):
        self.close()


class NativePrefetchReader(object):
    """Background-thread record reader (ThreadedIter's role): file IO and
    record framing proceed while Python decodes the previous batch."""

    def __init__(self, path, capacity=16):
        lib = io_lib()
        if lib is None:
            raise OSError("native IO library unavailable")
        self._lib = lib
        self._h = lib.MXTPUPrefetchReaderCreate(path.encode(), capacity)
        if not self._h:
            raise IOError("cannot open %s" % path)

    def read(self):
        out = ctypes.c_char_p()
        size = ctypes.c_uint64()
        ok = self._lib.MXTPUPrefetchReaderNext(self._h, ctypes.byref(out),
                                               ctypes.byref(size))
        if not ok:
            return None
        return ctypes.string_at(out, size.value)

    def close(self):
        if self._h:
            self._lib.MXTPUPrefetchReaderFree(self._h)
            self._h = None

    def __del__(self):
        self.close()


def available():
    return io_lib() is not None
