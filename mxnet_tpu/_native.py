"""ctypes binding to the native runtime (``native/`` → ``libmxtpu.so``).

The reference loads ``libmxnet.so`` through ctypes (``python/mxnet/base.py``:
``_LIB``/``check_call``); this is the same pattern for the TPU build's native
core (engine, storage, profiler, recordio — see ``native/include/mxtpu/c_api.h``).
The library is built on demand with ``make`` the first time it's needed and
cached; every consumer has a pure-Python fallback, and a build that fails
says so (a warning, and :func:`status`).
"""

from __future__ import annotations

import collections
import contextlib
import ctypes
import fcntl
import os
import subprocess
import threading
import warnings

__all__ = ["lib", "available", "status", "RecordLoader", "DecodeLoader",
           "buf_to_bytes"]

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "native")
_SO_PATH = os.path.join(_NATIVE_DIR, "build", "libmxtpu.so")

_lock = threading.Lock()
_lib = None
_tried = False
_status = "not loaded"


def _configure(lib):
    """Declare argtypes/restypes for the C ABI."""
    c = ctypes
    lib.mxtpu_var_new.restype = c.c_void_p
    lib.mxtpu_var_delete.argtypes = [c.c_void_p]
    lib.mxtpu_push.argtypes = [
        c.CFUNCTYPE(None, c.c_void_p), c.c_void_p,
        c.CFUNCTYPE(None, c.c_void_p),
        c.POINTER(c.c_void_p), c.c_int, c.POINTER(c.c_void_p), c.c_int,
        c.c_int, c.c_int, c.c_char_p]
    lib.mxtpu_wait_for_var.argtypes = [c.c_void_p]
    lib.mxtpu_engine_pending.restype = c.c_long
    lib.mxtpu_storage_alloc.restype = c.c_void_p
    lib.mxtpu_storage_alloc.argtypes = [c.c_size_t]
    lib.mxtpu_storage_free.argtypes = [c.c_void_p, c.c_size_t]
    lib.mxtpu_storage_direct_free.argtypes = [c.c_void_p, c.c_size_t]
    lib.mxtpu_storage_pooled_bytes.restype = c.c_size_t
    lib.mxtpu_storage_used_bytes.restype = c.c_size_t
    lib.mxtpu_profiler_set_state.argtypes = [c.c_int]
    lib.mxtpu_profiler_dump.argtypes = [c.c_char_p]
    lib.mxtpu_profiler_add_event.argtypes = [
        c.c_char_p, c.c_char_p, c.c_int64, c.c_int64, c.c_int]
    lib.mxtpu_recordio_writer_open.restype = c.c_void_p
    lib.mxtpu_recordio_writer_open.argtypes = [c.c_char_p]
    lib.mxtpu_recordio_writer_write.argtypes = [
        c.c_void_p, c.c_char_p, c.c_size_t]
    lib.mxtpu_recordio_writer_tell.restype = c.c_long
    lib.mxtpu_recordio_writer_tell.argtypes = [c.c_void_p]
    lib.mxtpu_recordio_writer_close.argtypes = [c.c_void_p]
    lib.mxtpu_recordio_reader_open.restype = c.c_void_p
    lib.mxtpu_recordio_reader_open.argtypes = [c.c_char_p]
    lib.mxtpu_recordio_reader_next.argtypes = [
        c.c_void_p, c.POINTER(c.POINTER(c.c_char)), c.POINTER(c.c_size_t)]
    lib.mxtpu_recordio_reader_tell.restype = c.c_long
    lib.mxtpu_recordio_reader_tell.argtypes = [c.c_void_p]
    lib.mxtpu_recordio_reader_close.argtypes = [c.c_void_p]
    lib.mxtpu_loader_create.restype = c.c_void_p
    lib.mxtpu_loader_create.argtypes = [
        c.c_char_p, c.c_int, c.c_int, c.c_int, c.c_uint, c.c_int, c.c_int]
    lib.mxtpu_loader_next.argtypes = [
        c.c_void_p, c.POINTER(c.POINTER(c.c_char)), c.POINTER(c.c_size_t)]
    lib.mxtpu_loader_next_batch.argtypes = [
        c.c_void_p, c.c_int, c.POINTER(c.POINTER(c.c_char)),
        c.POINTER(c.c_size_t)]
    lib.mxtpu_loader_reset.argtypes = [c.c_void_p]
    lib.mxtpu_loader_free.argtypes = [c.c_void_p]
    lib.mxtpu_decode_loader_create.restype = c.c_void_p
    lib.mxtpu_decode_loader_create.argtypes = [
        c.c_char_p, c.c_int, c.c_int, c.c_int, c.c_uint, c.c_int, c.c_int,
        c.c_int, c.c_int, c.c_int, c.c_int, c.c_int, c.c_int]
    lib.mxtpu_decode_loader_next_batch.argtypes = [
        c.c_void_p, c.c_int, c.POINTER(c.c_ubyte), c.POINTER(c.c_float)]
    lib.mxtpu_decode_loader_skipped.restype = c.c_long
    lib.mxtpu_decode_loader_skipped.argtypes = [c.c_void_p]
    lib.mxtpu_decode_loader_reset.argtypes = [c.c_void_p]
    lib.mxtpu_decode_loader_free.argtypes = [c.c_void_p]
    lib.mxtpu_buf_free.argtypes = [c.POINTER(c.c_char)]
    lib.mxtpu_version.restype = c.c_char_p
    return lib


def _build():
    """``make`` the library; returns None on success, else why not."""
    try:
        subprocess.run(["make", "-s", "-j4"], cwd=_NATIVE_DIR, check=True,
                       capture_output=True, timeout=300)
    except subprocess.CalledProcessError as exc:
        tail = (exc.stderr or b"").decode("utf-8", "replace").strip()
        return "make failed: %s" % tail[-300:]
    except (OSError, subprocess.TimeoutExpired) as exc:
        return "make did not run: %r" % (exc,)
    return None


@contextlib.contextmanager
def _one_process():
    """Hold ``native/build/.lock`` for this process alone: the workers
    of a parallel test run share the checkout, and a library that one of
    them is still linking must be neither loaded nor built over by the
    others (both end in a segmentation fault)."""
    build = os.path.dirname(_SO_PATH)
    try:
        os.makedirs(build, exist_ok=True)
        fd = open(os.path.join(build, ".lock"), "w")
    except OSError:             # a checkout that cannot be written to
        yield
        return
    with fd:
        fcntl.flock(fd, fcntl.LOCK_EX)
        yield


def _load():
    """(configured CDLL, None), or (None, why it cannot be loaded)."""
    try:
        return _configure(ctypes.CDLL(_SO_PATH)), None
    except (OSError, AttributeError) as exc:
        return None, "cannot load %s: %s" % (_SO_PATH, exc)


def lib():
    """Return the configured CDLL, building it if needed; None on failure.

    A failed build or load is not silent: it warns once with the reason
    and :func:`status` keeps it.  Disable entirely with MXTPU_NO_NATIVE=1
    (forces pure-Python fallbacks — the analog of the reference's
    NaiveEngine debug switch at the build level).
    """
    global _lib, _tried, _status
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("MXTPU_NO_NATIVE"):
            _status = "disabled by MXTPU_NO_NATIVE"
            return None
        with _one_process():
            found = os.path.exists(_SO_PATH)
            why = None if found else _build()
            if why is None:
                _lib, why = _load()
                if _lib is None and found:
                    # stale .so missing newer symbols: rebuild once, then
                    # retry
                    found = False
                    why = _build()
                    if why is None:
                        _lib, why = _load()
        if _lib is None:
            _status = "unavailable, pure-Python fallbacks in use (%s)" % why
            warnings.warn("native runtime " + _status)
        else:
            _status = "loaded %s (%s)" % (
                _SO_PATH, "found built" if found
                else "built now from native/src")
        return _lib


def status():
    """One line on which native runtime this process got, and why."""
    lib()
    return _status


def available():
    return lib() is not None


def buf_to_bytes(libh, ptr, length):
    """Copy a malloc'd native buffer into bytes and free it."""
    data = ctypes.string_at(ptr, length)
    libh.mxtpu_buf_free(ptr)
    return data


class RecordLoader(object):
    """Threaded prefetching sharded record loader (native
    ``mxtpu_loader_*``; the dmlc ``ThreadedIter``+``InputSplit`` role —
    reference ``src/io/iter_image_recordio_2.cc:104-112``).  Designed for
    multi-core hosts where the reader thread overlaps decode; on a 1-core
    box it's pure overhead vs the Python reader."""

    _BATCH = 64  # records per binding-layer crossing

    def __init__(self, path, part_index=0, num_parts=1, shuffle=False,
                 seed=0, queue_size=256, shuffle_chunk=1024):
        self._lib = lib()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        self._h = self._lib.mxtpu_loader_create(
            path.encode(), part_index, num_parts, int(shuffle), seed,
            queue_size, shuffle_chunk)
        if not self._h:
            raise IOError("cannot open %s" % path)
        self._pending = collections.deque()

    def __iter__(self):
        return self

    def __next__(self):
        rec = self.next_record()
        if rec is None:
            raise StopIteration
        return rec

    def next_record(self):
        """Next record (batched under the hood: one ctypes crossing pulls
        up to _BATCH queued records)."""
        if self._pending:
            return self._pending.popleft()
        outs = (ctypes.POINTER(ctypes.c_char) * self._BATCH)()
        lens = (ctypes.c_size_t * self._BATCH)()
        r = self._lib.mxtpu_loader_next_batch(self._h, self._BATCH, outs,
                                              lens)
        if r > 0:
            for i in range(r):
                self._pending.append(
                    buf_to_bytes(self._lib, outs[i], lens[i]))
            return self._pending.popleft()
        if r == 0:
            return None
        raise IOError("record stream corrupt")

    def reset(self):
        self._pending.clear()
        self._lib.mxtpu_loader_reset(self._h)

    def close(self):
        if getattr(self, "_h", None):
            self._lib.mxtpu_loader_free(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class DecodeLoader(object):
    """Parallel JPEG decode + augment pipeline (native
    ``mxtpu_decode_loader_*``; the reference's OMP decode inside
    ``iter_image_recordio_2.cc:104-112,296``).  Worker threads decode
    libjpeg (DCT-scaled), resize, crop and mirror OFF the GIL; Python
    receives finished uint8 HWC batches with one memcpy."""

    def __init__(self, path, out_h, out_w, part_index=0, num_parts=1,
                 shuffle=False, seed=0, queue_size=256, shuffle_chunk=1024,
                 n_workers=None, resize_shorter=0, rand_crop=False,
                 rand_mirror=False):
        self._lib = lib()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        if n_workers is None:
            n_workers = max(1, (os.cpu_count() or 1) - 1)
        self._h = self._lib.mxtpu_decode_loader_create(
            path.encode(), part_index, num_parts, int(shuffle), seed,
            queue_size, shuffle_chunk, n_workers, out_h, out_w,
            resize_shorter, int(rand_crop), int(rand_mirror))
        if not self._h:
            raise IOError("cannot open %s" % path)
        self._hw = (out_h, out_w)

    def next_batch(self, max_n):
        """(data uint8 (n, H, W, 3), labels float32 (n,)) or None at
        epoch end."""
        import numpy as np

        h, w = self._hw
        data = np.empty((max_n, h, w, 3), dtype=np.uint8)
        labels = np.empty((max_n,), dtype=np.float32)
        n = self._lib.mxtpu_decode_loader_next_batch(
            self._h, max_n,
            data.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
        if n <= 0:
            return None
        return data[:n], labels[:n]

    def skipped(self):
        return int(self._lib.mxtpu_decode_loader_skipped(self._h))

    def reset(self):
        self._lib.mxtpu_decode_loader_reset(self._h)

    def close(self):
        if getattr(self, "_h", None):
            self._lib.mxtpu_decode_loader_free(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
