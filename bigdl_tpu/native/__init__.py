"""Native host-side runtime — ctypes bindings + numpy fallbacks.

The reference keeps its data plane native (BigDL-core: MKL/MKL-DNN/
bigquant/OpenCV shipped as ``.so`` inside jars — SURVEY.md §2.3).  The
TPU rebuild's chip compute is XLA, but the host feeding path stays
native: ``native/bigdl_tpu_native.cpp`` provides the fp16 wire codec,
one-pass minibatch gather/normalize, and the OpenCV-replacement image
ops.  This wrapper builds the library on first use (``make`` in
``native/``, again whenever the source is newer than the binary) and
falls back to numpy implementations, with a warning, when no compiler
is available, so the framework never hard-requires the binary.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
import time
from typing import Optional

import numpy as np

log = logging.getLogger("bigdl_tpu.native")

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    "native",
)
_SO_PATH = os.path.join(_NATIVE_DIR, "libbigdl_tpu_native.so")
_SRC_PATH = os.path.join(_NATIVE_DIR, "bigdl_tpu_native.cpp")

_lib = None
_lib_lock = threading.Lock()
_build_attempted = False

_i64 = ctypes.c_int64
_f32p = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
_u16p = np.ctypeslib.ndpointer(np.uint16, flags="C_CONTIGUOUS")
_u8p = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
_i64p = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")


def _try_build() -> bool:
    global _build_attempted
    if _build_attempted:
        return os.path.exists(_SO_PATH)
    _build_attempted = True
    from bigdl_tpu.config import config, refresh_from_env

    refresh_from_env()
    if config.no_native:
        return False
    try:
        subprocess.run(
            ["make", "-s"], cwd=_NATIVE_DIR, check=True,
            capture_output=True, timeout=120,
        )
        return os.path.exists(_SO_PATH)
    except (OSError, subprocess.SubprocessError) as e:
        # the trainer's feed path runs on this library: say so, and
        # never load a binary older than its source in its place
        log.warning("native build failed (%s); using numpy fallbacks", e)
        return False


def _stale() -> bool:
    """No binary yet, or the source is newer than it (the .so is not
    under version control, so a checkout can meet an old one)."""
    try:
        return os.path.getmtime(_SRC_PATH) > os.path.getmtime(_SO_PATH)
    except OSError:
        return not os.path.exists(_SO_PATH)


def _load() -> Optional[ctypes.CDLL]:
    global _lib
    if _lib is not None:
        return _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _stale() and not _try_build():
            return None
        try:
            lib = ctypes.CDLL(_SO_PATH)
        except OSError as e:
            log.warning("native load failed (%s); using numpy fallbacks", e)
            return None
        lib.fp16_compress.argtypes = [_f32p, _u16p, _i64]
        lib.fp16_decompress.argtypes = [_u16p, _f32p, _i64]
        lib.gather_rows.argtypes = [_f32p, _i64p, _f32p, _i64, _i64]
        lib.gather_rows_mt.argtypes = [_f32p, _i64p, _f32p, _i64, _i64,
                                       ctypes.c_int]
        lib.gather_normalize_u8.argtypes = [_u8p, _i64p, _f32p, _i64, _i64,
                                            _i64, _f32p, _f32p]
        lib.resize_bilinear_chw.argtypes = [_f32p, _f32p] + [_i64] * 5
        lib.crop_chw.argtypes = [_f32p, _f32p] + [_i64] * 7
        lib.hflip_chw.argtypes = [_f32p, _f32p] + [_i64] * 3
        lib.normalize_chw.argtypes = [_f32p, _i64, _i64, _f32p, _f32p]
        lib.native_abi_version.restype = ctypes.c_int
        if lib.native_abi_version() != 1:
            log.warning("native ABI mismatch; using numpy fallbacks")
            return None
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


# ==========================================================================
# fp16 codec («bigdl»/parameters/FP16CompressedTensor wire format)
# ==========================================================================


def fp16_compress(arr: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(arr, np.float32)
    lib = _load()
    if lib is None:
        return a.astype(np.float16).view(np.uint16).reshape(a.shape)
    out = np.empty(a.shape, np.uint16)
    lib.fp16_compress(a.reshape(-1), out.reshape(-1), a.size)
    return out


def fp16_decompress(arr: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(arr, np.uint16)
    lib = _load()
    if lib is None:
        return a.view(np.float16).astype(np.float32).reshape(a.shape)
    out = np.empty(a.shape, np.float32)
    lib.fp16_decompress(a.reshape(-1), out.reshape(-1), a.size)
    return out


# ==========================================================================
# minibatch assembly
# ==========================================================================


def gather_rows(src: np.ndarray, idx: np.ndarray,
                n_threads: int = 0) -> np.ndarray:
    """dst[i] = src[idx[i]] for 2-D-viewable float32 src (one memcpy per
    row, parallel across rows)."""
    s = np.ascontiguousarray(src, np.float32)
    flat = s.reshape(s.shape[0], -1)
    ix = np.ascontiguousarray(idx, np.int64)
    lib = _load()
    if lib is None:
        return flat[ix].reshape((len(ix),) + s.shape[1:])
    out = np.empty((len(ix), flat.shape[1]), np.float32)
    if n_threads <= 0:
        n_threads = min(4, os.cpu_count() or 1)
    lib.gather_rows_mt(flat, ix, out, len(ix), flat.shape[1], n_threads)
    return out.reshape((len(ix),) + s.shape[1:])


def gather_normalize_u8(src: np.ndarray, idx: np.ndarray,
                        mean: np.ndarray, std: np.ndarray) -> np.ndarray:
    """One-pass uint8 gather + per-channel (x-mean)/std, for (N, C, H, W)
    uint8 datasets — the MNIST/CIFAR feeding path."""
    s = np.ascontiguousarray(src, np.uint8)
    n, c = s.shape[0], s.shape[1]
    hw = int(np.prod(s.shape[2:]))
    ix = np.ascontiguousarray(idx, np.int64)
    m = np.ascontiguousarray(mean, np.float32).reshape(-1)
    sd = np.ascontiguousarray(std, np.float32).reshape(-1)
    if m.size == 1:
        m = np.repeat(m, c)
    if sd.size == 1:
        sd = np.repeat(sd, c)
    lib = _load()
    if lib is None:
        g = s[ix].astype(np.float32)
        return (g - m.reshape(1, c, *([1] * (s.ndim - 2)))) / \
            sd.reshape(1, c, *([1] * (s.ndim - 2)))
    out = np.empty((len(ix), c * hw), np.float32)
    lib.gather_normalize_u8(s.reshape(n, -1).reshape(-1), ix,
                            out.reshape(-1), len(ix), c, hw, m, sd)
    return out.reshape((len(ix),) + s.shape[1:])


# ==========================================================================
# image ops (OpenCV replacements; CHW float32)
# ==========================================================================


def resize_bilinear(img: np.ndarray, out_h: int, out_w: int) -> np.ndarray:
    a = np.ascontiguousarray(img, np.float32)
    c, h, w = a.shape
    lib = _load()
    if lib is None:
        import jax

        return np.asarray(jax.image.resize(a, (c, out_h, out_w), "bilinear"))
    out = np.empty((c, out_h, out_w), np.float32)
    lib.resize_bilinear_chw(a, out, c, h, w, out_h, out_w)
    return out


def crop(img: np.ndarray, y: int, x: int, out_h: int, out_w: int) -> np.ndarray:
    a = np.ascontiguousarray(img, np.float32)
    c, h, w = a.shape
    lib = _load()
    if lib is None:
        return a[:, y : y + out_h, x : x + out_w].copy()
    out = np.empty((c, out_h, out_w), np.float32)
    lib.crop_chw(a, out, c, h, w, y, x, out_h, out_w)
    return out


def hflip(img: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(img, np.float32)
    lib = _load()
    if lib is None:
        return a[:, :, ::-1].copy()
    out = np.empty_like(a)
    lib.hflip_chw(a, out, *a.shape)
    return out


def normalize(img: np.ndarray, mean, std) -> np.ndarray:
    a = np.ascontiguousarray(img, np.float32).copy()
    c = a.shape[0]
    hw = int(np.prod(a.shape[1:]))
    m = np.ascontiguousarray(np.broadcast_to(np.asarray(mean, np.float32),
                                             (c,)))
    sd = np.ascontiguousarray(np.broadcast_to(np.asarray(std, np.float32),
                                              (c,)))
    lib = _load()
    if lib is None:
        return (a - m.reshape(c, *([1] * (a.ndim - 1)))) / \
            sd.reshape(c, *([1] * (a.ndim - 1)))
    lib.normalize_chw(a.reshape(-1), c, hw, m, sd)
    return a


# ==========================================================================
# prefetching loader — double-buffered background minibatch assembly
# ==========================================================================


class PrefetchIterator:
    """Wraps a batch-producing iterable; a daemon thread assembles the
    next batch while the chip consumes the current one (the reference's
    Engine.default prefetch role on the data path).  With the obs tracer
    on, the worker records its busy time a batch as ``feed.gather``
    (``step`` counts up from ``first_step``)."""

    def __init__(self, iterable, depth: int = 2, first_step: int = 0):
        import queue

        self._iterable = iterable
        self._first_step = int(first_step)
        self._queue: "queue.Queue" = queue.Queue(maxsize=max(1, depth))
        self._done = object()
        self._thread: Optional[threading.Thread] = None
        self._err: Optional[BaseException] = None

    def _put(self, item, stop: threading.Event) -> bool:
        """Bounded put that gives up when the consumer has stopped — the
        producer must never block forever on an abandoned queue."""
        import queue

        while not stop.is_set():
            try:
                self._queue.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def __iter__(self):
        stop = threading.Event()

        def worker():
            from bigdl_tpu import obs

            tracer = obs.get_tracer()
            try:
                t0 = time.perf_counter()
                for k, item in enumerate(self._iterable):
                    # retroactive: the try that ends the epoch is no batch
                    tracer.complete("feed.gather", t0,
                                    time.perf_counter() - t0,
                                    step=self._first_step + k)
                    if not self._put(item, stop):
                        return  # consumer broke out early
                    t0 = time.perf_counter()
            except BaseException as e:  # noqa: BLE001 - forwarded to consumer
                self._err = e
            finally:
                self._put(self._done, stop)

        self._thread = threading.Thread(target=worker, daemon=True)
        self._thread.start()
        try:
            while True:
                item = self._queue.get()
                if item is self._done:
                    if self._err is not None:
                        raise self._err
                    return
                yield item
        finally:
            # consumer stopped (break / exception / GC): release the
            # producer so the thread and its pinned batches are freed
            stop.set()
