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

**Who owns a training batch.**  ``gather_rows`` returns an array of the
caller's own unless the caller passes ``out=``.  The trainer's feed
passes one: ``optimize()`` holds a :class:`StagingRing`, whose few
host buffers every batch of the call is gathered into by the standing
threads of a :class:`GatherPool`, and ``ArrayDataSet.data_into`` is how
the dataset produces its batches there.  A staged batch belongs to the
ring and is lent to the loop: the loop gives it back
(:meth:`StagingRing.release`) once the step that trained on it has had
its loss read, the one event after which neither the copy to the chips
nor the step can still read the host array (``jax.device_put`` returns
when the copy is enqueued, and on the CPU backend the device array may
BE the host array).  Until then the worker never writes there: with no
free buffer it allocates a new one and counts it.  ``ArrayDataSet.
data()`` and everything else that calls ``gather_rows`` without
``out=`` keeps getting arrays nobody else will touch.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
import time
import weakref
from concurrent.futures import ThreadPoolExecutor
from typing import List, Optional, Tuple

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


def usable_cores() -> int:
    """The cores this process may run on (a container's share, not the
    machine's count)."""
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:  # no affinity call on this platform
        return os.cpu_count() or 1


# a thread is worth waking for this much of a batch: under it the copy
# runs on the caller's thread (a batch of a few small rows)
_MIN_BYTES_PER_THREAD = 1 << 20


class GatherPool:
    """Standing threads that share one batch's row gather.

    Each thread copies a contiguous range of the batch's rows: one call
    of the library's ``gather_rows`` (ctypes releases the GIL for it),
    or one ``np.take`` where the library did not load.  The threads
    start with the first copy that is worth splitting and end with
    :meth:`close`; ``n_threads`` 0 sizes the pool from the cores the
    process may use.  On the chip's host this buys what a pool in the
    library would (PERF.md section 6, PR 27) and serves the fallback
    too."""

    def __init__(self, n_threads: int = 0):
        self.size = int(n_threads) if n_threads > 0 else usable_cores()
        self._lock = threading.Lock()
        self._executor: Optional[ThreadPoolExecutor] = None
        self._closed = False

    def ranges(self, n_rows: int, row_bytes: int) -> List[Tuple[int, int]]:
        """The row ranges a batch is split into, one a thread."""
        parts = min(self.size, n_rows,
                    n_rows * row_bytes // _MIN_BYTES_PER_THREAD)
        if parts <= 1:
            return [(0, n_rows)]
        chunk = -(-n_rows // parts)
        return [(lo, min(n_rows, lo + chunk))
                for lo in range(0, n_rows, chunk)]

    def gather_rows(self, src: np.ndarray, idx: np.ndarray,
                    out: Optional[np.ndarray] = None) -> np.ndarray:
        """``out[i] = src[idx[i]]`` for float32 ``src``, as ``src[idx]``
        gives it bit for bit; into ``out`` when given (nothing is
        allocated then), else into a new array."""
        s = np.ascontiguousarray(src, np.float32)
        flat = s.reshape(s.shape[0], -1)
        ix = np.ascontiguousarray(idx, np.int64)
        n, rows = len(ix), flat.shape[0]
        if n:
            # the library copies from wherever an index points
            if ix.min() < -rows or ix.max() >= rows:
                raise IndexError(
                    f"row index out of range for {rows} rows")
            if ix.min() < 0:
                ix = np.where(ix < 0, ix + rows, ix)
        shape = (n,) + s.shape[1:]
        if out is None:
            out = np.empty(shape, np.float32)
        elif (not isinstance(out, np.ndarray) or out.shape != shape
              or out.dtype != np.float32 or not out.flags.c_contiguous
              or not out.flags.writeable):
            raise ValueError(
                f"out must be a writeable C-contiguous float32 array of "
                f"shape {shape}")
        dst = out.reshape(n, -1)
        lib = _load()
        if lib is not None:
            def copy(lo, hi):
                lib.gather_rows(flat, ix[lo:hi], dst[lo:hi], hi - lo,
                                flat.shape[1])
        else:
            def copy(lo, hi):
                # the indices are checked above; any other mode makes
                # numpy gather into a buffer of its own and copy back
                np.take(flat, ix[lo:hi], axis=0, out=dst[lo:hi],
                        mode="clip")
        ranges = self.ranges(n, flat.shape[1] * 4)
        futures = None
        with self._lock:
            if len(ranges) > 1 and not self._closed:
                if self._executor is None:
                    self._executor = ThreadPoolExecutor(
                        self.size, thread_name_prefix="bigdl-gather")
                futures = [self._executor.submit(copy, lo, hi)
                           for lo, hi in ranges]
        if futures is None:
            copy(0, n)
        else:
            for f in futures:
                f.result()
        return out

    def close(self):
        """End the threads (copies under way finish first)."""
        with self._lock:
            self._closed = True
            executor, self._executor = self._executor, None
        if executor is not None:
            executor.shutdown(wait=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def gather_rows(src: np.ndarray, idx: np.ndarray, n_threads: int = 0,
                out: Optional[np.ndarray] = None) -> np.ndarray:
    """dst[i] = src[idx[i]] for 2-D-viewable float32 src (one memcpy per
    row, row ranges in parallel on a :class:`GatherPool` of this one
    call's; ``n_threads`` 0 is the pool's own size).  With ``out=`` the
    rows land there and nothing is allocated."""
    with GatherPool(n_threads) as pool:
        return pool.gather_rows(src, idx, out=out)


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


# batches the prefetch thread may hold ready for the loop
_PREFETCH_DEPTH = 2
# and those the loop itself holds at once: the one the worker is
# producing, the one staged on the chips for the next step, the one
# whose step is dispatched, the one whose loss is still to be read
_HELD_BY_LOOP = 4


def _aligned_empty(shape) -> np.ndarray:
    """A float32 array on a 64-byte boundary (numpy's own lie on 16): a
    device can then read the batch where it lies.  XLA's CPU client does
    just that and makes no copy, so on that backend the step's input IS
    this memory, and the tests of the ring's rule mean something."""
    nbytes = int(np.prod(shape)) * 4
    raw = np.empty(nbytes + 64, np.uint8)
    start = -raw.ctypes.data % 64
    return raw[start:start + nbytes].view(np.float32).reshape(shape)


class StagingRing:
    """The trainer's reused host batches: a few buffers that the
    batches of one ``optimize()`` call are gathered into, and the
    :class:`GatherPool` that fills them.

    ``gather`` (the prefetch thread) takes a buffer nobody can still
    read, or makes one: at first need, at the batch's shape, again if
    the shape changes; it never waits for one and never takes one that
    is not back.  ``release`` (the loop) gives a batch back once the
    step that trained on it has had its loss read, or when it was
    dropped before any step saw it; arrays the ring did not lend are
    ignored, so the loop may hand it whatever the dataset produced.
    The ring keeps at most as many as can be in flight: the prefetch
    queue's depth and the loop's own four."""

    def __init__(self, prefetch_depth: int = _PREFETCH_DEPTH):
        from bigdl_tpu import obs
        from bigdl_tpu.obs import names

        self.depth = int(prefetch_depth) + _HELD_BY_LOOP
        self.pool = GatherPool()
        self._lock = threading.Lock()
        self._free: List[np.ndarray] = []
        # id -> buffer, for as long as anyone holds it
        self._lent: "weakref.WeakValueDictionary" = \
            weakref.WeakValueDictionary()
        #: ``bytes`` / ``threads`` / ``staging`` of the newest gather,
        #: for the worker's ``feed.gather`` span
        self.last: Optional[dict] = None
        batches = obs.get_registry().counter(
            names.FEED_STAGING_BATCHES_TOTAL,
            "Training batches gathered into a reused or a new host buffer",
            labels=("staging",))
        self._count = {k: batches.labels(staging=k)
                       for k in ("reused", "new")}

    def gather(self, src: np.ndarray, idx: np.ndarray) -> np.ndarray:
        shape = (len(idx),) + tuple(np.shape(src)[1:])
        buf, staging = None, "reused"
        with self._lock:
            while self._free and buf is None:
                buf = self._free.pop()
                if buf.shape != shape:
                    buf = None  # another batch shape's: let it go
        if buf is None:
            buf, staging = _aligned_empty(shape), "new"
        self.pool.gather_rows(src, idx, out=buf)
        with self._lock:
            self._lent[id(buf)] = buf
        self._count[staging].inc()
        self.last = {
            "bytes": buf.nbytes, "staging": staging,
            "threads": len(self.pool.ranges(
                shape[0], buf.nbytes // max(1, shape[0])))}
        return buf

    def release(self, batch):
        if not isinstance(batch, np.ndarray):
            return
        with self._lock:
            if self._lent.pop(id(batch), None) is batch \
                    and len(self._free) < self.depth:
                self._free.append(batch)

    def close(self):
        """End the gather threads and let the buffers go."""
        self.pool.close()
        with self._lock:
            self._free.clear()


class PrefetchIterator:
    """Wraps a batch-producing iterable; a daemon thread assembles the
    next batch while the chip consumes the current one (the reference's
    Engine.default prefetch role on the data path).  With the obs tracer
    on, the worker records its busy time a batch as ``feed.gather``
    (``step`` counts up from ``first_step``; ``bytes``, ``threads`` and
    ``staging`` are what ``staging``, the :class:`StagingRing` the
    iterable gathers through, says of the batch)."""

    def __init__(self, iterable, depth: int = _PREFETCH_DEPTH,
                 first_step: int = 0,
                 staging: Optional[StagingRing] = None):
        import queue

        self._iterable = iterable
        self._first_step = int(first_step)
        self._staging = staging
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
                ring = self._staging
                for k, item in enumerate(self._iterable):
                    how = {}
                    if ring is not None and ring.last is not None:
                        how, ring.last = ring.last, None
                    # retroactive: the try that ends the epoch is no batch
                    tracer.complete("feed.gather", t0,
                                    time.perf_counter() - t0,
                                    step=self._first_step + k, **how)
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
