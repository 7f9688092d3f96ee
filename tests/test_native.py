"""Native runtime tests — fp16 codec, gather/normalize, image ops,
prefetcher; each native path is diffed against its numpy reference
(reference analogue: BigDL-core is tested through the JVM wrappers)."""

import numpy as np
import pytest

from bigdl_tpu import native


def test_native_library_builds_and_loads():
    # the image ships g++, so the native path must actually be live here
    assert native.available()


def test_fp16_roundtrip_matches_numpy_half():
    rs = np.random.RandomState(0)
    x = np.concatenate([
        rs.randn(1000).astype(np.float32) * 10,
        np.asarray([0.0, -0.0, 1e-8, -1e-8, 65504.0, -65504.0, 1e9, -1e9,
                    np.inf, -np.inf], np.float32),
    ])
    comp = native.fp16_compress(x)
    assert comp.dtype == np.uint16
    with np.errstate(over="ignore"):
        half = x.astype(np.float16)
    # bit-exact against IEEE round-to-nearest-even (numpy half)
    np.testing.assert_array_equal(comp, half.view(np.uint16))
    dec = native.fp16_decompress(comp)
    np.testing.assert_array_equal(dec, half.astype(np.float32))


def test_fp16_nan():
    comp = native.fp16_compress(np.asarray([np.nan], np.float32))
    assert np.isnan(native.fp16_decompress(comp)[0])


def test_gather_rows():
    rs = np.random.RandomState(1)
    src = rs.randn(50, 3, 4).astype(np.float32)
    idx = rs.permutation(50)[:20]
    out = native.gather_rows(src, idx)
    np.testing.assert_array_equal(out, src[idx])


def test_gather_normalize_u8():
    rs = np.random.RandomState(2)
    src = rs.randint(0, 256, (30, 3, 8, 8), dtype=np.uint8)
    idx = rs.permutation(30)[:10]
    mean = np.asarray([125.0, 122.0, 114.0], np.float32)
    std = np.asarray([63.0, 62.0, 66.0], np.float32)
    out = native.gather_normalize_u8(src, idx, mean, std)
    expect = (src[idx].astype(np.float32)
              - mean[None, :, None, None]) / std[None, :, None, None]
    np.testing.assert_allclose(out, expect, rtol=1e-6)


def test_resize_bilinear_identity_and_scale():
    rs = np.random.RandomState(3)
    img = rs.rand(3, 8, 8).astype(np.float32)
    same = native.resize_bilinear(img, 8, 8)
    np.testing.assert_allclose(same, img, atol=1e-6)
    up = native.resize_bilinear(img, 16, 16)
    assert up.shape == (3, 16, 16)
    # bilinear preserves the mean approximately
    assert abs(up.mean() - img.mean()) < 0.02


def test_crop_and_hflip():
    rs = np.random.RandomState(4)
    img = rs.rand(2, 10, 12).astype(np.float32)
    c = native.crop(img, 2, 3, 5, 6)
    np.testing.assert_array_equal(c, img[:, 2:7, 3:9])
    f = native.hflip(img)
    np.testing.assert_array_equal(f, img[:, :, ::-1])


def test_normalize():
    rs = np.random.RandomState(5)
    img = rs.rand(3, 6, 6).astype(np.float32)
    out = native.normalize(img, [0.5, 0.4, 0.3], [0.2, 0.2, 0.25])
    expect = (img - np.asarray([0.5, 0.4, 0.3], np.float32)[:, None, None]) \
        / np.asarray([0.2, 0.2, 0.25], np.float32)[:, None, None]
    np.testing.assert_allclose(out, expect, rtol=1e-5)


def test_prefetch_iterator_order_and_errors():
    items = list(range(20))
    out = list(native.PrefetchIterator(iter(items)))
    assert out == items

    def boom():
        yield 1
        raise ValueError("producer failed")

    it = native.PrefetchIterator(boom())
    got = []
    with pytest.raises(ValueError):
        for x in it:
            got.append(x)
    assert got == [1]


def test_prefetch_iterator_early_break_releases_producer():
    import threading
    import time

    produced = []

    def gen():
        for i in range(100):
            produced.append(i)
            yield i

    before = threading.active_count()
    it = native.PrefetchIterator(gen(), depth=2)
    for x in it:
        if x == 3:
            break
    # producer must wind down instead of blocking forever on the queue
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before
    assert len(produced) < 100


# ---- gather_rows(out=), the standing pool, the staging ring (ISSUE 27)
def _rows(order, n_src=96, n=64, row=(4, 4096)):
    """A batch big enough to be split over threads (64 rows of 64 KiB)."""
    rs = np.random.RandomState(7)
    src = rs.randn(n_src, *row).astype(np.float32)
    idx = {"in_order": np.arange(16, 16 + n),
           "permuted": rs.permutation(n_src)[:n],
           "repeated": rs.randint(0, 5, n)}[order]
    return src, idx


@pytest.fixture(params=["library", "fallback"])
def either_path(request, monkeypatch):
    """The native library, then numpy in its place."""
    if request.param == "fallback":
        monkeypatch.setattr(native, "_load", lambda: None)
        assert not native.available()
    return request.param


@pytest.mark.parametrize("n_threads", [1, 0])
@pytest.mark.parametrize("order", ["in_order", "permuted", "repeated"])
def test_gather_rows_into_out_equals_numpy_bit_for_bit(
        order, n_threads, either_path):
    src, idx = _rows(order)
    out = np.full((len(idx),) + src.shape[1:], np.nan, np.float32)
    got = native.gather_rows(src, idx, n_threads=n_threads, out=out)
    assert got is out
    want = src[idx]
    assert out.tobytes() == want.tobytes()
    # and without out= the array is new and the caller's own
    fresh = native.gather_rows(src, idx, n_threads=n_threads)
    assert fresh.tobytes() == want.tobytes()
    assert not np.shares_memory(fresh, src)


def test_gather_rows_splits_a_large_batch_and_not_a_small_one(either_path):
    pool = native.GatherPool(4)
    try:
        assert len(pool.ranges(64, 64 << 10)) == 4
        assert pool.ranges(8, 64) == [(0, 8)]
        # a few small rows: the caller's thread, no thread started
        src, idx = _rows("permuted", row=(4,))
        np.testing.assert_array_equal(pool.gather_rows(src, idx), src[idx])
        assert pool._executor is None
        src, idx = _rows("permuted")
        np.testing.assert_array_equal(pool.gather_rows(src, idx), src[idx])
        assert pool._executor is not None
    finally:
        pool.close()


@pytest.mark.parametrize("bad", [
    np.empty((63, 4, 4096), np.float32),               # rows
    np.empty((64, 4096, 4), np.float32),               # row shape
    np.empty((64, 4, 4096), np.float64),               # dtype
    np.empty((64, 4, 8192), np.float32)[:, :, ::2],    # not contiguous
], ids=["rows", "row_shape", "dtype", "strided"])
def test_gather_rows_refuses_a_wrong_out(bad, either_path):
    src, idx = _rows("permuted")
    with pytest.raises(ValueError):
        native.gather_rows(src, idx, out=bad)


def test_gather_rows_checks_its_indices(either_path):
    src, _ = _rows("in_order")
    for bad in ([0, 96], [-97, 0]):
        with pytest.raises(IndexError):
            native.gather_rows(src, np.asarray(bad))
    # negative indices count from the end, as numpy's do
    idx = np.asarray([-1, 0, -96, 5])
    np.testing.assert_array_equal(native.gather_rows(src, idx), src[idx])


def _gather_threads():
    import threading

    return [t for t in threading.enumerate()
            if t.name.startswith("bigdl-gather")]


def test_pool_threads_stand_between_calls_and_end_with_close():
    src, idx = _rows("permuted")
    with native.GatherPool(3) as pool:
        pool.gather_rows(src, idx)
        first = _gather_threads()
        assert 1 <= len(first) <= 3
        for _ in range(4):
            pool.gather_rows(src, idx)
        # the same threads serve every call: at most three ever start
        assert set(first) <= set(_gather_threads())
        assert len(_gather_threads()) <= 3
    assert _gather_threads() == []
    # a closed pool still copies, on the caller's thread
    np.testing.assert_array_equal(pool.gather_rows(src, idx), src[idx])
    assert _gather_threads() == []
    # the module-level call leaves nothing behind either
    native.gather_rows(src, idx)
    assert _gather_threads() == []


def test_ring_threads_end_with_the_feed_after_an_early_break():
    import contextlib
    import threading
    import time

    from bigdl_tpu.dataset.dataset import ArrayDataSet

    src, _ = _rows("in_order")
    ds = ArrayDataSet(src, np.arange(len(src), dtype=np.float32),
                      batch_size=32, shuffle=True)
    before = threading.active_count()
    with contextlib.closing(native.StagingRing()) as ring:
        it = native.PrefetchIterator(ds.data_into(ring.gather), staging=ring)
        for inp, _tgt in it:
            assert _gather_threads()
            break
    deadline = time.time() + 5.0
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert _gather_threads() == []
    assert threading.active_count() <= before


def test_ring_reuses_only_what_was_released():
    src, _ = _rows("in_order")
    ring = native.StagingRing(prefetch_depth=2)
    try:
        assert ring.depth == 6
        idx = np.arange(32)
        a = ring.gather(src, idx)
        assert a.ctypes.data % 64 == 0
        assert ring.last == {"bytes": a.nbytes, "staging": "new",
                             "threads": len(ring.pool.ranges(
                                 32, a.nbytes // 32))}
        b = ring.gather(src, idx + 32)
        assert b is not a and not np.shares_memory(a, b)  # a is not back
        ring.release(a)
        c = ring.gather(src, idx + 64)
        assert c is a and ring.last["staging"] == "reused"
        np.testing.assert_array_equal(c, src[64:96])
        # not the ring's: ignored; released twice: taken back once
        ring.release(np.zeros_like(a))
        ring.release((a, b))
        ring.release(b)
        ring.release(b)
        assert len(ring._free) == 1
        # another batch shape: the old buffer goes, a new one is made
        d = ring.gather(src, np.arange(16))
        assert d.shape[0] == 16 and ring.last["staging"] == "new"
        assert ring._free == []
        # never more kept than can be in flight
        many = [ring.gather(src, idx) for _ in range(ring.depth + 3)]
        for m in many:
            ring.release(m)
        assert len(ring._free) == ring.depth
    finally:
        ring.close()


def test_data_yields_arrays_of_the_callers_own():
    from bigdl_tpu.dataset.dataset import ArrayDataSet

    src, _ = _rows("in_order")
    ds = ArrayDataSet(src, np.arange(len(src), dtype=np.float32),
                      batch_size=32, shuffle=False)
    batches = list(ds.data(train=True))
    assert len(batches) == 3
    for k, (inp, tgt) in enumerate(batches):
        np.testing.assert_array_equal(inp, src[32 * k:32 * (k + 1)])
        for other, _ in batches[:k]:
            assert not np.shares_memory(inp, other)
        assert not np.shares_memory(inp, src)


def test_a_subclass_with_batches_of_its_own_keeps_them_under_data_into():
    from bigdl_tpu.dataset.dataset import ArrayDataSet, DistributedDataSet

    class Halves(ArrayDataSet):
        def data(self, train=True):
            yield self.features[:2], self.labels[:2]

    src, _ = _rows("in_order", n_src=8, row=(4,))
    labels = np.arange(8, dtype=np.float32)

    def refuse(features, rows):
        raise AssertionError("not this dataset's way to make a batch")

    (inp, _tgt), = Halves(src, labels, 4).data_into(refuse)
    np.testing.assert_array_equal(inp, src[:2])
    per_process = DistributedDataSet(src, labels, 4, shuffle=False,
                                     process_id=0, num_processes=2)
    assert [i.shape for i, _ in per_process.data_into(refuse)] \
        == [(2, 4), (2, 4)]
