"""DataSet abstractions.

Rebuild of «bigdl»/dataset/DataSet.scala: ``LocalDataSet`` (host
iterators) and ``DistributedDataSet`` (reference: an RDD per executor;
here: a marker that batches should be sharded over the mesh data axis by
the optimizer's ``_put_batch``).
"""

from __future__ import annotations

from typing import Iterable, Iterator, Optional, Sequence

import numpy as np

from bigdl_tpu.common import RandomGenerator
from bigdl_tpu.dataset.sample import MiniBatch, Sample, samples_to_minibatch


class DataSet:
    """Iterable of (input, target) numpy batches."""

    def data(self, train: bool = True) -> Iterator:
        raise NotImplementedError

    def size(self) -> int:
        raise NotImplementedError

    # reference: DataSet.transform / ``->`` chaining
    def transform(self, transformer):
        return _TransformedDataSet(self, transformer)

    def __rshift__(self, transformer):
        return self.transform(transformer)


class _TransformedDataSet(DataSet):
    def __init__(self, base: DataSet, transformer):
        self.base = base
        self.transformer = transformer

    def data(self, train: bool = True):
        return self.transformer(self.base.data(train))

    def size(self):
        return self.base.size()


class LocalDataSet(DataSet):
    pass


class ArrayDataSet(LocalDataSet):
    """In-memory (features, labels) arrays batched to (input, target).

    Shuffles per epoch with the global RNG in train mode; drops the
    ragged tail batch in train mode (keeps it for eval) so the jitted
    step never retraces on a new batch shape — the TPU analogue of the
    reference's fixed-size MiniBatch packing.

    ``data()`` yields arrays that are the caller's to keep
    (``list(ds.data())`` holds distinct batches).  ``data_into(gather)``
    is for the trainer's loop, which has its batches gathered into a
    ring of reused host buffers and says itself when one may be
    overwritten (``native.StagingRing``).
    """

    def __init__(self, features, labels, batch_size: int = 32,
                 shuffle: bool = True):
        if isinstance(features, (list, tuple)):
            self.features = [np.asarray(f) for f in features]
            self._multi = True
            n = self.features[0].shape[0]
        else:
            self.features = np.asarray(features)
            self._multi = False
            n = self.features.shape[0]
        self.labels = np.asarray(labels)
        self.batch_size = batch_size
        self.shuffle = shuffle
        self._n = n

    def size(self):
        return self._n

    def data(self, train: bool = True):
        # single float32 feature arrays assemble through the native
        # row gather (bigdl_tpu/native — the BigDL-core replacement for
        # the host data plane); every batch is an array of the
        # caller's own
        from bigdl_tpu import native as _native

        return self._batches(train, _native.gather_rows)

    def data_into(self, gather, train: bool = True):
        """The batches of :meth:`data`, each float32 feature batch
        produced by ``gather(features, rows)`` into memory of the
        caller's choosing (the trainer's feed passes
        ``native.StagingRing.gather``).  Such a batch belongs to the
        caller: what ``gather`` returns is yielded as it is, and may be
        overwritten as soon as the caller's own rule says so, so this is
        for a loop that knows when it has finished with a batch.
        Features that never go through the row gather (several arrays,
        another dtype), all labels, and every batch of a subclass that
        makes its own in ``data`` are arrays of their own, as from
        :meth:`data`."""
        if type(self).data is not ArrayDataSet.data:
            return self.data(train)
        return self._batches(train, gather)

    def _batches(self, train, gather):
        idx = np.arange(self._n)
        if train and self.shuffle:
            idx = RandomGenerator.RNG.randperm(self._n)
        bs = self.batch_size
        n_full = self._n // bs
        if self._multi or self.features.dtype != np.float32:
            gather = None
        for b in range(n_full):
            sel = idx[b * bs : (b + 1) * bs]
            if self._multi:
                inp = tuple(f[sel] for f in self.features)
            elif gather is not None:
                inp = gather(self.features, sel)
            else:
                inp = self.features[sel]
            yield inp, self.labels[sel]
        rem = self._n - n_full * bs
        if rem and not train:
            sel = idx[n_full * bs :]
            if self._multi:
                inp = tuple(f[sel] for f in self.features)
            else:
                inp = self.features[sel]
            yield inp, self.labels[sel]


class SampleDataSet(LocalDataSet):
    """Dataset over Sample records with pad-at-batch semantics
    (reference: DataSet.array(samples) -> SampleToMiniBatch)."""

    def __init__(self, samples: Sequence[Sample], batch_size: int = 32,
                 padding_value: float = 0.0, fixed_length: Optional[int] = None,
                 shuffle: bool = True):
        self.samples = list(samples)
        self.batch_size = batch_size
        self.padding_value = padding_value
        self.fixed_length = fixed_length
        self.shuffle = shuffle

    def size(self):
        return len(self.samples)

    def data(self, train: bool = True):
        order = np.arange(len(self.samples))
        if train and self.shuffle:
            order = RandomGenerator.RNG.randperm(len(self.samples))
        bs = self.batch_size
        n_full = len(self.samples) // bs
        for b in range(n_full):
            batch = [self.samples[i] for i in order[b * bs : (b + 1) * bs]]
            mb = samples_to_minibatch(batch, self.padding_value, self.fixed_length)
            yield mb.input, mb.target
        rem = len(self.samples) - n_full * bs
        if rem and not train:
            batch = [self.samples[i] for i in order[n_full * bs :]]
            mb = samples_to_minibatch(batch, self.padding_value, self.fixed_length)
            yield mb.input, mb.target


def iter_process_batches(n: int, batch_size: int, pid: int, nproc: int,
                         shuffle: bool, pad_tail: bool = False):
    """The per-process batch-slicing contract shared by every
    distributed dataset: derive the SAME global epoch permutation on
    every process (seeded global RNG), then yield this process's
    contiguous ``batch_size // nproc`` index slice of each full global
    batch.  DistriOptimizer assembles the global device array from
    these shards via ``make_array_from_process_local_data``.

    ``pad_tail``: also yield the final partial global batch, its index
    list repeat-padded to the process multiple (the reference's
    SampleToMiniBatch padding — the repeated sample is counted, exactly
    as the reference counts its pad copies).  Every process yields the
    same tail length, so the trainer's local divisor padding stays
    consistent across hosts.  Off (historical drop-the-tail) for eval
    iteration, where repeated rows would distort metric counts."""
    if batch_size % nproc:
        raise ValueError(
            f"global batch {batch_size} not divisible by {nproc} processes"
        )
    local = batch_size // nproc
    idx = RandomGenerator.RNG.randperm(n) if shuffle else np.arange(n)
    for b in range(n // batch_size):
        globl = idx[b * batch_size: (b + 1) * batch_size]
        yield globl[pid * local: (pid + 1) * local]
    rem = n % batch_size
    if pad_tail and rem:
        tail = idx[n - rem:]
        pad_to = -(-rem // nproc) * nproc
        if pad_to != rem:
            tail = np.concatenate(
                [tail, np.repeat(tail[-1:], pad_to - rem)])
        local_t = pad_to // nproc
        yield tail[pid * local_t: (pid + 1) * local_t]


class DistributedDataSet(ArrayDataSet):
    """Per-process distributed dataset (reference: DistributedDataSet
    wraps an RDD coalesced to nodeNumber — SURVEY.md §3.2 job 0).

    The iterator contract (VERDICT r1 item 4): every process derives the
    SAME global epoch permutation from the shared seeded RNG, then each
    yields only its own contiguous slice of every global batch —
    ``local = global_batch // num_processes`` rows.  DistriOptimizer
    assembles the global device array from these per-process shards via
    ``jax.make_array_from_process_local_data``, so no host ever holds or
    ships the full batch (the reference's executors likewise feed their
    cached partition only).

    Defaults read ``jax.process_index()/process_count()`` at iteration
    time; pass ``process_id``/``num_processes`` to override (tests).
    """

    per_process = True

    def __init__(self, features, labels, batch_size: int = 32,
                 shuffle: bool = True, process_id: Optional[int] = None,
                 num_processes: Optional[int] = None):
        super().__init__(features, labels, batch_size, shuffle)
        self._pid = process_id
        self._nproc = num_processes

    def _world(self):
        if self._pid is not None and self._nproc is not None:
            return self._pid, self._nproc
        import jax

        return jax.process_index(), jax.process_count()

    def data(self, train: bool = True):
        pid, nproc = self._world()
        for mine in iter_process_batches(
            self._n, self.batch_size, pid, nproc,
            shuffle=train and self.shuffle, pad_tail=train,
        ):
            if self._multi:
                feats = tuple(f[mine] for f in self.features)
            else:
                feats = self.features[mine]
            yield feats, self.labels[mine]


class PartitionStreamDataSet(DataSet):
    """Streams batches from a partitioned row source WITHOUT collecting
    the dataset to the driver (VERDICT r1 item 4 — the DLEstimator path's
    mapPartitions-style feeding; reference: ⟦DLEstimator.scala⟧ feeds the
    Optimizer straight from the DataFrame's RDD).

    ``source`` must expose ``num_partitions()`` and ``iter_partition(i)``
    yielding ``(feature_row, label_row)`` pairs — satisfied by the spark
    adapter in dlframes (which rides ``rdd.toLocalIterator``-style
    partition streaming) and by the fake-RDD test shim.  In a multi-host
    world each process consumes partitions ``i % num_processes ==
    process_id`` — the per-process iterator contract.
    """

    def __init__(self, source, batch_size: int = 32,
                 feature_size: Optional[Sequence[int]] = None,
                 label_size: Optional[Sequence[int]] = None,
                 process_id: int = 0, num_processes: int = 1,
                 size_hint: Optional[int] = None):
        self.source = source
        self.batch_size = batch_size
        self.feature_size = list(feature_size) if feature_size else None
        self.label_size = list(label_size) if label_size else None
        self._pid = process_id
        self._nproc = num_processes
        self._size_hint = size_hint

    def size(self):
        return self._size_hint or 0

    def _shape(self, arr, size):
        arr = np.asarray(arr, np.float32)
        if size is not None:
            arr = arr.reshape([arr.shape[0]] + size)
            if size == [1]:
                arr = arr.reshape(-1)
        return arr

    def data(self, train: bool = True):
        bs = self.batch_size
        feat_buf: list = []
        lbl_buf: list = []
        n_parts = self.source.num_partitions()
        for p in range(n_parts):
            if p % self._nproc != self._pid:
                continue
            for feat, lbl in self.source.iter_partition(p):
                feat_buf.append(np.asarray(feat, np.float32))
                lbl_buf.append(np.asarray(lbl, np.float32))
                if len(feat_buf) == bs:
                    yield (
                        self._shape(np.stack(feat_buf), self.feature_size),
                        self._shape(np.stack(lbl_buf), self.label_size),
                    )
                    feat_buf, lbl_buf = [], []
        # ragged tail: dropped in train mode (jit shape stability — same
        # policy as ArrayDataSet), kept for eval
        if feat_buf and not train:
            yield (
                self._shape(np.stack(feat_buf), self.feature_size),
                self._shape(np.stack(lbl_buf), self.label_size),
            )


def to_dataset(data, batch_size: int = 32) -> Optional[DataSet]:
    """Coerce user input to a DataSet (reference: Optimizer accepts
    RDD[Sample] or DataSet)."""
    if data is None:
        return None
    if isinstance(data, DataSet):
        return data
    if isinstance(data, tuple) and len(data) == 2:
        return ArrayDataSet(data[0], data[1], batch_size)
    if isinstance(data, (list,)) and data and isinstance(data[0], Sample):
        return SampleDataSet(data, batch_size)
    raise TypeError(f"cannot build a DataSet from {type(data)}")
