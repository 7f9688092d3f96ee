"""DistriOptimizer — THE distributed trainer.

Rebuild of «bigdl»/optim/DistriOptimizer.scala + «bigdl»/parameters/
AllReduceParameter.scala (SURVEY.md §3.2, §2.5).

Reference data plane, per iteration (one Spark job):

    putGradients:   local flat gradient split into numPartition FP16
                    blocks pushed to slice owners via BlockManager
    aggregate:      owner sums its incoming blocks, /= numSamples,
                    clipping processors, optimMethod on the owned slice
    sendWeight:     owner publishes its updated weight slice
    getWeights:     every worker prefetches all slices next iteration

That push-to-owner / pull-from-owner pattern **is literally
reduce-scatter + all-gather** over a flat parameter vector with the
optimizer state sharded by owner (ZeRO-1 before the name).  The
TPU-native rebuild says exactly that, inside one jitted ``shard_map``
over the ``data`` mesh axis:

    grads  = vjp(local sub-batch)            # per-chip compute
    gshard = psum_scatter(flat(grads))       # "putGradients+aggregate"
    gshard /= global_batch; clip             # ParameterProcessors
    wshard, ostate = optim.step(gshard, wshard, ostate)   # owner update
    weights = all_gather(wshard)             # "sendWeight+getWeights"

The Spark job-per-iteration barrier becomes the implicit synchrony of the
jitted step; FP16 wire compression maps to an optional bf16 cast before
the reduce-scatter (native on TPU ICI), or to the stronger
``wire_dtype="int8"`` blockwise-quantized exchange (int8 payload +
per-block f32 scales through one all_to_all pair, f32 accumulation —
EQuARX-style, half the bf16 bytes).  The same step compiles for a
multi-host DCN+ICI mesh — XLA picks the collective implementation.
"""

from __future__ import annotations

import logging
import math

import numpy as np

from bigdl_tpu.optim.optimizer import BaseOptimizer, LocalOptimizer
from bigdl_tpu.obs import names


def _jnp():
    import jax.numpy as jnp

    return jnp


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking disabled: the
    gathered weight vector is replicated by construction (all_gather),
    which the static vma checker cannot infer."""
    import jax

    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def int8_blockwise_reduce_scatter(g, axis, n, block):
    """Quantized reduce-scatter (inside shard_map): ``g`` is the local
    flat gradient, length divisible by ``n * block``.

    Round 5 shipped this as a quantize-once / all_to_all / dequantize
    exchange; it is now the int8 face of the staged ring in
    ``parallel/wire.py`` — the partial sum for each chunk rides the
    ring ``n-1`` hops, re-quantized per hop (payload + f32 scales on
    the wire) with f32 accumulation, so the compression applies inside
    the reduction stages themselves (EQuARX, arXiv:2506.17615).  Same
    wire bytes as the a2a shape; the blockwise scale still bounds each
    hop's element error by its block's max/254."""
    from bigdl_tpu.parallel import wire

    out, _ = wire.reduce_scatter(
        g, axis, n, wire.WireSpec("int8", block=block))
    return out


#: a TPU tile is 128 lanes wide: a leaf with fewer elements than that
#: behind its second dimension cannot be read out of the flat vector by
#: a reshape without padding every one of those tails to a whole tile
_LANES = 128


def leaf_is_relaid(shape) -> bool:
    """Which route a leaf takes between the flat vector and its own
    shape, from the shape alone: rank 3 or more with 2 to 127 elements
    behind the second dimension (a convolution kernel ``(out, in, kh,
    kw)``: 2-D, volumetric, grouped, depthwise, a 7 x 7 stem) goes by a
    transposition, everything else (vectors, ``Linear``'s matrices,
    embeddings, 1 x 1 kernels, a tail of 128 or more) by the plain
    reshape.  See ``DistriOptimizer._init_params``."""
    return len(shape) >= 3 and 2 <= math.prod(shape[2:]) < _LANES


def unpack_leaf(seg, shape):
    """A leaf's slice of the flat vector (``leaf.reshape(-1)`` order) as
    the leaf.  A relaid leaf is read as the matrix ``(out * in, taps)``
    it is in memory, transposed once to taps-first, and handed on behind
    a logical ``transpose`` to ``(out, in) + taps``, which the compiler
    folds into the layout its convolution reads (taps outermost, ``in``
    along the lanes)."""
    shape = tuple(int(d) for d in shape)
    if not leaf_is_relaid(shape):
        return seg.reshape(shape)
    jnp = _jnp()
    r = len(shape)
    taps_first = seg.reshape(shape[0] * shape[1], -1).T.reshape(
        shape[2:] + shape[:2])
    return jnp.transpose(taps_first, (r - 2, r - 1) + tuple(range(r - 2)))


def pack_leaf(leaf):
    """``unpack_leaf``'s inverse: the leaf as its slice of the flat
    vector, the same elements in the same order as ``leaf.reshape(-1)``.
    A relaid leaf is brought taps-first by a logical ``transpose`` (its
    gradient comes out of the convolution that way), read as the matrix
    ``(taps, out * in)`` and transposed once."""
    shape = leaf.shape
    if not leaf_is_relaid(shape):
        return leaf.reshape(-1)
    jnp = _jnp()
    r = len(shape)
    taps_first = jnp.transpose(leaf, tuple(range(2, r)) + (0, 1))
    return taps_first.reshape(-1, shape[0] * shape[1]).T.reshape(-1)


class FlatLayout:
    """The flat ZeRO-1 vector's layout: ``concatenate(leaf.reshape(-1))``
    in ``jax.tree.leaves`` order, in the leaves' common dtype
    (``jax.flatten_util.ravel_pytree``'s, element for element), with an
    unpack and a pack that choose each leaf's route by
    ``leaf_is_relaid``."""

    def __init__(self, tree):
        import jax

        jnp = _jnp()
        leaves, self.treedef = jax.tree.flatten(tree)
        self.shapes = [tuple(int(d) for d in np.shape(x)) for x in leaves]
        self.dtypes = [jnp.result_type(x) for x in leaves]
        self.sizes = [int(np.prod(s, dtype=np.int64)) for s in self.shapes]
        self.offsets = [int(o) for o in
                        np.cumsum([0] + self.sizes, dtype=np.int64)]
        self.dtype = jnp.result_type(*self.dtypes) if leaves \
            else jnp.dtype(jnp.float32)

    @property
    def elems(self) -> int:
        return self.offsets[-1]

    def said(self) -> dict:
        """How often the transposition engages (the tracer's event
        ``distri.unpack``)."""
        relaid = [z for s, z in zip(self.shapes, self.sizes)
                  if leaf_is_relaid(s)]
        return dict(leaves=len(self.shapes), relaid_leaves=len(relaid),
                    elems=self.elems, relaid_elems=int(sum(relaid)))

    def unpack(self, flat):
        """Flat vector (padding allowed behind it) -> the tree."""
        import jax

        leaves = []
        for shape, dtype, off, size in zip(self.shapes, self.dtypes,
                                           self.offsets, self.sizes):
            leaf = unpack_leaf(jax.lax.slice_in_dim(flat, off, off + size),
                               shape)
            leaves.append(leaf if flat.dtype == dtype else leaf.astype(dtype))
        return jax.tree.unflatten(self.treedef, leaves)

    def pack(self, tree, dtype=None):
        """The tree -> flat vector, each leaf cast to ``dtype`` (default:
        the layout's) BEFORE it is moved: a cast commutes with moving
        elements, and a narrower leaf is half the bytes to move."""
        import jax

        jnp = _jnp()
        dtype = self.dtype if dtype is None else dtype
        leaves = jax.tree.leaves(tree)
        if not leaves:
            return jnp.zeros((0,), dtype)
        return jnp.concatenate([pack_leaf(x.astype(dtype)) for x in leaves])


class DistriOptimizer(LocalOptimizer):
    """Synchronous data-parallel trainer with ZeRO-1 sharded updates."""

    def __init__(self, model, dataset, criterion, batch_size=32, mesh=None,
                 wire_dtype=None, data_axes=None, int8_block=None,
                 wire_block=None, wire_ef=None, overlap_bucket_mb=None):
        super().__init__(model, dataset, criterion, batch_size)
        from bigdl_tpu.engine import Engine
        from bigdl_tpu.parallel import wire as W

        if mesh is None:
            if not Engine.is_initialized():
                Engine.init()
            mesh = Engine.mesh()
        self.mesh = mesh
        # hierarchical data parallelism (multi-slice): pass
        # data_axes=("dcn", "data") over a 2-level mesh and the batch /
        # flat-parameter shards split over BOTH axes — XLA then builds
        # the hierarchical collective (reduce-scatter inside each ICI
        # slice, cross-slice exchange over DCN) from the axis order
        self.axes = tuple(data_axes) if data_axes else (mesh.axis_names[0],)
        for a in self.axes:
            if a not in mesh.axis_names:
                raise ValueError(f"data axis {a!r} not in mesh axes "
                                 f"{mesh.axis_names}")
        self.axis = self.axes if len(self.axes) > 1 else self.axes[0]
        self.n_shards = 1
        for a in self.axes:
            self.n_shards *= mesh.shape[a]
        # reference: FP16CompressedTensor on-the-wire compression for
        # gradient blocks; bf16 is the TPU-native equivalent, int8 /
        # fp8 the blockwise-quantized EQuARX-style staged-ring options
        # (parallel/wire.py).  Unset knobs fall back to config
        # (BIGDL_WIRE_DTYPE / BIGDL_WIRE_BLOCK / BIGDL_WIRE_EF).
        from bigdl_tpu.config import config

        if wire_dtype is None:
            wire_dtype = config.wire.dtype
        if wire_dtype not in W.WIRE_DTYPES and \
                wire_dtype not in W.UNCOMPRESSED:
            # an unknown spelling must not silently train uncompressed
            raise ValueError(
                f"wire_dtype {wire_dtype!r} not supported; choose "
                "'bfloat16', 'int8', 'fp8_e4m3', 'fp8_e5m2', 'float32' "
                "or 'none'")
        self.wire_dtype = wire_dtype
        block = wire_block if wire_block is not None else int8_block
        if block is not None and int(block) < 1:
            raise ValueError(
                f"wire_block/int8_block must be positive, got {block}")
        if wire_dtype in W.WIRE_DTYPES:
            spec = W.WireSpec.from_config(
                dtype=wire_dtype, block=block, error_feedback=wire_ef)
        else:
            if wire_ef:
                raise ValueError(
                    "error feedback needs a compressed wire dtype "
                    f"(got {wire_dtype!r})")
            spec = None
        self.wire = spec
        # legacy spelling: the int8 wire's block knob names the block
        # for every scaled dtype
        self.int8_block = spec.block if spec is not None else \
            int(block) if block is not None else config.wire.block
        # the staged ring (scaled dtypes, or any EF wire) runs over ONE
        # ring; plain bf16 keeps the native psum_scatter, which XLA
        # lowers hierarchically
        self._staged_ring = spec is not None and (spec.scaled
                                                  or spec.error_feedback)
        if self._staged_ring and len(self.axes) > 1:
            raise NotImplementedError(
                f"the {wire_dtype!r} staged-ring wire over hierarchical "
                "data axes is not supported; use a single data axis or "
                "bfloat16")
        # bucketed comm/compute overlap (ISSUE 11): the gradient
        # exchange is split into ~bucket_mb MiB buckets launched
        # last-layer-first, so each bucket's reduce-scatter rides under
        # the remaining backward; <= 0 keeps the monolithic exchange.
        # The plan is derived lazily against the padded layout in
        # _init_opt_state (it needs the alignment quantum).
        if overlap_bucket_mb is None:
            overlap_bucket_mb = config.overlap_bucket_mb
        self.overlap_bucket_mb = float(overlap_bucket_mb)
        self._buckets = None
        self._pad = 0
        self._warned_batch_sizes = set()
        self._host_mask = None
        self._device_mask = None

    # ------------------------------------------------------------ sharding
    def _init_params(self):
        """The ZeRO-1 data plane works on the flat parameter vector (the
        reference's AllReduceParameter flat layout):
        ``concatenate(leaf.reshape(-1))`` in ``jax.tree.leaves`` order,
        which is what ``velocity``, ``wire_ef``, ``_topology()``,
        ``elastic.ensure_shard_layout``, the health boundaries and the
        frozen intervals all index.

        The unpack and the pack between that vector and the leaves are
        the optimizer's own (``FlatLayout``), and a leaf's route follows
        from its shape alone (``leaf_is_relaid``).  The chip keeps a
        convolution kernel ``(out, in, kh, kw)`` in the layout
        ``{1,0,3,2}``: taps outermost, ``in`` along the 128 lanes.  The
        flat order has the TAP varying fastest, so a plain ``reshape`` of
        a slice makes the compiler build an array whose 3 x 3 tail is
        padded to a whole tile (268 MB for one 4.7 MB bfloat16 512 x 512
        x 3 x 3 kernel) and copy THAT into the convolution's layout, and
        the same on the gradient's way back: 14 ms of ResNet-50's 65 ms
        step on four chips (PERF.md, PR 51).  So a leaf of rank 3 or more
        with 2 to 127 elements behind its second dimension is cut out as
        the matrix ``(out * in, taps)`` and transposed ONCE, at the
        memory rate; every other leaf (a vector, a matrix, an embedding,
        a 1 x 1 kernel, a tail of 128 or more, which fills its lanes)
        takes the plain reshape and costs what it cost."""
        import jax

        self._layout = layout = FlatLayout(self.model.params())
        # one program each for the reads outside the step (validation,
        # histograms, write-back) and for this pack, not an eager
        # dispatch a leaf
        self._unpack = jax.jit(layout.unpack)
        flat = jax.jit(layout.pack)(self.model.params())
        # static shape metadata for the collective byte footprint —
        # host-side ints, no device read
        self._flat_elems = int(flat.size)
        self._flat_dtype = str(flat.dtype)
        return flat

    def _params_tree(self, pvar):
        # unpack on device: the flat ZeRO vector -> params pytree with
        # no host round-trip
        return self._unpack(pvar)

    def _topology(self):
        """Checkpoint topology tag: the flat ZeRO-1 layout plus the
        world size and padding it was written under, so restore at a
        different world knows exactly what to strip and re-pad
        (resilience/elastic.py ensure_shard_layout)."""
        topo = {"world_size": self.n_shards,
                "shard_layout": "zero1_flat",
                "step": self.state["neval"],
                "flat_elems": getattr(self, "_flat_elems", None),
                "pad": self._pad,
                # the wire the run trained under — a resize-resume can
                # see whether an EF residual rides the optimizer state
                # without opening the npz
                "wire": {"dtype": self.wire_dtype,
                         "block": self.int8_block,
                         "ef": bool(self.wire is not None
                                    and self.wire.error_feedback)}}
        # overlapped runs leave the ZeRO-1 state vectors in the
        # bucketed shard-major layout — the manifest must carry the
        # plan so a resume at a different plan/world can re-permute
        # (resilience/elastic.ensure_shard_layout); single-bucket runs
        # omit the key (parameter-major, the historical layout)
        if self._buckets is not None and len(self._buckets) > 1:
            topo["buckets"] = [[s, z] for s, z in self._buckets]
        return topo

    def _write_back(self, pvar, mod_state):
        # the unpack allocates fresh arrays; mod_state is copied so the
        # model never aliases buffers the donated step will delete
        import jax

        jnp = _jnp()
        self.model.set_params(self._unpack(pvar))
        self.model.set_state(
            jax.tree.map(lambda a: jnp.array(a, copy=True), mod_state)
        )

    def _init_opt_state(self, flat):
        """Optimizer state lives only on the owner shard (reference:
        «bigdl»/parameters/AllReduceParameter.scala — "optimizer state
        lives only there")."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        jnp = _jnp()
        n = self.n_shards
        # scaled wires (int8/fp8) need whole quantization blocks per
        # shard; everything else just whole shards
        quantum = n * self.int8_block \
            if (self.wire is not None and self.wire.scaled) else n
        self._pad = (-flat.size) % quantum
        shard_len = (flat.size + self._pad) // n
        # bucketed overlap plan (parallel/wire.py): contiguous quantum-
        # aligned slices of the padded flat layout, each ~bucket_mb MiB
        # of gradient; the step launches one exchange per bucket,
        # last-layer-first.  Summed wire bytes equal the monolithic
        # exchange exactly (every bucket is whole quanta).
        from bigdl_tpu.parallel import wire as _W

        itemsize = max(1, np.dtype(self._flat_dtype).itemsize) \
            if getattr(self, "_flat_dtype", None) else 4
        target = int(self.overlap_bucket_mb * (1 << 20) / itemsize) \
            if self.overlap_bucket_mb > 0 else 0
        self._buckets = _W.plan_buckets(flat.size + self._pad, quantum,
                                        target)
        opt = self.optim_method
        if opt.state is not None:
            # guard against an OptimMethod whose state was built by
            # LocalOptimizer (nested pytree slots) — the ZeRO data plane
            # needs flat shard-shaped state
            for v in opt.state.values():
                if not hasattr(v, "ndim"):
                    raise ValueError(
                        "optim_method.state was initialised for tree "
                        "parameters (LocalOptimizer); reset it (state=None) "
                        "before reusing the method with DistriOptimizer"
                    )
            # topology-aware resume (resilience/elastic.py): state
            # restored from a checkpoint written at a different world
            # size carries the OLD padded length — strip the old
            # alignment padding, re-pad for this mesh's quantum, and
            # re-place P(axis); same-world resumes pass through
            from bigdl_tpu.resilience import elastic

            opt.state = elastic.ensure_shard_layout(
                opt.state, flat_elems=int(flat.size), pad=self._pad,
                n_shards=n, mesh=self.mesh, axis=self.axis,
                topology=getattr(opt, "loaded_topology", None),
                buckets=self._buckets)
        if opt.state is None:
            # build state against a single shard-sized template, then
            # expand vector entries across the mesh
            template = jnp.zeros((shard_len,), flat.dtype)
            local = opt.init_state(template)
            sharded = {}
            for k, v in local.items():
                if v.ndim == 1 and v.shape[0] == shard_len:
                    full = jnp.tile(v, n)
                    sharded[k] = jax.device_put(
                        full, NamedSharding(self.mesh, P(self.axis))  # noqa: E501  (tuple spec shards over all data axes)
                    )
                else:
                    sharded[k] = jax.device_put(
                        v, NamedSharding(self.mesh, P())
                    )
            opt.state = sharded
        # error-feedback residual (parallel/wire.py): one f32 row per
        # device in flat-parameter coordinates, sharded so each device
        # owns exactly its own row.  Lives in the optimizer state so it
        # rides checkpoints with the flat ZeRO-1 vectors and is re-laid
        # -out by elastic.ensure_shard_layout on world resize (a
        # checkpointed residual from a DIFFERENT world is reset to
        # zeros there — safe: it is a correction term, not state the
        # update depends on).
        padded = flat.size + self._pad
        if self.wire is not None and self.wire.error_feedback:
            ef = opt.state.get("wire_ef")
            if ef is None or tuple(ef.shape) != (n, padded):
                opt.state["wire_ef"] = jax.device_put(
                    jnp.zeros((n, padded), jnp.float32),
                    NamedSharding(self.mesh, P(self.axis, None)))
        else:
            # resumed without EF: drop a checkpointed residual instead
            # of threading dead state through the step
            opt.state.pop("wire_ef", None)
        # stamp the method with the layout its state is NOW in: a later
        # re-init (second optimize(), a bucket-plan or world change)
        # then re-partitions from accurate provenance instead of a
        # stale checkpoint tag — with the bucketed shard-major layout,
        # "what order are these vectors in" is no longer answerable
        # from their length alone
        opt.loaded_topology = self._topology()
        return opt.state

    def _collective_byte_footprint(self):
        """The static wire-byte budget of one standard train step —
        every collective ``sharded_step`` programs, costed from shapes
        the driver already holds (obs/collectives.py cost model; no
        device reads, no extra syncs).  Publishes the per-step gauges +
        the int8-vs-f32 savings-ratio gauge and returns the bound
        footprint the driver loop commits per resolved step."""
        import jax

        from bigdl_tpu import obs
        from bigdl_tpu.config import config
        from bigdl_tpu.obs import collectives as C

        n = self.n_shards
        padded = self._flat_elems + self._pad
        pdtype = self._flat_dtype
        fp = C.StepFootprint()
        # ---- putGradients + aggregate: the gradient exchange ---------
        if self._staged_ring:
            ex = C.staged_ring_exchange_bytes(
                padded, n, self.int8_block, self.wire.wire_name)
            exchange = 0.0
            for name, b in ex.items():
                fp.add("ring_rs", name, b)
                exchange += b
        else:
            wire = {"bfloat16": "bfloat16", "float32": "float32"}.get(
                self.wire_dtype, pdtype)  # "none" ships the grad dtype
            exchange = C.reduce_scatter_bytes(padded, wire, n)
            fp.add("psum_scatter", wire, exchange)
        # global-norm psum on the sharded gradient (always computed)
        fp.add("psum", "float32", C.all_reduce_bytes(1, "float32", n))
        if config.nonfinite_guard:
            fp.add("pmin", "float32", C.all_reduce_bytes(1, "float32", n))
        if self._health_monitor is not None:
            # the (L, 4) per-layer health-stats psum (obs/health.py)
            n_layers = len(self._health_monitor.names)
            fp.add("psum", "float32",
                   C.all_reduce_bytes(n_layers * 4, "float32", n))
        # loss pmean/psum (scalar, f32 either way)
        fp.add("pmean", "float32", C.all_reduce_bytes(1, "float32", n))
        # sendWeight + getWeights: the full padded vector comes back
        fp.add("all_gather", pdtype, C.all_gather_bytes(padded, pdtype, n))
        # BN running stats pmean (floating model-state leaves)
        import jax.numpy as jnp

        for leaf in jax.tree.leaves(self.model.state()):
            if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype,
                                                         jnp.floating):
                fp.add("pmean", str(leaf.dtype),
                       C.all_reduce_bytes(int(leaf.size), leaf.dtype, n))
        fp.bind(obs.get_registry())
        # the goodput window classifier estimates comm seconds from the
        # same static budget (obs/goodput.py, BIGDL_WIRE_GBPS)
        self._obs_ledger.set_comm_bytes_per_step(fp.total())
        # overlap accounting (ISSUE 11): with K buckets, the first K-1
        # exchanges (in launch order) ride under the remaining backward
        # — only the final bucket's exchange (plus the gathers/psums the
        # update chain serializes on) is EXPOSED wall time.  The ledger
        # classifies comm_bound from the exposed bytes; the gauges make
        # the overlap itself observable (obs/report.py "overlap" block,
        # the exposed_comm_high alert rule).
        n_buckets = len(self._buckets) if self._buckets else 1
        registry = obs.get_registry()
        registry.gauge(
            names.OVERLAP_BUCKETS,
            "Gradient-exchange buckets of the overlapped step "
            "(1 = monolithic, no overlap)").set(float(n_buckets))
        if n_buckets > 1:
            hidden = exchange * (n_buckets - 1) / n_buckets
            exposed = fp.total() - hidden
            self._obs_ledger.set_exposed_comm_bytes_per_step(exposed)
            registry.gauge(
                names.OVERLAP_EXPOSED_COMM_FRACTION,
                "Share of the per-step collective bytes NOT hidden "
                "under backward by the bucketed exchange").set(
                round(exposed / fp.total(), 6) if fp.total() else 0.0)
            if config.obs.wire_gbps > 0:
                registry.gauge(
                    names.OVERLAP_EXPOSED_COMM_SECONDS,
                    "Estimated per-step collective seconds not hidden "
                    "by backward (exposed bytes / BIGDL_WIRE_GBPS)").set(
                    exposed / (config.obs.wire_gbps * 1e9))
        else:
            self._obs_ledger.set_exposed_comm_bytes_per_step(None)
        # the EQuARX argument as a gauge: f32 exchange bytes over what
        # the configured wire actually ships, on the gradient path
        f32_exchange = C.reduce_scatter_bytes(padded, "float32", n)
        ratio = C.record_savings("grad", f32_exchange, exchange,
                                 registry=obs.get_registry())
        tracer = obs.get_tracer()
        if tracer.enabled:
            tracer.event("collective.footprint",
                         wire_dtype=self.wire_dtype, n_shards=n,
                         padded_elems=padded,
                         bytes_per_step=round(fp.total(), 1),
                         savings_ratio=round(ratio, 4),
                         breakdown={k: round(v, 1)
                                    for k, v in fp.by_op().items()})
        return fp

    def _build_train_step(self):
        """Returns a dispatcher: full batches run the plain compiled
        step; a padded final batch (``_prepare_batch`` set a mask) runs
        a lazily-built masked variant whose gradient divides by the
        VALID sample count — the reference's SampleToMiniBatch padding
        semantics (VERDICT r3 weak #7), so the loss trajectory matches
        an unpadded single-device run exactly (modulo BN batch stats,
        which see the pad copies — same as the reference's padding)."""
        self._plain_step = self._build_step_impl(masked=False)
        self._masked_step = None
        # the masked final-batch variant adds only one scalar psum
        # (valid count) on top of this; the standard step's budget is
        # the per-step account
        self._collective_footprint = self._collective_byte_footprint()
        # how often the unpack's transposition engages, said once a
        # program (as ops/grouped_matmul.py says its tiles)
        from bigdl_tpu import obs

        said = self._layout.said()
        logging.getLogger("bigdl_tpu.optim").debug("distri.unpack %s", said)
        tracer = obs.get_tracer()
        if tracer.enabled:
            tracer.event("distri.unpack", **said)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        replicated = NamedSharding(self.mesh, P())

        def dispatch(pvar, opt_state, mod_state, rng, inp, tgt):
            # the first call's params and model state come from the
            # model (one device), every later call's are step outputs
            # (replicated over the mesh).  Commit the first call's to
            # the mesh, or the step is traced and compiled twice
            if pvar.sharding != replicated:
                pvar, mod_state = jax.device_put((pvar, mod_state),
                                                 replicated)
            mask = self._device_mask
            if mask is None:
                return self._plain_step(pvar, opt_state, mod_state, rng,
                                        inp, tgt)
            if self._masked_step is None:
                self._masked_step = self._build_step_impl(masked=True)
            return self._masked_step(pvar, opt_state, mod_state, rng,
                                     inp, tgt, mask)

        return dispatch

    def _build_step_impl(self, masked: bool):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from bigdl_tpu.config import config

        jnp = _jnp()
        guard = config.nonfinite_guard
        opt = self.optim_method
        clipper = self._clipper
        loss_fn = self._loss_fn(masked=masked)
        n = self.n_shards
        axis = self.axis
        pad = self._pad
        wire = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
                "none": None}.get(self.wire_dtype, None)
        wire_spec = self.wire
        staged_ring = self._staged_ring
        ef_on = wire_spec is not None and wire_spec.error_feedback
        global_batch = self.batch_size
        # overlap plan (ISSUE 11): contiguous quantum-aligned buckets of
        # the padded flat layout; one exchange per bucket, emitted
        # last-layer-first so each bucket's wire launches under the
        # remaining backward.  One bucket = the monolithic exchange.
        buckets = [(int(s), int(z)) for s, z in self._buckets]
        # per-layer health telemetry on the ZeRO shard (obs/health.py):
        # layer boundaries in the ravelled layout — each device
        # segment-sums its shard's contribution and ONE (L, 4) psum
        # makes every host's stats global
        health_on = self._health_monitor is not None
        boundaries = None
        if health_on:
            from bigdl_tpu.obs import health as H

            boundaries = jnp.asarray(
                np.cumsum(H.layer_sizes(self.model.params())), jnp.int32)
        # freeze support on the flat ZeRO vector.  VERDICT r4 weak #5:
        # do NOT embed a flat-param-sized f32 mask as a jit constant
        # (plus a second padded copy for the shard slice) — that doubles
        # HBM for the mask alone at large scale.  Frozen leaves occupy
        # contiguous ranges of the ravelled vector (ravel_pytree
        # concatenates in tree.leaves order), so record merged
        # (start, end) intervals host-side and rebuild any piece of the
        # mask on the fly from iota comparisons: O(#frozen-runs) cheap
        # vector ops, no O(n) constants.
        frozen_intervals = None
        if self.model.has_frozen():
            import jax as _jax

            sizes = [int(np.size(x))
                     for x in _jax.tree.leaves(self.model.params())]
            keeps = [float(x)
                     for x in _jax.tree.leaves(self.model.grad_mask())]
            if len(sizes) != len(keeps):  # tree.map used to raise here
                raise ValueError(
                    f"grad_mask leaves ({len(keeps)}) do not match "
                    f"params leaves ({len(sizes)})")
            frozen_intervals = []
            off = 0
            for sz, keep in zip(sizes, keeps):
                if keep == 0.0 and sz:
                    if frozen_intervals and frozen_intervals[-1][1] == off:
                        frozen_intervals[-1][1] = off + sz  # merge run
                    else:
                        frozen_intervals.append([off, off + sz])
                off += sz
            if off + pad >= 2 ** 31:
                # the on-the-fly mask addresses flat positions with an
                # int32 iota; past 2^31 elements it would wrap silently
                raise NotImplementedError(
                    "frozen-parameter masking indexes the ravelled "
                    f"vector with int32 ({off} params + {pad} pad "
                    ">= 2^31); shard the model (tensor parallelism) "
                    "or enable jax_enable_x64")

        def _keep_mask(offset, length, dtype):
            """1.0 where trainable, 0.0 inside a frozen interval, for
            flat positions [offset, offset+length) — offset may be a
            traced shard index."""
            idx = jax.lax.iota(jnp.int32, length) + offset
            m = jnp.ones((length,), dtype)
            for s, e in frozen_intervals:
                m = m * (1.0 - ((idx >= s) & (idx < e)).astype(dtype))
            return m

        # where the very next thing the flat gradient meets is the cast
        # to the wire, the leaves are cast first and packed in that
        # dtype: the same bits on the wire, half the bytes to move
        pack_dtype = wire if not staged_ring else None
        grads = self._value_and_flat_grad

        def sharded_step(flat_p, opt_st, mstate, rng, inp, tgt, mask=None):
            # named_scopes carry the reference's Metrics phase names into
            # profiler traces / HLO metadata (SURVEY.md §5 Tracing)
            rest = (mstate, rng, inp, tgt) + ((mask,) if masked else ())
            (loss_aux, new_mstate), grad = grads(loss_fn, flat_p, rest,
                                                 pack_dtype)
            with jax.named_scope("put_gradient"):
                if frozen_intervals is not None:
                    # 0 / 1 commute with the cast the pack already made
                    grad = grad * _keep_mask(0, grad.shape[0], grad.dtype)
                # ---- putGradients + aggregateGradientPartition ----------
                # one exchange per overlap bucket, emitted last-layer-
                # first: the ravel layout is first-layer-first and the
                # backward resolves the LAST layers' gradients first, so
                # the highest-offset bucket's wire can start while the
                # rest of the backward is still running.  This device
                # ends up owning its slice of EVERY bucket (the shard-
                # major layout _topology records); one bucket reproduces
                # the monolithic exchange exactly.
                g = jnp.pad(grad, (0, pad))
                new_ef = None
                pieces = [None] * len(buckets)
                if staged_ring:
                    from bigdl_tpu.parallel import wire as W

                    # in-reduce quantization (parallel/wire.py): the
                    # partial sums ride the ring re-quantized per hop,
                    # accumulated in f32; with EF on, this device's
                    # residual rows (flat-parameter coords) ride along
                    # per bucket and come back updated
                    ef = opt_st.get("wire_ef")
                    ef_flat = None if ef is None else ef.reshape(-1)
                    ef_pieces = [None] * len(buckets)
                    for b in reversed(range(len(buckets))):
                        s, z = buckets[b]
                        ef_b = None if ef_flat is None else \
                            jax.lax.slice_in_dim(
                                ef_flat, s, s + z).reshape(n, z // n)
                        pieces[b], ef_pieces[b] = W.reduce_scatter(
                            jax.lax.slice_in_dim(g, s, s + z), axis, n,
                            wire_spec, ef=ef_b)
                    if ef_flat is not None:
                        # per-bucket rows flatten back to flat-parameter
                        # coords; ascending concat rebuilds the full row
                        new_ef = ef_pieces[0] if len(ef_pieces) == 1 \
                            else jnp.concatenate(
                                [e.reshape(-1) for e in ef_pieces])
                else:
                    for b in reversed(range(len(buckets))):
                        s, z = buckets[b]
                        pieces[b] = jax.lax.psum_scatter(
                            jax.lax.slice_in_dim(g, s, s + z), axis,
                            scatter_dimension=0, tiled=True)
                gshard = pieces[0] if len(pieces) == 1 \
                    else jnp.concatenate(pieces)
            with jax.named_scope("aggregate_gradient"):
                gshard = gshard.astype(flat_p.dtype)
                # reference: gradient /= numSamples — the global batch,
                # or the global VALID count under final-batch padding
                if masked:
                    valid = jax.lax.psum(jnp.sum(mask), axis)
                    gshard = gshard / valid
                else:
                    gshard = gshard / global_batch
                # ParameterProcessors on the *sharded* gradient, with the
                # global norm via psum — matching L2NormClippingProcessor
                sq = jax.lax.psum(jnp.sum(gshard * gshard), axis)
                # health stats see the batch-scaled, pre-clip gradient
                # (clipping hides exactly the explosions the telemetry
                # exists to show)
                g_for_health = gshard if health_on else None
                gshard = clipper(gshard, global_sq_norm=sq)
            if guard:
                # non-finite step guard: every replica must agree to
                # skip or the all_gathered weights diverge — pmin of the
                # local shard's finiteness is the global verdict
                ok_local = jnp.all(jnp.isfinite(gshard)) \
                    & jnp.isfinite(loss_aux)
                ok = jax.lax.pmin(
                    ok_local.astype(jnp.float32), axis) > 0
            else:
                ok = jnp.array(True)
            with jax.named_scope("optimizer_update"):
                # ---- owner-slice weight update (ZeRO-1) -----------------
                if isinstance(axis, tuple):
                    # combined owner index over hierarchical data axes,
                    # major-to-minor in axis order (matches the
                    # P(axes)-tuple shard layout psum_scatter produces)
                    idx = jax.lax.axis_index(axis[0])
                    for a in axis[1:]:
                        idx = idx * self.mesh.shape[a] \
                            + jax.lax.axis_index(a)
                else:
                    idx = jax.lax.axis_index(axis)
                shard_len = (flat_p.size + pad) // n
                padded_p = jnp.pad(flat_p, (0, pad))
                if len(buckets) == 1:
                    wshard = jax.lax.dynamic_slice(
                        padded_p, (idx * shard_len,), (shard_len,))
                else:
                    # bucketed ownership: this device's chunk of every
                    # bucket, ascending — element-aligned with gshard
                    wshard = jnp.concatenate([
                        jax.lax.dynamic_slice(
                            padded_p, (s + idx * (z // n),), (z // n,))
                        for s, z in buckets])
                # the EF residual is wire state, not optimizer state —
                # the method never sees it; it re-enters the state dict
                # updated by the staged ring above
                opt_in = {k: v for k, v in opt_st.items()
                          if k != "wire_ef"} if ef_on else opt_st
                new_wshard, new_opt = opt.step(gshard, wshard, opt_in)
                if ef_on:
                    new_opt = dict(new_opt)
                    new_opt["wire_ef"] = (
                        new_ef.reshape(opt_st["wire_ef"].shape)
                        if new_ef is not None else opt_st["wire_ef"])
                if guard:
                    # skipped step: owner shard and opt state pass
                    # through unchanged (graceful degradation — the
                    # driver counts the skip and may escalate)
                    new_wshard = jnp.where(ok, new_wshard, wshard)
                    new_opt = jax.tree.map(
                        lambda a, b: jnp.where(ok, a, b)
                        if hasattr(a, "dtype") else a,
                        new_opt, opt_st)
                if frozen_intervals is not None:
                    # mask the UPDATE as well as the gradient: optimizer
                    # -internal weight decay adds wd*p past the zeroed
                    # gradient — frozen parameters must not move at all.
                    # Padding positions (flat idx >= true size) fall in
                    # no frozen interval, so the tail mask is 1 — the
                    # padded lanes are discarded by the final slice.
                    if len(buckets) == 1:
                        mshard = _keep_mask(idx * shard_len, shard_len,
                                            wshard.dtype)
                    else:
                        mshard = jnp.concatenate([
                            _keep_mask(s + idx * (z // n), z // n,
                                       wshard.dtype)
                            for s, z in buckets])
                    new_wshard = wshard + mshard * (new_wshard - wshard)
                if health_on:
                    from bigdl_tpu.obs import health as H

                    # (L, 4) global per-layer stats: new_wshard is
                    # post-guard/post-freeze, so a skipped step reports
                    # a zero update; nonfinite counts come from the
                    # summed pre-clip gradient.  Bucketed shards are not
                    # contiguous in flat coords — hand the per-position
                    # coordinates over explicitly.
                    positions = None
                    if len(buckets) > 1:
                        positions = jnp.concatenate([
                            jax.lax.iota(jnp.int32, z // n)
                            + (s + idx * (z // n))
                            for s, z in buckets])
                    health_stats = H.flat_shard_stats(
                        g_for_health, wshard, new_wshard,
                        idx * shard_len, boundaries, axis,
                        positions=positions)
            with jax.named_scope("send_weights"):
                # ---- sendWeightPartition + getWeights -------------------
                if len(buckets) == 1:
                    new_flat = jax.lax.all_gather(new_wshard, axis,
                                                  tiled=True)
                else:
                    # per-bucket gather mirrors the per-bucket scatter;
                    # ascending concat restores flat-parameter order
                    off, parts = 0, []
                    for s, z in buckets:
                        c = z // n
                        parts.append(jax.lax.all_gather(
                            jax.lax.slice_in_dim(new_wshard, off,
                                                 off + c),
                            axis, tiled=True))
                        off += c
                    new_flat = jnp.concatenate(parts)
                new_flat = new_flat[: flat_p.size]
            if guard:
                # a poisoned forward also poisons BN running stats —
                # a skipped step must not keep NaN statistics either
                new_mstate = jax.tree.map(
                    lambda a, b: jnp.where(ok, a, b)
                    if hasattr(a, "dtype") else a,
                    new_mstate, mstate)
            # keep BN running stats in sync across replicas (the reference
            # leaves them per-replica; pmean is strictly better and free)
            new_mstate = jax.tree.map(
                lambda s: jax.lax.pmean(s, axis)
                if hasattr(s, "dtype") and jnp.issubdtype(s.dtype, jnp.floating)
                else s,
                new_mstate,
            )
            if masked:
                # true masked mean: sum of valid per-sample losses over
                # the global valid count (shards hold unequal counts)
                loss = jax.lax.psum(loss_aux, axis) / valid
            else:
                loss = jax.lax.pmean(loss_aux, axis)
            if health_on:
                return (new_flat, new_opt, new_mstate, loss, ok,
                        health_stats)
            return new_flat, new_opt, new_mstate, loss, ok

        opt_state_specs = {
            k: P(axis) if v.ndim == 1
            else (P(axis, None) if k == "wire_ef" else P())
            for k, v in opt.state.items()}
        mstate_spec = jax.tree.map(lambda _: P(), self.model.state())

        in_specs = (P(), opt_state_specs, mstate_spec, P(), P(axis), P(axis))
        if masked:
            in_specs = in_specs + (P(axis),)
        out_specs = (P(), opt_state_specs, mstate_spec, P(), P())
        if health_on:
            out_specs = out_specs + (P(),)  # psum'd -> replicated
        mapped = _shard_map(
            sharded_step,
            self.mesh,
            in_specs=in_specs,
            out_specs=out_specs,
        )
        # donate params/opt-state/model-state like LocalOptimizer: the
        # step updates in place on-device instead of holding two copies
        # of the flat vector + sharded velocity in HBM (the driver loop
        # rebinds from the outputs; _write_back copies before any host
        # read)
        return jax.jit(mapped, donate_argnums=(0, 1, 2))

    def _loss_fn(self, masked: bool = False):
        """Reference semantics: sub-model gradients are *summed* then
        divided by the global batch size (SURVEY.md §7 hard part 2).  The
        criterion's sizeAverage divides by the local sub-batch; multiply
        back so psum_scatter(sum) / global_batch is exact.

        ``masked=True`` builds the padded-final-batch variant: the
        criterion runs per sample (vmap over singleton batches — exact
        for every per-sample-decomposable criterion, which the classic
        set all is), pad rows are zero-weighted, and the aux loss is the
        local masked SUM (the sharded step divides by the global valid
        count)."""
        model, criterion = self.model, self.criterion
        local_bs = self.batch_size // self.n_shards

        def forward(p, mstate, rng, inp):
            import jax

            jnp = _jnp()
            pc, inpc = self._cast_for_compute(p, inp)
            out, new_mstate = model.apply(pc, mstate, inpc, training=True,
                                          rng=rng)
            out = jax.tree.map(
                lambda a: a.astype(jnp.float32)
                if hasattr(a, "dtype") and jnp.issubdtype(a.dtype,
                                                          jnp.floating)
                else a,
                out,
            )
            return out, new_mstate

        if masked:
            def loss_fn(p, mstate, rng, inp, tgt, mask):
                import jax

                jnp = _jnp()
                out, new_mstate = forward(p, mstate, rng, inp)
                single = lambda t: jax.tree.map(lambda a: a[None], t)
                per = jax.vmap(
                    lambda o, t: criterion.loss(single(o), single(t))
                )(out, tgt)
                local_sum = jnp.sum(per * mask)
                total = local_sum + model.regularization_loss(p)
                return total, (local_sum, new_mstate)

            return loss_fn

        def loss_fn(p, mstate, rng, inp, tgt):
            out, new_mstate = forward(p, mstate, rng, inp)
            per_mean = criterion.loss(out, tgt)
            # un-average: total local loss; grads then sum over samples, and
            # the sharded step divides by the global batch afterwards
            total = per_mean * local_bs if getattr(
                criterion, "size_average", True
            ) else per_mean
            # each replica adds the full regularizer gradient before the
            # sum-then-/globalBatch — the reference does the same inside
            # every replica's accGradParameters
            total = total + model.regularization_loss(p)
            return total, (per_mean, new_mstate)

        return loss_fn

    def _value_and_flat_grad(self, loss_fn, flat_p, rest, pack_dtype):
        """``loss_fn``'s aux and its gradient as a flat vector in
        ``pack_dtype`` (None: the flat vector's own), inside the sharded
        step.  The leaves are cut out of ``flat_p`` OUTSIDE the
        differentiated function and the gradient is taken with respect
        to the TREE, then packed by the inverse route: differentiating
        through the unpack instead lets the compiler rebuild every saved
        copy as one pass over the whole flat vector
        (scripts/ravel_layout_probe.py, form C against form D)."""
        import jax

        layout = self._layout
        with jax.named_scope("get_weights"):
            p = layout.unpack(flat_p)
        with jax.named_scope("computing"):
            # ---- local replica compute (per-core fwd/bwd) ---------------
            (_, aux), gtree = jax.value_and_grad(loss_fn, has_aux=True)(
                p, *rest)
        with jax.named_scope("put_gradient"):
            return aux, layout.pack(gtree, pack_dtype)

    def _prepare_batch(self, inp, tgt):
        """The P(data) input sharding needs the batch divisible by the
        mesh; PAD the remainder by repeating the last sample and mark
        the pad rows in a mask that ``_build_train_step``'s masked
        variant folds into the loss/gradient mean (the reference's
        SampleToMiniBatch padding — SURVEY.md §2.1 "Dataset core";
        VERDICT r3 weak #7).  Nothing is ever trimmed or dropped."""
        bs = np.asarray(inp).shape[0]
        # per-process datasets yield LOCAL slices: divisibility is
        # against this process's device count, not the global mesh
        divisor = self.n_shards
        if getattr(self.dataset, "per_process", False):
            import jax

            divisor = max(1, self.n_shards // jax.process_count())
        rem = bs % divisor
        if rem == 0:
            self._host_mask = None
            return inp, tgt
        pad_n = divisor - rem
        if bs not in self._warned_batch_sizes:
            self._warned_batch_sizes.add(bs)
            logging.getLogger("bigdl_tpu.optim").info(
                "DistriOptimizer: batch of %d not divisible by the %d-way "
                "device split — padding with %d masked copies of the last "
                "sample (exact masked-mean semantics)", bs, divisor, pad_n,
            )
        inp = np.asarray(inp)
        tgt = np.asarray(tgt)
        inp = np.concatenate([inp, np.repeat(inp[-1:], pad_n, axis=0)])
        tgt = np.concatenate([tgt, np.repeat(tgt[-1:], pad_n, axis=0)])
        self._host_mask = np.concatenate(
            [np.ones(bs, np.float32), np.zeros(pad_n, np.float32)])
        return inp, tgt

    def _put_batch(self, inp, tgt):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sh = NamedSharding(self.mesh, P(self.axis))
        mask = getattr(self, "_host_mask", None)
        if getattr(self.dataset, "per_process", False) \
                and jax.process_count() > 1:
            # per-process shard -> global array without any host holding
            # the full batch (reference: executors feed their own cached
            # partition only)
            put = lambda a: jax.make_array_from_process_local_data(
                sh, np.asarray(a))
        else:
            # from host memory straight to each device's shard: going
            # through jnp.asarray first would land the whole global
            # batch on device 0 and scatter from there
            put = lambda a: jax.device_put(np.asarray(a), sh)
        self._device_mask = None if mask is None else put(mask)
        return put(inp), put(tgt)

    def optimize(self):
        # reference: retryNum < maxRetry => reload last checkpoint and
        # continue (SURVEY.md §3.2 tail; §5 failure semantics).  The
        # blind retry became a classified policy (resilience/retry.py):
        # fatal errors (bad config — ValueError/TypeError/…) surface on
        # the FIRST attempt with zero checkpoint reloads; transient ones
        # (XLA/OSError/injected faults/non-finite escalation) back off
        # exponentially and reload the newest INTACT checkpoint.
        import time

        from bigdl_tpu import obs
        from bigdl_tpu.resilience.retry import RetryPolicy, classify

        log = logging.getLogger("bigdl_tpu.optim")
        policy = RetryPolicy.from_config(max_retries=self.max_retry)
        retry_counter = obs.get_registry().counter(
            names.RETRY_ATTEMPTS_TOTAL,
            "Training failures handled by the retry policy",
            labels=("classification", "error"))
        while True:
            try:
                return super().optimize()
            except Exception as e:
                kind = classify(e)
                if not self.checkpoint_path or kind == "fatal":
                    # structured telemetry even for the non-retried path:
                    # a fatal config error at step N is exactly what a
                    # post-mortem trace must show
                    retry_counter.labels(classification=kind,
                                         error=type(e).__name__).inc()
                    obs.get_tracer().event(
                        "resilience.failure", classification=kind,
                        error=type(e).__name__, step=self.state["neval"],
                        retried=False)
                    raise
                delay = policy.record_failure(e)
                retry_counter.labels(classification="transient",
                                     error=type(e).__name__).inc()
                if delay is None:
                    log.error(
                        "retry budget exhausted after %d transient "
                        "failures; surfacing the last one", policy.attempts)
                    obs.get_tracer().event(
                        "resilience.retry_budget_exhausted",
                        attempts=policy.attempts,
                        error=type(e).__name__, step=self.state["neval"])
                    raise
                log.exception(
                    "transient training failure (%s); retry %d/%d from "
                    "last intact checkpoint in %.2fs",
                    type(e).__name__, policy.attempts, self.max_retry,
                    delay,
                )
                obs.get_tracer().event(
                    "resilience.retry", classification="transient",
                    error=type(e).__name__, attempt=policy.attempts,
                    max_retries=self.max_retry,
                    delay_s=round(delay, 4), step=self.state["neval"])
                self._summary_resilience(self.state["neval"],
                                         retries=policy.attempts)
                if delay > 0:
                    time.sleep(delay)
                from bigdl_tpu.utils.serializer import load_latest_checkpoint

                extra = load_latest_checkpoint(
                    self.checkpoint_path, self.model, self.optim_method
                )
                # rewind the driver-side counters to the checkpoint so
                # triggers/LR schedule/RNG all resume from the same point
                # (the reference re-runs from the checkpoint, not from the
                # crash iteration)
                if "epoch" in extra:
                    self.state["epoch"] = extra["epoch"]
                if "neval" in extra:
                    self.state["neval"] = extra["neval"]
                # a mid-epoch checkpoint (emergency / iteration trigger)
                # resumes `neval - epoch_neval0` batches into the epoch:
                # fast-forward the data iterator that far so the replay
                # stays batch-aligned with the uninterrupted run
                self.state["epoch_neval0"] = extra.get(
                    "epoch_neval0", self.state["neval"])
                self._pending_fast_forward = max(
                    0, self.state["neval"] - self.state["epoch_neval0"])
                # a streaming dataset seeks to the checkpoint's trained
                # offset instead of fast-forwarding an epoch replay —
                # the crashed attempt's records past the checkpoint are
                # re-read and re-trained exactly once
                from bigdl_tpu.resilience import elastic as _elastic

                _elastic.restore_stream(self, extra)
                # goodput: the in-process retry replays every step
                # between the checkpoint and the crash — stamp this
                # attempt's own max step as the rework high-water mark
                obs.get_ledger().stamp_resume(self.state["neval"])
                # re-stamp /healthz with the restored step so the hang
                # watchdog's stall clock restarts at the rewind instead
                # of reading the pre-crash stamp's age
                from bigdl_tpu.obs import server as _obs_server

                if _obs_server.get_server() is not None:
                    _obs_server.note_step(self.state["neval"])
