"""Fused conv + BatchNorm (+ReLU) module and model transform.

TPU-era fusion (no reference analogue — the reference's fusion layer
is the mkldnn backend's ConvBnRelu, SURVEY.md §2.1, deleted by design):
``SpatialConvolutionBatchNorm`` computes a bias-free 1x1 or 3x3
convolution with the BN statistics accumulated in the conv epilogue
(ops/conv_bn.py Pallas kernels), so training-mode BN never re-reads
the activation.  Semantics match ``SpatialConvolution(with_bias=False)
-> SpatialBatchNormalization (-> ReLU)`` exactly: same shifted
single-pass statistics and numerics contract, same running-stat
EMA conventions (layers.py BatchNormalization).

``fuse_conv_bn(model)`` rewrites those triples inside ``Sequential``
containers in place and returns the model; weights are shared (same
arrays), so a fused model stays checkpoint-compatible with its source
architecture's values at fuse time.
"""

from __future__ import annotations


from bigdl_tpu.nn.layers import (
    MsraFiller,
    ReLU,
    SpatialBatchNormalization,
    SpatialConvolution,
    _to_device,
)
from bigdl_tpu.nn.module import AbstractModule, Sequential


def _jnp():
    import jax.numpy as jnp

    return jnp


class SpatialConvolutionBatchNorm(AbstractModule):
    """Fused ``conv (no bias) + SpatialBatchNormalization`` with an
    optional fused ReLU.  Kernel 1 or 3 (torch-style symmetric padding
    ``(k-1)//2``).  Weight layout: (n_output, n_input) for the 1x1 case
    — the kernel as a matrix, kept for checkpoint compatibility — and
    (n_output, n_input, k, k) otherwise."""

    param_names = ("weight", "bn_weight", "bn_bias")
    state_names = ("running_mean", "running_var")

    def __init__(self, n_input_plane: int, n_output_plane: int,
                 stride: int = 1, eps: float = 1e-5,
                 momentum: float = 0.1, with_relu: bool = False,
                 kernel: int = 1):
        super().__init__()
        self._config = dict(
            n_input_plane=n_input_plane, n_output_plane=n_output_plane,
            stride=stride, eps=eps, momentum=momentum, with_relu=with_relu,
            kernel=kernel,
        )
        self.n_input_plane = n_input_plane
        self.n_output_plane = n_output_plane
        self.stride = stride
        self.eps = eps
        self.momentum = momentum
        self.with_relu = with_relu
        self.kernel = kernel
        self.pad = (kernel - 1) // 2
        jnp = _jnp()
        shape = (n_output_plane, n_input_plane) if kernel == 1 \
            else (n_output_plane, n_input_plane, kernel, kernel)
        fan_in = n_input_plane * kernel * kernel
        w = MsraFiller(False).init(shape, fan_in, n_output_plane)
        self.weight = _to_device(w)
        self.bn_weight = jnp.ones(n_output_plane, dtype=jnp.float32)
        self.bn_bias = jnp.zeros(n_output_plane, dtype=jnp.float32)
        self.running_mean = jnp.zeros(n_output_plane, dtype=jnp.float32)
        self.running_var = jnp.ones(n_output_plane, dtype=jnp.float32)

    @classmethod
    def from_pair(cls, conv: SpatialConvolution,
                  bn: SpatialBatchNormalization, with_relu: bool):
        k = conv.kernel_w
        assert conv.kernel_h == k and k in (1, 3)
        assert conv.stride_w == conv.stride_h
        assert conv.pad_w == conv.pad_h == (k - 1) // 2
        assert not conv.with_bias and conv.n_group == 1
        m = cls(conv.n_input_plane, conv.n_output_plane,
                stride=conv.stride_w, eps=bn.eps, momentum=bn.momentum,
                with_relu=with_relu, kernel=k)
        m.weight = conv.weight[:, :, 0, 0] if k == 1 else conv.weight
        if bn.affine:
            m.bn_weight = bn.weight
            m.bn_bias = bn.bias
        m.running_mean = bn.running_mean
        m.running_var = bn.running_var
        if getattr(conv, "_name", None):
            m.set_name(conv._name + "+bn")
        return m

    def _fold(self, params, mean, var, center):
        jnp = _jnp()
        import jax.lax as lax

        inv = lax.rsqrt(var + self.eps)
        scale = inv * params["bn_weight"].astype(jnp.float32)
        offset = params["bn_bias"].astype(jnp.float32) \
            - (mean - center) * scale
        return scale, offset

    def apply(self, params, state, input, *, training=False, rng=None):
        jnp = _jnp()
        import jax.lax as lax

        from bigdl_tpu.ops.conv_bn import conv_bn_stats

        w = params["weight"].astype(input.dtype)
        rm = state["running_mean"]

        def _normalize(y, scale, offset, center):
            dt = y.dtype
            out = (y - center.astype(dt)[None, :, None, None]) \
                * scale.astype(dt)[None, :, None, None] \
                + offset.astype(dt)[None, :, None, None]
            return jnp.maximum(out, 0) if self.with_relu else out

        if not training:
            if self.kernel == 1:
                if self.stride != 1:
                    input = input[:, :, ::self.stride, ::self.stride]
                y = jnp.einsum("oc,nchw->nohw", w, input)
            else:
                y = lax.conv_general_dilated(
                    input, w, (self.stride, self.stride),
                    [(self.pad, self.pad), (self.pad, self.pad)],
                    dimension_numbers=("NCHW", "OIHW", "NCHW"),
                )
            scale, offset = self._fold(
                params, rm, state["running_var"], rm)
            return _normalize(y, scale, offset, rm), state

        # epilogue statistics centered on the loop-carried running mean,
        # straight-line — the same design, numerics contract (exact
        # mean at any shift, geometrically self-healing variance), and
        # chip measurements as BatchNormalization in layers.py: every
        # guarded rescue variant (lax.cond, jnp.where-subsample)
        # measured far slower (2026-07, on another toolchain;
        # scripts/bn_ab.py).
        y, s1, s2 = conv_bn_stats(input, w, rm, stride=self.stride,
                                  pad=self.pad)
        n = y.shape[0] * y.shape[2] * y.shape[3]
        d = s1 / n
        m2 = s2 / n
        mean = rm + d  # exact at any shift
        var = jnp.maximum(m2 - lax.square(d), 0.0)
        scale, offset = self._fold(params, mean, var, rm)
        out = _normalize(y, scale, offset, rm)
        unbiased = var * (n / max(1, n - 1))
        new_state = {
            "running_mean": (1 - self.momentum) * rm + self.momentum * mean,
            "running_var": (1 - self.momentum) * state["running_var"]
            + self.momentum * unbiased,
        }
        return out, new_state

    def __repr__(self):
        tail = " + ReLU" if self.with_relu else ""
        return (f"SpatialConvolutionBatchNorm({self.n_input_plane} -> "
                f"{self.n_output_plane}, {self.kernel}x{self.kernel}"
                f"/{self.stride}{tail})")


def _is_fusable_conv(m):
    # 1x1 and 3x3 torch-padded convs have Pallas epilogue-stats kernels
    # (ops/conv_bn.py; both compile under the installed Mosaic and
    # match the XLA reference on the chip, chip_smoke.py kernels
    # phase); the 7x7 stem stays on XLA's native conv — its C=3 tap
    # dots would starve the MXU
    return (
        isinstance(m, SpatialConvolution)
        and type(m) is SpatialConvolution
        and m.kernel_w == m.kernel_h
        and m.kernel_w in (1, 3)
        and m.stride_w == m.stride_h
        and m.stride_w in (1, 2)
        and m.pad_w == m.pad_h == (m.kernel_w - 1) // 2
        and m.n_group == 1 and not m.with_bias
    )


def fuse_conv_bn(model):
    """Rewrite every ``[1x1/3x3 conv (no bias),
    SpatialBatchNormalization, (ReLU)]`` run inside ``Sequential``
    containers into one ``SpatialConvolutionBatchNorm``, recursively.
    In-place; returns the model."""
    for child in getattr(model, "modules", []):
        fuse_conv_bn(child)
    if isinstance(model, Sequential):
        mods = model.modules
        out = []
        i = 0
        while i < len(mods):
            m = mods[i]
            nxt = mods[i + 1] if i + 1 < len(mods) else None
            if (
                _is_fusable_conv(m)
                and isinstance(nxt, SpatialBatchNormalization)
                and type(nxt) is SpatialBatchNormalization
                and nxt.affine
                and nxt.n_output == m.n_output_plane
            ):
                with_relu = i + 2 < len(mods) and type(mods[i + 2]) is ReLU
                out.append(
                    SpatialConvolutionBatchNorm.from_pair(m, nxt, with_relu)
                )
                i += 3 if with_relu else 2
            else:
                out.append(m)
                i += 1
        model.modules = out
    return model
