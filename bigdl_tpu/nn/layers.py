"""Layer library.

Rebuild of the «bigdl»/nn/ one-file-per-layer library (SURVEY.md §2.1 "Layer
library", ~200-300 layers with hand-derived backwards).  Each class here
implements only the *pure forward* (``update_output_pure`` /  ``apply``);
``updateGradInput``/``accGradParameters`` parity comes from ``jax.vjp`` in
the base class.  Docstrings cite the reference file each layer rebuilds.

TPU notes: convolutions lower to ``lax.conv_general_dilated`` which XLA
tiles onto the MXU; elementwise layers fuse into their producers.  Data
layout follows the reference's NCHW API; XLA's layout assignment re-tiles
for the MXU internally, so no ``MemoryData``/reorder machinery is needed
(SURVEY.md §2.3: the mkldnn layout layer is deleted, not ported).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import numpy as np

from bigdl_tpu.common import RandomGenerator
from bigdl_tpu.nn.module import AbstractModule


def _jnp():
    import jax.numpy as jnp

    return jnp


def _lax():
    import jax.lax as lax

    return lax


# --------------------------------------------------------------------------
# Initialization methods («bigdl»/nn/InitializationMethod.scala)
# --------------------------------------------------------------------------


class InitializationMethod:
    def init(self, shape, fan_in, fan_out):
        raise NotImplementedError


class Zeros(InitializationMethod):
    def init(self, shape, fan_in, fan_out):
        return np.zeros(shape, dtype=np.float32)


class Ones(InitializationMethod):
    def init(self, shape, fan_in, fan_out):
        return np.ones(shape, dtype=np.float32)


class ConstInitMethod(InitializationMethod):
    def __init__(self, value):
        self.value = value

    def init(self, shape, fan_in, fan_out):
        return np.full(shape, self.value, dtype=np.float32)


class RandomUniform(InitializationMethod):
    """Torch-style default: U(-1/sqrt(fanIn), 1/sqrt(fanIn)) when no bounds
    given («bigdl»/nn/InitializationMethod.scala RandomUniform)."""

    def __init__(self, lower=None, upper=None):
        self.lower, self.upper = lower, upper

    def init(self, shape, fan_in, fan_out):
        if self.lower is None:
            stdv = 1.0 / math.sqrt(max(1, fan_in))
            lo, hi = -stdv, stdv
        else:
            lo, hi = self.lower, self.upper
        return RandomGenerator.RNG.uniform(lo, hi, size=shape).astype(np.float32)


class RandomNormal(InitializationMethod):
    def __init__(self, mean=0.0, stdv=1.0):
        self.mean, self.stdv = mean, stdv

    def init(self, shape, fan_in, fan_out):
        return RandomGenerator.RNG.normal(self.mean, self.stdv, size=shape).astype(
            np.float32
        )


class Xavier(InitializationMethod):
    """Glorot uniform («bigdl»/nn/InitializationMethod.scala Xavier) —
    the reference's default for Linear/SpatialConvolution weights."""

    def init(self, shape, fan_in, fan_out):
        limit = math.sqrt(6.0 / (fan_in + fan_out))
        return RandomGenerator.RNG.uniform(-limit, limit, size=shape).astype(
            np.float32
        )


class MsraFiller(InitializationMethod):
    """Kaiming/He init («bigdl»: MsraFiller, used by the ResNet recipe)."""

    def __init__(self, variance_norm_average=True):
        self.avg = variance_norm_average

    def init(self, shape, fan_in, fan_out):
        n = (fan_in + fan_out) / 2.0 if self.avg else fan_in
        std = math.sqrt(2.0 / max(1.0, n))
        return RandomGenerator.RNG.normal(0.0, std, size=shape).astype(np.float32)


def _to_device(x):
    jnp = _jnp()
    return jnp.asarray(x)


# --------------------------------------------------------------------------
# Dense / embedding
# --------------------------------------------------------------------------


class Linear(AbstractModule):
    """«bigdl»/nn/Linear.scala — y = x W^T + b.

    On TPU this is one MXU matmul; keep batch large and let XLA fuse the
    bias add.
    """

    param_names = ("weight", "bias")

    def __init__(
        self,
        input_size: int,
        output_size: int,
        with_bias: bool = True,
        w_regularizer=None,
        b_regularizer=None,
        init_weight=None,
        init_bias=None,
        init_method: Optional[InitializationMethod] = None,
    ):
        super().__init__()
        self._config = dict(
            input_size=input_size, output_size=output_size, with_bias=with_bias
        )
        self.input_size = input_size
        self.output_size = output_size
        self.with_bias = with_bias
        self._init_method = init_method or Xavier()
        self._regularizers = []
        if w_regularizer is not None:
            self._regularizers.append(("weight", w_regularizer))
        if b_regularizer is not None:
            self._regularizers.append(("bias", b_regularizer))
        self.weight = None
        self.bias = None
        self.reset()
        if init_weight is not None:
            self.weight = _to_device(init_weight)
        if init_bias is not None and with_bias:
            self.bias = _to_device(init_bias)

    def reset(self):
        w = self._init_method.init(
            (self.output_size, self.input_size), self.input_size, self.output_size
        )
        self.weight = _to_device(w)
        if self.with_bias:
            self.bias = _to_device(np.zeros(self.output_size, dtype=np.float32))
        return self

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        y = jnp.matmul(input, params["weight"].T)
        if self.with_bias:
            y = y + params["bias"]
        return y

    def __repr__(self):
        return f"Linear({self.input_size} -> {self.output_size})"


class LookupTable(AbstractModule):
    """«bigdl»/nn/LookupTable.scala — embedding lookup.

    Reference semantics: indices are **1-based**; optional ``paddingValue``
    rows stay zero; optional ``maxNorm`` renormalises looked-up rows.
    """

    param_names = ("weight",)

    def __init__(
        self,
        n_index: int,
        n_output: int,
        padding_value: float = 0.0,
        max_norm: float = float("inf"),
        norm_type: float = 2.0,
        w_regularizer=None,
    ):
        super().__init__()
        self._config = dict(
            n_index=n_index, n_output=n_output, padding_value=padding_value
        )
        self.n_index = n_index
        self.n_output = n_output
        self.padding_value = padding_value
        self.max_norm = max_norm
        self.norm_type = norm_type
        self._regularizers = (
            [("weight", w_regularizer)] if w_regularizer is not None else []
        )
        self.weight = None
        self.reset()

    def reset(self):
        w = RandomGenerator.RNG.normal(
            0.0, 1.0, size=(self.n_index, self.n_output)
        ).astype(np.float32)
        if self.padding_value > 0:
            w[int(self.padding_value) - 1] = 0.0
        self.weight = _to_device(w)
        return self

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        idx = input.astype(jnp.int32) - 1  # reference is 1-based
        w = params["weight"]
        if self.max_norm != float("inf"):
            norms = jnp.linalg.norm(w, ord=self.norm_type, axis=1, keepdims=True)
            w = w * jnp.minimum(1.0, self.max_norm / (norms + 1e-7))
        return jnp.take(w, idx, axis=0)

    def __repr__(self):
        return f"LookupTable({self.n_index}, {self.n_output})"


# --------------------------------------------------------------------------
# Convolutions
# --------------------------------------------------------------------------


def _auto_batch(x, full_ndim):
    if x.ndim == full_ndim - 1:
        return x[None], True
    return x, False


def _conv_pads(pad_h, pad_w, kh, kw, dh, dw):
    """Reference: pad == -1 means TF-style SAME («bigdl»/nn/
    SpatialConvolution.scala)."""
    if pad_h == -1 or pad_w == -1:
        return "SAME"
    return [(pad_h, pad_h), (pad_w, pad_w)]


class SpatialConvolution(AbstractModule):
    """«bigdl»/nn/SpatialConvolution.scala — 2-D conv over NCHW input.

    Reference arg order is width-first (kW, kH, dW, dH, padW, padH), kept
    here.  ``n_group`` maps to ``feature_group_count``.  The reference's
    im2col + MKL gemm path (SURVEY.md §3.3 native boundary) is replaced by
    one ``lax.conv_general_dilated`` that XLA maps onto the MXU directly.
    """

    param_names = ("weight", "bias")

    def __init__(
        self,
        n_input_plane: int,
        n_output_plane: int,
        kernel_w: int,
        kernel_h: int,
        stride_w: int = 1,
        stride_h: int = 1,
        pad_w: int = 0,
        pad_h: int = 0,
        n_group: int = 1,
        with_bias: bool = True,
        w_regularizer=None,
        b_regularizer=None,
        init_method: Optional[InitializationMethod] = None,
    ):
        super().__init__()
        self._config = dict(
            n_input_plane=n_input_plane,
            n_output_plane=n_output_plane,
            kernel_w=kernel_w,
            kernel_h=kernel_h,
            stride_w=stride_w,
            stride_h=stride_h,
            pad_w=pad_w,
            pad_h=pad_h,
            n_group=n_group,
            with_bias=with_bias,
        )
        self.n_input_plane = n_input_plane
        self.n_output_plane = n_output_plane
        self.kernel_w, self.kernel_h = kernel_w, kernel_h
        self.stride_w, self.stride_h = stride_w, stride_h
        self.pad_w, self.pad_h = pad_w, pad_h
        self.n_group = n_group
        self.with_bias = with_bias
        self._init_method = init_method or MsraFiller(False)
        self._regularizers = []
        if w_regularizer is not None:
            self._regularizers.append(("weight", w_regularizer))
        if b_regularizer is not None:
            self._regularizers.append(("bias", b_regularizer))
        self.weight = None
        self.bias = None
        self.reset()

    def reset(self):
        fan_in = self.n_input_plane // self.n_group * self.kernel_h * self.kernel_w
        fan_out = self.n_output_plane // self.n_group * self.kernel_h * self.kernel_w
        w = self._init_method.init(
            (
                self.n_output_plane,
                self.n_input_plane // self.n_group,
                self.kernel_h,
                self.kernel_w,
            ),
            fan_in,
            fan_out,
        )
        self.weight = _to_device(w)
        if self.with_bias:
            self.bias = _to_device(
                np.zeros(self.n_output_plane, dtype=np.float32)
            )
        return self

    def update_output_pure(self, params, input, *, training=False, rng=None):
        lax = _lax()
        x, squeezed = _auto_batch(input, 4)
        y = lax.conv_general_dilated(
            x,
            params["weight"],
            window_strides=(self.stride_h, self.stride_w),
            padding=_conv_pads(
                self.pad_h,
                self.pad_w,
                self.kernel_h,
                self.kernel_w,
                self.stride_h,
                self.stride_w,
            ),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=self.n_group,
        )
        if self.with_bias:
            y = y + params["bias"][None, :, None, None]
        return y[0] if squeezed else y

    def __repr__(self):
        return (
            f"SpatialConvolution({self.n_input_plane} -> {self.n_output_plane}, "
            f"{self.kernel_w}x{self.kernel_h}, {self.stride_w},{self.stride_h}, "
            f"{self.pad_w},{self.pad_h})"
        )


class SpatialDilatedConvolution(SpatialConvolution):
    """«bigdl»/nn/SpatialDilatedConvolution.scala"""

    def __init__(
        self,
        n_input_plane,
        n_output_plane,
        kernel_w,
        kernel_h,
        stride_w=1,
        stride_h=1,
        pad_w=0,
        pad_h=0,
        dilation_w=1,
        dilation_h=1,
        **kw,
    ):
        self.dilation_w, self.dilation_h = dilation_w, dilation_h
        super().__init__(
            n_input_plane,
            n_output_plane,
            kernel_w,
            kernel_h,
            stride_w,
            stride_h,
            pad_w,
            pad_h,
            **kw,
        )
        self._config.update(dilation_w=dilation_w, dilation_h=dilation_h)

    def update_output_pure(self, params, input, *, training=False, rng=None):
        lax = _lax()
        x, squeezed = _auto_batch(input, 4)
        y = lax.conv_general_dilated(
            x,
            params["weight"],
            window_strides=(self.stride_h, self.stride_w),
            padding=[(self.pad_h, self.pad_h), (self.pad_w, self.pad_w)],
            rhs_dilation=(self.dilation_h, self.dilation_w),
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            feature_group_count=self.n_group,
        )
        if self.with_bias:
            y = y + params["bias"][None, :, None, None]
        return y[0] if squeezed else y


class SpatialFullConvolution(AbstractModule):
    """«bigdl»/nn/SpatialFullConvolution.scala — transposed conv
    (deconvolution).  out = (in-1)*stride - 2*pad + kernel + adj."""

    param_names = ("weight", "bias")

    def __init__(
        self,
        n_input_plane,
        n_output_plane,
        kernel_w,
        kernel_h,
        stride_w=1,
        stride_h=1,
        pad_w=0,
        pad_h=0,
        adj_w=0,
        adj_h=0,
        n_group=1,
        with_bias=True,
        init_method: Optional[InitializationMethod] = None,
    ):
        super().__init__()
        self._config = dict(
            n_input_plane=n_input_plane,
            n_output_plane=n_output_plane,
            kernel_w=kernel_w,
            kernel_h=kernel_h,
            stride_w=stride_w,
            stride_h=stride_h,
            pad_w=pad_w,
            pad_h=pad_h,
            adj_w=adj_w,
            adj_h=adj_h,
            n_group=n_group,
            with_bias=with_bias,
        )
        self.n_input_plane = n_input_plane
        self.n_output_plane = n_output_plane
        self.kernel_w, self.kernel_h = kernel_w, kernel_h
        self.stride_w, self.stride_h = stride_w, stride_h
        self.pad_w, self.pad_h = pad_w, pad_h
        self.adj_w, self.adj_h = adj_w, adj_h
        self.n_group = n_group
        self.with_bias = with_bias
        self._init_method = init_method or MsraFiller(False)
        self.weight = None
        self.bias = None
        self.reset()

    def reset(self):
        fan_in = self.n_input_plane * self.kernel_h * self.kernel_w
        fan_out = self.n_output_plane * self.kernel_h * self.kernel_w
        # stored as (out, in/group, kh, kw) so the transposed pass below can
        # run as a regular conv with lhs dilation + flipped kernel
        w = self._init_method.init(
            (
                self.n_output_plane,
                self.n_input_plane // self.n_group,
                self.kernel_h,
                self.kernel_w,
            ),
            fan_in,
            fan_out,
        )
        self.weight = _to_device(w)
        if self.with_bias:
            self.bias = _to_device(np.zeros(self.n_output_plane, dtype=np.float32))
        return self

    def update_output_pure(self, params, input, *, training=False, rng=None):
        lax = _lax()
        jnp = _jnp()
        x, squeezed = _auto_batch(input, 4)
        # transposed conv == conv with input dilation, flipped kernel, and
        # swapped in/out channel roles
        w = params["weight"]  # (out, in/g, kh, kw)
        w = jnp.flip(w, axis=(-2, -1))
        w = jnp.swapaxes(w, 0, 1)  # (in/g, out, kh, kw) -> conv 'IOHW'
        y = lax.conv_general_dilated(
            x,
            w,
            window_strides=(1, 1),
            padding=[
                (
                    self.kernel_h - 1 - self.pad_h,
                    self.kernel_h - 1 - self.pad_h + self.adj_h,
                ),
                (
                    self.kernel_w - 1 - self.pad_w,
                    self.kernel_w - 1 - self.pad_w + self.adj_w,
                ),
            ],
            lhs_dilation=(self.stride_h, self.stride_w),
            dimension_numbers=("NCHW", "IOHW", "NCHW"),
            feature_group_count=self.n_group,
        )
        if self.with_bias:
            y = y + params["bias"][None, :, None, None]
        return y[0] if squeezed else y


class TemporalConvolution(AbstractModule):
    """«bigdl»/nn/TemporalConvolution.scala — 1-D conv over (N, T, C_in)
    frames (the text-classification CNN path)."""

    param_names = ("weight", "bias")

    def __init__(
        self,
        input_frame_size,
        output_frame_size,
        kernel_w,
        stride_w=1,
        with_bias=True,
        init_method=None,
    ):
        super().__init__()
        self._config = dict(
            input_frame_size=input_frame_size,
            output_frame_size=output_frame_size,
            kernel_w=kernel_w,
            stride_w=stride_w,
        )
        self.input_frame_size = input_frame_size
        self.output_frame_size = output_frame_size
        self.kernel_w = kernel_w
        self.stride_w = stride_w
        self.with_bias = with_bias
        self._init_method = init_method or Xavier()
        self.reset()

    def reset(self):
        fan_in = self.input_frame_size * self.kernel_w
        fan_out = self.output_frame_size * self.kernel_w
        self.weight = _to_device(
            self._init_method.init(
                (self.output_frame_size, self.input_frame_size, self.kernel_w),
                fan_in,
                fan_out,
            )
        )
        self.bias = (
            _to_device(np.zeros(self.output_frame_size, dtype=np.float32))
            if self.with_bias
            else None
        )
        return self

    def update_output_pure(self, params, input, *, training=False, rng=None):
        lax = _lax()
        x, squeezed = _auto_batch(input, 3)
        y = lax.conv_general_dilated(
            x,
            params["weight"],
            window_strides=(self.stride_w,),
            padding=[(0, 0)],
            dimension_numbers=("NWC", "OIW", "NWC"),
        )
        if self.with_bias:
            y = y + params["bias"]
        return y[0] if squeezed else y


# --------------------------------------------------------------------------
# Pooling
# --------------------------------------------------------------------------


def _pool_pad(in_size, k, s, pad, ceil_mode):
    """Output size + (lo, hi) padding for one spatial dim, honoring the
    reference's floor/ceil mode («bigdl»/nn/SpatialMaxPooling.scala).
    pad == -1 means TF-style SAME (matching the conv convention)."""
    if pad == -1:
        out = -(-in_size // s)
        needed = max(0, (out - 1) * s + k - in_size)
        lo = needed // 2
        return out, (lo, needed - lo)
    if ceil_mode:
        out = int(math.ceil((in_size + 2 * pad - k) / s)) + 1
    else:
        out = int(math.floor((in_size + 2 * pad - k) / s)) + 1
    if pad > 0 or ceil_mode:
        # reference guard: last window must start inside the padded input
        if (out - 1) * s >= in_size + pad:
            out -= 1
    needed = max(0, (out - 1) * s + k - in_size - pad)
    return out, (pad, needed)


class SpatialMaxPooling(AbstractModule):
    """«bigdl»/nn/SpatialMaxPooling.scala (NCHW; width-first args;
    ``ceil()`` switches to ceil mode)."""

    def __init__(self, kw, kh, dw=None, dh=None, pad_w=0, pad_h=0,
                 ceil_mode=False):
        super().__init__()
        self.kw, self.kh = kw, kh
        self.dw = dw if dw is not None else kw
        self.dh = dh if dh is not None else kh
        self.pad_w, self.pad_h = pad_w, pad_h
        self.ceil_mode = ceil_mode
        self._config = dict(
            kw=kw, kh=kh, dw=self.dw, dh=self.dh, pad_w=pad_w, pad_h=pad_h,
            ceil_mode=ceil_mode,
        )

    def ceil(self):
        self.ceil_mode = True
        self._config["ceil_mode"] = True
        return self

    def floor(self):
        self.ceil_mode = False
        self._config["ceil_mode"] = False
        return self

    def update_output_pure(self, params, input, *, training=False, rng=None):
        lax = _lax()
        jnp = _jnp()
        x, squeezed = _auto_batch(input, 4)
        h, w = x.shape[2], x.shape[3]
        _, ph = _pool_pad(h, self.kh, self.dh, self.pad_h, self.ceil_mode)
        _, pw = _pool_pad(w, self.kw, self.dw, self.pad_w, self.ceil_mode)
        y = lax.reduce_window(
            x,
            -jnp.inf,
            lax.max,
            window_dimensions=(1, 1, self.kh, self.kw),
            window_strides=(1, 1, self.dh, self.dw),
            padding=[(0, 0), (0, 0), ph, pw],
        )
        return y[0] if squeezed else y

    def __repr__(self):
        return f"SpatialMaxPooling({self.kw}x{self.kh}, {self.dw},{self.dh})"


class SpatialAveragePooling(AbstractModule):
    """«bigdl»/nn/SpatialAveragePooling.scala — default counts padded
    cells in the divisor (countIncludePad=true), like the reference."""

    def __init__(
        self,
        kw,
        kh,
        dw=1,
        dh=1,
        pad_w=0,
        pad_h=0,
        global_pooling=False,
        ceil_mode=False,
        count_include_pad=True,
        divide=True,
    ):
        super().__init__()
        self.kw, self.kh, self.dw, self.dh = kw, kh, dw, dh
        self.pad_w, self.pad_h = pad_w, pad_h
        self.global_pooling = global_pooling
        self.ceil_mode = ceil_mode
        self.count_include_pad = count_include_pad
        self.divide = divide
        self._config = dict(
            kw=kw, kh=kh, dw=dw, dh=dh, pad_w=pad_w, pad_h=pad_h,
            global_pooling=global_pooling, ceil_mode=ceil_mode,
            count_include_pad=count_include_pad, divide=divide,
        )

    def ceil(self):
        self.ceil_mode = True
        self._config["ceil_mode"] = True
        return self

    def update_output_pure(self, params, input, *, training=False, rng=None):
        lax = _lax()
        jnp = _jnp()
        x, squeezed = _auto_batch(input, 4)
        kh, kw = self.kh, self.kw
        if self.global_pooling:
            kh, kw = x.shape[2], x.shape[3]
        h, w = x.shape[2], x.shape[3]
        _, ph = _pool_pad(h, kh, self.dh, self.pad_h, self.ceil_mode)
        _, pw = _pool_pad(w, kw, self.dw, self.pad_w, self.ceil_mode)
        summed = lax.reduce_window(
            x,
            0.0,
            lax.add,
            window_dimensions=(1, 1, kh, kw),
            window_strides=(1, 1, self.dh, self.dw),
            padding=[(0, 0), (0, 0), ph, pw],
        )
        if not self.divide:
            y = summed
        elif self.count_include_pad:
            y = summed / (kh * kw)
        else:
            ones = jnp.ones_like(x)
            counts = lax.reduce_window(
                ones,
                0.0,
                lax.add,
                window_dimensions=(1, 1, kh, kw),
                window_strides=(1, 1, self.dh, self.dw),
                padding=[(0, 0), (0, 0), ph, pw],
            )
            y = summed / counts
        return y[0] if squeezed else y


# --------------------------------------------------------------------------
# Activations (all stateless; fuse into producers under XLA)
# --------------------------------------------------------------------------


class _Elementwise(AbstractModule):
    def __init__(self, **config):
        super().__init__()
        self._config = config

    def __repr__(self):
        return type(self).__name__


class ReLU(_Elementwise):
    """«bigdl»/nn/ReLU.scala (ip=true in-place flag is a no-op here: XLA
    fuses, there is no buffer to save)."""

    def __init__(self, ip: bool = False):
        super().__init__()

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().maximum(input, 0)


class ReLU6(_Elementwise):
    """«bigdl»/nn/ReLU6.scala"""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().clip(input, 0, 6)


class Tanh(_Elementwise):
    """«bigdl»/nn/Tanh.scala"""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().tanh(input)


class Sigmoid(_Elementwise):
    """«bigdl»/nn/Sigmoid.scala"""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        import jax

        return jax.nn.sigmoid(input)


class LogSoftMax(_Elementwise):
    """«bigdl»/nn/LogSoftMax.scala — over the last dim (class dim)."""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        import jax

        return jax.nn.log_softmax(input, axis=-1)


class SoftMax(_Elementwise):
    """«bigdl»/nn/SoftMax.scala"""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        import jax

        return jax.nn.softmax(input, axis=-1)


class SoftMin(_Elementwise):
    """«bigdl»/nn/SoftMin.scala"""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        import jax

        return jax.nn.softmax(-input, axis=-1)


class SoftPlus(_Elementwise):
    """«bigdl»/nn/SoftPlus.scala (beta param)"""

    def __init__(self, beta: float = 1.0):
        super().__init__(beta=beta)
        self.beta = beta

    def update_output_pure(self, params, input, *, training=False, rng=None):
        import jax

        return jax.nn.softplus(self.beta * input) / self.beta


class SoftSign(_Elementwise):
    """«bigdl»/nn/SoftSign.scala"""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        return input / (1 + jnp.abs(input))


class ELU(_Elementwise):
    """«bigdl»/nn/ELU.scala"""

    def __init__(self, alpha: float = 1.0, inplace: bool = False):
        super().__init__(alpha=alpha)
        self.alpha = alpha

    def update_output_pure(self, params, input, *, training=False, rng=None):
        import jax

        return jax.nn.elu(input, alpha=self.alpha)


class LeakyReLU(_Elementwise):
    """«bigdl»/nn/LeakyReLU.scala"""

    def __init__(self, negval: float = 0.01, inplace: bool = False):
        super().__init__(negval=negval)
        self.negval = negval

    def update_output_pure(self, params, input, *, training=False, rng=None):
        import jax

        return jax.nn.leaky_relu(input, negative_slope=self.negval)


class HardTanh(_Elementwise):
    """«bigdl»/nn/HardTanh.scala"""

    def __init__(self, min_value: float = -1.0, max_value: float = 1.0, inplace=False):
        super().__init__(min_value=min_value, max_value=max_value)
        self.min_value, self.max_value = min_value, max_value

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().clip(input, self.min_value, self.max_value)


class HardSigmoid(_Elementwise):
    """«bigdl»/nn/HardSigmoid.scala — clip(0.2x + 0.5, 0, 1)"""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().clip(0.2 * input + 0.5, 0.0, 1.0)


class Clamp(HardTanh):
    """«bigdl»/nn/Clamp.scala"""

    def __init__(self, min_value, max_value):
        super().__init__(min_value, max_value)


class Threshold(_Elementwise):
    """«bigdl»/nn/Threshold.scala — x if x > th else value"""

    def __init__(self, th: float = 1e-6, v: float = 0.0, ip: bool = False):
        super().__init__(th=th, v=v)
        self.th, self.v = th, v

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        return jnp.where(input > self.th, input, self.v)


class PReLU(AbstractModule):
    """«bigdl»/nn/PReLU.scala — learnable negative slope (shared or
    per-channel)."""

    param_names = ("weight",)

    def __init__(self, n_output_plane: int = 0):
        super().__init__()
        self._config = dict(n_output_plane=n_output_plane)
        self.n_output_plane = n_output_plane
        n = max(1, n_output_plane)
        self.weight = _to_device(np.full(n, 0.25, dtype=np.float32))

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        w = params["weight"]
        if self.n_output_plane > 0 and input.ndim >= 3:
            # per-channel over NCHW / CHW
            shape = [1] * input.ndim
            shape[-3] = self.n_output_plane
            w = w.reshape(shape)
        return jnp.where(input > 0, input, w * input)


class GELU(_Elementwise):
    """TPU-era addition (not in the 0.x reference; used by modern recipes)."""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        import jax

        return jax.nn.gelu(input)


class SELU(_Elementwise):
    """«bigdl»/nn/SELU.scala — scaled exponential linear unit (fixed
    lambda/alpha from Klambauer et al.)."""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        import jax

        return jax.nn.selu(input)


# --------------------------------------------------------------------------
# Elementwise math layers
# --------------------------------------------------------------------------


class Abs(_Elementwise):
    """«bigdl»/nn/Abs.scala"""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().abs(input)


class Square(_Elementwise):
    """«bigdl»/nn/Square.scala"""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return input * input


class Sqrt(_Elementwise):
    """«bigdl»/nn/Sqrt.scala"""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().sqrt(input)


class Power(_Elementwise):
    """«bigdl»/nn/Power.scala — (shift + scale*x)^power"""

    def __init__(self, power, scale=1.0, shift=0.0):
        super().__init__(power=power, scale=scale, shift=shift)
        self.power, self.scale, self.shift = power, scale, shift

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return (self.shift + self.scale * input) ** self.power


class Log(_Elementwise):
    """«bigdl»/nn/Log.scala"""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().log(input)


class Exp(_Elementwise):
    """«bigdl»/nn/Exp.scala"""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().exp(input)


class Negative(_Elementwise):
    """«bigdl»/nn/Negative.scala"""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return -input


class Floor(_Elementwise):
    """TF-interop vocabulary («bigdl»/utils/tf/loaders/Floor.scala)."""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().floor(input)


class Ceil(_Elementwise):
    """TF-interop vocabulary («bigdl»/utils/tf/loaders/Ceil.scala)."""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().ceil(input)


class Round(_Elementwise):
    """TF-interop vocabulary («bigdl»/utils/tf/loaders/Round.scala)."""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().round(input)


class Sign(_Elementwise):
    """TF-interop vocabulary («bigdl»/utils/tf/loaders/Sign.scala)."""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().sign(input)


class Log1p(_Elementwise):
    """«bigdl»/nn/Log1p — numerically stable log(1 + x)."""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().log1p(input)


class Expm1(_Elementwise):
    """TF-interop vocabulary — numerically stable exp(x) - 1."""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().expm1(input)


class Erf(_Elementwise):
    """TF-interop vocabulary («bigdl»/utils/tf/loaders/Erf.scala)."""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        import jax

        return jax.scipy.special.erf(input)


class Sin(_Elementwise):
    """TF-interop vocabulary («bigdl»/utils/tf/loaders/Sin.scala)."""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().sin(input)


class Cos(_Elementwise):
    """TF-interop vocabulary («bigdl»/utils/tf/loaders/Cos.scala)."""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().cos(input)


class ArgMax(_Elementwise):
    """TF-interop vocabulary («bigdl»/utils/tf/loaders/ArgMax.scala).

    Returns float32 indices along ``dim`` (1-based, counting the batch
    axis, matching :class:`Max`'s convention).  Non-differentiable: the
    integer argmax carries no tangent, so gradients through it are zero.
    """

    def __init__(self, dim=1):
        super().__init__(dim=dim)
        self.dim = dim

    def update_output_pure(self, params, input, *, training=False, rng=None):
        axis = self.dim - 1 if self.dim > 0 else self.dim
        return _jnp().argmax(input, axis=axis).astype("float32")


class AddConstant(_Elementwise):
    """«bigdl»/nn/AddConstant.scala"""

    def __init__(self, constant_scalar, inplace=False):
        super().__init__(constant_scalar=constant_scalar)
        self.constant_scalar = constant_scalar

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return input + self.constant_scalar


class DivConstant(_Elementwise):
    """TF-interop vocabulary — exact ``x / constant``.

    FloorDiv lowering needs true division: multiplying by a rounded
    reciprocal is off by one ulp at exact multiples, which Floor
    amplifies into an off-by-one result.
    """

    def __init__(self, constant_scalar):
        super().__init__(constant_scalar=constant_scalar)
        self.constant_scalar = constant_scalar

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return input / self.constant_scalar


class MulConstant(_Elementwise):
    """«bigdl»/nn/MulConstant.scala"""

    def __init__(self, scalar, inplace=False):
        super().__init__(scalar=scalar)
        self.scalar = scalar

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return input * self.scalar


# --------------------------------------------------------------------------
# Learnable elementwise layers
# --------------------------------------------------------------------------


class CMul(AbstractModule):
    """«bigdl»/nn/CMul.scala — learnable broadcast multiply."""

    param_names = ("weight",)

    def __init__(self, size: Sequence[int]):
        super().__init__()
        self._config = dict(size=list(size))
        self.size = tuple(size)
        self.weight = _to_device(np.ones(self.size, dtype=np.float32))

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return input * params["weight"]


class CAdd(AbstractModule):
    """«bigdl»/nn/CAdd.scala — learnable broadcast add."""

    param_names = ("bias",)

    def __init__(self, size: Sequence[int]):
        super().__init__()
        self._config = dict(size=list(size))
        self.size = tuple(size)
        self.bias = _to_device(np.zeros(self.size, dtype=np.float32))

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return input + params["bias"]


class Add(AbstractModule):
    """«bigdl»/nn/Add.scala — learnable bias over last dim."""

    param_names = ("bias",)

    def __init__(self, input_size: int):
        super().__init__()
        self._config = dict(input_size=input_size)
        self.bias = _to_device(np.zeros(input_size, dtype=np.float32))

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return input + params["bias"]


class Mul(AbstractModule):
    """«bigdl»/nn/Mul.scala — single learnable scalar multiplier."""

    param_names = ("weight",)

    def __init__(self):
        super().__init__()
        self.weight = _to_device(
            RandomGenerator.RNG.uniform(-1, 1, size=(1,)).astype(np.float32)
        )

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return input * params["weight"][0]


class Scale(AbstractModule):
    """«bigdl»/nn/Scale.scala — CMul then CAdd."""

    param_names = ("weight", "bias")

    def __init__(self, size: Sequence[int]):
        super().__init__()
        self._config = dict(size=list(size))
        self.size = tuple(size)
        self.weight = _to_device(np.ones(self.size, dtype=np.float32))
        self.bias = _to_device(np.zeros(self.size, dtype=np.float32))

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return input * params["weight"] + params["bias"]


# --------------------------------------------------------------------------
# Normalization
# --------------------------------------------------------------------------


class BatchNormalization(AbstractModule):
    """«bigdl»/nn/BatchNormalization.scala — over (N, C) input.

    Reference conventions kept: eps=1e-5, momentum=0.1, running stats
    updated as (1-momentum)*running + momentum*batch, running variance
    stored unbiased, batch normalisation uses biased variance; training
    mode uses batch stats, evaluate mode uses running stats.

    Momentum-warmup caveat (single-pass shifted statistics): training
    stats are computed in one pass shifted by the RUNNING mean, and
    the r05 A/B hunt removed every in-step rescue for a stale shift
    (each was measured slower on chip — see the ``apply`` comment and
    scripts/bn_ab.py).  So for roughly the first 1/momentum training
    steps (~10 at the default 0.1), while ``running_mean`` is still
    cold (zeros) on heavily un-normalized input, the batch variance
    ``m2 - d^2`` cancels digits and the normalized output can be
    mis-scaled.  The running mean converges geometrically at the
    momentum rate and the variance self-heals within
    ``~log(d^2/var)/(2*momentum)`` steps; the batch MEAN is exact at
    any shift, so only the scale (not the centering) wobbles during
    warmup.  If the input distribution is pathological (|E[x]| more
    than ~64 batch-stds from 0), normalize the data or warm the
    running stats instead of expecting the first steps' outputs to be
    unit-variance.
    """

    param_names = ("weight", "bias")
    state_names = ("running_mean", "running_var")

    # which axes are reduced over; subclass overrides
    _feature_ndim = 2

    def __init__(
        self,
        n_output: int,
        eps: float = 1e-5,
        momentum: float = 0.1,
        affine: bool = True,
        init_weight=None,
        init_bias=None,
    ):
        super().__init__()
        self._config = dict(
            n_output=n_output, eps=eps, momentum=momentum, affine=affine
        )
        self.n_output = n_output
        self.eps = eps
        self.momentum = momentum
        self.affine = affine
        jnp = _jnp()
        if affine:
            self.weight = (
                _to_device(init_weight)
                if init_weight is not None
                else jnp.ones(n_output, dtype=jnp.float32)
            )
            self.bias = (
                _to_device(init_bias)
                if init_bias is not None
                else jnp.zeros(n_output, dtype=jnp.float32)
            )
        else:
            self.weight = None
            self.bias = None
        self.running_mean = jnp.zeros(n_output, dtype=jnp.float32)
        self.running_var = jnp.ones(n_output, dtype=jnp.float32)

    def _axes_and_shape(self, input):
        if input.ndim == self._feature_ndim:  # batched
            if self._feature_ndim == 2:
                return (0,), (1, self.n_output)
            return (0, 2, 3), (1, self.n_output, 1, 1)
        raise ValueError(
            f"{type(self).__name__} expects {self._feature_ndim}-d input, "
            f"got {input.ndim}-d"
        )

    def _fold(self, params, mean, var, center):
        """Fold (mean, var, weight, bias) into per-channel f32
        (scale, offset) for the CENTERED normalize
        ``y = (x - center) * scale + offset``.

        Centering keeps full precision at any activation magnitude: the
        uncentered ``x*scale + offset`` form loses ~mean/std * 2^-24 of
        the output to f32 rounding of the large ``x*scale`` product,
        while here the big terms cancel before scaling.  ``center`` is
        whatever per-channel vector is cheaply available — the stats
        mean itself (exact), or the running mean (off by the tiny
        shifted-mean d, equally good)."""
        jnp = _jnp()
        lax = _lax()
        inv = lax.rsqrt(var + self.eps)
        if self.affine:
            scale = inv * params["weight"].astype(jnp.float32)
            offset = params["bias"].astype(jnp.float32) \
                - (mean - center) * scale
        else:
            scale = inv
            offset = -(mean - center) * scale
        return scale, offset

    def apply(self, params, state, input, *, training=False, rng=None):
        jnp = _jnp()
        lax = _lax()
        axes, bshape = self._axes_and_shape(input)

        def _normalize(scale, offset, center):
            # elementwise pass in the INPUT dtype: under a bf16 compute
            # policy it runs at half the HBM bytes (measured ~4% of a
            # ResNet-50 step, scripts/perf_probe.py), and no full-tensor
            # f32 copy of the input is ever materialized.  The centered
            # subtract is exact-ish at any magnitude (nearby values),
            # so low-precision here costs only the input's own ulp.
            dt = input.dtype
            return (input - center.astype(dt).reshape(bshape)) \
                * scale.astype(dt).reshape(bshape) \
                + offset.astype(dt).reshape(bshape)

        if not training:
            rm = state["running_mean"]
            scale, offset = self._fold(
                params, rm, state["running_var"], rm
            )
            return _normalize(scale, offset, rm), state

        # statistics always accumulate in f32: under a bf16 compute
        # policy the batch reductions would otherwise lose ~3 decimal
        # digits and drift the running stats
        xf = input.astype(jnp.float32)
        # BN is the bandwidth tax of conv nets on TPU (BASELINE.md):
        # naive mean-then-var reads the activation twice.  Shifted
        # single-pass stats read it once — E[x-s] and E[(x-s)^2] are
        # two reductions over the same fused operand, with s = the
        # running mean.  The shift MUST be loop-carried, not derived
        # from the batch: any data-derived s puts a reduction barrier
        # between the producing op and the stats pass, forcing an
        # extra HBM read of the activation (chip A/B at b128,
        # scripts/bn_ab.py: rm-shift 50.1 ms/step, single-pixel shift
        # 53.4, sample-0-mean shift 64.5, naive two-pass 57.8).
        #
        # Numerics contract: m2 - d^2 loses digits when the shift is
        # very stale (|E[x] - rm| > ~64 batch-stds: cold running_mean
        # on extremely un-normalized input).  Because mean = rm + d is
        # EXACT at any shift, the running mean converges geometrically
        # at the momentum rate and the variance self-heals within
        # ~log(d^2/var)/(2*momentum) steps — and this form is strictly
        # more accurate than the uncentered E[x^2]-E[x]^2 single-pass
        # that flax/haiku ship (their s = 0 is the worst case of ours).
        # Every guarded alternative was measured SLOWER on chip
        # (scripts/bn_ab.py variant names, b128 ms/step): nocond 50.1,
        # where (jnp.where subsample rescue) 85.5, s0 (sample-0-mean
        # shift) 64.5, cond (lax.cond rescue) 89.8-at-b32-scale + OOM
        # at b64+, twopass 57.8.
        # (Measured 2026-07 on another toolchain.)  That XLA wanted BN
        # as one straight-line dependency chain; anything else
        # defeated fusion/scheduling.
        rm = state["running_mean"]
        xc = xf - rm.reshape(bshape)
        d = jnp.mean(xc, axis=axes)
        m2 = jnp.mean(lax.square(xc), axis=axes)
        mean = rm + d  # exact at any shift
        var = jnp.maximum(m2 - lax.square(d), 0.0)  # biased
        scale, offset = self._fold(params, mean, var, rm)
        y = _normalize(scale, offset, rm)
        n = 1
        for a in axes:
            n *= input.shape[a]
        unbiased = var * (n / max(1, n - 1))
        new_state = {
            "running_mean": (1 - self.momentum) * state["running_mean"]
            + self.momentum * mean,
            "running_var": (1 - self.momentum) * state["running_var"]
            + self.momentum * unbiased,
        }
        return y, new_state

    def __repr__(self):
        return f"{type(self).__name__}({self.n_output})"


class SpatialBatchNormalization(BatchNormalization):
    """«bigdl»/nn/SpatialBatchNormalization.scala — NCHW input, stats per
    channel."""

    _feature_ndim = 4


class Normalize(_Elementwise):
    """«bigdl»/nn/Normalize.scala — Lp-normalise along dim 1."""

    def __init__(self, p: float = 2.0, eps: float = 1e-10):
        super().__init__(p=p, eps=eps)
        self.p, self.eps = p, eps

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        if self.p == float("inf"):
            norm = jnp.max(jnp.abs(input), axis=1, keepdims=True)
        else:
            norm = jnp.sum(jnp.abs(input) ** self.p, axis=1, keepdims=True) ** (
                1.0 / self.p
            )
        return input / (norm + self.eps)


class SpatialCrossMapLRN(_Elementwise):
    """«bigdl»/nn/SpatialCrossMapLRN.scala — AlexNet/Inception local
    response normalisation across channels:
    out = in * (k + alpha/size * sum_window in^2)^(-beta)."""

    def __init__(self, size=5, alpha=1.0, beta=0.75, k=1.0):
        super().__init__(size=size, alpha=alpha, beta=beta, k=k)
        self.size, self.alpha, self.beta, self.k = size, alpha, beta, k

    def update_output_pure(self, params, input, *, training=False, rng=None):
        lax = _lax()
        x, squeezed = _auto_batch(input, 4)
        sq = x * x
        half = (self.size - 1) // 2
        summed = lax.reduce_window(
            sq,
            0.0,
            lax.add,
            window_dimensions=(1, self.size, 1, 1),
            window_strides=(1, 1, 1, 1),
            padding=[(0, 0), (half, self.size - 1 - half), (0, 0), (0, 0)],
        )
        y = x * (self.k + self.alpha / self.size * summed) ** (-self.beta)
        return y[0] if squeezed else y


# --------------------------------------------------------------------------
# Dropout
# --------------------------------------------------------------------------


class Dropout(AbstractModule):
    """«bigdl»/nn/Dropout.scala — inverted dropout: at train time zero with
    prob p and scale by 1/(1-p); identity at eval (scale handled so eval
    needs no rescale, matching the reference's default scale=true)."""

    def __init__(self, init_p: float = 0.5, inplace: bool = False, scale: bool = True):
        super().__init__()
        self._config = dict(init_p=init_p, scale=scale)
        self.p = init_p
        self.scale = scale

    def update_output_pure(self, params, input, *, training=False, rng=None):
        if not training or self.p <= 0.0 or rng is None:
            return input
        import jax

        jnp = _jnp()
        keep = 1.0 - self.p
        mask = jax.random.bernoulli(rng, keep, shape=input.shape)
        y = jnp.where(mask, input, 0.0)
        if self.scale:
            y = y / keep
        return y

    def set_p(self, p):
        self.p = p
        return self

    def __repr__(self):
        return f"Dropout({self.p})"


# --------------------------------------------------------------------------
# Shape ops
# --------------------------------------------------------------------------


class Reshape(AbstractModule):
    """«bigdl»/nn/Reshape.scala — batch_mode None: auto-detect whether the
    first dim is a batch dim (reference semantics)."""

    def __init__(self, size: Sequence[int], batch_mode: Optional[bool] = None):
        super().__init__()
        self._config = dict(size=list(size), batch_mode=batch_mode)
        self.size = tuple(int(s) for s in size)
        self.batch_mode = batch_mode
        self._nelement = int(np.prod(self.size))

    def update_output_pure(self, params, input, *, training=False, rng=None):
        total = int(np.prod(input.shape))
        batched = self.batch_mode
        if batched is None:
            # reference auto-detect: first dim is a batch dim when the
            # element count doesn't match, or (batch==1 case) when the
            # remaining dims alone carry exactly nelement
            batched = total != self._nelement or (
                input.shape[0] == 1
                and input.ndim > len(self.size)
                and int(np.prod(input.shape[1:])) == self._nelement
            )
        if batched:
            return input.reshape((input.shape[0],) + self.size)
        return input.reshape(self.size)

    def __repr__(self):
        return f"Reshape({'x'.join(map(str, self.size))})"


class View(AbstractModule):
    """«bigdl»/nn/View.scala — reshape with -1 wildcard; num_input_dims
    governs batch handling (simplified: -1 resolves against the full
    element count, keeping batch when sizes don't consume it)."""

    def __init__(self, *sizes, **kwargs):
        super().__init__()
        if not sizes and "sizes" in kwargs:
            sizes = tuple(kwargs["sizes"])
        if len(sizes) == 1 and isinstance(sizes[0], (list, tuple)):
            sizes = tuple(sizes[0])
        self._config = dict(sizes=list(sizes))
        self.sizes = tuple(int(s) for s in sizes)

    def update_output_pure(self, params, input, *, training=False, rng=None):
        total = int(np.prod(input.shape))
        known = int(np.prod([s for s in self.sizes if s != -1]))
        if -1 in self.sizes:
            return input.reshape(
                tuple(total // known if s == -1 else s for s in self.sizes)
            )
        if known == total:
            return input.reshape(self.sizes)
        return input.reshape((input.shape[0],) + self.sizes)


class Squeeze(AbstractModule):
    """«bigdl»/nn/Squeeze.scala — 1-based dim."""

    def __init__(self, dim: Optional[int] = None, num_input_dims: int = 0):
        super().__init__()
        self._config = dict(dim=dim)
        self.dim = dim

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        if self.dim is None:
            return jnp.squeeze(input)
        return jnp.squeeze(input, axis=self.dim - 1)


class Unsqueeze(AbstractModule):
    """«bigdl»/nn/Unsqueeze.scala — 1-based position."""

    def __init__(self, pos: int, num_input_dims: int = 0):
        super().__init__()
        self._config = dict(pos=pos)
        self.pos = pos

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().expand_dims(input, axis=self.pos - 1)


class Transpose(AbstractModule):
    """«bigdl»/nn/Transpose.scala — sequence of (dim1, dim2) swaps,
    1-based."""

    def __init__(self, permutations: Sequence[Sequence[int]]):
        super().__init__()
        self._config = dict(permutations=[list(p) for p in permutations])
        self.permutations = [tuple(p) for p in permutations]

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        y = input
        for d1, d2 in self.permutations:
            y = jnp.swapaxes(y, d1 - 1, d2 - 1)
        return y


class Contiguous(AbstractModule):
    """«bigdl»/nn/Contiguous.scala — no-op under XLA (layout is the
    compiler's concern)."""

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return input


class Replicate(AbstractModule):
    """«bigdl»/nn/Replicate.scala — repeat along a new 1-based dim."""

    def __init__(self, n_features: int, dim: int = 1, n_dim: int = float("inf")):
        super().__init__()
        self._config = dict(n_features=n_features, dim=dim)
        self.n_features, self.dim = n_features, dim

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        y = jnp.expand_dims(input, axis=self.dim - 1)
        reps = [1] * y.ndim
        reps[self.dim - 1] = self.n_features
        return jnp.tile(y, reps)


class Narrow(AbstractModule):
    """«bigdl»/nn/Narrow.scala — 1-based offset slice along dim."""

    def __init__(self, dim: int, offset: int, length: int = 1):
        super().__init__()
        self._config = dict(dim=dim, offset=offset, length=length)
        self.dim, self.offset, self.length = dim, offset, length

    def update_output_pure(self, params, input, *, training=False, rng=None):
        d = self.dim - 1 if self.dim > 0 else input.ndim + self.dim
        length = self.length
        if length < 0:
            length = input.shape[d] - self.offset + 2 + length
        start = self.offset - 1
        idx = [slice(None)] * input.ndim
        idx[d] = slice(start, start + length)
        return input[tuple(idx)]


class Padding(AbstractModule):
    """«bigdl»/nn/Padding.scala — pad `pad` cells (negative: before) along
    1-based dim with value."""

    def __init__(self, dim, pad, n_input_dim, value=0.0, n_index=1):
        super().__init__()
        self._config = dict(dim=dim, pad=pad, n_input_dim=n_input_dim, value=value)
        self.dim, self.pad, self.n_input_dim, self.value = dim, pad, n_input_dim, value

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        d = self.dim - 1
        if input.ndim > self.n_input_dim:
            d += 1  # batch dim present
        widths = [(0, 0)] * input.ndim
        widths[d] = (-self.pad, 0) if self.pad < 0 else (0, self.pad)
        return jnp.pad(input, widths, constant_values=self.value)


class SpatialZeroPadding(AbstractModule):
    """«bigdl»/nn/SpatialZeroPadding.scala — NCHW edge padding."""

    def __init__(self, pad_left, pad_right=None, pad_top=None, pad_bottom=None):
        super().__init__()
        pad_right = pad_left if pad_right is None else pad_right
        pad_top = pad_left if pad_top is None else pad_top
        pad_bottom = pad_left if pad_bottom is None else pad_bottom
        self._config = dict(
            pad_left=pad_left,
            pad_right=pad_right,
            pad_top=pad_top,
            pad_bottom=pad_bottom,
        )
        self.pads = (pad_left, pad_right, pad_top, pad_bottom)

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        l, r, t, b = self.pads
        widths = [(0, 0)] * (input.ndim - 2) + [(t, b), (l, r)]
        return jnp.pad(input, widths)


class SpatialUpSamplingNearest(AbstractModule):
    """«bigdl»/nn/SpatialUpSamplingNearest.scala"""

    def __init__(self, scale: int):
        super().__init__()
        self._config = dict(scale=scale)
        self.scale = scale

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        y = jnp.repeat(input, self.scale, axis=-2)
        return jnp.repeat(y, self.scale, axis=-1)


class SpatialUpSamplingBilinear(AbstractModule):
    """«bigdl»/nn/SpatialUpSamplingBilinear.scala (align_corners=true,
    matching the reference)."""

    def __init__(self, output_height: int, output_width: int):
        super().__init__()
        self._config = dict(output_height=output_height, output_width=output_width)
        self.oh, self.ow = output_height, output_width

    def update_output_pure(self, params, input, *, training=False, rng=None):
        import jax

        x, squeezed = _auto_batch(input, 4)
        y = jax.image.resize(
            x, (x.shape[0], x.shape[1], self.oh, self.ow), method="linear"
        )
        return y[0] if squeezed else y


class Mean(AbstractModule):
    """«bigdl»/nn/Mean.scala — 1-based dim; squeeze by default."""

    def __init__(self, dim: int = 1, n_input_dims: int = -1, squeeze: bool = True):
        super().__init__()
        self._config = dict(dim=dim, n_input_dims=n_input_dims, squeeze=squeeze)
        self.dim, self.n_input_dims, self.squeeze = dim, n_input_dims, squeeze

    def _axis(self, input):
        d = self.dim - 1
        if self.n_input_dims > 0 and input.ndim > self.n_input_dims:
            d += 1
        return d

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().mean(input, axis=self._axis(input), keepdims=not self.squeeze)


class Sum(Mean):
    """«bigdl»/nn/Sum.scala"""

    def __init__(self, dim=1, n_input_dims=-1, size_average=False, squeeze=True):
        super().__init__(dim, n_input_dims, squeeze)
        self.size_average = size_average
        self._config["size_average"] = size_average

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        ax = self._axis(input)
        y = jnp.sum(input, axis=ax, keepdims=not self.squeeze)
        if self.size_average:
            y = y / input.shape[ax]
        return y


class Max(AbstractModule):
    """«bigdl»/nn/Max.scala — max over 1-based dim (values only)."""

    def __init__(self, dim: int = 1, num_input_dims: int = -1):
        super().__init__()
        self._config = dict(dim=dim)
        self.dim = dim

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().max(input, axis=self.dim - 1)


class Min(AbstractModule):
    """«bigdl»/nn/Min.scala"""

    def __init__(self, dim: int = 1, num_input_dims: int = -1):
        super().__init__()
        self._config = dict(dim=dim)
        self.dim = dim

    def update_output_pure(self, params, input, *, training=False, rng=None):
        return _jnp().min(input, axis=self.dim - 1)


class Index(AbstractModule):
    """«bigdl»/nn/Index.scala — table input (tensor, 1-based indices)."""

    def __init__(self, dimension: int):
        super().__init__()
        self._config = dict(dimension=dimension)
        self.dimension = dimension

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        t, idx = input
        return jnp.take(t, idx.astype(jnp.int32) - 1, axis=self.dimension - 1)


class Masking(AbstractModule):
    """«bigdl»/nn/Masking.scala — zero timesteps equal to mask_value."""

    def __init__(self, mask_value: float = 0.0):
        super().__init__()
        self._config = dict(mask_value=mask_value)
        self.mask_value = mask_value

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        mask = jnp.any(input != self.mask_value, axis=-1, keepdims=True)
        return jnp.where(mask, input, 0.0)


# --------------------------------------------------------------------------
# Gradient-shaping layers (need custom vjp)
# --------------------------------------------------------------------------


def _gradient_reversal_fn():
    import jax

    @jax.custom_vjp
    def f(x, lam):
        return x

    def fwd(x, lam):
        return x, lam

    def bwd(lam, g):
        return (-lam * g, None)

    f.defvjp(fwd, bwd)
    return f


class GradientReversal(AbstractModule):
    """«bigdl»/nn/GradientReversal.scala — identity forward, negated
    (scaled) gradient backward (domain-adaptation trick)."""

    def __init__(self, the_lambda: float = 1.0):
        super().__init__()
        self._config = dict(the_lambda=the_lambda)
        self.the_lambda = the_lambda
        self._fn = None

    def set_lambda(self, lam):
        self.the_lambda = lam
        return self

    def update_output_pure(self, params, input, *, training=False, rng=None):
        if self._fn is None:
            self._fn = _gradient_reversal_fn()
        return self._fn(input, self.the_lambda)


def _l1_penalty_fn():
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def f(x, w):
        return x

    def fwd(x, w):
        return x, (x, w)

    def bwd(res, g):
        x, w = res
        return (g + w * jnp.sign(x), None)

    f.defvjp(fwd, bwd)
    return f


class L1Penalty(AbstractModule):
    """«bigdl»/nn/L1Penalty.scala — identity forward that injects an L1
    sparsity gradient on the way back."""

    def __init__(self, l1weight: float, size_average: bool = False, provide_output=True):
        super().__init__()
        self._config = dict(l1weight=l1weight, size_average=size_average)
        self.l1weight = l1weight
        self.size_average = size_average
        self._fn = None

    def update_output_pure(self, params, input, *, training=False, rng=None):
        if self._fn is None:
            self._fn = _l1_penalty_fn()
        w = self.l1weight
        if self.size_average:
            w = w / int(np.prod(input.shape))
        return self._fn(input, w)


# --------------------------------------------------------------------------
# Misc similarity layers
# --------------------------------------------------------------------------


class Cosine(AbstractModule):
    """«bigdl»/nn/Cosine.scala — cosine similarity of input to each weight
    row."""

    param_names = ("weight",)

    def __init__(self, input_size: int, output_size: int):
        super().__init__()
        self._config = dict(input_size=input_size, output_size=output_size)
        stdv = 1.0 / math.sqrt(input_size)
        self.weight = _to_device(
            RandomGenerator.RNG.uniform(
                -stdv, stdv, size=(output_size, input_size)
            ).astype(np.float32)
        )

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        w = params["weight"]
        xn = input / (jnp.linalg.norm(input, axis=-1, keepdims=True) + 1e-12)
        wn = w / (jnp.linalg.norm(w, axis=-1, keepdims=True) + 1e-12)
        return jnp.matmul(xn, wn.T)


class Euclidean(AbstractModule):
    """«bigdl»/nn/Euclidean.scala — distance of input to each weight
    column."""

    param_names = ("weight",)

    def __init__(self, input_size: int, output_size: int, fast_backward=True):
        super().__init__()
        self._config = dict(input_size=input_size, output_size=output_size)
        stdv = 1.0 / math.sqrt(input_size)
        self.weight = _to_device(
            RandomGenerator.RNG.uniform(
                -stdv, stdv, size=(output_size, input_size)
            ).astype(np.float32)
        )

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        diff = input[..., None, :] - params["weight"]
        return jnp.sqrt(jnp.sum(diff * diff, axis=-1) + 1e-12)


class Bilinear(AbstractModule):
    """«bigdl»/nn/Bilinear.scala — y_k = x1^T W_k x2 + b_k over a table
    input (x1, x2)."""

    param_names = ("weight", "bias")

    def __init__(self, input_size1, input_size2, output_size, bias_res=True):
        super().__init__()
        self._config = dict(
            input_size1=input_size1,
            input_size2=input_size2,
            output_size=output_size,
            bias_res=bias_res,
        )
        stdv = 1.0 / math.sqrt(input_size1)
        self.weight = _to_device(
            RandomGenerator.RNG.uniform(
                -stdv, stdv, size=(output_size, input_size1, input_size2)
            ).astype(np.float32)
        )
        self.bias = (
            _to_device(np.zeros(output_size, dtype=np.float32)) if bias_res else None
        )

    def update_output_pure(self, params, input, *, training=False, rng=None):
        jnp = _jnp()
        x1, x2 = input
        y = jnp.einsum("bi,kij,bj->bk", x1, params["weight"], x2)
        if "bias" in params:
            y = y + params["bias"]
        return y


__all__ = [
    "InitializationMethod", "Zeros", "Ones", "ConstInitMethod",
    "RandomUniform", "RandomNormal", "Xavier", "MsraFiller",
    "Linear", "LookupTable",
    "SpatialConvolution", "SpatialDilatedConvolution",
    "SpatialFullConvolution", "TemporalConvolution",
    "SpatialMaxPooling", "SpatialAveragePooling",
    "ReLU", "ReLU6", "Tanh", "Sigmoid", "LogSoftMax", "SoftMax", "SoftMin",
    "SoftPlus", "SoftSign", "ELU", "LeakyReLU", "HardTanh", "HardSigmoid",
    "Clamp", "Threshold", "PReLU", "GELU",
    "SELU",
    "Abs", "Square", "Sqrt", "Power", "Log", "Exp", "Negative",
    "Floor", "Ceil", "Round", "Sign", "Log1p", "Expm1", "Erf",
    "Sin", "Cos", "ArgMax",
    "AddConstant", "MulConstant", "DivConstant",
    "CMul", "CAdd", "Add", "Mul", "Scale",
    "BatchNormalization", "SpatialBatchNormalization", "Normalize",
    "SpatialCrossMapLRN",
    "Dropout",
    "Reshape", "View", "Squeeze", "Unsqueeze", "Transpose", "Contiguous",
    "Replicate", "Narrow", "Padding", "SpatialZeroPadding",
    "SpatialUpSamplingNearest", "SpatialUpSamplingBilinear",
    "Mean", "Sum", "Max", "Min", "Index", "Masking",
    "GradientReversal", "L1Penalty",
    "Cosine", "Euclidean", "Bilinear",
]
