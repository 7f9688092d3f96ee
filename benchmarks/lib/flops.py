"""Operations and bytes the algorithms need, from their shapes.

These are the numerators of ``train_mfu`` and ``decode_step_roofline``.
They count what the mathematics requires (2 per multiply-add, backward
twice the forward), never what a particular program happens to execute,
so a program that recomputes or copies gets no credit for it.
"""

from __future__ import annotations

RESNET50_STAGES = ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2))


def resnet50_forward_flops_per_image(img: int = 224,
                                     classes: int = 1000) -> float:
    """2 x multiply-adds of every convolution and the classifier of the
    bottleneck ResNet-50 (He et al. 2015, Table 1), stride on the 3x3."""
    flops = 0.0

    def conv(cin, cout, k, h_in, stride):
        nonlocal flops
        h_out = -(-h_in // stride)
        flops += 2.0 * k * k * cin * cout * h_out * h_out
        return h_out

    h = conv(3, 64, 7, img, 2)
    h = -(-h // 2)  # 3x3 / 2 max pool
    cin = 64
    for width, blocks, stride in RESNET50_STAGES:
        for i in range(blocks):
            st = stride if i == 0 else 1
            conv(cin, width, 1, h, 1)
            h2 = conv(width, width, 3, h, st)
            conv(width, width * 4, 1, h2, 1)
            if i == 0:
                conv(cin, width * 4, 1, h, st)
            h, cin = h2, width * 4
    return flops + 2.0 * cin * classes


def resnet50_train_flops_per_image(img: int = 224,
                                   classes: int = 1000) -> float:
    """Forward plus backward; the backward of a convolution or a matrix
    product is twice its forward."""
    return 3.0 * resnet50_forward_flops_per_image(img, classes)


def gpt2_matmul_params(n_layer: int, dim: int, vocab: int,
                       mlp_ratio: int = 4) -> float:
    """Weights every decoded token multiplies by: the four attention
    projections and the two MLP matrices of each layer, and the head."""
    per_layer = 4.0 * dim * dim + 2.0 * mlp_ratio * dim * dim
    return n_layer * per_layer + float(vocab) * dim


def gpt2_decode_step_flops(n_layer: int, dim: int, vocab: int, batch: int,
                           context: int, mlp_ratio: int = 4) -> float:
    """One decode step of ``batch`` sequences that each attend over
    ``context`` positions: 2 per weight per token, plus scores and the
    weighted sum over the context (2 * 2 * context * dim a layer)."""
    dense = 2.0 * gpt2_matmul_params(n_layer, dim, vocab, mlp_ratio)
    attn = 4.0 * n_layer * context * dim
    return batch * (dense + attn)


def gpt2_decode_step_bytes(n_layer: int, dim: int, vocab: int, batch: int,
                           pages: int, page_size: int, weight_itemsize: int,
                           kv_itemsize: int, mlp_ratio: int = 4) -> float:
    """Bytes one decode step has to read: every matrix once, and the K
    and V pages the step's page-table bucket names (``pages`` a slot).
    Biases, layer norms, the embedding rows and the one new K/V row a
    slot writes are left out (under 0.1 % at these sizes)."""
    weights = gpt2_matmul_params(n_layer, dim, vocab, mlp_ratio) \
        * weight_itemsize
    kv = 2.0 * n_layer * batch * pages * page_size * dim * kv_itemsize
    return weights + kv


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak rate and bytes over peak bandwidth."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
