"""bigdl_tpu.models — reference model zoo.

Rebuild of «bigdl»/models/ (SURVEY.md §2.1 "Reference models"): lenet,
resnet (CIFAR + ImageNet), inception, vgg, alexnet, rnn (PTB LM),
autoencoder — each with a builder and a runnable train entry point —
and the language models served behind ``serving.LMEngine``:
``TransformerLM`` and ``LongCatFlash`` (latent attention, the
shortcut-connected double layer, a dropless expert layer); by module,
not re-exported here: ``joyai_flash`` (a step that verifies its own
draft), ``sdar_moe`` (generation by blocks), ``zaya`` (attention in a
compressed latent, a bounded-past slot state), ``falcon_h1`` (a
state-space mixer beside grouped attention, a slot state that sums over
the whole past), ``ling_flash`` (a delta-rule linear attention with a
decay a channel in five layers of six, latent attention in the sixth,
experts chosen by groups: layers that differ in what a slot keeps) and
``olmo_hybrid`` (a gated delta rule, one decay a head on 96 x 192
tiles, in three layers of four, full attention of 30 heads in the
fourth, the reordered norm).
"""

from bigdl_tpu.models.lenet import build_lenet5
from bigdl_tpu.models.resnet import (
    build_resnet_cifar,
    build_resnet_imagenet,
    imagenet_recipe_optim,
)
from bigdl_tpu.models.vgg import build_vgg16, build_vgg19, build_vgg_cifar
from bigdl_tpu.models.alexnet import build_alexnet, build_alexnet_original
from bigdl_tpu.models.inception import build_inception_v1, build_inception_v2
from bigdl_tpu.models.ncf import build_ncf
from bigdl_tpu.models.autoencoder import build_autoencoder
from bigdl_tpu.models.rnn import build_ptb_lm
from bigdl_tpu.models.transformer import TransformerLM, build_transformer_lm
from bigdl_tpu.models.longcat_flash import LongCatFlash, build_longcat_flash
from bigdl_tpu.models.wide_and_deep import build_wide_and_deep, pack_batch

__all__ = [
    "build_lenet5", "build_resnet_cifar", "build_resnet_imagenet",
    "imagenet_recipe_optim", "build_vgg16", "build_vgg19", "build_vgg_cifar",
    "build_alexnet", "build_alexnet_original", "build_inception_v1",
    "build_inception_v2", "build_ncf", "build_wide_and_deep", "pack_batch",
    "build_autoencoder", "build_ptb_lm", "TransformerLM",
    "build_transformer_lm", "LongCatFlash", "build_longcat_flash",
]
