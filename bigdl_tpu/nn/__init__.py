"""bigdl_tpu.nn — the module library.

Rebuild of «bigdl»/nn/ (layer library, containers, criterions) and
«bigdl»/nn/abstractnn/ (the module contract).  One import surface exposing
every layer by its reference name, so user code reads like classic BigDL:

    from bigdl_tpu.nn import Sequential, SpatialConvolution, ReLU, Linear
"""

from bigdl_tpu.nn.module import (
    AbstractModule,
    Container,
    Sequential,
    Remat,
    Identity,
    Echo,
)
from bigdl_tpu.nn.layers import *  # noqa: F401,F403
from bigdl_tpu.nn.layers import __all__ as _layers_all
from bigdl_tpu.nn.graph import DynamicGraph, Graph, Input, Node, Model
from bigdl_tpu.nn.control_ops import (
    IfElse,
    LoopCondition,
    MergeOps,
    NextIteration,
    SwitchOps,
    WhileLoop,
)
from bigdl_tpu.nn.tree_lstm import BinaryTreeLSTM
from bigdl_tpu.nn.quantized import (
    QuantizedLinear,
    QuantizedSpatialConvolution,
    Quantizer,
)
from bigdl_tpu.nn.sparse import (
    LookupTableSparse,
    SparseJoinTable,
    SparseLinear,
    SparseTensor,
    SparseTensorMath,
)
from bigdl_tpu.nn.latent import (
    GatedMLP,
    LatentAttention,
    RMSNorm,
)
from bigdl_tpu.nn.experts import DroplessExperts
from bigdl_tpu.nn.ssm import Mamba2Mixer
from bigdl_tpu.nn.delta import DeltaMixer, GatedDeltaMixer
from bigdl_tpu.nn.attention import (
    LayerNorm,
    MultiHeadAttention,
    TransformerBlock,
    PositionalEmbedding,
)
from bigdl_tpu.nn.table_ops import (
    ConcatTable,
    ParallelTable,
    CAddTable,
    CSubTable,
    CMulTable,
    CDivTable,
    CMaxTable,
    CMinTable,
    JoinTable,
    SelectTable,
    WhereTable,
    InTopK,
    FlattenTable,
    MM,
    MV,
    CosineDistance,
    DotProduct,
    Concat,
)
from bigdl_tpu.nn.criterion import (
    AbstractCriterion,
    ClassNLLCriterion,
    CrossEntropyCriterion,
    MSECriterion,
    AbsCriterion,
    SmoothL1Criterion,
    BCECriterion,
    BCECriterionWithLogits,
    MultiLabelSoftMarginCriterion,
    MarginCriterion,
    HingeEmbeddingCriterion,
    DistKLDivCriterion,
    CosineEmbeddingCriterion,
    SoftmaxWithCriterion,
    MultiCriterion,
    ParallelCriterion,
    TimeDistributedCriterion,
    ClassSimplexCriterion,
    L1Cost,
    MarginRankingCriterion,
    MultiMarginCriterion,
)
from bigdl_tpu.nn.recurrent import (
    Recurrent,
    RnnCell,
    LSTM,
    LSTMPeephole,
    GRU,
    BiRecurrent,
    TimeDistributed,
    Select,
    MultiRNNCell,
    ConvLSTMPeephole,
)
from bigdl_tpu.nn.table_ops import (
    CAveTable,
    SplitTable,
    BifurcateSplitTable,
    NarrowTable,
    Pack,
    MixtureTable,
    MapTable,
    Bottle,
)
from bigdl_tpu.nn.criterion import (
    CosineDistanceCriterion,
    DiceCoefficientCriterion,
    SoftMarginCriterion,
    MultiLabelMarginCriterion,
    GaussianCriterion,
    KLDCriterion,
    L1HingeEmbeddingCriterion,
    PoissonCriterion,
    CosineProximityCriterion,
    MeanAbsolutePercentageCriterion,
    MeanSquaredLogarithmicCriterion,
)
from bigdl_tpu.nn.volumetric import *  # noqa: F401,F403
from bigdl_tpu.nn.volumetric import __all__ as _volumetric_all
from bigdl_tpu.nn.fused import (
    SpatialConvolutionBatchNorm,
    fuse_conv_bn,
)
from bigdl_tpu.nn.layers_extra import *  # noqa: F401,F403
from bigdl_tpu.nn.layers_extra import __all__ as _extra_all

__all__ = (
    [
        "AbstractModule", "Container", "Sequential", "Identity", "Echo",
        "Graph", "DynamicGraph", "Input", "Node", "Model",
        "SwitchOps", "MergeOps", "IfElse", "WhileLoop", "LoopCondition",
        "NextIteration", "BinaryTreeLSTM",
        "ConcatTable", "ParallelTable", "CAddTable", "CSubTable", "CMulTable",
        "CDivTable", "CMaxTable", "CMinTable", "JoinTable", "SelectTable",
        "WhereTable", "InTopK",
        "FlattenTable", "MM", "MV", "CosineDistance", "DotProduct", "Concat",
        "CAveTable", "SplitTable", "BifurcateSplitTable", "NarrowTable",
        "Pack", "MixtureTable", "MapTable", "Bottle",
        "AbstractCriterion", "ClassNLLCriterion", "CrossEntropyCriterion",
        "MSECriterion", "AbsCriterion", "SmoothL1Criterion", "BCECriterion",
        "BCECriterionWithLogits", "MultiLabelSoftMarginCriterion",
        "MarginCriterion", "HingeEmbeddingCriterion", "DistKLDivCriterion",
        "CosineEmbeddingCriterion", "SoftmaxWithCriterion", "MultiCriterion",
        "ParallelCriterion", "TimeDistributedCriterion",
        "ClassSimplexCriterion", "L1Cost", "MarginRankingCriterion",
        "MultiMarginCriterion",
        "CosineDistanceCriterion", "DiceCoefficientCriterion",
        "SoftMarginCriterion", "MultiLabelMarginCriterion",
        "GaussianCriterion", "KLDCriterion", "L1HingeEmbeddingCriterion",
        "PoissonCriterion", "CosineProximityCriterion",
        "MeanAbsolutePercentageCriterion",
        "MeanSquaredLogarithmicCriterion",
        "Recurrent", "RnnCell", "LSTM", "LSTMPeephole", "GRU", "BiRecurrent",
        "TimeDistributed", "Select", "MultiRNNCell", "ConvLSTMPeephole",
        "LayerNorm", "MultiHeadAttention", "TransformerBlock",
        "PositionalEmbedding",
        "RMSNorm", "GatedMLP", "LatentAttention", "DroplessExperts",
        "Mamba2Mixer",
        "DeltaMixer", "GatedDeltaMixer",
        "SpatialConvolutionBatchNorm", "fuse_conv_bn",
    ]
    + list(_layers_all)
    + list(_volumetric_all)
    + list(_extra_all)
)
