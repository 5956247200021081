"""nn — the layer + criterion inventory (ref dl/.../bigdl/nn, SURVEY.md §2.3)."""

from bigdl_tpu.nn.module import (
    Module, TensorModule, Container, Criterion, Context,
)
from bigdl_tpu.nn import init
from bigdl_tpu.nn.init import InitializationMethod, Default, Xavier, BilinearFiller, MSRA
from bigdl_tpu.nn.containers import (
    Sequential, Concat, ConcatTable, ParallelTable, MapTable, Bottle,
    Recompute,
)
from bigdl_tpu.nn.activations import (
    ReLU, ReLU6, PReLU, RReLU, LeakyReLU, ELU, Tanh, TanhShrink, Sigmoid,
    LogSigmoid, LogSoftMax, SoftMax, SoftMin, SoftPlus, SoftShrink, SoftSign,
    HardTanh, HardShrink, Threshold, Clamp, Abs, Sqrt, Square, Power, Exp,
    Log, GradientReversal,
)
from bigdl_tpu.nn.linear import (
    Linear, Bilinear, CMul, CAdd, Mul, Add, MulConstant, AddConstant, MM, MV,
    Cosine, Euclidean, LookupTable, GatedLinearUnit, LmHead, TiedLmHead,
)
from bigdl_tpu.nn.conv import (
    SpatialConvolution, SpatialShareConvolution, SpatialDilatedConvolution,
    SpatialFullConvolution, SpatialConvolutionMap,
)
from bigdl_tpu.nn.pooling import (
    SpatialMaxPooling, SpatialAveragePooling, RoiPooling,
)
from bigdl_tpu.nn.normalization import (
    BatchNormalization, SpatialBatchNormalization, SpatialCrossMapLRN,
    SpatialSubtractiveNormalization, SpatialDivisiveNormalization,
    SpatialContrastiveNormalization, LayerNorm, RMSNorm,
)
from bigdl_tpu.nn.shape_ops import (
    Reshape, InferReshape, View, Transpose, Replicate, Squeeze, Unsqueeze,
    Padding, SpatialZeroPadding, Contiguous, Copy, Identity, Echo,
)
from bigdl_tpu.nn.table_ops import (
    CAddTable, CSubTable, CMulTable, CDivTable, CMaxTable, CMinTable,
    JoinTable, SelectTable, NarrowTable, FlattenTable, MixtureTable,
    DotProduct, PairwiseDistance, CosineDistance, CriterionTable,
)
from bigdl_tpu.nn.reductions import (
    Mean, Sum, Max, Min, Index, Select, Narrow, MaskedSelect,
)
from bigdl_tpu.nn.dropout import Dropout, L1Penalty
from bigdl_tpu.nn.nms import Nms, nms_mask, nms_indices
from bigdl_tpu.nn.recurrent import (
    Cell, RnnCell, LSTMCell, GRUCell, Recurrent, BiRecurrent, TimeDistributed,
)
from bigdl_tpu.nn.moe import MoE, DroplessMoE
from bigdl_tpu.nn.shortconv import ShortConv
from bigdl_tpu.nn.attention import (MultiHeadSelfAttention,
                                    SinusoidalPositionalEncoding,
                                    GroupedQueryAttention,
                                    GatedGroupedQueryAttention,
                                    LatentAttention)
from bigdl_tpu.nn.criterion import (
    ClassNLLCriterion, CrossEntropyCriterion, MSECriterion, AbsCriterion,
    BCECriterion, DistKLDivCriterion, ClassSimplexCriterion,
    CosineEmbeddingCriterion, HingeEmbeddingCriterion,
    L1HingeEmbeddingCriterion, MarginCriterion, MarginRankingCriterion,
    MultiCriterion, ParallelCriterion, MultiLabelMarginCriterion,
    MultiLabelSoftMarginCriterion, MultiMarginCriterion, SmoothL1Criterion,
    SmoothL1CriterionWithWeights, SoftMarginCriterion, SoftmaxWithCriterion,
    L1Cost, TimeDistributedCriterion,
)
