"""paddle.nn — layers, functional, initializers.

Reference analogue: python/paddle/nn/ (25.2k LoC).
"""
from . import functional  # noqa: F401
from . import utils  # noqa: F401
from . import quant  # noqa: F401
from . import initializer  # noqa: F401
from .layer_base import Layer, Parameter  # noqa: F401
from .param_attr import ParamAttr  # noqa: F401
from .clip import ClipGradByGlobalNorm, ClipGradByNorm, ClipGradByValue  # noqa: F401
from .layer.activation import (  # noqa: F401
    CELU, ELU, GELU, GLU, Hardshrink, Hardsigmoid, Hardswish, Hardtanh,
    LeakyReLU, LogSigmoid, LogSoftmax, Maxout, Mish, PReLU, ReLU, ReLU6, SELU,
    Sigmoid, Silu, Softmax, Softplus, Softshrink, Softsign, Swish, Tanh,
    Tanhshrink, ThresholdedReLU,
)
from .layer.common import (  # noqa: F401
    AlphaDropout, Bilinear, CosineSimilarity, Dropout, Dropout2D, Dropout3D,
    Embedding, Flatten, Fold, Identity, LayerDict, LayerList, Linear, Pad1D,
    Pad2D, Pad3D, PairwiseDistance, ParameterList, PixelShuffle,
    PixelUnshuffle, Sequential, Unfold, Upsample, UpsamplingBilinear2D,
    UpsamplingNearest2D, ZeroPad2D,
)
from .layer.conv import (  # noqa: F401
    Conv1D, Conv1DTranspose, Conv2D, Conv2DTranspose, Conv3D, Conv3DTranspose,
)
from .layer.loss import (  # noqa: F401
    BCELoss, BCEWithLogitsLoss, CrossEntropyLoss, CTCLoss, HingeEmbeddingLoss,
    HSigmoidLoss, KLDivLoss, L1Loss, MarginRankingLoss, MSELoss, NLLLoss,
    SmoothL1Loss,
)
from .layer.norm import (  # noqa: F401
    BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D, GroupNorm,
    InstanceNorm1D, InstanceNorm2D, InstanceNorm3D, LayerNorm, RMSNorm,
    LocalResponseNorm, SpectralNorm, SyncBatchNorm,
)
from .layer.pooling import (  # noqa: F401
    AdaptiveAvgPool1D, AdaptiveAvgPool2D, AdaptiveAvgPool3D,
    AdaptiveMaxPool1D, AdaptiveMaxPool2D, AdaptiveMaxPool3D, AvgPool1D,
    AvgPool2D, AvgPool3D, MaxPool1D, MaxPool2D, MaxPool3D, MaxUnPool1D,
    MaxUnPool2D, MaxUnPool3D,
)
from .layer.rnn import (  # noqa: F401
    GRU, LSTM, RNN, BeamSearchDecoder, BiRNN, GRUCell, LSTMCell, RNNCellBase,
    SimpleRNN, SimpleRNNCell, dynamic_decode,
)
from .layer.transformer import (  # noqa: F401
    MultiHeadAttention, Transformer, TransformerDecoder,
    TransformerDecoderLayer, TransformerEncoder, TransformerEncoderLayer,
)
from .utils_fns import clip_grad_norm_, clip_grad_value_  # noqa: F401
