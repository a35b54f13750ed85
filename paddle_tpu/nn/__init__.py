"""paddle.nn namespace. Parity: python/paddle/nn/__init__.py."""
from . import initializer
from . import functional
# reference keeps `paddle.nn.loss` as a module alias of nn.layer.loss
# ("keep it for too many used in unitests", ref nn/__init__.py:145)
from .layer import loss
from .layer.layers import Layer
from .layer.container import Sequential, LayerList, ParameterList, LayerDict
from .layer.common import (Identity, Linear, GatedMLP, RotaryEmbedding,
                           Embedding, Flatten, Dropout,
                           Dropout2D, Dropout3D, AlphaDropout, Upsample,
                           UpsamplingNearest2D, UpsamplingBilinear2D, Pad1D,
                           Pad2D, Pad3D, ZeroPad2D, CosineSimilarity,
                           Bilinear, Unfold, Fold)
from .layer.conv import (Conv1D, Conv2D, Conv3D, Conv1DTranspose,
                         Conv2DTranspose, Conv3DTranspose)
from .layer.norm import (BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D,
                         SyncBatchNorm, LayerNorm, RMSNorm, GroupNorm,
                         InstanceNorm1D, InstanceNorm2D, InstanceNorm3D,
                         LocalResponseNorm, SpectralNorm)
from .layer.pooling import (AvgPool1D, AvgPool2D, AvgPool3D, MaxPool1D,
                            MaxPool2D, MaxPool3D, AdaptiveAvgPool1D,
                            AdaptiveAvgPool2D, AdaptiveAvgPool3D,
                            AdaptiveMaxPool1D, AdaptiveMaxPool2D,
                            AdaptiveMaxPool3D, MaxUnPool2D, MaxUnPool1D,
                            MaxUnPool3D)
from .layer.activation import (ReLU, ReLU6, GELU, SELU, ELU, CELU, Sigmoid,
                               LogSigmoid, Hardshrink, Hardsigmoid,
                               Hardswish, Hardtanh, LeakyReLU, PReLU, RReLU,
                               Softmax, LogSoftmax, Softplus, Softshrink,
                               Softsign, Swish, SiLU, Mish, Tanh,
                               Tanhshrink, ThresholdedReLU, Maxout, GLU)
from .layer.loss import (HSigmoidLoss, CrossEntropyLoss, NLLLoss, BCELoss,
                         BCEWithLogitsLoss, MSELoss, L1Loss, SmoothL1Loss,
                         HuberLoss, KLDivLoss, MarginRankingLoss, CTCLoss,
                         HingeEmbeddingLoss, CosineEmbeddingLoss,
                         SoftMarginLoss, TripletMarginLoss,
                         TripletMarginWithDistanceLoss)
from .layer.distance import PairwiseDistance
from .layer.vision import PixelShuffle, PixelUnshuffle, ChannelShuffle
from .clip import ClipGradByValue, ClipGradByNorm, ClipGradByGlobalNorm
from . import utils

# RNN / Transformer families land with their modules
try:
    from .layer.rnn import (RNNCellBase, SimpleRNNCell, LSTMCell, GRUCell,
                            RNN, BiRNN, SimpleRNN, LSTM, GRU)
except ImportError:
    pass
try:
    from .layer.transformer import (MultiHeadAttention,
                                    TransformerEncoderLayer,
                                    TransformerEncoder,
                                    TransformerDecoderLayer,
                                    TransformerDecoder, Transformer)
except ImportError:
    pass

Silu = SiLU  # reference exposes both spellings
from .layer.decode import BeamSearchDecoder, dynamic_decode
