"""Common layers. Parity: python/paddle/nn/layer/common.py."""
import contextlib
import threading

import numpy as np

from ...framework.core import Tensor
from ...framework.param_attr import ParamAttr
from .. import functional as F
from .. import initializer as I
from .layers import Layer

__all__ = ["Identity", "Linear", "GatedMLP", "RotaryEmbedding", "Embedding", "Flatten", "Dropout",
           "Dropout2D", "Dropout3D", "AlphaDropout", "Upsample",
           "UpsamplingNearest2D", "UpsamplingBilinear2D", "Pad1D", "Pad2D",
           "Pad3D", "ZeroPad2D", "CosineSimilarity", "Bilinear", "Unfold",
           "Fold"]


class Identity(Layer):
    def __init__(self, *args, **kwargs):
        super().__init__()

    def forward(self, input):
        return input


class Linear(Layer):
    """y = xW + b with W: [in_features, out_features].
    Parity: python/paddle/nn/layer/common.py:Linear."""

    def __init__(self, in_features, out_features, weight_attr=None,
                 bias_attr=None, name=None):
        super().__init__()
        self._in_features = in_features
        self._out_features = out_features
        self.weight = self.create_parameter(
            [in_features, out_features], attr=weight_attr,
            default_initializer=I.XavierNormal())
        self.bias = self.create_parameter(
            [out_features], attr=bias_attr, is_bias=True)

    def forward(self, input):
        return F.linear(input, self.weight, self.bias)

    def extra_repr(self):
        return f"in_features={self._in_features}, " \
               f"out_features={self._out_features}"


_tally = threading.local()


def save_point(t, name):
    """Tag the traced activation `t` as the remat save point `name`
    (jax.ad_checkpoint.checkpoint_name): a jax.checkpoint policy that
    names it keeps it for the backward pass, any other policy treats the
    tag as the identity. Inside `tally_save_points()` its bytes are
    counted under its name. Eager values pass untouched (the tape holds
    them; re-wrapping would orphan them from it)."""
    import jax
    v = t.value
    if not isinstance(v, jax.core.Tracer):
        return t
    counts = getattr(_tally, "counts", None)
    if counts is not None:
        counts[name] = counts.get(name, 0) + v.size * v.dtype.itemsize
    from jax.ad_checkpoint import checkpoint_name
    return Tensor(checkpoint_name(v, name))


@contextlib.contextmanager
def tally_save_points():
    """{save point name: bytes} of what `save_point` tags while the block
    traces (this thread only)."""
    outer = getattr(_tally, "counts", None)
    _tally.counts = counts = {}
    try:
        yield counts
    finally:
        _tally.counts = outer


class GatedMLP(Layer):
    """SiLU-gated feed-forward (Shazeer, 2020, "GLU variants"):
    down(act(gate(x)) * up(x)), three bias-free Linear layers `gate_proj`,
    `up_proj` [hidden_size, intermediate_size] and `down_proj`
    [intermediate_size, hidden_size]. `activation` names a function of
    nn.functional ("silu" by default). The gate and up outputs are the
    save points "mlp_gate" and "mlp_up", kept by a remat policy that
    names them (models/decoder.py's) so that a backward pass does not
    run the two full-width matmuls again."""

    def __init__(self, hidden_size, intermediate_size, activation="silu",
                 weight_attr=None, name=None):
        super().__init__()
        self.gate_proj = Linear(hidden_size, intermediate_size,
                                weight_attr=weight_attr, bias_attr=False)
        self.up_proj = Linear(hidden_size, intermediate_size,
                              weight_attr=weight_attr, bias_attr=False)
        self.down_proj = Linear(intermediate_size, hidden_size,
                                weight_attr=weight_attr, bias_attr=False)
        self._act = getattr(F, activation)

    def forward(self, x):
        gate = save_point(self.gate_proj(x), "mlp_gate")
        up = save_point(self.up_proj(x), "mlp_up")
        return self.down_proj(self._act(gate) * up)


class RotaryEmbedding(Layer):
    """Rotary position embedding over the whole last axis of its input
    [batch, seq, ..., dim] (see nn.functional.rotary_embedding: the pair
    of dim i is dim i + dim/2). Holds no parameter and no table: the
    angles follow the sequence length of each call."""

    def __init__(self, dim, theta=10000.0, name=None):
        super().__init__()
        if dim % 2:
            raise ValueError(f"rotary dim must be even, got {dim}")
        self._dim = dim
        self._theta = float(theta)

    def forward(self, x, position_ids=None):
        if x.shape[-1] != self._dim:
            raise ValueError(f"RotaryEmbedding({self._dim}) got a last "
                             f"axis of {x.shape[-1]}")
        return F.rotary_embedding(x, self._theta, position_ids)

    def extra_repr(self):
        return f"dim={self._dim}, theta={self._theta}"


class Embedding(Layer):
    def __init__(self, num_embeddings, embedding_dim, padding_idx=None,
                 sparse=False, weight_attr=None, name=None):
        super().__init__()
        self._num_embeddings = num_embeddings
        self._embedding_dim = embedding_dim
        self._padding_idx = None if padding_idx is None else (
            padding_idx if padding_idx >= 0
            else num_embeddings + padding_idx)
        self.weight = self.create_parameter(
            [num_embeddings, embedding_dim], attr=weight_attr,
            default_initializer=I.Normal(0.0, 1.0))
        if self._padding_idx is not None:
            w = np.array(self.weight.numpy())
            w[self._padding_idx] = 0
            self.weight.set_value(w)

    def forward(self, x):
        return F.embedding(x, self.weight, padding_idx=self._padding_idx)

    def extra_repr(self):
        return f"{self._num_embeddings}, {self._embedding_dim}"


class Flatten(Layer):
    def __init__(self, start_axis=1, stop_axis=-1):
        super().__init__()
        self.start_axis = start_axis
        self.stop_axis = stop_axis

    def forward(self, input):
        from ...tensor.manipulation import flatten
        return flatten(input, self.start_axis, self.stop_axis)


class Dropout(Layer):
    def __init__(self, p=0.5, axis=None, mode="upscale_in_train", name=None):
        super().__init__()
        self.p = p
        self.axis = axis
        self.mode = mode

    def forward(self, input):
        return F.dropout(input, self.p, axis=self.axis,
                         training=self.training, mode=self.mode)

    def extra_repr(self):
        return f"p={self.p}"


class Dropout2D(Layer):
    def __init__(self, p=0.5, data_format="NCHW", name=None):
        super().__init__()
        self.p = p
        self.data_format = data_format

    def forward(self, input):
        return F.dropout2d(input, self.p, training=self.training,
                           data_format=self.data_format)


class Dropout3D(Layer):
    def __init__(self, p=0.5, data_format="NCDHW", name=None):
        super().__init__()
        self.p = p
        self.data_format = data_format

    def forward(self, input):
        return F.dropout3d(input, self.p, training=self.training,
                           data_format=self.data_format)


class AlphaDropout(Layer):
    def __init__(self, p=0.5, name=None):
        super().__init__()
        self.p = p

    def forward(self, input):
        return F.alpha_dropout(input, self.p, training=self.training)


class Upsample(Layer):
    def __init__(self, size=None, scale_factor=None, mode="nearest",
                 align_corners=False, align_mode=0, data_format="NCHW",
                 name=None):
        super().__init__()
        self.size = size
        self.scale_factor = scale_factor
        self.mode = mode
        self.align_corners = align_corners
        self.align_mode = align_mode
        self.data_format = data_format

    def forward(self, x):
        return F.interpolate(x, self.size, self.scale_factor, self.mode,
                             self.align_corners, self.align_mode,
                             self.data_format)


class UpsamplingNearest2D(Layer):
    def __init__(self, size=None, scale_factor=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self.size = size
        self.scale_factor = scale_factor
        self.data_format = data_format

    def forward(self, x):
        return F.interpolate(x, self.size, self.scale_factor, "nearest",
                             data_format=self.data_format)


class UpsamplingBilinear2D(Layer):
    def __init__(self, size=None, scale_factor=None, data_format="NCHW",
                 name=None):
        super().__init__()
        self.size = size
        self.scale_factor = scale_factor
        self.data_format = data_format

    def forward(self, x):
        return F.interpolate(x, self.size, self.scale_factor, "bilinear",
                             align_corners=True,
                             data_format=self.data_format)


class _PadNd(Layer):
    def __init__(self, padding, mode, value, data_format):
        super().__init__()
        self.padding = padding
        self.mode = mode
        self.value = value
        self.data_format = data_format

    def forward(self, x):
        return F.pad(x, self.padding, self.mode, self.value,
                     self.data_format)


class Pad1D(_PadNd):
    def __init__(self, padding, mode="constant", value=0.0,
                 data_format="NCL", name=None):
        if isinstance(padding, int):
            padding = [padding, padding]
        super().__init__(padding, mode, value, data_format)


class Pad2D(_PadNd):
    def __init__(self, padding, mode="constant", value=0.0,
                 data_format="NCHW", name=None):
        if isinstance(padding, int):
            padding = [padding] * 4
        super().__init__(padding, mode, value, data_format)


class Pad3D(_PadNd):
    def __init__(self, padding, mode="constant", value=0.0,
                 data_format="NCDHW", name=None):
        if isinstance(padding, int):
            padding = [padding] * 6
        super().__init__(padding, mode, value, data_format)


class ZeroPad2D(Pad2D):
    def __init__(self, padding, data_format="NCHW", name=None):
        super().__init__(padding, "constant", 0.0, data_format)


class CosineSimilarity(Layer):
    def __init__(self, axis=1, eps=1e-8):
        super().__init__()
        self.axis = axis
        self.eps = eps

    def forward(self, x1, x2):
        return F.cosine_similarity(x1, x2, self.axis, self.eps)


class Bilinear(Layer):
    def __init__(self, in1_features, in2_features, out_features,
                 weight_attr=None, bias_attr=None, name=None):
        super().__init__()
        self.weight = self.create_parameter(
            [out_features, in1_features, in2_features], attr=weight_attr)
        self.bias = self.create_parameter([out_features], attr=bias_attr,
                                          is_bias=True)

    def forward(self, x1, x2):
        return F.bilinear(x1, x2, self.weight, self.bias)


class Unfold(Layer):
    def __init__(self, kernel_sizes, strides=1, paddings=0, dilations=1,
                 name=None):
        super().__init__()
        self.kernel_sizes = kernel_sizes
        self.strides = strides
        self.paddings = paddings
        self.dilations = dilations

    def forward(self, x):
        return F.unfold(x, self.kernel_sizes, self.strides, self.paddings,
                        self.dilations)


class Fold(Layer):
    def __init__(self, output_sizes, kernel_sizes, strides=1, paddings=0,
                 dilations=1, name=None):
        super().__init__()
        self.output_sizes = output_sizes
        self.kernel_sizes = kernel_sizes
        self.strides = strides
        self.paddings = paddings
        self.dilations = dilations

    def forward(self, x):
        return F.fold(x, self.output_sizes, self.kernel_sizes, self.strides,
                      self.paddings, self.dilations)
