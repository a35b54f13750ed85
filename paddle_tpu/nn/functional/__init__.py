"""paddle.nn.functional namespace.
Parity: python/paddle/nn/functional/__init__.py."""
from .activation import *  # noqa: F401,F403
from .common import (linear, dropout, dropout2d, dropout3d, alpha_dropout,
                     pad, zeropad2d, cosine_similarity, bilinear,
                     interpolate, upsample, unfold, fold, label_smooth)
from .conv import (conv1d, conv2d, conv3d, conv1d_transpose,
                   conv2d_transpose, conv3d_transpose)
from .norm import (normalize, layer_norm, rms_norm, batch_norm, instance_norm,
                   group_norm, local_response_norm)
from .pooling import (avg_pool1d, avg_pool2d, avg_pool3d, max_pool1d,
                      max_pool2d, max_pool3d, adaptive_avg_pool1d,
                      adaptive_avg_pool2d, adaptive_avg_pool3d,
                      adaptive_max_pool1d, adaptive_max_pool2d,
                      adaptive_max_pool3d, max_unpool2d)
from .loss import (cross_entropy, softmax_with_cross_entropy, nll_loss,
                   binary_cross_entropy, binary_cross_entropy_with_logits,
                   mse_loss, l1_loss, smooth_l1_loss, huber_loss, kl_div,
                   margin_ranking_loss, hinge_embedding_loss,
                   cosine_embedding_loss, soft_margin_loss,
                   triplet_margin_loss, triplet_margin_with_distance_loss,
                   square_error_cost, sigmoid_focal_loss, ctc_loss,
                   npair_loss)
from .input import one_hot, embedding
from .vision import (pixel_shuffle, pixel_unshuffle, channel_shuffle,
                     affine_grid, grid_sample)
from .extension import sequence_mask, temporal_shift, diag_embed
from .attention import (scaled_dot_product_attention, sparse_attention,
                        rotary_embedding)
from .misc_gap import (elu_, tanh_, max_unpool1d, max_unpool3d,
                       dice_loss, hsigmoid_loss, log_loss,
                       margin_cross_entropy, gather_tree,
                       class_center_sample)
