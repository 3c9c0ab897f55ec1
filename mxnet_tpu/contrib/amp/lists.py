"""AMP op lists (reference: python/mxnet/contrib/amp/lists/symbol_fp16.py).

On TPU the low-precision target is **bfloat16** (same exponent range as fp32,
so no loss scaling is required for the default policy), but the classic fp16
policy with dynamic loss scaling is also supported for parity.

- ``TARGET_DTYPE_OPS``: MXU-bound ops whose float inputs are cast DOWN to the
  target dtype (matmul/conv FLOPs at 2x rate, halved HBM traffic).
- ``FP32_OPS``: numerically sensitive ops whose inputs are cast UP to fp32
  (softmax/exp/log reductions, losses).
- everything else runs in whatever dtype arrives (jnp type promotion handles
  mixed inputs; the norm layers internally accumulate statistics in fp32 —
  see ops/nn.py batch_norm/layer_norm).
"""

# ops that should run on the MXU in the low-precision target dtype
TARGET_DTYPE_OPS = [
    "Convolution",
    "Deconvolution",
    "FullyConnected",
    "dot",
    "batch_dot",
    "_contrib_interleaved_matmul_selfatt_qk",
    "_contrib_interleaved_matmul_selfatt_valatt",
    "_contrib_interleaved_matmul_encdec_qk",
    "_contrib_interleaved_matmul_encdec_valatt",
    "_contrib_flash_attention",
    "_contrib_moe_swiglu",
    "RNN",
]

# inputs (by position) of a TARGET_DTYPE_OPS op that keep their dtype: the
# expert layer's tokens, router weight and selection bias, so that its
# router logits, its scores and the choice of experts are float32; the op
# casts the tokens it gathers to the expert weights' dtype itself
KEEP_DTYPE_INPUTS = {
    "_contrib_moe_swiglu": (0, 1, 5),
}

# numerically sensitive ops pinned to fp32
FP32_OPS = [
    "softmax",
    "log_softmax",
    "softmin",
    "SoftmaxOutput",
    "SoftmaxActivation",
    "softmax_cross_entropy",
    "CTCLoss",
    "LRN",
    "L2Normalization",
    "InstanceNorm",
    "exp",
    "log",
    "log2",
    "log10",
    "log1p",
    "expm1",
    "power",
    "norm",
    "mean",
    "sum",
    "nansum",
    "prod",
    "nanprod",
    "cumsum",
    "erf",
    "erfinv",
    "gamma",
    "gammaln",
    "MakeLoss",
    "LinearRegressionOutput",
    "LogisticRegressionOutput",
    "MAERegressionOutput",
]

# kept for API parity with the reference lists module
FP16_FP32_OPS = []  # "run in either" — we leave input dtypes untouched
