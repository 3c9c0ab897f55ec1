"""Registry-driven op sweep (VERDICT r4 item 6).

For (nearly) every registered op: an fp32 execute + finiteness case, a
low-precision dtype ladder (bf16/fp16), a view-input consistency case, and
— where the op is differentiable — a numeric-gradient check through the
autograd tape.  This is the systematic analog of the reference's
~10k-line ``tests/python/unittest/test_operator.py`` oracle corpus
(SURVEY.md §5.1), generated from the op registry so new ops cannot ship
untested: the coverage-floor test at the bottom fails if the sweep covers
fewer than 300 registered names.

Everything dispatches through ``ndarray.invoke`` — the same seam AMP, the
profiler, and hybridize ride — so the sweep exercises the real path.
"""
import numpy as np
import pytest

import mxnet_tpu as mx  # noqa: F401  (registers the op table)
from mxnet_tpu.ndarray.ndarray import NDArray, array, invoke
from mxnet_tpu.ops.registry import OP_TABLE
from mxnet_tpu.util.test_utils import check_numeric_gradient

SEED = 12345


# ---------------------------------------------------------------------------
# input generators (numpy fp32); keep element counts tiny — the numeric-grad
# harness is O(elements) forward evaluations
# ---------------------------------------------------------------------------
def P(*shapes, lo=-1.0, hi=1.0):
    return lambda rs: [rs.uniform(lo, hi, s).astype("f") for s in shapes]


def POS(*shapes, lo=0.3, hi=1.6):
    return P(*shapes, lo=lo, hi=hi)


def AWAY0(*shapes, lo=0.25, hi=1.0):
    """Magnitudes in [lo, hi] with random sign: keeps kinked ops (abs,
    relu, sign...) away from their non-differentiable point."""

    def gen(rs):
        return [(rs.uniform(lo, hi, s) * rs.choice([-1.0, 1.0], s)
                 ).astype("f") for s in shapes]

    return gen


def DISTINCT(*shapes):
    """Well-separated values (sort/topk grads need no ties)."""

    def gen(rs):
        return [(rs.permutation(int(np.prod(s))).reshape(s) * 0.25 + 0.1
                 ).astype("f") for s in shapes]

    return gen


class S:
    """One op's sweep spec."""

    def __init__(self, inputs, kwargs=None, dtypes=("bfloat16", "float16"),
                 grad=None, grad_idx=None, post=None, rtol=1e-2, atol=1e-3,
                 int_dtypes=(), view=True):
        self.inputs = inputs
        self.kwargs = dict(kwargs or {})
        self.dtypes = dtypes
        self.grad = grad          # None -> registry differentiable flag
        self.grad_idx = grad_idx  # subset of inputs to grad-check
        self.post = post or (lambda o: o[0] if isinstance(o, (list, tuple))
                             else o)
        self.rtol, self.atol = rtol, atol
        self.int_dtypes = int_dtypes
        self.view = view


SPECS = {}


def add(names, *args, **kwargs):
    spec = S(*args, **kwargs)
    for n in ([names] if isinstance(names, str) else names):
        assert n not in SPECS, n
        SPECS[n] = spec


# --------------------------- elementwise unary -----------------------------
add(["sin", "cos", "tanh", "arctan", "arcsinh", "sigmoid", "log_sigmoid",
     "softsign", "gelu", "erf", "negative", "identity", "square",
     "hard_sigmoid", "degrees", "radians", "sinh", "cosh", "expm1",
     "cbrt", "smooth_l1"], P((2, 3)))
add(["abs", "relu", "sign"], AWAY0((2, 3)))
add(["exp"], P((2, 3), lo=-1.5, hi=1.0))
add(["tan"], P((2, 3), lo=-0.9, hi=0.9))
add(["arcsin", "arccos"], P((2, 3), lo=-0.8, hi=0.8))
add(["arctanh", "erfinv"], P((2, 3), lo=-0.7, hi=0.7))
add(["arccosh"], POS((2, 3), lo=1.3, hi=2.5))
add(["log", "log10", "log1p", "log2", "sqrt", "rsqrt", "rcbrt",
     "reciprocal", "gamma", "gammaln", "digamma"], POS((2, 3)))
add(["ceil", "floor", "round", "rint", "fix", "trunc", "logical_not",
     "isnan", "isinf", "isfinite", "zeros_like", "ones_like",
     "stop_gradient", "argmax_channel"], P((2, 3), lo=-2, hi=2),
    grad=False, int_dtypes=("int32",))
add("clip", AWAY0((2, 3)), kwargs={"a_min": -0.8, "a_max": 0.8})
add("cast", P((2, 3)), kwargs={"dtype": "float16"}, grad=False)
add("LeakyReLU", AWAY0((2, 3)), kwargs={"act_type": "leaky",
                                        "slope": 0.25})
add("Activation", AWAY0((2, 3)), kwargs={"act_type": "tanh"})

# --------------------------- binary broadcast ------------------------------
add(["broadcast_add", "broadcast_sub", "broadcast_mul", "broadcast_maximum",
     "broadcast_minimum", "broadcast_hypot", "arctan2"],
    AWAY0((2, 3), (1, 3)), int_dtypes=("int32",))
add(["broadcast_div", "broadcast_mod"],
    lambda rs: [rs.uniform(-1, 1, (2, 3)).astype("f"),
                rs.uniform(0.5, 1.5, (1, 3)).astype("f")])
add("broadcast_power", POS((2, 3), (1, 3)))
add(["broadcast_equal", "broadcast_not_equal", "broadcast_greater",
     "broadcast_greater_equal", "broadcast_lesser",
     "broadcast_lesser_equal", "broadcast_logical_and",
     "broadcast_logical_or", "broadcast_logical_xor"],
    P((2, 3), (1, 3), lo=-2, hi=2), grad=False, int_dtypes=("int32",))
add(["broadcast_add_scalar", "broadcast_sub_scalar", "broadcast_mul_scalar",
     "broadcast_maximum_scalar", "broadcast_minimum_scalar"],
    AWAY0((2, 3)), kwargs={"scalar": 0.7})
add(["broadcast_div_scalar", "broadcast_mod_scalar"],
    AWAY0((2, 3)), kwargs={"scalar": 0.7})
add("broadcast_power_scalar", POS((2, 3)), kwargs={"scalar": 1.3})
add(["broadcast_equal_scalar", "broadcast_not_equal_scalar",
     "broadcast_greater_scalar", "broadcast_greater_equal_scalar",
     "broadcast_lesser_scalar", "broadcast_lesser_equal_scalar"],
    P((2, 3)), kwargs={"scalar": 0.1}, grad=False)
add(["add_n", "maximum_n"], AWAY0((2, 3), (2, 3), (2, 3)))
add("where", P((2, 3), (2, 3), (2, 3)), grad_idx=[1, 2])

# --------------------------- reductions ------------------------------------
add(["sum", "mean", "nansum"], P((2, 3, 2)), kwargs={"axis": 1})
add(["max", "min"], DISTINCT((2, 3)), kwargs={"axis": 1})
add(["prod", "nanprod"], POS((2, 3)), kwargs={"axis": 0})
add("norm", AWAY0((2, 3)), kwargs={"axis": 1})
add("moments", P((2, 3)), kwargs={"axes": (0,)})
add(["argmax", "argmin"], DISTINCT((2, 4)), kwargs={"axis": 1},
    grad=False)
add("argsort", DISTINCT((2, 4)), grad=False)
add("sort", DISTINCT((2, 4)))
add("topk", DISTINCT((2, 4)), kwargs={"k": 2}, grad=False)
add("histogram", P((8,), lo=0, hi=1), kwargs={"bin_cnt": 4,
                                              "range": (0.0, 1.0)},
    grad=False)
add("multi_sum_sq", P((2, 2), (3,)), kwargs={"num_arrays": 2}, grad=False)

# --------------------------- shape / indexing ------------------------------
add("reshape", P((2, 6)), kwargs={"shape": (3, 4)})
add("flatten", P((2, 2, 3)))
add("expand_dims", P((2, 3)), kwargs={"axis": 1})
add("squeeze", P((2, 1, 3)))
add("transpose", P((2, 3, 2)), kwargs={"axes": (1, 0, 2)})
add("swapaxes", P((2, 3)), kwargs={"dim1": 0, "dim2": 1})
add("tile", P((2, 2)), kwargs={"reps": (2, 1)})
add("repeat", P((2, 2)), kwargs={"repeats": 2, "axis": 1})
add("broadcast_to", P((1, 3)), kwargs={"shape": (2, 3)})
add("broadcast_axis", P((1, 3)), kwargs={"axis": 0, "size": 2})
add("broadcast_like", P((1, 3), (2, 3)), grad_idx=[0])
add("slice", P((3, 4)), kwargs={"begin": (0, 1), "end": (2, 3)})
add("slice_axis", P((3, 4)), kwargs={"axis": 1, "begin": 1, "end": 3})
add("slice_like", P((3, 4), (2, 2)), grad_idx=[0])
add("reverse", P((3, 2)), kwargs={"axis": 0})
add("pad", P((1, 1, 3, 3)),
    kwargs={"mode": "constant",
            "pad_width": (0, 0, 0, 0, 1, 1, 1, 1)})
add("space_to_depth", P((1, 1, 4, 4)), kwargs={"block_size": 2})
add("depth_to_space", P((1, 4, 2, 2)), kwargs={"block_size": 2})
add("stack", P((2, 3), (2, 3)), kwargs={"axis": 1})
add("concat", P((2, 2), (2, 3)), kwargs={"dim": 1})
add("split", P((2, 4)), kwargs={"num_outputs": 2, "axis": 1})
add("diag", P((3, 3)))
add("one_hot", lambda rs: [rs.randint(0, 4, (3,)).astype("f")],
    kwargs={"depth": 4}, grad=False)
add("take", lambda rs: [rs.uniform(-1, 1, (4, 2)).astype("f"),
                        np.array([0, 2, 3], "f")],
    kwargs={"axis": 0}, grad_idx=[0])
add("batch_take", lambda rs: [rs.uniform(-1, 1, (3, 4)).astype("f"),
                              np.array([1, 0, 2], "f")], grad_idx=[0])
add("pick", lambda rs: [rs.uniform(-1, 1, (3, 4)).astype("f"),
                        np.array([1, 0, 2], "f")],
    kwargs={"axis": 1}, grad_idx=[0])
add("gather_nd", lambda rs: [rs.uniform(-1, 1, (3, 2)).astype("f"),
                             np.array([[0, 2], [1, 0]], "f")],
    grad_idx=[0])
add("scatter_nd", lambda rs: [rs.uniform(-1, 1, (2,)).astype("f"),
                              np.array([[0, 2]], "f")],
    kwargs={"shape": (4,)}, grad=False)
add("boolean_mask", lambda rs: [rs.uniform(-1, 1, (4, 2)).astype("f"),
                                np.array([1, 0, 1, 1], "f")],
    grad=False, view=False)
add("where_index", lambda rs: [np.array([0.0, 1.0, 0.0, 2.0], "f")],
    grad=False, view=False)
add("ravel_multi_index", lambda rs: [np.array([[1, 0], [2, 1]], "f")],
    kwargs={"shape": (3, 4)}, grad=False)
add("unravel_index", lambda rs: [np.array([5, 1], "f")],
    kwargs={"shape": (3, 4)}, grad=False)
add("_contrib_index_array", P((2, 3)), grad=False)
add("_contrib_index_copy", lambda rs: [
    rs.uniform(-1, 1, (4, 2)).astype("f"), np.array([1, 3], "f"),
    rs.uniform(-1, 1, (2, 2)).astype("f")], grad_idx=[0, 2])
add("sequence_mask", lambda rs: [rs.uniform(-1, 1, (3, 2, 2)).astype("f"),
                                 np.array([2, 3], "f")],
    kwargs={"use_sequence_length": True}, grad_idx=[0])
add("sequence_last", lambda rs: [rs.uniform(-1, 1, (3, 2, 2)).astype("f"),
                                 np.array([2, 3], "f")],
    kwargs={"use_sequence_length": True}, grad_idx=[0])
add("sequence_reverse", lambda rs: [
    rs.uniform(-1, 1, (3, 2, 2)).astype("f"), np.array([2, 3], "f")],
    kwargs={"use_sequence_length": True}, grad_idx=[0])

# --------------------------- creation --------------------------------------
add("arange", lambda rs: [], kwargs={"start": 0, "stop": 6, "step": 1.5},
    grad=False, view=False)
add("linspace", lambda rs: [], kwargs={"start": 0, "stop": 1, "num": 5},
    grad=False, view=False)
add("zeros", lambda rs: [], kwargs={"shape": (2, 3)}, grad=False,
    view=False)
add("ones", lambda rs: [], kwargs={"shape": (2, 3)}, grad=False,
    view=False)
add("full", lambda rs: [], kwargs={"shape": (2,), "val": 1.5}, grad=False,
    view=False)
add("eye", lambda rs: [], kwargs={"N": 3, "M": 4, "k": 1}, grad=False,
    view=False)

# --------------------------- linalg / contractions -------------------------
add("dot", P((2, 3), (3, 2)))
add("matmul", P((2, 3), (3, 2)))
add("batch_dot", P((2, 2, 3), (2, 3, 2)))
add("khatri_rao", P((2, 2), (3, 2)))
add("linalg_gemm", P((2, 3), (3, 2), (2, 2)),
    kwargs={"alpha": 0.5, "beta": 0.25})
add("linalg_gemm2", P((2, 3), (3, 2)))
add("linalg_syrk", P((2, 3)))
add("linalg_det",
    lambda rs: [(rs.uniform(-1, 1, (2, 2)) + 2 * np.eye(2)).astype("f")])
add("linalg_sumlogdiag",
    lambda rs: [(rs.uniform(0.5, 1.5, (3, 3)) + np.eye(3)).astype("f")])
add("linalg_inverse",
    lambda rs: [(rs.uniform(-0.3, 0.3, (3, 3)) + np.eye(3)).astype("f")],
    rtol=3e-2, atol=3e-3, dtypes=())  # XLA has no bf16/fp16 inverse
add("linalg_potrf",
    lambda rs: [(lambda L: L @ L.T + 0.5 * np.eye(3))(
        rs.uniform(0.2, 1.0, (3, 3))).astype("f")], rtol=3e-2, atol=3e-3,
    dtypes=())  # XLA has no bf16/fp16 cholesky
add("linalg_trsm",
    lambda rs: [(np.tril(rs.uniform(0.2, 0.6, (3, 3))) + np.eye(3)
                 ).astype("f"), rs.uniform(-1, 1, (3, 2)).astype("f")],
    rtol=3e-2, atol=3e-3)
add("linalg_svd", P((2, 3)), grad=False, dtypes=())

# --------------------------- softmax family --------------------------------
add(["softmax", "softmin", "log_softmax"], P((2, 4)))
add("masked_softmax", lambda rs: [rs.uniform(-1, 1, (2, 4)).astype("f"),
                                  np.array([[1, 1, 0, 1],
                                            [1, 0, 1, 1]], "f")],
    grad_idx=[0])

# --------------------------- NN layers -------------------------------------
add("FullyConnected", P((2, 3), (4, 3), (4,)), kwargs={"num_hidden": 4})
add("Convolution", P((1, 2, 4, 4), (3, 2, 2, 2), (3,)),
    kwargs={"kernel": (2, 2), "num_filter": 3}, rtol=3e-2, atol=3e-3)
add("Deconvolution", P((1, 2, 3, 3), (2, 3, 2, 2)),
    kwargs={"kernel": (2, 2), "stride": (2, 2), "num_filter": 3,
            "no_bias": True}, rtol=3e-2, atol=3e-3)
add("Pooling", P((1, 2, 4, 4)), kwargs={"kernel": (2, 2), "stride": (2, 2),
                                        "pool_type": "avg"})
add("BatchNorm", lambda rs: [rs.uniform(-1, 1, (2, 3, 2)).astype("f"),
                             np.ones(3, "f"), np.zeros(3, "f"),
                             np.zeros(3, "f"), np.ones(3, "f")],
    kwargs={"fix_gamma": False, "use_global_stats": True}, grad_idx=[0])
add("LayerNorm", P((2, 4), (4,), (4,)))
add("GroupNorm", P((2, 4, 2), (4,), (4,)), kwargs={"num_groups": 2},
    grad_idx=[0], rtol=3e-2, atol=3e-3)
add("InstanceNorm", P((2, 3, 4), (3,), (3,)), grad_idx=[0],
    rtol=3e-2, atol=3e-3)
add("rms_norm", P((2, 4), (4,)))
add("L2Normalization", AWAY0((2, 4)))
add("LRN", P((1, 4, 2, 2)), kwargs={"nsize": 3})
add("Dropout", P((2, 3)), kwargs={"mode": "always", "p": 0.0})
add("Embedding", lambda rs: [np.array([1, 0, 3], "f"),
                             rs.uniform(-1, 1, (4, 2)).astype("f")],
    kwargs={"input_dim": 4, "output_dim": 2}, grad_idx=[1])
add("UpSampling", P((1, 2, 2, 2)), kwargs={"scale": 2,
                                           "sample_type": "nearest"})
add("BilinearResize2D", P((1, 1, 3, 3)), kwargs={"height": 5, "width": 5})

# --------------------------- loss layers (custom vjp: execute-only) --------
add("SoftmaxOutput", lambda rs: [rs.uniform(-1, 1, (2, 3)).astype("f"),
                                 np.array([0, 2], "f")], grad=False)
add("SVMOutput", lambda rs: [rs.uniform(-1, 1, (2, 3)).astype("f"),
                             np.array([0, 2], "f")], grad=False)
add(["LinearRegressionOutput", "MAERegressionOutput",
     "LogisticRegressionOutput"],
    P((2, 3), (2, 3)), grad=False)
add("MakeLoss", P((2, 3)), grad=False)
add("CTCLoss", lambda rs: [rs.uniform(-1, 1, (4, 1, 5)).astype("f"),
                           np.array([[1, 2]], "f")], grad=False)

# --------------------------- attention / transformer -----------------------
add("swiglu", P((2, 3), (2, 3)))
add("rope", P((1, 2, 4, 4)))
add("_contrib_qk_norm_rope", P((1, 4, 8), (4,)),
    kwargs={"heads": 2, "norm": True})
add("_contrib_flash_attention", P((1, 2, 4, 4), (1, 2, 4, 4), (1, 2, 4, 4)),
    kwargs={"causal": True}, rtol=3e-2, atol=3e-3)
add("_contrib_interleaved_matmul_selfatt_qk", P((3, 1, 12)),
    kwargs={"heads": 2})
add("_contrib_interleaved_matmul_selfatt_valatt",
    P((3, 1, 12), (2, 3, 3)), kwargs={"heads": 2})
add("_contrib_interleaved_matmul_encdec_qk", P((3, 1, 4), (3, 1, 8)),
    kwargs={"heads": 2})
add("_contrib_interleaved_matmul_encdec_valatt", P((3, 1, 8), (2, 3, 3)),
    kwargs={"heads": 2})
add("_contrib_moe_swiglu", P((1, 4, 6), (6, 2), (2, 6, 4), (2, 6, 4),
                             (2, 4, 6)),
    kwargs={"capacity_factor": 4.0}, grad_idx=[0], rtol=3e-2, atol=3e-3)

# --------------------------- vision / detection ----------------------------
add("Correlation", P((1, 2, 5, 5), (1, 2, 5, 5)),
    kwargs={"kernel_size": 1, "max_displacement": 1, "pad_size": 1},
    rtol=3e-2, atol=3e-3)
add("ROIPooling", lambda rs: [rs.uniform(-1, 1, (1, 2, 6, 6)).astype("f"),
                              np.array([[0, 0, 0, 4, 4]], "f")],
    kwargs={"pooled_size": (2, 2), "spatial_scale": 1.0}, grad=False)
add("_contrib_ROIAlign",
    lambda rs: [rs.uniform(-1, 1, (1, 2, 6, 6)).astype("f"),
                np.array([[0, 0.5, 0.5, 4.0, 4.0]], "f")],
    kwargs={"pooled_size": (2, 2), "spatial_scale": 1.0}, grad_idx=[0],
    rtol=3e-2, atol=3e-3)
add("_contrib_PSROIPooling",
    lambda rs: [rs.uniform(-1, 1, (1, 8, 6, 6)).astype("f"),
                np.array([[0, 0, 0, 4, 4]], "f")],
    kwargs={"output_dim": 2, "pooled_size": 2, "spatial_scale": 1.0},
    grad=False)
add("_contrib_DeformableConvolution",
    P((1, 2, 4, 4), (1, 8, 3, 3), (2, 2, 2, 2)),
    kwargs={"kernel": (2, 2), "num_filter": 2, "no_bias": True},
    grad=False)
add("_contrib_box_iou", lambda rs: [np.array([[0, 0, 2, 2]], "f"),
                                    np.array([[1, 1, 3, 3]], "f")],
    grad=False)
add("_contrib_box_nms",
    lambda rs: [np.array([[[0, 0.9, 0, 0, 2, 2],
                           [0, 0.8, 0.1, 0.1, 2, 2]]], "f")], grad=False,
    view=False)
add("_contrib_bipartite_matching", P((3, 3), lo=0, hi=1), grad=False)
add("_contrib_MultiBoxPrior", P((1, 2, 4, 4)),
    kwargs={"sizes": (0.5,), "ratios": (1.0,)}, grad=False)
add("_contrib_MultiBoxDetection",
    lambda rs: [np.array([[[0.1, 0.9], [0.8, 0.2]]], "f").reshape(1, 2, 2),
                rs.uniform(-0.1, 0.1, (1, 8)).astype("f"),
                np.array([[[0.1, 0.1, 0.4, 0.4],
                           [0.5, 0.5, 0.9, 0.9]]], "f")], grad=False,
    view=False)
add("_contrib_MultiBoxTarget",
    lambda rs: [np.array([[[0.1, 0.1, 0.4, 0.4],
                           [0.5, 0.5, 0.9, 0.9]]], "f"),
                np.array([[[0, 0.1, 0.1, 0.45, 0.45]]], "f"),
                rs.uniform(0, 1, (1, 2, 2)).astype("f")], grad=False,
    view=False)
add("_contrib_Proposal",
    lambda rs: [rs.uniform(0, 1, (1, 2, 2, 2)).astype("f"),
                rs.uniform(-0.1, 0.1, (1, 4, 2, 2)).astype("f"),
                np.array([[32, 32, 1.0]], "f")],
    kwargs={"scales": (8,), "ratios": (1.0,), "rpn_pre_nms_top_n": 4,
            "rpn_post_nms_top_n": 2, "rpn_min_size": 1}, grad=False,
    view=False)
add("BilinearSampler",
    lambda rs: [rs.uniform(-1, 1, (1, 1, 4, 4)).astype("f"),
                rs.uniform(-0.9, 0.9, (1, 2, 3, 3)).astype("f")],
    rtol=3e-2, atol=3e-3)
add("GridGenerator", P((1, 6)),
    kwargs={"transform_type": "affine", "target_shape": (3, 3)})
add("SpatialTransformer",
    lambda rs: [rs.uniform(-1, 1, (1, 1, 4, 4)).astype("f"),
                np.array([[1.0, 0, 0.1, 0, 1.0, -0.1]], "f")],
    kwargs={"target_shape": (3, 3), "transform_type": "affine",
            "sampler_type": "bilinear"}, rtol=3e-2, atol=3e-3)

# --------------------------- image ops -------------------------------------
add(["image_flip_left_right", "image_flip_top_bottom"],
    P((4, 4, 3), lo=0, hi=1))
add("image_normalize", P((3, 4, 4), lo=0, hi=1),
    kwargs={"mean": 0.5, "std": 0.25})
add("image_to_tensor", P((4, 4, 3), lo=0, hi=1))
add("image_resize", P((4, 4, 3), lo=0, hi=1), kwargs={"size": (2, 2)},
    grad=False)
add("image_crop", P((4, 4, 3), lo=0, hi=1),
    kwargs={"x0": 1, "y0": 1, "width": 2, "height": 2})
add(["image_random_brightness", "image_random_contrast",
     "image_random_saturation", "image_random_hue"],
    P((4, 4, 3), lo=0, hi=1), kwargs={"min_factor": 0.8,
                                      "max_factor": 1.2}, grad=False)
add("image_random_color_jitter", P((4, 4, 3), lo=0, hi=1),
    kwargs={"brightness": 0.1}, grad=False)
add("image_random_lighting", P((4, 4, 3), lo=0, hi=1), grad=False)
add(["image_random_flip_left_right", "image_random_flip_top_bottom"],
    P((4, 4, 3), lo=0, hi=1), grad=False)

# --------------------------- random / sampling -----------------------------
add(["random_uniform", "random_normal"], lambda rs: [],
    kwargs={"shape": (2, 3)}, grad=False, view=False)
add("random_gamma", lambda rs: [], kwargs={"alpha": 2.0, "shape": (2,)},
    grad=False, view=False)
add("random_exponential", lambda rs: [], kwargs={"lam": 1.5,
                                                 "shape": (2,)},
    grad=False, view=False)
add("random_poisson", lambda rs: [], kwargs={"lam": 2.0, "shape": (2,)},
    grad=False, view=False)
add("random_negative_binomial", lambda rs: [],
    kwargs={"k": 2, "p": 0.5, "shape": (2,)}, grad=False, view=False)
add("random_randint", lambda rs: [], kwargs={"low": 0, "high": 5,
                                             "shape": (2,)},
    grad=False, view=False)
add("bernoulli", lambda rs: [], kwargs={"prob": 0.5, "shape": (2, 2)},
    grad=False, view=False)
add("sample_multinomial", lambda rs: [np.array([[0.2, 0.3, 0.5]], "f")],
    grad=False)
add(["sample_uniform_like", "sample_normal_like"], P((2, 2)), grad=False)
add("shuffle", P((4, 2)), grad=False)
add("_random_pdf_uniform",
    lambda rs: [rs.uniform(0.1, 0.9, (1, 3)).astype("f"),
                np.array([0.0], "f"), np.array([1.0], "f")], grad_idx=[0])
add("_random_pdf_normal", lambda rs: [rs.uniform(-1, 1, (1, 3)).astype("f"),
                                      np.array([0.1], "f"),
                                      np.array([1.2], "f")])
add("_random_pdf_gamma",
    lambda rs: [rs.uniform(0.5, 2, (1, 3)).astype("f"),
                np.array([2.0], "f"), np.array([1.5], "f")])
add("_random_pdf_exponential",
    lambda rs: [rs.uniform(0.2, 2, (1, 3)).astype("f"),
                np.array([1.5], "f")])
add("_random_pdf_poisson", lambda rs: [np.array([[0, 1, 3]], "f"),
                                       np.array([2.0], "f")], grad_idx=[1])
add("_random_pdf_negative_binomial",
    lambda rs: [np.array([[0, 1, 2]], "f"), np.array([3.0], "f"),
                np.array([0.4], "f")], grad_idx=[1, 2])
add("_random_pdf_generalized_negative_binomial",
    lambda rs: [np.array([[0, 1, 2]], "f"), np.array([2.0], "f"),
                np.array([0.5], "f")], grad_idx=[1, 2])
add("_random_pdf_dirichlet",
    lambda rs: [np.array([[[0.2, 0.3, 0.5]]], "f"),
                np.array([[1.5, 2.0, 1.2]], "f")], grad_idx=[1])

# --------------------------- optimizer update kernels ----------------------
add("sgd_update", P((3,), (3,)), kwargs={"lr": 0.1}, grad=False)
add("sgd_mom_update", P((3,), (3,), (3,)), kwargs={"lr": 0.1,
                                                   "momentum": 0.9},
    grad=False)
add("adam_update", P((3,), (3,), (3,), (3,)), kwargs={"lr": 0.01},
    grad=False)
add("nag_mom_update", P((3,), (3,), (3,)), kwargs={"lr": 0.1,
                                                   "momentum": 0.9},
    grad=False)
add("adagrad_update", lambda rs: [rs.uniform(-1, 1, 3).astype("f"),
                                  rs.uniform(-1, 1, 3).astype("f"),
                                  rs.uniform(0, 1, 3).astype("f")],
    kwargs={"lr": 0.1}, grad=False)
add("adadelta_update", lambda rs: [rs.uniform(-1, 1, 3).astype("f"),
                                   rs.uniform(-1, 1, 3).astype("f"),
                                   rs.uniform(0, 1, 3).astype("f"),
                                   rs.uniform(0, 1, 3).astype("f")],
    grad=False)
add("rmsprop_update", lambda rs: [rs.uniform(-1, 1, 3).astype("f"),
                                  rs.uniform(-1, 1, 3).astype("f"),
                                  rs.uniform(0, 1, 3).astype("f")],
    kwargs={"lr": 0.01}, grad=False)
add("rmspropalex_update", lambda rs: [rs.uniform(-1, 1, 3).astype("f"),
                                      rs.uniform(-1, 1, 3).astype("f"),
                                      rs.uniform(0.5, 1, 3).astype("f"),
                                      np.zeros(3, "f"),
                                      np.zeros(3, "f")],
    kwargs={"lr": 0.01}, grad=False)
add("ftrl_update", lambda rs: [rs.uniform(-1, 1, 3).astype("f"),
                               rs.uniform(-1, 1, 3).astype("f"),
                               rs.uniform(-1, 1, 3).astype("f"),
                               rs.uniform(0, 1, 3).astype("f")],
    kwargs={"lr": 0.1}, grad=False)
add("ftml_update", lambda rs: [rs.uniform(-1, 1, 3).astype("f"),
                               rs.uniform(-1, 1, 3).astype("f"),
                               rs.uniform(0, 1, 3).astype("f"),
                               rs.uniform(0, 1, 3).astype("f"),
                               rs.uniform(-1, 1, 3).astype("f")],
    kwargs={"lr": 0.01, "t": 1}, grad=False)
add("signsgd_update", P((3,), (3,)), kwargs={"lr": 0.1}, grad=False)
add("signum_update", P((3,), (3,), (3,)), kwargs={"lr": 0.1}, grad=False)
add("lamb_update_phase1", P((3,), (3,), (3,), (3,)), kwargs={"t": 1},
    grad=False)
add("lamb_update_phase2",
    lambda rs: [rs.uniform(-1, 1, 3).astype("f"),
                rs.uniform(-1, 1, 3).astype("f"),
                np.array([1.0], "f"), np.array([1.0], "f")],
    kwargs={"lr": 0.01}, grad=False)
add("multi_sgd_update", P((3,), (3,), (2,), (2,)),
    kwargs={"lrs": (0.1, 0.1), "wds": (0.0, 0.0), "num_weights": 2},
    grad=False)

# --------------------------- quantization ----------------------------------
add("_contrib_quantize_v2", P((2, 3)),
    kwargs={"min_calib_range": -1.0, "max_calib_range": 1.0}, grad=False)
add("_contrib_quantize", lambda rs: [rs.uniform(-1, 1, (2, 3)).astype("f"),
                                     np.array([-1.0], "f"),
                                     np.array([1.0], "f")], grad=False)
add("_contrib_dequantize",
    lambda rs: [rs.randint(-100, 100, (2, 3)).astype("int8"),
                np.array([-1.0], "f"), np.array([1.0], "f")],
    grad=False, dtypes=())
add("amp_multicast", P((2, 3), (4,)), kwargs={"num_outputs": 2},
    grad=False)
add("_sg_fused_dense_act", P((2, 3), (4, 3), (4,)),
    kwargs={"num_hidden": 4, "act_type": "relu"})
add("_sg_fused_conv_act", P((1, 2, 4, 4), (3, 2, 2, 2), (3,)),
    kwargs={"kernel": (2, 2), "num_filter": 3, "act_type": "relu"},
    rtol=3e-2, atol=3e-3)
add("_contrib_quantized_fully_connected",
    lambda rs: [rs.uniform(-1, 1, (2, 3)).astype("f"),
                rs.randint(-127, 127, (4, 3)).astype("int8"),
                np.array([0.02], "f"),
                np.array([-1.0, 1.0], "f"),
                rs.uniform(-0.1, 0.1, (4,)).astype("f")],
    kwargs={"num_hidden": 4}, grad=False, dtypes=())
add("_contrib_quantized_conv",
    lambda rs: [rs.uniform(-1, 1, (1, 2, 4, 4)).astype("f"),
                rs.randint(-127, 127, (3, 2, 2, 2)).astype("int8"),
                np.array([0.02], "f"),
                np.array([-1.0, 1.0], "f")],
    kwargs={"kernel": (2, 2), "num_filter": 3, "no_bias": True},
    grad=False, dtypes=())
add("_contrib_requantize",
    lambda rs: [rs.randint(-1000, 1000, (2, 3)).astype("int32"),
                np.array([-10.0], "f"), np.array([10.0], "f")],
    kwargs={"min_calib_range": -5.0, "max_calib_range": 5.0},
    grad=False, dtypes=())


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------
def _call(name, nds, kwargs):
    out = invoke(name, list(nds), dict(kwargs))
    return out


def _flat(out):
    return list(out) if isinstance(out, (list, tuple)) else [out]


def _finite(o):
    a = np.asarray(o.asnumpy(), dtype="float32") \
        if "float" in str(o.dtype) or "bfloat" in str(o.dtype) \
        else o.asnumpy()
    if a.dtype.kind == "f":
        assert np.isfinite(a).all()


@pytest.mark.parametrize("name", sorted(SPECS))
def test_op_executes_fp32_and_views(name):
    spec = SPECS[name]
    rs = np.random.RandomState(SEED)
    arrs = spec.inputs(rs)
    nds = [array(a) for a in arrs]
    out = _call(name, nds, spec.kwargs)
    for o in _flat(out):
        _finite(o)
    if not spec.view or not arrs or OP_TABLE[name].needs_rng:
        # rng ops draw a fresh key per invoke: view-vs-contiguous outputs
        # are intentionally different draws
        return
    # same op fed NDArray VIEWS (spec-chain slices) must agree exactly
    views = []
    for a in arrs:
        stacked = array(np.stack([np.zeros_like(a), a]))
        views.append(stacked[1])
    vout = _call(name, views, spec.kwargs)
    for o, v in zip(_flat(out), _flat(vout)):
        np.testing.assert_array_equal(np.asarray(o.asnumpy()),
                                      np.asarray(v.asnumpy()),
                                      err_msg=f"{name} view mismatch")


@pytest.mark.parametrize("name", sorted(
    n for n, s in SPECS.items() if s.dtypes))
def test_op_low_precision_ladder(name):
    """bf16 (the TPU compute dtype) and fp16 execute and stay finite."""
    spec = SPECS[name]
    for dt in spec.dtypes:
        rs = np.random.RandomState(SEED)
        arrs = spec.inputs(rs)
        if not arrs:
            continue
        import jax.numpy as jnp

        nds = []
        for a in arrs:
            if a.dtype.kind == "f":
                nds.append(NDArray._from_jax(
                    jnp.asarray(a).astype(dt), None))
            else:
                nds.append(array(a))
        out = _call(name, nds, spec.kwargs)
        for o in _flat(out):
            _finite(o)


@pytest.mark.parametrize("name", sorted(
    n for n, s in SPECS.items() if s.int_dtypes))
def test_op_int_ladder(name):
    spec = SPECS[name]
    for dt in spec.int_dtypes:
        rs = np.random.RandomState(SEED)
        arrs = spec.inputs(rs)
        nds = [array((a * 4).astype(dt)) for a in arrs]
        out = _call(name, nds, spec.kwargs)
        for o in _flat(out):
            _finite(o)


def _grad_enabled(name, spec):
    if spec.grad is not None:
        return spec.grad
    return OP_TABLE[name].differentiable


@pytest.mark.parametrize("name", sorted(
    n for n, s in SPECS.items() if _grad_enabled(n, s) and s.inputs(
        np.random.RandomState(0))))
def test_op_numeric_gradient(name):
    """Finite-difference check through the autograd tape (the reference's
    check_numeric_gradient oracle, SURVEY §5.1)."""
    spec = SPECS[name]
    rs = np.random.RandomState(SEED)
    arrs = spec.inputs(rs)
    sel = spec.grad_idx if spec.grad_idx is not None else \
        list(range(len(arrs)))
    consts = {i: array(a) for i, a in enumerate(arrs) if i not in sel}

    def f(*sel_nds):
        it = iter(sel_nds)
        full = [next(it) if i in sel else consts[i]
                for i in range(len(arrs))]
        return spec.post(_call(name, full, spec.kwargs))

    check_numeric_gradient(f, [arrs[i] for i in sel], rtol=spec.rtol,
                           atol=spec.atol)


@pytest.mark.parametrize("grad_req,op", [
    ("add", "relu"), ("add", "FullyConnected"), ("null", "relu"),
    ("null", "broadcast_mul"),
])
def test_grad_req_semantics(grad_req, op):
    """grad_req='add' accumulates across backward passes; 'null' never
    writes — the tape-level contract every swept op rides."""
    from mxnet_tpu import autograd

    rs = np.random.RandomState(SEED)
    x = array(rs.uniform(0.2, 1.0, (2, 3)).astype("f"))
    x.attach_grad(grad_req=grad_req)
    extra = []
    if op == "FullyConnected":
        w = array(rs.uniform(-1, 1, (4, 3)).astype("f"))
        b = array(np.zeros(4, "f"))
        extra, kw = [w, b], {"num_hidden": 4}
    else:
        kw = {}
        if op == "broadcast_mul":
            extra = [array(np.full((2, 3), 2.0, "f"))]
    for _ in range(2):
        with autograd.record():
            y = invoke(op, [x] + extra, kw)
            loss = y.sum()
        loss.backward()
    g = x.grad.asnumpy()
    # reference single-pass gradient with grad_req='write'
    x2 = array(x.asnumpy())
    x2.attach_grad()
    with autograd.record():
        y = invoke(op, [x2] + extra, kw)
        loss = y.sum()
    loss.backward()
    single = x2.grad.asnumpy()
    if grad_req == "null":
        assert np.allclose(g, 0.0)
    else:
        np.testing.assert_allclose(g, 2 * single, rtol=1e-5)


def test_sweep_covers_at_least_300_registered_names():
    """The VERDICT r4 item-6 'done' bar: >=300 of the registered op names
    carry at least one dtype-laddered, grad-checked (where differentiable)
    sweep case.  Aliases share their canonical op's spec."""
    covered = set()
    for key, od in OP_TABLE.items():
        if od.name in SPECS:
            covered.add(key)
    assert len(covered) >= 300, (
        f"sweep covers {len(covered)} of {len(OP_TABLE)} registered names")
    # and the sweep itself must not reference unknown ops
    unknown = [n for n in SPECS if n not in OP_TABLE]
    assert not unknown, unknown
