"""Tensor operator family: elemwise, broadcast, reduce, matrix, indexing,
init, ordering, linalg.

Reference: ``src/operator/tensor/*.{cc,cu,h}`` (~90k LoC of C++/CUDA kernels,
SURVEY.md §3.2).  TPU-native: each op is one pure jax function — XLA fuses
elementwise chains into single kernels (replacing the reference's NVRTC
pointwise-fusion pass) and tiles matmuls onto the MXU, so there is nothing to
hand-schedule here.  Gradients come from ``jax.vjp`` (≙ FGradient attrs).
"""
from __future__ import annotations

import numpy as _np

from .registry import register


def _jnp():
    import jax.numpy as jnp

    return jnp


def _lax():
    from jax import lax

    return lax


def _norm_axis(axis):
    if isinstance(axis, list):
        return tuple(axis)
    return axis


# ==========================================================================
# elementwise unary  (reference: src/operator/tensor/elemwise_unary_op*.cc)
# ==========================================================================
def _unary(name, f, differentiable=True, aliases=()):
    def fn(x):
        return f(_jnp(), x)

    fn.__name__ = name
    register(name, differentiable=differentiable, aliases=aliases)(fn)


_unary("abs", lambda jnp, x: jnp.abs(x))
_unary("sign", lambda jnp, x: jnp.sign(x))
_unary("negative", lambda jnp, x: -x)
_unary("reciprocal", lambda jnp, x: 1.0 / x)
_unary("square", lambda jnp, x: jnp.square(x))
_unary("sqrt", lambda jnp, x: jnp.sqrt(x))
_unary("rsqrt", lambda jnp, x: 1.0 / jnp.sqrt(x))
_unary("cbrt", lambda jnp, x: jnp.cbrt(x))
_unary("rcbrt", lambda jnp, x: 1.0 / jnp.cbrt(x))
_unary("exp", lambda jnp, x: jnp.exp(x))
_unary("expm1", lambda jnp, x: jnp.expm1(x))
_unary("log", lambda jnp, x: jnp.log(x))
_unary("log2", lambda jnp, x: jnp.log2(x))
_unary("log10", lambda jnp, x: jnp.log10(x))
_unary("log1p", lambda jnp, x: jnp.log1p(x))
_unary("sin", lambda jnp, x: jnp.sin(x))
_unary("cos", lambda jnp, x: jnp.cos(x))
_unary("tan", lambda jnp, x: jnp.tan(x))
_unary("arcsin", lambda jnp, x: jnp.arcsin(x))
_unary("arccos", lambda jnp, x: jnp.arccos(x))
_unary("arctan", lambda jnp, x: jnp.arctan(x))
_unary("sinh", lambda jnp, x: jnp.sinh(x))
_unary("cosh", lambda jnp, x: jnp.cosh(x))
_unary("tanh", lambda jnp, x: jnp.tanh(x))
_unary("arcsinh", lambda jnp, x: jnp.arcsinh(x))
_unary("arccosh", lambda jnp, x: jnp.arccosh(x))
_unary("arctanh", lambda jnp, x: jnp.arctanh(x))
_unary("degrees", lambda jnp, x: jnp.degrees(x))
_unary("radians", lambda jnp, x: jnp.radians(x))
_unary("floor", lambda jnp, x: jnp.floor(x), differentiable=False)
_unary("ceil", lambda jnp, x: jnp.ceil(x), differentiable=False)
_unary("round", lambda jnp, x: jnp.round(x), differentiable=False)
_unary("rint", lambda jnp, x: jnp.rint(x), differentiable=False)
_unary("trunc", lambda jnp, x: jnp.trunc(x), differentiable=False)
# fix == truncate toward zero; jnp.trunc is the stable spelling (jnp.fix
# rides numpy's deprecation track)
_unary("fix", lambda jnp, x: jnp.trunc(x), differentiable=False)
_unary("gamma", lambda jnp, x: _gamma_impl(jnp, x))
_unary("gammaln", lambda jnp, x: _gammaln_impl(jnp, x))
_unary("erf", lambda jnp, x: _erf_impl(jnp, x))
_unary("erfinv", lambda jnp, x: _erfinv_impl(jnp, x))
_unary("relu", lambda jnp, x: jnp.maximum(x, 0))
_unary("sigmoid", lambda jnp, x: _sigmoid_impl(jnp, x))
_unary("softsign", lambda jnp, x: x / (1 + jnp.abs(x)))
_unary("logical_not", lambda jnp, x: (~(x != 0)).astype(x.dtype), differentiable=False)
_unary("identity", lambda jnp, x: x, aliases=("_copy", "stop_gradient_off"))
_unary("zeros_like", lambda jnp, x: jnp.zeros_like(x), differentiable=False)
_unary("ones_like", lambda jnp, x: jnp.ones_like(x), differentiable=False)
_unary("isnan", lambda jnp, x: jnp.isnan(x), differentiable=False)
_unary("isinf", lambda jnp, x: jnp.isinf(x), differentiable=False)
_unary("isfinite", lambda jnp, x: jnp.isfinite(x), differentiable=False)


def _sigmoid_impl(jnp, x):
    from jax import nn

    return nn.sigmoid(x)


def _erf_impl(jnp, x):
    from jax.scipy.special import erf

    return erf(x)


def _erfinv_impl(jnp, x):
    from jax.scipy.special import erfinv

    return erfinv(x)


def _gamma_impl(jnp, x):
    from jax.scipy.special import gammaln

    return jnp.exp(gammaln(x)) * jnp.sign(_reflection_sign(jnp, x))


def _reflection_sign(jnp, x):
    # gamma(x) sign for x<0 alternates; for the common positive domain this is 1
    return jnp.where(x > 0, 1.0, jnp.cos(jnp.pi * jnp.floor(x)) * 0 + 1.0)


def _gammaln_impl(jnp, x):
    from jax.scipy.special import gammaln

    return gammaln(x)


@register("stop_gradient", aliases=("BlockGrad", "block_grad"), differentiable=False)
def stop_gradient(x):
    return _lax().stop_gradient(x)


@register("clip")
def clip(x, a_min=None, a_max=None):
    return _jnp().clip(x, a_min, a_max)


@register("cast", aliases=("Cast", "amp_cast"))
def cast(x, dtype="float32"):
    jnp = _jnp()
    dt = jnp.bfloat16 if dtype == "bfloat16" else _np.dtype(dtype)
    return x.astype(dt)


# ==========================================================================
# elementwise binary (+broadcast, +scalar)
# (reference: src/operator/tensor/elemwise_binary*_op*.cc)
# jnp broadcasts natively, so elemwise_* and broadcast_* share impls.
# ==========================================================================
def _binary(name, f, differentiable=True, aliases=()):
    def fn(a, b):
        return f(_jnp(), a, b)

    fn.__name__ = name
    register(name, differentiable=differentiable, aliases=aliases)(fn)


_binary("broadcast_add", lambda jnp, a, b: a + b, aliases=("elemwise_add", "add"))
_binary("broadcast_sub", lambda jnp, a, b: a - b, aliases=("elemwise_sub", "subtract"))
_binary("broadcast_mul", lambda jnp, a, b: a * b, aliases=("elemwise_mul", "multiply"))
_binary("broadcast_div", lambda jnp, a, b: a / b, aliases=("elemwise_div", "divide"))
_binary("broadcast_mod", lambda jnp, a, b: jnp.mod(a, b), aliases=("mod",))
_binary("broadcast_power", lambda jnp, a, b: jnp.power(a, b), aliases=("power",))
_binary("broadcast_maximum", lambda jnp, a, b: jnp.maximum(a, b), aliases=("maximum",))
_binary("broadcast_minimum", lambda jnp, a, b: jnp.minimum(a, b), aliases=("minimum",))
_binary("broadcast_hypot", lambda jnp, a, b: jnp.hypot(a, b))
_binary("arctan2", lambda jnp, a, b: jnp.arctan2(a, b))
_binary("broadcast_equal", lambda jnp, a, b: (a == b).astype(_np.float32), differentiable=False, aliases=("equal",))
_binary("broadcast_not_equal", lambda jnp, a, b: (a != b).astype(_np.float32), differentiable=False, aliases=("not_equal",))
_binary("broadcast_greater", lambda jnp, a, b: (a > b).astype(_np.float32), differentiable=False, aliases=("greater",))
_binary("broadcast_greater_equal", lambda jnp, a, b: (a >= b).astype(_np.float32), differentiable=False, aliases=("greater_equal",))
_binary("broadcast_lesser", lambda jnp, a, b: (a < b).astype(_np.float32), differentiable=False, aliases=("lesser",))
_binary("broadcast_lesser_equal", lambda jnp, a, b: (a <= b).astype(_np.float32), differentiable=False, aliases=("lesser_equal",))
_binary("broadcast_logical_and", lambda jnp, a, b: ((a != 0) & (b != 0)).astype(_np.float32), differentiable=False, aliases=("logical_and",))
_binary("broadcast_logical_or", lambda jnp, a, b: ((a != 0) | (b != 0)).astype(_np.float32), differentiable=False, aliases=("logical_or",))
_binary("broadcast_logical_xor", lambda jnp, a, b: ((a != 0) ^ (b != 0)).astype(_np.float32), differentiable=False, aliases=("logical_xor",))


def _binary_scalar(name, f, differentiable=True):
    def fn(a, scalar=0.0, reverse=False):
        jnp = _jnp()
        s = scalar
        return f(jnp, s, a) if reverse else f(jnp, a, s)

    fn.__name__ = name + "_scalar"
    register(name + "_scalar", differentiable=differentiable)(fn)


_binary_scalar("broadcast_add", lambda jnp, a, b: a + b)
_binary_scalar("broadcast_sub", lambda jnp, a, b: a - b)
_binary_scalar("broadcast_mul", lambda jnp, a, b: a * b)
_binary_scalar("broadcast_div", lambda jnp, a, b: a / b)
_binary_scalar("broadcast_mod", lambda jnp, a, b: jnp.mod(a, b))
_binary_scalar("broadcast_power", lambda jnp, a, b: jnp.power(a, b))
_binary_scalar("broadcast_maximum", lambda jnp, a, b: jnp.maximum(a, b))
_binary_scalar("broadcast_minimum", lambda jnp, a, b: jnp.minimum(a, b))
_binary_scalar("broadcast_equal", lambda jnp, a, b: (a == b).astype(_np.float32), differentiable=False)
_binary_scalar("broadcast_not_equal", lambda jnp, a, b: (a != b).astype(_np.float32), differentiable=False)
_binary_scalar("broadcast_greater", lambda jnp, a, b: (a > b).astype(_np.float32), differentiable=False)
_binary_scalar("broadcast_greater_equal", lambda jnp, a, b: (a >= b).astype(_np.float32), differentiable=False)
_binary_scalar("broadcast_lesser", lambda jnp, a, b: (a < b).astype(_np.float32), differentiable=False)
_binary_scalar("broadcast_lesser_equal", lambda jnp, a, b: (a <= b).astype(_np.float32), differentiable=False)


@register("where")
def where(cond, x, y):
    return _jnp().where(cond != 0, x, y)


@register("maximum_n")
def maximum_n(*arrays):
    jnp = _jnp()
    out = arrays[0]
    for a in arrays[1:]:
        out = jnp.maximum(out, a)
    return out


@register("add_n", aliases=("ElementWiseSum", "sum_n"))
def add_n(*arrays):
    out = arrays[0]
    for a in arrays[1:]:
        out = out + a
    return out


# ==========================================================================
# reductions  (reference: src/operator/tensor/broadcast_reduce_op*.cc)
# ==========================================================================
def _reduce(name, f, differentiable=True, aliases=()):
    def fn(x, axis=None, keepdims=False, exclude=False):
        jnp = _jnp()
        ax = _norm_axis(axis)
        if exclude and ax is not None:
            if isinstance(ax, int):
                ax = (ax,)
            ax = tuple(i for i in range(x.ndim) if i not in tuple(a % x.ndim for a in ax))
        return f(jnp, x, ax, keepdims)

    fn.__name__ = name
    register(name, differentiable=differentiable, aliases=aliases)(fn)


_reduce("sum", lambda jnp, x, ax, kd: jnp.sum(x, axis=ax, keepdims=kd), aliases=("sum_axis",))
_reduce("nansum", lambda jnp, x, ax, kd: jnp.nansum(x, axis=ax, keepdims=kd))
_reduce("mean", lambda jnp, x, ax, kd: jnp.mean(x, axis=ax, keepdims=kd))
_reduce("prod", lambda jnp, x, ax, kd: jnp.prod(x, axis=ax, keepdims=kd))
_reduce("nanprod", lambda jnp, x, ax, kd: jnp.nanprod(x, axis=ax, keepdims=kd))
_reduce("max", lambda jnp, x, ax, kd: jnp.max(x, axis=ax, keepdims=kd), aliases=("max_axis",))
_reduce("min", lambda jnp, x, ax, kd: jnp.min(x, axis=ax, keepdims=kd), aliases=("min_axis",))


@register("norm")
def norm(x, ord=2, axis=None, keepdims=False):
    jnp = _jnp()
    ax = _norm_axis(axis)
    if ord == 1:
        return jnp.sum(jnp.abs(x), axis=ax, keepdims=keepdims)
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=ax, keepdims=keepdims))


@register("argmax", differentiable=False)
def argmax(x, axis=None, keepdims=False):
    jnp = _jnp()
    r = jnp.argmax(x, axis=axis, keepdims=keepdims).astype(_np.float32)
    return r


@register("argmin", differentiable=False)
def argmin(x, axis=None, keepdims=False):
    return _jnp().argmin(x, axis=axis, keepdims=keepdims).astype(_np.float32)


@register("argmax_channel", differentiable=False)
def argmax_channel(x):
    return _jnp().argmax(x, axis=1).astype(_np.float32)


@register("moments", nout=2)
def moments(x, axes=None, keepdims=False):
    jnp = _jnp()
    ax = _norm_axis(axes)
    mean = jnp.mean(x, axis=ax, keepdims=keepdims)
    var = jnp.mean(jnp.square(x - jnp.mean(x, axis=ax, keepdims=True)), axis=ax,
                   keepdims=keepdims)
    return mean, var


# ==========================================================================
# matrix / shape manipulation (reference: src/operator/tensor/matrix_op.cc)
# ==========================================================================
@register("dot")
def dot(a, b, transpose_a=False, transpose_b=False):
    """MXNet dot: contracts last axis of a with first axis of b (after
    optional transposes).  Lowers straight to the MXU."""
    jnp = _jnp()
    if transpose_a:
        a = jnp.transpose(a)
    if transpose_b:
        b = jnp.transpose(b)
    return jnp.tensordot(a, b, axes=1) if a.ndim > 1 or b.ndim > 1 else jnp.dot(a, b)


@register("batch_dot")
def batch_dot(a, b, transpose_a=False, transpose_b=False):
    jnp = _jnp()
    if transpose_a:
        a = jnp.swapaxes(a, -1, -2)
    if transpose_b:
        b = jnp.swapaxes(b, -1, -2)
    return jnp.matmul(a, b)


@register("matmul")
def matmul(a, b):
    return _jnp().matmul(a, b)


@register("reshape", aliases=("Reshape",))
def reshape(x, shape=None, reverse=False):
    return x.reshape(shape)


@register("transpose")
def transpose(x, axes=None):
    return _jnp().transpose(x, axes=axes)


@register("flatten", aliases=("Flatten",))
def flatten(x):
    return x.reshape((x.shape[0], -1))


@register("expand_dims")
def expand_dims(x, axis=0):
    return _jnp().expand_dims(x, axis)


@register("squeeze")
def squeeze(x, axis=None):
    return _jnp().squeeze(x, axis=_norm_axis(axis))


@register("swapaxes", aliases=("SwapAxis",))
def swapaxes(x, dim1=0, dim2=1):
    return _jnp().swapaxes(x, dim1, dim2)


@register("broadcast_to")
def broadcast_to(x, shape=None):
    jnp = _jnp()
    # MXNet allows 0 meaning "keep this dim"
    tgt = tuple(s if s != 0 else x.shape[i] for i, s in enumerate(shape))
    return jnp.broadcast_to(x, tgt)


@register("broadcast_axis", aliases=("broadcast_axes",))
def broadcast_axis(x, axis=None, size=None):
    jnp = _jnp()
    axes = axis if isinstance(axis, (tuple, list)) else (axis,)
    sizes = size if isinstance(size, (tuple, list)) else (size,)
    tgt = list(x.shape)
    for a, s in zip(axes, sizes):
        tgt[a] = s
    return jnp.broadcast_to(x, tuple(tgt))


@register("concat", aliases=("Concat",))
def concat(*arrays, dim=1):
    return _jnp().concatenate(arrays, axis=dim)


@register("stack")
def stack(*arrays, axis=0):
    return _jnp().stack(arrays, axis=axis)


@register("split", aliases=("SliceChannel", "slice_channel"), nout="dynamic")
def split(x, num_outputs=1, axis=1, squeeze_axis=False):
    jnp = _jnp()
    parts = jnp.split(x, num_outputs, axis=axis)
    if squeeze_axis:
        parts = [jnp.squeeze(p, axis=axis) for p in parts]
    return tuple(parts) if len(parts) > 1 else parts[0]


@register("slice", aliases=("crop",))
def slice_op(x, begin=None, end=None, step=None):
    idx = tuple(slice(b, e, s)
                for b, e, s in zip(begin, end, step or (None,) * len(begin)))
    return x[idx]


@register("_slice_key")
def _slice_key(x, key=None):
    """Internal: differentiable basic indexing (used by NDArray.__getitem__
    under autograd recording)."""
    return x[key]


@register("_scatter_set_key")
def _scatter_set_key(x, v, key=None):
    """Internal: differentiable sliced write (NDArray.__setitem__ under
    autograd recording — SURVEY.md hard-part 1: the reference records
    in-place writes as write-var engine ops; here the functional update's
    vjp routes cotangents to the untouched region of ``x`` and the written
    ``v``)."""
    return x.at[key].set(v.astype(x.dtype))


@register("slice_axis")
def slice_axis(x, axis=0, begin=0, end=None):
    jnp = _jnp()
    return _lax().slice_in_dim(x, begin, end if end is not None else x.shape[axis],
                               axis=axis)


@register("slice_like")
def slice_like(x, like, axes=None):
    tgt = list(x.shape)
    axes = axes or range(x.ndim)
    for a in axes:
        tgt[a] = like.shape[a]
    idx = tuple(slice(0, t) for t in tgt)
    return x[idx]


@register("tile")
def tile(x, reps=None):
    return _jnp().tile(x, reps)


@register("repeat")
def repeat(x, repeats=1, axis=None):
    return _jnp().repeat(x, repeats, axis=axis)


@register("reverse", aliases=("flip",))
def reverse(x, axis=0):
    return _jnp().flip(x, axis=_norm_axis(axis))


@register("pad", aliases=("Pad",))
def pad(x, mode="constant", pad_width=None, constant_value=0.0):
    jnp = _jnp()
    pw = [(pad_width[2 * i], pad_width[2 * i + 1]) for i in range(len(pad_width) // 2)]
    if mode == "constant":
        return jnp.pad(x, pw, constant_values=constant_value)
    if mode == "edge":
        return jnp.pad(x, pw, mode="edge")
    if mode == "reflect":
        return jnp.pad(x, pw, mode="reflect")
    raise ValueError(f"unknown pad mode {mode}")


@register("depth_to_space")
def depth_to_space(x, block_size=1):
    jnp = _jnp()
    n, c, h, w = x.shape
    bs = block_size
    y = x.reshape(n, bs, bs, c // (bs * bs), h, w)
    y = jnp.transpose(y, (0, 3, 4, 1, 5, 2))
    return y.reshape(n, c // (bs * bs), h * bs, w * bs)


@register("space_to_depth")
def space_to_depth(x, block_size=1):
    jnp = _jnp()
    n, c, h, w = x.shape
    bs = block_size
    y = x.reshape(n, c, h // bs, bs, w // bs, bs)
    y = jnp.transpose(y, (0, 3, 5, 1, 2, 4))
    return y.reshape(n, c * bs * bs, h // bs, w // bs)


@register("diag")
def diag(x, k=0):
    jnp = _jnp()
    if x.ndim == 1:
        return jnp.diag(x, k)
    return jnp.diagonal(x, offset=k, axis1=-2, axis2=-1)


# ==========================================================================
# indexing ops (reference: src/operator/tensor/indexing_op.cc)
# ==========================================================================
@register("take")
def take(a, indices, axis=0, mode="clip"):
    jnp = _jnp()
    idx = indices.astype(_np.int32)
    if mode == "wrap":
        idx = jnp.mod(idx, a.shape[axis])
    else:
        idx = jnp.clip(idx, 0, a.shape[axis] - 1)
    return jnp.take(a, idx, axis=axis)


def _embedding(data, weight, hands_on):
    from .. import telemetry
    from . import embed_add_rows

    jnp = _jnp()
    idx = jnp.clip(data.astype(_np.int32), 0, weight.shape[0] - 1)
    by_rows = embed_add_rows.use_pallas(weight, idx.size)
    telemetry.EMBEDDING_GRAD_CALLS.labels(
        path="rows" if by_rows else "scatter").inc()
    if by_rows:
        return embed_add_rows.take_rows(weight, idx, hands_on)
    rows = jnp.take(weight, idx, axis=0)
    return (rows, weight) if hands_on else rows


@register("Embedding", aliases=("embedding",))
def embedding(data, weight, input_dim=None, output_dim=None, dtype=None,
              sparse_grad=False):
    return _embedding(data, weight, False)


@register("_contrib_shared_embedding", nout=2, aliases=("shared_embedding",))
def shared_embedding(data, weight):
    """``Embedding`` that hands its table on: ``(rows, weight)``, the table
    unchanged, for a head tied to it to read in the embedding's place.  The
    head's gradient of the table then comes back through this op, whose rule
    adds the rows' gradient into it in place (``ops/embed_add_rows.py``);
    off that kernel's gate the table handed on is the one handed in and the
    program is ``Embedding``'s."""
    return _embedding(data, weight, True)


@register("one_hot", differentiable=False)
def one_hot(indices, depth=1, on_value=1.0, off_value=0.0, dtype="float32"):
    from jax import nn

    jnp = _jnp()
    oh = nn.one_hot(indices.astype(_np.int32), depth, dtype=_np.dtype(dtype))
    return oh * (on_value - off_value) + off_value


@register("gather_nd")
def gather_nd(data, indices):
    idx = tuple(indices.astype(_np.int32))
    return data[idx]


@register("scatter_nd", differentiable=False)
def scatter_nd(data, indices, shape=None):
    jnp = _jnp()
    out = jnp.zeros(shape, dtype=data.dtype)
    idx = tuple(indices.astype(_np.int32))
    return out.at[idx].set(data)


@register("pick")
def pick(data, index, axis=-1, keepdims=False, mode="clip"):
    jnp = _jnp()
    idx = jnp.clip(index.astype(_np.int32), 0, data.shape[axis] - 1)
    picked = jnp.take_along_axis(data, jnp.expand_dims(idx, axis), axis=axis)
    if not keepdims:
        picked = jnp.squeeze(picked, axis=axis)
    return picked


@register("where_index", differentiable=False)
def where_index(x):
    # dynamic-size output: materialized on host; used only eagerly
    return _jnp().asarray(_np.argwhere(_np.asarray(x)))


@register("boolean_mask", differentiable=False)
def boolean_mask(data, index, axis=0):
    mask = _np.asarray(index) != 0
    return _jnp().asarray(_np.compress(mask, _np.asarray(data), axis=axis))


@register("index_array", differentiable=False, creation=False)
def index_array(data, axes=None):
    jnp = _jnp()
    idx = jnp.stack(jnp.meshgrid(*[jnp.arange(s) for s in data.shape],
                                 indexing="ij"), axis=-1)
    if axes is not None:
        idx = idx[..., list(axes)]
    return idx.astype(_np.int64)


@register("sequence_mask", aliases=("SequenceMask",))
def sequence_mask(data, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0):
    jnp = _jnp()
    if sequence_length is None or not use_sequence_length:
        return data
    maxlen = data.shape[axis]
    steps = jnp.arange(maxlen)
    if axis == 0:
        mask = steps[:, None] < sequence_length[None, :]
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    else:  # axis == 1
        mask = steps[None, :] < sequence_length[:, None]
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, value)


@register("sequence_last", aliases=("SequenceLast",))
def sequence_last(data, sequence_length=None, use_sequence_length=False, axis=0):
    jnp = _jnp()
    if not use_sequence_length or sequence_length is None:
        return jnp.take(data, -1, axis=axis)
    idx = (sequence_length - 1).astype(_np.int32)
    return jnp.take_along_axis(
        data, idx.reshape((1, -1) + (1,) * (data.ndim - 2)), axis=axis
    ).squeeze(axis)


@register("sequence_reverse", aliases=("SequenceReverse",))
def sequence_reverse(data, sequence_length=None, use_sequence_length=False, axis=0):
    jnp = _jnp()
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(data, axis=axis)
    maxlen = data.shape[axis]
    steps = jnp.arange(maxlen)
    rev_idx = sequence_length[None, :] - 1 - steps[:, None]
    rev_idx = jnp.where(rev_idx >= 0, rev_idx, steps[:, None]).astype(_np.int32)
    return jnp.take_along_axis(
        data, rev_idx.reshape(rev_idx.shape + (1,) * (data.ndim - 2)), axis=0)


# ==========================================================================
# init ops (reference: src/operator/tensor/init_op.cc)
# ==========================================================================
@register("zeros", creation=True, differentiable=False)
def zeros(shape=None, dtype="float32"):
    return _jnp().zeros(shape, dtype=_dt(dtype))


@register("ones", creation=True, differentiable=False)
def ones(shape=None, dtype="float32"):
    return _jnp().ones(shape, dtype=_dt(dtype))


@register("full", creation=True, differentiable=False)
def full(shape=None, val=0.0, dtype="float32"):
    return _jnp().full(shape, val, dtype=_dt(dtype))


@register("arange", creation=True, differentiable=False)
def arange(start=0, stop=None, step=1.0, repeat=1, dtype="float32"):
    jnp = _jnp()
    r = jnp.arange(start, stop, step, dtype=_dt(dtype))
    if repeat != 1:
        r = jnp.repeat(r, repeat)
    return r


@register("linspace", creation=True, differentiable=False)
def linspace(start=0, stop=1, num=50, endpoint=True, dtype="float32"):
    return _jnp().linspace(start, stop, num, endpoint=endpoint, dtype=_dt(dtype))


@register("eye", creation=True, differentiable=False)
def eye(N=1, M=0, k=0, dtype="float32"):
    return _jnp().eye(int(N), int(M) if M else None, k=int(k), dtype=_dt(dtype))


def _dt(dtype):
    if dtype == "bfloat16" or dtype is None and False:
        return _jnp().bfloat16
    return _np.dtype(dtype)


# ==========================================================================
# ordering (reference: src/operator/tensor/ordering_op.cc)
# ==========================================================================
@register("sort")
def sort(x, axis=-1, is_ascend=True):
    jnp = _jnp()
    r = jnp.sort(x, axis=axis)
    return r if is_ascend else jnp.flip(r, axis=axis)


@register("argsort", differentiable=False)
def argsort(x, axis=-1, is_ascend=True, dtype="float32"):
    jnp = _jnp()
    r = jnp.argsort(x, axis=axis)
    if not is_ascend:
        r = jnp.flip(r, axis=axis)
    return r.astype(_np.dtype(dtype))


@register("topk", differentiable=False, nout="dynamic")
def topk(x, axis=-1, k=1, ret_typ="indices", is_ascend=False, dtype="float32"):
    jnp = _jnp()
    vals = x if not is_ascend else -x
    if axis != -1 and axis != x.ndim - 1:
        vals_m = jnp.moveaxis(vals, axis, -1)
    else:
        vals_m = vals
    top_v, top_i = _lax().top_k(vals_m, k)
    if is_ascend:
        top_v = -top_v
    if axis != -1 and axis != x.ndim - 1:
        top_v = jnp.moveaxis(top_v, -1, axis)
        top_i = jnp.moveaxis(top_i, -1, axis)
    if ret_typ == "indices":
        return top_i.astype(_np.dtype(dtype))
    if ret_typ == "value":
        return top_v
    if ret_typ == "both":
        return top_v, top_i.astype(_np.dtype(dtype))
    if ret_typ == "mask":
        from jax import nn as _jnn

        # top_i: (..., k) indices into the (moved-to-last) axis; one-hot over
        # the class dim then sum over k -> 0/1 mask shaped like x
        oh = _jnp().sum(_jnn.one_hot(top_i if axis in (-1, x.ndim - 1)
                                     else jnp.moveaxis(top_i, axis, -1),
                                     x.shape[axis], dtype=x.dtype), axis=-2)
        if axis not in (-1, x.ndim - 1):
            oh = jnp.moveaxis(oh, -1, axis)
        return oh
    raise ValueError(ret_typ)


@register("shuffle", needs_rng=True, differentiable=False)
def shuffle(key, x):
    from jax import random as jr

    return jr.permutation(key, x, axis=0)


# ==========================================================================
# linalg namespace (reference: src/operator/tensor/la_op.cc)
# ==========================================================================
@register("linalg_gemm")
def linalg_gemm(A, B, C, transpose_a=False, transpose_b=False, alpha=1.0, beta=1.0):
    jnp = _jnp()
    a = jnp.swapaxes(A, -1, -2) if transpose_a else A
    b = jnp.swapaxes(B, -1, -2) if transpose_b else B
    return alpha * jnp.matmul(a, b) + beta * C


@register("linalg_gemm2")
def linalg_gemm2(A, B, transpose_a=False, transpose_b=False, alpha=1.0):
    jnp = _jnp()
    a = jnp.swapaxes(A, -1, -2) if transpose_a else A
    b = jnp.swapaxes(B, -1, -2) if transpose_b else B
    return alpha * jnp.matmul(a, b)


@register("linalg_potrf")
def linalg_potrf(A):
    return _jnp().linalg.cholesky(A)


@register("linalg_trsm")
def linalg_trsm(A, B, transpose=False, rightside=False, lower=True, alpha=1.0):
    from jax.scipy.linalg import solve_triangular

    a = A
    if transpose:
        a = _jnp().swapaxes(a, -1, -2)
        lower = not lower
    if rightside:
        x = solve_triangular(a.swapaxes(-1, -2), (alpha * B).swapaxes(-1, -2),
                             lower=not lower)
        return x.swapaxes(-1, -2)
    return solve_triangular(a, alpha * B, lower=lower)


@register("linalg_sumlogdiag")
def linalg_sumlogdiag(A):
    jnp = _jnp()
    return jnp.sum(jnp.log(jnp.diagonal(A, axis1=-2, axis2=-1)), axis=-1)


@register("linalg_syrk")
def linalg_syrk(A, transpose=False, alpha=1.0):
    jnp = _jnp()
    at = jnp.swapaxes(A, -1, -2)
    return alpha * (jnp.matmul(at, A) if transpose else jnp.matmul(A, at))


@register("linalg_det")
def linalg_det(A):
    return _jnp().linalg.det(A)


@register("linalg_inverse")
def linalg_inverse(A):
    return _jnp().linalg.inv(A)


@register("linalg_svd", nout=3)
def linalg_svd(A):
    jnp = _jnp()
    u, s, vt = jnp.linalg.svd(A, full_matrices=False)
    return u, s, vt


# ==========================================================================
# misc
# ==========================================================================
@register("histogram", differentiable=False, nout=2)
def histogram(x, bin_cnt=10, range=None):
    jnp = _jnp()
    lo, hi = range if range is not None else (float(_np.asarray(x).min()),
                                              float(_np.asarray(x).max()))
    cnt, edges = jnp.histogram(x, bins=int(bin_cnt), range=(lo, hi))
    return cnt.astype(_np.float32), edges


@register("amp_multicast", nout="dynamic")
def amp_multicast(*arrays, num_outputs=None):
    jnp = _jnp()
    # cast all to widest dtype among inputs (reference: amp_multicast)
    widest = _np.result_type(*[_np.dtype(a.dtype) if a.dtype != jnp.bfloat16 else _np.float32 for a in arrays])
    return tuple(a.astype(widest) for a in arrays)


# ==========================================================================
# misc late additions (reference: src/operator/tensor + contrib misc)
# ==========================================================================
@register("hard_sigmoid")
def hard_sigmoid(x, alpha=0.2, beta=0.5):
    jnp = _jnp()
    return jnp.clip(alpha * x + beta, 0.0, 1.0)


@register("log_sigmoid")
def log_sigmoid(x):
    from jax import nn

    return nn.log_sigmoid(x)


@register("gelu")
def gelu_op(x):
    from jax import nn

    return nn.gelu(x, approximate=False)


@register("unravel_index", differentiable=False)
def unravel_index(x, shape=None):
    jnp = _jnp()
    idx = jnp.unravel_index(x.astype(_np.int64), shape)
    return jnp.stack(idx, axis=0)


@register("ravel_multi_index", differentiable=False)
def ravel_multi_index(x, shape=None):
    jnp = _jnp()
    strides = _np.concatenate([_np.cumprod(shape[::-1])[::-1][1:], [1]])
    return jnp.sum(x * jnp.asarray(strides)[:, None], axis=0)


@register("khatri_rao")
def khatri_rao(*mats):
    """Column-wise Kronecker product (reference:
    src/operator/contrib/krprod.cc)."""
    jnp = _jnp()
    out = mats[0]
    for m in mats[1:]:
        out = (out[:, None, :] * m[None, :, :]).reshape(-1, out.shape[1])
    return out


@register("_contrib_index_copy", aliases=("index_copy",))
def index_copy(old, index, new):
    """Copy rows of ``new`` into ``old`` at ``index`` (reference:
    src/operator/contrib/index_copy.cc)."""
    return old.at[index.astype(_np.int32)].set(new)


@register("_contrib_index_array", aliases=("index_array",),
          differentiable=False)
def index_array(data, axes=None):
    """Per-element N-D indices (reference: src/operator/contrib/index_array.cc)."""
    jnp = _jnp()
    shape = data.shape
    if axes is None:
        axes = tuple(range(len(shape)))
    elif isinstance(axes, int):
        axes = (axes,)
    grids = jnp.meshgrid(*[jnp.arange(s) for s in shape], indexing="ij")
    sel = [grids[a] for a in axes]
    return jnp.stack(sel, axis=-1).astype(_np.int64)


@register("broadcast_like")
def broadcast_like(lhs, rhs, lhs_axes=None, rhs_axes=None):
    """Broadcast lhs to rhs's shape (reference:
    src/operator/tensor/broadcast_reduce_op_value.cc broadcast_like)."""
    jnp = _jnp()
    if lhs_axes is None:
        return jnp.broadcast_to(lhs, rhs.shape)
    target = list(lhs.shape)
    for la, ra in zip(lhs_axes, rhs_axes):
        target[la] = rhs.shape[ra]
    return jnp.broadcast_to(lhs, tuple(target))


@register("batch_take")
def batch_take(a, indices):
    """Per-row element pick: out[i] = a[i, indices[i]] (reference:
    src/operator/tensor/indexing_op.cc batch_take)."""
    jnp = _jnp()
    idx = indices.astype("int32").reshape(-1)
    return jnp.take_along_axis(a, idx[:, None], axis=1)[:, 0]


@register("multi_sum_sq")
def multi_sum_sq(*arrays, num_arrays=None):
    """Sum of squares per input array (reference:
    src/operator/contrib/multi_sum_sq.cc — the global-norm building block
    for LAMB/clip_global_norm)."""
    jnp = _jnp()
    return jnp.stack([jnp.sum(jnp.square(a.astype(jnp.float32)))
                      for a in arrays])


@register("masked_softmax")
def masked_softmax(data, mask=None, axis=-1, temperature=1.0,
                   normalize=True):
    """Softmax with a boolean mask (reference:
    src/operator/nn/softmax.cc masked_softmax, 1.x)."""
    jnp = _jnp()
    z = data / temperature
    if mask is not None:
        z = jnp.where(mask != 0, z, -jnp.inf)
    z = z - jnp.max(jnp.where(jnp.isneginf(z), -1e30, z), axis=axis,
                    keepdims=True)
    e = jnp.exp(z)
    if mask is not None:
        e = jnp.where(mask != 0, e, 0.0)
    denom = jnp.sum(e, axis=axis, keepdims=True)
    return e / jnp.maximum(denom, 1e-30)


@register("digamma")
def digamma(x):
    from jax.scipy.special import digamma as _dg

    return _dg(x)
