"""Precisions a reference can compute in, and the step below each.

A reference multiplies through ``ops(precision).round`` on both operands of
every matrix multiplication and convolution and under
``ops(precision).matmul``.  ``float32`` is the plain reference.  The others
are the controls of ``check.py``: the reference computed one step below
the precision a cell states, which has to come out as not correct.
"""
from __future__ import annotations

from collections import namedtuple

import jax
import jax.numpy as jnp

Ops = namedtuple("Ops", "round matmul")

# the nearest precision below the one a cell states
BELOW = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def _straight_through(x, rounded):
    return x + jax.lax.stop_gradient(rounded - x)


def _round_bfloat16(x):
    return _straight_through(x, x.astype(jnp.bfloat16).astype(x.dtype))


def _round_float8(x):
    # per-tensor scaling to the format's range, as fp8 training recipes do;
    # without it small activations flush to zero and the control is no
    # temptation at all
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return _straight_through(x, rounded)


def ops(precision):
    """``Ops(round, matmul)`` for a precision's name."""
    table = {
        "float32": Ops(lambda x: x, "highest"),
        "bfloat16": Ops(_round_bfloat16, "highest"),
        "float8_e4m3fn": Ops(_round_float8, "highest"),
    }
    if precision not in table:
        raise ValueError(f"unknown precision {precision!r}")
    return table[precision]
