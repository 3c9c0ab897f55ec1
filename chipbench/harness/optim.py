"""The reference's plain optimizer: Adam (Kingma & Ba 2014, with bias
correction), float32."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def init(name, params):
    if name != "adam":
        raise ValueError(f"unknown optimizer {name!r}")
    zeros = {k: jnp.zeros_like(v) for k, v in params.items()}
    return {"m": zeros, "v": dict(zeros), "t": 0}


@jax.jit
def _adam(params, grads, m, v, lr, b1, b2, eps, c1, c2):
    m = {k: b1 * m[k] + (1 - b1) * grads[k] for k in params}
    v = {k: b2 * v[k] + (1 - b2) * grads[k] ** 2 for k in params}
    new = {k: params[k] - lr * (m[k] / c1) / (jnp.sqrt(v[k] / c2) + eps)
           for k in params}
    return new, m, v


def update(params, grads, state, hyper):
    """One step; ``hyper`` holds MXNet's names for the hyper-parameters."""
    b1, b2 = hyper.get("beta1", 0.9), hyper.get("beta2", 0.999)
    t = state["t"] + 1
    new, m, v = _adam(params, grads, state["m"], state["v"],
                      hyper["learning_rate"], b1, b2,
                      hyper.get("epsilon", 1e-8), 1 - b1 ** t, 1 - b2 ** t)
    return new, {"m": m, "v": v, "t": t}
