"""``correct``: the step object the window drives, followed through its
first steps by the configuration's plain reference.

Compared, each against a limit of its own from the cell's file (``PERF.md``
gives the readings every limit was set from):

- ``loss_gap``: each of the first steps' losses against the reference's,
  the widest relative gap;
- ``first_gradient_gap``: the norm of the first gradient as the optimizer
  got it, by the worst leaf;
- ``first_gradient_error``: the norm of the difference between that gradient
  and the reference's, by the worst leaf.  The gap of two norms takes a
  random sign from the rounding noise and swings from seed to seed; the
  norm of the difference measures the noise itself, is steady, and is what
  separates a lower precision (``PERF.md``, section 2);
- ``change_gap``: the norm of the parameters' change over those steps, by
  the worst leaf among those whose reference gradient is not all but zero
  (``NEGLIGIBLE`` of the median leaf's): a key bias has no gradient but
  rounding noise, and Adam turns noise into steps of the full size, in the
  program and in the reference alike, so such a leaf reads as the fault
  this number is there to catch (a step that returns its state unchanged).

By the worst leaf: measured against the reference's norm of that leaf or
of the median leaf, whichever is larger, since some gradients are all but
zero.  Nothing about the shape of the loss curve is looked at.
"""
from __future__ import annotations

import math
import statistics

import numpy as np


def follow(reference, cfg, precision, weights, batches, cell):
    """The reference's first ``len(batches)`` steps from ``weights``:
    losses, and the squared norms of the first gradient and of the
    parameters' change, leaf by leaf."""
    import jax
    import jax.numpy as jnp

    from chipbench.harness import optim

    @jax.jit
    def sq_gaps(a, b):
        return {k: jnp.sum(jnp.square(a[k] - b[k])) for k in a}

    hyper = cell["optimizer_params"]
    params, state = weights, optim.init(cell["optimizer"], weights)
    losses, first = [], None
    for batch in batches:
        loss, grads = reference.loss_and_grads(
            cfg, precision, params, batch, cell.get("reference_block_rows", 0))
        if first is None:
            first = jax.device_get(grads)
        params, state = optim.update(params, grads, state, hyper)
        losses.append(float(loss))
    change = jax.device_get(sq_gaps(params, weights))
    return {"losses": losses,
            "first_gradient": first,
            "change_sq": {k: float(v) for k, v in change.items()}}


NEGLIGIBLE = 1e-3


def _worst_leaf(gaps, ref_norms):
    floor = statistics.median(ref_norms.values())
    rel = {k: gaps[k] / max(ref_norms[k], floor) for k in gaps}
    leaf = max(rel, key=rel.get)
    return rel[leaf], leaf


def _norm(x):
    return float(np.sqrt(np.sum(np.square(x, dtype=np.float64))))


def leaf_numbers(got, ref):
    """Leaf by leaf: the reference's norm of the first gradient, the gap of
    the norms, the norm of the difference, and the same two of the change
    (its difference is not kept, so None)."""
    out = {}
    for k, v in ref["first_gradient"].items():
        g = got["first_gradient"][k].astype(np.float32)
        out[k] = {"g_ref": _norm(v), "g_gap": abs(_norm(g) - _norm(v)),
                  "g_err": _norm(g - v),
                  "c_ref": math.sqrt(ref["change_sq"][k]),
                  "c_gap": abs(math.sqrt(got["change_sq"][k])
                               - math.sqrt(ref["change_sq"][k]))}
    return out


def compare(got, ref):
    """``{statistic: (value, where)}`` of one followed run against the
    reference's, over every leaf.  A non-finite value compares as
    infinite."""
    loss = [abs(a - b) / abs(b) for a, b in zip(got["losses"], ref["losses"])]
    step = max(range(len(loss)), key=loss.__getitem__)
    numbers = leaf_numbers(got, ref)
    g_ref = {k: v["g_ref"] for k, v in numbers.items()}
    g_floor = NEGLIGIBLE * statistics.median(g_ref.values())
    c_ref = {k: v["c_ref"] for k, v in numbers.items()
             if v["g_ref"] > g_floor}
    out = {"loss_gap": (loss[step], f"step {step + 1}"),
           "first_gradient_gap": _worst_leaf(
               {k: v["g_gap"] for k, v in numbers.items()}, g_ref),
           "first_gradient_error": _worst_leaf(
               {k: v["g_err"] for k, v in numbers.items()}, g_ref),
           "change_gap": _worst_leaf(
               {k: numbers[k]["c_gap"] for k in c_ref}, c_ref)}
    return {k: (v if math.isfinite(v) else math.inf, where)
            for k, (v, where) in out.items()}


def verdict(stats, limits, say):
    """True when every statistic is within its limit; says each beside it."""
    ok = True
    for name, (value, where) in stats.items():
        good = value <= limits[name]
        ok &= good
        say(f"check {name}: {value:.6g} (limit {limits[name]:.6g}, at "
            f"{where}) {'ok' if good else 'NOT CORRECT'}")
    return ok
