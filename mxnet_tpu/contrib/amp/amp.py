"""Automatic mixed precision (reference: python/mxnet/contrib/amp/amp.py).

The reference's ``amp.init()`` monkey-patches the generated op namespaces to
insert ``amp_cast``/``amp_multicast`` around allow/deny-listed ops.  Here all
imperative and traced execution funnels through ``ndarray.invoke`` (the
MXImperativeInvokeEx analog), so one hook there applies the cast policy to
every path — eager NDArray code, ``hybridize()`` traces, and the fused
``parallel.TrainStep`` jit (which traces through the same invoke).

Casts are wrapped *inside* the op function so they are part of the traced
computation: under ``jax.vjp`` the cast's transpose casts gradients back to
the master-weight dtype (fp32), which is exactly the mixed-precision
master-weights contract.  XLA fuses the casts into the convolution/matmul
epilogues, so the policy costs no extra HBM passes.
"""
from __future__ import annotations

from contextlib import contextmanager

from ...base import MXNetError
from .loss_scaler import LossScaler
from . import lists

__all__ = ["init", "init_trainer", "scale_loss", "unscale", "convert_model",
           "convert_hybrid_block", "list_fp16_ops", "list_fp32_ops"]

_DEFAULT_TARGET = "bfloat16"

# monotonic policy-install token (never rewinds): two different scoped
# policies can never share a dispatch-cache key even after _cast_scope
# restores earlier state
_EPOCH = iter(range(1, 1 << 62)).__next__


def _amp_dict():
    from ...ndarray.ndarray import _AMP

    return _AMP


def _floating(v):
    import jax.numpy as jnp

    return hasattr(v, "dtype") and jnp.issubdtype(v.dtype, jnp.floating)


def _make_wrap(target_dtype, target_ops, fp32_ops):
    import jax.numpy as jnp

    tgt = jnp.dtype(target_dtype)
    f32 = jnp.dtype("float32")

    def wrap(od, fn):
        name = od.name
        if name in target_ops:
            to = tgt
        elif name in fp32_ops:
            to = f32
        else:
            return fn

        keep = lists.KEEP_DTYPE_INPUTS.get(name, ())

        def cast_fn(*arrays):
            cast = tuple(
                a.astype(to)
                if _floating(a) and a.dtype != to and i not in keep else a
                for i, a in enumerate(arrays))
            return fn(*cast)

        return cast_fn

    return wrap


def init(target_dtype=_DEFAULT_TARGET, target_dtype_ops=None, fp32_ops=None,
         conditional_fp32_ops=None, excluded_sym_names=None):
    """Enable AMP globally (reference: amp.init).

    target_dtype: 'bfloat16' (TPU default; no loss scaling needed) or
    'float16' (classic AMP; pair with a dynamic LossScaler via init_trainer).
    """
    if target_dtype not in ("bfloat16", "float16"):
        raise MXNetError(f"unsupported AMP target_dtype {target_dtype!r}")
    t_ops = frozenset(target_dtype_ops if target_dtype_ops is not None
                      else lists.TARGET_DTYPE_OPS)
    f_ops = frozenset(fp32_ops if fp32_ops is not None else lists.FP32_OPS)
    st = _amp_dict()
    st["wrap"] = _make_wrap(target_dtype, t_ops, f_ops)
    st["target"] = target_dtype
    # fresh policy token: the eager dispatch cache keys executables on it,
    # so re-init with different lists/dtype can never serve stale casts
    st["epoch"] = _EPOCH()
    st["on"] = True


def disable():
    """Turn AMP off (not in the reference API; useful for tests)."""
    st = _amp_dict()
    st["on"] = False
    st["wrap"] = None
    st["target"] = None
    st["epoch"] = _EPOCH()


@contextmanager
def _cast_scope(target_dtype=_DEFAULT_TARGET, target_dtype_ops=None,
                fp32_ops=None):
    """Scoped AMP: used by TrainStep(dtype=...) so the cast policy is active
    exactly while the model trace runs, without flipping global state for the
    caller's eager code."""
    st = _amp_dict()
    prev = dict(st)
    try:
        init(target_dtype, target_dtype_ops=target_dtype_ops,
             fp32_ops=fp32_ops)
        yield
    finally:
        st.update(prev)


def init_trainer(trainer, loss_scaler=None):
    """Attach dynamic loss scaling to a Gluon Trainer (reference:
    amp.init_trainer).  The trainer's step() gains overflow-skip semantics:
    non-finite scaled gradients skip the update and shrink the scale.

    Composes with the numerical-integrity guard: ``guard.attach`` must
    come AFTER init_trainer (the guard's unified step then owns both the
    verdict and the loss-scale bookkeeping, one host sync total) —
    wrapping an already-guarded trainer would re-split the sync."""
    st = _amp_dict()
    if not st["on"]:
        raise MXNetError("call amp.init() before amp.init_trainer()")
    if getattr(trainer, "_guard", None) is not None:
        raise MXNetError(
            "amp.init_trainer on a guard-attached trainer: attach order "
            "is amp first, then guard.attach (the guard step subsumes "
            "the AMP overflow sync)")
    if loss_scaler is None:
        loss_scaler = LossScaler(dynamic=(st["target"] == "float16"))
    trainer._amp_loss_scaler = loss_scaler
    trainer._amp_original_scale = trainer._scale
    trainer._amp_unscaled = False

    orig_step = trainer.step

    def amp_step(batch_size, ignore_stale_grad=False):
        scaler = trainer._amp_loss_scaler
        overflow = scaler.has_overflow(trainer._params)
        if not overflow:
            # if unscale() already divided the grads this iteration, don't
            # rescale again
            eff = 1.0 if trainer._amp_unscaled else scaler.loss_scale
            trainer._scale = trainer._amp_original_scale / eff
            orig_step(batch_size, ignore_stale_grad=ignore_stale_grad)
            trainer._scale = trainer._amp_original_scale
        trainer._amp_unscaled = False
        scaler.update_scale(overflow)

    trainer.step = amp_step
    return trainer


@contextmanager
def scale_loss(loss, trainer):
    """``with amp.scale_loss(loss, trainer) as scaled: scaled.backward()``"""
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is None or scaler.loss_scale == 1.0:
        yield loss
        return
    s = scaler.loss_scale
    if isinstance(loss, (list, tuple)):
        yield type(loss)(l * s for l in loss)
    else:
        yield loss * s


def unscale(trainer):
    """Divide current gradients by the loss scale in place (reference:
    amp.unscale — for gradient clipping between backward and step).  A
    one-shot flag tells the next trainer.step() not to rescale again; the
    dynamic loss scale itself is untouched."""
    scaler = getattr(trainer, "_amp_loss_scaler", None)
    if scaler is None or scaler.loss_scale == 1.0:
        return
    if getattr(trainer, "_amp_unscaled", False):
        return  # already unscaled this iteration
    inv = 1.0 / scaler.loss_scale
    for p in trainer._params:
        if p.grad_req == "null" or p._data is None:
            continue
        for g in p.list_grad():
            g._set(g._get() * inv)
    trainer._amp_unscaled = True


def convert_model(block, target_dtype=_DEFAULT_TARGET,
                  excluded_params=("gamma", "beta", "moving_mean",
                                   "moving_var", "running_mean",
                                   "running_var")):
    """Cast a trained block's parameters to the target dtype for inference
    (reference: amp.convert_model).  Norm-layer params stay fp32."""
    import jax.numpy as jnp

    for name, p in block.collect_params().items():
        if any(name.endswith(sfx) for sfx in excluded_params):
            continue
        if p._data is None:
            continue
        v = p.data()._get()
        if jnp.issubdtype(v.dtype, jnp.floating):
            p.data()._set(v.astype(target_dtype))
            p.dtype = target_dtype
    return block


convert_hybrid_block = convert_model


def list_fp16_ops():
    return list(lists.TARGET_DTYPE_OPS)


def list_fp32_ops():
    return list(lists.FP32_OPS)
