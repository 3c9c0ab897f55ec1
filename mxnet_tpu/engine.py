"""Execution-engine controls: numeric precision policy + determinism switch.

Reference: the engine in MXNet 1.x is configured through env vars read by
``src/engine/engine.cc`` — ``MXNET_ENGINE_TYPE=NaiveEngine`` turns the async
threaded engine into a synchronous, deterministic one (SURVEY.md §5 oracle 5,
§6.6 env-var layer).  The TPU build's "engine" is the JAX/XLA runtime, so the
two knobs map to:

- **Matmul precision** (``MXNET_TPU_MATMUL_PRECISION``): on TPU the MXU
  multiplies fp32 operands via bf16 passes at XLA's *default* precision,
  which silently degrades fp32 semantics (observed: flash-attention rows
  attending few keys drift 8%+ relative, CPU-vs-TPU Convolution diverges
  past a 2e-2 ladder).  The TPU-native stance: **fp32 means fp32** — speed
  comes from *explicitly* choosing bf16 (AMP / ``dtype='bfloat16'``), not
  from silently truncating fp32.  Default is therefore ``highest``
  (bf16x6/fp32-accurate passes); bf16 inputs are unaffected (single MXU
  pass is already exact for them), so the benchmark path loses nothing.
- **Determinism/naive engine** (``MXNET_ENGINE_TYPE=NaiveEngine`` or
  :func:`set_engine_type`): maps to ``jax.disable_jit`` — ops execute
  eagerly, op-by-op, in deterministic program order with no fusion, the
  direct analog of NaiveEngine's synchronous single-op execution.
"""
from __future__ import annotations

import contextlib
import os

__all__ = ["set_matmul_precision", "set_engine_type", "engine_type",
           "naive_engine", "set_nan_check", "nan_check_enabled"]

_VALID_PRECISION = ("default", "high", "highest", "bfloat16",
                    "tensorfloat32", "float32")
_engine_type = "ThreadedEnginePerDevice"  # reference default engine name


def set_matmul_precision(precision):
    """Set XLA's default matmul/conv precision for fp32 operands.

    ``highest`` (default) = fp32-accurate MXU passes; ``default`` = XLA's
    native bf16-pass behavior (fastest fp32, loosest numerics).
    """
    import jax

    if precision not in _VALID_PRECISION:
        from .base import MXNetError

        raise MXNetError(
            f"unknown matmul precision {precision!r}; one of {_VALID_PRECISION}")
    if precision == "default":
        jax.config.update("jax_default_matmul_precision", None)
    else:
        jax.config.update("jax_default_matmul_precision", precision)


def _place_compile_cache():
    """Point JAX's persistent compilation cache at one fixed place.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it by itself and
    nothing is set here.  Otherwise the cache is ``<checkout>/.jax_cache``,
    derived from this package's location: the directory is part of the
    cache key, so it must not depend on the working directory, the
    process or the time.  ``jax_persistent_cache_min_compile_time_secs``
    keeps its default of 1 s on purpose: the step and serving executables
    (seconds to minutes each) are stored, the per-op eager executables
    (milliseconds each, thousands of them) are not.  Returns the
    directory set, or None when it was left to the environment."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def _init_from_env():
    _place_compile_cache()
    prec = os.environ.get("MXNET_TPU_MATMUL_PRECISION", "highest")
    if prec != "default":
        try:
            set_matmul_precision(prec)
        except Exception:
            # an env-var typo must not make `import mxnet_tpu` raise
            import warnings

            warnings.warn(
                f"MXNET_TPU_MATMUL_PRECISION={prec!r} not recognized; "
                "falling back to 'highest'", stacklevel=2)
            set_matmul_precision("highest")
    if os.environ.get("MXNET_ENGINE_TYPE", "") == "NaiveEngine":
        set_engine_type("NaiveEngine")
    if os.environ.get("MXNET_NAN_CHECK", "") in ("1", "true", "True"):
        set_nan_check(True)


def engine_type():
    return _engine_type


def set_engine_type(name):
    """Switch between the async fused engine and the deterministic naive one.

    ``NaiveEngine`` disables jit globally (eager, op-by-op, deterministic
    order — the debugging mode of reference `src/engine/naive_engine.cc`);
    any other reference engine name restores normal jit execution.
    """
    global _engine_type
    import jax

    # jax.disable_jit() the context manager is thread-local; the engine
    # switch must apply process-wide (data-loader/prefetch threads included),
    # so flip the global config value instead.
    jax.config.update("jax_disable_jit", name == "NaiveEngine")
    # the eager jit-cache must not serve fused executables in op-by-op
    # deterministic mode
    from .ndarray import dispatch_cache as _dc

    _dc.set_engine_bypass(name == "NaiveEngine")
    _engine_type = name


@contextlib.contextmanager
def naive_engine():
    """Scoped determinism switch: ``with mx.engine.naive_engine(): ...``"""
    prev = _engine_type
    if prev == "NaiveEngine":
        yield
        return
    set_engine_type("NaiveEngine")
    try:
        yield
    finally:
        set_engine_type(prev)


def set_nan_check(enabled=True):
    """Device-side NaN/Inf sanitizer on the imperative dispatch seam
    (SURVEY.md §6.2: the TPU analog of the reference's sanitizer CI lane;
    env: MXNET_NAN_CHECK=1).  Synchronizes per op while on — a debug mode,
    like NaiveEngine."""
    from .ndarray.ndarray import _NAN_CHECK

    _NAN_CHECK["on"] = bool(enabled)


def nan_check_enabled():
    from .ndarray.ndarray import _NAN_CHECK

    return _NAN_CHECK["on"]
