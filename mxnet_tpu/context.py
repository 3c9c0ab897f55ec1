"""Device contexts: ``mx.cpu()``, ``mx.tpu()``, ``mx.gpu()``.

Reference: ``python/mxnet/context.py`` (class Context, mx.cpu()/mx.gpu(),
num_gpus) — SURVEY.md §3.5 "Misc frontend": this is *the thing mx.tpu()
extends* per the north star.  Here a Context is a thin, hashable handle that
resolves to a concrete ``jax.Device``.

Design notes (TPU-first):
- ``tpu`` maps to the JAX devices whose platform is "tpu".
- ``gpu`` is accepted for script compatibility and resolves to the
  accelerator as well ("GluonCV scripts run unmodified by swapping
  mx.gpu() -> mx.tpu()" — we go one better and make the swap optional).
- The default context is where JAX computes by default: ``tpu(0)`` on a
  host with a chip, ``cpu(0)`` otherwise.  A context is a label on an
  array, and the creation paths (``nd.array``, the creation ops,
  ``Parameter`` initialisation) put the value on the device the label
  names, so the two agree; ``mx.cpu()`` on a TPU host is the host CPU.
- ``cpu_pinned``/``cpu_shared`` degenerate to cpu: XLA manages host staging
  buffers itself, so the reference's pinned/shm storage managers
  (src/storage/) have no TPU-side analog.
"""
from __future__ import annotations

import threading

from .base import MXNetError

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context", "num_gpus", "num_tpus"]


def _jax():
    import jax

    return jax


class Context:
    """Device context. Hashable, comparable; ``with ctx:`` sets the default.

    Reference: python/mxnet/context.py class Context.
    """

    devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "cpu_shared", 5: "tpu"}
    devstr2type = {v: k for k, v in devtype2str.items()}
    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            if device_type not in self.devstr2type:
                raise MXNetError(f"unknown device type {device_type!r}")
            self.device_typeid = self.devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return self.devtype2str[self.device_typeid]

    # -- jax resolution ----------------------------------------------------
    @property
    def device(self):
        """Concrete jax.Device this context resolves to.

        In a multi-process (jax.distributed) job, contexts index the
        *process-local* devices — the reference's ctx numbering is likewise
        per-worker (each ps-lite worker sees only its own GPUs)."""
        jax = _jax()
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = [d for d in jax.local_devices() if d.platform == "cpu"] \
                or jax.devices("cpu")
        else:  # tpu / gpu -> accelerator backend
            devs = _accelerator_devices()
            if not devs:
                raise MXNetError(
                    f"Context {self} requested but no accelerator devices are "
                    "visible to JAX; use mx.cpu() or set JAX_PLATFORMS."
                )
        if self.device_id >= len(devs):
            raise MXNetError(
                f"{self}: device_id out of range (have {len(devs)} devices)"
            )
        return devs[self.device_id]

    # -- default-context management ---------------------------------------
    @classmethod
    def _current(cls):
        if not hasattr(cls._default_ctx, "value"):
            cls._default_ctx.value = Context(
                "tpu" if _accelerator_devices() else "cpu", 0)
        return cls._default_ctx.value

    def __enter__(self):
        self._old_ctx = Context._current()
        Context._default_ctx.value = self
        return self

    def __exit__(self, *exc):
        Context._default_ctx.value = self._old_ctx
        return False

    def __eq__(self, other):
        return (
            isinstance(other, Context)
            and self.device_typeid == other.device_typeid
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return self.__repr__()


def _accelerator_devices():
    """Process-local jax devices whose platform is "tpu"."""
    return [d for d in _jax().local_devices() if d.platform == "tpu"]


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Compatibility alias: resolves to the accelerator backend (see module
    docstring). Falls back at *resolution* time, not here."""
    return Context("gpu", device_id)


def num_gpus():
    return len(_accelerator_devices())


def num_tpus():
    return len(_accelerator_devices())


def current_context():
    return Context._current()
