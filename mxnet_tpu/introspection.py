"""Online MFU accounting: per-executable FLOPs meet the wall clock.

The bench rounds compute MFU offline, once per round, from analytic
FLOP formulas.  This module makes utilization a *live* metric: every
compiled executable's FLOP count is captured ONCE at compile time from
XLA's own cost model (``lower(...).compile().cost_analysis()`` — the
TrainStep AOT path and the serving prefill/decode/sample grid), and
every steady-state dispatch does
nothing but a host-side float add into a trailing window.  From the
window and a per-device peak-FLOPs registry two gauges fall out:

- ``mxnet_model_flops_utilization`` — dispatched FLOPs over
  ``elapsed × peak × device_count`` for the trailing window.  The gauge
  is created LAZILY: when ``cost_analysis`` is unavailable (platform
  quirk) or the device peak is
  unknown (non-TPU backend, no ``MXNET_DEVICE_PEAK_FLOPS`` override),
  the gauge is simply **absent** — never present-but-wrong.
- ``mxnet_executable_flops_total{kind}`` — raw dispatched FLOPs by
  consumer kind (``train_step`` / ``serving_prefill`` /
  ``serving_decode`` / ``serving_sample``), always on.

Hot-path contract: :func:`account_flops` never touches a device array —
no host syncs, no traces; ``flops_of`` runs only inside the (already
cold) compile paths.  FLOP counts from ``cost_analysis`` are for the
whole (global) program, so utilization divides by the GLOBAL device
count — every SPMD rank computes the same number, which is what the
cross-rank aggregation (``telemetry_agg``) expects to see agree.
"""
from __future__ import annotations

import threading
import time
from collections import deque

from . import env as _env
from . import telemetry as _telemetry

__all__ = ["device_peak_flops", "flops_of", "account_flops",
           "utilization", "window_stats", "reset"]

# bf16 peak FLOP/s per chip by device_kind substring (the same table
# bench.py's offline MFU uses; MXNET_DEVICE_PEAK_FLOPS overrides)
_PEAKS = (("v5 lite", 197e12), ("v5e", 197e12), ("v5p", 459e12),
          ("v6", 918e12), ("v4", 275e12), ("v3", 123e12), ("v2", 45e12))

_LOCK = threading.Lock()
_WINDOW: deque = deque(maxlen=512)    # (perf_counter t, flops)
_WINDOW_SUM = [0.0]                   # running sum (no O(window) scans)
_MFU_GAUGE = None                     # created lazily on first valid util
_DEVICES = [None]                     # cached global device count
_KIND_PEAK = [False]                  # cached device-kind table lookup

_FLOPS_TOTAL = _telemetry.counter(
    "mxnet_executable_flops_total",
    "FLOPs dispatched, from compile-time cost_analysis, by consumer",
    labelnames=("kind",))


def device_peak_flops():
    """Per-device peak FLOP/s: the ``MXNET_DEVICE_PEAK_FLOPS`` override
    when set, else the TPU device-kind table, else None (unknown — the
    MFU gauge stays absent rather than guessing a CPU peak).  The env
    var is re-read every call (the bench A/B flips it mid-process); the
    device-kind table lookup is resolved once and cached — this runs on
    every account_flops, so it must stay one env read + one list
    read."""
    override = _env.device_peak_flops_override()
    if override > 0:
        return override
    if _KIND_PEAK[0] is False:
        peak = None
        try:
            import jax

            kind = jax.devices()[0].device_kind.lower()
            for sub, p in _PEAKS:
                if sub in kind:
                    peak = p
                    break
        except Exception:
            peak = None
        _KIND_PEAK[0] = peak
    return _KIND_PEAK[0]


def _device_count():
    if _DEVICES[0] is None:
        try:
            import jax

            _DEVICES[0] = max(1, jax.device_count())
        except Exception:
            _DEVICES[0] = 1
    return _DEVICES[0]


def flops_of(compiled):
    """FLOP count of a compiled executable from XLA's cost model, or
    None when unavailable (the graceful-fallback contract: an absent
    count means an absent gauge, never a wrong one)."""
    try:
        v = float(compiled.cost_analysis().get("flops", 0.0))
        return v if v > 0 else None
    except Exception:
        return None


def account_flops(flops, kind="train_step"):
    """Record one dispatched executable's FLOPs (host-side only: a
    float add + deque append + gauge arithmetic — ZERO device work).
    Called with the compile-time count on every TrainStep call and
    every serving prefill/decode step; a None/0 count is a no-op."""
    if not flops:
        return
    now = time.perf_counter()
    _FLOPS_TOTAL.labels(kind=kind).inc(float(flops))
    with _LOCK:
        if len(_WINDOW) == _WINDOW.maxlen:
            # about to evict the oldest entry: keep the running sum
            # exact so utilization never scans the window
            _WINDOW_SUM[0] -= _WINDOW[0][1]
        _WINDOW.append((now, float(flops)))
        _WINDOW_SUM[0] += float(flops)
    _update_gauge(now)


def utilization(now=None):
    """Model FLOPs utilization over the trailing window: dispatched
    FLOPs / (elapsed × peak × global device count).  None when the peak
    is unknown or fewer than two events are in the window."""
    peak = device_peak_flops()
    if not peak:
        return None
    if now is None:
        now = time.perf_counter()
    with _LOCK:
        if len(_WINDOW) < 2:
            return None
        t0 = _WINDOW[0][0]
        total = _WINDOW_SUM[0]
    dt = now - t0
    if dt <= 0:
        return None
    return total / (dt * peak * _device_count())


def _update_gauge(now):
    global _MFU_GAUGE
    util = utilization(now)
    if util is None:
        return
    if _MFU_GAUGE is None:
        # lazy registration IS the fallback contract: with no usable
        # FLOPs source or peak the family never exists, so a scrape
        # sees "no data" instead of a fabricated 0.0
        _MFU_GAUGE = _telemetry.gauge(
            "mxnet_model_flops_utilization",
            "dispatched FLOPs over elapsed x peak x device count "
            "(trailing window; absent when FLOPs/peak are unknown)")
    _MFU_GAUGE.set(util)


def window_stats():
    """Diagnostics: ``{"events", "flops", "span_s", "peak",
    "devices"}`` for the trailing window (bench/teldump context)."""
    now = time.perf_counter()
    with _LOCK:
        events = len(_WINDOW)
        total = _WINDOW_SUM[0]
        span = (now - _WINDOW[0][0]) if _WINDOW else 0.0
    return {"events": events, "flops": total, "span_s": span,
            "peak": device_peak_flops(), "devices": _device_count()}


def reset():
    """Clear the accounting window (test isolation / bench A-B arms).
    The lazily-created gauge family, once registered, stays registered
    (telemetry families are process-wide); its value re-zeros through
    ``telemetry.reset()``."""
    with _LOCK:
        _WINDOW.clear()
        _WINDOW_SUM[0] = 0.0
    _DEVICES[0] = None
    _KIND_PEAK[0] = False
