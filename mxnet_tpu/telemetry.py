"""Unified runtime telemetry: metrics registry, step timeline, compile
tracing, and Prometheus/JSON exporters.

The reference stack's only visibility was a Chrome-trace profiler plus
ad-hoc counters (``dispatch_cache.stats()``, ``fault.stats()``).  This
module is the cross-cutting layer that makes a running job diagnosable:

- **Metrics registry**: process-wide, thread-safe Counter / Gauge /
  Histogram families with labels and exponential buckets.  Recording is
  always-on and cheap (one lock + dict update); nothing here sits on the
  per-op eager hot path — the dispatch cache and fault seams keep their
  own lock-striped counters and are *scraped* through collectors at
  export time instead of double-counting per call.
- **Step timeline**: ``step_begin()`` / ``phase(name)`` / ``step_end()``
  attribute each training step to phases (``data``, ``forward_backward``,
  ``optimizer``, ``collectives``, ``checkpoint``, ``other``).  Phases
  nest with *innermost-wins* attribution — the outer phase's clock pauses
  while an inner phase runs — so per-step phase durations always sum to
  the step's wall time.  Completed steps land in a bounded ring
  (``MXNET_TELEMETRY_TIMELINE_STEPS``, default 256) and, when the
  profiler is active, as ``step_phase`` spans in the Chrome trace.
- **Fused-step records**: every ``TrainStep.__call__`` opens a record in
  the same ring (``kind: "fused"``; ``step_records()``): one step and one
  batch number from the producer thread's staging to the instant a later
  call's ``is_ready()`` look first sees the step complete, with what the
  process did between calls; a step seen after four running medians is a
  stall, counted and kept.  Nothing of it runs before the first call.
- **Compile-event tracer**: every fresh ``jax.jit`` trace — a registry op
  (dispatch_cache miss), a hybridized block build, or a TrainStep — is
  recorded with its elapsed time and a *cause* (``new_op`` /
  ``new_shape`` / ``new_dtype`` / ``new_attrs`` / ``mode_change`` /
  ``recompile`` / ``trace_failure``), so retrace storms are diagnosable
  from the event stream instead of guessed from step-time jitter.
- **Exporters**: ``render_prometheus()`` (text exposition),
  ``snapshot()`` (JSON; also embedded in ``profiler.dump()`` otherData
  and ``bench.py`` extras), and an opt-in background HTTP endpoint
  (``MXNET_TELEMETRY_PORT`` or ``start_http_server(port)``) serving
  ``/metrics``, ``/snapshot``, and ``/healthz``.

Metric catalog (see README "Observability" for the full table): step
phases (``mxnet_step_phase_seconds``), compile events
(``mxnet_compile_events_total{kind,cause}``), dispatch cache
(``mxnet_dispatch_cache_*`` via collector), fault seams
(``mxnet_fault_seam_*_total{seam}`` via collector), DataLoader
(``mxnet_dataloader_batch_wait_seconds``, worker liveness), kvstore
traffic (``mxnet_kvstore_{push,pull}_bytes_total``), checkpoint
durations, and ``mxnet_recovery_restarts_total``.
"""
from __future__ import annotations

import itertools
import json
import threading
import time
from collections import deque

from . import env as _env

__all__ = ["Counter", "Gauge", "Histogram", "counter", "gauge", "histogram",
           "exponential_buckets", "register_collector", "snapshot",
           "render_prometheus", "start_http_server", "stop_http_server",
           "register_http_route", "unregister_http_route",
           "step_begin", "step_end", "step_abort", "step_scope", "phase",
           "maybe_phase", "trace_annotation", "timeline", "compile_event",
           "compile_events", "step_scalar", "collect_step_scalars",
           "defer_step_scalars", "drain_step_scalars",
           "StepTrack", "batch_taken", "step_records",
           "goodput_note", "goodput_summary",
           "heartbeat", "last_heartbeat", "reset"]

_LOCK = threading.RLock()
_FAMILIES: dict = {}        # name -> _Family
_COLLECTORS: list = []      # zero-arg callables -> [family dict, ...]

# default duration buckets: 100µs .. ~13s, exponential
_TIME_BUCKETS = None  # filled after exponential_buckets is defined


def exponential_buckets(start, factor, count):
    """``count`` bucket upper bounds growing geometrically from ``start``
    (Prometheus-style; +Inf is implicit)."""
    out = []
    b = float(start)
    for _ in range(count):
        out.append(b)
        b *= factor
    return out


_TIME_BUCKETS = exponential_buckets(1e-4, 2.0, 18)


# --------------------------------------------------------------------------
# metric primitives
# --------------------------------------------------------------------------
class _Child:
    __slots__ = ("_value",)

    def __init__(self):
        self._value = 0.0


class Counter(_Child):
    """Monotonic counter (family child)."""

    def inc(self, amount=1.0):
        if amount < 0:
            raise ValueError("counters only go up")
        with _LOCK:
            self._value += amount

    @property
    def value(self):
        return self._value


class Gauge(_Child):
    """Settable value (family child)."""

    def set(self, value):
        with _LOCK:
            self._value = float(value)

    def inc(self, amount=1.0):
        with _LOCK:
            self._value += amount

    def dec(self, amount=1.0):
        with _LOCK:
            self._value -= amount

    @property
    def value(self):
        return self._value


class Histogram:
    """Histogram with cumulative-at-export buckets (family child)."""

    __slots__ = ("_buckets", "_counts", "_sum", "_count")

    def __init__(self, buckets=None):
        bs = sorted(float(b) for b in (buckets or _TIME_BUCKETS))
        self._buckets = bs
        self._counts = [0] * len(bs)
        self._sum = 0.0
        self._count = 0

    def observe(self, value):
        v = float(value)
        with _LOCK:
            self._sum += v
            self._count += 1
            for i, b in enumerate(self._buckets):
                if v <= b:
                    self._counts[i] += 1
                    break

    @property
    def count(self):
        return self._count

    @property
    def sum(self):
        return self._sum

    def cumulative(self):
        """[(upper_bound, cumulative_count), ...] ending with +Inf."""
        out = []
        acc = 0
        with _LOCK:
            for b, c in zip(self._buckets, self._counts):
                acc += c
                out.append((b, acc))
            out.append((float("inf"), self._count))
        return out


class _Family:
    """A named metric family with fixed label names; children per label
    value tuple.  Unlabeled families proxy their single ``()`` child."""

    def __init__(self, name, help, mtype, labelnames=(), buckets=None):
        self.name = name
        self.help = help
        self.type = mtype
        self.labelnames = tuple(labelnames)
        self._buckets = buckets
        self._children: dict = {}
        if not self.labelnames:
            self._children[()] = self._new_child()

    def _new_child(self):
        if self.type == "counter":
            return Counter()
        if self.type == "gauge":
            return Gauge()
        return Histogram(self._buckets)

    def labels(self, *values, **kw):
        if kw:
            if values:
                raise ValueError("pass labels positionally or by name")
            values = tuple(str(kw[n]) for n in self.labelnames)
        else:
            values = tuple(str(v) for v in values)
        if len(values) != len(self.labelnames):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, "
                f"got {values}")
        with _LOCK:
            child = self._children.get(values)
            if child is None:
                child = self._children[values] = self._new_child()
            return child

    # unlabeled convenience proxies
    def inc(self, amount=1.0):
        self._children[()].inc(amount)

    def set(self, value):
        self._children[()].set(value)

    def dec(self, amount=1.0):
        self._children[()].dec(amount)

    def observe(self, value):
        self._children[()].observe(value)

    @property
    def value(self):
        return self._children[()].value

    @property
    def count(self):
        return self._children[()].count

    @property
    def sum(self):
        return self._children[()].sum

    def cumulative(self):
        return self._children[()].cumulative()

    def remove(self, *values, **kw):
        """Drop one labeled child (stale-series cleanup — e.g. a
        re-published sharding plan's obsolete per-param rows; no-op when
        the label set was never created)."""
        if kw:
            if values:
                raise ValueError("pass labels positionally or by name")
            values = tuple(str(kw[n]) for n in self.labelnames)
        else:
            values = tuple(str(v) for v in values)
        with _LOCK:
            self._children.pop(values, None)

    def children(self):
        with _LOCK:
            return list(self._children.items())


def _get_or_create(name, help, mtype, labelnames=(), buckets=None):
    with _LOCK:
        fam = _FAMILIES.get(name)
        if fam is not None:
            if fam.type != mtype or fam.labelnames != tuple(labelnames):
                raise ValueError(
                    f"metric {name!r} re-registered with a different "
                    f"type/labels ({fam.type}{fam.labelnames} vs "
                    f"{mtype}{tuple(labelnames)})")
            return fam
        fam = _Family(name, help, mtype, labelnames, buckets)
        _FAMILIES[name] = fam
        return fam


def counter(name, help="", labelnames=()):
    """Get-or-create a Counter family."""
    return _get_or_create(name, help, "counter", labelnames)


def gauge(name, help="", labelnames=()):
    """Get-or-create a Gauge family."""
    return _get_or_create(name, help, "gauge", labelnames)


def histogram(name, help="", labelnames=(), buckets=None):
    """Get-or-create a Histogram family (default: exponential duration
    buckets 100µs..13s)."""
    return _get_or_create(name, help, "histogram", labelnames, buckets)


def register_collector(fn):
    """Register a zero-arg callable run at export time returning a list of
    ``{"name", "type", "help", "samples": [(labels_dict, value), ...]}``
    dicts — the scrape-time bridge for subsystems that keep their own
    counters (dispatch cache, fault seams) so their hot paths never pay a
    second lock."""
    with _LOCK:
        _COLLECTORS.append(fn)
    return fn


# --------------------------------------------------------------------------
# step scalars: numbers a layer computes on the device inside a fused step
# (pairs an expert layer routed, its fullest expert's load) and the
# registered counter or histogram each belongs to.  They leave the step as
# outputs beside the loss and are read once they are there, never on the
# dispatch path: a step waits for nothing of this.
# --------------------------------------------------------------------------
_SCALARS = threading.local()     # .open: the innermost collector's dict
_DEFERRED: deque = deque()       # (scalars, loss, record, track) a step
_SEEN: deque = deque()           # scalars of steps seen complete, to be read


class collect_step_scalars:
    """Trace-time collector: within it ``step_scalar`` calls gather into
    ``.values`` (``{family name: [traced scalars]}``).  Collectors nest: a
    ``jax.checkpoint``-ed layer opens its own, returns ``stacked()`` with
    its outputs and hands that to ``step_scalar`` again outside, so that no
    traced value crosses the checkpoint's boundary but as an output."""

    def __enter__(self):
        self._outer = getattr(_SCALARS, "open", None)
        self.values = _SCALARS.open = {}
        return self

    def __exit__(self, *exc):
        _SCALARS.open = self._outer

    def stacked(self):
        """``{family name: 1-d array}`` of what was gathered."""
        import jax.numpy as jnp

        return {name: jnp.concatenate([jnp.ravel(v) for v in got])
                for name, got in self.values.items()}


def step_scalar(name, value):
    """While a fused step is traced: give ``value`` (a traced scalar, or an
    array of them) to the registered counter or histogram ``name``.  It
    reaches the family when the step that computed it has completed: a
    counter is increased by the sum, a histogram observes each.  Outside a
    collector (eager code, inference) nothing is recorded."""
    found = getattr(_SCALARS, "open", None)
    if found is not None:
        if name not in _FAMILIES:
            raise KeyError(f"step_scalar: no metric family {name!r}")
        found.setdefault(name, []).append(value)


def defer_step_scalars(arrays, loss, record, track):
    """A dispatched step's scalars (``{family name: device array}``), its
    loss and its record (``track.open``'s), kept until the step is seen
    complete (``_look``, by the next ``StepTrack.open``, or whoever reads
    the metrics).  The scalars of the steps seen complete before this one
    was dispatched are read now, behind the dispatch."""
    _DEFERRED.append((arrays, loss, record, track))
    _read_seen()


def _look(now, judged=True):
    """See, without waiting and without reading anything, which of the
    deferred steps are done, oldest first: stamp their records
    ``seen_complete`` at ``now`` and leave their scalars to be read."""
    while _DEFERRED:
        arrays, loss, record, track = _DEFERRED[0]
        if not (loss.is_ready() and all(a.is_ready()
                                        for a in arrays.values())):
            # the step completes between this reading and the next
            record["unready_at"] = now
            return
        _DEFERRED.popleft()
        if arrays:
            _SEEN.append(arrays)
        track.seen(record, now, judged)


def _read_seen():
    """Record the scalars of the steps seen complete."""
    import numpy as np

    while _SEEN:
        for name, a in _SEEN.popleft().items():
            fam, got = _FAMILIES[name], np.asarray(a, dtype=np.float64)
            if fam.type == "counter":
                fam.inc(float(got.sum()))
            else:
                for v in got.ravel():
                    fam.observe(float(v))


def drain_step_scalars(wait=True):
    """Record the deferred scalars of every completed step, oldest first,
    and stamp its record ``seen_complete``.  With ``wait``, read the
    scalars of the steps still running too; a step that has none to read
    is never waited for, and the drain ends at the first such step that
    still runs.  ``snapshot()`` and ``render_prometheus()`` call it: who
    reads the metrics is not dispatching, and a reader's look says when it
    read, not how the steps ran (no interval is judged)."""
    _look(time.perf_counter(), judged=False)
    while wait and _DEFERRED and _DEFERRED[0][0]:
        arrays, _, record, track = _DEFERRED.popleft()
        _SEEN.append(arrays)
        _read_seen()        # waits for the step
        track.seen(record, time.perf_counter(), judged=False)
        _look(time.perf_counter(), judged=False)
    _read_seen()


# --------------------------------------------------------------------------
# step timeline
# --------------------------------------------------------------------------
_TIMELINE_CAP = max(1, _env.get_int("MXNET_TELEMETRY_TIMELINE_STEPS", 256))
_STEPS: deque = deque(maxlen=_TIMELINE_CAP)
_CUR = None          # active step: {"step", "thread", "t0", "wall0", "phases",
#                      "stack"}
_STEP_SEQ = [0]

_PHASE_HIST = histogram(
    "mxnet_step_phase_seconds",
    "per-step time attributed to each phase (exclusive of nested phases)",
    labelnames=("phase",))
_STEP_HIST = histogram("mxnet_step_seconds", "training step wall time")
_STEPS_TOTAL = counter("mxnet_steps_total", "completed timeline steps")

# goodput ledger: wall time classified into what the job was DOING.
# "productive" accrues automatically from the step timeline (step wall
# minus any in-step checkpoint phase); the non-productive buckets are
# noted by the lifecycle/recovery seams that own them — checkpoint
# saves, run_with_recovery restart downtime, live resharding transfers,
# watchdog-diagnosed stalls, and numerical-integrity rewinds (time lost
# to wrong VALUES rather than lost processes; mxnet_tpu/guard.py).
# The ratio gauge is computed at export time by a collector so
# recording stays one counter add.
_GOODPUT = counter(
    "mxnet_goodput_seconds_total",
    "wall time by goodput bucket (productive = step wall minus in-step "
    "checkpoint time; checkpoint/restart/reshard/stall/rewind noted by "
    "their owning seams)", labelnames=("bucket",))


MOE_ROUTED_PAIRS = counter(
    "mxnet_moe_routed_pairs_total",
    "(token, expert) pairs the dropless expert layers of this process "
    "computed: those routed to the experts held here (step scalar)")
MOE_WALKED_ROWS = counter(
    "mxnet_moe_walked_rows_total",
    "rows that the sorted walks of the dropless expert layers covered to "
    "gather their pairs, whole granules (step scalar): over "
    "mxnet_moe_routed_pairs_total, 1.0 is no row gathered in vain")
MOE_LIVE_PARTS = counter(
    "mxnet_moe_live_parts_total",
    "parts of the sorted rows that held a pair, which the dropless expert "
    "layers walked (step scalar): over mxnet_moe_parts_total, 1.0 is a load "
    "that fills every part, where walking the live ones alone saves nothing")
MOE_PARTS = counter(
    "mxnet_moe_parts_total",
    "parts that the shapes of the dropless expert layers allow, tokens x "
    "top_k rows in parts of a fixed size, one count a layer a step (step "
    "scalar)")
MOE_LOAD_MAX_OVER_MEAN = histogram(
    "mxnet_moe_expert_load_max_over_mean",
    "fullest held expert's pairs over the mean of the held experts, one "
    "observation a layer a step (step scalar)",
    buckets=exponential_buckets(1.0, 1.1, 30))


MOE_ADD_ROWS_CALLS = counter(
    "mxnet_moe_add_rows_calls_total",
    "the dropless expert layers' ways back traced (combine's forward, "
    "dispatch's backward), by the path they took: the Pallas kernel over "
    "the rows that hold a pair, or XLA's scatter-add of a whole part",
    ("path",))
EMBEDDING_GRAD_CALLS = counter(
    "mxnet_embedding_grad_calls_total",
    "Embedding calls traced, by the way back their table's gradient takes: "
    "rows (ops/embed_add_rows.py's Pallas kernel: one pass over the table's "
    "gradient, the cotangent's rows fetched by DMA through the sorted ids "
    "and added into their rows) or scatter (XLA's scatter-add, everything "
    "off the kernel's gate)",
    ("path",))
MOE_ADDED_ROWS = counter(
    "mxnet_moe_added_rows_total",
    "rows that the ways back of the dropless expert layers walked to add "
    "their pairs, forward (step scalar): over mxnet_moe_routed_pairs_total, "
    "1.0 is the rows that hold a pair alone (the kernel); XLA's scatter-add "
    "walks whole parts and reads above it")
MOE_GROUP_LIMITED_CALLS = counter(
    "mxnet_moe_group_limited_calls_total",
    "dropless expert layers traced whose choice of experts is confined to "
    "the best groups of the router's outputs (moe_swiglu's n_group > 1), one "
    "count a layer a trace")
MOE_ROUTER_KEPT = counter(
    "mxnet_moe_router_kept_total",
    "dropless routers traced, by what their backward keeps of the scores in "
    "place of a second product: choice (a sigmoid router: the chosen "
    "experts' scores, tokens x top_k) or logits (a softmax router: the "
    "row's logits, tokens x experts); one count a layer a trace",
    ("kept",))
KDA_CALLS = counter(
    "mxnet_kda_calls_total",
    "kda (chunked gated delta rule) calls traced, by the path their state "
    "pass took", ("path",))
KDA_CHUNKS = counter(
    "mxnet_kda_chunks_total",
    "chunks of a call's rows that the traced kda calls walk, heads and "
    "samples not counted: rows / chunk, one count a call a trace")


SSM_SCAN_CALLS = counter(
    "mxnet_selective_scan_fwd_calls_total",
    "selective_scan (chunked state-space scan) calls traced, by the path "
    "their chunks' walk took: the Pallas kernels or the scan of scans",
    ("path",))
SSM_SCAN_CHUNKS = counter(
    "mxnet_selective_scan_chunks_total",
    "chunks of a call's rows that the traced selective_scan calls walk, "
    "samples not counted: rows / chunk, one count a call a trace")


LAYER_CHECKPOINT_KEPT_BYTES = counter(
    "mxnet_layer_checkpoint_kept_bytes_total",
    "bytes of the values an op names for a decoder layer's checkpoint to "
    "keep beside the layer's input (the attention op's output and row "
    "statistics, the delta rule's output and chunk states, the router's "
    "choice, its scores and the sorted walks' plan), from their "
    "shapes: one count a named value a layer a trace",
    ("name",))
LAYER_HANDED_ON_BYTES = counter(
    "mxnet_layer_handed_on_bytes_total",
    "bytes of what a decoder layer hands on to later layers beside the "
    "residual stream (a state-space layer's scan output, the memory; an "
    "attention layer's K and V), from their shapes: one count a value a "
    "trace",
    ("name",))
PARAMETER_GRAD_BUFFERS = counter(
    "mxnet_parameter_grad_buffers_total",
    "gradient buffers of Gluon parameters made, one a parameter a context, "
    "by the first grad() or backward that asked: 0 after a fused TrainStep "
    "run, which never asks")
PARAMETER_GRAD_BYTES = counter(
    "mxnet_parameter_grad_bytes_total",
    "bytes of the gradient buffers that mxnet_parameter_grad_buffers_total "
    "counts")


ATTENTION_VISIBLE_PAIRS = counter(
    "mxnet_attention_visible_pairs_total",
    "(query, key) pairs that the mask and the segment ids show, over the "
    "flash_attention calls under segment ids of this process's fused steps, "
    "a sample counted once whatever its heads; computed on the device from "
    "the ids (step scalar)")
ATTENTION_WALKED_PAIRS = counter(
    "mxnet_attention_walked_pairs_total",
    "(query, key) pairs of the tiles those calls' forwards walk, from their "
    "shapes alone (step scalar): mxnet_attention_visible_pairs_total over "
    "it is the share of the walked pairs that counts, and 1 minus it what "
    "skipping tiles from the ids could save at most")


def goodput_note(bucket, seconds):
    """Charge ``seconds`` of wall time to a goodput ``bucket``
    (``checkpoint`` / ``restart`` / ``reshard`` / ``stall`` /
    ``rewind`` / caller-defined).  ``productive`` accrues automatically
    from the step timeline — loops never call this themselves."""
    if seconds > 0:
        _GOODPUT.labels(bucket=str(bucket)).inc(float(seconds))


def goodput_summary():
    """``{"buckets": {...seconds...}, "tracked_s", "productive_ratio"}``
    — productive wall time over everything the ledger has classified
    (``productive_ratio`` is None until anything was tracked)."""
    buckets = {}
    for values, child in _GOODPUT.children():
        buckets[values[0]] = child.value
    total = sum(buckets.values())
    prod = buckets.get("productive", 0.0)
    return {"buckets": buckets, "tracked_s": total,
            "productive_ratio": (prod / total) if total > 0 else None}


def _goodput_collector():
    s = goodput_summary()
    if s["productive_ratio"] is None:
        return []
    return [{"name": "mxnet_goodput_ratio", "type": "gauge",
             "help": "productive wall time over all ledger-classified "
                     "time (goodput)",
             "samples": [({}, s["productive_ratio"])]}]


def _chrome_span(name, t0, t1, cat):
    try:
        from . import profiler as _prof

        _prof._record_span(name, t0, t1, cat)
    except Exception:
        pass


def _flight_note(kind, **fields):
    """Context event into the flight-recorder ring (step boundaries,
    compile events) — lazy + failure-tolerant like ``_agg_tick``; a
    disabled recorder costs one module-dict lookup and a bool read."""
    try:
        from . import flight_recorder as _flight

        _flight.record_event(kind, **fields)
    except Exception:
        pass


# -- goodput SLO alerting (ROADMAP follow-on (d)) ---------------------------
# a WINDOW is one completed timeline step: at each step_end the DELTA
# of the goodput ledger since the previous step is classified, and
# MXNET_GOODPUT_SLO_WINDOWS consecutive windows below MXNET_GOODPUT_SLO
# fire one alert (lifecycle event + counter + flight-recorder entry).
# The alert re-arms only after a window back at/above the SLO, so a
# sustained degradation fires once, not every step.
_SLO_BREACHES = counter(
    "mxnet_goodput_slo_breaches_total",
    "goodput-SLO alerts: productive ratio below MXNET_GOODPUT_SLO for "
    "MXNET_GOODPUT_SLO_WINDOWS consecutive windows")
_SLO_STATE = {"last": None, "below": 0, "fired": False}


def _goodput_slo_tick():
    slo = _env.goodput_slo()
    if slo <= 0:
        return
    s = goodput_summary()
    cur = (s["tracked_s"], s["buckets"].get("productive", 0.0))
    last = _SLO_STATE["last"]
    _SLO_STATE["last"] = cur
    if last is None:
        return
    d_total = cur[0] - last[0]
    d_prod = cur[1] - last[1]
    if d_total <= 0:
        return          # nothing classified since the last boundary
    ratio = d_prod / d_total
    if ratio >= slo:
        _SLO_STATE["below"] = 0
        _SLO_STATE["fired"] = False
        return
    _SLO_STATE["below"] += 1
    if _SLO_STATE["fired"] or \
            _SLO_STATE["below"] < _env.goodput_slo_windows():
        return
    _SLO_STATE["fired"] = True
    _SLO_BREACHES.inc()
    try:
        from . import lifecycle as _lc

        _lc.note_goodput_slo_breach(ratio, slo, _SLO_STATE["below"])
    except Exception:   # alerting must never break a step boundary
        pass


# step heartbeat: monotonic timestamp of the last step-boundary activity
# (step_begin/step_end, or an explicit heartbeat() from a custom loop /
# lifecycle.check_stop).  The lifecycle watchdog reads it to enforce a
# per-step deadline; None = no step activity yet this process.
_HEARTBEAT = [None]


def heartbeat():
    """Mark step-boundary liveness for the stall watchdog
    (:mod:`mxnet_tpu.lifecycle`).  Cheap: one monotonic read + store."""
    _HEARTBEAT[0] = time.monotonic()


def last_heartbeat():
    """Monotonic time of the last heartbeat, or None."""
    return _HEARTBEAT[0]


def step_begin(step=None):
    """Open a timeline step.  An unfinished previous step is finalized
    first (robustness beats strictness in a training loop)."""
    global _CUR
    heartbeat()
    with _LOCK:
        if _CUR is not None:
            _finalize_locked(time.perf_counter())
        if step is None:
            step = _STEP_SEQ[0]
        step = int(step)
        _STEP_SEQ[0] = step + 1
        _CUR = {"step": step, "thread": threading.get_ident(),
                "t0": time.perf_counter(), "wall0": time.time(),
                "phases": {}, "stack": []}
    # return the local, not _CUR["step"]: a concurrent step_end/abort may
    # have nulled _CUR the instant the lock dropped
    _flight_note("step", event="begin", step=step)
    return step


def _finalize_locked(now):
    """Complete the active step (lock held).  Returns the record."""
    global _CUR
    cur = _CUR
    _CUR = None
    if cur is None:
        return None
    stack = cur["stack"]
    if stack:
        # only the innermost frame has unclaimed elapsed time: every outer
        # frame was charged (and left paused) when its inner frame entered
        name, t = stack[-1]
        cur["phases"][name] = cur["phases"].get(name, 0.0) + (now - t)
        del stack[:]
    wall = now - cur["t0"]
    phases = cur["phases"]
    other = wall - sum(phases.values())
    if other > 1e-9:
        phases["other"] = other
    rec = {"step": cur["step"], "time": cur["wall0"],
           "wall_s": wall, "phases": dict(phases)}
    _STEPS.append(rec)
    for pname, dt in phases.items():
        _PHASE_HIST.labels(phase=pname).observe(dt)
    _STEP_HIST.observe(wall)
    _STEPS_TOTAL.inc()
    # goodput: a step is productive time EXCEPT what it spent inside a
    # checkpoint save (that phase is charged to the checkpoint bucket by
    # the save path itself — charging it here too would double-count)
    prod = wall - phases.get("checkpoint", 0.0)
    if prod > 0:
        _GOODPUT.labels(bucket="productive").inc(prod)
    _chrome_span(f"step {cur['step']}", cur["t0"], now, "step")
    return rec


def step_end():
    """Close the active step; returns its record (phase durations sum to
    the step wall time — unattributed time lands in ``other``)."""
    heartbeat()
    with _LOCK:
        rec = _finalize_locked(time.perf_counter())
    if rec is not None:
        _flight_note("step", event="end", step=rec["step"],
                     wall_s=rec["wall_s"])
    _goodput_slo_tick()
    _agg_tick()
    return rec


def _agg_tick():
    """Cross-rank aggregation stride hook: every completed step (and
    every ``lifecycle.check_stop``) advances the aggregator's tick
    counter; every ``MXNET_TELEMETRY_AGG_EVERY``-th tick publishes this
    rank's snapshot and (on rank 0) merges the peers'.  Pure host-side
    file IO — NEVER a device collective — so it is safe at any stride
    and cannot desync SPMD peers.  A disabled aggregator costs one
    module-dict lookup and an int check."""
    try:
        from . import telemetry_agg as _agg

        _agg.tick()
    except Exception:   # aggregation must never break a step boundary
        pass


def step_abort():
    """Discard the active step without recording (e.g. the data phase hit
    StopIteration — there is no step)."""
    global _CUR
    with _LOCK:
        _CUR = None


def trace_annotation(name, **ids):
    """``jax.profiler.TraceAnnotation("mx:" + name, **ids)``: a host span on
    the clock of whatever JAX trace is running (the device trace's), and
    next to nothing when none is.  ``ids`` (``step=k``, ``batch=n``) are
    the event's stats in the trace: a step's spans are found by number.
    For spans that are not phases, such as those of a producer thread."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation("mx:" + name, **ids)


class _PhaseScope:
    __slots__ = ("name", "stamps", "_ids", "_t0", "_note")

    def __init__(self, name, ids):
        self.name = name
        self.stamps = None      # [start, end] on time.perf_counter(), at exit
        self._ids = ids
        self._t0 = None
        self._note = None

    def __enter__(self):
        now = time.perf_counter()
        self._t0 = now
        with _LOCK:
            cur = _CUR
            # the step's stack belongs to the thread that opened the step:
            # a phase on another thread (a prefetch producer, an async
            # checkpoint write) must not pause or charge that thread's
            # open phase, and observes into the histogram instead
            if cur is not None and cur["thread"] == threading.get_ident():
                stack = cur["stack"]
                if stack:
                    # pause the outer phase: charge it up to now
                    oname, ot = stack[-1]
                    cur["phases"][oname] = \
                        cur["phases"].get(oname, 0.0) + (now - ot)
                    stack[-1][1] = now
                stack.append([self.name, now])
        self._note = trace_annotation(self.name, **self._ids)
        self._note.__enter__()
        return self

    def __exit__(self, *exc):
        self._note.__exit__(*exc)
        now = time.perf_counter()
        self.stamps = [self._t0, now]
        with _LOCK:
            cur = _CUR
            if cur is None or cur["thread"] != threading.get_ident():
                # phase outside a step: still observable in the histogram
                _PHASE_HIST.labels(phase=self.name).observe(now - self._t0)
            elif cur["stack"] and cur["stack"][-1][0] == self.name:
                _, t = cur["stack"].pop()
                cur["phases"][self.name] = \
                    cur["phases"].get(self.name, 0.0) + (now - t)
                if cur["stack"]:
                    cur["stack"][-1][1] = now  # outer phase resumes
        _chrome_span(f"phase:{self.name}", self._t0, now, "step_phase")
        return False


def phase(name, **ids):
    """Context manager attributing its (exclusive) duration to ``name`` in
    the active step; outside a step (or on another thread than the step's)
    it records straight to the phase histogram.  Either way it is also a
    ``jax.profiler.TraceAnnotation`` named ``mx:<name>`` with ``ids`` as
    its stats, so it shows in any JAX trace that is running, on the device
    trace's clock.  After exit ``.stamps`` is its ``[start, end]``, the
    readings the histogram's observation was made from."""
    return _PhaseScope(name, ids)


class _NullScope:
    """Reusable no-op context for call sites with an opt-in telemetry flag
    (Trainer/Estimator): the disabled path pays one attribute read."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SCOPE = _NullScope()


def maybe_phase(enabled, name):
    """``phase(name)`` when ``enabled``, else a shared no-op scope."""
    return _PhaseScope(name, {}) if enabled else _NULL_SCOPE


class _StepScope:
    def __init__(self, step):
        self._step = step

    def __enter__(self):
        return step_begin(self._step)

    def __exit__(self, *exc):
        step_end()
        return False


def step_scope(step=None):
    """``with telemetry.step_scope(): ...`` — begin/end a timeline step."""
    return _StepScope(step)


def timeline():
    """Completed step records of ``step_begin`` / ``step_end``, oldest
    first (bounded ring; a fused step's records share it and are
    ``step_records()``'s)."""
    with _LOCK:
        return [dict(r, phases=dict(r["phases"])) for r in _STEPS
                if "kind" not in r]


# --------------------------------------------------------------------------
# fused-step records: one record a TrainStep call in the ring above, under
# the step's number and the number of the batch that caused it, stamped
# when the program first sees the step complete.  Every time is a reading
# of time.perf_counter().
# --------------------------------------------------------------------------
STALL_FACTOR = 4.0     # an interval this many running medians long stalls
STALL_MEDIAN_OF = 32   # intervals the running median looks back over
STALL_MIN = 8          # and the fewest it judges from
_STALLS: deque = deque(maxlen=8)     # the stalls kept from the ring's turnover
_TAKEN = threading.local()           # .batch: what this thread took last
_TRACK_IDS = itertools.count(1)      # a StepTrack's number in the process
_GC2: deque = deque(maxlen=64)       # [start, end] of generation-2 collections
# what the process has done so far, by name; a record holds the differences
_WATCH = {"installed": False, "gc_t0": None, "last": None, "compiles": 0,
          "cache_misses": 0, "cache_retrieval_s": 0.0,
          "backend_compile_s": 0.0}
_USAGE = ("ru_nivcsw", "ru_nvcsw", "ru_majflt", "ru_inblock", "ru_oublock")

_INTERVAL_HIST = histogram(
    "mxnet_train_step_interval_seconds",
    "time between the instants at which consecutive fused steps were first "
    "seen complete (each an upper bound by one call's spacing)")
_STALLS_TOTAL = counter(
    "mxnet_train_step_stalls_total",
    "fused steps seen complete after an interval over four times the "
    "running median of the last 32")


def _on_gc(phase, info):
    # generation 2 alone: the collection that pauses for milliseconds
    if info["generation"] == 2:
        if phase == "start":
            _WATCH["gc_t0"] = time.perf_counter()
        elif _WATCH["gc_t0"] is not None:
            _GC2.append([_WATCH["gc_t0"], time.perf_counter()])
            _WATCH["gc_t0"] = None


def _on_jax_event(name, **kw):
    if name == "/jax/compilation_cache/cache_misses":
        _WATCH["cache_misses"] += 1


def _on_jax_seconds(name, secs, **kw):
    if name == "/jax/compilation_cache/cache_retrieval_time_sec":
        _WATCH["cache_retrieval_s"] += secs
    elif name == "/jax/core/compile/backend_compile_duration":
        _WATCH["backend_compile_s"] += secs


def _since_previous_call(now):
    """What the process did since the previous ``StepTrack.open``: seconds,
    the differences of one ``getrusage`` (switched out, waited, paged,
    blocks read and written, CPU seconds), the generation-2 collections
    that ended, compile events and the persistent cache's traffic.  None at
    the first, which installs what listens (nothing does before it)."""
    import resource

    if not _WATCH["installed"]:
        import gc

        from jax import monitoring

        _WATCH["installed"] = True
        gc.callbacks.append(_on_gc)
        monitoring.register_event_listener(_on_jax_event)
        monitoring.register_event_duration_secs_listener(_on_jax_seconds)
    usage = resource.getrusage(resource.RUSAGE_SELF)
    cur = {name[3:]: getattr(usage, name) for name in _USAGE}
    cur["cpu_s"] = usage.ru_utime + usage.ru_stime
    cur["seconds"] = now
    for name in ("compiles", "cache_misses", "cache_retrieval_s",
                 "backend_compile_s"):
        cur[name] = _WATCH[name]
    last, _WATCH["last"] = _WATCH["last"], cur
    collections = []
    while _GC2:
        collections.append(_GC2.popleft())
    if last is None:
        return None
    since = {name: value - last[name] for name, value in cur.items()}
    since["gc2"] = collections
    return since


def batch_taken(batch):
    """``PrefetchIterator.__next__`` leaves what the calling thread took
    last: ``{"batch": n, "prefetch.stage": [start, end, thread],
    "prefetch.wait": [start, end, thread]}``.  The next ``StepTrack.open``
    on this thread takes it as the batch that caused its step."""
    _TAKEN.batch = batch


class StepTrack:
    """What one ``TrainStep`` keeps between calls to write its records:
    when it last saw a step complete and the intervals before that."""

    def __init__(self, net):
        self.net = net
        self.id = next(_TRACK_IDS)
        self._last_seen = None
        self._intervals = deque(maxlen=STALL_MEDIAN_OF)
        self._stall = None      # the kept stall still short of later records

    def open(self, step):
        """Open the record of step ``step`` in the ring and return it.
        Looks, without waiting, at whether earlier steps are done (one
        ``is_ready()`` a step in flight) and stamps those that are; the
        caller fills ``spans`` and hands the record to
        ``defer_step_scalars`` with the loss."""
        now = time.perf_counter()
        taken = getattr(_TAKEN, "batch", None) or {}
        _TAKEN.batch = None
        record = {
            "kind": "fused", "net": self.net, "track": self.id,
            "step": int(step), "batch": taken.get("batch"),
            "time": time.time(), "thread": threading.get_ident(),
            "opened": now,
            # name -> [start, end] (the batch's two: [start, end, thread])
            "spans": {k: v for k, v in taken.items() if k != "batch"},
            "since_previous_call": _since_previous_call(now),
            # bracket the step's completion: the last poll that found it
            # running and the first that found it done, a call apart
            "unready_at": None, "seen_complete": None, "interval_s": None,
            "in_flight": None,
        }
        with _LOCK:
            _STEPS.append(record)
        if self._stall is not None:
            if record["step"] <= self._stall["step"] + 2:
                self._stall["records"].append(record)
            else:
                self._stall = None
        _look(now)
        record["in_flight"] = sum(1 for held in _DEFERRED if held[3] is self)
        return record

    def seen(self, record, now, judged=True):
        """``record``'s step was found complete at ``now``; the interval
        since the one before is observed and ``judged`` for a stall."""
        record["seen_complete"] = now
        last, self._last_seen = self._last_seen, now
        if last is None or now <= last or not judged:
            return      # the first, or one more found by the same poll
        record["interval_s"] = interval = now - last
        _INTERVAL_HIST.observe(interval)
        recent = self._intervals
        if len(recent) >= STALL_MIN:
            median = sorted(recent)[len(recent) // 2]
            if interval > STALL_FACTOR * median:
                self._stalled(record, last, median)
        recent.append(interval)

    def _stalled(self, record, last, median):
        """Count a stall and keep from the ring's turnover its record, those
        of the two steps on either side (the later ones join as they open)
        and those of the calls made while it lasted (opened after ``last``:
        their ``since_previous_call`` is what the process did meanwhile)."""
        _STALLS_TOTAL.inc()
        step = record["step"]
        with _LOCK:
            mine = [r for r in _STEPS if r.get("track") == self.id]
        self._stall = {
            "net": self.net, "track": self.id, "step": step,
            "interval_s": record["interval_s"], "median_s": median,
            "records": [r for r in mine if abs(r["step"] - step) <= 2],
            "calls_meanwhile": [r for r in mine if r["opened"] > last]}
        _STALLS.append(self._stall)
        _flight_note("step_stall", net=self.net, step=step,
                     interval_s=record["interval_s"], median_s=median,
                     in_flight=record["in_flight"])


def _copy_record(record):
    out = dict(record, spans={k: list(v)
                              for k, v in record["spans"].items()})
    since = record["since_previous_call"]
    if since is not None:
        out["since_previous_call"] = dict(
            since, gc2=[list(c) for c in since["gc2"]])
    return out


def step_records():
    """``{"records": the ring's fused-step records, oldest first,
    "stalls": the last 8 stalls}``, copies.  A record: ``net``, ``track``
    (which ``TrainStep`` of the process), ``step`` and ``batch`` (the
    delivering prefetcher's number, None for a batch from no prefetcher),
    ``spans`` (``train_step.prepare`` / ``.compile`` / ``.execute`` as
    ``[start, end]`` on ``thread``; ``prefetch.stage`` / ``prefetch.wait``
    as ``[start, end, thread]``), ``in_flight`` (this ``TrainStep``'s steps
    dispatched and not yet seen complete, at dispatch),
    ``since_previous_call``, and ``seen_complete``: the first instant at
    which the program saw the step's loss ready, an upper bound of its
    completion by one call's spacing (``unready_at`` is the last look that
    found it running), with ``interval_s`` since the step before."""
    with _LOCK:
        records = [_copy_record(r) for r in _STEPS if "kind" in r]
        stalls = [dict(s, records=[_copy_record(r) for r in s["records"]],
                       calls_meanwhile=[_copy_record(r)
                                        for r in s["calls_meanwhile"]])
                  for s in _STALLS]
    return {"records": records, "stalls": stalls}


# --------------------------------------------------------------------------
# compile-event tracer
# --------------------------------------------------------------------------
_COMPILE_CAP = max(1, _env.get_int("MXNET_TELEMETRY_COMPILE_EVENTS", 512))
_COMPILE_EVENTS: deque = deque(maxlen=_COMPILE_CAP)

_COMPILES_TOTAL = counter(
    "mxnet_compile_events_total",
    "fresh jax.jit traces by kind (op/block/train_step) and cause",
    labelnames=("kind", "cause"))
_COMPILE_HIST = histogram(
    "mxnet_compile_seconds",
    "elapsed trace+compile (+first run for ops) per fresh jit",
    labelnames=("kind",))


def compile_event(kind, name, elapsed_s, cause, **extra):
    """Record one fresh jit trace.  ``kind``: ``op`` (dispatch cache miss),
    ``block`` (hybridized Gluon block build), ``train_step``,
    ``graph_pass`` (one graph-compiler pass application — ``extra``
    carries ``nodes_before``/``nodes_after``).  ``cause`` names why a
    new executable was needed (``new_op``/``new_shape``/``new_dtype``/
    ``new_attrs``/``mode_change``/``recompile``/``trace_failure``/...).
    Extra keyword fields land verbatim on the event record."""
    now = time.perf_counter()
    with _LOCK:
        _WATCH["compiles"] += 1
        _COMPILE_EVENTS.append(dict({"kind": kind, "name": name,
                                     "elapsed_s": float(elapsed_s),
                                     "cause": cause, "time": time.time()},
                                    **extra))
    _COMPILES_TOTAL.labels(kind=kind, cause=cause).inc()
    _COMPILE_HIST.labels(kind=kind).observe(elapsed_s)
    _flight_note("compile", name=str(name), compile_kind=str(kind),
                 cause=str(cause), elapsed_s=float(elapsed_s))
    _chrome_span(f"compile:{kind}:{name}", now - float(elapsed_s), now,
                 "compile")


def compile_events():
    """Recorded compile events, oldest first (bounded ring)."""
    with _LOCK:
        return [dict(e) for e in _COMPILE_EVENTS]


# --------------------------------------------------------------------------
# built-in collectors: dispatch cache + fault seams (scraped, not mirrored)
# --------------------------------------------------------------------------
def _dispatch_cache_collector():
    from .ndarray import dispatch_cache as _dc

    s = _dc.stats()
    def fam(name, mtype, help, value):
        return {"name": name, "type": mtype, "help": help,
                "samples": [({}, value)]}
    return [
        fam("mxnet_dispatch_cache_hits_total", "counter",
            "eager jit-cache hits", s["hits"]),
        fam("mxnet_dispatch_cache_misses_total", "counter",
            "eager jit-cache misses (fresh compiles)", s["misses"]),
        fam("mxnet_dispatch_cache_evictions_total", "counter",
            "eager jit-cache LRU evictions", s["evictions"]),
        fam("mxnet_dispatch_cache_bypasses_total", "counter",
            "eager jit-cache bypasses (unhashable/tracer/blocked)",
            s["bypasses"]),
        fam("mxnet_dispatch_cache_size", "gauge",
            "cached executables", s["size"]),
        fam("mxnet_dispatch_cache_capacity", "gauge",
            "executable LRU capacity", s["capacity"]),
        fam("mxnet_dispatch_cache_enabled", "gauge",
            "1 while the eager jit fast path is on", int(s["enabled"])),
    ]


def _fault_collector():
    from . import fault as _fault

    s = _fault.stats()
    out = []
    for metric, help in (("calls", "seam traversals"),
                         ("trips", "injected/observed seam failures"),
                         ("retries", "transient-error retries")):
        out.append({
            "name": f"mxnet_fault_seam_{metric}_total", "type": "counter",
            "help": help,
            "samples": [({"seam": seam}, c[metric])
                        for seam, c in sorted(s.items())]})
    return out


register_collector(_dispatch_cache_collector)
register_collector(_fault_collector)
register_collector(_goodput_collector)


# --------------------------------------------------------------------------
# exporters
# --------------------------------------------------------------------------
def _collected_families():
    with _LOCK:
        collectors = list(_COLLECTORS)
    out = []
    for fn in collectors:
        try:
            out.extend(fn())
        except Exception:   # a broken collector must not kill the scrape
            continue
    return out


def _escape_label(v):
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _fmt_labels(labels):
    if not labels:
        return ""
    inner = ",".join(f'{k}="{_escape_label(v)}"'
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def _fmt_value(v):
    if v == float("inf"):
        return "+Inf"
    f = float(v)
    return repr(int(f)) if f == int(f) else repr(f)


def render_prometheus():
    """Prometheus text exposition (version 0.0.4) of every registered
    family plus collector output."""
    drain_step_scalars()
    lines = []
    with _LOCK:
        families = list(_FAMILIES.values())
    for fam in families:
        lines.append(f"# HELP {fam.name} {fam.help}")
        lines.append(f"# TYPE {fam.name} {fam.type}")
        for values, child in fam.children():
            labels = dict(zip(fam.labelnames, values))
            if fam.type == "histogram":
                for le, cum in child.cumulative():
                    bl = dict(labels)
                    bl["le"] = _fmt_value(le)
                    lines.append(f"{fam.name}_bucket{_fmt_labels(bl)} {cum}")
                lines.append(f"{fam.name}_sum{_fmt_labels(labels)} "
                             f"{_fmt_value(child.sum)}")
                lines.append(f"{fam.name}_count{_fmt_labels(labels)} "
                             f"{child.count}")
            else:
                lines.append(f"{fam.name}{_fmt_labels(labels)} "
                             f"{_fmt_value(child.value)}")
    for fd in _collected_families():
        lines.append(f"# HELP {fd['name']} {fd.get('help', '')}")
        lines.append(f"# TYPE {fd['name']} {fd['type']}")
        for labels, value in fd["samples"]:
            lines.append(f"{fd['name']}{_fmt_labels(labels)} "
                         f"{_fmt_value(value)}")
    return "\n".join(lines) + "\n"


def snapshot():
    """JSON-able snapshot: every metric family (registered + collected),
    the step timeline, the fused steps' records (``"step_records"``),
    compile events, and aggregate summaries.  Embedded in
    ``profiler.dump()`` otherData and ``bench.py`` extras."""
    drain_step_scalars()
    metrics = {}
    with _LOCK:
        families = list(_FAMILIES.values())
    for fam in families:
        samples = []
        for values, child in fam.children():
            labels = dict(zip(fam.labelnames, values))
            if fam.type == "histogram":
                samples.append({
                    "labels": labels,
                    "buckets": {_fmt_value(le): cum
                                for le, cum in child.cumulative()},
                    "sum": child.sum, "count": child.count})
            else:
                samples.append({"labels": labels, "value": child.value})
        metrics[fam.name] = {"type": fam.type, "help": fam.help,
                             "samples": samples}
    for fd in _collected_families():
        metrics[fd["name"]] = {
            "type": fd["type"], "help": fd.get("help", ""),
            "samples": [{"labels": labels, "value": value}
                        for labels, value in fd["samples"]]}
    steps = timeline()
    phase_totals: dict = {}
    for rec in steps:
        for pname, dt in rec["phases"].items():
            phase_totals[pname] = phase_totals.get(pname, 0.0) + dt
    events = compile_events()
    # totals come from the counter/histogram families, NOT the bounded
    # event ring: in a long retrace storm the ring keeps only the tail —
    # the diagnosis payload must not understate compile pressure exactly
    # when it is worst
    with _LOCK:
        n_compiles = sum(c.value
                         for _, c in _COMPILES_TOTAL.children())
        compile_s = sum(h.sum for _, h in _COMPILE_HIST.children())
    return {
        "time": time.time(),
        "metrics": metrics,
        "steps": steps,
        "step_phase_totals": phase_totals,
        "step_records": step_records(),
        "compile_events": events,
        "compile": {"count": int(n_compiles), "total_s": compile_s,
                    "events_kept": len(events)},
        "goodput": goodput_summary(),
        "graph": _graph_section(),
    }


def _graph_section():
    """Graph-compiler pipeline stats (pipeline runs, per-pass node
    deltas, fused-op count).  Import is lazy and failure-tolerant: the
    snapshot must work before (or without) the graph tier loading."""
    try:
        from .graph import stats_snapshot as _gs

        return _gs()
    except Exception:
        return {}


def reset():
    """Zero every registered family and clear the timeline + compile ring
    (test isolation; collectors' sources have their own reset_stats)."""
    global _CUR
    with _LOCK:
        for fam in _FAMILIES.values():
            for values in list(fam._children):
                fam._children[values] = fam._new_child()
            if not fam.labelnames:
                fam._children.setdefault((), fam._new_child())
        _STEPS.clear()
        _STALLS.clear()
        _GC2.clear()
        _WATCH["last"] = _TAKEN.batch = None
        _DEFERRED.clear()
        _SEEN.clear()
        _COMPILE_EVENTS.clear()
        _CUR = None
        _STEP_SEQ[0] = 0
        _HEARTBEAT[0] = None
        _SLO_STATE.update(last=None, below=0, fired=False)


# --------------------------------------------------------------------------
# HTTP endpoint (opt-in: MXNET_TELEMETRY_PORT or start_http_server)
# --------------------------------------------------------------------------
_HTTP_SERVER = None
_HTTP_THREAD = None
_HTTP_ROUTES: dict = {}   # path -> handler(method, path, query, body_bytes)


def register_http_route(path, handler):
    """Mount an application route on the telemetry endpoint.

    ``handler(method, path, query, body_bytes) -> (status, content_type,
    body_bytes[, headers_dict])`` is called for GET and POST requests
    whose path matches exactly; the optional 4th element carries extra
    response headers (the fleet router's 429 Retry-After rides it).
    This is how the serving plane (:mod:`mxnet_tpu.serving`)
    exposes its inference API beside ``/metrics`` — one 127.0.0.1 server
    per process, one port to scrape and to query.  Routes registered
    after the server started are live immediately (the handler resolves
    them per request).  Built-in paths (``/metrics``, ``/snapshot``,
    ``/healthz``) cannot be shadowed."""
    with _LOCK:
        _HTTP_ROUTES[path] = handler


def unregister_http_route(path):
    """Remove a mounted route (idempotent)."""
    with _LOCK:
        _HTTP_ROUTES.pop(path, None)


def _dispatch_route(method, path, query, body):
    with _LOCK:
        handler = _HTTP_ROUTES.get(path)
    if handler is None:
        return None
    try:
        return handler(method, path, query, body)
    except Exception as e:   # a broken app route must not kill the server
        return (500, "text/plain",
                f"route {path} failed: {e!r}\n".encode())


def start_http_server(port=None, addr="127.0.0.1"):
    """Serve ``/metrics`` (Prometheus text), ``/snapshot`` (JSON), and
    ``/healthz`` on a daemon thread.  ``port=0`` picks a free port; the
    bound port is on the returned server (``server_address[1]``).
    Idempotent: a second call returns the running server."""
    global _HTTP_SERVER, _HTTP_THREAD
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    if port is None:
        port = _env.get_int("MXNET_TELEMETRY_PORT", 0)

    class _Handler(BaseHTTPRequestHandler):
        def _reply(self, status, ctype, body, headers=None):
            self.send_response(status)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            for k, v in (headers or {}).items():
                self.send_header(k, str(v))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            path, _, query = self.path.partition("?")
            if path in ("/metrics", "/"):
                self._reply(200, "text/plain; version=0.0.4; charset=utf-8",
                            render_prometheus().encode())
            elif path in ("/snapshot", "/json"):
                self._reply(200, "application/json",
                            json.dumps(snapshot()).encode())
            elif path == "/healthz":
                self._reply(200, "text/plain", b"ok\n")
            else:
                out = _dispatch_route("GET", path, query, b"")
                if out is None:
                    self.send_response(404)
                    self.end_headers()
                    return
                self._reply(*out)

        def do_POST(self):
            path, _, query = self.path.partition("?")
            length = int(self.headers.get("Content-Length") or 0)
            body = self.rfile.read(length) if length else b""
            out = _dispatch_route("POST", path, query, body)
            if out is None:
                self.send_response(404)
                self.end_headers()
                return
            self._reply(*out)

        def log_message(self, *a):   # no per-scrape stderr spam
            pass

    # check-and-create under one lock section: two racing callers must not
    # each bind a server (the loser's socket/thread would leak unreachable)
    with _LOCK:
        if _HTTP_SERVER is not None:
            return _HTTP_SERVER
        server = ThreadingHTTPServer((addr, int(port)), _Handler)
        server.daemon_threads = True
        thread = threading.Thread(target=server.serve_forever,
                                  name="mxnet-telemetry-http", daemon=True)
        thread.start()
        _HTTP_SERVER, _HTTP_THREAD = server, thread
        return server


def stop_http_server():
    """Shut the background endpoint down (idempotent)."""
    global _HTTP_SERVER, _HTTP_THREAD
    with _LOCK:
        server, thread = _HTTP_SERVER, _HTTP_THREAD
        _HTTP_SERVER = _HTTP_THREAD = None
    if server is not None:
        server.shutdown()
        server.server_close()
    if thread is not None:
        thread.join(timeout=5)
