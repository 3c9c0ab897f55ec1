"""Profiler: per-op stats + Chrome trace over jax.profiler.

Reference: ``python/mxnet/profiler.py`` + ``src/profiler/`` (SURVEY.md
§6.1): Chrome-trace event file, per-op aggregate statistics table
(``dumps()``), user scopes/markers/counters.  TPU mapping:

- ``start()/stop()`` also drive ``jax.profiler`` traces (XLA per-HLO-op
  attribution, open in TensorBoard/Perfetto) — the on-device truth.
- Python-level op events come from the ``invoke`` seam: when
  ``profile_imperative`` (or profile_all) is set, each imperative op is
  timed with a sync, exactly the trade the reference's profiler makes
  (honest per-op wall time requires serializing the async engine).
- ``dump()`` writes a standard Chrome ``traceEvents`` JSON (op spans,
  markers as instant events, counters as counter events);
  ``dumps()`` returns the aggregate per-op summary table.
"""
from __future__ import annotations

import hashlib
import json
import os
import re
import sys
import threading
import time
from collections import OrderedDict

from .base import MXNetError

__all__ = ["set_config", "start", "stop", "dump", "dumps", "pause", "resume",
           "Task", "Frame", "Marker", "Counter", "Domain", "Scope",
           "scopes_of", "register_executable", "op_scopes", "scope_digest"]

# ``jax.named_scope`` names inside the compiled programs: they land in
# every HLO instruction's ``op_name`` metadata, which is how a device trace
# (and ``scopes_of``) tells one part of a fused step from another.  The
# backward pass needs no scope of its own: JAX names the ops it derives
# from the forward ``transpose(jvp(mx_forward))``.
SCOPE_FORWARD = "mx_forward"
SCOPE_OPTIMIZER = "mx_optimizer"
SCOPE_ATTENTION_BWD = "mxnet_flash_attention_bwd"
SCOPE_ATTENTION_PLAIN_FWD = "mxnet_attention_plain_fwd"
# the Pallas attention kernels' own names (ops/flash_attention.py): the
# forward's, then "_window" under the window, then "_segments" under segment
# ids (mxnet_flash_attention_fwd_window_segments); the backward kernel's
# is the backward's scope with the same suffixes
KERNEL_ATTENTION_FWD = "mxnet_flash_attention_fwd"
KERNEL_SUFFIX_WINDOW = "_window"
KERNEL_SUFFIX_SEGMENTS = "_segments"
# the expert layer (parallel/expert_parallel.py, dropless routing): router,
# top-k, sort, gather and scatter; and the grouped products over the
# experts held.  Backward ops carry transpose(jvp(<scope>))
SCOPE_MOE_ROUTE = "mx_moe_route"
SCOPE_MOE_EXPERTS = "mx_moe_experts"
# the way back's Pallas kernel (ops/moe_add_rows.py): a part's live rows
# added into their tokens' rows in place; a row of mx_moe_route
KERNEL_MOE_ADD_ROWS = "mxnet_moe_add_rows"
# the embedding's way back (ops/embed_add_rows.py): one pass over the table's
# gradient that adds the cotangent's rows into their ids' rows, fetched by
# DMA through the sorted ids; called under SCOPE_EMBED, a row of mx_embed
KERNEL_EMBED_ADD_ROWS = "mxnet_embed_add_rows"
# the shared expert beside them (model_zoo/language/llama.py::LlamaMoEMLP):
# a dense SwiGLU that every token passes
SCOPE_MOE_SHARED = "mx_moe_shared"
# the mixers that are no softmax attention over per-head K and V
# (model_zoo/language/llama.py): the chunked gated delta rule (ops/kda.py),
# whose forward and hand-written backward are named inside it by
# KERNEL_KDA_FWD / KERNEL_KDA_BWD (scopes of its passes, no parts of their
# own); and what stands around a mixer's core: the short convolution with
# its SiLU, the l2 norm of q and k, decay and beta, the gated norm of the
# delta rule's output, latent attention's head-wise gate
SCOPE_KDA = "mx_kda"
SCOPE_MIXER_GATE = "mx_mixer_gate"
KERNEL_KDA_FWD = "mxnet_kda_fwd"
KERNEL_KDA_BWD = "mxnet_kda_bwd"
# the selective state-space scan (ops/selective_scan.py) of a state-space
# layer: the scope around the op, and inside it the names of its two walks
# (the Pallas kernels' own names on a TPU, scopes of the scans elsewhere)
SCOPE_SSM_SCAN = "mx_ssm_scan"
KERNEL_SSM_SCAN_FWD = "mxnet_selective_scan_fwd"
KERNEL_SSM_SCAN_BWD = "mxnet_selective_scan_bwd"
# the transformer block's own parts (model_zoo/language/llama.py, bert.py;
# the loss in parallel/data_parallel.py::TrainStep), entered at the call
# sites one after the other, so that no op's own name holds two of them:
# the q/k/v/o projections with the gate's and the head reshapes beside them;
# a dense layer's FFN; every norm of a block; RoPE and its positions; the
# embedding gathers; the head (BERT's MLM transform, pooler and NSP with
# it); the cast of the outputs to float32 and the caller's loss function
SCOPE_ATTENTION_PROJ = "mx_attn_proj"
SCOPE_FFN = "mx_ffn"
SCOPE_NORM = "mx_norm"
SCOPE_ROPE = "mx_rope"
SCOPE_EMBED = "mx_embed"
SCOPE_HEAD = "mx_head"
SCOPE_LOSS = "mx_loss"


def scope_digest():
    """Eight hex digits over every ``SCOPE_*`` / ``KERNEL_*`` constant of
    this module.  ``TrainStep`` names its jitted function with it: JAX's
    persistent compile-cache key holds a program's name and leaves its
    metadata out, so an executable cached before a scope was added or
    renamed would come back with the old op names."""
    named = sorted((k, v) for k, v in globals().items()
                   if k.startswith(("SCOPE_", "KERNEL_")))
    return hashlib.sha1(repr(named).encode()).hexdigest()[:8]

_CONFIG = {"filename": "profile.json", "profile_all": False,
           "profile_imperative": False, "dir": None, "jax_trace": True,
           "continuous_dump": False}
_ACTIVE = False
_PAUSED = False
_LOCK = threading.Lock()
_EVENTS = []   # chrome trace events
_AGG = {}      # opname -> [count, total_s, min_s, max_s]
_T0 = None
_DUMPED_ONCE = False  # continuous_dump: later dumps merge into the file


def set_config(profile_all=False, profile_symbolic=False,
               profile_imperative=False, profile_memory=False,
               profile_api=False, filename="profile.json",
               continuous_dump=False, jax_trace=True, **kwargs):
    global _DUMPED_ONCE
    _CONFIG.update(profile_all=profile_all, filename=filename,
                   profile_imperative=profile_imperative or profile_all,
                   jax_trace=jax_trace,
                   continuous_dump=bool(continuous_dump))
    _CONFIG["dir"] = os.path.dirname(os.path.abspath(filename)) or "."
    _DUMPED_ONCE = False


def _record_op(opname, t0, t1):
    with _LOCK:
        _EVENTS.append({"name": opname, "ph": "X", "pid": 0,
                        "tid": threading.get_ident() % 1000,
                        "ts": (t0 - _T0) * 1e6, "dur": (t1 - t0) * 1e6,
                        "cat": "operator"})
        agg = _AGG.get(opname)
        dt = t1 - t0
        if agg is None:
            _AGG[opname] = [1, dt, dt, dt]
        else:
            agg[0] += 1
            agg[1] += dt
            agg[2] = min(agg[2], dt)
            agg[3] = max(agg[3], dt)


def _instant(name, cat):
    if _T0 is None or not _ACTIVE or _PAUSED:
        return
    with _LOCK:
        _EVENTS.append({"name": name, "ph": "i", "pid": 0, "s": "g",
                        "tid": threading.get_ident() % 1000,
                        "ts": (time.perf_counter() - _T0) * 1e6, "cat": cat})


def _record_span(name, t0, t1, cat="step_phase", tid=1000, args=None):
    """Telemetry hook: merge a step-phase / compile / request span into
    the Chrome trace (its own tid row so phases don't interleave with op
    events).  ``t0``/``t1`` are perf_counter values — the same clock as
    ``_T0``; ``args`` (JSON-able dict) lands on the event verbatim (the
    serving request tracer carries trace ids/outcomes through it)."""
    if _T0 is None or not _ACTIVE or _PAUSED:
        return
    ev = {"name": name, "ph": "X", "pid": 0, "tid": tid,
          "ts": (t0 - _T0) * 1e6, "dur": (t1 - t0) * 1e6,
          "cat": cat}
    if args:
        ev["args"] = dict(args)
    with _LOCK:
        _EVENTS.append(ev)


def _counter(name, value):
    if _T0 is None or not _ACTIVE or _PAUSED:
        return
    with _LOCK:
        _EVENTS.append({"name": name, "ph": "C", "pid": 0,
                        "ts": (time.perf_counter() - _T0) * 1e6,
                        "args": {name: value}})


def start():
    global _ACTIVE, _T0, _PAUSED, _DUMPED_ONCE
    from .ndarray.ndarray import _PROFILE

    _T0 = time.perf_counter()
    _PAUSED = False
    _DUMPED_ONCE = False  # a new session never merges into an old file
    if _CONFIG.get("jax_trace", True):
        import jax

        logdir = _CONFIG.get("dir") or "."
        jax.profiler.start_trace(os.path.join(logdir, "jax_trace"))
    if _CONFIG.get("profile_imperative") or _CONFIG.get("profile_all"):
        _PROFILE["record"] = _record_op
        _PROFILE["on"] = True
    _ACTIVE = True


def stop():
    global _ACTIVE
    from .ndarray.ndarray import _PROFILE

    if not _ACTIVE:
        return
    _PROFILE["on"] = False
    _PROFILE["record"] = None
    if _CONFIG.get("jax_trace", True):
        import jax

        jax.profiler.stop_trace()
    _ACTIVE = False


def pause():
    global _PAUSED
    from .ndarray.ndarray import _PROFILE

    _PAUSED = True
    _PROFILE["on"] = False


def resume():
    global _PAUSED
    from .ndarray.ndarray import _PROFILE

    if not _ACTIVE:  # resume without a prior start() is a no-op
        return
    _PAUSED = False
    if _CONFIG.get("profile_imperative") or _CONFIG.get("profile_all"):
        _PROFILE["record"] = _record_op
        _PROFILE["on"] = True


def dump(finished=True, profile_process="worker", drain=None):
    """Write the Chrome traceEvents file (open in chrome://tracing /
    Perfetto; the XLA-level trace lives in jax_trace/ for TensorBoard).

    ``drain=True`` removes the written events from the in-memory buffer
    so a later dump never re-emits them.  Under
    ``set_config(continuous_dump=True)`` draining is implied (the file IS
    the buffer then — ``drain=False`` is ignored) and successive
    ``dump()`` calls MERGE the drained increments into the existing trace
    file, so periodic dumping from a long-running job yields one growing,
    duplicate-free trace."""
    global _DUMPED_ONCE
    from . import fault as _fault
    from . import telemetry as _telemetry
    from .ndarray import dispatch_cache as _dc

    if _CONFIG["continuous_dump"]:
        # the merge base is "everything drained so far"; leaving events
        # undrained while merging would re-emit them on the next dump
        drain = True
    elif drain is None:
        drain = False
    dstats = _dc.stats()
    with _LOCK:
        events = list(_EVENTS)
        if drain:
            _EVENTS.clear()
    if _CONFIG["continuous_dump"] and _DUMPED_ONCE and \
            os.path.exists(_CONFIG["filename"]):
        try:
            with open(_CONFIG["filename"]) as f:
                prior = json.load(f).get("traceEvents", [])
            events = prior + events
        except (OSError, ValueError):
            pass  # unreadable prior dump: write this increment standalone
    with open(_CONFIG["filename"], "w") as f:
        json.dump({"traceEvents": events,
                   "displayTimeUnit": "ms",
                   "otherData": {
                       "xla_trace": "see jax_trace/ (TensorBoard)",
                       "eager_dispatch_cache": {
                           k: dstats[k] for k in
                           ("enabled", "hits", "misses", "evictions",
                            "bypasses", "size", "capacity")},
                       "fault_seams": _fault.stats(),
                       "telemetry": _telemetry.snapshot()}}, f)
    _DUMPED_ONCE = True
    return _CONFIG["filename"]


def dumps(reset=False):
    """Aggregate per-op statistics table (reference: profiler.dumps), with
    the eager dispatch-cache hit/miss per op (ndarray/dispatch_cache.py)
    appended so the jit fast path's behavior shows up next to the timings.

    The Jit columns are the dispatch cache's own cumulative counters (all
    invokes since mx.nd.reset_dispatch_stats(), profiling on or off) — they
    are NOT bounded by the Count column, which only accumulates while
    profiling is active, and ``reset=True`` does not clear them."""
    from .ndarray import dispatch_cache as _dc

    dstats = _dc.stats()
    per_op = dstats["per_op"]
    with _LOCK:
        rows = [(name, a[0], a[1] * 1e3, a[2] * 1e3, a[3] * 1e3,
                 a[1] / a[0] * 1e3) for name, a in sorted(_AGG.items())]
        if reset:
            _AGG.clear()
            _EVENTS.clear()
    lines = ["Profile Statistics:",
             f"{'Name':<32}{'Total Count':>12}{'Total(ms)':>12}"
             f"{'Min(ms)':>10}{'Max(ms)':>10}{'Avg(ms)':>10}"
             f"{'JitHit':>8}{'JitMiss':>8}"]
    for name, cnt, tot, mn, mx, avg in rows:
        hm = per_op.get(name)
        hit, miss = (hm["hits"], hm["misses"]) if hm else (0, 0)
        lines.append(f"{name:<32}{cnt:>12}{tot:>12.3f}{mn:>10.3f}"
                     f"{mx:>10.3f}{avg:>10.3f}{hit:>8}{miss:>8}")
    lines.append(
        f"Eager dispatch cache: enabled={dstats['enabled']} "
        f"hits={dstats['hits']} misses={dstats['misses']} "
        f"evictions={dstats['evictions']} bypasses={dstats['bypasses']} "
        f"size={dstats['size']}/{dstats['capacity']} "
        "(cumulative since reset_dispatch_stats; not scoped to profiling)")
    # failure-domain counters (mxnet_tpu.fault): which seams saw traffic,
    # injected/observed trips, and transient-error retries — cumulative
    # since fault.reset_stats(), like the dispatch-cache counters above
    from . import fault as _fault

    fstats = _fault.stats()
    lines.append(f"Fault seams:{'':<20}{'Calls':>12}{'Trips':>10}"
                 f"{'Retries':>10}")
    for seam in _fault.SEAMS:
        c = fstats[seam]
        lines.append(f"  {seam:<30}{c['calls']:>12}{c['trips']:>10}"
                     f"{c['retries']:>10}")
    return "\n".join(lines)


# --------------------------------------------------------------------------
# op-to-scope tables: which part of the step an HLO instruction belongs to
# --------------------------------------------------------------------------
_OP_SCOPES: OrderedDict = OrderedDict()   # name -> table, newest last
_OP_SCOPES_CAP = 8

_HLO_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_HLO_OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
# computations whose instructions a device trace shows beside the entry's:
# loop bodies and conditions, branches, and what a ``call`` applies
_HLO_SHOWN = re.compile(
    r"\b(?:body|condition|true_computation|false_computation)=%?([\w.\-]+)"
    r"|\bbranch_computations=\{([^}]*)\}")
_HLO_TO_APPLY = re.compile(r"\bto_apply=%?([\w.\-]+)")


def _scope_classes(op_names):
    """Sorted classes among ``forward`` / ``backward`` / ``optimizer`` that
    the ``op_name``s fall in."""
    found = set()
    for name in op_names:
        if SCOPE_OPTIMIZER in name:
            found.add("optimizer")
        elif SCOPE_FORWARD in name:
            found.add("backward" if "transpose(" in name else "forward")
    return sorted(found)


# What an instruction is by its own name, whatever its metadata says: the
# TPU's expansion of a grouped product drops the program's scope
# (``ragged-dot-*`` custom calls, named so in ``op_name`` too), and GSPMD
# hands an all-reduce the name of the gradient it sums.
PART_COLLECTIVES = "collectives"
PART_OPTIMIZER = "optimizer"
PART_MIXED = "mixed"
_PART_BY_INSTRUCTION = (("ragged-dot", SCOPE_MOE_EXPERTS),
                        ("all-reduce", PART_COLLECTIVES))


def _part_finder():
    """``innermost(name)``: the last part name in ``name`` (an ``op_name``
    or an instruction's name), or ``""``.  The part names are the scopes
    above but ``mx_forward`` and ``mx_optimizer`` (classes, not parts) and
    the attention forward kernel's name; a step's few hundred distinct
    names are looked at once each."""
    names = {v for k, v in globals().items() if k.startswith("SCOPE_")}
    names = names - {SCOPE_FORWARD, SCOPE_OPTIMIZER} | {KERNEL_ATTENTION_FWD}
    findall = re.compile("|".join(
        map(re.escape, sorted(names, key=len, reverse=True)))).findall
    memo = {}

    def innermost(name):
        if name not in memo:
            found = findall(name)
            memo[name] = found[-1] if found else ""
        return memo[name]

    return innermost


def _hlo_computations(text):
    """``(entry, {computation: [(instruction, own op_name, what follows the
    name on its line)]})`` of an HLO module's text."""
    computations = {}
    entry = current = None
    for line in text.splitlines():
        if not line:
            continue
        if line[0] not in " }" and line.endswith("{"):
            head = line.split(None, 2)
            is_entry = head[0] == "ENTRY"
            name = head[1 if is_entry else 0].lstrip("%")
            current = computations[name] = []
            if is_entry:
                entry = name
        elif line[0] == "}":
            current = None
        elif current is not None:
            m = _HLO_INSTRUCTION.match(line)
            if m:
                own = _HLO_OP_NAME.search(line)
                current.append((m.group(1), own.group(1) if own else "",
                                line[m.end() - 1:]))
    return entry, computations


def scopes_of(compiled):
    """``{instruction: {"scope": op_name, "classes": [...], "part": name}}``
    for every instruction of a compiled executable that a device trace can
    show: the entry computation's, and those of loop bodies, branches and
    calls.

    ``scope`` is the instruction's ``op_name`` metadata verbatim (the
    ``jax.named_scope`` path it was traced under); ``classes`` is the
    sorted set of ``forward`` / ``backward`` / ``optimizer`` over that name
    and, for a fusion, the names of the instructions it fused (those of
    nested fusions too).  One class: cleanly attributed.  Two or more: a
    fusion the compiler made across the parts.  None: unscoped (copies,
    infeed).

    ``part`` resolves every instruction to one part of the model by one
    rule.  (i) What the instruction is by itself: an attention kernel, a
    grouped product or an all-reduce by its instruction name, else the
    innermost (last) part scope in its own ``op_name`` (names the compiler
    joined by ``;`` each by itself).  Else (ii), for a fusion, the one part
    among the ``op_name``s of what it fused, nested fusions too;
    ``mx_optimizer`` is no part, so a weight gradient's matmul with Adam's
    update as epilogue is the part of its matmul.  Else (iii) ``"mixed"``
    where it fused several parts, ``"optimizer"`` where ``mx_optimizer``
    alone names it, ``""`` where nothing does (the compiler's copies and
    fills).  A ``while`` / ``call`` / ``conditional`` keeps the part of its
    own name; the ops of its body are rows of their own.

    Parsed once from ``compiled.as_text()``; empty where the text cannot
    be had or carries no metadata, so that a reader finds nothing rather
    than something wrong."""
    try:
        text = compiled.as_text()
    except Exception:   # a runtime that keeps no text for this executable
        return {}
    if not text:
        return {}
    entry, computations = _hlo_computations(text)
    innermost = _part_finder()

    def fused_names(comp, seen):
        if comp in seen:
            return []
        seen.add(comp)
        out = []
        for _, own, rest in computations.get(comp, ()):
            out.append(own)
            for called in _HLO_CALLS.findall(rest):
                out += fused_names(called, seen)
        return out

    def part_of(name, names):
        for prefix, part in _PART_BY_INSTRUCTION:
            if name.startswith(prefix) or names[0].startswith(prefix):
                return part
        kernel = innermost(name.split(".", 1)[0])
        if kernel:
            return kernel
        # where the compiler merged instructions it joined their names by
        # ";": the own name is then several, each looked at by itself
        own = set(map(innermost, names[0].split(";"))) - {""}
        if len(own) == 1:
            return own.pop()
        fused = own | set(map(innermost, names[1:])) - {""}
        if fused:
            return fused.pop() if len(fused) == 1 else PART_MIXED
        return PART_OPTIMIZER if any(
            SCOPE_OPTIMIZER in n for n in names) else ""

    table = {}
    todo, shown = [entry], set()
    while todo:
        comp = todo.pop()
        if comp in shown or comp not in computations:
            continue
        shown.add(comp)
        for name, own, rest in computations[comp]:
            names = [own]
            for called in _HLO_CALLS.findall(rest):
                names += fused_names(called, set())
            table[name] = {"scope": sys.intern(own),
                           "classes": _scope_classes(names),
                           "part": part_of(name, names)}
            for one, many in _HLO_SHOWN.findall(rest):
                todo += [one] if one else [
                    c.strip().lstrip("%") for c in many.split(",")]
            opcode = _HLO_OPCODE.search(rest)
            if opcode and opcode.group(1) == "call":
                todo += _HLO_TO_APPLY.findall(rest)
    if not any(row["scope"] for row in table.values()):
        return {}
    return table


def register_executable(name, compiled):
    """Keep ``scopes_of(compiled)`` under ``name`` for ``op_scopes()`` and
    return it.  The table is kept, never the executable (a loaded step
    program holds gigabytes of device scratch); the newest
    ``_OP_SCOPES_CAP`` names stay, and a name registered again replaces
    its table."""
    table = scopes_of(compiled)
    with _LOCK:
        _OP_SCOPES.pop(name, None)
        _OP_SCOPES[name] = table
        while len(_OP_SCOPES) > _OP_SCOPES_CAP:
            _OP_SCOPES.popitem(last=False)
    return table


def op_scopes():
    """The registered op-to-scope tables, ``{name: table}``, oldest first.
    ``TrainStep`` registers ``train_step:<NetClass>`` at each compile.
    With a Perfetto trace of ``fusion.801`` in hand,
    ``op_scopes()["train_step:MyNet"]["fusion.801"]`` says which part of
    the step it is."""
    with _LOCK:
        return OrderedDict(_OP_SCOPES)


class Domain:
    def __init__(self, name):
        self.name = name


class _Scope:
    def __init__(self, name):
        self.name = name
        self._ctx = None
        self._t0 = None

    def start(self):
        import jax

        self._ctx = jax.profiler.TraceAnnotation(self.name)
        self._ctx.__enter__()
        self._t0 = time.perf_counter()

    def stop(self):
        if self._ctx is not None:
            self._ctx.__exit__(None, None, None)
            self._ctx = None
        if self._t0 is not None and _T0 is not None and _ACTIVE \
                and not _PAUSED:
            _record_op(f"scope:{self.name}", self._t0, time.perf_counter())
        self._t0 = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *a):
        self.stop()
        return False


class Task(_Scope):
    def __init__(self, domain=None, name="task"):
        super().__init__(name)


class Frame(_Scope):
    def __init__(self, domain=None, name="frame"):
        super().__init__(name)


class Marker:
    """Instant event in the trace (reference: profiler.Marker.mark)."""

    def __init__(self, domain=None, name="marker"):
        self.name = name

    def mark(self, scope="process"):
        _instant(self.name, "marker")


class Counter:
    """Named counter recorded into the trace (reference: profiler.Counter)."""

    def __init__(self, domain=None, name="counter", value=0):
        self.name = name
        self.value = value
        _counter(self.name, value)

    def set_value(self, value):
        self.value = value
        _counter(self.name, value)

    def increment(self, delta=1):
        self.set_value(self.value + delta)

    def decrement(self, delta=1):
        self.set_value(self.value - delta)

    def __iadd__(self, delta):
        self.increment(delta)
        return self

    def __isub__(self, delta):
        self.decrement(delta)
        return self


Scope = _Scope
