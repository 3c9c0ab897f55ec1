"""From a profiler trace to numbers: device busy and idle time, a named
kernel's time, and who the host was when the device sat idle.

``read_xplane`` turns JAX's ``.xplane.pb`` into plain event lists (seconds
on the profiler's clock, which host threads and devices share); everything
else works on those lists, so it is checked on the recorded events in
``chipbench/testdata`` with no profiler at hand.

What a TPU v5e trace looks like (looked at by hand, PR 23): one plane per
chip named ``/device:TPU:<n>`` with the lines ``XLA Ops`` (one event per
executed HLO op, named by its HLO text ``%name = shape op(...)``),
``XLA Modules``, ``Steps`` and ``Async XLA Ops`` (DMA copies and the
start-to-done spans of asynchronous collectives, which overlap compute);
``jax.profiler.TraceAnnotation`` spans land on the annotating thread's
line of the plane ``/host:CPU``.
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def op_name(event_name):
    """``%fusion.8 = f32[...] fusion(...)`` -> ``fusion.8``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def read_xplane(path, host_spans):
    """``{"devices": {chip: {"ops": [...]}}, "host": [...]}`` with every
    event as ``[name, start_s, duration_s]``.  Host events are those of the
    benchmark's own spans."""
    from jax.profiler import ProfileData

    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            out["devices"][m.group(1)] = {"ops": [
                [e.name[:160], e.start_ns * 1e-9, e.duration_ns * 1e-9]
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events]}
        elif plane.name == "/host:CPU":
            # the annotating thread's line is named after the process
            for line in plane.lines:
                out["host"] += [
                    [e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9]
                    for e in line.events if e.name in host_spans]
    if not out["devices"]:
        raise ValueError(f"{path} holds no /device:TPU plane")
    return out


def clip(events, t0, t1):
    """The part of each event inside [t0, t1]."""
    out = []
    for name, start, dur in events:
        a, b = max(start, t0), min(start + dur, t1)
        if b > a:
            out.append([name, a, b - a])
    return out


def union(events):
    """Sorted, merged [start, end] intervals covered by ``events``."""
    merged = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], start + dur)
        else:
            merged.append([start, start + dur])
    return merged


def length(intervals):
    return sum(b - a for a, b in intervals)


def subtract(intervals, cover):
    """The part of ``intervals`` that ``cover`` does not cover (both merged)."""
    out = []
    for a, b in intervals:
        for c, d in cover:
            if d <= a:
                continue
            if c >= b:
                break
            if c > a:
                out.append([a, c])
            a = max(a, d)
            if a >= b:
                break
        if a < b:
            out.append([a, b])
    return out


def window_of(trace):
    """The traced window: from the first of the benchmark's host spans to
    the end of the last."""
    if not trace["host"]:
        raise ValueError("the trace holds none of the benchmark's host spans")
    return (min(s for _, s, _ in trace["host"]),
            max(s + d for _, s, d in trace["host"]))


def kernel_events(trace, window, pattern):
    """Per chip, the clipped ``XLA Ops`` events whose name holds ``pattern``."""
    return {chip: [e for e in clip(dev["ops"], *window) if pattern in e[0]]
            for chip, dev in trace["devices"].items()}


def idle_gaps(dev, host, window):
    """Idle gaps of one chip inside the window as ``[span, seconds]``, each
    named by the innermost of the benchmark's host spans open when the
    gap began (``none`` where there was none)."""
    busy = union(clip(dev["ops"], *window))
    gaps = subtract([list(window)], busy)
    out = []
    for a, b in gaps:
        open_ = [(s, n) for n, s, d in host if s <= a < s + d]
        out.append([max(open_)[1] if open_ else "none", b - a])
    return out


def summary(trace, steps):
    """What the harness reports from a traced window of ``steps`` steps."""
    window = window_of(trace)
    window_s = window[1] - window[0]
    chips = sorted(trace["devices"])
    busy = {c: length(union(clip(trace["devices"][c]["ops"], *window)))
            for c in chips}
    if not any(busy.values()):
        raise ValueError("no operation ran on a device inside the window")
    op_time, gap_time = {}, {}
    for c in chips:
        for name, _, dur in clip(trace["devices"][c]["ops"], *window):
            op_time[op_name(name)] = op_time.get(op_name(name), 0.0) + dur
        for span, dur in idle_gaps(trace["devices"][c], trace["host"], window):
            gap_time[span] = gap_time.get(span, 0.0) + dur
    top = lambda d: [[k, v / len(chips)] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"window_s": window_s, "steps": steps, "chips": len(chips),
            "busy_s": sum(busy.values()) / len(chips),
            "busy_s_by_chip": busy,
            "breakdown": {"device_ops": top(op_time),
                          "idle_gaps": top(gap_time)}}
