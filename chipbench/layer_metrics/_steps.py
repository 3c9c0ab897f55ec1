"""What the readers of the program's step records share: the records, their
join with the traced window, and a chip's idle time split by what the
program was doing.

The program keeps a record a call of its fused step
(``mxnet_tpu.telemetry.snapshot()["step_records"]``): the step's number and
its batch's, the spans of the call and of the batch's staging on
``time.perf_counter()``, the steps in flight at dispatch, what the process
did since the previous call, and the instant at which a later call first
saw the step complete.  A program that keeps none (an older one) gives
every reader here None.

**The two clocks.**  The profiler's times run from the trace's start and
the records' from ``time.perf_counter()``'s.  The benchmark's own
``dispatch_step`` spans are on the first, one a step, and each encloses
exactly one call of the program; the last records of the ring are the
traced window's calls (nothing dispatches after it).  So the offset is the
least difference between a record's ``train_step.prepare`` start and its
span's start, and the join is checked: every record, shifted, lies inside
its span, or no reader gives a number.
"""
from __future__ import annotations

PREPARE = "train_step.prepare"
COMPILE = "train_step.compile"
EXECUTE = "train_step.execute"
COLLECTION = "collection"      # a generation-2 pass of Python's collector
SLACK = 1e-6                   # seconds a shifted record may stick out


def say(text):
    print("chipbench: steps: " + text, flush=True)


def records(ctx):
    """``{"records": [...], "stalls": [...]}`` of the program, read once a
    run and kept in ``ctx``; None where the program keeps no records."""
    if "_step_records" not in ctx:
        from mxnet_tpu import telemetry

        ctx["_step_records"] = telemetry.snapshot().get("step_records")
    return ctx["_step_records"]


def dispatch_spans(ctx):
    """``[start, end]`` of the benchmark's ``dispatch_step`` spans of the
    traced window, in order."""
    return sorted([s, s + d] for name, s, d in ctx["trace"]["host"]
                  if name == "dispatch_step")


def windows(ctx, got):
    """The records of the run's windows as the ring still holds them,
    oldest first: the untraced window's (``ctx["dispatched"]`` calls) and
    the traced one's after it (``ctx["steps"]``).  Set-up's calls come
    before both and are in neither."""
    fused = got["records"]
    cut = len(fused) - ctx["steps"]
    first = max(0, cut - ctx["dispatched"])
    return [w for w in (fused[first:cut], fused[cut:]) if w]


def join(ctx):
    """The traced window's records on the trace's clock, read once a run:
    ``{"offset", "records", "spans"}``, or None with a printed reason where
    the program keeps no records or they do not fit their spans."""
    if "_step_join" not in ctx:
        ctx["_step_join"] = _join(ctx)
    return ctx["_step_join"]


def _join(ctx):
    got = records(ctx)
    if got is None:
        return None
    spans = dispatch_spans(ctx)
    mine = got["records"][-len(spans):] if spans else []
    whole = [r for r in mine if PREPARE in r["spans"]
             and EXECUTE in r["spans"]]
    if not spans or len(whole) != len(spans):
        say(f"no join: {len(spans)} dispatch_step spans in the trace, "
            f"{len(whole)} records with a prepare and an execute span among "
            f"the ring's last {len(mine)}")
        return None
    offset = min(r["spans"][PREPARE][0] - s[0]
                 for r, s in zip(mine, spans))
    for r, (a, b) in zip(mine, spans):
        start = r["spans"][PREPARE][0] - offset
        end = r["spans"][EXECUTE][1] - offset
        if start < a - SLACK or end > b + SLACK:
            say(f"no join: step {r['step']}'s call, shifted by {offset:.6f} "
                f"s, runs {start:.6f}-{end:.6f} and its dispatch_step span "
                f"{a:.6f}-{b:.6f}: the records are not this window's calls")
            return None
    late = max(r["spans"][PREPARE][0] - offset - s[0]
               for r, s in zip(mine, spans))
    say(f"join: {len(mine)} records (steps {mine[0]['step']}-"
        f"{mine[-1]['step']}) inside their dispatch_step spans; clocks "
        f"{offset:.6f} s apart; a call begins at most {late * 1e6:.0f} us "
        "after its span")
    return {"offset": offset, "records": mine, "spans": spans}


def program_spans(got, offset):
    """``{name: [[name, start, duration], ...]}`` on the trace's clock: the
    spans of every record the ring holds (its call's and its batch's on
    either thread) and the collections the records name."""
    out = {}
    for r in got["records"]:
        for name, stamps in r["spans"].items():
            out.setdefault(name, []).append(
                [name, stamps[0] - offset, stamps[1] - stamps[0]])
        for a, b in (r["since_previous_call"] or {}).get("gc2", ()):
            out.setdefault(COLLECTION, []).append(
                [COLLECTION, a - offset, b - a])
    return out


def intersect(a, b):
    """The part that two lists of merged, sorted intervals share.  One pass
    over both: a traced window holds a gap between most pairs of ops, some
    hundred thousand, and ``harness.trace.subtract`` walks its cover from
    the start for every interval."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def minus(a, b):
    """The part of ``a`` outside ``b`` (both merged and sorted), one pass:
    what ``a`` shares with the gaps between ``b``'s intervals."""
    inf = float("inf")
    edges = [-inf] + [t for interval in b for t in interval] + [inf]
    return intersect(a, [[edges[k], edges[k + 1]]
                         for k in range(0, len(edges), 2)])


def step_starts(ops, window, steps):
    """When each of the window's ``steps`` runs began on one chip: the
    starts of the instruction of the fused step that ran once a step and
    first of those in the window (which opens with nothing in flight), or
    None.  The step's instructions are those of the program's own table
    (``_scopes.step_table()``): a call also runs two small programs before
    it hands the step over (the key's), whose ops are on the line too."""
    from chipbench.harness.trace import clip, op_name
    from chipbench.layer_metrics import _scopes

    table = _scopes.step_table()
    starts = {}
    for name, start, _ in clip(ops, *window):
        starts.setdefault(op_name(name), []).append(start)
    once = [sorted(s) for name, s in starts.items()
            if len(s) == steps and (table is None or name in table)]
    return min(once) if once else None


def idle_split(ctx):
    """A chip's idle seconds of the traced window in three, read once a
    run: ``{"under": by chip, merged intervals under an open span of the
    program; "queued": those under none with a step dispatched whose run
    on that chip had not begun; "rest": what is left (the loop did not feed
    the device); "by_span": seconds by span name, every chip's; "starts":
    by chip, each step's start}``, or None."""
    if "_idle_split" not in ctx:
        ctx["_idle_split"] = _idle_split(ctx)
    return ctx["_idle_split"]


def _idle_split(ctx):
    from chipbench.harness import trace

    joined = join(ctx)
    if joined is None:
        return None
    window, offset = ctx["window"], joined["offset"]
    spans = program_spans(records(ctx), offset)
    open_ = trace.union([e for events in spans.values() for e in events])
    out = {"under": {}, "queued": {}, "rest": {}, "by_span": {}, "starts": {}}
    for chip, dev in ctx["trace"]["devices"].items():
        starts = step_starts(dev["ops"], window, len(joined["records"]))
        if starts is None:
            say(f"no split: no instruction ran {len(joined['records'])} "
                f"times on chip {chip} in the window")
            return None
        gaps = trace.subtract([list(window)],
                              trace.union(trace.clip(dev["ops"], *window)))
        waiting = trace.union([
            ["", r["spans"][EXECUTE][1] - offset,
             begun - (r["spans"][EXECUTE][1] - offset)]
            for r, begun in zip(joined["records"], starts)
            if begun > r["spans"][EXECUTE][1] - offset])
        out["under"][chip] = intersect(gaps, open_)
        bare = minus(gaps, open_)
        out["queued"][chip] = intersect(bare, waiting)
        out["rest"][chip] = minus(bare, waiting)
        out["starts"][chip] = starts
        for name, events in spans.items():
            out["by_span"][name] = out["by_span"].get(name, 0.0) \
                + trace.length(intersect(gaps, trace.union(events)))
    return out


def share(ctx, intervals_by_chip):
    """Percent of the traced window, averaged over chips."""
    from chipbench.harness.trace import length

    window_s = ctx["window"][1] - ctx["window"][0]
    return sum(length(i) for i in intervals_by_chip.values()) \
        / len(intervals_by_chip) / window_s * 100.0
