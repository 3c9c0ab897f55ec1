"""Fused step (``TrainStep.__call__``): over the calls of the run's windows
that the program's ring still holds (256 records: the traced window and the
last of the untraced one; set-up's calls and a call that compiled are left
out), the longest interval between the instants at which consecutive steps
were first seen complete, over the median one.  The program stamps a step
``seen_complete`` when a later call's look (``is_ready()``, no wait) first
finds its loss ready: an upper bound by one call's spacing, which in this
loop is the wait for that very step.  A sound run reads just above 1; a run
that stalls reads the stall over the step.  Prints the longest interval's
step with what the program and the process did meanwhile."""

USAGE = ("nivcsw", "nvcsw", "majflt", "inblock", "oublock", "cpu_s",
         "compiles", "cache_misses", "cache_retrieval_s", "backend_compile_s")


def intervals_of(window):
    """``[(seconds, earlier record, later record), ...]`` between the
    consecutive steps of one window that a call of the same window saw
    complete, two found by one look counted as one."""
    from chipbench.layer_metrics import _steps

    last_call = window[-1]["opened"]
    seen = [r for r in window if _steps.COMPILE not in r["spans"]
            and r["seen_complete"] is not None
            and r["seen_complete"] <= last_call]
    return [(b["seen_complete"] - a["seen_complete"], a, b)
            for a, b in zip(seen, seen[1:])
            if b["seen_complete"] > a["seen_complete"]]


def read(ctx):
    from chipbench.layer_metrics import _steps

    got = _steps.records(ctx)
    if got is None:
        return None
    found = [i for w in _steps.windows(ctx, got) for i in intervals_of(w)]
    if len(found) < 2:
        _steps.say(f"{len(found)} intervals between steps seen complete: "
                   "no ratio")
        return None
    ordered = sorted(seconds for seconds, _, _ in found)
    median = ordered[len(ordered) // 2]
    longest, before, step = max(found, key=lambda f: f[0])
    a, b = before["seen_complete"], step["seen_complete"]
    calls = [r for r in got["records"] if a < r["opened"] <= b]
    since = [r["since_previous_call"] for r in calls
             if r["since_previous_call"]]
    open_ = {}
    for r in got["records"]:
        for name, stamps in r["spans"].items():
            over = min(stamps[1], b) - max(stamps[0], a)
            if over > 0:
                thread = stamps[2] if len(stamps) > 2 else r["thread"]
                key = f"{name} (thread {thread % 10000})"
                open_[key] = open_.get(key, 0.0) + over
    _steps.say(f"{len(found)} intervals, median {median * 1e3:.3f} ms; the "
               f"longest {longest * 1e3:.3f} ms ended at step "
               f"{step['step']} (batch {step['batch']}, {step['in_flight']} "
               f"in flight at its dispatch), seen by "
               f"{len(calls)} call(s) of steps "
               f"{[r['step'] for r in calls]}")
    _steps.say("meanwhile the program's spans were open for (s): "
               + (", ".join(f"{k} {v:.6f}" for k, v in sorted(
                   open_.items(), key=lambda kv: -kv[1])) or "none"))
    _steps.say("meanwhile the process: " + ", ".join(
        f"{name} {sum(s[name] for s in since):.6g}" for name in USAGE)
        + f", collector {sum(d - c for s in since for c, d in s['gc2']):.6f}"
        " s")
    _steps.say(f"stalls the program kept: {len(got['stalls'])}" + "".join(
        f"; step {s['step']}: {s['interval_s']:.3f} s over a median of "
        f"{s['median_s'] * 1e3:.1f} ms" for s in got["stalls"]))
    return longest / median
