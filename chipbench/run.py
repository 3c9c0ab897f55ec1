"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Builds the cell's step from ``--seed`` on the TPU, drives it through its
first steps (set-up: they compile), measures a window of ``--seconds``,
then follows the same first steps with the configuration's plain reference
and decides ``correct``.  The last line of standard output is one JSON
object.  There is no option that lifts the chip requirement.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import gc
import glob
import gzip
import importlib.metadata
import json
import math
import os
import resource
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

TRACE_SECONDS = 4.0      # a traced window is short: traces are large
UNTRACED_SECONDS = 10.0  # the window before it, in a --trace 1 run
OUT_DIR = os.path.join(ROOT, "chipbench_out")
HOST_SPANS = ("next_batch", "dispatch_step", "wait_loss")


def say(key, value):
    print(f"chipbench: {key}: {value}", flush=True)


def cache_entries(path):
    if not path or not os.path.isdir(path):
        return 0
    return sum(1 for f in os.listdir(path) if not f.endswith("-atime"))


def peak_bytes(device):
    """Peak bytes of a chip's memory: the arrays (``peak_bytes_in_use``)
    plus the scratch its loaded programs reserve (``peak_bytes_reserved``,
    where the activations of a fused step live; the first count leaves them
    out).  The reservation is made when the step's program is loaded and
    stands while it is (``bytes_reserved`` reads the same after the window),
    so the two peaks are held at once.  0 on a backend that keeps no such
    counts, the CPU of the tests."""
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) + stats.get(
        "peak_bytes_reserved", 0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import mxnet_tpu  # noqa: F401  places the compile cache; touches no backend
    import jax

    from chipbench.harness.cell import Cell, find_chips
    from chipbench.harness.peaks import peaks_of

    cell = Cell(args.workload)
    devices = find_chips(cell)
    if devices is None:
        return 3
    peaks = peaks_of(devices[0].device_kind)
    # the eager path's per-op executables compile in under a second each,
    # under JAX's threshold for the persistent cache: with the threshold
    # at 0 a second run of a cell finds every program in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    say("versions", " ".join(f"{p} {importlib.metadata.version(p)}"
                             for p in ("jax", "jaxlib", "libtpu")))
    result = run_cell(cell, devices, peaks, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


def run_cell(cell, devices, peaks, seed, seconds, traced):
    """Everything of a run but the look for a chip; returns the result
    line's object."""
    import jax

    from chipbench.harness import check, loop, trace as tracing

    kind = devices[0].device_kind
    cache_dir = jax.config.jax_compilation_cache_dir
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, secs, **kw: compiles.append(secs)
        if name == "/jax/core/compile/backend_compile_duration" else None)
    say("cell", f"{cell.name} seed {seed} seconds {seconds} trace "
        f"{int(traced)}")
    say("device", f"{devices[0].platform} / {kind} x {len(devices)}")
    say("compile cache", f"{cache_dir} ({cache_entries(cache_dir)} entries "
        "before)")

    spec, cfg = cell.spec, cell.cfg
    weights = cell.reference.init_params(cfg, seed)
    runner = cell.driver.Runner(spec, cfg, cell.build, weights)
    del weights
    pool = loop.make_pool(cell.build, cfg, spec, seed)
    feed = loop.open_feed(pool)
    spans = loop.Spans()
    trace_dir = os.path.join(OUT_DIR, f"{cell.name}.seed{seed}.trace")
    try:
        got = loop.first_steps(runner, feed, spec["check_steps"])
        say("first steps", f"losses {got['losses']}")
        compiled_in_setup = len(compiles)
        setup_s = time.perf_counter() - T_START
        # a traced run reads what the host's clock gives in a short untraced
        # window first, and traces a shorter one after it: traces are large
        usage0 = resource.getrusage(resource.RUSAGE_SELF)
        win = loop.window(runner, feed, min(seconds, UNTRACED_SECONDS)
                          if traced else seconds, spans)
        usage1 = resource.getrusage(resource.RUSAGE_SELF)
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            traced_win = loop.window(runner, feed, TRACE_SECONDS,
                                     loop.Spans(annotate=True))
            jax.profiler.stop_trace()
    finally:
        feed.close()
    peak = max(peak_bytes(d) for d in devices)
    compiled_in_window = len(compiles) - compiled_in_setup
    say("programs compiled or loaded", f"{compiled_in_setup} in set-up "
        f"({sum(compiles[:compiled_in_setup]):.1f} s), {compiled_in_window} "
        f"in the window; the driver counts {runner.compiles()}")
    say("compile cache entries after", cache_entries(cache_dir))

    # the window is all the steps dispatched within `seconds` and all the
    # time until the last of them completed: the rate is those samples over
    # that time, and the tail is the tail of every gap between completions
    stamps = win["stamps"]
    rate = len(stamps) * spec["batch"] / stamps[-1] / cell.chips \
        if stamps else 0.0
    times = loop.step_times_ms(stamps)
    say("window", f"{win['attempted']} steps dispatched, {len(stamps)} "
        f"completed in {stamps[-1] if stamps else 0.0:.4f} s, "
        f"{win['failed']} failed")
    if times:
        longest = max(range(len(times)), key=times.__getitem__)
        say("step times (ms)", f"median {loop.percentile(times, 0.5):.3f}, "
            f"p95 {loop.percentile(times, 0.95):.3f}, longest "
            f"{times[longest]:.1f} at step {longest + 1} "
            f"({stamps[longest]:.2f} s into the window)")
    cpu = [u.ru_utime + u.ru_stime for u in (usage0, usage1)]
    say("process over the window", f"cpu {cpu[1] - cpu[0]:.2f} s, switched "
        f"out {usage1.ru_nivcsw - usage0.ru_nivcsw} times")
    say("host spans (s)", {k: round(v, 4) for k, v in spans.seconds.items()})
    say("peak bytes (arrays + reserved scratch)",
        [peak_bytes(d) for d in devices])
    say("memory_stats of chip 0", devices[0].memory_stats())
    say("setup_s", round(setup_s, 3))

    # the check leg, after the window and after the peak was read: the
    # program's state is freed first, so the reference has the chip
    del runner
    gc.collect()
    t_check = time.perf_counter()
    ref = check.follow(cell.reference, cfg, "float32",
                       cell.reference.init_params(cfg, seed),
                       pool[:spec["check_steps"]], spec)
    say("reference losses", ref["losses"])
    stats = check.compare(got, ref)
    correct = check.verdict(stats, spec["limits"], lambda s: print(
        "chipbench: " + s, flush=True))
    for name, ok in (("every loss in the window finite",
                      win["failed"] == 0 and not (
                          traced and traced_win["failed"])),
                     ("nothing compiled inside the window",
                      compiled_in_window == 0),
                     ("steps completed in the window", len(stamps) > 0)):
        say(f"check {name}", "ok" if ok else "NOT CORRECT")
        correct &= ok
    say("check leg seconds", round(time.perf_counter() - t_check, 2))

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    both = [win, traced_win] if traced else [win]
    result = {"correct": bool(correct),
              "attempted": sum(w["attempted"] for w in both),
              "failed": sum(w["failed"] for w in both)}
    if not traced:
        values = {"samples_per_s_per_chip": rate,
                  "step_ms_p95": loop.percentile(times, 0.95) if times
                  else math.inf,
                  "peak_hbm_gib": peak / 2 ** 30, "setup_s": setup_s}
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.metrics("end_to_end")}
    else:
        xplane = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))[0]
        events = tracing.read_xplane(xplane, HOST_SPANS)
        shutil.rmtree(trace_dir)
        # the traced window runs from the first host span to the end of the
        # last, the drain included: every step dispatched completed inside
        steps = len(traced_win["stamps"])
        summary = tracing.summary(events, steps)
        ctx = {"cell": spec, "cfg": cfg, "chips": cell.chips, "peaks": peaks,
               "build": cell.build, "trace": events, "summary": summary,
               "window": tracing.window_of(events), "steps": steps,
               "samples_per_s_per_chip": rate, "spans": spans.seconds,
               "dispatched": win["attempted"]}
        result["metrics"] = {}
        for m in cell.metrics("per_layer"):
            value = cell.read_layer_metric(m["name"], ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = summary["breakdown"]
        os.makedirs(OUT_DIR, exist_ok=True)
        stem = os.path.join(OUT_DIR, f"{cell.name}.seed{seed}")
        with open(stem + ".trace_summary.json", "w") as f:
            json.dump({**summary, "metrics": result["metrics"]}, f, indent=1)
        with gzip.open(stem + ".trace_events.json.gz", "wt") as f:
            json.dump(events, f)
        say("trace summary", stem + ".trace_summary.json")
    result["device"] = device
    return result


if __name__ == "__main__":
    sys.exit(main())
