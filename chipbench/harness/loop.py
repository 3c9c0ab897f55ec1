"""The feed, the first steps and the measured window.

One ``PrefetchIterator`` cycles the pool of host batches for the whole
run, so the first steps go through the window's own call and feed.
Completions are read without draining the pipeline: step i's loss is
waited for only after step i + 2 is dispatched, and stamped then.
"""
from __future__ import annotations

import itertools
import math
import time
from collections import deque
from contextlib import contextmanager, nullcontext

import numpy as np

AHEAD = 2          # steps in flight when one is waited for
POOL = 8           # host batches made from the seed


def make_pool(build, cfg, cell, seed):
    """``POOL`` host batches from ``seed``, rows all different."""
    rng = np.random.default_rng(seed)
    return [build.make_batch(cfg, cell, rng) for _ in range(POOL)]


def open_feed(pool):
    """The repo's device prefetcher at its default depth over the pool,
    cycled for ever.  Close it when the run ends."""
    from mxnet_tpu.gluon.data.prefetcher import PrefetchIterator

    return PrefetchIterator(itertools.cycle(pool))


class Spans:
    """The benchmark's own host spans: seconds by name, and, while a trace
    is taken, ``jax.profiler.TraceAnnotation``s on the profiler's clock."""

    def __init__(self, annotate=False):
        self.seconds = {}
        self._annotate = annotate

    @contextmanager
    def __call__(self, name):
        if self._annotate:
            import jax

            note = jax.profiler.TraceAnnotation(name)
        else:
            note = nullcontext()
        t0 = time.perf_counter()
        with note:
            yield
        self.seconds[name] = self.seconds.get(name, 0.0) \
            + time.perf_counter() - t0


def first_steps(runner, feed, n):
    """Drive ``runner`` through its first ``n`` steps, one at a time.
    Returns what ``check.compare`` takes.  Part of set-up: the first call
    compiles."""
    spans = Spans()
    losses = []
    first = None
    for _ in range(n):
        losses.append(float(runner.step(next(feed), spans)))
        if first is None:
            first = runner.first_gradient()
    return {"losses": losses, "first_gradient": first,
            "change_sq": runner.change_sq_norms()}


def window(runner, feed, seconds, spans):
    """Dispatch steps for ``seconds``, then wait for those in flight: the
    window closes when the last step dispatched has completed.  Returns the
    stamps (seconds since the window opened) at which each step's loss was
    seen complete, the losses, and the steps dispatched and failed."""
    pending, stamps, losses = deque(), [], []
    attempted = failed = 0
    t_open = time.perf_counter()

    def wait_one():
        nonlocal failed
        with spans("wait_loss"):
            value = float(pending.popleft())
        stamps.append(time.perf_counter() - t_open)
        losses.append(value)
        failed += not math.isfinite(value)

    while time.perf_counter() - t_open < seconds:
        with spans("next_batch"):
            batch = next(feed)
        attempted += 1
        try:
            pending.append(runner.step(batch, spans))
        except Exception as e:  # a step that raises is a failed step
            failed += 1
            print(f"chipbench: step {attempted} raised {e!r}", flush=True)
            continue
        if len(pending) > AHEAD:
            wait_one()
    while pending:
        wait_one()
    return {"stamps": stamps, "losses": losses, "attempted": attempted,
            "failed": failed}


def percentile(values, q):
    """Nearest-rank percentile."""
    ordered = sorted(values)
    return ordered[min(len(ordered) - 1, math.ceil(q * len(ordered)) - 1)]


def step_times_ms(stamps):
    """Milliseconds between consecutive completions, every step's: the
    first is the time from the window's opening to the first completion."""
    return [(b - a) * 1e3 for a, b in zip([0.0] + stamps, stamps)]
