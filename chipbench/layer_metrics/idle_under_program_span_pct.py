"""Fused step (``TrainStep.__call__`` and the staging that feeds it): the
share of the traced window in which a chip ran no operation while a span of
the program was open on any thread (``train_step.prepare`` / ``.compile`` /
``.execute``, ``prefetch.stage`` on the producer's thread,
``prefetch.wait``, a generation-2 collection), averaged over chips.  The
spans are the program's own step records
(``telemetry.snapshot()["step_records"]``), put on the trace's clock by
``_steps.join``.  With ``idle_with_steps_queued_pct`` and what is left (the
loop did not feed the device) it sums to ``device_idle_pct``.  Prints the
seconds by span."""


def read(ctx):
    from chipbench.layer_metrics import _steps

    split = _steps.idle_split(ctx)
    if split is None:
        return None
    chips = len(split["under"])
    _steps.say("idle under a span of the program, seconds a chip (a gap "
               "under two counts in both): " + ", ".join(
                   f"{name} {seconds / chips:.6f}" for name, seconds in
                   sorted(split["by_span"].items(), key=lambda kv: -kv[1])))
    _steps.say("idle with nothing queued and nothing open: "
               f"{_steps.share(ctx, split['rest']):.4f}% of the window")
    return _steps.share(ctx, split["under"])
