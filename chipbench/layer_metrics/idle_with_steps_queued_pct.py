"""Device (TPU v5e, under the fused step's dispatch): the share of the
traced window in which a chip ran no operation, no span of the program was
open on any thread, and at least one step was dispatched (its
``train_step.execute`` had returned) whose run on that chip had not begun,
averaged over chips.  A step's run on a chip begins at the instruction that
runs once a step and first (``_steps.step_starts``); the window opens with
nothing in flight.  Such a gap is not the host's: the program had handed
the work over and was doing nothing.  Prints the ten longest with the step
each preceded."""


def read(ctx):
    from chipbench.layer_metrics import _steps

    split = _steps.idle_split(ctx)
    if split is None:
        return None
    steps = [r["step"] for r in _steps.join(ctx)["records"]]
    gaps = []
    for chip, intervals in split["queued"].items():
        starts = split["starts"][chip]
        for a, b in intervals:
            # the first run to begin after the gap opened
            nxt = next((k for k, t in enumerate(starts) if t >= b - 1e-9),
                       len(starts) - 1)
            gaps.append((b - a, chip, steps[nxt]))
    _steps.say("longest idle gaps with a step queued and nothing open: "
               + (", ".join(f"{seconds * 1e3:.3f} ms on chip {chip} before "
                            f"step {step}" for seconds, chip, step in
                            sorted(gaps, reverse=True)[:10]) or "none"))
    return _steps.share(ctx, split["queued"])
