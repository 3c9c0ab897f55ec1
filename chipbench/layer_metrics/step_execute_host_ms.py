"""Fused step (``TrainStep.__call__``, phase ``train_step.execute``): host
milliseconds a call inside the call into the compiled executable alone.
The program's own histogram ``mxnet_step_phase_seconds``, exact sum over
count, from the process's start: the set-up steps, the untraced window and
the traced one together."""


def read(ctx):
    from chipbench.layer_metrics import _scopes

    return _scopes.mean_ms("mxnet_step_phase_seconds",
                           phase="train_step.execute")
