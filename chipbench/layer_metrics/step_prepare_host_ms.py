"""Fused step (``TrainStep.__call__``, phase ``train_step.prepare``): host
milliseconds a call before the executable is called: staging both inputs,
the key, the signature, three walks over the state trees.  The program's
own histogram ``mxnet_step_phase_seconds``, exact sum over count, from the
process's start: the set-up steps, the untraced window and the traced one
together (compiling has a phase of its own and is not in it)."""


def read(ctx):
    from chipbench.layer_metrics import _scopes

    return _scopes.mean_ms("mxnet_step_phase_seconds",
                           phase="train_step.prepare")
